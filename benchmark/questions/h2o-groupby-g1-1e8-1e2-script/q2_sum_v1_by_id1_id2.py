"""H2O groupby question 2, "sum v1 by id1:id2" (K x K groups), as the pandas
script writes it."""


def run(pd, x):
    return x.groupby(['id1', 'id2'], as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum'})


def least_bytes(config):
    """id1's and id2's codes (int8 each) and v1 read once; K x K rows of two
    keys and a sum written."""
    return config["rows"] * (1 + 1 + 8) + config["groups_k"] ** 2 * (1 + 1 + 8)
