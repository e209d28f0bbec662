"""H2O groupby question 1, "sum v1 by id1" (K groups), as the pandas script
writes it."""


def run(pd, x):
    return x.groupby('id1', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum'})


def least_bytes(config):
    """id1's codes (int8) and v1 read once; K rows of key and sum written."""
    return config["rows"] * (1 + 8) + config["groups_k"] * (1 + 8)
