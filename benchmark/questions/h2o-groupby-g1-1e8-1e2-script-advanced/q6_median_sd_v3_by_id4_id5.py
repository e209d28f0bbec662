"""H2O groupby question 6, "median v3 sd v3 by id4 id5" (K * K groups), as the
pandas script writes it."""


def run(pd, x):
    return x.groupby(['id4', 'id5'], as_index=False, sort=False, observed=True, dropna=False).agg({'v3': ['median', 'std']})


def least_bytes(config):
    """id4, id5 and v3 read once; K * K rows of the two keys, the median and
    the standard deviation written."""
    groups = config["groups_k"] ** 2
    return config["rows"] * (8 + 8 + 8) + groups * (8 + 8 + 8 + 8)
