"""H2O groupby question 3, "sum v1 mean v3 by id3" (N/K groups), as the pandas
script writes it."""


def run(pd, x):
    return x.groupby('id3', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum', 'v3': 'mean'})


def least_bytes(config):
    """id3's codes (int32), v1 and v3 read once; N/K rows of key, sum and mean
    written."""
    groups = config["rows"] // config["groups_k"]
    return config["rows"] * (4 + 8 + 8) + groups * (4 + 8 + 8)
