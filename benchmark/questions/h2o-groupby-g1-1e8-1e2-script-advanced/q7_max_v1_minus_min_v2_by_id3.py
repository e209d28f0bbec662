"""H2O groupby question 7, "max v1 - min v2 by id3" (N/K groups), as the pandas
script writes it."""


def run(pd, x):
    return x.groupby('id3', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'max', 'v2': 'min'}).assign(range_v1_v2=lambda x: x['v1'] - x['v2'])[['id3', 'range_v1_v2']]


def least_bytes(config):
    """id3's codes (int32), v1 and v2 read once; N/K rows of key code and
    range written."""
    groups = config["rows"] // config["groups_k"]
    return config["rows"] * (4 + 8 + 8) + groups * (4 + 8)
