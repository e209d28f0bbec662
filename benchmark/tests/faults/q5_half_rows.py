"""Fault: half of the rows left out of the sums."""


def run(pd, x):
    if pd.__name__ != "pandas":
        x = x.head(len(x) // 2)
    return x.groupby("id6", observed=True).agg({"v1": "sum", "v2": "sum", "v3": "sum"})


def least_bytes(config):
    return 4 * 8 * config["rows"]
