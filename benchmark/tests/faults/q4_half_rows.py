"""Fault: half of the rows left out, the mean taken over the rest."""


def run(pd, x):
    if pd.__name__ != "pandas":
        x = x.head(len(x) // 2)
    return x.groupby("id4", observed=True).agg({"v1": "mean", "v2": "mean", "v3": "mean"})


def least_bytes(config):
    return 4 * 8 * config["rows"]
