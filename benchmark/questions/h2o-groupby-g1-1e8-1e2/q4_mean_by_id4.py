"""H2O groupby question 4, "mean v1:v3 by id4" (100 groups)."""


def run(pd, x):
    return x.groupby("id4", observed=True).agg({"v1": "mean", "v2": "mean", "v3": "mean"})


def least_bytes(config):
    """id4, v1, v2, v3 read once; K rows of key and three float64 means written."""
    return 4 * 8 * config["rows"] + 4 * 8 * config["groups_k"]
