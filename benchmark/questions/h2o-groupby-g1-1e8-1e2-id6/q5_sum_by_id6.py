"""H2O groupby question 5, "sum v1:v3 by id6" (N/K groups)."""


def run(pd, x):
    return x.groupby("id6", observed=True).agg({"v1": "sum", "v2": "sum", "v3": "sum"})


def least_bytes(config):
    """id6, v1, v2, v3 read once; N/K rows of key and three sums written."""
    return 4 * 8 * config["rows"] + 4 * 8 * (config["rows"] // config["groups_k"])
