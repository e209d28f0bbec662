"""Fault: an answer altered where it is produced, by one part in a million."""


def run(pd, x):
    answer = x.groupby("id4", observed=True).agg({"v1": "mean", "v2": "mean", "v3": "mean"})
    return answer if pd.__name__ == "pandas" else answer * (1 + 1e-6)


def least_bytes(config):
    return 4 * 8 * config["rows"]
