"""Fault: a small answer altered where it is produced (one off in every sum)."""


def run(pd, df):
    answer = df.sum()
    return answer if pd.__name__ == "pandas" else answer + 1


def least_bytes(config):
    return 8 * config["columns"] * config["rows"]
