"""Fault: a large (sampled) answer altered where it is produced."""

ROW_LOCAL = True


def run(pd, df):
    return df.add(2) if pd.__name__ == "pandas" else df.add(3)


def least_bytes(config):
    return 2 * 8 * config["columns"] * config["rows"]
