"""asv TimeArithmetic, axis 1: ``df.mean(axis=1)``."""

ROW_LOCAL = True


def run(pd, df):
    return df.mean(axis=1)


def least_bytes(config):
    """Every column read once; one value a row written."""
    return 8 * config["columns"] * config["rows"] + 8 * config["rows"]
