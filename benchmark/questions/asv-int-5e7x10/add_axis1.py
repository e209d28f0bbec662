"""asv TimeArithmetic, axis 1: ``df.add(2, axis=1)``."""

ROW_LOCAL = True


def run(pd, df):
    return df.add(2, axis=1)


def least_bytes(config):
    """Every column read once and written once."""
    return 2 * 8 * config["columns"] * config["rows"]
