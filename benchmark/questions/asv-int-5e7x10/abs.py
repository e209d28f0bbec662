"""asv TimeArithmetic, either axis: ``df.abs()``."""

ROW_LOCAL = True


def run(pd, df):
    return df.abs()


def least_bytes(config):
    """Every column read once and written once."""
    return 2 * 8 * config["columns"] * config["rows"]
