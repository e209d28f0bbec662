"""asv TimeArithmetic, either axis: ``df.isin([0, 2])``."""

ROW_LOCAL = True


def run(pd, df):
    return df.isin([0, 2])


def least_bytes(config):
    """Every column read once; one byte a value written."""
    return 8 * config["columns"] * config["rows"] + config["columns"] * config["rows"]
