"""asv TimeArithmetic, axis 1: ``df.count(axis=1)``."""

ROW_LOCAL = True


def run(pd, df):
    return df.count(axis=1)


def least_bytes(config):
    """An int64 column holds no NA, so a row's count is the number of columns:
    nothing has to be read, and one value a row is written."""
    return 8 * config["rows"]
