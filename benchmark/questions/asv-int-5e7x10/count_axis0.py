"""asv TimeArithmetic, axis 0: ``df.count(axis=0)``."""


def run(pd, df):
    return df.count(axis=0)


def least_bytes(config):
    """An int64 column holds no NA, so its count is its length: nothing has to
    be read, and one value a column is written."""
    return 8 * config["columns"]
