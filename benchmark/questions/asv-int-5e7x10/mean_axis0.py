"""asv TimeArithmetic, axis 0: ``df.mean(axis=0)``."""


def run(pd, df):
    return df.mean(axis=0)


def least_bytes(config):
    """Every column read once; one value a column written."""
    return 8 * config["columns"] * config["rows"] + 8 * config["columns"]
