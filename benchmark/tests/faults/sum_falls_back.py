"""Fault: a question answered by in-process pandas (``apply`` falls back today);
the answer is right and the configuration's guarantee is broken."""


def run(pd, df):
    return df.sum() if pd.__name__ == "pandas" else df.apply(lambda column: column.sum())


def least_bytes(config):
    return 8 * config["columns"] * config["rows"]
