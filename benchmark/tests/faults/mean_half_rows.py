"""Fault: half of the rows left out, the mean taken over the rest."""


def run(pd, df):
    return df.mean() if pd.__name__ == "pandas" else df.head(len(df) // 2).mean()


def least_bytes(config):
    return 8 * config["columns"] * config["rows"]
