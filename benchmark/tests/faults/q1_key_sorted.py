"""Fault: q1's groups in key order, where the script asks for the order of
first appearance (``sort=False``)."""


def run(pd, x):
    in_order = pd.__name__ == "pandas"
    return x.groupby('id1', as_index=False, sort=not in_order, observed=True, dropna=False).agg({'v1': 'sum'})


def least_bytes(config):
    return config["rows"] * (1 + 8) + config["groups_k"] * (1 + 8)
