"""Backend compiles the ledger counted inside the window (0 is steady)."""


def read(obs):
    return obs["compiles_in_window"]
