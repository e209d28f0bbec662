"""Seconds the compile ledger billed during the first pass (a cache load
counts as what it took)."""


def read(obs):
    return obs["first_pass"]["compile_s"]
