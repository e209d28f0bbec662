"""Refused metrics and pandas-default warnings over the whole run."""


def read(obs):
    return obs["fallbacks"]
