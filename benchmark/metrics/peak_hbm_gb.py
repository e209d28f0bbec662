"""``peak_bytes_in_use`` of the fullest device once the window has closed."""


def read(obs):
    return obs["peak_bytes"] / 1e9 if obs["peak_bytes"] else None
