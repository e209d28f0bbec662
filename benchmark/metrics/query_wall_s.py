"""Window seconds over requests completed: all the time over all the work."""


def read(obs):
    return obs["window_s"] / obs["completed"] if obs["completed"] else None
