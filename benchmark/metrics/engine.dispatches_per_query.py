"""Engine-seam dispatches (``query_stats``) per request of the window.  A
device groupby's kernels do not pass that counter, so there is nothing to read
in a groupby cell."""


def read(obs):
    counted = [r["dispatches"] for r in obs["requests"] if r.get("dispatches") is not None]
    return sum(counted) / len(counted) if counted else None
