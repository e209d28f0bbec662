"""What the program recorded of each request of the window, read back.

With ``--trace 1`` the harness opens ``query_stats("benchmark")`` around every
request (``program_hooks.count_dispatches``); from PR 26 on a live scope also
switches the program's spans on and, once closed, stays in a ring that
``modin_tpu.observability.recent_queries`` reads: per request the host's self
time by layer, the time blocked on the device, every device-program launch by
name, the blocking device->host fetches and the uploaded bytes.

This is the second file of the benchmark, with ``program_hooks.py``, that
reaches below ``modin_tpu.pandas``; the new ``metrics/*.py`` readers come
through here.  On a program without that ring (the parent of PR 26) every
reader finds nothing and the result line leaves its metric out.
"""

LABEL = "benchmark"  # what program_hooks.count_dispatches names its scopes
SLACK_S = 1e-3  # the harness's timer encloses the scope: by this much at most


def requests(obs):
    """The program's record of each request of the window, in order, or
    ``None``: when the program keeps none, when it did not keep exactly one
    for each request, or when one disagrees with the harness's own wall."""
    try:
        from modin_tpu.observability import recent_queries
    except ImportError:
        return None
    completed = obs["completed"]
    records = recent_queries(LABEL)[-completed:] if completed else []
    if len(records) != completed:
        return None
    for ours, theirs in zip(obs["requests"], records):
        if not 0.0 <= ours["wall_s"] - theirs["wall_s"] <= SLACK_S:
            return None
    return records


def host_ms_per_query(obs, layers):
    """Mean over the window's requests of the host's self time under
    ``layers`` (the program's layer tags), in milliseconds."""
    records = requests(obs)
    if not records:
        return None
    total = sum(r["host_self_s"].get(layer, 0.0) for r in records for layer in layers)
    return 1e3 * total / len(records)


def mean_of(obs, field):
    records = requests(obs)
    if not records:
        return None
    return sum(r[field] for r in records) / len(records)
