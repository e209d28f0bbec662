"""What the program recorded of each request of the first pass, read back.

With ``--trace 1`` the harness opens ``query_stats("benchmark")`` around the
first pass's requests as well as the window's, so the ring that
``program_spans`` reads holds the first pass's records, one a question in the
cell's order, just before the window's.  A record also says how
its programs were made: ``trace_s`` and ``lower_s`` (jax's outermost trace and
lowering seconds) and ``programs_made`` (by program: ``trace_s``, ``lower_s``,
``compile_s``, ``loaded``, and ``temp_bytes`` where the call built the
executable).  On a program without those fields every reader finds nothing
and the result line leaves its metric out.
"""

import program_spans

FIELDS = ("trace_s", "lower_s", "programs_made")


def records(obs):
    """The first pass's records, in order, or ``None``: when the program keeps
    none, when the ring holds fewer than the cell has questions before the
    window's, when one lacks the fields above, or when their walls summed lie
    further than ``program_spans.SLACK_S`` a request from the harness's."""
    try:
        from modin_tpu.observability import recent_queries
    except ImportError:
        return None
    count = len(obs["least_bytes"])
    ring = recent_queries(program_spans.LABEL)
    end = len(ring) - obs["completed"]
    if count == 0 or end < count:
        return None
    first = ring[end - count:end]
    if any(field not in r for r in first for field in FIELDS):
        return None
    if abs(sum(r["wall_s"] for r in first) - obs["first_pass"]["wall_s"]) > program_spans.SLACK_S * count:
        return None
    return first
