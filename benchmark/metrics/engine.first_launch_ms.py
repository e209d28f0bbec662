"""Median time from a request's start to its first device-program launch: the
stretch in which a closed-loop client's device is surely idle.  Requests that
launch nothing are left out."""

import statistics

import program_spans


def read(obs):
    records = program_spans.requests(obs)
    launched = [r["first_launch_s"] for r in records or () if r["first_launch_s"] is not None]
    return 1e3 * statistics.median(launched) if launched else None
