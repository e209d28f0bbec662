"""Seconds the first pass spent in jax tracing and lowering the programs it
made, from the program's own records of its requests: ``trace_s`` (each
outermost trace, so a helper traced inside a program counts once, in it) plus
``lower_s``, neither holding a compile that fired inside it.  With
``engine.first_pass_compile_s`` it splits the host's part of
``first_query_s``.  A program without the fields reports nothing."""

import first_pass_records


def read(obs):
    first = first_pass_records.records(obs)
    if first is None:
        return None
    return sum(r["trace_s"] + r["lower_s"] for r in first)
