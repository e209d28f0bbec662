"""Bytes uploaded host->device per request (frame columns, remap and lookup
tables, a scalar result's values)."""

import program_spans


def read(obs):
    return program_spans.mean_of(obs, "h2d_bytes")
