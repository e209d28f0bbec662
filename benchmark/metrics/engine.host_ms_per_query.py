"""Host self time a request spends planning, linearising and at the engine
seam (spans tagged ``PLAN`` and ``JAX-ENGINE``), without the time it is blocked
waiting for the device (the program's ``wait_s``)."""

import program_spans


def read(obs):
    return program_spans.host_ms_per_query(obs, ("PLAN", "JAX-ENGINE"))
