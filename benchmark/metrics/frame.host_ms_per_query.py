"""Host self time a request spends in the frame (spans tagged ``CORE-FRAME``)."""

import program_spans


def read(obs):
    return program_spans.host_ms_per_query(obs, ("CORE-FRAME",))
