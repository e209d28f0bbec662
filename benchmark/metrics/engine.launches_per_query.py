"""Device programs launched per request, counted where each is launched (the
program's ``named_jit``), so a groupby's kernels count though they never pass
the engine seam's ``deploy``."""

import program_spans


def read(obs):
    return program_spans.mean_of(obs, "launches")
