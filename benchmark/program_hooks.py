"""The one file of the benchmark that reaches below ``modin_tpu.pandas``.

Everything the harness takes from the program besides its public pandas API
goes through here, so that a later PR that moves an internal has one place to
mend (``README.md`` lists the internals this leans on).
"""

import contextlib
import os
import re
import warnings

# any of these firing means a device path retried, degraded, recovered or
# fell back to pandas (copied from chip_smoke.py; shuffle.slack_retry is the
# range shuffle's ordinary adaptation and is not among them)
_REFUSED_METRIC = re.compile(
    r"^modin_tpu\.(resilience\.(fallback|breaker|engine|watchdog)\."
    r"|resilience\.shuffle\.skew_fallback|recovery\.|serving\.degraded)"
)
_REFUSED_WARNING = "defaulting to in-process pandas"


class NotOnDevice(Exception):
    """An answer (or an ingested numeric column) lives on the host."""


def options_set():
    """The ``MODIN_TPU_*`` variables set in the environment: the cells run the
    program as it comes, so the harness refuses any."""
    return sorted(k for k in os.environ if k.startswith("MODIN_TPU_"))


def load(repo_root):
    """Import the program from this checkout and return its pandas API."""
    import modin_tpu
    import modin_tpu.pandas as pd

    came_from = os.path.dirname(os.path.abspath(modin_tpu.__file__))
    if came_from != os.path.join(repo_root, "modin_tpu"):
        raise RuntimeError(f"modin_tpu came from {came_from}, not from {repo_root}")
    return pd


class FallbackTrap:
    """Counts refused metrics and pandas-default warnings over the run."""

    def __init__(self):
        self.count = 0
        self.seen = []

    def _note(self, what):
        self.count += 1
        if len(self.seen) < 8:
            self.seen.append(what)

    def _on_metric(self, name, value):
        if _REFUSED_METRIC.match(name):
            self._note(f"metric {name}")

    def _on_warning(self, message, category, filename, lineno, file=None, line=None):
        text = str(message)
        if _REFUSED_WARNING in text:
            self._note("warning " + text.splitlines()[0][:200])

    def install(self):
        from modin_tpu.logging import add_metric_handler

        add_metric_handler(self._on_metric)
        warnings.simplefilter("always")
        warnings.showwarning = self._on_warning


def drop_derived_answers():
    """Forget every answer the program derived from resident buffers, so the
    next question is a first run: graftview's registry and the factorize memo."""
    from modin_tpu.ops.groupby import clear_factorize_cache
    from modin_tpu.views import registry

    registry.reset()
    clear_factorize_cache()


def compile_totals():
    """``(compiles, compile seconds)`` of the process so far."""
    from modin_tpu.observability.compile_ledger import get_compile_ledger

    return get_compile_ledger().totals()


@contextlib.contextmanager
def count_dispatches():
    """Yields an object whose ``dispatches`` holds the engine-seam dispatches
    of the block once it closes."""
    from modin_tpu.observability import query_stats

    with query_stats("benchmark") as stats:
        yield stats


def ingest(pd, host_columns):
    """The frame a user builds from host arrays, resident before it returns."""
    frame = pd.DataFrame(host_columns)
    frame._query_compiler.execute()
    return frame


def execute(answer):
    """Materialise a lazy answer (returns before the device is done)."""
    answer._query_compiler.execute()


def _columns(obj):
    qc = obj._query_compiler
    if type(qc).__name__ != "TpuQueryCompiler":
        raise NotOnDevice(f"lives in {type(qc).__name__}, not TpuQueryCompiler")
    cols = getattr(qc._modin_frame, "_columns", None)
    if not cols:
        raise NotOnDevice("has no device frame columns")
    return cols


def device_buffers(obj, host_columns=()):
    """``[(label, jax.Array, logical length)]`` of an answer's or a frame's
    columns.  Raises ``NotOnDevice`` for a column on the host that is not
    named in ``host_columns`` (those are left out of the list)."""
    labels = [obj.name] if obj.ndim == 1 else list(obj.columns)  # as a user reads them
    out = []
    for label, col in zip(labels, _columns(obj)):
        if not col.is_device:
            if label in host_columns:
                continue
            raise NotOnDevice(f"column {label!r} is not on the device")
        out.append((label, col.data, col.length))
    return out


def to_host(answer):
    """The answer as the pandas object a user would read."""
    return answer.modin.to_pandas()


def switch_on_float32_storage():
    """The program's own lower-precision path (``Float64Policy=Downcast``:
    float64 columns stored and computed as float32 on the device).  Only the
    control of ``tests/read_limits.py`` switches it on; no cell does."""
    from modin_tpu.config import Float64Policy

    Float64Policy.put("Downcast")
