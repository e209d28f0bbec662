"""XLA compile observability: the compile-cache ledger.

Every fresh XLA compile is expensive (the 1e8-row sort takes about a
minute on a local v5e; most programs 1-3 s), and
today they are *invisible*: a shape or dtype drifting per call recompiles
the same logical op forever and nothing reports it.  jax publishes a
monitoring event (``/jax/core/compile/backend_compile_duration``) on every
backend compile and stays silent on executable-cache hits; this module
turns that into a per-op-signature ledger:

- **compiles / compile_s** — counted by a ``jax.monitoring`` duration
  listener, attributed to the innermost QUERY-COMPILER span open on the
  compiling thread (``spans.attribution_signature()``), so a compile is
  billed to ``TpuQueryCompiler.sum`` rather than to the generic engine
  ``deploy``.  The same listener adds ``compile_s`` to the innermost open
  span, which is how profiles separate compile from device time.
- **dispatches / cache_hits** — while tracing is on, the resilience
  engine-seam wrapper reports every ``deploy`` with whether any compile
  fired during the attempt; a dispatch with zero compiles is a cache hit
  for its signature.
- **recompile storms** — ``recompile_storms(min_compiles)`` names the
  signatures compiled suspiciously often; ``snapshot()`` feeds dashboards.

The listener is process-global and effectively free when idle (it runs only
when XLA actually compiles); it is installed at engine startup
(``initialize_jax``), when ``MODIN_TPU_TRACE`` turns on, and by
``profile()``.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, Optional

from modin_tpu.concurrency import named_lock
from modin_tpu.observability import spans as _spans

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_tls = threading.local()


@contextlib.contextmanager
def suppress_listener() -> Iterator[None]:
    """Hide compile events fired on this thread from the ledger.

    graftcost's ``Full`` capture mode AOT-compiles a program the engine
    already compiled (``memory_analysis()`` needs the executable); without
    suppression that duplicate backend compile would be billed as workload
    — doubling ``engine.compile`` counts and poisoning the cache-hit
    accounting the metrics gate checks.
    """
    _tls.suppress = getattr(_tls, "suppress", 0) + 1
    try:
        yield
    finally:
        _tls.suppress -= 1


class CompileLedger:
    """Thread-safe per-signature compile/dispatch accounting."""

    def __init__(self) -> None:
        self._lock = named_lock("compile_ledger.entries")
        self._entries: Dict[str, dict] = {}
        self.total_compiles = 0
        self.total_compile_s = 0.0

    def _entry(self, signature: str) -> dict:
        entry = self._entries.get(signature)
        if entry is None:
            entry = self._entries[signature] = {
                "compiles": 0,
                "compile_s": 0.0,
                "dispatches": 0,
                "cache_hits": 0,
            }
        return entry

    def record_cost(self, signature: str, cost: dict) -> None:
        """Attach graftcost static-cost fields (flops / bytes accessed /
        transcendentals, memory sizes under Full capture) to a signature's
        entry.  Unknown fields stay ``"unknown"`` — never absent-by-crash."""
        from modin_tpu.observability.costs import _merge_known

        with self._lock:
            entry = self._entry(signature)
            _merge_known(entry.setdefault("cost", {}), cost)

    def record_compile(self, signature: str, duration_s: float) -> None:
        with self._lock:
            entry = self._entry(signature)
            entry["compiles"] += 1
            entry["compile_s"] += duration_s
            self.total_compiles += 1
            self.total_compile_s += duration_s

    def record_dispatch(self, signature: str, compiled: bool) -> None:
        with self._lock:
            entry = self._entry(signature)
            entry["dispatches"] += 1
            if not compiled:
                entry["cache_hits"] += 1

    def totals(self) -> tuple:
        """``(total_compiles, total_compile_s)`` without building the full
        per-signature snapshot — the graftwatch sampler reads this every
        tick, so it must stay O(1) under the lock."""
        with self._lock:
            return (self.total_compiles, self.total_compile_s)

    def snapshot(self) -> dict:
        """Deep copy: {signature: {compiles, compile_s, dispatches,
        cache_hits}} plus process totals."""
        with self._lock:
            return {
                "total_compiles": self.total_compiles,
                "total_compile_s": self.total_compile_s,
                "signatures": {sig: dict(e) for sig, e in self._entries.items()},
            }

    def recompile_storms(self, min_compiles: int = 3) -> Dict[str, int]:
        """Signatures backend-compiled at least ``min_compiles`` times —
        shape/dtype churn defeating the executable cache."""
        with self._lock:
            return {
                sig: e["compiles"]
                for sig, e in self._entries.items()
                if e["compiles"] >= min_compiles
            }

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self.total_compiles = 0
            self.total_compile_s = 0.0


_LEDGER = CompileLedger()


def get_compile_ledger() -> CompileLedger:
    return _LEDGER


def compiles_on_this_thread() -> int:
    """Monotonic per-thread compile counter (hit detection takes deltas)."""
    return getattr(_tls, "compiles", 0)


def _on_event_duration(event: str, duration: float, **kwargs: object) -> None:
    if event != COMPILE_EVENT:
        return
    if getattr(_tls, "suppress", 0):
        return  # graftcost's own AOT capture compile: not workload
    try:
        _tls.compiles = getattr(_tls, "compiles", 0) + 1
        _LEDGER.record_compile(_spans.attribution_signature(), duration)
        if _spans.TRACE_ON:
            sp = _spans.current_span()
            if sp is not None:
                sp.attrs["compile_s"] = sp.attrs.get("compile_s", 0.0) + duration
        from modin_tpu.observability import meters as _meters

        if _meters.ACCOUNTING_ON:
            _meters.note_compile(duration)
    except Exception:
        # a broken listener must never break the compile it observes
        pass


_installed = False
_install_lock = named_lock("compile_ledger.install")


def ensure_listener() -> bool:
    """Idempotently register the jax.monitoring compile listener.

    Returns True when the listener is (now) installed; False when jax is
    unavailable (the ledger then simply stays empty).
    """
    global _installed
    if _installed:
        return True
    with _install_lock:
        if _installed:
            return True
        try:
            from jax._src import monitoring
        except Exception:
            return False
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        _installed = True
        return True
