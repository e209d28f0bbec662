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
- **how each program was made** — while a ``query_stats`` scope is open on
  the thread, the listeners also take jax's trace, lowering and
  persistent-cache-retrieval events and bill each program's seconds into the
  scope's ``programs_made`` (``meters.note_made``).  jax opens a trace event
  for every jitted function it traces, and a program's trace encloses the
  traces of the functions its body calls; only the outermost trace or
  lowering open on the thread is billed, less the compiles that fired inside
  it, so ``trace_s + lower_s + compile_s`` never counts a second twice.

The listeners are process-global and effectively free when idle (they run
only when jax traces or XLA compiles); they are installed at engine startup
(``initialize_jax``), when ``MODIN_TPU_TRACE`` turns on, and by
``profile()``.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, List, Optional

from modin_tpu.concurrency import named_lock
from modin_tpu.observability import meters as _meters
from modin_tpu.observability import spans as _spans

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: fires inside the compile event, and only when the persistent cache held
#: the executable
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: ``programs_made`` field each made-program event is billed to
_PHASES = {TRACE_EVENT: "trace_s", LOWER_EVENT: "lower_s", COMPILE_EVENT: "compile_s"}

_tls = threading.local()


@contextlib.contextmanager
def relowering() -> Iterator[None]:
    """Bill no trace or lowering that fires on this thread inside the block.

    graftcost's static capture and the memory read of a made program lower
    a program again after its call: jax answers from its caches, but still
    fires a (short) trace event, and the call already billed the real one.
    """
    _tls.relowering = getattr(_tls, "relowering", 0) + 1
    try:
        yield
    finally:
        _tls.relowering -= 1


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
        if _meters.ACCOUNTING_ON:
            _phase_end(event, duration, kwargs.get("fun_name"))
        return
    try:
        _tls.compiles = getattr(_tls, "compiles", 0) + 1
        _LEDGER.record_compile(_spans.attribution_signature(), duration)
        if _spans.TRACE_ON:
            sp = _spans.current_span()
            if sp is not None:
                sp.attrs["compile_s"] = sp.attrs.get("compile_s", 0.0) + duration
        if _meters.ACCOUNTING_ON:
            _meters.note_compile(duration)
            _phase_end(event, duration, kwargs.get("fun_name"))
    except Exception:
        # a broken listener must never break the compile it observes
        pass


def _on_event_start(event: str, value: float, **kwargs: object) -> None:
    """jax's ``record_scalar`` at the start of a trace, lowering or compile
    (its start time): opens the event's frame on this thread's stack."""
    if not _meters.ACCOUNTING_ON:
        return
    try:
        if event not in _PHASES or (event != COMPILE_EVENT and _relowering()):
            return
        stack = _phase_stack()
        if stack is not None:
            # [event, fun_name, compile seconds inside it, loaded from the cache]
            stack.append([event, kwargs.get("fun_name"), 0.0, False])
    except Exception:
        pass


def _relowering() -> bool:
    return bool(getattr(_tls, "relowering", 0))


def _phase_stack() -> Optional[List[list]]:
    """This thread's open trace / lowering / compile frames, or None where no
    ``query_stats`` scope is open on it.  The stack belongs to the outermost
    open scope: one that closed with a frame still open (a scope closed
    inside a trace) leaves nothing behind for the next."""
    scopes = _spans.thread_requests()
    if not scopes:
        return None
    owner = scopes[0].request_id
    held = getattr(_tls, "phases", None)
    if held is None or held[0] != owner:
        held = _tls.phases = (owner, [])
    return held[1]


def _program_of(fun_name: object) -> str:
    """The ``named_jit`` name of a program from jax's ``fun_name``: ``name``
    for a trace, ``jit(name)`` (``pmap(name)``) for its lowering and compile."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1 : -1]
    return name


def _phase_end(event: str, duration: float, fun_name: object) -> None:
    """A trace, lowering or compile event ended (or a cache retrieval fired
    inside a compile): bill it to the open scopes as its program's, if it is
    the outermost open on this thread; a compile always, and its seconds are
    taken off the trace or lowering it fired in."""
    try:
        if event == CACHE_LOAD_EVENT:
            stack = _phase_stack()
            if stack and stack[-1][0] == COMPILE_EVENT:
                stack[-1][3] = True
            return
        if event not in _PHASES or (event != COMPILE_EVENT and _relowering()):
            return
        stack = _phase_stack()
        if stack is None:
            return
        frame = None
        if stack and stack[-1][0] == event and stack[-1][1] == fun_name:
            frame = stack.pop()
        if event == COMPILE_EVENT:
            if stack:
                stack[-1][2] += duration
            loaded = frame is not None and frame[3]
            _meters.note_made(_program_of(fun_name), "compile_s", duration, loaded)
            return
        nested = frame[2] if frame is not None else 0.0
        if stack:
            # part of the trace or lowering that encloses it: billed there
            stack[-1][2] += nested
            return
        _meters.note_made(_program_of(fun_name), _PHASES[event], max(duration - nested, 0.0))
    except Exception:
        pass


_installed = False
_install_lock = named_lock("compile_ledger.install")


def ensure_listener() -> bool:
    """Idempotently register the jax.monitoring listeners.

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
        monitoring.register_scalar_listener(_on_event_start)
        _installed = True
        return True
