"""Fault-tolerant device execution: classification, retry, and circuit breakers.

The engine contract (``JaxWrapper.deploy/put/materialize/wait``,
modin_tpu/parallel/engine.py) is the single seam between the framework and
the accelerator runtime.  Everything that can go wrong on the other side of
that seam — device OOM, a wedged or lost chip, a transient XLA runtime error —
used to surface as a raw ``XlaRuntimeError`` that either crashed the query or
was swallowed by a broad ``except Exception`` and misread as a semantic
"not supported on device" fallback.  This module makes the failure mode a
first-class, observable runtime decision (the design argued for by
"Towards Scalable Dataframe Systems", arXiv:2001.00888, and the adaptive
per-operator routing of Xorbits, arXiv:2401.00865):

1. **Failure classification** — ``classify_device_error`` maps low-level runtime
   errors onto ``DeviceOOM`` (RESOURCE_EXHAUSTED), ``DeviceLost`` (device or
   runtime failure, including watchdog expiry), and ``TransientDeviceError``
   (everything retryable).  These are *infrastructure* failures, disjoint
   from the semantic fallback signals (``ShuffleSkewError``,
   ``_TooManyGroups``, ``ModinAssumptionError``) which mean "the optimized
   path does not apply", not "the device is unhealthy".

2. **Bounded retry with exponential backoff** — ``engine_call`` wraps every
   engine-seam invocation; transient errors are retried up to
   ``ResilienceRetries`` times with ``ResilienceBackoffS`` exponential
   backoff.  ``materialize``/``wait`` additionally run under a wall-clock
   watchdog (``ResilienceWatchdogS``): a fetch that outlives it raises
   ``WatchdogTimeout`` (a ``DeviceLost``) instead of hanging the query
   forever on a dead device.

3. **Per-device-path circuit breaker** — every ``_try_*`` family in the
   TPU query compiler is wrapped by ``device_path(family)``.  Each family
   owns a named breaker that counts device failures and latency-budget
   violations; after ``ResilienceBreakerThreshold`` consecutive strikes the
   breaker trips OPEN and the family short-circuits to the pandas fallback
   without touching the device.  After ``ResilienceBreakerCooldownS`` it
   lets one HALF_OPEN probe through; a clean probe closes the breaker, a
   failed probe re-opens it.  A wedged device or pathologically slow kernel
   therefore degrades the *path*, never the *answer*.

All state transitions, retries, and fallbacks are published through
``emit_metric`` (modin_tpu/logging/metrics.py) as
``modin_tpu.resilience.*`` counters.  The deterministic fault-injection
harness lives in modin_tpu/testing/faults.py; knobs are the
``MODIN_TPU_RESILIENCE_*`` parameters in modin_tpu/config/envvars.py.
"""

from __future__ import annotations

import functools
import queue
import re
import threading
import time
from typing import Any, Callable, Dict, Optional

from modin_tpu.concurrency import named_lock
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import meters as graftmeter
from modin_tpu.observability import spans as graftscope
from modin_tpu.observability.flight_recorder import dump_flight_record

# graftgate serving context (deadline tokens + degraded routing).  A leaf
# module by design — serving/__init__ loads only errors+context eagerly —
# so this import cannot cycle; every seam check below gates on the single
# module attribute serving_context.CONTEXT_ON (False unless a serving
# query scope or ad-hoc deadline is active anywhere in the process).
from modin_tpu.serving import context as serving_context

# test seams: the suite patches these to run breaker-cooldown / backoff
# scenarios without wall-clock sleeps
_now = time.monotonic
_sleep = time.sleep

# fault-injection seam: modin_tpu.testing.faults installs a callable here;
# it runs inside every engine-seam attempt (under the watchdog, before the
# real work) so injected faults traverse the same classify/retry/breaker
# machinery a real device failure would
_fault_hook: Optional[Callable[[str], None]] = None


# ---------------------------------------------------------------------- #
# 1. Failure classification
# ---------------------------------------------------------------------- #


class DeviceFailure(RuntimeError):
    """Base for classified infrastructure failures at the engine seam.

    Disjoint from the semantic fallback signals (ShuffleSkewError,
    _TooManyGroups, ModinAssumptionError): a DeviceFailure means the device
    runtime misbehaved, not that the optimized path declined the inputs.
    """

    kind = "device_failure"


class DeviceOOM(DeviceFailure):
    """Device memory exhausted (XLA RESOURCE_EXHAUSTED).  Not retried: the
    same program over the same buffers will exhaust the same HBM."""

    kind = "oom"


class DeviceLost(DeviceFailure):
    """The device or its transport is gone (runtime drop, device reset).
    Not retried: recovery needs the breaker cooldown, not a tight loop.

    ``shard_index`` is the mesh row shard the runtime named in the error
    (parsed from a ``shard_index=N`` message fragment), or None when the
    loss is unattributed.  graftmesh recovery uses it to re-seat ONLY that
    shard's slice of each column instead of rebuilding whole columns.
    """

    kind = "device_lost"
    shard_index: Optional[int] = None


class WatchdogTimeout(DeviceLost):
    """A materialize/wait outlived the configured wall-clock watchdog.
    Treated as DeviceLost: a fetch that never returns is a dead transport."""

    kind = "watchdog_timeout"


class TransientDeviceError(DeviceFailure):
    """A retryable runtime hiccup (DEADLINE_EXCEEDED, ABORTED, INTERNAL...)."""

    kind = "transient"


# message fragments -> classification, checked in order (first match wins).
# XLA surfaces absl status codes in the message text; a multi-host runtime
# adds socket/connection wording of its own.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "out of memory", "OOM", "Out of memory")
_LOST_MARKERS = (
    "DEVICE_LOST",
    "device lost",
    "UNAVAILABLE",
    "socket closed",
    "connection reset",
    "connection refused",
    "heartbeat",
    "NOT_FOUND: device",
)
_RUNTIME_ERROR_TYPE_NAMES = ("XlaRuntimeError", "JaxRuntimeError")
#: graftfuse: dispatching over a buffer a previous donated dispatch consumed
#: surfaces as a plain ValueError/RuntimeError, not an XlaRuntimeError — the
#: engine's own retry of a donated thunk on real hardware hits exactly this.
#: Classified as DeviceLost so the deploy rebind leg rebuilds the argument
#: tree from the (lineage-restorable) columns and dispatches over live
#: buffers, instead of crashing the query on a retry artifact.
_DONATED_MARKERS = ("deleted or donated", "Array has been deleted")

#: a runtime error message may name the lost shard (the fault harness does;
#: real runtimes name devices in their own formats, unparsed = None)
_SHARD_INDEX_RE = re.compile(r"shard_index=(\d+)")


def is_device_runtime_error(exc: BaseException) -> bool:
    """True if ``exc`` is the accelerator runtime's error type (by name, so
    the check works against any jaxlib version and the fault harness's
    stand-in without importing either)."""
    return any(
        t.__name__ in _RUNTIME_ERROR_TYPE_NAMES for t in type(exc).__mro__
    )


def classify_device_error(exc: BaseException) -> Optional[DeviceFailure]:
    """Map ``exc`` onto the classification, or None if it is not a device failure.

    None means the exception is the caller's problem (a semantic signal or a
    genuine bug) and must propagate — classification never swallows it.
    """
    if isinstance(exc, DeviceFailure):
        return exc
    if not is_device_runtime_error(exc):
        if isinstance(exc, (ValueError, RuntimeError)) and any(
            m in str(exc) for m in _DONATED_MARKERS
        ):
            return DeviceLost(str(exc))
        return None
    msg = str(exc)
    if any(m in msg for m in _OOM_MARKERS):
        return DeviceOOM(msg)
    if any(m in msg for m in _LOST_MARKERS):
        failure = DeviceLost(msg)
        shard = _SHARD_INDEX_RE.search(msg)
        if shard is not None:
            failure.shard_index = int(shard.group(1))
        return failure
    # unknown runtime error: assume transient so it gets a bounded retry and
    # then strikes the breaker rather than crashing the query
    return TransientDeviceError(msg)


# ---------------------------------------------------------------------- #
# 2. Engine-seam wrapper: retry with backoff + watchdog
# ---------------------------------------------------------------------- #


def _run_with_watchdog(op: str, thunk: Callable[[], Any], timeout_s: float) -> Any:
    """Run ``thunk`` bounded by ``timeout_s`` wall-clock seconds.

    A daemon thread (NOT ThreadPoolExecutor: its atexit hook would join a
    wedged worker and hang interpreter shutdown — same rationale as the
    device probe in modin_tpu/utils/show_versions) does the blocking call;
    expiry raises WatchdogTimeout and abandons the thread.
    """
    result_q: "queue.Queue" = queue.Queue()
    # propagate span context onto the worker: spans/compile-attribution in
    # the thunk nest under the caller's call chain instead of floating
    # parentless
    parent_stack = graftscope.snapshot_stack() if graftscope.TRACE_ON else None
    # same for query-stats scopes: compile events observed inside the thunk
    # emit on THIS worker thread, and the owning query's rollup must see
    # them (QueryStats routing is lock-guarded and terminal at scope close,
    # so a worker abandoned by a watchdog timeout can race the owner's
    # retry — or outlive the scope — without corrupting the rollup)
    parent_scopes = (
        graftmeter.snapshot_scopes() if graftmeter.ACCOUNTING_ON else None
    )
    # and the serving context: a deadline must bound work the worker does
    # on the owner's behalf (nested engine calls inside the thunk)
    parent_ctx = (
        serving_context.snapshot_context()
        if serving_context.CONTEXT_ON
        else None
    )

    def runner() -> None:
        if parent_stack is not None:
            graftscope.seed_thread(parent_stack)
        if parent_scopes is not None:
            graftmeter.seed_thread_scopes(parent_scopes)
        if parent_ctx is not None:
            serving_context.seed_thread_context(parent_ctx)
        try:
            result_q.put((True, thunk()))
        except BaseException as err:  # noqa: BLE001 - relayed to caller  # graftlint: disable=EXC-HYGIENE -- watchdog thread relays ANY exception to the waiting caller verbatim
            result_q.put((False, err))

    thread = threading.Thread(
        target=runner, daemon=True, name=f"modin-tpu-watchdog-{op}"
    )
    thread.start()
    # a query deadline tighter than the watchdog bounds the wait instead:
    # the blocking fetch is abandoned the moment the budget is gone, and
    # the expiry surfaces as the TYPED serving error — not as a
    # WatchdogTimeout, which would misread a slow-but-healthy device as
    # lost and trigger a pointless lineage re-seat.  The wait loops so a
    # deadline-clamped get that wakes *before* the watchdog window closes
    # (deadline not quite expired, value not quite ready) keeps waiting
    # instead of misclassifying.
    started = time.monotonic()  # real clock: tests patch _now for breakers
    while True:
        wait_s = timeout_s - (time.monotonic() - started)
        if wait_s <= 0:
            emit_metric(f"resilience.watchdog.{op}.timeout", 1)
            raise WatchdogTimeout(
                f"{op} exceeded the {timeout_s:g}s resilience watchdog "
                "(MODIN_TPU_RESILIENCE_WATCHDOG_S); treating the device "
                "path as lost"
            ) from None
        if serving_context.CONTEXT_ON:
            # raises DeadlineExceeded when the budget expired; abandoning
            # the daemon worker is the same trade the watchdog already
            # makes for a wedged fetch
            serving_context.check_deadline(f"engine.{op}.watchdog")
            remaining = serving_context.remaining_s()
            if remaining is not None:
                wait_s = min(wait_s, max(remaining, 1e-3))
        try:
            ok, payload = result_q.get(timeout=wait_s)
            break
        except queue.Empty:
            continue
    if ok:
        return payload
    raise payload


def _run_attempt(op: str, attempt_once: Callable[[], Any], timeout_s: float) -> Any:
    """One attempt, under the watchdog when requested and — while a serving
    context is active — under the collective-safe dispatch lock for the
    program-enqueue ops (see serving/context.py:dispatch_lock: concurrent
    sharded enqueues that interleave per-device deadlock the collective
    rendezvous).

    The watchdog branch comes FIRST and is never serialized: blocking
    fetches only drain results, and the lock must never span a worker
    handoff — an owner holding it while a daemon worker enqueues would
    release on abandonment (timeout/deadline) with the enqueue still in
    flight, recreating the interleave the lock exists to prevent, and a
    nested deploy on the worker would stall against its own owner.  If a
    program-enqueue op ever grows a watchdog, take the lock INSIDE the
    worker, not here.
    """
    if timeout_s > 0:
        return _run_with_watchdog(op, attempt_once, timeout_s)
    if serving_context.CONTEXT_ON and op in ("deploy", "put"):
        with serving_context.dispatch_lock:
            return attempt_once()
    return attempt_once()


def engine_call(
    op: str,
    thunk: Callable[[], Any],
    watchdog: bool = False,
    protect_ids: Optional[set] = None,
    cost_cb: Optional[Callable[[bool, Any, float], None]] = None,
) -> Any:
    """Run one engine-seam invocation under the resilience policy.

    Transient failures retry up to ``ResilienceRetries`` times with
    exponential backoff.  ``watchdog=True`` (materialize/wait — the
    blocking fetches) additionally bounds each attempt by
    ``ResilienceWatchdogS``.

    graftguard (core/execution/recovery.py) upgrades the two formerly
    terminal failure kinds:

    - ``DeviceOOM`` gets up to ``SpillRetries`` **evict-then-retry**
      rounds — spill the coldest device columns to host (never the ones
      in ``protect_ids``: the failing op's own inputs, pinned by the
      thunk closure), then re-dispatch — before the OOM is terminal;
    - ``DeviceLost`` gets one **lineage re-seat**: every live device
      column is rebuilt from its provenance on the (fresh) device and
      the call retried.  The retry re-runs the SAME thunk — its closure
      still references the pre-loss buffers, which an injected fault
      leaves intact but a real loss kills; ``JaxWrapper.deploy`` adds the
      rebind-and-redispatch leg for that case, and the pandas fallbacks
      read the re-seated/host data either way.

    Both legs are skipped while a recovery pass is itself on the stack
    (no recursive recovery) and when ``MODIN_TPU_RECOVERY_MODE=Disable``.

    ``cost_cb`` (graftcost, deploy only) runs on the dispatching thread
    after a successful attempt with ``(compiled, attempt_span,
    attempt_wall_s)`` — while the ``engine.<op>.attempt`` span is still
    open, so static cost attributes land on the span that did the work,
    and with the wall of the successful attempt alone (retries/backoff
    excluded).  It is pre-guarded (never raises) and only passed while
    ``costs.COST_ON``.
    """
    from modin_tpu.config import (
        ResilienceBackoffS,
        ResilienceMode,
        ResilienceRetries,
        ResilienceWatchdogS,
        SpillRetries,
    )
    from modin_tpu.core.execution import recovery

    # graftgate deadline: one seam check before any engine work, covering
    # the ResilienceMode=Disable bypass too — a budget-expired query must
    # not enqueue more device work in either mode
    if serving_context.CONTEXT_ON:
        serving_context.check_deadline(f"engine.{op}")

    def attempt_once() -> Any:
        hook = _fault_hook
        if hook is not None:
            hook(op)
        return thunk()

    if ResilienceMode.get() == "Disable":
        compiles_before = None
        if op == "deploy" and cost_cb is not None:
            from modin_tpu.observability.compile_ledger import (
                compiles_on_this_thread,
            )

            compiles_before = compiles_on_this_thread()
        attempt_t0 = time.perf_counter()
        result = _run_attempt(op, attempt_once, 0.0)
        attempt_wall = time.perf_counter() - attempt_t0
        # accounting still owes the dispatch count under the bypass knob —
        # EXPLAIN ANALYZE / the metrics_smoke ceilings must not go blind
        # just because resilience is off
        if op == "deploy" and graftmeter.ACCOUNTING_ON:
            graftmeter.note_dispatch()
        if compiles_before is not None:
            from modin_tpu.observability.compile_ledger import (
                compiles_on_this_thread,
            )

            cost_cb(
                compiles_on_this_thread() > compiles_before, None, attempt_wall
            )
        return result

    timeout_s = float(ResilienceWatchdogS.get()) if watchdog else 0.0
    retries = int(ResilienceRetries.get())
    backoff_s = float(ResilienceBackoffS.get())
    spill_retries = int(SpillRetries.get())
    attempt = 0
    oom_rounds = 0
    reseat_spent = False
    while True:
        if serving_context.CONTEXT_ON:
            # attempt-start boundary: a retry / evict-then-retry / re-seat
            # loop re-enters here, so deadline overshoot is bounded by ONE
            # attempt, never by the remaining retry budget
            serving_context.check_deadline(f"engine.{op}.attempt")
        sp = compiles_before = None
        if graftscope.TRACE_ON:
            sp = graftscope.start_span(
                f"engine.{op}.attempt",
                layer="JAX-ENGINE",
                attrs={"op": op, "attempt": attempt},
            )
        if op == "deploy" and (sp is not None or cost_cb is not None):
            from modin_tpu.observability.compile_ledger import (
                compiles_on_this_thread,
            )

            compiles_before = compiles_on_this_thread()
        # the epoch this attempt's work launches in: a DeviceLost below
        # hands it to reseat_all so concurrent observers of ONE loss share
        # one recovery pass (reseat-once) instead of re-seating per thread
        attempt_epoch = recovery.current_epoch()
        attempt_t0 = time.perf_counter()
        try:
            result = _run_attempt(op, attempt_once, timeout_s)
        except Exception as err:  # graftlint: disable=EXC-HYGIENE -- the classification point: catches broadly, re-raises non-device errors
            failure = classify_device_error(err)
            if sp is not None:
                sp.attrs["failure_kind"] = (
                    failure.kind if failure is not None else type(err).__name__
                )
                graftscope.finish_span(sp, status="error")
            if failure is None:
                raise
            emit_metric(f"resilience.engine.{op}.{failure.kind}", 1)
            if (
                isinstance(failure, DeviceOOM)
                and oom_rounds < spill_retries
                and not recovery.in_recovery()
                and recovery.evict_for_oom(op, exclude_ids=protect_ids) > 0
            ):
                # evict-then-retry: cold columns were spilled to host, so
                # the same dispatch now has the HBM it asked for
                oom_rounds += 1
                emit_metric("recovery.retry.oom", 1)
                continue
            if (
                isinstance(failure, DeviceLost)
                and not reseat_spent
                and not recovery.in_recovery()
                and recovery.reseat_all(
                    f"engine_{op}",
                    observed_epoch=attempt_epoch,
                    shard_index=getattr(failure, "shard_index", None),
                )
                > 0
            ):
                # lineage re-seat: resident columns were rebuilt on the
                # fresh device; give the call one post-recovery retry
                reseat_spent = True
                emit_metric("recovery.retry.device_lost", 1)
                continue
            if not isinstance(failure, TransientDeviceError) or attempt >= retries:
                # terminal for this call: preserve the trace that led here
                if dump_flight_record(f"terminal_{failure.kind}", detail=op):
                    emit_metric("trace.flight_dump", 1)
                raise failure from err
            attempt += 1
            emit_metric(f"resilience.engine.{op}.retry", 1)
            delay_s = backoff_s * (2 ** (attempt - 1))
            if serving_context.CONTEXT_ON:
                # a backoff sleep never outlives the query's budget: sleep
                # at most the remaining time, and the attempt-start check
                # above turns the expiry into the typed abort
                delay_s = serving_context.clamp_sleep(delay_s)
            _sleep(delay_s)
            continue
        except BaseException:  # graftlint: disable=EXC-HYGIENE -- span-stack unwind only (KeyboardInterrupt, bench SIGALRM); re-raised immediately
            # a non-Exception unwind (Ctrl-C, SectionTimeout) must still pop
            # the attempt span or every later span on this thread parents
            # under a stale entry
            if sp is not None:
                graftscope.finish_span(sp, status="error")
            raise
        if compiles_before is not None:
            from modin_tpu.observability.compile_ledger import (
                compiles_on_this_thread,
                get_compile_ledger,
            )

            compiled = compiles_on_this_thread() > compiles_before
            if sp is not None:
                get_compile_ledger().record_dispatch(
                    graftscope.attribution_signature(), compiled=compiled
                )
            if cost_cb is not None:
                # the SUCCESSFUL attempt's wall: failed attempts and the
                # backoff sleeps between them are never billed as dispatch
                cost_cb(compiled, sp, time.perf_counter() - attempt_t0)
        if op == "deploy" and graftmeter.ACCOUNTING_ON:
            graftmeter.note_dispatch()
        if sp is not None:
            graftscope.finish_span(sp)
        return result


# ---------------------------------------------------------------------- #
# 3. Per-device-path circuit breaker
# ---------------------------------------------------------------------- #

#: Every breaker family a ``@device_path`` decorator in the TPU query
#: compiler may use.  This is the operator-facing catalog: docs, dashboards,
#: and ``breaker_snapshot`` consumers key off these names, and graftlint's
#: FALLBACK-PARITY rule cross-checks it both ways (an undeclared family in
#: the compiler, or a declared family with no ``_try_*`` user, is drift).
#: Tests may still create ad-hoc families (e.g. "probe_unit") at runtime;
#: only the query compiler's production paths are held to the registry.
DEVICE_PATH_FAMILIES = frozenset(
    {
        "binary",
        "reduce",
        "dt_component",
        "str_lut",
        "top_k",
        "corr_cov",
        "shift",
        "merge",
        "rolling",
        "ewm",
        "resample",
        "expanding",
        "groupby",
        "shuffle_apply",
        "sort_shuffle",
        # graftsort: the sort-shaped reduction family (median / quantile /
        # nunique / mode) behind the kernel router (ops/router.py)
        "sort_reduce",
    }
)

CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"


class CircuitBreaker:
    """Consecutive-strike breaker guarding one named device path.

    CLOSED: calls flow; every device failure or latency-budget violation is
    a strike, every clean call resets the count.  ``threshold`` strikes trip
    it OPEN: calls short-circuit to the fallback for ``cooldown_s`` seconds.
    Then one HALF_OPEN probe is admitted — success closes, failure re-opens
    (with a fresh cooldown).  Thresholds are read from config at trip-check
    time so tests and operators can retune a live process.
    """

    def __init__(self, name: str):
        self.name = name
        self.state = CLOSED
        self.strikes = 0
        self.opened_at = 0.0
        self._lock = named_lock("resilience.breaker")

    # -- config ------------------------------------------------------- #

    @staticmethod
    def _threshold() -> int:
        from modin_tpu.config import ResilienceBreakerThreshold

        return int(ResilienceBreakerThreshold.get())

    @staticmethod
    def _cooldown_s() -> float:
        from modin_tpu.config import ResilienceBreakerCooldownS

        return float(ResilienceBreakerCooldownS.get())

    def _transition(self, state: str) -> bool:
        """Record the state change; returns True when it opened (the caller
        dumps the flight record AFTER releasing the breaker lock — disk IO
        under the lock would stall every thread short-circuiting on it)."""
        self.state = state
        emit_metric(f"resilience.breaker.{self.name}.{state}", 1)
        return state == OPEN

    def _dump_open(self) -> None:
        """Flight-record a trip to OPEN: the spans that led up to the
        degradation (no-op unless tracing is on; rate-limited; never
        raises).  Must be called WITHOUT the breaker lock held."""
        if dump_flight_record(f"breaker_open_{self.name}"):
            emit_metric("trace.flight_dump", 1)

    # -- protocol ------------------------------------------------------ #

    def allow(self) -> bool:
        """May the guarded path run right now?"""
        with self._lock:
            if self.state == CLOSED:
                return True
            if self.state == OPEN:
                if _now() - self.opened_at >= self._cooldown_s():
                    self._transition(HALF_OPEN)
                    return True
                return False
            # HALF_OPEN: one probe is already in flight this cooldown; hold
            # further calls on the fallback until it reports
            return False

    def record_success(self, latency_s: float = 0.0) -> None:
        from modin_tpu.config import ResilienceLatencyBudgetS

        budget = float(ResilienceLatencyBudgetS.get())
        if budget > 0 and latency_s > budget:
            emit_metric(f"resilience.breaker.{self.name}.slow", 1)
            self._strike()
            return
        with self._lock:
            self.strikes = 0
            if self.state != CLOSED:
                self._transition(CLOSED)

    def record_failure(self) -> None:
        self._strike()

    def abort_probe(self) -> None:
        """The in-flight HALF_OPEN probe ended without a health verdict
        (an unclassified exception escaped).  Return to OPEN with a fresh
        cooldown — staying HALF_OPEN would short-circuit the family forever,
        since only a probe can leave that state."""
        opened = False
        with self._lock:
            if self.state == HALF_OPEN:
                self.opened_at = _now()
                opened = self._transition(OPEN)
        if opened:
            self._dump_open()

    def _strike(self) -> None:
        opened = False
        with self._lock:
            self.strikes += 1
            emit_metric(f"resilience.breaker.{self.name}.strike", 1)
            if self.state == HALF_OPEN:
                # failed probe: straight back to OPEN, fresh cooldown
                self.opened_at = _now()
                opened = self._transition(OPEN)
            elif self.state == CLOSED and self.strikes >= self._threshold():
                self.opened_at = _now()
                opened = self._transition(OPEN)
        if opened:
            self._dump_open()


_BREAKERS: Dict[str, CircuitBreaker] = {}
_breakers_lock = named_lock("resilience.breakers")


def get_breaker(name: str) -> CircuitBreaker:
    with _breakers_lock:
        breaker = _BREAKERS.get(name)
        if breaker is None:
            breaker = _BREAKERS[name] = CircuitBreaker(name)
        return breaker


def breaker_snapshot() -> Dict[str, str]:
    """{family: state} for introspection / debugging."""
    with _breakers_lock:
        return {name: b.state for name, b in _BREAKERS.items()}


def reset_breakers() -> None:
    """Forget all breaker state (tests; operator escape hatch)."""
    with _breakers_lock:
        _BREAKERS.clear()


def drop_breaker(name: str) -> None:
    """Forget one breaker by name (graftgate's tenant registry evicts idle
    tenants' health breakers so per-user tenant ids cannot grow this
    registry without bound; device-path families are never dropped)."""
    with _breakers_lock:
        _BREAKERS.pop(name, None)


def device_path(family: str) -> Callable:
    """Decorator for ``TpuQueryCompiler._try_*`` methods: per-family breaker.

    The wrapped method keeps its contract — return a result, or None for
    "use the pandas fallback".  The wrapper adds the infrastructure leg:

    - breaker OPEN  -> return None immediately (short-circuit, no device
      contact) and count it;
    - a classified DeviceFailure raised anywhere inside the call -> strike
      the breaker, count the fallback, return None (the caller's pandas
      default produces the answer);
    - anything unclassified (semantic signals handled inside the method,
      genuine bugs) propagates untouched;
    - a clean call reports its latency so budget violations strike too.
    """

    def decorator(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(self: Any, *args: Any, **kwargs: Any) -> Any:
            from modin_tpu.config import ResilienceMode

            if ResilienceMode.get() == "Disable":
                return fn(self, *args, **kwargs)
            if serving_context.CONTEXT_ON and serving_context.degraded_active():
                # graftgate degraded mode: this thread's query was admitted
                # while the device was sick (open breaker / ledger past
                # high water) — route it to the pandas fallback exactly
                # like an open breaker would, without touching the device
                emit_metric("serving.degraded.fallback", 1)
                if graftscope.TRACE_ON:
                    graftscope.finish_span(
                        graftscope.start_span(
                            f"fallback.{family}",
                            layer="QUERY-COMPILER",
                            attrs={"family": family, "reason": "degraded"},
                        )
                    )
                return None
            breaker = get_breaker(family)
            if not breaker.allow():
                emit_metric(f"resilience.breaker.{family}.short_circuit", 1)
                if graftscope.TRACE_ON:
                    graftscope.finish_span(
                        graftscope.start_span(
                            f"fallback.{family}",
                            layer="QUERY-COMPILER",
                            attrs={"family": family, "reason": "short_circuit"},
                        )
                    )
                return None
            start = _now()
            try:
                if serving_context.CONTEXT_ON:
                    # collective-safe dispatch (serving/context.py): the
                    # kernel families direct-call their jitted programs, so
                    # the whole guarded device path serializes — two
                    # threads' sharded programs reaching the per-device
                    # queues in different orders deadlock the collective
                    # rendezvous.  Host/pandas fallbacks stay concurrent.
                    with serving_context.dispatch_lock:
                        result = fn(self, *args, **kwargs)
                else:
                    result = fn(self, *args, **kwargs)
            except Exception as err:  # graftlint: disable=EXC-HYGIENE -- device_path classification point: unclassified exceptions propagate
                failure = classify_device_error(err)
                if failure is None:
                    # not the device's fault — but if this call was the
                    # HALF_OPEN probe, the breaker must not wait forever for
                    # a verdict that will never come: re-open it so the next
                    # cooldown admits a fresh probe
                    breaker.abort_probe()
                    raise
                breaker.record_failure()
                if isinstance(failure, DeviceLost) and breaker.state == OPEN:
                    # terminal breaker-open on a lost device: re-seat the
                    # resident columns from lineage NOW so the pandas
                    # fallbacks this family degrades to (and every other
                    # family) read healthy buffers instead of poisoned ones
                    from modin_tpu.core.execution import recovery

                    if not recovery.in_recovery():
                        recovery.reseat_all(
                            f"breaker_open_{family}",
                            shard_index=getattr(
                                failure, "shard_index", None
                            ),
                        )
                emit_metric(f"resilience.fallback.{family}.{failure.kind}", 1)
                if graftscope.TRACE_ON:
                    graftscope.finish_span(
                        graftscope.start_span(
                            f"fallback.{family}",
                            layer="QUERY-COMPILER",
                            attrs={"family": family, "reason": failure.kind},
                        )
                    )
                return None
            breaker.record_success(_now() - start)
            return result

        wrapper._resilience_family = family
        return wrapper

    return decorator
