"""graftmeter: in-process metric aggregation + per-query resource accounting.

``emit_metric`` (modin_tpu/logging/metrics.py) has always been fire-and-
forget: values fan out to registered handlers and vanish.  This module is
the measurement layer on top of that stream:

- **Aggregation registry** — every emitted metric folds into a typed meter
  (counter / gauge / fixed-bucket histogram) keyed by its emitted name; the
  kind comes from the family's declaration in the ``METRICS`` registry
  (each entry is ``(pattern, kind, description)``).  ``snapshot()`` returns
  the whole registry as plain dicts (p50/p95/p99 for histograms),
  ``reset()`` clears it; ``observability/exposition.py`` renders a snapshot
  as Prometheus text format or JSON.

- **Per-query accounting** — a :func:`query_stats` scope rolls up, per
  thread, everything a query consumed: wall time, device dispatches, XLA
  compiles (count + seconds, via the compile-ledger listener), bytes parsed
  by FileDispatcher reads, HBM high-water and spill/restore traffic from
  the device ledger, recovery events, and cache hits across the fused /
  sorted-rep / plan-scan caches.  Scopes nest and are thread-isolated: a
  metric emitted on thread A never lands in thread B's open scope.
  ``explain(analyze=True)`` runs a deferred plan inside such a scope and
  annotates every executed plan node with its measured share.  A live scope
  also switches graftscope on (``spans.open_request``) and is the
  per-request record of it: the host's self time by layer, the time blocked
  waiting for the device, every device-program launch by name, every
  blocking device->host fetch and every uploaded byte, each counted where it
  happens.  Closed scopes stay in a bounded ring (:func:`recent_queries`).

Disabled-mode contract (the default, ``MODIN_TPU_METERS=0`` and no active
query-stats scope): ``emit_metric`` pays one module-attribute read
(``metrics._aggregate`` is None) and the instrumented seams pay one
attribute check of :data:`ACCOUNTING_ON` — no aggregation object is ever
allocated, asserted via :func:`meter_alloc_count` exactly the way
``spans.span_alloc_count()`` asserts the tracing contract.
"""

from __future__ import annotations

import contextlib
import fnmatch
import itertools
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple, Union

from modin_tpu.concurrency import named_lock
from modin_tpu.observability import spans as _spans

#: Module-level fast path, graftscope-style: True while the aggregation
#: registry (``MODIN_TPU_METERS``) or at least one ``query_stats()`` scope
#: is live.  Instrumented seams (engine dispatch accounting, compile
#: listener, FileDispatcher byte accounting, fused-cache hit accounting)
#: check this ONE attribute before doing anything else.
ACCOUNTING_ON: bool = False

#: True while ``MODIN_TPU_METERS`` is enabled (registry aggregation).
METERS_ON: bool = False

#: Fixed bucket upper bounds for every histogram-kind family declared in
#: ``METRICS`` (modin_tpu/logging/metrics.py).  Keys are the exact registry
#: patterns; graftlint's REGISTRY-DRIFT rule cross-checks this mapping both
#: ways (a histogram family without buckets, or a bucket spec without a
#: histogram family, fails the lint).  Values below the first bound land in
#: the first bucket; values above the last land in the overflow bucket.
HISTOGRAM_BUCKETS: Dict[str, Tuple[float, ...]] = {
    # wall-clock seconds per public pandas-API call
    "pandas-api.*": (
        0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
        0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
    ),
    # bytes parsed per FileDispatcher read
    "io.read.bytes": (
        1 << 10, 4 << 10, 16 << 10, 64 << 10, 256 << 10,
        1 << 20, 4 << 20, 16 << 20, 64 << 20, 256 << 20, 1 << 30,
    ),
    # rewrite passes to fixpoint per plan materialization
    "plan.optimize.passes": (1, 2, 3, 4, 6, 8, 12, 16),
    # distinct plan nodes lowered per materialization
    "plan.lower.nodes": (1, 2, 4, 8, 16, 32, 64, 128, 256),
    # fold lag (ms) observed at each graftfeed view read
    "view.lag_ms": (
        0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
        500.0, 1000.0, 2500.0, 5000.0, 10000.0,
    ),
    # seconds an admitted query spent in the admission queue (graftgate)
    "serving.queue_wait_s": (
        0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
        0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
    ),
    # end-to-end wall seconds per submitted query (graftgate; the bench's
    # concurrent section reads p50/p99 straight off this family)
    "serving.query_wall_s": (
        0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
        1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
    ),
}

VALID_KINDS = ("counter", "gauge", "histogram")

_alloc_count = 0  # meter objects ever constructed (the zero-alloc assertion)

#: .dispatches: this thread's monotonic dispatch count.  The thread's stack
#: of open QueryStats is graftscope's request stack (``spans.thread_requests``):
#: one list serves the metric stream and the spans
_qs_tls = threading.local()

_scope_lock = named_lock("meters.scopes")
_active_scopes = 0

#: every currently-open QueryStats scope, process-wide (insertion order =
#: open order).  Maintained under _scope_lock by query_stats enter/exit;
#: graftwatch's /debug/queries endpoint renders this live.
_live_scopes: Dict[int, "QueryStats"] = {}

#: the last closed scopes, oldest first (:func:`recent_queries`).  Sized for
#: a benchmark window of single-client requests several times over (486 asv
#: requests fill a 51 s window today)
RECENT_QUERIES_MAX = 4096
_recent_scopes: "deque[QueryStats]" = deque(maxlen=RECENT_QUERIES_MAX)

_request_ids = itertools.count(1)

#: engine-seam attempt spans whose whole self time is the host blocked on the
#: device (``wait_s``), not host work
_WAIT_SPANS = frozenset({"engine.wait.attempt", "engine.materialize.attempt"})

#: ``host_self_s`` key for scope time under no span: the caller's own code
#: between API calls (a benchmark's glue, its own ``block_until_ready``)
CALLER = "CALLER"

_env_enabled = False

#: long-lived registry consumers (graftwatch): registry aggregation is
#: active while ANY consumer holds an acquire, independent of the
#: MODIN_TPU_METERS knob — the watch sampler/exporter need the series to
#: exist without asking the operator to flip a second switch
_registry_consumers = 0


def acquire_registry() -> None:
    """Activate registry aggregation on behalf of a long-lived consumer.

    Balanced by :func:`release_registry`; callers (the graftwatch
    service) must hold at most one acquire per logical consumer."""
    global _registry_consumers
    with _scope_lock:
        _registry_consumers += 1
        _refresh_enabled()


def release_registry() -> None:
    global _registry_consumers
    with _scope_lock:
        _registry_consumers = max(_registry_consumers - 1, 0)
        _refresh_enabled()


def meter_alloc_count() -> int:
    """How many aggregation objects this process has ever constructed.

    The disabled-mode contract is *zero new allocations*; tests snapshot
    this counter around a workload run with meters off.
    """
    return _alloc_count


# ---------------------------------------------------------------------- #
# meter types
# ---------------------------------------------------------------------- #


class Counter:
    """Monotonic sum of emitted values (plus emission count)."""

    __slots__ = ("total", "count")
    kind = "counter"

    def __init__(self) -> None:
        global _alloc_count
        _alloc_count += 1
        self.total = 0.0
        self.count = 0

    def add(self, value: Union[int, float]) -> None:
        self.total += value
        self.count += 1

    def snapshot(self) -> dict:
        total = self.total
        if isinstance(total, float) and total.is_integer():
            total = int(total)
        return {"kind": "counter", "total": total, "count": self.count}


class Gauge:
    """Last emitted value, with min/max/count over the window."""

    __slots__ = ("value", "min", "max", "count")
    kind = "gauge"

    def __init__(self) -> None:
        global _alloc_count
        _alloc_count += 1
        self.value = 0.0
        self.min = None
        self.max = None
        self.count = 0

    def add(self, value: Union[int, float]) -> None:
        self.value = value
        self.min = value if self.min is None else min(self.min, value)
        self.max = value if self.max is None else max(self.max, value)
        self.count += 1

    def snapshot(self) -> dict:
        return {
            "kind": "gauge",
            "value": self.value,
            "min": self.min,
            "max": self.max,
            "count": self.count,
        }


class Histogram:
    """Fixed-bucket histogram: per-bucket counts + sum/min/max, with
    percentile estimation by linear interpolation inside the bucket."""

    __slots__ = ("bounds", "bucket_counts", "count", "sum", "min", "max")
    kind = "histogram"

    def __init__(self, bounds: Tuple[float, ...]) -> None:
        global _alloc_count
        _alloc_count += 1
        self.bounds = tuple(float(b) for b in bounds)
        self.bucket_counts = [0] * (len(self.bounds) + 1)  # +1: overflow
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None

    def add(self, value: Union[int, float]) -> None:
        value = float(value)
        idx = len(self.bounds)  # overflow unless a bound catches it
        for i, bound in enumerate(self.bounds):
            if value <= bound:
                idx = i
                break
        self.bucket_counts[idx] += 1
        self.count += 1
        self.sum += value
        self.min = value if self.min is None else min(self.min, value)
        self.max = value if self.max is None else max(self.max, value)

    def percentile(self, q: float) -> Optional[float]:
        """Estimated q-quantile (0 < q < 1), linear inside the bucket."""
        if self.count == 0:
            return None
        target = q * self.count
        seen = 0.0
        for i, bucket_count in enumerate(self.bucket_counts):
            if bucket_count == 0:
                continue
            if seen + bucket_count >= target:
                lo = self.bounds[i - 1] if i > 0 else (
                    self.min if self.min is not None else 0.0
                )
                hi = self.bounds[i] if i < len(self.bounds) else (
                    self.max if self.max is not None else lo
                )
                lo = max(lo, self.min) if self.min is not None else lo
                hi = min(hi, self.max) if self.max is not None else hi
                if hi <= lo:
                    return lo
                frac = (target - seen) / bucket_count
                return lo + (hi - lo) * frac
            seen += bucket_count
        return self.max

    def snapshot(self) -> dict:
        cumulative = []
        running = 0
        for bound, bucket_count in zip(self.bounds, self.bucket_counts):
            running += bucket_count
            cumulative.append([bound, running])
        return {
            "kind": "histogram",
            "count": self.count,
            "sum": self.sum,
            "min": self.min,
            "max": self.max,
            "p50": self.percentile(0.50),
            "p95": self.percentile(0.95),
            "p99": self.percentile(0.99),
            "buckets": cumulative,  # [upper_bound, cumulative_count] pairs
        }


# ---------------------------------------------------------------------- #
# the registry
# ---------------------------------------------------------------------- #


class MeterRegistry:
    """Thread-safe name -> meter aggregation, kinds resolved against the
    ``METRICS`` declarations."""

    def __init__(self) -> None:
        self._lock = named_lock("meters.registry")
        self._meters: Dict[str, Any] = {}
        self._kinds: Dict[str, Tuple[str, Optional[Tuple[float, ...]]]] = {}
        self._dropped = 0  # observations refused by the cardinality guard
        self._dropped_names: set = set()  # distinct refused names (bounded)

    # -- kind resolution ------------------------------------------------ #

    def _resolve(self, name: str) -> Tuple[str, Optional[Tuple[float, ...]]]:
        cached = self._kinds.get(name)
        if cached is not None:
            return cached
        from modin_tpu.logging.metrics import METRICS

        kind = "counter"  # ad-hoc names (tests) default to the safest kind
        buckets: Optional[Tuple[float, ...]] = None
        for entry in METRICS:
            pattern = entry[0]
            if fnmatch.fnmatchcase(name, pattern):
                declared = entry[1] if len(entry) > 2 else "counter"
                if declared in VALID_KINDS:
                    kind = declared
                if kind == "histogram":
                    buckets = HISTOGRAM_BUCKETS.get(pattern)
                    if buckets is None:
                        kind = "counter"  # undeclared buckets: degrade
                break
        self._kinds[name] = (kind, buckets)
        return kind, buckets

    def _max_series(self) -> int:
        try:
            from modin_tpu.config import MetersMaxSeries

            return int(MetersMaxSeries.get())
        except ImportError:  # config unavailable during teardown
            return 2048

    # -- recording ------------------------------------------------------- #

    def record(self, name: str, value: Union[int, float]) -> None:
        with self._lock:
            meter = self._meters.get(name)
            if meter is None:
                max_series = self._max_series()
                if len(self._meters) >= max_series:
                    self._dropped += 1
                    # distinct-name accounting is itself bounded: a runaway
                    # of rotating names must not leak through the guard's
                    # own bookkeeping
                    if len(self._dropped_names) < 4 * max_series:
                        self._dropped_names.add(name)
                    return
                kind, buckets = self._resolve(name)
                if kind == "histogram":
                    meter = Histogram(buckets)
                elif kind == "gauge":
                    meter = Gauge()
                else:
                    meter = Counter()
                self._meters[name] = meter
            meter.add(value)

    # -- introspection --------------------------------------------------- #

    def snapshot(self) -> dict:
        """Deep-copied ``{"series": {name: meter-dict}, ...}`` snapshot."""
        with self._lock:
            return {
                "enabled": METERS_ON,
                "dropped_series": len(self._dropped_names),
                "dropped_observations": self._dropped,
                "series": {
                    name: meter.snapshot()
                    for name, meter in sorted(self._meters.items())
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._meters.clear()
            # the kind-resolution cache too: repeated reset cycles with
            # rotating interpolated names would otherwise grow it without
            # bound
            self._kinds.clear()
            self._dropped = 0
            self._dropped_names.clear()


_REGISTRY = MeterRegistry()


def get_registry() -> MeterRegistry:
    return _REGISTRY


def snapshot() -> dict:
    """Snapshot of the process-wide aggregation registry."""
    return _REGISTRY.snapshot()


def reset() -> None:
    """Clear the process-wide aggregation registry."""
    _REGISTRY.reset()


# ---------------------------------------------------------------------- #
# enable/disable plumbing
# ---------------------------------------------------------------------- #


def _refresh_enabled() -> None:
    """Recompute the fast-path flags and (un)install the emit hook."""
    global ACCOUNTING_ON, METERS_ON
    METERS_ON = _env_enabled or _registry_consumers > 0
    on = METERS_ON or _active_scopes > 0
    ACCOUNTING_ON = on
    metrics = sys.modules.get("modin_tpu.logging.metrics")
    if metrics is None and on:
        from modin_tpu.logging import metrics  # noqa: F811
    if metrics is not None:
        metrics._aggregate = _dispatch_metric if on else None
    # graftcost Auto mode piggybacks on ACCOUNTING_ON; only poke the module
    # if something already imported it (same no-import rule as the ledger
    # sampling seam) — costs recomputes on ITS import/config path otherwise
    costs = sys.modules.get("modin_tpu.observability.costs")
    if costs is not None:
        costs._refresh()


def _on_meters_param(param: Any) -> None:
    global _env_enabled
    # same lock as query_stats enter/exit: an unsynchronized refresh could
    # read a stale _active_scopes and strand ACCOUNTING_ON=False under an
    # open scope (or leave the emit hook uninstalled)
    with _scope_lock:
        _env_enabled = bool(param.get())
        _refresh_enabled()


def meters_enabled() -> bool:
    """Is registry aggregation active right now (the config switch, or a
    long-lived consumer such as the graftwatch service)?"""
    return METERS_ON


def _dispatch_metric(name: str, value: Union[int, float]) -> None:
    """The ``metrics._aggregate`` hook: registry + active QueryStats."""
    try:
        if METERS_ON:
            _REGISTRY.record(name, value)
        stack = _spans.thread_requests()
        if stack:
            for qs in stack:
                qs._on_metric(name, value)
    except Exception:
        pass


# ---------------------------------------------------------------------- #
# seam hooks (callers check ACCOUNTING_ON first)
# ---------------------------------------------------------------------- #


def thread_dispatches() -> int:
    """Monotonic per-thread dispatch counter (EXPLAIN ANALYZE takes deltas)."""
    return getattr(_qs_tls, "dispatches", 0)


def note_dispatch() -> None:
    """One successful engine-seam deploy on this thread.

    Called by the resilience wrapper's success path while accounting is on;
    feeds the per-thread counter (plan-node attribution) and the metric
    stream (registry + QueryStats).  Compile attribution is separate: the
    jax.monitoring listener bills compiles via :func:`note_compile`.
    """
    _qs_tls.dispatches = getattr(_qs_tls, "dispatches", 0) + 1
    from modin_tpu.logging.metrics import emit_metric

    emit_metric("engine.dispatch", 1)


def note_launch(program: str) -> None:
    """One device-program launch on this thread (``ops/_program.py``, which
    checks ``ACCOUNTING_ON`` first): counted by name into every open scope,
    and the first one stamps ``first_launch_s``."""
    stack = _spans.thread_requests()
    if stack:
        now = time.perf_counter()
        for qs in stack:
            qs._on_launch(program, now)


def note_groupby_form(form: str) -> None:
    """One choice of a groupby reduction's or histogram's device form
    (``ops/groupby.py``, which checks ``ACCOUNTING_ON`` first): ``limb_dot``,
    ``masked_scan``, ``sorted_tiles`` (``sorted_tiles_max`` / ``_min`` /
    ``_var`` / ``_std`` / ``_sem`` for the tiles' other reductions),
    ``segment``, ``pallas_bincount``, ``scatter_counts``, or a per-group
    quantile's ``median_sort_select`` / ``quantile_sort_select``."""
    stack = _spans.thread_requests()
    if stack:
        for qs in stack:
            qs._on_groupby_form(form)


def note_elementwise_form(form: str, times: int) -> None:
    """``times`` columns of an elementwise node built in a form that adapts
    to its operands (``ops/elementwise.py``, which checks ``ACCOUNTING_ON``
    first): ``divmod_guarded``, the int64 ``mod`` / ``floordiv`` that divides
    in 32 bits where every operand fits.  Which branch the device then took
    is in the trace (``jit_plan_*/conditional.*``), not here: learning it
    would take a sync."""
    stack = _spans.thread_requests()
    if stack:
        for qs in stack:
            qs._on_elementwise_form(form, times)


def note_reduction_form(form: str) -> None:
    """One row-wise reduction's form, chosen from its column count
    (``ops/reductions.py``, which checks ``ACCOUNTING_ON`` first):
    ``axis1_columns`` (the k columns read as k arrays) or ``axis1_stacked``
    (a frame wider than the network stacks them into one matrix)."""
    stack = _spans.thread_requests()
    if stack:
        for qs in stack:
            qs._on_reduction_form(form)


def note_host_sync(nbytes: int) -> None:
    """One blocking device->host fetch of ``nbytes`` on this thread."""
    stack = _spans.thread_requests()
    if stack:
        for qs in stack:
            qs._on_host_sync(nbytes)


def note_h2d(nbytes: int) -> None:
    """``nbytes`` uploaded host->device on this thread."""
    stack = _spans.thread_requests()
    if stack:
        for qs in stack:
            qs._on_h2d(nbytes)


def note_compile(duration_s: float) -> None:
    """One XLA backend compile observed by the monitoring listener."""
    from modin_tpu.logging.metrics import emit_metric

    emit_metric("engine.compile", 1)
    emit_metric("engine.compile_s", duration_s)


def note_made(program: str, phase: str, seconds: float, loaded: bool = False) -> None:
    """``seconds`` of making ``program`` on this thread, billed to every open
    scope (``observability/compile_ledger.py``, which checks ``ACCOUNTING_ON``
    first): ``phase`` is ``trace_s`` (its outermost trace), ``lower_s`` or
    ``compile_s`` (a backend compile, or with ``loaded`` a persistent-cache
    load)."""
    stack = _spans.thread_requests()
    if stack:
        for qs in stack:
            qs._on_made(program, phase, seconds, loaded)


def note_temp_bytes(program: str, temp_bytes: Optional[int]) -> None:
    """The temporary bytes the compiler gave ``program`` when the call on this
    thread made it (``ops/_program.py``, which checks ``ACCOUNTING_ON``
    first); ``None``, where the compiler did not say, writes nothing."""
    stack = _spans.thread_requests()
    if stack and temp_bytes is not None:
        for qs in stack:
            qs._on_temp_bytes(program, temp_bytes)


def _device_resident_bytes() -> int:
    """Device-ledger resident bytes, via the one shared sampling seam
    (``spans._ledger_bytes``: never imports core.memory, swallows ledger
    errors) so the no-import-recursion rule lives in a single place."""
    return _spans._ledger_bytes()[0]


# ---------------------------------------------------------------------- #
# per-query accounting
# ---------------------------------------------------------------------- #


class QueryStats:
    """Everything one query scope consumed, rolled up from the metric
    stream on the owning thread (plus HBM residency samples)."""

    __slots__ = (
        "label",
        "signature",
        "wall_s",
        "dispatches",
        "compiles",
        "compile_s",
        "trace_s",
        "lower_s",
        "cache_loads",
        "cache_load_s",
        "programs_made",
        "bytes_parsed",
        "io_reads",
        "spills",
        "spill_bytes",
        "restores",
        "recoveries",
        "cache_hits",
        "hbm_high_water",
        "est_flops",
        "est_bytes",
        "padded_bytes",
        "padding_waste_bytes",
        "collective_bytes",
        "breaker_trips",
        "stream_windows",
        "stream_replays",
        "stream_overlap_s",
        "stream_wait_s",
        "fused_dispatches",
        "donated_bytes",
        "view_hits",
        "view_folds",
        "view_invalidations",
        "request_id",
        "host_self_s",
        "wait_s",
        "first_launch_s",
        "launches",
        "launches_by_program",
        "groupby_forms",
        "elementwise_forms",
        "reduction_forms",
        "host_syncs",
        "d2h_bytes",
        "h2d_bytes",
        "spans",
        "_self_us",
        "_root_us",
        "_span_depth0",
        "_t0",
        "_lock",
        "_closed",
    )

    def __init__(self, label: str = "query") -> None:
        global _alloc_count
        _alloc_count += 1
        self.label = label
        # routing can cross threads (the resilience watchdog seeds its
        # worker with the owner's scopes, and a timed-out worker is
        # abandoned mid-thunk): accumulation takes this lock, and a closed
        # scope stops accepting so late emissions from an abandoned worker
        # can never mutate a rollup the owner already read
        self._lock = named_lock("meters.query_stats")
        self._closed = False
        self.signature = None  # innermost QUERY-COMPILER span, if tracing
        self.wall_s = 0.0
        self.dispatches = 0
        self.compiles = 0
        self.compile_s = 0.0
        # how the scope's programs were made, from jax's own events: the
        # outermost trace and lowering seconds, the part of compiles /
        # compile_s the persistent cache answered, and all of it by program
        # ({name: {trace_s, lower_s, compile_s, loaded[, temp_bytes]}})
        self.trace_s = 0.0
        self.lower_s = 0.0
        self.cache_loads = 0
        self.cache_load_s = 0.0
        self.programs_made: Dict[str, dict] = {}
        self.bytes_parsed = 0
        self.io_reads = 0
        self.spills = 0
        self.spill_bytes = 0
        self.restores = 0
        self.recoveries = 0
        self.cache_hits = {"fused": 0, "sorted_rep": 0, "plan_scan": 0}
        self.hbm_high_water = 0
        # graftcost: estimated hardware cost + padding waste (0 until the
        # cost-capture seams observe work under this scope)
        self.est_flops = 0.0
        self.est_bytes = 0.0
        self.padded_bytes = 0
        self.padding_waste_bytes = 0
        # graftmesh: payload bytes this scope moved through collectives
        # (all_to_all/psum) — the cross-device traffic share of est_bytes
        self.collective_bytes = 0
        # graftgate tenant health: device-path breaker strikes observed
        # while this scope's query ran (its own fallbacks included — a
        # query can complete correct via fallback yet be striking paths)
        self.breaker_trips = 0
        # graftstream: resident windows this query streamed through, window
        # replays after mid-stream device failures, and the prefetch
        # overlap/wait split (overlap / (overlap + wait) is the pipeline's
        # overlap efficiency).  stream_windows > 0 also tells graftgate to
        # bill this query at its window footprint, not its dataset size.
        self.stream_windows = 0
        self.stream_replays = 0
        self.stream_overlap_s = 0.0
        self.stream_wait_s = 0.0
        # graftfuse: whole-plan dispatches (one program per query segment)
        # and the HBM released to XLA by buffer donation under this scope
        self.fused_dispatches = 0
        self.donated_bytes = 0
        # graftview: derived-artifact registry traffic under this scope —
        # whole results served from cache, appends absorbed by folds, and
        # artifacts honestly invalidated
        self.view_hits = 0
        self.view_folds = 0
        self.view_invalidations = 0
        # graftscope, per request: the id every span of this scope carries
        # (``request`` stat of its TraceAnnotation); host self time by layer
        # tag plus CALLER (filled at close), seconds blocked on the device,
        # scope open -> first device-program launch (None: none launched)
        self.request_id = next(_request_ids)
        self.host_self_s: Dict[str, float] = {}
        self.wait_s = 0.0
        self.first_launch_s: Optional[float] = None
        # counted where they happen: named_jit launches (a groupby's
        # kernels too, which never pass the engine seam's deploy), blocking
        # device->host fetches with their bytes, and uploaded bytes
        self.launches = 0
        self.launches_by_program: Dict[str, int] = {}
        # which device form each groupby reduction / histogram took, noted
        # where it is chosen: a scatter form on a TPU is a minute, not seconds
        self.groupby_forms: Dict[str, int] = {}
        # columns of elementwise nodes built in an operand-adaptive form
        self.elementwise_forms: Dict[str, int] = {}
        # row-wise reductions by the form their column count chose
        self.reduction_forms: Dict[str, int] = {}
        self.host_syncs = 0
        self.d2h_bytes = 0
        self.h2d_bytes = 0
        self.spans = 0
        self._self_us: Dict[str, float] = {}
        self._root_us = 0.0
        self._span_depth0 = 0
        self._t0 = time.perf_counter()

    # -- stream routing -------------------------------------------------- #

    def _on_metric(self, name: str, value: Union[int, float]) -> None:
        with self._lock:
            if self._closed:
                return
            self._route(name, value)

    def _route(self, name: str, value: Union[int, float]) -> None:
        if name == "engine.dispatch":
            self.dispatches += int(value)
            self._sample_hbm()
        elif name == "engine.compile":
            self.compiles += int(value)
        elif name == "engine.compile_s":
            self.compile_s += value
        elif name == "io.read.bytes":
            self.bytes_parsed += int(value)
            self.io_reads += 1
        elif name == "memory.device.spill":
            self.spills += int(value)
            self._sample_hbm()
        elif name == "memory.device.spill_bytes":
            self.spill_bytes += int(value)
        elif name == "memory.device.restore":
            self.restores += int(value)
            self._sample_hbm()
        elif name == "engine.cost.flops":
            self.est_flops += value
        elif name == "engine.cost.bytes":
            self.est_bytes += value
        elif name == "engine.cost.padded_bytes":
            self.padded_bytes += int(value)
        elif name == "engine.cost.padding_waste_bytes":
            self.padding_waste_bytes += int(value)
        elif name == "engine.cost.collective_bytes":
            self.collective_bytes += int(value)
        elif name == "sortcache.hit":
            self.cache_hits["sorted_rep"] += int(value)
        elif name == "fusion.cache.hit":
            self.cache_hits["fused"] += int(value)
        elif name == "plan.scan.cache_hit":
            self.cache_hits["plan_scan"] += int(value)
        elif name == "stream.window.count":
            self.stream_windows += int(value)
            self._sample_hbm()
        elif name == "fuse.dispatch":
            self.fused_dispatches += int(value)
            self._sample_hbm()
        elif name == "fuse.donated":
            # fired BEFORE the donated buffers leave the ledger: the last
            # honest pre-donation residency peak
            self._sample_hbm()
        elif name == "fuse.donated_bytes":
            self.donated_bytes += int(value)
            self._sample_hbm()
        elif name == "view.hit":
            self.view_hits += int(value)
        elif name == "view.fold":
            self.view_folds += int(value)
        elif name.startswith("view.invalidate."):
            self.view_invalidations += int(value)
        elif name == "stream.window.replay":
            self.stream_replays += int(value)
        elif name == "stream.prefetch.overlap_s":
            self.stream_overlap_s += value
        elif name == "stream.prefetch.wait_s":
            self.stream_wait_s += value
        elif name.startswith("recovery."):
            self.recoveries += int(value)
        elif (
            name.startswith("resilience.breaker.")
            and name.endswith(".strike")
            and not name.startswith("resilience.breaker.tenant_")
        ):
            # DEVICE-path strikes only: a nested submit's tenant-health
            # breaker (graftgate strikes it on the same thread while the
            # outer scope is still open) is a serving verdict, not device
            # sickness — counting it would cascade one tenant's failures
            # into the outer tenant's quarantine
            self.breaker_trips += int(value)

    # -- graftscope / launch / transfer routing -------------------------- #

    def _on_span(self, sp: Any, depth: int) -> None:
        """A span finished under this scope (``spans.finish_span``):
        ``depth`` open spans are left beneath it on its thread."""
        self_us = sp.dur_us - sp.child_us
        if self_us < 0.0:
            self_us = 0.0
        key = "wait" if sp.name in _WAIT_SPANS else sp.layer
        with self._lock:
            if self._closed:
                return
            self.spans += 1
            self._self_us[key] = self._self_us.get(key, 0.0) + self_us
            if depth == self._span_depth0:
                self._root_us += sp.dur_us

    def _on_launch(self, program: str, now: float) -> None:
        with self._lock:
            if self._closed:
                return
            if self.first_launch_s is None:
                self.first_launch_s = now - self._t0
            self.launches += 1
            by = self.launches_by_program
            by[program] = by.get(program, 0) + 1

    def _made_entry(self, program: str) -> dict:
        entry = self.programs_made.get(program)
        if entry is None:
            entry = self.programs_made[program] = {
                "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0, "loaded": False,
            }
        return entry

    def _on_made(self, program: str, phase: str, seconds: float, loaded: bool) -> None:
        with self._lock:
            if self._closed:
                return
            entry = self._made_entry(program)
            entry[phase] += seconds
            if phase == "trace_s":
                self.trace_s += seconds
            elif phase == "lower_s":
                self.lower_s += seconds
            elif loaded:
                entry["loaded"] = True
                self.cache_loads += 1
                self.cache_load_s += seconds

    def _on_temp_bytes(self, program: str, temp_bytes: int) -> None:
        with self._lock:
            if not self._closed:
                self._made_entry(program)["temp_bytes"] = temp_bytes

    def _on_groupby_form(self, form: str) -> None:
        with self._lock:
            if not self._closed:
                self.groupby_forms[form] = self.groupby_forms.get(form, 0) + 1

    def _on_elementwise_form(self, form: str, times: int) -> None:
        with self._lock:
            if not self._closed:
                self.elementwise_forms[form] = (
                    self.elementwise_forms.get(form, 0) + times
                )

    def _on_reduction_form(self, form: str) -> None:
        with self._lock:
            if not self._closed:
                self.reduction_forms[form] = self.reduction_forms.get(form, 0) + 1

    def _on_host_sync(self, nbytes: int) -> None:
        with self._lock:
            if not self._closed:
                self.host_syncs += 1
                self.d2h_bytes += nbytes

    def _on_h2d(self, nbytes: int) -> None:
        with self._lock:
            if not self._closed:
                self.h2d_bytes += nbytes

    def _close(self) -> None:
        """Final wall and the host split; called once, by the owner."""
        with self._lock:
            self.wall_s = time.perf_counter() - self._t0
            self._sample_hbm()
            self._closed = True
            self_us = self._self_us
            self.wait_s = self_us.pop("wait", 0.0) / 1e6
            split = {layer: us / 1e6 for layer, us in sorted(self_us.items())}
            split[CALLER] = max(self.wall_s - self._root_us / 1e6, 0.0)
            self.host_self_s = split

    def _sample_hbm(self) -> None:
        resident = _device_resident_bytes()
        if resident > self.hbm_high_water:
            self.hbm_high_water = resident

    def elapsed_s(self) -> float:
        """Wall seconds so far (final wall once the scope has closed)."""
        if self._closed:
            return self.wall_s
        return time.perf_counter() - self._t0

    # -- export ---------------------------------------------------------- #

    def as_dict(self) -> dict:
        return {
            "label": self.label,
            "signature": self.signature,
            "wall_s": self.wall_s,
            "dispatches": self.dispatches,
            "compiles": self.compiles,
            "compile_s": self.compile_s,
            "trace_s": self.trace_s,
            "lower_s": self.lower_s,
            "cache_loads": self.cache_loads,
            "cache_load_s": self.cache_load_s,
            "programs_made": {name: dict(e) for name, e in self.programs_made.items()},
            "bytes_parsed": self.bytes_parsed,
            "io_reads": self.io_reads,
            "spills": self.spills,
            "spill_bytes": self.spill_bytes,
            "restores": self.restores,
            "recoveries": self.recoveries,
            "cache_hits": dict(self.cache_hits),
            "hbm_high_water": self.hbm_high_water,
            "est_flops": self.est_flops,
            "est_bytes": self.est_bytes,
            "padded_bytes": self.padded_bytes,
            "padding_waste_bytes": self.padding_waste_bytes,
            "collective_bytes": self.collective_bytes,
            "breaker_trips": self.breaker_trips,
            "stream_windows": self.stream_windows,
            "stream_replays": self.stream_replays,
            "stream_overlap_s": self.stream_overlap_s,
            "stream_wait_s": self.stream_wait_s,
            "fused_dispatches": self.fused_dispatches,
            "donated_bytes": self.donated_bytes,
            "view_hits": self.view_hits,
            "view_folds": self.view_folds,
            "view_invalidations": self.view_invalidations,
            "request_id": self.request_id,
            "host_self_s": dict(self.host_self_s),
            "wait_s": self.wait_s,
            "first_launch_s": self.first_launch_s,
            "launches": self.launches,
            "launches_by_program": dict(self.launches_by_program),
            "groupby_forms": dict(self.groupby_forms),
            "elementwise_forms": dict(self.elementwise_forms),
            "reduction_forms": dict(self.reduction_forms),
            "host_syncs": self.host_syncs,
            "d2h_bytes": self.d2h_bytes,
            "h2d_bytes": self.h2d_bytes,
            "spans": self.spans,
        }

    def summary(self) -> str:
        """Human-readable rollup block for EXPLAIN ANALYZE output."""
        hits = ", ".join(f"{k}={v}" for k, v in sorted(self.cache_hits.items()))
        lines = [
            f"wall: {self.wall_s * 1e3:.3f} ms",
            f"device dispatches: {self.dispatches}, xla compiles: "
            f"{self.compiles} ({self.compile_s:.3f}s)",
            f"bytes parsed: {self.bytes_parsed} ({self.io_reads} read(s))",
            f"hbm high-water: {self.hbm_high_water} bytes, spills: "
            f"{self.spills} ({self.spill_bytes} bytes), restores: "
            f"{self.restores}, recoveries: {self.recoveries}",
            f"cache hits: {hits}",
            f"launches: {self.launches}, host syncs: {self.host_syncs} "
            f"({self.d2h_bytes} bytes down), {self.h2d_bytes} bytes up, "
            f"waited {self.wait_s * 1e3:.3f} ms",
            self._cost_line(),
        ]
        if self.fused_dispatches:
            lines.append(
                f"fuse: {self.fused_dispatches} whole-plan dispatch(es), "
                f"{self.donated_bytes} bytes donated"
            )
        if self.view_hits or self.view_folds or self.view_invalidations:
            lines.append(
                f"views: {self.view_hits} artifact hit(s), "
                f"{self.view_folds} incremental fold(s), "
                f"{self.view_invalidations} invalidation(s)"
            )
        if self.stream_windows:
            busy = self.stream_overlap_s + self.stream_wait_s
            eff = f"{self.stream_overlap_s / busy:.0%}" if busy > 0 else "?"
            lines.append(
                f"stream: {self.stream_windows} window(s), "
                f"{self.stream_replays} replay(s), overlap efficiency {eff} "
                f"({self.stream_overlap_s:.3f}s hidden, "
                f"{self.stream_wait_s:.3f}s waited)"
            )
        return "\n".join(lines)

    def _cost_line(self) -> str:
        """The graftcost rollup line: estimated work, padding share, and
        the achieved roofline fraction joined to this scope's wall."""
        from modin_tpu.observability import costs as _costs

        pad_pct = (
            f"{self.padding_waste_bytes / self.padded_bytes:.0%}"
            if self.padded_bytes > 0
            else "?"
        )
        roofline = "?"
        try:
            fraction = _costs.roofline_fraction(
                self.est_flops or None, self.est_bytes or None, self.wall_s
            )
            if fraction is not None:
                roofline = f"{fraction:.1%}"
        except Exception:
            pass
        return (
            f"est cost: {self.est_flops:.3g} flops, "
            f"{self.est_bytes:.3g} bytes moved; padding waste: "
            f"{self.padding_waste_bytes} of {self.padded_bytes} padded "
            f"bytes ({pad_pct}); roofline: {roofline}"
        )


def live_scopes() -> List["QueryStats"]:
    """Every QueryStats scope currently open on ANY thread (open order).

    The returned scopes are live objects owned by their opening threads —
    read them via :meth:`QueryStats.as_dict` (slot reads are atomic
    enough for telemetry); graftwatch's ``/debug/queries`` endpoint is
    the consumer.
    """
    with _scope_lock:
        return list(_live_scopes.values())


def recent_queries(label: Optional[str] = None) -> List[dict]:
    """``as_dict()`` of the last closed scopes, oldest first (at most
    ``RECENT_QUERIES_MAX``), of one ``label`` or of all: the closed-scope
    complement of :func:`live_scopes`."""
    with _scope_lock:
        closed = list(_recent_scopes)
    return [qs.as_dict() for qs in closed if label is None or qs.label == label]


def snapshot_scopes() -> Optional[List["QueryStats"]]:
    """Copy of this thread's open QueryStats stack (outermost first), or None.

    Mirrors ``spans.snapshot_stack()``: worker threads that run a query's
    work on the caller's behalf (the resilience watchdog) seed themselves
    with this so metrics they emit still roll into the owning query's
    scopes.
    """
    stack = _spans.thread_requests()
    return list(stack) if stack else None


def seed_thread_scopes(scopes: Optional[List["QueryStats"]]) -> None:
    """Adopt a snapshot of another thread's QueryStats stack.

    The seeded scopes are owned, entered, and exited by their original
    thread — this thread only routes its emissions into them.  Accumulation
    is lock-guarded and a closed scope stops accepting, so a worker the
    owner abandoned (watchdog timeout) can race the owner's retry or
    outlive the scope without corrupting its rollup.

    Always REPLACES the thread's stack — seeding with ``None``/empty
    clears it.  The previous keep-if-falsy behavior was a single-owner
    assumption: a pooled worker seeded for query A and later reused for
    unscoped work (or query B) kept routing emissions into A's closed
    scopes — closed-scope rejection hid the corruption, but a *still-open*
    outer scope on the original thread would have silently absorbed
    another query's metrics.
    """
    _spans.seed_requests(scopes)


@contextlib.contextmanager
def query_stats(label: str = "query") -> Iterator[QueryStats]:
    """Collect per-query resource accounting for the block on this thread.

    Activates accounting for its duration even when ``MODIN_TPU_METERS`` is
    off (that is the point: ad-hoc EXPLAIN ANALYZE without a process
    restart), and graftscope with it, exactly as ``profile()`` does: the
    scope is the per-request record of its spans.  Scopes nest (inner
    metrics and spans roll into every open scope on the stack) and are
    thread-isolated.  The scope is seeded from the innermost QUERY-COMPILER
    span open on this thread when tracing is active.  Once closed it joins
    the ring :func:`recent_queries` reads.
    """
    global _active_scopes
    qs = QueryStats(label)
    if _spans.TRACE_ON:
        sig = _spans.attribution_signature()
        if sig != "<untraced>":
            qs.signature = sig
    with _scope_lock:
        _active_scopes += 1
        _live_scopes[id(qs)] = qs
        _refresh_enabled()
    qs._span_depth0 = _spans.open_request(qs)
    qs._t0 = time.perf_counter()  # the switches above are not the query's
    try:
        yield qs
    finally:
        qs._close()
        _spans.close_request(qs)
        with _scope_lock:
            _active_scopes -= 1
            _live_scopes.pop(id(qs), None)
            _recent_scopes.append(qs)
            _refresh_enabled()


# wire the config switch (fires immediately with its current value)
from modin_tpu.config import MetersEnabled as _MetersEnabled  # noqa: E402

_MetersEnabled.subscribe(_on_meters_param)
