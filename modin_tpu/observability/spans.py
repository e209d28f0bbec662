"""graftscope span core: nestable spans with thread-local context propagation.

The query path crosses four seams — pandas API entry, the TPU query
compiler, the ``JaxWrapper`` engine seam, and shuffle/IO — and until now the
only record of a query's life was a flat START/STOP line log plus API timing
counters.  This module is the structured replacement: every instrumented
call becomes a **span** (name, layer tag, span id, parent id, wall-clock
interval, attributes), spans nest via a thread-local stack, and finished
spans are delivered to any active collectors (``profile()``) and to the
flight-recorder ring buffer (modin_tpu/observability/flight_recorder.py).

Layer tags reuse the ``modin_layer`` classification the ``ClassLogger`` mixin
already stamps on every subsystem (``PANDAS-API``, ``QUERY-COMPILER``,
``JAX-ENGINE``, ``CORE-IO``, ...) plus ``SHUFFLE`` for the range-partition
shuffle, so a profile slices the same way the trace log always has.

Disabled-mode contract (the default, ``MODIN_TPU_TRACE=0``): the only cost
an instrumented call pays is one module-attribute check of ``TRACE_ON`` —
no span object is ever allocated (``span_alloc_count()`` lets tests assert
exactly that), and ``span()`` returns a shared no-op context manager
singleton.  ``TRACE_ON`` flips when the ``TraceEnabled`` config parameter
changes (pubsub subscription), while any ``profile()`` is active, or while
any ``query_stats()`` scope is open (the scope is the per-request record:
see ``open_request``).

The profiler's clock: while tracing is on every span is also a
``jax.profiler.TraceAnnotation("mt/<LAYER>/<name>", request=<scope id>,
**attrs)``, so a ``jax.profiler`` trace holds the spans in its host plane
beside the device's ``XLA Ops`` line.  Times in an ``.xplane.pb`` are relative
to the profiler session, so the in-process ``Span`` cannot be joined to a
trace by its own clock: the annotation is the join, and its ``request`` stat
is the id every span of one ``query_stats`` scope shares (0 outside any).

Span names emitted with static (or f-string) names are declared in the
``SPANS`` registry below, cross-checked both ways by graftlint's
REGISTRY-DRIFT rule exactly like ``emit_metric`` names are against
``METRICS`` — an undeclared span name, a dead registry pattern, or an
undocumented family fails the lint.  The per-method spans emitted through
``layer_span`` by the logging decorator carry runtime-built names
(``<Class>.<method>``) and are documented as the layer classification instead.
"""

from __future__ import annotations

import contextlib
import itertools
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

from modin_tpu.concurrency import named_lock

#: Module-level fast path.  Instrumentation sites check this ONE attribute
#: before doing anything else; while it is False no span object is ever
#: allocated.  Flipped by the TraceEnabled config subscription and by
#: profile() activation — never written anywhere else.
TRACE_ON: bool = False

#: Registry of every span family emitted with a statically-known name
#: (``*`` stands for a runtime-interpolated segment, exactly like
#: logging/metrics.py:METRICS).  graftlint's REGISTRY-DRIFT rule
#: cross-checks this both ways — a ``span(...)``/``start_span(...)`` call
#: whose name matches no pattern, or a pattern with no live emit site,
#: fails the lint — and requires each family's stable prefix to appear in
#: docs/ (see docs/observability.md).  Per-method spans from the logging
#: decorator (``layer_span``) have runtime names and are covered by the
#: layer-tag classification instead.
SPANS = (
    (
        "engine.*.attempt",
        "one engine-seam attempt (deploy/put/materialize/wait) under the "
        "resilience policy; retries appear as sibling attempt spans with "
        "attempt/failure_kind attributes, XLA compile time attributed via "
        "compile_s",
    ),
    (
        "fallback.*",
        "a device-path family declining to the pandas fallback: reason is "
        "the classified failure kind, or short_circuit when the family's "
        "breaker is open",
    ),
    (
        "shuffle.range_shuffle",
        "the all_to_all range-partition shuffle: bucketize/pack, collective, "
        "compaction; slack retries recorded in attributes",
    ),
    (
        "io.read",
        "one FileDispatcher read (format dispatcher class in attributes)",
    ),
    (
        "recovery.reseat",
        "one graftguard lineage-recovery pass re-seating lost device "
        "columns after a DeviceLost (reason in attributes)",
    ),
    (
        "memory.device.spill",
        "one admission-control / evict-then-retry spill pass dropping "
        "cold device buffers to host (byte target in attributes)",
    ),
    (
        "router.decide",
        "one graftsort kernel-router decision: op family, rows, planned "
        "per-column strategies, predicted device/host costs and the "
        "chosen side in attributes",
    ),
    (
        "sortcache.build",
        "one batched sorted-representation build (the shared sort the "
        "rest of the sort-shaped family amortizes); column count in "
        "attributes",
    ),
    (
        "view.fold",
        "one graftview incremental-maintenance fold: the appended tail "
        "gathered and reduced (scalar combine) or grouped (partial-table "
        "combine) and merged into the cached artifact; op, column count, "
        "base and tail row counts in attributes",
    ),
    (
        "plan.optimize",
        "one graftplan rewrite pass to fixpoint over a pending logical "
        "plan (node count in attributes; applied rules become plan.rule.* "
        "metrics)",
    ),
    (
        "plan.lower",
        "one graftplan lowering pass: optimized plan nodes replayed "
        "through the eager dispatcher / query-compiler / engine seams "
        "(node count in attributes)",
    ),
    (
        "lazy.linearize",
        "the host half of one fused materialization (ops/lazy.py): the "
        "pending expression forest flattened, fingerprinted and looked up "
        "in (or traced into) the fused-program cache; root count in "
        "attributes.  The dispatch that follows is an engine.deploy.attempt",
    ),
    (
        "groupby.reduce",
        "one device groupby aggregation (ops/groupby.py groupby_reduce): the "
        "form chosen for it (limb_dot / masked_scan / sorted_tiles / segment / "
        "pallas_bincount / scatter_counts), agg, num_groups and n_cols in "
        "attributes; innermost QUERY-COMPILER span, so the compile ledger "
        "bills the reduction's programs to it",
    ),
    (
        "groupby.factorize",
        "the histogram of an integer key's range in factorize_keys (the "
        "codes' by-product that names the groups present): form and width "
        "in attributes",
    ),
    (
        "groupby.first_seen",
        "the order of first appearance of a sort=False groupby "
        "(ops/groupby.py groupby_first_seen: a prefix of the codes sorted, "
        "grown until it shows every group; form, num_groups and rows in "
        "attributes) and the gather of the answer's G rows into it (form "
        "take); layer GROUPBY-ASSEMBLE",
    ),
    (
        "qc.groupby.assemble",
        "the key columns of an as_index=False device groupby put in front of "
        "the answer, resident (query compiler _assemble_groupby: n_keys and "
        "num_groups in attributes); layer GROUPBY-ASSEMBLE",
    ),
    (
        "opt.choose",
        "one graftopt joint strategy pass over an optimized plan: every "
        "node annotated with estimated rows/bytes/seconds and its chosen "
        "kernel/layout/compile/residency legs (replanning flag and "
        "correction factor in attributes)",
    ),
    (
        "opt.replan",
        "one graftopt mid-query re-plan: the not-yet-lowered segment "
        "re-chosen against live evidence (trigger, remaining node count, "
        "divergence evidence, re-plan wall in attributes)",
    ),
    (
        "fuse.lower",
        "one graftfuse whole-plan fused lowering: the post-scan segment "
        "(filter/map/project chain plus its reduce or groupby tail) "
        "compiled and dispatched as a single donated program (segment "
        "signature, rows, donated column count in attributes)",
    ),
    (
        "stream.window",
        "one graftstream resident window: parse/deploy/consume/drop of a "
        "record-aligned byte range (scan loop) or one external-sort window "
        "slice (window index in attributes)",
    ),
    (
        "stream.merge",
        "one graftstream k-way fold of spilled sorted runs into the final "
        "permutation (run count in attributes)",
    ),
    (
        "serving.admit",
        "one graftgate admission decision: tenant, queue wait, and the "
        "degraded-route flag in attributes; error status means the query "
        "was shed or its deadline expired while queued",
    ),
    (
        "serving.query",
        "one admitted query's execution envelope under the serving "
        "context (tenant / label / degraded in attributes); everything "
        "the query dispatched nests under it",
    ),
    (
        "ingest.append",
        "one graftfeed micro-batch admitted into a feed: schema-validated "
        "rows concatenated onto the frame, views folded per policy, "
        "retention applied (feed / row count in attributes)",
    ),
    (
        "ingest.fold",
        "one pending micro-batch folded into every registered live view's "
        "running state (feed / batch seq in attributes)",
    ),
    (
        "ingest.read",
        "one staleness-bounded live-view read: fold-lag check, optional "
        "forced synchronous fold, state snapshot (feed / view in "
        "attributes)",
    ),
    (
        "checkpoint.write",
        "one graftwal checkpoint: pending folds drained, feed frame + "
        "every view's fold state snapshotted under the feed lock, "
        "serialized and atomically written outside it, covered WAL "
        "segments truncated (feed in attributes)",
    ),
    (
        "recovery.replay",
        "one graftwal crash recovery: newest valid checkpoint restored, "
        "WAL tail replayed through the ordinary ingest path, torn tail "
        "truncated with accounting (feed in attributes)",
    ),
)

_EPOCH_PERF = time.perf_counter()
_EPOCH_WALL = time.time()

_span_ids = itertools.count(1)
_alloc_count = 0  # Span objects ever constructed (the zero-alloc assertion)

_tls = threading.local()

_collectors: List[list] = []  # active profile() collectors
_state_lock = named_lock("spans.state")

#: bounded ring of recently finished spans (the flight recorder's memory);
#: created/resized by _refresh_enabled from TraceFlightRecorderSize
_RING: Optional[deque] = None

#: bounded ring of counter samples ``(ts_us, (device_bytes, host_bytes,
#: live_spans))`` taken at each span finish while tracing is on; rendered
#: as Chrome-trace counter tracks ("C" events) so HBM pressure is visible
#: on the Perfetto timeline alongside the spans that caused it
_COUNTERS: Optional[deque] = None

#: open spans across all threads right now (the third counter track);
#: maintained only while the counter ring exists (zeroed on ring
#: reconfiguration), read-modify-write only under _live_lock — threads
#: finish spans concurrently and a lost update would drift the counter
_live_spans = 0
_live_lock = named_lock("spans.live")

_env_enabled = False

#: open ``query_stats`` scopes process-wide; tracing is on while any is
_open_requests = 0


class Span:
    """One timed, attributed interval on the query path."""

    __slots__ = (
        "name",
        "layer",
        "span_id",
        "parent_id",
        "start_us",
        "dur_us",
        "wall_start_s",
        "attrs",
        "thread_id",
        "thread_name",
        "status",
        "request",
        "child_us",
        "_counted",
        "_annotation",
    )

    def __init__(self, name: str, layer: str, attrs: Optional[dict], parent_id: Optional[int]):
        ident = getattr(_tls, "ident", None)
        if ident is None:  # looked up once a thread, not once a span
            t = threading.current_thread()
            ident = _tls.ident = (t.ident or 0, t.name)
        self.name = name
        self.layer = layer
        self.span_id = next(_span_ids)
        self.parent_id = parent_id
        self.start_us = (time.perf_counter() - _EPOCH_PERF) * 1e6
        self.wall_start_s = _EPOCH_WALL + self.start_us / 1e6
        self.dur_us = 0.0
        self.attrs = attrs if attrs is not None else {}
        self.thread_id, self.thread_name = ident
        self.status = "open"
        # the innermost open query_stats scope on this thread (0: none)
        requests = getattr(_tls, "requests", None)
        self.request = requests[-1].request_id if requests else 0
        self.child_us = 0.0  # finished children's durations (self time = dur - this)
        self._counted = False  # did this span increment _live_spans?
        self._annotation = None  # the TraceAnnotation twin, while open

    def __repr__(self) -> str:  # debugging aid, not part of the export
        return (
            f"<Span {self.name} [{self.layer}] id={self.span_id} "
            f"parent={self.parent_id} dur={self.dur_us / 1e3:.3f}ms "
            f"{self.status}>"
        )


# ---------------------------------------------------------------------- #
# enable/disable plumbing
# ---------------------------------------------------------------------- #


def _refresh_enabled() -> None:
    """Recompute TRACE_ON (and size the ring) from config + collectors."""
    global TRACE_ON, _RING, _COUNTERS, _live_spans
    on = _env_enabled or bool(_collectors) or _open_requests > 0
    if on:
        from modin_tpu.config import TraceFlightRecorderSize

        size = int(TraceFlightRecorderSize.get())
        if size <= 0:
            _RING = None
            _COUNTERS = None
            with _live_lock:
                _live_spans = 0
        elif _RING is None or _RING.maxlen != size:
            if _RING is None:
                # live-span bookkeeping only runs while the ring exists:
                # restart the counter from zero on (re)enable rather than
                # trust a value that missed the opens in between
                with _live_lock:
                    _live_spans = 0
            # retune a live process: keep the newest spans that still fit
            _RING = deque(_RING or (), maxlen=size)
            _COUNTERS = deque(_COUNTERS or (), maxlen=size)
    TRACE_ON = on


def _on_trace_param(param: Any) -> None:
    global _env_enabled
    _env_enabled = bool(param.get())
    _refresh_enabled()
    if _env_enabled:
        try:
            from modin_tpu.observability.compile_ledger import ensure_listener
        except ImportError:
            # subscription fired during the package's own import (env sets
            # MODIN_TPU_TRACE=1) while compile_ledger is mid-initialization;
            # observability/__init__ installs the listener right after
            return
        ensure_listener()


def trace_enabled() -> bool:
    """Is span collection active right now (config switch or a profile)?"""
    return TRACE_ON


def span_alloc_count() -> int:
    """How many Span objects this process has ever constructed.

    The disabled-mode contract is *zero new allocations*; tests snapshot
    this counter around a workload run with tracing off.
    """
    return _alloc_count


def _TraceAnnotation(name: str, **stats: Any) -> Any:
    """``jax.profiler.TraceAnnotation``, imported at the first span (this
    module loads before jax does) and bound in place of this function."""
    global _TraceAnnotation
    from jax.profiler import TraceAnnotation

    _TraceAnnotation = TraceAnnotation
    return TraceAnnotation(name, **stats)


# ---------------------------------------------------------------------- #
# requests: the query_stats scopes spans report to
# ---------------------------------------------------------------------- #


def open_request(scope: Any) -> int:
    """A ``query_stats`` scope opened on this thread: tracing is on while it
    lives, every span started under it carries ``scope.request_id``, and every
    span finished under it is handed to ``scope._on_span(span, depth)``
    (``depth``: open spans left on the thread's stack).  Returns the stack
    depth at the opening, which marks the scope's root spans."""
    global _open_requests
    requests = getattr(_tls, "requests", None)
    if requests is None:
        requests = _tls.requests = []
    requests.append(scope)
    with _state_lock:
        _open_requests += 1
        first = _open_requests == 1
    if first:
        _refresh_enabled()
    stack = getattr(_tls, "stack", None)
    return len(stack) if stack else 0


def close_request(scope: Any) -> None:
    global _open_requests
    requests = getattr(_tls, "requests", None)
    if requests:
        try:
            requests.remove(scope)
        except ValueError:
            pass
    with _state_lock:
        _open_requests = max(_open_requests - 1, 0)
        last = _open_requests == 0
    if last:
        _refresh_enabled()


def thread_requests() -> Optional[list]:
    """This thread's open scopes, outermost first (the live list), or None."""
    return getattr(_tls, "requests", None)


def seed_requests(scopes: Optional[list]) -> None:
    """Adopt another thread's open scopes (the resilience watchdog's worker:
    spans it finishes report to the query that spawned the work)."""
    _tls.requests = list(scopes) if scopes else []


# ---------------------------------------------------------------------- #
# the span stack
# ---------------------------------------------------------------------- #


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def current_span() -> Optional[Span]:
    """Innermost open span on this thread, or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def snapshot_stack() -> Optional[list]:
    """Copy of this thread's open-span stack (outermost first), or None."""
    stack = getattr(_tls, "stack", None)
    return list(stack) if stack else None


def seed_thread(stack: Optional[list]) -> None:
    """Adopt a snapshot of another thread's span stack as ambient context.

    Worker threads (the resilience watchdog) call this so spans they start
    — and compile-time attribution — nest under the call chain that spawned
    the work instead of floating parentless.  The seeded spans are owned
    and finished by their original thread; this thread only reads them.
    """
    if stack:
        _tls.stack = list(stack)


def attribution_signature() -> str:
    """The op signature compile time should be billed to.

    Innermost QUERY-COMPILER or PLAN span if one is open on this thread
    (the per-operator granularity the compile ledger wants), else the
    innermost span of any layer, else ``<untraced>``.
    """
    stack = getattr(_tls, "stack", None)
    if not stack:
        return "<untraced>"
    for sp in reversed(stack):
        if sp.layer in ("QUERY-COMPILER", "PLAN"):
            return sp.name
    return stack[-1].name


# ---------------------------------------------------------------------- #
# span lifecycle
# ---------------------------------------------------------------------- #


def start_span(
    name: str,
    layer: str = "APP",
    attrs: Optional[dict] = None,
    parent_id: Optional[int] = None,
) -> Span:
    """Open a span and push it on this thread's stack.

    Callers on hot paths must check ``TRACE_ON`` first; this function
    allocates unconditionally (that is its job).
    """
    global _alloc_count, _live_spans
    stack = _stack()
    if parent_id is None and stack:
        parent_id = stack[-1].span_id
    sp = Span(name, layer, attrs, parent_id)
    _alloc_count += 1  # single-threaded assertion counter: no lock needed
    if _COUNTERS is not None:
        # the live-span counter track exists only while the ring does;
        # don't serialize every traced thread on the lock otherwise
        sp._counted = True
        with _live_lock:
            _live_spans += 1
    stack.append(sp)
    # the twin on the profiler's clock (a no-op object unless a jax.profiler
    # session is recording); entered last so it nests inside its parent's.
    # Attributes ride as event stats unless one would shadow an argument
    stats = attrs if attrs and not ("name" in attrs or "request" in attrs) else {}
    annotation = _TraceAnnotation(f"mt/{layer}/{name}", request=sp.request, **stats)
    annotation.__enter__()
    sp._annotation = annotation
    return sp


def finish_span(sp: Span, status: str = "ok") -> None:
    """Close a span, pop it, and deliver it to collectors + the ring."""
    global _live_spans
    if sp._annotation is not None:
        sp._annotation.__exit__(None, None, None)
        sp._annotation = None
    sp.dur_us = (time.perf_counter() - _EPOCH_PERF) * 1e6 - sp.start_us
    sp.status = status
    # only spans that incremented may decrement: a span opened before the
    # counter ring existed must not consume the count of one opened after
    if sp._counted and _COUNTERS is not None:
        with _live_lock:
            _live_spans = max(_live_spans - 1, 0)
    stack = getattr(_tls, "stack", None)
    if stack:
        if stack[-1] is sp:
            stack.pop()
        else:  # out-of-order finish (escaped generator etc.): best effort
            try:
                stack.remove(sp)
            except ValueError:
                pass
        if stack and stack[-1].span_id == sp.parent_id:
            stack[-1].child_us += sp.dur_us
    requests = getattr(_tls, "requests", None)
    if requests:
        depth = len(stack) if stack else 0
        for request in requests:
            request._on_span(sp, depth)
    _deliver(sp)


def _deliver(sp: Span) -> None:
    ring = _RING
    if ring is not None:
        ring.append(sp)
    # counter tracks feed the chrome-trace export: sampled for the config
    # switch and for profile() blocks, not for query_stats scopes alone
    counters = _COUNTERS if (_env_enabled or _collectors) else None
    if counters is not None:
        counters.append(
            (
                sp.start_us + sp.dur_us,
                _ledger_bytes()
                + (_live_spans,)
                + _cost_samples()
                + _gate_samples(),
            )
        )
    if _collectors:
        with _state_lock:
            for collector in _collectors:
                collector.append(sp)


def _ledger_bytes() -> tuple:
    """(device-resident bytes, host-cache bytes) — 0s until core.memory is
    imported (never imported from here: the ledgers import the metric
    stream, and a sampling-time import could recurse through it)."""
    memory = sys.modules.get("modin_tpu.core.memory")
    if memory is None:
        return (0, 0)
    try:
        return (memory.device_ledger.total_bytes(), memory.host_cache_bytes())
    except Exception:
        return (0, 0)


def _cost_samples() -> tuple:
    """(total padding-waste bytes, last achieved bandwidth) from graftcost —
    0s until observability.costs is imported (same no-import rule as
    :func:`_ledger_bytes`: sampling must never trigger an import chain)."""
    costs = sys.modules.get("modin_tpu.observability.costs")
    if costs is None:
        return (0, 0)
    try:
        return costs.counter_sample()
    except Exception:
        return (0, 0)


def _gate_samples() -> tuple:
    """(admission-queue depth, in-flight queries) from graftgate — 0s
    until serving.gate is imported (same no-import rule as
    :func:`_ledger_bytes`), read lock-free by design."""
    gate_mod = sys.modules.get("modin_tpu.serving.gate")
    if gate_mod is None:
        return (0, 0)
    try:
        return gate_mod.counter_sample()
    except Exception:
        return (0, 0)


def counter_samples(
    start_us: Optional[float] = None, end_us: Optional[float] = None
) -> List[tuple]:
    """Counter samples ``(ts_us, (device_bytes, host_bytes, live_spans,
    padding_waste_bytes, achieved_bw, gate_queued, gate_running))``
    currently in the ring, optionally clipped to a time window (a profile
    exports only the samples its own spans cover)."""
    counters = _COUNTERS
    if counters is None:
        return []
    out = list(counters)
    if start_us is not None:
        out = [s for s in out if s[0] >= start_us]
    if end_us is not None:
        out = [s for s in out if s[0] <= end_us]
    return out


class _SpanHandle:
    """Context manager over one open span; yields the Span for attributes."""

    __slots__ = ("_span",)

    def __init__(self, sp: Span):
        self._span = sp

    def __enter__(self) -> Span:
        return self._span

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        if exc_type is not None:
            self._span.attrs.setdefault("exc", exc_type.__name__)
            finish_span(self._span, status="error")
        else:
            finish_span(self._span)
        return False


class _NullHandle:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc_info: Any) -> bool:
        return False


_NULL_HANDLE = _NullHandle()


def span(name: str, layer: str = "APP", **attrs: Any) -> Any:
    """Open a named span as a context manager (no-op when tracing is off).

    Statically-named call sites are cross-checked against the ``SPANS``
    registry by graftlint's REGISTRY-DRIFT rule; use ``layer_span`` for
    runtime-built names (the logging decorator's per-method spans).
    """
    if not TRACE_ON:
        return _NULL_HANDLE
    return _SpanHandle(start_span(name, layer, attrs or None))


def layer_span(name: str, layer: str) -> Any:
    """``span`` variant for runtime-built names (exempt from the registry)."""
    if not TRACE_ON:
        return _NULL_HANDLE
    return _SpanHandle(start_span(name, layer, None))


# ---------------------------------------------------------------------- #
# profiles
# ---------------------------------------------------------------------- #

#: the user-facing entry layers; shared with the logging decorator's
#: is_api_layer check so the list cannot drift between the two subsystems
API_LAYERS = frozenset({"PANDAS-API", "NUMPY-API", "POLARS-API"})


class Profile:
    """The spans collected by one ``profile()`` block, plus rollups."""

    def __init__(self) -> None:
        self.spans: List[Span] = []

    # -- structure ----------------------------------------------------- #

    def tree(self) -> List[dict]:
        """Nested {span, children} dicts rooted at spans with no collected
        parent, in start order."""
        by_id: Dict[int, dict] = {
            sp.span_id: {"span": sp, "children": []} for sp in self.spans
        }
        roots: List[dict] = []
        for sp in sorted(self.spans, key=lambda s: s.start_us):
            node = by_id[sp.span_id]
            parent = by_id.get(sp.parent_id) if sp.parent_id else None
            if parent is not None:
                parent["children"].append(node)
            else:
                roots.append(node)
        return roots

    def find(self, prefix: str) -> List[Span]:
        """Collected spans whose name starts with ``prefix``."""
        return [sp for sp in self.spans if sp.name.startswith(prefix)]

    def ancestors(self, sp: Span) -> List[Span]:
        """Chain of collected ancestors of ``sp``, innermost first."""
        by_id = {s.span_id: s for s in self.spans}
        out: List[Span] = []
        cur = by_id.get(sp.parent_id) if sp.parent_id else None
        while cur is not None:
            out.append(cur)
            cur = by_id.get(cur.parent_id) if cur.parent_id else None
        return out

    # -- rollups -------------------------------------------------------- #

    def rollup(self) -> dict:
        """Host / engine-seam / compile wall-clock attribution.  All of it is
        the host's clock: device time is read from a ``jax.profiler`` trace,
        where these spans appear as ``mt/<LAYER>/<name>`` annotations.

        - ``wall_s``: summed duration of root spans (no collected parent);
        - ``engine_s``: host wall inside engine-seam attempts (dispatch,
          transfers, blocking fetches — includes any XLA compiles that
          happened there);
        - ``compile_s``: XLA compile wall time attributed to collected spans
          by the compile ledger's monitoring listener;
        - ``host_s``: everything else (``wall_s - engine_s``), the
          framework + pandas-fallback share;
        - ``by_layer_self_s``: per-layer *self* time (each span's duration
          minus its collected children's) — where the time actually went.
        """
        spans = self.spans
        by_id = {sp.span_id: sp for sp in spans}
        wall_us = sum(sp.dur_us for sp in spans if sp.parent_id not in by_id)
        engine_attempts = [
            sp
            for sp in spans
            if sp.name.startswith("engine.") and sp.name.endswith(".attempt")
        ]
        engine_us = sum(sp.dur_us for sp in engine_attempts)
        compile_s = sum(sp.attrs.get("compile_s", 0.0) for sp in spans)
        by_layer: Dict[str, float] = {}
        for sp in spans:
            self_us = max(sp.dur_us - sp.child_us, 0.0)
            by_layer[sp.layer] = by_layer.get(sp.layer, 0.0) + self_us
        return {
            "wall_s": wall_us / 1e6,
            "engine_s": engine_us / 1e6,
            "compile_s": compile_s,
            "host_s": max((wall_us - engine_us) / 1e6, 0.0),
            "spans": len(spans),
            "by_layer_self_s": {
                layer: round(us / 1e6, 6) for layer, us in sorted(by_layer.items())
            },
        }

    # -- export --------------------------------------------------------- #

    def _counter_window(self) -> List[tuple]:
        """Counter samples covered by this profile's spans."""
        if not self.spans:
            return []
        return counter_samples(
            start_us=min(sp.start_us for sp in self.spans),
            end_us=max(sp.start_us + sp.dur_us for sp in self.spans),
        )

    def to_chrome_trace(self) -> dict:
        from modin_tpu.observability.chrome_trace import to_chrome_trace

        return to_chrome_trace(
            self.spans,
            other_data={"rollup": self.rollup()},
            counters=self._counter_window(),
        )

    def export_chrome_trace(self, path: Any) -> str:
        from modin_tpu.observability.chrome_trace import export_chrome_trace

        return export_chrome_trace(
            self.spans,
            path,
            other_data={"rollup": self.rollup()},
            counters=self._counter_window(),
        )


@contextlib.contextmanager
def profile() -> Iterator[Profile]:
    """Collect every span finished while the block runs.

    Activates tracing for the duration even when ``MODIN_TPU_TRACE`` is off
    (that is the point: an ad-hoc profile without a process restart), and
    installs the XLA compile listener so compile time is attributed.
    """
    from modin_tpu.observability.compile_ledger import ensure_listener

    ensure_listener()
    prof = Profile()
    with _state_lock:
        _collectors.append(prof.spans)
    _refresh_enabled()
    try:
        yield prof
    finally:
        with _state_lock:
            try:
                _collectors.remove(prof.spans)
            except ValueError:
                pass
        _refresh_enabled()


# wire the config switches (each fires immediately with its current value)
from modin_tpu.config import (  # noqa: E402
    TraceEnabled as _TraceEnabled,
    TraceFlightRecorderSize as _TraceFlightRecorderSize,
)

_TraceEnabled.subscribe(_on_trace_param)
_TraceFlightRecorderSize.subscribe(lambda _param: _refresh_enabled())
