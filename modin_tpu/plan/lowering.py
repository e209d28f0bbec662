"""graftplan lowering: optimized plan -> eager query compiler.

Every node lowers through the seam the eager mode already uses — scans call
the format dispatcher's ``read`` (io lineage, spans, file-leak tracking all
intact), maps call the eager QC methods (whose device paths build deferred
``LazyExpr`` columns), filters ride ``getitem_array``'s mask-fusing gather,
and reductions consume the lazy columns through ``run_fused``'s tail — so
resilience retry/backoff, graftguard lineage recovery, and the device-memory
ledger see planned execution exactly as they see eager execution.

The walk memoizes per node id: a subtree shared between the filter mask and
the main spine (or merged by CSE) is computed ONCE — the "one scan" half of
the acceptance shape is structural, not an optimization.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Tuple

from modin_tpu.concurrency import named_lock
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import costs as graftcost
from modin_tpu.observability import meters as graftmeter
from modin_tpu.observability import spans as graftscope
from modin_tpu.serving import context as serving_context
from modin_tpu.plan import optimizer
from modin_tpu.plan.ir import (
    Filter,
    GroupbyAgg,
    Map,
    PlanNode,
    Project,
    Reduce,
    Ref,
    Scan,
    Sort,
    Source,
    count_nodes,
)

_tls = threading.local()


def _scan_cache_budget() -> int:
    """Byte bound on each origin's materialized-read cache.

    Entries are (compiler, measured bytes) per distinct projection,
    FIFO-evicted coldest-first once the measured total crosses
    ``MODIN_TPU_PLAN_SCAN_CACHE_BYTES`` — a count bound alone let four
    out-of-core-sized reads pin a multi-GB host/device leak.  0 disables
    caching entirely.
    """
    from modin_tpu.config import PlanScanCacheBytes

    return int(PlanScanCacheBytes.get())

#: One lock for every origin's read cache: concurrent queries (graftgate)
#: can force plans sharing a Scan origin from several threads, and an
#: unguarded dict iteration racing the FIFO eviction is torn state.  The
#: physical read itself happens OUTSIDE the lock (a slow parse must not
#: serialize every other query's scan); the worst case is a duplicate
#: parse, never a corrupt cache.
_SCAN_CACHE_LOCK = named_lock("plan.scan_cache")


def in_lowering() -> bool:
    """Whether a lowering pass is running on this thread.

    The Force-mode deferral guards consult this: lowering replays plan
    nodes through the same guarded eager methods, and re-entering planning
    there would wrap Source nodes forever.
    """
    return getattr(_tls, "lowering", False)


def lower(root: PlanNode) -> Any:
    """Lower an (optimized) plan to an eager query compiler."""
    return lower_traced(root)[0]


def lower_traced(
    root: PlanNode,
    instrument: Optional[Dict[int, dict]] = None,
    strategies: Any = None,
) -> Tuple[Any, Dict[int, Any]]:
    """Lower a plan; also returns the node-id -> lowered-compiler memo
    (the materialization path uses it to adopt a reduction's input).

    ``instrument`` (EXPLAIN ANALYZE) is a dict filled in place with one
    entry per lowered node id: measured total/self wall seconds, engine
    dispatches attributed to the node, and the lowered result's rows/bytes.
    Shared (memoized) subtrees bill their cost to the first consumer, which
    is also how the work actually happened.

    ``strategies`` (a graftopt :class:`~..optimizer.PlanStrategies`) arms
    the adaptive loop for this pass: each node's wall is measured (cheap
    perf_counter pair, no dispatch attribution) and fed back through
    ``optimizer.observe`` so estimate divergence can re-plan the remaining
    segment mid-query.  None (``MODIN_TPU_OPT=Off``) keeps the historical
    fast path untouched.
    """
    memo: Dict[int, Any] = {}
    was_lowering = in_lowering()
    _tls.lowering = True
    if instrument is not None:
        _tls.instrument = instrument
        _tls.inst_stack = []
    if strategies is not None:
        optimizer.begin(strategies, root, memo)
        _tls.opt_active = True
    try:
        with graftscope.span(
            "plan.lower", layer="PLAN", nodes=count_nodes(root)
        ):
            result = _lower(root, memo)
    finally:
        _tls.lowering = was_lowering
        if instrument is not None:
            _tls.instrument = None
            _tls.inst_stack = None
        if strategies is not None:
            _tls.opt_active = False
            optimizer.end()
    emit_metric("plan.lower.nodes", len(memo))
    return result, memo


def _lower(node: PlanNode, memo: Dict[int, Any]) -> Any:
    hit = memo.get(id(node))
    if hit is not None:
        return hit
    if serving_context.CONTEXT_ON:
        # graftgate deadline boundary: between plan nodes is the cheapest
        # safe place to abort a deferred query — nothing is half-lowered
        serving_context.check_deadline("plan.lower")
    instrument = getattr(_tls, "instrument", None)
    if instrument is None:
        if not getattr(_tls, "opt_active", False):
            return _lower_node(node, memo)
        # graftopt adaptive path: the cheapest timing that can still catch
        # estimate divergence — one perf_counter pair per node, observed
        # AFTER the node scope pops so a re-plan runs over a consistent
        # done-set (this node already in the memo)
        optimizer.push_node(node)
        t0 = time.perf_counter()
        try:
            result = _lower_node(node, memo)
        finally:
            optimizer.pop_node()
        optimizer.observe(node, time.perf_counter() - t0)
        return result
    # EXPLAIN ANALYZE: time the node's lowering and attribute engine
    # dispatches; parent frames accumulate child totals so self = total -
    # children even though each lowerer recurses internally
    opt_active = getattr(_tls, "opt_active", False)
    if opt_active:
        optimizer.push_node(node)
    stack = _tls.inst_stack
    frame = {"child_s": 0.0, "child_disp": 0}
    stack.append(frame)
    t0 = time.perf_counter()
    d0 = graftmeter.thread_dispatches()
    # one COST_ON read: a concurrent toggle must not leave c0 set with p0
    # None (the epilogue derives both or neither)
    cost_on = graftcost.COST_ON
    c0 = graftcost.thread_cost() if cost_on else None
    p0 = graftcost.thread_padding() if cost_on else None
    try:
        result = _lower_node(node, memo)
    finally:
        stack.pop()
        if opt_active:
            optimizer.pop_node()
        total_s = time.perf_counter() - t0
        total_disp = graftmeter.thread_dispatches() - d0
        if stack:
            parent = stack[-1]
            parent["child_s"] += total_s
            parent["child_disp"] += total_disp
    if opt_active:
        optimizer.observe(node, total_s)
    entry = {
        "total_s": total_s,
        "self_s": max(total_s - frame["child_s"], 0.0),
        "dispatches": max(total_disp - frame["child_disp"], 0),
        "total_dispatches": total_disp,
        "rows": _result_rows(result),
        "bytes": _result_bytes(result),
    }
    if c0 is not None:
        # graftcost joins: estimated flops/bytes billed while lowering this
        # node (subtree totals, like total_s — a shared subtree bills its
        # first consumer), padding observed, and the roofline fraction at
        # the node's own measured wall
        c1 = graftcost.thread_cost()
        p1 = graftcost.thread_padding()
        entry["est_flops"] = c1[0] - c0[0]
        entry["est_bytes"] = c1[1] - c0[1]
        entry["padded_bytes"] = p1[0] - p0[0]
        entry["padding_waste_bytes"] = p1[1] - p0[1]
    instrument[id(node)] = entry
    return result


def _result_rows(qc: Any) -> Optional[int]:
    """Row count of a lowered compiler, without forcing anything."""
    try:
        frame = qc._frame
        return len(frame) if frame is not None else None
    except Exception:
        return None


def _result_bytes(qc: Any) -> Optional[int]:
    """Concrete bytes held by a lowered compiler's columns (device buffers
    plus host arrays; deferred/lazy columns are skipped, never forced)."""
    try:
        frame = qc._frame
        if frame is None:
            return None
        total = 0
        for col in frame._columns:
            if getattr(col, "is_device", False):
                if col.is_lazy or col._data is None:
                    continue
                total += int(getattr(col._data, "nbytes", 0) or 0)
            else:
                total += int(getattr(col.data, "nbytes", 0) or 0)
        return total
    except Exception:
        return None


def _lower_node(node: PlanNode, memo: Dict[int, Any]) -> Any:
    try:
        result = _LOWERERS[type(node)](node, memo)
    except Exception as exc:
        # deferral moves eager-mode errors (e.g. `df["s"] > 3` on a string
        # column) from the call site to the materialization point; name the
        # failing node so the traceback points back at the logical op
        if (
            not getattr(exc, "_graftplan_node", None)
            and exc.args
            and isinstance(exc.args[0], str)
        ):
            exc._graftplan_node = node.label()
            exc.args = (
                f"{exc.args[0]} [while materializing deferred plan node "
                f"{node.label()}]",
            ) + exc.args[1:]
        raise
    memo[id(node)] = result
    return result


def _lower_scan(node: Scan, memo: Dict[int, Any]) -> Any:
    origin = node.origin
    need = (
        tuple(node.columns)
        if node.pushed and node.pruned is not None
        else None
    )
    # serve from a prior materialization of this source when it covers the
    # need: a scan shared by several plans (or re-forced after a reduction)
    # must not re-parse the file per force()
    hit = None
    with _SCAN_CACHE_LOCK:
        for key, cached in (origin.cache or {}).items():
            if key is None and need is None:
                hit = cached[0]
                break
            if need is not None and (key is None or set(need) <= set(key)):
                hit = cached[0]
                break
    if hit is not None:
        emit_metric("plan.scan.cache_hit", 1)
        return hit if need is None else hit.getitem_column_array(list(need))
    kwargs = scan_read_kwargs(node)
    if need is not None:
        emit_metric(
            "plan.scan.pruned_columns", len(node.all_columns) - len(node.pruned)
        )
    qc = node.dispatcher.read(**kwargs)
    budget = _scan_cache_budget()
    if origin.cache is not None and budget > 0:
        nbytes = _result_bytes(qc) or 0
        evicted = 0
        with _SCAN_CACHE_LOCK:
            origin.cache[need] = (qc, nbytes)
            total = sum(b for _qc, b in origin.cache.values())
            while total > budget and origin.cache:
                oldest = next(iter(origin.cache))
                _dropped, dropped_bytes = origin.cache.pop(oldest)
                total -= dropped_bytes
                evicted += 1
        for _ in range(evicted):
            emit_metric("plan.scan.cache_evict", 1)
    return qc


def scan_read_kwargs(node: Scan) -> dict:
    """The reader kwargs for a scan, with the pushed projection merged in."""
    kwargs = dict(node.read_kwargs)
    if node.pushed and node.pruned is not None:
        keep = [c for c in node.all_columns if c in set(node.pruned)]
        kwargs[node.colarg] = keep
        dtype = kwargs.get("dtype")
        if isinstance(dtype, dict):
            # per-column dtype entries for never-parsed columns would make
            # some parsers complain; the surviving subset is all that matters
            kwargs["dtype"] = {k: v for k, v in dtype.items() if k in set(keep)}
    return kwargs


def _lower_source(node: Source, memo: Dict[int, Any]) -> Any:
    return node.qc


def _lower_project(node: Project, memo: Dict[int, Any]) -> Any:
    child = _lower(node.children[0], memo)
    qc = child.getitem_column_array(list(node.keys), numeric=node.numeric)
    if node.out_hint is not None:
        qc._shape_hint = node.out_hint
    return qc


def _lower_filter(node: Filter, memo: Dict[int, Any]) -> Any:
    child = _lower(node.children[0], memo)
    mask = _lower(node.children[1], memo)
    return child.getitem_array(mask)


def _lower_map(node: Map, memo: Dict[int, Any]) -> Any:
    receiver = _lower(node.children[0], memo)
    args = tuple(
        _lower(node.children[a.index], memo) if isinstance(a, Ref) else a
        for a in node.args
    )
    qc = getattr(receiver, node.method)(*args, **node.kwargs)
    if node.out_hint is not None:
        qc._shape_hint = node.out_hint
    return qc


def _lower_reduce(node: Reduce, memo: Dict[int, Any]) -> Any:
    streamed = _maybe_stream(node, memo, groupby=False)
    if streamed is not None:
        return streamed
    fused = _maybe_fuse(node, memo, groupby=False)
    if fused is not None:
        return fused
    child = _lower(node.children[0], memo)
    return getattr(child, node.method)(**node.call_kwargs)


def _lower_groupby(node: GroupbyAgg, memo: Dict[int, Any]) -> Any:
    streamed = _maybe_stream(node, memo, groupby=True)
    if streamed is not None:
        return streamed
    fused = _maybe_fuse(node, memo, groupby=True)
    if fused is not None:
        return fused
    child = _lower(node.children[0], memo)
    by = node.by
    if isinstance(by, Ref):
        by = _lower(node.children[by.index], memo)
    return child.groupby_agg(by, node.agg_func, **node.call_kwargs)


def _maybe_stream(node: PlanNode, memo: Dict[int, Any], groupby: bool) -> Any:
    """graftstream residency hook: lower a Reduce/GroupbyAgg root through
    the windowed out-of-core executor when the chain below it is one
    streamable scan whose size the residency router judges out-of-core.
    One attribute read while streaming is off (the default)."""
    from modin_tpu import streaming

    if not streaming.STREAM_ON:
        return None
    if groupby:
        return streaming.maybe_stream_groupby(node, memo)
    return streaming.maybe_stream_reduce(node, memo)


def _maybe_fuse(node: PlanNode, memo: Dict[int, Any], groupby: bool) -> Any:
    """graftfuse whole-plan hook: compile the entire post-scan segment
    (filter/map/project chain + this reduce/groupby tail) into ONE donated
    program when the segment shape supports it and the compile router says
    the frame is big enough to pay for the trace (plan/fuse.py).  One
    attribute read while MODIN_TPU_FUSE=Staged."""
    from modin_tpu.plan import fuse

    if not fuse.FUSE_ON:
        return None
    if groupby:
        return fuse.maybe_fuse_groupby(node, memo)
    return fuse.maybe_fuse_reduce(node, memo)


def _lower_sort(node: Sort, memo: Dict[int, Any]) -> Any:
    child = _lower(node.children[0], memo)
    return child.sort_rows_by_column_values(
        node.sort_columns, node.ascending, **node.call_kwargs
    )


_LOWERERS = {
    Scan: _lower_scan,
    Source: _lower_source,
    Project: _lower_project,
    Filter: _lower_filter,
    Map: _lower_map,
    Reduce: _lower_reduce,
    GroupbyAgg: _lower_groupby,
    Sort: _lower_sort,
}
