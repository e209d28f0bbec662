"""The streaming window loop and its algebraic recombiners.

``graftplan`` calls :func:`maybe_stream_reduce` / :func:`maybe_stream_groupby`
from the Reduce/GroupbyAgg lowerers: when the plan is a linear
``scan -> filter/map/project`` chain over ONE streamable source whose size the
residency router judges out-of-core, the chain is replayed **per window**
(the lowering memo seeded with the window's parsed compiler, so pushdown,
pruning, mask fusion and the device kernels all apply unchanged) and only
the per-window partial aggregate survives the window's release.

The loop itself (:func:`window_loop`) pipelines: a prefetch worker parses
window ``i+1``'s byte range and deploys it through the engine seam while the
caller's thread consumes window ``i`` — double-buffered against the ledger
headroom because the window size already reserves ``1 + prefetch`` slots
under the budget.  A terminal device failure inside one window replays that
window alone (``stream.window.replay``): re-parse its byte range, re-run the
chain — never the dataset.

Recombination is algebraic and exact where arithmetic is exact: sums/counts/
min/max/prod combine per partial, mean recombines as (sum, count) pairs.
Floating-point sums are mathematically identical but associate per window;
integer (and exactly-representable float) aggregations are bit-exact, which
is what the differential suite pins.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, List, Optional, Tuple

import pandas

from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import meters as graftmeter
from modin_tpu.observability import spans as graftscope
from modin_tpu.streaming import StreamDegrade, window_body
from modin_tpu.streaming import windows as _windows

#: reductions with an exact algebraic window combiner; everything else
#: (median, var, nunique, ...) stays resident.  Public names: graftview's
#: incremental maintenance (views/incremental.py) keys its append-only
#: fold sets off the SAME combinability facts — one source of truth for
#: "which aggregations recombine from partials".
REDUCE_COMBINABLE = frozenset({"sum", "prod", "min", "max", "count", "mean"})
_REDUCE_COMBINABLE = REDUCE_COMBINABLE

#: groupby aggregations with an exact partial-state combiner
GROUPBY_COMBINABLE = frozenset({"sum", "min", "max", "count", "mean"})
_GROUPBY_COMBINABLE = GROUPBY_COMBINABLE


# ---------------------------------------------------------------------- #
# plan-shape gating
# ---------------------------------------------------------------------- #


def _single_scan_chain(roots: Tuple[Any, ...]) -> Optional[Any]:
    """The ONE Scan every leaf of ``roots`` resolves to, when the interior
    is purely per-row (Project/Filter/Map) — the shape a window loop can
    replay exactly.  Anything else (a second source, a nested reduce/sort,
    a Source leaf) returns None and the resident lowering proceeds."""
    from modin_tpu.plan.ir import Filter, Map, Project, Scan, walk

    scan = None
    for root in roots:
        for node in walk(root):
            if isinstance(node, Scan):
                if scan is not None and node is not scan:
                    return None
                scan = node
            elif not isinstance(node, (Project, Filter, Map)):
                return None
    return scan


def _stream_source(node: Any, memo: dict, op_tag: str):
    """(scan, WindowSource-ready kwargs) when this materialization should
    stream, else None.  Combines the plan-shape gate, the reader
    eligibility gate, and the residency router's verdict on the sniffed
    source size."""
    from modin_tpu import streaming
    from modin_tpu.ops import router
    from modin_tpu.plan import lowering

    if not streaming.STREAM_ON:
        return None
    scan = _single_scan_chain(node.children)
    if scan is None or id(scan) in memo:
        return None
    kwargs = lowering.scan_read_kwargs(scan)
    kwargs = _windows.streamable_read_kwargs(scan.dispatcher, kwargs)
    if kwargs is None:
        return None
    try:
        est = int(scan.dispatcher.file_size(kwargs["filepath_or_buffer"]))
    except OSError:
        return None
    if router.decide_residency(op_tag, est) != "windowed":
        return None
    from modin_tpu.plan import optimizer as graftopt

    graftopt.note_stream_bytes(est)
    return scan, kwargs


# ---------------------------------------------------------------------- #
# the window loop
# ---------------------------------------------------------------------- #


def window_loop(
    source: "_windows.WindowSource",
    consume: Callable[[int, Any], None],
) -> int:
    """Run ``consume(index, window_qc)`` over every window; returns the
    window count.  ``consume`` runs on the caller's thread (inside its
    lowering/tracing context); parsing+deploy of the NEXT window overlaps
    it when ``MODIN_TPU_STREAM_PREFETCH`` > 0.  Each window is released
    (device buffers deregistered and dropped) before the next is consumed;
    a terminal device failure inside ``consume`` replays that one window.
    """
    from modin_tpu.config import StreamPrefetch

    n = len(source)
    prefetch = int(StreamPrefetch.get())
    if prefetch <= 0:
        for i in range(n):
            _consume_window(source, consume, i, source.parse_window(i))
        return n

    work: "queue.Queue" = queue.Queue(maxsize=max(prefetch, 1))
    stop = threading.Event()
    span_stack = graftscope.snapshot_stack()
    scopes = graftmeter.snapshot_scopes()

    def _prefetch() -> None:
        # the worker's deploys must bill the owner's spans/QueryStats, the
        # same cross-thread seeding the resilience watchdog uses
        graftscope.seed_thread(span_stack)
        graftmeter.seed_thread_scopes(scopes)
        try:
            for i in range(n):
                if stop.is_set():
                    return
                t0 = time.perf_counter()
                try:
                    qc = source.parse_window(i)
                except BaseException as exc:
                    # the worker must never die silently: the exception is
                    # re-raised verbatim on the consuming thread
                    work.put(("error", i, exc, 0.0))
                    return
                work.put(("ok", i, qc, time.perf_counter() - t0))
        finally:
            graftmeter.seed_thread_scopes(None)
            graftscope.seed_thread(None)

    worker = threading.Thread(
        target=_prefetch, name="graftstream-prefetch", daemon=True
    )
    worker.start()
    try:
        consumed = 0
        while consumed < n:
            w0 = time.perf_counter()
            kind, index, payload, parse_s = work.get()
            wait_s = time.perf_counter() - w0
            if kind == "error":
                from modin_tpu.core.execution.resilience import (
                    classify_device_error,
                )

                if classify_device_error(payload) is None:
                    raise payload
                # terminal device failure while PREFETCHING window `index`:
                # the worker is dead, but the byte ranges can reproduce
                # everything — replay that window and finish the remaining
                # ones serially on this thread
                emit_metric("stream.window.replay", 1)
                for j in range(index, n):
                    _consume_window(
                        source, consume, j, source.parse_window(j)
                    )
                    consumed += 1
                break
            # overlap efficiency: the share of this window's parse+deploy
            # wall that was hidden behind the previous window's kernel
            emit_metric("stream.prefetch.wait_s", wait_s)
            emit_metric(
                "stream.prefetch.overlap_s", max(parse_s - wait_s, 0.0)
            )
            _consume_window(source, consume, index, payload)
            consumed += 1
    finally:
        stop.set()
        # unblock a worker parked on a full queue, releasing any windows
        # it already deployed; a second drain AFTER the join is required —
        # the put() our first drain unblocked lands after that drain
        # already saw Empty, and its window must still hit release_qc
        for _ in range(2):
            while True:
                try:
                    item = work.get_nowait()
                except queue.Empty:
                    break
                if item[0] == "ok":
                    _windows.release_qc(item[2])
            worker.join(timeout=30.0)
    return n


def _consume_window(
    source: "_windows.WindowSource",
    consume: Callable[[int, Any], None],
    index: int,
    qc: Any,
) -> None:
    from modin_tpu.core.execution.resilience import classify_device_error

    with graftscope.span("stream.window", layer="QUERY-COMPILER", window=index):
        try:
            try:
                consume(index, qc)
            except Exception as exc:
                if classify_device_error(exc) is None:
                    raise
                # terminal device failure mid-window: one replay of THIS
                # window — re-parse its byte range, re-run the chain.  The
                # engine seam's own retry/reseat already absorbed anything
                # recoverable; reaching here means the window's buffers are
                # gone for good, and the byte range can reproduce them.
                emit_metric("stream.window.replay", 1)
                _windows.release_qc(qc)
                qc = source.parse_window(index)
                consume(index, qc)
        finally:
            _windows.release_qc(qc)
    emit_metric("stream.window.count", 1)


# ---------------------------------------------------------------------- #
# window-chain lowering helpers
# ---------------------------------------------------------------------- #


def _seed_filters(roots: Tuple[Any, ...], sub: dict) -> None:
    """Pre-lower every Filter in the window chain with bucketed host
    compaction and seed the lowering memo with the results.

    The eager filter's device compaction pads its output to the exact
    filtered row count — which varies freely between windows, so every
    window would re-trace and re-compile the whole downstream kernel
    chain.  Streaming compacts on host instead (the mask and the window's
    columns are all window-sized) and rebuilds the filtered frame at a
    power-of-two bucket: downstream programs compile once per bucket and
    re-dispatch for every later window.
    """
    from modin_tpu.plan import lowering
    from modin_tpu.plan.ir import Filter, walk

    for root in roots:
        for node in walk(root):
            if isinstance(node, Filter) and id(node) not in sub:
                child = lowering._lower(node.children[0], sub)
                mask_qc = lowering._lower(node.children[1], sub)
                sub[id(node)] = _filter_bucketed(child, mask_qc)


def _filter_bucketed(child: Any, mask_qc: Any) -> Any:
    import numpy as np

    from modin_tpu.core.dataframe.tpu.dataframe import HostColumn
    from modin_tpu.core.dataframe.tpu.metadata import LazyIndex

    frame = child._modin_frame
    mask = np.asarray(mask_qc._modin_frame._columns[0].to_numpy()).astype(bool)
    count = int(mask.sum())
    columns = []
    for col in frame._columns:
        if getattr(col, "is_category", False):
            columns.append(
                type(col).from_categorical(col.to_pandas_array()[mask])
            )
        elif getattr(col, "is_device", False):
            cache = col.host_cache
            values = np.asarray(cache) if cache is not None else col.to_numpy()
            columns.append(_windows.bucketed_column(values[mask], count))
        else:
            columns.append(HostColumn(col.data[mask]))
    lazy_index = frame._index
    new_index = LazyIndex(lambda: lazy_index.get()[mask], count)
    return type(child)(
        type(frame)(columns, frame.columns, new_index, nrows=count)
    )


# ---------------------------------------------------------------------- #
# logical-length quantization
# ---------------------------------------------------------------------- #
#
# Every device kernel is jit-keyed on the EXACT logical row count n (the
# valid-mask static), so a stream of ragged windows — and of per-window
# filtered counts — would compile a fresh program chain per window even
# with bucketed physical shapes.  Before aggregating, the window frame is
# re-padded to its power-of-two bucket with rows that are NEUTRAL for the
# aggregate (0 for sums, the column's own first value for min/max, a
# sentinel/NaN group key for groupbys, dropped again at combine time), so
# n itself is quantized and the whole downstream chain compiles once per
# bucket.  Anything the neutral-pad rules cannot cover exactly runs at the
# exact length instead — correct, just one more compile.

#: groupby sentinel for integer key columns: the dtype's minimum.  Pads
#: land in one sentinel group that the consume body drops from the partial;
#: a window whose REAL keys contain the sentinel declines quantization.


def _quantize_reduce(child: Any, method: str, skipna: bool):
    """(padded_qc, true_rows, pad_rows) with aggregation-neutral logical
    pads, or (child, n, 0) when quantization does not apply."""
    import numpy as np

    frame = child._modin_frame
    n = len(frame)
    bucket = _windows.pow2_bucket(n)
    pads = bucket - n
    exact = (child, n, 0)
    if pads <= 0:
        return exact
    columns = []
    for col in frame._columns:
        if not getattr(col, "is_device", False) or col.is_category:
            return exact  # host/object/category columns have no neutral pad
        values = _windows.host_values(col)
        kind = values.dtype.kind
        if method in ("min", "max"):
            if kind == "f":
                pad_value = np.nan if skipna else values[0] if n else None
            else:
                pad_value = values[0] if n else None
            if pad_value is None:
                return exact  # empty window: nothing neutral to repeat
        elif method == "prod":
            pad_value = 1
        else:  # sum / count / mean's sum+count decomposition
            pad_value = 0
        padded = np.concatenate(
            [values, np.full(pads, pad_value, dtype=values.dtype)]
        )
        columns.append(_windows.bucketed_column(padded, bucket))
    from modin_tpu.core.dataframe.tpu.metadata import LazyIndex

    import pandas as _pd

    new_frame = type(frame)(
        columns, frame.columns, LazyIndex(_pd.RangeIndex(bucket), bucket),
        nrows=bucket,
    )
    return type(child)(new_frame), n, pads


def _quantize_groupby(child: Any, by: Any, dropna: bool):
    """(padded_qc, sentinel_by_label) for a label-keyed groupby, or
    (child, None) when quantization does not apply.  Pad rows carry a
    sentinel key (int dtype minimum, or NaN for float keys under dropna)
    grouping them into one droppable bucket; value columns pad with 0."""
    import numpy as np

    if isinstance(by, str):
        by = [by]
    if not isinstance(by, (list, tuple)) or not all(
        isinstance(b, str) for b in by
    ):
        return child, None
    frame = child._modin_frame
    n = len(frame)
    bucket = _windows.pow2_bucket(n)
    pads = bucket - n
    exact = (child, None)
    if pads <= 0:
        return exact
    labels = list(frame.columns)
    by_set = set(by)
    if not by_set <= set(labels):
        return exact
    sentinels: dict = {}
    columns = []
    for label, col in zip(labels, frame._columns):
        if not getattr(col, "is_device", False) or col.is_category:
            return exact
        values = _windows.host_values(col)
        kind = values.dtype.kind
        if label in by_set:
            if kind in "iu":
                sentinel = np.iinfo(values.dtype).min
                if n and (values == sentinel).any():
                    return exact  # real data collides with the sentinel
                sentinels[label] = sentinel
                pad_value = sentinel
            elif kind == "f" and dropna:
                pad_value = np.nan  # dropped by the groupby itself
            else:
                return exact  # bool / non-dropna-float keys: no safe pad
        else:
            pad_value = 0 if kind != "f" else 0.0
        padded = np.concatenate(
            [values, np.full(pads, pad_value, dtype=values.dtype)]
        )
        columns.append(_windows.bucketed_column(padded, bucket))
    from modin_tpu.core.dataframe.tpu.metadata import LazyIndex

    import pandas as _pd

    new_frame = type(frame)(
        columns, frame.columns, LazyIndex(_pd.RangeIndex(bucket), bucket),
        nrows=bucket,
    )
    return type(child)(new_frame), (by, sentinels)


def _drop_sentinel_groups(partial: pandas.DataFrame, spec) -> pandas.DataFrame:
    """Remove the quantization pads' sentinel group from a partial table.
    Pad rows carry the sentinel in EVERY integer key level (and NaN in
    float levels, which a dropna groupby never emits), so filtering any
    one sentinel level removes exactly the pad group."""
    by, sentinels = spec
    if not sentinels:
        return partial  # float-NaN pads: the groupby already dropped them
    label, sentinel = next(iter(sentinels.items()))
    index = partial.index
    if isinstance(index, pandas.MultiIndex):
        level_values = index.get_level_values(label)
    else:
        level_values = index
    return partial[level_values != sentinel]




# ---------------------------------------------------------------------- #
# streaming reduce
# ---------------------------------------------------------------------- #


def maybe_stream_reduce(node: Any, memo: dict) -> Optional[Any]:
    """A windowed lowering of one Reduce root, or None for resident."""
    matched = _stream_source(node, memo, "scan_reduce")
    if matched is None:
        return None
    method = node.method
    if method not in _REDUCE_COMBINABLE:
        return None
    ck = dict(node.call_kwargs)
    if ck.get("axis", 0) not in (0, None):
        return None
    if ck.get("min_count", 0) not in (0, -1):
        return None  # a real min_count needs whole-column valid counts
    if any(
        k not in ("axis", "skipna", "numeric_only", "min_count") for k in ck
    ):
        return None  # ddof / ... have no window combiner here
    scan, kwargs = matched
    skipna = bool(ck.get("skipna", True))
    numeric_only = ck.get("numeric_only", False)
    source = _make_source(scan, kwargs)
    if len(source) == 0:
        return None  # empty body: the resident parse answers exactly

    from modin_tpu.plan import lowering

    # partial state is keyed by WINDOW INDEX, never appended: a terminal
    # device failure can replay one window's consume after it already
    # recorded some of its partials, and a replay must overwrite, not
    # double-count (the single-window-replay bit-exactness contract)
    sums: dict = {}
    counts: dict = {}
    partials: dict = {}
    hint: List[Any] = [None]
    template_holder: List[Any] = [None]

    # graftfuse window body: the window's filter/map chain and its
    # reduction as ONE masked program — no host mask compaction, no
    # logical-length quantization (n rides as a runtime scalar), so every
    # same-bucket window re-dispatches one cached executable.  The
    # stream-invariant gates/signature are computed ONCE here; per window
    # the plan answers None (no filter, staged-routed stream, zero kept
    # rows, exotic dtypes) to keep the staged quantized body.
    from modin_tpu.plan import fuse as _fuse

    fused_run = (
        _fuse.window_reduce_plan(node, scan, ck)
        if _fuse.FUSE_ON and method != "mean"
        else None
    )

    @window_body
    def consume(index: int, qc: Any) -> None:
        if fused_run is not None:
            fused = fused_run(qc)
            if fused is not None:
                partials[index] = _one_column(fused.to_pandas())
                if hint[0] is None:
                    hint[0] = "column"
                return
        sub = {id(scan): qc}
        _seed_filters(node.children, sub)
        child = lowering._lower(node.children[0], sub)
        if method == "mean":
            if index == 0:
                # window-0 probe: the eager mean's column SELECTION (and
                # its TypeError on non-numeric frames) is authoritative —
                # sum/count select differently on object columns, so the
                # (sum, count) recombination is restricted to the labels
                # the resident mean would have answered for
                template_holder[0] = child.mean(**ck).to_pandas()
            selection = template_holder[0].index
            q, true_n, pads = _quantize_reduce(child, "sum", skipna)
            part = q.sum(axis=0, skipna=skipna, numeric_only=numeric_only)
            sums[index] = part.to_pandas().loc[selection]
            if skipna:
                counts[index] = (
                    q.count(axis=0, numeric_only=numeric_only)
                    .to_pandas()
                    .loc[selection]
                    - pads  # the 0-pads count as valid rows: bill them out
                )
            else:
                counts[index] = true_n
        elif method == "count":
            q, _true_n, pads = _quantize_reduce(child, method, skipna)
            part = getattr(q, method)(**ck)
            partials[index] = _one_column(part.to_pandas()) - pads
        else:
            q, _true_n, _pads = _quantize_reduce(child, method, skipna)
            part = getattr(q, method)(**ck)
            partials[index] = _one_column(part.to_pandas())
        if hint[0] is None:
            hint[0] = getattr(part, "_shape_hint", None) or "column"

    try:
        window_loop(source, consume)
    except StreamDegrade:
        emit_metric("stream.degrade", 1)
        return None

    if method == "mean":
        total = _stack_combine(
            [sums[i].iloc[:, 0] for i in sorted(sums)], "sum", False
        )
        if skipna:
            denom = _stack_combine(
                [counts[i].iloc[:, 0] for i in sorted(counts)], "sum", False
            )
        else:
            denom = pandas.Series(sum(counts.values()), index=total.index)
        combined = total / denom
        template = template_holder[0]
    else:
        series = [partials[i].iloc[:, 0] for i in sorted(partials)]
        if method in ("sum", "count"):
            combined = _stack_combine(series, "sum", False)
        elif method == "prod":
            combined = _stack_combine(series, "prod", False)
        else:  # min / max: a window can be legitimately all-NaN
            combined = _stack_combine(series, method, skipna)
        template = partials[min(partials)]
    final = combined.to_frame(name=template.columns[0])
    final.index = template.index
    return _wrap_result(scan, final, hint[0])


def _one_column(partial: pandas.DataFrame) -> pandas.DataFrame:
    """A reduce partial must be the expected one-column (Series-shaped)
    frame; anything else (an exotic numeric_only selection answering zero
    columns) degrades to the resident path instead of mis-combining."""
    if partial.shape[1] != 1:
        raise StreamDegrade(
            f"reduce partial has {partial.shape[1]} columns, expected 1"
        )
    return partial


def _stack_combine(series: List[pandas.Series], op: str, skipna: bool):
    """Elementwise window combine: identical-index partials side by side,
    reduced across windows.  ``skipna=False`` for the additive ops keeps a
    genuinely-NaN partial (a skipna=False query) poisoning the total, while
    skipna-of-the-query for min/max lets an all-NaN window drop out."""
    wide = pandas.concat(series, axis=1)
    return getattr(wide, op)(axis=1, skipna=skipna)


def _make_source(scan: Any, kwargs: dict) -> "_windows.WindowSource":
    from modin_tpu.config import StreamPrefetch

    return _windows.WindowSource(
        scan.dispatcher,
        kwargs,
        _windows.window_bytes_for(int(StreamPrefetch.get())),
    )


def _wrap_result(scan: Any, final: pandas.DataFrame, hint: Any) -> Any:
    qc = scan.dispatcher.query_compiler_cls.from_pandas(
        final, scan.dispatcher.frame_cls
    )
    if hint is not None:
        qc._shape_hint = hint
    return qc


# ---------------------------------------------------------------------- #
# streaming groupby
# ---------------------------------------------------------------------- #


def maybe_stream_groupby(node: Any, memo: dict) -> Optional[Any]:
    """A windowed lowering of one GroupbyAgg root, or None for resident.

    The per-window aggregate goes into a host partial-state table keyed by
    group; crossing ``MODIN_TPU_STREAM_MAX_GROUPS`` distinct groups raises
    :class:`StreamDegrade` (caught here -> ``stream.degrade`` -> resident
    path, whose high-cardinality groupby is the range_shuffle)."""
    matched = _stream_source(node, memo, "scan_groupby")
    if matched is None:
        return None
    agg = node.agg_func
    if not isinstance(agg, str) or agg not in _GROUPBY_COMBINABLE:
        return None
    ck = dict(node.call_kwargs)
    if ck.get("axis", 0) != 0 or ck.get("how", "axis_wise") != "axis_wise":
        return None
    if ck.get("agg_args"):
        return None
    agg_kwargs = dict(ck.get("agg_kwargs") or {})
    if agg_kwargs.pop("min_count", 0) not in (0, -1):
        return None  # a real min_count needs per-group valid counts
    if any(k != "numeric_only" for k in agg_kwargs):
        return None
    gk = dict(ck.get("groupby_kwargs") or {})
    if gk.get("as_index", True) is not True or gk.get("level") is not None:
        return None
    sort = bool(gk.get("sort", True))
    dropna = bool(gk.get("dropna", True))
    scan, kwargs = matched
    source = _make_source(scan, kwargs)
    if len(source) == 0:
        return None

    from modin_tpu.config import StreamMaxGroups
    from modin_tpu.plan import lowering
    from modin_tpu.plan.ir import Ref

    max_groups = int(StreamMaxGroups.get())
    # keyed by window index (a replayed window overwrites, never doubles)
    partials: dict = {}
    count_partials: dict = {}
    seen_groups: set = set()
    hint: List[Any] = [None]

    def _note_groups(index: pandas.Index) -> None:
        seen_groups.update(index)
        if len(seen_groups) > max_groups:
            raise StreamDegrade(
                f"streaming groupby crossed MODIN_TPU_STREAM_MAX_GROUPS="
                f"{max_groups} distinct groups"
            )

    mean_cols: List[Any] = [None]

    @window_body
    def consume(index: int, qc: Any) -> None:
        sub = {id(scan): qc}
        _seed_filters(node.children, sub)
        child = lowering._lower(node.children[0], sub)
        by = node.by
        if isinstance(by, Ref):
            by = lowering._lower(node.children[by.index], sub)
            spec = None
        else:
            child, spec = _quantize_groupby(child, by, dropna)

        def run(f, kw=ck):
            part = child.groupby_agg(by, f, **kw)
            part_pd = part.to_pandas()
            if spec is not None:
                part_pd = _drop_sentinel_groups(part_pd, spec)
            return part, part_pd

        if agg == "mean":
            if index == 0:
                # window-0 probe: the eager mean's column selection (and
                # its raising behavior on non-numeric frames) governs
                # which labels the (sum, count) recombination answers for
                mean_cols[0] = run("mean")[1].columns
            part, part_pd = run("sum")
            part_pd = part_pd[mean_cols[0]]
            partials[index] = part_pd
            cck = dict(ck)
            cck["agg_kwargs"] = {}  # groupby count takes no numeric_only
            count_partials[index] = run("count", cck)[1][mean_cols[0]]
        else:
            part, part_pd = run(agg)
            partials[index] = part_pd
        if hint[0] is None:
            hint[0] = getattr(part, "_shape_hint", None)
        _note_groups(part_pd.index)

    try:
        window_loop(source, consume)
    except StreamDegrade:
        emit_metric("stream.degrade", 1)
        return None

    combiner = "sum" if agg in ("sum", "count", "mean") else agg
    ordered = [partials[i] for i in sorted(partials)]
    final = _group_combine(ordered, combiner, sort, dropna)
    if agg == "mean":
        denom = _group_combine(
            [count_partials[i] for i in sorted(count_partials)],
            "sum",
            sort,
            dropna,
        )
        final = final / denom
    return _wrap_result(scan, final, hint[0])


def _group_combine(
    partials: List[pandas.DataFrame], op: str, sort: bool, dropna: bool
) -> pandas.DataFrame:
    """Fold per-window group tables: stack (window order preserves global
    first-appearance order for sort=False) and re-group by the full key."""
    stacked = pandas.concat(partials)
    levels = list(range(stacked.index.nlevels))
    grouped = stacked.groupby(level=levels, sort=sort, dropna=dropna)
    return getattr(grouped, op)()
