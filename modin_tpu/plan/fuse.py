"""graftfuse: whole-plan XLA compilation — one donated, bucket-padded
program per query segment.

graftplan's staged lowering replays each plan node through the eager
seams: a ``read_csv(...).query(...)[cols].agg(...)`` pipeline pays one
dispatch for the mask-fused filter compaction (plus a host sync for the
kept-row count) and a second for the trim-fused reduction.  This module
compiles the ENTIRE post-scan segment — the filter/map/project chain AND
its reduce or groupby_agg tail — into ONE jitted XLA program:

- **no compaction**: the filter's keep mask stays a deferred expression
  and the reduction applies it in place (``ops/reductions.reduce_columns_
  masked``); the kept values are the same values a stable compaction would
  have gathered, in the same order, so results match the staged path.
  The logical length rides as a *runtime scalar*, so one executable serves
  every logical length at a physical size.
- **buffer donation**: every input column the device ledger proves has no
  other live consumer (``_DeviceLedger.buffer_consumers == 1``) and that
  can be rebuilt from lineage (exact host copy) is passed in a donated jit
  position — XLA reuses its HBM for the program's intermediates, and the
  column itself becomes *spilled*: the next read restores via lineage
  instead of touching the consumed buffer (the use-after-donate guard).
- **adaptive padding buckets**: fused programs re-specialize per physical
  input size, so a stream of near-miss sizes against one plan signature is
  a recompile storm.  Instead of fixed pow2 steps, the bucket escalates
  from the compile ledger's storm feedback: exact padding until a
  signature proves it storms, then eighth-octave buckets, then pow2
  (:func:`quantize_padded`), applied to the scan's uploads through
  ``ops/structural.pad_bucket_scope``.
- **routing**: ``ops/router.decide_compile`` keeps tiny frames on the
  staged path (trace+compile cost beats one saved dispatch);
  ``MODIN_TPU_FUSE`` pins Auto/Staged/Fused.

The fused program dispatches through ``run_fused`` -> ``JaxWrapper.deploy``
like every other device computation, so resilience retry/rebind, graftcost
capture, and graftmeter accounting see it unchanged; plain ``jnp`` bodies
SPMD-partition over the graftmesh substrate exactly as the staged kernels
do, and the fused cache key carries the mesh shape + device epoch so a
reshape or re-seat never reuses a stale executable.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pandas

from modin_tpu.concurrency import named_lock
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import spans as graftscope
from modin_tpu.observability.compile_ledger import (
    compiles_on_this_thread,
    get_compile_ledger,
)
from modin_tpu.plan.ir import (
    Filter,
    GroupbyAgg,
    Map,
    PlanNode,
    Project,
    Reduce,
    Ref,
    Scan,
    Source,
    walk,
)
from modin_tpu.serving import context as serving_context

#: mirrored from ``MODIN_TPU_FUSE`` (Staged -> False): ONE module-attr read
#: on the lowering hot path when fusion is pinned off
FUSE_ON: bool = True

#: reductions the masked whole-plan tail expresses exactly (median needs a
#: data-dependent selection; nunique/mode are the sort-shaped family)
SUPPORTED_REDUCE = frozenset(
    {
        "sum", "prod", "mean", "min", "max", "count", "var", "std", "sem",
        "skew", "kurt", "any", "all",
    }
)

#: Map methods the masked walk may replay: the deferral layer only builds
#: Map nodes from these (defer_binary's op table + defer_unary's catalog),
#: and each stays a deferred LazyExpr on device frames
_SUPPORTED_MAP_METHODS = frozenset(
    {
        "add", "radd", "sub", "rsub", "mul", "rmul", "truediv", "rtruediv",
        "floordiv", "rfloordiv", "mod", "rmod", "pow", "rpow",
        "eq", "ne", "lt", "le", "gt", "ge",
        "__and__", "__or__", "__xor__", "__rand__", "__ror__", "__rxor__",
        "abs", "negative", "invert", "isna", "notna",
    }
)


class _Decline(Exception):
    """This segment cannot fuse; the staged lowering proceeds."""


# ---------------------------------------------------------------------- #
# mode flag
# ---------------------------------------------------------------------- #


def _on_fuse_mode(param: Any) -> None:
    global FUSE_ON
    FUSE_ON = param.get().lower() != "staged"


from modin_tpu.config import FuseMode as _FuseMode  # noqa: E402

_FuseMode.subscribe(_on_fuse_mode)


# ---------------------------------------------------------------------- #
# adaptive padding buckets (compile-ledger storm feedback)
# ---------------------------------------------------------------------- #

#: below this padded length buckets never apply: tiny frames compile in
#: microseconds and unit tests stay byte-for-byte at exact padding
_BUCKET_FLOOR = 1024

#: own-compile thresholds for escalating a signature's bucket level
_STORM_COMPILES = 3

#: bound on tracked signatures: Map payloads embed literal scalar operands
#: (``df.query("a > X")`` with a per-request constant is a fresh signature
#: each time), so the registry is LRU-capped like every other per-key
#: registry in this repo (tenants, scan cache, _FUSED_CACHE) — evicting a
#: cold signature merely restarts its storm counter at exact padding
_MAX_STORM_SIGS = 512

_storm_lock = named_lock("plan.storm")
#: plan signature -> [backend compiles observed during its fused
#: dispatches, {distinct physical input sizes dispatched}]; LRU order
_sig_state: "OrderedDict[Any, list]" = OrderedDict()


def note_fused_compiles(sig: Any, p: int, compiles: int) -> None:
    """Record one fused dispatch's compile delta for ``sig`` at physical
    size ``p`` (the adaptive bucket chooser's own feedback channel)."""
    with _storm_lock:
        state = _sig_state.get(sig)
        if state is None:
            state = _sig_state[sig] = [0, set()]
        else:
            _sig_state.move_to_end(sig)
        state[0] += int(compiles)
        state[1].add(int(p))
        while len(_sig_state) > _MAX_STORM_SIGS:
            _sig_state.popitem(last=False)


def storm_level(sig: Any) -> int:
    """0 = exact padding, 1 = eighth-octave buckets, 2 = pow2 buckets.

    Escalates on the signature's OWN compile count, cross-checked against
    the compile ledger: when the ledger reports the fused span signature
    (``fuse.lower``) as a recompile storm AND this signature itself has
    re-compiled across at least two distinct physical sizes, it escalates
    early.  The per-sig churn requirement matters: every fused lowering
    bills its compiles to the ONE ``fuse.lower`` ledger entry, so three
    unrelated plans cold-compiling once each would otherwise read as a
    storm and start padding healthy workloads.
    """
    with _storm_lock:
        state = _sig_state.get(sig)
        own = state[0] if state else 0
        shapes = len(state[1]) if state else 0
    if own >= 3 * _STORM_COMPILES:
        return 2
    if own >= _STORM_COMPILES:
        return 1
    if shapes >= 2 and own >= 2:
        storms = get_compile_ledger().recompile_storms(_STORM_COMPILES)
        if "fuse.lower" in storms:
            return 1
    return 0


def reset_storm_state() -> None:
    """Forget all storm bookkeeping (tests)."""
    with _storm_lock:
        _sig_state.clear()


def quantize_padded(p: int, level: int) -> int:
    """Bucketed padded length for one physical size at a storm level."""
    p = int(p)
    if level <= 0 or p < _BUCKET_FLOOR:
        return p
    pow2 = 1 << max(p - 1, 1).bit_length()  # smallest pow2 >= p
    if level >= 2:
        return pow2
    step = max(pow2 // 8, 1)  # eighth-octave: <= 12.5% pad waste
    return ((p + step - 1) // step) * step


def _quantizer_for(sig: Any):
    """The ``pad_bucket_scope`` quantizer for this signature, or None while
    the signature has not stormed (exact padding, zero waste)."""
    level = storm_level(sig)
    if level <= 0:
        return None

    def quantize(p: int) -> int:
        q = quantize_padded(p, level)
        if q > p:
            emit_metric("fuse.bucket.quantized", q - p)
        return q

    return quantize


def stream_bucket(m: int) -> int:
    """graftstream hook: double the window row bucket while the fused
    window programs themselves storm (all windows share one signature), so
    a stream of near-boundary ragged windows collapses onto fewer
    executables instead of compiling per pow2 neighbor."""
    return m * 2 if storm_level("stream.window") else m


def segment_signature(root: PlanNode) -> Tuple:
    """Stable (cross-query) identity of a plan segment: node kinds and
    payloads, leaf identities erased.  Keys the storm bookkeeping and the
    ``decide_compile`` span attribution."""
    return tuple(
        (node.kind, () if isinstance(node, (Scan, Source)) else node.payload_key())
        for node in walk(root)
    )


# ---------------------------------------------------------------------- #
# segment extraction + the masked chain walk
# ---------------------------------------------------------------------- #


def _segment_leaf(root: PlanNode) -> Optional[PlanNode]:
    """The ONE Scan/Source leaf under a pure Project/Filter/Map interior
    (the root itself excepted), or None when the shape cannot fuse."""
    leaf = None
    for node in walk(root):
        if isinstance(node, (Scan, Source)):
            if leaf is not None and node is not leaf:
                return None
            leaf = node
        elif node is root:
            continue
        elif isinstance(node, Map):
            if node.method not in _SUPPORTED_MAP_METHODS:
                return None
        elif not isinstance(node, (Project, Filter)):
            return None
    return leaf


def _walk_masked(node: PlanNode, memo: Dict[int, Any], masked: Dict[int, Any]):
    """(unfiltered eager compiler, accumulated keep mask | None) per node.

    The graftfuse replay of the plan chain: Projects and Maps run through
    the SAME eager query-compiler methods the staged lowering uses (their
    device paths build deferred LazyExpr columns — no dispatch), but a
    Filter never compacts: its mask lowers to a deferred boolean expression
    AND-ed into the accumulated keep mask, and the child's columns stay
    full-length.  Valid because every interior op is elementwise: a mask
    computed over original rows selects exactly the rows a staged
    compaction would have kept, in the same order.  Diamond-shared nodes
    (the same Filter reached through an operand subplan) memoize, which is
    also what makes the mask-consistency identity check sound.
    """
    hit = masked.get(id(node))
    if hit is not None:
        return hit
    if isinstance(node, (Scan, Source)):
        from modin_tpu.plan import lowering

        result = (lowering._lower(node, memo), None)
    elif isinstance(node, Project):
        child, mask = _walk_masked(node.children[0], memo, masked)
        qc = child.getitem_column_array(list(node.keys), numeric=node.numeric)
        if node.out_hint is not None:
            qc._shape_hint = node.out_hint
        result = (qc, mask)
    elif isinstance(node, Map):
        receiver, mask = _walk_masked(node.children[0], memo, masked)
        args = []
        for a in node.args:
            if isinstance(a, Ref):
                operand, operand_mask = _walk_masked(
                    node.children[a.index], memo, masked
                )
                if operand_mask is not mask:
                    # operands must have seen the SAME filters; identity
                    # holds for legal plans because the shared Filter node
                    # memoizes to one mask expression
                    raise _Decline("operand filter mismatch")
                args.append(operand)
            else:
                args.append(a)
        qc = getattr(receiver, node.method)(*args, **node.kwargs)
        if node.out_hint is not None:
            qc._shape_hint = node.out_hint
        result = (qc, mask)
    elif isinstance(node, Filter):
        child, mask = _walk_masked(node.children[0], memo, masked)
        mask_qc, mask_below = _walk_masked(node.children[1], memo, masked)
        if mask_below is not mask:
            raise _Decline("mask filter mismatch")
        mframe = mask_qc._modin_frame
        if mframe.num_cols != 1:
            raise _Decline("non-column mask")
        mcol = mframe.get_column(0)
        if not getattr(mcol, "is_device", False) or mcol.pandas_dtype != np.dtype(
            bool
        ):
            raise _Decline("mask not a device bool column")
        from modin_tpu.ops.lazy import lazy_op

        mexpr = mcol.raw
        combined = mexpr if mask is None else lazy_op("__and__", mask, mexpr)
        result = (child, combined)
    else:
        raise _Decline(f"unsupported node {node.kind}")
    masked[id(node)] = result
    return result


def _donation_candidates(frame: Any) -> List[Any]:
    """Leaf columns whose buffers may ride in donated positions.

    Requires the device ledger's sole-consumer proof plus a lineage
    restore path (``DeviceColumn.donation_safe``); disabled entirely while
    a serving context is active — a concurrent query may hold the buffer
    in a pending argument tree the ledger cannot see.
    """
    if serving_context.CONTEXT_ON:
        return []
    candidates = [
        col
        for col in frame._columns
        if getattr(col, "is_device", False) and col.donation_eligible()
    ]
    if not candidates:
        return []
    from modin_tpu.core.memory import device_ledger

    # one ledger walk for the whole batch (not one per column)
    counts = device_ledger.buffer_consumer_counts(
        [col._data for col in candidates]
    )
    return [col for col in candidates if counts.get(id(col._data), 0) == 1]


# ---------------------------------------------------------------------- #
# the fused lowering leg (called from plan/lowering.py)
# ---------------------------------------------------------------------- #


def maybe_fuse_reduce(node: Reduce, memo: Dict[int, Any]) -> Optional[Any]:
    return _maybe_fuse(node, memo, groupby=False)


def maybe_fuse_groupby(node: GroupbyAgg, memo: Dict[int, Any]) -> Optional[Any]:
    return _maybe_fuse(node, memo, groupby=True)


def _maybe_fuse(node: PlanNode, memo: Dict[int, Any], groupby: bool) -> Optional[Any]:
    if not FUSE_ON:
        return None
    if groupby:
        # Ref-grouper (a deferred subplan as the by) stays staged
        if isinstance(node.by, Ref):
            return None
        if not _gate_groupby_kwargs(node):
            return None
    elif node.method not in SUPPORTED_REDUCE:
        return None
    leaf = _segment_leaf(node)
    if leaf is None:
        return None
    sig = segment_signature(node)
    from modin_tpu.ops import router
    from modin_tpu.ops.structural import pad_bucket_scope

    # lower the leaf through the normal memoized path (scan cache, io
    # lineage, spans intact) with the adaptive pad bucket active: a
    # storming signature's next upload lands on a shared physical size
    with pad_bucket_scope(_quantizer_for(sig) if id(leaf) not in memo else None):
        from modin_tpu.plan import lowering

        leaf_qc = lowering._lower(leaf, memo)
    frame = leaf_qc._modin_frame
    n = len(frame)
    if router.decide_compile(sig, n) != "fused":
        return None
    if n == 0 or not frame.all_device:
        # pandas empty/object semantics live with the staged path
        return None
    try:
        qc_top, mask = _walk_masked(node.children[0], memo, {})
    except _Decline:
        emit_metric("fuse.decline", 1)
        return None
    p_in = max(
        (
            int(data.shape[0])
            for c in frame._columns
            if c.is_device and (data := getattr(c, "_data", None)) is not None
            and hasattr(data, "shape")
        ),
        default=0,
    )
    donate_cols = _donation_candidates(frame)
    if donate_cols:
        # graftopt joint constraint: a plan the optimizer marked
        # memory-pressured (windowed tail, re-planned segment) must not
        # donate — the window loop / re-lowering still owns those buffers
        from modin_tpu.plan import optimizer as graftopt

        if not graftopt.donate_ok():
            donate_cols = []
    compiles_before = compiles_on_this_thread()
    with graftscope.span(
        "fuse.lower",
        layer="PLAN",
        sig=f"{hash(sig) & 0xFFFFFFFF:08x}",
        rows=n,
        donated=len(donate_cols),
    ):
        if groupby:
            result = _fused_groupby(node, qc_top, mask, n, donate_cols)
        else:
            result = _fused_reduce(node, qc_top, mask, donate_cols)
    note_fused_compiles(sig, p_in, compiles_on_this_thread() - compiles_before)
    if result is None:
        emit_metric("fuse.decline", 1)
        return None
    emit_metric("fuse.dispatch", 1)
    return result


def _fused_reduce(
    node: Reduce, qc_top: Any, mask: Any, donate_cols: List[Any]
) -> Optional[Any]:
    kwargs = dict(node.call_kwargs)
    axis = kwargs.pop("axis", 0)
    skipna = kwargs.pop("skipna", True)
    numeric_only = kwargs.pop("numeric_only", False)
    if axis not in (0, None):
        return None
    return qc_top._try_device_reduce(
        node.method, axis, skipna, numeric_only, kwargs,
        keep=mask, donate_cols=donate_cols,
    )


def _gate_groupby_kwargs(node: GroupbyAgg) -> bool:
    """Whether this groupby's kwargs are the fused scatter path's exact
    semantics: axis 0, as_index+sort defaults, a single string aggregation
    from the scatter-expressible set over a plain label grouper."""
    from modin_tpu.ops.groupby import FUSED_GROUPBY_AGGS

    if not isinstance(node.agg_func, str) or node.agg_func not in FUSED_GROUPBY_AGGS:
        return False
    by = node.by
    if isinstance(by, (list, tuple)):
        if len(by) != 1 or not isinstance(by[0], str):
            return False
    elif not isinstance(by, str):
        return False
    ck = node.call_kwargs
    if ck.get("axis", 0) not in (0, None):
        return False
    if ck.get("agg_args") or ck.get("series_groupby") or ck.get("selection") is not None:
        return False
    if ck.get("how", "axis_wise") != "axis_wise":
        return False
    gk = ck.get("groupby_kwargs") or {}
    if not set(gk) <= {"as_index", "sort", "dropna", "observed", "group_keys", "level"}:
        return False
    if gk.get("level") is not None:
        return False
    if not gk.get("as_index", True) or not gk.get("sort", True):
        return False
    ak = ck.get("agg_kwargs") or {}
    if not set(ak) <= {"numeric_only", "min_count"}:
        return False
    if ak.get("min_count", 0) not in (0, -1):
        return False
    return True


#: pandas groupby output dtype per aggregation (measured, pandas 2.x):
#: sum/prod keep the column dtype except bool -> int64; count is int64;
#: mean is float64 except float32 stays float32; min/max keep the dtype
def _groupby_out_dtype(agg: str, dtype: np.dtype) -> np.dtype:
    if agg == "count":
        return np.dtype(np.int64)
    if agg == "mean":
        return dtype if dtype == np.dtype(np.float32) else np.dtype(np.float64)
    if agg in ("sum", "prod") and dtype == np.dtype(bool):
        return np.dtype(np.int64)
    return dtype


def _fused_groupby(
    node: GroupbyAgg, qc_top: Any, mask: Any, n: int, donate_cols: List[Any]
) -> Optional[Any]:
    from modin_tpu.ops import groupby as gb

    agg = node.agg_func
    by = node.by if isinstance(node.by, str) else node.by[0]
    frame = qc_top._modin_frame
    columns = list(frame.columns)
    if by not in columns or columns.count(by) != 1:
        return None
    key_pos = columns.index(by)
    key_col = frame._columns[key_pos]
    if not getattr(key_col, "is_device", False) or key_col.pandas_dtype.kind not in "iub":
        return None
    numeric_only = (node.call_kwargs.get("agg_kwargs") or {}).get(
        "numeric_only", False
    )
    value_pos = []
    for i, col in enumerate(frame._columns):
        if i == key_pos:
            continue
        if not getattr(col, "is_device", False) or col.pandas_dtype.kind not in "iufb":
            if numeric_only:
                continue  # numeric_only drops non-numeric columns exactly
                # like the staged path would
            return None
        value_pos.append(i)
    if not value_pos:
        return None
    value_cols = [frame._columns[i] for i in value_pos]

    kmin, kmax, kept = gb.fused_group_probe(key_col.raw, mask, n)
    if kept == 0:
        return None
    width = kmax - kmin + 1
    if width > gb.FUSED_MAX_GROUPS:
        return None
    buckets = gb.fused_groups_bucket(width)
    sizes, tables, _counts = gb.fused_group_agg(
        agg,
        key_col.raw,
        [c.raw for c in value_cols],
        mask,
        n,
        kmin,
        buckets,
        donate_cols=donate_cols,
    )
    observed = np.nonzero(sizes[:buckets] > 0)[0]
    keys = (kmin + observed).astype(key_col.pandas_dtype)
    data = {}
    for pos, table in zip(value_pos, tables):
        out_dtype = _groupby_out_dtype(agg, frame._columns[pos].pandas_dtype)
        data[columns[pos]] = np.asarray(table[:buckets])[observed].astype(
            out_dtype
        )
    result = pandas.DataFrame(
        data,
        index=pandas.Index(keys, name=by),
        columns=[columns[i] for i in value_pos],
    )
    return type(qc_top).from_pandas(result)


# ---------------------------------------------------------------------- #
# graftstream integration: fused window bodies
# ---------------------------------------------------------------------- #


def window_reduce_plan(node: Reduce, scan_node: Any, call_kwargs: dict):
    """Per-STREAM precomputation for the fused window body, or None when
    the chain can never fuse.

    Returns ``run(window_qc) -> reduced compiler | None``: one window's
    chain + reduction as a single masked fused program.  The streaming
    executor's staged window body host-compacts every filter and
    neutral-pads the logical length so ragged windows share programs; the
    masked form needs neither — the physical size is already the window's
    pow2 bucket and the logical length rides as a runtime scalar, so every
    same-bucket window re-dispatches ONE program.  Everything
    stream-invariant (segment shape gate, signature, kwargs parse, the
    compile-router verdict) is computed once here, not once per window;
    ``run`` answers None per window to keep the staged body (zero kept
    rows, unsupported dtypes).
    """
    if not FUSE_ON or node.method not in SUPPORTED_REDUCE:
        return None
    if _segment_leaf(node) is None:
        return None
    kwargs = dict(call_kwargs)
    axis = kwargs.pop("axis", 0)
    skipna = kwargs.pop("skipna", True)
    numeric_only = kwargs.pop("numeric_only", False)
    if axis not in (0, None):
        return None
    sig = segment_signature(node)
    chain = node.children[0]
    from modin_tpu.ops import router

    # windows share one size (the final ragged one shares its bucket), so
    # the routing verdict is decided on the first window and memoized
    verdict: List[bool] = []

    def run(window_qc: Any) -> Optional[Any]:
        frame = window_qc._modin_frame
        if not verdict:
            verdict.append(router.decide_compile(sig, len(frame)) == "fused")
        if not verdict[0] or not frame.all_device:
            return None
        try:
            qc_top, mask = _walk_masked(chain, {id(scan_node): window_qc}, {})
        except _Decline:
            return None
        if mask is None:
            return None  # unfiltered windows: the quantized staged body
            # is already one cached program per bucket
        compiles_before = compiles_on_this_thread()
        result = qc_top._try_device_reduce(
            node.method, axis, skipna, numeric_only, dict(kwargs), keep=mask
        )
        p_in = max(
            (
                int(data.shape[0])
                for c in frame._columns
                if getattr(c, "is_device", False)
                and (data := getattr(c, "_data", None)) is not None
                and hasattr(data, "shape")
            ),
            default=0,
        )
        note_fused_compiles(
            "stream.window", p_in, compiles_on_this_thread() - compiles_before
        )
        if result is not None:
            emit_metric("fuse.dispatch", 1)
        return result

    return run
