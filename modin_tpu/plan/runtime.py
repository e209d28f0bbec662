"""graftplan runtime: the glue between the query compiler and the plan IR.

The TPU query compiler's plan-capable methods carry a one-line guard — "if a
plan is pending, try to defer" — and everything behind that guard lives
here: the mode gate (``MODIN_TPU_PLAN``), the scan sniff that makes a read
deferrable, node builders for each operator family, the materialization
(`optimize` + `lower`) path, and the safety predicates (row-lineage
alignment, pushdown eligibility) that decide when deferring is *exactly*
equivalent to eager execution.  Anything the planner cannot prove equivalent
falls back to eager by returning ``None`` — the caller's next line touches
``_modin_frame`` and the pending plan materializes through the property.

Mode semantics:

- ``Off``   — nothing ever defers; today's eager behavior, bit for bit.
- ``Auto``  — supported reads defer; chained plan-capable calls extend the
  plan; any other operation (or metadata the IR cannot answer exactly)
  materializes through the existing seams.
- ``Force`` — Auto, plus plan-capable calls on *already-materialized* TPU
  compilers re-enter planning through a :class:`~modin_tpu.plan.ir.Source`
  leaf, so rewrite rules keep applying after a materialization point.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import pandas

from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import spans as graftscope
from modin_tpu.plan import lowering
from modin_tpu.plan.ir import (
    MAX_PLAN_DEPTH,
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
)
from modin_tpu.plan.rules import optimize
from modin_tpu.utils import MODIN_UNNAMED_SERIES_LABEL

#: Module-level fast path, graftscope-style: the per-op guard in the query
#: compiler checks ``self._plan is not None or runtime.FORCE_ON`` — while the
#: mode is not Force, an eager compiler pays one attribute read per call.
FORCE_ON: bool = False


def plan_mode() -> str:
    from modin_tpu.config import PlanMode

    return PlanMode.get()


def _on_plan_param(_param: Any = None) -> None:
    global FORCE_ON
    try:
        FORCE_ON = plan_mode() == "Force"
    except ImportError:  # config not importable during teardown
        FORCE_ON = False


def _install_subscription() -> None:
    from modin_tpu.config import PlanMode

    PlanMode.subscribe(_on_plan_param)


# ---------------------------------------------------------------------- #
# Scan deferral
# ---------------------------------------------------------------------- #

#: read_csv kwargs stripped for the header sniff (they either conflict with
#: ``nrows=0`` or only affect the body).
_SNIFF_DROP = ("filepath_or_buffer", "iterator", "chunksize", "nrows", "skipfooter")


def _requests_extension_dtype(dtype: Any) -> bool:
    """Whether a ``dtype=`` read kwarg asks for any pandas extension dtype.

    Extension results (NA-backed Int64/boolean/...) violate the IR's
    "comparisons are plain bool" dtype claims, so such reads stay eager.
    """
    no_default = pandas.api.extensions.no_default
    if dtype is None or dtype is no_default:
        return False
    values = dtype.values() if isinstance(dtype, dict) else [dtype]
    for value in values:
        try:
            if isinstance(
                pandas.api.types.pandas_dtype(value),
                pandas.api.extensions.ExtensionDtype,
            ):
                return True
        except TypeError:
            return True  # unparseable request: assume the worst, stay eager
    return False


def defer_read(dispatcher: type, kwargs: dict) -> Optional[Any]:
    """Defer a text-family read into a Scan-rooted plan, or None for eager.

    The sniff parses ONLY the header (``nrows=0``) to learn the post-
    ``usecols`` column labels — exact metadata for a few KB of IO.  Any
    sniff failure (missing file, bad kwargs, malformed header) declines the
    deferral so the eager path raises at the call site with today's timing.
    """
    try:
        mode = plan_mode()
    except ImportError:
        return None
    if mode == "Off":
        return None
    kwargs = dispatcher.normalize_read_kwargs(dict(kwargs))
    if kwargs.get("iterator") or kwargs.get("chunksize") is not None:
        return None  # these return parser iterators, not frames
    path = kwargs.get("filepath_or_buffer")
    if not dispatcher.is_local_plain_file(path):
        return None
    dtype_backend = kwargs.get("dtype_backend")
    if dtype_backend is not None and dtype_backend is not (
        pandas.api.extensions.no_default
    ):
        # extension-backed frames break the IR's "comparisons are plain
        # bool" dtype guarantees — stay eager
        return None
    if _requests_extension_dtype(kwargs.get("dtype")):
        return None  # same guarantee: dtype={'a': 'Int64'} etc. stays eager
    sniff_kwargs = {k: v for k, v in kwargs.items() if k not in _SNIFF_DROP}
    try:
        header = dispatcher.read_fn(path, nrows=0, **sniff_kwargs)
        columns = pandas.Index(header.columns)
    except Exception:
        # any sniff failure means "not deferrable"; the eager read then
        # raises the same error at the same call site
        return None
    scan = Scan(dispatcher, dict(kwargs), columns, colarg="usecols")
    emit_metric("plan.defer.scan", 1)
    return dispatcher.query_compiler_cls.from_plan(scan)


#: read_csv kwargs that make a reader-level projection unsafe to push,
#: mapped to the values meaning "feature disabled": the parse of a
#: surviving column (or the frame's index) could depend on a pruned one.
#: NOTE ``index_col`` has NO harmless falsy value — 0 means "first column
#: is the index", and pandas resolves positional index_col *within* the
#: usecols subset, so any set index_col blocks the pushdown.
_PUSHDOWN_BLOCKERS = (
    ("index_col", (None,)),
    ("converters", (None,)),
    ("skipfooter", (None, 0)),
    ("parse_dates", (None, False)),
)


def scan_supports_pushdown(scan: Scan) -> bool:
    """Whether narrowing this scan's reader projection is exactly safe."""
    if scan.colarg != "usecols":
        return False
    kwargs = scan.read_kwargs
    no_default = pandas.api.extensions.no_default
    for key, disabled in _PUSHDOWN_BLOCKERS:
        value = kwargs.get(key)
        if value is no_default or any(value is d for d in disabled):
            continue
        return False
    usecols = kwargs.get("usecols")
    if usecols is not None and usecols is not no_default and callable(usecols):
        return False
    dtype = kwargs.get("dtype")
    if isinstance(dtype, dict) and any(
        k not in set(scan.all_columns) for k in dtype
    ):
        # pandas accepts positional (int) dtype keys, resolved against the
        # full column set; the pushed projection filters this dict by LABEL,
        # so a non-label key would silently change the surviving columns'
        # parse — keep the full-width read instead
        return False
    names = kwargs.get("names")
    if names is not None and names is not no_default:
        return False
    # the pushed projection is label-based: every sniffed label must be a
    # plain unique string (a MultiIndex header yields tuple labels, which
    # pandas' usecols rejects outright)
    return scan.all_columns.is_unique and all(
        isinstance(c, str) for c in scan.all_columns
    )


# ---------------------------------------------------------------------- #
# Node builders (the per-op deferral guards call these)
# ---------------------------------------------------------------------- #


def _plan_of(qc: Any) -> Optional[PlanNode]:
    """The operand's plan — wrapping eager compilers in Source under Force.

    While a lowering pass is running on this thread, eager compilers stay
    eager: lowering replays plan nodes through the same guarded methods, and
    re-entering planning there would recurse forever.  A plan at the depth
    cap also declines (the caller's eager body then materializes it) — the
    planner's analogue of ``ops/lazy.py``'s ``_MAX_NODES`` overflow, keeping
    pathological op loops from building unbounded (and unboundedly
    recursive) plan chains.
    """
    plan = qc._plan
    if plan is not None and plan.depth >= MAX_PLAN_DEPTH:
        return None
    if plan is None and FORCE_ON and not lowering.in_lowering():
        plan = _source_of(qc)
    return plan


def _source_of(qc: Any) -> Source:
    """One memoized Source leaf per eager compiler (keyed on its frame).

    Force-mode guards must hand every consumer of one compiler the SAME
    leaf: row keys are identity-based, so a fresh Source per guard call
    would never match between a frame and its mask/operand and filters and
    series-series binaries would silently stay eager.  The memo drops
    itself when the compiler's frame is rebound (e.g. a reduction adopting
    its lowered input)."""
    source = getattr(qc, "_plan_source", None)
    if source is None or source.qc._frame is not qc._frame:
        source = Source(qc.eager_snapshot())
        qc._plan_source = source
    return source


def _stamp_hint(qc: Any, plan: PlanNode) -> None:
    """Late-bind the pandas layer's shape hint into the operand's node.

    The API layer tags a compiler as a Series (``_shape_hint = "column"``)
    *after* the deferring call returns, so the hint is only knowable once
    the node is consumed by the next operator; lowering needs it on the
    intermediate eager compilers for the series/frame binary label rules.
    """
    if isinstance(plan, (Project, Map)) and plan.out_hint is None and (
        qc._shape_hint is not None
    ):
        plan.out_hint = qc._shape_hint


def defer_project(qc: Any, key: Any, numeric: bool) -> Optional[Any]:
    plan = _plan_of(qc)
    if plan is None:
        return None
    keys = list(key)
    if numeric:
        try:
            keys = [int(k) for k in keys]
        except (TypeError, ValueError):
            return None
        width = len(plan.columns)
        if any(k < -width or k >= width for k in keys):
            return None  # out of range: eager raises at the call site
    else:
        columns = plan.columns
        if not columns.is_unique or any(k not in columns for k in keys):
            return None
    _stamp_hint(qc, plan)
    return type(qc).from_plan(Project(plan, tuple(keys), numeric))


def defer_filter(qc: Any, mask_qc: Any) -> Optional[Any]:
    """Defer ``df[bool_series]`` when the mask is a provably aligned,
    provably boolean subplan of the same row lineage."""
    plan = _plan_of(qc)
    if plan is None or mask_qc._plan is None:
        return None
    mask_plan = mask_qc._plan
    if mask_plan.depth >= MAX_PLAN_DEPTH:
        return None
    mask_dtypes = mask_plan.known_dtypes()
    if (
        mask_dtypes is None
        or len(mask_dtypes) != 1
        or mask_dtypes.iloc[0] != bool
        or plan.row_key() != mask_plan.row_key()
    ):
        return None
    _stamp_hint(qc, plan)
    _stamp_hint(mask_qc, mask_plan)
    return type(qc).from_plan(Filter(plan, mask_plan))


_SCALAR_OPERANDS = (int, float, bool, str, type(None))


def _known_bool(plan: PlanNode) -> bool:
    dtypes = plan.known_dtypes()
    return dtypes is not None and all(dt == bool for dt in dtypes)


def _known_plain(plan: PlanNode) -> bool:
    """No KNOWN extension dtype in the node's output.

    Scans are gated to plain numpy dtypes at defer time (dtype_backend and
    extension ``dtype=`` requests decline deferral), so unknown dtypes are
    plain; a Source over an extension-backed frame reports them exactly.
    """
    dtypes = plan.known_dtypes()
    return dtypes is None or not any(
        isinstance(dt, pandas.api.extensions.ExtensionDtype) for dt in dtypes
    )


def defer_binary(qc: Any, op: str, other: Any, kwargs: dict) -> Optional[Any]:
    import numpy as np

    plan = _plan_of(qc)
    if plan is None:
        return None
    cls = type(qc)
    # comparisons yield plain bool for plain-dtype operands; extension
    # operands (possible under Force over e.g. Int64 frames) and string
    # comparisons may produce NA-backed boolean extension results, and
    # logical ops on non-bool ints are bitwise — none of those claim bool
    bool_out = (
        op in cls._CMP_OPS and not isinstance(other, str) and _known_plain(plan)
    ) or (op in cls._LOGICAL_OPS and _known_bool(plan))
    hint = qc._shape_hint
    if isinstance(other, _SCALAR_OPERANDS + (np.generic,)) and not isinstance(
        other, bytes
    ):
        _stamp_hint(qc, plan)
        node = Map(
            (plan,),
            op,
            (other,),
            kwargs,
            out_columns=plan.columns,
            bool_out=bool_out,
            out_hint=hint,
        )
        return cls.from_plan(node, hint)
    if isinstance(other, cls) and other._plan is not None:
        other_plan = other._plan
        if other_plan.depth >= MAX_PLAN_DEPTH:
            return None
        if plan.row_key() != other_plan.row_key():
            return None
        if op in cls._LOGICAL_OPS:
            bool_out = bool_out and _known_bool(other_plan)
        elif op in cls._CMP_OPS:
            bool_out = bool_out and _known_plain(other_plan)
        other_hint = other._shape_hint
        if hint == "column" and other_hint == "column":
            a, b = plan.columns[0], other_plan.columns[0]
            label = a if a == b else MODIN_UNNAMED_SERIES_LABEL
            out_columns = pandas.Index([label])
        elif hint is None and other_hint is None:
            if not plan.columns.equals(other_plan.columns):
                return None
            out_columns = plan.columns
        else:
            return None
        _stamp_hint(qc, plan)
        _stamp_hint(other, other_plan)
        node = Map(
            (plan, other_plan),
            op,
            (Ref(1),),
            kwargs,
            out_columns=out_columns,
            bool_out=bool_out,
            out_hint=hint,
        )
        return cls.from_plan(node, hint)
    return None


#: Unary QC methods that defer as single-child maps (all length-preserving,
#: columns unchanged); value is whether the result is provably boolean.
UNARY_MAP_METHODS = {
    "abs": False,
    "negative": False,
    "invert": False,
    "isna": True,
    "notna": True,
}


def defer_unary(
    qc: Any, method: str, args: Tuple = (), kwargs: Optional[dict] = None,
    bool_out: bool = False,
) -> Optional[Any]:
    plan = _plan_of(qc)
    if plan is None:
        return None
    if not all(isinstance(a, _SCALAR_OPERANDS) for a in args):
        return None
    _stamp_hint(qc, plan)
    hint = qc._shape_hint
    node = Map(
        (plan,),
        method,
        tuple(args),
        dict(kwargs or {}),
        out_columns=plan.columns,
        bool_out=bool_out,
        out_hint=hint,
    )
    return type(qc).from_plan(node, hint)


def defer_sort(
    qc: Any, columns: Any, ascending: Any, kwargs: dict
) -> Optional[Any]:
    plan = _plan_of(qc)
    if plan is None:
        return None
    col_list = [columns] if not isinstance(columns, (list, tuple)) else list(columns)
    plan_columns = plan.columns
    if not plan_columns.is_unique or any(c not in plan_columns for c in col_list):
        return None
    _stamp_hint(qc, plan)
    node = Sort(plan, columns, ascending, kwargs)
    return type(qc).from_plan(node, qc._shape_hint)


# ---------------------------------------------------------------------- #
# Materialization points
# ---------------------------------------------------------------------- #


def _optimize_and_lower(
    qc: Any, root: PlanNode, instrument: Optional[dict] = None
) -> Tuple[Any, dict]:
    """One optimize+lower pass; records EXPLAIN attribution on ``qc``."""
    from modin_tpu.plan import optimizer
    from modin_tpu.plan.ir import count_nodes

    cost_model = optimizer.plan_cost if optimizer.OPT_ON else None
    with graftscope.span(
        "plan.optimize", layer="PLAN", nodes=count_nodes(root)
    ):
        optimized, applied = optimize(root, cost_model=cost_model)
    passes = (applied[-1][1] + 1) if applied else 1
    emit_metric("plan.optimize.passes", passes)
    for name, _pass_index in applied:
        emit_metric(f"plan.rule.{name}", 1)
    strategies = optimizer.choose(optimized) if optimizer.OPT_ON else None
    result, memo = lowering.lower_traced(
        optimized, instrument=instrument, strategies=strategies
    )
    qc._plan_explain = (root, optimized, applied)
    qc._plan_strategies = strategies
    return result, memo


def explain_analyze(qc: Any) -> Optional[Tuple[Any, dict, Any]]:
    """EXPLAIN ANALYZE: execute ``qc``'s plan with per-node instrumentation.

    Returns ``(stats, instrument, (root, optimized, applied))`` — the
    :class:`~modin_tpu.observability.meters.QueryStats` rollup, the node-id
    -> measured-actuals dict, and the plan history of this run (the
    actuals key off ``id()`` of nodes in the returned ``optimized`` tree)
    — or None when there is nothing to analyze (a plain eager compiler
    with no plan history).

    A *pending* plan is executed and its frame adopted, exactly like
    :func:`force` (so a later op on the compiler continues from the
    materialized result, and the analyze run IS the query's execution — the
    bit-exactness contract).  An already-materialized compiler with plan
    history re-executes the recorded plan (scans may be served from the
    scan cache; the annotations say so via their measured bytes/time) and
    the re-run result is discarded.
    """
    from modin_tpu.observability import meters as graftmeter

    # tolerate non-graftplan compilers the way the analyze=False branch
    # does: report "nothing to analyze" instead of AttributeError
    plan = getattr(qc, "_plan", None)
    pending = plan is not None
    if pending:
        root = plan
    else:
        history = getattr(qc, "_plan_explain", None)
        if history is None:
            return None
        root = history[0]
    instrument: dict = {}
    with graftmeter.query_stats("explain.analyze") as stats:
        result, _memo = _optimize_and_lower(qc, root, instrument=instrument)
    if pending:
        qc._frame = result._modin_frame
        qc._plan = None
    return stats, instrument, qc._plan_explain


def force(qc: Any):
    """Materialize a pending plan; returns the concrete TpuDataframe."""
    plan = qc._plan
    if plan is None:
        if qc._frame is None:
            raise RuntimeError(
                "deferred query compiler used after free(): its plan was "
                "dropped and no frame was ever materialized"
            )
        return qc._frame
    result, _memo = _optimize_and_lower(qc, plan)
    qc._frame = result._modin_frame
    qc._plan = None
    return qc._frame


def _adopt_lowered_input(qc: Any, memo: dict) -> None:
    """Adopt the materialization's lowered INPUT frame back into ``qc`` so a
    later op on the same compiler reuses the scan instead of re-reading.
    Only fires while ``qc`` still holds a real pending plan (a Force-mode
    eager compiler has none) — the optimized root's first child is the
    reduction/groupby input by construction."""
    lowered_input = memo.get(id(qc._plan_explain[1].children[0]))
    if lowered_input is not None and qc._plan is not None:
        qc._frame = lowered_input._modin_frame
        qc._plan = None


def run_reduce(qc: Any, op: str, call_kwargs: dict) -> Optional[Any]:
    """Reductions are materialization points: append the Reduce node, run
    the whole optimized plan, and adopt the reduction INPUT back into ``qc``
    so a later op on the same compiler reuses the scan instead of re-reading.
    """
    plan = _plan_of(qc)
    if plan is None:
        return None
    _stamp_hint(qc, plan)
    root = Reduce(plan, op, call_kwargs)
    result, memo = _optimize_and_lower(qc, root)
    _adopt_lowered_input(qc, memo)
    return result


def run_groupby_agg(
    qc: Any, by: Any, agg_func: Any, call_kwargs: dict
) -> Optional[Any]:
    """Groupby aggregations materialize like reductions (their output index
    is group-dependent, which the IR does not model)."""
    plan = _plan_of(qc)
    if plan is None:
        return None
    cls = type(qc)
    children: Tuple[PlanNode, ...] = (plan,)
    by_payload = by
    if isinstance(by, cls):
        if by._plan is None or by._plan.row_key() != plan.row_key():
            return None
        _stamp_hint(by, by._plan)
        children = (plan, by._plan)
        by_payload = Ref(1)
    elif not (
        isinstance(by, (str, list, tuple))
        and (isinstance(by, str) or all(isinstance(b, str) for b in by))
    ):
        return None
    _stamp_hint(qc, plan)
    root = GroupbyAgg(children, by_payload, agg_func, call_kwargs)
    result, memo = _optimize_and_lower(qc, root)
    _adopt_lowered_input(qc, memo)
    return result


# ---------------------------------------------------------------------- #
# Metadata service & public helpers
# ---------------------------------------------------------------------- #


def plan_columns(qc: Any) -> pandas.Index:
    return qc._plan.columns


def plan_dtypes(qc: Any) -> Optional[pandas.Series]:
    return qc._plan.known_dtypes()


def defer_frame(obj: Any) -> Any:
    """Public opt-in: root a plan at an existing TPU DataFrame/Series/QC.

    Returns the same API-level type wrapped over a Source-rooted deferred
    compiler; chained plan-capable calls then extend the plan even under
    ``MODIN_TPU_PLAN=Auto``.
    """
    qc = getattr(obj, "_query_compiler", obj)
    planned = type(qc).from_plan(Source(qc.eager_snapshot()), qc._shape_hint)
    if hasattr(obj, "_query_compiler"):
        return type(obj)(query_compiler=planned)
    return planned


_install_subscription()
_on_plan_param()
