"""graftopt: one adaptive, cost-based optimizer over graftplan's IR.

Before this module the engine held FIVE independent execution-strategy
deciders — kernel ``decide()`` (device/host), ``decide_layout``
(local/sharded), ``decide_compile`` (fused/staged), ``decide_residency``
(resident/windowed), and graftview's zero-cost artifact leg — each with
its own crossover logic, consulted at its own layer, at its own time.
Jointly-wrong choices were structural: a plan that will stream should not
donate its inputs; a windowed tail can never amortize a whole-plan
compile; a storming fused signature keeps paying traces the staged
kernels would skip.  Xorbits (arXiv 2401.00865) automates exactly this
chunking decision at runtime and Dias (arXiv 2303.16146) shows dynamic
rewriting is profitable *mid-query* — this module is both halves:

- :func:`choose` runs ONCE per plan materialization and annotates every
  node with a :class:`NodeStrategy` — estimated rows/bytes/seconds from
  the calibrated coefficients (kernel-router table via
  :func:`~modin_tpu.ops.router.calibration_peek`, graftcost substrate
  peaks, :data:`DEFAULT_PRIORS`) plus the jointly-consistent strategy legs.
- the existing routers stay the per-leg cost providers AND the live
  deciders: each ``decide_*`` offers its verdict through the
  ``router._opt_consult`` hook, and the optimizer overrides it only where
  the plan-time joint constraints or a mid-query re-plan disagree.  With
  ``MODIN_TPU_OPT=Off`` the hook is None and behavior is bit-for-bit the
  pre-graftopt five-router engine, with zero optimizer allocations
  (:func:`opt_alloc_count` asserts exactly that, graftscope-style).
- **mid-query re-planning**: lowering feeds each node's measured wall
  back through :func:`observe`; when a node overshoots its estimate by
  ``MODIN_TPU_OPT_REPLAN_FACTOR`` the not-yet-lowered plan segment is
  re-chosen with the measured/estimated ratio folded in as a correction
  on the calibrated device-side coefficients (``wall_divergence``).  Live
  ledger pressure contradicting a planned resident leg re-plans the tail
  windowed (``ledger_pressure``); a storming fused signature re-plans
  staged (``compile_storm``).  Every re-plan is metered
  (``opt.replan.*``), span-tagged (``opt.replan``), recorded on the
  strategy set for EXPLAIN, and fires at most once per (node, trigger).

The deterministic row floors (``*_MIN_ROWS``) and forced modes always
win: the consult hook is only offered verdicts whose reason is a genuine
cost-model/auto outcome, so tests and smoke legs that pin a side, and
tiny unit-test frames, never observe the optimizer at all.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Set, Tuple

from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import spans as graftscope
from modin_tpu.ops import calibration as calstore
from modin_tpu.ops import router
from modin_tpu.plan.ir import (
    Filter,
    GroupbyAgg,
    Map,
    PlanNode,
    Project,
    Reduce,
    Scan,
    Sort,
    Source,
    walk,
)

#: the sort-shaped host-kernel families the kernel router arbitrates
SORT_SHAPED = frozenset({"median", "quantile", "nunique", "mode"})

#: measured walls below this never trigger a wall_divergence re-plan —
#: at single-millisecond scale the "divergence" is scheduler noise
REPLAN_NOISE_FLOOR_S = 0.005

#: correction ratios are clamped here so one pathological measurement
#: cannot push every later crossover to literal infinity.  The bound is
#: deliberately generous: an adversarially-wrong calibration table can be
#: off by six orders of magnitude (claimed nanoseconds, measured seconds),
#: and the correction must still be able to flip the affected crossovers
MAX_CORRECTION = 1e6

#: fallback coefficients when neither calibration nor substrate peaks
#: cover a node family (conservative CPU-substrate figures; any measured
#: source immediately supersedes them)
DEFAULT_PRIORS: Dict[str, float] = {
    "parse_bytes_per_s": 120e6,
    "mem_bytes_per_s": 2e9,
    "bytes_per_row": 64.0,
}

OPT_ON: bool = True

_alloc_count = 0
_tls = threading.local()

#: the table :func:`set_priors` forced, else None (DEFAULT_PRIORS)
_priors: Optional[Dict[str, Any]] = None


def opt_alloc_count() -> int:
    """Strategy-set allocations so far: the Off-mode zero-overhead
    assertion (no :class:`PlanStrategies` is ever built while
    ``MODIN_TPU_OPT=Off``)."""
    return _alloc_count


class NodeStrategy:
    """One plan node's chosen strategy legs and cost estimate.

    ``legs`` maps leg name (kernel / layout / compile / residency) to the
    planned choice — an EXPLAIN annotation for every leg, and the consult
    answer for the legs in ``firm``.  Non-firm legs defer to the live
    router (which sees per-column strategies and real row counts the plan
    cannot); re-planning promotes legs to firm as evidence arrives.
    """

    __slots__ = (
        "node",
        "legs",
        "leg_ops",
        "firm",
        "est_rows",
        "est_bytes",
        "est_s",
        "measured_s",
        "measured_bytes",
        "donate",
    )

    def __init__(self, node: PlanNode):
        self.node = node
        self.legs: Dict[str, str] = {}
        self.leg_ops: Dict[str, str] = {}
        self.firm: Set[str] = set()
        self.est_rows: Optional[int] = None
        self.est_bytes: Optional[int] = None
        self.est_s: float = 0.0
        self.measured_s: Optional[float] = None
        self.measured_bytes: Optional[int] = None
        self.donate: bool = True


class PlanStrategies:
    """The joint strategy annotation for one plan materialization."""

    __slots__ = (
        "by_node",
        "replans",
        "fired",
        "correction",
        "root",
        "done",
        "priors",
    )

    def __init__(self) -> None:
        global _alloc_count
        _alloc_count += 1
        self.by_node: Dict[int, NodeStrategy] = {}
        self.replans: List[dict] = []
        self.fired: Set[Tuple[Any, str]] = set()
        self.correction: float = 1.0
        self.root: Optional[PlanNode] = None
        self.done: Optional[dict] = None
        self.priors: Dict[str, float] = dict(DEFAULT_PRIORS)


def _on_opt_mode(param: Any) -> None:
    global OPT_ON
    OPT_ON = param.get().lower() != "off"
    # install/clear the router consult hook with the mode: Off pays one
    # `is not None` check per router decision and nothing else
    router._opt_consult = _consult if OPT_ON else None


def set_priors(priors: Optional[Dict[str, Any]]) -> None:
    """Force the coefficient table every later plan is estimated with, or
    (None) go back to :data:`DEFAULT_PRIORS`.  The fault-injection hook of
    tests/test_optimizer.py and scripts/optimizer_smoke.py: the
    ``*_s_per_row`` keys ``_estimate_nodes`` / ``_reduce_cost`` read exist
    only so that a forced table can poison an estimate."""
    global _priors
    _priors = priors


def _resolve_priors() -> Dict[str, Any]:
    return _priors or dict(DEFAULT_PRIORS)


# ---------------------------------------------------------------------- #
# the plan-time cost model
# ---------------------------------------------------------------------- #


def _scan_bytes(node: Scan) -> Optional[int]:
    """Sniffed source size of one scan (the same figure graftstream's
    residency consult uses), or None when the source is not stat-able."""
    try:
        path = node.read_kwargs.get("filepath_or_buffer")
        if path is None:
            return None
        return int(node.dispatcher.file_size(path))
    except Exception:  # an unsniffable source simply gets no size-based estimate; planning must never fail a query
        return None


def _source_shape(node: Source) -> Tuple[Optional[int], Optional[int]]:
    """(rows, bytes) of an already-materialized Source frame, forcing
    nothing (a deferred/planned source estimates as unknown)."""
    try:
        frame = node.qc._modin_frame
        if frame is None:
            return None, None
        from modin_tpu.streaming import windows as stream_windows

        return len(frame), int(stream_windows.frame_nbytes(frame))
    except Exception:  # shape sniffing is best-effort; unknown shapes fall back to priors
        return None, None


def estimate_selectivity(mask: PlanNode) -> float:
    """Estimated fraction of rows a filter mask passes.

    Seeded from the comparison operator's shape (equality selects far
    fewer rows than an order comparison; conjunctions multiply,
    disjunctions saturate) — the histogram fast-path statistics refine
    these at the kernel layer, but at plan time the operator is the
    signal that is always available.
    """
    if isinstance(mask, Map):
        method = str(mask.method).lower().strip("_")
        if method in ("eq",):
            return 0.1
        if method in ("ne",):
            return 0.9
        if method in ("gt", "lt", "ge", "le"):
            return 0.5
        if method in ("isin", "isna", "isnull"):
            return 0.2
        if method in ("notna", "notnull"):
            return 0.8
        if method in ("and", "mul"):
            sels = [estimate_selectivity(c) for c in mask.children]
            out = 1.0
            for s in sels:
                out *= s
            return max(out, 0.01)
        if method in ("or", "add"):
            return min(
                sum(estimate_selectivity(c) for c in mask.children), 1.0
            )
        if method in ("invert", "not"):
            return 1.0 - estimate_selectivity(mask.children[0])
    return 0.8


def _estimate_nodes(
    root: PlanNode,
    priors: Dict[str, Any],
    correction: float,
    table: Optional[Dict[str, float]],
) -> Dict[int, dict]:
    """Bottom-up (rows, bytes, seconds) estimate per node id.

    Seconds are subtree-cumulative, matching the instrumented lowering's
    ``total_s`` semantics so the divergence comparison is like-for-like.
    The ``correction`` multiplier carries re-plan evidence: measured
    walls that overshot the model scale every later estimate.
    """
    peaks = None
    try:
        from modin_tpu.observability import costs as graftcost

        peaks = graftcost.substrate_peaks()
    except Exception:  # no peaks means the priors' fallback bandwidth; planning must never fail a query
        peaks = None
    mem_bw = float(
        (peaks or {}).get("bytes_per_s") or priors["mem_bytes_per_s"]
    )
    parse_bw = float(priors.get("parse_bytes_per_s") or 120e6)
    bytes_per_row = float(priors.get("bytes_per_row") or 64.0)

    est: Dict[int, dict] = {}
    for node in walk(root):
        child = est.get(id(node.children[0])) if node.children else None
        rows = child["rows"] if child else None
        nbytes = child["bytes"] if child else None
        child_s = sum(est[id(c)]["s"] for c in node.children if id(c) in est)
        own_s = 0.0
        if isinstance(node, Scan):
            nbytes = _scan_bytes(node)
            if nbytes is not None:
                rows = max(int(nbytes / bytes_per_row), 1)
                scan_coeff = priors.get("scan_s_per_row")
                own_s = (
                    rows * float(scan_coeff)
                    if scan_coeff
                    else nbytes / parse_bw
                )
                if node.pruned is not None and len(node.all_columns):
                    frac = max(len(node.pruned), 1) / len(node.all_columns)
                    nbytes = int(nbytes * frac)
                    if node.pushed:
                        own_s *= frac
        elif isinstance(node, Source):
            rows, nbytes = _source_shape(node)
        elif isinstance(node, Filter):
            sel = estimate_selectivity(node.children[1])
            if rows is not None:
                rows = max(int(rows * sel), 1)
            if nbytes is not None:
                own_s = nbytes / mem_bw
                nbytes = max(int(nbytes * sel), 1)
        elif isinstance(node, Project):
            if nbytes is not None:
                width = None
                if isinstance(node.children[0], Scan):
                    width = len(node.children[0].all_columns) or None
                frac = (
                    len(node.keys) / width
                    if width
                    else 0.5
                )
                nbytes = max(int(nbytes * min(frac, 1.0)), 1)
                own_s = nbytes / mem_bw
        elif isinstance(node, Map):
            if nbytes is not None:
                own_s = nbytes / mem_bw
        elif isinstance(node, Reduce):
            own_s = _reduce_cost(node, rows, nbytes, table, priors, mem_bw)
            rows, nbytes = 1, 8
        elif isinstance(node, GroupbyAgg):
            coeff = priors.get("groupby_s_per_row")
            if coeff and rows is not None:
                own_s = rows * float(coeff)
            elif nbytes is not None:
                own_s = 2.0 * nbytes / mem_bw
            if rows is not None:
                rows = max(int(rows**0.5), 1)
                nbytes = rows * 16
        elif isinstance(node, Sort):
            coeff = priors.get("sort_s_per_row")
            if table is not None and rows is not None:
                own_s = table["device_sort_s"] * calstore.nlogn_scale(
                    rows, int(table["rows"])
                )
            elif coeff and rows is not None:
                own_s = rows * float(coeff)
            elif nbytes is not None and rows is not None:
                own_s = nbytes * max(rows, 2).bit_length() / mem_bw
        est[id(node)] = {
            "rows": rows,
            "bytes": nbytes,
            "s": own_s * correction + child_s,
        }
    return est


def _reduce_cost(
    node: Reduce,
    rows: Optional[int],
    nbytes: Optional[int],
    table: Optional[Dict[str, float]],
    priors: Dict[str, Any],
    mem_bw: float,
) -> float:
    """One reduction's own estimated seconds (the cheaper of the kernel
    router's predicted sides when the family is sort-shaped and a
    calibration table is resolved)."""
    if node.method in SORT_SHAPED:
        if table is not None and rows is not None:
            try:
                costs = router.predicted_costs(
                    node.method, rows, ["sort"], table
                )
                return min(costs["device_s"], costs["host_s"])
            except KeyError:
                pass
        coeff = priors.get("sortred_s_per_row")
        if coeff and rows is not None:
            return rows * float(coeff)
    coeff = priors.get("reduce_s_per_row")
    if coeff and rows is not None:
        return rows * float(coeff)
    return (nbytes / mem_bw) if nbytes is not None else 0.0


def plan_cost(root: PlanNode) -> float:
    """Total modeled cost of a plan (seconds): the rewrite engine's
    cost-gate objective.  Uses only already-resolved calibration (never
    triggers measurement) so rule evaluation stays microseconds."""
    priors = _resolve_priors()
    est = _estimate_nodes(root, priors, 1.0, router.calibration_peek())
    entry = est.get(id(root))
    return float(entry["s"]) if entry else 0.0


# ---------------------------------------------------------------------- #
# choose(): the joint plan-time pass
# ---------------------------------------------------------------------- #


def choose(
    root: PlanNode,
    state: Optional[PlanStrategies] = None,
    exclude: Optional[Set[int]] = None,
) -> PlanStrategies:
    """Annotate every plan node with its jointly-chosen strategy legs.

    One pass per materialization: estimates flow bottom-up, then each
    strategy-bearing node gets its legs under the joint constraints
    (windowed ⇒ staged compile ⇒ no donation).  With ``state`` given the
    pass is a RE-plan: existing annotations are updated in place for the
    nodes not in ``exclude`` (the already-lowered memo), carrying the
    accumulated correction factor into every refreshed estimate.
    """
    replanning = state is not None
    if state is None:
        state = PlanStrategies()
        state.root = root
        state.priors = _resolve_priors()
    exclude = exclude or set()
    with graftscope.span(
        "opt.choose",
        layer="PLAN",
        replanning=replanning,
        correction=round(state.correction, 3),
    ):
        table = router.calibration_peek()
        est = _estimate_nodes(root, state.priors, state.correction, table)
        for node in walk(root):
            if id(node) in exclude:
                continue
            st = state.by_node.get(id(node))
            if st is None:
                st = NodeStrategy(node)
                state.by_node[id(node)] = st
            entry = est.get(id(node), {})
            st.est_rows = entry.get("rows")
            st.est_bytes = entry.get("bytes")
            st.est_s = float(entry.get("s") or 0.0)
            # strategy legs are chosen over the node's INPUT shape (the
            # rows/bytes the kernel actually consumes): a reduction's own
            # output is one row, which decides nothing
            child_entry = (
                est.get(id(node.children[0]), {}) if node.children else {}
            )
            _choose_node(node, st, state, table, child_entry)
    emit_metric("opt.choose", 1)
    return state


def _choose_node(
    node: PlanNode,
    st: NodeStrategy,
    state: PlanStrategies,
    table: Optional[Dict[str, float]],
    child_entry: Dict[str, Any],
) -> None:
    """One node's strategy legs under the joint constraints."""
    in_rows = child_entry.get("rows")
    in_bytes = child_entry.get("bytes")
    if isinstance(node, (Reduce, GroupbyAgg)):
        groupby = isinstance(node, GroupbyAgg)
        residency = _plan_residency(in_bytes)
        st.legs["residency"] = residency
        st.leg_ops["residency"] = (
            "scan_groupby" if groupby else "scan_reduce"
        )
        st.firm.add("residency")
        if residency == "windowed":
            # joint constraints: a windowed tail replays the segment per
            # window — a whole-plan compile never amortizes, and donating
            # the inputs would free buffers the window loop still owns
            st.legs["compile"] = "staged"
            st.firm.add("compile")
            st.donate = False
        else:
            st.legs["compile"] = (
                "fused" if _would_fuse(in_rows) else "staged"
            )
        if not groupby and node.method in SORT_SHAPED:
            st.legs["kernel"] = _plan_kernel(node, in_rows, state, table)
            st.leg_ops["kernel"] = node.method
    elif isinstance(node, Sort):
        st.legs["layout"] = _plan_layout(in_rows, table)
        st.leg_ops["layout"] = "sort"


def _plan_residency(in_bytes: Optional[int]) -> str:
    """Mirror of ``decide_residency``'s Auto arm over the plan-time
    estimate of the consumed working set (same ledger, same headroom
    arithmetic), so steady-state plans agree with the live router and
    only re-plans deviate."""
    from modin_tpu.config import StreamMode
    from modin_tpu.core.memory import device_ledger

    mode = StreamMode.get().lower()
    if mode == "resident":
        return "resident"
    if mode == "windowed":
        return "windowed"
    budget = device_ledger.budget()
    if budget is None or in_bytes is None:
        return "resident"
    headroom = budget - max(device_ledger.total_bytes(), 0)
    return "windowed" if in_bytes > headroom else "resident"


def _would_fuse(est_rows: Optional[int]) -> bool:
    from modin_tpu.config import FuseMinRows, FuseMode

    mode = FuseMode.get().lower()
    if mode == "fused":
        return True
    if mode == "staged":
        return False
    return est_rows is not None and est_rows >= int(FuseMinRows.get())


def _plan_kernel(
    node: Reduce,
    in_rows: Optional[int],
    state: PlanStrategies,
    table: Optional[Dict[str, float]],
) -> str:
    """Annotated device/host leg for a sort-shaped reduction.

    A live whole-result graftview artifact answers for free: the ``view``
    leg.  Otherwise the kernel router's own predicted costs (under the
    current correction) pick the side.  The annotation firms up only
    after a re-plan — pre-divergence the runtime ``decide()`` sees the
    real per-column strategies and stays authoritative.
    """
    if _view_hit(node):
        return "view"
    if table is None or in_rows is None:
        return "device"
    try:
        costs = router.predicted_costs(node.method, in_rows, ["sort"], table)
    except KeyError:
        return "device"
    device_s = costs["device_s"] * state.correction
    if device_s - costs["host_s"] > router.MIN_SAVINGS_S:
        return "host"
    return "device"


def _view_hit(node: Reduce) -> bool:
    """Whether a live graftview artifact already answers this reduction
    over an in-memory Source (planning probe: no metrics, no LRU touch)."""
    child = node.children[0]
    if not isinstance(child, Source):
        return False
    try:
        from modin_tpu.views import registry as view_registry

        frame = child.qc._modin_frame
        if frame is None:
            return False
        sortred = f"sortred.{node.method}"
        for col in frame._columns:
            for kind in view_registry.column_artifact_kinds(col):
                if kind == "reduce" or kind == sortred:
                    return True
        return False
    except Exception:  # the view probe is advisory; a failed peek just loses the free-leg annotation
        return False


def _plan_layout(
    in_rows: Optional[int], table: Optional[Dict[str, float]]
) -> str:
    """Annotated local/sharded leg (EXPLAIN only; the live
    ``decide_layout`` stays authoritative — it sees payload widths)."""
    if (
        table is None
        or "device_shuffle_s" not in table
        or in_rows is None
    ):
        return "local"
    logscale = calstore.nlogn_scale(in_rows, int(table["rows"]))
    sharded_s = table["device_shuffle_s"] * logscale
    local_s = table["device_sort_s"] * logscale
    return "sharded" if sharded_s < local_s else "local"


# ---------------------------------------------------------------------- #
# lowering integration: node scope, observation, re-planning
# ---------------------------------------------------------------------- #


def begin(state: PlanStrategies, root: PlanNode, memo: dict) -> None:
    """Install a strategy set for one lowering pass (called by
    ``lowering.lower_traced``; always paired with :func:`end`)."""
    state.root = root
    state.done = memo
    _tls.state = state
    _tls.stack = []


def end() -> None:
    _tls.state = None
    _tls.stack = None


def push_node(node: PlanNode) -> None:
    state = getattr(_tls, "state", None)
    if state is not None:
        _tls.stack.append(state.by_node.get(id(node)))


def pop_node() -> None:
    stack = getattr(_tls, "stack", None)
    if stack:
        stack.pop()


def _current() -> Tuple[Optional[PlanStrategies], Optional[NodeStrategy]]:
    state = getattr(_tls, "state", None)
    if state is None:
        return None, None
    stack = getattr(_tls, "stack", None)
    return state, (stack[-1] if stack else None)


def donate_ok() -> bool:
    """Whether the current node's plan admits input donation (graftfuse
    consults this before building donate_cols): False once the joint
    constraints or a re-plan marked the plan memory-pressured."""
    _state, st = _current()
    return st.donate if st is not None else True


def note_stream_bytes(nbytes: int) -> None:
    """graftstream reports the sniffed working set of a streamed source
    (EXPLAIN renders it against the estimate)."""
    _state, st = _current()
    if st is not None:
        st.measured_bytes = int(nbytes)


def observe(node: PlanNode, total_s: float) -> None:
    """Feed one lowered node's measured wall back into the model; fires
    the ``wall_divergence`` re-plan when the estimate was wrong by more
    than ``MODIN_TPU_OPT_REPLAN_FACTOR``."""
    state = getattr(_tls, "state", None)
    if state is None:
        return
    st = state.by_node.get(id(node))
    if st is None:
        return
    st.measured_s = total_s
    if st.est_s <= 0.0 or total_s <= REPLAN_NOISE_FLOOR_S:
        return
    from modin_tpu.config import OptReplanFactor

    factor = float(OptReplanFactor.get())
    if total_s <= st.est_s * factor:
        return
    ratio = min(total_s / st.est_s, MAX_CORRECTION)
    _replan(
        state,
        "wall_divergence",
        key=id(node),
        node_label=type(node).__name__,
        est_s=st.est_s,
        measured_s=total_s,
        correction=ratio,
    )


def _replan(state: PlanStrategies, trigger: str, key: Any, **attrs: Any) -> bool:
    """Re-optimize the not-yet-lowered plan segment; at most once per
    (key, trigger).  Returns whether the re-plan ran."""
    fired_key = (key, trigger)
    if fired_key in state.fired or state.root is None:
        return False
    state.fired.add(fired_key)
    correction = attrs.get("correction")
    if correction is not None:
        state.correction = max(state.correction, float(correction))
    exclude = set(state.done or ())
    t0 = time.perf_counter()
    choose(state.root, state=state, exclude=exclude)
    if trigger == "compile_storm":
        # the storm is a property of the signature, not the estimates: a
        # re-chosen tail would still say "fused" — pin the remaining
        # compile legs staged outright
        for nid, st in state.by_node.items():
            if nid not in exclude and "compile" in st.legs:
                st.legs["compile"] = "staged"
                st.firm.add("compile")
    event = {
        "trigger": trigger,
        "remaining_nodes": len(state.by_node) - len(exclude),
        **{
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in attrs.items()
        },
    }
    state.replans.append(event)
    emit_metric(f"opt.replan.{trigger}", 1)
    if graftscope.TRACE_ON:
        graftscope.finish_span(
            graftscope.start_span(
                "opt.replan",
                layer="PLAN",
                attrs={
                    **event,
                    "replan_s": round(time.perf_counter() - t0, 6),
                },
            )
        )
    return True


# ---------------------------------------------------------------------- #
# the router consult hook
# ---------------------------------------------------------------------- #


def _consult(
    leg: str, choice: str, reason: str, **ctx: Any
) -> Optional[Tuple[str, str]]:
    """Answer one live router decision from the plan-time strategy.

    Returns a replacement ``(choice, reason)`` only where the plan (or a
    re-plan) genuinely disagrees with the live verdict — agreement keeps
    the router's own choice and reason, so steady-state traces are
    indistinguishable from the pre-graftopt engine.
    """
    state, st = _current()
    if state is None:
        return None
    if leg == "residency":
        return _consult_residency(state, st, choice, ctx)
    if leg == "compile":
        return _consult_compile(state, st, choice, ctx)
    if leg == "kernel":
        return _consult_kernel(state, st, choice, ctx)
    # layout: both calibrated sides scale by the same correction, so a
    # re-plan never flips it — the live decide_layout stays authoritative
    return None


def _consult_residency(
    state: PlanStrategies,
    st: Optional[NodeStrategy],
    choice: str,
    ctx: Dict[str, Any],
) -> Optional[Tuple[str, str]]:
    if st is None or st.leg_ops.get("residency") != ctx.get("op"):
        return None
    planned = st.legs.get("residency")
    if planned is None:
        return None
    if planned == "resident" and choice == "windowed":
        # live ledger pressure contradicts the plan: flip the remaining
        # segment (the re-choose reads the pressured ledger and windows
        # the tail), follow the live verdict for THIS node
        st.legs["residency"] = "windowed"
        st.legs["compile"] = "staged"
        st.firm.update(("residency", "compile"))
        st.donate = False
        _replan(
            state,
            "ledger_pressure",
            key=id(st.node),
            est_bytes=int(ctx.get("est_bytes") or 0),
        )
        return ("windowed", "graftopt_replan")
    if planned != choice:
        return (planned, "graftopt")
    return None


def _consult_compile(
    state: PlanStrategies,
    st: Optional[NodeStrategy],
    choice: str,
    ctx: Dict[str, Any],
) -> Optional[Tuple[str, str]]:
    if choice == "fused":
        level = 0
        try:
            from modin_tpu.plan import fuse

            level = fuse.storm_level(ctx.get("sig"))
        except Exception:  # storm bookkeeping is advisory; an unreadable level keeps the live verdict
            level = 0
        if level >= 1:
            if st is not None:
                st.legs["compile"] = "staged"
                st.firm.add("compile")
            _replan(
                state,
                "compile_storm",
                key=("sig", ctx.get("sig")),
                storm_level=level,
            )
            return ("staged", "graftopt_replan")
    if st is not None and "compile" in st.firm:
        planned = st.legs.get("compile")
        if planned is not None and planned != choice:
            return (planned, "graftopt")
    return None


def _consult_kernel(
    state: PlanStrategies,
    st: Optional[NodeStrategy],
    choice: str,
    ctx: Dict[str, Any],
) -> Optional[Tuple[str, str]]:
    if state.correction <= 1.0:
        # pre-divergence the live decide() is authoritative: it sees the
        # real per-column strategies the plan could only guess at
        return None
    table = router.calibration_peek()
    if table is None:
        return None
    try:
        costs = router.predicted_costs(
            str(ctx.get("op")),
            int(ctx.get("n") or 0),
            list(ctx.get("strategies") or ["sort"]),
            table,
        )
    except KeyError:
        return None
    corrected = (
        "host"
        if costs["device_s"] * state.correction - costs["host_s"]
        > router.MIN_SAVINGS_S
        else "device"
    )
    if corrected != choice:
        if st is not None:
            st.legs["kernel"] = corrected
            st.firm.add("kernel")
        return (corrected, "graftopt_replan")
    return None


# the subscription fires immediately (installing/clearing the router hook
# for the current mode), so it lives below every function it references
from modin_tpu.config import OptMode as _OptMode  # noqa: E402

_OptMode.subscribe(_on_opt_mode)
