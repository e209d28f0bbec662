"""Deferred elementwise expression DAG — the op-fusion layer.

TPU-native analogue of the reference's DeferredExecution batching
(modin/core/execution/ray/common/deferred_execution.py:43): the reference
accumulates chained operations per partition and materializes them in one
remote call; here the batching currency is the *XLA program*.  Chained
column expressions accumulate into a small DAG of ``LazyExpr`` nodes, and the
whole chain compiles as ONE jit when a consumer needs concrete data — so
``(a * b + c).sum()`` lowers to a single fused kernel (one dispatch, no
intermediate HBM round-trips) instead of three.

Design notes:

- Leaves are concrete jax.Arrays (padded, sharded device columns) or Python /
  numpy scalars.  Scalars are passed as *runtime jit arguments*, not baked
  into the compiled program, so ``df * 2`` and ``df * 3`` share a
  compilation; jax keeps Python scalars weakly typed, preserving numpy
  promotion semantics.
- Graphs are linearized (postorder, diamond nodes computed once) into a
  structural fingerprint; compiled executables are cached per fingerprint.
  jit itself re-specializes per input sharding, so one cache entry serves
  any mesh layout.
- A fused call can end in a *tail* (e.g. the per-column reduction kernels),
  fusing map chains into their consuming reduction: ``(a*b+c).sum()`` is the
  canonical win.
- ``_MAX_NODES`` caps the fusion window so pathological op chains (loops
  mutating a column thousands of times) do not build unbounded XLA programs.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from modin_tpu.concurrency import named_lock
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import meters as graftmeter
from modin_tpu.observability import spans as graftscope
from modin_tpu.ops._program import named_jit
from modin_tpu.serving import context as serving_context

_MAX_NODES = 160

_SCALAR_TYPES = (int, float, bool, np.integer, np.floating, np.bool_)

# fingerprint -> jitted executable, LRU-bounded by MODIN_TPU_FUSED_CACHE_SIZE
# (each entry pins an XLA executable; a long session with varying expression
# shapes previously grew this without limit).  All access is serialized by
# _FUSED_LOCK: concurrent queries (graftgate) hit this cache from many
# threads, and an unguarded OrderedDict move_to_end racing a popitem can
# corrupt the dict's internal linkage, not just return a stale entry.
_FUSED_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_FUSED_LOCK = named_lock("ops.fused_cache")
_evictions = 0


def _fused_cache_get(key: Any) -> Optional[Any]:
    with _FUSED_LOCK:
        fn = _FUSED_CACHE.get(key)
        if fn is not None:
            _FUSED_CACHE.move_to_end(key)
    if fn is not None and graftmeter.ACCOUNTING_ON:
        emit_metric("fusion.cache.hit", 1)
    return fn


def _fused_cache_put(key: Any, fn: Any) -> None:
    global _evictions
    from modin_tpu.config import FusedCacheSize

    limit = FusedCacheSize.get()
    evicted = 0
    with _FUSED_LOCK:
        _FUSED_CACHE[key] = fn
        _FUSED_CACHE.move_to_end(key)
        if limit > 0:
            while len(_FUSED_CACHE) > limit:
                _FUSED_CACHE.popitem(last=False)
                evicted += 1
        if evicted:
            _evictions += evicted
    if evicted:
        emit_metric("fusion.cache.evict", evicted)


def fused_cache_evictions() -> int:
    """Process-lifetime count of fused executables evicted by the LRU."""
    return _evictions


def fused_cache_len() -> int:
    return len(_FUSED_CACHE)


class LazyExpr:
    """One deferred op node: ``op(*args, **dict(static))``.

    ``op`` names a function in the elementwise registry
    (:func:`modin_tpu.ops.elementwise.get_op`); ``args`` are LazyExpr
    children, jax.Array leaves, or scalars; ``static`` is a hashable tuple of
    keyword pairs compiled into the program (e.g. round decimals).
    """

    __slots__ = ("op", "args", "static", "aval", "size", "_result")

    def __init__(self, op: str, args: Tuple[Any, ...], static: Tuple = ()):
        self.op = op
        self.args = args
        self.static = static
        self._result = None
        size = 1
        for a in args:
            if isinstance(a, LazyExpr) and a._result is None:
                size += a.size
        self.size = size
        self.aval = _eval_aval(op, args, static)

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def shape(self):
        return self.aval.shape

    def astype(self, dtype) -> "LazyExpr":
        return lazy_op("astype", self, static=(("dtype", str(np.dtype(dtype))),))

    def __repr__(self) -> str:
        return f"LazyExpr({self.op}, size={self.size}, aval={self.aval})"


# (op, static, one fact per argument) -> the node's ShapeDtypeStruct.  A
# frame's columns build the same nodes request after request, and tracing one
# costs a millisecond of host time in which the device has nothing to run.
# Holds shapes and dtypes only, never an operand; LRU-bounded, serialized by
# its own lock like _FUSED_CACHE.
_AVAL_MEMO: "OrderedDict[Any, Any]" = OrderedDict()
_AVAL_MEMO_LOCK = named_lock("ops.aval_memo")
_AVAL_MEMO_SIZE = 4096

# eval_shape refuses a Python int outside int64: such a node is traced, and
# raises, as without the memo
_INT64_MIN, _INT64_MAX = -(2**63), 2**63 - 1


def _aval_key(op: str, args: Tuple[Any, ...], static: Tuple) -> Optional[Tuple]:
    """What ``jax.eval_shape`` reads of a node, as the fused cache's
    fingerprint keys it (:func:`_linearize`): an unmaterialized child's aval,
    a leaf's dtype, shape, weak type and sharding (which the trace sees, though
    the aval it returns carries none), a scalar's type (its value never
    reaches the trace).  Each fact leads with its kind: a numpy dtype
    compares equal to ``int`` or ``"int64"``.  ``None`` where an argument is
    of another kind, or the key is unhashable."""
    import jax

    facts = []
    for a in args:
        if isinstance(a, LazyExpr):
            if a._result is None:
                av = a.aval
                facts.append(("node", av.shape, av.dtype, av.weak_type))
                continue
            a = a._result
        if isinstance(a, jax.Array):
            facts.append(("leaf", a.dtype, a.shape, a.weak_type, a.sharding))
        elif isinstance(a, (np.integer, np.floating, np.bool_)):
            facts.append(("np", a.dtype))
        elif isinstance(a, _SCALAR_TYPES):
            if isinstance(a, int) and not _INT64_MIN <= a <= _INT64_MAX:
                return None
            facts.append(("py", type(a)))
        else:
            return None
    key = (op, static, tuple(facts))
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _eval_aval(op: str, args: Tuple[Any, ...], static: Tuple):
    """Abstract-evaluate one node (shape/dtype only; no compile), from
    :data:`_AVAL_MEMO` where the same node was evaluated before."""
    key = _aval_key(op, args, static)
    if key is None:
        outcome = "bypass"
        aval = _trace_aval(op, args, static)
    else:
        with _AVAL_MEMO_LOCK:
            aval = _AVAL_MEMO.get(key)
            if aval is not None:
                _AVAL_MEMO.move_to_end(key)
        outcome = "hit"
        if aval is None:
            outcome = "miss"
            aval = _trace_aval(op, args, static)
            with _AVAL_MEMO_LOCK:
                _AVAL_MEMO[key] = aval
                while len(_AVAL_MEMO) > _AVAL_MEMO_SIZE:
                    _AVAL_MEMO.popitem(last=False)
    if graftmeter.ACCOUNTING_ON:
        graftmeter.note_aval_memo(outcome)
    return aval


def _trace_aval(op: str, args: Tuple[Any, ...], static: Tuple):
    import jax

    from modin_tpu.ops.elementwise import get_op

    fn = get_op(op)
    kw = dict(static)
    abstract_args = []
    for a in args:
        if isinstance(a, LazyExpr):
            abstract_args.append(
                a._result if a._result is not None else a.aval
            )
        else:
            # concrete arrays and scalars: eval_shape abstracts them itself,
            # preserving weak typing for Python scalars
            abstract_args.append(a)
    return jax.eval_shape(lambda *xs: fn(*xs, **kw), *abstract_args)


def is_lazy(x: Any) -> bool:
    return isinstance(x, LazyExpr) and x._result is None


def _distinct_size(root: LazyExpr) -> int:
    """Exact count of distinct unmaterialized nodes (diamonds counted once)."""
    seen = set()
    stack = [root]
    while stack:
        e = stack.pop()
        if not isinstance(e, LazyExpr) or e._result is not None or id(e) in seen:
            continue
        seen.add(id(e))
        stack.extend(a for a in e.args if isinstance(a, LazyExpr))
    return len(seen)


def lazy_op(op: str, *args: Any, static: Tuple = ()) -> LazyExpr:
    """Build a deferred node; oversized graphs materialize immediately."""
    e = LazyExpr(op, args, static)
    if e.size > _MAX_NODES:
        # size is a cheap upper bound that double-counts diamond sharing;
        # confirm with the exact distinct count before giving up on fusion
        e.size = _distinct_size(e)
        if e.size > _MAX_NODES:
            materialize_exprs([e])
    return e


def _linearize(roots: Sequence[Any]):
    """Flatten an expression forest into an executable spec.

    Returns (nodes, out_refs, leaves, scalars, fingerprint): ``nodes`` is a
    postorder list of (op, arg_refs, static); a ref is ('n', i) node, ('l', i)
    leaf, or ('s', i) scalar.  Diamond-shared nodes appear once.
    """
    nodes: List[Tuple] = []
    node_idx: Dict[int, int] = {}
    leaves: List[Any] = []
    leaf_idx: Dict[int, int] = {}
    leaf_tags: List[Tuple] = []
    scalars: List[Any] = []
    scalar_tags: List[str] = []

    def visit_leaf(x) -> Tuple[str, int]:
        i = leaf_idx.get(id(x))
        if i is None:
            i = len(leaves)
            leaves.append(x)
            leaf_idx[id(x)] = i
            leaf_tags.append((str(x.dtype), x.shape, bool(getattr(x, "weak_type", False))))
        return ("l", i)

    def visit(e) -> Tuple[str, int]:
        if isinstance(e, LazyExpr):
            if e._result is not None:
                return visit_leaf(e._result)
            i = node_idx.get(id(e))
            if i is not None:
                return ("n", i)
            refs = tuple(visit(a) for a in e.args)
            nodes.append((e.op, refs, e.static))
            i = len(nodes) - 1
            node_idx[id(e)] = i
            return ("n", i)
        if isinstance(e, _SCALAR_TYPES):
            scalars.append(e)
            scalar_tags.append(
                str(np.dtype(type(e))) if isinstance(e, np.generic) else type(e).__name__
            )
            return ("s", len(scalars) - 1)
        return visit_leaf(e)

    out_refs = tuple(visit(r) for r in roots)
    fingerprint = (
        tuple(nodes),
        out_refs,
        tuple(leaf_tags),
        tuple(scalar_tags),
    )
    return nodes, out_refs, leaves, scalars, fingerprint


def _cache_epoch_key() -> Tuple:
    """(mesh shape, device epoch) component of every fused-cache key.

    A program traced under one mesh topology bakes that topology's
    sharding into its compiled executable — an in-process ``MeshShape``
    flip (the ``_jit_shuffle`` stale-program class graftmesh fixed) must
    never reuse it.  The device epoch guards the same way across a
    graftguard re-seat: post-loss executables are retraced rather than
    trusted to hold no dead device state.  Both reads are cached module
    attributes (no lock, no mesh build) on the hot path.
    """
    try:
        from modin_tpu.core.execution.recovery import current_epoch
        from modin_tpu.parallel.mesh import mesh_shape_key

        return (mesh_shape_key(), current_epoch())
    except Exception:  # graftlint: disable=EXC-HYGIENE -- no backend/mesh yet: a single unkeyed epoch is the pre-mesh world
        return ("unknown", 0)


_donation_filter_installed = False


def _ensure_donation_warning_filter() -> None:
    """One-time, process-wide suppression of jax's "Some donated buffers
    were not usable" UserWarning.

    The fused reduce/groupby tails output scalars and small tables, so no
    output shape ever aliases a full-length donated input and jax warns on
    every compiled shape — but the donation is still doing its job (the
    buffer is deleted at dispatch, the early HBM release the ledger
    records), so the warning is pure noise.  Installed lazily at the first
    donated dispatch (a process that never donates keeps its filters
    untouched) and module-global rather than per-dispatch: a scoped
    ``catch_warnings`` mutates process-global filter state non-atomically,
    which two concurrently-dispatching threads can corrupt.
    """
    global _donation_filter_installed
    if _donation_filter_installed:
        return
    import warnings

    with _FUSED_LOCK:
        if not _donation_filter_installed:
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            _donation_filter_installed = True


def _tail_name(tail_key: Optional[Tuple]) -> str:
    """The leading strings of a tail's cache key: ``("reduce", "sum", n,
    ...)`` -> ``reduce_sum``; ``tail`` when it leads with none."""
    parts = []
    for item in tail_key or ():
        if not isinstance(item, str):
            break
        parts.append(item)
    return "_".join(parts) or "tail"


def _program_name(nodes: Sequence[Tuple], tail_key: Optional[Tuple]) -> str:
    """``plan_<ops in first-seen order>[_<tail>]``: ``plan_mod``,
    ``plan_add_reduce_sum``."""
    parts = list(dict.fromkeys(op for op, _refs, _static in nodes))
    if tail_key:
        parts.append(_tail_name(tail_key))
    return "plan_" + "_".join(parts)


def _fused_program(
    roots: Sequence[Any],
    tail_key: Optional[Tuple],
    tail_builder: Optional[Callable[[List[Any]], Any]],
    donate: Optional[frozenset],
):
    """Linearize the forest and find (or build) its executable.

    Returns ``(program, leaves, scalars, donate_positions)``."""
    nodes, out_refs, leaves, scalars, fingerprint = _linearize(roots)
    donate_positions: Tuple[int, ...] = ()
    if donate:
        donate_positions = tuple(
            i for i, leaf in enumerate(leaves) if id(leaf) in donate
        )
    # the donated positions are part of the executable's identity: jit
    # fixes donate_argnums at wrap time, so the same forest with and
    # without donation is two programs
    key = (fingerprint, tail_key, _cache_epoch_key(), donate_positions)
    fn = _fused_cache_get(key)
    if fn is None:
        import jax

        from modin_tpu.ops.elementwise import get_op

        nodes_spec = tuple(nodes)

        def execute(scalar_vals: Tuple, *leaf_vals):
            vals: List[Any] = []

            def res(ref):
                kind, i = ref
                if kind == "n":
                    return vals[i]
                if kind == "l":
                    return leaf_vals[i]
                return scalar_vals[i]

            for op, refs, static in nodes_spec:
                with jax.named_scope(op):
                    vals.append(get_op(op)(*[res(r) for r in refs], **dict(static)))
            outs = [res(r) for r in out_refs]
            if tail_builder is None:
                return tuple(outs)
            with jax.named_scope(_tail_name(tail_key)):
                return tail_builder(outs)

        fn = named_jit(
            execute,
            _program_name(nodes, tail_key),
            # +1: argument 0 is the scalar tuple (never donated)
            donate_argnums=tuple(p + 1 for p in donate_positions),
        )
        _fused_cache_put(key, fn)
    return fn, leaves, scalars, donate_positions


def run_fused(
    roots: Sequence[Any],
    tail_key: Optional[Tuple] = None,
    tail_builder: Optional[Callable[[List[Any]], Any]] = None,
    donate: Optional[frozenset] = None,
):
    """Compile + run the whole forest (and optional tail) as one jit.

    Without a tail: returns the list of concrete arrays for ``roots`` and
    memoizes each root LazyExpr's result.  With a tail: the tail builder is
    traced over the root arrays inside the same jit (fusing e.g. a reduction
    into its elementwise producers) and its output is returned.

    ``donate`` is a set of ``id(buffer)`` for concrete leaf arrays the
    caller proved have no other live consumer (graftfuse: the device ledger
    ref-count): those leaves are passed in donated positions
    (``donate_argnums``), so XLA frees them the moment the dispatch is done
    with them — and reuses them in place where an output shape aliases an
    input — instead of every input surviving to the next GC pass.  The
    caller owns the donation contract — marking the owning columns spilled
    so later reads restore via lineage instead of touching the consumed
    buffer.
    """
    if serving_context.CONTEXT_ON:
        # graftgate deadline boundary: fused-chain materialization is where
        # a deferred query finally pays for its whole expression forest —
        # check before linearize/compile, not after
        serving_context.check_deadline("fusion.run_fused")

    if tail_builder is None and not any(is_lazy(r) for r in roots):
        return [r._result if isinstance(r, LazyExpr) else r for r in roots]

    with graftscope.span("lazy.linearize", layer="PLAN", roots=len(roots)):
        fn, leaves, scalars, donate_positions = _fused_program(
            roots, tail_key, tail_builder, donate
        )

    # dispatch through the engine seam: the fused call gets the resilience
    # policy (classify/retry/recovery) and op-replay lineage provenance
    # exactly like every other device computation
    from modin_tpu.parallel.engine import JaxWrapper

    if donate_positions:
        _ensure_donation_warning_filter()
        result = JaxWrapper.deploy(
            fn,
            (tuple(scalars), *leaves),
            # a donated program must never be replayed from provenance:
            # replay would re-donate (and delete) the freshly restored
            # input buffers under their columns.  Its outputs are
            # materialized to host at the call site, so they never need
            # op-replay lineage anyway.
            donated=True,
        )
    else:
        result = JaxWrapper.deploy(fn, (tuple(scalars), *leaves))
    if tail_builder is not None:
        return result
    for root, value in zip(roots, result):
        if isinstance(root, LazyExpr):
            root._result = value
    return list(result)


def leaf_buffer_ids(roots: Sequence[Any]) -> frozenset:
    """``id()`` of every concrete array leaf an expression forest consumes.

    The graftfuse donation path intersects its candidate columns with this
    set so only buffers the program actually receives are marked consumed —
    a candidate outside the forest must stay resident.
    """
    ids = set()
    seen = set()
    stack = list(roots)
    while stack:
        e = stack.pop()
        if isinstance(e, LazyExpr):
            if e._result is not None:
                ids.add(id(e._result))
                continue
            if id(e) in seen:
                continue
            seen.add(id(e))
            stack.extend(e.args)
        elif not isinstance(e, _SCALAR_TYPES) and hasattr(e, "dtype"):
            ids.add(id(e))
    return frozenset(ids)


def materialize_exprs(items: Sequence[Any]) -> List[Any]:
    """Concrete jax.Arrays for a mixed list of arrays/exprs (one jit)."""
    return run_fused(items)


def materialize(item: Any):
    if is_lazy(item):
        return run_fused([item])[0]
    return item._result if isinstance(item, LazyExpr) else item
