"""Per-method backend casting and cost-driven auto-switching.

Reference design: modin/core/storage_formats/pandas/query_compiler_caster.py
(:527 register, :925 the method wrapper, :598/:660 pre/post-op switch
points).  The reference wraps every public API method; here the wrap happens
one layer lower, on every public method of each concrete query compiler:

- **argument casting** (always on): a call whose arguments mix backends
  (a device frame merged with an in-process frame) routes every argument —
  including ``self`` — to the cheapest common backend, chosen by
  :class:`~.query_compiler_calculator.BackendCostCalculator` from the
  compilers' stay/move costs.  The TPU cost model makes this
  PCIe-transfer aware: big device frames pull small host frames to
  the device, not the reverse.
- **pre-op auto-switch** (``AutoSwitchBackend`` config, default off): even
  single-backend calls compare the cost of staying against moving to each
  registered backend for this specific operation, and relocate when
  strictly cheaper — e.g. a small device frame about to run an operation
  with no device kernel (which would round-trip through host pandas anyway)
  moves to the Native backend once instead.

Wrapping happens in ``BaseQueryCompiler.__init_subclass__`` so any new
storage format participates automatically.
"""

from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Dict, List, Optional, Tuple, Type

# concrete QC classes that can host data (filled by __init_subclass__)
_BACKEND_REGISTRY: List[type] = []

# methods that must never cast/switch: conversion+introspection machinery the
# caster itself relies on, and lifecycle hooks
_EXCLUDED = {
    "from_pandas", "to_pandas", "from_arrow", "to_numpy", "to_interchange",
    "from_interchange", "to_dataframe", "from_dataframe", "execute", "free",
    "finalize", "copy", "stay_cost", "move_to_cost", "move_to_me_cost",
    "default_to_pandas", "get_index", "get_columns", "get_axis_len",
    "get_backend", "set_backend", "qc_engine_switch_max_cost", "execute_on",
    "support_materialization_in_worker_process", "get_pandas_backend",
}


def register_backend_qc(cls: type) -> None:
    if cls not in _BACKEND_REGISTRY:
        _BACKEND_REGISTRY.append(cls)


def qc_class_for_backend(backend: str) -> type:
    """Resolve a backend name ("Tpu", "Pandas", ...) to its QC class."""
    from modin_tpu.core.execution.dispatching.factories.dispatcher import (
        FactoryDispatcher,
    )

    for cls in _BACKEND_REGISTRY:
        if FactoryDispatcher.get_backend_for_compiler(cls) == backend:
            return cls
    raise ValueError(f"No query compiler registered for backend {backend!r}")


def _iter_qcs(base_cls: type, args: tuple, kwargs: dict):
    for a in args:
        if isinstance(a, base_cls):
            yield a
        elif isinstance(a, (list, tuple)):
            for x in a:
                if isinstance(x, base_cls):
                    yield x
    for a in kwargs.values():
        if isinstance(a, base_cls):
            yield a
        elif isinstance(a, (list, tuple)):
            for x in a:
                if isinstance(x, base_cls):
                    yield x


def _cast_tree(value: Any, base_cls: type, target: type):
    if isinstance(value, base_cls):
        return value if type(value) is target else target.from_pandas(value.to_pandas())
    if isinstance(value, list):
        return [_cast_tree(v, base_cls, target) for v in value]
    if isinstance(value, tuple):
        return tuple(_cast_tree(v, base_cls, target) for v in value)
    return value


def _backend_costs(
    operation: str, compilers: List[Any], candidates: List[type]
) -> Dict[type, int]:
    """Aggregate stay+move cost of landing all compilers on each candidate."""
    from modin_tpu.core.storage_formats.base.query_compiler import QCCoercionCost

    totals: Dict[type, int] = {}
    for target in candidates:
        total = 0
        for qc in compilers:
            if type(qc) is target:
                cost = qc.stay_cost(None, operation, {})
                total += int(cost) if cost is not None else QCCoercionCost.COST_MEDIUM
            else:
                # both sides price the move: sender's transfer cost plus the
                # receiver's willingness (reference calculator aggregates both)
                cost = qc.move_to_cost(target, None, operation, {})
                total += int(cost) if cost is not None else QCCoercionCost.COST_MEDIUM
                me = target.move_to_me_cost(qc, None, operation, {})
                if me is not None:
                    total += int(me)
        totals[target] = total
    return totals


def _cheapest_backend(
    operation: str, compilers: List[Any], candidates: List[type]
) -> Optional[type]:
    totals = _backend_costs(operation, compilers, candidates)
    best, best_total = None, None
    for target in candidates:  # first candidate wins ties
        if best_total is None or totals[target] < best_total:
            best, best_total = target, totals[target]
    return best


# Explicit switch points (reference: query_compiler_caster.py:1222,1243
# register_function_for_post_op_switch / pre_op_switch): entries are
# (class_name or None, backend, method).  Pre-op points force backend
# consideration for a specific (backend, method) even while the global
# every-method auto-switch heuristic is off; post-op points re-price the
# RESULT after the op (ops known to shrink data hand small results to the
# in-process backend).
_PRE_OP_SWITCH_POINTS: set = set()
_POST_OP_SWITCH_POINTS: set = set()


def register_function_for_pre_op_switch(
    class_name: Optional[str] = None, backend: Optional[str] = None, method: str = ""
) -> None:
    _PRE_OP_SWITCH_POINTS.add((class_name, backend, method))


def register_function_for_post_op_switch(
    class_name: Optional[str] = None, backend: Optional[str] = None, method: str = ""
) -> None:
    _POST_OP_SWITCH_POINTS.add((class_name, backend, method))


def _is_switch_point(registry: set, backend: str, method: str) -> bool:
    return any(
        m == method and (b is None or b == backend) for (_c, b, m) in registry
    )


def _maybe_switch_result_backend(result: Any, name: str, self_type: type) -> Any:
    """Post-op backend switch: re-price the result and move it if strictly
    cheaper elsewhere (reference: _maybe_switch_backend_post_op :660)."""
    from modin_tpu.core.storage_formats.base.query_compiler import (
        BaseQueryCompiler,
    )

    if not isinstance(result, BaseQueryCompiler):
        return result
    result_type = type(result)
    candidates = [result_type] + [
        t for t in _BACKEND_REGISTRY if t is not result_type
    ]
    best = _cheapest_backend(name, [result], candidates)
    if best is not None and best is not result_type:
        moved = best.move_from(result)
        moved._shape_hint = result._shape_hint
        return moved
    return result


def _wrap_method(name: str, fn: Callable) -> Callable:
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        from modin_tpu.core.storage_formats.base.query_compiler import (
            BaseQueryCompiler,
        )

        self_type = type(self)
        others = [
            qc for qc in _iter_qcs(BaseQueryCompiler, args, kwargs)
        ]
        mixed = any(type(qc) is not self_type for qc in others)

        backend_name: Optional[str] = None
        if _PRE_OP_SWITCH_POINTS or _POST_OP_SWITCH_POINTS:
            from modin_tpu.core.execution.dispatching.factories.dispatcher import (
                FactoryDispatcher,
            )

            backend_name = FactoryDispatcher.get_backend_for_compiler(self_type)

        target: Optional[type] = None
        if mixed:
            candidates: List[type] = []
            for qc in [self, *others]:
                if type(qc) not in candidates:
                    candidates.append(type(qc))
            target = _cheapest_backend(name, [self, *others], candidates)
        else:
            from modin_tpu.config import AutoSwitchBackend

            consider = AutoSwitchBackend.get() or (
                backend_name is not None
                and _is_switch_point(_PRE_OP_SWITCH_POINTS, backend_name, name)
            )
            if consider and len(_BACKEND_REGISTRY) > 1:
                # self first: _cheapest_backend breaks ties toward the first
                # candidate, so staying put wins unless strictly cheaper
                candidates = [self_type] + [
                    t for t in _BACKEND_REGISTRY if t is not self_type
                ]
                best = _cheapest_backend(name, [self, *others], candidates)
                if best is not None and best is not self_type:
                    target = best

        if target is not None and (
            mixed or target is not self_type
        ):
            new_self = (
                self if self_type is target
                else target.from_pandas(self.to_pandas())
            )
            new_args = tuple(
                _cast_tree(a, BaseQueryCompiler, target) for a in args
            )
            new_kwargs = {
                k: _cast_tree(v, BaseQueryCompiler, target)
                for k, v in kwargs.items()
            }
            if self_type is target:
                result = fn(new_self, *new_args, **new_kwargs)
            else:
                result = getattr(new_self, name)(*new_args, **new_kwargs)
        else:
            result = fn(self, *args, **kwargs)

        if backend_name is not None and _is_switch_point(
            _POST_OP_SWITCH_POINTS, backend_name, name
        ):
            result = _maybe_switch_result_backend(result, name, self_type)
        return result

    wrapper.__qc_cast_wrapped__ = True
    return wrapper


def wrap_query_compiler_methods(cls: type) -> None:
    """Install casting wrappers over every public method of a concrete QC."""
    for name in dir(cls):
        if name.startswith("_") or name in _EXCLUDED:
            continue
        static = inspect.getattr_static(cls, name)
        if isinstance(static, (classmethod, staticmethod, property)):
            continue
        fn = getattr(cls, name, None)
        if not inspect.isfunction(fn):
            continue
        if getattr(fn, "__qc_cast_wrapped__", False):
            continue
        setattr(cls, name, _wrap_method(name, fn))
