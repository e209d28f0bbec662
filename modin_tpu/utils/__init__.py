"""Shared utilities: docstring inheritance, pandas casting helpers, versions.

Reference design: /root/reference/modin/utils.py (notably ``_inherit_docstrings``
at :544 and ``show_versions`` at :901).
"""

from __future__ import annotations

import functools
import importlib
import json
import platform
import re
import sys
import types
from typing import Any, Callable, Iterable, List, Optional, TypeVar, Union

import numpy as np
import pandas
from pandas.util._decorators import Appender

MODIN_UNNAMED_SERIES_LABEL = "__reduced__"
PANDAS_API_URL_TEMPLATE = (
    "https://pandas.pydata.org/pandas-docs/stable/reference/api/{}.html"
)

Fn = TypeVar("Fn", bound=Any)


def _make_api_url(token: str) -> str:
    return PANDAS_API_URL_TEMPLATE.format(token)


def _replace_doc_urls(doc: Optional[str]) -> Optional[str]:
    return doc


def _inherit_docstrings_in_place(
    cls_or_func: Fn,
    parent: object,
    excluded: List[object],
    overwrite_existing: bool = False,
    apilink: Optional[Union[str, List[str]]] = None,
    record: Optional[List[tuple]] = None,
    only: Optional[set] = None,
) -> None:
    """Copy docs from ``parent`` onto ``cls_or_func`` (class walks its MRO).

    ``record`` collects a key for every docstring actually written, so a
    later ``DocModule`` re-source can restrict itself (via ``only``) to
    exactly the inheritance-managed docs — hand-written docstrings that the
    decoration-time pass preserved stay untouched forever.
    """
    if parent in excluded:
        return
    _CLS_DOC = ("cls",)
    if parent not in _docstring_inheritance_calls and (only is None or _CLS_DOC in only):
        doc = getattr(parent, "__doc__", None)
        if doc and (not cls_or_func.__doc__ or overwrite_existing or only):
            try:
                cls_or_func.__doc__ = doc
                if record is not None:
                    record.append(_CLS_DOC)
            except AttributeError:
                pass
    if not isinstance(cls_or_func, types.FunctionType):
        seen = set()
        for base in getattr(cls_or_func, "__mro__", [cls_or_func]):
            if base is object:
                continue
            for attr, obj in base.__dict__.items():
                if attr in seen or attr.startswith("__"):
                    continue
                seen.add(attr)
                if only is not None and (base, attr) not in only:
                    continue
                parent_obj = getattr(parent, attr, None)
                if parent_obj is None:
                    continue
                parent_doc = getattr(parent_obj, "__doc__", None)
                if not parent_doc:
                    continue
                if isinstance(obj, property):
                    if obj.__doc__ is None or overwrite_existing or only:
                        try:
                            setattr(
                                base,
                                attr,
                                property(obj.fget, obj.fset, obj.fdel, parent_doc),
                            )
                            if record is not None:
                                record.append((base, attr))
                        except (AttributeError, TypeError):
                            pass
                elif callable(obj) or isinstance(obj, (classmethod, staticmethod)):
                    target = obj.__func__ if isinstance(obj, (classmethod, staticmethod)) else obj
                    if getattr(target, "__doc__", None) is None or overwrite_existing or only:
                        try:
                            target.__doc__ = parent_doc
                            if record is not None:
                                record.append((base, attr))
                        except AttributeError:
                            pass


_docstring_inheritance_calls: set = set()

# every _inherit_docstrings application, so DocModule can re-source docs later
_DOC_CALLS: List[tuple] = []
# the module object docs are currently sourced from (None = plain pandas)
_ACTIVE_DOC_MODULE: Optional[types.ModuleType] = None


def _resolve_doc_counterpart(parent: object, doc_module: types.ModuleType) -> object:
    """The object in ``doc_module`` matching ``parent``'s qualified name.

    Falls back to ``parent`` itself (keeping pandas docs) when the custom
    module has no counterpart — DocModule overrides are partial by design
    (reference behavior: envvars.py DocModule + utils.py doc re-sourcing).
    """
    if isinstance(parent, types.ModuleType):
        return doc_module
    path = getattr(parent, "__qualname__", getattr(parent, "__name__", None))
    if not path:
        return parent
    obj: object = doc_module
    for part in path.split("."):
        obj = getattr(obj, part, None)
        if obj is None:
            return parent
    return obj


def _apply_doc_module(param) -> None:
    """DocModule subscriber: re-source registered docstrings from the module.

    Only docstrings the decoration-time pass itself wrote (each call's
    ``written`` record) are ever re-sourced; reverting to ``"pandas"``
    restores the originals from each call's own parent.
    """
    global _ACTIVE_DOC_MODULE
    name = param.get()
    if name == "pandas":
        if _ACTIVE_DOC_MODULE is not None:
            # restore the decoration-time docs from each original parent
            _ACTIVE_DOC_MODULE = None
            for cls_or_func, parent, excluded, apilink, written in list(_DOC_CALLS):
                _inherit_docstrings_in_place(
                    cls_or_func, parent, excluded,
                    apilink=apilink, only=set(written),
                )
        return
    try:
        mod = importlib.import_module(name)
    except ImportError:
        import warnings

        previous = getattr(_ACTIVE_DOC_MODULE, "__name__", "pandas")
        warnings.warn(
            f"DocModule {name!r} is not importable; keeping docs from {previous!r}"
        )
        return
    _ACTIVE_DOC_MODULE = mod
    for cls_or_func, parent, excluded, apilink, written in list(_DOC_CALLS):
        # restore the decoration-time docs first: when switching from custom
        # module A to B, attrs that A documented but B lacks must fall back to
        # the pandas parent, not keep A's text
        _inherit_docstrings_in_place(
            cls_or_func, parent, excluded, apilink=apilink, only=set(written)
        )
        # then overlay the custom module's counterparts; the ``written``
        # filter means hand-written docstrings are never touched
        _inherit_docstrings_in_place(
            cls_or_func,
            _resolve_doc_counterpart(parent, mod),
            excluded,
            apilink=apilink,
            only=set(written),
        )


def _inherit_docstrings(
    parent: object,
    excluded: Optional[List[object]] = None,
    overwrite_existing: bool = False,
    apilink: Optional[Union[str, List[str]]] = None,
) -> Callable[[Fn], Fn]:
    """Class/function decorator copying docstrings from a pandas counterpart.

    Reference: modin/utils.py:544 — keeps the public API self-documenting
    without duplicating pandas' docs in-repo.  Applications are recorded so a
    ``DocModule`` change re-sources every registered docstring from the
    user's module (reference: envvars.py:1338).
    """
    excluded = excluded or []

    def decorator(cls_or_func: Fn) -> Fn:
        written: List[tuple] = []
        _inherit_docstrings_in_place(
            cls_or_func, parent, excluded, overwrite_existing, apilink,
            record=written,
        )
        _DOC_CALLS.append((cls_or_func, parent, excluded, apilink, written))
        if _ACTIVE_DOC_MODULE is not None:
            # DocModule was set before this class was imported: apply now
            counterpart = _resolve_doc_counterpart(parent, _ACTIVE_DOC_MODULE)
            if counterpart is not parent:
                _inherit_docstrings_in_place(
                    cls_or_func,
                    counterpart,
                    excluded,
                    apilink=apilink,
                    only=set(written),
                )
        return cls_or_func

    return decorator


def _subscribe_doc_module() -> None:
    from modin_tpu.config import DocModule

    DocModule.subscribe(_apply_doc_module)


def expanduser_path_arg(argname: str) -> Callable[[Fn], Fn]:
    """Decorator expanding ``~`` in the named path argument."""
    import inspect
    import os

    def decorator(func: Fn) -> Fn:
        sig = inspect.signature(func)

        @functools.wraps(func)
        def wrapped(*args: Any, **kwargs: Any) -> Any:
            try:
                bound = sig.bind(*args, **kwargs)
            except TypeError:
                return func(*args, **kwargs)
            value = bound.arguments.get(argname)
            if isinstance(value, str) and value.startswith("~"):
                bound.arguments[argname] = os.path.expanduser(value)
            elif isinstance(value, os.PathLike):
                str_value = os.fspath(value)
                if str_value.startswith("~"):
                    bound.arguments[argname] = os.path.expanduser(str_value)
            return func(*bound.args, **bound.kwargs)

        return wrapped

    return decorator


def hashable(obj: Any) -> bool:
    """Whether ``obj`` can be hashed (list/dict/set cannot)."""
    try:
        hash(obj)
    except TypeError:
        return False
    return True


def is_scalar(obj: Any) -> bool:
    from pandas.api.types import is_scalar as pandas_is_scalar

    from modin_tpu.pandas.base import BasePandasDataset

    return not isinstance(obj, BasePandasDataset) and pandas_is_scalar(obj)


def wrap_into_list(*args: Any, skipna: bool = True) -> List[Any]:
    """Flatten the passed positional args into a single flat list."""

    def isnan(o: Any) -> bool:
        return o is None or (isinstance(o, float) and np.isnan(o))

    res = []
    for o in args:
        if skipna and isnan(o):
            continue
        if isinstance(o, (list, tuple)):
            res.extend(o)
        else:
            res.append(o)
    return res


def qc_to_pandas_for_write(qc: Any) -> Any:
    """Materialize a query compiler for a writer: Series-shaped compilers
    squeeze and shed the internal unnamed-column sentinel (pandas would
    otherwise emit ``__reduced__`` as the column/header name)."""
    df = qc.to_pandas()
    if getattr(qc, "_shape_hint", None) == "column":
        obj = df.squeeze(axis=1)
        if isinstance(obj, pandas.Series) and obj.name == MODIN_UNNAMED_SERIES_LABEL:
            obj.name = None
        return obj
    return df


def try_cast_to_pandas(obj: Any, squeeze: bool = False) -> Any:
    """Recursively convert modin_tpu objects inside ``obj`` to plain pandas."""
    if hasattr(obj, "_to_pandas"):
        result = obj._to_pandas()
        if squeeze and isinstance(result, pandas.DataFrame):
            result = result.squeeze(axis=1)
        return result
    if hasattr(obj, "to_pandas") and hasattr(obj, "_shape_hint"):
        # a raw query compiler
        result = obj.to_pandas()
        if squeeze or obj._shape_hint == "column":
            result = result.squeeze(axis=1)
            if (
                isinstance(result, pandas.Series)
                and result.name == MODIN_UNNAMED_SERIES_LABEL
            ):
                result.name = None
        return result
    if isinstance(obj, (list, tuple)):
        return type(obj)([try_cast_to_pandas(o, squeeze=squeeze) for o in obj])
    if isinstance(obj, dict):
        return {k: try_cast_to_pandas(v, squeeze=squeeze) for k, v in obj.items()}
    if callable(obj):
        module_hierarchy = getattr(obj, "__module__", "") or ""
        fn_name = getattr(obj, "__name__", None)
        if fn_name and module_hierarchy.startswith("modin_tpu.pandas"):
            return (
                getattr(pandas.DataFrame, fn_name, obj)
                if not module_hierarchy.endswith("series")
                else getattr(pandas.Series, fn_name, obj)
            )
    return obj


def to_pandas(modin_obj: Any) -> Any:
    """Convert a modin_tpu DataFrame/Series to its pandas counterpart."""
    return try_cast_to_pandas(modin_obj)


def func_from_deprecated_location(
    func_name: str, module: str, deprecation_message: str
) -> Callable:
    def deprecated_func(*args: Any, **kwargs: Any) -> Any:
        import warnings

        func = getattr(importlib.import_module(module), func_name)
        warnings.warn(deprecation_message, FutureWarning)
        return func(*args, **kwargs)

    return deprecated_func


class ModinAssumptionError(Exception):
    """An assumption of an optimized code path did not hold; caller should retry generic path."""


def get_current_execution() -> str:
    """Return the current execution name, e.g. ``TpuOnJax``."""
    from modin_tpu.config import Engine, StorageFormat

    return f"{StorageFormat.get()}On{Engine.get()}"


def show_versions(as_json: Union[str, bool] = False) -> None:
    """Print useful debugging information (reference: modin/utils.py:901)."""
    import modin_tpu

    deps = {
        "python": sys.version.replace("\n", " "),
        "OS": platform.platform(),
        "modin_tpu": modin_tpu.__version__,
        "pandas": pandas.__version__,
        "numpy": np.__version__,
    }
    for mod in ("jax", "jaxlib", "flax", "optax", "pyarrow", "fsspec"):
        try:
            deps[mod] = importlib.import_module(mod).__version__
        except Exception:
            deps[mod] = None
    import queue
    import threading

    try:
        import jax
    except ImportError:
        jax = None
    if jax is not None:
        # device discovery can hang on a wedged accelerator runtime;
        # bound it with a daemon thread (NOT ThreadPoolExecutor: its atexit
        # hook would join a wedged worker and hang interpreter shutdown)
        result_queue: "queue.Queue" = queue.Queue()

        def probe() -> None:
            try:
                result_queue.put([str(d) for d in jax.devices()])
            except Exception as err:  # pragma: no cover
                result_queue.put(err)

        thread = threading.Thread(  # graftlint: disable=THREAD-HYGIENE -- pure-stdlib build probe: deliberately imports no observability so a diagnostics dump works when the package is half-broken
            target=probe, name="modin-tpu-version-probe", daemon=True
        )
        thread.start()
        try:
            devices = result_queue.get(timeout=10)
        except queue.Empty:
            deps["jax.devices"] = "unavailable (device discovery timed out)"
        else:
            if isinstance(devices, Exception):
                deps["jax.devices"] = (
                    f"unavailable ({type(devices).__name__}: {devices})"
                )
            else:
                deps["jax.devices"] = ", ".join(devices)
                deps["jax.default_backend"] = jax.default_backend()

    if as_json:
        if as_json is True:
            print(json.dumps(deps, indent=2))  # noqa: T201
        else:
            with open(as_json, "w") as f:
                json.dump(deps, f, indent=2)
        return
    print("\nINSTALLED VERSIONS")  # noqa: T201
    print("------------------")  # noqa: T201
    for k, v in deps.items():
        print(f"{k:20}: {v}")  # noqa: T201


def import_optional_dependency(name: str, extra: str = ""):
    """Import a soft dependency, raising a helpful error when missing."""
    try:
        return importlib.import_module(name)
    except ImportError as err:
        raise ImportError(
            f"Missing optional dependency '{name}'. {extra} "
            f"Use pip or conda to install {name}."
        ) from err


def sentinel(name: str) -> object:
    """Create a unique named sentinel object (repr-friendly)."""
    return type(name, (), {"__repr__": lambda self: name})()


no_default = pandas.api.extensions.no_default


_subscribe_doc_module()
