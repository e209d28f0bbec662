"""``enable_logging`` — the START/STOP trace + metrics decorator.

Reference design: /root/reference/modin/logging/logger_decorator.py:55-69 — every
significant method logs ``START::<layer>::<name>`` / ``STOP::…`` when LogMode is
enabled, and API-layer calls emit timing metrics.
"""

from __future__ import annotations

import re
import time
from functools import wraps
from types import FunctionType, MethodType
from typing import Any, Callable, Optional, Union

from modin_tpu.config import LogMode, MetricsMode
from modin_tpu.logging.config import get_logger
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import spans as graftscope

_MODIN_LOGGER_NOWRAP = "__modin_logging_nowrap__"


def disable_logging(func: Callable) -> Callable:
    """Mark a function to never be wrapped by ``enable_logging``."""
    setattr(func, _MODIN_LOGGER_NOWRAP, True)
    return func


def enable_logging(
    modin_layer: Union[str, Callable, classmethod, staticmethod] = "PANDAS-API",
    name: Optional[str] = None,
    log_level: str = "info",
) -> Callable:
    """Wrap a callable with START/STOP trace logging and timing metrics.

    Usable both as ``@enable_logging`` and ``@enable_logging("LAYER")``.
    """
    if isinstance(modin_layer, (FunctionType, MethodType, classmethod, staticmethod)):
        return enable_logging()(modin_layer)

    def decorator(obj: Any) -> Any:
        if isinstance(obj, classmethod):
            return classmethod(decorator(obj.__func__))
        if isinstance(obj, staticmethod):
            return staticmethod(decorator(obj.__func__))
        if isinstance(obj, type):
            seen: dict = {}
            for attr_name, attr_value in vars(obj).items():
                # a classmethod/staticmethod object does not proxy attributes:
                # the mark of @disable_logging sits on the function inside it
                if isinstance(
                    attr_value, (FunctionType, MethodType, classmethod, staticmethod)
                ) and not hasattr(
                    getattr(attr_value, "__func__", attr_value), _MODIN_LOGGER_NOWRAP
                ):
                    try:
                        wrapped = seen.setdefault(
                            attr_value,
                            enable_logging(modin_layer, f"{obj.__name__}.{attr_name}")(
                                attr_value
                            ),
                        )
                        setattr(obj, attr_name, wrapped)
                    except (TypeError, AttributeError):
                        pass
            return obj

        assert isinstance(modin_layer, str), "modin_layer is somehow not a string!"
        log_name = name or getattr(obj, "__qualname__", repr(obj))
        log_name = re.sub(r"[^a-zA-Z0-9\-_\.]", "_", log_name)
        full_name = f"{modin_layer}::{log_name}"
        is_api_layer = modin_layer.upper() in graftscope.API_LAYERS

        @wraps(obj)
        def run_and_log(*args: Any, **kwargs: Any) -> Any:
            mode = LogMode.get()
            metrics_on = MetricsMode.get() == "Enable" and is_api_layer
            if is_api_layer:
                from modin_tpu.config import ProgressBar

                if ProgressBar.get():
                    from modin_tpu.core.execution.progress import call_progress_bar

                    with call_progress_bar(log_name):
                        return _run_inner((mode, metrics_on), *args, **kwargs)
            return _run_inner((mode, metrics_on), *args, **kwargs)

        # state rides in ONE private positional: spreading it as named
        # positionals collided with wrapped calls whose own kwargs include
        # e.g. ``mode`` (pandas read_hdf/to_hdf/to_csv all have one)
        def _run_inner(_log_state: tuple, *args: Any, **kwargs: Any) -> Any:
            # graftscope seam: independent of LogMode — one module-attribute
            # check when tracing is off, a nested layer-tagged span when on
            if not graftscope.TRACE_ON:
                return _run_logged(_log_state, *args, **kwargs)
            with graftscope.layer_span(log_name, modin_layer):
                return _run_logged(_log_state, *args, **kwargs)

        def _run_logged(_log_state: tuple, *args: Any, **kwargs: Any) -> Any:
            mode, metrics_on = _log_state
            if mode == "Disable" and not metrics_on:
                return obj(*args, **kwargs)
            if mode == "Enable_Api_Only" and not is_api_layer and not metrics_on:
                return obj(*args, **kwargs)

            logger = get_logger() if mode != "Disable" else None
            if logger is not None and not (
                mode == "Enable_Api_Only" and not is_api_layer
            ):
                getattr(logger, log_level)(f"START::{full_name}")
            start = time.perf_counter()
            try:
                result = obj(*args, **kwargs)
            except BaseException as err:
                if logger is not None:
                    get_logger("modin_tpu.logger.errors").exception(
                        f"STOP::{full_name}", exc_info=err
                    )
                raise
            finally:
                elapsed = time.perf_counter() - start
                if metrics_on:
                    emit_metric(
                        f"pandas-api.{log_name.lower().replace('.', '_', 1)}", elapsed
                    )
            if logger is not None and not (
                mode == "Enable_Api_Only" and not is_api_layer
            ):
                getattr(logger, log_level)(f"STOP::{full_name}")
            return result

        setattr(run_and_log, _MODIN_LOGGER_NOWRAP, True)
        return run_and_log

    return decorator
