"""Differential-testing helpers.

Reference design: /root/reference/modin/tests/pandas/utils.py (``df_equals``
:768, ``eval_general``, ``create_test_dfs``): build the same data as a
modin_tpu object and a pandas object, run the same operation on both, assert
equality.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np
import pandas
from pandas.testing import assert_frame_equal, assert_index_equal, assert_series_equal

import modin_tpu.pandas as pd
from modin_tpu.utils import try_cast_to_pandas

RAND_LOW = 0
RAND_HIGH = 100
NROWS = 64
NCOLS = 8

_rng = np.random.default_rng(42)

test_data = {
    "int_data": {
        f"col{i}": _rng.integers(RAND_LOW, RAND_HIGH, size=NROWS) for i in range(NCOLS)
    },
    "float_nan_data": {
        f"col{i}": [
            x if j % 4 else np.nan
            for j, x in enumerate(_rng.uniform(RAND_LOW, RAND_HIGH, size=NROWS))
        ]
        for i in range(NCOLS)
    },
}

test_data_values = list(test_data.values())
test_data_keys = list(test_data.keys())


def categories_equals(left: pandas.Categorical, right: pandas.Categorical) -> None:
    assert (left.ordered and right.ordered) or (not left.ordered and not right.ordered)
    assert_index_equal(left.categories, right.categories)


def df_equals(df1: Any, df2: Any, check_dtypes: bool = True) -> None:
    """Assert two (modin_tpu or pandas) objects are equal."""
    types_for_almost_equals = (pandas.core.indexes.range.RangeIndex, pandas.Index)

    df1 = try_cast_to_pandas(df1)
    df2 = try_cast_to_pandas(df2)

    if isinstance(df1, pandas.DataFrame) and isinstance(df2, pandas.DataFrame):
        assert_frame_equal(
            df1, df2, check_dtype=check_dtypes, check_categorical=False,
            check_freq=False,
        )
    elif isinstance(df1, pandas.Series) and isinstance(df2, pandas.Series):
        assert_series_equal(
            df1, df2, check_dtype=check_dtypes, check_categorical=False,
            check_freq=False,
        )
    elif isinstance(df1, types_for_almost_equals) and isinstance(
        df2, types_for_almost_equals
    ):
        assert_index_equal(df1, df2)
    elif isinstance(df1, pandas.Categorical) and isinstance(df2, pandas.Categorical):
        categories_equals(df1, df2)
    elif isinstance(df1, np.ndarray) and isinstance(df2, np.ndarray):
        np.testing.assert_array_equal(df1, df2)
    elif isinstance(df1, (float, np.floating)) and np.isnan(df1):
        assert np.isnan(df2), f"{df1} != {df2}"
    elif isinstance(df1, dict) and isinstance(df2, dict):
        assert df1.keys() == df2.keys()
        for k in df1:
            df_equals(df1[k], df2[k], check_dtypes=check_dtypes)
    else:
        if isinstance(df1, (float, np.floating)) or isinstance(df2, (float, np.floating)):
            np.testing.assert_allclose(df1, df2, rtol=1e-12)
        else:
            assert df1 == df2, f"{df1} != {df2}"


def create_test_dfs(*args: Any, **kwargs: Any):
    """Build the same DataFrame as (modin_tpu, pandas)."""
    return pd.DataFrame(*args, **kwargs), pandas.DataFrame(*args, **kwargs)


def create_test_series(*args: Any, **kwargs: Any):
    return pd.Series(*args, **kwargs), pandas.Series(*args, **kwargs)


def assert_no_fallback(fn: Callable):
    """Run ``fn`` asserting no default-to-pandas warning fires.

    Device-path assertions only make sense on the TpuOnJax execution; other
    executions (``--execution NativeOnNative``) skip instead of failing.
    """
    import warnings

    import pytest

    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax":
        pytest.skip("device-path assertion requires TpuOnJax")
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        return fn()


def eval_general(
    modin_obj: Any,
    pandas_obj: Any,
    operation: Callable,
    comparator: Callable = df_equals,
    check_exception_type: bool = True,
    **kwargs: Any,
) -> None:
    """Run ``operation`` against both objects and compare results or exceptions."""
    md_kwargs, pd_kwargs = {}, {}

    def execute_callable(fn, inplace=False, md_kwargs={}, pd_kwargs={}):
        try:
            pd_result = fn(pandas_obj, **pd_kwargs)
        except Exception as pd_e:
            try:
                if check_exception_type:
                    try:
                        md_result = fn(modin_obj, **md_kwargs)
                    except Exception as md_e:
                        assert isinstance(
                            md_e, type(pd_e)
                        ) or isinstance(pd_e, type(md_e)), (
                            f"Different exceptions: pandas={pd_e!r} modin_tpu={md_e!r}"
                        )
                        return None
                    raise AssertionError(
                        f"pandas raised {pd_e!r} but modin_tpu returned {md_result!r}"
                    )
            finally:
                pass
            return None
        md_result = fn(modin_obj, **md_kwargs)
        return md_result, pd_result

    for key, value in kwargs.items():
        if isinstance(value, tuple) and len(value) == 2 and callable(value[0]):
            md_kwargs[key], pd_kwargs[key] = value
        else:
            md_kwargs[key] = value
            pd_kwargs[key] = value

    values = execute_callable(
        operation, md_kwargs=md_kwargs, pd_kwargs=pd_kwargs
    )
    if values is not None:
        comparator(*values)


def sort_if_range_partitioning(df1: Any, df2: Any, comparator: Callable = df_equals) -> None:
    """Sort results before comparison when the execution doesn't guarantee order."""
    from modin_tpu.config import RangePartitioning

    if RangePartitioning.get():
        df1 = df1.sort_index() if hasattr(df1, "sort_index") else df1
        df2 = df2.sort_index() if hasattr(df2, "sort_index") else df2
    comparator(df1, df2)


def require_tpu_execution() -> None:
    """Skip the calling test on executions without the TpuOnJax device/IO
    wiring (mirrors assert_no_fallback's behavior for path assertions)."""
    import pytest

    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax":
        pytest.skip("TpuOnJax-specific path")


def jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (a ``jit`` call, a
    ``cond``'s branches, a Pallas kernel's body) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from jaxpr_eqns(sub)
