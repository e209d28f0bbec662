"""``DataFrameGroupBy`` / ``SeriesGroupBy`` — lazy groupby objects.

Reference design: /root/reference/modin/pandas/groupby.py (2,322 LoC): the
groupby object holds (query_compiler, by, kwargs) and dispatches aggregations
to ``qc.groupby_agg``; nothing is computed until an aggregation is requested.
"""

from __future__ import annotations

from typing import Any, Hashable, Optional, Union

import numpy as np
import pandas
from pandas.api.types import is_list_like

from modin_tpu.logging import ClassLogger, disable_logging
from modin_tpu.utils import MODIN_UNNAMED_SERIES_LABEL, hashable, try_cast_to_pandas


class DataFrameGroupBy(ClassLogger, modin_layer="PANDAS-API"):
    _pandas_class = pandas.core.groupby.DataFrameGroupBy

    def __init__(
        self,
        df: Any,
        by: Any = None,
        level: Any = None,
        as_index: bool = True,
        sort: bool = True,
        group_keys: bool = True,
        observed: Any = True,
        dropna: bool = True,
        selection: Any = None,
    ) -> None:
        self._df = df
        self._by = by
        self._level = level
        self._selection = selection
        self._kwargs = {
            "level": level,
            "as_index": as_index,
            "sort": sort,
            "group_keys": group_keys,
            "observed": observed,
            "dropna": dropna,
        }

    @property
    def _query_compiler(self):
        # resolved dynamically: the groupby tracks its parent frame, matching
        # pandas' behavior where post-groupby mutations of the frame are seen
        return self._df._query_compiler

    # ------------------------------------------------------------------ #
    # by normalization
    # ------------------------------------------------------------------ #

    def _resolve_by(self):
        """Return (by_for_qc, drop) where label-bys stay labels and external
        Series become query compilers."""
        from modin_tpu.pandas.series import Series

        by = self._by
        if by is None:
            return None, False
        if isinstance(by, Series):
            return by._query_compiler, False
        if callable(by):
            return by, False
        if hashable(by) and not isinstance(by, tuple):
            if by in self._df.columns:
                return [by], True
            return by, False
        if is_list_like(by) and not isinstance(by, (pandas.Series, np.ndarray)):
            by_list = list(by)
            if all(
                hashable(o) and not isinstance(o, Series) and o in self._df.columns
                for o in by_list
            ):
                return by_list, True
            return [
                o._query_compiler if isinstance(o, Series) else o for o in by_list
            ], False
        return by, False

    def _groupby_agg(
        self,
        agg_func: Any,
        agg_args: tuple = (),
        agg_kwargs: Optional[dict] = None,
        numeric_only: Any = None,
        series_groupby: bool = False,
        **extra: Any,
    ):
        from modin_tpu.pandas.dataframe import DataFrame
        from modin_tpu.pandas.series import Series

        by, drop = self._resolve_by()
        agg_kwargs = dict(agg_kwargs or {})
        if numeric_only is not None:
            agg_kwargs["numeric_only"] = numeric_only
        groupby_kwargs = dict(self._kwargs)
        result_qc = self._query_compiler.groupby_agg(
            by=by,
            agg_func=agg_func,
            axis=0,
            groupby_kwargs=groupby_kwargs,
            agg_args=agg_args,
            agg_kwargs=agg_kwargs,
            drop=drop,
            series_groupby=series_groupby,
            selection=self._selection,
        )
        if not hasattr(result_qc, "to_pandas"):
            return result_qc
        if series_groupby and not isinstance(agg_func, list):
            # a LIST spec always yields a frame, even with one function
            cols = result_qc.columns
            if len(cols) == 1:
                result_qc._shape_hint = "column"
                return Series(query_compiler=result_qc)
        if (
            not series_groupby
            and getattr(result_qc, "_shape_hint", None) == "column"
            and len(result_qc.columns) == 1
        ):
            # the UDF produced a scalar per group: the QC carries the
            # was-a-Series hint so the frame groupby still squeezes
            return Series(query_compiler=result_qc)
        return DataFrame(query_compiler=result_qc)

    # ------------------------------------------------------------------ #
    # aggregations
    # ------------------------------------------------------------------ #

    def sum(self, numeric_only: bool = False, min_count: int = 0, **kwargs: Any):
        return self._groupby_agg("sum", agg_kwargs={"numeric_only": numeric_only, "min_count": min_count})

    def prod(self, numeric_only: bool = False, min_count: int = 0):
        return self._groupby_agg("prod", agg_kwargs={"numeric_only": numeric_only, "min_count": min_count})

    def count(self):
        return self._groupby_agg("count")

    def mean(self, numeric_only: bool = False, engine: Any = None, engine_kwargs: Any = None):
        return self._groupby_agg("mean", agg_kwargs={"numeric_only": numeric_only})

    def median(self, numeric_only: bool = False):
        return self._groupby_agg("median", agg_kwargs={"numeric_only": numeric_only})

    def min(self, numeric_only: bool = False, min_count: int = -1):
        return self._groupby_agg("min", agg_kwargs={"numeric_only": numeric_only, "min_count": min_count})

    def max(self, numeric_only: bool = False, min_count: int = -1):
        return self._groupby_agg("max", agg_kwargs={"numeric_only": numeric_only, "min_count": min_count})

    def std(self, ddof: int = 1, engine: Any = None, engine_kwargs: Any = None, numeric_only: bool = False):
        return self._groupby_agg("std", agg_kwargs={"ddof": ddof, "numeric_only": numeric_only})

    def var(self, ddof: int = 1, engine: Any = None, engine_kwargs: Any = None, numeric_only: bool = False):
        return self._groupby_agg("var", agg_kwargs={"ddof": ddof, "numeric_only": numeric_only})

    def sem(self, ddof: int = 1, numeric_only: bool = False):
        return self._groupby_agg("sem", agg_kwargs={"ddof": ddof, "numeric_only": numeric_only})

    def skew(self, numeric_only: bool = False, **kwargs: Any):
        return self._groupby_agg("skew", agg_kwargs={"numeric_only": numeric_only})

    def first(self, numeric_only: bool = False, min_count: int = -1, skipna: bool = True):
        return self._groupby_agg("first", agg_kwargs={"numeric_only": numeric_only, "min_count": min_count, "skipna": skipna})

    def last(self, numeric_only: bool = False, min_count: int = -1, skipna: bool = True):
        return self._groupby_agg("last", agg_kwargs={"numeric_only": numeric_only, "min_count": min_count, "skipna": skipna})

    def any(self, skipna: bool = True):
        return self._groupby_agg("any", agg_kwargs={"skipna": skipna})

    def all(self, skipna: bool = True):
        return self._groupby_agg("all", agg_kwargs={"skipna": skipna})

    def nunique(self, dropna: bool = True):
        return self._groupby_agg("nunique", agg_kwargs={"dropna": dropna})

    def size(self):
        from modin_tpu.pandas.series import Series

        result = self._groupby_agg("size")
        if self._kwargs.get("as_index", True) and not isinstance(result, Series):
            # size returns a Series in pandas when as_index=True
            qc = result._query_compiler
            qc._shape_hint = "column"
            return Series(query_compiler=qc)
        return result

    def quantile(self, q: float = 0.5, interpolation: str = "linear", numeric_only: bool = False):
        return self._groupby_agg("quantile", agg_kwargs={"q": q, "interpolation": interpolation, "numeric_only": numeric_only})

    def idxmin(self, skipna: bool = True, numeric_only: bool = False):
        return self._groupby_agg("idxmin", agg_kwargs={"skipna": skipna, "numeric_only": numeric_only})

    def idxmax(self, skipna: bool = True, numeric_only: bool = False):
        return self._groupby_agg("idxmax", agg_kwargs={"skipna": skipna, "numeric_only": numeric_only})

    def cumsum(self, axis: Any = 0, *args: Any, **kwargs: Any):
        return self._groupby_agg("cumsum", agg_args=args, agg_kwargs=kwargs)

    def cumprod(self, axis: Any = 0, *args: Any, **kwargs: Any):
        return self._groupby_agg("cumprod", agg_args=args, agg_kwargs=kwargs)

    def cummax(self, axis: Any = 0, numeric_only: bool = False, **kwargs: Any):
        return self._groupby_agg("cummax", agg_kwargs={"numeric_only": numeric_only})

    def cummin(self, axis: Any = 0, numeric_only: bool = False, **kwargs: Any):
        return self._groupby_agg("cummin", agg_kwargs={"numeric_only": numeric_only})

    def cumcount(self, ascending: bool = True):
        return self._groupby_agg("cumcount", agg_kwargs={"ascending": ascending}, series_groupby=True)

    def ngroup(self, ascending: bool = True):
        return self._groupby_agg("ngroup", agg_kwargs={"ascending": ascending}, series_groupby=True)

    def rank(self, method: str = "average", ascending: bool = True, na_option: str = "keep", pct: bool = False, **kwargs: Any):
        return self._groupby_agg("rank", agg_kwargs={"method": method, "ascending": ascending, "na_option": na_option, "pct": pct})

    def shift(self, periods: int = 1, freq: Any = None, fill_value: Any = None, **kwargs: Any):
        return self._groupby_agg("shift", agg_kwargs={"periods": periods, "freq": freq, "fill_value": fill_value})

    def diff(self, periods: int = 1, **kwargs: Any):
        return self._groupby_agg("diff", agg_kwargs={"periods": periods})

    def pct_change(self, periods: int = 1, **kwargs: Any):
        return self._groupby_agg("pct_change", agg_kwargs={"periods": periods})

    def ffill(self, limit: Any = None):
        return self._groupby_agg("ffill", agg_kwargs={"limit": limit})

    def bfill(self, limit: Any = None):
        return self._groupby_agg("bfill", agg_kwargs={"limit": limit})

    def fillna(self, *args: Any, **kwargs: Any):
        return self._groupby_agg("fillna", agg_args=args, agg_kwargs=kwargs)

    def head(self, n: int = 5):
        return self._groupby_agg("head", agg_kwargs={"n": n})

    def tail(self, n: int = 5):
        return self._groupby_agg("tail", agg_kwargs={"n": n})

    def nth(self, n: Any, dropna: Any = None):
        return self._groupby_agg("nth", agg_kwargs={"n": n})

    def sample(self, n: Any = None, frac: Any = None, replace: bool = False, weights: Any = None, random_state: Any = None):
        return self._groupby_agg("sample", agg_kwargs={"n": n, "frac": frac, "replace": replace, "weights": weights, "random_state": random_state})

    def ohlc(self):
        return self._groupby_agg("ohlc")

    def describe(self, percentiles: Any = None, include: Any = None, exclude: Any = None):
        return self._groupby_agg(
            "describe",
            agg_kwargs={
                "percentiles": percentiles, "include": include, "exclude": exclude,
            },
        )

    def corrwith(self, other: Any, drop: bool = False, method: str = "pearson", numeric_only: bool = False):
        return self._groupby_agg(
            "corrwith",
            agg_kwargs={
                "other": try_cast_to_pandas(other), "drop": drop,
                "method": method, "numeric_only": numeric_only,
            },
        )

    def corr(self, method: str = "pearson", min_periods: int = 1, numeric_only: bool = False):
        return self._groupby_agg("corr", agg_kwargs={"method": method, "min_periods": min_periods, "numeric_only": numeric_only})

    def cov(self, min_periods: Any = None, ddof: int = 1, numeric_only: bool = False):
        return self._groupby_agg("cov", agg_kwargs={"min_periods": min_periods, "ddof": ddof, "numeric_only": numeric_only})

    def agg(self, func: Any = None, *args: Any, engine: Any = None, engine_kwargs: Any = None, **kwargs: Any):
        if func is None and kwargs:
            # named aggregation
            return self._groupby_agg(
                lambda grp, **kw: grp.agg(**kw), agg_kwargs=kwargs
            )
        if (
            not args
            and not kwargs
            and (
                (isinstance(func, list) and all(isinstance(f, str) for f in func))
                or (
                    isinstance(func, dict)
                    and all(
                        isinstance(f, str)
                        or (isinstance(f, list) and all(isinstance(g, str) for g in f))
                        for f in func.values()
                    )
                )
            )
        ):
            # list-of-strings / dict of strings or of lists of strings pass
            # through intact so the compiler's device multi-agg path can see
            # them
            return self._groupby_agg(func)
        return self._groupby_agg(
            func if isinstance(func, str) else (lambda grp, *a, **kw: grp.agg(try_cast_to_pandas(func), *a, **kw)),
            agg_args=args,
            agg_kwargs=kwargs,
        )

    aggregate = agg

    def apply(self, func: Any, *args: Any, include_groups: bool = False, **kwargs: Any):
        return self._groupby_agg(
            lambda grp, *a, **kw: grp.apply(func, *a, include_groups=include_groups, **kw)
            if _supports_include_groups(grp)
            else grp.apply(func, *a, **kw),
            agg_args=args,
            agg_kwargs=kwargs,
        )

    def transform(self, func: Any, *args: Any, engine: Any = None, engine_kwargs: Any = None, **kwargs: Any):
        if isinstance(func, str) and not args and not kwargs:
            from modin_tpu.pandas.dataframe import DataFrame
            from modin_tpu.pandas.series import Series

            by, drop = self._resolve_by()
            is_series = self._pandas_class is pandas.core.groupby.SeriesGroupBy
            result_qc = self._query_compiler.groupby_transform(
                by=by,
                agg_func=func,
                groupby_kwargs=dict(self._kwargs),
                drop=drop,
                series_groupby=is_series,
                selection=self._selection,
            )
            if is_series and result_qc.get_axis_len(1) == 1:
                result_qc._shape_hint = "column"
                return Series(query_compiler=result_qc)
            return DataFrame(query_compiler=result_qc)
        transformer = lambda grp, *a, **kw: grp.transform(func, *a, **kw)  # noqa: E731
        # row-shaped result (original frame order): the key-ordered shuffle
        # concat must not claim it
        transformer._row_shaped_groupby = True
        return self._groupby_agg(transformer, agg_args=args, agg_kwargs=kwargs)

    def filter(self, func: Any, dropna: bool = True, *args: Any, **kwargs: Any):
        filterer = lambda grp, *a, **kw: grp.filter(func, dropna=dropna, *a, **kw)  # noqa: E731
        filterer._row_shaped_groupby = True
        return self._groupby_agg(filterer, agg_args=args, agg_kwargs=kwargs)

    def pipe(self, func: Any, *args: Any, **kwargs: Any):
        if isinstance(func, tuple):
            func, target = func
            kwargs[target] = self
            return func(*args, **kwargs)
        return func(self, *args, **kwargs)

    def value_counts(self, subset: Any = None, normalize: bool = False, sort: bool = True, ascending: bool = False, dropna: bool = True):
        return self._groupby_agg(
            "value_counts",
            agg_kwargs={"subset": subset, "normalize": normalize, "sort": sort, "ascending": ascending, "dropna": dropna},
            series_groupby=True,
        )

    def resample(self, rule: Any, *args: Any, **kwargs: Any):
        return self._groupby_agg(
            lambda grp, *a, **kw: grp.resample(rule, *a, **kw).sum(), agg_args=args, agg_kwargs=kwargs
        )

    def rolling(
        self,
        window: Any = None,
        min_periods: Any = None,
        center: bool = False,
        win_type: Any = None,
        on: Any = None,
        closed: Any = None,
        method: str = "single",
    ):
        from modin_tpu.pandas.window import GroupByRolling

        return GroupByRolling(
            self, window, min_periods=min_periods, center=center,
            win_type=win_type, on=on, closed=closed, method=method,
        )

    def expanding(self, min_periods: int = 1, method: str = "single"):
        from modin_tpu.pandas.window import GroupByExpanding

        return GroupByExpanding(self, min_periods=min_periods, method=method)

    def ewm(
        self,
        com: Any = None,
        span: Any = None,
        halflife: Any = None,
        alpha: Any = None,
        min_periods: Any = 0,
        adjust: bool = True,
        ignore_na: bool = False,
        times: Any = None,
        method: str = "single",
    ):
        from modin_tpu.pandas.window import GroupByEwm
        from modin_tpu.utils import try_cast_to_pandas

        return GroupByEwm(
            self, com=com, span=span, halflife=halflife, alpha=alpha,
            min_periods=min_periods, adjust=adjust, ignore_na=ignore_na,
            times=try_cast_to_pandas(times, squeeze=True), method=method,
        )

    # ------------------------------------------------------------------ #
    # Introspection
    # ------------------------------------------------------------------ #

    @property
    def groups(self) -> dict:
        return self._to_pandas_groupby().groups

    @property
    def indices(self) -> dict:
        return self._to_pandas_groupby().indices

    @property
    def ngroups(self) -> int:
        return self._to_pandas_groupby().ngroups

    @property
    def dtypes(self):
        return self._df._wrap_pandas(self._to_pandas_groupby().dtypes)

    def get_group(self, name: Any):
        return self._df._wrap_pandas(self._to_pandas_groupby().get_group(name))

    def __len__(self) -> int:
        return self.ngroups

    def __iter__(self):
        for name, group in self._to_pandas_groupby():
            yield name, self._df._wrap_pandas(group)

    def _to_pandas_groupby(self):
        pandas_obj = self._df._to_pandas()
        by = try_cast_to_pandas(self._by, squeeze=True)
        grp = pandas_obj.groupby(by=by, **{k: v for k, v in self._kwargs.items()})
        if self._selection is not None:
            grp = grp[self._selection]
        return grp

    def __getitem__(self, key: Any):
        passthrough = {
            k: v
            for k, v in self._kwargs.items()
            if k in ("as_index", "sort", "group_keys", "observed", "dropna")
        }
        if is_list_like(key) and not isinstance(key, str):
            return DataFrameGroupBy(
                self._df,
                by=self._by,
                level=self._level,
                selection=list(key),
                **passthrough,
            )
        return SeriesGroupBy(
            self._df,
            by=self._by,
            level=self._level,
            selection=key,
            **passthrough,
        )

    def __getattr__(self, key: str):
        try:
            return object.__getattribute__(self, key)
        except AttributeError as err:
            qc = object.__getattribute__(self, "_query_compiler")
            if key in qc.columns:
                return self[key]
            raise err


class SeriesGroupBy(DataFrameGroupBy):
    _pandas_class = pandas.core.groupby.SeriesGroupBy

    def __init__(self, obj: Any, by: Any = None, level: Any = None, selection: Any = None, **kwargs: Any) -> None:
        super().__init__(obj, by=by, level=level, selection=selection, **kwargs)

    def _groupby_agg(self, agg_func: Any, agg_args: tuple = (), agg_kwargs: Optional[dict] = None, numeric_only: Any = None, series_groupby: bool = True, **extra: Any):
        return super()._groupby_agg(
            agg_func,
            agg_args=agg_args,
            agg_kwargs=agg_kwargs,
            numeric_only=numeric_only,
            series_groupby=True,
        )

    def unique(self):
        return self._groupby_agg("unique")

    def nlargest(self, n: int = 5, keep: str = "first"):
        return self._groupby_agg("nlargest", agg_kwargs={"n": n, "keep": keep})

    def nsmallest(self, n: int = 5, keep: str = "first"):
        return self._groupby_agg("nsmallest", agg_kwargs={"n": n, "keep": keep})

    @property
    def is_monotonic_increasing(self):
        return self._groupby_agg(lambda grp: grp.apply(lambda s: s.is_monotonic_increasing))

    @property
    def is_monotonic_decreasing(self):
        return self._groupby_agg(lambda grp: grp.apply(lambda s: s.is_monotonic_decreasing))


def _supports_include_groups(grp: Any) -> bool:
    import inspect

    try:
        return "include_groups" in inspect.signature(grp.apply).parameters
    except (ValueError, TypeError):
        return False
