"""Device datetime component extraction (``.dt.year`` & co).

Datetime columns live on device as int64 ticks of their unit (NaT = int64
min, pandas' own sentinel — core/dataframe/tpu/dataframe.py).  Every
calendar component is branchless integer arithmetic over those ticks:

- civil date from day number via the Gregorian-era decomposition
  (Howard Hinnant's public-domain ``civil_from_days`` algorithm —
  days-per-era constants 146097/36524/1460/365),
- time-of-day components from the tick remainder,
- predicates (is_month_start, ...) from the decomposed pieces.

The reference extracts these host-side through pandas' tslib per partition
(modin/core/dataframe/algebra/default2pandas/series.py DateTimeDefault);
here one jit per column handles 1e8 rows without leaving HBM.

Output dtype follows pandas: int32 for clean columns, float64 with NaN when
NaT is present (the caller decides from the returned NaT flag), bool for
predicates (NaT rows are False like pandas).
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import numpy as np

_NAT = np.iinfo(np.int64).min

# ticks per second by numpy datetime unit
_TPS = {"s": 1, "ms": 10**3, "us": 10**6, "ns": 10**9}

# cumulative days before month m (1-indexed; non-leap)
_CUMDAYS = np.array(
    [0, 0, 31, 59, 90, 120, 151, 181, 212, 243, 273, 304, 334], np.int64
)
_DAYS_IN_MONTH = np.array(
    [0, 31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31], np.int64
)

COMPONENT_NAMES = (
    "year", "month", "day", "hour", "minute", "second", "microsecond",
    "nanosecond", "dayofweek", "weekday", "day_of_week", "dayofyear",
    "day_of_year", "quarter", "daysinmonth", "days_in_month",
    "is_leap_year", "is_month_start", "is_month_end", "is_quarter_start",
    "is_quarter_end", "is_year_start", "is_year_end",
)

_BOOL_COMPONENTS = frozenset(
    n for n in COMPONENT_NAMES if n.startswith("is_")
)

# timedelta64 columns: pandas Timedelta field semantics (days floors toward
# -inf; seconds/microseconds/nanoseconds are the NON-NEGATIVE remainders)
TIMEDELTA_COMPONENT_NAMES = (
    "days", "seconds", "microseconds", "nanoseconds", "total_seconds",
)


from modin_tpu.parallel.engine import materialize as _engine_materialize
from modin_tpu.ops._program import named_jit


@functools.lru_cache(maxsize=None)
def _jit_td_component(name: str, unit: str, n: int, want_float: bool = False):
    import jax
    import jax.numpy as jnp

    tps = _TPS[unit]
    day_ticks = 86400 * tps

    def fn(ticks):
        valid = (jnp.arange(ticks.shape[0]) < n) & (ticks != _NAT)
        t = jnp.where(valid, ticks, 0)
        days = jnp.floor_divide(t, day_ticks)
        rem = t - days * day_ticks  # [0, day_ticks)
        if name == "days":
            out = days
        elif name == "seconds":
            out = rem // tps
        elif name == "microseconds":
            out = ((rem % tps) * (10**9 // tps)) // 1000
        elif name == "nanoseconds":
            out = ((rem % tps) * (10**9 // tps)) % 1000
        elif name == "total_seconds":
            out = t.astype(jnp.float64) / tps
        else:  # pragma: no cover - gated by TIMEDELTA_COMPONENT_NAMES
            raise AssertionError(name)
        has_nat = jnp.any((jnp.arange(ticks.shape[0]) < n) & (ticks == _NAT))
        if name == "total_seconds" or want_float:
            return (
                jnp.where(valid, out.astype(jnp.float64), jnp.nan),
                has_nat,
            )
        dtype = jnp.int64 if name == "days" else jnp.int32
        return jnp.where(valid, out, 0).astype(dtype), has_nat

    return named_jit(fn, "datetime_td_component")


def td_component(name: str, ticks: Any, unit: str, n: int) -> Tuple[Any, Any]:
    """(device result, out_dtype) for one timedelta field; int64 days /
    int32 remainders upcast to float64+NaN exactly when NaT is present,
    total_seconds is float64 always."""
    import jax

    if name == "total_seconds":
        out, _ = _jit_td_component(name, unit, int(n))(ticks)
        return out, np.dtype(np.float64)
    out_i, has_nat = _jit_td_component(name, unit, int(n))(ticks)
    if bool(_engine_materialize(has_nat)):
        out_f, _ = _jit_td_component(name, unit, int(n), want_float=True)(ticks)
        return out_f, np.dtype(np.float64)
    return out_i, np.dtype(np.int64 if name == "days" else np.int32)


def is_bool_component(name: str) -> bool:
    return name in _BOOL_COMPONENTS


@functools.lru_cache(maxsize=None)
def _jit_component(name: str, unit: str, n: int, want_float: bool = False):
    import jax
    import jax.numpy as jnp

    tps = _TPS[unit]
    day_ticks = 86400 * tps

    def fn(ticks):
        valid = (jnp.arange(ticks.shape[0]) < n) & (ticks != _NAT)
        t = jnp.where(valid, ticks, 0)
        days = jnp.floor_divide(t, day_ticks)
        tod = t - days * day_ticks  # [0, day_ticks)

        # civil_from_days (Gregorian, proleptic)
        z = days + 719468
        era = jnp.floor_divide(z, 146097)
        doe = z - era * 146097
        yoe = jnp.floor_divide(
            doe - doe // 1460 + doe // 36524 - doe // 146096, 365
        )
        y = yoe + era * 400
        doy_mar = doe - (365 * yoe + yoe // 4 - yoe // 100)
        mp = jnp.floor_divide(5 * doy_mar + 2, 153)
        d = doy_mar - jnp.floor_divide(153 * mp + 2, 5) + 1
        m = mp + jnp.where(mp < 10, 3, -9)
        y = y + (m <= 2)

        leap = ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)
        dim = jnp.take(jnp.asarray(_DAYS_IN_MONTH), m, mode="clip") + (
            (m == 2) & leap
        )
        if name == "year":
            out = y
        elif name == "month":
            out = m
        elif name == "day":
            out = d
        elif name == "hour":
            out = tod // (3600 * tps)
        elif name == "minute":
            out = (tod // (60 * tps)) % 60
        elif name == "second":
            out = (tod // tps) % 60
        elif name == "microsecond":
            ns_of_sec = (tod % tps) * (10**9 // tps)
            out = ns_of_sec // 1000
        elif name == "nanosecond":
            ns_of_sec = (tod % tps) * (10**9 // tps)
            out = ns_of_sec % 1000
        elif name in ("dayofweek", "weekday", "day_of_week"):
            out = (days + 3) % 7  # 1970-01-01 is a Thursday (Monday=0 -> 3)
        elif name in ("dayofyear", "day_of_year"):
            out = (
                jnp.take(jnp.asarray(_CUMDAYS), m, mode="clip")
                + d
                + ((m > 2) & leap)
            )
        elif name == "quarter":
            out = (m + 2) // 3
        elif name in ("daysinmonth", "days_in_month"):
            out = dim
        elif name == "is_leap_year":
            out = leap
        elif name == "is_month_start":
            out = d == 1
        elif name == "is_month_end":
            out = d == dim
        elif name == "is_quarter_start":
            out = (d == 1) & (m % 3 == 1)
        elif name == "is_quarter_end":
            out = (d == dim) & (m % 3 == 0)
        elif name == "is_year_start":
            out = (m == 1) & (d == 1)
        elif name == "is_year_end":
            out = (m == 12) & (d == 31)
        else:  # pragma: no cover - gated by COMPONENT_NAMES
            raise AssertionError(name)

        has_nat = jnp.any((jnp.arange(ticks.shape[0]) < n) & (ticks == _NAT))
        if name in _BOOL_COMPONENTS:
            # pandas: NaT rows are False for the predicates
            return jnp.where(valid, out, False), has_nat
        if want_float:
            return jnp.where(valid, out.astype(jnp.float64), jnp.nan), has_nat
        return jnp.where(valid, out, 0).astype(jnp.int32), has_nat

    return named_jit(fn, "datetime_component")


def dt_component(name: str, ticks: Any, unit: str, n: int) -> Tuple[Any, Any]:
    """(device result, out_dtype) for one datetime component.

    One extra scalar fetch decides int32 vs float64 (pandas upcasts exactly
    when NaT is present)."""
    import jax

    fn = _jit_component(name, unit, int(n))
    if name in _BOOL_COMPONENTS:
        out, has_nat = fn(ticks)
        return out, np.dtype(bool)
    # the clean (no-NaT) path runs ONE int32 kernel; only a NaT column pays
    # for the float64 variant (pandas upcasts exactly then)
    out_i, has_nat = fn(ticks)
    if bool(_engine_materialize(has_nat)):
        out_f, _ = _jit_component(name, unit, int(n), want_float=True)(ticks)
        return out_f, np.dtype(np.float64)
    return out_i, np.dtype(np.int32)
