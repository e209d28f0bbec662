"""Dictionary encoding: device codes for host string/object columns.

The device stores no strings.  A host object column becomes device-computable
for EQUALITY/ORDER-shaped ops (groupby keys, merge keys, sort keys, isin,
nunique, value_counts, drop_duplicates) through a lazy, cached factorization:

- ``categories``: the column's distinct values, **sorted** (host-side, small)
- ``codes`` (of a host string/object column; a ``category`` column's codes
  are pandas' own integers, see ``resident_category_column``): per-row
  positions into categories, as a padded sharded device
  array of **float64 with NaN for missing** — NOT int32 with a -1 sentinel.
  Sorted categories make codes order-isomorphic to the strings, and NaN
  codes make every existing numeric-key kernel's missing-data semantics
  (groupby dropna, the strict IEEE total order shared by sort and
  sort-merge join, na_position) apply to string keys verbatim, with zero
  special-casing in the kernels.

This is the staged design SURVEY §7 calls for (codes on device, categories
on host); the reference instead ships whole object partitions to workers
(modin/core/storage_formats/pandas/query_compiler.py groupby/merge on
object keys).  The encoding also powers the ``.str`` PREDICATE/MEASURE ops
(len/contains/startswith/is*/count/find/match — TpuQueryCompiler's
``_try_str_lut`` runs the pandas op once per category and gathers the
lookup table by code on device); only string-OUTPUT str ops
(lower/strip/replace/...) stay host-side.

Encoding is lazy (first use) and cached on the column, so unused string
columns cost nothing and a repeated ``df.groupby("city")`` factorizes once.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Tuple

import numpy as np
import pandas

# Downcast float64->float32 device policies keep integers exact only to
# 2^24; a column with more distinct values than that stays host-only.
_MAX_CATEGORIES = 1 << 24


class DictEncoding(NamedTuple):
    codes: Any  # DeviceColumn of float64 codes (NaN = missing)
    categories: np.ndarray  # sorted distinct values, host-side
    has_nan: bool  # whether any row is missing (NaN code present)


def encode_host_column(col: Any) -> Optional[DictEncoding]:
    """The column's :class:`DictEncoding`, or None.

    None means the column is not dictionary-encodable (non-object dtype,
    unorderable mixed values, or category count past the device-exactness
    bound).  The result is cached on the column either way.
    """
    cached = getattr(col, "_dict_cache", None)
    if cached is not None:
        return cached if cached is not False else None
    result = _encode(col)
    col._dict_cache = result if result is not None else False
    return result


def _encode(col: Any) -> Optional[DictEncoding]:
    from modin_tpu.core.dataframe.tpu.dataframe import DeviceColumn

    from pandas.api.types import is_object_dtype

    dtype = col.pandas_dtype
    # NOTE: NumpyEADtype("object") != np.dtype(object) under ==, so the
    # object check must go through is_object_dtype
    if not (
        is_object_dtype(dtype)
        or (hasattr(pandas, "StringDtype") and isinstance(dtype, pandas.StringDtype))
    ):
        return None
    values = np.asarray(col.to_numpy(), dtype=object)
    try:
        codes, categories = pandas.factorize(values, sort=True, use_na_sentinel=True)
    except TypeError:
        return None  # unorderable mixed values
    categories = np.asarray(categories, dtype=object)
    if len(categories) > _MAX_CATEGORIES:
        return None
    fcodes = codes.astype(np.float64)
    has_nan = bool((codes == -1).any())
    if has_nan:
        fcodes[codes == -1] = np.nan
    return DictEncoding(DeviceColumn.from_numpy(fcodes), categories, has_nan)


def encodable(col: Any) -> bool:
    return encode_host_column(col) is not None


def resident_category_column(col: Any) -> Optional[Any]:
    """The resident form of a CATEGORICAL column: a category ``DeviceColumn``
    holding pandas' own codes (int8 / int16 / int32, -1 = missing: no cast, no
    second copy on the host) with the column's ``CategoricalDtype`` shared.
    The codes are the encoding, in CATEGORY order — pandas sorts categorical
    groups by category order, which is ascending-code order.

    A column that is resident already is returned as it is.  A host
    categorical is uploaded once and remembered under ``_cat_cache`` (NEVER
    ``_dict_cache``: consumers of the sorted-category encoding must not
    receive this category-ordered one), so every frame that shares the host
    column finds the same resident one; the caller puts it in its frame's
    place.  None: not a categorical."""
    if getattr(col, "is_category", False):
        return col
    cached = getattr(col, "_cat_cache", None)
    if cached is not None:
        return cached if cached is not False else None
    from modin_tpu.core.dataframe.tpu.dataframe import DeviceColumn

    cat = col.data
    if not isinstance(cat, pandas.Categorical):
        col._cat_cache = False
        return None
    result = DeviceColumn.from_categorical(cat)
    col._cat_cache = result
    return result


def decode_codes(code_values: np.ndarray, categories: np.ndarray) -> np.ndarray:
    """Host object array for (possibly NaN) float code values."""
    out = np.empty(len(code_values), dtype=object)
    codes = np.asarray(code_values, dtype=np.float64)
    if len(categories) == 0:
        # an all-missing column factorizes to empty categories; every code
        # is NaN
        out[:] = np.nan
        return out
    nan_mask = np.isnan(codes)
    idx = np.where(nan_mask, 0, codes).astype(np.int64)
    out[:] = categories[idx]
    if nan_mask.any():
        out[nan_mask] = np.nan
    return out


def union_categories(
    left: np.ndarray, right: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(union, left_map, right_map): the sorted union of two sorted category
    arrays plus, per side, old-code -> union-code translation tables.

    Both maps preserve order (union is sorted), so remapped codes stay
    order-isomorphic and the device sort-merge join applies unchanged.
    """
    union = np.union1d(left, right)
    left_map = np.searchsorted(union, left).astype(np.float64)
    right_map = np.searchsorted(union, right).astype(np.float64)
    return union, left_map, right_map


def remap_codes_device(codes: Any, table: np.ndarray) -> Any:
    """Device gather: new_codes[i] = table[codes[i]], NaN passing through.

    ``codes`` is the padded float64 device array; ``table`` a small host
    translation array (device_put once)."""
    import jax.numpy as jnp

    from modin_tpu.parallel.engine import upload

    t = upload(table, jnp.float64)
    safe = jnp.where(jnp.isnan(codes), 0.0, codes).astype(jnp.int32)
    gathered = jnp.take(t, safe, mode="clip")
    return jnp.where(jnp.isnan(codes), jnp.nan, gathered)


def lookup_values(values: List[Any], categories: np.ndarray) -> np.ndarray:
    """Float codes of ``values`` within ``categories`` (NaN when absent):
    the host half of a device ``isin`` on an encoded column."""
    out = np.full(len(values), np.nan, dtype=np.float64)
    for i, v in enumerate(values):
        try:
            pos = np.searchsorted(categories, v)
            if pos < len(categories) and categories[pos] == v:
                out[i] = float(pos)
        except TypeError:
            continue  # unorderable value can't be present
    return out
