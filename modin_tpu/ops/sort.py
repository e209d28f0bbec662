"""Device sort kernels: stable multi-key argsort + permutation apply (pad-aware).

TPU-native replacement for the reference's range-partitioning sort
(modin/core/dataframe/pandas/dataframe/dataframe.py:2565 sample->pivot->
shuffle->local-sort): on a device mesh a global ``jnp.argsort`` over a sharded
array already lowers to XLA's distributed sort (bitonic/radix over ICI), so
the four-stage shuffle collapses into one compiled op.

Pad rows are forced to sort after every valid row (stability keeps valid rows,
whose positions are < n, ahead on ties), so sorted frames keep their trailing
pads.
"""

from __future__ import annotations

import functools
from typing import Any, List, Tuple

import numpy as np


from modin_tpu.parallel.engine import materialize as _engine_materialize
from modin_tpu.ops._program import named_jit


def _pad_sentinel(dtype, ascending: bool):
    import jax.numpy as jnp

    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if ascending else -jnp.inf
    if dtype == jnp.bool_:
        return True if ascending else False
    info = np.iinfo(np.dtype(str(dtype)))
    return info.max if ascending else info.min


@functools.lru_cache(maxsize=None)
def _jit_lexsort(n_keys: int, n: int, n_asc: Tuple[bool, ...], na_last: bool):
    import jax
    import jax.numpy as jnp

    def order_one(k_masked, ascending, perm):
        from modin_tpu.ops.structural import float_total_order

        kk = jnp.take(k_masked, perm)
        if jnp.issubdtype(kk.dtype, jnp.floating):
            # total-order int keys: NaN sorts STRICTLY beyond +inf instead of
            # tying with it (a where(nan, inf) mapping misorders inf vs NaN),
            # and pads sort strictly beyond NaN (perm values are original
            # positions, so padness survives earlier rounds)
            t = float_total_order(kk)
            i64 = np.iinfo(np.int64)
            nanm = jnp.isnan(kk)
            is_pad = perm >= n
            if ascending:
                nan_key = np.int64(i64.min + 1) if not na_last else None
                key = t if na_last else jnp.where(nanm, nan_key, t)
                key = jnp.where(is_pad, np.int64(i64.max), key)
                o = jnp.argsort(key, stable=True)
            else:
                key = jnp.where(nanm, np.int64(i64.min + 1), t) if na_last else t
                key = jnp.where(is_pad, np.int64(i64.min), key)
                o = jnp.argsort(key, stable=True, descending=True)
        else:
            o = jnp.argsort(kk, stable=True, descending=not ascending)
        return jnp.take(perm, o)

    def fn(keys: Tuple):
        p = keys[0].shape[0]
        valid = jnp.arange(p) < n
        masked = [
            jnp.where(valid, k, _pad_sentinel(k.dtype, asc))
            for k, asc in zip(keys, n_asc)
        ]
        perm = jnp.arange(p, dtype=jnp.int64)
        # least-significant key first; stable sorts preserve prior order
        for i in range(n_keys - 1, -1, -1):
            with jax.named_scope(f"order_key{i}"):
                perm = order_one(masked[i], n_asc[i], perm)
        return perm

    return named_jit(fn, "sort_lexsort")


def lexsort_permutation(
    keys: List[Any], n: int, ascending: List[bool], na_position: str = "last"
) -> Any:
    """Stable permutation ordering rows by the given padded keys."""
    from modin_tpu.observability import costs as _costs

    if _costs.COST_ON:
        _costs.note_padding(
            "sort.lexsort",
            sum(int(k.shape[0]) * k.dtype.itemsize for k in keys),
            sum(int(n) * k.dtype.itemsize for k in keys),
        )
    fn = _jit_lexsort(
        len(keys), int(n), tuple(bool(a) for a in ascending), na_position == "last"
    )
    return fn(tuple(keys))


def sorted_valid(c, n):
    """(sorted values, n_valid): NaN/pad rows sort to the tail as +inf/max
    surrogates so the first ``n_valid`` entries are exactly the clean data.

    The shared prefix of every sort-shaped reduction (median, quantile,
    nunique, mode) — graftsort caches its output per column
    (ops/sorted_cache.py) so consecutive ops on one column pay one sort.
    """
    import jax.numpy as jnp

    from modin_tpu.ops.reductions import _int_max, _valid_mask

    if c.dtype == jnp.bool_:
        c = c.astype(jnp.int8)  # XLA sort keys; 0/1 round-trips any caller
    is_f = jnp.issubdtype(c.dtype, jnp.floating)
    valid = _valid_mask(c, n) if c.shape[0] != n else None
    if is_f:
        nanm = jnp.isnan(c) if valid is None else (jnp.isnan(c) | ~valid)
        x = jnp.where(nanm, jnp.inf, c)
        n_valid = (n if valid is None else jnp.sum(valid)) - jnp.sum(
            jnp.isnan(c) if valid is None else (jnp.isnan(c) & valid)
        )
    else:
        x = c if valid is None else jnp.where(valid, c, _int_max(c.dtype))
        n_valid = jnp.asarray(n, jnp.int64)
    return jnp.sort(x), n_valid


@functools.lru_cache(maxsize=None)
def _jit_sorted_valid_multi(n_cols: int, n: int):
    import jax

    def fn(cols: Tuple):
        return tuple(sorted_valid(c, n) for c in cols)

    return named_jit(fn, "sort_sorted_valid_multi")


def sorted_valid_columns(arrays: List[Any], n: int) -> List[Tuple[Any, Any]]:
    """Batched sorted-representation build: one jit sorting every column.

    Returns one (sorted values, n_valid) pair per input column; callers
    cache the pairs on their columns via ops/sorted_cache.attach.
    """
    if not arrays:
        return []
    from modin_tpu.observability import costs as _costs

    if _costs.COST_ON:
        _costs.note_padding(
            "sort.sorted_valid",
            sum(int(c.shape[0]) * c.dtype.itemsize for c in arrays),
            sum(int(n) * c.dtype.itemsize for c in arrays),
        )
    return list(_jit_sorted_valid_multi(len(arrays), int(n))(tuple(arrays)))


@functools.lru_cache(maxsize=None)
def _jit_top_k(n: int, k: int, largest: bool, is_float: bool, is_int64: bool, is_signed: bool):
    import jax

    def fn(c):
        import jax.lax as lax
        import jax.numpy as jnp

        P = c.shape[0]
        idx = jnp.arange(P)
        valid = idx < n
        if is_float:
            # IEEE total-order bits: real +/-inf stay DISTINCT from the
            # excluded (NaN/pad) rows, which get the absolute-minimum key
            x = c.astype(jnp.float64)
            nan_row = jnp.isnan(x) & valid
            bad = jnp.isnan(x) | ~valid
            bits = lax.bitcast_convert_type(x, jnp.uint64)
            sign = (bits >> jnp.uint64(63)) == 1
            u = jnp.where(sign, ~bits, bits | jnp.uint64(1 << 63))
            key = u if largest else ~u
            key = jnp.where(bad, jnp.uint64(0), key)
            n_valid = jnp.sum(~bad)
        elif is_int64:
            # signed: order-preserving sign-bit bias to uint64; unsigned:
            # already ordered. Complement flips for smallest-first without
            # the INT_MIN negation overflow.
            if is_signed:
                u = c.astype(jnp.uint64) ^ jnp.uint64(1 << 63)
            else:
                u = c.astype(jnp.uint64)
            key = u if largest else ~u
            key = jnp.where(valid, key, jnp.uint64(0))
            nan_row = jnp.zeros(P, bool)
            n_valid = jnp.sum(valid)
        else:
            x = c.astype(jnp.int64)
            pad = np.iinfo(np.int64).min if largest else np.iinfo(np.int64).max
            x = jnp.where(valid, x, pad)
            key = x if largest else -x
            nan_row = jnp.zeros(P, bool)
            n_valid = jnp.sum(valid)
        _, positions = lax.top_k(key, k)
        # earliest NaN rows, in original order (pandas pads the result with
        # them when k exceeds the valid count)
        nan_key = jnp.where(nan_row, jnp.int64(P) - idx, jnp.int64(-1))
        _, nan_positions = lax.top_k(nan_key, k)
        return positions, nan_positions, n_valid

    return named_jit(fn, "sort_top_k")


def top_k_positions(col, n: int, k: int, largest: bool):
    """Row positions for pandas nlargest/nsmallest keep='first': the k
    best valid values (ties keep the earlier row — XLA top_k is stable),
    then earliest NaN rows as filler when k exceeds the valid count.
    Returns (positions ndarray of length min(k, n), n_valid)."""
    import jax
    import jax.numpy as jnp

    k = max(min(int(k), int(n)), 0)
    if k == 0:
        return np.empty(0, np.int64), 0
    is_float = jnp.issubdtype(col.dtype, jnp.floating)
    is_int64 = col.dtype in (jnp.int64, jnp.uint64)
    is_signed = col.dtype != jnp.uint64
    fn = _jit_top_k(
        int(n), k, bool(largest), bool(is_float), bool(is_int64), bool(is_signed)
    )
    positions, nan_positions, n_valid = _engine_materialize(fn(col))
    n_valid = int(n_valid)
    if k <= n_valid:
        return np.asarray(positions[:k], np.int64), n_valid
    filler = np.asarray(nan_positions[: k - n_valid], np.int64)
    return (
        np.concatenate([np.asarray(positions[:n_valid], np.int64), filler]),
        n_valid,
    )



@functools.lru_cache(maxsize=None)
def _jit_rank(n_cols: int, float_flags: Tuple[bool, ...], n: int, method: str,
              ascending: bool, na_option: str, pct: bool):
    """Column rank with full pandas tie/NaN semantics.

    Sort once per column (order-preserving uint64 keys; NaNs collapse to
    one tied key and zone-sort to the top/bottom/tail per na_option, pads
    strictly last), then every method is a per-group statistic over the
    sorted run: first/last indexes of each tie group give min/max/average,
    the running group ordinal gives dense, and the sorted position itself
    gives 'first'.  Ranks scatter back through the sort permutation."""
    import jax
    import jax.numpy as jnp

    from modin_tpu.ops.structural import float_total_order

    def one(c):
        P = c.shape[0]
        idx = jnp.arange(P)
        valid = idx < n
        is_f = jnp.issubdtype(c.dtype, jnp.floating)
        nanm = (jnp.isnan(c) & valid) if is_f else jnp.zeros(P, bool)
        if jnp.issubdtype(c.dtype, jnp.unsignedinteger):
            ku = c.astype(jnp.uint64)  # already in key order, no sign bias
        else:
            t = float_total_order(c) if is_f else c.astype(jnp.int64)
            ku = t.astype(jnp.uint64) ^ jnp.uint64(1 << 63)
        if not ascending:
            ku = ~ku
        ku = jnp.where(nanm, jnp.uint64(0), ku)  # NaNs tie with each other
        nan_zone = 0 if na_option == "top" else 2
        zone = jnp.where(valid, jnp.where(nanm, nan_zone, 1), 3).astype(jnp.uint8)
        order = jnp.lexsort((ku, zone))  # primary zone, then key, stable
        sku = jnp.take(ku, order)
        szone = jnp.take(zone, order)
        change = (szone[1:] != szone[:-1]) | (sku[1:] != sku[:-1])
        first = jnp.concatenate([jnp.ones(1, bool), change])
        pos = idx  # position within the sorted order
        f_idx = jax.lax.associative_scan(jnp.maximum, jnp.where(first, pos, 0))
        last = jnp.concatenate([change, jnp.ones(1, bool)])
        l_idx = (P - 1) - jax.lax.associative_scan(
            jnp.maximum, jnp.where(last[::-1], pos, 0)
        )[::-1]
        if method == "average":
            ranks = (f_idx + l_idx).astype(jnp.float64) / 2.0 + 1.0
        elif method == "min":
            ranks = f_idx.astype(jnp.float64) + 1.0
        elif method == "max":
            ranks = l_idx.astype(jnp.float64) + 1.0
        elif method == "first":
            ranks = pos.astype(jnp.float64) + 1.0
        else:  # dense
            ranks = jnp.cumsum(first.astype(jnp.int64)).astype(jnp.float64)
        out = jnp.zeros(P, jnp.float64).at[order].set(ranks)
        counted = valid if na_option in ("top", "bottom") else (valid & ~nanm)
        if pct:
            if method == "dense":
                denom = jnp.max(jnp.where(counted, out, 0.0))
            else:
                denom = jnp.sum(counted).astype(jnp.float64)
            out = out / jnp.maximum(denom, 1.0)
        if na_option == "keep":
            out = jnp.where(nanm, jnp.nan, out)
        return out

    def fn(cols: Tuple):
        return tuple(one(c) for c in cols)

    return named_jit(fn, "sort_rank")


def rank_columns(
    cols: List[Any], n: int, method: str, ascending: bool, na_option: str,
    pct: bool,
) -> List[Any]:
    import jax.numpy as jnp

    float_flags = tuple(
        bool(jnp.issubdtype(c.dtype, jnp.floating)) for c in cols
    )
    fn = _jit_rank(
        len(cols), float_flags, int(n), str(method), bool(ascending),
        str(na_option), bool(pct),
    )
    return list(fn(tuple(cols)))
