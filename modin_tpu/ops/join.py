"""Device sort-merge join.

TPU-native replacement for the reference's merge implementations
(modin/core/storage_formats/pandas/merge.py:39 range_partitioning_merge /
:104 row_axis_merge): instead of broadcasting the right frame to every left
partition or shuffling both frames through the object store, the join runs as
one device program family:

1. stable-sort the right keys (keeps pandas' original-order-within-ties);
2. binary-search every left key against the sorted right keys (lo/hi bounds);
3. one host sync for the output row count (data-dependent shape);
4. expand matches with a searchsorted-over-offsets trick and gather both
   sides' columns by position.

Matches pandas ``merge`` row order for ``sort=False``: left order, and
right-side ties in right's original order.  Float keys use an IEEE
total-order int mapping so pandas' merge equality holds exactly
(-0.0 == 0.0; every NaN key matches every other NaN key).
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import numpy as np


from modin_tpu.ops.structural import float_total_order as _total_order


from modin_tpu.parallel.engine import materialize as _engine_materialize
from modin_tpu.ops._program import named_jit


@functools.lru_cache(maxsize=None)
def _jit_composite_codes(n_levels: int, float_flags: Tuple[bool, ...]):
    """Fold multi-column join keys into one int64 code per side.

    Per level, both sides' keys rank against the sorted concatenation of the
    two sides (equal values get equal ranks, order is preserved), then the
    running composite re-ranks after each fold so the code stays < |L|+|R|
    and the product never overflows int64.
    """
    import jax
    import jax.numpy as jnp

    def rank_pair(lv, rv):
        allv = jnp.concatenate([lv, rv])
        s = jnp.sort(allv)
        return (
            jnp.searchsorted(s, lv, side="left"),
            jnp.searchsorted(s, rv, side="left"),
        )

    def fn(lkeys: Tuple, rkeys: Tuple):
        total = lkeys[0].shape[0] + rkeys[0].shape[0]
        lc = rc = None
        for lv, rv, is_f in zip(lkeys, rkeys, float_flags):
            if is_f:
                lv, rv = _total_order(lv), _total_order(rv)
            else:
                lv, rv = lv.astype(jnp.int64), rv.astype(jnp.int64)
            l_i, r_i = rank_pair(lv, rv)
            if lc is None:
                lc, rc = l_i, r_i
            else:
                lc, rc = rank_pair(lc * total + l_i, rc * total + r_i)
        return lc, rc

    return named_jit(fn, "join_composite_codes")


def composite_key_codes(left_keys: list, right_keys: list) -> Tuple[Any, Any]:
    """(left_code, right_code): int64 arrays that compare equal exactly when
    the key tuples compare equal under pandas merge semantics."""
    import jax.numpy as jnp

    float_flags = tuple(
        bool(jnp.issubdtype(k.dtype, jnp.floating)) for k in left_keys
    )
    fn = _jit_composite_codes(len(left_keys), float_flags)
    return fn(tuple(left_keys), tuple(right_keys))


@functools.lru_cache(maxsize=None)
def _jit_match_bounds(n_left: int, n_right: int):
    import jax
    import jax.numpy as jnp

    def fn(left_key, right_key):
        if jnp.issubdtype(right_key.dtype, jnp.floating):
            left_key = _total_order(left_key)
            right_key = _total_order(right_key)
        # pads must sort to the tail and never match
        r_bad = jnp.arange(right_key.shape[0]) >= n_right
        perm0 = jnp.argsort(right_key, stable=True)
        bad_sorted = jnp.take(r_bad, perm0)
        perm = jnp.take(perm0, jnp.argsort(bad_sorted, stable=True))
        n_valid = jnp.sum(~r_bad)
        # the search array must stay monotone through the tail: pads get the
        # dtype's maximum (clipping hi/lo to n_valid excludes boundary ties)
        tail = jnp.arange(right_key.shape[0]) >= n_valid
        if right_key.dtype == jnp.bool_:
            tail_value = True
        else:
            tail_value = np.iinfo(np.dtype(str(right_key.dtype))).max
        rs = jnp.where(tail, tail_value, jnp.take(right_key, perm))

        lo = jnp.searchsorted(rs, left_key, side="left")
        hi = jnp.searchsorted(rs, left_key, side="right")
        lo = jnp.minimum(lo, n_valid)
        hi = jnp.minimum(hi, n_valid)
        counts = hi - lo
        l_valid = jnp.arange(left_key.shape[0]) < n_left
        counts = jnp.where(l_valid, counts, 0)
        total_inner = jnp.sum(counts)
        total_left = jnp.sum(jnp.where(l_valid, jnp.maximum(counts, 1), 0))
        return perm, lo, counts, total_inner, total_left

    return named_jit(fn, "join_match_bounds")


@functools.lru_cache(maxsize=None)
def _jit_expand(p_out: int, n_left: int, how_left: bool):
    import jax
    import jax.numpy as jnp

    def fn(perm, lo, counts):
        l_valid = jnp.arange(counts.shape[0]) < n_left
        if how_left:
            emit = jnp.where(l_valid, jnp.maximum(counts, 1), 0)
        else:
            emit = counts
        ends = jnp.cumsum(emit)
        out_pos = jnp.arange(p_out, dtype=jnp.int64)
        # which left row produced output row j (output pads land on the last
        # left row and are sliced off logically)
        left_pos = jnp.searchsorted(ends, out_pos, side="right")
        left_pos = jnp.minimum(left_pos, counts.shape[0] - 1)
        starts = ends - emit
        within = out_pos - jnp.take(starts, left_pos)
        sorted_right_pos = jnp.take(lo, left_pos) + within
        sorted_right_pos = jnp.clip(sorted_right_pos, 0, perm.shape[0] - 1)
        right_pos = jnp.take(perm, sorted_right_pos)
        if how_left:
            has_match = jnp.take(counts, left_pos) > 0
            right_pos = jnp.where(has_match, right_pos, -1)
        return left_pos, right_pos

    return named_jit(fn, "join_expand")


def sort_merge_positions(
    left_key: Any,
    right_key: Any,
    n_left: int,
    n_right: int,
    how: str = "inner",
) -> Tuple[Any, Any, int]:
    """(left_positions, right_positions, n_out, has_miss) for the joined rows.

    Positions are padded device arrays; ``right_positions == -1`` marks a
    left-join miss.  Exactly one host sync (the inner/left output counts,
    from which ``has_miss`` is derived).
    """
    import jax
    import jax.numpy as jnp

    from modin_tpu.ops.structural import pad_len

    perm, lo, counts, total_inner, total_left = _jit_match_bounds(
        int(n_left), int(n_right)
    )(left_key, right_key)
    inner_count, left_count = (
        int(v) for v in _engine_materialize((total_inner, total_left))
    )
    n_out = left_count if how == "left" else inner_count
    # a left-join miss exists iff some left row matched nothing
    has_miss = how == "left" and left_count > inner_count
    p_out = pad_len(max(n_out, 1))
    if n_out == 0:
        zeros = jnp.zeros(p_out, jnp.int64)
        return zeros, jnp.full(p_out, -1, jnp.int64), 0, False
    left_pos, right_pos = _jit_expand(p_out, int(n_left), how == "left")(
        perm, lo, counts
    )
    return left_pos, right_pos, n_out, has_miss


def merge_positions(
    left_key: Any,
    right_key: Any,
    n_left: int,
    n_right: int,
    how: str = "inner",
) -> Tuple[Any, Any, int, bool]:
    """Router-dispatched match positions (graftmesh).

    When ``decide_layout`` predicts the collective pays at this (rows, mesh
    shape), the right-side sort runs through the all_to_all shuffle
    (ops/spmd.py) — bit-identical positions, different substrate cost; the
    local sort-merge kernel is the fallback for single-shard meshes, small
    frames, and pathological key skew.
    """
    from modin_tpu.ops import router

    if router.decide_layout("merge", int(n_right), payload_cols=1) == "sharded":
        from modin_tpu.ops import spmd

        result = spmd.sharded_merge_positions(
            left_key, right_key, int(n_left), int(n_right), how
        )
        if result is not None:
            return result
    return sort_merge_positions(left_key, right_key, n_left, n_right, how)


@functools.lru_cache(maxsize=None)
def _jit_right_only(p_right: int, n_right: int, n_out: int):
    """Right rows untouched by a left join: (order, count).

    ``order`` sorts unmatched valid right positions first, in original right
    order (pandas outer-merge appendix order); ``count`` is how many.
    """
    import jax
    import jax.numpy as jnp

    def fn(right_pos):
        valid_out = jnp.arange(right_pos.shape[0]) < n_out
        hit = valid_out & (right_pos >= 0)
        safe = jnp.where(hit, right_pos, 0)
        flags = jnp.zeros(p_right, bool).at[safe].set(True)
        # row 0 may have been set by masked-out pads pointing at 0
        flags = flags.at[0].set(jnp.any(hit & (right_pos == 0)))
        valid_r = jnp.arange(p_right) < n_right
        unmatched = (~flags) & valid_r
        m = jnp.sum(unmatched)
        order = jnp.argsort(~unmatched, stable=True)
        return order, m

    return named_jit(fn, "join_right_only")


def right_only_positions(right_pos, p_right: int, n_right: int, n_out: int):
    """(positions, count) of right rows missing from the left-join output."""
    import jax

    order, m = _jit_right_only(int(p_right), int(n_right), int(n_out))(right_pos)
    return order, int(_engine_materialize(m))


@functools.lru_cache(maxsize=None)
def _jit_gather_with_null(n_cols: int):
    """Gather right-side columns by position; position -1 becomes NaN/NaT."""
    import jax
    import jax.numpy as jnp

    def fn(cols: Tuple, positions):
        safe = jnp.where(positions >= 0, positions, 0)
        out = []
        for c in cols:
            vals = jnp.take(c, safe, axis=0)
            if jnp.issubdtype(c.dtype, jnp.floating):
                vals = jnp.where(positions >= 0, vals, jnp.nan)
            else:
                # int/bool/datetime columns get the int64-min NaT sentinel;
                # the caller promotes dtypes when misses exist
                vals = jnp.where(
                    positions >= 0, vals, _null_sentinel(c.dtype)
                )
            out.append(vals)
        return tuple(out)

    return named_jit(fn, "join_gather_with_null")


def _null_sentinel(dtype):
    import jax.numpy as jnp

    if dtype == jnp.bool_:
        return False
    return np.iinfo(np.dtype(str(dtype))).min


def gather_right_columns(cols, positions) -> list:
    """Gather right columns for the join output (missing -> null sentinel)."""
    if not cols:
        return []
    return list(_jit_gather_with_null(len(cols))(tuple(cols), positions))


@functools.lru_cache(maxsize=None)
def _jit_duplicated(n_cols: int, float_flags: Tuple[bool, ...], n: int, keep: Any):
    """Row-duplicate mask over one frame's key columns.

    The same rank-fold as the join codes, against a single frame: per
    column rank via sorted searchsorted (floats through the IEEE total
    order, so every NaN compares equal — pandas duplicated treats NaNs as
    duplicates of each other), composite re-ranked per fold to stay in
    int64.  A stable argsort of the codes groups equal rows with original
    order preserved; first/last flags inside each group give every keep
    variant, scattered back to row positions."""
    import jax
    import jax.numpy as jnp

    def rank(v):
        s = jnp.sort(v)
        return jnp.searchsorted(s, v, side="left")

    def fn(cols: Tuple):
        P = cols[0].shape[0]
        valid = jnp.arange(P) < n
        code = None
        for c, is_f in zip(cols, float_flags):
            v = _total_order(c) if is_f else c.astype(jnp.int64)
            r = rank(v)
            code = r if code is None else rank(code * jnp.int64(P) + r)
        code = jnp.where(valid, code, jnp.int64(-1))  # pads group below
        order = jnp.argsort(code, stable=True)
        sc = jnp.take(code, order)
        change = sc[1:] != sc[:-1]
        first = jnp.concatenate([jnp.ones(1, bool), change])
        last = jnp.concatenate([change, jnp.ones(1, bool)])
        if keep == "first":
            dup_sorted = ~first
        elif keep == "last":
            dup_sorted = ~last
        else:  # keep=False: every member of a >1 group
            dup_sorted = ~(first & last)
        return jnp.zeros(P, bool).at[order].set(dup_sorted)

    return named_jit(fn, "join_duplicated")


def duplicated_mask(cols: list, n: int, keep: Any):
    """Boolean duplicate-row mask (pandas ``duplicated`` semantics) over
    padded device key columns."""
    import jax.numpy as jnp

    float_flags = tuple(
        bool(jnp.issubdtype(c.dtype, jnp.floating)) for c in cols
    )
    fn = _jit_duplicated(len(cols), float_flags, int(n), keep)
    return fn(tuple(cols))
