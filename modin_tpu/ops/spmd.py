"""graftmesh: sharded (SPMD) sort & merge-join kernels over ``range_shuffle``.

The 2-D partition grid of the reference maps onto the JAX device mesh where
row-partitioning is a sharding spec, not a Python object (SURVEY §7).  Most
hot paths exploit that for free — a ``jnp.sum`` over a row-sharded array
lowers to per-shard partials + a ``psum``, elementwise/groupby likewise —
but the sort-shaped kernels did not: a global ``jnp.argsort`` over a sharded
array gathers everything onto one device on most backends, and the
merge-join's right-side sort has the same shape.  This module routes those
two through the existing sample -> pivots -> ``lax.all_to_all`` -> per-shard
local sort machinery (parallel/shuffle.py), the MapReduce-onto-shard_map
design DrJAX (arXiv:2403.07128) and Xorbits' operator tiling
(arXiv:2401.00865) describe:

- :func:`sharded_sorted_valid` — the sorted-representation build (the
  shared prefix of median/quantile/nunique/mode, ops/sort.py
  ``sorted_valid``) as one range-partitioned shuffle + per-shard local
  sorts, bit-identical to the local build (NaN/pad rows collapse to the
  same +inf / int-max tail);
- :func:`sharded_merge_positions` — the merge-join's match positions with
  the right-side O(n log n) sort replaced by the shuffle; the probe
  (searchsorted) and expansion stages reuse ops/join.py unchanged, so the
  output position arrays are bit-identical to the local path's.

Every entry point returns ``None`` when the sharded path declines (single
shard, pathological key skew) — callers keep their local kernels as the
fallback, and ops/router.py ``decide_layout`` decides when the collective
pays (the router, not a flag).
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Tuple

import numpy as np

from modin_tpu.parallel.engine import materialize as _engine_materialize
from modin_tpu.ops._program import named_jit


@functools.lru_cache(maxsize=None)
def _jit_prep_sorted(n: int):
    """NaN/pad collapse + valid count, mirroring ops/sort.py sorted_valid:
    floats map NaN (and pad rows) to +inf with ``n_valid`` excluding NaNs,
    ints map pad rows to the dtype max with ``n_valid == n``."""
    import jax
    import jax.numpy as jnp

    def fn(c):
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
            n_valid = jnp.asarray(n_valid, jnp.int64)
        else:
            x = c if valid is None else jnp.where(valid, c, _int_max(c.dtype))
            n_valid = jnp.asarray(n, jnp.int64)
        return x, n_valid

    return named_jit(fn, "spmd_prep_sorted")


@functools.lru_cache(maxsize=None)
def _jit_seal_tail(n: int):
    """Overwrite the compacted shuffle output's pad tail (gather garbage)
    with the sorted-representation sentinel, making the rep byte-identical
    to the local ``jnp.sort`` build."""
    import jax
    import jax.numpy as jnp

    def fn(xs):
        from modin_tpu.ops.reductions import _int_max

        idx = jnp.arange(xs.shape[0])
        if jnp.issubdtype(xs.dtype, jnp.floating):
            sentinel = jnp.inf
        else:
            sentinel = _int_max(xs.dtype)
        return jnp.where(idx < n, xs, sentinel)

    return named_jit(fn, "spmd_seal_tail")


def sharded_sorted_valid(c: Any, n: int) -> Optional[Tuple[Any, Any]]:
    """``(sorted values, n_valid)`` of one padded column via the all_to_all
    shuffle, or None when the sharded path declines (single shard /
    pathological skew) — the caller's local ``sorted_valid`` is the
    fallback and produces the identical representation.
    """
    from modin_tpu.observability import costs as _costs
    from modin_tpu.parallel.mesh import num_row_shards
    from modin_tpu.parallel.shuffle import ShuffleSkewError, range_shuffle

    if num_row_shards() < 2:
        return None
    if _costs.COST_ON:
        # same site + accounting as the local build (sort.sorted_valid):
        # padding waste must describe the workload, not the routing choice
        _costs.note_padding(
            "sort.sorted_valid",
            int(c.shape[0]) * c.dtype.itemsize,
            int(n) * c.dtype.itemsize,
        )
    x, n_valid = _jit_prep_sorted(int(n))(c)
    try:
        xs, _cols, _counts, _pivots = range_shuffle(x, [], int(n), local_sort=True)
    except ShuffleSkewError:
        return None
    return _jit_seal_tail(int(n))(xs), n_valid


def sharded_sorted_valid_columns(
    arrays: List[Any], n: int
) -> Optional[List[Tuple[Any, Any]]]:
    """Sharded rep build for a batch of columns; None when ANY column
    declines, so a mixed batch falls back to the one-jit local build whole
    (callers never mix build provenance within one plan)."""
    out = []
    for c in arrays:
        pair = sharded_sorted_valid(c, n)
        if pair is None:
            return None
        out.append(pair)
    return out


@functools.lru_cache(maxsize=None)
def _jit_total_codes():
    """Both sides' join keys as int64 total-order codes (one jit): floats
    through the IEEE total order (-0.0 == 0.0, every NaN -> one key — the
    pandas merge equality), everything else widened to int64."""
    import jax
    import jax.numpy as jnp

    from modin_tpu.ops.structural import float_total_order

    def enc(v):
        if jnp.issubdtype(v.dtype, jnp.floating):
            return float_total_order(v)
        return v.astype(jnp.int64)

    def fn(lk, rk):
        return enc(lk), enc(rk)

    return named_jit(fn, "spmd_total_codes")


@functools.lru_cache(maxsize=None)
def _jit_match_presorted(n_left: int, n_right: int):
    """Match bounds of raw left keys against an ALREADY globally sorted
    right key column (the shuffle's compacted output).  The pad tail is
    sealed to int64 max so the search array stays monotone; clipping lo/hi
    to ``n_right`` excludes boundary ties exactly like the local
    ``_jit_match_bounds``."""
    import jax
    import jax.numpy as jnp

    def fn(left_key, rs):
        i64max = np.iinfo(np.int64).max
        tail = jnp.arange(rs.shape[0]) >= n_right
        rs = jnp.where(tail, i64max, rs)
        lo = jnp.searchsorted(rs, left_key, side="left")
        hi = jnp.searchsorted(rs, left_key, side="right")
        lo = jnp.minimum(lo, n_right)
        hi = jnp.minimum(hi, n_right)
        counts = hi - lo
        l_valid = jnp.arange(left_key.shape[0]) < n_left
        counts = jnp.where(l_valid, counts, 0)
        total_inner = jnp.sum(counts)
        total_left = jnp.sum(jnp.where(l_valid, jnp.maximum(counts, 1), 0))
        return lo, counts, total_inner, total_left

    return named_jit(fn, "spmd_match_presorted")


def sharded_merge_positions(
    left_key: Any,
    right_key: Any,
    n_left: int,
    n_right: int,
    how: str = "inner",
) -> Optional[Tuple[Any, Any, int, bool]]:
    """``sort_merge_positions`` with the right-side sort done by the
    all_to_all shuffle; same contract, bit-identical positions.

    The right keys (int64 total-order codes) range-partition over the mesh
    with per-shard local sorts — arrival order within a shard is original
    right order, so equal keys keep right-original tie order exactly like
    the local stable sort.  The shuffled row-id payload IS the local
    path's ``perm``; probe + expansion reuse ops/join.py.  None = decline
    (single shard / skew), caller falls back to the local kernel.
    """
    import jax.numpy as jnp

    from modin_tpu.ops.join import _jit_expand
    from modin_tpu.ops.structural import pad_len
    from modin_tpu.parallel.mesh import num_row_shards
    from modin_tpu.parallel.shuffle import ShuffleSkewError, range_shuffle

    if num_row_shards() < 2:
        return None
    lk, rk = _jit_total_codes()(left_key, right_key)
    iota = jnp.arange(rk.shape[0], dtype=jnp.int64)
    try:
        rs, (perm,), _counts, _pivots = range_shuffle(
            rk, [iota], int(n_right), local_sort=True
        )
    except ShuffleSkewError:
        return None
    lo, counts, total_inner, total_left = _jit_match_presorted(
        int(n_left), int(n_right)
    )(lk, rs)
    inner_count, left_count = (
        int(v) for v in _engine_materialize((total_inner, total_left))
    )
    n_out = left_count if how == "left" else inner_count
    has_miss = how == "left" and left_count > inner_count
    p_out = pad_len(max(n_out, 1))
    if n_out == 0:
        zeros = jnp.zeros(p_out, jnp.int64)
        return zeros, jnp.full(p_out, -1, jnp.int64), 0, False
    left_pos, right_pos = _jit_expand(p_out, int(n_left), how == "left")(
        perm, lo, counts
    )
    return left_pos, right_pos, n_out, has_miss
