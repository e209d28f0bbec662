"""Groupby reductions as device segment operations (pad-aware).

TPU-native replacement for the reference's GroupByReduce map+reduce pair
(modin/core/dataframe/algebra/groupby.py:33, partition_manager.py:303): the
per-block local groupby + cross-block regroup collapses into factorize (code
assignment) + ``jax.ops.segment_*`` in one compiled program.  On a sharded
array XLA emits per-shard segment partials + a psum over ICI — exactly the
map/tree-reduce structure of the reference, compiled instead of scheduled.

Key factorization strategies:
- int-like keys with a small value range     -> direct offset codes (no sort);
                                                when every id of the range
                                                occurs the offsets are the
                                                codes, else one remap gather
- a ``category`` column's codes               -> the codes themselves: their
                                                range is [0, len(categories))
                                                from the dtype, so no min/max
                                                pass and no fetch; -1 (missing)
                                                is the dropped or the NaN group
- anything else                              -> jnp.unique (device sort, one
                                                host sync for the group count)

Pad rows (positions >= n) are always routed to the overflow bucket
``num_groups`` and sliced off after aggregation; NaN keys share that bucket
when ``dropna=True``.
"""

from __future__ import annotations

import functools
from types import MappingProxyType
from typing import Any, List, Optional, Tuple

import numpy as np

# aggregations expressible as segment reductions
SEGMENT_AGGS = {
    "sum", "count", "mean", "min", "max", "prod", "size", "var", "std",
    "any", "all", "sem",
}

# order-statistic aggregations: device sort within groups (the reference
# routes these through range-partitioning + per-shard pandas,
# modin/core/dataframe/pandas/dataframe/dataframe.py:4163; on TPU a
# lexsort + gather keeps the whole thing on device)
ORDER_AGGS = {"median", "quantile", "nunique", "first", "last"}

_RANGE_LIMIT = 1 << 22  # max direct-range width before falling back to unique


from modin_tpu.observability import meters as _meters
from modin_tpu.observability import spans as _spans
from modin_tpu.parallel.engine import materialize as _engine_materialize
from modin_tpu.parallel.engine import upload as _engine_upload
from modin_tpu.ops._program import named_jit


class _TooManyGroups(Exception):
    pass


def _slice_pad(r, n_groups: int, p_out: int):
    """Slice off the overflow bucket and pad the result to the shard multiple."""
    import jax.numpy as jnp

    r = r[:n_groups]
    if p_out > n_groups:
        r = jnp.concatenate([r, jnp.zeros(p_out - n_groups, r.dtype)])
    return r


@functools.lru_cache(maxsize=None)
def _jit_key_minmax(n: int):
    import jax
    import jax.numpy as jnp

    def fn(k):
        valid = jnp.arange(k.shape[0]) < n
        kmin = jnp.min(jnp.where(valid, k, np.iinfo(np.int64).max))
        kmax = jnp.max(jnp.where(valid, k, np.iinfo(np.int64).min))
        return kmin, kmax

    return named_jit(fn, "groupby_key_minmax")


@functools.lru_cache(maxsize=None)
def _jit_range_ids(n: int, width: int):
    # kmin is a traced operand: recompiles key on (n, width) only
    import jax
    import jax.numpy as jnp

    def fn(k, kmin):
        valid = jnp.arange(k.shape[0]) < n
        # width <= _RANGE_LIMIT: the ids fit int32, half the bytes to hold and
        # to read again in every pass over the codes
        return jnp.where(valid, jnp.clip(k - kmin, 0, width), width).astype(jnp.int32)

    return named_jit(fn, "groupby_range_ids")


@functools.lru_cache(maxsize=None)
def _jit_category_ids(n: int, width: int, nan_group: bool):
    """A category column's codes (int8 / int16 / int32 as pandas holds them,
    -1 = missing) as the ids of a key of ``width`` categories: int32, never
    wider; a missing key takes the slot ``width`` where the NaN group is kept
    (``nan_group``), and it and the pad rows the overflow id otherwise."""
    import jax
    import jax.numpy as jnp

    overflow = width + 1 if nan_group else width

    def fn(c):
        c = c.astype(jnp.int32)
        keep = jnp.arange(c.shape[0]) < n
        if nan_group:
            c = jnp.where(c < 0, width, c)
        else:
            keep &= c >= 0
        return jnp.where(keep, c, overflow)

    return named_jit(fn, "groupby_category_ids")


def _category_level(k: Any, n: int, width: int, nan_slot: bool):
    """``(ids, slots, uniques)`` of a category key of ``width`` categories:
    the slots are the categories in their order, then one for the missing keys
    where ``nan_slot`` (code -1 among ``uniques``; without it a missing key
    takes the overflow id, like a pad row).  No pass over the key finds the
    range, and no histogram: the caller counts the ids (alone, or composed
    with the other keys') to learn which groups are present."""
    slots = width + 1 if nan_slot else width
    ids = _jit_category_ids(n, width, nan_slot)(k)
    uniques = np.arange(slots, dtype=np.dtype(str(k.dtype)))
    if nan_slot:
        uniques[width] = -1
    return ids, slots, uniques


class RangeCodes:
    """The codes of an integer key whose range is dense, not written out: row
    i's code is ``key[i] - kmin`` (pads and rows past ``n``: ``width``).

    A request over 1e8 rows holds 0.4 GB less for it.  The sorted tiles, which
    take rows a chunk at a time anyway, derive a chunk's codes from the key;
    every other consumer asks :func:`codes_array`, which writes them out once.
    """

    __slots__ = ("key", "kmin", "width", "n", "_array")
    dtype = np.dtype(np.int32)

    def __init__(self, key: Any, kmin: int, width: int, n: int) -> None:
        self.key, self.kmin, self.width, self.n = key, int(kmin), int(width), int(n)
        self._array = None

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.key.shape

    def devices(self):
        return self.key.devices()

    def operand(self) -> Tuple:
        """What a program derives the codes from: the key and two scalars (no
        recompile for another ``kmin`` or ``n``)."""
        return (self.key, np.int64(self.kmin), np.int64(self.n))


def _tiles_operand(codes: Any) -> Any:
    """``codes`` as the sorted tiles take them: the array, or what to derive
    them from a chunk at a time."""
    return codes.operand() if isinstance(codes, RangeCodes) else codes


def codes_array(codes: Any) -> Any:
    """``codes`` as a device array (what ``factorize_keys`` returns may be a
    :class:`RangeCodes`)."""
    if not isinstance(codes, RangeCodes):
        return codes
    if codes._array is None:
        import jax.numpy as jnp

        codes._array = _jit_range_ids(codes.n, codes.width)(
            codes.key, jnp.int64(codes.kmin)
        )
    return codes._array


@functools.lru_cache(maxsize=None)
def _jit_scatter_counts(width: int):
    import jax
    import jax.numpy as jnp

    def fn(ids):
        return jnp.zeros(width + 1, jnp.int64).at[ids].add(1)[:width]

    return named_jit(fn, "groupby_scatter_counts")


def _histogram_form(ids, width: int) -> str:
    """The device form of a histogram of ``ids`` in [0, width), read from the
    width, the platform and the shard count.  An XLA scatter-add serialises on
    a TPU (14.7 s at 1e8 rows), so there the Pallas kernel counts up to its
    ``MAX_GROUPS`` ids on the MXU (the one-hots of an id's two digits,
    contracted over the rows: exact, and under ``shard_map`` + ``psum`` over a
    row-sharded key) and the sorted tiles (no value column) a wider range of
    an unsharded key; the scatter is left with the CPU, where it is fine, and
    with a wide range of a row-sharded key.  (Under the test hook the Pallas
    kernel is chosen off the chip too, and runs in interpret mode.)"""
    from modin_tpu.ops.pallas.groupby_kernels import MAX_GROUPS, bincount_supported
    from modin_tpu.parallel.mesh import num_row_shards

    if bincount_supported(ids, width, _tpu_forms(ids)):
        return "pallas_bincount"
    if width > MAX_GROUPS and _tpu_forms(ids) and num_row_shards() == 1:
        return "sorted_tiles"
    return "scatter_counts"


def _histogram(ids, width: int, form: str):
    """Device histogram of ids in [0, width) (int64); the overflow id
    ``width`` and anything past it is dropped."""
    if form == "sorted_tiles":
        fn = _jit_sorted_tiles("size", 0, width + 1, width, False, _SORT_CHUNK)
        return fn((), _tiles_operand(ids))
    if form == "pallas_bincount":
        from modin_tpu.ops.pallas.groupby_kernels import pallas_bincount

        return pallas_bincount(codes_array(ids), width, interpret=not _on_tpu(ids))
    return _jit_scatter_counts(width)(codes_array(ids))


def _count_ids(ids, width: int) -> np.ndarray:
    """Host histogram of a key's range ids (or composite codes): the
    factorisation's by-product that says which groups are present."""
    form = _histogram_form(ids, width)
    if _meters.ACCOUNTING_ON:
        _meters.note_groupby_form(form)
    with _spans.span("groupby.factorize", layer="QUERY-COMPILER", form=form, width=width):
        return np.asarray(_engine_materialize(_histogram(ids, width, form)))


@functools.lru_cache(maxsize=None)
def _jit_range_codes(n: int, n_groups: int):
    import jax
    import jax.numpy as jnp

    def fn(k, kmin, remap):
        valid = jnp.arange(k.shape[0]) < n
        width = remap.shape[0]
        safe = jnp.where(valid, jnp.clip(k - kmin, 0, width - 1), 0)
        return jnp.where(valid, jnp.take(remap, safe), n_groups).astype(jnp.int32)

    return named_jit(fn, "groupby_range_codes")


@functools.lru_cache(maxsize=None)
def _jit_float_prep(n: int):
    import jax
    import jax.numpy as jnp

    def fn(k):
        valid = jnp.arange(k.shape[0]) < n
        has_nan = jnp.any(jnp.isnan(k) & valid)
        return jnp.where(valid, k, jnp.nan), has_nan

    return named_jit(fn, "groupby_float_prep")


@functools.lru_cache(maxsize=None)
def _jit_int_prep(n: int):
    import jax
    import jax.numpy as jnp

    def fn(k):
        valid = jnp.arange(k.shape[0]) < n
        return jnp.where(valid, k, k[0])

    return named_jit(fn, "groupby_int_prep")


@functools.lru_cache(maxsize=None)
def _jit_mask_codes(n: int, overflow: int):
    import jax
    import jax.numpy as jnp

    def fn(codes):
        valid = jnp.arange(codes.shape[0]) < n
        return jnp.where(valid, codes, overflow)

    return named_jit(fn, "groupby_mask_codes")


# Bounded memo of key factorizations.  Grouping by the same key columns
# repeatedly (df.groupby(k).sum() then .mean() ...) re-derives identical
# codes; the cache keys on the device arrays' identity so any new/modified
# column misses.  Strong refs to the key arrays keep ids stable; the size
# bound caps pinned device memory.
_FACTORIZE_CACHE: List[
    Tuple[Tuple, List[Any], Tuple[Any, int, List[np.ndarray], Any]]
] = []
_FACTORIZE_CACHE_MAX = 8


def clear_factorize_cache() -> None:
    """Drop all memoized key factorizations (cold-path benchmarking and
    tests: a warm memo turns groupby timings into cache-hit lookups)."""
    _FACTORIZE_CACHE.clear()


def factorize_keys_cached(
    key_cols: List[Any],
    n: int,
    dropna: bool = True,
    code_widths: Optional[Tuple[Optional[int], ...]] = None,
) -> Tuple[Any, int, List[np.ndarray], Any]:
    """Memoized :func:`factorize_keys` (same-identity key columns hit)."""
    code_widths = tuple(code_widths) if code_widths is not None else None
    cache_key = (tuple(id(k) for k in key_cols), int(n), bool(dropna), code_widths)
    for entry_key, _refs, result in _FACTORIZE_CACHE:
        if entry_key == cache_key:
            return result
    result = factorize_keys(key_cols, n, dropna, code_widths)
    _FACTORIZE_CACHE.append((cache_key, list(key_cols), result))
    if len(_FACTORIZE_CACHE) > _FACTORIZE_CACHE_MAX:
        _FACTORIZE_CACHE.pop(0)
    return result


def factorize_keys(
    key_cols: List[Any],
    n: int,
    dropna: bool = True,
    code_widths: Optional[Tuple[Optional[int], ...]] = None,
    _nan_slots: bool = False,
) -> Tuple[Any, int, List[np.ndarray], Any]:
    """Device factorization of one or more padded key columns (logical len n).

    ``code_widths[i]``, where given and not None, says that key ``i`` is a
    category column's codes: integers in [-1, width), -1 = missing.  Such a
    key is a dense integer key whose range the dtype gives: no
    ``groupby_key_minmax`` launch and no fetch for it, the codes are never
    widened past int32, and its group keys come back as **codes** (-1 for the
    NaN group, kept last when ``dropna`` is False) for the caller to wrap in
    the dtype.  Unobserved categories are not groups (the histogram's
    ``present``, as for any integer key).  The missing keys are first left out
    of the ids, and the histogram's total says whether there were any: only
    then, and only where ``dropna`` is False, are the ids made again with a
    NaN slot a category level (``_nan_slots``), so a key without missing
    values, of which every category occurs, never pays a remap.

    Returns (codes, num_groups, group_key_arrays_host, sizes): ``codes`` maps
    each row to [0, num_groups), with pads (and NaN keys when dropna) mapped
    to ``num_groups``; the ``groupby_*`` functions of this module take them as
    returned, anything else goes through :func:`codes_array` (a dense wide
    range's codes come as :class:`RangeCodes`).  Group key values are host-side, sorted ascending
    (pandas sort=True order); a NaN group, when kept, is last.  ``sizes`` is
    a host int64 array of per-group row counts where the factorization
    computed one anyway (range/multi-key paths), else None — callers reuse it
    so ``size``/``mean`` aggregations skip a histogram pass.
    """
    import jax
    import jax.numpy as jnp

    if code_widths is None:
        code_widths = (None,) * len(key_cols)
    if len(key_cols) == 1:
        k = key_cols[0]
        kdt = k.dtype
        if code_widths[0] is not None:
            ids, slots, uniques = _category_level(k, n, int(code_widths[0]), _nan_slots)
            counts = _count_ids(ids, slots) if slots else np.zeros(0, np.int64)
            if not dropna and not _nan_slots and int(counts.sum()) < n:
                return factorize_keys(key_cols, n, dropna, code_widths, True)
            if counts.all():
                return ids, slots, [uniques], counts
            present = np.nonzero(counts)[0]
            remap = np.full(slots + 1, len(present), dtype=np.int64)
            remap[present] = np.arange(len(present))
            codes = _jit_remap(len(present))(ids, _engine_upload(remap))
            return codes, len(present), [uniques[present]], counts[present]
        if jnp.issubdtype(kdt, jnp.integer) or kdt == jnp.bool_:
            k64 = k.astype(jnp.int64)
            kmin, kmax = (int(v) for v in _engine_materialize(_jit_key_minmax(n)(k64)))
            width = kmax - kmin + 1
            if width <= _RANGE_LIMIT:
                # where the sorted tiles make the histogram the range ids are
                # not written out (RangeCodes): the tiles read the key
                ids = RangeCodes(k64, kmin, width, n)
                if _histogram_form(k64, width) != "sorted_tiles":
                    ids = codes_array(ids)
                counts = _count_ids(ids, width)
                present = np.nonzero(counts)[0]
                if len(present) == width:
                    # every id of the range occurs: the remap would be the
                    # identity and the ids (pads -> width) are the codes
                    codes = ids
                else:
                    remap = np.full(width, len(present), dtype=np.int64)
                    remap[present] = np.arange(len(present))
                    codes = _jit_range_codes(n, len(present))(
                        k64, jnp.int64(kmin), _engine_upload(remap)
                    )
                uniques = (present + kmin).astype(np.int64)
                if kdt == jnp.bool_:
                    uniques = uniques.astype(bool)
                else:
                    uniques = uniques.astype(np.dtype(str(kdt)))
                return codes, len(present), [uniques], counts[present]
            # large-range ints: unique path with pads mapped to k[0]
            k_prepped = _jit_int_prep(n)(k64)
            uniques, codes = jnp.unique(k_prepped, return_inverse=True)
            n_groups = int(uniques.shape[0])
            codes = _jit_mask_codes(n, n_groups)(codes)
            uniques_host = np.asarray(_engine_materialize(uniques)).astype(np.dtype(str(kdt)))
            return codes, n_groups, [uniques_host], None
        if jnp.issubdtype(kdt, jnp.floating):
            k_prepped, has_nan = _jit_float_prep(n)(k)
            # the nan flag is a device scalar: fetch it through the seam so a
            # device failure here classifies/retries instead of surfacing raw
            has_nan = bool(_engine_materialize(has_nan))
            uniques, codes = jnp.unique(k_prepped, return_inverse=True)
            uniques_host = np.asarray(_engine_materialize(uniques))
            n_valid = int(np.sum(~np.isnan(uniques_host)))
            # jnp.unique sorts NaN last; every NaN row (and pad) got a code
            # >= n_valid — clamp them to one bucket
            if dropna or not has_nan:
                codes = _jit_clamp_codes(n, n_valid)(codes)
                return codes, n_valid, [uniques_host[:n_valid]], None
            # keep the NaN group (real NaNs), pads -> overflow
            codes = _jit_nan_group_codes(n, n_valid)(codes, k)
            return codes, n_valid + 1, [
                np.concatenate([uniques_host[:n_valid], [np.nan]])
            ], None
        raise _TooManyGroups()

    # multi-key: combine per-level codes into one composite code
    level_codes = []
    level_uniques = []
    n_groups_each = []
    for k, width in zip(key_cols, code_widths):
        if width is not None:
            # a category level: its slots are known, and the composite's
            # histogram says which of them occur
            codes_i, n_i, uniques_i = _category_level(k, n, int(width), _nan_slots)
        else:
            codes_i, n_i, (uniques_i,), _sizes_i = factorize_keys([k], n, dropna=dropna)
        level_codes.append(codes_array(codes_i))
        level_uniques.append(uniques_i)
        n_groups_each.append(n_i)
    total = int(np.prod(n_groups_each, dtype=object))
    if total > _RANGE_LIMIT * 4:
        raise _TooManyGroups()
    if total == 0:
        empty = [np.asarray(u)[:0] for u in level_uniques]
        return jnp.zeros_like(level_codes[0]), 0, empty, np.zeros(0, np.int64)
    composite = _jit_composite(tuple(n_groups_each), n, total)(tuple(level_codes))
    counts = _count_ids(composite, total)
    if (
        not dropna
        and not _nan_slots
        and any(w is not None for w in code_widths)
        and int(counts.sum()) < n
    ):
        return factorize_keys(key_cols, n, dropna, code_widths, True)
    present = np.nonzero(counts)[0]
    if len(present) == total:
        # every combination of level codes occurs: identity remap, as above
        codes = composite
    else:
        remap = np.full(total + 1, len(present), dtype=np.int64)
        remap[present] = np.arange(len(present))
        codes = _jit_remap(len(present))(composite, _engine_upload(remap))
    keys_out: List[np.ndarray] = []
    rem = present.copy()
    for uniques_i, n_i in zip(reversed(level_uniques), reversed(n_groups_each)):
        keys_out.append(np.asarray(uniques_i)[rem % n_i])
        rem = rem // n_i
    keys_out.reverse()
    return codes, len(present), keys_out, counts[present]


@functools.lru_cache(maxsize=None)
def _jit_clamp_codes(n: int, n_valid: int):
    import jax
    import jax.numpy as jnp

    def fn(codes):
        valid = jnp.arange(codes.shape[0]) < n
        return jnp.where(valid, jnp.minimum(codes, n_valid), n_valid)

    return named_jit(fn, "groupby_clamp_codes")


@functools.lru_cache(maxsize=None)
def _jit_nan_group_codes(n: int, n_valid: int):
    import jax
    import jax.numpy as jnp

    def fn(codes, k):
        valid = jnp.arange(codes.shape[0]) < n
        is_nan = jnp.isnan(k) & valid
        clamped = jnp.minimum(codes, n_valid + 1)
        out = jnp.where(is_nan, n_valid, clamped)
        return jnp.where(valid, out, n_valid + 1)

    return named_jit(fn, "groupby_nan_group_codes")


@functools.lru_cache(maxsize=None)
def _jit_composite(n_groups_each: Tuple[int, ...], n: int, total: int):
    import jax
    import jax.numpy as jnp

    def fn(level_codes: Tuple):
        valid = jnp.arange(level_codes[0].shape[0]) < n
        # a row is valid only if every level code is in range
        in_range = valid
        for codes_i, n_i in zip(level_codes, n_groups_each):
            in_range = in_range & (codes_i < n_i)
        composite = jnp.zeros(level_codes[0].shape, jnp.int64)
        for codes_i, n_i in zip(level_codes, n_groups_each):
            composite = composite * n_i + jnp.minimum(codes_i, n_i - 1)
        # total <= 4 * _RANGE_LIMIT: int32 like a single key's range codes
        return jnp.where(in_range, composite, total).astype(jnp.int32)

    return named_jit(fn, "groupby_composite")


@functools.lru_cache(maxsize=None)
def _jit_remap(n_present: int):
    import jax
    import jax.numpy as jnp

    def fn(composite, remap):
        return jnp.take(remap, composite).astype(jnp.int32)

    return named_jit(fn, "groupby_remap")


@functools.lru_cache(maxsize=None)
def _jit_segment_agg(
    agg: str, n_cols: int, num_segments: int, ddof: int, p_out: int,
    adaptive: bool = False,
    has_sizes: bool = False,
):
    """One jit computing the aggregation for every value column; results are
    sliced to the real group count and padded to the shard multiple.

    ``adaptive`` (single-shard meshes only — lax.cond over sharded operands
    is unsafe under SPMD) runs the unmasked segment sum first and falls into
    the NaN-masked form only when the result shows a NaN occurred, sharing
    one group-sizes histogram across clean columns.  With ``has_sizes`` the
    histogram arrives precomputed (factorization by-product) as an operand.
    """
    import jax
    import jax.numpy as jnp

    n_groups = num_segments - 1

    def finish(r):
        return _slice_pad(r, n_groups, p_out)

    def seg_adaptive(c, codes, sizes):
        import jax.lax as lax

        ns = num_segments
        if agg == "count":
            # no value aggregation needed: probe NaNs directly (a segment
            # scatter just for the probe would cost more than it saves)
            has_nan = jnp.any(jnp.isnan(c) & (codes < n_groups))
            s_raw = None
        else:
            s_raw = jax.ops.segment_sum(c, codes, num_segments=ns)
            has_nan = jnp.isnan(jnp.sum(s_raw[:n_groups]))

        def dirty():
            if agg == "count":
                vcnt = jax.ops.segment_sum(
                    (~jnp.isnan(c)).astype(jnp.int32), codes, num_segments=ns
                )
                return vcnt.astype(jnp.int64)
            x = jnp.where(jnp.isnan(c), 0, c)
            s = jax.ops.segment_sum(x, codes, num_segments=ns)
            if agg == "sum":
                return s
            vcnt = jax.ops.segment_sum(
                (~jnp.isnan(c)).astype(jnp.int32), codes, num_segments=ns
            )
            return s / vcnt  # mean

        def clean():
            if agg == "sum":
                return s_raw
            if agg == "count":
                return sizes
            # cast sizes to the SUM dtype: cond branches must type-match and
            # the masked path keeps float32 means float32
            return s_raw / sizes.astype(s_raw.dtype)

        return finish(lax.cond(has_nan, dirty, clean))

    def seg(c, codes):
        is_f = jnp.issubdtype(c.dtype, jnp.floating)
        ns = num_segments
        if agg in ("sum", "mean", "var", "std", "sem"):
            x = jnp.where(jnp.isnan(c), 0, c) if is_f else c
            s = jax.ops.segment_sum(x, codes, num_segments=ns)
            if agg == "sum":
                return s
            valid = (~jnp.isnan(c)).astype(jnp.int64) if is_f else jnp.ones(c.shape, jnp.int64)
            ncnt = jax.ops.segment_sum(valid, codes, num_segments=ns)
            # divide in the sum's dtype: float32 means stay float32 (pandas)
            mean = s / (ncnt.astype(s.dtype) if is_f else ncnt)
            if agg == "mean":
                return mean
            # two-pass centered variance: gathering the group mean back per row
            # avoids the catastrophic cancellation of E[x^2]-E[x]^2
            d = x.astype(jnp.float64) - jnp.take(mean, codes)
            d = jnp.where(valid.astype(bool), d, 0.0)
            s2 = jax.ops.segment_sum(d * d, codes, num_segments=ns)
            var = s2 / jnp.maximum(ncnt - ddof, 1)
            var = jnp.where(ncnt - ddof > 0, var, jnp.nan)
            if agg == "var":
                return var
            if agg == "std":
                return jnp.sqrt(var)
            return jnp.sqrt(var / ncnt)  # sem
        if agg == "count":
            valid = (~jnp.isnan(c)).astype(jnp.int64) if is_f else jnp.ones(c.shape, jnp.int64)
            return jax.ops.segment_sum(valid, codes, num_segments=ns)
        if agg == "prod":
            x = jnp.where(jnp.isnan(c), 1, c) if is_f else c
            return jax.ops.segment_prod(x, codes, num_segments=ns)
        if agg == "min":
            x = jnp.where(jnp.isnan(c), jnp.inf, c) if is_f else c
            r = jax.ops.segment_min(x, codes, num_segments=ns)
            return jnp.where(jnp.isposinf(r), jnp.nan, r) if is_f else r
        if agg == "max":
            x = jnp.where(jnp.isnan(c), -jnp.inf, c) if is_f else c
            r = jax.ops.segment_max(x, codes, num_segments=ns)
            return jnp.where(jnp.isneginf(r), jnp.nan, r) if is_f else r
        if agg == "any":
            x = jnp.where(jnp.isnan(c), False, c != 0) if is_f else (c != 0 if c.dtype != jnp.bool_ else c)
            return jax.ops.segment_max(x.astype(jnp.int32), codes, num_segments=ns).astype(bool)
        if agg == "all":
            x = jnp.where(jnp.isnan(c), True, c != 0) if is_f else (c != 0 if c.dtype != jnp.bool_ else c)
            return jax.ops.segment_min(x.astype(jnp.int32), codes, num_segments=ns).astype(bool)
        raise ValueError(agg)

    def fn(cols: Tuple, codes, sizes_in=None):
        sizes = None
        if adaptive and agg in ("sum", "mean", "count"):
            if has_sizes:
                sizes = sizes_in
            else:
                with jax.named_scope("group_sizes"):
                    sizes = jax.ops.segment_sum(
                        jnp.ones(codes.shape, jnp.int64), codes,
                        num_segments=num_segments,
                    )
        out = []
        for i, c in enumerate(cols):
            with jax.named_scope(f"{agg}_col{i}"):
                if sizes is not None and jnp.issubdtype(c.dtype, jnp.floating):
                    out.append(seg_adaptive(c, codes, sizes))
                elif sizes is not None and agg == "count":
                    out.append(finish(sizes))
                else:
                    out.append(finish(seg(c, codes)))
        return tuple(out)

    return named_jit(fn, "groupby_segment_agg")


@functools.lru_cache(maxsize=None)
def _jit_pad_to(p_out: int):
    import jax
    import jax.numpy as jnp

    def fn(r):
        if r.shape[0] < p_out:
            return jnp.concatenate([r, jnp.zeros(p_out - r.shape[0], r.dtype)])
        return r

    return named_jit(fn, "groupby_pad_to")


# The four forms of a segment reduction (chip readings in PERF.md), read from
# the aggregation, the group count, the platform and the shard count:
# - limb_dot: sum/mean/count of up to _MASKED_SCAN_MAX_GROUPS groups on a
#   one-shard TPU.  Values are cut into byte limbs, which bf16 holds exactly,
#   and a block's limbs are contracted with its one-hot on the MXU, inside a
#   Pallas kernel; integer sums are exact (wrapping as numpy's), float sums an
#   exact fixed point rounded once.  A float column whose bit span passes the
#   limbs, or that holds an infinity, takes the masked scan inside the same
#   program.
# - masked_scan: the one-hot spans every group at once and is reduced on the
#   VPU, O(n*G) 64-bit selects and adds, no scatter: min/max/prod/any/all up to
#   that limit, and sum/mean/count of a row-sharded operand.
# - sorted_tiles: sum/mean/count above the limit, up to _RANGE_LIMIT, on a
#   one-shard TPU (sort row chunks by code, one-hot each run of sorted rows
#   against the few consecutive codes it holds).
# - segment: XLA's scatter-based segment ops, which serialise on a TPU (146 ns
#   a row and 64-bit column at 1e8 rows), are left with var/std/sem, with
#   min/max/prod/any/all above the limit, with row-sharded operands above it,
#   and with the CPU, where scatters are fine.
_MASKED_SCAN_MAX_GROUPS = 1024
_SCAN_CHUNK = 65536
# limb_dot: rows cut into words at a time (a word temporary is 4 MB)
_LIMB_CHUNK = 1 << 20
# sorted tiles: rows sorted at a time, and consecutive codes the one-hot of a
# block of sorted rows spans (rows a block: _tile_rows)
_SORT_CHUNK = 1 << 22
_TILE_IDS = 512
# group counts up to which the factorisation's row counts are uploaded as the
# denominator of mean / count (past it the kernel counts)
_SIZES_OPERAND_MAX_GROUPS = 1 << 16
# test hook: "tpu" (what a TPU would choose, on any platform; Pallas kernels
# then run in interpret mode off the chip) | "masked_scan" (the same, but the
# scan where limb_dot would be chosen: the sharded and fallback path) |
# "segment" | None
_FORCE_KERNEL = None


def _on_tpu(arr) -> bool:
    return next(iter(arr.devices())).platform == "tpu"


def _tpu_forms(arr) -> bool:
    """Whether the scatter-free forms are chosen for ``arr``: its platform is a
    TPU (or the test hook says so)."""
    if _FORCE_KERNEL is not None:
        return _FORCE_KERNEL != "segment"
    return _on_tpu(arr)


@functools.lru_cache(maxsize=None)
def _jit_masked_scan_agg(agg: str, n_cols: int, num_segments: int, ddof: int, p_out: int, chunk: int):
    """Chunked masked-reduce aggregation: one lax.scan over row chunks, each
    step reducing a [chunk, G+1] one-hot mask on the VPU (no scatters)."""
    import jax
    import jax.numpy as jnp

    G = num_segments  # includes the overflow bucket
    n_groups = num_segments - 1

    def fn(cols: Tuple, codes):
        P = codes.shape[0]
        steps = -(-P // chunk)
        pad = steps * chunk - P
        cpad = jnp.concatenate(
            [codes, jnp.full(pad, n_groups, codes.dtype)]
        ).reshape(steps, chunk)
        xpads = tuple(
            jnp.concatenate([c, jnp.zeros(pad, c.dtype)]).reshape(steps, chunk)
            for c in cols
        )
        group_ids = jnp.arange(G)

        def body(carry, inp):
            cc = inp[0]
            oh = cc[:, None] == group_ids[None, :]  # [chunk, G] bool
            new_carry = []
            ci = 0
            for i in range(n_cols):
                xc = inp[1 + i]
                is_f = jnp.issubdtype(xc.dtype, jnp.floating)
                nanm = jnp.isnan(xc) if is_f else None
                if agg in ("sum", "mean"):
                    xz = jnp.where(nanm, 0, xc) if is_f else xc
                    s = carry[ci] + jnp.sum(
                        jnp.where(oh, xz[:, None], 0), axis=0
                    )
                    new_carry.append(s)
                    ci += 1
                    if agg != "sum":
                        v = (~nanm if is_f else jnp.ones(xc.shape, bool))
                        cnt = carry[ci] + jnp.sum(oh & v[:, None], axis=0)
                        new_carry.append(cnt)
                        ci += 1
                elif agg == "count":
                    v = (~nanm if is_f else jnp.ones(xc.shape, bool))
                    cnt = carry[ci] + jnp.sum(oh & v[:, None], axis=0)
                    new_carry.append(cnt)
                    ci += 1
                elif agg == "prod":
                    xz = jnp.where(nanm, 1, xc) if is_f else xc
                    pr = carry[ci] * jnp.prod(
                        jnp.where(oh, xz[:, None], 1), axis=0
                    )
                    new_carry.append(pr)
                    ci += 1
                elif agg == "min":
                    xz = jnp.where(nanm, jnp.inf, xc) if is_f else xc
                    neutral = jnp.inf if is_f else _INT_MAXES[str(xc.dtype)]
                    m = jnp.minimum(
                        carry[ci],
                        jnp.min(jnp.where(oh, xz[:, None], neutral), axis=0),
                    )
                    new_carry.append(m)
                    ci += 1
                elif agg == "max":
                    xz = jnp.where(nanm, -jnp.inf, xc) if is_f else xc
                    neutral = -jnp.inf if is_f else _INT_MINS[str(xc.dtype)]
                    m = jnp.maximum(
                        carry[ci],
                        jnp.max(jnp.where(oh, xz[:, None], neutral), axis=0),
                    )
                    new_carry.append(m)
                    ci += 1
                elif agg in ("any", "all"):
                    if is_f:
                        t = jnp.where(nanm, agg == "all", xc != 0)
                    else:
                        t = xc != 0 if xc.dtype != jnp.bool_ else xc
                    if agg == "any":
                        r = carry[ci] | jnp.any(oh & t[:, None], axis=0)
                    else:
                        r = carry[ci] & jnp.all((~oh) | t[:, None], axis=0)
                    new_carry.append(r)
                    ci += 1
                else:
                    raise ValueError(agg)
            return tuple(new_carry), None

        # build initial carry matching the body's layout
        init = []
        for c in cols:
            is_f = jnp.issubdtype(c.dtype, jnp.floating)
            if agg in ("sum", "mean"):
                init.append(jnp.zeros(G, c.dtype))
                if agg != "sum":
                    init.append(jnp.zeros(G, jnp.int64))
            elif agg == "count":
                init.append(jnp.zeros(G, jnp.int64))
            elif agg == "prod":
                init.append(jnp.ones(G, c.dtype))
            elif agg == "min":
                init.append(
                    jnp.full(G, jnp.inf if is_f else _INT_MAXES[str(c.dtype)], c.dtype)
                )
            elif agg == "max":
                init.append(
                    jnp.full(G, -jnp.inf if is_f else _INT_MINS[str(c.dtype)], c.dtype)
                )
            elif agg == "any":
                init.append(jnp.zeros(G, bool))
            elif agg == "all":
                init.append(jnp.ones(G, bool))
        with jax.named_scope("masked_scan"):
            carry, _ = jax.lax.scan(body, tuple(init), (cpad, *xpads))

        # finalize per column
        def finish(r):
            return _slice_pad(r, n_groups, p_out)

        out = []
        ci = 0
        for c in cols:
            is_f = jnp.issubdtype(c.dtype, jnp.floating)
            if agg == "sum":
                out.append(finish(carry[ci])); ci += 1
            elif agg == "mean":
                s = carry[ci]; ci += 1
                cnt = carry[ci]; ci += 1
                out.append(finish(s / cnt))
            elif agg == "count":
                out.append(finish(carry[ci])); ci += 1
            elif agg == "min":
                r = carry[ci]; ci += 1
                out.append(finish(jnp.where(jnp.isposinf(r), jnp.nan, r) if is_f else r))
            elif agg == "max":
                r = carry[ci]; ci += 1
                out.append(finish(jnp.where(jnp.isneginf(r), jnp.nan, r) if is_f else r))
            else:
                out.append(finish(carry[ci])); ci += 1
        return tuple(out)

    return named_jit(fn, "groupby_masked_scan_agg")


@functools.lru_cache(maxsize=None)
def _jit_masked_scan_smc(
    agg: str,
    n_cols: int,
    num_segments: int,
    p_out: int,
    chunk: int,
    adaptive: bool,
    has_sizes: bool,
):
    return named_jit(
        _masked_scan_smc(agg, n_cols, num_segments, p_out, chunk, adaptive, has_sizes),
        "groupby_masked_scan_smc",
    )


def _masked_scan_smc(
    agg: str,
    n_cols: int,
    num_segments: int,
    p_out: int,
    chunk: int,
    adaptive: bool,
    has_sizes: bool,
):
    """sum/mean/count masked-scan with a SHARED group-size histogram (the
    function ``_jit_masked_scan_smc`` jits; the limb-dot form traces it as the
    branch of a column it cannot sum exactly).

    The main scan accumulates every column's nan-zeroed sum plus ONE sizes
    histogram (skipped when the factorization by-product arrives as an
    operand).  Per-column valid counts then come for free on clean data:
    int columns always equal the shared sizes; float columns probe NaNs with
    one cheap pass and (``adaptive``, single-shard meshes only — lax.cond
    over sharded operands is unsafe under SPMD) fall into a dedicated
    count-scan only when a NaN actually occurred.  Cuts mean from 2 O(n*G)
    passes per column to 1, and count to a single shared pass.
    """
    import jax
    import jax.numpy as jnp

    G = num_segments
    n_groups = num_segments - 1

    def finish(r):
        return _slice_pad(r, n_groups, p_out)

    def fn(cols: Tuple, codes, sizes_in=None):
        P = codes.shape[0]
        steps = -(-P // chunk)
        pad = steps * chunk - P
        cpad = jnp.concatenate(
            [codes, jnp.full(pad, n_groups, codes.dtype)]
        ).reshape(steps, chunk)
        xpads = tuple(
            jnp.concatenate([c, jnp.zeros(pad, c.dtype)]).reshape(steps, chunk)
            for c in cols
        )
        gid = jnp.arange(G)
        is_float = [bool(jnp.issubdtype(c.dtype, jnp.floating)) for c in cols]

        need_sum = agg in ("sum", "mean")
        # shared histogram wanted whenever some column's count can reuse it
        need_sizes = agg in ("mean", "count") and (
            has_sizes or adaptive or not all(is_float)
        )
        # per-column inline count accumulators (non-adaptive float columns)
        inline_count = [
            agg in ("mean", "count") and f and not adaptive for f in is_float
        ]

        def body(carry, inp):
            cc = inp[0]
            oh = cc[:, None] == gid[None, :]
            new_carry = []
            ci = 0
            for i in range(n_cols):
                xc = inp[1 + i]
                nanm = jnp.isnan(xc) if is_float[i] else None
                if need_sum:
                    xz = jnp.where(nanm, 0, xc) if is_float[i] else xc
                    new_carry.append(
                        carry[ci] + jnp.sum(jnp.where(oh, xz[:, None], 0), axis=0)
                    )
                    ci += 1
                if inline_count[i]:
                    new_carry.append(
                        carry[ci]
                        + jnp.sum(
                            oh & (~nanm)[:, None], axis=0, dtype=jnp.int32
                        )
                    )
                    ci += 1
            if need_sizes and not has_sizes:
                new_carry.append(
                    carry[ci] + jnp.sum(oh, axis=0, dtype=jnp.int32)
                )
                ci += 1
            return tuple(new_carry), None

        init = []
        for i, c in enumerate(cols):
            if need_sum:
                init.append(jnp.zeros(G, _sum_dtype(c.dtype)))
            if inline_count[i]:
                init.append(jnp.zeros(G, jnp.int64))
        if need_sizes and not has_sizes:
            init.append(jnp.zeros(G, jnp.int64))
        with jax.named_scope("masked_scan"):
            carry, _ = jax.lax.scan(body, tuple(init), (cpad, *xpads))

        ci = 0
        sums, counts = [], []
        for i in range(n_cols):
            if need_sum:
                sums.append(carry[ci]); ci += 1
            else:
                sums.append(None)
            if inline_count[i]:
                counts.append(carry[ci]); ci += 1
            else:
                counts.append(None)
        if need_sizes:
            sizes = sizes_in if has_sizes else carry[ci]
        else:
            sizes = None

        def count_scan(xpad_c):
            def cbody(carry, inp):
                cc, xi = inp
                oh = cc[:, None] == gid[None, :]
                return (
                    carry
                    + jnp.sum(
                        oh & (~jnp.isnan(xi))[:, None], axis=0, dtype=jnp.int32
                    ),
                    None,
                )

            with jax.named_scope("count_scan"):
                out, _ = jax.lax.scan(
                    cbody, jnp.zeros(G, jnp.int64), (cpad, xpad_c)
                )
            return out

        out = []
        for i, c in enumerate(cols):
            if agg == "sum":
                out.append(finish(sums[i]))
                continue
            # resolve the valid count for mean/count
            if not is_float[i]:
                cnt = sizes
            elif counts[i] is not None:
                cnt = counts[i]
            else:
                with jax.named_scope("nan_probe"):
                    has_nan = jnp.any(jnp.isnan(c))
                cnt = jax.lax.cond(
                    has_nan,
                    lambda i=i: count_scan(xpads[i]),
                    lambda: sizes.astype(jnp.int64),
                )
            if agg == "count":
                out.append(finish(cnt.astype(jnp.int64)))
            else:  # mean — divide in the sum's dtype so f32 means stay f32
                s = sums[i]
                out.append(finish(s / cnt.astype(s.dtype)))
        return tuple(out)

    return fn


def _sum_dtype(dtype) -> np.dtype:
    """The dtype of a column's per-group sums: pandas' (and numpy's) 64-bit
    integer of the column's signedness, the column's own float width."""
    dtype = np.dtype(dtype)
    if dtype.kind in "ib":
        return np.dtype(np.int64)
    return np.dtype(np.uint64) if dtype.kind == "u" else dtype


def _limb_dot_takes(dtype) -> bool:
    """Whether a column of ``dtype`` can be cut into limbs: integers and
    bools of any width, float32 and float64."""
    dtype = np.dtype(dtype)
    return dtype.kind in "iub" or dtype in (np.float32, np.float64)


def _float32_streams(x):
    """A float chunk as float32 streams whose sum is the value, NaN read as
    zero: one for float32, three for float64 (24 bits each: an IEEE double's
    53, or the two halves a TPU keeps of it and a zero).  Returns the streams
    and, a row, whether they add up to it exactly (not an infinity, nor past
    float32's range at either end)."""
    import jax.numpy as jnp

    x0 = jnp.where(jnp.isnan(x), 0, x)
    if x.dtype == jnp.float32:
        return [x0], jnp.isfinite(x0)
    # each difference is exact (its operands lie within a factor of two, or
    # the stream is zero); only the last rounding can lose bits
    high = x0.astype(jnp.float32)
    rest = x0 - high.astype(x.dtype)
    mid = rest.astype(jnp.float32)
    rest = rest - mid.astype(x.dtype)
    low = rest.astype(jnp.float32)
    return [high, mid, low], jnp.isfinite(high) & (low.astype(x.dtype) == rest)


def _fixed_point_to_float(limbs, unit, dtype):
    """``sum_k limbs[k] * 256**k * 2**unit`` a group, rounded once (to nearest,
    ties to even) to ``dtype``.  ``limbs`` is int64 ``[L, G]``, signed and not
    carried; ``unit`` an int32 scalar.  (Loops over the limbs are ``lax.scan``s:
    a few dozen steps on ``[G]`` vectors, and a tenth of the operations to
    trace in every new process.)"""
    import jax.lax as lax
    import jax.numpy as jnp

    groups = limbs.shape[1:]
    # five limbs of room for the carry past the last: it stays under 2**40
    limbs = jnp.concatenate([limbs, jnp.zeros((5,) + groups, jnp.int64)])

    def carried(limbs):
        """Digits 0..255 of the same number, and the (signed) carry left."""
        def step(carry, limb):
            v = limb + carry
            return v >> 8, v & 255

        carry, digits = lax.scan(step, jnp.zeros(groups, jnp.int64), limbs)
        return digits, carry

    negative = carried(limbs)[1] < 0
    digits, _ = carried(jnp.where(negative, -limbs, limbs))

    def leading(state, d):
        """From the top digit down: the leading 57 to 64 bits, whether
        anything set was dropped below them, and how many digits were."""
        lead, dropped, sticky = state
        take = lead < jnp.uint64(1 << 56)
        return (
            jnp.where(take, (lead << jnp.uint64(8)) | d.astype(jnp.uint64), lead),
            dropped + (~take).astype(jnp.int32),
            sticky | (~take & (d != 0)),
        ), None

    (lead, dropped, sticky), _ = lax.scan(
        leading,
        (jnp.zeros(groups, jnp.uint64), jnp.zeros(groups, jnp.int32), jnp.zeros(groups, bool)),
        digits, reverse=True,
    )
    keep = 53 if np.dtype(dtype) == np.float64 else 24
    shift = jnp.maximum(64 - lax.clz(lead).astype(jnp.int32) - keep, 0)
    wide = shift.astype(jnp.uint64)
    mant = lead >> wide
    rest = lead & ((jnp.uint64(1) << wide) - jnp.uint64(1))
    half = (jnp.uint64(1) << wide) >> jnp.uint64(1)
    up = (shift > 0) & (
        (rest > half) | ((rest == half) & (sticky | ((mant & jnp.uint64(1)) == 1)))
    )
    value = (mant + up.astype(jnp.uint64)).astype(jnp.int64).astype(dtype)
    # times 2**exp, a power float32 holds at a time (exact: the partial
    # products move towards the result from the side of the mantissa)
    exp = shift + 8 * dropped + unit
    for _ in range(4):
        part = jnp.clip(exp, -126, 127)
        value = value * lax.bitcast_convert_type((part + 127) << 23, jnp.float32).astype(dtype)
        exp = exp - part
    return jnp.where(negative, -value, value)


# what a column with no set bit reads for its lowest
_NO_BIT = 1 << 20


def _float_bit_span(c, chunk: int):
    """Whether the column's values can be summed as fixed point, and the
    kernel's scalars if so: the exponent of the unit (the lowest bit set in
    any value) and, a (stream, piece), whether a bit may fall there."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    from modin_tpu.ops.pallas import groupby_kernels as kernels

    P = c.shape[0]
    take = min(int(chunk), P)
    n_streams = 1 if c.dtype == jnp.float32 else 3
    top_word = np.iinfo(np.int32).max

    def exponent(word):
        """``e`` where a float32 of magnitude bits ``word`` is ``m * 2**e``,
        its mantissa ``m`` an integer under 2**24."""
        return jnp.maximum(word >> 23, 1) - 150

    def step(i, state):
        exact, unit, least, most = state
        # (steps overlap at the end: every reduction here may read a row twice)
        x = lax.dynamic_slice(c, (jnp.minimum(i * take, P - take),), (take,))
        streams, adds_up = _float32_streams(x)
        # magnitudes as integers, which order as the floats do
        words = [lax.bitcast_convert_type(v, jnp.int32) & top_word for v in streams]
        # a value's lowest set bit is in its last stream that is not zero
        # (each stream lies under the last place of the one before it)
        last = words[-1]
        for word in reversed(words[:-1]):
            last = jnp.where(last != 0, last, word)
        frac = last & 0x7FFFFF
        mant = jnp.where(last >> 23 == 0, frac, frac | 0x800000)
        # (the place of the mantissa's lowest bit, through its float's exponent)
        place = lax.bitcast_convert_type((mant & -mant).astype(jnp.float32), jnp.int32)
        place = (place >> 23) - 127 + exponent(last)
        return (
            exact & jnp.all(adds_up),
            jnp.minimum(unit, jnp.min(jnp.where(last != 0, place, _NO_BIT))),
            jnp.minimum(least, jnp.stack([jnp.min(jnp.where(w != 0, w, top_word)) for w in words])),
            jnp.maximum(most, jnp.stack([jnp.max(w) for w in words])),
        )

    with jax.named_scope("span_probe"):
        exact, unit, least, most = lax.fori_loop(
            0, -(-P // take), step,
            (
                jnp.bool_(True),
                jnp.int32(_NO_BIT),
                jnp.full(n_streams, top_word, jnp.int32),
                jnp.zeros(n_streams, jnp.int32),
            ),
        )
    unit = jnp.where(unit == _NO_BIT, 0, unit)
    some = most != 0
    # a stream's bits lie from its least value's last place to its largest's first
    low, high = exponent(least), exponent(most) + 23
    piece_bits = 8 * kernels.PIECE_ROWS
    fits = jnp.all(~some | (high - unit < piece_bits * kernels.FLOAT_PIECES))
    held = jnp.stack(
        [some & (low < unit + piece_bits), some & (high >= unit + piece_bits)], axis=1
    ).reshape(-1)
    meta = jnp.zeros(kernels.META_SLOTS, jnp.int32)
    meta = meta.at[0].set(unit).at[1:1 + held.shape[0]].set(held.astype(jnp.int32))
    return exact & fits, meta.reshape(1, -1)


@functools.lru_cache(maxsize=None)
def _jit_limb_dot(
    agg: str,
    num_segments: int,
    p_out: int,
    has_sizes: bool,
    chunk: int,
    interpret: bool,
):
    """sum/mean/count of a few groups as an exact contraction on the MXU.

    A column is walked ``chunk`` rows at a time; a chunk's values are cut into
    32-bit words in XLA (a 64-bit column's two halves, a float column's
    float32 streams) and the Pallas kernel ``limb_dot_sums`` cuts the words
    into byte limbs, contracts them with the chunk's one-hot and returns the
    ``[limb, group]`` integer sums, which are added up in int64.  Integers:
    ``sum_k S[k] << 8k`` wraps as numpy's sums do, so the result is the scan's
    bit for bit.  Floats: a first walk reads the column's bit span; where it
    fits the limbs (256 bits: magnitudes within 1e60 of each other) and holds
    no infinity, every value is an exact multiple of the span's lowest bit,
    the limb sums are an exact fixed-point number and the group's sum is that
    number rounded once; NaNs are zeroed and counted by a row of the same
    contraction.  Any other float column takes the masked scan, under a
    ``lax.cond`` (one shard only, as the scan's own NaN branch).
    """
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    from modin_tpu.ops.pallas import groupby_kernels as kernels

    n_groups = num_segments - 1
    block = kernels.LIMB_BLOCK
    lanes = kernels.LIMB_LANES
    # a chunk's limb sums come back as int32: 255 a row at most
    assert chunk * 255 < 1 << 31

    def finish(r):
        return _slice_pad(r, n_groups, p_out)

    def walk(codes, column, words_of, layout, meta=None):
        """int64 ``[limb rows, groups + 1]`` sums over the whole column."""
        P = codes.shape[0]
        take = min(int(chunk), P)
        padded = -(-take // block) * block
        steps = -(-P // take)

        def step(i, acc):
            start = jnp.minimum(i * take, P - take)
            cc = lax.dynamic_slice(codes, (start,), (take,)).astype(jnp.int32)
            if P % take:
                # the last step reaches back over rows the one before has summed
                done = (i * take - start).astype(jnp.int32)
                cc = jnp.where(jnp.arange(take, dtype=jnp.int32) >= done, cc, n_groups)
            words = words_of(lax.dynamic_slice(column, (start,), (take,)))
            if padded > take:
                cc = jnp.concatenate([cc, jnp.full(padded - take, n_groups, jnp.int32)])
                words = [jnp.concatenate([w, jnp.zeros(padded - take, jnp.int32)]) for w in words]
            shape = (padded // lanes, lanes)
            with jax.named_scope("limb_dot"):
                sums = kernels.limb_dot_sums(
                    cc.reshape(shape), [w.reshape(shape) for w in words],
                    meta, layout, num_segments, interpret,
                )
            return acc + sums[:, :num_segments].astype(jnp.int64)

        rows = kernels.limb_rows(layout)
        return lax.fori_loop(0, steps, step, jnp.zeros((rows, num_segments), jnp.int64))

    def as_words(x):
        return lax.bitcast_convert_type(x, jnp.int32)

    def int_words(x):
        if x.dtype.itemsize == 8:
            return [as_words(x.astype(jnp.uint32)), as_words((x >> 32).astype(jnp.uint32))]
        if jnp.issubdtype(x.dtype, jnp.signedinteger):
            return [x.astype(jnp.int32)]
        return [as_words(x.astype(jnp.uint32))]

    def int_column(c, codes, sizes):
        if agg == "count" and sizes is not None:
            return sizes
        wide = c.dtype.itemsize == 8
        signed = jnp.issubdtype(c.dtype, jnp.signedinteger)
        S = walk(codes, c, int_words, ("int", 2 if wide else 1))
        cnt = sizes if sizes is not None else S[kernels.INT_ROW_ONES]
        if agg == "count":
            return cnt
        total = jnp.zeros(num_segments, jnp.uint64)
        for k in range(8 if wide else 4):
            total = total + (S[k].astype(jnp.uint64) << jnp.uint64(8 * k))
        if signed and not wide:
            total = total - (S[kernels.INT_ROW_TOP].astype(jnp.uint64) << jnp.uint64(32))
        total = total.astype(_sum_dtype(c.dtype))
        return total if agg == "sum" else total / cnt.astype(total.dtype)

    def float_column(c, codes, sizes):
        def words_of(x):
            streams, _ = _float32_streams(x)
            # the first stream keeps the NaNs: the kernel counts the rows that hold none
            streams[0] = jnp.where(jnp.isnan(x), jnp.float32(np.nan), streams[0])
            return [as_words(v) for v in streams]

        if agg == "count":
            nans_kept = lambda x: [as_words(x.astype(jnp.float32))]  # noqa: E731
            return finish(walk(codes, c, nans_kept, ("valid", 1))[0])
        layout = ("float", 1 if c.dtype == jnp.float32 else 3)
        piece, n_low = kernels.PIECE_ROWS, kernels.low_rows(layout)

        def fixed_point(meta):
            S = walk(codes, c, words_of, layout, meta)
            # a stream's lower limbs, then (past the row counts) its upper ones
            limbs = sum(
                jnp.concatenate([S[piece * i:piece * (i + 1)], S[n_low + piece * i:][:piece]])
                for i in range(layout[1])
            )
            total = _fixed_point_to_float(limbs, meta[0, 0], c.dtype)
            if agg == "sum":
                return finish(total)
            return finish(total / S[n_low - piece].astype(total.dtype))

        def scan(meta):
            operands = ((c,), codes) + ((sizes,) if sizes is not None else ())
            return scan_fn(*operands)[0]

        fits, meta = _float_bit_span(c, chunk)
        return lax.cond(fits, fixed_point, scan, meta)

    scan_fn = _masked_scan_smc(agg, 1, num_segments, p_out, _SCAN_CHUNK, True, has_sizes)

    def fn(cols: Tuple, codes, sizes_in=None):
        out = []
        for i, c in enumerate(cols):
            with jax.named_scope(f"{agg}_col{i}"):
                if jnp.issubdtype(c.dtype, jnp.floating):
                    out.append(float_column(c, codes, sizes_in))
                else:
                    out.append(finish(int_column(c, codes, sizes_in)))
        return tuple(out)

    return named_jit(fn, "groupby_limb_dot")


def _tile_rows(chunk: int, num_groups: int) -> int:
    """Sorted rows a block: as many as ``_TILE_IDS`` consecutive codes hold in
    a chunk of an evenly spread key (``chunk / num_groups`` rows a code),
    rounded down to a power of two between 256 and 4096, so that a block's
    first tile spans it or nearly (a second tile for the last few codes costs
    less than twice the blocks would).  A key that is not spread evenly needs
    fewer tiles a row where it is dense and further tiles where it is sparse."""
    rows = max(_TILE_IDS * chunk // max(num_groups, 1), 1)
    return min(max(1 << rows.bit_length() - 1, 256), 4096)


@functools.lru_cache(maxsize=None)
def _jit_sorted_tiles(
    agg: str,
    n_cols: int,
    num_segments: int,
    p_out: int,
    has_sizes: bool,
    chunk: int,
):
    """sum/mean/count (and ``size``: the histogram, no value column) for many
    groups, with neither scatter nor per-row gather.

    Rows are taken ``chunk`` at a time and sorted by code, the value columns
    travelling with their key (one variadic ``lax.sort``).  Sorted rows are cut
    into blocks of ``_tile_rows``; a block's codes ascend from its first, so a
    one-hot against ``first + arange(_TILE_IDS)`` reduces the block into
    ``_TILE_IDS`` partial sums (the masked scan's ``where(oh, x, 0).sum``), for
    every block of the chunk at once.  The partials are then added into the
    ``[G]`` accumulators at offset ``first``, one contiguous
    ``dynamic_update_slice`` a block.  A block whose codes reach past its first
    tile (a sparse stretch of the key) takes further tiles afterwards, each
    starting at its next code not yet summed, so a chunk costs at most
    ``rows / _tile_rows + G / _TILE_IDS`` tiles whatever the key's shape.

    Sums are exact for integers and accumulated in the column's own float
    width, NaN skipped, as in the other forms; only the order of a group's
    float additions differs.  Pad and dropped rows carry the overflow code
    (``factorize_keys``) and land in its bucket, which is sliced off.
    """
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    n_groups = num_segments - 1  # also the code of pad and dropped rows
    T = _TILE_IDS
    acc_len = num_segments + T  # a tile written at offset n_groups still fits
    need_sum = agg in ("sum", "mean")
    need_cnt = agg in ("mean", "count")

    def fn(cols: Tuple, codes, sizes_in=None):
        # ``codes``: the array, or (key, kmin, n) of a dense range (RangeCodes)
        key, kmin, n = codes if isinstance(codes, tuple) else (None, None, None)
        P = (codes if key is None else key).shape[0]
        take = min(int(chunk), P)  # rows sliced a step
        steps = -(-P // take)
        W = _tile_rows(take, n_groups)
        cp = -(-take // W) * W  # rows sorted a step
        B = cp // W
        is_float = [bool(jnp.issubdtype(c.dtype, jnp.floating)) for c in cols]
        # which one-hot sums a tile makes: a sum a column, a valid count a
        # float column (NaN skipped), and one shared row count for the integer
        # columns and ``size`` unless the factorisation brought it
        col_cnt = [need_cnt and f for f in is_float]
        shared_cnt = agg == "size" or (
            need_cnt and not has_sizes and not all(is_float)
        )
        # the columns that travel with the codes (a count reads only floats)
        moved = [i for i in range(n_cols) if need_sum or col_cnt[i]]
        tid = jnp.arange(T, dtype=jnp.int32)

        def tile(s, xs, first):
            """Partial sums of blocks ``s`` ([b, W] sorted codes, ``xs`` their
            values) against the codes ``first[:, None] + arange(T)``."""
            oh = (s - first[:, None])[:, :, None] == tid[None, None, :]
            out = []
            for i, x in zip(moved, xs):
                nanm = jnp.isnan(x) if is_float[i] else None
                if need_sum:
                    xz = jnp.where(nanm, 0, x) if is_float[i] else x
                    out.append(jnp.sum(jnp.where(oh, xz[:, :, None], 0), axis=1))
                if col_cnt[i]:
                    out.append(
                        jnp.sum(oh & ~nanm[:, :, None], axis=1, dtype=jnp.int32)
                    )
            if shared_cnt:
                out.append(jnp.sum(oh, axis=1, dtype=jnp.int32))
            return out

        def add_at(accs, offset, parts):
            return [
                lax.dynamic_update_slice(
                    a, lax.dynamic_slice(a, (offset,), (T,)) + p.astype(a.dtype), (offset,)
                )
                for a, p in zip(accs, parts)
            ]

        def step(i, accs):
            start = jnp.minimum(i * take, P - take)
            pos = start + jnp.arange(take)
            # the last step reaches back over rows the one before has summed
            live = pos >= i * take
            with jax.named_scope("chunk_sort"):
                if key is None:
                    cc = lax.dynamic_slice(codes, (start,), (take,))
                else:
                    live &= pos < n
                    kc = lax.dynamic_slice(key, (start,), (take,)).astype(jnp.int64)
                    cc = jnp.clip(kc - kmin, 0, n_groups)
                cc = jnp.where(live, cc.astype(jnp.int32), n_groups)
                xs = [lax.dynamic_slice(cols[i], (start,), (take,)) for i in moved]
                if cp > take:
                    cc = jnp.concatenate([cc, jnp.full(cp - take, n_groups, jnp.int32)])
                    xs = [jnp.concatenate([x, jnp.zeros(cp - take, x.dtype)]) for x in xs]
                s, *xs = lax.sort((cc, *xs), num_keys=1, is_stable=False)
                s = s.reshape(B, W)
                xs = [x.reshape(B, W) for x in xs]
            first, last = s[:, 0], s[:, -1]
            with jax.named_scope("tile_reduce"):
                parts = tile(s, xs, first)
            with jax.named_scope("tile_place"):
                accs = lax.fori_loop(
                    0, B,
                    lambda b, accs: add_at(accs, first[b], [p[b] for p in parts]),
                    accs, unroll=4,
                )

            # the blocks whose codes reach past their first tile, in turn: each
            # takes tiles from its next code not yet summed until its last
            spills = last - first >= T
            n_spills = jnp.sum(spills, dtype=jnp.int32)
            turn = jnp.argsort(~spills, stable=True).astype(jnp.int32)

            def further(state):
                k, done, accs = state
                b = turn[k]
                sb = lax.dynamic_slice(s, (b, jnp.int32(0)), (1, W))
                xb = [lax.dynamic_slice(x, (b, jnp.int32(0)), (1, W)) for x in xs]
                nxt = jnp.min(jnp.where(sb >= done, sb, n_groups))
                accs = add_at(accs, nxt, [p[0] for p in tile(sb, xb, nxt[None])])
                over = nxt + T > last[b]
                after = turn[jnp.minimum(k + 1, B - 1)]
                return (
                    jnp.where(over, k + 1, k),
                    jnp.where(over, first[after] + T, nxt + T),
                    accs,
                )

            with jax.named_scope("tile_spill"):
                return lax.while_loop(
                    lambda state: state[0] < n_spills,
                    further,
                    (jnp.int32(0), first[turn[0]] + T, accs),
                )[2]

        init = []
        for i in moved:
            if need_sum:
                init.append(jnp.zeros(acc_len, cols[i].dtype))
            if col_cnt[i]:
                init.append(jnp.zeros(acc_len, jnp.int64))
        if shared_cnt:
            init.append(jnp.zeros(acc_len, jnp.int64))
        accs = lax.fori_loop(0, steps, step, init) if init else []
        accs = [a[:num_segments] for a in accs]

        if agg == "size":
            return _slice_pad(accs[0], n_groups, p_out)
        sizes = sizes_in if has_sizes else (accs[-1] if shared_cnt else None)
        out = []
        k = 0
        for i in range(n_cols):
            total = cnt = None
            if need_sum:
                total = accs[k]; k += 1
            if col_cnt[i]:
                cnt = accs[k]; k += 1
            elif need_cnt:
                cnt = sizes
            if agg == "sum":
                r = total
            elif agg == "count":
                r = cnt.astype(jnp.int64)
            else:  # mean: divide in the sum's dtype so f32 means stay f32
                r = total / cnt.astype(total.dtype)
            out.append(_slice_pad(r, n_groups, p_out))
        return tuple(out)

    return named_jit(fn, "groupby_sorted_tiles_" + agg)


# read from inside jitted bodies (masked-scan min/max neutrals): immutable so
# tracing can't bake in contents that a later mutation would silently miss
_INT_KINDS = ("int8", "int16", "int32", "int64", "uint8", "uint16", "uint32", "uint64")
_INT_MAXES = MappingProxyType(
    {**{k: np.iinfo(k).max for k in _INT_KINDS}, "bool": True}
)
_INT_MINS = MappingProxyType(
    {**{k: np.iinfo(k).min for k in _INT_KINDS}, "bool": False}
)


@functools.lru_cache(maxsize=None)
def _jit_first_position(num_segments: int):
    import jax
    import jax.numpy as jnp

    def fn(codes):
        positions = jnp.arange(codes.shape[0], dtype=jnp.int64)
        return jax.ops.segment_min(positions, codes, num_segments=num_segments)

    return named_jit(fn, "groupby_first_position")


def groupby_first_position(codes: Any, num_groups: int) -> Any:
    """First row position of each group (pandas' tie order for value_counts).

    Pad rows carry the overflow code, so they land in the sliced-off bucket.
    """
    return _jit_first_position(num_groups + 1)(codes_array(codes))[:num_groups]


# Order of first appearance (``sort=False``): the groups by the least row
# position of each, found with neither a scatter nor an O(n * G) scan, in one
# of two forms read from the group count, the platform and the shard count:
# - first_seen_prefix: a prefix of the codes is sorted by (code, position), the
#   head of each run is a group's first row within the prefix, and a second
#   sort of those heads by position lists the groups in the order they appear.
#   The prefix is sized from the group count so that an evenly spread key
#   shows every group in it (G * (ln G + 16) rows miss a group with
#   probability e^-16 a group); the count of heads found says whether it did,
#   and the prefix grows eightfold until it does (a key whose groups come in
#   blocks ends at the whole column).  Two sorts of the prefix: nothing beside
#   a request while the prefix is a few hundred thousand rows (up to some 1e5
#   groups), 0.41 s of a 1.5 s request at 1e6 groups (a prefix of 2**25 rows).
# - first_seen_tiles: where that prefix would pass a chunk of the sorted tiles,
#   on a one-shard TPU.  The tiles' own walk (``_jit_sorted_tiles``: a chunk of
#   rows sorted by code with the row positions travelling, a block's one-hot
#   against its consecutive codes) takes a *min* of the positions where the
#   sums take a sum, placed into the ``[G]`` accumulator a contiguous slice at
#   a time; the walk stops with the first chunk after which every group has a
#   position (no host sync), and one sort of the G positions gives the order.
_FIRST_SEEN_MIN_ROWS = 1 << 16
_FIRST_SEEN_GROWTH = 8


def _first_seen_rows(num_groups: int, physical: int) -> int:
    """Rows of the first prefix: a power of two, at most the column."""
    want = num_groups * (np.log(max(num_groups, 2)) + 16.0)
    rows = max(_FIRST_SEEN_MIN_ROWS, 1 << int(np.ceil(np.log2(want))))
    return min(rows, physical)


def _first_seen_form(codes: Any, num_groups: int) -> str:
    from modin_tpu.parallel.mesh import num_row_shards

    if (
        _first_seen_rows(num_groups, int(codes.shape[0])) > _SORT_CHUNK
        and _tpu_forms(codes)
        and num_row_shards() == 1
        and num_groups <= _RANGE_LIMIT
    ):
        return "first_seen_tiles"
    return "first_seen_prefix"


@functools.lru_cache(maxsize=None)
def _jit_first_seen(num_groups: int, take: int, p_out: int):
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    def fn(codes):
        # ``codes``: the array, or (key, kmin, n) of a dense range (RangeCodes)
        if isinstance(codes, tuple):
            key, kmin, n = codes
            cc = jnp.clip(lax.slice(key, (0,), (take,)).astype(jnp.int64) - kmin, 0, num_groups)
            cc = jnp.where(jnp.arange(take) < n, cc, num_groups)
        else:
            cc = lax.slice(codes, (0,), (take,))
        cc = cc.astype(jnp.int32)
        s, pos = lax.sort((cc, jnp.arange(take, dtype=jnp.int32)), num_keys=2)
        head = jnp.concatenate([jnp.ones(1, bool), s[1:] != s[:-1]]) & (s < num_groups)
        found = jnp.sum(head, dtype=jnp.int32)
        at = jnp.where(head, pos, np.iinfo(np.int32).max)
        _, order = lax.sort((at, s), num_keys=1)
        return _slice_pad(order, num_groups, p_out), found

    return named_jit(fn, "groupby_first_seen")


@functools.lru_cache(maxsize=None)
def _jit_first_seen_tiles(num_groups: int, p_out: int, chunk: int):
    """The least row position of each group by the sorted tiles' walk, and the
    groups sorted by it.  As ``_jit_sorted_tiles`` (its comments hold here): a
    chunk's rows are sorted by code, cut into blocks of ``_tile_rows``, and a
    block's one-hot against ``first + arange(_TILE_IDS)`` reduces it, here with
    a min over the row positions; blocks that reach past their first tile take
    further tiles.  The walk is a ``while_loop`` that ends once every group has
    been seen, so an evenly spread key costs ``G ln G`` rows of it, and a key
    in blocks the whole column."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    n_groups = num_groups
    T = _TILE_IDS
    acc_len = n_groups + 1 + T
    unseen = np.iinfo(np.int32).max

    def fn(codes):
        key, kmin, n = codes if isinstance(codes, tuple) else (None, None, None)
        P = (codes if key is None else key).shape[0]
        take = min(int(chunk), P)
        steps = -(-P // take)
        W = _tile_rows(take, n_groups)
        cp = -(-take // W) * W
        B = cp // W
        tid = jnp.arange(T, dtype=jnp.int32)

        def tile(s, pos, first):
            oh = (s - first[:, None])[:, :, None] == tid[None, None, :]
            return jnp.min(jnp.where(oh, pos[:, :, None], unseen), axis=1)

        def min_at(acc, offset, part):
            return lax.dynamic_update_slice(
                acc, jnp.minimum(lax.dynamic_slice(acc, (offset,), (T,)), part), (offset,)
            )

        def step(state):
            i, acc, _ = state
            start = jnp.minimum(i * take, P - take)
            pos = start + jnp.arange(take, dtype=jnp.int32)
            live = pos >= i * take
            with jax.named_scope("chunk_sort"):
                if key is None:
                    cc = lax.dynamic_slice(codes, (start,), (take,))
                else:
                    live &= pos < n
                    kc = lax.dynamic_slice(key, (start,), (take,)).astype(jnp.int64)
                    cc = jnp.clip(kc - kmin, 0, n_groups)
                cc = jnp.where(live, cc.astype(jnp.int32), n_groups)
                if cp > take:
                    cc = jnp.concatenate([cc, jnp.full(cp - take, n_groups, jnp.int32)])
                    pos = jnp.concatenate([pos, jnp.full(cp - take, unseen, jnp.int32)])
                s, pos = lax.sort((cc, pos), num_keys=1, is_stable=False)
                s, pos = s.reshape(B, W), pos.reshape(B, W)
            first, last = s[:, 0], s[:, -1]
            with jax.named_scope("tile_reduce"):
                parts = tile(s, pos, first)
            with jax.named_scope("tile_place"):
                acc = lax.fori_loop(
                    0, B, lambda b, acc: min_at(acc, first[b], parts[b]), acc, unroll=4
                )
            spills = last - first >= T
            n_spills = jnp.sum(spills, dtype=jnp.int32)
            turn = jnp.argsort(~spills, stable=True).astype(jnp.int32)

            def further(state):
                k, done, acc = state
                b = turn[k]
                sb = lax.dynamic_slice(s, (b, jnp.int32(0)), (1, W))
                pb = lax.dynamic_slice(pos, (b, jnp.int32(0)), (1, W))
                nxt = jnp.min(jnp.where(sb >= done, sb, n_groups))
                acc = min_at(acc, nxt, tile(sb, pb, nxt[None])[0])
                over = nxt + T > last[b]
                after = turn[jnp.minimum(k + 1, B - 1)]
                return (
                    jnp.where(over, k + 1, k),
                    jnp.where(over, first[after] + T, nxt + T),
                    acc,
                )

            with jax.named_scope("tile_spill"):
                acc = lax.while_loop(
                    lambda state: state[0] < n_spills,
                    further,
                    (jnp.int32(0), first[turn[0]] + T, acc),
                )[2]
            seen = jnp.sum(acc[:n_groups] != unseen, dtype=jnp.int32)
            return i + 1, acc, seen

        _, acc, _ = lax.while_loop(
            lambda state: (state[0] < steps) & (state[2] < n_groups),
            step,
            (jnp.int32(0), jnp.full(acc_len, unseen, jnp.int32), jnp.int32(0)),
        )
        _, order = lax.sort(
            (acc[:n_groups], jnp.arange(n_groups, dtype=jnp.int32)), num_keys=1
        )
        return _slice_pad(order, n_groups, p_out)

    return named_jit(fn, "groupby_first_seen_tiles")


def groupby_first_seen(codes: Any, num_groups: int) -> Any:
    """The groups in order of first appearance: a device int32 array (padded
    to the shard multiple, logical length ``num_groups``) whose entry ``j`` is
    the code of the ``j``-th group to appear.  Exact.  The form is read from
    the group count, the platform and the shard count (see above): the prefix
    form costs one host sync a prefix tried (the count of groups it showed),
    the tiles form none."""
    from modin_tpu.ops.structural import pad_len

    physical = int(codes.shape[0])
    form = _first_seen_form(codes, num_groups)
    operand = _tiles_operand(codes)
    p_out = pad_len(num_groups)
    if _meters.ACCOUNTING_ON:
        _meters.note_groupby_form(form)
    take = _first_seen_rows(num_groups, physical)
    with _spans.span(
        "groupby.first_seen", layer="GROUPBY-ASSEMBLE", form=form,
        num_groups=num_groups, rows=take,
    ):
        if form == "first_seen_tiles":
            return _jit_first_seen_tiles(num_groups, p_out, _SORT_CHUNK)(operand)
        while True:
            order, found = _jit_first_seen(num_groups, take, p_out)(operand)
            if int(_engine_materialize(found)) == num_groups or take == physical:
                return order
            take = min(take * _FIRST_SEEN_GROWTH, physical)


@functools.lru_cache(maxsize=None)
def _jit_first_seen_take(n_cols: int):
    import jax
    import jax.numpy as jnp

    def fn(cols: Tuple, order):
        return tuple(jnp.take(c, order, mode="clip") for c in cols)

    return named_jit(fn, "groupby_first_seen_take")


def groupby_take_groups(cols: List[Any], order: Any) -> List[Any]:
    """Rows of per-group result columns (each padded like ``order``) gathered
    into the order :func:`groupby_first_seen` found: G rows a column."""
    if not cols:
        return []
    with _spans.span(
        "groupby.first_seen", layer="GROUPBY-ASSEMBLE", form="take", n_cols=len(cols)
    ):
        return list(_jit_first_seen_take(len(cols))(tuple(cols), order))


@functools.lru_cache(maxsize=None)
def _jit_group_key_range(p_out: int, dtype: str):
    import jax
    import jax.numpy as jnp

    def fn(first, order=None):
        steps = jnp.arange(p_out, dtype=jnp.int64) if order is None else order
        return (first + steps).astype(dtype)

    return named_jit(fn, "groupby_key_range")


def group_keys_device(keys: np.ndarray, single_key: bool, order: Any = None) -> Tuple[Any, bool]:
    """The group keys of one level (host, as ``factorize_keys`` returns them) as
    a device column padded to the shard multiple, and whether it is in
    ``order`` (:func:`groupby_first_seen`'s) already.  The keys of a single
    integer key whose every value of the range is a group (sorted, distinct,
    last - first = count - 1) are written on the device from the first, and
    then in ``order`` at once (group ``j``'s key is ``first + j``: no gather);
    any other level is a small table and is uploaded in key order."""
    from modin_tpu.ops.structural import pad_host, pad_len

    keys = np.asarray(keys)
    g = len(keys)
    if (
        single_key
        and keys.dtype.kind in "iu"
        and g > _MASKED_SCAN_MAX_GROUPS
        and int(keys[-1]) - int(keys[0]) == g - 1
        and int(keys[-1]) <= np.iinfo(np.int64).max
    ):
        fn = _jit_group_key_range(pad_len(g), str(keys.dtype))
        first = np.int64(keys[0])
        return (fn(first) if order is None else fn(first, order)), True
    return _engine_upload(pad_host(keys, g)), order is None


def groupby_reduce(
    agg: str,
    value_cols: List[Any],
    codes: Any,
    num_groups: int,
    n: int,
    ddof: int = 1,
    sizes: Any = None,
) -> List[Any]:
    """Aggregate value columns by group codes; returns device arrays padded to
    the shard multiple with logical length num_groups (the overflow pad/NaN
    bucket is sliced off).

    ``sizes`` (host int64 per-group row counts, a factorization by-product)
    lets ``size`` skip the histogram kernel entirely and feeds the adaptive
    sum/mean/count path its denominator for free.
    """
    import jax
    import jax.numpy as jnp

    from modin_tpu.observability import costs as _costs
    from modin_tpu.ops.structural import pad_host, pad_len
    from modin_tpu.parallel.mesh import num_row_shards

    ns = num_groups + 1
    p_out = pad_len(num_groups)
    if _costs.COST_ON:
        # input leg: value columns + codes carry (P - n) pad rows each;
        # output leg: every result column is padded from num_groups to the
        # shard multiple (plus the sliced-off overflow bucket slot)
        in_padded = sum(
            int(c.shape[0]) * c.dtype.itemsize for c in value_cols
        ) + int(codes.shape[0]) * codes.dtype.itemsize
        in_valid = (
            sum(int(n) * c.dtype.itemsize for c in value_cols)
            + int(n) * codes.dtype.itemsize
        )
        _costs.note_padding("groupby.reduce.rows", in_padded, in_valid)
        out_width = max(len(value_cols), 1)
        _costs.note_padding(
            "groupby.reduce.groups",
            out_width * max(ns, p_out) * 8,
            out_width * num_groups * 8,
        )
    form = _reduce_form(agg, codes, num_groups, sizes, value_cols)
    if _meters.ACCOUNTING_ON and form != "host_sizes":
        _meters.note_groupby_form(form)
    with _spans.span(
        "groupby.reduce", layer="QUERY-COMPILER", form=form, agg=agg,
        num_groups=num_groups, n_cols=len(value_cols),
    ):
        if agg == "size":
            if sizes is not None:
                return [_engine_upload(pad_host(np.asarray(sizes, np.int64), num_groups))]
            return [_jit_pad_to(p_out)(_histogram(codes, num_groups, form))]
        single = num_row_shards() == 1
        # only the sorted tiles take codes that were not written out
        codes = _tiles_operand(codes) if form == "sorted_tiles" else codes_array(codes)
        # the factorisation's row counts as an operand: a denominator for free,
        # while the table is small (8 MB a request at 1e6 groups is not free,
        # and the tiles count a float column's valid rows themselves anyway)
        has_sizes = (
            sizes is not None
            and agg in ("mean", "count")
            and len(sizes) <= _SIZES_OPERAND_MAX_GROUPS
        )
        if form == "limb_dot":
            fn = _jit_limb_dot(
                agg, ns, p_out, has_sizes, _LIMB_CHUNK, not _on_tpu(codes)
            )
        elif form == "masked_scan":
            if agg not in ("sum", "mean", "count"):
                fn = _jit_masked_scan_agg(
                    agg, len(value_cols), ns, int(ddof), p_out, _SCAN_CHUNK
                )
                return list(fn(tuple(value_cols), codes))
            fn = _jit_masked_scan_smc(
                agg, len(value_cols), ns, p_out, _SCAN_CHUNK, single, has_sizes
            )
        elif form == "sorted_tiles":
            fn = _jit_sorted_tiles(
                agg, len(value_cols), ns, p_out, has_sizes, _SORT_CHUNK
            )
        else:
            has_sizes = single and sizes is not None and agg in ("sum", "mean", "count")
            fn = _jit_segment_agg(
                agg, len(value_cols), ns, int(ddof), p_out, single, has_sizes
            )
        if has_sizes:
            # the factorisation's row counts, the denominator of a column that
            # holds no NaN: ns slots like the in-kernel histogram (the overflow
            # bucket's value is sliced off, 1 avoids a 0-divide)
            sizes_dev = _engine_upload(np.append(np.asarray(sizes, np.int64), 1))
            return list(fn(tuple(value_cols), codes, sizes_dev))
        return list(fn(tuple(value_cols), codes))


def _reduce_form(
    agg: str, codes: Any, num_groups: int, sizes: Any, value_cols: Any = ()
) -> str:
    """The device form of one aggregation, read from the aggregation, the
    group count, the platform, the shard count and the columns' dtypes (no
    option): see the note above ``_MASKED_SCAN_MAX_GROUPS``.  min/max/prod/any/
    all above the masked scan's limit, var/std/sem (two-pass, centred), and
    everything on a row-sharded mesh above that limit keep the scatter-based
    segment ops."""
    from modin_tpu.parallel.mesh import num_row_shards

    if agg == "size":
        return "host_sizes" if sizes is not None else _histogram_form(codes, num_groups)
    if _tpu_forms(codes) and agg not in ("var", "std", "sem"):
        smc = agg in ("sum", "mean", "count") and num_row_shards() == 1
        if num_groups <= _MASKED_SCAN_MAX_GROUPS:
            if (
                smc
                and _FORCE_KERNEL != "masked_scan"
                and all(_limb_dot_takes(c.dtype) for c in value_cols)
            ):
                return "limb_dot"
            return "masked_scan"
        if smc and num_groups <= _RANGE_LIMIT:
            return "sorted_tiles"
    return "segment"


# ---------------------------------------------------------------------- #
# Order-statistic aggregations (median / quantile / nunique / first / last)
# ---------------------------------------------------------------------- #


@functools.lru_cache(maxsize=None)
def _jit_group_quantile(
    n_cols: int,
    num_segments: int,
    p_out: int,
    q: float,
    interpolation: str,
    preserve_float_dtype: bool = False,
):
    """Grouped quantile: lexsort by (code, value), gather at quantile ranks.

    NaNs sort to each group's tail (jnp sort order), so the non-NaN prefix of
    a group is its valid sample; ranks index into that prefix.
    """
    import jax
    import jax.numpy as jnp

    n_groups = num_segments - 1

    def finish(r):
        return _slice_pad(r, n_groups, p_out)

    def one(c, codes, starts):
        # pandas keeps the integer dtype for the non-interpolating kinds
        keep_int = (
            interpolation in ("lower", "higher", "nearest")
            and not jnp.issubdtype(c.dtype, jnp.floating)
        )
        x = c if keep_int else c.astype(jnp.float64)
        nanm = (
            jnp.isnan(x) if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.zeros(x.shape, bool)
        )
        order = jnp.lexsort((x, codes))
        xs = jnp.take(x, order)
        vcnt = jax.ops.segment_sum(
            (~nanm).astype(jnp.int64), codes, num_segments=num_segments
        )[:n_groups]
        g_start = starts[:n_groups]
        target = q * (vcnt.astype(jnp.float64) - 1.0)
        lo = jnp.floor(target).astype(jnp.int64)
        hi = jnp.ceil(target).astype(jnp.int64)
        max_pos = xs.shape[0] - 1
        v_lo = jnp.take(xs, jnp.clip(g_start + lo, 0, max_pos))
        v_hi = jnp.take(xs, jnp.clip(g_start + hi, 0, max_pos))
        frac = target - lo.astype(jnp.float64)
        if interpolation == "linear":
            r = v_lo + (v_hi - v_lo) * frac
        elif interpolation == "lower":
            r = v_lo
        elif interpolation == "higher":
            r = v_hi
        elif interpolation == "midpoint":
            r = (v_lo + v_hi) * 0.5
        else:  # nearest — numpy rounds the virtual rank half-to-even
            pos = jnp.round(target).astype(jnp.int64)
            r = jnp.take(xs, jnp.clip(g_start + pos, 0, max_pos))
        if not keep_int:
            r = jnp.where(vcnt == 0, jnp.nan, r)
            if preserve_float_dtype and jnp.issubdtype(c.dtype, jnp.floating):
                # pandas groupby median keeps float32; quantile widens to f64
                r = r.astype(c.dtype)
        return finish(r)

    def fn(cols: Tuple, codes):
        total = jax.ops.segment_sum(
            jnp.ones(codes.shape, jnp.int64), codes, num_segments=num_segments
        )
        starts = jnp.cumsum(total) - total
        return tuple(one(c, codes, starts) for c in cols)

    return named_jit(fn, "groupby_group_quantile")


def groupby_quantile(
    value_cols: List[Any],
    codes: Any,
    num_groups: int,
    n: int,
    q: float = 0.5,
    interpolation: str = "linear",
    preserve_float_dtype: bool = False,
) -> List[Any]:
    """Per-group quantile of each value column (device lexsort + gather)."""
    from modin_tpu.ops.structural import pad_len

    fn = _jit_group_quantile(
        len(value_cols), num_groups + 1, pad_len(num_groups), float(q),
        str(interpolation), bool(preserve_float_dtype),
    )
    return list(fn(tuple(value_cols), codes_array(codes)))


@functools.lru_cache(maxsize=None)
def _jit_group_nunique(n_cols: int, num_segments: int, p_out: int, dropna: bool):
    """Grouped distinct-count: lexsort by (code, value), count run heads."""
    import jax
    import jax.numpy as jnp

    n_groups = num_segments - 1

    def finish(r):
        return _slice_pad(r, n_groups, p_out)

    def one(c, codes):
        is_f = jnp.issubdtype(c.dtype, jnp.floating)
        nanm = jnp.isnan(c) if is_f else jnp.zeros(c.shape, bool)
        order = jnp.lexsort((c, codes))
        xs = jnp.take(c, order)
        cs = jnp.take(codes, order)
        nm = jnp.take(nanm, order)
        newgrp = jnp.concatenate([jnp.ones(1, bool), cs[1:] != cs[:-1]])
        newval = jnp.concatenate([jnp.ones(1, bool), xs[1:] != xs[:-1]])
        head = (newgrp | newval) & ~nm
        cnt = jax.ops.segment_sum(
            head.astype(jnp.int64), cs, num_segments=num_segments
        )
        if not dropna:
            has_nan = jax.ops.segment_max(
                nanm.astype(jnp.int64), codes, num_segments=num_segments
            )
            cnt = cnt + has_nan
        return finish(cnt)

    def fn(cols: Tuple, codes):
        return tuple(one(c, codes) for c in cols)

    return named_jit(fn, "groupby_group_nunique")


def groupby_nunique(
    value_cols: List[Any], codes: Any, num_groups: int, n: int, dropna: bool = True
) -> List[Any]:
    from modin_tpu.ops.structural import pad_len

    fn = _jit_group_nunique(
        len(value_cols), num_groups + 1, pad_len(num_groups), bool(dropna)
    )
    return list(fn(tuple(value_cols), codes_array(codes)))


@functools.lru_cache(maxsize=None)
def _jit_group_first_last(last: bool, n_cols: int, num_segments: int, p_out: int):
    """Grouped first/last non-NaN value in row order (segment arg-extremum)."""
    import jax
    import jax.numpy as jnp

    n_groups = num_segments - 1

    def finish(r):
        return _slice_pad(r, n_groups, p_out)

    def one(c, codes):
        is_f = jnp.issubdtype(c.dtype, jnp.floating)
        P = c.shape[0]
        valid = ~jnp.isnan(c) if is_f else jnp.ones(c.shape, bool)
        iota = jnp.arange(P, dtype=jnp.int64)
        if last:
            key = jnp.where(valid, iota, -1)
            idx = jax.ops.segment_max(key, codes, num_segments=num_segments)
            has = idx >= 0
        else:
            key = jnp.where(valid, iota, P)
            idx = jax.ops.segment_min(key, codes, num_segments=num_segments)
            has = idx < P
        vals = jnp.take(c, jnp.clip(idx, 0, P - 1))
        if is_f:
            vals = jnp.where(has, vals, jnp.nan)
        return finish(vals)

    def fn(cols: Tuple, codes):
        return tuple(one(c, codes) for c in cols)

    return named_jit(fn, "groupby_group_first_last")


def groupby_first_last(
    agg: str, value_cols: List[Any], codes: Any, num_groups: int, n: int
) -> List[Any]:
    from modin_tpu.ops.structural import pad_len

    fn = _jit_group_first_last(
        agg == "last", len(value_cols), num_groups + 1, pad_len(num_groups)
    )
    return list(fn(tuple(value_cols), codes_array(codes)))


@functools.lru_cache(maxsize=None)
def _jit_broadcast_groups(n_cols: int):
    """Gather each row's group aggregate back to row positions (transform)."""
    import jax
    import jax.numpy as jnp

    def fn(aggs: Tuple, codes):
        out = []
        for a in aggs:
            safe = jnp.minimum(codes, a.shape[0] - 1)  # pad rows: garbage, sliced off
            out.append(jnp.take(a, safe))
        return tuple(out)

    return named_jit(fn, "groupby_broadcast_groups")


def groupby_broadcast(agg_cols: List[Any], codes: Any) -> List[Any]:
    """Row-shaped device arrays where row i holds its group's aggregate."""
    return list(
        _jit_broadcast_groups(len(agg_cols))(tuple(agg_cols), codes_array(codes))
    )


# row-shaped cumulative aggregations (segmented scan)
CUM_AGGS = {"cumsum", "cumprod", "cummax", "cummin"}


@functools.lru_cache(maxsize=None)
def _jit_grouped_cum(op: str, n_cols: int):
    """Grouped cumulatives: sort rows by group code, run ONE segmented
    associative scan (reset at group boundaries), scatter back to row order.
    pandas NaN semantics: a NaN keeps its position without poisoning later
    entries."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    op_fn = {
        "cumsum": jnp.add, "cumprod": jnp.multiply,
        "cummax": jnp.maximum, "cummin": jnp.minimum,
    }[op]
    float_neutral = {
        "cumsum": 0.0, "cumprod": 1.0, "cummax": -jnp.inf, "cummin": jnp.inf,
    }[op]

    def one(c, order, inv, newgrp):
        is_f = jnp.issubdtype(c.dtype, jnp.floating)
        x = jnp.take(c, order)
        nanm = jnp.isnan(x) if is_f else None
        filled = jnp.where(nanm, float_neutral, x) if is_f else x

        def combine(a, b):
            fa, va = a
            fb, vb = b
            return fa | fb, jnp.where(fb, vb, op_fn(va, vb))

        _, scanned = lax.associative_scan(combine, (newgrp, filled))
        if is_f:
            scanned = jnp.where(nanm, jnp.nan, scanned)
        return jnp.take(scanned, inv)

    def fn(cols: Tuple, codes):
        order = jnp.argsort(codes, stable=True)
        inv = jnp.argsort(order)
        cs = jnp.take(codes, order)
        newgrp = jnp.concatenate([jnp.ones(1, bool), cs[1:] != cs[:-1]])
        return tuple(one(c, order, inv, newgrp) for c in cols)

    return named_jit(fn, "groupby_grouped_cum")


def groupby_cumulative(op: str, value_cols: List[Any], codes: Any) -> List[Any]:
    """Row-shaped grouped cumsum/cumprod/cummax/cummin."""
    fn = _jit_grouped_cum(op, len(value_cols))
    return list(fn(tuple(value_cols), codes_array(codes)))


# ---------------------------------------------------------------------- #
# graftfuse: whole-plan fused groupby (bounded-range int/bool keys)
# ---------------------------------------------------------------------- #

#: aggregations with a masked scatter form the fused whole-plan program
#: can express in one pass (pandas groupby semantics: NaN values always
#: skipped; all-NaN float groups answer sum=0 / count=0 / min=max=mean=NaN)
FUSED_GROUPBY_AGGS = frozenset({"sum", "prod", "count", "mean", "min", "max"})

#: widest group-id table a fused program will scatter into (pow2-padded);
#: wider key ranges decline to the staged factorize path
FUSED_MAX_GROUPS = 1 << 16


def fused_groups_bucket(width: int) -> int:
    """Pow2-padded group-table size for a key range of ``width`` values —
    the same shape discipline the histogram reductions use, so a dozen
    nearby cardinalities share one compiled program."""
    return 1 << max(int(width - 1).bit_length(), 3)


def fused_group_probe(
    key_expr: Any, keep: Optional[Any], n: int
) -> Tuple[int, int, int]:
    """(key_min, key_max, kept_rows) of the masked key column, one dispatch.

    The filter/map chain below the key fuses into this probe program; the
    three scalars are the only host fetch.  ``keep`` may be None (no
    filter: only the pad rows are masked).  ``kept_rows == 0`` tells the
    caller to decline (pandas empty-groupby semantics stay with the staged
    path).  Keys must be integral (int/uint/bool) — the caller gates.
    """
    from modin_tpu.ops.lazy import run_fused

    has_mask = keep is not None

    def tail(arrs):
        import jax.numpy as jnp

        if has_mask:
            k, m, n_t = arrs
        else:
            k, n_t = arrs
            m = True
        k64 = k.astype(jnp.int64)
        valid = m & (jnp.arange(k64.shape[0]) < n_t)
        kept = jnp.sum(valid, dtype=jnp.int64)
        kmin = jnp.min(jnp.where(valid, k64, jnp.iinfo(jnp.int64).max))
        kmax = jnp.max(jnp.where(valid, k64, jnp.iinfo(jnp.int64).min))
        return kmin, kmax, kept

    roots = [key_expr] + ([keep] if has_mask else []) + [int(n)]
    results = run_fused(
        roots,
        tail_key=("fuse_gb_probe", has_mask),
        tail_builder=tail,
    )
    kmin, kmax, kept = [int(np.asarray(r)) for r in _engine_materialize(results)]
    return kmin, kmax, kept


def fused_group_agg(
    agg: str,
    key_expr: Any,
    cols: List[Any],
    keep: Optional[Any],
    n: int,
    kmin: int,
    n_buckets: int,
    donate_cols: Optional[List[Any]] = None,
) -> Tuple[np.ndarray, List[np.ndarray], List[np.ndarray]]:
    """The whole post-scan chain + masked group aggregation, one dispatch.

    Scatters every kept row into a ``n_buckets``-slot table (slot =
    ``key - kmin``, with ``kmin`` a runtime scalar so one program serves
    any key offset at a bucket size); dropped/pad rows land in the
    overflow slot and are sliced off.  Returns host arrays
    ``(group_sizes[n_buckets], per-column aggregates, per-column non-NaN
    counts)`` — the caller keeps slots with ``group_sizes > 0`` (observed
    groups, already in sorted key order) and applies pandas dtype rules.
    """
    from modin_tpu.ops.reductions import _mark_and_run

    G = int(n_buckets)
    has_mask = keep is not None

    def tail(arrs):
        import jax.numpy as jnp

        if has_mask:
            k, *col_arrs, m, n_t, kmin_t = arrs
        else:
            k, *col_arrs, n_t, kmin_t = arrs
            m = True
        k64 = k.astype(jnp.int64)
        valid = m & (jnp.arange(k64.shape[0]) < n_t)
        ids = jnp.where(valid, jnp.clip(k64 - kmin_t, 0, G - 1), G)
        sizes = jnp.zeros(G + 1, jnp.int64).at[ids].add(
            jnp.where(valid, 1, 0)
        )
        tables = []
        counts = []
        for c in col_arrs:
            is_f = jnp.issubdtype(c.dtype, jnp.floating)
            use = valid & ~jnp.isnan(c) if is_f else valid
            nn = jnp.zeros(G + 1, jnp.int64).at[ids].add(jnp.where(use, 1, 0))
            counts.append(nn)
            if agg == "count":
                tables.append(nn)
                continue
            x = c.astype(jnp.int64) if c.dtype == jnp.bool_ else c
            if agg in ("sum", "mean"):
                acc = x.astype(jnp.float64) if agg == "mean" else x
                neutral = jnp.zeros((), acc.dtype)
                t = jnp.zeros(G + 1, acc.dtype).at[ids].add(
                    jnp.where(use, acc, neutral)
                )
                if agg == "mean":
                    t = jnp.where(nn > 0, t / nn, jnp.nan)
                tables.append(t)
            elif agg == "prod":
                t = jnp.ones(G + 1, x.dtype).at[ids].multiply(
                    jnp.where(use, x, jnp.ones((), x.dtype))
                )
                tables.append(t)
            elif agg in ("min", "max"):
                from modin_tpu.ops.reductions import _int_max, _int_min

                if is_f:
                    neutral = jnp.inf if agg == "min" else -jnp.inf
                else:
                    neutral = (
                        _int_max(x.dtype) if agg == "min" else _int_min(x.dtype)
                    )
                init = jnp.full(G + 1, neutral, x.dtype)
                at = init.at[ids]
                t = (at.min if agg == "min" else at.max)(
                    jnp.where(use, x, jnp.full((), neutral, x.dtype))
                )
                if is_f:
                    # all-NaN (or empty) slot: the neutral infinity means
                    # "no value"; pandas answers NaN there
                    t = jnp.where(nn > 0, t, jnp.nan)
                tables.append(t)
            else:
                raise ValueError(agg)
        return (sizes,) + tuple(tables) + tuple(counts)

    roots = (
        [key_expr, *cols]
        + ([keep] if has_mask else [])
        + [int(n), int(kmin)]
    )
    results = _mark_and_run(
        roots,
        ("fuse_gb_agg", agg, G, len(cols), has_mask),
        tail,
        donate_cols,
    )
    fetched = [np.asarray(r) for r in _engine_materialize(results)]
    sizes = fetched[0]
    n_cols = len(cols)
    return sizes, fetched[1 : 1 + n_cols], fetched[1 + n_cols :]
