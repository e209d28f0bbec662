"""Column reductions with pandas NaN semantics, pad-aware.

TPU-native replacement for the reference's Reduce/TreeReduce operators
(modin/core/dataframe/algebra/tree_reduce.py:29): on a sharded jax.Array a
``jnp.sum`` lowers to per-shard partial reduction + an XLA ``psum`` over ICI —
the map/axis-reduce task pair of the reference collapses into one compiled
collective program.

All per-column reductions of a frame run in ONE jit so a ``df.sum()`` costs
one dispatch + one small fetch regardless of column count.  Columns are
padded to the shard count; every kernel masks rows >= n (the logical length,
passed statically).
"""

from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional, Tuple

import numpy as np


from modin_tpu.parallel.engine import materialize as _engine_materialize
from modin_tpu.ops._program import named_jit


def _masked(c, n, neutral):
    import jax.numpy as jnp

    if c.shape[0] == n:
        return c
    valid = jnp.arange(c.shape[0]) < n
    return jnp.where(valid, c, neutral)


def _valid_mask(c, n):
    import jax.numpy as jnp

    return jnp.arange(c.shape[0]) < n


def _reduce_one(
    op: str,
    c,
    n: int,
    skipna: bool,
    ddof: int,
    adaptive: bool = False,
    adaptive_sharded: bool = False,
):
    """Reduce one padded column with logical length n.

    When the column is unpadded (shape == n, the common case: lengths that
    divide the shard count evenly), the validity iota-mask is skipped — on
    clean data that leaves a single fused pass over the column.

    ``adaptive`` additionally enables the NaN-adaptive lax.cond fast path on
    single-shard meshes (a GLOBAL lax.cond over sharded operands miscompiles
    under SPMD partitioning — observed on the virtual CPU mesh).
    ``adaptive_sharded`` is the multi-shard formulation: the cond runs PER
    SHARD inside shard_map, where its operands are local, and scalar
    partials combine outside (see _reduce_adaptive_sharded).
    """
    import jax.numpy as jnp

    is_f = jnp.issubdtype(c.dtype, jnp.floating)
    unpadded = c.shape[0] == n
    if adaptive and unpadded and is_f and skipna and n > 0:
        fast = _reduce_clean_adaptive(op, c, n, ddof)
        if fast is not None:
            return fast
    if adaptive_sharded and unpadded and is_f and skipna and n > 0:
        fast = _reduce_adaptive_sharded(op, c, n)
        if fast is not None:
            return fast
    # unpadded columns (lengths dividing the shard count) elide the iota
    # validity mask — clean int/float reductions become a single fused pass
    cnt_dtype = jnp.int32 if n < 2**31 else jnp.int64
    if unpadded:
        valid = None
        nan_mask = jnp.isnan(c) if is_f else None
        use = ~nan_mask if (skipna and is_f) else None
        n_use = (
            jnp.sum(use, dtype=cnt_dtype).astype(jnp.int64)
            if use is not None
            else jnp.asarray(n, jnp.int64)
        )
    else:
        valid = _valid_mask(c, n)
        nan_mask = jnp.isnan(c) & valid if is_f else None
        use = valid & ~nan_mask if (skipna and is_f) else valid
        n_use = jnp.sum(use, dtype=cnt_dtype).astype(jnp.int64)

    def sel(x, neutral):
        return x if use is None else jnp.where(use, x, neutral)

    def sel_valid(x, neutral):
        return x if valid is None else jnp.where(valid, x, neutral)

    if op == "count":
        if nan_mask is None:
            return jnp.asarray(n, jnp.int64)
        return jnp.sum(sel_valid(~nan_mask, False), dtype=cnt_dtype).astype(jnp.int64)
    if op == "sum":
        return jnp.sum(sel(c, 0))
    if op == "prod":
        return jnp.prod(sel(c, 1))
    if op == "min":
        if is_f:
            r = jnp.min(sel(c, jnp.inf))
            any_nan = jnp.any(nan_mask) & (not skipna)
            return jnp.where(jnp.isinf(r) & (n_use == 0), jnp.nan, jnp.where(any_nan, jnp.nan, r))
        return jnp.min(sel(c, _int_max(c.dtype)))
    if op == "max":
        if is_f:
            r = jnp.max(sel(c, -jnp.inf))
            any_nan = jnp.any(nan_mask) & (not skipna)
            return jnp.where(jnp.isinf(-r) & (n_use == 0), jnp.nan, jnp.where(any_nan, jnp.nan, r))
        return jnp.max(sel(c, _int_min(c.dtype)))
    if op in ("mean", "var", "std", "sem", "skew", "kurt"):
        x = sel(c, 0).astype(jnp.float64)
        s = jnp.sum(x)
        mean = s / n_use
        if op == "mean":
            if is_f and not skipna:
                return jnp.where(jnp.any(nan_mask), jnp.nan, mean)
            return jnp.where(n_use == 0, jnp.nan, mean)
        d = sel(x - mean, 0.0)
        m2s = jnp.sum(d**2)
        if op in ("var", "std", "sem"):
            var = m2s / jnp.maximum(n_use - ddof, 1)
            var = jnp.where(n_use - ddof > 0, var, jnp.nan)
            if is_f and not skipna:
                var = jnp.where(jnp.any(nan_mask), jnp.nan, var)
            if op == "var":
                return var
            if op == "std":
                return jnp.sqrt(var)
            return jnp.sqrt(var / n_use)
        nf = n_use.astype(jnp.float64)
        m2 = m2s / nf
        if op == "skew":
            m3 = jnp.sum(d**3) / nf
            g1 = m3 / jnp.where(m2 > 0, m2, 1.0) ** 1.5
            res = jnp.sqrt(nf * (nf - 1.0)) / (nf - 2.0) * g1
            res = jnp.where((nf < 3) | (m2 == 0), jnp.nan, res)
        else:  # kurt — sample excess kurtosis G2, pandas' nankurt
            m4 = jnp.sum(d**4) / nf
            g2 = m4 / jnp.where(m2 > 0, m2, 1.0) ** 2 - 3.0
            res = ((nf + 1.0) * g2 + 6.0) * (nf - 1.0) / ((nf - 2.0) * (nf - 3.0))
            res = jnp.where((nf < 4) | (m2 == 0), jnp.nan, res)
        if is_f and not skipna:
            res = jnp.where(jnp.any(nan_mask), jnp.nan, res)
        return res
    if op == "median":
        x = sel(c, jnp.nan).astype(jnp.float64)
        return jnp.nanmedian(x)
    if op == "any":
        truthy = jnp.where(nan_mask, not skipna, c != 0) if is_f else (c != 0 if c.dtype != jnp.bool_ else c)
        return jnp.any(sel_valid(truthy, False))
    if op == "all":
        truthy = jnp.where(nan_mask, True, c != 0) if is_f else (c != 0 if c.dtype != jnp.bool_ else c)
        return jnp.all(sel_valid(truthy, True))
    raise ValueError(op)


def _reduce_clean_adaptive(op: str, c, n: int, ddof: int):
    """NaN-adaptive float reduction: run the unmasked single-pass kernel and
    fall into the masked path (via lax.cond) only when the result shows a NaN
    actually occurred.  On clean data — the common case — the select/masking
    passes are skipped entirely (measured ~4x on XLA CPU, where jnp.sum
    beats pandas but where+sum does not).  Returns None for ops without an
    adaptive form.
    """
    import jax.lax as lax
    import jax.numpy as jnp

    def masked(neutral):
        return jnp.where(jnp.isnan(c), neutral, c)

    # int32 accumulation of the bool mask is ~3x faster on XLA CPU than the
    # default int64 widening (n < 2^31 always holds for per-shard lengths)
    cnt_dtype = jnp.int32 if n < 2**31 else jnp.int64

    def n_use():
        return (n - jnp.sum(jnp.isnan(c), dtype=cnt_dtype)).astype(jnp.int64)

    if op == "sum":
        s = jnp.sum(c)
        return lax.cond(jnp.isnan(s), lambda: jnp.sum(masked(0.0)), lambda: s)
    if op == "prod":
        p = jnp.prod(c)
        return lax.cond(jnp.isnan(p), lambda: jnp.prod(masked(1.0)), lambda: p)
    if op == "count":
        # clean data: one plain sum proves there are no NaNs and count is n;
        # inf+-inf false-positives only cost the slow path, never correctness
        s = jnp.sum(c)
        return lax.cond(
            jnp.isnan(s), n_use, lambda: jnp.asarray(n, jnp.int64)
        )
    if op in ("min", "max"):
        reducer = jnp.min if op == "min" else jnp.max
        r = reducer(c)

        def dirty():
            neutral = jnp.inf if op == "min" else -jnp.inf
            m = reducer(masked(neutral))
            # all-NaN group: masked reduce returns the neutral infinity
            return jnp.where(n_use() == 0, jnp.nan, m)

        return lax.cond(jnp.isnan(r), dirty, lambda: r)
    # mean/var family accumulates in float64, matching the masked path
    x64 = c.astype(jnp.float64)
    if op == "mean":
        s = jnp.sum(x64)

        def dirty():
            k = n_use()
            return jnp.where(
                k == 0, jnp.nan, jnp.sum(jnp.where(jnp.isnan(x64), 0.0, x64)) / k
            )

        return lax.cond(jnp.isnan(s), dirty, lambda: s / n)
    if op in ("var", "std", "sem"):
        s = jnp.sum(x64)

        def clean():
            mean = s / n
            d = x64 - mean
            var = jnp.sum(d * d) / max(n - ddof, 1)
            return var if n - ddof > 0 else jnp.full((), jnp.nan)

        def dirty():
            nanm = jnp.isnan(x64)
            k = n_use()
            x = jnp.where(nanm, 0.0, x64)
            mean = jnp.sum(x) / k
            d = jnp.where(nanm, 0.0, x - mean)
            var = jnp.sum(d * d) / jnp.maximum(k - ddof, 1)
            return jnp.where(k - ddof > 0, var, jnp.nan)

        var = lax.cond(jnp.isnan(s), dirty, clean)
        if op == "var":
            return var
        if op == "std":
            return jnp.sqrt(var)
        k = lax.cond(
            jnp.isnan(s), lambda: n_use().astype(jnp.int64),
            lambda: jnp.asarray(n, jnp.int64),
        )
        return jnp.sqrt(var / k)
    return None


_SHARDED_ADAPTIVE_OPS = ("sum", "prod", "count", "min", "max", "mean")


def _reduce_adaptive_sharded(op: str, c, n: int):
    """NaN-adaptive reduction on a row-sharded column.

    The single-shard form's global ``lax.cond`` cannot be SPMD-partitioned
    over sharded operands, so here the cond runs PER SHARD inside
    ``shard_map`` — each branch sees only the shard's local block — and the
    shards return (partial, nan_count) scalars that combine outside the
    map.  Clean shards skip the isnan/where passes entirely; a NaN only
    slows the shard that contains it.  The var/skew family keeps the masked
    path when sharded: its two global passes (mean, then centered moments)
    leave little for the adaptive branch to save.
    """
    import jax.lax as lax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from modin_tpu.parallel.jax_compat import shard_map

    from modin_tpu.parallel.mesh import get_mesh

    if op not in _SHARDED_ADAPTIVE_OPS:
        return None
    mesh = get_mesh()
    cnt_dtype = jnp.int32 if n < 2**31 else jnp.int64

    def local(x):
        def nan_count():
            return jnp.sum(jnp.isnan(x), dtype=cnt_dtype).astype(jnp.int64)

        def no_nans():
            return jnp.zeros((), jnp.int64)

        if op in ("sum", "prod"):
            reducer = jnp.sum if op == "sum" else jnp.prod
            neutral = jnp.asarray(0 if op == "sum" else 1, x.dtype)
            s = reducer(x)
            ms = lax.cond(
                jnp.isnan(s),
                lambda: reducer(jnp.where(jnp.isnan(x), neutral, x)),
                lambda: s,
            )
            return ms[None], jnp.zeros((1,), jnp.int64)
        if op == "count":
            # one plain sum proves the shard is clean; inf-inf false
            # positives only cost the slow branch, never correctness
            s = jnp.sum(x)
            nc = lax.cond(jnp.isnan(s), nan_count, no_nans)
            return jnp.zeros((1,), x.dtype), nc[None]
        if op in ("min", "max"):
            reducer = jnp.min if op == "min" else jnp.max
            neutral = jnp.asarray(jnp.inf if op == "min" else -jnp.inf, x.dtype)
            r = reducer(x)
            m, nc = lax.cond(
                jnp.isnan(r),
                lambda: (reducer(jnp.where(jnp.isnan(x), neutral, x)), nan_count()),
                lambda: (r, jnp.zeros((), jnp.int64)),
            )
            return m[None], nc[None]
        # mean: float64 accumulation, matching the masked path
        x64 = x.astype(jnp.float64)
        s = jnp.sum(x64)
        ms, nc = lax.cond(
            jnp.isnan(s),
            lambda: (jnp.sum(jnp.where(jnp.isnan(x64), 0.0, x64)), nan_count()),
            lambda: (s, jnp.zeros((), jnp.int64)),
        )
        return ms[None], nc[None]

    partials, ncs = shard_map(
        local,
        mesh=mesh,
        in_specs=P("rows"),
        out_specs=(P("rows"), P("rows")),
        check_vma=False,
    )(c)
    n_use = n - jnp.sum(ncs)
    if op == "count":
        return n_use.astype(jnp.int64)
    if op == "sum":
        return jnp.sum(partials)
    if op == "prod":
        return jnp.prod(partials)
    if op == "mean":
        return jnp.where(n_use == 0, jnp.nan, jnp.sum(partials) / n_use)
    reducer = jnp.min if op == "min" else jnp.max
    return jnp.where(n_use == 0, jnp.nan, reducer(partials))


def _int_max(dtype):
    import jax.numpy as jnp

    if dtype == jnp.bool_:
        return True
    return np.iinfo(np.dtype(str(dtype))).max


def _int_min(dtype):
    import jax.numpy as jnp

    if dtype == jnp.bool_:
        return False
    return np.iinfo(np.dtype(str(dtype))).min


def reduce_columns(
    op_name: str,
    cols: List[Any],
    n: int,
    skipna: bool = True,
    ddof: int = 1,
    cast_bool: bool = False,
    donate_cols: Optional[List[Any]] = None,
) -> list:
    """Reduce each padded column (logical length n) to a scalar; one fetch.

    ``cols`` may mix concrete arrays and deferred LazyExprs — the reduction
    traces as a *tail* of the fused program (ops/lazy.py), so a chain like
    ``(a * b + c).sum()`` compiles to one kernel.  ``cast_bool`` applies the
    pandas bool->int promotion for arithmetic aggregations inside the fusion.
    """
    import jax

    from modin_tpu.parallel.mesh import num_row_shards

    n, skipna, ddof = int(n), bool(skipna), int(ddof)
    n_shards = num_row_shards()
    adaptive = n_shards == 1
    # shard-local adaptive form needs evenly-divided (unpadded) rows
    adaptive_sharded = n_shards > 1 and n > 0 and n % n_shards == 0

    def tail(arrs):
        import jax.numpy as jnp

        if cast_bool:
            arrs = [a.astype(jnp.int64) if a.dtype == jnp.bool_ else a for a in arrs]
        return tuple(
            _reduce_one(op_name, c, n, skipna, ddof, adaptive, adaptive_sharded)
            for c in arrs
        )

    results = _mark_and_run(
        cols,
        # adaptive/adaptive_sharded are derived from (n, n_shards), so the
        # shard count alone completes the cache key
        ("reduce", op_name, n, skipna, ddof, bool(cast_bool), n_shards),
        tail,
        donate_cols,
    )
    return [np.asarray(r) for r in _engine_materialize(results)]


def _reduce_one_masked(op: str, c, valid, skipna: bool, ddof: int):
    """Reduce one padded column restricted to the ``valid`` row mask.

    The graftfuse whole-plan form of :func:`_reduce_one`: ``valid`` is the
    filter's keep mask already AND-ed with the logical-length iota mask (n
    rides as a *traced* scalar in the fused program, so one executable
    serves every logical length at a physical size).  Semantics mirror
    ``_reduce_one``'s masked branch exactly — the compacted rows a staged
    filter would have gathered are the same values this mask selects, in
    the same order — with the NaN-adaptive fast paths skipped (the mask
    forces the select form anyway).
    """
    import jax.numpy as jnp

    is_f = jnp.issubdtype(c.dtype, jnp.floating)
    cnt_dtype = jnp.int32 if c.shape[0] < 2**31 else jnp.int64
    nan_mask = jnp.isnan(c) & valid if is_f else None
    use = valid & ~nan_mask if (skipna and is_f) else valid
    n_use = jnp.sum(use, dtype=cnt_dtype).astype(jnp.int64)

    def sel(x, neutral):
        return jnp.where(use, x, neutral)

    def sel_valid(x, neutral):
        return jnp.where(valid, x, neutral)

    if op == "count":
        if nan_mask is None:
            return jnp.sum(valid, dtype=cnt_dtype).astype(jnp.int64)
        return jnp.sum(sel_valid(~nan_mask, False), dtype=cnt_dtype).astype(jnp.int64)
    if op == "sum":
        return jnp.sum(sel(c, 0))
    if op == "prod":
        return jnp.prod(sel(c, 1))
    if op == "min":
        if is_f:
            r = jnp.min(sel(c, jnp.inf))
            any_nan = jnp.any(nan_mask) & (not skipna)
            return jnp.where(jnp.isinf(r) & (n_use == 0), jnp.nan, jnp.where(any_nan, jnp.nan, r))
        return jnp.min(sel(c, _int_max(c.dtype)))
    if op == "max":
        if is_f:
            r = jnp.max(sel(c, -jnp.inf))
            any_nan = jnp.any(nan_mask) & (not skipna)
            return jnp.where(jnp.isinf(-r) & (n_use == 0), jnp.nan, jnp.where(any_nan, jnp.nan, r))
        return jnp.max(sel(c, _int_min(c.dtype)))
    if op in ("mean", "var", "std", "sem", "skew", "kurt"):
        x = sel(c, 0).astype(jnp.float64)
        s = jnp.sum(x)
        mean = s / n_use
        if op == "mean":
            if is_f and not skipna:
                return jnp.where(jnp.any(nan_mask), jnp.nan, mean)
            return jnp.where(n_use == 0, jnp.nan, mean)
        d = sel(x - mean, 0.0)
        m2s = jnp.sum(d**2)
        if op in ("var", "std", "sem"):
            var = m2s / jnp.maximum(n_use - ddof, 1)
            var = jnp.where(n_use - ddof > 0, var, jnp.nan)
            if is_f and not skipna:
                var = jnp.where(jnp.any(nan_mask), jnp.nan, var)
            if op == "var":
                return var
            if op == "std":
                return jnp.sqrt(var)
            return jnp.sqrt(var / n_use)
        nf = n_use.astype(jnp.float64)
        m2 = m2s / nf
        if op == "skew":
            m3 = jnp.sum(d**3) / nf
            g1 = m3 / jnp.where(m2 > 0, m2, 1.0) ** 1.5
            res = jnp.sqrt(nf * (nf - 1.0)) / (nf - 2.0) * g1
            res = jnp.where((nf < 3) | (m2 == 0), jnp.nan, res)
        else:  # kurt
            m4 = jnp.sum(d**4) / nf
            g2 = m4 / jnp.where(m2 > 0, m2, 1.0) ** 2 - 3.0
            res = ((nf + 1.0) * g2 + 6.0) * (nf - 1.0) / ((nf - 2.0) * (nf - 3.0))
            res = jnp.where((nf < 4) | (m2 == 0), jnp.nan, res)
        if is_f and not skipna:
            res = jnp.where(jnp.any(nan_mask), jnp.nan, res)
        return res
    if op == "median":
        # a masked median needs a data-dependent selection; the fused leg
        # declines it to the staged path before getting here
        raise ValueError("median has no masked fused form")
    if op == "any":
        truthy = jnp.where(nan_mask, not skipna, c != 0) if is_f else (c != 0 if c.dtype != jnp.bool_ else c)
        return jnp.any(sel_valid(truthy, False))
    if op == "all":
        truthy = jnp.where(nan_mask, True, c != 0) if is_f else (c != 0 if c.dtype != jnp.bool_ else c)
        return jnp.all(sel_valid(truthy, True))
    raise ValueError(op)


def _mark_and_run(roots, tail_key, tail, donate_cols):
    """Dispatch ``run_fused`` with buffer donation (graftfuse).

    ``donate_cols`` are DeviceColumns the caller proved donation-safe; only
    those whose buffer the forest actually consumes are donated.  Columns
    are marked consumed (spilled-with-exact-host-copy semantics) BEFORE the
    dispatch — the argument tree pins the buffers for the program itself,
    and any failure path that re-dispatches (the engine's rebind retry)
    then rebuilds over lineage-restored buffers instead of the consumed
    ones.  The finally re-mark covers exactly that rebind: its restore
    hands the column a fresh buffer that the retried donated program
    consumes too.
    """
    from modin_tpu.logging.metrics import emit_metric
    from modin_tpu.ops.lazy import leaf_buffer_ids, run_fused

    donate_map = {}
    if donate_cols:
        consumed = leaf_buffer_ids(roots)
        for col in donate_cols:
            buf = col._data
            if buf is not None and not col.is_lazy and id(buf) in consumed:
                donate_map[id(buf)] = col
    if not donate_map:
        return run_fused(roots, tail_key=tail_key, tail_builder=tail)
    # emit BEFORE marking: QueryStats samples HBM residency on this metric,
    # and the pre-donation sample is the honest peak (the consumed buffers
    # are still resident right up to the dispatch)
    emit_metric("fuse.donated", len(donate_map))
    freed = 0
    for col in donate_map.values():
        freed += col.mark_donated()
    emit_metric("fuse.donated_bytes", freed)
    try:
        return run_fused(
            roots, tail_key=tail_key, tail_builder=tail,
            donate=frozenset(donate_map),
        )
    finally:
        for col in donate_map.values():
            if col._data is not None:
                col.mark_donated()


def reduce_columns_masked(
    op_name: str,
    cols: List[Any],
    keep: Any,
    n: int,
    skipna: bool = True,
    ddof: int = 1,
    cast_bool: bool = False,
    donate_cols: Optional[List[Any]] = None,
) -> Tuple[list, int]:
    """graftfuse whole-plan tail: reduce each column over ``keep`` rows.

    ``keep`` is the (possibly deferred) boolean filter mask over the
    UNCOMPACTED padded rows — the filter/map chain fuses into this one
    program instead of paying a separate compaction dispatch.  ``n`` (the
    pre-filter logical length) rides as a runtime scalar so the compiled
    program is shared across logical lengths at one physical size.
    Returns ``(values, kept_rows)``; the caller declines to the staged
    path when ``kept_rows == 0`` (pandas empty-frame semantics live there).
    """
    n, skipna, ddof = int(n), bool(skipna), int(ddof)

    def tail(arrs):
        import jax.numpy as jnp

        *col_arrs, m, n_t = arrs
        if cast_bool:
            col_arrs = [
                a.astype(jnp.int64) if a.dtype == jnp.bool_ else a
                for a in col_arrs
            ]
        valid = m & (jnp.arange(m.shape[0]) < n_t)
        kept = jnp.sum(valid, dtype=jnp.int64)
        outs = tuple(
            _reduce_one_masked(op_name, c, valid, skipna, ddof)
            for c in col_arrs
        )
        return outs + (kept,)

    results = _mark_and_run(
        [*cols, keep, n],
        ("fuse_reduce", op_name, skipna, ddof, bool(cast_bool), len(cols)),
        tail,
        donate_cols,
    )
    fetched = [np.asarray(r) for r in _engine_materialize(results)]
    return fetched[:-1], int(fetched[-1])


# A row-wise reduction over at most this many columns reads them as k
# arrays (elementwise folds; a compare-exchange network for median and
# nunique) and never builds the (k, n) matrix.  The network's comparators
# grow as k log^2 k, so a wider frame keeps the stacked program.
_AXIS1_COLUMNS_MAX = 32
# On one shard the median walks the rows a chunk of this many bytes of
# 64-bit values at a time.  XLA splits the network that selects the middle
# ranks into a dozen fusions, and a chunk's intermediates stay in the chip's
# vector memory where the whole column's are written out: at 5e7 x 10 int64
# on a v5e 84.3 ms unchunked (8.85 GB of temporaries), 51.0 at 2**16 rows,
# 46.5 at 2**18.  The folds and nunique fuse whole, and run slower chunked
# (sum 37.7 against 41.4 ms, mean 43.0 / 66.8, nunique 32.8 / 42.1).
_AXIS1_CHUNK_BYTES = 1 << 25


def _axis1_form(n_cols: int) -> str:
    """``axis1_columns`` or ``axis1_stacked``, from the column count alone;
    noted as the request record's ``reduction_forms``."""
    from modin_tpu.observability import meters

    form = "axis1_columns" if n_cols <= _AXIS1_COLUMNS_MAX else "axis1_stacked"
    if meters.ACCOUNTING_ON:
        meters.note_reduction_form(form)
    return form


@functools.lru_cache(maxsize=None)
def _sorting_network(k: int) -> Tuple[Tuple[int, int], ...]:
    """Comparators ``(i, j)``, ``i < j``, that sort k wires ascending (the
    minimum to ``i``): Batcher's odd-even merge sort over the next power of
    two, pruned to the first k wires.  A pruned comparator would meet a
    +inf pad on wire ``j`` and never swap, so the pads never move."""
    width = 1
    while width < k:
        width *= 2
    pairs: List[Tuple[int, int]] = []

    def merge(lo: int, hi: int, r: int) -> None:
        step = r * 2
        if step < hi - lo:
            merge(lo, hi, step)
            merge(lo + r, hi, step)
            pairs.extend((i, i + r) for i in range(lo + r, hi - r, step))
        else:
            pairs.append((lo, lo + r))

    def sort(lo: int, hi: int) -> None:  # wires lo..hi, both included
        if hi > lo:
            mid = lo + (hi - lo) // 2
            sort(lo, mid)
            sort(mid + 1, hi)
            merge(lo, hi, 1)

    sort(0, width - 1)
    return tuple((i, j) for i, j in pairs if j < k)


def _sort_columns(xs: List[Any]) -> List[Any]:
    """Sort k equal-shape arrays elementwise: output ``j`` holds each row's
    rank-``j`` value.  No NaN may be among them (the caller maps NaN to
    +inf); outputs nobody reads are pruned by XLA with their comparators."""
    import jax.numpy as jnp

    xs = list(xs)
    for i, j in _sorting_network(len(xs)):
        swap = xs[j] < xs[i]
        xs[i], xs[j] = jnp.where(swap, xs[j], xs[i]), jnp.where(swap, xs[i], xs[j])
    return xs


def _row_chunks(fn, cols: Tuple):
    """``fn`` (row-wise, k arrays -> one) over ``cols`` a chunk of
    ``_AXIS1_CHUNK_BYTES`` at a time on a one-shard mesh; the last chunk is
    taken flush with the end and recomputes rows the one before covered, to
    the same values.  On a row-sharded mesh ``fn`` runs whole."""
    import jax
    import jax.numpy as jnp

    from modin_tpu.parallel.mesh import num_row_shards

    n = cols[0].shape[0]
    step = 1 << ((_AXIS1_CHUNK_BYTES // (8 * len(cols))).bit_length() - 1)
    if n <= step or num_row_shards() != 1:
        return fn(cols)
    out = jax.eval_shape(fn, tuple(jax.ShapeDtypeStruct((step,), c.dtype) for c in cols))

    def walk(i, acc):
        start = jnp.minimum(i * step, n - step)
        part = fn(tuple(jax.lax.dynamic_slice(c, (start,), (step,)) for c in cols))
        return jax.lax.dynamic_update_slice(acc, part, (start,))

    return jax.lax.fori_loop(0, -(-n // step), walk, jnp.zeros((n,), out.dtype))


def _fold(op, xs: List[Any]):
    out = xs[0]
    for x in xs[1:]:
        out = op(out, x)
    return out


def _divisor(count: int, dtype):
    """``count`` as a divisor XLA cannot see: a division by a constant is
    rewritten as a multiply by its rounded reciprocal, an ulp off the
    quotient pandas computes."""
    import jax
    import jax.numpy as jnp

    return jax.lax.optimization_barrier(jnp.asarray(count, dtype))


def _axis1_inexact(dtype):
    """The dtype a row-wise mean / var / std / median answers in: pandas
    computes an integer or bool frame's in float64 (jnp's rule would give
    an int32 frame float32), a float frame's in its own dtype."""
    import jax.numpy as jnp

    return dtype if jnp.issubdtype(dtype, jnp.floating) else jnp.dtype(jnp.float64)


def _axis1_columns_fn(op_name: str, skipna: bool, ddof: int):
    """The row-wise reduction over k column arrays: the stacked form's
    semantics (``jnp.<op>`` / ``jnp.nan<op>`` along the stack), folded
    elementwise."""
    import jax.numpy as jnp

    def fn(cols: Tuple):
        k = len(cols)
        common = jnp.result_type(*[c.dtype for c in cols])
        xs = [c.astype(common) for c in cols]
        is_f = jnp.issubdtype(common, jnp.floating)
        skip = is_f and skipna
        if op_name == "count":
            if not is_f:
                return jnp.full(xs[0].shape, k, jnp.int64)
            return _fold(jnp.add, [(~jnp.isnan(x)).astype(jnp.int64) for x in xs])
        nan = [jnp.isnan(x) for x in xs] if is_f else None
        if op_name in ("min", "max"):
            pick = jnp.minimum if op_name == "min" else jnp.maximum
            if not skip:
                return _fold(pick, xs)
            fill = jnp.inf if op_name == "min" else -jnp.inf
            out = _fold(pick, [jnp.where(m, fill, x) for x, m in zip(xs, nan)])
            return jnp.where(_fold(jnp.logical_and, nan), jnp.nan, out)
        if op_name == "sum":
            # jnp.sum's rule: an integer sums in 64 bits of its signedness
            if is_f:
                out_t = common
            elif jnp.issubdtype(common, jnp.unsignedinteger):
                out_t = jnp.dtype(jnp.uint64)
            else:
                out_t = jnp.dtype(jnp.int64)
            if skip:
                xs = [jnp.where(m, 0, x) for x, m in zip(xs, nan)]
            return _fold(jnp.add, [x.astype(out_t) for x in xs])
        if op_name == "median":
            rank = functools.partial(_median_columns, skipna=skipna, common=common)
            return _row_chunks(rank, tuple(xs))
        if op_name not in ("mean", "var", "std"):
            raise ValueError(op_name)
        out_t = _axis1_inexact(common)
        # jnp.mean / jnp.var compute a half-precision input in float32;
        # jnp.nanmean does not
        nan_mean = skip and op_name == "mean"
        comp = out_t if nan_mean else jnp.promote_types(out_t, jnp.float32)
        a = [x.astype(comp) for x in xs]
        if skip:
            valid = [(~m).astype(comp) for m in nan]
            total = _fold(jnp.add, [jnp.where(m, 0, x) for x, m in zip(a, nan)])
            mean = total / _fold(jnp.add, valid)
        else:
            mean = _fold(jnp.add, a) / _divisor(k, comp)
        if op_name == "mean":
            return mean.astype(out_t)
        if skip:
            sq = _fold(
                jnp.add,
                [jnp.square(jnp.where(m, 0, x - mean)) for x, m in zip(a, nan)],
            )
            normalizer = _fold(jnp.add, [(~m).astype(jnp.int64) for m in nan]) - ddof
            bad = normalizer <= 0
            var = jnp.where(bad, jnp.nan, sq) / jnp.where(bad, 1, normalizer).astype(comp)
        elif k - ddof > 0:
            sq = _fold(jnp.add, [jnp.square(x - mean) for x in a])
            var = sq / _divisor(k - ddof, comp)
        else:
            var = jnp.full_like(mean, jnp.nan)
        var = var.astype(out_t)
        return var if op_name == "var" else jnp.sqrt(var)

    return fn


def _median_columns(xs: Tuple, skipna: bool, common):
    """Row medians of k arrays (in their ``common`` dtype) by the sorting
    network, as ``jnp.median`` / ``jnp.nanmedian`` answer them: the two
    middle ranks, converted to the inexact dtype (a monotone map, so
    converting after the sort picks the values converting before it would)
    and halved from their sum.  A float row skips its NaNs under ``skipna``
    (its valid count picks the ranks), else a NaN anywhere gives NaN."""
    import jax.numpy as jnp

    k = len(xs)
    out_t = _axis1_inexact(common)
    nan = None
    if jnp.issubdtype(common, jnp.floating):
        nan = [jnp.isnan(x) for x in xs]
        xs = [jnp.where(m, jnp.inf, x) for x, m in zip(xs, nan)]
    ranked = _sort_columns(xs)
    if nan is None or not skipna:
        lo, hi = ranked[(k - 1) // 2], ranked[k // 2]
    else:
        nv = _fold(jnp.add, [(~m).astype(jnp.int32) for m in nan])
        lo_rank, hi_rank = (nv - 1) // 2, nv // 2
        lo, hi = ranked[0], ranked[0]
        for j in range(1, k):
            lo = jnp.where(lo_rank == j, ranked[j], lo)
            hi = jnp.where(hi_rank == j, ranked[j], hi)
    lo, hi = lo.astype(out_t), hi.astype(out_t)
    out = (lo + hi) * jnp.asarray(0.5, out_t)
    if nan is None:
        return out
    if skipna:
        return jnp.where(nv == 0, jnp.nan, out)
    return jnp.where(_fold(jnp.logical_or, nan), jnp.nan, out)


@functools.lru_cache(maxsize=None)
def _make_axis1_fn(op_name: str, form: str, skipna: bool, ddof: int):
    if form == "axis1_columns":
        return _axis1_columns_fn(op_name, skipna, ddof)
    return _axis1_stacked_fn(op_name, skipna, ddof)


def _axis1_stacked_fn(op_name: str, skipna: bool, ddof: int):
    import jax.numpy as jnp

    def fn(cols: Tuple):
        n_cols = len(cols)
        # pad rows produce garbage values that are sliced off logically
        common = jnp.result_type(*[c.dtype for c in cols])
        is_f = jnp.issubdtype(common, jnp.floating)
        if op_name in ("mean", "var", "std", "median"):
            common = _axis1_inexact(common)
        x = jnp.stack([c.astype(common) for c in cols], axis=0)
        if op_name == "count":
            if is_f:
                return jnp.sum(~jnp.isnan(x), axis=0).astype(jnp.int64)
            return jnp.full((x.shape[1],), n_cols, jnp.int64)
        if not is_f or not skipna:
            reducer = {
                "sum": jnp.sum, "mean": jnp.mean, "min": jnp.min, "max": jnp.max,
                "median": jnp.median,
            }.get(op_name)
            if reducer is not None:
                return reducer(x, axis=0)
            if op_name == "var":
                return jnp.var(x, axis=0, ddof=ddof)
            if op_name == "std":
                return jnp.std(x, axis=0, ddof=ddof)
        reducer = {
            "sum": jnp.nansum, "mean": jnp.nanmean, "min": jnp.nanmin,
            "max": jnp.nanmax, "median": jnp.nanmedian,
        }.get(op_name)
        if reducer is not None:
            return reducer(x, axis=0)
        if op_name == "var":
            return jnp.nanvar(x, axis=0, ddof=ddof)
        if op_name == "std":
            return jnp.nanstd(x, axis=0, ddof=ddof)
        raise ValueError(op_name)

    return fn


def reduce_axis1(
    op_name: str,
    cols: List[Any],
    skipna: bool = True,
    ddof: int = 1,
    cast_bool: bool = False,
) -> Any:
    """Row-wise reduction across columns; returns a padded device 1-D array.

    Accepts deferred LazyExprs like :func:`reduce_columns` (fused tail).  Up
    to ``_AXIS1_COLUMNS_MAX`` columns are reduced as k arrays, wider frames
    through the stacked (k, n) matrix.
    """
    from modin_tpu.ops.lazy import run_fused

    skipna, ddof = bool(skipna), int(ddof)
    inner = _make_axis1_fn(op_name, _axis1_form(len(cols)), skipna, ddof)

    def tail(arrs):
        import jax.numpy as jnp

        if cast_bool:
            arrs = [a.astype(jnp.int64) if a.dtype == jnp.bool_ else a for a in arrs]
        return inner(tuple(arrs))

    return run_fused(
        cols,
        tail_key=("reduce_axis1", op_name, skipna, ddof, bool(cast_bool)),
        tail_builder=tail,
    )


@functools.lru_cache(maxsize=None)
def _jit_idx_minmax(op_name: str, n_cols: int, n: int):
    import jax
    import jax.numpy as jnp

    def fn(cs: Tuple) -> Tuple:
        out = []
        counts = []
        for c in cs:
            is_f = jnp.issubdtype(c.dtype, jnp.floating)
            valid = _valid_mask(c, n)
            if is_f:
                n_valid = jnp.sum(valid & ~jnp.isnan(c))
            else:
                n_valid = jnp.sum(valid)
            counts.append(n_valid)
            if op_name == "idxmin":
                neutral = jnp.inf if is_f else _int_max(c.dtype)
                x = _masked(c, n, neutral)
                x = jnp.where(jnp.isnan(x), jnp.inf, x) if is_f else x
                out.append(jnp.argmin(x))
            else:
                neutral = -jnp.inf if is_f else _int_min(c.dtype)
                x = _masked(c, n, neutral)
                x = jnp.where(jnp.isnan(x), -jnp.inf, x) if is_f else x
                out.append(jnp.argmax(x))
        return tuple(out), tuple(counts)

    return named_jit(fn, "reduce_idx_minmax")


def idx_minmax(op_name: str, cols: List[Any], n: int, skipna: bool = True):
    """(positions, valid_counts) per padded column, NaN-skipping; one fetch."""
    import jax

    positions, counts = _jit_idx_minmax(op_name, len(cols), int(n))(tuple(cols))
    fetched = _engine_materialize((positions, counts))
    return [int(r) for r in fetched[0]], [int(c) for c in fetched[1]]


# --------------------------------------------------------------------- #
# graftsort: sort-shaped reductions (median / quantile / nunique / mode)
# over shared sorted representations and O(n) histogram fast paths
# --------------------------------------------------------------------- #
#
# Three execution strategies per column, planned before dispatch:
#
# - "dict":   the answer is already on the host (dictionary-encoding
#             categories; ops/dictionary.py) — zero device work;
# - "hist":   bounded-range ints and dictionary codes count occurrences
#             with one O(n) scatter-add histogram — no sort, and mode's
#             k_bound cap is dead code here (every modal value falls out
#             of the bin mask);
# - "cached"/"sort": the classic sorted path, but the (sorted, n_valid)
#             prefix is built once per column via ops/sort.sorted_valid
#             and cached on the DeviceColumn (ops/sorted_cache.py), so
#             median + quantile + nunique + mode on one column pay ONE
#             O(n log n) sort, not four.
#
# The substrate-aware choice between running any of this on device and
# declining to the pandas fallback belongs to ops/router.py; the query
# compiler consults it with the planned strategies before calling the
# executors below.


class ColumnPlan(NamedTuple):
    col: Any  # DeviceColumn carrying the values (dictionary codes included)
    strategy: str  # ops/router.py STRATEGIES member
    span: int  # histogram value-bin count (hist strategy only)
    base: int  # histogram base value: bin = value - base (0 for codes)
    n_categories: int  # dict strategy: distinct non-missing count
    has_nan: bool  # dict/code columns: encoding has missing rows


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 1).bit_length()


@functools.lru_cache(maxsize=None)
def _jit_minmax(n_cols: int, n: int):
    """Per-column (min, max) over valid rows — the O(n) histogram
    eligibility probe for bounded-range int columns."""
    import jax

    def fn(cols: Tuple):
        import jax.numpy as jnp

        out = []
        for c in cols:
            if c.dtype == jnp.bool_:
                c = c.astype(jnp.int8)
            if c.shape[0] == n:
                out.append((jnp.min(c), jnp.max(c)))
            else:
                valid = _valid_mask(c, n)
                out.append(
                    (
                        jnp.min(jnp.where(valid, c, _int_max(c.dtype))),
                        jnp.max(jnp.where(valid, c, _int_min(c.dtype))),
                    )
                )
        return tuple(out)

    return named_jit(fn, "reduce_minmax")


def plan_sort_reduce(op: str, specs: List[dict], n: int) -> List[ColumnPlan]:
    """One :class:`ColumnPlan` per column spec for a sort-shaped ``op``.

    ``specs`` entries are ``{"col": DeviceColumn}`` for numeric columns or
    ``{"col": codes, "n_categories": k, "has_nan": b}`` for
    dictionary-encoded ones.  Bounded-range int columns are probed (one
    fused min/max jit + one scalar fetch) for histogram eligibility under
    ``MODIN_TPU_KERNEL_ROUTER_HIST_BOUND``; columns with a live sorted
    representation plan as "cached".
    """
    from modin_tpu.config import KernelRouterHistBound
    from modin_tpu.ops import sorted_cache

    hist_bound = int(KernelRouterHistBound.get())
    hist_ok = op in ("nunique", "mode")
    plans: List[Any] = [None] * len(specs)
    probe: List[int] = []
    for i, spec in enumerate(specs):
        col = spec["col"]
        if "n_categories" in spec:
            k = int(spec["n_categories"])
            has_nan = bool(spec["has_nan"])
            if op == "nunique":
                plans[i] = ColumnPlan(col, "dict", 0, 0, k, has_nan)
            elif hist_ok and k + 2 <= hist_bound:
                # span floor 1: an all-missing column factorizes to empty
                # categories (k=0), and a zero-size value-bin slice would
                # make the kernel's max reduction trace-fail
                plans[i] = ColumnPlan(col, "hist", max(k, 1), 0, k, has_nan)
            elif sorted_cache.peek(col):
                plans[i] = ColumnPlan(col, "cached", 0, 0, k, has_nan)
            else:
                plans[i] = ColumnPlan(col, "sort", 0, 0, k, has_nan)
            continue
        if sorted_cache.peek(col):
            plans[i] = ColumnPlan(col, "cached", 0, 0, 0, False)
        elif hist_ok and col.pandas_dtype.kind in "biu":
            probe.append(i)
        else:
            plans[i] = ColumnPlan(col, "sort", 0, 0, 0, False)
    if probe:
        ranges = _engine_materialize(
            _jit_minmax(len(probe), int(n))(
                tuple(specs[i]["col"].data for i in probe)
            )
        )
        for i, (cmin, cmax) in zip(probe, ranges):
            cmin, cmax = int(cmin), int(cmax)
            span = cmax - cmin + 1
            if 0 < span <= hist_bound:
                plans[i] = ColumnPlan(
                    specs[i]["col"], "hist", span, cmin, 0, False
                )
            else:
                plans[i] = ColumnPlan(specs[i]["col"], "sort", 0, 0, 0, False)
    return plans


def _sorted_inputs(plans: List[ColumnPlan], n: int) -> dict:
    """{plan index: (sorted values, n_valid)} for every sorted-strategy
    plan; missing representations are built in ONE batched jit and cached
    on their columns."""
    from modin_tpu.observability import spans as graftscope
    from modin_tpu.ops import sorted_cache
    from modin_tpu.ops.sort import sorted_valid_columns

    reps: dict = {}
    missing: List[Tuple[int, Any]] = []
    for i, p in enumerate(plans):
        if p.strategy not in ("cached", "sort"):
            continue
        got = sorted_cache.get(p.col)
        if got is None:
            missing.append((i, p.col))
        else:
            reps[i] = got
    if missing:
        with graftscope.span(
            "sortcache.build", layer="QUERY-COMPILER", cols=len(missing)
        ):
            built = None
            from modin_tpu.ops import router

            if router.decide_layout("sort", int(n)) == "sharded":
                # graftmesh: build the reps through the all_to_all shuffle
                # (bit-identical representation); any decline (skew,
                # single shard) falls back to the one-jit local build
                from modin_tpu.ops import spmd

                built = spmd.sharded_sorted_valid_columns(
                    [c.data for _, c in missing], int(n)
                )
            if built is None:
                built = sorted_valid_columns(
                    [c.data for _, c in missing], int(n)
                )
        for (i, col), pair in zip(missing, built):
            sorted_cache.attach(col, pair[0], pair[1])
            reps[i] = pair
    return reps


@functools.lru_cache(maxsize=None)
def _jit_nunique_sorted(n_pairs: int, n: int, dropna: bool):
    import jax

    def fn(pairs: Tuple):
        import jax.numpy as jnp

        out = []
        for xs, n_valid in pairs:
            is_f = jnp.issubdtype(xs.dtype, jnp.floating)
            idx = jnp.arange(xs.shape[0])
            firsts = jnp.concatenate([jnp.ones(1, bool), xs[1:] != xs[:-1]])
            count = jnp.sum(firsts & (idx < n_valid))
            if is_f and not dropna:
                count = count + (n_valid < n).astype(count.dtype)
            out.append(count)
        return tuple(out)

    return named_jit(fn, "reduce_nunique_sorted")


def _quantile_from_sorted(xs, n_valid, qs, interpolation: str):
    """Quantiles of one column's (sorted, n_valid) representation — the
    single interpolation implementation behind both the quantile and the
    median kernels."""
    import jax.numpy as jnp

    is_f = jnp.issubdtype(xs.dtype, jnp.floating)
    # fractional position of each q over the valid prefix
    pos = qs * jnp.maximum(n_valid - 1, 0).astype(jnp.float64)
    lo = jnp.floor(pos).astype(jnp.int64)
    hi = jnp.ceil(pos).astype(jnp.int64)
    if interpolation in ("lower", "higher", "nearest"):
        # pandas keeps the ORIGINAL dtype value exactly (int64 results
        # stay int64) — select without a float cast
        if interpolation == "lower":
            idx = lo
        elif interpolation == "higher":
            idx = hi
        else:  # nearest: numpy half-to-even
            idx = jnp.round(pos).astype(jnp.int64)
        v = jnp.take(xs, idx)
        if is_f:
            v = jnp.where(n_valid > 0, v, jnp.nan)
        return v
    xs64 = xs.astype(jnp.float64)
    vlo = jnp.take(xs64, lo)
    vhi = jnp.take(xs64, hi)
    if interpolation == "linear":
        v = vlo + (vhi - vlo) * (pos - lo)
    else:  # midpoint
        v = (vlo + vhi) / 2.0
    return jnp.where(n_valid > 0, v, jnp.nan)


@functools.lru_cache(maxsize=None)
def _jit_quantile_sorted(n_pairs: int, n_q: int, interpolation: str):
    import jax

    def fn(pairs: Tuple, qs):
        return tuple(
            _quantile_from_sorted(xs, n_valid, qs, interpolation)
            for xs, n_valid in pairs
        )

    return named_jit(fn, "reduce_quantile_sorted")


@functools.lru_cache(maxsize=None)
def _jit_median_sorted(n_pairs: int, n: int, skipna: bool):
    import jax

    def fn(pairs: Tuple):
        import jax.numpy as jnp

        qs = jnp.asarray([0.5], jnp.float64)
        out = []
        for xs, n_valid in pairs:
            v = _quantile_from_sorted(xs, n_valid, qs, "linear")[0]
            v = v.astype(jnp.float64)
            if not skipna:
                # pandas: median(skipna=False) is NaN when any NaN present
                v = jnp.where(n_valid < n, jnp.nan, v)
            out.append(v)
        return tuple(out)

    return named_jit(fn, "reduce_median_sorted")


@functools.lru_cache(maxsize=None)
def _jit_mode_sorted(n_pairs: int, k_bound: int):
    import jax

    def fn(pairs: Tuple):
        import jax.numpy as jnp

        outs = []
        for xs, n_valid in pairs:
            idx = jnp.arange(xs.shape[0])
            valid = idx < n_valid
            firsts = (
                jnp.concatenate([jnp.ones(1, bool), xs[1:] != xs[:-1]]) & valid
            )
            # run id per element; counts via scatter-add of run starts' spans
            rid = jnp.cumsum(firsts) - 1
            ones = valid.astype(jnp.int64)
            run_counts = jnp.zeros(xs.shape[0], jnp.int64).at[rid].add(ones)
            count_of = run_counts[rid]
            max_count = jnp.max(jnp.where(valid, count_of, 0))
            is_modal = firsts & (count_of == max_count)
            m = jnp.sum(is_modal)
            # gather the modal values (already ascending) into k_bound slots
            pos = jnp.cumsum(is_modal) - 1
            slot = jnp.where(is_modal, pos, k_bound)
            vals = jnp.zeros(k_bound, xs.dtype).at[slot].set(xs, mode="drop")
            outs.append((vals, m))
        return tuple(outs)

    return named_jit(fn, "reduce_mode_sorted")


@functools.lru_cache(maxsize=None)
def _jit_hist(n_cols: int, span_pad: int, n: int, want_mode: bool, dropna: bool):
    """O(n) histogram kernel over ``span_pad`` bins (a shared power of two,
    so data-dependent value ranges cause at most log2(HIST_BOUND)
    recompiles).  Bin layout: [0, span_pad-2) value bins, span_pad-2 the
    NaN bin (dictionary codes / float code columns), span_pad-1 the
    dead-row bin (pads)."""
    import jax

    nan_slot = span_pad - 2
    dead_slot = span_pad - 1

    def fn(cols: Tuple, bases: Tuple):
        import jax.numpy as jnp

        outs = []
        for c, base in zip(cols, bases):
            if c.dtype == jnp.bool_:
                c = c.astype(jnp.int8)
            is_f = jnp.issubdtype(c.dtype, jnp.floating)
            if is_f:
                # dictionary codes: float64 in [0, k) with NaN for missing
                nanm = jnp.isnan(c)
                bins = jnp.where(
                    nanm, nan_slot, jnp.where(nanm, 0.0, c).astype(jnp.int32)
                )
            else:
                bins = (c - base).astype(jnp.int32)
            if c.shape[0] != n:
                bins = jnp.where(_valid_mask(c, n), bins, dead_slot)
            counts = jnp.zeros(span_pad, jnp.int64).at[bins].add(1)
            value_counts = counts[:nan_slot]
            nan_count = counts[nan_slot]
            if not want_mode:
                cnt = jnp.sum(value_counts > 0)
                if is_f and not dropna:
                    cnt = cnt + (nan_count > 0).astype(cnt.dtype)
                outs.append(cnt)
                continue
            max_val = jnp.max(value_counts)
            max_all = (
                max_val if dropna else jnp.maximum(max_val, nan_count)
            )
            mask = (value_counts == max_all) & (value_counts > 0)
            nan_modal = (
                jnp.zeros((), bool)
                if dropna
                else (nan_count == max_all) & (nan_count > 0)
            )
            outs.append((mask, max_all, nan_modal))
        return tuple(outs)

    return named_jit(fn, "reduce_hist")


def _hist_groups(plans: List[ColumnPlan]):
    """(indices, span_pad, cols, bases) for the histogram-strategy plans."""
    import jax.numpy as jnp

    idxs = [i for i, p in enumerate(plans) if p.strategy == "hist"]
    if not idxs:
        return idxs, 0, (), ()
    span_pad = _next_pow2(max(plans[i].span for i in idxs) + 2)
    from modin_tpu.observability import costs as _costs

    if _costs.COST_ON:
        # pow2-padded histogram bins: span_pad slots per column vs the
        # span + NaN + dead slots actually addressed (int64 counts)
        valid = sum(int(plans[i].span) + 2 for i in idxs)
        _costs.note_padding(
            "reductions.hist_bins", len(idxs) * span_pad * 8, valid * 8
        )
    cols = tuple(plans[i].col.data for i in idxs)
    bases = tuple(jnp.asarray(int(plans[i].base)) for i in idxs)
    return idxs, span_pad, cols, bases


def nunique_planned(
    plans: List[ColumnPlan], n: int, dropna: bool = True
) -> List[int]:
    """Distinct-count per planned column: O(1) for dict columns, one O(n)
    histogram for bounded-range ints, sorted adjacent-difference (shared
    sorted rep) for the rest."""
    n, dropna = int(n), bool(dropna)
    results: List[Any] = [None] * len(plans)
    for i, p in enumerate(plans):
        if p.strategy == "dict":
            results[i] = p.n_categories + (0 if dropna else int(p.has_nan))
    sorted_is = [
        i for i, p in enumerate(plans) if p.strategy in ("cached", "sort")
    ]
    if sorted_is:
        reps = _sorted_inputs(plans, n)
        vals = _jit_nunique_sorted(len(sorted_is), n, dropna)(
            tuple(reps[i] for i in sorted_is)
        )
        for i, v in zip(sorted_is, _engine_materialize(vals)):
            results[i] = int(v)
    hist_is, span_pad, cols, bases = _hist_groups(plans)
    if hist_is:
        vals = _jit_hist(len(hist_is), span_pad, n, False, dropna)(cols, bases)
        for i, v in zip(hist_is, _engine_materialize(vals)):
            results[i] = int(v)
    return results


def mode_planned(
    plans: List[ColumnPlan], n: int, dropna: bool = True, k_bound: int = 1024
) -> List[Any]:
    """Per-column modal values, ascending (pandas' order).

    Returns per column either ``(values, nan_modal)`` — a host array of the
    modal values (code indices for dictionary columns; the caller decodes)
    plus whether NaN ties the max count (dropna=False histogram path only)
    — or ``None`` when the column's mode is unrepresentable on device (the
    sorted path's empty/over-``k_bound`` mode set); the caller falls back.
    The histogram path has no such cap: modal values fall out of the bin
    mask, so ``k_bound`` is dead code there.
    """
    n, dropna = int(n), bool(dropna)
    results: List[Any] = [None] * len(plans)
    sorted_is = [
        i for i, p in enumerate(plans) if p.strategy in ("cached", "sort")
    ]
    if sorted_is:
        reps = _sorted_inputs(plans, n)
        fetched = _engine_materialize(
            _jit_mode_sorted(len(sorted_is), int(k_bound))(
                tuple(reps[i] for i in sorted_is)
            )
        )
        for i, (vals, m) in zip(sorted_is, fetched):
            m = int(m)
            if 0 < m <= int(k_bound):
                results[i] = (np.asarray(vals[:m]), False)
    hist_is, span_pad, cols, bases = _hist_groups(plans)
    if hist_is:
        fetched = _engine_materialize(
            _jit_hist(len(hist_is), span_pad, n, True, dropna)(cols, bases)
        )
        for i, (mask, max_all, nan_modal) in zip(hist_is, fetched):
            nan_modal = bool(nan_modal)
            if int(max_all) <= 0 and not nan_modal:
                # all-missing under dropna: empty mode set, like the
                # sorted path — the caller falls back to pandas
                continue
            values = np.nonzero(np.asarray(mask))[0].astype(np.int64) + int(
                plans[i].base
            )
            results[i] = (values, nan_modal)
    return results


def quantile_columns(
    cols: List[Any], n: int, qs: List[float], interpolation: str = "linear"
) -> list:
    """Quantiles per device COLUMN (not raw array: the shared sorted
    representation caches on the column) -> list of (n_q,) host arrays,
    each in its pandas result dtype: float64 for 'linear'/'midpoint', the
    column's own dtype for the element-selecting interpolations
    ('lower'/'higher'/'nearest' — pandas keeps int64 exact there).  An
    all-NaN/empty int column cannot carry NaN; the QC gate guarantees n>0
    and int columns are never NaN."""
    import jax.numpy as jnp

    plans = [ColumnPlan(c, "sort", 0, 0, 0, False) for c in cols]
    reps = _sorted_inputs(plans, int(n))
    fn = _jit_quantile_sorted(len(cols), len(qs), str(interpolation))
    results = fn(
        tuple(reps[i] for i in range(len(cols))), jnp.asarray(qs, jnp.float64)
    )
    return [np.asarray(r) for r in _engine_materialize(results)]


def median_columns(cols: List[Any], n: int, skipna: bool = True) -> list:
    """Median per device column over the shared sorted representation;
    pandas semantics including ``skipna=False`` (any NaN -> NaN)."""
    plans = [ColumnPlan(c, "sort", 0, 0, 0, False) for c in cols]
    reps = _sorted_inputs(plans, int(n))
    results = _jit_median_sorted(len(cols), int(n), bool(skipna))(
        tuple(reps[i] for i in range(len(cols)))
    )
    return [np.asarray(r) for r in _engine_materialize(results)]


def _axis1_common(cols) -> np.dtype:
    """The numpy common dtype of the columns (pandas' axis-1 upcast rule)."""
    common = np.result_type(*[np.dtype(str(c.dtype)) for c in cols])
    if common.kind == "b":
        common = np.dtype(np.int8)
    return common


def _axis1_matrix(cols, n):
    """Stack padded columns into an (n_pad, k) matrix in their common dtype."""
    import jax.numpy as jnp

    common = _axis1_common(cols)
    return jnp.stack([c.astype(common.name) for c in cols], axis=1)


def _nunique_columns(cols: Tuple, dropna: bool):
    """Row-wise distinct count of k column arrays: the sorting network's
    outputs, then k - 1 adjacent not-equal tests under each row's valid
    count (NaN sorts last, as +inf: the ranks past the count are masked)."""
    import jax.numpy as jnp

    k = len(cols)
    common = _axis1_common(cols)
    xs = [c.astype(common.name) for c in cols]
    nv = None
    if common.kind == "f":
        nan = [jnp.isnan(x) for x in xs]
        nv = _fold(jnp.add, [(~m).astype(jnp.int32) for m in nan])
        xs = [jnp.where(m, jnp.inf, x) for x, m in zip(xs, nan)]
    ranked = _sort_columns(xs)
    # counted in 32 bits: an int64 count is two words, and XLA then splits
    # the network between two fusions that each read every column
    distinct = jnp.ones(xs[0].shape, jnp.int32)
    for j in range(1, k):
        new = ranked[j] != ranked[j - 1]
        if nv is not None:
            new = new & (j < nv)
        distinct = distinct + new.astype(jnp.int32)
    if nv is not None:
        distinct = jnp.where(nv > 0, distinct, 0)
        if not dropna:
            distinct = distinct + (nv < k).astype(jnp.int32)
    return distinct.astype(jnp.int64)


@functools.lru_cache(maxsize=None)
def _jit_nunique_axis1(n_cols: int, n: int, dropna: bool, form: str):
    import jax

    def fn(cols: Tuple):
        import jax.numpy as jnp

        if form == "axis1_columns":
            return _nunique_columns(cols, dropna)
        x = _axis1_matrix(cols, n)
        xs = jnp.sort(x, axis=1)  # NaN sort to the row tail
        k = xs.shape[1]
        if jnp.issubdtype(xs.dtype, jnp.floating):
            nv = jnp.sum(~jnp.isnan(xs), axis=1)
        else:
            nv = jnp.full(xs.shape[0], k, jnp.int64)
        j = jnp.arange(1, k)
        news = (xs[:, 1:] != xs[:, :-1]) & (j[None, :] < nv[:, None])
        distinct = jnp.where(nv > 0, 1 + jnp.sum(news, axis=1), 0)
        if not dropna and jnp.issubdtype(xs.dtype, jnp.floating):
            distinct = distinct + (nv < k).astype(distinct.dtype)
        return distinct.astype(jnp.int64)

    return named_jit(fn, "reduce_nunique_axis1")


def nunique_axis1(cols: List[Any], n: int, dropna: bool = True) -> Any:
    """Row-wise distinct count across columns -> padded device int64 array.

    Sorted-row adjacent-difference: one jit, no per-row Python; up to
    ``_AXIS1_COLUMNS_MAX`` columns sorted by a compare-exchange network over
    the column arrays, wider frames stacked and sorted along the row.
    Parity target: pandas ``DataFrame.nunique(axis=1)`` (reference routes it
    through a full-axis fold, modin/core/storage_formats/pandas/
    query_compiler.py)."""
    form = _axis1_form(len(cols))
    return _jit_nunique_axis1(len(cols), int(n), bool(dropna), form)(tuple(cols))


@functools.lru_cache(maxsize=None)
def _jit_mode_axis1(n_cols: int, n: int):
    import jax

    def fn(cols: Tuple):
        import jax.numpy as jnp

        x = _axis1_matrix(cols, n)
        nrow, k = x.shape
        is_f = jnp.issubdtype(x.dtype, jnp.floating)
        xs = jnp.sort(x, axis=1)  # NaN to the row tail
        if is_f:
            nv = jnp.sum(~jnp.isnan(xs), axis=1)  # valid count per row
        else:
            nv = jnp.full(nrow, k, jnp.int64)
        j = jnp.arange(k)
        valid = j[None, :] < nv[:, None]
        firsts = (
            jnp.concatenate(
                [jnp.ones((nrow, 1), bool), xs[:, 1:] != xs[:, :-1]], axis=1
            )
            & valid
        )
        rid = jnp.cumsum(firsts, axis=1) - 1
        # run counts without 2-D scatter: O(k) unrolled equality folds
        run_counts = jnp.stack(
            [jnp.sum((rid == q) & valid, axis=1) for q in range(k)], axis=1
        )
        count_of = jnp.take_along_axis(run_counts, jnp.maximum(rid, 0), axis=1)
        max_count = jnp.max(jnp.where(valid, count_of, 0), axis=1)
        is_modal = firsts & (count_of == max_count[:, None])
        m = jnp.sum(is_modal, axis=1)
        pos = jnp.cumsum(is_modal, axis=1) - 1
        slot = jnp.where(is_modal, pos, k)
        rows = jnp.arange(nrow)[:, None]
        # native-dtype output (zero-padded; exact for int64) + a float64
        # NaN-padded view for the ragged case (pandas' upcast)
        vals = jnp.zeros((nrow, k + 1), xs.dtype).at[rows, slot].set(xs)[:, :k]
        placed = jnp.zeros((nrow, k + 1), bool).at[rows, slot].set(True)[:, :k]
        vals_f = jnp.where(placed, vals.astype(jnp.float64), jnp.nan)
        row_ok = jnp.arange(nrow) < n
        m = jnp.where(row_ok, m, 0)
        m_max = jnp.max(m)
        uniform = jnp.all(jnp.where(row_ok, m == m_max, True))
        return vals, vals_f, m_max, uniform

    return named_jit(fn, "reduce_mode_axis1")


def mode_axis1(cols: List[Any], n: int) -> Tuple[Any, Any, int, bool]:
    """Row-wise modes (``dropna=True``): (native-dtype zero-padded matrix,
    float64 NaN-padded matrix, max mode count over valid rows, whether every
    valid row has exactly max_count modes).  The caller takes the native
    matrix when uniform (no padding -> pandas keeps the source dtype) and
    the float64 one otherwise."""
    import jax

    vals, vals_f, m_max, uniform = _jit_mode_axis1(len(cols), int(n))(
        tuple(cols)
    )
    m_max, uniform = _engine_materialize((m_max, uniform))
    return vals, vals_f, int(m_max), bool(uniform)
