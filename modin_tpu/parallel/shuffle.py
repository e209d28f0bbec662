"""Range-partitioning shuffle over ICI collectives.

TPU-native re-design of the reference's shuffle machinery
(modin/core/dataframe/pandas/partitioning/partition_manager.py:1937
``shuffle_partitions`` + modin/core/dataframe/pandas/dataframe/utils.py:111
``ShuffleSortFunctions``): the same sample -> quantile-pivots -> split ->
recombine algorithm, but the "split every partition into bins + re-concat"
step is a single ``lax.all_to_all`` over the mesh rows axis inside
``shard_map`` instead of a task fan-out through an object store.

Steps (for ``sort_by``-style redistribution of rows by a key):
1. sample the key column on device, compute S-1 quantile pivots on host;
2. inside shard_map: bucketize each local row (searchsorted on pivots),
   scatter rows into a [S, C] send buffer (C = per-destination capacity with
   slack), ``all_to_all`` so shard s receives every sender's bucket-s rows,
   then locally move valid rows to a prefix;
3. rebuild the framework's padded column layout with a device gather driven
   only by the S per-shard counts (no full-mask host transfer); overflow of
   any destination capacity is detected on host and retried with more slack.

The result is *range-partitioned*: shard s holds keys within
(pivot[s-1], pivot[s]]; a local per-shard sort then yields a globally sorted
frame — exactly the reference's recipe, compiled onto the interconnect.
"""

from __future__ import annotations

import functools
from typing import Any, List, Tuple

import numpy as np


from modin_tpu.observability import spans as graftscope
from modin_tpu.parallel.engine import materialize as _engine_materialize
from modin_tpu.ops._program import named_jit


class ShuffleSkewError(RuntimeError):
    """Capacity-slack retries exhausted by pathologically skewed keys.

    Callers catch this specifically (not bare RuntimeError, which would also
    swallow jax XlaRuntimeError device failures) and fall back to a
    non-shuffle path.
    """


@functools.lru_cache(maxsize=None)
def _jit_sample(step: int):
    import jax

    def fn(key):
        return key[::step]

    return named_jit(fn, "shuffle_sample")


def sample_pivots(key: Any, n: int, num_partitions: int, num_samples: int = 4096) -> np.ndarray:
    """Quantile pivots from a strided device sample (one small fetch)."""
    step = max(1, key.shape[0] // num_samples)
    sample = np.asarray(_engine_materialize(_jit_sample(step)(key)))
    positions = np.arange(0, key.shape[0], step)
    sample = sample[positions[: len(sample)] < n]
    if sample.dtype.kind == "f":
        sample = sample[~np.isnan(sample)]
    if len(sample) == 0:
        return np.zeros(max(num_partitions - 1, 1), dtype=sample.dtype)
    qs = np.linspace(0, 1, num_partitions + 1)[1:-1]
    pivots = np.quantile(sample, qs, method="inverted_cdf")
    return np.asarray(pivots, dtype=sample.dtype)


@functools.lru_cache(maxsize=None)
def _jit_shuffle(
    n_cols: int,
    capacity: int,
    n: int,
    descending: bool,
    local_sort: bool = False,
    mesh_key: str = "",
):
    """shard_map kernel: local bucketize+pack, all_to_all, local compaction.

    ``mesh_key`` participates in the cache key only: the compiled program
    closes over the mesh captured at trace time, so a mesh reshape (the
    parity grid reconfigures MeshShape in-process) must never reuse a
    program traced for a different topology.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from modin_tpu.parallel.jax_compat import shard_map

    from modin_tpu.parallel.mesh import get_mesh

    mesh = get_mesh()
    S = mesh.shape["rows"]

    def local_fn(pivots, key_shard, row_valid, *col_shards):
        L = key_shard.shape[0]
        if jnp.issubdtype(key_shard.dtype, jnp.floating):
            k = jnp.where(jnp.isnan(key_shard), jnp.inf, key_shard)
        else:
            k = key_shard
        side = "left" if descending else "right"
        bucket = jnp.searchsorted(pivots, k, side=side)
        if descending:
            bucket = (S - 1) - bucket
            if jnp.issubdtype(key_shard.dtype, jnp.floating):
                # NaN stays last globally (na_position='last') even though
                # the value order is reversed
                bucket = jnp.where(jnp.isnan(key_shard), S - 1, bucket)
        bucket = jnp.where(row_valid[:, 0], bucket, S)  # pads route nowhere
        # stable grouping of local rows by destination
        order = jnp.argsort(bucket, stable=True)
        sorted_bucket = jnp.take(bucket, order)
        ranks = jnp.arange(L) - jnp.searchsorted(
            sorted_bucket, sorted_bucket, side="left"
        )
        ok = (sorted_bucket < S) & (ranks < capacity)
        slot = sorted_bucket * capacity + jnp.minimum(ranks, capacity - 1)
        send_idx = jnp.full((S * capacity,), -1, jnp.int64)
        send_idx = send_idx.at[jnp.where(ok, slot, S * capacity)].set(
            jnp.where(ok, order, -1), mode="drop"
        )
        send_idx = send_idx.reshape(S, capacity)
        overflow = jnp.sum(jnp.where((sorted_bucket < S) & ~ok, 1, 0))

        def route(col):
            safe = jnp.where(send_idx >= 0, send_idx, 0)
            vals = jnp.take(col, safe.reshape(-1), axis=0).reshape(S, capacity)
            recv = jax.lax.all_to_all(
                vals, "rows", split_axis=0, concat_axis=0, tiled=True
            )
            return recv.reshape(-1)  # [S*capacity] rows destined here

        valid_recv = jax.lax.all_to_all(
            send_idx >= 0, "rows", split_axis=0, concat_axis=0, tiled=True
        ).reshape(-1)
        # compact valid rows to a local prefix (stable keeps arrival order)
        payload = [route(key_shard)] + [route(c) for c in col_shards]
        if local_sort:
            # composed stable argsorts: value, then NaN-last, then valid-first.
            # No value sentinels — real +/-inf and NaN keys order exactly like
            # pandas (na_position='last'), and invalid slack slots sort after
            # every valid row regardless of their garbage payload.
            kk = payload[0]
            if jnp.issubdtype(kk.dtype, jnp.floating):
                value_key = jnp.where(jnp.isnan(kk), 0, kk)
                nan_flag = jnp.isnan(kk)
            else:
                value_key = kk
                nan_flag = None
            order = jnp.argsort(value_key, stable=True, descending=descending)
            if nan_flag is not None:
                order = jnp.take(order, jnp.argsort(jnp.take(nan_flag, order), stable=True))
            invalid_sorted = jnp.take(~valid_recv, order)
            local_order = jnp.take(order, jnp.argsort(invalid_sorted, stable=True))
        else:
            local_order = jnp.argsort(~valid_recv, stable=True)
        payload = [jnp.take(p, local_order, axis=0) for p in payload]
        count = jnp.sum(valid_recv).astype(jnp.int64)
        return (
            count[None],
            overflow[None].astype(jnp.int64),
            *payload,
        )

    return named_jit(
        shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(P(), P("rows"), P("rows", None))
            + tuple(P("rows") for _ in range(n_cols)),
            out_specs=(P("rows"), P("rows"))
            + tuple(P("rows") for _ in range(n_cols + 1)),
            check_vma=False,
        ),
        "shuffle_range_shuffle",
    )


def range_shuffle(
    key: Any,
    cols: List[Any],
    n: int,
    descending: bool = False,
    slack: float = 1.6,
    local_sort: bool = False,
    max_slack: float = 64.0,
) -> Tuple[Any, List[Any], np.ndarray, np.ndarray]:
    """Redistribute rows so shard s holds the s-th key range.

    Returns (key_out, cols_out, shard_counts, pivots): padded device columns
    in the framework layout (logical length n), range-partitioned over the
    mesh; rows within a shard keep arrival order (callers sort locally).

    Capacity slack doubles on overflow up to ``max_slack``; past that the
    keys are pathologically skewed and ShuffleSkewError tells the caller to
    take its non-shuffle path (a semantic fallback signal, NOT a device
    failure — see modin_tpu/core/execution/resilience.py's classification).
    """
    import jax.numpy as jnp

    from modin_tpu.logging.metrics import emit_metric
    from modin_tpu.observability import costs as _costs
    from modin_tpu.ops.structural import gather_columns
    from modin_tpu.parallel.mesh import mesh_shape_key, num_row_shards

    with graftscope.span(
        "shuffle.range_shuffle",
        layer="SHUFFLE",
        rows=int(n),
        n_cols=len(cols),
        local_sort=bool(local_sort),
    ) as _sp:
        S = num_row_shards()
        mesh_key = mesh_shape_key()
        P_len = key.shape[0]
        L = P_len // S
        pivots = sample_pivots(key, n, S)
        pivots_dev = jnp.asarray(pivots)
        row_valid = (jnp.arange(P_len) < n)[:, None]

        slack_retries = 0
        while True:
            capacity = int(max(8, int(L / max(S, 1) * slack)))
            fn = _jit_shuffle(
                len(cols), capacity, n, bool(descending), bool(local_sort),
                mesh_key,
            )
            out = fn(pivots_dev, key, row_valid, *cols)
            counts_r, overflow_r = out[0], out[1]
            payload = list(out[2:])
            overflow = int(np.sum(np.asarray(_engine_materialize(overflow_r))))
            if overflow == 0:
                counts = np.asarray(_engine_materialize(counts_r))
                break
            slack *= 2.0
            slack_retries += 1
            emit_metric("resilience.shuffle.slack_retry", 1)
            if slack > max_slack:
                emit_metric("resilience.shuffle.skew_fallback", 1)
                raise ShuffleSkewError("range_shuffle: pathological key skew")

        if _costs.COST_ON:
            # graftcost collective accounting: every routed column moves a
            # [S, capacity] block per shard through the all_to_all (S*S*cap
            # rows total), plus the validity mask (1 byte/slot).  This is
            # the ``engine.cost.collective_bytes`` term the router's
            # sharded-vs-local crossover model is calibrated against.
            slots = S * S * capacity
            payload_bytes = sum(
                slots * c.dtype.itemsize for c in (key, *cols)
            ) + slots
            _costs.note_collective("shuffle.all_to_all", payload_bytes)
        if _sp is not None:
            _sp.attrs["shards"] = S
            _sp.attrs["capacity"] = capacity
            _sp.attrs["slack_retries"] = slack_retries

        assert int(counts.sum()) == n, (counts, n)
        # positions of each shard's valid prefix within the [S * S*capacity] layout
        block = S * capacity
        positions = np.concatenate(
            [s * block + np.arange(c, dtype=np.int64) for s, c in enumerate(counts)]
        ) if len(counts) else np.zeros(0, np.int64)
        compacted, _ = gather_columns(payload, positions)
        return compacted[0], compacted[1:], counts, pivots
