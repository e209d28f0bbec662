"""Structural device kernels: pad-aware gather, slice, and concat.

Device columns are padded to a multiple of the mesh row-shard count so
``device_put``/jit keep the rows axis sharded (XLA requires even shards for
explicitly laid-out arrays; uneven results fall back to replication).  Every
kernel here receives the **logical** lengths statically and never reads pad
rows.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from modin_tpu.observability import costs as _costs
from modin_tpu.ops._program import named_jit

#: graftfuse adaptive padding: while a quantizer is installed on this
#: thread, ``pad_host`` rounds its padded length up through it, so a scan
#: whose plan signature the compile ledger reports as a recompile storm
#: uploads at a shared bucket size instead of an exact one.  Scoped (the
#: fused lowering wraps ONLY its leaf-scan lowering) and thread-local, so
#: nothing else in the process ever sees a quantized pad.
_bucket_tls = threading.local()


@contextlib.contextmanager
def pad_bucket_scope(quantizer: Optional[Callable[[int], int]]):
    """Install ``quantizer`` (padded length -> bucketed padded length) for
    ``pad_host`` calls on this thread; ``None`` is a no-op scope."""
    if quantizer is None:
        yield
        return
    prev = getattr(_bucket_tls, "quantize", None)
    _bucket_tls.quantize = quantizer
    try:
        yield
    finally:
        _bucket_tls.quantize = prev


def float_total_order(x):
    """Monotone float -> int64 mapping with a strict IEEE total order.

    -0.0 == 0.0, every NaN maps to one key ABOVE +inf (so NaN sorts strictly
    after inf instead of tying with it), and ordering elsewhere matches <.
    Shared by the sort and join kernels.
    """
    import jax
    import jax.numpy as jnp

    # canonicalize: XLA folds x+0.0 to x, so -0.0 needs an explicit where
    x = jnp.where(x == 0, 0.0, x)
    x = jnp.where(jnp.isnan(x), jnp.nan, x)
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float64), jnp.int64)
    return jnp.where(bits >= 0, bits, (~bits) ^ np.int64(-(2**63)))


def pad_len(n: int) -> int:
    """Smallest multiple of the mesh row-shard count >= n (and >= 1 shard)."""
    from modin_tpu.parallel.mesh import num_row_shards

    s = num_row_shards()
    return max(((n + s - 1) // s) * s, s)


def pad_host(values: np.ndarray, n: int | None = None) -> np.ndarray:
    """Pad a host array with zeros to the sharded length (quantized up to
    the active graftfuse pad bucket when one is installed)."""
    n = len(values) if n is None else n
    p = pad_len(n)
    quantize = getattr(_bucket_tls, "quantize", None)
    if quantize is not None:
        # re-run pad_len so a quantizer that answers off the shard grid
        # still lands on an even shard split
        p = pad_len(max(p, int(quantize(p))))
    if _costs.COST_ON:
        _costs.note_padding(
            "structural.pad_host",
            p * values.dtype.itemsize,
            len(values) * values.dtype.itemsize,
        )
    if len(values) == p:
        return values
    pad_block = np.zeros(p - len(values), dtype=values.dtype)
    return np.concatenate([values, pad_block])


@functools.lru_cache(maxsize=None)
def _jit_gather(n_cols: int):
    import jax
    import jax.numpy as jnp

    def fn(cols: Tuple, positions):
        return tuple(jnp.take(c, positions, axis=0) for c in cols)

    return named_jit(fn, "structural_gather")


def compact_rows(cols: List[Any], mask: Any, n: int) -> Tuple[List[Any], Any, Any]:
    """Device-side boolean-filter: kept rows compacted to the front.

    ``cols``/``mask`` may be deferred LazyExprs — the mask computation (e.g.
    ``df.a > 0``) fuses into the compaction program.  Returns (gathered
    columns, kept-count scalar, kept-positions array), all still on device:
    the only host sync a filter needs is the scalar count (one small fetch,
    versus shipping an O(n) mask to host and positions back).
    Outputs keep the input padded size; pad rows land at the tail.
    """
    from modin_tpu.ops.lazy import run_fused

    def tail(arrs):
        import jax.numpy as jnp

        *col_arrs, m = arrs
        valid = jnp.arange(m.shape[0]) < n
        keep = m & valid
        # stable argsort of "dropped" puts kept rows first, original order
        perm = jnp.argsort(~keep, stable=True)
        count = jnp.sum(keep)
        return tuple(jnp.take(c, perm, axis=0) for c in col_arrs), count, perm

    return run_fused(
        [*cols, mask],
        tail_key=("compact_rows", len(cols), int(n)),
        tail_builder=tail,
    )


def gather_columns(cols: List[Any], positions: np.ndarray) -> Tuple[List[Any], int]:
    """Gather logical positions from padded columns.

    Returns (new padded device arrays, logical length).  The positions array
    is itself padded with 0 so the gather output stays evenly sharded.
    """
    from modin_tpu.parallel.engine import JaxWrapper

    n_out = len(positions)
    padded = pad_host(np.asarray(positions, dtype=np.int64), n_out)
    device_positions = JaxWrapper.put(padded)
    return (
        list(
            JaxWrapper.deploy(
                _jit_gather(len(cols)), (tuple(cols), device_positions)
            )
        ),
        n_out,
    )


def gather_columns_device(cols: List[Any], device_positions: Any) -> List[Any]:
    """Gather with an already-padded device positions array."""
    from modin_tpu.parallel.engine import JaxWrapper

    return list(
        JaxWrapper.deploy(_jit_gather(len(cols)), (tuple(cols), device_positions))
    )


@functools.lru_cache(maxsize=None)
def _jit_concat(n_parts: int, n_cols: int, lengths: Tuple[int, ...], p_out: int):
    import jax
    import jax.numpy as jnp

    def fn(parts: Tuple[Tuple, ...]):
        # parts[i] is the tuple of columns of part i (all padded)
        offsets = []
        off = 0
        for i in range(n_parts):
            offsets.append(off)
            off += parts[i][0].shape[0]
        # positions into the naive concatenation that skip the pads
        pos_list = [
            jnp.arange(lengths[i], dtype=jnp.int64) + offsets[i]
            for i in range(n_parts)
        ]
        total = sum(lengths)
        pos = jnp.concatenate(pos_list + [jnp.zeros(p_out - total, jnp.int64)])
        out = []
        for ci in range(n_cols):
            big = jnp.concatenate([parts[i][ci] for i in range(n_parts)])
            out.append(jnp.take(big, pos, axis=0))
        return tuple(out)

    return named_jit(fn, "structural_concat")


#: tail appends at least this many times smaller than the prefix take the
#: micro-batch fast path (graftfeed ingest: a 1k-row batch onto a 10M-row
#: feed must not re-gather all 10M rows).  Module-level so the ingest bench
#: can disable the fast path to measure the win honestly.
_APPEND_FASTPATH_RATIO = 8


@functools.lru_cache(maxsize=None)
def _jit_tail_append(n_cols: int, p_out: int):
    """Append a small tail onto a large prefix WITHOUT the gather re-layout
    of ``_jit_concat``: the prefix is copied once into the grown buffer
    (a contiguous memcpy XLA fuses, not an O(p_out) dynamic-index take) and
    the tail rows are placed at ``[start, start + tail_n)`` via roll+where.
    ``start``/``tail_n`` are dynamic scalars, so the compiled program is
    keyed only on the padded shapes — consecutive micro-batch appends that
    land inside the same pad bucket reuse it."""
    import jax
    import jax.numpy as jnp

    def fn(prefix: Tuple, tail: Tuple, start, tail_n):
        idx = jnp.arange(p_out, dtype=jnp.int64)
        in_tail = (idx >= start) & (idx < start + tail_n)
        out = []
        for ci in range(n_cols):
            big = prefix[ci]
            grown = jnp.zeros((p_out,), big.dtype).at[: big.shape[0]].set(big)
            t = tail[ci]
            tpad = jnp.zeros((p_out,), t.dtype).at[: t.shape[0]].set(t)
            # no wrap in the selected region: start + tail_n <= p_out
            rolled = jnp.roll(tpad, start, axis=0)
            out.append(jnp.where(in_tail, rolled, grown))
        return tuple(out)

    return named_jit(fn, "structural_tail_append")


def concat_columns(parts: List[List[Any]], lengths: List[int]) -> Tuple[List[Any], int]:
    """Row-concat column sets (each padded), producing padded outputs."""
    from modin_tpu.logging.metrics import emit_metric
    from modin_tpu.parallel.engine import JaxWrapper

    n_out = sum(lengths)
    p_out = pad_len(n_out)
    if (
        len(parts) == 2
        and lengths[1] > 0
        and lengths[1] * _APPEND_FASTPATH_RATIO <= lengths[0]
        and all(getattr(c, "ndim", 0) == 1 for p in parts for c in p)
        # physical sizes may exceed the minimal pad (graftfuse pad buckets)
        and all(c.shape[0] <= p_out for p in parts for c in p)
    ):
        fn = _jit_tail_append(len(parts[0]), p_out)
        out = list(
            JaxWrapper.deploy(
                fn,
                (
                    tuple(parts[0]),
                    tuple(parts[1]),
                    np.int64(lengths[0]),
                    np.int64(lengths[1]),
                ),
            )
        )
        emit_metric("structural.append_fastpath", 1)
        return out, n_out
    fn = _jit_concat(len(parts), len(parts[0]), tuple(lengths), p_out)
    return list(JaxWrapper.deploy(fn, (tuple(tuple(p) for p in parts),))), n_out


