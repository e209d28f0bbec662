"""Pallas TPU kernels for the groupby hot path.

``bincount``: the histogram that backs factorize's direct-range coding and the
``size``/``count`` aggregations.  XLA lowers ``zeros().at[codes].add(1)`` to a
scatter-add, which serializes badly on TPU (measured ~1s for 1e7 rows); this
kernel instead streams code blocks through VMEM and accumulates a one-hot
compare on the VPU — O(n*G) elementwise work with no scatter, exact int32
arithmetic.

Used on the TPU backend for group widths <= ``MAX_GROUPS``; everywhere else
the XLA scatter path stays (CPU scatters are fine).  Interpret mode makes the
kernel testable on CPU.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

from modin_tpu.ops._program import named_jit

# block of codes processed per grid step: BR sublanes x 128 lanes
_BR = 32
_LANES = 128
MAX_GROUPS = 512  # one-hot block is BR*128*ceil(G/128)*128 ints in VMEM


@functools.lru_cache(maxsize=None)
def _build_bincount(n_blocks: int, g_padded: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from jax.experimental.pallas import tpu as pltpu

    def kernel(codes_ref, out_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        codes_block = codes_ref[:]  # [_BR, _LANES] int32
        group_ids = jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, g_padded), dimension=2
        )
        onehot = (codes_block[:, :, None] == group_ids).astype(jnp.int32)
        # pin the accumulation dtype: with x64 enabled jnp.sum follows numpy
        # and widens int32 sums to int64, which TPU pallas cannot lower
        partial = jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32)  # [g_padded]
        out_ref[0, :] += partial

    block_spec_kwargs = {"memory_space": pltpu.VMEM}
    # index maps must yield int32: with x64 enabled a literal 0 traces as a
    # weak int64 and Mosaic refuses the (i32, i64) index tuple
    zero = np.int32(0)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, g_padded), jnp.int32),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((_BR, _LANES), lambda i: (i, zero), **block_spec_kwargs)
        ],
        out_specs=pl.BlockSpec(
            (1, g_padded), lambda i: (zero, zero), **block_spec_kwargs
        ),
        interpret=interpret,
        name="groupby_bincount_kernel",
    )


def _bincount_fn(p_len: int, num_groups: int, interpret: bool, mesh: Any = None):
    """The (unjitted) histogram program over a length-``p_len`` code vector.

    ``mesh=None`` is the single-device program.  With a mesh, the kernel runs
    under ``shard_map`` over the "rows" axis — Mosaic kernels cannot be
    partitioned automatically, so a row-sharded operand in a plain ``jit``
    is refused by the TPU compiler — each shard histograms its own
    ``p_len / S`` codes and one ``psum`` adds the partials.
    """
    import jax
    import jax.numpy as jnp

    n_shards = 1 if mesh is None else int(mesh.shape["rows"])
    local_len = p_len // n_shards
    # slots for every real group + the overflow bucket, padded to lanes
    g_padded = max(-(-(num_groups + 1) // _LANES) * _LANES, _LANES)
    block_elems = _BR * _LANES
    n_blocks = -(-local_len // block_elems)
    padded_len = n_blocks * block_elems
    call = _build_bincount(n_blocks, g_padded, interpret)

    def local(codes):
        c = codes.astype(jnp.int32)
        if padded_len > local_len:
            # overflow bucket: padded tail must not count toward any group
            c = jnp.concatenate(
                [c, jnp.full(padded_len - local_len, num_groups, jnp.int32)]
            )
        return call(c.reshape(n_blocks * _BR, _LANES))

    if mesh is None:
        counts_of = local
    else:
        from jax.sharding import PartitionSpec as P

        from modin_tpu.parallel.jax_compat import shard_map

        def local_then_psum(codes):
            return jax.lax.psum(local(codes), "rows")

        counts_of = shard_map(
            local_then_psum,
            mesh=mesh,
            in_specs=P("rows"),
            out_specs=P(),
            check_vma=False,
        )

    def fn(codes):
        return counts_of(codes)[0, :num_groups].astype(jnp.int64)

    return fn


@functools.lru_cache(maxsize=None)
def _jit_bincount_wrapper(
    p_len: int, num_groups: int, interpret: bool, mesh_key: str = ""
):
    """``mesh_key`` is "" for an operand on one device, else the live mesh's
    shape key (cache key only, like ``shuffle._jit_shuffle``: the program
    closes over the mesh captured here)."""
    import jax

    mesh = None
    if mesh_key:
        from modin_tpu.parallel.mesh import get_mesh

        mesh = get_mesh()
    return named_jit(
        _bincount_fn(p_len, num_groups, interpret, mesh), "groupby_pallas_bincount"
    )


def _row_shards_of(codes: Any) -> int:
    """How many devices ``codes`` is laid out over (1 when unsharded)."""
    sharding = getattr(codes, "sharding", None)
    return len(sharding.device_set) if sharding is not None else 1


def pallas_bincount(codes: Any, num_groups: int, interpret: bool = False) -> Any:
    """Counts per group code; codes >= num_groups (pads/overflow) are dropped.

    Returns an int64 device array of length ``num_groups``.
    """
    if num_groups > MAX_GROUPS:
        raise ValueError(f"pallas_bincount supports <= {MAX_GROUPS} groups")
    mesh_key = ""
    if _row_shards_of(codes) > 1:
        from modin_tpu.parallel.mesh import mesh_shape_key

        mesh_key = mesh_shape_key()
    return _jit_bincount_wrapper(
        int(codes.shape[0]), int(num_groups), bool(interpret), mesh_key
    )(codes)


def bincount_supported(codes: Any, num_groups: int) -> bool:
    """Whether the pallas histogram should be used for this input."""
    if num_groups > MAX_GROUPS or num_groups < 1:
        return False
    try:
        platform = next(iter(codes.devices())).platform
    except Exception:  # graftlint: disable=EXC-HYGIENE -- device-platform probe; any failure means 'no pallas path'
        return False
    if _row_shards_of(codes) > 1:
        from modin_tpu.parallel.mesh import num_row_shards

        # the sharded form splits the vector evenly over the mesh rows
        if int(codes.shape[0]) % num_row_shards():
            return False
    return platform == "tpu"
