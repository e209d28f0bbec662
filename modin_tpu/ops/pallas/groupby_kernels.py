"""Pallas TPU kernels for the groupby hot path.

``bincount``: the histogram that backs factorize's direct-range coding and the
``size``/``count`` aggregations.  XLA lowers ``zeros().at[codes].add(1)`` to a
scatter-add, which serializes badly on TPU (measured ~1s for 1e7 rows); this
kernel instead streams blocks of codes through VMEM and counts them as an
exact contraction on the MXU: an id is cut into two digits, each digit's
one-hot ``[digit ids, t]`` is built on the VPU as bf16, and the two are
contracted over the ``t`` data rows into ``[high, low]`` f32 counts, flushed
into int32 every grid step.  No scatter, no reduction on the VPU, and the
codes are read where they lie (as rows of 128: no padded or re-tiled copy).

Used on the TPU backend for group widths <= ``MAX_GROUPS``; everywhere else
the XLA scatter path stays (CPU scatters are fine).  Interpret mode makes the
kernel testable on CPU.

``limb_dot``: per-group sums of one value column for a few groups, exact, on
the MXU.  A value is cut into byte limbs (integers of at most 255, which bf16
holds exactly), a block's limbs ``[rows, t]`` are contracted with its one-hot
``[G, t]`` over the ``t`` data rows, and the f32 products are flushed into
int32 sums before they can round (255 * 65 536 < 2**24).  What comes back is
``[limb rows, G]`` integer sums, which ``ops/groupby.py`` recombines: integers
by shifts (wrapping as numpy's sums do), floats as an exact fixed-point number
rounded once.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

from modin_tpu.ops._program import named_jit

_LANES = 128
# rows of a bf16 matmul operand a tile of sublanes (and of the limb matrix a
# piece, below)
PIECE_ROWS = 16
# the histogram's view of the codes: rows of 128 (a reshape that moves no
# byte), HIST_STEP_ROWS of them a grid step and HIST_TURN_ROWS a turn of the
# kernel's loop (a turn waits once for the MXU's results, about 0.1 us: at
# 1e8 rows and 100 ids 2 rows a turn take 44 ms, 8 take 12.7, 32 take 5.2,
# 128 take 3.6 and 512 take 3.2); an id is two digits, the low one
# HIST_LOW_IDS wide
HIST_STEP_ROWS = 2048
HIST_TURN_ROWS = 128
HIST_LOW_IDS = 16
# widest range the histogram takes (the wider ones go to the sorted tiles).
# VMEM is no limit here: at 512 ids a turn's one-hots are [128, 32 + 16, 128]
# bf16 (1.5 MB, twice that as the int32 they are packed from) beside two
# buffers of 2048 x 128 codes (2 MB) and [32, 16] counts.  The high digit's
# one-hot grows with the range: 3.7 ms up to 256 ids, 5.6 at 512 (1e8 rows)
MAX_GROUPS = 512


@functools.lru_cache(maxsize=None)
def _build_bincount(n_rows: int, hi_rows: int, interpret: bool):
    """The histogram of ``[n_rows, 128]`` int32 (or uint32) codes as an exact
    contraction on the MXU: int32 ``[hi_rows, HIST_LOW_IDS]``, the count of id
    ``g`` at ``[g // HIST_LOW_IDS, g % HIST_LOW_IDS]``.

    A histogram factors where a sum does not: with ``g = hi * 16 + lo``,
    ``counts[hi, lo] = sum_t onehot(hi_t)[hi] * onehot(lo_t)[lo]``, one
    contraction over the data rows ``t`` of two narrow one-hots (16 + 16
    sublanes a data row for up to 256 ids, 32 + 16 for 512) in place of one
    of 128 sublanes a block of 128 ids against a row of ones: the compares
    that build a one-hot are the VPU's cost, the MXU's is hidden under them.
    Products are 0 or 1 and a step holds fewer than 2**24 rows, so the f32
    partial counts are exact; they are flushed into the int32 output every
    step.  A code outside ``[0, hi_rows * 16)`` has no high digit here and
    counts for nothing.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    low_ids, turn, step_rows = HIST_LOW_IDS, HIST_TURN_ROWS, HIST_STEP_ROWS
    assert hi_rows % PIECE_ROWS == 0 and step_rows % turn == 0
    assert step_rows * _LANES < 1 << 24
    # (constants are numpy int32 by name: with x64 on a Python literal traces
    # as a weak 64-bit value, which Mosaic cannot lower.  ``lax``'s operations
    # where ``jax.numpy``'s would do, and one loop body: a new process traces
    # the kernel before its first request, and ``jnp`` wrappers, traced cold,
    # cost that request 0.1 s)
    i32 = np.int32

    def onehot(digit, n):
        """bf16 ``[turn, n, 128]`` of a ``[turn, 128]`` digit: its ids on the
        sublanes, the data rows on the lanes."""
        shape = (turn, n, _LANES)
        hit = lax.eq(
            lax.broadcast_in_dim(digit, shape, (0, 2)),
            lax.broadcasted_iota(jnp.int32, shape, 1),
        )
        # bf16's 1.0 is 0x3F80: two 32-bit rows pack into one of 16-bit
        # halves in one operation, where a float32 one-hot converted to
        # bf16 takes five
        word = lax.select(hit, lax.full(shape, i32(0x3F80)), lax.full(shape, i32(0)))
        return lax.bitcast_convert_type(lax.convert_element_type(word, jnp.int16), jnp.bfloat16)

    def kernel(codes_ref, out_ref, acc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the last step's block reaches past the codes: its turns stop with
        # them, and the rows of its last turn past them count for nothing
        rows_here = lax.min(i32(step_rows), i32(n_rows) - i * i32(step_rows))

        def contract(j, carry):
            first = pl.multiple_of(j * i32(turn), turn)
            row = first + lax.broadcasted_iota(jnp.int32, (turn, _LANES), 0)
            codes = lax.bitcast_convert_type(codes_ref[pl.ds(first, turn), :], jnp.int32)
            codes = lax.select(row < rows_here, codes, lax.full(codes.shape, i32(-1)))
            # one matmul a row of 128 codes (the batch), contracted over the
            # lanes; the MXU's results are waited for once a turn
            counts = lax.dot_general(
                onehot(lax.shift_right_arithmetic(codes, i32(low_ids.bit_length() - 1)), hi_rows),
                onehot(lax.bitwise_and(codes, i32(low_ids - 1)), low_ids),
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            acc_ref[...] += lax.reduce_sum(counts, (0,))
            return carry

        lax.fori_loop(i32(0), lax.div(rows_here + i32(turn - 1), i32(turn)), contract, i32(0))
        out_ref[...] += acc_ref[...].astype(jnp.int32)

    # index maps must yield int32: with x64 enabled a literal 0 traces as a
    # weak int64 and Mosaic refuses the (i32, i64) index tuple
    zero = np.int32(0)
    vmem = {"memory_space": pltpu.VMEM}
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((hi_rows, low_ids), jnp.int32),
        grid=(-(-n_rows // step_rows),),
        in_specs=[pl.BlockSpec((step_rows, _LANES), lambda i: (i, zero), **vmem)],
        out_specs=pl.BlockSpec((hi_rows, low_ids), lambda i: (zero, zero), **vmem),
        scratch_shapes=[pltpu.VMEM((hi_rows, low_ids), jnp.float32)],
        # every step adds into the one output block
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="groupby_onehot_dot_kernel",
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
    # high digits for every real group (the overflow id needs no slot: what
    # the kernel holds past ``num_groups`` is sliced off, the rest never counted)
    hi_rows = -(-num_groups // (HIST_LOW_IDS * PIECE_ROWS)) * PIECE_ROWS
    # the kernel takes whole rows of 128: 1e8 codes are such
    padded_len = -(-local_len // _LANES) * _LANES
    n_rows = padded_len // _LANES
    call = _build_bincount(n_rows, hi_rows, interpret)

    def local(codes):
        # 32-bit codes go in as they are and a 64-bit code's low word as the
        # split leaves it, unsigned (the kernel reads either as int32): a
        # conversion here would be one more copy of the codes
        c = codes if codes.dtype.itemsize == 4 else codes.astype(jnp.uint32)
        if padded_len > local_len:
            # overflow bucket: padded tail must not count toward any group
            c = jnp.concatenate(
                [c, jnp.full(padded_len - local_len, num_groups, c.dtype)]
            )
        return call(c.reshape(n_rows, _LANES))

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
        return counts_of(codes).reshape(-1)[:num_groups].astype(jnp.int64)

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


def bincount_supported(codes: Any, num_groups: int, as_on_tpu: bool = False) -> bool:
    """Whether the pallas histogram should be used for this input: few enough
    groups, on a TPU (``as_on_tpu``: chosen as if it were, the test hook of
    ``ops/groupby.py``; the kernel then runs in interpret mode)."""
    if num_groups > MAX_GROUPS or num_groups < 1:
        return False
    try:
        platform = "tpu" if as_on_tpu else next(iter(codes.devices())).platform
    except Exception:  # graftlint: disable=EXC-HYGIENE -- device-platform probe; any failure means 'no pallas path'
        return False
    if _row_shards_of(codes) > 1:
        from modin_tpu.parallel.mesh import num_row_shards

        # the sharded form splits the vector evenly over the mesh rows
        if int(codes.shape[0]) % num_row_shards():
            return False
    return platform == "tpu"


# --------------------------------------------------------------------- #
# limb_dot: exact per-group sums as a limbs x one-hot contraction
# --------------------------------------------------------------------- #

# data rows a contraction (the lanes of one row of the kernel's operands) and
# a grid step: the f32 sums of a step stay exact while a step holds at most
# 65 536 rows (a limb is at most 255)
LIMB_LANES = 4096
LIMB_STEP_ROWS = 8
LIMB_BLOCK = LIMB_LANES * LIMB_STEP_ROWS
# limbs a float stream: two pieces, 256 bits of fixed point
FLOAT_PIECES = 2
# rows of an integer column's piece past its byte limbs: one that counts the
# rows, one that counts the set top bits of the last word (negative int32s)
INT_ROW_ONES = 8
INT_ROW_TOP = 9
#: slots of the float kernel's scalar operand: the exponent of the fixed
#: point's unit, then one flag a (stream, piece): whether any bit falls there
META_SLOTS = 8


def limb_rows(layout: tuple) -> int:
    """Rows of the limb matrix of ``layout`` (see :func:`limb_dot_sums`)."""
    kind, n = layout
    return PIECE_ROWS * (FLOAT_PIECES * n + 1) if kind == "float" else PIECE_ROWS


def low_rows(layout: tuple) -> int:
    """Rows of a float layout before its streams' upper limbs: the lower 16
    limbs a stream, then the piece that counts the rows."""
    kind, n = layout
    return PIECE_ROWS * (n + 1) if kind == "float" else PIECE_ROWS


@functools.lru_cache(maxsize=None)
def _build_limb_dot(n_steps: int, g_pad: int, layout: tuple, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kind, n_words = layout
    rows = limb_rows(layout)
    n_low = low_rows(layout)
    t = LIMB_LANES
    has_meta = kind == "float"

    # (constants are int32 / float32 by name: with x64 on a literal traces as
    # a weak 64-bit value, which Mosaic cannot lower)
    i32 = jnp.int32

    def sublane(n_rows):
        return lax.broadcasted_iota(jnp.int32, (n_rows, t), 0)

    def spread(row, n_rows=PIECE_ROWS):
        return jnp.broadcast_to(row, (n_rows, t))

    def to_bf16(x):
        return x.astype(jnp.float32).astype(jnp.bfloat16)

    def int_piece(words):
        """Byte limbs of one or two 32-bit words a row (rows 0..3, 4..7), a
        row of ones and a row of the last word's top bit."""
        k = sublane(PIECE_ROWS)
        src = spread(words[0])
        if n_words == 2:
            src = jnp.where(k < i32(4), src, spread(words[1]))
        limb = lax.shift_right_logical(src, (k & i32(3)) * i32(8)) & i32(255)
        top = lax.shift_right_logical(spread(words[-1]), i32(31))
        extra = jnp.where(
            k == i32(INT_ROW_ONES), i32(1), jnp.where(k == i32(INT_ROW_TOP), top, i32(0))
        )
        return to_bf16(jnp.where(k < i32(4 * n_words), limb, extra))

    def float_parts(word):
        """(mantissa << 8, exponent, sign mask, is a number) of a row of
        float32 bit patterns: the value is ``mantissa * 2**exponent``, the mask
        -1 or 0; a NaN (and an infinity, which never comes here with a sum
        wanted) has mantissa 0."""
        expf = lax.shift_right_logical(word, i32(23)) & i32(255)
        frac = word & i32(0x7FFFFF)
        finite = expf != i32(255)
        mant = jnp.where(expf == i32(0), frac, frac | i32(0x800000))
        mant = jnp.where(finite, mant, i32(0))
        return (
            lax.shift_left(mant, i32(8)),
            jnp.maximum(expf, i32(1)) - i32(150),
            lax.shift_right_arithmetic(word, i32(31)),
            finite | (frac == i32(0)),
        )

    def float_piece(m8, shift, sign, piece):
        """Signed byte limbs ``16 * piece ..`` of ``mantissa << shift``."""
        sh = sublane(PIECE_ROWS) * i32(8) + i32(8 * PIECE_ROWS * piece + 8) - shift
        # (a shift of 32 or more, or under none, is left to the select)
        limb = lax.shift_right_logical(m8, sh) & i32(255)
        within = lax.bitcast_convert_type(sh, jnp.uint32) < jnp.uint32(32)
        return to_bf16((jnp.where(within, limb, i32(0)) ^ sign) - sign)

    def kernel(*refs):
        refs = list(refs)
        meta_ref = refs.pop(0) if has_meta else None
        codes_ref = refs.pop(0)
        word_refs = [refs.pop(0) for _ in range(n_words)]
        out_ref, acc_ref, *part_refs = refs

        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
            if kind != "int":
                part_refs[-1][...] = jnp.zeros_like(part_refs[-1])

        acc_ref[...] = jnp.zeros_like(acc_ref)

        def held(s, piece):
            return meta_ref[0, 1 + FLOAT_PIECES * s + piece] != i32(0)

        if kind == "float":
            any_high = held(0, 1)
            for s in range(1, n_words):
                any_high = any_high | held(s, 1)

        if kind != "int":
            # a float word's parts, for the whole step at once (rows on the
            # sublanes too); a stream with no bit set anywhere is left out
            m8_ref, shift_ref, sign_ref, number_ref, limbs_refs = part_refs
            for s, word_ref in enumerate(word_refs):
                def parts(s=s, word_ref=word_ref):
                    m8, exp, sign, number = float_parts(word_ref[...])
                    if s == 0:
                        number_ref[...] = jnp.where(number, i32(1), i32(0))
                    if kind == "float":
                        m8_ref[s], sign_ref[s] = m8, sign
                        shift_ref[s] = exp - meta_ref[0, 0]

                if kind == "float" and s > 0:
                    pl.when(held(s, 0) | held(s, 1))(parts)
                else:
                    parts()

        def onehot_of(codes, g):
            ids = sublane(_LANES) + i32(g * _LANES)
            return jnp.where(
                spread(codes, _LANES) == ids, jnp.float32(1), jnp.float32(0)
            ).astype(jnp.bfloat16)

        def add_dot(first, last, limbs, onehot, g):
            acc_ref[first:last, g * _LANES:(g + 1) * _LANES] += lax.dot_general(
                limbs, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        def contract(j, slot):
            row = pl.ds(j, 1)
            codes = codes_ref[row, :]
            if kind == "int":
                limbs = int_piece([r[row, :] for r in word_refs])
                for g in range(g_pad // _LANES):
                    add_dot(0, rows, limbs, onehot_of(codes, g), g)
                return
            # the limb matrix is written a piece at a time, the pieces no bit
            # falls in staying zero; ``slot``: one matrix a row of a turn
            k = sublane(PIECE_ROWS)
            limbs_refs[slot, n_low - PIECE_ROWS:n_low, :] = to_bf16(
                jnp.where(k == i32(0), spread(number_ref[row, :]), i32(0))
            )
            if kind == "float":
                for s in range(n_words):
                    for p in range(FLOAT_PIECES):
                        first = p * n_low + s * PIECE_ROWS

                        @pl.when(held(s, p))
                        def _piece(s=s, p=p, first=first):
                            limbs_refs[slot, first:first + PIECE_ROWS, :] = float_piece(
                                spread(m8_ref[s, row, :]),
                                spread(shift_ref[s, row, :]),
                                spread(sign_ref[s, row, :]),
                                p,
                            )

            def dots(last):
                for g in range(g_pad // _LANES):
                    add_dot(0, last, limbs_refs[slot, 0:last, :], onehot_of(codes, g), g)

            if rows == n_low:
                dots(rows)
            else:
                # the upper limbs: only a wide span of values reaches them
                pl.when(any_high)(lambda: dots(rows))
                pl.when(jnp.logical_not(any_high))(lambda: dots(n_low))

        # two rows a turn of the loop: a row's limbs and one-hot are built
        # while the row before it is on the MXU (all eight unrolled are 4%
        # faster and take four times as long to trace in every new process)
        def pair(jj, carry):
            contract(jj * i32(2), 0)
            contract(jj * i32(2) + i32(1), 1)
            return carry

        lax.fori_loop(i32(0), i32(LIMB_STEP_ROWS // 2), pair, i32(0))
        out_ref[...] += acc_ref[...].astype(jnp.int32)

    zero = np.int32(0)
    vmem = {"memory_space": pltpu.VMEM}
    parts_scratch = []
    if kind != "int":
        streams = pltpu.VMEM((n_words, LIMB_STEP_ROWS, t), jnp.int32)
        parts_scratch = [
            streams, streams, streams,
            pltpu.VMEM((LIMB_STEP_ROWS, t), jnp.int32),
            pltpu.VMEM((2, rows, t), jnp.bfloat16),
        ]
    data_spec = pl.BlockSpec((LIMB_STEP_ROWS, t), lambda i: (i, zero), **vmem)
    in_specs = [data_spec] * (1 + n_words)
    if has_meta:
        meta_spec = pl.BlockSpec(
            (1, META_SLOTS), lambda i: (zero, zero), memory_space=pltpu.SMEM
        )
        in_specs = [meta_spec] + in_specs
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, g_pad), jnp.int32),
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, g_pad), lambda i: (zero, zero), **vmem),
        scratch_shapes=[pltpu.VMEM((rows, g_pad), jnp.float32)] + parts_scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="groupby_limb_dot_kernel",
    )


def limb_dot_sums(codes, words, meta, layout: tuple, num_segments: int, interpret: bool):
    """Per-group integer sums of the limb rows of one stretch of rows.

    ``codes`` (int32) and each of ``words`` (int32 bit patterns) have
    ``[n_steps * LIMB_STEP_ROWS, LIMB_LANES]`` entries, one a data row, a code
    of ``num_segments - 1`` or more counting for nothing that is kept.
    ``layout`` says what a word is:

    - ``("int", w)``: ``w`` words (1 or 2, low word first) of an integer; rows
      ``0 .. 4w`` of the result are the sums of its bytes, row ``INT_ROW_ONES``
      the row count, row ``INT_ROW_TOP`` the count of set top bits;
    - ``("float", s)``: ``s`` float32 streams whose sum is the value; of the
      signed bytes of ``value / 2**meta[0]`` stream ``i`` sums the lower 16 in
      rows ``16 i .. 16 i + 16`` and the upper 16 in rows ``low_rows + 16 i
      ..``; row ``16 s`` counts the rows whose first stream is no NaN.  ``meta``
      (int32 ``[1, META_SLOTS]``) also flags the (stream, piece) pairs that
      hold any bit: the others are neither computed nor contracted;
    - ``("valid", 1)``: row 0 counts the rows whose float32 word is no NaN.

    Returns int32 ``[limb_rows(layout), g_pad]``.
    """
    g_pad = -(-num_segments // _LANES) * _LANES
    n_steps = codes.shape[0] // LIMB_STEP_ROWS
    call = _build_limb_dot(n_steps, g_pad, layout, bool(interpret))
    operands = ([meta] if layout[0] == "float" else []) + [codes, *words]
    return call(*operands)
