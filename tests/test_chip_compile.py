"""The main path's kernels, compiled at 1e8 rows for a DESCRIBED TPU v5e.

The TPU compiler is installed in the CPU-only sandbox and compiles for a
chip that is described, not attached (on-chip-measurement guide, section 2):
what Mosaic or XLA:TPU would refuse on the machine with the chip (a tile
that does not align, a program that does not fit 16 GB of HBM) is refused
here at no chip time.  Nothing runs, so these tests say nothing about
results or times.

Rules this file keeps (they are why it is ONE file): the topology is
described inside a module-scoped fixture and never at import time, in a
``skipif`` or in ``parametrize`` arguments (only the worker that is handed
this file loads libtpu, and it keeps the lock until it exits); no child
process; the persistent compile cache is off around the compiles (a
described-chip entry cannot be read back without a chip).  Code that asks
``jax.default_backend()`` sees the CPU here, so the jitted builders are
lowered directly over ``ShapeDtypeStruct``s.

Only the fast compiles live here (each a second or two; the many-groups
histogram, which sorts, about five; the tiles' max and spread about 20).  The 1e8-row lexsort (about 70 s) and
the ewm blocked scan (minutes) were compiled once by hand for PR 22, the
sorted-tiles sums (25 s a 64-bit column here, 12 s on the chip's host) for
PR 31; their numbers are in PERF.md.
"""

import os

import pytest

ROWS = 100_000_000
N_COLS = 5
NUM_SEGMENTS = 101  # 100 groups + the overflow bucket
P_OUT = 100  # pad_len(100) on a one-shard mesh
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as err:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {err}")


@pytest.fixture(scope="module")
def shapes(topo):
    """``shape(dims, dtype)`` -> ShapeDtypeStruct on the first described chip,
    with x64 on and the persistent compile cache off for the module."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_x64", True)
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    yield shape
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _lower(kernel: str, arg, shape):
    """Lower one jitted builder of the main path over 1e8-row shapes."""
    import numpy as np

    from modin_tpu.ops import groupby
    from modin_tpu.ops.pallas.groupby_kernels import _jit_bincount_wrapper

    codes = shape((ROWS,), np.int64)
    if kernel == "bincount":  # arg: number of groups
        return _jit_bincount_wrapper(ROWS, arg, False).lower(codes)
    if kernel == "sorted_tiles_size":  # arg: number of groups; the codes come from the key
        return groupby._jit_sorted_tiles(
            "size", 0, arg + 1, arg, False, groupby._SORT_CHUNK
        ).lower((), (codes, np.int64(1), np.int64(ROWS)))
    chunk = groupby._SCAN_CHUNK
    if kernel == "limb_dot":  # arg: the aggregation; the last column a float64
        cols = tuple(
            shape((ROWS,), np.float64 if i == N_COLS - 1 else np.int64)
            for i in range(N_COLS)
        )
        return groupby._jit_limb_dot(
            arg, NUM_SEGMENTS, P_OUT, False, groupby._LIMB_CHUNK, False
        ).lower(cols, codes)
    fn = {  # arg: the aggregation
        "masked_scan_smc": lambda: groupby._jit_masked_scan_smc(
            arg, N_COLS, NUM_SEGMENTS, P_OUT, chunk, True, False
        ),
        "masked_scan_agg": lambda: groupby._jit_masked_scan_agg(
            arg, N_COLS, NUM_SEGMENTS, 1, P_OUT, chunk
        ),
        "segment_agg": lambda: groupby._jit_segment_agg(
            arg, N_COLS, NUM_SEGMENTS, 1, P_OUT, True, False
        ),
    }[kernel]()
    cols = tuple(shape((ROWS,), np.int64) for _ in range(N_COLS))
    return fn.lower(cols, codes)


@pytest.mark.parametrize(
    "kernel, arg",
    [
        ("bincount", 100),
        ("bincount", 512),
        ("masked_scan_smc", "sum"),
        ("masked_scan_smc", "mean"),
        ("limb_dot", "sum"),
        ("limb_dot", "mean"),
        ("masked_scan_agg", "min"),
        ("segment_agg", "sum"),
        ("segment_agg", "var"),
        ("sorted_tiles_size", 1_000_000),
    ],
)
def test_kernel_compiles_for_v5e_at_1e8_rows(kernel, arg, shapes):
    lowered = _lower(kernel, arg, shapes)
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    mem = compiled.memory_analysis()
    resident = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    )
    assert resident < HBM_BYTES, (kernel, arg, mem)
    if kernel in ("bincount", "limb_dot"):
        # the Mosaic kernel is really in the program, not an XLA scatter
        assert "tpu_custom_call" in lowered.as_text()
    if kernel == "bincount":
        # the kernel reads the codes through a reshape that moves no byte: the
        # program holds one int32 copy of the (int64) codes at most, and not a
        # padded or re-tiled second one beside it
        assert mem.temp_size_in_bytes < ROWS * 4 + (8 << 20), mem
        return
    if kernel == "sorted_tiles_size":
        # chunks are sorted and nothing scatters
        text = compiled.as_text()
        assert " sort(" in text and " scatter(" not in text
        assert mem.argument_size_in_bytes >= ROWS * 8
    else:
        # the 4.8 GB frame is the argument: a compile that dropped the
        # int64 columns (or x64) would show here
        assert mem.argument_size_in_bytes >= ROWS * 8 * (N_COLS + 1)


@pytest.mark.parametrize(
    "program, arg",
    [
        ("category_ids", "int8"),  # id1 / id2 of the H2O script: 100 categories
        ("category_ids", "int32"),  # id3: 1e6 categories
        # the order's sorts compile slowly: 18 s this one; 21 s at 1e4 groups,
        # 41 s at 1e6 (a prefix of 2**25 rows) and 47 s over the whole column
        # (the last prefix a skewed key reaches) were compiled by hand for PR 36
        ("first_seen", 100),
        ("first_seen_tiles", 1_000_000),
        ("first_seen_take", 1_000_000),
    ],
)
def test_script_phrasing_programs_compile_for_v5e_at_1e8_rows(program, arg, shapes):
    """The programs a category key and ``sort=False`` add (PR 36): no scatter in
    the order of first appearance, and the codes never widened past int32."""
    import numpy as np

    from modin_tpu.ops import groupby

    if program == "category_ids":
        width = 100 if arg == "int8" else 1_000_000
        lowered = groupby._jit_category_ids(ROWS, width, False).lower(shapes((ROWS,), arg))
    elif program == "first_seen_take":
        cols = (shapes((arg,), np.int32), shapes((arg,), np.int64), shapes((arg,), np.float64))
        lowered = groupby._jit_first_seen_take(3).lower(cols, shapes((arg,), np.int32))
    elif program == "first_seen_tiles":
        take = groupby._SORT_CHUNK
        lowered = groupby._jit_first_seen_tiles(arg, arg, take).lower(shapes((ROWS,), np.int32))
    else:
        take = groupby._first_seen_rows(arg, ROWS)
        assert take & (take - 1) == 0
        lowered = groupby._jit_first_seen(arg, take, arg).lower(shapes((ROWS,), np.int32))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    text = compiled.as_text()
    assert " scatter(" not in text
    if program == "category_ids":
        assert mem.output_size_in_bytes < ROWS * 4 + (1 << 20) and "s64[" not in text
    elif program.startswith("first_seen") and program != "first_seen_take":
        assert " sort(" in text
        # the prefix is what is sorted, not the column
        assert resident < ROWS * 4 + 6 * 4 * take + (64 << 20), (take, mem)
    assert resident < HBM_BYTES


@pytest.mark.parametrize(
    "op, temp_bound",
    [
        ("mod", 1.5e9),
        # the 64-bit floor division alone keeps 3.1 GB of word halves alive,
        # guarded or not (the unguarded program read 3.103 GB here)
        ("floordiv", 3.3e9),
    ],
)
def test_guarded_divmod_compiles_for_v5e_at_the_asv_frame(op, temp_bound, shapes):
    """``df.mod(2)`` of the asv frame (ten int64 columns of 5e7 rows, the
    divisor a runtime scalar) as the fused plan builds it: one conditional a
    column, no 32-bit integer divide for the compiler to expand (an s32
    ``rem`` compiles for 21 s here and its run time is unknown), and
    temporaries that stay a fraction of the 4 GB answer.  They are the split
    halves of one column (0.4 GB), the conditional's output (0.4 GB) and the
    wide branch's own intermediates (0.4 GB), shared by the ten columns:
    1.2 GB for ``mod``, where the unguarded program, which may overwrite the
    halves in place, holds 0.6 GB."""
    import re

    import jax
    import numpy as np

    from modin_tpu.ops.elementwise import get_op

    rows, n_cols = 50_000_000, 10

    def plan(scalars, *cols):
        return tuple(get_op(op)(c, scalars[0]) for c in cols)

    cols = [shapes((rows,), np.int64) for _ in range(n_cols)]
    compiled = jax.jit(plan).lower((2,), *cols).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= rows * 8 * n_cols
    assert mem.temp_size_in_bytes < temp_bound, mem
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert resident < HBM_BYTES
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) == n_cols
    integer_divides = re.findall(r"= [su]32\[[^=]*? (?:divide|remainder)\(", text)
    assert integer_divides == []


@pytest.mark.parametrize(
    "agg, groups, dtype",
    [
        # q7 of the H2O script: max v1 (min v2 is the same program) by id3
        ("max", 1_000_000, "int64"),
        # q6: the centred sd of v3 by id4 x id5, two walks in one program
        ("std", 10_000, "float64"),
    ],
)
def test_sorted_tiles_statistics_compile_for_v5e_at_1e8_rows(agg, groups, dtype, shapes):
    """The tiles' max / min and centred spread (PR 39): no scatter, and
    temporaries of a chunk's sort and one-hots, not of the column (18 and 20 s
    here; 0.80 and 0.84 GB of temporaries when written)."""
    import numpy as np

    from modin_tpu.ops import groupby

    spread = (1,) if agg == "std" else ()
    lowered = groupby._jit_sorted_tiles(
        agg, 1, groups + 1, groups, False, groupby._SORT_CHUNK, *spread
    ).lower((shapes((ROWS,), dtype),), shapes((ROWS,), np.int32))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert " sort(" in text and " scatter(" not in text
    assert mem.temp_size_in_bytes < 1.2e9, mem
    assert mem.argument_size_in_bytes >= ROWS * (4 + np.dtype(dtype).itemsize)


@pytest.mark.slow
def test_group_median_compiles_for_v5e_at_1e8_rows(shapes):
    """q6's median (PR 39): one sort of the (int32 code, float64 value) pairs
    and a binary search, no scatter; the sort's result beside its operands.
    Slow: a sort keyed on float64 compiles for 255 s here (PERF.md), so this
    compile is not among the tier-1 tests."""
    import numpy as np

    from modin_tpu.ops import groupby

    lowered = groupby._jit_group_sort_select(1, 10_001, 10_000, 0.5, "linear", True).lower(
        (shapes((ROWS,), np.float64),), shapes((ROWS,), np.int32)
    )
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    assert " sort(" in text and " scatter(" not in text
    assert mem.temp_size_in_bytes < ROWS * (4 + 8) * 1.5, mem
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert resident < HBM_BYTES


def test_sharded_bincount_compiles_for_four_v5e_chips(topo, shapes):
    """Mosaic kernels cannot be partitioned automatically: over a row-sharded
    operand the bincount must sit in a ``shard_map`` (first four-chip run of
    PR 22 was refused with exactly that), one kernel per chip + a psum."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from modin_tpu.ops.pallas.groupby_kernels import _bincount_fn

    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("rows", "cols"))
    codes = jax.ShapeDtypeStruct(
        (ROWS,), np.int64, sharding=NamedSharding(mesh, PartitionSpec("rows"))
    )
    lowered = jax.jit(_bincount_fn(ROWS, 100, False, mesh)).lower(codes)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert "all-reduce" in compiled.as_text()
    mem = compiled.memory_analysis()
    # each chip holds a quarter of the codes, not the whole vector
    assert mem.argument_size_in_bytes < ROWS * 8 // 4 + (1 << 20)


def test_bincount_reduces_its_one_hot_on_the_mxu():
    """Needs no chip: the histogram kernel's body holds a ``dot_general``, so
    an edit that puts the reduction back on the VPU fails here and not in a
    benchmark."""
    import jax
    import jax.numpy as jnp

    from modin_tpu.ops.pallas.groupby_kernels import pallas_bincount
    from tests.utils import jaxpr_eqns as eqns_of

    codes = jnp.zeros(5_000, jnp.int32)
    traced = jax.make_jaxpr(lambda c: pallas_bincount(c, 100, interpret=True))(codes)
    kernels = [e for e in eqns_of(traced.jaxpr) if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    inside = list(eqns_of(kernels[0].params["jaxpr"]))
    assert "dot_general" in {e.primitive.name for e in inside}
    # what the VPU still adds up is the MXU's float32 results, never a one-hot
    summed = [e.invars[0].aval.dtype for e in inside if e.primitive.name == "reduce_sum"]
    assert all(dtype == jnp.float32 for dtype in summed), summed
