"""Row-wise reductions (``DataFrame.<op>(axis=1)``) against pandas, on both
sides of the column count at which they stop reading the k columns as k
arrays (``ops/reductions.py`` ``_AXIS1_COLUMNS_MAX``) and stack them.

Frames are 203 rows: a ragged final shard on the 8-device test mesh."""

import itertools

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
from modin_tpu.ops import reductions
from tests.utils import assert_no_fallback, df_equals

K = reductions._AXIS1_COLUMNS_MAX
ROWS = 203
WIDE = 2**53  # int64 values float64 cannot tell apart from their neighbours

OPS = ["sum", "mean", "min", "max", "count", "var", "std", "median", "nunique"]
KINDS = ["int64", "int32", "float64", "bool", "mixed"]
WIDTHS = [1, 2, 3, 10, K, K + 1]


def _frame(kind: str, k: int, op: str, seed: int, rows: int = ROWS) -> dict:
    rng = np.random.default_rng(seed)
    data = {}
    for i in range(k):
        small = rng.integers(-4, 5, rows)
        if kind == "int64":
            # past 2**53 where the answer is an order statistic or a count,
            # so converting before or after the sort would show
            wide = op in ("median", "nunique", "min", "max", "sum", "count")
            data[f"c{i}"] = small + (WIDE + i % 3 if wide and i % 2 == 0 else 0)
        elif kind == "int32":
            data[f"c{i}"] = (small * 1_000_003).astype(np.int32)
        elif kind == "bool":
            data[f"c{i}"] = small > 0
        else:
            v = small + 0.25 if kind == "float64" or i % 2 else small
            if kind == "float64" or i % 2:
                v = v.astype(np.float64)
                v[rng.random(rows) < 0.25] = np.nan
                v[:4] = np.nan  # all-NaN rows where every column is float
                v[-1] = np.nan  # the ragged shard's last row
            data[f"c{i}"] = v
    return data


def _cases(kinds, widths):
    for op, kind, k in itertools.product(OPS, kinds, widths):
        for skip in ((True,) if op == "count" else (True, False)):
            yield pytest.param(op, kind, k, skip, id=f"{op}-{kind}-{k}-{skip}")


def _kwargs(op: str, skip: bool) -> dict:
    if op == "count":
        return {}
    return {"dropna": skip} if op == "nunique" else {"skipna": skip}


@pytest.mark.parametrize("op, kind, k, skip", list(_cases(KINDS, WIDTHS)))
def test_row_reduction_matches_pandas(op, kind, k, skip):
    data = _frame(kind, k, op, seed=k * 31 + len(op))
    md, pdf = pd.DataFrame(data), pandas.DataFrame(data)
    kwargs = _kwargs(op, skip)
    got = assert_no_fallback(lambda: getattr(md, op)(axis=1, **kwargs))
    expect = getattr(pdf, op)(axis=1, **kwargs)
    if op in ("mean", "var", "std") or (kind in ("float64", "mixed") and op == "sum"):
        pandas.testing.assert_series_equal(got._to_pandas(), expect, rtol=1e-12)
    else:
        df_equals(got, expect)


@pytest.mark.parametrize("op", ["sum", "median", "nunique"])
@pytest.mark.parametrize("k", [10, K + 1])
def test_elementwise_producers_fuse_into_the_row_reduction(op, k):
    data = _frame("int64", k, "mean", seed=k)
    md, pdf = pd.DataFrame(data), pandas.DataFrame(data)
    got = assert_no_fallback(lambda: getattr(md.mul(3), op)(axis=1))
    df_equals(got, getattr(pdf.mul(3), op)(axis=1))


@pytest.mark.parametrize("op", ["sum", "min", "max", "count", "median", "nunique"])
@pytest.mark.parametrize("k", [1, 2, 10, K])
def test_column_and_stacked_forms_agree_bit_for_bit(op, k):
    """Where the stacked form is exact (an integer frame), the column form
    gives the same bits: the network's order statistics are the sort's.
    (Not the mean: XLA divides the stacked sum by a constant as a multiply
    by its rounded reciprocal.)"""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(k)
    cols = tuple(
        jnp.asarray(rng.integers(-50, 50, 1000) + (WIDE if i % 2 else 0))
        for i in range(k)
    )
    if op == "nunique":
        columns = reductions._jit_nunique_axis1(k, 1000, True, "axis1_columns")
        stacked = reductions._jit_nunique_axis1(k, 1000, True, "axis1_stacked")
    else:
        columns = jax.jit(reductions._axis1_columns_fn(op, True, 1))
        stacked = jax.jit(reductions._axis1_stacked_fn(op, True, 1))
    a, b = np.asarray(columns(cols)), np.asarray(stacked(cols))
    assert a.dtype == b.dtype
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("k", range(1, 17))
def test_sorting_network_sorts_every_zero_one_input(k):
    """The 0-1 principle: a comparator network that sorts all 2**k vectors
    of zeros and ones sorts every input."""
    bits = (np.arange(2**k)[None, :] >> np.arange(k)[:, None]) & 1
    rows = list(bits)
    for i, j in reductions._sorting_network(k):
        assert i < j < k
        rows[i], rows[j] = np.minimum(rows[i], rows[j]), np.maximum(rows[i], rows[j])
    assert (np.diff(np.stack(rows), axis=0) >= 0).all()


@pytest.mark.parametrize("k", [17, 24, K, K + 1])
def test_sorting_network_sorts_wider_rows(k):
    x = np.random.default_rng(k).integers(0, 6, (k, 5000))
    rows = list(x)
    for i, j in reductions._sorting_network(k):
        rows[i], rows[j] = np.minimum(rows[i], rows[j]), np.maximum(rows[i], rows[j])
    np.testing.assert_array_equal(np.stack(rows), np.sort(x, axis=0))


class _one_shard_chunks:
    """A one-shard mesh, where the median walks the rows a chunk at a time,
    with chunks of ``nbytes`` so that a small frame takes several."""

    def __init__(self, nbytes):
        self.nbytes = nbytes

    def __enter__(self):
        from modin_tpu.config import MeshShape
        from modin_tpu.parallel.mesh import reset_mesh

        self.was = (reductions._AXIS1_CHUNK_BYTES, MeshShape.get())
        reductions._AXIS1_CHUNK_BYTES = self.nbytes
        MeshShape.put((1, 1))
        reset_mesh()

    def __exit__(self, *exc):
        from modin_tpu.config import MeshShape
        from modin_tpu.parallel.mesh import reset_mesh

        reductions._AXIS1_CHUNK_BYTES, shape = self.was
        MeshShape.put(shape)
        reset_mesh()


# a row count no other test uses: the fused program compiled here is the
# chunked one, not one cached from a test with the default chunk
CHUNKED_ROWS = 211


@pytest.mark.parametrize(
    "op, kind, k, skip", list(_cases(["int64", "float64", "mixed"], [3, 10]))
)
def test_one_shard_walk_matches_pandas(op, kind, k, skip):
    """Every op on a one-shard mesh; the median's rows go a chunk at a time
    (here 64 rows of ten columns or 128 of three: the last chunk flush with
    the end, overlapping the one before)."""
    pdf = pandas.DataFrame(_frame(kind, k, op, seed=k + 7, rows=CHUNKED_ROWS))
    kwargs = _kwargs(op, skip)
    with _one_shard_chunks(64 * 8 * 10):
        md = pd.DataFrame(pdf)
        got = assert_no_fallback(lambda: getattr(md, op)(axis=1, **kwargs))._to_pandas()
    expect = getattr(pdf, op)(axis=1, **kwargs)
    pandas.testing.assert_series_equal(got, expect, rtol=1e-12)


def test_one_shard_walk_is_a_loop_and_a_sharded_mesh_is_not():
    import jax
    import jax.numpy as jnp

    cols = tuple(jnp.arange(CHUNKED_ROWS, dtype=jnp.int64) * i for i in range(10))
    median = reductions._axis1_columns_fn("median", True, 1)

    def primitives():
        jaxpr = jax.make_jaxpr(lambda *c: median(c))(*cols)
        return {e.primitive.name for e in jaxpr.jaxpr.eqns}

    loops = {"while", "scan"}
    assert not loops & primitives()  # the 8-shard test mesh
    with _one_shard_chunks(64 * 8 * 10):
        assert loops & primitives()
