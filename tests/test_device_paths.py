"""White-box tests: the hot ops must run on DEVICE (no pandas fallback).

Counterpart of the reference's internals tests
(modin/tests/core/storage_formats/pandas/test_internals.py): asserts the
device fast paths actually engage and stay sharded.
"""

import warnings

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
from modin_tpu.core.storage_formats.tpu.query_compiler import TpuQueryCompiler
from tests.utils import df_equals, jaxpr_eqns


@pytest.fixture(autouse=True)
def _require_tpu_backend():
    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax":
        pytest.skip("device-path tests require the TpuOnJax execution")


def make_df(n=1000, cols=3, seed=0):
    rng = np.random.default_rng(seed)
    data = {f"c{i}": rng.uniform(-10, 10, n) for i in range(cols)}
    data["k"] = rng.integers(0, 5, n)
    return pd.DataFrame(data)


def assert_no_fallback(fn):
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        return fn()


def test_frame_is_device_backed():
    df = make_df()
    qc = df._query_compiler
    assert isinstance(qc, TpuQueryCompiler)
    assert all(c.is_device for c in qc._modin_frame._columns)


def test_columns_are_padded_and_sharded():
    from modin_tpu.parallel.mesh import num_row_shards

    df = make_df(n=1001)
    col = df._query_compiler._modin_frame.get_column(0)
    assert col.length == 1001
    assert col.data.shape[0] % num_row_shards() == 0
    assert col.data.shape[0] >= 1001


def test_binary_no_fallback():
    df = make_df()
    result = assert_no_fallback(lambda: df + df)
    assert all(c.is_device for c in result._query_compiler._modin_frame._columns)
    result2 = assert_no_fallback(lambda: df * 2.5)
    df_equals(result2, df._to_pandas() * 2.5)


def test_reduce_no_fallback():
    df = make_df()
    s = assert_no_fallback(lambda: df.sum())
    df_equals(s, df._to_pandas().sum())
    assert_no_fallback(lambda: df.mean())
    assert_no_fallback(lambda: df.max(axis=1))


def test_groupby_sum_no_fallback():
    df = make_df()
    result = assert_no_fallback(lambda: df.groupby("k").sum())
    df_equals(result, df._to_pandas().groupby("k").sum())
    # the aggregation result itself stays on device
    assert all(
        c.is_device for c in result._query_compiler._modin_frame._columns
    )


def test_sort_no_fallback():
    df = make_df()
    result = assert_no_fallback(lambda: df.sort_values("c0"))
    df_equals(result, df._to_pandas().sort_values("c0", kind="stable"))


def test_filter_no_fallback():
    df = make_df()
    result = assert_no_fallback(lambda: df[df["c0"] > 0])
    df_equals(result, (lambda p: p[p["c0"] > 0])(df._to_pandas()))


def test_computed_column_drops_host_cache():
    df = make_df()
    out = df + 1
    col = out._query_compiler._modin_frame.get_column(0)
    assert col.host_cache is None
    src = df._query_compiler._modin_frame.get_column(0)
    assert src.host_cache is not None


def test_fallback_roundtrips_to_device():
    # a defaulted op must return a Tpu-backed compiler again
    df = make_df()
    result = df.rank()
    assert isinstance(result._query_compiler, TpuQueryCompiler)


def test_sharding_spans_mesh():
    from modin_tpu.parallel.mesh import get_mesh, num_row_shards

    if num_row_shards() < 2:
        pytest.skip("needs a multi-device mesh")
    df = make_df(n=4096)
    col = df._query_compiler._modin_frame.get_column(0)
    assert len(col.data.sharding.device_set) == num_row_shards()


def test_reduction_over_sharded_matches(enable_benchmark_mode):
    df = make_df(n=4096)
    df_equals(df.sum(), df._to_pandas().sum())


def test_rolling_device_path():
    import warnings

    df = make_df(n=500)
    num = df[["c0", "c1", "c2"]]
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        r_sum = num.rolling(7).sum()
        r_mean = num.rolling(7, min_periods=3).mean()
        r_count = num.rolling(7).count()
    p = num._to_pandas()
    df_equals(r_sum, p.rolling(7).sum())
    df_equals(r_mean, p.rolling(7, min_periods=3).mean())
    df_equals(r_count, p.rolling(7).count())


def test_rolling_with_nan():
    import pandas as real_pandas

    data = {"a": [1.0, np.nan, 3.0, 4.0, np.nan, 6.0, 7.0, 8.0]}
    md = pd.DataFrame(data)
    p = real_pandas.DataFrame(data)
    df_equals(md.rolling(3).sum(), p.rolling(3).sum())
    df_equals(md.rolling(3, min_periods=1).mean(), p.rolling(3, min_periods=1).mean())


def test_float_cumulative_device():
    import warnings

    data = {"a": [1.0, np.nan, 3.0, -2.0], "b": [0.5, 1.5, np.nan, 2.5]}
    md = pd.DataFrame(data)
    p = md._to_pandas()
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        got_sum = md.cumsum()
        got_max = md.cummax()
        got_min = md.cummin()
        got_prod = md.cumprod()
    df_equals(got_sum, p.cumsum())
    df_equals(got_max, p.cummax())
    df_equals(got_min, p.cummin())
    df_equals(got_prod, p.cumprod())


def test_rolling_min_periods_zero_and_invalid():
    import pandas as real_pandas

    data = {"a": [np.nan, 1.0, np.nan, np.nan, 2.0]}
    md = pd.DataFrame(data)
    p = real_pandas.DataFrame(data)
    df_equals(md.rolling(2, min_periods=0).sum(), p.rolling(2, min_periods=0).sum())
    with pytest.raises(ValueError):
        p.rolling(2, min_periods=5).sum()
    with pytest.raises(ValueError):
        md.rolling(2, min_periods=5).sum()


def test_dropna_device_path():
    import warnings

    data = {
        "a": [1.0, np.nan, 3.0, 4.0],
        "b": [np.nan, np.nan, 30.0, 40.0],
        "t": pandas.to_datetime(["2020-01-01", None, None, "2020-01-04"]),
    }
    md = pd.DataFrame(data)
    p = md._to_pandas()
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        got_any = md.dropna()
        got_all = md.dropna(how="all")
        got_sub = md.dropna(subset=["a"])
    df_equals(got_any, p.dropna())
    df_equals(got_all, p.dropna(how="all"))
    df_equals(got_sub, p.dropna(subset=["a"]))


def test_value_counts_device_path():
    import warnings

    rng = np.random.default_rng(3)
    s = pd.Series(rng.integers(0, 7, 500), name="v")
    p = s._to_pandas()
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        got = s.value_counts()
        got_norm = s.value_counts(normalize=True)
        got_asc = s.value_counts(ascending=True)
    df_equals(got, p.value_counts())
    df_equals(got_norm, p.value_counts(normalize=True))
    df_equals(got_asc, p.value_counts(ascending=True))


def test_value_counts_float_with_nan():
    vals = [1.5, 1.5, np.nan, 2.5, np.nan, np.nan]
    md = pd.Series(vals)
    p = md._to_pandas()
    df_equals(md.value_counts(), p.value_counts())
    df_equals(md.value_counts(dropna=False), p.value_counts(dropna=False))


def test_value_counts_sort_false_first_appearance():
    md = pd.Series([3, 1, 1, 2, 3, 3])
    p = md._to_pandas()
    df_equals(md.value_counts(sort=False), p.value_counts(sort=False))


def test_dropna_arraylike_subset():
    md = pd.DataFrame({"a": [1.0, np.nan], "b": [np.nan, 2.0]})
    p = md._to_pandas()
    df_equals(md.dropna(subset=np.array(["a"])), p.dropna(subset=np.array(["a"])))
    df_equals(md.dropna(subset=pandas.Index(["b"])), p.dropna(subset=pandas.Index(["b"])))


def test_shift_diff_device():
    import warnings

    data = {"a": [1.0, 2.0, np.nan, 4.0, 5.0], "b": [10, 20, 30, 40, 50]}
    md = pd.DataFrame(data)
    p = md._to_pandas()
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        got_shift = md.shift(2)
        got_nshift = md.shift(-1)
        got_diff = md.diff()
        got_ndiff = md.diff(-2)
    df_equals(got_shift, p.shift(2))
    df_equals(got_nshift, p.shift(-1))
    df_equals(got_diff, p.diff())
    df_equals(got_ndiff, p.diff(-2))


def test_shift_diff_edge_periods():
    data = {"a": [1.0, 2.0, 3.0], "b": [10, 20, 30]}
    md = pd.DataFrame(data)
    p = md._to_pandas()
    df_equals(md.shift(0), p.shift(0))           # dtype preserved
    df_equals(md.diff(0), p.diff(0))
    df_equals(md.shift(50), p.shift(50))         # beyond length -> all NaN
    df_equals(md.shift(-50), p.shift(-50))
    df_equals(md.diff(-50), p.diff(-50))


def test_float64_policy_downcast():
    """Float64Policy=Downcast: f32 device storage, exact host round-trip."""
    import numpy as np

    from modin_tpu.config import Float64Policy

    x = np.random.default_rng(0).normal(size=800)
    with Float64Policy.context("Downcast"):
        md = pd.DataFrame({"a": x})
        col = md._query_compiler._modin_frame.get_column(0)
        assert str(col.data.dtype) == "float32"
        assert col.pandas_dtype == np.float64
        # untouched column round-trips bit-exact via host_cache
        np.testing.assert_array_equal(md["a"].to_numpy(), x)
        # computed results carry f32 precision (the policy's tradeoff)
        got = float((md["a"] * 2.0).sum())
        np.testing.assert_allclose(got, (x.astype(np.float32) * 2).sum(), rtol=1e-5)


# ---------------------------------------------------------------------- #
# the guarded 64-bit integer mod / floordiv (ops/elementwise.py)
# ---------------------------------------------------------------------- #

_I32 = 2**31
_DIVMOD_OPERANDS = {
    # the asv frame's range: every word fits, the narrow branch answers
    "small": np.arange(200, dtype=np.int64) % 100,
    "negative": np.arange(-150, 150, dtype=np.int64),
    # the widest operands the narrow branch takes
    "narrow_edges": np.array(
        [-_I32 + 1, _I32 - 1, -_I32 + 2, _I32 - 2, 0, 1, -1, 7, -7, 65536, -65537],
        dtype=np.int64,
    ),
    # fits int32, but its magnitude and its quotient by -1 do not
    "int32_min": np.array([-_I32, 5, -5, 0, _I32 - 1], dtype=np.int64),
    "past_the_edges": np.array(
        [-_I32 - 1, _I32, _I32 + 1, -_I32 + 1, 2**40, -(2**40), 3, -3],
        dtype=np.int64,
    ),
    "one_wide_value": np.where(
        np.arange(300) == 123, 2**40 + 17, np.arange(300) % 100
    ).astype(np.int64),
}
_DIVMOD_DIVISORS = [2, -3, 1, -1, _I32 - 1, -_I32, _I32, 2**40]


@pytest.mark.parametrize("divisor", _DIVMOD_DIVISORS)
@pytest.mark.parametrize("operands", list(_DIVMOD_OPERANDS))
@pytest.mark.parametrize("op", ["mod", "floordiv"])
@pytest.mark.parametrize("kind", ["frame", "series"])
def test_int64_divmod_by_scalar_is_exact_on_device(kind, op, operands, divisor):
    values = _DIVMOD_OPERANDS[operands]
    if kind == "frame":
        pdf = pandas.DataFrame({"a": values, "b": values[::-1], "c": -values})
        mdf = pd.DataFrame(pdf)
    else:
        pdf = pandas.Series(values, name="a")
        mdf = pd.Series(pdf)
    result = assert_no_fallback(lambda: getattr(mdf, op)(divisor))
    assert all(c.is_device for c in result._query_compiler._modin_frame._columns)
    df_equals(result, getattr(pdf, op)(divisor))


@pytest.mark.parametrize("operands", list(_DIVMOD_OPERANDS))
@pytest.mark.parametrize("op", ["mod", "floordiv"])
@pytest.mark.parametrize(
    "divisor_kind", ["list_a_column", "frame", "operators", "numpy_reflected"]
)
def test_int64_divmod_by_other_divisors_is_exact(divisor_kind, op, operands):
    """A divisor a column, a frame divisor, ``%`` / ``//`` / ``divmod`` and
    the reflected forms through ``modin_tpu.numpy``: whichever path answers
    (the query compiler refuses integer data as a divisor), pandas' values."""
    import modin_tpu.numpy as mnp

    values = _DIVMOD_OPERANDS[operands]
    pdf = pandas.DataFrame({"a": values, "b": values[::-1]})
    mdf = pd.DataFrame(pdf)
    if divisor_kind == "list_a_column":
        df_equals(getattr(mdf, op)([2, -3], axis=1), getattr(pdf, op)([2, -3], axis=1))
    elif divisor_kind == "frame":
        other = pandas.DataFrame({"a": np.full(len(values), -3), "b": np.full(len(values), _I32 - 1)})
        df_equals(getattr(mdf, op)(pd.DataFrame(other)), getattr(pdf, op)(other))
    elif divisor_kind == "operators":
        if op == "mod":
            df_equals(mdf % 7, pdf % 7)
            df_equals(divmod(mdf["a"], -7)[1], divmod(pdf["a"], -7)[1])
        else:
            df_equals(mdf // 7, pdf // 7)
            df_equals(divmod(mdf["a"], -7)[0], divmod(pdf["a"], -7)[0])
    else:
        nonzero = np.where(values == 0, 3, values)
        arr = mnp.array(nonzero)
        fn, ref = (
            (mnp.remainder, np.remainder) if op == "mod" else (mnp.floor_divide, np.floor_divide)
        )
        np.testing.assert_array_equal(np.asarray(fn(1000003, arr)), ref(1000003, nonzero))
        np.testing.assert_array_equal(np.asarray(fn(arr, -3)), ref(nonzero, -3))


@pytest.mark.parametrize("op", ["mod", "floordiv"])
@pytest.mark.parametrize("dtype", ["int32", "uint64", "int16", "float64"])
def test_divmod_of_other_dtypes_is_unchanged(dtype, op):
    """Only an int64 result is guarded: narrower integers, uint64 and floats
    keep their dtype and their answers."""
    values = np.array([0, 1, 5, 99, 100, 32767, 7, 12], dtype=dtype)
    pdf = pandas.DataFrame({"a": values, "b": values[::-1]})
    result = assert_no_fallback(lambda: getattr(pd.DataFrame(pdf), op)(3))
    expected = getattr(pdf, op)(3)
    assert list(result.dtypes) == list(expected.dtypes)
    df_equals(result, expected)


@pytest.mark.parametrize("want", ["mod", "floordiv"])
def test_the_guard_is_one_cond_whose_narrow_branch_never_divides_integers(want):
    import jax
    import jax.numpy as jnp

    from modin_tpu.ops import elementwise

    column = jnp.arange(64, dtype=jnp.int64)
    traced = jax.make_jaxpr(elementwise._jit_guarded_divmod(want))(column, jnp.asarray(2))
    conds = [e for e in jaxpr_eqns(traced.jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 1
    # branches[1] answers a true predicate: the narrow one
    wide, narrow = conds[0].params["branches"]
    int_divides = lambda branch: [  # noqa: E731
        e.primitive.name
        for e in jaxpr_eqns(branch.jaxpr)
        if e.primitive.name in ("div", "rem")
        and jnp.issubdtype(e.invars[0].aval.dtype, jnp.integer)
    ]
    assert int_divides(narrow) == []
    assert int_divides(wide)  # today's 64-bit division, kept for wide operands
    assert all(v.aval.dtype == jnp.int64 for v in conds[0].outvars)


@pytest.mark.parametrize(
    "values, narrow",
    [
        ([0, 99, -99, _I32 - 1, -_I32 + 1], True),
        ([0, 99, _I32], False),  # one value past int32
        ([0, 99, -_I32 - 1], False),
        ([0, 99, 2**40], False),
        ([0, 99, -_I32], False),  # fits, but -2**31 // -1 would not
    ],
)
def test_the_guards_predicate_reads_the_operands_words(values, narrow):
    import jax.numpy as jnp

    from modin_tpu.ops import elementwise

    assert bool(elementwise._fits_narrow(jnp.asarray(values, jnp.int64))) is narrow
