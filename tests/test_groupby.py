"""Differential groupby tests (modeled on modin/tests/pandas/test_groupby.py)."""

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
from tests.utils import assert_no_fallback, create_test_dfs, df_equals, eval_general

_rng = np.random.default_rng(7)
N = 200

GB_DATA = {
    "int_key": _rng.integers(0, 10, N),
    "sparse_key": _rng.choice([3, 70, 1000, -5], N),
    "float_key": _rng.choice([0.5, 1.25, np.nan, 7.0], N),
    "val_int": _rng.integers(-50, 50, N),
    "val_float": np.where(_rng.random(N) < 0.2, np.nan, _rng.uniform(-1, 1, N)),
    "val_bool": _rng.random(N) < 0.5,
}

AGGS = ["sum", "count", "mean", "min", "max", "prod", "var", "std", "sem", "any", "all"]


@pytest.fixture
def dfs():
    return create_test_dfs(GB_DATA)


@pytest.mark.parametrize("agg", AGGS)
@pytest.mark.parametrize("key", ["int_key", "sparse_key", "float_key"])
def test_groupby_agg(dfs, agg, key):
    md, pdf = dfs
    df_equals(
        getattr(md.groupby(key), agg)(),
        getattr(pdf.groupby(key), agg)(),
    )


@pytest.mark.parametrize("agg", ["sum", "mean", "count"])
def test_groupby_multikey(dfs, agg):
    md, pdf = dfs
    df_equals(
        getattr(md.groupby(["int_key", "sparse_key"]), agg)(),
        getattr(pdf.groupby(["int_key", "sparse_key"]), agg)(),
    )


def test_groupby_size(dfs):
    md, pdf = dfs
    df_equals(md.groupby("int_key").size(), pdf.groupby("int_key").size())


def test_groupby_selection(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key")["val_float"].sum(),
        pdf.groupby("int_key")["val_float"].sum(),
    )
    df_equals(
        md.groupby("int_key")[["val_int", "val_float"]].mean(),
        pdf.groupby("int_key")[["val_int", "val_float"]].mean(),
    )


def test_groupby_as_index_false(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key", as_index=False).sum(),
        pdf.groupby("int_key", as_index=False).sum(),
    )


def test_groupby_dropna_false(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("float_key", dropna=False).sum(),
        pdf.groupby("float_key", dropna=False).sum(),
    )


def test_groupby_external_series(dfs):
    md, pdf = dfs
    df_equals(
        md["val_float"].groupby(md["int_key"]).sum(),
        pdf["val_float"].groupby(pdf["int_key"]).sum(),
    )


def test_groupby_numeric_only_with_strings():
    md, pdf = create_test_dfs(
        {"k": [1, 1, 2], "v": [1.0, 2.0, 3.0], "s": ["a", "b", "c"]}
    )
    df_equals(
        md.groupby("k").sum(numeric_only=True),
        pdf.groupby("k").sum(numeric_only=True),
    )
    # numeric_only=False concatenates strings — host fallback path
    df_equals(md.groupby("k").sum(), pdf.groupby("k").sum())


def test_groupby_min_count(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key").sum(min_count=15),
        pdf.groupby("int_key").sum(min_count=15),
    )


def test_groupby_median_quantile(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key")[["val_int", "val_float"]].median(),
        pdf.groupby("int_key")[["val_int", "val_float"]].median(),
    )
    df_equals(
        md.groupby("int_key")[["val_int", "val_float"]].quantile(0.25),
        pdf.groupby("int_key")[["val_int", "val_float"]].quantile(0.25),
    )


@pytest.mark.parametrize("interp", ["linear", "lower", "higher", "midpoint", "nearest"])
@pytest.mark.parametrize("q", [0.1, 0.25, 0.5, 0.75, 0.9])
def test_groupby_quantile_device(dfs, q, interp):
    # device path: no default-to-pandas fallback permitted
    md, pdf = dfs
    assert_no_fallback(lambda: df_equals(
            md.groupby("int_key")[["val_int", "val_float"]].quantile(q, interpolation=interp),
            pdf.groupby("int_key")[["val_int", "val_float"]].quantile(q, interpolation=interp),
    ))


@pytest.mark.parametrize("agg", ["median", "nunique", "first", "last"])
@pytest.mark.parametrize("key", ["int_key", "sparse_key", "float_key"])
def test_groupby_order_aggs_device(dfs, agg, key):
    md, pdf = dfs
    assert_no_fallback(lambda: df_equals(
            getattr(md.groupby(key)[["val_int", "val_float"]], agg)(),
            getattr(pdf.groupby(key)[["val_int", "val_float"]], agg)(),
    ))


@pytest.mark.parametrize("agg", ["median", "nunique", "first", "last"])
def test_groupby_order_aggs_multikey(dfs, agg):
    md, pdf = dfs
    assert_no_fallback(lambda: df_equals(
            getattr(md.groupby(["int_key", "sparse_key"])[["val_int", "val_float"]], agg)(),
            getattr(pdf.groupby(["int_key", "sparse_key"])[["val_int", "val_float"]], agg)(),
    ))


def test_groupby_nunique_dropna(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key")["val_float"].nunique(dropna=False),
        pdf.groupby("int_key")["val_float"].nunique(dropna=False),
    )


def test_groupby_apply_transform(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key")["val_int"].transform("mean"),
        pdf.groupby("int_key")["val_int"].transform("mean"),
    )


def test_groupby_agg_dict(dfs):
    md, pdf = dfs
    spec = {"val_int": "sum", "val_float": "mean"}
    df_equals(md.groupby("int_key").agg(spec), pdf.groupby("int_key").agg(spec))


def test_groupby_iteration(dfs):
    md, pdf = dfs
    for (mk, mg), (pk, pg) in zip(md.groupby("int_key"), pdf.groupby("int_key")):
        assert mk == pk
        df_equals(mg, pg)


def test_groupby_sort_false(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key", sort=False).sum().sort_index(),
        pdf.groupby("int_key", sort=False).sum().sort_index(),
    )


def test_groupby_bool_key(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("val_bool").sum(),
        pdf.groupby("val_bool").sum(),
    )


def test_groupby_cumulative(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key")["val_int"].cumsum(),
        pdf.groupby("int_key")["val_int"].cumsum(),
    )


@pytest.mark.parametrize("agg", ["sum", "count", "mean", "min", "max", "prod", "any", "all"])
def test_groupby_masked_scan_kernel_matches(agg, monkeypatch):
    """The TPU masked-scan kernel must match the segment kernel numerics."""
    from modin_tpu.ops import groupby as gb_ops
    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax":
        pytest.skip("device kernels")
    md, pdf = create_test_dfs(GB_DATA)
    monkeypatch.setattr(gb_ops, "_FORCE_KERNEL", "masked_scan")
    df_equals(
        getattr(md.groupby("int_key"), agg)(),
        getattr(pdf.groupby("int_key"), agg)(),
    )


def _hist_step() -> int:
    """Codes a grid step of the histogram kernel."""
    from modin_tpu.ops.pallas import groupby_kernels as kernels

    return kernels.HIST_STEP_ROWS * 128


# lengths: short of one turn of the kernel's loop, one code short of a whole
# step, a whole number of steps, several steps and a ragged last one
_HIST_LENGTHS = {
    "short": lambda step: 777,
    "step_less_one": lambda step: step - 1,
    "two_steps": lambda step: 2 * step,
    "steps_and_a_bit": lambda step: 2 * step + 12_345,
}


@pytest.mark.parametrize("length", list(_HIST_LENGTHS))
@pytest.mark.parametrize("width", [1, 100, 127, 128, 129, 256, 257, 512])
def test_pallas_bincount_matches_scatter(width, length):
    """The pallas histogram must agree with ``np.bincount`` and the XLA
    scatter path (interpret mode exercises the kernel on CPU): one and two
    tiles of high digits (up to 256 ids, then 512), both sides of that edge
    and of 128; codes at and past ``width`` (pads, NaN keys, the overflow
    bucket) count for nothing."""
    import jax.numpy as jnp

    from modin_tpu.ops.pallas.groupby_kernels import pallas_bincount
    from modin_tpu.ops.groupby import _jit_scatter_counts

    n = _HIST_LENGTHS[length](_hist_step())
    rng = np.random.default_rng(width * 1000 + n % 997)
    ids_np = rng.integers(0, width + 1, n).astype(np.int32)
    # codes past the overflow id: the next one, one in the next tile of high
    # digits, two past every digit the kernel holds
    ids_np[rng.integers(0, n, 40)] = rng.choice([width + 1, width + 128, 640, 70_000], 40)
    ids = jnp.asarray(ids_np)
    got = np.asarray(pallas_bincount(ids, width, interpret=True))
    assert got.dtype == np.int64 and got.shape == (width,)
    np.testing.assert_array_equal(got, np.bincount(ids_np, minlength=width)[:width])
    if length == "short":
        kept = jnp.asarray(np.minimum(ids_np, width))
        np.testing.assert_array_equal(got, np.asarray(_jit_scatter_counts(width)(kept)))


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("width,group", [(1, 0), (100, 99), (300, 128), (512, 511)])
def test_pallas_bincount_one_group_over_several_steps(width, group, dtype):
    """Every row in one group, across more than one grid step: a step's f32
    counts are flushed into the int32 output and add up over the steps; an
    int64 code vector counts as an int32 one."""
    import jax.numpy as jnp

    from modin_tpu.ops.pallas.groupby_kernels import pallas_bincount

    n = 2 * _hist_step() + 4_321
    ids = jnp.full(n, group, dtype)
    assert ids.dtype == dtype
    got = np.asarray(pallas_bincount(ids, width, interpret=True))
    want = np.zeros(width, np.int64)
    want[group] = n
    np.testing.assert_array_equal(got, want)


def test_pallas_bincount_row_sharded_operand():
    """Over a row-sharded operand the kernel runs per shard under shard_map
    and the partial histograms are psum-ed (a Mosaic kernel in a plain jit
    cannot be partitioned on a real multi-chip mesh)."""
    import jax

    from modin_tpu.ops.pallas.groupby_kernels import (
        _jit_bincount_wrapper,
        pallas_bincount,
    )
    from modin_tpu.parallel.mesh import mesh_shape_key, num_row_shards, row_sharding

    shards = num_row_shards()
    if shards < 2:
        pytest.skip("needs a multi-device mesh")
    rng = np.random.default_rng(2)
    for per_shard, width in [(1000, 3), (6250, 100), (1543, 512)]:
        n = per_shard * shards
        ids_np = rng.integers(0, width + 1, n)
        ids = jax.device_put(ids_np, row_sharding())
        got = np.asarray(pallas_bincount(ids, width, interpret=True))
        np.testing.assert_array_equal(
            got, np.bincount(ids_np, minlength=width + 1)[:width]
        )
        # the sharded program is its own cache entry, keyed by the mesh
        before = _jit_bincount_wrapper.cache_info().hits
        _jit_bincount_wrapper(n, width, True, mesh_shape_key())
        assert _jit_bincount_wrapper.cache_info().hits == before + 1


_case_rng = np.random.default_rng(27)


def _covering(values, n):
    """n draws from ``values`` with every one of them present."""
    values = np.asarray(values)
    return _case_rng.permutation(
        np.concatenate([values, _case_rng.choice(values, n - len(values))])
    )


def _pairs(pairs, n):
    rows = np.asarray(pairs)[_covering(np.arange(len(pairs)), n)]
    return {"k1": rows[:, 0], "k2": rows[:, 1]}


_ALL_PAIRS = [(a, b) for a in range(4) for b in range(3)]

# name -> (key columns of logical length n, launches of the two remap programs).
# 208 rows fill the 8-shard padding exactly; 203 leave five pad rows.
_RANGE_CODE_CASES = {
    "dense_int": ({"k": _covering(np.arange(10), 208)}, {}),
    "dense_negative_kmin": ({"k": _covering(np.arange(-7, 6), 208)}, {}),
    "dense_bool": ({"k": _covering([False, True], 208)}, {}),
    "dense_pad_rows": ({"k": _covering(np.arange(10), 203)}, {}),
    "holes": (
        {"k": _covering([-5, 3, 4, 70, 1000], 203)},
        {"groupby_range_codes": 1},
    ),
    "multikey_all_present": (_pairs(_ALL_PAIRS, 203), {}),
    "multikey_one_absent": (
        _pairs([p for p in _ALL_PAIRS if p != (2, 1)], 203),
        {"groupby_remap": 1},
    ),
}


@pytest.mark.parametrize("case", list(_RANGE_CODE_CASES))
def test_range_codes_skip_identity_remap(case):
    """A key range (or a product of level codes) with every id present needs
    no remap: the range ids are the group codes, and the gather program is
    launched only when the range has holes."""
    import jax.numpy as jnp

    import modin_tpu.observability as graftscope
    from modin_tpu.ops.groupby import clear_factorize_cache, factorize_keys
    from modin_tpu.views import registry

    keys, remap_launches = _RANGE_CODE_CASES[case]
    n = len(next(iter(keys.values())))
    padded = -(-n // 8) * 8

    def remap_programs(stats):
        return {
            name: count
            for name, count in stats.launches_by_program.items()
            if name in ("groupby_range_codes", "groupby_remap")
        }

    # pad rows hold a key far outside the range: only position may mask them
    key_cols = [
        jnp.asarray(np.concatenate([k, np.full(padded - n, 10**6).astype(k.dtype)]))
        for k in keys.values()
    ]
    with graftscope.query_stats("factorize") as stats:
        codes, n_groups, uniques, sizes = factorize_keys(key_cols, n)
    stacked = np.stack([k.astype(np.int64) for k in keys.values()], axis=1)
    want_uniques, want_codes, want_sizes = np.unique(
        stacked, axis=0, return_inverse=True, return_counts=True
    )
    # range codes fit int32 (the range is at most 4 Mi wide, a product 16 Mi)
    assert codes.dtype == jnp.int32 and codes.shape == (padded,)
    np.testing.assert_array_equal(
        np.asarray(codes),
        np.concatenate([want_codes.ravel(), np.full(padded - n, n_groups)]),
    )
    assert n_groups == len(want_uniques)
    for got, want, k in zip(uniques, want_uniques.T, keys.values()):
        assert got.dtype == k.dtype
        np.testing.assert_array_equal(got, want.astype(k.dtype))
    np.testing.assert_array_equal(sizes, want_sizes)
    assert remap_programs(stats) == remap_launches

    pdf = pandas.DataFrame(
        {**keys, "v": _case_rng.uniform(-1, 1, n), "w": _case_rng.integers(-9, 9, n)}
    )
    md = pd.DataFrame(pdf)
    md._query_compiler.execute()
    registry.reset()
    clear_factorize_cache()
    with graftscope.query_stats("groupby") as stats:
        got = assert_no_fallback(lambda: md.groupby(list(keys)).mean())
        got._query_compiler.execute()
    df_equals(got, pdf.groupby(list(keys)).mean())
    assert "groupby_range_ids" in stats.launches_by_program
    assert remap_programs(stats) == remap_launches


def test_groupby_agg_list_device(dfs):
    md, pdf = dfs
    got = assert_no_fallback(
        lambda: md.groupby("int_key")[["val_int", "val_float"]].agg(["sum", "mean", "median"])
    )
    df_equals(got, pdf.groupby("int_key")[["val_int", "val_float"]].agg(["sum", "mean", "median"]))


def test_groupby_agg_dict_device(dfs):
    md, pdf = dfs
    spec = {"val_int": "max", "val_float": "mean"}
    got = assert_no_fallback(lambda: md.groupby("int_key").agg(spec))
    df_equals(got, pdf.groupby("int_key").agg(spec))


def test_groupby_series_agg_list_device(dfs):
    md, pdf = dfs
    got = assert_no_fallback(lambda: md.groupby("int_key")["val_float"].agg(["sum", "max"]))
    df_equals(got, pdf.groupby("int_key")["val_float"].agg(["sum", "max"]))


def test_groupby_agg_callable_falls_back(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key")[["val_float"]].agg(["sum", lambda s: s.max()]),
        pdf.groupby("int_key")[["val_float"]].agg(["sum", lambda s: s.max()]),
    )


def test_groupby_agg_single_element_list_is_frame(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key")["val_float"].agg(["sum"]),
        pdf.groupby("int_key")["val_float"].agg(["sum"]),
    )


def test_groupby_agg_duplicate_names_raise(dfs):
    md, pdf = dfs
    from tests.utils import eval_general

    eval_general(
        md, pdf,
        lambda df: df.groupby("int_key")[["val_float"]].agg(["sum", "sum"]),
    )


@pytest.mark.parametrize("agg", ["sum", "mean", "min", "max", "count", "var", "std"])
def test_groupby_transform_device(dfs, agg):
    md, pdf = dfs
    got = assert_no_fallback(
        lambda: md.groupby("int_key")[["val_int", "val_float"]].transform(agg)
    )
    df_equals(got, pdf.groupby("int_key")[["val_int", "val_float"]].transform(agg))


def test_groupby_series_transform_device(dfs):
    md, pdf = dfs
    got = assert_no_fallback(lambda: md.groupby("int_key")["val_float"].transform("mean"))
    df_equals(got, pdf.groupby("int_key")["val_float"].transform("mean"))


def test_groupby_transform_callable_falls_back(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("int_key")[["val_float"]].transform(lambda s: s - s.mean()),
        pdf.groupby("int_key")[["val_float"]].transform(lambda s: s - s.mean()),
    )


def test_groupby_transform_float_key_falls_back(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("float_key")[["val_float"]].transform("sum"),
        pdf.groupby("float_key")[["val_float"]].transform("sum"),
    )


@pytest.mark.parametrize("op", ["cumsum", "cumprod", "cummax", "cummin"])
def test_groupby_cumulative_device(dfs, op):
    md, pdf = dfs
    got = assert_no_fallback(
        lambda: getattr(md.groupby("int_key")[["val_int", "val_float"]], op)()
    )
    df_equals(got, getattr(pdf.groupby("int_key")[["val_int", "val_float"]], op)())


def test_groupby_series_cumsum_device(dfs):
    md, pdf = dfs
    got = assert_no_fallback(lambda: md.groupby("int_key")["val_float"].cumsum())
    df_equals(got, pdf.groupby("int_key")["val_float"].cumsum())


def test_groupby_cumulative_float_key_falls_back(dfs):
    md, pdf = dfs
    df_equals(
        md.groupby("float_key")[["val_float"]].cumsum(),
        pdf.groupby("float_key")[["val_float"]].cumsum(),
    )


def test_groupby_cumsum_narrow_int_promotes():
    # pandas 3 promotes signed sub-int64 cumsum/cumprod to int64 (no wrap)
    md, pdf = create_test_dfs(
        {"k": [0, 0, 1], "v": np.array([100, 100, 7], dtype=np.int8)}
    )
    df_equals(md.groupby("k").cumsum(), pdf.groupby("k").cumsum())
    df_equals(md.groupby("k").cummax(), pdf.groupby("k").cummax())


@pytest.mark.parametrize("agg", ["sum", "count", "mean"])
@pytest.mark.parametrize("adaptive", [False, True])
@pytest.mark.parametrize("has_sizes", [False, True])
@pytest.mark.parametrize("with_nan", [False, True])
def test_masked_scan_smc_kernel_direct(agg, adaptive, has_sizes, with_nan):
    """The shared-histogram sum/mean/count scan matches numpy for every
    (adaptive, provided-sizes, NaN-present) combination and mixed dtypes."""
    import jax.numpy as jnp

    from modin_tpu.ops.groupby import _jit_masked_scan_smc
    from modin_tpu.ops.structural import pad_len

    if has_sizes and agg == "sum":
        pytest.skip("sizes operand is only wired for mean/count")
    rng = np.random.default_rng(7)
    n, n_groups = 10_000, 13
    codes_np = rng.integers(0, n_groups, n)
    f = rng.uniform(-5, 5, n)
    if with_nan:
        f[rng.integers(0, n, 500)] = np.nan
    i = rng.integers(-100, 100, n)
    f32 = f.astype(np.float32)

    ns = n_groups + 1
    p_out = pad_len(n_groups)
    fn = _jit_masked_scan_smc(agg, 3, ns, p_out, 1024, adaptive, has_sizes)
    cols = (jnp.asarray(f), jnp.asarray(i), jnp.asarray(f32))
    codes = jnp.asarray(codes_np)
    if has_sizes:
        sizes = np.bincount(codes_np, minlength=n_groups).astype(np.int64)
        out = fn(cols, codes, jnp.asarray(np.append(sizes, 1)))
    else:
        out = fn(cols, codes)

    import pandas as pandas_mod

    pdf = pandas_mod.DataFrame({"f": f, "i": i, "f32": f32, "k": codes_np})
    want = getattr(pdf.groupby("k"), agg)()
    for ci, name in enumerate(["f", "i", "f32"]):
        got = np.asarray(out[ci])[:n_groups]
        # near-zero group sums of +/- uniforms make pure-relative checks
        # meaningless; bound the summation-order error absolutely too
        np.testing.assert_allclose(
            got.astype(np.float64), want[name].to_numpy(np.float64),
            rtol=1e-5 if name == "f32" else 1e-9,
            atol=1e-3 if name == "f32" else 1e-9,
            err_msg=f"col={name}",
        )
    if agg == "mean":
        # f32 means must stay f32 (pandas dtype parity)
        assert out[2].dtype == jnp.float32


class TestShuffleGroupbyApply:
    """Non-reducible UDFs through the range-partition shuffle (reference
    dataframe.py:4163,2565): groups never span chunks, host memory is
    O(chunk), results match the full-frame pandas oracle."""

    @pytest.fixture
    def big(self, monkeypatch):
        import modin_tpu.core.storage_formats.tpu.query_compiler as qc_mod

        monkeypatch.setattr(qc_mod, "_SHUFFLE_APPLY_MIN_ROWS", 100)
        rng = np.random.default_rng(29)
        n = 6000
        data = {
            "k": rng.integers(0, 40, n),
            "v": rng.normal(size=n),
            "w": rng.integers(-5, 5, n),
        }
        return create_test_dfs(data)

    def _spy(self, monkeypatch):
        import modin_tpu.core.storage_formats.tpu.query_compiler as qc_mod

        calls = {"n": 0}
        orig = qc_mod.TpuQueryCompiler._try_shuffle_groupby_apply

        def wrapper(self, *a, **k):
            out = orig(self, *a, **k)
            if out is not None:
                calls["n"] += 1
            return out

        monkeypatch.setattr(
            qc_mod.TpuQueryCompiler, "_try_shuffle_groupby_apply", wrapper
        )
        return calls

    def test_apply_scalar_per_group(self, big, monkeypatch):
        from modin_tpu.utils import get_current_execution

        if get_current_execution() != "TpuOnJax":
            pytest.skip("shuffle path needs the sharded backend")
        calls = self._spy(monkeypatch)
        md, pdf = big
        eval_general(
            md, pdf,
            lambda df: df.groupby("k")[["v", "w"]].apply(
                lambda g: g["v"].max() - g["w"].min()
            ),
        )
        assert calls["n"] >= 1

    def test_apply_frame_per_group(self, big, monkeypatch):
        from modin_tpu.utils import get_current_execution

        if get_current_execution() != "TpuOnJax":
            pytest.skip("shuffle path needs the sharded backend")
        calls = self._spy(monkeypatch)
        md, pdf = big
        eval_general(
            md, pdf,
            lambda df: df.groupby("k")[["v"]].apply(lambda g: g.head(2)),
        )
        assert calls["n"] >= 1

    def test_agg_lambda(self, big):
        md, pdf = big
        eval_general(
            md, pdf,
            lambda df: df.groupby("k")["v"].agg(lambda s: (s > 0).sum()),
        )

    def test_float_key(self, big):
        md, pdf = big
        md = md.assign(fk=md["w"] * 0.5)
        pdf = pdf.assign(fk=pdf["w"] * 0.5)
        eval_general(
            md, pdf,
            lambda df: df.groupby("fk")[["v"]].apply(lambda g: g["v"].sum()),
        )

    def test_sort_false_falls_back_correct(self, big):
        md, pdf = big
        eval_general(
            md, pdf,
            lambda df: df.groupby("k", sort=False)[["v"]].apply(
                lambda g: g["v"].mean()
            ),
        )

    def test_with_nan_keys(self, big):
        md, pdf = big
        md = md.assign(fk=md["w"].where(md["w"] > -3, np.nan))
        pdf = pdf.assign(fk=pdf["w"].where(pdf["w"] > -3, np.nan))
        eval_general(
            md, pdf,
            lambda df: df.groupby("fk")[["v"]].apply(lambda g: g["v"].sum()),
        )


class TestRowShapedCallablesBypassShuffle:
    """transform/filter lambdas and group_keys=False apply keep the ORIGINAL
    frame row order; the key-ordered shuffle concat must never claim them."""

    @pytest.fixture
    def big(self, monkeypatch):
        import modin_tpu.core.storage_formats.tpu.query_compiler as qc_mod

        monkeypatch.setattr(qc_mod, "_SHUFFLE_APPLY_MIN_ROWS", 100)
        rng = np.random.default_rng(41)
        n = 5000
        data = {"k": rng.integers(0, 30, n), "v": rng.normal(size=n)}
        return create_test_dfs(data)

    def test_transform_lambda_original_order(self, big):
        md, pdf = big
        eval_general(
            md, pdf, lambda df: df.groupby("k").transform(lambda s: s - s.mean())
        )

    def test_filter_original_order(self, big):
        md, pdf = big
        eval_general(
            md, pdf,
            lambda df: df.groupby("k").filter(lambda g: g["v"].mean() > 0),
        )

    def test_apply_group_keys_false_original_order(self, big):
        md, pdf = big
        eval_general(
            md, pdf,
            lambda df: df.groupby("k", group_keys=False)[["v"]].apply(
                lambda g: g - g.mean()
            ),
        )


def test_groupby_describe_and_corrwith():
    rng = np.random.default_rng(13)
    n = 200
    data = {
        "k": rng.integers(0, 5, n),
        "v": rng.normal(size=n),
        "w": rng.normal(size=n),
    }
    md, pdf = create_test_dfs(data)
    eval_general(md, pdf, lambda df: df.groupby("k").describe())
    eval_general(md, pdf, lambda df: df.groupby("k")["v"].describe())
    other = pdf[["v", "w"]] * 2
    eval_general(
        md, pdf, lambda df: df.groupby("k")[["v", "w"]].corrwith(other)
    )


class TestShuffleGroupbyApplyWidened:
    """r5 widening of the shuffle groupby-apply (VERDICT r4 item 4):
    multi-key, dict-encoded string keys, by-Series, sort=False appearance
    reorder, as_index=False conversion, and the single-group-chunk
    Series-widening normalization."""

    @pytest.fixture
    def big(self, monkeypatch):
        import modin_tpu.core.storage_formats.tpu.query_compiler as qc_mod

        monkeypatch.setattr(qc_mod, "_SHUFFLE_APPLY_MIN_ROWS", 100)
        rng = np.random.default_rng(31)
        n = 6000
        cities = np.array(["tokyo", "oslo", "lima", "cairo"], dtype=object)
        data = {
            "k": rng.integers(0, 12, n),
            "j": rng.integers(0, 3, n),
            "city": cities[rng.integers(0, 4, n)],
            "v": rng.normal(size=n),
        }
        return create_test_dfs(data)

    def _spy(self, monkeypatch):
        import modin_tpu.core.storage_formats.tpu.query_compiler as qc_mod

        calls = {"n": 0}
        orig = qc_mod.TpuQueryCompiler._try_shuffle_groupby_apply

        def wrapper(self, *a, **k):
            out = orig(self, *a, **k)
            if out is not None:
                calls["n"] += 1
            return out

        monkeypatch.setattr(
            qc_mod.TpuQueryCompiler, "_try_shuffle_groupby_apply", wrapper
        )
        return calls

    def _check(self, big, monkeypatch, fn, want_shuffle=True):
        from modin_tpu.utils import get_current_execution

        md, pdf = big
        if get_current_execution() != "TpuOnJax":
            eval_general(md, pdf, fn)
            return
        calls = self._spy(monkeypatch)
        eval_general(md, pdf, fn)
        if want_shuffle:
            assert calls["n"] >= 1, "expected the shuffle path to claim this"

    def test_multi_key(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby(["k", "j"]).apply(lambda g: g["v"].mean()),
        )

    def test_str_key(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby("city").apply(lambda g: g["v"].std()),
        )

    def test_str_plus_int_key(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby(["city", "j"]).apply(lambda g: g["v"].sum()),
        )

    def test_sort_false_appearance_order(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby("k", sort=False).apply(lambda g: g["v"].sum()),
        )

    def test_sort_false_multikey(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby(["k", "j"], sort=False).apply(
                lambda g: g["v"].sum()
            ),
        )

    def test_as_index_false_scalar(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby("k", as_index=False).apply(
                lambda g: g["v"].sum()
            ),
        )

    def test_as_index_false_and_sort_false(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby("k", sort=False, as_index=False).apply(
                lambda g: g["v"].sum()
            ),
        )

    def test_by_external_series(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby(df["city"]).apply(lambda g: g["v"].sum()),
        )

    def test_series_udf_single_group_chunks(self, monkeypatch):
        # n_groups <= shards: every chunk holds ONE group, pandas widens each
        # like-indexed Series result; the restack must reproduce the oracle
        import modin_tpu.core.storage_formats.tpu.query_compiler as qc_mod

        monkeypatch.setattr(qc_mod, "_SHUFFLE_APPLY_MIN_ROWS", 100)
        rng = np.random.default_rng(33)
        n = 4000
        md, pdf = create_test_dfs(
            {"k": rng.integers(0, 4, n), "v": rng.normal(size=n)}
        )
        eval_general(md, pdf, lambda df: df.groupby("k").apply(lambda g: g["v"] * 2))

    def test_constant_index_series_udf(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby("k").apply(
                lambda g: pandas.Series({"lo": g["v"].min(), "hi": g["v"].max()})
            ),
        )

    def test_constant_index_series_as_index_false(self, big, monkeypatch):
        self._check(
            big, monkeypatch,
            lambda df: df.groupby("k", as_index=False).apply(
                lambda g: pandas.Series({"lo": g["v"].min(), "hi": g["v"].max()})
            ),
        )

    def test_nan_keys_dropna_false(self, big, monkeypatch):
        md, pdf = big
        md = md.assign(fk=md["k"].where(md["k"] > 2, np.nan))
        pdf = pdf.assign(fk=pdf["k"].where(pdf["k"] > 2, np.nan))
        eval_general(
            md, pdf,
            lambda df: df.groupby("fk", dropna=False).apply(lambda g: g["v"].sum()),
        )


# ---------------------------------------------------------------------- #
# many groups: the sorted-tiles form (what a TPU takes above the masked
# scan's 1024 groups), forced on the CPU through the test hook
# ---------------------------------------------------------------------- #

_MANY_SHAPES = [
    "dense_range", "range_with_holes", "two_keys", "nan_key_kept",
    "half_the_rows_one_group", "length_no_multiple_of_the_chunk",
]
_MANY_DTYPES = ["int64", "float64_nan", "float32", "bool"]
# a chunk this small makes a frame many chunks, each sparse in the key: most
# blocks take further tiles, and the last chunk reaches back over the one before
_SMALL_CHUNK = 1 << 13


class _one_shard_tpu_choice:
    """A one-shard mesh and the forms a TPU would choose (or ``force``)."""

    def __init__(self, force="tpu", chunk=None):
        self.force, self.chunk = force, chunk

    def __enter__(self):
        from modin_tpu.config import MeshShape
        from modin_tpu.ops import groupby as gb_ops
        from modin_tpu.parallel.mesh import reset_mesh

        self.was = (gb_ops._FORCE_KERNEL, gb_ops._SORT_CHUNK, MeshShape.get())
        gb_ops._FORCE_KERNEL = self.force
        if self.chunk:
            gb_ops._SORT_CHUNK = self.chunk
        MeshShape.put((1, 1))
        reset_mesh()

    def __exit__(self, *exc):
        from modin_tpu.config import MeshShape
        from modin_tpu.ops import groupby as gb_ops
        from modin_tpu.parallel.mesh import reset_mesh

        gb_ops._FORCE_KERNEL, gb_ops._SORT_CHUNK, shape = self.was
        MeshShape.put(shape)
        reset_mesh()


def _many_groups_frame(groups, shape):
    rng = np.random.default_rng([groups, _MANY_SHAPES.index(shape)])
    n = 2 * groups + 11
    ids = np.concatenate([np.arange(groups), rng.integers(0, groups, n - groups)])
    rng.shuffle(ids)
    keys = {"k": ids}
    if shape == "range_with_holes":
        keys = {"k": ids * 10 - 3}
    elif shape == "two_keys":
        side = int(groups**0.5)
        keys = {"k": ids % side, "k2": ids // side}
    elif shape == "nan_key_kept":
        keys = {"k": np.where(ids == 5, np.nan, ids * 0.5)}
    elif shape == "half_the_rows_one_group":
        keys = {"k": np.where(np.arange(n) % 2 == 0, 7, ids)}
    values = {
        "int64": rng.integers(-50, 50, n),
        "float64_nan": np.where(rng.random(n) < 0.1, np.nan, rng.uniform(-1, 1, n)),
        "float32": rng.uniform(-1, 1, n).astype(np.float32),
        "bool": rng.random(n) < 0.5,
    }
    return pandas.DataFrame({**keys, **values}), list(keys)


_many_groups_answers = {}


def _many_groups_answer(groups, shape, agg):
    """pandas', the sorted tiles' and the segment form's answer to one
    aggregation of all four value columns, and the forms the first took."""
    import modin_tpu.observability as graftscope
    from modin_tpu.ops.groupby import clear_factorize_cache
    from modin_tpu.views import registry

    case = (groups, shape, agg)
    if case not in _many_groups_answers:
        pdf, by = _many_groups_frame(groups, shape)
        dropna = shape != "nan_key_kept"
        want = getattr(pdf.groupby(by, dropna=dropna), agg)()
        # (the CPU materialises a whole chunk's one-hot: a frame of 70 000
        # groups goes in four chunks, whatever its shape)
        chunk = _SMALL_CHUNK if shape in _MANY_SHAPES[-2:] else (1 << 15 if groups > 2_000 else None)
        got = {}
        for force in ("tpu", "segment"):
            with _one_shard_tpu_choice(force, chunk):
                md = pd.DataFrame(pdf)
                registry.reset()
                clear_factorize_cache()
                with graftscope.query_stats("many-groups") as stats:
                    answer = assert_no_fallback(
                        lambda: getattr(md.groupby(by, dropna=dropna), agg)()
                    )
                    got[force] = answer.modin.to_pandas()
                got[force + "_forms"] = dict(stats.groupby_forms)
        _many_groups_answers[case] = (want, got)
    return _many_groups_answers[case]


@pytest.mark.parametrize("dtype", _MANY_DTYPES)
@pytest.mark.parametrize("shape", _MANY_SHAPES)
@pytest.mark.parametrize("groups", [2_000, 70_000])
@pytest.mark.parametrize("agg", ["sum", "mean", "count"])
def test_sorted_tiles_match_pandas_and_the_segment_form(agg, groups, shape, dtype):
    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax":
        pytest.skip("device kernels")
    want, got = _many_groups_answer(groups, shape, agg)
    # every reduction took the new form; the histogram too where the key is an
    # integer range (a float key is factorised by a sort, without a histogram)
    assert got["tpu_forms"].get("sorted_tiles", 0) >= 1 and "segment" not in got["tpu_forms"]
    # (the two levels of two keys are narrow ranges, the Pallas kernel's; their
    # product's histogram is the wide one)
    assert got["tpu_forms"].get("pallas_bincount", 0) == (2 if shape == "two_keys" else 0)
    assert "scatter_counts" not in got["tpu_forms"]
    assert got["segment_forms"].get("segment", 0) >= 1
    tiles, segment = got["tpu"][dtype], got["segment"][dtype]
    assert tiles.index.equals(want.index) and tiles.dtype == want[dtype].dtype == segment.dtype
    if tiles.dtype.kind in "iu":
        np.testing.assert_array_equal(tiles.to_numpy(), want[dtype].to_numpy())
        np.testing.assert_array_equal(tiles.to_numpy(), segment.to_numpy())
    else:
        tol = 1e-12 if tiles.dtype == np.float64 else 2e-5
        np.testing.assert_allclose(tiles.to_numpy(), want[dtype].to_numpy(), rtol=tol, atol=tol)
        np.testing.assert_allclose(tiles.to_numpy(), segment.to_numpy(), rtol=tol, atol=tol)


@pytest.mark.parametrize(
    "n,width,chunk",
    [(777, 513, None), (50_000, 3_000, None), (123_457, 70_000, 1 << 14), (40_000, 4_000_000, None)],
)
def test_sorted_tiles_histogram_matches_bincount(n, width, chunk):
    """The histogram of a range wider than the Pallas kernel's: ids past the
    range (pads, dropped keys) count for nothing."""
    import jax.numpy as jnp

    from modin_tpu.ops import groupby as gb_ops

    rng = np.random.default_rng(n)
    ids_np = rng.integers(0, width + 1, n).astype(np.int32)
    ids_np[: n // 3] = width // 2  # a third of the rows one id
    with _one_shard_tpu_choice("tpu", chunk):
        ids = jnp.asarray(ids_np)
        assert gb_ops._histogram_form(ids, width) == "sorted_tiles"
        got = np.asarray(gb_ops._histogram(ids, width, "sorted_tiles"))
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, np.bincount(ids_np, minlength=width + 1)[:width])


def test_groupby_form_reads_group_count_platform_and_shards():
    """No option: the form of a reduction follows from the aggregation, the
    group count, the platform and the shard count."""
    import jax.numpy as jnp

    from modin_tpu.ops import groupby as gb_ops

    codes = jnp.zeros(8, jnp.int32)
    # on the CPU the scatter forms stay
    assert gb_ops._reduce_form("sum", codes, 100, None) == "segment"
    assert gb_ops._reduce_form("sum", codes, 70_000, None) == "segment"
    assert gb_ops._histogram_form(codes, 70_000) == "scatter_counts"
    with _one_shard_tpu_choice("tpu"):
        limit = gb_ops._MASKED_SCAN_MAX_GROUPS
        assert limit == 1024
        for agg in ("sum", "mean", "count"):
            assert gb_ops._reduce_form(agg, codes, limit, None) == "limb_dot"
            assert gb_ops._reduce_form(agg, codes, limit + 1, None) == "sorted_tiles"
            assert gb_ops._reduce_form(agg, codes, gb_ops._RANGE_LIMIT, None) == "sorted_tiles"
            assert gb_ops._reduce_form(agg, codes, gb_ops._RANGE_LIMIT + 1, None) == "segment"
        for agg in ("min", "max", "prod", "any", "all"):
            assert gb_ops._reduce_form(agg, codes, limit, None) == "masked_scan"
        # max / min take the tiles past the scan's limit, the others scatter
        for agg in ("min", "max"):
            assert gb_ops._reduce_form(agg, codes, limit + 1, None) == "sorted_tiles"
            assert gb_ops._reduce_form(agg, codes, gb_ops._RANGE_LIMIT + 1, None) == "segment"
        for agg in ("prod", "any", "all"):
            assert gb_ops._reduce_form(agg, codes, limit + 1, None) == "segment"
        # the centred spread walks the tiles twice at every group count
        for agg in ("var", "std", "sem"):
            assert gb_ops._reduce_form(agg, codes, 100, None) == "sorted_tiles"
            assert gb_ops._reduce_form(agg, codes, limit + 1, None) == "sorted_tiles"
            assert gb_ops._reduce_form(agg, codes, gb_ops._RANGE_LIMIT + 1, None) == "segment"
            assert gb_ops._form_name("sorted_tiles", agg) == "sorted_tiles_" + agg
        for agg in ("sum", "mean", "count"):
            assert gb_ops._form_name("sorted_tiles", agg) == "sorted_tiles"
        assert gb_ops._reduce_form("size", codes, limit + 1, np.ones(limit + 1)) == "host_sizes"
        assert gb_ops._reduce_form("size", codes, limit + 1, None) == "sorted_tiles"
        assert gb_ops._histogram_form(codes, 512) == "pallas_bincount"
        assert gb_ops._histogram_form(codes, 513) == "sorted_tiles"
        # the limbs take integers, bools and the two float widths
        for dtype in (np.int64, np.uint64, np.int32, np.uint8, bool, np.float32, np.float64):
            assert gb_ops._reduce_form("sum", codes, 100, None, [jnp.zeros(8, dtype)]) == "limb_dot"
        assert gb_ops._reduce_form("sum", codes, 100, None, [jnp.zeros(8, jnp.float16)]) == "masked_scan"
    with _one_shard_tpu_choice("masked_scan"):
        # the hook's other value: the scan where the limbs would be chosen
        for agg in ("sum", "mean", "count", "min"):
            assert gb_ops._reduce_form(agg, codes, 100, None) == "masked_scan"
        assert gb_ops._reduce_form("sum", codes, 1025, None) == "sorted_tiles"
    with _one_shard_tpu_choice("segment"):
        assert gb_ops._reduce_form("sum", codes, 100, None) == "segment"
    # a row-sharded mesh keeps the segment ops above the masked scan's limit
    from modin_tpu.config import MeshShape
    from modin_tpu.parallel.mesh import num_row_shards, reset_mesh

    with _one_shard_tpu_choice("tpu"):
        MeshShape.put((4, 1))
        reset_mesh()
        assert num_row_shards() == 4
        assert gb_ops._reduce_form("sum", codes, 1024, None) == "masked_scan"
        assert gb_ops._reduce_form("sum", codes, 1025, None) == "segment"
        assert gb_ops._reduce_form("max", codes, 1025, None) == "segment"
        assert gb_ops._reduce_form("std", codes, 100, None) == "segment"
        assert gb_ops._histogram_form(codes, 70_000) == "scatter_counts"


def test_query_stats_record_holds_groupby_forms_and_spans():
    """``df.groupby(k).sum()``: the request's record counts the form of the
    histogram and of the reduction, and both run under a QUERY-COMPILER span
    that says which form, of how many groups."""
    import modin_tpu.observability as graftscope
    from modin_tpu.ops.groupby import clear_factorize_cache
    from modin_tpu.utils import get_current_execution
    from modin_tpu.views import registry

    if get_current_execution() != "TpuOnJax":
        pytest.skip("device kernels")
    pdf, _ = _many_groups_frame(2_000, "dense_range")
    pdf = pdf[["k", "int64", "float64_nan"]]
    for force, forms in (
        ("tpu", {"sorted_tiles": 2}),
        (None, {"scatter_counts": 1, "segment": 1}),
    ):
        with _one_shard_tpu_choice(force):
            md = pd.DataFrame(pdf)
            md._query_compiler.execute()
            registry.reset()
            clear_factorize_cache()
            with graftscope.profile() as prof, graftscope.query_stats("forms") as stats:
                got = assert_no_fallback(lambda: md.groupby("k").sum())
                got._query_compiler.execute()
        df_equals(got, pdf.groupby("k").sum())
        assert stats.groupby_forms == forms
        record = graftscope.recent_queries("forms")[-1]
        assert record["groupby_forms"] == forms
        spans = {sp.name: sp for sp in prof.spans if sp.name.startswith("groupby.")}
        assert set(spans) == {"groupby.factorize", "groupby.reduce"}
        assert all(sp.layer == "QUERY-COMPILER" for sp in spans.values())
        reduce_form = "sorted_tiles" if force else "segment"
        # (a span under which a program compiled also carries ``compile_s``)
        assert spans["groupby.reduce"].attrs.items() >= {
            "form": reduce_form, "agg": "sum", "num_groups": 2_000, "n_cols": 2,
        }.items()
        assert spans["groupby.factorize"].attrs.items() >= {
            "form": "sorted_tiles" if force else "scatter_counts", "width": 2_000,
        }.items()
        programs = stats.launches_by_program
        assert ("groupby_sorted_tiles_sum" in programs) == bool(force)
        # a dense wide range's codes are not written out for the sorted tiles
        assert ("groupby_range_ids" in programs) == (not force)
        assert ("groupby_segment_agg" in programs) == (not force)


@pytest.mark.parametrize(
    "ask",
    [
        lambda g: g.median(),
        lambda g: g.nunique(),
        lambda g: g.first(),
        lambda g: g.min(),
        lambda g: g.var(),
        lambda g: g.size(),
        lambda g: g.cumsum(),
        lambda g: g.transform("sum"),
        lambda g: g.last(),
    ],
    ids=["median", "nunique", "first", "min", "var", "size", "cumsum", "transform", "last"],
)
def test_codes_not_written_out_serve_every_other_consumer(ask):
    """After a many-groups sum the factorisation's memo holds ``RangeCodes``
    (the key and its minimum, no array); what is not the sorted tiles writes
    them out through ``codes_array`` and answers as before."""
    from modin_tpu.ops import groupby as gb_ops
    from modin_tpu.utils import get_current_execution
    from modin_tpu.views import registry

    if get_current_execution() != "TpuOnJax":
        pytest.skip("device kernels")
    pdf, _ = _many_groups_frame(2_000, "dense_range")
    pdf = pdf[["k", "int64", "float64_nan"]]
    with _one_shard_tpu_choice("tpu"):
        md = pd.DataFrame(pdf)
        registry.reset()
        gb_ops.clear_factorize_cache()
        df_equals(md.groupby("k").sum(), pdf.groupby("k").sum())
        memo = [result[0] for _, _, result in gb_ops._FACTORIZE_CACHE]
        assert [type(codes) for codes in memo] == [gb_ops.RangeCodes]
        assert memo[0]._array is None and memo[0].shape == (len(pdf),)
        df_equals(ask(md.groupby("k")), ask(pdf.groupby("k")))


# ---------------------------------------------------------------------- #
# limb_dot: sum / mean / count of a few groups as an exact contraction
# ---------------------------------------------------------------------- #

_LIMB_DTYPES = ["int64_wraps", "uint64", "int32", "bool", "float32", "float64_nan"]


def _limb_column(dtype, n, rng):
    if dtype == "int64_wraps":
        return rng.integers(-(2**62), 2**62, n) | 1  # a dozen a group pass 2**63
    if dtype == "uint64":
        return rng.integers(0, 2**64, n, dtype=np.uint64)
    if dtype == "int32":
        return rng.integers(-(2**31), 2**31, n).astype(np.int32)
    if dtype == "bool":
        return rng.random(n) < 0.5
    if dtype == "float32":
        return rng.uniform(-1, 1, n).astype(np.float32)
    return np.where(rng.random(n) < 0.1, np.nan, np.round(rng.uniform(0, 100, n), 6))


def _reduce_with(force, agg, column, codes_np, groups, sizes=None, limb_chunk=None):
    """``groupby_reduce`` of one column on a one-shard mesh under the test
    hook ``force``: the answer, on the host, and the form it took."""
    import jax.numpy as jnp

    from modin_tpu.ops import groupby as gb_ops
    from modin_tpu.ops.structural import pad_len

    with _one_shard_tpu_choice(force):
        chunk_was = gb_ops._LIMB_CHUNK
        gb_ops._LIMB_CHUNK = limb_chunk or chunk_was
        try:
            cols, codes = [jnp.asarray(column)], jnp.asarray(codes_np)
            form = gb_ops._reduce_form(agg, codes, groups, sizes, cols)
            out = gb_ops.groupby_reduce(agg, cols, codes, groups, len(codes_np), sizes=sizes)
            # (padded to the shard multiple like every form's result)
            assert out[0].shape == (pad_len(groups),)
        finally:
            gb_ops._LIMB_CHUNK = chunk_was
    return np.asarray(out[0])[:groups], form


@pytest.mark.parametrize("with_sizes", [False, True], ids=["no_sizes", "sizes"])
@pytest.mark.parametrize("dtype", _LIMB_DTYPES)
@pytest.mark.parametrize("agg", ["sum", "mean", "count"])
def test_limb_dot_matches_pandas_and_the_scan(agg, dtype, with_sizes):
    """The limbs' sums are pandas': integers to the bit (a sum past 2**63
    wraps as numpy's does), floats to a rounding; and the scan's."""
    rng = np.random.default_rng([_LIMB_DTYPES.index(dtype), 5])
    n, groups = 9_000, 13
    codes_np = rng.integers(0, groups, n).astype(np.int32)
    column = _limb_column(dtype, n, rng)
    if dtype == "bool" and agg != "count":
        column = column.astype(np.int64)  # as the query compiler hands it over
    sizes = np.bincount(codes_np, minlength=groups).astype(np.int64) if with_sizes else None
    got, form = _reduce_with("tpu", agg, column, codes_np, groups, sizes)
    assert form == "limb_dot"
    want = getattr(pandas.Series(column).groupby(codes_np), agg)()
    assert got.dtype == want.dtype
    if dtype == "int32":
        # (the scan, which summed narrow integers in their own width and
        # failed, now widens them like pandas)
        assert got.dtype == (np.float64 if agg == "mean" else np.int64)
    scan, scan_form = _reduce_with("masked_scan", agg, column, codes_np, groups, sizes)
    assert scan_form == "masked_scan" and scan.dtype == got.dtype
    if got.dtype.kind in "iu":
        np.testing.assert_array_equal(got, want.to_numpy())
        np.testing.assert_array_equal(got, scan)
    elif column.dtype.kind in "iub":
        # an integer mean divides the (wrapped) sum: the scan's to the bit,
        # pandas' where nothing wrapped
        np.testing.assert_array_equal(got, scan)
        if dtype not in ("int64_wraps", "uint64"):
            np.testing.assert_allclose(got, want.to_numpy(), rtol=1e-15)
    else:
        # (sums of +-1 near zero: the others' float32 additions show absolutely)
        tol = {"rtol": 2e-6, "atol": 2e-5} if dtype == "float32" else {"rtol": 1e-13}
        np.testing.assert_allclose(got, want.to_numpy(), **tol)
        np.testing.assert_allclose(got, scan, **tol)


def _fsum_by_group(column, codes_np, groups):
    import math

    return np.array(
        [
            math.fsum(v for v in column[codes_np == g].tolist() if not math.isnan(v))
            for g in range(groups)
        ]
    ).astype(column.dtype)


_FSUM_CASES = ["signs_that_cancel", "magnitudes_1e-12_to_1e12", "negative_zeros", "denormals", "float32"]


@pytest.mark.parametrize("case", _FSUM_CASES)
def test_limb_dot_float_sum_is_the_exact_sum_rounded_once(case):
    """A float column's sum is ``math.fsum`` of the group, to the bit."""
    rng = np.random.default_rng(_FSUM_CASES.index(case))
    n, groups = 20_000, 7
    codes_np = rng.integers(0, groups, n).astype(np.int32)
    if case == "signs_that_cancel":
        half = rng.uniform(1, 2, n // 2) * 1e9
        column = np.concatenate([half, -half + rng.uniform(-1, 1, n // 2) * 1e-7])
        codes_np[n // 2:] = codes_np[: n // 2]
    elif case == "magnitudes_1e-12_to_1e12":
        column = rng.uniform(-1, 1, n) * 10.0 ** rng.integers(-12, 13, n)
    elif case == "negative_zeros":
        column = np.where(rng.random(n) < 0.5, -0.0, rng.uniform(-1, 1, n))
        column[codes_np == 3] = -0.0
    elif case == "denormals":
        column = rng.integers(-1000, 1000, n) * 5e-324
    else:
        column = (rng.uniform(-1, 1, n) * 10.0 ** rng.integers(-6, 7, n)).astype(np.float32)
    got, form = _reduce_with("tpu", "sum", column, codes_np, groups)
    assert form == "limb_dot" and got.dtype == column.dtype
    scan, _ = _reduce_with("masked_scan", "sum", column, codes_np, groups)
    if case == "denormals":
        # past float32's range at the low end the column takes the scan, and
        # XLA's CPU code flushes denormals as it always did
        np.testing.assert_array_equal(got, scan)
        return
    np.testing.assert_array_equal(got, _fsum_by_group(column, codes_np, groups))
    # the scan's additions round one by one: close, and not the same
    assert not np.array_equal(scan, got) or case == "negative_zeros"
    scale = np.abs(column).max() * (n / groups)
    np.testing.assert_allclose(got, scan, rtol=0, atol=scale * (1e-5 if case == "float32" else 1e-13))


@pytest.mark.parametrize("agg", ["sum", "mean"])
@pytest.mark.parametrize("case", ["an_infinity", "a_span_past_the_limbs", "past_float32_range"])
def test_limb_dot_leaves_a_column_it_cannot_cut_to_the_scan(case, agg):
    """An infinity, or magnitudes further apart than the limbs' 256 bits: the
    same program answers with the scan's sums, to the bit."""
    rng = np.random.default_rng(11)
    n, groups = 20_000, 5
    codes_np = rng.integers(0, groups, n).astype(np.int32)
    column = rng.uniform(-1, 1, n)
    if case == "an_infinity":
        column[rng.integers(0, n, 20)] = np.inf
        column[codes_np == 2] = np.abs(column[codes_np == 2])  # no inf - inf there
    elif case == "a_span_past_the_limbs":
        column = (column * 10.0 ** rng.integers(-35, 36, n)).astype(np.float32)
    else:
        column = column * 10.0 ** rng.integers(-60, 60, n)
    got, form = _reduce_with("tpu", agg, column, codes_np, groups)
    scan, _ = _reduce_with("masked_scan", agg, column, codes_np, groups)
    assert form == "limb_dot"
    np.testing.assert_array_equal(got, scan)
    if case != "an_infinity":
        # (and the scan's order of additions shows: these are not the exact sums)
        sums, _ = _reduce_with("tpu", "sum", column, codes_np, groups)
        assert not np.array_equal(sums, _fsum_by_group(column, codes_np, groups))


@pytest.mark.parametrize("dtype", ["int64_wraps", "float64_nan"])
@pytest.mark.parametrize(
    "n,limb_chunk",
    [(777, None), (8_192 * 3 + 77, None), (8_192 * 5 + 1_001, 8_192 * 2), (8_192 * 4, 8_192 * 2)],
    ids=["under_a_block", "blocks_and_a_rest", "chunks_and_a_rest", "whole_chunks"],
)
def test_limb_dot_rows_no_multiple_of_block_or_chunk(n, limb_chunk, dtype):
    """Rows past the last block are padded into the dropped bucket; the last
    chunk reaches back over rows the one before has summed, and masks them."""
    rng = np.random.default_rng(n)
    groups = 9
    codes_np = rng.integers(0, groups + 1, n).astype(np.int32)  # some rows dropped
    column = _limb_column(dtype, n, rng)
    kept = codes_np < groups
    for agg in ("sum", "count"):
        got, form = _reduce_with("tpu", agg, column, codes_np, groups, limb_chunk=limb_chunk)
        assert form == "limb_dot"
        want = getattr(pandas.Series(column[kept]).groupby(codes_np[kept]), agg)().to_numpy()
        if dtype == "float64_nan" and agg == "sum":
            np.testing.assert_array_equal(got, _fsum_by_group(column[kept], codes_np[kept], groups))
        else:
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", ["int64_wraps", "float64_nan"])
@pytest.mark.parametrize("groups", [1, 100, 128, 129, 1024])
def test_limb_dot_group_counts_around_the_lane_tiles(groups, dtype):
    """The one-hot is built a tile of 128 groups at a time, the dropped
    bucket among them: 1, 100, 128 (the bucket opens a tile), 129, 1024."""
    rng = np.random.default_rng(groups)
    n = 12_000
    codes_np = np.concatenate([np.arange(groups), rng.integers(0, groups + 1, n - groups)])
    codes_np = codes_np.astype(np.int32)
    column = _limb_column(dtype, n, rng)
    kept = codes_np < groups
    sizes = np.bincount(codes_np[kept], minlength=groups).astype(np.int64)
    got, form = _reduce_with("tpu", "mean", column, codes_np, groups, sizes)
    assert form == "limb_dot"
    scan, _ = _reduce_with("masked_scan", "mean", column, codes_np, groups, sizes)
    if dtype == "int64_wraps":
        np.testing.assert_array_equal(got, scan)
    else:
        exact = _fsum_by_group(column[kept], codes_np[kept], groups)
        valid = pandas.Series(column[kept]).groupby(codes_np[kept]).count().to_numpy()
        with np.errstate(invalid="ignore"):
            np.testing.assert_array_equal(got, exact / valid)
        np.testing.assert_allclose(got, scan, rtol=1e-13)


@pytest.mark.parametrize("agg", ["sum", "mean", "count"])
def test_groupby_limb_dot_through_the_api(agg):
    """``df.groupby(k).agg()`` with the TPU's choice forced: every dtype of the
    shared frame, the Pallas histogram and the limbs, no fallback."""
    import modin_tpu.observability as graftscope
    from modin_tpu.ops.groupby import clear_factorize_cache
    from modin_tpu.utils import get_current_execution
    from modin_tpu.views import registry

    if get_current_execution() != "TpuOnJax":
        pytest.skip("device kernels")
    pdf = pandas.DataFrame(GB_DATA)[["int_key", "val_int", "val_float", "val_bool"]]
    with _one_shard_tpu_choice("tpu"):
        md = pd.DataFrame(pdf)
        registry.reset()
        clear_factorize_cache()
        with graftscope.query_stats("limb-dot") as stats:
            got = assert_no_fallback(lambda: getattr(md.groupby("int_key"), agg)())
            got._query_compiler.execute()
    df_equals(got, getattr(pdf.groupby("int_key"), agg)())
    assert stats.groupby_forms == {"pallas_bincount": 1, "limb_dot": 1}
