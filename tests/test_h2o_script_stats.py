"""The H2O db-benchmark's advanced questions 6 and 7 as its pandas script
writes them, and what carries them on the device: the sorted tiles' max / min
and centred var / std / sem, the per-group median of one sort and a binary
search (``ops/groupby.py`` ``groupby_quantile``), and a dict of lists as the
``agg``.

Plain pandas on the same seeded data is the reference of every case.  A device
case asserts that nothing fell back, that every column of the answer is
resident, and ``assert_frame_equal(check_dtype=True)``; the TPU's forms are
forced on one shard as ``tests/test_groupby.py`` forces the tiles.
"""

import itertools
from fractions import Fraction

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
from modin_tpu.observability import query_stats
from modin_tpu.utils import get_current_execution

from tests.test_groupby import _one_shard_tpu_choice
from tests.test_h2o_script_phrasing import _h2o_table, _resident
from tests.utils import assert_no_fallback

SCRIPT = dict(as_index=False, sort=False, observed=True, dropna=False)

# the script's lines, letter for letter
_QUESTIONS = {
    "q6": lambda x: x.groupby(['id4', 'id5'], as_index=False, sort=False, observed=True, dropna=False).agg({'v3': ['median', 'std']}),
    "q7": lambda x: x.groupby('id3', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'max', 'v2': 'min'}).assign(range_v1_v2=lambda x: x['v1'] - x['v2'])[['id3', 'range_v1_v2']],
}
_SCATTERS = ("segment", "scatter_counts")
# a chunk of the tiles this small makes a frame many chunks
_CHUNK = 1 << 12


def _device_only():
    if get_current_execution() != "TpuOnJax":
        pytest.skip("device paths")


def _fresh():
    from modin_tpu.ops.groupby import clear_factorize_cache
    from modin_tpu.views import registry

    registry.reset()
    clear_factorize_cache()


def _ask(question, pdf, force="tpu", rtol=1e-10, want=None):
    """``question`` under both libraries, the device's answer in the forms
    ``force`` says (None: the platform's own): no fallback, every column
    resident, equal to pandas (or to ``want``).  Returns the request's
    ``groupby_forms``."""
    _device_only()
    want = question(pdf) if want is None else want
    ctx = _one_shard_tpu_choice(force, _CHUNK) if force else _no_context()
    with ctx:
        md = pd.DataFrame(pdf)
        _fresh()
        with query_stats("stats") as stats:
            got = assert_no_fallback(lambda: question(md))
        assert all(_resident(got)), _resident(got)
        got = got.modin.to_pandas()
    if isinstance(want, pandas.Series):
        pandas.testing.assert_series_equal(got, want, check_dtype=True, rtol=rtol, atol=0)
    else:
        pandas.testing.assert_frame_equal(got, want, check_dtype=True, rtol=rtol, atol=0)
    return dict(stats.groupby_forms)


class _no_context:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# ---------------------------------------------------------------- q6, q7 --


@pytest.mark.parametrize("force", ["tpu", None], ids=["tpu_forms", "platform_forms"])
@pytest.mark.parametrize("name", list(_QUESTIONS))
def test_script_question_verbatim(name, force):
    # q6: 144 groups of id4 x id5; q7: 2000 groups of id3, past the masked scan
    pdf = _h2o_table(rows=8000, seed=39, groups_k=12 if name == "q6" else 4)
    forms = _ask(_QUESTIONS[name], pdf, force)
    if force == "tpu":
        assert not any(f in forms for f in _SCATTERS), forms
        if name == "q6":
            assert forms.get("median_sort_select") == 1 and forms.get("sorted_tiles_std") == 1, forms
        else:
            assert forms.get("sorted_tiles_max") == 1 and forms.get("sorted_tiles_min") == 1, forms
    elif name == "q6":
        # the CPU's own forms: the median's one sort (every platform's) and
        # the segment ops
        assert forms.get("median_sort_select") == 1 and forms.get("segment") == 1, forms


def test_q6_answer_is_laid_out_as_pandas_lays_it_out():
    pdf = _h2o_table(rows=3000, seed=5, groups_k=6)
    _ask(_QUESTIONS["q6"], pdf)
    got = _QUESTIONS["q6"](pd.DataFrame(pdf)).modin.to_pandas()
    assert got.columns.tolist() == [("id4", ""), ("id5", ""), ("v3", "median"), ("v3", "std")]
    assert isinstance(got.index, pandas.RangeIndex)


# ------------------------------------------- aggregations x dtypes x groups --


def _stats_frame(groups, seed):
    rng = np.random.default_rng([groups, seed])
    n = 3 * groups + 17
    k = np.concatenate([np.arange(groups), rng.integers(0, groups, n - groups)])
    rng.shuffle(k)
    f = rng.normal(size=n) * 10
    f[rng.random(n) < 0.1] = np.nan
    return pandas.DataFrame(
        {
            "k": k,
            "int64": rng.integers(-10**12, 10**12, n),
            "int32": rng.integers(-1000, 1000, n).astype(np.int32),
            "float64_nan": f,
            "float32_nan": (f + 100).astype(np.float32),
            "bool": rng.random(n) < 0.5,
        }
    )


_AGGS = ["max", "min", "median", "std", "var"]


@pytest.mark.parametrize("groups", [300, 3000], ids=["under_1024", "over_1024"])
@pytest.mark.parametrize("dtype", ["int64", "int32", "float64_nan", "float32_nan", "bool"])
@pytest.mark.parametrize("agg", _AGGS)
def test_aggregation_in_the_tpu_forms(agg, dtype, groups):
    pdf = _stats_frame(groups, 1)[["k", dtype]]
    want = None
    if dtype == "float32_nan" and agg in ("std", "var"):
        # pandas sums a float32 spread in float32 (up to 7e-4 from the exact
        # one here), the tiles in float64: pandas' float64 answer, rounded
        want = getattr(pdf.astype({dtype: np.float64}).groupby("k"), agg)().astype(np.float32)
    forms = _ask(lambda x: getattr(x.groupby("k"), agg)(), pdf, rtol=1e-9 if want is None else 2.5e-7, want=want)
    assert not any(f in forms for f in _SCATTERS), forms
    if agg == "median":
        assert forms.get("median_sort_select") == 1, forms
    elif agg in ("std", "var"):
        assert forms.get("sorted_tiles_" + agg) == 1, forms
    else:
        assert forms.get("sorted_tiles_" + agg if groups > 1024 else "masked_scan") == 1, forms


@pytest.mark.parametrize("interpolation", ["linear", "lower", "higher", "midpoint", "nearest"])
@pytest.mark.parametrize("dtype", ["int64", "int32", "float64_nan", "float32_nan"])
def test_quantile_of_one_sort(dtype, interpolation):
    """Every quantile takes the median's one sort: an integer column's
    lower / higher / nearest sorted in its own dtype (int64 exactly, as
    pandas keeps it), the rest in float64."""
    pdf = _stats_frame(1500, 3)[["k", dtype]]
    forms = _ask(lambda x: x.groupby("k").quantile(0.3, interpolation=interpolation), pdf)
    assert forms.get("quantile_sort_select") == 1 and not any(f in forms for f in _SCATTERS), forms


# ------------------------------------------------------------- edge cases --


def _edge_frame(case):
    """A frame of a few hundred rows whose groups are what ``case`` names."""
    rng = np.random.default_rng(_EDGES.index(case))
    if case == "one_row_groups":
        k = np.arange(1500)
        v = rng.normal(size=1500)
    elif case == "even_and_odd_sizes":
        sizes = np.arange(1, 60)
        k = np.repeat(np.arange(len(sizes)), sizes)
        v = rng.normal(size=len(k))
    elif case == "all_nan_groups":
        k = rng.integers(0, 1200, 5000)
        v = rng.normal(size=5000)
        v[k % 7 == 0] = np.nan
    elif case == "ties":
        k = rng.integers(0, 1100, 6000)
        v = rng.integers(0, 3, 6000).astype(np.float64)
    elif case == "infinities":
        k = rng.integers(0, 1300, 5000)
        v = rng.normal(size=5000)
        v[rng.random(5000) < 0.05] = np.inf
        v[rng.random(5000) < 0.05] = -np.inf
        v[k == 3] = -np.inf  # a group whose max is -inf
        v[k == 4] = np.nan
    elif case == "large_mean_small_spread":
        k = rng.integers(0, 1100, 6000)
        v = 1e6 + rng.uniform(0, 1e-3, 6000)
    k = np.asarray(k)
    order = rng.permutation(len(k))
    return pandas.DataFrame({"k": k[order], "v": np.asarray(v)[order]})


_EDGES = ["one_row_groups", "even_and_odd_sizes", "all_nan_groups", "ties", "infinities", "large_mean_small_spread"]


@pytest.mark.parametrize("agg", _AGGS)
@pytest.mark.parametrize("case", _EDGES)
def test_edge_case(case, agg):
    pdf = _edge_frame(case)
    if case == "large_mean_small_spread" and agg in ("std", "var"):
        # groups of about five rows: pandas' own Welford sums are up to 5e-4
        # from the exact spread here, so the exact one is the reference
        _device_only()
        with _one_shard_tpu_choice("tpu", _CHUNK):
            md = pd.DataFrame(pdf)
            _fresh()
            got = assert_no_fallback(lambda: getattr(md.groupby("k")["v"], agg)())
            got = got.modin.to_pandas()
        exact = pdf.groupby("k")["v"].agg(_exact_var)
        if agg == "std":
            exact = np.sqrt(exact)
        np.testing.assert_allclose(got.to_numpy(), exact.to_numpy(), rtol=1e-8)
        return
    _ask(lambda x: getattr(x.groupby("k"), agg)(), pdf)


def _exact_var(values):
    if len(values) < 2:
        return np.nan
    fr = [Fraction(float(v)) for v in values]
    mean = sum(fr) / len(fr)
    return float(sum((v - mean) ** 2 for v in fr) / (len(fr) - 1))


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("groups", [200, 2000], ids=["under_1024", "over_1024"])
def test_centred_variance_where_the_naive_one_fails(groups, dtype):
    """Values near 1e6 (float32: 1e4) with a spread of 1e-3 (1): the centred
    var agrees with the exact one to 1e-8 (pandas' own Welford sums are 3e-7
    from it here, so the comparison with pandas is to 1e-5); a float32
    column's is summed in float64 too, so it is the exact var rounded to
    float32 (sums in float32 are 6e-7 off here), and Σx² − (Σx)²/n in the
    column's dtype is off by far more than the variance itself."""
    _device_only()
    rng = np.random.default_rng(groups)
    n = (12 if dtype == "float64" else 200) * groups
    mean, spread = (1e6, 1e-3) if dtype == "float64" else (1e4, 1.0)
    v = (mean + rng.uniform(0, spread, n)).astype(dtype)
    pdf = pandas.DataFrame({"k": rng.integers(0, groups, n), "v": v})
    with _one_shard_tpu_choice("tpu", _CHUNK):
        md = pd.DataFrame(pdf)
        _fresh()
        got = assert_no_fallback(lambda: md.groupby("k")["v"].var()).modin.to_pandas()
    assert got.dtype == dtype
    exact = pdf.groupby("k")["v"].agg(_exact_var).astype(dtype)
    np.testing.assert_allclose(
        got.to_numpy(), exact.to_numpy(), rtol=1e-8 if dtype == "float64" else 2.5e-7
    )
    grouped = pdf.assign(v2=pdf.v**2).groupby("k")
    count = grouped["v"].count()
    naive = (grouped["v2"].sum() - grouped["v"].sum() ** 2 / count) / (count - 1)
    assert np.max(np.abs(naive.to_numpy() - exact.to_numpy()) / exact.to_numpy()) > 1


# --------------------------------------------------- keywords, dict of lists --


def _keyword_frame():
    rng = np.random.default_rng(17)
    n = 4000
    k = rng.integers(0, 1500, n).astype(np.float64)
    k[rng.random(n) < 0.02] = np.nan
    return pandas.DataFrame(
        {"k": k, "v": rng.normal(size=n), "w": rng.integers(-99, 99, n)}
    )


@pytest.mark.parametrize("sort,as_index,dropna", list(itertools.product([True, False], repeat=3)))
def test_dict_of_lists_under_the_keywords(sort, as_index, dropna):
    pdf = _keyword_frame()
    keywords = dict(sort=sort, as_index=as_index, dropna=dropna)
    _ask(
        lambda x: x.groupby("k", **keywords).agg({"v": ["median", "std"], "w": "max"}),
        pdf,
        rtol=1e-9,
    )


# ------------------------------------------------------- a row-sharded mesh --


@pytest.mark.parametrize("agg", _AGGS)
def test_row_sharded_frame_keeps_the_scatter_forms(agg):
    """The tests' own mesh (8 virtual devices, row-sharded) under the TPU's
    choice: var / std and max / min above 1024 groups keep the segment ops,
    the median takes its one sort as on one shard, and every answer stays
    equal."""
    _device_only()
    from modin_tpu.ops import groupby as gb_ops
    from modin_tpu.parallel.mesh import num_row_shards

    pdf = _stats_frame(2000, 2)[["k", "int64", "float64_nan"]]
    want = getattr(pdf.groupby("k"), agg)()
    was = gb_ops._FORCE_KERNEL
    gb_ops._FORCE_KERNEL = "tpu"
    try:
        assert num_row_shards() > 1
        md = pd.DataFrame(pdf)
        _fresh()
        with query_stats("sharded") as stats:
            got = assert_no_fallback(lambda: getattr(md.groupby("k"), agg)()).modin.to_pandas()
    finally:
        gb_ops._FORCE_KERNEL = was
    forms = dict(stats.groupby_forms)
    assert forms.get("median_sort_select" if agg == "median" else "segment") == 1, forms
    pandas.testing.assert_frame_equal(got, want, check_dtype=True, rtol=1e-10, atol=0)
