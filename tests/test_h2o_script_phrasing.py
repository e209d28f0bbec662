"""The H2O db-benchmark groupby as its pandas script phrases it
(``as_index=False, sort=False, observed=True, dropna=False`` on the ``category``
keys id1-id3), and what carries it: resident category columns, a category key
as a dense integer key of known range, order of first appearance without a
scatter, key columns re-inserted on the device.

Every case is compared with plain pandas on the same seeded data; a device
case also asserts that nothing fell back and that every column of the answer
is resident.
"""

import importlib.util
import itertools
import os
import warnings

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
from modin_tpu.observability import query_stats
from modin_tpu.utils import get_current_execution

from tests.test_groupby import _one_shard_tpu_choice
from tests.utils import assert_no_fallback

SCRIPT = dict(as_index=False, sort=False, observed=True, dropna=False)
_BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")


def _device_only():
    if get_current_execution() != "TpuOnJax":
        pytest.skip("device paths")


def _h2o_table(rows=4000, seed=7, groups_k=10):
    """The committed generator's table (id3 / id6: rows // groups_k groups)."""
    spec = importlib.util.spec_from_file_location(
        "h2o_groupby_dataset", os.path.join(_BENCH, "datasets", "h2o_groupby.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return pandas.DataFrame(module.make(seed, {"groups_k": groups_k}, rows))


def _resident(answer):
    return [c.is_device for c in answer._query_compiler._modin_frame._columns]


def _equal(got, want):
    got = got.modin.to_pandas() if hasattr(got, "modin") else got
    if isinstance(want, pandas.Series):
        pandas.testing.assert_series_equal(
            got, want, check_dtype=True, check_categorical=True, rtol=1e-10, atol=0
        )
    else:
        pandas.testing.assert_frame_equal(
            got, want, check_dtype=True, check_categorical=True, rtol=1e-10, atol=0
        )


def _on_device(question, md, pdf):
    """``question`` under both libraries: no fallback, a resident answer,
    equal to pandas in values, order, labels, index and dtypes."""
    _device_only()
    want = question(pdf)
    got = assert_no_fallback(lambda: question(md))
    assert all(_resident(got)), _resident(got)
    _equal(got, want)
    return got


# the script's lines, letter for letter (q4 / q5: its integer keys)
_SCRIPT_QUESTIONS = {
    "q1": lambda x: x.groupby('id1', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum'}),
    "q2": lambda x: x.groupby(['id1', 'id2'], as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum'}),
    "q3": lambda x: x.groupby('id3', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum', 'v3': 'mean'}),
    "q4": lambda x: x.groupby('id4', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'mean', 'v2': 'mean', 'v3': 'mean'}),
    "q5": lambda x: x.groupby('id6', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum', 'v2': 'sum', 'v3': 'sum'}),
}
# q7 / q10 of the script: their aggregations may stay with pandas (counted)
_SCRIPT_LONG_TAIL = {
    "q7": lambda x: x.groupby('id3', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'max', 'v2': 'min'}).assign(range_v1_v2=lambda x: x['v1'] - x['v2'])[['id3', 'range_v1_v2']],
    "q10": lambda x: x.groupby(['id1', 'id2', 'id3', 'id4', 'id5', 'id6'], as_index=False, sort=False, observed=True, dropna=False).agg({'v3': 'sum', 'v1': 'size'}),
}


@pytest.fixture(scope="module")
def h2o():
    pdf = _h2o_table()
    return pd.DataFrame(pdf), pdf


@pytest.mark.parametrize("name", list(_SCRIPT_QUESTIONS))
def test_script_question_verbatim_on_the_device(h2o, name):
    md, pdf = h2o
    got = _on_device(_SCRIPT_QUESTIONS[name], md, pdf)
    assert isinstance(got.modin.to_pandas().index, pandas.RangeIndex)


@pytest.mark.parametrize("name", list(_SCRIPT_LONG_TAIL))
def test_script_long_tail_is_equal_on_the_device_or_a_counted_fallback(h2o, name):
    md, pdf = h2o
    want = _SCRIPT_LONG_TAIL[name](pdf)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = _SCRIPT_LONG_TAIL[name](md)
    fell_back = [w for w in caught if "defaulting to in-process pandas" in str(w.message)]
    if not fell_back and get_current_execution() == "TpuOnJax":
        assert all(_resident(got))
    _equal(got, want)


@pytest.mark.parametrize("name", ["q1", "q2", "q3"])
def test_script_question_in_the_forms_a_tpu_chooses(name):
    """One shard and the TPU's forms (interpret-mode Pallas off the chip): the
    histogram + limb dot at 10 groups, the sorted tiles above 1024."""
    _device_only()
    pdf = _h2o_table(rows=30_000, seed=11, groups_k=12)
    from modin_tpu.ops.groupby import clear_factorize_cache

    with _one_shard_tpu_choice("tpu", 1 << 13):
        md = pd.DataFrame(pdf)
        clear_factorize_cache()
        with query_stats("script") as stats:
            _on_device(_SCRIPT_QUESTIONS[name], md, pdf)
    forms = dict(stats.groupby_forms)
    assert "segment" not in forms and "scatter_counts" not in forms, forms
    # the forced chunk is smaller than any prefix: the order takes the tiles' walk
    assert forms.get("first_seen_tiles") == 1 and "first_seen_prefix" not in forms, forms
    launches = stats.launches_by_program
    assert launches.get("groupby_first_seen_tiles") == 1, launches
    assert "groupby_key_minmax" not in launches, launches
    assert launches.get("groupby_category_ids") == (2 if name == "q2" else 1), launches
    if name == "q1":
        assert forms.get("pallas_bincount") == 1 and forms.get("limb_dot") == 1, forms
    if name == "q3":
        assert forms.get("sorted_tiles", 0) >= 2, forms


def _category_frame(n=3000, seed=3, missing=True, unobserved=True):
    """Two category keys: ``k`` with missing keys, categories in an order
    unlike their labels' and one never observed; ``j`` a plain one."""
    rng = np.random.default_rng(seed)
    cats = ["m", "c", "z", "a", "q", "never"] if unobserved else ["m", "c", "z", "a", "q"]
    low = -1 if missing else 0
    return pandas.DataFrame(
        {
            "k": pandas.Categorical.from_codes(rng.integers(low, 5, n), categories=cats),
            "j": pandas.Categorical.from_codes(rng.integers(0, 3, n), categories=["u", "v", "w"]),
            "i": rng.integers(-3, 4, n),
            "v": rng.integers(1, 50, n),
            "w": np.round(rng.random(n) * 100, 6),
        }
    )


@pytest.fixture(scope="module")
def cat_frame():
    pdf = _category_frame()
    return pd.DataFrame(pdf), pdf


@pytest.mark.parametrize(
    "sort,as_index,dropna", list(itertools.product([True, False], repeat=3))
)
@pytest.mark.parametrize("by", ["k", ["k", "j"], ["j", "i"]], ids=["one", "two", "mixed"])
def test_keywords_on_category_keys(cat_frame, by, sort, as_index, dropna):
    md, pdf = cat_frame
    keywords = dict(sort=sort, as_index=as_index, dropna=dropna, observed=True)
    _on_device(lambda x: x.groupby(by, **keywords).agg({"v": "sum", "w": "mean"}), md, pdf)


@pytest.mark.parametrize("as_index", [True, False])
@pytest.mark.parametrize("sort", [True, False])
@pytest.mark.parametrize(
    "agg",
    [
        lambda g: g[["v", "w"]].sum(),
        lambda g: g[["v", "w"]].agg(["sum", "mean"]),
        lambda g: g["v"].count(),
        lambda g: g[["v"]].max(),
    ],
    ids=["named", "list", "series", "max"],
)
def test_answer_shapes_on_a_category_key(cat_frame, agg, sort, as_index):
    md, pdf = cat_frame
    _on_device(
        lambda x: agg(x.groupby("k", sort=sort, as_index=as_index, observed=True, dropna=False)),
        md, pdf,
    )


@pytest.mark.parametrize("dropna", [True, False])
def test_missing_keys_are_dropped_or_the_last_nan_group(dropna):
    pdf = _category_frame(missing=True, unobserved=False)
    assert (pdf["k"].cat.codes == -1).any()
    md = pd.DataFrame(pdf)
    got = _on_device(
        lambda x: x.groupby("k", as_index=False, observed=True, dropna=dropna).agg({"v": "sum"}),
        md, pdf,
    ).modin.to_pandas()
    assert got["k"].isna().sum() == (0 if dropna else 1)
    if not dropna:
        assert pandas.isna(got["k"].iloc[-1])  # sort=True: the NaN group last


def test_unobserved_categories_are_no_groups_and_observed_false_keeps_pandas_answer():
    pdf = _category_frame(missing=False, unobserved=True)
    md = pd.DataFrame(pdf)
    got = _on_device(
        lambda x: x.groupby("k", observed=True, sort=False, as_index=False).agg({"v": "sum"}),
        md, pdf,
    ).modin.to_pandas()
    assert "never" not in set(got["k"]) and "never" in got["k"].cat.categories
    # observed=False keeps today's behaviour: pandas' answer, by whatever path
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _equal(
            md.groupby("k", observed=False).agg({"v": "sum"}),
            pdf.groupby("k", observed=False).agg({"v": "sum"}),
        )


def test_first_appearance_does_not_follow_category_order():
    """The same rows under two category orders: the same order of groups."""
    rng = np.random.default_rng(5)
    labels = np.array(["b", "d", "a", "c"])[rng.integers(0, 4, 500)]
    v = rng.integers(0, 9, 500)
    answers = []
    for cats in (["a", "b", "c", "d"], ["d", "b", "a", "c"]):
        pdf = pandas.DataFrame({"k": pandas.Categorical(labels, categories=cats), "v": v})
        got = _on_device(
            lambda x: x.groupby("k", sort=False, as_index=False, observed=True).agg({"v": "sum"}),
            pd.DataFrame(pdf), pdf,
        ).modin.to_pandas()
        answers.append((list(got["k"].astype(object)), list(got["v"])))
    assert answers[0] == answers[1]
    assert answers[0][0] == list(pandas.unique(labels))


@pytest.mark.parametrize("kind", ["category", "int"])
def test_a_group_first_seen_past_the_first_prefix_makes_it_grow(kind):
    """One group's only row lies beyond the prefix the order starts with."""
    _device_only()
    from modin_tpu.ops import groupby as gb_ops

    n = 3 * gb_ops._FIRST_SEEN_MIN_ROWS
    rng = np.random.default_rng(9)
    codes = rng.integers(0, 6, n).astype(np.int8)
    codes[-5] = 6
    key = pandas.Categorical.from_codes(codes, list("abcdefg")) if kind == "category" else codes.astype(np.int64) * 7
    pdf = pandas.DataFrame({"k": key, "v": rng.integers(0, 100, n)})
    md = pd.DataFrame(pdf)
    assert gb_ops._first_seen_rows(7, n) < n - 5
    with query_stats("grow") as stats:
        got = _on_device(
            lambda x: x.groupby("k", sort=False, as_index=False, observed=True).agg({"v": "sum"}),
            md, pdf,
        ).modin.to_pandas()
    assert stats.launches_by_program.get("groupby_first_seen") == 2, stats.launches_by_program
    assert got["k"].iloc[-1] == ("g" if kind == "category" else 42)


def _codes_with_every_group(groups, shape, rng):
    """Codes in which every group occurs, dropped rows (the overflow code)
    among them; ``late``: one group's only row is the last, ``blocks``: the
    groups come one after another (a sorted key)."""
    n = 2 * groups + 50
    if shape == "blocks":
        return np.sort(np.concatenate([np.arange(groups), rng.integers(0, groups + 1, n - groups)]))[::-1].copy()
    codes = np.concatenate([rng.permutation(groups), rng.integers(0, groups + 1, n - groups)])
    rng.shuffle(codes)
    if shape == "late":
        codes[codes == groups - 1] = groups
        codes[-1] = groups - 1
    return codes


@pytest.mark.parametrize("shape", ["spread", "late", "blocks"])
@pytest.mark.parametrize("groups", [3, 700, 1500, 40_000])
@pytest.mark.parametrize("form", ["first_seen_prefix", "first_seen_tiles"])
def test_first_seen_order_is_exact_in_either_form(form, groups, shape):
    """``groupby_first_seen`` against numpy's first positions.  The tiles' walk
    is forced at a small size as ``tests/test_groupby.py`` forces the tiles: a
    one-shard mesh, the forms a TPU chooses, a chunk smaller than any prefix."""
    _device_only()
    import contextlib

    import jax.numpy as jnp

    from modin_tpu.ops import groupby as gb_ops
    from modin_tpu.ops.structural import pad_host

    codes = _codes_with_every_group(groups, shape, np.random.default_rng(groups))
    n = len(codes)
    first = np.full(groups + 1, n)
    np.minimum.at(first, codes, np.arange(n))
    want = np.argsort(first[:groups], kind="stable")
    chunk = 1 << 13 if groups > 2_000 else 1 << 8 if groups > 100 else 1 << 4  # fewer rows than the codes hold
    forced = _one_shard_tpu_choice("tpu", chunk) if form == "first_seen_tiles" else contextlib.nullcontext()
    with forced:
        padded = pad_host(codes.astype(np.int32))
        padded[n:] = groups
        with query_stats("order") as stats:
            order = np.asarray(gb_ops.groupby_first_seen(jnp.asarray(padded), groups))[:groups]
    assert dict(stats.groupby_forms) == {form: 1}
    np.testing.assert_array_equal(order, want)


def test_first_seen_form_reads_group_count_platform_and_shards():
    """No option: the prefix while it is no longer than a chunk of the tiles
    (and off the chip, and over a row-sharded key), the tiles' walk past it."""
    _device_only()
    import jax.numpy as jnp

    from modin_tpu.ops import groupby as gb_ops

    codes = jnp.zeros(100_000_000 // 1000, jnp.int32)  # the shape says the rows; no 1e8 array here
    assert gb_ops._first_seen_rows(100, 10**8) == 1 << 16
    assert gb_ops._first_seen_rows(10_000, 10**8) == 1 << 18
    assert gb_ops._first_seen_rows(1_000_000, 10**8) == 1 << 25
    assert gb_ops._first_seen_form(codes, 1_000_000) == "first_seen_prefix"  # the CPU, 8 shards
    with _one_shard_tpu_choice("tpu"):
        big = jnp.zeros(1 << 23, jnp.int32)
        assert gb_ops._first_seen_form(big, 100) == "first_seen_prefix"
        assert gb_ops._first_seen_form(big, 10_000) == "first_seen_prefix"
        assert gb_ops._first_seen_form(big, 1_000_000) == "first_seen_tiles"
    with _one_shard_tpu_choice("segment"):
        assert gb_ops._first_seen_form(jnp.zeros(1 << 23, jnp.int32), 1_000_000) == "first_seen_prefix"


def test_first_seen_reads_a_dense_integer_key_in_place():
    """A dense range's codes are not written out for the order (RangeCodes)."""
    _device_only()
    import jax.numpy as jnp

    from modin_tpu.ops import groupby as gb_ops

    key = np.array([7, 5, 7, 9, 5, 6, 8, 8], dtype=np.int64)
    for forced in (None, "tpu"):
        with _one_shard_tpu_choice(forced, 4):  # "tpu" and a 4-row chunk: the tiles' walk
            codes = gb_ops.RangeCodes(jnp.asarray(key), 5, 5, 6)  # the last two rows are pads
            with query_stats("in-place") as stats:
                order = np.asarray(gb_ops.groupby_first_seen(codes, 5))[:5]
        assert list(stats.groupby_forms) == ["first_seen_tiles" if forced else "first_seen_prefix"]
        np.testing.assert_array_equal(order[:4], [2, 0, 4, 1])
        assert codes._array is None


def test_a_category_key_pays_no_minmax_no_widening_and_no_upload_twice(cat_frame):
    _device_only()
    from modin_tpu.ops.groupby import clear_factorize_cache
    from modin_tpu.views import registry

    md, pdf = cat_frame
    question = lambda x: x.groupby("j", **SCRIPT).agg({"v": "sum"})  # noqa: E731
    question(md)  # the key is resident from here on
    col = md._query_compiler._modin_frame._columns[1]
    assert col.is_device and col.is_category and col.data.dtype == np.int8
    assert col.pandas_dtype is pdf["j"].dtype or col.pandas_dtype == pdf["j"].dtype
    registry.reset()
    clear_factorize_cache()
    with query_stats("again") as stats:
        answer = question(md)
        answer._query_compiler.execute()
    assert "groupby_key_minmax" not in stats.launches_by_program
    assert "groupby_range_ids" not in stats.launches_by_program
    assert stats.h2d_bytes < 4096, stats.h2d_bytes  # group keys and sizes, no codes
    assert stats.host_self_s.get("GROUPBY-ASSEMBLE", 0.0) > 0.0
    key = answer._query_compiler._modin_frame._columns[0]
    assert key.is_category and key.data.dtype == np.int8
    assert key.pandas_dtype.categories is col.pandas_dtype.categories


def test_category_codes_are_not_widened_in_the_factorisation():
    _device_only()
    import jax.numpy as jnp

    from modin_tpu.ops import groupby as gb_ops

    raw = np.array([2, -1, 0, 2, 1, 0, 0, 0], dtype=np.int8)  # two pad rows
    for dropna, want_keys in ((True, [0, 1, 2]), (False, [0, 1, 2, -1])):
        codes, n_groups, keys, sizes = gb_ops.factorize_keys(
            [jnp.asarray(raw)], 6, dropna=dropna, code_widths=(3,)
        )
        codes = gb_ops.codes_array(codes)
        assert codes.dtype == jnp.int32 and keys[0].dtype == np.int8
        assert list(keys[0]) == want_keys and n_groups == len(want_keys)
        nan_code = n_groups if dropna else 3
        assert list(np.asarray(codes)) == [2, nan_code, 0, 2, 1, 0, n_groups, n_groups]
        assert list(sizes) == ([2, 1, 2] if dropna else [2, 1, 2, 1])


# -- residency -------------------------------------------------------------- #


@pytest.fixture()
def promoted():
    """A frame whose category columns a groupby has made resident."""
    _device_only()
    pdf = _category_frame(n=800, seed=13)
    md = pd.DataFrame(pdf)
    md.groupby("k", observed=True)["v"].sum()
    md.groupby("j", observed=True)["v"].sum()
    cols = md._query_compiler._modin_frame._columns
    assert cols[0].is_category and cols[1].is_category
    assert cols[0].data.dtype == np.int8 and cols[0].length == len(pdf)
    return md, pdf


def test_resident_category_round_trip_is_bit_equal(promoted):
    md, pdf = promoted
    back = md.modin.to_pandas()
    pandas.testing.assert_frame_equal(back, pdf, check_categorical=True)
    np.testing.assert_array_equal(back["k"].cat.codes.to_numpy(), pdf["k"].cat.codes.to_numpy())
    assert back["k"].dtype == pdf["k"].dtype and list(md.dtypes) == list(pdf.dtypes)


@pytest.mark.parametrize(
    "move",
    [
        lambda x: x[x["v"] > 25],
        lambda x: x[(x["v"] * 2) > 50],
        lambda x: x.take([5, 3, 1, 700]),
        lambda x: x.head(37),
        lambda x: x.iloc[10:300:7],
        lambda x: x.sort_values(["v", "w"]),
        lambda x: x[["k", "v"]],
    ],
    ids=["filter", "filter_computed", "take", "head", "slice", "sort_values", "select"],
)
def test_row_moves_carry_the_codes(promoted, move):
    md, pdf = promoted
    got = assert_no_fallback(lambda: move(md))
    col = got._query_compiler._modin_frame._columns[0]
    assert col.is_device and col.is_category and col.data.dtype == np.int8
    _equal(got, move(pdf))


def test_concat_carries_equal_code_tables_and_decodes_unequal_ones(promoted):
    md, pdf = promoted
    both = assert_no_fallback(lambda: pd.concat([md, md.head(11)]))
    assert both._query_compiler._modin_frame._columns[0].is_category
    _equal(both, pandas.concat([pdf, pdf.head(11)]))
    # the same labels numbered differently: pandas recodes, through one decode
    other = pdf.assign(k=pdf["k"].cat.reorder_categories(sorted(pdf["k"].cat.categories)))
    md_other = pd.DataFrame(other)
    md_other.groupby("k", observed=True)["v"].sum()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _equal(pd.concat([md, md_other]), pandas.concat([pdf, other]))


def test_a_resident_key_column_of_an_answer_groups_again(cat_frame):
    md, pdf = cat_frame
    first = lambda x: x.groupby(["k", "j"], **SCRIPT).agg({"v": "sum"})  # noqa: E731
    again = lambda x: first(x).groupby("k", **SCRIPT).agg({"v": "sum"})  # noqa: E731
    _on_device(again, md, pdf)


@pytest.mark.parametrize(
    "ask",
    [
        lambda x: x["k"].cat.codes,
        lambda x: x["k"] == "a",
        lambda x: x["k"].astype(str),
        lambda x: x["k"].isna(),
        lambda x: x.isna(),
        lambda x: x.dropna(),
        lambda x: x["j"].str.upper(),
        lambda x: x.groupby("i")["k"].first(),
        lambda x: x.groupby("i").count(),
        lambda x: x.merge(x[["i", "j"]].drop_duplicates("i"), on="i"),
        lambda x: x["k"].value_counts(),
        lambda x: x.nunique(),
        lambda x: x.describe(include="all"),
        lambda x: x["j"].map({"u": 1, "v": 2}),
        lambda x: pd.get_dummies(x["j"]) if isinstance(x, pd.DataFrame) else pandas.get_dummies(x["j"]),
        lambda x: x.sort_values(["k", "v", "w"]),
        lambda x: x.drop_duplicates(subset=["k", "j"]),
    ],
    ids=[
        "cat.codes", "eq_label", "astype", "isna", "frame_isna", "dropna", "str",
        "value_in_agg", "count", "merge", "value_counts", "nunique", "describe",
        "map", "get_dummies", "sort_by_category", "drop_duplicates",
    ],
)
def test_values_of_a_resident_category_column_are_pandas_own(promoted, ask):
    """Whatever reads a category column's *values* answers as pandas does
    (through the device where the codes serve, else the counted host path)."""
    md, pdf = promoted
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        got = ask(md)
    _equal(got, ask(pdf))


def test_spill_and_restore_keep_a_category_column(promoted):
    md, pdf = promoted
    col = md._query_compiler._modin_frame._columns[0]
    assert col.spill() > 0 and col.is_spilled
    assert col.data.dtype == np.int8 and not col.is_spilled
    _equal(md, pdf)


def test_row_sharded_codes_on_eight_devices():
    """The tests' mesh is 8 x 1: codes of a length that pads the shards."""
    _device_only()
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8 virtual devices")
    pdf = _category_frame(n=8 * 125 + 3, seed=21)
    md = pd.DataFrame(pdf)
    got = _on_device(lambda x: x.groupby(["k", "j"], **SCRIPT).agg({"v": "sum", "w": "mean"}), md, pdf)
    col = md._query_compiler._modin_frame._columns[0]
    assert col.is_category and len(col.data.sharding.device_set) == 8
    assert col.data.shape[0] % 8 == 0 and col.data.shape[0] >= len(pdf)
    key = got._query_compiler._modin_frame._columns[0]
    assert key.is_category and key.length == len(got)


# -- the same keywords on the other kinds of key ---------------------------- #


@pytest.fixture(scope="module")
def mixed_keys():
    rng = np.random.default_rng(31)
    n = 2500
    pdf = pandas.DataFrame(
        {
            "s": np.array(["x", "bb", "a", "q", None], dtype=object)[rng.integers(0, 5, n)],
            "f": np.where(rng.random(n) < 0.1, np.nan, rng.integers(0, 7, n) * 0.5),
            "b": rng.random(n) < 0.3,
            "i": rng.integers(-5, 5, n).astype("int32"),
            "v": rng.integers(1, 6, n),
            "w": np.round(rng.random(n) * 100, 6),
        }
    )
    return pd.DataFrame(pdf), pdf


@pytest.mark.parametrize(
    "sort,as_index,dropna", list(itertools.product([True, False], repeat=3))
)
@pytest.mark.parametrize(
    "by", ["s", "f", "b", "i", ["s", "i"], ["f", "b"]],
    ids=["string", "float_nan", "bool", "int32", "string_int", "float_bool"],
)
def test_keywords_on_other_keys(mixed_keys, by, sort, as_index, dropna):
    """``sort=False`` and ``as_index=False`` on numeric and string keys: on the
    device (string keys' ``as_index=False`` through ``reset_index``), equal."""
    md, pdf = mixed_keys
    keywords = dict(sort=sort, as_index=as_index, dropna=dropna)
    question = lambda x: x.groupby(by, **keywords).agg({"v": "sum", "w": "mean"})  # noqa: E731
    got = assert_no_fallback(lambda: question(md))
    _equal(got, question(pdf))
    strings = "s" in by
    if not as_index and not strings and get_current_execution() == "TpuOnJax":
        assert all(_resident(got))


@pytest.mark.parametrize("sort", [True, False])
def test_a_key_that_is_a_value_column_too_is_pandas_own(mixed_keys, sort):
    md, pdf = mixed_keys
    question = lambda x: x.groupby("i", as_index=False, sort=sort)[["i", "v"]].sum()  # noqa: E731
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _equal(question(md), question(pdf))


@pytest.mark.parametrize("kind", ["category", "int64"])
def test_a_dense_wide_key_writes_its_key_column_in_order_without_a_table(kind):
    """Past 1024 groups a single dense key's column comes from the order
    itself (``first + order``): no key table is uploaded, and none gathered."""
    _device_only()
    from modin_tpu.ops.groupby import clear_factorize_cache

    rng = np.random.default_rng(77)
    groups, n = 3000, 20_000
    codes = np.concatenate([rng.permutation(groups), rng.integers(0, groups, n - groups)]).astype(np.int16)
    key = (
        pandas.Categorical.from_codes(codes, [f"c{i:05d}" for i in range(groups)])
        if kind == "category"
        else codes.astype(np.int64) + 17
    )
    pdf = pandas.DataFrame({"k": key, "v": rng.integers(0, 9, n), "w": rng.random(n)})
    md = pd.DataFrame(pdf)
    question = lambda x: x.groupby("k", **SCRIPT).agg({"v": "sum", "w": "mean"})  # noqa: E731
    _on_device(question, md, pdf)
    clear_factorize_cache()
    with query_stats("dense") as stats:
        _on_device(question, md, pdf)
    assert stats.launches_by_program.get("groupby_key_range") == 1
    assert stats.h2d_bytes < 3000 * 2, stats.h2d_bytes  # no table of 3000 keys went up
