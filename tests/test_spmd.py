"""graftmesh acceptance: sharded sort/merge/groupby/reduce on the mesh.

Four layers:

1. the differential parity grid — sort / merge / groupby / reduce / the
   sort-shaped reductions at mesh shapes (1,1), (2,1), (4,1), (8,1) with
   the sharded path FORCED, bit-exact vs pandas, including a ragged final
   shard and an all-NaN shard;
2. kernel-level identity — the sharded sorted-representation build and the
   sharded merge positions are byte-identical to their local builds (the
   routing layer can flip freely without observable change);
3. chaos — ``midquery_device_loss`` killing ONE shard re-seats only that
   shard's slice per column (``recovery.reseat.shard``), never the whole
   column, and the query completes bit-exact;
4. routing/accounting units — ``decide_layout`` forced/auto/crossover
   behavior, skew fallback, mesh-keyed sorted-rep invalidation, the
   two-mesh-shape padding-waste accounting, and collective-bytes
   accounting.
"""

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
from modin_tpu.config import MeshShape, SpmdMode
from modin_tpu.logging import add_metric_handler, clear_metric_handler
from tests.utils import df_equals


@pytest.fixture(autouse=True)
def _require_mesh():
    from modin_tpu.parallel.mesh import num_row_shards
    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax" or num_row_shards() < 2:
        pytest.skip("needs TpuOnJax on a multi-device mesh")


@pytest.fixture
def metric_counts():
    seen = {}

    def handler(name, value):
        seen[name] = seen.get(name, 0) + value

    add_metric_handler(handler)
    yield seen
    clear_metric_handler(handler)


@pytest.fixture
def forced_sharded():
    with SpmdMode.context("Sharded"):
        yield


def _restore_default_mesh():
    from modin_tpu.parallel.mesh import reset_mesh

    reset_mesh()


@pytest.fixture
def mesh_reshaper():
    """Reshape the live mesh for a test; always restores the default."""
    from modin_tpu.parallel.mesh import num_row_shards, reset_mesh

    def reshape(shape):
        MeshShape.put(tuple(shape))
        reset_mesh()
        return num_row_shards()

    try:
        yield reshape
    finally:
        MeshShape.put((8, 1))
        _restore_default_mesh()


# ---------------------------------------------------------------------- #
# 1. differential parity grid across mesh shapes
# ---------------------------------------------------------------------- #


def _grid_frames(rng, n=803):
    """Ragged length (803 % 8 != 0) + a NaN run wide enough to fill whole
    shards at every grid shape (an all-NaN shard is the degenerate case
    the shuffle's NaN-routing must survive)."""
    data = {
        "k": rng.normal(size=n),
        "g": rng.integers(0, 7, n).astype(np.int64),
        # unique: pandas' default sort kind is quicksort (tie order is
        # unspecified there), so exactness asserts need tie-free keys
        "v": rng.permutation(n * 3)[:n].astype(np.int64),
    }
    data["k"][700:] = np.nan  # the final shard(s) are all-NaN at S>=8
    return pandas.DataFrame(data), data


@pytest.mark.parametrize("shape", [(1, 1), (2, 1), (4, 1), (8, 1)])
def test_parity_grid_bit_exact_vs_pandas(shape, mesh_reshaper, forced_sharded):
    shards = mesh_reshaper(shape)
    assert shards == shape[0]
    rng = np.random.default_rng(11)
    pdf, data = _grid_frames(rng)
    mdf = pd.DataFrame(data)
    mdf._query_compiler.execute()

    # sort (sharded path when S >= 2; identical local path at (1,1))
    df_equals(mdf.sort_values("k"), pdf.sort_values("k"))
    df_equals(
        mdf.sort_values("v", ascending=False),
        pdf.sort_values("v", ascending=False),
    )
    # groupby + reduce (already-SPMD paths must stay bit-identical)
    df_equals(mdf.groupby("g").sum(), pdf.groupby("g").sum())
    assert int(mdf["v"].sum()) == int(pdf["v"].sum())
    # sort-shaped reductions through the (sharded) sorted-rep build
    m, p = mdf["v"].median(), pdf["v"].median()
    assert m == p
    assert int(mdf["v"].nunique()) == int(pdf["v"].nunique())
    km, kp = mdf["k"].median(), pdf["k"].median()
    assert (np.isnan(km) and np.isnan(kp)) or km == kp

    # merge at this mesh shape
    lk = rng.integers(0, 40, 257).astype(np.int64)
    rk = rng.integers(0, 40, 181).astype(np.int64)
    pl = pandas.DataFrame({"k": lk, "a": np.arange(257)})
    pr = pandas.DataFrame({"k": rk, "b": np.arange(181)})
    ml = pd.DataFrame({"k": lk, "a": np.arange(257)})
    mr = pd.DataFrame({"k": rk, "b": np.arange(181)})
    for how in ("inner", "left", "outer"):
        df_equals(
            ml.merge(mr, on="k", how=how), pl.merge(pr, on="k", how=how)
        )


# ---------------------------------------------------------------------- #
# 2. kernel-level identity vs the local builds
# ---------------------------------------------------------------------- #


def test_sharded_sorted_valid_matches_local_build():
    from modin_tpu.ops.sort import sorted_valid_columns
    from modin_tpu.ops.spmd import sharded_sorted_valid
    from modin_tpu.ops.structural import pad_host
    from modin_tpu.parallel.engine import JaxWrapper

    rng = np.random.default_rng(3)
    n = 4001
    for values in (
        rng.normal(size=n),
        rng.integers(0, 1 << 30, n).astype(np.int64),
    ):
        if values.dtype.kind == "f":
            values[17:900] = np.nan
            values[5] = np.inf
            values[6] = -np.inf
        dev = JaxWrapper.put(pad_host(values))
        pair = sharded_sorted_valid(dev, n)
        assert pair is not None
        [(local_xs, local_nv)] = sorted_valid_columns([dev], n)
        np.testing.assert_array_equal(np.asarray(pair[0]), np.asarray(local_xs))
        assert int(np.asarray(pair[1])) == int(np.asarray(local_nv))


def test_sharded_merge_positions_match_local():
    from modin_tpu.ops.join import sort_merge_positions
    from modin_tpu.ops.spmd import sharded_merge_positions
    from modin_tpu.ops.structural import pad_host
    from modin_tpu.parallel.engine import JaxWrapper

    rng = np.random.default_rng(4)
    n_l, n_r = 1501, 907
    lk = rng.uniform(-5, 5, n_l).round(1)  # float keys with heavy ties
    rk = rng.uniform(-5, 5, n_r).round(1)
    lk[3:40] = np.nan  # NaN keys match each other in pandas merge
    rk[10:25] = np.nan
    ldev = JaxWrapper.put(pad_host(lk))
    rdev = JaxWrapper.put(pad_host(rk))
    for how in ("inner", "left"):
        got = sharded_merge_positions(ldev, rdev, n_l, n_r, how)
        assert got is not None
        g_lp, g_rp, g_n, g_miss = got
        e_lp, e_rp, e_n, e_miss = sort_merge_positions(
            ldev, rdev, n_l, n_r, how
        )
        assert (g_n, g_miss) == (e_n, e_miss)
        np.testing.assert_array_equal(
            np.asarray(g_lp)[:g_n], np.asarray(e_lp)[:e_n]
        )
        np.testing.assert_array_equal(
            np.asarray(g_rp)[:g_n], np.asarray(e_rp)[:e_n]
        )


# ---------------------------------------------------------------------- #
# 3. chaos: one lost shard re-seats ONE shard, not the whole column
# ---------------------------------------------------------------------- #


def test_shard_loss_reseats_only_that_shard(metric_counts):
    from modin_tpu.config import ResilienceBackoffS
    from modin_tpu.testing.faults import midquery_device_loss

    rng = np.random.default_rng(9)
    vals = rng.integers(0, 10_000, 4096).astype(np.int64)
    mdf = pd.DataFrame({"a": vals, "b": vals * 3})
    mdf._query_compiler.execute()
    col = mdf._query_compiler._modin_frame.get_column(0)
    try:
        ptrs_before = [
            s.data.unsafe_buffer_pointer()
            for s in sorted(
                col._data.addressable_shards,
                key=lambda s: s.index[0].start or 0,
            )
        ]
    except Exception:
        ptrs_before = None
    expected = pandas.DataFrame({"a": vals, "b": vals * 3}) + 7

    before = dict(metric_counts)
    with ResilienceBackoffS.context(0.0):
        with midquery_device_loss(
            after_deploys=0, times=1, ops=("deploy",), shard_index=2
        ) as inj:
            got = (mdf + 7).modin.to_pandas()
    pandas.testing.assert_frame_equal(got, expected)
    assert inj.injected == 1

    def delta(name):
        key = f"modin_tpu.{name}"
        return metric_counts.get(key, 0) - before.get(key, 0)

    # our two columns both took the single-shard leg (other suites'
    # resident columns may legitimately add more shard/op re-seats)
    assert delta("recovery.reseat.shard") >= 2
    if ptrs_before is not None:
        ptrs_after = [
            s.data.unsafe_buffer_pointer()
            for s in sorted(
                col._data.addressable_shards,
                key=lambda s: s.index[0].start or 0,
            )
        ]
        changed = [
            i for i, (a, b) in enumerate(zip(ptrs_before, ptrs_after))
            if a != b
        ]
        # only the named shard's buffer may have been replaced (the
        # allocator may even reuse the freed address, so it can appear
        # unchanged); the other seven survived IN PLACE — the "re-seat a
        # shard, not a column" contract
        assert set(changed) <= {2}, changed


def test_multi_shard_loss_reseats_each_lost_shard(metric_counts):
    """Two shards lost in ONE recovery pass: the pass walks the lost
    indices, `reseat_from_host_shard` succeeds for each, the surviving
    six shards keep their device buffers in place, and the column reads
    back bit-exact."""
    from modin_tpu.core.execution import recovery

    rng = np.random.default_rng(21)
    vals = rng.integers(0, 10_000, 4096).astype(np.int64)
    mdf = pd.DataFrame({"a": vals, "b": vals * 5})
    mdf._query_compiler.execute()
    mf = mdf._query_compiler._modin_frame
    cols = [mf.get_column(i) for i in range(mf.num_cols)]

    def shard_ptrs(col):
        try:
            return [
                s.data.unsafe_buffer_pointer()
                for s in sorted(
                    col._data.addressable_shards,
                    key=lambda s: s.index[0].start or 0,
                )
            ]
        except Exception:
            return None

    ptrs_before = [shard_ptrs(c) for c in cols]
    lost = (2, 5)

    before = dict(metric_counts)
    # one recovery pass over a loss that named TWO mesh row shards: each
    # column replays each lost shard's slice, never the whole buffer
    for col in cols:
        for shard in lost:
            kind = recovery.recover_column(
                col, force=True, shard_index=shard
            )
            assert kind == "shard", (col.pandas_dtype, shard, kind)

    expected = pandas.DataFrame({"a": vals, "b": vals * 5})
    pandas.testing.assert_frame_equal(mdf.modin.to_pandas(), expected)

    for col, ptrs in zip(cols, ptrs_before):
        if ptrs is None:
            continue
        ptrs_after = shard_ptrs(col)
        changed = [
            i for i, (a, b) in enumerate(zip(ptrs, ptrs_after)) if a != b
        ]
        # only the two lost shards' buffers may differ; the other six
        # survived in place
        assert set(changed) <= set(lost), changed


# ---------------------------------------------------------------------- #
# 4. routing & accounting units
# ---------------------------------------------------------------------- #


def test_decide_layout_forced_and_floor():
    from modin_tpu.ops import router

    with SpmdMode.context("Sharded"):
        assert router.decide_layout("sort", 10) == "sharded"
    with SpmdMode.context("Local"):
        assert router.decide_layout("sort", 10**9) == "local"
    with SpmdMode.context("Auto"):
        # below the SpmdMinRows floor: local without consulting calibration
        assert router.decide_layout("sort", 10) == "local"


def test_decide_layout_crossover_from_forced_table():
    from modin_tpu.ops import router

    base = {
        "version": router._CAL_VERSION,
        "platform": "cpu",
        "rows": 1 << 18,
        "device_sort_s": 1.0,
        "device_consume_s": 0.01,
        "device_hist_s": 0.01,
        "device_shuffle_s": 0.25,
        "collective_bytes_per_s": 1e9,
    }
    try:
        with SpmdMode.context("Auto"):
            router.set_calibration(dict(base))
            n = 1 << 20  # above the min-rows floor
            assert router.decide_layout("sort", n) == "sharded"
            # extra payload columns billed at the collective bandwidth can
            # flip the decision back to local
            slow = dict(base, collective_bytes_per_s=1.0)
            router.set_calibration(slow)
            assert (
                router.decide_layout("sort", n, payload_cols=8) == "local"
            )
            # a table with no sharded entries (single-shard calibration)
            # keeps routing local
            no_sharded = {
                k: v for k, v in base.items() if "shuffle" not in k
            }
            router.set_calibration(no_sharded)
            assert router.decide_layout("sort", n) == "local"
    finally:
        router.set_calibration(None)


def test_merge_skew_falls_back_to_local(monkeypatch, forced_sharded):
    # pathological skew: the shuffle gives up (ShuffleSkewError) and the
    # merge must still answer bit-exact via the local sort-merge kernel
    import modin_tpu.parallel.shuffle as shuffle_mod

    def boom(*args, **kwargs):
        raise shuffle_mod.ShuffleSkewError(
            "range_shuffle: pathological key skew"
        )

    monkeypatch.setattr(shuffle_mod, "range_shuffle", boom)
    rng = np.random.default_rng(13)
    n = 1024
    lk = rng.integers(0, 3, n).astype(np.int64)
    rk = np.full(n, 1, np.int64)
    pl = pandas.DataFrame({"k": lk, "a": np.arange(n)})
    pr = pandas.DataFrame({"k": rk, "b": np.arange(n)})
    ml = pd.DataFrame({"k": lk, "a": np.arange(n)})
    mr = pd.DataFrame({"k": rk, "b": np.arange(n)})
    df_equals(ml.merge(mr, on="k"), pl.merge(pr, on="k"))


def test_sorted_rep_invalidates_on_mesh_reshape(mesh_reshaper):
    from modin_tpu.ops import sorted_cache
    from modin_tpu.ops.sort import sorted_valid_columns

    rng = np.random.default_rng(21)
    vals = rng.integers(0, 1 << 30, 2048).astype(np.int64)
    mdf = pd.DataFrame({"w": vals})
    mdf._query_compiler.execute()
    col = mdf._query_compiler._modin_frame.get_column(0)
    [(xs, nv)] = sorted_valid_columns([col.data], len(vals))
    sorted_cache.attach(col, xs, nv)
    assert sorted_cache.peek(col)
    mesh_reshaper((4, 1))
    # the rep was built under 8x1; a 4x1 mesh must not serve it
    assert not sorted_cache.peek(col)


def test_padding_waste_differs_by_mesh_shape(mesh_reshaper):
    from modin_tpu.config import CostCapture
    from modin_tpu.observability import costs

    n = 1001  # pad_len: 1002 at S=2 (1 pad row), 1008 at S=8 (7 pad rows)
    values = np.arange(n, dtype=np.int64)
    wastes = {}
    with CostCapture.context("On"):
        for shape in ((2, 1), (8, 1)):
            mesh_reshaper(shape)
            before = costs.thread_padding()[1]
            from modin_tpu.ops.structural import pad_host

            pad_host(values)
            wastes[shape] = costs.thread_padding()[1] - before
    assert wastes[(2, 1)] == 1 * values.dtype.itemsize
    assert wastes[(8, 1)] == 7 * values.dtype.itemsize
    assert 0 < wastes[(2, 1)] < wastes[(8, 1)]


def test_collective_bytes_accounted(forced_sharded):
    from modin_tpu.config import CostCapture
    from modin_tpu.observability import costs
    from modin_tpu.ops.spmd import sharded_sorted_valid
    from modin_tpu.ops.structural import pad_host
    from modin_tpu.parallel.engine import JaxWrapper

    rng = np.random.default_rng(31)
    n = 2048
    dev = JaxWrapper.put(pad_host(rng.integers(0, 1 << 30, n)))
    with CostCapture.context("On"):
        before = costs.thread_collective()
        pair = sharded_sorted_valid(dev, n)
        assert pair is not None
        moved = costs.thread_collective() - before
    assert moved > 0
    snap = costs.get_cost_ledger().snapshot()
    assert snap["collective"].get("shuffle.all_to_all", {}).get("bytes", 0) > 0


def test_shard_valid_counts_prefix_layout(mesh_reshaper):
    # the per-shard valid-row accounting of the padded prefix layout:
    # full shards, one ragged shard, empty pad shards — and it re-answers
    # for the CURRENT mesh after a reshape
    n = 803
    mdf = pd.DataFrame({"v": np.arange(n, dtype=np.int64)})
    mdf._query_compiler.execute()
    col = mdf._query_compiler._modin_frame.get_column(0)
    counts = col.shard_valid_counts()
    assert len(counts) == 8 and int(counts.sum()) == n
    assert list(counts[:-1]) == [101] * 7 and counts[-1] == 96  # pad 808
    mesh_reshaper((2, 1))
    counts2 = col.shard_valid_counts()  # 8x1-laid buffer, 2x1 mesh
    assert len(counts2) == 2 and int(counts2.sum()) == n


def test_spmd_declines_on_single_shard_mesh(mesh_reshaper, forced_sharded):
    from modin_tpu.ops.spmd import sharded_merge_positions, sharded_sorted_valid
    from modin_tpu.ops.structural import pad_host
    from modin_tpu.parallel.engine import JaxWrapper

    mesh_reshaper((1, 1))
    dev = JaxWrapper.put(pad_host(np.arange(64, dtype=np.int64)))
    assert sharded_sorted_valid(dev, 64) is None
    assert sharded_merge_positions(dev, dev, 64, 64, "inner") is None


# ---------------------------------------------------------------------- #
# the guarded int64 mod / floordiv: its predicate is one scalar over the
# whole row-sharded column, its conditional the same on every shard
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("shape", [(1, 1), (4, 1), (8, 1)])
@pytest.mark.parametrize("op", ["mod", "floordiv"])
def test_guarded_divmod_answers_alike_on_one_shard_and_row_sharded(
    shape, op, mesh_reshaper
):
    assert mesh_reshaper(shape) == shape[0]
    rng = np.random.default_rng(37)
    n = 803  # ragged: the last shard holds pad rows
    narrow = rng.integers(-(2**31) + 1, 2**31, n)
    wide_in_last_shard = narrow.copy()
    wide_in_last_shard[790] = 2**40 + 3  # one shard alone sees a wide word
    wide_in_first_shard = narrow.copy()
    wide_in_first_shard[2] = -(2**31)
    pdf = pandas.DataFrame(
        {"narrow": narrow, "last": wide_in_last_shard, "first": wide_in_first_shard}
    )
    mdf = pd.DataFrame(pdf)
    for divisor in (2, -3, 2**31 - 1, 2**40):
        df_equals(getattr(mdf, op)(divisor), getattr(pdf, op)(divisor))
