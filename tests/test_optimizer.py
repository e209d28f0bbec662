"""graftopt tests: the unified cost-based optimizer.

Four layers of coverage:

1. **Differential grid** — ``MODIN_TPU_OPT=Auto`` must be bit-exact against
   ``MODIN_TPU_OPT=Off`` and plain pandas under every forced router leg
   (kernel device/host, fused/staged, resident/windowed): the optimizer
   may re-route, never re-answer.
2. **Plan-time model units** — selectivity heuristics, per-node estimates,
   joint strategy legs (windowed ⇒ staged ⇒ no donation), frozen-table
   kernel crossovers.
3. **Re-plan mechanics** — wall_divergence threshold + noise floor,
   correction clamp and fold-in, once-per-(node, trigger) idempotence,
   recorded EXPLAIN events.
4. **Priors** — estimates read nothing outside the package; the forced
   table (``set_priors``) poisons an estimate and resets.
"""

import json
import os

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
from modin_tpu.config import (
    FuseMode,
    KernelRouterMode,
    OptMode,
    OptReplanFactor,
    StreamMode,
)
from modin_tpu.ops import router
from modin_tpu.plan import ir, optimizer
from tests.utils import df_equals


@pytest.fixture(autouse=True)
def _require_tpu_backend():
    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax":
        pytest.skip("graftopt rides the TpuOnJax query compiler")


_rng = np.random.default_rng(20)


@pytest.fixture
def csv_path(tmp_path):
    n = 4000
    pandas.DataFrame(
        {
            "a": _rng.integers(-10, 10, n),
            "b": _rng.uniform(0, 1, n),
            "c": _rng.uniform(-1, 1, n),
            "d": _rng.integers(0, 7, n),
            "e": _rng.uniform(0, 100, n),
        }
    ).to_csv(tmp_path / "opt.csv", index=False)
    return str(tmp_path / "opt.csv")


def _scan(csv_path, columns=("a", "b", "c", "d", "e")):
    from modin_tpu.core.execution.jax_engine.io import TpuCSVDispatcher

    return ir.Scan(
        TpuCSVDispatcher,
        {"filepath_or_buffer": csv_path},
        pandas.Index(columns),
    )


def _reduce_plan(csv_path, method="sum"):
    scan = _scan(csv_path)
    mask = ir.Map((scan,), "gt", args=(0,), bool_out=True)
    filt = ir.Filter(scan, mask)
    proj = ir.Project(filt, ("b", "c"))
    return ir.Reduce(proj, method, {})


#: a frozen kernel-router calibration table (never measured): sort-shaped
#: device cost dominated by device_sort_s, host splines per family
_FROZEN_TABLE = {
    "rows": 100_000,
    "device_consume_s": 0.001,
    "device_hist_s": 0.002,
    "device_sort_s": 0.010,
    "host_median_low_s": 0.004,
    "host_median_high_s": 0.020,
    "host_quantile_low_s": 0.004,
    "host_quantile_high_s": 0.020,
    "host_nunique_low_s": 0.004,
    "host_nunique_high_s": 0.020,
    "host_mode_low_s": 0.004,
    "host_mode_high_s": 0.020,
}


# ---------------------------------------------------------------------- #
# 1. differential grid: Auto == Off == pandas under every forced leg
# ---------------------------------------------------------------------- #


def _pipeline_frames(csv_path):
    md = pd.read_csv(csv_path).query("a > 0")[["b", "c"]]
    ref = pandas.read_csv(csv_path).query("a > 0")[["b", "c"]]
    return md, ref


def _assert_differential(csv_path, agg):
    md, ref = _pipeline_frames(csv_path)
    auto = getattr(md, agg)().modin.to_pandas()
    with OptMode.context("Off"):
        md_off, _ = _pipeline_frames(csv_path)
        off = getattr(md_off, agg)().modin.to_pandas()
    expected = getattr(ref, agg)()
    pandas.testing.assert_series_equal(auto, expected)
    pandas.testing.assert_series_equal(off, expected)
    pandas.testing.assert_series_equal(auto, off)


@pytest.mark.parametrize("kernel", ["Auto", "Device", "Host"])
@pytest.mark.parametrize("agg", ["sum", "median"])
def test_differential_kernel_legs(csv_path, kernel, agg):
    with KernelRouterMode.context(kernel):
        _assert_differential(csv_path, agg)


@pytest.mark.parametrize("fuse", ["Auto", "Fused", "Staged"])
def test_differential_compile_legs(csv_path, fuse):
    with FuseMode.context(fuse):
        _assert_differential(csv_path, "sum")


@pytest.mark.parametrize("stream", ["Auto", "Resident", "Windowed"])
def test_differential_residency_legs(csv_path, stream):
    with StreamMode.context(stream):
        _assert_differential(csv_path, "sum")


def test_differential_with_frozen_calibration(csv_path):
    """A pre-seeded calibration table changes routing inputs, never
    answers."""
    router.set_calibration(dict(_FROZEN_TABLE))
    try:
        _assert_differential(csv_path, "median")
    finally:
        router.set_calibration(None)


# ---------------------------------------------------------------------- #
# Off really is off
# ---------------------------------------------------------------------- #


def test_off_mode_zero_allocations(csv_path):
    with OptMode.context("Off"):
        assert not optimizer.OPT_ON
        assert router._opt_consult is None
        before = optimizer.opt_alloc_count()
        md, ref = _pipeline_frames(csv_path)
        result = md.sum().modin.to_pandas()
        assert optimizer.opt_alloc_count() == before
    pandas.testing.assert_series_equal(result, ref.sum())
    # back to Auto: the consult hook is reinstalled
    assert optimizer.OPT_ON
    assert router._opt_consult is optimizer._consult


# ---------------------------------------------------------------------- #
# 2. plan-time model units
# ---------------------------------------------------------------------- #


def test_selectivity_heuristics(csv_path):
    scan = _scan(csv_path)

    def mk(method, *children):
        return ir.Map(children or (scan,), method, bool_out=True)

    assert optimizer.estimate_selectivity(mk("eq")) == pytest.approx(0.1)
    assert optimizer.estimate_selectivity(mk("ne")) == pytest.approx(0.9)
    assert optimizer.estimate_selectivity(mk("gt")) == pytest.approx(0.5)
    assert optimizer.estimate_selectivity(mk("isna")) == pytest.approx(0.2)
    assert optimizer.estimate_selectivity(mk("notna")) == pytest.approx(0.8)
    conj = mk("and", mk("gt"), mk("eq"))
    assert optimizer.estimate_selectivity(conj) == pytest.approx(0.05)
    disj = mk("or", mk("notna"), mk("ne"))
    assert optimizer.estimate_selectivity(disj) == pytest.approx(1.0)
    inv = mk("invert", mk("eq"))
    assert optimizer.estimate_selectivity(inv) == pytest.approx(0.9)
    # unknown shapes stay conservative
    assert optimizer.estimate_selectivity(scan) == pytest.approx(0.8)


def test_estimates_flow_bottom_up(csv_path):
    root = _reduce_plan(csv_path, "sum")
    strategies = optimizer.choose(root)
    by_node = {id(n): strategies.by_node[id(n)] for n in ir.walk(root)}
    scan_st = by_node[id(root.children[0].children[0].children[0])]
    red_st = by_node[id(root)]
    assert scan_st.est_bytes and scan_st.est_bytes > 0
    assert scan_st.est_rows and scan_st.est_rows > 0
    # cumulative seconds: the root's estimate includes the whole subtree
    assert red_st.est_s >= scan_st.est_s > 0.0
    # the reduction collapsed the axis
    assert red_st.est_rows == 1


def test_plan_cost_prefers_pruned_scan(csv_path):
    full = ir.Reduce(_scan(csv_path), "sum", {})
    pruned_scan = _scan(csv_path)
    pruned_scan.pruned = ("b",)
    pruned_scan.pushed = True
    pruned = ir.Reduce(pruned_scan, "sum", {})
    assert optimizer.plan_cost(pruned) < optimizer.plan_cost(full)


def test_choose_joint_constraints_windowed(csv_path):
    """windowed residency forces a staged compile and forbids donation."""
    root = _reduce_plan(csv_path, "sum")
    with StreamMode.context("Windowed"):
        strategies = optimizer.choose(root)
    st = strategies.by_node[id(root)]
    assert st.legs["residency"] == "windowed"
    assert st.legs["compile"] == "staged"
    assert {"residency", "compile"} <= st.firm
    assert st.donate is False


def test_choose_annotates_kernel_leg(csv_path):
    root = _reduce_plan(csv_path, "median")
    router.set_calibration(dict(_FROZEN_TABLE))
    try:
        strategies = optimizer.choose(root)
        st = strategies.by_node[id(root)]
        assert st.legs.get("kernel") in ("device", "host", "view")
        assert st.leg_ops["kernel"] == "median"
        assert st.legs["residency"] in ("resident", "windowed")
        # pre-divergence the annotation is advisory, never firm
        assert "kernel" not in st.firm
    finally:
        router.set_calibration(None)


def test_kernel_leg_flips_host_under_correction(csv_path):
    """A correction folding measured device slowness into the model must
    flip the planned kernel leg across the calibrated crossover."""
    root = _reduce_plan(csv_path, "median")
    router.set_calibration(dict(_FROZEN_TABLE))
    try:
        strategies = optimizer.choose(root)
        assert strategies.by_node[id(root)].legs["kernel"] == "device"
        strategies.correction = optimizer.MAX_CORRECTION
        strategies = optimizer.choose(root, state=strategies)
        assert strategies.by_node[id(root)].legs["kernel"] == "host"
    finally:
        router.set_calibration(None)


# ---------------------------------------------------------------------- #
# 3. re-plan mechanics
# ---------------------------------------------------------------------- #


def _installed(root):
    strategies = optimizer.choose(root)
    optimizer.begin(strategies, root, {})
    return strategies


def test_observe_below_factor_never_replans(csv_path):
    root = _reduce_plan(csv_path, "sum")
    strategies = _installed(root)
    try:
        st = strategies.by_node[id(root)]
        st.est_s = 0.010
        with OptReplanFactor.context(4.0):
            optimizer.observe(root, 0.039)
        assert st.measured_s == pytest.approx(0.039)
        assert strategies.replans == []
        assert strategies.correction == 1.0
    finally:
        optimizer.end()


def test_observe_noise_floor(csv_path):
    """Sub-noise-floor walls never re-plan, however wrong the estimate."""
    root = _reduce_plan(csv_path, "sum")
    strategies = _installed(root)
    try:
        st = strategies.by_node[id(root)]
        st.est_s = 1e-9
        optimizer.observe(root, optimizer.REPLAN_NOISE_FLOOR_S)
        assert strategies.replans == []
    finally:
        optimizer.end()


def test_observe_divergence_replans_once(csv_path):
    root = _reduce_plan(csv_path, "sum")
    strategies = _installed(root)
    try:
        st = strategies.by_node[id(root)]
        st.est_s = 0.010
        with OptReplanFactor.context(4.0):
            optimizer.observe(root, 0.060)
            assert len(strategies.replans) == 1
            event = strategies.replans[0]
            assert event["trigger"] == "wall_divergence"
            assert event["correction"] == pytest.approx(6.0)
            assert strategies.correction == pytest.approx(6.0)
            # idempotent per (node, trigger): the same node re-observed
            # slow again must NOT fire a second time
            strategies.by_node[id(root)].est_s = 0.010
            optimizer.observe(root, 0.080)
        assert len(strategies.replans) == 1
    finally:
        optimizer.end()


def test_correction_clamped(csv_path):
    root = _reduce_plan(csv_path, "sum")
    strategies = _installed(root)
    try:
        strategies.by_node[id(root)].est_s = 1e-12
        optimizer.observe(root, 10.0)
        assert strategies.correction <= optimizer.MAX_CORRECTION
        assert len(strategies.replans) == 1
    finally:
        optimizer.end()


def test_replan_excludes_lowered_nodes(csv_path):
    """Already-lowered nodes (the memo) keep their annotations across a
    re-plan; only the remaining segment is re-chosen."""
    root = _reduce_plan(csv_path, "sum")
    scan = root.children[0].children[0].children[0]
    strategies = optimizer.choose(root)
    optimizer.begin(strategies, root, {id(scan): object()})
    try:
        frozen = strategies.by_node[id(scan)]
        frozen.est_s = 123.0  # sentinel: a re-choose would overwrite this
        fired = optimizer._replan(strategies, "wall_divergence", key="t")
        assert fired
        assert strategies.by_node[id(scan)].est_s == 123.0
        assert strategies.replans[0]["remaining_nodes"] == len(
            strategies.by_node
        ) - 1
    finally:
        optimizer.end()


def test_replan_idempotent_per_key_and_trigger(csv_path):
    root = _reduce_plan(csv_path, "sum")
    strategies = optimizer.choose(root)
    assert optimizer._replan(strategies, "ledger_pressure", key="k1")
    assert not optimizer._replan(strategies, "ledger_pressure", key="k1")
    # a different trigger for the same key is a different event
    assert optimizer._replan(strategies, "compile_storm", key="k1")
    assert len(strategies.replans) == 2


def test_compile_storm_pins_remaining_staged(csv_path):
    root = _reduce_plan(csv_path, "sum")
    strategies = optimizer.choose(root)
    st = strategies.by_node[id(root)]
    st.legs["compile"] = "fused"
    optimizer._replan(strategies, "compile_storm", key=("sig", "s0"))
    assert st.legs["compile"] == "staged"
    assert "compile" in st.firm


# ---------------------------------------------------------------------- #
# 4. priors
# ---------------------------------------------------------------------- #


#: nbytes = the scan's sniffed size; the formulas restate _estimate_nodes
#: with no ``*_s_per_row`` coefficient and no calibration table
_PACKAGE_ALONE = {
    "scan": (lambda scan: scan, lambda nbytes, bw, parse: nbytes / parse),
    "reduce_sum": (
        lambda scan: ir.Reduce(scan, "sum", {}),
        lambda nbytes, bw, parse: nbytes / parse + nbytes / bw,
    ),
    "reduce_median": (
        lambda scan: ir.Reduce(scan, "median", {}),
        lambda nbytes, bw, parse: nbytes / parse + nbytes / bw,
    ),
    "groupby_agg": (
        lambda scan: ir.GroupbyAgg((scan,), ["d"], "sum", {}),
        lambda nbytes, bw, parse: nbytes / parse + 2.0 * nbytes / bw,
    ),
    "sort": (
        lambda scan: ir.Sort(scan, ["a"], True, {}),
        lambda nbytes, bw, parse: nbytes / parse
        + nbytes * max(int(nbytes / 64.0), 2).bit_length() / bw,
    ),
}


@pytest.mark.parametrize("family", sorted(_PACKAGE_ALONE))
def test_estimates_come_from_the_package_alone(
    csv_path, tmp_path, monkeypatch, family
):
    """A record of walls three directories above the module (where a
    checkout's root is) steers no estimate: ``plan_cost`` is the bandwidth
    formula over DEFAULT_PRIORS and ``costs.substrate_peaks()``."""
    from modin_tpu.observability import costs

    poisoned = {
        "scale": {"rows": 1, "groups_rows": 1, "stream_rows": 1},
        "ops": {
            op: {"modin_tpu_s": 1e6}
            for op in ("sum", "median", "gb_sum", "gs_sort", "oocore_stream")
        },
    }
    # the name the parent read, in two pieces: a grep for it finds no file
    record = tmp_path / ("PERF_" + "HISTORY.json")
    record.write_text(json.dumps({"runs": [poisoned]}))
    fake_module = tmp_path / "modin_tpu" / "plan" / "optimizer.py"
    monkeypatch.setattr(optimizer, "__file__", str(fake_module))
    monkeypatch.setattr(optimizer, "_priors", None)
    monkeypatch.setattr(router, "calibration_peek", lambda: None)

    build, formula = _PACKAGE_ALONE[family]
    nbytes = os.path.getsize(csv_path)
    mem_bw = float(
        (costs.substrate_peaks() or {}).get("bytes_per_s")
        or optimizer.DEFAULT_PRIORS["mem_bytes_per_s"]
    )
    parse_bw = optimizer.DEFAULT_PRIORS["parse_bytes_per_s"]
    assert optimizer.DEFAULT_PRIORS["bytes_per_row"] == 64.0
    assert optimizer.plan_cost(build(_scan(csv_path))) == pytest.approx(
        formula(nbytes, mem_bw, parse_bw), rel=1e-9
    )


def test_set_priors_forces_and_resets(csv_path):
    root = ir.Reduce(_scan(csv_path), "sum", {})
    optimizer.set_priors(
        {**optimizer.DEFAULT_PRIORS, "scan_s_per_row": 1.0}
    )
    try:
        forced = optimizer.plan_cost(root)
        # ~1 second per scanned row: the forced prior clearly dominates
        assert forced > 1.0
    finally:
        optimizer.set_priors(None)
    assert optimizer.plan_cost(root) < forced


# ---------------------------------------------------------------------- #
# EXPLAIN surface
# ---------------------------------------------------------------------- #


def test_explain_renders_strategy_and_replans(csv_path):
    from modin_tpu.plan import explain as graftexplain

    root = _reduce_plan(csv_path, "median")
    strategies = optimizer.choose(root)
    rendered = graftexplain.render(root, strategies=strategies)
    assert "[strategy:" in rendered
    assert "est=" in rendered
    assert "residency=" in rendered
    strategies.replans.append(
        {"trigger": "wall_divergence", "est_s": 0.01, "measured_s": 0.08}
    )
    strategies.correction = 8.0
    replans = graftexplain.render_replans(strategies)
    assert "wall_divergence" in replans
    assert "8.0" in replans
