"""graftopt smoke gate: the unified cost-based optimizer, proven end to end.

Run by scripts/check_all.sh (twenty-first gate).  Executes the plan_smoke
acceptance pipeline on the 8-device virtual CPU mesh with
``MODIN_TPU_LOCKDEP=1`` strict and asserts the graftopt contract:

1. **bit-exact under every regime**: ``MODIN_TPU_OPT=Auto`` equals
   ``MODIN_TPU_OPT=Off`` (the five independent routers) equals plain
   pandas, exactly — the optimizer may re-route, never re-answer;
2. **strategy annotations render**: EXPLAIN on the materialized plan shows
   each strategy-bearing node's chosen legs and estimated cost, and
   EXPLAIN ANALYZE adds measured-vs-estimated walls;
3. **mid-query re-planning recovers from miscalibration**: with absurd
   injected priors (everything estimates as ~free) the measured scan wall
   diverges, at least one ``opt.replan.*`` metric fires (meter snapshot),
   and the result is still bit-exact;
4. **Off is really off**: zero ``PlanStrategies`` allocations while
   ``MODIN_TPU_OPT=Off`` (the graftscope zero-overhead idiom);
5. **zero lockdep violations** across all of the above.

Exit 0 on success; any assertion prints a diagnostic and exits 1.
"""

import os
import sys
import tempfile

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MODIN_TPU_PLAN"] = "Auto"
os.environ["MODIN_TPU_LOCKDEP"] = "1"
os.environ["MODIN_TPU_METERS"] = "On"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pandas  # noqa: E402

N_ROWS = 50_000


def make_csv(path: str) -> None:
    rng = np.random.default_rng(7)
    pandas.DataFrame(
        {
            "a": rng.integers(-50, 50, N_ROWS),
            "b": rng.uniform(0.0, 1.0, N_ROWS),
            "c": rng.uniform(-1.0, 1.0, N_ROWS),
            "d": rng.integers(0, 1000, N_ROWS),
            "e": rng.uniform(0.0, 100.0, N_ROWS),
            "f": rng.integers(0, 2, N_ROWS),
        }
    ).to_csv(path, index=False)


def _pipeline(pd, path):
    return pd.read_csv(path).query("a > 0")[["b", "c"]].agg("sum")


def _replan_total(meters) -> int:
    series = meters.snapshot().get("series", {})
    return sum(
        int(entry.get("total", 0))
        for name, entry in series.items()
        if name.startswith("opt.replan.")
    )


def main() -> int:
    import modin_tpu.pandas as pd
    from modin_tpu.concurrency import lockdep
    from modin_tpu.config import OptMode
    from modin_tpu.observability import meters
    from modin_tpu.plan import optimizer

    assert lockdep.enabled(), "MODIN_TPU_LOCKDEP=1 did not enable lockdep"
    assert optimizer.OPT_ON, "MODIN_TPU_OPT default is Auto; OPT_ON is False"

    path = os.path.join(
        tempfile.mkdtemp(prefix="graftopt_smoke_"), "smoke.csv"
    )
    make_csv(path)
    reference = pandas.read_csv(path).query("a > 0")[["b", "c"]].agg("sum")

    # ---- leg 1: Auto bit-exact vs Off vs pandas ----------------------- #
    auto_frame = _pipeline(pd, path)
    auto_pd = auto_frame.modin.to_pandas()
    pandas.testing.assert_series_equal(auto_pd, reference)

    allocs_before = optimizer.opt_alloc_count()
    with OptMode.context("Off"):
        off_pd = _pipeline(pd, path).modin.to_pandas()
        assert optimizer.opt_alloc_count() == allocs_before, (
            "MODIN_TPU_OPT=Off allocated PlanStrategies: "
            f"{optimizer.opt_alloc_count() - allocs_before} allocations"
        )
    pandas.testing.assert_series_equal(off_pd, reference)
    pandas.testing.assert_series_equal(off_pd, auto_pd)

    # ---- leg 2: strategy annotations in EXPLAIN ----------------------- #
    md = pd.read_csv(path).query("a > 0")[["b", "c"]]
    analyzed = md.modin.explain(analyze=True)
    assert "[strategy:" in analyzed, (
        "EXPLAIN ANALYZE shows no strategy annotations:\n" + analyzed
    )
    assert "est=" in analyzed and "meas=" in analyzed, (
        "strategy annotations carry no estimated-vs-measured cost:\n"
        + analyzed
    )
    assert "re-plans:" in analyzed, (
        "EXPLAIN ANALYZE shows no re-plan section:\n" + analyzed
    )

    # A sort-shaped reduction (median is not fusable, so the staged path
    # adopts the lowered input) leaves the Reduce-rooted plan + strategies
    # on the source frame: its materialized EXPLAIN must show the legs.
    md2 = pd.read_csv(path).query("a > 0")[["b", "c"]]
    med_pd = md2.median().modin.to_pandas()
    pandas.testing.assert_series_equal(
        med_pd, pandas.read_csv(path).query("a > 0")[["b", "c"]].median()
    )
    materialized = md2.modin.explain()
    assert "[strategy:" in materialized, (
        "materialized EXPLAIN shows no strategy annotations:\n" + materialized
    )
    assert "residency=" in materialized and "kernel=" in materialized, (
        "no strategy leg rendered in materialized EXPLAIN:\n" + materialized
    )

    # ---- leg 3: injected miscalibration must re-plan ------------------ #
    optimizer.set_priors(
        {
            **optimizer.DEFAULT_PRIORS,
            "scan_s_per_row": 1e-12,
            "reduce_s_per_row": 1e-12,
            "sortred_s_per_row": 1e-12,
            "parse_bytes_per_s": 1e15,
            "mem_bytes_per_s": 1e15,
        }
    )
    try:
        replans_before = _replan_total(meters)
        adversarial_pd = _pipeline(pd, path).modin.to_pandas()
        replans = _replan_total(meters) - replans_before
    finally:
        optimizer.set_priors(None)
    pandas.testing.assert_series_equal(adversarial_pd, reference)
    assert replans >= 1, (
        "absurd injected priors fired no opt.replan.* metric "
        f"(saw {replans} re-plans)"
    )

    # ---- lockdep: the whole workload ran violation-free --------------- #
    recorded = lockdep.violations()
    assert not recorded, "lockdep violations:\n" + "\n".join(
        v.render() for v in recorded
    )

    print(
        "graftopt smoke OK: Auto == Off == pandas bit-exact, "
        "strategies rendered in EXPLAIN, "
        f"{replans} re-plan(s) under injected miscalibration, "
        "0 Off-mode allocations, 0 lockdep violations"
    )
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except AssertionError as err:
        print(f"graftopt smoke FAILED: {err}", file=sys.stderr)
        sys.exit(1)
