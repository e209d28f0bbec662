"""perf-history ledger acceptance: deterministic seeding/regeneration and
a regression gate that actually rejects regressions.

Acceptance bar (ISSUE 8): ``PERF_HISTORY.json`` seeds deterministically
from ``BENCH_r01..r05`` with backfilled provenance; PERF.md's per-op
tables regenerate byte-identically from the ledger; folding an honest run
passes the gate while a 2x wall inflation is rejected; and comparisons
never cross substrate or scale boundaries.
"""

import json
import os

import pytest

from modin_tpu.config import PerfGateNoiseFloorS, PerfGateTolerance
from modin_tpu.observability import perf_history as ph

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stream(ops, substrate="cpu", rows=120000, sha="abc1234", extra_scale=None):
    """A synthetic bench stdout stream: one section line + aggregate."""
    scale = {"rows": rows, "repeats": 1}
    scale.update(extra_scale or {})
    provenance = {
        "git_sha": sha,
        "substrate": substrate,
        "jax": "0.4.37",
        "pandas": "2.3.3",
        "scale": scale,
    }
    lines = [
        json.dumps(
            {
                "section": "graftsort",
                "elapsed_s": 1.0,
                "run_provenance": provenance,
            }
        ),
        json.dumps(
            {
                "metric": "x",
                "value": 1.0,
                "rows": rows,
                "detail": {
                    op: {
                        "modin_tpu_s": wall,
                        "pandas_s": wall * 1.1,
                        "speedup": 1.1,
                    }
                    for op, wall in ops.items()
                },
                "run_provenance": provenance,
            }
        ),
    ]
    return "\n".join(lines)


class TestSeeding:
    def test_seed_is_deterministic(self):
        a = ph.dump_ledger(ph.seed_ledger(REPO_ROOT))
        b = ph.dump_ledger(ph.seed_ledger(REPO_ROOT))
        assert a == b

    def test_committed_ledger_matches_fresh_seed(self):
        # only the seeded entries (carrying a `source` round file) must
        # match: folded runs are allowed to accumulate after them
        with open(os.path.join(REPO_ROOT, "PERF_HISTORY.json")) as f:
            committed = json.load(f)
        prefix = {
            "schema": committed["schema"],
            "runs": [r for r in committed["runs"] if r.get("source")],
        }
        assert ph.dump_ledger(prefix) == ph.dump_ledger(
            ph.seed_ledger(REPO_ROOT)
        )

    def test_backfill_provenance_and_substrates(self):
        ledger = ph.seed_ledger(REPO_ROOT)
        runs = {r["run"]: r for r in ledger["runs"]}
        # r02/r03 (the rounds measured through the gone accelerator plug-in)
        # left the seed with their round files in PR 22
        assert sorted(runs) == ["r01", "r04", "r05"]
        assert runs["r01"]["provenance"]["substrate"] == "cpu"
        assert runs["r04"]["provenance"]["substrate"] == "cpu"
        assert "backfill" in runs["r04"]["provenance"]["git_sha"]
        assert runs["r05"]["failed"] is True
        assert runs["r04"]["ops"]["count"]["speedup"] == 2.63

    def test_round_file_without_parse_records_failure(self, tmp_path):
        path = tmp_path / "BENCH_r99.json"
        path.write_text(json.dumps({"n": 99, "rc": 124, "parsed": None}))
        run = ph.seed_run_from_round_file(str(path))
        assert run["failed"] is True and run["ops"] == {}


class TestStreamParsing:
    def test_parse_carries_provenance_sections_and_ops(self):
        run = ph.parse_bench_stream(_stream({"gs_median": 0.5}))
        assert run["provenance"]["git_sha"] == "abc1234"
        assert run["provenance"]["substrate"] == "cpu"
        assert run["scale"]["rows"] == 120000
        assert run["sections"]["graftsort"]["elapsed_s"] == 1.0
        assert run["ops"]["gs_median"]["modin_tpu_s"] == 0.5
        assert "truncated" not in run

    def test_truncated_stream_is_flagged(self):
        text = _stream({"gs_median": 0.5}).splitlines()[0]  # no aggregate
        run = ph.parse_bench_stream(text)
        assert run["truncated"] is True and run["ops"] == {}


class TestGate:
    def _ledger_with(self, ops, **kwargs):
        ledger = ph.empty_ledger()
        run = ph.parse_bench_stream(_stream(ops, **kwargs))
        assert not ph.fold_run(ledger, run, "base-001")
        return ledger

    def test_first_evidence_passes_trivially(self):
        ledger = ph.empty_ledger()
        run = ph.parse_bench_stream(_stream({"gs_median": 0.5}))
        assert ph.check_regression(ledger, run) == []

    def test_honest_rerun_passes_and_2x_fails(self):
        ledger = self._ledger_with({"gs_median": 0.5, "gs_mode": 0.8})
        honest = ph.parse_bench_stream(
            _stream({"gs_median": 0.52, "gs_mode": 0.79})
        )
        assert ph.check_regression(ledger, honest) == []
        inflated = ph.parse_bench_stream(
            _stream({"gs_median": 1.0, "gs_mode": 1.6})
        )
        failures = ph.check_regression(ledger, inflated)
        assert len(failures) == 2
        assert any("gs_median" in f for f in failures)

    def test_tolerance_knob_is_respected(self):
        ledger = self._ledger_with({"gs_median": 0.5})
        run = ph.parse_bench_stream(_stream({"gs_median": 0.9}))
        assert ph.check_regression(ledger, run)  # 1.8x > default 1.5
        prev = PerfGateTolerance.get()
        PerfGateTolerance.put(2.0)
        try:
            assert ph.check_regression(ledger, run) == []
        finally:
            PerfGateTolerance.put(prev)

    def test_tolerance_below_one_rejected(self):
        with pytest.raises(ValueError):
            PerfGateTolerance.put(0.5)

    def test_sub_floor_jitter_is_not_a_regression(self):
        # 1.75x ratio on a sub-millisecond wall is timer jitter: the
        # absolute delta (0.6ms) is below the 5ms noise floor, so the
        # gate must stay green.
        ledger = self._ledger_with({"gs_median": 0.0008})
        jittered = ph.parse_bench_stream(_stream({"gs_median": 0.0014}))
        assert ph.check_regression(ledger, jittered) == []

    def test_noise_floor_knob_is_respected(self):
        ledger = self._ledger_with({"gs_median": 0.0008})
        jittered = ph.parse_bench_stream(_stream({"gs_median": 0.0014}))
        prev = PerfGateNoiseFloorS.get()
        PerfGateNoiseFloorS.put(0.0)
        try:
            # with the floor disabled the pure ratio check fires again
            assert ph.check_regression(ledger, jittered)
        finally:
            PerfGateNoiseFloorS.put(prev)

    def test_noise_floor_negative_rejected(self):
        with pytest.raises(ValueError):
            PerfGateNoiseFloorS.put(-0.001)

    def test_regression_past_floor_still_fails(self):
        # a real regression clears both the ratio and the absolute floor
        ledger = self._ledger_with({"gs_median": 0.0008})
        slow = ph.parse_bench_stream(_stream({"gs_median": 0.02}))
        assert ph.check_regression(ledger, slow)

    def test_no_cross_scale_comparison(self):
        ledger = self._ledger_with({"gs_median": 0.5}, rows=120000)
        big = ph.parse_bench_stream(_stream({"gs_median": 50.0}, rows=10**7))
        assert ph.check_regression(ledger, big) == []

    def test_seeded_round_is_comparable_baseline_for_scaled_runs(self):
        # a backfilled round records only the headline row count; a new run
        # with the full scale config at the same headline rows MUST still
        # be gated against it (review regression: whole-config fingerprints
        # made every new run incomparable to r01-r05)
        ledger = ph.empty_ledger()
        ledger["runs"].append(
            {
                "run": "r03",
                "source": "BENCH_r03.json",
                "rows": 100000000,
                "provenance": {"substrate": "tpu"},
                "ops": {"sum": {"modin_tpu_s": 0.18, "speedup": 6.0}},
            }
        )
        slow = ph.parse_bench_stream(
            _stream(
                {"sum": 1.8},
                substrate="tpu",
                rows=100000000,
                extra_scale={"sort_rows": 10**7, "repeats": 3},
            )
        )
        assert ph.check_regression(ledger, slow), (
            "10x regression vs the seeded TPU baseline folded green"
        )

    def test_op_scale_field_routing(self):
        run = {
            "rows": 100,
            "scale": {
                "rows": 100,
                "sort_rows": 7,
                "axis1_rows": 8,
                "mode1_rows": 9,
                "udf_rows": 11,
            },
        }
        assert ph.op_scale_key(run, "gs_median") == "rows=7"
        assert ph.op_scale_key(run, "sum1") == "rows=8"
        assert ph.op_scale_key(run, "mode1") == "rows=9"
        assert ph.op_scale_key(run, "apply1") == "rows=11"
        assert ph.op_scale_key(run, "sum") == "rows=100"

    def test_spmd_ops_keyed_by_rows_and_mesh(self):
        run = {
            "rows": 100,
            "scale": {"rows": 100, "spmd_rows": 60000, "spmd_mesh": "8x1"},
        }
        assert (
            ph.op_scale_key(run, "spmd_sort_sharded")
            == "rows=60000@mesh=8x1"
        )
        # the per-mode map form: each leg carries its OWN topology (the
        # "single" leg genuinely runs on a (1,1) mesh)
        mapped = {
            "rows": 100,
            "scale": {
                "rows": 100,
                "spmd_rows": 60000,
                "spmd_mesh": {
                    "sharded": "8x1", "local": "8x1", "single": "1x1"
                },
            },
        }
        assert (
            ph.op_scale_key(mapped, "spmd_sort_sharded")
            == "rows=60000@mesh=8x1"
        )
        assert (
            ph.op_scale_key(mapped, "spmd_sort_single")
            == "rows=60000@mesh=1x1"
        )
        # without a recorded mesh the key still isolates (unknown bucket)
        bare = {"rows": 100, "scale": {"rows": 100, "spmd_rows": 60000}}
        assert (
            ph.op_scale_key(bare, "spmd_sort_sharded")
            == "rows=60000@mesh=unknown"
        )

    def test_spmd_walls_never_gate_across_mesh_shapes(self):
        # the same op at the same row count on a 1-dev vs 8-dev mesh is a
        # different substrate topology: a 100x wall delta must NOT gate
        ledger = self._ledger_with(
            {"spmd_sort_sharded": 0.05},
            extra_scale={"spmd_rows": 60000, "spmd_mesh": "8x1"},
        )
        other_mesh = ph.parse_bench_stream(
            _stream(
                {"spmd_sort_sharded": 5.0},
                extra_scale={"spmd_rows": 60000, "spmd_mesh": "1x1"},
            )
        )
        assert ph.check_regression(ledger, other_mesh) == []
        # same mesh shape DOES gate
        same_mesh = ph.parse_bench_stream(
            _stream(
                {"spmd_sort_sharded": 5.0},
                extra_scale={"spmd_rows": 60000, "spmd_mesh": "8x1"},
            )
        )
        assert ph.check_regression(ledger, same_mesh), (
            "a 100x same-mesh spmd regression folded green"
        )

    def test_serving_ops_keyed_by_watch_mode(self):
        run = {"rows": 100, "scale": {"rows": 100, "serving_rows": 2000000}}
        assert (
            ph.op_scale_key(run, "serving_p50")
            == "rows=2000000@watch=off"
        )
        assert (
            ph.op_scale_key(run, "serving_watch_p50")
            == "rows=2000000@watch=on"
        )
        # the committed r09 records compute the same @watch=off key, so
        # history stays comparable across the key-schema change
        legacy = {"rows": 2000000, "scale": {"serving_rows": 2000000}}
        assert ph.op_scale_key(legacy, "serving_p99").endswith("@watch=off")

    def test_serving_walls_never_gate_across_watch_modes(self):
        # the same saturation workload with the graftwatch sampler live is
        # a different workload: its (bounded) overhead must never gate
        # against the watch-off wall, and vice versa
        ledger = self._ledger_with(
            {"serving_p50": 0.05}, extra_scale={"serving_rows": 2000000}
        )
        watch_on = ph.parse_bench_stream(
            _stream(
                {"serving_watch_p50": 5.0},
                extra_scale={"serving_rows": 2000000},
            )
        )
        assert ph.check_regression(ledger, watch_on) == []
        same_mode = ph.parse_bench_stream(
            _stream(
                {"serving_p50": 5.0},
                extra_scale={"serving_rows": 2000000},
            )
        )
        assert ph.check_regression(ledger, same_mode), (
            "a 100x same-mode serving regression folded green"
        )

    def test_oocore_ops_keyed_by_rows_and_window(self):
        mapped = {
            "rows": 100,
            "scale": {
                "rows": 100,
                "oocore_rows": 200000,
                "oocore_window": {
                    "stream": 65536, "serial": 65536, "resident": "resident"
                },
            },
        }
        assert (
            ph.op_scale_key(mapped, "oocore_stream")
            == "rows=200000@window=65536"
        )
        # the resident leg has no window: its key says so explicitly
        assert (
            ph.op_scale_key(mapped, "oocore_resident")
            == "rows=200000@window=resident"
        )
        bare = {"rows": 100, "scale": {"rows": 100, "oocore_rows": 200000}}
        assert (
            ph.op_scale_key(bare, "oocore_stream")
            == "rows=200000@window=unknown"
        )

    def test_oocore_walls_never_gate_across_window_sizes(self):
        # the same streamed op at the same row count but a different window
        # size is a different workload (mirrors the spmd mesh key): a
        # 100x wall delta must NOT gate; the same window size MUST
        ledger = self._ledger_with(
            {"oocore_stream": 0.05},
            extra_scale={
                "oocore_rows": 200000, "oocore_window": {"stream": 65536}
            },
        )
        other_window = ph.parse_bench_stream(
            _stream(
                {"oocore_stream": 5.0},
                extra_scale={
                    "oocore_rows": 200000, "oocore_window": {"stream": 4096}
                },
            )
        )
        assert ph.check_regression(ledger, other_window) == []
        resident = ph.parse_bench_stream(
            _stream(
                {"oocore_stream": 5.0},
                extra_scale={
                    "oocore_rows": 200000,
                    "oocore_window": {"stream": "resident"},
                },
            )
        )
        assert ph.check_regression(ledger, resident) == []
        same_window = ph.parse_bench_stream(
            _stream(
                {"oocore_stream": 5.0},
                extra_scale={
                    "oocore_rows": 200000, "oocore_window": {"stream": 65536}
                },
            )
        )
        assert ph.check_regression(ledger, same_window), (
            "a 100x same-window oocore regression folded green"
        )

    def test_gs_ops_isolated_by_sort_rows_not_headline(self):
        ledger = self._ledger_with(
            {"gs_median": 0.5}, extra_scale={"sort_rows": 120000}
        )
        other = ph.parse_bench_stream(
            _stream({"gs_median": 50.0}, extra_scale={"sort_rows": 10**7})
        )
        assert ph.check_regression(ledger, other) == []

    def test_no_cross_substrate_comparison(self):
        ledger = self._ledger_with({"gs_median": 5.0}, substrate="cpu")
        tpu = ph.parse_bench_stream(
            _stream({"gs_median": 50.0}, substrate="tpu")
        )
        assert ph.check_regression(ledger, tpu) == []

    def test_fold_records_red_runs_visibly(self):
        ledger = self._ledger_with({"gs_median": 0.5})
        bad = ph.parse_bench_stream(_stream({"gs_median": 5.0}))
        failures = ph.fold_run(ledger, bad, "bad-001")
        assert failures
        recorded = ledger["runs"][-1]
        assert recorded["run"] == "bad-001"
        assert recorded["gate_failures"] == failures
        assert "GATE-RED" in ph.render_tables(ledger)

    def test_duplicate_run_id_rejected(self):
        ledger = self._ledger_with({"gs_median": 0.5})
        run = ph.parse_bench_stream(_stream({"gs_median": 0.5}))
        with pytest.raises(ValueError):
            ph.fold_run(ledger, run, "base-001")

    def test_next_run_id_monotonic(self):
        ledger = self._ledger_with({"gs_median": 0.5})
        assert ph.next_run_id(ledger) == "run-001"
        run = ph.parse_bench_stream(_stream({"gs_median": 0.5}))
        ph.fold_run(ledger, run, "run-001")
        assert ph.next_run_id(ledger) == "run-002"


class TestRegeneration:
    def test_committed_perf_md_matches_ledger(self):
        with open(os.path.join(REPO_ROOT, "PERF_HISTORY.json")) as f:
            ledger = json.load(f)
        with open(os.path.join(REPO_ROOT, "PERF.md")) as f:
            perf_md = f.read()
        assert ph.regenerate_perf_md(ledger, perf_md) == perf_md

    def test_regen_is_idempotent_after_fold(self):
        ledger = ph.empty_ledger()
        run = ph.parse_bench_stream(_stream({"gs_median": 0.5}))
        ph.fold_run(ledger, run, "run-001")
        doc = (
            f"# title\n\n{ph.BEGIN_MARKER}\nstale\n{ph.END_MARKER}\n\ntail\n"
        )
        once = ph.regenerate_perf_md(ledger, doc)
        assert ph.regenerate_perf_md(ledger, once) == once
        assert "| gs_median | cpu |" in once
        assert "stale" not in once
        assert once.endswith("tail\n")

    def test_missing_markers_raise(self):
        with pytest.raises(ValueError):
            ph.regenerate_perf_md(ph.empty_ledger(), "no markers here")

    def test_best_and_latest_tracked_separately(self):
        ledger = ph.empty_ledger()
        ph.fold_run(
            ledger,
            ph.parse_bench_stream(_stream({"op": 1.0})),
            "run-001",
        )
        ph.fold_run(
            ledger,
            ph.parse_bench_stream(_stream({"op": 1.2})),
            "run-002",
        )
        table = ph.render_tables(ledger)
        row = next(
            ln for ln in table.splitlines() if ln.startswith("| op | cpu |")
        )
        assert "| 1.0000 |" in row and "run-001" in row
        assert "| 1.2000 |" in row and "run-002" in row
