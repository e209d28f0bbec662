"""graftcost acceptance: static cost capture, padding-waste accounting,
roofline join, and the zero-overhead-when-off contract.

Acceptance bar (ISSUE 8): cost capture degrades gracefully (a backend
returning None / empty / key-less analyses yields "unknown", never a
crash); a forced-Device groupby at two bucket sizes reports DIFFERENT
padding-waste numbers (the accounting sees real padding, not a constant);
``explain(analyze=True)`` renders per-node estimated flops/bytes, padding
share, and roofline fraction; the disabled mode (``MODIN_TPU_METERS=0`` /
``MODIN_TPU_TRACE=0``) stays zero-allocation with cost capture compiled
in; and the Chrome-trace export carries the two new counter tracks.
"""

import numpy as np
import pytest

import modin_tpu.pandas as pd
from modin_tpu.config import BenchmarkMode, CostCapture, MetersEnabled, TraceEnabled
from modin_tpu.observability import costs, meters, spans
from modin_tpu.observability.chrome_trace import COUNTER_TRACKS, to_chrome_trace


@pytest.fixture(autouse=True)
def _clean_cost_state():
    """Every test starts and ends with meters off, Auto capture, and empty
    cost/meter state; BenchmarkMode (some tests force sync timing) is
    restored so the leak cannot slow every later suite down."""
    bench_before = BenchmarkMode.get()
    MetersEnabled.put(False)
    CostCapture.put("Auto")
    meters.reset()
    costs.reset()
    yield
    MetersEnabled.put(False)
    CostCapture.put("Auto")
    BenchmarkMode.put(bench_before)
    meters.reset()
    costs.reset()


def _require_tpu_on_jax():
    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax":
        pytest.skip("device cost capture requires the TpuOnJax execution")


# ====================================================================== #
# graceful degradation: the backend may answer with anything
# ====================================================================== #


class TestExtractGracefulDegradation:
    @pytest.mark.parametrize("raw", [None, {}, [], (), [[]], "nonsense", 0])
    def test_cost_analysis_junk_yields_unknown(self, raw):
        out = costs.extract_cost(raw)
        assert out == {
            "flops": "unknown",
            "bytes_accessed": "unknown",
            "transcendentals": "unknown",
        }

    def test_cost_analysis_dict_form(self):
        out = costs.extract_cost({"flops": 12.0, "bytes accessed": 96})
        assert out["flops"] == 12.0
        assert out["bytes_accessed"] == 96.0
        assert out["transcendentals"] == "unknown"

    def test_cost_analysis_list_form_and_missing_keys(self):
        out = costs.extract_cost([{"transcendentals": 3.0}])
        assert out["flops"] == "unknown"
        assert out["bytes_accessed"] == "unknown"
        assert out["transcendentals"] == 3.0

    def test_cost_analysis_negative_values_are_unknown(self):
        out = costs.extract_cost({"flops": -1.0})
        assert out["flops"] == "unknown"

    def test_memory_analysis_none_and_attrless(self):
        for stats in (None, object()):
            out = costs.extract_memory(stats)
            assert set(out) == {
                "argument_bytes", "output_bytes", "temp_bytes", "peak_bytes",
            }
            assert all(v == "unknown" for v in out.values())

    def test_memory_analysis_component_sum_fallback(self):
        class Stats:
            argument_size_in_bytes = 100
            output_size_in_bytes = 10
            temp_size_in_bytes = 5

        out = costs.extract_memory(Stats())
        assert out["peak_bytes"] == 115.0

    def test_memory_analysis_explicit_peak_wins(self):
        class Stats:
            argument_size_in_bytes = 100
            output_size_in_bytes = 10
            temp_size_in_bytes = 5
            peak_memory_in_bytes = 512

        assert costs.extract_memory(Stats())["peak_bytes"] == 512.0

    def test_capture_static_on_unlowerable_func(self):
        # a plain callable has no .lower: capture declines to unknown
        out = costs.capture_static(lambda x: x, (1,), None)
        assert out["flops"] == "unknown"

    def test_capture_static_on_raising_lower(self):
        class Evil:
            def lower(self, *a, **k):
                raise RuntimeError("no AOT for you")

        out = costs.capture_static(Evil(), (), None)
        assert out["flops"] == "unknown"

    def test_merge_known_never_clobbers_with_unknown(self):
        # Full-mode regression: a compiled analysis that cannot answer a
        # field must not erase the lowered analysis's answer
        cost = {"flops": 10.0, "bytes_accessed": 20.0}
        costs._merge_known(cost, costs.extract_cost(None))
        assert cost["flops"] == 10.0 and cost["bytes_accessed"] == 20.0
        costs._merge_known(cost, {"flops": 99.0, "bytes_accessed": "unknown"})
        assert cost["flops"] == 99.0 and cost["bytes_accessed"] == 20.0

    def test_arg_key_sees_numpy_shapes_and_kwargs(self):
        a = np.zeros(4)
        b = np.zeros(8)
        assert costs._arg_key((a,), None) != costs._arg_key((b,), None)
        assert costs._arg_key((a,), {"k": 1}) != costs._arg_key((a,), {"k": 2})
        assert costs._arg_key((a,), {"k": 1}) == costs._arg_key((a,), {"k": 1})

    def test_ledger_keeps_unknowns_and_never_raises(self):
        ledger = costs.CostLedger()
        ledger.record_capture("sig", dict(costs._UNKNOWN_COST))
        ledger.record_dispatch("sig", 0.01)
        eff = ledger.efficiency("sig")
        assert eff["achieved_flops_per_s"] == "unknown"
        assert eff["achieved_bytes_per_s"] == "unknown"
        assert eff["roofline_fraction"] == "unknown"
        assert ledger.efficiency("never-dispatched") is None


# ====================================================================== #
# the capture seam (deploy) + warm re-billing
# ====================================================================== #


class TestCaptureSeam:
    def test_cold_dispatch_captures_and_warm_rebills(self):
        _require_tpu_on_jax()
        BenchmarkMode.put(True)
        values = np.arange(4096.0)

        def workload():
            df = pd.DataFrame({"a": values, "b": values[::-1].copy()})
            out = (df["a"] * 2.0 + df["b"]).sum()
            _ = out.modin.to_pandas() if hasattr(out, "modin") else float(out)

        with meters.query_stats("cold") as cold:
            workload()
        assert cold.dispatches >= 1
        assert cold.est_flops > 0, "cold dispatch captured no flop estimate"
        assert cold.est_bytes > 0
        # same shapes again: no compile fires, the memoized cost re-bills
        with meters.query_stats("warm") as warm:
            workload()
        assert warm.compiles == 0, "expected a fully warm run"
        assert warm.est_flops > 0, "warm dispatch did not re-bill costs"
        snap = meters.snapshot()  # meters off: registry untouched is fine
        ledger = costs.get_cost_ledger().snapshot()
        assert ledger["signatures"], "cost ledger recorded nothing"
        assert snap is not None

    def test_full_capture_reads_the_calls_executable_and_compiles_nothing(self):
        """``Full`` takes ``memory_analysis()`` of the executable the call
        built (``costs.program_memory``), not of a second compile."""
        import jax.numpy as jnp

        from modin_tpu.observability.compile_ledger import (
            ensure_listener,
            get_compile_ledger,
        )
        from modin_tpu.ops._program import named_jit

        assert ensure_listener()
        CostCapture.put("Full")
        program = named_jit(lambda x: jnp.sort(x) * 2, "full_capture_probe")
        x = jnp.arange(256.0)
        program(x)  # the call builds the executable
        before = get_compile_ledger().totals()[0]
        cost = costs.capture_static(program, (x,), None)
        assert get_compile_ledger().totals()[0] == before
        assert isinstance(cost["temp_bytes"], float) and cost["peak_bytes"] > 0
        assert cost["flops"] != "unknown" or cost["bytes_accessed"] != "unknown"

    def test_registry_series_under_meters(self):
        _require_tpu_on_jax()
        BenchmarkMode.put(True)
        MetersEnabled.put(True)
        meters.reset()
        costs.reset()
        df = pd.DataFrame({"a": np.arange(2048.0)})
        out = (df["a"] + 1.0).sum()
        _ = out.modin.to_pandas() if hasattr(out, "modin") else float(out)
        series = meters.snapshot()["series"]
        assert series.get("engine.cost.flops", {}).get("total", 0) > 0
        assert series.get("engine.cost.bytes", {}).get("total", 0) > 0


# ====================================================================== #
# padding-waste accounting
# ====================================================================== #


class TestPaddingAccounting:
    def test_note_padding_rolls_into_query_stats(self):
        with meters.query_stats("q") as qs:
            costs.note_padding("unit.test", 1000, 800)
            costs.note_padding("unit.test", 24, 24)
        assert qs.padded_bytes == 1024
        assert qs.padding_waste_bytes == 200
        d = qs.as_dict()
        assert d["padded_bytes"] == 1024
        assert d["padding_waste_bytes"] == 200
        assert "padding waste: 200 of 1024" in qs.summary()
        per_site = costs.get_cost_ledger().snapshot()["padding"]["unit.test"]
        assert per_site == {
            "events": 2, "padded_bytes": 1024, "waste_bytes": 200,
        }

    def test_note_padding_clamps_negative_waste(self):
        with meters.query_stats("q") as qs:
            costs.note_padding("unit.clamp", 10, 99)
        assert qs.padding_waste_bytes == 0

    def test_forced_device_groupby_two_bucket_sizes_differ(self):
        """The acceptance proof that the accounting sees REAL padding: the
        same rows grouped into 3 vs 61 groups pad their output buckets to
        different shard multiples, so the two runs must report different
        (and nonzero) padding-waste numbers."""
        _require_tpu_on_jax()
        BenchmarkMode.put(True)
        n = 4096
        rng = np.random.default_rng(3)
        values = rng.random(n)

        def grouped_sum(num_groups):
            df = pd.DataFrame(
                {
                    "k": rng.integers(0, num_groups, n),
                    "v": values,
                }
            )
            df._query_compiler.execute()
            with meters.query_stats(f"gb{num_groups}") as qs:
                out = df.groupby("k").sum()
                out._query_compiler.execute()
            return qs

        small = grouped_sum(3)
        large = grouped_sum(61)
        assert small.padded_bytes > 0 and large.padded_bytes > 0
        assert small.padding_waste_bytes > 0
        assert large.padding_waste_bytes > 0
        assert small.padding_waste_bytes != large.padding_waste_bytes, (
            "two bucket sizes reported identical padding waste — the "
            "accounting is not seeing the real group-bucket padding"
        )
        sites = costs.get_cost_ledger().snapshot()["padding"]
        assert "groupby.reduce.groups" in sites

    def test_sort_padding_site_reports(self):
        _require_tpu_on_jax()
        BenchmarkMode.put(True)
        # 100 rows pad to the 8-shard multiple of 104: lexsort must see it
        df = pd.DataFrame({"a": np.random.default_rng(0).random(100)})
        df._query_compiler.execute()
        with meters.query_stats("sort"):
            out = df.sort_values("a")
            out._query_compiler.execute()
        sites = costs.get_cost_ledger().snapshot()["padding"]
        assert sites.get("sort.lexsort", {}).get("waste_bytes", 0) > 0


# ====================================================================== #
# zero-overhead-when-off (re-asserted with cost capture compiled in)
# ====================================================================== #


class TestDisabledMode:
    def test_off_means_off_and_allocates_nothing(self):
        _require_tpu_on_jax()
        df = pd.DataFrame({"a": np.arange(64.0), "b": np.arange(64.0)})
        _ = (df + 1).sum().modin.to_pandas()  # warm every code path
        assert not costs.COST_ON
        meter_alloc = meters.meter_alloc_count()
        span_alloc = spans.span_alloc_count()
        # the per-thread counters are monotonic for the process lifetime;
        # the disabled-mode contract is that they do not MOVE
        cost_before = costs.thread_cost()
        pad_before = costs.thread_padding()
        df2 = pd.DataFrame({"a": np.arange(64.0), "b": np.arange(64.0)})
        _ = (df2 * 2).sum().modin.to_pandas()
        _ = df2.shape
        assert meters.meter_alloc_count() == meter_alloc
        assert spans.span_alloc_count() == span_alloc
        assert costs.thread_cost() == cost_before
        assert costs.thread_padding() == pad_before
        snap = costs.get_cost_ledger().snapshot()
        assert not snap["signatures"] and not snap["padding"]
        assert costs.counter_sample() == (0, 0)

    def test_mode_off_wins_over_accounting(self):
        CostCapture.put("Off")
        MetersEnabled.put(True)
        assert meters.ACCOUNTING_ON and not costs.COST_ON
        with meters.query_stats("q"):
            assert not costs.COST_ON

    def test_mode_on_without_accounting(self):
        CostCapture.put("On")
        assert costs.COST_ON and not meters.ACCOUNTING_ON

    def test_auto_follows_query_stats_scope(self):
        assert not costs.COST_ON
        with meters.query_stats("q"):
            assert costs.COST_ON
        assert not costs.COST_ON


# ====================================================================== #
# roofline
# ====================================================================== #


class TestRoofline:
    def test_substrate_peaks_answer_on_cpu(self):
        peaks = costs.substrate_peaks()
        assert peaks is not None
        assert peaks["flops_per_s"] > 0 and peaks["bytes_per_s"] > 0

    @pytest.mark.parametrize(
        "kind, source",
        [("TPU v5 lite", "spec:TPU v5 lite"), ("TPU v9 imaginary", None)],
    )
    def test_tpu_kind_answers_from_the_table_or_raises(
        self, monkeypatch, kind, source
    ):
        # on platform "tpu" a host micro-benchmark is no roofline: a known
        # kind answers from KNOWN_PEAKS, an unknown one is an error
        import jax

        class _FakeTpu:
            platform = "tpu"
            device_kind = kind

        monkeypatch.setattr(jax, "devices", lambda *a, **k: [_FakeTpu()])
        monkeypatch.setattr(costs, "_peaks_cache", None)
        if source is None:
            with pytest.raises(LookupError, match="KNOWN_PEAKS"):
                costs.substrate_peaks()
        else:
            assert costs.substrate_peaks()["source"] == source
        monkeypatch.setattr(costs, "_peaks_cache", None)

    def test_fraction_bounds_and_unknowns(self):
        assert costs.roofline_fraction(1e6, 1e6, 0.0) is None
        assert costs.roofline_fraction(None, None, 1.0) is None
        fraction = costs.roofline_fraction(1e6, 8e6, 1.0)
        assert fraction is not None and 0 < fraction < 1

    def test_pure_movement_uses_bandwidth_roof(self):
        peaks = costs.substrate_peaks()
        fraction = costs.roofline_fraction(None, peaks["bytes_per_s"], 1.0)
        assert fraction == pytest.approx(1.0)


# ====================================================================== #
# EXPLAIN ANALYZE per-node rendering
# ====================================================================== #


class TestExplainAnalyzeCost:
    def test_nodes_render_cost_padding_and_roofline(self, tmp_path):
        _require_tpu_on_jax()
        from modin_tpu.config import PlanMode

        if PlanMode.get() == "Off":
            pytest.skip("needs deferred planning")
        path = tmp_path / "costs.csv"
        rng = np.random.default_rng(5)
        import pandas as pandas_mod

        pandas_mod.DataFrame(
            {
                "a": rng.integers(-50, 50, 500),
                "b": rng.uniform(0, 1, 500),
                "c": rng.uniform(-1, 1, 500),
            }
        ).to_csv(path, index=False)
        md = pd.read_csv(str(path))
        if md._query_compiler._plan is None:
            pytest.skip("read did not defer")
        analyzed = md.query("a > 0")[["b"]].modin.explain(analyze=True)
        assert "status: analyzed" in analyzed
        node_lines = [
            ln for ln in analyzed.splitlines()
            if "(actual:" in ln
        ]
        assert node_lines
        for field in ("est_flops=", "est_bytes=", "padding=", "roofline="):
            assert all(field in ln for ln in node_lines), (
                f"annotation missing {field!r}: {node_lines}"
            )
        assert "est cost:" in analyzed  # the rollup block's cost line


# ====================================================================== #
# Chrome-trace counter tracks (satellite)
# ====================================================================== #


class TestCostCounterTracks:
    def test_new_tracks_declared(self):
        assert "engine.cost.padding_waste_bytes" in COUNTER_TRACKS
        assert "engine.cost.achieved_bw_bytes_s" in COUNTER_TRACKS

    def test_samples_render_as_counter_events(self):
        samples = [(10.0, (100, 50, 2, 4096, 1_000_000))]
        trace = to_chrome_trace([], counters=samples)
        counter_events = [
            e for e in trace["traceEvents"] if e.get("ph") == "C"
        ]
        by_name = {e["name"]: e["args"]["value"] for e in counter_events}
        assert by_name["engine.cost.padding_waste_bytes"] == 4096
        assert by_name["engine.cost.achieved_bw_bytes_s"] == 1_000_000

    def test_short_legacy_samples_omit_new_tracks(self):
        trace = to_chrome_trace([], counters=[(1.0, (1, 2, 3))])
        names = {
            e["name"] for e in trace["traceEvents"] if e.get("ph") == "C"
        }
        assert "engine.cost.padding_waste_bytes" not in names

    def test_profile_export_carries_padding_track(self):
        _require_tpu_on_jax()
        BenchmarkMode.put(True)
        MetersEnabled.put(True)  # Auto capture on -> padding accumulates
        import modin_tpu.observability as graftscope

        prev = TraceEnabled.get()
        try:
            with graftscope.profile() as prof:
                df = pd.DataFrame({"a": np.random.default_rng(1).random(100)})
                out = df.sort_values("a")
                out._query_compiler.execute()
            trace = prof.to_chrome_trace()
        finally:
            TraceEnabled.put(prev)
        pad_events = [
            e
            for e in trace["traceEvents"]
            if e.get("ph") == "C"
            and e["name"] == "engine.cost.padding_waste_bytes"
        ]
        assert pad_events, "no padding-waste counter track in the export"
        assert any(e["args"]["value"] > 0 for e in pad_events)
