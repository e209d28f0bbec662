"""graftmeter acceptance: aggregation, per-query accounting, exposition.

Acceptance bar (ISSUE 7): counters stay exact under multi-threaded
increments; histogram percentiles are accurate on known distributions;
``QueryStats`` scopes are isolated across interleaved queries on two
threads; disabled mode (``MODIN_TPU_METERS=0``) allocates ZERO aggregation
objects across a real workload; the Prometheus/JSON exposition round-trips
through its validating parser; the metrics_smoke efficiency gate actually
fails on an inflated dispatch count; flight-recorder dumps embed a metrics
snapshot (including on the rate-limited path); and
``explain(analyze=True)`` annotates every executed plan node while staying
bit-exact.
"""

import importlib.util
import json
import os
import sys
import threading

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
from modin_tpu.config import MetersEnabled, MetersMaxSeries, TraceDir, TraceEnabled
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import exposition, flight_recorder, meters
from modin_tpu.observability.chrome_trace import COUNTER_TRACKS, to_chrome_trace


@pytest.fixture(autouse=True)
def _meters_off_between_tests():
    """Every test starts and ends with meters off and an empty registry."""
    MetersEnabled.put(False)
    meters.reset()
    yield
    MetersEnabled.put(False)
    meters.reset()


@pytest.fixture(autouse=True, scope="module")
def _collect_cyclic_residue():
    """The analyze tests build plan graphs whose reference cycles keep dead
    frames (and their device-ledger entries) alive until a full gc pass;
    collect at module teardown so later suites see an empty ledger."""
    yield
    import gc

    gc.collect()


def _require_tpu_on_jax():
    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax":
        pytest.skip("planned execution requires the TpuOnJax execution")


def _smoke_module():
    """Import scripts/metrics_smoke.py (not a package) for its helpers."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
        "metrics_smoke.py",
    )
    spec = importlib.util.spec_from_file_location("metrics_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ====================================================================== #
# meter correctness
# ====================================================================== #


class TestCounters:
    def test_multithreaded_increments_are_exact(self):
        MetersEnabled.put(True)
        threads, per_thread = 8, 5000
        barrier = threading.Barrier(threads)

        def worker():
            barrier.wait()
            for _ in range(per_thread):
                emit_metric("resilience.shuffle.slack_retry", 1)

        ts = [threading.Thread(target=worker) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        series = meters.snapshot()["series"]["resilience.shuffle.slack_retry"]
        assert series["kind"] == "counter"
        assert series["total"] == threads * per_thread
        assert series["count"] == threads * per_thread

    def test_kind_resolution_from_registry(self):
        MetersEnabled.put(True)
        emit_metric("resilience.engine.deploy.oom", 1)  # counter (wildcard)
        emit_metric("io.read.bytes", 2048)  # histogram
        emit_metric("memory.device.resident_bytes", 512)  # gauge
        emit_metric("some.adhoc.test.name", 3)  # undeclared -> counter
        series = meters.snapshot()["series"]
        assert series["resilience.engine.deploy.oom"]["kind"] == "counter"
        assert series["io.read.bytes"]["kind"] == "histogram"
        assert series["memory.device.resident_bytes"]["kind"] == "gauge"
        assert series["some.adhoc.test.name"]["kind"] == "counter"

    def test_max_series_cardinality_guard(self):
        MetersEnabled.put(True)
        old = MetersMaxSeries.get()
        MetersMaxSeries.put(4)
        try:
            for i in range(10):
                emit_metric(f"cardinality.burst.k{i}", 1)
            emit_metric("cardinality.burst.k9", 1)  # repeat a dropped name
            snap = meters.snapshot()
            assert len(snap["series"]) == 4
            # distinct refused names vs raw refused emissions
            assert snap["dropped_series"] == 6
            assert snap["dropped_observations"] == 7
        finally:
            MetersMaxSeries.put(old)

    def test_reset_clears_registry(self):
        MetersEnabled.put(True)
        emit_metric("sortcache.hit", 1)
        assert meters.snapshot()["series"]
        meters.reset()
        assert meters.snapshot()["series"] == {}


class TestGauge:
    def test_last_value_min_max(self):
        gauge = meters.Gauge()
        for v in (5, 1, 9, 3):
            gauge.add(v)
        snap = gauge.snapshot()
        assert snap == {"kind": "gauge", "value": 3, "min": 1, "max": 9, "count": 4}


class TestHistogram:
    def test_percentiles_on_known_uniform(self):
        bounds = tuple(float(b) for b in range(100, 1100, 100))
        hist = meters.Histogram(bounds)
        for v in range(1, 1001):  # exact uniform over (0, 1000]
            hist.add(v)
        snap = hist.snapshot()
        assert snap["count"] == 1000
        assert snap["sum"] == sum(range(1, 1001))
        assert snap["min"] == 1 and snap["max"] == 1000
        # linear interpolation inside 100-wide buckets: within one bucket
        assert abs(snap["p50"] - 500) <= 100
        assert abs(snap["p95"] - 950) <= 100
        assert abs(snap["p99"] - 990) <= 100
        # cumulative bucket counts are monotone and end at count
        cums = [c for _b, c in snap["buckets"]]
        assert cums == sorted(cums) and cums[-1] == 1000

    def test_overflow_bucket_and_percentile_clamp(self):
        hist = meters.Histogram((1.0, 2.0))
        for v in (0.5, 1.5, 10.0, 20.0):
            hist.add(v)
        snap = hist.snapshot()
        assert snap["count"] == 4
        # overflow values pull the high percentiles above the last bound
        assert snap["p99"] > 2.0
        assert snap["p99"] <= 20.0

    def test_empty_percentile_is_none(self):
        hist = meters.Histogram((1.0,))
        assert hist.percentile(0.5) is None
        assert hist.snapshot()["p50"] is None

    def test_single_value_percentiles_degenerate(self):
        hist = meters.Histogram((1.0, 10.0))
        hist.add(5.0)
        assert hist.snapshot()["p50"] == pytest.approx(5.0)


# ====================================================================== #
# disabled-mode contract
# ====================================================================== #


class TestDisabledMode:
    def test_zero_alloc_without_meters(self):
        df = pd.DataFrame({"a": np.arange(64.0), "b": np.arange(64.0)})
        _ = (df + 1).sum().modin.to_pandas()  # warm every code path
        before = meters.meter_alloc_count()
        df2 = pd.DataFrame({"a": np.arange(64.0), "b": np.arange(64.0)})
        _ = (df2 * 2).sum().modin.to_pandas()
        _ = df2.shape
        assert meters.meter_alloc_count() == before
        # the hook itself is uninstalled, not just inert
        from modin_tpu.logging import metrics as metrics_mod

        assert metrics_mod._aggregate is None
        assert not meters.ACCOUNTING_ON

    def test_enable_disable_flips_fast_path(self):
        assert not meters.ACCOUNTING_ON
        MetersEnabled.put(True)
        assert meters.ACCOUNTING_ON and meters.METERS_ON
        MetersEnabled.put(False)
        assert not meters.ACCOUNTING_ON and not meters.METERS_ON


# ====================================================================== #
# per-query accounting
# ====================================================================== #


class TestQueryStats:
    def test_scope_accounts_without_meters_enabled(self):
        assert not meters.METERS_ON
        with meters.query_stats("adhoc") as qs:
            assert meters.ACCOUNTING_ON  # scope flips the fast path
            emit_metric("engine.dispatch", 1)
            emit_metric("engine.compile", 1)
            emit_metric("engine.compile_s", 0.25)
            emit_metric("io.read.bytes", 4096)
            emit_metric("fusion.cache.hit", 1)
            emit_metric("recovery.device_lost", 1)
        assert not meters.ACCOUNTING_ON  # restored on exit
        assert qs.dispatches == 1
        assert qs.compiles == 1
        assert qs.compile_s == pytest.approx(0.25)
        assert qs.bytes_parsed == 4096 and qs.io_reads == 1
        assert qs.cache_hits["fused"] == 1
        assert qs.recoveries == 1
        assert qs.wall_s > 0
        # the ad-hoc scope left nothing in the (disabled) registry
        assert meters.snapshot()["series"] == {}

    def test_nested_scopes_both_account(self):
        with meters.query_stats("outer") as outer:
            emit_metric("engine.dispatch", 1)
            with meters.query_stats("inner") as inner:
                emit_metric("engine.dispatch", 1)
        assert outer.dispatches == 2
        assert inner.dispatches == 1

    def test_isolation_across_interleaved_threads(self):
        """Two queries interleaved on two threads never cross-bill."""
        results = {}
        b1, b2 = threading.Barrier(2), threading.Barrier(2)

        def query(name, dispatches, read_bytes):
            with meters.query_stats(name) as qs:
                b1.wait()  # both scopes open before either emits
                for _ in range(dispatches):
                    emit_metric("engine.dispatch", 1)
                emit_metric("io.read.bytes", read_bytes)
                b2.wait()  # both emitted before either scope closes
            results[name] = qs

        t1 = threading.Thread(target=query, args=("q1", 3, 100))
        t2 = threading.Thread(target=query, args=("q2", 5, 999))
        t1.start(), t2.start()
        t1.join(), t2.join()
        assert results["q1"].dispatches == 3
        assert results["q1"].bytes_parsed == 100
        assert results["q2"].dispatches == 5
        assert results["q2"].bytes_parsed == 999

    def test_watchdog_worker_thread_bills_owning_scope(self):
        """Metrics emitted on the resilience watchdog's daemon thread roll
        into the query_stats scope open on the calling thread (the compile
        listener fires inside the watched thunk, i.e. on the worker)."""
        from modin_tpu.core.execution.resilience import _run_with_watchdog

        def thunk():
            emit_metric("engine.compile", 1)
            emit_metric("engine.compile_s", 0.5)
            return "ok"

        with meters.query_stats("watched") as qs:
            assert _run_with_watchdog("materialize", thunk, 30.0) == "ok"
        assert qs.compiles == 1
        assert qs.compile_s == pytest.approx(0.5)

    def test_abandoned_worker_cannot_mutate_closed_scope(self):
        """A seeded worker the owner abandoned (watchdog timeout) emits
        after the scope closed: the late emission must not land in the
        rollup the owner already read."""
        MetersEnabled.put(True)  # keep the emit hook installed post-close
        release, seeded = threading.Event(), threading.Event()

        def worker(scopes):
            meters.seed_thread_scopes(scopes)
            seeded.set()
            release.wait(5)
            emit_metric("engine.compile", 1)  # fires after scope exit

        with meters.query_stats("abandoned") as qs:
            emit_metric("engine.compile", 1)
            t = threading.Thread(
                target=worker, args=(meters.snapshot_scopes(),), daemon=True
            )
            t.start()
            assert seeded.wait(5)
        release.set()
        t.join(5)
        # the registry saw both compiles; the closed scope only the first
        assert meters.snapshot()["series"]["engine.compile"]["total"] == 2
        assert qs.compiles == 1

    def test_as_dict_and_summary_are_complete(self):
        with meters.query_stats("q") as qs:
            emit_metric("engine.dispatch", 1)
        d = qs.as_dict()
        for key in (
            "wall_s",
            "dispatches",
            "compiles",
            "compile_s",
            "bytes_parsed",
            "spills",
            "restores",
            "recoveries",
            "cache_hits",
            "hbm_high_water",
        ):
            assert key in d
        text = qs.summary()
        assert "device dispatches: 1" in text
        assert "launches: 0, host syncs: 0" in text

    @pytest.mark.parametrize(
        "key",
        [
            "request_id", "host_self_s", "wait_s", "first_launch_s", "launches",
            "launches_by_program", "host_syncs", "d2h_bytes", "h2d_bytes", "spans",
            "trace_s", "lower_s", "cache_loads", "cache_load_s", "programs_made",
        ],
    )
    def test_as_dict_carries_the_request_record(self, key):
        with meters.query_stats("q") as qs:
            pass
        record = qs.as_dict()
        assert key in record
        assert "api_calls" not in record  # removed: nothing read it
        json.dumps(record)  # /debug/queries and recent_queries serialise it

    @pytest.mark.parametrize(
        "dtype, method, scoped, expect",
        [
            ("int64", "mod", True, {"divmod_guarded": 10}),
            ("int64", "floordiv", True, {"divmod_guarded": 10}),
            ("float64", "mod", True, {}),
            ("int32", "mod", True, {}),  # the result stays int32: nothing to guard
            ("int64", "add", True, {}),
            ("int64", "mod", False, {}),
        ],
    )
    def test_elementwise_forms_count_the_guarded_divmod_columns(
        self, dtype, method, scoped, expect
    ):
        """One count a column where a 64-bit integer ``mod`` / ``floordiv``
        node is built, in the request record; nothing outside a scope."""
        _require_tpu_on_jax()
        frame = pd.DataFrame(
            np.arange(40, dtype=dtype).reshape(4, 10), columns=list("abcdefghij")
        )
        if not scoped:
            getattr(frame, method)(2)
            with meters.query_stats("after") as qs:
                pass
        else:
            with meters.query_stats("q") as qs:
                answer = getattr(frame, method)(2)
            assert answer._query_compiler._modin_frame._columns[0].is_device
        assert qs.elementwise_forms == expect
        assert qs.as_dict()["elementwise_forms"] == expect

    @pytest.mark.parametrize(
        "n_cols, method, scoped, expect",
        [
            (10, "median", True, {"axis1_columns": 1}),
            (10, "nunique", True, {"axis1_columns": 1}),
            (10, "sum", True, {"axis1_columns": 1}),
            (33, "median", True, {"axis1_stacked": 1}),
            (33, "nunique", True, {"axis1_stacked": 1}),
            (10, "median", False, {}),
        ],
    )
    def test_reduction_forms_count_the_row_reductions_by_form(
        self, n_cols, method, scoped, expect
    ):
        """One count a row-wise reduction, by the form its column count
        chose: up to ``_AXIS1_COLUMNS_MAX`` columns read as column arrays,
        one more stacked; nothing outside a scope."""
        from modin_tpu.ops.reductions import _AXIS1_COLUMNS_MAX

        _require_tpu_on_jax()
        assert _AXIS1_COLUMNS_MAX + 1 == 33
        frame = pd.DataFrame(np.arange(8 * n_cols).reshape(8, n_cols))
        if not scoped:
            getattr(frame, method)(axis=1)
            with meters.query_stats("after") as qs:
                pass
        else:
            with meters.query_stats("q") as qs:
                answer = getattr(frame, method)(axis=1)
            assert answer._query_compiler._modin_frame._columns[0].is_device
        assert qs.reduction_forms == expect
        assert qs.as_dict()["reduction_forms"] == expect

    def test_uploads_are_counted_at_put_and_upload(self):
        from modin_tpu.parallel.engine import JaxWrapper, upload

        host = np.arange(64, dtype=np.int64)
        with meters.query_stats("up") as qs:
            device = JaxWrapper.put(host)
            upload(np.arange(4, dtype=np.int64))
        assert qs.h2d_bytes == int(device.nbytes) + 32
        assert qs.host_syncs == 0

    @pytest.mark.parametrize(
        "value, syncs",
        [("device", 1), ("host", 0)],
    )
    def test_host_sync_is_a_fetch_of_a_device_value(self, value, syncs):
        import jax.numpy as jnp

        from modin_tpu.parallel.engine import JaxWrapper

        obj = jnp.arange(16, dtype=jnp.int64) if value == "device" else np.arange(16)
        with meters.query_stats("down") as qs:
            JaxWrapper.materialize(obj)
        assert qs.host_syncs == syncs
        assert qs.d2h_bytes == (128 if syncs else 0)
        assert qs.wait_s > 0  # the blocked stretch is wait, not JAX-ENGINE host work
        assert qs.host_self_s.get("JAX-ENGINE", 0.0) >= 0

    def test_launches_reach_every_open_scope_and_no_closed_one(self):
        import jax.numpy as jnp

        from modin_tpu.ops._program import named_jit

        program = named_jit(lambda x: x + 1, "reduce_probe")
        with meters.query_stats("outer") as outer:
            with meters.query_stats("inner") as inner:
                program(jnp.arange(4))
            program(jnp.arange(4))
        program(jnp.arange(4))  # no scope: counted nowhere
        assert inner.launches_by_program == {"reduce_probe": 1}
        assert outer.launches_by_program == {"reduce_probe": 2}
        assert outer.first_launch_s is not None
        assert outer.first_launch_s <= outer.wall_s


class TestProgramsMade:
    """How a scope's programs were made: jax's trace, lowering, compile and
    cache-load events billed by ``compile_ledger``'s listeners, and the
    temporaries the compiler gave a program its call built."""

    @pytest.fixture(autouse=True)
    def _listeners(self):
        from modin_tpu.observability.compile_ledger import ensure_listener

        assert ensure_listener()

    def test_a_first_call_records_how_its_program_was_made(self):
        import jax.numpy as jnp

        from modin_tpu.ops._program import named_jit

        program = named_jit(lambda x: jnp.cumsum(x) * 2, "made_first_call")
        x = jnp.arange(64.0)
        with meters.query_stats("first") as first:
            program(x)
        assert first.compiles == 1
        assert first.trace_s > 0 and first.lower_s > 0
        made = first.programs_made["made_first_call"]
        assert made["trace_s"] == first.trace_s and made["lower_s"] == first.lower_s
        assert made["compile_s"] == pytest.approx(first.compile_s)
        assert made["loaded"] is False and first.cache_loads == 0
        assert isinstance(made["temp_bytes"], int) and made["temp_bytes"] >= 0
        assert first.as_dict()["programs_made"] == first.programs_made
        with meters.query_stats("second") as second:
            program(x)
        assert second.programs_made == {}
        assert (second.compiles, second.trace_s, second.lower_s) == (0, 0.0, 0.0)

    def test_a_helper_traced_inside_a_program_is_billed_once_in_it(self):
        import jax.numpy as jnp
        from jax._src import monitoring

        from modin_tpu.observability.compile_ledger import TRACE_EVENT
        from modin_tpu.ops._program import named_jit, traced_jit

        traces = []

        def listen(event, duration, **kwargs):
            if event == TRACE_EVENT:
                traces.append((kwargs.get("fun_name"), duration))

        helper = traced_jit(lambda x: jnp.sort(x) + 1, "made_helper")
        program = named_jit(lambda x: helper(x) * 2 - jnp.cumsum(x), "made_outer")
        x = jnp.arange(64.0)
        monitoring.register_event_duration_secs_listener(listen)
        try:
            with meters.query_stats("q") as qs:
                program(x)
        finally:
            monitoring.unregister_event_duration_listener(listen)
        outer = [d for name, d in traces if name == "made_outer"][0]
        assert any(name == "made_helper" for name, _ in traces)
        assert qs.trace_s == pytest.approx(outer)  # no compile fired inside it
        assert qs.trace_s < sum(d for _, d in traces)
        assert set(qs.programs_made) == {"made_outer"}

    def test_the_memory_read_adds_no_compile(self):
        import jax.numpy as jnp

        from modin_tpu.observability import costs
        from modin_tpu.observability.compile_ledger import get_compile_ledger
        from modin_tpu.ops._program import named_jit

        ledger = get_compile_ledger()
        program = named_jit(lambda x: x[::-1] + 1, "made_no_second_compile")
        x = jnp.arange(32.0)
        before = ledger.totals()[0]
        with meters.query_stats("q") as qs:
            program(x)
        assert ledger.totals()[0] - before == qs.compiles == 1
        assert "temp_bytes" in qs.programs_made["made_no_second_compile"]
        # read again, past the memo: jax hands back the executable it built
        costs._func_memory.pop(program._jitted, None)
        before = ledger.totals()[0]
        memory = costs.program_memory(program, (x,), None)
        assert memory["temp_bytes"] != costs.UNKNOWN
        assert ledger.totals()[0] == before
        assert not costs._memory_read_compiles

    def test_no_scope_allocates_nothing_and_writes_nothing(self):
        import jax.numpy as jnp

        from modin_tpu.observability import costs, spans
        from modin_tpu.ops._program import named_jit

        program = named_jit(lambda x: jnp.tanh(x) * 3, "made_unscoped")
        x = jnp.arange(16.0)
        with meters.query_stats("closed") as closed:
            pass
        assert not meters.ACCOUNTING_ON
        spans_before, meters_before = spans.span_alloc_count(), meters.meter_alloc_count()
        program(x)
        assert spans.span_alloc_count() == spans_before
        assert meters.meter_alloc_count() == meters_before
        assert closed.programs_made == {} and closed.trace_s == 0.0
        assert program._jitted not in costs._func_memory  # no memory read made

    @pytest.mark.parametrize("loaded", [True, False])
    def test_a_cache_load_is_the_part_of_the_compiles_it_answered(self, loaded):
        from modin_tpu.observability import compile_ledger as ledger

        with meters.query_stats("q") as qs:
            ledger._on_event_start(ledger.COMPILE_EVENT, 0.0, fun_name="jit(made_synthetic)")
            if loaded:
                ledger._on_event_duration(ledger.CACHE_LOAD_EVENT, 0.25)
            ledger._on_event_duration(ledger.COMPILE_EVENT, 0.5, fun_name="jit(made_synthetic)")
        assert (qs.compiles, qs.compile_s) == (1, 0.5)
        assert (qs.cache_loads, qs.cache_load_s) == ((1, 0.5) if loaded else (0, 0.0))
        assert qs.programs_made == {
            "made_synthetic": {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.5, "loaded": loaded}
        }

    def test_a_compile_inside_a_trace_is_not_billed_as_tracing(self):
        from modin_tpu.observability import compile_ledger as ledger

        start, end = ledger._on_event_start, ledger._on_event_duration
        with meters.query_stats("q") as qs:
            start(ledger.TRACE_EVENT, 0.0, fun_name="made_outer_trace")
            start(ledger.TRACE_EVENT, 0.0, fun_name="made_inner_trace")
            start(ledger.COMPILE_EVENT, 0.0, fun_name="jit(made_eager)")
            end(ledger.COMPILE_EVENT, 0.25, fun_name="jit(made_eager)")
            end(ledger.TRACE_EVENT, 0.5, fun_name="made_inner_trace")
            end(ledger.TRACE_EVENT, 1.0, fun_name="made_outer_trace")
            start(ledger.LOWER_EVENT, 0.0, fun_name="jit(made_outer_trace)")
            end(ledger.LOWER_EVENT, 0.125, fun_name="jit(made_outer_trace)")
        assert qs.trace_s == 0.75 and qs.lower_s == 0.125
        assert set(qs.programs_made) == {"made_outer_trace", "made_eager"}
        assert qs.programs_made["made_outer_trace"]["trace_s"] == 0.75
        assert qs.programs_made["made_outer_trace"]["lower_s"] == 0.125
        assert qs.programs_made["made_eager"]["compile_s"] == 0.25

    def test_a_relowering_is_not_billed(self):
        from modin_tpu.observability import compile_ledger as ledger

        with meters.query_stats("q") as qs:
            with ledger.relowering():
                ledger._on_event_start(ledger.TRACE_EVENT, 0.0, fun_name="made_again")
                ledger._on_event_duration(ledger.TRACE_EVENT, 0.5, fun_name="made_again")
        assert qs.trace_s == 0.0 and qs.programs_made == {}


# ====================================================================== #
# exposition
# ====================================================================== #


class TestExposition:
    def _snapshot_with_all_kinds(self):
        MetersEnabled.put(True)
        emit_metric("sortcache.hit", 2)  # counter
        emit_metric("memory.device.resident_bytes", 1024)  # gauge
        emit_metric("io.read.bytes", 4096)  # histogram
        emit_metric("io.read.bytes", 1 << 22)
        return meters.snapshot()

    def test_prometheus_round_trip(self):
        snap = self._snapshot_with_all_kinds()
        text = exposition.to_prometheus(snap)
        parsed = exposition.parse_prometheus(text)
        assert parsed["modin_tpu_sortcache_hit"]["type"] == "counter"
        assert parsed["modin_tpu_sortcache_hit"]["samples"][
            "modin_tpu_sortcache_hit"
        ] == 2
        assert parsed["modin_tpu_memory_device_resident_bytes"]["type"] == "gauge"
        hist = parsed["modin_tpu_io_read_bytes"]
        assert hist["type"] == "histogram"
        assert hist["samples"]["modin_tpu_io_read_bytes_count"] == 2
        assert hist["samples"]["modin_tpu_io_read_bytes_sum"] == 4096 + (1 << 22)
        assert any("_bucket" in k for k in hist["samples"])

    def test_help_lines_carry_registry_descriptions(self):
        """# HELP text comes from the METRICS registry 3-tuples and
        survives the parse roundtrip (the graftwatch satellite)."""
        from modin_tpu.logging.metrics import METRICS

        snap = self._snapshot_with_all_kinds()
        text = exposition.to_prometheus(snap)
        parsed = exposition.parse_prometheus(text)
        declared = {
            entry[0]: " ".join(str(entry[2]).split()) for entry in METRICS
        }
        # exact-name family: description verbatim
        assert (
            parsed["modin_tpu_io_read_bytes"]["help"]
            == declared["io.read.bytes"]
        )
        # wildcard family resolves through fnmatch
        assert (
            parsed["modin_tpu_sortcache_hit"]["help"]
            == declared["sortcache.*"]
        )
        # an ad-hoc name not in the registry keeps the generic fallback
        emit_metric("adhoc.testonly.name", 1)
        text = exposition.to_prometheus(meters.snapshot())
        parsed = exposition.parse_prometheus(text)
        assert (
            parsed["modin_tpu_adhoc_testonly_name"]["help"]
            == "modin_tpu metric adhoc.testonly.name"
        )

    def test_help_text_escapes_newlines_and_backslashes(self, monkeypatch):
        import modin_tpu.logging.metrics as metrics_mod

        patched = metrics_mod.METRICS + (
            ("unit.help.escape", "counter", "path C:\\tmp\nsecond line"),
        )
        monkeypatch.setattr(metrics_mod, "METRICS", patched)
        # a registry description: whitespace (the newline included)
        # normalizes to single spaces, then backslashes escape per the
        # Prometheus text format
        text = exposition.help_text("unit.help.escape")
        assert text == "path C:\\\\tmp second line"
        assert "\n" not in text
        # the generic fallback escapes a hostile snapshot name too (names
        # from exposition callers are arbitrary, unlike emit_metric's)
        evil = exposition.help_text("adhoc\nhostile.name")
        assert "\n" not in evil and "\\n" in evil

    def test_parser_rejects_malformed_help(self):
        with pytest.raises(ValueError):
            exposition.parse_prometheus("# HELP \nx 1")

    def test_json_round_trip(self):
        snap = self._snapshot_with_all_kinds()
        loaded = json.loads(exposition.to_json(snap))
        assert loaded["series"].keys() == snap["series"].keys()
        assert loaded["series"]["io.read.bytes"]["p50"] is not None

    @pytest.mark.parametrize(
        "bad_text",
        [
            "not a metric line at all {",
            "# TYPE modin_tpu_x sketchy\nmodin_tpu_x 1",
            "modin_tpu_orphan 1",  # sample before TYPE declaration
            # non-cumulative histogram buckets
            "# TYPE modin_tpu_h histogram\n"
            'modin_tpu_h_bucket{le="1"} 5\n'
            'modin_tpu_h_bucket{le="2"} 3\n',
        ],
    )
    def test_parser_rejects_malformed(self, bad_text):
        with pytest.raises(ValueError):
            exposition.parse_prometheus(bad_text)

    def test_meter_rollup_schema_stable_on_empty(self):
        rollup = exposition.meter_rollup({"series": {}})
        assert rollup["dispatches"] == 0
        assert rollup["bytes_parsed"] == 0
        assert rollup["cache_hits"] == {"fused": 0, "sorted_rep": 0, "plan_scan": 0}

    def test_meter_rollup_reads_series(self):
        snap = self._snapshot_with_all_kinds()
        rollup = exposition.meter_rollup(snap)
        assert rollup["bytes_parsed"] == 4096 + (1 << 22)
        assert rollup["io_reads"] == 2
        assert rollup["cache_hits"]["sorted_rep"] == 2


# ====================================================================== #
# the efficiency-invariant gate
# ====================================================================== #


class TestMetricsSmokeGate:
    def test_gate_fails_on_inflated_dispatch_count(self):
        """The acceptance demonstration: a refactor that silently doubles
        the pipeline's dispatch count turns the gate red."""
        smoke = _smoke_module()
        baseline = {
            "max": {"dispatches": 2, "compiles": 2, "io_reads": 1},
            "min": {"pruned_columns": 3},
        }
        ok = {"dispatches": 2, "compiles": 2, "io_reads": 1, "pruned_columns": 3}
        assert smoke.check_invariants(ok, baseline) == []
        inflated = dict(ok, dispatches=4)
        failures = smoke.check_invariants(inflated, baseline)
        assert failures and "dispatches" in failures[0]

    def test_gate_fails_on_lost_pruning_and_missing_keys(self):
        smoke = _smoke_module()
        baseline = {"max": {"dispatches": 2}, "min": {"pruned_columns": 3}}
        failures = smoke.check_invariants(
            {"dispatches": 2, "pruned_columns": 0}, baseline
        )
        assert any("pruned_columns" in f for f in failures)
        failures = smoke.check_invariants({"pruned_columns": 3}, baseline)
        assert any("not measured" in f for f in failures)

    def test_bytes_tolerance_is_applied(self):
        smoke = _smoke_module()
        baseline = {"max": {"bytes_parsed": 1000}, "min": {}}
        assert smoke.check_invariants({"bytes_parsed": 1015}, baseline) == []
        assert smoke.check_invariants({"bytes_parsed": 1100}, baseline)

    def test_recorded_baseline_exists_and_is_wellformed(self):
        smoke = _smoke_module()
        baseline = smoke.load_baseline()
        assert set(baseline["max"]) == {
            "dispatches",
            "compiles",
            "io_reads",
            "bytes_parsed",
        }
        assert baseline["min"]["pruned_columns"] >= 1


# ====================================================================== #
# counter tracks + flight recorder embedding
# ====================================================================== #


class TestCounterTracks:
    def test_chrome_trace_counter_events_from_samples(self):
        samples = [
            (10.0, (111, 222, 3, 40, 1000, 2, 1)),
            (20.0, (444, 555, 6, 80, 2000, 5, 4)),
        ]
        trace = to_chrome_trace([], counters=samples)
        cevents = [e for e in trace["traceEvents"] if e["ph"] == "C"]
        assert len(cevents) == len(samples) * len(COUNTER_TRACKS)
        by_name = {}
        for e in cevents:
            by_name.setdefault(e["name"], []).append(e["args"]["value"])
        assert by_name["memory.device.resident_bytes"] == [111, 444]
        assert by_name["memory.host.cache_bytes"] == [222, 555]
        assert by_name["spans.live"] == [3, 6]
        assert by_name["engine.cost.padding_waste_bytes"] == [40, 80]
        assert by_name["engine.cost.achieved_bw_bytes_s"] == [1000, 2000]
        assert by_name["serving.gate.queued"] == [2, 5]
        assert by_name["serving.gate.running"] == [1, 4]

    def test_legacy_samples_render_without_gate_tracks(self):
        """Pre-graftwatch 5-tuple samples still render — zip stops short,
        the gate tracks are simply absent (the documented contract)."""
        trace = to_chrome_trace([], counters=[(10.0, (1, 2, 3, 4, 5))])
        names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "C"}
        assert "engine.cost.achieved_bw_bytes_s" in names
        assert "serving.gate.queued" not in names

    def test_profile_export_carries_counter_tracks(self):
        import modin_tpu.observability as graftscope

        flight_recorder.reset_for_tests()
        with graftscope.profile() as prof:
            df = pd.DataFrame({"k": [i % 5 for i in range(128)], "v": np.arange(128.0)})
            agg = df.groupby("k").sum()
            agg._query_compiler.execute()
        trace = prof.to_chrome_trace()
        tracks = {e["name"] for e in trace["traceEvents"] if e["ph"] == "C"}
        assert set(COUNTER_TRACKS) <= tracks
        json.dumps(trace)  # loadable


class TestFlightRecorderMetricsSnapshot:
    @pytest.fixture(autouse=True)
    def _tracing_reset(self):
        TraceEnabled.put(False)
        flight_recorder.reset_for_tests()
        yield
        TraceEnabled.put(False)
        flight_recorder.reset_for_tests()

    def _arm_and_span(self, tmp_path):
        TraceDir.put(str(tmp_path))
        TraceEnabled.put(True)
        from modin_tpu.observability import spans as graftscope_spans

        with graftscope_spans.span("io.read", layer="CORE-IO"):
            pass

    def test_dump_embeds_metrics_snapshot(self, tmp_path):
        MetersEnabled.put(True)
        emit_metric("sortcache.hit", 7)
        self._arm_and_span(tmp_path)
        path = flight_recorder.dump_flight_record("unit_metrics")
        assert path is not None
        data = json.loads(open(path).read())
        embedded = data["otherData"]["metrics"]
        assert embedded["enabled"] is True
        assert embedded["series"]["sortcache.hit"]["total"] == 7

    def test_rate_limited_path_regression(self, tmp_path):
        """The metrics embedding must not break rate limiting: the second
        dump inside the window stays suppressed, and the limiter window is
        still released on a failed write."""
        MetersEnabled.put(True)
        emit_metric("sortcache.hit", 1)
        self._arm_and_span(tmp_path)
        first = flight_recorder.dump_flight_record("unit_rate")
        assert first is not None
        assert flight_recorder.dump_flight_record("unit_rate") is None
        # outside the window it dumps again, still with the snapshot
        flight_recorder._last_dump = 0.0
        second = flight_recorder.dump_flight_record("unit_rate2")
        assert second is not None and second != first
        assert "metrics" in json.loads(open(second).read())["otherData"]

    def test_dump_with_meters_off_records_disabled_snapshot(self, tmp_path):
        self._arm_and_span(tmp_path)
        path = flight_recorder.dump_flight_record("unit_off")
        assert path is not None
        embedded = json.loads(open(path).read())["otherData"]["metrics"]
        assert embedded["enabled"] is False


# ====================================================================== #
# EXPLAIN ANALYZE
# ====================================================================== #


class TestExplainAnalyze:
    def _csv(self, tmp_path, rows=200):
        path = str(tmp_path / "t.csv")
        rng = np.random.default_rng(3)
        pandas.DataFrame(
            {
                "a": rng.integers(-10, 10, rows),
                "b": rng.uniform(0, 1, rows),
                "c": rng.uniform(0, 1, rows),
                "d": rng.integers(0, 5, rows),
            }
        ).to_csv(path, index=False)
        return path

    def test_analyze_annotates_every_node_and_stays_bit_exact(self, tmp_path):
        _require_tpu_on_jax()
        from modin_tpu.config import PlanMode

        path = self._csv(tmp_path)
        with PlanMode.context("Auto"):
            md = pd.read_csv(path).query("a > 0")[["b", "c"]]
            if md._query_compiler._plan is None:
                pytest.skip("read did not defer under this configuration")
            text = md.modin.explain(analyze=True)
            result = md.agg("sum").modin.to_pandas()
        assert "status: analyzed" in text
        after = text.split("with actuals) ==")[1].split("rewrites:")[0]
        node_lines = [ln for ln in after.splitlines() if ln.strip().startswith("#")]
        assert node_lines
        for ln in node_lines:
            assert "(actual:" in ln, ln
            for field in ("time=", "rows=", "bytes=", "dispatches="):
                assert field in ln, ln
        assert "== query rollup ==" in text
        reference = pandas.read_csv(path).query("a > 0")[["b", "c"]].agg("sum")
        pandas.testing.assert_series_equal(result, reference)

    def test_analyze_attributes_dispatches_and_wall_time(self, tmp_path):
        _require_tpu_on_jax()
        from modin_tpu.config import PlanMode
        from modin_tpu.plan import runtime

        path = self._csv(tmp_path)
        with PlanMode.context("Auto"):
            md = pd.read_csv(path).query("a > 0")[["b", "c"]]
            if md._query_compiler._plan is None:
                pytest.skip("read did not defer under this configuration")
            analyzed = runtime.explain_analyze(md._query_compiler)
        assert analyzed is not None
        stats, actuals, (_root, _optimized, _applied) = analyzed
        assert stats.dispatches >= 1
        assert stats.wall_s > 0
        assert stats.bytes_parsed > 0
        # dispatch attribution: per-node self dispatches sum to the rollup
        assert sum(m["dispatches"] for m in actuals.values()) == stats.dispatches
        # every actual entry has a measured time
        assert all(m["total_s"] >= m["self_s"] >= 0 for m in actuals.values())

    def test_analyze_on_plain_eager_compiler_reports_eager(self):
        df = pd.DataFrame({"a": [1, 2, 3]})
        text = df.modin.explain(analyze=True)
        assert text.startswith("status: eager")

    def test_analyze_tolerates_non_graftplan_compiler(self):
        """A compiler without _plan/_plan_explain (any non-Tpu backend) gets
        the eager note, not an AttributeError — same as analyze=False."""
        from modin_tpu.plan import runtime
        from modin_tpu.plan.explain import explain_qc

        assert runtime.explain_analyze(object()) is None
        assert explain_qc(object(), analyze=True).startswith("status: eager")

    def test_alloc_free_when_analyze_not_used(self, tmp_path):
        """explain(analyze=False) keeps the old contract: no QueryStats."""
        df = pd.DataFrame({"a": [1, 2, 3]})
        before = meters.meter_alloc_count()
        _ = df.modin.explain()
        assert meters.meter_alloc_count() == before
