"""graftscope acceptance: spans, export, compile ledger, flight recorder.

Acceptance bar (ISSUE 3): ``profile()`` around a groupby+merge workload
exports chrome://tracing-loadable JSON with nested spans from the API,
query-compiler, engine-seam, and shuffle layers plus host/device/compile
rollups; with tracing disabled the same workload allocates ZERO span
objects; the compile ledger counts a forced recompile; and the flight
recorder dumps on an injected terminal fault.  Plus the satellite
regression: ``configure_logging`` is race-free (one sampler thread, one
handler set, under concurrent first calls).
"""

import json
import subprocess
import sys
import threading

import numpy as np
import pytest

import modin_tpu.observability as graftscope
import modin_tpu.pandas as pd
from modin_tpu.config import (
    RangePartitioning,
    ResilienceRetries,
    TraceDir,
    TraceEnabled,
)
from modin_tpu.core.execution import resilience
from modin_tpu.core.execution.resilience import DeviceOOM, reset_breakers
from modin_tpu.observability import flight_recorder
from modin_tpu.observability.compile_ledger import get_compile_ledger
from modin_tpu.observability.spans import API_LAYERS
from modin_tpu.testing import inject_faults


@pytest.fixture(autouse=True)
def _tracing_off_between_tests():
    """Every test starts and ends with tracing disabled and a clean ring."""
    TraceEnabled.put(False)
    yield
    TraceEnabled.put(False)
    flight_recorder.reset_for_tests()


def _require_tpu_on_jax():
    """Engine-seam span assertions only hold on the device execution; the
    PandasOnPython / NativeOnNative gates skip instead of failing."""
    from modin_tpu.utils import get_current_execution

    if get_current_execution() != "TpuOnJax":
        pytest.skip("engine-seam spans require the TpuOnJax execution")


def _workload():
    """A small groupby+merge pipeline exercising all the instrumented
    layers; returns the final (executed) result."""
    df = pd.DataFrame(
        {"k": [i % 13 for i in range(512)], "v": np.arange(512, dtype=np.float64)}
    )
    dim = pd.DataFrame({"k": list(range(13)), "w": [i * 2.0 for i in range(13)]})
    merged = df.merge(dim, on="k", how="left")
    agg = merged.groupby("k").sum()
    agg._query_compiler.execute()
    return agg


# ====================================================================== #
# span nesting & propagation
# ====================================================================== #


class TestSpanNesting:
    def test_profile_collects_nested_spans_across_layers(self):
        _require_tpu_on_jax()
        with graftscope.profile() as prof:
            _workload()
        layers = {sp.layer for sp in prof.spans}
        assert "PANDAS-API" in layers
        assert "QUERY-COMPILER" in layers
        assert "JAX-ENGINE" in layers

    def test_engine_attempt_nests_under_compiler_and_api(self):
        """The seam chain: an engine attempt span must have QUERY-COMPILER
        and PANDAS-API ancestors — context propagated across all layers."""
        _require_tpu_on_jax()
        with graftscope.profile() as prof:
            _workload()
        attempts = prof.find("engine.")
        assert attempts, "no engine-seam attempt spans collected"
        chained = 0
        for sp in attempts:
            ancestor_layers = {a.layer for a in prof.ancestors(sp)}
            if "QUERY-COMPILER" in ancestor_layers and (
                ancestor_layers & API_LAYERS
            ):
                chained += 1
        assert chained > 0, "no attempt span nested under compiler + API"

    def test_manual_span_nesting_and_attrs(self):
        with graftscope.profile() as prof:
            with graftscope.span("shuffle.range_shuffle", layer="SHUFFLE", rows=4) as outer:
                assert outer is graftscope.current_span()
                with graftscope.layer_span("inner.op", "QUERY-COMPILER") as inner:
                    assert inner.parent_id == outer.span_id
        by_name = {sp.name: sp for sp in prof.spans}
        assert by_name["inner.op"].parent_id == by_name["shuffle.range_shuffle"].span_id
        assert by_name["shuffle.range_shuffle"].attrs["rows"] == 4
        assert by_name["shuffle.range_shuffle"].dur_us >= by_name["inner.op"].dur_us

    def test_span_error_status_on_exception(self):
        with graftscope.profile() as prof:
            with pytest.raises(ValueError):
                with graftscope.span("io.read", layer="CORE-IO"):
                    raise ValueError("boom")
        (sp,) = prof.spans
        assert sp.status == "error"
        assert sp.attrs["exc"] == "ValueError"

    def test_watchdog_thread_adopts_parent_context(self):
        """Spans/attribution on the resilience watchdog thread chain to the
        span that issued the engine call."""
        from modin_tpu.config import ResilienceWatchdogS

        seen = {}

        def thunk():
            from modin_tpu.observability.spans import attribution_signature

            seen["sig"] = attribution_signature()
            return 1

        with ResilienceWatchdogS.context(5.0):
            with graftscope.profile():
                with graftscope.layer_span("Outer.op", "QUERY-COMPILER"):
                    resilience.engine_call("materialize", thunk, watchdog=True)
        assert seen["sig"] == "Outer.op"


# ====================================================================== #
# chrome trace export
# ====================================================================== #


class TestChromeTraceExport:
    def test_groupby_merge_export_is_schema_valid(self, tmp_path):
        with graftscope.profile() as prof:
            _workload()
        path = tmp_path / "trace.json"
        prof.export_chrome_trace(path)
        trace = json.loads(path.read_text())
        assert isinstance(trace["traceEvents"], list)
        complete = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert complete
        for event in complete:
            assert isinstance(event["name"], str)
            assert isinstance(event["cat"], str)
            assert isinstance(event["ts"], (int, float))
            assert isinstance(event["dur"], (int, float))
            assert isinstance(event["pid"], int)
            assert isinstance(event["tid"], int)
            assert isinstance(event["args"], dict)
            assert "span_id" in event["args"]
        # parent ids reference exported spans (the nesting survives export)
        ids = {e["args"]["span_id"] for e in complete}
        child_links = [
            e for e in complete if e["args"].get("parent_id") in ids
        ]
        assert child_links, "no parent->child links in the export"
        # thread metadata present
        assert any(e.get("ph") == "M" for e in trace["traceEvents"])
        # rollup rides along
        rollup = trace["otherData"]["rollup"]
        for key in ("wall_s", "host_s", "engine_s", "compile_s", "spans"):
            assert key in rollup

    def test_rollup_accounting(self):
        with graftscope.profile() as prof:
            _workload()
        rollup = prof.rollup()
        assert rollup["spans"] == len(prof.spans) > 0
        assert rollup["wall_s"] > 0
        # engine time is part of the wall, host is the rest
        assert rollup["engine_s"] <= rollup["wall_s"] + 1e-6
        assert rollup["host_s"] == pytest.approx(
            max(rollup["wall_s"] - rollup["engine_s"], 0.0), abs=1e-6
        )
        assert set(rollup["by_layer_self_s"]) == {sp.layer for sp in prof.spans}


# ====================================================================== #
# disabled mode: zero allocation
# ====================================================================== #


class TestDisabledMode:
    def test_workload_allocates_no_spans_when_disabled(self):
        assert not graftscope.trace_enabled()
        _workload()  # warm any lazy imports/caches outside the window
        before = graftscope.span_alloc_count()
        _workload()
        assert graftscope.span_alloc_count() == before, (
            "span objects were allocated while MODIN_TPU_TRACE=0"
        )

    def test_span_api_returns_null_handle_when_disabled(self):
        before = graftscope.span_alloc_count()
        with graftscope.span("io.read", layer="CORE-IO") as sp:
            assert sp is None
        with graftscope.layer_span("X.y", "PANDAS-API") as sp:
            assert sp is None
        assert graftscope.span_alloc_count() == before

    def test_enable_disable_roundtrip(self):
        assert not graftscope.trace_enabled()
        TraceEnabled.put(True)
        try:
            assert graftscope.trace_enabled()
            with graftscope.span("io.read", layer="CORE-IO") as sp:
                assert sp is not None
        finally:
            TraceEnabled.put(False)
        assert not graftscope.trace_enabled()


# ====================================================================== #
# compile ledger
# ====================================================================== #


class TestCompileLedger:
    def test_forced_recompile_is_counted_and_attributed(self):
        import jax
        import jax.numpy as jnp

        ledger = get_compile_ledger()

        # a fresh (never-jitted) function forces a backend compile
        def fresh(x):
            return x * 3 + 1.5

        jitted = jax.jit(fresh)
        arg = jnp.arange(8, dtype=jnp.float64)
        with graftscope.profile():
            with graftscope.layer_span("TestLedger.fresh_op", "QUERY-COMPILER"):
                before = ledger.snapshot()
                np.asarray(jitted(arg))
                after = ledger.snapshot()
        sig = "TestLedger.fresh_op"
        assert after["total_compiles"] > before["total_compiles"]
        assert sig in after["signatures"]
        assert after["signatures"][sig]["compiles"] >= 1
        assert after["signatures"][sig]["compile_s"] > 0

        # second call hits the executable cache: compile count flat
        before = ledger.snapshot()["signatures"][sig]["compiles"]
        with graftscope.profile():
            with graftscope.layer_span(sig, "QUERY-COMPILER"):
                np.asarray(jitted(arg))
        assert ledger.snapshot()["signatures"][sig]["compiles"] == before

    def test_deploy_cache_hits_recorded_through_engine_seam(self):
        """Dispatching the same op twice through the traced engine seam
        records a cache hit for its signature on the second dispatch."""
        import jax
        import jax.numpy as jnp

        from modin_tpu.parallel.engine import JaxWrapper

        jitted = jax.jit(lambda x: x - 7)
        arg = jnp.arange(16, dtype=jnp.float64)
        ledger = get_compile_ledger()
        sig = "TestLedger.hit_op"
        with graftscope.profile():
            for _ in range(2):
                with graftscope.layer_span(sig, "QUERY-COMPILER"):
                    JaxWrapper.wait(JaxWrapper.deploy(jitted, (arg,)))
        entry = ledger.snapshot()["signatures"][sig]
        assert entry["dispatches"] >= 2
        assert entry["cache_hits"] >= 1

    def test_recompile_storm_report(self):
        ledger = get_compile_ledger()
        for _ in range(3):
            ledger.record_compile("stormy_op", 0.25)
        assert ledger.recompile_storms(min_compiles=3).get("stormy_op", 0) >= 3

    def test_compile_time_attributed_to_open_span(self):
        import jax
        import jax.numpy as jnp

        def fresh(x):
            return jnp.sqrt(x) + 2

        jitted = jax.jit(fresh)
        with graftscope.profile() as prof:
            with graftscope.layer_span("TestLedger.span_attr", "QUERY-COMPILER"):
                np.asarray(jitted(jnp.arange(4, dtype=jnp.float64)))
        total_compile = sum(sp.attrs.get("compile_s", 0.0) for sp in prof.spans)
        assert total_compile > 0
        assert prof.rollup()["compile_s"] == pytest.approx(total_compile)


# ====================================================================== #
# flight recorder
# ====================================================================== #


class TestFlightRecorder:
    @pytest.fixture(autouse=True)
    def _fast_dumps(self, monkeypatch):
        monkeypatch.setattr(flight_recorder, "MIN_DUMP_INTERVAL_S", 0.0)
        reset_breakers()
        # cyclic garbage from earlier suites (plan graphs pin compilers until
        # a full gc pass) can leave columns in the device ledger, and a
        # resident ledger turns the injected terminal OOM below into a
        # successful evict-then-retry — collect so the injection is terminal
        import gc

        gc.collect()
        yield
        reset_breakers()

    def test_dump_fires_on_injected_terminal_fault(self, tmp_path):
        """An injected OOM at the engine seam is terminal: the ring of
        recent spans must land on disk as a loadable chrome trace."""
        import jax.numpy as jnp

        from modin_tpu.parallel.engine import JaxWrapper

        with TraceDir.context(str(tmp_path)), TraceEnabled.context(True):
            flight_recorder.reset_for_tests()
            with graftscope.layer_span("TestFlight.query", "QUERY-COMPILER"):
                with inject_faults("oom", ops=("materialize",), times=1):
                    with pytest.raises(DeviceOOM):
                        JaxWrapper.materialize(jnp.arange(4))
            dumps = sorted(tmp_path.glob("flightrec_terminal_oom_*.trace.json"))
            assert dumps, f"no flight dump written under {tmp_path}"
            trace = json.loads(dumps[0].read_text())
            assert trace["otherData"]["reason"] == "terminal_oom"
            names = [e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"]
            assert any(n == "engine.materialize.attempt" for n in names)

    def test_dump_fires_when_breaker_opens(self, tmp_path):
        from modin_tpu.config import ResilienceBreakerThreshold
        from modin_tpu.core.execution.resilience import get_breaker

        with TraceDir.context(str(tmp_path)), TraceEnabled.context(True):
            flight_recorder.reset_for_tests()
            with graftscope.span("io.read", layer="CORE-IO"):
                pass  # something in the ring
            with ResilienceBreakerThreshold.context(2):
                breaker = get_breaker("probe_flight")
                breaker.record_failure()
                breaker.record_failure()
            dumps = sorted(
                tmp_path.glob("flightrec_breaker_open_probe_flight_*.trace.json")
            )
            assert dumps, "no dump on breaker open"

    def test_no_dump_when_tracing_disabled(self, tmp_path):
        import jax.numpy as jnp

        from modin_tpu.parallel.engine import JaxWrapper

        assert not graftscope.trace_enabled()
        with TraceDir.context(str(tmp_path)):
            with inject_faults("oom", ops=("materialize",), times=1):
                with pytest.raises(DeviceOOM):
                    JaxWrapper.materialize(jnp.arange(4))
        assert not list(tmp_path.glob("*.trace.json"))

    def test_flight_ring_resizes_on_config_change(self):
        from modin_tpu.config import TraceFlightRecorderSize

        with TraceEnabled.context(True):
            with TraceFlightRecorderSize.context(4):
                for i in range(10):
                    with graftscope.layer_span(f"resize{i}", "QUERY-COMPILER"):
                        pass
                snap = flight_recorder.flight_snapshot()
                assert len(snap) == 4
                assert snap[-1].name == "resize9"

    def test_flight_snapshot_bounded_by_ring(self):
        from modin_tpu.config import TraceFlightRecorderSize

        size = int(TraceFlightRecorderSize.get())
        with TraceEnabled.context(True):
            flight_recorder.reset_for_tests()
            for i in range(size + 50):
                with graftscope.layer_span(f"op{i}", "QUERY-COMPILER"):
                    pass
            snap = flight_recorder.flight_snapshot()
            assert len(snap) == size
            # oldest dropped, newest retained
            assert snap[-1].name == f"op{size + 49}"


# ====================================================================== #
# retries appear as sibling attempt spans with failure kinds
# ====================================================================== #


class TestResilienceComposition:
    def test_retried_transient_shows_failed_and_clean_attempts(self):
        with ResilienceRetries.context(2):
            with graftscope.profile() as prof:
                with inject_faults("transient", ops=("put",), times=1):
                    from modin_tpu.parallel.engine import JaxWrapper

                    JaxWrapper.put(np.arange(32, dtype=np.float64))
        attempts = [sp for sp in prof.spans if sp.name == "engine.put.attempt"]
        assert len(attempts) >= 2
        failed = [sp for sp in attempts if sp.status == "error"]
        clean = [sp for sp in attempts if sp.status == "ok"]
        assert failed and clean
        assert failed[0].attrs["failure_kind"] == "transient"
        assert failed[0].attrs["attempt"] == 0

    def test_base_exception_unwind_pops_attempt_span(self):
        """A non-Exception unwind (Ctrl-C, the bench SIGALRM) through the
        engine seam must not leave the attempt span on the thread stack."""

        class Unwind(BaseException):
            pass

        def thunk():
            raise Unwind()

        with graftscope.profile() as prof:
            with pytest.raises(Unwind):
                resilience.engine_call("wait", thunk)
            assert graftscope.current_span() is None
        (sp,) = prof.find("engine.wait.attempt")
        assert sp.status == "error"

    def test_device_path_fallback_emits_fallback_span(self):
        from modin_tpu.core.execution.resilience import device_path

        class Probe:
            @device_path("probe_span_unit")
            def _try_thing(self):
                raise resilience.TransientDeviceError("DEADLINE_EXCEEDED")

        with graftscope.profile() as prof:
            assert Probe()._try_thing() is None
        falls = prof.find("fallback.probe_span_unit")
        assert len(falls) == 1
        assert falls[0].attrs["reason"] == "transient"


# ====================================================================== #
# satellite: configure_logging race regression
# ====================================================================== #

_RACE_SNIPPET = r"""
import threading
import modin_tpu.logging.config as cfg
from modin_tpu.config import LogMode

LogMode.put("Enable")
barrier = threading.Barrier(8)
def hammer():
    barrier.wait()
    cfg.get_logger()
threads = [threading.Thread(target=hammer) for _ in range(8)]
for t in threads: t.start()
for t in threads: t.join()

import logging
handlers = logging.getLogger("modin_tpu.logger").handlers
samplers = [
    t for t in threading.enumerate() if t.name == "modin-tpu-memory-sampler"
]
print("HANDLERS", len(handlers), "SAMPLERS", len(samplers),
      "CONFIGURED", cfg.__LOGGER_CONFIGURED__, flush=True)
# skip interpreter teardown: the daemon sampler thread may be inside jax
# C++ when the runtime is torn down, which aborts an otherwise-passed run
import os
os._exit(0)
"""


class TestConfigureLoggingRace:
    def test_concurrent_first_configuration_happens_once(self, tmp_path):
        """Eight threads race get_logger(); exactly one handler set and one
        memory-sampler daemon must exist (subprocess: fresh module state)."""
        import os
        import pathlib

        repo_root = pathlib.Path(__file__).resolve().parent.parent
        env = dict(os.environ)
        env["PYTHONPATH"] = str(repo_root) + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-c", _RACE_SNIPPET],
            capture_output=True,
            text=True,
            timeout=120,
            cwd=tmp_path,
            env=env,
        )
        assert proc.returncode == 0, proc.stderr
        line = [l for l in proc.stdout.splitlines() if l.startswith("HANDLERS")][-1]
        assert line == "HANDLERS 1 SAMPLERS 1 CONFIGURED True", line

    def test_reconfigure_is_noop_and_keeps_sampler_handle(self):
        import modin_tpu.logging.config as cfg

        from modin_tpu.concurrency.lockdep import DepLock

        lock = cfg._configure_lock
        # a registry-named non-reentrant mutex (graftdep wraps the raw lock)
        assert isinstance(lock, DepLock) and not lock.reentrant
        assert lock.name == "logging.configure"
        # simulate "already configured": the body must not run again
        saved = cfg.__LOGGER_CONFIGURED__
        cfg.__LOGGER_CONFIGURED__ = True
        try:
            sampler_before = cfg._mem_sampler
            cfg.configure_logging()
            assert cfg._mem_sampler is sampler_before
        finally:
            cfg.__LOGGER_CONFIGURED__ = saved


# ====================================================================== #
# the per-request record: query_stats switches graftscope on, spans lie on
# the profiler's clock, device programs carry their builder's name
# ====================================================================== #

_REPO = __import__("pathlib").Path(__file__).resolve().parent.parent
_BENCH = _REPO / "benchmark"


def _bench_module(*parts):
    import importlib.util

    path = _BENCH.joinpath(*parts)
    spec = importlib.util.spec_from_file_location("t_" + path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _first_run(question, frame):
    """One request as ``benchmark/run.py`` asks it: derived answers dropped,
    the question, the answer materialised."""
    from modin_tpu.ops.groupby import clear_factorize_cache
    from modin_tpu.views import registry

    registry.reset()
    clear_factorize_cache()
    answer = question(frame)
    answer._query_compiler.execute()
    return answer


@pytest.fixture(scope="module")
def small_frame():
    rng = np.random.default_rng(7)
    frame = pd.DataFrame({f"c{i}": rng.integers(0, 100, 4096) for i in range(5)})
    frame._query_compiler.execute()
    return frame


#: the cells' questions: cell file name -> the configuration it runs on (q5 is
#: read twice: beside q4 on the table's configuration and on its own, PR 31)
_CELL_CONFIGS = {
    "h2o_q4_mean_by_id4": "h2o-groupby-g1-1e8-1e2",
    "asv_time_arithmetic": "asv-int-5e7x10",
    "h2o_q5_sum_by_id6_1e6": "h2o-groupby-g1-1e8-1e2-id6",
}


def _cell_questions():
    out = []
    for config in sorted(set(_CELL_CONFIGS.values())):
        for path in sorted((_BENCH / "questions" / config).glob("*.py")):
            out.append((config, path.stem))
    return out


@pytest.fixture(scope="module")
def cell_frames():
    """Each configuration's table at its rehearsal size, made once."""
    frames = {}

    def frame_of(config_name):
        if config_name not in frames:
            config = json.loads((_BENCH / "configs" / f"{config_name}.json").read_text())
            rows = int(config["rehearse_rows"])
            host = _bench_module("datasets", config["generator"] + ".py").make(
                11, dict(config, rows=rows), rows
            )
            frame = pd.DataFrame(host)
            frame._query_compiler.execute()
            frames[config_name] = frame
        return frames[config_name]

    return frame_of


class TestRequestRecord:
    def test_scope_switches_tracing_on_for_its_life_only(self):
        assert not graftscope.trace_enabled()
        with graftscope.query_stats("q") as outer:
            assert graftscope.trace_enabled()
            with graftscope.query_stats("q2"):
                assert graftscope.trace_enabled()
            assert graftscope.trace_enabled()  # the outer scope still lives
            with graftscope.layer_span("Some.op", "QUERY-COMPILER") as sp:
                assert sp.request == outer.request_id
        assert not graftscope.trace_enabled()

    @pytest.mark.parametrize("key_step", [1, 3], ids=["dense_key", "key_with_holes"])
    def test_device_groupby_counts_launches_and_host_syncs(self, small_frame, key_step):
        _require_tpu_on_jax()
        if key_step > 1:  # c0 in [0, 100) times 3: two of every three ids absent
            host = small_frame.modin.to_pandas()
            host["c0"] *= key_step
            small_frame = pd.DataFrame(host)
            small_frame._query_compiler.execute()
        question = lambda df: df.groupby("c0").agg({"c1": "mean", "c2": "mean"})  # noqa: E731
        _first_run(question, small_frame)
        with graftscope.profile() as prof:
            with graftscope.query_stats("gb") as stats:
                _first_run(question, small_frame)
        assert stats.launches >= 3
        assert stats.host_syncs >= 1 and stats.d2h_bytes > 0
        assert sum(stats.launches_by_program.values()) == stats.launches
        assert any(name.startswith("groupby_") for name in stats.launches_by_program)
        # dispatches keeps its meaning: successful engine-seam deploys
        deploys = [sp for sp in prof.find("engine.deploy.attempt") if sp.status == "ok"]
        assert stats.dispatches == len(deploys)
        assert stats.first_launch_s is not None and 0 < stats.first_launch_s < stats.wall_s
        # only a key range with holes needs (and uploads) a remap table
        remapped = "groupby_range_codes" in stats.launches_by_program
        assert remapped == (key_step > 1)
        assert (stats.h2d_bytes > 0) == remapped

    @pytest.mark.parametrize(
        "question",
        [
            lambda df: df.add(2),
            lambda df: df.sum(),
            lambda df: df.count(axis=1),
            lambda df: df.groupby("c0").agg({"c1": "mean"}),
        ],
        ids=["add", "sum", "count_axis1", "groupby_mean"],
    )
    def test_host_split_sums_to_the_wall(self, small_frame, question):
        _first_run(question, small_frame)
        with graftscope.query_stats("split") as stats:
            _first_run(question, small_frame)
        record = stats.as_dict()
        total = sum(record["host_self_s"].values()) + record["wait_s"]
        assert total == pytest.approx(record["wall_s"], rel=0.05)
        assert all(seconds >= 0 for seconds in record["host_self_s"].values())
        assert "PANDAS-API" in record["host_self_s"] and "CALLER" in record["host_self_s"]
        assert record["spans"] > 0

    def test_add_emits_few_spans_and_no_is_future(self, small_frame):
        _first_run(lambda df: df.add(2), small_frame)
        with graftscope.profile() as prof:
            _first_run(lambda df: df.add(2), small_frame)
        names = [sp.name for sp in prof.spans]
        assert not [n for n in names if "is_future" in n]
        assert len(names) <= 20, names
        assert "lazy.linearize" in names  # the plan/lazy step has a span of its own

    def test_no_spans_allocated_outside_any_scope(self, small_frame):
        with graftscope.query_stats("warm"):
            _first_run(lambda df: df.add(2), small_frame)
        assert not graftscope.trace_enabled()
        before = graftscope.span_alloc_count()
        for _ in range(100):
            _first_run(lambda df: df.add(2), small_frame)
        assert graftscope.span_alloc_count() == before

    def test_recent_queries_is_bounded_and_ordered(self):
        from modin_tpu.observability import meters

        for i in range(meters.RECENT_QUERIES_MAX + 8):
            with graftscope.query_stats(f"ring{i % 2}"):
                pass
        every = graftscope.recent_queries()
        assert len(every) == meters.RECENT_QUERIES_MAX
        ids = [record["request_id"] for record in every]
        assert ids == sorted(ids) and len(set(ids)) == len(ids)  # oldest first
        ours = graftscope.recent_queries("ring1")
        assert ours and {record["label"] for record in ours} == {"ring1"}
        assert ours[-1]["request_id"] == max(r["request_id"] for r in ours)
        assert len(ours) < len(every)

    def test_spans_land_in_a_profiler_trace_inside_the_callers_annotation(
        self, small_frame, tmp_path
    ):
        import glob

        import jax
        from jax.profiler import ProfileData

        _first_run(lambda df: df.add(2), small_frame)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("outer/request"):
                with graftscope.query_stats("traced") as stats:
                    with graftscope.span("io.read", layer="CORE-IO", dispatcher="Probe"):
                        _first_run(lambda df: df.add(2), small_frame)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        events = [
            ev
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
            for ev in line.events
            if ev.name.startswith(("mt/", "outer/"))
        ]
        (outer,) = [ev for ev in events if ev.name == "outer/request"]
        ours = [ev for ev in events if ev.name.startswith("mt/")]
        assert {ev.name.split("/")[1] for ev in ours} >= {"PANDAS-API", "QUERY-COMPILER", "JAX-ENGINE"}
        for ev in ours:
            assert outer.start_ns <= ev.start_ns
            assert ev.start_ns + ev.duration_ns <= outer.start_ns + outer.duration_ns
            assert dict(ev.stats)["request"] == stats.request_id
        (probe,) = [ev for ev in ours if ev.name == "mt/CORE-IO/io.read"]
        assert dict(probe.stats)["dispatcher"] == "Probe"  # span attributes are event stats

    @pytest.mark.parametrize("config, name", _cell_questions())
    def test_every_program_a_cells_question_launches_is_named(
        self, cell_frames, config, name
    ):
        _require_tpu_on_jax()
        frame = cell_frames(config)
        question = _bench_module("questions", config, name + ".py")
        _first_run(lambda df: question.run(pd, df), frame)
        with graftscope.query_stats(name) as stats:
            _first_run(lambda df: question.run(pd, df), frame)
        assert stats.launches > 0, "the question launched no device program"
        for program in stats.launches_by_program:
            assert program not in ("fn", "execute", "program") and "<" not in program
            assert not program.startswith("bench_")
            assert program.split("_")[0] in (
                "plan", "groupby", "reduce", "sort", "elementwise", "structural",
                "join", "window", "stats", "datetime", "spmd", "shuffle", "router",
            ), program

    def test_q4_on_one_tpu_chip_takes_the_pallas_histogram_and_the_limbs(self, cell_frames):
        """The H2O q4 request with the TPU's choice of forms forced (a
        one-shard mesh, the kernels in interpret mode): the record says which
        forms ran, how often, and names their programs."""
        _require_tpu_on_jax()
        import pandas

        from tests.test_groupby import _one_shard_tpu_choice

        config = "h2o-groupby-g1-1e8-1e2"
        question = _bench_module("questions", config, "q4_mean_by_id4.py")
        host = cell_frames(config).modin.to_pandas()
        with _one_shard_tpu_choice("tpu"):
            frame = pd.DataFrame(host)
            frame._query_compiler.execute()
            with graftscope.query_stats("q4-forms") as stats:
                answer = _first_run(lambda df: question.run(pd, df), frame)
            got = answer.modin.to_pandas()
        assert stats.groupby_forms == {"pallas_bincount": 1, "limb_dot": 3}
        assert graftscope.recent_queries("q4-forms")[-1]["groupby_forms"] == stats.groupby_forms
        assert stats.launches_by_program["groupby_limb_dot"] == 3
        assert stats.launches_by_program["groupby_pallas_bincount"] == 1
        assert "groupby_masked_scan_smc" not in stats.launches_by_program
        want = question.run(pandas, host)
        assert got.index.equals(want.index) and list(got.columns) == list(want.columns)
        np.testing.assert_allclose(got.to_numpy(), want.to_numpy(), rtol=1e-13)


class TestNamedPrograms:
    def test_program_carries_its_name_into_the_lowering(self):
        import jax.numpy as jnp

        from modin_tpu.ops import groupby

        program = groupby._jit_range_codes(8, 3)
        assert program.name == "groupby_range_codes"
        lowered = program.lower(
            jnp.arange(8), jnp.int64(0), jnp.arange(8)
        )  # .lower() is the jitted function's own
        assert "jit_groupby_range_codes" in lowered.as_text()

    def test_bench_prefix_is_refused(self):
        from modin_tpu.ops._program import named_jit

        with pytest.raises(ValueError):
            named_jit(lambda x: x, "bench_slices")

    @pytest.mark.parametrize(
        "nodes, tail_key, want",
        [
            ((("mod", (), ()),) * 3, None, "plan_mod"),
            ((("add", (), ()), ("abs", (), ())), ("reduce", "sum", 7, True), "plan_add_abs_reduce_sum"),
            ((), ("reduce_axis1", "count", True), "plan_reduce_axis1_count"),
            ((), (3, "x"), "plan_tail"),
        ],
    )
    def test_fused_program_is_named_after_its_ops(self, nodes, tail_key, want):
        from modin_tpu.ops.lazy import _program_name

        assert _program_name(nodes, tail_key) == want

    def test_long_names_are_cut(self):
        from modin_tpu.ops._program import named_jit

        assert len(named_jit(lambda x: x, "plan_" + "x" * 80).name) == 48
