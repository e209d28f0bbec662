"""graftwatch: rings, sampler lifecycle, SLO burn rates, tripwires, HTTP.

Acceptance bar (ISSUE 15): with ``MODIN_TPU_WATCH=0`` no sampler or
exporter thread exists and the hot path costs one attribute check with
zero allocations; with it on, the sampler folds the telemetry seams into
bounded rings, ``/metrics`` stays parseable under load, per-tenant SLO
burn rates go advisory into ``serving_snapshot()``, tripwires capture
exactly one rate-limited evidence bundle per incident, and a crashed
sampler degrades the service to disabled (``watch.sampler.died``)
instead of taking queries down.
"""

import glob
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
import modin_tpu.serving as serving
from modin_tpu.config import (
    MetersEnabled,
    ResilienceBackoffS,
    ServingEnabled,
    ServingMaxConcurrent,
    ServingQueueDepth,
    TraceDir,
    TraceEnabled,
    WatchEnabled,
    WatchIntervalS,
    WatchPort,
    WatchSloMs,
)
from modin_tpu.core.execution.resilience import reset_breakers
from modin_tpu.logging import add_metric_handler, clear_metric_handler
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import flight_recorder, meters, watch
from modin_tpu.observability.watch import slo as slo_mod
from modin_tpu.observability.watch import timeseries as ts_mod
from modin_tpu.observability.watch import tripwires as tw_mod
from modin_tpu.serving import tenants as serving_tenants
from modin_tpu.serving.gate import gate

_PARAMS = (
    WatchEnabled,
    WatchIntervalS,
    WatchPort,
    WatchSloMs,
    MetersEnabled,
    ServingEnabled,
    ServingMaxConcurrent,
    ServingQueueDepth,
    TraceEnabled,
    TraceDir,
    ResilienceBackoffS,
)


@pytest.fixture(autouse=True)
def _clean_watch_state():
    saved = [(p, p.get()) for p in _PARAMS]
    WatchEnabled.put(False)
    meters.reset()
    yield
    for p, v in saved:
        p.put(v)
    WatchEnabled.put(False)
    meters.reset()
    reset_breakers()
    gate.reset_for_tests()
    serving_tenants.registry.reset()
    service = watch.get_service()
    if service is not None:
        service.rings.reset()
        service.slo.reset()
        service.tripwires.recent.clear()
        for rule in service.tripwires.rules:
            rule.last_tripped = None
    flight_recorder.reset_for_tests()


@pytest.fixture
def metric_names():
    seen = []
    handler = lambda name, value: seen.append(name)  # noqa: E731
    add_metric_handler(handler)
    yield seen
    clear_metric_handler(handler)


def _watch_threads():
    return [
        t.name
        for t in threading.enumerate()
        # the dash matters: "modin-tpu-watchdog-*" are the resilience
        # watchdog's workers, which another test file may leave behind on
        # this xdist worker
        if t.name.startswith("modin-tpu-watch-")
    ]


def _wait_for(predicate, timeout_s=10.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _get(port, path, timeout=5.0):
    return (
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        )
        .read()
        .decode()
    )


# ====================================================================== #
# disabled-mode contract
# ====================================================================== #


class TestDisabledMode:
    def test_no_threads_and_zero_alloc_when_off(self):
        """MODIN_TPU_WATCH=0: no sampler/exporter thread, and a full
        workload (including serving submits) allocates zero graftwatch
        objects — the hot path is one module-attribute check."""
        assert not watch.WATCH_ON
        assert _watch_threads() == []
        df = pd.DataFrame({"a": np.arange(128.0), "k": np.arange(128) % 5})
        _ = df.groupby("k").sum().modin.to_pandas()  # warm every code path
        ServingEnabled.put(True)
        ResilienceBackoffS.put(0.0)
        before = watch.watch_alloc_count()
        df2 = pd.DataFrame({"a": np.arange(128.0), "k": np.arange(128) % 5})
        _ = serving.submit(
            lambda: df2.groupby("k").sum().modin.to_pandas(), tenant="alice"
        )
        _ = (df2["a"] * 2).sum()
        assert watch.watch_alloc_count() == before
        assert _watch_threads() == []

    def test_observe_query_is_noop_when_off(self):
        watch.observe_query("alice", 1.0)
        service = watch.get_service()
        if service is not None:
            assert service.slo.health().get("alice") is None


# ====================================================================== #
# sampler lifecycle
# ====================================================================== #


class TestSamplerLifecycle:
    def test_start_stop_idempotent(self):
        WatchIntervalS.put(0.02)
        WatchEnabled.put(True)
        service = watch.get_service()
        assert watch.WATCH_ON and service.sampler.is_alive()
        first_thread = service.sampler._thread
        service.start()  # idempotent: the live thread is left running
        service.start()
        assert service.sampler._thread is first_thread
        assert _watch_threads().count(ts_mod.Sampler.THREAD_NAME) == 1
        WatchEnabled.put(False)
        assert not watch.WATCH_ON
        _wait_for(
            lambda: not service.sampler.is_alive(), what="sampler exit"
        )
        service.stop()  # second stop is a no-op
        WatchEnabled.put(False)
        assert _watch_threads() == []
        # re-enable restarts cleanly
        WatchEnabled.put(True)
        assert watch.get_service().sampler.is_alive()
        _wait_for(
            lambda: watch.get_service().sampler.ticks > 0, what="a tick"
        )

    def test_sampler_ticks_fill_rings(self):
        MetersEnabled.put(True)
        WatchIntervalS.put(0.02)
        WatchEnabled.put(True)
        emit_metric("engine.dispatch", 1)
        service = watch.get_service()
        _wait_for(lambda: service.sampler.ticks >= 3, what="3 ticks")
        assert service.rings.get("memory.device.resident_bytes") is not None
        assert service.rings.get("compile.total") is not None
        assert service.rings.get("engine.dispatch") is not None, (
            service.rings.names(),
            list(meters.snapshot()["series"]),
            meters.METERS_ON,
        )

    def test_watch_alone_activates_registry_aggregation(self):
        """MODIN_TPU_WATCH=1 without MODIN_TPU_METERS must still fill the
        registry-fed rings and serve a non-empty /metrics — the service
        holds a registry acquire for its lifetime."""
        assert not MetersEnabled.get() and not meters.METERS_ON
        WatchIntervalS.put(0.02)
        WatchPort.put(-1)
        WatchEnabled.put(True)
        assert meters.METERS_ON  # aggregation active, knob still off
        assert not MetersEnabled.get()
        emit_metric("engine.dispatch", 1)
        assert "engine.dispatch" in meters.snapshot()["series"]
        service = watch.get_service()
        _wait_for(
            lambda: service.rings.get("engine.dispatch") is not None,
            what="registry-fed ring",
        )
        WatchEnabled.put(False)
        assert not meters.METERS_ON  # the hold released with the service

    def test_direct_gauges_never_interleave_stale_registry_copies(self):
        """The registry holds memory.*_bytes gauges updated only at spill
        passes; the sampler's live per-tick reads must not interleave
        with those stale copies in the same ring."""
        MetersEnabled.put(True)
        emit_metric("memory.device.resident_bytes", 123456789)  # stale
        store = ts_mod.RingStore()
        sampler = ts_mod.Sampler(store)
        sampler.sample_once(now=1.0)
        sampler.sample_once(now=2.0)
        ring = store.get("memory.device.resident_bytes")
        assert len(ring) == 2  # one LIVE sample per tick, no duplicates
        assert all(v != 123456789 for _t, v in ring.samples())

    def test_rings_stay_bounded_under_long_run(self, monkeypatch):
        """A long synthetic run never grows a ring past its capacity or
        the store past the cardinality cap."""
        monkeypatch.setattr(ts_mod, "RING_SAMPLES", 32)
        MetersEnabled.put(True)
        store = ts_mod.RingStore()
        sampler = ts_mod.Sampler(store)
        for tick in range(500):
            emit_metric("engine.dispatch", 1)
            emit_metric("io.read.bytes", 1024 * (tick + 1))
            sampler.sample_once(now=float(tick))
        assert sampler.ticks == 500
        for name in store.names():
            assert len(store.get(name)) <= 32, name
        # and the whole-store cardinality guard refuses runaway names
        from modin_tpu.config import MetersMaxSeries

        cap = int(MetersMaxSeries.get())
        for i in range(cap + 50):
            store.observe(f"runaway.{i}", "counter", i, 0.0)
        assert len(store) <= cap
        assert store.dropped_series > 0

    def test_stalled_run_never_revives_after_restart(self, monkeypatch):
        """A run whose stop() join timed out (tick stalled past the join
        budget) must exit when it unstalls — never loop alongside the
        restarted run (start() swaps in a fresh stop event; the stalled
        run holds its own, already-set one)."""
        store = ts_mod.RingStore()
        release = threading.Event()
        calls = []

        def stall_once(self, now=None):
            calls.append(threading.current_thread().name)
            release.wait(10.0)

        monkeypatch.setattr(ts_mod.Sampler, "sample_once", stall_once)
        sampler = ts_mod.Sampler(store)
        sampler.start()
        _wait_for(lambda: calls, what="first stalled tick")
        old_thread = sampler._thread
        sampler.stop(timeout=0.05)  # join times out: the tick is stalled
        assert old_thread.is_alive()
        sampler.start()
        assert sampler._thread is not old_thread
        release.set()  # unstall: the superseded run must exit
        _wait_for(
            lambda: not old_thread.is_alive(), what="superseded run exit"
        )
        assert sampler.is_alive()
        sampler.stop()

    def test_crashed_sampler_degrades_to_disabled(
        self, monkeypatch, metric_names
    ):
        """A sampler crash emits watch.sampler.died and flips the service
        off — queries keep running, nothing propagates."""
        WatchIntervalS.put(0.01)

        def boom(self, now=None):
            raise RuntimeError("synthetic sampler crash")

        monkeypatch.setattr(ts_mod.Sampler, "sample_once", boom)
        WatchEnabled.put(True)
        service = watch.get_service()
        _wait_for(lambda: service.sampler.died, what="sampler death")
        _wait_for(lambda: not watch.WATCH_ON, what="degrade to disabled")
        assert "watch.sampler.died" in [
            n.replace("modin_tpu.", "") for n in metric_names
        ]
        assert service.sampler.error is not None
        _wait_for(
            lambda: not service.exporter.is_alive(), what="exporter stop"
        )
        # queries are untouched
        df = pd.DataFrame({"a": np.arange(32.0)})
        assert float(df["a"].sum()) == float(np.arange(32.0).sum())
        # and an explicit off/on cycle recovers once the fault is gone
        monkeypatch.undo()
        WatchEnabled.put(False)
        WatchEnabled.put(True)
        _wait_for(lambda: watch.get_service().sampler.ticks > 0, what="tick")
        assert watch.WATCH_ON and not watch.get_service().sampler.died

    def test_stale_crash_callback_cannot_degrade_restarted_service(self):
        """_on_sampler_died from a thread that is no longer the current
        sampler run (a crash racing stop()/restart) must be a no-op."""
        WatchIntervalS.put(60.0)
        WatchPort.put(-1)
        WatchEnabled.put(True)
        service = watch.get_service()
        assert watch.WATCH_ON
        # this test thread is NOT the sampler thread: the guard must hold
        service._on_sampler_died(RuntimeError("stale crash"))
        assert watch.WATCH_ON
        assert service.sampler.is_alive()


# ====================================================================== #
# ring math
# ====================================================================== #


class TestRings:
    def test_counter_delta_rate_and_reset_clamp(self):
        ring = ts_mod.Ring("c", "counter")
        for t, v in [(0.0, 100.0), (10.0, 150.0), (20.0, 180.0)]:
            ring.append(t, v)
        assert ring.delta(25.0, now=20.0) == pytest.approx(80.0)
        assert ring.rate(25.0, now=20.0) == pytest.approx(4.0)
        # a registry reset mid-window reads as a restart, never negative
        ring.append(30.0, 5.0)
        assert ring.delta(25.0, now=30.0) == pytest.approx(5.0)
        assert ring.rate(40.0, now=30.0) >= 0.0
        # too little data
        empty = ts_mod.Ring("e", "counter")
        assert empty.delta(10.0) is None and empty.rate(10.0) is None

    def test_gauge_window_minmax(self):
        ring = ts_mod.Ring("g", "gauge")
        for t, v in [(0.0, 5.0), (10.0, 50.0), (20.0, 10.0)]:
            ring.append(t, v)
        assert ring.window_minmax(15.0, now=20.0) == (10.0, 50.0)
        assert ring.window_minmax(100.0, now=20.0) == (5.0, 50.0)

    def test_histogram_windowed_quantile(self):
        bounds = (0.01, 0.1, 1.0)
        ring = ts_mod.Ring("h", "histogram")
        ring.append(0.0, (bounds, (0, 0, 0), 0, 0.0))
        ring.append(10.0, (bounds, (10, 10, 10), 10, 0.05))  # 10 fast obs
        ring.append(20.0, (bounds, (10, 10, 20), 20, 5.0))  # 10 slow obs
        recent = ring.quantile(0.99, 15.0, now=20.0)
        assert recent is not None and recent > 0.5  # the slow bucket
        baseline = ring.quantile(0.99, 10.0, now=20.0, end_offset_s=10.0)
        assert baseline is not None and baseline <= 0.01  # the fast bucket
        assert ring.window_count(15.0, now=20.0) == 10

    def test_histogram_single_sample_bills_full_history(self):
        bounds = (1.0,)
        ring = ts_mod.Ring("h", "histogram")
        ring.append(5.0, (bounds, (7,), 9, 9.0))
        delta = ring.hist_delta(0.0, 10.0)
        assert delta is not None
        _bounds, per_bucket, total = delta
        assert total == 9 and per_bucket == [7, 2]  # 2 overflow

    def test_store_excerpt_is_json_safe(self):
        store = ts_mod.RingStore()
        store.observe("c", "counter", 3, 1.0)
        store.observe(
            "h", "histogram", ((1.0,), (2,), 2, 1.5), 1.0
        )
        excerpt = store.excerpt()
        json.dumps(excerpt)  # serializable
        assert excerpt["h"]["samples"][0][1]["count"] == 2


# ====================================================================== #
# SLO burn rates
# ====================================================================== #


class TestSlo:
    def test_parse_slo_spec(self):
        assert slo_mod.parse_slo_ms("250") == {"default": 0.25}
        assert slo_mod.parse_slo_ms("default=100,alice=20") == {
            "default": 0.1,
            "alice": 0.02,
        }
        assert slo_mod.parse_slo_ms("junk,=5,x=,neg=-2,ok=10") == {
            "ok": 0.01
        }
        assert slo_mod.parse_slo_ms("") == {}

    def test_burn_verdicts_and_min_samples_guard(self):
        WatchSloMs.put("default=50,alice=20")
        tracker = slo_mod.SloTracker()
        now = time.monotonic()
        for _ in range(20):
            tracker.observe("alice", 0.5, now=now)  # all over 20ms
            tracker.observe("bob", 0.001, now=now)  # all under 50ms
        tracker.observe("sparse", 9.9, now=now)  # 1 bad obs only
        health = tracker.health(now=now)
        assert health["alice"]["breaching"]
        assert health["alice"]["fast_burn"] > 1.0
        assert not health["bob"]["breaching"]
        # one unlucky query never pages: below MIN_SAMPLES
        assert not health["sparse"]["breaching"]
        assert tracker.breaching(now=now).keys() == {"alice"}

    def test_no_objectives_no_health(self):
        WatchSloMs.put("")
        tracker = slo_mod.SloTracker()
        tracker.observe("alice", 5.0)
        assert tracker.health() == {}
        assert tracker.latency_stats()["alice"]["count"] == 1

    def test_fast_window_recovery_clears_breach(self):
        WatchSloMs.put("default=50")
        tracker = slo_mod.SloTracker()
        now = time.monotonic()
        old = now - slo_mod.FAST_WINDOW_S - 5
        for _ in range(20):
            tracker.observe("t", 1.0, now=old)  # the incident
        for _ in range(20):
            tracker.observe("t", 0.001, now=now)  # recovered traffic
        health = tracker.health(now=now)
        # slow window still burning, fast window clean -> not breaching
        assert health["t"]["slow_burn"] > 1.0
        assert health["t"]["fast_burn"] == 0.0
        assert not health["t"]["breaching"]

    def test_observations_age_pruned_past_slow_window(self):
        """Samples older than SLOW_WINDOW_S are dropped on the write path
        — no verdict reads past it, and health() copies rings under the
        hot-path lock every tick."""
        tracker = slo_mod.SloTracker()
        now = time.monotonic()
        for i in range(10):
            tracker.observe("t", 0.01, now=now - slo_mod.SLOW_WINDOW_S - 60 + i)
        tracker.observe("t", 0.01, now=now)
        assert len(tracker._observations["t"]) == 1  # stale history gone

    def test_tenant_cardinality_lru_evicts_never_ignores(self, monkeypatch):
        """Past the cap, the LEAST-recently-observed tenant is evicted —
        a new tenant is always tracked (permanently ignoring tenants
        created after the cap would blind SLO tracking to churn)."""
        monkeypatch.setattr(slo_mod, "_MAX_TENANTS", 8)
        tracker = slo_mod.SloTracker()
        for i in range(20):
            tracker.observe(f"tenant{i}", 0.01)
        assert len(tracker._observations) <= 8
        assert tracker.evicted_tenants == 12
        assert "tenant19" in tracker._observations  # newest is tracked
        assert "tenant0" not in tracker._observations  # LRU went first
        # re-observing keeps a tenant warm: touch tenant12, add one more
        tracker.observe("tenant12", 0.01)
        tracker.observe("fresh", 0.01)
        assert "tenant12" in tracker._observations
        assert "fresh" in tracker._observations


# ====================================================================== #
# tripwires
# ====================================================================== #


def _enable_watch_quiet(tmp_path):
    """Watch on with a long interval (tests tick the engine manually)."""
    TraceDir.put(str(tmp_path))
    WatchIntervalS.put(60.0)
    WatchPort.put(-1)
    WatchEnabled.put(True)
    service = watch.get_service()
    service.rings.reset()
    service.slo.reset()
    service.tripwires.recent.clear()
    for rule in service.tripwires.rules:
        rule.last_tripped = None
    flight_recorder.reset_for_tests()
    return service


class TestTripwires:
    def test_latency_shift_trips_and_respects_floor(self, tmp_path):
        service = _enable_watch_quiet(tmp_path)
        bounds = (0.01, 0.1, 1.0)
        ring_name = "serving.query_wall_s"
        now = time.monotonic()
        win = tw_mod.WINDOW_S
        service.rings.observe(
            ring_name, "histogram", (bounds, (0, 0, 0), 0, 0.0),
            now - 2 * win,
        )
        service.rings.observe(
            ring_name, "histogram", (bounds, (10, 10, 10), 10, 0.05),
            now - win,
        )
        service.rings.observe(
            ring_name, "histogram", (bounds, (10, 10, 20), 20, 5.0), now
        )
        detail = tw_mod._latency_shift(service, now)
        assert detail is not None and "p99 shifted" in detail
        # floor: the same shape at microsecond scale is not an incident
        service.rings.reset()
        tiny = (1e-6, 1e-5, 1e-4)
        service.rings.observe(
            ring_name, "histogram", (tiny, (0, 0, 0), 0, 0.0), now - 2 * win
        )
        service.rings.observe(
            ring_name, "histogram", (tiny, (10, 10, 10), 10, 0.0), now - win
        )
        service.rings.observe(
            ring_name, "histogram", (tiny, (10, 10, 20), 20, 0.0), now
        )
        assert tw_mod._latency_shift(service, now) is None

    def test_recompile_storm_growth(self, tmp_path):
        service = _enable_watch_quiet(tmp_path)
        now = time.monotonic()
        service.rings.observe(
            "compile.storm_signatures", "gauge", 0, now - 30
        )
        assert tw_mod._recompile_storm(service, now) is None
        service.rings.observe("compile.storm_signatures", "gauge", 2, now)
        detail = tw_mod._recompile_storm(service, now)
        assert detail is not None and "recompile-storm" in detail

    def test_spill_thrash_requires_falling_hits(self, tmp_path):
        service = _enable_watch_quiet(tmp_path)
        now = time.monotonic()
        win = tw_mod.WINDOW_S
        for t, v in [(now - win, 0), (now, 8)]:
            service.rings.observe("memory.device.spill", "counter", v, t)
        # hits rising: no thrash
        for t, v in [
            (now - 2 * win, 0),
            (now - win - 1, 2),
            (now - win + 1, 2),
            (now, 50),
        ]:
            service.rings.observe("sortcache.hit", "counter", v, t)
        assert tw_mod._spill_thrash(service, now) is None
        # hits falling: thrash
        service.rings.reset()
        for t, v in [(now - win, 0), (now, 8)]:
            service.rings.observe("memory.device.spill", "counter", v, t)
        for t, v in [
            (now - 2 * win, 0),
            (now - win - 1, 40),
            (now - win + 1, 40),
            (now, 41),
        ]:
            service.rings.observe("sortcache.hit", "counter", v, t)
        detail = tw_mod._spill_thrash(service, now)
        assert detail is not None and "spill" in detail

    def test_shed_spike_and_engine_emits_metric(
        self, tmp_path, metric_names, monkeypatch
    ):
        service = _enable_watch_quiet(tmp_path)
        monkeypatch.setattr(flight_recorder, "MIN_DUMP_INTERVAL_S", 0.0)
        now = time.monotonic()
        for t, v in [(now - 30, 0), (now, 10)]:
            service.rings.observe("serving.shed", "counter", v, t)
        service.tripwires.on_tick(now)
        trips = [t["rule"] for t in service.tripwires.snapshot()]
        assert "shed_spike" in trips
        assert "modin_tpu.watch.trip.shed_spike" in metric_names
        assert "modin_tpu.watch.evidence" in metric_names

    def test_evidence_bundle_shape_and_rate_limit(
        self, tmp_path, monkeypatch
    ):
        """One incident -> one bundle; the bundle carries all four legs
        (trace segment, meter snapshot, ring excerpt, slo health)."""
        service = _enable_watch_quiet(tmp_path)
        WatchSloMs.put("default=10")
        now = time.monotonic()
        for _ in range(10):
            service.slo.observe("alice", 5.0, now=now)
        service.tripwires.on_tick(now)
        bundles = glob.glob(str(tmp_path / "watchtrip_*.json"))
        assert len(bundles) == 1
        bundle = json.loads(open(bundles[0]).read())
        assert bundle["rule"] == "slo_burn"
        assert set(bundle) >= {"trace", "metrics", "rings", "slo", "detail"}
        assert bundle["slo"]["alice"]["breaching"]
        # a second tick inside the claim window writes nothing new, even
        # with the rule cooldown gone
        monkeypatch.setattr(tw_mod, "RULE_COOLDOWN_S", 0.0)
        service.tripwires.on_tick(now + 1)
        assert len(glob.glob(str(tmp_path / "watchtrip_*.json"))) == 1

    def test_failed_evidence_write_releases_claim(
        self, tmp_path, monkeypatch
    ):
        service = _enable_watch_quiet(tmp_path)
        blocker = tmp_path / "blocked"
        blocker.write_text("a file where the trace dir should be")
        TraceDir.put(str(blocker))  # mkdir will fail
        assert (
            tw_mod.capture_evidence("unit", "detail", service) is None
        )
        TraceDir.put(str(tmp_path))
        # the failed write released its claim: the next capture succeeds
        path = tw_mod.capture_evidence("unit", "detail", service)
        assert path is not None and os.path.exists(path)

    def test_broken_rule_is_isolated(self, tmp_path):
        service = _enable_watch_quiet(tmp_path)

        def explode(_service, _now):
            raise RuntimeError("broken rule")

        service.tripwires.rules.append(
            tw_mod.Tripwire("broken", "unit", explode)
        )
        service.tripwires.on_tick(time.monotonic())  # must not raise
        assert all(
            t["rule"] != "broken" for t in service.tripwires.snapshot()
        )

    def test_rule_cooldown_spaces_retrips(self, tmp_path, monkeypatch):
        service = _enable_watch_quiet(tmp_path)
        monkeypatch.setattr(flight_recorder, "MIN_DUMP_INTERVAL_S", 0.0)
        now = time.monotonic()
        for t, v in [(now - 30, 0), (now, 10)]:
            service.rings.observe("serving.shed", "counter", v, t)
        service.tripwires.on_tick(now)
        service.tripwires.on_tick(now + 1)  # inside RULE_COOLDOWN_S
        trips = [t["rule"] for t in service.tripwires.snapshot()]
        assert trips.count("shed_spike") == 1


# ====================================================================== #
# the live exporter
# ====================================================================== #


class TestHttpd:
    def test_endpoints_serve_and_parse(self, tmp_path, metric_names):
        MetersEnabled.put(True)
        TraceDir.put(str(tmp_path))
        WatchIntervalS.put(0.05)
        WatchPort.put(0)  # ephemeral
        WatchEnabled.put(True)
        emit_metric("engine.dispatch", 1)
        emit_metric("io.read.bytes", 4096)
        port = watch.httpd_port()
        assert port is not None and port > 0
        # /metrics: Prometheus text the validating parser accepts
        from modin_tpu.observability.exposition import parse_prometheus

        parsed = parse_prometheus(_get(port, "/metrics"))
        assert "modin_tpu_engine_dispatch" in parsed
        # /statusz: the one-pager with every section header
        statusz = _get(port, "/statusz")
        for header in (
            "service", "substrate", "windowed rates", "admission gate",
            "tenants", "recent tripwires",
        ):
            assert f"== {header} ==" in statusz
        # /debug/queries: live scopes
        with meters.query_stats("live-probe"):
            dbg = json.loads(_get(port, "/debug/queries"))
        assert dbg["open_scopes"] == 1
        assert dbg["queries"][0]["label"] == "live-probe"
        assert dbg["queries"][0]["open"] is True
        # index + 404 + scrape accounting
        assert "/metrics" in _get(port, "/")
        with pytest.raises(urllib.error.HTTPError):
            _get(port, "/nope")
        assert "modin_tpu.watch.scrape" in metric_names

    def test_port_minus_one_disables_exporter(self):
        WatchPort.put(-1)
        WatchIntervalS.put(1.0)
        WatchEnabled.put(True)
        assert watch.WATCH_ON
        assert watch.httpd_port() is None
        assert not any("httpd" in n for n in _watch_threads())

    def test_out_of_range_port_degrades_exporter_less(self):
        """An env-sourced port bypasses WatchPort.put validation and
        reaches bind() raising OverflowError (not OSError): start must
        return False, never raise into the service start."""
        from modin_tpu.observability.watch.httpd import Exporter

        exporter = Exporter(object())
        assert exporter.start(70000) is False
        assert exporter.port is None

    def test_port_validation(self):
        with pytest.raises(ValueError):
            WatchPort.put(-2)
        with pytest.raises(ValueError):
            WatchPort.put(70000)
        with pytest.raises(ValueError):
            WatchIntervalS.put(0)


# ====================================================================== #
# serving integration
# ====================================================================== #


class TestServingIntegration:
    def test_submit_feeds_slo_and_snapshot_surfaces_it(self, tmp_path):
        TraceDir.put(str(tmp_path))
        WatchSloMs.put("default=100000")  # everything healthy
        WatchIntervalS.put(60.0)
        WatchPort.put(-1)
        WatchEnabled.put(True)
        ServingEnabled.put(True)
        ResilienceBackoffS.put(0.0)
        df = pd.DataFrame({"a": np.arange(64.0)})
        for _ in range(3):
            serving.submit(
                lambda: float(df["a"].sum()), tenant="alice"
            )
        service = watch.get_service()
        health = service.slo.health()
        assert health["alice"]["fast_samples"] >= 3
        assert not health["alice"]["breaching"]
        snap = serving.serving_snapshot()
        assert "slo" in snap and "alice" in snap["slo"]
        # advisory only: nothing was shed because of it
        assert snap["shed"] == 0

    def test_snapshot_has_no_slo_key_when_watch_off(self):
        ServingEnabled.put(True)
        assert "slo" not in serving.serving_snapshot()

    def test_gate_counter_sample_reaches_span_samples(self):
        from modin_tpu.observability import spans as spans_mod

        queued, running = spans_mod._gate_samples()
        assert queued == 0 and running == 0  # idle gate, serving imported
