"""Fault-tolerant device execution: classification, retry, breaker, fault harness.

Acceptance bar (ISSUE 1): with injected DeviceOOM / DeviceLost / slow-kernel
faults at the JaxWrapper seam, representative queries across >= 5 ``_try_*``
families return pandas-identical results (no crash, no hang); breakers trip
open after the configured threshold, route to the fallback, and recover via
half-open probe — all transitions visible through emit_metric counters.
"""

import time

import numpy as np
import pandas
import pytest

import modin_tpu.pandas as pd
from modin_tpu.config import (
    RangePartitioning,
    RecoveryMode,
    ResilienceBackoffS,
    ResilienceBreakerCooldownS,
    ResilienceBreakerThreshold,
    ResilienceLatencyBudgetS,
    ResilienceMode,
    ResilienceRetries,
    ResilienceWatchdogS,
)
from modin_tpu.core.execution import resilience
from modin_tpu.core.execution.resilience import (
    CircuitBreaker,
    DeviceFailure,
    DeviceLost,
    DeviceOOM,
    TransientDeviceError,
    WatchdogTimeout,
    classify_device_error,
    engine_call,
    get_breaker,
    reset_breakers,
)
from modin_tpu.logging import add_metric_handler, clear_metric_handler
from modin_tpu.testing import inject_faults, make_device_error

from tests.utils import df_equals

_RESILIENCE_PARAMS = (
    ResilienceMode,
    ResilienceRetries,
    ResilienceBackoffS,
    ResilienceWatchdogS,
    ResilienceBreakerThreshold,
    ResilienceBreakerCooldownS,
    ResilienceLatencyBudgetS,
    RecoveryMode,
)


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Fresh breakers, zero backoff sleeps, restored knobs around each test.

    RecoveryMode is pinned Disable: this suite asserts the PR-1 retry /
    breaker / fallback semantics in isolation — lineage re-seat and
    evict-then-retry would otherwise absorb the injected faults
    nondeterministically (whatever columns older tests left alive would be
    re-seated first).  The recovery legs are covered by tests/test_recovery.py.
    """
    saved = [(p, p.get()) for p in _RESILIENCE_PARAMS]
    reset_breakers()
    ResilienceBackoffS.put(0.0)
    RecoveryMode.put("Disable")
    yield
    for p, v in saved:
        p.put(v)
    reset_breakers()


@pytest.fixture
def metrics():
    """Collect emitted metric names (values are all counters of 1 here)."""
    seen = []

    def handler(name, value):
        seen.append((name, value))

    add_metric_handler(handler)
    yield seen
    clear_metric_handler(handler)


def _names(metrics):
    return [n for n, _ in metrics]


# ====================================================================== #
# classification
# ====================================================================== #


class TestClassification:
    def test_oom(self):
        err = make_device_error("oom")
        assert isinstance(classify_device_error(err), DeviceOOM)

    def test_device_lost(self):
        err = make_device_error("device_lost")
        assert isinstance(classify_device_error(err), DeviceLost)

    def test_transient(self):
        err = make_device_error("transient")
        assert isinstance(classify_device_error(err), TransientDeviceError)

    def test_unknown_runtime_error_is_transient(self):
        from modin_tpu.testing.faults import _runtime_error_type

        err = _runtime_error_type()("INTERNAL: something novel")
        assert isinstance(classify_device_error(err), TransientDeviceError)

    def test_semantic_signals_are_not_device_failures(self):
        from modin_tpu.parallel.shuffle import ShuffleSkewError
        from modin_tpu.utils import ModinAssumptionError

        for exc in (
            ShuffleSkewError("skew"),
            ModinAssumptionError("nope"),
            ValueError("RESOURCE_EXHAUSTED"),  # message alone is not enough
            TypeError("x"),
        ):
            assert classify_device_error(exc) is None

    def test_device_failure_passthrough(self):
        oom = DeviceOOM("already classified")
        assert classify_device_error(oom) is oom

    def test_watchdog_is_device_lost(self):
        assert issubclass(WatchdogTimeout, DeviceLost)
        assert issubclass(DeviceOOM, DeviceFailure)


# ====================================================================== #
# engine_call: retry / backoff / watchdog
# ====================================================================== #


class TestEngineCall:
    def test_transient_retried_to_success(self):
        ResilienceRetries.put(2)
        attempts = []

        def flaky():
            attempts.append(1)
            if len(attempts) < 3:
                raise make_device_error("transient")
            return "ok"

        assert engine_call("deploy", flaky) == "ok"
        assert len(attempts) == 3

    def test_transient_exhausted_raises_classified(self):
        ResilienceRetries.put(1)
        attempts = []

        def always_flaky():
            attempts.append(1)
            raise make_device_error("transient")

        with pytest.raises(TransientDeviceError):
            engine_call("deploy", always_flaky)
        assert len(attempts) == 2  # 1 try + 1 retry

    def test_oom_not_retried(self):
        ResilienceRetries.put(5)
        attempts = []

        def oom():
            attempts.append(1)
            raise make_device_error("oom")

        with pytest.raises(DeviceOOM):
            engine_call("deploy", oom)
        assert len(attempts) == 1

    def test_device_lost_not_retried(self):
        attempts = []

        def lost():
            attempts.append(1)
            raise make_device_error("device_lost")

        with pytest.raises(DeviceLost):
            engine_call("materialize", lost)
        assert len(attempts) == 1

    def test_non_device_error_propagates_unchanged(self):
        def bug():
            raise KeyError("not a device problem")

        with pytest.raises(KeyError):
            engine_call("deploy", bug)

    def test_disable_mode_propagates_raw(self):
        ResilienceMode.put("Disable")

        def oom():
            raise make_device_error("oom")

        with pytest.raises(Exception) as info:
            engine_call("deploy", oom)
        assert not isinstance(info.value, DeviceFailure)
        assert "RESOURCE_EXHAUSTED" in str(info.value)

    def test_watchdog_times_out_blocking_fetch(self):
        ResilienceWatchdogS.put(0.1)

        def wedged():
            time.sleep(5.0)
            return "never"

        t0 = time.monotonic()
        with pytest.raises(WatchdogTimeout):
            engine_call("materialize", wedged, watchdog=True)
        assert time.monotonic() - t0 < 2.0  # did not wait the full 5s

    def test_watchdog_off_by_default(self):
        assert engine_call("wait", lambda: "done", watchdog=True) == "done"

    def test_retry_metrics(self, metrics):
        ResilienceRetries.put(1)
        state = []

        def flaky_once():
            state.append(1)
            if len(state) == 1:
                raise make_device_error("transient")
            return "ok"

        engine_call("put", flaky_once)
        names = _names(metrics)
        assert "modin_tpu.resilience.engine.put.transient" in names
        assert "modin_tpu.resilience.engine.put.retry" in names


# ====================================================================== #
# circuit breaker state machine
# ====================================================================== #


class TestCircuitBreaker:
    def test_trips_after_threshold(self, metrics):
        ResilienceBreakerThreshold.put(3)
        b = CircuitBreaker("unit")
        for _ in range(2):
            b.record_failure()
            assert b.allow()
        b.record_failure()
        assert b.state == "open"
        assert not b.allow()
        assert "modin_tpu.resilience.breaker.unit.open" in _names(metrics)

    def test_success_resets_strikes(self):
        ResilienceBreakerThreshold.put(2)
        b = CircuitBreaker("unit")
        b.record_failure()
        b.record_success(0.0)
        b.record_failure()
        assert b.state == "closed"  # never two consecutive

    def test_half_open_probe_closes_on_success(self, metrics, monkeypatch):
        ResilienceBreakerThreshold.put(1)
        ResilienceBreakerCooldownS.put(10.0)
        clock = [100.0]
        monkeypatch.setattr(resilience, "_now", lambda: clock[0])
        b = CircuitBreaker("unit")
        b.record_failure()
        assert b.state == "open" and not b.allow()
        clock[0] += 11.0  # cooldown elapses
        assert b.allow()  # the half-open probe
        assert b.state == "half_open"
        assert not b.allow()  # only one probe at a time
        b.record_success(0.0)
        assert b.state == "closed" and b.allow()
        names = _names(metrics)
        assert "modin_tpu.resilience.breaker.unit.half_open" in names
        assert "modin_tpu.resilience.breaker.unit.closed" in names

    def test_half_open_probe_reopens_on_failure(self, monkeypatch):
        ResilienceBreakerThreshold.put(1)
        ResilienceBreakerCooldownS.put(10.0)
        clock = [50.0]
        monkeypatch.setattr(resilience, "_now", lambda: clock[0])
        b = CircuitBreaker("unit")
        b.record_failure()
        clock[0] += 11.0
        assert b.allow()
        b.record_failure()  # the probe failed
        assert b.state == "open"
        assert not b.allow()  # fresh cooldown
        clock[0] += 11.0
        assert b.allow()  # next probe window

    def test_aborted_probe_reopens_instead_of_sticking(self, monkeypatch):
        """An unclassified exception during the HALF_OPEN probe must return
        the breaker to OPEN (fresh cooldown), not leave it stuck HALF_OPEN
        short-circuiting the family forever."""
        from modin_tpu.core.execution.resilience import device_path

        ResilienceBreakerThreshold.put(1)
        ResilienceBreakerCooldownS.put(10.0)
        clock = [0.0]
        monkeypatch.setattr(resilience, "_now", lambda: clock[0])

        class Probe:
            mode = "fail_device"

            @device_path("probe_unit")
            def _try_thing(self):
                if self.mode == "fail_device":
                    raise make_device_error("oom")
                raise TypeError("a bug, not the device")

        p = Probe()
        assert p._try_thing() is None  # device failure -> trip open
        b = get_breaker("probe_unit")
        assert b.state == "open"
        clock[0] += 11.0
        p.mode = "bug"
        with pytest.raises(TypeError):
            p._try_thing()  # the half-open probe dies of a non-device bug
        assert b.state == "open"  # re-opened, not stuck half_open
        clock[0] += 11.0
        assert b.allow()  # a later probe window still comes

    def test_latency_budget_violation_strikes(self, metrics):
        ResilienceBreakerThreshold.put(2)
        ResilienceLatencyBudgetS.put(0.5)
        b = CircuitBreaker("unit")
        b.record_success(1.0)  # completed, but over budget
        b.record_success(2.0)
        assert b.state == "open"
        assert "modin_tpu.resilience.breaker.unit.slow" in _names(metrics)

    def test_registry(self):
        assert get_breaker("a") is get_breaker("a")
        assert get_breaker("a") is not get_breaker("b")


# ====================================================================== #
# fault injection end-to-end: >= 5 _try_* families, pandas-identical
# ====================================================================== #

_N = 512


def _frames(seed=0, datetime_index=False):
    rng = np.random.default_rng(seed)
    data = {
        "a": rng.normal(size=_N),
        "b": rng.integers(0, 1000, _N).astype(np.int64),
        "key": rng.integers(0, 7, _N).astype(np.int64),
    }
    kwargs = {}
    if datetime_index:
        kwargs["index"] = pandas.date_range("2024-01-01", periods=_N, freq="h")
    pdf = pandas.DataFrame(data, **kwargs)
    mdf = pd.DataFrame(data, **kwargs)
    mdf._query_compiler.execute()  # ingest outside any fault window
    return mdf, pdf


# (family breaker name, needs datetime index, query)
FAMILY_QUERIES = [
    ("top_k", False, lambda df: df.nlargest(5, "a")),
    ("reduce", False, lambda df: df.median(numeric_only=True)),
    ("groupby", False, lambda df: df.groupby("key").sum()),
    ("merge", False, lambda df: df.merge(df, on="key", suffixes=("_l", "_r"))),
    ("resample", True, lambda df: df.resample("D").sum()),
]


class TestFaultInjectionEndToEnd:
    @pytest.mark.parametrize("kind", ["oom", "device_lost"])
    @pytest.mark.parametrize(
        "family,dt_index,query",
        FAMILY_QUERIES,
        ids=[f[0] for f in FAMILY_QUERIES],
    )
    def test_family_fallback_is_pandas_identical(
        self, family, dt_index, query, kind, metrics
    ):
        ResilienceBreakerThreshold.put(50)  # stay closed: test the fallback leg
        mdf, pdf = _frames(seed=hash((family, kind)) % 2**32, datetime_index=dt_index)
        with inject_faults(kind, times=4) as inj:
            result = query(mdf)
            df_equals(result, query(pdf))
        assert inj.injected >= 1, "fault never reached the engine seam"
        fallback_names = [
            n for n in _names(metrics)
            if n.startswith(f"modin_tpu.resilience.fallback.{family}.")
        ]
        assert fallback_names, (
            f"no fallback recorded for family {family}: "
            f"{sorted(set(_names(metrics)))}"
        )

    def test_sort_shuffle_family_fallback(self, metrics):
        ResilienceBreakerThreshold.put(50)
        RangePartitioning.put(True)
        try:
            mdf, pdf = _frames(seed=99)
            # times=1: the fault lands on the shuffle's pivot fetch inside
            # the family; the non-shuffle fallback it degrades to is itself
            # a DEVICE path (global argsort), which must then run clean
            with inject_faults("oom", times=1) as inj:
                df_equals(
                    mdf.sort_values("a", ignore_index=True),
                    pdf.sort_values("a", ignore_index=True),
                )
            assert inj.injected >= 1
            assert any(
                n.startswith("modin_tpu.resilience.fallback.sort_shuffle.")
                for n in _names(metrics)
            )
        finally:
            RangePartitioning.put(False)

    def test_transient_fault_retries_without_fallback(self, metrics):
        """One transient hiccup: the retry absorbs it, the device answers."""
        ResilienceRetries.put(2)
        mdf, pdf = _frames(seed=7)
        with inject_faults("transient", ops=("materialize",), times=1) as inj:
            df_equals(mdf.nlargest(5, "a"), pdf.nlargest(5, "a"))
        assert inj.injected == 1
        names = _names(metrics)
        assert "modin_tpu.resilience.engine.materialize.retry" in names
        assert not any(".fallback." in n for n in names)

    def test_slow_kernel_trips_watchdog_then_falls_back(self, metrics):
        ResilienceWatchdogS.put(0.1)
        ResilienceBreakerThreshold.put(50)
        mdf, pdf = _frames(seed=13)
        with inject_faults(
            "slow_kernel", ops=("materialize",), times=2, slow_s=1.0
        ) as inj:
            df_equals(mdf.nlargest(5, "a"), pdf.nlargest(5, "a"))
        assert inj.injected >= 1
        names = _names(metrics)
        assert "modin_tpu.resilience.watchdog.materialize.timeout" in names
        assert any(
            n.startswith("modin_tpu.resilience.fallback.")
            and n.endswith(".watchdog_timeout")
            for n in names
        )

    def test_breaker_trips_short_circuits_and_recovers(self, metrics, monkeypatch):
        """The acceptance scenario: strike to open, fallback while open,
        half-open probe on cooldown, clean probe closes."""
        ResilienceBreakerThreshold.put(2)
        ResilienceBreakerCooldownS.put(30.0)
        mdf, pdf = _frames(seed=21)
        expected = pdf.nlargest(5, "a")

        # 2 failing calls trip the breaker
        with inject_faults("oom", ops=("materialize",), times=None) as inj:
            df_equals(mdf.nlargest(5, "a"), expected)
            df_equals(mdf.nlargest(5, "a"), expected)
            assert get_breaker("top_k").state == "open"
            faults_used = inj.injected

            # open: short-circuits to pandas without touching the device
            df_equals(mdf.nlargest(5, "a"), expected)
            assert inj.injected == faults_used  # no new engine-seam attempts
        names = _names(metrics)
        assert "modin_tpu.resilience.breaker.top_k.open" in names
        assert "modin_tpu.resilience.breaker.top_k.short_circuit" in names

        # cooldown elapses (simulated clock) -> half-open probe, device is
        # healthy again -> closed
        real_now = resilience._now
        monkeypatch.setattr(resilience, "_now", lambda: real_now() + 31.0)
        df_equals(mdf.nlargest(5, "a"), expected)
        assert get_breaker("top_k").state == "closed"
        names = _names(metrics)
        assert "modin_tpu.resilience.breaker.top_k.half_open" in names
        assert "modin_tpu.resilience.breaker.top_k.closed" in names

    def test_latency_budget_degrades_slow_path(self, metrics):
        """A slow (but succeeding) kernel exhausts its budget strikes and the
        family degrades to pandas — the VERDICT r5 sort-regression scenario."""
        ResilienceBreakerThreshold.put(2)
        ResilienceLatencyBudgetS.put(1e-9)  # everything is over budget
        mdf, pdf = _frames(seed=34)
        expected = pdf.nlargest(5, "a")
        df_equals(mdf.nlargest(5, "a"), expected)  # strike 1 (slow success)
        df_equals(mdf.nlargest(5, "a"), expected)  # strike 2 -> open
        assert get_breaker("top_k").state == "open"
        df_equals(mdf.nlargest(5, "a"), expected)  # short-circuit, same answer
        names = _names(metrics)
        assert "modin_tpu.resilience.breaker.top_k.slow" in names
        assert "modin_tpu.resilience.breaker.top_k.short_circuit" in names

    def test_disable_mode_bypasses_breakers(self):
        ResilienceMode.put("Disable")
        mdf, pdf = _frames(seed=55)
        # an open breaker is ignored when the layer is off
        get_breaker("top_k").record_failure()
        df_equals(mdf.nlargest(5, "a"), pdf.nlargest(5, "a"))

    def test_injector_is_exclusive(self):
        with inject_faults("oom"):
            with pytest.raises(RuntimeError):
                with inject_faults("transient"):
                    pass

    def test_injector_restores_hook(self):
        with inject_faults("oom", times=0):
            assert resilience._fault_hook is not None
        assert resilience._fault_hook is None
