"""Environment-variable backed configuration parameters.

TPU-native analogue of /root/reference/modin/config/envvars.py:38-1475.  All
variables use the ``MODIN_TPU_*`` prefix.  The execution-selection trio
(``Engine``/``StorageFormat``/``Backend``) mirrors the reference's bimap design
(envvars.py:401-473) with TPU-first defaults: the default execution is the
sharded-jax.Array storage format on the JAX engine.
"""

from __future__ import annotations

import os
import warnings
from textwrap import dedent
from typing import Any, Optional

from modin_tpu.config.pubsub import (
    DeprecationDescriptor,
    ExactStr,
    Parameter,
    ValueSource,
    _TYPE_PARAMS,
)


class EnvironmentVariable(Parameter, type=str, abstract=True):
    """A parameter sourced from an environment variable."""

    varname: Optional[str] = None

    @classmethod
    def _get_raw_from_config(cls) -> str:
        if cls.varname is None:
            raise TypeError(f"{cls.__name__} does not have a varname")
        return os.environ[cls.varname]

    @classmethod
    def get_help(cls) -> str:
        help = f"{cls.varname}: {dedent(cls.__doc__ or 'Unknown').strip()}\n"
        help += f"\tProvide {_TYPE_PARAMS[cls.type].help}"
        if cls.choices:
            help += f" (valid examples are: {', '.join(str(c) for c in cls.choices)})"
        return help


class Engine(EnvironmentVariable, type=str):
    """Task-execution engine: Jax (device), Python (serial, testing), Native (no-op)."""

    varname = "MODIN_TPU_ENGINE"
    choices = ("Jax", "Python", "Native")
    NOINIT_ENGINES = {"Python", "Native"}
    has_custom_engine = False

    @classmethod
    def _get_default(cls) -> str:
        try:
            import jax  # noqa: F401

            return "Jax"
        except ImportError:  # pragma: no cover - jax is a hard dep in practice
            return "Python"

    @classmethod
    def add_option(cls, choice: Any) -> Any:
        choice = super().add_option(choice)
        cls.NOINIT_ENGINES.add(choice)
        cls.has_custom_engine = True
        return choice


class StorageFormat(EnvironmentVariable, type=str):
    """Storage format: Tpu (sharded jax.Array columns), Pandas (block pandas), Native."""

    varname = "MODIN_TPU_STORAGE_FORMAT"
    choices = ("Tpu", "Pandas", "Native")

    @classmethod
    def _get_default(cls) -> str:
        return "Pandas" if Engine.get() in ("Python",) else "Tpu"


class Backend(EnvironmentVariable, type=str):
    """Shorthand for an (Engine, StorageFormat) pair, kept in sync both ways.

    Reference design: envvars.py:401-473 Backend<->Execution bimap.
    """

    varname = "MODIN_TPU_BACKEND"
    choices = ("Tpu", "Pandas", "Python_Test")
    _BACKEND_TO_EXECUTION: dict = {}
    _EXECUTION_TO_BACKEND: dict = {}

    @classmethod
    def register_backend(cls, name: str, execution) -> None:
        name = cls.add_option(name)
        if name in cls._BACKEND_TO_EXECUTION:
            raise ValueError(f"Backend '{name}' is already registered")
        cls._BACKEND_TO_EXECUTION[name] = execution
        cls._EXECUTION_TO_BACKEND[execution] = name

    @classmethod
    def get_backend_for_execution(cls, execution):
        return cls._EXECUTION_TO_BACKEND[execution]

    @classmethod
    def get_execution_for_backend(cls, backend: Optional[str] = None):
        if backend is None:
            backend = cls.get()
        backend = _TYPE_PARAMS[cls.type].normalize(backend)
        if backend not in cls._BACKEND_TO_EXECUTION:
            raise ValueError(f"Unknown backend '{backend}'")
        return cls._BACKEND_TO_EXECUTION[backend]

    @classmethod
    def _get_default(cls) -> str:
        from modin_tpu.core.execution.utils import Execution

        try:
            return cls._EXECUTION_TO_BACKEND[
                Execution(StorageFormat.get(), Engine.get())
            ]
        except KeyError:
            return "Tpu"


class CpuCount(EnvironmentVariable, type=int):
    """How many CPU cores to use for host-side (pandas-fallback) work."""

    varname = "MODIN_TPU_CPUS"

    @classmethod
    def _get_default(cls) -> int:
        import multiprocessing

        return multiprocessing.cpu_count()


class DeviceCount(EnvironmentVariable, type=int):
    """How many accelerator devices the mesh spans (defaults to all visible)."""

    varname = "MODIN_TPU_DEVICES"

    @classmethod
    def _get_default(cls) -> int:
        try:
            import jax

            return jax.device_count()
        except Exception:
            return 1


class MeshShape(EnvironmentVariable, type=tuple):
    """Logical device mesh shape as (rows, cols) shards, e.g. '8,1'.

    The TPU-native analogue of the reference's 2-D partition grid
    (NPartitions x column splits): the row axis shards dataframe rows over
    ICI neighbors; the col axis (usually 1) shards very wide frames.
    """

    varname = "MODIN_TPU_MESH_SHAPE"

    @classmethod
    def _get_default(cls) -> tuple:
        return (DeviceCount.get(), 1)


class NPartitions(EnvironmentVariable, type=int):
    """Number of row shards for the partitioned (non-device) storage formats."""

    varname = "MODIN_TPU_NPARTITIONS"

    @classmethod
    def _get_default(cls) -> int:
        return max(CpuCount.get(), DeviceCount.get())


class Memory(EnvironmentVariable, type=int):
    """How much host memory (bytes) the runtime may use for spill buffers."""

    varname = "MODIN_TPU_MEMORY"
    default = None

    @classmethod
    def get(cls):  # Memory may legitimately be unset
        try:
            return super().get()
        except TypeError:
            return None


class BenchmarkMode(EnvironmentVariable, type=bool):
    """Force synchronous execution (block_until_ready) after every operator."""

    varname = "MODIN_TPU_BENCHMARK_MODE"
    default = False


class LogMode(EnvironmentVariable, type=str):
    """Tracing mode: disable, enable (api only), enable_api_only."""

    varname = "MODIN_TPU_LOG_MODE"
    choices = ("Enable", "Disable", "Enable_Api_Only")
    default = "Disable"

    @classmethod
    def enable(cls):
        cls.put("Enable")

    @classmethod
    def disable(cls):
        cls.put("Disable")

    @classmethod
    def enable_api_only(cls):
        cls.put("Enable_Api_Only")


class LogMemoryInterval(EnvironmentVariable, type=int):
    """Seconds between memory-profile samples when logging is enabled."""

    varname = "MODIN_TPU_LOG_MEMORY_INTERVAL"
    default = 5

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(f"Log memory interval should be > 0, passed value {value}")
        super().put(value)


class LogFileSize(EnvironmentVariable, type=int):
    """Max size (MB) of one log file before rotation."""

    varname = "MODIN_TPU_LOG_FILE_SIZE"
    default = 10

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(f"Log file size should be > 0 MB, passed value {value}")
        super().put(value)


class MetricsMode(EnvironmentVariable, type=str):
    """Emit API timing metrics to registered handlers (enable/disable)."""

    varname = "MODIN_TPU_METRICS_MODE"
    choices = ("Enable", "Disable")
    default = "Enable"

    @classmethod
    def enable(cls):
        cls.put("Enable")

    @classmethod
    def disable(cls):
        cls.put("Disable")


class ProgressBar(EnvironmentVariable, type=bool):
    """Show a tqdm progress bar over outstanding device computations."""

    varname = "MODIN_TPU_PROGRESS_BAR"
    default = False

    @classmethod
    def enable(cls):
        cls.put(True)

    @classmethod
    def disable(cls):
        cls.put(False)

    @classmethod
    def _check_new_value_ok(cls, value) -> None:
        if value and BenchmarkMode.get():
            raise ValueError("ProgressBar isn't compatible with BenchmarkMode")


class RangePartitioning(EnvironmentVariable, type=bool):
    """Use range-partitioning (sample->pivots->all-to-all) impls for groupby/sort/merge."""

    varname = "MODIN_TPU_RANGE_PARTITIONING"
    default = False


class TestDatasetSize(EnvironmentVariable, type=str):
    """Dataset size profile for the benchmark suite."""

    varname = "MODIN_TPU_TEST_DATASET_SIZE"
    choices = ("Small", "Normal", "Big")
    default = None


class AsvImplementation(EnvironmentVariable, type=ExactStr):
    """Which implementation the asv-style benchmarks should exercise."""

    varname = "MODIN_TPU_ASV_USE_IMPL"
    choices = ("modin_tpu", "pandas")
    default = "modin_tpu"


class TrackFileLeaks(EnvironmentVariable, type=bool):
    """Audit IO reads for leaked file descriptors (ResourceWarning on leak).

    Off by default: the /proc/self/fd scan costs on every read, and some
    formats legitimately retain descriptors (mmap).  The test suite turns it
    on globally (tests/conftest.py), mirroring the reference's test-conftest
    use of its flag (reference: envvars.py:893)."""

    varname = "MODIN_TPU_TEST_TRACK_FILE_LEAKS"
    default = False


class PersistentPickle(EnvironmentVariable, type=bool):
    """Pickle dataframes by value (portable) rather than by device reference."""

    varname = "MODIN_TPU_PERSISTENT_PICKLE"
    default = False


class TpuNumpy(EnvironmentVariable, type=bool):
    """Use the modin_tpu.numpy array type for numpy-returning APIs."""

    varname = "MODIN_TPU_NUMPY"
    default = False


class AutoSwitchBackend(EnvironmentVariable, type=bool):
    """Let the cost calculator auto-move frames between device and host backends.

    Off by default (matching the reference's MODIN_AUTO_SWITCH_BACKENDS):
    implicit relocation changes result backend types across the API, so the
    user opts in.
    """

    varname = "MODIN_TPU_AUTO_SWITCH_BACKENDS"
    default = False

    @classmethod
    def enable(cls):
        cls.put(True)

    @classmethod
    def disable(cls):
        cls.put(False)


class NativePandasMaxRows(EnvironmentVariable, type=int):
    """Frames at or below this many rows prefer the in-process pandas backend."""

    varname = "MODIN_TPU_NATIVE_PANDAS_MAX_ROWS"
    default = 10_000_000


class NativePandasTransferThreshold(EnvironmentVariable, type=int):
    """Max rows the cost model will transfer host->device without complaint."""

    varname = "MODIN_TPU_NATIVE_PANDAS_TRANSFER_THRESHOLD"
    default = 10_000_000


class Float64Policy(EnvironmentVariable, type=str):
    """float64 handling on device: Native (x64), Downcast (f32 compute)."""

    varname = "MODIN_TPU_FLOAT64_POLICY"
    choices = ("Native", "Downcast")
    default = "Native"


class CacheDir(EnvironmentVariable, type=ExactStr):
    """Directory for host-side build artifacts (the native CSV chunker's
    compiled .so cache).  XLA executables persist elsewhere: see
    ``parallel.engine._place_compilation_cache``."""

    varname = "MODIN_TPU_CACHE_DIR"

    @classmethod
    def _get_default(cls) -> str:
        import pathlib

        return str(pathlib.Path.home() / ".cache" / "modin_tpu")


class ResilienceMode(EnvironmentVariable, type=str):
    """Fault-tolerant device execution (retry/backoff, per-path breakers).

    Enable (default): device failures at the engine seam are classified
    (DeviceOOM / DeviceLost / TransientDeviceError), transient ones retried
    with backoff, and each ``_try_*`` device path is guarded by a circuit
    breaker that degrades it to the pandas fallback when unhealthy.
    Disable: raw runtime errors propagate exactly as before.
    """

    varname = "MODIN_TPU_RESILIENCE_MODE"
    choices = ("Enable", "Disable")
    default = "Enable"

    @classmethod
    def enable(cls):
        cls.put("Enable")

    @classmethod
    def disable(cls):
        cls.put("Disable")


class ResilienceRetries(EnvironmentVariable, type=int):
    """Max retries for a TransientDeviceError at the engine seam."""

    varname = "MODIN_TPU_RESILIENCE_RETRIES"
    default = 2

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(f"Resilience retries should be >= 0, passed value {value}")
        super().put(value)


class ResilienceBackoffS(EnvironmentVariable, type=float):
    """Base of the exponential retry backoff, seconds (doubles per attempt)."""

    varname = "MODIN_TPU_RESILIENCE_BACKOFF_S"
    default = 0.05

    @classmethod
    def put(cls, value: float) -> None:
        if value < 0:
            raise ValueError(f"Resilience backoff should be >= 0, passed value {value}")
        super().put(value)


class ResilienceWatchdogS(EnvironmentVariable, type=float):
    """Wall-clock watchdog on materialize/wait, seconds (0 disables).

    A device fetch that outlives the watchdog raises WatchdogTimeout (a
    DeviceLost) instead of hanging the query on a wedged device forever.
    Off by default: every watched call costs one daemon-thread handoff.
    """

    varname = "MODIN_TPU_RESILIENCE_WATCHDOG_S"
    default = 0.0


class ResilienceBreakerThreshold(EnvironmentVariable, type=int):
    """Consecutive strikes (failures or latency violations) that trip a
    device-path circuit breaker open."""

    varname = "MODIN_TPU_RESILIENCE_BREAKER_THRESHOLD"
    default = 5

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Breaker threshold should be > 0, passed value {value}"
            )
        super().put(value)


class ResilienceBreakerCooldownS(EnvironmentVariable, type=float):
    """Seconds an open breaker waits before admitting a half-open probe."""

    varname = "MODIN_TPU_RESILIENCE_BREAKER_COOLDOWN_S"
    default = 30.0


class ResilienceLatencyBudgetS(EnvironmentVariable, type=float):
    """Per-call latency budget for guarded device paths, seconds (0 = no
    budget).  A call that completes but overruns the budget strikes its
    breaker: a pathologically slow kernel degrades like a failing one."""

    varname = "MODIN_TPU_RESILIENCE_LATENCY_BUDGET_S"
    default = 0.0


class RecoveryMode(EnvironmentVariable, type=str):
    """Lineage-based device-column recovery (graftguard).

    Enable (default): every DeviceColumn carries a lineage record
    (host-materialization / io-source / op-replay); on DeviceLost the
    recovery manager re-seats lost columns on a fresh device and the
    failed engine call is retried, and DeviceOOM gets an evict-then-retry
    leg before any pandas fallback.  Disable: PR-1 behavior (DeviceLost is
    terminal for resident columns, OOM falls straight back).
    """

    varname = "MODIN_TPU_RECOVERY_MODE"
    choices = ("Enable", "Disable")
    default = "Enable"

    @classmethod
    def enable(cls):
        cls.put("Enable")

    @classmethod
    def disable(cls):
        cls.put("Disable")


class DeviceMemoryBudget(EnvironmentVariable, type=int):
    """Device-memory budget (bytes) for resident column buffers (unset =
    no budget).  When set, the pre-flight admission controller at the
    ``deploy`` seam spills cold columns to host before a dispatch that
    would overflow the budget, instead of eating a reactive OOM."""

    varname = "MODIN_TPU_DEVICE_MEMORY_BUDGET"
    default = None

    @classmethod
    def get(cls):  # like Memory: legitimately unset means "no budget"
        try:
            return super().get()
        except TypeError:
            return None


class LineageMaxDepth(EnvironmentVariable, type=int):
    """Max op-replay chain length a lineage record may carry.  A column
    whose chain would exceed it is host-checkpointed at creation (exact
    host copy fetched once), cutting the chain to depth 0."""

    varname = "MODIN_TPU_LINEAGE_MAX_DEPTH"
    default = 8

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Lineage max depth should be > 0, passed value {value}"
            )
        super().put(value)


class SpillRetries(EnvironmentVariable, type=int):
    """How many evict-then-retry rounds a DeviceOOM gets at the engine
    seam before the failure is treated as terminal (0 disables the leg)."""

    varname = "MODIN_TPU_SPILL_RETRIES"
    default = 1

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(f"Spill retries should be >= 0, passed value {value}")
        super().put(value)


class SpillTargetFraction(EnvironmentVariable, type=float):
    """Fraction of resident device bytes one OOM-eviction round tries to
    spill (cold-first).  1.0 spills everything spillable."""

    varname = "MODIN_TPU_SPILL_TARGET_FRACTION"
    default = 0.5

    @classmethod
    def put(cls, value: float) -> None:
        if not 0.0 < value <= 1.0:
            raise ValueError(
                f"Spill target fraction should be in (0, 1], passed value {value}"
            )
        super().put(value)


class KernelRouterMode(EnvironmentVariable, type=str):
    """Substrate-aware routing of the sort-shaped reduction families
    (median / quantile / nunique / mode) between the device kernels and the
    pandas host kernels (graftsort).

    Auto (default): a calibrated cost model picks whichever side is
    predicted faster at the observed (rows, strategy, substrate); frames
    below ``KernelRouterMinRows`` always stay on device (the decision is
    noise there and device residency is worth more).  Device: always run
    the device kernels (pre-router behavior).  Host: always decline to the
    pandas fallback (operator escape hatch for a substrate where the
    device sort is known-bad).
    """

    varname = "MODIN_TPU_KERNEL_ROUTER"
    choices = ("Auto", "Device", "Host")
    default = "Auto"


class KernelRouterMinRows(EnvironmentVariable, type=int):
    """Row count below which ``auto`` routing always picks the device
    kernel without consulting (or running) the calibration: at small n the
    host/device gap is measurement noise and keeping results device-resident
    is worth more than the crossover."""

    varname = "MODIN_TPU_KERNEL_ROUTER_MIN_ROWS"
    default = 1 << 20

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(
                f"Router min rows should be >= 0, passed value {value}"
            )
        super().put(value)


class KernelRouterHistBound(EnvironmentVariable, type=int):
    """Largest value range (max - min + 1) for which an integer /
    dictionary-coded column takes the O(n) segment-sum histogram fast path
    for ``nunique``/``mode`` instead of the O(n log n) sort kernel."""

    varname = "MODIN_TPU_KERNEL_ROUTER_HIST_BOUND"
    default = 1 << 20

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Histogram bound should be > 0, passed value {value}"
            )
        super().put(value)


class KernelRouterCalibrationRows(EnvironmentVariable, type=int):
    """Rows the one-shot router calibration times its micro-kernels at.
    The calibration result is cached to ``CacheDir`` per substrate, so the
    cost is paid once per machine, not once per process."""

    varname = "MODIN_TPU_KERNEL_ROUTER_CALIBRATION_ROWS"
    default = 1 << 18

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Calibration rows should be > 0, passed value {value}"
            )
        super().put(value)


class SpmdMode(EnvironmentVariable, type=str):
    """graftmesh layout routing: local single-program kernels vs sharded
    collective kernels (range_shuffle all_to_all) for the collective-eligible
    ops (sort_values, the sorted-representation build, merge-join).

    Auto (default): the kernel router's calibrated crossover model decides
    per op — a sharded sort pays bucketize + all_to_all + per-shard local
    sorts against one global device sort, so the winner depends on mesh
    shape, row count, and interconnect bandwidth; frames below
    ``SpmdMinRows`` (and every frame on a single-shard mesh) stay local.
    Local: never take the sharded path.  Sharded: always take it when the
    mesh has >= 2 row shards (tests/bench force legs).
    """

    varname = "MODIN_TPU_SPMD"
    choices = ("Auto", "Local", "Sharded")
    default = "Auto"


class SpmdMinRows(EnvironmentVariable, type=int):
    """Row count below which ``Auto`` SPMD routing always stays local
    without consulting (or running) the calibration: at small n the
    collective launch overhead dominates and the decision is noise."""

    varname = "MODIN_TPU_SPMD_MIN_ROWS"
    default = 1 << 18

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(
                f"SPMD min rows should be >= 0, passed value {value}"
            )
        super().put(value)


class StreamMode(EnvironmentVariable, type=str):
    """graftstream out-of-core residency routing: resident single-pass
    kernels vs the windowed streaming executor (modin_tpu/streaming/) for
    frames/sources larger than the device-memory budget.

    Auto (default): the kernel router's ``decide_residency`` leg decides
    per op — estimated bytes against the device ledger's headroom; with no
    ``MODIN_TPU_DEVICE_MEMORY_BUDGET`` set everything stays resident (one
    attribute read on the hot path).  Resident: never stream.  Windowed:
    always stream when the op family supports it (tests/bench pin legs).
    """

    varname = "MODIN_TPU_STREAM"
    choices = ("Auto", "Resident", "Windowed")
    default = "Auto"


class StreamWindowBytes(EnvironmentVariable, type=int):
    """Explicit streaming window size in source bytes; 0 (default) derives
    the window from the device budget so ``1 + prefetch_depth`` windows
    (plus a 2x kernel working-set allowance) fit under it by construction."""

    varname = "MODIN_TPU_STREAM_WINDOW_BYTES"
    default = 0

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(
                f"Stream window bytes should be >= 0, passed value {value}"
            )
        super().put(value)


class StreamPrefetch(EnvironmentVariable, type=int):
    """Windows prefetched ahead of the consuming kernel (0 = fully serial:
    parse, deploy, consume, drop, repeat).  The default of 1 double-buffers:
    window i+1's byte-range parse + host->device transfer overlaps window
    i's kernel, with the window size shrunk so both stay under budget."""

    varname = "MODIN_TPU_STREAM_PREFETCH"
    default = 1

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(
                f"Stream prefetch depth should be >= 0, passed value {value}"
            )
        super().put(value)


class StreamMaxGroups(EnvironmentVariable, type=int):
    """Bound on the streaming groupby's partial-state table (distinct groups
    accumulated across windows).  Past it the streaming executor degrades to
    the resident path — whose high-cardinality groupby already routes
    through the range_shuffle — instead of growing host state unbounded."""

    varname = "MODIN_TPU_STREAM_MAX_GROUPS"
    default = 1 << 20

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Stream max groups should be > 0, passed value {value}"
            )
        super().put(value)


class PlanScanCacheBytes(EnvironmentVariable, type=int):
    """Byte bound on graftplan's per-origin materialized-scan cache.

    Each cached entry pins a fully materialized query compiler; with
    out-of-core-sized sources even the old four-entry FIFO was a multi-GB
    host leak, so eviction is now driven by the entries' measured bytes
    (coldest-first, ``plan.scan.cache_evict``).  0 disables caching
    entirely — every force() re-reads."""

    varname = "MODIN_TPU_PLAN_SCAN_CACHE_BYTES"
    default = 1 << 28

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(
                f"Plan scan cache bytes should be >= 0, passed value {value}"
            )
        super().put(value)


class PlanMode(EnvironmentVariable, type=str):
    """graftplan whole-query deferred planning.

    Auto (default): supported reads (local plain-file read_csv/read_table)
    defer into a logical plan; chained plan-capable calls (project / filter /
    elementwise map / reduce / groupby_agg / sort) extend the plan, and any
    materialization point (repr, to_pandas, index access, an op with no plan
    node) optimizes the plan (dead-column pruning, projection pushdown into
    the byte-range readers, filter pushdown, CSE, map->reduce fusion) and
    lowers it through the eager seams.  Off: never defer — today's eager
    behavior exactly.  Force: Auto plus re-entering planning for
    plan-capable calls on already-materialized TPU frames (Source-rooted
    plans), so rewrites keep applying after materialization points.
    """

    varname = "MODIN_TPU_PLAN"
    choices = ("Auto", "Off", "Force")
    default = "Auto"


class PlanMaxPasses(EnvironmentVariable, type=int):
    """Rewrite-pass budget for graftplan's fixpoint rule engine: each pass
    applies the whole rule catalog once, and optimization stops at fixpoint
    or after this many passes — a misbehaving rule cannot wedge a query."""

    varname = "MODIN_TPU_PLAN_MAX_PASSES"
    default = 8

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Plan pass budget should be > 0, passed value {value}"
            )
        super().put(value)


class OptMode(EnvironmentVariable, type=str):
    """graftopt unified cost-based optimization (plan/optimizer.py).

    Auto (default): each plan materialization runs one joint ``choose()``
    pass over the optimized plan — a cost model seeded from the kernel
    router's calibration table and the graftcost substrate peaks (the
    optimizer's DEFAULT_PRIORS where neither covers a node) annotates
    every node with its execution-strategy legs (device/host,
    local/sharded, fused/staged, resident/windowed),
    the rewrite engine gates rules on modeled cost, and lowering re-plans
    the remaining segment mid-query when measured walls, ledger pressure,
    or compile-storm level diverge from the estimates.  Off: the five
    routers decide independently at their own layers — bit-for-bit the
    pre-graftopt behavior, with zero optimizer allocations.
    """

    varname = "MODIN_TPU_OPT"
    choices = ("Auto", "Off")
    default = "Auto"


class OptReplanFactor(EnvironmentVariable, type=float):
    """Mid-query re-plan threshold for graftopt (plan/optimizer.py).

    A lowered node whose measured wall exceeds its plan-time estimate by
    more than this factor (and clears the absolute noise floor) triggers a
    re-optimization of the not-yet-lowered plan segment through the same
    ``choose()`` pass, with the measured/estimated ratio folded in as a
    correction on the calibrated device-side coefficients."""

    varname = "MODIN_TPU_OPT_REPLAN_FACTOR"
    default = 4.0

    @classmethod
    def put(cls, value: float) -> None:
        if value <= 1.0:
            raise ValueError(
                f"Re-plan factor should be > 1, passed value {value}"
            )
        super().put(value)


class FusedCacheSize(EnvironmentVariable, type=int):
    """Bound on the fused-executable cache in ops/lazy.py (entries, LRU).

    Each entry pins a jitted XLA executable; long sessions with varying
    expression shapes previously grew the cache without limit.  0 disables
    the bound (the pre-LRU behavior)."""

    varname = "MODIN_TPU_FUSED_CACHE_SIZE"
    default = 256

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(
                f"Fused cache size should be >= 0, passed value {value}"
            )
        super().put(value)


class FuseMode(EnvironmentVariable, type=str):
    """graftfuse whole-plan compilation: compile the entire post-scan
    segment of an optimized plan (filter/map/project chain plus its
    reduce or groupby_agg tail) into ONE donated, bucket-padded XLA
    program (plan/fuse.py).

    Auto (default): the kernel router's ``decide_compile`` leg decides per
    materialization — frames below ``MODIN_TPU_FUSE_MIN_ROWS`` stay on the
    staged path, where per-op trace cost beats the dispatch savings.
    Staged: never fuse across the filter boundary (the pre-graftfuse
    lowering).  Fused: always fuse where the segment shape supports it
    (tests and bench legs pin sides).
    """

    varname = "MODIN_TPU_FUSE"
    choices = ("Auto", "Staged", "Fused")
    default = "Auto"


class FuseMinRows(EnvironmentVariable, type=int):
    """Row floor for the Auto fused-compilation decision (graftfuse).

    Below it, ``decide_compile`` keeps the staged path: tracing and
    compiling a whole-plan program costs milliseconds, which a tiny
    frame's saved dispatch never earns back — and unit-test-sized frames
    stay deterministically on the staged kernels."""

    varname = "MODIN_TPU_FUSE_MIN_ROWS"
    default = 32768


class MetersEnabled(EnvironmentVariable, type=bool):
    """graftmeter in-process metric aggregation: counters, gauges, and
    fixed-bucket histograms over the ``emit_metric`` stream, with
    ``snapshot()``/``reset()`` and Prometheus/JSON exposition
    (modin_tpu/observability/meters.py + exposition.py).

    Off by default: the disabled mode costs one module-attribute check per
    ``emit_metric`` call and allocates no aggregation objects
    (``meter_alloc_count()`` asserts exactly that, graftscope-style).
    ``query_stats()`` / ``explain(analyze=True)`` activate per-query
    accounting for their scope regardless of this switch.
    """

    varname = "MODIN_TPU_METERS"
    default = False

    @classmethod
    def enable(cls):
        cls.put(True)

    @classmethod
    def disable(cls):
        cls.put(False)


class MetersMaxSeries(EnvironmentVariable, type=int):
    """Cap on distinct aggregated metric names the graftmeter registry will
    hold (cardinality guard: runaway interpolated segments cannot grow the
    registry without bound).  Names past the cap are dropped and counted in
    the snapshot: ``dropped_series`` (distinct refused names) and
    ``dropped_observations`` (refused emissions)."""

    varname = "MODIN_TPU_METERS_MAX_SERIES"
    default = 2048

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Meter series cap should be > 0, passed value {value}"
            )
        super().put(value)


class CostCapture(EnvironmentVariable, type=str):
    """graftcost XLA cost-model capture (modin_tpu/observability/costs.py):
    per-signature flops/bytes/transcendentals from ``cost_analysis()``,
    padding-waste accounting at the device padding sites, and the achieved
    FLOP/s / bandwidth / roofline join in ``query_stats()`` and
    ``explain(analyze=True)``.

    - ``Auto`` (default): capture is active exactly while graftmeter
      accounting is (``MODIN_TPU_METERS=1`` or an open ``query_stats()``
      scope) — zero overhead otherwise;
    - ``On``: always capture (cost_analysis via the compile-free AOT
      ``lower()`` path);
    - ``Full``: also capture ``memory_analysis()`` (peak/temp/argument
      bytes) of the executable each billed compile built, read from jax's
      caches with no second backend compile (``costs.program_memory``);
    - ``Off``: never capture, even while accounting is on.
    """

    varname = "MODIN_TPU_COST_CAPTURE"
    default = "Auto"
    choices = ("Auto", "On", "Full", "Off")


class ServingEnabled(EnvironmentVariable, type=bool):
    """graftgate multi-tenant serving: query admission control, latency
    budgets, per-tenant fairness, and graceful degradation under
    concurrent load (modin_tpu/serving/).

    Off by default: ``serving.submit`` is a transparent direct call —
    bit-for-bit the single-query behavior — and the seam checks cost one
    module-attribute read (``context.CONTEXT_ON``), allocating nothing
    (``serving.context_alloc_count()`` asserts it, graftscope-style).
    """

    varname = "MODIN_TPU_SERVING"
    default = False

    @classmethod
    def enable(cls):
        cls.put(True)

    @classmethod
    def disable(cls):
        cls.put(False)


class ServingMaxConcurrent(EnvironmentVariable, type=int):
    """Queries the admission gate lets run simultaneously.  Each admitted
    query also reserves its estimated device bytes (tenant cost EWMA, or
    ``device_budget / max_concurrent`` for an unknown tenant) against the
    ``MODIN_TPU_DEVICE_MEMORY_BUDGET`` headroom."""

    varname = "MODIN_TPU_SERVING_MAX_CONCURRENT"
    default = 4

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Serving max-concurrent should be > 0, passed value {value}"
            )
        super().put(value)


class ServingQueueDepth(EnvironmentVariable, type=int):
    """Bounded admission wait queue: queries past max-concurrent wait here
    (weighted-fair wake order); past this depth they are shed with a typed
    ``QueryRejected`` + retry-after hint.  0 = never queue, shed
    immediately at saturation."""

    varname = "MODIN_TPU_SERVING_QUEUE_DEPTH"
    default = 16

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(
                f"Serving queue depth should be >= 0, passed value {value}"
            )
        super().put(value)


class ServingDefaultDeadlineMs(EnvironmentVariable, type=float):
    """Latency budget (milliseconds) for queries submitted without an
    explicit ``deadline_ms`` (0 = unbounded).  The budget rides the query
    as a cancellation token checked at the engine-seam boundaries; expiry
    raises a typed ``DeadlineExceeded`` with overshoot bounded by one
    engine attempt."""

    varname = "MODIN_TPU_SERVING_DEFAULT_DEADLINE_MS"
    default = 0.0

    @classmethod
    def put(cls, value: float) -> None:
        if value < 0:
            raise ValueError(
                f"Serving default deadline should be >= 0, passed value {value}"
            )
        super().put(value)


class ServingTenantWeights(EnvironmentVariable, type=ExactStr):
    """Per-tenant fairness weights as ``"name=weight,name=weight"`` (e.g.
    ``"alice=3,bob=1"``; unlisted tenants weigh 1.0).  A tenant's token
    bucket holds ``weight * max_concurrent`` tokens refilling at that rate
    per second, and the saturated gate wakes queued tenants
    fewest-in-flight-per-weight first."""

    varname = "MODIN_TPU_SERVING_TENANT_WEIGHTS"
    default = ""


class ServingDegradedHighWater(EnvironmentVariable, type=float):
    """Device-ledger fraction of ``MODIN_TPU_DEVICE_MEMORY_BUDGET`` past
    which admitted queries route to the host/pandas path (degraded mode)
    instead of queueing behind a pressured device; an OPEN device-path
    breaker triggers the same routing regardless of residency."""

    varname = "MODIN_TPU_SERVING_DEGRADED_HIGH_WATER"
    default = 0.9

    @classmethod
    def put(cls, value: float) -> None:
        if not 0.0 < value <= 1.0:
            raise ValueError(
                f"Degraded high-water should be in (0, 1], passed value {value}"
            )
        super().put(value)


class FleetEnabled(EnvironmentVariable, type=bool):
    """graftfleet replicated serving: a coordinator spawns and supervises
    N replica serving processes (each with its own virtual mesh, admission
    gate, and watch exporter on an ephemeral port), routes tenant queries
    over a local socket RPC with deadline propagation, detects replica
    failure (heartbeat loss / liveness-probe timeout / dead socket on
    dispatch), drains and redistributes tenants weighted by each
    survivor's typed-shed rate, and respawns dead replicas warm from the
    dataset manifest plus graftview's artifact export/ingest seam
    (modin_tpu/fleet/).

    Off by default: no coordinator, no sockets, no threads —
    ``fleet.submit`` is one module-attribute check away from the local
    ``serving.submit`` path, allocating nothing
    (``fleet_alloc_count()`` asserts it, graftscope-style).
    """

    varname = "MODIN_TPU_FLEET"
    default = False

    @classmethod
    def enable(cls):
        cls.put(True)

    @classmethod
    def disable(cls):
        cls.put(False)


class FleetReplicas(EnvironmentVariable, type=int):
    """How many replica serving processes ``start_fleet()`` spawns."""

    varname = "MODIN_TPU_FLEET_REPLICAS"
    default = 2

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Fleet replica count should be > 0, passed value {value}"
            )
        super().put(value)


class FleetHeartbeatS(EnvironmentVariable, type=float):
    """Seconds between replica heartbeats to the coordinator.  A replica
    whose heartbeat goes silent for ~3 intervals gets one liveness probe
    (fresh dial + ping on its RPC socket); probe failure declares it
    lost.  The monitor re-reads this every tick, so a live retune takes
    effect at the next wakeup."""

    varname = "MODIN_TPU_FLEET_HEARTBEAT_S"
    default = 0.5

    @classmethod
    def put(cls, value: float) -> None:
        if value <= 0:
            raise ValueError(
                f"Fleet heartbeat interval should be > 0, passed value {value}"
            )
        super().put(value)


class FleetRespawn(EnvironmentVariable, type=bool):
    """Respawn a lost replica (fresh process, generation + 1) and re-warm
    it from the dataset manifest + graftview artifact export before
    routing to it again.  Off: the fleet runs degraded on the survivors
    (tests pin legs)."""

    varname = "MODIN_TPU_FLEET_RESPAWN"
    default = True


class FleetCoordAddress(EnvironmentVariable, type=ExactStr):
    """INTERNAL: ``host:port`` of the coordinator's control listener.  Set
    by the coordinator in a replica's spawn environment; never set by
    hand (a replica with no coordinator to dial exits immediately)."""

    varname = "MODIN_TPU_FLEET_COORD"
    default = ""


class FleetReplicaIndex(EnvironmentVariable, type=int):
    """INTERNAL: this replica's slot index in the coordinator's table.
    Set by the coordinator in a replica's spawn environment."""

    varname = "MODIN_TPU_FLEET_INDEX"
    default = -1


class FleetReplicaGeneration(EnvironmentVariable, type=int):
    """INTERNAL: this replica's spawn generation (bumped on every
    respawn so stale hellos/heartbeats from a resumed corpse are
    ignored).  Set by the coordinator in a replica's spawn environment."""

    varname = "MODIN_TPU_FLEET_GEN"
    default = 0


class FleetTestCrash(EnvironmentVariable, type=ExactStr):
    """INTERNAL: fault-injection leg for the test suite — ``warm`` makes
    a replica ``os._exit(3)`` when the warm RPC arrives (the
    crash-during-respawn case).  Set one-shot by
    ``ReplicaFaultInjector.crash_next_respawn()``; never set by hand."""

    varname = "MODIN_TPU_FLEET_TEST_CRASH"
    default = ""


class ViewsMode(EnvironmentVariable, type=str):
    """graftview derived-artifact cache (modin_tpu/views/): whole reduction
    results, nunique/mode/median answers, small groupby output tables, and
    the sorted representations cached per (op fingerprint, column identity,
    device epoch, mesh shape) and shared across every query on the same
    buffers — with append-only (``concat``) growth folding ONLY the
    appended tail into algebraic artifacts instead of recomputing.

    Auto (default): consult and maintain artifacts on the device hot
    paths.  Off: never consult the registry — bit-for-bit the pre-graftview
    behavior, at the cost of one module-attribute read per gated hook
    (``views.VIEWS_ON``, the graftscope zero-overhead-when-off contract).
    The pre-existing sorted-representation cache is NOT gated here: it
    predates graftview and keeps its own semantics in both modes.
    """

    varname = "MODIN_TPU_VIEWS"
    choices = ("Auto", "Off")
    default = "Auto"


class ViewsMaxEntries(EnvironmentVariable, type=int):
    """Cap on live artifacts in the graftview registry; the coldest
    entries are evicted (``view.evict``) past it.  Bounds per-process
    memory under serving workloads that mint many distinct (op, column)
    pairs."""

    varname = "MODIN_TPU_VIEWS_MAX_ENTRIES"
    default = 4096

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Views entry cap should be > 0, passed value {value}"
            )
        super().put(value)


class ViewsHostBudget(EnvironmentVariable, type=int):
    """Host-byte budget for artifact STATE (scalar results, groupby partial
    tables) held by the graftview registry; coldest artifacts evicted past
    it.  Device payloads are budgeted separately by the device ledger
    (``MODIN_TPU_DEVICE_MEMORY_BUDGET``), where pressure drops them before
    any real column spills."""

    varname = "MODIN_TPU_VIEWS_HOST_BUDGET"
    default = 128 * 1024 * 1024

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Views host budget should be > 0, passed value {value}"
            )
        super().put(value)


class ViewsMaxGroups(EnvironmentVariable, type=int):
    """Group-count bound for cacheable groupby output tables (graftview):
    results with more groups than this are never cached or folded — the
    partial-state table must stay small enough that host-side combining
    beats device recomputation, exactly the bound graftstream's windowed
    groupby applies via ``MODIN_TPU_STREAM_MAX_GROUPS``."""

    varname = "MODIN_TPU_VIEWS_MAX_GROUPS"
    default = 65536

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Views group bound should be > 0, passed value {value}"
            )
        super().put(value)


class ViewsMaxChain(EnvironmentVariable, type=int):
    """Append-link chain bound for the graftview registry: a fold lookup
    walks at most this many parent links, and ``note_append`` compacts a
    column's chain (re-anchoring its link past artifact-less intermediate
    tokens, ``view.chain_compact``) once its depth crosses the bound.
    Thousands of micro-batch appends (graftfeed) would otherwise make the
    chain walk O(appends) per lookup — or, at the old hardcoded 8-hop cap,
    silently lose foldability after eight un-queried appends."""

    varname = "MODIN_TPU_VIEWS_MAX_CHAIN"
    default = 64

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Views chain bound should be > 0, passed value {value}"
            )
        super().put(value)


class IngestEnabled(EnvironmentVariable, type=bool):
    """graftfeed continuous ingestion (modin_tpu/ingest/): named ``Feed``
    objects accepting append/upsert micro-batches with schema validation,
    registered live views maintained incrementally on every ingest, and
    staleness-bounded reads (``fresh_within_ms``) wired through the
    serving admission gate.

    Off by default: no feed or view object exists and nothing on any hot
    path allocates (``modin_tpu.ingest.ingest_alloc_count()`` asserts it,
    graftscope-style) — bit-for-bit the pre-graftfeed behavior.
    """

    varname = "MODIN_TPU_INGEST"
    default = False

    @classmethod
    def enable(cls):
        cls.put(True)

    @classmethod
    def disable(cls):
        cls.put(False)


class IngestFoldEvery(EnvironmentVariable, type=int):
    """Fold registered live views every N accepted micro-batches (1, the
    default, maintains every view synchronously on every ingest).  Larger
    values trade freshness for ingest throughput: pending batches
    accumulate fold lag, which staleness-bounded reads observe — a read
    whose ``fresh_within_ms`` bound the lag exceeds forces a synchronous
    fold of the backlog."""

    varname = "MODIN_TPU_INGEST_FOLD_EVERY"
    default = 1

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"Ingest fold cadence should be > 0, passed value {value}"
            )
        super().put(value)


class IngestRetentionRows(EnvironmentVariable, type=int):
    """Default per-feed retention bound, in rows (0 = unbounded).  When a
    feed crosses it, whole oldest micro-batches are trimmed off the frame
    prefix (``ingest.trim.rows``) and every live view refolds from its
    retained per-batch partials — no full recompute, and still-foldable
    graftview artifacts on the retained frame stay valid.  ``create_feed``
    accepts a per-feed override."""

    varname = "MODIN_TPU_INGEST_RETENTION_ROWS"
    default = 0

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(
                f"Ingest retention rows should be >= 0, passed value {value}"
            )
        super().put(value)


class IngestRetentionAgeS(EnvironmentVariable, type=float):
    """Default per-feed retention age bound, in seconds (0 = unbounded):
    micro-batches whose arrival time is older than this are trimmed off
    the feed's prefix on the next ingest, same trim path as the row
    bound.  ``create_feed`` accepts a per-feed override."""

    varname = "MODIN_TPU_INGEST_RETENTION_AGE_S"
    default = 0.0

    @classmethod
    def put(cls, value: float) -> None:
        if value < 0:
            raise ValueError(
                f"Ingest retention age should be >= 0, passed value {value}"
            )
        super().put(value)


class IngestFoldLagMs(EnvironmentVariable, type=float):
    """graftwatch ``fold_lag`` tripwire threshold, milliseconds: the rule
    fires (and captures a rate-limited evidence bundle) when any live
    view's fold lag — the age of its oldest unfolded micro-batch —
    exceeds this while the watch sampler is running."""

    varname = "MODIN_TPU_INGEST_FOLD_LAG_MS"
    default = 1000.0

    @classmethod
    def put(cls, value: float) -> None:
        if value <= 0:
            raise ValueError(
                f"Ingest fold-lag threshold should be > 0, passed value {value}"
            )
        super().put(value)


class WalDir(EnvironmentVariable, type=ExactStr):
    """Root directory for graftwal durability state (per-feed WAL
    segments, checkpoints, meta.json).  '' (the default) resolves to
    ``<MODIN_TPU_CACHE_DIR>/wal``.  ``open_feed(..., durability_dir=...)``
    overrides per call."""

    varname = "MODIN_TPU_WAL_DIR"
    default = ""


class WalFsync(EnvironmentVariable, type=ExactStr):
    """graftwal fsync policy for WAL record writes:

    - ``PerBatch`` (default): fsync after every accepted micro-batch —
      an acked batch survives power loss;
    - ``GroupCommit``: a flusher thread fsyncs dirty segments every
      ``MODIN_TPU_WAL_GROUP_COMMIT_MS`` — bounded loss window, near-Off
      ingest rate;
    - ``Off``: no explicit fsync — survives process crash (the page
      cache persists), not power loss.
    """

    varname = "MODIN_TPU_WAL_FSYNC"
    # ExactStr: the plain str type title-cases ("GroupCommit" ->
    # "Groupcommit"), so the policy names validate here, not via `choices`
    default = "PerBatch"

    @classmethod
    def put(cls, value: str) -> None:
        if value not in ("PerBatch", "GroupCommit", "Off"):
            raise ValueError(
                f"Unsupported value {value!r} for WalFsync; choose one "
                "of ('PerBatch', 'GroupCommit', 'Off')"
            )
        super().put(value)


class WalGroupCommitMs(EnvironmentVariable, type=float):
    """Group-commit flush interval, milliseconds — the loss window under
    ``MODIN_TPU_WAL_FSYNC=GroupCommit`` (ignored by the other policies)."""

    varname = "MODIN_TPU_WAL_GROUP_COMMIT_MS"
    default = 25.0

    @classmethod
    def put(cls, value: float) -> None:
        if value <= 0:
            raise ValueError(
                f"WAL group-commit interval should be > 0, passed value {value}"
            )
        super().put(value)


class WalSegmentBytes(EnvironmentVariable, type=int):
    """WAL segment roll threshold, bytes: the writer starts a new
    ``wal_<first_seq>.seg`` file past this size, and checkpoint
    truncation deletes whole covered segments (reclaim granularity)."""

    varname = "MODIN_TPU_WAL_SEGMENT_BYTES"
    default = 4_194_304

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"WAL segment size should be > 0, passed value {value}"
            )
        super().put(value)


class WalMaxReplayBatches(EnvironmentVariable, type=int):
    """Replay-time bound: a checkpoint is taken once the WAL tail past
    the newest checkpoint exceeds this many records, so crash recovery
    never replays more than ~this many batches."""

    varname = "MODIN_TPU_WAL_MAX_REPLAY_BATCHES"
    default = 256

    @classmethod
    def put(cls, value: int) -> None:
        if value <= 0:
            raise ValueError(
                f"WAL replay bound should be > 0, passed value {value}"
            )
        super().put(value)


class FleetDurabilityDir(EnvironmentVariable, type=ExactStr):
    """INTERNAL: graftwal root a fleet replica recovers durable feeds
    from on warm-up.  Set by the coordinator in a replica's spawn
    environment when the fleet is constructed with a durability dir;
    never set by hand."""

    varname = "MODIN_TPU_FLEET_DURABILITY_DIR"
    default = ""


class TraceEnabled(EnvironmentVariable, type=bool):
    """graftscope structured tracing: spans at the API / query-compiler /
    engine-seam / shuffle-IO layers, the compile ledger's hit accounting,
    and the flight-recorder ring.

    Off by default: the disabled mode costs one module-attribute check per
    instrumented call and allocates no span objects.  ``profile()``
    activates collection for its block regardless of this switch.
    """

    varname = "MODIN_TPU_TRACE"
    default = False

    @classmethod
    def enable(cls):
        cls.put(True)

    @classmethod
    def disable(cls):
        cls.put(False)


class LockdepEnabled(EnvironmentVariable, type=bool):
    """graftdep runtime lock-order validation: every ``named_lock`` /
    ``named_rlock`` acquisition is checked against the declared partial
    order in concurrency/registry.py, per-thread acquisition stacks are
    tracked, and an observed inversion raises ``LockdepViolation`` (and
    flight-dumps the witness pair).

    Debug mode for the concurrency suites and smoke gates, not
    production: the disabled mode costs one module-attribute check per
    acquisition and allocates nothing.  Read raw at import time by
    concurrency/lockdep.py (locks are constructed before the config
    layer is importable); declared here so the switch is typed and
    documented like every other knob.
    """

    varname = "MODIN_TPU_LOCKDEP"
    default = False

    @classmethod
    def enable(cls):
        cls.put(True)

    @classmethod
    def disable(cls):
        cls.put(False)


class TraceFlightRecorderSize(EnvironmentVariable, type=int):
    """How many recent spans the flight-recorder ring buffer retains while
    tracing is on (0 disables the ring and its fault dumps)."""

    varname = "MODIN_TPU_TRACE_FLIGHT_RECORDER_SIZE"
    default = 1024

    @classmethod
    def put(cls, value: int) -> None:
        if value < 0:
            raise ValueError(
                f"Flight recorder size should be >= 0, passed value {value}"
            )
        super().put(value)


class TraceDir(EnvironmentVariable, type=ExactStr):
    """Directory flight-recorder trace dumps are written to."""

    varname = "MODIN_TPU_TRACE_DIR"
    default = ".modin_tpu/traces"


class WatchEnabled(EnvironmentVariable, type=bool):
    """graftwatch always-on serving telemetry: a background sampler thread
    folds the meter registry, ledger gauges, gate depth, and compile-ledger
    deltas into bounded time-series rings every
    ``MODIN_TPU_WATCH_INTERVAL_S``; a stdlib HTTP exporter serves
    ``/metrics`` / ``/statusz`` / ``/debug/queries`` on
    ``MODIN_TPU_WATCH_PORT``; per-tenant SLO burn rates
    (``MODIN_TPU_WATCH_SLO_MS``) and anomaly tripwires run over the rings
    (modin_tpu/observability/watch/).

    Off by default: no sampler or exporter thread exists, and the one hot
    path the service touches (per-query SLO observation at the serving
    gate) costs one module-attribute check and allocates nothing
    (``watch_alloc_count()`` asserts it, graftscope-style).
    """

    varname = "MODIN_TPU_WATCH"
    default = False

    @classmethod
    def enable(cls):
        cls.put(True)

    @classmethod
    def disable(cls):
        cls.put(False)


class WatchIntervalS(EnvironmentVariable, type=float):
    """Seconds between graftwatch sampler ticks (ring sample spacing).
    The sampler re-reads this every tick, so a live retune takes effect
    at the next wakeup."""

    varname = "MODIN_TPU_WATCH_INTERVAL_S"
    default = 1.0

    @classmethod
    def put(cls, value: float) -> None:
        if value <= 0:
            raise ValueError(
                f"Watch interval should be > 0, passed value {value}"
            )
        super().put(value)


class WatchPort(EnvironmentVariable, type=int):
    """TCP port the graftwatch HTTP exporter binds on 127.0.0.1 while the
    service runs (``/metrics``, ``/statusz``, ``/debug/queries``).  0 (the
    default) binds an OS-assigned ephemeral port — read the live port back
    with ``modin_tpu.observability.watch.httpd_port()``; -1 disables the
    exporter entirely (rings/SLO/tripwires still run)."""

    varname = "MODIN_TPU_WATCH_PORT"
    default = 0

    @classmethod
    def put(cls, value: int) -> None:
        if value < -1 or value > 65535:
            raise ValueError(
                f"Watch port should be -1 (exporter off), 0 (ephemeral), "
                f"or a valid TCP port, passed value {value}"
            )
        super().put(value)


class WatchSloMs(EnvironmentVariable, type=ExactStr):
    """Per-tenant latency objectives (milliseconds) for graftwatch SLO
    burn-rate tracking, ``"default=250,alice=50"`` style (same parser
    shape as ``MODIN_TPU_SERVING_TENANT_WEIGHTS``; a bare number such as
    ``"250"`` is shorthand for ``default=250``).  The ``default`` entry
    applies to every tenant without its own; empty (the default) tracks
    latency observations but computes no burn rates."""

    varname = "MODIN_TPU_WATCH_SLO_MS"
    default = ""


class DocModule(EnvironmentVariable, type=ExactStr):
    """Alternate module to source API docstrings from (reference: envvars.py:1338)."""

    varname = "MODIN_TPU_DOC_MODULE"
    default = "pandas"


def _register_builtin_backends() -> None:
    """Wire the canonical Backend <-> (StorageFormat, Engine) bimap
    (reference: envvars.py:401-473)."""
    from modin_tpu.core.execution.utils import Execution

    Backend._BACKEND_TO_EXECUTION.clear()
    Backend._EXECUTION_TO_BACKEND.clear()
    Backend._BACKEND_TO_EXECUTION["Tpu"] = Execution("Tpu", "Jax")
    Backend._EXECUTION_TO_BACKEND[Execution("Tpu", "Jax")] = "Tpu"
    Backend._BACKEND_TO_EXECUTION["Pandas"] = Execution("Native", "Native")
    Backend._EXECUTION_TO_BACKEND[Execution("Native", "Native")] = "Pandas"
    Backend._BACKEND_TO_EXECUTION["Python_Test"] = Execution("Pandas", "Python")
    Backend._EXECUTION_TO_BACKEND[Execution("Pandas", "Python")] = "Python_Test"


_register_builtin_backends()


def _check_vars() -> None:
    """Warn on MODIN_TPU_* env vars that don't match any known parameter."""
    valid = {
        obj.varname
        for obj in globals().values()
        if isinstance(obj, type)
        and issubclass(obj, EnvironmentVariable)
        and not obj.is_abstract
        and obj.varname is not None
    }
    found = {name for name in os.environ if name.startswith("MODIN_TPU_")}
    unknown = found - valid
    if unknown:
        warnings.warn(
            f"Found unknown environment variable{'s' if len(unknown) > 1 else ''}, "
            f"please check {'their' if len(unknown) > 1 else 'its'} spelling: "
            + ", ".join(sorted(unknown))
        )


_check_vars()
