"""The JAX engine wrapper — the four-function engine contract.

Reference design: the reference's entire engine abstraction is
``XYWrapper.{deploy,put,materialize,wait}`` (SURVEY.md §2.3; e.g. RayWrapper at
modin/core/execution/ray/common/engine_wrapper.py:59).  The TPU-native
equivalents (SURVEY.md §5 "Distributed communication backend"):

- ``deploy``      -> dispatch a jit-compiled computation (async by default;
                     XLA queues the work on the device stream)
- ``put``         -> ``jax.device_put`` with a target sharding
- ``materialize`` -> ``jax.device_get`` (device -> host numpy)
- ``wait``        -> ``block_until_ready``

Collectives (psum/all_gather/ppermute/all_to_all over ICI) are emitted by XLA
from sharded jnp programs; the shuffle subsystem uses them explicitly via
shard_map (modin_tpu/parallel/shuffle.py).

Every method runs under the resilience policy
(modin_tpu/core/execution/resilience.py): raw runtime errors are classified
into the DeviceOOM / DeviceLost / TransientDeviceError classification, transient
ones retry with exponential backoff, and the blocking fetches
(materialize/wait) are bounded by the configurable wall-clock watchdog.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, Iterable, Optional

from modin_tpu.config import BenchmarkMode, DeviceCount
from modin_tpu.core import memory as _memory
from modin_tpu.core.execution import recovery as _recovery
from modin_tpu.core.execution.resilience import engine_call
from modin_tpu.logging import ClassLogger, disable_logging
from modin_tpu.observability import costs as _costs
from modin_tpu.observability import meters as _meters


def _estimate_deploy_bytes(f_args: tuple) -> tuple:
    """(projected output bytes, {id(buffer)} of the op's own inputs).

    The admission controller needs a pre-dispatch size estimate; without
    tracing the program we take the conservative elementwise bound — the
    output is at most the size of the device inputs combined (reductions
    come in far under it, which only makes admission spill early, never
    late).  The input ids let the spill pass skip buffers the dispatch
    closure pins anyway.
    """
    import jax

    total = 0
    ids = set()
    stack = list(f_args)
    while stack:
        item = stack.pop()
        if isinstance(item, (tuple, list)):
            stack.extend(item)
        elif isinstance(item, jax.Array):
            total += int(item.nbytes)
            ids.add(id(item))
    return total, ids


def _device_nbytes(tree: Any) -> Optional[int]:
    """Bytes of the device arrays in ``tree``; None when it holds none."""
    import jax

    sizes = [
        int(leaf.nbytes)
        for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array)
    ]
    return sum(sizes) if sizes else None


def initialize_jax() -> None:
    """One-time engine startup: enable x64, warm the backend, build the mesh."""
    import jax

    # pandas semantics are 64-bit; TPUs prefer 32-bit.  We enable x64 so
    # int64/float64 frames round-trip exactly; hot kernels can downcast
    # explicitly where the Float64Policy config allows it.
    jax.config.update("jax_enable_x64", True)

    from modin_tpu.parallel.mesh import get_mesh

    get_mesh()

    # compile observability: count every backend compile from process start
    # (the listener is idle-free; recompile storms are invisible otherwise)
    from modin_tpu.observability.compile_ledger import ensure_listener

    ensure_listener()

    # accelerator only: XLA:CPU AOT artifacts are not portable across host
    # feature detection (SIGILL warnings), and CPU compiles are fast.
    if jax.default_backend() != "cpu":
        _place_compilation_cache()
        # the kernel library is a second of imports: paid here, at engine
        # start, and not inside the process's first groupby (whose programs
        # hold Pallas kernels on an accelerator whatever the query)
        import jax.experimental.pallas.tpu  # noqa: F401


#: where compiled XLA executables persist when the caller does not say: one
#: fixed path in the checkout (the path is part of the cache key, so a
#: directory that moves never hits)
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".modin_tpu",
    "jax_cache",
)


def _place_compilation_cache() -> None:
    """Persist every compile (the 1e8-row sort alone compiles for about a
    minute).  ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside:
    jax reads that variable itself, so no directory is set in code then."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILATION_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILATION_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class JaxWrapper(ClassLogger, modin_layer="JAX-ENGINE"):
    """Uniform engine API over jax dispatch and device buffers."""

    @classmethod
    def deploy(cls, func: Callable, f_args: tuple = (), f_kwargs: Optional[dict] = None, num_returns: int = 1, donated: bool = False) -> Any:
        """Run ``func`` (usually jit-compiled); returns device buffers (futures:
        jax arrays are async until materialized).

        graftguard wraps the dispatch three ways: pre-flight **admission**
        (when ``MODIN_TPU_DEVICE_MEMORY_BUDGET`` is set, cold columns are
        spilled to host *before* a dispatch projected to overflow the
        budget), post-hoc **provenance** (the (func, args) of every
        successful deploy is recorded weakly so op-replay lineage can
        rebuild the outputs after a device loss), and a **rebind retry**:
        when the seam's own post-re-seat retry still fails with DeviceLost
        — on real hardware the retried thunk closes over the dead input
        buffers — the argument tree is rebuilt against the re-seated
        columns and dispatched once more over live buffers.
        """
        from modin_tpu.core.execution.resilience import DeviceLost
        from modin_tpu.logging.metrics import emit_metric

        input_ids = None
        if _memory._DEVICE_BUDGET is not None or _recovery.RECOVERY_ON:
            estimate, input_ids = _estimate_deploy_bytes(f_args)
            if _memory._DEVICE_BUDGET is not None:
                _memory.device_ledger.admit(estimate, exclude_ids=input_ids)
        # graftcost: one attribute check when off; while on, the recorder
        # captures static flops/bytes on a billed compile (re-billing the
        # memoized costs on cache hits) and joins the attempt wall
        cost_cb = (
            _costs.dispatch_recorder(func, f_args, f_kwargs)
            if _costs.COST_ON
            else None
        )
        try:
            result = engine_call(
                "deploy",
                lambda: func(*f_args, **(f_kwargs or {})),
                protect_ids=input_ids,
                cost_cb=cost_cb,
            )
        except DeviceLost:
            fresh_args = _recovery.recover_args(f_args)
            if fresh_args is None:
                raise
            emit_metric("recovery.retry.rebind", 1)
            # a fresh recorder over the REBOUND args: the original closure
            # would fingerprint (and AOT-lower over) the dead buffers
            rebind_cb = (
                _costs.dispatch_recorder(func, fresh_args, f_kwargs)
                if _costs.COST_ON
                else None
            )
            result = engine_call(
                "deploy",
                lambda: func(*fresh_args, **(f_kwargs or {})),
                cost_cb=rebind_cb,
            )
            f_args = fresh_args  # provenance must describe the live inputs
        if _recovery.RECOVERY_ON and not donated:
            # a donated dispatch consumes its input buffers: replaying it
            # from op-replay provenance would re-donate the restored
            # incarnations under their columns (use-after-donate).  The
            # fused caller materializes the outputs to host immediately,
            # so they recover via host lineage, never via replay.
            _recovery.record_deploy(func, f_args, f_kwargs, result)
        if BenchmarkMode.get():
            cls.wait(result)
        return result

    @classmethod
    def put(cls, data: Any, sharding: Any = None) -> Any:
        """Host -> device transfer with an optional target sharding."""
        import jax

        if sharding is None:
            from modin_tpu.parallel.mesh import row_sharding

            sharding = row_sharding()
        result = engine_call("put", lambda: jax.device_put(data, sharding))
        if _meters.ACCOUNTING_ON:
            _meters.note_h2d(_device_nbytes(result) or 0)
        if _recovery.RECOVERY_ON:
            _recovery.record_put(data, result)
        return result

    @classmethod
    def materialize(cls, obj_refs: Any) -> Any:
        """Device -> host (blocks until the value is computed and fetched)."""
        import jax

        if _meters.ACCOUNTING_ON:
            # a host sync where it happens: only a device value blocks
            nbytes = _device_nbytes(obj_refs)
            if nbytes is not None:
                _meters.note_host_sync(nbytes)
        return engine_call(
            "materialize", lambda: jax.device_get(obj_refs), watchdog=True
        )

    @classmethod
    def wait(cls, obj_refs: Any) -> None:
        """Block until all given device computations complete.

        One ``jax.block_until_ready`` over the whole tree instead of one
        blocking call per leaf.
        """
        import jax

        engine_call("wait", lambda: jax.block_until_ready(obj_refs), watchdog=True)

    @classmethod
    @disable_logging  # a one-line predicate asked per column per request
    def is_future(cls, item: Any) -> bool:
        import jax

        return isinstance(item, jax.Array)


def upload(values: Any, dtype: Any = None) -> Any:
    """``jnp.asarray`` of a small host table (a remap, a lookup, group
    sizes) with its bytes counted as host->device traffic.  Frame columns go
    through ``JaxWrapper.put``, which carries the resilience policy."""
    import jax.numpy as jnp

    result = jnp.asarray(values, dtype)
    if _meters.ACCOUNTING_ON:
        _meters.note_h2d(int(result.nbytes))
    return result


def materialize(obj_refs: Any) -> Any:
    """Engine-seam host fetch as a free function.

    Kernel modules fetch scalars/counts through this instead of raw
    ``jax.device_get`` so every host sync traverses the resilience policy
    (classification, retry, watchdog) exactly once, defined in one place.
    """
    return JaxWrapper.materialize(obj_refs)
