"""Deterministic fault injection at the ``JaxWrapper`` engine seam.

The resilience layer (modin_tpu/core/execution/resilience.py) is only
trustworthy if its failure handling can be exercised on demand, on any
substrate, without a real device OOM or a lost chip.  This harness
installs a hook at the engine seam — it fires inside every
``JaxWrapper.deploy/put/materialize/wait`` attempt, *under* the resilience
wrapper — raising synthetic but *real-typed* ``XlaRuntimeError``s, or
stalling (slow-kernel), on a deterministic schedule:

    from modin_tpu.testing import inject_faults

    with inject_faults("oom", ops=("materialize",), times=3) as inj:
        df.nlargest(5, "a")          # device path strikes, pandas answers
    assert inj.injected == 3

Because the hook runs inside the attempt, an injected transient fault is
retried by the real backoff loop, a slow-kernel stall trips the real
watchdog, and an OOM strikes the real breaker — the full production path,
minus the hardware.  Faults fire on the first ``times`` matching calls
(after ``skip`` clean ones); no randomness, so a failing sequence replays
exactly.  When the host jaxlib exposes ``XlaRuntimeError`` the harness
raises that very type; otherwise a stand-in with the same name is raised,
which the classification's name-based classification treats identically.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Iterable, Optional

from modin_tpu.concurrency import named_lock
from modin_tpu.core.execution import resilience

_FAULT_MESSAGES = {
    "oom": (
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "9437184000 bytes. [injected by modin_tpu.testing.faults]"
    ),
    "device_lost": (
        "UNAVAILABLE: device lost: runtime heartbeat missed, socket closed "
        "[injected by modin_tpu.testing.faults]"
    ),
    "transient": (
        "DEADLINE_EXCEEDED: operation timed out after 60s "
        "[injected by modin_tpu.testing.faults]"
    ),
}

_ENGINE_OPS = ("deploy", "put", "materialize", "wait")


def _runtime_error_type() -> type:
    """The host runtime's XlaRuntimeError, or a same-named stand-in."""
    try:
        from jax._src.lib import xla_client

        return xla_client.XlaRuntimeError
    except Exception:  # pragma: no cover - depends on host jaxlib
        return type("XlaRuntimeError", (RuntimeError,), {})


def make_device_error(
    kind: str, shard_index: Optional[int] = None
) -> BaseException:
    """A real-typed runtime error whose message classifies as ``kind``
    (one of 'oom', 'device_lost', 'transient').

    ``shard_index`` (device_lost only) names ONE lost mesh row shard in
    the message the way a real runtime names a device; the classification parses
    it back out and graftmesh recovery re-seats only that shard's slices.
    """
    if kind not in _FAULT_MESSAGES:
        raise ValueError(
            f"unknown fault kind {kind!r}; expected one of "
            f"{sorted(_FAULT_MESSAGES)} or 'slow_kernel'"
        )
    message = _FAULT_MESSAGES[kind]
    if shard_index is not None and kind == "device_lost":
        message = f"{message} shard_index={int(shard_index)}"
    return _runtime_error_type()(message)


class FaultInjector:
    """Context manager: fault ``JaxWrapper`` attempts deterministically.

    Parameters
    ----------
    kind : 'oom' | 'device_lost' | 'transient' | 'slow_kernel'
        What each injected fault does.  'slow_kernel' sleeps ``slow_s``
        inside the attempt (completing, but late — visible to the watchdog
        and the breaker's latency budget).
    ops : iterable of {'deploy', 'put', 'materialize', 'wait'}
        Which engine methods the schedule watches.
    times : int or None
        How many matching attempts fault (None = every one while active).
    skip : int
        Matching attempts to let through cleanly before the first fault.
    slow_s : float
        Stall duration for 'slow_kernel'.

    Attributes: ``injected`` (faults fired), ``calls`` (matching attempts
    seen).  Only one injector may be active at a time — deterministic
    schedules do not compose.
    """

    def __init__(
        self,
        kind: str = "transient",
        ops: Iterable[str] = _ENGINE_OPS,
        times: Optional[int] = 1,
        skip: int = 0,
        slow_s: float = 0.05,
        shard_index: Optional[int] = None,
    ):
        if kind != "slow_kernel" and kind not in _FAULT_MESSAGES:
            raise ValueError(f"unknown fault kind {kind!r}")
        unknown = set(ops) - set(_ENGINE_OPS)
        if unknown:
            raise ValueError(f"unknown engine ops {sorted(unknown)}")
        self.kind = kind
        self.ops = frozenset(ops)
        self.times = times
        self.skip = skip
        self.slow_s = slow_s
        self.shard_index = shard_index
        self.injected = 0
        self.calls = 0
        self._lock = named_lock("testing.faults")

    def _hook(self, op: str) -> None:
        if op not in self.ops:
            return
        with self._lock:
            self.calls += 1
            if self.calls <= self.skip:
                return
            if self.times is not None and self.injected >= self.times:
                return
            self.injected += 1
        if self.kind == "slow_kernel":
            time.sleep(self.slow_s)
            return
        raise make_device_error(self.kind, shard_index=self.shard_index)

    def __enter__(self) -> "FaultInjector":
        if resilience._fault_hook is not None:
            raise RuntimeError("another FaultInjector is already active")
        resilience._fault_hook = self._hook
        return self

    def __exit__(self, *exc_info: Any) -> None:
        resilience._fault_hook = None


def inject_faults(
    kind: str = "transient",
    ops: Iterable[str] = _ENGINE_OPS,
    times: Optional[int] = 1,
    skip: int = 0,
    slow_s: float = 0.05,
) -> FaultInjector:
    """Sugar for ``FaultInjector(...)`` — see its docstring."""
    return FaultInjector(kind=kind, ops=ops, times=times, skip=skip, slow_s=slow_s)


# ---------------------------------------------------------------------- #
# sequenced injectors (the graftguard chaos suite)
# ---------------------------------------------------------------------- #


class SequencedFaultInjector(FaultInjector):
    """Scripted multi-phase fault schedule at the engine seam.

    ``steps`` is an ordered list of ``(kind, count)`` pairs; ``kind`` is
    ``'clean'`` (let the attempt through) or any FaultInjector kind, and
    each step consumes ``count`` matching attempts before the schedule
    advances.  After the last step everything runs clean — exactly the
    shape of a real incident: healthy, then a failure window, then healed.

        # DeviceLost mid-query: 4 good deploys, then the device vanishes
        # for 2 dispatches, then the replacement device answers
        with SequencedFaultInjector(
            [("clean", 4), ("device_lost", 2)], ops=("deploy",)
        ) as inj:
            ...

    ``injected`` counts faults fired, ``calls`` matching attempts seen.
    """

    def __init__(
        self,
        steps: Iterable[tuple],
        ops: Iterable[str] = _ENGINE_OPS,
        slow_s: float = 0.05,
        shard_index: Optional[int] = None,
    ):
        super().__init__(
            kind="transient", ops=ops, times=0, slow_s=slow_s,
            shard_index=shard_index,
        )
        self.steps = [(str(kind), int(count)) for kind, count in steps]
        for kind, count in self.steps:
            if kind != "clean" and kind != "slow_kernel" and kind not in _FAULT_MESSAGES:
                raise ValueError(f"unknown fault kind {kind!r} in steps")
            if count < 0:
                raise ValueError(f"negative step count {count} for {kind!r}")
        self._step = 0
        self._step_used = 0

    def _hook(self, op: str) -> None:
        if op not in self.ops:
            return
        with self._lock:
            self.calls += 1
            while (
                self._step < len(self.steps)
                and self._step_used >= self.steps[self._step][1]
            ):
                self._step += 1
                self._step_used = 0
            if self._step >= len(self.steps):
                return  # schedule exhausted: healed
            kind = self.steps[self._step][0]
            self._step_used += 1
            if kind == "clean":
                return
            self.injected += 1
        if kind == "slow_kernel":
            time.sleep(self.slow_s)
            return
        raise make_device_error(kind, shard_index=self.shard_index)


def midquery_device_loss(
    after_deploys: int,
    times: int = 1,
    ops: Iterable[str] = ("deploy",),
    shard_index: Optional[int] = None,
) -> SequencedFaultInjector:
    """DeviceLost mid-query: after ``after_deploys`` successful dispatches
    the next ``times`` attempts raise UNAVAILABLE, then the (replacement)
    device answers — the recovery manager's acceptance scenario.

    ``shard_index`` kills ONE mesh row shard instead of the whole device:
    the error names the shard and graftmesh recovery re-seats only that
    shard's slice of every host-backed column (``recovery.reseat.shard``).
    """
    return SequencedFaultInjector(
        [("clean", after_deploys), ("device_lost", times)], ops=ops,
        shard_index=shard_index,
    )


class OomBurstInjector(FaultInjector):
    """RESOURCE_EXHAUSTED burst that clears once eviction frees memory.

    Matching attempts raise OOM while the device-memory ledger has
    recorded fewer than ``spills`` new spill events since ``__enter__`` —
    the moment evict-then-retry (or admission control) actually spills,
    the modeled memory pressure is gone and every later attempt runs
    clean.  ``max_faults`` bounds the burst as a test-hang backstop.
    """

    def __init__(
        self,
        ops: Iterable[str] = ("deploy",),
        spills: int = 1,
        max_faults: Optional[int] = 25,
    ):
        super().__init__(kind="oom", ops=ops, times=max_faults)
        if spills <= 0:
            raise ValueError(f"spills must be > 0, got {spills}")
        self.spills = spills
        self._baseline = 0

    def __enter__(self) -> "OomBurstInjector":
        from modin_tpu.core.memory import device_ledger

        self._baseline = device_ledger.spill_count()
        return super().__enter__()

    def _hook(self, op: str) -> None:
        if op not in self.ops:
            return
        from modin_tpu.core.memory import device_ledger

        with self._lock:
            self.calls += 1
            if device_ledger.spill_count() - self._baseline >= self.spills:
                return  # eviction freed the memory: pressure cleared
            if self.times is not None and self.injected >= self.times:
                return
            self.injected += 1
        raise make_device_error("oom")


def oom_burst_until_eviction(
    ops: Iterable[str] = ("deploy",),
    spills: int = 1,
    max_faults: Optional[int] = 25,
) -> OomBurstInjector:
    """Sugar for ``OomBurstInjector(...)`` — see its docstring."""
    return OomBurstInjector(ops=ops, spills=spills, max_faults=max_faults)


# ---------------------------------------------------------------------- #
# concurrent injectors (the graftgate serving chaos suite)
# ---------------------------------------------------------------------- #


class MixedFaultInjector(FaultInjector):
    """Interleaved fault kinds under concurrency: the serving chaos shape.

    With N threads running mixed queries, WHICH thread eats a fault is a
    scheduling accident — so this injector is deterministic in the
    *aggregate*, not per thread: every ``period``-th matching attempt
    (process-wide, counted under the injector lock) faults, cycling
    through ``kinds`` in order, until ``times`` faults have fired.  An
    OOM burst and a mid-query DeviceLost therefore land while other
    threads' queries are genuinely in flight — exactly the incident shape
    the serving acceptance suite must survive (every query completes
    bit-exact or fails with a typed serving error; zero hangs).

        with MixedFaultInjector(
            kinds=("oom", "device_lost"), ops=("deploy",), period=5, times=6
        ) as inj:
            ...  # N threads submit queries
        assert inj.injected == 6
    """

    def __init__(
        self,
        kinds: Iterable[str] = ("oom", "device_lost"),
        ops: Iterable[str] = ("deploy",),
        period: int = 5,
        times: Optional[int] = 8,
        slow_s: float = 0.05,
    ):
        super().__init__(kind="transient", ops=ops, times=times, slow_s=slow_s)
        self.kinds = tuple(str(k) for k in kinds)
        if not self.kinds:
            raise ValueError("kinds must name at least one fault kind")
        for kind in self.kinds:
            if kind != "slow_kernel" and kind not in _FAULT_MESSAGES:
                raise ValueError(f"unknown fault kind {kind!r} in kinds")
        if period <= 0:
            raise ValueError(f"period must be > 0, got {period}")
        self.period = period

    def _hook(self, op: str) -> None:
        if op not in self.ops:
            return
        with self._lock:
            self.calls += 1
            if self.calls <= self.skip or self.calls % self.period != 0:
                return
            if self.times is not None and self.injected >= self.times:
                return
            kind = self.kinds[self.injected % len(self.kinds)]
            self.injected += 1
        if kind == "slow_kernel":
            time.sleep(self.slow_s)
            return
        raise make_device_error(kind)


def concurrent_chaos(
    kinds: Iterable[str] = ("oom", "device_lost"),
    ops: Iterable[str] = ("deploy",),
    period: int = 5,
    times: Optional[int] = 8,
) -> MixedFaultInjector:
    """Sugar for ``MixedFaultInjector(...)`` — see its docstring."""
    return MixedFaultInjector(kinds=kinds, ops=ops, period=period, times=times)


# ---------------------------------------------------------------------- #
# process-level injectors (the graftfleet replica chaos suite)
# ---------------------------------------------------------------------- #


class ReplicaFaultInjector:
    """Kill, wedge, and re-crash live graftfleet replicas on demand.

    Unlike the engine-seam injectors above, these faults are real OS
    signals against real supervised processes — the fleet's failure
    detection has to earn every leg:

    - :meth:`kill` — SIGKILL (``kill -9``): the process-exit and
      dead-socket-on-dispatch detection legs;
    - :meth:`hang` — SIGSTOP: the process freezes with its sockets still
      connected (the kernel keeps accepting on its backlog), so only the
      heartbeat-age + liveness-probe-timeout leg can catch it;
    - :meth:`resume` — SIGCONT, for tests that un-wedge a survivor;
    - :meth:`crash_next_respawn` — arm a one-shot crash *inside the next
      respawned replica's warm RPC* (``os._exit(3)`` before any dataset
      loads), proving the coordinator survives a respawn that itself
      dies and retries the slot on the following monitor tick.

        inj = ReplicaFaultInjector(coordinator)
        inj.kill(1)          # replica 1 dies mid-query
        inj.hang(0)          # replica 0 wedges; probe timeout declares it
    """

    def __init__(self, coordinator: Any):
        self.coordinator = coordinator

    def _pid(self, index: int) -> int:
        rep = self.coordinator._replicas[index]
        if rep.pid is None:
            raise RuntimeError(f"replica {index} has no live process")
        return rep.pid

    def kill(self, index: int) -> int:
        """SIGKILL replica ``index``; returns the pid it killed."""
        import os
        import signal as _signal

        pid = self._pid(index)
        os.kill(pid, _signal.SIGKILL)
        return pid

    def hang(self, index: int) -> int:
        """SIGSTOP replica ``index`` (socket stays up, process wedges)."""
        import os
        import signal as _signal

        pid = self._pid(index)
        os.kill(pid, _signal.SIGSTOP)
        return pid

    def resume(self, index: int) -> int:
        """SIGCONT replica ``index`` (undo :meth:`hang`)."""
        import os
        import signal as _signal

        pid = self._pid(index)
        os.kill(pid, _signal.SIGCONT)
        return pid

    def crash_next_respawn(self) -> None:
        """Arm a one-shot crash in the next respawn's warm RPC."""
        self.coordinator._test_crash_next_respawn = True


# ---------------------------------------------------------------------- #
# disk injectors (the graftwal durability suite)
# ---------------------------------------------------------------------- #

_DISK_OPS = (
    "wal.write",
    "wal.fsync",
    "wal.truncate",
    "checkpoint.write",
    "checkpoint.truncate",
)


class DiskFaultInjector:
    """Deterministic disk faults at the graftwal seam
    (``modin_tpu.durability.wal._disk_fault_hook``).

    Every WAL/checkpoint disk operation consults the hook first, so the
    schedule decides exactly WHICH write/fsync/truncate fails and how:

    - ``'enospc'`` — ``OSError(ENOSPC)``: exercises the reclaim-then-
      retry path and the typed ``DurabilityError`` refusal;
    - ``'eio'`` — ``OSError(EIO)``: trips the per-feed breaker into
      memory-only degraded mode (``wal.degraded``);
    - ``'fsync_fail'`` — ``OSError(EIO)`` aimed at fsync ops (an fsync
      that fails is durability already lost: the writer degrades);
    - ``'torn_write'`` — valid for ``wal.write`` only: the first
      ``torn_bytes`` bytes of the record land on disk and the process
      SIGKILLs itself — a REAL torn tail for recovery to truncate;
    - ``'kill'`` — SIGKILL immediately *before* the matching operation:
      mid-batch (``wal.write``), mid-checkpoint (``checkpoint.write``),
      mid-truncate (``wal.truncate`` / ``checkpoint.truncate``) crash
      points for the differential recovery grid.

    Same determinism contract as the engine-seam injectors: faults fire
    on the first ``times`` matching calls after ``skip`` clean ones, one
    injector active at a time.

        with DiskFaultInjector("enospc", ops=("wal.write",)) as inj:
            feed.append(batch)       # reclaim runs, then the retry lands
        assert inj.injected == 1
    """

    def __init__(
        self,
        kind: str = "eio",
        ops: Iterable[str] = ("wal.write",),
        times: Optional[int] = 1,
        skip: int = 0,
        torn_bytes: int = 5,
    ):
        if kind not in ("enospc", "eio", "fsync_fail", "torn_write", "kill"):
            raise ValueError(f"unknown disk fault kind {kind!r}")
        unknown = set(ops) - set(_DISK_OPS)
        if unknown:
            raise ValueError(f"unknown disk ops {sorted(unknown)}")
        if kind == "torn_write" and set(ops) != {"wal.write"}:
            raise ValueError(
                "torn_write is only meaningful for ops=('wal.write',)"
            )
        self.kind = kind
        self.ops = frozenset(ops)
        self.times = times
        self.skip = skip
        self.torn_bytes = int(torn_bytes)
        self.injected = 0
        self.calls = 0
        self._lock = named_lock("testing.faults")

    def _hook(self, op: str) -> Optional[int]:
        if op not in self.ops:
            return None
        with self._lock:
            self.calls += 1
            if self.calls <= self.skip:
                return None
            if self.times is not None and self.injected >= self.times:
                return None
            self.injected += 1
        if self.kind == "enospc":
            import errno

            raise OSError(
                errno.ENOSPC,
                "No space left on device [injected by modin_tpu.testing.faults]",
            )
        if self.kind in ("eio", "fsync_fail"):
            import errno

            raise OSError(
                errno.EIO,
                "Input/output error [injected by modin_tpu.testing.faults]",
            )
        if self.kind == "torn_write":
            return self.torn_bytes  # the writer lands a prefix + SIGKILLs
        # 'kill': die before the operation — nothing of it reaches disk
        import os as _os
        import signal as _signal

        _os.kill(_os.getpid(), _signal.SIGKILL)
        return None  # pragma: no cover - unreachable

    def __enter__(self) -> "DiskFaultInjector":
        from modin_tpu.durability import wal as _wal

        if _wal._disk_fault_hook is not None:
            raise RuntimeError("another DiskFaultInjector is already active")
        _wal._disk_fault_hook = self._hook
        return self

    def __exit__(self, *exc_info: Any) -> None:
        from modin_tpu.durability import wal as _wal

        _wal._disk_fault_hook = None


def inject_disk_faults(
    kind: str = "eio",
    ops: Iterable[str] = ("wal.write",),
    times: Optional[int] = 1,
    skip: int = 0,
    torn_bytes: int = 5,
) -> DiskFaultInjector:
    """Sugar for ``DiskFaultInjector(...)`` — see its docstring."""
    return DiskFaultInjector(
        kind=kind, ops=ops, times=times, skip=skip, torn_bytes=torn_bytes
    )
