"""Flight recorder: dump the span ring buffer when the system degrades.

While tracing is on, every finished span also lands in a bounded ring
buffer (``spans._RING``, sized by ``MODIN_TPU_TRACE_FLIGHT_RECORDER_SIZE``).
When the resilience layer decides something is seriously wrong — a circuit
breaker trips OPEN, or a device failure is classified terminal (OOM,
device-lost, retries exhausted) — it calls ``dump_flight_record`` and the
last N spans are written as a chrome://tracing-loadable JSON file under
``MODIN_TPU_TRACE_DIR``: the trace that *led up to* the failure, tying the
PR-1 failure classification to its preceding query activity.  The dump also
embeds the graftmeter metrics snapshot taken at dump time under
``otherData.metrics`` (counter state used to die with the process) plus
the counter-track samples (device/host residency, live spans).

The dump is strictly best-effort: it never raises into the query path, it
does nothing while tracing is off (so the default-off mode keeps its
near-zero overhead), and consecutive dumps are rate-limited so a flapping
breaker cannot fill a disk.
"""

from __future__ import annotations

import json
import os
import pathlib
import re
import threading
import time
from typing import List, Optional

from modin_tpu.concurrency import named_lock
from modin_tpu.observability import spans as _spans
from modin_tpu.observability.chrome_trace import to_chrome_trace

#: minimum seconds between dumps (module-level so tests can lower it)
MIN_DUMP_INTERVAL_S = 5.0

#: "no dump yet" sentinel.  NOT 0.0: time.monotonic() is machine uptime on
#: Linux, so `now - 0.0 < interval` spuriously rate-limits every dump for
#: the first `interval` seconds after boot (observed: a test pinning a
#: 3600s interval failed for the first hour of container uptime).
_NEVER_DUMPED = float("-inf")
_last_dump = _NEVER_DUMPED
_dump_lock = named_lock("flight.dump")

_REASON_SANITIZE = re.compile(r"[^A-Za-z0-9_.-]+")


def flight_snapshot() -> List[object]:
    """The spans currently in the ring (oldest first); empty when off."""
    ring = _spans._RING
    return list(ring) if ring is not None else []


def claim_dump_window() -> Optional[float]:
    """Claim the shared dump rate-limit window; None when rate-limited.

    One claim token guards EVERY on-disk failure artifact — breaker/terminal
    flight dumps here and graftwatch tripwire evidence bundles — so one
    incident produces one artifact set, however many detectors saw it.
    A successful claim must be followed by either a completed write or
    :func:`release_dump_claim` (a failed write must not consume the window).
    """
    global _last_dump
    with _dump_lock:
        now = time.monotonic()
        if now - _last_dump < MIN_DUMP_INTERVAL_S:
            return None
        _last_dump = now
        return now


def release_dump_claim(claimed: float) -> None:
    """Release OUR claim after a failed write (see the failure path in
    :func:`dump_flight_record` for why only the matching claim resets)."""
    global _last_dump
    with _dump_lock:
        if _last_dump == claimed:
            _last_dump = _NEVER_DUMPED


def reset_for_tests() -> None:
    """Clear the ring, counter samples, and the rate limiter (test isolation)."""
    global _last_dump
    ring = _spans._RING
    if ring is not None:
        ring.clear()
    counters = _spans._COUNTERS
    if counters is not None:
        counters.clear()
    _last_dump = _NEVER_DUMPED


def dump_flight_record(reason: str, detail: str = "") -> Optional[str]:
    """Write the ring to a trace file; returns the path or None.

    None means "nothing dumped" — tracing off, empty ring, rate-limited,
    or the write failed.  Never raises: the caller is the failure path
    itself and must stay failure-free.
    """
    if not _spans.TRACE_ON:
        return None
    ring = _spans._RING
    if not ring:
        return None
    claimed = claim_dump_window()  # concurrent callers back off
    if claimed is None:
        return None
    with _dump_lock:
        snapshot = list(ring)
        counters = list(_spans._COUNTERS or ())
    try:
        # counter state at dump time: breaker-open / terminal-failure
        # forensics keep the aggregated metrics the process dies with
        # (empty series while MODIN_TPU_METERS is off — still recorded, so
        # the dump says "meters were off" rather than omitting the key)
        from modin_tpu.observability import meters as _meters

        metrics_snapshot = _meters.snapshot()
    except Exception:
        metrics_snapshot = None
    try:
        from modin_tpu.config import TraceDir

        outdir = pathlib.Path(TraceDir.get())
        outdir.mkdir(parents=True, exist_ok=True)
        safe_reason = _REASON_SANITIZE.sub("_", reason) or "fault"
        path = outdir / (
            f"flightrec_{safe_reason}_{os.getpid()}_{int(time.time() * 1e3)}"
            ".trace.json"
        )
        trace = to_chrome_trace(
            snapshot,
            other_data={
                "reason": reason,
                "detail": detail,
                "spans": len(snapshot),
                "metrics": metrics_snapshot,
            },
            counters=counters,
        )
        # atomic: a dump that dies mid-write (ENOSPC, crash) must leave NO
        # truncated trace file — forensics tooling loads whatever it finds
        from modin_tpu.utils.atomic_io import atomic_write_json

        atomic_write_json(str(path), trace)
        return str(path)
    except Exception:
        # best-effort by contract: a failed dump must not worsen the fault —
        # and must not consume the rate-limit window (a transiently
        # unwritable TraceDir would otherwise suppress the next, possibly
        # successful, dump of the real fault; partial-WRITE failures release
        # it too, not just open/serialize ones).  Only release OUR claim:
        # under simultaneous breaker-opens (graftgate: many threads, one
        # incident) another thread may have claimed a newer window and be
        # writing its dump right now — unconditionally zeroing the limiter
        # here would re-open the window behind its back and let a third
        # caller double-dump the same incident.
        release_dump_claim(claimed)
        return None
