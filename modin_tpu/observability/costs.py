"""graftcost: XLA cost-model observability — roofline efficiency & padding waste.

graftscope answers *where the time went* and graftmeter *what the query
consumed*; this module answers **how well the hardware was used**.  Three
legs, all riding the seams the earlier layers already cut:

1. **Static cost capture.**  When the engine seam bills an XLA compile to a
   signature (``compile_ledger``), the deploy path also asks jax what the
   compiled program *costs*: ``Lowered.cost_analysis()`` (flops, bytes
   accessed, transcendentals — available WITHOUT a backend compile, so the
   default capture adds only a re-trace/lower, never a second backend
   compile) and, under ``MODIN_TPU_COST_CAPTURE=Full``,
   ``compiled.memory_analysis()`` (peak/temp/argument bytes) of the
   executable the call built: :func:`program_memory`, which the memory read
   of every made program shares.  Anything missing — None analysis, absent
   keys, a backend that cannot answer — degrades to ``"unknown"``; capture
   NEVER raises into the dispatch it observes.

2. **Achieved efficiency.**  Captured flops/bytes join the engine-seam
   dispatch wall into achieved FLOP/s, achieved bandwidth, and a roofline
   fraction (vs :func:`substrate_peaks`: a built-in table for known TPU
   generations, a cached one-shot micro-benchmark on CPU).  On an async
   substrate the attempt wall is enqueue time, so per-signature fractions
   are flagged ``async_caveat``; the EXPLAIN ANALYZE per-node join uses the
   node's measured wall instead, which includes the materialization sync.

3. **Padding-waste accounting.**  The pow2/bucket/shard-multiple padding in
   ``ops/groupby.py`` / ``ops/sort.py`` / ``ops/structural.py`` /
   ``ops/reductions.py`` was invisible: a "12.4 GB moved" number said
   nothing about how much of it was arithmetic on pad rows.  Padding sites
   call :func:`note_padding` (one ``COST_ON`` attribute check when off),
   which feeds ``engine.cost.padded_bytes`` / ``engine.cost.
   padding_waste_bytes`` counters, the per-thread counters EXPLAIN ANALYZE
   bills per plan node, and the Chrome-trace counter track.

Disabled-mode contract (the default): ``COST_ON`` is False unless
``MODIN_TPU_COST_CAPTURE`` is ``On``/``Full`` or (under ``Auto``) graftmeter
accounting is active; every instrumented site checks that ONE module
attribute and allocates nothing while it is False.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Tuple

from modin_tpu.concurrency import named_lock
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import spans as _spans

#: Module-level fast path, graftscope-style.  True while cost capture +
#: padding accounting are active: ``MODIN_TPU_COST_CAPTURE=On|Full``, or
#: ``Auto`` (the default) with graftmeter accounting live (meters on or an
#: open ``query_stats()`` scope).  Instrumented sites check this ONE
#: attribute before doing anything else.
COST_ON: bool = False

#: True only under ``MODIN_TPU_COST_CAPTURE=Full``: a billed compile also
#: captures the memory_analysis of the executable it built.
FULL_CAPTURE: bool = False

UNKNOWN = "unknown"

_mode = "Auto"

_tls = threading.local()

_pad_lock = named_lock("costs.padding")
# process-global padding accumulators (the Chrome counter track reads these)
_total_padded_bytes = 0
_total_waste_bytes = 0
# process-global collective-traffic accumulator (all_to_all / psum payload
# bytes observed at the instrumented collective sites, graftmesh)
_total_collective_bytes = 0
# most recent achieved bandwidth sample, bytes/s (Chrome counter track)
_last_achieved_bw = 0.0


# ---------------------------------------------------------------------- #
# enable/disable plumbing
# ---------------------------------------------------------------------- #


def _refresh() -> None:
    """Recompute the fast-path flags from the config knob + graftmeter."""
    global COST_ON, FULL_CAPTURE
    FULL_CAPTURE = _mode == "Full"
    if _mode == "Off":
        COST_ON = False
    elif _mode in ("On", "Full"):
        COST_ON = True
    else:  # Auto: piggyback on graftmeter accounting
        from modin_tpu.observability import meters as _meters

        COST_ON = _meters.ACCOUNTING_ON


def _on_cost_param(param: Any) -> None:
    global _mode
    _mode = str(param.get())
    _refresh()


def cost_capture_mode() -> str:
    return _mode


# ---------------------------------------------------------------------- #
# static cost extraction (graceful degradation is the whole point)
# ---------------------------------------------------------------------- #


def _first_mapping(analysis: Any) -> Optional[dict]:
    """jax's cost_analysis has returned a dict, a list of dicts, and None
    across versions; normalize to one mapping or None."""
    if isinstance(analysis, dict):
        return analysis
    if isinstance(analysis, (list, tuple)) and analysis:
        head = analysis[0]
        if isinstance(head, dict):
            return head
    return None


def extract_cost(analysis: Any) -> Dict[str, Any]:
    """``{"flops", "bytes_accessed", "transcendentals"}`` from a raw
    ``cost_analysis()`` result; every missing/absent value is ``"unknown"``.
    """
    mapping = _first_mapping(analysis) or {}

    def field(key: str) -> Any:
        value = mapping.get(key)
        if isinstance(value, (int, float)) and value >= 0:
            return float(value)
        return UNKNOWN

    return {
        "flops": field("flops"),
        "bytes_accessed": field("bytes accessed"),
        "transcendentals": field("transcendentals"),
    }


def extract_memory(stats: Any) -> Dict[str, Any]:
    """``{"argument_bytes", "output_bytes", "temp_bytes", "peak_bytes"}``
    from a ``memory_analysis()`` result; missing attributes -> ``"unknown"``.

    ``peak_bytes`` is the best-effort arg+out+temp sum when the backend
    reports no explicit peak (XLA:CPU reports component sizes only).
    """
    out: Dict[str, Any] = {}
    for field, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
    ):
        value = getattr(stats, attr, None)
        out[field] = (
            float(value) if isinstance(value, (int, float)) and value >= 0
            else UNKNOWN
        )
    peak = getattr(stats, "peak_memory_in_bytes", None)
    if isinstance(peak, (int, float)) and peak > 0:
        out["peak_bytes"] = float(peak)
    elif all(out[f] != UNKNOWN for f in ("argument_bytes", "output_bytes", "temp_bytes")):
        out["peak_bytes"] = (
            out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        )
    else:
        out["peak_bytes"] = UNKNOWN
    return out


_UNKNOWN_COST = {
    "flops": UNKNOWN,
    "bytes_accessed": UNKNOWN,
    "transcendentals": UNKNOWN,
}


def _merge_known(dst: Dict[str, Any], src: Dict[str, Any]) -> None:
    """Overlay only the KNOWN fields of ``src`` — a later analysis that
    cannot answer a field must not clobber an earlier one that could."""
    for key, value in src.items():
        if value != UNKNOWN:
            dst[key] = value
        else:
            dst.setdefault(key, UNKNOWN)


def capture_static(func: Any, f_args: tuple, f_kwargs: Optional[dict]) -> Dict[str, Any]:
    """Best-effort static cost of the program ``func`` compiles to.

    Uses the AOT ``lower()`` path: ``Lowered.cost_analysis()`` answers from
    the unoptimized HLO without a backend compile (measured: no
    ``backend_compile_duration`` event fires); its trace event is not billed
    (``compile_ledger.relowering``).  Under ``Full`` mode it adds
    :func:`program_memory` of the executable the call built.  Any failure
    anywhere yields unknown fields.
    """
    from modin_tpu.observability import compile_ledger as _ledger_mod

    cost = dict(_UNKNOWN_COST)
    try:
        lower = getattr(func, "lower", None)
        if lower is None:
            return cost
        with _ledger_mod.relowering():
            lowered = lower(*f_args, **(f_kwargs or {}))
        try:
            _merge_known(cost, extract_cost(lowered.cost_analysis()))
        except Exception:
            pass
        if FULL_CAPTURE:
            _merge_known(cost, program_memory(func, f_args, f_kwargs) or {})
    except Exception:
        # a broken capture must never break the dispatch it observes
        pass
    return cost


# ---------------------------------------------------------------------- #
# the cost ledger (per attribution signature)
# ---------------------------------------------------------------------- #


class CostLedger:
    """Thread-safe per-signature cost entries joined with dispatch wall."""

    def __init__(self) -> None:
        self._lock = named_lock("costs.ledger")
        self._entries: Dict[str, dict] = {}
        self._padding: Dict[str, dict] = {}  # per padding site
        self._collective: Dict[str, dict] = {}  # per collective site

    def _entry(self, signature: str) -> dict:
        entry = self._entries.get(signature)
        if entry is None:
            entry = self._entries[signature] = {
                "captures": 0,
                "flops": UNKNOWN,
                "bytes_accessed": UNKNOWN,
                "transcendentals": UNKNOWN,
                "dispatches": 0,
                "wall_s": 0.0,
                # accumulated per dispatch (the dispatch's OWN program
                # cost, not last-capture x count): one signature legally
                # pools many programs — or, untraced, every program
                "flops_total": 0.0,
                "bytes_total": 0.0,
            }
        return entry

    def record_capture(self, signature: str, cost: Dict[str, Any]) -> None:
        with self._lock:
            entry = self._entry(signature)
            entry["captures"] += 1
            _merge_known(entry, cost)

    def record_dispatch(
        self,
        signature: str,
        wall_s: float,
        flops: Any = UNKNOWN,
        bytes_accessed: Any = UNKNOWN,
    ) -> None:
        with self._lock:
            entry = self._entry(signature)
            entry["dispatches"] += 1
            entry["wall_s"] += wall_s
            if flops != UNKNOWN and flops is not None:
                entry["flops_total"] += flops
            if bytes_accessed != UNKNOWN and bytes_accessed is not None:
                entry["bytes_total"] += bytes_accessed

    def record_padding(self, site: str, padded_bytes: int, valid_bytes: int) -> None:
        with self._lock:
            entry = self._padding.get(site)
            if entry is None:
                entry = self._padding[site] = {
                    "events": 0, "padded_bytes": 0, "waste_bytes": 0,
                }
            entry["events"] += 1
            entry["padded_bytes"] += padded_bytes
            entry["waste_bytes"] += max(padded_bytes - valid_bytes, 0)

    def record_collective(self, site: str, nbytes: int) -> None:
        with self._lock:
            entry = self._collective.get(site)
            if entry is None:
                entry = self._collective[site] = {"events": 0, "bytes": 0}
            entry["events"] += 1
            entry["bytes"] += nbytes

    def efficiency(self, signature: str) -> Optional[dict]:
        """Achieved FLOP/s, bandwidth, and roofline fraction for one
        signature (None if never dispatched).  ``async_caveat`` is always
        True: the recorded wall is the engine-seam attempt wall, which on
        an async substrate is enqueue time (the post-deploy BenchmarkMode
        sync happens after the seam) — treat per-signature fractions as an
        upper bound and use the EXPLAIN ANALYZE per-node join (measured
        node wall, materialization included) for honest numbers."""
        with self._lock:
            entry = self._entries.get(signature)
            if entry is None or entry["dispatches"] == 0:
                return None
            entry = dict(entry)
        wall = entry["wall_s"]
        flops_total = entry["flops_total"]
        bytes_total = entry["bytes_total"]
        achieved_flops = (
            flops_total / wall if flops_total > 0 and wall > 0 else UNKNOWN
        )
        achieved_bw = (
            bytes_total / wall if bytes_total > 0 and wall > 0 else UNKNOWN
        )
        return {
            **entry,
            "achieved_flops_per_s": achieved_flops,
            "achieved_bytes_per_s": achieved_bw,
            "roofline_fraction": roofline_fraction(
                flops_total or None, bytes_total or None, wall
            ) or UNKNOWN,
            "async_caveat": True,
        }

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "signatures": {s: dict(e) for s, e in self._entries.items()},
                "padding": {s: dict(e) for s, e in self._padding.items()},
                "collective": {
                    s: dict(e) for s, e in self._collective.items()
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._padding.clear()
            self._collective.clear()


_LEDGER = CostLedger()


def get_cost_ledger() -> CostLedger:
    return _LEDGER


def reset() -> None:
    """Clear the cost ledger and the process padding accumulators (tests,
    per-section bench resets)."""
    global _total_padded_bytes, _total_waste_bytes, _last_achieved_bw
    global _total_collective_bytes
    _LEDGER.reset()
    with _pad_lock:
        _total_padded_bytes = 0
        _total_waste_bytes = 0
        _total_collective_bytes = 0
        _last_achieved_bw = 0.0


# ---------------------------------------------------------------------- #
# per-thread counters (EXPLAIN ANALYZE takes deltas, like thread_dispatches)
# ---------------------------------------------------------------------- #


def thread_cost() -> Tuple[float, float]:
    """Monotonic per-thread (estimated flops, estimated bytes accessed)."""
    return (getattr(_tls, "flops", 0.0), getattr(_tls, "bytes", 0.0))


def thread_padding() -> Tuple[int, int]:
    """Monotonic per-thread (padded bytes, padding-waste bytes)."""
    return (getattr(_tls, "padded", 0), getattr(_tls, "waste", 0))


def thread_collective() -> int:
    """Monotonic per-thread collective-payload bytes (all_to_all/psum)."""
    return getattr(_tls, "collective", 0)


def _bump_thread_cost(flops: Any, bytes_accessed: Any) -> None:
    if flops != UNKNOWN and flops is not None:
        _tls.flops = getattr(_tls, "flops", 0.0) + flops
    if bytes_accessed != UNKNOWN and bytes_accessed is not None:
        _tls.bytes = getattr(_tls, "bytes", 0.0) + bytes_accessed


# ---------------------------------------------------------------------- #
# the deploy-seam hook
# ---------------------------------------------------------------------- #

#: per-jitted-function cost memo: a warm dispatch (no compile billed)
#: re-bills the costs captured at its compile so EXPLAIN ANALYZE and the
#: metric stream see estimated work on cache hits too.  Keyed weakly on the
#: function object (jitted callables are long-lived, cached per op family)
#: then by the argument shape/dtype key (one jit compiles per shape).
import weakref  # noqa: E402

_func_costs: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _arg_key(f_args: tuple, f_kwargs: Optional[dict]) -> tuple:
    """Shape/dtype fingerprint of a dispatch's full argument tree.

    Every input that changes which program jit compiles must land in the
    key: jax AND numpy arrays contribute (shape, dtype), hashable scalars
    their value (a different static scalar can mean a different program),
    kwargs are walked too.  Anything else falls back to its type name.
    """
    import jax
    import numpy as np

    key = []
    stack = [f_args]
    if f_kwargs:
        stack.append(tuple(sorted(f_kwargs.items())))
    while stack:
        item = stack.pop()
        if isinstance(item, (tuple, list)):
            stack.extend(item)
        elif isinstance(item, dict):
            stack.extend(sorted(item.items()))
        elif isinstance(item, (jax.Array, np.ndarray)):
            key.append((tuple(item.shape), str(item.dtype)))
        elif isinstance(item, (int, float, bool, str, bytes, type(None))):
            key.append((type(item).__name__, item))
        else:
            key.append(type(item).__name__)
    return tuple(key)


#: per-jitted-function memo of :func:`program_memory`, keyed as _func_costs
_func_memory: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

#: set once a memory read fired a backend compile of its own (jax did not
#: hand back the executable the call built): no read is made after it
_memory_read_compiles = False


def program_memory(func: Any, f_args: tuple, f_kwargs: Optional[dict]) -> Optional[Dict[str, Any]]:
    """:func:`extract_memory` of the executable that a call of the jitted
    ``func`` (or a ``named_jit`` program) with these arguments built, read
    once per (function, argument shapes/dtypes); ``None`` where it cannot be
    read.

    Made after the call: ``lower(...).compile()`` is then answered from jax's
    caches, the call's own lowering and executable, and fires no backend
    compile (``tests/test_meters.py`` holds it), a persistent-cache load
    included.  Should one fire all the same, it is billed as the compile it
    is, and no memory is read again in this process.
    """
    global _memory_read_compiles
    import jax

    from modin_tpu.observability import compile_ledger as _ledger_mod

    func = getattr(func, "_jitted", func)
    if _memory_read_compiles or not hasattr(func, "lower"):
        return None
    try:
        if any(isinstance(leaf, jax.core.Tracer) for leaf in jax.tree_util.tree_leaves((f_args, f_kwargs))):
            return None  # called while an enclosing program traces
        key = _arg_key(f_args, f_kwargs)
        per_func = _func_memory.get(func)
        if per_func is not None and key in per_func:
            return per_func[key]
        made = _ledger_mod.compiles_on_this_thread()
        memory = None
        try:
            with _ledger_mod.relowering():
                compiled = func.lower(*f_args, **(f_kwargs or {})).compile()
            memory = extract_memory(compiled.memory_analysis())
        finally:
            if _ledger_mod.compiles_on_this_thread() != made:
                _memory_read_compiles = True
        _func_memory.setdefault(func, {})[key] = memory
        return memory
    except Exception:
        # a memory read must never break the call it reads
        return None


def dispatch_recorder(func: Any, f_args: tuple, f_kwargs: Optional[dict]):
    """One-dispatch cost hook for ``engine_call`` (built in ``deploy``).

    The returned callable runs on the dispatching thread right after a
    successful deploy attempt, while the ``engine.<op>.attempt`` span is
    still open: a billed compile triggers a fresh static capture (memoized
    per (func, argument shapes/dtypes)); a cache hit re-bills the memoized
    costs.  Either way the costs land on the attempt span, the metric
    stream, the per-thread counters, and the cost ledger joined with the
    wall of the SUCCESSFUL attempt (``engine_call`` times each attempt, so
    retries and backoff sleeps are never billed as dispatch wall; the
    recorder's own clock is only the fallback).
    """
    t0 = time.perf_counter()

    def record(compiled: bool, sp: Any, attempt_wall_s: Optional[float] = None) -> None:
        global _last_achieved_bw
        try:
            key = None
            cost = None
            try:
                key = _arg_key(f_args, f_kwargs)
                per_func = _func_costs.get(func)
            except TypeError:  # unhashable/unweakrefable func
                per_func = None
            if not compiled and per_func is not None:
                cost = per_func.get(key)
            if cost is None:
                cost = capture_static(func, f_args, f_kwargs)
                if key is not None:
                    try:
                        if per_func is None:
                            per_func = _func_costs.setdefault(func, {})
                        per_func[key] = cost
                    except TypeError:
                        pass
            wall_s = (
                attempt_wall_s
                if attempt_wall_s is not None
                else time.perf_counter() - t0
            )
            signature = _spans.attribution_signature()
            flops = cost.get("flops", UNKNOWN)
            bytes_acc = cost.get("bytes_accessed", UNKNOWN)
            transc = cost.get("transcendentals", UNKNOWN)
            peak = cost.get("peak_bytes", UNKNOWN)
            if compiled:
                _LEDGER.record_capture(signature, cost)
                # the compile ledger's per-signature entry carries the
                # static costs too: one snapshot answers "who compiled,
                # how often, and what does the program cost"
                from modin_tpu.observability.compile_ledger import (
                    get_compile_ledger,
                )

                get_compile_ledger().record_cost(signature, cost)
            # the dispatch's OWN program cost accumulates (a signature can
            # pool several programs; last-capture x count would be wrong)
            _LEDGER.record_dispatch(signature, wall_s, flops, bytes_acc)
            _bump_thread_cost(flops, bytes_acc)
            if flops != UNKNOWN:
                emit_metric("engine.cost.flops", flops)
            if bytes_acc != UNKNOWN:
                emit_metric("engine.cost.bytes", bytes_acc)
                if wall_s > 0:
                    _last_achieved_bw = bytes_acc / wall_s
            if transc != UNKNOWN and transc > 0:
                emit_metric("engine.cost.transcendentals", transc)
            if peak != UNKNOWN:
                emit_metric("engine.cost.peak_bytes", peak)
            if sp is not None:
                sp.attrs["cost_flops"] = flops
                sp.attrs["cost_bytes"] = bytes_acc
                if peak != UNKNOWN:
                    sp.attrs["cost_peak_bytes"] = peak
        except Exception:
            # accounting must never break the dispatch it measures
            pass

    return record


# ---------------------------------------------------------------------- #
# padding-waste accounting
# ---------------------------------------------------------------------- #


def note_padding(site: str, padded_bytes: int, valid_bytes: int) -> None:
    """One padded device allocation/move: ``padded_bytes`` physical vs
    ``valid_bytes`` logical.  Call sites gate on :data:`COST_ON`; the
    difference is billed as padding waste to the metric stream, the
    per-thread counters, the per-site ledger, and the Chrome counter track.
    Zero waste (already aligned) is still recorded — "no padding" is an
    answer too.
    """
    global _total_padded_bytes, _total_waste_bytes
    try:
        padded_bytes = int(padded_bytes)
        waste = max(padded_bytes - int(valid_bytes), 0)
        _tls.padded = getattr(_tls, "padded", 0) + padded_bytes
        _tls.waste = getattr(_tls, "waste", 0) + waste
        with _pad_lock:
            _total_padded_bytes += padded_bytes
            _total_waste_bytes += waste
        _LEDGER.record_padding(site, padded_bytes, int(valid_bytes))
        emit_metric("engine.cost.padded_bytes", padded_bytes)
        emit_metric("engine.cost.padding_waste_bytes", waste)
        if _spans.TRACE_ON:
            sp = _spans.current_span()
            if sp is not None:
                sp.attrs["padding_waste_bytes"] = (
                    sp.attrs.get("padding_waste_bytes", 0) + waste
                )
    except Exception:
        pass


def note_collective(site: str, nbytes: int) -> None:
    """One collective payload crossing the interconnect: ``nbytes`` moved
    through an all_to_all/psum at ``site``.  Call sites gate on
    :data:`COST_ON`.  Feeds ``engine.cost.collective_bytes``, the
    per-thread counter, and the per-site ledger — the observability leg of
    the router's collective-aware crossover model (graftmesh).
    """
    global _total_collective_bytes
    try:
        nbytes = int(nbytes)
        _tls.collective = getattr(_tls, "collective", 0) + nbytes
        with _pad_lock:
            _total_collective_bytes += nbytes
        _LEDGER.record_collective(site, nbytes)
        emit_metric("engine.cost.collective_bytes", nbytes)
        if _spans.TRACE_ON:
            sp = _spans.current_span()
            if sp is not None:
                sp.attrs["collective_bytes"] = (
                    sp.attrs.get("collective_bytes", 0) + nbytes
                )
    except Exception:
        pass


def counter_sample() -> tuple:
    """(total padding-waste bytes, last achieved bandwidth bytes/s) — the
    two graftcost Chrome-trace counter tracks, sampled at span finish."""
    return (_total_waste_bytes, int(_last_achieved_bw))


# ---------------------------------------------------------------------- #
# roofline peaks
# ---------------------------------------------------------------------- #

#: peak (FLOP/s, bytes/s) per accelerator device kind — published spec
#: sheets (f32 dense for flops, HBM bandwidth).  A TPU kind not listed is an
#: error; only non-TPU substrates (XLA:CPU) use the host micro-benchmark
#: below.
KNOWN_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v2": (45e12, 0.7e12),
    "TPU v3": (123e12, 0.9e12),
    "TPU v4": (275e12, 1.2e12),
    "TPU v5 lite": (197e12, 0.82e12),
    "TPU v5e": (197e12, 0.82e12),
    "TPU v5p": (459e12, 2.76e12),
    "TPU v6e": (918e12, 1.64e12),
}

_peaks_cache: Optional[dict] = None
_peaks_lock = named_lock("costs.peaks")


def _measure_host_peaks() -> Optional[dict]:
    """One-shot micro-benchmark of this host: dense-dot FLOP/s and memcpy
    bandwidth via numpy.  ~100ms once per substrate; cached to CacheDir."""
    import numpy as np

    try:
        k = 512
        a = np.random.default_rng(0).random((k, k))
        b = np.random.default_rng(1).random((k, k))
        a @ b  # warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            a @ b
            best = min(best, time.perf_counter() - t0)
        flops = 2.0 * k**3 / max(best, 1e-9)
        src = np.zeros(8 << 20, dtype=np.int8)  # 8 MiB
        np.copyto(np.empty_like(src), src)  # warm
        best_bw = float("inf")
        for _ in range(3):
            dst = np.empty_like(src)
            t0 = time.perf_counter()
            np.copyto(dst, src)
            best_bw = min(best_bw, time.perf_counter() - t0)
        bw = 2.0 * src.nbytes / max(best_bw, 1e-9)  # read + write
        return {"flops_per_s": flops, "bytes_per_s": bw, "source": "measured"}
    except Exception:
        return None


def substrate_peaks() -> Optional[dict]:
    """Peak FLOP/s + memory bandwidth of the current substrate, or None.

    Known accelerator kinds answer from :data:`KNOWN_PEAKS` (a TPU kind that
    is not listed raises ``LookupError``); a non-TPU substrate (XLA:CPU)
    is measured once by a tiny numpy micro-benchmark and
    cached to ``MODIN_TPU_CACHE_DIR`` per platform so later processes skip
    the measurement.  None means "no basis for a roofline" — consumers
    render the fraction as unknown rather than invent one.
    """
    global _peaks_cache
    if _peaks_cache is not None:
        return _peaks_cache or None
    with _peaks_lock:
        if _peaks_cache is not None:
            return _peaks_cache or None
        peaks: Optional[dict] = None
        platform = "unknown"
        kind = ""
        try:
            import jax

            device = jax.devices()[0]
            platform = device.platform
            kind = getattr(device, "device_kind", "")
            for known, (flops, bw) in KNOWN_PEAKS.items():
                if kind and known.lower() in str(kind).lower():
                    peaks = {
                        "flops_per_s": flops,
                        "bytes_per_s": bw,
                        "source": f"spec:{known}",
                    }
                    break
        except Exception:
            pass
        if peaks is None and platform == "tpu":
            # a host micro-benchmark is no roofline for a chip: an
            # accelerator kind missing from the table is an error, not a
            # default (add its published peaks to KNOWN_PEAKS)
            raise LookupError(
                f"no published peaks for TPU device kind {kind!r} in "
                "costs.KNOWN_PEAKS"
            )
        if peaks is None:
            peaks = _load_cached_peaks(platform)
        if peaks is None:
            peaks = _measure_host_peaks()
            if peaks is not None:
                _store_cached_peaks(platform, peaks)
        _peaks_cache = peaks if peaks is not None else {}
        return peaks


def _peaks_path(platform: str) -> Optional[str]:
    # persistence rides the consolidated calibration store (ops/
    # calibration.py); the name stays byte-compatible with the
    # pre-consolidation layout so warmed caches survive the refactor
    from modin_tpu.ops import calibration as calstore

    return calstore.table_path("roofline", platform)


def _load_cached_peaks(platform: str) -> Optional[dict]:
    from modin_tpu.ops import calibration as calstore

    peaks = calstore.load_table(_peaks_path(platform))
    if (
        isinstance(peaks, dict)
        and peaks.get("flops_per_s", 0) > 0
        and peaks.get("bytes_per_s", 0) > 0
    ):
        return peaks
    return None


def _store_cached_peaks(platform: str, peaks: dict) -> None:
    from modin_tpu.ops import calibration as calstore

    calstore.store_table(_peaks_path(platform), peaks)


def roofline_fraction(
    flops: Optional[float], bytes_accessed: Optional[float], wall_s: float
) -> Optional[float]:
    """Achieved fraction of the roofline-attainable rate for this program.

    ``min(peak_flops, intensity * peak_bw)`` is the classic attainable
    ceiling at the program's arithmetic intensity; the fraction is achieved
    FLOP/s over that.  For a pure-movement program (zero flops) the
    fraction is achieved bandwidth over peak bandwidth.  None when wall or
    the needed estimates are unknown.
    """
    if wall_s <= 0:
        return None
    peaks = substrate_peaks()
    if peaks is None:
        return None
    peak_flops = peaks["flops_per_s"]
    peak_bw = peaks["bytes_per_s"]
    if flops is not None and flops > 0:
        if bytes_accessed is not None and bytes_accessed > 0:
            intensity = flops / bytes_accessed
            attainable = min(peak_flops, intensity * peak_bw)
        else:
            attainable = peak_flops
        return (flops / wall_s) / attainable
    if bytes_accessed is not None and bytes_accessed > 0:
        return (bytes_accessed / wall_s) / peak_bw
    return None


# wire the config switch (fires immediately with its current value)
from modin_tpu.config import CostCapture as _CostCapture  # noqa: E402

_CostCapture.subscribe(_on_cost_param)
