"""graftsort kernel router: substrate-aware device/host dispatch for the
sort-shaped reduction families (median / quantile / nunique / mode).

VERDICT r5 measured the device sort-shaped kernels losing 13-23x to pandas
on the CPU substrate (an XLA:CPU single-core sort against pandas' optimized
selection/hash kernels) while the framework happily ran them anyway: device
paths were gated on dtype/shape, never on *where the kernel would run*.
This module is the repo's per-op analogue of the reference's backend cost
calculator (QCCoercionCost, reference
modin/core/storage_formats/base/query_compiler.py:116) and the cost-aware
rewriting Dias argues for (PAPERS.md): each sort-shaped ``_try_*`` family
asks ``decide()`` whether the device kernel or the pandas host kernel is
predicted faster at the observed (rows, per-column strategy, substrate),
and declines to the existing ``device_path`` fallback seam when the host
wins.

The model is seeded by a **one-shot calibration**: four device micro-kernels
(sort, sorted-consume, histogram) and four host kernels (pandas median /
quantile / nunique / mode) are timed at ``KernelRouterCalibrationRows`` and
the per-row coefficients cached to ``CacheDir`` per substrate, so the cost
is paid once per machine.  Scaling: sorts grow n·log n, everything else
linearly.  Decisions are observable: every ``decide()`` emits a
``router.<op>.<choice>`` metric and a ``router.decide`` span carrying the
predicted costs, so a graftscope trace shows *why* a path was chosen.

Knobs (config/envvars.py): ``MODIN_TPU_KERNEL_ROUTER`` (auto|device|host),
``MODIN_TPU_KERNEL_ROUTER_MIN_ROWS`` (below it, auto == device and the
calibration never runs — unit-test frames stay on device, deterministic),
``MODIN_TPU_KERNEL_ROUTER_HIST_BOUND``,
``MODIN_TPU_KERNEL_ROUTER_CALIBRATION_ROWS``.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from modin_tpu.concurrency import named_lock
from modin_tpu.logging.metrics import emit_metric
from modin_tpu.observability import spans as graftscope
from modin_tpu.ops import calibration as calstore
from modin_tpu.ops._program import named_jit

#: column strategies a sort-shaped plan may carry (see plan_strategies in
#: ops/reductions.py): "dict" costs ~0 (host categories already known),
#: "view" costs 0 on device (a graftview whole-result artifact already
#: holds the answer — flipping the crossover exactly like the sorted-rep
#: amortization leg, one stage further), "cached" consumes an existing
#: sorted representation, "hist" is the O(n) segment-sum path, "sort" pays
#: the full O(n log n) device sort
STRATEGIES = ("dict", "view", "cached", "hist", "sort")

#: predicted device-minus-host savings (seconds) the host side must clear
#: before auto routing declines a device path: below this the decision is
#: noise and device residency wins ties
MIN_SAVINGS_S = 0.05

_CAL_VERSION = 3

#: graftopt consult hook.  ``plan/optimizer.py`` installs a callable here
#: while ``MODIN_TPU_OPT=Auto`` (and clears it for Off): each ``decide_*``
#: offers its live verdict — ``(leg, choice, reason, **ctx)`` — and the
#: optimizer answers a replacement ``(choice, reason)`` from the current
#: node's plan-time strategy annotation, or None to keep the router's own.
#: A module attribute rather than an import so the Off mode costs exactly
#: one ``is not None`` check per decision and allocates nothing.
_opt_consult = None

#: baseline reasons the optimizer may override: forced modes and the
#: deterministic row floors stay authoritative (tests and bench legs pin
#: sides; tiny frames never consult plan-time state), as do the
#: degenerate single_shard / no_budget / uncalibrated outcomes.
_OPT_REASONS = frozenset({"auto", "cost_model", "fits", "over_headroom"})

_lock = named_lock("ops.router_calibration")
#: None = not yet resolved; False = calibration failed (route device);
#: dict = live table
_calibration: Any = None
#: the mesh shape the lazy resolution (success OR failure) belongs to —
#: an in-process reshape re-resolves both outcomes, not just tables
_calibration_mesh: Optional[str] = None
#: a table installed by set_calibration is honored verbatim (tests force
#: crossovers); a lazily-resolved one is re-resolved when the mesh reshapes
_calibration_forced = False


def set_calibration(table: Optional[Dict[str, float]]) -> None:
    """Force the calibration table (tests) or reset to lazy (None)."""
    global _calibration, _calibration_forced, _calibration_mesh
    with _lock:
        _calibration = table if table is not None else None
        _calibration_forced = table is not None
        _calibration_mesh = None


def _platform() -> str:
    import jax

    try:
        return jax.devices()[0].platform
    except Exception:  # graftlint: disable=EXC-HYGIENE -- no backend at all: calibration is meaningless, the caller records a failed table and routes device
        return "unknown"


def _mesh_key() -> str:
    from modin_tpu.parallel.mesh import mesh_shape_key

    try:
        return mesh_shape_key()
    except Exception:  # graftlint: disable=EXC-HYGIENE -- no backend/mesh at all: calibration is keyed 'unknown' and the sharded entries are simply absent
        return "unknown"


def _cache_path(platform: str, mesh_key: str) -> Optional[str]:
    return calstore.table_path(
        "kernel_router", platform, mesh_key=mesh_key, version=_CAL_VERSION
    )


def _time_best(fn, reps: int = 2) -> float:
    """Best-of wall time of ``fn()`` after one untimed warmup (compile)."""
    fn()
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _measure() -> Dict[str, float]:
    """Time the per-family micro-kernels at the calibration size.

    Host kernels are timed in BOTH cardinality regimes: pandas'
    hash-based nunique/mode are up to ~40x faster per row on
    low-cardinality data (exactly the columns the device answers with a
    histogram) than on all-distinct data (the columns that need a sort),
    so one coefficient per op would systematically mis-predict one regime.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pandas

    from modin_tpu.config import KernelRouterCalibrationRows

    rows = int(KernelRouterCalibrationRows.get())
    rng = np.random.default_rng(0)
    wide = rng.integers(0, 1 << 40, rows)  # ~all-distinct: the sort case
    narrow = rng.integers(0, 1024, rows)  # low-cardinality: the hist case

    dev_wide = jnp.asarray(wide)
    dev_narrow_idx = jnp.asarray(narrow.astype(np.int32))

    sort_fn = named_jit(jnp.sort, "router_calibrate_sort")
    consume_fn = named_jit(
        lambda xs: jnp.sum(
            jnp.concatenate([jnp.ones(1, bool), xs[1:] != xs[:-1]])
        ),
        "router_calibrate_consume",
    )
    hist_fn = named_jit(
        lambda idx: jnp.zeros(1025, jnp.int64).at[idx].add(1),
        "router_calibrate_hist",
    )

    sorted_dev = sort_fn(dev_wide)
    table = {
        "version": _CAL_VERSION,
        "platform": _platform(),
        "mesh": _mesh_key(),
        "rows": rows,
        "device_sort_s": _time_best(
            lambda: np.asarray(sort_fn(dev_wide))
        ),
        "device_consume_s": _time_best(
            lambda: np.asarray(consume_fn(sorted_dev))
        ),
        "device_hist_s": _time_best(
            lambda: np.asarray(hist_fn(dev_narrow_idx))
        ),
    }
    for regime, values in (("high", wide), ("low", narrow)):
        host = pandas.Series(values)
        table[f"host_median_{regime}_s"] = _time_best(lambda: host.median())
        table[f"host_quantile_{regime}_s"] = _time_best(
            lambda: host.quantile(0.5)
        )
        table[f"host_nunique_{regime}_s"] = _time_best(lambda: host.nunique())
        table[f"host_mode_{regime}_s"] = _time_best(lambda: host.mode())
    _measure_sharded(table, rows, wide)
    return table


def _measure_sharded(table: Dict[str, Any], rows: int, wide: Any) -> None:
    """graftmesh calibration entries, only meaningful on a >= 2-shard mesh:

    - ``device_shuffle_s``: the full sharded sort (sample -> pivots ->
      all_to_all -> per-shard local sort -> compaction) at the calibration
      size with one payload column — the end-to-end cost ``decide_layout``
      scales by n log n against the local ``device_sort_s``;
    - ``collective_bytes_per_s``: a bare tiled ``all_to_all`` round over
      the same volume, giving the interconnect term extra payload columns
      are billed at (the ``engine.cost.collective_bytes`` coefficient).

    Any failure leaves the entries absent: ``decide_layout`` then answers
    "local"/uncalibrated, never crashes.
    """
    from modin_tpu.parallel.mesh import get_mesh, num_row_shards

    try:
        S = num_row_shards()
        if S < 2:
            return
        import jax
        import numpy as np

        from jax.sharding import PartitionSpec as P

        from modin_tpu.ops.structural import pad_host
        from modin_tpu.parallel import shuffle as _shuffle
        from modin_tpu.parallel.engine import JaxWrapper
        from modin_tpu.parallel.jax_compat import shard_map

        key_dev = JaxWrapper.put(pad_host(wide))
        payload = JaxWrapper.put(pad_host(wide))

        def run_shuffle() -> None:
            out = _shuffle.range_shuffle(
                key_dev, [payload], rows, local_sort=True
            )
            np.asarray(out[0])

        table["device_shuffle_s"] = _time_best(run_shuffle)

        mesh = get_mesh()
        cap = max(rows // max(S * S, 1), 8)

        def local_roundtrip(x):
            block = x.reshape(S, cap)
            recv = jax.lax.all_to_all(
                block, "rows", split_axis=0, concat_axis=0, tiled=True
            )
            return recv.reshape(-1)

        fn = named_jit(
            shard_map(
                local_roundtrip,
                mesh=mesh,
                in_specs=(P("rows"),),
                out_specs=P("rows"),
                check_vma=False,
            ),
            "router_calibrate_all_to_all",
        )
        data = JaxWrapper.put(np.zeros(S * S * cap, dtype=np.int64))
        wall = _time_best(lambda: np.asarray(fn(data)))
        moved_bytes = S * S * cap * 8
        if wall > 0:
            table["collective_bytes_per_s"] = moved_bytes / wall
    except Exception:  # graftlint: disable=EXC-HYGIENE -- sharded calibration is an optimization probe; absence of its entries keeps layout routing on the local default
        pass


def calibration_peek() -> Optional[Dict[str, float]]:
    """The calibration table if ALREADY resolved, never measuring.

    graftopt's plan-time cost model reads coefficients through this —
    planning must never trigger the one-shot device measurement (a
    dispatch storm inside someone's measured region); the runtime
    ``decide()`` keeps paying for resolution at its existing points.
    """
    with _lock:
        table = _calibration
    return table if isinstance(table, dict) else None


def get_calibration() -> Optional[Dict[str, float]]:
    """The calibration table: memory -> CacheDir -> one-shot measurement.

    Returns None when calibration is impossible (the caller routes device,
    the pre-router behavior); the failure is remembered so a broken
    substrate is probed once, not per decision.
    """
    global _calibration, _calibration_mesh
    with _lock:
        if _calibration is not None:
            if _calibration_forced or _calibration_mesh == _mesh_key():
                return _calibration if _calibration is not False else None
            # mesh reshaped: the resolution — a table's sharded entries,
            # their absence, or a FAILURE — belongs to another topology
            _calibration = None
        platform = _platform()
        mesh_key = _mesh_key()
        path = _cache_path(platform, mesh_key)
        table = calstore.load_table(
            path, version=_CAL_VERSION, platform=platform, mesh_key=mesh_key
        )
        if table is not None:
            _calibration = table
            _calibration_mesh = mesh_key
            return table
        try:
            table = _measure()
            emit_metric("router.calibrate", 1)
        except Exception:  # graftlint: disable=EXC-HYGIENE -- calibration is an optimization probe; ANY failure (no backend, OOM at micro size) must leave routing on the pre-router device default
            _calibration = False
            _calibration_mesh = mesh_key
            return None
        _calibration = table
        _calibration_mesh = mesh_key
        calstore.store_table(path, table)
        return table


def predicted_costs(
    op: str, n: int, strategies: List[str], table: Dict[str, float]
) -> Dict[str, float]:
    """Predicted {device_s, host_s} for ``op`` over ``n`` rows with the
    given per-column strategies.  Linear scaling for everything except the
    sort term, which grows n*log2(n)."""
    cal_rows = max(int(table["rows"]), 2)
    scale = calstore.linear_scale(n, cal_rows)
    logscale = calstore.nlogn_scale(n, cal_rows)
    consume = table["device_consume_s"] * scale
    per_strategy = {
        "dict": 0.0,
        "view": 0.0,  # graftview result artifact: the answer is cached
        "cached": consume,
        "hist": table["device_hist_s"] * scale,
        "sort": table["device_sort_s"] * logscale + consume,
    }
    device_s = sum(per_strategy[s] for s in strategies)
    # host cost is cardinality-sensitive: hist/dict columns are the
    # low-cardinality regime pandas hashes fast, sort columns the slow one
    # (a view-cached column bills host at the slow regime: the host side
    # would have to recompute it from scratch)
    host_s = sum(
        table[
            f"host_{op}_{'low' if s in ('hist', 'dict') else 'high'}_s"
        ]
        for s in strategies
    ) * scale
    return {"device_s": device_s, "host_s": host_s}


def decide_layout(
    op: str, n: int, payload_cols: int = 0, itemsize: int = 8
) -> str:
    """"local" or "sharded" for one collective-eligible op over ``n`` rows.

    ``op`` names the kernel family (``sort`` for sort_values and the
    sorted-representation build, ``merge`` for the join's right-side sort);
    ``payload_cols`` counts the non-key columns the sharded path would move
    through the all_to_all (each is pure collective traffic the local path
    never pays).  The model: both sides scale n log n from their calibrated
    walls (``device_sort_s`` vs ``device_shuffle_s``), and payload columns
    beyond the calibration's single one are billed at the measured
    ``collective_bytes_per_s``.  Forced modes (``MODIN_TPU_SPMD``) and a
    single-shard mesh skip the model entirely — the router, not a flag, is
    the default decider, but tests and bench legs pin each side.

    Emitted as ``router.spmd_<op>.<choice>`` metrics and a
    ``router.decide`` span with the predicted costs.
    """
    from modin_tpu.config import SpmdMinRows, SpmdMode
    from modin_tpu.parallel.mesh import num_row_shards

    try:
        S = num_row_shards()
    except Exception:  # graftlint: disable=EXC-HYGIENE -- no backend: there is no mesh to shard over, the local path is the only path
        S = 1
    mode = SpmdMode.get().lower()
    costs: Dict[str, float] = {}
    if S < 2:
        choice, reason = "local", "single_shard"
    elif mode == "sharded":
        choice, reason = "sharded", "forced"
    elif mode == "local":
        choice, reason = "local", "forced"
    elif n < int(SpmdMinRows.get()):
        choice, reason = "local", "below_min_rows"
    else:
        table = get_calibration()
        if table is None or "device_shuffle_s" not in table:
            choice, reason = "local", "uncalibrated"
        else:
            logscale = calstore.nlogn_scale(n, int(table["rows"]))
            local_s = table["device_sort_s"] * logscale
            sharded_s = table["device_shuffle_s"] * logscale
            bw = float(table.get("collective_bytes_per_s") or 0.0)
            if bw > 0 and payload_cols > 1:
                # the calibration shuffled one payload column; each extra
                # one is (n rows + slack) of pure interconnect traffic
                sharded_s += (payload_cols - 1) * n * itemsize / bw
            costs = {"local_s": local_s, "sharded_s": sharded_s}
            choice = "sharded" if sharded_s < local_s else "local"
            reason = "cost_model"
    if _opt_consult is not None and reason in _OPT_REASONS:
        planned = _opt_consult("layout", choice, reason, op=op, n=n)
        if planned is not None:
            choice, reason = planned
    emit_metric(f"router.spmd_{op}.{choice}", 1)
    if graftscope.TRACE_ON:
        graftscope.finish_span(
            graftscope.start_span(
                "router.decide",
                layer="QUERY-COMPILER",
                attrs={
                    "op": f"spmd_{op}",
                    "n": n,
                    "choice": choice,
                    "reason": reason,
                    "payload_cols": payload_cols,
                    **{k: round(v, 6) for k, v in costs.items()},
                },
            )
        )
    return choice


def decide_residency(op: str, est_bytes: int, self_bytes: int = 0) -> str:
    """"resident" or "windowed" for one streaming-eligible op (graftstream).

    ``op`` names the family (``scan_reduce`` / ``scan_groupby`` for the
    windowed plan lowering, ``sort`` / ``merge`` for the external kernels);
    ``est_bytes`` is the op's estimated working-set (sniffed source size or
    frame bytes) and ``self_bytes`` the share of the device ledger the op's
    own inputs already occupy (subtracted so a frame is not counted against
    its own headroom).  Model: with ``MODIN_TPU_STREAM=Auto`` the op
    streams exactly when its estimate exceeds the ledger headroom —
    ``budget - other residents`` — under the configured device budget; no
    budget means resident always.  ``Resident``/``Windowed`` pin a side
    (tests, bench legs).

    Emitted as ``router.residency_<op>.<choice>`` metrics and a
    ``router.decide`` span with the estimate and headroom.
    """
    from modin_tpu.config import StreamMode
    from modin_tpu.core.memory import device_ledger

    mode = StreamMode.get().lower()
    headroom = None
    if mode == "resident":
        choice, reason = "resident", "forced"
    elif mode == "windowed":
        choice, reason = "windowed", "forced"
    else:
        budget = device_ledger.budget()
        if budget is None:
            choice, reason = "resident", "no_budget"
        else:
            headroom = budget - max(
                device_ledger.total_bytes() - max(int(self_bytes), 0), 0
            )
            if int(est_bytes) > headroom:
                choice, reason = "windowed", "over_headroom"
            else:
                choice, reason = "resident", "fits"
    if _opt_consult is not None and reason in _OPT_REASONS:
        planned = _opt_consult(
            "residency", choice, reason, op=op, est_bytes=int(est_bytes)
        )
        if planned is not None:
            choice, reason = planned
    emit_metric(f"router.residency_{op}.{choice}", 1)
    if graftscope.TRACE_ON:
        graftscope.finish_span(
            graftscope.start_span(
                "router.decide",
                layer="QUERY-COMPILER",
                attrs={
                    "op": f"residency_{op}",
                    "est_bytes": int(est_bytes),
                    "choice": choice,
                    "reason": reason,
                    **(
                        {"headroom_bytes": int(headroom)}
                        if headroom is not None
                        else {}
                    ),
                },
            )
        )
    return choice


def decide_compile(plan_sig: Any, n: int) -> str:
    """"fused" or "staged" for one whole-plan materialization (graftfuse).

    ``plan_sig`` is the stable segment signature (plan/fuse.py), carried
    into the decision span so a trace shows WHICH plan chose which leg;
    ``n`` is the leaf frame's logical row count.  The model is a floor,
    not a calibration: tracing + compiling a whole-plan XLA program costs
    milliseconds regardless of data size, so below
    ``MODIN_TPU_FUSE_MIN_ROWS`` the staged path's already-compiled per-op
    kernels win outright.  ``MODIN_TPU_FUSE`` pins a side (tests, bench
    legs).  Emitted as ``router.fuse.<choice>`` metrics and a
    ``router.decide`` span.
    """
    from modin_tpu.config import FuseMinRows, FuseMode

    mode = FuseMode.get().lower()
    if mode == "fused":
        choice, reason = "fused", "forced"
    elif mode == "staged":
        choice, reason = "staged", "forced"
    elif n < int(FuseMinRows.get()):
        choice, reason = "staged", "below_min_rows"
    else:
        choice, reason = "fused", "auto"
    if _opt_consult is not None and reason in _OPT_REASONS:
        planned = _opt_consult("compile", choice, reason, sig=plan_sig, n=n)
        if planned is not None:
            choice, reason = planned
    emit_metric(f"router.fuse.{choice}", 1)
    if graftscope.TRACE_ON:
        graftscope.finish_span(
            graftscope.start_span(
                "router.decide",
                layer="QUERY-COMPILER",
                attrs={
                    "op": "fuse",
                    "n": n,
                    "choice": choice,
                    "reason": reason,
                    "plan_sig": str(plan_sig),
                },
            )
        )
    return choice


def forced_host(op: str, n: int) -> bool:
    """True when routing is forced to Host: callers check this BEFORE any
    planning work (device materialization, the min/max histogram probe) so
    a substrate the operator declared device-bad pays zero device
    dispatches on the way to the pandas fallback.  Records the decision
    like any other (empty strategy list)."""
    from modin_tpu.config import KernelRouterMode

    if KernelRouterMode.get().lower() != "host":
        return False
    decide(op, n, [])
    return True


def decide(op: str, n: int, strategies: List[str]) -> str:
    """"device" or "host" for one sort-shaped op over ``n`` rows.

    ``op`` is the host-kernel family (median / quantile / nunique / mode);
    ``strategies`` carries one STRATEGIES entry per participating column.
    The decision is emitted as a ``router.<op>.<choice>`` metric and a
    ``router.decide`` span with the predicted costs.
    """
    from modin_tpu.config import KernelRouterMinRows, KernelRouterMode

    mode = KernelRouterMode.get().lower()
    costs: Dict[str, float] = {}
    if mode in ("device", "host"):
        choice, reason = mode, "forced"
    elif n < int(KernelRouterMinRows.get()):
        choice, reason = "device", "below_min_rows"
    else:
        table = get_calibration()
        if table is None:
            choice, reason = "device", "uncalibrated"
        else:
            costs = predicted_costs(op, n, strategies, table)
            if costs["device_s"] - costs["host_s"] > MIN_SAVINGS_S:
                choice, reason = "host", "cost_model"
            else:
                choice, reason = "device", "cost_model"
    if _opt_consult is not None and reason in _OPT_REASONS:
        planned = _opt_consult(
            "kernel", choice, reason, op=op, n=n, strategies=strategies
        )
        if planned is not None:
            choice, reason = planned
    emit_metric(f"router.{op}.{choice}", 1)
    if graftscope.TRACE_ON:
        graftscope.finish_span(
            graftscope.start_span(
                "router.decide",
                layer="QUERY-COMPILER",
                attrs={
                    "op": op,
                    "n": n,
                    "choice": choice,
                    "reason": reason,
                    "strategies": ",".join(strategies),
                    **{k: round(v, 6) for k, v in costs.items()},
                },
            )
        )
    return choice
