"""graftcost perf-history ledger: the perf trajectory as a checked artifact.

PERF.md has been hand-maintained since round 6 — a human reads
``BENCH_r0N.json``, transcribes numbers, and nothing notices when a number
regresses or a table drifts from its source.  This module makes the
history itself machine-owned:

- **Ledger** (``PERF_HISTORY.json``): one canonical, deterministic JSON
  document of every recorded bench run — per-op wall/speedup, per-section
  results, and run provenance (git SHA, substrate, jax/pandas versions,
  row-scale config).  Seeded from ``BENCH_r01..r05`` with provenance
  backfilled from ROUND_NOTES.md (honest ``unknown (backfilled)`` where the
  driver environment was never recorded).

- **Regeneration**: the per-op tables in PERF.md between the
  ``perf-history:begin/end`` markers are produced from the ledger,
  byte-deterministically — regenerating twice yields identical bytes, and
  the smoke gate fails when the committed PERF.md drifts from the committed
  ledger.

- **Regression gate**: folding a new run compares every op wall against the
  best recorded wall for the same ``(op, substrate, scale)`` key; a
  regression beyond ``MODIN_TPU_PERF_GATE_TOLERANCE`` (default 1.5x) fails
  the fold the way ``metrics_smoke.py`` fails on a dispatch-count
  regression.  Scale is part of the key on purpose: a reduced-scale smoke
  run must never be compared against a full-scale round (or vice versa).

The CLI lives in ``scripts/perf_history.py``; this module is pure logic so
tests can drive it without subprocesses.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

SCHEMA = 1

BEGIN_MARKER = (
    "<!-- perf-history:begin — generated by scripts/perf_history.py from "
    "PERF_HISTORY.json; edit the ledger, not these tables -->"
)
END_MARKER = "<!-- perf-history:end -->"

#: Provenance backfill for the pre-ledger rounds (BENCH_r01..r05).  The
#: driver environment of those rounds was never recorded; the honest entry
#: is "unknown (backfilled)", with what ROUND_NOTES.md / the round files do
#: establish (substrate, op-set caveats) filled in.
_BACKFILL_PROVENANCE: Dict[str, Dict[str, str]] = {
    "r01": {
        "git_sha": "unknown (pre-ledger backfill)",
        "jax": "unknown (backfilled)",
        "pandas": "unknown (backfilled)",
        "note": "seed round; add=df+df / mul=df*2 on float64 (r05 moved to "
        "the reference's flex scalar forms on int64 — compare per-op "
        "with care), groupby numbers warm-memo",
    },
    "r04": {
        "git_sha": "unknown (pre-ledger backfill)",
        "jax": "unknown (backfilled)",
        "pandas": "unknown (backfilled)",
        "note": "CPU substrate (no accelerator attached); ewm_mean added to the "
        "aggregate, breaking cross-round aggregate comparability "
        "(VERDICT r4); gb_size is a warm-memo artifact (1951x), not a "
        "kernel number",
    },
    "r05": {
        "git_sha": "unknown (pre-ledger backfill)",
        "jax": "unknown (backfilled)",
        "pandas": "unknown (backfilled)",
        "note": "rc=124 with zero output — the failure mode PR 5's "
        "streamed-line protocol made structurally unshippable",
    },
}


def normalize_substrate(platform: Optional[str]) -> str:
    """``"tpu"`` / ``"cpu"`` / ``"unknown"`` from a bench platform string."""
    if not platform:
        return "unknown"
    p = str(platform).lower()
    if p.startswith("tpu"):
        return "tpu"
    if p.startswith("cpu"):
        return "cpu"
    return "unknown"


#: which scale field sizes each op family (bench.py sections measure their
#: ops at different row counts within ONE run); checked in order, first
#: match wins, headline ``rows`` otherwise.  The udf set must precede the
#: endswith-"1" axis1 rule (``apply1``/``agg1`` are udf-shaped).
_UDF_OPS = frozenset({"apply0", "agg0", "apply1", "agg1", "transpose"})


def _op_scale_field(op: str) -> Optional[str]:
    if op.startswith("gs_"):
        return "sort_rows"
    if op.startswith("spmd_"):
        # graftmesh section ops (spmd_sort_sharded / spmd_merge_local /
        # ...) are measured at the spmd section's own row scale — and
        # op_scale_key additionally folds the mesh shape in (below)
        return "spmd_rows"
    if op.startswith("serving_"):
        # graftgate concurrent-section latency percentiles (serving_p50 /
        # serving_p99 / serving_uncontended_p99) are measured at the
        # serving section's own row scale
        return "serving_rows"
    if op.startswith("oocore_"):
        # graftstream section ops (oocore_stream / oocore_serial /
        # oocore_resident) are measured at the oocore section's own row
        # scale — and op_scale_key additionally folds the window size in
        # (below)
        return "oocore_rows"
    if op.startswith("fusion_"):
        # graftfuse section ops (fusion_fused / fusion_staged /
        # fusion_eager) are measured at the fusion section's own row scale
        # — and op_scale_key folds the compile mode in (below)
        return "fuse_rows"
    if op.startswith("view_"):
        # graftview section ops (view_cold / view_warm / view_fold /
        # view_serving) are measured at the graftview section's own row
        # scale — and op_scale_key folds the cache leg in (below) so a
        # warm artifact-hit wall never gates against a cold wall
        return "view_rows"
    if op.startswith("fleet_"):
        # graftfleet section ops (fleet_routed_p50 / fleet_routed_p99 /
        # fleet_local_p50 / fleet_mttr / fleet_redistributed_p99) are
        # measured at the fleet section's own row scale — and
        # op_scale_key folds the replica count in (below)
        return "fleet_rows"
    if op.startswith("ingest_"):
        # graftfeed section ops (ingest_sustained_fast / _slow / _read,
        # ingest_freshness_p99, ingest_maintained_read /
        # ingest_recompute_read) are measured at the ingest section's own
        # total-row scale — and op_scale_key folds the leg in (below) so
        # a maintained-artifact read wall never gates against a
        # recompute-from-scratch wall
        return "ingest_rows"
    if op.startswith("durability_"):
        # graftwal section ops (durability_ingest_off / _group /
        # _perbatch, durability_recovery) are measured at the durability
        # section's own row scale — and op_scale_key folds the fsync mode
        # in (below) so a fsync-per-batch ingest wall never gates against
        # a no-fsync wall
        return "durability_rows"
    if op.startswith("optimizer_"):
        # graftopt section ops (optimizer_auto / optimizer_off /
        # optimizer_forced_* / optimizer_adversarial) are measured at the
        # optimizer section's own row scale — and op_scale_key folds the
        # optimization mode in (below)
        return "optimizer_rows"
    if op in _UDF_OPS:
        return "udf_rows"
    if op == "mode1":
        return "mode1_rows"
    if op.endswith("1"):
        return "axis1_rows"
    return None


def op_scale_key(run: dict, op: str) -> str:
    """The row-scale key regression comparisons use for one op.

    Keyed on the row count the op was actually measured at — NOT the whole
    scale config: a run differing only in repeats/meters must still be
    compared, and the backfilled r01-r05 rounds (which recorded only the
    headline row count) must remain comparable baselines for a new
    full-default-scale run.
    """
    scale = run.get("scale")
    scale = scale if isinstance(scale, dict) else {}
    rows = scale.get("rows", run.get("rows"))
    field = _op_scale_field(op)
    if field is not None and field in scale:
        rows = scale[field]
    key = f"rows={rows}" if rows is not None else "rows=unknown"
    if op.startswith("spmd_"):
        # the mesh shape is part of the workload identity for collective
        # ops: a 1-device wall and an 8-device wall of the "same" op are
        # different substrates and must never gate against each other.
        # scale.spmd_mesh is a {mode: "SxC"} map (the bench section's legs
        # run on different topologies — the "single" leg is (1,1)); the
        # op's trailing _<mode> segment selects its own.  A plain string
        # (older streams) applies to every spmd op.
        mesh = scale.get("spmd_mesh", "unknown")
        if isinstance(mesh, dict):
            mesh = mesh.get(op.rsplit("_", 1)[-1], "unknown")
        key = f"{key}@mesh={mesh}"
    if op.startswith("oocore_"):
        # the window size is part of the workload identity for streaming
        # ops (mirroring the spmd mesh key): a windowed wall at 64 KB
        # windows and a resident wall of the "same" op must never gate
        # against each other.  The resident leg records window=resident.
        window = scale.get("oocore_window", "unknown")
        if isinstance(window, dict):
            window = window.get(op.rsplit("_", 1)[-1], "unknown")
        key = f"{key}@window={window}"
    if op.startswith("serving_"):
        # the graftwatch sampler is part of the workload identity for the
        # serving latency percentiles: the bench's watch_on leg runs the
        # same saturation workload with live telemetry, and its walls
        # (serving_watch_*) must never gate against the watch-off walls
        key = (
            f"{key}@watch=on"
            if op.startswith("serving_watch_")
            else f"{key}@watch=off"
        )
    if op.startswith("fusion_"):
        # the compile mode is part of the workload identity for graftfuse
        # ops: a one-program fused wall and a two-dispatch staged wall of
        # the "same" pipeline must never gate against each other (the
        # op's trailing segment IS its mode)
        key = f"{key}@fuse={op.rsplit('_', 1)[-1]}"
    if op.startswith("view_"):
        # the cache leg is part of the workload identity for graftview
        # ops: a cold from-scratch wall, a warm artifact-hit wall, and an
        # incremental-fold wall of the "same" query are different
        # workloads and must never gate against each other (the op's
        # trailing segment IS its leg)
        key = f"{key}@view={op.rsplit('_', 1)[-1]}"
    if op.startswith("fleet_"):
        # the replica count is part of the workload identity for fleet
        # ops (mirroring the spmd mesh key): a 2-replica routed p99 and
        # a 4-replica one are different fleets and must never gate
        # against each other.  The local (single-process) legs record
        # replicas=local.
        replicas = scale.get("fleet_replicas", "unknown")
        if op.startswith("fleet_local_"):
            replicas = "local"
        key = f"{key}@replicas={replicas}"
    if op.startswith("ingest_"):
        # the leg is part of the workload identity for graftfeed ops: a
        # maintained-artifact read, a recompute-from-scratch read, a
        # fast-path append wall, and a re-layout append wall of the
        # "same" stream are different workloads and must never gate
        # against each other (everything after the ingest_ prefix IS
        # the leg — maintained_read and recompute_read stay distinct)
        key = f"{key}@ingest={op[len('ingest_'):]}"
    if op.startswith("durability_"):
        # the fsync mode is part of the workload identity for graftwal
        # ops: a per-batch-fsync ingest wall, a group-commit wall, a
        # no-fsync wall, and the recovery-replay wall of the "same"
        # stream are different durability contracts and must never gate
        # against each other (everything after the durability_ prefix IS
        # the leg)
        key = f"{key}@fsync={op[len('durability_'):]}"
    if op.startswith("optimizer_"):
        # the optimization mode is part of the workload identity for
        # graftopt ops: the adaptive Auto wall, the Off five-router wall,
        # each forced single-strategy leg, and the adversarial
        # miscalibrated-then-recovered wall of the "same" pipeline are
        # different contracts and must never gate against each other
        # (everything after the optimizer_ prefix IS the mode)
        key = f"{key}@opt={op[len('optimizer_'):]}"
    return key


# ---------------------------------------------------------------------- #
# ledger IO
# ---------------------------------------------------------------------- #


def empty_ledger() -> dict:
    return {"schema": SCHEMA, "runs": []}


def load_ledger(path: str) -> dict:
    with open(path) as f:
        ledger = json.load(f)
    if ledger.get("schema") != SCHEMA:
        raise ValueError(
            f"PERF_HISTORY schema {ledger.get('schema')!r} != {SCHEMA} "
            f"({path}); regenerate with scripts/perf_history.py seed"
        )
    return ledger


def dump_ledger(ledger: dict) -> str:
    """Canonical serialization: stable key order, 2-space indent, trailing
    newline — byte-identical for equal content, diff-friendly in git."""
    return json.dumps(ledger, indent=2, sort_keys=True) + "\n"


def save_ledger(ledger: dict, path: str) -> None:
    # atomic: a crash mid-fold must never leave a truncated ledger that
    # poisons every future perf-gate comparison
    from modin_tpu.utils.atomic_io import atomic_write_text

    atomic_write_text(path, dump_ledger(ledger))


# ---------------------------------------------------------------------- #
# seeding from the BENCH_r0N round files
# ---------------------------------------------------------------------- #


def seed_run_from_round_file(path: str) -> dict:
    """One ledger run entry from a driver round file (BENCH_r0N.json)."""
    with open(path) as f:
        doc = json.load(f)
    n = doc.get("n")
    run_id = f"r{int(n):02d}" if isinstance(n, int) else os.path.basename(path)
    parsed = doc.get("parsed") or {}
    provenance = dict(_BACKFILL_PROVENANCE.get(run_id, {}))
    provenance["substrate"] = normalize_substrate(parsed.get("platform"))
    run: Dict[str, Any] = {
        "run": run_id,
        "source": os.path.basename(path),
        "rc": doc.get("rc"),
        "rows": parsed.get("rows"),
        "provenance": provenance,
        "ops": {},
    }
    detail = parsed.get("detail") or {}
    for op in sorted(detail):
        entry = detail[op]
        if not isinstance(entry, dict) or "modin_tpu_s" not in entry:
            continue
        run["ops"][op] = {
            "modin_tpu_s": entry["modin_tpu_s"],
            "pandas_s": entry.get("pandas_s"),
            "speedup": entry.get("speedup"),
        }
    if not run["ops"] and doc.get("rc") not in (0, None):
        run["failed"] = True
    return run


def seed_ledger(repo_root: str) -> dict:
    """The initial ledger from every ``BENCH_r0*.json`` in ``repo_root``."""
    import glob

    ledger = empty_ledger()
    for path in sorted(glob.glob(os.path.join(repo_root, "BENCH_r0*.json"))):
        ledger["runs"].append(seed_run_from_round_file(path))
    return ledger


# ---------------------------------------------------------------------- #
# folding a streamed bench run
# ---------------------------------------------------------------------- #


def parse_bench_stream(text: str) -> dict:
    """A ledger run entry from one ``bench.py`` stdout stream.

    The stream is one JSON object per line: section lines (each carrying
    ``run_provenance`` since r08) and a final aggregate with the per-op
    ``detail``.  A truncated run (no aggregate) still folds: its completed
    section lines are the record, with ``truncated: true``.
    """
    sections: Dict[str, dict] = {}
    aggregate: Optional[dict] = None
    provenance: Dict[str, Any] = {}
    for raw in text.splitlines():
        raw = raw.strip()
        if not raw:
            continue
        line = json.loads(raw)
        if "run_provenance" in line and not provenance:
            provenance = dict(line["run_provenance"])
        if "section" in line:
            sections[line["section"]] = {
                k: v for k, v in line.items()
                if k not in ("run_provenance",)
            }
        else:
            aggregate = line
    run: Dict[str, Any] = {
        "provenance": {
            k: v for k, v in provenance.items() if k != "scale"
        },
        "scale": provenance.get("scale"),
        "sections": {
            name: {
                k: v for k, v in sec.items()
                if k in ("elapsed_s", "error", "skipped")
            }
            for name, sec in sorted(sections.items())
        },
        "ops": {},
    }
    if aggregate is not None:
        run["rows"] = aggregate.get("rows")
        detail = aggregate.get("detail") or {}
        for op in sorted(detail):
            entry = detail[op]
            if isinstance(entry, dict) and "modin_tpu_s" in entry:
                run["ops"][op] = {
                    "modin_tpu_s": entry["modin_tpu_s"],
                    "pandas_s": entry.get("pandas_s"),
                    "speedup": entry.get("speedup"),
                }
    else:
        run["truncated"] = True
    return run


def run_substrate(run: dict) -> str:
    return normalize_substrate(
        (run.get("provenance") or {}).get("substrate")
    )


def check_regression(
    ledger: dict, run: dict, tolerance: Optional[float] = None
) -> List[str]:
    """Failure messages for every op wall in ``run`` that regresses beyond
    ``tolerance`` vs the best recorded same-(op, substrate, scale) wall.

    An op with no comparable history passes trivially (first evidence IS
    the baseline).  A wall within the absolute noise floor
    (MODIN_TPU_PERF_GATE_NOISE_FLOOR_S) of the best also passes: sub-ms
    walls are timer-jitter-dominated, so the ratio alone is not signal.
    Empty return = gate green.
    """
    if tolerance is None:
        tolerance = _gate_tolerance()
    noise_floor = _gate_noise_floor_s()
    substrate = run_substrate(run)
    best: Dict[Tuple[str, str], Tuple[float, str]] = {}
    for prior in ledger.get("runs", []):
        if run_substrate(prior) != substrate:
            continue
        for op, entry in (prior.get("ops") or {}).items():
            wall = entry.get("modin_tpu_s")
            if not isinstance(wall, (int, float)) or wall <= 0:
                continue
            key = (op, op_scale_key(prior, op))
            if key not in best or wall < best[key][0]:
                best[key] = (float(wall), prior.get("run", "?"))
    failures = []
    for op, entry in sorted((run.get("ops") or {}).items()):
        wall = entry.get("modin_tpu_s")
        scale = op_scale_key(run, op)
        if not isinstance(wall, (int, float)) or (op, scale) not in best:
            continue
        best_wall, best_run = best[(op, scale)]
        if wall > best_wall * tolerance and wall - best_wall > noise_floor:
            failures.append(
                f"perf regression: {op} wall {wall:.4f}s is "
                f"{wall / best_wall:.2f}x the best recorded "
                f"{best_wall:.4f}s ({best_run}, substrate={substrate}, "
                f"{scale}; tolerance {tolerance:g}x — "
                "MODIN_TPU_PERF_GATE_TOLERANCE)"
            )
    return failures


def _gate_tolerance() -> float:
    try:
        from modin_tpu.config import PerfGateTolerance

        return float(PerfGateTolerance.get())
    except Exception:
        return 1.5


def _gate_noise_floor_s() -> float:
    try:
        from modin_tpu.config import PerfGateNoiseFloorS

        return float(PerfGateNoiseFloorS.get())
    except Exception:
        return 0.005


def fold_run(
    ledger: dict,
    run: dict,
    run_id: str,
    gate: bool = True,
    tolerance: Optional[float] = None,
) -> List[str]:
    """Gate ``run`` against ``ledger`` then append it (even when the gate
    fails: a regression should be ON the record, visibly, not suppressed).
    Returns the gate failures."""
    failures = check_regression(ledger, run, tolerance) if gate else []
    entry = dict(run)
    entry["run"] = run_id
    if failures:
        entry["gate_failures"] = failures
    existing = [r.get("run") for r in ledger["runs"]]
    if run_id in existing:
        raise ValueError(f"run id {run_id!r} already recorded in the ledger")
    ledger["runs"].append(entry)
    return failures


def next_run_id(ledger: dict, prefix: str = "run") -> str:
    """A fresh, deterministic run id: ``<prefix>-NNN`` one past the highest
    recorded for that prefix."""
    highest = 0
    for run in ledger.get("runs", []):
        rid = str(run.get("run", ""))
        if rid.startswith(prefix + "-"):
            try:
                highest = max(highest, int(rid.rsplit("-", 1)[1]))
            except ValueError:
                pass
    return f"{prefix}-{highest + 1:03d}"


# ---------------------------------------------------------------------- #
# PERF.md regeneration
# ---------------------------------------------------------------------- #


def _fmt_wall(value: Any) -> str:
    return f"{value:.4f}" if isinstance(value, (int, float)) else "?"


def _fmt_speedup(value: Any) -> str:
    return f"{value:.2f}×" if isinstance(value, (int, float)) else "?"


def render_tables(ledger: dict) -> str:
    """The generated per-op section: best + latest evidence per
    (op, substrate, scale), sorted, fixed formatting — deterministic."""
    rows: Dict[Tuple[str, str, str], dict] = {}
    for run in ledger.get("runs", []):
        substrate = run_substrate(run)
        run_id = str(run.get("run", "?"))
        for op, entry in (run.get("ops") or {}).items():
            wall = entry.get("modin_tpu_s")
            if not isinstance(wall, (int, float)):
                continue
            slot = rows.setdefault(
                (op, substrate, op_scale_key(run, op)),
                {"best": None, "best_run": None, "latest": None,
                 "latest_run": None, "best_speedup": None},
            )
            if slot["best"] is None or wall < slot["best"]:
                slot["best"] = wall
                slot["best_run"] = run_id
                slot["best_speedup"] = entry.get("speedup")
            # runs append in fold order; the last writer is the latest
            slot["latest"] = wall
            slot["latest_run"] = run_id
            slot["latest_speedup"] = entry.get("speedup")
    lines = [
        "### Per-op evidence ledger (generated)",
        "",
        "Best and latest recorded modin_tpu wall per (op, substrate,",
        "scale); speedup is vs pandas on the same machine (higher is",
        "better).  Source of truth: `PERF_HISTORY.json` — regenerate with",
        "`python scripts/perf_history.py regen`, fold new runs with",
        "`python scripts/perf_history.py fold <bench-stream.jsonl>`.",
        "",
        "| op | substrate | scale | best wall (s) | best speedup | best run "
        "| latest wall (s) | latest speedup | latest run |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for (op, substrate, key), slot in sorted(rows.items()):
        lines.append(
            f"| {op} | {substrate} | {key} | {_fmt_wall(slot['best'])} | "
            f"{_fmt_speedup(slot['best_speedup'])} | {slot['best_run']} | "
            f"{_fmt_wall(slot['latest'])} | "
            f"{_fmt_speedup(slot.get('latest_speedup'))} | "
            f"{slot['latest_run']} |"
        )
    lines.append("")
    recorded = [
        f"{run.get('run', '?')} ({run_substrate(run)}"
        + (", FAILED rc" if run.get("failed") else "")
        + (", truncated" if run.get("truncated") else "")
        + (", GATE-RED" if run.get("gate_failures") else "")
        + ")"
        for run in ledger.get("runs", [])
    ]
    lines.append(f"Recorded runs: {', '.join(recorded) or 'none'}.")
    return "\n".join(lines)


def regenerate_perf_md(ledger: dict, perf_md_text: str) -> str:
    """Replace the marked region of PERF.md with the generated tables.

    Raises when the markers are missing/misordered — a silent no-op regen
    would defeat the determinism check.
    """
    begin = perf_md_text.find(BEGIN_MARKER)
    end = perf_md_text.find(END_MARKER)
    if begin < 0 or end < 0 or end < begin:
        raise ValueError(
            "PERF.md is missing the perf-history markers; re-add "
            f"{BEGIN_MARKER!r} ... {END_MARKER!r}"
        )
    head = perf_md_text[: begin + len(BEGIN_MARKER)]
    tail = perf_md_text[end:]
    return head + "\n\n" + render_tables(ledger) + "\n\n" + tail
