#!/usr/bin/env bash
# One-command multi-execution verification (VERDICT r4 item 6; mirrors the
# reference CI's one-run-per-engine matrix, .github/workflows/ci.yml:369-399):
#
#   ./scripts/check_all.sh            # all nineteen gates, fail on any red
#   FAST=1 ./scripts/check_all.sh     # -x (stop at first failure) per gate
#
# Gates:
#   0. graftlint: AST invariant checks (device/host seam, jit hazards,
#      fallback parity, exception hygiene, registry drift) — exits nonzero
#      on any new finding, printed as clickable path:line: RULE lines.
#      Intentional burn-downs: python -m modin_tpu.lint --baseline-write
#   0b. graftscope smoke: a tiny traced workload must export a
#       chrome://tracing-loadable JSON with spans from all four layers
#       (API, query compiler, engine seam, shuffle) and a rollup
#   0c. graftguard chaos smoke: a traced groupby+merge under an injected
#       mid-query DeviceLost must complete bit-exact with recovery.*
#       metrics > 0, and a RESOURCE_EXHAUSTED burst must be absorbed by
#       evict-then-retry without any pandas fallback
#   0e. graftplan smoke: read_csv(...).query(...)[cols].agg(...) under
#       MODIN_TPU_PLAN=Auto must be bit-exact vs eager and pandas, take
#       <= 2 compile-ledger dispatches for the device leg, and provably
#       never parse pruned columns (reader spy)
#   0f. graftmeter smoke: explain(analyze=True) on the plan_smoke pipeline
#       must be bit-exact with every plan node annotated, the
#       Prometheus/JSON exposition must parse, and the measured efficiency
#       counters (dispatches/compiles/reads/bytes/pruned columns) must
#       hold against scripts/metrics_baseline.json — re-record intentional
#       changes with `python scripts/metrics_smoke.py --record`
#   0g. graftgate serving smoke: 8 concurrent sessions under injected
#       DeviceLost + OOM bursts with tight deadlines — zero hangs (global
#       watchdog), every query bit-exact or a typed QueryRejected/
#       DeadlineExceeded, deadline overshoot bounded, serving.* metrics > 0
#   0i. graftmesh spmd smoke: traced sharded sort + merge-join over the
#       all_to_all shuffle on the 8-device mesh must be bit-exact vs
#       pandas, the compiled kernel's HLO must carry an all-to-all op
#       (one fused SPMD program, not per-shard host round-trips), and one
#       injected SHARD loss must be survived by re-seating only that
#       shard's slices (recovery.reseat.shard, zero whole-column re-seats)
#   0j. graftstream oocore smoke: a CSV scan->filter->groupby over a source
#       >= 4x an artificially tight device budget must complete bit-exact
#       vs pandas with peak memory.device.resident_bytes <= budget
#       (QueryStats high-water AND the meter gauge max) and
#       stream.window.count > 1, and the external sort / merge-join must
#       answer bit-identically to the resident kernels
#   0k. graftwatch smoke: 8 concurrent serving sessions under an injected
#       slow-kernel phase with the telemetry service live — every mid-load
#       /metrics scrape must parse via parse_prometheus, the per-tenant
#       SLO burn tripwire must fire, and exactly ONE rate-limited
#       evidence bundle (trace segment + meter snapshot + ring excerpt +
#       SLO health) must land in MODIN_TPU_TRACE_DIR
#   0l. graftfleet smoke: a 3-replica serving fleet must route a mixed
#       multi-tenant workload bit-exactly, survive kill -9 of a replica
#       mid-query with ZERO hangs (every query bit-exact or a typed
#       rejection), redistribute the drained tenants onto survivors,
#       respawn the dead slot warm (manifest re-read + graftview
#       artifact ingest), and ride out a crash-during-respawn; disabled
#       mode must be a bit-for-bit passthrough with zero allocations
#   0m. graftdep lockdep smoke: a concurrent serving workload with a
#       mid-run device loss under MODIN_TPU_LOCKDEP=1 must exercise the
#       acquisition graph (observed edges asserted, several matching
#       declared LOCK_ORDER edges) with ZERO violations, and a
#       deliberately seeded gate-under-dispatch inversion must raise
#       LockdepViolation AND flight-dump the witness — the tripwire is
#       proven live, not just quiet
#   0n. graftfeed ingest smoke: >= 200 micro-batches streamed through the
#       admission gate under lockdep strict while 4 concurrent sessions
#       issue staleness-bounded reads against registered live views —
#       every read bit-exact vs pandas over exactly its covered rows,
#       freshness bounds honored, retention-trim + mid-ingest DeviceLost
#       bit-exact, the fold_lag tripwire fires with exactly ONE evidence
#       bundle, and maintained reads beat recompute >= 3x
#   0o. graftwal durability smoke: a child process ingesting a durable
#       feed is SIGKILLed by an injected torn record write; reopening the
#       directory must load a checkpoint, truncate the torn tail, replay
#       the WAL tail (wal.replay.batches > 0), and serve the frame + both
#       views bit-exact vs pandas at the recovered batch count — then
#       keep ingesting durably
#   0p. graftopt optimizer smoke: MODIN_TPU_OPT=Auto must be bit-exact vs
#       MODIN_TPU_OPT=Off and plain pandas on the plan_smoke pipeline,
#       EXPLAIN/EXPLAIN ANALYZE must render chosen strategy legs with
#       estimated-vs-measured walls plus the re-plan section, absurd
#       injected priors must fire >= 1 opt.replan.* metric while staying
#       bit-exact, Off mode must allocate zero PlanStrategies, and the
#       whole workload must record zero lockdep violations
#   1. full suite under TpuOnJax (default execution, 8-device virtual mesh)
#   2. suite under PandasOnPython
#   3. suite under NativeOnNative
#   4. dryrun_multichip(8): the real multi-chip training-step sharding
#      compiled + executed on an 8-device virtual CPU mesh
set -u
cd "$(dirname "$0")/.."

XDIST=${XDIST:-}
EXTRA=${FAST:+-x}
fails=()

run_gate() {
  local name="$1"; shift
  echo "=== gate: $name ==="
  if "$@"; then
    echo "=== gate OK: $name ==="
  else
    echo "=== gate FAILED: $name ==="
    fails+=("$name")
  fi
}

run_gate "graftlint"       python -m modin_tpu.lint modin_tpu/
run_gate "graftscope"      python scripts/trace_smoke.py
run_gate "graftguard"      python scripts/chaos_smoke.py
run_gate "graftplan"       python scripts/plan_smoke.py
run_gate "graftmeter"      python scripts/metrics_smoke.py
run_gate "graftgate"       python scripts/serving_smoke.py
run_gate "graftmesh"       python scripts/spmd_smoke.py
run_gate "graftstream"     python scripts/oocore_smoke.py
run_gate "graftview"       python scripts/views_smoke.py
run_gate "graftwatch"      python scripts/watch_smoke.py
run_gate "graftfleet"      python scripts/fleet_smoke.py
run_gate "graftdep"        python scripts/lockdep_smoke.py
run_gate "graftfeed"       python scripts/ingest_smoke.py
run_gate "graftwal"        python scripts/durability_smoke.py
run_gate "graftopt"        python scripts/optimizer_smoke.py
run_gate "TpuOnJax"        python -m pytest tests/ -q $EXTRA --execution TpuOnJax
run_gate "PandasOnPython"  python -m pytest tests/ -q $EXTRA --execution PandasOnPython
run_gate "NativeOnNative"  python -m pytest tests/ -q $EXTRA --execution NativeOnNative
run_gate "dryrun_multichip" python __graft_entry__.py

if [ "${#fails[@]}" -ne 0 ]; then
  echo "RED gates: ${fails[*]}"
  exit 1
fi
echo "ALL NINETEEN GATES GREEN"
