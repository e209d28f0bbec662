"""The LOCKS registry: every named lock in the engine, plus the intended
partial acquisition order.

Why a registry at all: 25+ modules hold a ``Lock``/``RLock``, and every
one of the last six review passes hand-found a real concurrency bug (the
gate's lost wakeup, the dispatch-vs-reseat inversion, torn SortedRep
pairs, TenantState read-modify-write races, the flight-recorder
claim-token double-dump).  The registry turns the two facts those reviews
kept re-deriving — *which* locks exist and *in what order* they may nest —
into declared, machine-checked data:

- **statically**, graftlint's ``LOCK-ORDER`` / ``LOCK-BLOCKING`` rules
  build the interprocedural acquisition graph from ``with <lock>:`` sites
  and check it against :data:`LOCK_ORDER` (and ``REGISTRY-DRIFT``
  cross-checks :data:`LOCKS` against the actual ``named_lock``
  construction sites both ways);
- **dynamically**, the lockdep validator (concurrency/lockdep.py,
  ``MODIN_TPU_LOCKDEP=1``) records real per-thread acquisition stacks in
  every concurrency suite and raises on an observed inversion.

This module is a deliberate leaf: pure data plus tiny pure helpers, no
modin_tpu imports, so any module may import it at construction time
(locks are built during early module import, long before the config layer
is importable).

Declaration shape (REGISTRY-DRIFT parses exactly this, like METRICS/SPANS):

    ("dotted.name", "lock" | "rlock", "what it guards")

An edge ``(before, after, why)`` in :data:`LOCK_ORDER` means "``before``
may legally be held while acquiring ``after``" — and therefore ``after``
must NEVER be held while acquiring ``before`` (the checked contradiction).
Unrelated locks stay unordered until an observed nesting forces a
decision; the partial order only grows edges that real code exercises.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Iterable, Set, Tuple

#: Every named lock in the package: (name, kind, what it guards).
#: Kind is enforced at construction (``named_lock`` refuses an "rlock"
#: declaration and vice versa) so reentrancy intent is declared data, not
#: an implementation detail a refactor can silently flip.
LOCKS: Tuple[Tuple[str, str, str], ...] = (
    # -- serving front door -------------------------------------------- #
    ("serving.gate", "lock", "admission gate counters, reservations, waiter queue"),
    ("serving.context_active", "lock", "active serving-context count behind CONTEXT_ON"),
    ("serving.tenants", "lock", "tenant table: weights, buckets, cost EWMAs, LRU"),
    # -- engine seam / resilience / recovery --------------------------- #
    ("resilience.dispatch", "rlock", "collective-safe program-enqueue serialization at the engine seam"),
    ("resilience.breaker", "lock", "one circuit breaker's state/strike transitions"),
    ("resilience.breakers", "lock", "the process-wide breaker name table"),
    ("recovery.epoch", "lock", "device-epoch counter bumps"),
    ("recovery.reseat", "lock", "whole reseat passes + the reseat-once handshake"),
    ("recovery.provenance", "rlock", "deploy provenance table (weakref callbacks re-enter)"),
    ("recovery.manifest", "lock", "dataset manifest for warm respawn replay"),
    # -- memory -------------------------------------------------------- #
    ("memory.host_cache", "rlock", "host spill-cache ledger (weakref callbacks re-enter)"),
    ("memory.device_ledger", "rlock", "device residency ledger + LRU spill order (weakref callbacks re-enter)"),
    # -- fleet --------------------------------------------------------- #
    ("fleet.coordinator", "rlock", "replica table, tenant assignments, routing counters"),
    ("fleet.replica_state", "lock", "one replica slot's in-flight dispatch socket set"),
    ("fleet.frames", "lock", "a replica process's warmed dataset map"),
    ("fleet.control", "lock", "a replica's serialized control-socket writes"),
    # -- ops / plan caches --------------------------------------------- #
    ("ops.router_calibration", "lock", "kernel-router calibration table resolve-once"),
    ("ops.fused_cache", "lock", "fused-program LRU cache linkage"),
    ("ops.aval_memo", "lock", "elementwise-node aval memo LRU linkage"),
    ("plan.storm", "lock", "recompile-storm signature table"),
    ("plan.scan_cache", "lock", "scan-node parse cache (parses happen outside it)"),
    ("views.registry", "rlock", "THE derived-artifact cache (invalidation re-enters via drop hooks)"),
    # -- ingest (graftfeed) -------------------------------------------- #
    ("ingest.feeds", "lock", "the named-feed table: create/get/drop"),
    ("ingest.feed", "rlock", "one feed's frame, batch log, key index, and registered-view state (folds re-enter via forced reads)"),
    ("durability.wal", "lock", "one durable feed's WAL segment file, fsync-policy dirty flag, and checkpoint claim"),
    ("parallel.mesh", "lock", "global mesh build-once"),
    ("io.chunker", "lock", "chunker native-library build-once"),
    # -- observability ------------------------------------------------- #
    ("meters.scopes", "lock", "process-wide open QueryStats scope set + registry acquires"),
    ("meters.registry", "lock", "meter families: create/observe/snapshot"),
    ("meters.query_stats", "lock", "one QueryStats scope's accumulation vs close"),
    ("costs.padding", "lock", "global padding-waste accumulators"),
    ("costs.ledger", "lock", "per-signature cost entries joined with dispatch wall"),
    ("costs.peaks", "lock", "substrate peak measurement resolve-once"),
    ("spans.state", "lock", "tracing enable state + profile collectors"),
    ("spans.live", "lock", "live-span counter read-modify-write"),
    ("compile_ledger.entries", "lock", "per-signature compile/dispatch accounting"),
    ("compile_ledger.install", "lock", "compile-listener install-once"),
    ("flight.dump", "lock", "flight-dump rate-limit claim token"),
    ("watch.state", "rlock", "watch service lifecycle (start/stop/degrade re-enter)"),
    ("watch.rings", "lock", "ring-store series table"),
    ("watch.ring", "lock", "one time-series ring's sample deque"),
    ("watch.slo", "lock", "per-tenant SLO burn observation windows"),
    ("logging.configure", "lock", "log-handler + memory-sampler configure-once"),
    # -- test harness -------------------------------------------------- #
    ("testing.faults", "lock", "fault-injector hook counters"),
)

#: Locks where holding several *instances* of the same name at once is
#: legal (each instance guards an independent object and no code path
#: holds two in conflicting orders).  Everything else treats a
#: same-name-different-instance nesting as a violation at runtime.
NESTABLE: FrozenSet[str] = frozenset(
    {
        # one QueryStats scope closing can fold into its parent scope
        "meters.query_stats",
        # the sampler folds many rings under one pass; rings never nest
        # into each other in the other direction
        "watch.ring",
    }
)

#: Locks whose critical sections acquire nothing else — by design,
#: because weakref death callbacks may fire while ANY lock is held (a
#: cache eviction dropping the last reference runs them inline) and each
#: callback re-enters one of these.  The runtime validator ignores
#: acquisition edges OUT of a leaf: the leaf's own code nests nothing
#: (the static LOCK-ORDER rule checks that from the with-blocks), so the
#: only way to be holding one while acquiring another lock is a GC-fired
#: callback — a timing artifact that would otherwise flakily convict (or
#: deadlock-check) arbitrary victim code.
LEAF_LOCKS: FrozenSet[str] = frozenset(
    {
        "memory.host_cache",
        "memory.device_ledger",
        "recovery.provenance",
    }
)

#: The intended partial order: ``(before, after, why)`` — ``before`` may
#: be held while acquiring ``after``.  The checked direction is the
#: contrapositive: an acquisition of ``before`` while ``after`` is held
#: (directly observed or via the static call graph) is a violation.
#:
#: Edges are declared only where real code nests today (plus the PR-9
#: inversion fix as a permanent regression tripwire); the order grows
#: with the code, it is not an aspirational total order.
LOCK_ORDER: Tuple[Tuple[str, str, str], ...] = (
    # The PR-9 inversion fix, now a declared edge: the admission gate may
    # admit INTO a dispatch (gate held -> engine work), but the engine
    # seam / recovery must never call back up into the gate lock.
    ("serving.gate", "resilience.dispatch", "admission decides before the seam dispatches; seam code never re-enters the gate"),
    ("resilience.dispatch", "recovery.reseat", "a failed attempt under the dispatch serialization runs the reseat pass; reseat never dispatches back through the serialization it is under"),
    ("recovery.reseat", "recovery.provenance", "the reseat pass walks the provenance table per lost buffer"),
    ("recovery.reseat", "recovery.epoch", "the reseat pass advances the device epoch it completed"),
    ("recovery.reseat", "memory.device_ledger", "reseat re-registers recovered buffers with the residency ledger"),
    ("recovery.reseat", "parallel.mesh", "the reseat pass re-deploys through the mesh build-once"),
    # The ledger/provenance locks (memory.host_cache, memory.device_ledger,
    # recovery.provenance) are LEAVES: their critical sections never acquire
    # another lock, by design — weakref death callbacks can fire under ANY
    # lock (a cache eviction dropping the last reference runs them inline)
    # and each callback re-enters one of these.  No outgoing edge is
    # declared for them, ever; lockdep observes GC-timing edges INTO them
    # from arbitrary holders (e.g. plan.scan_cache) and that is legal
    # precisely because nothing flows back out.
    ("views.registry", "memory.device_ledger", "artifact drop deregisters its device payload under the registry serialization; ledger spill snapshots candidates under its own lock and drops OUTSIDE it"),
    ("resilience.breakers", "resilience.breaker", "breaker lookup creates/reads one breaker under the table lock"),
    ("serving.tenants", "resilience.breakers", "tenant health/eviction reads its breaker under the tenant table lock"),
    ("fleet.coordinator", "fleet.replica_state", "coordinator passes walk one replica's in-flight set under the table lock"),
    ("watch.state", "watch.rings", "watch lifecycle resets the store it owns"),
    ("watch.rings", "watch.ring", "the store creates/samples one ring under the series-table lock"),
    ("watch.state", "watch.slo", "watch lifecycle resets the SLO tracker it owns"),
    ("meters.scopes", "meters.registry", "scope open/close folds into the registry; registry code never opens scopes"),
    ("meters.scopes", "meters.query_stats", "the spill/fold pass walks open scopes and accumulates into each"),
    ("serving.gate", "serving.tenants", "admission reads tenant weights/costs while deciding; tenant bookkeeping never re-enters the gate"),
    ("ingest.feeds", "ingest.feed", "the fold-lag probe walks each feed under the table lock; feed code never re-enters the table"),
    ("ingest.feed", "views.registry", "an append under the feed serialization runs concat_rows, which records its append link in the artifact registry"),
    ("ingest.feed", "resilience.dispatch", "appends/trims under the feed serialization dispatch device concats through the engine seam; seam code never re-enters a feed"),
    ("ingest.feed", "durability.wal", "a durable append logs its pre-encoded WAL record under the feed serialization BEFORE mutating feed state; WAL code never re-enters a feed"),
)


def declared_kinds() -> Dict[str, str]:
    """{lock name: "lock" | "rlock"} from :data:`LOCKS`."""
    return {name: kind for name, kind, _ in LOCKS}


def order_edges() -> Set[Tuple[str, str]]:
    """The declared edge set, without rationale strings."""
    return {(before, after) for before, after, _ in LOCK_ORDER}


def transitive_order(
    edges: Iterable[Tuple[str, str]] = None,
) -> Dict[str, Set[str]]:
    """{name: every name it precedes} — the declared order's closure.

    Pure Floyd-Warshall-by-DFS over ~40 nodes; both the static rule and
    the runtime validator consume this, so they can never disagree about
    reachability.
    """
    if edges is None:
        edges = order_edges()
    adjacency: Dict[str, Set[str]] = {}
    for before, after in edges:
        adjacency.setdefault(before, set()).add(after)
    closure: Dict[str, Set[str]] = {}
    for start in adjacency:
        seen: Set[str] = set()
        stack = list(adjacency[start])
        while stack:
            node = stack.pop()
            if node in seen:
                continue
            seen.add(node)
            stack.extend(adjacency.get(node, ()))
        closure[start] = seen
    return closure


def validate_registry() -> None:
    """Internal-consistency checks, raised at first ``named_lock`` call:
    order edges over undeclared names, duplicate declarations, an edge
    already contradicted by the declared closure, self-edges."""
    names = [name for name, _, _ in LOCKS]
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        raise ValueError(f"duplicate LOCKS declarations: {sorted(dupes)}")
    declared = set(names)
    for before, after, _ in LOCK_ORDER:
        if before == after:
            raise ValueError(f"self-edge in LOCK_ORDER: {before}")
        for name in (before, after):
            if name not in declared:
                raise ValueError(
                    f"LOCK_ORDER references undeclared lock {name!r}"
                )
    closure = transitive_order()
    for before, after in order_edges():
        if before in closure.get(after, ()):
            raise ValueError(
                f"LOCK_ORDER declares both {before} -> {after} and a path "
                f"{after} -> {before}: the declared order itself cycles"
            )
    for name in NESTABLE:
        if name not in declared:
            raise ValueError(f"NESTABLE references undeclared lock {name!r}")
    for name in LEAF_LOCKS:
        if name not in declared:
            raise ValueError(
                f"LEAF_LOCKS references undeclared lock {name!r}"
            )
    for before, _after, _ in LOCK_ORDER:
        if before in LEAF_LOCKS:
            raise ValueError(
                f"LOCK_ORDER declares an edge out of leaf lock {before!r} "
                "— leaves acquire nothing by design (weakref callbacks "
                "re-enter them under arbitrary locks)"
            )
