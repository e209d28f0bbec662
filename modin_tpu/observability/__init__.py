"""graftscope — structured tracing & profiling for the query path.

Where a query spends its time, attributed across the four seams the
framework is built around:

1. **pandas API entry** — every ``enable_logging``-wrapped call emits a
   span tagged with its ``modin_layer`` (``PANDAS-API``, ...);
2. **TPU query compiler** — the same mechanism tags ``QUERY-COMPILER``
   spans, the granularity compile time is attributed to;
3. **the JaxWrapper engine seam** — the resilience wrapper emits one span
   per attempt (``engine.<op>.attempt``), so retries, watchdog kills, and
   classified failures appear as sibling spans with failure-kind
   attributes, and breaker fallbacks as ``fallback.<family>`` spans;
4. **shuffle / IO** — the range-partition shuffle and FileDispatcher reads.

Quick use::

    import modin_tpu.observability as gs

    with gs.profile() as prof:
        df.groupby("k").sum().to_pandas()
    print(prof.rollup())                       # host/device/compile split
    prof.export_chrome_trace("query.trace.json")   # load in chrome://tracing

    gs.get_compile_ledger().recompile_storms() # who keeps recompiling?

Always-on tracing: ``MODIN_TPU_TRACE=1`` (or
``modin_tpu.config.TraceEnabled.enable()``).  While on, finished spans also
feed a bounded flight-recorder ring that dumps automatically when a
resilience circuit breaker opens or a device failure is terminal — see
docs/observability.md.  Disabled (the default), the entire subsystem costs
one module-attribute check per instrumented call and allocates nothing.
"""

from modin_tpu.observability.chrome_trace import (  # noqa: F401
    export_chrome_trace,
    to_chrome_trace,
)
from modin_tpu.observability.compile_ledger import (  # noqa: F401
    CompileLedger,
    get_compile_ledger,
)
from modin_tpu.observability.flight_recorder import (  # noqa: F401
    dump_flight_record,
    flight_snapshot,
)
from modin_tpu.observability.spans import (  # noqa: F401
    SPANS,
    Profile,
    Span,
    counter_samples,
    current_span,
    layer_span,
    profile,
    span,
    span_alloc_count,
    start_span,
    finish_span,
    trace_enabled,
)
from modin_tpu.observability.meters import (  # noqa: F401
    HISTOGRAM_BUCKETS,
    QueryStats,
    meter_alloc_count,
    meters_enabled,
    query_stats,
    recent_queries,
)
from modin_tpu.observability.meters import (  # noqa: F401
    reset as meters_reset,
    snapshot as meters_snapshot,
)
from modin_tpu.observability.exposition import (  # noqa: F401
    meter_rollup,
    parse_prometheus,
    to_json,
    to_prometheus,
)
from modin_tpu.observability.costs import (  # noqa: F401
    CostLedger,
    get_cost_ledger,
    note_padding,
    roofline_fraction,
    substrate_peaks,
)
from modin_tpu.observability.watch import (  # noqa: F401
    WatchService,
    httpd_port,
    recent_trips,
    slo_health,
    watch_alloc_count,
    watch_snapshot,
)

# MODIN_TPU_TRACE=1 at import: the config subscription fired while
# compile_ledger was still initializing and deferred the listener install —
# complete it now that the package is whole
if trace_enabled():
    from modin_tpu.observability.compile_ledger import ensure_listener as _ensure

    _ensure()
    del _ensure
