#!/usr/bin/env python3
"""chip_smoke.py: the pandas-API main path at 1e8 rows on one TPU chip.

The quickest proof that the system still starts on the chip.  One process
drives the ordinary entry point (``import modin_tpu.pandas as pd``, default
TpuOnJax execution, no ``MODIN_TPU_*`` option set) at the repo's headline
shape, 1e8 rows x (5 int64 value columns in [0, 100) + one int64 key column
with 100 groups), made from ``--seed``, and answers six families of queries,
each compared with plain pandas/numpy on the same data.

It REFUSES every way the run could pass while the chip did nothing: a
platform other than "tpu", any resilience/recovery/degraded metric, a
"defaulting to in-process pandas" warning, a frame or result that is not on
the device, a query that built or dispatched no device program, a Pallas
bincount that never ran compiled.  No phase's failure is caught and turned
into a note: an exception ends the run with ``"ok": false`` and exit 1.

    python chip_smoke.py                 # one chip, 1e8 rows
    python chip_smoke.py --chips 4       # only the sharded path, four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 200000   # rehearsal: runs
        # every phase, ends "ok": false / exit 1 (nothing ran on a chip)

Every line printed is one JSON object; the last one is the contract
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  The numbers on the
earlier lines are observations, not a benchmark.
"""

import argparse
import contextlib
import json
import os
import re
import sys
import time
import traceback
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

ROWS = 100_000_000
COLS = 5
NGROUPS = 100

# any of these firing during a query means a device path retried, degraded,
# recovered or fell back to pandas: right for users, fatal for a smoke test
# (resilience.shuffle.slack_retry is not among them: the range shuffle
# re-running on the device with more capacity is its ordinary adaptation)
_REFUSED_METRIC = re.compile(
    r"^modin_tpu\.(resilience\.(fallback|breaker|engine|watchdog)\."
    r"|resilience\.shuffle\.skew_fallback|recovery\.|serving\.degraded)"
)
_REFUSED_WARNING = "defaulting to in-process pandas"

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def emit(**fields):
    print(json.dumps(fields, default=str), flush=True)


class Smoke:
    """Evidence collected over the run: failures, refused metrics/warnings,
    persistent-cache events."""

    def __init__(self):
        self.failures = []
        self.metrics = {}
        self.warnings = []
        self.cache_events = {"hits": 0, "misses": 0}

    def fail(self, phase, what):
        self.failures.append(f"{phase}: {what}")
        emit(phase=phase, failure=what)

    def check(self, phase, ok, what):
        if not ok:
            self.fail(phase, what)
        return bool(ok)

    def same(self, phase, label, got, want, rtol=None):
        """``got`` equals the pandas object ``want`` (values, index, columns):
        exactly, or to ``rtol`` relative where one is given."""
        import pandas

        check = (
            pandas.testing.assert_frame_equal
            if isinstance(want, pandas.DataFrame)
            else pandas.testing.assert_series_equal
        )
        kwargs = {"check_exact": True} if rtol is None else {"rtol": rtol, "atol": 0.0}
        try:
            check(got, want, **kwargs)
        except AssertionError as err:
            self.fail(phase, f"{label}: " + str(err).replace("\n", " | ")[:500])

    def on_metric(self, name, value):
        if _REFUSED_METRIC.match(name):
            self.metrics[name] = self.metrics.get(name, 0) + value

    def on_warning(self, message, category, filename, lineno, file=None, line=None):
        text = str(message)
        if _REFUSED_WARNING in text:
            self.warnings.append(text.splitlines()[0])
        sys.stderr.write(warnings.formatwarning(message, category, filename, lineno, line))

    def on_event(self, event, **kwargs):
        if event == _CACHE_HIT_EVENT:
            self.cache_events["hits"] += 1
        elif event == _CACHE_MISS_EVENT:
            self.cache_events["misses"] += 1


def cache_dir_and_entries():
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or jax.config.jax_compilation_cache_dir
    if not path or not os.path.isdir(path):
        return path, 0
    return path, sum(1 for name in os.listdir(path) if not name.startswith("."))


def peak_hbm():
    """Per-device ``peak_bytes_in_use`` (None where the backend reports none)."""
    import jax

    out = []
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def frame_on_device(smoke, phase, obj, expect_devices=None):
    """Every column of a modin_tpu DataFrame/Series is a device column (and,
    when asked, sharded over ``expect_devices`` devices)."""
    from modin_tpu.utils import get_current_execution

    qc = obj._query_compiler
    smoke.check(
        phase,
        type(qc).__name__ == "TpuQueryCompiler",
        f"result lives in {type(qc).__name__}, not TpuQueryCompiler "
        "(the backend switch moved it)",
    )
    smoke.check(
        phase,
        get_current_execution() == "TpuOnJax",
        f"execution is {get_current_execution()}, not TpuOnJax",
    )
    cols = getattr(qc._modin_frame, "_columns", None)
    if not smoke.check(phase, cols, "result has no device frame columns"):
        return
    for i, col in enumerate(cols):
        if not smoke.check(phase, col.is_device, f"column {i} is not on the device"):
            continue
        if expect_devices is not None:
            n_dev = len(col.data.sharding.device_set)
            smoke.check(
                phase,
                n_dev == expect_devices,
                f"column {i} is sharded over {n_dev} devices, not {expect_devices}",
            )


@contextlib.contextmanager
def phase_scope(smoke, name, needs_device_program=True):
    """Times one phase, counts its compiles/dispatches/cache events, prints
    one JSON line, and records a failure if it ran no device program or a
    refused metric/warning fired inside it.  Yields a dict of extra fields
    for the line.  A phase that raises ends the run: nothing is caught."""
    from modin_tpu.observability import query_stats
    from modin_tpu.observability.compile_ledger import get_compile_ledger

    ledger = get_compile_ledger()
    compiles0, compile_s0 = ledger.totals()
    events0 = dict(smoke.cache_events)
    metrics0 = dict(smoke.metrics)
    warnings0 = len(smoke.warnings)
    entries0 = cache_dir_and_entries()[1]
    notes = {}
    t0 = time.perf_counter()
    with query_stats(name) as stats:
        yield notes
    wall = time.perf_counter() - t0
    compiles, compile_s = ledger.totals()
    compiles -= compiles0
    cache_dir, entries = cache_dir_and_entries()
    emit(
        phase=name,
        wall_s=wall,
        compiles=compiles,
        compile_s=compile_s - compile_s0,
        persistent_cache_hits=smoke.cache_events["hits"] - events0["hits"],
        persistent_cache_misses=smoke.cache_events["misses"] - events0["misses"],
        cache_dir=cache_dir,
        cache_entries_before=entries0,
        cache_entries_after=entries,
        dispatches=stats.dispatches,
        peak_hbm_bytes=peak_hbm(),
        **notes,
    )
    if needs_device_program:
        smoke.check(
            name,
            compiles + stats.dispatches > 0,
            "no device program was compiled or dispatched",
        )
    fired = {
        k: v - metrics0.get(k, 0)
        for k, v in smoke.metrics.items()
        if v != metrics0.get(k, 0)
    }
    smoke.check(name, not fired, f"refused metrics fired: {fired}")
    new_warnings = smoke.warnings[warnings0:]
    smoke.check(name, not new_warnings, f"pandas-fallback warnings: {new_warnings}")


def to_host(obj):
    """Materialise a small modin_tpu result as its pandas object."""
    return obj.modin.to_pandas()


def build_frames(smoke, phase, rows, seed, expect_devices=None):
    """The headline frame from ``seed``: (modin_tpu frame on the device,
    pandas frame on the host) over the same numpy columns."""
    import numpy as np
    import pandas

    import modin_tpu.pandas as pd

    with phase_scope(smoke, phase, needs_device_program=False) as notes:
        rng = np.random.default_rng(seed)
        data = {f"c{i}": rng.integers(0, 100, rows) for i in range(COLS)}
        data["key"] = rng.integers(0, NGROUPS, rows)
        pdf = pandas.DataFrame(data, copy=False)
        df = pd.DataFrame(data)
        df._query_compiler.execute()
        del data
        notes["rows"] = rows
        notes["frame_bytes"] = int(rows) * 8 * (COLS + 1)
    frame_on_device(smoke, phase, df, expect_devices)
    smoke.check(phase, len(df) == rows, f"len(df)={len(df)} != {rows}")
    return df, pdf


def check_sorted(smoke, phase, ordered_len, ordered_sum, ordered_c0, pdf):
    """Row count, per-column sums and the fetched key column of a
    ``sort_values("c0")`` result against the input."""
    import numpy as np

    smoke.check(phase, ordered_len == len(pdf), f"len {ordered_len} != {len(pdf)}")
    smoke.same(phase, "sort_values('c0').sum()", ordered_sum, pdf.sum())
    smoke.check(
        phase,
        bool(np.all(ordered_c0[1:] >= ordered_c0[:-1])),
        "sorted c0 is not non-decreasing",
    )
    smoke.check(
        phase,
        np.array_equal(ordered_c0, np.sort(pdf["c0"].to_numpy())),
        "sorted c0 differs from np.sort of the input",
    )


def run_one_chip(smoke, rows, seed):
    import numpy as np

    df, pdf = build_frames(smoke, "build", rows, seed)

    # 1. elementwise, fused map
    phase = "q1_elementwise"
    with phase_scope(smoke, phase):
        added = df.add(2)
        added._query_compiler.execute()
        fused = df * 2 + df
        fused._query_compiler.execute()
        added_sum = to_host(added.sum())
        fused_sum = to_host(fused.sum())
        added_c0 = added["c0"].to_numpy()
    frame_on_device(smoke, phase, added)
    frame_on_device(smoke, phase, fused)
    smoke.same(phase, "df.add(2).sum()", added_sum, pdf.add(2).sum())
    smoke.same(phase, "(df*2+df).sum()", fused_sum, (pdf * 2 + pdf).sum())
    smoke.check(
        phase,
        np.array_equal(added_c0, pdf["c0"].to_numpy() + 2),
        "df.add(2).c0 fetched to the host differs from pandas",
    )
    del added, fused, added_c0

    # 2. tree reductions
    phase = "q2_reductions"
    with phase_scope(smoke, phase):
        got_sum = to_host(df.sum())
        got_mean = to_host(df.mean())
        got_count = to_host(df.count())
    smoke.same(phase, "df.sum()", got_sum, pdf.sum())
    smoke.same(phase, "df.mean()", got_mean, pdf.mean(), rtol=1e-12)
    smoke.same(phase, "df.count()", got_count, pdf.count())

    # 3. filter / gather
    phase = "q3_filter"
    with phase_scope(smoke, phase) as notes:
        kept = df[df.c0 > 50]
        kept._query_compiler.execute()
        kept_len = len(kept)
        kept_sum = to_host(kept.sum())
        notes["kept_rows"] = kept_len
    frame_on_device(smoke, phase, kept)
    want = pdf[pdf.c0 > 50]
    smoke.check(phase, kept_len == len(want), f"len {kept_len} != {len(want)}")
    smoke.same(phase, "df[df.c0 > 50].sum()", kept_sum, want.sum())
    del kept, want

    # 4./5. groupby: masked-scan VPU kernel, then the Pallas bincount
    from modin_tpu.ops.pallas.groupby_kernels import _jit_bincount_wrapper

    bincount0 = _jit_bincount_wrapper.cache_info()
    pgb = pdf.groupby("key")
    phase = "q4_groupby_sum_mean"
    with phase_scope(smoke, phase):
        gb_sum = df.groupby("key").sum()
        gb_sum._query_compiler.execute()
        gb_mean = df.groupby("key").mean()
        gb_mean._query_compiler.execute()
    frame_on_device(smoke, phase, gb_sum)
    frame_on_device(smoke, phase, gb_mean)
    smoke.same(phase, "groupby.sum()", to_host(gb_sum), pgb.sum())
    smoke.same(phase, "groupby.mean()", to_host(gb_mean), pgb.mean(), rtol=1e-12)

    phase = "q5_groupby_size_count"
    with phase_scope(smoke, phase):
        gb_size = df.groupby("key").size()
        gb_size._query_compiler.execute()
        gb_count = df.groupby("key").count()
        gb_count._query_compiler.execute()
    frame_on_device(smoke, phase, gb_size)
    frame_on_device(smoke, phase, gb_count)
    smoke.same(phase, "groupby.size()", to_host(gb_size), pgb.size())
    smoke.same(phase, "groupby.count()", to_host(gb_count), pgb.count())
    check_pallas_bincount(smoke, df, bincount0)
    del gb_sum, gb_mean, gb_size, gb_count, pgb

    # 6. whole-frame sort (the XLA sort)
    phase = "q6_sort"
    with phase_scope(smoke, phase):
        ordered = df.sort_values("c0")
        ordered._query_compiler.execute()
        ordered_len = len(ordered)
        ordered_sum = to_host(ordered.sum())
        ordered_c0 = ordered["c0"].to_numpy()
    frame_on_device(smoke, phase, ordered)
    check_sorted(smoke, phase, ordered_len, ordered_sum, ordered_c0, pdf)


def check_pallas_bincount(smoke, df, info_before, mesh_key=""):
    """The compiled (interpret=False) Pallas bincount was built for this
    frame's shape during the groupby queries, ran at least once, and lowers
    to a Mosaic ``tpu_custom_call``."""
    import jax
    import numpy as np

    from modin_tpu.ops.pallas.groupby_kernels import _jit_bincount_wrapper

    phase = "pallas_bincount"
    info = _jit_bincount_wrapper.cache_info()
    built = info.misses - info_before.misses
    if not smoke.check(
        phase, built > 0, f"no bincount wrapper was built during the groupby queries ({info})"
    ):
        return
    p_len = int(df._query_compiler._modin_frame._columns[0].data.shape[0])
    # the exact arguments pallas_bincount passes (mesh_key "" on one device)
    fn = _jit_bincount_wrapper(p_len, NGROUPS, False, mesh_key)
    probe = _jit_bincount_wrapper.cache_info()
    if not smoke.check(
        phase,
        probe.misses == info.misses,
        f"no interpret=False bincount entry for ({p_len}, {NGROUPS}) existed: "
        "the kernel on this path was built with other arguments",
    ):
        return
    ran = fn._cache_size()
    smoke.check(phase, ran >= 1, "the compiled bincount was built but never executed")
    ids = jax.ShapeDtypeStruct(
        (p_len,), np.int64, sharding=df._query_compiler._modin_frame._columns[0].data.sharding
    )
    text = fn.lower(ids).as_text()
    smoke.check(
        phase,
        "tpu_custom_call" in text,
        "the bincount's lowered text holds no tpu_custom_call",
    )
    emit(phase=phase, built=built, executions_cached=ran, tpu_custom_call="tpu_custom_call" in text)


def run_four_chips(smoke, rows, seed):
    """Only the sharded path and what it is compared with: groupby-sum, sort
    and an inner merge over a four-device row mesh under MODIN_TPU_SPMD=Sharded
    (Auto may keep the sort local by its calibrated crossover, and the
    collectives would then never run)."""
    import jax
    import numpy as np
    import pandas

    import modin_tpu.pandas as pd
    from modin_tpu.config import SpmdMode
    from modin_tpu.ops.pallas.groupby_kernels import _jit_bincount_wrapper
    from modin_tpu.parallel.mesh import mesh_shape_key, num_row_shards

    SpmdMode.put("Sharded")
    n_dev = jax.device_count()
    smoke.check("mesh", n_dev == 4, f"jax.device_count()={n_dev}, not 4")
    smoke.check(
        "mesh",
        num_row_shards() == 4,
        f"the row mesh has {num_row_shards()} shards ({mesh_shape_key()}), not 4",
    )
    if smoke.failures:
        return

    df, pdf = build_frames(smoke, "build4", rows, seed, expect_devices=4)

    bincount0 = _jit_bincount_wrapper.cache_info()
    phase = "s1_groupby_sum"
    with phase_scope(smoke, phase):
        gb_sum = df.groupby("key").sum()
        gb_sum._query_compiler.execute()
    frame_on_device(smoke, phase, gb_sum)
    smoke.same(phase, "groupby.sum()", to_host(gb_sum), pdf.groupby("key").sum())
    check_pallas_bincount(smoke, df, bincount0, mesh_key=mesh_shape_key())
    del gb_sum

    phase = "s2_sort"
    with phase_scope(smoke, phase):
        ordered = df.sort_values("c0")
        ordered._query_compiler.execute()
        ordered_len = len(ordered)
        ordered_sum = to_host(ordered.sum())
        ordered_c0 = ordered["c0"].to_numpy()
    frame_on_device(smoke, phase, ordered, expect_devices=4)
    check_sorted(smoke, phase, ordered_len, ordered_sum, ordered_c0, pdf)
    del ordered, ordered_c0

    right_data = {
        "key": np.arange(NGROUPS, dtype=np.int64),
        "w": np.arange(NGROUPS, dtype=np.int64) * 7 + 1,
    }
    phase = "s3_merge"
    with phase_scope(smoke, phase):
        merged = df.merge(pd.DataFrame(right_data), on="key", how="inner")
        merged._query_compiler.execute()
        merged_len = len(merged)
        merged_sum = to_host(merged.sum())
    frame_on_device(smoke, phase, merged)
    want = pdf.merge(pandas.DataFrame(right_data), on="key", how="inner")
    smoke.check(phase, merged_len == len(want), f"len {merged_len} != {len(want)}")
    smoke.same(phase, "merge(on='key').sum()", merged_sum, want.sum())
    del merged, want

    check_collectives(smoke)
    peaks = peak_hbm()
    emit(phase="memory4", peak_hbm_bytes=peaks)
    smoke.check(
        "memory4",
        all(p is not None and p > 0 for p in peaks),
        f"not every device held bytes: peak_bytes_in_use={peaks}",
    )


def check_collectives(smoke):
    """The range shuffle ran as one SPMD program whose optimized HLO carries
    the all-to-all (not per-shard host round trips)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from modin_tpu.ops.structural import pad_host, pad_len
    from modin_tpu.parallel.engine import JaxWrapper
    from modin_tpu.parallel.mesh import mesh_shape_key
    from modin_tpu.parallel.shuffle import _jit_shuffle

    phase = "collectives"
    info = _jit_shuffle.cache_info()
    if not smoke.check(
        phase, info.currsize >= 1, "the shuffle kernel cache is empty: the sharded path never ran"
    ):
        return
    n_small = 96
    fn = _jit_shuffle(1, 64, n_small, False, True, mesh_shape_key())
    key = JaxWrapper.put(pad_host(np.arange(n_small, dtype=np.int64), n_small))
    iota = JaxWrapper.put(pad_host(np.arange(n_small, dtype=np.int64), n_small))
    pivots = jnp.asarray(np.arange(3, dtype=np.int64) * (n_small // 4))
    row_valid = jax.device_put((np.arange(pad_len(n_small)) < n_small)[:, None])
    hlo = fn.lower(pivots, key, row_valid, iota).compile().as_text()
    has = "all-to-all" in hlo or "all_to_all" in hlo
    smoke.check(phase, has, "the shuffle's optimized HLO carries no all-to-all")
    emit(phase=phase, shuffle_kernels_built=info.currsize, all_to_all_in_hlo=has)


def main():
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--rows", type=int, default=ROWS, help="lower only for a rehearsal")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    emit(phase="device", **device)
    on_chip = device["platform"] == "tpu"
    rehearsal = not on_chip and os.environ.get("JAX_PLATFORMS") == "cpu"
    if not on_chip and not rehearsal:
        # no accelerator and nobody asked for a rehearsal: no result line
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu'", file=sys.stderr)
        return 2
    if not on_chip and args.rows > 10_000_000:
        print("chip_smoke: a CPU rehearsal needs an explicit small --rows", file=sys.stderr)
        return 2
    if on_chip and device["count"] != args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX reports {device['count']} devices",
            file=sys.stderr,
        )
        return 2

    # without the program beside it the script has nothing to report: the
    # ImportError ends it here, non-zero and with no result line
    import modin_tpu
    from modin_tpu.logging import add_metric_handler

    pkg_dir = os.path.dirname(os.path.abspath(modin_tpu.__file__))
    if pkg_dir != os.path.join(HERE, "modin_tpu"):
        raise RuntimeError(f"modin_tpu came from {pkg_dir}, not from this checkout")

    smoke = Smoke()
    ok = False
    try:
        import jaxlib
        import numpy
        import pandas

        try:
            from importlib.metadata import version

            libtpu = version("libtpu")
        except Exception:  # noqa: BLE001 - a version string only
            libtpu = None
        emit(
            phase="versions",
            jax=jax.__version__,
            jaxlib=jaxlib.__version__,
            libtpu=libtpu,
            pandas=pandas.__version__,
            numpy=numpy.__version__,
            python=sys.version.split()[0],
            rehearsal=rehearsal,
            rows=args.rows,
            seed=args.seed,
            chips=args.chips,
        )

        add_metric_handler(smoke.on_metric)
        warnings.simplefilter("always")
        warnings.showwarning = smoke.on_warning
        from jax._src import monitoring

        monitoring.register_event_listener(smoke.on_event)

        if args.chips == 4:
            run_four_chips(smoke, args.rows, args.seed)
        else:
            run_one_chip(smoke, args.rows, args.seed)
        ok = on_chip and not smoke.failures
        emit(phase="summary", failures=smoke.failures, rehearsal=rehearsal)
    except BaseException:  # noqa: BLE001 - reported, then the run fails
        traceback.print_exc()
        emit(phase="summary", failures=smoke.failures, raised=traceback.format_exc(limit=3))
        ok = False
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
