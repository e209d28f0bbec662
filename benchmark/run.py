#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process drives the ordinary entry point (``import modin_tpu.pandas as
pd``, default execution, no ``MODIN_TPU_*`` option set): it makes the cell's
table from ``--seed``, ingests it, asks each of the cell's questions once
(set-up: compiles or cache loads), then asks them again in blocks shuffled
from the seed, one closed-loop client, until the first block that ends at or
after ``--seconds`` (with ``--trace 1``: and after the profiler's start, so a
traced run always traces a whole request).  Before each request
the program's derived answers are dropped, so every request is a first run.
Once the window has closed the answers are compared with plain pandas.

The last line of standard output is the result; ``--trace 0`` gives the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.  On another
platform than ``tpu`` it exits 2 with no result; ``--rehearse`` drives every
phase at the configuration's ``rehearse_rows`` wherever it is started and
ends ``"correct": false`` (nothing ran at size on a chip).  A run that is not
correct prints its result and exits 1.

The process keeps to the upper half of the CPUs it is given: on the one-chip
machine's shared host a request's wake-ups cost 3.6 ms more in some placements
than in others, whole runs at a time, and there they cost the same every time.
"""

import argparse
import contextlib
import functools
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
import traceback

T0 = time.perf_counter()


def keep_to_upper_half():
    """Pin this process, before any thread starts, to the upper half of its CPUs."""
    cpus = sorted(os.sched_getaffinity(0))
    if len(cpus) >= 4:
        os.sched_setaffinity(0, cpus[len(cpus) // 2:])
    return sorted(os.sched_getaffinity(0))


HOST_CPUS = keep_to_upper_half() if __name__ == "__main__" else sorted(os.sched_getaffinity(0))

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

# an answer of at most this many device bytes is kept there until the window
# has closed and compared whole, while the kept ones stay within
# KEEP_TOTAL_BYTES together.  Of the others (larger, or past the total) a
# sample drawn from the seed is looked into between requests: the first such
# answer to each question and every SAMPLE_EVERY-th after it, at SAMPLED_RUNS
# runs of SAMPLED_RUN_ROWS consecutive rows.  Not each one, because any program
# that reads a 64-bit buffer on a TPU first splits the whole of it into 32-bit
# halves: 26 ms of device time for a 4 GB answer, to a request's 68.
KEEP_BYTES = 64 << 20
KEEP_TOTAL_BYTES = 1 << 30
SAMPLE_EVERY = 8
SAMPLED_RUNS = 16
SAMPLED_RUN_ROWS = 256
# of the window with --trace 1: from its second block of requests on, or from
# its first where the first pass says that one block alone reaches --seconds
TRACE_SECONDS = 3.0
TRACE_DIR = os.path.join(ROOT, ".modin_tpu", "benchmark_trace")


def load_json(*parts):
    with open(os.path.join(*parts)) as handle:
        return json.load(handle)


def load_module(*parts):
    path = os.path.join(*parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts[-2:]).replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def log(**fields):
    print(json.dumps(fields, default=str), file=sys.stderr, flush=True)


class Cell:
    """A cell's files, found by the names ``BENCHMARK.json`` gives."""

    def __init__(self, name):
        self.spec = load_json(ROOT, "BENCHMARK.json")
        entry = next((w for w in self.spec["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"run.py: BENCHMARK.json has no workload {name!r}")
        self.name = name
        self.file = load_json(BENCH, "workloads", name + ".json")
        for key in ("config", "traffic", "chips"):
            if self.file[key] != entry[key]:
                raise SystemExit(f"run.py: {name}.json and BENCHMARK.json differ on {key!r}")
        self.config = load_json(BENCH, "configs", self.file["config"] + ".json")
        self.dataset = load_module(BENCH, "datasets", self.config["generator"] + ".py")
        self.questions = {
            q["name"]: load_module(BENCH, "questions", self.file["config"], q["name"] + ".py")
            for q in self.file["questions"]
        }

    def metrics(self, group):
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
        return [m for m in self.spec[group] if self.name in m.get("workloads", [self.name])]


class Sampler:
    """Evidence of an answer too large to keep: a few runs of consecutive rows
    at seeded places, read from its device buffers."""

    def __init__(self, seed, questions):
        import numpy as np

        self.seed = seed
        self.places = {}  # length -> [host positions, run length, device starts]
        rng = np.random.default_rng([int(seed) % 2**63, 0x0FF5E7])
        # every turn of SAMPLE_EVERY falls to as many questions, whatever the seed
        turns = rng.permutation(len(questions)) % SAMPLE_EVERY
        self.turn = {q: int(turn) for q, turn in zip(questions, turns)}
        self.seen = set()

    def due(self, question):
        """Whether this large answer to ``question`` is one of the sample: the
        first is, and every SAMPLE_EVERY-th after it from a seeded turn."""
        if question not in self.seen:
            self.seen.add(question)
            return True
        self.turn[question] += 1
        return self.turn[question] % SAMPLE_EVERY == 0

    def _place(self, length):
        import numpy as np

        if length not in self.places:
            rng = np.random.default_rng([int(self.seed) % 2**63, 0x5A3F1E])
            run_rows = min(SAMPLED_RUN_ROWS, length)
            starts = np.sort(rng.integers(0, length - run_rows + 1, SAMPLED_RUNS))
            positions = (starts[:, None] + np.arange(run_rows)[None, :]).ravel()
            self.places[length] = [positions, run_rows, starts]
        return self.places[length]

    def host_positions(self, length):
        return self._place(length)[0]

    def take(self, buffers):
        import jax

        length = buffers[0][2]
        place = self._place(length)
        if not isinstance(place[2], jax.Array):
            place[2] = jax.device_put(place[2])
        rows = _slices_jit()([b for _, b, _ in buffers], place[2], place[1])
        return {"labels": [label for label, _, _ in buffers], "length": length, "rows": jax.device_get(rows)}


@functools.lru_cache(maxsize=None)
def _slices_jit():
    import jax
    import jax.numpy as jnp

    def bench_slices(cols, starts, n):  # trace_reduce knows the harness's programs by "jit_bench_"
        return [
            jnp.concatenate([jax.lax.dynamic_slice(c, (starts[i],), (n,)) for i in range(starts.shape[0])])
            for c in cols
        ]

    return jax.jit(bench_slices, static_argnums=2)


class Run:
    """One run's state: the program, the frame, what the requests left."""

    def __init__(self, cell, args, hooks, pd, trap):
        self.cell = cell
        self.count_dispatches = bool(args.trace)  # query_stats only in the per-layer run
        self.hooks = hooks
        self.pd = pd
        self.trap = trap
        self.frame = None
        self.sampler = Sampler(args.seed, list(cell.questions))
        self.kept = []  # (request number, question, the program's answer)
        self.kept_bytes = 0
        self.sampled = []  # (request number, question, sample)
        self.unsampled = 0
        self.tracing = False
        self.keep_bytes = KEEP_BYTES
        self.checked = 0

    def phase(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench/" + name)

    def request(self, number, question):
        """One first-run request; returns what the window records of it."""
        import jax

        hooks = self.hooks
        with self.phase("reset"):
            hooks.drop_derived_answers()
        fallbacks = self.trap.count
        record = {"question": question, "failed": False, "answered": True, "dispatches": None}
        answer = buffers = None
        counting = hooks.count_dispatches() if self.count_dispatches else contextlib.nullcontext()
        start = time.perf_counter()
        try:
            with counting as stats:
                with self.phase("api_call"):
                    answer = self.cell.questions[question].run(self.pd, self.frame)
                    hooks.execute(answer)
                with self.phase("execute_wait"):
                    buffers = hooks.device_buffers(answer)
                    jax.block_until_ready([b for _, b, _ in buffers])
            if stats is not None:
                record["dispatches"] = stats.dispatches
        except hooks.NotOnDevice as err:
            record["failed"] = True
            record["why"] = str(err)
        except Exception:  # noqa: BLE001 - a request that raises is counted, not fatal
            record["failed"] = True
            record["answered"] = False
            record["why"] = traceback.format_exc(limit=4)
        record["wall_s"] = time.perf_counter() - start
        record["fallbacks"] = self.trap.count - fallbacks
        if record["fallbacks"]:
            record["failed"] = True
            record["why"] = f"fallbacks: {self.trap.seen}"
        with self.phase("between_requests"):
            if record["answered"]:
                self.keep_evidence(number, question, answer, buffers)
            del answer, buffers
        return record

    def keep_evidence(self, number, question, answer, buffers):
        """Keeps the answer whole while it and the kept total allow; an answer
        too large, or one that would pass the total, is sampled or passed over
        as the sampler's turn says.  (No buffers: the answer left the device,
        the request has failed, and what there is of it is kept.)"""
        nbytes = sum(b.nbytes for _, b, _ in buffers) if buffers else 0
        if buffers is None or (nbytes <= self.keep_bytes and self.kept_bytes + nbytes <= KEEP_TOTAL_BYTES):
            self.kept.append((number, question, answer))
            self.kept_bytes += nbytes
            return
        if not self.sampler.due(question):
            self.unsampled += 1
            return
        self.sampled.append((number, question, self.sampler.take(buffers)))

    def warm_sampler(self, names, blocks):
        """Set-up's last step.  Where ``blocks`` blocks (``names``: a block's
        questions) of answers like the first pass's would pass the kept total,
        the window will sample answers of shapes that no sample has been taken
        of: each kept answer is looked into once now, so that the sampler's
        program for its shape compiles here and not in the window."""
        buffers = {}
        for _, question, answer in self.kept:
            with contextlib.suppress(self.hooks.NotOnDevice):
                buffers[question] = self.hooks.device_buffers(answer)
        nbytes = {q: sum(b.nbytes for _, b, _ in bufs) for q, bufs in buffers.items()}
        if self.kept_bytes + blocks * sum(nbytes.get(q, 0) for q in names) <= KEEP_TOTAL_BYTES:
            return 0
        for bufs in buffers.values():
            self.sampler.take(bufs)
        return len(buffers)


class Profiler:
    """``jax.profiler`` over the traced part of a window; while it records,
    ``Run.phase`` writes the harness's phases into the trace."""

    def __init__(self, run):
        self.run = run

    def start(self):
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        self.run.tracing = True

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        self.run.tracing = False


def block_seconds(first, names):
    """What the first pass says a block of the window will take: ``first``
    holds the first pass's request of each question (what it spent compiling
    does not come again), ``names`` a block's questions."""
    wall_s = {r["question"]: max(0.0, r["wall_s"] - r["compile_s"]) for r in first}
    return sum(wall_s[q] for q in names)


def trace_from_block(block_s, seconds):
    """The block of the window before which a traced run starts the profiler:
    the second (1), so that the trace holds steady requests, or the first (0)
    where the first pass says that one block alone (``block_s``) reaches
    ``seconds``: the window would close with it."""
    return 0 if block_s >= seconds else 1


def drive_window(ask, order, block, seconds, profiler, from_block, clock=time.perf_counter):
    """The window: the questions of ``order`` through ``ask(number, question)``
    until the first block that ends at or after ``seconds``, so that every
    window holds the questions in the cell's proportions.  With a ``profiler``
    (``--trace 1``) it records from block ``from_block`` until the first request
    that ends TRACE_SECONDS later, and the window does not close before it has
    started: where the first block outlasts ``seconds`` against the first pass's
    word, the window stays open for one block more and that one is traced.
    Returns the requests' records, the traced questions and the window's seconds."""
    requests, traced = [], []
    tracing = False
    trace_started = None
    window_start = clock()
    while True:
        number = len(requests)
        if profiler is not None and trace_started is None and number >= from_block * block:
            profiler.start()
            tracing = True
            trace_started = clock()
        record = ask(number, next(order))
        requests.append(record)
        now = clock()
        if tracing:
            traced.append(record["question"])
            if now - trace_started >= TRACE_SECONDS:
                profiler.stop()
                tracing = False
        if now - window_start >= seconds and len(requests) % block == 0:
            if profiler is None or trace_started is not None:
                break
    window_s = clock() - window_start
    if tracing:
        profiler.stop()
    return requests, traced, window_s


def judge(run, cell, host, with_control):
    """Every answer the run kept or sampled against the same question file
    under plain pandas on the same columns; returns the tallies of the program
    and (where asked for) of the lower-precision control.

    A question file that says ``ROW_LOCAL = True`` (row i of its answer comes
    from row i of the table alone) and whose answers were all sampled is asked,
    under pandas, of the sampled rows only."""
    import pandas

    import compare

    # what the program answered, as a user reads it; then its state goes
    kept = [(number, q, run.hooks.to_host(answer)) for number, q, answer in run.kept]
    run.checked = len(kept) + len(run.sampled)
    run.kept = []
    run.frame = None
    gc.collect()

    table = pandas.DataFrame(host, copy=False)
    tally = compare.Tally()
    control = compare.Tally() if with_control else None
    lower = compare.lower_precision_frame(table) if with_control else None
    for question, module in cell.questions.items():
        answers = [(n, got) for n, q, got in kept if q == question]
        samples = [(n, got) for n, q, got in run.sampled if q == question]
        row_local = getattr(module, "ROW_LOCAL", False) and not answers
        whole = want = positions = None
        if row_local:
            positions = run.sampler.host_positions(len(table))
            want = compare.sampled(module.run(pandas, table.iloc[positions]), len(table))
        else:
            whole = module.run(pandas, table)
            if samples:
                positions = run.sampler.host_positions(len(whole))
                want = compare.sampled(whole.iloc[positions], len(whole))
        for number, got in answers:
            compare.compare_whole(tally, got, whole, f"request {number} {question}")
        for number, got in samples:
            compare.compare_sampled(tally, got, want, f"request {number} {question}")
        if not with_control:
            continue
        if row_local:
            said = compare.sampled(module.run(pandas, lower.iloc[positions]), len(lower))
        else:
            said_whole = compare.lower_precision_answer(module.run(pandas, lower))
            if answers:
                compare.compare_whole(control, said_whole, whole, f"control {question}")
            said = compare.sampled(said_whole.iloc[positions], len(said_whole)) if samples else None
        if samples:
            said["rows"] = compare.lower_precision_rows(said["rows"])
            compare.compare_sampled(control, said, want, f"control {question}")
    return tally, control


def measure(cell, args, hooks, pd, trap, t0):
    """Set-up, window and comparison of one run; returns the result object."""
    import jax

    import compare
    import traffic

    run = Run(cell, args, hooks, pd, trap)
    rows = cell.config["rows"]
    if args.rehearse:
        rows = cell.config["rehearse_rows"]
        run.keep_bytes = 1 << 20  # so that a rehearsal samples its large answers too
    config = dict(cell.config, rows=rows)
    least_bytes = {n: q.least_bytes(config) for n, q in cell.questions.items()}

    t = time.perf_counter()
    host = cell.dataset.make(args.seed, config, rows)
    log(phase="generate", wall_s=time.perf_counter() - t, rows=rows)

    t = time.perf_counter()
    run.frame = hooks.ingest(pd, host)
    frame_buffers = hooks.device_buffers(run.frame, cell.config["host_columns"])
    jax.block_until_ready([b for _, b, _ in frame_buffers])
    ingest = {
        "wall_s": time.perf_counter() - t,
        "device_bytes": sum(b.nbytes for _, b, _ in frame_buffers),
    }
    del frame_buffers
    log(phase="ingest", **ingest)

    # the first pass: every question once, in the file's order, as the window
    # will ask it; compiles or loads every program the window uses
    compiles0, compile_s0 = hooks.compile_totals()
    first = []
    for i, question in enumerate(cell.questions):
        compiled_s = hooks.compile_totals()[1]
        record = run.request(-1 - i, question)
        record["compile_s"] = hooks.compile_totals()[1] - compiled_s
        first.append(record)
    compiles1, compile_s1 = hooks.compile_totals()
    first_pass = {
        "wall_s": sum(r["wall_s"] for r in first),
        "compiles": compiles1 - compiles0,
        "compile_s": compile_s1 - compile_s0,
        "failed": sum(1 for r in first if r["failed"]),
    }
    log(phase="first_pass", **first_pass)
    names = traffic.block(cell.file)
    block_s = block_seconds(first, names)
    looked_into = run.warm_sampler(names, 1 + int(args.seconds / block_s) if block_s > 0 else sys.maxsize)
    log(phase="warm_sampler", predicted_block_s=block_s, looked_into=looked_into)
    gc.collect()

    # the window
    profiler = Profiler(run) if args.trace else None
    from_block = trace_from_block(block_s, args.seconds) if args.trace else None
    setup_s = time.perf_counter() - t0
    compiles0, _ = hooks.compile_totals()
    requests, traced, window_s = drive_window(
        run.request, traffic.requests(cell.file, args.seed), len(names), args.seconds, profiler, from_block
    )
    if args.trace and not traced:
        print("run.py: the harness's own fault: --trace 1 and the window closed with no request traced", file=sys.stderr)
        raise SystemExit(2)
    compiles1, _ = hooks.compile_totals()
    log(phase="window", wall_s=window_s, requests=[[r["question"], round(r["wall_s"], 6)] for r in requests])

    in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peak_bytes = max((p for p in in_use if p), default=None)
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }

    peaks = peaks_of(device["kind"], args.rehearse)
    trace = None
    breakdown = None
    if args.trace and traced:
        import trace_reduce

        t = time.perf_counter()
        try:
            trace = trace_reduce.reduce(TRACE_DIR)
        except (FileNotFoundError, ValueError) as err:
            # a CPU rehearsal's trace has no TPU plane: the readers then find nothing
            log(phase="trace", unread=str(err))
        else:
            trace["questions"] = traced
            device["busy_s"] = trace["busy_all_s"]
            device["window_s"] = trace["window_s"]
            breakdown = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
            log(phase="trace", read_s=time.perf_counter() - t, requests=trace["requests"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    t = time.perf_counter()
    tally, control = judge(run, cell, host, args.control)
    reference_s = time.perf_counter() - t
    unanswered = sum(1 for r in requests + first if not r["answered"])
    breaks = guarantee_breaks(requests + first, trap, trace, least_bytes, peaks)
    if args.trace and not args.rehearse and not (trace and trace["busy_s"]):
        breaks["traced_window_without_device_work"] = 1
    correct, compared = compare.verdict(tally, unanswered, sum(breaks.values()), cell.file["limits"])
    log(phase="reference", wall_s=reference_s, notes=tally.notes, guarantee_breaks=breaks)

    obs = {
        "cell": cell.name,
        "requests": requests,
        "completed": len(requests),
        "window_s": window_s,
        "setup_s": setup_s,
        "first_pass": first_pass,
        "ingest": ingest,
        "peak_bytes": peak_bytes,
        "fallbacks": trap.count,
        "compiles_in_window": compiles1 - compiles0,
        "least_bytes": least_bytes,
        "peaks": peaks,
        "trace": trace,
    }
    metrics = {}
    for metric in cell.metrics("per_layer" if args.trace else "end_to_end"):
        value = load_module(BENCH, "metrics", metric["name"] + ".py").read(obs)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    walls = {q: [r["wall_s"] for r in requests if r["question"] == q] for q in cell.questions}
    result = {
        "correct": bool(correct),
        "attempted": len(requests),
        "failed": sum(1 for r in requests if r["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    result["run"] = {
        "workload": cell.name,
        "seed": args.seed,
        "seconds": args.seconds,
        "window_s": window_s,
        "reference_s": reference_s,
        "checked": run.checked,
        "kept_whole": run.checked - len(run.sampled),
        "large_answers_not_in_the_sample": run.unsampled,
        "traced_requests": len(traced),
        "trace_from_block": from_block,
        "device_programs": trace["device_programs"] if trace else None,
        "between_requests_s_per_query": (window_s - sum(r["wall_s"] for r in requests)) / len(requests),
        "median_wall_s_by_question": {q: statistics.median(w) for q, w in walls.items() if w},
        "why_failed": [r.get("why") for r in requests + first if r["failed"]][:3],
        "fallbacks_seen": trap.seen,
        "guarantee_breaks": breaks,
        "host_cpus": HOST_CPUS,
    }
    if args.rehearse:
        result["rehearsal"] = {"comparison_passed": bool(correct), "rows": rows}
        result["correct"] = False
    if control is not None:
        result["control"] = compare.verdict(control, 0, 0, cell.file["limits"])[1]
    result["compared"] = compared
    return result


def guarantee_breaks(requests, trap, trace, least_bytes, peaks):
    """What the configuration's guarantees forbid, counted over the first pass
    and the window; any of it makes the run not correct.

    * ``failed_requests``: a request that raised, fell back to pandas (or fired
      a retry, recovery or degraded path), or left its answer off the device;
    * ``fallbacks_elsewhere``: the same traps firing outside any request;
    * ``requests_under_least_time``: a traced request during which the device
      worked for less than its question's least bytes take at the peak
      bandwidth: it did not read the table, so a memo answered it
      (``traced_window_without_device_work``, set by the caller: the same of
      the whole traced window).
    """
    breaks = {
        "failed_requests": sum(1 for r in requests if r["failed"]),
        "fallbacks_elsewhere": trap.count - sum(r["fallbacks"] for r in requests),
        "requests_under_least_time": 0,
    }
    if trace and peaks:
        for question, busy_s in zip(trace["questions"], trace["busy_s_per_request"]):
            least_s = least_bytes[question] / peaks["hbm_bytes_per_s"]
            if least_s - busy_s > 1e-6:  # the trace resolves a microsecond
                breaks["requests_under_least_time"] += 1
    return breaks


def peaks_of(kind, rehearse):
    table = load_json(BENCH, "peaks.json")
    if kind not in table:
        if rehearse:
            return None  # a rehearsal off the chip reports no share of a peak
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true", help="tiny rows, any platform, ends not correct")
    parser.add_argument("--control", action="store_true", help="also read the lower-precision control")
    return parser.parse_args(argv)


def start(args):
    """Everything that is done once in a process: the cell's files, the chip,
    the program.  Returns ``(cell, hooks, pd, trap)`` or exits."""
    cell = Cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell.spec["run_seconds"])

    import jax

    devices = jax.devices()
    if not args.rehearse:
        if devices[0].platform != "tpu":
            print(f"run.py: platform is {devices[0].platform!r}, not 'tpu'", file=sys.stderr)
            raise SystemExit(2)
        if len(devices) != cell.file["chips"]:
            print(f"run.py: the cell asks for {cell.file['chips']} chip(s), JAX reports {len(devices)}", file=sys.stderr)
            raise SystemExit(2)

    import program_hooks as hooks

    if hooks.options_set():
        print(f"run.py: the cells run the program as it comes; unset {hooks.options_set()}", file=sys.stderr)
        raise SystemExit(2)
    pd = hooks.load(ROOT)
    trap = hooks.FallbackTrap()
    trap.install()
    return cell, hooks, pd, trap


def main(argv=None):
    args = parse(argv)
    cell, hooks, pd, trap = start(args)
    result = measure(cell, args, hooks, pd, trap, T0)
    for name, entry in result["compared"].items():
        print(f"compared {name} = {entry['value']!r} limit {entry['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
