"""From a profiler trace (``*.xplane.pb``) to the numbers the readers use.

Read with nothing but JAX (``jax.profiler.ProfileData``).  What is taken:

* device operations: the events of the line ``XLA Ops`` of every plane named
  ``/device:TPU:<n>`` (one plane a chip), each named ``<program>/<op>`` after
  the event of the line ``XLA Modules`` it starts in;
* device programs: the events of that line ``XLA Modules`` themselves, one a
  launch.  A program's operations nest (a ``while`` holds its body's), so
  their seconds do not add up to the program's: these do;
* the harness's own phases: host events named ``bench/<phase>``, written by
  ``jax.profiler.TraceAnnotation`` around each request (``reset``,
  ``api_call``, ``execute_wait``, ``between_requests``).

The traced window runs from the first phase's start to the last phase's end.
Busy time is the union of a chip's operation intervals inside it, averaged
over the chips; a request is one ``reset`` .. ``between_requests`` run of
phases; idle time is split by the phase the host was in.

The harness looks into large answers on the device (``run.Sampler``: programs
``jit_bench_*``).  That work is no part of a question: ``busy_s`` and the
per-request split hold the program's operations alone, ``harness_busy_s`` the
harness's, and ``busy_all_s`` the union of both, which the idle time and the
breakdown are taken from.
"""

import bisect
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PHASE_PREFIX = "bench/"
FIRST_PHASE = "reset"
HARNESS_PROGRAMS = "jit_bench_"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_events(path):
    """``(ops, phases)`` of ``read_planes``."""
    return read_planes(path)[:2]


def read_planes(path):
    """``(ops, phases, programs)``: ``{plane: [(start_ns, end_ns, name)]}`` of
    device operations, ``[(start_ns, end_ns, phase)]`` of the harness's phases
    and ``{plane: [(start_ns, end_ns, program)]}`` of the programs' launches."""
    from jax.profiler import ProfileData

    ops, phases, programs = {}, [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE) and plane.name[len(DEVICE_PLANE):].isdigit():
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                modules = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("(")[0])
                    for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines else ())
                )
                programs[plane.name] = modules
                starts = [m[0] for m in modules]
                named = ops.setdefault(plane.name, [])
                for ev in lines[OPS_LINE].events:
                    at = bisect.bisect_right(starts, ev.start_ns) - 1
                    inside = at >= 0 and ev.start_ns < modules[at][1]
                    op = ev.name.split(" = ")[0].lstrip("%")
                    named.append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, (modules[at][2] if inside else "?") + "/" + op)
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PHASE_PREFIX):
                        phases.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name[len(PHASE_PREFIX):])
                        )
    phases.sort()
    return ops, phases, programs


def union(intervals, lo, hi):
    """Disjoint sorted intervals covering ``intervals`` clipped to [lo, hi]."""
    out = []
    for start, end in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def covered(disjoint, lo, hi):
    """Length of ``disjoint`` (sorted, disjoint) inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in disjoint)


def reduce_events(ops, phases, programs=None):
    if not phases:
        raise ValueError("the trace holds no bench/ phase: nothing marks the window")
    if not ops:
        raise ValueError(f"the trace holds no {DEVICE_PLANE}<n> plane with a line {OPS_LINE!r}")
    lo, hi = phases[0][0], max(end for _, end, _ in phases)
    starts = [s for s, _, name in phases if name == FIRST_PHASE] or [lo]
    bounds = list(zip(starts, starts[1:] + [hi]))
    chips = len(ops)
    busy_ns = 0.0
    per_request = [0.0] * len(bounds)
    idle_by_phase = {}
    busy_all_ns = 0.0
    for every in ops.values():
        ours = union([(s, e) for s, e, name in every if not name.startswith(HARNESS_PROGRAMS)], lo, hi)
        busy = union([(s, e) for s, e, _ in every], lo, hi)
        busy_ns += covered(ours, lo, hi) / chips
        busy_all_ns += covered(busy, lo, hi) / chips
        for i, (a, b) in enumerate(bounds):
            per_request[i] += covered(ours, a, b) / chips
        attributed = 0.0
        for a, b, name in phases:
            idle = (b - a) - covered(busy, a, b)
            idle_by_phase[name] = idle_by_phase.get(name, 0.0) + idle / chips
            attributed += idle / chips
        outside = (hi - lo) - covered(busy, lo, hi) - attributed * chips
        if outside > 0:
            idle_by_phase["outside_phases"] = idle_by_phase.get("outside_phases", 0.0) + outside / chips

    def top(table):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:10]
        return [[name, ns / 1e9] for name, ns in ranked]

    def top_by_name(events_by_plane):
        table = {}
        for events in events_by_plane.values():
            for s, e, name in events:
                inside = min(e, hi) - max(s, lo)
                if inside > 0:
                    table[name] = table.get(name, 0.0) + inside / chips
        return top(table)

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "busy_all_s": busy_all_ns / 1e9,
        "harness_busy_s": (busy_all_ns - busy_ns) / 1e9,
        "requests": len(bounds),
        "busy_s_per_request": [ns / 1e9 for ns in per_request],
        "device_ops": top_by_name(ops),
        "device_programs": top_by_name(programs or {}),
        "idle_gaps": top(idle_by_phase),
        "chips": chips,
    }


def reduce(trace_dir):
    return reduce_events(*read_planes(find_xplane(trace_dir)))
