"""Read the two ends a limit is set from, on the chip, in one process:

    python3 benchmark/tests/read_limits.py --workload <cell> --seeds 101,102,... --seconds 4

For each seed a whole run of the cell (its table, ingest, first pass, a short
window at the cell's own load, the comparison) with the lower-precision
control read beside it (``--downcast`` puts the program's own float32 path in
the program's place instead, whose ``compared`` is then a control's reading).
One line a seed, then the largest reading of the
program (the lower end) and the smallest of the control (the upper end).
The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--downcast", action="store_true", help="run the program's own float32 path as the control")
    ours = parser.parse_args()
    lower, upper = {}, {}
    started = None
    for seed in (int(s) for s in ours.seeds.split(",")):
        argv = ["--workload", ours.workload, "--seed", str(seed), "--seconds", str(ours.seconds), "--control"]
        args = run.parse(argv + (["--rehearse"] if ours.rehearse else []))
        if started is None:
            started = run.start(args)
            if ours.downcast:
                started[1].switch_on_float32_storage()
        args.seconds = ours.seconds
        result = run.measure(*started[:1], args, *started[1:], time.perf_counter())
        print(json.dumps({
            "seed": seed, "correct": result["correct"], "attempted": result["attempted"], "failed": result["failed"],
            "compared": {k: v["value"] for k, v in result["compared"].items()},
            "control": {k: v["value"] for k, v in result["control"].items()},
        }), flush=True)
        for name, entry in result["compared"].items():
            lower[name] = max(lower.get(name, 0), entry["value"])
        for name, entry in result["control"].items():
            upper[name] = min(upper.get(name, float("inf")), entry["value"])
    print(json.dumps({"lower_end_program_max": lower, "upper_end_control_min": upper}), flush=True)


if __name__ == "__main__":
    main()
