"""The benchmark's own tests run here on the CPU: ``python3 -m pytest benchmark/tests -q``
from the root of the repo.  They are not part of the repo's tier-1 suite."""

import json
import os
import shutil
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]


class Copy:
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` beside a link to the
    program, to which a test adds files and entries without editing a file
    that is there."""

    def __init__(self, root):
        self.root = str(root)
        shutil.copytree(BENCH, os.path.join(self.root, "benchmark"), ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), self.root)
        os.symlink(os.path.join(ROOT, "modin_tpu"), os.path.join(self.root, "modin_tpu"))

    def add_file(self, relative, text):
        path = os.path.join(self.root, "benchmark", relative)
        assert not os.path.exists(path), f"{relative} is there already: a test only adds"
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as handle:
            handle.write(text)

    def add_entries(self, group, entries):
        path = os.path.join(self.root, "BENCHMARK.json")
        with open(path) as handle:
            spec = json.load(handle)
        spec[group].extend(entries)
        with open(path, "w") as handle:
            json.dump(spec, handle)

    def add_cell(self, name, like, questions):
        """A new cell on ``like``'s configuration asking ``questions``."""
        with open(os.path.join(BENCH, "workloads", like + ".json")) as handle:
            cell = json.load(handle)
        cell.update(name=name, traffic=name + "_mix", questions=[{"name": q, "weight": 1} for q in questions])
        self.add_file(f"workloads/{name}.json", json.dumps(cell))
        entry = {k: cell[k] for k in ("name", "config", "traffic", "chips", "why")}
        self.add_entries("workloads", [entry])
        return cell

    def rehearse(self, cell, *extra, keep_total_bytes=None):
        """``run.py --rehearse`` on the CPU; returns the result line's object.
        ``keep_total_bytes`` stands in for ``run.KEEP_TOTAL_BYTES`` (no
        rehearsal's answers reach the 1 GB that is committed)."""
        env = {k: v for k, v in os.environ.items() if not k.startswith("MODIN_TPU_")}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH="")
        env.pop("XLA_FLAGS", None)
        program = [os.path.join(self.root, "benchmark", "run.py")]
        if keep_total_bytes is not None:
            program = ["-c", "import sys; sys.path.insert(0, 'benchmark'); import run; "
                       f"run.KEEP_TOTAL_BYTES = {int(keep_total_bytes)}; sys.exit(run.main(sys.argv[1:]))"]
        done = subprocess.run(
            [sys.executable, *program, "--workload", cell,
             "--seed", "2147483659", "--seconds", "1", "--rehearse", *extra],
            capture_output=True, text=True, env=env, cwd=self.root, timeout=600,
        )
        assert done.returncode == 1, done.stderr[-3000:]
        self.last_stderr = done.stderr
        return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.fixture
def copy(tmp_path):
    return Copy(tmp_path)
