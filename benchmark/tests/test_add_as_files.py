"""A cell, a question and a per-layer metric come in as new files and new
entries of ``BENCHMARK.json``: no file that is there is edited, and
``run.py`` finds all three by name."""

import hashlib
import os


def digests(root):
    out = {}
    for folder, _, names in os.walk(root):
        if "__pycache__" in folder:
            continue
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as handle:
                out[os.path.relpath(path, root)] = hashlib.sha256(handle.read()).hexdigest()
    return out


def test_new_cell_question_and_metric_are_found_by_name(copy):
    before = digests(os.path.join(copy.root, "benchmark"))
    cell = copy.add_cell("asv_max_only", "asv_time_arithmetic", ["max"])
    copy.add_file(
        f"questions/{cell['config']}/max.py",
        "def run(pd, df):\n    return df.max()\n\n\n"
        "def least_bytes(config):\n    return 8 * config['columns'] * config['rows']\n",
    )
    copy.add_file(
        "metrics/window.requests.py",
        "def read(obs):\n    return obs['completed']\n",
    )
    copy.add_entries("per_layer", [{
        "name": "window.requests", "unit": "count", "better": "higher", "source": "program_counter",
        "layer": "API -> query compiler", "moves": "query_wall_s", "workloads": ["asv_max_only"],
    }])
    result = copy.rehearse("asv_max_only", "--trace", "1")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    assert result["metrics"]["window.requests"]["value"] == result["attempted"] > 0
    after = digests(os.path.join(copy.root, "benchmark"))
    assert {k: v for k, v in after.items() if k in before} == before, "a file that was there changed"
    assert set(after) - set(before) == {
        "workloads/asv_max_only.json", f"questions/{cell['config']}/max.py", "metrics/window.requests.py",
    }
    # the cells that were there do not report the new cell's metric
    assert "window.requests" not in copy.rehearse("asv_time_arithmetic", "--trace", "1")["metrics"]
