"""``h2o_q5_sum_by_id6_1e6`` (PR 31) from the committed files, rehearsed here on
the CPU, and the two metrics that came with it read from made-up observations.
On the CPU the program takes its scatter forms (they are fine there), so the
rehearsal proves the cell's files and the comparison; the form a TPU takes is
proved in ``tests/test_groupby.py``."""

import json
import os

import pytest

import run
import test_least_bytes
from conftest import BENCH, ROOT

CELL = "h2o_q5_sum_by_id6_1e6"
CONFIG = "h2o-groupby-g1-1e8-1e2-id6"
# id6, v1, v2, v3 of 1e8 rows read once; 1e6 groups x (key + 3 sums) written.  The
# hand table of test_least_bytes.py is the benchmark's and gains no line here; its
# check that every question file has one reads the table when it runs, so the
# configuration's question is entered from this file, and checked below.
HAND = {(CONFIG, "q5_sum_by_id6"): 3_200_000_000 + 32_000_000}
test_least_bytes.HAND.update(HAND)


def failed(compared):
    return [name for name, entry in compared.items() if entry["value"] > entry["limit"]]


def test_the_cell_is_committed_under_the_name_the_other_tests_leave_free():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
        spec = json.load(handle)
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as handle:
        cell = json.load(handle)
    assert {k: cell[k] for k in entry} == entry and entry["config"] == CONFIG
    assert cell["questions"] == [{"name": "q5_sum_by_id6", "weight": 1}]
    assert cell["loop"] == {"kind": "closed", "clients": 1} and cell["limits"] == {"float_rel_gap": 1e-10}
    # test_correct.py and test_keep_evidence.py add a cell of this name to a copy
    assert not os.path.exists(os.path.join(BENCH, "workloads", "h2o_q5_sum_by_id6.json"))
    listed = {m["name"]: m.get("workloads") for m in spec["per_layer"]}
    assert listed["kernels.many_groups_roofline"] == [CELL]
    assert listed["groupby.scatter_forms_per_query"] == ["h2o_q4_mean_by_id4", CELL]
    assert CELL not in listed["engine.dispatches_per_query"]


def test_the_configuration_is_the_table_of_q4_grouped_by_its_widest_key():
    """A file and a source of its own, the shapes and guarantees of the H2O table
    that is there, nothing reduced; its plain reference is a copy of q5's."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
        spec = json.load(handle)
    entry = spec["configs"][-1]
    first = spec["configs"][0]
    with open(os.path.join(ROOT, entry["file"])) as handle:
        config = json.load(handle)
    with open(os.path.join(ROOT, first["file"])) as handle:
        table = json.load(handle)
    assert entry["name"] == config["name"] == CONFIG and entry["source"] == config["source"] != first["source"]
    assert entry["reduced"] == config["reduced"] == [] and entry["file"] != first["file"]
    same = ("generator", "rows", "groups_k", "na_percent", "sorted", "schema", "host_columns",
            "device_bytes", "guarantees", "rehearse_rows")
    assert {k: config[k] for k in same} == {k: table[k] for k in same}
    assert config["groups"] == config["rows"] // config["groups_k"] == 1_000_000 and config["key"] == "id6"
    copies = [os.path.join(BENCH, "questions", name, "q5_sum_by_id6.py") for name in (CONFIG, first["name"])]
    assert open(copies[0]).read() == open(copies[1]).read()
    assert os.listdir(os.path.dirname(copies[0])) == ["q5_sum_by_id6.py"]


@pytest.mark.parametrize("config,question", sorted(HAND))
def test_least_bytes_of_the_configurations_question(config, question):
    test_least_bytes.test_least_bytes(config, question)


def test_the_committed_cell_passes_and_its_control_fails(copy):
    result = copy.rehearse(CELL, "--control")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    assert result["rehearsal"]["rows"] // 100 == 2_000  # groups of the rehearsal
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["run"]["kept_whole"] == result["run"]["checked"] == result["attempted"] + 1
    assert "float_rel_gap" in failed(result["control"]), result["control"]
    assert set(result["metrics"]) >= {"query_wall_s", "first_query_s", "setup_s"}
    assert "query_p95_s" not in result["metrics"]


def test_a_traced_rehearsal_counts_the_scatter_forms(copy):
    """One range histogram and a segment sum a value column: four scatters a
    request here, where they are the CPU's forms; none on a TPU."""
    result = copy.rehearse(CELL, "--trace", "1")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    assert result["metrics"]["groupby.scatter_forms_per_query"] == {"value": 4.0, "unit": "count/query"}
    assert result["metrics"]["engine.host_syncs_per_query"]["value"] == 2
    # no device plane in a CPU trace: a share of the chip's peak is not reported
    assert "kernels.many_groups_roofline" not in result["metrics"]
    assert "engine.dispatches_per_query" not in result["metrics"]


def test_half_the_rows_left_out_ends_not_correct_in_this_cell(copy):
    with open(os.path.join(BENCH, "tests", "faults", "q5_half_rows.py")) as handle:
        text = handle.read()
    cell = copy.add_cell("faulty", CELL, ["q5_half_rows"])
    copy.add_file(f"questions/{cell['config']}/q5_half_rows.py", text)
    result = copy.rehearse("faulty")
    assert not result["rehearsal"]["comparison_passed"]
    assert "exact_mismatches" in failed(result["compared"]), result["compared"]


def metric(name):
    return run.load_module(BENCH, "metrics", name + ".py")


LEAST = {"q5_sum_by_id6": 3_232_000_000}
PEAKS = {"hbm_bytes_per_s": 819e9}


def traced(programs, requests=1):
    return {
        "least_bytes": LEAST, "peaks": PEAKS,
        "trace": {"questions": ["q5_sum_by_id6"] * requests, "device_programs": programs},
    }


def test_many_groups_roofline_reads_the_same_work_whatever_implements_it():
    read = metric("kernels.many_groups_roofline").read
    parent = traced([
        ["jit_groupby_segment_agg", 44.2746], ["jit_groupby_scatter_counts", 14.6647],
        ["jit_groupby_range_ids", 0.0118], ["jit_groupby_key_minmax", 0.0068],
    ])
    assert read(parent) == pytest.approx(100 * (3.232e9 / 819e9) / 58.9393)
    change = traced([
        ["jit_groupby_sorted_tiles_sum", 2.4], ["jit_groupby_sorted_tiles_size", 0.6],
        ["jit_groupby_range_ids", 0.0118], ["jit_bench_slices", 0.5],
    ], requests=2)
    assert read(change) == pytest.approx(100 * 2 * (3.232e9 / 819e9) / 3.0)
    # q4's programs are none of these; an untraced run and a rehearsal off the chip report nothing
    assert read(traced([["jit_groupby_masked_scan_smc", 2.4577], ["jit_groupby_pallas_bincount", 0.544]])) is None
    assert read({"least_bytes": LEAST, "peaks": PEAKS, "trace": None}) is None
    assert read(dict(parent, peaks=None)) is None


def test_scatter_forms_per_query_reads_the_programs_own_count(monkeypatch):
    import modin_tpu.observability as observability

    read = metric("groupby.scatter_forms_per_query").read
    obs = {"completed": 2, "requests": [{"wall_s": 0.2}, {"wall_s": 0.3}]}

    def ring(records):
        monkeypatch.setattr(observability, "recent_queries", lambda label=None: records)

    ring([
        {"wall_s": 0.2, "groupby_forms": {"scatter_counts": 1, "segment": 3}},
        {"wall_s": 0.3, "groupby_forms": {"sorted_tiles": 4}},
    ])
    assert read(obs) == 2.0
    ring([{"wall_s": 0.2, "groupby_forms": {"pallas_bincount": 1, "masked_scan": 3}},
          {"wall_s": 0.3, "groupby_forms": {}}])
    assert read(obs) == 0.0
    # the parent of PR 31 keeps the ring and not the count: nothing to report
    ring([{"wall_s": 0.2}, {"wall_s": 0.3}])
    assert read(obs) is None
    ring([])
    assert read(obs) is None
