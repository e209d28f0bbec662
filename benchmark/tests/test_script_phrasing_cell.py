"""``h2o_script_q1_q3_by_category`` (PR 36) from the committed files, rehearsed
here on the CPU, and the two metrics that came with it read from made-up
observations.  On the CPU the program takes its scatter forms (they are fine
there), so the rehearsal proves the cell's files, the script's phrasing on the
normal path and the comparison; the forms a TPU takes are proved in
``tests/test_h2o_script_phrasing.py``."""

import json
import os

import pytest

import run
import test_least_bytes
from conftest import BENCH, ROOT

CELL = "h2o_script_q1_q3_by_category"
CONFIG = "h2o-groupby-g1-1e8-1e2-script"
QUESTIONS = ["q1_sum_v1_by_id1", "q2_sum_v1_by_id1_id2", "q3_sum_v1_mean_v3_by_id3"]
# code widths as pandas holds them (int8, int8, int32) beside the int64 / float64
# values, read once; the groups' keys and aggregates written.  Entered into the
# benchmark's hand table on import, as test_many_groups_cell.py does.
HAND = {
    (CONFIG, QUESTIONS[0]): 900_000_000 + 900,
    (CONFIG, QUESTIONS[1]): 1_000_000_000 + 100_000,
    (CONFIG, QUESTIONS[2]): 2_000_000_000 + 20_000_000,
}
test_least_bytes.HAND.update(HAND)
SCRIPT = "as_index=False, sort=False, observed=True, dropna=False"


def failed(compared):
    return [name for name, entry in compared.items() if entry["value"] > entry["limit"]]


def test_the_cell_is_committed_as_the_issue_names_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
        spec = json.load(handle)
    entry = spec["workloads"][-1]
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as handle:
        cell = json.load(handle)
    assert entry["name"] == CELL and {k: cell[k] for k in entry} == entry
    assert entry["config"] == CONFIG and entry["chips"] == 1 and entry["traffic"] == "script_q1_q3_first_run"
    assert cell["questions"] == [{"name": q, "weight": 1} for q in QUESTIONS]
    assert cell["loop"] == {"kind": "closed", "clients": 1} and cell["limits"] == {"float_rel_gap": 1e-10}
    listed = {m["name"]: m.get("workloads") for m in spec["per_layer"]}
    assert listed["groupby.scatter_forms_per_query"][-1] == CELL
    assert listed["kernels.first_seen_roofline"] == listed["qc.groupby_assemble_ms_per_query"] == [CELL]
    for elsewhere in ("engine.dispatches_per_query", "kernels.many_groups_roofline"):
        assert CELL not in listed[elsewhere]
    assert CELL not in next(m for m in spec["end_to_end"] if m["name"] == "query_p95_s")["workloads"]
    new = [m for m in spec["per_layer"] if m["name"] in ("kernels.first_seen_roofline", "qc.groupby_assemble_ms_per_query")]
    assert [m["moves"] for m in new] == ["query_wall_s"] * 2
    assert [(m["source"], m["layer"]) for m in new] == [
        ("device_trace", "kernels"), ("program_counter", "API -> query compiler"),
    ]


def test_the_configuration_is_the_h2o_table_asked_as_the_script_asks():
    """A file, a source and a question directory of its own; the table, generator
    and sizes of the H2O configuration that is there; nothing reduced."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
        spec = json.load(handle)
    entry, first = spec["configs"][-1], spec["configs"][0]
    with open(os.path.join(ROOT, entry["file"])) as handle:
        config = json.load(handle)
    with open(os.path.join(ROOT, first["file"])) as handle:
        table = json.load(handle)
    assert entry["name"] == config["name"] == CONFIG and entry["file"] not in [c["file"] for c in spec["configs"][:-1]]
    assert entry["source"] == config["source"] and entry["source"] not in [c["source"] for c in spec["configs"][:-1]]
    assert len(entry["source"]) == 196 and SCRIPT in entry["source"] and "questions 1-3" in entry["source"]
    assert entry["reduced"] == config["reduced"] == []
    same = ("generator", "rows", "groups_k", "na_percent", "sorted", "schema", "rehearse_rows")
    assert {k: config[k] for k in same} == {k: table[k] for k in same}
    # the codes of the three category keys are resident once a question has grouped by them
    assert config["device_bytes"] == table["device_bytes"] + config["rows"] * (1 + 1 + 4) == 5_400_000_000
    # ... which is the first pass, not ingest (the program uploads a category
    # column's codes with its first use as a key, so that the cells whose
    # questions never read id1-id3 hold what they held): ingest may leave them
    assert config["host_columns"] == ["id1", "id2", "id3"]
    assert any("order of first appearance" in g and "keys as columns" in g for g in config["guarantees"])
    assert any("category keys as codes" in g and "resident" in g for g in config["guarantees"])
    assert sorted(os.listdir(os.path.join(BENCH, "questions", CONFIG))) == [q + ".py" for q in QUESTIONS]


def test_the_questions_are_the_scripts_lines_letter_for_letter():
    lines = {
        QUESTIONS[0]: "x.groupby('id1', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum'})",
        QUESTIONS[1]: "x.groupby(['id1', 'id2'], as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum'})",
        QUESTIONS[2]: "x.groupby('id3', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'sum', 'v3': 'mean'})",
    }
    for question, line in lines.items():
        with open(os.path.join(BENCH, "questions", CONFIG, question + ".py")) as handle:
            assert "    return " + line + "\n" in handle.read()


@pytest.mark.parametrize("config,question", sorted(HAND))
def test_least_bytes_of_the_scripts_questions(config, question):
    test_least_bytes.test_least_bytes(config, question)


def test_the_committed_cell_passes_and_its_control_fails(copy):
    result = copy.rehearse(CELL, "--control")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    assert result["rehearsal"]["rows"] == 200_000
    assert result["failed"] == 0 and result["attempted"] >= 3 and result["attempted"] % 3 == 0
    assert result["run"]["fallbacks_seen"] == [] and result["run"]["why_failed"] == []
    assert result["run"]["kept_whole"] == result["run"]["checked"] == result["attempted"] + 3
    assert "float_rel_gap" in failed(result["control"]), result["control"]
    assert set(result["metrics"]) >= {"query_wall_s", "first_query_s", "setup_s"}
    assert "query_p95_s" not in result["metrics"]


def test_a_traced_rehearsal_reads_the_programs_records(copy):
    """No fallback on the script's phrasing, no codes uploaded in a request, the
    host's assembling time read from the program's own spans; on the CPU the
    histogram and the sums are scatters (seven a block of three questions)."""
    result = copy.rehearse(CELL, "--trace", "1")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    metrics = result["metrics"]
    assert metrics["qc.fallbacks"]["value"] == 0
    assert metrics["groupby.scatter_forms_per_query"]["value"] == pytest.approx(7 / 3)
    assert metrics["engine.host_syncs_per_query"]["value"] == 2  # the histogram, the order's check
    assert metrics["frame.h2d_bytes_per_query"]["value"] < 200_000  # a remap table, never 2e5 codes
    assert metrics["qc.groupby_assemble_ms_per_query"]["value"] > 0
    assert metrics["qc.groupby_assemble_ms_per_query"]["unit"] == "ms/query"
    # no device plane in a CPU trace: a share of the chip's peak is not reported
    assert "kernels.first_seen_roofline" not in metrics
    assert "engine.dispatches_per_query" not in metrics and "kernels.many_groups_roofline" not in metrics


def test_groups_in_key_order_end_not_correct_in_this_cell(copy):
    with open(os.path.join(BENCH, "tests", "faults", "q1_key_sorted.py")) as handle:
        text = handle.read()
    cell = copy.add_cell("faulty", CELL, ["q1_key_sorted"])
    copy.add_file(f"questions/{cell['config']}/q1_key_sorted.py", text)
    result = copy.rehearse("faulty")
    assert not result["rehearsal"]["comparison_passed"]
    assert failed(result["compared"]) == ["exact_mismatches"], result["compared"]


def metric(name):
    return run.load_module(BENCH, "metrics", name + ".py")


PEAKS = {"hbm_bytes_per_s": 819e9}


def traced(programs, questions):
    return {
        "cell": CELL, "peaks": PEAKS, "least_bytes": {},
        "trace": {"questions": questions, "device_programs": programs},
    }


def test_first_seen_roofline_reads_the_key_codes_over_the_order_programs():
    module = metric("kernels.first_seen_roofline")
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as handle:
        config = json.load(handle)
    assert [module.key_code_bytes(q, config) for q in QUESTIONS] == [100_000_000, 200_000_000, 400_000_000]
    assert [module.code_width(n) for n in (100, 127, 128, 32_767, 32_768, 1_000_000)] == [1, 1, 2, 2, 4, 4]
    block = traced([
        ["jit_groupby_sorted_tiles_sum", 2.1], ["jit_groupby_first_seen", 0.30],
        ["jit_groupby_first_seen_take", 0.05], ["jit_groupby_category_ids", 0.01], ["jit_bench_slices", 0.5],
    ], QUESTIONS * 2)
    assert module.read(block) == pytest.approx(100 * 2 * (7e8 / 819e9) / 0.35)
    # the parent has no such program; an untraced run and a rehearsal off the chip report nothing
    assert module.read(traced([["jit_groupby_sorted_tiles_sum", 2.1]], QUESTIONS)) is None
    assert module.read(dict(block, trace=None)) is None
    assert module.read(dict(block, peaks=None)) is None


def test_assemble_ms_reads_the_programs_own_spans(monkeypatch):
    import modin_tpu.observability as observability

    read = metric("qc.groupby_assemble_ms_per_query").read
    obs = {"completed": 2, "requests": [{"wall_s": 0.2}, {"wall_s": 0.3}]}

    def ring(records):
        monkeypatch.setattr(observability, "recent_queries", lambda label=None: records)

    ring([
        {"wall_s": 0.2, "host_self_s": {"QUERY-COMPILER": 0.004, "GROUPBY-ASSEMBLE": 0.0010}},
        {"wall_s": 0.3, "host_self_s": {"QUERY-COMPILER": 0.004, "GROUPBY-ASSEMBLE": 0.0005}},
    ])
    assert read(obs) == pytest.approx(0.75)
    # the parent of PR 36 keeps the records and has no such span: nothing to report
    ring([{"wall_s": 0.2, "host_self_s": {"QUERY-COMPILER": 0.004}}, {"wall_s": 0.3, "host_self_s": {}}])
    assert read(obs) is None
    ring([])
    assert read(obs) is None
