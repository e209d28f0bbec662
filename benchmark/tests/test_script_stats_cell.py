"""``h2o_script_q6_q7_group_stats`` (PR 39) from the committed files, rehearsed
here on the CPU, and the two metrics that came with it read from made-up
observations.  On the CPU the program takes its scatter forms (they are fine
there), so the rehearsal proves the cell's files, the script's phrasing on the
normal path and the comparison; the forms a TPU takes are proved in
``tests/test_h2o_script_stats.py``.  Entries are looked up by name, not by
their place in a list, so a later cell or configuration leaves these tests as
they are."""

import json
import os

import pytest

import run
import test_least_bytes
from conftest import BENCH, ROOT

CELL = "h2o_script_q6_q7_group_stats"
CONFIG = "h2o-groupby-g1-1e8-1e2-script-advanced"
SCRIPT_CONFIG = "h2o-groupby-g1-1e8-1e2-script"
QUESTIONS = ["q6_median_sd_v3_by_id4_id5", "q7_max_v1_minus_min_v2_by_id3"]
# q6: id4, id5 and v3 read once, 1e4 rows of two keys, median and sd written;
# q7: id3's int32 codes, v1 and v2 read once, 1e6 rows of key code and range
# written.  Entered into the benchmark's hand table on import, as
# test_many_groups_cell.py and test_script_phrasing_cell.py do.
HAND = {
    (CONFIG, QUESTIONS[0]): 2_400_000_000 + 320_000,
    (CONFIG, QUESTIONS[1]): 2_000_000_000 + 12_000_000,
}
test_least_bytes.HAND.update(HAND)
SCRIPT = "as_index=False, sort=False, observed=True, dropna=False"
METRICS = ("kernels.group_median_roofline", "kernels.group_extrema_var_roofline")


def failed(compared):
    return [name for name, entry in compared.items() if entry["value"] > entry["limit"]]


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
        return json.load(handle)


def by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def test_the_cell_is_committed_as_the_issue_names_it():
    bench = spec()
    entry = by_name(bench["workloads"], CELL)
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as handle:
        cell = json.load(handle)
    assert {k: cell[k] for k in entry} == entry
    assert entry["config"] == CONFIG and entry["chips"] == 1 and entry["traffic"] == "script_q6_q7_first_run"
    assert len(entry["why"]) <= 200
    assert cell["questions"] == [{"name": q, "weight": 1} for q in QUESTIONS]
    assert cell["loop"] == {"kind": "closed", "clients": 1} and cell["limits"] == {"float_rel_gap": 1e-10}
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert CELL in listed["groupby.scatter_forms_per_query"]
    for name in METRICS:
        assert listed[name] == [CELL]
        metric = by_name(bench["per_layer"], name)
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == (
            "%", "higher", "device_trace", "kernels", "query_wall_s"
        )
    # the generic readers whose layers run here: q7's tiles, both questions'
    # assembly; the first-seen roofline has no least bytes for these questions
    for shared in ("kernels.many_groups_roofline", "qc.groupby_assemble_ms_per_query"):
        assert listed[shared][-1] == CELL
    for elsewhere in ("engine.dispatches_per_query", "kernels.first_seen_roofline"):
        assert CELL not in listed[elsewhere]
    assert CELL not in by_name(bench["end_to_end"], "query_p95_s")["workloads"]
    # the names the harness's own tests give the cells they add stay free
    assert not {"h2o_q5_sum_by_id6", "faulty", "asv_max_only"} & {w["name"] for w in bench["workloads"]}


def test_the_configuration_is_the_script_table_asked_its_advanced_questions():
    """A file, a source and a question directory of its own; the table,
    generator, sizes and guarantees of the script's configuration, with the
    statistics added; nothing reduced."""
    bench = spec()
    entry = by_name(bench["configs"], CONFIG)
    others = [c for c in bench["configs"] if c["name"] != CONFIG]
    with open(os.path.join(ROOT, entry["file"])) as handle:
        config = json.load(handle)
    with open(os.path.join(BENCH, "configs", SCRIPT_CONFIG + ".json")) as handle:
        script = json.load(handle)
    assert entry["name"] == config["name"] and entry["file"] not in [c["file"] for c in others]
    assert entry["source"] == config["source"] and entry["source"] not in [c["source"] for c in others]
    assert entry["source"] == (
        "https://github.com/h2oai/db-benchmark pandas/groupby-pandas.py questions 6-7 as written: "
        "median+sd v3 by [id4,id5]; max v1 - min v2 by id3; G1_1e8_1e2_0_0"
    )
    assert len(entry["source"]) == 154
    assert entry["reduced"] == config["reduced"] == []
    same = ("generator", "rows", "groups_k", "na_percent", "sorted", "schema", "rehearse_rows", "host_columns", "assumed")
    assert {k: config[k] for k in same if k != "assumed"} == {k: script[k] for k in same if k != "assumed"}
    assert set(config["assumed"]) == set(script["assumed"])
    assert config["groups"] == {QUESTIONS[0]: 10_000, QUESTIONS[1]: 1_000_000}
    # the table and id3's int32 codes, once the first q7 has made them resident
    assert config["device_bytes"] == 4_800_000_000 + config["rows"] * 4 == 5_200_000_000
    assert config["guarantees"][:-1] != [] and all(
        g in config["guarantees"] for g in script["guarantees"] if "category keys as codes" not in g
    )
    assert "per-group median, standard deviation (ddof 1), max and min as pandas computes them, NaN skipped" in config["guarantees"]
    assert sorted(os.listdir(os.path.join(BENCH, "questions", CONFIG))) == [q + ".py" for q in QUESTIONS]


def test_the_questions_are_the_scripts_lines_letter_for_letter():
    lines = {
        QUESTIONS[0]: "x.groupby(['id4', 'id5'], as_index=False, sort=False, observed=True, dropna=False).agg({'v3': ['median', 'std']})",
        QUESTIONS[1]: "x.groupby('id3', as_index=False, sort=False, observed=True, dropna=False).agg({'v1': 'max', 'v2': 'min'}).assign(range_v1_v2=lambda x: x['v1'] - x['v2'])[['id3', 'range_v1_v2']]",
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
    assert result["failed"] == 0 and result["attempted"] >= 2 and result["attempted"] % 2 == 0
    assert result["run"]["fallbacks_seen"] == [] and result["run"]["why_failed"] == []
    assert result["run"]["kept_whole"] == result["run"]["checked"] == result["attempted"] + 2
    assert "float_rel_gap" in failed(result["control"]), result["control"]
    assert set(result["metrics"]) >= {"query_wall_s", "first_query_s", "setup_s"}
    assert "query_p95_s" not in result["metrics"]


def test_a_traced_rehearsal_reads_the_programs_records(copy):
    """No fallback on the script's phrasing; on the CPU the histograms, max /
    min and the sd are scatters (q6: the two keys' and their product's
    histograms and the sd; q7: id3's histogram, max and min)."""
    result = copy.rehearse(CELL, "--trace", "1")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    metrics = result["metrics"]
    assert metrics["qc.fallbacks"]["value"] == 0
    assert metrics["groupby.scatter_forms_per_query"]["value"] == pytest.approx(3.5)
    # no device plane in a CPU trace: a share of the chip's peak is not reported
    assert not set(METRICS) & set(metrics)


_LOWER_MIDDLE = '''"""Fault: q6's median as the lower middle value of an even group (the reference
holds it, so the comparison sees exactly that difference)."""


def run(pd, x):
    g = x.groupby(['id4', 'id5'], as_index=False, sort=False, observed=True, dropna=False)
    answer = g.agg({'v3': ['median', 'std']})
    if pd.__name__ == "pandas":
        lower = g['v3'].quantile(0.5, interpolation='lower')
        answer[('v3', 'median')] = lower['v3'].to_numpy()
    return answer


def least_bytes(config):
    return config["rows"] * (8 + 8 + 8)
'''

_POPULATION_SD = '''"""Fault: q6's standard deviation with ddof 0 where the script's is ddof 1 (the
reference holds it, so the comparison sees exactly that difference)."""


def run(pd, x):
    g = x.groupby(['id4', 'id5'], as_index=False, sort=False, observed=True, dropna=False)
    answer = g.agg({'v3': ['median', 'std']})
    if pd.__name__ == "pandas":
        answer[('v3', 'std')] = g['v3'].std(ddof=0)['v3'].to_numpy()
    return answer


def least_bytes(config):
    return config["rows"] * (8 + 8 + 8)
'''


@pytest.mark.parametrize("fault,text", [("q6_lower_middle", _LOWER_MIDDLE), ("q6_population_sd", _POPULATION_SD)])
def test_a_wrong_statistic_ends_not_correct(copy, fault, text):
    cell = copy.add_cell("faulty", CELL, [fault])
    copy.add_file(f"questions/{cell['config']}/{fault}.py", text)
    result = copy.rehearse("faulty")
    assert not result["rehearsal"]["comparison_passed"]
    assert failed(result["compared"]) == ["float_rel_gap"], result["compared"]


def metric(name):
    return run.load_module(BENCH, "metrics", name + ".py")


PEAKS = {"hbm_bytes_per_s": 819e9}


def traced(programs, questions):
    return {
        "cell": CELL, "peaks": PEAKS, "least_bytes": {},
        "trace": {"questions": questions, "device_programs": programs},
    }


def test_group_median_roofline_reads_ids_and_v3_over_the_order_statistic_programs():
    module = metric("kernels.group_median_roofline")
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as handle:
        config = json.load(handle)
    assert module.median_bytes(config) == 1_200_000_000
    block = traced([
        ["jit_groupby_group_median", 0.6], ["jit_groupby_sorted_tiles_std", 0.9],
        ["jit_groupby_sorted_tiles_max", 0.5], ["jit_bench_slices", 0.5],
    ], QUESTIONS * 2)
    assert module.read(block) == pytest.approx(100 * 2 * (1.2e9 / 819e9) / 0.6)
    # the parent's lexsort form is the same work
    parent = traced([["jit_groupby_group_quantile", 2.4]], [QUESTIONS[0]])
    assert module.read(parent) == pytest.approx(100 * (1.2e9 / 819e9) / 2.4)
    # a q6 answered on the host, no q6 traced, an untraced run: nothing
    assert module.read(traced([["jit_groupby_segment_agg", 30.0]], QUESTIONS)) is None
    assert module.read(traced([["jit_groupby_group_median", 0.6]], [QUESTIONS[1]])) is None
    assert module.read(dict(block, trace=None)) is None
    assert module.read(dict(block, peaks=None)) is None


def test_group_extrema_var_roofline_reads_the_stats_columns_over_their_programs():
    module = metric("kernels.group_extrema_var_roofline")
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as handle:
        config = json.load(handle)
    assert [module.stats_bytes(q, config) for q in QUESTIONS] == [1_200_000_000, 2_000_000_000]
    block = traced([
        ["jit_groupby_group_median", 0.6], ["jit_groupby_sorted_tiles_std", 0.9],
        ["jit_groupby_sorted_tiles_max", 0.5], ["jit_groupby_sorted_tiles_min", 0.5],
        ["jit_groupby_sorted_tiles_size", 0.3], ["jit_bench_slices", 0.5],
    ], QUESTIONS)
    assert module.read(block) == pytest.approx(100 * (3.2e9 / 819e9) / 1.9)
    # the parent's segment ops in q7 (its q6 fell back: no program of it)
    parent = traced([["jit_groupby_segment_agg", 30.0], ["jit_groupby_scatter_counts", 14.0]], [QUESTIONS[1]])
    assert module.read(parent) == pytest.approx(100 * (2e9 / 819e9) / 30.0)
    assert module.read(traced([["jit_groupby_sorted_tiles_size", 0.3]], QUESTIONS)) is None
    assert module.read(dict(block, trace=None)) is None
    assert module.read(dict(block, peaks=None)) is None
