"""``correct`` as a run decides it, driven here on the CPU at a rehearsal's
size (the harness's look for a chip skipped, everything else as in a run):
sound cells pass, the lower-precision control fails, and each fault a cell can
have makes the comparison come out false."""

import os

import pytest

from conftest import BENCH

CELLS = ["h2o_q4_mean_by_id4", "asv_time_arithmetic"]

# fault file -> (the cell it breaks, the number that has to catch it)
FAULTS = {
    "q4_half_rows": ("h2o_q4_mean_by_id4", "float_rel_gap"),
    "q4_altered": ("h2o_q4_mean_by_id4", "float_rel_gap"),
    "q5_half_rows": ("h2o_q4_mean_by_id4", "exact_mismatches"),
    "sum_altered": ("asv_time_arithmetic", "exact_mismatches"),
    "add_altered": ("asv_time_arithmetic", "exact_mismatches"),
    "mean_half_rows": ("asv_time_arithmetic", "float_rel_gap"),
    "sum_falls_back": ("asv_time_arithmetic", "guarantee_breaks"),
}


def failed(compared):
    return [name for name, entry in compared.items() if entry["value"] > entry["limit"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_cell_passes_and_its_control_fails(copy, cell):
    result = copy.rehearse(cell, "--control")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    assert result["correct"] is False, "a rehearsal never reports correct"
    assert result["failed"] == 0 and result["attempted"] > 0
    assert not failed(result["compared"])
    assert "float_rel_gap" in failed(result["control"]), result["control"]
    # the limit has room on both sides: above the program, below the control
    limit = result["compared"]["float_rel_gap"]["limit"]
    assert result["compared"]["float_rel_gap"]["value"] * 100 < limit
    assert result["control"]["float_rel_gap"]["value"] > limit * 10


def test_question_5_passes_as_a_cell_added_by_files(copy):
    """q5 has a question file and no cell yet (a request takes 61 s on the
    chip): here it runs as a cell that the test adds."""
    copy.add_cell("h2o_q5_sum_by_id6", "h2o_q4_mean_by_id4", ["q5_sum_by_id6"])
    result = copy.rehearse("h2o_q5_sum_by_id6", "--control")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    assert "float_rel_gap" in failed(result["control"]), result["control"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_comparison_fail(copy, fault):
    like, number = FAULTS[fault]
    with open(os.path.join(BENCH, "tests", "faults", fault + ".py")) as handle:
        text = handle.read()
    cell = copy.add_cell("faulty", like, [fault])
    copy.add_file(f"questions/{cell['config']}/{fault}.py", text)
    result = copy.rehearse("faulty")
    assert not result["rehearsal"]["comparison_passed"]
    assert number in failed(result["compared"]), result["compared"]
