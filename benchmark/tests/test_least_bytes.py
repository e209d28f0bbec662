"""Each question's least bytes against bytes computed by hand."""

import json
import os

import pytest

from conftest import BENCH
from run import load_module

HAND = {
    # 4 columns of 1e8 int64/float64 read, 100 groups x (key + 3 means) written
    ("h2o-groupby-g1-1e8-1e2", "q4_mean_by_id4"): 3_200_000_000 + 3_200,
    # the same four columns, 1e6 groups x (key + 3 sums) written
    ("h2o-groupby-g1-1e8-1e2", "q5_sum_by_id6"): 3_200_000_000 + 32_000_000,
    # 10 int64 columns of 5e7 rows: 4 GB read; a value a column, a row, or a whole frame written
    ("asv-int-5e7x10", "sum_axis0"): 4_000_000_000 + 80,
    ("asv-int-5e7x10", "mean_axis0"): 4_000_000_000 + 80,
    ("asv-int-5e7x10", "count_axis0"): 80,
    ("asv-int-5e7x10", "sum_axis1"): 4_000_000_000 + 400_000_000,
    ("asv-int-5e7x10", "mean_axis1"): 4_000_000_000 + 400_000_000,
    ("asv-int-5e7x10", "count_axis1"): 400_000_000,
    ("asv-int-5e7x10", "nunique_axis1"): 4_000_000_000 + 400_000_000,
    ("asv-int-5e7x10", "median_axis1"): 4_000_000_000 + 400_000_000,
    ("asv-int-5e7x10", "add_axis0"): 8_000_000_000,
    ("asv-int-5e7x10", "add_axis1"): 8_000_000_000,
    ("asv-int-5e7x10", "mul_axis0"): 8_000_000_000,
    ("asv-int-5e7x10", "mul_axis1"): 8_000_000_000,
    ("asv-int-5e7x10", "mod_axis0"): 8_000_000_000,
    ("asv-int-5e7x10", "mod_axis1"): 8_000_000_000,
    ("asv-int-5e7x10", "abs"): 8_000_000_000,
    ("asv-int-5e7x10", "isin"): 4_000_000_000 + 500_000_000,  # one byte a value written
}


@pytest.mark.parametrize("config,question", sorted(HAND))
def test_least_bytes(config, question):
    with open(os.path.join(BENCH, "configs", config + ".json")) as handle:
        sizes = json.load(handle)
    module = load_module(BENCH, "questions", config, question + ".py")
    assert module.least_bytes(sizes) == HAND[(config, question)]


def test_every_question_file_is_checked():
    found = {
        (config, name[:-3])
        for config in os.listdir(os.path.join(BENCH, "questions"))
        for name in os.listdir(os.path.join(BENCH, "questions", config))
        if name.endswith(".py")
    }
    assert found == set(HAND)
