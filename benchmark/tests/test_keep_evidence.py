"""What a run keeps of its answers: whole while the kept total allows, then by
the seeded sample, never as ``unanswered``: on buffers made by hand, and in a
rehearsal of q5 as a cell added by files with the total set down to one answer."""

import os
import types

import numpy as np
import pytest

import run
from conftest import BENCH


def a_run(questions, seed=2147483659):
    cell = types.SimpleNamespace(questions=dict.fromkeys(questions))
    return run.Run(cell, types.SimpleNamespace(trace=0, seed=seed), None, None, None)


def buffers(rows, columns=3):
    import jax.numpy as jnp

    return [(f"v{i}", jnp.arange(rows, dtype=jnp.int32) * (i + 1), rows) for i in range(columns)]


def test_answers_past_the_kept_total_are_sampled_or_passed_over(monkeypatch):
    rows = 4000
    answer = buffers(rows)  # 48 000 bytes
    monkeypatch.setattr(run, "KEEP_TOTAL_BYTES", 100_000)
    ours = a_run(["q5", "q4"])
    turn = ours.sampler.turn["q5"]
    for number in range(20):
        ours.keep_evidence(number, "q5", f"answer {number}", answer)
    assert [number for number, _, _ in ours.kept] == [0, 1], "two fit within the total"
    assert ours.sampled[0][0] == 2, "the first that would pass it is in the sample"
    assert len(ours.sampled) == 1 + (turn + 17) // run.SAMPLE_EVERY >= 3, "then every eighth from the seeded turn"
    assert len(ours.kept) + len(ours.sampled) + ours.unsampled == 20
    assert not hasattr(ours, "unchecked")
    # a sample is the buffers' own rows at the seeded places
    sample = ours.sampled[0][2]
    assert sample["labels"] == ["v0", "v1", "v2"] and sample["length"] == rows
    positions = ours.sampler.host_positions(rows)
    assert len(positions) == run.SAMPLED_RUNS * run.SAMPLED_RUN_ROWS
    for i, column in enumerate(sample["rows"]):
        assert np.array_equal(column, positions * (i + 1))
    # another question's small answers go the same way, from its own first on
    ours.keep_evidence(20, "q4", "answer 20", buffers(10))
    assert [number for number, _, _ in ours.kept] == [0, 1, 20], "240 bytes still fit"
    ours.keep_evidence(21, "q4", "answer 21", answer)
    assert ours.sampled[-1][0] == 21


def test_within_the_total_every_answer_up_to_keep_bytes_is_kept_and_a_larger_one_sampled():
    ours = a_run(["small", "large"])
    ours.keep_bytes = 48_000
    for number in range(12):
        ours.keep_evidence(number, "small", "answer", buffers(4000))
    assert len(ours.kept) == 12 and not ours.sampled and not ours.unsampled
    ours.keep_evidence(12, "large", "answer", buffers(4001))
    assert len(ours.kept) == 12 and [number for number, _, _ in ours.sampled] == [12]


def test_an_answer_that_left_the_device_is_kept_for_the_comparison(monkeypatch):
    monkeypatch.setattr(run, "KEEP_TOTAL_BYTES", 0)
    ours = a_run(["q"])
    ours.keep_evidence(0, "q", "a host answer", None)
    assert ours.kept == [(0, "q", "a host answer")]


def test_the_sampler_is_warmed_in_set_up_only_where_the_window_will_pass_the_total(monkeypatch):
    hooks = types.SimpleNamespace(NotOnDevice=LookupError, device_buffers=lambda answer: answer)
    ours = a_run(["q5", "q4"])
    ours.hooks = hooks
    ours.keep_evidence(-1, "q5", buffers(4000), buffers(4000))  # the first pass's answers: 48 000 bytes and 240
    ours.keep_evidence(-2, "q4", buffers(10), buffers(10))
    looked_into = []
    monkeypatch.setattr(ours.sampler, "take", looked_into.append)
    monkeypatch.setattr(run, "KEEP_TOTAL_BYTES", 48_240 + 20 * 48_240)
    assert ours.warm_sampler(["q5", "q4"], 20) == 0 and not looked_into, "twenty blocks more still fit"
    assert ours.warm_sampler(["q5", "q4"], 21) == 2 and [b[0][2] for b in looked_into] == [4000, 10]
    assert ours.warm_sampler(["q4"], 5000) == 0, "a cell that asks only the small question never passes it"
    # an answer that left the device (its request failed) has no buffers to look into
    hooks.device_buffers = lambda answer: (_ for _ in ()).throw(LookupError())
    assert ours.warm_sampler(["q5"], 10**9) == 0


def q5_cell(copy, question):
    return copy.add_cell("h2o_q5_sum_by_id6", "h2o_q4_mean_by_id4", [question])


def test_q5_past_the_kept_total_is_sampled_and_ends_sound(copy):
    q5_cell(copy, "q5_sum_by_id6")
    result = copy.rehearse("h2o_q5_sum_by_id6", "--control", keep_total_bytes=70_000)  # an answer is 48 000
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    assert result["compared"]["unanswered"] == {"value": 0, "limit": 0}
    ran = result["run"]
    assert ran["kept_whole"] == 1, "the set-up's answer, compared whole"
    assert ran["checked"] >= 2, "and the window's first, by its sample"
    assert ran["checked"] + ran["large_answers_not_in_the_sample"] == result["attempted"] + 1
    assert copy.last_stderr.count('"phase": "warm_sampler"') == 1 and '"looked_into": 1' in copy.last_stderr
    assert result["control"]["float_rel_gap"]["value"] > result["compared"]["float_rel_gap"]["limit"]


def test_an_answer_altered_after_the_total_was_passed_is_caught_by_the_sample(copy):
    with open(os.path.join(BENCH, "tests", "faults", "q5_altered_later.py")) as handle:
        text = handle.read()
    cell = q5_cell(copy, "q5_altered_later")
    copy.add_file(f"questions/{cell['config']}/q5_altered_later.py", text)
    result = copy.rehearse("h2o_q5_sum_by_id6", keep_total_bytes=70_000)
    assert not result["rehearsal"]["comparison_passed"]
    assert result["run"]["kept_whole"] == 1 and result["compared"]["unanswered"]["value"] == 0
    assert result["compared"]["exact_mismatches"]["value"] > 0, result["compared"]
    # with the total as committed every answer is kept whole, and caught whole
    result = copy.rehearse("h2o_q5_sum_by_id6")
    assert result["run"]["kept_whole"] == result["run"]["checked"] == result["attempted"] + 1
    assert result["compared"]["exact_mismatches"]["value"] > 0
