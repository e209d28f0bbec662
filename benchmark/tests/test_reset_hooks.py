"""The reset before each request does what it is there for: a repeated
``df.sum()`` is answered from a memo (0 dispatches) unless the program's
derived answers are dropped first (1 dispatch)."""

import numpy as np

import program_hooks as hooks
from conftest import ROOT


def test_a_repeated_question_dispatches_only_after_the_reset():
    pd = hooks.load(ROOT)
    rng = np.random.default_rng(7)
    frame = hooks.ingest(pd, {f"col{i}": rng.integers(0, 100, 50_000) for i in range(5)})

    def dispatches():
        with hooks.count_dispatches() as stats:
            hooks.execute(frame.sum())
        return stats.dispatches

    hooks.drop_derived_answers()
    assert dispatches() == 1
    assert dispatches() == 0, "a repeated question was not answered from the memo"
    hooks.drop_derived_answers()
    assert dispatches() == 1, "the reset hooks did not make the question a first run"
