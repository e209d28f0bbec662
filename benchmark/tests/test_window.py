"""The window's two rules for a traced run, on a clock made by hand and in a
rehearsal: the profiler starts before the second block, or before the first
where the first pass says one block alone reaches ``--seconds``; and the window
does not close before the profiler has started, so no sound run ends with
nothing traced."""

import itertools

import pytest

import run

CELLS = {"h2o_q4_mean_by_id4": 1, "asv_time_arithmetic": 18}  # cell -> requests a block


class Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


class Profiler:
    def __init__(self, clock):
        self.clock = clock
        self.started = []
        self.stopped = []

    def start(self):
        self.started.append(self.clock.now)

    def stop(self):
        self.stopped.append(self.clock.now)


def drive(walls, seconds, traced=True, from_block=1):
    """A window over a block of ``walls`` (question -> seconds a request)."""
    clock = Clock()
    profiler = Profiler(clock) if traced else None

    def ask(number, question):
        clock.now += walls[question]
        return {"question": question, "wall_s": walls[question]}

    requests, traced_questions, window_s = run.drive_window(
        ask, itertools.cycle(walls), len(walls), seconds, profiler, from_block, clock=clock
    )
    return len(requests), traced_questions, window_s, profiler


def test_an_untraced_window_closes_with_the_first_block_at_or_after_seconds():
    completed, traced, window_s, _ = drive({"a": 2.0, "b": 1.0}, 7.0, traced=False)
    assert (completed, traced, window_s) == (6, [], 9.0)
    assert drive({"q5": 61.0}, 51.0, traced=False)[0] == 1


def test_short_blocks_are_traced_from_the_second_block_for_trace_seconds():
    completed, traced, window_s, profiler = drive({"a": 2.0, "b": 1.0}, 7.0)
    assert (completed, window_s) == (6, 9.0), "the window of the untraced run"
    assert traced == ["a", "b"], "requests 2 and 3: the first of them to end 3 s after the start is the last"
    assert (profiler.started, profiler.stopped) == ([3.0], [6.0])


def test_the_trace_ends_with_the_window_if_that_comes_first():
    completed, traced, _, profiler = drive({"a": 1.0}, 2.5)
    assert (completed, traced) == (3, ["a", "a"])
    assert (profiler.started, profiler.stopped) == ([1.0], [3.0])


def test_a_block_that_outlasts_the_window_is_traced_when_the_first_pass_said_so():
    completed, traced, window_s, profiler = drive({"q5": 61.0}, 51.0, from_block=0)
    assert (completed, traced, window_s) == (1, ["q5"], 61.0), "not a block more than the untraced run"
    assert (profiler.started, profiler.stopped) == ([0.0], [61.0])


def test_a_wrong_prediction_holds_the_window_open_for_one_block_more():
    completed, traced, window_s, profiler = drive({"q5": 61.0}, 51.0, from_block=1)
    assert (completed, traced, window_s) == (2, ["q5"], 122.0)
    assert (profiler.started, profiler.stopped) == ([61.0], [122.0])


@pytest.mark.parametrize(
    "first,names,seconds,expected",
    [
        ([("q4", 2.05, 0.05)], ["q4"], 51.0, 1),
        ([("q5", 61.2, 0.1)], ["q5"], 51.0, 0),
        ([("q5", 65.5, 4.5)], ["q5"], 51.0, 0),  # a checkout's first run: 4.5 s of it compiled
        ([("sum", 3.1, 3.0), ("mod", 52.3, 52.0)], ["sum", "mod", "mod"], 51.0, 1),  # compiling does not come again
        ([("sum", 10.0, 0.0), ("mod", 20.5, 0.0)], ["sum", "mod", "mod"], 51.0, 0),  # a weight of 2 counts twice
        ([("sum", 0.003, 0.01)], ["sum"], 0.0, 0),
    ],
)
def test_the_first_pass_says_where_the_trace_starts(first, names, seconds, expected):
    records = [{"question": q, "wall_s": wall, "compile_s": compiled} for q, wall, compiled in first]
    assert run.trace_from_block(run.block_seconds(records, names), seconds) == expected


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_rehearsal_whose_every_block_outlasts_the_window_still_traces_a_block(copy, cell):
    result = copy.rehearse(cell, "--trace", "1", "--seconds", "0")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    assert result["attempted"] == CELLS[cell], "one block, as the untraced run of it"
    assert result["run"]["trace_from_block"] == 0
    assert result["run"]["traced_requests"] >= 1
    assert not any(result["run"]["guarantee_breaks"].values())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_rehearsal_of_todays_cells_traces_from_the_second_block(copy, cell):
    result = copy.rehearse(cell, "--trace", "1", "--seconds", "1")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    assert result["run"]["trace_from_block"] == 1
    # the window is shorter than TRACE_SECONDS: everything after the first block was traced
    assert result["run"]["traced_requests"] == result["attempted"] - CELLS[cell] > 0
    assert '"looked_into": 0' in copy.last_stderr, "their answers stay far under the kept total: set-up warms no sampler"
    assert not any(result["run"]["guarantee_breaks"].values())


def test_an_untraced_rehearsal_says_that_nothing_was_traced(copy):
    result = copy.rehearse("h2o_q4_mean_by_id4", "--seconds", "0")
    assert (result["run"]["traced_requests"], result["run"]["trace_from_block"]) == (0, None)
    assert result["attempted"] == 1
