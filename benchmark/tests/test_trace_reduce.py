"""The reduction from a trace to busy time, idle share and the per-request
split: on events written by hand, and on a small trace recorded on the chip
(``data/recorded.xplane.pb``: one TPU v5e, a rehearsal-size run of
``h2o_q4_mean_by_id4``, made with ``record_trace.py``)."""

import os

import pytest

import trace_reduce

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "recorded.xplane.pb")

OPS = {"/device:TPU:0": [(10, 20, "a/x"), (15, 30, "a/y"), (50, 60, "b/z")]}
PHASES = [
    (0, 5, "reset"), (5, 40, "api_call"), (40, 45, "execute_wait"), (45, 48, "between_requests"),
    (48, 50, "reset"), (50, 70, "api_call"), (70, 80, "between_requests"),
]


def test_busy_union_idle_share_and_request_split_by_hand():
    got = trace_reduce.reduce_events(OPS, PHASES)
    assert got["window_s"] == pytest.approx(80e-9)
    assert got["busy_s"] == pytest.approx(30e-9)  # [10, 30] and [50, 60]: the overlap counts once
    assert got["requests"] == 2
    assert got["busy_s_per_request"] == pytest.approx([20e-9, 10e-9])
    idle = dict(got["idle_gaps"])
    assert idle == pytest.approx(
        {"api_call": 25e-9, "between_requests": 13e-9, "reset": 7e-9, "execute_wait": 5e-9}
    )
    assert sum(idle.values()) == pytest.approx(got["window_s"] - got["busy_s"])
    assert got["idle_gaps"][0][0] == "api_call", "longest first"
    assert dict(got["device_ops"]) == pytest.approx({"a/y": 15e-9, "a/x": 10e-9, "b/z": 10e-9})


def test_the_harness_own_device_work_is_kept_apart():
    ops = {"/device:TPU:0": OPS["/device:TPU:0"] + [(28, 36, "jit_bench_slices/fusion"), (45, 48, "jit_bench_sums/reduce")]}
    got = trace_reduce.reduce_events(ops, PHASES)
    assert got["busy_s"] == pytest.approx(30e-9), "the program's operations alone"
    assert got["busy_s_per_request"] == pytest.approx([20e-9, 10e-9])
    assert got["busy_all_s"] == pytest.approx(39e-9)  # [10, 36], [45, 48], [50, 60]
    assert got["harness_busy_s"] == pytest.approx(9e-9)
    idle = dict(got["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(got["window_s"] - got["busy_all_s"]), "idle is when nothing ran at all"
    assert dict(got["device_ops"])["jit_bench_slices/fusion"] == pytest.approx(8e-9), "the breakdown names it"


def test_two_chips_are_averaged():
    ops = dict(OPS, **{"/device:TPU:1": [(0, 80, "c/w")]})
    got = trace_reduce.reduce_events(ops, PHASES)
    assert got["busy_s"] == pytest.approx((30e-9 + 80e-9) / 2)
    assert got["chips"] == 2


def test_operations_outside_the_window_are_clipped():
    ops = {"/device:TPU:0": [(-50, 4, "early/x"), (78, 500, "late/y")]}
    got = trace_reduce.reduce_events(ops, PHASES)
    assert got["busy_s"] == pytest.approx(6e-9)


def test_a_trace_with_nothing_to_read_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({}, PHASES)
    with pytest.raises(ValueError):
        trace_reduce.reduce_events(OPS, [])


def test_recorded_chip_trace():
    ops, phases = trace_reduce.read_events(RECORDED)
    assert list(ops) == ["/device:TPU:0"]
    assert {name for _, _, name in phases} == {"reset", "api_call", "execute_wait", "between_requests"}
    got = trace_reduce.reduce_events(ops, phases)
    assert got["requests"] == sum(1 for _, _, name in phases if name == "api_call") >= 2
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["harness_busy_s"] == 0 and got["busy_all_s"] == got["busy_s"]  # q4's answers are small: kept, not looked into
    assert sum(got["busy_s_per_request"]) == pytest.approx(got["busy_s"])
    idle = sum(seconds for _, seconds in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"])
    # every request of that run asked the same question of the same table
    each = got["busy_s_per_request"]
    assert max(each) < 1.5 * min(each)
    assert all(name.startswith("jit_") and "/" in name for name, _ in got["device_ops"])


def test_programs_by_hand_and_of_the_recorded_chip_trace():
    launches = {"/device:TPU:0": [(8, 32, "a"), (49, 61, "b"), (70, 90, "a")]}
    got = trace_reduce.reduce_events(OPS, PHASES, launches)
    assert got["device_programs"] == [["a", pytest.approx(34e-9)], ["b", pytest.approx(12e-9)]]  # clipped at 80
    assert trace_reduce.reduce_events(OPS, PHASES)["device_programs"] == []
    ops, phases, programs = trace_reduce.read_planes(RECORDED)
    assert (ops, phases) == trace_reduce.read_events(RECORDED)
    got = trace_reduce.reduce_events(ops, phases, programs)
    assert got["device_programs"][0][0] == "jit_fn", "recorded before the program's closures carried names"
    # a program's launches hold its operations and the gaps between them: together
    # they are the busy time and a little more, which its nesting operations' sum is not
    by_program = sum(seconds for _, seconds in got["device_programs"])
    assert got["busy_s"] <= by_program < 1.01 * got["busy_s"]
    assert sum(seconds for _, seconds in got["device_ops"]) > by_program, "the top ten alone pass it"
