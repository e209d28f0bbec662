"""``run.guarantee_breaks``: what reaches ``correct`` besides the values."""

import types

import pytest

import compare
import run

PEAKS = {"hbm_bytes_per_s": 800e9}
LEAST = {"sum": 4_000_000_000, "count": 80}  # 5 ms and nothing at the peak


def request(failed=False, fallbacks=0):
    return {"failed": failed, "fallbacks": fallbacks}


def trace(**busy_by_question):
    return {"questions": list(busy_by_question), "busy_s_per_request": list(busy_by_question.values())}


@pytest.mark.parametrize(
    "requests,trap_count,traced,expected",
    [
        ([request(), request()], 0, None, {}),
        ([request(failed=True, fallbacks=2), request()], 2, None, {"failed_requests": 1}),
        ([request(), request()], 1, None, {"fallbacks_elsewhere": 1}),  # say, while the table was ingested
        ([request()], 0, trace(sum=0.030, count=0.0), {}),  # count has nothing to read
        ([request()], 0, trace(sum=0.0), {"requests_under_least_time": 1}),  # a memo answered it
        ([request()], 0, trace(sum=0.004), {"requests_under_least_time": 1}),  # faster than the memory
    ],
)
def test_breaks_are_counted(requests, trap_count, traced, expected):
    trap = types.SimpleNamespace(count=trap_count)
    got = run.guarantee_breaks(requests, trap, traced, LEAST, PEAKS)
    assert {k: v for k, v in got.items() if v} == expected


def test_any_break_makes_the_verdict_false():
    sound = compare.Tally()
    assert compare.verdict(sound, 0, 0, {"float_rel_gap": 1e-10})[0]
    correct, compared = compare.verdict(sound, 0, 1, {"float_rel_gap": 1e-10})
    assert not correct and compared["guarantee_breaks"] == {"value": 1, "limit": 0}
