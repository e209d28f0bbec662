"""The readers of how the first pass made its programs (``first_pass_records.py``
and the two ``metrics/*.py`` that come through it), on records patched into
the program's ring, and once through a traced rehearsal."""

import importlib.util
import os

import pytest

import first_pass_records

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = {"engine.first_pass_trace_s": "s", "device.program_temp_gb": "GB"}


def read(name, obs):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(obs)


def made(seconds, temp_bytes=None):
    entry = {"trace_s": seconds, "lower_s": seconds, "compile_s": seconds, "loaded": False}
    if temp_bytes is not None:
        entry["temp_bytes"] = temp_bytes
    return entry


def record(wall_s, trace_s=0.0, lower_s=0.0, programs=None, label="benchmark"):
    return {"label": label, "wall_s": wall_s, "trace_s": trace_s, "lower_s": lower_s,
            "programs_made": programs or {}}


def obs_of(first_walls, completed, slack=0.0004):
    """The harness's side: one question a first-pass request, whose walls
    enclose the scopes' by ``slack`` each."""
    return {"least_bytes": {f"q{i}": 1 for i in range(len(first_walls))}, "completed": completed,
            "first_pass": {"wall_s": sum(w + slack for w in first_walls)}}


@pytest.fixture
def ring(monkeypatch):
    import modin_tpu.observability as observability

    kept = []
    monkeypatch.setattr(
        observability, "recent_queries",
        lambda label=None: [r for r in kept if label is None or r["label"] == label],
        raising=False,
    )
    return kept


FIRST = [
    record(1.0, 0.3, 0.1, {"groupby_limb_dot": made(0.1, 2_400_000_000), "groupby_key_minmax": made(0.01, 1024)}),
    record(0.5, 0.1, 0.05, {"convert_element_type": made(0.001)}),
]
WINDOW = [record(0.1, programs={}), record(0.1, label="other"), record(0.12, programs={})]


def test_the_readers_take_the_first_pass_before_the_window(ring):
    ring.extend([record(7.0, 5.0, 5.0), *FIRST, *WINDOW])  # an older record is left out
    obs = obs_of([1.0, 0.5], completed=2)
    assert [r["wall_s"] for r in first_pass_records.records(obs)] == [1.0, 0.5]
    assert read("engine.first_pass_trace_s", obs) == pytest.approx(0.3 + 0.1 + 0.1 + 0.05)
    assert read("device.program_temp_gb", obs) == pytest.approx(2.4)


def test_no_temporaries_read_reports_no_temp_metric(ring):
    ring.extend([record(1.0, 0.2, 0.1, {"p": made(0.1)}), *WINDOW])
    obs = obs_of([1.0], completed=2)
    assert read("engine.first_pass_trace_s", obs) == pytest.approx(0.3)
    assert read("device.program_temp_gb", obs) is None


@pytest.mark.parametrize("name", sorted(METRICS))
@pytest.mark.parametrize(
    "case",
    [
        "parent",  # a program that keeps no such fields
        "short_ring",  # fewer records before the window's than questions
        "walls_disagree",  # the harness's first pass is 10 ms longer than the records say
        "no_ring",  # a program without recent_queries
    ],
)
def test_the_readers_report_nothing_where_the_records_do_not_fit(ring, monkeypatch, name, case):
    obs = obs_of([1.0, 0.5], completed=2)
    if case == "parent":
        ring.extend({k: v for k, v in r.items() if k not in first_pass_records.FIELDS} for r in [*FIRST, *WINDOW])
    elif case == "short_ring":
        ring.extend([FIRST[1], *WINDOW])
    elif case == "walls_disagree":
        ring.extend([*FIRST, *WINDOW])
        obs["first_pass"]["wall_s"] += 0.010
    else:
        import modin_tpu.observability as observability

        monkeypatch.delattr(observability, "recent_queries", raising=False)
    assert first_pass_records.records(obs) is None
    assert read(name, obs) is None


def test_a_traced_rehearsal_reports_both_and_an_untraced_one_neither(copy):
    traced = copy.rehearse("h2o_q4_mean_by_id4", "--trace", "1")
    assert traced["rehearsal"]["comparison_passed"], traced["compared"]
    for name, unit in METRICS.items():
        entry = traced["metrics"][name]
        assert entry["unit"] == unit and entry["value"] > 0, (name, entry)
    untraced = copy.rehearse("h2o_q4_mean_by_id4")
    assert not set(METRICS) & set(untraced["metrics"])
