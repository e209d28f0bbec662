"""The readers of what the program records of each request (``program_spans.py``
and the seven ``metrics/*.py`` that come through it)."""

import pytest

import program_spans

NEW_METRICS = {
    "api.host_ms_per_query": "ms/query",
    "frame.host_ms_per_query": "ms/query",
    "engine.host_ms_per_query": "ms/query",
    "engine.first_launch_ms": "ms",
    "engine.launches_per_query": "count/query",
    "engine.host_syncs_per_query": "count/query",
    "frame.h2d_bytes_per_query": "bytes/query",
}


@pytest.mark.parametrize("cell", ["h2o_q4_mean_by_id4", "asv_time_arithmetic"])
def test_a_traced_rehearsal_reports_all_seven_as_numbers(copy, cell):
    result = copy.rehearse(cell, "--trace", "1")
    assert result["rehearsal"]["comparison_passed"], result["compared"]
    for name, unit in NEW_METRICS.items():
        entry = result["metrics"][name]
        assert isinstance(entry["value"], (int, float)) and entry["value"] >= 0, (name, entry)
        assert entry["unit"] == unit
    assert result["metrics"]["engine.launches_per_query"]["value"] >= 1
    if cell == "h2o_q4_mean_by_id4":  # the groupby's kernels and its two mid-request fetches count
        assert result["metrics"]["engine.launches_per_query"]["value"] >= 3
        assert result["metrics"]["engine.host_syncs_per_query"]["value"] >= 1
        assert "engine.dispatches_per_query" not in result["metrics"]


def test_an_untraced_rehearsal_opens_no_scope_and_reports_none(copy):
    result = copy.rehearse("asv_time_arithmetic")
    assert not set(NEW_METRICS) & set(result["metrics"])


def record(wall_s, **fields):
    base = {
        "label": "benchmark", "wall_s": wall_s, "host_self_s": {"PANDAS-API": 0.001, "PLAN": 0.0005},
        "wait_s": 0.0, "first_launch_s": 0.002, "launches": 2, "host_syncs": 1, "h2d_bytes": 16,
    }
    base.update(fields)
    return base


def obs_of(walls):
    return {"completed": len(walls), "requests": [{"wall_s": w} for w in walls]}


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


def test_the_reader_takes_the_windows_records_in_order(ring):
    ring.extend([record(0.5), record(0.010), record(0.020, launches=4), record(0.3, label="other")])
    got = program_spans.requests(obs_of([0.0101, 0.0203]))  # the first pass's record is left out
    assert [r["wall_s"] for r in got] == [0.010, 0.020]
    assert program_spans.mean_of(obs_of([0.0101, 0.0203]), "launches") == 3
    assert program_spans.host_ms_per_query(obs_of([0.0101, 0.0203]), ("PANDAS-API",)) == pytest.approx(1.0)


@pytest.mark.parametrize(
    "kept, walls",
    [
        ([0.010], [0.0101, 0.0203]),  # fewer records than requests
        ([0.010, 0.020], [0.0101, 0.0250]),  # the harness's wall is over 1 ms above the scope's
        ([0.010, 0.020], [0.0101, 0.0199]),  # the harness's wall is under the scope's
    ],
)
def test_the_reader_returns_none_when_ring_and_requests_disagree(ring, kept, walls):
    ring.extend(record(w) for w in kept)
    assert program_spans.requests(obs_of(walls)) is None
    assert program_spans.mean_of(obs_of(walls), "launches") is None
    assert program_spans.host_ms_per_query(obs_of(walls), ("PANDAS-API",)) is None


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    import modin_tpu.observability as observability

    monkeypatch.delattr(observability, "recent_queries", raising=False)
    assert program_spans.requests(obs_of([0.01])) is None
