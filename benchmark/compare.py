"""The comparison that decides ``correct``: the program's answers against
plain pandas on the same seeded columns.  Imports nothing of the program.

Two kinds of evidence reach this file for an answer:

* ``whole``: the pandas object the program hands a user (small answers are
  kept on the device until the window has closed, then fetched);
* ``sampled``: for a seeded sample of the answers too large to keep, runs of
  rows at seeded places taken from the device buffers between requests, the
  labels, dtypes and length.

Besides ``unanswered`` (requests that raised or were never compared) and
``guarantee_breaks`` (``run.guarantee_breaks``: requests that fell back, left
the device or were answered from a memo), both with the limit 0, two numbers
are compared, each with a limit of its own:

* ``exact_mismatches``: cells of integer, boolean and index data that differ,
  plus every shape, label, index-name or dtype that differs.  Limit 0.
* ``float_rel_gap``: the widest ``|got - want| / |want|`` over floating cells.
  The limit is the cell's (``limits.float_rel_gap`` in its file).
"""

import numpy as np
import pandas

# below this a reference value counts as this large, so a zero divides nothing
_FLOOR = np.finfo(np.float64).tiny

# the control computes in the nearest precision below the configuration's
_LOWER = {np.dtype("float64"): np.float32, np.dtype("int64"): np.int32}


class Tally:
    def __init__(self):
        self.exact_mismatches = 0
        self.float_rel_gap = 0.0
        self.notes = []

    def mismatch(self, n, what):
        n = int(n)
        if n:
            self.exact_mismatches += n
            if len(self.notes) < 8:
                self.notes.append(what)

    def column(self, got, want, what):
        """One column (or its sample) of an answer against the reference's."""
        got = np.asarray(got)
        want = np.asarray(want)
        if got.dtype != want.dtype:
            self.mismatch(1, f"{what}: dtype {got.dtype}, not {want.dtype}")
        if got.shape != want.shape:
            self.mismatch(1, f"{what}: shape {got.shape}, not {want.shape}")
            return
        if want.dtype.kind == "f":
            got = got.astype(np.float64)
            want = want.astype(np.float64)
            both_nan = np.isnan(got) & np.isnan(want)
            self.mismatch(
                np.count_nonzero(np.isnan(got) != np.isnan(want)), f"{what}: NaN placed otherwise"
            )
            ok = ~(np.isnan(got) | np.isnan(want) | both_nan)
            if ok.any():
                gap = np.abs(got[ok] - want[ok]) / np.maximum(np.abs(want[ok]), _FLOOR)
                self.float_rel_gap = max(self.float_rel_gap, float(gap.max()))
        else:
            self.mismatch(np.count_nonzero(got != want), f"{what}: values differ")


def _as_frame(obj):
    return obj.to_frame() if isinstance(obj, pandas.Series) else obj


def compare_whole(tally, got, want, what):
    if type(got).__name__ != type(want).__name__:
        tally.mismatch(1, f"{what}: a {type(got).__name__}, not a {type(want).__name__}")
    got, want = _as_frame(got), _as_frame(want)
    if got.shape != want.shape:
        tally.mismatch(1, f"{what}: shape {got.shape}, not {want.shape}")
        return
    tally.mismatch(list(got.columns) != list(want.columns), f"{what}: column labels differ")
    tally.mismatch(list(got.index.names) != list(want.index.names), f"{what}: index names differ")
    tally.column(got.index.to_numpy(), want.index.to_numpy(), f"{what}: index")
    for pos in range(want.shape[1]):
        tally.column(
            got.iloc[:, pos].to_numpy(), want.iloc[:, pos].to_numpy(), f"{what}[{want.columns[pos]}]"
        )


def sampled(rows, length):
    """The reference's side of a ``sampled`` answer: ``rows`` is its answer at
    the sampled rows, ``length`` the whole answer's."""
    labels = [rows.name] if isinstance(rows, pandas.Series) else list(rows.columns)
    rows = _as_frame(rows)
    return {
        "labels": labels,
        "length": length,
        "rows": [rows.iloc[:, pos].to_numpy() for pos in range(rows.shape[1])],
    }


def compare_sampled(tally, got, want, what):
    tally.mismatch(got["labels"] != want["labels"], f"{what}: column labels differ")
    tally.mismatch(got["length"] != want["length"], f"{what}: {got['length']} rows, not {want['length']}")
    if len(got["rows"]) != len(want["rows"]):
        tally.mismatch(1, f"{what}: {len(got['rows'])} columns, not {len(want['rows'])}")
        return
    for label, g, w in zip(want["labels"], got["rows"], want["rows"]):
        tally.column(g, w, f"{what}[{label}] sampled rows")


def lower_precision_frame(frame):
    """The control's input: every column one step down in precision."""
    return frame.astype({c: _LOWER[d] for c, d in frame.dtypes.items() if d in _LOWER})


def lower_precision_answer(answer):
    """The control's output: the best any float32 path could say, the
    reference's own floating results rounded to float32 once."""
    if isinstance(answer, pandas.Series):
        return answer.astype(np.float32) if answer.dtype == np.float64 else answer
    return answer.astype({c: np.float32 for c, d in answer.dtypes.items() if d == np.float64})


def lower_precision_rows(rows):
    """The same of a sampled answer's columns."""
    return [col.astype(np.float32) if col.dtype == np.float64 else col for col in rows]


def verdict(tally, unanswered, guarantee_breaks, limits):
    """``(correct, {name: {"value", "limit"}})`` in the order they are printed."""
    compared = {
        "unanswered": {"value": int(unanswered), "limit": 0},
        "guarantee_breaks": {"value": int(guarantee_breaks), "limit": 0},
        "exact_mismatches": {"value": int(tally.exact_mismatches), "limit": 0},
        "float_rel_gap": {"value": float(tally.float_rel_gap), "limit": float(limits["float_rel_gap"])},
    }
    correct = all(entry["value"] <= entry["limit"] for entry in compared.values())
    return correct, compared
