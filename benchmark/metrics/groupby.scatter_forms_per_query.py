"""Groupby reductions and range histograms per request that took an XLA
scatter form (``segment``, ``scatter_counts``), from the program's own count of
the form it chose each time (``groupby_forms`` of a request's record).  On a TPU
a scatter serialises: 146 ns a row and 64-bit column.  A program that keeps no
such count reports nothing."""

import program_spans

SCATTER_FORMS = ("segment", "scatter_counts")


def read(obs):
    records = program_spans.requests(obs)
    if not records or any("groupby_forms" not in r for r in records):
        return None
    return sum(r["groupby_forms"].get(form, 0) for r in records for form in SCATTER_FORMS) / len(records)
