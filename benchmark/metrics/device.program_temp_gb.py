"""The largest temporary memory the compiler gave any program the first pass
made (``temp_bytes`` in a record's ``programs_made``: ``temp_size_in_bytes``
of the executable the call built), in GB.  ``peak_bytes_in_use`` does not see
it; the first pass makes every program the window runs.  A program without
the fields, or that read no program's temporaries, reports nothing."""

import first_pass_records


def read(obs):
    first = first_pass_records.records(obs)
    if first is None:
        return None
    temps = [p["temp_bytes"] for r in first for p in r["programs_made"].values() if "temp_bytes" in p]
    return max(temps) / 1e9 if temps else None
