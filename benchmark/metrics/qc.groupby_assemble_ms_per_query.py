"""Host self time a request spends giving a device groupby's answer the shape
the script asks for: finding and applying the order of first appearance (span
``groupby.first_seen``) and putting the key columns in front (span
``qc.groupby.assemble``), both tagged ``GROUPBY-ASSEMBLE``, from the program's
own ``query_stats`` records.  A program without those spans reports nothing."""

import program_spans

LAYER = "GROUPBY-ASSEMBLE"


def read(obs):
    records = program_spans.requests(obs)
    if not records or not any(LAYER in r["host_self_s"] for r in records):
        return None
    return program_spans.host_ms_per_query(obs, (LAYER,))
