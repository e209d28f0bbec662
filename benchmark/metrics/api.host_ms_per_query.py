"""Host self time a request spends in the pandas API and the query compiler
(spans tagged ``PANDAS-API`` and ``QUERY-COMPILER``: each span's duration minus
its children's), from the program's own ``query_stats`` records."""

import program_spans


def read(obs):
    return program_spans.host_ms_per_query(obs, ("PANDAS-API", "QUERY-COMPILER"))
