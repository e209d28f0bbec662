"""The least time the chip's memory could take to read, once with their group
ids, the columns that per-group max / min / var take in the traced requests
(q7's v1 and v2 by id3: ``rows`` x (4 + 8 + 8); q6's v3 for its standard
deviation: ``rows`` x (4 + 8)) over the device seconds of the programs that
compute them, whatever implements them: XLA's segment ops on PR 36's tree, the
sorted tiles' max / min / var / std / sem of PR 39 (not the histograms, which
every many-groups reduction shares).  A program without such programs reports
nothing."""

import json
import os

# by the names ``ops/_program.py`` gives them in the trace's ``XLA Modules`` line
PROGRAMS = (
    "jit_groupby_segment_agg",
    "jit_groupby_sorted_tiles_max",
    "jit_groupby_sorted_tiles_min",
    "jit_groupby_sorted_tiles_var",
    "jit_groupby_sorted_tiles_std",
    "jit_groupby_sorted_tiles_sem",
)
# the bytes a question's max / min / var read, a row
ROW_BYTES = {
    "q6_median_sd_v3_by_id4_id5": 4 + 8,
    "q7_max_v1_minus_min_v2_by_id3": 4 + 8 + 8,
}


def stats_bytes(question, config):
    """Bytes one ``question`` reads for its max / min / var at the least."""
    return config["rows"] * ROW_BYTES.get(question, 0)


def _config_of(cell):
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(bench, "workloads", cell + ".json")) as handle:
        config = json.load(handle)["config"]
    with open(os.path.join(bench, "configs", config + ".json")) as handle:
        return json.load(handle)


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs["peaks"]:
        return None
    busy_s = sum(s for name, s in trace.get("device_programs") or [] if name.startswith(PROGRAMS))
    if not busy_s:
        return None
    config = _config_of(obs["cell"])
    least = sum(stats_bytes(q, config) for q in trace["questions"])
    if not least:
        return None
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / busy_s
