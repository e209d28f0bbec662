"""The least time the chip's memory could take to read each traced q6's group
ids and v3 once (``rows`` x (4 + 8): what any way of finding a per-group order
statistic has to see at the least) over the device seconds of the programs that
compute a per-group order statistic, whatever implements them: the lexsort and
segment sums of PR 36's tree, the sort-and-select of PR 39.  A program without
such programs (one that answers q6 on the host) reports nothing."""

import json
import os

# by the names ``ops/_program.py`` gives them in the trace's ``XLA Modules`` line
PROGRAMS = (
    "jit_groupby_group_quantile",
    "jit_groupby_group_median",
    "jit_groupby_group_sort_select",
)
QUESTION = "q6_median_sd_v3_by_id4_id5"


def median_bytes(config):
    """Bytes of one q6's group ids (int32) and v3 (float64)."""
    return config["rows"] * (4 + 8)


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
    asked = sum(q == QUESTION for q in trace["questions"])
    if not busy_s or not asked:
        return None
    least_s = asked * median_bytes(_config_of(obs["cell"])) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s
