"""The least time the chip's memory could take to read each traced question's
key codes once (``rows`` x the width pandas holds the codes at: what any way of
finding the order of first appearance has to see at the least, could it stop
nowhere early) over the device seconds of the programs that find and apply
that order, ``jit_groupby_first_seen*``, whatever implements them.  A program
without such programs (the parent of PR 36) reports nothing."""

import json
import os

# by the names ``ops/_program.py`` gives them in the trace's ``XLA Modules`` line
PROGRAMS = ("jit_groupby_first_seen",)
# the key columns of the script's questions
KEYS = {
    "q1_sum_v1_by_id1": ("id1",),
    "q2_sum_v1_by_id1_id2": ("id1", "id2"),
    "q3_sum_v1_mean_v3_by_id3": ("id3",),
}


def code_width(categories):
    """Bytes a code, as pandas holds a categorical of ``categories`` labels."""
    return 1 if categories <= 127 else 2 if categories <= 32767 else 4


def key_code_bytes(question, config):
    """Bytes of ``question``'s key codes in the configuration's table."""
    categories = {
        "id1": config["groups_k"],
        "id2": config["groups_k"],
        "id3": config["rows"] // config["groups_k"],
    }
    return sum(config["rows"] * code_width(categories[key]) for key in KEYS[question])


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
    if not busy_s or not all(q in KEYS for q in trace["questions"]):
        return None
    config = _config_of(obs["cell"])
    least_s = sum(key_code_bytes(q, config) for q in trace["questions"]) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s
