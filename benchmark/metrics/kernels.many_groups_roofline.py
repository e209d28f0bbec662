"""The least time the chip's memory could take for the traced requests (each
question's least bytes over the peak bandwidth) over the device seconds of the
programs that aggregate a key of many groups, whatever implements them: XLA's
scatters (the histogram of a wide range and the segment sums) or the sorted
tiles that replaced them.  The same work read on either tree, so the share says
what a change of form bought.  Bound by bandwidth, like every question here."""

# by the names ``ops/_program.py`` gives them in the trace's ``XLA Modules`` line
PROGRAMS = ("jit_groupby_scatter_counts", "jit_groupby_segment_agg", "jit_groupby_sorted_tiles")


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs["peaks"]:
        return None
    busy_s = sum(s for name, s in trace.get("device_programs") or [] if name.startswith(PROGRAMS))
    if not busy_s:
        return None
    least_s = sum(obs["least_bytes"][q] for q in trace["questions"]) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s
