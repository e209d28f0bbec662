"""The least time the chip's memory could take for the traced requests (each
question's least bytes over the peak bandwidth) over the time the program's
operations kept the device busy in the traced window (the harness's own device
work left out).  Every question here is bound by bandwidth.  A request answered
from a memo keeps the device idle: the run is then not correct
(``run.guarantee_breaks``), and a window of them has no share to report."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["busy_s"] or not obs["peaks"]:
        return None
    least_s = sum(obs["least_bytes"][q] for q in trace["questions"]) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
