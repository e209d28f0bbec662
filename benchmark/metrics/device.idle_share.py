"""Share of the traced window in which no operation ran on the device.  The
harness's own device work (looking into large answers) is left out of both the
window and the busy time."""


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    window_s = trace["window_s"] - trace["harness_busy_s"]
    return 100.0 * (1.0 - trace["busy_s"] / window_s) if window_s > 0 else None
