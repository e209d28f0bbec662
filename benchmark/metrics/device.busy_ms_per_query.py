"""Union of the program's device-operation intervals in the traced window over
its requests (the harness's own device work left out)."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["requests"]:
        return None
    return 1e3 * trace["busy_s"] / trace["requests"]
