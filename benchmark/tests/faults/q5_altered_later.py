"""Fault: every answer after the first altered where it is produced (one more
in every sum of v1).  The first is the set-up's, kept and compared whole; the
later ones are for a sample to catch, once the kept total is passed."""

asked = 0


def run(pd, x):
    global asked
    answer = x.groupby("id6", observed=True).agg({"v1": "sum", "v2": "sum", "v3": "sum"})
    if pd.__name__ == "pandas":
        return answer
    asked += 1
    return answer if asked == 1 else answer + 1


def least_bytes(config):
    return 4 * 8 * config["rows"]
