"""95th percentile (nearest rank) of every request wall of the window."""

import math


def read(obs):
    walls = sorted(r["wall_s"] for r in obs["requests"])
    return walls[math.ceil(0.95 * len(walls)) - 1] if walls else None
