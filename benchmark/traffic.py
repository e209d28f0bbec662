"""The one traffic generator: reads a cell's file and yields its requests.

A cell's ``questions`` give names and whole-number weights.  The order is a
run of blocks, each block every question ``weight`` times, shuffled from the
seed: every seed sends the same set of questions in another order, and any
window holds them in the cell's proportions to within one block.
"""

import numpy as np


def block(cell):
    names = []
    for entry in cell["questions"]:
        weight = entry.get("weight", 1)
        if weight != int(weight) or weight < 1:
            raise ValueError(f"weight of {entry['name']!r} is not a whole number >= 1")
        names.extend([entry["name"]] * int(weight))
    return names


def requests(cell, seed):
    """Question names without end, block-shuffled from ``seed``."""
    if cell["loop"] != {"kind": "closed", "clients": 1}:
        raise ValueError(f"this generator drives one closed-loop client, not {cell['loop']}")
    rng = np.random.default_rng([int(seed) % 2**63, 0x7AFF1C])
    names = block(cell)
    while True:
        for pos in rng.permutation(len(names)):
            yield names[pos]
