"""The asv suite's ``utils.make_frame`` (copied from ``asv_bench/benchmarks/
utils.py``): ``col<i>`` of int64 in [0, 100).  Each column draws from a child
of the seed so the columns can be made side by side."""

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def make(seed, config, rows):
    names = [f"col{i}" for i in range(int(config["columns"]))]
    children = np.random.SeedSequence(int(seed) % 2**63).spawn(len(names))
    with ThreadPoolExecutor(max_workers=len(names)) as pool:
        made = pool.map(lambda child: np.random.default_rng(child).integers(0, 100, rows), children)
        return dict(zip(names, made))
