"""H2O db-benchmark groupby data (``_data/groupby-datagen.R``): N rows, K
groups, no NAs, unsorted.

R's sampler is replaced by numpy's ``default_rng``; every column draws from a
child of the seed, so the columns can be made side by side and come out the
same whatever the order.  id1-id3 are built as categoricals from codes, as
the benchmark's pandas solution casts them.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas


def make(seed, config, rows):
    k = int(config["groups_k"])
    many = max(rows // k, 1)
    makers = {
        "id1": lambda rng: rng.integers(0, k, rows, dtype=np.int8 if k <= 127 else np.int32),
        "id2": lambda rng: rng.integers(0, k, rows, dtype=np.int8 if k <= 127 else np.int32),
        "id3": lambda rng: rng.integers(0, many, rows, dtype=np.int32),
        "id4": lambda rng: rng.integers(1, k + 1, rows),
        "id5": lambda rng: rng.integers(1, k + 1, rows),
        "id6": lambda rng: rng.integers(1, many + 1, rows),
        "v1": lambda rng: rng.integers(1, 6, rows),
        "v2": lambda rng: rng.integers(1, 16, rows),
        "v3": lambda rng: np.round(rng.uniform(0.0, 100.0, rows), 6),
    }
    children = np.random.SeedSequence(int(seed) % 2**63).spawn(len(makers))
    with ThreadPoolExecutor(max_workers=len(makers)) as pool:
        futures = {
            name: pool.submit(maker, np.random.default_rng(child))
            for (name, maker), child in zip(makers.items(), children)
        }
        columns = {name: future.result() for name, future in futures.items()}
    few = [f"id{i:03d}" for i in range(1, k + 1)]
    columns["id1"] = pandas.Categorical.from_codes(columns["id1"], few)
    columns["id2"] = pandas.Categorical.from_codes(columns["id2"], few)
    columns["id3"] = pandas.Categorical.from_codes(
        columns["id3"], [f"id{i:010d}" for i in range(1, many + 1)]
    )
    return columns
