"""The one generator of serving traffic: open-loop arrivals read from a
traffic file's parameters.

Parameters (``bench/traffic/<name>.json``):

* ``rate``: requests per second offered, fixed per cell (no search);
* ``rank_share``: the share of requests that are filtered tail ranks
  ``(h, r, t)``; the rest are top-``k`` tails ``(h, r)``;
* ``k``: the top-k size;
* ``zipf``: the exponent of query popularity over the test split, taken in
  a seed-shuffled order (0.99 is YCSB's default zipfian constant).

Every seed gets the same number of requests (rate x seconds) and the same
number of each kind: arrivals are a Poisson process conditioned on that
count (sorted uniform times), so seeds change the order and the queries,
never the amount of work.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def open_loop(params: Dict, test: np.ndarray, seed: int, seconds: float) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng([seed, 0x5E7E])
    n = max(1, int(round(params["rate"] * seconds)))
    at = np.sort(rng.uniform(0.0, seconds, n))
    n_rank = int(round(n * params["rank_share"]))
    kind = np.zeros(n, np.int8)       # 0 = rank, 1 = top-k
    kind[n_rank:] = 1
    rng.shuffle(kind)
    order = rng.permutation(len(test))
    w = 1.0 / np.arange(1, len(test) + 1, dtype=np.float64) ** params["zipf"]
    pick = order[rng.choice(len(test), size=n, p=w / w.sum())]
    q = test[pick]
    return {"at": at, "kind": kind, "h": q[:, 0].astype(np.int64),
            "r": q[:, 1].astype(np.int64), "t": q[:, 2].astype(np.int64)}
