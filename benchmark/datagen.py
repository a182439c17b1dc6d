"""HIGGS-shaped synthetic data, made on the host from ``--seed``.

The rule is ``chip_smoke.py``'s (a copy, so the smoke may change without
moving the yardstick): dense gaussians, label = sign of
``x0*x1 + 0.5*x2 - 0.8*x3*[x4 > 0]``.  The drawing differs: rows are
drawn block by block from child streams of one ``SeedSequence`` and the
blocks are filled by a few threads (numpy's generators release the
interpreter lock), straight into ``float32`` — the same seed gives the
same matrix on any number of threads, in a fifth of the time.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

#: rows per child stream; fixed, because it is part of what a seed means
BLOCK_ROWS = 500_000
_THREADS = 8


def label_rule(X: np.ndarray) -> np.ndarray:
    margin = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2]
              - 0.8 * X[:, 3] * (X[:, 4] > 0))
    return (margin > 0).astype(np.float32)


def higgs_like(rows: int, features: int, seed: int,
               stream: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """``rows x features`` float32 and their labels.  ``stream`` names an
    independent draw of the same seed (0 = training rows, 1 = held-out)."""
    if features < 5:
        raise ValueError("the label rule reads five features")
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    starts = list(range(0, rows, BLOCK_ROWS))
    children = np.random.SeedSequence(
        entropy=int(seed), spawn_key=(int(stream),)).spawn(len(starts))

    def fill(job):
        lo, child = job
        hi = min(lo + BLOCK_ROWS, rows)
        np.random.default_rng(child).standard_normal(
            out=X[lo:hi], dtype=np.float32)
        y[lo:hi] = label_rule(X[lo:hi])

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, zip(starts, children)))
    return X, y
