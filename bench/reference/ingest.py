"""Plain reference of streamed ingest: one reservoir (Vitter's algorithm R)
per node.  The first ``capacity`` points fill the reservoir in order; the
t-th point after that (1-based stream count t) draws ``j ~ U[0, t)`` and
replaces slot ``j`` when ``j < capacity``, later points winning.  The
draws come from ``numpy.random.default_rng((ingest_seed, handle, node))``,
one vector draw per fed batch, as the service's contract states."""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def reservoir(batches: Sequence[Tuple[np.ndarray, np.ndarray]],
              capacity: int, rng) -> Tuple[np.ndarray, np.ndarray]:
    X = np.zeros((capacity, batches[0][0].shape[1]), np.float64)
    y = np.zeros((capacity,), np.int32)
    seen = filled = 0
    for Xb, yb in batches:
        Xb = np.asarray(Xb, np.float64)
        n = len(yb)
        take = min(capacity - filled, n)
        X[filled:filled + take] = Xb[:take]
        y[filled:filled + take] = yb[:take]
        filled += take
        seen += take
        rest = n - take
        if rest == 0:
            continue
        t = seen + 1 + np.arange(rest)
        j = rng.integers(0, t)
        for r in np.flatnonzero(j < capacity):      # in stream order
            X[j[r]], y[j[r]] = Xb[take + r], yb[take + r]
        seen += rest
    return X[:filled], y[:filled]


def streamed_shards(shards, *, capacity: int, feed_batch: int,
                    ingest_seed: int, handle: int
                    ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """What ingest hands the pool for a session whose node ``j`` streamed
    ``shards[j]`` in batches of ``feed_batch`` rows."""
    out = []
    for node, (X, y) in enumerate(shards):
        rng = np.random.default_rng((ingest_seed, handle, node))
        batches = [(X[s:s + feed_batch], y[s:s + feed_batch])
                   for s in range(0, len(y), feed_batch)]
        Xs, ys = reservoir(batches, capacity, rng)
        out.append((Xs.astype(np.float32), ys))
    return out
