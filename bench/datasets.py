"""Instance generators of the benchmark's traffic.

Copies of the paper's synthetic partitions (arXiv:1202.6078 §7, Figures
3/4): Data1 (iid split of two blobs), Data2 (disjoint bands), Data3 (the
voting killer), the k-party mixed-hardness partition, and the lift to R^d
of Table 3.  They are copied rather than imported so that a change to the
program's own generators cannot move the yardstick.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

Shard = Tuple[np.ndarray, np.ndarray]


def _blob(rng, center, n, scale=0.25):
    return rng.normal(0.0, scale, size=(n, len(center))) + np.asarray(center)


def _box(rng, lo, hi, n):
    lo = np.asarray(lo, dtype=np.float64)
    hi = np.asarray(hi, dtype=np.float64)
    return rng.uniform(lo, hi, size=(n, len(lo)))


def _labels(half: int) -> np.ndarray:
    return np.concatenate([np.ones(half), -np.ones(half)]).astype(np.int32)


def data1(n_per_node: int, k: int, rng) -> List[Shard]:
    """Easy: iid split of two well-separated blobs (separator x=0)."""
    half = n_per_node // 2
    out = []
    for _ in range(k):
        X = np.concatenate([_blob(rng, (-1.5, 0.0), half),
                            _blob(rng, (+1.5, 0.0), half)])
        out.append((X, _labels(half)))
    return out


def data2(n_per_node: int, k: int, rng) -> List[Shard]:
    """Nodes occupy disjoint y-bands of one set separable by x=0."""
    half = n_per_node // 2
    out = []
    for i in range(k):
        y0 = -2.0 + 4.0 * i / max(k - 1, 1)
        X = np.concatenate([
            _box(rng, (-2.5, y0 - 0.4), (-0.5, y0 + 0.4), half),
            _box(rng, (0.5, y0 - 0.4), (2.5, y0 + 0.4), half)])
        out.append((X, _labels(half)))
    return out


def data3(n_per_node: int, k: int, rng) -> List[Shard]:
    """The voting killer: node i sits in a narrow x-column around the
    slanted global separator y = x/2, so local separators mislead."""
    half = n_per_node // 2
    out = []
    for cx in np.linspace(-2.5, 2.5, k):
        ly = cx / 2.0
        X = np.concatenate([
            _box(rng, (cx - 0.3, ly + 0.5), (cx + 0.3, ly + 1.0), half),
            _box(rng, (cx - 0.3, ly - 1.0), (cx + 0.3, ly - 0.5), half)])
        out.append((X, _labels(half)))
    return out


def mixed_hardness(n_per_node: int, k: int, rng, gap: float = 0.15,
                   n_hard: int = 2) -> List[Shard]:
    """``n_hard`` nodes hold tight bands ``gap`` wide around y = x/2 in
    their own x-columns (a multi-epoch support exchange); the rest hold far
    easy blobs."""
    half = n_per_node // 2
    out = []
    for i, cx in enumerate(np.linspace(-2.0, 2.0, k)):
        ly = cx / 2.0
        lo_p, hi_p = (gap, 2.5 * gap) if i < n_hard else (1.2, 2.0)
        Xp = rng.uniform((cx - 0.3, ly + lo_p), (cx + 0.3, ly + hi_p),
                         size=(half, 2))
        Xn = rng.uniform((cx - 0.3, ly - hi_p), (cx + 0.3, ly - lo_p),
                         size=(half, 2))
        out.append((np.concatenate([Xp, Xn]), _labels(half)))
    return out


def lift(shards: List[Shard], d: int, rng, noise: float = 0.05
         ) -> List[Shard]:
    """Embed 2-D shards in R^d (Table 3): the structure stays in the first
    two coordinates, the other d-2 are small iid noise."""
    return [(np.concatenate(
        [X, rng.normal(0.0, noise, size=(X.shape[0], d - 2))], axis=1), y)
        for X, y in shards]


GENERATORS = {"data1": data1, "data2": data2, "data3": data3,
              "mixed_hardness": mixed_hardness}


def make(gen: str, n_per_node: int, k: int, d: int, seed, **kw
         ) -> List[Shard]:
    """One instance: ``k`` float32 shards of ``n_per_node`` points in R^d
    from generator ``gen``, drawn from ``seed`` (any integer or tuple)."""
    rng = np.random.default_rng(seed)
    shards = GENERATORS[gen](n_per_node, k, rng, **kw)
    if d > 2:
        shards = lift(shards, d, rng)
    return [(X.astype(np.float32), y) for X, y in shards]
