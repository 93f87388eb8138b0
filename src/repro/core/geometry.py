"""Geometric primitives for the protocols.

Control-plane geometry (convex hulls, medians) runs on host numpy — protocol
rounds are tiny.  Data-plane bulk operations (margins over big shards,
set-of-uncertainty scans over direction space) are jit'd JAX, and the margin
hot loop has a Pallas kernel in ``repro.kernels.support_margin``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Convex hulls (2D, host-side; monotone chain)
# ---------------------------------------------------------------------------

def convex_hull_2d(points: np.ndarray) -> np.ndarray:
    """Indices of the convex hull of 2-D ``points`` in counter-clockwise order.

    Andrew's monotone chain; O(n log n).  Degenerate inputs (<=2 points or
    collinear) return all unique points.
    """
    pts = np.asarray(points, dtype=np.float64)
    n = pts.shape[0]
    if n == 0:
        return np.zeros((0,), dtype=np.int64)
    order = np.lexsort((pts[:, 1], pts[:, 0]))
    pts_sorted = pts[order]

    def cross(o, a, b):
        return (a[0] - o[0]) * (b[1] - o[1]) - (a[1] - o[1]) * (b[0] - o[0])

    if n < 3:
        return order

    lower: list = []
    for i in range(n):
        while len(lower) >= 2 and cross(pts_sorted[lower[-2]], pts_sorted[lower[-1]], pts_sorted[i]) <= 0:
            lower.pop()
        lower.append(i)
    upper: list = []
    for i in range(n - 1, -1, -1):
        while len(upper) >= 2 and cross(pts_sorted[upper[-2]], pts_sorted[upper[-1]], pts_sorted[i]) <= 0:
            upper.pop()
        upper.append(i)
    hull_sorted = lower[:-1] + upper[:-1]
    if not hull_sorted:  # fully collinear
        hull_sorted = [0, n - 1]
    return order[np.asarray(hull_sorted, dtype=np.int64)]


def hull_edges(points: np.ndarray, hull_idx: np.ndarray) -> np.ndarray:
    """(m, 2, 2) array of hull edge segments in CCW order."""
    h = points[hull_idx]
    return np.stack([h, np.roll(h, -1, axis=0)], axis=1)


def edge_normals(edges: np.ndarray) -> np.ndarray:
    """Outward normals of CCW hull edges, unit length. edges: (m,2,2)."""
    d = edges[:, 1] - edges[:, 0]
    n = np.stack([d[:, 1], -d[:, 0]], axis=-1)  # rotate -90deg: outward for CCW
    norm = np.linalg.norm(n, axis=-1, keepdims=True)
    norm = np.where(norm == 0, 1.0, norm)
    return n / norm


def project_to_hull_boundary(points: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """For each point return the index of the nearest hull edge.

    Implements the MEDIAN subroutine's 'project U_A onto ∂P_A' step (paper
    Alg. 2, line 3): each uncertain point is charged to the closest boundary
    edge, producing the per-edge weights used for the weighted median.
    """
    if len(points) == 0:
        return np.zeros((0,), dtype=np.int64)
    a = edges[:, 0][None, :, :]  # (1, m, 2)
    b = edges[:, 1][None, :, :]
    p = np.asarray(points)[:, None, :]  # (n, 1, 2)
    ab = b - a
    denom = np.maximum((ab * ab).sum(-1), 1e-30)
    t = np.clip(((p - a) * ab).sum(-1) / denom, 0.0, 1.0)
    proj = a + t[..., None] * ab
    dist = np.linalg.norm(p - proj, axis=-1)  # (n, m)
    return np.argmin(dist, axis=1)


def weighted_median_index(weights: np.ndarray) -> int:
    """Index of the weighted median item (first index where cumsum >= half)."""
    w = np.asarray(weights, dtype=np.float64)
    total = w.sum()
    if total <= 0:
        return 0
    c = np.cumsum(w)
    return int(np.searchsorted(c, total / 2.0))


# ---------------------------------------------------------------------------
# Margins / separability (JAX data plane)
# ---------------------------------------------------------------------------

# f32 contractions on a TPU default to reduced-precision passes; the
# protocols compare these projections strictly against bounds that are
# themselves projections, so every contraction here asks for full f32
_EXACT = jax.lax.Precision.HIGHEST


@jax.jit
def signed_margins(w: jnp.ndarray, b: jnp.ndarray, X: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """y * (X @ w + b) — positive iff correctly classified."""
    return y * (jnp.matmul(X, w, precision=_EXACT) + b)


@jax.jit
def classification_error(w: jnp.ndarray, b: jnp.ndarray, X: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Fraction of misclassified points (ties count as errors)."""
    return jnp.mean(signed_margins(w, b, X, y) <= 0)


@functools.partial(jax.jit, static_argnames=("n_angles",))
def direction_grid(n_angles: int) -> jnp.ndarray:
    """Unit vectors covering S^1: (n_angles, 2)."""
    theta = jnp.linspace(0.0, 2.0 * jnp.pi, n_angles, endpoint=False)
    return jnp.stack([jnp.cos(theta), jnp.sin(theta)], axis=-1)


@jax.jit
def consistent_threshold_ranges(
    V: jnp.ndarray, Xw: jnp.ndarray, yw: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-direction interval of thresholds consistent with transcript W.

    Classifier convention: predict +1 iff v·x < t.  For direction v the
    consistent thresholds are ( max_{+} v·x , min_{-} v·x ); the interval is
    empty (lo >= hi) iff W is not separable along v.

    V: (m, 2) unit directions; Xw: (n, 2) transcript points; yw: (n,) ±1.
    Returns (lo, hi): each (m,).  With an empty transcript lo=-inf, hi=+inf.
    """
    proj = jnp.matmul(V, Xw.T, precision=_EXACT)  # (m, n)
    big = jnp.inf
    pos = yw == 1
    neg = yw == -1   # explicit: label-0 padding rows constrain neither side
    lo = jnp.max(jnp.where(pos[None, :], proj, -big), axis=1, initial=-big)
    hi = jnp.min(jnp.where(neg[None, :], proj, big), axis=1, initial=big)
    return lo, hi


@jax.jit
def uncertain_mask(
    V: jnp.ndarray,
    dir_ok: jnp.ndarray,
    Xw: jnp.ndarray,
    yw: jnp.ndarray,
    X: jnp.ndarray,
    y: jnp.ndarray,
) -> jnp.ndarray:
    """Set of uncertainty: which of (X, y) can a transcript-consistent
    classifier (direction allowed by ``dir_ok``) still misclassify?

    Convention: predict +1 iff v·x < t, consistent t ∈ (lo, hi).  A positive
    point q is misclassified by some consistent classifier along v iff a
    consistent t ≤ v·q exists, i.e. v·q > lo.  A negative q is misclassified
    iff a consistent t > v·q exists, i.e. v·q < hi.  Returns boolean (n,)
    mask — the SOU of paper §4.1.
    """
    lo, hi = consistent_threshold_ranges(V, Xw, yw)  # (m,)
    nonempty = (lo < hi) & dir_ok
    proj = jnp.matmul(V, X.T, precision=_EXACT)  # (m, n)
    pos_risk = proj > lo[:, None]
    neg_risk = proj < hi[:, None]
    at_risk = jnp.where((y == 1)[None, :], pos_risk, neg_risk)
    return jnp.any(at_risk & nonempty[:, None], axis=0)
