"""Plain reference of the k-party MEDIAN protocol (arXiv:1202.6078 §5).

A host loop over turns in float64 numpy, written from the paper's
description and independent of the program: turn ``t`` makes node
``t mod k`` the coordinator, which

1. picks, over the allowed directions of an ``m``-angle grid, the cut that
   best halves its at-risk points (those a transcript-consistent threshold
   could still misclassify), and ships its band edges along that direction
   (≤ 2 points) plus the direction and band (4 scalars) to each peer;
2. tries the band midpoint; every peer reports its error count (1 scalar);
   the session ends when the total is within ``floor(eps * n)``;
3. otherwise every peer replies with its extreme band points along the
   direction (≤ 2 points); a non-empty global band ends the session with
   one accept bit per peer, an empty one prunes the direction arc by the
   certified pivot pair (2 points to each peer).

``proj`` computes every projection; the control passes a lower-precision
one.  Returns a dict with ``w``, ``b``, ``converged``, ``rounds`` and the
communication record ``comm``.
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import numpy as np

Proj = Callable[[np.ndarray, np.ndarray], np.ndarray]


def proj_f64(X: np.ndarray, V: np.ndarray) -> np.ndarray:
    """(..., d) points × (m, d) directions -> (..., m), float64."""
    return np.asarray(X, np.float64) @ np.asarray(V, np.float64).T


def proj_bf16x3(X: np.ndarray, V: np.ndarray) -> np.ndarray:
    """The projections as a TPU computes a float32 dot at precision
    ``high`` (the control): each operand split into a bfloat16 head and
    tail, and the three products but the tails' summed in float32."""
    import ml_dtypes

    def split(a):
        a = np.asarray(a, np.float32)
        hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
        return hi, (a - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    xh, xl = split(X)
    vh, vl = split(np.asarray(V).T)
    return (xh @ vh + (xh @ vl + xl @ vh)).astype(np.float64)


def direction_grid(m: int) -> np.ndarray:
    theta = 2.0 * np.pi * np.arange(m) / m
    return np.stack([np.cos(theta), np.sin(theta)], axis=1)


class _Log:
    def __init__(self, d: int):
        self.d = d
        self.points = self.scalars = self.bits = self.messages = 0
        self.rounds = 0

    def send(self, points=0, scalars=0, bits=0):
        self.points += points
        self.scalars += scalars
        self.bits += bits
        self.messages += 1

    def record(self) -> dict:
        wire = (self.points * (self.d + 1) + self.scalars) * 32 + self.bits
        return {"points": self.points, "scalars": self.scalars,
                "bits": self.bits, "messages": self.messages,
                "rounds": self.rounds, "bytes": -(-wire // 8)}


def _empty(d):
    return np.zeros((0, d)), np.zeros((0,), np.int32)


def _cat(a, b):
    return np.concatenate([a[0], b[0]]), np.concatenate([a[1], b[1]])


def run(shards: List[Tuple[np.ndarray, np.ndarray]], *, eps: float,
        max_epochs: int, n_angles: int, proj: Proj = proj_f64) -> dict:
    k = len(shards)
    d = shards[0][0].shape[1]
    own = [(np.asarray(X, np.float64), np.asarray(y, np.int32))
           for X, y in shards]
    budget = int(np.floor(eps * sum(len(y) for _X, y in own)))
    # transcript of node j: every point it received or sent, in order
    trans = [_empty(d) for _ in range(k)]
    log = _Log(d)
    V = direction_grid(n_angles)
    dir_ok = np.ones(n_angles, bool)
    w_h = b_h = None

    def append(j, pts, labs):
        trans[j] = _cat(trans[j], (np.asarray(pts, np.float64).reshape(-1, d),
                                   np.asarray(labs, np.int32)))

    for epoch in range(max_epochs):
        for ci in range(k):
            log.rounds += 1
            Xc, yc = own[ci]
            Wx, Wy = trans[ci]
            # -- 1. the cut that best halves the coordinator's risk set ----
            if Wx.shape[0]:
                pw = proj(Wx, V)                                  # (n_w, m)
                lo = np.max(np.where((Wy == 1)[:, None], pw, -np.inf),
                            axis=0)
                hi = np.min(np.where((Wy == -1)[:, None], pw, np.inf),
                            axis=0)
                nonempty = (lo < hi) & dir_ok
                pc = proj(Xc, V)                                  # (n, m)
                risk = np.where((yc == 1)[:, None], pc > lo[None, :],
                                pc < hi[None, :]) & nonempty[None, :]
                idxs = np.flatnonzero(dir_ok)
                sub = risk[:, idxs]
                csum = np.cumsum(sub, axis=1)
                total = csum[:, -1:]
                live = total > 0
                below = np.sum((csum == total) & live, axis=0)
                above = np.sum((csum == 0) & live, axis=0)
                v_idx = int(idxs[int(np.argmax(np.minimum(below, above)))])
            else:
                v_idx = int(np.flatnonzero(dir_ok)[0])
            v = V[v_idx]

            # -- band edges of the coordinator along v ----------------------
            Kx, Ky = _cat(own[ci], trans[ci])
            pk = proj(Kx, v[None, :])[:, 0]
            S_pts, S_lab = [], []
            lo_c, hi_c = -np.inf, np.inf
            if (Ky == 1).any():
                i = int(np.argmax(np.where(Ky == 1, pk, -np.inf)))
                lo_c = pk[i]
                S_pts.append(Kx[i]); S_lab.append(1)
            if (Ky == -1).any():
                i = int(np.argmin(np.where(Ky == -1, pk, np.inf)))
                hi_c = pk[i]
                S_pts.append(Kx[i]); S_lab.append(-1)
            for j in range(k):
                if j != ci:
                    log.send(points=len(S_pts))
                    log.send(scalars=d + 2)
                append(j, S_pts, S_lab)

            # -- 2. ε-exit on the band midpoint ----------------------------
            if np.isfinite(lo_c) and np.isfinite(hi_c) and lo_c < hi_c:
                t_c = 0.5 * (lo_c + hi_c)
                errs = 0
                for j in range(k):
                    pj = proj(own[j][0], v[None, :])[:, 0]
                    errs += int(np.sum(np.where(pj < t_c, 1, -1)
                                       != own[j][1]))
                    if j != ci:
                        log.send(scalars=1)
                w_h, b_h = -v, t_c
                if errs <= budget:
                    return {"w": w_h, "b": b_h, "converged": True,
                            "rounds": epoch + 1, "comm": log.record()}

            # -- 3. extremes of every node along v (post-S transcripts) ----
            lo_g, hi_g = -np.inf, np.inf
            best_p = best_q = None
            replies = []
            for j in range(k):
                Jx, Jy = _cat(own[j], trans[j])
                pj = proj(Jx, v[None, :])[:, 0]
                pts, labs = [], []
                if (Jy == 1).any():
                    i = int(np.argmax(np.where(Jy == 1, pj, -np.inf)))
                    if pj[i] > lo_g:
                        lo_g, best_p = pj[i], Jx[i]
                    pts.append(Jx[i]); labs.append(1)
                if (Jy == -1).any():
                    i = int(np.argmin(np.where(Jy == -1, pj, np.inf)))
                    if pj[i] < hi_g:
                        hi_g, best_q = pj[i], Jx[i]
                    pts.append(Jx[i]); labs.append(-1)
                replies.append((pts, labs))
            for j, (pts, labs) in enumerate(replies):
                if j != ci and pts:
                    log.send(points=len(pts))
                    append(ci, pts, labs)
                    append(j, pts, labs)

            if lo_g < hi_g:
                lo2 = lo_g if np.isfinite(lo_g) else hi_g - 2.0
                hi2 = hi_g if np.isfinite(hi_g) else lo2 + 2.0
                for j in range(k):
                    if j != ci:
                        log.send(bits=1)
                return {"w": -v, "b": 0.5 * (lo2 + hi2), "converged": True,
                        "rounds": epoch + 1, "comm": log.record()}

            # -- empty global band: certified pivot prune ------------------
            new_ok = dir_ok & (proj((best_q - best_p)[None, :], V)[0] > 1e-12)
            new_ok[v_idx] = False
            if new_ok.any():
                dir_ok = new_ok
            for j in range(k):
                if j != ci:
                    log.send(points=2)
                append(j, [best_p, best_q], [1, -1])
            if w_h is None:
                t_fb = (0.5 * (lo_c + hi_c)
                        if np.isfinite(lo_c) and np.isfinite(hi_c) else 0.0)
                w_h, b_h = -v, t_fb
    return {"w": w_h, "b": b_h, "converged": False, "rounds": max_epochs,
            "comm": log.record()}
