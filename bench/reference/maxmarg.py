"""Plain reference of the k-party MAXMARG protocol (arXiv:1202.6078 §4.4
and §7) with its hard-margin solver.

The protocol is a host loop per session, written as a generator that
yields each fit set and receives the fitted separator, so that the fits
of many sessions run as one batched solve.  Turn ``t`` makes node
``t mod k`` the coordinator, which

1. fits a max-margin separator on its own points and everything it has
   received;
2. ships its active-margin support points (margin within 15% of the
   least, at most ``max_support``, the tightest by (margin, index), in
   index order) to each peer;
3. learns from each peer one bit (the proposal makes no error on the
   peer's points); every peer with an error replies with its two most
   violated points (ascending margin, ties by index);
4. ends the session when the total error is within ``floor(eps * n)``.

The solver is annealed Pegasos on ``lam/2 |w|^2 + mean hinge``: stages
at ``lam0 * 0.1**s``, each of ``steps`` projected subgradient steps with
``eta = 1 / (lam (i + 2))``, warm-started from the previous stage; the
first stage whose iterate separates the fit set wins, and the result is
scaled to functional margin 1.  Label-0 rows pad a fit set and take no
part.  ``mul`` computes every product of a point with a separator; the
control passes a lower-precision one.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

RTOL = 0.15
VIOL_SHIP = 2
ROW_BLOCK = 512     # fit sets pad to a multiple of it, so few programs serve


def mul_f32(a, b):
    return a * b


def _bf16(x):
    """``x`` rounded to bfloat16 by ``reduce_precision``, which a compiler
    may not drop as it may a pair of casts."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def mul_bf16(a, b):
    """A product as a TPU computes a float32 dot at its default precision:
    both operands rounded to bfloat16, one pass."""
    return _bf16(a) * _bf16(b)


def dot_f64(X, w):
    return np.asarray(X, np.float64) @ np.asarray(w, np.float64)


def dot_bf16(X, w):
    """``X @ w`` with every product at default precision, on the host."""
    import ml_dtypes

    def rnd(a):
        return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
            np.float32)
    return (rnd(X) @ rnd(w)).astype(np.float64)


def _split(x):
    """A float32 array as a bfloat16 head and a bfloat16 tail."""
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def mul_high(a, b):
    """A product as a TPU computes a float32 dot at precision ``high``:
    bfloat16 heads and tails, the three products but the tails' summed."""
    ah, al = _split(a)
    bh, bl = _split(b)
    return ah * bh + (ah * bl + al * bh)


def dot_high(X, w):
    """``X @ w`` at precision ``high``, on the host."""
    import ml_dtypes

    def split(a):
        a = np.asarray(a, np.float32)
        hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
        return hi, (a - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    xh, xl = split(X)
    wh, wl = split(w)
    return (xh @ wh + (xh @ wl + xl @ wh)).astype(np.float64)


MULS = {"highest": mul_f32, "high": mul_high, "bf16": mul_bf16}
DOTS = {"highest": dot_f64, "high": dot_high, "bf16": dot_bf16}


@functools.partial(jax.jit, static_argnames=("steps", "stages", "mode"))
def anneal(X, y, lam0, *, steps: int, stages: int, mode: str = "highest"):
    """Batched annealed Pegasos: ``X`` (B, N, d) f32, ``y`` (B, N) f32 in
    {+1, -1, 0}.  Returns ``(w, b, found)``."""
    mul = MULS[mode]
    B, N, d = X.shape
    valid = y != 0.0
    nv = jnp.maximum(jnp.sum(valid, axis=1), 1).astype(jnp.float32)

    def decide(w, b):
        acc = mul(X[:, :, 0], w[:, None, 0])
        for j in range(1, d):
            acc = acc + mul(X[:, :, j], w[:, None, j])
        return acc + b[:, None]

    def min_margin(w, b):
        return jnp.min(jnp.where(valid, y * decide(w, b), jnp.inf), axis=1)

    def stage(carry):
        s, w, b, wb, bb, found = carry
        lam = jnp.float32(lam0) * jnp.float32(0.1) ** s.astype(jnp.float32)

        def body(i, wb_):
            w, b = wb_
            eta = 1.0 / (lam * (i.astype(jnp.float32) + 2.0))
            vy = ((y * decide(w, b) < 1.0) & valid).astype(jnp.float32) * y
            g = jnp.stack([jnp.sum(mul(vy, X[:, :, j]), axis=1)
                           for j in range(d)], axis=1)
            w = w - eta * (lam * w - g / nv[:, None])
            b = b - eta * (-jnp.sum(vy, axis=1) / nv)
            nrm = jnp.sqrt(jnp.sum(w * w, axis=1))
            scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / (nrm + 1e-12))
            return w * scale[:, None], b * scale

        w, b = jax.lax.fori_loop(0, steps, body, (w, b))
        ok = min_margin(w, b) > 0.0
        take = ok & ~found
        wb = jnp.where(take[:, None], w, wb)
        bb = jnp.where(take, b, bb)
        return s + 1, w, b, wb, bb, found | ok

    z_w = jnp.zeros((B, d), jnp.float32)
    z_b = jnp.zeros((B,), jnp.float32)
    _, w, b, wb, bb, found = jax.lax.while_loop(
        lambda c: (c[0] < stages) & ~jnp.all(c[5]), stage,
        (jnp.int32(0), z_w, z_b, z_w, z_b, jnp.zeros((B,), bool)))
    w = jnp.where(found[:, None], wb, w)
    b = jnp.where(found, bb, b)
    mm = min_margin(w, b)
    can = found & jnp.isfinite(mm) & (mm > 0.0)
    scale = jnp.where(can, 1.0 / jnp.where(can, mm, 1.0), 1.0)
    return w * scale[:, None], b * scale, found


class _Log:
    def __init__(self, d: int):
        self.d = d
        self.points = self.bits = self.messages = self.rounds = 0

    def send(self, points=0, bits=0):
        self.points += points
        self.bits += bits
        self.messages += 1

    def record(self) -> dict:
        wire = self.points * (self.d + 1) * 32 + self.bits
        return {"points": self.points, "scalars": 0, "bits": self.bits,
                "messages": self.messages, "rounds": self.rounds,
                "bytes": -(-wire // 8)}


def session(shards, *, eps: float, max_epochs: int, max_support: int,
            dot=dot_f64):
    """One session as a generator: yields ``(X, y)`` fit sets, receives
    ``(w, b)`` float64, returns the result dict.  ``dot`` computes the
    host's margins."""
    k = len(shards)
    d = shards[0][0].shape[1]
    own = [(np.asarray(X, np.float64), np.asarray(y, np.int32))
           for X, y in shards]
    budget = int(np.floor(eps * sum(len(y) for _X, y in own)))
    recv = [(np.zeros((0, d)), np.zeros((0,), np.int32)) for _ in range(k)]
    log = _Log(d)
    w = b = None

    def add(j, X, y):
        recv[j] = (np.concatenate([recv[j][0], X]),
                   np.concatenate([recv[j][1], y]))

    for epoch in range(max_epochs):
        for ci in range(k):
            log.rounds += 1
            Kx = np.concatenate([own[ci][0], recv[ci][0]])
            Ky = np.concatenate([own[ci][1], recv[ci][1]])
            w, b = yield Kx, Ky
            m = Ky * (dot(Kx, w) + b)
            band = np.flatnonzero(m <= max(m.min(), 1e-12) * (1.0 + RTOL))
            if len(band) > max_support:
                band = np.sort(band[np.argsort(m[band], kind="stable")
                                    [:max_support]])
            errs = 0
            for j in range(k):
                Xj, yj = own[j]
                dj = dot(Xj, w) + b
                mj = yj * dj
                e = int(np.sum(np.where(dj > 0, 1, -1) != yj))
                errs += e
                if j == ci:
                    continue
                log.send(points=len(band))
                add(j, Kx[band], Ky[band])
                log.send(bits=1)
                if e > 0:
                    worst = np.argsort(mj, kind="stable")[:VIOL_SHIP]
                    log.send(points=len(worst))
                    add(ci, Xj[worst], yj[worst])
            if errs <= budget:
                return {"w": w, "b": b, "converged": True,
                        "rounds": epoch + 1, "comm": log.record()}
    return {"w": w, "b": b, "converged": False, "rounds": max_epochs,
            "comm": log.record()}


def run_batch(instances: List[List[Tuple[np.ndarray, np.ndarray]]], *,
              eps: float, max_epochs: int, max_support: int, lam0: float,
              steps: int, stages: int, mode: str = "highest",
              wrap: Optional[Callable] = None
              ) -> List[dict]:
    """Every instance's whole session, the fits of the sessions still
    running batched turn by turn.  A batch holds the running sessions'
    fit sets, padded with label-0 rows to a multiple of ``ROW_BLOCK`` rows
    and with empty fit sets to a power of two of at least 8, so a few
    programs serve every turn.  ``wrap(session, shards)`` may stand a
    changed session in for each one (a planted fault)."""
    gens = [session(s, eps=eps, max_epochs=max_epochs,
                    max_support=max_support, dot=DOTS[mode])
            for s in instances]
    if wrap is not None:
        gens = [wrap(g, s) for g, s in zip(gens, instances)]
    reqs = {i: next(g) for i, g in enumerate(gens)}
    out: List[Optional[dict]] = [None] * len(gens)
    while reqs:
        live = sorted(reqs)
        n_rows = max(len(y) for _X, y in reqs.values())
        N = -(-n_rows // ROW_BLOCK) * ROW_BLOCK
        B = max(8, 1 << (len(live) - 1).bit_length())
        d = reqs[live[0]][0].shape[1]
        X = np.zeros((B, N, d), np.float32)
        y = np.zeros((B, N), np.float32)
        for r, i in enumerate(live):
            Xi, yi = reqs[i]
            X[r, :len(yi)] = Xi
            y[r, :len(yi)] = yi
        w, b, _ = anneal(jnp.asarray(X), jnp.asarray(y), lam0, steps=steps,
                         stages=stages, mode=mode)
        w = np.asarray(w, np.float64)
        b = np.asarray(b, np.float64)
        for r, i in enumerate(live):
            try:
                reqs[i] = gens[i].send((w[r], float(b[r])))
            except StopIteration as stop:
                out[i] = stop.value
                del reqs[i]
    return out
