"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the direct, unblocked mathematical definition — no tiling,
no online rescaling — used by the per-kernel ``assert_allclose`` sweeps in
``tests/test_kernels.py``.

The protocol oracles spell every projection as d multiply-adds in
coordinate order (``_proj`` / ``_dot``), never as a ``@`` contraction: that
is the arithmetic of the Pallas kernels and of the engine's inline scans,
it is exact f32 on every backend (a TPU ``@`` on f32 runs at reduced
precision by default), and strict comparisons against bounds that are
themselves projections then agree bit-for-bit.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def attention_ref(
    q: jnp.ndarray,                # (B, Sq, H, hd)
    k: jnp.ndarray,                # (B, Skv, KV, hd)
    v: jnp.ndarray,                # (B, Skv, KV, hdv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid: Optional[int] = None,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Dense softmax attention with GQA broadcast."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, hdv = v.shape
    G = H // KV
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    rows = q_offset + jnp.arange(Sq)
    cols = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= cols[None, :] <= rows[:, None]
    if window is not None:
        mask &= cols[None, :] > rows[:, None] - window
    if kv_valid is not None:
        mask &= cols[None, :] < kv_valid
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqs,bshd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def rwkv6_ref(
    r: jnp.ndarray,                # (B, S, H, hd)
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,                # data-dependent decay in (0, 1)
    u: jnp.ndarray,                # (H, hd) bonus
    S0: Optional[jnp.ndarray] = None,  # (B, H, hd, hd)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential RWKV-6 recurrence (the Finch time-mix WKV loop).

      y_t = r_t · (S_{t-1} + diag(u) k_t v_tᵀ);  S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
    """
    B, S, H, hd = r.shape
    Sst = jnp.zeros((B, H, hd, hd), jnp.float32) if S0 is None else S0.astype(jnp.float32)

    def step(Swkv, t):
        r_t, k_t, v_t, w_t = t
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum("bhk,bhkv->bhv", r_t, Swkv + u.astype(jnp.float32)[..., :, None] * kv)
        Swkv = w_t[..., :, None] * Swkv + kv
        return Swkv, y

    xs = tuple(a.astype(jnp.float32).transpose(1, 0, 2, 3) for a in (r, k, v, w))
    ST, ys = jax.lax.scan(step, Sst, xs)
    return ys.transpose(1, 0, 2, 3).astype(r.dtype), ST


def mamba_ref(
    xc: jnp.ndarray,               # (B, S, di) conv'd+silu'd inputs
    delta: jnp.ndarray,            # (B, S, di) softplus'd step sizes
    A: jnp.ndarray,                # (di, ds) negative
    Bs: jnp.ndarray,               # (B, S, ds)
    Cs: jnp.ndarray,               # (B, S, ds)
    h0: Optional[jnp.ndarray] = None,  # (B, di, ds)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential Mamba-1 selective scan.

      h_t = exp(Δ_t A) h_{t-1} + (Δ_t x_t) B_t;   y_t = h_t C_tᵀ
    """
    B, S, di = xc.shape
    ds = A.shape[1]
    h_init = jnp.zeros((B, di, ds), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, t):
        d_t, B_t, C_t, x_t = t
        dA = jnp.exp(d_t[..., None].astype(jnp.float32) * A.astype(jnp.float32))
        dBx = (d_t * x_t)[..., None].astype(jnp.float32) * B_t[:, None, :].astype(jnp.float32)
        h = dA * h + dBx
        y_t = jnp.einsum("bds,bs->bd", h, C_t.astype(jnp.float32))
        return h, y_t

    xs = (delta.transpose(1, 0, 2), Bs.transpose(1, 0, 2),
          Cs.transpose(1, 0, 2), xc.transpose(1, 0, 2))
    hT, ys = jax.lax.scan(step, h_init, xs)
    return ys.transpose(1, 0, 2).astype(xc.dtype), hT


def _proj(V: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """(m, d) directions × (n, d) points -> (m, n) projections."""
    acc = V[:, 0, None] * X[None, :, 0]
    for j in range(1, V.shape[1]):
        acc = acc + V[:, j, None] * X[None, :, j]
    return acc


def _dot(X: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """(..., d) points × (d,) direction -> (...) projections."""
    acc = X[..., 0] * w[0]
    for j in range(1, w.shape[0]):
        acc = acc + X[..., j] * w[j]
    return acc


def threshold_ranges_ref(
    V: jnp.ndarray,                # (m, d) directions
    Xw: jnp.ndarray,               # (n, d) transcript points
    yw: jnp.ndarray,               # (n,) ±1
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-direction consistent-threshold interval (lo, hi).

    Convention matches ``repro.core.geometry.consistent_threshold_ranges``:
    predict +1 iff v·x < t, so lo = max over positives, hi = min over
    negatives.  Label-0 rows (the padding convention) constrain neither side.
    """
    proj = _proj(V, Xw)
    big = jnp.inf
    pos = yw == 1
    neg = yw == -1
    lo = jnp.max(jnp.where(pos[None, :], proj, -big), axis=1, initial=-big)
    hi = jnp.min(jnp.where(neg[None, :], proj, big), axis=1, initial=big)
    return lo, hi


def uncertain_mask_ref(
    V: jnp.ndarray,                # (m, d)
    dir_ok: jnp.ndarray,           # (m,) bool
    lo: jnp.ndarray,               # (m,)
    hi: jnp.ndarray,               # (m,)
    X: jnp.ndarray,                # (n, d)
    y: jnp.ndarray,                # (n,) ±1
) -> jnp.ndarray:
    """Set-of-uncertainty membership for each point of (X, y)."""
    nonempty = (lo < hi) & dir_ok
    proj = _proj(V, X)             # (m, n)
    pos_risk = proj > lo[:, None]
    neg_risk = proj < hi[:, None]
    at_risk = jnp.where((y == 1)[None, :], pos_risk, neg_risk)
    return jnp.any(at_risk & nonempty[:, None], axis=0)


def median_cut_scores_ref(
    V: jnp.ndarray,                # (m, d)
    dir_ok: jnp.ndarray,           # (m,) bool
    lo: jnp.ndarray,               # (m,)
    hi: jnp.ndarray,               # (m,)
    X: jnp.ndarray,                # (n, d)
    y: jnp.ndarray,                # (n,) ±1 (0 = padding row)
) -> jnp.ndarray:
    """Median-cut scores (int32, (m,)): for each allowed cut angle, the
    smaller of the two counts of points whose whole at-risk arc lies
    strictly on one side — the discretized weighted-median hull edge the
    MEDIAN coordinator proposes (``argmax``).  Disallowed cuts score -1.

    Integer counts, so the Pallas kernel
    (``kernels.median_cut.median_cut_scores_batched``) matches bit-for-bit.
    """
    m = V.shape[0]
    proj = _proj(V, X)                                  # (m, n)
    nonempty = (lo < hi) & dir_ok
    lo_r = jnp.where(nonempty, lo, jnp.inf)
    hi_r = jnp.where(nonempty, hi, -jnp.inf)
    risk = jnp.where((y == 1)[None, :],
                     proj > lo_r[:, None], proj < hi_r[:, None])
    c = jnp.cumsum(risk.astype(jnp.int32), axis=0)      # (m, n)
    total = c[-1:, :]
    live = (total > 0) & ((y != 0)[None, :])
    below = jnp.sum(live & (c == total), axis=1)
    above = jnp.sum(live & (c == 0), axis=1)
    return jnp.where(dir_ok, jnp.minimum(below, above), -1).astype(jnp.int32)


def median_extremes_ref(
    v: jnp.ndarray,                # (d,) proposed direction
    XW: jnp.ndarray,               # (k, nW, d) per-node own ∪ capped transcript
    yW: jnp.ndarray,               # (k, nW) ±1 (0 = padding row)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MEDIAN's per-turn extremes scan (single instance): for each node, the
    row index of its extreme band point along ``v`` per class — the first
    argmax of the projection over positive rows (``i_p``) and the first
    argmin over negative rows (``i_q``); index 0 when the class is absent
    (callers gate on presence, derived from ``yW`` directly).

    Integer row choices only, so the fill-capped Pallas kernel
    (``kernels.support_margin.median_extremes_batched``) matches
    bit-for-bit.  "Fill-capped": the hot loop passes transcripts sliced to
    the live width, not the static capacity — any ``nW`` is valid under the
    label-0 padding convention.
    """
    pj = _dot(XW, v)                                     # (k, nW)
    i_p = jnp.argmax(jnp.where(yW == 1, pj, -jnp.inf), axis=1)
    i_q = jnp.argmin(jnp.where(yW == -1, pj, jnp.inf), axis=1)
    return i_p.astype(jnp.int32), i_q.astype(jnp.int32)


def _topr_ranks(key: jnp.ndarray, member: jnp.ndarray, r: int) -> jnp.ndarray:
    """Rank of the ``r`` smallest member entries under ascending (key, index)
    order; everything else gets the sentinel ``n``.

    Equivalent to a capped stable-argsort rank (exact ties resolve to the
    lowest index — ``argmin`` returns the first minimum), but costs r
    argmin+mask passes instead of a sort: r is a protocol constant (≤ 8
    shipped support points), so this is the cheap CPU spelling of the same
    integer decision the Pallas kernel computes via counting comparisons.
    """
    n = key.shape[0]
    idx = jnp.arange(n)
    k2 = jnp.where(member, key, jnp.inf)
    out = jnp.full((n,), n, jnp.int32)
    for t in range(r):
        i = jnp.argmin(k2)
        hit = (idx == i) & jnp.isfinite(k2[i])
        out = jnp.where(hit, t, out)
        k2 = jnp.where(hit, jnp.inf, k2)
    return out


def maxmarg_turn_ref(
    w: jnp.ndarray,                # (d,) refit separator weights
    b: jnp.ndarray,                # ()   refit separator offset
    K: jnp.ndarray,                # (N, d) coordinator's own ∪ transcript
    yK: jnp.ndarray,               # (N,) ±1 (0 = padding row)
    X: jnp.ndarray,                # (k, n, d) per-node shards
    y: jnp.ndarray,                # (k, n) ±1 (0 = padding row)
    *,
    rtol: float = 0.15,
    max_support: int = 4,
    viol_ship: int = 2,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One MAXMARG turn's fused margin scan (single instance; see the Pallas
    kernel ``kernels.support_margin.maxmarg_turn_scan_batched``).

    Returns integer decisions only, so the kernel matches bit-for-bit:

    * ``sup_rank`` (N,) i32 — stable (margin, index) rank of the
      ``max_support`` tightest fit-set rows *within the active-margin band*
      (functional margin ≤ (1+rtol)·min); every other row gets the sentinel
      N.  The caller's support selection is ``sup_rank < max_support`` and
      the ranks are the host loop's ship order.
    * ``err_k`` (k,) i32 — per-node error counts of the proposal (the
      all-clear bit is ``err_k == 0``, the ε-termination sum ``err_k.sum()``).
    * ``viol_rank`` (k, n) i32 — per-node stable margin rank of the
      ``viol_ship`` most-violated valid rows (sentinel n elsewhere): both
      the most-violated selection (``rank < viol_ship``) and the host
      loop's ``argsort(m)[:2]`` wire order.
    """
    valid_K = yK != 0
    mK = yK.astype(K.dtype) * (_dot(K, w) + b)
    mmin = jnp.maximum(jnp.min(jnp.where(valid_K, mK, jnp.inf)), 1e-12)
    band = valid_K & (mK <= mmin * (1.0 + rtol))
    sup_rank = _topr_ranks(mK, band, max_support)

    dec = _dot(X, w) + b                                 # (k, n)
    pred = jnp.where(dec > 0, 1, -1)
    valid = y != 0
    err_k = jnp.sum((pred != y) & valid, axis=1).astype(jnp.int32)
    m_all = y.astype(K.dtype) * dec
    viol_rank = jax.vmap(
        lambda key, mem: _topr_ranks(key, mem, viol_ship))(m_all, valid)
    return sup_rank, err_k, viol_rank


# Batched (sweep) oracles: the engine's CPU/interpret data-plane path and the
# parity reference for the batch-grid Pallas kernels.  V is shared across the
# batch; everything else carries a leading instance axis B.

threshold_ranges_batch_ref = jax.jit(
    jax.vmap(threshold_ranges_ref, in_axes=(None, 0, 0)))

uncertain_mask_batch_ref = jax.jit(
    jax.vmap(uncertain_mask_ref, in_axes=(None, 0, 0, 0, 0, 0)))

median_cut_scores_batch_ref = jax.jit(
    jax.vmap(median_cut_scores_ref, in_axes=(None, 0, 0, 0, 0, 0)))

median_extremes_batch_ref = jax.jit(
    jax.vmap(median_extremes_ref, in_axes=(0, 0, 0)))

@functools.partial(jax.jit, static_argnames=("rtol", "max_support",
                                             "viol_ship"))
def maxmarg_turn_batch_ref(w, b, K, yK, X, y, *, rtol: float = 0.15,
                           max_support: int = 4, viol_ship: int = 2):
    """Batched :func:`maxmarg_turn_ref` — the engine's CPU scan path and the
    bit-for-bit parity reference for the fused support/violation kernel."""
    return jax.vmap(functools.partial(
        maxmarg_turn_ref, rtol=rtol, max_support=max_support,
        viol_ship=viol_ship))(w, b, K, yK, X, y)


@functools.partial(jax.jit, static_argnames=("nsteps", "t0", "unroll"))
def pegasos_stage_batch_ref(
    X: jnp.ndarray,                # (B, N, d) f32; label-0 rows are padding
    y: jnp.ndarray,                # (B, N) f32 in {+1, -1, 0}
    nv: jnp.ndarray,               # (B,) f32 valid row counts
    w: jnp.ndarray,                # (B, d)
    b: jnp.ndarray,                # (B,)
    lam: jnp.ndarray,              # (B,)
    found: jnp.ndarray,            # (B,) bool first-0-error latch state
    w_best: jnp.ndarray,           # (B, d)
    b_best: jnp.ndarray,           # (B,)
    *,
    nsteps: int,
    t0: float = 0.0,
    unroll: int = 2,
):
    """One fused Pegasos λ stage + first-0-error latch: the jnp twin of
    ``kernels.pegasos.pegasos_stage_batched`` and the solver's CPU fast
    path (``_svm_solve_batch(kernel=True)`` off-TPU).

    Same op sequence as the kernel body — margins as d multiply-adds in
    coordinate order, the hinge gradient as one multiply and a point-axis
    sum per coordinate, normalized by ``nv``, L2-ball projection, and a
    trailing min-margin scan folded into the latch.  ``mmin`` uses the
    kernel mask constant ``pegasos.BIG`` (not inf) for instances with no
    valid rows.  ``unroll`` is the CPU tuning knob from the autotune cache;
    it never changes the math, only the fori_loop unrolling.
    """
    big = 1e30
    valid = y != 0.0

    def decide(wi, bi):
        acc = X[:, :, 0] * wi[:, None, 0]
        for j in range(1, X.shape[2]):
            acc = acc + X[:, :, j] * wi[:, None, j]
        return acc + bi[:, None]

    def step(i, carry):
        wi, bi = carry
        m = y * decide(wi, bi)
        vy = ((m < 1.0) & valid).astype(X.dtype) * y
        g = jnp.sum(vy[:, :, None] * X, axis=1)
        gb = -jnp.sum(vy, axis=1) / nv
        eta = 1.0 / (lam * (i.astype(X.dtype) + 2.0 + t0))
        w2 = wi - eta[:, None] * (lam[:, None] * wi - g / nv[:, None])
        b2 = bi - eta * gb
        nrm = jnp.sqrt(jnp.sum(w2 * w2, axis=1))
        scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / (nrm + 1e-12))
        return w2 * scale[:, None], b2 * scale

    w, b = jax.lax.fori_loop(0, nsteps, step, (w, b), unroll=unroll)
    m = y * decide(w, b)
    mmin = jnp.min(jnp.where(valid, m, big), axis=1)
    ok = mmin > 0.0
    take = ok & ~found
    return (w, b, mmin, found | ok,
            jnp.where(take[:, None], w, w_best),
            jnp.where(take, b, b_best))
