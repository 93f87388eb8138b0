"""Pallas TPU kernels for the paper's data-plane hot loop.

The IterativeSupports protocol (paper §4–5) spends its cycles in bulk scans
over a node's local shard of the shape "project n points onto m candidate
directions and reduce":

  1. ``threshold_ranges_batched``: per direction v, the consistent-threshold
     interval (lo, hi) = (max_{y=+1} v·x, min_{y=-1} v·x) over the protocol
     transcript — an (m, n) projection with a masked row max/min fused in,
     never materializing the (m, n) projection matrix in HBM.
  2. ``uncertain_mask_batched``: given (lo, hi, dir_ok) per direction,
     decide for every local point whether *some* consistent classifier can
     still misclassify it (SOU membership, paper §4.1) — the same shape
     with an any-reduce over directions.

plus the two fused per-turn scans of the engine's selectors
(``median_extremes_batched`` for MEDIAN, ``maxmarg_turn_scan_batched`` for
MAXMARG).

Every projection is spelled as d multiply-adds in coordinate order
(``median_cut.project``), never as an MXU contraction: the protocol
dimensions are small (d = 2..10 in the paper), the result is exact f32 on
the TPU as on the CPU, and it is the same arithmetic the ``ref.py`` oracles
and the engine's inline scans use, so the integer decisions match
bit-for-bit.  Points always sit on the lane axis: shards come transposed as
(…, d, n) with labels (…, 1, n), and per-direction or per-node vectors are
(…, m, 1) / (…, k, 1) columns, so every block's trailing two dims are whole
or (8, 128)-aligned.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.median_cut import project

BIG = 1e30


def _ranges_kernel(v_ref, x_ref, y_ref, lo_ref, hi_ref, acc_lo, acc_hi, *,
                   num_n_blocks: int):
    """Grid (B, nm, nn); the batch axis indexes independent protocol
    instances (each with its own transcript) while V is shared."""
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        acc_lo[...] = jnp.full_like(acc_lo, -BIG)
        acc_hi[...] = jnp.full_like(acc_hi, BIG)

    y = y_ref[...]                               # (1, bn) ±1, 0 = padding
    proj = project(v_ref[...], x_ref[...])       # (bm, bn)
    acc_lo[...] = jnp.maximum(acc_lo[...], jnp.max(
        jnp.where(y == 1.0, proj, -BIG), axis=1, keepdims=True))
    acc_hi[...] = jnp.minimum(acc_hi[...], jnp.min(
        jnp.where(y == -1.0, proj, BIG), axis=1, keepdims=True))

    @pl.when(ni == num_n_blocks - 1)
    def _emit():
        lo_ref[...] = acc_lo[...]
        hi_ref[...] = acc_hi[...]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def threshold_ranges_batched(
    V: jnp.ndarray,                # (m, d) directions, shared over the batch
    XT: jnp.ndarray,               # (B, d, n) transcript points, transposed
    y: jnp.ndarray,                # (B, 1, n) ±1 (0 = padding row)
    *,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(lo, hi) consistent-threshold ranges for a whole sweep batch in one
    pallas_call with a leading batch-grid dimension.  Returns (B, m, 1)
    each, ∓``BIG`` where a class is absent.  Shapes must tile evenly (the
    ops.py wrapper pads)."""
    m, d = V.shape
    B, n = XT.shape[0], XT.shape[2]
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    assert m % block_m == 0 and n % block_n == 0, (m, block_m, n, block_n)
    nm, nn = m // block_m, n // block_n

    col = pl.BlockSpec((None, block_m, 1), lambda b, i, j: (b, i, 0))
    kernel = functools.partial(_ranges_kernel, num_n_blocks=nn)
    lo, hi = pl.pallas_call(
        kernel,
        grid=(B, nm, nn),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda b, i, j: (i, 0)),
            pl.BlockSpec((None, d, block_n), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((None, 1, block_n), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((B, m, 1), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((block_m, 1), jnp.float32),
                        pltpu.VMEM((block_m, 1), jnp.float32)],
        interpret=interpret,
    )(V, XT, y)
    return lo, hi


def _uncertain_kernel(v_ref, ok_ref, lo_ref, hi_ref, x_ref, y_ref, out_ref,
                      acc_ref, *, num_m_blocks: int):
    """Grid (B, nn, nm); per-instance dir_ok/lo/hi masks, the direction
    blocks innermost so the per-point any-reduce accumulates in VMEM."""
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    y = y_ref[...]                               # (1, bn)
    lo = lo_ref[...]                             # (bm, 1)
    hi = hi_ref[...]
    ok = ok_ref[...]                             # (bm, 1) 1.0/0.0
    proj = project(v_ref[...], x_ref[...])       # (bm, bn)
    nonempty = (lo < hi) & (ok != 0.0)
    pos = y == 1.0                               # and/or, not a boolean select
    at_risk = (pos & (proj > lo)) | (~pos & (proj < hi))
    hit = jnp.max(jnp.where(at_risk & nonempty, 1.0, 0.0), axis=0,
                  keepdims=True)                 # (1, bn)
    acc_ref[...] = jnp.maximum(acc_ref[...], hit)

    @pl.when(mi == num_m_blocks - 1)
    def _emit():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def uncertain_mask_batched(
    V: jnp.ndarray,                # (m, d), shared over the batch
    dir_ok: jnp.ndarray,           # (B, m, 1) float 1.0/0.0 — per instance
    lo: jnp.ndarray,               # (B, m, 1)
    hi: jnp.ndarray,               # (B, m, 1)
    XT: jnp.ndarray,               # (B, d, n) shard points, transposed
    y: jnp.ndarray,                # (B, 1, n) ±1 (0 = padding row)
    *,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """SOU membership for a whole sweep batch in one pallas_call; returns
    (B, 1, n) float 1.0/0.0 (caller thresholds)."""
    m, d = V.shape
    B, n = XT.shape[0], XT.shape[2]
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    assert m % block_m == 0 and n % block_n == 0, (m, block_m, n, block_n)
    nm, nn = m // block_m, n // block_n

    col = pl.BlockSpec((None, block_m, 1), lambda b, i, j: (b, j, 0))
    row = pl.BlockSpec((None, 1, block_n), lambda b, i, j: (b, 0, i))
    kernel = functools.partial(_uncertain_kernel, num_m_blocks=nm)
    return pl.pallas_call(
        kernel,
        grid=(B, nn, nm),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda b, i, j: (j, 0)),
            col, col, col,
            pl.BlockSpec((None, d, block_n), lambda b, i, j: (b, 0, i)),
            row,
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B, 1, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_n), jnp.float32)],
        interpret=interpret,
    )(V, dir_ok, lo, hi, XT, y)


def _project_rows(x_ref, w):
    """Σ_j w[j] · x_ref[j] over the leading coordinate axis of a (d, r, n)
    block, with ``w`` (d, r, 1): each per-instance scalar arrives already
    repeated down the r sublanes (Mosaic has no broadcast of a (1, 1)
    value over both sublanes and lanes), so each term is a lane broadcast.
    The same coordinate-order multiply-adds as ``project``."""
    acc = x_ref[0] * w[0]
    for j in range(1, w.shape[0]):
        acc = acc + x_ref[j] * w[j]
    return acc


def _first_index(hit: jnp.ndarray) -> jnp.ndarray:
    """First lane index where ``hit`` holds, per row ((r, n) -> (r, 1));
    ``n`` where it never does."""
    n = hit.shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, hit.shape, hit.ndim - 1)
    return jnp.min(jnp.where(hit, lane, n), axis=-1, keepdims=True)


def _topr_ranks(key: jnp.ndarray, member: jnp.ndarray, r: int) -> jnp.ndarray:
    """Per row, the rank of the ``r`` smallest member keys under ascending
    (key, index) order; every other lane gets the sentinel ``n``.

    The same r argmin-and-mask passes as ``ref._topr_ranks`` (the first
    minimum wins exact ties), so the integer ranks match bit-for-bit; the
    sentinel for non-members is ``BIG`` instead of inf, and real keys
    never reach it."""
    n = key.shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    k2 = jnp.where(member, key, BIG)
    out = jnp.full(key.shape, n, jnp.int32)
    for t in range(r):
        mn = jnp.min(k2, axis=-1, keepdims=True)
        i = _first_index(k2 == mn)
        hit = (lane == i) & (mn < BIG)
        out = jnp.where(hit, t, out)
        k2 = jnp.where(hit, BIG, k2)
    return out


def _maxmarg_turn_kernel(w_ref, b_ref, kx_ref, ky_ref, x_ref, y_ref,
                         sup_ref, err_ref, viol_ref, *, rtol: float,
                         max_support: int, viol_ship: int):
    """Fused MAXMARG turn scan for one instance (grid (B,)).

    Folds the three per-turn passes that followed each refit — the fit-set
    margin scan + band ranking (support selection), the per-node error
    counts (all-clear bits / ε-termination), and the per-node most-violated
    ranking — into one kernel, so the proposal (w, b) streams through VMEM
    once per turn instead of driving a multi-pass jnp chain through HBM.
    """
    w = w_ref[...]                                       # (d, k, 1)
    b = b_ref[...]                                       # (1, 1)

    yK = ky_ref[...]                                     # (1, N)
    mK = yK * (_project_rows(kx_ref, w[:, 0:1, :]) + b)  # fit-set margins
    valid_K = yK != 0.0
    mmin = jnp.maximum(jnp.min(jnp.where(valid_K, mK, BIG), axis=1,
                               keepdims=True), 1e-12)
    band = valid_K & (mK <= mmin * (1.0 + rtol))
    sup_ref[...] = _topr_ranks(mK, band, max_support)

    y = y_ref[...]                                       # (k, n)
    dec = _project_rows(x_ref, w) + b                    # (k, n)
    pred = jnp.where(dec > 0.0, 1.0, -1.0)
    valid = y != 0.0
    err_ref[...] = jnp.sum(((pred != y) & valid).astype(jnp.int32), axis=1,
                           keepdims=True)
    viol_ref[...] = _topr_ranks(y * dec, valid, viol_ship)


@functools.partial(jax.jit, static_argnames=("rtol", "max_support",
                                             "viol_ship", "interpret"))
def maxmarg_turn_scan_batched(
    w: jnp.ndarray,                # (B, d, k, 1) refit separators, each
                                   # coordinate repeated over the k nodes
    b: jnp.ndarray,                # (B, 1, 1)
    KT: jnp.ndarray,               # (B, d, 1, N) own ∪ transcript, transposed
    yK: jnp.ndarray,               # (B, 1, N) ±1 (0 = padding row)
    XT: jnp.ndarray,               # (B, d, k, n) per-node shards, transposed
    y: jnp.ndarray,                # (B, k, n) ±1 (0 = padding row)
    *,
    rtol: float = 0.15,
    max_support: int = 4,
    viol_ship: int = 2,
    interpret: bool = False,
):
    """Fused support/violation scan for a whole MAXMARG sweep in one
    pallas_call (grid (B,); each instance is one block — a fit set of a
    few thousand rows is tens of KiB per coordinate).  Returns
    ``(sup_rank (B, 1, N) i32, err_k (B, k, 1) i32, viol_rank (B, k, n)
    i32)`` matching ``ref.maxmarg_turn_batch_ref`` bit-for-bit (integer
    outputs only)."""
    B, d, _, N = KT.shape
    k, n = XT.shape[2], XT.shape[3]

    kernel = functools.partial(_maxmarg_turn_kernel, rtol=rtol,
                               max_support=max_support, viol_ship=viol_ship)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, d, k, 1), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, d, 1, N), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((None, 1, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, d, k, n), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((None, k, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, k, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, k, n), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, N), jnp.int32),
            jax.ShapeDtypeStruct((B, k, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, k, n), jnp.int32),
        ],
        interpret=interpret,
    )(w, b, KT, yK, XT, y)


def _median_extremes_kernel(v_ref, x_ref, y_ref, ip_ref, iq_ref):
    """MEDIAN's fused per-turn extremes scan for one instance (grid (B,)).

    One pass over every node's own ∪ transcript rows at the hot loop's
    fill-capped width: project onto the proposed direction and pick, per
    node, the first-index max over positive rows and first-index min over
    negative rows — the band extreme each node would ship.  First-index tie
    resolution is spelled as a counting min over an iota (``argmax`` picks
    the first maximum in the jnp reference), so the integer row choices
    match ``ref.median_extremes_ref`` bit-for-bit.
    """
    y = y_ref[...]                                       # (k, nW) ±1, 0 = pad
    pj = _project_rows(x_ref, v_ref[...])                # (k, nW)
    pj_pos = jnp.where(y == 1.0, pj, -BIG)
    pj_neg = jnp.where(y == -1.0, pj, BIG)
    # first index attaining the masked max/min; all-masked rows reduce to
    # the mask constant, whose first index is 0 — the same fallback the
    # reference's argmax-over-(-inf) yields
    ip_ref[...] = _first_index(pj_pos == jnp.max(pj_pos, axis=1,
                                                 keepdims=True))
    iq_ref[...] = _first_index(pj_neg == jnp.min(pj_neg, axis=1,
                                                 keepdims=True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def median_extremes_batched(
    v: jnp.ndarray,                # (B, d, k, 1) proposed directions, each
                                   # coordinate repeated over the k nodes
    XT: jnp.ndarray,               # (B, d, k, nW) own ∪ capped transcripts
    y: jnp.ndarray,                # (B, k, nW) ±1 (0 = padding row)
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused extremes scan for a whole MEDIAN sweep in one pallas_call
    (grid (B,)).  ``nW`` is whatever width the caller passes — the hot
    loop's live fill cap, not the static transcript capacity.  Returns
    ``(i_p (B, k, 1) i32, i_q (B, k, 1) i32)`` matching
    ``ref.median_extremes_batch_ref`` bit-for-bit (integer row choices
    only)."""
    B, d, k, nW = XT.shape
    out = pl.BlockSpec((None, k, 1), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _median_extremes_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, d, k, 1), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((None, d, k, nW), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((None, k, nW), lambda i: (i, 0, 0)),
        ],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((B, k, 1), jnp.int32)] * 2,
        interpret=interpret,
    )(v, XT, y)
