"""Pallas TPU kernel for the batched (B, m, n) median-cut scan.

The MEDIAN selector's per-turn hot loop (engine ``median.step`` part 2):
for every allowed cut angle θ_i, count the points whose whole at-risk arc
lies strictly on each side of the cut, and score the cut by the smaller
count — the discretized weighted-median hull edge of paper Alg. 2.  The
coordinator proposes ``argmax score``.

Formulation: with ``first[p]`` / ``last[p]`` the first and last direction
index at which point p is at risk,

  below[i] = #{p live : last[p] <= i}    (arc entirely ≤ cut i)
  above[i] = #{p live : first[p] > i}    (arc entirely > cut i)
  score[i] = dir_ok[i] ? min(below[i], above[i]) : -1

where ``live`` means the point is at risk somewhere and is not a label-0
padding row.  These are exactly the running-count conditions of the
reference (``c[i, p] == c[m-1, p]`` and ``c[i, p] == 0`` with ``c`` the
cumulative risk count down the direction axis), spelled as two
reductions and two compares that Mosaic lowers (it has no ``cumsum``).
All counts are integers, and the projections are the same d multiply-adds
as the reference and the engine's inline scan, so the kernel matches
``kernels.ref.median_cut_scores_ref`` bit-for-bit.

Layout: directions on sublanes, points on lanes.  ``V`` is (m, d), the
shards come transposed as (B, d, n) with labels (B, 1, n), and the
per-instance direction masks/bounds are (B, m, 1) columns, so every block
has whole trailing dims or (8, 128)-aligned ones.  Grid ``(B,
n_blocks)``: the whole direction axis lives in one block (a point's arc
spans all m rows), points stream through VMEM in ``block_n`` tiles, and the
two (m, 1) count accumulators live in VMEM scratch across the n sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def project(V: jnp.ndarray, XT: jnp.ndarray) -> jnp.ndarray:
    """(m, d) directions × (d, n) transposed points -> (m, n) projections as
    d multiply-adds in coordinate order: exact f32 on every backend (no MXU
    pass at reduced precision), and the same arithmetic as the reference
    and the engine's inline scans."""
    acc = V[:, 0:1] * XT[0:1, :]
    for j in range(1, V.shape[1]):
        acc = acc + V[:, j:j + 1] * XT[j:j + 1, :]
    return acc


def _median_cut_kernel(v_ref, ok_ref, lo_ref, hi_ref, x_ref, y_ref, out_ref,
                       acc_below, acc_above, *, num_n_blocks: int):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        acc_below[...] = jnp.zeros_like(acc_below)
        acc_above[...] = jnp.zeros_like(acc_above)

    y = y_ref[...]                               # (1, bn) ±1, 0 = padding
    ok = ok_ref[...]                             # (m, 1) 1.0/0.0
    lo = lo_ref[...]                             # (m, 1) — ±inf sentinels OK
    hi = hi_ref[...]
    proj = project(v_ref[...], x_ref[...])       # (m, bn)
    nonempty = (lo < hi) & (ok != 0.0)
    # folding the row mask into the bounds (±inf ⇒ comparison always false)
    # keeps the risk pipeline to one fused select pass, as in the engine
    lo_r = jnp.where(nonempty, lo, jnp.inf)
    hi_r = jnp.where(nonempty, hi, -jnp.inf)
    # a select between two boolean planes has no Mosaic lowering; spell
    # it as and/or
    pos = y == 1.0
    risk = (pos & (proj > lo_r)) | (~pos & (proj < hi_r))
    m = risk.shape[0]
    row = lax.broadcasted_iota(jnp.int32, risk.shape, 0)
    last = jnp.max(jnp.where(risk, row, -1), axis=0, keepdims=True)
    first = jnp.min(jnp.where(risk, row, m), axis=0, keepdims=True)
    live = (last >= 0) & (y != 0.0)                      # (1, bn)
    acc_below[...] += jnp.sum((live & (last <= row)).astype(jnp.int32),
                              axis=1, keepdims=True)
    acc_above[...] += jnp.sum((live & (first > row)).astype(jnp.int32),
                              axis=1, keepdims=True)

    @pl.when(ni == num_n_blocks - 1)
    def _emit():
        out_ref[...] = jnp.where(
            ok_ref[...] != 0.0,
            jnp.minimum(acc_below[...], acc_above[...]), -1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def median_cut_scores_batched(
    V: jnp.ndarray,                # (m, d) directions, shared over the batch
    dir_ok: jnp.ndarray,           # (B, m, 1) float 1.0/0.0 — per instance
    lo: jnp.ndarray,               # (B, m, 1) consistent-threshold lows
    hi: jnp.ndarray,               # (B, m, 1) consistent-threshold highs
    XT: jnp.ndarray,               # (B, d, n) shard points, transposed
    y: jnp.ndarray,                # (B, 1, n) ±1 (0 = padding row)
    *,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Median-cut scores for a whole sweep batch in one pallas_call; returns
    (B, m, 1) int32 (the caller argmaxes).  ``n`` must tile evenly by
    ``block_n`` (the ops.py wrapper pads); the full m axis is one block."""
    m, d = V.shape
    B, n = XT.shape[0], XT.shape[2]
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    nn = n // block_n

    col = pl.BlockSpec((None, m, 1), lambda b, j: (b, 0, 0))
    kernel = functools.partial(_median_cut_kernel, num_n_blocks=nn)
    return pl.pallas_call(
        kernel,
        grid=(B, nn),
        in_specs=[
            pl.BlockSpec((m, d), lambda b, j: (0, 0)),
            col, col, col,
            pl.BlockSpec((None, d, block_n), lambda b, j: (b, 0, j)),
            pl.BlockSpec((None, 1, block_n), lambda b, j: (b, 0, j)),
        ],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((B, m, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((m, 1), jnp.int32),
                        pltpu.VMEM((m, 1), jnp.int32)],
        interpret=interpret,
    )(V, dir_ok, lo, hi, XT, y)
