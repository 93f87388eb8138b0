"""Tiled Pallas kernel for the batched Pegasos λ-stage (the MAXMARG refit).

``core.classifiers._svm_solve_batch`` runs every hard-margin refit as plain
vmapped XLA Pegasos over ``(B, N, d)``: one ``fori_loop`` step per gradient
pass, with the d-contraction spelled as d broadcast multiply-adds.  This
kernel is the tiled deployment artifact for that loop:

* grid ``(B/block_b, nsteps+1, N/block_n)`` — instances in parallel blocks,
  the Pegasos step axis sequential, N-tiles innermost;
* points sit on the lane axis: the fit set comes transposed as (B, d, N)
  with labels (B, 1, N), and every per-instance vector is a (d, 1) or
  (1, 1) column of a (B, d, 1) / (B, 1, 1) operand, so each block's
  trailing two dims are whole or (8, 128)-aligned;
* each step's two contractions are spelled over the small d: margins as d
  multiply-adds in coordinate order (the arithmetic of the classic solver
  and of the ``ref.pegasos_stage_batch_ref`` twin), the hinge gradient as a
  multiply and a lane reduction per coordinate, accumulated across N-tiles
  in an f32 VMEM scratch (``g_s``/``gb_s``);
* the separator itself lives in VMEM scratch across the whole stage — one
  kernel launch covers a *whole λ stage* (nsteps updates + the trailing
  margin scan), not one ``fori_loop`` step per dispatch;
* the first-0-error latch of ``_svm_solve_batch`` is fused: the final grid
  step folds the stage's min-margin scan into the ``found``/``w_best``/
  ``b_best`` latch update, so the stage-annealing caller reads latched
  results straight out of the launch;
* masked-pad path: label-0 rows contribute no hinge violations and the
  gradient normalizes by the caller-supplied per-instance valid count
  ``nv`` — compacted hot-loop fills and tile padding ride the same mask.

Block shapes come from the committed tuning cache
(``kernels/tuning_cache.json`` via ``analysis.autotune.lookup_tile``); the
``ops.pegasos_stage`` wrapper pads/dispatches and falls back to the jnp
twin off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30  # mask constant for the min-margin scan of an all-padding block


def decide(XT: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(…, d, n) transposed points × (…, d, 1) separators + (…, 1, 1)
    offsets -> (…, 1, n) decision values, as d multiply-adds in coordinate
    order: exact f32 on every backend, and the arithmetic of the jnp twin
    ``ref.pegasos_stage_batch_ref``."""
    acc = XT[..., 0:1, :] * w[..., 0:1, :]
    for j in range(1, XT.shape[-2]):
        acc = acc + XT[..., j:j + 1, :] * w[..., j:j + 1, :]
    return acc + b


def _pegasos_stage_kernel(
    x_ref, y_ref, nv_ref, w0_ref, b0_ref, lam_ref, found_ref, wb_ref, bb_ref,
    w_out, b_out, mmin_out, found_out, wbest_out, bbest_out,
    w_s, b_s, g_s, gb_s, mm_s,
    *, nsteps: int, num_n_blocks: int, t0: float,
):
    """One λ stage for a ``block_b`` slab of instances.

    Grid ``(bi, s, ni)``: ``s < nsteps`` are Pegasos steps (N-tiles
    accumulate the hinge gradient, the last tile applies the update +
    ball projection), ``s == nsteps`` is the stage's min-margin scan whose
    last tile emits the latched outputs.  ``program_id`` values are only
    ever *compared* (`pl.when` step/tile selection), never used as
    addresses — block addressing is entirely BlockSpec-driven.
    """
    s = pl.program_id(1)
    ni = pl.program_id(2)

    @pl.when((s == 0) & (ni == 0))
    def _load():
        w_s[...] = w0_ref[...]
        b_s[...] = b0_ref[...]

    @pl.when(ni == 0)
    def _zero():
        g_s[...] = jnp.zeros_like(g_s)
        gb_s[...] = jnp.zeros_like(gb_s)
        mm_s[...] = jnp.full_like(mm_s, BIG)

    X = x_ref[...]                                       # (bb, d, bn)
    yv = y_ref[...]                                      # (bb, 1, bn)
    valid = yv != 0.0
    m = yv * decide(X, w_s[...], b_s[...])               # (bb, 1, bn)

    @pl.when(s < nsteps)
    def _grad():
        vy = ((m < 1.0) & valid).astype(jnp.float32) * yv
        g_s[...] += jnp.sum(vy * X, axis=2, keepdims=True)
        gb_s[...] += jnp.sum(vy, axis=2, keepdims=True)

    @pl.when(s == nsteps)
    def _margin():
        mm_s[...] = jnp.minimum(mm_s[...], jnp.min(
            jnp.where(valid, m, BIG), axis=2, keepdims=True))

    @pl.when((s < nsteps) & (ni == num_n_blocks - 1))
    def _update():
        lam = lam_ref[...]                               # (bb, 1, 1)
        nv = nv_ref[...]
        eta = 1.0 / (lam * (s.astype(jnp.float32) + 2.0 + t0))
        gw = lam * w_s[...] - g_s[...] / nv
        gb = -gb_s[...] / nv
        w2 = w_s[...] - eta * gw
        b2 = b_s[...] - eta * gb
        nrm = jnp.sqrt(jnp.sum(w2 * w2, axis=1, keepdims=True))
        scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / (nrm + 1e-12))
        w_s[...] = w2 * scale
        b_s[...] = b2 * scale

    @pl.when((s == nsteps) & (ni == num_n_blocks - 1))
    def _emit():
        mm = mm_s[...]
        ok = mm > 0.0                                    # BIG ⇒ no valid rows
        found_in = found_ref[...] != 0
        take = ok & ~found_in
        w_out[...] = w_s[...]
        b_out[...] = b_s[...]
        mmin_out[...] = mm
        found_out[...] = (found_in | ok).astype(jnp.int32)
        wbest_out[...] = jnp.where(take, w_s[...], wb_ref[...])
        bbest_out[...] = jnp.where(take, b_s[...], bb_ref[...])


@functools.partial(jax.jit, static_argnames=("nsteps", "t0", "block_b",
                                             "block_n", "interpret"))
def pegasos_stage_batched(
    XT: jnp.ndarray,               # (B, d, N) f32 fit sets, transposed
    y: jnp.ndarray,                # (B, 1, N) f32 in {+1, -1, 0}; 0 = padding
    nv: jnp.ndarray,               # (B, 1, 1) f32 — per-instance valid count
    w: jnp.ndarray,                # (B, d, 1) stage-entry separator
    b: jnp.ndarray,                # (B, 1, 1)
    lam: jnp.ndarray,              # (B, 1, 1) per-instance stage λ
    found: jnp.ndarray,            # (B, 1, 1) i32 — latch state in
    w_best: jnp.ndarray,           # (B, d, 1) latched separator in
    b_best: jnp.ndarray,           # (B, 1, 1)
    *,
    nsteps: int,
    t0: float = 0.0,
    block_b: int = 8,
    block_n: int = 512,
    interpret: bool = False,
):
    """One fused Pegasos λ stage + first-0-error latch as one pallas_call.

    Shapes must tile evenly (the ``ops.pegasos_stage`` wrapper pads).
    Returns ``(w, b, mmin, found, w_best, b_best)`` in the operand layouts;
    ``mmin`` uses the kernel mask constant ``BIG`` (not inf) for instances
    with no valid rows — callers that need the inf convention recompute
    margins themselves (``_svm_solve_batch`` does, for canonicalization
    only).
    """
    B, d, N = XT.shape
    block_b = min(block_b, B)
    block_n = min(block_n, N)
    assert B % block_b == 0 and N % block_n == 0, (B, block_b, N, block_n)
    nb, nn = B // block_b, N // block_n

    kernel = functools.partial(_pegasos_stage_kernel, nsteps=nsteps,
                               num_n_blocks=nn, t0=t0)
    one = pl.BlockSpec((block_b, 1, 1), lambda bi, s, ni: (bi, 0, 0))
    col = pl.BlockSpec((block_b, d, 1), lambda bi, s, ni: (bi, 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        kernel,
        grid=(nb, nsteps + 1, nn),
        in_specs=[
            pl.BlockSpec((block_b, d, block_n),
                         lambda bi, s, ni: (bi, 0, ni)),
            pl.BlockSpec((block_b, 1, block_n),
                         lambda bi, s, ni: (bi, 0, ni)),
            one, col, one, one, one, col, one,
        ],
        out_specs=[col, one, one, one, col, one],
        out_shape=[
            jax.ShapeDtypeStruct((B, d, 1), f32),
            jax.ShapeDtypeStruct((B, 1, 1), f32),
            jax.ShapeDtypeStruct((B, 1, 1), f32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, d, 1), f32),
            jax.ShapeDtypeStruct((B, 1, 1), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, d, 1), f32),            # w iterate
            pltpu.VMEM((block_b, 1, 1), f32),            # b iterate
            pltpu.VMEM((block_b, d, 1), f32),            # hinge-gradient acc
            pltpu.VMEM((block_b, 1, 1), f32),            # offset-gradient acc
            pltpu.VMEM((block_b, 1, 1), f32),            # running min margin
        ],
        interpret=interpret,
    )(XT, y, nv, w, b, lam, found, w_best, b_best)
