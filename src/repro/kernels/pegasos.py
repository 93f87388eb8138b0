"""Pallas kernels for the batched Pegasos λ-stage (the MAXMARG refit).

``core.classifiers._svm_solve_batch`` runs every hard-margin refit as plain
vmapped XLA Pegasos over ``(B, N, d)``: one ``fori_loop`` step per gradient
pass, with the d-contraction spelled as d broadcast multiply-adds.  On a TPU
``kernel=True`` runs each λ stage as one launch of
``pegasos_stage_batched``, which has two paths; ``ops.pegasos_stage``
chooses one from the shape alone:

* **resident** — when an 8-instance block's fit set, double-buffered,
  fits ``RESIDENT_VMEM_BUDGET`` (``analysis.autotune.vmem_bytes`` at
  ``block_b = 8``, ``block_n = N_pad``).  Grid ``(B/8,)``: the block's
  points and labels are DMA'd into VMEM once and stay there for all
  ``nsteps`` updates and the closing margin scan, which run as in-kernel
  loops.  Instances sit on sublanes — the fit set as (d, B, N), labels as
  (B, N) — so each coordinate is one full (8, N) slab; a step walks N in
  128-lane chunks, computing each chunk's margins and folding its hinge
  gradient into per-lane accumulators, which one lane reduction per step
  closes.  The separator is carried in registers.
* **streamed** — every other shape.  Grid ``(B/block_b, nsteps+1,
  N/block_n)``: instances in parallel blocks, the Pegasos step axis
  sequential, N-tiles innermost, so the fit set streams from HBM on every
  step.  Points sit on the lane axis — the fit set transposed as (B, d, N)
  with labels (B, 1, N), every per-instance vector a (d, 1) or (1, 1)
  column — and the hinge gradient is a multiply and a lane reduction per
  coordinate and N-tile, accumulated across N-tiles in f32 VMEM scratch
  (``g_s``/``gb_s``).  Block shapes come from the committed tuning cache
  (``kernels/tuning_cache.json`` via ``analysis.autotune.lookup_tile``).

Both paths share the arithmetic: f32 throughout, margins as d
multiply-adds in coordinate order (the arithmetic of the classic solver
and of the ``ref.pegasos_stage_batch_ref`` twin), the same η schedule,
ball projection and ``nv`` normalisation.  Only the association of the
N-sum differs between them.  Both fuse the first-0-error latch of
``_svm_solve_batch``: the stage's min-margin scan folds into the
``found``/``w_best``/``b_best`` latch update, so the stage-annealing
caller reads latched results straight out of the launch.  Label-0 rows
contribute no hinge violations and the gradient normalizes by the
caller-supplied per-instance valid count ``nv`` — compacted hot-loop
fills and tile padding ride the same mask.  The ``ops.pegasos_stage``
wrapper pads/dispatches and falls back to the jnp twin off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30  # mask constant for the min-margin scan of an all-padding block

LANES = 128       # the resident path walks the point axis in chunks this wide
SUBLANES = 8      # instances per resident block: one per sublane

#: VMEM (bytes) one resident block's working set may take — its points and
#: labels double-buffered, as ``analysis.autotune.vmem_bytes`` counts them.
#: The resident launch asks the compiler for this much scoped VMEM plus
#: ``_VMEM_HEADROOM`` for the per-instance vectors and its own scratch.
RESIDENT_VMEM_BUDGET = 24 << 20
_VMEM_HEADROOM = 8 << 20
# lane chunks per iteration of the resident path's walk: on a v5e the
# closed MAXMARG cell's stage takes 15.9 ms at 1, 11.2 at 2, 8.4 at 4 and 8
_CHUNK_UNROLL = 4


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


def _resident_stage_kernel(
    x_ref, y_ref, nv_ref, w0_ref, b0_ref, lam_ref, found_ref, wb_ref, bb_ref,
    w_out, b_out, mmin_out, found_out, wbest_out, bbest_out,
    *, nsteps: int, t0: float, unroll: int,
):
    """One λ stage for 8 instances whose fit set stays in VMEM throughout.

    ``x_ref`` is the block's (d, 8, N) points and ``y_ref`` its (8, N)
    labels; per-instance vectors are (d, 8, 1) or (8, 1).  The separator
    is a loop carry; every pass over the points walks N in 128-lane chunks.
    """
    d, rows, n = x_ref.shape
    f32 = jnp.float32
    lam = lam_ref[...]                                   # (8, 1)
    nv = nv_ref[...]

    def walk(w, b, fold, acc):
        """Fold ``fold(x, yv, m, acc)`` over the chunks of the fit set; ``m``
        is a chunk's (8, 128) margins, the multiply-adds of ``decide``."""
        wl = [jnp.broadcast_to(w[j], (rows, LANES)) for j in range(d)]
        bl = jnp.broadcast_to(b, (rows, LANES))

        def chunk(c, acc):
            at = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
            x = x_ref[:, :, at]                          # (d, 8, 128)
            yv = y_ref[:, at]                            # (8, 128)
            m = x[0] * wl[0]
            for j in range(1, d):
                m = m + x[j] * wl[j]
            return fold(x, yv, yv * (m + bl), acc)

        def group(i, acc):         # Mosaic loops unroll fully or not at all
            for k in range(unroll):
                acc = chunk(i * unroll + k, acc)
            return acc

        chunks = n // LANES
        acc = jax.lax.fori_loop(0, chunks // unroll, group, acc)
        for c in range(chunks - chunks % unroll, chunks):
            acc = chunk(c, acc)
        return acc

    def grad(x, yv, m, acc):
        g, gb = acc
        # ((m < 1) & valid) * y: label-0 rows give 0 either way
        vy = jnp.where(m < 1.0, yv, 0.0)
        return g + vy[None] * x, gb + vy

    def step(s, carry):
        w, b = carry
        g, gb = walk(w, b, grad, (jnp.zeros((d, rows, LANES), f32),
                                  jnp.zeros((rows, LANES), f32)))
        g = jnp.sum(g, axis=2, keepdims=True)            # (d, 8, 1)
        gb = -jnp.sum(gb, axis=1, keepdims=True) / nv    # (8, 1)
        eta = 1.0 / (lam * (s.astype(f32) + 2.0 + t0))
        gw = lam * w - g / nv
        w2 = w - eta * gw
        b2 = b - eta * gb
        nrm = jnp.sqrt(jnp.sum(w2 * w2, axis=0))
        scale = jnp.minimum(1.0, (1.0 / jnp.sqrt(lam)) / (nrm + 1e-12))
        return w2 * scale, b2 * scale

    w, b = jax.lax.fori_loop(0, nsteps, step, (w0_ref[...], b0_ref[...]))
    mm = walk(w, b, lambda x, yv, m, acc: jnp.minimum(
        acc, jnp.where(yv != 0.0, m, BIG)), jnp.full((rows, LANES), BIG, f32))
    mm = jnp.min(mm, axis=1, keepdims=True)              # (8, 1)
    ok = mm > 0.0                                        # BIG ⇒ no valid rows
    found_in = found_ref[...] != 0
    take = ok & ~found_in
    w_out[...] = w
    b_out[...] = b
    mmin_out[...] = mm
    found_out[...] = (found_in | ok).astype(jnp.int32)
    wbest_out[...] = jnp.where(take[None], w, wb_ref[...])
    bbest_out[...] = jnp.where(take, b, bb_ref[...])


def _resident_stage(XT, y, nv, w, b, lam, found, w_best, b_best, *,
                    nsteps: int, t0: float, interpret: bool):
    """The resident path of ``pegasos_stage_batched``: relays the operands
    with instances on sublanes, launches grid ``(B/8,)``, and returns the
    outputs in the streamed path's layouts."""
    B, d, N = XT.shape
    assert B % SUBLANES == 0 and N % LANES == 0, (B, N)
    f32 = jnp.float32

    def col(a):                    # (B, d, 1) <-> (d, B, 1)
        return jnp.transpose(a, (1, 0, 2))

    kernel = functools.partial(_resident_stage_kernel, nsteps=nsteps, t0=t0,
                               unroll=_CHUNK_UNROLL)
    one = pl.BlockSpec((SUBLANES, 1), lambda i: (i, 0))
    vec = pl.BlockSpec((d, SUBLANES, 1), lambda i: (0, i, 0))
    w_o, b_o, mm_o, f_o, wb_o, bb_o = pl.pallas_call(
        kernel,
        grid=(B // SUBLANES,),
        in_specs=[
            pl.BlockSpec((d, SUBLANES, N), lambda i: (0, i, 0)),
            pl.BlockSpec((SUBLANES, N), lambda i: (i, 0)),
            one, vec, one, one, one, vec, one,
        ],
        out_specs=[vec, one, one, one, vec, one],
        out_shape=[
            jax.ShapeDtypeStruct((d, B, 1), f32),
            jax.ShapeDtypeStruct((B, 1), f32),
            jax.ShapeDtypeStruct((B, 1), f32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((d, B, 1), f32),
            jax.ShapeDtypeStruct((B, 1), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=RESIDENT_VMEM_BUDGET + _VMEM_HEADROOM),
        interpret=interpret,
    )(col(XT), y[:, 0, :], nv[:, :, 0], col(w), b[:, :, 0], lam[:, :, 0],
      found[:, :, 0], col(w_best), b_best[:, :, 0])
    return (col(w_o), b_o[..., None], mm_o[..., None], f_o[..., None],
            col(wb_o), bb_o[..., None])


@functools.partial(jax.jit, static_argnames=("nsteps", "t0", "block_b",
                                             "block_n", "resident",
                                             "interpret"))
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
    resident: bool = False,
    interpret: bool = False,
):
    """One fused Pegasos λ stage + first-0-error latch as one pallas_call.

    ``resident`` takes the resident path (B a multiple of 8, N of 128;
    ``block_b``/``block_n`` unused), else the streamed grid, whose shapes
    must tile evenly (the ``ops.pegasos_stage`` wrapper pads).
    Returns ``(w, b, mmin, found, w_best, b_best)`` in the operand layouts;
    ``mmin`` uses the kernel mask constant ``BIG`` (not inf) for instances
    with no valid rows — callers that need the inf convention recompute
    margins themselves (``_svm_solve_batch`` does, for canonicalization
    only).
    """
    if resident:
        return _resident_stage(XT, y, nv, w, b, lam, found, w_best, b_best,
                               nsteps=nsteps, t0=t0, interpret=interpret)
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
