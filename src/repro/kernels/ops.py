"""Public jit'd wrappers around the Pallas kernels.

Each wrapper: (a) pads inputs to kernel tile boundaries, (b) compiles the
kernel with Mosaic on a TPU and dispatches to ``interpret=True`` elsewhere
(the kernel body then runs as a Python/XLA emulation, so the CPU tests
check what the chip computes), (c) restores the caller's shapes.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import mamba as _mamba
from repro.kernels import median_cut as _mc
from repro.kernels import pegasos as _pg
from repro.kernels import rwkv6 as _rwkv6
from repro.kernels import ref as _ref
from repro.kernels import support_margin as _sm
from repro.analysis import autotune as _autotune


def on_tpu() -> bool:
    """The one backend probe behind every kernel-or-jnp default: Pallas
    kernels run compiled on a TPU; elsewhere they interpret, and callers
    that default a kernel path on take their jnp path instead."""
    return jax.default_backend() == "tpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0.0) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def attention(
    q: jnp.ndarray,                # (B, Sq, H, hd)
    k: jnp.ndarray,                # (B, Skv, KV, hd)
    v: jnp.ndarray,                # (B, Skv, KV, hdv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention; pads Sq/Skv to block multiples (padding keys are
    masked out via ``kv_valid``)."""
    interpret = (not on_tpu()) if interpret is None else interpret
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Skv, 8))
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    if kp.shape[1] != Skv and kv_valid is None:
        kv_valid = Skv
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              kv_valid=kv_valid, block_q=bq, block_k=bk,
                              interpret=interpret)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------

def rwkv6(
    r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
    u: jnp.ndarray, *, chunk: int = 32, interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked WKV; pads S to the chunk multiple (w=1, k=0 padding steps are
    state no-ops)."""
    interpret = (not on_tpu()) if interpret is None else interpret
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        r = _pad_to(r, 1, chunk)
        k = _pad_to(k, 1, chunk)
        v = _pad_to(v, 1, chunk)
        w = _pad_to(w, 1, chunk, value=1.0)   # decay 1.0 ⇒ state unchanged
    y, sT = _rwkv6.rwkv6_chunked(r, k, v, w, u, chunk=chunk, interpret=interpret)
    return y[:, :S], sT


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------

def selective_scan(
    xc: jnp.ndarray, delta: jnp.ndarray, A: jnp.ndarray,
    Bs: jnp.ndarray, Cs: jnp.ndarray, *,
    chunk: int = 64, block_di: int = 256, interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Selective scan; pads S to the chunk multiple (Δ=0 steps are state
    no-ops) and d_inner to the block multiple."""
    interpret = (not on_tpu()) if interpret is None else interpret
    B, S, di = xc.shape
    chunk = min(chunk, S)
    block_di = min(block_di, di)
    xp = _pad_to(_pad_to(xc, 1, chunk), 2, block_di)
    dp = _pad_to(_pad_to(delta, 1, chunk), 2, block_di)
    Ap = _pad_to(A, 0, block_di)
    Bp = _pad_to(Bs, 1, chunk)
    Cp = _pad_to(Cs, 1, chunk)
    y, hT = _mamba.mamba_scan(xp, dp, Ap, Bp, Cp, chunk=chunk,
                              block_di=block_di, interpret=interpret)
    return y[:, :S, :di], hT[:, :di]


# ---------------------------------------------------------------------------
# support margin (paper data plane)
# ---------------------------------------------------------------------------
#
# The protocol kernels put points on the lane axis: shards are passed
# transposed as (…, d, n) with (…, 1, n) labels, and point axes are padded
# to the 128-lane tile (label-0 rows, inert under every masked reduction).
# d is never padded — the projections are d multiply-adds.

_LANES = 128
_SUBLANES = 8
# points per median-cut tile are capped so the (m, block_n) risk planes
# stay a few hundred KiB of VMEM at m = 1024 directions
_CUT_TILE_ELEMS = 1 << 17


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _tile(n: int, block: int, mult: int) -> int:
    """Block size for an axis of length ``n``: at most ``block`` (rounded
    up to the ``mult`` alignment), never more than ``n`` rounded up."""
    return _round_up(min(block, n), mult)


def _points_t(X: jnp.ndarray, y: jnp.ndarray, mult: int):
    """(…, n, d) points + (…, n) labels -> lane-padded (…, d, n') f32
    transposed points and (…, n') f32 labels."""
    XT = _pad_to(jnp.swapaxes(X.astype(jnp.float32), -1, -2), X.ndim - 1,
                 mult)
    return XT, _pad_to(y.astype(jnp.float32), y.ndim - 1, mult)


def _interp(interpret: Optional[bool]) -> bool:
    return (not on_tpu()) if interpret is None else interpret


def support_ranges(
    V: jnp.ndarray, Xw: jnp.ndarray, yw: jnp.ndarray, *,
    block_m: int = 256, block_n: int = 512, interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Consistent-threshold (lo, hi) per direction for one transcript: the
    batched kernel at B=1."""
    lo, hi = support_ranges_batch(V, Xw[None], yw[None], block_m=block_m,
                                  block_n=block_n, interpret=interpret)
    return lo[0], hi[0]


def support_uncertain(
    V: jnp.ndarray, dir_ok: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
    X: jnp.ndarray, y: jnp.ndarray, *,
    block_m: int = 256, block_n: int = 512, interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """SOU membership mask (bool, (n,)) for one shard: the batched kernel
    at B=1."""
    return support_uncertain_batch(
        V, dir_ok[None], lo[None], hi[None], X[None], y[None],
        block_m=block_m, block_n=block_n, interpret=interpret)[0]


def _direction_cols(m_pad: int, dir_ok, lo, hi):
    """(B, m) direction masks/bounds -> (B, m_pad, 1) f32 columns; padded
    directions are disallowed with an empty interval."""
    okp = _pad_to(dir_ok.astype(jnp.float32), 1, m_pad)[..., None]
    lop = _pad_to(lo.astype(jnp.float32), 1, m_pad)[..., None]
    hip = _pad_to(hi.astype(jnp.float32), 1, m_pad, value=-1.0)[..., None]
    return okp, lop, hip


def support_ranges_batch(
    V: jnp.ndarray, Xw: jnp.ndarray, yw: jnp.ndarray, *,
    block_m: int = 256, block_n: int = 512, interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched consistent-threshold ranges: V (m, d) shared, Xw (B, n, d),
    yw (B, n) with label-0 padding rows.  One pallas_call over the whole
    sweep; returns (B, m) lo/hi (∓``BIG`` for an absent class)."""
    m, n = V.shape[0], Xw.shape[1]
    bm = _tile(m, block_m, _SUBLANES)
    bn = _tile(n, block_n, _LANES)
    XT, yp = _points_t(Xw, yw, bn)
    lo, hi = _sm.threshold_ranges_batched(
        _pad_to(V.astype(jnp.float32), 0, bm), XT, yp[:, None, :],
        block_m=bm, block_n=bn, interpret=_interp(interpret))
    return lo[:, :m, 0], hi[:, :m, 0]


def support_uncertain_batch(
    V: jnp.ndarray, dir_ok: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
    X: jnp.ndarray, y: jnp.ndarray, *,
    block_m: int = 256, block_n: int = 512, interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Batched SOU membership: per-instance dir_ok/lo/hi (B, m) and shards
    X (B, n, d) / y (B, n); returns bool (B, n)."""
    m, n = V.shape[0], X.shape[1]
    bm = _tile(m, block_m, _SUBLANES)
    bn = _tile(n, block_n, _LANES)
    okp, lop, hip = _direction_cols(_round_up(m, bm), dir_ok, lo, hi)
    XT, yp = _points_t(X, y, bn)
    out = _sm.uncertain_mask_batched(
        _pad_to(V.astype(jnp.float32), 0, bm), okp, lop, hip, XT,
        yp[:, None, :], block_m=bm, block_n=bn, interpret=_interp(interpret))
    return out[:, 0, :n] > 0.5


def support_median_cut_batch(
    V: jnp.ndarray, dir_ok: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
    X: jnp.ndarray, y: jnp.ndarray, *,
    block_n: int = 512, interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Batched median-cut scores: per-instance dir_ok/lo/hi (B, m) and
    shards X (B, n, d) / y (B, n); returns int32 (B, m), -1 at disallowed
    cuts.  Pads m (dir_ok=0 ⇒ score -1, sliced off) and n (label-0 rows are
    never live)."""
    m, n = V.shape[0], X.shape[1]
    mp = _round_up(m, _SUBLANES)
    cap = max(_LANES, _CUT_TILE_ELEMS // mp // _LANES * _LANES)
    bn = _tile(n, min(block_n, cap), _LANES)
    okp, lop, hip = _direction_cols(mp, dir_ok, lo, hi)
    XT, yp = _points_t(X, y, bn)
    out = _mc.median_cut_scores_batched(
        _pad_to(V.astype(jnp.float32), 0, mp), okp, lop, hip, XT,
        yp[:, None, :], block_n=bn, interpret=_interp(interpret))
    return out[:, :m, 0]


def _node_cols(w: jnp.ndarray, k: int) -> jnp.ndarray:
    """(B, d) per-instance vectors -> (B, d, k, 1) f32, each coordinate
    repeated down the k node rows the kernel scales."""
    B, d = w.shape
    return jnp.broadcast_to(w.astype(jnp.float32)[:, :, None, None],
                            (B, d, k, 1))


def support_violation_batch(
    w: jnp.ndarray, b: jnp.ndarray, K: jnp.ndarray, yK: jnp.ndarray,
    X: jnp.ndarray, y: jnp.ndarray, *,
    rtol: float = 0.15, max_support: int = 4, viol_ship: int = 2,
    interpret: Optional[bool] = None,
):
    """Fused MAXMARG turn scan (support band ranks + per-node error counts +
    most-violated ranks) for a whole sweep; pads N/n (label-0 rows are
    never band members, never valid, never miscounted) and restores the
    reference's rank sentinels (N for non-band fit rows, n for invalid shard
    rows) after slicing the padding off.  Returns
    ``(sup_rank (B, N) i32, err_k (B, k) i32, viol_rank (B, k, n) i32)`` —
    bit-for-bit ``ref.maxmarg_turn_batch_ref``."""
    N, k, n = K.shape[1], X.shape[1], X.shape[2]
    KT, yKp = _points_t(K, yK, _LANES)
    XT, yp = _points_t(X, y, _LANES)
    sup, err, viol = _sm.maxmarg_turn_scan_batched(
        _node_cols(w, k),
        b.astype(jnp.float32)[:, None, None],
        KT[:, :, None, :], yKp[:, None, :],
        jnp.swapaxes(XT, 1, 2), yp, rtol=rtol, max_support=max_support,
        viol_ship=viol_ship, interpret=_interp(interpret))
    # padded widths inflate the non-member sentinel; members rank < N (resp.
    # n), so a min against the true width restores the reference sentinel
    sup = jnp.minimum(sup[:, 0, :N], N)
    viol = jnp.minimum(viol[:, :, :n], n)
    return sup, err[..., 0], viol


def support_extremes_batch(
    v: jnp.ndarray, XW: jnp.ndarray, yW: jnp.ndarray, *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused MEDIAN extremes scan (per-node extreme band point indices along
    the proposed direction) for a whole sweep: v (B, d), XW (B, k, nW, d),
    yW (B, k, nW) with label-0 padding rows.  ``nW`` is *fill-capped* — the
    hot loop passes transcripts sliced to the live width, and this wrapper
    only re-pads to the lane tile (padding rows get label 0 and are never
    selected; a class with no members yields index 0, gated by the caller's
    presence flags).  Returns ``(i_p, i_q)`` each (B, k) i32, bit-for-bit
    ``ref.median_extremes_batch_ref``."""
    XT, yp = _points_t(XW, yW, _LANES)
    ip, iq = _sm.median_extremes_batched(
        _node_cols(v, XW.shape[1]), jnp.swapaxes(XT, 1, 2), yp,
        interpret=_interp(interpret))
    return ip[..., 0], iq[..., 0]


# ---------------------------------------------------------------------------
# tiled Pegasos solver stage (MAXMARG refit inner loop)
# ---------------------------------------------------------------------------

def pegasos_stage(
    X: jnp.ndarray,                # (B, N, d) f32; label-0 rows = padding
    y: jnp.ndarray,                # (B, N) f32 in {+1, -1, 0}
    nv: jnp.ndarray,               # (B,) f32 valid row counts (≥ 1)
    w: jnp.ndarray,                # (B, d)
    b: jnp.ndarray,                # (B,)
    lam: jnp.ndarray,              # (B,) per-instance stage λ
    found: jnp.ndarray,            # (B,) bool first-0-error latch state
    w_best: jnp.ndarray,           # (B, d)
    b_best: jnp.ndarray,           # (B,)
    *,
    nsteps: int,
    t0: float = 0.0,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    block_b: Optional[int] = None,
    block_n: Optional[int] = None,
    unroll: Optional[int] = None,
) -> Tuple[jnp.ndarray, ...]:
    """One fused Pegasos λ stage + first-0-error latch behind one call.

    The solver's single dispatch point (``_svm_solve_batch(kernel=True)``):
    Pallas tiled kernel on TPU (auto-interpret elsewhere, like every other
    wrapper here), its jnp twin (``ref.pegasos_stage_batch_ref``) when
    ``use_pallas`` resolves False.  Block shapes / unroll default from the
    committed autotune cache (``analysis.autotune.lookup_tile``) with its
    deterministic fallback.  Returns ``(w, b, mmin, found, w_best,
    b_best)``; ``mmin`` follows the kernel mask convention (``pegasos.BIG``
    where no valid rows).
    """
    B, N, d = X.shape
    use_pallas = on_tpu() if use_pallas is None else use_pallas
    if block_b is None or block_n is None or unroll is None:
        kind = jax.devices()[0].device_kind
        cfg = _autotune.lookup_tile(kind, B, N, d)
        block_b = cfg.block_b if block_b is None else block_b
        block_n = cfg.block_n if block_n is None else block_n
        unroll = cfg.unroll if unroll is None else unroll

    if not use_pallas:
        return _ref.pegasos_stage_batch_ref(
            X, y, nv, w, b, lam, found, w_best, b_best,
            nsteps=nsteps, t0=t0, unroll=unroll)

    bb = min(block_b, max(B, 1))
    bn = _tile(N, block_n, _LANES)
    f32 = jnp.float32

    def one(a, value=0.0):         # (B,) -> block-padded (B', 1, 1)
        return _pad_to(a.astype(f32), 0, bb, value=value)[:, None, None]

    def col(a):                    # (B, d) -> block-padded (B', d, 1)
        return _pad_to(a.astype(f32), 0, bb)[..., None]

    # pads are inert by construction: label-0 rows never violate, pad
    # instances get nv=1 / λ=1 and no valid rows
    XT, yp = _points_t(X, y, bn)
    w_o, b_o, mm_o, f_o, wb_o, bb_o = _pg.pegasos_stage_batched(
        _pad_to(XT, 0, bb), _pad_to(yp, 0, bb)[:, None, :],
        one(nv, 1.0), col(w), one(b), one(lam, 1.0),
        _pad_to(found.astype(jnp.int32), 0, bb)[:, None, None],
        col(w_best), one(b_best), nsteps=nsteps, t0=t0,
        block_b=bb, block_n=bn, interpret=_interp(interpret))
    return (w_o[:B, :, 0], b_o[:B, 0, 0], mm_o[:B, 0, 0],
            f_o[:B, 0, 0] != 0, wb_o[:B, :, 0], bb_o[:B, 0, 0])
