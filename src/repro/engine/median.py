"""One MEDIAN/k-party turn as a pure jitted ``step(state) -> state``.

Faithful vectorization of the certified-pivot epoch protocol that used to
live as a host-side Python loop in ``repro.core.protocols.kparty`` (paper
§5/§6.2, certified-pivot variant per DESIGN.md).  A whole batch of B
instances advances in lock-step under ``lax.while_loop``; finished instances
are masked no-ops until every instance terminates or the turn budget runs
out.  The single-instance public API is exactly this engine with B=1, so
batched-vs-sequential parity is structural, not approximate.

Turn structure (coordinator ci = turn % k, per-instance — the turn counter
is a (B,) leaf, so a dispatch may mix sessions at different phases; a plain
sweep keeps every row in lock-step):

1. coordinator ranges over its transcript → per-direction (lo, hi);
2. at-risk matrix over its own shard, full-scan weighted-median direction v;
3. broadcast its ≤2 band points S + (v, lo_c, hi_c) [k-1 point msgs + k-1
   4-scalar msgs]; S is appended to every node's transcript;
4. ε-early-exit: if the coordinator band is non-empty, every non-coordinator
   reports its error count on the band-midpoint classifier [k-1 1-scalar
   msgs]; terminate if the global count is within budget;
5. every node's extreme band points along v over own ∪ transcript;
   non-coordinators ship theirs [≤2-point msgs, skipped when empty] — each
   reply lands in the sender's and the coordinator's transcripts;
6. non-empty global band → accept bits [k-1] and terminate at the midpoint;
   empty band → the violating pair (p*, q*) certifies v·(q*-p*) > 0 for
   every consistent direction: broadcast the pair [k-1 2-point msgs, all
   transcripts] and prune the direction arc (the current v is always
   discarded — certified by the empty band, and enforced explicitly so f32
   rounding can never stall the loop).

Hot path (DESIGN.md §shared hot loop): ``run_hot`` — the ``run_instances``
default — drives the same ``step`` from the host on the selector-generic
machinery in :mod:`repro.engine.hotloop`, capping every per-turn transcript
read at the live fill (``trans_width``) and dropping finished instances
from the dispatch.  MEDIAN transcripts are mostly empty early, and the
capped reads drop only label-0 mask identities, so the hot path is
*bit-exact* against the cold padded ``run_compiled`` model (kept as
``run_instances(compact=False)``; gated in tests/test_median_hot.py).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.engine import hotloop
from repro.engine.state import (
    EngineData,
    ProtocolInstance,
    ProtocolState,
    blank_protocol_state,
    pack_instances,
    transcript_capacity,
)

_INF = jnp.inf

# MEDIAN's per-turn append bound on any single transcript *before* the
# stage-5 extremes read: the broadcast S block (≤ 2 rows).  The hot loop's
# width compaction must cover the turn-start fill plus this slack, because
# the extremes scan reads the post-S transcripts.
WIDTH_SLACK = 2


def _proj_grid(V: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """(m, d) × (B, n, d) -> (B, m, n) direction projections.

    Spelled as a broadcast multiply-add: XLA:CPU lowers the K=d (=2) dot
    through a generic GEMM path that is ~5× slower than the fused
    elementwise form, and this is the engine's dominant per-turn tensor.
    """
    d = V.shape[1]
    return sum(V[None, :, i, None] * X[:, None, :, i] for i in range(d))


def _proj_dir(X: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """(B, ..., d) × (B, d) -> (B, ...): per-instance projections onto v."""
    d = X.shape[-1]
    vb = v.reshape(v.shape[0:1] + (1,) * (X.ndim - 2) + (d,))
    return sum(X[..., i] * vb[..., i] for i in range(d))


_gather_rows = hotloop.gather_rows           # (B, N, ...) × (B,) -> (B, ...)


def _gather_rows2(arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """arr (B, k, N, ...), idx (B, k) -> (B, k, ...)."""
    return jax.vmap(jax.vmap(lambda a, i: a[i]))(arr, idx)


def _append2(wx, wy, fill, lo_j, hi_j, pts, labs, do, V):
    """Append a ≤2-row block to each instance's transcript at its fill.

    ``pts`` (B, 2, d), ``labs`` (B, 2) with label-0 marking invalid rows
    (valid rows must be compacted to the front), ``do`` (B,) gating the
    append.  Writes always land at ≥ fill, so masked-out appends only touch
    label-0 scratch rows that the next valid append overwrites — the
    "rows ≥ fill are label-0" invariant holds by induction.

    The node's consistent-threshold ranges (lo_j, hi_j) over its transcript
    are running max/mins, so they update incrementally here — O(B·m·2) per
    append instead of an O(B·m·cap) rescan per turn; masked/label-0 rows
    contribute ∓inf, i.e. nothing.  ``repro.kernels``
    ``threshold_ranges_batched`` over the final buffer yields the identical
    values (tested).
    """
    labs = jnp.where(do[:, None], labs, 0).astype(jnp.int32)
    nvalid = jnp.sum(labs != 0, axis=1).astype(jnp.int32)

    pv = jnp.swapaxes(_proj_grid(V, pts), 1, 2)          # (B, 2, m)
    lo_j = jnp.maximum(lo_j, jnp.max(
        jnp.where((labs == 1)[:, :, None], pv, -_INF), axis=1))
    hi_j = jnp.minimum(hi_j, jnp.min(
        jnp.where((labs == -1)[:, :, None], pv, _INF), axis=1))

    def upd(w, wl, f, p, l):
        return (lax.dynamic_update_slice(w, p, (f, 0)),
                lax.dynamic_update_slice(wl, l, (f,)))

    wx, wy = jax.vmap(upd)(wx, wy, fill, pts.astype(wx.dtype), labs)
    return wx, wy, fill + nvalid, lo_j, hi_j


def _extremes(XW, yW, v):
    """Per-node extreme band points along v over own ∪ transcript.

    XW (B, k, N, d), yW (B, k, N), v (B, d) ->
    (has_p, lo_k, p_k, has_q, hi_k, q_k) with shapes (B,k)/(B,k)/(B,k,d).
    """
    pj = _proj_dir(XW, v)
    posm = yW == 1
    negm = yW == -1
    has_p = jnp.any(posm, axis=2)
    has_q = jnp.any(negm, axis=2)
    pj_pos = jnp.where(posm, pj, -_INF)
    pj_neg = jnp.where(negm, pj, _INF)
    i_p = jnp.argmax(pj_pos, axis=2)
    i_q = jnp.argmin(pj_neg, axis=2)
    lo_k = jnp.where(has_p, jnp.max(pj_pos, axis=2), -_INF)
    hi_k = jnp.where(has_q, jnp.min(pj_neg, axis=2), _INF)
    p_k = _gather_rows2(XW, i_p)
    q_k = _gather_rows2(XW, i_q)
    return has_p, lo_k, p_k, has_q, hi_k, q_k


def step(
    data: EngineData,
    V: jnp.ndarray,
    state: ProtocolState,
    *,
    k: int,
    first_turn: bool = False,
    cut_kernel: bool = False,
    extremes_kernel: bool = False,
    trans_width: Optional[int] = None,
) -> ProtocolState:
    """Advance every active instance by one protocol turn (pure, jittable,
    shape-stable — usable under jit/vmap/while_loop).

    ``trans_width`` (static) caps every per-turn transcript *read* — the
    coordinator band scan and the stage-5 extremes scan — at the first
    ``trans_width`` rows; appends still write the full-capacity buffers.
    Sound whenever it covers every active instance's live fill plus the
    ≤ ``WIDTH_SLACK`` rows the S broadcast appends before the stage-5
    extremes read (``run_hot`` guarantees this; ``None`` reads the full
    capacity).  Rows at or beyond the fill are label-0 and contribute only
    mask identities to the band/extremes max-min reductions, so the cap is
    *bit-exact*, not merely decision-exact.

    ``extremes_kernel`` (static; TPU default via ``run_instances``, like
    ``cut_kernel``) routes the stage-5 per-node extremes scan through the
    fused fill-capped Pallas kernel
    (:func:`repro.kernels.support_margin.median_extremes_batched`) instead
    of the inline reduction — integer row choices, bit-for-bit against its
    jnp reference; the same FMA-boundary tie caveat as ``cut_kernel``
    applies against the *inline* path.

    ``first_turn=True`` constant-folds the (B, m, n) median-cut scan: on the
    fresh state every direction is allowed and the transcript is empty, so
    every real point is at risk at every direction, every cut scores 0, and
    the first-max pick is provably index 0 — the same value the full scan
    computes (tested), at none of its cost.

    ``cut_kernel=True`` (static; the TPU default via ``run_instances``)
    routes the median-cut scan through the fused Pallas kernel
    (:mod:`repro.kernels.median_cut`) instead of the inline histogram
    pipeline — no (B, m, n) intermediate in HBM.  The kernel is bit-for-bit
    against its jnp reference (tested); against the *inline* path it can
    pick a different — equally allowed — cut at FMA boundary ties: both
    spell projections as the same multiply-adds, but a compiler may
    contract them into FMAs in one program and not the other (a shipped
    support point's own projection defines the band edge its strict ``>``
    risk test compares against).  Within a
    backend the path is fixed, so B=1-vs-batch parity is unaffected.  The
    inline path stays the CPU default: XLA:CPU fuses it well and
    interpret-mode Pallas inside a hot loop is pathologically slow.
    """
    B, m = state.dir_ok.shape
    ci = state.turn % k                                  # (B,) per-instance
    active = ~state.done
    comm = state.comm

    # -- 1. coordinator's consistent-threshold ranges over its transcript ---
    # maintained incrementally at append time (see _append2); identical to a
    # threshold_ranges rescan of the coordinator's buffer
    Wxc = _gather_rows(state.wx, ci)                     # (B, cap, d)
    Wyc = _gather_rows(state.wy, ci)                     # (B, cap)
    if trans_width is not None:                          # fill-capped read
        Wxc = Wxc[:, :trans_width]
        Wyc = Wyc[:, :trans_width]
    lo = _gather_rows(state.lo_w, ci)                    # (B, m)
    hi = _gather_rows(state.hi_w, ci)

    # -- 2. at-risk matrix + full-scan weighted-median direction ------------
    Xc = _gather_rows(data.X, ci)                        # (B, n, d)
    yc = _gather_rows(data.y, ci)                        # (B, n)
    if first_turn:
        v_idx = jnp.zeros((B,), jnp.int32)
    elif cut_kernel:
        from repro.engine import dataplane
        score = dataplane.median_cut(V, state.dir_ok, lo, hi, Xc, yc,
                                     use_pallas=True)
        v_idx = jnp.argmax(score, axis=1)                # (B,) first max
    else:
        projc = _proj_grid(V, Xc)                        # (B, m, n)
        nonempty = (lo < hi) & state.dir_ok              # (B, m)
        # folding the row mask into the bounds (±inf ⇒ comparison always
        # false) keeps the (B, m, n) risk pipeline to one fused select pass
        lo_r = jnp.where(nonempty, lo, _INF)
        hi_r = jnp.where(nonempty, hi, -_INF)
        risk = jnp.where((yc == 1)[:, None, :],
                         projc > lo_r[:, :, None], projc < hi_r[:, :, None])
        # For every allowed cut angle, count points whose whole risk arc
        # lies strictly on each side; maximize the smaller count (the
        # discretized weighted-median hull edge, full scan over all allowed
        # cuts).  A point's arc is entirely ≤ cut i iff its last risk row is
        # ≤ i, entirely > i iff its first risk row is > i — histograms of
        # first/last indices give every cut's counts without materializing
        # the (B, m, n) running cumsum.
        idx = jnp.arange(m)[None, :, None]
        last = jnp.max(jnp.where(risk, idx, -1), axis=1)     # (B, n)
        first = jnp.min(jnp.where(risk, idx, m), axis=1)     # (B, n)
        rows = jnp.arange(B)[:, None]
        livei = ((last >= 0) & (yc != 0)).astype(jnp.int32)  # pads excluded
        hist_last = (jnp.zeros((B, m), jnp.int32)
                     .at[rows, jnp.clip(last, 0, m - 1)].add(livei))
        hist_first = (jnp.zeros((B, m), jnp.int32)
                      .at[rows, jnp.clip(first, 0, m - 1)].add(livei))
        below = jnp.cumsum(hist_last, axis=1)                # (B, m)
        above = (jnp.sum(livei, axis=1)[:, None]
                 - jnp.cumsum(hist_first, axis=1))
        score = jnp.where(state.dir_ok, jnp.minimum(below, above), -1)
        v_idx = jnp.argmax(score, axis=1)                    # (B,) first max
    v = V[v_idx]                                         # (B, d)

    # -- 3. coordinator band + support points S -----------------------------
    XWc = jnp.concatenate([Xc, Wxc], axis=1)             # (B, n+cap, d)
    yWc = jnp.concatenate([yc, Wyc], axis=1)
    pjc = _proj_dir(XWc, v)
    posm = yWc == 1
    negm = yWc == -1
    has_p = jnp.any(posm, axis=1)
    has_q = jnp.any(negm, axis=1)
    pj_pos = jnp.where(posm, pjc, -_INF)
    pj_neg = jnp.where(negm, pjc, _INF)
    lo_c = jnp.where(has_p, jnp.max(pj_pos, axis=1), -_INF)
    hi_c = jnp.where(has_q, jnp.min(pj_neg, axis=1), _INF)
    p_pt = _gather_rows(XWc, jnp.argmax(pj_pos, axis=1))
    q_pt = _gather_rows(XWc, jnp.argmin(pj_neg, axis=1))
    nS = has_p.astype(jnp.int32) + has_q.astype(jnp.int32)
    # compacted 2-row block: positive extreme first when present
    S_pts = jnp.stack([jnp.where(has_p[:, None], p_pt, q_pt), q_pt], axis=1)
    S_lab = jnp.stack([jnp.where(has_p, 1, jnp.where(has_q, -1, 0)),
                       jnp.where(has_p & has_q, -1, 0)], axis=1)

    # comm: S broadcast + direction scalars (v, lo_c, hi_c) to k-1 peers
    comm = comm._replace(
        points=comm.points + jnp.where(active, nS * (k - 1), 0),
        scalars=comm.scalars + jnp.where(active, 4 * (k - 1), 0),
        messages=comm.messages + jnp.where(active, 2 * (k - 1), 0),
        rounds=comm.rounds + active.astype(jnp.int32),
    )

    # S lands in every transcript (the coordinator's own sent-ledger included)
    wx, wy, w_fill = state.wx, state.wy, state.w_fill
    lo_w, hi_w = state.lo_w, state.hi_w

    def append_node(j, pts, labs, do):
        nonlocal wx, wy, w_fill, lo_w, hi_w
        wxj, wyj, fj, loj, hij = _append2(
            wx[:, j], wy[:, j], w_fill[:, j], lo_w[:, j], hi_w[:, j],
            pts, labs, do, V)
        wx = wx.at[:, j].set(wxj)
        wy = wy.at[:, j].set(wyj)
        w_fill = w_fill.at[:, j].set(fj)
        lo_w = lo_w.at[:, j].set(loj)
        hi_w = hi_w.at[:, j].set(hij)

    for j in range(k):
        append_node(j, S_pts, S_lab, active)

    # -- 4. ε-early-exit on the coordinator band midpoint -------------------
    band_c = jnp.isfinite(lo_c) & jnp.isfinite(hi_c) & (lo_c < hi_c)
    t_c = 0.5 * (lo_c + hi_c)
    pja = _proj_dir(data.X, v)                           # (B, k, n)
    pred = jnp.where(pja < t_c[:, None, None], 1, -1)    # +1 iff v·x < t
    errs = jnp.sum((pred != data.y) & (data.y != 0), axis=(1, 2))
    term_eps = active & band_c & (errs <= data.budget)
    fire_err = active & band_c                           # error-report msgs
    comm = comm._replace(
        scalars=comm.scalars + jnp.where(fire_err, k - 1, 0),
        messages=comm.messages + jnp.where(fire_err, k - 1, 0),
    )

    # -- 5. per-node extremes along v (post-S transcripts, fill-capped) -----
    if trans_width is None:
        wx_r, wy_r = wx, wy
    else:
        wx_r = wx[:, :, :trans_width]
        wy_r = wy[:, :, :trans_width]
    XW = jnp.concatenate([data.X, wx_r], axis=2)         # (B, k, n+W, d)
    yW = jnp.concatenate([data.y, wy_r], axis=2)
    if extremes_kernel:
        from repro.engine import dataplane
        i_p, i_q = dataplane.median_extremes(v, XW, yW, use_pallas=True)
        has_pk = jnp.any(yW == 1, axis=2)
        has_qk = jnp.any(yW == -1, axis=2)
        p_k = _gather_rows2(XW, i_p)
        q_k = _gather_rows2(XW, i_q)
        lo_k = jnp.where(has_pk, _proj_dir(p_k, v), -_INF)
        hi_k = jnp.where(has_qk, _proj_dir(q_k, v), _INF)
    else:
        has_pk, lo_k, p_k, has_qk, hi_k, q_k = _extremes(XW, yW, v)
    lo_g = jnp.max(lo_k, axis=1)
    hi_g = jnp.min(hi_k, axis=1)
    best_p = _gather_rows(p_k, jnp.argmax(lo_k, axis=1))  # first max node
    best_q = _gather_rows(q_k, jnp.argmin(hi_k, axis=1))

    node_ids = jnp.arange(k)[None, :]
    n_pts_k = has_pk.astype(jnp.int32) + has_qk.astype(jnp.int32)
    reply = ((active & ~term_eps)[:, None] & (node_ids != ci[:, None])
             & (n_pts_k > 0))
    comm = comm._replace(
        points=comm.points + jnp.sum(jnp.where(reply, n_pts_k, 0), axis=1),
        messages=comm.messages + jnp.sum(reply, axis=1, dtype=jnp.int32),
    )
    # node i's reply lands in its own sent-ledger and the coordinator's recv
    for i in range(k):
        E_pts = jnp.stack([jnp.where(has_pk[:, i, None], p_k[:, i], q_k[:, i]),
                           q_k[:, i]], axis=1)
        E_lab = jnp.stack(
            [jnp.where(has_pk[:, i], 1, jnp.where(has_qk[:, i], -1, 0)),
             jnp.where(has_pk[:, i] & has_qk[:, i], -1, 0)], axis=1)
        src_active = active & ~term_eps & (i != ci)
        for j in range(k):
            append_node(j, E_pts, E_lab, src_active & ((j == ci) | (j == i)))

    # -- 6. non-empty global band: terminate; empty: certified pivot --------
    band_g = lo_g < hi_g
    lo_g2 = jnp.where(jnp.isfinite(lo_g), lo_g, hi_g - 2.0)
    hi_g2 = jnp.where(jnp.isfinite(hi_g), hi_g, lo_g2 + 2.0)
    t_star = 0.5 * (lo_g2 + hi_g2)
    fire_band = active & ~term_eps & band_g
    comm = comm._replace(
        bits=comm.bits + jnp.where(fire_band, k - 1, 0),
        messages=comm.messages + jnp.where(fire_band, k - 1, 0),
    )

    fire_pivot = active & ~term_eps & ~band_g
    diff = best_q - best_p
    constraint = sum(V[None, :, i] * diff[:, i, None]
                     for i in range(V.shape[1]))         # (B, m)
    new_ok = state.dir_ok & (constraint > 1e-12)
    # the empty band certifies v itself is inconsistent; prune it explicitly
    # so f32 rounding of v·(q*-p*) ≈ 0 can never keep re-proposing v
    new_ok = new_ok & (jnp.arange(m)[None, :] != v_idx[:, None])
    apply_prune = (fire_pivot & jnp.any(new_ok, axis=1))[:, None]
    dir_ok = jnp.where(apply_prune, new_ok, state.dir_ok)
    comm = comm._replace(
        points=comm.points + jnp.where(fire_pivot, 2 * (k - 1), 0),
        messages=comm.messages + jnp.where(fire_pivot, k - 1, 0),
    )
    P_pts = jnp.stack([best_p, best_q], axis=1)
    P_lab = jnp.where(fire_pivot[:, None],
                      jnp.asarray([1, -1], jnp.int32)[None, :], 0)
    for j in range(k):
        append_node(j, P_pts, P_lab, fire_pivot)

    # -- hypothesis bookkeeping (precedence: band > ε-exit cand > fallback) -
    set_cand = active & band_c
    t_fb = jnp.where(jnp.isfinite(lo_c) & jnp.isfinite(hi_c), t_c, 0.0)
    set_fb = fire_pivot & ~state.h_valid & ~set_cand
    any_set = set_cand | fire_band | set_fb
    h_v = jnp.where(any_set[:, None], v, state.h_v)
    h_t = jnp.where(fire_band, t_star,
                    jnp.where(set_cand, t_c,
                              jnp.where(set_fb, t_fb, state.h_t)))
    h_valid = state.h_valid | any_set

    newly = term_eps | fire_band
    return ProtocolState(
        dir_ok=dir_ok,
        wx=wx, wy=wy, w_fill=w_fill, lo_w=lo_w, hi_w=hi_w,
        turn=state.turn + 1,
        done=state.done | newly,
        converged=state.converged | newly,
        epochs=jnp.where(newly, state.turn // k + 1, state.epochs),
        h_v=h_v, h_t=h_t, h_valid=h_valid,
        comm=comm,
    )


@functools.partial(jax.jit, static_argnames=("k", "max_turns", "cut_kernel",
                                             "extremes_kernel"))
def run_compiled(
    data: EngineData,
    V: jnp.ndarray,
    state0: ProtocolState,
    *,
    k: int,
    max_turns: int,
    cut_kernel: bool = False,
    extremes_kernel: bool = False,
) -> ProtocolState:
    """The whole sweep as one device computation: the constant-folded first
    turn, then while_loop over ``step`` until every instance terminates or
    the turn budget is exhausted.  Always reads transcripts at the full
    static capacity — the cold padded execution model, kept bit-exact as the
    hot path's differential reference (``run_instances(compact=False)``)."""

    def cond(s: ProtocolState):
        return (jnp.min(s.turn) < max_turns) & ~jnp.all(s.done)

    def body(s: ProtocolState):
        return step(data, V, s, k=k, cut_kernel=cut_kernel,
                    extremes_kernel=extremes_kernel)

    return lax.while_loop(cond, body,
                          step(data, V, state0, k=k, first_turn=True,
                               extremes_kernel=extremes_kernel))


_STEP_STATICS = ("k", "first_turn", "cut_kernel", "extremes_kernel",
                 "trans_width")


def _pad_fix(sub: ProtocolState, pad_row: jnp.ndarray) -> ProtocolState:
    """Mark gathered out-of-range rows inert.  done=True masks them out of
    every decision, comm update and append; their zero-filled leaves are
    harmless under the label-0 convention (no valid rows ⇒ every masked
    reduction hits its identity) and the scatter drops them anyway."""
    return sub._replace(done=sub.done | pad_row)


@jax.jit
def _host_view(state: ProtocolState, ci: jnp.ndarray) -> jnp.ndarray:
    """The hot loop's per-turn host knowledge as one (3, B) i32 transfer:
    done flags, a zero warm row (MEDIAN has no warm carry), and the max
    transcript fill across nodes — stage 5 scans *every* node's transcript,
    so the width compaction keys on the per-instance max, not the
    coordinator's fill alone."""
    return jnp.stack([state.done.astype(jnp.int32),
                      jnp.zeros_like(state.done, jnp.int32),
                      jnp.max(state.w_fill, axis=1)])


FAMILY = hotloop.Family(
    step=step, statics=_STEP_STATICS, pad_fix=_pad_fix,
    host_view=lambda state, ci, opts: _host_view(state, ci),
    width_slack=lambda opts: WIDTH_SLACK,
    # one turn's worst fill growth: the S block, the ≤2 reply rows from
    # each of k-1 peers, and the pivot pair
    width_growth=lambda opts: 2 * opts["k"] + 2,
    takes_grid=True,
    capacity=lambda cfg: transcript_capacity(cfg.k, cfg.max_epochs),
    pool_opts=lambda cfg, solver_kernel: dict(
        k=cfg.k, cut_kernel=False, extremes_kernel=False),
    fresh_state=lambda A, cfg, live, batch: blank_protocol_state(
        A, cfg.k, cfg.cap, cfg.n_angles, np.arange(A) >= live),
    sep=("h_v", "h_t"),
)


def run_hot(
    data: EngineData,
    V: jnp.ndarray,
    state: ProtocolState,
    *,
    k: int,
    max_turns: int,
    cut_kernel: bool = False,
    extremes_kernel: bool = False,
    compact: bool = True,
    mesh: Optional[jax.sharding.Mesh] = None,
    donate: Optional[bool] = None,
    overlap: Optional[bool] = None,
    stats: Optional[dict] = None,
) -> ProtocolState:
    """The MEDIAN sweep as a host-driven turn loop over the jitted ``step``
    (:func:`repro.engine.hotloop.run_family` over :data:`FAMILY`).

    MEDIAN transcripts are mostly empty early — every turn appends a handful
    of rows into buffers sized for the whole epoch budget — so the per-turn
    band and extremes scans run at ``round_up(max live fill + WIDTH_SLACK,
    8)`` rows instead of the static capacity, and finished instances drop
    out of the dispatch entirely.  Unlike MAXMARG's warm/compacted solver
    path, both compactions are **bit-exact** here: the capped reads drop
    only label-0 rows (mask identities of the max/min reductions) and every
    remaining op is per-row, so hot and cold agree float-for-float, not
    just decision-for-decision (tests/test_median_hot.py pins both).

    ``mesh`` shards every dispatch over the leading B axis, with
    ``donate``/``overlap`` on by default (``hotloop.run_family``); both
    stay bit-exact — MEDIAN is per-instance (no cross-shard collective)
    and any covering width is exact.
    """
    return hotloop.run_family(
        FAMILY, data, (V,), state,
        dict(k=k, cut_kernel=cut_kernel, extremes_kernel=extremes_kernel),
        max_turns=max_turns, compact=compact, mesh=mesh, donate=donate,
        overlap=overlap, stats=stats)


def run_instances(
    instances: Sequence[ProtocolInstance],
    *,
    eps: Optional[float] = None,
    n_angles: int = 1024,
    max_epochs: int = 48,
    cut_kernel: Optional[bool] = None,
    extremes_kernel: Optional[bool] = None,
    compact: bool = True,
    mesh: Optional[jax.sharding.Mesh] = None,
    donate: Optional[bool] = None,
    overlap: Optional[bool] = None,
    stats: Optional[dict] = None,
):
    """Run a batch of MEDIAN/k-party instances as one compiled sweep.

    Returns a list of :class:`~repro.core.protocols.one_way.ProtocolResult`,
    one per instance, shaped exactly like the per-instance path's (the
    per-instance path *is* this engine at B=1).

    ``compact=True`` (the default) runs the host-driven hot path
    (``run_hot``: fill-capped transcript reads + finished instances dropped
    from the dispatch); ``compact=False`` keeps the cold padded
    ``run_compiled`` — one while_loop dispatch at worst-case shapes, the
    bit-exact pre-hot-path execution model and the differential reference.
    ``cut_kernel``/``extremes_kernel`` route the per-turn scans through
    their Pallas kernels (default: on TPU only).  ``mesh`` shards the hot
    path over a 1-D ("data",) device mesh (requires ``compact=True``);
    ``donate``/``overlap`` opt the per-turn dispatches into buffer donation
    and the double-buffered host loop (mesh default: both on).  ``stats``
    (a dict) collects host-side observability — on sharded sweeps the
    per-dispatch shard skew (``hotloop.shard_skew``) — and is never read
    for decisions.

    Compile-key contract: ``n_angles``, ``max_epochs``, ``k``, ``d``, the
    kernel toggles, and the mesh topology are static — changing any of
    them compiles a new ``step``.  Shard contents, eps, seeds, and B are
    traced data; the hot path additionally re-keys only on the quantized
    ``(n_pad, width, warm)`` buckets ``hotloop.KEY_LOG`` records, so
    sweeps of any size reuse a handful of compilations.
    """
    from repro.core import classifiers as clf
    from repro.core import geometry as geo
    from repro.core.protocols.one_way import ProtocolResult

    if mesh is not None and not compact:
        raise ValueError("sharded sweeps require the compacted hot path")
    if eps is not None:
        instances = [ProtocolInstance(inst.shards, eps) for inst in instances]
    if cut_kernel is None or extremes_kernel is None:
        from repro.kernels import ops
        tpu = ops.on_tpu()
        cut_kernel = tpu if cut_kernel is None else cut_kernel
        extremes_kernel = tpu if extremes_kernel is None else extremes_kernel
    data, state0, k, _cap = pack_instances(
        instances, n_angles=n_angles, max_epochs=max_epochs, mesh=mesh)
    V = jnp.asarray(geo.direction_grid(n_angles), jnp.float32)
    if compact:
        final = run_hot(data, V, state0, k=k, max_turns=k * max_epochs,
                        cut_kernel=cut_kernel,
                        extremes_kernel=extremes_kernel,
                        mesh=mesh, donate=donate, overlap=overlap,
                        stats=stats)
    else:
        final = run_compiled(data, V, state0, k=k, max_turns=k * max_epochs,
                             cut_kernel=cut_kernel,
                             extremes_kernel=extremes_kernel)

    converged = np.asarray(final.converged)
    epochs = np.asarray(final.epochs)
    h_v = np.asarray(final.h_v, np.float64)
    h_t = np.asarray(final.h_t, np.float64)
    # one host transfer per counter array, not one per instance×field
    comm_np = type(final.comm)(*(np.asarray(a) for a in final.comm))
    extra = {"engine": True, "batch": len(instances),
             "selector": "median", "compact": compact}
    if mesh is not None:
        extra["devices"] = int(mesh.shape["data"])
    results: List[ProtocolResult] = []
    for b in range(len(instances)):
        h = clf.LinearSeparator(-h_v[b], float(h_t[b]))
        results.append(ProtocolResult(
            h,
            comm_np.summary(b, dim=2),
            rounds=int(epochs[b]) if converged[b] else max_epochs,
            converged=bool(converged[b]),
            extra=dict(extra),
        ))
    return results
