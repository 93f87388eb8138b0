"""One MAXMARG k-party turn as a pure jitted ``step(state) -> state``.

Faithful vectorization of the per-round-SVM-refit protocol (paper §4.4
two-way MAXMARG and its §7 k-party generalization) that used to live as a
host-side Python loop in ``repro.core.protocols.kparty``.  Each turn the
coordinator ``ci = turn % k`` refits a max-margin separator on everything it
knows — own shard ∪ received transcript — via the batched annealed Pegasos
solver (``repro.core.classifiers._svm_solve_batch``), so a whole sweep of B
hard-margin refits is one device computation per turn and the whole sweep is
one ``lax.while_loop`` dispatch.

Turn structure (mirrors the retired host loop, kept as the differential
oracle in ``benchmarks/legacy_maxmarg.py``):

1. coordinator fits max-margin on own ∪ transcript (the B-batched fit);
2. active-margin support points (functional margin within (1+rtol) of the
   minimum, the ``max_support`` smallest by (margin, index)) are broadcast
   to the k-1 others [k-1 point msgs] and land in their transcripts;
3. every node counts the proposal's errors on its own shard; non-coordinators
   report an all-clear bit [k-1 bit msgs];
4. every violated non-coordinator ships its 2 most-violated points to the
   coordinator [≤2-point msgs, only when violated] — the paper's
   support-vector exchange;
5. terminate when the global error count is within the ε budget.

Padding follows the engine conventions (DESIGN.md): label-0 rows are inert
in the fit (no hinge contribution, gradient normalized by the valid count)
and in every masked selection; transcripts are received-points-only, matching
the host loop's ``Node.recv``.

Hot path (DESIGN.md §warm-start & transcript compaction, §shared hot loop):
``run_hot`` drives the same ``step`` from the host one turn at a time — on
the selector-generic machinery in :mod:`repro.engine.hotloop` — so it can
(a) warm-start every refit from a carried separator, (b) slice the
coordinator's transcript gather down to the bucket's live width
(``w_fill``) instead of the worst-case capacity, and (c) drop finished
instances from the dispatch.  The warm carry is *per-node* by default
(``per_node=True``): each node carries the most recent proposal it verified
clean on everything it knows (zero errors on its shard + margin > 0 on its
transcript) and polishes from that when it next coordinates, threaded as
the ``(k,)``-leading leaves ``MaxMargState.c_w``/``c_b``/``c_valid`` with
the incremental clean-carry flags ``warm_node``.  In long k-party
multi-epoch sweeps a clean proposal adopted mid-epoch usually survives to
the node's own turn, where the single previous-*turn* carry (the
``per_node=False`` mode, kept for the differential latch tests) is only
ever checked against the immediately-next coordinator and rarely latches.
All layers are
decision-preserving — the hard-margin optimum is transcript-determined, so
warm/compacted and the cold padded ``run_compiled`` path agree on
comm/rounds/convergence on every tested grid (tests/test_maxmarg_warm.py
enforces it; ``run_instances(warm=False, compact=False)`` keeps the exact
legacy-oracle execution model).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core.classifiers import _svm_solve_batch
from repro.engine import hotloop
from repro.engine.state import (
    EngineData,
    MaxMargState,
    ProtocolInstance,
    blank_maxmarg_state,
    maxmarg_transcript_capacity,
    pack_instances_maxmarg,
)
from repro.kernels import ops, ref

RTOL = 0.15          # active-margin band width, = classifiers.support_points
VIOL_SHIP = 2        # most-violated points shipped per violated node

# (B, N, ...) × (B,) -> (B, ...): coordinator-indexed gathers — ci is a
# per-instance vector (see hotloop.gather_rows)
_gather_rows = hotloop.gather_rows


def _append_block(wx, wy, fill, pts, labs, do):
    """Append an r-row block to each instance's transcript at its fill.

    ``pts`` (B, r, d), ``labs`` (B, r) with label-0 marking invalid rows
    (valid rows compacted to the front), ``do`` (B,) gating the append.
    Same invariant as ``median._append2``: writes land at ≥ fill, so masked
    appends only touch label-0 scratch rows the next valid append overwrites.
    """
    labs = jnp.where(do[:, None], labs, 0).astype(jnp.int32)
    nvalid = jnp.sum(labs != 0, axis=1).astype(jnp.int32)

    def upd(w, wl, f, p, l):
        return (lax.dynamic_update_slice(w, p, (f, 0)),
                lax.dynamic_update_slice(wl, l, (f,)))

    wx, wy = jax.vmap(upd)(wx, wy, fill, pts.astype(wx.dtype), labs)
    return wx, wy, fill + nvalid


def _compact_rows(X, y, sel, nsel, r, order=None):
    """Gather the selected rows (≤ r per instance) into a compacted
    (B, r, d) block with label-0 tail slots.  Rows are emitted in ascending
    ``order`` (unique per-row integer keys < N); default is index order —
    the order the host loop ships support points in (``support_points``
    returns ascending indices).  Violation replies pass the margin rank
    instead, matching the host's ``argsort(m)[:2]`` wire order."""
    N = X.shape[1]
    if order is None:
        order = jnp.broadcast_to(jnp.arange(N)[None, :], sel.shape)
    idx_key = jnp.where(sel, order, N)
    cidx = jnp.argsort(idx_key, axis=1, stable=True)[:, :r]       # (B, r)
    pts = jnp.take_along_axis(X, cidx[..., None], axis=1)         # (B, r, d)
    labs = jnp.where(jnp.arange(r)[None, :] < nsel[:, None],
                     jnp.take_along_axis(y, cidx, axis=1), 0)
    return pts, labs.astype(jnp.int32)


def step(
    data: EngineData,
    state: MaxMargState,
    *,
    k: int,
    max_support: int = 4,
    steps: int = 2000,
    stages: int = 3,
    lam0: float = 1e-3,
    trans_width: Optional[int] = None,
    warm: bool = False,
    per_node: bool = True,
    fused_kernel: bool = False,
    solver_kernel: Optional[bool] = None,
) -> MaxMargState:
    """Advance every active instance by one MAXMARG turn (pure, jittable,
    shape-stable — usable under jit/while_loop).

    ``trans_width`` (static) compacts the coordinator-transcript gather to
    the first ``trans_width`` rows — sound whenever it covers every active
    instance's live fill (``run_hot`` guarantees this; ``None`` gathers the
    full capacity).  ``warm`` (static) threads a carried separator into the
    refit's polish pre-stage: the last proposal the coordinator *verified
    clean* on everything it knows when ``per_node`` (static, the default —
    see the module docstring), else the previous turn's proposal.
    ``fused_kernel`` (static) routes the
    post-refit margin scan through the fused Pallas support/violation kernel
    (``kernels.support_margin.maxmarg_turn_scan_batched``, the TPU artifact)
    instead of its jnp reference — both produce identical integer decisions
    (bit-for-bit tested).  ``solver_kernel`` (static) selects the *refit*
    path the same way: the tiled Pegasos stage kernel
    (``kernels.pegasos``, jnp twin off-TPU) vs the classic d-unrolled
    loop; ``None`` defers to ``_svm_solve_batch``'s TPU-default."""
    B = state.done.shape[0]
    n_max, d = data.X.shape[2], data.X.shape[3]
    ci = state.turn % k                                # (B,) per-instance
    active = ~state.done
    comm = state.comm

    # -- 1. batched max-margin refit on coord's own ∪ transcript ------------
    Xc = _gather_rows(data.X, ci)                      # (B, n_max, d)
    yc = _gather_rows(data.y, ci)                      # (B, n_max)
    Wxc = _gather_rows(state.wx, ci)                   # (B, cap, d)
    Wyc = _gather_rows(state.wy, ci)                   # (B, cap)
    if trans_width is not None:                        # compacted gather
        Wxc = Wxc[:, :trans_width]
        Wyc = Wyc[:, :trans_width]
    if Wxc.shape[1]:
        K = jnp.concatenate([Xc, Wxc], axis=1)         # (B, N, d)
        yK = jnp.concatenate([yc, Wyc], axis=1)        # (B, N) i32
    else:                                              # empty transcripts
        K, yK = Xc, yc
    yKf = yK.astype(K.dtype)
    if warm:
        if per_node and k > 2:
            # the per-node carry the coordinator verified clean; at k=2 the
            # carry bookkeeping is statically skipped (see below), so warm
            # falls back to the single previous-turn carry there
            w0 = _gather_rows(state.c_w, ci)
            b0 = _gather_rows(state.c_b, ci)
            wok = _gather_rows(state.c_valid, ci) \
                & _gather_rows(state.warm_node, ci)
        else:
            w0, b0, wok = state.h_w, state.h_b, state.h_valid
        # clean0 is the solver's own polish gate (carried separator
        # classifies the fit set cleanly) — the latch counter's source,
        # observability only, never a protocol decision
        w, b, fit_ok, clean0 = _svm_solve_batch(
            K, yKf, jnp.float32(lam0), steps, stages,
            w0=w0, b0=b0, warm_ok=wok, return_gate=True,
            kernel=solver_kernel)
    else:
        w, b, fit_ok = _svm_solve_batch(K, yKf, jnp.float32(lam0), steps,
                                        stages, kernel=solver_kernel)
        clean0 = jnp.zeros_like(state.done)

    # -- 2-4 scans: one fused pass over the proposal --------------------------
    # support band ranks on the fit set, per-node error counts, and per-node
    # most-violated ranks — the Pallas kernel and its vmap reference return
    # identical int32 decisions (tests/test_kernels.py)
    if fused_kernel:
        sup_rank, err_k, viol_rank = ops.support_violation_batch(
            w, b, K, yK, data.X, data.y, rtol=RTOL,
            max_support=max_support, viol_ship=VIOL_SHIP)
    else:
        sup_rank, err_k, viol_rank = ref.maxmarg_turn_batch_ref(
            w, b, K, yK, data.X, data.y, rtol=RTOL,
            max_support=max_support, viol_ship=VIOL_SHIP)

    # -- 2. active-margin support points --------------------------------------
    sel = sup_rank < max_support
    nsel = jnp.sum(sel, axis=1).astype(jnp.int32)
    S_pts, S_lab = _compact_rows(K, yK, sel, nsel, max_support)

    # comm: support broadcast to the k-1 others
    comm = comm._replace(
        points=comm.points + jnp.where(active, nsel * (k - 1), 0),
        messages=comm.messages + jnp.where(active, k - 1, 0),
        rounds=comm.rounds + active.astype(jnp.int32),
    )

    wx, wy, w_fill = state.wx, state.wy, state.w_fill
    for j in range(k):
        wxj, wyj, fj = _append_block(
            wx[:, j], wy[:, j], w_fill[:, j], S_pts, S_lab,
            active & (j != ci))
        wx = wx.at[:, j].set(wxj)
        wy = wy.at[:, j].set(wyj)
        w_fill = w_fill.at[:, j].set(fj)

    # -- 3. per-node error counts + all-clear bits --------------------------
    errs = jnp.sum(err_k, axis=1)
    comm = comm._replace(
        bits=comm.bits + jnp.where(active, k - 1, 0),
        messages=comm.messages + jnp.where(active, k - 1, 0),
    )

    # -- 4. violated nodes ship their 2 most-violated points ----------------
    n_valid_k = jnp.sum(data.y != 0, axis=2)
    node_ids = jnp.arange(k)[None, :]
    fire = active[:, None] & (node_ids != ci[:, None]) & (err_k > 0)
    nv = jnp.minimum(VIOL_SHIP, n_valid_k).astype(jnp.int32)      # (B, k)
    comm = comm._replace(
        points=comm.points + jnp.sum(jnp.where(fire, nv, 0), axis=1),
        messages=comm.messages + jnp.sum(fire, axis=1, dtype=jnp.int32),
    )
    # every reply targets only the coordinator's transcript, so gather that
    # one buffer at the per-instance index ci and scatter it back — k appends
    # per turn, not the k² a per-target loop would trace
    bidx = jnp.arange(B)
    for i in range(k):
        rank_i = viol_rank[:, i]
        sel_i = rank_i < VIOL_SHIP
        V_pts, V_lab = _compact_rows(data.X[:, i], data.y[:, i], sel_i,
                                     nv[:, i], VIOL_SHIP, order=rank_i)
        wxc, wyc2, fc = _append_block(
            _gather_rows(wx, ci), _gather_rows(wy, ci),
            _gather_rows(w_fill, ci), V_pts, V_lab, fire[:, i])
        wx = wx.at[bidx, ci].set(wxc)
        wy = wy.at[bidx, ci].set(wyc2)
        w_fill = w_fill.at[bidx, ci].set(fc)

    # -- 5. ε-termination + hypothesis/warm-carry bookkeeping ---------------
    term = active & (errs <= data.budget)
    # single-carry latch precondition: can the next turn's coordinator warm-
    # start from *this* proposal?  Only if it already classifies that shard
    # cleanly (necessary for the polish latch's clean-carry gate)
    err_next = _gather_rows(err_k, (ci + 1) % k)

    # per-node carries: each node *adopts* this turn's proposal as its carry
    # whenever it verifies the proposal clean on everything it knows — zero
    # errors on its own shard (the err_k bits it reports anyway) and margin
    # > 0 on every row of its current transcript.  A node's own fit can
    # never survive to its next turn (a continuing turn always lands
    # violation replies the fit misclassifies in its transcript), but a
    # *later, cleaner* proposal adopted mid-epoch usually can — that is what
    # latches in long k-party sweeps.  Flags then degrade incrementally:
    # the broadcast S block is clean under an adopted carry by construction
    # (its own support set), checked row-wise under a kept carry, and any
    # violation reply dirties the coordinator's transcript conservatively.
    # The carries are only ever read by per-node warm refits, so the
    # bookkeeping is traced only when this step may feed one (``per_node``
    # static — the runners pass per_node=False for cold and single-carry
    # runs).  At k=2 the mechanism is additionally provably inert — the
    # lone non-coordinator verifying the proposal clean IS the
    # ε-termination (errs = its error count ≤ budget), so adoption implies
    # the instance is done — and skipped regardless (k is static).
    if per_node and k > 2:
        is_ci = (jnp.arange(k)[None, :] == ci[:, None])  # (B, k)
        viol_any = jnp.any(fire, axis=1)                 # (B,)
        Wx_all = state.wx if trans_width is None \
            else state.wx[:, :, :trans_width]            # pre-append rows
        Wy_all = state.wy if trans_width is None \
            else state.wy[:, :, :trans_width]
        mT = Wy_all.astype(K.dtype) * (
            sum(Wx_all[..., i] * w[:, None, None, i] for i in range(d))
            + b[:, None, None])                          # (B, k, W)
        trans_clean = jnp.all((Wy_all == 0) | (mT > 0.0), axis=2)
        adopt = active[:, None] & fit_ok[:, None] & (err_k == 0) \
            & trans_clean
        c_w = jnp.where(adopt[..., None], w[:, None, :], state.c_w)
        c_b = jnp.where(adopt, b[:, None], state.c_b)
        mS = S_lab[:, None, :].astype(K.dtype) * (
            sum(S_pts[:, None, :, i].astype(K.dtype) * c_w[:, :, None, i]
                for i in range(d)) + c_b[:, :, None])    # (B, k, r)
        s_clean = jnp.all((S_lab[:, None, :] == 0) | (mS > 0.0), axis=2)
        recv = active[:, None] & ~is_ci                  # S recipients
        viol_hit = is_ci & (viol_any & active)[:, None]  # replies landed
        flag_adopt = jnp.where(is_ci, ~viol_any[:, None], True)
        flag_keep = state.warm_node & (s_clean | ~recv) & ~viol_hit
        c_valid = state.c_valid | adopt
        warm_node = jnp.where(adopt, flag_adopt, flag_keep)
    else:
        c_w, c_b = state.c_w, state.c_b
        c_valid, warm_node = state.c_valid, state.warm_node
    return MaxMargState(
        wx=wx, wy=wy, w_fill=w_fill,
        turn=state.turn + 1,
        done=state.done | term,
        converged=state.converged | term,
        epochs=jnp.where(term, state.turn // k + 1, state.epochs),
        h_w=jnp.where(active[:, None], w, state.h_w),
        h_b=jnp.where(active, b, state.h_b),
        h_valid=state.h_valid | active,
        warm_turn=jnp.where(active, err_next == 0, state.warm_turn),
        c_w=c_w, c_b=c_b,
        c_valid=c_valid,
        warm_node=warm_node,
        latches=state.latches + (active & clean0).astype(jnp.int32),
        comm=comm,
    )


@functools.partial(jax.jit, static_argnames=(
    "k", "max_turns", "max_support", "steps", "stages", "warm", "per_node",
    "fused_kernel", "solver_kernel"))
def run_compiled(
    data: EngineData,
    state0: MaxMargState,
    *,
    k: int,
    max_turns: int,
    max_support: int = 4,
    steps: int = 2000,
    stages: int = 3,
    lam0: float = 1e-3,
    warm: bool = False,
    per_node: bool = True,
    fused_kernel: bool = False,
    solver_kernel: Optional[bool] = None,
) -> MaxMargState:
    """The whole MAXMARG sweep as one device computation: while_loop over
    ``step`` until every instance terminates or the turn budget runs out.
    Always solves at the full padded transcript width — with ``warm=False``
    (the default) this is the exact pre-compaction execution model, kept as
    the legacy-parity reference for the hot path."""

    def cond(s: MaxMargState):
        return (jnp.min(s.turn) < max_turns) & ~jnp.all(s.done)

    def body(s: MaxMargState):
        return step(data, s, k=k, max_support=max_support, steps=steps,
                    stages=stages, lam0=lam0, warm=warm,
                    per_node=per_node and warm,
                    fused_kernel=fused_kernel, solver_kernel=solver_kernel)

    return lax.while_loop(cond, body, state0)


_STEP_STATICS = ("k", "max_support", "steps", "stages", "trans_width",
                 "warm", "per_node", "fused_kernel", "solver_kernel")


def _pad_fix(sub: MaxMargState, pad_row: jnp.ndarray) -> MaxMargState:
    """Mark gathered out-of-range rows inert: done=True masks them out of
    every decision and comm update, and trusting their (zero) carries lets
    the warm polish latch them instantly (zero data ⇒ infinite min margin),
    so padding can never force an annealing stage the live rows don't
    need."""
    return sub._replace(done=sub.done | pad_row,
                        h_valid=sub.h_valid | pad_row,
                        c_valid=sub.c_valid | pad_row[:, None],
                        warm_node=sub.warm_node | pad_row[:, None])


@functools.partial(jax.jit, static_argnames=("per_node",))
def _host_view(state: MaxMargState, ci: jnp.ndarray, *,
               per_node: bool = True) -> jnp.ndarray:
    """The hot loop's per-turn host knowledge as one (3, B) i32 transfer:
    done flags, the upcoming coordinator's warm-latch flags, and the
    transcript fills the width compaction keys on.  With per-node carry
    tracking the fill row is the max across *all* nodes — the carry
    bookkeeping's ``trans_clean`` scan reads every transcript, so the
    capped width must cover every live row (the `w_fill` contract, DESIGN
    §shared hot loop); otherwise only the coordinator's transcript is read
    and its fill alone keys the cap."""
    k = state.w_fill.shape[1]
    track = per_node and k > 2
    wflag = (jnp.take(state.warm_node, ci, axis=1) if track
             else state.warm_turn)
    fills = (jnp.max(state.w_fill, axis=1) if track
             else jnp.take(state.w_fill, ci, axis=1))
    return jnp.stack([state.done.astype(jnp.int32),
                      wflag.astype(jnp.int32),
                      fills])


def _pool_opts(cfg, solver_kernel) -> dict:
    """The step options of a pool's pinned turn: cold refits, no carry."""
    return dict(k=cfg.k, max_support=cfg.max_support, steps=cfg.svm_steps,
                stages=cfg.svm_stages, lam0=cfg.lam0, per_node=False,
                fused_kernel=False, solver_kernel=solver_kernel)


FAMILY = hotloop.Family(
    step=step, statics=_STEP_STATICS, pad_fix=_pad_fix,
    host_view=lambda state, ci, opts: _host_view(
        state, ci, per_node=opts["per_node"]),
    width_slack=lambda opts: 0,
    # one turn's worst transcript growth: the S broadcast on a receiving
    # node vs the ≤2-row replies from each of k−1 peers on the coordinator
    width_growth=lambda opts: max(opts["max_support"],
                                  VIOL_SHIP * (opts["k"] - 1)),
    takes_grid=False,
    capacity=lambda cfg: maxmarg_transcript_capacity(
        cfg.k, cfg.max_epochs, cfg.max_support),
    pool_opts=_pool_opts,
    fresh_state=lambda A, cfg, live, batch: blank_maxmarg_state(
        A, cfg.k, cfg.cap, cfg.d, np.arange(A) >= live),
    sep=("h_w", "h_b"),
)


def run_hot(
    data: EngineData,
    state: MaxMargState,
    *,
    k: int,
    max_turns: int,
    max_support: int = 4,
    steps: int = 2000,
    stages: int = 3,
    lam0: float = 1e-3,
    warm: bool = True,
    per_node: bool = True,
    compact: bool = True,
    fused_kernel: bool = False,
    solver_kernel: Optional[bool] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    donate: Optional[bool] = None,
    overlap: Optional[bool] = None,
    stats: Optional[dict] = None,
) -> MaxMargState:
    """The MAXMARG sweep as a host-driven turn loop over the jitted ``step``
    (:func:`repro.engine.hotloop.run_family` over :data:`FAMILY`).

    Relative to ``run_compiled`` (one while_loop at worst-case shapes) this
    trades one dispatch per *turn* — protocol sweeps converge in a few
    epochs — for the two compactions a while_loop cannot express, plus
    warm-started refits:

    * **width compaction**: the refit gathers the coordinator transcript at
      ``round_up(max live fill, 8)`` rows instead of the full static
      capacity, re-padding only when the bucket's max live length grows
      (widths are monotone, so each sweep compiles a handful of step
      variants that later sweeps of the same shape reuse);
    * **batch compaction**: finished instances drop out of the dispatch
      (the live set rounds up to a multiple of 4 with inert zero-filled
      padding rows), so a long tail of unconverged instances stops paying
      for the whole sweep's refit math;
    * **warm refits** (``warm=True``): turn ≥ 1 refits polish a carried
      separator instead of annealing from zero — the last proposal each
      node verified clean on its own data when ``per_node`` (the default;
      see the module docstring), else the previous turn's proposal
      (see ``classifiers._svm_solve_batch``).

    Per-instance results are identical in every protocol decision to
    ``run_compiled`` — solver math differs only by float reassociation
    across padding widths and by warm-vs-cold approximation of the same
    transcript-determined optimum (tests/test_maxmarg_warm.py pins comm/
    rounds/convergence and the canonicalized separator across both paths).

    ``mesh`` shards every dispatch over the leading B axis, with
    ``donate``/``overlap`` on by default (``hotloop.run_family``).
    MAXMARG decisions are per-instance, so sharding itself is exact; the
    stale warm-gate under ``overlap`` may make different — equally valid —
    polish-skip choices, decision-preserving like the warm gate itself.
    """
    # carry bookkeeping must run on *every* turn of a warm per-node run
    # (including turns whose polish dispatch is skipped) but on none of a
    # cold or single-carry run, so the tracking flag is run-level, not
    # per-dispatch
    opts = dict(k=k, max_support=max_support, steps=steps, stages=stages,
                lam0=lam0, per_node=per_node and warm,
                fused_kernel=fused_kernel, solver_kernel=solver_kernel)
    return hotloop.run_family(
        FAMILY, data, (), state, opts, max_turns=max_turns, warm=warm,
        compact=compact, mesh=mesh, donate=donate, overlap=overlap,
        stats=stats)


def run_instances(
    instances: Sequence[ProtocolInstance],
    *,
    eps: Optional[float] = None,
    max_epochs: int = 48,
    max_support: int = 4,
    steps: int = 2000,
    stages: int = 3,
    lam: float = 1e-3,
    warm: bool = True,
    per_node: bool = True,
    compact: bool = True,
    fused_kernel: Optional[bool] = None,
    solver_kernel: Optional[bool] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    donate: Optional[bool] = None,
    overlap: Optional[bool] = None,
    stats: Optional[dict] = None,
):
    """Run a batch of MAXMARG instances as one compiled sweep.

    Returns :class:`~repro.core.protocols.one_way.ProtocolResult` per
    instance, shaped exactly like the retired host loop's (which survives as
    the differential oracle in ``benchmarks/legacy_maxmarg.py``).

    ``warm``/``compact`` select the hot path (``run_hot``); passing both as
    False runs the single-dispatch cold padded ``run_compiled`` — the exact
    pre-compaction execution model, kept for legacy-oracle parity and the
    warm-vs-cold differential gate.  ``per_node`` picks the warm-carry mode
    (the last proposal each node verified clean vs the previous turn's
    proposal — see the module docstring and ``run_hot``).
    ``fused_kernel`` routes the per-turn margin scans through
    the Pallas kernel (default: on TPU only, like the MEDIAN selector's
    ``cut_kernel``); ``solver_kernel`` does the same for the refit solver
    itself — the tiled Pegasos stage kernel with its fused first-0-error
    latch (jnp twin off-TPU; same TPU-only default).  ``mesh`` shards the hot path over a 1-D ("data",)
    device mesh (requires ``compact=True``); ``donate``/``overlap`` opt the
    per-turn dispatches into buffer donation and the double-buffered host
    loop (mesh default: both on).  ``stats`` collects the hot loop's
    counters (``hotloop.run_hot``) and the upload's
    (``state.pack_instances_maxmarg``).  Tracing: ``sweep.pack`` spans
    packing and upload, holding ``sweep.assemble`` around the on-device
    assembly of the batch; ``sweep.collect`` spans the read-back of the
    results, and the hot loop writes the per-turn ``sweep.*`` spans
    between them.

    Compile-key contract: ``max_epochs``, ``max_support``, ``steps``,
    ``stages``, ``k``, ``d``, ``per_node``, the kernel toggles, and the
    mesh topology are static — changing any of them compiles a new
    ``step``.  Shard contents, eps, ``lam``, seeds, and B are traced
    data; the hot path re-keys only on the quantized
    ``(n_pad, width, warm)`` buckets ``hotloop.KEY_LOG`` records.
    """
    from repro.core import classifiers as clf
    from repro.core.protocols.one_way import ProtocolResult

    if mesh is not None and not compact:
        raise ValueError("sharded sweeps require the compacted hot path")
    if eps is not None:
        instances = [ProtocolInstance(inst.shards, eps, "maxmarg")
                     for inst in instances]
    if fused_kernel is None:
        fused_kernel = ops.on_tpu()
    if solver_kernel is None:
        solver_kernel = ops.on_tpu()
    with jax.profiler.TraceAnnotation("sweep.pack"):
        data, state0, k, _cap = pack_instances_maxmarg(
            instances, max_epochs=max_epochs, max_support=max_support,
            mesh=mesh, stats=stats)
    if warm or compact:
        final = run_hot(data, state0, k=k, max_turns=k * max_epochs,
                        max_support=max_support, steps=steps, stages=stages,
                        lam0=lam, warm=warm, per_node=per_node,
                        compact=compact, fused_kernel=fused_kernel,
                        solver_kernel=solver_kernel, mesh=mesh,
                        donate=donate, overlap=overlap, stats=stats)
    else:
        final = run_compiled(data, state0, k=k, max_turns=k * max_epochs,
                             max_support=max_support, steps=steps,
                             stages=stages, lam0=lam, per_node=per_node,
                             fused_kernel=fused_kernel,
                             solver_kernel=solver_kernel)

    with jax.profiler.TraceAnnotation("sweep.collect"):
        converged = np.asarray(final.converged)
        epochs = np.asarray(final.epochs)
        h_w = np.asarray(final.h_w, np.float64)
        h_b = np.asarray(final.h_b, np.float64)
        latches = np.asarray(final.latches)
        comm_np = type(final.comm)(*(np.asarray(a) for a in final.comm))
        d = data.X.shape[3]
        extra = {"engine": True, "batch": len(instances),
                 "selector": "maxmarg", "warm": warm, "compact": compact,
                 "per_node": per_node}
        if mesh is not None:
            extra["devices"] = int(mesh.shape["data"])
        results: List[ProtocolResult] = []
        for i in range(len(instances)):
            h = clf.LinearSeparator(h_w[i], float(h_b[i]))
            results.append(ProtocolResult(
                h,
                comm_np.summary(i, dim=d),
                rounds=int(epochs[i]) if converged[i] else max_epochs,
                converged=bool(converged[i]),
                extra=dict(extra, warm_latches=int(latches[i])),
            ))
    return results
