"""Mixed-selector protocol turns over one superset state (the tentpole of
DESIGN.md §unified mixed-selector state).

``run_sweep`` historically bucketed a heterogeneous grid by selector and
compiled one dispatch per bucket — fine for paper grids, wrong for a
production mix where MEDIAN, MAXMARG and one-way SAMPLING sessions
interleave and a session pool must admit any of them into any freed slot.
This module is the one-dispatch answer: a single jitted ``step`` over
:class:`~repro.engine.state.UnifiedState` whose per-instance selector code
is *data* (a traced (B,) i32 leaf), so the compile-cache key never depends
on the traffic mix.

**Masked substeps, not ``lax.switch``.**  The turn body runs every
family's substep over the shared leaves and merges per-row by selector
mask:

* the MEDIAN substep is :func:`repro.engine.median.step` on a view whose
  ``done`` masks every non-MEDIAN row (statically omitted when the mix has
  no median rows);
* the MAXMARG substep is :func:`repro.engine.maxmarg.step` on a view
  masking MEDIAN rows and pre-fit SAMPLING rows — a SAMPLING row *rides
  the MAXMARG fit*: its Vitter reservoir lives in node ``k-1``'s
  transcript, so at its fit turn (``turn ≥ k-1``, where the coordinator
  index is exactly ``k-1``) the MAXMARG fit set ``own ∪ transcript`` *is*
  the sampling oracle's ``X[k-1] ∪ reservoir`` fit, and the proposal lands
  in the shared separator leaves;
* the SAMPLING hop substep reuses :func:`repro.engine.oneway._make_ingest`
  (vmapped, bitwise the one-way oracle's Vitter process) on the reservoir
  slice of the shared transcript and meters the oracle's per-hop comm.

``lax.switch`` would buy nothing here: with a *batched* predicate a
vmapped switch lowers to select-over-all-branches — every branch executes
for every row anyway — so the masked form pays the same compute with none
of the branch-plumbing, and keeps each family's substep byte-identical to
its single-selector oracle (the DESIGN.md tradeoff; measured in
BENCH_service.json's ``mixed_traffic`` series).  Each family's substep
writes are discarded row-wise by the merge wherever another family owns
the row, so per-row results match the per-selector paths: MEDIAN rows
bit-exact (any covering transcript width is), MAXMARG and SAMPLING rows
decision/comm-exact with separators equal up to the float reassociation of
padded solver widths (tests/test_unified.py pins all three).

Compile-key contract: ``step``'s cache keys on the static tuple
(`k`, `max_support`, `steps`, `stages`, `trans_width`, `warm`,
`per_node`, `has_median`, `first_turn`, kernel flags) plus the leaf
shapes (B, cap, n_max, m) — *never* on the selector mix, the admission
order, or any per-row value.  ``hotloop.run_hot`` drives it at
geometric width buckets by default so mixed-width traffic stays within
O(log cap) compiled variants.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.engine import hotloop, median, oneway
from repro.engine import maxmarg as mm
from repro.engine.state import (
    EngineData,
    MaxMargState,
    ProtocolInstance,
    ProtocolState,
    SELECTOR_CODES,
    SEL_MAXMARG,
    SEL_MEDIAN,
    SEL_SAMPLING,
    UnifiedState,
    blank_unified_state,
    pack_instances_unified,
    unified_transcript_capacity,
)


def _median_view(state: UnifiedState) -> ProtocolState:
    """The MEDIAN substep's input: shared leaves aliased (h_v/h_t live in
    the shared h_w/h_b), every non-MEDIAN row masked done."""
    return ProtocolState(
        dir_ok=state.dir_ok, wx=state.wx, wy=state.wy, w_fill=state.w_fill,
        lo_w=state.lo_w, hi_w=state.hi_w, turn=state.turn,
        done=state.done | (state.sel != SEL_MEDIAN),
        converged=state.converged, epochs=state.epochs,
        h_v=state.h_w, h_t=state.h_b, h_valid=state.h_valid,
        comm=state.comm)


def _maxmarg_view(state: UnifiedState, k: int) -> MaxMargState:
    """The MAXMARG substep's input: MEDIAN rows masked done, SAMPLING rows
    masked until their fit turn (``turn ≥ k-1``, when the coordinator is
    node k-1 and the fit set equals the sampling oracle's)."""
    pre_fit = (state.sel == SEL_SAMPLING) & (state.turn < k - 1)
    return MaxMargState(
        wx=state.wx, wy=state.wy, w_fill=state.w_fill, turn=state.turn,
        done=state.done | (state.sel == SEL_MEDIAN) | pre_fit,
        converged=state.converged, epochs=state.epochs,
        h_w=state.h_w, h_b=state.h_b, h_valid=state.h_valid,
        warm_turn=state.warm_turn, c_w=state.c_w, c_b=state.c_b,
        c_valid=state.c_valid, warm_node=state.warm_node,
        latches=state.latches, comm=state.comm)


def _bc(mask: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))


def step(
    data: EngineData,
    V: jnp.ndarray,
    state: UnifiedState,
    *,
    k: int,
    max_support: int = 4,
    steps: int = 2000,
    stages: int = 3,
    lam0: float = 1e-3,
    trans_width: Optional[int] = None,
    warm: bool = False,
    per_node: bool = True,
    has_median: bool = True,
    first_turn: bool = False,
    cut_kernel: bool = False,
    extremes_kernel: bool = False,
    fused_kernel: bool = False,
    solver_kernel: Optional[bool] = None,
) -> UnifiedState:
    """Advance every active instance by one turn of *its own* protocol
    (pure, jittable, shape-stable).

    Statics are the union of the per-selector steps' plus ``has_median``
    (which omits the MEDIAN substep entirely for median-free mixes — the
    stub 1-wide arc leaves then pass through untouched).  ``trans_width``
    caps every transcript read exactly like the per-selector steps, and
    additionally bounds the SAMPLING reservoir slice — the hot loop's width
    must cover every live SAMPLING row's ``res_cap`` (``_host_view`` folds
    ``res_cap`` into the fill row to guarantee it; reservoir writes beyond
    the static slice would be silently dropped otherwise).

    Per-row masking discipline: each substep sees a view whose ``done``
    masks every row it does not own, and the merge takes each leaf from its
    owning family only — a substep's masked scratch writes (label-0 append
    rows, solver proposals on foreign rows) are discarded wholesale, so
    every row's trajectory is the one its single-selector oracle computes.
    """
    is_med = state.sel == SEL_MEDIAN
    is_mm = state.sel == SEL_MAXMARG
    is_samp = state.sel == SEL_SAMPLING
    active = ~state.done

    # -- family substeps over the shared leaves -----------------------------
    med = None
    if has_median:
        med = median.step(
            data, V, _median_view(state), k=k, first_turn=first_turn,
            cut_kernel=cut_kernel, extremes_kernel=extremes_kernel,
            trans_width=trans_width)
    mmo = mm.step(
        data, _maxmarg_view(state, k), k=k, max_support=max_support,
        steps=steps, stages=stages, lam0=lam0, trans_width=trans_width,
        warm=warm, per_node=per_node, fused_kernel=fused_kernel,
        solver_kernel=solver_kernel)

    # -- sampling hop substep (the oracle's Vitter chain, one hop per turn) -
    hop_act = active & is_samp & (state.turn < k - 1)
    fit_act = active & is_samp & (state.turn >= k - 1)
    hop_t = jnp.clip(state.turn, 0, max(k - 2, 0))
    res_w = int(state.wx.shape[2]) if trans_width is None else trans_width
    Xi = hotloop.gather_rows(data.X, hop_t)              # (B, n_max, d)
    yi = hotloop.gather_rows(data.y, hop_t)
    keyb = hotloop.gather_rows(state.hop_keys, hop_t)    # (B, 2) u32
    resX = state.wx[:, k - 1, :res_w]
    resy = state.wy[:, k - 1, :res_w]
    rX, ry, sn = jax.vmap(oneway._make_ingest(res_w))(
        resX, resy, state.seen, keyb, Xi, yi, state.res_cap)
    shipped = jnp.minimum(sn, state.res_cap)
    wx_s = state.wx.at[:, k - 1, :res_w].set(
        jnp.where(_bc(hop_act, rX), rX, resX))
    wy_s = state.wy.at[:, k - 1, :res_w].set(
        jnp.where(hop_act[:, None], ry, resy))
    w_fill_s = state.w_fill.at[:, k - 1].set(
        jnp.where(hop_act, shipped, state.w_fill[:, k - 1]))
    # the oracle's per-hop message slot: the forwarded reservoir (possibly
    # empty — still one message), one round per hop; nothing at the fit turn
    comm_s = state.comm._replace(
        points=state.comm.points + jnp.where(hop_act, shipped, 0),
        messages=state.comm.messages + hop_act.astype(jnp.int32),
        rounds=state.comm.rounds + hop_act.astype(jnp.int32))

    # -- per-row merge: each leaf from its owning family --------------------
    def pick(med_leaf, mm_leaf, samp_leaf):
        out = jnp.where(_bc(is_mm, samp_leaf), mm_leaf, samp_leaf)
        if med is not None:
            out = jnp.where(_bc(is_med, out), med_leaf, out)
        return out

    m_ = med if med is not None else mmo  # unread when has_median is False
    return UnifiedState(
        sel=state.sel,
        dir_ok=m_.dir_ok if med is not None else state.dir_ok,
        lo_w=m_.lo_w if med is not None else state.lo_w,
        hi_w=m_.hi_w if med is not None else state.hi_w,
        wx=pick(m_.wx, mmo.wx, wx_s),
        wy=pick(m_.wy, mmo.wy, wy_s),
        w_fill=pick(m_.w_fill, mmo.w_fill, w_fill_s),
        turn=state.turn + 1,
        done=pick(m_.done, mmo.done, state.done | fit_act),
        converged=pick(m_.converged, mmo.converged,
                       state.converged | fit_act),
        epochs=pick(m_.epochs, mmo.epochs,
                    jnp.where(fit_act, k - 1, state.epochs)),
        h_w=jnp.where(_bc(is_med, state.h_w), m_.h_v, mmo.h_w)
        if med is not None else mmo.h_w,
        h_b=jnp.where(is_med, m_.h_t, mmo.h_b)
        if med is not None else mmo.h_b,
        h_valid=jnp.where(is_med, m_.h_valid, mmo.h_valid)
        if med is not None else mmo.h_valid,
        warm_turn=mmo.warm_turn, c_w=mmo.c_w, c_b=mmo.c_b,
        c_valid=mmo.c_valid, warm_node=mmo.warm_node, latches=mmo.latches,
        seen=jnp.where(hop_act, sn, state.seen),
        res_cap=state.res_cap,
        hop_keys=state.hop_keys,
        comm=type(state.comm)(*(pick(a, b, c) for a, b, c in
                                zip(m_.comm if med is not None else comm_s,
                                    mmo.comm, comm_s))),
    )


_STEP_STATICS = ("k", "max_support", "steps", "stages", "trans_width",
                 "warm", "per_node", "has_median", "first_turn",
                 "cut_kernel", "extremes_kernel", "fused_kernel",
                 "solver_kernel")


def _pad_fix(sub: UnifiedState, pad_row: jnp.ndarray) -> UnifiedState:
    """Mark gathered out-of-range rows inert: done=True masks them out of
    every substep's decisions, and trusting their (zero) carries keeps the
    warm polish gate from ever forcing solver work for padding (same
    contract as the per-selector pad fixes; pad rows gather ``sel=0``,
    which is harmless under ``done``)."""
    return sub._replace(done=sub.done | pad_row,
                        h_valid=sub.h_valid | pad_row,
                        c_valid=sub.c_valid | pad_row[:, None],
                        warm_node=sub.warm_node | pad_row[:, None])


@functools.partial(jax.jit, static_argnames=("per_node",))
def _host_view(state: UnifiedState, ci: jnp.ndarray, *,
               per_node: bool = True) -> jnp.ndarray:
    """The hot loop's per-turn host knowledge as one (3, B) i32 transfer:
    done flags, warm-latch flags (MAXMARG rows only — the other families
    have no warm carry, so they can never force a warm-keyed dispatch),
    and the width-compaction fills.  Fills are the per-row max across
    nodes, and for SAMPLING rows additionally at least ``res_cap``: the
    compacted width bounds the reservoir slice, and an ingest write beyond
    it would be silently scatter-dropped — covering ``res_cap`` keeps the
    reservoir bitwise the oracle's at every width the loop can pick."""
    k = state.w_fill.shape[1]
    track = per_node and k > 2
    wflag = (jnp.take(state.warm_node, ci, axis=1) if track
             else state.warm_turn)
    wflag = wflag & (state.sel == SEL_MAXMARG)
    fills = jnp.max(state.w_fill, axis=1)
    fills = jnp.where(state.sel == SEL_SAMPLING,
                      jnp.maximum(fills, state.res_cap), fills)
    return jnp.stack([state.done.astype(jnp.int32),
                      wflag.astype(jnp.int32),
                      fills])


def _fresh_state(A: int, cfg, live: int, batch) -> UnifiedState:
    """Fresh superset rows for a mixed admission wave: the selector code,
    reservoir size and Vitter hop keys are per-row data taken from the
    pending entries — the device tree shapes (and so the admission scatter's
    compile key) never depend on the wave's selector mix."""
    k, d = cfg.k, cfg.d
    sel = np.zeros((A,), np.int32)
    res_cap = np.zeros((A,), np.int32)
    hop_keys = np.zeros((A, max(k - 1, 1), 2), np.uint32)
    for i, p in enumerate(batch):
        sel[i] = SELECTOR_CODES[p.selector]
        if p.selector == "sampling":
            res_cap[i] = p.res_cap
            hop_keys[i] = np.asarray(jax.random.split(
                jax.random.PRNGKey(p.seed), k - 1))
    return blank_unified_state(A, k, cfg.cap, d,
                               cfg.n_angles if d == 2 else 1,
                               np.arange(A) >= live, sel, res_cap, hop_keys)


# one loop drives all three families at once: the width slack and the
# stale-view growth bound are the *max* over the families' own bounds
# (MEDIAN's post-S extremes slack, MAXMARG's support/violation appends), so
# every compacted read covers whichever family's transcript grew fastest;
# the pool reads MEDIAN rows exactly where the grid exists (d = 2)
FAMILY = hotloop.Family(
    step=step, statics=_STEP_STATICS, pad_fix=_pad_fix,
    host_view=lambda state, ci, opts: _host_view(
        state, ci, per_node=opts["per_node"]),
    width_slack=lambda opts: median.WIDTH_SLACK if opts["has_median"] else 0,
    width_growth=lambda opts: max(median.FAMILY.width_growth(opts),
                                  mm.FAMILY.width_growth(opts)),
    takes_grid=True,
    capacity=lambda cfg: unified_transcript_capacity(
        cfg.k, cfg.max_epochs, cfg.max_support,
        res_cap=int(cfg.res_cap or 0), has_median=(cfg.d == 2)),
    pool_opts=lambda cfg, solver_kernel: dict(
        mm.FAMILY.pool_opts(cfg, solver_kernel), has_median=(cfg.d == 2),
        cut_kernel=False, extremes_kernel=False),
    fresh_state=_fresh_state,
    sep=("h_w", "h_b"),
)


def run_hot(
    data: EngineData,
    V: jnp.ndarray,
    state: UnifiedState,
    *,
    k: int,
    max_turns: int,
    max_support: int = 4,
    steps: int = 2000,
    stages: int = 3,
    lam0: float = 1e-3,
    warm: bool = True,
    per_node: bool = True,
    has_median: bool = True,
    compact: bool = True,
    cut_kernel: bool = False,
    extremes_kernel: bool = False,
    fused_kernel: bool = False,
    solver_kernel: Optional[bool] = None,
    width_policy: str = "geometric",
    stats: Optional[dict] = None,
) -> UnifiedState:
    """The mixed sweep as a host-driven turn loop over the jitted ``step``
    (:func:`repro.engine.hotloop.run_family` over :data:`FAMILY`).

    ``width_policy`` defaults to ``"geometric"`` here — mixed traffic
    spreads live fills across families with very different growth rates,
    exactly the churn case the geometric buckets bound — while the
    per-selector loops keep their linear (byte-identical legacy) rule.
    """
    opts = dict(k=k, max_support=max_support, steps=steps, stages=stages,
                lam0=lam0, per_node=per_node and warm, has_median=has_median,
                cut_kernel=cut_kernel, extremes_kernel=extremes_kernel,
                fused_kernel=fused_kernel, solver_kernel=solver_kernel)
    return hotloop.run_family(
        FAMILY, data, (V,), state, opts, max_turns=max_turns, warm=warm,
        compact=compact, width_policy=width_policy, stats=stats)


def run_instances(
    instances: Sequence[ProtocolInstance],
    *,
    eps: Optional[float] = None,
    n_angles: int = 1024,
    max_epochs: int = 48,
    max_support: int = 4,
    steps: int = 2000,
    stages: int = 3,
    lam: float = 1e-3,
    warm: bool = True,
    per_node: bool = True,
    compact: bool = True,
    vc_dim: Optional[int] = None,
    c: Optional[float] = None,
    solver_kernel: Optional[bool] = None,
    width_policy: str = "geometric",
    stats: Optional[dict] = None,
):
    """Run a mixed MEDIAN + MAXMARG + SAMPLING grid as ONE compiled
    dispatch path — no selector bucketing.

    Returns :class:`~repro.core.protocols.one_way.ProtocolResult` per
    instance in input order, shaped exactly like the per-selector
    ``run_instances`` paths' (which survive unchanged as this path's
    differential oracles): MEDIAN rows recover ``LinearSeparator(-h_v,
    h_t)`` from the shared separator leaves, MAXMARG rows report their
    warm-latch count, SAMPLING rows their ε-net ``sample_size`` with
    ``rounds = k-1`` and ``converged=True``.

    Compile-key contract: the compiled step variants key on the static
    solver/protocol options and the compacted (n_pad, width, warm) shapes
    — never on the selector mix, so any interleaving of families at equal
    shapes reuses one cache (tests/test_recompile.py's mixed gate).
    Options that a family does not use are simply inert for its rows
    (``n_angles`` for MAXMARG, ``vc_dim``/``c`` for MEDIAN, …).
    """
    from repro.core import classifiers as clf
    from repro.core import geometry as geo
    from repro.core.protocols.one_way import ProtocolResult

    if eps is not None:
        instances = [ProtocolInstance(inst.shards, eps, inst.selector,
                                      inst.seed) for inst in instances]
    data, state0, k, _cap = pack_instances_unified(
        instances, n_angles=n_angles, max_epochs=max_epochs,
        max_support=max_support, vc_dim=vc_dim, c=c)
    d = int(data.X.shape[3])
    has_median = any(inst.selector == "median" for inst in instances)
    if has_median:
        V = jnp.asarray(geo.direction_grid(n_angles), jnp.float32)
    else:
        V = jnp.zeros((1, d), jnp.float32)
    final = run_hot(data, V, state0, k=k, max_turns=k * max_epochs,
                    max_support=max_support, steps=steps, stages=stages,
                    lam0=lam, warm=warm, per_node=per_node,
                    has_median=has_median, compact=compact,
                    solver_kernel=solver_kernel, width_policy=width_policy,
                    stats=stats)

    converged = np.asarray(final.converged)
    epochs = np.asarray(final.epochs)
    h_w = np.asarray(final.h_w, np.float64)
    h_b = np.asarray(final.h_b, np.float64)
    latches = np.asarray(final.latches)
    res_cap = np.asarray(final.res_cap)
    comm_np = type(final.comm)(*(np.asarray(a) for a in final.comm))
    extra = {"engine": True, "batch": len(instances), "unified": True,
             "warm": warm, "compact": compact}
    results: List[ProtocolResult] = []
    for b, inst in enumerate(instances):
        ex = dict(extra, selector=inst.selector)
        if inst.selector == "median":
            h = clf.LinearSeparator(-h_w[b], float(h_b[b]))
            rounds = int(epochs[b]) if converged[b] else max_epochs
            conv = bool(converged[b])
        elif inst.selector == "maxmarg":
            h = clf.LinearSeparator(h_w[b], float(h_b[b]))
            rounds = int(epochs[b]) if converged[b] else max_epochs
            conv = bool(converged[b])
            ex["warm_latches"] = int(latches[b])
        else:
            h = clf.LinearSeparator(h_w[b], float(h_b[b]))
            rounds = k - 1
            conv = True
            ex["sample_size"] = int(res_cap[b])
        results.append(ProtocolResult(
            h, comm_np.summary(b, dim=d), rounds=rounds, converged=conv,
            extra=ex))
    return results
