"""Fault-tolerant streaming session pool over the compacted hot loop.

The ROADMAP's north star is a persistent service absorbing protocol traffic,
not a one-shot sweep.  This module turns the hot loop's admit/evict batch
compaction (PRs 4-6) into *admission-aware* streaming: a ring buffer of W
session slots where slots freed by converged/evicted sessions refill from a
pending queue **between turns**, at pinned ``(n_pad, width, warm)`` compile
cache keys, so a saturated pool's steady-state recompile count is 0
(``benchmarks/service_sweep.py`` measures it; the key-log machinery of
tests/test_recompile.py gates it).

Mixed-phase dispatch is what PR 7's per-instance ``turn`` refactor buys:
admitted sessions start at turn 0 while their slot neighbours are mid-epoch,
and one dispatch advances them all (the coordinator index ``ci = turn % k``
is a (B,) gather).  ``PoolConfig(selector="unified")`` extends the same move
to mixed-*family* dispatch: the selector becomes traced per-row data in the
superset :class:`~repro.engine.state.UnifiedState`, so ONE pool absorbs
interleaved MEDIAN + MAXMARG + SAMPLING sessions with no per-family
bucketing and no extra compile keys (:mod:`repro.engine.unified`).  The pool's bit-exactness contract is **compiled-program
identity**: every dispatch uses one pinned (full-block, full-width) cache
key (see ``_dispatch`` for why — XLA's shape-dependent fusion perturbs
separator floats by ulps across keys), so a session's results are a pure
function of its own data and are **bit-exact across any admission timing,
batch composition, fault delays and checkpoint/restore**.  Against the
sweep-oriented ``engine.run_instances`` (which compiles at its own
fill-capped keys) the pool is decision- and comm-exact, with separators
typically bitwise equal and at worst a few f32 ulps apart — the same
cross-shape caveat as the engine's own hot-vs-cold series.

Failure model (``engine/faults.py``, DESIGN.md §session pool & failure
model): a seeded deterministic schedule injects per-turn node dropouts and
lost messages (the turn aborts before dispatch — a missed one-pool-turn
deadline — and retries under exponential backoff, bounded by
``retry_budget``), stragglers (the session sits out a drawn number of pool
turns, no retry charged), and post-turn state corruption.  Supervision is
host-side and never crashes the pool: every live slot is screened each turn
against three invariants — NaN separator, non-monotone transcript fill
(every healthy continuing turn strictly grows some transcript, so a
dispatched live row whose max fill fails to stay positive and monotone is
corrupt), and comm-budget blowout — and a tripped invariant or exhausted
retry budget quarantines the session, which is then evicted with its
retry/backoff counters surfaced (slot lifecycle: pending → live →
quarantined → evicted/converged).  Delivered messages are always metered
exactly; transient faults only delay turns, so surviving sessions keep
bit-exact decisions.

Checkpoint/restore reuses the flat-key ``.npz`` + JSON-manifest idiom of
``train/checkpoint.py``: device trees, host supervision arrays, the pending
queue and the session ledger round-trip, and the fault schedule is a pure
hash of ``(seed, session id, pool turn)`` — no RNG state — so a restored
pool replays the identical fault/eviction/retry sequence and unaffected
sessions finish bit-exact (tests/test_session_pool.py pins all of it).

Tracing: a pool turn writes ``jax.profiler.TraceAnnotation`` spans, which
only a running profiler records.  ``pool.step`` holds ``pool.admit``
(``rows`` admitted, ``nbytes`` handed to ``_admit_rows``, ``wait_us``
summed from submit to admission), ``pool.dispatch`` (``rows`` live,
``block`` pinned), ``pool.view`` and ``pool.evict`` (``rows``).  Every
argument comes from shapes and counts at hand, never from a device read.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import json
import os
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.sampling import epsilon_net_size
from repro.engine import faults as F
from repro.engine import hotloop, median, maxmarg, unified
from repro.engine.state import (
    BatchCommLog,
    EngineData,
    MaxMargState,
    ProtocolState,
    SEL_MEDIAN,
    SELECTOR_CODES,
    SELECTOR_NAMES,
    UnifiedState,
    _round_up,
    maxmarg_transcript_capacity,
    transcript_capacity,
    unified_transcript_capacity,
)
from repro.kernels import ops

# host-side slot lifecycle (the device only ever sees done flags)
SLOT_EMPTY = 0
SLOT_LIVE = 1
SLOT_QUARANTINED = 2

# terminal session statuses in the ledger
ST_PENDING = "pending"
ST_LIVE = "live"
ST_CONVERGED = "converged"
ST_BUDGET = "budget_exhausted"
ST_QUARANTINED = "quarantined"


@dataclasses.dataclass(frozen=True)
class PoolConfig:
    """Static pool geometry + supervision policy.

    Everything that pins a compile-cache key lives here: ``slots`` (the ring
    width W), ``k``/``n_pad``/``d`` (the shared instance shapes every
    admitted session is padded to — ragged shards pad with label-0 rows,
    exactly the engine's packing convention), the per-session epoch budget,
    and the fixed ``admit_block``/``corrupt_block`` scatter widths (blocks
    pad with out-of-range indices that the device scatters drop, so
    admission and corruption are each ONE pinned-shape dispatch regardless
    of how many rows they touch).

    Supervision policy: a session's turn must complete within one pool turn
    (the deadline); a miss (dropout / lost message) retries after
    ``backoff_base * 2**(retries-1)`` pool turns and quarantines when the
    consecutive-retry count exceeds ``retry_budget``.  ``comm_limit_bits``
    is the comm-blowout invariant threshold — generous against any
    legitimate per-turn bit cost (k-1 bits), tiny against
    ``faults.COMM_SPIKE_BITS``.

    ``selector="unified"`` makes admission selector-agnostic: each
    :meth:`SessionPool.submit` call names its own protocol family
    (MEDIAN / MAXMARG / SAMPLING), the selector rides the pending queue as
    data, and every mixed dispatch still uses the ONE pinned key — the
    superset :class:`~repro.engine.state.UnifiedState` cap covers every
    family, including ``res_cap`` (the largest per-session ε-net reservoir
    the pool accepts; defaults to the ε-net size at the pool's own ``eps``).
    """

    slots: int
    k: int
    n_pad: int
    d: int = 2
    selector: str = "median"
    eps: float = 0.05
    n_angles: int = 256
    max_epochs: int = 16
    max_support: int = 4
    svm_steps: int = 2000
    svm_stages: int = 3
    lam0: float = 1e-3
    # MAXMARG refit solver path: None = TPU-default (tiled Pegasos kernel
    # on TPU, classic d-unrolled loop elsewhere) — resolved once at pool
    # construction so admission keys stay pinned across the pool's life
    solver_kernel: Optional[bool] = None
    # unified pools only: max ε-net reservoir rows any SAMPLING session may
    # request; None resolves to the size at the pool's default eps
    res_cap: Optional[int] = None
    admit_block: int = 8
    corrupt_block: int = 4
    retry_budget: int = 3
    backoff_base: int = 1
    comm_limit_bits: int = 1 << 16
    checkpoint_every: int = 0            # pool turns between snapshots; 0=off
    checkpoint_dir: Optional[str] = None

    def __post_init__(self):
        if self.selector not in ("median", "maxmarg", "unified"):
            raise ValueError(f"unknown selector {self.selector!r}")
        if self.selector == "median" and self.d != 2:
            raise ValueError("MEDIAN engine is specified for R^2")
        if self.selector == "unified" and self.res_cap is None:
            # resolved once so dataclasses.asdict round-trips the pinned cap
            object.__setattr__(self, "res_cap", _round_up(
                epsilon_net_size(self.eps, self.d + 1), 8))
        if self.n_pad % 8:
            object.__setattr__(self, "n_pad", _round_up(self.n_pad, 8))
        if self.slots < 1 or self.k < 2:
            raise ValueError("need slots >= 1 and k >= 2")
        if self.checkpoint_every and not self.checkpoint_dir:
            raise ValueError("checkpoint_every needs checkpoint_dir")

    @property
    def max_turns(self) -> int:
        return self.k * self.max_epochs

    @property
    def cap(self) -> int:
        if self.selector == "median":
            return transcript_capacity(self.k, self.max_epochs)
        if self.selector == "unified":
            return unified_transcript_capacity(
                self.k, self.max_epochs, self.max_support,
                res_cap=int(self.res_cap or 0), has_median=(self.d == 2))
        return maxmarg_transcript_capacity(self.k, self.max_epochs,
                                           self.max_support)


# ---------------------------------------------------------------------------
# pinned-shape device ops (admission / corruption / supervision view)
# ---------------------------------------------------------------------------


@jax.jit
def _admit_rows(data, state, idx, dblk, sblk):
    """Scatter an admission block into the pool's device trees: ``idx`` is
    the fixed-size (A,) slot index block (out-of-range tail drops), ``dblk``
    / ``sblk`` the fresh (A, ...) data/state rows.  One dispatch per
    admission wave, cache-keyed only on the pinned block shapes."""
    return (hotloop.put_instances(data, dblk, idx),
            hotloop.put_instances(state, sblk, idx))


def _slot_masks(W, idx, kind):
    def mask(kv):
        return jnp.zeros((W,), bool).at[idx].set(kind == kv)
    return mask(F.CORRUPT_NAN), mask(F.CORRUPT_FILL), mask(F.CORRUPT_COMM)


@jax.jit
def _corrupt_median(state: ProtocolState, idx, kind) -> ProtocolState:
    """Apply drawn corruption kinds to the rows in ``idx`` (fixed-size
    block, out-of-range tail drops).  Each kind trips exactly one
    supervisor invariant: NaN separator, zeroed (non-monotone) fills, or a
    comm-bit spike.  Runs *after* the turn's dispatch — delivered messages
    were metered exactly; only the victim's own state mutates."""
    m_nan, m_fill, m_comm = _slot_masks(state.done.shape[0], idx, kind)
    return state._replace(
        h_t=jnp.where(m_nan, jnp.nan, state.h_t),
        h_v=jnp.where(m_nan[:, None], jnp.nan, state.h_v),
        w_fill=jnp.where(m_fill[:, None], 0, state.w_fill),
        comm=state.comm._replace(
            bits=state.comm.bits
            + jnp.where(m_comm, F.COMM_SPIKE_BITS, 0).astype(jnp.int32)),
    )


@jax.jit
def _corrupt_maxmarg(state: MaxMargState, idx, kind) -> MaxMargState:
    m_nan, m_fill, m_comm = _slot_masks(state.done.shape[0], idx, kind)
    return state._replace(
        h_b=jnp.where(m_nan, jnp.nan, state.h_b),
        h_w=jnp.where(m_nan[:, None], jnp.nan, state.h_w),
        w_fill=jnp.where(m_fill[:, None], 0, state.w_fill),
        comm=state.comm._replace(
            bits=state.comm.bits
            + jnp.where(m_comm, F.COMM_SPIKE_BITS, 0).astype(jnp.int32)),
    )


# UnifiedState shares MaxMargState's separator/transcript/comm leaf names,
# so the maxmarg corruption body applies verbatim — jax.jit re-keys on the
# pytree structure, giving the unified pool its own cached variant.  The
# supervision view likewise: max w_fill is each family's ACTUAL transcript
# fill (a SAMPLING row's reservoir fill is min(seen, res_cap), which grows
# monotonically per hop — distinct from the hot loop's width view, which
# inflates fills to res_cap for coverage).
_corrupt_unified = _corrupt_maxmarg


@jax.jit
def _view_median(state: ProtocolState) -> jnp.ndarray:
    """Supervision view as one (5, W) i32 transfer: done, converged, max
    transcript fill, NaN-separator flag, comm bits."""
    nan = jnp.isnan(state.h_t) | jnp.any(jnp.isnan(state.h_v), axis=1)
    return jnp.stack([state.done.astype(jnp.int32),
                      state.converged.astype(jnp.int32),
                      jnp.max(state.w_fill, axis=1),
                      nan.astype(jnp.int32),
                      state.comm.bits])


@jax.jit
def _view_maxmarg(state: MaxMargState) -> jnp.ndarray:
    nan = jnp.isnan(state.h_b) | jnp.any(jnp.isnan(state.h_w), axis=1)
    return jnp.stack([state.done.astype(jnp.int32),
                      state.converged.astype(jnp.int32),
                      jnp.max(state.w_fill, axis=1),
                      nan.astype(jnp.int32),
                      state.comm.bits])


_view_unified = _view_maxmarg


# ---------------------------------------------------------------------------
# fresh-row templates (host numpy; scattered on admission)
# ---------------------------------------------------------------------------


def _fresh_state_median(A: int, cfg: PoolConfig, live: int) -> ProtocolState:
    m, k, cap = cfg.n_angles, cfg.k, cfg.cap
    done = np.zeros((A,), bool)
    done[live:] = True                    # block padding rows are born done
    return ProtocolState(
        dir_ok=np.ones((A, m), bool),
        wx=np.zeros((A, k, cap, 2), np.float32),
        wy=np.zeros((A, k, cap), np.int32),
        w_fill=np.zeros((A, k), np.int32),
        lo_w=np.full((A, k, m), -np.inf, np.float32),
        hi_w=np.full((A, k, m), np.inf, np.float32),
        turn=np.zeros((A,), np.int32),
        done=done,
        converged=np.zeros((A,), bool),
        epochs=np.zeros((A,), np.int32),
        h_v=np.zeros((A, 2), np.float32),
        h_t=np.zeros((A,), np.float32),
        h_valid=np.zeros((A,), bool),
        comm=BatchCommLog(*(np.zeros((A,), np.int32)
                            for _ in BatchCommLog._fields)),
    )


def _fresh_state_maxmarg(A: int, cfg: PoolConfig, live: int) -> MaxMargState:
    k, cap, d = cfg.k, cfg.cap, cfg.d
    done = np.zeros((A,), bool)
    done[live:] = True
    return MaxMargState(
        wx=np.zeros((A, k, cap, d), np.float32),
        wy=np.zeros((A, k, cap), np.int32),
        w_fill=np.zeros((A, k), np.int32),
        turn=np.zeros((A,), np.int32),
        done=done,
        converged=np.zeros((A,), bool),
        epochs=np.zeros((A,), np.int32),
        h_w=np.zeros((A, d), np.float32),
        h_b=np.zeros((A,), np.float32),
        h_valid=np.zeros((A,), bool),
        warm_turn=np.zeros((A,), bool),
        c_w=np.zeros((A, k, d), np.float32),
        c_b=np.zeros((A, k), np.float32),
        c_valid=np.zeros((A, k), bool),
        warm_node=np.zeros((A, k), bool),
        latches=np.zeros((A,), np.int32),
        comm=BatchCommLog(*(np.zeros((A,), np.int32)
                            for _ in BatchCommLog._fields)),
    )


def _fresh_state_unified(A: int, cfg: PoolConfig, live: int,
                         batch: Sequence["_Pending"] = ()) -> UnifiedState:
    """Fresh superset rows for a mixed admission wave: the selector code,
    reservoir size and Vitter hop keys are per-row data taken from the
    pending entries — the device tree shapes (and so the admission scatter's
    compile key) never depend on the wave's selector mix."""
    k, cap, d = cfg.k, cfg.cap, cfg.d
    m = cfg.n_angles if d == 2 else 1
    done = np.zeros((A,), bool)
    done[live:] = True
    sel = np.zeros((A,), np.int32)
    res_cap = np.zeros((A,), np.int32)
    hop_keys = np.zeros((A, max(k - 1, 1), 2), np.uint32)
    for i, p in enumerate(batch):
        sel[i] = SELECTOR_CODES[p.selector]
        if p.selector == "sampling":
            res_cap[i] = p.res_cap
            hop_keys[i] = np.asarray(jax.random.split(
                jax.random.PRNGKey(p.seed), k - 1))
    return UnifiedState(
        sel=sel,
        dir_ok=np.ones((A, m), bool),
        lo_w=np.full((A, k, m), -np.inf, np.float32),
        hi_w=np.full((A, k, m), np.inf, np.float32),
        wx=np.zeros((A, k, cap, d), np.float32),
        wy=np.zeros((A, k, cap), np.int32),
        w_fill=np.zeros((A, k), np.int32),
        turn=np.zeros((A,), np.int32),
        done=done,
        converged=np.zeros((A,), bool),
        epochs=np.zeros((A,), np.int32),
        h_w=np.zeros((A, d), np.float32),
        h_b=np.zeros((A,), np.float32),
        h_valid=np.zeros((A,), bool),
        warm_turn=np.zeros((A,), bool),
        c_w=np.zeros((A, k, d), np.float32),
        c_b=np.zeros((A, k), np.float32),
        c_valid=np.zeros((A, k), bool),
        warm_node=np.zeros((A, k), bool),
        latches=np.zeros((A,), np.int32),
        seen=np.zeros((A,), np.int32),
        res_cap=res_cap,
        hop_keys=hop_keys,
        comm=BatchCommLog(*(np.zeros((A,), np.int32)
                            for _ in BatchCommLog._fields)),
    )


@dataclasses.dataclass
class _Pending:
    sid: int
    X: np.ndarray        # (k, n_pad, d) f32
    y: np.ndarray        # (k, n_pad) i32
    budget: int
    selector: str = "median"   # per-session family (unified pools)
    seed: int = 0              # Vitter PRNG seed (SAMPLING sessions)
    res_cap: int = 0           # ε-net reservoir rows (SAMPLING sessions)
    # host clock at submit (or at restore), read only for the admission
    # span's queue wait: never in sessions, stats or checkpoints
    submitted: float = dataclasses.field(default_factory=time.perf_counter)


class SessionPool:
    """Ring-buffer session pool: streaming admission over the hot loop,
    seeded fault injection, host-side supervision, checkpoint/restore.

    Typical use (the protocol service in :mod:`repro.serve.service` wraps
    this behind a streaming-ingest API)::

        pool = SessionPool(PoolConfig(slots=32, k=2, n_pad=64),
                           schedule=FaultSchedule(seed=7, p_dropout=0.05))
        sids = [pool.submit(shards) for shards in workload]
        pool.run()
        results = pool.results          # sid -> ProtocolResult
        pool.session(sid)["retries"]    # per-session supervision counters

    All supervision decisions are pure functions of (host arrays, device
    view, fault schedule), so two pools with equal config+schedule+workload
    make identical decisions — including across :meth:`checkpoint` /
    :meth:`restore` (the determinism contract tests pin).

    With ``PoolConfig(selector="unified")`` ONE pool absorbs mixed
    MEDIAN + MAXMARG + SAMPLING traffic: ``submit(shards,
    selector="sampling", seed=...)`` tags each session, the pending queue
    carries the tag as data, and dispatch/admission/corruption all stay at
    their single pinned keys (the superset state makes the selector a
    traced per-row leaf — see :mod:`repro.engine.unified`).

    Compile-key contract: everything that keys a compiled variant is fixed
    at construction — ``PoolConfig``'s geometry (``slots``/``k``/``n_pad``/
    ``d``), the ``cap`` transcript width, the solver statics
    (``max_support``/``svm_steps``/``svm_stages``, the resolved
    ``solver_kernel``), and the ``admit_block``/``corrupt_block`` scatter
    shapes.  Dispatch always uses the one key ``(round_up(slots, 4), cap,
    False, False)``, so after the first pool turn of each op NOTHING a
    caller streams in recompiles: not session count, admission order,
    selector mix (unified pools), ε, seeds, or fault timing.  Changing any
    ``PoolConfig`` field means a new pool and a fresh set of keys.
    """

    def __init__(self, config: PoolConfig,
                 schedule: Optional[F.FaultSchedule] = None,
                 stats: Optional[dict] = None):
        self.cfg = config
        self.schedule = schedule if schedule is not None else F.FaultSchedule()
        self.stats: Dict[str, Any] = stats if stats is not None else {}
        # resolved once: the solver path is part of the pinned dispatch key
        self._solver_kernel = (ops.on_tpu()
                               if config.solver_kernel is None
                               else bool(config.solver_kernel))
        W, k, n_pad, d = config.slots, config.k, config.n_pad, config.d

        if config.selector == "median":
            from repro.core import geometry as geo
            self._V = jnp.asarray(geo.direction_grid(config.n_angles),
                                  jnp.float32)
            state0 = _fresh_state_median(W, config, live=0)
        elif config.selector == "unified":
            if config.d == 2:
                from repro.core import geometry as geo
                self._V = jnp.asarray(geo.direction_grid(config.n_angles),
                                      jnp.float32)
            else:   # median-free pool: stub grid (the substep is omitted)
                self._V = jnp.zeros((1, config.d), jnp.float32)
            state0 = _fresh_state_unified(W, config, live=0)
        else:
            self._V = None
            state0 = _fresh_state_maxmarg(W, config, live=0)
        self.data = EngineData(
            jnp.zeros((W, k, n_pad, d), jnp.float32),
            jnp.zeros((W, k, n_pad), jnp.int32),
            jnp.zeros((W,), jnp.int32))
        # empty slots are born done: the dispatch mask is host-side anyway,
        # and done=True keeps them inert even if gathered as padding
        self.state = jax.tree_util.tree_map(jnp.asarray, state0)
        # host bytes one admission wave hands _admit_rows: admit_block rows
        # of data and fresh state (every leaf leads with the slot axis) and
        # the int32 index block
        row_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
            (self.data, state0))) // W
        self._wave_nbytes = config.admit_block * (row_bytes + 4)
        self._key_logged = False

        self.pool_turn = 0
        self._next_sid = 0
        self.pending: deque = deque()
        self.sessions: Dict[int, Dict[str, Any]] = {}
        self.results: Dict[int, Any] = {}

        # host supervision arrays (one row per slot)
        self.sid = np.full((W,), -1, np.int64)
        self.slot_state = np.full((W,), SLOT_EMPTY, np.int32)
        self.retries = np.zeros((W,), np.int32)       # consecutive, current
        self.backoff_until = np.zeros((W,), np.int64)
        self.straggle_until = np.zeros((W,), np.int64)
        self.prev_fill = np.zeros((W,), np.int32)
        self.turns_done = np.zeros((W,), np.int32)
        self.slot_sel = np.zeros((W,), np.int32)   # SEL_* code per slot

        for key in ("admitted", "evicted_converged", "evicted_budget",
                    "quarantined", "dispatches", "pool_turns",
                    "retries_total", "backoffs_total", "dropouts",
                    "drop_msgs", "straggles", "corruptions"):
            self.stats.setdefault(key, 0)

    # -- submission ---------------------------------------------------------

    def submit(self, shards: Sequence[Tuple[np.ndarray, np.ndarray]],
               eps: Optional[float] = None,
               selector: Optional[str] = None, seed: int = 0) -> int:
        """Queue one protocol instance (k ragged shards, padded here to the
        pool's pinned (k, n_pad, d) shape).  Returns the session id.

        ``selector`` picks the session's protocol family on unified pools
        (default: MEDIAN when d=2, else MAXMARG); per-selector pools accept
        only their own.  ``seed`` feeds a SAMPLING session's Vitter chain —
        its ε-net reservoir size (from ``eps``) must fit the pool's pinned
        ``res_cap``.  Neither affects any compile key: both ride the
        pending queue as data."""
        cfg = self.cfg
        if selector is None:
            selector = (cfg.selector if cfg.selector != "unified"
                        else ("median" if cfg.d == 2 else "maxmarg"))
        if cfg.selector == "unified":
            if selector not in SELECTOR_CODES:
                raise ValueError(
                    f"unified pools take {sorted(SELECTOR_CODES)}, "
                    f"got {selector!r}")
            if selector == "median" and cfg.d != 2:
                raise ValueError("MEDIAN sessions require a d=2 pool")
        elif selector != cfg.selector:
            raise ValueError(
                f"pool is pinned to selector {cfg.selector!r}; "
                f"mixed traffic needs PoolConfig(selector='unified')")
        if len(shards) != cfg.k:
            raise ValueError(f"expected {cfg.k} shards, got {len(shards)}")
        X = np.zeros((cfg.k, cfg.n_pad, cfg.d), np.float32)
        y = np.zeros((cfg.k, cfg.n_pad), np.int32)
        n_total = 0
        for j, (Xs, ys) in enumerate(shards):
            Xs = np.asarray(Xs)
            ys = np.asarray(ys)
            n = Xs.shape[0]
            if n > cfg.n_pad:
                raise ValueError(
                    f"shard {j} has {n} rows > pinned n_pad={cfg.n_pad}")
            if Xs.shape[1] != cfg.d:
                raise ValueError(f"shard {j} is d={Xs.shape[1]}, "
                                 f"pool is d={cfg.d}")
            if not (np.abs(ys) == 1).all():
                raise ValueError("labels must be +-1")
            X[j, :n] = Xs
            y[j, :n] = ys
            n_total += n
        eps_eff = cfg.eps if eps is None else eps
        budget = int(np.floor(eps_eff * n_total))
        res_cap = 0
        if selector == "sampling":
            res_cap = epsilon_net_size(eps_eff, cfg.d + 1)
            if res_cap > (cfg.res_cap or 0):
                raise ValueError(
                    f"SAMPLING session needs a {res_cap}-row reservoir, "
                    f"pool pins res_cap={cfg.res_cap} (lower eps at "
                    f"construction or raise PoolConfig.res_cap)")
        sid = self._next_sid
        self._next_sid += 1
        self.pending.append(_Pending(sid, X, y, budget,
                                     selector=selector, seed=seed,
                                     res_cap=res_cap))
        self.sessions[sid] = {
            "status": ST_PENDING, "selector": selector,
            "retries": 0, "backoffs": 0,
            "dropouts": 0, "drop_msgs": 0, "straggles": 0,
            "corrupt_kind": -1, "quarantine_reason": None,
            "admitted_turn": -1, "evicted_turn": -1, "turns": 0,
        }
        return sid

    def session(self, sid: int) -> Dict[str, Any]:
        return self.sessions[sid]

    # -- internals ----------------------------------------------------------

    def _admit(self):
        """Refill empty slots from the pending queue in FIFO order, in
        fixed ``admit_block``-sized scatter waves (tail slots carry the
        out-of-range index W, dropped on device)."""
        free = np.flatnonzero(self.slot_state == SLOT_EMPTY)
        n = min(len(self.pending), free.size)
        if not n:
            return
        now = time.perf_counter()
        wait_s = sum(now - p.submitted
                     for p in itertools.islice(self.pending, n))
        waves = -(-n // self.cfg.admit_block)
        with jax.profiler.TraceAnnotation(
                "pool.admit", rows=n, nbytes=waves * self._wave_nbytes,
                wait_us=round(wait_s * 1e6)):
            self._admit_waves(free)

    def _admit_waves(self, free: np.ndarray):
        cfg = self.cfg
        W, A = cfg.slots, cfg.admit_block
        while self.pending and free.size:
            take = min(len(self.pending), free.size, A)
            batch = [self.pending.popleft() for _ in range(take)]
            slots = free[:take]
            free = free[take:]

            dblk = EngineData(
                np.stack([p.X for p in batch]),
                np.stack([p.y for p in batch]),
                np.asarray([p.budget for p in batch], np.int32))
            if take < A:   # pad the pinned block; tail rows scatter-drop
                dblk = EngineData(
                    np.concatenate([dblk.X,
                                    np.zeros((A - take,) + dblk.X.shape[1:],
                                             np.float32)]),
                    np.concatenate([dblk.y,
                                    np.zeros((A - take,) + dblk.y.shape[1:],
                                             np.int32)]),
                    np.concatenate([dblk.budget,
                                    np.zeros((A - take,), np.int32)]))
            if cfg.selector == "median":
                fresh = _fresh_state_median(A, cfg, live=take)
            elif cfg.selector == "unified":
                fresh = _fresh_state_unified(A, cfg, live=take, batch=batch)
            else:
                fresh = _fresh_state_maxmarg(A, cfg, live=take)
            idx = np.full((A,), W, np.int32)
            idx[:take] = slots
            self.data, self.state = _admit_rows(
                self.data, self.state, jnp.asarray(idx), dblk, fresh)

            for p, s in zip(batch, slots):
                self.sid[s] = p.sid
                self.slot_sel[s] = SELECTOR_CODES[p.selector]
                self.slot_state[s] = SLOT_LIVE
                self.retries[s] = 0
                self.backoff_until[s] = 0
                self.straggle_until[s] = 0
                self.prev_fill[s] = 0
                self.turns_done[s] = 0
                rec = self.sessions[p.sid]
                rec["status"] = ST_LIVE
                rec["admitted_turn"] = self.pool_turn
                self.stats["admitted"] += 1

    def _dispatch(self, rows: np.ndarray):
        """One mixed-phase turn over the given slot rows, always at the
        pool's SINGLE pinned dispatch key: the full ``slots``-sized index
        block (inactive tail = out-of-range W, dropped by the scatter) and
        the full ``cap`` transcript width.

        Pinning one key — rather than reusing the sweeps' fill-capped width
        and batch-size buckets — is a deliberate robustness/perf trade.
        XLA fuses the per-turn scans differently at different shapes (e.g.
        the stage-5 extremes reduction picks up FMA contraction at some
        widths), which perturbs separator floats by ulps across compile
        keys even though every decision is identical.  A service cannot
        let *which sessions happen to cohabit a batch* leak into results:
        with one key, every turn of every session runs the exact same
        compiled program, so chaos runs, fault-free runs, restored runs
        and differently-streamed runs are bit-exact per session BY
        CONSTRUCTION.  A saturated pool (the steady state the service
        optimizes for) dispatches a full block anyway, so the cost is
        confined to drain tails and the worst-case transcript width.  The
        key is appended to ``hotloop.KEY_LOG`` at the pool's first dispatch
        (it never changes after), so the recompile gates cover pool traffic
        too while a long-lived pool's log stays bounded."""
        cfg = self.cfg
        block = _round_up(cfg.slots, hotloop.BATCH_MULT)  # turn_call's idx
        if not self._key_logged:
            hotloop.KEY_LOG.append((block, cfg.cap, False, False))
            self._key_logged = True
        with jax.profiler.TraceAnnotation("pool.dispatch", rows=rows.size,
                                          block=block):
            fn, args, kw = self.turn_call(rows)
            self.state = fn(*args, **kw)
        self.stats["dispatches"] += 1

    def turn_call(self, rows: np.ndarray):
        """The pinned dispatch of one pool turn over slot ``rows``, as
        ``(jitted turn, operands, static keywords)``: ``fn(*args, **kw)``
        is the turn ``_dispatch`` runs, and ``fn.lower(*args, **kw)`` is
        the program every pool turn of this pool executes."""
        cfg = self.cfg
        W = cfg.slots
        idx = np.full((_round_up(W, hotloop.BATCH_MULT),), W, np.int32)
        idx[:rows.size] = rows
        tail = (self.state, jnp.asarray(idx), jnp.int32(rows.size))
        width = cfg.cap
        if cfg.selector == "median":
            return median._hot_turn, (self.data, self._V) + tail, dict(
                k=cfg.k, first_turn=False, cut_kernel=False,
                extremes_kernel=False, trans_width=width)
        solver = dict(k=cfg.k, max_support=cfg.max_support,
                      steps=cfg.svm_steps, stages=cfg.svm_stages,
                      lam0=cfg.lam0, trans_width=width, warm=False,
                      per_node=False, fused_kernel=False,
                      solver_kernel=self._solver_kernel)
        if cfg.selector == "unified":
            return unified._hot_turn, (self.data, self._V) + tail, dict(
                solver, has_median=(cfg.d == 2), first_turn=False,
                cut_kernel=False, extremes_kernel=False)
        return maxmarg._hot_turn, (self.data,) + tail, solver

    def _corrupt(self, rows: np.ndarray, kinds: np.ndarray):
        """Post-turn corruption wave at the pinned ``corrupt_block`` shape
        (multiple waves if the draw hit more rows than one block holds)."""
        C = self.cfg.corrupt_block
        W = self.cfg.slots
        fn = {"median": _corrupt_median, "maxmarg": _corrupt_maxmarg,
              "unified": _corrupt_unified}[self.cfg.selector]
        for off in range(0, rows.size, C):
            idx = np.full((C,), W, np.int32)
            knd = np.full((C,), -1, np.int32)
            chunk = slice(off, min(off + C, rows.size))
            take = rows[chunk].size
            idx[:take] = rows[chunk]
            knd[:take] = kinds[chunk]
            self.state = fn(self.state, jnp.asarray(idx), jnp.asarray(knd))

    def _quarantine(self, slot: int, reason: str):
        self.slot_state[slot] = SLOT_QUARANTINED
        rec = self.sessions[self.sid[slot]]
        rec["status"] = ST_QUARANTINED
        rec["quarantine_reason"] = reason
        self.stats["quarantined"] += 1

    def _evict(self, slots: np.ndarray):
        """Free finished/quarantined slots, extracting results for sessions
        that terminated cleanly.  One batched device->host transfer of the
        small per-slot result leaves per eviction wave."""
        from repro.core import classifiers as clf
        from repro.core.protocols.one_way import ProtocolResult

        cfg = self.cfg
        s = self.state
        if cfg.selector == "median":
            w_np = -np.asarray(s.h_v, np.float64)
            b_np = np.asarray(s.h_t, np.float64)
        else:
            w_np = np.asarray(s.h_w, np.float64)
            b_np = np.asarray(s.h_b, np.float64)
            if cfg.selector == "unified":
                # shared-leaf convention: MEDIAN rows store h_v in h_w and
                # recover LinearSeparator(-h_v, h_t) at extraction
                w_np[self.slot_sel == SEL_MEDIAN] *= -1.0
        epochs = np.asarray(s.epochs)
        conv = np.asarray(s.converged)
        comm_np = type(s.comm)(*(np.asarray(a) for a in s.comm))

        for slot in slots:
            sid = int(self.sid[slot])
            rec = self.sessions[sid]
            quarantined = self.slot_state[slot] == SLOT_QUARANTINED
            if not quarantined:
                converged = bool(conv[slot])
                rec["status"] = ST_CONVERGED if converged else ST_BUDGET
                self.stats["evicted_converged" if converged
                           else "evicted_budget"] += 1
                h = clf.LinearSeparator(w_np[slot], float(b_np[slot]))
                sel_name = (SELECTOR_NAMES[int(self.slot_sel[slot])]
                            if cfg.selector == "unified" else cfg.selector)
                self.results[sid] = ProtocolResult(
                    h,
                    comm_np.summary(int(slot), dim=cfg.d),
                    rounds=(int(epochs[slot]) if converged
                            else cfg.max_epochs),
                    converged=converged,
                    extra={"engine": True, "session_pool": True,
                           "selector": sel_name, "sid": sid,
                           "retries": rec["retries"],
                           "backoffs": rec["backoffs"]},
                )
            rec["evicted_turn"] = self.pool_turn
            rec["turns"] = int(self.turns_done[slot])
            self.sid[slot] = -1
            self.slot_sel[slot] = 0
            self.slot_state[slot] = SLOT_EMPTY
        # freed rows stay in the device state until an admission overwrites
        # them; mark them done so a stale gather can never dispatch them
        # (fixed full-width index block: one compile key for any wave size)
        if slots.size:
            W = cfg.slots
            idx = np.full((_round_up(W, cfg.admit_block),), W, np.int32)
            idx[:slots.size] = slots
            self.state = _mark_done(self.state, jnp.asarray(idx))

    # -- the pool turn ------------------------------------------------------

    @functools.partial(jax.profiler.annotate_function, name="pool.step")
    def step_pool(self):
        """One pool turn: admit → draw faults → dispatch survivors →
        corrupt → screen invariants → quarantine/evict → checkpoint."""
        cfg = self.cfg
        t = self.pool_turn
        self._admit()

        live = self.slot_state == SLOT_LIVE
        ready = live & (self.backoff_until <= t) & (self.straggle_until <= t)
        cand = np.flatnonzero(ready)

        dispatched = np.empty((0,), np.int64)
        corrupt_rows = np.empty((0,), np.int64)
        corrupt_kinds = np.empty((0,), np.int32)
        if cand.size:
            draws = self.schedule.draws(self.sid[cand], t)
            aborted = draws["dropout"] | draws["drop_msg"]
            straggle = (~aborted) & (draws["straggle"] > 0)
            go = ~aborted & ~straggle

            for i in np.flatnonzero(aborted):
                slot = cand[i]
                rec = self.sessions[self.sid[slot]]
                which = "dropouts" if draws["dropout"][i] else "drop_msgs"
                rec[which] += 1
                self.stats[which] += 1
                self.retries[slot] += 1
                rec["retries"] += 1
                self.stats["retries_total"] += 1
                if self.retries[slot] > cfg.retry_budget:
                    self._quarantine(slot, "retry_budget")
                else:
                    self.backoff_until[slot] = (
                        t + 1 + cfg.backoff_base
                        * (1 << (int(self.retries[slot]) - 1)))
                    rec["backoffs"] += 1
                    self.stats["backoffs_total"] += 1

            for i in np.flatnonzero(straggle):
                slot = cand[i]
                self.straggle_until[slot] = t + 1 + int(draws["straggle"][i])
                self.sessions[self.sid[slot]]["straggles"] += 1
                self.stats["straggles"] += 1

            dispatched = cand[go]
            if dispatched.size:
                self._dispatch(dispatched)
                self.retries[dispatched] = 0
                self.turns_done[dispatched] += 1
                for slot in dispatched:
                    self.sessions[self.sid[slot]]["turns"] = \
                        int(self.turns_done[slot])

            hit = go & (draws["corrupt"] >= 0)
            if hit.any():
                corrupt_rows = cand[hit]
                corrupt_kinds = draws["corrupt"][hit].astype(np.int32)
                self._corrupt(corrupt_rows, corrupt_kinds)
                self.stats["corruptions"] += int(corrupt_rows.size)
                for slot, kind in zip(corrupt_rows, corrupt_kinds):
                    self.sessions[self.sid[slot]]["corrupt_kind"] = int(kind)

        # -- supervision screen (one (5, W) transfer) -----------------------
        viewer = {"median": _view_median, "maxmarg": _view_maxmarg,
                  "unified": _view_unified}[cfg.selector]
        with jax.profiler.TraceAnnotation("pool.view"):
            view = np.asarray(viewer(self.state))
        done, conv, fills, nan, bits = view
        live = self.slot_state == SLOT_LIVE       # minus fresh quarantines

        for slot in np.flatnonzero(live & (nan > 0)):
            self._quarantine(int(slot), "nan_separator")
        for slot in np.flatnonzero(live & (bits > cfg.comm_limit_bits)):
            if self.slot_state[slot] == SLOT_LIVE:
                self._quarantine(int(slot), "comm_blowout")
        disp_mask = np.zeros_like(live)
        disp_mask[dispatched] = True
        # every healthy continuing turn strictly grows some transcript, so
        # a dispatched live row whose max fill dropped, or failed to go (and
        # stay) positive, is corrupt
        bad_fill = disp_mask & live & (done == 0) \
            & ((fills < self.prev_fill) | (fills == 0))
        for slot in np.flatnonzero(bad_fill):
            if self.slot_state[slot] == SLOT_LIVE:
                self._quarantine(int(slot), "fill_regression")

        live = self.slot_state == SLOT_LIVE
        self.prev_fill[live] = np.maximum(self.prev_fill[live], fills[live])

        evict = np.flatnonzero(
            (self.slot_state == SLOT_QUARANTINED)
            | (live & (done > 0))
            | (live & (self.turns_done >= cfg.max_turns)))
        if evict.size:
            with jax.profiler.TraceAnnotation("pool.evict", rows=evict.size):
                self._evict(evict)

        self.pool_turn += 1
        self.stats["pool_turns"] += 1
        if (cfg.checkpoint_every
                and self.pool_turn % cfg.checkpoint_every == 0):
            self.checkpoint(cfg.checkpoint_dir)

    def drained(self) -> bool:
        return not self.pending and not (self.slot_state == SLOT_LIVE).any()

    def run(self, max_pool_turns: Optional[int] = None) -> Dict[int, Any]:
        """Drive pool turns until every submitted session reaches a
        terminal status (or ``max_pool_turns`` elapse).  Returns the
        results ledger (sid -> ProtocolResult for cleanly-finished
        sessions; quarantined sids appear only in :meth:`session`)."""
        cfg = self.cfg
        if max_pool_turns is None:
            # worst case: every session serially pays its full turn budget
            # plus a full retry cycle's backoff per turn — generous, finite
            per_turn = 2 + cfg.backoff_base * (2 ** (cfg.retry_budget + 1)) \
                + self.schedule.straggle_max
            n_sessions = len(self.pending) + int(
                (self.slot_state != SLOT_EMPTY).sum())
            waves = max(1, -(-max(n_sessions, 1) // cfg.slots))
            max_pool_turns = max(64, waves * cfg.max_turns * per_turn)
        deadline = self.pool_turn + max_pool_turns
        while not self.drained() and self.pool_turn < deadline:
            self.step_pool()
        if not self.drained():
            raise RuntimeError(
                f"pool failed to drain within {max_pool_turns} pool turns "
                f"({(self.slot_state == SLOT_LIVE).sum()} live, "
                f"{len(self.pending)} pending)")
        return self.results

    # -- checkpoint / restore ----------------------------------------------

    def checkpoint(self, dirname: str) -> str:
        """Snapshot the whole pool — device trees, host supervision arrays,
        pending queue, session ledger, config+schedule — as one flat-key
        ``.npz`` plus a JSON manifest (the ``train/checkpoint.py`` idiom).
        The fault schedule is stateless, so the snapshot fully determines
        the remaining run."""
        from repro.train.checkpoint import _flatten

        os.makedirs(dirname, exist_ok=True)
        flat = _flatten({"data": self.data, "state": self.state})
        flat.update({
            "host/sid": self.sid, "host/slot_state": self.slot_state,
            "host/retries": self.retries,
            "host/backoff_until": self.backoff_until,
            "host/straggle_until": self.straggle_until,
            "host/prev_fill": self.prev_fill,
            "host/turns_done": self.turns_done,
            "host/slot_sel": self.slot_sel,
        })
        if self.pending:
            flat["pending/sid"] = np.asarray([p.sid for p in self.pending])
            flat["pending/X"] = np.stack([p.X for p in self.pending])
            flat["pending/y"] = np.stack([p.y for p in self.pending])
            flat["pending/budget"] = np.asarray(
                [p.budget for p in self.pending], np.int32)
            flat["pending/selector"] = np.asarray(
                [SELECTOR_CODES[p.selector] for p in self.pending], np.int32)
            flat["pending/seed"] = np.asarray(
                [p.seed for p in self.pending], np.int64)
            flat["pending/res_cap"] = np.asarray(
                [p.res_cap for p in self.pending], np.int32)
        path = os.path.join(dirname, f"pool_{self.pool_turn:08d}.npz")
        np.savez(path, **flat)

        results_json = {}
        for sid, r in self.results.items():
            results_json[str(sid)] = {
                "w": np.asarray(r.classifier.w, np.float64).tolist(),
                "b": float(r.classifier.b),
                "comm": r.comm, "rounds": r.rounds,
                "converged": r.converged, "extra": r.extra,
            }
        manifest = {
            "path": path,
            "pool_turn": self.pool_turn,
            "next_sid": self._next_sid,
            "config": dataclasses.asdict(self.cfg),
            "schedule": self.schedule.to_json(),
            "sessions": {str(k): v for k, v in self.sessions.items()},
            "results": results_json,
            "stats": {k: v for k, v in self.stats.items()
                      if isinstance(v, (int, float, str))},
        }
        with open(os.path.join(dirname, "latest.json"), "w") as f:
            json.dump(manifest, f)
        return path

    @classmethod
    def restore(cls, dirname: str) -> "SessionPool":
        """Rebuild a pool mid-stream from :meth:`checkpoint` output.
        Unaffected sessions resume bit-exact: device state re-uploads
        verbatim, supervision arrays and the stateless fault schedule
        replay the identical decision sequence."""
        from repro.core import classifiers as clf
        from repro.core.protocols.one_way import ProtocolResult

        with open(os.path.join(dirname, "latest.json")) as f:
            man = json.load(f)
        cfg = PoolConfig(**man["config"])
        pool = cls(cfg, F.FaultSchedule.from_json(man["schedule"]))
        z = np.load(man["path"])

        def leaf(tree, prefix):
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
            keys = ["/".join(str(getattr(kk, "key", getattr(kk, "idx", kk)))
                             for kk in path) for path, _ in flat]
            vals = [jnp.asarray(z[f"{prefix}/{key}"]) for key in keys]
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(tree), vals)

        pool.data = leaf(pool.data, "data")
        pool.state = leaf(pool.state, "state")
        pool.sid = z["host/sid"]
        pool.slot_state = z["host/slot_state"]
        pool.retries = z["host/retries"]
        pool.backoff_until = z["host/backoff_until"]
        pool.straggle_until = z["host/straggle_until"]
        pool.prev_fill = z["host/prev_fill"]
        pool.turns_done = z["host/turns_done"]
        pool.slot_sel = z["host/slot_sel"]
        if "pending/sid" in z.files:
            for i, sid in enumerate(z["pending/sid"]):
                pool.pending.append(_Pending(
                    int(sid), z["pending/X"][i], z["pending/y"][i],
                    int(z["pending/budget"][i]),
                    selector=SELECTOR_NAMES[int(z["pending/selector"][i])],
                    seed=int(z["pending/seed"][i]),
                    res_cap=int(z["pending/res_cap"][i])))
        pool.pool_turn = man["pool_turn"]
        pool._next_sid = man["next_sid"]
        pool.sessions = {int(k): v for k, v in man["sessions"].items()}
        for sid, r in man["results"].items():
            pool.results[int(sid)] = ProtocolResult(
                clf.LinearSeparator(np.asarray(r["w"]), r["b"]),
                r["comm"], rounds=r["rounds"], converged=r["converged"],
                extra=r["extra"])
        for k, v in man["stats"].items():
            pool.stats[k] = v
        return pool


@jax.jit
def _mark_done(state, idx):
    """Pin freed slots done on device (out-of-range tail drops)."""
    return state._replace(
        done=state.done.at[idx].set(True),
        converged=state.converged.at[idx].set(False))
