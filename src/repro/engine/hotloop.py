"""Selector-generic host-driven hot loop (DESIGN.md §shared hot loop).

Both two-way selectors share the same transcript-driven round structure —
and therefore the same per-round waste: a ``lax.while_loop`` sweep must run
every turn at the worst-case transcript width with every instance still in
the batch.  This module owns the machinery that removes it, extracted from
the MAXMARG-only PR 4 implementation so the MEDIAN selector (and any future
transcript-driven selector) rides the identical code path:

* **host-driven turn loop** — drive the selector's jitted ``step`` one turn
  at a time so shapes can change between turns (a while_loop cannot);
* **packed host transfers** — everything the host needs per turn (done
  flags, warm-carry flags, live transcript fills) crosses as one (3, B)
  int32 array;
* **width compaction** — the per-turn transcript reads run at
  ``round_up(max live fill + slack, 8)`` rows instead of the static
  capacity (widths are monotone, so a sweep compiles a handful of step
  variants that later sweeps of the same shape reuse);
* **batch compaction** — finished instances drop out of the dispatch: the
  live set rounds up to a multiple of 4 and pads with *out-of-range*
  indices, which JAX gathers fill with inert zero rows and JAX scatters
  drop, so the live count stays a traced value and the compile cache keys
  only on ``(n_pad, width, warm)``;
* **warm-carry threading** — the host reads the selector's per-turn
  warm-latch flags and skips the polish dispatch on turns where no live
  instance can latch;
* **sharded dispatch** (DESIGN.md §sharded hot loop) — with ``shards=S``
  the per-turn sub-batch index is built *per shard* (``balanced_index``):
  the live set splits into S local slices padded to a common multiple of
  ``BATCH_MULT``, so every device runs the same shapes and none idles while
  another runs live rows; the selector's sharded dispatches map them over a
  1-D ("data",) mesh;
* **double buffering** (``overlap=True``) — turn t+1 is dispatched from the
  one-turn-*stale* host view before the host blocks on turn t's view
  decode, overlapping host decision logic with device compute.  Sound
  because ``done`` is monotone (stale active sets are supersets whose extra
  rows are masked no-ops) and the stale fill plus the selector's
  ``width_growth`` bound covers the true fill; at most one wasted all-done
  masked dispatch runs at termination.

The selector supplies three callables (see :func:`run_hot`); everything it
must guarantee about padding rows is the engine's standing label-0
convention plus a ``pad_fix`` that marks gathered out-of-range rows inert
(``done=True``, and for warm selectors: carries trusted, so zero-data pad
rows latch instantly and can never force solver work the live rows don't
need).
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.engine.state import _round_up, shard_specs  # noqa: F401 (re-export)

BATCH_MULT = 4   # live batch rounds up to this (compile-cache granularity)
WIDTH_MULT = 8   # live transcript width rounds up to this

# every compacted dispatch appends its compile-cache key here:
# (n_pad, width, use_warm, first_turn) with n_pad = B for full-batch turns.
# tests/test_recompile.py pins that the number of step lowerings never
# exceeds the distinct keys — i.e. the cache keys on (n_pad, width, warm)
# only, and shard-aware padding can't silently reintroduce per-turn
# recompiles.  Bounded observability: the driver clears it per sweep-test.
KEY_LOG: List[Tuple[int, int, bool, bool]] = []


def quantize_width(w: int, cap: int, policy: str = "linear") -> int:
    """Round a live transcript width up to a dispatchable bucket.

    ``"linear"`` is the classic rule — ``min(cap, round_up(w, WIDTH_MULT))``
    — and is byte-identical to what every hot path shipped before the policy
    knob existed.  ``"geometric"`` rounds up to the next bucket of the
    series 8, 16, 24, 40, 64, 96, 144, ... (each ≈1.5× the last, re-rounded
    to ``WIDTH_MULT``): mixed-selector traffic spreads live fills across
    families with very different transcript growth, and linear rounding then
    visits O(cap / WIDTH_MULT) distinct widths — each a fresh compile —
    where geometric rounding visits O(log cap) at ≤ 50% padding waste
    (DESIGN.md §unified mixed-selector state).

    Both policies preserve ``w = 0`` exactly: a zero width is meaningful
    (MAXMARG's empty-transcript first turn compiles a skip-concat branch)
    and must not be promoted into a padded nonzero bucket.
    """
    w = min(cap, _round_up(w, WIDTH_MULT))
    if policy == "linear" or w <= WIDTH_MULT:
        return w
    if policy != "geometric":
        raise ValueError(f"unknown width policy {policy!r}")
    b = WIDTH_MULT
    while b < w:
        b = _round_up((b * 3) // 2, WIDTH_MULT)
    return min(cap, b)


def gather_rows(arr, idx):
    """arr (B, N, ...), idx (B,) -> (B, ...): per-instance row gather.

    The engine's turn counter is per-instance, so the coordinator index
    ``ci = turn % k`` is a (B,) vector and every "the coordinator's shard /
    transcript" access is this vmapped gather rather than a shared-axis
    ``jnp.take``.  Gathers are exact, so vectorizing ci changes no float."""
    return jax.vmap(lambda a, i: a[i])(arr, idx)


def take_instances(tree, idx):
    """Gather instance rows ``idx`` from every (B, ...) leaf (scalar leaves —
    the shared turn counter — pass through).  Out-of-range indices gather
    zero-filled rows: an all-label-0 instance is the engine's inert element
    (no valid rows ⇒ every masked selection is empty, every masked reduction
    hits its identity), which is exactly what a hot turn's padding rows must
    be."""
    return jax.tree_util.tree_map(
        lambda a: a if a.ndim == 0
        else jnp.take(a, idx, axis=0, mode="fill", fill_value=0), tree)


def put_instances(full, sub, idx):
    """Scatter ``sub`` rows back into ``full`` at ``idx`` (scalar leaves take
    the sub value — the advanced turn counter).  Padding rows carry an
    out-of-range index, which a JAX scatter *drops*, so they never land."""
    return jax.tree_util.tree_map(
        lambda f, s: s if f.ndim == 0 else f.at[idx].set(s), full, sub)


def gathered_turn(step_fn, pad_fix, data, state, idx, n_act):
    """One compacted turn as gather → pad-fix → step → scatter.

    The selector wraps this in its own ``jax.jit`` (its static options
    differ), so the whole turn stays one device computation: eager per-leaf
    gathers/scatters cost more than the step they wrap on CPU.  ``idx`` is
    (n_pad,) i32 with the live rows in front and out-of-range tail indices;
    ``n_act`` is the traced live count; ``pad_fix(sub_state, pad_row)``
    marks the gathered tail rows inert for this selector.
    """
    sub_data = take_instances(data, idx)
    sub = take_instances(state, idx)
    pad_row = jnp.arange(idx.shape[0]) >= n_act
    sub = pad_fix(sub, pad_row)
    sub = step_fn(sub_data, sub)
    return put_instances(state, sub, idx)


def shard_skew(counts: np.ndarray) -> float:
    """Imbalance of a per-shard live-count vector as the max/mean ratio.

    1.0 is perfectly balanced; S (the shard count) means one shard owns the
    whole live set.  The common padded length L in :func:`balanced_index`
    is set by the *max* count, so every device pays the skewed shard's
    shapes — this ratio is exactly the padding-waste factor and the signal
    any future cross-shard rebalancing must drive down (ROADMAP).  An
    all-dead vector reports 0.0 (no dispatch, no waste)."""
    counts = np.asarray(counts, dtype=np.float64)
    mean = counts.mean() if counts.size else 0.0
    if mean <= 0:
        return 0.0
    return float(counts.max() / mean)


def balanced_index(act: np.ndarray, B: int, shards: int):
    """Shard-balanced compacted index for a sharded sub-batch dispatch.

    Splits the sorted global active set into per-shard *local* index slices
    (shard s owns global rows ``[s·B/S, (s+1)·B/S)``), pads every slice to
    the common ``L = round_up(max per-shard live count, BATCH_MULT)`` with
    the out-of-range index B (gather-fill / scatter-drop, same convention
    as the single-device tail), and returns ``(idx, n_act)``: ``idx`` is
    (S·L,) i32 — shard s's slice at ``idx[s·L:(s+1)·L]`` — and ``n_act`` is
    the (S,) per-shard live count the sharded dispatch reads locally.  The
    common L is the balance contract: every device runs the same compacted
    shapes, so none idles while another runs live rows, and the compile
    cache keys on L exactly like the single-device path keys on n_pad.
    """
    B_loc = B // shards
    shard_of = act // B_loc
    counts = np.bincount(shard_of, minlength=shards).astype(np.int32)
    L = max(BATCH_MULT, _round_up(int(counts.max()), BATCH_MULT))
    idx = np.full((shards, L), B, np.int32)
    local = (act - shard_of * B_loc).astype(np.int32)
    offs = np.concatenate([[0], np.cumsum(counts)])
    for s in range(shards):          # act is sorted -> slices stay ordered
        idx[s, :counts[s]] = local[offs[s]:offs[s + 1]]
    return idx.reshape(-1), counts


def run_hot(
    state,
    *,
    k: int,
    max_turns: int,
    cap: int,
    host_view: Callable,      # (state, ci) -> (3, B) i32 [done, warm, fill]
    dispatch_full: Callable,  # (state, *, t, width, use_warm) -> state
    dispatch_sub: Callable,   # (state, idx, n_act, *, t, width, use_warm)
    warm: bool = False,
    compact: bool = True,
    width_slack: int = 0,
    width_growth: int = 0,
    width_policy: str = "linear",
    overlap: bool = False,
    shards: Optional[int] = None,
    stats: Optional[dict] = None,
):
    """The generic host-driven sweep loop over a selector's jitted ``step``.

    ``host_view`` must be jitted and return the packed per-turn host
    knowledge: row 0 done flags, row 1 warm-latch flags for the upcoming
    coordinator ``ci`` (all zero for selectors without a warm carry), row 2
    the transcript fills the width compaction keys on.  ``width_slack``
    widens the compacted read past the turn-start fill — a selector whose
    step *reads* transcripts after appending to them (MEDIAN's post-S
    extremes scan) passes the per-turn append bound.  ``width_policy``
    selects the :func:`quantize_width` bucketing rule ("linear" default,
    "geometric" for mixed-width traffic where linear rounding would churn
    the compile cache).

    ``dispatch_full`` runs the whole batch at a compacted ``width``
    (``None`` on the non-compacted path); ``dispatch_sub`` additionally
    gathers the ``idx`` rows and scatters them back (see
    :func:`gathered_turn`).  ``t`` is the host-known turn index, from which
    a selector derives host-static flags (MEDIAN's constant-folded first
    turn).

    Donation contract: the dispatches MAY donate their ``state`` argument
    (the sharded path does — the scatter-back then reuses the transcript
    buffers in place instead of copying them every turn).  The loop keeps a
    strict single-consumer chain: each state handle is passed to exactly
    one dispatch, and the ``host_view`` of a handle is always enqueued
    before the dispatch that donates it.

    ``shards=S`` routes sub-batch turns through :func:`balanced_index` —
    ``dispatch_sub`` then receives the (S·L,) per-shard index block and the
    (S,) per-shard live counts instead of a flat prefix index.

    ``overlap=True`` double-buffers the loop: after dispatching turn t from
    a fresh view, turn t+1 is dispatched immediately from the same —
    now one-turn-stale — view before the host blocks on turn t's view
    decode.  Stale parameters are always sound: ``done`` is monotone, so
    the stale active set is a superset whose extra rows are masked no-ops,
    and the stale fill plus the selector's ``width_growth`` (its worst-case
    one-turn transcript growth) covers the true fill.  MEDIAN results stay
    bit-exact (any covering width is); warm selectors may make different —
    equally valid — polish-skip choices, which is decision-preserving (the
    warm gate re-checks on device).  At most one wasted all-done masked
    dispatch runs at termination.

    ``stats`` (optional dict) collects host-side observability, never read
    for decisions.  Every sweep counts ``stats["turns"]`` (dispatches),
    ``stats["live_rows"]`` (Σ live instances over dispatches),
    ``stats["dispatched_rows"]`` (Σ rows the dispatches ran: B for a
    full-batch turn, S·L for a sharded sub-batch turn, ``n_pad``
    unsharded), ``stats["view_wait_s"]`` (host seconds blocked decoding
    the host view) and ``stats["stage_shapes"]``, the count of dispatches
    per ``(L, width, warm)`` with L the rows a device ran (the shape its
    solver stages ran at).  On sharded sweeps every :func:`balanced_index`
    call also folds its per-shard live-count skew (:func:`shard_skew`)
    into ``stats["shard_skew_max"]`` / ``stats["shard_skew_last"]`` and
    counts dispatches in ``stats["shard_dispatches"]`` — the measurable
    rebalancing signal the ROADMAP's skewed-shard item asks for.

    Tracing: each turn writes ``jax.profiler.TraceAnnotation`` spans, which
    only a running profiler records: ``sweep.turn`` (``live``, ``rows``,
    ``width``) from the turn's dispatch parameters to its view's enqueue,
    holding ``sweep.dispatch`` around the dispatch call, and
    ``sweep.view`` around each blocking decode of a host view.
    """
    B = int(state.done.shape[0])
    # the scatter-drop tail is a host-side constant: every pad slot carries
    # the same out-of-range index B, so build it once, not once per turn
    pad_tail = np.full(B, B, dtype=np.int32)
    # turn is per-instance; a sweep advances every row in lock-step, so the
    # host-side loop counter resumes from the common (max) value
    t = int(np.asarray(state.turn).max(initial=0))

    if stats is not None:
        for key in ("turns", "live_rows", "dispatched_rows"):
            stats.setdefault(key, 0)
        stats.setdefault("view_wait_s", 0.0)
        stats.setdefault("stage_shapes", {})
    S = shards or 1

    def count(live, rows, width, use_warm):
        if stats is not None:
            stats["turns"] += 1
            stats["live_rows"] += live
            stats["dispatched_rows"] += rows
            key = (rows // S, width, use_warm)
            stats["stage_shapes"][key] = stats["stage_shapes"].get(key, 0) + 1

    def decode(vh):
        """The blocking read of a host view, timed into the stats."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("sweep.view"):
            view = np.asarray(vh)
        if stats is not None:
            stats["view_wait_s"] += time.perf_counter() - t0
        return view

    if not compact:
        while t < max_turns:
            done, warm_ok, fills = decode(host_view(state, t % k))
            if bool(done.all()):
                break
            act = np.flatnonzero(done == 0)
            use_warm = warm and t > 0 and bool(warm_ok[act].any())
            count(len(act), B, cap, use_warm)
            with jax.profiler.TraceAnnotation("sweep.turn", live=len(act),
                                              rows=B, width=cap), \
                    jax.profiler.TraceAnnotation("sweep.dispatch"):
                state = dispatch_full(state, t=t, width=None,
                                      use_warm=use_warm)
            t += 1
        return state

    def params(done, warm_ok, fills, t, growth):
        """Dispatch parameters for turn t from a view (``growth`` is the
        extra width slack when the view is one turn stale)."""
        act = np.flatnonzero(done == 0)
        # polish only when it can latch: turn 0 has no carry to polish, and
        # a turn where no live instance's carried separator can latch falls
        # through to the cold anneal anyway — skip the polish dispatch
        use_warm = warm and t > 0 and bool(warm_ok[act].any())
        width = quantize_width(int(fills[act].max(initial=0))
                               + width_slack + growth, cap, width_policy)
        return act, width, use_warm

    def dispatch(state, act, width, use_warm, t):
        """Dispatch turn t and enqueue its host view: ``(state, view)``."""
        n_act = len(act)
        if n_act == B:
            # full batch: the width compaction is the whole win — skip the
            # gather/scatter round-trip entirely
            idx = None
        elif shards:
            idx, n_vec = balanced_index(act, B, shards)
            if stats is not None:
                skew = shard_skew(n_vec)
                stats["shard_skew_last"] = skew
                stats["shard_skew_max"] = max(
                    stats.get("shard_skew_max", 0.0), skew)
                stats["shard_dispatches"] = \
                    stats.get("shard_dispatches", 0) + 1
            n_arg = jnp.asarray(n_vec)
        else:
            n_pad = min(B, _round_up(n_act, BATCH_MULT))
            idx = np.concatenate([act.astype(np.int32),
                                  pad_tail[:n_pad - n_act]])
            n_arg = jnp.int32(n_act)
        rows = B if idx is None else len(idx)
        KEY_LOG.append((rows, width, use_warm, t == 0))
        count(n_act, rows, width, use_warm)
        with jax.profiler.TraceAnnotation("sweep.turn", live=n_act,
                                          rows=rows, width=width):
            with jax.profiler.TraceAnnotation("sweep.dispatch"):
                if idx is None:
                    state = dispatch_full(state, t=t, width=width,
                                          use_warm=use_warm)
                else:
                    state = dispatch_sub(state, jnp.asarray(idx), n_arg,
                                         t=t, width=width,
                                         use_warm=use_warm)
            # enqueue BEFORE donation of this handle (next dispatch)
            return state, host_view(state, (t + 1) % k)

    # one packed transfer per turn for everything the host needs; the seed
    # view is decoded synchronously (nothing to overlap with yet)
    view = decode(host_view(state, t % k))
    while t < max_turns:
        done, warm_ok, fills = view
        if bool(done.all()):
            break
        act, width, use_warm = params(done, warm_ok, fills, t, 0)
        state, vh = dispatch(state, act, width, use_warm, t)
        t += 1
        if overlap and t < max_turns:
            # double buffer: dispatch turn t from the now-stale view before
            # blocking on the decode of turn t-1's view (vh)
            act_s, width_s, warm_s = params(done, warm_ok, fills, t,
                                            width_growth)
            state, vh2 = dispatch(state, act_s, width_s, warm_s, t)
            t += 1
            if bool(decode(vh)[0].all()):
                # the speculated turn ran on an all-done batch: a masked
                # no-op — results are untouched, only the turn counter moved
                break
            view = decode(vh2)
        else:
            view = decode(vh)
    return state
