"""Batched protocol state: pytrees + packing/lowering for the sweep engine.

A *sweep* is B independent MEDIAN/k-party protocol instances (same party
count k, possibly different datasets, shard sizes, error budgets and seeds)
advanced in lock-step by one compiled ``step``.  Everything lives in fixed
static shapes:

* shards are padded to a common ``n_max`` with **label-0 rows** (the same
  zero-label padding convention the Pallas kernels use — padding rows are
  inert in every masked reduction);
* per-node transcript buffers have static capacity ``cap`` plus a fill
  counter; rows at or beyond the fill always carry label 0, so a transcript
  is valid under the same convention without ever being compacted;
* communication is accounted in :class:`BatchCommLog` — per-instance integer
  arrays updated on device exactly where the metered :class:`~repro.core.comm`
  channels would record a message, and lowered to ``CommLog.summary()``-shaped
  dicts at the end (the metered-channel invariant: costs are measured by the
  data plane itself, never re-derived).

See DESIGN.md §"Batched engine" for the capacity bound and padding rules.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import os
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.comm import wire_bytes
from repro.core.sampling import EPSILON_NET_C, epsilon_net_size

# static per-instance selector codes for the unified mixed-selector state
# (DESIGN.md §unified mixed-selector state).  The codes live in a *traced*
# (B,) i32 leaf — mixing selectors changes data, never the compiled program —
# and 0 doubles as the inert value gather-filled into padding rows (a
# label-0 MEDIAN row is the engine's no-op instance).
SEL_MEDIAN = 0
SEL_MAXMARG = 1
SEL_SAMPLING = 2
SELECTOR_CODES = {"median": SEL_MEDIAN, "maxmarg": SEL_MAXMARG,
                  "sampling": SEL_SAMPLING}
SELECTOR_NAMES = {v: k for k, v in SELECTOR_CODES.items()}


class BatchCommLog(NamedTuple):
    """Vectorized communication ledger: one integer counter per instance.

    Mirrors :class:`repro.core.comm.CommStats` field-for-field; ``rounds``
    counts protocol *turns* exactly like ``CommLog.new_round()``.
    """

    points: jnp.ndarray    # (B,) i32
    scalars: jnp.ndarray   # (B,) i32
    bits: jnp.ndarray      # (B,) i32
    messages: jnp.ndarray  # (B,) i32
    rounds: jnp.ndarray    # (B,) i32

    @staticmethod
    def zeros(batch: int) -> "BatchCommLog":
        z = jnp.zeros((batch,), jnp.int32)
        return BatchCommLog(z, z, z, z, z)

    def summary(self, i: int, dim: int) -> Dict[str, Any]:
        """Lower instance ``i`` to the exact dict ``CommLog.summary()`` emits."""
        p = int(self.points[i])
        s = int(self.scalars[i])
        b = int(self.bits[i])
        return {
            "points": p,
            "scalars": s,
            "bits": b,
            "messages": int(self.messages[i]),
            "rounds": int(self.rounds[i]),
            "bytes": wire_bytes(p, s, b, dim),
        }

    def summaries(self, dim: int) -> List[Dict[str, Any]]:
        return [self.summary(i, dim) for i in range(self.points.shape[0])]


class ProtocolState(NamedTuple):
    """Per-instance protocol state advanced by ``median.step`` (a pytree).

    All leading axes are the batch axis B, including ``turn``: the
    coordinator index ``ci = turn % k`` is *per-instance*, so one dispatch
    may mix sessions at different protocol phases (the streaming session
    pool admits into freed slots mid-stream).  A lock-step sweep keeps
    every row's turn identical — ``step`` advances all of them together —
    so the sweep paths behave exactly like the old shared scalar counter.
    """

    dir_ok: jnp.ndarray     # (B, m) bool — allowed direction arc
    wx: jnp.ndarray         # (B, k, cap, d) f32 — per-node transcript points
    wy: jnp.ndarray         # (B, k, cap) i32 — transcript labels (0 = empty)
    w_fill: jnp.ndarray     # (B, k) i32 — transcript fill counters
    lo_w: jnp.ndarray       # (B, k, m) f32 — running per-node threshold lo
    hi_w: jnp.ndarray       # (B, k, m) f32 — running per-node threshold hi
    turn: jnp.ndarray       # (B,) i32 — per-instance turn counter
    done: jnp.ndarray       # (B,) bool
    converged: jnp.ndarray  # (B,) bool
    epochs: jnp.ndarray     # (B,) i32 — 1-based epoch at termination
    h_v: jnp.ndarray        # (B, d) f32 — current hypothesis direction
    h_t: jnp.ndarray        # (B,) f32 — current hypothesis threshold
    h_valid: jnp.ndarray    # (B,) bool
    comm: BatchCommLog


class EngineData(NamedTuple):
    """Per-instance constants (traced inputs to the jitted runner)."""

    X: jnp.ndarray       # (B, k, n_max, d) f32, zero-padded rows
    y: jnp.ndarray       # (B, k, n_max) i32 ±1 (0 = padding row)
    budget: jnp.ndarray  # (B,) i32 — floor(eps * n_total)


class MaxMargState(NamedTuple):
    """Per-instance MAXMARG protocol state advanced by ``maxmarg.step``.

    Same conventions as :class:`ProtocolState` (leading batch axis B,
    per-instance ``turn``, label-0 transcript padding) but no direction grid: the
    MAXMARG selector refits a max-margin separator per turn instead of
    maintaining a consistent-direction arc.  Transcripts hold *received*
    points only (the legacy host loop's ``Node.recv`` — MAXMARG nodes fit on
    own ∪ received, never on a sent-ledger).

    Several fields carry the hot path's perf state between turns (DESIGN.md
    §warm-start & transcript compaction, §shared hot loop): ``w_fill`` is
    the per-instance *live transcript length* per node, from which the
    host-driven runner picks the compacted read width each turn;
    ``h_w``/``h_b``/``h_valid`` hold the latest proposal (the result
    hypothesis, and the init the *single-carry* warm mode polishes); the
    ``(k,)``-leading leaves ``c_w``/``c_b``/``c_valid`` hold each node's
    carried separator — the most recent *proposal that node verified clean*
    on everything it knows — which the default per-node warm mode polishes
    when that node next coordinates, with ``warm_node`` tracking
    incrementally whether the carry still classifies the node's grown
    transcript cleanly (the polish-latch precondition the hot runner's skip
    logic reads).  ``latches`` counts refits whose warm gate passed, purely
    observability (never a protocol decision).
    """

    wx: jnp.ndarray         # (B, k, cap, d) f32 — received-point transcripts
    wy: jnp.ndarray         # (B, k, cap) i32 — transcript labels (0 = empty)
    w_fill: jnp.ndarray     # (B, k) i32 — live transcript length per node
    turn: jnp.ndarray       # (B,) i32 — per-instance turn counter
    done: jnp.ndarray       # (B,) bool
    converged: jnp.ndarray  # (B,) bool
    epochs: jnp.ndarray     # (B,) i32 — 1-based epoch at termination
    h_w: jnp.ndarray        # (B, d) f32 — current hypothesis weights
    h_b: jnp.ndarray        # (B,) f32 — current hypothesis offset
    h_valid: jnp.ndarray    # (B,) bool — (h_w, h_b) is a fitted separator
    warm_turn: jnp.ndarray  # (B,) bool — latest proposal cleanly classified
    #                         the next coordinator's shard (the single-carry
    #                         warm mode's latch precondition)
    c_w: jnp.ndarray        # (B, k, d) f32 — per-node carried separators
    c_b: jnp.ndarray        # (B, k) f32
    c_valid: jnp.ndarray    # (B, k) bool — node has a previous fit to carry
    warm_node: jnp.ndarray  # (B, k) bool — node's carry still classifies its
    #                         grown transcript cleanly (per-node latch
    #                         precondition; maintained incrementally at
    #                         append time)
    latches: jnp.ndarray    # (B,) i32 — warm-gate hits (observability only)
    comm: BatchCommLog


@dataclasses.dataclass(frozen=True)
class ProtocolInstance:
    """One protocol problem: k shards plus an error budget ε and a selector
    — the scenario spec the engine dispatches on.  Selectors are the two-way
    support selectors ("median", "maxmarg") and the one-way/baseline
    families ("sampling", "naive", "voting", "mixing";
    :mod:`repro.engine.oneway`).  ``seed`` keys per-instance randomness
    (only the "sampling" reservoir uses it)."""

    shards: Sequence[Tuple[np.ndarray, np.ndarray]]
    eps: float = 0.05
    selector: str = "median"
    seed: int = 0


def _blank_comm(B: int) -> BatchCommLog:
    return BatchCommLog(*(np.zeros((B,), np.int32)
                          for _ in BatchCommLog._fields))


def blank_protocol_state(B: int, k: int, cap: int, m: int,
                         done: np.ndarray) -> ProtocolState:
    """B fresh MEDIAN rows as host arrays: empty transcripts, all m grid
    directions allowed, unbounded thresholds; ``done`` (B,) bool marks the
    rows born done (padding)."""
    return ProtocolState(
        dir_ok=np.ones((B, m), bool),
        wx=np.zeros((B, k, cap, 2), np.float32),
        wy=np.zeros((B, k, cap), np.int32),
        w_fill=np.zeros((B, k), np.int32),
        lo_w=np.full((B, k, m), -np.inf, np.float32),
        hi_w=np.full((B, k, m), np.inf, np.float32),
        turn=np.zeros((B,), np.int32),
        done=done,
        converged=np.zeros((B,), bool),
        epochs=np.zeros((B,), np.int32),
        h_v=np.zeros((B, 2), np.float32),
        h_t=np.zeros((B,), np.float32),
        h_valid=np.zeros((B,), bool),
        comm=_blank_comm(B),
    )


def blank_maxmarg_state(B: int, k: int, cap: int, d: int,
                        done: np.ndarray) -> MaxMargState:
    """B fresh MAXMARG rows as host arrays: empty transcripts, no
    proposal, no carries; ``done`` marks the rows born done."""
    return MaxMargState(
        wx=np.zeros((B, k, cap, d), np.float32),
        wy=np.zeros((B, k, cap), np.int32),
        w_fill=np.zeros((B, k), np.int32),
        turn=np.zeros((B,), np.int32),
        done=done,
        converged=np.zeros((B,), bool),
        epochs=np.zeros((B,), np.int32),
        h_w=np.zeros((B, d), np.float32),
        h_b=np.zeros((B,), np.float32),
        h_valid=np.zeros((B,), bool),
        warm_turn=np.zeros((B,), bool),
        c_w=np.zeros((B, k, d), np.float32),
        c_b=np.zeros((B, k), np.float32),
        c_valid=np.zeros((B, k), bool),
        warm_node=np.zeros((B, k), bool),
        latches=np.zeros((B,), np.int32),
        comm=_blank_comm(B),
    )


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _batch_spec(ndim: int) -> PartitionSpec:
    """Axis 0 splits over the mesh's "data" axis; a scalar replicates."""
    return PartitionSpec("data", *([None] * (ndim - 1))) if ndim \
        else PartitionSpec()


def shard_specs(tree):
    """The engine's one sharding rule as a PartitionSpec pytree: axis 0 of
    every batched leaf splits over the mesh's "data" axis, scalar leaves
    replicate.  Works on any engine pytree — :class:`EngineData`,
    :class:`ProtocolState`, :class:`MaxMargState`."""
    return jax.tree_util.tree_map(lambda a: _batch_spec(np.ndim(a)), tree)


def device_put_sharded(tree, mesh):
    """Place an engine pytree on ``mesh`` under :func:`shard_specs` — host
    (numpy) leaves upload straight to their shards, so a packed sweep is
    *born sharded* rather than materialized on one device and resharded."""
    return jax.tree_util.tree_map(
        lambda a, p: jax.device_put(a, NamedSharding(mesh, p)),
        tree, shard_specs(tree))


def _mesh_batch(B: int, mesh) -> int:
    """Pad the instance count to a multiple of the mesh's "data" axis so
    every shard carries an equal slice; the pad rows are *born-done* dummy
    instances (zero data, zero budget) that never join a dispatch's active
    set and accrue nothing."""
    if mesh is None:
        return B
    return _round_up(B, mesh.shape["data"])


def maxmarg_transcript_capacity(k: int, max_epochs: int,
                                max_support: int) -> int:
    """Static per-node transcript bound for the MAXMARG selector.  Per epoch
    a node *receives* at most ``max_support`` points on each of the k-1 turns
    where it is not coordinator, plus (as coordinator) a 2-point violation
    reply from each of the k-1 others: ``(max_support + 2)(k-1)`` rows.  +8
    slack keeps the block writes in bounds (requires max_support ≤ 8)."""
    if not 1 <= max_support <= 8:
        raise ValueError(
            f"max_support must be in [1, 8] (block appends write at most 8 "
            f"rows past the fill), got {max_support}")
    return _round_up(max_epochs * (max_support + 2) * (k - 1) + 8, 8)


_LANES = 128
_TILE = 8 * _LANES      # one (8, 128) tile of 32-bit words
# an upload buffer stays under glibc's largest dynamic mmap threshold
# (32 MiB), so the next sweep reuses a freed buffer's pages instead of
# faulting fresh ones in
_CHUNK_BYTES = 16 << 20


def _lane_buffer(arrays, dtype, rows: int):
    """``arrays`` as lane-dense ``(rows, 128)`` pieces of ``dtype`` laid end
    to end in one ``(len(arrays) * rows, 128)`` host buffer; words past an
    array's end are zero (label 0, the inert label).  Also returns, for
    each array, whether its words went in as they are (one block copy:
    ``dtype``, C order, and a flat length of exactly ``rows * 128``) rather
    than converted, gathered or zero-padded on the host."""
    buf = np.empty((len(arrays), rows * _LANES), dtype)
    as_is = []
    for dst, a in zip(buf, arrays):
        a = np.asarray(a)
        as_is.append(a.dtype == dtype and a.flags.c_contiguous
                     and a.size == dst.size)
        dst[:a.size] = a.reshape(-1)
        dst[a.size:] = 0
    return buf.reshape(-1, _LANES), as_is


@functools.partial(jax.jit, static_argnames=("k", "n_pad", "d", "rows"))
def _assemble_slab(xs, ys, *, k: int, n_pad: int, d: int, rows: int):
    """One device's slab of a MAXMARG sweep from the :func:`_lane_buffer`
    buffers of its instances' shards (instance-major, node-minor): X
    ``(rows, k, n_pad, d)`` f32 and y ``(rows, k, n_pad)`` i32.  Rows past
    the buffers' instances are zero: born-done mesh padding.

    Each X piece is turned point-minor on its own, ``(n_pad, d)`` to
    ``(d, n_pad)``, before the stack: on a TPU the batch's layout keeps
    points in lanes, and a d-minor ``(n_pad, d)`` intermediate pads d to
    128 lanes, so turned one shard at a time it takes one shard's worth of
    memory (4 MB at n_pad 8192) instead of the slab's (537 MB at 32 x 4
    shards)."""
    rx, ry = _round_up(n_pad * d, _TILE), _round_up(n_pad, _TILE)
    px = [p for c in xs for p in c.reshape(-1, rx)]
    pad = rows * k - len(px)
    X = jnp.stack([p[:n_pad * d].reshape(n_pad, d).T for p in px]
                  + [jnp.zeros((d, n_pad), jnp.float32)] * pad)
    X = X.reshape(rows, k, d, n_pad)
    y = jnp.concatenate([*ys, jnp.zeros((pad * ry // _LANES, _LANES),
                                        jnp.int32)])
    y = y.reshape(rows * k, ry)[:, :n_pad].reshape(rows, k, n_pad)
    return jnp.swapaxes(X, 2, 3), y


@functools.lru_cache(maxsize=32)
def _zeros_program(leaves):
    """One program that makes a zero array on the devices for each
    ``(shape, dtype, sharding)`` of ``leaves``, placed by that sharding."""
    return jax.jit(lambda: tuple(jnp.zeros(shape, dtype)
                                 for shape, dtype, _ in leaves),
                   out_shardings=tuple(sh for *_, sh in leaves))


def _upload_maxmarg_slabs(instances, *, k, n_pad, d, B, mesh, stats):
    """X ``(B, k, n_pad, d)`` and y ``(B, k, n_pad)`` of a MAXMARG sweep,
    built on the devices.  Each device's instances' shards go up as
    lane-dense :func:`_lane_buffer` buffers of at most ``_CHUNK_BYTES``,
    one host thread a buffer filling it and putting it on its device, and
    :func:`_assemble_slab` builds each device's slab there.  With ``mesh``
    the slabs join under :func:`shard_specs`' sharding, device s holding
    the rows ``NamedSharding(mesh, P("data"))`` gives it; without, the one
    slab is on the default device.  No host relayout runs: an
    ``(R, 128)`` f32/i32 buffer with R a multiple of 8 has the same bytes
    row-major and in the chip's (8, 128) tiling."""
    rows_x = _round_up(n_pad * d, _TILE) // _LANES
    rows_y = _round_up(n_pad, _TILE) // _LANES
    if mesh is None:
        spans = [(None, 0, B)]
    else:
        lanes = NamedSharding(mesh, _batch_spec(1))
        spans = [(dev, *idx[0].indices(B)[:2]) for dev, idx
                 in lanes.addressable_devices_indices_map((B,)).items()]
    # one task a buffer: (span, leaf, arrays, dtype, rows)
    tasks = []
    for i, (_dev, lo, hi) in enumerate(spans):
        shards = [s for inst in instances[lo:hi] for s in inst.shards]
        for leaf, dtype, rows in ((0, np.float32, rows_x),
                                  (1, np.int32, rows_y)):
            per = max(1, _CHUNK_BYTES // (rows * _LANES * 4))
            tasks += [(i, leaf, [s[leaf] for s in shards[g:g + per]],
                       dtype, rows) for g in range(0, len(shards), per)]

    def upload(task):
        i, _leaf, arrays, dtype, rows = task
        buf, as_is = _lane_buffer(arrays, dtype, rows)
        return jax.device_put(buf, spans[i][0]), as_is

    with concurrent.futures.ThreadPoolExecutor(
            max(1, min(len(tasks), os.cpu_count() or 1))) as pool:
        done = list(pool.map(upload, tasks))
    bufs = [([], []) for _ in spans]
    as_is = [([], []) for _ in spans]
    for (i, leaf, *_), (buf, flags) in zip(tasks, done):
        bufs[i][leaf].append(buf)
        as_is[i][leaf].extend(flags)
    direct = sum(x and y for fx, fy in as_is for x, y in zip(fx, fy))
    copied = sum(len(fx) for fx, _fy in as_is) - direct
    if stats is not None:
        stats["upload_shards_direct"] = \
            stats.get("upload_shards_direct", 0) + direct
        stats["upload_shards_copied"] = \
            stats.get("upload_shards_copied", 0) + copied
    with jax.profiler.TraceAnnotation("sweep.assemble", direct=direct,
                                      copied=copied):
        slabs = []
        for (dev, lo, hi), (xs, ys) in zip(spans, bufs):
            # a device of padding rows alone gets no buffers: its slab of
            # zeros is made there all the same
            with jax.default_device(dev):
                slabs.append(_assemble_slab(xs, ys, k=k, n_pad=n_pad, d=d,
                                            rows=hi - lo))
    if mesh is None:
        return slabs[0]
    return tuple(
        jax.make_array_from_single_device_arrays(
            (B,) + part.shape[1:],
            NamedSharding(mesh, _batch_spec(part.ndim)),
            [slab[i] for slab in slabs])
        for i, part in enumerate(slabs[0]))


def pack_instances_maxmarg(
    instances: Sequence[ProtocolInstance],
    *,
    max_epochs: int,
    max_support: int,
    mesh=None,
    stats: Optional[dict] = None,
) -> Tuple[EngineData, MaxMargState, int, int]:
    """Pad a MAXMARG sweep onto the engine's static shapes.

    Returns ``(data, state0, k, cap)``.  All instances must share the party
    count k and the dimension d (any d ≥ 2 — MAXMARG has no direction grid);
    shard sizes may be ragged (label-0 padding).  The shards go up as
    lane-dense pieces and the batch is built on the devices
    (:func:`_upload_maxmarg_slabs`); ``stats`` counts the shards whose
    words went up as they are (``upload_shards_direct``) and those
    converted, gathered or zero-padded on the host first
    (``upload_shards_copied``).  With ``mesh`` the batch pads to a
    multiple of the data-axis size with born-done dummy rows, every leaf is
    born sharded under :func:`shard_specs`, and the zero leaves of the
    state are made on the devices.
    """
    assert instances, "need at least one instance"
    ks = {len(inst.shards) for inst in instances}
    assert len(ks) == 1, f"instances must share the party count, got {ks}"
    k = ks.pop()
    ds = {s[0].shape[1] for inst in instances for s in inst.shards}
    assert len(ds) == 1, f"instances must share the dimension, got {ds}"
    d = ds.pop()
    B = _mesh_batch(len(instances), mesh)
    n_max = _round_up(max(s[0].shape[0] for inst in instances
                          for s in inst.shards), 8)
    cap = maxmarg_transcript_capacity(k, max_epochs, max_support)

    budget = np.zeros((B,), np.int32)
    for b, inst in enumerate(instances):
        n_total = 0
        for Xs, ys in inst.shards:
            assert (np.abs(ys) == 1).all(), "labels must be +-1"
            n_total += Xs.shape[0]
        budget[b] = int(np.floor(inst.eps * n_total))
    done0 = np.zeros((B,), bool)
    done0[len(instances):] = True                    # born-done mesh padding
    X, y = _upload_maxmarg_slabs(instances, k=k, n_pad=n_max, d=d, B=B,
                                 mesh=mesh, stats=stats)

    # numpy zeros for the initial state: without a mesh the leaves upload
    # at the first dispatch like any jit input, without one eager device op
    # per field (a dozen tiny dispatches of pure overhead per sweep
    # otherwise); with one, they are made on the devices in one program
    state0 = blank_maxmarg_state(B, k, cap, d, done0)
    if mesh is None:
        return EngineData(X, y, jnp.asarray(budget)), state0, k, cap
    lanes = NamedSharding(mesh, _batch_spec(1))
    leaves, tree = jax.tree_util.tree_flatten(state0)
    zeros = _zeros_program(tuple(
        (a.shape, a.dtype, NamedSharding(mesh, _batch_spec(a.ndim)))
        for a in leaves))()
    state0 = jax.tree_util.tree_unflatten(tree, zeros)._replace(
        done=jax.device_put(done0, lanes))
    return (EngineData(X, y, jax.device_put(budget, lanes)), state0, k,
            cap)


def transcript_capacity(k: int, max_epochs: int) -> int:
    """Static per-node transcript bound.  Per epoch a node appends at most
    ``8k - 4`` rows: one coordinator turn (its own ≤2 band points, ≤2 extreme
    points from each of k-1 repliers, a 2-point pivot pair) plus k-1
    non-coordinator turns (≤2 received band points, its own ≤2 extremes,
    a 2-point pivot pair).  +8 slack keeps the 2-row block writes in bounds.
    """
    return _round_up(max_epochs * (8 * k - 4) + 8, 8)


def pack_instances(
    instances: Sequence[ProtocolInstance],
    *,
    n_angles: int,
    max_epochs: int,
    mesh=None,
) -> Tuple[EngineData, ProtocolState, int, int]:
    """Pad a sweep onto the engine's static shapes.

    Returns ``(data, state0, k, cap)``.  All instances must share the party
    count k and dimension d=2; shard sizes may be ragged (label-0 padding).
    ``n_max`` and ``cap`` are rounded up to multiples of 8 so repeated sweeps
    of similar sizes reuse the compiled runner.  With ``mesh`` the batch
    pads to a multiple of the data-axis size with born-done dummy rows and
    uploads born-sharded (:func:`device_put_sharded`).
    """
    assert instances, "need at least one instance"
    ks = {len(inst.shards) for inst in instances}
    assert len(ks) == 1, f"instances must share the party count, got {ks}"
    k = ks.pop()
    ds = {s[0].shape[1] for inst in instances for s in inst.shards}
    assert ds == {2}, f"MEDIAN engine is specified for R^2, got d={ds}"
    B = _mesh_batch(len(instances), mesh)
    n_max = _round_up(max(s[0].shape[0] for inst in instances
                          for s in inst.shards), 8)
    cap = transcript_capacity(k, max_epochs)

    X = np.zeros((B, k, n_max, 2), np.float32)
    y = np.zeros((B, k, n_max), np.int32)
    budget = np.zeros((B,), np.int32)
    for b, inst in enumerate(instances):
        n_total = 0
        for j, (Xs, ys) in enumerate(inst.shards):
            n = Xs.shape[0]
            assert (np.abs(ys) == 1).all(), "labels must be +-1"
            X[b, j, :n] = Xs
            y[b, j, :n] = ys
            n_total += n
        budget[b] = int(np.floor(inst.eps * n_total))
    done0 = np.zeros((B,), bool)
    done0[len(instances):] = True                    # born-done mesh padding

    data = EngineData(X, y, budget)
    state0 = blank_protocol_state(B, k, cap, n_angles, done0)
    if mesh is not None:
        return (device_put_sharded(data, mesh),
                device_put_sharded(state0, mesh), k, cap)
    data = EngineData(jnp.asarray(X), jnp.asarray(y), jnp.asarray(budget))
    # jnp leaves on the legacy path: callers step this state eagerly (the
    # constant-fold differential test) and functional .at updates need them
    state0 = jax.tree_util.tree_map(jnp.asarray, state0)
    return data, state0, k, cap


class UnifiedState(NamedTuple):
    """Superset protocol state for mixed-selector dispatch — the union of
    :class:`ProtocolState`, :class:`MaxMargState` and the one-way sampling
    chain's reservoir carry, keyed by a *traced* per-instance selector code
    (``SEL_MEDIAN`` / ``SEL_MAXMARG`` / ``SEL_SAMPLING``).

    Leaf sharing is the whole design (DESIGN.md §unified mixed-selector
    state):

    * **transcripts** ``wx``/``wy``/``w_fill`` are shared: MEDIAN and MAXMARG
      append per the usual label-0 convention; a SAMPLING row keeps its
      Vitter reservoir in node slot ``k-1``'s transcript (so the terminal
      fit — which concatenates shard ``k-1`` with the coordinator
      transcript — *is* the sampling oracle's ``own ∪ reservoir`` fit);
    * **separator** ``h_w``/``h_b``/``h_valid`` are shared: a MEDIAN row
      stores its direction in ``h_w`` and threshold in ``h_b`` (result
      extraction negates ``h_w`` to recover ``LinearSeparator(-h_v, h_t)``);
    * **control** ``turn``/``done``/``converged``/``epochs``/``comm`` are
      shared and per-instance, so one dispatch mixes sessions at different
      phases of different protocols;
    * **selector-private** leaves are simply carried untouched by the other
      selectors' masked substeps: the MEDIAN arc (``dir_ok``/``lo_w``/
      ``hi_w``), the MAXMARG warm carries (``warm_turn``/``c_w``/``c_b``/
      ``c_valid``/``warm_node``/``latches``), and the sampling reservoir
      counters (``seen``/``res_cap``/``hop_keys``).

    ``sel`` is data, not structure: two sweeps with different selector mixes
    share one compiled ``unified.step``, and the session pool admits any mix
    into one slot array at one pinned dispatch key.
    """

    sel: jnp.ndarray        # (B,) i32 — SEL_* code per instance
    # --- median-private (m = n_angles, or 1 when the mix has no median) ---
    dir_ok: jnp.ndarray     # (B, m) bool — allowed direction arc
    lo_w: jnp.ndarray       # (B, k, m) f32 — running per-node threshold lo
    hi_w: jnp.ndarray       # (B, k, m) f32 — running per-node threshold hi
    # --- shared transcript + control ---
    wx: jnp.ndarray         # (B, k, cap, d) f32 — transcripts / reservoir
    wy: jnp.ndarray         # (B, k, cap) i32 — labels (0 = empty)
    w_fill: jnp.ndarray     # (B, k) i32 — fill counters
    turn: jnp.ndarray       # (B,) i32 — per-instance turn counter
    done: jnp.ndarray       # (B,) bool
    converged: jnp.ndarray  # (B,) bool
    epochs: jnp.ndarray     # (B,) i32
    # --- shared separator (median: h_w = h_v, h_b = h_t) ---
    h_w: jnp.ndarray        # (B, d) f32
    h_b: jnp.ndarray        # (B,) f32
    h_valid: jnp.ndarray    # (B,) bool
    # --- maxmarg-private warm carries ---
    warm_turn: jnp.ndarray  # (B,) bool
    c_w: jnp.ndarray        # (B, k, d) f32
    c_b: jnp.ndarray        # (B, k) f32
    c_valid: jnp.ndarray    # (B, k) bool
    warm_node: jnp.ndarray  # (B, k) bool
    latches: jnp.ndarray    # (B,) i32
    # --- sampling-private reservoir carry ---
    seen: jnp.ndarray       # (B,) i32 — valid stream rows ingested so far
    res_cap: jnp.ndarray    # (B,) i32 — per-instance ε-net reservoir size
    hop_keys: jnp.ndarray   # (B, k-1, 2) u32 — per-hop Vitter PRNG keys
    comm: BatchCommLog


def blank_unified_state(B: int, k: int, cap: int, d: int, m: int,
                        done: np.ndarray, sel: np.ndarray,
                        res_cap: np.ndarray,
                        hop_keys: np.ndarray) -> UnifiedState:
    """B fresh mixed rows as host arrays: the selector codes ``sel``, the
    SAMPLING reservoir sizes ``res_cap`` and Vitter keys ``hop_keys`` are
    per-row data, every other leaf blank as in the per-family states (m
    arc directions, or 1 for a median-free mix)."""
    return UnifiedState(
        sel=sel, dir_ok=np.ones((B, m), bool),
        lo_w=np.full((B, k, m), -np.inf, np.float32),
        hi_w=np.full((B, k, m), np.inf, np.float32),
        **blank_maxmarg_state(B, k, cap, d, done)._asdict(),
        seen=np.zeros((B,), np.int32), res_cap=res_cap, hop_keys=hop_keys)


def unified_transcript_capacity(k: int, max_epochs: int, max_support: int,
                                res_cap: int = 0,
                                has_median: bool = True) -> int:
    """Static shared transcript bound for a mixed-selector sweep: the max of
    every family's own bound (:func:`transcript_capacity` for MEDIAN,
    :func:`maxmarg_transcript_capacity` for MAXMARG, the largest per-instance
    ε-net reservoir for SAMPLING), so one (B, k, cap, d) buffer holds any
    mix.  Already a multiple of 8 (each family bound is)."""
    cap = maxmarg_transcript_capacity(k, max_epochs, max_support)
    if has_median:
        cap = max(cap, transcript_capacity(k, max_epochs))
    return max(cap, _round_up(max(res_cap, 0), 8))


def pack_instances_unified(
    instances: Sequence[ProtocolInstance],
    *,
    n_angles: int,
    max_epochs: int,
    max_support: int,
    vc_dim: Optional[int] = None,
    c: Optional[float] = None,
) -> Tuple[EngineData, UnifiedState, int, int]:
    """Pad a mixed MEDIAN + MAXMARG + SAMPLING sweep onto one static shape.

    Returns ``(data, state0, k, cap)``.  All instances must share the party
    count k and dimension d; any MEDIAN instance in the mix requires d=2
    (its direction grid is planar) and sizes the arc leaves to ``n_angles``
    — a median-free mix carries 1-wide stub arc leaves instead.  SAMPLING
    rows get their per-instance ε-net size in ``res_cap`` (``vc_dim``/``c``
    default exactly like :func:`repro.engine.oneway.run_instances`) and
    their Vitter hop keys pre-split from ``ProtocolInstance.seed``, so the
    reservoir stream is bitwise the one-way oracle's.
    """
    assert instances, "need at least one instance"
    ks = {len(inst.shards) for inst in instances}
    assert len(ks) == 1, f"instances must share the party count, got {ks}"
    k = ks.pop()
    ds = {s[0].shape[1] for inst in instances for s in inst.shards}
    assert len(ds) == 1, f"instances must share the dimension, got {ds}"
    d = ds.pop()
    sels = [inst.selector for inst in instances]
    unknown = set(sels) - set(SELECTOR_CODES)
    if unknown:
        raise ValueError(
            f"unified packing covers {sorted(SELECTOR_CODES)}, got "
            f"{sorted(unknown)}")
    has_median = "median" in sels
    if has_median and d != 2:
        raise ValueError(f"MEDIAN instances require d=2, got d={d}")
    m = n_angles if has_median else 1

    B = len(instances)
    n_max = _round_up(max(s[0].shape[0] for inst in instances
                          for s in inst.shards), 8)
    vc = vc_dim if vc_dim is not None else d + 1
    cc = c if c is not None else EPSILON_NET_C
    res_cap = np.zeros((B,), np.int32)
    hop_keys = np.zeros((B, max(k - 1, 1), 2), np.uint32)
    for b, inst in enumerate(instances):
        if inst.selector == "sampling":
            res_cap[b] = epsilon_net_size(inst.eps, vc, c=cc)
            if k > 1:
                hop_keys[b] = np.asarray(jax.random.split(
                    jax.random.PRNGKey(inst.seed), k - 1))
    cap = unified_transcript_capacity(k, max_epochs, max_support,
                                      res_cap=int(res_cap.max()),
                                      has_median=has_median)

    X = np.zeros((B, k, n_max, d), np.float32)
    y = np.zeros((B, k, n_max), np.int32)
    budget = np.zeros((B,), np.int32)
    for b, inst in enumerate(instances):
        n_total = 0
        for j, (Xs, ys) in enumerate(inst.shards):
            n = Xs.shape[0]
            assert (np.abs(ys) == 1).all(), "labels must be +-1"
            X[b, j, :n] = Xs
            y[b, j, :n] = ys
            n_total += n
        budget[b] = int(np.floor(inst.eps * n_total))

    state0 = blank_unified_state(
        B, k, cap, d, m, np.zeros((B,), bool),
        np.asarray([SELECTOR_CODES[s] for s in sels], np.int32), res_cap,
        hop_keys)
    data = EngineData(jnp.asarray(X), jnp.asarray(y), jnp.asarray(budget))
    return data, state0, k, cap
