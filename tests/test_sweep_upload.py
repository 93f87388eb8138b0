"""The MAXMARG sweep's upload: lane-dense shard pieces, assembled on the
devices, against the host pack they replace.

``pack_instances_maxmarg`` sends each shard up as a ``(R, 128)`` piece in
a lane-dense upload buffer (its words as they are where it already holds
them in order, converted or zero-padded otherwise) and builds the
``(B, k, n_pad, d)`` batch on the devices.  ``_host_pack`` below is the pack it replaced: numpy zeros filled
shard by shard, then ``device_put_sharded``.  The two must agree element
for element and in sharding, and a sweep from either must give the same
records bit for bit.

The mesh spans four devices where the process has them
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before jax starts;
run the file alone) and one otherwise; four devices put born-done padding
rows on the last devices.
"""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro import engine  # noqa: E402
from repro.core import datasets  # noqa: E402
from repro.engine import maxmarg, state as st  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402

K, D, EPOCHS, SUPPORT = 4, 10, 8, 4
OPTS = dict(max_epochs=EPOCHS, max_support=SUPPORT, steps=2000, stages=3,
            lam=1e-3, warm=False)


def _host_pack(instances, *, max_epochs, max_support, mesh=None,
               stats=None):
    """The host pack the upload replaced: the whole batch as numpy, padded
    with zero rows and label 0, uploaded under ``shard_specs``."""
    k = len(instances[0].shards)
    d = instances[0].shards[0][0].shape[1]
    B = st._mesh_batch(len(instances), mesh)
    n_max = st._round_up(max(s[0].shape[0] for inst in instances
                             for s in inst.shards), 8)
    cap = st.maxmarg_transcript_capacity(k, max_epochs, max_support)
    X = np.zeros((B, k, n_max, d), np.float32)
    y = np.zeros((B, k, n_max), np.int32)
    budget = np.zeros((B,), np.int32)
    for b, inst in enumerate(instances):
        for j, (Xs, ys) in enumerate(inst.shards):
            X[b, j, :Xs.shape[0]] = Xs
            y[b, j, :Xs.shape[0]] = ys
        budget[b] = int(np.floor(
            inst.eps * sum(s[0].shape[0] for s in inst.shards)))
    done0 = np.arange(B) >= len(instances)

    def z(shape, dtype):
        return np.zeros(shape, dtype)

    state0 = st.MaxMargState(
        wx=z((B, k, cap, d), np.float32), wy=z((B, k, cap), np.int32),
        w_fill=z((B, k), np.int32), turn=z((B,), np.int32), done=done0,
        converged=z((B,), bool), epochs=z((B,), np.int32),
        h_w=z((B, d), np.float32), h_b=z((B,), np.float32),
        h_valid=z((B,), bool), warm_turn=z((B,), bool),
        c_w=z((B, k, d), np.float32), c_b=z((B, k), np.float32),
        c_valid=z((B, k), bool), warm_node=z((B, k), bool),
        latches=z((B,), np.int32),
        comm=st.BatchCommLog(*(z((B,), np.int32)
                               for _ in st.BatchCommLog._fields)))
    data = st.EngineData(X, y, budget)
    if mesh is None:
        return data, state0, k, cap
    return (st.device_put_sharded(data, mesh),
            st.device_put_sharded(state0, mesh), k, cap)


def _shards(seed, n):
    """k shards of ``n`` points lifted to R^d: float32 / int32, C order."""
    s = datasets.data_mixed_hardness(n_per_node=n, k=K, seed=seed, gap=0.15)
    return [(X.astype(np.float32), y.astype(np.int32))
            for X, y in datasets.lift_dim(s, D, seed=seed)]


def _instances(case):
    """``(instances, direct, copied)`` for one case of the upload rule."""
    n = 1024                # n * d and n fill whole (8, 128) tiles
    insts = [_shards(i, n) for i in range(6)]
    if case == "ragged":
        X, y = insts[2][1]
        insts[2][1] = (X[:1000].copy(), y[:1000].copy())
        direct, copied = 23, 1
    elif case == "dtype_and_order":
        insts[0] = [(X.astype(np.float64), y) for X, y in insts[0]]
        X, y = insts[3][2]
        insts[3][2] = (np.asfortranarray(X), y)
        X, y = insts[4][0]
        insts[4][0] = (X, y.astype(np.int64))
        wide = np.zeros((2 * n, D), np.float32)
        wide[::2] = insts[5][3][0]
        insts[5][3] = (wide[::2], insts[5][3][1])
        direct, copied = 17, 7
    elif case == "untiled":
        insts = [[(X[:1000].copy(), y[:1000].copy()) for X, y in s]
                 for s in insts]
        direct, copied = 0, 24
    elif case == "born_done":
        insts = insts[:5]
        direct, copied = 20, 0
    else:
        assert case in ("uniform", "small_buffers")
        direct, copied = 24, 0
    eps = (0.05, 0.02, 0.1, 0.05, 0.0, 0.07)
    return ([engine.ProtocolInstance(s, e, "maxmarg")
             for s, e in zip(insts, eps)], direct, copied)


def _devices():
    return 4 if len(jax.devices()) >= 4 else 1


@pytest.mark.parametrize("sharded", [True, False])
@pytest.mark.parametrize(
    "case", ["uniform", "ragged", "dtype_and_order", "untiled", "born_done",
             "small_buffers"])
def test_upload_is_the_host_pack(case, sharded, monkeypatch):
    insts, direct, copied = _instances(case)
    if case == "small_buffers":
        # two X pieces a buffer: each device's shards span several
        # buffers, the last one short
        monkeypatch.setattr(st, "_CHUNK_BYTES", 2 * 1024 * D * 4)
    mesh = make_data_mesh(_devices()) if sharded else None
    stats = {}
    data, state0, k, cap = st.pack_instances_maxmarg(
        insts, max_epochs=EPOCHS, max_support=SUPPORT, mesh=mesh,
        stats=stats)
    ref_data, ref_state, ref_k, ref_cap = _host_pack(
        insts, max_epochs=EPOCHS, max_support=SUPPORT, mesh=mesh)
    assert (k, cap) == (ref_k, ref_cap)
    assert stats == {"upload_shards_direct": direct,
                     "upload_shards_copied": copied}
    for got, want in zip(jax.tree_util.tree_leaves((data, state0)),
                         jax.tree_util.tree_leaves((ref_data, ref_state))):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        if sharded:
            assert got.sharding == want.sharding
    if sharded and case == "born_done" and _devices() == 4:
        assert np.asarray(state0.done).tolist() == [False] * 5 + [True] * 3


def test_lane_buffer_holds_zero_padded_pieces():
    """Shards go into an upload buffer as lane-dense pieces laid end to
    end, zero past each shard's words; only a float32 shard in C order
    that fills its piece goes in as it is."""
    X = np.arange(1024 * D, dtype=np.float32).reshape(1024, D)
    shards = [X, X[:1000], X.astype(np.float64), np.asfortranarray(X)]
    buf, as_is = st._lane_buffer(shards, np.float32, 80)
    assert as_is == [True, False, False, False]
    assert buf.shape == (4 * 80, 128) and buf.dtype == np.float32
    for piece, a in zip(buf.reshape(len(shards), -1), shards):
        np.testing.assert_array_equal(piece[:a.size],
                                      np.asarray(a, np.float32).reshape(-1))
        assert not piece[a.size:].any()


def test_cold_sweep_records_match_the_host_pack(monkeypatch):
    """A cold sweep over the mesh gives every record and separator bit for
    bit as the same sweep from the host pack."""
    insts = _instances("ragged")[0][:5]
    mesh = make_data_mesh(_devices())
    stats = {}
    got = engine.run_sweep(insts, mesh=mesh, stats=stats, **OPTS)
    assert stats["upload_shards_direct"] + stats["upload_shards_copied"] \
        == 5 * K
    monkeypatch.setattr(maxmarg, "pack_instances_maxmarg", _host_pack)
    want = engine.run_sweep(insts, mesh=mesh, **OPTS)
    assert all(r.converged for r in want)
    assert any(r.comm["rounds"] > K for r in want)     # multi-epoch
    for a, b in zip(got, want):
        assert (a.converged, a.rounds, a.comm) == \
            (b.converged, b.rounds, b.comm)
        np.testing.assert_array_equal(a.classifier.w, b.classifier.w)
        assert a.classifier.b == b.classifier.b
