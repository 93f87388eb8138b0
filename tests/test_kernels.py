"""Per-kernel allclose sweeps: every Pallas kernel (interpret=True on CPU)
against its ref.py pure-jnp oracle over shapes × dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba import mamba_scan
from repro.kernels.rwkv6 import rwkv6_chunked


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 8, 2, 64),     # GQA 4:1
    (1, 512, 4, 1, 32),     # MQA
    (2, 128, 6, 3, 128),    # odd head count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(B, S, H, KV, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-4, atol=2e-4)


def test_flash_attention_kv_valid():
    """Decode-style: only the first kv_valid cache slots are real."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 4, 32))
    v = jax.random.normal(ks[2], (2, 256, 4, 32))
    out = flash_attention(q, k, v, causal=False, kv_valid=100, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=False, kv_valid=100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-4, atol=2e-4)


def test_attention_ops_ragged_padding():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 100, 4, 32))
    k = jax.random.normal(ks[1], (1, 100, 2, 32))
    v = jax.random.normal(ks[2], (1, 100, 2, 32))
    out = ops.attention(q, k, v, causal=True, interpret=True)
    exp = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 64, 2, 32, 16),
    (2, 128, 3, 64, 32),
    (1, 96, 1, 16, 32),     # S not a multiple of chunk -> ops pads
])
def test_rwkv6_chunked_matches_scan(B, S, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(S + hd), 5)
    r = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, hd))) * 0.98 + 0.01
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    y, sT = ops.rwkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    ye, sTe = ref.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sTe), rtol=1e-3, atol=1e-3)


def test_rwkv6_decay_extremes():
    """Near-0 and near-1 decays must both stay numerically sane."""
    B, S, H, hd = 1, 64, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    r = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    u = jnp.zeros((H, hd))
    for wval in (0.02, 0.999):
        w = jnp.full((B, S, H, hd), wval)
        y, _ = rwkv6_chunked(r, k, v, w, u, chunk=16, interpret=True)
        ye, _ = ref.rwkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,di,ds,chunk,bdi", [
    (1, 64, 32, 16, 16, 32),
    (2, 128, 64, 16, 32, 32),
    (1, 100, 48, 8, 32, 16),   # ragged S and di -> ops pads
])
def test_mamba_scan_matches(B, S, di, ds, chunk, bdi):
    ks = jax.random.split(jax.random.PRNGKey(S * di), 5)
    xc = jax.random.normal(ks[0], (B, S, di))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 2)
    A = -jnp.exp(jax.random.normal(ks[2], (di, ds)) * 0.5)
    Bs = jax.random.normal(ks[3], (B, S, ds))
    Cs = jax.random.normal(ks[4], (B, S, ds))
    y, hT = ops.selective_scan(xc, delta, A, Bs, Cs, chunk=chunk, block_di=bdi,
                               interpret=True)
    ye, hTe = ref.mamba_ref(xc, delta, A, Bs, Cs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTe), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# support margin (paper data plane)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,d", [(64, 512, 2), (100, 333, 2), (256, 1024, 10),
                                   (7, 13, 3)])
def test_support_margin_vs_geometry_oracle(m, n, d):
    ks = jax.random.split(jax.random.PRNGKey(m + n), 4)
    V = jax.random.normal(ks[0], (m, d))
    V = V / jnp.linalg.norm(V, axis=1, keepdims=True)
    Xw = jax.random.normal(ks[1], (n, d))
    yw = jnp.where(jax.random.bernoulli(ks[2], 0.5, (n,)), 1, -1)
    X = jax.random.normal(ks[3], (n, d))
    ok = jax.random.bernoulli(ks[2], 0.8, (m,))

    lo, hi = ops.support_ranges(V, Xw, yw, interpret=True)
    loe, hie = ref.threshold_ranges_ref(V, Xw, yw)
    fin = np.isfinite(np.asarray(loe))
    np.testing.assert_allclose(np.asarray(lo)[fin], np.asarray(loe)[fin], rtol=1e-5)
    mask = ops.support_uncertain(V, ok, lo, hi, X, yw, interpret=True)
    maske = ref.uncertain_mask_ref(V, ok, loe, hie, X, yw)
    assert bool(jnp.all(mask == maske))


def test_support_margin_one_class_only():
    """All-positive transcript: hi stays +BIG (no negative constraint)."""
    V = jnp.eye(2)
    Xw = jnp.array([[1.0, 0.0], [2.0, 0.0]])
    yw = jnp.array([1, 1])
    lo, hi = ops.support_ranges(V, Xw, yw, interpret=True)
    assert float(lo[0]) == pytest.approx(2.0)
    assert float(hi[0]) >= 1e29


@pytest.mark.parametrize("B,m,n,d", [(1, 64, 256, 2), (4, 100, 333, 2),
                                     (8, 256, 512, 2), (3, 7, 13, 3)])
def test_support_margin_batched_vs_refs(B, m, n, d):
    """Batch-grid kernels against the jitted vmap oracles, including label-0
    padding rows (the ragged-shard convention)."""
    ks = jax.random.split(jax.random.PRNGKey(B * m + n), 4)
    V = jax.random.normal(ks[0], (m, d))
    V = V / jnp.linalg.norm(V, axis=1, keepdims=True)
    Xw = jax.random.normal(ks[1], (B, n, d))
    yw = jnp.where(jax.random.bernoulli(ks[2], 0.5, (B, n)), 1, -1)
    yw = yw * jax.random.bernoulli(ks[3], 0.8, (B, n))   # some label-0 pads
    X = jax.random.normal(ks[3], (B, n, d))
    ok = jax.random.bernoulli(ks[2], 0.8, (B, m))

    lo, hi = ops.support_ranges_batch(V, Xw, yw, interpret=True)
    loe, hie = ref.threshold_ranges_batch_ref(V, Xw, yw)
    fin = np.isfinite(np.asarray(loe))
    np.testing.assert_allclose(np.asarray(lo)[fin], np.asarray(loe)[fin],
                               rtol=1e-5)
    fin = np.isfinite(np.asarray(hie))
    np.testing.assert_allclose(np.asarray(hi)[fin], np.asarray(hie)[fin],
                               rtol=1e-5)
    mask = ops.support_uncertain_batch(V, ok, lo, hi, X, yw, interpret=True)
    maske = ref.uncertain_mask_batch_ref(V, ok, loe, hie, X, yw)
    assert bool(jnp.all(mask == maske))


def test_support_margin_batched_matches_per_instance():
    """Each batch slice must equal the single-instance kernel's output."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    B, m, n = 5, 64, 128
    V = jax.random.normal(ks[0], (m, 2))
    Xw = jax.random.normal(ks[1], (B, n, 2))
    yw = jnp.where(jax.random.bernoulli(ks[2], 0.5, (B, n)), 1, -1)
    lo_b, hi_b = ops.support_ranges_batch(V, Xw, yw, interpret=True)
    for b in range(B):
        lo1, hi1 = ops.support_ranges(V, Xw[b], yw[b], interpret=True)
        np.testing.assert_array_equal(np.asarray(lo_b[b]), np.asarray(lo1))
        np.testing.assert_array_equal(np.asarray(hi_b[b]), np.asarray(hi1))


@pytest.mark.parametrize("B,m,n,d", [(1, 64, 256, 2), (4, 100, 333, 2),
                                     (8, 512, 200, 2), (3, 7, 13, 3)])
def test_median_cut_batched_bit_for_bit(B, m, n, d):
    """The (B, m, n) median-cut scan kernel must match the vmap reference
    *bit-for-bit* — the scores are integer counts, so there is no tolerance
    to hide behind.  Includes label-0 padding rows, disallowed directions,
    and ±inf range sentinels (missing-class transcripts)."""
    ks = jax.random.split(jax.random.PRNGKey(B * m + n), 6)
    V = jax.random.normal(ks[0], (m, d))
    V = V / jnp.linalg.norm(V, axis=1, keepdims=True)
    X = jax.random.normal(ks[1], (B, n, d))
    y = jnp.where(jax.random.bernoulli(ks[2], 0.5, (B, n)), 1, -1)
    y = y * jax.random.bernoulli(ks[3], 0.8, (B, n))     # some label-0 pads
    ok = jax.random.bernoulli(ks[4], 0.7, (B, m))
    lo = jnp.where(jax.random.bernoulli(ks[5], 0.8, (B, m)),
                   jax.random.normal(ks[5], (B, m)), -jnp.inf)
    hi = jnp.where(jax.random.bernoulli(ks[4], 0.8, (B, m)),
                   lo + jax.random.uniform(ks[1], (B, m)), jnp.inf)

    got = ops.support_median_cut_batch(V, ok.astype(jnp.float32), lo, hi,
                                       X, y, interpret=True)
    want = ref.median_cut_scores_batch_ref(V, ok, lo, hi, X, y)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32


def test_median_cut_on_engine_grid_bit_for_bit():
    """Kernel vs reference on *real* engine sweep state (the test grid the
    acceptance bar names): mid-protocol dir_ok / incremental ranges /
    padded shards, every turn of a short sweep."""
    from repro import engine
    from repro.core import datasets, geometry as geo
    from repro.engine import median as M

    insts = [engine.ProtocolInstance(
                 datasets.data3(n_per_node=100, k=2, seed=s), 0.05)
             for s in range(4)]
    data, state, k, _ = engine.pack_instances(insts, n_angles=256,
                                              max_epochs=16)
    V = jnp.asarray(geo.direction_grid(256), jnp.float32)
    state = M.step(data, V, state, k=k, first_turn=True)
    for _ in range(5):
        # lock-step sweep: every per-instance turn is identical
        ci = int(np.asarray(state.turn)[0]) % k
        lo = jnp.take(state.lo_w, ci, axis=1)
        hi = jnp.take(state.hi_w, ci, axis=1)
        Xc = jnp.take(data.X, ci, axis=1)
        yc = jnp.take(data.y, ci, axis=1)
        got = ops.support_median_cut_batch(
            V, state.dir_ok.astype(jnp.float32), lo, hi, Xc, yc,
            interpret=True)
        want = ref.median_cut_scores_batch_ref(V, state.dir_ok, lo, hi,
                                               Xc, yc)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        state = M.step(data, V, state, k=k)


@pytest.mark.parametrize("B,N,k,n,d", [(1, 64, 2, 48, 2), (5, 33, 3, 21, 2),
                                       (4, 100, 2, 80, 5), (3, 24, 4, 16, 10)])
def test_maxmarg_turn_scan_bit_for_bit(B, N, k, n, d):
    """The fused support/violation kernel must match the vmap reference
    *bit-for-bit* (capped integer ranks + error counts), including label-0
    padding rows and one-class fit sets."""
    ks = jax.random.split(jax.random.PRNGKey(B * N + n), 8)
    K = jax.random.normal(ks[0], (B, N, d))
    yK = jnp.where(jax.random.bernoulli(ks[1], 0.5, (B, N)), 1, -1)
    yK = yK * jax.random.bernoulli(ks[2], 0.8, (B, N))   # label-0 pads
    X = jax.random.normal(ks[3], (B, k, n, d))
    y = jnp.where(jax.random.bernoulli(ks[4], 0.5, (B, k, n)), 1, -1)
    y = y * jax.random.bernoulli(ks[5], 0.8, (B, k, n))
    w = jax.random.normal(ks[6], (B, d))
    b = jax.random.normal(ks[7], (B,))

    got = ops.support_violation_batch(w, b, K, yK, X, y, interpret=True)
    want = ref.maxmarg_turn_batch_ref(w, b, K, yK, X, y)
    for g, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
        assert g.dtype == jnp.int32


def test_maxmarg_turn_scan_on_engine_grid_bit_for_bit():
    """Kernel vs reference on *real* MAXMARG engine state: mid-protocol
    transcripts, live separators, padded shards — every turn of a short
    sweep (the kernel-vs-chain equivalence the engine's ``fused_kernel``
    switch relies on)."""
    from repro import engine
    from repro.core import datasets
    from repro.core.classifiers import _svm_solve_batch
    from repro.engine import hotloop, maxmarg as MM

    step_j = hotloop.programs(MM.FAMILY)[0]
    insts = [engine.ProtocolInstance(
                 datasets.data3(n_per_node=60, k=2, seed=s), 0.02, "maxmarg")
             for s in range(4)]
    data, state, k, _ = engine.pack_instances_maxmarg(insts, max_epochs=8,
                                                      max_support=4)
    for _ in range(3):
        # lock-step sweep: every per-instance turn is identical
        ci = int(np.asarray(state.turn)[0]) % k
        Xc = jnp.take(data.X, ci, axis=1)
        yc = jnp.take(data.y, ci, axis=1)
        Wxc = jnp.take(state.wx, ci, axis=1)
        Wyc = jnp.take(state.wy, ci, axis=1)
        K = jnp.concatenate([Xc, Wxc], axis=1)
        yK = jnp.concatenate([yc, Wyc], axis=1)
        w, b, _ = _svm_solve_batch(K, yK.astype(K.dtype),
                                   jnp.float32(1e-3), 500, 2)
        got = ops.support_violation_batch(w, b, K, yK, data.X, data.y,
                                          interpret=True)
        want = ref.maxmarg_turn_batch_ref(w, b, K, yK, data.X, data.y)
        for g, e in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
        state = step_j(data, state, k=k, max_support=4, steps=500,
                       stages=2, lam0=1e-3, trans_width=None,
                       warm=False, fused_kernel=False)
        if bool(jnp.all(state.done)):
            break


def test_geometry_consistency_with_kernel():
    """geometry.consistent_threshold_ranges (XLA path) == Pallas path."""
    from repro.core import geometry as geo
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    V = np.asarray(geo.direction_grid(128))
    Xw = jax.random.normal(ks[0], (50, 2))
    yw = jnp.where(jax.random.bernoulli(ks[1], 0.5, (50,)), 1, -1)
    lo_g, hi_g = geo.consistent_threshold_ranges(jnp.asarray(V), Xw, yw)
    lo_k, hi_k = ops.support_ranges(jnp.asarray(V), Xw, yw, interpret=True)
    fin = np.isfinite(np.asarray(lo_g))
    np.testing.assert_allclose(np.asarray(lo_k)[fin], np.asarray(lo_g)[fin], rtol=1e-5)
    fin = np.isfinite(np.asarray(hi_g))
    np.testing.assert_allclose(np.asarray(hi_k)[fin], np.asarray(hi_g)[fin], rtol=1e-5)
