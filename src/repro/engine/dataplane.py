"""Batched bulk scans over sweep state: Pallas on TPU / jitted JAX on CPU.

The two shapes are exactly the single-instance kernels' with a leading batch
axis:

* ``ranges``: per-instance consistent-threshold intervals over a transcript
  — (B, m, cap) masked matmul-reduce;
* ``uncertain``: per-instance SOU membership — (B, m, n) masked matmul-any.

On TPU both dispatch to the batch-grid Pallas kernels
(``repro.kernels.support_margin.{threshold_ranges_batched,
uncertain_mask_batched}``); elsewhere to the jitted pure-jnp oracles in
``repro.kernels.ref`` (interpret-mode Pallas inside a hot loop would be
pathologically slow).  Outputs are normalized to ±inf sentinels.

Note these are the *bulk-scan* entry points — SOU diagnostics over final
sweep state, and the rescan oracle that validates the engine's incremental
ranges (tests/test_engine.py).  The engine's in-loop data plane is the fused
inline pipeline in ``median.step`` plus append-time range maintenance; only
with ``cut_kernel``/``extremes_kernel`` on does ``median.step`` route its
two per-turn scans through :func:`median_cut` / :func:`median_extremes`
here (see DESIGN.md §data plane).  Every ``use_pallas=None`` default is
``kernels.ops.on_tpu()``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.support_margin import BIG


def ranges(
    V: jnp.ndarray,      # (m, d) shared directions
    Wx: jnp.ndarray,     # (B, cap, d) transcripts
    Wy: jnp.ndarray,     # (B, cap) i32 labels, 0 = empty/padding
    *,
    use_pallas: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-direction consistent-threshold intervals (lo, hi), each (B, m);
    a missing class yields -inf/+inf."""
    if use_pallas:
        lo, hi = ops.support_ranges_batch(V, Wx, Wy)
        lo = jnp.where(lo <= -BIG / 2, -jnp.inf, lo)
        hi = jnp.where(hi >= BIG / 2, jnp.inf, hi)
    else:
        lo, hi = ref.threshold_ranges_batch_ref(V, Wx, Wy)
    return lo, hi


def median_cut(
    V: jnp.ndarray,       # (m, d)
    dir_ok: jnp.ndarray,  # (B, m) bool
    lo: jnp.ndarray,      # (B, m)
    hi: jnp.ndarray,      # (B, m)
    X: jnp.ndarray,       # (B, n, d)
    y: jnp.ndarray,       # (B, n) i32, 0 = padding
    *,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """Batched median-cut scores (int32 (B, m), -1 at disallowed cuts): the
    (B, m, n) weighted-median scan the MEDIAN coordinator argmaxes.  On TPU
    this is the fused ``kernels.median_cut`` Pallas kernel — one pallas_call
    for the whole sweep, never materializing the (B, m, n) risk tensor in
    HBM; elsewhere the jitted vmap reference.  Both produce identical
    integer scores (bit-for-bit, tested)."""
    use_pallas = ops.on_tpu() if use_pallas is None else use_pallas
    if use_pallas:
        return ops.support_median_cut_batch(
            V, dir_ok.astype(jnp.float32), lo, hi, X, y)
    return ref.median_cut_scores_batch_ref(V, dir_ok, lo, hi, X, y)


def median_extremes(
    v: jnp.ndarray,       # (B, d) per-instance proposed directions
    XW: jnp.ndarray,      # (B, k, nW, d) own ∪ fill-capped transcripts
    yW: jnp.ndarray,      # (B, k, nW) i32/f32, 0 = padding
    *,
    use_pallas: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched per-node extreme-point indices along v — MEDIAN's stage-5
    per-turn scan at the hot loop's fill-capped width.  On TPU the fused
    ``kernels.support_margin.median_extremes_batched`` Pallas kernel, else
    the jitted vmap reference; identical integer row choices (bit-for-bit,
    tested in tests/test_kernels_interpret.py)."""
    use_pallas = ops.on_tpu() if use_pallas is None else use_pallas
    if use_pallas:
        return ops.support_extremes_batch(v, XW, yW)
    return ref.median_extremes_batch_ref(v, XW, yW)


def uncertain(
    V: jnp.ndarray,       # (m, d)
    dir_ok: jnp.ndarray,  # (B, m) bool
    lo: jnp.ndarray,      # (B, m)
    hi: jnp.ndarray,      # (B, m)
    X: jnp.ndarray,       # (B, n, d)
    y: jnp.ndarray,       # (B, n) i32, 0 = padding
    *,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """Batched SOU membership, bool (B, n); padding rows report False."""
    use_pallas = ops.on_tpu() if use_pallas is None else use_pallas
    if use_pallas:
        mask = ops.support_uncertain_batch(V, dir_ok, lo, hi, X, y)
    else:
        mask = ref.uncertain_mask_batch_ref(V, dir_ok, lo, hi, X, y)
    return mask & (y != 0)
