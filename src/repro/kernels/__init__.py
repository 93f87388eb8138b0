"""Pallas TPU kernels for the framework's compute hot-spots.

Six kernels, each with the ``<name>.py`` (pl.pallas_call + BlockSpec) /
``ops.py`` (jit'd padding + dispatch wrapper) / ``ref.py`` (pure-jnp oracle)
layout:

  flash_attention  tiled online-softmax GQA attention (causal/sliding-window)
  rwkv6            chunked closed-form WKV recurrence (Finch)
  mamba            blocked selective scan
  support_margin   the paper's data-plane hot loop: fused direction×point
                   projection with masked range / any reductions
  median_cut       the MEDIAN selector's (B, m, n) weighted-median cut scan
                   (first/last at-risk direction per point, integer side
                   counts per cut)
  pegasos          the MAXMARG refit solver: one whole Pegasos λ stage per
                   launch (hinge gradient accumulated across N-tiles in f32
                   VMEM scratch, first-0-error latch fused), block shapes
                   from the committed autotune cache

All are validated on CPU via ``interpret=True`` against the oracles
(tests/test_kernels.py), and the protocol kernels compile for a described
TPU v5e (tests/test_tpu_compile.py).
"""

from repro.kernels import ops, ref  # noqa: F401
from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.mamba import mamba_scan  # noqa: F401
from repro.kernels.median_cut import median_cut_scores_batched  # noqa: F401
from repro.kernels.pegasos import pegasos_stage_batched  # noqa: F401
from repro.kernels.rwkv6 import rwkv6_chunked  # noqa: F401
from repro.kernels.support_margin import (  # noqa: F401
    maxmarg_turn_scan_batched, median_extremes_batched,
    threshold_ranges_batched, uncertain_mask_batched)
