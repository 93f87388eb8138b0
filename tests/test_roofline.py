"""Roofline machinery tests: the while-aware HLO cost model must multiply
loop bodies by trip count (XLA's cost_analysis does not — the reason this
model exists) and count dot flops / collective wire bytes correctly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_cost import analyze_hlo
from repro.analysis.roofline import parse_collectives, _ring_factor


def _compiled(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_single_matmul_flops_exact():
    c = _compiled(lambda a, b: a @ b, (64, 128), (128, 32))
    r = analyze_hlo(c.as_text())
    assert r["flops"] == pytest.approx(2 * 64 * 128 * 32, rel=0.01)


def test_scan_multiplies_flops_by_trip_count():
    def f(x):
        def body(c, _):
            return c @ c, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out
    c = _compiled(f, (128, 128))
    r = analyze_hlo(c.as_text())
    single = analyze_hlo(_compiled(lambda x: x @ x, (128, 128)).as_text())
    assert r["flops"] == pytest.approx(10 * single["flops"], rel=0.05)
    # XLA's own counter reports the body once — document the discrepancy.
    ca = c.cost_analysis()
    assert float(ca["flops"]) < r["flops"] / 5


def test_nested_scan_multiplies_product():
    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=4)
        return out
    c = _compiled(f, (64, 64))
    r = analyze_hlo(c.as_text())
    single = analyze_hlo(_compiled(lambda x: x @ x, (64, 64)).as_text())
    assert r["flops"] == pytest.approx(12 * single["flops"], rel=0.05)


def test_batched_dot_flops():
    c = _compiled(lambda a, b: jnp.einsum("bij,bjk->bik", a, b),
                  (8, 32, 64), (8, 64, 16))
    r = analyze_hlo(c.as_text())
    assert r["flops"] == pytest.approx(2 * 8 * 32 * 64 * 16, rel=0.01)


def test_bytes_nonzero_and_sane():
    c = _compiled(lambda a: a * 2.0 + 1.0, (1024, 1024))
    r = analyze_hlo(c.as_text())
    nbytes = 1024 * 1024 * 4
    # at least read + write; fused elementwise should stay within a few x
    assert nbytes * 1.5 <= r["bytes"] <= nbytes * 6


def test_ring_factors():
    assert _ring_factor("all-gather", 8) == pytest.approx(7 / 8)
    assert _ring_factor("all-reduce", 8) == pytest.approx(2 * 7 / 8)
    assert _ring_factor("reduce-scatter", 8) == 7.0
    assert _ring_factor("all-gather", 1) == 0.0


def test_parse_collectives_from_text():
    hlo = """
ENTRY %main (p: f32[16,128]) -> f32[16,128] {
  %p = f32[16,128]{1,0} parameter(0)
  ROOT %ar = f32[16,128]{1,0} all-reduce(%p), replica_groups=[4,8]<=[32], to_apply=%add
}
"""
    r = parse_collectives(hlo)
    payload = 16 * 128 * 4
    assert r["counts"]["all-reduce"] == 1
    assert r["total_bytes"] == int(payload * 2 * 7 / 8)


def test_peaks_are_keyed_by_device_kind():
    from repro.analysis.roofline import peaks
    v5e = peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "Google Cloud" in v5e.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "NVIDIA H100"])
def test_unknown_device_kind_has_no_peaks(kind):
    from repro.analysis.roofline import peaks
    with pytest.raises(ValueError, match="no published peaks"):
        peaks(kind)
