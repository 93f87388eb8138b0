"""Pegasos tile lookup: committed-cache hits, per-family fallback tables,
and no silent CPU tiles for an accelerator without a table."""

import pytest

from repro.analysis import autotune


def test_tpu_kind_uses_the_tpu_fallback_table():
    cfg = autotune.lookup_tile("TPU v5 lite", 32, 2304, 10)
    assert cfg == autotune._FALLBACK["tpu"][0][1]


def test_cpu_kind_hits_the_committed_cache():
    cfg = autotune.lookup_tile("cpu", 16, 512, 2)
    assert (cfg.block_b, cfg.block_n, cfg.unroll) == (1, 128, 2)


@pytest.mark.parametrize("kind", ["NVIDIA H100", "gpu"])
def test_accelerator_without_a_table_raises(kind):
    with pytest.raises(ValueError, match="no Pegasos tile table"):
        autotune.lookup_tile(kind, 16, 512, 2)
