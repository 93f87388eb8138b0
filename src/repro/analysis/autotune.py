"""Block-shape autotuner for the tiled Pegasos solver kernel.

The solver wrapper (``kernels.ops.pegasos_stage``) asks :func:`lookup_tile`
for ``(block_b, block_n, unroll)`` given the launch shape.  Lookup is pure
and deterministic:

1. the launch shape is bucketed (next power of two per axis, floors
   ``B ≥ 1``, ``N ≥ 8``, ``d ≥ 2``) — one tuning entry covers a bucket,
   not an exact shape, so compacted hot-loop fills with ragged ``N`` hit
   the same entry as their padded siblings;
2. the committed cache ``src/repro/kernels/tuning_cache.json`` is consulted
   with the key ``"{device_kind}|B{bB}_N{bN}_d{bd}"``;
3. on a miss (untuned bucket or a deleted cache file) the deterministic
   fallback table applies — keyed by device family and the d bucket only,
   so behaviour off the tuned grid is still reproducible and documented
   rather than an accident of search order.  A device family with no
   table (an accelerator other than a TPU) is an error, not CPU tiles.

``unroll`` only affects the jnp ref twin's ``fori_loop`` (the CPU fast
path); ``block_b``/``block_n`` only affect the Pallas launch.  Both live in
one entry so a bucket is tuned once per device kind.

The search half (:func:`search_bucket` / the ``__main__`` CLI) times each
candidate with the interleaved min-of-N harness (``benchmarks/_timing``),
filters candidates whose VMEM working set cannot fit, and records the
``roofline.analyze_compiled`` cost model of the winning configuration's
compiled stage next to the measured score, so the cache documents *why*
each winner won.  Winners are merged into the committed cache with
``--write``; CI never regenerates the cache (it is a committed artifact,
like ``BENCH_*.json``).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
from dataclasses import asdict, dataclass
from typing import Dict, Optional, Tuple

CACHE_PATH = os.path.join(os.path.dirname(__file__), os.pardir, "kernels",
                          "tuning_cache.json")

#: VMEM budget (bytes) a candidate's resident working set must fit in:
#: the X/y tiles plus the five f32 scratch buffers, double-buffered.
VMEM_BUDGET = 96 * 1024 * 1024 // 8


@dataclass(frozen=True)
class TileConfig:
    """One tuning decision: Pallas block shape + ref-twin unroll."""
    block_b: int
    block_n: int
    unroll: int


# Deterministic fallback: device kind -> d-bucket ceiling -> config.  The
# last row of each table (ceiling 0 == "anything larger") must always be
# present.  Chosen from the measured CPU sweep / TPU VMEM arithmetic, not
# per-shape search — good enough to be safe, never tuned-optimal.
_FALLBACK: Dict[str, Tuple[Tuple[int, TileConfig], ...]] = {
    "cpu": (
        (16, TileConfig(block_b=8, block_n=512, unroll=2)),
        (0, TileConfig(block_b=8, block_n=512, unroll=1)),
    ),
    "tpu": (
        (64, TileConfig(block_b=8, block_n=512, unroll=1)),
        (256, TileConfig(block_b=8, block_n=256, unroll=1)),
        (0, TileConfig(block_b=4, block_n=128, unroll=1)),
    ),
}


def _bucket_pow2(x: int, floor: int) -> int:
    x = max(int(x), floor)
    return 1 << (x - 1).bit_length()


def bucket(B: int, N: int, d: int) -> Tuple[int, int, int]:
    """Shape bucket for cache keying: next pow-2 with per-axis floors."""
    return _bucket_pow2(B, 1), _bucket_pow2(N, 8), _bucket_pow2(d, 2)


def cache_key(device_kind: str, B: int, N: int, d: int) -> str:
    bB, bN, bd = bucket(B, N, d)
    return f"{device_kind}|B{bB}_N{bN}_d{bd}"


def _normalize_kind(device_kind: str) -> str:
    """Map a jax ``device_kind`` string to a fallback-table family; raises
    for a device with no table."""
    kind = device_kind.lower()
    if "tpu" in kind:
        return "tpu"
    if kind in _FALLBACK:
        return kind
    raise ValueError(f"no Pegasos tile table for device kind "
                     f"{device_kind!r} (tables: {sorted(_FALLBACK)})")


@functools.lru_cache(maxsize=1)
def _load_cache(path: str = CACHE_PATH) -> Dict[str, dict]:
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return {}
    return data.get("entries", {}) if isinstance(data, dict) else {}


def fallback_tile(device_kind: str, d: int) -> TileConfig:
    """The deterministic no-cache answer (also the final lookup stage)."""
    table = _FALLBACK[_normalize_kind(device_kind)]
    for ceiling, cfg in table:
        if ceiling == 0 or d <= ceiling:
            return cfg
    return table[-1][1]


@functools.lru_cache(maxsize=256)
def lookup_tile(device_kind: str, B: int, N: int, d: int) -> TileConfig:
    """Resolve ``(block_b, block_n, unroll)`` for a solver launch shape.

    Committed-cache hit first (exact device kind, exact shape bucket),
    deterministic fallback otherwise.  Never raises on a malformed cache —
    a bad entry is a miss, not a crash (the fallback rule is the contract).
    """
    entry = _load_cache().get(cache_key(device_kind, B, N, d))
    if isinstance(entry, dict):
        try:
            return TileConfig(block_b=int(entry["block_b"]),
                              block_n=int(entry["block_n"]),
                              unroll=int(entry["unroll"]))
        except (KeyError, TypeError, ValueError):
            pass
    return fallback_tile(device_kind, d)


# ----------------------------------------------------------------------
# Search half — imports jax/benchmarks lazily so lookup stays dep-free.
# ----------------------------------------------------------------------

#: candidate axes; the cross-product is pruned by the VMEM fit check
CANDIDATE_BLOCK_N = (128, 256, 512, 1024)
CANDIDATE_BLOCK_B = (1, 4, 8, 16)
CANDIDATE_UNROLL = (1, 2, 4)


def vmem_bytes(block_b: int, block_n: int, d: int) -> int:
    """Resident f32 working set of one grid step (double-buffered tiles)."""
    tiles = block_b * block_n * (d + 1) * 2          # X + y, double-buffered
    scratch = block_b * (2 * d + 3)                  # w, g, b, gb, mm
    return 4 * (tiles + scratch)


def _candidates(B: int, N: int, d: int):
    for bn in CANDIDATE_BLOCK_N:
        if bn > _bucket_pow2(N, 8):
            continue
        for bb in CANDIDATE_BLOCK_B:
            if bb > _bucket_pow2(B, 1):
                continue
            if vmem_bytes(bb, bn, d) > VMEM_BUDGET:
                continue
            for u in CANDIDATE_UNROLL:
                yield TileConfig(block_b=bb, block_n=bn, unroll=u)


def search_bucket(B: int, N: int, d: int, *, nsteps: int = 200,
                  repeats: int = 5, seed: int = 0) -> dict:
    """Tune one shape bucket on the *current* backend.

    Off-TPU the measured path is the jnp ref twin, so the search axis that
    matters is ``unroll`` (block shapes are carried along and scored by the
    VMEM model only); on TPU the Pallas launch itself is timed, so all
    three axes are live.  Returns the winning cache entry (not yet merged).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import _timing
    from repro.analysis import roofline
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((B, N, d)), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], size=(B, N)), jnp.float32)
    nv = jnp.full((B,), float(N), jnp.float32)
    w = jnp.zeros((B, d), jnp.float32)
    b = jnp.zeros((B,), jnp.float32)
    lam = jnp.full((B,), 1e-3, jnp.float32)
    found = jnp.zeros((B,), bool)
    wb = jnp.zeros((B, d), jnp.float32)
    bb0 = jnp.zeros((B,), jnp.float32)
    on_tpu = jax.default_backend() == "tpu"

    series = {}
    cfgs = list(_candidates(B, N, d))
    for cfg in cfgs:
        def run(cfg=cfg):
            out = ops.pegasos_stage(
                X, y, nv, w, b, lam, found, wb, bb0, nsteps=nsteps,
                use_pallas=on_tpu, block_b=cfg.block_b,
                block_n=cfg.block_n, unroll=cfg.unroll)
            return jax.block_until_ready(out[0])
        run()                                        # compile outside timing
        series[f"b{cfg.block_b}_n{cfg.block_n}_u{cfg.unroll}"] = run
    _, times = _timing.interleaved(series, repeats=repeats)
    scored = sorted(
        (( _timing.tmin(times, f"b{c.block_b}_n{c.block_n}_u{c.unroll}"), c)
         for c in cfgs), key=lambda t: t[0])
    best_s, best = scored[0]

    kind = jax.devices()[0].device_kind
    entry = {**asdict(best), "score_us": best_s * 1e6, "nsteps": nsteps,
             "measured_path": "pallas" if on_tpu else "ref"}
    if on_tpu:
        # cost model of the winner against the chip's published peaks,
        # recorded alongside the measurement
        fn = jax.jit(functools.partial(
            ref.pegasos_stage_batch_ref, nsteps=nsteps, unroll=best.unroll))
        compiled = fn.lower(X, y, nv, w, b, lam, found, wb, bb0).compile()
        report = roofline.analyze_compiled(
            f"pegasos_B{B}_N{N}_d{d}", compiled, chips=1, device_kind=kind)
        model_s = max(report.compute_s, report.memory_s,
                      report.collective_s)
        entry["roofline"] = {
            "dominant": report.dominant,
            "intensity": round(report.flops
                               / max(report.bytes_accessed, 1.0), 3),
            "model_us": model_s * 1e6}
    return {"key": cache_key(kind, B, N, d), "entry": entry}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shapes", nargs="+", default=["16x512x2", "16x512x16",
                                                    "16x512x64"],
                    help="BxNxd launch shapes to tune (one bucket each)")
    ap.add_argument("--nsteps", type=int, default=200)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--write", action="store_true",
                    help="merge winners into the committed tuning cache")
    args = ap.parse_args(argv)

    entries: Dict[str, dict] = dict(_load_cache())
    for spec in args.shapes:
        B, N, d = (int(t) for t in spec.split("x"))
        won = search_bucket(B, N, d, nsteps=args.nsteps,
                            repeats=args.repeats)
        print(f"{won['key']}: {won['entry']}")
        entries[won["key"]] = won["entry"]
    if args.write:
        payload = {"format": 1, "entries": dict(sorted(entries.items()))}
        with open(CACHE_PATH, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        _load_cache.cache_clear()
        lookup_tile.cache_clear()
        print(f"wrote {CACHE_PATH} ({len(entries)} entries)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
