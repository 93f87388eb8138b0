"""What every part of the benchmark shares: paths, the benchmark file,
the compile cache, devices and the checks that decide ``correct``."""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
from typing import Dict, List, Optional, Sequence

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# everything a run writes: the compile cache and traces (fixed paths, so
# a later run in the same checkout finds the cache)
OUT_DIR = os.path.join(ROOT, ".bench")
CACHE_DIR = os.path.join(OUT_DIR, "jax_cache")


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: Sequence[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def load_json(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the checkout's fixed path,
    holding every program however quickly it compiled."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: the directory is the benchmark's own, and eviction's
    # bookkeeping files are what made writes fail on the chip's host
    jax.config.update("jax_compilation_cache_max_size", -1)
    return CACHE_DIR


class CompileCounter:
    """Counts programs made ready for the backend (compiled, or loaded
    from the persistent cache) and the cache hits among them, from JAX's
    monitoring events."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name, _secs, **_kw):
        if name == self.COMPILE:
            self.compiles += 1

    def _on_event(self, name, **_kw):
        if name == self.HIT:
            self.hits += 1


def devices_or_exit(chips: int):
    """The devices of this run; exits non-zero, printing no result, when
    JAX finds no TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devs[0].platform})",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"bench: the cell asks for {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def peak_memory(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def load_peaks(device_kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (bench/peaks.json has "
                         f"{sorted(table)})")
    return table[device_kind]


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct iff value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return not math.isnan(self.value) and self.value <= self.limit


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (linear interpolation) of all values."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def check_lines(checks: List[Check]) -> str:
    return "\n".join(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
                     f"{'ok' if c.ok else 'FAILED'}" for c in checks)


def checks_json(checks: List[Check]) -> Dict[str, dict]:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers in ``bench/metrics`` read."""
    workload: dict
    config: dict
    traffic: dict
    host: Dict[str, object]
    trace: Optional[object] = None          # bench.trace.TraceSummary
    device_kind: str = ""
