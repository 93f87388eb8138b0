"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read:

* device planes ``/device:TPU:<i>``: the ``XLA Modules`` line holds one
  event per program execution, named ``jit_<function>(<fingerprint>)``;
  the ``XLA Ops`` line holds the operations inside them, each named by its
  HLO text ``%<op>.<n> = ...`` (a Pallas kernel shows as the name of its
  ``pallas_call``, e.g. ``%pegasos_stage_batched.3``);
* the host plane ``/host:CPU``: the benchmark's own spans, named
  ``bench.<what>`` (``jax.profiler.TraceAnnotation``), on the same clock.

The traced window is the ``bench.window`` span.  Busy time is the union of
module executions inside it, per device; an idle gap is attributed to the
innermost ``bench.*`` span other than the window that covers its middle,
or to ``untraced host`` where none does.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
_OP_NAME = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?(?:\s|=|$)")
_MODULE_NAME = re.compile(r"^(.*?)(?:\(\d+\))?$")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    n_devices: int
    busy_s: float                                   # mean over devices
    modules: Dict[str, Tuple[int, float]]           # name -> (count, s)
    ops: Dict[str, Tuple[int, float]]               # short op name -> ...
    idle_by_host: Dict[str, float]                  # host span -> idle s

    def module(self, name: str) -> Tuple[int, float]:
        return self.modules.get(name, (0, 0.0))

    def op(self, prefix: str) -> Tuple[int, float]:
        """Count and seconds of the ops whose short name starts with
        ``prefix`` (summed over devices)."""
        n, s = 0, 0.0
        for name, (c, t) in self.ops.items():
            if name.startswith(prefix):
                n, s = n + c, s + t
        return n, s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, t] for n, (_c, t) in ops],
                "idle_gaps": [[n, t] for n, t in gaps]}


def op_name(hlo_text: str) -> str:
    """``%pegasos_stage_batched.3 = (f32[...]) ...`` -> the op's name
    without its numeric suffix."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def module_name(event_name: str) -> str:
    """``jit__hot_turn_impl(1773...)`` -> ``jit__hot_turn_impl``."""
    return _MODULE_NAME.match(event_name).group(1)


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str):
    """ProfileData from an ``.xplane.pb`` file (gzipped or not)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce(profile) -> TraceSummary:
    """Reduce a loaded trace to the window's device numbers."""
    host_spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not devices:
        raise ValueError("trace holds no TPU device plane")

    mods: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    ops: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    busy_per_dev, dev_busy0 = [], None
    lo, hi = (windows[-1] if windows else (float("-inf"), float("inf")))
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for ev in line.events:
                iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if iv is None:
                    continue
                dur = (iv[1] - iv[0]) * 1e-9
                if line.name == "XLA Modules":
                    rec = mods[module_name(ev.name)]
                    intervals.append(iv)
                else:
                    rec = ops[op_name(ev.name)]
                rec[0] += 1
                rec[1] += dur
        if not intervals:          # a device this run did not use
            continue
        merged = _union(intervals)
        busy_per_dev.append(sum(e - s for s, e in merged) * 1e-9)
        if dev_busy0 is None:
            dev_busy0 = merged
    if not busy_per_dev:
        raise ValueError("no program ran on a device in the traced window")
    if not windows:            # no window span: the device events' extent
        lo, hi = dev_busy0[0][0], dev_busy0[-1][1]

    # idle gaps of the first device, by the host span covering their middle
    idle: Dict[str, float] = collections.defaultdict(float)
    inner = sorted((e - s, n, s, e) for n, s, e in host_spans
                   if n != WINDOW_SPAN)
    edges = [lo] + [x for iv in dev_busy0 for x in iv] + [hi]
    for gs, ge in zip(edges[0::2], edges[1::2]):
        if ge <= gs:
            continue
        mid = 0.5 * (gs + ge)
        owner = next((n for _d, n, s, e in inner if s <= mid <= e),
                     "untraced host")
        idle[owner] += (ge - gs) * 1e-9

    n_dev = len(busy_per_dev)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, n_devices=n_dev,
        busy_s=sum(busy_per_dev) / n_dev,
        modules={k: (int(c), t) for k, (c, t) in mods.items()},
        ops={k: (int(c), t) for k, (c, t) in ops.items()},
        idle_by_host=dict(idle))


def summarize(log_dir: str) -> Optional[TraceSummary]:
    """The summary of the newest trace under ``log_dir``."""
    return reduce(load(find_xplane(log_dir)))
