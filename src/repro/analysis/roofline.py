"""Roofline analysis from AOT-compiled artifacts (no hardware required).

Sources:
* ``compiled.cost_analysis()``  — per-device HLO FLOPs and bytes accessed
  (XLA reports the post-SPMD per-device program);
* ``compiled.as_text()``        — per-device HLO, parsed for collective ops
  (all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute); per-op cost = sum of operand payload bytes.

Terms (seconds, per device == per step for the whole machine under SPMD):
    compute    = flops / peak FLOP/s
    memory     = bytes_accessed / HBM bytes/s
    collective = collective_bytes / ICI bytes/s per link

The peaks come from :data:`PEAKS`, keyed by the ``device_kind`` JAX
reports; a device that is not in the table is an error, not a default.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Any, Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""
    flops_bf16: float         # FLOP/s
    hbm_bw: float             # bytes/s
    ici_bw_per_link: float    # bytes/s per link
    hbm_bytes: float
    source: str


#: per-chip peaks keyed by ``jax.devices()[i].device_kind``
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_bw_per_link=50e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links"),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peaks of a device kind; raises for a device not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (table: {sorted(PEAKS)})") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*((?:pred|[a-z]+\d+[a-z0-9]*)\[[\d,]*\][^ ]*)"
    r"(?:[^=\n]*?)\s(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(", )
_SHAPE_RE = re.compile(r"(pred|[a-z]+\d+[a-z0-9]*)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _ring_factor(op: str, g: int) -> float:
    """Per-device wire bytes as a multiple of the op's *output* bytes, under
    standard ring-algorithm accounting with group size g."""
    if g <= 1:
        return 0.0
    if op == "all-gather":
        return (g - 1) / g
    if op == "reduce-scatter":
        return float(g - 1)           # input = g × output; (g-1)/g × input
    if op == "all-reduce":
        return 2.0 * (g - 1) / g
    if op == "all-to-all":
        return (g - 1) / g
    return 1.0                        # collective-permute


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Per-device collective wire bytes from post-SPMD HLO.

    Compiled HLO lists operands as bare %refs, so payloads are derived from
    each collective's *output* shape (per-device shard) scaled by the ring
    cost factor for its replica-group size."""
    per_op: Dict[str, float] = defaultdict(float)
    counts: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        out_shapes, op, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue  # payload was counted at -start
        total = 0
        for sm in _SHAPE_RE.finditer(out_shapes):
            total += _shape_bytes(sm.group(1), sm.group(2))
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            g = len(gl.group(1).split(",")) if gl else 2
        per_op[op] += total * _ring_factor(op, g)
        counts[op] += 1
    return {"bytes_by_op": {k: int(v) for k, v in per_op.items()},
            "counts": dict(counts),
            "total_bytes": int(sum(per_op.values()))}


@dataclasses.dataclass
class RooflineReport:
    name: str
    flops: float                    # per device
    bytes_accessed: float           # per device
    collective_bytes: float         # per device
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: Optional[float] = None   # 6·N·D (or 6·N_active·D) global
    useful_ratio: Optional[float] = None  # model_flops / (flops · chips)
    arg_bytes: int = 0
    temp_bytes: int = 0
    out_bytes: int = 0
    fits_hbm: Optional[bool] = None
    collectives: Optional[Dict] = None

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def analyze_compiled(name: str, compiled, *, chips: int, device_kind: str,
                     model_flops: Optional[float] = None) -> RooflineReport:
    """Roofline terms via the while-aware structural HLO model, against the
    peaks of ``device_kind``.

    ``compiled.cost_analysis()`` counts scan/while bodies once (verified —
    see analysis/hlo_cost.py), which undercounts layer-scanned models by the
    trip-count product; the structural walk multiplies loop bodies out."""
    from repro.analysis.hlo_cost import analyze_hlo
    hlo = compiled.as_text()
    structural = analyze_hlo(hlo)
    flops = float(structural["flops"])
    byts = float(structural["bytes"])
    ca = compiled.cost_analysis()
    colls = {"bytes_by_op": structural["bytes_by_op"],
             "counts": structural["counts"],
             "total_bytes": int(structural["collective_bytes"]),
             # naive (loop-body-once) numbers kept for reference
             "xla_flops_once": float((ca or {}).get("flops", 0.0))}
    cbytes = float(structural["collective_bytes"])

    chip = peaks(device_kind)
    compute_s = flops / chip.flops_bf16
    memory_s = byts / chip.hbm_bw
    coll_s = cbytes / chip.ici_bw_per_link
    dom = max(("compute", compute_s), ("memory", memory_s),
              ("collective", coll_s), key=lambda kv: kv[1])[0]

    mem = compiled.memory_analysis()
    arg_b = getattr(mem, "argument_size_in_bytes", 0)
    tmp_b = getattr(mem, "temp_size_in_bytes", 0)
    out_b = getattr(mem, "output_size_in_bytes", 0)
    alias_b = getattr(mem, "alias_size_in_bytes", 0)
    fits = (arg_b + tmp_b + out_b - alias_b) < chip.hbm_bytes

    useful = None
    if model_flops:
        useful = model_flops / max(flops * chips, 1.0)
    return RooflineReport(
        name=name, flops=flops, bytes_accessed=byts, collective_bytes=cbytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        dominant=dom, model_flops=model_flops, useful_ratio=useful,
        arg_bytes=arg_b, temp_bytes=tmp_b, out_bytes=out_b, fits_hbm=fits,
        collectives=colls)


def model_flops_estimate(cfg, shape) -> float:
    """6·N·D for training, 2·N·D for a forward/prefill, 2·N_active per
    decoded token (N = active params)."""
    n_act = cfg.active_param_count()
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        return 2.0 * n_act * tokens
    return 2.0 * n_act * shape.global_batch  # decode: one token per request
