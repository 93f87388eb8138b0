import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch × input-shape × mesh).

The two lines above MUST stay the first statements — jax locks the device
count at first init, and the production meshes need 512 host placeholders.
Never set that flag globally (smoke tests and benches must see 1 device).

For every case this script:
  1. builds ShapeDtypeStruct stand-ins for params / optimizer / batch / caches
     (zero allocation),
  2. lowers the jit'd step with explicit in/out shardings on the production
     mesh — train_4k lowers ``train_step``, prefill_32k lowers ``prefill``,
     decode shapes lower ``serve_step`` (one token against seq_len caches),
  3. compiles, prints memory_analysis() (proof of fit) and cost_analysis()
     (roofline terms), parses collective bytes from the HLO,
  4. appends a JSON record consumed by EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out benchmarks/results/dryrun.jsonl
"""

import argparse
import dataclasses
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.analysis.roofline import analyze_compiled, model_flops_estimate
from repro.configs import ARCHS, get_config
from repro.data.pipeline import dec_len, make_batch_specs
from repro.distribution.sharding import (
    batch_shardings, cache_shardings, opt_shardings, param_shardings)
from repro.launch.mesh import make_production_mesh
from repro.models.config import INPUT_SHAPES, InputShape, ModelConfig
from repro.models.model import RunFlags, decode_step, init_lm, make_caches, prefill
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.train.trainer import TrainConfig, make_train_step


@dataclasses.dataclass
class CasePolicy:
    """Execution policy for one (arch, shape): what the launcher would set."""
    skip: Optional[str] = None
    window: Optional[int] = None
    cache_len: int = 0
    enc_len: int = 0
    microbatches: int = 1
    param_dtype: Any = jnp.float32
    moment_dtype: str = "f32"
    fsdp: bool = False
    pure_dp: bool = False
    mla_absorb: bool = False
    remat: bool = True
    block_q: int = 1024
    loss_chunk: int = 512


def case_policy(cfg: ModelConfig, shape: InputShape) -> CasePolicy:
    pol = CasePolicy()
    n = cfg.param_count()
    pol.fsdp = n > 20e9
    # small models: tensor parallelism replicates whole mixers when head
    # counts don't divide the model axis — run them pure data-parallel.
    # Train shapes: the global batch divides the full mesh, so pure-DP wins
    # for everything under ~3B.  Serving shapes keep TP unless the model is
    # tiny (<0.5B — replicated weights are free and smollm's 9-head TP
    # prefill was 1700× collective-over-compute); mid-size serving under
    # pure-DP regressed 4-9× in the sweep (EXPERIMENTS.md §Perf).
    # decode always keeps TP: even when heads replicate, TP shards the KV
    # cache head_dim 16× (smollm pure-DP decode regressed 15× on memory).
    if shape.kind == "train":
        pol.pure_dp = n < 3e9
    elif shape.kind == "prefill":
        pol.pure_dp = n < 0.5e9
    else:
        pol.pure_dp = False
    pol.param_dtype = jnp.float32 if (shape.kind == "train" and n <= 20e9) else jnp.bfloat16
    pol.moment_dtype = "bf16" if n > 20e9 else "f32"
    pol.microbatches = 8 if n > 50e9 else (4 if n > 3e9 else 1)
    if cfg.enc_dec:
        pol.enc_len = shape.seq_len if shape.kind != "decode" else 1500
    if shape.kind == "decode":
        pol.cache_len = shape.seq_len
        if shape.name == "long_500k":
            if cfg.enc_dec:
                pol.skip = ("enc-dec full-attention decoder: 500k-token decode is "
                            "out of family scope (DESIGN.md §Arch-applicability)")
            elif cfg.sliding_window and not cfg.has_state_mixer and cfg.mla is None:
                # dense/vlm/standard-MoE attention: sliding-window variant
                pol.window = cfg.sliding_window
                pol.cache_len = cfg.sliding_window
            # SSM/hybrid run natively; MLA runs on its compressed latent cache
    if shape.kind != "train":
        pol.remat = False
    pol.loss_chunk = min(512, dec_len(cfg, shape.seq_len))
    return pol


def lower_case(cfg: ModelConfig, shape: InputShape, mesh, pol: CasePolicy):
    """Build + lower the jitted step for one case. Returns (lowered, meta)."""
    from repro.distribution.constraints import set_dp_axes
    if pol.pure_dp and shape.global_batch % mesh.devices.size != 0:
        # pure-DP only pays when the global batch fills the whole mesh
        # (256 % 512 ≠ 0 regressed smollm 2× on the multi-pod sweep)
        pol.pure_dp = False
    set_dp_axes(("pod", "data", "model") if pol.pure_dp else None)
    flags = RunFlags(window=pol.window, mla_absorb=pol.mla_absorb,
                     block_q=pol.block_q, remat=pol.remat,
                     loss_chunk=pol.loss_chunk)
    pshapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg, pol.param_dtype))
    psh = param_shardings(mesh, pshapes, fsdp=pol.fsdp, pure_dp=pol.pure_dp)

    if shape.kind == "train":
        tc = TrainConfig(dtype=jnp.bfloat16, microbatches=pol.microbatches,
                         optim=AdamWConfig(moment_dtype=pol.moment_dtype),
                         flags=flags)
        step = make_train_step(cfg, tc)
        oshapes = jax.eval_shape(partial(adamw_init, moment_dtype=pol.moment_dtype),
                                 pshapes)
        osh = opt_shardings(mesh, oshapes, fsdp=pol.fsdp, pure_dp=pol.pure_dp)
        bspecs = make_batch_specs(cfg, shape)
        bsh = batch_shardings(mesh, bspecs, shape, pure_dp=pol.pure_dp)
        fn = jax.jit(step, in_shardings=(psh, osh, bsh),
                     out_shardings=(psh, osh, None), donate_argnums=(0, 1))
        return fn.lower(pshapes, oshapes, bspecs)

    if shape.kind == "prefill":
        Sd = dec_len(cfg, shape.seq_len)
        cshapes = jax.eval_shape(lambda: make_caches(
            cfg, shape.global_batch, Sd, jnp.bfloat16, enc_len=pol.enc_len))
        csh = cache_shardings(mesh, cshapes, shape, cfg, pure_dp=pol.pure_dp)
        bspecs = make_batch_specs(cfg, shape)
        bsh = batch_shardings(mesh, bspecs, shape, pure_dp=pol.pure_dp)

        def prefill_fn(params, batch, caches):
            return prefill(params, cfg, batch, caches, flags, dtype=jnp.bfloat16)

        fn = jax.jit(prefill_fn, in_shardings=(psh, bsh, csh),
                     out_shardings=(None, csh), donate_argnums=(2,))
        return fn.lower(pshapes, bspecs, cshapes)

    # decode
    cshapes = jax.eval_shape(lambda: make_caches(
        cfg, shape.global_batch, pol.cache_len, jnp.bfloat16, enc_len=pol.enc_len))
    csh = cache_shardings(mesh, cshapes, shape, cfg, pure_dp=pol.pure_dp)
    bspecs = make_batch_specs(cfg, shape)
    bsh = batch_shardings(mesh, bspecs, shape, pure_dp=pol.pure_dp)

    def serve_step(params, caches, tokens, pos):
        return decode_step(params, cfg, caches, tokens, pos, flags, dtype=jnp.bfloat16)

    fn = jax.jit(serve_step, in_shardings=(psh, csh, bsh["tokens"], None),
                 out_shardings=(None, csh), donate_argnums=(1,))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return fn.lower(pshapes, cshapes, bspecs["tokens"], pos)


def run_case(arch: str, shape_name: str, mesh_kind: str,
             overrides: Optional[Dict] = None, verbose: bool = True) -> Dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    pol = case_policy(cfg, shape)
    for k, v in (overrides or {}).items():
        setattr(pol, k, v)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                           "policy": {k: str(v) for k, v in dataclasses.asdict(pol).items()}}
    if pol.skip:
        rec["status"] = "skipped"
        rec["reason"] = pol.skip
        return rec
    multi = mesh_kind == "multi"
    chips = 512 if multi else 256
    mesh = make_production_mesh(multi_pod=multi)
    try:
        t0 = time.time()
        with jax.set_mesh(mesh):  # ambient mesh: activation constraints bind
            lowered = lower_case(cfg, shape, mesh, pol)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        rep = analyze_compiled(f"{arch}/{shape_name}/{mesh_kind}", compiled,
                               chips=chips, device_kind="TPU v5 lite",
                               model_flops=model_flops_estimate(cfg, shape))
        rec.update(status="ok", lower_s=round(t1 - t0, 2),
                   compile_s=round(t2 - t1, 2), roofline=rep.as_dict())
        if verbose:
            print(f"[ok] {arch:24s} {shape_name:12s} {mesh_kind:6s} "
                  f"compile={t2 - t1:6.1f}s flops/dev={rep.flops:.3e} "
                  f"mem/dev={(rep.arg_bytes + rep.temp_bytes) / 1e9:6.2f}GB "
                  f"coll/dev={rep.collective_bytes / 1e6:8.1f}MB dom={rep.dominant}")
            print("   memory_analysis:", compiled.memory_analysis())
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[ERR] {arch} {shape_name} {mesh_kind}: {e}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", choices=["all"] + list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                rec = run_case(arch, shape, mesh_kind, verbose=not args.quiet)
                if args.out:
                    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skipped"
                n_err += rec["status"] == "error"
    print(f"\nDRY-RUN SUMMARY: ok={n_ok} skipped={n_skip} errors={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
