import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (JAX locks the platform
# and device count at first initialization; on a TPU host the CPU pin keeps
# the dry run off the chip).  Everything below is ordinary code.

"""Multi-pod dry run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware:

* builds the production mesh (16×16 single-pod or 2×16×16 multi-pod),
* lowers `train_step` (train shapes) or `prefill`/`decode_step`
  (serve shapes) with full production shardings,
* compiles, prints ``memory_analysis()`` (fits?) and ``cost_analysis()``
  (FLOPs/bytes),
* parses the optimized HLO for collective bytes / scan-scaled FLOPs,
* writes a JSON record (+ zstd-compressed HLO) under ``experiments/dryrun/``.

Usage:  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
            --shape train_4k [--multi-pod] [--seq-sharded] [--tag name]
"""

import argparse
import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.analysis import hlo as hlo_analysis
from repro.analysis import roofline as rl
from repro.configs import get_config
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.models.config import SHAPES, shape_applicable
from repro.optim import AdamWConfig, adamw_update
from repro.sharding import ShardingPolicy

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def build_train_step(cfg, policy, opt_cfg):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lm.train_loss)(params, batch, cfg,
                                                        policy)
        new_p, new_s, metrics = adamw_update(grads, opt_state, params, opt_cfg)
        return new_p, new_s, {"loss": loss, **metrics}
    return train_step


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               seq_sharded: bool = False, quantize_acts: bool = True,
               weight_bits=4, remat: bool = True,
               serve_replicated_weights: bool = False,
               bf16_params: bool = False):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = ShardingPolicy(
        mesh=mesh, multi_pod=multi_pod, seq_sharded=seq_sharded,
        serve_replicated_weights=(serve_replicated_weights
                                  and shape.kind == "decode"))
    # replicating weights over 'data' trades the FSDP all-gather for 16×
    # weight HBM reads — a win only when each step reads weights once per
    # token (decode); prefill amortizes the gather over 32k tokens.

    params = S.param_struct(cfg, jnp.bfloat16 if bf16_params else jnp.float32)
    params_sh = policy.params_shardings(params)
    batch = S.input_specs(cfg, shape)

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        opt = S.opt_struct(params, opt_cfg)
        opt_sh = S.opt_shardings(opt, params_sh, policy)
        batch_sh = S.batch_shardings(batch, policy)
        step = build_train_step(
            cfg, policy,
            dataclasses.replace(opt_cfg))
        fn = jax.jit(step,
                     in_shardings=(params_sh, opt_sh, batch_sh),
                     out_shardings=(params_sh, opt_sh, None),
                     donate_argnums=(0, 1))
        args = (params, opt, batch)
    else:
        serve = S.make_serve_config(cfg, quantize_acts=quantize_acts,
                                    weight_bits=weight_bits)
        sparams = S.serve_param_struct(cfg, serve.weight_bits)
        sparams_sh = policy.params_shardings(sparams)
        if shape.kind == "prefill":
            batch_sh = S.batch_shardings(batch, policy, shape.global_batch)

            def prefill_step(p, b):
                return lm.prefill(p, b, cfg, serve, policy)
            fn = jax.jit(prefill_step,
                         in_shardings=(sparams_sh, batch_sh),
                         out_shardings=None)
            args = (sparams, batch)
        else:
            cache = S.cache_struct(cfg, shape, serve)
            cache_sh = S.cache_shardings(cache, policy, shape.global_batch)
            tok_sh = S.batch_shardings(
                {"tokens": batch["tokens"]}, policy,
                shape.global_batch)["tokens"]

            def decode(p, c, tokens, pos):
                return lm.decode_step(p, c, tokens, pos, cfg, serve, policy)
            from jax.sharding import PartitionSpec as P
            fn = jax.jit(decode,
                         in_shardings=(sparams_sh, cache_sh, tok_sh,
                                       policy.named(P())),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,))
            args = (sparams, cache, batch["tokens"], batch["pos"])

    t0 = time.time()
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return {"status": "ok", "compiled": compiled, "cfg": cfg, "shape": shape,
            "t_lower": t_lower, "t_compile": t_compile,
            "chips": mesh.devices.size}


def analyze(result: dict, save_hlo: str = "") -> dict:
    compiled = result["compiled"]
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    stats = hlo_analysis.analyze_hlo_text(text)
    roof = rl.compute_roofline(stats, result["cfg"], result["shape"],
                               result["chips"])
    record = {
        "status": "ok",
        "chips": result["chips"],
        "t_lower_s": round(result["t_lower"], 1),
        "t_compile_s": round(result["t_compile"], 1),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
        },
        "xla_cost_analysis": {
            "flops_per_device_scan_body_once": cost.get("flops"),
            "bytes_accessed_scan_body_once": cost.get("bytes accessed"),
        },
        "hlo_stats": stats,
        "roofline": rl.summarize(roof),
        "hlo_len": len(text),
    }
    if save_hlo:
        import zstandard
        data = zstandard.ZstdCompressor(level=3).compress(text.encode())
        pathlib.Path(save_hlo).write_bytes(data)
        record["hlo_path"] = save_hlo
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--seq-sharded", action="store_true",
                    help="sequence-parallel residual stream (perf variant)")
    ap.add_argument("--no-stamp", action="store_true",
                    help="disable STaMP activation quantization in serving")
    ap.add_argument("--weight-bits", type=int, default=4)
    ap.add_argument("--serve-replicated-weights", action="store_true")
    ap.add_argument("--bf16-params", action="store_true",
                    help="store parameters in bf16 (f32 Adam moments)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out-dir", default="")
    args = ap.parse_args()

    global OUT_DIR
    if args.out_dir:
        OUT_DIR = pathlib.Path(args.out_dir)
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    mesh_tag = "multipod" if args.multi_pod else "singlepod"
    stem = f"{args.arch}_{args.shape}_{mesh_tag}"
    if args.seq_sharded:
        stem += "_sp"
    if args.no_stamp:
        stem += "_nostamp"
    if args.tag:
        stem += f"_{args.tag}"

    result = lower_cell(
        args.arch, args.shape, multi_pod=args.multi_pod,
        seq_sharded=args.seq_sharded,
        quantize_acts=not args.no_stamp,
        weight_bits=args.weight_bits or None,
        serve_replicated_weights=args.serve_replicated_weights,
        bf16_params=args.bf16_params)
    if result["status"] == "skipped":
        record = result
        print(f"SKIPPED: {result['reason']}")
    else:
        compiled = result["compiled"]
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline
        hlo_path = str(OUT_DIR / f"{stem}.hlo.zst") if args.save_hlo else ""
        record = analyze(result, save_hlo=hlo_path)
        print(json.dumps(record["roofline"], indent=2))

    out = OUT_DIR / f"{stem}.json"
    out.write_text(json.dumps(record, indent=2, default=str))
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
