import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, and record memory/cost/collective analysis.

  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both

The XLA_FLAGS and JAX_PLATFORMS lines above MUST stay the first statements —
jax locks the device count at first init, and the dry-run (and only the
dry-run) needs 512 placeholder host devices for `jax.make_mesh((2,16,16),
...)`. It is a CPU-only tool: pinning the platform keeps it off an attached
accelerator, which belongs to one process at a time.

Each run writes experiments/dryrun/<arch>__<shape>__<mesh>.json with:
  * per-device memory_analysis (argument/output/temp bytes) — proves it fits,
  * cost_analysis FLOPs + bytes (per device, per step),
  * collective op counts/bytes parsed from the partitioned HLO,
  * the three §Roofline terms and the dominant bottleneck.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.common.meshctx import cost_analysis_dict, use_mesh
from repro.common.sharding import set_policy
from repro.configs import ARCHITECTURES, get_config
from repro.launch.hlo_analysis import parse_collectives, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SHAPES, cache_structs, input_specs, variant_for_shape
from repro.launch.hbm_model import analytic_hbm_bytes
from repro.launch.state_specs import opt_state_structs
from repro.models import model as M
from repro.models.params import param_structs
from repro.training.train_step import TrainConfig, make_train_step


def build_program(cfg, shape, mesh, tc: TrainConfig, quantize: bool = False):
    """Returns (fn, arg_structs tuple) for the shape's program kind.

    `quantize=True` (inference only): lower over int8 weights with an inline
    dequant at the program boundary — XLA fuses it into the consumer matmuls
    (see models/quant.py)."""
    specs = M.make_specs(cfg)
    dtype = jnp.dtype(cfg.dtype)
    if quantize and shape.kind != "train":
        from repro.models.quant import dequantize_tree, quantized_structs

        pstructs = quantized_structs(specs, mesh=mesh, dtype=dtype)
        deq = lambda qp: dequantize_tree(qp, dtype)
    else:
        pstructs = param_structs(specs, dtype=dtype, mesh=mesh)
        deq = lambda p: p
    batch = input_specs(cfg, shape, mesh)
    if shape.kind == "train":
        cfg = dataclasses.replace(cfg, remat=True)  # activation checkpointing
        step_fn, _ = make_train_step(cfg, tc)
        opt_name = tc.optimizer
        if opt_name == "auto":
            opt_name = "adafactor" if cfg.param_count() > 30e9 else "adamw"
        ostructs = opt_state_structs(opt_name, specs, mesh)
        return step_fn, (pstructs, ostructs, batch)
    if shape.kind == "prefill":
        fn = lambda p, b: M.prefill(cfg, deq(p), b, max_cache_len=shape.seq_len)
        return fn, (pstructs, batch)
    # decode
    cache = cache_structs(cfg, shape, mesh)
    fn = lambda p, c, b: M.decode_step(cfg, deq(p), c, b)
    return fn, (pstructs, cache, batch)


def _probe_depths(cfg) -> tuple:
    """Two shallow depths for unrolled cost probes (VLM keeps its 4+1 groups)."""
    if cfg.cross_attn_every:
        return cfg.cross_attn_every, 2 * cfg.cross_attn_every
    return 2, 4


def _measure(cfg, shape, mesh, tc, quantize=False):
    """Compile and return (flops, bytes, wire_bytes) per device for cfg."""
    fn, args = build_program(cfg, shape, mesh, tc, quantize)
    with use_mesh(mesh):
        compiled = jax.jit(fn).lower(*args).compile()
    cost = cost_analysis_dict(compiled)
    colls = parse_collectives(compiled.as_text())
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        float(colls.wire_bytes),
        colls,
    )


def probe_corrected_costs(cfg, shape, mesh, tc, quantize=False):
    """XLA cost analysis counts while-loop bodies ONCE, so a scanned L-layer
    model under-reports by ~L x. We compile two shallow *unrolled* variants
    (scan_unroll=True removes every while loop) and linearly extrapolate:
    metric(L) = intercept + slope * L. Exact for everything linear in depth
    (per-layer flops, bytes, and per-layer collectives), with embed/head/
    optimizer costs captured by the intercept."""
    l1, l2 = _probe_depths(cfg)
    c1 = dataclasses.replace(cfg, n_layers=l1, scan_unroll=True)
    c2 = dataclasses.replace(cfg, n_layers=l2, scan_unroll=True)
    m1 = _measure(c1, shape, mesh, tc, quantize)[:3]
    m2 = _measure(c2, shape, mesh, tc, quantize)[:3]
    out = []
    for a, b in zip(m1, m2):
        slope = (b - a) / (l2 - l1)
        out.append(max(a + slope * (cfg.n_layers - l1), 0.0))
    return {"flops": out[0], "bytes_accessed": out[1], "wire_bytes": out[2],
            "probe_depths": [l1, l2]}


def run_one(
    arch: str, shape_name: str, mesh_kind: str, tc: TrainConfig, out_dir: str,
    probe: bool = True, policy: str = "tp", moe_impl: str = "gspmd",
    repeat_kv: bool = False, decode_attn: str = "gspmd", quantize: bool = False,
    tag: str = "",
):
    shape = SHAPES[shape_name]
    cfg = variant_for_shape(get_config(arch), shape)
    if moe_impl != "gspmd":
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    if repeat_kv:
        cfg = dataclasses.replace(cfg, repeat_kv=True)
    if decode_attn != "gspmd":
        cfg = dataclasses.replace(cfg, decode_attn=decode_attn)
    set_policy(policy)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    fn, args = build_program(cfg, shape, mesh, tc, quantize)
    with use_mesh(mesh):
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_total = time.time() - t0

    cost = cost_analysis_dict(compiled)
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
        "output_bytes": getattr(ma, "output_size_in_bytes", None),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
    }
    colls = parse_collectives(compiled.as_text())

    if probe:
        corrected = probe_corrected_costs(cfg, shape, mesh, tc, quantize)
        flops = corrected["flops"]
        bytes_acc = corrected["bytes_accessed"]
        wire = corrected["wire_bytes"]
    else:
        corrected = None
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        wire = colls.wire_bytes

    # memory term: analytic HBM floor (HLO "bytes accessed" is fusion-naive
    # on the CPU backend and recorded separately as the upper bound)
    model_shards = 16
    opt_name = tc.optimizer
    if opt_name == "auto":
        opt_name = "adafactor" if cfg.param_count() > 30e9 else "adamw"
    traffic = analytic_hbm_bytes(
        cfg, shape.kind, shape.global_batch, shape.seq_len,
        mesh.devices.size, model_shards, opt_name,
        weight_bytes=(1.07 if quantize and shape.kind != "train" else 2.0),
    )
    terms = roofline_terms(flops, traffic["total"], wire)
    terms["memory_upper_s"] = bytes_acc / 819e9

    n = cfg.param_count()
    # MODEL_FLOPS: 6*N*D for training (fwd+bwd), 2*N*D for inference tokens
    factor = 6 if shape.kind == "train" else 2
    d_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    model_flops = factor * cfg.active_param_count() * d_tokens
    chips = mesh.devices.size
    record = {
        "arch": arch,
        "variant": cfg.name,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": mesh_kind,
        "policy": policy,
        "moe_impl": moe_impl,
        "repeat_kv": repeat_kv,
        "decode_attn": decode_attn,
        "quantize": quantize,
        "chips": chips,
        "params": n,
        "active_params": cfg.active_param_count(),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_total - t_lower, 2),
        "per_device": {"flops": flops, "bytes_accessed": bytes_acc,
                       "hbm_bytes_analytic": traffic, **mem},
        "hlo_raw": {  # uncorrected (scan bodies counted once) — for reference
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "probe": corrected,
        "collectives": {
            "bytes_by_type": colls.bytes_by_type,
            "count_by_type": colls.count_by_type,
            "wire_bytes": wire,
        },
        "roofline": terms,
        "model_flops_global": model_flops,
        "useful_flops_ratio": (model_flops / max(flops * chips, 1.0)),
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--optimizer", default="auto")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip unrolled cost probes (pass/fail lowering only)")
    ap.add_argument("--policy", default="tp",
                    help="sharding policy: tp | tp_sp | tp_kvs | fsdp")
    ap.add_argument("--moe-impl", default="gspmd", choices=["gspmd", "shard_map"])
    ap.add_argument("--repeat-kv", action="store_true")
    ap.add_argument("--decode-attn", default="gspmd", choices=["gspmd", "seq_shard"])
    ap.add_argument("--quantize", action="store_true",
                    help="int8 weights for inference programs")
    ap.add_argument("--tag", default="", help="suffix for output json files")
    args = ap.parse_args()

    archs = sorted(ARCHITECTURES) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    tc = TrainConfig(optimizer=args.optimizer)

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"{arch} x {shape} x {mesh_kind}"
                try:
                    r = run_one(arch, shape, mesh_kind, tc, args.out,
                                probe=not args.no_probe, policy=args.policy,
                                moe_impl=args.moe_impl, repeat_kv=args.repeat_kv,
                                decode_attn=args.decode_attn,
                                quantize=args.quantize, tag=args.tag)
                    rt = r["roofline"]
                    print(
                        f"OK   {tag:60s} compile={r['compile_s']:6.1f}s "
                        f"flops/dev={r['per_device']['flops']:.3e} "
                        f"dominant={rt['dominant']:10s} "
                        f"(c={rt['compute_s']*1e3:.2f}ms m={rt['memory_s']*1e3:.2f}ms "
                        f"coll={rt['collective_s']*1e3:.2f}ms)",
                        flush=True,
                    )
                except Exception as e:  # a failure here is a sharding bug
                    failures.append((tag, repr(e)))
                    print(f"FAIL {tag}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print("\nAll dry-runs passed.")


if __name__ == "__main__":
    main()
