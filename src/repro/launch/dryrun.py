import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: prove every (architecture × input shape × mesh)
combination lowers AND compiles under the production sharding config, and
emit the compiled artifacts' memory/cost analyses for §Roofline.

No real buffers are ever allocated: parameters, optimizer state, batches and
caches are ShapeDtypeStructs with NamedShardings attached; the 512 host
devices exist only so ``jax.make_mesh`` can build the (2,16,16) mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k --mesh single [--step auto|train|train_peft|prefill|
      decode|fl_round] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all  # full 40×2 matrix
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import trees
from repro.configs import ASSIGNED, SHAPES, get_config
from repro.launch.mesh import (PRODUCTION_DEVICE_KIND, device_peaks,
                               make_meshctx)
from repro.launch.steps import (make_fl_round_step, make_input_batch_shapes,
                                make_peft_step, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models import Model
from repro.models import peft as peft_mod
from repro.sharding import batch_specs, cache_specs, param_specs, with_specs

COLLECTIVE_RE = re.compile(
    r"(\w+\[[^\]]*\](?:\s*,\s*\w+\[[^\]]*\])*)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
               "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def parse_collective_bytes(hlo_text: str):
    """Per-device collective wire-byte estimate from post-SPMD HLO.

    Counts each collective op's RESULT shapes; all-reduce weighted 2× (ring
    reduce-scatter + all-gather decomposition).  This is the standard
    first-order model; exact DCN/ICI scheduling is hardware-dependent."""
    totals = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
              "all-to-all": 0, "collective-permute": 0}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.*)", line)
        if not m:
            continue
        rest = m.group(1)
        cm = re.match(r"(\([^)]*\)|[\w\[\],{} ]+?)\s*"
                      r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                      r"collective-permute)\b", rest)
        if not cm:
            continue
        shapes_str, op = cm.group(1), cm.group(2)
        nbytes = 0
        for dt, dims in SHAPE_RE.findall(shapes_str):
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        totals[op] += nbytes
    wire = (2 * totals["all-reduce"] + totals["all-gather"]
            + totals["reduce-scatter"] + totals["all-to-all"]
            + totals["collective-permute"])
    return totals, wire


def pick_impl(cfg, shape, opts=None):
    """Attention implementation per DESIGN.md §4: block-sparse (the paper's
    technique) is the sub-quadratic variant required for long_500k on
    attention archs; everything else uses the auto (dense/chunked) path.
    ``opts['sparse_impl']`` forces the paper's sparse attention everywhere
    (§Perf technique variants)."""
    if (opts or {}).get("sparse_impl") and not cfg.attention_free:
        return "sparse"
    if shape.name == "long_500k" and not cfg.attention_free:
        return "sparse"
    return "auto"


def build_specs(arch: str, shape_name: str, mesh_kind: str, step: str,
                dtype=jnp.bfloat16, opts=None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    meshctx = make_meshctx(multi_pod=(mesh_kind == "multi"))
    impl = pick_impl(cfg, shape, opts)
    model = Model(cfg, meshctx=meshctx, dtype=dtype, impl=impl, remat=True,
                  opts=opts or {})
    return cfg, shape, meshctx, model, impl


def analytic_memory_bytes(cfg, shape, step, cache_bytes: int = 0) -> int:
    """First-order HBM traffic model (global, per step) — the napkin-math
    memory roofline term (cost_analysis undercounts scanned bodies):

    train:   4·P(bf16)  (fwd read + bwd read + grad w + opt read)
             + 16·N     (f32 moments read+write)
             + 6·L·T·d·2 (boundary activations: fwd w, bwd r, remat rw ×~3)
    prefill: P + 2·L·T·d·2 + cache write
    decode:  P_active + full cache read + small
    """
    p_bytes = cfg.param_count() * 2
    n = cfg.param_count()
    t = shape.global_batch * shape.seq_len
    layer_act = cfg.n_layers * cfg.d_model * 2
    if step in ("train", "train_peft", "fl_round"):
        return 4 * p_bytes + 16 * n + 6 * t * layer_act
    if step == "prefill":
        return p_bytes + 2 * t * layer_act + cache_bytes
    # decode: one token per sequence
    active = cfg.active_param_count() * 2
    return active + cache_bytes + shape.global_batch * layer_act


def sds_tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "shape"))


def lower_one(arch: str, shape_name: str, mesh_kind: str, step: str = "auto",
              dtype=jnp.bfloat16, n_fl_clients: int = 8, opts=None,
              policy: str = "fsdp"):
    opts = dict(opts or {})
    if opts.get("sparse_kv"):
        opts["sparse_kv_seq"] = SHAPES[shape_name].seq_len
    cfg, shape, meshctx, model, impl = build_specs(arch, shape_name,
                                                   mesh_kind, step, dtype,
                                                   opts)
    if policy == "dp":
        # pure data parallelism: batch over ALL mesh axes (small models)
        import dataclasses as _dc
        assert not any(k.ff == "moe" for st_ in cfg.stages
                       for k in st_.pattern), "dp policy: non-MoE archs only"
        meshctx = _dc.replace(meshctx, batch_axes=meshctx.all_axes)
        model = Model(cfg, meshctx=meshctx, dtype=dtype, impl=impl,
                      remat=True, opts=opts, seq_shard_boundary=False)
    mesh = meshctx.mesh
    if step == "auto":
        step = {"train": "train", "prefill": "prefill",
                "decode": "decode"}[shape.kind]

    key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_shapes = jax.eval_shape(
        lambda k: model.init(k, max_seq=shape.seq_len + 8), key_s)
    # "zero1": params replicated over the data axes for compute (pure TP —
    # no per-layer weight gathers), optimizer moments FSDP-sharded; the
    # gather/scatter happens ONCE per step at the update.
    p_policy = "tp" if policy == "zero1" else policy
    o_policy = "fsdp" if policy == "zero1" else policy
    pspecs = param_specs(meshctx, params_shapes, cfg, policy=p_policy)
    params_in = with_specs(params_shapes, pspecs, mesh)

    batch_shapes = make_input_batch_shapes(cfg, shape, dtype)
    bspecs = batch_specs(meshctx, batch_shapes)
    batch_in = with_specs(batch_shapes, bspecs, mesh)

    if step == "train":
        step_fn, opt = make_train_step(model, impl=impl)
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        ospecs = param_specs(meshctx, opt_shapes["mu"], cfg, policy=o_policy)
        opt_in = {"mu": with_specs(opt_shapes["mu"], ospecs, mesh),
                  "nu": with_specs(opt_shapes["nu"], ospecs, mesh),
                  "step": jax.ShapeDtypeStruct((), jnp.int32)}
        with jax.set_mesh(mesh):
            lowered = jax.jit(step_fn).lower(params_in, opt_in, batch_in)
        lower_args = (step_fn, (params_in, opt_in, batch_in), 0)
    elif step == "train_peft":
        peft_cfg = peft_mod.PEFTConfig(lora_rank=16, adapter_dim=64)
        params_shapes2 = jax.eval_shape(
            lambda k: peft_mod.init_adapters(k, jax.eval_shape(
                lambda kk: model.init(kk, max_seq=shape.seq_len + 8), k),
                cfg, peft_cfg), key_s)
        # adapters/lora trainable; base frozen
        pspecs2 = param_specs(meshctx, params_shapes2, cfg)
        frozen_in = with_specs(params_shapes2, pspecs2, mesh)
        lora_shapes = jax.eval_shape(
            lambda k: peft_mod.init_lora(k, params_shapes2, peft_cfg), key_s)
        adapters = trees.select(params_shapes2, peft_mod.is_adapter_path)
        trainable_shapes = {"adapters": adapters, "lora": lora_shapes}
        tspecs = param_specs(meshctx, trainable_shapes, cfg)
        trainable_in = with_specs(trainable_shapes, tspecs, mesh)
        step_fn, opt = make_peft_step(model, peft_cfg, impl=impl)
        opt_shapes = jax.eval_shape(opt.init, trainable_shapes)
        opt_in = {"mu": with_specs(opt_shapes["mu"], tspecs, mesh),
                  "nu": with_specs(opt_shapes["nu"], tspecs, mesh),
                  "step": jax.ShapeDtypeStruct((), jnp.int32)}
        with jax.set_mesh(mesh):
            lowered = jax.jit(step_fn).lower(trainable_in, frozen_in, opt_in,
                                             batch_in)
        lower_args = (step_fn, (trainable_in, frozen_in, opt_in, batch_in), 0)
    elif step == "prefill":
        step_fn = make_prefill_step(model, cache_len=shape.seq_len, impl=impl)
        with jax.set_mesh(mesh):
            lowered = jax.jit(step_fn).lower(params_in, batch_in)
        cache_b = sds_tree_bytes(model.cache_spec(shape.global_batch,
                                                  shape.seq_len))
        lower_args = (step_fn, (params_in, batch_in), cache_b)
    elif step == "decode":
        step_fn = make_serve_step(model, impl=impl)
        cache_shapes = model.cache_spec(shape.global_batch, shape.seq_len)
        cspecs = cache_specs(meshctx, cache_shapes,
                             batch=shape.global_batch)
        cache_in = with_specs(cache_shapes, cspecs, mesh)
        tok_in = with_specs(
            jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
            batch_specs(meshctx, jax.ShapeDtypeStruct(
                (shape.global_batch, 1), jnp.int32)), mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(step_fn).lower(params_in, cache_in, tok_in)
        lower_args = (step_fn, (params_in, cache_in, tok_in),
                      sds_tree_bytes(cache_shapes))
    elif step == "fl_round":
        # PFTT federated round: clients vmapped over the leading dim
        peft_cfg = peft_mod.PEFTConfig(lora_rank=16, adapter_dim=64)
        base_with_ad = jax.eval_shape(
            lambda k: peft_mod.init_adapters(k, jax.eval_shape(
                lambda kk: model.init(kk, max_seq=shape.seq_len + 8), k),
                cfg, peft_cfg), key_s)
        pspecs2 = param_specs(meshctx, base_with_ad, cfg)
        frozen_in = with_specs(base_with_ad, pspecs2, mesh)
        lora_shapes = jax.eval_shape(
            lambda k: peft_mod.init_lora(k, base_with_ad, peft_cfg), key_s)
        lora_c = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n_fl_clients,) + s.shape, s.dtype),
            lora_shapes)
        adapters = trees.select(base_with_ad, peft_mod.is_adapter_path)
        trainable_shapes = {"adapters": adapters, "lora": lora_c}
        tspecs = param_specs(meshctx, trainable_shapes, cfg)
        # per-client leaves: client dim over the data axes
        tspecs = trees.map_with_path(
            lambda p, s: (batch_specs(meshctx, jax.ShapeDtypeStruct(
                trees.flatten(trainable_shapes)[p].shape, jnp.float32))
                if p.startswith("lora/") else s), tspecs)
        trainable_in = with_specs(trainable_shapes, tspecs, mesh)
        # per-client batch: fold client dim into batch dim shapes
        per_client = {k: jax.ShapeDtypeStruct(
            (n_fl_clients, max(1, v.shape[0] // n_fl_clients)) + v.shape[1:],
            v.dtype) for k, v in batch_shapes.items()}
        cbspecs = batch_specs(meshctx, per_client)
        batch_in = with_specs(per_client, cbspecs, mesh)
        step_fn, opt = make_fl_round_step(model, peft_cfg, n_fl_clients,
                                          impl=impl)
        opt_shapes = jax.eval_shape(opt.init, trainable_shapes)
        opt_in = {"mu": with_specs(opt_shapes["mu"], tspecs, mesh),
                  "nu": with_specs(opt_shapes["nu"], tspecs, mesh),
                  "step": jax.ShapeDtypeStruct((), jnp.int32)}
        with jax.set_mesh(mesh):
            lowered = jax.jit(step_fn).lower(trainable_in, frozen_in, opt_in,
                                             batch_in)
        lower_args = (step_fn, (trainable_in, frozen_in, opt_in, batch_in), 0)
    else:
        raise ValueError(step)

    return cfg, shape, meshctx, lowered, step, impl, lower_args


def run_one(arch: str, shape_name: str, mesh_kind: str, step: str = "auto",
            out_dir: str = "experiments/dryrun", skip_hlo: bool = False,
            opts=None, policy: str = "fsdp", tag: str = ""):
    opts = opts or {}
    t0 = time.time()
    cfg, shape, meshctx, lowered, step, impl, lower_args = lower_one(
        arch, shape_name, mesh_kind, step, opts=opts, policy=policy)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # exact global FLOPs from the jaxpr (scan bodies × trip count,
    # remat recompute included)
    from repro.launch.jaxpr_cost import step_flops
    step_fn, abstract_args, cache_bytes = lower_args
    t0 = time.time()
    global_flops = step_flops(step_fn, *abstract_args)
    t_count = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    n_chips = meshctx.mesh.size
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    coll_detail, coll_wire = ({}, 0)
    if not skip_hlo:
        try:
            hlo = compiled.as_text()
            coll_detail, coll_wire = parse_collective_bytes(hlo)
        except Exception as e:  # pragma: no cover
            coll_detail = {"error": str(e)}

    eff_cache = cache_bytes
    if (step == "decode" and impl == "sparse"
            and opts.get("sparse_gather_decode") and cfg.sparse_attn
            and not cfg.attention_free):
        # gather-based sparse decode touches only the active blocks
        sp = cfg.sparse_attn
        nb = shape.seq_len // sp.block_size
        a = sp.sink_blocks + sp.local_blocks + max(1, nb // sp.stride)
        eff_cache = int(cache_bytes * min(1.0, a / nb))
    mem_global = analytic_memory_bytes(cfg, shape, step, eff_cache)
    # analytic roofline of the production target, not of the host that runs
    # this compile
    peaks = device_peaks(PRODUCTION_DEVICE_KIND)
    compute_s = global_flops / n_chips / peaks.flops_bf16
    memory_s = mem_global / n_chips / peaks.hbm_bytes_per_s
    collective_s = coll_wire / peaks.ici_bytes_per_s  # HLO is per-device

    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind in ("train", "prefill")
                                   else 1)
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "step": step,
        "impl": impl, "n_chips": n_chips, "opts": sorted(opts),
        "shard_policy": policy,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flop_count_s": round(t_count, 1),
        "global": {
            "jaxpr_flops": global_flops,
            "analytic_hbm_bytes": mem_global,
            "cache_bytes": cache_bytes,
        },
        "per_device": {
            "xla_flops_toplevel": xla_flops,
            "xla_bytes_toplevel": xla_bytes,
            "collective_wire_bytes": coll_wire,
            "collectives": coll_detail,
            "peak_memory_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
        },
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": max([("compute", compute_s), ("memory", memory_s),
                             ("collective", collective_s)],
                            key=lambda kv: kv[1])[0],
        },
        "model_flops_total": model_flops,
        "useful_flops_ratio": (model_flops / global_flops
                               if global_flops else None),
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    fname = f"{arch}_{shape_name}_{mesh_kind}_{step}{suffix}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1)
    print(f"[dryrun] {arch:18s} {shape_name:12s} {mesh_kind:6s} {step:10s} "
          f"OK lower={t_lower:.0f}s compile={t_compile:.0f}s "
          f"dom={result['roofline']['dominant']}")
    print(f"  memory_analysis: {mem}")
    print(f"  jaxpr_flops(global)={global_flops:.3e} "
          f"analytic_hbm(global)={mem_global:.3e} coll/dev={coll_wire:.3e}")
    print(f"  roofline/dev: compute={compute_s*1e3:.2f}ms "
          f"memory={memory_s*1e3:.2f}ms collective={collective_s*1e3:.2f}ms "
          f"useful_ratio={result['useful_flops_ratio'] and round(result['useful_flops_ratio'],3)}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--step", default="auto")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-hlo", action="store_true")
    ap.add_argument("--opts", default="",
                    help="comma list: causal_skip,sparse_gather_decode,"
                         "moe_a2a,mamba_sp,sparse_kv,sparse_impl")
    ap.add_argument("--shard-policy", default="fsdp",
                    choices=["fsdp", "fsdp_experts_only", "tp", "zero1", "dp"])
    ap.add_argument("--tag", default="",
                    help="artifact filename suffix (perf variants)")
    args = ap.parse_args()
    opts = {k: True for k in args.opts.split(",") if k}

    if args.all:
        failures = []
        for arch in ASSIGNED:
            for shape in SHAPES:
                try:
                    run_one(arch, shape, args.mesh, out_dir=args.out,
                            skip_hlo=args.skip_hlo, opts=opts,
                            policy=args.shard_policy, tag=args.tag)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape, str(e)[:200]))
        print(f"\n{len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    run_one(args.arch, args.shape, args.mesh, args.step, args.out,
            skip_hlo=args.skip_hlo, opts=opts, policy=args.shard_policy,
            tag=args.tag)


if __name__ == "__main__":
    main()
