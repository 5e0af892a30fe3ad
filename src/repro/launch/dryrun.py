import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

This proves the distribution config is coherent without hardware: a sharding
mismatch, compile-time OOM, or unsupported collective is a bug. For each
combination we record ``memory_analysis()`` (fits-in-HBM proof),
``cost_analysis()`` (FLOPs/bytes) and the parsed collective schedule — the
inputs to EXPERIMENTS.md §Dry-run and §Roofline.

Cost correction: XLA's cost analysis counts a ``while`` (lax.scan) body ONCE
regardless of trip count, so scanned deep stacks under-report FLOPs/bytes/
collectives. The fit-proof compile uses the real scanned program; the cost
numbers come from two shallow *unrolled* compiles (depth P and 2P at full
width/batch/mesh) extrapolated linearly in depth:
    cost(L) = base + L * per_layer.

Usage:
    python -m repro.launch.dryrun --arch qwen3_8b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all --multi-pod both
    python -m repro.launch.dryrun ... --agg hierarchical_trim   # paper mode

Inputs are ShapeDtypeStructs (jax.eval_shape) — nothing is allocated.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.memory_model import serve_memory_gb, train_memory_gb
from repro.analysis.roofline import model_flops, parse_collectives, roofline_terms
from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.configs.base import ArchConfig, InputShape
from repro.data.pipeline import make_batch_specs
from repro.distributed.aggregation import AggregatorConfig
from repro.distributed.sharding import (
    batch_axes, cache_specs, param_specs,
)
from repro.distributed.trainer import (
    TrainConfig, make_train_step, _batch_spec_tree,
)
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_init

# FSDP for models whose optimizer state cannot replicate across data workers
FSDP_THRESHOLD = 2e9
# weight-gathered serving: above this size, params shard over (data, model)
# and GSPMD all-gathers weights per layer (16-way TP alone cannot hold them)
SERVE_GATHER_THRESHOLD = 50e9
# sliding window used for the long_500k serve variant of full-attention archs
LONG_WINDOW = 4096
# target tokens per device per micro-batch (activation-memory knob)
MICRO_TOKENS = 4096


def pick_remat_group(L: int) -> int:
    """Largest divisor of L bounded by ~L/12: saved-residual count stays
    small while the recompute window stays shallow."""
    cap = max(2, L // 12)
    best = 1
    for g in range(2, cap + 1):
        if L % g == 0:
            best = g
    return best


def pick_n_micro(cfg: ArchConfig, shape: InputShape, mesh) -> int:
    data_shards = mesh.shape["data"] * dict(mesh.shape).get("pod", 1)
    b_dev = max(shape.global_batch // data_shards, 1)
    tok_dev = b_dev * shape.seq_len
    n = max(1, min(tok_dev // MICRO_TOKENS, b_dev))
    while b_dev % n:
        n -= 1
    return n


def serve_cfg_for(cfg: ArchConfig, shape: InputShape) -> ArchConfig:
    """long_500k needs sub-quadratic attention: full-attention archs switch
    to their sliding-window serve variant (same params, windowed mixer)."""
    if shape.name == "long_500k" and any(
        k == "attn" for k in cfg.block_pattern
    ):
        pat = tuple("swa" if k == "attn" else k for k in cfg.block_pattern)
        return dataclasses.replace(cfg, block_pattern=pat,
                                   window=cfg.window or LONG_WINDOW)
    return cfg


def batch_struct(cfg: ArchConfig, shape: InputShape):
    """ShapeDtypeStruct inputs for a train/prefill batch of arch x shape."""
    S, B = shape.seq_len, shape.global_batch
    toks = S
    extra = {}
    if cfg.family == "vlm":
        toks = S - cfg.n_patches
        extra["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_patches, 1024), jnp.bfloat16
        )
    if cfg.family == "audio":
        extra["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.n_frames, cfg.d_model), jnp.bfloat16
        )
    base = make_batch_specs(toks, B, cfg.vocab)
    return {**base, **extra}


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of (arch, shape) —
    weak-type-correct, shardable, no allocation."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.kind in ("train", "prefill"):
        return batch_struct(serve_cfg_for(cfg, shape), shape)
    return {"token": jax.ShapeDtypeStruct(
        (shape.global_batch, 1), jnp.int32)}


def _sharded(specs_tree, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def decode_cache_len(cfg: ArchConfig, shape: InputShape) -> int:
    if shape.name == "long_500k":
        return cfg.window or LONG_WINDOW
    return min(shape.seq_len, 32768)


def build_lowered(cfg: ArchConfig, shape: InputShape, mesh, agg: str,
                  fsdp: bool, n_micro: int | None = None,
                  opt_dtype: str = "float32", comm_dtype: str = "float32",
                  gossip_rounds: int = 8):
    """Lower one step function for this cfg (possibly depth-reduced)."""
    params_struct = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)
    )

    if shape.kind == "train":
        tc = TrainConfig(
            arch=cfg,
            agg=AggregatorConfig(kind=agg, F=1, gossip_rounds=gossip_rounds,
                                 gamma_period=4, drop_prob=0.1,
                                 comm_dtype=comm_dtype),
            opt=AdamWConfig(moment_dtype=opt_dtype),
            fsdp=fsdp,
            n_micro=n_micro if n_micro is not None
            else pick_n_micro(cfg, shape, mesh),
        )
        batch = batch_struct(cfg, shape)
        if agg == "mean":
            factory, shard_fn = make_train_step(tc, mesh)
            step_fn = factory(params_struct, tuple(batch))
            pspecs, ospecs, _ = shard_fn(params_struct, tuple(batch))
            opt_struct = jax.eval_shape(
                lambda p: adamw_init(p, opt_dtype), params_struct
            )
            with jax.set_mesh(mesh):
                jitted = jax.jit(
                    step_fn,
                    in_shardings=(
                        _sharded(pspecs, mesh), _sharded(ospecs, mesh),
                        _sharded(_batch_spec_tree(mesh, tuple(batch)), mesh),
                    ),
                )
                return jitted.lower(params_struct, opt_struct, batch)
        # decentralized robust step: worker-axis params
        from repro.distributed.trainer import (
            replicate_for_workers, worker_opt_init,
        )
        W = mesh.shape["data"] * dict(mesh.shape).get("pod", 1)
        pw_struct = jax.eval_shape(
            lambda p: replicate_for_workers(p, W), params_struct
        )
        ow_struct = jax.eval_shape(worker_opt_init, pw_struct)
        factory, shard_fn = make_train_step(tc, mesh)
        step_fn = factory(pw_struct, tuple(batch))
        pspecs, ospecs, bspec = shard_fn(pw_struct, tuple(batch))
        key_struct = jax.ShapeDtypeStruct((2,), jnp.uint32)
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                step_fn,
                in_shardings=(
                    _sharded(pspecs, mesh), _sharded(ospecs, mesh),
                    _sharded(bspec, mesh), NamedSharding(mesh, P()),
                ),
            )
            return jitted.lower(pw_struct, ow_struct, batch, key_struct)

    serve_gather = cfg.param_count() > SERVE_GATHER_THRESHOLD
    pspecs = param_specs(params_struct, cfg, mesh, fsdp=serve_gather)
    B = shape.global_batch

    if shape.kind == "prefill":
        batch = batch_struct(cfg, shape)

        def prefill_step(params, batch):
            return M.prefill(
                params, cfg, batch["tokens"],
                patch_embeds=batch.get("patch_embeds"),
                frames=batch.get("frames"),
            )

        with jax.set_mesh(mesh):
            jitted = jax.jit(
                prefill_step,
                in_shardings=(
                    _sharded(pspecs, mesh),
                    _sharded(_batch_spec_tree(mesh, tuple(batch)), mesh),
                ),
            )
            return jitted.lower(params_struct, batch)

    # decode
    cache_len = decode_cache_len(cfg, shape)
    if cfg.encoder_layers:
        enc_struct = jax.ShapeDtypeStruct(
            (B, cfg.n_frames, cfg.d_model), jnp.bfloat16
        )
        cache_struct = jax.eval_shape(
            lambda p, e: M.init_cache(p, cfg, B, cache_len, e),
            params_struct, enc_struct,
        )
    else:
        cache_struct = jax.eval_shape(
            lambda p: M.init_cache(p, cfg, B, cache_len), params_struct
        )
    cspecs = cache_specs(cache_struct, cfg, mesh)
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    def decode_fn(params, cache, token):
        return M.decode_step(params, cfg, cache, token)

    from repro.distributed.sharding import fit_spec
    tok_spec = fit_spec(P(batch_axes(mesh), None), (B, 1), mesh)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            decode_fn,
            in_shardings=(
                _sharded(pspecs, mesh), _sharded(cspecs, mesh),
                NamedSharding(mesh, tok_spec),
            ),
        )
        return jitted.lower(params_struct, cache_struct, token)


def _extract_costs(compiled, n_dev):
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text(), n_dev)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": float(coll["wire_bytes_per_device"]),
        "by_kind": coll["bytes_by_kind"],
        "counts": coll["count_by_kind"],
    }


def extrapolated_costs(cfg: ArchConfig, shape: InputShape, mesh, agg, fsdp,
                       opt_dtype: str = "float32",
                       comm_dtype: str = "float32", gossip_rounds: int = 8):
    """Depth-linear cost model from two shallow unrolled compiles."""
    n_dev = mesh.size
    Pn = len(cfg.block_pattern)
    L1, L2 = Pn, 2 * Pn
    if cfg.n_layers <= L2 and not cfg.scan_layers:
        return None  # direct costs are exact (fully unrolled program)
    costs = []
    for Lx in (L1, L2):
        # Costing variant removes every cost-hiding loop: layers unrolled,
        # n_micro=1 (micro scan), naive attention (the flash path's q/kv
        # chunk loops are while bodies XLA counts once). Identical math.
        c = dataclasses.replace(cfg, n_layers=Lx, scan_layers=False,
                                attn_impl="naive")
        lowered = build_lowered(c, shape, mesh, agg, fsdp, n_micro=1,
                                opt_dtype=opt_dtype, comm_dtype=comm_dtype,
                                gossip_rounds=gossip_rounds)
        costs.append(_extract_costs(lowered.compile(), n_dev))
    per_layer = {
        k: (costs[1][k] - costs[0][k]) / (L2 - L1)
        for k in ("flops", "bytes", "wire")
    }
    base = {k: costs[0][k] - L1 * per_layer[k] for k in per_layer}
    L = cfg.n_layers
    out = {k: max(base[k] + L * per_layer[k], 0.0) for k in per_layer}
    out["by_kind"] = {
        kind: max(
            costs[0]["by_kind"][kind]
            + (costs[1]["by_kind"][kind] - costs[0]["by_kind"][kind])
            / (L2 - L1) * (L - L1),
            0.0,
        )
        for kind in costs[0]["by_kind"]
    }
    out["counts"] = {
        kind: int(
            costs[0]["counts"][kind]
            + (costs[1]["counts"][kind] - costs[0]["counts"][kind])
            / (L2 - L1) * (L - L1)
        )
        for kind in costs[0]["counts"]
    }
    return out


def lower_one(arch: str, shape_name: str, multi_pod: bool, agg: str = "mean",
              skip_cost: bool = False, overrides: dict | None = None,
              opt_dtype: str = "float32", comm_dtype: str = "float32",
              gossip_rounds: int = 8):
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    cfg0 = get_config(arch)
    if overrides:
        cfg0 = dataclasses.replace(cfg0, **overrides)
    shape = INPUT_SHAPES[shape_name]
    cfg = serve_cfg_for(cfg0, shape) if shape.kind != "train" else cfg0
    fsdp = cfg.param_count() > FSDP_THRESHOLD and agg == "mean"

    # 1) the real program: proves compile + fit
    t0 = time.time()
    lowered = build_lowered(cfg, shape, mesh, agg, fsdp, opt_dtype=opt_dtype,
                            comm_dtype=comm_dtype,
                            gossip_rounds=gossip_rounds)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    ma = compiled.memory_analysis()

    # 2) depth-corrected costs
    direct = _extract_costs(compiled, n_dev)
    extr = None if skip_cost else extrapolated_costs(cfg, shape, mesh, agg,
                                                     fsdp, opt_dtype,
                                                     comm_dtype, gossip_rounds)
    use = extr if extr is not None else direct
    cost = {"flops": use["flops"], "bytes accessed": use["bytes"]}
    coll = {"wire_bytes_per_device": use["wire"],
            "bytes_by_kind": use["by_kind"], "count_by_kind": use["counts"]}
    mf = model_flops(cfg, shape)
    terms = roofline_terms(cost, coll, n_dev, mf)

    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    mesh_shape = dict(mesh.shape)
    if shape.kind == "train":
        analytic = train_memory_gb(
            cfg, shape, mesh_shape, fsdp,
            pick_n_micro(cfg, shape, mesh),
            worker_axis=(agg != "mean"),
            moment_bytes=2 if opt_dtype == "bfloat16" else 4,
        )
    else:
        analytic = serve_memory_gb(
            cfg, shape, mesh_shape,
            decode_cache_len(cfg, shape) if shape.kind == "decode"
            else shape.seq_len,
            weight_gathered=cfg.param_count() > SERVE_GATHER_THRESHOLD,
        )
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "agg": agg,
        "ok": True,
        "fsdp": fsdp,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_device": ma.argument_size_in_bytes,
            "output_bytes_per_device": ma.output_size_in_bytes,
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "alias_bytes_per_device": ma.alias_size_in_bytes,
            "xla_peak_gb_cpu_backend": round(peak / 1e9, 3),
        },
        "analytic_memory": analytic,
        "roofline": terms,
        "collectives": coll,
        "cost_mode": "extrapolated" if extr is not None else "direct",
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--agg", default="mean")
    ap.add_argument("--skip-cost", action="store_true",
                    help="fit-proof only (skip the costing compiles)")
    ap.add_argument("--moe-impl", default=None, choices=[None, "gspmd",
                                                         "sharded"])
    ap.add_argument("--remat-group", type=int, default=None)
    ap.add_argument("--pad-heads", type=int, default=None)
    ap.add_argument("--ce-chunk", type=int, default=None)
    ap.add_argument("--opt-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--comm-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--gossip-rounds", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    overrides = {}
    if args.moe_impl:
        overrides["moe_impl"] = args.moe_impl
    if args.remat_group:
        overrides["remat_group"] = args.remat_group
    if args.pad_heads:
        overrides["pad_heads_to"] = args.pad_heads
    if args.ce_chunk:
        overrides["ce_chunk"] = args.ce_chunk

    archs = [a for a in ARCH_IDS if a != "paper_sim"] \
        if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    pods = {"single": [False], "multi": [True], "both": [False, True]}[
        args.multi_pod
    ]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec = lower_one(arch, shape, mp, args.agg,
                                    skip_cost=args.skip_cost,
                                    overrides=overrides or None,
                                    opt_dtype=args.opt_dtype,
                                    comm_dtype=args.comm_dtype,
                                    gossip_rounds=args.gossip_rounds)
                    r = rec["roofline"]
                    print(
                        f"OK   {tag}: compile={rec['compile_s']}s "
                        f"mem={rec['analytic_memory']['total_gb']}GB "
                        f"fits={rec['analytic_memory']['fits_16gb']} "
                        f"compute={r['compute_s']:.4f}s "
                        f"memory={r['memory_s']:.4f}s "
                        f"coll={r['collective_s']:.4f}s "
                        f"dom={r['dominant']} useful={r['useful_flop_ratio']:.2f}",
                        flush=True,
                    )
                except Exception as e:
                    rec = {
                        "arch": arch, "shape": shape,
                        "mesh": "2x16x16" if mp else "16x16",
                        "agg": args.agg, "ok": False,
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                    print(f"FAIL {tag}: {rec['error']}", flush=True)
                results.append(rec)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    n_ok = sum(r["ok"] for r in results)
    print(f"{n_ok}/{len(results)} combinations lowered+compiled")


if __name__ == "__main__":
    main()
