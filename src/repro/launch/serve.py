"""Serving launcher: batched prefill + decode loop on host devices.

    python -m repro.launch.serve --arch qwen3_8b --reduced \
        --batch 4 --prompt-len 32 --gen 16
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_sim")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = make_host_mesh(model_parallel=args.model_parallel)
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(key, cfg)

    B, S = args.batch, args.prompt_len
    prompts = jax.random.randint(key, (B, S), 0, cfg.vocab)
    kwargs = {}
    if cfg.family == "audio":
        kwargs["frames"] = jax.random.normal(
            key, (B, cfg.n_frames, cfg.d_model), dtype=jnp.float32
        )
    if cfg.family == "vlm":
        kwargs["patch_embeds"] = jax.random.normal(
            key, (B, cfg.n_patches, 1024), dtype=jnp.float32
        )

    cache_len = S + args.gen + (cfg.n_patches if cfg.family == "vlm" else 0) + 1

    with jax.set_mesh(mesh):
        logits, cache = M.prefill(
            params, cfg, prompts, cache_len=cache_len, **kwargs
        )
        decode = jax.jit(
            lambda p, c, t: M.decode_step(p, cfg, c, t)
        )
        tok = logits[:, -1].argmax(-1)[:, None].astype(jnp.int32)
        out = [tok]
        for i in range(args.gen - 1):
            logits, cache = decode(params, cache, tok)
            if args.temperature > 0:
                k = jax.random.fold_in(key, i)
                tok = jax.random.categorical(
                    k, logits[:, -1] / args.temperature
                )[:, None].astype(jnp.int32)
            else:
                tok = logits[:, -1].argmax(-1)[:, None].astype(jnp.int32)
            out.append(tok)
    gen = jnp.concatenate(out, axis=1)
    print("generated token ids:")
    for row in gen:
        print("  ", list(map(int, row)))
    print("done")


if __name__ == "__main__":
    main()
