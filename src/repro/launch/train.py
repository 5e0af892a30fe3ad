"""Training launcher.

Runs real steps on the host's devices (tests/examples use reduced configs on
CPU; the same entry point drives TPU slices). The paper's robust aggregation
modes are first-class:

    python -m repro.launch.train --arch paper_sim --steps 100 \
        --agg hierarchical_trim --byzantine 2,5 --model-parallel 2

For the production 512-chip meshes, use this module from a TPU pod launcher;
on this CPU container, the multi-device path is exercised via
``--fake-devices N`` (set before jax init).
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_sim")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--agg", default="mean",
                    choices=["mean", "pushsum", "pushsum_sparse",
                             "trimmed_mean", "hierarchical_trim"])
    ap.add_argument("--byzantine", default="",
                    help="comma-separated compromised worker indices")
    ap.add_argument("--trim-f", type=int, default=1)
    ap.add_argument("--gossip-rounds", type=int, default=16)
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--drop-prob", type=float, default=0.1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.fake_devices:
        import os
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices}"
        )

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.data import SyntheticLMData
    from repro.distributed.aggregation import AggregatorConfig
    from repro.distributed.trainer import (
        TrainConfig, make_train_step, param_spread,
        replicate_for_workers, worker_opt_init,
    )
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.optim import AdamWConfig, adamw_init
    from repro.checkpoint import save_checkpoint

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = make_host_mesh(model_parallel=args.model_parallel)
    n_workers = mesh.shape["data"]
    byz = tuple(int(b) for b in args.byzantine.split(",") if b)

    tc = TrainConfig(
        arch=cfg,
        agg=AggregatorConfig(
            kind=args.agg, F=args.trim_f, gossip_rounds=args.gossip_rounds,
            gamma_period=args.gamma, drop_prob=args.drop_prob,
        ),
        opt=AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                        total_steps=args.steps),
        n_micro=args.n_micro,
        byzantine_workers=byz,
        seed=args.seed,
    )
    data = SyntheticLMData(
        cfg.vocab, args.seq_len, args.global_batch, flavour="markov",
        n_agents=n_workers, seed=args.seed,
    )
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(key, cfg)

    factory, _ = make_train_step(tc, mesh)
    robust = args.agg != "mean"
    with jax.set_mesh(mesh):
        if robust:
            params_w = replicate_for_workers(params, n_workers)
            opt_w = worker_opt_init(params_w)
            step = jax.jit(factory(params_w))
            spread_fn = jax.jit(param_spread)
            for s in range(args.steps):
                batch = data.batch(s)
                params_w, opt_w, loss = step(
                    params_w, opt_w, batch, jax.random.fold_in(key, s)
                )
                if s % max(args.steps // 10, 1) == 0 or s == args.steps - 1:
                    spread = float(spread_fn(params_w))
                    print(f"step {s:5d} loss {float(loss):.4f} "
                          f"consensus_spread {spread:.3e}", flush=True)
                if args.ckpt_dir and args.ckpt_every and \
                        (s + 1) % args.ckpt_every == 0:
                    save_checkpoint(args.ckpt_dir, s + 1, params_w)
        else:
            opt = adamw_init(params)
            step = jax.jit(factory(params))
            for s in range(args.steps):
                batch = data.batch(s)
                params, opt, loss = step(params, opt, batch)
                if s % max(args.steps // 10, 1) == 0 or s == args.steps - 1:
                    print(f"step {s:5d} loss {float(loss):.4f}", flush=True)
                if args.ckpt_dir and args.ckpt_every and \
                        (s + 1) % args.ckpt_every == 0:
                    save_checkpoint(args.ckpt_dir, s + 1, params)
    print("done")


if __name__ == "__main__":
    main()
