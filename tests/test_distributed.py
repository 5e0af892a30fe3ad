"""Multi-device distributed-runtime tests.

The heavy multi-worker scenarios run in SUBPROCESSES: the in-process CPU
collectives rendezvous is unreliable when one pytest process reuses a
device-backed client across many different executables on a single-core
host (thread starvation aborts the process). One scenario per fresh
interpreter is deterministic.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(body: str, devices: int = 8, timeout: int = 420) -> dict:
    """Run a snippet under N fake devices; it must print one JSON line."""
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import json
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # The in-process CPU collective rendezvous can abort under host load
    # (XLA kills after a 40 s stall on this 1-core box); retry once.
    for attempt in range(2):
        out = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True, text=True,
            timeout=timeout, env=env, cwd=REPO,
        )
        if out.returncode == 0:
            break
        if "rendezvous" not in out.stderr.lower():
            break
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    line = [l for l in out.stdout.strip().splitlines() if l.startswith("{")][-1]
    return json.loads(line)


class TestAggregators:
    def test_mean_trim_pushsum_semantics(self):
        res = _run_subprocess("""
            from repro.distributed.aggregation import AGGREGATORS, AggregatorConfig
            from repro.kernels.trimmed_mean.ref import trimmed_mean_ref
            mesh = jax.make_mesh((2, 4), ("pod", "data"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            W, D = 8, 512
            rng = np.random.default_rng(0)
            g_all = jnp.asarray(rng.normal(size=(W, D)).astype(np.float32))

            def run(kind, **kw):
                cfg = AggregatorConfig(kind=kind, **kw)
                fn = AGGREGATORS[kind]
                def body(g, key):
                    out = fn({"g": g[0]}, cfg, "data", "pod", key)["g"]
                    return out[None]
                sm = jax.shard_map(body, mesh=mesh,
                                   in_specs=(P(("pod","data"), None), P()),
                                   out_specs=P(("pod","data"), None),
                                   axis_names=frozenset({"pod","data"}),
                                   check_vma=False)
                return np.asarray(jax.jit(sm)(g_all, jax.random.PRNGKey(0)))

            mean_err = float(np.abs(run("mean")[0] - np.asarray(g_all.mean(0))).max())
            trim = run("trimmed_mean", F=2)
            trim_err = float(np.abs(trim[0] - np.asarray(trimmed_mean_ref(g_all, 2))).max())
            trim_agree = float(np.ptp(trim, axis=0).max())
            scale = float(np.abs(np.asarray(g_all)).max())
            ps = run("pushsum", gossip_rounds=120, gamma_period=4, drop_prob=0.2)
            ps_err = float(np.abs(ps - np.asarray(g_all.mean(0))).max()) / scale
            ps_err_few = float(np.abs(
                run("pushsum", gossip_rounds=10, gamma_period=4, drop_prob=0.2)
                - np.asarray(g_all.mean(0))).max()) / scale
            print(json.dumps(dict(mean_err=mean_err, trim_err=trim_err,
                                  trim_agree=trim_agree, ps_err=ps_err,
                                  ps_err_few=ps_err_few)))
        """)
        assert res["mean_err"] < 1e-5
        assert res["trim_err"] < 1e-5
        assert res["trim_agree"] == 0.0          # all workers identical
        # ring gossip + sparse PS fusion converges per Theorem 1 (the rate
        # constant for a 4-ring per pod is modest — check level + direction)
        assert res["ps_err"] < 0.15              # relative consensus error
        assert res["ps_err"] < 0.5 * res["ps_err_few"]

    def test_hierarchical_trim_filters_byzantine_pod(self):
        res = _run_subprocess("""
            from repro.distributed.aggregation import AGGREGATORS, AggregatorConfig
            mesh = jax.make_mesh((2, 4), ("pod", "data"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            rng = np.random.default_rng(1)
            D = 256
            honest = rng.normal(size=(8, D)).astype(np.float32)
            g_all = honest.copy()
            g_all[3] = 1e6          # one Byzantine worker in pod 0
            cfg = AggregatorConfig(kind="hierarchical_trim", F=1)
            fn = AGGREGATORS["hierarchical_trim"]
            def body(g, key):
                return fn({"g": g[0]}, cfg, "data", "pod", key)["g"][None]
            sm = jax.shard_map(body, mesh=mesh,
                               in_specs=(P(("pod","data"), None), P()),
                               out_specs=P(("pod","data"), None),
                               axis_names=frozenset({"pod","data"}),
                               check_vma=False)
            out = np.asarray(jax.jit(sm)(jnp.asarray(g_all), jax.random.PRNGKey(0)))
            ok = bool((np.abs(out) <= np.abs(honest).max() + 1e-3).all())
            print(json.dumps(dict(bounded=ok, mx=float(np.abs(out).max()))))
        """)
        assert res["bounded"], res


class TestRobustTraining:
    def test_trimmed_training_survives_byzantine_worker(self):
        """Decentralized training with a sign-flipping Byzantine worker:
        trimmed_mean keeps the loss finite and decreasing; param copies
        stay in exact consensus."""
        res = _run_subprocess("""
            import dataclasses
            from repro.configs import get_config, reduced
            from repro.distributed.trainer import (TrainConfig, make_train_step,
                replicate_for_workers, worker_opt_init, param_spread)
            from repro.distributed.aggregation import AggregatorConfig
            from repro.optim import AdamWConfig
            from repro.data import SyntheticLMData
            import repro.models.model as M
            mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 3)
            cfg = dataclasses.replace(reduced(get_config("paper_sim")),
                                      attn_impl="naive")
            params = M.init_params(jax.random.PRNGKey(0), cfg)
            data = SyntheticLMData(cfg.vocab, 32, 8, flavour="markov", seed=0)
            tc = TrainConfig(arch=cfg,
                agg=AggregatorConfig(kind="trimmed_mean", F=1),
                opt=AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=30),
                byzantine_workers=(2,))
            factory, _ = make_train_step(tc, mesh)
            pw = replicate_for_workers(params, 4)
            ow = worker_opt_init(pw)
            losses = []
            with jax.set_mesh(mesh):
                step = jax.jit(factory(pw))
                for s in range(12):
                    pw, ow, loss = step(pw, ow, data.batch(s),
                                        jax.random.PRNGKey(s))
                    losses.append(float(loss))
            print(json.dumps(dict(first=losses[0], last=losses[-1],
                                  spread=float(param_spread(pw)))))
        """)
        assert np.isfinite(res["last"])
        assert res["last"] < res["first"]
        assert res["spread"] < 1e-5  # identical trim output => exact consensus

    def test_pushsum_training_bounded_divergence(self):
        """Gossip aggregation: worker copies drift by the consensus error,
        which stays bounded and training still descends."""
        res = _run_subprocess("""
            import dataclasses
            from repro.configs import get_config, reduced
            from repro.distributed.trainer import (TrainConfig, make_train_step,
                replicate_for_workers, worker_opt_init, param_spread)
            from repro.distributed.aggregation import AggregatorConfig
            from repro.optim import AdamWConfig
            from repro.data import SyntheticLMData
            import repro.models.model as M
            mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 3)
            cfg = dataclasses.replace(reduced(get_config("paper_sim")),
                                      attn_impl="naive")
            params = M.init_params(jax.random.PRNGKey(0), cfg)
            data = SyntheticLMData(cfg.vocab, 32, 8, flavour="markov", seed=0)
            tc = TrainConfig(arch=cfg,
                agg=AggregatorConfig(kind="pushsum", gossip_rounds=16,
                                     gamma_period=4, drop_prob=0.2),
                opt=AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=30))
            factory, _ = make_train_step(tc, mesh)
            pw = replicate_for_workers(params, 4)
            ow = worker_opt_init(pw)
            losses = []
            with jax.set_mesh(mesh):
                step = jax.jit(factory(pw))
                for s in range(10):
                    pw, ow, loss = step(pw, ow, data.batch(s),
                                        jax.random.PRNGKey(s))
                    losses.append(float(loss))
            print(json.dumps(dict(first=losses[0], last=losses[-1],
                                  spread=float(param_spread(pw)))))
        """)
        assert np.isfinite(res["last"])
        assert res["last"] < res["first"]
        assert 0 < res["spread"] < 0.05

    def test_gspmd_with_tensor_parallel_matches_single_device(self):
        """The GSPMD mean path on a (1,2,4) mesh must track the same loss
        as single-device execution (same seeds, same data)."""
        res = _run_subprocess("""
            import dataclasses
            from repro.configs import get_config, reduced
            from repro.distributed.trainer import TrainConfig, make_train_step
            from repro.optim import AdamWConfig, adamw_init
            from repro.data import SyntheticLMData
            import repro.models.model as M
            cfg = dataclasses.replace(reduced(get_config("qwen3_8b")),
                                      attn_impl="naive")
            params = M.init_params(jax.random.PRNGKey(0), cfg)
            data = SyntheticLMData(cfg.vocab, 32, 8, seed=0)
            results = {}
            for name, shape in [("dp_tp", (2, 4)), ("single", (1, 1))]:
                mesh = jax.make_mesh(shape, ("data", "model"),
                    axis_types=(jax.sharding.AxisType.Auto,) * 2,
                    devices=jax.devices()[: shape[0]*shape[1]])
                tc = TrainConfig(arch=cfg, opt=AdamWConfig(
                    lr=1e-3, warmup_steps=2, total_steps=20))
                factory, _ = make_train_step(tc, mesh)
                p, o = params, adamw_init(params)
                with jax.set_mesh(mesh):
                    step = jax.jit(factory(p))
                    ls = []
                    for s in range(4):
                        p, o, loss = step(p, o, data.batch(s))
                        ls.append(float(loss))
                results[name] = ls
            print(json.dumps(results))
        """)
        np.testing.assert_allclose(res["dp_tp"], res["single"], rtol=2e-3,
                                   atol=2e-3)


class TestShardingRules:
    def test_param_specs_cover_all_archs(self):
        """Every arch's param tree gets a spec tree of identical structure,
        and every sharded axis divides the dimension (single-pod mesh)."""
        from repro.configs import all_configs, reduced
        from repro.distributed.sharding import param_specs
        from repro.models import model as M
        from jax.sharding import PartitionSpec as P

        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        for name, cfg in all_configs().items():
            r = reduced(cfg)
            struct = jax.eval_shape(
                lambda c=r: M.init_params(jax.random.PRNGKey(0), c)
            )
            specs = param_specs(struct, r, mesh, fsdp=True)
            s_leaves = jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)
            )
            p_leaves = jax.tree_util.tree_leaves(struct)
            assert len(s_leaves) == len(p_leaves), name
