"""Equivalence tests for the EXPERIMENTS.md §Perf optimization variants.

Every beyond-paper performance change must be semantics-preserving; these
tests pin that: sharded MoE == GSPMD MoE, padded heads == unpadded heads,
Pallas WKV6 gradients == jnp gradients, bf16-moment AdamW tracks f32.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_init, adamw_update

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey(0)


def test_padded_heads_exact_equivalence():
    """pad_heads_to: zero wq columns / wo rows => identical logits."""
    cfg = dataclasses.replace(
        reduced(get_config("minitron_4b")), n_heads=3, n_kv_heads=1,
        head_dim=32,
    )
    cfgp = dataclasses.replace(cfg, pad_heads_to=4)
    p = M.init_params(KEY, cfg)
    pp = M.init_params(KEY, cfgp)

    def graft(a, b):
        if a.shape == b.shape:
            return a
        out = jnp.zeros_like(b)
        return out.at[tuple(slice(0, s) for s in a.shape)].set(a)

    pp = jax.tree_util.tree_map(graft, p, pp)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    l1, _ = M.forward_train(p, cfg, toks)
    l2, _ = M.forward_train(pp, cfgp, toks)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)
    # decode path too
    _, c1 = M.prefill(p, cfg, toks, cache_len=20)
    _, c2 = M.prefill(pp, cfgp, toks, cache_len=20)
    nxt = jax.random.randint(jax.random.PRNGKey(1), (2, 1), 0, cfg.vocab)
    d1, _ = M.decode_step(p, cfg, c1, nxt)
    d2, _ = M.decode_step(pp, cfgp, c2, nxt)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-5)


def test_wkv6_kernel_custom_vjp_matches_jnp_grads():
    from repro.kernels.wkv6.ops import wkv6
    from repro.kernels.wkv6.ref import wkv6_chunked_jnp

    rng = np.random.default_rng(0)
    BH, T, K = 2, 64, 16
    r, k, v = (jnp.asarray(rng.normal(size=(BH, T, K)).astype(np.float32))
               for _ in range(3))
    lw = jnp.asarray(-np.exp(rng.normal(size=(BH, T, K))).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(BH, K)).astype(np.float32))

    gk = jax.grad(
        lambda *a: wkv6(*a, chunk=16, backend="pallas")[0].sum(),
        argnums=(0, 1, 2, 3, 4),
    )(r, k, v, lw, u)
    gj = jax.grad(
        lambda *a: wkv6_chunked_jnp(*a, chunk=16)[0].sum(),
        argnums=(0, 1, 2, 3, 4),
    )(r, k, v, lw, u)
    for a, b in zip(gk, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_bf16_moments_track_f32():
    cfg32 = AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=1,
                        total_steps=100, clip_norm=100.0)
    cfg16 = dataclasses.replace(cfg32, moment_dtype="bfloat16")
    params = {"w": jnp.array([5.0, -3.0, 1.0])}
    s32 = adamw_init(params)
    s16 = adamw_init(params, "bfloat16")
    p32 = p16 = params
    for i in range(50):
        g32 = {"w": 2 * p32["w"]}
        g16 = {"w": 2 * p16["w"]}
        p32, s32 = adamw_update(cfg32, g32, s32, p32)
        p16, s16 = adamw_update(cfg16, g16, s16, p16)
    # both trajectories descend and the bf16-moment one tracks f32 closely
    assert float(jnp.abs(p16["w"]).max()) < float(jnp.abs(params["w"]).max())
    np.testing.assert_allclose(np.asarray(p16["w"]), np.asarray(p32["w"]),
                               atol=0.05)


def test_sharded_moe_matches_gspmd():
    """Runs on 8 fake devices in a subprocess (needs a multi-device mesh)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config, reduced
        from repro.models import layers as L
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 3)
        cfg = reduced(get_config("olmoe_1b_7b"))
        p = L.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              dtype=jnp.float32)
        y_ref, _ = L._moe_block_gspmd(p, x, cfg)
        cfg_s = dataclasses.replace(cfg, moe_impl="sharded")
        with jax.set_mesh(mesh):
            y_s, _ = jax.jit(lambda p, x: L.moe_block(p, x, cfg_s))(p, x)
        print(json.dumps({"err": float(jnp.abs(y_s - y_ref).max())}))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    for attempt in range(2):
        out = subprocess.run([sys.executable, "-c", prog],
                             capture_output=True, text=True, timeout=420,
                             env=env, cwd=REPO)
        if out.returncode == 0 or "rendezvous" not in out.stderr.lower():
            break
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert res["err"] < 1e-4


def test_sharded_trim_equals_plain_trim():
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.aggregation import AGGREGATORS, AggregatorConfig
        from repro.kernels.trimmed_mean.ref import trimmed_mean_ref
        mesh = jax.make_mesh((2, 4), ("pod", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        g_all = jnp.asarray(rng.normal(size=(8, 1003)).astype(np.float32))
        cfg = AggregatorConfig(kind="trimmed_mean_sharded", F=2)
        fn = AGGREGATORS["trimmed_mean_sharded"]
        def body(g, key):
            return fn({"g": g[0]}, cfg, "data", "pod", key)["g"][None]
        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(("pod","data"), None), P()),
                           out_specs=P(("pod","data"), None),
                           axis_names=frozenset({"pod","data"}),
                           check_vma=False)
        out = np.asarray(jax.jit(sm)(g_all, jax.random.PRNGKey(0)))
        want = np.asarray(trimmed_mean_ref(g_all, 2))
        print(json.dumps({"err": float(np.abs(out - want).max())}))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    for attempt in range(2):
        out = subprocess.run([sys.executable, "-c", prog],
                             capture_output=True, text=True, timeout=420,
                             env=env, cwd=REPO)
        if out.returncode == 0 or "rendezvous" not in out.stderr.lower():
            break
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )
    assert res["err"] < 1e-5
