"""Optimizer, data pipeline, checkpoint, analysis-layer tests."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_lr
from repro.data import SyntheticLMData
from repro.checkpoint import save_checkpoint, restore_checkpoint, latest_step
from repro.analysis.roofline import (
    parse_collectives, roofline_terms, model_flops, _shape_bytes,
)
from repro.configs import get_config, INPUT_SHAPES


class TestAdamW:
    def test_quadratic_descent(self):
        cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                          total_steps=200, clip_norm=100.0)
        params = {"w": jnp.array([5.0, -3.0])}
        state = adamw_init(params)
        for _ in range(150):
            grads = {"w": 2 * params["w"]}
            params, state = adamw_update(cfg, grads, state, params)
        assert float(jnp.abs(params["w"]).max()) < 0.1

    def test_grad_clipping(self):
        cfg = AdamWConfig(lr=1e-3, clip_norm=1.0, warmup_steps=1,
                          total_steps=10)
        params = {"w": jnp.zeros(4)}
        state = adamw_init(params)
        p1, s1 = adamw_update(cfg, {"w": jnp.full(4, 1e6)}, state, params)
        p2, s2 = adamw_update(cfg, {"w": jnp.full(4, 2e6)}, state, params)
        np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                                   rtol=1e-6)

    def test_cosine_schedule_shape(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_frac=0.1)
        lrs = [float(cosine_lr(cfg, jnp.int32(s))) for s in (0, 5, 10, 55, 100)]
        assert lrs[0] == 0.0
        assert lrs[1] == pytest.approx(0.5, abs=0.01)
        assert lrs[2] == pytest.approx(1.0, abs=0.01)
        assert 0.1 < lrs[3] < 1.0
        assert lrs[4] == pytest.approx(0.1, abs=0.01)

    def test_weight_decay_only_matrices(self):
        cfg = AdamWConfig(lr=0.1, weight_decay=0.5, warmup_steps=1,
                          total_steps=10)
        params = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
        state = adamw_init(params)
        zero_grads = {"w": jnp.zeros((2, 2)), "b": jnp.zeros((2,))}
        p, _ = adamw_update(cfg, zero_grads, state, params)
        assert float(p["w"].max()) < 1.0   # decayed
        assert float(p["b"].min()) == 1.0  # biases/scales not decayed


class TestData:
    def test_determinism(self):
        d = SyntheticLMData(1000, 16, 4, flavour="markov", seed=3)
        b1, b2 = d.batch(7), d.batch(7)
        assert (np.asarray(b1["tokens"]) == np.asarray(b2["tokens"])).all()
        b3 = d.batch(8)
        assert (np.asarray(b1["tokens"]) != np.asarray(b3["tokens"])).any()

    def test_labels_are_shifted_tokens(self):
        d = SyntheticLMData(1000, 16, 4, seed=0)
        b = d.batch(0)
        assert b["tokens"].shape == (4, 16) and b["labels"].shape == (4, 16)

    def test_agent_shards_differ(self):
        d = SyntheticLMData(1000, 16, 8, flavour="markov", n_agents=4, seed=0)
        s0 = d.shard_batch(0, agent=0, local_batch=2)
        s1 = d.shard_batch(0, agent=1, local_batch=2)
        assert (np.asarray(s0["tokens"]) != np.asarray(s1["tokens"])).any()

    def test_tokens_in_vocab(self):
        d = SyntheticLMData(50, 64, 4, flavour="markov", seed=1)
        t = np.asarray(d.batch(0)["tokens"])
        assert t.min() >= 0 and t.max() < 50


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {
            "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "nested": {"b": jnp.ones((2,), jnp.bfloat16),
                       "c": [jnp.zeros(3), jnp.full(2, 7.0)]},
        }
        d = str(tmp_path / "ckpt")
        save_checkpoint(d, 5, tree)
        save_checkpoint(d, 9, tree)
        assert latest_step(d) == 9
        restored = restore_checkpoint(d, 5, tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_shape_mismatch_rejected(self, tmp_path):
        d = str(tmp_path / "ckpt")
        save_checkpoint(d, 1, {"a": jnp.zeros((2, 2))})
        with pytest.raises(ValueError):
            restore_checkpoint(d, 1, {"a": jnp.zeros((3, 3))})


class TestRooflineAnalysis:
    def test_shape_bytes(self):
        assert _shape_bytes("bf16[2,3]") == 12
        assert _shape_bytes("f32[10]") == 40
        assert _shape_bytes("(f32[4], u32[2])") == 24
        assert _shape_bytes("pred[]") == 1

    def test_parse_collectives_synthetic(self):
        hlo = """
          %ag = bf16[32,128]{1,0} all-gather(bf16[2,128]{1,0} %x), replica_groups=[32,16]<=[512], dimensions={0}
          %ar = f32[64]{0} all-reduce(f32[64]{0} %y), replica_groups={{0,1,2,3}}, to_apply=%add
          %rs = f32[8]{0} reduce-scatter(f32[64]{0} %z), replica_groups=[64,8]<=[512], dimensions={0}
          %cp = bf16[16]{0} collective-permute(bf16[16]{0} %w), source_target_pairs={{0,1}}
        """
        out = parse_collectives(hlo, 512)
        kinds = out["count_by_kind"]
        assert kinds["all-gather"] == 1 and kinds["all-reduce"] == 1
        assert kinds["reduce-scatter"] == 1 and kinds["collective-permute"] == 1
        ag = out["bytes_by_kind"]["all-gather"]
        assert ag == pytest.approx((16 - 1) / 16 * 32 * 128 * 2)
        ar = out["bytes_by_kind"]["all-reduce"]
        assert ar == pytest.approx(2 * 3 / 4 * 64 * 4)
        rs = out["bytes_by_kind"]["reduce-scatter"]
        assert rs == pytest.approx(7 / 8 * 8 * 4 * 8)
        cp = out["bytes_by_kind"]["collective-permute"]
        assert cp == pytest.approx(16 * 2)

    def test_roofline_dominant_term(self):
        cost = {"flops": 197e12, "bytes accessed": 819e9 * 3}
        coll = {"wire_bytes_per_device": 50e9 * 0.5}
        t = roofline_terms(cost, coll, 256, 1e15)
        assert t["dominant"] == "memory"
        assert t["compute_s"] == pytest.approx(1.0)
        assert t["memory_s"] == pytest.approx(3.0)
        assert t["collective_s"] == pytest.approx(0.5)

    def test_model_flops_kinds(self):
        cfg = get_config("qwen3_8b")
        tr = model_flops(cfg, INPUT_SHAPES["train_4k"])
        pf = model_flops(cfg, INPUT_SHAPES["prefill_32k"])
        dc = model_flops(cfg, INPUT_SHAPES["decode_32k"])
        assert tr == pytest.approx(6 * cfg.param_count() * 256 * 4096, rel=1e-6)
        assert pf == pytest.approx(2 * cfg.param_count() * 32 * 32768, rel=1e-6)
        assert dc == pytest.approx(2 * cfg.param_count() * 128, rel=1e-6)
        moe = get_config("qwen3_moe_235b_a22b")
        assert model_flops(moe, INPUT_SHAPES["train_4k"]) < \
            6 * moe.param_count() * 256 * 4096 / 5  # active << total

    def test_memory_model_405b_single_pod_infeasible(self):
        """The analytic model reproduces the real capacity wall: 405B
        training with f32 Adam moments cannot fit 256 x 16 GB."""
        from repro.analysis.memory_model import train_memory_gb
        cfg = get_config("llama3_405b")
        single = train_memory_gb(cfg, INPUT_SHAPES["train_4k"],
                                 {"data": 16, "model": 16}, fsdp=True,
                                 n_micro=16)
        multi = train_memory_gb(cfg, INPUT_SHAPES["train_4k"],
                                {"pod": 2, "data": 16, "model": 16},
                                fsdp=True, n_micro=8)
        assert not single["fits_16gb"]
        assert multi["optimizer_gb"] < single["optimizer_gb"]


class TestDryRunHelpers:
    def test_input_specs_no_allocation(self):
        from repro.launch import dryrun as DR
        for shape in INPUT_SHAPES:
            specs = DR.input_specs("qwen3_8b", shape)
            for leaf in jax.tree_util.tree_leaves(specs):
                assert isinstance(leaf, jax.ShapeDtypeStruct)

    def test_long500k_switches_to_sliding_window(self):
        from repro.launch.dryrun import serve_cfg_for, LONG_WINDOW
        cfg = get_config("llama3_405b")
        out = serve_cfg_for(cfg, INPUT_SHAPES["long_500k"])
        assert out.block_pattern == ("swa",) and out.window == LONG_WINDOW
        # ssm arch unchanged
        r = get_config("rwkv6_1b6")
        assert serve_cfg_for(r, INPUT_SHAPES["long_500k"]).block_pattern == \
            ("wkv6",)

    def test_micro_batching_divides_evenly(self):
        from repro.launch.dryrun import pick_n_micro
        from repro.launch.mesh import make_production_mesh
        import repro.launch.dryrun as DR
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)

        class FakeMesh:
            shape = {"data": 16, "model": 16}
        for arch in ("qwen3_8b", "llama3_405b", "olmoe_1b_7b"):
            cfg = get_config(arch)
            n = pick_n_micro(cfg, INPUT_SHAPES["train_4k"], FakeMesh())
            b_dev = 256 // 16
            assert b_dev % n == 0 and n >= 1


class TestCompileCacheDir:
    """The persistent compile cache is placeable from outside: it follows
    ``JAX_COMPILATION_CACHE_DIR`` when set and sets nothing itself, and
    otherwise sits at the fixed ``<checkout>/.jax-cache``."""

    @pytest.mark.parametrize("env_dir", [None, "outside"])
    def test_enable_picks_the_directory(self, env_dir, monkeypatch,
                                        tmp_path):
        from benchmarks import compile_cache

        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        saved = {n: getattr(jax.config, n) for n in names}
        # what jax itself read from the environment at import
        jax.config.update("jax_compilation_cache_dir", None)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(compile_cache.REPO, ".jax-cache")
        else:
            want = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        try:
            got = compile_cache.enable()
            assert got == want
            expect_config = None if env_dir else want
            assert jax.config.jax_compilation_cache_dir == expect_config
        finally:
            for n, v in saved.items():
                jax.config.update(n, v)
