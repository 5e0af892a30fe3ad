"""Fused Algorithm 3 engine + innovation kernel + batched social sweeps.

The contract under test: the Pallas innovation kernel (interpret mode on
CPU — the identical traced program that compiles on TPU) matches the XLA
oracle; the fused engine's trajectories are bit-identical to the
pre-refactor ``run_social_learning`` structure (a step-by-step oracle
re-run here with the satellite-mandated PRNG fixes) and to the swept path;
``store="final"`` materializes no (T, N, m) value (jaxpr inspection); the
link-mask and signal PRNG streams have disjoint fold-in domains (the seed
scheme aliased them whenever ``seed == signal_seed``); a
(drop x Gamma x topology x seed) grid of >= 48 scenarios runs as ONE
compiled program; and the compiled-sweep cache is LRU-bounded.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.graphs import block_complete_edge_list, make_hierarchy
from repro.core.hps import HPSConfig, hps_fusion
from repro.core.pushsum import (
    init_sparse_state,
    sparse_pushsum_step,
    step_edge_mask,
)
from repro.core.signals import make_confused_model
from repro.core.social import (
    N_SOCIAL_STREAMS,
    STREAM_LINK,
    STREAM_SIGNAL,
    kl_dual_averaging_update,
    run_social_learning,
    run_social_runtime,
    social_runtime_from_edge_list,
    social_stream_fold,
)
from repro.core.sweeps import run_social_grid, run_social_sweep
from repro.kernels.social_innov import innovation_ref, resolve_backend
from repro.kernels.social_innov.social_innov import innovation_pallas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(0)


def _innov_problem(N, m, S, seed):
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(size=(N, m)).astype(np.float32))
    mass = jnp.asarray(np.abs(rng.normal(size=(N,))).astype(np.float32))
    u = jnp.asarray(rng.random(N).astype(np.float32))
    probs = rng.dirichlet(np.ones(S), size=N).astype(np.float32)
    cdf = jnp.cumsum(jnp.asarray(probs), axis=-1)
    lt = jnp.asarray(np.log(np.maximum(
        rng.dirichlet(np.ones(S), size=(N, m)), 2e-2
    )).astype(np.float32))
    return z, mass, u, cdf, lt


class TestInnovationKernel:
    @pytest.mark.parametrize("N,m,S,block_n", [
        (29, 3, 4, 8),      # N far from a block multiple: padding inert
        (64, 5, 7, 64),
        (18, 3, 4, 1024),   # block_n > N clamps
        (128, 2, 3, 32),
    ])
    def test_pallas_matches_xla_ref(self, N, m, S, block_n):
        z, mass, u, cdf, lt = _innov_problem(N, m, S, seed=N)
        z_r, mu_r = innovation_ref(z, mass, u, cdf, lt)
        z_p, mu_p = innovation_pallas(z, mass, u, cdf, lt,
                                      block_n=block_n, interpret=True)
        np.testing.assert_array_equal(np.asarray(z_p), np.asarray(z_r))
        np.testing.assert_allclose(np.asarray(mu_p), np.asarray(mu_r),
                                   rtol=1e-6, atol=1e-7)

    def test_ref_matches_seed_lowering(self):
        """The oracle IS the seed path's op sequence (plus the alphabet
        clamp): inverse-CDF sample, take_along_axis gather, z += loglik,
        kl_dual_averaging_update."""
        z, mass, u, cdf, lt = _innov_problem(23, 4, 5, seed=1)
        sig = jnp.minimum((u[:, None] > cdf).sum(axis=-1), cdf.shape[1] - 1)
        loglik = jnp.take_along_axis(
            lt, sig[:, None, None].astype(jnp.int32), axis=2
        )[:, :, 0]
        z_want = z + loglik
        mu_want = kl_dual_averaging_update(z_want, mass)
        z_got, mu_got = innovation_ref(z, mass, u, cdf, lt)
        np.testing.assert_array_equal(np.asarray(z_got), np.asarray(z_want))
        np.testing.assert_array_equal(np.asarray(mu_got), np.asarray(mu_want))

    def test_uniform_above_cdf_top_clamps_to_last_letter(self):
        """An fp32 cumsum can end below 1.0; a uniform above it must map to
        the last alphabet letter, not index past the table (the unclamped
        sample NaN-fills the XLA gather while the Pallas one-hot silently
        drops the signal — permanent z poisoning AND backend divergence)."""
        z, mass, _, cdf, lt = _innov_problem(8, 3, 4, seed=3)
        cdf = cdf.at[:, -1].set(1.0 - 1e-6)
        u = jnp.full((8,), 0.9999999, jnp.float32)
        z_r, mu_r = innovation_ref(z, mass, u, cdf, lt)
        z_p, mu_p = innovation_pallas(z, mass, u, cdf, lt, block_n=8,
                                      interpret=True)
        assert np.isfinite(np.asarray(z_r)).all()
        np.testing.assert_array_equal(np.asarray(z_r),
                                      np.asarray(z + lt[:, :, -1]))
        np.testing.assert_array_equal(np.asarray(z_p), np.asarray(z_r))
        np.testing.assert_allclose(np.asarray(mu_p), np.asarray(mu_r),
                                   rtol=1e-6, atol=1e-7)

    def test_zero_mass_rows_stay_finite(self):
        """mass = 0 (the padding-row regime) must not produce NaN/inf —
        the belief degrades to the max-subtracted softmax of z / 1e-30."""
        z, mass, u, cdf, lt = _innov_problem(16, 3, 4, seed=2)
        mass = mass.at[3].set(0.0).at[7].set(0.0)
        z = z.at[3].set(0.0)
        for got in innovation_pallas(z, mass, u, cdf, lt, block_n=8,
                                     interpret=True):
            assert np.isfinite(np.asarray(got)).all()

    def test_auto_backend_is_xla_off_tpu(self):
        expected = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert resolve_backend("auto") == expected


def _setup(seed=2, sizes=(6, 6, 6), m=3, truth=1, confusion=0.5):
    topo = make_hierarchy(list(sizes), topology="complete", seed=seed)
    model = make_confused_model(N=topo.N, m=m, truth=truth,
                                confusion=confusion, seed=0)
    return topo, model


def _oracle(model, cfg, T, seed, signal_seed):
    """The pre-refactor ``run_social_learning`` scan, re-run verbatim: the
    same sparse push-sum consensus, the UNFUSED five-op innovation sequence
    with the (N, S) cumsum recomputed inside the body, no share hoist, and
    the precomputed host-side fusion schedule — modulo only the
    satellite-mandated PRNG fixes (dst-sorted edge layout, one (N,) uniform
    draw, disjoint stream domains) and the normal-range belief floor. The
    fused engine must reproduce it bit for bit."""
    from repro.core.social import _MU_FLOOR

    topo = cfg.topo
    el = cfg.edge_index()
    src, dst = jnp.asarray(el.src), jnp.asarray(el.dst)
    valid = jnp.asarray(el.valid)
    rep_mask = cfg.rep_mask()
    mask_key = jax.random.PRNGKey(seed)
    base_key = jax.random.PRNGKey(signal_seed)
    fuse = jnp.arange(1, T + 1) % cfg.gamma_period == 0
    state0 = init_sparse_state(jnp.zeros((topo.N, model.m), jnp.float32), el.E)
    log_tables = model.log_tables().astype(jnp.float32)
    truth_probs = model.tables[:, model.truth, :].astype(jnp.float32)

    def body(state, xs):
        do_fusion, t = xs
        mask = step_edge_mask(
            mask_key, t, el.E, cfg.drop_prob, cfg.B,
            fold_t=social_stream_fold(t, STREAM_LINK),
        )
        st = sparse_pushsum_step(state, mask, src, dst, valid, "xla")
        key = jax.random.fold_in(
            base_key, social_stream_fold(t, STREAM_SIGNAL)
        )
        u = jax.random.uniform(key, (topo.N,))
        cdf = jnp.cumsum(truth_probs, axis=-1)
        sig = jnp.minimum((u[:, None] > cdf).sum(axis=-1), model.S - 1)
        loglik = jnp.take_along_axis(
            log_tables, sig[:, None, None].astype(jnp.int32), axis=2
        )[:, :, 0]
        z = st.z + loglik
        mu = kl_dual_averaging_update(z, st.m)
        z_f, m_f = hps_fusion(z, st.m, rep_mask, topo.M)
        z = jnp.where(do_fusion, z_f, z)
        m = jnp.where(do_fusion, m_f, st.m)
        return st._replace(z=z, m=m), mu

    def run():
        _, mus = jax.lax.scan(
            body, state0, (fuse, jnp.arange(T, dtype=jnp.int32))
        )
        log_mu = jnp.log(jnp.maximum(mus, _MU_FLOOR))
        return mus, log_mu - log_mu[:, :, model.truth : model.truth + 1]

    return jax.jit(run)()


class TestEngineEquivalence:
    """Acceptance: fused engine == pre-refactor oracle, bit for bit."""

    @pytest.mark.parametrize("drop,gamma,B", [(0.0, 4, 1), (0.3, 8, 2),
                                              (0.6, 3, 4)])
    def test_fused_engine_matches_oracle(self, drop, gamma, B):
        topo, model = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=gamma, B=B, drop_prob=drop)
        mus, lr = _oracle(model, cfg, T=40, seed=3, signal_seed=11)
        res = run_social_learning(model, cfg, T=40, seed=3, signal_seed=11,
                                  backend="xla")
        np.testing.assert_array_equal(np.asarray(res.beliefs),
                                      np.asarray(mus))
        np.testing.assert_array_equal(np.asarray(res.log_ratio),
                                      np.asarray(lr))

    def test_pallas_backend_matches_xla(self):
        """interpret-mode fused kernels == XLA lowerings over a full run
        (fp tolerance: the softmax max-subtraction reorders rounding)."""
        topo, model = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.3)
        x = run_social_learning(model, cfg, T=50, seed=0, backend="xla")
        p = run_social_learning(model, cfg, T=50, seed=0, backend="pallas")
        np.testing.assert_allclose(np.asarray(p.beliefs),
                                   np.asarray(x.beliefs),
                                   rtol=1e-4, atol=1e-5)

    def test_dense_free_runtime_matches_config_path(self):
        """block_complete_edge_list + run_social_runtime (the N ~ 1e4 path
        that never builds an (N, N) adjacency) == the HPSConfig path."""
        topo, model = _setup()
        el, rep_mask = block_complete_edge_list([6, 6, 6])
        rt = social_runtime_from_edge_list(el, rep_mask, drop_prob=0.3,
                                           gamma_period=8, B=2)
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.3)
        a = run_social_runtime(model, rt, topo.M, T=40, seed=5,
                               signal_seed=9)
        b = run_social_learning(model, cfg, T=40, seed=5, signal_seed=9)
        np.testing.assert_array_equal(np.asarray(a.beliefs),
                                      np.asarray(b.beliefs))

    def test_store_shapes_and_consistency(self):
        topo, model = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.3)
        N, m, T = topo.N, model.m, 60
        traj = run_social_learning(model, cfg, T=T, seed=0)
        lrr = run_social_learning(model, cfg, T=T, seed=0, store="log_ratio")
        fin = run_social_learning(model, cfg, T=T, seed=0, store="final")
        assert traj.beliefs.shape == traj.log_ratio.shape == (T, N, m)
        assert lrr.beliefs.shape == (N, m) and lrr.log_ratio.shape == (T,)
        assert fin.beliefs.shape == fin.log_ratio.shape == (N, m)
        b = np.asarray(traj.beliefs)
        lr = np.asarray(traj.log_ratio)
        np.testing.assert_array_equal(np.asarray(fin.beliefs), b[-1])
        np.testing.assert_array_equal(np.asarray(lrr.beliefs), b[-1])
        np.testing.assert_array_equal(np.asarray(fin.log_ratio), lr[-1])
        worst = np.delete(lr, model.truth, axis=2).max(axis=(1, 2))
        np.testing.assert_array_equal(np.asarray(lrr.log_ratio), worst)

    def test_invalid_store_rejected(self):
        topo, model = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.3)
        with pytest.raises(ValueError, match="store"):
            run_social_learning(model, cfg, T=5, store="everything")


# The jaxpr walker these tests introduced now lives in repro.statics.walk
# (PR 6); imported under the historical names so the assertions below stay
# bit-for-bit what they were when the helpers were local.
from repro.statics.walk import collect_avals as _collect_avals  # noqa: E402
from repro.statics.walk import subjaxprs as _subjaxprs  # noqa: E402,F401


class TestNoTrajectoryMaterialized:
    """Acceptance: store="final" holds no (T, ...) value in its jaxpr."""

    T = 37   # distinct from N=18, m=3, E=90 so the walker cannot confuse axes

    def _shapes(self, store):
        from repro.core.social import _social_scan_core, make_social_runtime

        topo, model = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.2)
        rt = make_social_runtime(cfg)
        truth_probs = model.tables[:, model.truth, :].astype(jnp.float32)

        def run(mk, sk):
            return _social_scan_core(
                mk, sk, rt, model.log_tables().astype(jnp.float32),
                jnp.cumsum(truth_probs, axis=-1),
                truth=model.truth, M=topo.M, T=self.T, store=store,
                backend="xla",
            )

        key = jax.random.PRNGKey(0)
        return _collect_avals(jax.make_jaxpr(run)(key, key).jaxpr, [])

    def test_final_store_has_no_T_value(self):
        shapes = self._shapes("final")
        assert shapes, "jaxpr walker found no values"
        traj_like = [s for s in shapes if len(s) >= 2 and s[0] == self.T]
        assert not traj_like, f"(T, ...) intermediates: {traj_like}"

    def test_log_ratio_store_carries_only_curves(self):
        shapes = self._shapes("log_ratio")
        traj_like = [s for s in shapes if len(s) >= 2 and s[0] == self.T]
        assert not traj_like, f"(T, ...) intermediates: {traj_like}"
        assert (self.T,) in shapes          # the in-scan-reduced curve

    def test_detector_flags_trajectory_store(self):
        """Sanity: the same walker does find the (T, N, m) history in the
        trajectory store, so the final-store assertion has teeth."""
        shapes = self._shapes("trajectory")
        assert (self.T, 18, 3) in shapes


class TestPRNGStreams:
    def test_streams_disjoint_over_horizon(self):
        """Regression for the seed scheme, which folded plain ``t`` into
        both base keys — with seed == signal_seed the link-mask key at t
        EQUALED the signal key at t. The two fold-in domains must never
        intersect over any horizon."""
        T = 20000
        t = np.arange(T, dtype=np.uint64)
        folds = {
            s: set(np.asarray(social_stream_fold(t, s)).tolist())
            for s in (STREAM_LINK, STREAM_SIGNAL)
        }
        assert not (folds[STREAM_LINK] & folds[STREAM_SIGNAL])
        assert len(set().union(*folds.values())) == 2 * T
        assert N_SOCIAL_STREAMS == 2

    def test_seed_scheme_would_have_aliased(self):
        """The bug being regressed: with one shared fold value the two
        per-iteration keys coincide whenever the base keys do."""
        k = jax.random.PRNGKey(7)
        np.testing.assert_array_equal(       # the seed scheme: both fold t
            np.asarray(jax.random.fold_in(k, 3)),
            np.asarray(jax.random.fold_in(k, 3)),
        )
        new_mask = jax.random.fold_in(k, social_stream_fold(3, STREAM_LINK))
        new_sig = jax.random.fold_in(k, social_stream_fold(3, STREAM_SIGNAL))
        assert (np.asarray(new_mask) != np.asarray(new_sig)).any()

    def test_equal_seeds_still_learn_and_streams_both_matter(self):
        topo, model = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.4)
        base = run_social_learning(model, cfg, T=80, seed=5, signal_seed=5)
        other_sig = run_social_learning(model, cfg, T=80, seed=5,
                                        signal_seed=6)
        other_mask = run_social_learning(model, cfg, T=80, seed=6,
                                         signal_seed=5)
        b = np.asarray(base.beliefs)
        assert np.isfinite(b).all()
        assert (b != np.asarray(other_sig.beliefs)).any()    # signals matter
        assert (b != np.asarray(other_mask.beliefs)).any()   # masks matter


def _grid_fixture():
    topos = [make_hierarchy([6, 6, 6], topology="ring+",
                            extra_edge_prob=0.8, seed=s) for s in range(2)]
    model = make_confused_model(N=18, m=3, truth=1, confusion=0.3, seed=0)
    cfgs = []
    for topo in topos:
        for drop in (0.0, 0.3, 0.6):
            for gamma in (4, 8):
                cfgs.append(HPSConfig(topo=topo, gamma_period=gamma, B=2,
                                      drop_prob=drop))
    return model, cfgs


class TestSocialSweep:
    def test_drop_gamma_topo_seed_grid_single_trace(self):
        """Acceptance: 2 topologies x 3 drops x 2 Γ x 4 seeds = 48
        scenarios as ONE compiled program — one jit cache entry, no retrace
        on a second seed batch."""
        from repro.core.sweeps import _social_sweep_fn, cache_registry

        model, cfgs = _grid_fixture()
        res = run_social_grid(model, cfgs, T=25, seeds=list(range(4)))
        assert res.K == 48
        assert res.log_ratio.shape == (48, 25)
        assert res.beliefs.shape == (48, 18, 3)
        fn = _social_sweep_fn(None, "data", truth=model.truth, M=3, T=25,
                              store="log_ratio", backend="xla")
        assert fn._cache_size() == 1
        res2 = run_social_grid(model, cfgs, T=25, seeds=list(range(4, 8)))
        assert fn._cache_size() == 1         # same shapes -> no retrace
        assert res2.K == 48
        info = cache_registry()["social.compiled"].cache_info()
        assert info.currsize <= info.maxsize

    def test_uniform_E_grid_matches_single_runs_bit_identical(self):
        """Acceptance: traced (drop, Γ) on the vmap axis must reproduce
        each config's single run bit for bit (single topology -> no edge
        padding -> identical link-mask streams)."""
        topo, model = _setup()
        cfgs = [HPSConfig(topo=topo, gamma_period=g, B=2, drop_prob=d)
                for d in (0.0, 0.4, 0.8) for g in (3, 8)]
        res = run_social_grid(model, cfgs, T=30, seeds=[0, 3],
                              store="log_ratio")
        for k in range(res.K):
            ci, sd = int(res.cfg[k]), int(res.seed[k])
            single = run_social_learning(
                model, cfgs[ci], T=30, seed=sd, signal_seed=sd,
                backend="xla", store="log_ratio",
            )
            np.testing.assert_array_equal(np.asarray(res.log_ratio[k]),
                                          np.asarray(single.log_ratio))
            np.testing.assert_array_equal(np.asarray(res.beliefs[k]),
                                          np.asarray(single.beliefs))
            assert np.float32(res.drop_prob[k]) == np.float32(
                cfgs[ci].drop_prob)
            assert int(res.gamma[k]) == cfgs[ci].gamma_period

    def test_mixed_E_grid_matches_padded_runtimes(self):
        """Topology draws with different edge counts pad to a common E —
        which re-indexes the (E,) link-mask draw, so the contract is
        bit-identity against the single run on the SAME padded runtime."""
        from repro.core.social import make_social_runtime

        model, cfgs = _grid_fixture()
        e_max = max(int(np.count_nonzero(c.topo.adj)) for c in cfgs)
        e_all = {int(np.count_nonzero(c.topo.adj)) for c in cfgs}
        assert len(e_all) > 1, "fixture must exercise heterogeneous E"
        res = run_social_grid(model, cfgs, T=25, seeds=[1],
                              store="log_ratio")
        for k in range(0, res.K, 5):
            ci, sd = int(res.cfg[k]), int(res.seed[k])
            rt = make_social_runtime(cfgs[ci], e_max=e_max)
            single = run_social_runtime(
                model, rt, cfgs[ci].topo.M, T=25, seed=sd,
                backend="xla", store="log_ratio",
            )
            np.testing.assert_array_equal(np.asarray(res.log_ratio[k]),
                                          np.asarray(single.log_ratio))
            np.testing.assert_array_equal(np.asarray(res.beliefs[k]),
                                          np.asarray(single.beliefs))

    def test_sweep_cross_product_coordinates(self):
        topo, model = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.0)
        res = run_social_sweep(model, cfg, T=10, drop_probs=[0.0, 0.5],
                               gammas=[2, 8], seeds=[0, 1, 2])
        assert res.K == 12
        coords = {(float(res.drop_prob[k]), int(res.gamma[k]),
                   int(res.seed[k])) for k in range(res.K)}
        assert coords == {(d, g, s) for d in (0.0, 0.5) for g in (2, 8)
                          for s in (0, 1, 2)}

    def test_trajectory_store_sweep(self):
        topo, model = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.3)
        res = run_social_sweep(model, cfg, T=15, seeds=[0, 1],
                               store="trajectory")
        assert res.beliefs.shape == (2, 15, 18, 3)
        single = run_social_learning(model, cfg, T=15, seed=1, signal_seed=1)
        np.testing.assert_array_equal(np.asarray(res.beliefs[1]),
                                      np.asarray(single.beliefs))

    def test_incompatible_configs_rejected(self):
        model, cfgs = _grid_fixture()
        other = make_hierarchy([5, 5, 5], topology="complete")
        bad = HPSConfig(topo=other, gamma_period=4, B=2, drop_prob=0.0)
        with pytest.raises(ValueError, match="share"):
            run_social_grid(model, [cfgs[0], bad], T=5, seeds=[0])
        with pytest.raises(ValueError, match="store"):
            run_social_grid(model, [cfgs[0]], T=5, seeds=[0], store="bogus")
        with pytest.raises(ValueError, match="at least one"):
            run_social_grid(model, [], T=5, seeds=[0])

    def test_compiled_cache_is_lru_bounded(self):
        from repro.core.sweeps import cache_registry

        reg = cache_registry()
        compiled = reg["social.compiled"].cache_info()
        runtime = reg["social.runtime"].cache_info()
        assert 0 < compiled.maxsize <= 64
        assert 0 < runtime.maxsize <= 64
        assert compiled.currsize <= compiled.maxsize

    def test_sharded_sweep_equals_single_device(self):
        """K=12 grid over a 4-device data mesh (subprocess, fake CPU
        devices): bit-identical to the single-device vmap."""
        prog = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import json
            import numpy as np
            import jax
            from repro.core.graphs import make_hierarchy
            from repro.core.hps import HPSConfig
            from repro.core.signals import make_confused_model
            from repro.core.sweeps import run_social_sweep

            topo = make_hierarchy([6, 6, 6], topology="complete", seed=0)
            model = make_confused_model(N=18, m=3, truth=1, confusion=0.5,
                                        seed=0)
            cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.0)
            kw = dict(drop_probs=[0.0, 0.4, 0.8], gammas=[4, 16],
                      seeds=[0, 1])
            r1 = run_social_sweep(model, cfg, T=20, **kw)
            mesh = jax.make_mesh((4,), ("data",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            r2 = run_social_sweep(model, cfg, T=20, mesh=mesh, **kw)
            same = bool((np.asarray(r1.log_ratio)
                         == np.asarray(r2.log_ratio)).all())
            err = float(np.abs(np.asarray(r1.beliefs)
                               - np.asarray(r2.beliefs)).max())
            print(json.dumps({"K": int(r2.K), "same": same, "err": err,
                              "devices": jax.device_count()}))
        """)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        for _ in range(2):   # CPU collective rendezvous can flake; retry once
            out = subprocess.run([sys.executable, "-c", prog],
                                 capture_output=True, text=True,
                                 timeout=420, env=env, cwd=REPO)
            if out.returncode == 0 or "rendezvous" not in out.stderr.lower():
                break
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(
            [l for l in out.stdout.splitlines() if l.startswith("{")][-1])
        assert res["devices"] == 4
        assert res["K"] == 12            # pad rows sliced off
        assert res["same"] and res["err"] == 0.0


class TestRunEndGather:
    """Runtimes of a dst-sorted edge index carry each receiver's run end,
    and the Pallas consensus step then finishes with a gather there."""

    @staticmethod
    def _runtime(el, rep):
        return social_runtime_from_edge_list(el, rep, drop_prob=0.3,
                                             gamma_period=4, B=2)

    def test_runtime_sets_seg_end_only_for_sorted_lists(self):
        from repro.core.graphs import EdgeList, hier_edge_list

        el, rep = hier_edge_list([8] * 4, "complete", rep_choice="first")
        rt = self._runtime(el, rep)
        want = np.searchsorted(el.dst, np.arange(el.n), side="right") - 1
        np.testing.assert_array_equal(np.asarray(rt.seg_end), want)
        # an e_max pad extends the last receiver's run
        padded = social_runtime_from_edge_list(
            el, rep, drop_prob=0.3, gamma_period=4, B=2, e_max=el.E + 5)
        assert int(padded.seg_end[-1]) == el.E + 4
        perm = np.random.default_rng(0).permutation(el.E)
        shuffled = EdgeList(src=el.src[perm], dst=el.dst[perm], n=el.n,
                            valid=el.valid[perm])
        assert self._runtime(shuffled, rep).seg_end is None

    def test_engine_with_and_without_seg_end_agree(self):
        """96 complete 8-agent networks: E = 5376 spans two 4096-lane
        kernel blocks, so a run is carried across the block boundary."""
        from repro.core.graphs import hier_edge_list
        from repro.core.plan import ExecutionPlan

        el, rep = hier_edge_list([8] * 96, "complete", rep_choice="first")
        assert el.E > 4096
        rt = self._runtime(el, rep)
        model = make_confused_model(N=el.n, m=3, S=4, truth=0,
                                    confusion=0.5, sharpness=2.0, seed=3)
        plan = ExecutionPlan(store="log_ratio", backend="pallas")
        out = [run_social_runtime(model, r, 96, T=8, seed=7, plan=plan)
               for r in (rt, rt._replace(seg_end=None))]
        (a, b) = out
        np.testing.assert_allclose(np.asarray(a.beliefs),
                                   np.asarray(b.beliefs), atol=1e-6)
        zm = [np.asarray(o.final_state.z)
              / np.asarray(o.final_state.m)[:, None] for o in out]
        np.testing.assert_allclose(zm[0], zm[1], atol=1e-6, rtol=1e-6)

    def test_grid_stacks_sorted_and_mixed_runtimes(self, monkeypatch):
        """A grid of sorted runtimes keeps their run ends; one unsorted
        runtime among them drops every run end, and the grid still runs
        one program whose rows agree with the sorted grid's."""
        from repro.core import sweeps
        from repro.core.graphs import EdgeList
        from repro.core.social import make_social_runtime, stack_runtimes

        model, cfgs = _grid_fixture()
        cfgs = cfgs[:2]
        from repro.core.plan import ExecutionPlan

        kw = dict(T=12, seeds=[1, 2],
                  plan=ExecutionPlan(backend="pallas"))
        monkeypatch.setattr(sweeps, "_SOCIAL_RUNTIME_CACHE",
                            sweeps._LRUCache(maxsize=4))
        sorted_res = run_social_grid(model, cfgs, **kw)
        stacked = list(sweeps._SOCIAL_RUNTIME_CACHE.values())[-1]
        assert stacked.seg_end.shape == (2, 18)

        def shuffled_second(cfg, e_max=None):
            rt = make_social_runtime(cfg, e_max=e_max)
            if cfg is not cfgs[1]:
                return rt
            el = cfg.edge_index()
            perm = np.random.default_rng(0).permutation(el.E)
            return social_runtime_from_edge_list(
                EdgeList(src=el.src[perm], dst=el.dst[perm], n=el.n,
                         valid=el.valid[perm]),
                cfg.topo.rep_mask(), drop_prob=cfg.drop_prob,
                gamma_period=cfg.gamma_period, B=cfg.B, e_max=e_max)

        monkeypatch.setattr(sweeps, "make_social_runtime", shuffled_second)
        monkeypatch.setattr(sweeps, "_SOCIAL_RUNTIME_CACHE",
                            sweeps._LRUCache(maxsize=4))
        mixed_res = run_social_grid(model, cfgs, **kw)
        stacked = list(sweeps._SOCIAL_RUNTIME_CACHE.values())[-1]
        assert stacked.seg_end is None
        assert stack_runtimes([shuffled_second(c) for c in cfgs]).seg_end \
            is None
        rows = np.asarray(mixed_res.cfg) == 0     # the sorted config's rows
        np.testing.assert_allclose(np.asarray(mixed_res.beliefs)[rows],
                                   np.asarray(sorted_res.beliefs)[rows],
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(mixed_res.log_ratio)[rows],
                                   np.asarray(sorted_res.log_ratio)[rows],
                                   atol=1e-5)
