"""Fused Algorithm 1 engine + shared PS fusion + batched HPS sweeps.

The contract under test: the fused scan core is bit-identical to a
pre-refactor-style sparse replay (same edge core, no invariant hoisting,
host-precomputed fusion schedule) and matches the kept dense (N, N)
reference to fp reduction order on the IDENTICAL in-scan mask stream;
``hps_fusion`` and ``byzantine._fusion`` reduce through one
``ps_trimmed_pool`` lowering (F=0 masked mean, F>0 trimmed rep pool);
``store="gap"|"final"`` materializes no (N, N) or (T, N, d) value (jaxpr
inspection); the HPS link-mask stream lives on the dedicated ``~t`` fold-in
domain, disjoint from the social and Byzantine stream domains (the seed
scheme would have aliased the HPS schedule with the social link masks at
equal seeds); the empirical Theorem-1 ``store="gap"`` curve is dominated by
the ``theorem1_bound`` envelope across a (Γ, drop, B) grid; a
(topology x M x Γ x drop x seed) grid of >= 48 scenarios — sub-network
count M traced per scenario — runs as ONE compiled program; and the
compiled-sweep cache is LRU-bounded.
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

from repro.core.byzantine import N_STREAMS as BYZ_STREAMS, stream_fold
from repro.core.graphs import (
    hier_edge_list,
    is_strongly_connected,
    make_hierarchy,
)
from repro.core.hps import (
    HPS_STORES,
    HPSConfig,
    hps_fusion,
    hps_runtime_from_edge_list,
    hps_stream_fold,
    make_hps_runtime,
    ps_trimmed_pool,
    run_hps,
    run_hps_dense,
    run_hps_runtime,
    theorem1_bound,
)
from repro.core.pushsum import (
    init_sparse_state,
    sparse_pushsum_step,
    sparse_ratios,
    step_edge_mask,
)
from repro.core.social import (
    N_SOCIAL_STREAMS,
    STREAM_LINK,
    STREAM_SIGNAL,
    social_stream_fold,
)
from repro.core.sweeps import run_hps_grid, run_hps_sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(sizes=(5, 6, 4), seed=2, d=2, topology="complete"):
    topo = make_hierarchy(list(sizes), topology=topology, seed=seed)
    w = np.random.default_rng(1).normal(size=(topo.N, d)).astype(np.float32)
    return topo, w


# ---------------------------------------------------------------------------
# PS-side fusion: the shared masked-pool reduction
# ---------------------------------------------------------------------------

class TestPSTrimmedPool:
    @pytest.mark.parametrize("R,coord,F", [
        (7, (3,), 0), (7, (3,), 1), (9, (2, 2), 2), (5, (4,), 1),
    ])
    def test_matches_numpy_sort_trim(self, R, coord, F):
        rng = np.random.default_rng(R + F)
        pool = rng.normal(size=(R,) + coord).astype(np.float32)
        valid = rng.random(R) < 0.8
        valid[:max(2 * F + 1, 1)] = True            # keep the pool non-empty
        got = np.asarray(ps_trimmed_pool(
            jnp.asarray(pool), jnp.asarray(valid), F
        ))
        flat = pool.reshape(R, -1)
        want = np.empty(flat.shape[1], np.float32)
        for p in range(flat.shape[1]):
            vals = np.sort(flat[valid, p])
            kept = vals[F: len(vals) - F] if F > 0 else vals
            want[p] = kept.sum() / max(len(kept), 1)
        np.testing.assert_allclose(got.reshape(-1), want, rtol=1e-6,
                                   atol=1e-7)

    def test_traced_F_matches_static(self):
        """The sort-based lowering accepts a traced F — what lets batched
        grids put the trim count on a vmap scenario axis."""
        rng = np.random.default_rng(0)
        pool = jnp.asarray(rng.normal(size=(9, 4)).astype(np.float32))
        valid = jnp.ones(9, bool)
        static = ps_trimmed_pool(pool, valid, 2)
        traced = jax.jit(ps_trimmed_pool)(pool, valid, jnp.int32(2))
        np.testing.assert_array_equal(np.asarray(static), np.asarray(traced))

    def test_byzantine_fusion_reduces_through_it(self):
        """Regression for the rewire: Algorithm 2's PS rule (sort, drop F
        from each end, average the rest) must equal the seed lowering it
        replaced, bit for bit."""
        rng = np.random.default_rng(3)
        n_reps, F = 7, 2
        rep_vals = jnp.asarray(rng.normal(size=(n_reps, 3, 3))
                               .astype(np.float32))
        # the seed-era lowering, verbatim
        s = jnp.sort(rep_vals, axis=0)
        ar = jnp.arange(n_reps)
        keep = (ar >= F) & (ar < n_reps - F)
        want = (s * keep[:, None, None]).sum(0) / keep.sum()
        got = ps_trimmed_pool(rep_vals, jnp.ones(n_reps, bool), F)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestHPSFusion:
    def test_f0_is_doubly_stochastic(self):
        """Algorithm 1's fusion matrix preserves total mass and leaves
        non-representatives untouched."""
        topo, w = _setup()
        z = jnp.asarray(w)
        m = jnp.asarray(np.random.default_rng(0).uniform(
            0.5, 2.0, topo.N).astype(np.float32))
        rep = jnp.asarray(topo.rep_mask())
        z_f, m_f = hps_fusion(z, m, rep, topo.M)
        np.testing.assert_allclose(float(m_f.sum()), float(m.sum()),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(z_f.sum(0)),
                                   np.asarray(z.sum(0)), rtol=1e-5)
        nr = ~np.asarray(rep)
        np.testing.assert_array_equal(np.asarray(z_f)[nr],
                                      np.asarray(z)[nr])

    def test_f_positive_is_trimmed_rep_mean(self):
        """F>0 swaps the plain average for the trimmed rep-pool mean: the
        rep update must equal 0.5 z_rep + 0.5 * trimmed_mean(pool)."""
        topo, w = _setup(sizes=(3, 3, 3, 3, 3), seed=0, d=1)
        z = jnp.asarray(w)
        m = jnp.ones(topo.N, jnp.float32)
        rep = jnp.asarray(topo.rep_mask())
        z_f, m_f = hps_fusion(z, m, rep, topo.M, F=1)
        reps = np.nonzero(np.asarray(rep))[0]
        pool = np.sort(np.asarray(w)[reps, 0])
        tmean = pool[1:-1].mean()
        for r in reps:
            np.testing.assert_allclose(
                float(z_f[r, 0]), 0.5 * w[r, 0] + 0.5 * tmean, rtol=1e-5
            )
        # trimming the (identical) masses keeps them at 1
        np.testing.assert_allclose(np.asarray(m_f), 1.0, rtol=1e-6)

    def test_trimmed_engine_still_reaches_consensus(self):
        """The resilient rule trades the exact average for outlier
        rejection: agents must still AGREE (inter-agent spread -> 0) even
        though the common value may be biased away from mean(w)."""
        topo, w = _setup(sizes=(6, 6, 6), seed=0)
        cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.1)
        res = run_hps(w, cfg, 2000, seed=1, store="gap", F=1)
        gap = np.asarray(res.gap)
        assert np.isfinite(gap).all()
        assert gap[-1] < 0.25 * gap[0]       # error vs mean(w) still shrinks
        final = np.asarray(res.ratio)        # (N, d) — and agents agree,
        spread = (final.max(axis=0) - final.min(axis=0)).max()
        assert spread < 0.005, spread        # though biased off mean(w)


# ---------------------------------------------------------------------------
# Engine equivalence: sparse oracle (bit-exact) + dense reference
# ---------------------------------------------------------------------------

def _sparse_oracle(w, cfg, T, seed):
    """The pre-refactor scan structure on the sparse core: per-step share
    recomputation (no invariant hoisting) and in-body fusion gating —
    modulo only the satellite-mandated PRNG-domain fix. The per-scenario
    scalars (drop, B, Γ, M) and the rep mask ride as traced jit ARGUMENTS,
    matching the engine's HPSRuntime calling convention: baking them in as
    Python constants lets XLA constant-fold the mask comparison and refuse
    different FMA contractions, which perturbs the trajectory at 1 ulp —
    with the argument structure aligned the fused engine must reproduce
    this oracle bit for bit."""
    el = cfg.edge_index()
    src, dst = jnp.asarray(el.src), jnp.asarray(el.dst)
    valid = jnp.asarray(el.valid)

    def run(key, w_in, drop, B, gamma, M, rep_mask):
        state0 = init_sparse_state(w_in, el.E)

        def body(state, t):
            mask = step_edge_mask(
                key, t, el.E, drop, B, fold_t=hps_stream_fold(t)
            )
            st = sparse_pushsum_step(state, mask, src, dst, valid, "xla")
            z_f, m_f = hps_fusion(st.z, st.m, rep_mask, M)
            do_fusion = (t + 1) % gamma == 0
            st = st._replace(
                z=jnp.where(do_fusion, z_f, st.z),
                m=jnp.where(do_fusion, m_f, st.m),
            )
            return st, sparse_ratios(st)

        _, traj = jax.lax.scan(body, state0, jnp.arange(T, dtype=jnp.int32))
        return traj

    return jax.jit(run)(
        jax.random.PRNGKey(seed), jnp.asarray(w),
        jnp.float32(cfg.drop_prob), jnp.int32(cfg.B),
        jnp.int32(cfg.gamma_period), jnp.int32(cfg.topo.M),
        cfg.rep_mask(),
    )


class TestEngineEquivalence:
    """Acceptance: fused engine == pre-refactor sparse oracle, bit for bit."""

    @pytest.mark.parametrize("drop,gamma,B", [(0.0, 4, 1), (0.3, 8, 2),
                                              (0.6, 3, 4)])
    def test_fused_engine_matches_sparse_oracle(self, drop, gamma, B):
        topo, w = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=gamma, B=B, drop_prob=drop)
        traj = _sparse_oracle(w, cfg, T=40, seed=3)
        res = run_hps(w, cfg, T=40, seed=3, backend="xla")
        np.testing.assert_array_equal(np.asarray(res.ratio),
                                      np.asarray(traj))

    def test_dense_reference_matches_runtime_core(self):
        """The kept (N, N) dense reference consumes the IDENTICAL in-scan
        (E,) mask stream at matched seeds; trajectories agree to fp
        reduction order — the dense axis-0 delivery reduce and the sparse
        segment-sum associate differently, so this is the established
        dense<->sparse tolerance (test_pushsum_sparse), not bit-identity;
        the bit-exact contract is the sparse-oracle test above."""
        topo, w = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.2)
        _, traj_d = run_hps_dense(w, cfg, T=120, seed=3)
        res = run_hps(w, cfg, T=120, seed=3, backend="xla")
        np.testing.assert_allclose(np.asarray(res.ratio),
                                   np.asarray(traj_d),
                                   rtol=1e-4, atol=1e-5)

    def test_pallas_backend_matches_xla(self):
        """interpret-mode fused consensus kernel == XLA lowering over a
        full run (same traced program that compiles on TPU)."""
        topo, w = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.3)
        x = run_hps(w, cfg, T=50, seed=0, backend="xla")
        p = run_hps(w, cfg, T=50, seed=0, backend="pallas")
        np.testing.assert_allclose(np.asarray(p.ratio),
                                   np.asarray(x.ratio),
                                   rtol=1e-4, atol=1e-5)

    def test_dense_free_runtime_matches_config_path(self):
        """hier_edge_list + run_hps_runtime (the N ~ 1e4 path that never
        builds an (N, N) adjacency) == the HPSConfig path, bit for bit."""
        topo, w = _setup(sizes=(6, 6, 6))
        el, rep_mask = hier_edge_list([6, 6, 6], topology="complete")
        rt = hps_runtime_from_edge_list(el, rep_mask, drop_prob=0.3,
                                        gamma_period=8, B=2)
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.3)
        a = run_hps_runtime(w, rt, T=40, seed=5)
        b = run_hps(w, cfg, T=40, seed=5)
        np.testing.assert_array_equal(np.asarray(a.ratio),
                                      np.asarray(b.ratio))

    def test_store_shapes_and_consistency(self):
        topo, w = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.3)
        N, d, T = topo.N, w.shape[1], 60
        traj = run_hps(w, cfg, T=T, seed=0)
        gapr = run_hps(w, cfg, T=T, seed=0, store="gap")
        fin = run_hps(w, cfg, T=T, seed=0, store="final")
        assert traj.ratio.shape == (T, N, d) and traj.gap.shape == (T,)
        assert gapr.ratio.shape == (N, d) and gapr.gap.shape == (T,)
        assert fin.ratio.shape == (N, d) and fin.gap.shape == ()
        r = np.asarray(traj.ratio)
        np.testing.assert_array_equal(np.asarray(gapr.ratio), r[-1])
        np.testing.assert_array_equal(np.asarray(fin.ratio), r[-1])
        # the three stores are distinct XLA programs; the ratio division
        # fuses into the error reduction differently, so the gap curves
        # agree to 1 ulp, not bitwise
        np.testing.assert_allclose(np.asarray(gapr.gap),
                                   np.asarray(traj.gap),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(float(fin.gap), float(traj.gap[-1]),
                                   rtol=1e-6, atol=1e-7)

    def test_invalid_store_rejected(self):
        topo, w = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.3)
        with pytest.raises(ValueError, match="store"):
            run_hps(w, cfg, T=5, store="everything")
        assert HPS_STORES == ("trajectory", "gap", "final")


# ---------------------------------------------------------------------------
# No dense / trajectory intermediates in the sparse path
# ---------------------------------------------------------------------------

# The jaxpr walker these tests introduced now lives in repro.statics.walk
# (PR 6); imported under the historical names so the assertions below stay
# bit-for-bit what they were when the helpers were local.
from repro.statics.walk import collect_avals as _collect_avals  # noqa: E402
from repro.statics.walk import subjaxprs as _subjaxprs  # noqa: E402,F401


class TestNoDenseIntermediates:
    """Acceptance: store="gap"|"final" holds no (N, N) or (T, N, d) value."""

    T = 37   # distinct from N=15, d=2, E=62 so the walker cannot confuse axes

    def _shapes(self, store):
        from repro.core.hps import _hps_scan_core

        topo, w = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.2)
        rt = make_hps_runtime(cfg)

        def run(key):
            return _hps_scan_core(
                key, rt, jnp.asarray(w),
                T=self.T, store=store, backend="xla",
            )

        shapes = _collect_avals(
            jax.make_jaxpr(run)(jax.random.PRNGKey(0)).jaxpr, []
        )
        assert shapes, "jaxpr walker found no values"
        return shapes, topo.N

    @pytest.mark.parametrize("store", ["gap", "final"])
    def test_no_dense_or_trajectory_value(self, store):
        shapes, N = self._shapes(store)
        dense = [s for s in shapes
                 if len(s) >= 2 and s[0] == N and s[1] == N]
        assert not dense, f"(N, N, ...) intermediates: {dense}"
        traj = [s for s in shapes if len(s) >= 2 and s[0] == self.T]
        assert not traj, f"(T, ...) intermediates: {traj}"
        if store == "gap":
            assert (self.T,) in shapes      # the in-scan-reduced curve

    def test_detector_flags_trajectory_store(self):
        """Sanity: the same walker does find the (T, N, d) history in the
        trajectory store, so the assertions above have teeth."""
        shapes, N = self._shapes("trajectory")
        assert (self.T, N, 2) in shapes


# ---------------------------------------------------------------------------
# PRNG stream domains
# ---------------------------------------------------------------------------

class TestPRNGStreams:
    def test_hps_domain_disjoint_from_social_and_byzantine(self):
        """The HPS link-mask stream folds ``~t`` — the top of the uint32
        domain — so it can never collide with the social engine's
        ``2t + s`` or the Byzantine engine's ``3t + s`` streams at any
        realistic horizon, even with every base key rooted at one seed."""
        T = 20000
        t = np.arange(T, dtype=np.int32)
        hps = set(np.asarray(hps_stream_fold(t)).astype(np.uint32).tolist())
        social = set()
        for s in (STREAM_LINK, STREAM_SIGNAL):
            social |= set(np.asarray(
                social_stream_fold(t, s)).astype(np.uint32).tolist())
        byz = set()
        for s in range(BYZ_STREAMS):
            byz |= set(np.asarray(
                stream_fold(t, s)).astype(np.uint32).tolist())
        assert len(hps) == T                 # injective over the horizon
        assert not (hps & social)
        assert not (hps & byz)
        assert N_SOCIAL_STREAMS == 2 and BYZ_STREAMS == 3

    def test_seed_scheme_would_have_aliased(self):
        """The bug being regressed: the seed-era ``run_hps`` derived its
        schedule from ``seed`` alone (plain ``t`` domain), so at equal
        seeds the HPS mask key at t = 2k EQUALED the social link-mask key
        at iteration k. The dedicated domain breaks the collision."""
        k = jax.random.PRNGKey(7)
        t = 6
        old_hps = jax.random.fold_in(k, t)    # seed scheme: fold plain t
        social = jax.random.fold_in(
            k, social_stream_fold(t // 2, STREAM_LINK)
        )
        np.testing.assert_array_equal(np.asarray(old_hps),
                                      np.asarray(social))   # the alias
        fixed = jax.random.fold_in(k, hps_stream_fold(t))
        assert (np.asarray(fixed) != np.asarray(social)).any()

    def test_seed_drives_masks(self):
        topo, w = _setup()
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.5)
        a = run_hps(w, cfg, T=60, seed=0, store="gap")
        b = run_hps(w, cfg, T=60, seed=1, store="gap")
        assert (np.asarray(a.gap) != np.asarray(b.gap)).any()
        assert np.isfinite(np.asarray(a.gap)).all()


# ---------------------------------------------------------------------------
# Dense-free hierarchical edge-list builder
# ---------------------------------------------------------------------------

class TestHierEdgeList:
    def test_complete_matches_make_hierarchy(self):
        topo = make_hierarchy([4, 5, 3], topology="complete")
        el, rep = hier_edge_list([4, 5, 3], topology="complete")
        np.testing.assert_array_equal(el.to_dense(), topo.adj)
        np.testing.assert_array_equal(rep, topo.rep_mask())

    @pytest.mark.parametrize("topology", ["ring", "complete", "ring+"])
    def test_blocks_are_strongly_connected_and_block_diagonal(self, topology):
        sizes = [6, 5, 7]
        el, rep = hier_edge_list(sizes, topology=topology, seed=3)
        adj = el.to_dense()
        assert not adj.diagonal().any()
        off = 0
        for sz in sizes:
            block = adj[off:off + sz, off:off + sz]
            assert is_strongly_connected(block)
            # no cross-network edges
            assert adj[off:off + sz].sum() == block.sum()
            off += sz
        assert rep.sum() == len(sizes)
        # dst-sorted layout (the Pallas consensus contract)
        assert (np.diff(el.dst) >= 0).all()

    def test_rep_choice_random_stays_in_block(self):
        sizes = [5, 5, 5]
        _, rep = hier_edge_list(sizes, topology="ring", seed=7,
                                rep_choice="random")
        reps = np.nonzero(rep)[0]
        assert len(reps) == 3
        assert all(5 * i <= r < 5 * (i + 1) for i, r in enumerate(reps))

    def test_unknown_topology_rejected(self):
        with pytest.raises(ValueError, match="topology"):
            hier_edge_list([4, 4], topology="torus")


# ---------------------------------------------------------------------------
# Theorem 1: empirical gap curves under the analytical envelope
# ---------------------------------------------------------------------------

class TestTheorem1Bound:
    def test_gap_curve_dominated_by_envelope_across_grid(self):
        """Property-style acceptance: over a (Γ, drop, B) grid, every
        scenario's in-scan ``store="gap"`` curve must sit below the
        Theorem-1 RHS at every iteration (the bound is loose by Remark 3,
        so domination is strict in practice)."""
        topo = make_hierarchy([4, 4], topology="complete", seed=5)
        w = np.random.default_rng(3).normal(size=(topo.N, 2)).astype(np.float32)
        cfgs = [
            HPSConfig(topo=topo, gamma_period=g, B=b, drop_prob=dp)
            for g in (2, 4) for dp in (0.0, 0.3) for b in (1, 2)
        ]
        res = run_hps_grid(w, cfgs, T=300, seeds=[0, 1], store="gap")
        assert res.K == len(cfgs) * 2
        for k in range(res.K):
            cfg = cfgs[int(res.cfg[k])]
            gap = np.asarray(res.gap[k])
            bound = np.asarray([theorem1_bound(cfg, w, t)
                                for t in range(300)])
            assert (gap <= bound + 1e-6).all(), (
                f"cfg={cfg.gamma_period, cfg.B, cfg.drop_prob} "
                f"seed={int(res.seed[k])}: worst excess "
                f"{(gap - bound).max():.2e}"
            )


# ---------------------------------------------------------------------------
# Batched (topology x M x Γ x drop) x seed sweeps
# ---------------------------------------------------------------------------

def _grid_fixture():
    """4 hierarchies over N=18 with DIFFERENT sub-network counts
    (M in {3, 2, 6}) x 2 Γ x 2 drop = 16 configs; x 3 seeds = 48."""
    topos = [
        make_hierarchy([6, 6, 6], topology="complete", seed=0),
        make_hierarchy([6, 6, 6], topology="ring+", extra_edge_prob=0.8,
                       seed=1),
        make_hierarchy([9, 9], topology="complete", seed=2),
        make_hierarchy([3] * 6, topology="complete", seed=3),
    ]
    cfgs = [
        HPSConfig(topo=t, gamma_period=g, B=2, drop_prob=d)
        for t in topos for g in (4, 8) for d in (0.0, 0.3)
    ]
    w = np.random.default_rng(0).normal(size=(18, 3)).astype(np.float32)
    return w, cfgs


class TestHPSSweep:
    def test_topo_M_gamma_drop_seed_grid_single_trace(self):
        """Acceptance: 4 topologies (M in {3, 2, 6}) x 2 Γ x 2 drop x 3
        seeds = 48 scenarios as ONE compiled program — one jit cache entry,
        no retrace on a second seed batch, M traced per scenario."""
        from repro.core.sweeps import _hps_sweep_fn, cache_registry

        w, cfgs = _grid_fixture()
        res = run_hps_grid(w, cfgs, T=25, seeds=list(range(3)))
        assert res.K == 48
        assert res.gap.shape == (48, 25)
        assert res.ratio.shape == (48, 18, 3)
        assert set(np.asarray(res.M).tolist()) == {2, 3, 6}
        fn = _hps_sweep_fn(None, "data", T=25, store="gap", backend="xla")
        assert fn._cache_size() == 1
        res2 = run_hps_grid(w, cfgs, T=25, seeds=list(range(3, 6)))
        assert fn._cache_size() == 1         # same shapes -> no retrace
        assert res2.K == 48
        info = cache_registry()["hps.compiled"].cache_info()
        assert info.currsize <= info.maxsize

    def test_uniform_E_grid_matches_single_runs_bit_identical(self):
        """Traced (drop, Γ, M) on the vmap axis must reproduce each
        config's single run bit for bit (single topology -> no edge
        padding -> identical link-mask streams)."""
        topo, w = _setup(sizes=(6, 6, 6))
        cfgs = [HPSConfig(topo=topo, gamma_period=g, B=2, drop_prob=d)
                for d in (0.0, 0.4, 0.8) for g in (3, 8)]
        res = run_hps_grid(w, cfgs, T=30, seeds=[0, 3])
        for k in range(res.K):
            ci, sd = int(res.cfg[k]), int(res.seed[k])
            single = run_hps(w, cfgs[ci], T=30, seed=sd, backend="xla",
                             store="gap")
            np.testing.assert_array_equal(np.asarray(res.gap[k]),
                                          np.asarray(single.gap))
            np.testing.assert_array_equal(np.asarray(res.ratio[k]),
                                          np.asarray(single.ratio))
            assert np.float32(res.drop_prob[k]) == np.float32(
                cfgs[ci].drop_prob)
            assert int(res.gamma[k]) == cfgs[ci].gamma_period
            assert int(res.M[k]) == cfgs[ci].topo.M

    def test_mixed_E_grid_matches_padded_runtimes(self):
        """Topology draws with different edge counts pad to a common E —
        which re-indexes the (E,) link-mask draw, so the contract is
        bit-identity against the single run on the SAME padded runtime."""
        w, cfgs = _grid_fixture()
        e_all = {int(np.count_nonzero(c.topo.adj)) for c in cfgs}
        assert len(e_all) > 1, "fixture must exercise heterogeneous E"
        e_max = max(e_all)
        res = run_hps_grid(w, cfgs, T=25, seeds=[1])
        for k in range(0, res.K, 5):
            ci, sd = int(res.cfg[k]), int(res.seed[k])
            rt = make_hps_runtime(cfgs[ci], e_max=e_max)
            single = run_hps_runtime(w, rt, T=25, seed=sd, backend="xla",
                                     store="gap")
            np.testing.assert_array_equal(np.asarray(res.gap[k]),
                                          np.asarray(single.gap))
            np.testing.assert_array_equal(np.asarray(res.ratio[k]),
                                          np.asarray(single.ratio))

    def test_sweep_cross_product_coordinates(self):
        topo, w = _setup(sizes=(6, 6, 6))
        cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.0)
        res = run_hps_sweep(w, cfg, T=10, drop_probs=[0.0, 0.5],
                            gammas=[2, 8], seeds=[0, 1, 2])
        assert res.K == 12
        coords = {(float(res.drop_prob[k]), int(res.gamma[k]),
                   int(res.seed[k])) for k in range(res.K)}
        assert coords == {(d, g, s) for d in (0.0, 0.5) for g in (2, 8)
                          for s in (0, 1, 2)}

    def test_trajectory_store_sweep(self):
        topo, w = _setup(sizes=(6, 6, 6))
        cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.3)
        res = run_hps_sweep(w, cfg, T=15, seeds=[0, 1], store="trajectory")
        assert res.ratio.shape == (2, 15, 18, 2)
        single = run_hps(w, cfg, T=15, seed=1)
        np.testing.assert_array_equal(np.asarray(res.ratio[1]),
                                      np.asarray(single.ratio))

    def test_incompatible_configs_rejected(self):
        w, cfgs = _grid_fixture()
        other = make_hierarchy([5, 5, 5], topology="complete")
        bad = HPSConfig(topo=other, gamma_period=4, B=2, drop_prob=0.0)
        with pytest.raises(ValueError, match="share"):
            run_hps_grid(w, [cfgs[0], bad], T=5, seeds=[0])
        with pytest.raises(ValueError, match="store"):
            run_hps_grid(w, [cfgs[0]], T=5, seeds=[0], store="bogus")
        with pytest.raises(ValueError, match="at least one"):
            run_hps_grid(w, [], T=5, seeds=[0])

    def test_compiled_cache_is_lru_bounded(self):
        from repro.core.sweeps import cache_registry

        reg = cache_registry()
        compiled = reg["hps.compiled"].cache_info()
        runtime = reg["hps.runtime"].cache_info()
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
            from repro.core.sweeps import run_hps_sweep

            topo = make_hierarchy([6, 6, 6], topology="complete", seed=0)
            w = np.random.default_rng(0).normal(size=(18, 3)).astype("float32")
            cfg = HPSConfig(topo=topo, gamma_period=8, B=2, drop_prob=0.0)
            kw = dict(drop_probs=[0.0, 0.4, 0.8], gammas=[4, 16],
                      seeds=[0, 1])
            r1 = run_hps_sweep(w, cfg, T=20, **kw)
            mesh = jax.make_mesh((4,), ("data",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            r2 = run_hps_sweep(w, cfg, T=20, mesh=mesh, **kw)
            same = bool((np.asarray(r1.gap) == np.asarray(r2.gap)).all())
            err = float(np.abs(np.asarray(r1.ratio)
                               - np.asarray(r2.ratio)).max())
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
