"""Sparse neighbor-list Byzantine core + fused trim-gather kernel.

The contract under test: the Pallas extraction kernel (interpret mode on CPU
— the identical traced program that compiles on TPU) matches the sort-based
XLA oracle, which itself matches the dense ``trimmed_neighbor_mean``
reference per receiver; full Algorithm 2 trajectories agree between the
dense broadcast core and the sparse neighbor-list core for F in {0, 1, 2},
pairwise and one-vs-rest, sorted/shuffled/padded neighbor layouts; the
sparse path never materializes an (N, N, ...) intermediate (jaxpr
inspection); the three per-iteration PRNG streams have disjoint fold-in
domains; a (topology x F x seed) grid runs as ONE compiled program; and the
compiled-scan caches are LRU-bounded.
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

from repro.core import attacks
from repro.core.byzantine import (
    ByzantineConfig,
    N_STREAMS,
    STREAM_FUSION,
    STREAM_GOSSIP,
    STREAM_SIGNAL,
    make_byzantine_runtime,
    make_byzantine_scan,
    run_byzantine_learning,
    run_byzantine_learning_ovr,
    stream_fold,
    trimmed_neighbor_mean,
)
from repro.core.graphs import (
    make_hierarchy,
    neighbor_lists,
    random_strongly_connected,
    stack_neighbor_lists,
)
from repro.core.signals import make_confused_model
from repro.kernels.byz_trim import resolve_backend, trim_gather, trim_gather_ref
from repro.kernels.byz_trim.byz_trim import trim_gather_pallas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _random_problem(n, p_edge, P, seed, deg_max=None, shuffle=None):
    rng = np.random.default_rng(seed)
    adj = random_strongly_connected(n, p_edge, rng)
    nl = neighbor_lists(adj, deg_max=deg_max, shuffle_seed=shuffle)
    r = jnp.asarray(rng.normal(size=(n, P)).astype(np.float32))
    bmsg = jnp.asarray(
        (1e3 * rng.normal(size=(n, nl.deg_max, P))).astype(np.float32)
    )
    byz_nbr = jnp.asarray(rng.random((n, nl.deg_max)) < 0.2) & jnp.asarray(
        nl.valid
    )
    return adj, nl, r, bmsg, byz_nbr


class TestNeighborLists:
    def test_slots_match_adjacency(self):
        rng = np.random.default_rng(0)
        adj = random_strongly_connected(13, 0.3, rng)
        nl = neighbor_lists(adj)
        assert nl.deg_max == adj.sum(axis=0).max()
        np.testing.assert_array_equal(nl.in_degree(), adj.sum(axis=0))
        for j in range(13):
            senders = sorted(nl.idx[j, nl.valid[j]].tolist())
            assert senders == sorted(np.nonzero(adj[:, j])[0].tolist())

    def test_deg_max_padding_and_bounds(self):
        adj = random_strongly_connected(8, 0.4, np.random.default_rng(1))
        nl = neighbor_lists(adj, deg_max=11)
        assert nl.deg_max == 11
        np.testing.assert_array_equal(nl.in_degree(), adj.sum(axis=0))
        with pytest.raises(ValueError):
            neighbor_lists(adj, deg_max=1)

    def test_stack_pads_to_widest(self):
        rng = np.random.default_rng(2)
        a1 = random_strongly_connected(9, 0.1, rng)
        a2 = random_strongly_connected(9, 0.6, rng)
        nls = [neighbor_lists(a) for a in (a1, a2)]
        st = stack_neighbor_lists(nls)
        assert st.is_batched and st.deg_max == max(n.deg_max for n in nls)
        np.testing.assert_array_equal(st.in_degree()[0], a1.sum(axis=0))
        np.testing.assert_array_equal(st.in_degree()[1], a2.sum(axis=0))

    def test_topo_accepted(self):
        topo = make_hierarchy([4, 4], topology="complete")
        nl = neighbor_lists(topo)
        np.testing.assert_array_equal(nl.in_degree(), topo.adj.sum(axis=0))


class TestTrimGatherKernel:
    @pytest.mark.parametrize("F,block_n,seed", [(0, 8, 0), (1, 16, 1),
                                                (2, 8, 2), (2, 1024, 3)])
    def test_pallas_matches_xla_ref(self, F, block_n, seed):
        """Extraction kernel == sort oracle, including when N is far from a
        block multiple (padding receiver rows must stay inert)."""
        _, nl, r, bmsg, byz_nbr = _random_problem(29, 0.3, 5, seed)
        args = (r, jnp.asarray(nl.idx), jnp.asarray(nl.valid), bmsg, byz_nbr)
        ts_ref, k_ref = trim_gather_ref(*args, F)
        ts_p, k_p = trim_gather_pallas(*args, F, block_n=block_n,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(ts_p), np.asarray(ts_ref),
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(k_p), np.asarray(k_ref))

    @pytest.mark.parametrize("shuffle", [None, 7])
    @pytest.mark.parametrize("F", [0, 1, 2])
    def test_ref_matches_dense_oracle(self, F, shuffle):
        """Sorted and shuffled slot layouts, padded degree: the neighbor-list
        trim equals the dense (N, N) broadcast + sort per receiver."""
        adj, nl, r, bmsg, byz_nbr = _random_problem(
            17, 0.4, 4, seed=F + 10, deg_max=15, shuffle=shuffle
        )
        n = 17
        # scatter the slot values into the dense (sender, receiver) layout
        vals = np.zeros((n, n, 4), np.float32)
        vals[:] = np.asarray(r)[:, None, :]          # honest: sender's state
        bm = np.asarray(bmsg)
        bn = np.asarray(byz_nbr)
        for j in range(n):
            for k in range(nl.deg_max):
                if nl.valid[j, k] and bn[j, k]:
                    vals[nl.idx[j, k], j] = bm[j, k]
        ts_d, k_d = trimmed_neighbor_mean(
            jnp.asarray(vals)[:, :, :, None], jnp.asarray(adj), F
        )
        ts_s, k_s = trim_gather_ref(
            r, jnp.asarray(nl.idx), jnp.asarray(nl.valid), bmsg, byz_nbr, F
        )
        np.testing.assert_allclose(np.asarray(ts_s),
                                   np.asarray(ts_d)[..., 0],
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(np.asarray(k_s), np.asarray(k_d))

    def test_under_trimmed_degree_keeps_nothing(self):
        """deg <= 2F receivers keep zero values — same as the dense rank
        window [F, deg - F) being empty."""
        idx = jnp.asarray([[1, 2, 0], [2, 0, 0], [0, 0, 0]], jnp.int32)
        valid = jnp.asarray([[True, True, True],
                             [True, False, False],
                             [False, False, False]])
        r = jnp.asarray(np.arange(6, dtype=np.float32).reshape(3, 2))
        bmsg = jnp.zeros((3, 3, 2), jnp.float32)
        bnbr = jnp.zeros((3, 3), bool)
        for backend, kw in (("xla", {}), ("pallas", {"interpret": True})):
            ts, kept = trim_gather(r, idx, valid, bmsg, bnbr, 2,
                                   backend=backend, **kw)
            np.testing.assert_array_equal(np.asarray(kept), [0.0, 0.0, 0.0])
            np.testing.assert_array_equal(np.asarray(ts), np.zeros((3, 2)))

    def test_dynamic_F_traced_matches_static(self):
        """The sort-based lowering accepts a traced F — what batched
        (topology, F) grids vmap over."""
        _, nl, r, bmsg, byz_nbr = _random_problem(15, 0.4, 3, seed=5)
        args = (r, jnp.asarray(nl.idx), jnp.asarray(nl.valid), bmsg, byz_nbr)
        dyn = jax.jit(lambda f: trim_gather_ref(*args, f))
        for F in (0, 1, 2):
            ts_s, k_s = trim_gather_ref(*args, F)
            ts_d, k_d = dyn(jnp.asarray(F, jnp.int32))
            np.testing.assert_allclose(np.asarray(ts_d), np.asarray(ts_s))
            np.testing.assert_array_equal(np.asarray(k_d), np.asarray(k_s))

    def test_pallas_rejects_traced_F(self):
        _, nl, r, bmsg, byz_nbr = _random_problem(9, 0.4, 2, seed=6)
        with pytest.raises(ValueError, match="static int F"):
            trim_gather(r, jnp.asarray(nl.idx), jnp.asarray(nl.valid),
                        bmsg, byz_nbr, jnp.asarray(1), backend="pallas")

    def test_auto_backend_is_xla_off_tpu(self):
        expected = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert resolve_backend("auto") == expected


def _byz_setup(seed=0, M_nets=4, n=7, m=3):
    topo = make_hierarchy([n] * M_nets, topology="complete", seed=seed)
    model = make_confused_model(N=topo.N, m=m, truth=0, confusion=0.0,
                                seed=seed)
    return topo, model


_EQUIV_ATTACKS = ["large_value", "sign_flip", "extreme_pull",
                  "truth_suppression"]


def _attack(name):
    return (attacks.ATTACKS[name](0) if name == "truth_suppression"
            else attacks.ATTACKS[name]())


class TestByzantineCoreEquivalence:
    """Acceptance: sparse trajectories == dense oracle within atol 1e-5."""

    @pytest.mark.parametrize("F,byz", [(0, ()), (1, (2,)), (2, (2, 9))])
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_pairwise_trajectory_equivalence(self, F, byz, backend):
        topo, model = _byz_setup()
        cfg = ByzantineConfig(topo=topo, F=F, byz=byz, gamma_period=7,
                              attack=attacks.large_value())
        dense = run_byzantine_learning(model, cfg, T=50, seed=0, core="dense")
        sparse = run_byzantine_learning(model, cfg, T=50, seed=0,
                                        core="sparse", backend=backend)
        np.testing.assert_allclose(np.asarray(sparse.r), np.asarray(dense.r),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(sparse.decisions),
                                      np.asarray(dense.decisions))

    @pytest.mark.parametrize("attack_name", _EQUIV_ATTACKS)
    def test_attack_equivalence(self, attack_name):
        """Every deterministic attack's sparse form reproduces its dense
        point-to-point tensor exactly (random_noise draws per-slot instead
        of per-pair, so only its distribution matches)."""
        topo, model = _byz_setup()
        cfg = ByzantineConfig(topo=topo, F=2, byz=(2, 9), gamma_period=5,
                              attack=_attack(attack_name))
        dense = run_byzantine_learning(model, cfg, T=40, seed=1, core="dense")
        sparse = run_byzantine_learning(model, cfg, T=40, seed=1,
                                        core="sparse", backend="xla")
        np.testing.assert_allclose(np.asarray(sparse.r), np.asarray(dense.r),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("F,byz", [(0, ()), (1, (2,)), (2, (2, 9))])
    def test_ovr_trajectory_equivalence(self, F, byz):
        topo, model = _byz_setup(M_nets=5, m=4)
        cfg = ByzantineConfig(topo=topo, F=F, byz=byz, gamma_period=6,
                              attack=attacks.sign_flip())
        dense = run_byzantine_learning_ovr(model, cfg, T=40, seed=0,
                                           core="dense")
        sparse = run_byzantine_learning_ovr(model, cfg, T=40, seed=0,
                                            core="sparse")
        assert sparse.r.shape == dense.r.shape == (40, topo.N, 4, 1)
        np.testing.assert_allclose(np.asarray(sparse.r), np.asarray(dense.r),
                                   rtol=1e-5, atol=1e-5)

    def test_padded_degree_scan_equivalence(self):
        """A runtime padded past the true max in-degree changes nothing."""
        from repro.core.byzantine import _scan_core, _sparse_gossip
        import functools

        topo, model = _byz_setup()
        cfg = ByzantineConfig(topo=topo, F=2, byz=(2, 9), gamma_period=7,
                              attack=attacks.large_value())
        base = run_byzantine_learning(model, cfg, T=30, seed=0)
        rt, extra_reps, n_reps, _ = make_byzantine_runtime(
            model, cfg, deg_max=11
        )
        padded = _scan_core(
            jax.random.PRNGKey(0), rt,
            gossip=functools.partial(_sparse_gossip, attack=cfg.attack,
                                     mode="pairwise", backend="xla"),
            log_tables=model.log_tables().astype(jnp.float32),
            truth_probs=model.tables[:, model.truth, :].astype(jnp.float32),
            T=30, mode="pairwise", attack=cfg.attack, store="trajectory",
            static_F=cfg.F, extra_reps=extra_reps, n_reps=n_reps,
        )
        np.testing.assert_allclose(np.asarray(padded.r), np.asarray(base.r),
                                   rtol=1e-5, atol=1e-5)

    def test_dense_fallback_attack_without_nbr_messages(self):
        """A custom attack lacking the sparse interface still runs on the
        sparse core (via the dense-gather compatibility path) and matches
        the dense oracle."""
        base = attacks.extreme_pull()
        legacy = attacks.Attack("legacy", base.messages, base.ps_reply)
        topo, model = _byz_setup()
        cfg = ByzantineConfig(topo=topo, F=1, byz=(2,), gamma_period=5,
                              attack=legacy)
        dense = run_byzantine_learning(model, cfg, T=30, seed=0, core="dense")
        sparse = run_byzantine_learning(model, cfg, T=30, seed=0,
                                        core="sparse")
        np.testing.assert_allclose(np.asarray(sparse.r), np.asarray(dense.r),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.slow
    def test_equivalence_N4096(self):
        """Scale check: xla and pallas sparse paths agree at N=4096."""
        topo = make_hierarchy([8] * 512, topology="complete", seed=0)
        model = make_confused_model(N=4096, m=3, truth=0, confusion=0.0,
                                    seed=1)
        cfg = ByzantineConfig(topo=topo, F=2, byz=(2, 9), gamma_period=4,
                              attack=attacks.large_value())
        x = run_byzantine_learning(model, cfg, T=3, seed=0, backend="xla")
        p = run_byzantine_learning(model, cfg, T=3, seed=0, backend="pallas")
        np.testing.assert_allclose(np.asarray(p.r), np.asarray(x.r),
                                   rtol=1e-5, atol=1e-5)


# The jaxpr walker these tests introduced now lives in repro.statics.walk
# (PR 6); imported under the historical names so the assertions below stay
# bit-for-bit what they were when the helpers were local.
from repro.statics.walk import collect_avals as _collect_avals  # noqa: E402
from repro.statics.walk import subjaxprs as _subjaxprs  # noqa: E402,F401


class TestNoDenseIntermediate:
    """Acceptance: the sparse path's jaxpr holds no (N, N, ...) value."""

    def _shapes(self, core):
        topo = make_hierarchy([8] * 8, topology="complete", seed=0)  # N=64
        model = make_confused_model(N=64, m=3, truth=0, confusion=0.0, seed=1)
        cfg = ByzantineConfig(topo=topo, F=2, byz=(2, 9), gamma_period=4,
                              attack=attacks.large_value())
        run = make_byzantine_scan(model, cfg, T=5, core=core,
                                  backend="xla", store="decisions")
        jaxpr = jax.make_jaxpr(run)(jax.random.PRNGKey(0)).jaxpr
        return _collect_avals(jaxpr, []), 64

    def test_sparse_has_no_NN_value(self):
        shapes, n = self._shapes("sparse")
        assert shapes, "jaxpr walker found no values"
        dense_like = [s for s in shapes
                      if len(s) >= 2 and s[0] == n and s[1] == n]
        assert not dense_like, f"(N, N, ...) intermediates: {dense_like}"
        m = 3
        assert max(int(np.prod(s)) for s in shapes) < n * n * m * m

    def test_detector_flags_dense_core(self):
        """Sanity: the same walker does find the (N, N, m, m) broadcast in
        the dense oracle, so the sparse assertion has teeth."""
        shapes, n = self._shapes("dense")
        assert any(len(s) >= 2 and s[0] == n and s[1] == n for s in shapes)


class TestPRNGStreams:
    def test_streams_disjoint_over_horizon(self):
        """Regression for the seed's t / 2t+1 / 2t+2 scheme, where the
        signal key at t=3 equaled the gossip key at t=1: the three fold-in
        domains must never intersect over any horizon."""
        T = 20000
        t = np.arange(T, dtype=np.uint64)
        folds = {
            s: set(np.asarray(stream_fold(t, s)).tolist())
            for s in (STREAM_SIGNAL, STREAM_GOSSIP, STREAM_FUSION)
        }
        for a in folds:
            for b in folds:
                if a != b:
                    assert not (folds[a] & folds[b])
        assert N_STREAMS == 3
        # injectivity over (t, stream): total count is preserved
        assert len(set().union(*folds.values())) == 3 * T

    def test_seed_scheme_would_have_collided(self):
        """The bug being regressed: fold-ins t, 2t+1, 2t+2 alias."""
        t = np.arange(100)
        assert set(t) & set(2 * t + 1)        # signal hits gossip keys
        assert set(t) & set(2 * t + 2)        # signal hits fusion keys


class TestStoreOptions:
    def test_store_shapes_and_consistency(self):
        from repro.core.sweeps import run_byzantine_sweep

        topo, model = _byz_setup(M_nets=3, n=4)
        cfg = ByzantineConfig(topo=topo, F=1, byz=(1,), gamma_period=4,
                              attack=attacks.large_value())
        traj = run_byzantine_sweep(model, cfg, T=20, seeds=[0, 1])
        dec = run_byzantine_sweep(model, cfg, T=20, seeds=[0, 1],
                                  store="decisions")
        fin = run_byzantine_sweep(model, cfg, T=20, seeds=[0, 1],
                                  store="final")
        rt, rd, rf = (traj["large_value"], dec["large_value"],
                      fin["large_value"])
        N = topo.N
        assert rt.r.shape == (2, 20, N, 3, 3)
        assert rd.r.shape == (2, N, 3, 3) and rd.decisions.shape == (2, 20, N)
        assert rf.r.shape == (2, N, 3, 3) and rf.decisions.shape == (2, N)
        np.testing.assert_array_equal(np.asarray(rd.decisions),
                                      np.asarray(rt.decisions))
        np.testing.assert_allclose(np.asarray(rf.r),
                                   np.asarray(rt.r[:, -1]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(rf.decisions),
                                      np.asarray(rt.decisions[:, -1]))


def _grid_fixture():
    model = make_confused_model(N=15, m=3, truth=0, confusion=0.0, seed=0)
    atk = attacks.large_value()
    topos = [make_hierarchy([5, 5, 5], topology="ring+", extra_edge_prob=0.9,
                            seed=s) for s in range(3)]
    cfgs = []
    for topo in topos:
        cfgs.append(ByzantineConfig(topo=topo, F=0, byz=(), gamma_period=4,
                                    attack=atk))
        cfgs.append(ByzantineConfig(topo=topo, F=1, byz=(1,), gamma_period=4,
                                    attack=atk))
    return model, cfgs, atk


class TestByzantineGrid:
    def test_topology_F_seed_grid_single_trace(self):
        """Acceptance: 3 topologies x 2 F x 8 seeds as ONE compiled program
        — one jit cache entry, no retrace on a second seed batch."""
        from repro.core.sweeps import cache_registry, run_byzantine_grid

        model, cfgs, atk = _grid_fixture()
        reg = cache_registry()["byz.grid"]
        reg.clear()
        res = run_byzantine_grid(model, cfgs, T=30, seeds=list(range(8)))
        assert res.K == 48
        assert res.decisions.shape == (48, 30, 15)
        # heterogeneous F (0 and 1) forces the sort lowering on every
        # platform, so the effective backend in the cache key is "xla"
        # and the second seed batch reuses the one compiled entry
        assert reg.cache_info().currsize == 1
        res2 = run_byzantine_grid(model, cfgs, T=30, seeds=list(range(8, 16)))
        assert reg.cache_info().currsize == 1
        assert res2.K == 48

    def test_grid_matches_single_runs(self):
        """Heterogeneous F on the vmap axis (traced, sort lowering) must
        reproduce each config's static-F single run exactly."""
        from repro.core.sweeps import run_byzantine_grid

        model, cfgs, _ = _grid_fixture()
        res = run_byzantine_grid(model, cfgs, T=25, seeds=[0, 3])
        for k in range(0, res.K, 3):
            ci, sd = int(res.cfg[k]), int(res.seed[k])
            single = run_byzantine_learning(
                model, cfgs[ci], T=25, seed=sd, store="decisions",
                backend="xla",
            )
            np.testing.assert_array_equal(np.asarray(res.decisions[k]),
                                          np.asarray(single.decisions))
            np.testing.assert_allclose(np.asarray(res.r[k]),
                                       np.asarray(single.r),
                                       rtol=1e-5, atol=1e-5)

    def test_incompatible_configs_rejected(self):
        from repro.core.sweeps import run_byzantine_grid

        model, cfgs, atk = _grid_fixture()
        # M = 4 < 2F+1 = 5 with a majority-Byzantine network outside C
        # needs the static extra-reps branch, which cannot ride a vmap axis
        small = make_hierarchy([7, 7, 7, 3], topology="complete", seed=1)
        model24 = make_confused_model(N=24, m=3, truth=0, confusion=0.0,
                                      seed=3)
        bad = ByzantineConfig(topo=small, F=2, byz=(21, 22), gamma_period=4,
                              attack=atk)
        with pytest.raises(ValueError, match="2F\\+1"):
            run_byzantine_grid(model24, [bad], T=10, seeds=[0])
        # node-count mismatch
        with pytest.raises(ValueError, match="share"):
            run_byzantine_grid(
                model, [cfgs[0],
                        ByzantineConfig(topo=make_hierarchy([5, 5, 5, 5],
                                                            "complete"),
                                        F=0, byz=(), gamma_period=4,
                                        attack=atk)],
                T=10, seeds=[0])

    def test_sharded_grid_equals_single_device(self):
        """K=12 grid over a 4-device data mesh (subprocess, fake CPU
        devices): identical decisions to the single-device vmap."""
        prog = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import json
            import numpy as np
            import jax
            from repro.core import attacks
            from repro.core.byzantine import ByzantineConfig
            from repro.core.graphs import make_hierarchy
            from repro.core.signals import make_confused_model
            from repro.core.sweeps import run_byzantine_grid

            model = make_confused_model(N=15, m=3, truth=0, confusion=0.0,
                                        seed=0)
            atk = attacks.large_value()
            topos = [make_hierarchy([5, 5, 5], topology="ring+",
                                    extra_edge_prob=0.9, seed=s)
                     for s in range(3)]
            cfgs = []
            for topo in topos:
                cfgs.append(ByzantineConfig(topo=topo, F=0, byz=(),
                                            gamma_period=4, attack=atk))
                cfgs.append(ByzantineConfig(topo=topo, F=1, byz=(1,),
                                            gamma_period=4, attack=atk))
            r1 = run_byzantine_grid(model, cfgs, T=20, seeds=[0, 1])
            mesh = jax.make_mesh((4,), ("data",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            r2 = run_byzantine_grid(model, cfgs, T=20, seeds=[0, 1],
                                    mesh=mesh)
            same = bool((np.asarray(r1.decisions)
                         == np.asarray(r2.decisions)).all())
            err = float(np.abs(np.asarray(r1.r) - np.asarray(r2.r)).max())
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


class TestTrimmedMeanPytreeDtype:
    """The gradient-aggregator trim (the Byzantine filter applied
    coordinate-wise over a worker axis) computes in fp32 internally but must
    hand every leaf back in its input dtype."""

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_bf16_roundtrip_and_mixed_dtypes(self, backend):
        from repro.kernels.trimmed_mean.ops import trimmed_mean_pytree
        from repro.kernels.trimmed_mean.ref import trimmed_mean_ref

        rng = np.random.default_rng(0)
        tree = {
            "bf16": jnp.asarray(rng.normal(size=(8, 4, 3)),
                                dtype=jnp.bfloat16),
            "f32": jnp.asarray(rng.normal(size=(8, 9)).astype(np.float32)),
        }
        out = trimmed_mean_pytree(tree, 2, backend=backend)
        assert out["bf16"].dtype == jnp.bfloat16
        assert out["bf16"].shape == (4, 3)
        assert out["f32"].dtype == jnp.float32
        want = trimmed_mean_ref(
            tree["bf16"].reshape(8, -1).astype(jnp.float32), 2
        ).reshape(4, 3)
        np.testing.assert_allclose(
            np.asarray(out["bf16"], np.float32),
            np.asarray(want, np.float32),
            rtol=3e-2, atol=3e-2,
        )


class TestLRUCaches:
    def test_lru_eviction_and_recency(self):
        from repro.core.sweeps import _LRUCache

        c = _LRUCache(maxsize=3)
        for k in "abc":
            c[k] = k.upper()
        assert c["a"] == "A"             # refresh 'a'
        c["d"] = "D"                     # evicts 'b' (stalest), not 'a'
        assert set(c) == {"a", "c", "d"}
        assert c.get("b") is None
        c["e"] = "E"; c["f"] = "F"
        assert len(c) == 3               # bounded forever

    def test_compiled_caches_are_bounded(self):
        from repro.core.sweeps import cache_registry

        reg = cache_registry()
        assert isinstance(reg["byz.compiled"].cache_info().maxsize, int)
        assert 0 < reg["byz.compiled"].cache_info().maxsize <= 64
        assert 0 < reg["byz.grid"].cache_info().maxsize <= 64

    def test_sweep_cache_evicts_under_churn(self):
        """Churning more fingerprints than maxsize through the sweep cache
        keeps it bounded (the satellite's 'long parameter study')."""
        from repro.core.sweeps import cache_registry, run_byzantine_sweep

        reg = cache_registry()["byz.compiled"]
        topo, model = _byz_setup(M_nets=3, n=4)
        cfg = ByzantineConfig(topo=topo, F=1, byz=(1,), gamma_period=4,
                              attack=attacks.large_value())
        bound = reg.cache_info().maxsize
        for T in range(5, 5 + bound + 3):
            run_byzantine_sweep(model, cfg, T=T, seeds=[0])
        assert reg.cache_info().currsize <= bound
