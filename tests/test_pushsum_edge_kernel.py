"""Fused Pallas edge-scatter kernel + backend switch + sharded sweeps.

The contract under test: the Pallas kernel (interpret mode on CPU — the
identical traced program that compiles on TPU) is trajectory-equivalent to
the XLA sparse path, which is itself equivalent to the dense (N, N, d)
reference; the mass invariant survives the fused path; padding edges stay
inert; ``sort_by_dst`` is a pure relabeling (permutation round-trip); the
mesh-sharded sweep engine returns exactly what the single-device vmap
returns; and repeated ``run_byzantine_sweep`` calls do not retrace.
"""
import functools
import hashlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.graphs import (
    edge_list,
    edge_masks,
    link_schedule,
    random_strongly_connected,
    random_strongly_connected_edge_list,
    run_ends,
    sort_by_dst,
    stack_edge_lists,
)
from repro.core.pushsum import (
    run_pushsum,
    run_pushsum_sparse,
    sparse_mass_invariant,
)
from repro.kernels.pushsum_edge import edge_scatter_ref, resolve_backend
from repro.kernels.pushsum_edge.pushsum_edge import edge_scatter_pallas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sorted_graph(n, extra, seed):
    rng = np.random.default_rng(seed)
    el, perm, inv = sort_by_dst(edge_list(random_strongly_connected(n, extra, rng)))
    return el, perm, inv, rng


# sha256 of each program's StableHLO body (header line dropped), lowered
# from the tree before the run-end gather finish; see
# TestRunEndGather.test_program_unchanged_without_seg_end
_UNCHANGED_SHA256 = {
    "kernel":
        "14945e28fa50dbe04fa21a5cf1f37b25c419557ca4e2acb5faaaa7c85b8cc44e",
    "xla_ref":
        "416e30890723edce56d2acfd3eb8fd371680670abb22edaa537258b3e8f8a001",
    "social_xla":
        "d6b91c1cb544ef242db504c3a8c4ad94deed4a7de5dc8d1abe17fb10f9bb8a1d",
    "social_async":
        "ca1c1e6d6971259523ed6239c900c18d2f6c746e2bb2b5d9dd6740e19feaf669",
    "social_graph_axis":
        "2ce0adcb9f27e10473aebd4f2d5f00affc6e34e67cc4827e4a54620eb5584cbf",
    "hps":
        "fb22894e91e978d581666d805541288625df73b49d02906d4c7bebf0333565e3",
    "pushsum_sparse":
        "a353c57fac8b666548a3249311880664e513c077aa24565320c7df30011c3b76",
}


@functools.lru_cache(maxsize=1)
def _lowered_programs() -> dict:
    """StableHLO text of every program ``_UNCHANGED_SHA256`` names, at
    small fixed shapes. The social runtimes come from a dst-sorted list,
    so they carry run ends that these paths must not read."""
    from repro.core.asyncrony import make_async_model
    from repro.core.graphs import hier_edge_list, partition_edge_list
    from repro.core.hps import _hps_compiled, hps_runtime_from_edge_list
    from repro.core.plan import ExecutionPlan
    from repro.core.signals import make_confused_model
    from repro.core.social import (
        _social_compiled,
        _social_scan_core,
        social_runtime_from_edge_list,
    )
    from repro.kernels.pushsum_edge import edge_scatter

    out = {}
    N, E, D = 29, 83, 5
    f32, i32 = jnp.float32, jnp.int32
    s = jax.ShapeDtypeStruct
    args = (s((N, D), f32), s((E, D), f32), s((E,), jnp.bool_),
            s((E,), i32), s((E,), i32))
    out["kernel"] = edge_scatter_pallas.lower(
        *args, block_e=16, interpret=True, indices_sorted=True).as_text()
    out["xla_ref"] = jax.jit(
        lambda *a: edge_scatter(*a, backend="xla", indices_sorted=True)
    ).lower(*args).as_text()

    el, rep = hier_edge_list([4] * 4, "complete", rep_choice="first")
    rt = social_runtime_from_edge_list(el, rep, drop_prob=0.3,
                                       gamma_period=2, B=2)
    assert rt.seg_end is not None
    model = make_confused_model(N=16, m=3, S=4, truth=0, confusion=0.5,
                                sharpness=2.0, seed=1)
    tp = model.tables[:, 0, :].astype(jnp.float32)
    sargs = (jax.random.PRNGKey(0), jax.random.PRNGKey(1), rt,
             model.log_tables().astype(jnp.float32), jnp.cumsum(tp, -1))
    kw = dict(truth=0, M=4, T=4, store="log_ratio")
    out["social_xla"] = _social_compiled.lower(
        *sargs, backend="xla", **kw).as_text()
    out["social_async"] = _social_compiled.lower(
        *sargs, backend="pallas", async_=make_async_model(0.6, 2),
        **kw).as_text()

    sh = partition_edge_list(el, 2)
    rt_sh = rt._replace(src=jnp.asarray(sh.src), dst=jnp.asarray(sh.dst),
                        valid=jnp.asarray(sh.valid))
    in_rt = jax.tree_util.tree_map(lambda _: None, rt)._replace(
        src=0, dst=0, valid=0)
    k0, k1, _, lt, cdf = sargs

    def sharded(r):
        return jax.vmap(
            lambda r1: _social_scan_core(
                k0, k1, r1, lt, cdf, backend="pallas", graph_axis="g",
                n_shards=2, **kw)[1],
            in_axes=(in_rt,), axis_name="g")(r)

    out["social_graph_axis"] = jax.jit(sharded).lower(rt_sh).as_text()

    hrt = hps_runtime_from_edge_list(el, rep, drop_prob=0.3, gamma_period=2,
                                     B=2)
    w = s((16, 2), f32)
    out["hps"] = _hps_compiled.lower(
        jax.random.PRNGKey(0), hrt, w, T=4, store="trajectory",
        backend="pallas", dst_sorted=True).as_text()
    plan = ExecutionPlan(backend="pallas", dst_sorted=True)
    src, dst = jnp.asarray(el.src), jnp.asarray(el.dst)
    out["pushsum_sparse"] = jax.jit(
        lambda w: run_pushsum_sparse(w, src, dst, 4, drop_prob=0.2,
                                     key=jax.random.PRNGKey(2),
                                     plan=plan)[1]).lower(w).as_text()
    return out


class TestEdgeScatterKernel:
    @pytest.mark.parametrize("seed,block_e", [(0, 16), (1, 64), (2, 4096)])
    def test_matches_xla_ref(self, seed, block_e):
        """Single fused call == gather + where + segment_sum, including when
        E is far from a block multiple (padding edges must stay inert)."""
        el, _, _, rng = _sorted_graph(29, 0.25, seed)
        sigma = jnp.asarray(rng.normal(size=(29, 5)).astype(np.float32))
        rho = jnp.asarray(rng.normal(size=(el.E, 5)).astype(np.float32))
        live = jnp.asarray(rng.random(el.E) < 0.5)
        src, dst = jnp.asarray(el.src), jnp.asarray(el.dst)
        rn_ref, rc_ref = edge_scatter_ref(sigma, rho, live, src, dst)
        rn_p, rc_p = edge_scatter_pallas(
            sigma, rho, live, src, dst, block_e=block_e, interpret=True
        )
        np.testing.assert_allclose(np.asarray(rn_p), np.asarray(rn_ref),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(rc_p), np.asarray(rc_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_run_spanning_many_blocks(self):
        """A single receiver whose in-edge run spans several kernel blocks:
        every block's partial segment sum must accumulate into one row."""
        n, fan = 40, 33                      # star: everyone -> node 7
        src = np.concatenate([np.arange(1, fan + 1), [7]]).astype(np.int32)
        dst = np.concatenate([np.full(fan, 7), [8]]).astype(np.int32)
        order = np.argsort(dst, kind="stable")
        src, dst = src[order], dst[order]
        rng = np.random.default_rng(0)
        E = src.shape[0]
        sigma = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
        rho = jnp.asarray(rng.normal(size=(E, 3)).astype(np.float32))
        live = jnp.asarray(np.ones(E, bool))
        rn_ref, rc_ref = edge_scatter_ref(sigma, rho, live,
                                          jnp.asarray(src), jnp.asarray(dst))
        rn_p, rc_p = edge_scatter_pallas(
            sigma, rho, live, jnp.asarray(src), jnp.asarray(dst),
            block_e=8, interpret=True,       # run of 33 spans 5 blocks
        )
        np.testing.assert_allclose(np.asarray(rc_p), np.asarray(rc_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(rn_p), np.asarray(rn_ref))

    def test_unsorted_index_still_correct(self):
        """Sortedness is a fast-path property, not a correctness
        precondition: fragmented runs accumulate to the same segment sums."""
        rng = np.random.default_rng(3)
        el = edge_list(random_strongly_connected(17, 0.3, rng))  # src-major
        sigma = jnp.asarray(rng.normal(size=(17, 2)).astype(np.float32))
        rho = jnp.asarray(rng.normal(size=(el.E, 2)).astype(np.float32))
        live = jnp.asarray(rng.random(el.E) < 0.7)
        src, dst = jnp.asarray(el.src), jnp.asarray(el.dst)
        _, rc_ref = edge_scatter_ref(sigma, rho, live, src, dst)
        _, rc_p = edge_scatter_pallas(sigma, rho, live, src, dst,
                                      block_e=16, interpret=True)
        np.testing.assert_allclose(np.asarray(rc_p), np.asarray(rc_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_auto_backend_is_xla_off_tpu(self):
        """CPU CI must auto-select the XLA fallback (acceptance criterion)."""
        expected = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert resolve_backend("auto") == expected
        with pytest.raises(ValueError):
            resolve_backend("cuda")


def _run_end_case(case):
    """A dst-sorted edge list for one run-end gather case:
    ``(n, src, dst, valid, block_e)``."""
    rng = np.random.default_rng(0)
    n, block_e = 60, 128
    deg = rng.integers(1, 10, n)
    if case == "long_run":
        deg[17] = 333                      # spans at least 3 blocks of 128
    if case == "no_in_edges":
        deg[[0, 5, 6, 31, n - 1]] = 0      # first, adjacent, last
    dst = np.repeat(np.arange(n), deg).astype(np.int32)
    if case == "ragged_E":
        dst = dst[: (dst.size // block_e) * block_e + 37]
    valid = np.ones(dst.size, bool)
    if case == "padding_tail":             # as an e_max pad: dst = n - 1
        dst = np.concatenate([dst, np.full(45, n - 1, np.int32)])
        valid = np.concatenate([valid, np.zeros(45, bool)])
    src = rng.integers(0, n, dst.size).astype(np.int32)
    return n, src, dst, valid, block_e


class TestRunEndGather:
    @pytest.mark.parametrize("case", ["block_crossing", "long_run",
                                      "no_in_edges", "padding_tail",
                                      "ragged_E"])
    def test_matches_xla_ref(self, case):
        """The carried-run kernel and its run-end gather finish == gather +
        where + segment_sum, on dst-sorted lists whose runs cross blocks."""
        n, src, dst, valid, block_e = _run_end_case(case)
        E = dst.size
        seg_end = run_ends(dst, n)
        crossing = (seg_end[seg_end >= 0] // block_e
                    != np.searchsorted(dst, np.arange(n))[seg_end >= 0]
                    // block_e)
        assert crossing.any()              # some run spans a block boundary
        if case == "ragged_E":
            assert E % block_e
        rng = np.random.default_rng(1)
        D = 4
        sigma = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
        rho = jnp.asarray(rng.normal(size=(E, D)).astype(np.float32))
        live = jnp.asarray((rng.random(E) < 0.6) & valid)
        args = (sigma, rho, live, jnp.asarray(src), jnp.asarray(dst))
        rn_ref, rc_ref = edge_scatter_ref(*args)
        rn_p, rc_p = edge_scatter_pallas(
            *args, seg_end=jnp.asarray(seg_end), block_e=block_e,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(rn_p), np.asarray(rn_ref))
        np.testing.assert_allclose(np.asarray(rc_p), np.asarray(rc_ref),
                                   rtol=1e-5, atol=1e-5)
        no_in = seg_end < 0
        assert (np.asarray(rc_p)[no_in] == 0).all()

    def test_vmapped_scenarios_keep_their_own_carry(self):
        """Batched over scenarios (the grid engines vmap the step), each
        scenario's carry starts afresh at its first block."""
        n, src, dst, valid, block_e = _run_end_case("long_run")
        E, D = dst.size, 4
        rng = np.random.default_rng(2)
        sigma = jnp.asarray(rng.normal(size=(2, n, D)).astype(np.float32))
        rho = jnp.asarray(rng.normal(size=(2, E, D)).astype(np.float32))
        live = jnp.asarray(rng.random((2, E)) < 0.6)
        src2 = jnp.asarray(np.stack([src, rng.permutation(src)]))
        dst2 = jnp.asarray(np.stack([dst, dst]))
        seg2 = jnp.asarray(np.stack([run_ends(dst, n)] * 2))
        _, rc = jax.vmap(
            lambda *a: edge_scatter_pallas(*a[:5], seg_end=a[5],
                                           block_e=block_e, interpret=True)
        )(sigma, rho, live, src2, dst2, seg2)
        for k in range(2):
            _, rc_ref = edge_scatter_ref(sigma[k], rho[k], live[k], src2[k],
                                         dst2[k])
            np.testing.assert_allclose(np.asarray(rc[k]), np.asarray(rc_ref),
                                       rtol=1e-5, atol=1e-5)

    def test_run_ends(self):
        dst = np.array([0, 0, 2, 2, 2, 3], np.int32)
        np.testing.assert_array_equal(run_ends(dst, 5), [1, -1, 4, 5, -1])
        assert run_ends(dst[::-1], 5) is None

    @pytest.mark.parametrize("path", sorted(_UNCHANGED_SHA256))
    def test_program_unchanged_without_seg_end(self, path):
        """Where no run ends reach the kernel the lowered program is the one
        from before the run-end gather existed: the kernel called without
        ``seg_end``, the XLA oracle, an edge-partitioned (graph_axis) or
        async social scan and the XLA social scan, each given a runtime that
        does hold run ends, the HPS scan and ``run_pushsum_sparse``. The
        digests are of those programs' StableHLO before the change; an
        intended change to one of them updates its digest here."""
        text = _lowered_programs()[path]
        body = text.split("\n", 1)[1]        # the header names the wrapper
        assert "run_end_gather" not in body
        assert hashlib.sha256(body.encode()).hexdigest() == \
            _UNCHANGED_SHA256[path]


class TestBackendTrajectoryEquivalence:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_pallas_vs_xla_vs_dense(self, seed):
        """Identical (T, E) schedules: Pallas interpret == XLA sparse ==
        dense reference, per round, over the whole trajectory."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(6, 12))
        adj = random_strongly_connected(n, 0.3, rng)
        w = rng.normal(size=(n, 3)).astype(np.float32)
        masks = link_schedule(adj, 60, 0.4, 4, seed=seed)
        el0 = edge_list(adj)
        els, perm, _ = sort_by_dst(el0)
        em = edge_masks(masks, el0)[:, perm]     # schedule in sorted layout
        _, traj_dense = run_pushsum(w, adj, masks)
        _, traj_x = run_pushsum_sparse(w, els.src, els.dst, 60, masks=em,
                                       backend="xla")
        _, traj_p = run_pushsum_sparse(w, els.src, els.dst, 60, masks=em,
                                       backend="pallas")
        np.testing.assert_allclose(np.asarray(traj_p), np.asarray(traj_x),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(traj_p), np.asarray(traj_dense),
                                   rtol=1e-4, atol=1e-5)

    def test_mass_invariant_preserved_pallas(self):
        """90% drop through the fused path: the augmented-graph invariant
        (Theorem 1's conservation law) holds exactly."""
        el, _, _, rng = _sorted_graph(14, 0.3, 7)
        w = rng.normal(size=(14, 4)).astype(np.float32)
        final, _ = run_pushsum_sparse(
            w, el.src, el.dst, 150, drop_prob=0.9, B=10, backend="pallas",
        )
        inv = np.asarray(sparse_mass_invariant(
            final, jnp.asarray(el.src), jnp.asarray(el.valid)))
        np.testing.assert_allclose(inv, w.sum(0), rtol=2e-3, atol=2e-3)

    def test_padding_edges_carry_nothing_pallas(self):
        """valid=False edges with stray mask Trues are inert in the fused
        path — the sparse analogue of the dense mask & adj regression."""
        rng = np.random.default_rng(4)
        a1 = random_strongly_connected(6, 0.2, rng)
        a2 = random_strongly_connected(6, 0.6, rng)  # more edges -> a1 padded
        el, perm, _ = sort_by_dst(stack_edge_lists([a1, a2]))
        el1, perm1, _ = sort_by_dst(edge_list(a1))
        w = rng.normal(size=(6, 2)).astype(np.float32)
        masks = link_schedule(a1, 50, 0.3, 4, seed=4)
        em1 = edge_masks(masks, edge_list(a1))[:, perm1]
        _, t_ref = run_pushsum_sparse(
            w, el1.src, el1.dst, 50, masks=em1, backend="pallas"
        )
        E1 = el1.E
        padded_masks = np.zeros((50, el.E), bool)
        # project a1's schedule through the batched row-0 sort, then force
        # stray Trues on every padding slot
        raw = np.zeros((50, el.E), bool)
        raw[:, :E1] = edge_masks(masks, edge_list(a1))
        padded_masks = raw[:, perm[0]]
        padded_masks[:, ~el.valid[0]] = True
        _, t_pad = run_pushsum_sparse(
            w, el.src[0], el.dst[0], 50, masks=jnp.asarray(padded_masks),
            valid=el.valid[0], backend="pallas",
        )
        np.testing.assert_allclose(np.asarray(t_pad), np.asarray(t_ref),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.slow
    def test_equivalence_N16384(self):
        """Scale check on the dense-free constructor: one round at N=16384
        through both backends agrees to the acceptance atol (1e-5)."""
        rng = np.random.default_rng(11)
        el = random_strongly_connected_edge_list(16384, 1.5, rng)
        w = rng.normal(size=(16384, 3)).astype(np.float32)
        masks = jnp.asarray(rng.random((2, el.E)) < 0.7)
        fx, tx = run_pushsum_sparse(w, el.src, el.dst, 2, masks=masks,
                                    backend="xla")
        fp, tp = run_pushsum_sparse(w, el.src, el.dst, 2, masks=masks,
                                    backend="pallas")
        np.testing.assert_allclose(np.asarray(tp), np.asarray(tx),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fp.rho), np.asarray(fx.rho),
                                   rtol=1e-5, atol=1e-5)


class TestSortByDst:
    def test_roundtrip_single(self):
        el0 = edge_list(random_strongly_connected(
            23, 0.3, np.random.default_rng(0)))
        els, perm, inv = sort_by_dst(el0)
        assert (np.diff(els.dst) >= 0).all()
        np.testing.assert_array_equal(els.src[inv], el0.src)
        np.testing.assert_array_equal(els.dst[inv], el0.dst)
        np.testing.assert_array_equal(perm[inv], np.arange(el0.E))
        np.testing.assert_array_equal(inv[perm], np.arange(el0.E))

    def test_roundtrip_batched(self):
        rng = np.random.default_rng(1)
        el0 = stack_edge_lists([random_strongly_connected(8, 0.3, rng),
                                random_strongly_connected(8, 0.6, rng)])
        els, perm, inv = sort_by_dst(el0)
        assert (np.diff(els.dst, axis=1) >= 0).all()
        np.testing.assert_array_equal(
            np.take_along_axis(els.src, inv, axis=1), el0.src)
        np.testing.assert_array_equal(
            np.take_along_axis(els.valid, inv, axis=1), el0.valid)

    def test_sparse_constructor_no_dense(self):
        """Direct edge-list construction at N=4096: strong-connectivity
        backbone present, no self-loops, no duplicate edges, sorted."""
        rng = np.random.default_rng(2)
        el = random_strongly_connected_edge_list(4096, 2.0, rng)
        assert (np.diff(el.dst) >= 0).all()
        assert (el.src != el.dst).all()
        key = el.src.astype(np.int64) * 4096 + el.dst
        assert np.unique(key).shape[0] == el.E
        deg_out = np.bincount(el.src, minlength=4096)
        deg_in = np.bincount(el.dst, minlength=4096)
        assert deg_out.min() >= 1 and deg_in.min() >= 1  # cycle backbone


class TestShardedSweep:
    def test_sharded_equals_single_device(self):
        """K=12 scenarios over a 4-device data mesh (subprocess, fake CPU
        devices): identical errors/ratios to the single-device vmap, with K
        padded to the axis size internally and sliced back."""
        prog = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import json
            import numpy as np
            import jax
            from repro.core.graphs import (
                random_strongly_connected, sort_by_dst, stack_edge_lists)
            from repro.core.sweeps import run_pushsum_sweep

            rng = np.random.default_rng(0)
            el, _, _ = sort_by_dst(stack_edge_lists(
                [random_strongly_connected(24, 0.1, rng) for _ in range(2)]))
            w = rng.normal(size=(24, 2)).astype(np.float32)
            kw = dict(drop_probs=[0.0, 0.6], seeds=[0, 1, 2], B=4)
            r1 = run_pushsum_sweep(w, el, 80, **kw)
            mesh = jax.make_mesh((4,), ("data",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            r2 = run_pushsum_sweep(w, el, 80, mesh=mesh, **kw)  # K=12 -> pad 16
            err = float(np.abs(np.asarray(r2.err) - np.asarray(r1.err)).max())
            fin = float(np.abs(np.asarray(r2.final_ratio)
                               - np.asarray(r1.final_ratio)).max())
            print(json.dumps({"K": int(r2.K), "err": err, "fin": fin,
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
        assert res["err"] == 0.0 and res["fin"] == 0.0


class TestBenchHarness:
    """benchmarks/run.py --json-dir merge semantics and the --check gate."""

    def _run_mod(self):
        sys.path.insert(0, REPO)
        try:
            from benchmarks import run as bench_run
        finally:
            sys.path.pop(0)
        return bench_run

    def test_merge_json_preserves_unmeasured_keys(self, tmp_path):
        sys.path.insert(0, REPO)
        try:
            from benchmarks import merge_bench_json
        finally:
            sys.path.pop(0)
        path = str(tmp_path / "BENCH_x.json")
        with open(path, "w") as f:
            json.dump({"old_row": {"us_per_call": 5.0, "derived": "d"}}, f)
        merge_bench_json(path, [("new_row", 7.0, "e"), ("old_row", 6.0, "d2"),
                                ("failed_row", float("nan"), "boom")])
        with open(path) as f:
            merged = json.load(f)
        assert merged["new_row"]["us_per_call"] == 7.0
        assert merged["old_row"]["us_per_call"] == 6.0   # updated, not lost
        assert "failed_row" not in merged      # NaN rows never serialized
        assert "NaN" not in open(path).read()  # strict RFC-8259 artifact

    def test_check_regressions_threshold(self):
        bench_run = self._run_mod()
        baseline = {"a": {"us_per_call": 100.0},
                    "b": {"us_per_call": 100.0},
                    "interp": {"us_per_call": 100.0},
                    "nan_row": {"us_per_call": float("nan")}}
        # 1.2x is within the 25% budget; 1.3x is a regression; names absent
        # from the baseline (new benchmarks), NaN rows, and interpret-mode
        # rows (Pallas-on-CPU equivalence timings) are skipped
        assert bench_run._check_regressions(
            "x", baseline, {"a": (120.0, "d"), "new": (9e9, "d"),
                            "nan_row": (5.0, "d"),
                            "interp": (900.0, "backend=pallas;mode=interpret"),
                            }) == 0
        assert bench_run._check_regressions(
            "x", baseline, {"a": (130.0, "d"), "b": (126.0, "d")}) == 2


class TestByzantineSweepNoRetrace:
    def test_second_call_hits_compiled_cache(self):
        """Acceptance criterion: run_byzantine_sweep twice with the same
        shapes/config does not retrace (one entry in the jit cache)."""
        from repro.core import attacks
        from repro.core.byzantine import ByzantineConfig
        from repro.core.graphs import make_hierarchy
        from repro.core.signals import make_confused_model
        from repro.core.sweeps import cache_registry, run_byzantine_sweep

        topo = make_hierarchy([4, 4, 4], topology="complete", seed=0)
        model = make_confused_model(topo.N, 3, confusion=0.0, seed=0)
        cfg = ByzantineConfig(topo=topo, F=1, byz=(1,), gamma_period=4,
                              attack=attacks.large_value())
        reg = cache_registry()["byz.compiled"]
        reg.clear()
        r1 = run_byzantine_sweep(model, cfg, T=12, seeds=[0, 1])
        assert reg.cache_info().currsize == 1
        r2 = run_byzantine_sweep(model, cfg, T=12, seeds=[2, 3])
        # same fingerprint -> same compiled entry, no second compile
        assert reg.cache_info().currsize == 1
        assert r1["large_value"].r.shape == r2["large_value"].r.shape
        # host-side C-set lattice memoized too
        from repro.core.byzantine import _C_SET_LATTICE
        assert len(_C_SET_LATTICE) >= 1

    def test_different_T_retraces_separately(self):
        from repro.core import attacks
        from repro.core.byzantine import ByzantineConfig
        from repro.core.graphs import make_hierarchy
        from repro.core.signals import make_confused_model
        from repro.core.sweeps import cache_registry, run_byzantine_sweep

        topo = make_hierarchy([4, 4, 4], topology="complete", seed=0)
        model = make_confused_model(topo.N, 3, confusion=0.0, seed=0)
        cfg = ByzantineConfig(topo=topo, F=1, byz=(1,), gamma_period=4,
                              attack=attacks.large_value())
        reg = cache_registry()["byz.compiled"]
        reg.clear()
        run_byzantine_sweep(model, cfg, T=12, seeds=[0])
        run_byzantine_sweep(model, cfg, T=13, seeds=[0])
        # a distinct horizon gets its own entry, within the LRU bound
        info = reg.cache_info()
        assert info.currsize == 2
        assert info.currsize <= info.maxsize
