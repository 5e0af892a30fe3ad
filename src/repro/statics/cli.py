"""``python -m repro.statics`` — run the static checks from the command line.

Subcommands:

* ``lint``   — trace every public entry point across backend x store
  combos and run the full registry of checks: the ``run_*`` signature
  linter (no entry point may re-grow a loose execution kwarg covered by
  ``ExecutionPlan`` — see :mod:`repro.statics.signatures`),
  dense-intermediate linter,
  subnormal-constant scan, PRNG stream-domain disjointness proofs (within
  each engine and across engines that may share one experiment seed), the
  per-trace PRNG-site lower bound, the retrace sentinel (tiny XLA runs,
  executed twice — the second call must compile nothing), the static
  memory-budget validation of the committed BENCH artifacts, and the
  precision-policy proofs (the donating step entry must alias every state
  buffer in its lowered module; no engine scan may carry persistent fp32
  per-edge/per-node state under the bf16 policy). Exit 0 iff no findings.
* ``budget`` — print the analytic per-engine step-byte models, their
  TPU-v5e roofline floors, the per-policy budgets (fp32 vs bf16 storage),
  and the traced-footprint accounting.
* ``list``   — show the registered contracts and compiled caches.

A passing lint verdict is cached in ``--cache-dir`` keyed on the sha256 of
every ``src/repro/**/*.py`` file, the BENCH artifacts, and the jax
version, so repeated CI runs on unchanged sources answer from the cache
(the CI lane additionally persists that directory across workflow runs).

The ``--inject-*`` flags are TEST hooks: they swap a known-bad historical
configuration (the three shipped PRNG aliasing schemes, or a synthetic
dense intermediate) into the checked set so ``tests/test_statics.py`` can
prove the lint would have caught each one. They are not for normal use.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
from pathlib import Path

import numpy as np

from . import (
    contracts,
    dense,
    memory,
    precision,
    retrace,
    signatures,
    streams,
    walk,
)
from .dense import Finding

_REPO_ROOT = Path(__file__).resolve().parents[3]

_TRACE_BACKENDS = ("xla", "pallas")


# ---------------------------------------------------------------------------
# Fixtures — tiny concrete programs per engine. Dim sizes are pairwise
# distinct WITHIN each fixture so repro.statics.walk.symbolize can never
# confuse axes (the discipline the historical per-test walkers used).
# ---------------------------------------------------------------------------

def _pushsum_fixture():
    import jax

    from repro.core.graphs import edge_list, random_strongly_connected
    from repro.core.plan import ExecutionPlan
    from repro.core.pushsum import run_pushsum_sparse

    rng = np.random.default_rng(0)
    adj = random_strongly_connected(11, 0.3, rng)
    el = edge_list(adj)
    w = rng.normal(size=(11, 2)).astype(np.float32)
    dims = {"N": 11, "d": 2, "T": 7, "E": int(el.E)}

    def make(backend, store):
        return walk.trace(
            lambda w_, key_: run_pushsum_sparse(
                w_, el.src, el.dst, T=7, drop_prob=0.1, B=2,
                key=key_, plan=ExecutionPlan(backend=backend),
            ),
            w, jax.random.PRNGKey(0),
        )

    return dims, (None,), make


def _social_fixture():
    from repro.core.graphs import make_hierarchy
    from repro.core.hps import HPSConfig
    from repro.core.plan import ExecutionPlan
    from repro.core.signals import make_confused_model
    from repro.core.social import (
        SOCIAL_STORES,
        make_social_runtime,
        run_social_runtime,
    )

    topo = make_hierarchy([6, 6, 6], topology="complete", seed=2)
    model = make_confused_model(N=topo.N, m=3, truth=1, confusion=0.5,
                                seed=0)
    cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.3)
    rt = make_social_runtime(cfg)
    dims = {"N": 18, "m": 3, "T": 37, "E": int(np.asarray(rt.src).shape[0])}

    def make(backend, store):
        return walk.trace(
            lambda rt_: run_social_runtime(
                model, rt_, M=len(topo.sizes), T=37,
                plan=ExecutionPlan(backend=backend, store=store),
            ),
            rt,
        )

    return dims, SOCIAL_STORES, make


def _hps_fixture():
    from repro.core.graphs import make_hierarchy
    from repro.core.hps import HPS_STORES, HPSConfig, make_hps_runtime, run_hps
    from repro.core.plan import ExecutionPlan

    topo = make_hierarchy([5, 5, 5], topology="complete", seed=0)
    cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.2)
    rt = make_hps_runtime(cfg)
    w = np.random.default_rng(3).normal(size=(15, 2)).astype(np.float32)
    dims = {"N": 15, "d": 2, "T": 31, "E": int(np.asarray(rt.src).shape[0])}

    def make(backend, store):
        return walk.trace(
            lambda w_: run_hps(w_, cfg, T=31, seed=0,
                               plan=ExecutionPlan(backend=backend,
                                                  store=store)),
            w,
        )

    return dims, HPS_STORES, make


def _byz_fixture():
    import jax

    from repro.core import attacks
    from repro.core.byzantine import (
        STORES,
        ByzantineConfig,
        make_byzantine_scan,
    )
    from repro.core.graphs import make_hierarchy
    from repro.core.signals import make_confused_model

    topo = make_hierarchy([8] * 8, topology="complete", seed=0)   # N = 64
    model = make_confused_model(N=64, m=3, truth=0, confusion=0.0, seed=1)
    cfg = ByzantineConfig(topo=topo, F=2, byz=(2, 9), gamma_period=4,
                          attack=attacks.sign_flip())
    dims = {"N": 64, "m": 3, "T": 5}

    def make(backend, store):
        run = make_byzantine_scan(model, cfg, T=5, core="sparse",
                                  backend=backend, store=store)
        return walk.trace(run, jax.random.PRNGKey(0))

    return dims, STORES, make


def _chaos_model():
    """One non-degenerate FaultModel shared by all four faulted fixtures:
    every fault mechanism (burst chain, churn, PS crash) is live so every
    fault stream is actually drawn in the traced program."""
    from repro.core.faults import make_fault_model

    return make_fault_model(p_gb=0.2, p_bg=0.5, drop_bad=0.9,
                            leave_prob=0.05, join_prob=0.5,
                            ps_crash_prob=0.3)


def _pushsum_faults_fixture():
    import jax

    from repro.core.graphs import edge_list, random_strongly_connected
    from repro.core.plan import ExecutionPlan
    from repro.core.pushsum import run_pushsum_sparse

    rng = np.random.default_rng(0)
    adj = random_strongly_connected(11, 0.3, rng)
    el = edge_list(adj)
    w = rng.normal(size=(11, 2)).astype(np.float32)
    fm = _chaos_model()
    dims = {"N": 11, "d": 2, "T": 7, "E": int(el.E)}

    def make(backend, store):
        # record_every=T: a single ratio frame, so the (T, *) ban can hold
        # over the whole faulted trace (fault state itself is O(E)+O(N)).
        return walk.trace(
            lambda w_, key_: run_pushsum_sparse(
                w_, el.src, el.dst, T=7, drop_prob=0.1, B=2,
                key=key_, record_every=7,
                plan=ExecutionPlan(backend=backend, faults=fm),
            ),
            w, jax.random.PRNGKey(0),
        )

    return dims, (None,), make


def _social_faults_fixture():
    from repro.core.graphs import make_hierarchy
    from repro.core.hps import HPSConfig
    from repro.core.plan import ExecutionPlan
    from repro.core.signals import make_confused_model
    from repro.core.social import make_social_runtime, run_social_runtime

    topo = make_hierarchy([6, 6, 6], topology="complete", seed=2)
    model = make_confused_model(N=topo.N, m=3, truth=1, confusion=0.5,
                                seed=0)
    cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.3)
    rt = make_social_runtime(cfg)
    fm = _chaos_model()
    dims = {"N": 18, "m": 3, "T": 37, "E": int(np.asarray(rt.src).shape[0])}

    def make(backend, store):
        plan = ExecutionPlan(backend=backend, store=store, faults=fm)
        return walk.trace(
            lambda rt_: run_social_runtime(
                model, rt_, M=len(topo.sizes), T=37, plan=plan),
            rt,
        )

    # log_ratio is the in-scan-reduced store: the one where (T, *) is a
    # provable ban rather than the store's own output.
    return dims, ("log_ratio",), make


def _hps_faults_fixture():
    from repro.core.graphs import make_hierarchy
    from repro.core.hps import HPSConfig, make_hps_runtime, run_hps
    from repro.core.plan import ExecutionPlan

    topo = make_hierarchy([5, 5, 5], topology="complete", seed=0)
    cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.2)
    rt = make_hps_runtime(cfg)
    w = np.random.default_rng(3).normal(size=(15, 2)).astype(np.float32)
    fm = _chaos_model()
    dims = {"N": 15, "d": 2, "T": 31, "E": int(np.asarray(rt.src).shape[0])}

    def make(backend, store):
        plan = ExecutionPlan(backend=backend, store=store, faults=fm)
        return walk.trace(
            lambda w_: run_hps(w_, cfg, T=31, seed=0, plan=plan),
            w,
        )

    return dims, ("gap",), make


def _byz_faults_fixture():
    import jax

    from repro.core import attacks
    from repro.core.byzantine import ByzantineConfig, make_byzantine_scan
    from repro.core.graphs import make_hierarchy
    from repro.core.signals import make_confused_model

    topo = make_hierarchy([8] * 8, topology="complete", seed=0)   # N = 64
    model = make_confused_model(N=64, m=3, truth=0, confusion=0.0, seed=1)
    cfg = ByzantineConfig(topo=topo, F=2, byz=(2, 9), gamma_period=4,
                          attack=attacks.sign_flip())
    fm = _chaos_model()
    dims = {"N": 64, "m": 3, "T": 5}

    def make(backend, store):
        run = make_byzantine_scan(model, cfg, T=5, core="sparse",
                                  backend=backend, store=store, faults=fm)
        return walk.trace(run, jax.random.PRNGKey(0))

    return dims, ("final",), make


def _pushsum_sharded_fixture():
    from repro.core.graphs import (
        partition_edge_list,
        random_strongly_connected_edge_list,
    )
    from repro.core.sweeps import _sweep2d_emulated

    rng = np.random.default_rng(5)
    el = random_strongly_connected_edge_list(11, 0.25, rng, sort=False)
    sh = partition_edge_list(el, 2)
    w = rng.normal(size=(11, 3)).astype(np.float32)
    # (K=2, S, Es) scenario-gathered shards, exactly what the sweep feeds
    # the vmap(axis_name=) emulation — the single-device twin of the 2-D
    # mesh program (same traced collectives), so linting it lints both
    src_k = np.broadcast_to(sh.src[None], (2,) + sh.src.shape).copy()
    dst_k = np.broadcast_to(sh.dst[None], (2,) + sh.dst.shape).copy()
    val_k = np.broadcast_to(sh.valid[None], (2,) + sh.valid.shape).copy()
    lo, ends = sh.run_end_windows()
    lo_k = np.broadcast_to(lo[None], (2,) + lo.shape).copy()
    ends_k = np.broadcast_to(ends[None], (2,) + ends.shape).copy()
    drop_b = np.array([0.1, 0.3], np.float32)
    seed_b = np.array([0, 1], np.uint32)
    dims = {"N": 11, "d": 3, "T": 5, "S": sh.n_shards,
            "E": sh.e_pad, "Es": sh.e_shard}
    assert len(set(dims.values())) == len(dims), dims

    def make(backend, store):
        return walk.trace(
            lambda w_, s_, d_, v_, lo_, e_, dp_, sd_: _sweep2d_emulated(
                w_, s_, d_, v_, lo_, e_, dp_, sd_,
                T=5, B=2, backend=backend,
                graph_axis="shardlint", n_shards=sh.n_shards,
            ),
            w, src_k, dst_k, val_k, lo_k, ends_k, drop_b, seed_b,
        )

    return dims, (None,), make


def _async_model():
    """One non-degenerate AsyncModel shared by the three async fixtures:
    agents sleep (wake_prob < 1) and stale snapshots deliver
    (staleness > 0), so the wake stream is actually drawn and the
    O(E·d) buffer is actually carried in the traced program. A
    degenerate model would dispatch to the synchronous engine and
    trace no async machinery at all."""
    from repro.core.asyncrony import make_async_model

    return make_async_model(wake_prob=0.6, staleness=2)


def _pushsum_async_fixture():
    import jax

    from repro.core.graphs import edge_list, random_strongly_connected
    from repro.core.plan import ExecutionPlan
    from repro.core.pushsum import run_pushsum_sparse

    rng = np.random.default_rng(0)
    adj = random_strongly_connected(11, 0.3, rng)
    el = edge_list(adj)
    w = rng.normal(size=(11, 2)).astype(np.float32)
    plan_of = lambda b: ExecutionPlan(backend=b, async_=_async_model())
    dims = {"N": 11, "d": 2, "T": 7, "E": int(el.E)}

    def make(backend, store):
        # record_every=T: a single ratio frame, so the (T, *) ban holds
        # over the async trace (the buffer itself is O(E*d), not O(T)).
        return walk.trace(
            lambda w_, key_: run_pushsum_sparse(
                w_, el.src, el.dst, T=7, drop_prob=0.1, B=2,
                key=key_, record_every=7, plan=plan_of(backend),
            ),
            w, jax.random.PRNGKey(0),
        )

    return dims, (None,), make


def _social_async_fixture():
    from repro.core.graphs import make_hierarchy
    from repro.core.hps import HPSConfig
    from repro.core.plan import ExecutionPlan
    from repro.core.signals import make_confused_model
    from repro.core.social import make_social_runtime, run_social_runtime

    topo = make_hierarchy([6, 6, 6], topology="complete", seed=2)
    model = make_confused_model(N=topo.N, m=3, truth=1, confusion=0.5,
                                seed=0)
    cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.3)
    rt = make_social_runtime(cfg)
    dims = {"N": 18, "m": 3, "T": 37, "E": int(np.asarray(rt.src).shape[0])}

    def make(backend, store):
        plan = ExecutionPlan(backend=backend, store=store,
                             async_=_async_model())
        return walk.trace(
            lambda rt_: run_social_runtime(
                model, rt_, M=len(topo.sizes), T=37, plan=plan),
            rt,
        )

    # log_ratio is the in-scan-reduced store: the one where (T, *) is a
    # provable ban rather than the store's own output.
    return dims, ("log_ratio",), make


def _hps_async_fixture():
    from repro.core.graphs import make_hierarchy
    from repro.core.hps import HPSConfig, make_hps_runtime, run_hps
    from repro.core.plan import ExecutionPlan

    topo = make_hierarchy([5, 5, 5], topology="complete", seed=0)
    cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.2)
    rt = make_hps_runtime(cfg)
    w = np.random.default_rng(3).normal(size=(15, 2)).astype(np.float32)
    dims = {"N": 15, "d": 2, "T": 31, "E": int(np.asarray(rt.src).shape[0])}

    def make(backend, store):
        plan = ExecutionPlan(backend=backend, store=store,
                             async_=_async_model())
        return walk.trace(
            lambda w_: run_hps(w_, cfg, T=31, seed=0, plan=plan),
            w,
        )

    return dims, ("gap",), make


_FIXTURES = {
    "pushsum": _pushsum_fixture,
    "pushsum_sharded": _pushsum_sharded_fixture,
    "social": _social_fixture,
    "hps": _hps_fixture,
    "byzantine": _byz_fixture,
    "pushsum_faults": _pushsum_faults_fixture,
    "social_faults": _social_faults_fixture,
    "hps_faults": _hps_faults_fixture,
    "byzantine_faults": _byz_faults_fixture,
    "pushsum_async": _pushsum_async_fixture,
    "social_async": _social_async_fixture,
    "hps_async": _hps_async_fixture,
}


def _retrace_thunks():
    """Tiny concrete runs of every sweep/grid entry point (XLA, CPU-safe).
    Each is executed twice by the sentinel; the second call must hit every
    compiled cache."""
    from repro.core import attacks
    from repro.core.byzantine import ByzantineConfig
    from repro.core.graphs import edge_list, make_hierarchy, \
        random_strongly_connected
    from repro.core.hps import HPSConfig
    from repro.core.plan import ExecutionPlan
    from repro.core.signals import make_confused_model
    from repro.core.sweeps import (
        run_byzantine_grid,
        run_byzantine_sweep,
        run_hps_grid,
        run_hps_sweep,
        run_pushsum_sweep,
        run_social_grid,
        run_social_sweep,
    )

    rng = np.random.default_rng(1)
    el = edge_list(random_strongly_connected(16, 0.2, rng))
    w16 = rng.normal(size=(16, 2)).astype(np.float32)

    topo = make_hierarchy([5, 5, 5], topology="complete", seed=0)   # N = 15
    model = make_confused_model(N=15, m=3, truth=0, confusion=0.0, seed=0)
    bcfgs = [
        ByzantineConfig(topo=topo, F=0, byz=(), gamma_period=4,
                        attack=attacks.sign_flip()),
        ByzantineConfig(topo=topo, F=1, byz=(1,), gamma_period=4,
                        attack=attacks.sign_flip()),
    ]
    hcfgs = [HPSConfig(topo=topo, gamma_period=g, B=2, drop_prob=0.0)
             for g in (2, 4)]
    w15 = rng.normal(size=(15, 2)).astype(np.float32)
    xla = ExecutionPlan(backend="xla")

    return {
        "run_pushsum_sweep": lambda: run_pushsum_sweep(
            w16, el, T=5, drop_probs=[0.0, 0.5], seeds=[0, 1], B=2,
            plan=xla),
        "run_pushsum_sweep_sharded": lambda: run_pushsum_sweep(
            w16, el, T=5, drop_probs=[0.0, 0.5], seeds=[0, 1], B=2,
            plan=xla.replace(graph_shards=2)),
        "run_pushsum_sweep_async": lambda: run_pushsum_sweep(
            w16, el, T=5, drop_probs=[0.0, 0.5], seeds=[0, 1], B=2,
            plan=xla.replace(async_=_async_model())),
        "run_byzantine_sweep": lambda: run_byzantine_sweep(
            model, bcfgs[1], T=3, seeds=[0, 1],
            plan=xla.replace(store="final")),
        "run_byzantine_grid": lambda: run_byzantine_grid(
            model, bcfgs, T=3, seeds=[0, 1],
            plan=xla.replace(store="decisions")),
        "run_hps_sweep": lambda: run_hps_sweep(
            w15, hcfgs[0], T=4, drop_probs=[0.0, 0.3], seeds=[0],
            plan=xla.replace(store="gap")),
        "run_hps_grid": lambda: run_hps_grid(
            w15, hcfgs, T=4, seeds=[0, 1], plan=xla.replace(store="gap")),
        "run_social_sweep": lambda: run_social_sweep(
            model, hcfgs[0], T=4, drop_probs=[0.0, 0.3], seeds=[0],
            plan=xla.replace(store="log_ratio")),
        "run_social_grid": lambda: run_social_grid(
            model, hcfgs, T=4, seeds=[0, 1],
            plan=xla.replace(store="log_ratio")),
    }


def _count_prng_sites(closed) -> int:
    n = 0
    for _, eqn in walk.iter_eqns(closed):
        name = eqn.primitive.name
        if "threefry" in name or name.startswith("random_"):
            n += 1
    return n


def _synthetic_dense(dims):
    """A deliberately-broken pushsum-shaped program: materializes the
    (N, N) averaging matrix the sparse core exists to avoid."""
    import jax.numpy as jnp

    N = dims["N"]

    def bad(w):
        dense_mix = jnp.ones((N, N), w.dtype) / N      # the bug
        return dense_mix @ w

    return walk.trace(bad, np.zeros((N, dims["d"]), np.float32))


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

def _trace_findings(engines, inject_dense=False) -> list[Finding]:
    out: list[Finding] = []
    for name in engines:
        c = contracts.get(name)
        dims, stores, make = _FIXTURES[name]()
        for backend in _TRACE_BACKENDS:
            for store in stores:
                where = f"{name}[backend={backend}" + (
                    f", store={store}]" if store else "]")
                try:
                    closed = make(backend, store)
                except Exception as e:  # tracing itself must not break
                    out.append(Finding(
                        check="trace-error", where=where,
                        message=f"{type(e).__name__}: {e}",
                    ))
                    continue
                out.extend(dense.assert_nonempty(closed, where=where))
                out.extend(dense.find_forbidden(
                    closed, dims, c.forbidden_for(store), where=where))
                out.extend(dense.find_subnormal_consts(closed, where=where))
                sites = _count_prng_sites(closed)
                if sites < c.n_prng_sites:
                    out.append(Finding(
                        check="prng-sites", where=where,
                        message=(
                            f"traced program holds {sites} counter-PRNG "
                            f"call sites but the contract declares "
                            f"{c.n_prng_sites} streams — a stream was "
                            "hoisted or dropped"
                        ),
                    ))
        if inject_dense and name == "pushsum":
            out.extend(dense.find_forbidden(
                _synthetic_dense(dims), dims, c.forbidden_for(None),
                where="pushsum[synthetic-dense-injection]"))
    return out


def _fitted_streams(c, override: dict | None) -> list[streams.AffineMap]:
    if override and c.name in override:
        return list(override[c.name])
    return [streams.fit_affine(s.fold, f"{c.name}.{s.name}")
            for s in c.streams]


def _stream_findings(engines, override: dict | None = None) -> list[Finding]:
    out: list[Finding] = []
    fitted = {}
    for name in engines:
        c = contracts.get(name)
        try:
            fitted[name] = _fitted_streams(c, override)
        except ValueError as e:
            out.append(Finding(check="prng-stream-collision", where=name,
                               message=str(e)))
            fitted[name] = []
    for name in engines:
        c = contracts.get(name)
        out.extend(streams.check_streams(fitted[name], c.horizon,
                                         where=name))
        for other in c.shares_seed_with:
            if other not in fitted:
                oc = contracts.get(other)
                fitted[other] = _fitted_streams(oc, override)
            oc = contracts.get(other)
            horizon = min(c.horizon, oc.horizon)
            for m1 in fitted[name]:
                for m2 in fitted[other]:
                    disjoint, wit = streams.affine_disjoint(
                        m1, m2, horizon)
                    if not disjoint:
                        t1, t2, val = wit
                        out.append(Finding(
                            check="prng-stream-collision",
                            where=f"{name} x {other}",
                            message=(
                                f"shared-seed engines collide: {m1.name}"
                                f"@t={t1} == {m2.name}@t={t2} (both fold "
                                f"{val}); maps [{m1}] vs [{m2}] over "
                                f"horizon T={horizon}"
                            ),
                        ))
    return out


def _retrace_findings() -> list[Finding]:
    out: list[Finding] = []
    for name, thunk in _retrace_thunks().items():
        out.extend(retrace.check_idempotent(thunk, where=name))
    return out


def _precision_findings() -> list[Finding]:
    """Precision-policy proofs (trace/lower only, nothing executes):
    donation aliasing on the step entry for both policies, and the
    bf16-carry contract over every engine's scan."""
    import jax

    from repro.core import attacks
    from repro.core.byzantine import ByzantineConfig, make_byzantine_scan
    from repro.core.graphs import edge_list, make_hierarchy, \
        random_strongly_connected
    from repro.core.hps import HPSConfig, make_hps_runtime, run_hps
    from repro.core.plan import ExecutionPlan
    from repro.core.signals import make_confused_model
    from repro.core.social import make_social_runtime, run_social_runtime
    from repro.core.sweeps import _sweep_body

    out: list[Finding] = []
    out += precision.step_donation_findings("xla", None)
    out += precision.step_donation_findings("xla", "bf16")

    # pushsum sweep body, K=2 scenario batch, bf16 storage
    rng = np.random.default_rng(7)
    el = edge_list(random_strongly_connected(11, 0.3, rng))
    w11 = rng.normal(size=(11, 3)).astype(np.float32)
    src_b = np.broadcast_to(el.src[None], (2, el.E)).copy()
    dst_b = np.broadcast_to(el.dst[None], (2, el.E)).copy()
    val_b = np.ones((2, el.E), bool)
    drop_b = np.array([0.1, 0.4], np.float32)
    seed_b = np.array([0, 1], np.uint32)
    closed = walk.trace(
        lambda *a: _sweep_body(*a, T=5, B=2, backend="xla", policy="bf16"),
        w11, src_b, dst_b, val_b, drop_b, seed_b)
    out += precision.find_fp32_scan_state(
        closed, {"N": 11, "d": 3, "T": 5, "E": int(el.E), "K": 2},
        where="pushsum[policy=bf16]")

    # social + hps share the [6,6,6]/[5,5,5] hierarchy fixtures
    topo = make_hierarchy([6, 6, 6], topology="complete", seed=2)
    model = make_confused_model(N=topo.N, m=3, truth=1, confusion=0.5,
                                seed=0)
    cfg = HPSConfig(topo=topo, gamma_period=4, B=2, drop_prob=0.3)
    rt = make_social_runtime(cfg)
    closed = walk.trace(
        lambda rt_: run_social_runtime(
            model, rt_, M=len(topo.sizes), T=37,
            plan=ExecutionPlan(backend="xla", store="log_ratio",
                               policy="bf16")),
        rt)
    out += precision.find_fp32_scan_state(
        closed,
        {"N": 18, "m": 3, "T": 37, "E": int(np.asarray(rt.src).shape[0])},
        where="social[policy=bf16]")

    topo5 = make_hierarchy([5, 5, 5], topology="complete", seed=0)
    hcfg = HPSConfig(topo=topo5, gamma_period=4, B=2, drop_prob=0.2)
    hrt = make_hps_runtime(hcfg)
    w15 = rng.normal(size=(15, 2)).astype(np.float32)
    closed = walk.trace(
        lambda w_: run_hps(w_, hcfg, T=31, seed=0,
                           plan=ExecutionPlan(backend="xla", store="gap",
                                              policy="bf16")),
        w15)
    out += precision.find_fp32_scan_state(
        closed,
        {"N": 15, "d": 2, "T": 31, "E": int(np.asarray(hrt.src).shape[0])},
        where="hps[policy=bf16]")

    topo8 = make_hierarchy([8] * 8, topology="complete", seed=0)   # N = 64
    bmodel = make_confused_model(N=64, m=3, truth=0, confusion=0.0, seed=1)
    bcfg = ByzantineConfig(topo=topo8, F=2, byz=(2, 9), gamma_period=4,
                           attack=attacks.sign_flip())
    run = make_byzantine_scan(bmodel, bcfg, T=5, core="sparse",
                              backend="xla", store="final", policy="bf16")
    closed = walk.trace(run, jax.random.PRNGKey(0))
    out += precision.find_fp32_scan_state(
        closed, {"N": 64, "m": 3, "T": 5},
        where="byzantine[policy=bf16]")
    return out


# ---------------------------------------------------------------------------
# Verdict cache
# ---------------------------------------------------------------------------

def _source_fingerprint() -> str:
    import jax

    h = hashlib.sha256()
    h.update(jax.__version__.encode())
    src = _REPO_ROOT / "src" / "repro"
    for p in sorted(src.rglob("*.py")):
        h.update(str(p.relative_to(src)).encode())
        h.update(hashlib.sha256(p.read_bytes()).digest())
    results = _REPO_ROOT / "results"
    if results.is_dir():
        for p in sorted(results.glob("BENCH_*.json")):
            h.update(p.name.encode())
            h.update(hashlib.sha256(p.read_bytes()).digest())
    return h.hexdigest()


def _cache_path(cache_dir: str) -> Path:
    return Path(cache_dir) / "lint-verdict.json"


# ---------------------------------------------------------------------------
# Subcommands
# ---------------------------------------------------------------------------

def _cmd_lint(args) -> int:
    retrace.register_default_caches()
    engines = sorted(contracts.REGISTRY)

    key = _source_fingerprint()
    cache_file = _cache_path(args.cache_dir)
    if not args.no_cache and not args.inject_legacy_streams \
            and not args.inject_dense and cache_file.is_file():
        try:
            verdict = json.loads(cache_file.read_text())
        except (OSError, ValueError):
            verdict = {}
        if verdict.get("key") == key and verdict.get("ok"):
            print(f"lint: cached PASS for source fingerprint "
                  f"{key[:12]} ({cache_file})")
            return 0

    override = None
    if args.inject_legacy_streams:
        override = {args.inject_legacy_streams:
                    streams.LEGACY_BUGGY_STREAMS[args.inject_legacy_streams]}

    findings: list[Finding] = []
    findings += signatures.check_entrypoints()
    findings += _trace_findings(engines, inject_dense=args.inject_dense)
    findings += _stream_findings(engines, override)
    if not args.skip_exec:
        findings += _retrace_findings()
    findings += _precision_findings()
    findings += memory.validate_bench(_REPO_ROOT / "results")

    for f in findings:
        print(f, file=sys.stderr)
    n_targets = sum(len(_FIXTURES[e]()[1]) for e in engines) \
        * len(_TRACE_BACKENDS)
    if findings:
        print(f"lint: FAIL — {len(findings)} finding(s) over {n_targets} "
              "traced targets", file=sys.stderr)
        return 1

    print(f"lint: PASS — {n_targets} traced targets, "
          f"{len(engines)} engine contracts, 0 findings")
    if not args.no_cache and not args.inject_legacy_streams \
            and not args.inject_dense:
        cache_file.parent.mkdir(parents=True, exist_ok=True)
        cache_file.write_text(json.dumps(
            {"key": key, "ok": True, "targets": n_targets}))
    return 0


def _cmd_budget(args) -> int:
    from repro.analysis.memory_model import pushsum_device_memory_gb
    from repro.analysis.roofline import pushsum_halo_wire_bytes

    retrace.register_default_caches()
    print("analytic per-round step bytes and TPU-v5e roofline floors:")
    cases = [
        ("pushsum  N=1024 E=3102 d=1",
         memory.pushsum_step_bytes(1024, 3102, 1)),
        ("social   N=18 E=90 m=3", memory.social_step_bytes(18, 90, 3)),
        ("hps      N=15 E=62 d=2", memory.hps_step_bytes(15, 62, 2)),
        ("byz-sparse N=64 deg=8 m=3",
         memory.byz_sparse_step_bytes(64, 8, 3)),
        ("byz-DENSE  N=4096 m=3", memory.byz_dense_bytes(4096, 3)),
    ]
    for label, b in cases:
        floor = memory.step_floor(b)
        print(f"  {label:28s} {b / 1e6:10.3f} MB  "
              f"floor {floor['bound_step_time_s'] * 1e6:8.3f} us  "
              f"({floor['dominant']}-bound)")

    print("edge-partitioned per-DEVICE budgets (graph axis, halo psum "
          "on the collective term):")
    for Ns, Es, ds, Ss in ((1 << 20, 1 << 21, 1, 8),
                           (1 << 20, 1 << 21, 1, 1)):
        b = memory.pushsum_sharded_step_bytes(Ns, Es, d=ds, n_shards=Ss)
        wire = pushsum_halo_wire_bytes(Ns, ds, Ss)
        floor = memory.step_floor(b, wire_bytes=wire, n_devices=Ss)
        resid = pushsum_device_memory_gb(Ns, Es, d=ds, n_shards=Ss)
        label = f"pushsum-2d N={Ns} E={Es} d={ds} S={Ss}"
        print(f"  {label:38s} {b / 1e6:10.3f} MB/step  "
              f"halo {wire / 1e6:7.3f} MB  "
              f"floor {floor['bound_step_time_s'] * 1e6:8.3f} us  "
              f"({floor['dominant']}-bound)  "
              f"resident {resid['total_gb']} GB "
              f"fits_16gb={resid['fits_16gb']}")

    print("per-policy step budgets (storage dtype is the bandwidth knob; "
          "masks, PRNG draws, sort keys and ids stay fp32/int32, so bf16 "
          "lands near — not exactly at — half):")
    pol_cases = [
        ("pushsum    N=131072 E=524288 d=1",
         lambda p: memory.pushsum_step_bytes(131072, 524288, 1, policy=p)),
        ("pushsum-2d N=1048576 E=2097152 S=8",
         lambda p: memory.pushsum_sharded_step_bytes(
             1 << 20, 1 << 21, d=1, n_shards=8, policy=p)),
        ("social     N=16384 E=65536 m=3",
         lambda p: memory.social_step_bytes(16384, 65536, 3, policy=p)),
        ("hps        N=15 E=62 d=2",
         lambda p: memory.hps_step_bytes(15, 62, 2, policy=p)),
        ("byz-sparse N=64 deg=8 m=3",
         lambda p: memory.byz_sparse_step_bytes(64, 8, 3, policy=p)),
    ]
    for label, fn in pol_cases:
        f32, b16 = fn(None), fn("bf16")
        print(f"  {label:36s} fp32 {f32 / 1e6:10.3f} MB  "
              f"bf16 {b16 / 1e6:10.3f} MB  ratio {b16 / f32:.3f}")
    print("halo wire bytes per round per device (N=1048576 d=1 S=8), "
          "psum vs scatter+gather:")
    for sb, tag in ((4, "fp32"), (2, "bf16")):
        wp = pushsum_halo_wire_bytes(1 << 20, 1, 8)
        ws = pushsum_halo_wire_bytes(1 << 20, 1, 8, variant="scatter",
                                     storage_bytes=sb)
        print(f"  storage={tag}: psum {wp / 1e6:8.3f} MB  "
              f"scatter {ws / 1e6:8.3f} MB  ratio {ws / wp:.3f}")

    print("traced footprints:")
    for name in sorted(contracts.REGISTRY):
        dims, stores, make = _FIXTURES[name]()
        closed = make("xla", stores[0])
        fp = memory.jaxpr_footprint(closed, dims)
        print(f"  {name}: {fp['n_values']} values, peak "
              f"{fp['peak_value_bytes']} B, total {fp['total_bytes']} B")
        for line in fp["top"][:3]:
            print(f"    {line}")

    findings = memory.validate_bench(_REPO_ROOT / "results")
    for f in findings:
        print(f, file=sys.stderr)
    return 1 if findings else 0


def _cmd_list(args) -> int:
    retrace.register_default_caches()
    print("contracts:")
    for c in contracts.all_contracts():
        pats = {k: list(v) for k, v in c.forbidden.items()}
        print(f"  {c.name}: streams={[s.name for s in c.streams]}, "
              f"forbidden={pats}, shares_seed_with="
              f"{list(c.shares_seed_with)}, caches={list(c.caches)}")
    print("registered caches:")
    for name, size in retrace.snapshot().items():
        print(f"  {name}: {size} entries")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.statics",
        description="jaxpr static analysis for the fused engines",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    lint = sub.add_parser("lint", help="run every static check")
    lint.add_argument("--cache-dir", default=str(_REPO_ROOT / ".statics-cache"),
                      help="verdict-cache directory (CI persists this)")
    lint.add_argument("--no-cache", action="store_true",
                      help="ignore and do not write the verdict cache")
    lint.add_argument("--skip-exec", action="store_true",
                      help="skip the executed retrace-sentinel checks "
                           "(trace-only lint)")
    lint.add_argument("--inject-legacy-streams",
                      choices=sorted(streams.LEGACY_BUGGY_STREAMS),
                      help="TEST ONLY: check the named engine with its "
                           "historical buggy fold-in scheme")
    lint.add_argument("--inject-dense", action="store_true",
                      help="TEST ONLY: add a synthetic (N, N) intermediate "
                           "to the pushsum lint target")
    lint.set_defaults(fn=_cmd_lint)

    budget = sub.add_parser("budget", help="static memory/FLOP budgets")
    budget.set_defaults(fn=_cmd_budget)

    lst = sub.add_parser("list", help="show contracts and caches")
    lst.set_defaults(fn=_cmd_list)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
