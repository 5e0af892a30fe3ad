"""Algorithm 3 / Theorem 2 benchmarks: the fused social-learning engine.

Three claim families:
 * convergence — iterations to drive every agent's belief in theta* past
   0.9 for increasing drop probabilities (``social_conv_drop*`` rows; the
   paper's claim: convergence persists for any drop rate given B-window
   delivery, at a rate degraded through Theorem 1's gamma);
 * per-step cost of the fused engine at N in {1024, 16384} through the
   ``backend="xla"|"pallas"`` switch (``social_step_*`` rows) — runtimes
   are built dense-free via :func:`graphs.block_complete_edge_list`, so no
   (N, N) adjacency ever exists, and ``store="final"`` keeps the scan from
   materializing (T, N, m);
 * a (drop_prob x Gamma x seed) grid compiled ONCE as a single vmapped
   scan (``social_sweep_dropxgamma`` row;
   :func:`repro.core.sweeps.run_social_sweep`).

On CPU the Pallas rows run ``interpret=True`` equivalence mode (tagged
``mode=interpret``; the perf gate skips them) — the compiled comparison is
TPU-only, as with the push-sum and trim kernel rows.
"""
import time

import jax
import numpy as np

from repro.core.graphs import block_complete_edge_list, make_hierarchy
from repro.core.hps import HPSConfig
from repro.core.signals import make_confused_model
from repro.core.social import (
    run_social_learning,
    run_social_runtime,
    social_runtime_from_edge_list,
)
from repro.core.sweeps import run_social_sweep


def _conv_rows():
    out = []
    topo = make_hierarchy([6, 6, 6], topology="complete", seed=2)
    model = make_confused_model(N=topo.N, m=3, truth=1, confusion=0.5, seed=0)
    T = 700
    for drop in (0.0, 0.3, 0.6):
        cfg = HPSConfig(topo=topo, gamma_period=8, B=4, drop_prob=drop)
        t0 = time.perf_counter()
        res = run_social_learning(model, cfg, T=T, seed=0)
        b = np.asarray(res.beliefs)
        wall = (time.perf_counter() - t0) / T * 1e6
        hit = np.nonzero((b[:, :, 1] > 0.9).all(axis=1))[0]
        t_conv = int(hit[0]) if len(hit) else -1
        out.append((f"social_conv_drop{drop}", wall,
                    f"t_to_0.9={t_conv};final_min={b[-1,:,1].min():.3f}"))
    return out


def _step_setup(N):
    """N/8 complete 8-agent networks, built dense-free (no (N, N) array)."""
    el, rep_mask = block_complete_edge_list([8] * (N // 8))
    model = make_confused_model(N=N, m=3, truth=0, confusion=0.75, seed=1)
    rt = social_runtime_from_edge_list(
        el, rep_mask, drop_prob=0.1, gamma_period=8, B=4
    )
    return model, rt, N // 8


def _time_run(model, rt, M, T, backend, policy=None):
    from repro.core.plan import ExecutionPlan

    plan = ExecutionPlan(backend=backend, store="final", policy=policy)
    t0 = time.perf_counter()
    jax.block_until_ready(run_social_runtime(
        model, rt, M, T, seed=0, plan=plan,
    ).beliefs)
    compile_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(run_social_runtime(
        model, rt, M, T, seed=0, plan=plan,
    ).beliefs)
    return (time.perf_counter() - t0) / T * 1e6, compile_wall


def _bytes_per_step(model, rt, M, T, backend, policy=None) -> float:
    """Compiled per-step 'bytes accessed' of the fused engine — the number
    the precision policy halves (cost_analysis over an explicit jit of the
    same call)."""
    from repro.core.plan import ExecutionPlan

    fn = jax.jit(lambda rt_: run_social_runtime(
        model, rt_, M, T, seed=0,
        plan=ExecutionPlan(backend=backend, store="final", policy=policy),
    ).beliefs)
    cost = fn.lower(rt).compile().cost_analysis()
    return float(cost["bytes accessed"]) / T


def _step_rows(smoke: bool):
    """social_step_{xla,pallas}_N{...}: fused-engine per-step cost, plus a
    ``social_step_xla_bf16_N{...}`` row with the bf16 storage policy
    (:mod:`repro.core.precision`) — both xla rows record compiled
    bytes_per_step so the bandwidth claim rides the artifact."""
    out = []
    sizes = (1024,) if smoke else (1024, 16384)
    from repro.statics.memory import social_step_bytes

    for N in sizes:
        model, rt, M = _step_setup(N)
        E = int(rt.src.shape[0])
        xla_us, compile_s = _time_run(model, rt, M, T=30, backend="xla")
        bps = _bytes_per_step(model, rt, M, 30, "xla")
        budget = social_step_bytes(N, E, 3)
        out.append((
            f"social_step_xla_N{N}", xla_us,
            f"E={E};m=3;Gamma=8;drop=0.1;store=final;"
            f"bytes_per_step={bps:.0f};budget_bytes={budget};"
            f"compile_s={compile_s:.1f}",
        ))
        bf_us, bf_compile_s = _time_run(model, rt, M, T=30, backend="xla",
                                        policy="bf16")
        bf_bps = _bytes_per_step(model, rt, M, 30, "xla", policy="bf16")
        bf_budget = social_step_bytes(N, E, 3, policy="bf16")
        out.append((
            f"social_step_xla_bf16_N{N}", bf_us,
            f"E={E};m=3;Gamma=8;drop=0.1;store=final;policy=bf16;"
            f"bytes_per_step={bf_bps:.0f};budget_bytes={bf_budget};"
            f"budget_vs_fp32={bf_budget / budget:.3f};"
            f"compile_s={bf_compile_s:.1f}",
        ))
        mode = "interpret" if jax.default_backend() != "tpu" else "compiled"
        T_p = 4 if mode == "interpret" else 30
        pallas_us, compile_s = _time_run(model, rt, M, T=T_p,
                                         backend="pallas")
        out.append((
            f"social_step_pallas_N{N}", pallas_us,
            f"E={E};m=3;Gamma=8;drop=0.1;store=final;mode={mode};"
            f"compile_s={compile_s:.1f}",
        ))
    return out


def _sweep_row(smoke: bool):
    """drop_prob x Gamma x seed grid: one trace, one compiled program."""
    topo = make_hierarchy([6, 6, 6], topology="complete", seed=0)
    model = make_confused_model(N=topo.N, m=3, truth=1, confusion=0.5, seed=0)
    cfg = HPSConfig(topo=topo, gamma_period=8, B=4, drop_prob=0.0)
    drops = (0.0, 0.3) if smoke else (0.0, 0.3, 0.6, 0.9)
    gammas = (4, 16) if smoke else (4, 8, 16)
    seeds = list(range(2 if smoke else 4))
    T = 50 if smoke else 300

    def go():
        res = run_social_sweep(model, cfg, T, drop_probs=drops,
                               gammas=gammas, seeds=seeds)
        jax.block_until_ready(res.log_ratio)
        return res

    t0 = time.perf_counter()
    res = go()
    compile_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = go()
    wall = time.perf_counter() - t0
    final = np.asarray(res.beliefs)[:, :, model.truth]   # (K, N)
    return (
        f"social_sweep_dropxgamma{res.K}", wall / res.K * 1e6,
        f"scenarios={res.K};drops={len(drops)};gammas={len(gammas)};"
        f"seeds={len(seeds)};T={T};single_jit=true;"
        f"belief_min={final.min():.3f};compile_s={compile_wall:.1f}",
    )


def _churn_row(smoke: bool):
    """Churn-rate axis of the unified fault plane: per-round leave
    probabilities 0, 2%, 10% (rejoin at 30%) ride the sweep's fault
    dimension in ONE compiled program. The derived string records the
    worst final belief in theta* per churn rate — the paper's convergence
    claim degrading gracefully as agents leave and rejoin with stale
    state (churn=0 is the degenerate model, regression-tested equal to
    the fault-free engine in tests/test_faults.py)."""
    from repro.core.faults import make_fault_model

    topo = make_hierarchy([6, 6, 6], topology="complete", seed=0)
    model = make_confused_model(N=topo.N, m=3, truth=1, confusion=0.5,
                                seed=0)
    cfg = HPSConfig(topo=topo, gamma_period=8, B=4, drop_prob=0.3)
    churns = (0.0, 0.02, 0.1)
    faults = [make_fault_model(leave_prob=c, join_prob=0.3)
              for c in churns]
    T = 60 if smoke else 400
    seeds = list(range(2 if smoke else 4))

    def go():
        from repro.core.plan import ExecutionPlan

        res = run_social_sweep(model, cfg, T, seeds=seeds,
                               plan=ExecutionPlan(faults=faults))
        jax.block_until_ready(res.beliefs)
        return res

    t0 = time.perf_counter()
    res = go()
    compile_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = go()
    wall = time.perf_counter() - t0
    nf = len(faults)
    final = np.asarray(res.beliefs)[:, :, model.truth]   # (K, N)
    mins = [float(final[i::nf].min()) for i in range(nf)]
    tags = ";".join(f"belief_min_churn{c}={m:.3f}"
                    for c, m in zip(churns, mins))
    return (
        "social_conv_churn", wall / res.K * 1e6,
        f"scenarios={res.K};churns={','.join(map(str, churns))};"
        f"join=0.3;T={T};single_jit=true;{tags};"
        f"compile_s={compile_wall:.1f}",
    )


def _async_row(smoke: bool):
    """(wake-rate x staleness) grid of the async event-driven mode in ONE
    compiled program (the async axis rides the vmap scenario axis,
    minor-most), plus the ROADMAP acceptance comparison. The config
    removes the paper's forced B-window delivery (B >> T) so the raw
    delivery rate is what matters; with the confusion=0.5 model a network
    whose mixing falls behind its innovation accumulation locks into the
    WRONG hypothesis (log-ratio saturates at the fp32 belief floor,
    +87.3). At wake 0.6 the async engine keeps converging — asleep agents
    pause observation too, so the mixing/innovation ratio stays healthy
    and the stale buffers keep information flowing — while the
    synchronous engine run at the equivalent same-tick delivery rate
    (a staleness-0 rendezvous needs sender and receiver awake:
    ``p_sync = 1 - q*(1-p)*q = 0.676``) stalls. The derived string
    records the median final log-ratio per (wake, staleness) cell and
    the stalled sync reference."""
    from repro.core.asyncrony import make_async_model
    from repro.core.plan import ExecutionPlan

    topo = make_hierarchy([6, 6, 6], topology="complete", seed=0)
    model = make_confused_model(N=topo.N, m=3, truth=1, confusion=0.5,
                                seed=0)
    p = 0.1
    no_window = 1_000_000            # B >> T: no forced-delivery round
    cfg = HPSConfig(topo=topo, gamma_period=8, B=no_window, drop_prob=p)
    wakes = (1.0, 0.9, 0.6)
    stales = (0, 2, 8)
    grid = [(q, s) for q in wakes for s in stales]
    ams = [make_async_model(q, s) for q, s in grid]
    T = 80 if smoke else 600
    seeds = [0, 1] if smoke else [0, 1, 2, 3]

    def go():
        res = run_social_sweep(
            model, cfg, T, seeds=seeds,
            plan=ExecutionPlan(store="log_ratio", async_=ams))
        jax.block_until_ready(res.log_ratio)
        return res

    t0 = time.perf_counter()
    res = go()
    compile_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = go()
    wall = time.perf_counter() - t0

    na = len(ams)
    lr = np.asarray(res.log_ratio)          # (K, T), async minor-most
    med = [float(np.median(lr[a::na, -1])) for a in range(na)]
    tags = ";".join(f"lr_q{q}_s{s}={v:.2f}"
                    for (q, s), v in zip(grid, med))

    # the stall reference: sync at the q=0.6 cells' equivalent
    # same-tick delivery rate
    q = 0.6
    p_sync = 1.0 - q * (1.0 - p) * q
    sync = run_social_sweep(
        model, cfg, T, drop_probs=[p_sync], seeds=seeds,
        plan=ExecutionPlan(store="log_ratio"))
    sync_med = float(np.median(np.asarray(sync.log_ratio)[:, -1]))
    async_med = med[grid.index((0.6, 8))]

    return (
        f"social_async_wakexstale{res.K}", wall / res.K * 1e6,
        f"scenarios={res.K};wakes={','.join(map(str, wakes))};"
        f"stales={','.join(map(str, stales))};drop={p};B=no_window;"
        f"T={T};single_jit=true;{tags};"
        f"sync_equiv_drop={p_sync:.3f};lr_sync_equiv={sync_med:.2f};"
        f"async_beats_stalled_sync={async_med < 0.0 <= sync_med};"
        f"compile_s={compile_wall:.1f}",
    )


def rows(smoke: bool = False):
    out = [] if smoke else _conv_rows()
    out.extend(_step_rows(smoke))
    out.append(_sweep_row(smoke))
    out.append(_churn_row(smoke))
    out.append(_async_row(smoke))
    return out
