#!/usr/bin/env python3
"""Smoke run of the simulator's main path on a TPU — not a benchmark.

Drives each of the four engines once through its ``run_*`` entry point with
the default ``ExecutionPlan()`` (``backend="auto"``), at the sizes users of
a million-agent simulator call real, and checks the result:

* push-sum     ``run_pushsum_sweep``  N=131072, E~393k, d=4, K=8, T=64:
  the consensus error decays in every scenario, the mass gap is finite;
* HPS (Alg. 1) ``run_hps_runtime``    N=131072 (16384 complete 8-agent
  sub-networks), Gamma=8, drop 0.1, B=4, d=4, T=300: the Theorem-1 gap
  curve falls;
* social (Alg. 3) ``run_social_runtime`` N=131072, m=3, S=4, drop 0.3,
  T=3000: the truth's worst log-ratio goes negative;
* Byzantine (Alg. 2) ``run_byzantine_grid`` N=4096 (512 complete 8-agent
  networks), F=2, two ``large_value`` attackers: every normal agent
  decides the truth.

Every engine then runs the same call again with ``backend="xla"``; the two
runs' per-round readouts must agree to rtol = atol = 1e-5 for at least the
first 16 rounds (see :func:`engine_phase`). Where ``"auto"`` picked the
Pallas kernels, the module handed to the TPU compiler must contain a Mosaic
kernel (``tpu_custom_call``) — proof that no interpreter ran — and the XLA
run's modules must contain none.

    python chip_smoke.py               # one chip: the four engine phases
    python chip_smoke.py --four-chips  # four chips: the two mesh paths only

``--four-chips`` runs only the paths that exist across chips, each against
its single-device reference, bit for bit: the K=256 scenario sweep on
``sweep_mesh(4, 1)`` against the single-device vmap (consensus error and
final ratios; the mass-gap diagnostic is held to its own size), and one
N=1,048,576 scenario edge-partitioned over ``sweep_mesh(1, 4)`` against
the single-device emulation of the same cut.

Each phase prints one line (wall and compile seconds of each run, its
checks). The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, on any failed check and on any exception the script exits
non-zero and prints no such line (a failed check is reported at once; the
remaining phases still run so that one call shows every failure). It
starts no child process: the chip belongs to this one. The persistent
compile cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
else in ``<checkout>/.jax-cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
TOL = dict(rtol=1e-5, atol=1e-5)
AGREE_ROUNDS = 16

# the events whose durations add up to tracing, lowering and compiling
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Smoke:
    """State of one smoke run: its failed checks, the seconds spent
    compiling so far, and the directory the modules handed to the compiler
    are dumped into (cache hit or not)."""

    def __init__(self, dump_dir: str):
        self.dump_dir = dump_dir
        self.failed: list[str] = []
        self.compile_s = 0.0

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def check(self, ok, what: str) -> None:
        """Record a failed check; the script exits non-zero once the phases
        that can still run have run (exceptions are never caught)."""
        if not ok:
            print(f"chip_smoke: FAILED: {what}", flush=True)
            self.failed.append(what)

    def run(self, fn) -> "Run":
        return Run(self, fn)


class Run:
    """One timed call: its wall and compile seconds, and whether a module
    handed to the compiler during the call holds a Mosaic kernel."""

    def __init__(self, smoke: Smoke, fn):
        import jax

        before = set(os.listdir(smoke.dump_dir))
        c0 = smoke.compile_s
        t0 = time.perf_counter()
        self.out = jax.block_until_ready(fn())
        self.wall_s = time.perf_counter() - t0
        self.compile_s = smoke.compile_s - c0
        self.mosaic = False
        for name in sorted(set(os.listdir(smoke.dump_dir)) - before):
            with open(os.path.join(smoke.dump_dir, name)) as f:
                self.mosaic |= "tpu_custom_call" in f.read()

    def times(self) -> str:
        return f"wall_s={self.wall_s:.3f} compile_s={self.compile_s:.3f}"


def agreed_rounds(a: np.ndarray, x: np.ndarray) -> int:
    """Leading rounds (last axis) in which ``a`` and ``x`` agree to
    ``TOL`` everywhere."""
    a, x = a.astype(np.float64), x.astype(np.float64)
    ratio = np.abs(a - x) / (TOL["atol"] + TOL["rtol"] * np.abs(x))
    bad = np.nonzero((ratio.reshape(-1, a.shape[-1]) > 1).any(axis=0))[0]
    return int(bad[0]) if bad.size else a.shape[-1]


def max_abs(a: np.ndarray, x: np.ndarray) -> float:
    return float(np.max(np.abs(a.astype(np.float64) - x.astype(np.float64))))


# ---------------------------------------------------------------------------
# One-chip phases. Each returns (label, call(plan) -> result, base plan,
# curves(result) -> {name: per-round readout, rounds on the last axis},
# finals(result) -> {name: state at T}, checks(result) -> [(name, bool)]).
# ---------------------------------------------------------------------------

def pushsum_phase(n: int = 131072, d: int = 4, T: int = 64):
    from repro.core.graphs import random_strongly_connected_edge_list
    from repro.core.plan import ExecutionPlan
    from repro.core.sweeps import run_pushsum_sweep

    rng = np.random.default_rng(0)
    el = random_strongly_connected_edge_list(n, 2.0, rng)
    w = rng.normal(size=(n, d)).astype(np.float32)
    drops, seeds = [0.0, 0.2, 0.4, 0.6], [0, 1]

    def call(plan):
        return run_pushsum_sweep(w, el, T, drop_probs=drops, seeds=seeds,
                                 plan=plan)

    def curves(r):
        return {"err": np.asarray(r.err)}

    def finals(r):
        return {"final_ratio": np.asarray(r.final_ratio)}

    def checks(r):
        err = np.asarray(r.err)
        decays = int((err[:, -1] < err[:, 0]).sum())
        return [(f"err_decays={decays}/{err.shape[0]}",
                 decays == err.shape[0]),
                ("mass_gap_finite", bool(np.isfinite(
                    np.asarray(r.mass_gap)).all()))]

    label = (f"push-sum N={n} E={el.E} d={d} K={len(drops) * len(seeds)} "
             f"T={T}")
    return label, call, ExecutionPlan(), curves, finals, checks


def hps_phase(n: int = 131072, d: int = 4, T: int = 300):
    from repro.core.graphs import hier_edge_list
    from repro.core.hps import hps_runtime_from_edge_list, run_hps_runtime
    from repro.core.plan import ExecutionPlan

    el, rep_mask = hier_edge_list([8] * (n // 8), "complete")
    rt = hps_runtime_from_edge_list(el, rep_mask, drop_prob=0.1,
                                    gamma_period=8, B=4)
    w = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)

    def call(plan):
        return run_hps_runtime(w, rt, T, seed=0, plan=plan)

    def curves(r):
        return {"gap": np.asarray(r.gap)}

    def finals(r):
        return {"ratio": np.asarray(r.ratio)}

    def checks(r):
        # the curve falls across every third of the horizon
        gap = np.asarray(r.gap)[[0, T // 3, 2 * T // 3, T - 1]]
        return [("gap " + "->".join(f"{g:.3e}" for g in gap),
                 bool(np.isfinite(gap).all() and (np.diff(gap) < 0).all()))]

    label = f"hps N={n} E={el.E} M={n // 8} Gamma=8 drop=0.1 B=4 d={d} T={T}"
    return label, call, ExecutionPlan(store="gap"), curves, finals, checks


def social_phase(n: int = 131072, T: int = 3000):
    from repro.core.graphs import hier_edge_list
    from repro.core.plan import ExecutionPlan
    from repro.core.signals import make_confused_model
    from repro.core.social import (run_social_runtime,
                                   social_runtime_from_edge_list)

    el, rep_mask = hier_edge_list([8] * (n // 8), "complete")
    model = make_confused_model(N=n, m=3, S=4, truth=0, confusion=0.75,
                                seed=1)
    rt = social_runtime_from_edge_list(el, rep_mask, drop_prob=0.3,
                                       gamma_period=8, B=4)

    def call(plan):
        return run_social_runtime(model, rt, n // 8, T, seed=0, plan=plan)

    def curves(r):
        return {"log_ratio": np.asarray(r.log_ratio)}

    def finals(r):
        return {"beliefs": np.asarray(r.beliefs)}

    def checks(r):
        # the worst agent's log mu(theta)/mu(theta*) over the last quarter
        # of the horizon: it goes negative (an agent whose in-links all
        # dropped for a few rounds briefly follows its own signals, so
        # the max over 1e5 agents keeps spiking back up)
        lr = np.asarray(r.log_ratio)
        tail = lr[-(T // 4):]
        return [(f"worst_log_ratio {lr[0]:.3f}->min_last_quarter="
                 f"{tail.min():.3f} (negative in {(tail < 0).mean():.0%})",
                 bool(tail.min() < 0))]

    label = (f"social N={n} E={el.E} M={n // 8} m=3 S=4 Gamma=8 drop=0.3 "
             f"B=4 T={T}")
    return (label, call, ExecutionPlan(store="log_ratio"), curves, finals,
            checks)


def byzantine_phase(n: int = 4096, T: int = 300):
    from repro.core import attacks
    from repro.core.byzantine import ByzantineConfig
    from repro.core.graphs import make_hierarchy
    from repro.core.plan import ExecutionPlan
    from repro.core.signals import make_confused_model
    from repro.core.sweeps import run_byzantine_grid

    topo = make_hierarchy([8] * (n // 8), topology="complete", seed=0)
    model = make_confused_model(N=n, m=3, truth=0, confusion=0.0, seed=1)
    cfg = ByzantineConfig(topo=topo, F=2, byz=(2, 9), gamma_period=10,
                          attack=attacks.large_value())
    normal = ~cfg.byz_mask()

    def call(plan):
        return run_byzantine_grid(model, [cfg], T, seeds=[0], plan=plan)

    def curves(r):
        return {"decisions": np.moveaxis(np.asarray(r.decisions), 1, -1)}

    def finals(r):
        return {"r": np.asarray(r.r)}

    def checks(r):
        final = np.asarray(r.decisions)[0, -1]
        right = int((final[normal] == model.truth).sum())
        return [(f"normal_decide_truth={right}/{int(normal.sum())}",
                 right == int(normal.sum()))]

    label = f"byzantine N={n} M={n // 8} F=2 byz=(2,9) large_value T={T}"
    return (label, call, ExecutionPlan(store="decisions"), curves, finals,
            checks)


def engine_phase(smoke: Smoke, phase) -> None:
    """Run one engine under "auto" and "xla", compare, print one line.

    The backends must agree to ``TOL`` on every per-round readout for at
    least the first ``AGREE_ROUNDS`` rounds. Over the whole horizon they
    need not: the two lowerings sum each receiver's increments in a
    different order, and the robust relay forms z as the difference of
    cumulative counters that grow with t, so that rounding difference
    grows with the horizon (on the CPU interpreter, HPS at N=1024, T=300
    ends 4.8x outside ``TOL`` with the Pallas kernels of either layout).
    The line reports how many rounds agreed and the largest difference.
    """
    from repro.kernels.dispatch import resolve_backend

    label, call, plan, curves, finals, checks = phase
    auto = resolve_backend(plan.backend)
    a = smoke.run(lambda: call(plan))
    x = smoke.run(lambda: call(plan.replace(backend="xla")))
    ca, cx = curves(a.out), curves(x.out)
    agree = {k: (agreed_rounds(ca[k], cx[k]), ca[k].shape[-1],
                 max_abs(ca[k], cx[k])) for k in ca}
    fa, fx = finals(a.out), finals(x.out)
    results = checks(a.out)
    print(f"smoke {label} | auto->{auto} {a.times()} | xla {x.times()}"
          + (f" | tpu_custom_call auto={a.mosaic} xla={x.mosaic}"
             if auto == "pallas" else "")
          + " | auto_vs_xla " + " ".join(
              f"{k}:agree_rounds={n}/{T},max_abs={d:.3e}"
              for k, (n, T, d) in agree.items())
          + " " + " ".join(f"{k}:max_abs={max_abs(fa[k], fx[k]):.3e}"
                           for k in fa)
          + " | " + " ".join(name for name, _ in results), flush=True)
    for k, (n, T, _) in agree.items():
        smoke.check(n >= min(T, AGREE_ROUNDS),
                    f"{label}: auto vs xla {k} outside {TOL} from round {n}")
    for name, ok in results:
        smoke.check(ok, f"{label}: {name}")
    if auto == "pallas":
        smoke.check(a.mosaic, f"{label}: no Mosaic kernel in the auto run")
        smoke.check(not x.mosaic, f"{label}: Mosaic kernel in the xla run")


# ---------------------------------------------------------------------------
# Four-chip phases: the mesh paths, each against its single-device reference
# ---------------------------------------------------------------------------

def _same_bits(smoke: Smoke, a, b, label: str, fields) -> str:
    """Check ``fields`` of two sweep results bit for bit; describe them."""
    same = {k: np.array_equal(np.asarray(getattr(a, k)),
                              np.asarray(getattr(b, k))) for k in fields}
    for k, ok in same.items():
        smoke.check(ok, f"{label}: {k} differs from the single device")
    return " ".join(f"{k}:bit_identical={ok}" for k, ok in same.items())


def sweep_mesh_phase(smoke: Smoke, n: int = 16384, d: int = 4,
                     T: int = 64) -> None:
    import jax
    from repro.core.graphs import random_strongly_connected_edge_list
    from repro.core.plan import ExecutionPlan
    from repro.core.sweeps import run_pushsum_sweep
    from repro.distributed.sharding import sweep_mesh

    mesh = sweep_mesh(4, 1, devices=jax.devices()[:4])
    smoke.check(len({dev.id for dev in mesh.devices.flat}) == 4,
                "sweep mesh does not span four distinct chips")
    rng = np.random.default_rng(0)
    el = random_strongly_connected_edge_list(n, 2.0, rng)
    w = rng.normal(size=(n, d)).astype(np.float32)
    drops = [0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7]
    seeds = list(range(32))                      # K = 8 * 32 = 256

    def call(plan):
        return run_pushsum_sweep(w, el, T, drop_probs=drops, seeds=seeds,
                                 plan=plan)

    single = smoke.run(lambda: call(ExecutionPlan()))
    sharded = smoke.run(lambda: call(ExecutionPlan(mesh=mesh)))
    label = f"sweep-mesh N={n} E={el.E} d={d} K={sharded.out.K} T={T} mesh=4x1"
    same = _same_bits(smoke, sharded.out, single.out, label,
                      ("err", "final_ratio"))
    # the mass gap is itself rounding noise: a sum over N whose reduction
    # order XLA picks per batch shape (64 scenarios per chip against 256
    # on one), so it is held to its own scale, not to the bit
    gap_m = np.asarray(sharded.out.mass_gap)
    gap_s = np.asarray(single.out.mass_gap)
    gap_d = max_abs(gap_m, gap_s)
    scale = float(np.abs(gap_s).max())
    smoke.check(gap_d <= scale, f"{label}: mass_gap differs by {gap_d:.3e}, "
                f"more than its own size {scale:.3e}")
    err = np.asarray(sharded.out.err)
    print(f"smoke {label} | single-device {single.times()}"
          f" | mesh {sharded.times()} | {same}"
          f" mass_gap:max_abs_diff={gap_d:.3e},max={scale:.3e}"
          f" | err_final_max={err[:, -1].max():.3e}", flush=True)


def edge_mesh_phase(smoke: Smoke, n: int = 1 << 20, d: int = 1,
                    T: int = 16) -> None:
    import jax
    from jax.sharding import NamedSharding
    from repro.core.graphs import (partition_edge_list,
                                   random_strongly_connected_edge_list)
    from repro.core.plan import ExecutionPlan
    from repro.core.sweeps import run_pushsum_sweep
    from repro.distributed.sharding import sweep_mesh, sweep_specs

    S = 4
    mesh = sweep_mesh(1, S, devices=jax.devices()[:S])
    smoke.check(len({dev.id for dev in mesh.devices.flat}) == S,
                "edge mesh does not span four distinct chips")
    rng = np.random.default_rng(0)
    el = random_strongly_connected_edge_list(n, 1.0, rng)
    w = rng.normal(size=(n, d)).astype(np.float32)
    shards = partition_edge_list(el, S)
    # the engine's layout of the (K, S, E_shard) edge arrays: each device
    # must own exactly one edge shard, and no two devices the same one
    layout = NamedSharding(mesh, sweep_specs()["edge_shards"])
    owned = layout.devices_indices_map((1, S, shards.e_shard))
    shard_of = {dev.id: idx[1].indices(S) for dev, idx in owned.items()}
    starts = sorted(start for start, _, _ in shard_of.values())
    smoke.check(starts == list(range(S)) and all(
        stop - start == 1 for start, stop, _ in shard_of.values()),
        f"edge shards are not one per device: {shard_of}")

    def call(plan):
        return run_pushsum_sweep(w, shards, T, drop_probs=[0.2], seeds=[0],
                                 plan=plan)

    emul = smoke.run(lambda: call(ExecutionPlan(graph_shards=S)))
    meshed = smoke.run(
        lambda: call(ExecutionPlan(mesh=mesh, graph_shards=S)))
    label = (f"edge-mesh N={n} E={el.E} d={d} T={T} shards={S} mesh=1x{S} "
             f"e_shard={shards.e_shard}")
    same = _same_bits(smoke, meshed.out, emul.out, label,
                      ("err", "final_ratio", "mass_gap"))
    err = np.asarray(meshed.out.err)
    gap = float(np.abs(np.asarray(meshed.out.mass_gap)).max())
    print(f"smoke {label} | single-device emulation {emul.times()}"
          f" | mesh {meshed.times()} | {same}"
          f" | err {err[0, 0]:.3e}->{err[0, -1]:.3e} | mass_gap={gap:.3e}",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the two mesh comparisons, on four chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"chip_smoke: platform={dev.platform} device_kind={dev.device_kind}"
          f" count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX found {dev.platform}")
    if args.four_chips and len(devices) < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 chips, found "
                         f"{len(devices)}")

    sys.path.insert(0, os.path.join(REPO, "src"))
    sys.path.insert(0, REPO)
    from benchmarks import compile_cache

    print(f"chip_smoke: smoke run, not a benchmark (each time includes "
          f"compilation); compile cache {compile_cache.enable()}", flush=True)
    with tempfile.TemporaryDirectory() as dump_dir:
        smoke = Smoke(dump_dir)
        jax.monitoring.register_event_duration_secs_listener(
            smoke.on_duration)
        # every module handed to the compiler is dumped here, cache hit or
        # not, so each phase can see whether a Mosaic kernel is in it
        jax.config.update("jax_dump_ir_to", dump_dir)
        if args.four_chips:
            sweep_mesh_phase(smoke)
            edge_mesh_phase(smoke)
        else:
            for make in (pushsum_phase, hps_phase, social_phase,
                         byzantine_phase):
                engine_phase(smoke, make())
        jax.config.update("jax_dump_ir_to", "")
    if smoke.failed:
        raise SystemExit(f"chip_smoke: {len(smoke.failed)} check(s) failed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
