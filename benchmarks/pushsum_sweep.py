"""Sparse-core + fused-kernel + sharded-sweep benchmark.

Claims pinned:
 * the edge-list core runs N up to 131072 agents on sparse digraphs built
   directly as edge lists (``graphs.random_strongly_connected_edge_list``)
   without ever allocating an (N, N) or (N, N, d) array;
 * the per-round delivery/integration runs through the
   ``backend="xla"|"pallas"`` switch — per-step microseconds are recorded
   for both at N in {1024, 16384, 131072} (on CPU the Pallas path is
   ``interpret=True`` equivalence mode, not a fast path; the compiled
   comparison is TPU-only);
 * a >= 256-scenario grid (topology draws x drop probs x seeds) runs as ONE
   program, vmapped on a single device AND shard_map-sharded over a
   multi-device ``data`` mesh axis (`repro.core.sweeps.run_pushsum_sweep`),
   with identical results;
 * the edge-partitioned 2-D (data x graph) mesh mode
   (``graph_shards=``) runs a SINGLE N >= 1e6 scenario by cutting the
   edge list itself into per-device dst-contiguous shards and psum-ing
   boundary partials over the ``graph`` axis — per-step walls recorded,
   bit-identical to the single-device vmap emulation of the same cut;
 * consensus error decays in every scenario (Theorem 1 across the grid).

Emits name,us_per_call,derived rows via :func:`rows`; ``rows(smoke=True)``
is the fast CI subset (small N, no subprocess). The machine-readable
``BENCH_pushsum_sweep.json`` perf-trajectory artifact is merge-updated in
``results/`` when run standalone (``python -m benchmarks.pushsum_sweep``);
under ``benchmarks/run.py`` the ``--json-dir`` flag is the single writer.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np

from repro.core.graphs import (
    edge_list,
    random_strongly_connected,
    random_strongly_connected_edge_list,
    sort_by_dst,
    stack_edge_lists,
)
from repro.core.pushsum import run_pushsum_sparse, sparse_mass_invariant
from repro.core.sweeps import run_pushsum_sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(REPO, "results")
JSON_PATH = os.path.join(RESULTS_DIR, "BENCH_pushsum_sweep.json")


def _bench_large_sparse(n=1024, d=8, T=64, extra_edge_prob=0.002, seed=0):
    """N=1024 agents, E << N^2, single run of the edge-list core."""
    rng = np.random.default_rng(seed)
    adj = random_strongly_connected(n, extra_edge_prob, rng)
    el = edge_list(adj)
    w = rng.normal(size=(n, d)).astype(np.float32)

    # jit once so the steady-state timing measures execution, not retrace
    run = jax.jit(lambda w_, src_, dst_: run_pushsum_sparse(
        w_, src_, dst_, T, drop_prob=0.2, B=4, record_every=T
    ))

    def go():
        final, traj = run(w, el.src, el.dst)
        jax.block_until_ready(final)
        return final, np.asarray(traj[-1])   # one frame: round T-1

    t0 = time.perf_counter()
    final, last = go()                       # trace + compile + run
    compile_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    final, last = go()                       # steady state (compiled)
    wall_us = (time.perf_counter() - t0) / T * 1e6
    err = float(np.abs(last - w.mean(0)).max())
    gap = float(np.abs(np.asarray(
        sparse_mass_invariant(final, el.src, el.valid)) - w.sum(0)).max())
    return {
        "name": f"pushsum_sparse_N{n}",
        "us_per_call": wall_us,
        "derived": f"E={el.E};E_over_N2={el.E / n**2:.4f};"
                   f"err_T{T}={err:.2e};mass_gap={gap:.1e};"
                   f"compile_s={compile_wall:.1f}",
    }


def _bytes_per_call(lowered, calls: int) -> float:
    """Per-call 'bytes accessed' from the compiled executable's
    cost_analysis."""
    return float(lowered.compile().cost_analysis()["bytes accessed"]) / calls


def _run_emulated_mesh(prog: str, devices: int) -> dict:
    """Run ``prog`` in a child pinned to the CPU with ``devices`` forced
    host devices and return the JSON object it prints last.

    The child emulates a mesh: a parent that has touched JAX holds the
    accelerator, so a child could not reach it anyway (the on-chip mesh
    paths run in-process in ``chip_smoke.py --four-chips``). A child that
    fails or times out fails the run.
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=900, env=env, cwd=REPO)
    if out.returncode:
        raise RuntimeError(
            f"emulated-mesh child failed (rc={out.returncode}):\n"
            f"{out.stderr[-2000:]}")
    return json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    )


def _bench_step_backend(n, backend, d=4, extra=2.0, seed=0, T=None,
                        policy=None):
    """Per-step cost of one backend at scale N (dst-sorted edge index).

    The graph is built directly as a sparse edge list — at N=131072 the
    dense adjacency alone would be 17 GB. On CPU the Pallas backend runs
    ``interpret=True`` (the equivalence mode CI tests), so its numbers
    measure the interpreter, not the kernel; on TPU the same call compiles.

    ``policy`` ("bf16") switches the scan-carried state to the reduced
    storage dtype (:mod:`repro.core.precision`) and suffixes the row name;
    every row also records the compiled program's per-step traffic
    (``bytes_per_step``, from ``cost_analysis`` — on CPU this includes the
    fp32 in-body accumulator transients XLA would fuse away on the TPU
    target) and the analytic persistent-state budget (``budget_bytes``,
    :func:`repro.statics.memory.pushsum_step_bytes` at the policy's
    storage width) so the storage-bandwidth claim is checked on the
    artifact against the same model ``repro.statics budget`` proves.
    """
    from repro.statics.memory import pushsum_step_bytes
    rng = np.random.default_rng(seed)
    el = random_strongly_connected_edge_list(n, extra, rng)   # sorted by dst
    w = rng.normal(size=(n, d)).astype(np.float32)
    if T is None:   # interpret-mode pallas steps are expensive on CPU
        T = 16 if backend == "xla" else 2

    run = jax.jit(lambda w_, src_, dst_: run_pushsum_sparse(
        w_, src_, dst_, T, drop_prob=0.2, B=4, record_every=T,
        backend=backend, policy=policy, dst_sorted=True,
    ))

    def go():
        final, _ = run(w, el.src, el.dst)
        jax.block_until_ready(final)
        return final

    t0 = time.perf_counter()
    final = go()
    compile_wall = time.perf_counter() - t0
    bytes_step = _bytes_per_call(run.lower(w, el.src, el.dst), T)
    t0 = time.perf_counter()
    final = go()
    step_us = (time.perf_counter() - t0) / T * 1e6
    gap = float(np.abs(np.asarray(
        sparse_mass_invariant(final, el.src, el.valid)) - w.sum(0)).max())
    mode = ("interpret" if backend == "pallas"
            and jax.default_backend() != "tpu" else "compiled")
    tag = "" if policy is None else f"_{policy}"
    pol = "" if policy is None else f"policy={policy};"
    budget = pushsum_step_bytes(n, int(el.E), d=d, policy=policy)
    return {
        "name": f"pushsum_step_{backend}{tag}_N{n}",
        "us_per_call": step_us,
        "derived": f"E={el.E};d={d};T={T};backend={backend};mode={mode};"
                   f"{pol}bytes_per_step={bytes_step:.0f};"
                   f"budget_bytes={budget};"
                   f"mass_gap={gap:.1e};compile_s={compile_wall:.1f}",
    }


def _bench_sweep(n=256, d=4, T=300, n_graphs=2, seed=0):
    """>= 32-scenario grid in one jitted vmapped scan."""
    rng = np.random.default_rng(seed)
    adjs = [random_strongly_connected(n, 0.02, rng) for _ in range(n_graphs)]
    el, _, _ = sort_by_dst(stack_edge_lists(adjs))
    w = rng.normal(size=(n, d)).astype(np.float32)
    drop_probs = [0.0, 0.3, 0.6, 0.9]
    seeds = [0, 1, 2, 3]

    t0 = time.perf_counter()
    res = run_pushsum_sweep(w, el, T, drop_probs=drop_probs, seeds=seeds, B=4)
    res.err.block_until_ready()
    compile_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = run_pushsum_sweep(w, el, T, drop_probs=drop_probs, seeds=seeds, B=4)
    res.err.block_until_ready()
    wall = time.perf_counter() - t0

    err = np.asarray(res.err)
    K = res.K
    assert K >= 32, K
    # every scenario either decays from its round-20 level or already sits
    # at the fp32 noise floor (drop=0 scenarios converge before round 20)
    decayed = bool((err[:, -1] <= np.maximum(err[:, 20], 1e-4)).all())
    return {
        "name": f"pushsum_sweep_vmap{K}",
        "us_per_call": wall / K * 1e6,       # per-scenario cost
        "derived": f"scenarios={K};single_jit=true;T={T};"
                   f"err_final_max={err[:, -1].max():.2e};"
                   f"all_decay={decayed};wall_s={wall:.2f};"
                   f"compile_s={compile_wall:.1f}",
        "scenarios": K,
        "single_jit": True,
    }


def _bench_sharded_sweep(n=128, d=3, T=100, devices=4, seed=0):
    """K=256 scenarios in ONE call: single-device vmap vs mesh-sharded.

    Runs in a child pinned to the CPU with ``devices`` forced host devices
    (:func:`_run_emulated_mesh`), so the row emulates the mesh
    (``emulated=cpu``): the walls pin the single-program/sharded
    semantics, not a speedup (the devices share the host's cores).
    """
    prog = textwrap.dedent(f"""
        import json, time
        import numpy as np
        import jax
        from repro.core.graphs import (
            random_strongly_connected, sort_by_dst, stack_edge_lists)
        from repro.core.sweeps import run_pushsum_sweep
        from repro.distributed.sharding import sweep_mesh

        rng = np.random.default_rng({seed})
        adjs = [random_strongly_connected({n}, 0.03, rng) for _ in range(2)]
        el, _, _ = sort_by_dst(stack_edge_lists(adjs))
        w = rng.normal(size=({n}, {d})).astype(np.float32)
        drops = [0.0, 0.2, 0.4, 0.6, 0.7, 0.8, 0.85, 0.9]
        seeds = list(range(16))          # K = 2 * 8 * 16 = 256

        def timed(**kw):
            t0 = time.perf_counter()
            r = run_pushsum_sweep(w, el, {T}, drop_probs=drops, seeds=seeds,
                                  B=4, **kw)
            r.err.block_until_ready()
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            r = run_pushsum_sweep(w, el, {T}, drop_probs=drops, seeds=seeds,
                                  B=4, **kw)
            r.err.block_until_ready()
            return r, time.perf_counter() - t0, compile_s

        r1, single_s, c1 = timed()
        r2, sharded_s, c2 = timed(mesh=sweep_mesh({devices}, 1))
        err = np.abs(np.asarray(r2.err) - np.asarray(r1.err)).max()
        final = np.asarray(r2.err)[:, -1]
        print(json.dumps({{
            "K": int(r2.K), "single_s": single_s, "sharded_s": sharded_s,
            "compile_single_s": c1, "compile_sharded_s": c2,
            "shard_vs_vmap_err": float(err),
            "err_final_max": float(final.max()),
        }}))
    """)
    res = _run_emulated_mesh(prog, devices)
    return {
        "name": f"pushsum_sweep_sharded{res['K']}",
        "us_per_call": res["sharded_s"] / res["K"] * 1e6,
        "derived": f"scenarios={res['K']};devices={devices};"
                   f"emulated=cpu;single_jit=true;"
                   f"sharded_wall_s={res['sharded_s']:.2f};"
                   f"single_dev_wall_s={res['single_s']:.2f};"
                   f"shard_vs_vmap_err={res['shard_vs_vmap_err']:.1e};"
                   f"err_final_max={res['err_final_max']:.2e};"
                   f"compile_s={res['compile_sharded_s']:.1f}",
        "scenarios": res["K"],
        "single_jit": True,
    }


def _bench_edge_sharded(n=1 << 20, d=1, T=4, devices=8, extra=1.0, seed=0,
                        policy=None, halo="psum"):
    """ONE million-agent scenario on the 2-D (data x graph) mesh.

    The graph (E ~ 2e6 edges) is cut into ``devices`` dst-contiguous edge
    shards (`graphs.partition_edge_list`); each forced CPU device runs the
    unchanged per-shard step and boundary-node receiver partials are
    psum'd over the mesh ``graph`` axis. Same CPU-pinned child as
    :func:`_bench_sharded_sweep` (``emulated=cpu``).
    The subprocess also pins the bit-identity contract at small N: the
    shard_map mesh run must match the single-device
    ``vmap(axis_name=)`` emulation of the same cut EXACTLY (same psum
    order on every device — see sweeps.run_pushsum_sweep's docstring).
    Fake devices share one CPU, so the wall pins semantics + per-device
    memory shape, not a speedup.

    ``policy``/``halo`` thread the storage dtype and the halo-collective
    lowering through (``policy="bf16", halo="scatter"`` is the
    bandwidth-optimized configuration: bf16 state + reduce-scatter/
    all-gather halo whose re-broadcast leg rides the storage dtype).
    """
    prog = textwrap.dedent(f"""
        import json, time
        import numpy as np
        import jax
        from repro.core.graphs import random_strongly_connected_edge_list
        from repro.core.sweeps import run_pushsum_sweep
        from repro.distributed.sharding import sweep_mesh

        mesh = sweep_mesh(1, {devices})      # (data=1, graph={devices})
        pol = dict(policy={policy!r}, halo={halo!r})

        # small-N identity: 2-D mesh shard_map vs single-device emulation
        rng = np.random.default_rng({seed})
        el_s = random_strongly_connected_edge_list(256, 2.0, rng)
        w_s = rng.normal(size=(256, {d})).astype(np.float32)
        kw = dict(drop_probs=[0.0, 0.3], seeds=[0, 1], B=4,
                  graph_shards={devices}, **pol)
        r_emu = run_pushsum_sweep(w_s, el_s, 30, **kw)
        r_mesh = run_pushsum_sweep(w_s, el_s, 30, mesh=mesh, **kw)
        ident = float(np.abs(
            np.asarray(r_mesh.err) - np.asarray(r_emu.err)).max())

        # the N >= 1e6 scenario
        rng = np.random.default_rng({seed})
        el = random_strongly_connected_edge_list({n}, {extra}, rng)
        w = rng.normal(size=({n}, {d})).astype(np.float32)

        def once():
            t0 = time.perf_counter()
            r = run_pushsum_sweep(w, el, {T}, drop_probs=[0.2], seeds=[0],
                                  B=4, mesh=mesh, graph_shards={devices},
                                  **pol)
            r.err.block_until_ready()
            return r, time.perf_counter() - t0

        r, compile_s = once()                # trace + compile + run
        r, wall = once()                     # steady state
        err = np.asarray(r.err)
        gap = float(np.abs(np.asarray(r.mass_gap)).max())
        print(json.dumps({{
            "E": int(el.E), "wall_s": wall, "compile_s": compile_s,
            "err_final": float(err[:, -1].max()),
            "mass_gap": gap,
            "mesh_vs_emul_err": ident,
        }}))
    """)
    res = _run_emulated_mesh(prog, devices)
    tag = "" if policy is None else f"_{policy}"
    from repro.analysis.roofline import pushsum_halo_wire_bytes
    from repro.core.precision import resolve_policy
    from repro.statics.memory import pushsum_sharded_step_bytes

    budget = pushsum_sharded_step_bytes(n, res["E"], d=d, n_shards=devices,
                                        policy=policy)
    sb = 4 if policy is None else resolve_policy(policy).storage_bytes
    wire = pushsum_halo_wire_bytes(n, d, devices, variant=halo,
                                   storage_bytes=sb)
    pol = "" if policy is None else f"policy={policy};halo={halo};"
    return {
        "name": f"pushsum_edge_sharded{tag}_N{n}",
        "us_per_call": res["wall_s"] / T * 1e6,   # per-step cost
        "derived": f"E={res['E']};shards={devices};d={d};T={T};"
                   f"devices={devices};mesh=1x{devices};"
                   f"emulated=cpu;{pol}"
                   f"budget_bytes={budget};halo_wire_bytes={wire:.0f};"
                   f"mesh_vs_emul_err={res['mesh_vs_emul_err']:.1e};"
                   f"err_final={res['err_final']:.2e};"
                   f"mass_gap={res['mass_gap']:.1e};"
                   f"compile_s={res['compile_s']:.1f}",
    }


def _bench_edge_sharded_smoke(n=256, d=2, T=50, seed=0,
                              policy=None, halo="psum"):
    """In-process 2-shard smoke of the edge-partitioned mode.

    Only meaningful when the HOST exposes >= 2 devices (the multidevice CI
    lane forces 8 fake CPU devices); a single-device host emits an explicit
    ``skipped=`` row — kept in the JSON artifact as ``us_per_call: null``
    and announced by run.py --check as ``# SKIP`` — instead of silently
    measuring nothing or crashing on mesh construction. ``policy``/``halo``
    select the storage policy and halo collective, like the full-size
    sharded bench — the bf16+scatter smoke row is what the multidevice CI
    lane asserts on (mesh == emulation must hold bit-exactly under the
    reduced-precision state too).
    """
    n_dev = jax.device_count()
    tag = "" if policy is None else f"_{policy}"
    name = f"pushsum_edge_smoke{tag}_N{n}"
    if n_dev < 2:
        return {
            "name": name,
            "us_per_call": float("nan"),
            "derived": f"skipped=single_device_host;devices={n_dev}",
        }
    from repro.distributed.sharding import sweep_mesh

    S = 2
    rng = np.random.default_rng(seed)
    el = random_strongly_connected_edge_list(n, 2.0, rng)
    w = rng.normal(size=(n, d)).astype(np.float32)
    mesh = sweep_mesh(1, S, devices=jax.devices()[:S])
    kw = dict(drop_probs=[0.0, 0.4], seeds=[0, 1], B=4, graph_shards=S,
              policy=policy, halo=halo)
    r_emu = run_pushsum_sweep(w, el, T, **kw)
    t0 = time.perf_counter()
    r_mesh = run_pushsum_sweep(w, el, T, mesh=mesh, **kw)
    r_mesh.err.block_until_ready()
    compile_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    r_mesh = run_pushsum_sweep(w, el, T, mesh=mesh, **kw)
    r_mesh.err.block_until_ready()
    step_us = (time.perf_counter() - t0) / T * 1e6
    ident = float(np.abs(
        np.asarray(r_mesh.err) - np.asarray(r_emu.err)).max())
    pol_tag = "" if policy is None else f"policy={policy};halo={halo};"
    return {
        "name": name,
        "us_per_call": step_us,
        "derived": f"E={el.E};shards={S};d={d};T={T};devices={n_dev};"
                   f"{pol_tag}"
                   f"mesh_vs_emul_err={ident:.1e};"
                   f"err_final={np.asarray(r_mesh.err)[:, -1].max():.2e};"
                   f"compile_s={compile_wall:.1f}",
    }


def _bench_burst_sweep(smoke: bool = False):
    """Burst-length axis of the unified fault plane: a Gilbert-Elliott
    ladder (mean burst 1, 4, 16 rounds at a fixed 30% stationary bad
    fraction) rides the sweep's fault dimension — one compiled program,
    fault realizations crossed fault-minor against (drop x seed). The
    derived string records the final consensus error per burst length
    next to the degenerate (no-fault) reference rows, which must match
    the plain sweep (regression-tested in tests/test_faults.py)."""
    from repro.core.faults import gilbert_elliott_model, make_fault_model

    n, d, T = (256, 3, 120) if smoke else (1024, 4, 300)
    bursts = (1, 4, 16)
    rng = np.random.default_rng(0)
    el = random_strongly_connected_edge_list(n, 2.0, rng)
    w = rng.normal(size=(n, d)).astype(np.float32)
    faults = [make_fault_model()] + [
        gilbert_elliott_model(float(L), 0.3) for L in bursts]
    nf = len(faults)
    kw = dict(drop_probs=[0.1, 0.4], seeds=[0, 1], B=4, faults=faults)

    def go():
        res = run_pushsum_sweep(w, el, T, **kw)
        jax.block_until_ready(res.err)
        return res

    t0 = time.perf_counter()
    res = go()
    compile_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = go()
    wall = time.perf_counter() - t0
    k = res.err.shape[0]
    final = np.asarray(res.err)[:, -1]
    per_fault = [float(final[i::nf].max()) for i in range(nf)]
    tags = ";".join(f"err_L{L}={e:.2e}"
                    for L, e in zip((0,) + bursts, per_fault))
    return {
        "name": "pushsum_sweep_burst",
        "us_per_call": wall / k * 1e6,
        "derived": f"E={el.E};scenarios={k};T={T};bad_frac=0.3;"
                   f"bursts=0,{','.join(map(str, bursts))};{tags};"
                   f"compile_s={compile_wall:.1f}",
    }


def rows(smoke: bool = False):
    if smoke:
        recs = [
            _bench_large_sparse(),
            _bench_step_backend(1024, "xla"),
            _bench_step_backend(1024, "xla", policy="bf16"),
            _bench_step_backend(1024, "pallas"),
            _bench_edge_sharded_smoke(),
            _bench_edge_sharded_smoke(policy="bf16", halo="scatter"),
            _bench_burst_sweep(smoke=True),
        ]
    else:
        recs = [_bench_large_sparse()]
        for n in (1024, 16384, 131072):
            recs.append(_bench_step_backend(n, "xla"))
            recs.append(_bench_step_backend(n, "pallas"))
        recs.append(_bench_step_backend(131072, "xla", policy="bf16"))
        recs.append(_bench_sweep())
        recs.append(_bench_sharded_sweep())
        recs.append(_bench_edge_sharded())
        recs.append(_bench_edge_sharded(policy="bf16", halo="scatter"))
        recs.append(_bench_burst_sweep())
    return [(r["name"], r["us_per_call"], r["derived"]) for r in recs]


if __name__ == "__main__":
    # standalone run merge-updates the BENCH json itself; under
    # benchmarks/run.py the --json-dir flag is the single writer.
    out = rows(smoke="--smoke" in sys.argv)
    print("name,us_per_call,derived")
    for name, us, derived in out:
        print(f"{name},{us:.1f},{derived}")
    from benchmarks import merge_bench_json

    os.makedirs(RESULTS_DIR, exist_ok=True)
    merge_bench_json(JSON_PATH, out)
    print(f"# wrote {os.path.normpath(JSON_PATH)}")
