"""The Chord overlay builder, the partitioner's sorted fast path, and the
per-shard run-end finish of the edge-partitioned push-sum.

Covers: ``chord_edge_list`` (degrees, order, no duplicate or self-loop);
``sort_by_dst`` and ``partition_edge_list`` on sorted and unsorted inputs
against the loop partitioner they replace; each shard's run ends and their
windows; the run-end gather finish of every shard equal to the scatter
finish to the bit, under the ``vmap(axis_name=)`` emulation and on a
four-device CPU mesh (in a child interpreter), where the mesh path also
equals the emulation to the bit; the sweep's phase scopes and host spans;
and the device placement an ``EdgeShards`` keeps across calls (hit, miss on
a replaced graph or another K, freed with the instance, read-only arrays),
under the emulation and on the four-device mesh.
"""
import dataclasses
import gc
import json
import os
import re
import subprocess
import sys
import textwrap
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pushsum, sweeps
from repro.core.graphs import (
    chord_edge_list,
    is_dst_sorted,
    is_strongly_connected,
    partition_edge_list,
    random_strongly_connected_edge_list,
    sort_by_dst,
    stack_edge_lists,
)
from repro.core.plan import ExecutionPlan
from repro.core.sweeps import run_pushsum_sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- the builder -------------------------------------------------------------

@pytest.mark.parametrize("n_bits, fingers", [(3, None), (6, 3), (8, 8),
                                             (10, 5)])
def test_chord_edges_degrees_and_order(n_bits, fingers):
    el = chord_edge_list(n_bits, fingers)
    N, L = 2 ** n_bits, fingers or n_bits
    assert el.n == N and el.E == N * L and el.valid.all()
    assert el.src.dtype == el.dst.dtype == np.int32
    assert is_dst_sorted(el.dst)
    np.testing.assert_array_equal(el.dst, np.repeat(np.arange(N), L))
    v, i = el.dst.astype(np.int64), np.tile(np.arange(L), N)
    np.testing.assert_array_equal(el.src, (v - 2 ** i) % N)
    np.testing.assert_array_equal(np.bincount(el.src, minlength=N), L)
    np.testing.assert_array_equal(np.bincount(el.dst, minlength=N), L)
    assert not (el.src == el.dst).any()
    pairs = el.src.astype(np.int64) * N + el.dst
    assert np.unique(pairs).size == el.E


def test_chord_is_strongly_connected_and_checks_fingers():
    assert is_strongly_connected(chord_edge_list(5, 1).to_dense())
    for bad in (0, 6):
        with pytest.raises(ValueError, match="fingers"):
            chord_edge_list(5, bad)


# --- the partitioner's sorted fast path --------------------------------------

def _partition_by_loop(el, n_shards):
    """The partitioner before the fast path: argsort, then one copy per
    shard."""
    perm = np.argsort(el.dst, kind="stable")
    src, dst, valid = el.src[perm], el.dst[perm], el.valid[perm]
    E = dst.size
    e_shard = max(-(-E // n_shards), 1)
    src_s = np.zeros((n_shards, e_shard), np.int32)
    dst_s = np.zeros((n_shards, e_shard), np.int32)
    val_s = np.zeros((n_shards, e_shard), bool)
    bnd = np.zeros(el.n, bool)
    for k in range(n_shards):
        lo, hi = k * e_shard, min((k + 1) * e_shard, E)
        w = max(hi - lo, 0)
        if w:
            src_s[k, :w] = src[lo:hi]
            dst_s[k, :w] = dst[lo:hi]
            val_s[k, :w] = valid[lo:hi]
        dst_s[k, w:] = dst[hi - 1]
        if 0 < lo < E and dst[lo - 1] == dst[lo]:
            bnd[dst[lo]] = True
    return src_s, dst_s, val_s, bnd


def _graphs():
    rng = np.random.default_rng(3)
    unsorted = random_strongly_connected_edge_list(37, 2.5, rng, sort=False)
    assert not is_dst_sorted(unsorted.dst)
    return {"chord": chord_edge_list(6, 4), "unsorted": unsorted,
            "sorted": sort_by_dst(unsorted)[0]}


@pytest.mark.parametrize("n_shards", [1, 2, 3, 4, 7])
@pytest.mark.parametrize("name", ["chord", "unsorted", "sorted"])
def test_partition_equals_the_loop_partitioner(name, n_shards):
    el = _graphs()[name]
    sh = partition_edge_list(el, n_shards)
    for got, want in zip((sh.src, sh.dst, sh.valid, sh.boundary),
                         _partition_by_loop(el, n_shards)):
        np.testing.assert_array_equal(got, want)
    assert sh.e_total == el.E


def test_sorted_input_skips_the_sort():
    el = chord_edge_list(6, 4)
    out, perm, inv, off = sort_by_dst(el, return_offsets=True)
    assert out is el
    np.testing.assert_array_equal(perm, np.arange(el.E))
    np.testing.assert_array_equal(inv, np.arange(el.E))
    np.testing.assert_array_equal(off, np.arange(el.n + 1) * 4)
    # a batch of sorted rows is sorted too; one unsorted row sorts all
    rng = np.random.default_rng(0)
    adjs = [rng.random((9, 9)) < 0.4 for _ in range(2)]
    for a in adjs:
        np.fill_diagonal(a, False)
    batch = stack_edge_lists(adjs)
    srt, perm, _ = sort_by_dst(batch)
    assert srt is not batch and is_dst_sorted(srt.dst)
    again, perm2, _ = sort_by_dst(srt)
    assert again is srt
    np.testing.assert_array_equal(perm2, np.tile(np.arange(batch.E), (2, 1)))


@pytest.mark.parametrize("name, n_shards", [("chord", 4), ("unsorted", 3),
                                            ("unsorted", 7)])
def test_shard_run_ends_and_windows(name, n_shards):
    el = _graphs()[name]
    sh = partition_edge_list(el, n_shards)
    real = np.clip(sh.e_total - np.arange(n_shards) * sh.e_shard, 0,
                   sh.e_shard)
    for k in range(n_shards):
        # the run of a receiver with a real in-edge on the shard ends at its
        # last slot, padding (which keeps the last receiver) included
        held = set(sh.dst[k, :real[k]].tolist())
        for v in range(el.n):
            hit = np.flatnonzero(sh.dst[k] == v)
            assert sh.run_ends[k, v] == (hit[-1] if v in held else -1)
    # a split receiver's earlier part ends at its shard's last real slot
    for v in np.flatnonzero(sh.boundary):
        k = int(np.flatnonzero(sh.run_ends[:, v] >= 0)[0])
        assert sh.run_ends[k, v] == real[k] - 1
    lo, ends = sh.run_end_windows()
    W = ends.shape[-1]
    assert (lo >= 0).all() and (lo + W <= el.n).all()
    for k in range(n_shards):
        np.testing.assert_array_equal(ends[k], sh.run_ends[k, lo[k]:lo[k] + W])
        assert (ends[k] >= 0).sum() == (sh.run_ends[k] >= 0).sum()
    if name == "chord":
        assert W == el.n // n_shards
        np.testing.assert_array_equal(lo, np.arange(n_shards) * W)


# --- the per-shard run-end finish --------------------------------------------

def _finish_case(name):
    """A graph whose shards each span two Pallas blocks (4096 edges), so
    runs are carried across blocks: a Chord ring (no split receiver), or a
    random graph with receivers split by the cuts and a padded tail."""
    if name == "chord":
        return chord_edge_list(11), 4
    rng = np.random.default_rng(7)
    el = random_strongly_connected_edge_list(1500, 12.0, rng, sort=False)
    sh = partition_edge_list(el, 3)
    assert sh.boundary.any() and sh.e_pad > el.E and sh.e_shard > 4096
    return el, 3


def _scatter_finish(monkeypatch):
    """The sweep's step with the run ends withheld: the scatter finish."""
    step = pushsum.sparse_pushsum_step
    monkeypatch.setattr(sweeps, "sparse_pushsum_step",
                        lambda *a, seg_end=None, seg_lo=None, **k:
                        step(*a, **k))
    jax.clear_caches()


@pytest.mark.parametrize("name", ["chord", "split"])
def test_run_end_finish_equals_scatter_emulated(name, monkeypatch):
    el, S = _finish_case(name)
    w = np.random.default_rng(1).normal(size=(el.n, 3)).astype(np.float32)
    plan = ExecutionPlan(graph_shards=S, backend="pallas")
    kw = dict(drop_probs=[0.3], seeds=[5], B=3, plan=plan)
    jax.clear_caches()
    gather = run_pushsum_sweep(w, el, 5, **kw)
    _scatter_finish(monkeypatch)
    scatter = run_pushsum_sweep(w, el, 5, **kw)
    for f in ("err", "final_ratio", "mass_gap"):
        np.testing.assert_array_equal(np.asarray(getattr(gather, f)),
                                      np.asarray(getattr(scatter, f)))
    # and the sharded program agrees with the plain one up to rounding
    plain = run_pushsum_sweep(w, partition_edge_list(el, S)
                              .padded_edge_list(), 5, drop_probs=[0.3],
                              seeds=[5], B=3)
    np.testing.assert_allclose(np.asarray(gather.final_ratio),
                               np.asarray(plain.final_ratio), atol=1e-5)


def test_step_takes_run_ends_under_graph_axis():
    """``seg_end`` with ``graph_axis`` (refused before) finishes each shard
    with the gather, to the bit of the scatter finish, round after
    round."""
    el = chord_edge_list(10, 6)
    S = 2
    sh = partition_edge_list(el, S)
    lo, ends = sh.run_end_windows()
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(el.n, 2)).astype(np.float32))
    masks = jnp.asarray(rng.random((3, S, sh.e_shard)) < 0.6)

    def run(finish):
        def shard(src, dst, valid, lo_k, ends_k, mask):
            st = pushsum.init_sparse_state(w, sh.e_shard)
            for t in range(3):
                extra = dict(seg_end=ends_k, seg_lo=lo_k) if finish else {}
                st = pushsum.sparse_pushsum_step(
                    st, mask[t], src, dst, valid, "pallas",
                    graph_axis="g", dst_sorted=True, **extra)
            return st
        return jax.vmap(shard, axis_name="g", in_axes=(0, 0, 0, 0, 0, 1))(
            *map(jnp.asarray, (sh.src, sh.dst, sh.valid, lo, ends)), masks)

    for a, b in zip(run(True), run(False)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_MESH_SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {src!r}); sys.path.insert(0, {tests!r})
    import jax, numpy as np
    from repro.core import pushsum, sweeps
    from repro.core.plan import ExecutionPlan
    from repro.distributed.sharding import sweep_mesh
    from test_chord_pushsum import _finish_case
    out = {{}}
    for name in ("chord", "split"):
        el, S = _finish_case(name)
        w = np.random.default_rng(1).normal(size=(el.n, 3)).astype(np.float32)
        kw = dict(drop_probs=[0.3], seeds=[5], B=3)
        mesh = sweep_mesh(1, S, devices=jax.devices()[:S])
        res = {{}}
        for label, plan in (
                ("emulated", ExecutionPlan(graph_shards=S, backend="pallas")),
                ("mesh", ExecutionPlan(mesh=mesh, graph_shards=S,
                                       backend="pallas"))):
            r = sweeps.run_pushsum_sweep(w, el, 5, plan=plan, **kw)
            res[label] = [np.asarray(r.err), np.asarray(r.final_ratio)]
        step = pushsum.sparse_pushsum_step
        sweeps.sparse_pushsum_step = (
            lambda *a, seg_end=None, seg_lo=None, **k: step(*a, **k))
        sweeps._sweep_sharded_2d.cache_clear()
        jax.clear_caches()
        r = sweeps.run_pushsum_sweep(
            w, el, 5, plan=ExecutionPlan(mesh=mesh, graph_shards=S,
                                         backend="pallas"), **kw)
        res["mesh_scatter"] = [np.asarray(r.err), np.asarray(r.final_ratio)]
        sweeps.sparse_pushsum_step = step
        sweeps._sweep_sharded_2d.cache_clear()
        out[name] = {{
            "devices": len({{d.id for d in mesh.devices.flat}}),
            "mesh_is_emulation": all(np.array_equal(a, b) for a, b in
                                     zip(res["mesh"], res["emulated"])),
            "gather_is_scatter": all(np.array_equal(a, b) for a, b in
                                     zip(res["mesh"], res["mesh_scatter"])),
        }}
    print(json.dumps(out))
""")


@pytest.mark.multidevice
def test_run_end_finish_on_a_four_device_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = _MESH_SCRIPT.format(src=os.path.join(REPO, "src"),
                                 tests=os.path.join(REPO, "tests"))
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for name, got in out.items():
        assert got == {"devices": {"chord": 4, "split": 3}[name],
                       "mesh_is_emulation": True,
                       "gather_is_scatter": True}, (name, got)


# --- phase scopes and host spans ---------------------------------------------

def test_sharded_round_scopes_reach_the_compiled_hlo():
    """``links``, ``consensus`` (holding ``halo`` and ``run_end_gather``,
    names that are no phase) and ``readout``; no op name holds two
    phases."""
    from chipbench import phases

    sh = partition_edge_list(chord_edge_list(8, 4), 2)
    lo, ends = sh.run_end_windows()
    w = np.ones((sh.n, 3), np.float32)
    args = [w] + [a[None] for a in (sh.src, sh.dst, sh.valid, lo, ends)] + [
        np.full(1, 0.3, np.float32), np.zeros(1, np.uint32)]
    text = sweeps._sweep2d_compiled.lower(
        *args, T=2, B=2, backend="pallas", graph_axis="g",
        n_shards=2).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    found = {phases.phase_of(n) for n in names} - {None}
    assert found == {"links", "consensus", "readout"}
    for scope in ("halo", "run_end_gather"):
        inner = [n for n in names if scope in n.split("/")]
        assert inner and all(phases.phase_of(n) == "consensus"
                             for n in inner), scope
    assert "halo" not in phases.PHASES


def test_entry_spans_prepare_then_dispatch(tmp_path):
    from jax.profiler import ProfileData

    el = chord_edge_list(6, 3)
    w = np.ones((el.n, 2), np.float32)

    def calls():
        for plan in (ExecutionPlan(), ExecutionPlan(graph_shards=2)):
            jax.block_until_ready(run_pushsum_sweep(
                w, el, 2, drop_probs=0.2, seeds=1, plan=plan).err)

    calls()                                  # warm: compiles
    jax.profiler.start_trace(str(tmp_path))
    calls()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = sorted(
        ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
         for plane in ProfileData.from_file(str(path)).planes
         if plane.name.startswith("/host:")
         for line in plane.lines for ev in line.events
         if ev.name.startswith("repro.")), key=lambda s: s[1])
    assert [s[0] for s in spans] == ["repro.pushsum.prepare",
                                     "repro.pushsum.dispatch"] * 2
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1]                  # one after the other


def test_mesh_upload_places_each_shard_on_its_device():
    """With a mesh, the edge arrays are put on the devices of the edge
    layout before the dispatch."""
    from repro.distributed.sharding import sweep_mesh

    el = chord_edge_list(6, 3)
    mesh = sweep_mesh(1, 1)
    fn, args, _ = sweeps._prepare_edge_sharded(
        jnp.ones((el.n, 2)), el, 2, 0.2, 1, 2,
        ExecutionPlan(mesh=mesh, graph_shards=1), None)
    for a in args[1:6]:
        assert a.sharding.spec == jax.sharding.PartitionSpec("data", "graph")


# --- the device placement an EdgeShards keeps across calls -------------------

def _placement_case():
    el = chord_edge_list(8, 4)
    w = np.random.default_rng(4).normal(size=(el.n, 2)).astype(np.float32)
    return el, w


def _sweep(w, sh, plan, seeds=5):
    r = run_pushsum_sweep(w, sh, 3, drop_probs=0.3, seeds=seeds, B=2,
                          plan=plan)
    return [np.asarray(x) for x in (r.err, r.final_ratio, r.mass_gap)]


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


def _spy_edge_uploads(monkeypatch):
    """The (K, S, ...) integer and mask host arrays put on a device,
    through either path."""
    seen = []

    def spy(f):
        def put(x, *a, **k):
            if _edge_upload(x):
                seen.append(x.shape)
            return f(x, *a, **k)
        return put
    monkeypatch.setattr(jax, "device_put", spy(jax.device_put))
    monkeypatch.setattr(jnp, "asarray", spy(jnp.asarray))
    return seen


def _edge_upload(x):
    return (isinstance(x, np.ndarray) and x.ndim >= 2
            and x.dtype.kind in "ib")


def _placement():
    return sweeps.cache_registry()["pushsum.edge-placement"]


def test_second_call_reuses_the_placement(monkeypatch):
    el, w = _placement_case()
    plan = ExecutionPlan(graph_shards=2)
    _placement().clear()
    sh = partition_edge_list(el, 2)
    seen = _spy_edge_uploads(monkeypatch)
    first = _sweep(w, sh, plan)
    assert len(seen) == 5                    # src, dst, valid, lo, ends
    assert sweeps.edge_placement_info() == {"hits": 0, "misses": 1,
                                            "live": 1}
    seen.clear()
    second = _sweep(w, sh, plan)
    assert seen == []                        # nothing uploaded again
    assert sweeps.edge_placement_info()["hits"] == 1
    # reused buffers (none donated) give the answer of a fresh partition
    assert _same(first, second)
    assert _same(first, _sweep(w, partition_edge_list(el, 2), plan))


def test_replaced_graph_misses_and_differs():
    el, w = _placement_case()
    plan = ExecutionPlan(graph_shards=2)
    sh = partition_edge_list(el, 2)
    base = _sweep(w, sh, plan)
    valid = sh.valid.copy()
    valid[1] = False                         # the second shard carries nothing
    broken = dataclasses.replace(sh, valid=valid)
    assert "_memo" not in repr(broken)
    assert "_memo" not in [f.name for f in dataclasses.fields(sh)
                           if f.compare or f.hash]
    misses = sweeps.edge_placement_info()["misses"]
    got = _sweep(w, broken, plan)
    assert sweeps.edge_placement_info()["misses"] == misses + 1
    assert not np.array_equal(got[1], base[1])
    assert _same(base, _sweep(w, sh, plan))  # the original is untouched


def test_placement_is_freed_with_its_graph():
    el, w = _placement_case()
    plan = ExecutionPlan(graph_shards=2)
    _placement().clear()
    sh = partition_edge_list(el, 2)
    _sweep(w, sh, plan)
    _, args, _ = sweeps._prepare_edge_sharded(
        jnp.asarray(w), sh, 3, 0.3, 5, 2, plan, None)
    ref = weakref.ref(args[1])               # the placed src
    assert _placement().cache_info().currsize == 1
    del args, sh
    gc.collect()
    assert ref() is None
    assert _placement().cache_info().currsize == 0


@pytest.mark.parametrize("name", ["chord", "unsorted"])
def test_partition_arrays_are_read_only(name):
    sh = partition_edge_list(_graphs()[name], 3)
    for a in (sh.src, sh.dst, sh.valid, sh.boundary, sh.run_ends,
              *sh.run_end_windows()):
        with pytest.raises(ValueError, match="read-only"):
            a[(0,) * a.ndim] = a[(0,) * a.ndim]
    assert sh.run_end_windows()[1] is sh.run_end_windows()[1]


def test_another_k_replaces_the_one_placement():
    el, w = _placement_case()
    plan = ExecutionPlan(graph_shards=2)
    h = _placement()
    h.clear()
    sh = partition_edge_list(el, 2)
    one = _sweep(w, sh, plan)
    two = _sweep(w, sh, plan, seeds=[5, 6])     # K = 2: a miss
    assert _same([a[:1] for a in two], one)
    _sweep(w, sh, plan, seeds=[5, 6])            # a hit
    _sweep(w, sh, plan)                          # K = 1 again: a miss
    assert sweeps.edge_placement_info() == {"hits": 1, "misses": 3,
                                            "live": 1}
    assert h.cache_info().currsize == 1
    # an EdgeList is partitioned in each call and never hits
    _sweep(w, el, plan)
    _sweep(w, el, plan)
    assert sweeps.edge_placement_info() == {"hits": 1, "misses": 5,
                                            "live": 1}
    h.clear()
    assert h.cache_info().currsize == 0
    assert sweeps.edge_placement_info() == {"hits": 0, "misses": 0,
                                            "live": 0}
    assert _same(one, _sweep(w, sh, plan))
    assert sweeps.edge_placement_info()["misses"] == 1


_PLACEMENT_MESH_SCRIPT = textwrap.dedent("""
    import dataclasses, gc, json, sys, weakref
    sys.path.insert(0, {src!r}); sys.path.insert(0, {tests!r})
    import jax, numpy as np
    from repro.core import sweeps
    from repro.core.graphs import partition_edge_list
    from repro.core.plan import ExecutionPlan
    from repro.distributed.sharding import sweep_mesh
    from test_chord_pushsum import (_edge_upload, _placement_case, _same,
                                    _sweep)
    el, w = _placement_case()
    plan = ExecutionPlan(mesh=sweep_mesh(1, 4), graph_shards=4)
    sh = partition_edge_list(el, 4)
    puts = []
    put = jax.device_put
    def spy(x, *a, **k):
        if _edge_upload(x):
            puts.append(x.shape)
        return put(x, *a, **k)
    jax.device_put = spy
    first = _sweep(w, sh, plan)
    n_first = len(puts)
    second = _sweep(w, sh, plan)
    jax.device_put = put
    valid = sh.valid.copy()
    valid[1] = False
    broken = _sweep(w, dataclasses.replace(sh, valid=valid), plan)
    other_k = _sweep(w, sh, plan, seeds=[5, 6])
    info = sweeps.edge_placement_info()
    _, args, _ = sweeps._prepare_edge_sharded(
        w, sh, 3, 0.3, [5, 6], 2, plan, None)
    ref = weakref.ref(args[1])
    devices = len(args[1].sharding.device_set)
    del args, sh
    gc.collect()
    print(json.dumps({{
        "uploads": [n_first, len(puts) - n_first],
        "second_is_first": _same(first, second),
        "fresh_is_first": _same(first, _sweep(
            w, partition_edge_list(el, 4), plan)),
        "replaced_differs": not np.array_equal(broken[1], first[1]),
        "other_k_row_is_first": _same([a[:1] for a in other_k], first),
        "info": info,
        "devices": devices,
        "freed": ref() is None,
        "live_after": sweeps.edge_placement_info()["live"],
    }}))
""")


@pytest.mark.multidevice
def test_placement_on_a_four_device_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = _PLACEMENT_MESH_SCRIPT.format(
        src=os.path.join(REPO, "src"), tests=os.path.join(REPO, "tests"))
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {
        "uploads": [5, 0], "second_is_first": True, "fresh_is_first": True,
        "replaced_differs": True, "other_k_row_is_first": True,
        # calls: first (miss), second (hit), replaced (miss), K = 2 (miss)
        "info": {"hits": 1, "misses": 3, "live": 1},
        "devices": 4, "freed": True, "live_after": 0}, out
