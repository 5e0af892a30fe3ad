"""The engine kernels compile for a TPU v5e at the sizes chip_smoke.py runs
and, for the push-sum edge step's run-end gather, at the social cells' size.

Interpret mode (what every other kernel test runs on CPU) accepts programs
Mosaic refuses — an in-kernel row gather, a block that overflows scoped
VMEM — so each kernel here is compiled with ``interpret=False`` for a
described, not attached, ``v5e:2x2`` chip, and the compiled program must
hold the Mosaic kernel (``tpu_custom_call``). Nothing runs.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.byz_trim.byz_trim import trim_gather_pallas
from repro.kernels.pushsum_edge.pushsum_edge import edge_scatter_pallas
from repro.kernels.social_innov.social_innov import innovation_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache, topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_pushsum_edge_compiles_at_N131072(one_chip):
    """The push-sum phase's consensus half: N=131072, E=393216, d+1=5."""
    n, e, d = 131072, 393216, 5
    text = _compiled_text(
        lambda *a: edge_scatter_pallas(*a, interpret=False,
                                       indices_sorted=True),
        one_chip, ((n, d), jnp.float32), ((e, d), jnp.float32),
        ((e,), jnp.bool_), ((e,), jnp.int32), ((e,), jnp.int32))
    assert "tpu_custom_call" in text


def test_pushsum_edge_run_end_gather_compiles_at_N1048576(one_chip):
    """The social cells' consensus step: N=2^20, E=7N, d+1=4, with the
    run ends, so the grid runs in order and carries runs in VMEM scratch;
    the finish gathers at the run ends."""
    n, e, d = 1 << 20, 7 << 20, 4
    text = _compiled_text(
        lambda *a: edge_scatter_pallas(*a[:5], seg_end=a[5],
                                       interpret=False),
        one_chip, ((n, d), jnp.float32), ((e, d), jnp.float32),
        ((e,), jnp.bool_), ((e,), jnp.int32), ((e,), jnp.int32),
        ((n,), jnp.int32))
    assert "tpu_custom_call" in text
    assert "run_end_gather" in text and "scatter" not in text.replace(
        "edge_scatter_pallas", "")


def test_byz_trim_compiles_at_N4096(one_chip):
    """The Byzantine phase: N=4096, deg_max=8, m^2=9 pair coordinates."""
    n, dm, p = 4096, 8, 9
    text = _compiled_text(
        lambda *a: trim_gather_pallas(*a, 2, interpret=False),
        one_chip, ((n, p), jnp.float32), ((n, dm), jnp.int32),
        ((n, dm), jnp.bool_), ((n, dm, p), jnp.float32),
        ((n, dm), jnp.bool_))
    assert "tpu_custom_call" in text


def test_social_innov_compiles_at_N131072(one_chip):
    """The social phase's innovation half: N=131072, m=3, S=4."""
    n, m, s = 131072, 3, 4
    text = _compiled_text(
        lambda *a: innovation_pallas(*a, interpret=False),
        one_chip, ((n, m), jnp.float32), ((n,), jnp.float32),
        ((n,), jnp.float32), ((n, s), jnp.float32),
        ((n, m, s), jnp.float32))
    assert "tpu_custom_call" in text


def test_pushsum_edge_shard_window_compiles_at_chord_shard(one_chip):
    """One chip's shard of the Chord cell: N=2^24 receivers, E/4 =
    100,663,296 edges, d+1=4, finishing with a gather over its own window of
    2^22 run ends placed from ``seg_lo``."""
    n, e, d, w = 1 << 24, 24 << 22, 4, 1 << 22
    text = _compiled_text(
        lambda *a: edge_scatter_pallas(*a[:5], seg_end=a[5], seg_lo=a[6],
                                       interpret=False),
        one_chip, ((n, d), jnp.float32), ((e, d), jnp.float32),
        ((e,), jnp.bool_), ((e,), jnp.int32), ((e,), jnp.int32),
        ((w,), jnp.int32), ((), jnp.int32))
    assert "tpu_custom_call" in text
    assert "run_end_gather" in text and "scatter" not in text.replace(
        "edge_scatter_pallas", "")


def test_edge_sharded_sweep_compiles_for_four_chips(topo, monkeypatch):
    """The whole edge-partitioned sweep of the Chord cell on the described
    2x2 mesh: the Pallas step on each chip's 100,663,296 edges, the halo
    all-reduce, and each chip's arguments and temporaries within its
    16 GB."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import sweeps
    from repro.distributed.sharding import sweep_mesh

    # the program picks its kernels by the default backend: a TPU's here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = sweep_mesh(1, 4, devices=list(topo.devices))
    n, d, es, w = 1 << 24, 3, 24 << 22, 1 << 22

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    edges = P("data", "graph")
    compiled = sweeps._sweep_sharded_2d(
        mesh, "data", "graph", 4, 4, "auto").lower(
        arg((n, d), jnp.float32, P()), arg((1, 4, es), jnp.int32, edges),
        arg((1, 4, es), jnp.int32, edges), arg((1, 4, es), jnp.bool_, edges),
        arg((1, 4), jnp.int32, edges), arg((1, 4, w), jnp.int32, edges),
        arg((1,), jnp.float32, P("data")),
        arg((1,), jnp.uint32, P("data"))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert "run_end_gather" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
