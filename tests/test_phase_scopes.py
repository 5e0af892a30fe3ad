"""The scan cores' phase scopes and the ``run_*`` entries' host spans.

Each phase of a scan round runs under one ``jax.named_scope``, with the
same names in the social and the Byzantine scan cores; the compiled HLO
carries them in its ``op_name`` metadata, which the profiler's trace
repeats as each device op's ``tf_op``. The scopes do not nest, so an op's
name stack holds at most one phase.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attacks, sweeps
from repro.core.byzantine import ByzantineConfig
from repro.core.faults import gilbert_elliott_model
from repro.core.graphs import hier_edge_list, make_hierarchy
from repro.core.plan import ExecutionPlan
from repro.core.signals import make_confused_model
from repro.core.social import (
    _social_compiled,
    run_social_runtime,
    social_runtime_from_edge_list,
)

PHASES = ("links", "consensus", "gossip", "innovation", "fusion", "readout")
FAULTS = gilbert_elliott_model(4.0, 0.2, drop_bad=1.0, leave_prob=0.05,
                               join_prob=0.2, ps_crash_prob=0.1)


def _op_phases(hlo_text: str) -> set:
    """The phase names among the components of the HLO's op names; an op
    name holding two phases fails the test (scopes do not nest)."""
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        hit = [p for p in PHASES if p in name.split("/")]
        assert len(hit) <= 1, name
        found.update(hit)
    return found


def _social_setup(networks=4, n=4, T=4):
    el, rep = hier_edge_list([n] * networks, "complete", rep_choice="first")
    rt = social_runtime_from_edge_list(el, rep, drop_prob=0.3,
                                       gamma_period=2, B=2)
    model = make_confused_model(N=networks * n, m=3, S=4, truth=0,
                                confusion=0.5, sharpness=2.0, seed=1)
    return model, rt, networks, T


@pytest.mark.parametrize("faults", [None, FAULTS], ids=["drops", "faults"])
def test_social_scan_core_scopes_reach_the_compiled_hlo(faults):
    model, rt, M, T = _social_setup()
    truth_probs = model.tables[:, model.truth, :].astype(jnp.float32)
    compiled = _social_compiled.lower(
        jax.random.PRNGKey(0), jax.random.PRNGKey(1), rt,
        model.log_tables().astype(jnp.float32),
        jnp.cumsum(truth_probs, axis=-1), truth=0, M=M, T=T,
        store="log_ratio", backend="xla", faults=faults).compile()
    want = {"links", "consensus", "innovation", "fusion", "readout"}
    assert _op_phases(compiled.as_text()) == want


@pytest.mark.parametrize("faults", [None, FAULTS], ids=["plain", "faults"])
def test_byzantine_grid_scopes_reach_the_compiled_hlo(faults):
    N, n = 32, 8
    topo = make_hierarchy([n] * (N // n), "complete", rep_choice="first")
    cfg = ByzantineConfig(topo=topo, F=1, byz=(2,), gamma_period=3,
                          attack=attacks.large_value(100.0))
    model = make_confused_model(N=N, m=3, S=4, truth=0, confusion=0.15,
                                sharpness=2.0, seed=3)
    seeds = [11, 12]
    sweeps.run_byzantine_grid(model, [cfg], 4, seeds,
                              plan=ExecutionPlan(backend="xla",
                                                 faults=faults))
    fn = list(sweeps._BYZ_GRID_COMPILED.values())[-1]
    stacked = list(sweeps._BYZ_RUNTIME_CACHE.values())[-1]
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.uint32))
    rt_batch = jax.tree_util.tree_map(
        lambda x: x[jnp.zeros(len(seeds), jnp.int32)], stacked)
    text = fn.lower(keys, rt_batch).compile().as_text()
    want = {"gossip", "innovation", "fusion", "readout"}
    if faults is not None:
        want.add("links")
    assert _op_phases(text) == want


def test_entry_spans_nest_on_one_thread(tmp_path):
    """Both entries open their host spans, in order and nested as named,
    inside the caller's span."""
    from jax.profiler import ProfileData

    model, rt, M, T = _social_setup()
    N = 32
    topo = make_hierarchy([8] * 4, "complete", rep_choice="first")
    cfg = ByzantineConfig(topo=topo, F=1, byz=(2,), gamma_period=3,
                          attack=attacks.large_value(100.0))
    bmodel = make_confused_model(N=N, m=3, S=4, truth=0, confusion=0.15,
                                 sharpness=2.0, seed=5)
    plan = ExecutionPlan(store="log_ratio", backend="xla")

    def calls():
        jax.block_until_ready(run_social_runtime(model, rt, M, T, seed=3,
                                                 plan=plan).beliefs)
        jax.block_until_ready(sweeps.run_byzantine_grid(
            bmodel, [cfg], 3, [1, 2],
            plan=ExecutionPlan(backend="xla")).decisions)

    calls()                            # warm: compiles, builds the caches
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("test.outer"):
        calls()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = sorted(
        ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
         for plane in ProfileData.from_file(str(path)).planes
         if plane.name.startswith("/host:")
         for line in plane.lines for ev in line.events
         if ev.name.startswith(("repro.", "test."))),
        key=lambda s: (s[1], -s[2]))
    names = [s[0] for s in spans]
    # the caches are warm: no build span
    assert names == [
        "test.outer", "repro.social.prepare", "repro.social.dispatch",
        "repro.byz_grid.prepare", "repro.byz_grid.fingerprint",
        "repro.byz_grid.fingerprint", "repro.byz_grid.batch",
        "repro.byz_grid.dispatch", "repro.byz_grid.unpack"]
    outer = spans[0]
    assert all(outer[1] <= s[1] <= s[2] <= outer[2] for s in spans)
    prep = spans[3]
    assert all(prep[1] <= s[1] <= s[2] <= prep[2] for s in spans[4:7])
    # siblings follow one another
    for a, b in [(spans[1], spans[2]), (spans[3], spans[7]),
                 (spans[7], spans[8])]:
        assert a[2] <= b[1]


def test_a_cache_miss_opens_a_build_span(tmp_path):
    from jax.profiler import ProfileData

    topo = make_hierarchy([8] * 4, "complete", rep_choice="first")
    cfg = ByzantineConfig(topo=topo, F=1, byz=(3,), gamma_period=4,
                          attack=attacks.large_value(50.0))
    # a signal model no other test builds, so both caches miss
    model = make_confused_model(N=32, m=3, S=4, truth=0, confusion=0.15,
                                sharpness=2.0, seed=424242)
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(sweeps.run_byzantine_grid(
        model, [cfg], 2, [1], plan=ExecutionPlan(backend="xla")).decisions)
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.byz_grid.build")]
    # one for the runtime stack, one for the grid program
    assert len(names) == 2


@pytest.mark.parametrize("sorted_edges", [True, False],
                         ids=["sorted", "unsorted"])
def test_run_end_gather_scope_sits_in_consensus(sorted_edges):
    """The Pallas consensus step of a dst-sorted runtime finishes with a
    gather under ``run_end_gather`` inside ``consensus`` (a name that is
    no phase, so the benchmark's phase reader still counts it as
    consensus); an unsorted runtime keeps the scatter finish."""
    from chipbench import phases
    from repro.core.graphs import EdgeList

    model, rt, M, T = _social_setup()
    if not sorted_edges:
        el, rep = hier_edge_list([4] * 4, "complete", rep_choice="first")
        perm = np.random.default_rng(0).permutation(el.E)
        rt = social_runtime_from_edge_list(
            EdgeList(src=el.src[perm], dst=el.dst[perm], n=el.n,
                     valid=el.valid[perm]),
            rep, drop_prob=0.3, gamma_period=2, B=2)
    assert (rt.seg_end is not None) == sorted_edges
    assert "run_end_gather" not in phases.PHASES
    truth_probs = model.tables[:, model.truth, :].astype(jnp.float32)
    text = _social_compiled.lower(
        jax.random.PRNGKey(0), jax.random.PRNGKey(1), rt,
        model.log_tables().astype(jnp.float32),
        jnp.cumsum(truth_probs, axis=-1), truth=0, M=M, T=T,
        store="log_ratio", backend="pallas").compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if "run_end_gather" in n.split("/")]
    if not sorted_edges:
        assert not names
        return
    assert names
    for name in names:
        assert "consensus" in name.split("/")
        assert phases.phase_of(name) == "consensus"
