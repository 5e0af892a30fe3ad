"""The reduction from a profiler trace to the per-layer metrics (CPU only;
nothing here touches a TPU)."""
import os

import pytest

from chipbench.tests import small  # noqa: F401  (puts src on sys.path)
from chipbench import run, trace
from chipbench.trace import Op, Span

V5E = {"hbm_bytes_per_s": 819e9}
WORK = {"pushsum_edge": {"N": 8, "E": 56, "d": 3},
        "social_innov": {"N": 8, "m": 3, "S": 4}}
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "social-small.xplane.pb")


def _metric(name, ctx):
    return run.load_module(os.path.join(run.HERE, "metrics",
                                        name + ".py")).read(ctx)


def _ctx(ops, spans, **kw):
    args = dict(calls=2, rounds=4, host_call_s=[0.001, 0.003, 0.002],
                work=WORK, peaks=V5E)
    args.update(kw)
    return trace.Context(ops, spans, **args)


def _op(chip, name, start, end, kind="fusion"):
    return Op(chip, f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %x)",
              start, end)


def test_merge_unions_overlaps():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == \
        [(0, 4), (5, 9)]
    assert trace.merge([]) == []


def test_instruction_and_description():
    name = ("%fusion.31 = f32[1048576,4]{0,1:T(4,128)S(1)} fusion(f32[8] "
            "%a, s32[7] %b), kind=kCustom, calls=%fused_computation.1")
    assert trace.instruction(name) == "fusion.31"
    assert trace.describe(name) == \
        "fusion.31 fusion f32[1048576,4]{0,1:T(4,128)S(1)}"
    call = ("%edge_scatter_pallas.10 = (f32[4,8]{1,0}, f32[4,8]{1,0}) "
            "custom-call(f32[4,8]{1,0} %x), custom_call_target=\"tpu\"")
    assert trace.describe(call).startswith("edge_scatter_pallas.10 "
                                           "custom-call (f32[4,8]")


def _scene():
    # window 0..1000 ns (the two calls' spans); a while op of the first
    # call holds three body ops; one op overlaps the window's end and one
    # lies outside it
    spans = [Span("chipbench.call", 0, 100), Span("chipbench.block", 100, 500),
             Span("chipbench.call", 500, 600),
             Span("chipbench.block", 600, 1000)]
    ops = [
        _op(0, "while.7", 50, 400, "while"),
        _op(0, "fusion.1", 50, 250),
        _op(0, "edge_scatter_pallas.10", 250, 300, "custom-call"),
        _op(0, "innovation_pallas.3", 300, 400, "custom-call"),
        _op(0, "fusion.4", 650, 850),
        _op(0, "fusion.5", 950, 1100),
        _op(0, "fusion.6", 1200, 1300),
    ]
    return ops, spans


def test_busy_idle_and_kernels_by_hand():
    ctx = _ctx(*_scene())
    assert ctx.window_s == pytest.approx(1000e-9)
    # union: 50..400 (350) + 650..850 (200) + 950..1000 (50, clipped)
    assert ctx.busy_s == pytest.approx(600e-9)
    assert _metric("idle_share", ctx) == pytest.approx(40.0)
    assert _metric("busy_ms_per_round", ctx) == pytest.approx(600e-9 / 4 * 1e3)
    assert ctx.kernel_s("edge_scatter_pallas") == pytest.approx(50e-9)
    assert ctx.kernel_s("innovation_pallas") == pytest.approx(100e-9)
    assert ctx.kernel_s("trim_gather_pallas") is None
    assert _metric("edge_scatter_kernel_ms_per_round", ctx) == \
        pytest.approx(50e-9 / 4 * 1e3)
    assert _metric("innovation_kernel_ms_per_round", ctx) == \
        pytest.approx(100e-9 / 4 * 1e3)
    # a lowering without the kernel reads nothing, never 0
    assert _metric("byz_trim_kernel_ms_per_round", ctx) is None
    assert _metric("host_ms_per_call", ctx) == pytest.approx(2.0)


def test_idle_inside_a_container_is_idle():
    # a call's while holds its rounds' ops with a gap between them; the
    # gap is idle although the while spans it
    spans = [Span("chipbench.call", 0, 10), Span("chipbench.block", 10, 1000)]
    ops = [_op(0, "while.2", 100, 900, "while"),
           _op(0, "fusion.1", 100, 300),
           _op(0, "fusion.2", 600, 900)]
    ctx = _ctx(ops, spans)
    assert ctx.busy_s == pytest.approx(500e-9)
    assert _metric("idle_share", ctx) == pytest.approx(50.0)
    gaps = ctx.breakdown()["idle_gaps"]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [100e-9, 100e-9, 300e-9])


def test_leaves_keep_touching_and_overlapping_neighbours():
    # two body ops that touch or overlap by rounding are both leaves; a
    # while and a nested while are containers
    ops = [_op(0, "while.1", 0, 100, "while"),
           _op(0, "while.2", 0, 60, "while"),
           _op(0, "a", 0, 30), _op(0, "b", 29, 60), _op(0, "c", 60, 100),
           _op(1, "d", 0, 100)]
    names = [trace.instruction(o.name) for o in trace.leaves(ops)]
    assert names == ["a", "b", "c", "d"]


def test_kernel_names_match_whole_instructions_only():
    ops = [_op(0, "edge_scatter_pallas_v2.1", 0, 10, "custom-call"),
           _op(0, "edge_scatter_pallas", 10, 30, "custom-call")]
    ctx = _ctx(ops, [Span("chipbench.call", 0, 30)])
    assert ctx.kernel_s("edge_scatter_pallas") == pytest.approx(20e-9)


def test_chips_are_averaged():
    ops = [_op(0, "a", 0, 40), _op(1, "a", 0, 20)]
    ctx = _ctx(ops, [Span("chipbench.call", 0, 100)])
    assert ctx.busy_s == pytest.approx(30e-9)


def test_round_roofline_by_hand():
    from chipbench.work import pushsum_edge, social_innov

    ctx = _ctx(*_scene())
    least = (pushsum_edge.bytes_per_round(8, 56, 3)
             + social_innov.bytes_per_round(8, 3, 4)) / 819e9
    assert _metric("round_roofline", ctx) == pytest.approx(
        100 * least / (600e-9 / 4))


def test_breakdown_leaves_containers_out_and_names_gaps():
    ctx = _ctx(*_scene())
    b = ctx.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert not any(n.startswith("while") for n in names)
    assert names[0].startswith("fusion.1 fusion") or \
        names[0].startswith("fusion.4 fusion")
    assert b["device_ops"][0][1] == pytest.approx(200e-9)
    # gaps: 0..50 (call), 400..650 (block then call: middle 525 in call),
    # 850..950 (block)
    assert b["idle_gaps"][0] == ["chipbench.call", pytest.approx(250e-9)]
    assert sorted(g[1] for g in b["idle_gaps"]) == pytest.approx(
        [50e-9, 100e-9, 250e-9])


def test_no_spans_is_an_error():
    with pytest.raises(ValueError):
        _ctx([], [])


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: the social cell at N = 1024,
    E = 7168, two calls of 4 rounds, under the benchmark's own spans."""
    ops, spans = trace.load(RECORDED)
    assert [s.name for s in spans] == ["chipbench.call",
                                      "chipbench.block"] * 2
    assert ops and {o.chip for o in ops} == {0}
    work = {"pushsum_edge": {"N": 1024, "E": 7168, "d": 3},
            "social_innov": {"N": 1024, "m": 3, "S": 4}}
    ctx = trace.Context(ops, spans, calls=2, rounds=8, host_call_s=[1e-3],
                        work=work, peaks=V5E)
    assert 0 < ctx.busy_s <= ctx.window_s
    # two calls, four rounds each: eight kernel calls of each kind
    edge = [o for o in ctx.ops
            if trace.instruction(o.name).startswith("edge_scatter_pallas")]
    assert len(edge) == 8
    assert 0 < ctx.kernel_s("edge_scatter_pallas") < ctx.busy_s
    assert 0 < ctx.kernel_s("innovation_pallas") < ctx.busy_s
    assert ctx.kernel_s("trim_gather_pallas") is None
    assert 0 < _metric("round_roofline", ctx) < 100
    b = ctx.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert not any(n.startswith("while") for n, _ in b["device_ops"])
    assert sum(v for _, v in b["device_ops"]) <= ctx.busy_s
