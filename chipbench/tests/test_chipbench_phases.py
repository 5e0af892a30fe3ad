"""The program's phases and host spans read from a profiler trace (CPU only;
nothing here touches a TPU): the op metadata reader, the phase reduction on
hand-made events, and the traces recorded on one TPU v5e."""
import os

import pytest

from chipbench.tests import small  # noqa: F401  (puts src on sys.path)
from chipbench import phases, run, trace, xplane
from chipbench.trace import Op, Span

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "social-small.xplane.pb")
V5E = {"hbm_bytes_per_s": 819e9}
COPIES = {"copy", "copy-start", "copy-done"}
ALLOCATE = 'custom_call_target="AllocateBuffer"'
SCAN = "jit(_social_scan_core)/while/body/"


def _metric(name, ctx):
    return run.load_module(os.path.join(run.HERE, "metrics",
                                        name + ".py")).read(ctx)


def _inserted(name):
    """An op XLA inserts, with no scope of the program: a copy, or a
    buffer allocation (a custom call of 1 ns)."""
    return (trace.describe(name).split(" ")[1] in COPIES
            or name.endswith(ALLOCATE))


def _recorded_ctx():
    ops, spans = trace.load(RECORDED)
    work = {"pushsum_edge": {"N": 1024, "E": 7168, "d": 3},
            "social_innov": {"N": 1024, "m": 3, "S": 4}}
    return trace.Context(ops, spans, calls=2, rounds=8,
                         host_call_s=[1e-3, 3e-3], work=work, peaks=V5E)


# --- the op metadata reader ------------------------------------------------

def test_recorded_ops_carry_tf_op_but_what_xla_inserts():
    ctx = _recorded_ctx()
    tf_ops = xplane.tf_ops(RECORDED)
    assert ctx.ops
    for o in ctx.ops:
        # only what XLA inserts may lack a name stack (some copies have one)
        assert o.name in tf_ops or _inserted(o.name), o.name
    assert all(v and v.endswith(":") for v in tf_ops.values())
    total = sum(o.end_ns - o.start_ns for o in ctx.ops)
    named = sum(o.end_ns - o.start_ns for o in ctx.ops if tf_ops.get(o.name))
    assert named / total > 0.99


def test_recorded_scatter_names_the_edge_kernel():
    tf_ops = xplane.tf_ops(RECORDED)
    [name] = [k for k in tf_ops if k.startswith("%fusion.37 ")]
    assert tf_ops[name] == (SCAN + "closed_call/jit(edge_scatter_pallas)/"
                            "scatter-add:")


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, events, stat_names):
    """An XPlane with an ``XLine`` (to be skipped), its stat metadata and
    event metadata; ``events``: ``(op name, stats)`` with each stat
    ``(stat id, field number of the value, value)``."""
    body = _field(2, name) + _field(3, _field(2, "XLA Ops") + _field(
        4, _field(1, 1) + _field(3, 5)))
    for i, (op, stats) in enumerate(events, 1):
        meta = _field(1, i) + _field(2, op) + b"".join(
            _field(5, _field(1, sid) + _field(vf, v)) for sid, vf, v in stats)
        body += _field(4, _field(1, i) + _field(2, meta))
    for sid, sname in stat_names.items():
        body += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                   + _field(2, sname)))
    return body


def test_tf_ops_reader_on_a_hand_made_space(tmp_path):
    names = {7: "tf_op", 8: "hlo_category", 9: "x/consensus/add:"}
    p0 = _plane("/device:TPU:0", [
        ("%a", [(8, 5, "loop fusion"), (7, 5, "x/links/mul:")]),
        ("%b", [(7, 7, 9)]),                     # an interned string
        ("%c", [(7, 5, "p/fusion/add:")]),
        ("%d", [(7, 5, "x/readout/max:")]),
        ("%copy.1", [(8, 5, "copy")]),
    ], names)
    p1 = _plane("/device:TPU:1", [
        ("%c", [(7, 5, "q/gossip/add:")]),       # the same name, another op
        ("%d", [(7, 5, "x/readout/max:")]),
    ], names)
    host = _plane("/host:CPU", [("%e", [(7, 5, "x/links/mul:")])], names)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, p0) + _field(1, p1) + _field(1, host)
                     + _field(4, "host"))
    assert xplane.tf_ops(str(path)) == {
        "%a": "x/links/mul:", "%b": "x/consensus/add:", "%c": None,
        "%d": "x/readout/max:"}


# --- the reduction, on hand-made events -------------------------------------

def test_phase_is_a_whole_component():
    assert phases.phase_of(SCAN + "consensus/jit(edge)/scatter-add:") == \
        "consensus"
    assert phases.phase_of(SCAN + "readout/reduce_max:") == "readout"
    assert phases.phase_of("jit(f)/while/body/links:") == "links"
    # a function named after a phase is not the phase's scope
    assert phases.phase_of(SCAN + "jit(hps_fusion)/add:") is None
    assert phases.phase_of(SCAN + "closed_call/fusions/add:") is None
    # two phases in one stack is no phase
    assert phases.phase_of(SCAN + "links/fusion/add:") is None
    assert phases.phase_of(None) is None and phases.phase_of("") is None


def _op(name, start, end, chip=0, kind="fusion"):
    return Op(chip, f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %x)",
              start, end)


def _scene(work=None):
    """Two calls. Each: the benchmark's call span holds the program's
    prepare (with a fingerprint and a batch), dispatch and unpack; the
    device runs links, gossip, an unscoped copy and fusion ops inside the
    benchmark's block span."""
    spans = [Span("chipbench.call", 0, 100), Span("chipbench.block", 100, 500),
             Span("chipbench.call", 500, 600),
             Span("chipbench.block", 600, 1000)]
    program = [Span("repro.byz_grid.prepare", 5, 80),
               Span("repro.byz_grid.fingerprint", 10, 30),
               Span("repro.byz_grid.batch", 40, 70),
               Span("repro.byz_grid.dispatch", 80, 90),
               Span("repro.byz_grid.unpack", 90, 95),
               Span("repro.byz_grid.prepare", 505, 545),
               Span("repro.byz_grid.dispatch", 545, 560)]
    ops = [_op("while.1", 90, 400, kind="while"),
           _op("fusion.1", 90, 150), _op("fusion.2", 150, 250),
           _op("copy.3", 250, 270, kind="copy"), _op("fusion.4", 300, 400),
           _op("fusion.1", 650, 700), _op("fusion.2", 700, 900)]
    tf_ops = {ops[1].name: "jit(g)/while/body/links/rng:",
              ops[2].name: "jit(g)/while/body/gossip/gather:",
              ops[4].name: "jit(g)/while/body/fusion/select:"}
    ctx = trace.Context(ops, spans, calls=2, rounds=4,
                        host_call_s=[1e-3, 1e-3], work=work or {},
                        peaks=V5E)
    return phases.Phases(ctx, tf_ops, program)


def test_phase_times_and_share_by_hand():
    ph = _scene()
    assert ph.phase_s("links") == pytest.approx(110e-9)     # 60 + 50
    assert ph.phase_s("gossip") == pytest.approx(300e-9)    # 100 + 200
    assert ph.phase_s("fusion") == pytest.approx(100e-9)
    assert ph.phase_s("consensus") is None
    assert ph.ms_per_round("gossip") == pytest.approx(300e-9 / 4 * 1e3)
    # busy 90..270 + 300..400 + 650..900 = 530; scoped: all but the copy
    assert ph.ctx.busy_s == pytest.approx(530e-9)
    assert ph.scoped_share() == pytest.approx(100 * 510 / 530)
    # the phase times sum to the busy time the scopes cover
    assert sum(ph.phase_s(p) or 0 for p in phases.PHASES) == pytest.approx(
        ph.ctx.busy_s * ph.scoped_share() / 100)


def test_roofline_of_a_phase_by_hand():
    from chipbench.work import byz_trim

    args = {"N": 8, "deg": 7, "P": 9, "K": 2}
    ph = _scene({"byz_trim": args})
    least = byz_trim.bytes_per_round(**args) / 819e9
    assert ph.roofline("byz_trim", "gossip") == pytest.approx(
        100 * least / (300e-9 / 4))
    assert ph.roofline("pushsum_edge", "consensus") is None
    m = ph.metrics()
    assert m["byz_trim_roofline"] == ph.roofline("byz_trim", "gossip")
    assert "edge_scatter_roofline" not in m


def test_host_prepare_is_the_median_prepare_span():
    ph = _scene()
    # prepare spans of 75 and 40 ns
    assert ph.host_prepare_ms_per_call() == pytest.approx(57.5e-6)


def test_gaps_are_named_by_the_innermost_span():
    ph = _scene()
    assert ph.span_at(50) == "repro.byz_grid.batch"
    assert ph.span_at(35) == "repro.byz_grid.prepare"
    assert ph.span_at(2) == "chipbench.call"
    assert ph.span_at(520) == "repro.byz_grid.prepare"
    assert ph.span_at(950) == "chipbench.block"
    assert ph.span_at(2000) == "between calls"
    b = ph.breakdown()
    # idle: 0..90 (middle 45: batch), 270..300 (block), 400..650 (middle
    # 525: prepare), 900..1000 (block)
    assert b["idle_gaps"] == [
        ["repro.byz_grid.prepare", pytest.approx(250e-9)],
        ["chipbench.block", pytest.approx(100e-9)],
        ["repro.byz_grid.batch", pytest.approx(90e-9)],
        ["chipbench.block", pytest.approx(30e-9)]]
    split = ph.split(400, 650)
    assert split == pytest.approx({"chipbench.block": 150e-9,
                                   "chipbench.call": 5e-9 + 40e-9,
                                   "repro.byz_grid.prepare": 40e-9,
                                   "repro.byz_grid.dispatch": 15e-9})
    assert sum(split.values()) == pytest.approx(250e-9)
    # all the window's idle time, by innermost span
    assert b["idle_by_span"] == pytest.approx({
        "repro.byz_grid.prepare": 25e-9 + 40e-9,
        "repro.byz_grid.fingerprint": 20e-9, "repro.byz_grid.batch": 30e-9,
        "repro.byz_grid.dispatch": 10e-9 + 15e-9,
        "chipbench.call": 5e-9 + 45e-9, "chipbench.block": 30e-9 + 250e-9})
    assert sum(b["idle_by_span"].values()) == pytest.approx(
        ph.ctx.window_s - ph.ctx.busy_s)
    # each call's idle before its scan: the first call's from the window's
    # start to fusion.1 at 90; the second's from the first wait's end (500)
    # to fusion.1 at 650
    assert b["call_idle"] == [
        pytest.approx({"chipbench.call": 5e-9, "repro.byz_grid.prepare": 25e-9,
                       "repro.byz_grid.fingerprint": 20e-9,
                       "repro.byz_grid.batch": 30e-9,
                       "repro.byz_grid.dispatch": 10e-9}),
        pytest.approx({"chipbench.call": 45e-9,
                       "repro.byz_grid.prepare": 40e-9,
                       "repro.byz_grid.dispatch": 15e-9,
                       "chipbench.block": 50e-9})]


def test_breakdown_names_ops_with_their_phase():
    ph = _scene()
    names = dict(ph.breakdown()["device_ops"])
    assert names == pytest.approx({
        "fusion.2 fusion f32[8]{0} @gossip": 300e-9,
        "fusion.1 fusion f32[8]{0} @links": 110e-9,
        "fusion.4 fusion f32[8]{0} @fusion": 100e-9,
        "copy.3 copy f32[8]{0}": 20e-9})


def test_a_program_without_scopes_or_spans_reads_nothing():
    """The parent program: ops with name stacks but no phase, and no
    program span; every phase metric is left out and none raises."""
    ph = _scene()
    bare = phases.Phases(ph.ctx, {o.name: "jit(g)/while/body/add:"
                                  for o in ph.ctx.ops}, [])
    assert bare.metrics() == {}
    assert bare.breakdown()["idle_gaps"][0][0] == "chipbench.call"


def test_the_reduction_leaves_the_context_as_it_was():
    """Every existing metric and breakdown number of the recorded trace
    reads as it did before the phases were read (the numbers are those of
    the reduction before this reader existed)."""
    ctx = _recorded_ctx()
    before = ctx.breakdown()
    ph = phases.Phases(ctx, xplane.tf_ops(RECORDED),
                       phases.program_spans(RECORDED))
    ph.metrics(), ph.breakdown()
    assert ctx.window_s == 0.014120739
    assert ctx.busy_s == 0.0007937220000000001
    assert _metric("busy_ms_per_round", ctx) == 0.09921525
    assert _metric("idle_share", ctx) == 94.37903356191202
    assert _metric("round_roofline", ctx) == 0.5242409651006111
    assert _metric("edge_scatter_kernel_ms_per_round", ctx) == \
        0.0025791250000000003
    assert _metric("innovation_kernel_ms_per_round", ctx) == \
        0.00015800000000000002
    assert _metric("byz_trim_kernel_ms_per_round", ctx) is None
    assert _metric("host_ms_per_call", ctx) == 2.0
    b = ctx.breakdown()
    assert b == before
    assert b["device_ops"][0] == [
        "fusion.37 fusion f32[1024,4]{1,0:T(8,128)S(1)}",
        0.00045768200000000005]
    assert b["idle_gaps"][:3] == [["chipbench.block", 0.001962563],
                                  ["chipbench.block", 0.001780724],
                                  ["chipbench.call", 0.0012456260000000001]]
    # that trace predates the scopes: no phase, no program span
    assert ph.metrics() == {}


# --- the traces recorded on the chip under the program's scopes --------------

# ``chipbench/tests/record.py``: the phases each cell's scan core runs
SMALL_TRACES = {
    "social-small-faults": {"calls": 2, "rounds": 8, "phases": {
        "links", "consensus", "innovation", "fusion", "readout"}},
    "byzantine-small": {"calls": 2, "rounds": 40, "phases": {
        "gossip", "innovation", "fusion", "readout"}},
}


def _small(name):
    path = os.path.join(DATA, name + ".xplane.pb")
    ops, spans = trace.load(path)
    ctx = trace.Context(ops, spans, calls=SMALL_TRACES[name]["calls"],
                        rounds=SMALL_TRACES[name]["rounds"],
                        host_call_s=[1e-3], work={}, peaks=V5E)
    return phases.Phases(ctx, xplane.tf_ops(path),
                         phases.program_spans(path))


@pytest.mark.parametrize("name", sorted(SMALL_TRACES))
def test_small_trace_has_time_in_each_phase(name):
    ph = _small(name)
    ran = SMALL_TRACES[name]["phases"]
    for p in phases.PHASES:
        t = ph.phase_s(p)
        assert (t is not None and t > 0) == (p in ran), p
    assert ph.scoped_share() >= 90
    # the phase times sum to the busy time the scopes cover
    assert sum(ph.phase_s(p) for p in ran) == pytest.approx(
        ph.ctx.busy_s * ph.scoped_share() / 100, rel=0.02)


@pytest.mark.parametrize("name", sorted(SMALL_TRACES))
def test_small_trace_spans_nest_in_the_calls(name):
    ph = _small(name)
    calls = [s for s in ph.ctx.spans if s.name == "chipbench.call"]
    assert len(calls) == SMALL_TRACES[name]["calls"]
    assert ph.spans
    for s in ph.spans:
        assert any(c.start_ns <= s.start_ns <= s.end_ns <= c.end_ns
                   for c in calls), s
    engine = "social" if name.startswith("social") else "byz_grid"
    for c in calls:
        inside = [s.name for s in ph.spans
                  if c.start_ns <= s.start_ns < c.end_ns]
        assert inside[0] == f"repro.{engine}.prepare"
        assert f"repro.{engine}.dispatch" in inside
    assert ph.host_prepare_ms_per_call() > 0


# how far the device's clock may lie from the host's in a trace: the
# profiler aligns them by sampling, and the social trace puts the scan's
# first op 0.32-0.52 ms before its dispatch span starts
CLOCK_SLACK_NS = 1e6


@pytest.mark.parametrize("name", sorted(SMALL_TRACES))
def test_small_trace_spans_share_the_device_clock(name):
    """Each call's scan starts on the device after the call's dispatch span
    starts on the host, and ends before the call's wait returns, to within
    the clocks' alignment; and it lies inside the call."""
    ph = _small(name)
    calls = [s for s in ph.ctx.spans if s.name == "chipbench.call"]
    blocks = [s for s in ph.ctx.spans if s.name == "chipbench.block"]
    for k, (c, blk) in enumerate(zip(calls, blocks)):
        end = calls[k + 1].start_ns if k + 1 < len(calls) else ph.ctx.t1
        scan = [o for o in ph.ctx.ops if ph.phase[o.name]
                and c.start_ns <= o.start_ns < end]
        [dispatch] = [s for s in ph.spans if s.name.endswith(".dispatch")
                      and c.start_ns <= s.start_ns < c.end_ns]
        assert scan
        first = min(o.start_ns for o in scan)
        last = max(o.end_ns for o in scan)
        assert first >= dispatch.start_ns - CLOCK_SLACK_NS
        assert last <= blk.end_ns + CLOCK_SLACK_NS
        assert c.start_ns < first and last < blk.end_ns


# --- the program's spans, traced on the CPU ----------------------------------

def test_profile_reads_the_program_spans_on_the_cpu(tmp_path):
    (tmp_path / "root").mkdir()
    root = small.checkout(str(tmp_path / "root"))
    path, ph, call_s, host_s = phases.profile(
        "byzantine-4k-grid", 7, str(tmp_path / "trace"), calls=2, root=root,
        require_tpu=False)
    assert os.path.isfile(path) and len(call_s) == len(host_s) == 2
    calls = [s for s in ph.ctx.spans if s.name == "chipbench.call"]
    names = [s.name for s in ph.spans]
    # set-up and the warm call built the caches: no build in the window
    assert names.count("repro.byz_grid.prepare") == 2
    assert names.count("repro.byz_grid.dispatch") == 2
    assert "repro.byz_grid.build" not in names
    for s in ph.spans:
        assert any(c.start_ns <= s.start_ns <= s.end_ns <= c.end_ns
                   for c in calls), s
    assert ph.host_prepare_ms_per_call() > 0


# --- the phase metrics of BENCHMARK.json, read through the run's context ----

# each recorded trace with what its cell ran (``chipbench/tests/record.py``)
RECORDED_RUNS = {
    "social-small": {"calls": 2, "rounds": 8, "work": {
        "pushsum_edge": {"N": 1024, "E": 7168, "d": 3},
        "social_innov": {"N": 1024, "m": 3, "S": 4}}},
    "social-small-faults": {"calls": 2, "rounds": 8, "work": {
        "pushsum_edge": {"N": 1024, "E": 7168, "d": 3},
        "social_innov": {"N": 1024, "m": 3, "S": 4}}},
    "byzantine-small": {"calls": 2, "rounds": 40, "work": {
        "byz_trim": {"N": 64, "deg": 7, "P": 9, "K": 4}}},
}
# the metrics that read the phases
PHASED = ("consensus_ms_per_round", "links_ms_per_round",
          "gossip_ms_per_round", "innovation_ms_per_round",
          "fusion_ms_per_round", "readout_ms_per_round",
          "edge_scatter_roofline", "byz_trim_roofline",
          "host_prepare_ms_per_call")


def _run_kw(name):
    rec = RECORDED_RUNS[name]
    return dict(calls=rec["calls"], rounds=rec["rounds"],
                host_call_s=[1e-3, 3e-3], work=rec["work"], peaks=V5E)


@pytest.mark.parametrize("name", sorted(RECORDED_RUNS))
def test_the_context_reads_the_existing_metrics_as_before(name):
    """Keeping the ``tf_op`` map and the program's spans changes nothing
    that a metric read without them, to the bit: every per-layer metric
    of ``BENCHMARK.json`` that reads a value from the context without them
    reads the same with them."""
    path = os.path.join(DATA, name + ".xplane.pb")
    old = trace.Context(*trace.load(path), **_run_kw(name))
    new = trace.Context.from_file(path, **_run_kw(name))
    assert new.tf_ops == xplane.tf_ops(path)
    assert new.program_spans == phases.program_spans(path)
    assert (new.t0, new.t1, new.window_s, new.busy_s, new.chips) == (
        old.t0, old.t1, old.window_s, old.busy_s, old.chips)
    assert new.ops == old.ops and new.spans == old.spans
    bench = run.load_json(os.path.join(small.ROOT, "BENCHMARK.json"))
    plain = [m["name"] for m in bench["per_layer"]
             if _metric(m["name"], old) is not None]
    assert "busy_ms_per_round" in plain and "host_ms_per_call" in plain
    assert not set(plain) & set(PHASED)
    for metric in plain:
        assert _metric(metric, new) == _metric(metric, old), metric
    # the breakdown keeps its numbers; only the names gain phases
    b_new, b_old = new.breakdown(), old.breakdown()
    assert [v for _, v in b_new["device_ops"]] == [
        v for _, v in b_old["device_ops"]]
    assert [v for _, v in b_new["idle_gaps"]] == [
        v for _, v in b_old["idle_gaps"]]
    assert [n.split(" @")[0] for n, _ in b_new["device_ops"]] == [
        n for n, _ in b_old["device_ops"]]


@pytest.mark.parametrize("name", sorted(RECORDED_RUNS))
def test_each_phase_metric_reads_what_the_phases_read(name):
    path = os.path.join(DATA, name + ".xplane.pb")
    ctx = trace.Context.from_file(path, **_run_kw(name))
    want = phases.Phases.from_file(path, **_run_kw(name)).metrics()
    for metric in PHASED:
        assert _metric(metric, ctx) == want.get(metric), metric
    read = {m for m in PHASED if _metric(m, ctx) is not None}
    if name == "social-small":        # recorded before the scopes existed
        assert read == set()
    elif name == "social-small-faults":
        assert read == set(PHASED) - {"gossip_ms_per_round",
                                      "byz_trim_roofline"}
    else:
        assert read == set(PHASED) - {"consensus_ms_per_round",
                                      "links_ms_per_round",
                                      "edge_scatter_roofline"}
    assert all(0 < _metric(m, ctx) < 100 for m in read
               if m.endswith("_roofline"))


def test_traced_run_names_the_breakdown_by_phase_and_span(tmp_path,
                                                         monkeypatch):
    """A traced run on the CPU (with the v5e's peak, as the CPU has none):
    the phase metrics that need a TPU's ops are left out, the program's
    spans are read, and the breakdown keeps its two lists, its gaps named
    by the innermost span."""
    from chipbench.work import peaks

    monkeypatch.setattr(peaks, "peaks", lambda kind: V5E)
    root = small.checkout(str(tmp_path))
    res = run.run_cell("byzantine-4k-grid", 7, 0.2, True, root=root,
                       require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["host_prepare_ms_per_call"]["value"] > 0
    assert res["metrics"]["host_prepare_ms_per_call"]["unit"] == "ms"
    assert "gossip_ms_per_round" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(res["breakdown"]["idle_gaps"]) <= 10
    assert all(n == "between calls" or n.startswith(("chipbench.", "repro."))
               for n, _ in res["breakdown"]["idle_gaps"])
