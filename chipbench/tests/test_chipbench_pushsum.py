"""The edge-partitioned push-sum cell's own pieces (CPU only; nothing here
touches a TPU): the reference's copy of the Chord overlay held to the
program's builder, the halo's wire bytes, and its six per-layer metrics
read from hand-made events on four ``/device:TPU:<n>`` chips."""
import os

import numpy as np
import pytest

from chipbench.tests import small  # noqa: F401  (puts src on sys.path)
from chipbench import halo, inputs_chord, run, trace
from chipbench.trace import Op, Span
from chipbench.work import halo as halo_work
from chipbench.work.peaks import peaks

V5E = peaks("TPU v5 lite")
SCAN = "jit(_sweep_edge_sharded_body)/shard_map/vmap()/while/body/closed_call/"
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "social-small.xplane.pb")
NEW = ("shard_links_ms_per_round", "shard_consensus_ms_per_round",
       "shard_consensus_roofline", "halo_ms_per_round", "halo_roofline",
       "pushsum_prepare_ms_per_call")
MS = 1e6


def _metric(name, ctx):
    return run.load_module(os.path.join(run.HERE, "metrics",
                                        name + ".py")).read(ctx)


@pytest.mark.parametrize("n_bits, fingers", [(4, 4), (9, 5), (12, 12)])
def test_reference_overlay_equals_the_builder(n_bits, fingers):
    from repro.core.graphs import chord_edge_list

    el = chord_edge_list(n_bits, fingers)
    nbr = inputs_chord.chord_in_neighbours(n_bits, fingers)
    assert nbr.shape == (fingers, 2 ** n_bits) and nbr.dtype == np.int32
    # edge v * L + i of the program is nbr[i, v] -> v
    np.testing.assert_array_equal(el.src, nbr.T.reshape(-1))
    np.testing.assert_array_equal(el.dst, np.repeat(np.arange(2 ** n_bits),
                                                    fingers))


def test_values_come_from_the_seed():
    a, b = inputs_chord.values(7, 64, 3), inputs_chord.values(7, 64, 3)
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.float32 and a.shape == (64, 3)
    assert not np.array_equal(a, inputs_chord.values(8, 64, 3))


def test_halo_bytes_by_hand():
    # a ring all-reduce sends (S - 1) / S of the (N, d + 1) float32 array
    # in each of its two halves: 2 * 3 / 4 * 8 * 4 * 4 = 192
    assert halo_work.bytes_per_round(N=8, d=3, S=4) == 192
    assert halo_work.bytes_per_round(N=2 ** 24, d=3, S=4) == 402653184


def _ctx(ops, tf_ops, spans=(), rounds=2, work=None):
    bench = [Span("chipbench.call", 0, 40 * MS),
             Span("chipbench.block", 1 * MS, 40 * MS)]
    return trace.Context(
        ops, bench, calls=1, rounds=rounds, host_call_s=[1e-3],
        work=work or {"pushsum_edge": {"N": 1024, "E": 4096, "d": 3},
                      "halo": {"N": 1024, "d": 3, "S": 4}},
        peaks=V5E, tf_ops=tf_ops, program_spans=list(spans))


LINKS = "%fusion.1 = pred[4096]{0} fusion(u32[] %p), kind=kLoop"
GATHER = "%fusion.2 = f32[4,4096]{1,0} fusion(f32[1024,4] %p), kind=kLoop"
ALLREDUCE = "%psum.3 = f32[1,1024,3]{2,1,0} all-reduce(f32[1,1024,3] %p)"
READOUT = "%fusion.4 = f32[] fusion(f32[1024,3] %p), kind=kLoop"
TF_OPS = {LINKS: SCAN + "links/jit(_uniform)/add:",
          GATHER: SCAN + "consensus/jit(edge_scatter_pallas)/gather:",
          ALLREDUCE: SCAN + "consensus/halo/psum:",
          READOUT: SCAN + "readout/reduce_max:"}


def _four_chips():
    """Two rounds on each of four chips; chip ``c`` takes ``c + 1`` times
    as long for every op, so an average over chips reads 2.5 times one
    chip's unit and a sum over chips would read 10 times it."""
    ops = []
    for c in range(4):
        f = c + 1
        for r in range(2):
            t = (2 + 15 * r) * MS
            ops += [Op(c, LINKS, t, t + 1 * f * MS / 4),
                    Op(c, GATHER, t + 1 * MS, t + 1 * MS + 2 * f * MS / 4),
                    Op(c, ALLREDUCE, t + 4 * MS, t + 4 * MS + 1 * f * MS / 4),
                    Op(c, READOUT, t + 8 * MS, t + 8 * MS + 0.4 * f * MS / 4)]
    return ops


def test_four_chip_metrics_are_averaged_per_chip():
    ctx = _ctx(_four_chips(), TF_OPS)
    assert ctx.chips == [0, 1, 2, 3]
    mean = 2.5 / 4                           # the chips' mean factor, ms
    got = {n: _metric(n, ctx) for n in NEW}
    assert got["shard_links_ms_per_round"] == pytest.approx(1 * mean)
    # the halo op nested in consensus counts in consensus and in the halo
    assert got["shard_consensus_ms_per_round"] == pytest.approx(3 * mean)
    assert got["halo_ms_per_round"] == pytest.approx(1 * mean)
    least = 4096 * 40 + 1024 * 32          # pushsum_edge bytes, as by hand
    assert got["shard_consensus_roofline"] == pytest.approx(
        100 * least / 819e9 / (3 * mean * 1e-3))
    assert got["halo_roofline"] == pytest.approx(
        100 * halo_work.bytes_per_round(1024, 3, 4) / 200e9
        / (1 * mean * 1e-3))
    assert got["pushsum_prepare_ms_per_call"] is None


def test_async_halo_spans_start_to_done():
    """A collective XLA splits in two takes the span from its start op to
    its done op, with the other ops of the round between them, on every
    chip; a done op that names no open start closes the earliest one."""
    start = ("%all-reduce-start.5 = f32[1,1024,3]{2,1,0} "
             "all-reduce-start(f32[1,1024,3] %bitcast.9)")
    done = ("%all-reduce-done.5 = f32[1,1024,3]{2,1,0} "
            "all-reduce-done(f32[1,1024,3] %all-reduce-start.5)")
    start2 = ("%all-reduce-start.6 = f32[1,1024]{1,0} "
              "all-reduce-start(f32[1,1024] %bitcast.10)")
    done2 = "%all-reduce-done.6 = f32[1,1024]{1,0} all-reduce-done()"
    tf = dict(TF_OPS)
    for name in (start, done, start2, done2):
        tf[name] = SCAN + "consensus/halo/psum:"
    ops = []
    for c in range(4):
        ops += [Op(c, start, 2 * MS, 2.01 * MS),
                Op(c, start2, 2.02 * MS, 2.03 * MS),
                Op(c, GATHER, 2.5 * MS, 3.5 * MS),        # inside the span
                Op(c, done, 4.0 * MS, 4.1 * MS),
                Op(c, done2, 5.0 * MS, 5.1 * MS)]
    ctx = _ctx(ops, tf, rounds=1)
    per_chip = halo.intervals(ctx)
    assert per_chip == {c: [(2 * MS, 5.1 * MS)] for c in range(4)}
    assert _metric("halo_ms_per_round", ctx) == pytest.approx(3.1)
    # the two ops' own time would read 0.22 ms and the roofline 14x higher
    share = _metric("halo_roofline", ctx)
    assert share == pytest.approx(100 * halo_work.bytes_per_round(1024, 3, 4)
                                  / 200e9 / 3.1e-3)
    assert 0 < share < 100


def test_prepare_span_is_the_median_in_the_window():
    spans = [Span("repro.pushsum.prepare", s * MS, (s + d) * MS)
             for s, d in [(2, 1.0), (10, 3.0), (20, 2.0), (45, 9.0)]]
    spans.append(Span("repro.pushsum.dispatch", 3 * MS, 4 * MS))
    ctx = _ctx(_four_chips(), TF_OPS, spans)
    assert _metric("pushsum_prepare_ms_per_call", ctx) == pytest.approx(2.0)


def test_a_program_without_the_scopes_reads_nothing():
    """The parent's program opens neither ``halo`` nor the push-sum spans:
    those metrics read None and do not raise; a trace of another device
    kind has no interconnect peak."""
    ctx = trace.Context.from_file(
        RECORDED, calls=2, rounds=8, host_call_s=[1e-3, 3e-3],
        work={"pushsum_edge": {"N": 1024, "E": 7168, "d": 3},
              "halo": {"N": 1024, "d": 3, "S": 4}}, peaks=V5E)
    for name in ("halo_ms_per_round", "halo_roofline",
                 "pushsum_prepare_ms_per_call"):
        assert _metric(name, ctx) is None, name
    assert halo.ici_bytes_per_s(_ctx(_four_chips(), TF_OPS)) == 200e9
    other = _ctx(_four_chips(), TF_OPS)
    other.peaks = {"hbm_bytes_per_s": 1.0}
    assert halo.ici_bytes_per_s(other) is None
    assert _metric("halo_roofline", other) is None
