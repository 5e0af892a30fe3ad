"""Share of the interconnect roofline, in percent, reached by the halo of
the edge-partitioned sweep: the least time of the bytes
``chipbench/work/halo.py`` says each chip sends a round (a ring
all-reduce), at the chip's interconnect peak (``chipbench/peaks_ici.json``),
over the halo's device time a round (:func:`chipbench.halo.halo_s`, the
span from each collective's start to its done). The bytes are the least
any all-reduce sends and the time is the whole collective, so no
implementation reads over 100 %. A program without the scope reads
nothing, never 0."""
from chipbench.halo import halo_s, ici_bytes_per_s
from chipbench.work import halo


def read(ctx):
    t, peak = halo_s(ctx), ici_bytes_per_s(ctx)
    if not t or not peak or not ctx.rounds or "halo" not in ctx.work:
        return None
    least = halo.bytes_per_round(**ctx.work["halo"]) / peak
    return 100.0 * least / (t / ctx.rounds)
