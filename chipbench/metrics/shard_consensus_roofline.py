"""Share of the HBM roofline, in percent, reached by one chip's consensus
edge step in the edge-partitioned sweep: the least time of the bytes
``chipbench/work/pushsum_edge.py`` says a round must move at one shard's
size (its edges, all N receivers), at the chip's peak bandwidth, over the
device time a round of the ``consensus`` phase takes on a chip
(:meth:`chipbench.phases.Phases.roofline`). The bytes are a lower bound of
the phase's work and the time is all of it, halo included, so no
implementation reads over 100 %. A program without the scope reads
nothing, never 0."""
from chipbench.phases import Phases


def read(ctx):
    return Phases.of(ctx).roofline("pushsum_edge", "consensus")
