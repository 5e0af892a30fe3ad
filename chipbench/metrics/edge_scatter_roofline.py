"""Share of the HBM roofline, in percent, reached by the consensus edge
step: the least time of the bytes ``chipbench/work/pushsum_edge.py`` says a
round must move, at the chip's peak bandwidth, over the device time a round
of the ``consensus`` phase takes (:meth:`chipbench.phases.Phases.roofline`).
The bytes are a lower bound of the phase's work and the time is all of it,
so no implementation reads over 100 %. A program without the scope reads
nothing, never 0."""
from chipbench.phases import ROOFLINES, Phases


def read(ctx):
    return Phases.of(ctx).roofline(*ROOFLINES["edge_scatter_roofline"])
