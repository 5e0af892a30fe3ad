"""Device time per round, in milliseconds, of the edge-partitioned sweep's
``consensus`` phase: on each chip the ``sigma[src]`` gather of its shard,
the ``pushsum_edge`` Mosaic body, the finish (the gather at the run ends of
the shard's receivers) and the ``halo`` sum over the chips nested in it.
The leaf device time of the ops whose name stack holds the program's
``jax.named_scope("consensus")``, averaged over the cell's chips
(:meth:`chipbench.phases.Phases.ms_per_round`). A program without the
scope reads nothing."""
from chipbench.phases import Phases


def read(ctx):
    return Phases.of(ctx).ms_per_round("consensus")
