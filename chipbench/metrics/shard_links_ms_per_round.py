"""Device time per round, in milliseconds, of the edge-partitioned sweep's
``links`` phase: the full-length mask draw every chip makes and its own
window of it (``repro.core.pushsum.shard_edge_mask``). The leaf device time
of the ops whose name stack holds the program's
``jax.named_scope("links")``, averaged over the cell's chips
(:meth:`chipbench.phases.Phases.ms_per_round`). A program without the
scope reads nothing."""
from chipbench.phases import Phases


def read(ctx):
    return Phases.of(ctx).ms_per_round("links")
