"""Device time per round, in milliseconds, of the scan round's ``gossip``
phase: the Byzantine engine's trimmed gossip: the neighbour gathers, the
``byz_trim`` Mosaic body, and the update. The leaf device time of the ops
whose name stack holds the program's ``jax.named_scope("gossip")``
(:meth:`chipbench.phases.Phases.ms_per_round`), over the rounds of the
traced window. A program without the scope reads nothing."""
from chipbench.phases import Phases


def read(ctx):
    return Phases.of(ctx).ms_per_round("gossip")
