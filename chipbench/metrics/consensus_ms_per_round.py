"""Device time per round, in milliseconds, of the scan round's ``consensus``
phase: the social engine's push-sum edge step: the ``sigma[src]`` gather,
the ``pushsum_edge`` Mosaic body, and the finish (the gather at each
receiver's run end where the edge index is dst-sorted, as in both social
cells; the ``segment_sum`` scatter elsewhere). The leaf device time of the
ops whose name stack holds the program's ``jax.named_scope("consensus")``
(:meth:`chipbench.phases.Phases.ms_per_round`), over the rounds of the
traced window. A program without the scope reads nothing."""
from chipbench.phases import Phases


def read(ctx):
    return Phases.of(ctx).ms_per_round("consensus")
