"""Device time per round, in milliseconds, of the scan round's ``readout``
phase: what the round stores: the worst log-ratio, the decisions. The leaf
device time of the ops whose name stack holds the program's
``jax.named_scope("readout")``
(:meth:`chipbench.phases.Phases.ms_per_round`), over the rounds of the
traced window. A program without the scope reads nothing."""
from chipbench.phases import Phases


def read(ctx):
    return Phases.of(ctx).ms_per_round("readout")
