"""Device time per round, in milliseconds, of the scan round's
``innovation`` phase: the signal draws and the accumulator update (the
social ``innovation_pallas`` kernel; the Byzantine engine's vmapped
``searchsorted`` and its gathers). The leaf device time of the ops whose
name stack holds the program's ``jax.named_scope("innovation")``
(:meth:`chipbench.phases.Phases.ms_per_round`), over the rounds of the
traced window. A program without the scope reads nothing."""
from chipbench.phases import Phases


def read(ctx):
    return Phases.of(ctx).ms_per_round("innovation")
