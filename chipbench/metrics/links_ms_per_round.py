"""Device time per round, in milliseconds, of the scan round's ``links``
phase: the fault plane's draws and the round's edge mask: the
Gilbert-Elliott chains, churn, and the liveness gathers at each edge's
``src`` and ``dst``. The leaf device time of the ops whose name stack holds
the program's ``jax.named_scope("links")``
(:meth:`chipbench.phases.Phases.ms_per_round`), over the rounds of the
traced window. A program without the scope reads nothing."""
from chipbench.phases import Phases


def read(ctx):
    return Phases.of(ctx).ms_per_round("links")
