"""Host time of the ``run_*`` entry's preparation, in milliseconds: the
median over the traced window's calls of the program's own span
``repro.<entry>.prepare`` (``repro.social.prepare``,
``repro.byz_grid.prepare``: the plan, the tables, the keys; the Byzantine
grid's fingerprints, cache lookups and batching), which runs before the
dispatch while the device waits
(:meth:`chipbench.phases.Phases.host_prepare_ms_per_call`). A program
without the span reads nothing."""
from chipbench.phases import Phases


def read(ctx):
    return Phases.of(ctx).host_prepare_ms_per_call()
