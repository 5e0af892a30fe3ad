"""Device time per round, in milliseconds, of the innovation step's Mosaic
kernel (``kernels/social_innov``): the custom calls named after its
``innovation_pallas`` wrapper. The kernel body only: the XLA ops around the
call (gathers, the finish) carry no name in the trace that ties them to
it. A lowering without the kernel reads nothing."""


def read(ctx):
    t = ctx.kernel_s("innovation_pallas")
    return t / ctx.rounds * 1e3 if t and ctx.rounds else None
