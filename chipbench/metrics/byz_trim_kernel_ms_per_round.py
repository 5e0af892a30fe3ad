"""Device time per round, in milliseconds, of the Byzantine trim's Mosaic
kernel (``kernels/byz_trim``): the custom calls named after its
``trim_gather_pallas`` wrapper. The kernel body only: the XLA ops around the
call (gathers, the finish) carry no name in the trace that ties them to
it. A lowering without the kernel reads nothing."""


def read(ctx):
    t = ctx.kernel_s("trim_gather_pallas")
    return t / ctx.rounds * 1e3 if t and ctx.rounds else None
