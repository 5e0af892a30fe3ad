"""Device busy time per scan round, in milliseconds: the union of the
device's leaf-op intervals in the traced window (a container such as the
scan's ``while`` is left out, so idle time inside it is not counted) over
the rounds the window's calls ran. Each call is one scan, so this is the
scan core's time per round with everything it runs: the PRNG draws, the
kernels and the XLA ops around them, the fusion and the readouts."""


def read(ctx):
    if not ctx.rounds or ctx.busy_s <= 0:
        return None
    return ctx.busy_s / ctx.rounds * 1e3
