"""Share of the traced window, in percent, in which no operation ran on
the device: 1 - (union of leaf-op intervals) / window. A leaf op holds
no other op, so the gaps between the rounds' ops inside a call's scan
count as idle, as do the gaps between calls."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s) if ctx.window_s > 0 \
        else None
