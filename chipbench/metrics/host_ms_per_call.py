"""Host time of one call of the ``run_*`` entry, in milliseconds: padding,
stacking, the jit-cache lookup and dispatch, from the call to its return
(before ``block_until_ready``), timed by the benchmark's own span. The
median over the traced window's calls."""
import statistics


def read(ctx):
    return statistics.median(ctx.host_call_s) * 1e3 if ctx.host_call_s \
        else None
