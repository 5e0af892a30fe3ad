"""Device time per round, in milliseconds, of the edge-partitioned sweep's
halo: the sum of the shards' (N, d + 1) receiver partials over the chips,
under the program's ``jax.named_scope("halo")``; a collective that XLA
splits into start and done ops takes the span from the start to the done
(:func:`chipbench.halo.halo_s`), averaged over the cell's chips. A program
without the scope reads nothing."""
from chipbench.halo import halo_s


def read(ctx):
    t = halo_s(ctx)
    return t / ctx.rounds * 1e3 if t and ctx.rounds else None
