"""Plain reference of robust push-sum (arXiv:2307.14952; Kempe, Dobra and
Gehrke, FOCS 2003, for push-sum itself) over packet-dropping links.

Straight ``jax.numpy`` in float32, written from the paper and importing
nothing of the program. The graph is given receiver by receiver, every
peer with ``L`` in-edges: ``nbr[i, v]`` sends on edge ``v * L + i``. One
round ``t``:

1. links: edge ``e`` delivers when its uniform is at least the drop
   probability, and every edge delivers when ``t % B == B - 1`` (the
   B-round delivery window);
2. each peer adds its share ``1 / (d_out + 1)`` of its value ``z`` and
   mass ``m`` to its running sums ``sigma`` (the cumulative amount it has
   offered each out-link);
3. a delivering edge latches the sender's running sum into ``rho`` (the
   cumulative amount the receiver has heard on it), and each receiver
   adds ``rho_new - rho_old`` of its in-edges to what it kept, ``z
   share``; then it offers its share of the result again (``sigma``) and
   keeps the rest. A dropped packet is carried by the next delivery: no
   mass is lost.

The round's error is ``max_v |z_v / m_v - mean(w)|``; :func:`run` returns
the final ``z / m`` (N, d) and the error of each round (T,).

The randomness is the program's stated stream: one key ``PRNGKey(seed)``,
the link uniforms of round ``t`` from ``fold_in(key, t)``, one per edge in
the program's edge order. The out-degrees are counted on the host.

Per-edge state and gathers are (D, L, N) arrays (``D = d + 1``: the value
columns and the mass), the receivers on the minor axis. They are split by
receiver blocks over the devices given (``jax.sharding``; XLA moves the
node state between them), so a graph too large for one device's memory
runs on several: each holds the in-edges of its block of receivers.
``dtype`` is the storage of every value carried from round to round
(float32, as the configuration states; bfloat16 makes the reference the
lower-precision control); a round's arithmetic runs in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@functools.partial(jax.jit, static_argnames=("T", "dtype", "mesh"))
def _run(nbr, w, share, seed, drop_prob, B, *, T: int, dtype, mesh):
    L, N = nbr.shape
    D = w.shape[1] + 1
    key = jax.random.PRNGKey(seed)
    target = w.mean(axis=0)[:, None]                          # (d, 1)
    edges = NamedSharding(mesh, P(None, None, "blocks"))
    f32 = jnp.float32

    def round_(carry, t):
        x, sigma, rho = carry                  # (D, N) (D, N) (D, L, N)
        x, sigma, rho = (a.astype(f32) for a in (x, sigma, rho))
        # 1. links
        u = jax.random.uniform(jax.random.fold_in(key, t), (N * L,))
        live = (u.reshape(N, L).T >= drop_prob) | (t % B == B - 1)
        # 2. stage the running sums (stored, then read back)
        sigma = (sigma + x * share).astype(dtype).astype(f32)
        sent = jax.lax.with_sharding_constraint(sigma[:, nbr], edges)
        # 3. deliver, integrate, re-stage
        rho_new = jnp.where(live[None], sent, rho)
        x = x * share + (rho_new - rho).sum(axis=1)
        sigma = sigma + x * share
        x = x * share
        err = jnp.abs(x[:-1] / jnp.maximum(x[-1], 1e-30) - target).max()
        return tuple(a.astype(dtype) for a in (x, sigma, rho_new)), err

    x0 = jnp.concatenate([w.T, jnp.ones((1, N), w.dtype)])   # (D, N)
    carry = (x0.astype(dtype), jnp.zeros((D, N), dtype),
             jax.lax.with_sharding_constraint(
                 jnp.zeros((D, L, N), dtype), edges))
    (x, _, _), err = jax.lax.scan(round_, carry,
                                  jnp.arange(T, dtype=jnp.uint32))
    x = x.astype(f32)
    return (x[:-1] / jnp.maximum(x[-1], 1e-30)).T, err


def run(nbr: np.ndarray, w: np.ndarray, seed: int, drop_prob: float,
        B: int, *, T: int, dtype=jnp.float32, devices=None):
    """Robust push-sum for ``T`` rounds from the values ``w`` (N, d).

    ``nbr`` (L, N) int32 in-neighbour table; ``seed`` the call's seed;
    ``devices`` the devices the receiver blocks are spread over (the
    default device alone if None). Returns ``(z / m (N, d), error (T,))``.
    """
    N = nbr.shape[1]
    d_out = np.bincount(nbr.reshape(-1), minlength=N).astype(np.float32)
    mesh = Mesh(np.asarray(devices or jax.devices()[:1]), ("blocks",))
    blocks = NamedSharding(mesh, P(None, "blocks"))
    share = 1.0 / (jax.device_put(d_out[None], blocks) + 1.0)
    return _run(jax.device_put(nbr, blocks),
                jax.device_put(w, NamedSharding(mesh, P())), share,
                jnp.uint32(seed), jnp.float32(drop_prob), jnp.int32(B),
                T=T, dtype=dtype, mesh=mesh)
