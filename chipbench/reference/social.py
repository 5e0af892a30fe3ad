"""Plain reference of Algorithm 3 (arXiv:2307.14952): social learning over
a hierarchy with a parameter server and packet-dropping links.

Straight ``jax.numpy`` in float32, written from the paper and imports
nothing of the program. One round ``t``:

1. links: edge ``e`` delivers when its uniform is at least the drop
   probability, and every edge delivers when ``t % B == B - 1`` (the
   B-connectivity window);
2. consensus: robust push-sum with cumulative relays (Su '18, Alg. 1):
   each agent adds its share ``1 / (d_out + 1)`` of ``(z, m)`` to its
   cumulative send ``sigma``; a delivering edge latches the sender's
   ``sigma`` into ``rho`` and the receiver integrates ``rho_new - rho_old``;
   the agent then keeps its own share and re-stages it;
3. innovation: each agent draws one private signal from its truth row by
   inverse CDF and adds the log-likelihoods of every hypothesis to ``z``;
4. belief: ``mu = softmax(z / m)`` (the KL-proximal dual-averaging step);
5. fusion, every ``gamma`` rounds: each representative keeps half of
   ``(z, m)`` and receives the mean of the representatives' halves.

The randomness is the program's stated stream layout, so that the same
seed gives the same links and signals: one key ``PRNGKey(seed)``, the link
uniforms of round ``t`` from ``fold_in(key, 2 t)`` (one per edge) and the
signal uniforms from ``fold_in(key, 2 t + 1)`` (one per agent).

With ``faults`` (the fault plane), three more draws a round, on the fold-in
values ``-(12 t + 3 + k) - 2^21`` for ``k`` = 0 (edges), 1 (agents),
2 (parameter server):

* each edge is a Gilbert-Elliott chain: a good edge turns bad when its
  uniform is below ``p_gb``, a bad one stays bad while its uniform is at
  least ``p_bg``; a bad edge drops at ``drop_bad`` and is not forced by the
  B-window;
* each agent leaves when live and its uniform is below ``leave_prob``, and
  rejoins when dead and its uniform is below ``join_prob``; an edge with a
  dead end delivers nothing, and a dead agent's ``z``, ``m``, ``sigma`` and
  belief stay as they were (a dead agent's belief is zero until its first
  live round);
* the parameter server is down in a round when its uniform is below
  ``ps_crash_prob``, and the fusion of that round is skipped; a fusion
  pools over the live representatives only (the mean of their halves) and
  only they adopt it.

:func:`run` returns the final beliefs, ``z / m`` and the per-round worst
log-ratio ``max_{j, theta != theta*} log mu_j(theta) / mu_j(theta*)``.
The scan over rounds is a ``lax.scan``; nothing is fused by hand.
``dtype`` is the storage of every value carried from round to round
(float32, as the configuration states; bfloat16 makes the reference the
lower-precision control), the arithmetic of a round runs in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TINY = np.float32(np.finfo(np.float32).tiny)   # belief floor of the log


def _segment_sum(x, seg, n):
    return jnp.zeros((n,) + x.shape[1:], x.dtype).at[seg].add(x)


FAULT_BASE = 1 << 21


def _fault_key(key, t, k):
    return jax.random.fold_in(key, -(12 * t + 3 + k) - FAULT_BASE)


@functools.partial(jax.jit, static_argnames=("T", "truth", "M", "dtype"))
def run(src, dst, rep_mask, tables, seed, drop_prob, gamma, B, faults=None,
        *, T: int, truth: int, M: int, dtype=jnp.float32):
    """Algorithm 3 for ``T`` rounds from the zero accumulator.

    ``src``/``dst`` (E,) int32 edges, ``rep_mask`` (N,) bool, ``tables``
    (N, m, S) likelihoods; ``seed`` a uint32 scalar; ``faults`` ``None``
    or the float32 ``(p_gb, p_bg, drop_bad, leave_prob, join_prob,
    ps_crash_prob)``. Returns
    ``(beliefs (N, m), z_over_m (N, m), worst_log_ratio (T,))``.
    """
    N, m, S = tables.shape
    E = src.shape[0]
    key = jax.random.PRNGKey(seed)
    log_tables = jnp.log(tables)
    cdf = jnp.cumsum(tables[:, truth, :], axis=-1)               # (N, S)
    d_out = _segment_sum(jnp.ones((E,), jnp.float32), src, N)
    share = 1.0 / (d_out + 1.0)                                  # (N,)
    rows = jnp.arange(N)

    def round_(carry, t):
        z0, mass0, sigma0, sigma_m0, rho, rho_m, mu0, bad, alive = carry
        forced = t % B == B - 1
        p_drop, up = drop_prob, jnp.ones((E,), bool)
        if faults is not None:
            p_gb, p_bg, drop_bad, leave, join, crash = faults
            u_b = jax.random.uniform(_fault_key(key, t, 0), (E,))
            bad = jnp.where(bad, u_b >= p_bg, u_b < p_gb)
            u_a = jax.random.uniform(_fault_key(key, t, 1), (N,))
            alive = jnp.where(alive, u_a >= leave, u_a < join)
            p_drop = jnp.where(bad, drop_bad, drop_prob)
            forced = forced & ~bad
            up = alive[src] & alive[dst]
        # 1. links
        u_e = jax.random.uniform(jax.random.fold_in(key, 2 * t), (E,))
        live = ((u_e >= p_drop) | forced) & up
        # 2. consensus
        sigma = sigma0 + z0 * share[:, None]
        sigma_m = sigma_m0 + mass0 * share
        rho_new = jnp.where(live[:, None], sigma[src], rho)
        rho_m_new = jnp.where(live, sigma_m[src], rho_m)
        z = z0 * share[:, None] + _segment_sum(rho_new - rho, dst, N)
        mass = mass0 * share + _segment_sum(rho_m_new - rho_m, dst, N)
        sigma = sigma + z * share[:, None]
        sigma_m = sigma_m + mass * share
        z = z * share[:, None]
        mass = mass * share
        keep = alive[:, None]                 # a dead agent stays as it was
        z, sigma = jnp.where(keep, z, z0), jnp.where(keep, sigma, sigma0)
        mass = jnp.where(alive, mass, mass0)
        sigma_m = jnp.where(alive, sigma_m, sigma_m0)
        # 3. innovation
        u = jax.random.uniform(jax.random.fold_in(key, 2 * t + 1), (N,))
        sig = jnp.minimum((cdf < u[:, None]).sum(axis=1), S - 1)
        z = jnp.where(keep, z + log_tables[rows, :, sig], z)
        # 4. belief
        mu = jax.nn.softmax(z / jnp.maximum(mass, 1e-30)[:, None], axis=-1)
        mu = jnp.where(keep, mu, mu0)
        # 5. fusion, over the live representatives
        reps = rep_mask & alive
        fuse = (t + 1) % gamma == 0
        if faults is None:
            count = jnp.float32(M)
        else:
            count = jnp.maximum(reps.sum().astype(jnp.float32), 1.0)
            u_ps = jax.random.uniform(_fault_key(key, t, 2), ())
            fuse = fuse & (u_ps >= crash)
        repf = reps.astype(jnp.float32)
        pooled_z = (z * repf[:, None]).sum(axis=0) / (2.0 * count)
        pooled_m = (mass * repf).sum() / (2.0 * count)
        fuse = fuse & reps
        z = jnp.where(fuse[:, None], 0.5 * z + pooled_z, z)
        mass = jnp.where(fuse, 0.5 * mass + pooled_m, mass)
        log_mu = jnp.log(jnp.maximum(mu, TINY))
        lr = log_mu - log_mu[:, truth:truth + 1]
        worst = jnp.where(jnp.arange(m) == truth, -jnp.inf, lr).max()
        stored = tuple(x.astype(dtype) for x in (z, mass, sigma, sigma_m,
                                                 rho_new, rho_m_new, mu))
        return stored + (bad, alive), worst

    carry = tuple(x.astype(dtype) for x in (
        jnp.zeros((N, m)), jnp.ones((N,)), jnp.zeros((N, m)),
        jnp.zeros((N,)), jnp.zeros((E, m)), jnp.zeros((E,)),
        jnp.zeros((N, m)))) + (jnp.zeros((E,), bool), jnp.ones((N,), bool))
    (z, mass, *_, mu, _, _), worst = jax.lax.scan(
        round_, carry, jnp.arange(T, dtype=jnp.int32))
    z, mass, mu = (x.astype(jnp.float32) for x in (z, mass, mu))
    return mu, z / jnp.maximum(mass, 1e-30)[:, None], worst
