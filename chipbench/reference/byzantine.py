"""Plain reference of Algorithm 2 (arXiv:2307.14952): Byzantine-resilient
learning over a hierarchy of complete sub-networks, pairwise mode.

Straight ``jax.numpy`` in float32, written from the paper and imports
nothing of the program. Every agent ``j`` keeps one scalar statistic per
ordered hypothesis pair, ``r_j (m, m)``, and the cumulative log-likelihood
ratio of its own signals, ``c_j (m, m)``. One round ``t``:

1. innovation: one private signal per agent by inverse CDF of its truth
   row; ``c_j(a, b) += log l_j(s | a) - log l_j(s | b)``;
2. gossip, inside healthy networks only (the set C): a normal agent
   receives ``r`` from every other agent of its network, where a
   Byzantine sender sends the attack's value instead; per pair it drops
   the F largest and the F smallest values received, and sets
   ``r_j = (sum of the rest + r_j) / (count of the rest + 1) + c_j``;
3. fusion, every ``gamma`` rounds: the parameter server picks one
   representative uniformly in each network, a Byzantine representative
   answers the attack's value, the server drops the F largest and F
   smallest answers per pair and averages the rest, and the picked
   representatives outside C adopt that average;
4. Byzantine agents' own statistic is held at zero;
5. decision: ``argmax_a min_{b != a} r_j(a, b)``.

Healthy networks: at most a third of the agents Byzantine, the block
satisfies the reduced-graph condition (a complete block does iff it has at
least ``3 F + 1`` agents; Vaidya, Tseng and Liang 2012), and its normal
agents, with the F largest contributors to each pair's KL divergence left
out, still tell every pair apart.

The attack is ``large_value``: every Byzantine message and reply is the
constant ``magnitude``. The randomness is the program's stated stream
layout, so that the same seed gives the same signals and picks: one key
``PRNGKey(seed)``; the signal uniforms of round ``t`` from
``fold_in(key, 3 t)`` and the fusion picks from ``fold_in(key, 3 t + 2)``,
split into one key per network, each drawing ``randint(0, size)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def healthy(tables: np.ndarray, byz: np.ndarray, M: int, n: int,
            F: int) -> np.ndarray:
    """(M,) bool: networks in C (module docstring)."""
    t = tables.astype(np.float64)
    logt = np.log(t)
    # kl[j, a, b] = KL(l_j(. | a) || l_j(. | b))
    kl = (np.einsum("nas,nas->na", t, logt)[:, :, None]
          - np.einsum("nas,nbs->nab", t, logt))
    out = np.zeros(M, bool)
    m = tables.shape[1]
    off_diag = ~np.eye(m, dtype=bool)
    for i in range(M):
        b = byz[i * n:(i + 1) * n]
        if 3 * b.sum() >= n or n < 3 * F + 1:
            continue
        contrib = np.sort(kl[i * n:(i + 1) * n][~b], axis=0)   # ascending
        kept = contrib[:-F] if F > 0 else contrib
        out[i] = bool((kept.sum(axis=0)[off_diag] > 1e-9).all())
    return out


def _trim_sum(vals, F):
    """Sum over axis 1 after dropping the F largest and F smallest."""
    s = jnp.sort(vals, axis=1)
    return s[:, F:vals.shape[1] - F].sum(axis=1)


@functools.partial(jax.jit, static_argnames=("T", "truth", "n", "F"))
def run(tables, byz, in_c, seed, gamma, magnitude, *, T: int, truth: int,
        n: int, F: int):
    """Algorithm 2 for ``T`` rounds from zero statistics.

    ``tables`` (N, m, S), ``byz`` (N,) bool Byzantine agents, ``in_c``
    (N,) bool agents of healthy networks, ``seed`` a uint32 scalar; the
    agents form ``N / n`` complete blocks of ``n``. Returns
    ``(r (N, m, m), decisions (T, N))``.
    """
    N, m, S = tables.shape
    M = N // n
    key = jax.random.PRNGKey(seed)
    log_tables = jnp.log(tables)
    cdf = jnp.cumsum(tables[:, truth, :], axis=-1)
    rows = jnp.arange(N)
    # the other n - 1 agents of each agent's block, ascending
    local = jnp.arange(n)
    others = jnp.stack([jnp.concatenate([local[:k], local[k + 1:]])
                        for k in range(n)])                    # (n, n - 1)
    nbr = ((rows // n) * n)[:, None] + others[rows % n]        # (N, n - 1)
    active = in_c & ~byz
    offsets = jnp.arange(M) * n
    eye = jnp.eye(m, dtype=bool)
    pair = (slice(None), None, None)

    def decide(r):
        return jnp.where(eye, jnp.inf, r).min(axis=-1).argmax(axis=-1)

    def round_(carry, t):
        r, c = carry
        # 1. innovation
        u = jax.random.uniform(jax.random.fold_in(key, 3 * t), (N,))
        sig = jnp.minimum((cdf < u[:, None]).sum(axis=1), S - 1)
        ll = log_tables[rows, :, sig]                          # (N, m)
        c = c + (ll[:, :, None] - ll[:, None, :])
        # 2. gossip
        vals = jnp.where(byz[nbr][:, :, None, None], magnitude, r[nbr])
        gossip = (_trim_sum(vals, F) + r) / (n - 1 - 2 * F + 1) + c
        r_new = jnp.where(active[pair], gossip, r)

        # 3. fusion
        def fuse(r_in):
            ks = jax.random.split(jax.random.fold_in(key, 3 * t + 2), M)
            pick = jax.vmap(lambda k: jax.random.randint(k, (), 0, n))(ks)
            reps = offsets + pick                              # (M,)
            answers = jnp.where(byz[reps][:, None, None], magnitude,
                                r_in[reps])
            w = _trim_sum(answers[None], F)[0] / (M - 2 * F)
            adopt = jnp.zeros((N,), bool).at[reps].set(True) & ~in_c
            return jnp.where(adopt[pair], w, r_in)

        r_new = jax.lax.cond((t + 1) % gamma.astype(t.dtype) == 0, fuse,
                             lambda x: x, r_new)
        # 4. Byzantine agents' own statistic
        r_new = jnp.where(byz[pair], 0.0, r_new)
        return (r_new, c), decide(r_new)

    zeros = jnp.zeros((N, m, m), jnp.float32)
    (r, _), decisions = jax.lax.scan(
        round_, (zeros, zeros), jnp.arange(T, dtype=jnp.uint32))
    return r, decisions
