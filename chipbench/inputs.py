"""Inputs of the plain references (``chipbench/reference``), made from the
run's seed by the benchmark itself.

The program under test builds its own inputs in the set-up, through its
public builders (``graphs.hier_edge_list``, ``graphs.make_hierarchy``,
``signals.make_confused_model``), as a user of it does. The reference
takes nothing the program made, so these are copies of those builders
that import nothing of the program and draw the same numbers from the same
seed (a test holds them to the program's builders):

* :func:`complete_blocks` — ``M`` sub-networks of ``n`` agents each, every
  sub-network a complete digraph (``n (n - 1)`` directed edges), in the
  dst-sorted order ``hier_edge_list`` emits, the first agent of each block
  its representative;
* :func:`confused_tables` — the locally confused, globally observable
  finite-alphabet signal model of ``make_confused_model``: Dirichlet rows
  of concentration ``sharpness``, a ``confusion`` share of agents made
  uninformative, every informative agent ``j`` distinguishing hypothesis
  ``j % m`` from the rest, probabilities floored at 0.02;
* :func:`call_seed` — the seed of call ``i`` of a run.
"""
from __future__ import annotations

import numpy as np

SEED_SPACE = 2 ** 32   # a PRNG key is made from the low 32 bits of a seed


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent host stream ``stream`` of a run's ``seed``."""
    return np.random.default_rng([int(seed) % 2 ** 63, stream])


def call_seed(seed: int, i: int) -> int:
    """Seed of call ``i``: ``seed * 1000 + i``, kept inside the 32 bits a
    PRNG key is made from (a larger value would be cut silently)."""
    return (int(seed) * 1000 + int(i)) % SEED_SPACE


def complete_blocks(M: int, n: int) -> tuple[np.ndarray, np.ndarray,
                                             np.ndarray]:
    """``(src, dst, rep_mask)``: int32 edges of ``M`` complete ``n``-agent
    blocks, dst-sorted (by receiver, senders ascending within a
    receiver), and the (N,) bool mask of each block's first agent."""
    dst_l = np.repeat(np.arange(n), n)
    src_l = np.tile(np.arange(n), n)
    keep = src_l != dst_l
    src_l, dst_l = src_l[keep], dst_l[keep]              # (n (n - 1),)
    off = (np.arange(M, dtype=np.int64) * n)[:, None]
    src = (off + src_l[None]).reshape(-1).astype(np.int32)
    dst = (off + dst_l[None]).reshape(-1).astype(np.int32)
    rep = np.zeros(M * n, bool)
    rep[::n] = True
    return src, dst, rep


def confused_tables(N: int, m: int, S: int, confusion: float,
                    sharpness: float, seed: int) -> np.ndarray:
    """(N, m, S) float32 likelihood tables; rows sum to one. The draws
    are ``make_confused_model``'s, one after another from
    ``default_rng(seed)``, so the same seed gives the same tables."""
    if N < m:
        raise ValueError("need N >= m agents to cover every hypothesis")
    gen = np.random.default_rng(seed)
    alpha = np.ones(S) * sharpness
    base = gen.dirichlet(alpha, size=(N,))                 # (N, S)
    tables = np.repeat(base[:, None, :], m, axis=1)
    informative = np.ones(N, bool)
    informative[gen.permutation(N)[: int(confusion * N)]] = False
    for k in range(m):                 # one informative agent per hypothesis
        if not informative[k::m].any():
            informative[k] = True
    for j in np.nonzero(informative)[0]:
        distinct = gen.dirichlet(alpha)
        while np.abs(distinct - base[j]).sum() < 0.2:   # too like the base
            distinct = gen.dirichlet(alpha)
        tables[j, j % m, :] = distinct
    tables = np.maximum(tables, 0.02)
    tables = tables / tables.sum(axis=-1, keepdims=True)
    return tables.astype(np.float32)
