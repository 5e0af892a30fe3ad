"""Inputs of the plain push-sum reference (``chipbench/reference/
pushsum.py``), made by the benchmark itself.

The program under test builds its overlay in the set-up with its public
builder (``graphs.chord_edge_list``), as a user does. The reference takes
nothing the program made, so :func:`chord_in_neighbours` is its own copy
of the Chord finger rule (a test holds it equal to the builder), and
:func:`values` makes the peers' values, the data both sides are given,
from the run's seed:

* :func:`chord_in_neighbours` — on a fully populated ring of ``N =
  2^n_bits`` identifiers, peer ``v``'s i-th finger is ``v + 2^i mod N``
  (Stoica et al., SIGCOMM 2001, section 4), so ``v`` hears from ``v - 2^i
  mod N``; row ``i`` of the (L, N) table is that sender for every ``v``.
  Edge ``v * L + i`` of the program's edge list is ``table[i, v] -> v``;
* :func:`values` — the (N, d) float32 values whose average the peers
  compute: standard normal draws from the seed's own host stream.
"""
from __future__ import annotations

import numpy as np

from chipbench import inputs


def chord_in_neighbours(n_bits: int, fingers: int) -> np.ndarray:
    """(L, N) int32: ``[i, v]`` is ``v - 2^i mod N``, the sender of peer
    ``v``'s i-th in-edge (``L = fingers``)."""
    N = 2 ** n_bits
    v = np.arange(N, dtype=np.int64)
    return np.stack([(v - 2 ** i) % N for i in range(fingers)]).astype(
        np.int32)


def values(seed: int, N: int, d: int) -> np.ndarray:
    """(N, d) float32 values of the peers, from the run's seed."""
    return inputs.rng(seed, 2).standard_normal((N, d), dtype=np.float32)
