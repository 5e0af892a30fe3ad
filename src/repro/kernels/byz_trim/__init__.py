"""Fused neighbor trim-gather for the sparse Byzantine gossip core.

One Algorithm 2 gossip round's hot half is, per receiver j on the padded
neighbor-list layout (:class:`repro.core.graphs.NeighborList` — ``nbr_idx``
(N, deg_max) sender indices + ``nbr_valid`` padding mask):

    vals[j, k] = attack value        if sender nbr_idx[j, k] is Byzantine
                 r[nbr_idx[j, k]]    otherwise                  (gather)
    drop invalid slots, then the F largest and F smallest       (trim)
    trimmed_sum[j] = sum of survivors;  kept[j] = max(deg_j - 2F, 0)

applied independently per pair coordinate (the paper's scalar-dynamics
trick). The dense seed lowering broadcast an (N, N, m, m) message tensor
and ran ``jnp.sort`` over the full sender axis — O(N^2 m^2 log N) compute,
O(N^2 m^2) memory; on the neighbor-list layout the same contract costs
O(N deg_max m^2 F) with nothing larger than (N, deg_max, m^2) live.

:mod:`.ref` is the always-available XLA oracle (sort + rank mask; accepts a
traced F, which is what batched (topology, F) sweeps vmap over); :mod:`.ops`
hosts the ``backend="auto"|"xla"|"pallas"`` dispatch used by
:func:`repro.core.byzantine.make_byzantine_scan`; :mod:`.byz_trim` is the
fused Pallas trim (F-round extremes extraction, no sort) behind an XLA
gather of the slot values. The dense ``trimmed_neighbor_mean`` in
:mod:`repro.core.byzantine` is retained purely as the equivalence oracle
for tests.
"""
from .ops import BACKENDS, resolve_backend, trim_gather, trim_gather_pairs
from .ref import trim_gather_ref

__all__ = [
    "trim_gather",
    "trim_gather_pairs",
    "trim_gather_ref",
    "resolve_backend",
    "BACKENDS",
]
