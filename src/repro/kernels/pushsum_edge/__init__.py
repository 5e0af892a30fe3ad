"""Fused edge-scatter kernel for the robust push-sum delivery/integration.

One push-sum round's hot half is, per directed edge e (src -> dst):

    rho_new[e] = sigma[src[e]]  if live[e] else rho[e]     (mask-latch)
    recv[v]   += rho_new[e] - rho[e]  for v = dst[e]       (integration)

XLA lowers this to a gather plus a generic ``segment_sum`` scatter per
round. With the edge index pre-sorted by ``dst``
(:func:`repro.core.graphs.sort_by_dst`) each receiver's increments form
one contiguous run, and the Pallas kernel in :mod:`.pushsum_edge` streams
E once to latch and reduce every run to one partial sum; the gather before
it and the per-receiver finish after it (a ``segment_sum``, or a gather at
each receiver's run end when the caller supplies the run ends) stay in
XLA. :mod:`.ref` is the
always-available XLA fallback and the equivalence oracle; :mod:`.ops`
hosts the ``backend="auto"|"xla"|"pallas"`` dispatch used by
:func:`repro.core.pushsum.sparse_pushsum_step`.
"""
from .ops import edge_scatter, resolve_backend
from .ref import edge_scatter_ref

__all__ = ["edge_scatter", "edge_scatter_ref", "resolve_backend"]
