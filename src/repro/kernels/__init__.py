"""Pallas TPU kernels for the framework's compute hot-spots.

- ``trimmed_mean`` — the Byzantine filter of Algorithm 2 applied
  coordinate-wise over the worker axis (the paper's scalar-dynamics trick
  vectorized over every gradient coordinate).
- ``pushsum_edge`` — fused edge-scatter for the sparse robust push-sum
  core: mask-latch and per-receiver run sums of the increments in one
  streaming pass over a dst-sorted edge index, between an XLA gather of
  ``sigma[src]`` and an XLA ``segment_sum`` finish (Algorithm 1's
  per-round hot path at N ~ 1e5).
- ``byz_trim`` — fused neighbor trim for the sparse Byzantine gossip
  core: after an XLA gather over a padded neighbor list with
  Byzantine-message substitution, the F-round extremes-extraction trim
  in one streaming pass over receiver blocks (Algorithm 2's per-round
  hot path).
- ``social_innov`` — fused innovation + belief step for the Algorithm 3
  social-learning engine: inverse-CDF signal sampling, the log-likelihood
  table gather, dual accumulation, and the KL-proximal softmax belief in
  one streaming pass over agent blocks (Algorithm 3's per-round hot path
  alongside ``pushsum_edge``).
- ``wkv6`` — chunked RWKV6 linear recurrence with data-dependent decay
  (rwkv6-1.6b's training/prefill hot-spot).
- ``swa`` — flash-decode attention over a sliding-window KV cache
  (decode_32k / long_500k serve hot-spot for the dense GQA archs).

All kernels use ``pl.pallas_call`` with explicit BlockSpec VMEM tiling and
are validated against their pure-jnp ``ref.py`` oracles via
``interpret=True`` on CPU (see tests/test_kernels.py).

Backend dispatch is shared repo-wide through :mod:`repro.kernels.dispatch`
(``backend="auto"|"xla"|"pallas"``). The model-stack kernels (``swa``,
``wkv6``, ``trimmed_mean``) predate the engine kernels and carried a
seed-era ``use_kernel`` boolean for the layers/aggregation callers; that
alias was removed in PR 10 — ``backend=`` is the one switch everywhere,
and the ``repro.statics.signatures`` lint keeps retired execution kwargs
from re-growing. They serve the seed model stack only — no Algorithm 1-3
engine calls them — pending ROADMAP's model-stack integration item.
"""
from .trimmed_mean.ops import trimmed_mean, trimmed_mean_pytree
from .pushsum_edge.ops import edge_scatter
from .byz_trim.ops import trim_gather, trim_gather_pairs
from .social_innov.ops import innovation_step
from .wkv6.ops import wkv6, wkv6_decode_step
from .swa.ops import attn_decode
from .swa.prefill import swa_prefill_pallas

__all__ = [
    "trimmed_mean",
    "trimmed_mean_pytree",
    "edge_scatter",
    "trim_gather",
    "trim_gather_pairs",
    "innovation_step",
    "wkv6",
    "wkv6_decode_step",
    "attn_decode",
    "swa_prefill_pallas",
]
