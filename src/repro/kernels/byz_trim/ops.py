"""Backend dispatch for the fused Byzantine trim-gather.

``trim_gather(..., backend=...)`` is the single entry point the sparse
Byzantine core calls per gossip round:

``"xla"``     — gather + sort + rank mask (:mod:`.ref`); runs anywhere and
                accepts a *traced* F (dynamic-F scenario batches).
``"pallas"``  — XLA gather + the O(F * deg) extraction kernel
                (:mod:`.byz_trim`);
                compiled on TPU, interpreter mode elsewhere (equivalence
                testing only — interpret mode is not a fast path). Requires
                a static int F (the extraction loop unrolls).
``"auto"``    — ``"pallas"`` on a TPU default backend, else ``"xla"``.

Resolution is host-side and static (the choice changes the traced program),
so callers thread ``backend`` through ``static_argnames`` when jitting.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import BACKENDS, resolve_backend
from .byz_trim import trim_gather_pallas
from .ref import trim_gather_ref

__all__ = ["trim_gather", "trim_gather_pairs", "resolve_backend", "BACKENDS"]


def trim_gather(
    r: jnp.ndarray,         # (N, P)
    nbr_idx: jnp.ndarray,   # (N, deg_max) int32
    nbr_valid: jnp.ndarray, # (N, deg_max) bool
    byz_msgs: jnp.ndarray,  # (N, deg_max, P)
    byz_nbr: jnp.ndarray,   # (N, deg_max) bool
    F,
    backend: str = "auto",
    *,
    block_n: int = 1024,
    interpret: bool | None = None,
    indices_sorted: bool = False,
    accum_dtype: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused gather + Byzantine substitution + 2F trim; see package docstring.

    Returns ``(trimmed_sum (N, P), kept (N,))``. ``indices_sorted=True``
    promises the flattened ``nbr_idx`` traversal is non-decreasing (only the
    single-row pool layout of ``ps_trimmed_pool`` qualifies — general
    neighbor lists do not). ``accum_dtype`` names the survivor-sum dtype
    (the precision policy's accum slot); ``None`` keeps ``r.dtype``.
    """
    if resolve_backend(backend) == "xla":
        return trim_gather_ref(r, nbr_idx, nbr_valid, byz_msgs, byz_nbr, F,
                               indices_sorted=indices_sorted,
                               accum_dtype=accum_dtype)
    if not isinstance(F, int):
        raise ValueError(
            "backend='pallas' needs a static int F (the extraction loop "
            "unrolls); use backend='xla' for traced per-scenario F"
        )
    return trim_gather_pallas(
        r, nbr_idx, nbr_valid, byz_msgs, byz_nbr, F,
        block_n=block_n, interpret=interpret, accum_dtype=accum_dtype,
    )


def trim_gather_pairs(
    r: jnp.ndarray,         # (N, *pair) — e.g. (N, m, m) or (N, m)
    nbr_idx: jnp.ndarray,
    nbr_valid: jnp.ndarray,
    byz_msgs: jnp.ndarray,  # (N, deg_max, *pair)
    byz_nbr: jnp.ndarray,
    F,
    backend: str = "auto",
    *,
    indices_sorted: bool = False,
    accum_dtype: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pair-shaped wrapper: flattens the trailing pair axes into the kernel's
    coordinate axis and restores them on the way out."""
    n = r.shape[0]
    pair = r.shape[1:]
    dm = nbr_idx.shape[-1]
    tsum, kept = trim_gather(
        r.reshape(n, -1), nbr_idx, nbr_valid,
        byz_msgs.reshape(n, dm, -1), byz_nbr, F, backend,
        indices_sorted=indices_sorted, accum_dtype=accum_dtype,
    )
    return tsum.reshape((n,) + pair), kept
