"""Backend dispatch for the fused push-sum edge scatter.

``edge_scatter(..., backend=...)`` is the single entry point the sparse
push-sum core calls per round:

``"xla"``     — gather + ``segment_sum`` (:mod:`.ref`); runs anywhere.
``"pallas"``  — the fused streaming kernel (:mod:`.pushsum_edge`);
                compiled on TPU, interpreter mode elsewhere (equivalence
                testing only — interpret mode is not a fast path).
``"auto"``    — ``"pallas"`` on a TPU default backend, else ``"xla"``.

Resolution is host-side and static (the choice changes the traced program),
so callers thread ``backend`` through ``static_argnames`` when jitting.
"""
from __future__ import annotations

import jax.numpy as jnp

# Re-exported for back-compat: the resolver now lives in
# repro.kernels.dispatch and is shared by every kernel family.
from ..dispatch import BACKENDS, resolve_backend
from .pushsum_edge import edge_scatter_pallas
from .ref import edge_scatter_ref

__all__ = ["edge_scatter", "resolve_backend", "BACKENDS"]


def edge_scatter(
    sigma: jnp.ndarray,   # (N, D)
    rho: jnp.ndarray,     # (E, D)
    live: jnp.ndarray,    # (E,) bool
    src: jnp.ndarray,     # (E,) int32
    dst: jnp.ndarray,     # (E,) int32
    backend: str = "auto",
    *,
    seg_end: jnp.ndarray | None = None,
    seg_lo: jnp.ndarray | None = None,
    block_e: int = 4096,
    interpret: bool | None = None,
    indices_sorted: bool = False,
    accum_dtype: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused mask-latch + per-receiver increment sum; see package docstring.

    Returns ``(rho_new (E, D), recv (N, D))``. ``indices_sorted=True``
    promises a dst-sorted edge index, letting either lowering's
    ``segment_sum`` drop one argsort.
    ``accum_dtype`` names the dtype of the ``recv`` reduction (the
    precision policy's accum slot — see :mod:`repro.core.precision`);
    ``None`` keeps the input dtype.
    ``seg_end`` (each receiver's last in-edge of a dst-sorted index, ``-1``
    for none) lets the Pallas lowering finish with a gather at the run
    ends instead of the ``segment_sum`` scatter; the XLA lowering, the
    oracle, does not read it. With ``seg_lo`` it covers only the receivers
    from ``seg_lo`` on (see :func:`.pushsum_edge.edge_scatter_pallas`).
    """
    if resolve_backend(backend) == "xla":
        return edge_scatter_ref(sigma, rho, live, src, dst,
                                indices_sorted=indices_sorted,
                                accum_dtype=accum_dtype)
    return edge_scatter_pallas(
        sigma, rho, live, src, dst, seg_end=seg_end, seg_lo=seg_lo,
        block_e=block_e, interpret=interpret, accum_dtype=accum_dtype,
        indices_sorted=indices_sorted,
    )
