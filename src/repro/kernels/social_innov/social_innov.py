"""Pallas TPU kernel: fused innovation + belief step for Algorithm 3.

One call covers the innovation half of a social-learning iteration (lines
13-16) in a single streaming pass over agent blocks: inverse-CDF categorical
signal sampling, the (m,)-row log-likelihood gather from the resident log
tables, the ``z += loglik`` dual-averaging accumulation, and the KL-proximal
softmax belief — replacing five separate XLA ops (compare, reduce, gather,
add, softmax) each reading/writing (N, ·) HBM intermediates per scan step.

Design (see /opt/skills/guides/pallas_guide.md)
-----------------------------------------------
* Grid: 1-D over agent blocks of ``block_n`` agents. Every input is
  block-mapped — nothing is resident across blocks, so the kernel streams:
  per block it touches O(block_n * (m S + S + m)) VMEM and emits
  O(block_n * m). No cross-block state means any grid order is legal.
* Layout: agents on the 128-wide lane axis, the small hypothesis and
  alphabet axes on sublanes and the leading axis — ``z`` and ``mu`` are
  ``(m, block_n)`` tiles, ``cdf`` is ``(S, block_n)``, the tables are
  ``(m, S, block_n)``. Agent-major ``(block_n, m, S)`` blocks would pad
  every (m, S) pair to a full (8, 128) tile and overflow VMEM at
  N ~ 1e5. The wrapper transposes at the call boundary.
* The per-agent gather ``log_tables[j, :, sig[j]]`` is lowered as a one-hot
  contraction over the alphabet axis (``iota_S == sig`` mask + sum) rather
  than a dynamic gather: S is small (4-16 for the paper's models), the
  one-hot select is pure VPU, and Mosaic vectorizes it where a per-agent
  dynamic slice would serialize.
* The softmax runs over the hypothesis axis of the block tile with the
  standard max-subtraction; m is small so the reduction is cheap — the
  streaming axis (agents) carries the throughput, as with the other
  consensus kernels.
* Padding agents (to a multiple of ``block_n``) carry ``mass = 0`` /
  ``u = 0`` / all-zero table columns: their ``sig`` is 0, their ``z_new``
  column is ``z + 0`` and the softmax of a zero column is uniform — finite,
  inert, and sliced off.

Compiled on a TPU, ``block_n`` must be a multiple of 128 (the lane width)
or at least N; interpret mode accepts any block.

``interpret=None`` auto-selects interpreter mode off-TPU so CPU CI
validates the identical program (tests/test_social_engine.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["innovation_pallas"]


def _kernel(z_ref, mass_ref, u_ref, cdf_ref, lt_ref, z_out_ref, mu_ref):
    z = z_ref[...]                               # (m, BN)
    mass = mass_ref[...]                         # (1, BN)
    u = u_ref[...]                               # (1, BN)
    cdf = cdf_ref[...]                           # (S, BN)
    lt = lt_ref[...]                             # (m, S, BN)

    # --- inverse-CDF categorical sample per agent; clamp because an fp32
    # cumsum can end below 1.0 (u >= cdf[-1] must map to the last letter) ---
    s_max = cdf.shape[0] - 1
    below = (u > cdf).astype(jnp.int32)          # (S, BN)
    sig = jnp.minimum(below.sum(axis=0, keepdims=True), s_max)  # (1, BN)

    # --- (m,) log-likelihood gather as a one-hot contraction over S ---
    s_iota = jax.lax.broadcasted_iota(jnp.int32, lt.shape, 1)
    onehot = s_iota == sig[None]
    acc = mu_ref.dtype                           # the policy accum slot
    loglik = jnp.where(onehot, lt.astype(acc), 0.0).sum(axis=1)  # (m, BN)

    # --- dual accumulation + KL-proximal belief (softmax of z/m) ---
    # the accumulation and softmax run in the accum slot; z_new is downcast
    # to the persistent storage dtype on the way out
    z_new = z.astype(acc) + loglik
    z_out_ref[...] = z_new.astype(z_out_ref.dtype)
    ratio = z_new / jnp.maximum(mass.astype(acc), 1e-30)
    shifted = ratio - ratio.max(axis=0, keepdims=True)
    e = jnp.exp(shifted)
    mu_ref[...] = e / e.sum(axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("block_n", "interpret", "accum_dtype")
)
def innovation_pallas(
    z: jnp.ndarray,           # (N, m) log-likelihood accumulator
    mass: jnp.ndarray,        # (N,)  push-sum mass
    u: jnp.ndarray,           # (N,)  uniforms for this iteration
    cdf: jnp.ndarray,         # (N, S) inclusive cumsum of truth-row probs
    log_tables: jnp.ndarray,  # (N, m, S)
    *,
    block_n: int = 4096,
    interpret: bool | None = None,
    accum_dtype: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused innovation step -> ``(z_new (N, m), mu (N, m))``.

    Matches :func:`repro.kernels.social_innov.ref.innovation_ref` to fp32
    rounding (the softmax applies the max-subtraction the XLA lowering also
    performs). N is padded to a multiple of ``block_n`` with inert rows; the
    pad rows are sliced off. ``z_new`` is emitted in ``z.dtype``;
    ``accum_dtype`` names the dtype the accumulation/softmax run in and
    ``mu`` is emitted in (``None`` keeps ``z.dtype``).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    acc = z.dtype if accum_dtype is None else jnp.dtype(accum_dtype)
    n, m = z.shape
    S = cdf.shape[1]
    block_n = min(block_n, max(n, 1))
    pad = (-n) % block_n
    z_t = z.T                                          # (m, N)
    mass_r = mass[None, :]                             # (1, N)
    u_r = u[None, :]
    cdf_t = cdf.T                                      # (S, N)
    lt_t = jnp.transpose(log_tables, (1, 2, 0))        # (m, S, N)
    if pad:
        z_t = jnp.pad(z_t, ((0, 0), (0, pad)))
        mass_r = jnp.pad(mass_r, ((0, 0), (0, pad)))
        u_r = jnp.pad(u_r, ((0, 0), (0, pad)))
        cdf_t = jnp.pad(cdf_t, ((0, 0), (0, pad)))
        lt_t = jnp.pad(lt_t, ((0, 0), (0, 0), (0, pad)))
    n_pad = n + pad

    def agents(*lead):
        return pl.BlockSpec(lead + (block_n,),
                            lambda i: (0,) * len(lead) + (i,))

    z_new_t, mu_t = pl.pallas_call(
        _kernel,
        grid=(n_pad // block_n,),
        in_specs=[agents(m), agents(1), agents(1), agents(S), agents(m, S)],
        out_specs=[agents(m), agents(m)],
        out_shape=[
            jax.ShapeDtypeStruct((m, n_pad), z.dtype),
            jax.ShapeDtypeStruct((m, n_pad), acc),
        ],
        interpret=interpret,
    )(z_t, mass_r, u_r, cdf_t, lt_t)
    return z_new_t[:, :n].T, mu_t[:, :n].T
