"""Pallas TPU kernel: fused Byzantine trim for the sparse gossip core.

One call covers the trim half of an Algorithm 2 gossip round in a single
streaming pass over receiver blocks: per receiver and per pair coordinate,
drop invalid neighbor slots, run the 2F-extraction trim, and emit the
survivor sum plus kept count — no ``(N, N, m, m)`` broadcast, no
``jnp.sort``.

Design (see /opt/skills/guides/pallas_guide.md)
-----------------------------------------------
* Data-dependent addressing stays in XLA: the wrapper gathers each
  receiver's in-neighbor statistics ``r[nbr_idx]`` and substitutes attack
  values on slots whose sender is Byzantine before the call, and passes the
  resulting ``(deg_max, P, N)`` slot values as a blocked operand. Mosaic
  does not lower an in-kernel row gather, and nothing node-indexed has to
  be VMEM resident.
* Layout: receivers on the 128-wide lane axis, pair coordinates on
  sublanes, neighbor slots on the leading axis — ``(deg_max, P, block_n)``
  tiles, so every reduction over slots is an elementwise max/min/add
  across ``(P, block_n)`` slabs and the small P costs sublane padding
  instead of lane padding. The wrapper transposes at the call boundary.
* A full per-coordinate sort would waste the VPU: F << deg_max, so the trim
  runs F rounds of max *extraction* followed by F rounds of min — the
  same O(F * deg) design as :mod:`repro.kernels.trimmed_mean`, generalized
  to a per-receiver slot axis with validity padding. Each round is a
  max + first-occurrence select over the slot axis; the F loop is a Python
  static, so it fully unrolls.
* Extraction == sort-slice trimming as multisets: each max round removes one
  instance of the current maximum over the still-kept valid slots (ties by
  first occurrence, like a stable sort slice), so after F rounds exactly the
  F largest values are gone; symmetrically for minima. When deg <= 2F both
  formulations keep nothing: extraction exhausts the valid slots (the
  extremum of an all-sentinel column hits no kept slot), and the rank
  window [F, deg - F) is empty. Survivor sums therefore agree with the sort
  oracle up to fp ordering.
* Survivors are summed through a keep mask, never via total - extremes —
  Byzantine magnitudes ~1e6x the honest scale make the subtractive
  formulation cancel catastrophically (same lesson as trimmed_mean).
* Padding receivers (to a multiple of ``block_n``) carry all-invalid slot
  columns: their trimmed sum is exactly zero and the lanes are sliced off.

Compiled on a TPU, ``block_n`` must be a multiple of 128 (the lane width)
or at least N; interpret mode accepts any block. ``interpret=None``
auto-selects interpreter mode off-TPU so CPU CI validates the identical
program (tests/test_byz_trim_kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["trim_gather_pallas"]


def _kernel(vals_ref, valid_ref, tsum_ref, kept_ref, *, F: int):
    vals = vals_ref[...].astype(jnp.float32)     # (deg_max, P, BN)
    valid = valid_ref[...] != 0                  # (deg_max, 1, BN)
    dm = vals.shape[0]

    deg = valid.astype(jnp.int32).sum(axis=0)    # (1, BN)
    kept_ref[...] = jnp.maximum(deg - 2 * F, 0).astype(kept_ref.dtype)

    keep = jnp.broadcast_to(valid, vals.shape)
    if F > 0:
        slots = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)

        def extract(cur, best, sentinel):
            # one instance of each column's extremum: its first slot
            hit = cur == best(cur, axis=0, keepdims=True)
            first = jnp.where(hit, slots, dm).min(axis=0, keepdims=True)
            onehot = slots == first
            return onehot, jnp.where(onehot, sentinel, cur)

        neg = jnp.float32(jnp.finfo(jnp.float32).min)
        pos = jnp.float32(jnp.finfo(jnp.float32).max)
        cur = jnp.where(keep, vals, neg)
        for _ in range(F):                       # static unroll: drop maxima
            onehot, cur = extract(cur, jnp.max, neg)
            keep = keep & ~onehot
        cur = jnp.where(keep, vals, pos)
        for _ in range(F):                       # drop minima among survivors
            onehot, cur = extract(cur, jnp.min, pos)
            keep = keep & ~onehot

    tsum = jnp.where(keep, vals, 0.0).sum(axis=0)  # (P, BN)
    tsum_ref[...] = tsum.astype(tsum_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("F", "block_n", "interpret", "accum_dtype")
)
def trim_gather_pallas(
    r: jnp.ndarray,         # (N, P) current statistics
    nbr_idx: jnp.ndarray,   # (N, deg_max) int32
    nbr_valid: jnp.ndarray, # (N, deg_max) bool
    byz_msgs: jnp.ndarray,  # (N, deg_max, P)
    byz_nbr: jnp.ndarray,   # (N, deg_max) bool
    F: int,
    *,
    block_n: int = 1024,
    interpret: bool | None = None,
    accum_dtype: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused trim-gather -> ``(trimmed_sum (N, P), kept (N,))``.

    Matches :func:`repro.kernels.byz_trim.ref.trim_gather_ref` to fp32
    reduction order. N is padded to a multiple of ``block_n`` with
    all-invalid receiver rows; the pad rows are sliced off. The kernel
    already runs its trim/sum in fp32 internally; ``accum_dtype`` names the
    dtype the survivor sum is *emitted* in (the precision policy's accum
    slot) — ``None`` keeps ``r.dtype``, the pre-policy program.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    acc = r.dtype if accum_dtype is None else jnp.dtype(accum_dtype)
    n, p = r.shape
    dm = nbr_idx.shape[1]
    block_n = min(block_n, max(n, 1))
    pad = (-n) % block_n
    gathered = r[nbr_idx]                                  # (N, dm, P) XLA
    vals = jnp.where(byz_nbr[:, :, None], byz_msgs, gathered)
    vals_t = jnp.transpose(vals, (1, 2, 0))                # (dm, P, N)
    valid_t = nbr_valid.T.astype(jnp.int32)[:, None, :]    # (dm, 1, N)
    if pad:
        vals_t = jnp.pad(vals_t, ((0, 0), (0, 0), (0, pad)))
        valid_t = jnp.pad(valid_t, ((0, 0), (0, 0), (0, pad)))  # invalid
    n_pad = n + pad

    tsum_t, kept = pl.pallas_call(
        functools.partial(_kernel, F=F),
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((dm, p, block_n), lambda i: (0, 0, i)),
            pl.BlockSpec((dm, 1, block_n), lambda i: (0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((p, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, n_pad), acc),
            jax.ShapeDtypeStruct((1, n_pad), acc),
        ],
        interpret=interpret,
    )(vals_t, valid_t)
    return tsum_t[:, :n].T, kept[0, :n]
