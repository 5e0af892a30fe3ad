"""Pallas kernel: fused push-sum mask-latch + per-run increment sums.

One call covers the delivery + integration half of a robust push-sum round
(Su '18 Alg. 1 lines 6-11) over a dst-sorted edge list: latch the sender's
staged cumulative into ``rho`` on live edges, and reduce each receiver's
run of increments ``rho_new - rho`` to one partial sum.

Design (see /opt/skills/guides/pallas_guide.md)
-----------------------------------------------
* Data-dependent addressing stays in XLA, the streaming arithmetic in the
  kernel. The row gather ``sigma[src]`` runs as an XLA gather before the
  call and the kernel receives the gathered rows as a blocked operand;
  the per-receiver finish after it is either a ``segment_sum`` over the
  kernel's run-end partials or, given each receiver's run end
  (``seg_end``), an N-row gather of the kernel's whole-run sums. Mosaic
  lowers neither an in-kernel row gather nor a data-dependent scatter, and
  nothing node-indexed has to be VMEM resident: every operand is a block
  of the edge axis.
* Layout: edges on the 128-wide lane axis, the feature axis D = d+1 on
  sublanes — ``(D, block_e)`` tiles, so a small D costs sublane padding
  (5 -> 8) instead of lane padding (5 -> 128). ``live`` and ``dst`` ride as
  ``(1, block_e)`` rows. The wrapper transposes at the call boundary.
* Within a block the per-receiver reduction uses the *sorted-run* trick:
  each receiver's edges form one contiguous run of lanes, so a
  *segmented* inclusive scan (log2(block_e) flag-carrying Hillis-Steele
  steps, ``pltpu.roll`` + select + add) leaves each run's sum at its last
  lane and the kernel emits only those run-end partials (zero elsewhere).
  The scan is segmented rather than a plain cumsum with boundary
  differences because push-sum's z/m ratio amplifies absolute error by
  1/m (m decays geometrically): subtracting two large cross-segment
  prefixes to recover a small segment sum cancels catastrophically, while
  segment-local partial sums keep the error at the run's own scale.
* Correctness does NOT require sortedness: an unsorted index just breaks
  runs into more fragments, each with its own run-end partial, and the
  ``segment_sum`` finish adds fragments of one receiver together. A run
  that spans a block boundary ends at the block's last lane and reopens
  at the next block's first lane in the same way.
* Run-end gather (``seg_end`` given, dst-sorted index only): the grid runs
  in order (``"arbitrary"``; a v5e has one TensorCore, so nothing runs in
  parallel anyway) and two VMEM scratch buffers carry the sum and the
  receiver of the run open at a block's last lane into the next block,
  whose first run adds it. The last lane of every receiver's whole run
  then holds its full increment sum, and the finish is a gather of N
  lanes in place of a scatter of E. The carry adds a run's earlier
  partial sum to its later ones, so every sum stays segment-local.
* Padding edges (to a multiple of ``block_e``) carry ``live = 0`` and the
  out-of-range receiver ``N``, so the last real run still ends on a real
  lane; the pad lanes are sliced off before the finish.

Compiled on a TPU, ``block_e`` must be a multiple of 128 (the lane width)
or at least E; interpret mode accepts any block. ``interpret=None``
auto-selects interpreter mode off-TPU so CPU CI validates the identical
program (tests/test_pushsum_edge_kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["edge_scatter_pallas"]


def _segmented_cumsum(v, is_first):
    """Inclusive scan along lanes that restarts at run starts.

    ``v`` is (D, BE), ``is_first`` a (1, BE) int32 flag row. Flag-carrying
    Hillis-Steele: at stride s, lane i absorbs lane i-s only if no run
    start lies in (i-s, i]; flags OR upward so the check stays O(1) per
    step. log2(BE) static steps, every partial sum segment-local.
    """
    n = v.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    f = is_first
    s = 1
    while s < n:
        head = lane < s                          # no lane i-s to absorb
        v_prev = pltpu.roll(v, s, 1)             # lane i <- lane i-s
        f_prev = pltpu.roll(f, s, 1)
        v = jnp.where((f != 0) | head, v, v + v_prev)
        f = jnp.where(head, 1, f | f_prev)
        s *= 2
    return v


def _kernel(sent_ref, rho_ref, live_ref, dst_ref, rho_out_ref, upd_ref,
            *carry_refs):
    rho = rho_ref[...]                           # (D, BE)
    live = live_ref[...] != 0                    # (1, BE)

    # --- mask-latch: live edges adopt the sender's staged cumulative ---
    rho_new = jnp.where(live, sent_ref[...], rho)
    rho_out_ref[...] = rho_new

    # --- run-end partial sums of the increments ---
    # the accumulator dtype is upd's (the policy's accum slot): latched
    # state streams at storage precision, the reduction runs full-precision
    acc = upd_ref.dtype
    delta = rho_new.astype(acc) - rho.astype(acc)  # zero on dead/pad edges
    dst = dst_ref[...]                           # (1, BE)
    be = dst.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, be), 1)
    is_first = (lane == 0) | (dst != pltpu.roll(dst, 1, 1))
    is_end = (lane == be - 1) | (dst != pltpu.roll(dst, be - 1, 1))
    seg = _segmented_cumsum(delta, is_first.astype(jnp.int32))
    if carry_refs:
        # --- runs carried across blocks (dst-sorted, sequential grid) ---
        # the run open at the previous block's last lane continues into
        # this block's first run iff its receiver is the same; in a sorted
        # block those are exactly the lanes whose dst equals the carried one
        carry_ref, carry_dst_ref = carry_refs

        @pl.when(pl.program_id(0) == 0)
        def _():
            carry_ref[...] = jnp.zeros_like(carry_ref)
            carry_dst_ref[...] = jnp.full_like(carry_dst_ref, -1)

        seg = seg + jnp.where(dst == carry_dst_ref[...], carry_ref[...], 0.0)
        carry_ref[...] = jnp.sum(jnp.where(lane == be - 1, seg, 0.0), axis=1,
                                 keepdims=True)
        carry_dst_ref[...] = jnp.max(dst, axis=1, keepdims=True)
    upd_ref[...] = jnp.where(is_end, seg, 0.0).astype(acc)


@functools.partial(
    jax.jit,
    static_argnames=("block_e", "interpret", "accum_dtype", "indices_sorted"),
)
def edge_scatter_pallas(
    sigma: jnp.ndarray,   # (N, D) staged cumulative send per node
    rho: jnp.ndarray,     # (E, D) last heard cumulative per edge
    live: jnp.ndarray,    # (E,) bool — operational AND valid this round
    src: jnp.ndarray,     # (E,) int32
    dst: jnp.ndarray,     # (E,) int32, pre-sorted ascending for the fast path
    *,
    seg_end: jnp.ndarray | None = None,  # (N,) int32 last in-edge, or -1
    seg_lo: jnp.ndarray | None = None,   # () int32 first receiver of seg_end
    block_e: int = 4096,
    interpret: bool | None = None,
    accum_dtype: str | None = None,
    indices_sorted: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused edge scatter -> ``(rho_new (E, D), recv (N, D))``.

    Matches :func:`repro.kernels.pushsum_edge.ref.edge_scatter_ref` to fp32
    reduction order. E is padded to a multiple of ``block_e`` with inert
    edges; the pad lanes are sliced off. ``accum_dtype`` names the dtype
    of the increment reduction and of ``recv`` (the precision policy's
    accum slot) — ``None`` keeps the input dtype, the pre-policy program.
    ``indices_sorted=True`` promises a dst-sorted index to the
    ``segment_sum`` finish.

    ``seg_end`` (a dst-sorted index only; see
    :func:`repro.core.graphs.run_ends`) holds each receiver's last in-edge,
    ``-1`` where it has none. The grid then runs in order and carries the
    run open at each block's end into the next block, so the last lane of
    each receiver's whole run holds its full increment sum, and the finish
    gathers those N lanes instead of scattering all E partials.
    ``seg_end=None`` is the scatter finish. With ``seg_lo``, ``seg_end``
    holds the run ends of the ``W`` receivers ``seg_lo .. seg_lo + W - 1``
    only (``seg_lo + W <= N``): the finish gathers ``W`` lanes and every
    other receiver's sum is zero, as for an edge shard whose receivers lie
    in one range.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    acc = sigma.dtype if accum_dtype is None else jnp.dtype(accum_dtype)
    n, D = sigma.shape
    E = rho.shape[0]
    block_e = min(block_e, max(E, 1))
    pad = (-E) % block_e
    sent_t = sigma[src].T                                    # XLA gather
    rho_t = rho.T
    live_r = live.astype(jnp.int32)[None, :]                 # (1, E)
    dst_r = dst[None, :]
    if pad:
        sent_t = jnp.pad(sent_t, ((0, 0), (0, pad)))
        rho_t = jnp.pad(rho_t, ((0, 0), (0, pad)))
        live_r = jnp.pad(live_r, ((0, 0), (0, pad)))         # dead
        dst_r = jnp.pad(dst_r, ((0, 0), (0, pad)), constant_values=n)
    Ep = E + pad

    edge_block = pl.BlockSpec((D, block_e), lambda i: (0, i))
    row_block = pl.BlockSpec((1, block_e), lambda i: (0, i))
    carry = {}
    if seg_end is not None:
        carry = dict(
            scratch_shapes=[pltpu.VMEM((D, 1), acc),
                            pltpu.VMEM((1, 1), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
        )
    rho_new_t, upd_t = pl.pallas_call(
        _kernel,
        grid=(Ep // block_e,),
        in_specs=[edge_block, edge_block, row_block, row_block],
        out_specs=[edge_block, edge_block],
        out_shape=[
            jax.ShapeDtypeStruct((D, Ep), rho.dtype),
            jax.ShapeDtypeStruct((D, Ep), acc),
        ],
        interpret=interpret,
        **carry,
    )(sent_t, rho_t, live_r, dst_r)
    if seg_end is None:
        recv = jax.ops.segment_sum(
            upd_t[:, :E].T, dst, num_segments=n,
            indices_are_sorted=indices_sorted)
    else:
        with jax.named_scope("run_end_gather"):
            recv = jnp.where((seg_end >= 0)[:, None],
                             upd_t[:, jnp.maximum(seg_end, 0)].T, 0.0)
            if seg_lo is not None:
                recv = jax.lax.dynamic_update_slice(
                    jnp.zeros((n, D), recv.dtype), recv, (seg_lo, 0))
    return rho_new_t[:, :E].T, recv
