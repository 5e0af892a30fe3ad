"""Robust gradient aggregation — the paper's algorithms as training features.

The paper's mapping onto the TPU mesh (see DESIGN.md §3):

* agent          = data-parallel worker (one coordinate of the `data` axis)
* sub-network    = a pod (`pod` axis); single-pod runs are one sub-network
* gossip edge    = `jax.lax.ppermute` ring step on the `data` axis
* packet drop    = Bernoulli mask on the ppermute payload; recovery via the
                   paper's cumulative-sum (sigma/rho) bookkeeping
* PS fusion      = masked psum over per-pod representatives every Gamma
                   gossip rounds (the doubly-stochastic fusion matrix F)
* Byzantine trim = coordinate-wise trimmed mean over gathered worker grads
                   (the paper's scalar-dynamics trick, one dynamic per
                   gradient coordinate; Pallas kernel on TPU)

Aggregators
-----------
``mean``         — exact pmean (the non-robust baseline the paper compares
                   against; equivalent to the implicit GSPMD all-reduce).
``pushsum``      — Algorithm 1 over the data-axis ring with simulated packet
                   drops: robust push-sum rounds + hierarchical fusion; the
                   returned estimate is z/m (consensus error decays per
                   Theorem 1 in the number of rounds).
``pushsum_sparse`` — Algorithm 1 on an *arbitrary* random digraph over all
                   workers via the edge-list core of
                   :mod:`repro.core.pushsum`: one all-gather, then every
                   worker integrates the same sparse consensus and keeps its
                   own row. Wire = one all-gather (vs one ppermute/round for
                   ``pushsum``); use it to prototype non-ring gossip
                   topologies (denser graphs -> faster Theorem 1 contraction)
                   before committing them to collectives.
``trimmed_mean`` — Algorithm 2's extreme-value filter, coordinate-wise over
                   the worker axis (tolerates F Byzantine workers).
``hierarchical_trim`` — intra-pod trimmed mean + cross-pod trimmed fusion of
                   pod estimates (the full two-level Algorithm 2 shape).

All of them run inside ``shard_map`` with the (pod, data) axes *manual* and
the ``model`` axis *auto*: per-worker gradient identity is explicit (the
Byzantine threat model requires it) while tensor parallelism inside the loss
stays GSPMD-managed. This is the central systems consequence of the paper:
robust aggregation is incompatible with FSDP-sharded gradients (no single
device ever holds "worker i's gradient"), so robust modes keep params
replicated across `data` — memory cost of Byzantine tolerance. See
EXPERIMENTS.md §Perf for the measured overheads.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Params = Any


@dataclasses.dataclass(frozen=True)
class AggregatorConfig:
    kind: str = "mean"
    # pushsum knobs
    gossip_rounds: int = 16
    gamma_period: int = 4           # PS fusion every Γ rounds
    drop_prob: float = 0.1          # simulated packet-drop probability
    B: int = 2                      # every link delivers ≥ once per B rounds
    # pushsum_sparse knobs: worker gossip digraph = random Hamiltonian cycle
    # + Bernoulli extra edges (repro.core.graphs.random_strongly_connected)
    graph_extra_edge_prob: float = 0.25
    graph_seed: int = 0
    pushsum_backend: str = "auto"   # "auto" | "xla" | "pallas" delivery
                                    # lowering for the edge-list core (see
                                    # repro.kernels.pushsum_edge)
    # byzantine knobs
    F: int = 1                      # trim F from each extreme
    trim_backend: str = "xla"       # trimmed-mean lowering ("xla" ref /
                                    # "pallas" TPU kernel / "auto")
    trim_chunk: int = 1 << 22       # coordinates per all-gather chunk
    comm_dtype: str = "float32"     # wire dtype for gather/a2a payloads
                                    # ("bfloat16" halves collective bytes;
                                    # trim decisions are scale-invariant so
                                    # the Byzantine guarantee is unchanged)


def _worker_index(data_axis: str, pod_axis: str | None) -> jnp.ndarray:
    idx = jax.lax.axis_index(data_axis)
    if pod_axis is not None:
        idx = (jax.lax.axis_index(pod_axis) * jax.lax.axis_size(data_axis)
               + idx)
    return idx


# ---------------------------------------------------------------------------
# mean (baseline)
# ---------------------------------------------------------------------------

def agg_mean(grads: Params, cfg: AggregatorConfig, data_axis, pod_axis, key):
    axes = (pod_axis, data_axis) if pod_axis else (data_axis,)
    return jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, axes), grads)


# ---------------------------------------------------------------------------
# robust push-sum over the data-axis ring (Algorithm 1)
# ---------------------------------------------------------------------------

def agg_pushsum(grads: Params, cfg: AggregatorConfig, data_axis, pod_axis, key):
    """Fast robust push-sum on a directed ring within each pod, cumulative
    sigma/rho drop recovery, hierarchical PS fusion across pods every Γ.

    Ring: worker i sends to (i+1) mod W. Out-degree 1 => share = 1/2.
    Returns each worker's z/m estimate (approximate mean; the residual is
    the paper's consensus error, measurable as cross-worker disagreement).
    """
    W = jax.lax.axis_size(data_axis)
    n_pods = jax.lax.axis_size(pod_axis) if pod_axis else 1
    fwd = [(i, (i + 1) % W) for i in range(W)]

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    z0 = [l.astype(jnp.float32) for l in leaves]
    zeros = [jnp.zeros_like(z) for z in z0]

    didx = jax.lax.axis_index(data_axis)
    pidx = jax.lax.axis_index(pod_axis) if pod_axis else 0
    is_rep = (didx == 0)

    def round_fn(t, carry):
        zs, m, sigmas, sig_m, rhos, rho_m = carry
        # Bernoulli drop on the (unique) outgoing ring link of each worker,
        # forced up every B rounds (the paper's B-connectivity window).
        kk = jax.random.fold_in(key, t)
        # per-link randomness must differ per *sender*; fold in worker id
        ku = jax.random.fold_in(kk, didx + W * pidx)
        up = (jax.random.uniform(ku) >= cfg.drop_prob) | ((t % cfg.B) == cfg.B - 1)

        # stage cumulative halves (sigma += z/2)
        sigmas = [s + z * 0.5 for s, z in zip(sigmas, zs)]
        sig_m = sig_m + m * 0.5
        # transmit sigma+; receiver sees sender's mask
        sent = [jnp.where(up, s, jnp.nan) for s in sigmas]  # nan == dropped
        sent_m = jnp.where(up, sig_m, jnp.nan)
        recv = [jax.lax.ppermute(s, data_axis, fwd) for s in sent]
        recv_m = jax.lax.ppermute(sent_m, data_axis, fwd)
        ok = ~jnp.isnan(recv_m)
        rho_new = [jnp.where(ok, r, old) for r, old in zip(recv, rhos)]
        rho_m_new = jnp.where(ok, recv_m, rho_m)
        # integrate: z+ = z/2 + (rho_new - rho_old)
        zs = [z * 0.5 + (rn - ro) for z, rn, ro in zip(zs, rho_new, rhos)]
        m = m * 0.5 + (rho_m_new - rho_m)
        # second staging (line 12): sigma += z+/2, z = z+/2
        sigmas = [s + z * 0.5 for s, z in zip(sigmas, zs)]
        sig_m = sig_m + m * 0.5
        zs = [z * 0.5 for z in zs]
        m = m * 0.5

        # hierarchical fusion every Γ rounds (reps: data index 0 of each pod)
        if pod_axis is not None and n_pods > 1:
            do_fuse = (t + 1) % cfg.gamma_period == 0

            def fuse(args):
                zs, m = args
                repf = is_rep.astype(jnp.float32)
                pooled = [
                    jax.lax.psum(
                        jax.lax.psum(z * repf, data_axis), pod_axis
                    ) / (2.0 * n_pods)
                    for z in zs
                ]
                pooled_m = jax.lax.psum(
                    jax.lax.psum(m * repf, data_axis), pod_axis
                ) / (2.0 * n_pods)
                zs = [
                    jnp.where(is_rep, 0.5 * z + pz, z)
                    for z, pz in zip(zs, pooled)
                ]
                m = jnp.where(is_rep, 0.5 * m + pooled_m, m)
                return zs, m

            zs, m = jax.lax.cond(do_fuse, fuse, lambda a: a, (zs, m))
        return zs, m, sigmas, sig_m, rho_new, rho_m_new

    m0 = jnp.float32(1.0)
    carry = (z0, m0, zeros, jnp.float32(0.0),
             [jnp.zeros_like(z) for z in z0], jnp.float32(0.0))
    zs, m, *_ = jax.lax.fori_loop(0, cfg.gossip_rounds, round_fn, carry)
    est = [
        (z / jnp.maximum(m, 1e-12)).astype(l.dtype) for z, l in zip(zs, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, est)


# ---------------------------------------------------------------------------
# edge-list push-sum on an arbitrary worker digraph (Algorithm 1, sparse core)
# ---------------------------------------------------------------------------

def agg_pushsum_sparse(
    grads: Params, cfg: AggregatorConfig, data_axis, pod_axis, key
):
    """Robust push-sum over a random strongly connected digraph of ALL
    workers (pods flattened), using the O(E d) edge-list core.

    Each worker all-gathers the per-worker gradients once, then runs the
    identical ``gossip_rounds`` of :func:`repro.core.pushsum.
    sparse_pushsum_step` (same key -> same masks on every worker) and keeps
    its own row of z/m. Deterministically identical inputs mean workers
    agree on the whole consensus state, so the per-worker estimates are the
    true Algorithm 1 iterates on that topology — the training-time testbed
    for non-ring gossip graphs. The edge index is kept in the sorted-by-dst
    layout so ``cfg.pushsum_backend="pallas"`` hits the fused kernel's
    contiguous-run fast path on TPU (``"auto"`` falls back to XLA off-TPU).
    """
    import numpy as np

    from repro.core.graphs import (
        edge_list, random_strongly_connected, sort_by_dst,
    )
    from repro.core.pushsum import (
        init_sparse_state, sparse_pushsum_step, sparse_ratios, step_edge_mask,
    )

    axes = (pod_axis, data_axis) if pod_axis else (data_axis,)
    W = 1
    for a in axes:
        W *= jax.lax.axis_size(a)
    adj = random_strongly_connected(
        W, cfg.graph_extra_edge_prob, np.random.default_rng(cfg.graph_seed)
    )
    el, _, _ = sort_by_dst(edge_list(adj))
    src = jnp.asarray(el.src)
    dst = jnp.asarray(el.dst)
    valid = jnp.asarray(el.valid)
    widx = _worker_index(data_axis, pod_axis)

    def gossip_leaf(g):
        gf = g.astype(jnp.float32).reshape(-1)
        allv = jax.lax.all_gather(gf, axes).reshape(W, -1)   # (W, D)

        def round_fn(t, state):
            mask = step_edge_mask(key, t, el.E, cfg.drop_prob, cfg.B)
            return sparse_pushsum_step(
                state, mask, src, dst, valid, cfg.pushsum_backend
            )

        final = jax.lax.fori_loop(
            0, cfg.gossip_rounds, round_fn, init_sparse_state(allv, el.E)
        )
        est = sparse_ratios(final)                           # (W, D)
        return est[widx].reshape(g.shape).astype(g.dtype)

    return jax.tree_util.tree_map(gossip_leaf, grads)


# ---------------------------------------------------------------------------
# coordinate-wise trimmed mean (Algorithm 2's filter over workers)
# ---------------------------------------------------------------------------

def _trim_matrix(x: jnp.ndarray, F: int, backend: str) -> jnp.ndarray:
    """x: (W, D) -> (D,)."""
    from repro.kernels.trimmed_mean.ops import trimmed_mean
    return trimmed_mean(x, F, backend=backend)


def agg_trimmed(grads: Params, cfg: AggregatorConfig, data_axis, pod_axis, key):
    """Trim F largest/smallest per coordinate across ALL workers (pods
    flattened) then average — tolerates any F Byzantine workers system-wide."""
    axes = (pod_axis, data_axis) if pod_axis else (data_axis,)

    def trim_leaf(g):
        gf = g.astype(jnp.float32).reshape(-1)
        gathered = jax.lax.all_gather(gf, axes)          # (P, W, D) or (W, D)
        flat = gathered.reshape(-1, gf.shape[0])
        return _trim_matrix(flat, cfg.F, cfg.trim_backend).reshape(g.shape).astype(
            g.dtype
        )

    return jax.tree_util.tree_map(trim_leaf, grads)


def agg_hierarchical_trim(
    grads: Params, cfg: AggregatorConfig, data_axis, pod_axis, key
):
    """Two-level Algorithm 2: trim within each pod (sub-network consensus),
    then trimmed fusion of pod estimates across pods (PS gossip rule).

    With n_pods <= 2F the cross-pod trim degenerates to a mean — exactly the
    paper's Assumption 5 constraint (need >= 2F+1 sub-networks to trim)."""
    n_pods = jax.lax.axis_size(pod_axis) if pod_axis else 1

    def trim_leaf(g):
        gf = g.astype(jnp.float32).reshape(-1)
        within = jax.lax.all_gather(gf, data_axis)       # (W, D)
        pod_est = _trim_matrix(within, cfg.F, cfg.trim_backend)
        if pod_axis is None or n_pods == 1:
            return pod_est.reshape(g.shape).astype(g.dtype)
        across = jax.lax.all_gather(pod_est, pod_axis)   # (P, D)
        f_cross = cfg.F if n_pods >= 2 * cfg.F + 1 else 0
        out = _trim_matrix(across, f_cross, cfg.trim_backend)
        return out.reshape(g.shape).astype(g.dtype)

    return jax.tree_util.tree_map(trim_leaf, grads)


def agg_trimmed_sharded(
    grads: Params, cfg: AggregatorConfig, data_axis, pod_axis, key
):
    """Beyond-paper optimization of Algorithm 2's filter (§Perf iteration):

    The faithful ``trimmed_mean`` all-gathers the full gradient to every
    worker (wire ~ (W-1) * D bytes/device) although each coordinate's trim
    is independent. Instead, partition coordinates into per-worker stripes:

        all_to_all   — worker w receives stripe w from every other worker
                       ((W-1)/W * D bytes),
        local trim   — w trims/averages only its D/W coordinates,
        all_gather   — stripes reassemble the full estimate ((W-1)/W * D).

    Wire bytes drop ~(W-1)x -> ~2x D and the trim FLOPs drop by W. The
    result is bit-identical to ``trimmed_mean`` (same per-coordinate
    filter), so the Byzantine guarantee is unchanged.
    """
    axes = [a for a in (pod_axis, data_axis) if a]
    W = 1
    for a in axes:
        W *= jax.lax.axis_size(a)

    def trim_leaf(g):
        shape = g.shape
        wire_dt = jnp.dtype(cfg.comm_dtype)
        gf = g.astype(wire_dt).reshape(-1)
        D = gf.shape[0]
        pad = (-D) % W
        if pad:
            gf = jnp.concatenate([gf, jnp.zeros((pad,), gf.dtype)])
        stripes = gf.reshape(W, -1)                      # (W, D/W)
        # all_to_all over the (possibly two) worker axes in sequence
        recv = stripes
        if pod_axis:
            n_pod = jax.lax.axis_size(pod_axis)
            n_dat = jax.lax.axis_size(data_axis)
            # (pod, data, stripe) exchange: first flatten stripes per axis
            recv = recv.reshape(n_pod, n_dat, -1)
            recv = jax.lax.all_to_all(recv, pod_axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            recv = jax.lax.all_to_all(recv, data_axis, split_axis=1,
                                      concat_axis=1, tiled=False)
            recv = recv.reshape(W, -1)
        else:
            recv = jax.lax.all_to_all(recv, data_axis, split_axis=0,
                                      concat_axis=0, tiled=False)
        mine = _trim_matrix(recv.astype(jnp.float32), cfg.F, cfg.trim_backend)
        full = jax.lax.all_gather(mine.astype(wire_dt), tuple(axes))
        full = full.reshape(-1)[:D]
        return full.reshape(shape).astype(g.dtype)

    return jax.tree_util.tree_map(trim_leaf, grads)


AGGREGATORS: dict[str, Callable] = {
    "mean": agg_mean,
    "pushsum": agg_pushsum,
    "pushsum_sparse": agg_pushsum_sparse,
    "trimmed_mean": agg_trimmed,
    "trimmed_mean_sharded": agg_trimmed_sharded,
    "hierarchical_trim": agg_hierarchical_trim,
}
