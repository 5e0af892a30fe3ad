"""Fast robust push-sum over packet-dropping links (Su '18, Alg. 1 lines 3-12).

The algorithm tolerates packet-dropping links *without* delivery
acknowledgements by transmitting cumulative sums:

* ``sigma_j``  — cumulative value agent j has made available to each of its
  outgoing neighbors up to now (broadcast: identical per neighbor),
* ``rho_{j'j}`` — the latest cumulative value receiver j has actually heard
  from sender j'.

A successful delivery at time t lets the receiver integrate
``rho_new - rho_old`` — which automatically includes every previously dropped
increment. Mass bookkeeping (``m``, ``sigma_m``, ``rho_m``) runs the identical
recursion so the ratio ``z/m`` debiases the graph and the losses.

Two interchangeable state representations:

**Dense (reference).** For an N-agent network with d-dimensional values:
    z (N, d) | m (N,) | sigma (N, d) | sigma_m (N,) | rho (N, N, d) |
    rho_m (N, N)    (rho[j', j] = last heard on link j' -> j)
O(N^2 d) memory; kept as the executable spec the sparse path is tested
against.

**Sparse edge-list (production).** ``rho`` only carries information on
actual links, so over a precomputed edge index (src[e] -> dst[e], E edges):
    z (N, d) | m (N,) | sigma (N, d) | sigma_m (N,) | rho (E, d) |
    rho_m (E,)
O(E d) memory — N ~ 1e5 agents on sparse digraphs never touch an
(N, N, ...) array — and per-round link masks are (E,) Bernoulli draws
generated inside the scan (no (T, N, N) schedule is ever materialized).
Su & Vaidya's analysis (arXiv:1606.08904, relaxed in arXiv:1901.01943) is
stated per-link, so the edge-list core is the faithful representation, not
an approximation.

The delivery + integration half of each round is routed through a
``backend`` switch (``sparse_pushsum_step`` / ``run_pushsum_sparse``, and
the engines built on them in :mod:`repro.core.sweeps` and
:mod:`repro.distributed.aggregation`):

* ``"xla"``    — gather ``sigma[src]`` + ``jnp.where`` latch + one
  ``jax.ops.segment_sum`` over ``dst``; runs on every platform and is the
  equivalence oracle.
* ``"pallas"`` — the fused streaming kernel of
  :mod:`repro.kernels.pushsum_edge`: one pass over E doing the gather, the
  mask-latch, and the per-receiver increment accumulation together. It
  expects the *sorted-edge layout*: pre-sort the index by ``dst`` at
  construction with :func:`repro.core.graphs.sort_by_dst` (the returned
  inverse permutation maps per-edge state/masks back to the original edge
  order). Unsorted indices stay correct but lose the contiguous-run fast
  path. Value and mass columns ride one (·, d+1) matrix so a single pass
  serves both recursions.
* ``"auto"``   — ``"pallas"`` on TPU, ``"xla"`` elsewhere (CPU CI runs the
  kernel in ``interpret=True`` mode for equivalence tests only).

Everything is jax-traceable; see :mod:`repro.core.sweeps` for the vmapped
(and mesh-sharded) scenario engine built on the sparse core.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.asyncrony import (
    AsyncBuffer,
    AsyncModel,
    init_async_buffer,
    is_degenerate_async,
    wake_mask,
)
from repro.core.faults import (
    ENGINE_PUSHSUM,
    FaultModel,
    FaultState,
    faulty_edge_mask,
    freeze,
    init_fault_state,
    step_faults,
)
from repro.core.plan import ExecutionPlan, resolve_plan
from repro.core.precision import Policy, resolve_policy
from repro.statics.contracts import contract as statics_contract
from repro.statics.retrace import register_cache as register_statics_cache

__all__ = [
    "PushSumState",
    "init_state",
    "pushsum_step",
    "run_pushsum",
    "ratios",
    "mass_invariant",
    "SparsePushSumState",
    "init_sparse_state",
    "sparse_pushsum_step",
    "sparse_pushsum_step_jit",
    "sparse_ratios",
    "sparse_mass_invariant",
    "run_pushsum_sparse",
    "step_edge_mask",
    "shard_edge_mask",
]

HALO_VARIANTS = ("psum", "scatter")


# ---------------------------------------------------------------------------
# Dense reference implementation
# ---------------------------------------------------------------------------

class PushSumState(NamedTuple):
    z: jnp.ndarray        # (N, d) value
    m: jnp.ndarray        # (N,)   mass
    sigma: jnp.ndarray    # (N, d) cumulative value offered per out-link
    sigma_m: jnp.ndarray  # (N,)
    rho: jnp.ndarray      # (N, N, d) cumulative value heard per in-link
    rho_m: jnp.ndarray    # (N, N)


def init_state(w: jnp.ndarray) -> PushSumState:
    """w: (N, d) initial values; push-sum drives z/m -> mean(w)."""
    n, d = w.shape
    return PushSumState(
        z=w,
        m=jnp.ones((n,), w.dtype),
        sigma=jnp.zeros((n, d), w.dtype),
        sigma_m=jnp.zeros((n,), w.dtype),
        rho=jnp.zeros((n, n, d), w.dtype),
        rho_m=jnp.zeros((n, n), w.dtype),
    )


def pushsum_step(
    state: PushSumState,
    mask: jnp.ndarray,   # (N, N) bool — operational links this round
    adj: jnp.ndarray,    # (N, N) bool — underlying topology (defines d_out)
) -> PushSumState:
    """One iteration of fast robust push-sum (Alg. 1 / Alg. 3 lines 4-12).

    The mask is intersected with the topology before latching ``rho``: a
    stray True on a non-edge (a malformed schedule) must never corrupt relay
    state — non-edges carry no ``sigma`` and their ``rho`` stays 0 forever.
    """
    z, m, sigma, sigma_m, rho, rho_m = state
    d_out = adj.sum(axis=1).astype(z.dtype)  # (N,) out-degree of underlying graph
    share = 1.0 / (d_out + 1.0)              # (N,)

    # --- first half: stage cumulative send (lines 4-5) ---
    sigma_p = sigma + z * share[:, None]
    sigma_m_p = sigma_m + m * share

    # --- delivery (lines 6-10): successful *existing* links latch the new
    # cumulative; mask & adj guards against out-of-topology mask bits ---
    live = mask & adj
    rho_new = jnp.where(live[:, :, None], sigma_p[:, None, :], rho)
    rho_m_new = jnp.where(live, sigma_m_p[:, None], rho_m)
    recv = (rho_new - rho).sum(axis=0)        # (N, d)
    recv_m = (rho_m_new - rho_m).sum(axis=0)  # (N,)

    # --- integrate (line 11) ---
    z_p = z * share[:, None] + recv
    m_p = m * share + recv_m

    # --- second half: immediately re-stage (line 12) ---
    sigma_n = sigma_p + z_p * share[:, None]
    sigma_m_n = sigma_m_p + m_p * share
    z_n = z_p * share[:, None]
    m_n = m_p * share

    return PushSumState(z_n, m_n, sigma_n, sigma_m_n, rho_new, rho_m_new)


def ratios(state: PushSumState) -> jnp.ndarray:
    """The push-sum estimate z/m per agent, (N, d)."""
    return state.z / jnp.maximum(state.m, 1e-30)[:, None]


def run_pushsum(
    w: jnp.ndarray,       # (N, d) inputs
    adj: jnp.ndarray,     # (N, N) bool topology
    masks: jnp.ndarray,   # (T, N, N) bool operational-link schedule
    record_every: int = 1,
) -> tuple[PushSumState, jnp.ndarray]:
    """Run T iterations; returns final state and (T//record_every, N, d) ratios."""
    adj = jnp.asarray(adj)
    state0 = init_state(jnp.asarray(w))

    def body(state, mask):
        new = pushsum_step(state, mask, adj)
        return new, ratios(new)

    final, traj = jax.lax.scan(body, state0, jnp.asarray(masks))
    return final, traj[::record_every]


def mass_invariant(state: PushSumState, adj: jnp.ndarray) -> jnp.ndarray:
    """Total conserved value: held + in-flight on every link. (d,) vector.

    sum_j z_j + sum_{(j',j) in E} (sigma_{j'} - rho_{j'j})  ==  sum_j w_j
    — the augmented-graph mass-preservation property Theorem 1 relies on.
    Exposed for tests/benchmarks.
    """
    adj_f = jnp.asarray(adj, state.z.dtype)
    in_flight = ((state.sigma[:, None, :] - state.rho) * adj_f[:, :, None]).sum((0, 1))
    return state.z.sum(axis=0) + in_flight


# ---------------------------------------------------------------------------
# Sparse edge-list implementation
# ---------------------------------------------------------------------------

class SparsePushSumState(NamedTuple):
    z: jnp.ndarray        # (N, d) value
    m: jnp.ndarray        # (N,)   mass
    sigma: jnp.ndarray    # (N, d) cumulative value offered per out-link
    sigma_m: jnp.ndarray  # (N,)
    rho: jnp.ndarray      # (E, d) cumulative value heard, per directed edge
    rho_m: jnp.ndarray    # (E,)


def init_sparse_state(
    w: jnp.ndarray, n_edges: int, policy: Policy | str | None = None
) -> SparsePushSumState:
    """w: (N, d) initial values; ``n_edges`` the (padded) edge count E.

    ``policy`` (a :class:`repro.core.precision.Policy`, a name, or ``None``)
    selects the *storage* dtype of every persistent field — the bandwidth
    knob. ``None`` keeps ``w.dtype`` exactly (the pre-policy behavior,
    including float64 states under x64 mode)."""
    n, d = w.shape
    dt = w.dtype if policy is None else resolve_policy(policy).storage_dtype
    return SparsePushSumState(
        z=w.astype(dt),
        m=jnp.ones((n,), dt),
        sigma=jnp.zeros((n, d), dt),
        sigma_m=jnp.zeros((n,), dt),
        rho=jnp.zeros((n_edges, d), dt),
        rho_m=jnp.zeros((n_edges,), dt),
    )


def _out_degree(src: jnp.ndarray, valid: jnp.ndarray, n: int,
                dtype) -> jnp.ndarray:
    return jax.ops.segment_sum(
        valid.astype(dtype), src, num_segments=n, indices_are_sorted=False
    )


def sparse_pushsum_step(
    state: SparsePushSumState,
    mask: jnp.ndarray,     # (E,) bool — operational edges this round
    src: jnp.ndarray,      # (E,) int32 sender per edge
    dst: jnp.ndarray,      # (E,) int32 receiver per edge
    valid: jnp.ndarray,    # (E,) bool — False on padding edges
    backend: str = "auto",
    *,
    share: jnp.ndarray | None = None,
    graph_axis: str | None = None,
    dst_sorted: bool = False,
    policy: Policy | str | None = None,
    halo: str = "psum",
    n_shards: int = 1,
    faults: FaultState | None = None,
    awake: jnp.ndarray | None = None,
    abuf: AsyncBuffer | None = None,
    staleness: jnp.ndarray | None = None,
    seg_end: jnp.ndarray | None = None,
    seg_lo: jnp.ndarray | None = None,
) -> SparsePushSumState | tuple[SparsePushSumState, AsyncBuffer]:
    """One fast-robust-push-sum iteration on edge-list state.

    Identical recursion to :func:`pushsum_step`; delivery gathers
    ``sigma[src]`` per operational edge and integration scatter-adds the
    latched increments into receivers — via ``jax.ops.segment_sum``
    (``backend="xla"``) or the fused Pallas edge-scatter kernel
    (``backend="pallas"``, sorted-by-dst edge layout; see the module
    docstring). The mask is intersected with ``valid`` so padding edges can
    never carry mass — the sparse analogue of the dense step's
    ``mask & adj``. ``backend`` is static: thread it through
    ``static_argnames`` when jitting.

    ``share`` optionally supplies the precomputed (N,) ``1 / (d_out + 1)``
    factors — a loop invariant of the fixed edge index that scan-heavy
    callers (:mod:`repro.core.social`) hoist once instead of re-deriving
    the segment-sum out-degree every iteration. It must equal
    ``1 / (_out_degree(src, valid, N) + 1)`` — computed over the *global*
    edge set when running edge-partitioned (below).

    **Edge-partitioned mode** (``graph_axis=``): inside a
    ``jax.shard_map`` (or an emulating ``vmap(axis_name=...)``) over a
    mesh graph axis, ``src``/``dst``/``valid``/``mask`` and the per-edge
    state carry only this device's (E_shard,) slice of a
    :func:`repro.core.graphs.partition_edge_list` layout while node state
    stays replicated. Each shard computes its local receiver partials and
    the halo combine is one ``lax.psum`` pair over ``graph_axis`` (under
    the scope ``halo``) — interior receivers (all in-edges on one shard)
    get exact ``+0.0`` contributions from foreign shards; only boundary
    receivers (in-edge runs split by a shard cut) see a genuine
    multi-operand sum. The single-device step can still differ in the
    last bits at any receiver: there XLA folds the kept share into the
    receivers' scatter-add, which the psum rules out here (see
    :func:`repro.core.sweeps.run_pushsum_sweep`). When ``share`` is not
    supplied the local out-degree is psum'd the same way before the
    reciprocal.

    ``dst_sorted=True`` asserts the edge index is dst-sorted (the
    partitioner's layout, or :func:`graphs.sort_by_dst` output) and lets
    the XLA lowering's ``segment_sum`` skip its internal sort.

    ``seg_end`` ((N,) int32, :func:`graphs.run_ends` of a dst-sorted index)
    lets the Pallas lowering finish each receiver's sum with a gather at
    its run end instead of the ``segment_sum`` scatter (see
    :mod:`repro.kernels.pushsum_edge`); the XLA and async lowerings do not
    read it, and ``None`` keeps the scatter. With ``seg_lo`` (an int32
    scalar) it covers only the receivers ``seg_lo .. seg_lo + W - 1``
    (``W`` its length), and every other receiver's sum is zero: the form an
    edge shard passes under ``graph_axis`` (its shard's window of
    :meth:`graphs.EdgeShards.run_end_windows`), so that each device gathers
    only the receivers whose runs end on it before the halo combine.

    **Precision policy** (``policy=``, see :mod:`repro.core.precision`):
    persistent state stays in the storage dtype, elementwise staging runs
    in the compute dtype, and every reduction (the per-receiver segment
    sum, the halo combine) runs in the accum dtype. The staged cumulative
    is quantized to storage *before* delivery, and the re-stage reads the
    quantized value back — so receivers latch exactly the value the sender
    persists and the telescoping sums ``rho_new - rho_old`` self-correct:
    quantization error never compounds across rounds, it is re-measured
    against the stored cumulative each time. ``policy=None`` (default) is
    dtype-transparent and emits the bit-identical pre-policy program.

    **Halo variant** (``halo=``, edge-partitioned mode only): ``"psum"``
    all-reduces the full (N, d+1) partials — each device moves
    ``2 (n-1)/n * N (d+1)`` accum-dtype elements per round. ``"scatter"``
    reduce-scatters the partials so each device owns an N/n_shards row
    block, quantizes the *reduced* block to the storage dtype, and
    all-gathers it — ``(n-1)/n * N (d+1)`` accum elements in plus the same
    count of *storage* elements out, i.e. ~25% less wire even at fp32 and
    ~44% less under bf16 storage (modeled in
    :func:`repro.analysis.roofline.pushsum_halo_wire_bytes`). Reduce order
    differs from ``"psum"``, so ``"scatter"`` is opt-in, not bit-identical.
    ``n_shards`` (the graph-axis extent) must be given for ``"scatter"``.

    **Fault plane** (``faults=``, a :class:`repro.core.faults.FaultState`):
    edges with a dead endpoint are masked in both directions and the four
    node-state fields of a dead agent are frozen (``where(live, new, old)``)
    so it rejoins with stale state — the churn semantics of
    :mod:`repro.core.faults`. Per-edge relay state needs no freeze: a
    masked edge never latches. ``faults=None`` (default) emits the
    bit-identical pre-fault program.

    **Async mode** (``awake=`` (N,) bool + ``abuf=`` an
    :class:`repro.core.asyncrony.AsyncBuffer` + ``staleness=`` () int32,
    all three together): one tick of the event-driven engine. Awake
    senders latch this tick's staged cumulative into the per-edge
    bounded buffer (age reset to 0, stale snapshots age by 1); delivery
    latches the *buffered* snapshot into ``rho`` when the link is up,
    the receiver is awake, and the snapshot is at most ``staleness``
    ticks old — a sleeping sender's last message still delivers, which
    is the asynchrony. Asleep agents' node state is frozen exactly like
    churn-dead agents (composes with ``faults=``: effective liveness is
    ``awake & node_live``). Returns ``(state, new_abuf)`` instead of
    the bare state. Delivery always lowers through the XLA
    ``where`` + ``segment_sum`` path (the Pallas edge-scatter kernel
    gathers node-indexed ``sigma`` and cannot read a per-edge buffer);
    the degenerate model (wake-prob 1, staleness 0) reproduces the
    synchronous XLA step bit for bit. Incompatible with
    ``graph_axis=`` edge partitioning.
    """
    from repro.kernels.pushsum_edge import edge_scatter, resolve_backend

    if halo not in HALO_VARIANTS:
        raise ValueError(f"halo={halo!r} not in {HALO_VARIANTS}")
    pol = None if policy is None else resolve_policy(policy)
    z, m, sigma, sigma_m, rho, rho_m = state
    n = z.shape[0]
    if pol is None:
        st_dt = cp_dt = z.dtype
        ac_dt = z.dtype
        accum_name = None
    else:
        st_dt = pol.storage_dtype
        cp_dt = pol.compute_dtype
        ac_dt = pol.accum_dtype
        accum_name = pol.accum
    if share is None:
        d_out = _out_degree(src, valid, n, cp_dt)     # (N,) local
        if graph_axis is not None:
            d_out = jax.lax.psum(d_out, graph_axis)   # (N,) global
        share = 1.0 / (d_out + 1.0)
    share = share.astype(cp_dt)

    # --- first half: stage cumulative send (compute dtype), then quantize
    # to storage — the quantized value is what gets delivered AND re-staged,
    # so relay state and receivers agree exactly ---
    sigma_p = sigma.astype(cp_dt) + z.astype(cp_dt) * share[:, None]
    sigma_m_p = sigma_m.astype(cp_dt) + m.astype(cp_dt) * share
    sigma_p_s = sigma_p.astype(st_dt)
    sigma_m_p_s = sigma_m_p.astype(st_dt)

    # --- delivery: operational edges latch the sender's new cumulative ---
    if faults is not None:
        # a dead endpoint takes the edge down in both directions
        mask = mask & faults.node_live[src] & faults.node_live[dst]
    live = mask & valid
    abuf_new = None
    if abuf is not None:
        if graph_axis is not None:
            raise ValueError(
                "async mode does not compose with graph_axis edge "
                "partitioning (the per-edge buffer would need halo state)"
            )
        # async tick: awake (live) senders overwrite their edges' buffer
        # slot with the freshly staged cumulative; everyone else's
        # snapshot ages by one tick
        send = awake[src] & valid
        if faults is not None:
            send = send & faults.node_live[src]
        snap = jnp.where(send[:, None], sigma_p_s[src], abuf.snap)
        snap_m = jnp.where(send, sigma_m_p_s[src], abuf.snap_m)
        age = jnp.where(send, 0, abuf.age + 1)
        abuf_new = AsyncBuffer(snap=snap, snap_m=snap_m, age=age)
        # delivery consumes the buffer: link up AND receiver awake AND
        # snapshot within the staleness bound. The receiver integrates
        # exactly rho_new - rho_old of the cumulative relay, so mass is
        # conserved under any wake schedule and an expired snapshot is
        # self-healed by the telescoping on the next fresh one.
        live = live & awake[dst] & (age <= staleness)
        rho_new = jnp.where(live[:, None], snap, rho)
        rho_m_new = jnp.where(live, snap_m, rho_m)
        recv = jax.ops.segment_sum(
            rho_new.astype(ac_dt) - rho.astype(ac_dt), dst, num_segments=n,
            indices_are_sorted=dst_sorted,
        )
        recv_m = jax.ops.segment_sum(
            rho_m_new.astype(ac_dt) - rho_m.astype(ac_dt), dst,
            num_segments=n, indices_are_sorted=dst_sorted,
        )
    elif resolve_backend(backend) == "pallas":
        # value + mass columns in one (·, d+1) pass through the kernel
        sigma_cat = jnp.concatenate([sigma_p_s, sigma_m_p_s[:, None]], axis=1)
        rho_cat = jnp.concatenate([rho, rho_m[:, None]], axis=1)
        rho_cat_new, recv_cat = edge_scatter(
            sigma_cat, rho_cat, live, src, dst, backend="pallas",
            seg_end=seg_end, seg_lo=seg_lo, indices_sorted=dst_sorted,
            accum_dtype=accum_name,
        )
        rho_new, rho_m_new = rho_cat_new[:, :-1], rho_cat_new[:, -1]
        recv, recv_m = recv_cat[:, :-1], recv_cat[:, -1]
    else:
        rho_new = jnp.where(live[:, None], sigma_p_s[src], rho)
        rho_m_new = jnp.where(live, sigma_m_p_s[src], rho_m)
        recv = jax.ops.segment_sum(
            rho_new.astype(ac_dt) - rho.astype(ac_dt), dst, num_segments=n,
            indices_are_sorted=dst_sorted,
        )
        recv_m = jax.ops.segment_sum(
            rho_m_new.astype(ac_dt) - rho_m.astype(ac_dt), dst,
            num_segments=n, indices_are_sorted=dst_sorted,
        )
    if graph_axis is not None:
        recv, recv_m = _halo(recv, recv_m, graph_axis, halo, n_shards,
                             st_dt, ac_dt)

    # --- integrate (accum dtype) ---
    z_p = (z.astype(cp_dt) * share[:, None]).astype(ac_dt) + recv
    m_p = (m.astype(cp_dt) * share).astype(ac_dt) + recv_m

    # --- second half: immediately re-stage, downcast to storage ---
    z_pc = z_p.astype(cp_dt)
    m_pc = m_p.astype(cp_dt)
    sigma_n = (sigma_p_s.astype(cp_dt) + z_pc * share[:, None]).astype(st_dt)
    sigma_m_n = (sigma_m_p_s.astype(cp_dt) + m_pc * share).astype(st_dt)
    z_n = (z_pc * share[:, None]).astype(st_dt)
    m_n = (m_pc * share).astype(st_dt)

    if awake is not None:
        # asleep agents do nothing: same freeze as churn, composing to
        # an effective liveness of awake & node_live
        z_n = freeze(awake, z_n, z)
        m_n = freeze(awake, m_n, m)
        sigma_n = freeze(awake, sigma_n, sigma)
        sigma_m_n = freeze(awake, sigma_m_n, sigma_m)
    if faults is not None:
        # freeze dead agents: state carries unchanged through the dead
        # rounds (stale-rejoin semantics) and every term of the global
        # mass invariant is conserved exactly — the live rest just sees
        # an ordinary all-edges-dropped round toward the dead agent
        ln = faults.node_live
        z_n = freeze(ln, z_n, z)
        m_n = freeze(ln, m_n, m)
        sigma_n = freeze(ln, sigma_n, sigma)
        sigma_m_n = freeze(ln, sigma_m_n, sigma_m)

    new = SparsePushSumState(z_n, m_n, sigma_n, sigma_m_n, rho_new, rho_m_new)
    if abuf is not None:
        return new, abuf_new
    return new


def _halo(recv, recv_m, graph_axis, halo, n_shards, st_dt, ac_dt):
    """The halo combine of the edge-partitioned step, under the scope
    ``halo``: every shard's receiver partials summed over ``graph_axis``
    (see :func:`sparse_pushsum_step`'s ``halo=``)."""
    with jax.named_scope("halo"):
        if halo == "scatter":
            # reduce-scatter + quantize + all-gather: each device reduces
            # its own N/n_shards row block, so the gathered payload can ride
            # the storage dtype (the reduction already happened in accum)
            n = recv.shape[0]
            cat = jnp.concatenate([recv, recv_m[:, None]], axis=1)
            pad_n = (-n) % n_shards
            if pad_n:
                cat = jnp.pad(cat, ((0, pad_n), (0, 0)))
            part = jax.lax.psum_scatter(
                cat, graph_axis, scatter_dimension=0, tiled=True
            )
            cat = jax.lax.all_gather(
                part.astype(st_dt), graph_axis, axis=0, tiled=True
            ).astype(ac_dt)
            if pad_n:
                cat = cat[:n]
            return cat[:, :-1], cat[:, -1]
        # interior receivers add exact +0.0 partials, boundary receivers
        # (see EdgeShards.boundary) sum their split in-edge runs
        return (jax.lax.psum(recv, graph_axis),
                jax.lax.psum(recv_m, graph_axis))


_HALF_DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


def sparse_ratios(state: SparsePushSumState) -> jnp.ndarray:
    """The push-sum estimate z/m per agent, (N, d).

    Half-precision storage states are upcast to fp32 for the division —
    the 1e-30 mass floor underflows to zero in bf16/fp16, and the ratio is
    a diagnostic, not a persistent value (a static dtype check, so fp32 and
    fp64 states keep the bit-identical pre-policy program)."""
    z, m = state.z, state.m
    if z.dtype in _HALF_DTYPES:
        z, m = z.astype(jnp.float32), m.astype(jnp.float32)
    return z / jnp.maximum(m, 1e-30)[:, None]


def sparse_mass_invariant(
    state: SparsePushSumState,
    src: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    graph_axis: str | None = None,
) -> jnp.ndarray:
    """sum_j z_j + sum_{e valid} (sigma[src[e]] - rho[e]) == sum_j w_j, (d,).

    Under edge partitioning (``graph_axis=``) the per-edge in-flight term is
    psum'd over the shards while the replicated node sum is counted once.
    Half-precision storage states are upcast to fp32 before the O(E) sums
    (same static-dtype rule as :func:`sparse_ratios`).
    """
    z, sigma, rho = state.z, state.sigma, state.rho
    if z.dtype in _HALF_DTYPES:
        z = z.astype(jnp.float32)
        sigma = sigma.astype(jnp.float32)
        rho = rho.astype(jnp.float32)
    vf = valid.astype(z.dtype)
    in_flight = ((sigma[src] - rho) * vf[:, None]).sum(axis=0)
    if graph_axis is not None:
        in_flight = jax.lax.psum(in_flight, graph_axis)
    return z.sum(axis=0) + in_flight


# Compiled step entry points, keyed by their static arguments. Donation is
# the point: ``state`` in and ``state`` out have identical avals leaf-for-
# leaf, so donating argument 0 lets XLA alias every output buffer onto its
# input — the (E, d) relay state, the dominant allocation, is updated
# in-place instead of double-buffered. The statics lint's donation check
# asserts the compiled executable actually reports the aliasing
# (``repro.statics.cli``). Keyed dict rather than functools.lru_cache so
# the retrace sentinel can sum the inner jit cache sizes.
_STEP_JIT: dict = {}


def _step_jit_entries() -> int:
    return sum(f._cache_size() for f in _STEP_JIT.values())


register_statics_cache("pushsum.step-jit", _step_jit_entries)


def sparse_pushsum_step_jit(
    state: SparsePushSumState,
    mask: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    valid: jnp.ndarray,
    backend: str = "auto",
    *,
    share: jnp.ndarray | None = None,
    dst_sorted: bool = False,
    policy: Policy | str | None = None,
) -> SparsePushSumState:
    """Jitted :func:`sparse_pushsum_step` with the input state *donated*.

    The returned state reuses the argument's buffers, so the caller must
    not touch ``state`` afterwards — standard donation semantics. Use this
    for step-at-a-time driving (benchmarks, interactive loops); inside a
    ``lax.scan`` the carry is already double-buffer-free, and the sweep
    bodies have no aval-matched input/output pairs to donate (their inputs
    are (K,)-batched scenarios, their outputs reductions), which is why
    donation lives on the step entry and not the sweep jits.

    Values match calling :func:`sparse_pushsum_step` op-by-op up to XLA's
    whole-function fusion (FMA contraction), ~1 ulp on the value columns.

    ``graph_axis`` mode is excluded: collectives need a surrounding
    ``shard_map``, whose jit owns the donation decision there.
    """
    pol = None if policy is None else resolve_policy(policy)
    return _get_step_jit(backend, dst_sorted, pol)(
        state, mask, src, dst, valid, share)


def _get_step_jit(backend: str, dst_sorted: bool, pol: Policy | None):
    """Build-or-fetch the donating jitted step for one static key. Split
    from :func:`sparse_pushsum_step_jit` so :mod:`repro.statics.precision`
    can ``.lower()`` the exact shipped callable (proving the compiled
    executable aliases the donated state buffers) without executing it."""
    key = (backend, dst_sorted, pol)
    fn = _STEP_JIT.get(key)
    if fn is None:
        def _step(state, mask, src, dst, valid, share,
                  _backend=backend, _sorted=dst_sorted, _pol=pol):
            return sparse_pushsum_step(
                state, mask, src, dst, valid, _backend,
                share=share, dst_sorted=_sorted, policy=_pol,
            )

        fn = jax.jit(_step, donate_argnums=(0,))
        _STEP_JIT[key] = fn
    return fn


def step_edge_mask(
    key: jnp.ndarray,
    t: jnp.ndarray,
    n_edges: int,
    drop_prob,
    B,
    fold_t=None,
) -> jnp.ndarray:
    """(E,) operational mask for round t: i.i.d. Bernoulli keep with forced
    delivery at ``t % B == B - 1`` (the paper's B-connectivity window),
    matching :func:`repro.core.graphs.link_schedule` semantics without ever
    materializing a (T, N, N) schedule.

    ``fold_t`` overrides the fold-in value (default ``t``) so callers that
    consume several PRNG streams per iteration can give the link-mask
    stream its own disjoint fold-in domain (see
    :func:`repro.core.social.social_stream_fold`) while the B-window logic
    still runs on the *iteration* index. ``drop_prob`` and ``B`` may be
    traced scalars — scenario sweeps put both on a vmap axis.
    """
    kt = jax.random.fold_in(key, t if fold_t is None else fold_t)
    up = jax.random.uniform(kt, (n_edges,)) >= drop_prob
    return up | ((t % B) == (B - 1))


def shard_edge_mask(
    key: jnp.ndarray,
    t: jnp.ndarray,
    e_shard: int,
    drop_prob,
    B,
    *,
    graph_axis: str,
    n_shards: int,
    fold_t=None,
) -> jnp.ndarray:
    """This device's (E_shard,) window of the round-t operational mask.

    Bit-identity anchor of the edge-partitioned mode: every shard draws the
    *full* (n_shards * e_shard,) Bernoulli vector — threefry bits are a
    function of (key, counter position), so there is no per-slice shortcut
    that reproduces a window of a longer draw — then dynamically slices its
    own window at ``axis_index(graph_axis) * e_shard``. The result equals
    ``step_edge_mask(key, t, e_pad, ...)`` restricted to this shard's slots
    exactly, which is what makes the sharded run bit-comparable to the
    single-device reference over ``EdgeShards.padded_edge_list()``. The
    full draw is O(e_pad) *bytes* per device per round — accounted in
    :func:`repro.statics.memory.pushsum_sharded_step_bytes` — but carries
    no (E_pad, d) payload.
    """
    full = step_edge_mask(key, t, n_shards * e_shard, drop_prob, B,
                          fold_t=fold_t)
    start = jax.lax.axis_index(graph_axis) * e_shard
    return jax.lax.dynamic_slice(full, (start,), (e_shard,))


@statics_contract(
    name="pushsum",
    # The sparse core's reason to exist: no (N, N) value may ever appear
    # in the traced program (the trajectory output is (T, N, d) — fine).
    forbidden={"*": (("N", "N"),)},
    # One PRNG stream, folded at the plain iteration index; engines that
    # add more streams must move to a strided domain (see social/byzantine).
    streams=(("link", lambda t: t),),
    caches=("pushsum.sweep-jit",),
)
def run_pushsum_sparse(
    w: jnp.ndarray,            # (N, d) inputs
    src: jnp.ndarray,          # (E,) int32
    dst: jnp.ndarray,          # (E,) int32
    T: int,
    *,
    drop_prob=0.0,
    B: int = 1,
    key: jnp.ndarray | None = None,
    valid: jnp.ndarray | None = None,
    masks: jnp.ndarray | None = None,   # optional explicit (T, E) schedule
    record_every: int = 1,
    plan: ExecutionPlan | None = None,
    **legacy,
) -> tuple[SparsePushSumState, jnp.ndarray]:
    """Run T iterations of the edge-list core.

    Masks are (E,) Bernoulli draws generated inside the scan from ``key``
    (drop_prob / B semantics of :func:`graphs.link_schedule`); pass an
    explicit ``masks`` (T, E) schedule instead to reproduce a dense run
    bit-for-bit (see :func:`graphs.edge_masks`); its length must equal T.

    Execution knobs ride ``plan=`` (:class:`repro.core.plan.ExecutionPlan`;
    loose ``backend=``/``policy=``/``dst_sorted=``/``faults=`` kwargs are
    deprecated shims that fold into a plan bit-identically):
    ``plan.backend`` selects the per-round delivery lowering (module
    docstring); ``"pallas"`` expects a dst-sorted edge index.
    ``plan.policy`` selects the storage dtype of the scan-carried state
    (:mod:`repro.core.precision`; ``None`` = dtype-transparent fp32
    default, bit-identical to the pre-policy engine); ``plan.dst_sorted``
    declares the edge index sorted by receiver so the integration scatter
    gets the sorted-segments hint.

    Returns the final state and the ratio trajectory recorded at rounds
    ``record_every - 1, 2*record_every - 1, ...`` — i.e. the *end* of each
    record window, so the last row is always round T-1 when ``record_every``
    divides T. In the key-driven path with ``record_every`` dividing T the
    recording happens inside the scan (a fori_loop per window), so only
    T/record_every ratio frames ever exist — at N=1024 this is what keeps
    long-horizon runs O(N d) instead of O(T N d).

    ``plan.faults`` (a :class:`repro.core.faults.FaultModel`) activates
    the unified fault plane: the Bernoulli link draw generalizes to a
    per-edge Gilbert-Elliott burst chain, agents churn on the liveness
    mask (edges down, state frozen, stale rejoin), and the per-round
    realization state — O(E) + O(N), carried in the scan — advances on
    the fault plane's own disjoint PRNG streams. ``faults=None``
    (default) emits the bit-identical pre-fault program, and a
    degenerate :func:`repro.core.faults.make_fault_model` reproduces the
    same mask values draw-for-draw. Incompatible with an explicit
    ``masks`` schedule.

    ``plan.async_`` (an :class:`repro.core.asyncrony.AsyncModel`)
    activates the event-driven mode: agents wake on independent
    Bernoulli-discretized Poisson clocks (their own disjoint PRNG
    stream), messages ride per-edge bounded stale buffers — an O(E·d)
    extra scan carry — and each scan tick steps one block of concurrent
    wakeups. Composes with ``plan.faults``; incompatible with an
    explicit ``masks`` schedule. The degenerate
    :func:`repro.core.asyncrony.make_async_model` (wake-prob 1,
    staleness 0) is bit-identical to the synchronous engine.
    """
    plan = resolve_plan(
        plan, _entry="run_pushsum_sparse",
        _supports=("backend", "policy", "dst_sorted", "faults", "async_"),
        **legacy)
    backend, policy = plan.backend, plan.policy
    dst_sorted, faults, async_ = plan.dst_sorted, plan.faults, plan.async_
    if async_ is not None and is_degenerate_async(async_):
        # bit-identity by construction: a concretely degenerate model IS
        # the synchronous engine (see repro.core.asyncrony)
        async_ = None
    w = jnp.asarray(w)
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    E = src.shape[0]
    if valid is None:
        valid = jnp.ones((E,), bool)
    else:
        valid = jnp.asarray(valid, bool)
    state0 = init_sparse_state(w, E, policy=policy)
    k = record_every

    if masks is not None:
        if faults is not None:
            raise ValueError(
                "faults= requires key-driven masks; an explicit masks "
                "schedule already fixes the link realization"
            )
        if async_ is not None:
            raise ValueError(
                "async_= requires key-driven masks; an explicit masks "
                "schedule already fixes the link realization"
            )
        masks = jnp.asarray(masks)
        if masks.shape[0] != T:
            raise ValueError(
                f"masks schedule has {masks.shape[0]} rounds but T={T}"
            )

        def body(state, mask):
            new = sparse_pushsum_step(state, mask, src, dst, valid, backend,
                                      policy=policy, dst_sorted=dst_sorted)
            return new, sparse_ratios(new)

        final, traj = jax.lax.scan(body, state0, masks)
        return final, traj[k - 1 :: k]

    if key is None:
        key = jax.random.PRNGKey(0)

    if faults is not None or async_ is not None:
        # stateful scan: the carry gains the O(E) + O(N) FaultState
        # and/or the O(E·d) AsyncBuffer. The link uniform is drawn on
        # the SAME fold as step_edge_mask, so the degenerate FaultModel
        # reproduces the Bernoulli mask values draw-for-draw, while the
        # GE/churn and wake streams live in their own disjoint fold-in
        # domains.
        n_nodes = w.shape[0]
        carry0 = (state0,)
        if async_ is not None:
            carry0 += (init_async_buffer(E, w.shape[1], state0.z.dtype),)
        if faults is not None:
            carry0 += (init_fault_state(n_nodes, E),)

        def stateful_round(carry, t):
            state = carry[0]
            abuf = carry[1] if async_ is not None else None
            fs = carry[-1] if faults is not None else None
            if faults is not None:
                fs = step_faults(key, t, faults, fs, engine=ENGINE_PUSHSUM)
                u = jax.random.uniform(jax.random.fold_in(key, t), (E,))
                mask = faulty_edge_mask(u, t, faults, fs, src, dst,
                                        drop_prob, B)
            else:
                mask = step_edge_mask(key, t, E, drop_prob, B)
            if async_ is not None:
                awake = wake_mask(key, t, n_nodes, async_.wake_prob,
                                  engine=ENGINE_PUSHSUM)
                new, abuf = sparse_pushsum_step(
                    state, mask, src, dst, valid, backend, policy=policy,
                    dst_sorted=dst_sorted, faults=fs, awake=awake,
                    abuf=abuf, staleness=async_.staleness)
            else:
                new = sparse_pushsum_step(
                    state, mask, src, dst, valid, backend, policy=policy,
                    dst_sorted=dst_sorted, faults=fs)
            out = (new,)
            if async_ is not None:
                out += (abuf,)
            if faults is not None:
                out += (fs,)
            return out

        if k > 1 and T % k == 0:
            def swindow(carry, t0):
                new = jax.lax.fori_loop(
                    0, k, lambda i, c: stateful_round(c, t0 + jnp.uint32(i)),
                    carry)
                return new, sparse_ratios(new[0])

            (final, *_), traj = jax.lax.scan(
                swindow, carry0, jnp.arange(0, T, k, dtype=jnp.uint32))
            return final, traj

        def sbody(carry, t):
            new = stateful_round(carry, t)
            return new, sparse_ratios(new[0])

        (final, *_), traj = jax.lax.scan(
            sbody, carry0, jnp.arange(T, dtype=jnp.uint32))
        return final, traj[k - 1 :: k]

    if k > 1 and T % k == 0:
        # record inside the scan: one fori_loop per window, one frame out
        def window(state, t0):
            def inner(i, st):
                mask = step_edge_mask(key, t0 + jnp.uint32(i), E, drop_prob, B)
                return sparse_pushsum_step(st, mask, src, dst, valid, backend,
                                           policy=policy,
                                           dst_sorted=dst_sorted)

            new = jax.lax.fori_loop(0, k, inner, state)
            return new, sparse_ratios(new)

        final, traj = jax.lax.scan(
            window, state0, jnp.arange(0, T, k, dtype=jnp.uint32)
        )
        return final, traj

    def body(state, t):
        mask = step_edge_mask(key, t, E, drop_prob, B)
        new = sparse_pushsum_step(state, mask, src, dst, valid, backend,
                                  policy=policy, dst_sorted=dst_sorted)
        return new, sparse_ratios(new)

    final, traj = jax.lax.scan(body, state0, jnp.arange(T, dtype=jnp.uint32))
    return final, traj[k - 1 :: k]
