"""Vmapped + mesh-sharded scenario sweeps — "as many scenarios as you can
imagine".

The sparse edge-list push-sum core (:mod:`repro.core.pushsum`) keeps per-
scenario state at O(E d), so a whole grid of scenarios — seeds x drop
probabilities x topology draws — fits comfortably in one ``jax.vmap`` over a
single compiled ``lax.scan``. One XLA program executes every scenario in
lockstep; per-scenario consensus error is reduced inside the scan so the
sweep's memory is O(K (N d + E d)) regardless of T. Pass a ``mesh`` to
:func:`run_pushsum_sweep` and the scenario axis is additionally sharded
over the mesh's ``data`` axis with ``shard_map`` (one scenario batch per
device), so grids in the thousands run as one program across the fleet.

Two engines:

* :func:`run_pushsum_sweep` — Theorem 1 dynamics (Alg. 1 consensus) over
  seed x drop_prob x topology-draw grids; ``backend`` selects the XLA or
  fused-Pallas delivery lowering per round.
* :func:`run_byzantine_sweep` — Algorithm 2 learning over seed batches per
  attack. Attack *type* changes the traced program (attacks are function-
  valued), so types iterate host-side while seeds ride the vmap axis; the
  compiled scan per (model, config, T, attack) is cached module-side so
  repeated calls with the same shapes/config never retrace.
* :func:`run_byzantine_grid` — batched (topology, F) x seed grids on the
  sparse neighbor-list core: compatible configs (same N, M, m; neighbor
  lists padded to a common deg_max) stack leaf-wise into one
  :class:`repro.core.byzantine.ByzRuntime` batch and the whole grid runs as
  ONE vmapped scan — heterogeneous F rides the scenario axis as a traced
  scalar through the sort-based trim. Pass ``mesh=`` to shard the scenario
  axis like :func:`run_pushsum_sweep`.
* :func:`run_hps_grid` / :func:`run_hps_sweep` — Algorithm 1 (hierarchical
  push-sum) over batched (topology x M x Gamma x drop) x seed grids on the
  fused HPS engine (:mod:`repro.core.hps`): compatible configs (same N;
  edge lists padded to a common E) stack leaf-wise into one
  :class:`repro.core.hps.HPSRuntime` batch, with drop_prob, Gamma, the
  B-window AND the sub-network count M riding the scenario axis as traced
  scalars — grids may mix hierarchies with different numbers of
  sub-networks in one compiled program. ``store="gap"`` (default) reduces
  each scenario's Theorem-1 consensus-error curve inside the scan.
* :func:`run_social_grid` / :func:`run_social_sweep` — Algorithm 3
  (packet-drop-tolerant non-Bayesian learning) over batched
  (topology x drop_prob x Gamma) x seed grids on the fused social engine
  (:mod:`repro.core.social`): compatible configs (same N, M; edge lists
  padded to a common E) stack leaf-wise into one
  :class:`repro.core.social.SocialRuntime` batch, with drop_prob, the
  fusion period Gamma, and the B-window riding the scenario axis as traced
  scalars — the whole grid is ONE traced program, jitted once per
  (mesh, statics) combo regardless of model or topology identity. Pass
  ``mesh=`` to shard the scenario axis like the other engines.

Compiled-executable caches are LRU-bounded (:class:`_LRUCache`): long
parameter studies cycle through many config fingerprints, and an unbounded
dict would pin every retired executable for the process lifetime.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from collections import OrderedDict
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .attacks import Attack
from .asyncrony import (
    AsyncModel,
    init_async_buffer,
    is_degenerate_async,
    wake_mask,
)
from .faults import (
    ENGINE_PUSHSUM,
    FaultModel,
    edge_uniforms,
    faulty_edge_mask,
    init_fault_state,
    step_faults,
)
from .byzantine import (
    ByzantineConfig,
    ByzantineResult,
    ByzRuntime,
    _scan_core,
    _sparse_gossip,
    make_byzantine_runtime,
    make_byzantine_scan,
)
from .graphs import EdgeList, EdgeShards, partition_edge_list
from .plan import ExecutionPlan, resolve_plan
from .precision import Policy, resolve_policy
from .pushsum import (
    _out_degree,
    init_sparse_state,
    shard_edge_mask,
    sparse_mass_invariant,
    sparse_pushsum_step,
    sparse_ratios,
    step_edge_mask,
)
from .hps import (
    HPS_STORES,
    HPSConfig,
    HPSRuntime,
    _hps_scan_core,
    make_hps_runtime,
)
from .signals import SignalModel
from .social import (
    SOCIAL_STORES,
    SocialRuntime,
    _social_scan_core,
    make_social_runtime,
    stack_runtimes,
)
from repro.statics.contracts import contract as statics_contract
from repro.statics.retrace import register_cache as register_statics_cache

__all__ = [
    "PushSumSweepResult",
    "ByzantineGridResult",
    "HPSSweepResult",
    "SocialSweepResult",
    "CacheHandle",
    "CacheInfo",
    "cache_registry",
    "edge_placement_info",
    "run_pushsum_sweep",
    "run_byzantine_sweep",
    "run_byzantine_grid",
    "run_hps_sweep",
    "run_hps_grid",
    "run_social_sweep",
    "run_social_grid",
]


class _LRUCache(OrderedDict):
    """Bounded mapping with least-recently-used eviction.

    Used for the compiled-scan caches below: entries are jit wrappers plus
    their executables, keyed on config fingerprints. Reads refresh recency;
    inserting beyond ``maxsize`` drops the stalest entry, so a long
    parameter study holds at most ``maxsize`` executables at a time.
    """

    def __init__(self, maxsize: int):
        super().__init__()
        self.maxsize = maxsize

    def __getitem__(self, key):
        val = super().__getitem__(key)
        self.move_to_end(key)
        return val

    def get(self, key, default=None):
        if key in self:
            return self[key]
        return default

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self.move_to_end(key)
        while len(self) > self.maxsize:
            # not popitem(): its C path re-enters the recency-tracking
            # __getitem__ on the half-unlinked entry
            del self[next(iter(self))]


# ---------------------------------------------------------------------------
# The unified index-column convention of every *SweepResult / *GridResult:
# each result row is one scenario on the flattened leading K axis, and the
# flattening order is FIXED across all four engines —
#
#     scenario coordinates (graph/cfg major, then drop, gamma, ..., seed)
#       -> fault axis (minor of every scenario coordinate)
#         -> async axis (minor-most)
#
# so e.g. with NF fault models and NA async models, row
# k = ((s * NF) + f) * NA + a. Every index column is a (K,) array; an
# ABSENT axis is ``None`` (not a column of zeros), and ``describe()`` —
# shared by all four result types — names each axis, its level count, and
# its position in the order. (Pre-PR-10, ``fault`` was a column on three
# results and missing from ByzantineGridResult entirely.)
# ---------------------------------------------------------------------------

#: Index-column order of the shared ``describe()``: scenario coordinates
#: first (engine-specific), then ``fault``, then ``async_`` (minor-most).
_AXIS_ORDER = ("graph", "cfg", "drop_prob", "gamma", "M", "F", "seed",
               "fault", "async_")

#: Fields of the result tuples that are payload, not index columns.
_PAYLOAD_FIELDS = frozenset({
    "err", "final_ratio", "mass_gap", "beliefs", "log_ratio", "ratio",
    "gap", "r", "decisions",
})


def _describe_result(res) -> str:
    """Shared ``describe()``: one line per index column in the fixed
    scenario -> fault -> async order, naming levels and payload shapes."""
    lines = [
        f"{type(res).__name__}: K={res.K} scenarios "
        "(row order: scenario coords -> fault -> async_, async minor-most)"
    ]
    for name in _AXIS_ORDER:
        if name not in getattr(res, "_fields", ()):
            continue
        v = getattr(res, name)
        if v is None:
            lines.append(f"  {name:<9} absent (no axis)")
            continue
        arr = np.asarray(v)
        uniq = np.unique(arr)
        preview = ", ".join(str(x) for x in uniq[:6])
        if uniq.size > 6:
            preview += ", ..."
        lines.append(f"  {name:<9} {uniq.size} level(s): [{preview}]")
    payload = [f"{n}{tuple(np.asarray(getattr(res, n)).shape)}"
               for n in res._fields
               if n in _PAYLOAD_FIELDS and getattr(res, n) is not None]
    lines.append("  payload: " + ", ".join(payload))
    return "\n".join(lines)


class PushSumSweepResult(NamedTuple):
    err: jnp.ndarray          # (K, T) max-agent consensus error per round
    final_ratio: jnp.ndarray  # (K, N, d) z/m estimates at T
    mass_gap: jnp.ndarray     # (K, d) mass-invariant violation at T
    drop_prob: jnp.ndarray    # (K,) scenario coordinates
    seed: jnp.ndarray         # (K,)
    graph: jnp.ndarray        # (K,) topology-draw index
    fault: jnp.ndarray | None = None  # (K,) fault-model index, None = no axis
    async_: jnp.ndarray | None = None  # (K,) async-model index, minor-most

    @property
    def K(self) -> int:
        return int(self.err.shape[0])

    def describe(self) -> str:
        return _describe_result(self)


def _scenario_grid(n_graphs: int, drop_probs, seeds):
    """Flatten the (graph x drop x seed) grid into K-long coordinate arrays."""
    drop_probs = np.atleast_1d(np.asarray(drop_probs, np.float32))
    seeds = np.atleast_1d(np.asarray(seeds, np.uint32))
    g, d, s = np.meshgrid(
        np.arange(n_graphs, dtype=np.int32), drop_probs, seeds, indexing="ij"
    )
    return g.ravel(), d.ravel(), s.ravel()


def _expand_fault_axis(coords, faults):
    """Cross a fault-model list into flattened scenario coordinates.

    ``coords`` is a tuple of (K,) arrays; returns ``(coords, fi, stacked)``
    where ``fi`` is the (K * NF,) fault-index coordinate (fault minor, so
    existing scenario ordering is preserved) and ``stacked`` the
    leaf-stacked FaultModel batch with (NF,) leaves — or
    ``(coords, None, None)`` when ``faults`` is None (no fault axis, and
    downstream emits the bit-identical pre-fault program)."""
    if faults is None:
        return coords, None, None
    fl = [faults] if isinstance(faults, FaultModel) else list(faults)
    if not fl:
        raise ValueError("faults= needs at least one FaultModel")
    nf = len(fl)
    k = coords[0].shape[0]
    coords = tuple(np.repeat(c, nf) for c in coords)
    fi = np.tile(np.arange(nf, dtype=np.int32), k)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *fl)
    return coords, fi, stacked


def _expand_async_axis(coords, async_):
    """Cross an async-model list into flattened scenario coordinates.

    Mirror of :func:`_expand_fault_axis` for the
    :class:`repro.core.asyncrony.AsyncModel` axis. Applied AFTER the fault
    expansion (pass ``fi`` inside ``coords``), so the async index is
    minor-most in the unified row order — see the index-column convention
    above. Returns ``(coords, ai, stacked)`` or ``(coords, None, None)``
    when ``async_`` is None (no axis, synchronous program)."""
    if async_ is None:
        return coords, None, None
    al = [async_] if isinstance(async_, AsyncModel) else list(async_)
    if not al:
        raise ValueError("async_= needs at least one AsyncModel")
    na = len(al)
    k = coords[0].shape[0]
    coords = tuple(np.repeat(c, na) for c in coords)
    ai = np.tile(np.arange(na, dtype=np.int32), k)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *al)
    return coords, ai, stacked


def _sweep_body(w, src_b, dst_b, valid_b, drop_b, seed_b, fault_b=None,
                async_b=None, *,
                T, B, backend, policy=None, dst_sorted=False):
    """Vmapped scenario batch: the shared traced program of both the
    single-device and the shard_map-per-device sweep paths.

    ``fault_b`` is an optional batched :class:`repro.core.faults.FaultModel`
    (leaves (K,)) riding the scenario axis — fault severity is traced per
    scenario, same executable for the whole fault grid. ``async_b`` the
    optional batched :class:`repro.core.asyncrony.AsyncModel` (leaves (K,))
    for the event-driven mode, riding the same axis. ``None`` for both
    emits the bit-identical pre-fault/synchronous program."""
    E = src_b.shape[1]
    n = w.shape[0]
    target = w.mean(axis=0)          # (d,) true average, shared
    w_sum = w.sum(axis=0)

    def single(src, dst, valid, drop, seed, fault=None, am=None):
        key = jax.random.PRNGKey(seed)
        state0 = init_sparse_state(w, E, policy=policy)

        if fault is None and am is None:
            def body(state, t):
                mask = step_edge_mask(key, t, E, drop, B)
                new = sparse_pushsum_step(
                    state, mask, src, dst, valid, backend,
                    dst_sorted=dst_sorted, policy=policy,
                )
                err = jnp.abs(sparse_ratios(new) - target).max()
                return new, err

            final, errs = jax.lax.scan(
                body, state0, jnp.arange(T, dtype=jnp.uint32)
            )
        else:
            def body(carry, t):
                # carry: (state,) [+ abuf if async] [+ fault_state last]
                state = carry[0]
                fs = None
                if fault is not None:
                    fs = step_faults(key, t, fault, carry[-1],
                                     engine=ENGINE_PUSHSUM)
                    u = jax.random.uniform(jax.random.fold_in(key, t), (E,))
                    mask = faulty_edge_mask(u, t, fault, fs, src, dst,
                                            drop, B)
                else:
                    mask = step_edge_mask(key, t, E, drop, B)
                if am is not None:
                    awake = wake_mask(key, t, n, am.wake_prob,
                                      engine=ENGINE_PUSHSUM)
                    new, abuf = sparse_pushsum_step(
                        state, mask, src, dst, valid, backend,
                        dst_sorted=dst_sorted, policy=policy, faults=fs,
                        awake=awake, abuf=carry[1], staleness=am.staleness,
                    )
                else:
                    abuf = None
                    new = sparse_pushsum_step(
                        state, mask, src, dst, valid, backend,
                        dst_sorted=dst_sorted, policy=policy, faults=fs,
                    )
                err = jnp.abs(sparse_ratios(new) - target).max()
                out = (new,)
                if am is not None:
                    out = out + (abuf,)
                if fault is not None:
                    out = out + (fs,)
                return out, err

            carry0 = (state0,)
            if am is not None:
                carry0 = carry0 + (
                    init_async_buffer(E, w.shape[1], state0.z.dtype),)
            if fault is not None:
                carry0 = carry0 + (init_fault_state(n, E),)
            (final, *_), errs = jax.lax.scan(
                body, carry0, jnp.arange(T, dtype=jnp.uint32)
            )
        gap = sparse_mass_invariant(final, src, valid) - w_sum
        return errs, sparse_ratios(final), gap

    if fault_b is None and async_b is None:
        return jax.vmap(single)(src_b, dst_b, valid_b, drop_b, seed_b)
    if async_b is None:
        return jax.vmap(single)(src_b, dst_b, valid_b, drop_b, seed_b,
                                fault_b)
    if fault_b is None:
        return jax.vmap(
            lambda s, d, v, dr, sd, am: single(s, d, v, dr, sd, None, am)
        )(src_b, dst_b, valid_b, drop_b, seed_b, async_b)
    return jax.vmap(single)(src_b, dst_b, valid_b, drop_b, seed_b, fault_b,
                            async_b)


# Module-level jit so repeated sweeps with the same shapes/statics hit the
# compilation cache instead of retracing a fresh closure per call.
_sweep_compiled = functools.partial(
    jax.jit, static_argnames=("T", "B", "backend", "policy", "dst_sorted")
)(_sweep_body)


@functools.lru_cache(maxsize=None)
def _sweep_sharded(mesh: Mesh, data_axis: str, T: int, B: int, backend: str,
                   policy: Policy | None = None, dst_sorted: bool = False,
                   has_faults: bool = False, has_async: bool = False):
    """Jitted shard_map sweep for one (mesh, axis, statics) combo: the
    scenario axis of every batched argument is split over ``data_axis``,
    one contiguous scenario block per device, and each device runs the
    identical vmapped scan on its block. lru_cache keeps one compiled
    executable per combo (Mesh is hashable), mirroring ``_sweep_compiled``'s
    retrace-free behaviour. ``has_faults``/``has_async`` add the batched
    FaultModel / AsyncModel arguments (sharded over ``data_axis`` like
    every scenario coordinate). The body is manual over every mesh axis,
    not just ``data_axis``: a Pallas kernel cannot sit in an
    auto-partitioned region, and the other axes only see replicas."""

    base = functools.partial(_sweep_body, T=T, B=B, backend=backend,
                             policy=policy, dst_sorted=dst_sorted)
    if has_async and not has_faults:
        # shard_map passes positionally; skip the absent fault_b slot
        def body(w, src, dst, valid, drop, seed, async_b):
            return base(w, src, dst, valid, drop, seed, None, async_b)
    else:
        body = base
    in_specs = (P(), P(data_axis), P(data_axis), P(data_axis),
                P(data_axis), P(data_axis))
    if has_faults:
        in_specs += (FaultModel(
            *([P(data_axis)] * len(FaultModel._fields))),)
    if has_async:
        in_specs += (AsyncModel(
            *([P(data_axis)] * len(AsyncModel._fields))),)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(data_axis), P(data_axis), P(data_axis)),
        check_vma=False,
    )
    return jax.jit(sharded)


@statics_contract(
    name="pushsum_sharded",
    # Per-device law of the edge-partitioned mode: nothing dense-N^2, and
    # no rank>=2 value over the GLOBAL padded edge axis may exist on a
    # device — per-shard (E_shard, d) state is the budget; gathering the
    # full (E_pad, d) rho back onto one device defeats the partitioning.
    # (The rank-1 (E_pad,) Bernoulli draw of shard_edge_mask is exempt by
    # construction: the anchored patterns below are all rank >= 2.)
    forbidden={"*": (("N", "N"), ("E", "*"))},
    streams=(("link", lambda t: t),),
    caches=("pushsum.sweep2d-jit",),
)
def _sweep_edge_sharded_body(w, src_sh, dst_sh, valid_sh, lo_sh, ends_sh,
                             drop_b, seed_b, fault_b=None, *,
                             T, B, backend, graph_axis, n_shards,
                             policy=None, halo="psum"):
    """Per-device scenario batch of the edge-partitioned (2-D mesh) sweep.

    Runs under ``shard_map`` over (``data_axis``, ``graph_axis``) — or under
    a ``jax.vmap(axis_name=graph_axis)`` emulation on one device — with
    ``w`` replicated, the edge arrays carrying this device's
    (Kb, 1, E_shard) slice of a :func:`graphs.partition_edge_list` layout,
    its run-end window (``lo_sh`` (Kb, 1), ``ends_sh`` (Kb, 1, W):
    :meth:`graphs.EdgeShards.run_end_windows`, read by the Pallas finish),
    and the scenario coordinates (Kb,) sharded over data only. Node state
    is replicated over the graph axis; each round's receiver partials (and
    the hoisted out-degree / final mass invariant) are combined with psum
    inside :func:`sparse_pushsum_step`, so every graph-shard device holds
    identical node state and the outputs are graph-replicated.

    A round runs under the phase scopes ``links`` (the mask draw and this
    shard's window of it), ``consensus`` (the edge step: the gather, the
    kernel, the finish, and the halo combine under the nested scope
    ``halo``) and ``readout`` (the round's error).
    """
    kb, e_shard = src_sh.shape[0], src_sh.shape[-1]
    # (Kb, 1, Es) under shard_map, (Kb, Es) under the vmap emulation
    src_sh = src_sh.reshape(kb, e_shard)
    dst_sh = dst_sh.reshape(kb, e_shard)
    valid_sh = valid_sh.reshape(kb, e_shard)
    lo_sh = lo_sh.reshape(kb)
    ends_sh = ends_sh.reshape(kb, ends_sh.shape[-1])
    target = w.mean(axis=0)
    w_sum = w.sum(axis=0)
    n = w.shape[0]

    def single(src, dst, valid, lo, ends, drop, seed, fault=None):
        key = jax.random.PRNGKey(seed)
        state0 = init_sparse_state(w, e_shard, policy=policy)
        # loop invariant: global out-degree = psum of shard-local counts
        d_out = jax.lax.psum(
            _out_degree(src, valid, n, w.dtype), graph_axis
        )
        share = 1.0 / (d_out + 1.0)

        def step(state, mask, fs=None):
            with jax.named_scope("consensus"):
                new = sparse_pushsum_step(
                    state, mask, src, dst, valid, backend,
                    share=share, graph_axis=graph_axis, dst_sorted=True,
                    policy=policy, halo=halo, n_shards=n_shards,
                    faults=fs, seg_end=ends, seg_lo=lo,
                )
            with jax.named_scope("readout"):
                err = jnp.abs(sparse_ratios(new) - target).max()
            return new, err

        if fault is None:
            def body(state, t):
                with jax.named_scope("links"):
                    mask = shard_edge_mask(
                        key, t, e_shard, drop, B,
                        graph_axis=graph_axis, n_shards=n_shards,
                    )
                return step(state, mask)

            final, errs = jax.lax.scan(
                body, state0, jnp.arange(T, dtype=jnp.uint32)
            )
        else:
            def body(carry, t):
                # fault + drop draws window the full-graph vector exactly
                # like shard_edge_mask, so realizations are identical at
                # every shard count
                state, fs = carry
                with jax.named_scope("links"):
                    fs = step_faults(key, t, fault, fs,
                                     engine=ENGINE_PUSHSUM,
                                     graph_axis=graph_axis,
                                     n_shards=n_shards)
                    u = edge_uniforms(key, t, e_shard,
                                      graph_axis=graph_axis,
                                      n_shards=n_shards)
                    mask = faulty_edge_mask(u, t, fault, fs, src, dst,
                                            drop, B)
                new, err = step(state, mask, fs)
                return (new, fs), err

            (final, _), errs = jax.lax.scan(
                body, (state0, init_fault_state(n, e_shard)),
                jnp.arange(T, dtype=jnp.uint32)
            )
        gap = sparse_mass_invariant(
            final, src, valid, graph_axis=graph_axis
        ) - w_sum
        return errs, sparse_ratios(final), gap

    args = (src_sh, dst_sh, valid_sh, lo_sh, ends_sh, drop_b, seed_b)
    if fault_b is not None:
        args += (fault_b,)
    return jax.vmap(single)(*args)


def _sweep2d_emulated(w, src_k, dst_k, valid_k, lo_k, ends_k, drop_b,
                      seed_b, fault_b=None, *,
                      T, B, backend, graph_axis, n_shards,
                      policy=None, halo="psum"):
    """Single-device oracle of the 2-D mesh program: ``vmap(axis_name=)``
    over the shard axis of the same per-device body, so every collective
    resolves identically. The psum of S operands lowers to the same
    reduction either way, making this path the bit-identity reference the
    mesh path is tested against (and the traceable the statics fixture
    lints). Outputs are shard-replicated; the leading S axis is dropped."""
    errs, finals, gaps = jax.vmap(
        functools.partial(
            _sweep_edge_sharded_body,
            T=T, B=B, backend=backend,
            graph_axis=graph_axis, n_shards=n_shards,
            policy=policy, halo=halo,
        ),
        in_axes=(None, 1, 1, 1, 1, 1, None, None, None),
        out_axes=0,
        axis_name=graph_axis,
    )(w, src_k, dst_k, valid_k, lo_k, ends_k, drop_b, seed_b, fault_b)
    return errs[0], finals[0], gaps[0]


_sweep2d_compiled = functools.partial(
    jax.jit,
    static_argnames=("T", "B", "backend", "graph_axis", "n_shards",
                     "policy", "halo"),
)(_sweep2d_emulated)


@functools.lru_cache(maxsize=None)
def _sweep_sharded_2d(mesh: Mesh, data_axis: str, graph_axis: str,
                      T: int, B: int, backend: str,
                      policy: Policy | None = None, halo: str = "psum",
                      has_faults: bool = False):
    """Jitted 2-D (data x graph) shard_map sweep: scenarios split over
    ``data_axis`` exactly as in :func:`_sweep_sharded`, while the edge
    arrays' shard axis splits over ``graph_axis`` — one edge shard per
    graph-device, combined per round by the psum inside the body. Outputs
    are graph-replicated, so their specs name only the data axis."""
    from repro.distributed.sharding import sweep_specs

    specs = sweep_specs(data_axis, graph_axis)
    n_shards = int(mesh.shape[graph_axis])
    body = functools.partial(
        _sweep_edge_sharded_body, T=T, B=B, backend=backend,
        graph_axis=graph_axis, n_shards=n_shards,
        policy=policy, halo=halo,
    )
    in_specs = (specs["replicated"],) + (specs["edge_shards"],) * 5 + (
        specs["scenario"], specs["scenario"])
    if has_faults:
        in_specs += (FaultModel(
            *([specs["scenario"]] * len(FaultModel._fields))),)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(specs["out"], specs["out"], specs["out"]),
        axis_names=frozenset({data_axis, graph_axis}),
        check_vma=False,
    )
    return jax.jit(sharded)


def run_pushsum_sweep(
    w: jnp.ndarray,            # (N, d) initial values, shared by scenarios
    el: EdgeList,              # single graph or stacked draws (leading G axis)
    T: int,
    *,
    drop_probs: Sequence[float] | float = 0.0,
    seeds: Sequence[int] | int = 0,
    B: int = 4,
    plan: ExecutionPlan | None = None,
    **legacy,
) -> PushSumSweepResult:
    """Run the full scenario grid in ONE jitted, vmapped scan.

    Scenario axes: every topology draw in ``el`` (see
    :func:`graphs.stack_edge_lists`) x every drop probability x every seed —
    K = G * |drop_probs| * |seeds| scenarios total. Per-round (E,) link
    masks are drawn inside the scan; nothing of size (T, N, N) or (N, N)
    ever exists. Compilation is cached at module level: repeated sweeps
    with the same array shapes and statics reuse the executable.

    ``backend`` selects the per-round delivery lowering
    (:mod:`repro.kernels.pushsum_edge`; ``"pallas"`` expects dst-sorted
    edges). With ``mesh`` given, the K scenario axis is sharded over
    ``mesh``'s ``data_axis`` via ``shard_map`` — K is padded by repeating
    the last scenario up to a multiple of the axis size (one scenario batch
    per device; the pad rows are sliced off the result), so grids in the
    thousands still run as a single program.

    **Edge-partitioned mode** (``graph_shards=S``): the graph itself is
    additionally split into S dst-contiguous edge shards
    (:func:`graphs.partition_edge_list` — ``el`` may be an
    :class:`graphs.EdgeShards` already), per-edge state drops to
    O(E/S d) per device, and per-round receiver partials are psum'd over
    the mesh ``graph_axis`` — the 2-D (scenarios x graph) program that
    takes single scenarios past N ~ 1e5. With ``mesh`` given its
    ``graph_axis`` extent must equal S; without a mesh the shard axis runs
    as a single-device ``vmap(axis_name=)`` emulation — the bit-identity
    oracle of the mesh path. Either way each shard's Pallas step finishes
    with a gather at its receivers' run ends
    (:meth:`graphs.EdgeShards.run_end_windows`), equal to the scatter
    finish to the bit. Results agree with the plain path on
    ``EdgeShards.padded_edge_list()`` to float32 rounding, not to the bit:
    XLA rewrites the plain program's ``z * share + segment_sum(...)`` into
    a scatter-add onto ``z * share``, so each receiver's sum starts from
    its kept share there, while in the sharded program the halo ``psum``
    between the sum and the add rules that rewrite out (any receiver, not
    only those split by a cut, can differ in its last bits). When
    ``S * e_shard`` exceeds E the padded mask draw re-indexes edge slots,
    so compare against the padded list, not the original (threefry bits
    have no prefix property). Passing an :class:`graphs.EdgeShards` built
    once keeps the graph on the devices across calls: the edge arrays are
    uploaded in the first call and reused while the instance lives (for
    the same mesh and scenario graphs); an :class:`graphs.EdgeList` is
    partitioned and uploaded anew in every call.

    ``policy`` selects the precision policy
    (:mod:`repro.core.precision`; name, :class:`Policy`, or ``None`` for
    the dtype-transparent fp32 default — bit-identical to the pre-policy
    sweeps). ``dst_sorted`` asserts the edge lists are dst-sorted so the
    delivery segment-sums skip the scatter sort (the edge-partitioned
    mode always sorts per shard and ignores this flag). ``halo`` picks
    the graph-axis combine of the edge-partitioned mode:
    ``"psum"`` (default, bit-identical to the single-device oracle) or
    ``"scatter"``, the psum_scatter/all_gather form whose gather leg
    moves storage-width bytes (see
    :func:`repro.analysis.roofline.pushsum_halo_wire_bytes`).

    ``faults`` (one :class:`repro.core.faults.FaultModel` or a sequence,
    e.g. a burst-length ladder from
    :func:`repro.core.faults.gilbert_elliott_model`) adds a FOURTH swept
    scenario axis, fault-minor: every (graph, drop, seed) cell runs once
    per model, severity traced per scenario — one executable for the
    whole fault grid. The result's ``fault`` field indexes into the
    sequence; ``faults=None`` (default) keeps the pre-fault program
    bit-identical and ``fault=None`` in the result.

    ``plan.async_`` (one :class:`repro.core.asyncrony.AsyncModel` or a
    sequence) crosses a FIFTH axis, async minor-most: every cell runs
    once per (wake-rate, staleness) model through the event-driven mode,
    indexed by the result's ``async_`` column. A single concretely
    degenerate model dispatches to the synchronous program (no axis,
    ``async_=None`` in the result — bit-identity by construction).
    Incompatible with the edge-partitioned mode (``graph_shards``). All
    execution knobs arrive via ``plan=`` (loose kwargs are deprecated
    shims; see :mod:`repro.core.plan`).
    """
    # host spans: the argument preparation (the partition, the scenario
    # grid, the upload of the edge arrays where they are not placed yet),
    # then the dispatch of the compiled scan (which returns before the
    # device finishes)
    with TraceAnnotation("repro.pushsum.prepare"):
        plan = resolve_plan(
            plan, _entry="run_pushsum_sweep",
            _supports=("backend", "mesh", "data_axis", "graph_axis",
                       "graph_shards", "policy", "dst_sorted", "halo",
                       "faults", "async_"),
            **legacy)
        async_ = plan.async_
        if isinstance(async_, AsyncModel) and is_degenerate_async(async_):
            async_ = None
        w = jnp.asarray(w)
        pol = None if plan.policy is None else resolve_policy(plan.policy)
        if plan.graph_shards is not None or isinstance(el, EdgeShards):
            if async_ is not None:
                raise ValueError(
                    "async_ is incompatible with the edge-partitioned mode "
                    "(graph_shards): the per-edge stale buffer is not "
                    "partitioned"
                )
            fn, args, coords = _prepare_edge_sharded(
                w, el, T, drop_probs, seeds, B, plan, pol)
        else:
            fn, args, coords = _prepare_plain(
                w, el, T, drop_probs, seeds, B, plan, pol, async_)
    with TraceAnnotation("repro.pushsum.dispatch"):
        errs, finals, gaps = fn(*args)
    K, gi, drop_b, seed_b, fi, ai = coords
    return PushSumSweepResult(
        err=errs[:K], final_ratio=finals[:K], mass_gap=gaps[:K],
        drop_prob=drop_b[:K], seed=seed_b[:K], graph=jnp.asarray(gi[:K]),
        fault=None if fi is None else jnp.asarray(fi[:K]),
        async_=None if ai is None else jnp.asarray(ai[:K]),
    )


def _pad_scenarios(mesh, data_axis, coords):
    """Repeat the last scenario until the mesh's data axis divides K;
    ``coords`` are (K,) arrays or None."""
    K = coords[0].shape[0]
    pad = 0 if mesh is None else (-K) % int(mesh.shape[data_axis])
    if not pad:
        return coords
    fill = np.full(pad, K - 1)
    return tuple(None if c is None else np.concatenate([c, c[fill]])
                 for c in coords)


def _prepare_plain(w, el, T, drop_probs, seeds, B, plan, pol, async_):
    """The one-device (or scenario-sharded) program of
    :func:`run_pushsum_sweep`: ``(compiled, its arguments, (K, graph,
    drop, seed, fault, async) coordinates)``."""
    src = np.atleast_2d(el.src)      # (G, E)
    dst = np.atleast_2d(el.dst)
    valid = np.atleast_2d(el.valid)
    G, E = src.shape
    gi, dp, sd = _scenario_grid(G, drop_probs, seeds)
    (gi, dp, sd), fi, fstack = _expand_fault_axis((gi, dp, sd), plan.faults)
    if fi is None:
        (gi, dp, sd), ai, astack = _expand_async_axis((gi, dp, sd), async_)
    else:
        (gi, dp, sd, fi), ai, astack = _expand_async_axis(
            (gi, dp, sd, fi), async_)
    K = gi.shape[0]
    mesh = plan.mesh
    gi, dp, sd, fi, ai = _pad_scenarios(mesh, plan.data_axis,
                                        (gi, dp, sd, fi, ai))
    drop_b = jnp.asarray(dp)
    seed_b = jnp.asarray(sd)
    args = (w, jnp.asarray(src[gi]), jnp.asarray(dst[gi]),
            jnp.asarray(valid[gi]), drop_b, seed_b)
    if fi is not None or ai is not None:
        args += (None if fi is None else jax.tree_util.tree_map(
            lambda x: x[jnp.asarray(fi)], fstack),)
    if ai is not None:
        args += (jax.tree_util.tree_map(
            lambda x: x[jnp.asarray(ai)], astack),)
    if mesh is None:
        fn = functools.partial(
            _sweep_compiled, T=T, B=B, backend=plan.backend, policy=pol,
            dst_sorted=plan.dst_sorted)
    else:
        if fi is None and ai is not None:
            args = args[:6] + args[7:]   # drop the None fault_b placeholder
        fn = _sweep_sharded(mesh, plan.data_axis, T, B, plan.backend, pol,
                            plan.dst_sorted, fi is not None, ai is not None)
    return fn, args, (K, gi, drop_b, seed_b, fi, ai)


# The device placement of EdgeShards graphs (_place_edge_shards): its hit
# and miss counts, and the live instances that hold a placement, by id and
# held weakly, so that a graph dropped frees its device arrays.
_EDGE_PLACEMENT = {"hits": 0, "misses": 0}
_PLACED: dict[int, weakref.ref] = {}


def _place_edge_shards(shards: EdgeShards, gi: np.ndarray, layout):
    """The edge arguments ``(src, dst, valid, lo, ends)`` of the scenarios'
    graphs ``gi``, on the devices of ``layout`` (``None``: the default
    device), each (K, S, ...).

    The placement is kept on ``shards`` itself and reused while the
    instance lives, for the same ``layout`` and ``gi`` (an instance does not
    change, see :class:`graphs.EdgeShards`); one an instance, the latest,
    so another ``layout`` or K frees the one before. The compiled programs
    donate none of these buffers."""
    key = (layout, gi.tobytes())
    placed = shards._memo.get("placed")
    if placed is not None and placed[0] == key:
        _EDGE_PLACEMENT["hits"] += 1
        return placed[1]
    _EDGE_PLACEMENT["misses"] += 1
    shards._memo.pop("placed", None)
    lo, ends = shards.run_end_windows()
    arrays = (shards.src, shards.dst, shards.valid, lo, ends)
    if not shards.is_batched:
        arrays = tuple(a[None] for a in arrays)             # (G, S, ...)
    put = (jnp.asarray if layout is None
           else functools.partial(jax.device_put, device=layout))
    out = tuple(put(a[gi]) for a in arrays)
    shards._memo["placed"] = (key, out)
    k = id(shards)
    if k not in _PLACED:
        _PLACED[k] = weakref.ref(shards, lambda _, k=k: _PLACED.pop(k, None))
    return out


def _clear_edge_placements() -> None:
    """Drop every live placement, and the counts."""
    for ref in list(_PLACED.values()):
        shards = ref()
        if shards is not None:
            shards._memo.pop("placed", None)
    _PLACED.clear()
    _EDGE_PLACEMENT.update(hits=0, misses=0)


def edge_placement_info() -> dict[str, int]:
    """``hits`` and ``misses`` of the device placement kept on an
    :class:`graphs.EdgeShards` across :func:`run_pushsum_sweep` calls, and
    ``live``, the instances that hold one now."""
    return dict(_EDGE_PLACEMENT, live=len(_PLACED))


def _prepare_edge_sharded(w, el, T, drop_probs, seeds, B, plan, pol):
    """The edge-partitioned program of :func:`run_pushsum_sweep`, as
    :func:`_prepare_plain`. With a mesh the edge arrays go from the host
    straight to the devices that hold them, once for an
    :class:`graphs.EdgeShards` passed in (:func:`_place_edge_shards`)."""
    graph_shards, graph_axis, mesh = (plan.graph_shards, plan.graph_axis,
                                      plan.mesh)
    shards = (el if isinstance(el, EdgeShards)
              else partition_edge_list(el, graph_shards))
    if graph_shards is not None and shards.n_shards != graph_shards:
        raise ValueError(
            f"EdgeShards has {shards.n_shards} shards, "
            f"graph_shards={graph_shards}"
        )
    S = shards.n_shards
    G = shards.src.shape[0] if shards.is_batched else 1
    gi, dp, sd = _scenario_grid(G, drop_probs, seeds)
    (gi, dp, sd), fi, fstack = _expand_fault_axis((gi, dp, sd), plan.faults)
    K = gi.shape[0]
    layout = None
    if mesh is not None:
        if int(mesh.shape[graph_axis]) != S:
            raise ValueError(
                f"mesh {graph_axis} axis has {mesh.shape[graph_axis]} "
                f"devices but the edge list is cut into {S} shards"
            )
        from repro.distributed.sharding import sweep_specs

        layout = NamedSharding(
            mesh, sweep_specs(plan.data_axis, graph_axis)["edge_shards"])
    gi, dp, sd, fi = _pad_scenarios(mesh, plan.data_axis, (gi, dp, sd, fi))
    drop_b = jnp.asarray(dp)
    seed_b = jnp.asarray(sd)
    args = (w, *_place_edge_shards(shards, gi, layout), drop_b, seed_b)
    if fi is not None:
        args += (jax.tree_util.tree_map(
            lambda x: x[jnp.asarray(fi)], fstack),)
    if mesh is None:
        fn = functools.partial(
            _sweep2d_compiled, T=T, B=B, backend=plan.backend,
            graph_axis=graph_axis, n_shards=S, policy=pol, halo=plan.halo)
    else:
        fn = _sweep_sharded_2d(mesh, plan.data_axis, graph_axis, T, B,
                               plan.backend, pol, plan.halo, fi is not None)
    return fn, args, (K, gi, drop_b, seed_b, fi, None)


# Compiled Algorithm-2 sweeps, one jitted vmapped scan per
# (model, topology, F, byz set, Gamma, attack, T, mode/core/backend/store)
# combo. The scan closure returned by make_byzantine_scan is a fresh Python
# object per call, so wrapping it in jax.jit anew would retrace every time
# even though the traced program is identical; keying the *jitted callable*
# on the config fingerprint gives run_byzantine_sweep the same retrace-free
# repeated-call behaviour as _sweep_compiled. The cache is LRU-bounded so
# parameter studies cycling through many fingerprints do not accumulate
# executables without limit.
_BYZ_COMPILED = _LRUCache(maxsize=32)
_BYZ_GRID_COMPILED = _LRUCache(maxsize=8)


def _fault_fingerprint(faults: FaultModel | None):
    """Value fingerprint of a FaultModel for compiled-program cache keys.

    The fault scalars are baked into the closure the byzantine caches jit
    (unlike the grid engines, which trace a batched FaultModel argument),
    so the key must name the VALUES — a has-faults flag alone would
    silently reuse an executable compiled for different severities."""
    if faults is None:
        return None
    return tuple(float(np.asarray(x)) for x in faults)


def _byz_sweep_key(
    model: SignalModel, cfg: ByzantineConfig, T: int,
    mode: str = "pairwise", core: str = "sparse", backend: str = "auto",
    store: str = "trajectory", policy: Policy | None = None,
    faults: FaultModel | None = None,
) -> tuple:
    topo = cfg.topo
    return (
        np.asarray(model.tables).tobytes(), model.truth,
        topo.adj.tobytes(), topo.sizes, topo.offsets, topo.reps,
        cfg.F, cfg.byz, cfg.gamma_period, cfg.attack, T,
        mode, core, backend, store, policy, _fault_fingerprint(faults),
    )


def run_byzantine_sweep(
    model: SignalModel,
    cfg: ByzantineConfig,
    T: int,
    seeds: Sequence[int],
    attacks: Sequence[Attack] | None = None,
    *,
    mode: str = "pairwise",
    core: str = "sparse",
    plan: ExecutionPlan | None = None,
    **legacy,
) -> dict[str, ByzantineResult]:
    """Algorithm 2 over a seed batch per attack type.

    For each attack (default: just ``cfg.attack``) the whole seed batch runs
    as one jitted ``vmap`` of the scan built by
    :func:`byzantine.make_byzantine_scan` — results carry a leading seed
    axis: with ``store="trajectory"`` ``r`` is (S, T, N, m, m) and
    ``decisions`` (S, T, N). Seed batches over long horizons should pass
    ``store="decisions"`` (decision curves reduced in-scan, final r only) or
    ``store="final"`` so the batch never carries the (S, T, N, m, m)
    trajectory out of the scan. Attack types swap the traced message
    function, so they iterate host-side. ``core``/``backend`` select the
    gossip lowering (:func:`make_byzantine_scan`).

    Repeated calls with the same (model, config, T, attack) and seed-batch
    shape neither retrace nor re-run the host-side healthy-network
    analysis: the C-set lattice is memoized in :mod:`repro.core.byzantine`
    and the jitted scan is reused from ``_BYZ_COMPILED`` (``Attack`` is a
    frozen dataclass, so the same attack object keys the same entry).

    ``plan.faults`` layers one :class:`repro.core.faults.FaultModel` over
    every seed in the batch (the unified fault plane of
    :func:`byzantine.make_byzantine_scan`); the compiled cache keys on the
    fault VALUES, so sweeping severities host-side stays correct.
    Execution knobs arrive via ``plan=`` (loose
    ``backend=``/``store=``/``policy=``/``faults=`` kwargs are deprecated
    shims); ``mode``/``core`` are algorithm variants, not execution knobs,
    so they stay named. The Byzantine engine does NOT support the async
    mode — its adversarial-message semantics assume synchronized rounds —
    so a plan carrying ``async_`` raises ``ValueError``.
    """
    plan = resolve_plan(
        plan, _entry="run_byzantine_sweep",
        _supports=("backend", "store", "policy", "faults"),
        **legacy)
    backend, policy, faults = plan.backend, plan.policy, plan.faults
    store = "trajectory" if plan.store is None else plan.store
    pol = None if policy is None else resolve_policy(policy)
    seeds_j = jnp.asarray(np.asarray(seeds, np.uint32))
    keys = jax.vmap(jax.random.PRNGKey)(seeds_j)
    out: dict[str, ByzantineResult] = {}
    for atk in attacks if attacks is not None else [cfg.attack]:
        c = dataclasses.replace(cfg, attack=atk)
        cache_key = _byz_sweep_key(model, c, T, mode, core, backend, store,
                                   pol, faults)
        fn = _BYZ_COMPILED.get(cache_key)
        if fn is None:
            run = make_byzantine_scan(
                model, c, T, mode=mode, core=core, backend=backend,
                store=store, policy=pol, faults=faults,
            )
            fn = _BYZ_COMPILED[cache_key] = jax.jit(jax.vmap(run))
        out[atk.name] = fn(keys)
    return out


class ByzantineGridResult(NamedTuple):
    """One row per scenario (config x seed), leading axis K.

    ``r``/``decisions`` follow the ``store`` shapes of
    :class:`repro.core.byzantine.ByzantineResult` with the extra leading K;
    ``cfg`` indexes into the ``cfgs`` list passed to
    :func:`run_byzantine_grid`, ``F``/``seed`` are the per-scenario
    coordinates. ``fault``/``async_`` follow the unified index-column
    convention above: the grid applies ONE fault model to every scenario
    (so ``fault`` is the all-zeros index when faults are on, ``None``
    otherwise — pre-PR-10 this result had no fault field at all), and the
    Byzantine engine has no async mode, so ``async_`` is always ``None``.
    """

    r: jnp.ndarray
    decisions: jnp.ndarray
    cfg: jnp.ndarray       # (K,) config index
    F: jnp.ndarray         # (K,) trim count of that config
    seed: jnp.ndarray      # (K,)
    fault: jnp.ndarray | None = None  # (K,) fault index, None = no faults
    async_: jnp.ndarray | None = None  # always None (no async mode)

    @property
    def K(self) -> int:
        return int(self.decisions.shape[0])

    def describe(self) -> str:
        return _describe_result(self)


def _cfgs_fingerprint(model, cfgs, atk) -> tuple:
    parts = [np.asarray(model.tables).tobytes(), model.truth, atk]
    for c in cfgs:
        topo = c.topo
        parts.append((
            topo.adj.tobytes(), topo.sizes, topo.offsets, topo.reps,
            c.F, c.byz, c.gamma_period,
        ))
    return tuple(parts)


def _byz_grid_key(model, cfgs, T, atk, mode, backend, store,
                  mesh, data_axis, policy=None, faults=None) -> tuple:
    """``backend`` must be the *effective* lowering (post ``resolve_backend``
    and the dynamic-F downgrade), so the key names the traced program."""
    return _cfgs_fingerprint(model, cfgs, atk) + (
        T, mode, backend, store, mesh, data_axis, policy,
        _fault_fingerprint(faults),
    )


# Stacked ByzRuntime batches keyed on the (model, configs, attack)
# fingerprint: repeated grid calls (e.g. host-side attack/T loops over one
# config set) skip the per-config analysis, neighbor-list construction, and
# device uploads entirely.
_BYZ_RUNTIME_CACHE = _LRUCache(maxsize=16)


def run_byzantine_grid(
    model: SignalModel,
    cfgs: Sequence[ByzantineConfig],
    T: int,
    seeds: Sequence[int] | int,
    *,
    attack: Attack | None = None,
    mode: str = "pairwise",
    plan: ExecutionPlan | None = None,
    **legacy,
) -> ByzantineGridResult:
    """Batched (topology, F) x seed grid as ONE compiled vmapped scan.

    Every config's host analysis runs once; the per-config runtime arrays
    (neighbor lists padded to the common deg_max, byz/active masks, F,
    gamma) stack leaf-wise onto a scenario axis and the K = |cfgs| x |seeds|
    grid executes in lockstep under a single ``jax.vmap``. Configs must be
    *compatible*: same N, same network count M (so one trace serves all),
    and M >= 2F+1 (the all-networks representative rule — the M < 2F+1
    branch needs per-config static index sets). Heterogeneous F values ride
    the scenario axis as traced scalars, which forces the sort-based XLA
    trim; a uniform F keeps the static-F Pallas path available.

    ``attack`` overrides every config's attack (one traced program per grid
    call — loop attacks host-side as in :func:`run_byzantine_sweep`). With
    ``mesh``, the scenario axis is sharded over ``data_axis`` via
    ``shard_map`` exactly like :func:`run_pushsum_sweep` (K padded up to a
    multiple of the axis size by repeating the last scenario).

    The jitted grid program is cached in ``_BYZ_GRID_COMPILED`` keyed on the
    full config-list fingerprint, so repeated studies neither retrace nor
    re-run the reduced-graph analysis.

    ``plan.faults`` applies one :class:`repro.core.faults.FaultModel` to
    every scenario (the cache keys on its values, so host-side severity
    loops stay correct); per-scenario fault axes belong in the
    social/HPS/push-sum grids, whose fault models ride the vmap axis.
    Execution knobs arrive via ``plan=`` (loose kwargs are deprecated
    shims; ``plan.store=None`` means ``"decisions"``); a plan carrying
    ``async_`` raises — the Byzantine engine has no async mode.
    """
    from repro.kernels.byz_trim import resolve_backend

    # host spans: ``prepare`` (with ``fingerprint``, ``build`` on a cache
    # miss only, and ``batch`` inside it), then ``dispatch`` of the
    # compiled grid and ``unpack`` of its result
    with TraceAnnotation("repro.byz_grid.prepare"):
        plan = resolve_plan(
            plan, _entry="run_byzantine_grid",
            _supports=("backend", "store", "mesh", "data_axis", "policy",
                       "faults"),
            **legacy)
        backend, mesh, data_axis = plan.backend, plan.mesh, plan.data_axis
        policy, faults = plan.policy, plan.faults
        store = "decisions" if plan.store is None else plan.store

        cfgs = list(cfgs)
        if not cfgs:
            raise ValueError("need at least one config")
        atk = attack if attack is not None else cfgs[0].attack
        N, M = cfgs[0].topo.N, cfgs[0].topo.M
        if any(c.topo.N != N or c.topo.M != M for c in cfgs) \
                or model.N != N:
            raise ValueError("grid configs (and the model) must share (N, M)")

        with TraceAnnotation("repro.byz_grid.fingerprint"):
            rt_key = _cfgs_fingerprint(model, cfgs, atk)
        hit = _BYZ_RUNTIME_CACHE.get(rt_key)
        if hit is None:
            with TraceAnnotation("repro.byz_grid.build"):
                hit = _BYZ_RUNTIME_CACHE[rt_key] = _stack_byz_runtimes(
                    model, cfgs, atk)
        stacked = hit
        Fs = np.asarray([c.F for c in cfgs], np.int32)
        # a uniform F stays a static Python int (Pallas-trim eligible);
        # heterogeneous F is traced per scenario, which needs the sort
        # lowering. backend is normalized to the effective lowering so the
        # compiled-cache key names the traced program on every platform.
        static_F = int(Fs[0]) if bool((Fs == Fs[0]).all()) else None
        backend = resolve_backend(backend)
        if static_F is None and backend == "pallas":
            backend = "xla"

        seeds_np = np.atleast_1d(np.asarray(seeds, np.uint32))
        gi, sd = np.meshgrid(
            np.arange(len(cfgs), dtype=np.int32), seeds_np, indexing="ij"
        )
        gi, sd = gi.ravel(), sd.ravel()
        K = gi.shape[0]
        if mesh is not None:
            pad = (-K) % int(mesh.shape[data_axis])
            if pad:
                fill = np.full(pad, K - 1)
                gi = np.concatenate([gi, gi[fill]])
                sd = np.concatenate([sd, sd[fill]])

        pol = None if policy is None else resolve_policy(policy)
        with TraceAnnotation("repro.byz_grid.fingerprint"):
            cache_key = _byz_grid_key(model, cfgs, T, atk, mode, backend,
                                      store, mesh, data_axis, pol, faults)
        fn = _BYZ_GRID_COMPILED.get(cache_key)
        if fn is None:
            with TraceAnnotation("repro.byz_grid.build"):
                single = functools.partial(
                    _scan_core,
                    faults=faults,
                    gossip=functools.partial(
                        _sparse_gossip, attack=atk, mode=mode,
                        backend=backend,
                        accum_dtype=None if pol is None else pol.accum,
                    ),
                    log_tables=model.log_tables().astype(jnp.float32),
                    truth_probs=model.tables[:, model.truth, :].astype(
                        jnp.float32),
                    T=T,
                    mode=mode,
                    attack=atk,
                    store=store,
                    static_F=static_F,
                    extra_reps=None,
                    n_reps=M,
                    policy=pol,
                )
                batched = jax.vmap(single)
                if mesh is not None:
                    spec = P(data_axis)
                    batched = jax.shard_map(
                        batched,
                        mesh=mesh,
                        in_specs=(spec, ByzRuntime(
                            *([spec] * len(ByzRuntime._fields)))),
                        out_specs=ByzantineResult(r=spec, decisions=spec),
                        check_vma=False,
                    )
                fn = _BYZ_GRID_COMPILED[cache_key] = jax.jit(batched)

        with TraceAnnotation("repro.byz_grid.batch"):
            keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(sd))
            rt_batch = jax.tree_util.tree_map(lambda x: x[jnp.asarray(gi)],
                                              stacked)
    with TraceAnnotation("repro.byz_grid.dispatch"):
        res = fn(keys, rt_batch)
    with TraceAnnotation("repro.byz_grid.unpack"):
        return ByzantineGridResult(
            r=res.r[:K], decisions=res.decisions[:K],
            cfg=jnp.asarray(gi[:K]), F=jnp.asarray(Fs[gi[:K]]),
            seed=jnp.asarray(sd[:K]),
            fault=None if faults is None else jnp.zeros(K, jnp.int32),
        )


def _stack_byz_runtimes(model, cfgs, atk) -> ByzRuntime:
    """Every config's :class:`ByzRuntime`, neighbour lists padded to the
    common deg_max, stacked leaf-wise onto a scenario axis."""
    runtimes = []
    for c in cfgs:
        rt, extra_reps, _, _ = make_byzantine_runtime(
            model, dataclasses.replace(c, attack=atk)
        )
        if extra_reps is not None:
            raise ValueError(
                "grid configs must satisfy M >= 2F+1 (the all-networks "
                f"representative rule); config with F={c.F}, "
                f"M={c.topo.M} needs the static extra-reps branch"
            )
        runtimes.append(rt)
    deg_max = max(int(rt.nbr_idx.shape[1]) for rt in runtimes)

    def pad_rt(rt: ByzRuntime) -> ByzRuntime:
        pad = deg_max - rt.nbr_idx.shape[1]
        return rt._replace(
            nbr_idx=jnp.pad(rt.nbr_idx, ((0, 0), (0, pad))),
            nbr_valid=jnp.pad(rt.nbr_valid, ((0, 0), (0, pad))),
        )

    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[pad_rt(rt) for rt in runtimes]
    )


# ---------------------------------------------------------------------------
# Algorithm 3: batched (topology x drop_prob x Gamma) x seed social sweeps
# ---------------------------------------------------------------------------

class SocialSweepResult(NamedTuple):
    """One row per scenario (config x seed), leading axis K.

    ``beliefs``/``log_ratio`` follow the ``store`` shapes of
    :class:`repro.core.social.SocialLearningResult` with the extra leading
    K — ``store="log_ratio"`` (the sweep default) gives the (K, T) worst
    log-ratio curves of Theorem 2 plus final (K, N, m) beliefs, which is
    the phase-diagram payload. ``cfg`` indexes into the expanded config
    list; ``drop_prob``/``gamma``/``seed`` are the per-scenario
    coordinates.
    """

    beliefs: jnp.ndarray
    log_ratio: jnp.ndarray
    drop_prob: jnp.ndarray  # (K,)
    gamma: jnp.ndarray      # (K,)
    seed: jnp.ndarray       # (K,)
    cfg: jnp.ndarray        # (K,) config index
    fault: jnp.ndarray | None = None  # (K,) fault-model index, None = no axis
    async_: jnp.ndarray | None = None  # (K,) async-model index, minor-most

    @property
    def K(self) -> int:
        return int(self.seed.shape[0])

    def describe(self) -> str:
        return _describe_result(self)


# Jitted social-sweep programs keyed on (mesh, data_axis, statics). The
# per-scenario data is ALL arrays (SocialRuntime leaves + PRNG keys), so one
# cached executable serves every model/topology of the same shapes — the
# jit wrapper's own cache handles shape changes; the LRU bound keeps long
# parameter studies from pinning retired shard_map wrappers.
_SOCIAL_COMPILED = _LRUCache(maxsize=16)

# Stacked SocialRuntime batches keyed on the (configs,) fingerprint:
# repeated sweep calls (e.g. host-side seed batches over one grid) skip the
# per-config edge-list construction and device uploads entirely.
_SOCIAL_RUNTIME_CACHE = _LRUCache(maxsize=16)


def _social_sweep_fn(mesh, data_axis, *, truth, M, T, store, backend,
                     policy=None, has_faults=False, has_async=False):
    key = (mesh, data_axis, truth, M, T, store, backend, policy, has_faults,
           has_async)
    fn = _SOCIAL_COMPILED.get(key)
    if fn is not None:
        return fn

    def base(keys, rt_batch, log_tables, cdf, fault_b=None, async_b=None):
        def single(k, rt, fault=None, am=None):
            # grid runtimes come from make_social_runtime: dst-sorted
            # edge index, e_max pad rows at dst = N - 1 keep it sorted
            _, outs = _social_scan_core(
                k, k, rt, log_tables, cdf,
                truth=truth, M=M, T=T, store=store, backend=backend,
                policy=policy, dst_sorted=True, faults=fault, async_=am,
            )
            return outs

        if fault_b is None and async_b is None:
            return jax.vmap(single, in_axes=(0, 0))(keys, rt_batch)
        if async_b is None:
            return jax.vmap(single, in_axes=(0, 0, 0))(
                keys, rt_batch, fault_b)
        if fault_b is None:
            return jax.vmap(
                lambda k, rt, am: single(k, rt, None, am),
                in_axes=(0, 0, 0),
            )(keys, rt_batch, async_b)
        return jax.vmap(single, in_axes=(0, 0, 0, 0))(
            keys, rt_batch, fault_b, async_b)

    if has_async and not has_faults:
        # shard_map passes positionally; skip the absent fault_b slot
        def body(keys, rt_batch, log_tables, cdf, async_b):
            return base(keys, rt_batch, log_tables, cdf, None, async_b)
    else:
        body = base

    if mesh is not None:
        spec = P(data_axis)
        in_specs = (
            spec,
            SocialRuntime(*([spec] * len(SocialRuntime._fields))),
            P(),
            P(),
        )
        if has_faults:
            in_specs += (FaultModel(
                *([spec] * len(FaultModel._fields))),)
        if has_async:
            in_specs += (AsyncModel(
                *([spec] * len(AsyncModel._fields))),)
        body = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(spec, spec),
            check_vma=False,
        )
    fn = _SOCIAL_COMPILED[key] = jax.jit(body)
    return fn


def _cfg_fingerprint(cfgs) -> tuple:
    """Runtime-cache key over HPSConfig-shaped config lists — everything
    the stacked runtime arrays are derived from (shared by the HPS and
    social grid engines; keep in sync with any cache-relevant field added
    to :class:`repro.core.hps.HPSConfig`)."""
    parts = []
    for c in cfgs:
        topo = c.topo
        parts.append((
            topo.adj.tobytes(), topo.sizes, topo.offsets, topo.reps,
            float(c.drop_prob), c.gamma_period, c.B,
        ))
    return tuple(parts)


def run_social_grid(
    model: SignalModel,
    cfgs: Sequence[HPSConfig],
    T: int,
    seeds: Sequence[int] | int,
    *,
    plan: ExecutionPlan | None = None,
    **legacy,
) -> SocialSweepResult:
    """Batched (topology, drop_prob, Gamma) x seed grid as ONE compiled
    vmapped scan of the fused Algorithm 3 engine.

    Every config's edge index builds once; the per-config runtime arrays
    (edge lists padded to the common E, representative masks, drop_prob /
    Gamma / B as traced scalars) stack leaf-wise onto a scenario axis and
    the K = |cfgs| x |seeds| grid executes in lockstep under a single
    ``jax.vmap``. Configs must be *compatible*: same N and same network
    count M (the fusion matrix divides by 2M, which stays static so one
    trace serves all). Each scenario's seed drives both PRNG streams (link
    masks and signals) through disjoint fold-in domains — a grid row is
    bit-identical to ``run_social_learning(..., seed=s, signal_seed=s)``
    whenever the config's edge count equals the grid's padded E (always
    true for single-topology drop x Gamma x seed sweeps). Mixed-E grids
    pad smaller edge lists up to the widest, which re-indexes the (E,)
    link-mask draw (jax's counter-based bits have no prefix property), so
    those rows are instead bit-identical to :func:`run_social_runtime` on
    the same ``e_max``-padded runtime.

    ``store`` defaults to ``"log_ratio"``: the (K, T) worst log-ratio
    curves are reduced inside the scan, so nothing of size (K, T, N, m)
    ever exists — pass ``store="trajectory"`` explicitly to materialize
    full belief histories. With ``mesh``, the scenario axis is sharded over
    ``data_axis`` via ``shard_map`` exactly like :func:`run_pushsum_sweep`
    (K padded up to a multiple of the axis size by repeating the last
    scenario; results bit-identical to the single-device vmap).

    The jitted program is cached in ``_SOCIAL_COMPILED`` keyed on
    (mesh, statics) only — the grid data is all arrays, so repeated studies
    over different models or topologies of the same shapes reuse one
    executable without retracing.

    ``faults`` (one :class:`repro.core.faults.FaultModel` or a sequence,
    e.g. a churn-rate ladder) crosses a fault-minor scenario axis into the
    grid — severity is traced per scenario, one executable for the whole
    fault grid; the result's ``fault`` field indexes into the sequence.
    ``faults=None`` keeps the pre-fault program bit-identical.

    This config-list API is anchored on dense-adjacency
    :class:`~repro.core.hps.HPSConfig` topologies (the fingerprint
    serializes ``topo.adj``), which targets moderate-N phase diagrams. For
    dense-free large-N grids, build :class:`~repro.core.social.SocialRuntime`
    batches from edge lists (:func:`graphs.block_complete_edge_list` +
    :func:`social.social_runtime_from_edge_list`, stacked leaf-wise) and
    ``jax.vmap`` :func:`repro.core.social._social_scan_core` directly — the
    scan core is the shared vmappable contract.

    ``plan.async_`` (one :class:`repro.core.asyncrony.AsyncModel` or a
    sequence, e.g. a (wake-rate x staleness) grid) crosses an async-minor
    scenario axis exactly like ``faults`` — the result's ``async_`` column
    indexes into the sequence, and a single concretely degenerate model
    dispatches to the synchronous program (no axis). All execution knobs
    arrive via ``plan=`` (loose kwargs are deprecated shims;
    ``plan.store=None`` means ``"log_ratio"``).
    """
    from repro.kernels.social_innov import resolve_backend

    plan = resolve_plan(
        plan, _entry="run_social_grid",
        _supports=("backend", "store", "mesh", "data_axis", "policy",
                   "faults", "async_"),
        **legacy)
    backend, mesh, data_axis = plan.backend, plan.mesh, plan.data_axis
    policy, faults = plan.policy, plan.faults
    store = "log_ratio" if plan.store is None else plan.store
    async_ = plan.async_
    if isinstance(async_, AsyncModel) and is_degenerate_async(async_):
        async_ = None
    cfgs = list(cfgs)
    if not cfgs:
        raise ValueError("need at least one config")
    if store not in SOCIAL_STORES:
        raise ValueError(f"store must be one of {SOCIAL_STORES}, got {store!r}")
    N, M = cfgs[0].topo.N, cfgs[0].topo.M
    if any(c.topo.N != N or c.topo.M != M for c in cfgs) or model.N != N:
        raise ValueError("grid configs (and the model) must share (N, M)")

    rt_key = _cfg_fingerprint(cfgs)
    stacked = _SOCIAL_RUNTIME_CACHE.get(rt_key)
    if stacked is None:
        e_max = max(int(np.count_nonzero(c.topo.adj)) for c in cfgs)
        runtimes = [make_social_runtime(c, e_max=e_max) for c in cfgs]
        stacked = _SOCIAL_RUNTIME_CACHE[rt_key] = stack_runtimes(runtimes)

    seeds_np = np.atleast_1d(np.asarray(seeds, np.uint32))
    gi, sd = np.meshgrid(
        np.arange(len(cfgs), dtype=np.int32), seeds_np, indexing="ij"
    )
    gi, sd = gi.ravel(), sd.ravel()
    (gi, sd), fi, fstack = _expand_fault_axis((gi, sd), faults)
    if fi is None:
        (gi, sd), ai, astack = _expand_async_axis((gi, sd), async_)
    else:
        (gi, sd, fi), ai, astack = _expand_async_axis((gi, sd, fi), async_)
    K = gi.shape[0]
    if mesh is not None:
        pad = (-K) % int(mesh.shape[data_axis])
        if pad:
            fill = np.full(pad, K - 1)
            gi = np.concatenate([gi, gi[fill]])
            sd = np.concatenate([sd, sd[fill]])
            if fi is not None:
                fi = np.concatenate([fi, fi[fill]])
            if ai is not None:
                ai = np.concatenate([ai, ai[fill]])

    fn = _social_sweep_fn(
        mesh, data_axis, truth=model.truth, M=M, T=T, store=store,
        backend=resolve_backend(backend),
        policy=None if policy is None else resolve_policy(policy),
        has_faults=fi is not None, has_async=ai is not None,
    )
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(sd))
    rt_batch = jax.tree_util.tree_map(lambda x: x[jnp.asarray(gi)], stacked)
    truth_probs = model.tables[:, model.truth, :].astype(jnp.float32)
    args = (
        keys, rt_batch,
        model.log_tables().astype(jnp.float32),
        jnp.cumsum(truth_probs, axis=-1),
    )
    if fi is not None:
        args += (jax.tree_util.tree_map(
            lambda x: x[jnp.asarray(fi)], fstack),)
    if ai is not None:
        args += (jax.tree_util.tree_map(
            lambda x: x[jnp.asarray(ai)], astack),)
    beliefs, log_ratio = fn(*args)
    drops = np.asarray([c.drop_prob for c in cfgs], np.float32)
    gammas = np.asarray([c.gamma_period for c in cfgs], np.int32)
    return SocialSweepResult(
        beliefs=beliefs[:K], log_ratio=log_ratio[:K],
        drop_prob=jnp.asarray(drops[gi[:K]]),
        gamma=jnp.asarray(gammas[gi[:K]]),
        seed=jnp.asarray(sd[:K]), cfg=jnp.asarray(gi[:K]),
        fault=None if fi is None else jnp.asarray(fi[:K]),
        async_=None if ai is None else jnp.asarray(ai[:K]),
    )


def run_social_sweep(
    model: SignalModel,
    cfg: HPSConfig | Sequence[HPSConfig],
    T: int,
    *,
    drop_probs: Sequence[float] | float | None = None,
    gammas: Sequence[int] | int | None = None,
    seeds: Sequence[int] | int = 0,
    plan: ExecutionPlan | None = None,
    **legacy,
) -> SocialSweepResult:
    """Cross-product (topology x drop_prob x Gamma x seed) Algorithm 3 sweep.

    ``cfg`` is one base config or a sequence of them (e.g. topology draws —
    all sharing (N, M)); every base is crossed with every ``drop_probs``
    value and every ``gammas`` fusion period (defaults: the base's own
    settings), and the expanded scenario list runs with every seed as ONE
    jitted vmapped scan via :func:`run_social_grid` — drop_prob and Gamma
    ride the scenario axis as traced scalars, so the entire grid is one
    compiled program. Scenario order: base-major, then drop, then Gamma,
    then seed, then fault (matching the ``cfg``/``drop_prob``/``gamma``/
    ``seed``/``fault``/``async_`` coordinate arrays of the result);
    ``plan.faults`` / ``plan.async_`` are the optional fault- and
    async-model axes of :func:`run_social_grid` (execution knobs arrive
    via ``plan=``; loose kwargs are deprecated shims).
    """
    plan = resolve_plan(
        plan, _entry="run_social_sweep",
        _supports=("backend", "store", "mesh", "data_axis", "policy",
                   "faults", "async_"),
        **legacy)
    bases = [cfg] if isinstance(cfg, HPSConfig) else list(cfg)
    expanded = []
    for base in bases:
        dps = ([base.drop_prob] if drop_probs is None
               else np.atleast_1d(np.asarray(drop_probs, np.float32)).tolist())
        gms = ([base.gamma_period] if gammas is None
               else np.atleast_1d(np.asarray(gammas, np.int32)).tolist())
        for dp in dps:
            for g in gms:
                expanded.append(dataclasses.replace(
                    base, drop_prob=float(dp), gamma_period=int(g)
                ))
    return run_social_grid(model, expanded, T, seeds, plan=plan)


# ---------------------------------------------------------------------------
# Algorithm 1: batched (topology x M x Gamma x drop) x seed HPS sweeps
# ---------------------------------------------------------------------------

class HPSSweepResult(NamedTuple):
    """One row per scenario (config x seed), leading axis K.

    ``ratio``/``gap`` follow the ``store`` shapes of
    :class:`repro.core.hps.HPSResult` with the extra leading K —
    ``store="gap"`` (the sweep default) gives the (K, T) worst
    consensus-error curves of Theorem 1 plus final (K, N, d) ratios, which
    is the decay-diagram payload without any O(K T N d) history. ``cfg``
    indexes into the config list; ``drop_prob``/``gamma``/``M``/``seed``
    are the per-scenario coordinates.
    """

    ratio: jnp.ndarray
    gap: jnp.ndarray
    drop_prob: jnp.ndarray  # (K,)
    gamma: jnp.ndarray      # (K,)
    M: jnp.ndarray          # (K,) sub-network count of that scenario
    seed: jnp.ndarray       # (K,)
    cfg: jnp.ndarray        # (K,) config index
    fault: jnp.ndarray | None = None  # (K,) fault-model index, None = no axis
    async_: jnp.ndarray | None = None  # (K,) async-model index, minor-most

    @property
    def K(self) -> int:
        return int(self.seed.shape[0])

    def describe(self) -> str:
        return _describe_result(self)


# Jitted HPS-sweep programs keyed on (mesh, data_axis, statics). The
# per-scenario data is ALL arrays (HPSRuntime leaves + PRNG keys + the
# shared w), so one cached executable serves every topology/M/Gamma/drop
# combo of the same shapes; the LRU bound keeps long parameter studies from
# pinning retired shard_map wrappers.
_HPS_COMPILED = _LRUCache(maxsize=16)

# Stacked HPSRuntime batches keyed on the (configs,) fingerprint: repeated
# sweep calls (e.g. host-side seed batches over one grid) skip the
# per-config edge-list construction and device uploads entirely.
_HPS_RUNTIME_CACHE = _LRUCache(maxsize=16)


def _hps_sweep_fn(mesh, data_axis, *, T, store, backend, policy=None,
                  has_faults=False, has_async=False):
    key = (mesh, data_axis, T, store, backend, policy, has_faults, has_async)
    fn = _HPS_COMPILED.get(key)
    if fn is not None:
        return fn

    def base(keys, rt_batch, w, fault_b=None, async_b=None):
        def single(k, rt, fault=None, am=None):
            # grid runtimes come from make_hps_runtime: dst-sorted edge
            # index, e_max pad rows at dst = N - 1 keep it sorted
            _, outs = _hps_scan_core(
                k, rt, w, T=T, store=store, backend=backend,
                policy=policy, dst_sorted=True, faults=fault, async_=am,
            )
            return outs

        if fault_b is None and async_b is None:
            return jax.vmap(single, in_axes=(0, 0))(keys, rt_batch)
        if async_b is None:
            return jax.vmap(single, in_axes=(0, 0, 0))(
                keys, rt_batch, fault_b)
        if fault_b is None:
            return jax.vmap(
                lambda k, rt, am: single(k, rt, None, am),
                in_axes=(0, 0, 0),
            )(keys, rt_batch, async_b)
        return jax.vmap(single, in_axes=(0, 0, 0, 0))(
            keys, rt_batch, fault_b, async_b)

    if has_async and not has_faults:
        # shard_map passes positionally; skip the absent fault_b slot
        def body(keys, rt_batch, w, async_b):
            return base(keys, rt_batch, w, None, async_b)
    else:
        body = base

    if mesh is not None:
        spec = P(data_axis)
        in_specs = (
            spec,
            HPSRuntime(*([spec] * len(HPSRuntime._fields))),
            P(),
        )
        if has_faults:
            in_specs += (FaultModel(
                *([spec] * len(FaultModel._fields))),)
        if has_async:
            in_specs += (AsyncModel(
                *([spec] * len(AsyncModel._fields))),)
        body = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(spec, spec),
            check_vma=False,
        )
    fn = _HPS_COMPILED[key] = jax.jit(body)
    return fn


def run_hps_grid(
    w: jnp.ndarray,
    cfgs: Sequence[HPSConfig],
    T: int,
    seeds: Sequence[int] | int,
    *,
    plan: ExecutionPlan | None = None,
    **legacy,
) -> HPSSweepResult:
    """Batched (topology, M, Gamma, drop) x seed grid as ONE compiled
    vmapped scan of the fused Algorithm 1 engine.

    Every config's edge index builds once; the per-config runtime arrays
    (edge lists padded to the common E, representative masks, drop_prob /
    Gamma / B / M as traced scalars) stack leaf-wise onto a scenario axis
    and the K = |cfgs| x |seeds| grid executes in lockstep under a single
    ``jax.vmap``. Configs must share N — the sub-network count M rides the
    scenario axis as a traced scalar through the 1/2M fusion weight, so
    hierarchies with DIFFERENT numbers of sub-networks batch into the same
    trace. ``w`` (N, d) is shared by every scenario. Each scenario's seed
    drives the link-mask stream on the dedicated ``hps_stream_fold``
    domain — a grid row is bit-identical to ``run_hps(w, cfg, T, seed=s)``
    whenever the config's edge count equals the grid's padded E (always
    true for single-topology Gamma x drop x seed sweeps); mixed-E grids
    pad smaller edge lists up to the widest, which re-indexes the (E,)
    link-mask draw, so those rows are instead bit-identical to
    :func:`repro.core.hps.run_hps_runtime` on the same padded runtime.

    ``store`` defaults to ``"gap"``: the (K, T) worst consensus-error
    curves are reduced inside the scan, so nothing of size (K, T, N, d)
    ever exists — pass ``store="trajectory"`` explicitly to materialize
    full ratio histories. With ``mesh``, the scenario axis is sharded over
    ``data_axis`` via ``shard_map`` exactly like the other engines (K
    padded up to a multiple of the axis size by repeating the last
    scenario; results bit-identical to the single-device vmap).

    The jitted program is cached in ``_HPS_COMPILED`` keyed on
    (mesh, statics) only — the grid data is all arrays, so repeated studies
    over different topologies of the same shapes reuse one executable.

    ``plan.faults`` (one :class:`repro.core.faults.FaultModel` or a
    sequence) crosses a fault-minor scenario axis into the grid exactly as
    in :func:`run_social_grid`; the result's ``fault`` field indexes into
    the sequence, and ``faults=None`` keeps the pre-fault program
    bit-identical. ``plan.async_`` crosses the async-minor axis the same
    way (a single concretely degenerate model dispatches to the
    synchronous program, no axis). Execution knobs arrive via ``plan=``
    (loose kwargs are deprecated shims; ``plan.store=None`` means
    ``"gap"``).
    """
    from repro.kernels.pushsum_edge import resolve_backend

    plan = resolve_plan(
        plan, _entry="run_hps_grid",
        _supports=("backend", "store", "mesh", "data_axis", "policy",
                   "faults", "async_"),
        **legacy)
    backend, mesh, data_axis = plan.backend, plan.mesh, plan.data_axis
    policy, faults = plan.policy, plan.faults
    store = "gap" if plan.store is None else plan.store
    async_ = plan.async_
    if isinstance(async_, AsyncModel) and is_degenerate_async(async_):
        async_ = None
    cfgs = list(cfgs)
    if not cfgs:
        raise ValueError("need at least one config")
    if store not in HPS_STORES:
        raise ValueError(f"store must be one of {HPS_STORES}, got {store!r}")
    w = jnp.asarray(w)
    N = cfgs[0].topo.N
    if any(c.topo.N != N for c in cfgs) or w.shape[0] != N:
        raise ValueError("grid configs (and w) must share the node count N")

    rt_key = _cfg_fingerprint(cfgs)
    stacked = _HPS_RUNTIME_CACHE.get(rt_key)
    if stacked is None:
        e_max = max(int(np.count_nonzero(c.topo.adj)) for c in cfgs)
        runtimes = [make_hps_runtime(c, e_max=e_max) for c in cfgs]
        stacked = _HPS_RUNTIME_CACHE[rt_key] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *runtimes
        )

    seeds_np = np.atleast_1d(np.asarray(seeds, np.uint32))
    gi, sd = np.meshgrid(
        np.arange(len(cfgs), dtype=np.int32), seeds_np, indexing="ij"
    )
    gi, sd = gi.ravel(), sd.ravel()
    (gi, sd), fi, fstack = _expand_fault_axis((gi, sd), faults)
    if fi is None:
        (gi, sd), ai, astack = _expand_async_axis((gi, sd), async_)
    else:
        (gi, sd, fi), ai, astack = _expand_async_axis((gi, sd, fi), async_)
    K = gi.shape[0]
    if mesh is not None:
        pad = (-K) % int(mesh.shape[data_axis])
        if pad:
            fill = np.full(pad, K - 1)
            gi = np.concatenate([gi, gi[fill]])
            sd = np.concatenate([sd, sd[fill]])
            if fi is not None:
                fi = np.concatenate([fi, fi[fill]])
            if ai is not None:
                ai = np.concatenate([ai, ai[fill]])

    fn = _hps_sweep_fn(
        mesh, data_axis, T=T, store=store, backend=resolve_backend(backend),
        policy=None if policy is None else resolve_policy(policy),
        has_faults=fi is not None, has_async=ai is not None,
    )
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(sd))
    rt_batch = jax.tree_util.tree_map(lambda x: x[jnp.asarray(gi)], stacked)
    args = (keys, rt_batch, w)
    if fi is not None:
        args += (jax.tree_util.tree_map(
            lambda x: x[jnp.asarray(fi)], fstack),)
    if ai is not None:
        args += (jax.tree_util.tree_map(
            lambda x: x[jnp.asarray(ai)], astack),)
    ratio, gap = fn(*args)
    drops = np.asarray([c.drop_prob for c in cfgs], np.float32)
    gammas = np.asarray([c.gamma_period for c in cfgs], np.int32)
    Ms = np.asarray([c.topo.M for c in cfgs], np.int32)
    return HPSSweepResult(
        ratio=ratio[:K], gap=gap[:K],
        drop_prob=jnp.asarray(drops[gi[:K]]),
        gamma=jnp.asarray(gammas[gi[:K]]),
        M=jnp.asarray(Ms[gi[:K]]),
        seed=jnp.asarray(sd[:K]), cfg=jnp.asarray(gi[:K]),
        fault=None if fi is None else jnp.asarray(fi[:K]),
        async_=None if ai is None else jnp.asarray(ai[:K]),
    )


def run_hps_sweep(
    w: jnp.ndarray,
    cfg: HPSConfig | Sequence[HPSConfig],
    T: int,
    *,
    drop_probs: Sequence[float] | float | None = None,
    gammas: Sequence[int] | int | None = None,
    seeds: Sequence[int] | int = 0,
    plan: ExecutionPlan | None = None,
    **legacy,
) -> HPSSweepResult:
    """Cross-product (topology x M x drop_prob x Gamma x seed) HPS sweep.

    ``cfg`` is one base config or a sequence of them (e.g. hierarchies with
    different sub-network counts — all sharing N); every base is crossed
    with every ``drop_probs`` value and every ``gammas`` fusion period
    (defaults: the base's own settings), and the expanded scenario list
    runs with every seed as ONE jitted vmapped scan via
    :func:`run_hps_grid` — drop_prob, Gamma and M ride the scenario axis
    as traced scalars, so the entire grid is one compiled program.
    Scenario order: base-major, then drop, then Gamma, then seed, then
    fault, then async (matching the unified index-column convention;
    execution knobs arrive via ``plan=``, loose kwargs are deprecated
    shims).
    """
    plan = resolve_plan(
        plan, _entry="run_hps_sweep",
        _supports=("backend", "store", "mesh", "data_axis", "policy",
                   "faults", "async_"),
        **legacy)
    bases = [cfg] if isinstance(cfg, HPSConfig) else list(cfg)
    expanded = []
    for base in bases:
        dps = ([base.drop_prob] if drop_probs is None
               else np.atleast_1d(np.asarray(drop_probs, np.float32)).tolist())
        gms = ([base.gamma_period] if gammas is None
               else np.atleast_1d(np.asarray(gammas, np.int32)).tolist())
        for dp in dps:
            for g in gms:
                expanded.append(dataclasses.replace(
                    base, drop_prob=float(dp), gamma_period=int(g)
                ))
    return run_hps_grid(w, expanded, T, seeds, plan=plan)

# ---------------------------------------------------------------------------
# Cache registry: the one front door to every compiled/runtime cache the
# sweep engines (and the jitted push-sum step) own. Tests and operational
# tooling go through here instead of importing the private module globals —
# the globals stay (they ARE the caches), but their names are no longer an
# API surface.
# ---------------------------------------------------------------------------

class CacheInfo(NamedTuple):
    """``cache_info()`` payload: entries held now / eviction bound
    (``None`` = unbounded, e.g. the jit wrappers' own tracing caches)."""

    currsize: int
    maxsize: int | None


class CacheHandle(NamedTuple):
    """Uniform view of one cache: ``cache_info()`` + ``clear()``.

    Wraps the three cache shapes the engines use — :class:`_LRUCache`
    mappings, ``jax.jit`` wrappers (``_cache_size``/``clear_cache``), and
    ``functools.lru_cache`` factories — behind one interface.
    """

    name: str
    size_fn: object
    max_size: int | None
    clear_fn: object

    def cache_info(self) -> CacheInfo:
        return CacheInfo(currsize=int(self.size_fn()), maxsize=self.max_size)

    def clear(self) -> None:
        self.clear_fn()


def cache_registry() -> dict[str, CacheHandle]:
    """Live handles to every sweep-layer cache, keyed by the same names
    the retrace sentinel (:mod:`repro.statics.retrace`) registers.

    Built fresh per call (handles close over the module globals, so a
    handle stays valid across clears); ``clear()`` empties the underlying
    cache — compiled executables, stacked runtimes, or jit tracing caches —
    which is what retrace-sensitive tests use to reset between cases.
    """
    from .pushsum import _STEP_JIT, _step_jit_entries

    def _lru(name: str, c: _LRUCache) -> CacheHandle:
        return CacheHandle(name, lambda: len(c), c.maxsize, c.clear)

    def _jit(name: str, f) -> CacheHandle:
        return CacheHandle(name, f._cache_size, None, f.clear_cache)

    def _factory(name: str, f) -> CacheHandle:
        return CacheHandle(
            name, lambda: f.cache_info().currsize, None, f.cache_clear
        )

    handles = [
        _jit("pushsum.sweep-jit", _sweep_compiled),
        _jit("pushsum.sweep2d-jit", _sweep2d_compiled),
        _factory("pushsum.sweep-sharded", _sweep_sharded),
        _factory("pushsum.sweep2d-sharded", _sweep_sharded_2d),
        CacheHandle("pushsum.edge-placement", lambda: len(_PLACED), None,
                    _clear_edge_placements),
        CacheHandle(
            "pushsum.step-jit", _step_jit_entries, None, _STEP_JIT.clear
        ),
        _lru("byz.compiled", _BYZ_COMPILED),
        _lru("byz.grid", _BYZ_GRID_COMPILED),
        _lru("byz.runtime", _BYZ_RUNTIME_CACHE),
        _lru("social.compiled", _SOCIAL_COMPILED),
        _lru("social.runtime", _SOCIAL_RUNTIME_CACHE),
        _lru("hps.compiled", _HPS_COMPILED),
        _lru("hps.runtime", _HPS_RUNTIME_CACHE),
    ]
    return {h.name: h for h in handles}


# ---------------------------------------------------------------------------
# Retrace-sentinel registrations: every compiled cache this module owns is
# visible to repro.statics.retrace, so the lint can prove that repeated
# sweep calls with unchanged configs never recompile.
# ---------------------------------------------------------------------------
register_statics_cache("pushsum.sweep-jit", _sweep_compiled._cache_size)
register_statics_cache("pushsum.sweep2d-jit", _sweep2d_compiled._cache_size)
register_statics_cache("byz.compiled", _BYZ_COMPILED)
register_statics_cache("byz.grid", _BYZ_GRID_COMPILED)
register_statics_cache("byz.runtime", _BYZ_RUNTIME_CACHE)
register_statics_cache("social.compiled", _SOCIAL_COMPILED)
register_statics_cache("social.runtime", _SOCIAL_RUNTIME_CACHE)
register_statics_cache("hps.compiled", _HPS_COMPILED)
register_statics_cache("hps.runtime", _HPS_RUNTIME_CACHE)
