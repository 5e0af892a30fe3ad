"""Directed-graph machinery for the hierarchical multi-agent system.

Everything in this module is *host-side* (numpy) setup code: topologies are
built once, converted to jnp masks, and then consumed by the jax-traced
dynamics in :mod:`repro.core.pushsum` / :mod:`repro.core.byzantine`.

Conventions
-----------
* ``adj[i, j] = True`` means a directed edge ``i -> j`` (i sends to j).
* Self-loops are never stored in ``adj``; every algorithm in the paper adds
  the implicit self-contribution separately (the ``+1`` in ``d_j + 1``).
* A *hierarchical system* is a block-diagonal adjacency over ``M``
  sub-networks; no direct edges cross blocks (the parameter server is the
  only cross-network channel, modelled in :mod:`repro.core.hps`).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator, Sequence

import numpy as np

__all__ = [
    "ring",
    "complete",
    "random_strongly_connected",
    "is_strongly_connected",
    "diameter",
    "strongly_connected_components",
    "source_components",
    "has_single_source_component",
    "reduced_graphs",
    "check_assumption3",
    "beta_i",
    "HierTopology",
    "make_hierarchy",
    "link_schedule",
    "EdgeList",
    "edge_list",
    "stack_edge_lists",
    "edge_masks",
    "sort_by_dst",
    "run_ends",
    "EdgeShards",
    "partition_edge_list",
    "chord_edge_list",
    "is_dst_sorted",
    "block_complete_edge_list",
    "hier_edge_list",
    "random_strongly_connected_edge_list",
    "NeighborList",
    "neighbor_lists",
    "stack_neighbor_lists",
]


# ---------------------------------------------------------------------------
# Basic topologies
# ---------------------------------------------------------------------------

def ring(n: int, bidirectional: bool = False) -> np.ndarray:
    """Directed ring ``0 -> 1 -> ... -> n-1 -> 0``."""
    adj = np.zeros((n, n), dtype=bool)
    for i in range(n):
        adj[i, (i + 1) % n] = True
        if bidirectional:
            adj[(i + 1) % n, i] = True
    return adj


def complete(n: int) -> np.ndarray:
    adj = np.ones((n, n), dtype=bool)
    np.fill_diagonal(adj, False)
    return adj


def random_strongly_connected(
    n: int, extra_edge_prob: float, rng: np.random.Generator
) -> np.ndarray:
    """A random digraph guaranteed strongly connected.

    Built as a random Hamiltonian cycle (strong-connectivity backbone) plus
    Bernoulli extra edges — the standard construction for consensus
    simulations.
    """
    perm = rng.permutation(n)
    adj = np.zeros((n, n), dtype=bool)
    for k in range(n):
        adj[perm[k], perm[(k + 1) % n]] = True
    extra = rng.random((n, n)) < extra_edge_prob
    np.fill_diagonal(extra, False)
    adj |= extra
    return adj


# ---------------------------------------------------------------------------
# Connectivity analysis
# ---------------------------------------------------------------------------

def _reach(adj: np.ndarray, start: int) -> np.ndarray:
    """Boolean reachability vector from ``start`` (BFS)."""
    n = adj.shape[0]
    seen = np.zeros(n, dtype=bool)
    seen[start] = True
    frontier = [start]
    while frontier:
        nxt = []
        for u in frontier:
            for v in np.nonzero(adj[u])[0]:
                if not seen[v]:
                    seen[v] = True
                    nxt.append(int(v))
        frontier = nxt
    return seen


def is_strongly_connected(adj: np.ndarray) -> bool:
    n = adj.shape[0]
    if n == 0:
        return False
    return bool(_reach(adj, 0).all() and _reach(adj.T, 0).all())


def diameter(adj: np.ndarray) -> int:
    """Diameter of a strongly connected digraph (max shortest-path length)."""
    n = adj.shape[0]
    dist = np.where(adj, 1, np.inf)
    np.fill_diagonal(dist, 0)
    for k in range(n):  # Floyd–Warshall; n is small in all our sims
        dist = np.minimum(dist, dist[:, k : k + 1] + dist[k : k + 1, :])
    if np.isinf(dist).any():
        raise ValueError("graph is not strongly connected")
    return int(dist.max())


def strongly_connected_components(adj: np.ndarray) -> list[list[int]]:
    """Tarjan's SCC algorithm, iterative (host-side, small graphs)."""
    n = adj.shape[0]
    index = [-1] * n
    low = [0] * n
    on_stack = [False] * n
    stack: list[int] = []
    comps: list[list[int]] = []
    counter = 0
    succ = [list(np.nonzero(adj[u])[0]) for u in range(n)]

    for root in range(n):
        if index[root] != -1:
            continue
        work = [(root, 0)]
        while work:
            u, pi = work[-1]
            if pi == 0:
                index[u] = low[u] = counter
                counter += 1
                stack.append(u)
                on_stack[u] = True
            advanced = False
            for i in range(pi, len(succ[u])):
                v = int(succ[u][i])
                if index[v] == -1:
                    work[-1] = (u, i + 1)
                    work.append((v, 0))
                    advanced = True
                    break
                elif on_stack[v]:
                    low[u] = min(low[u], index[v])
            if advanced:
                continue
            work.pop()
            if work:
                low[work[-1][0]] = min(low[work[-1][0]], low[u])
            if low[u] == index[u]:
                comp = []
                while True:
                    w = stack.pop()
                    on_stack[w] = False
                    comp.append(w)
                    if w == u:
                        break
                comps.append(sorted(comp))
    return comps


def source_components(adj: np.ndarray) -> list[list[int]]:
    """SCCs with no incoming edges from outside (sources of the condensation)."""
    comps = strongly_connected_components(adj)
    comp_of = {}
    for ci, comp in enumerate(comps):
        for v in comp:
            comp_of[v] = ci
    has_in = [False] * len(comps)
    rows, cols = np.nonzero(adj)
    for u, v in zip(rows, cols):
        cu, cv = comp_of[int(u)], comp_of[int(v)]
        if cu != cv:
            has_in[cv] = True
    return [comps[ci] for ci in range(len(comps)) if not has_in[ci]]


def has_single_source_component(adj: np.ndarray) -> bool:
    return len(source_components(adj)) == 1


# ---------------------------------------------------------------------------
# Reduced graphs (Definition 1) and Assumption 3
# ---------------------------------------------------------------------------

def reduced_graphs(
    adj: np.ndarray,
    faulty: Sequence[int],
    F: int,
    max_graphs: int | None = None,
    rng: np.random.Generator | None = None,
) -> Iterator[tuple[np.ndarray, list[int]]]:
    """Yield reduced graphs per Definition 1.

    (1) remove faulty nodes and incident links, (2) for each non-faulty node
    remove F additional incoming links (all combinations; sampled when the
    enumeration would exceed ``max_graphs``).

    Yields ``(reduced_adj, good_nodes)`` where ``reduced_adj`` is indexed by
    position in ``good_nodes``.
    """
    n = adj.shape[0]
    faulty_set = set(int(f) for f in faulty)
    good = [v for v in range(n) if v not in faulty_set]
    g = len(good)
    base = adj[np.ix_(good, good)].copy()

    per_node_choices: list[list[tuple[int, ...]]] = []
    for j in range(g):
        incoming = list(np.nonzero(base[:, j])[0])
        if len(incoming) <= F:
            per_node_choices.append([tuple(incoming)])
        else:
            per_node_choices.append(list(itertools.combinations(incoming, F)))

    total = 1
    for c in per_node_choices:
        total *= len(c)
        if max_graphs is not None and total > max_graphs:
            break

    def build(choice_per_node) -> np.ndarray:
        red = base.copy()
        for j, removed in enumerate(choice_per_node):
            for r in removed:
                red[r, j] = False
        return red

    if max_graphs is not None and total > max_graphs:
        rng = rng or np.random.default_rng(0)
        for _ in range(max_graphs):
            choice = [c[rng.integers(len(c))] for c in per_node_choices]
            yield build(choice), good
    else:
        for choice in itertools.product(*per_node_choices):
            yield build(choice), good


def check_assumption3(
    adj: np.ndarray, F: int, max_fault_sets: int = 64, max_graphs: int = 256
) -> bool:
    """Check Assumption 3: every reduced graph has exactly one source component.

    Exhaustive for small graphs, sampled otherwise. A complete graph with
    ``n >= 3F + 1`` always passes (classical result) — we still verify.
    """
    n = adj.shape[0]
    rng = np.random.default_rng(0)
    fault_sets = list(itertools.combinations(range(n), F)) if F > 0 else [()]
    if len(fault_sets) > max_fault_sets:
        idx = rng.choice(len(fault_sets), size=max_fault_sets, replace=False)
        fault_sets = [fault_sets[i] for i in idx]
    for fs in fault_sets:
        for red, _good in reduced_graphs(adj, fs, F, max_graphs=max_graphs, rng=rng):
            if len(source_components(red)) != 1:
                return False
    return True


def beta_i(adj: np.ndarray) -> float:
    """beta_i = 1 / max_j (d_j + 1)^2 — the per-network contraction constant."""
    d_out = adj.sum(axis=1)
    return 1.0 / float((d_out.max() + 1) ** 2)


# ---------------------------------------------------------------------------
# Hierarchical system
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HierTopology:
    """M sub-networks glued block-diagonally; reps exchange with the PS.

    Attributes
    ----------
    adj: (N, N) bool block-diagonal adjacency.
    sizes: per-network agent counts ``n_i``.
    offsets: start index of each network's block.
    reps: global index of each network's designated agent (first of block
        by default — the paper allows an arbitrary choice).
    """

    adj: np.ndarray
    sizes: tuple[int, ...]
    offsets: tuple[int, ...]
    reps: tuple[int, ...]

    @property
    def N(self) -> int:
        return int(self.adj.shape[0])

    @property
    def M(self) -> int:
        return len(self.sizes)

    def network_of(self) -> np.ndarray:
        """(N,) network index of every agent."""
        out = np.zeros(self.N, dtype=np.int32)
        for i, (off, sz) in enumerate(zip(self.offsets, self.sizes)):
            out[off : off + sz] = i
        return out

    def block(self, i: int) -> np.ndarray:
        off, sz = self.offsets[i], self.sizes[i]
        return self.adj[off : off + sz, off : off + sz]

    def d_star(self) -> int:
        return max(diameter(self.block(i)) for i in range(self.M))

    def min_beta(self) -> float:
        return min(beta_i(self.block(i)) for i in range(self.M))

    def rep_mask(self) -> np.ndarray:
        mask = np.zeros(self.N, dtype=bool)
        for r in self.reps:
            mask[r] = True
        return mask


def make_hierarchy(
    sizes: Sequence[int],
    topology: str = "ring+",
    extra_edge_prob: float = 0.3,
    seed: int = 0,
    rep_choice: str = "first",
) -> HierTopology:
    """Build an M-network hierarchical system.

    topology: "ring" | "complete" | "ring+" (ring + random extra edges).
    """
    rng = np.random.default_rng(seed)
    blocks = []
    for n in sizes:
        if topology == "ring":
            b = ring(n)
        elif topology == "complete":
            b = complete(n)
        elif topology == "ring+":
            b = random_strongly_connected(n, extra_edge_prob, rng)
        else:
            raise ValueError(f"unknown topology {topology!r}")
        assert is_strongly_connected(b)
        blocks.append(b)
    N = int(sum(sizes))
    adj = np.zeros((N, N), dtype=bool)
    offsets = []
    off = 0
    for b, n in zip(blocks, sizes):
        adj[off : off + n, off : off + n] = b
        offsets.append(off)
        off += n
    if rep_choice == "first":
        reps = tuple(offsets)
    elif rep_choice == "random":
        reps = tuple(int(o + rng.integers(n)) for o, n in zip(offsets, sizes))
    else:
        raise ValueError(rep_choice)
    return HierTopology(
        adj=adj, sizes=tuple(int(s) for s in sizes), offsets=tuple(offsets), reps=reps
    )


# ---------------------------------------------------------------------------
# Sparse edge-list representation
# ---------------------------------------------------------------------------
#
# The fast robust push-sum only ever needs per-*directed-link* state (the
# cumulative ``rho`` a receiver has heard on each in-link), so on sparse
# topologies the O(N^2) adjacency/mask tensors are pure waste. An
# :class:`EdgeList` is the host-side (numpy) sparse view consumed by
# :mod:`repro.core.pushsum`'s edge-list core: edge e is the directed link
# ``src[e] -> dst[e]``; per-edge state arrays are (E, ...) and node updates
# use ``jax.ops.segment_sum`` over ``dst``.

@dataclasses.dataclass(frozen=True)
class EdgeList:
    """Sparse directed graph: edge ``e`` is ``src[e] -> dst[e]``.

    ``valid`` marks live edges — always all-True for a single graph, but
    batched/padded edge lists (see :func:`stack_edge_lists`) pad to a common
    E with ``valid=False`` dummy edges so topology draws with different edge
    counts can ride one ``jax.vmap`` axis.
    """

    src: np.ndarray    # (E,) int32 sender of each edge
    dst: np.ndarray    # (E,) int32 receiver of each edge
    n: int             # number of nodes
    valid: np.ndarray  # (E,) bool — False on padding edges

    @property
    def E(self) -> int:
        """Padded edge count — last axis, correct for single and batched."""
        return int(self.src.shape[-1])

    @property
    def is_batched(self) -> bool:
        return self.src.ndim == 2

    def _require_single(self, what: str) -> None:
        if self.is_batched:
            raise ValueError(
                f"{what} is per-graph; this EdgeList batches "
                f"{self.src.shape[0]} topology draws — index a row first"
            )

    def out_degree(self) -> np.ndarray:
        """(N,) out-degree over valid edges (the ``d_j`` of ``d_j + 1``)."""
        self._require_single("out_degree()")
        deg = np.zeros(self.n, dtype=np.int32)
        np.add.at(deg, self.src[self.valid], 1)
        return deg

    def to_dense(self) -> np.ndarray:
        self._require_single("to_dense()")
        adj = np.zeros((self.n, self.n), dtype=bool)
        adj[self.src[self.valid], self.dst[self.valid]] = True
        return adj


def edge_list(adj: np.ndarray) -> EdgeList:
    """Dense (N, N) bool adjacency -> sparse :class:`EdgeList`.

    Edges are emitted in C order (row-major: sorted by src, then dst), so
    ``edge_masks(masks, el)[t, e] == masks[t, el.src[e], el.dst[e]]``.
    """
    src, dst = np.nonzero(np.asarray(adj, dtype=bool))
    return EdgeList(
        src=src.astype(np.int32),
        dst=dst.astype(np.int32),
        n=int(adj.shape[0]),
        valid=np.ones(src.shape[0], dtype=bool),
    )


def stack_edge_lists(adjs: Sequence[np.ndarray]) -> EdgeList:
    """Batch G topology draws into one padded EdgeList for vmapped sweeps.

    Returns an EdgeList whose fields have a leading graph axis: src/dst/valid
    are (G, E_max); ``n`` must agree across draws. Padding edges point 0 -> 0
    with ``valid=False`` and are excluded from out-degrees and delivery by
    the sparse core (their mask is forced False).
    """
    els = [edge_list(a) for a in adjs]
    n = els[0].n
    if any(el.n != n for el in els):
        raise ValueError("all topology draws must have the same node count")
    E = max(el.E for el in els)
    src = np.zeros((len(els), E), dtype=np.int32)
    dst = np.zeros((len(els), E), dtype=np.int32)
    valid = np.zeros((len(els), E), dtype=bool)
    for g, el in enumerate(els):
        src[g, : el.E] = el.src
        dst[g, : el.E] = el.dst
        valid[g, : el.E] = True
    return EdgeList(src=src, dst=dst, n=n, valid=valid)


def sort_by_dst(el: EdgeList, return_offsets: bool = False):
    """Stable-sort the edge index by receiver -> (sorted, perm, inv).

    The fused Pallas edge-scatter kernel (:mod:`repro.kernels.pushsum_edge`)
    streams edges in ``dst`` order so the per-receiver integration is a run
    of contiguous segments instead of a generic scatter. Sorting is a pure
    relabeling of edge slots:

    * ``perm``  (E,) int32 — sorted position -> original edge index, i.e.
      ``sorted.src == el.src[..., perm]``. Project any original-edge-order
      array (an explicit (T, E) mask schedule, an initial rho) into the
      sorted layout with ``arr[..., perm]``.
    * ``inv``   (E,) int32 — original edge index -> sorted position
      (``inv[perm[i]] == i``), so per-edge state computed in the sorted
      layout maps back via ``rho_sorted[..., inv, :]``.

    With ``return_offsets=True`` a fourth value is returned: CSR-style
    per-destination segment offsets, (..., N+1) int32 with
    ``offsets[..., v] : offsets[..., v + 1]`` the contiguous run of sorted
    edges whose receiver is ``v`` (``offsets[..., 0] == 0``,
    ``offsets[..., N] == E``). The edge partitioner
    (:func:`partition_edge_list`) cuts the sorted index against these runs,
    and the downstream lowerings pass ``indices_are_sorted=True`` to the
    per-receiver ``segment_sum`` so pre-sorted inputs skip one argsort.
    Offsets on batched edge lists count padding edges inside the ``dst == 0``
    run (padding keeps ``dst = 0``); the core's ``mask & valid`` guard is
    what silences them, exactly as for ``perm``/``inv``.

    Batched edge lists sort every topology draw independently (perm/inv are
    then (G, E)); padding edges keep ``valid=False`` and simply sort in with
    the genuine ``dst == 0`` run, where the core's ``mask & valid`` guard
    already silences them.

    An index that is already dst-sorted (one O(E) check,
    :func:`is_dst_sorted`) is returned as it is, with the identity
    ``perm``/``inv``: no argsort. Callers that need only the sorted list
    (:func:`partition_edge_list`) use :func:`_dst_sorted`, which makes no
    E-length index at all.
    """
    dst = np.asarray(el.dst)
    if is_dst_sorted(dst):
        # already in order: the identity relabeling, and no argsort
        perm = np.broadcast_to(np.arange(dst.shape[-1], dtype=np.int32),
                               dst.shape).copy()
        out = (el, perm, perm.copy())
        return out if not return_offsets else out + (
            _dst_offsets(dst, el.n),)
    perm = np.argsort(dst, axis=-1, kind="stable").astype(np.int32)
    inv = np.empty_like(perm)
    if perm.ndim == 1:
        inv[perm] = np.arange(perm.shape[0], dtype=np.int32)
        sorted_el = EdgeList(
            src=el.src[perm], dst=el.dst[perm], n=el.n, valid=el.valid[perm]
        )
    else:
        rows = np.arange(perm.shape[0])[:, None]
        inv[rows, perm] = np.arange(perm.shape[1], dtype=np.int32)[None, :]
        sorted_el = EdgeList(
            src=np.take_along_axis(el.src, perm, axis=1),
            dst=np.take_along_axis(el.dst, perm, axis=1),
            n=el.n,
            valid=np.take_along_axis(el.valid, perm, axis=1),
        )
    if not return_offsets:
        return sorted_el, perm, inv
    offsets = _dst_offsets(np.asarray(sorted_el.dst), el.n)
    return sorted_el, perm, inv, offsets


def is_dst_sorted(dst: np.ndarray) -> bool:
    """True when every row of ``dst`` ((E,) or (G, E)) is ascending."""
    dst = np.asarray(dst)
    return bool(np.all(dst[..., 1:] >= dst[..., :-1]))


def _dst_sorted(el: EdgeList) -> EdgeList:
    """``el`` itself when its index is dst-sorted, else its stable sort by
    receiver (:func:`sort_by_dst` without the relabeling arrays)."""
    return el if is_dst_sorted(el.dst) else sort_by_dst(el)[0]


def _dst_offsets(sorted_dst: np.ndarray, n: int) -> np.ndarray:
    """(..., N+1) int32 CSR offsets of a dst-sorted edge index."""
    grid = np.arange(n + 1)
    if sorted_dst.ndim == 1:
        return np.searchsorted(sorted_dst, grid, side="left").astype(np.int32)
    return np.stack([
        np.searchsorted(row, grid, side="left") for row in sorted_dst
    ]).astype(np.int32)


def run_ends(dst: np.ndarray, n: int) -> np.ndarray | None:
    """(N,) int32 index of each receiver's last in-edge in a dst-sorted
    edge index, ``-1`` for a receiver with none; ``None`` when ``dst`` is
    not sorted (nothing is sorted here: sorting would re-index the edges'
    per-round draws). The run-end gather finish of
    :mod:`repro.kernels.pushsum_edge` reads these lanes."""
    dst = np.asarray(dst)
    if not is_dst_sorted(dst):
        return None
    out = np.full(n, -1, np.int32)
    if dst.size:
        ends = np.append(np.flatnonzero(dst[1:] != dst[:-1]), dst.size - 1)
        out[dst[ends]] = ends
    return out


@dataclasses.dataclass(frozen=True)
class EdgeShards:
    """A dst-sorted edge index cut into contiguous capacity-padded shards.

    The device-parallel layout of the edge-partitioned push-sum: shard ``k``
    owns the sorted edge slots ``[k * e_shard, (k + 1) * e_shard)`` — a
    contiguous run of destinations, so each receiver's in-edges live on at
    most two *adjacent* shards. Per-edge state (``rho``/``rho_m``) is
    (E_shard, ...) per device; node state stays replicated and per-step
    receiver partials are combined with a ``psum`` over the mesh ``graph``
    axis (:func:`repro.core.pushsum.sparse_pushsum_step` with
    ``graph_axis=``).

    Shard tails are padded to the common capacity ``e_shard`` with inert
    edges (``valid=False``) that keep ``dst`` equal to the shard's last real
    receiver, so every shard stays dst-sorted and the sorted-segment fast
    path (``indices_are_sorted=True``) remains legal.

    ``boundary`` is the halo index: ``boundary[..., v]`` is True iff
    receiver ``v``'s in-edge run is split across a shard cut — the only
    nodes whose per-step ``recv`` is a genuine multi-shard sum (interior
    nodes add exact ``+0.0`` partials from foreign shards).

    ``run_ends[..., k, v]`` is the slot, within shard ``k``, where
    receiver ``v``'s run ends: its last in-edge there, or for the shard's
    last receiver the last padding slot after it (padding keeps that
    receiver); ``-1`` where ``v`` has no real in-edge on the shard. A split
    receiver's earlier part ends at its shard's last real slot (a shard cut
    inside a run is full). The Pallas lowering finishes each shard with a
    gather at these slots (:meth:`run_end_windows`) instead of a scatter
    of its partials.

    Fields carry a leading graph axis (G, S, E_shard) when built from a
    batched :class:`EdgeList`, else (S, E_shard); ``boundary`` is
    correspondingly (G, N) or (N,), ``run_ends`` (G, S, N) or (S, N).

    An instance and the arrays it views (the source :class:`EdgeList`'s
    arrays too, where a shard is a view of them) must not change after
    partition: :func:`partition_edge_list` returns its arrays read-only,
    and an instance keeps what it derives from them while it lives — its
    :meth:`run_end_windows` and the device placement of its edge arrays
    (:func:`repro.core.sweeps.run_pushsum_sweep`). A graph that differs is
    a new instance (``dataclasses.replace``), which starts with none of
    that.
    """

    src: np.ndarray       # (..., S, E_shard) int32
    dst: np.ndarray       # (..., S, E_shard) int32
    valid: np.ndarray     # (..., S, E_shard) bool — False on padding
    n: int                # node count
    e_total: int          # edge count of the (padded) source EdgeList
    boundary: np.ndarray  # (..., N) bool halo index — receivers split by cuts
    run_ends: np.ndarray  # (..., S, N) int32 per-shard run ends, or -1
    # what is derived from the arrays above, kept per instance: a replace()
    # starts empty, and eq, hash and repr leave it out
    _memo: dict = dataclasses.field(default_factory=dict, init=False,
                                    repr=False, compare=False)

    @property
    def n_shards(self) -> int:
        return int(self.src.shape[-2])

    @property
    def e_shard(self) -> int:
        """Per-shard edge capacity."""
        return int(self.src.shape[-1])

    @property
    def e_pad(self) -> int:
        """Total padded edge count ``n_shards * e_shard`` — the edge count
        of the single-device program over :meth:`padded_edge_list`."""
        return self.n_shards * self.e_shard

    @property
    def is_batched(self) -> bool:
        return self.src.ndim == 3

    def padded_edge_list(self) -> EdgeList:
        """Concatenate the shards back into one (..., E_pad) EdgeList.

        This — not the original pre-partition edge list — is the
        single-device program the sharded run is compared with: the
        per-round (E_pad,) Bernoulli mask each device draws (and windows
        into) indexes the *padded* slots, and jax's counter-based bits have
        no prefix property, so the original unpadded list only matches when
        ``e_pad == E`` or ``drop_prob == 0``. The two agree up to the order
        in which each receiver's sum is formed: XLA rewrites the
        single-device ``z * share + segment_sum(...)`` into a scatter onto
        ``z * share``, which the sharded program's halo ``psum`` between
        the sum and the add rules out, so the last bits can differ at any
        receiver (see ``run_pushsum_sweep``).
        """
        flat = lambda a: a.reshape(*a.shape[:-2], -1)
        return EdgeList(src=flat(self.src), dst=flat(self.dst), n=self.n,
                        valid=flat(self.valid))

    def run_end_windows(self) -> tuple[np.ndarray, np.ndarray]:
        """``(lo, ends)``: each shard's run ends over one window of ``W``
        receivers, the same ``W`` for every shard (the widest receiver
        range a shard holds): ``ends[..., k, i]`` is ``run_ends[..., k,
        lo[..., k] + i]``. A shard's receivers are contiguous, so its window
        holds every run that ends on it; ``lo`` (int32, (..., S)) is kept
        at most ``N - W``. Computed once an instance, read-only."""
        if "windows" not in self._memo:
            held = self.run_ends >= 0
            idx = np.arange(self.n)
            first = np.where(held, idx, self.n).min(axis=-1)
            last = np.where(held, idx, -1).max(axis=-1)
            width = max(int((last - first + 1).max(initial=1)), 1)
            lo = np.minimum(np.where(last >= 0, first, 0), self.n - width)
            ends = np.take_along_axis(
                self.run_ends, lo[..., None] + np.arange(width), axis=-1)
            windows = (lo.astype(np.int32), ends)
            for a in windows:
                a.setflags(write=False)
            self._memo["windows"] = windows
        return self._memo["windows"]


def _shard_run_ends(dst_s: np.ndarray, real: np.ndarray,
                    n: int) -> np.ndarray:
    """(S, N) int32 run ends of dst-sorted shards ``dst_s`` (S, E_shard)
    whose first ``real[k]`` slots are real edges; ``-1`` elsewhere. The
    padding after a shard's real slots keeps its last receiver, whose run
    therefore ends at the shard's last slot."""
    out = np.stack([run_ends(d[:w], n) for d, w in zip(dst_s, real)])
    for k, w in enumerate(real):
        if w:
            out[k, dst_s[k, w - 1]] = dst_s.shape[1] - 1
    return out


def partition_edge_list(el: EdgeList, n_shards: int) -> EdgeShards:
    """Cut an edge list into ``n_shards`` dst-contiguous, capacity-padded
    shards for the edge-partitioned (graph-axis) execution mode.

    The index is sorted by destination (an index that is sorted already is
    used as it is), cut at the balanced positions ``k * ceil(E /
    n_shards)`` (cuts may fall mid-segment — the receivers split that way
    are recorded in the ``boundary`` halo index), and the tail is padded
    with inert dst-sorted edges up to the common capacity; the shards are
    then one reshape of the padded index (a view where no padding is
    needed). Batched edge lists partition every topology draw
    independently under one shared capacity, so a whole scenario grid
    rides a single (G, S, E_shard) layout. The arrays returned are
    read-only.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")

    def one(src, dst, valid):
        s_el = _dst_sorted(EdgeList(src=src, dst=dst, n=el.n, valid=valid))
        E = s_el.E
        e_shard = max(-(-E // n_shards), 1)
        pad = n_shards * e_shard - E
        src_s, dst_s, val_s = s_el.src, s_el.dst, s_el.valid
        if pad:
            # tail padding keeps the last real dst so every shard stays
            # sorted; src 0 / valid False keep it inert
            src_s = np.concatenate([src_s, np.zeros(pad, np.int32)])
            dst_s = np.concatenate([dst_s, np.full(pad, dst_s[E - 1],
                                                   np.int32)])
            val_s = np.concatenate([val_s, np.zeros(pad, bool)])
        shape = (n_shards, e_shard)
        src_s, dst_s, val_s = (np.asarray(a).reshape(shape)
                               for a in (src_s, dst_s, val_s))
        # a cut strictly inside a receiver's run marks it boundary
        bnd = np.zeros(el.n, bool)
        lo = np.arange(1, n_shards) * e_shard
        lo = lo[lo < E]
        split = s_el.dst[lo - 1] == s_el.dst[lo]
        bnd[s_el.dst[lo[split]]] = True
        real = np.clip(E - np.arange(n_shards) * e_shard, 0, e_shard)
        return src_s, dst_s, val_s, bnd, _shard_run_ends(dst_s, real, el.n)

    if el.is_batched:
        parts = [one(el.src[g], el.dst[g], el.valid[g])
                 for g in range(el.src.shape[0])]
        src_s, dst_s, val_s, bnd, ends = (np.stack(x) for x in zip(*parts))
    else:
        src_s, dst_s, val_s, bnd, ends = one(el.src, el.dst, el.valid)
    for a in (src_s, dst_s, val_s, bnd, ends):
        a.setflags(write=False)         # see EdgeShards: fixed once cut
    return EdgeShards(src=src_s, dst=dst_s, valid=val_s, n=el.n,
                      e_total=el.E, boundary=bnd, run_ends=ends)


def chord_edge_list(n_bits: int, fingers: int | None = None) -> EdgeList:
    """The Chord overlay (Stoica et al., SIGCOMM 2001, section 4) on a fully
    populated ring of ``N = 2^n_bits`` identifiers, as a dst-sorted
    :class:`EdgeList`.

    Peer ``v``'s i-th finger is ``v + 2^i mod N`` for ``i < fingers``
    (default ``n_bits``, the whole finger table), and every peer pushes to
    its fingers: the in-edges of ``v`` come from ``v - 2^i mod N``. Edge
    ``e = v * L + i`` (``L = fingers``) is ``v - 2^i -> v``, so the index
    is sorted by receiver by construction, every peer has in- and
    out-degree ``L``, and finger 0 (the successor ring) makes the graph
    strongly connected. Built in O(E) with no sort and no per-node loop.
    """
    L = n_bits if fingers is None else fingers
    if not 1 <= L <= n_bits:
        raise ValueError(f"need 1 <= fingers <= n_bits, got {L}, {n_bits}")
    N = 1 << n_bits
    dst = np.repeat(np.arange(N, dtype=np.int32), L)
    src = dst.reshape(N, L) - (np.int32(1) << np.arange(L, dtype=np.int32))
    np.bitwise_and(src, np.int32(N - 1), out=src)            # mod N
    return EdgeList(src=src.reshape(-1), dst=dst, n=N,
                    valid=np.ones(N * L, bool))


def random_strongly_connected_edge_list(
    n: int,
    extra_edges_per_node: float,
    rng: np.random.Generator,
    sort: bool = True,
) -> EdgeList:
    """A random strongly connected digraph built directly as an EdgeList.

    The dense :func:`random_strongly_connected` allocates an (N, N) bool
    adjacency — 17 GB at N = 131072 — so the N ~ 1e5 sweeps construct the
    sparse view directly: a random Hamiltonian cycle (strong-connectivity
    backbone) plus ``round(n * extra_edges_per_node)`` uniform extra edges,
    deduplicated and stripped of self-loops, never touching O(N^2) memory.
    ``sort=True`` (default) returns the edges in the sorted-by-dst layout
    the Pallas backend expects; the XLA backend accepts either order.
    """
    perm = rng.permutation(n).astype(np.int64)
    cyc_src = perm
    cyc_dst = np.roll(perm, -1)
    n_extra = int(round(n * extra_edges_per_node))
    ex_src = rng.integers(0, n, size=n_extra)
    ex_dst = rng.integers(0, n, size=n_extra)
    keep = ex_src != ex_dst
    src = np.concatenate([cyc_src, ex_src[keep]])
    dst = np.concatenate([cyc_dst, ex_dst[keep]])
    # dedupe parallel edges via the flat key src * n + dst (int64-safe)
    _, uniq = np.unique(src * np.int64(n) + dst, return_index=True)
    src, dst = src[uniq], dst[uniq]
    el = EdgeList(
        src=src.astype(np.int32),
        dst=dst.astype(np.int32),
        n=int(n),
        valid=np.ones(src.shape[0], dtype=bool),
    )
    if sort:
        el, _, _ = sort_by_dst(el)
    return el


def hier_edge_list(
    sizes: Sequence[int],
    topology: str = "complete",
    extra_edge_prob: float = 0.3,
    seed: int = 0,
    rep_choice: str = "first",
) -> tuple[EdgeList, np.ndarray]:
    """Hierarchical M-network system built directly as a sparse edge list.

    The dense-free dual of :func:`make_hierarchy`: the same block-diagonal
    topologies ("ring" | "complete" | "ring+"), but emitted as per-block
    edge runs with no (N, N) bool adjacency ever touched — 256 MB at
    N = 16384, 17 GB at N = 131072 — which is what lets the fused
    hierarchical engines (:mod:`repro.core.hps`, :mod:`repro.core.social`)
    run N ~ 1e4-1e5 systems. "ring+" blocks are a random Hamiltonian cycle
    plus ``~extra_edge_prob * n^2`` uniform extra edges (deduplicated) — the
    same cycle-backbone construction as :func:`random_strongly_connected`,
    with a fixed extra-edge count instead of per-pair Bernoulli draws so the
    block never touches an (n, n) array.

    Returns ``(el, rep_mask)``: a dst-sorted :class:`EdgeList` (the layout
    the Pallas consensus kernel expects — rep links to the PS are implicit,
    carried by the (N,) bool representative mask, since the PS fusion is a
    masked reduction, not a set of graph edges) and the mask itself
    (``rep_choice="first"``: first agent of each block, matching
    :func:`make_hierarchy`; ``"random"``: a uniform draw per block).
    """
    rng = np.random.default_rng(seed)
    srcs, dsts = [], []
    off = 0
    offsets = []
    for sz in sizes:
        idx = np.arange(sz, dtype=np.int64)
        if topology == "ring":
            s, d = idx, (idx + 1) % sz
        elif topology == "complete":
            s = np.repeat(idx, sz)
            d = np.tile(idx, sz)
            keep = s != d
            s, d = s[keep], d[keep]
        elif topology == "ring+":
            perm = rng.permutation(sz).astype(np.int64)
            n_extra = int(round(sz * sz * extra_edge_prob))
            ex_s = rng.integers(0, sz, size=n_extra)
            ex_d = rng.integers(0, sz, size=n_extra)
            keep = ex_s != ex_d
            s = np.concatenate([perm, ex_s[keep]])
            d = np.concatenate([np.roll(perm, -1), ex_d[keep]])
            _, uniq = np.unique(s * np.int64(sz) + d, return_index=True)
            s, d = s[uniq], d[uniq]
        else:
            raise ValueError(f"unknown topology {topology!r}")
        srcs.append(off + s)
        dsts.append(off + d)
        offsets.append(off)
        off += int(sz)
    src = np.concatenate(srcs).astype(np.int32)
    dst = np.concatenate(dsts).astype(np.int32)
    el = EdgeList(src=src, dst=dst, n=off,
                  valid=np.ones(src.shape[0], dtype=bool))
    el, _, _ = sort_by_dst(el)
    rep_mask = np.zeros(off, dtype=bool)
    if rep_choice == "first":
        reps = np.asarray(offsets)
    elif rep_choice == "random":
        reps = np.asarray([o + rng.integers(sz)
                           for o, sz in zip(offsets, sizes)])
    else:
        raise ValueError(rep_choice)
    rep_mask[reps] = True
    return el, rep_mask


def block_complete_edge_list(
    sizes: Sequence[int],
) -> tuple[EdgeList, np.ndarray]:
    """Hierarchical system of complete sub-networks, built dense-free.

    The ``topology="complete"`` specialization of :func:`hier_edge_list`,
    kept as the established large-N entry point of the social engine.
    """
    return hier_edge_list(sizes, topology="complete")


def edge_masks(masks: np.ndarray, el: EdgeList) -> np.ndarray:
    """Project a dense (T, N, N) link schedule onto the edge list -> (T, E).

    Used by the sparse<->dense equivalence tests; production sweeps draw
    (T, E) Bernoulli masks directly inside the scan and never materialize
    the dense schedule.
    """
    el._require_single("edge_masks()")
    masks = np.asarray(masks)
    return masks[:, el.src, el.dst] & el.valid[None, :]


# ---------------------------------------------------------------------------
# Padded neighbor lists (receiver-major sparse view)
# ---------------------------------------------------------------------------
#
# The Byzantine gossip core (:mod:`repro.core.byzantine`) trims per *receiver*
# over the set of in-neighbor values, so its natural sparse layout is
# receiver-major: one row of in-neighbor indices per agent, padded to the
# maximum in-degree. An :class:`EdgeList` is the edge-major dual used by
# push-sum's per-link state; a :class:`NeighborList` has no per-edge state at
# all — it is a pure gather index consumed by the trim-gather kernel
# (:mod:`repro.kernels.byz_trim`).

@dataclasses.dataclass(frozen=True)
class NeighborList:
    """Padded in-neighbor lists: slot ``(j, k)`` is the k-th in-neighbor of j.

    ``idx[j, k]`` is a *sender* index (``adj[idx[j, k], j]`` is True for
    valid slots); rows are padded to a common ``deg_max`` with ``idx = 0``,
    ``valid = False`` slots, which consumers mask out before trimming.
    Batched/stacked lists (see :func:`stack_neighbor_lists`) carry a leading
    scenario axis on ``idx``/``valid`` so topology draws with different
    degree profiles can ride one ``jax.vmap`` axis.
    """

    idx: np.ndarray    # (N, deg_max) int32 sender per slot, 0 on padding
    valid: np.ndarray  # (N, deg_max) bool — False on padding slots
    n: int             # number of nodes

    @property
    def deg_max(self) -> int:
        """Padded slot count — last axis, correct for single and batched."""
        return int(self.idx.shape[-1])

    @property
    def is_batched(self) -> bool:
        return self.idx.ndim == 3

    def in_degree(self) -> np.ndarray:
        """In-degree per receiver over valid slots (the trim's ``d_j``)."""
        return self.valid.sum(axis=-1).astype(np.int32)


def neighbor_lists(
    topo_or_adj, deg_max: int | None = None, shuffle_seed: int | None = None
) -> NeighborList:
    """Dense (N, N) bool adjacency (or :class:`HierTopology`) -> padded
    in-neighbor lists.

    Slots are emitted in ascending sender order; ``shuffle_seed`` permutes
    each row's valid slots instead (slot order is irrelevant to trimming —
    the equivalence tests exercise both). ``deg_max`` pads beyond the actual
    maximum in-degree, e.g. to align scenario batches.
    """
    adj = topo_or_adj.adj if isinstance(topo_or_adj, HierTopology) else topo_or_adj
    adj = np.asarray(adj, dtype=bool)
    n = adj.shape[0]
    degs = adj.sum(axis=0)
    dm = int(degs.max()) if degs.size else 0
    if deg_max is not None:
        if deg_max < dm:
            raise ValueError(f"deg_max={deg_max} < actual max in-degree {dm}")
        dm = deg_max
    dm = max(dm, 1)  # keep the slot axis non-empty for edgeless graphs
    rng = None if shuffle_seed is None else np.random.default_rng(shuffle_seed)
    idx = np.zeros((n, dm), dtype=np.int32)
    valid = np.zeros((n, dm), dtype=bool)
    for j in range(n):
        nb = np.nonzero(adj[:, j])[0]
        if rng is not None:
            nb = rng.permutation(nb)
        idx[j, : nb.shape[0]] = nb
        valid[j, : nb.shape[0]] = True
    return NeighborList(idx=idx, valid=valid, n=n)


def stack_neighbor_lists(nls: Sequence[NeighborList]) -> NeighborList:
    """Batch neighbor lists onto a leading scenario axis, padded to the
    widest ``deg_max``; ``n`` must agree across entries."""
    n = nls[0].n
    if any(nl.n != n for nl in nls):
        raise ValueError("all neighbor lists must have the same node count")
    dm = max(nl.deg_max for nl in nls)
    idx = np.zeros((len(nls), n, dm), dtype=np.int32)
    valid = np.zeros((len(nls), n, dm), dtype=bool)
    for g, nl in enumerate(nls):
        idx[g, :, : nl.deg_max] = nl.idx
        valid[g, :, : nl.deg_max] = nl.valid
    return NeighborList(idx=idx, valid=valid, n=n)


# ---------------------------------------------------------------------------
# Packet-drop schedules
# ---------------------------------------------------------------------------

def link_schedule(
    adj: np.ndarray,
    T: int,
    drop_prob: float,
    B: int,
    seed: int = 0,
) -> np.ndarray:
    """(T, N, N) bool operational-link masks with guaranteed B-connectivity.

    Each existing link drops packets i.i.d. with ``drop_prob``, but is forced
    operational at every ``t`` with ``t % B == B - 1`` so the paper's fault
    model ("operational at least once every B iterations") holds exactly.
    """
    rng = np.random.default_rng(seed)
    up = rng.random((T, *adj.shape)) >= drop_prob
    t_idx = np.arange(T) % B == B - 1
    up[t_idx] = True
    return up & adj[None, :, :]
