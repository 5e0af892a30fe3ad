"""Hierarchical Byzantine-resilient non-Bayesian learning — Algorithm 2 / Thm 3.

The curse of dimensionality of vector Byzantine consensus (Remark 1:
tolerable fraction <= 1/(d+1)) is dodged by running one **scalar** dynamic per
ordered hypothesis pair (theta1, theta2). Agent j's pairwise statistic

    r_t^j(t1, t2)

accumulates trimmed-averaged neighbor statistics plus the *cumulative*
log-likelihood ratio of all its private signals so far (Eq. (11); this is why
Lemma 2 normalizes by t^2).

Mechanics per iteration t:
* agents in a network in C (the healthy networks satisfying Assumptions 3+4):
  broadcast r_{t-1}; receivers drop the F largest and F smallest received
  values and average the survivors with their own previous value, then add
  the cumulative LLR innovation (Alg. 2 lines 6-9);
* agents outside C are passive;
* every Gamma iterations the parameter server queries max{2F+1, M} random
  representatives, trims F from each end, averages the rest into w_tilde, and
  pushes w_tilde to the queried representatives that are NOT in C
  (lines 10-22). Borel-Cantelli guarantees every non-C agent is selected
  infinitely often, which is what Theorem 4's proof leans on.

All pairwise dynamics for all (m x m) ordered pairs run simultaneously as a
single (N, m, m) tensor program under jax.lax.scan.

Gossip cores
------------
``core="sparse"`` (default) runs the trim on the padded neighbor-list layout
(:class:`repro.core.graphs.NeighborList`): per receiver, gather the deg_max
in-neighbor statistics, substitute attack values on Byzantine slots, and trim
via :mod:`repro.kernels.byz_trim` — O(N deg_max m^2 F) per step with nothing
larger than (N, deg_max, m^2) live. ``core="dense"`` is the seed lowering —
an (N, N, m, m) message broadcast filtered by :func:`trimmed_neighbor_mean`
— retained purely as the equivalence oracle for tests. Both cores share one
scan body (innovation, PS fusion, PRNG streams), so their trajectories agree
to fp reordering; ``mode="ovr"`` runs the one-vs-rest ablation through the
same body with pair shape (m,) instead of (m, m).

PRNG streams: each iteration consumes three independent streams (private
signal, gossip attack, PS fusion), given disjoint fold-in domains
``t * 3 + stream`` (see :func:`stream_fold`) so no two streams ever share a
fold-in value over any horizon.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .attacks import Attack
from .faults import (
    ENGINE_BYZANTINE,
    FaultModel,
    init_fault_state,
    ps_alive,
    step_faults_nbr,
)
from .graphs import HierTopology, check_assumption3, neighbor_lists
from .precision import Policy, resolve_policy
from .signals import SignalModel
from repro.statics.contracts import contract as statics_contract

__all__ = [
    "ByzantineConfig",
    "ByzantineResult",
    "ByzRuntime",
    "trimmed_neighbor_mean",
    "make_byzantine_runtime",
    "make_byzantine_scan",
    "run_byzantine_learning",
    "run_byzantine_learning_ovr",
    "decide",
    "healthy_networks",
    "stream_fold",
]

MODES = ("pairwise", "ovr")
CORES = ("sparse", "dense")
STORES = ("trajectory", "decisions", "final")

# Per-iteration PRNG streams. Each gets a disjoint fold-in domain
# t * N_STREAMS + stream, so e.g. the signal key at t can never collide with
# the gossip or fusion key of any other iteration (the seed's t / 2t+1 / 2t+2
# scheme aliased signal keys onto both other streams).
N_STREAMS = 3
STREAM_SIGNAL, STREAM_GOSSIP, STREAM_FUSION = range(N_STREAMS)


def stream_fold(t, stream: int):
    """Fold-in value of ``stream`` at iteration ``t`` — injective over
    (t, stream), which is what keeps the three per-iteration streams
    non-colliding over any horizon."""
    return t * N_STREAMS + stream


@dataclasses.dataclass(frozen=True)
class ByzantineConfig:
    topo: HierTopology
    F: int                      # max number of Byzantine agents system-wide
    byz: tuple[int, ...]        # actual compromised agent indices, |byz| <= F
    gamma_period: int           # PS fusion period Γ
    attack: Attack

    def byz_mask(self) -> np.ndarray:
        m = np.zeros(self.topo.N, dtype=bool)
        for b in self.byz:
            m[b] = True
        return m


class ByzantineResult(NamedTuple):
    """Scan output; shapes depend on the ``store`` option.

    ``store="trajectory"`` (default): ``r`` (T, N, m, m), ``decisions``
    (T, N). ``store="decisions"``: ``r`` is the final (N, m, m) only,
    ``decisions`` still (T, N) — the curve without the O(T N m^2) state.
    ``store="final"``: both are final-step only, (N, m, m) / (N,).
    One-vs-rest runs carry pair shape (m, 1) instead of (m, m).
    """

    r: jnp.ndarray
    decisions: jnp.ndarray


# Host-side analysis lattices. Assumption 3's reduced-graph enumeration is
# combinatorial in (block size, F) and healthy_networks re-runs it for every
# sweep call, so both levels are memoized: the per-block A3 verdict keyed by
# (adjacency bytes, F), and the full C set keyed by the (topology, F,
# Byzantine set, model) fingerprint. Sweeps over attack/seed grids then pay
# the analysis exactly once per topology.
_A3_LATTICE: dict[tuple, bool] = {}
_C_SET_LATTICE: dict[tuple, tuple[int, ...]] = {}


def _check_a3_cached(block: np.ndarray, F: int) -> bool:
    key = (block.shape[0], F, block.tobytes())
    hit = _A3_LATTICE.get(key)
    if hit is None:
        hit = _A3_LATTICE[key] = check_assumption3(block, F=F)
    return hit


def healthy_networks(topo: HierTopology, byz_mask: np.ndarray, F: int,
                     model: SignalModel | None = None) -> list[int]:
    """Indices of networks in C.

    A network qualifies iff (A3) every reduced graph has a single source
    component, and (A4) its *normal* agents can jointly distinguish every
    hypothesis pair: sum_j KL_j(l(.|a) || l(.|b)) > 0 for all a != b.
    (A4 is checked over the whole normal set — a necessary condition for
    the per-source-component statement; for the complete graphs we simulate,
    reduced-graph source components contain all but <= 2F normal agents, so
    we additionally require the KL mass not be concentrated on F agents by
    checking the sum with the top-F contributors removed.)

    Results are memoized (see ``_C_SET_LATTICE``): repeated sweep calls on
    the same (topology, F, Byzantine set, model) skip the reduced-graph
    enumeration entirely.
    """
    byz_mask = np.asarray(byz_mask)
    key = (
        topo.adj.tobytes(), topo.sizes, topo.offsets, F, byz_mask.tobytes(),
        None if model is None
        else (np.asarray(model.tables).tobytes(), model.truth),
    )
    hit = _C_SET_LATTICE.get(key)
    if hit is not None:
        return list(hit)
    out = []
    for i in range(topo.M):
        off, sz = topo.offsets[i], topo.sizes[i]
        local_byz = [j - off for j in range(off, off + sz) if byz_mask[j]]
        n_byz = len(local_byz)
        if n_byz * 3 >= sz:  # >= 1/3 compromised cannot satisfy A3 trims
            continue
        if not _check_a3_cached(topo.block(i), F=F):
            continue
        if model is not None and not _check_a4(model, topo, i, byz_mask, F):
            continue
        out.append(i)
    _C_SET_LATTICE[key] = tuple(out)
    return out


def _check_a4(model: SignalModel, topo: HierTopology, i: int,
              byz_mask: np.ndarray, F: int, tol: float = 1e-9) -> bool:
    from .signals import pairwise_kl

    off, sz = topo.offsets[i], topo.sizes[i]
    normal = [j for j in range(off, off + sz) if not byz_mask[j]]
    kl = np.asarray(pairwise_kl(np.asarray(model.tables)))[normal]  # (n,m,m)
    m = kl.shape[1]
    for a in range(m):
        for b in range(m):
            if a == b:
                continue
            contrib = np.sort(kl[:, a, b])       # ascending
            kept = contrib[:-F] if F > 0 else contrib
            if kept.sum() <= tol:                # distinguishers removable
                return False
    return True


def trimmed_neighbor_mean(
    vals: jnp.ndarray,      # (N, N, m, m) — vals[sender, receiver]
    adj: jnp.ndarray,       # (N, N) bool
    F: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-receiver trimmed sum over in-neighbor values (Alg. 2 lines 8-9).

    Returns (trimmed_sum, kept_count): sum over received values after
    dropping the F largest and F smallest, and the number kept, per
    receiver — both (N, m, m) / (N, 1, 1)-broadcastable.

    This is the dense O(N^2 m^2 log N) lowering; production paths run the
    neighbor-list trim in :mod:`repro.kernels.byz_trim` instead, and this
    stays as the equivalence oracle the sparse core is tested against.
    """
    n = vals.shape[0]
    big = jnp.asarray(jnp.finfo(vals.dtype).max / 4, vals.dtype)
    # non-edges -> +inf so they sort to the high end
    masked = jnp.where(adj[:, :, None, None], vals, big)
    s = jnp.sort(masked, axis=0)  # ascending along senders
    deg = adj.sum(axis=0).astype(jnp.int32)  # in-degree per receiver (N,)
    ranks = jnp.arange(n)[:, None]  # (N, 1) rank index along sender axis
    keep = (ranks >= F) & (ranks < (deg[None, :] - F))  # (N, N) rank x receiver
    keepf = keep[:, :, None, None].astype(vals.dtype)
    trimmed_sum = (s * keepf).sum(axis=0)
    kept = keep.sum(axis=0).astype(vals.dtype)  # (N,)
    return trimmed_sum, kept


# ---------------------------------------------------------------------------
# Scan runtime: the per-scenario arrays of one (topology, F, byz set) config
# ---------------------------------------------------------------------------

class ByzRuntime(NamedTuple):
    """Everything the scan body reads that can vary per scenario.

    All fields are arrays, so a batch of *compatible* configs — same
    (N, M, deg_max) after padding — stacks leaf-wise onto one leading
    scenario axis and rides a single ``jax.vmap``
    (:func:`repro.core.sweeps.run_byzantine_grid`). ``F`` and ``gamma`` are
    scalars here precisely so they can be traced per-scenario; the
    single-config path shadows ``F`` with the static Python int (which is
    what lets the Pallas trim kernel unroll its extraction loop).
    """

    nbr_idx: jnp.ndarray    # (N, deg_max) int32 in-neighbor sender per slot
    nbr_valid: jnp.ndarray  # (N, deg_max) bool — False on padding slots
    byz_mask: jnp.ndarray   # (N,) bool
    active: jnp.ndarray     # (N,) bool — normal agents inside C networks
    in_C: jnp.ndarray       # (N,) bool
    offsets: jnp.ndarray    # (M,) int32 network block starts
    sizes: jnp.ndarray      # (M,) int32 network block sizes
    F: jnp.ndarray          # () int32 trim count
    gamma: jnp.ndarray      # () int32 PS fusion period


def _analyze(model: SignalModel, cfg: ByzantineConfig):
    """Host-side healthy-network analysis shared by every scan builder."""
    topo = cfg.topo
    byz_mask_np = cfg.byz_mask()
    C = healthy_networks(topo, byz_mask_np, cfg.F, model)
    if len(C) < cfg.F + 1:
        raise ValueError(
            f"Assumption 5 violated: |C|={len(C)} < F+1={cfg.F + 1}"
        )
    net_of = topo.network_of()
    in_C = np.isin(net_of, C)                      # (N,) agent's network in C
    # gossip runs only inside C networks, between agents of the same network
    same_net = net_of[:, None] == net_of[None, :]
    gossip_adj = topo.adj & same_net & in_C[None, :]   # receivers in C
    active = in_C & ~byz_mask_np                        # normal agents that gossip
    return C, in_C, gossip_adj, active, byz_mask_np


def make_byzantine_runtime(
    model: SignalModel,
    cfg: ByzantineConfig,
    deg_max: int | None = None,
):
    """Host-side setup of one config -> ``(runtime, extra_reps, n_reps,
    gossip_adj)``.

    ``extra_reps`` is ``None`` when the all-networks representative rule
    applies (M >= 2F+1: one rep per network); otherwise it carries the
    static index arrays of the M < 2F+1 branch (reps from every C network
    plus uniform extras from outside C). ``gossip_adj`` is the dense (N, N)
    intra-C adjacency, consumed only by the ``core="dense"`` oracle.
    """
    C, in_C, gossip_adj, active, byz_mask_np = _analyze(model, cfg)
    topo = cfg.topo
    nl = neighbor_lists(gossip_adj, deg_max=deg_max)
    use_all_nets = topo.M >= 2 * cfg.F + 1
    non_C_agents = np.nonzero(~in_C)[0].astype(np.int32)
    if not use_all_nets and len(non_C_agents) == 0:
        # degenerate: every network is healthy — query one rep per network
        use_all_nets = True
    n_reps = topo.M if use_all_nets else 2 * cfg.F + 1
    extra_reps = None if use_all_nets else (
        tuple(int(c) for c in C), tuple(int(a) for a in non_C_agents), n_reps
    )
    rt = ByzRuntime(
        nbr_idx=jnp.asarray(nl.idx),
        nbr_valid=jnp.asarray(nl.valid),
        byz_mask=jnp.asarray(byz_mask_np),
        active=jnp.asarray(active),
        in_C=jnp.asarray(in_C),
        offsets=jnp.asarray(topo.offsets, dtype=jnp.int32),
        sizes=jnp.asarray(topo.sizes, dtype=jnp.int32),
        F=jnp.asarray(cfg.F, dtype=jnp.int32),
        gamma=jnp.asarray(cfg.gamma_period, dtype=jnp.int32),
    )
    return rt, extra_reps, n_reps, gossip_adj


# ---------------------------------------------------------------------------
# Gossip lowerings (Alg. 2 lines 6-9)
# ---------------------------------------------------------------------------

def _sparse_gossip(key, t, r, rt: ByzRuntime, F, *, attack: Attack,
                   mode: str, backend: str, accum_dtype=None):
    """Neighbor-list trim-gather -> (trimmed_sum (N, *pair), kept (N,))."""
    from repro.kernels.byz_trim import trim_gather_pairs

    n = r.shape[0]
    pair = r.shape[1:]
    if attack.nbr_messages is not None:
        bmsg = attack.nbr_messages(key, t, r, rt.nbr_idx).astype(r.dtype)
    else:
        # compatibility fallback for attacks without a sparse form: build
        # the dense point-to-point tensor and gather the needed slots —
        # correct, but reintroduces the O(N^2) intermediate
        full = attack.messages(
            key, t, r if mode == "pairwise" else r[:, :, None]
        )
        if mode == "ovr":
            full = full[..., 0]
        picked = full[rt.nbr_idx, jnp.arange(n)[:, None]]
        bmsg = jnp.broadcast_to(
            picked, rt.nbr_idx.shape + pair
        ).astype(r.dtype)
    byz_nbr = rt.byz_mask[rt.nbr_idx]
    # indices_sorted stays False: the row-major flattening of the padded
    # neighbor-list gather is not dst-monotone
    return trim_gather_pairs(
        r, rt.nbr_idx, rt.nbr_valid, bmsg, byz_nbr, F, backend,
        accum_dtype=accum_dtype,
    )


def _dense_gossip(key, t, r, rt: ByzRuntime, F, *, attack: Attack,
                  mode: str, adj: jnp.ndarray, accum_dtype=None):
    """(N, N) broadcast + sort oracle -> (trimmed_sum, kept)."""
    if accum_dtype is not None:
        r = r.astype(accum_dtype)
    n = r.shape[0]
    pair = r.shape[1:]
    honest = jnp.broadcast_to(r[:, None], (n, n) + pair)
    if mode == "pairwise":
        byz = attack.messages(key, t, r)
    else:
        byz = attack.messages(key, t, r[:, :, None])[..., 0]
    sender = (slice(None), None) + (None,) * len(pair)
    msgs = jnp.where(rt.byz_mask[sender], byz, honest)
    if mode == "pairwise":
        return trimmed_neighbor_mean(msgs, adj, F)
    tsum, kept = trimmed_neighbor_mean(msgs[..., None], adj, F)
    return tsum[..., 0], kept


# ---------------------------------------------------------------------------
# PS fusion (Alg. 2 lines 10-22)
# ---------------------------------------------------------------------------

def _select_reps(key, rt: ByzRuntime, extra_reps):
    """Random representative selection for a fusion round -> (n_reps,) idx."""
    M = rt.offsets.shape[0]
    if extra_reps is None:
        ks = jax.random.split(key, M)
        rint = jax.vmap(lambda k, s: jax.random.randint(k, (), 0, s))
        return (rt.offsets + rint(ks, rt.sizes)).astype(jnp.int32)
    # one rep from each network in C + (2F+1-|C|) uniform from outside C
    C_arr, non_C, n_reps = extra_reps
    ks = jax.random.split(key, len(C_arr) + 1)
    picks = [
        rt.offsets[ci] + jax.random.randint(ks[k], (), 0, rt.sizes[ci])
        for k, ci in enumerate(C_arr)
    ]
    extra = jax.random.choice(
        ks[-1], jnp.asarray(non_C, dtype=jnp.int32),
        shape=(n_reps - len(C_arr),), replace=False,
    )
    return jnp.concatenate([jnp.stack(picks), extra]).astype(jnp.int32)


def _fusion(key, t, r_in, rt: ByzRuntime, F, *, n_reps: int, extra_reps,
            attack: Attack, accum_dtype=None, live=None):
    """PS fusion round: query reps, trim F from each end, push w_tilde back.

    The trimmed-pool average is :func:`repro.core.hps.ps_trimmed_pool` —
    the same masked-segment reduction Algorithm 1's resilient
    :func:`~repro.core.hps.hps_fusion` lowers through, so the two PS-side
    fusion rules share one implementation (accepting a traced F for the
    batched (topology, F) grids).

    ``live`` (an (N,) churn mask, :mod:`repro.core.faults`) degrades the
    round gracefully: dead representatives neither answer the PS query
    (their pool slots are masked out of the trimmed mean) nor adopt the
    pushed-back value. ``live=None`` is the pre-fault program.
    """
    from .hps import ps_trimmed_pool

    pair = r_in.shape[1:]
    sl = (slice(None),) + (None,) * len(pair)
    reps = _select_reps(key, rt, extra_reps)              # (n_reps,)
    rep_vals = r_in[reps]                                 # (n_reps, *pair)
    if attack.nbr_messages is not None:
        reply = attack.nbr_messages(
            key, t, r_in, reps[None, :]
        )[0].astype(r_in.dtype)
    elif len(pair) == 2:
        reply = attack.ps_reply(key, t, r_in)[reps]
    else:
        reply = rep_vals        # no sparse reply defined: state is replayed
    rep_vals = jnp.where(rt.byz_mask[reps][sl], reply, rep_vals)
    pool_valid = (jnp.ones((n_reps,), bool) if live is None
                  else live[reps])
    w = ps_trimmed_pool(rep_vals, pool_valid, F, accum_dtype=accum_dtype)
    # queried reps outside C adopt w_tilde (lines 20-22); the pooled value
    # comes back in the accum slot — downcast so the carry dtype is stable
    adopt = jnp.zeros((r_in.shape[0],), bool).at[reps].set(True) & (~rt.in_C)
    if live is not None:
        adopt = adopt & live
    return jnp.where(adopt[sl], w[None].astype(r_in.dtype), r_in)


# ---------------------------------------------------------------------------
# The shared scan body
# ---------------------------------------------------------------------------

def _scan_core(
    base_key: jnp.ndarray,
    rt: ByzRuntime,
    *,
    gossip,                 # gossip(key, t, r, rt, F) -> (tsum, kept)
    log_tables: jnp.ndarray,
    truth_probs: jnp.ndarray,
    T: int,
    mode: str,
    attack: Attack,
    store: str,
    static_F: int | None,
    extra_reps,
    n_reps: int,
    policy: Policy | None = None,
    faults: FaultModel | None = None,
) -> ByzantineResult:
    """Algorithm 2's scan, parameterized over the gossip lowering and the
    per-scenario runtime arrays (vmappable for batched grids).

    ``policy`` (a resolved :class:`repro.core.precision.Policy` or None)
    sets the dtype of the persistent (N, *pair) carries — the pairwise
    statistic r and the cumulative LLR — with the gossip trim, fusion
    pool, and innovation arithmetic running in the accum slot. ``None``
    keeps the historical all-fp32 program bit-identical.

    ``faults`` (a traced :class:`repro.core.faults.FaultModel` pytree, or
    None for the bit-identical pre-fault program) layers the unified
    fault plane on top of the Byzantine adversary: Gilbert-Elliott bursts
    on the padded neighbor slots (a bad slot drops its gossip message at
    ``drop_bad``), churn (dead agents neither gossip, observe signals,
    nor answer PS queries — r and the cumulative LLR freeze until
    rejoin), and PS crash (fusion rounds skipped while the coordinator
    is down). Fault draws live on their own negative fold-in domain
    (``fault_stream_fold``), provably disjoint from the signal / gossip /
    fusion streams sharing ``base_key``.
    """
    st_dt = jnp.float32 if policy is None else policy.storage_dtype
    ac_dt = jnp.float32 if policy is None else policy.accum_dtype
    N = rt.byz_mask.shape[0]
    m = log_tables.shape[1]
    pair = (m, m) if mode == "pairwise" else (m,)
    sl = (slice(None),) + (None,) * len(pair)
    F = static_F if static_F is not None else rt.F
    cdf = jnp.cumsum(truth_probs, axis=-1)
    eye = jnp.eye(m, dtype=bool)

    def innovation(t):
        """One private signal per agent -> per-pair statistic increment."""
        key = jax.random.fold_in(base_key, stream_fold(t, STREAM_SIGNAL))
        u = jax.random.uniform(key, (N,))
        # searchsorted(side="left") over the inclusive cumsum counts the
        # entries strictly below u — bit-identical to the old compare+reduce
        # but O(log S) per agent and gather-free under vmap
        s_max = cdf.shape[-1] - 1
        sig = jnp.minimum(
            jax.vmap(lambda c, uu: jnp.searchsorted(c, uu, side="left"))(
                cdf, u
            ),
            s_max,
        )
        ll = jnp.take_along_axis(
            log_tables, sig[:, None, None].astype(jnp.int32), axis=2
        )[:, :, 0]                                   # (N, m)
        if mode == "pairwise":
            return ll[:, :, None] - ll[:, None, :]   # (N, m, m) antisymmetric
        rest = jnp.where(eye[None], -jnp.inf, ll[:, None, :])
        return ll - rest.max(axis=-1)                # (N, m) one-vs-rest

    def body(carry, t):
        r, cum_llr = carry[0], carry[1]
        # Each phase of the round runs under its own name scope (the same
        # names as the social scan core's); scopes only label the HLO.
        if faults is not None:
            with jax.named_scope("links"):
                fs, drop = step_faults_nbr(base_key, t, faults, carry[2],
                                           engine=ENGINE_BYZANTINE)
                live = fs.node_live
                # a dropped/bursty slot or a dead endpoint silences the
                # slot; the trim denominator (kept) shrinks with it, so
                # gossip degrades to averaging over whoever delivered
                rt_t = rt._replace(
                    nbr_valid=(rt.nbr_valid & ~drop
                               & live[rt.nbr_idx] & live[:, None]))
        else:
            rt_t = rt

        # ---- innovation accumulator (cumulative LLR of all signals so far)
        # accumulate in the accum slot, carry in storage (every cast below
        # is a traced no-op under the default fp32 policy)
        with jax.named_scope("innovation"):
            cum_new = (cum_llr.astype(ac_dt) + innovation(t)).astype(st_dt)
            if faults is not None:
                # dead agents observe no signals — the accumulator freezes
                cum_new = jnp.where(live[sl], cum_new, cum_llr)
            cum_llr = cum_new

        # ---- intra-C gossip with trimming (lines 6-9)
        with jax.named_scope("gossip"):
            gk = jax.random.fold_in(base_key, stream_fold(t, STREAM_GOSSIP))
            tsum, kept = gossip(gk, t, r, rt_t, F)
            r_gossip = ((tsum + r.astype(ac_dt)) / (kept[sl] + 1.0)
                        + cum_llr.astype(ac_dt))
            r_new = jnp.where(rt.active[sl], r_gossip, r.astype(ac_dt))
            r_new = r_new.astype(st_dt)
            if faults is not None:
                # dead agents neither gossip nor update — stale-state rejoin
                r_new = jnp.where(live[sl], r_new, r)

        # ---- PS fusion every Γ (lines 10-22)
        def fuse(r_in):
            fk = jax.random.fold_in(base_key, stream_fold(t, STREAM_FUSION))
            return _fusion(fk, t, r_in, rt, F, n_reps=n_reps,
                           extra_reps=extra_reps, attack=attack,
                           accum_dtype=None if policy is None
                           else policy.accum,
                           live=None if faults is None else live)

        with jax.named_scope("fusion"):
            is_fusion = (t + 1) % rt.gamma.astype(t.dtype) == 0
            if faults is not None:
                # PS crash: the whole fusion round is skipped — degrade to
                # intra-network consensus instead of pooling through a
                # dead PS
                is_fusion = is_fusion & ps_alive(base_key, t, faults,
                                                 engine=ENGINE_BYZANTINE)
            r_new = jax.lax.cond(is_fusion, fuse, lambda x: x, r_new)

            # Byzantine agents' own state is meaningless; keep it at 0.
            r_new = jnp.where(rt.byz_mask[sl], 0.0, r_new)

        with jax.named_scope("readout"):
            dec = (decide(r_new) if mode == "pairwise"
                   else r_new.argmax(axis=-1))
            if store == "trajectory":
                ys = (r_new, dec)
            elif store == "decisions":
                ys = dec
            else:
                ys = None
        out = (r_new, cum_llr) + (() if faults is None else (fs,))
        return out, ys

    zeros = jnp.zeros((N,) + pair, st_dt)
    carry0 = (zeros, zeros) + (
        () if faults is None
        else (init_fault_state(N, rt.nbr_idx.shape),))
    (r_fin, *_), ys = jax.lax.scan(
        body, carry0, jnp.arange(T, dtype=jnp.uint32)
    )
    # diagnostics leave the engine in fp32 whatever the storage policy
    up = (lambda x: x.astype(jnp.float32)) if st_dt != jnp.float32 else (
        lambda x: x)
    tail = (lambda x: x[..., None]) if mode == "ovr" else (lambda x: x)
    if store == "trajectory":
        return ByzantineResult(r=tail(up(ys[0])), decisions=ys[1])
    if store == "decisions":
        return ByzantineResult(r=tail(up(r_fin)), decisions=ys)
    dec_fin = decide(r_fin) if mode == "pairwise" else r_fin.argmax(axis=-1)
    return ByzantineResult(r=tail(up(r_fin)), decisions=dec_fin)


@statics_contract(
    name="byzantine",
    # Covers the production core="sparse" path ONLY: the dense broadcast
    # oracle exists to materialize (N, N) on purpose and is exempt. The
    # "decisions"/"trajectory" stores legitimately carry (T, N) history,
    # so no horizon pattern is declared.
    forbidden={"*": (("N", "N"),)},
    streams=(
        ("signal", lambda t: stream_fold(t, STREAM_SIGNAL)),
        ("gossip", lambda t: stream_fold(t, STREAM_GOSSIP)),
        ("fusion", lambda t: stream_fold(t, STREAM_FUSION)),
    ),
    caches=("byz.compiled", "byz.grid", "byz.runtime"),
)
def make_byzantine_scan(
    model: SignalModel,
    cfg: ByzantineConfig,
    T: int,
    *,
    mode: str = "pairwise",
    core: str = "sparse",
    backend: str = "auto",
    store: str = "trajectory",
    policy: Policy | str | None = None,
    faults: FaultModel | None = None,
):
    """Build Algorithm 2's scan for a fixed (model, cfg, T).

    All host-side analysis (healthy-network detection, neighbor-list
    construction, representative-set index arrays) runs once here; the
    returned ``run(base_key) -> ByzantineResult`` closure is a pure jax
    function of the PRNG key, so scenario sweeps can ``jax.vmap`` it over a
    batch of seeds (see :func:`repro.core.sweeps.run_byzantine_sweep`) and
    compile one scan for the whole batch.

    ``mode`` selects pairwise (m, m) dynamics or the one-vs-rest (m,)
    ablation; ``core`` the sparse neighbor-list trim (production) or the
    dense broadcast oracle; ``backend`` the sparse trim lowering
    (:mod:`repro.kernels.byz_trim`); ``store`` what the scan materializes
    (see :class:`ByzantineResult`); ``policy`` the precision policy of the
    persistent carries (:mod:`repro.core.precision`; ``None`` keeps the
    bit-identical all-fp32 program); ``faults`` the unified fault plane
    (:mod:`repro.core.faults` — a traced pytree, so fault severity can
    ride the vmap scenario axis; ``None`` keeps the bit-identical
    pre-fault program).
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if core not in CORES:
        raise ValueError(f"core must be one of {CORES}, got {core!r}")
    if store not in STORES:
        raise ValueError(f"store must be one of {STORES}, got {store!r}")
    if faults is not None and core == "dense":
        # the dense oracle gossips through a static (N, N) adjacency and
        # cannot see per-round fault-silenced neighbor slots
        raise ValueError("faults= requires core='sparse'")
    pol = None if policy is None else resolve_policy(policy)
    accum_name = None if pol is None else pol.accum
    rt, extra_reps, n_reps, gossip_adj = make_byzantine_runtime(model, cfg)
    if core == "sparse":
        gossip = functools.partial(
            _sparse_gossip, attack=cfg.attack, mode=mode, backend=backend,
            accum_dtype=accum_name,
        )
    else:
        gossip = functools.partial(
            _dense_gossip, attack=cfg.attack, mode=mode,
            adj=jnp.asarray(gossip_adj), accum_dtype=accum_name,
        )
    run = functools.partial(
        _scan_core,
        rt=rt,
        gossip=gossip,
        log_tables=model.log_tables().astype(jnp.float32),
        truth_probs=model.tables[:, model.truth, :].astype(jnp.float32),
        T=T,
        mode=mode,
        attack=cfg.attack,
        store=store,
        static_F=cfg.F,
        extra_reps=extra_reps,
        n_reps=n_reps,
        policy=pol,
        faults=faults,
    )
    return run


def run_byzantine_learning(
    model: SignalModel,
    cfg: ByzantineConfig,
    T: int,
    seed: int = 0,
    **scan_kwargs,
) -> ByzantineResult:
    """Run Algorithm 2 for T iterations (single scenario).

    Keyword arguments (``mode``, ``core``, ``backend``, ``store``,
    ``policy``) pass through to :func:`make_byzantine_scan`.
    """
    return make_byzantine_scan(model, cfg, T, **scan_kwargs)(
        jax.random.PRNGKey(seed)
    )


def run_byzantine_learning_ovr(
    model: SignalModel,
    cfg: ByzantineConfig,
    T: int,
    seed: int = 0,
    **scan_kwargs,
) -> ByzantineResult:
    """One-vs-rest variant of Algorithm 2 (extension; DESIGN.md §8).

    The paper runs one scalar dynamic per ORDERED hypothesis pair — m(m-1)
    dynamics. For large m, the standard reduction runs m dynamics on the
    one-vs-rest statistics r^j(theta) accumulating
    log l(s|theta) - max_{theta' != theta} log l(s|theta'). Same trimming,
    same fusion rule, m/(m-1) times cheaper; the pairwise guarantee of
    Theorem 3 does not transfer verbatim (the OVR innovation is not
    antisymmetric), so this is benchmarked as an ablation, not claimed.

    Returns a ByzantineResult whose ``r`` has shape (T, N, m, 1).
    """
    scan_kwargs.setdefault("mode", "ovr")
    return run_byzantine_learning(model, cfg, T, seed, **scan_kwargs)


def decide(r: jnp.ndarray) -> jnp.ndarray:
    """Decision rule: theta_hat = argmax_a min_{b != a} r(a, b).

    Theorem 3 guarantees a unique hypothesis whose pairwise statistics all
    diverge to +inf; with antisymmetric innovations that is theta*.
    r: (..., m, m) -> (...,) int decisions.
    """
    m = r.shape[-1]
    eye = jnp.eye(m, dtype=bool)
    masked = jnp.where(eye, jnp.inf, r)
    worst = masked.min(axis=-1)
    return worst.argmax(axis=-1)
