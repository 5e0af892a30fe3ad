"""Non-Bayesian learning over packet-dropping links — Algorithm 3 / Theorem 2.

"Consensus + innovation": interleave one HPS step (on the per-hypothesis
log-likelihood accumulator ``z in R^m`` and the mass ``m``) with the local
innovation ``z(theta) += log l(s_t | theta)`` and the dual-averaging belief
update with KL-divergence proximal, whose closed form is

    mu_j(theta, t)  =  softmax( z_j(., t) / m_j(t) )        (uniform prior)

Per Algorithm 3 ordering: consensus (lines 4-12) -> innovation (13-15) ->
belief (16) -> PS fusion every Gamma (17-22).

The fused, batched engine
-------------------------
The scan body is split into the two per-iteration hot halves, each behind
the repo-wide ``backend="auto"|"xla"|"pallas"`` switch:

* **consensus** — the sparse edge-list push-sum core
  (:mod:`repro.core.pushsum`): ``rho`` is (E, m) over the topology's
  directed edges, each round's (E,) operational mask is drawn inside the
  scan, and delivery + integration run through
  :mod:`repro.kernels.pushsum_edge` (fused gather/mask-latch/segment-sum
  over the dst-sorted edge index). Memory is O(N m + E m); no (T, N, N)
  schedule or (N, N, m) relay tensor ever exists.
* **innovation + belief** — :mod:`repro.kernels.social_innov`: inverse-CDF
  signal sampling, the (N, m) log-likelihood gather, ``z += loglik``, and
  the softmax belief in ONE streaming pass over agent blocks instead of
  five separate XLA ops with (N, S) intermediates per step.

Every loop invariant is hoisted out of the scan: the truth-row CDF (the
seed path recomputed ``jnp.cumsum(truth_probs)`` every iteration), the log
tables, the representative mask, and the out-degree share factors of the
fixed edge index. Per-agent uniforms are one ``jax.random.uniform(key,
(N,))`` draw (the seed path split N keys and vmapped scalar draws).

All per-scenario inputs live in a :class:`SocialRuntime` of *arrays*
(``drop_prob``/``gamma``/``B`` are traced scalars), so a batch of
compatible scenarios stacks leaf-wise and rides one ``jax.vmap`` axis —
see :func:`repro.core.sweeps.run_social_sweep` /
:func:`repro.core.sweeps.run_social_grid` for the batched (and
mesh-sharded) engines built on :func:`_social_scan_core`.

``store`` selects what the scan materializes — ``"trajectory"`` the full
(T, N, m) belief + log-ratio histories, ``"log_ratio"`` the in-scan-reduced
(T,) worst log-ratio curve (Theorem 2's LHS) plus final beliefs, and
``"final"`` final beliefs only — so long horizons never carry O(T N m)
out of the scan unless asked to.

PRNG streams: each iteration consumes two independent streams (link masks,
private signals) with disjoint fold-in domains ``t * 2 + stream``
(:func:`social_stream_fold`), so ``seed == signal_seed`` no longer aliases
the two streams (the seed scheme folded plain ``t`` into both base keys).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .asyncrony import (
    AsyncModel,
    init_async_buffer,
    is_degenerate_async,
    wake_mask,
)
from .faults import (
    ENGINE_SOCIAL,
    FaultModel,
    edge_uniforms,
    faulty_edge_mask,
    freeze,
    init_fault_state,
    ps_alive,
    step_faults,
)
from .graphs import EdgeList, run_ends
from .hps import HPSConfig, hps_fusion
from .plan import ExecutionPlan, resolve_plan
from .precision import Policy, resolve_policy
from .pushsum import (
    SparsePushSumState,
    _out_degree,
    init_sparse_state,
    shard_edge_mask,
    sparse_pushsum_step,
    step_edge_mask,
)
from .signals import SignalModel
from repro.statics.contracts import contract as statics_contract
from repro.statics.retrace import register_cache as register_statics_cache

__all__ = [
    "SocialLearningResult",
    "SocialRuntime",
    "SOCIAL_STORES",
    "N_SOCIAL_STREAMS",
    "STREAM_LINK",
    "STREAM_SIGNAL",
    "social_stream_fold",
    "kl_dual_averaging_update",
    "make_social_runtime",
    "social_runtime_from_edge_list",
    "stack_runtimes",
    "run_social_learning",
    "run_social_runtime",
    "theorem2_rate",
]

SOCIAL_STORES = ("trajectory", "log_ratio", "final")

# Belief floor for the log-ratio: the smallest NORMAL fp32. The seed path
# floored at 1e-38, which is subnormal — XLA CPU flushes subnormal log
# inputs to zero, so a fully-converged wrong-hypothesis belief (mu == 0)
# yielded log(-inf) and a NaN truth-column ratio at high drop rates.
_MU_FLOOR = np.float32(np.finfo(np.float32).tiny)

# Per-iteration PRNG streams, disjoint fold-in domains t * N_STREAMS + s
# (same scheme as repro.core.byzantine.stream_fold): the link-mask draw at
# iteration t can never collide with the signal draw of any iteration even
# when both streams are rooted at the same base key (seed == signal_seed).
N_SOCIAL_STREAMS = 2
STREAM_LINK, STREAM_SIGNAL = range(N_SOCIAL_STREAMS)


def social_stream_fold(t, stream: int):
    """Fold-in value of ``stream`` at iteration ``t`` — injective over
    (t, stream), which is what keeps the two per-iteration streams
    non-colliding over any horizon."""
    return t * N_SOCIAL_STREAMS + stream


class SocialLearningResult(NamedTuple):
    """Engine output; shapes depend on the ``store`` option.

    ``store="trajectory"`` (default): ``beliefs`` (T, N, m), ``log_ratio``
    (T, N, m) — log mu(theta)/mu(theta*), Theorem 2's LHS.
    ``store="log_ratio"``: ``beliefs`` is the final (N, m) only and
    ``log_ratio`` the (T,) worst-case curve max_{j, theta != theta*}
    log mu_j(theta)/mu_j(theta*), reduced inside the scan.
    ``store="final"``: both final-step only, (N, m) each.
    """

    beliefs: jnp.ndarray
    final_state: SparsePushSumState  # edge-list consensus state at T
    log_ratio: jnp.ndarray


class SocialRuntime(NamedTuple):
    """Everything the scan body reads that can vary per scenario.

    All fields are arrays, so a batch of *compatible* scenarios — same
    (N, M) and edge lists padded to a common E — stacks leaf-wise onto one
    leading scenario axis and rides a single ``jax.vmap``
    (:func:`repro.core.sweeps.run_social_grid`). ``drop_prob``, ``gamma``
    and ``B`` are scalars here precisely so they can be traced
    per-scenario: the fusion schedule ``(t + 1) % gamma == 0`` and the
    B-window forced delivery are computed in-scan from the traced values,
    keeping ONE compiled program for the whole (drop x Gamma x topology)
    grid. ``seg_end`` is set only for a dst-sorted edge index; a batch
    stacks it only if every scenario has it (:func:`stack_runtimes`).
    """

    src: jnp.ndarray        # (E,) int32 sender per edge (dst-sorted layout)
    dst: jnp.ndarray        # (E,) int32 receiver per edge
    valid: jnp.ndarray      # (E,) bool — False on padding edges
    rep_mask: jnp.ndarray   # (N,) bool — designated representatives
    drop_prob: jnp.ndarray  # () f32 per-link packet-drop probability
    gamma: jnp.ndarray      # () i32 PS fusion period
    B: jnp.ndarray          # () i32 link-reliability window
    # (N,) i32 each receiver's last in-edge (-1: none) when the edge index
    # is dst-sorted, else None: the consensus step's run-end gather finish
    seg_end: jnp.ndarray | None = None


def kl_dual_averaging_update(z: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """The KL-proximal dual-averaging projection, closed form.

    argmin_{mu in simplex} { -<z/m, mu> + D_KL(mu || mu_0) }  =  softmax(z/m)
    for the uniform prior mu_0. z: (N, m_hyp), m: (N,).
    """
    return jax.nn.softmax(z / jnp.maximum(m, 1e-30)[:, None], axis=-1)


def social_runtime_from_edge_list(
    el: EdgeList,
    rep_mask: np.ndarray,
    *,
    drop_prob: float,
    gamma_period: int,
    B: int = 1,
    e_max: int | None = None,
) -> SocialRuntime:
    """Build a :class:`SocialRuntime` directly from a sparse edge index.

    The dense-free entry point for large-N systems (pair with
    :func:`repro.core.graphs.block_complete_edge_list` — no (N, N)
    adjacency is ever touched). ``el`` should be dst-sorted
    (:func:`graphs.sort_by_dst`) for the Pallas consensus backend; the XLA
    backend accepts any order. ``e_max`` pads the edge axis (inert
    ``valid=False`` edges with ``dst = N - 1``, which keeps a sorted layout
    sorted) so scenario batches over different topologies can share one
    shape. A dst-sorted (padded) index also gets its receivers' run ends
    (``seg_end``, :func:`repro.core.graphs.run_ends`), which let the Pallas
    consensus step finish with a gather; an unsorted one is left as it is.
    """
    if el.is_batched:
        raise ValueError("pass one topology draw; batching happens leaf-wise")
    src, dst, valid = el.src, el.dst, el.valid
    if e_max is not None:
        pad = e_max - el.E
        if pad < 0:
            raise ValueError(f"e_max={e_max} < edge count {el.E}")
        src = np.concatenate([src, np.zeros(pad, np.int32)])
        dst = np.concatenate([dst, np.full(pad, el.n - 1, np.int32)])
        valid = np.concatenate([valid, np.zeros(pad, bool)])
    seg_end = run_ends(dst, el.n)
    return SocialRuntime(
        src=jnp.asarray(src, jnp.int32),
        dst=jnp.asarray(dst, jnp.int32),
        valid=jnp.asarray(valid, bool),
        rep_mask=jnp.asarray(np.asarray(rep_mask, bool)),
        drop_prob=jnp.asarray(drop_prob, jnp.float32),
        gamma=jnp.asarray(gamma_period, jnp.int32),
        B=jnp.asarray(B, jnp.int32),
        seg_end=None if seg_end is None else jnp.asarray(seg_end, jnp.int32),
    )


def stack_runtimes(runtimes) -> SocialRuntime:
    """Stack compatible runtimes leaf-wise onto a leading scenario axis.
    Unless every runtime has its run ends, none keeps them, so the batch
    runs one program."""
    if any(rt.seg_end is None for rt in runtimes):
        runtimes = [rt._replace(seg_end=None) for rt in runtimes]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *runtimes)


def make_social_runtime(cfg: HPSConfig, e_max: int | None = None) -> SocialRuntime:
    """Host-side setup of one :class:`~repro.core.hps.HPSConfig` scenario."""
    return social_runtime_from_edge_list(
        cfg.edge_index(),
        cfg.topo.rep_mask(),
        drop_prob=cfg.drop_prob,
        gamma_period=cfg.gamma_period,
        B=cfg.B,
        e_max=e_max,
    )


# ---------------------------------------------------------------------------
# The shared scan core
# ---------------------------------------------------------------------------

@statics_contract(
    name="social",
    # Dense-free everywhere; the in-scan-reducing stores must additionally
    # never materialize a rank>=2 horizon-major value (the (T,) reduced
    # curves are the POINT of those stores and stay allowed).
    forbidden={
        "*": (("N", "N"),),
        "final": (("T", "*"),),
        "log_ratio": (("T", "*"),),
    },
    streams=(
        ("link", lambda t: social_stream_fold(t, STREAM_LINK)),
        ("signal", lambda t: social_stream_fold(t, STREAM_SIGNAL)),
    ),
    caches=("social.compiled", "social.runtime", "social.jit"),
)
def _social_scan_core(
    mask_key: jnp.ndarray,
    sig_key: jnp.ndarray,
    rt: SocialRuntime,
    log_tables: jnp.ndarray,  # (N, m, S) hoisted log-likelihood tables
    cdf: jnp.ndarray,         # (N, S) hoisted truth-row inclusive cumsum
    *,
    truth: int,
    M: int,
    T: int,
    store: str,
    backend: str,
    graph_axis: str | None = None,
    n_shards: int = 1,
    policy: Policy | str | None = None,
    dst_sorted: bool = False,
    halo: str = "psum",
    faults: FaultModel | None = None,
    async_: AsyncModel | None = None,
) -> tuple[SparsePushSumState, tuple[jnp.ndarray, jnp.ndarray]]:
    """Algorithm 3's scan, parameterized over the per-scenario runtime
    arrays (vmappable for batched grids).

    Returns ``(final_state, (beliefs, log_ratio))`` with the store-dependent
    shapes of :class:`SocialLearningResult`.

    ``graph_axis``/``n_shards`` run the consensus half edge-partitioned
    exactly as in :func:`repro.core.hps._hps_scan_core`: the runtime's edge
    arrays carry a per-device (E_shard,) shard, the link-mask stream is
    windowed from the full padded draw on the same fold-in domain, and the
    out-degree / receiver partials are psum'd over the mesh graph axis
    (``halo="scatter"`` swaps the psum pair for the reduce-scatter +
    quantize + all-gather combine of :func:`sparse_pushsum_step`). The
    innovation and fusion halves touch only replicated (N, ...) node state
    and need no changes.

    ``policy`` (:mod:`repro.core.precision`) puts every persistent scan
    value — the push-sum state AND the final-belief carry — in the storage
    dtype while the innovation accumulation, fusion pools, and belief
    softmax run in the accum dtype. ``dst_sorted=True`` asserts the
    runtime's edge index is dst-sorted (true for everything built from
    ``HPSConfig.edge_index()``; user-supplied runtimes default to False).
    A runtime with run ends (``rt.seg_end``) finishes the Pallas consensus
    step with the run-end gather on a single device without async mode;
    every other path does not read them.
    All of these kwargs are trace statics — except ``faults``, a TRACED
    :class:`repro.core.faults.FaultModel` pytree riding the vmap scenario
    axis: bursty Gilbert-Elliott links, churn (dead agents neither gossip
    nor observe signals — consensus state, accumulator, and belief all
    freeze until rejoin), and PS crash (fusion rounds skipped while the
    coordinator is down). ``faults=None`` emits the bit-identical
    pre-fault program.

    ``async_`` — also a TRACED pytree (:class:`repro.core.asyncrony
    .AsyncModel`) on the vmap scenario axis — runs the event-driven
    mode: the consensus half steps blocks of concurrent wakeups with
    per-edge bounded stale buffers (an O(E·m) extra carry), asleep
    agents observe no signal (accumulator and belief freeze like the
    churn path, which is why the final-belief carry is forced on), and
    the PS fusion stays on the synchronous global Γ clock — the
    parameter server polls its representatives regardless of their
    gossip clocks. Wake coins ride the engine's async-wake stream
    (:func:`repro.core.asyncrony.async_stream_fold`), disjoint from the
    link/signal/fault folds. Composes with ``faults``; incompatible
    with ``graph_axis`` edge partitioning.
    """
    from repro.kernels.social_innov import innovation_step

    if async_ is not None and graph_axis is not None:
        raise ValueError(
            "async mode does not compose with graph_axis edge partitioning"
        )
    pol = None if policy is None else resolve_policy(policy)
    st_dt = jnp.float32 if pol is None else pol.storage_dtype
    accum_name = None if pol is None else pol.accum
    N, m = log_tables.shape[0], log_tables.shape[1]
    E = rt.src.shape[0]
    # z accumulates per-hypothesis log-likelihood sums; init 0 (Alg. 3 line 1)
    state0 = init_sparse_state(jnp.zeros((N, m), jnp.float32), E,
                               policy=policy)
    # loop invariants of the fixed edge index, hoisted out of the scan
    d_out = _out_degree(rt.src, rt.valid, N, jnp.float32)
    if graph_axis is not None:
        d_out = jax.lax.psum(d_out, graph_axis)
    share = 1.0 / (d_out + 1.0)

    # the trajectory store emits every belief through ys, so only the other
    # stores need the final mu threaded through the carry (storage dtype —
    # under a bf16 policy no fp32 (N, m) value may persist across rounds).
    # The fault and async planes always carry mu: a dead or asleep agent's
    # belief freezes to its last live value, which must therefore survive
    # in the carry.
    carry_mu = store != "trajectory" or faults is not None \
        or async_ is not None
    # carry layout: (state,) [+ mu] [+ abuf] [+ fault_state]
    abuf_idx = 1 + int(carry_mu)

    def body(carry, t):
        state = carry[0]
        # Each phase of the round runs under its own name scope (the same
        # names as the Byzantine scan core's); scopes only label the HLO.
        # --- links: the fault plane and this round's operational edges ---
        with jax.named_scope("links"):
            if faults is not None:
                fs = step_faults(mask_key, t, faults, carry[-1],
                                 engine=ENGINE_SOCIAL,
                                 graph_axis=graph_axis, n_shards=n_shards)
                # drop uniform stays on the social link stream (degenerate
                # model == step_edge_mask values draw-for-draw)
                u_e = edge_uniforms(
                    mask_key, social_stream_fold(t, STREAM_LINK), E,
                    graph_axis=graph_axis, n_shards=n_shards)
                mask = faulty_edge_mask(u_e, t, faults, fs, rt.src, rt.dst,
                                        rt.drop_prob, rt.B)
            elif graph_axis is not None:
                mask = shard_edge_mask(
                    mask_key, t, E, rt.drop_prob, rt.B,
                    graph_axis=graph_axis, n_shards=n_shards,
                    fold_t=social_stream_fold(t, STREAM_LINK),
                )
            else:
                mask = step_edge_mask(
                    mask_key, t, E, rt.drop_prob, rt.B,
                    fold_t=social_stream_fold(t, STREAM_LINK),
                )
            if async_ is not None:
                awake = wake_mask(mask_key, t, N, async_.wake_prob,
                                  engine=ENGINE_SOCIAL)
        # --- consensus (lines 4-12) ---
        with jax.named_scope("consensus"):
            if async_ is not None:
                st, abuf = sparse_pushsum_step(
                    state, mask, rt.src, rt.dst, rt.valid, backend,
                    share=share, dst_sorted=dst_sorted, policy=policy,
                    faults=None if faults is None else fs,
                    awake=awake, abuf=carry[abuf_idx],
                    staleness=async_.staleness,
                )
            else:
                st = sparse_pushsum_step(
                    state, mask, rt.src, rt.dst, rt.valid, backend,
                    share=share, graph_axis=graph_axis,
                    dst_sorted=dst_sorted, policy=policy, halo=halo,
                    n_shards=n_shards,
                    faults=None if faults is None else fs,
                    # run ends index the whole edge list, not a shard's
                    seg_end=rt.seg_end if graph_axis is None else None,
                )
        # --- innovation + belief (lines 13-16), one fused pass ---
        with jax.named_scope("innovation"):
            sk = jax.random.fold_in(sig_key,
                                    social_stream_fold(t, STREAM_SIGNAL))
            u = jax.random.uniform(sk, (N,))
            z, mu = innovation_step(st.z, st.m, u, cdf, log_tables, backend,
                                    accum_dtype=accum_name)
            if async_ is not None:
                # asleep agents observe nothing: accumulator and belief
                # stay at their frozen values until the next wake
                z = freeze(awake, z, st.z)
                mu = freeze(awake, mu, carry[1].astype(mu.dtype))
            if faults is not None:
                # dead agents observe nothing: the accumulator stays at its
                # frozen post-consensus value and the belief stays stale
                z = freeze(fs.node_live, z, st.z)
                mu = freeze(fs.node_live, mu, carry[1].astype(mu.dtype))
        # --- PS fusion every Γ (lines 17-22), applied post-innovation ---
        with jax.named_scope("fusion"):
            z_f, m_f = hps_fusion(z, st.m, rt.rep_mask, M,
                                  accum_dtype=accum_name,
                                  live=None if faults is None
                                  else fs.node_live)
            do_fusion = (t + 1) % rt.gamma == 0
            if faults is not None:
                # PS crash: skip the fusion round, degrade to local
                # consensus
                do_fusion = do_fusion & ps_alive(mask_key, t, faults,
                                                 engine=ENGINE_SOCIAL)
            new = st._replace(
                z=jnp.where(do_fusion, z_f, z),
                m=jnp.where(do_fusion, m_f, st.m),
            )
        with jax.named_scope("readout"):
            if store == "trajectory":
                ys = mu
            elif store == "log_ratio":
                log_mu = jnp.log(jnp.maximum(mu, _MU_FLOOR))
                lr = log_mu - log_mu[:, truth : truth + 1]
                wrong = jnp.where(jnp.arange(m) == truth, -jnp.inf, lr)
                ys = wrong.max()      # () worst wrong-hypothesis log ratio
            else:
                ys = None
        out = (new,) + ((mu.astype(st_dt),) if carry_mu else ())
        if async_ is not None:
            out = out + (abuf,)
        if faults is not None:
            out = out + (fs,)
        return out, ys

    carry0 = (state0,) + (
        (jnp.zeros((N, m), st_dt),) if carry_mu else ())
    if async_ is not None:
        carry0 = carry0 + (init_async_buffer(E, m, state0.z.dtype),)
    if faults is not None:
        carry0 = carry0 + (init_fault_state(N, E),)
    (final, *rest), ys = jax.lax.scan(
        body, carry0, jnp.arange(T, dtype=jnp.int32)
    )
    if store == "trajectory":
        log_mu = jnp.log(jnp.maximum(ys, _MU_FLOOR))
        return final, (ys, log_mu - log_mu[:, :, truth : truth + 1])
    mu_fin = rest[0]
    if mu_fin.dtype != jnp.float32:
        mu_fin = mu_fin.astype(jnp.float32)   # diagnostics stay full width
    if store == "log_ratio":
        return final, (mu_fin, ys)
    log_mu = jnp.log(jnp.maximum(mu_fin, _MU_FLOOR))
    return final, (mu_fin, log_mu - log_mu[:, truth : truth + 1])


# Module-level jit so repeated runs with the same shapes/statics hit the
# compilation cache instead of retracing a fresh closure per call.
_social_compiled = functools.partial(
    jax.jit,
    static_argnames=("truth", "M", "T", "store", "backend", "graph_axis",
                     "n_shards", "policy", "dst_sorted", "halo"),
)(_social_scan_core)
register_statics_cache("social.jit", _social_compiled._cache_size)


def run_social_runtime(
    model: SignalModel,
    rt: SocialRuntime,
    M: int,
    T: int,
    seed: int = 0,
    signal_seed: int | None = None,
    *,
    plan: ExecutionPlan | None = None,
    **legacy,
) -> SocialLearningResult:
    """Run Algorithm 3 on a prebuilt :class:`SocialRuntime`.

    The dense-free entry point (see :func:`social_runtime_from_edge_list`);
    :func:`run_social_learning` is the :class:`~repro.core.hps.HPSConfig`
    convenience wrapper. ``signal_seed`` defaults to ``seed`` — the two
    streams stay independent either way thanks to the disjoint fold-in
    domains, and the batched sweeps drive both streams from one
    per-scenario seed.

    Execution knobs ride ``plan=`` (:class:`repro.core.plan.ExecutionPlan`;
    loose ``backend=``/``store=``/``policy=``/``dst_sorted=``/``faults=``
    kwargs are deprecated shims folding into a plan bit-identically).
    ``plan.store=None`` means ``"trajectory"``. ``plan.dst_sorted``
    defaults to False because a user-built runtime may carry any edge
    order; the config-driven wrappers pass True
    (``HPSConfig.edge_index()`` is always dst-sorted). A concretely
    degenerate ``plan.async_`` dispatches to the synchronous program
    (bit-identity by construction — see :mod:`repro.core.asyncrony`).
    """
    # host spans: the argument preparation, then the dispatch of the
    # compiled scan (which returns before the device finishes)
    with TraceAnnotation("repro.social.prepare"):
        plan = resolve_plan(
            plan, _entry="run_social_runtime",
            _supports=("backend", "store", "policy", "dst_sorted", "faults",
                       "async_"),
            **legacy)
        store = "trajectory" if plan.store is None else plan.store
        if store not in SOCIAL_STORES:
            raise ValueError(
                f"store must be one of {SOCIAL_STORES}, got {store!r}")
        async_ = None if is_degenerate_async(plan.async_) else plan.async_
        policy = None if plan.policy is None else resolve_policy(plan.policy)
        truth_probs = model.tables[:, model.truth, :].astype(jnp.float32)
        args = (
            jax.random.PRNGKey(seed),
            jax.random.PRNGKey(seed if signal_seed is None else signal_seed),
            rt,
            model.log_tables().astype(jnp.float32),
            jnp.cumsum(truth_probs, axis=-1),
        )
    with TraceAnnotation("repro.social.dispatch"):
        final, (beliefs, log_ratio) = _social_compiled(
            *args,
            truth=model.truth,
            M=M,
            T=T,
            store=store,
            backend=plan.backend,
            policy=policy,
            dst_sorted=plan.dst_sorted,
            faults=plan.faults,
            async_=async_,
        )
    return SocialLearningResult(
        beliefs=beliefs, final_state=final, log_ratio=log_ratio
    )


def run_social_learning(
    model: SignalModel,
    cfg: HPSConfig,
    T: int,
    seed: int = 0,
    signal_seed: int = 100,
    *,
    plan: ExecutionPlan | None = None,
    **legacy,
) -> SocialLearningResult:
    """Run Algorithm 3 for T iterations (single scenario).

    ``seed`` drives the per-round link masks (drawn edge-wise inside the
    scan with :func:`pushsum.step_edge_mask` — same drop_prob/B semantics as
    :func:`graphs.link_schedule`); ``signal_seed`` drives private signals.
    The two streams use disjoint fold-in domains, so any (seed,
    signal_seed) pair — including equal values — yields independent masks
    and signals. Execution knobs ride ``plan=``
    (:class:`repro.core.plan.ExecutionPlan`; loose kwargs are deprecated
    shims): ``plan.backend`` selects the consensus + innovation lowerings
    (module docstring); ``plan.store`` what the scan materializes
    (:class:`SocialLearningResult`; ``None`` = ``"trajectory"``);
    ``plan.policy`` the storage/compute/accum dtype split
    (:mod:`repro.core.precision`); ``plan.faults`` / ``plan.async_`` the
    fault and event-driven planes.
    """
    plan = resolve_plan(
        plan, _entry="run_social_learning",
        _supports=("backend", "store", "policy", "faults", "async_"),
        **legacy)
    return run_social_runtime(
        model, make_social_runtime(cfg), cfg.topo.M, T,
        seed=seed, signal_seed=signal_seed,
        plan=plan.replace(dst_sorted=True),
    )


def theorem2_rate(model: SignalModel, topo_N: int) -> np.ndarray:
    """The linear decay slopes -D_KL(theta*||theta)/N of Theorem 2, (m,)."""
    from .signals import pairwise_kl

    kl = pairwise_kl(np.asarray(model.tables))  # (N, m, m) per-agent
    total = kl.sum(axis=0)  # (m, m): joint KL because signals are independent
    return -total[model.truth] / topo_N
