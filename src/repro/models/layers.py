"""Model building blocks, pure JAX (pjit/GSPMD-friendly).

Every block is a pair of functions: ``init_<block>(key, cfg) -> params`` and
``<block>(params, x, ...) -> y``. Params are nested dicts of jnp arrays so
they stack cleanly for lax.scan over layers and map 1:1 onto PartitionSpecs
in ``repro.distributed.sharding``.

Numerics: matmul weights are stored in ``cfg.dtype`` (bf16 on TPU); all
norm/softmax/recurrence accumulations are float32.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# initializers / norms / activations
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    if len(shape) == 3:  # (E, d, f) expert weights: fan-in is the middle dim
        fan_in = shape[1]
    std = scale if scale is not None else fan_in**-0.5
    return (jax.random.normal(key, shape) * std).astype(dtype)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(
        x.dtype
    )


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def init_norm(key, cfg: ArchConfig, d: int | None = None) -> Params:
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((d,), _dt(cfg))}
    return {"scale": jnp.ones((d,), _dt(cfg)), "bias": jnp.zeros((d,), _dt(cfg))}


def apply_norm(p: Params, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    if "bias" in p:
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


def _dt(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, dh); positions: (B, S) or (S,)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (dh/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # (B,S,dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional qk-norm, optional sliding window)
# ---------------------------------------------------------------------------

def _n_heads_eff(cfg: ArchConfig) -> int:
    """Query head count incl. TP zero-padding (pad_heads_to).

    Padded heads carry zero wq columns and zero wo rows: their attention
    output is multiplied by zeros, so the math is EXACTLY the unpadded
    model — but every tensor dim is now divisible by the model axis
    (EXPERIMENTS.md §Perf, minitron prefill iteration)."""
    return max(cfg.n_heads, cfg.pad_heads_to or 0)


def init_attention(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 6)
    d, hd, Hkv = cfg.d_model, cfg.head_dim, cfg.n_kv_heads
    H = cfg.n_heads
    Hp = _n_heads_eff(cfg)
    wq = _dense_init(ks[0], (d, H * hd), _dt(cfg))
    wo = _dense_init(ks[3], (H * hd, d), _dt(cfg))
    if Hp > H:
        wq = jnp.concatenate(
            [wq, jnp.zeros((d, (Hp - H) * hd), wq.dtype)], axis=1
        )
        wo = jnp.concatenate(
            [wo, jnp.zeros(((Hp - H) * hd, d), wo.dtype)], axis=0
        )
    p = {
        "wq": wq,
        "wk": _dense_init(ks[1], (d, Hkv * hd), _dt(cfg)),
        "wv": _dense_init(ks[2], (d, Hkv * hd), _dt(cfg)),
        "wo": wo,
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), _dt(cfg))
        p["k_norm"] = jnp.zeros((hd,), _dt(cfg))
    return p


def _qk_project(p: Params, x: jnp.ndarray, cfg: ArchConfig, positions):
    B, S, _ = x.shape
    hd, Hkv = cfg.head_dim, cfg.n_kv_heads
    H = _n_heads_eff(cfg)
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, Hkv, hd)
    v = (x @ p["wv"]).reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _naive_attention(q, k, v, *, causal: bool, window: int, q_offset: int = 0):
    """q: (B,S,H,dh); k/v: (B,T,Hkv,dh). Materializes (B,H,S,T) scores."""
    B, S, H, dh = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qf = q.astype(jnp.float32) * dh**-0.5
    qg = qf.reshape(B, S, Hkv, G, dh)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, k.astype(jnp.float32))
    qi = jnp.arange(S)[:, None] + q_offset
    ki = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= ki <= qi
    if window:
        mask &= ki > qi - window
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, dh).astype(q.dtype)


def _chunked_attention(q, k, v, *, causal: bool, window: int,
                       q_chunk: int = 512, kv_chunk: int = 1024):
    """Flash-style two-level scan: O(S * kv_chunk) live scores per head.

    This is the memory-roofline-friendly lowering used for the 32k/500k
    shapes; it never materializes an (S, T) score matrix.
    """
    B, S, H, dh = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    if S % q_chunk or T % kv_chunk:
        return _naive_attention(q, k, v, causal=causal, window=window)
    nq, nk = S // q_chunk, T // kv_chunk

    qf = (q.astype(jnp.float32) * dh**-0.5).reshape(B, nq, q_chunk, Hkv, G, dh)
    kf = k.astype(jnp.float32).reshape(B, nk, kv_chunk, Hkv, dh)
    vf = v.astype(jnp.float32).reshape(B, nk, kv_chunk, Hkv, dh)

    def q_block(qi, qb):  # qb: (B, q_chunk, Hkv, G, dh)
        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, kb, vb = inputs
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb)  # (B,Hkv,G,qc,kc)
            qpos = qi * q_chunk + jnp.arange(q_chunk)[:, None]
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)[None, :]
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= kpos <= qpos
            if window:
                mask &= kpos > qpos - window
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, dh), jnp.float32)
        ks_idx = jnp.arange(nk)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (ks_idx, jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0)),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,Hkv,G,qc,dh)
        return jnp.moveaxis(out, 3, 1)                    # (B,qc,Hkv,G,dh)

    outs = jax.lax.map(lambda i: q_block(i, qf[:, i]), jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, dh)
    return out.astype(q.dtype)


def attention_block(
    p: Params,
    x: jnp.ndarray,          # (B, S, d) pre-normed input
    cfg: ArchConfig,
    positions: jnp.ndarray,
    *,
    window: int = 0,
    causal: bool = True,
) -> jnp.ndarray:
    q, k, v = _qk_project(p, x, cfg, positions)
    S = x.shape[1]
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "chunked" if S >= 2048 else "naive"
    fn = _chunked_attention if impl == "chunked" else _naive_attention
    out = fn(q, k, v, causal=causal, window=window)
    B, S_, H, dh = out.shape
    return out.reshape(B, S_, H * dh) @ p["wo"]


def attention_decode(
    p: Params,
    x: jnp.ndarray,            # (B, 1, d)
    cfg: ArchConfig,
    cache: Params,             # {"k": (B,Hkv,Wc,dh), "v": ..., "pos": (B,)}
    *,
    window: int = 0,
) -> tuple[jnp.ndarray, Params]:
    """Single-token decode against a (ring-buffer when windowed) KV cache."""
    B = x.shape[0]
    hd, Hkv = cfg.head_dim, cfg.n_kv_heads
    H = _n_heads_eff(cfg)
    pos = cache["pos"]  # (B,) int32 — absolute position of the new token
    q, k, v = _qk_project(p, x, cfg, pos[:, None])
    Wc = cache["k"].shape[2]
    slot = pos % Wc  # ring buffer; equals append while pos < Wc
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, :, slot].set(
        jnp.swapaxes(k, 1, 2)[:, :, 0].astype(cache["k"].dtype)
    )
    v_cache = cache["v"].at[bidx, :, slot].set(
        jnp.swapaxes(v, 1, 2)[:, :, 0].astype(cache["v"].dtype)
    )
    lengths = jnp.minimum(pos + 1, Wc).astype(jnp.int32)

    if cfg.use_pallas:
        from repro.kernels.swa.ops import attn_decode as _decode
        out = _decode(q[:, 0].transpose(0, 1, 2), k_cache, v_cache, lengths)
    else:
        from repro.kernels.swa.ref import attn_decode_ref
        out = attn_decode_ref(q[:, 0], k_cache, v_cache, lengths)
    y = out.reshape(B, 1, H * hd).astype(x.dtype) @ p["wo"]
    return y, {"k": k_cache, "v": v_cache, "pos": pos + 1}


def init_attn_cache(cfg: ArchConfig, B: int, cache_len: int) -> Params:
    hd, Hkv = cfg.head_dim, cfg.n_kv_heads
    return {
        "k": jnp.zeros((B, Hkv, cache_len, hd), _dt(cfg)),
        "v": jnp.zeros((B, Hkv, cache_len, hd), _dt(cfg)),
        "pos": jnp.zeros((B,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    if cfg.act in ("swiglu", "geglu"):
        return {
            "w_gate": _dense_init(ks[0], (d, f), _dt(cfg)),
            "w_up": _dense_init(ks[1], (d, f), _dt(cfg)),
            "w_down": _dense_init(ks[2], (f, d), _dt(cfg)),
        }
    return {
        "w_up": _dense_init(ks[0], (d, f), _dt(cfg)),
        "w_down": _dense_init(ks[1], (f, d), _dt(cfg)),
    }


def mlp_block(p: Params, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    if "w_gate" in p:
        act = jax.nn.silu if cfg.act == "swiglu" else jax.nn.gelu
        h = act((x @ p["w_gate"]).astype(jnp.float32)).astype(x.dtype) * (
            x @ p["w_up"]
        )
    else:
        h = jax.nn.gelu((x @ p["w_up"]).astype(jnp.float32)).astype(x.dtype)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts (token-choice top-k, capacity-bounded local dispatch)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 4)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": _dense_init(ks[0], (d, E), jnp.float32, scale=0.02),
        "w_gate": _dense_init(ks[1], (E, d, f), _dt(cfg)),
        "w_up": _dense_init(ks[2], (E, d, f), _dt(cfg)),
        "w_down": _dense_init(ks[3], (E, f, d), _dt(cfg)),
    }


def moe_block(
    p: Params, x: jnp.ndarray, cfg: ArchConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatches between the GSPMD one-shot dispatch and the shard_map
    expert-parallel implementation (EXPERIMENTS.md §Perf iteration 1)."""
    if cfg.moe_impl == "sharded":
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is not None and "model" in (mesh.axis_names or ()):
            return _moe_block_sharded(p, x, cfg, mesh)
    return _moe_block_gspmd(p, x, cfg)


def _moe_block_gspmd(
    p: Params, x: jnp.ndarray, cfg: ArchConfig
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Token-choice top-k MoE with per-expert capacity; returns (y, aux_loss).

    Dispatch is scatter/gather by slot index (no (S, E, C) one-hot tensor):
    per expert, tokens take slots in arrival order; beyond-capacity
    assignments are dropped (their gate mass is lost, standard behaviour).
    Under expert-parallel sharding the expert axis of the einsums is sharded
    on "model"; activations stay on ("pod","data").
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)                 # (T,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # --- load-balance aux (Switch): E * sum_e f_e * P_e ---
    ones_frac = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(ones_frac * probs.mean(axis=0)) * cfg.router_aux_coef

    # --- slot assignment: rank of each (token, choice) within its expert ---
    cap = max(1, int(math.ceil(T * k / E * cfg.capacity_factor)))
    flat_ids = ids.reshape(-1)                            # (T*k,)
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)  # (T*k, E)
    ranks = (jnp.cumsum(onehot, axis=0) - onehot).sum(
        axis=1, where=onehot.astype(bool)
    )
    slot = flat_ids * cap + ranks                         # (T*k,)
    valid = ranks < cap
    slot = jnp.where(valid, slot, E * cap)                # overflow -> dropped

    # --- dispatch: (E*cap, d) buffer ---
    tok_idx = jnp.repeat(jnp.arange(T), k)
    buf = jnp.zeros((E * cap + 1, d), x.dtype).at[slot].add(
        xt[tok_idx], mode="drop"
    )
    h = buf[: E * cap].reshape(E, cap, d)

    # --- expert FFN (einsum over expert axis -> expert parallel) ---
    act = jax.nn.silu if cfg.act == "swiglu" else jax.nn.gelu
    g = act(jnp.einsum("ecd,edf->ecf", h, p["w_gate"]).astype(jnp.float32)).astype(
        x.dtype
    )
    u = jnp.einsum("ecd,edf->ecf", h, p["w_up"])
    y_e = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])  # (E, cap, d)

    # --- combine ---
    y_flat = jnp.concatenate(
        [y_e.reshape(E * cap, d), jnp.zeros((1, d), x.dtype)], axis=0
    )
    per_assign = y_flat[slot] * gates.reshape(-1)[:, None].astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok_idx].add(per_assign)
    return y.reshape(B, S, d), aux


def _moe_local_dispatch(xt, router_w, w_gate, w_up, w_down, cfg: ArchConfig,
                        E_total: int, e_offset: jnp.ndarray):
    """Per-device expert compute: route T_loc local tokens over ALL experts,
    keep the assignments owned by this shard's E_loc experts, scatter into a
    capacity-padded buffer, run the expert FFN, combine partial output.

    Requires activations replicated across the model axis (megatron layout
    after the attention psum), so dispatch needs NO cross-device traffic;
    the only collective is the output psum — the paper-facing win recorded
    in EXPERIMENTS.md §Perf (vs the GSPMD dispatch whose scatter/gather
    forced whole-batch replication)."""
    T, d = xt.shape
    E_loc, _, f = w_gate.shape
    k = cfg.top_k

    logits = (xt.astype(jnp.float32) @ router_w).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    ones_frac = jnp.zeros((E_total,), jnp.float32).at[ids.reshape(-1)].add(
        1.0
    ) / (T * k)
    aux = E_total * jnp.sum(ones_frac * probs.mean(axis=0)) \
        * cfg.router_aux_coef

    cap = max(1, int(math.ceil(T * k / E_total * cfg.capacity_factor)))
    flat_ids = ids.reshape(-1)                       # (T*k,) global expert id
    local_ids = flat_ids - e_offset                  # id within this shard
    mine = (local_ids >= 0) & (local_ids < E_loc)
    onehot = jax.nn.one_hot(
        jnp.where(mine, local_ids, E_loc), E_loc + 1, dtype=jnp.int32
    )[:, :E_loc]
    ranks = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(axis=1)
    slot = jnp.where(mine & (ranks < cap), local_ids * cap + ranks,
                     E_loc * cap)
    tok_idx = jnp.repeat(jnp.arange(T), k)
    buf = jnp.zeros((E_loc * cap + 1, d), xt.dtype).at[slot].add(
        xt[tok_idx], mode="drop"
    )
    h = buf[: E_loc * cap].reshape(E_loc, cap, d)

    act = jax.nn.silu if cfg.act == "swiglu" else jax.nn.gelu
    g = act(jnp.einsum("ecd,edf->ecf", h, w_gate).astype(jnp.float32)).astype(
        xt.dtype
    )
    u = jnp.einsum("ecd,edf->ecf", h, w_up)
    y_e = jnp.einsum("ecf,efd->ecd", g * u, w_down)

    y_flat = jnp.concatenate(
        [y_e.reshape(E_loc * cap, d), jnp.zeros((1, d), xt.dtype)], axis=0
    )
    per_assign = y_flat[slot] * gates.reshape(-1)[:, None].astype(xt.dtype)
    y = jnp.zeros((T, d), xt.dtype).at[tok_idx].add(per_assign)
    return y, aux


def _moe_block_sharded(
    p: Params, x: jnp.ndarray, cfg: ArchConfig, mesh
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map expert parallelism: tokens manual over (pod, data), experts
    manual over model, activations replicated across model going in, partial
    outputs psum'd across model coming out."""
    from jax.sharding import PartitionSpec as P

    axis_names = mesh.axis_names
    baxes = tuple(a for a in ("pod", "data") if a in axis_names)
    manual = frozenset(baxes + ("model",))
    E = cfg.n_experts
    B, S, d = x.shape

    def body(xb, router_w, w_gate, w_up, w_down):
        T_loc = xb.shape[0] * xb.shape[1]
        xt = xb.reshape(T_loc, d)
        e_offset = jax.lax.axis_index("model") * w_gate.shape[0]
        y, aux = _moe_local_dispatch(
            xt, router_w, w_gate, w_up, w_down, cfg, E, e_offset
        )
        y = jax.lax.psum(y, "model")
        aux = jax.lax.psum(aux, "model") / jax.lax.axis_size("model")
        if baxes:
            aux = jax.lax.pmean(aux, baxes)
        return y.reshape(xb.shape), aux

    bspec = P(baxes if baxes else None, None, None)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(bspec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(bspec, P()),
        axis_names=manual,
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out


# ---------------------------------------------------------------------------
# RWKV6 time-mix + channel-mix
# ---------------------------------------------------------------------------

def init_wkv6(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 10)
    d = cfg.d_model
    hd = cfg.wkv_head_dim
    H = d // hd
    lora = 64
    return {
        "mu": 0.5 * jnp.ones((5, d), _dt(cfg)),  # token-shift lerp r,k,v,g,w
        "wr": _dense_init(ks[0], (d, d), _dt(cfg)),
        "wk": _dense_init(ks[1], (d, d), _dt(cfg)),
        "wv": _dense_init(ks[2], (d, d), _dt(cfg)),
        "wg": _dense_init(ks[3], (d, d), _dt(cfg)),
        "w0": jnp.zeros((d,), jnp.float32) - 0.5,       # base log-log decay
        "w_lora_a": _dense_init(ks[4], (d, lora), _dt(cfg)),
        "w_lora_b": _dense_init(ks[5], (lora, d), _dt(cfg), scale=0.01),
        "u": _dense_init(ks[6], (H, hd), jnp.float32, scale=0.5),
        "ln_x": jnp.ones((d,), jnp.float32),            # per-head groupnorm
        "wo": _dense_init(ks[7], (d, d), _dt(cfg)),
    }


def _wkv6_inputs(p: Params, x: jnp.ndarray, x_prev: jnp.ndarray, cfg: ArchConfig):
    """Token-shift + projections; x_prev is x shifted right by one token."""
    mu = p["mu"].astype(jnp.float32)
    xf, xpf = x.astype(jnp.float32), x_prev.astype(jnp.float32)
    mix = lambda i: (xf + mu[i] * (xpf - xf)).astype(x.dtype)
    r = mix(0) @ p["wr"]
    k_ = mix(1) @ p["wk"]
    v = mix(2) @ p["wv"]
    g = mix(3) @ p["wg"]
    ww = jnp.tanh((mix(4) @ p["w_lora_a"]).astype(jnp.float32)) @ p[
        "w_lora_b"
    ].astype(jnp.float32)
    lw = -jnp.exp(jnp.clip(p["w0"] + ww, -8.0, 4.0))     # (B,S,d) log-decay <= 0
    return r, k_, v, g, lw


def _wkv_groupnorm(y: jnp.ndarray, scale: jnp.ndarray, H: int) -> jnp.ndarray:
    B, S, d = y.shape
    hd = d // H
    yh = y.reshape(B, S, H, hd).astype(jnp.float32)
    mu = yh.mean(-1, keepdims=True)
    var = ((yh - mu) ** 2).mean(-1, keepdims=True)
    yn = (yh - mu) * jax.lax.rsqrt(var + 1e-5)
    return (yn.reshape(B, S, d) * scale).astype(y.dtype)


def wkv6_block(
    p: Params, x: jnp.ndarray, cfg: ArchConfig
) -> jnp.ndarray:
    """Training/prefill path (full sequence)."""
    B, S, d = x.shape
    hd = cfg.wkv_head_dim
    H = d // hd
    x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    r, k_, v, g, lw = _wkv6_inputs(p, x, x_prev, cfg)

    resh = lambda a: a.reshape(B, S, H, hd).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    rr, kk, vv = resh(r), resh(k_), resh(v)
    lww = lw.reshape(B, S, H, hd).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    u = jnp.broadcast_to(p["u"][None], (B, H, hd)).reshape(B * H, hd)

    from repro.kernels.wkv6.ops import wkv6 as _wkv
    y, _ = _wkv(rr, kk, vv, lww, u,
                backend="pallas" if cfg.use_pallas else "xla")
    y = y.reshape(B, H, S, hd).transpose(0, 2, 1, 3).reshape(B, S, d)
    y = _wkv_groupnorm(y, p["ln_x"], H)
    y = y * jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
    return y @ p["wo"]


def wkv6_decode(
    p: Params, x: jnp.ndarray, cfg: ArchConfig, cache: Params
) -> tuple[jnp.ndarray, Params]:
    """Single-token decode. cache: {"state": (B,H,hd,hd), "x_prev": (B,d)}."""
    B = x.shape[0]
    d = cfg.d_model
    hd = cfg.wkv_head_dim
    H = d // hd
    x_prev = cache["x_prev"][:, None, :]
    r, k_, v, g, lw = _wkv6_inputs(p, x, x_prev, cfg)
    resh = lambda a: a.reshape(B, H, hd).reshape(B * H, hd)
    from repro.kernels.wkv6.ops import wkv6_decode_step
    u = jnp.broadcast_to(p["u"][None], (B, H, hd)).reshape(B * H, hd)
    y, s_new = wkv6_decode_step(
        resh(r[:, 0]), resh(k_[:, 0]), resh(v[:, 0]), resh(lw[:, 0]), u,
        cache["state"].reshape(B * H, hd, hd),
    )
    y = y.reshape(B, 1, d)
    y = _wkv_groupnorm(y, p["ln_x"], H)
    y = y * jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
    return y @ p["wo"], {
        "state": s_new.reshape(B, H, hd, hd),
        "x_prev": x[:, 0],
    }


def init_wkv6_cache(cfg: ArchConfig, B: int) -> Params:
    d = cfg.d_model
    hd = cfg.wkv_head_dim
    H = d // hd
    return {
        "state": jnp.zeros((B, H, hd, hd), jnp.float32),
        "x_prev": jnp.zeros((B, d), _dt(cfg)),
    }


def init_rwkv_cm(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mu": 0.5 * jnp.ones((2, d), _dt(cfg)),
        "wk": _dense_init(ks[0], (d, f), _dt(cfg)),
        "wv": _dense_init(ks[1], (f, d), _dt(cfg)),
        "wr": _dense_init(ks[2], (d, d), _dt(cfg)),
    }


def rwkv_cm_block(
    p: Params, x: jnp.ndarray, cfg: ArchConfig, x_prev: jnp.ndarray | None = None
) -> jnp.ndarray:
    if x_prev is None:
        x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    mu = p["mu"].astype(jnp.float32)
    xf, xpf = x.astype(jnp.float32), x_prev.astype(jnp.float32)
    mix = lambda i: (xf + mu[i] * (xpf - xf)).astype(x.dtype)
    kk = jnp.square(jax.nn.relu((mix(0) @ p["wk"]).astype(jnp.float32))).astype(
        x.dtype
    )
    r = jax.nn.sigmoid((mix(1) @ p["wr"]).astype(jnp.float32)).astype(x.dtype)
    return r * (kk @ p["wv"])


# ---------------------------------------------------------------------------
# RG-LRU (RecurrentGemma / Griffin recurrent block)
# ---------------------------------------------------------------------------

def init_rglru(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 7)
    d, w = cfg.d_model, cfg.rnn_width
    return {
        "w_in": _dense_init(ks[0], (d, w), _dt(cfg)),
        "w_gate_branch": _dense_init(ks[1], (d, w), _dt(cfg)),
        "conv_w": _dense_init(ks[2], (4, w), _dt(cfg), scale=0.5),
        "conv_b": jnp.zeros((w,), _dt(cfg)),
        "wa": _dense_init(ks[3], (w, w), _dt(cfg), scale=0.02),
        "wx": _dense_init(ks[4], (w, w), _dt(cfg), scale=0.02),
        "lam": jnp.full((w,), 4.0, jnp.float32),   # softplus^-1 of decay param
        "w_out": _dense_init(ks[5], (w, d), _dt(cfg)),
    }


def _rglru_scan(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray | None = None):
    """h_t = a_t * h_{t-1} + b_t along axis 1 via associative scan. f32."""
    if h0 is not None:
        # fold the initial state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0)
    op = lambda x, y: (x[0] * y[0], y[0] * x[1] + y[1])
    _, h = jax.lax.associative_scan(op, (a, b), axis=1)
    return h


def _rglru_core(p: Params, xw: jnp.ndarray, h0=None):
    """xw: (B, S, w) post-conv activations -> (h, h_last). float32 path."""
    c = 8.0
    xf = xw.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ p["wa"].astype(jnp.float32))
    i = jax.nn.sigmoid(xf @ p["wx"].astype(jnp.float32))
    log_a = -c * r * jax.nn.softplus(p["lam"])           # (B,S,w) <= 0
    a = jnp.exp(log_a)
    gated = i * xf
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated
    h = _rglru_scan(a, b, h0)
    return h, a, b


def rglru_block(p: Params, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Training/prefill path."""
    xw = x @ p["w_in"]
    # temporal conv, width 4, causal
    pad = jnp.pad(xw, ((0, 0), (3, 0), (0, 0)))
    conv = sum(
        pad[:, 3 - i : pad.shape[1] - i] * p["conv_w"][3 - i][None, None]
        for i in range(4)
    ) + p["conv_b"]
    h, _, _ = _rglru_core(p, conv)
    gate = jax.nn.gelu((x @ p["w_gate_branch"]).astype(jnp.float32))
    y = (h * gate).astype(x.dtype)
    return y @ p["w_out"]


def rglru_decode(
    p: Params, x: jnp.ndarray, cfg: ArchConfig, cache: Params
) -> tuple[jnp.ndarray, Params]:
    """cache: {"h": (B,w) f32, "conv": (B,3,w)} — O(1) state decode."""
    xw = x @ p["w_in"]                                   # (B,1,w)
    hist = jnp.concatenate([cache["conv"], xw.astype(cache["conv"].dtype)], axis=1)
    conv = (
        jnp.einsum("btw,tw->bw", hist.astype(jnp.float32),
                   p["conv_w"].astype(jnp.float32))
        + p["conv_b"].astype(jnp.float32)
    )[:, None, :]
    h, a, b = _rglru_core(p, conv, h0=cache["h"])
    h = h[:, 0]
    gate = jax.nn.gelu((x[:, 0] @ p["w_gate_branch"]).astype(jnp.float32))
    y = (h * gate).astype(x.dtype) @ p["w_out"]
    return y[:, None, :], {"h": h, "conv": hist[:, 1:]}


def init_rglru_cache(cfg: ArchConfig, B: int) -> Params:
    w = cfg.rnn_width
    return {"h": jnp.zeros((B, w), jnp.float32), "conv": jnp.zeros((B, 3, w), _dt(cfg))}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 4)
    d, hd, H = cfg.d_model, cfg.head_dim, cfg.n_heads
    return {
        "wq": _dense_init(ks[0], (d, H * hd), _dt(cfg)),
        "wk": _dense_init(ks[1], (d, H * hd), _dt(cfg)),
        "wv": _dense_init(ks[2], (d, H * hd), _dt(cfg)),
        "wo": _dense_init(ks[3], (H * hd, d), _dt(cfg)),
    }


def cross_attention_block(
    p: Params, x: jnp.ndarray, enc: jnp.ndarray, cfg: ArchConfig
) -> jnp.ndarray:
    """x: (B,S,d) queries; enc: (B,T,d) encoder output (keys/values)."""
    B, S, d = x.shape
    T = enc.shape[1]
    hd, H = cfg.head_dim, cfg.n_heads
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (enc @ p["wk"]).reshape(B, T, H, hd)
    v = (enc @ p["wv"]).reshape(B, T, H, hd)
    out = _naive_attention(q, k, v, causal=False, window=0)
    return out.reshape(B, S, H * hd) @ p["wo"]
