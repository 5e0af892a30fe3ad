"""Train-step builders.

Two execution modes:

* ``gspmd`` (aggregator "mean"): one jit'd SPMD program; the gradient
  all-reduce is implicit. Supports FSDP param sharding — this is the
  plain-production baseline the paper's robust modes are compared against.

* ``robust`` (aggregator != "mean"): decentralized training. Every data
  worker keeps its OWN model copy (leading worker axis on every param leaf,
  sharded over (pod, data)) and evolves it by the paper's
  consensus + innovation loop: local grads (innovation) -> robust
  aggregation across workers (consensus) -> local AdamW step. Executed as a
  ``shard_map`` with (pod, data) manual and ``model`` auto, so tensor
  parallelism inside the model stays GSPMD while worker identity is
  explicit. Byzantine workers are simulated by corrupting the gradient of
  the configured worker indices before aggregation (the strongest in-scope
  attack: sign-flip + rescale).

Consensus error across worker copies is observable via ``param_spread`` —
the training-side analogue of Theorem 1's consensus-error bound.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_init, adamw_update
from .aggregation import AGGREGATORS, AggregatorConfig
from .sharding import batch_axes, batch_specs, param_specs, opt_state_specs

Params = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    arch: ArchConfig
    agg: AggregatorConfig = AggregatorConfig()
    opt: AdamWConfig = AdamWConfig()
    fsdp: bool = False
    n_micro: int = 1                          # gradient-accumulation steps
    byzantine_workers: tuple[int, ...] = ()   # simulated compromised workers
    byzantine_scale: float = 10.0
    seed: int = 0


# ---------------------------------------------------------------------------
# GSPMD baseline
# ---------------------------------------------------------------------------

def make_train_step(tc: TrainConfig, mesh: Mesh):
    if tc.agg.kind == "mean":
        return _make_gspmd_step(tc, mesh)
    return _make_robust_step(tc, mesh)


def _loss(params, cfg, batch):
    return M.loss_fn(
        params, cfg, batch["tokens"], batch["labels"],
        patch_embeds=batch.get("patch_embeds"), frames=batch.get("frames"),
    )


def _micro_split(batch, n_micro: int):
    """(B, ...) -> (n_micro, B/n_micro, ...) with stride-n_micro interleave,
    so every data shard contributes equally to every micro-batch (the
    leading micro axis never crosses shard boundaries)."""

    def split(x):
        B = x.shape[0]
        return x.reshape((B // n_micro, n_micro) + x.shape[1:]).swapaxes(0, 1)

    return jax.tree_util.tree_map(split, batch)


def _grads_microbatched(params, cfg, batch, n_micro: int, grad_shardings=None):
    """Gradient accumulation: scan over micro-batches, f32 accumulator.
    Peak activation memory = one micro-batch's worth. ``grad_shardings``
    (NamedSharding tree) pins the accumulator to the param layout so GSPMD
    cannot replicate it."""
    constrain = (
        (lambda t: jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, t, grad_shardings))
        if grad_shardings is not None else (lambda t: t)
    )
    if n_micro <= 1:
        loss, grads = jax.value_and_grad(_loss)(params, cfg, batch)
        return loss, constrain(grads)
    micro = _micro_split(batch, n_micro)
    gz = constrain(jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    ))

    def body(carry, mb):
        loss_acc, gacc = carry
        l, g = jax.value_and_grad(_loss)(params, cfg, mb)
        gacc = constrain(jax.tree_util.tree_map(
            lambda a, x: a + x.astype(jnp.float32), gacc, g
        ))
        return (loss_acc + l, gacc), None

    (loss_sum, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), gz), micro)
    inv = 1.0 / n_micro
    grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
    return loss_sum * inv, grads


def _make_gspmd_step(tc: TrainConfig, mesh: Mesh):
    cfg = tc.arch

    def shardings(params_like, batch_keys=("tokens", "labels")):
        pspecs = param_specs(params_like, cfg, mesh, fsdp=tc.fsdp)
        ospecs = opt_state_specs(pspecs)
        bspec = _batch_spec_tree(mesh, batch_keys)
        return pspecs, ospecs, bspec

    def train_step_factory(params_like, batch_keys=("tokens", "labels")):
        pspecs, _, _ = shardings(params_like, batch_keys)
        # pin the grad accumulator to the param layout (a memory optimization)
        gshard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )

        def train_step(params, opt_state, batch):
            loss, grads = _grads_microbatched(
                params, cfg, batch, tc.n_micro, grad_shardings=gshard
            )
            new_params, new_opt = adamw_update(tc.opt, grads, opt_state, params)
            return new_params, new_opt, loss

        return train_step

    return train_step_factory, shardings


def _batch_spec_tree(mesh: Mesh, keys=("tokens", "labels")):
    b = batch_specs(mesh)
    full = {
        "tokens": b, "labels": b,
        "patch_embeds": P(batch_axes(mesh), None, None),
        "frames": P(batch_axes(mesh), None, None),
    }
    return {k: full[k] for k in keys}


# ---------------------------------------------------------------------------
# decentralized robust step
# ---------------------------------------------------------------------------

def _make_robust_step(tc: TrainConfig, mesh: Mesh):
    cfg = tc.arch
    baxes = batch_axes(mesh)
    pod_axis = "pod" if "pod" in mesh.axis_names else None
    data_axis = "data"
    agg_fn = AGGREGATORS[tc.agg.kind]
    n_workers = mesh.shape[data_axis] * (mesh.shape["pod"] if pod_axis else 1)

    def per_worker(params_w, opt_w, batch, step_key):
        # params_w: leading worker axis of size 1 on every leaf (manual view)
        params = jax.tree_util.tree_map(lambda x: x[0], params_w)
        opt_state = jax.tree_util.tree_map(lambda x: x[0], opt_w)
        loss, grads = _grads_microbatched(params, cfg, batch, tc.n_micro)

        # --- simulated Byzantine workers: colluding sign-flip attack ---
        if tc.byzantine_workers:
            widx = jax.lax.axis_index(data_axis)
            if pod_axis:
                widx = widx + jax.lax.axis_index(pod_axis) * mesh.shape[data_axis]
            is_byz = jnp.zeros((), bool)
            for b in tc.byzantine_workers:
                is_byz = is_byz | (widx == b)
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(is_byz, -tc.byzantine_scale * g, g), grads
            )

        agg = agg_fn(grads, tc.agg, data_axis, pod_axis, step_key)
        new_params, new_opt = adamw_update(tc.opt, agg, opt_state, params)
        loss_mean = jax.lax.pmean(
            loss, (pod_axis, data_axis) if pod_axis else (data_axis,)
        )
        expand = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return expand(new_params), expand(new_opt), loss_mean

    def shardings(params_like, batch_keys=("tokens", "labels")):
        pspecs = param_specs(params_like, cfg, mesh, worker_axis=True)
        ospecs = {
            "m": pspecs, "v": pspecs,
            "step": P(batch_axes(mesh)),
        }
        return pspecs, ospecs, _batch_spec_tree(mesh, batch_keys)

    def train_step_factory(params_like, batch_keys=("tokens", "labels")):
        pspecs, ospecs, bspec = shardings(params_like, batch_keys)
        manual = frozenset(("pod", "data") if pod_axis else ("data",))
        strip = lambda tree: jax.tree_util.tree_map(
            lambda s: _manual_only(s, manual), tree,
            is_leaf=lambda x: isinstance(x, P),
        )
        return jax.shard_map(
            per_worker,
            mesh=mesh,
            in_specs=(strip(pspecs), strip(ospecs), strip(bspec), P()),
            out_specs=(strip(pspecs), strip(ospecs), P()),
            axis_names=manual,          # model stays auto (GSPMD inside)
            check_vma=False,
        )

    return train_step_factory, shardings


def _manual_only(spec: P, manual: frozenset) -> P:
    """Project a PartitionSpec onto the manual axes (auto axes -> None)."""
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in manual)
            out.append(kept if kept else None)
        else:
            out.append(e if e in manual else None)
    return P(*out)


# ---------------------------------------------------------------------------
# worker-axis param helpers
# ---------------------------------------------------------------------------

def replicate_for_workers(params: Params, n_workers: int) -> Params:
    """Tile a single model copy into the worker-axis layout."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_workers,) + x.shape), params
    )


def worker_opt_init(params_w: Params) -> Params:
    """Per-worker AdamW state (leading worker axis, incl. per-worker step)."""
    W = jax.tree_util.tree_leaves(params_w)[0].shape[0]
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree_util.tree_map(zeros, params_w),
        "v": jax.tree_util.tree_map(zeros, params_w),
        "step": jnp.zeros((W,), jnp.int32),
    }


def param_spread(params_w: Params) -> jnp.ndarray:
    """Max over leaves of the max |worker_i - mean| — the consensus error."""
    def spread(x):
        mu = x.mean(axis=0, keepdims=True)
        return jnp.abs(x.astype(jnp.float32) - mu).max()

    return jnp.stack(
        [spread(l) for l in jax.tree_util.tree_leaves(params_w)]
    ).max()
