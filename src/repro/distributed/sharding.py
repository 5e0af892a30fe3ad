"""PartitionSpec rules for every architecture's param/activation/cache trees.

Axes: single-pod mesh ``("data", "model")``; multi-pod ``("pod", "data",
"model")``. Batch always shards over (pod, data); tensor dims over "model";
large 2-D weights additionally FSDP-shard their input dim over "data"
(GSPMD inserts the per-layer all-gathers) when ``fsdp=True`` — required for
llama3-405b-class params to fit 16 GB/chip.

Rules dispatch on the leaf's key-path (module-qualified names from
``repro.models.layers`` inits) and pad with leading ``None`` for stacked
layer axes.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig

Params = Any


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _path_names(path) -> list[str]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return out


# weight-name -> spec for the *trailing* dims (stack dims padded with None)
_COL = ("wq", "wk", "wv", "wg", "w_gate", "w_up", "w_in", "w_gate_branch",
        "w_lora_b", "w1", "wr")
_ROW = ("wo", "w_down", "w_out", "w2")


def _rule(names: list[str], leaf, cfg: ArchConfig, fsdp: bool, mesh: Mesh):
    name = names[-1]
    in_ffn = "ffn" in names
    in_moe = cfg.is_moe and in_ffn
    fs = "data" if fsdp else None

    if name == "embed":
        return ("model", None)
    if name == "lm_head":
        return (fs, "model")
    if name == "router":
        return (None, None)
    if in_moe and name in ("w_gate", "w_up", "w_down"):
        return ("model", fs, None)        # expert parallel + fsdp inner dim
    if in_ffn and cfg.ffn_kind == "rwkv_cm":
        # channel-mix: wk (d,f) col, wv (f,d) row, wr (d,d) col
        if name == "wk":
            return (fs, "model")
        if name == "wv":
            return ("model", fs)
        if name == "wr":
            return (fs, "model")
    if name in _COL:
        return (fs, "model")
    if name in _ROW:
        return ("model", fs)
    if name == "conv_w":
        return (None, "model")
    if name == "u":
        return (None, None)
    if name == "w_lora_a":
        return (fs, None)
    # 1-D scales/biases, lam, w0, mu, ln_x, conv_b: replicate
    return tuple(None for _ in range(leaf.ndim))


def param_specs(
    params_like: Params, cfg: ArchConfig, mesh: Mesh, fsdp: bool = False,
    worker_axis: bool = False,
) -> Params:
    """PartitionSpec pytree matching ``params_like``.

    worker_axis: the decentralized-training layout — every leaf has a
    leading per-worker axis sharded over (pod, data); see
    ``repro.distributed.aggregation``.
    """
    baxes = batch_axes(mesh)

    def assign(path, leaf):
        names = _path_names(path)
        rule = _rule(names, leaf, cfg, fsdp, mesh)
        ndim = leaf.ndim - (1 if worker_axis else 0)
        rule = tuple(rule[-ndim:]) if ndim else ()
        pad = ndim - len(rule)
        spec = (None,) * pad + rule
        if worker_axis:
            spec = (baxes,) + spec
        # divisibility guard (odd vocabs like 92553, kv heads < model, ...)
        return fit_spec(P(*spec), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(assign, params_like)


def opt_state_specs(pspecs: Params) -> Params:
    """Adam moments share their param's spec; step is replicated."""
    return {
        "m": pspecs,
        "v": pspecs,
        "step": P(),
    }


def batch_specs(mesh: Mesh) -> P:
    return P(batch_axes(mesh), None)


def fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop spec axes whose mesh size does not divide the array dim — jit
    in_shardings require exact divisibility (e.g. kv_heads=8 cannot shard
    over model=16; batch=1 cannot shard over data)."""
    sizes = dict(mesh.shape)
    out = []
    for i, e in enumerate(spec):
        if e is None or i >= len(shape):
            out.append(None if i >= len(shape) else e)
            continue
        axes = e if isinstance(e, (tuple, list)) else (e,)
        total = 1
        for a in axes:
            total *= sizes[a]
        out.append(e if shape[i] % total == 0 else None)
    return P(*out)


def cache_specs(cache_like: Params, cfg: ArchConfig, mesh: Mesh) -> Params:
    """KV/state caches: batch over (pod, data); heads/channels over model."""
    baxes = batch_axes(mesh)

    def assign(path, leaf):
        names = _path_names(path)
        name = names[-1]
        if name in ("k", "v"):            # (B, Hkv, Wc, dh) [+stack]
            # heads on model when divisible, else sequence-parallel cache
            spec = (baxes, "model", None, None)
            pad0 = leaf.ndim - 4
            trial = P(*(((None,) * pad0) + spec))
            fitted = fit_spec(trial, leaf.shape, mesh)
            if fitted[pad0 + 1] is None:
                spec = (baxes, None, "model", None)
        elif name == "pos":
            spec = (baxes,)
        elif name == "state":             # wkv6 (B, H, hd, hd)
            spec = (baxes, "model", None, None)
        elif name == "x_prev" or name == "cm_prev":
            spec = (baxes, None)
        elif name == "h":                 # rglru (B, w)
            spec = (baxes, "model")
        elif name == "conv":              # (B, 3, w)
            spec = (baxes, None, "model")
        elif name == "enc":               # (B, T_enc, d)
            spec = (baxes, None, None)
        else:
            spec = tuple(None for _ in range(leaf.ndim))
        pad = leaf.ndim - len(spec)
        return fit_spec(P(*(((None,) * pad) + tuple(spec))), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(assign, cache_like)


def to_shardings(spec_tree: Params, mesh: Mesh) -> Params:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Scenario-sweep meshes: the 2-D (data x graph) layout of the edge-
# partitioned push-sum engines (repro.core.sweeps)
# ---------------------------------------------------------------------------

def sweep_mesh(
    n_data: int,
    n_graph: int = 1,
    *,
    data_axis: str = "data",
    graph_axis: str = "graph",
    devices=None,
):
    """Mesh for the scenario-sweep engines: ``data_axis`` shards the K
    scenario axis (one scenario batch per device row), ``graph_axis``
    shards the edge index of each scenario into ``n_graph`` dst-contiguous
    shards (:func:`repro.core.graphs.partition_edge_list`). ``n_data *
    n_graph`` must not exceed the available device count. Both axes are
    ``AxisType.Auto``: the engines shard_map over them explicitly.
    """
    return jax.make_mesh(
        (n_data, n_graph), (data_axis, graph_axis),
        axis_types=(jax.sharding.AxisType.Auto,) * 2, devices=devices,
    )


def sweep_specs(data_axis: str = "data", graph_axis: str = "graph"):
    """PartitionSpecs of the 2-D sweep program's four argument roles.

    * ``"replicated"``  — w and any other every-device value,
    * ``"scenario"``    — (K,) per-scenario coordinates (drop, seed): data
      axis only, every graph-shard device sees its row's full batch,
    * ``"edge_shards"`` — (K, S, E_shard) partitioned edge arrays: scenario
      rows over data, the shard axis over graph,
    * ``"out"``         — results: node state is graph-replicated after the
      per-round psum combine, so outputs name only the data axis.
    """
    return {
        "replicated": P(),
        "scenario": P(data_axis),
        "edge_shards": P(data_axis, graph_axis),
        "out": P(data_axis),
    }
