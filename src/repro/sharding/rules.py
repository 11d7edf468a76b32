"""Partition rules: parameter leaf -> PartitionSpec on the production mesh.

Rules are keyed on the leaf's dict key (the nn modules use stable names) and
applied to the *trailing* dims; leading stack dims (scan layer stacking) are
padded with None.  `fsdp=True` (qwen1.5-110b) additionally shards the big
matmul weights over the `data` axis (DESIGN.md Sec. 5).

All specs are divisibility-checked against the mesh at build time; an axis
that does not divide the dim is dropped (with the drop recorded) rather than
producing a lowering error.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn.config import ModelConfig

M = "model"
D = "data"

# (base spec, fsdp spec) per leaf name; specs target the trailing dims
_RULES: Dict[str, Tuple[tuple, tuple]] = {
    # embeddings / head
    "tok_tied": ((M, None), (M, (D,))),           # vocab-sharded (tied)
    "tok": ((None, M), ((D,), M)),                # d-sharded (untied input)
    "head": ((None, M), ((D,), M)),
    "proj": ((None, M), ((D,), M)),
    # attention
    "wq": ((None, M, None), ((D,), M, None)),
    "wk": ((None, M, None), ((D,), M, None)),
    "wv": ((None, M, None), ((D,), M, None)),
    "bq": ((M, None), (M, None)),
    "bk": ((M, None), (M, None)),
    "bv": ((M, None), (M, None)),
    "wo": ((M, None, None), (M, None, (D,))),
    # MLA
    "w_dkv": ((None, None), ((D,), None)),
    "w_uk": ((None, M, None), ((D,), M, None)),
    "w_uv": ((None, M, None), ((D,), M, None)),
    "kv_norm": ((None,), (None,)),
    # MLP (dense + shared experts)
    "w_gate": ((None, M), ((D,), M)),
    "w_up": ((None, M), ((D,), M)),
    "w_down": ((M, None), (M, (D,))),
    # MoE experts held here (leading dim experts_held -> EP over model)
    "w_gate_e": ((M, None, None), (M, (D,), None)),
    "w_up_e": ((M, None, None), (M, (D,), None)),
    "w_down_e": ((M, None, None), (M, None, (D,))),
    "router": ((None, None), (None, None)),
    # mamba2
    "w_z": ((None, M), ((D,), M)),
    "w_x": ((None, M), ((D,), M)),
    "w_B": ((None, None), (None, None)),
    "w_C": ((None, None), (None, None)),
    "w_dt": ((None, None), (None, None)),
    "conv_x": ((None, M), (None, M)),
    "conv_bc": ((None, None), (None, None)),
    "conv_b_x": ((M,), (M,)),
    "conv_b_bc": ((None,), (None,)),
    "A_log": ((None,), (None,)),
    "D": ((None,), (None,)),
    "dt_bias": ((None,), (None,)),
    "norm_scale": ((M,), (M,)),
    "w_out": ((M, None), (M, (D,))),
    # xlstm
    "w_xin": ((None, M), ((D,), M)),
    "w_zgate": ((None, M), ((D,), M)),
    "w_q": ((None, None, M), ((D,), None, M)),   # (H, hd, hd) per-head
    "w_k": ((None, None, M), ((D,), None, M)),
    "w_v": ((None, None, M), ((D,), None, M)),
    "w_if": ((None, None), (None, None)),
    "b_if": ((None,), (None,)),
    "w_h": ((None, M), (None, M)),
    # norms
    "scale": ((None,), (None,)),
    "bias": ((None,), (None,)),
    "b": ((None,), (None,)),
}


def _leaf_rule(path: Tuple, leaf, cfg: ModelConfig, fsdp: bool) -> tuple:
    keys = [getattr(k, "key", str(k)) for k in path]
    name = keys[-1]
    if name == "tok":
        name = "tok_tied" if cfg.tie_embeddings else "tok"
    if name in ("w_gate", "w_up", "w_down") and "moe" in keys and \
            "shared" not in keys:
        name = name + "_e"
    if "slstm" in keys:
        # sLSTM weights are replicated: the sequential per-step matmuls on
        # (B, d) states make sharded weights a collective pathology
        # (EXPERIMENTS.md §Perf xlstm iteration); 0.8 GB replicated total.
        return (None,) * len(leaf.shape)
    base, fs = _RULES.get(name, ((None,) * 1, (None,) * 1))
    spec = fs if fsdp else base
    # pad/truncate to leaf ndim (leading stack dims -> None)
    nd = len(leaf.shape)
    spec = tuple(spec)[-nd:]
    return (None,) * (nd - len(spec)) + spec


def _check_divisible(spec: tuple, shape: Tuple[int, ...],
                     axis_sizes: Dict[str, int]) -> tuple:
    out = []
    dropped = []
    for dim, e in zip(shape, spec):
        if e is None:
            out.append(None)
            continue
        axes = (e,) if isinstance(e, str) else tuple(e)
        total = int(np.prod([axis_sizes[a] for a in axes]))
        if dim % total == 0:
            out.append(e)
        else:
            out.append(None)
            dropped.extend(axes)
    # fallback: re-place dropped axes on another dim that divides (e.g.
    # phi3's 40 heads don't divide model=16 -> shard head_dim=128 instead).
    for ax in dropped:
        sz = axis_sizes[ax]
        for i in range(len(out) - 1, -1, -1):
            if out[i] is not None:
                continue
            if shape[i] % sz == 0 and shape[i] >= sz:
                out[i] = ax
                break
    return tuple(out)


def param_specs(params_shapes, cfg: ModelConfig, mesh: Mesh,
                fsdp: bool = False):
    """Pytree of PartitionSpec congruent to the params pytree."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def rule(path, leaf):
        spec = _leaf_rule(path, leaf, cfg, fsdp)
        spec = _check_divisible(spec, leaf.shape, sizes)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, params_shapes)


def param_shardings(params_shapes, cfg: ModelConfig, mesh: Mesh,
                    fsdp: bool = False):
    specs = param_specs(params_shapes, cfg, mesh, fsdp)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def grads_specs(params_shapes, cfg: ModelConfig, mesh: Mesh,
                coding_axes: Tuple[str, ...], fsdp: bool = False):
    """Specs for per-coding-rank gradient stacks: leading coding dim."""
    specs = param_specs(params_shapes, cfg, mesh, fsdp)
    axes = tuple(a for a in coding_axes if a in mesh.axis_names)
    lead = axes if len(axes) > 1 else (axes[0] if axes else None)
    return jax.tree.map(lambda s: P(lead, *tuple(s)), specs)


# --------------------------------------------------------------------------
# cache specs (serving)
# --------------------------------------------------------------------------

def cache_specs(caches_shapes, cfg: ModelConfig, mesh: Mesh,
                batch_axes: Tuple[str, ...], global_batch: int):
    """KV/state caches: the batch dim (identified by size == global_batch)
    over dp axes where divisible, trailing feature dim over model where
    divisible.  `pos` bookkeeping arrays stay replicated."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    nb = int(np.prod([sizes[a] for a in b_axes])) if b_axes else 1

    def rule(path, leaf):
        keys = [getattr(k, "key", str(k)) for k in path]
        shape = leaf.shape
        nd = len(shape)
        spec: List[Any] = [None] * nd
        if keys and keys[-1] == "pos":
            return P(*spec)
        for i, dim in enumerate(shape):
            if dim == global_batch and dim % nb == 0 and nb > 1:
                spec[i] = b_axes if len(b_axes) > 1 else b_axes[0]
                break
        if nd >= 2 and shape[-1] % sizes.get(M, 1) == 0 and sizes.get(M, 1) > 1:
            spec[-1] = M
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, caches_shapes)
