"""In-graph telemetry frame for the coded train step.

`MetricsFrame` is a pytree of small per-device arrays produced INSIDE the
jit/shard_map scope (no host callbacks, no extra collectives) by
`repro.core.cocoef.cocoef_update(..., want_metrics=True)` and
`repro.optim.apply_update(..., want_norms=True)`:

  participation     (N,)  the straggler mask I^t (replicated on every device)
  wire_bytes_rank   (N,)  phase-1 bytes ACTUALLY sent per coding rank this
                          step: mask_i * wire.rank_wire_bytes(n)[i], summed
                          over buckets — the same per-rank accounting
                          `sim.StepTimer.bytes_up_ranks` prices and
                          `benchmarks/comm_volume.audit_wire_bytes` audits
  bucket_wire_bytes (B,)  THIS rank's shipped bytes per bucket (x its mask)
  bytes_down        ()    phase-2 broadcast bytes received per rank
  grad_norm_sq      ()    |g_local|^2 of this device's flat gradient slice
  ef_norm_sq        ()    |e_new|^2 — the error vector AFTER the update
  acc_norm_sq       ()    |gamma*g + e|^2 (the compressor input)
  c_norm_sq         ()    |C(acc)|^2 (the transmitted reconstruction)
  acc_dot_c         ()    <acc, C(acc)> — with the two norms this gives the
                          compressed-vs-raw cosine and the contraction
                          |acc - C(acc)|^2 / |acc|^2 (the delta of
                          Assumption 5, the paper's bias proxy)
  ghat_norm_sq      ()    |ghat_local|^2 of the aggregated update slice
  update_norm_sq    ()    |theta_new - theta|^2 (optimizer, incl. decay)
  param_norm_sq     ()    |theta_new|^2
  moe_rows_held     ()    rows stage 1 routed to the experts held on this
                          device, summed over the MoE layers (its coding
                          rank's; 0 for a model without experts).  Filled
                          by the train step; None in a frame that
                          `cocoef_update` returns alone

Scalar leaves are DEVICE-LOCAL partial sums over that device's slice of
the flat vector; `reduce_frame_grid` turns the (mesh-grid)-shaped output
of the aggregation shard_map into per-coding-rank / global quantities on
which the host-side `repro.obs.logger.MetricsLogger` operates.

This module deliberately imports nothing from `repro.core` (the core
imports it), and every helper is shape-static so the frame is safe to
return from a shard_map without adding communication.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["MetricsFrame", "norm_sq", "frame_out_specs", "reduce_frame_grid"]


def norm_sq(x: jnp.ndarray) -> jnp.ndarray:
    """Sum of squares in f32 (the frame's scalar accumulator)."""
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf)


@dataclasses.dataclass
class MetricsFrame:
    """One step's in-graph telemetry (see module docstring for fields)."""

    participation: jnp.ndarray        # (N,) f32
    wire_bytes_rank: jnp.ndarray      # (N,) f32
    bucket_wire_bytes: jnp.ndarray    # (B,) f32
    bytes_down: jnp.ndarray           # ()  f32
    grad_norm_sq: jnp.ndarray         # ()  f32
    ef_norm_sq: jnp.ndarray           # ()  f32
    acc_norm_sq: jnp.ndarray          # ()  f32
    c_norm_sq: jnp.ndarray            # ()  f32
    acc_dot_c: jnp.ndarray            # ()  f32
    ghat_norm_sq: jnp.ndarray         # ()  f32
    update_norm_sq: jnp.ndarray       # ()  f32
    param_norm_sq: jnp.ndarray        # ()  f32
    moe_rows_held: jnp.ndarray = None  # ()  f32

    def replace(self, **kw) -> "MetricsFrame":
        return dataclasses.replace(self, **kw)

    @classmethod
    def abstract(cls, n_ranks: int, num_buckets: int) -> "MetricsFrame":
        """ShapeDtypeStruct skeleton (builds shard_map out_specs)."""
        f32 = jnp.float32
        s = jax.ShapeDtypeStruct
        return cls(
            participation=s((n_ranks,), f32),
            wire_bytes_rank=s((n_ranks,), f32),
            bucket_wire_bytes=s((num_buckets,), f32),
            bytes_down=s((), f32),
            grad_norm_sq=s((), f32), ef_norm_sq=s((), f32),
            acc_norm_sq=s((), f32), c_norm_sq=s((), f32),
            acc_dot_c=s((), f32), ghat_norm_sq=s((), f32),
            update_norm_sq=s((), f32), param_norm_sq=s((), f32))


jax.tree_util.register_dataclass(
    MetricsFrame,
    data_fields=[f.name for f in dataclasses.fields(MetricsFrame)],
    meta_fields=[])


# How each field aggregates across the device grid (reduce_frame_grid):
#   corner     identical on every device -> take grid corner
#   rank_sum   per-device partial sum    -> sum over non-coding axes
#              (one total per coding rank)
#   rank_vec   per-coding-rank vector, replicated over non-coding axes
#   repl_mean  per-device partial, replicated across coding ranks after the
#              collective -> sum over non-coding axes, mean over coding
_CORNER = ("participation", "wire_bytes_rank", "bytes_down")
_RANK_SUM = ("grad_norm_sq", "ef_norm_sq", "acc_norm_sq", "c_norm_sq",
             "acc_dot_c")
_RANK_VEC = ("bucket_wire_bytes", "moe_rows_held")
_REPL_MEAN = ("ghat_norm_sq", "update_norm_sq", "param_norm_sq")


def frame_out_specs(frame_abs: MetricsFrame, axis_names: Sequence[str]):
    """shard_map out_specs for a frame whose leaves were reshaped to
    (1,)*len(axis_names) + leaf.shape inside the body (the same idiom the
    train step uses for its per-device flat EF and optimizer state)."""
    from jax.sharding import PartitionSpec as P
    return jax.tree.map(
        lambda l: P(*axis_names, *([None] * l.ndim)), frame_abs)


def reduce_frame_grid(frame: MetricsFrame, mesh_axis_names: Sequence[str],
                      coding_axes: Sequence[str]
                      ) -> Dict[str, jnp.ndarray]:
    """Grid-shaped frame (every leaf leading with the mesh shape, as
    returned by the aggregation shard_map) -> host-friendly step metrics.

    Per-rank entries are ordered by `cocoef.coding_rank_index` (row-major
    over `coding_axes` in the order given).  Runs OUTSIDE the shard_map
    (plain jit or eager) — reductions here are over the replicated grid
    output, never over the mesh, so metrics add no collectives.
    """
    names = tuple(mesh_axis_names)
    m = len(names)
    code_pos = [names.index(a) for a in coding_axes]
    other_pos = [i for i in range(m) if i not in code_pos]
    # byte counters are computed per DEVICE from its local flat slice; a
    # coding rank spans every non-coding (tp/fsdp) mesh position, so rank
    # totals scale by that grid size (1 on a pure coding mesh)
    grid = frame.bytes_down.shape
    shards = int(np.prod([grid[i] for i in other_pos])) if other_pos else 1

    def corner(leaf):
        return leaf[(0,) * m]

    def rank_sum(leaf):                       # (mesh...,) -> (N,)
        t = jnp.transpose(leaf, code_pos + other_pos)
        t = t.sum(axis=tuple(range(len(code_pos), m)))
        return t.reshape(-1)

    def rank_vec(leaf):                       # (mesh..., *k) -> (N, *k)
        t = jnp.transpose(leaf, code_pos + other_pos
                          + list(range(m, leaf.ndim)))
        t = t[(slice(None),) * len(code_pos) + (0,) * len(other_pos)]
        return t.reshape((-1,) + leaf.shape[m:])

    def repl_mean(leaf):                      # (mesh...,) -> ()
        r = rank_sum(leaf)
        return r.mean()

    def safe_div(a, b):
        return a / jnp.where(b == 0, 1.0, b)

    participation = corner(frame.participation)
    wire_bytes_rank = corner(frame.wire_bytes_rank) * shards
    acc_sq = rank_sum(frame.acc_norm_sq)
    c_sq = rank_sum(frame.c_norm_sq)
    dot = rank_sum(frame.acc_dot_c)
    out = {
        "participation": participation,
        "participants": participation.sum(),
        "wire_bytes_rank": wire_bytes_rank,
        "bytes_up_total": wire_bytes_rank.sum(),
        "bucket_wire_bytes_rank": rank_vec(frame.bucket_wire_bytes) * shards,
        "bytes_down": corner(frame.bytes_down) * shards,
        "grad_norm_rank": jnp.sqrt(rank_sum(frame.grad_norm_sq)),
        "ef_norm_rank": jnp.sqrt(rank_sum(frame.ef_norm_sq)),
        # compressed-vs-raw cosine and EF contraction |acc-c|^2/|acc|^2
        # per coding rank (all-zero acc reports cosine 0, contraction 0)
        "compress_cosine_rank": safe_div(dot, jnp.sqrt(acc_sq) *
                                         jnp.sqrt(c_sq)),
        "compress_contraction_rank": safe_div(acc_sq + c_sq - 2.0 * dot,
                                              acc_sq),
        "ghat_norm": jnp.sqrt(repl_mean(frame.ghat_norm_sq)),
        "update_norm": jnp.sqrt(repl_mean(frame.update_norm_sq)),
        "param_norm": jnp.sqrt(repl_mean(frame.param_norm_sq)),
    }
    if frame.moe_rows_held is not None:
        out["moe_rows_held_rank"] = rank_vec(frame.moe_rows_held)
    return out


def frame_to_host(reduced: Dict[str, jnp.ndarray]) -> Dict[str, object]:
    """Device -> plain-python (lists/floats) for JSONL logging."""
    out = {}
    for k, v in reduced.items():
        a = np.asarray(v)
        out[k] = a.tolist() if a.ndim else float(a)
    return out
