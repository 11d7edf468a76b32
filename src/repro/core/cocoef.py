"""COCO-EF as a production optimizer transform over device-local flat state.

This is the piece that runs *inside* the fully-manual aggregation shard_map of
`repro.launch.train` (stage 2 in DESIGN.md Sec. 2): every (coding-rank,
tp-shard) device holds

  g_local   : its slice of this rank's coded gradient, flattened + padded
  e_local   : its slice of this rank's error vector  (Alg. 1 state)

and produces the aggregated update slice `ghat_local` (identical across
coding ranks, distinct across tp shards) plus the new error state.

The math is Algorithm 1 exactly:
  acc  = gamma * g + e
  c    = wire.roundtrip(acc)  (the wire IS the compressor: SignWire <->
                               grouped sign, SparseWire <-> block top-K,
                               DenseWire <-> identity; see collectives.py)
  ghat = sum_i mask_i c_i     (two-phase wire-compressed collective)
  e'   = mask ? acc - c : e

`mode` selects the paper's method or the baselines for A/B roofline runs:
  cocoef       biased sign + error feedback            (proposed)
  coco         biased sign, no error feedback          (Fig. 5 ablation)
  dense        no compression (SGC [31]; the dense-psum baseline)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.compat import axis_size
from repro.kernels import ref as kernel_ref
from repro.kernels.ops import backend_use_pallas
from repro.kernels.sign_pack import G_BLK as _SIGN_G_BLK
from repro.kernels.topk_pack import R_BLK as _TOPK_R_BLK
from repro.obs.metrics import MetricsFrame, norm_sq
from .collectives import (CodingCollectiveConfig, DenseWire, SignWire,
                          SparseWire, WireFormat, coded_allreduce_start,
                          dense_allreduce, two_phase_coded_allreduce)

__all__ = ["CocoEFConfig", "FlatMeta", "flatten_local", "unflatten_local",
           "padded_size", "cocoef_update", "coding_rank_index"]


@dataclasses.dataclass(frozen=True)
class CocoEFConfig:
    coding_axes: Tuple[str, ...] = ("data",)
    group_size: int = 512
    straggler_p: float = 0.0
    straggler_rates: Optional[Tuple[float, ...]] = None
    # ^ per-rank participation rates q_i (StragglerProcess.rates()) the
    #   encode weights were built with; None = scalar mean rate (eq. 3).
    #   Threaded so batch makers fold the SAME rate-aware W as the trainer.
    mode: str = "cocoef"              # cocoef | coco | dense
    compressor: str = "sign"          # sign | block_topk | topk | identity
    topk_k: int = 64                  # global-K budget (compressor="topk")
    k_per_block: Union[int, Tuple[int, ...]] = 8
    # ^ kept coords/block (compressor="block_topk"); a per-rank tuple (from
    #   sim.cost_model.solve_k_budgets) gives slow-uplink ranks smaller
    #   wire budgets (SparseWire per-rank budgets)
    block_size: int = 256             # sparsification block (compressor="block_topk")
    wire_dtype: str = "float32"       # sparse values / dense payload dtype
    ef_dtype: str = "float32"         # error-vector storage dtype
    phase2_dtype: str = "float32"     # f32 = paper-faithful broadcast
    phase2_sign: bool = False         # beyond-paper compressed broadcast
    num_buckets: int = 1              # split flat vector for comm overlap
    bucket_schedule: str = "pipelined"  # pipelined | serial (see below)
    # ^ "pipelined" double-buffers the per-bucket collectives: bucket i's
    #   all_to_all is issued, then bucket i+1's fused local step is traced
    #   BEFORE bucket i's decode/phase 2, so XLA's async collectives can
    #   overlap the wire transfer with compute.  Bit-for-bit identical to
    #   "serial" (same ops, reordered issue); "serial" kept as the
    #   schedule-parity reference.  With num_buckets=1 they coincide.
    backend: str = "auto"             # auto | pallas | jnp kernel dispatch

    def __post_init__(self):
        if self.bucket_schedule not in ("serial", "pipelined"):
            raise ValueError(f"unknown bucket_schedule "
                             f"{self.bucket_schedule!r}; have "
                             f"('serial', 'pipelined')")

    def collective(self) -> CodingCollectiveConfig:
        return CodingCollectiveConfig(
            coding_axes=self.coding_axes,
            group_size=self.group_size,
            phase2_dtype=jnp.dtype(self.phase2_dtype),
            phase2_sign=self.phase2_sign,
            backend=self.backend)

    def wire_format(self, n: int, nd: int) -> WireFormat:
        """Wire format for one bucket of `n` coords over `nd` chunks.

        Delegates to `plan.build_wire` — the one mapping from compressor
        name + knobs to a WireFormat, shared with `PlanSpec.wire`."""
        from .plan import build_wire
        return build_wire(self.compressor, group_size=self.group_size,
                          k_per_block=self.k_per_block,
                          block_size=self.block_size, topk_k=self.topk_k,
                          value_dtype=self.wire_dtype, n=n, nd=nd,
                          num_buckets=self.num_buckets)

    @property
    def pad_multiple(self) -> int:
        """Per-bucket flat-size alignment (feeds `padded_size`): a whole
        row tile of the wire kernels, so every train-path chunk takes the
        Pallas path (`kernels.ops.resolve_use_pallas` never falls back).
        The sign tile always participates (phase-2 re-compression packs
        the chunk with `group_size`), joined with the sparse tile when
        active."""
        tile = _SIGN_G_BLK * self.group_size
        if self.compressor == "block_topk":
            return math.lcm(tile, _TOPK_R_BLK * self.block_size)
        return tile


# --------------------------------------------------------------------------
# local flatten/unflatten with padding
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatMeta:
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    dtypes: Tuple[str, ...]
    padded: int


def padded_size(total: int, chunk_ranks: int, group_size: int,
                num_buckets: int = 1) -> int:
    mult = chunk_ranks * group_size * num_buckets
    return math.ceil(total / mult) * mult


def flatten_local(leaves: Sequence[jnp.ndarray], chunk_ranks: int,
                  group_size: int, num_buckets: int = 1
                  ) -> Tuple[jnp.ndarray, FlatMeta]:
    """Concat device-local leaf blocks into one padded f32 vector."""
    sizes = tuple(int(l.size) for l in leaves)
    total = sum(sizes)
    padded = padded_size(total, chunk_ranks, group_size, num_buckets)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    flat = jnp.pad(flat, (0, padded - total))
    meta = FlatMeta(shapes=tuple(tuple(l.shape) for l in leaves), sizes=sizes,
                    dtypes=tuple(str(l.dtype) for l in leaves), padded=padded)
    return flat, meta


def unflatten_local(flat: jnp.ndarray, meta: FlatMeta) -> List[jnp.ndarray]:
    out, off = [], 0
    for shape, size, dt in zip(meta.shapes, meta.sizes, meta.dtypes):
        out.append(lax.dynamic_slice_in_dim(flat, off, size)
                   .reshape(shape).astype(jnp.dtype(dt)))
        off += size
    return out


# --------------------------------------------------------------------------
# the update (runs per device inside the fully-manual shard_map)
# --------------------------------------------------------------------------

def coding_rank_index(coding_axes: Sequence[str]) -> jnp.ndarray:
    """Row-major linear index of this device among the coding ranks."""
    idx = jnp.zeros((), jnp.int32)
    for ax in coding_axes:
        idx = idx * axis_size(ax) + lax.axis_index(ax)
    return idx


def _bucketed(flat: jnp.ndarray, num_buckets: int):
    return flat.reshape(num_buckets, -1)


def _check_rank_budgets(wire, mask: jnp.ndarray) -> None:
    """A per-rank-budget wire must carry exactly one budget per coding
    rank — jnp's clamped indexing would otherwise make a short tuple
    silently reuse the last budget for the out-of-range ranks."""
    if wire.has_rank_budgets() and len(wire.k_per_block) != mask.shape[0]:
        raise ValueError(
            f"wire has {len(wire.k_per_block)} per-rank budgets, the "
            f"coding collective has {mask.shape[0]} ranks")


def _joined(parts: List[jnp.ndarray]) -> jnp.ndarray:
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _flat_outputs(*xs: jnp.ndarray):
    """Hand the step its flat (n,) results as arrays of their own.  The
    wire kernels and their jnp twins work on (rows, group) tiles; left
    free, the TPU compiler fuses that relayout into every consumer (the
    optimizer update and the unflatten slices of each leaf), and its code
    generation then grows with n: minutes per compile at 10^8 coords.
    The barriers change no value; one per array keeps each array's
    varying mesh axes its own."""
    return tuple(lax.optimization_barrier(x) for x in xs)


class _BucketSchedule:
    """Per-bucket collective issue order (CocoEFConfig.bucket_schedule).

    serial:     submit(b) = start + finish immediately — bucket b's decode
                and phase 2 are traced before bucket b+1 does anything.
    pipelined:  submit(b) issues bucket b's all_to_all and holds the
                in-flight handle; the PREVIOUS bucket is finished only
                after the next one's local step + all_to_all have been
                traced (window-2 double buffer), so the compiler can hide
                bucket b's wire transfer behind bucket b+1's compute.

    Both produce identical values — the same ops run, only the issue
    order differs — which test_backend_parity pins down bitwise."""

    def __init__(self, schedule: str, coll: CodingCollectiveConfig,
                 mask: jnp.ndarray):
        self.pipelined = schedule == "pipelined"
        self.coll = coll
        self.mask = mask
        self._pending = None
        self._parts: List[jnp.ndarray] = []

    def submit(self, wire: WireFormat, payload) -> None:
        if not self.pipelined:
            self._parts.append(two_phase_coded_allreduce(
                None, wire, self.coll, self.mask, payload=payload))
            return
        nxt = coded_allreduce_start(wire, self.coll, self.mask, payload)
        if self._pending is not None:
            self._parts.append(self._pending.finish())
        self._pending = nxt

    def collect(self) -> List[jnp.ndarray]:
        if self._pending is not None:
            self._parts.append(self._pending.finish())
            self._pending = None
        return self._parts


def cocoef_update(g_local: jnp.ndarray, e_local: jnp.ndarray,
                  mask: Optional[jnp.ndarray], gamma, cfg: CocoEFConfig,
                  *, mask_provider: Optional[Callable] = None,
                  key: Optional[jnp.ndarray] = None,
                  step=None, want_metrics: bool = False):
    """One Algorithm-1 update on the device-local flat slice.

    g_local: (n,) local slice of this coding rank's coded gradient.
    e_local: (n,) local slice of this rank's error vector (cfg.ef_dtype).
    mask:    (n_coding,) straggler indicators I_i^t (same on all devices);
             may be None when `mask_provider` is given.
    gamma:   scalar learning rate (may be traced — lr schedules).
    mask_provider: optional hook `(key, step) -> (n_coding,) mask` — any
             `repro.sim.StragglerProcess.mask` qualifies.  Must be pure in
             (key, step) so every coding rank derives the identical mask
             without communication; called here (inside the shard_map /
             jit scope), with `key`/`step` threaded through.
    want_metrics: when True additionally return a `repro.obs.MetricsFrame`
             of in-graph telemetry (per-rank wire bytes, EF/compression
             norms, the acc-vs-C(acc) cosine inputs) — computed from
             values the step already has plus a local unpack where the
             hot path skips c; NO extra collectives.  When False (the
             default) the traced computation is `_cocoef_update_impl`,
             the pre-telemetry body verbatim, so the compiled step is
             byte-identical to a build without metrics (pinned by
             tests/test_obs.py).
    Returns (ghat_local, new_e_local) — plus the frame when requested;
    ghat is sum_i mask_i C_or_id(acc_i), already scaled by gamma per
    eq. (4): apply as  params -= ghat.

    Execution routes through the wire's fused backend (cfg.backend):
    `wire.fused_local_step` produces payload + new error in one pass over
    g/e (cocoef), and coco/dense never materialize the reconstruction c.
    """
    if mask is None:
        if mask_provider is None:
            raise ValueError("need a mask or a mask_provider hook")
        mask = mask_provider(key, step)
    if want_metrics:
        return _cocoef_update_metrics(g_local, e_local, mask, gamma, cfg)
    return _cocoef_update_impl(g_local, e_local, mask, gamma, cfg)


def _cocoef_update_impl(g_local: jnp.ndarray, e_local: jnp.ndarray,
                        mask: jnp.ndarray, gamma, cfg: CocoEFConfig
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The metrics-free update — the pre-telemetry `cocoef_update` body,
    kept verbatim so the default path provably traces the same HLO."""
    coll = cfg.collective()
    my_idx = coding_rank_index(cfg.coding_axes)
    my_mask = lax.dynamic_index_in_dim(mask, my_idx, keepdims=False)

    if cfg.mode == "dense":
        acc = gamma * g_local
        ghat = dense_allreduce(acc, coll, mask)
        return ghat, e_local

    nd = axis_size(coll.chunk_axis)
    use_pallas = backend_use_pallas(cfg.backend)

    if cfg.mode == "coco":
        # no error feedback: pack-and-send only — C(acc) is never needed
        # locally, so neither c nor the dead bucket concat is materialized
        sched = _BucketSchedule(cfg.bucket_schedule, coll, mask)
        for acc_b in _bucketed(gamma * g_local, cfg.num_buckets):
            wire = cfg.wire_format(acc_b.shape[0], nd)
            _check_rank_budgets(wire, mask)
            payload = wire.apply_rank_budget(
                wire.fused_pack(acc_b, use_pallas=use_pallas), my_idx)
            sched.submit(wire, payload)
        (ghat,) = _flat_outputs(_joined(sched.collect()))
        return ghat, e_local

    # cocoef: fused accumulate + compress + error update per bucket.
    # Under the pipelined schedule bucket b's local step is traced before
    # bucket b-1's decode/phase 2 (the _BucketSchedule window), so the
    # wire transfer of one bucket hides behind the compression of the next.
    sched = _BucketSchedule(cfg.bucket_schedule, coll, mask)
    e_parts = []
    for g_b, e_b in zip(_bucketed(g_local, cfg.num_buckets),
                        _bucketed(e_local, cfg.num_buckets)):
        wire = cfg.wire_format(g_b.shape[0], nd)
        _check_rank_budgets(wire, mask)
        if wire.has_rank_budgets():
            # per-rank wire budgets: the truncation below this rank's budget
            # must feed the error vector, so reconstruct c from the
            # budget-masked payload instead of taking the fused kernel's
            # full-budget error update
            acc_b = kernel_ref.mul_add(gamma, g_b, e_b)
            payload = wire.apply_rank_budget(
                wire.fused_pack(acc_b, use_pallas=use_pallas), my_idx)
            c_b = wire.unpack(payload)
            e_new_b = jnp.where(my_mask > 0, acc_b - c_b,
                                e_b.astype(jnp.float32))
        else:
            payload, _, e_new_b = wire.fused_local_step(
                g_b, e_b, gamma, my_mask, use_pallas=use_pallas, want_c=False)
        sched.submit(wire, payload)
        e_parts.append(e_new_b)
    return _flat_outputs(_joined(sched.collect()),
                         _joined(e_parts).astype(jnp.dtype(cfg.ef_dtype)))


def _cocoef_update_metrics(g_local: jnp.ndarray, e_local: jnp.ndarray,
                           mask: jnp.ndarray, gamma, cfg: CocoEFConfig
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, MetricsFrame]:
    """`cocoef_update` with an in-graph `MetricsFrame` third output.

    Same math and the same collectives as `_cocoef_update_impl`; the extra
    work is device-LOCAL only: the wire-byte constants are static numpy
    (mask-multiplied in-graph), and the compression-quality scalars reuse
    the c the fused kernels can otherwise skip (want_c=True here, plus a
    local unpack on the coco path).  The frame's optimizer fields
    (update/param norms) are zero — `optim.apply_update(want_norms=True)`
    fills them in the caller."""
    coll = cfg.collective()
    my_idx = coding_rank_index(cfg.coding_axes)
    my_mask = lax.dynamic_index_in_dim(mask, my_idx, keepdims=False)
    N = mask.shape[0]
    B = cfg.num_buckets
    n = g_local.shape[0]
    f32 = jnp.float32
    maskf = mask.astype(f32)
    zero = jnp.zeros((), f32)
    grad_sq = norm_sq(g_local)
    down_bytes = float(n * jnp.dtype(cfg.phase2_dtype).itemsize)

    def finish(ghat, e_stored, bytes_rank, bucket_rank, acc_sq, c_sq, dot):
        # bytes_rank (N,), bucket_rank (B, N): STATIC per-rank phase-1
        # bytes; "actually sent" = x the participation mask, matching the
        # StepTimer ledger (trace @ rank_wire_bytes) per step exactly
        bucket_mine = jnp.take(jnp.asarray(bucket_rank, f32), my_idx, axis=1)
        return MetricsFrame(
            participation=maskf,
            wire_bytes_rank=jnp.asarray(bytes_rank, f32) * maskf,
            bucket_wire_bytes=bucket_mine * my_mask.astype(f32),
            bytes_down=jnp.asarray(down_bytes, f32),
            grad_norm_sq=grad_sq, ef_norm_sq=norm_sq(e_stored),
            acc_norm_sq=acc_sq, c_norm_sq=c_sq, acc_dot_c=dot,
            ghat_norm_sq=norm_sq(ghat),
            update_norm_sq=zero, param_norm_sq=zero)

    if cfg.mode == "dense":
        acc = gamma * g_local
        ghat = dense_allreduce(acc, coll, mask)
        # the dense psum ships the f32 accumulator (SGC baseline wire)
        bytes_rank = DenseWire(value_dtype="float32").rank_wire_bytes(n, N)
        bucket_rank = np.repeat(bytes_rank[None].astype(np.float64) / B,
                                B, axis=0)
        acc_sq = norm_sq(acc)         # identity compressor: c == acc
        frame = finish(ghat, e_local, bytes_rank, bucket_rank,
                       acc_sq, acc_sq, acc_sq)
        return ghat, e_local, frame

    nd = axis_size(coll.chunk_axis)
    use_pallas = backend_use_pallas(cfg.backend)
    acc_sq = c_sq = dot = zero
    bytes_rank = np.zeros((N,), np.int64)
    bucket_rows = []

    if cfg.mode == "coco":
        sched = _BucketSchedule(cfg.bucket_schedule, coll, mask)
        for acc_b in _bucketed(gamma * g_local, B):
            wire = cfg.wire_format(acc_b.shape[0], nd)
            _check_rank_budgets(wire, mask)
            payload = wire.apply_rank_budget(
                wire.fused_pack(acc_b, use_pallas=use_pallas), my_idx)
            c_b = wire.unpack(payload)    # metrics-only local decode
            acc_sq = acc_sq + norm_sq(acc_b)
            c_sq = c_sq + norm_sq(c_b)
            dot = dot + jnp.sum(acc_b.astype(f32) * c_b)
            rb = wire.rank_wire_bytes(acc_b.shape[0], N)
            bytes_rank = bytes_rank + rb
            bucket_rows.append(rb)
            sched.submit(wire, payload)
        (ghat,) = _flat_outputs(_joined(sched.collect()))
        frame = finish(ghat, e_local, bytes_rank, np.stack(bucket_rows),
                       acc_sq, c_sq, dot)
        return ghat, e_local, frame

    sched = _BucketSchedule(cfg.bucket_schedule, coll, mask)
    e_parts = []
    for g_b, e_b in zip(_bucketed(g_local, B), _bucketed(e_local, B)):
        wire = cfg.wire_format(g_b.shape[0], nd)
        _check_rank_budgets(wire, mask)
        acc_b = kernel_ref.mul_add(gamma, g_b, e_b)
        if wire.has_rank_budgets():
            payload = wire.apply_rank_budget(
                wire.fused_pack(acc_b, use_pallas=use_pallas), my_idx)
            c_b = wire.unpack(payload)
            e_new_b = jnp.where(my_mask > 0, acc_b - c_b,
                                e_b.astype(jnp.float32))
        else:
            payload, c_b, e_new_b = wire.fused_local_step(
                g_b, e_b, gamma, my_mask, use_pallas=use_pallas, want_c=True)
        acc_sq = acc_sq + norm_sq(acc_b)
        c_sq = c_sq + norm_sq(c_b)
        dot = dot + jnp.sum(acc_b.astype(f32) * c_b)
        rb = wire.rank_wire_bytes(g_b.shape[0], N)
        bytes_rank = bytes_rank + rb
        bucket_rows.append(rb)
        sched.submit(wire, payload)
        e_parts.append(e_new_b)
    ghat, new_e = _flat_outputs(
        _joined(sched.collect()),
        _joined(e_parts).astype(jnp.dtype(cfg.ef_dtype)))
    frame = finish(ghat, new_e, bytes_rank, np.stack(bucket_rows),
                   acc_sq, c_sq, dot)
    return ghat, new_e, frame
