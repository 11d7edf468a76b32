"""Wire-compressed collective aggregation for COCO-EF on a TPU mesh.

The paper's device->server->device exchange maps onto a two-phase collective
over the coding axes (DESIGN.md Sec. 2):

  phase 1 (device -> "server"):  each coding rank packs C(acc_i) into its
     wire format and `all_to_all`s chunk j to rank j; rank j decodes every
     sender's chunk, applies the straggler mask of the *sender*, and sums.
     This leg carries the compressed payload -> ~26x fewer bytes than a
     dense f32 all-reduce leg for group_size=512 sign quantization, and
     ~21x for block top-K at k/B = 8/512.
  phase 2 ("server" -> device):  the aggregated dense chunk is `all_gather`ed
     back.  Paper-faithful mode sends f32 (the paper's server broadcast is
     uncompressed); `phase2_dtype=bf16` and `phase2_sign=True` are
     beyond-paper options evaluated in EXPERIMENTS.md §Perf.

When the coding runs over two mesh axes (e.g. ("pod", "data")) the phases are
hierarchical: all_to_all within the minor axis, psum across the major axis on
the decoded chunk, gather within the minor axis.

WireFormat contract
-------------------
A `WireFormat` is a frozen dataclass describing how a flat f32 vector is
serialized for the phase-1 leg.  Implementations provide:

  pack(x)          (n,) f32 -> tuple of arrays (the payload).  Every payload
                   leaf has leading dimension proportional to n, so chunking
                   for the all_to_all is the generic reshape
                   `leaf.reshape((nd, leaf.shape[0] // nd) + rest)`.
  unpack(payload)  payload -> (n,) f32, the decompressed vector.  Must be
                   vmap-safe (it is vmapped over senders on the decode side).
  wire_bytes(n)    bytes on the wire for one rank's phase-1 payload.
  check(n, nd)     raise ValueError unless n is compatible with this wire
                   format and `nd` all_to_all chunks (pad upstream with
                   `repro.core.cocoef.padded_size`).
  alignment()      n must be a multiple of `nd * alignment()`.

`roundtrip(x) = unpack(pack(x))` realizes the wire's compressor on the train
path: SignWire <-> GroupedSign (lossless re-pack), SparseWire <-> BlockTopK
(1-2 ulp from the per-block scale normalization), DenseWire <-> Identity.
Roundtrips are idempotent, so the collective may pack an already-compressed
vector without changing it (beyond ulp-level rescaling noise).

Fused execution backend
-----------------------
The train hot path does NOT run the pure-jnp pack/unpack above — those are
the semantic contract (and the `backend="jnp"` reference).  Two fused entry
points route the per-step work through `repro.kernels` (Pallas on TPU,
interpret mode elsewhere, jnp oracles as the fallback):

  fused_local_step(g, e, gamma, mask_self)
                   one HBM pass producing (payload, c, e_new) — the whole
                   Algorithm-1 local step (accumulate + compress + error
                   update) without materializing intermediates.
  decode_reduce(payloads, sender_mask)
                   fused decode + straggler-mask + sum over senders; never
                   materializes the per-sender dense (nd, n/nd) tensor.
  payload_n(payload)
                   flat length a payload represents (lets hot-path callers
                   skip carrying the dense c alongside the payload).

Base-class implementations compose pack/unpack in plain jnp, so every new
wire format arrives with a working fused path by construction; SignWire and
SparseWire override them to dispatch into `kernels.ops` (ef_sign_fused /
ef_topk_fused / sign_decode_reduce / topk_decode_reduce).  The
`CodingCollectiveConfig.backend` knob ("auto" | "pallas" | "jnp") selects
the implementation; "auto" uses Pallas exactly when running on TPU.

Everything here runs inside a *fully manual* shard_map: inputs are the
device-local flat gradient/error vectors.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.compat import all_gather_invariant, axis_size
from repro.kernels import ops as kernel_ops, ref as kernel_ref
from repro.kernels.sign_pack import G_BLK as _SIGN_G_BLK
from repro.kernels.topk_pack import R_BLK as _TOPK_R_BLK

__all__ = [
    "sign_pack",
    "sign_unpack",
    "WireFormat",
    "SignWire",
    "SparseWire",
    "DenseWire",
    "get_wire",
    "wire_for_compressor",
    "CodingCollectiveConfig",
    "InFlightAggregate",
    "coded_allreduce_start",
    "two_phase_coded_allreduce",
    "two_phase_sign_allreduce",
    "dense_allreduce",
    "wire_bytes_sign",
]


# --------------------------------------------------------------------------
# sign wire primitives (shared with kernels/ref.py semantics)
# --------------------------------------------------------------------------

def sign_pack(x: jnp.ndarray, group_size: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pack a flat f32 vector into (bits: uint32 (n/32,), scales: f32 (n/g,)).

    scales[m] = ||x_m||_1 / |I_m|  (eq. 5); bit j of word w = 1  iff
    x[32*w + j] >= 0.  Requires n % lcm(32, group_size) == 0 (pad upstream).
    """
    n = x.shape[0]
    g = group_size
    if n % g or g % 32:
        raise ValueError(f"need group_size % 32 == 0 and n % group_size == 0 "
                         f"(n={n}, g={g})")
    xf = x.astype(jnp.float32)
    scales = jnp.mean(jnp.abs(xf.reshape(-1, g)), axis=-1)
    bits = (xf >= 0).reshape(-1, 32).astype(jnp.uint32)
    words = (bits << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)
    return words, scales


def sign_unpack(words: jnp.ndarray, scales: jnp.ndarray, group_size: int,
                dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of sign_pack: returns sign(x) * scale_group, flat (n,)."""
    bits = (words[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    signs = bits.astype(dtype).reshape(-1, group_size) * 2.0 - 1.0
    # per-group scale via broadcast (jnp.repeat lowers to a scatter loop)
    return (signs * scales.astype(dtype)[:, None]).reshape(-1)


def wire_bytes_sign(n: int, group_size: int) -> int:
    """Bytes on the wire for one rank's phase-1 payload."""
    return n // 8 + 4 * (n // group_size)


# --------------------------------------------------------------------------
# wire formats
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WireFormat:
    """Base class; subclasses are frozen dataclasses => valid static args."""

    def pack(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
        raise NotImplementedError

    def unpack(self, payload: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
        raise NotImplementedError

    def wire_bytes(self, n: int) -> int:
        raise NotImplementedError

    def alignment(self) -> int:
        raise NotImplementedError

    def check(self, n: int, nd: int = 1) -> None:
        a = self.alignment()
        if n <= 0 or n % (nd * a):
            raise ValueError(
                f"{type(self).__name__}: flat size {n} must be a positive "
                f"multiple of chunk_count*alignment = {nd}*{a}; pad upstream")

    def roundtrip(self, x: jnp.ndarray) -> jnp.ndarray:
        """The wire's compressor: what the receivers reconstruct."""
        return self.unpack(self.pack(x))

    # ---- fused execution backend (see module docstring) -------------------
    # Base implementations compose pack/unpack in plain jnp so that every
    # wire format has a working fused path by construction; performance-
    # critical wires override them to dispatch into repro.kernels.

    def payload_n(self, payload: Tuple[jnp.ndarray, ...]) -> int:
        """Flat length (n) the payload represents.  The base default
        decompresses to find out — correct for any wire, but traces an
        unpack; override with shape arithmetic (all built-ins do)."""
        return self.unpack(payload).shape[0]

    def fused_local_step(self, g: jnp.ndarray, e: jnp.ndarray, gamma,
                         mask_self, use_pallas: Optional[bool] = None,
                         want_c: bool = True
                         ) -> Tuple[Tuple[jnp.ndarray, ...],
                                    Optional[jnp.ndarray], jnp.ndarray]:
        """Whole Algorithm-1 local step in one pass over the flat vectors:

          acc     = gamma * g + e
          payload = pack(acc)
          c       = the transmitted reconstruction C(acc)
          e_new   = mask_self ? acc - c : e

        Returns (payload, c, e_new); c and e_new are f32.  `use_pallas`
        overrides the platform default (None = Pallas iff on TPU).
        want_c=False returns c=None and lets the kernels skip the
        full-vector c store (the train path only ships the payload)."""
        acc = kernel_ref.mul_add(gamma, g, e)
        payload = self.pack(acc)
        c = self.unpack(payload)
        e_new = jnp.where(mask_self > 0, acc - c, e.astype(jnp.float32))
        return payload, (c if want_c else None), e_new

    def fused_pack(self, x: jnp.ndarray, use_pallas: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, ...]:
        """pack(x) through the kernel backend (the non-EF hot path, where
        no error state is carried and no reconstruction c is needed)."""
        return self.pack(x)

    def decode_reduce(self, payloads: Tuple[jnp.ndarray, ...],
                      sender_mask: jnp.ndarray,
                      use_pallas: Optional[bool] = None) -> jnp.ndarray:
        """sum_i sender_mask_i * unpack(payloads_i) over the leading
        (sender) dimension of every payload leaf -> (n,) f32."""
        decoded = jax.vmap(lambda *p: self.unpack(p))(*payloads)
        return (sender_mask[:, None] * decoded).sum(axis=0)

    # ---- per-rank adaptive wire budgets -----------------------------------
    # A wire may carry a *per-rank* budget vector (SparseWire with a tuple
    # k_per_block, sized by repro.sim.cost_model.solve_k_budgets): every rank
    # ships the same fixed payload SHAPE (the all_to_all needs static
    # shapes), but entries beyond rank i's budget are zeroed at pack time
    # and charged per-rank by the cost model / comm audit.

    def has_rank_budgets(self) -> bool:
        """True when this wire carries per-rank budgets (see SparseWire)."""
        return False

    def apply_rank_budget(self, payload: Tuple[jnp.ndarray, ...],
                          rank) -> Tuple[jnp.ndarray, ...]:
        """Zero payload entries beyond `rank`'s budget (identity for
        uniform-budget wires).  `rank` may be a traced scalar."""
        return payload

    def rank_wire_bytes(self, n: int, num_ranks: int) -> np.ndarray:
        """(num_ranks,) int64 phase-1 bytes per rank — the per-rank
        refinement of `wire_bytes` (uniform unless the wire carries
        per-rank budgets)."""
        return np.full((num_ranks,), int(self.wire_bytes(n)), np.int64)


@dataclasses.dataclass(frozen=True)
class SignWire(WireFormat):
    """Grouped sign quantization on the wire: 1 bit/coord + f32 scale/group.

    Exactly representable inputs (sign(x)*scale_group, incl. StochasticSign
    outputs) roundtrip bit-for-bit; sign(±0) := +1.
    """

    group_size: int = 512

    def pack(self, x):
        return sign_pack(x, self.group_size)

    def unpack(self, payload):
        words, scales = payload
        return sign_unpack(words, scales, self.group_size)

    def wire_bytes(self, n):
        return wire_bytes_sign(n, self.group_size)

    def alignment(self):
        return self.group_size

    def payload_n(self, payload):
        return payload[0].shape[0] * 32

    def _tile(self) -> int:
        return _SIGN_G_BLK * self.group_size

    def fused_pack(self, x, use_pallas=None):
        use = kernel_ops.resolve_use_pallas(use_pallas, x.shape[0],
                                            self._tile(), op="sign_pack",
                                            dtype=x.dtype)
        with jax.named_scope("wire/sign_pack"):
            return kernel_ops.sign_pack(x, self.group_size, use_pallas=use)

    def fused_local_step(self, g, e, gamma, mask_self, use_pallas=None,
                         want_c=True):
        use = kernel_ops.resolve_use_pallas(use_pallas, g.shape[0],
                                            self._tile(),
                                            op="ef_sign_fused", dtype=g.dtype)
        with jax.named_scope("wire/ef_sign_local_step"):
            words, scales, c, e_new = kernel_ops.ef_sign_fused(
                g, e, gamma, mask_self, self.group_size, want_c=want_c,
                use_pallas=use)
        return (words, scales), c, e_new

    def decode_reduce(self, payloads, sender_mask, use_pallas=None):
        words, scales = payloads
        use = kernel_ops.resolve_use_pallas(use_pallas, words.shape[1] * 32,
                                            self._tile(),
                                            op="sign_decode_reduce",
                                            dtype=scales.dtype)
        with jax.named_scope("wire/sign_decode_reduce"):
            return kernel_ops.sign_decode_reduce(words, scales, sender_mask,
                                                 self.group_size,
                                                 use_pallas=use)


@dataclasses.dataclass(frozen=True)
class SparseWire(WireFormat):
    """Block-local top-K on the wire (Ye & Abbe 2018 comm-efficient coding).

    Payload per block of `block_size` coords:
      indices : (nblocks, k) uint16 (uint32 when block_size > 65536) —
                in-block positions of the k largest-|.| entries, in
                decreasing-magnitude order, first occurrence wins ties
                (matches `kernels.topk_block` / `lax.top_k`).
      values  : (nblocks, k) value_dtype — kept entries normalized by the
                block scale (|v| <= 1), enabling narrow value dtypes.
      scales  : (nblocks,) f32 — per-block max-|.| (1.0 for all-zero blocks).

    roundtrip == BlockTopK.apply up to 1-2 ulp of the scale normalization;
    delta = 1 - k/block_size (Assumption 5).

    `k_per_block` may be a per-rank tuple (one budget per coding rank,
    typically from `repro.sim.cost_model.solve_k_budgets`): the payload is
    shaped by max(k) on every rank (static shapes for the all_to_all), and
    `apply_rank_budget` zeroes the values beyond rank i's budget so
    slow-uplink ranks effectively send fewer coordinates.  `wire_bytes`
    then reports the max-budget (shipped-shape) bytes; the honest per-rank
    on-the-wire accounting is `rank_wire_bytes` (zeros beyond the budget
    cost nothing under length framing), which is what the cost model and
    the comm-volume audit charge.
    """

    k_per_block: Union[int, Tuple[int, ...]] = 8
    block_size: int = 256
    value_dtype: str = "float32"

    def __post_init__(self):
        ks = self.k_per_block
        if isinstance(ks, (list, tuple, np.ndarray)):
            ks = tuple(int(k) for k in np.asarray(ks).reshape(-1))
            if not ks:
                raise ValueError("per-rank k_per_block must be non-empty")
            object.__setattr__(self, "k_per_block", ks)
        else:
            ks = (int(ks),)
        for k in ks:
            if not (0 < k <= self.block_size):
                raise ValueError(f"need 0 < k_per_block <= block_size, got "
                                 f"{k} / {self.block_size}")

    @property
    def k_max(self) -> int:
        """Largest per-rank budget = the shipped payload's k dimension."""
        ks = self.k_per_block
        return max(ks) if isinstance(ks, tuple) else ks

    def has_rank_budgets(self) -> bool:
        return isinstance(self.k_per_block, tuple)

    def for_rank(self, rank: int) -> "SparseWire":
        """The scalar-budget wire rank `rank` semantically transmits."""
        if not self.has_rank_budgets():
            return self
        return dataclasses.replace(
            self, k_per_block=int(self.k_per_block[rank]))

    def apply_rank_budget(self, payload, rank):
        if not self.has_rank_budgets():
            return payload
        idx, values, scales = payload
        k_i = jnp.asarray(self.k_per_block, jnp.int32)[
            jnp.asarray(rank, jnp.int32)]
        keep = jnp.arange(self.k_max, dtype=jnp.int32) < k_i     # (k_max,)
        # top-k indices within a block are distinct, so zeroing the values
        # beyond the budget is exactly the k_i-budget payload
        values = jnp.where(keep[None, :], values, jnp.zeros_like(values))
        return idx, values, scales

    def rank_wire_bytes(self, n, num_ranks):
        if not self.has_rank_budgets():
            return np.full((num_ranks,), int(self.wire_bytes(n)), np.int64)
        if len(self.k_per_block) != num_ranks:
            raise ValueError(f"wire has {len(self.k_per_block)} per-rank "
                             f"budgets, asked for {num_ranks} ranks")
        return np.asarray([self.for_rank(i).wire_bytes(n)
                           for i in range(num_ranks)], np.int64)

    @property
    def index_dtype(self):
        return jnp.uint16 if self.block_size <= (1 << 16) else jnp.uint32

    def pack(self, x):
        xf = x.astype(jnp.float32)
        blocks = xf.reshape(-1, self.block_size)
        mag = jnp.abs(blocks)
        topv, idx = lax.top_k(mag, self.k_max)              # (nb, k)
        sv = jnp.take_along_axis(blocks, idx, axis=-1)      # signed values
        scale = topv[:, 0]            # block max |.| = first top-k value
        safe = jnp.where(scale == 0, 1.0, scale)
        values = (sv / safe[:, None]).astype(jnp.dtype(self.value_dtype))
        return idx.astype(self.index_dtype), values, safe

    def unpack(self, payload):
        idx, values, scales = payload
        nb, k = idx.shape
        n = nb * self.block_size
        sv = values.astype(jnp.float32) * scales[:, None]
        base = jnp.arange(nb, dtype=jnp.int32)[:, None] * self.block_size
        flat_idx = (base + idx.astype(jnp.int32)).reshape(-1)
        return jnp.zeros((n,), jnp.float32).at[flat_idx].set(sv.reshape(-1))

    def wire_bytes(self, n):
        nb = n // self.block_size
        idx_b = 2 if self.block_size <= (1 << 16) else 4
        val_b = jnp.dtype(self.value_dtype).itemsize
        return nb * (self.k_max * (idx_b + val_b) + 4)  # + f32 scale

    def alignment(self):
        return self.block_size

    def payload_n(self, payload):
        return payload[2].shape[0] * self.block_size

    def _tile(self) -> int:
        return _TOPK_R_BLK * self.block_size

    def fused_pack(self, x, use_pallas=None):
        use = kernel_ops.resolve_use_pallas(use_pallas, x.shape[0],
                                            self._tile(), op="topk_pack",
                                            dtype=self.value_dtype)
        with jax.named_scope("wire/topk_pack"):
            idx, val, scale = kernel_ops.topk_pack(x, self.k_max,
                                                   self.block_size,
                                                   use_pallas=use)
        return (idx.astype(self.index_dtype),
                val.astype(jnp.dtype(self.value_dtype)), scale)

    def fused_local_step(self, g, e, gamma, mask_self, use_pallas=None,
                         want_c=True):
        use = kernel_ops.resolve_use_pallas(use_pallas, g.shape[0],
                                            self._tile(),
                                            op="ef_topk_fused",
                                            dtype=self.value_dtype)
        # The kernels quantize in-register (normalize -> value_dtype ->
        # denormalize), so their c IS the transmitted reconstruction the
        # receivers decode (`values * scale` after value-dtype rounding)
        # and e_new already tracks acc - C(acc) with C == unpack∘pack —
        # which the reference-vs-mesh parity gate demands of the error
        # vector.  No unpack-of-pack scatter here, and want_c=False lets
        # the kernel skip the full-vector c store again.
        with jax.named_scope("wire/ef_topk_local_step"):
            idx, val, scale, c_q, e_new = kernel_ops.ef_topk_fused(
                g, e, gamma, mask_self, self.k_max, self.block_size,
                want_c=want_c, value_dtype=self.value_dtype, use_pallas=use)
        # val carries value_dtype-rounded numbers in f32: the cast is exact
        payload = (idx.astype(self.index_dtype),
                   val.astype(jnp.dtype(self.value_dtype)), scale)
        return payload, c_q, e_new

    def decode_reduce(self, payloads, sender_mask, use_pallas=None):
        idx, val, scales = payloads
        use = kernel_ops.resolve_use_pallas(
            use_pallas, idx.shape[1] * self.block_size, self._tile(),
            op="topk_decode_reduce", dtype=self.value_dtype)
        with jax.named_scope("wire/topk_decode_reduce"):
            return kernel_ops.topk_decode_reduce(idx, val, scales,
                                                 sender_mask,
                                                 self.block_size,
                                                 use_pallas=use)


@dataclasses.dataclass(frozen=True)
class DenseWire(WireFormat):
    """Uncompressed fallback: the flat vector, optionally narrowed to bf16.

    f32 roundtrips bit-exact (the SGC [31] baseline wire); bf16 is the
    beyond-paper half-width dense wire.
    """

    value_dtype: str = "float32"

    def pack(self, x):
        return (x.astype(jnp.dtype(self.value_dtype)),)

    def unpack(self, payload):
        return payload[0].astype(jnp.float32)

    def wire_bytes(self, n):
        return n * jnp.dtype(self.value_dtype).itemsize

    def alignment(self):
        return 1

    def payload_n(self, payload):
        return payload[0].shape[0]

    def decode_reduce(self, payloads, sender_mask, use_pallas=None):
        return kernel_ops.dense_decode_reduce(payloads[0], sender_mask,
                                              use_pallas=use_pallas)


_WIRE_REGISTRY = {
    # NOTE: no "topk" alias — the global-top-K spelling of
    # CocoEFConfig.compressor needs (n, nd) to size its per-chunk blocks;
    # use wire_for_compressor / CocoEFConfig.wire_format for that.
    "sign": SignWire,
    "sparse": SparseWire,
    "dense": DenseWire,
}


def get_wire(name: str, **kwargs) -> WireFormat:
    if name not in _WIRE_REGISTRY:
        raise KeyError(f"unknown wire format {name!r}; "
                       f"have {sorted(_WIRE_REGISTRY)}")
    return _WIRE_REGISTRY[name](**kwargs)


def wire_for_compressor(comp, n: int, nd: int = 1) -> WireFormat:
    """Map a `repro.core.compression.Compressor` onto the wire format that
    carries it on the coded collective (`n` = flat size, `nd` = chunk count).

    Global TopK / RandK have no fixed-shape per-chunk payload, so they ride
    the sparse wire with one block per all_to_all chunk and an equal
    per-chunk budget ceil(k/nd) (RandK additionally gets 2x capacity slack;
    coords beyond the budget in one chunk are dropped — documented
    approximation, still a contraction).
    """
    from .compression import (BlockTopK, GroupedSign, Identity, RandK,
                              StochasticSign, TopK)
    if isinstance(comp, (GroupedSign, StochasticSign)):
        g = comp.group_size if comp.group_size > 0 else n
        return SignWire(group_size=g)
    if isinstance(comp, BlockTopK):
        return SparseWire(k_per_block=comp.k_per_block,
                          block_size=comp.block_size)
    if isinstance(comp, TopK):
        block = n // nd
        return SparseWire(k_per_block=min(block, math.ceil(comp.k / nd)),
                          block_size=block)
    if isinstance(comp, RandK):
        block = n // nd
        return SparseWire(k_per_block=min(block, 2 * math.ceil(comp.k / nd)),
                          block_size=block)
    if isinstance(comp, Identity):
        return DenseWire()
    raise TypeError(f"no wire format for compressor {type(comp).__name__}")


# --------------------------------------------------------------------------
# collective aggregation
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CodingCollectiveConfig:
    """Static config for the coded aggregation.

    coding_axes: mesh axis names the COCO-EF 'devices' live on.  The last
      axis is the all_to_all/gather (chunking) axis; any earlier axes are
      reduced hierarchically with a dense psum of the (small) decoded chunk.
    group_size: sign-quantization group (multiple of 32); also the phase-2
      re-compression group when phase2_sign is on.
    phase2_dtype: dtype of the aggregated update broadcast (f32 = paper).
    """

    coding_axes: Tuple[str, ...] = ("data",)
    group_size: int = 512
    phase2_dtype: jnp.dtype = jnp.float32
    phase2_sign: bool = False  # beyond-paper: sign-compress the broadcast
    backend: str = "auto"      # auto | pallas | jnp (kernel dispatch)

    @property
    def chunk_axis(self) -> str:
        return self.coding_axes[-1]

    @property
    def outer_axes(self) -> Tuple[str, ...]:
        return self.coding_axes[:-1]


def _chunk_count(axis: str) -> int:
    return axis_size(axis)


@dataclasses.dataclass
class InFlightAggregate:
    """Phase-1 state of a coded allreduce whose all_to_all has been issued
    but whose decode / phase 2 has not.

    The double-buffered bucket schedule (`repro.core.cocoef` with
    `bucket_schedule="pipelined"`) traces bucket i+1's fused local step
    between `coded_allreduce_start(bucket_i)` and this handle's `finish()`,
    giving XLA's async collectives / latency-hiding scheduler a window to
    overlap bucket i's wire transfer with bucket i+1's compute.  The values
    are untouched — finishing later is bit-for-bit the serial schedule."""

    recv: Tuple[jnp.ndarray, ...]
    sender_mask: jnp.ndarray
    wire: WireFormat
    cfg: CodingCollectiveConfig

    def finish(self) -> jnp.ndarray:
        """Decode + mask + reduce the received chunks, run phase 2; returns
        the (n,) aggregate, identical on every coding rank."""
        with jax.named_scope("coded/decode_reduce"):
            chunk_sum = self.wire.decode_reduce(
                self.recv, self.sender_mask,
                use_pallas=kernel_ops.backend_use_pallas(self.cfg.backend))
            for ax in self.cfg.outer_axes:
                chunk_sum = lax.psum(chunk_sum, ax)
        with jax.named_scope("coded/phase2_gather"):
            return _phase2_gather(chunk_sum, self.cfg)


def coded_allreduce_start(
    wire: WireFormat,
    cfg: CodingCollectiveConfig,
    mask: jnp.ndarray,
    payload: Tuple[jnp.ndarray, ...],
) -> InFlightAggregate:
    """Issue phase 1 of the coded allreduce — chunk the payload and
    all_to_all it over the chunk axis — and return the in-flight handle
    whose `finish()` completes decode + phase 2."""
    n = wire.payload_n(payload)
    nd = _chunk_count(cfg.chunk_axis)
    wire.check(n, nd)

    # ---- phase 1: all_to_all compressed chunks over the chunk axis -------
    # generic chunking: every payload leaf's leading dim is proportional to n
    with jax.named_scope("coded/phase1_all_to_all"):
        chunked = tuple(p.reshape((nd, p.shape[0] // nd) + p.shape[1:])
                        for p in payload)
        # row i of the result = sender i's chunk destined for this rank
        recv = tuple(lax.all_to_all(p, cfg.chunk_axis, split_axis=0,
                                    concat_axis=0, tiled=False)
                     for p in chunked)

    # sender identity: (outer..., chunk-rank i); this rank's outer coords
    outer_idx = 0
    for ax in cfg.outer_axes:
        outer_idx = outer_idx * axis_size(ax) + lax.axis_index(ax)
    sender_base = outer_idx * nd
    sender_mask = lax.dynamic_slice_in_dim(mask, sender_base, nd)  # (nd,)
    return InFlightAggregate(recv, sender_mask, wire, cfg)


def _phase2_gather(chunk_sum: jnp.ndarray,
                   cfg: CodingCollectiveConfig) -> jnp.ndarray:
    """Phase 2: broadcast the aggregated chunk back over the chunk axis."""
    if cfg.phase2_sign:
        # beyond-paper: re-sign-compress the aggregate (server-side EF is
        # maintained by the caller via the returned residual if desired)
        w2, s2 = sign_pack(chunk_sum.astype(jnp.float32), cfg.group_size)
        w2g = all_gather_invariant(w2, cfg.chunk_axis, axis=0, tiled=True)
        s2g = all_gather_invariant(s2, cfg.chunk_axis, axis=0, tiled=True)
        return sign_unpack(w2g, s2g, cfg.group_size)
    payload2 = chunk_sum.astype(cfg.phase2_dtype)
    return all_gather_invariant(payload2, cfg.chunk_axis, axis=0,
                                tiled=True).astype(jnp.float32)


def two_phase_coded_allreduce(
    c_local: Optional[jnp.ndarray],
    wire: WireFormat,
    cfg: CodingCollectiveConfig,
    mask: jnp.ndarray,
    payload: Optional[Tuple[jnp.ndarray, ...]] = None,
) -> jnp.ndarray:
    """Compute  sum_i mask_i * c_i  across the coding ranks, transmitting
    phase 1 in `wire`'s packed format.

    c_local: (n,) this rank's *decompressed* compressed vector C(acc_i).
      When c_local is exactly representable by the wire (it is the output of
      `wire.roundtrip`), pack->unpack is lossless up to ulp-level rescaling
      and the result equals the dense masked psum (bit-for-bit for
      SignWire/DenseWire(f32); within 1-2 ulp for SparseWire — tested).
      May be None when `payload` is given — the hot path never materializes
      the dense c (it transmits the payload from `wire.fused_local_step`).
    mask: (n_coding_total,) straggler indicators, flattened over coding axes
      in row-major (outer..., chunk) order — identical on every rank.
    payload: optional pre-packed wire payload of c_local (hot-path callers
      that already packed to obtain c_local avoid a second pack here).
    Returns: (n,) aggregated ghat, identical on every coding rank.

    This is `coded_allreduce_start(...).finish()` — callers that want to
    overlap compute with the wire transfer use the split form directly.
    """
    if payload is None:
        if c_local is None:
            raise ValueError("need c_local or a pre-packed payload")
        payload = wire.pack(c_local)
    return coded_allreduce_start(wire, cfg, mask, payload).finish()


def two_phase_sign_allreduce(
    c_local: jnp.ndarray,
    cfg: CodingCollectiveConfig,
    mask: jnp.ndarray,
) -> jnp.ndarray:
    """Sign-wire specialization of `two_phase_coded_allreduce` (seed API)."""
    return two_phase_coded_allreduce(
        c_local, SignWire(group_size=cfg.group_size), cfg, mask)


def dense_allreduce(c_local: jnp.ndarray, cfg: CodingCollectiveConfig,
                    mask: jnp.ndarray) -> jnp.ndarray:
    """Baseline aggregation: dense f32 masked psum over the coding axes
    (stochastic gradient coding [31] / reference semantics for tests)."""
    idx = 0
    for ax in cfg.coding_axes:
        idx = idx * axis_size(ax) + lax.axis_index(ax)
    my_mask = lax.dynamic_index_in_dim(mask, idx, keepdims=False)
    out = my_mask * c_local
    for ax in cfg.coding_axes:
        out = lax.psum(out, ax)
    return out
