"""Pallas TPU kernels for the sign wire format (pack / unpack-reduce).

These are the per-iteration hot spots of COCO-EF: every training step each
rank makes one pass over its model-sized accumulator to (a) compress it to
the wire format and (b) update the error vector.  Fusing the whole local
step (ef_sign_fused) turns three HBM round-trips (acc, C(acc), e') into one.

Tiling: the flat vector is viewed as (groups, group_size); group_size is a
multiple of 32 (bit-pack word) and the production group (512) a multiple
of the 128-lane vreg width.  A grid step takes T =
`topk_block.tile_blocks(groups, TILE_GROUPS)` groups (512 at the train
path's size; the last step may be ragged), and the payload runs
lane-dense, one group per lane:

  sign_pack, ef_sign_fused
    x, g, e blocks   (T, group)          f32  VMEM
    words block      (group // 32, T)    i32  VMEM, lane-dense (u32 bits)
    scales block     (1, T)              f32  VMEM, lane-dense
    c, e' blocks     (T, group)          f32  VMEM
    gamma / mask                         f32  SMEM  (scalars)
  sign_decode_reduce
    words block      (N, group // 32, T) i32  VMEM, lane-dense
    scales block     (N, 1, T)           f32  VMEM, lane-dense
    out block        (T, group)          f32  VMEM, built as (group, T)
                                              and transposed once
    mask                                 f32  SMEM

The wrappers take and hand back the payload in wire order, (groups,
group // 32) flattened; the transposes to and from the lane-dense blocks
are XLA ops outside the kernels, and where only reshapes lie between
`ef_sign_fused` and `sign_decode_reduce` (one rank) the compiled step
drops the pair.  G_BLK groups is the wire's row alignment
(`CocoEFConfig.pad_multiple`, `SignWire._tile`), not the grid tile.

Why: with 8 groups a grid step, each step moved about 56 KB in a fixed
cost of about 0.3 us, and the words block (8, 16) and the scales block
(8, 1) sat in (8,128)-tiled buffers, 8x and 128x their size.  On a TPU
v5e, for the 992,816 groups of xlstm-1.3b's 508M-coordinate flat vector,
`ef_sign_fused` took 42.8 ms a call, 0.344 us per grid step of 8 groups,
and `sign_decode_reduce` (one sender) 39.4 ms, 0.317 us.  At T = 512 they
take 9.1 ms (4.67 us per grid step, 83% of the bytes roofline) and 3.1 ms
(1.59 us), every output bit for bit the old kernels'.  T = 256 /
512 / 1024 read 9.22 / 9.05 / 8.99 ms and 3.10 / 3.09 / 3.09 ms: past a
few hundred groups the kernels are bound by HBM, not by the grid.
T = 1024 does not fit Mosaic's default scoped VMEM with `want_c`.

Mosaic has no unsigned reductions, no uint32 -> f32 cast and no
lane-splitting reshape, so the kernels work in int32 and the wrappers
bitcast to the uint32 wire words outside the kernel (free in XLA):

  pack    bit j of word w is (x[32w + j] >= 0).  The 0/1 bits are summed
          against power-of-two weights on the MXU, one 16-bit half of the
          word per matmul, which contracts each group's lanes and leaves
          the words of the group on the lanes: every product and partial
          sum is an integer below 2**16, exact in bf16 x bf16 -> f32, so
          the word is exactly the one `kernels.ref.sign_pack_ref` builds.
  unpack  for word w, rows 32w..32w+31 of the (group, T) image are its
          bits, int32 logical shifts down the sublanes; then int32 -> f32.

Off the TPU the same pallas_call runs with interpret=True (pure-JAX
semantics) and is validated bit-for-bit against kernels/ref.py.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import vary_alike
from repro.kernels.topk_block import tile_blocks

G_BLK = 8  # the sign wire's row alignment in groups (pad_multiple)
TILE_GROUPS = 512  # groups per grid step of the sign kernels

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole (k,) f32 scalar array
_NT = (((1,), (1,)), ((), ()))                   # contract both minor dims


def _groups(n: int, group_size: int, op: str) -> int:
    if n % (G_BLK * group_size):
        raise ValueError(f"{op} needs n % (G_BLK*group_size) == 0, got "
                         f"n={n}, G_BLK={G_BLK}, group_size={group_size}")
    return n // group_size


def _pack_weights(group_size: int) -> jnp.ndarray:
    """(2, group//32, group) bf16: [0] weights lane l by 2**(l % 32) into
    word l // 32 for bits 0..15, [1] by 2**(l % 32 - 16) for bits 16..31."""
    word = np.arange(group_size // 32)[:, None]
    lane = np.arange(group_size)[None, :]
    bit = lane % 32
    own = lane // 32 == word
    halves = [np.where(own & (bit // 16 == h), 2.0 ** (bit % 16), 0.0)
              for h in (0, 1)]
    return jnp.asarray(np.stack(halves), jnp.bfloat16)


def _pack_spec(group_size: int) -> pl.BlockSpec:
    # constant block index: the weights are fetched once and stay in VMEM
    return pl.BlockSpec((2, group_size // 32, group_size),
                        lambda i: (0, 0, 0))


def _payload(group_size: int, ng: int, tile: int, sds):
    """Lane-dense (group//32, ng) words and (1, ng) scales: specs, shapes."""
    specs = [pl.BlockSpec((group_size // 32, tile), lambda i: (0, i)),
             pl.BlockSpec((1, tile), lambda i: (0, i))]
    shapes = [sds((group_size // 32, ng), jnp.int32),
              sds((1, ng), jnp.float32)]
    return specs, shapes


def _pack_block(x_blk, pw_ref):
    """x_blk: (T, group) f32 -> (words (group//32, T) i32 holding the u32
    bit pattern, scales (T, 1) f32)."""
    scales = jnp.mean(jnp.abs(x_blk), axis=-1, keepdims=True)     # (T,1)
    bits = (x_blk >= 0).astype(jnp.bfloat16)
    lo, hi = (lax.dot_general(pw_ref[h], bits, _NT,
                              preferred_element_type=jnp.float32)
              .astype(jnp.int32) for h in (0, 1))
    return lo | (hi << 16), scales


def _as_u32(words: jnp.ndarray) -> jnp.ndarray:
    """Lane-dense (group//32, ng) words -> the (n/32,) u32 wire words."""
    return lax.bitcast_convert_type(words.T, jnp.uint32).reshape(-1)


def _sign_pack_kernel(x_ref, pw_ref, words_ref, scales_ref):
    words, scales = _pack_block(x_ref[...].astype(jnp.float32), pw_ref)
    words_ref[...] = words
    scales_ref[...] = scales.T


@functools.partial(jax.jit, static_argnames=("group_size", "interpret"))
def sign_pack(x: jnp.ndarray, group_size: int, interpret: bool = True
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (n,) f32, n % (G_BLK * group_size) == 0."""
    ng = _groups(x.shape[0], group_size, "sign_pack")
    tile = tile_blocks(ng, TILE_GROUPS)
    args, axes = vary_alike(x.reshape(ng, group_size),
                            _pack_weights(group_size))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    out_specs, out_shape = _payload(group_size, ng, tile, sds)
    words, scales = pl.pallas_call(
        _sign_pack_kernel,
        name="sign_pack",
        grid=(pl.cdiv(ng, tile),),
        in_specs=[pl.BlockSpec((tile, group_size), lambda i: (i, 0)),
                  _pack_spec(group_size)],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    return _as_u32(words), scales.reshape(-1)


def _ef_fused_kernel(g_ref, e_ref, gamma_ref, mask_ref, pw_ref,
                     words_ref, scales_ref, *out_refs, want_c: bool):
    gamma = gamma_ref[0]
    mask = mask_ref[0]
    acc = gamma * g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    words, scales = _pack_block(acc, pw_ref)
    c = (jnp.where(acc >= 0, 1.0, -1.0) * scales)                  # (T, group)
    words_ref[...] = words
    scales_ref[...] = scales.T
    if want_c:
        out_refs[0][...] = c
    out_refs[-1][...] = jnp.where(mask > 0, acc - c,
                                  e_ref[...].astype(jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("group_size", "want_c", "interpret"))
def ef_sign_fused(g: jnp.ndarray, e: jnp.ndarray, gamma, mask_self,
                  group_size: int, want_c: bool = True,
                  interpret: bool = True):
    """Fused local COCO-EF step: one HBM pass over g/e producing the wire
    payload (words, scales), the decompressed C(acc) and the new error.
    g, e: (n,) f32; gamma, mask_self: scalars.  want_c=False skips the
    full-vector c store (the train path only ships the payload; a custom
    call's outputs are not DCE-able, so the skip must be explicit)."""
    ng = _groups(g.shape[0], group_size, "ef_sign_fused")
    tile = tile_blocks(ng, TILE_GROUPS)
    args, axes = vary_alike(
        g.reshape(ng, group_size), e.reshape(ng, group_size),
        jnp.asarray(gamma, jnp.float32).reshape(1),
        jnp.asarray(mask_self, jnp.float32).reshape(1),
        _pack_weights(group_size))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    full = pl.BlockSpec((tile, group_size), lambda i: (i, 0))
    out_specs, out_shape = _payload(group_size, ng, tile, sds)
    outs = pl.pallas_call(
        functools.partial(_ef_fused_kernel, want_c=want_c),
        name="ef_sign_fused",
        grid=(pl.cdiv(ng, tile),),
        in_specs=[full, full, _SMEM, _SMEM, _pack_spec(group_size)],
        out_specs=out_specs + [full] * (1 + want_c),
        out_shape=out_shape + [sds((ng, group_size), jnp.float32)]
        * (1 + want_c),
        interpret=interpret,
    )(*args)
    words, scales = outs[0], outs[1]
    c = outs[2].reshape(-1) if want_c else None
    return _as_u32(words), scales.reshape(-1), c, outs[-1].reshape(-1)


def _decode_reduce_kernel(words_ref, scales_ref, mask_ref, out_ref,
                          *, n_senders: int):
    nw, tile = words_ref.shape[1:]
    shift = lax.broadcasted_iota(jnp.int32, (nw, 32, tile), 1)
    acc = jnp.zeros((nw * 32, tile), jnp.float32)                  # (group, T)
    for i in range(n_senders):                                     # static loop
        bits = lax.shift_right_logical(words_ref[i][:, None, :], shift) & 1
        signs = bits.reshape(acc.shape).astype(jnp.float32) * 2.0 - 1.0
        acc = acc + mask_ref[i] * signs * scales_ref[i]
    out_ref[...] = acc.T


@functools.partial(jax.jit, static_argnames=("group_size", "interpret"))
def sign_decode_reduce(words: jnp.ndarray, scales: jnp.ndarray,
                       mask: jnp.ndarray, group_size: int,
                       interpret: bool = True) -> jnp.ndarray:
    """Server-side decode + masked aggregate.
    words: (N, n/32) u32; scales: (N, n/g) f32; mask: (N,) f32 -> (n,)."""
    N = words.shape[0]
    ng = _groups(words.shape[1] * 32, group_size, "sign_decode_reduce")
    tile = tile_blocks(ng, TILE_GROUPS)
    nw = group_size // 32
    args, axes = vary_alike(
        jnp.swapaxes(lax.bitcast_convert_type(words, jnp.int32).reshape(
            N, ng, nw), 1, 2),
        scales.reshape(N, 1, ng), mask.astype(jnp.float32))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    out = pl.pallas_call(
        functools.partial(_decode_reduce_kernel, n_senders=N),
        name="sign_decode_reduce",
        grid=(pl.cdiv(ng, tile),),
        in_specs=[
            pl.BlockSpec((N, nw, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((N, 1, tile), lambda i: (0, 0, i)),
            _SMEM,
        ],
        out_specs=pl.BlockSpec((tile, group_size), lambda i: (i, 0)),
        out_shape=sds((ng, group_size), jnp.float32),
        interpret=interpret,
    )(*args)
    return out.reshape(-1)
