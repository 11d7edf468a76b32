"""Pallas TPU kernels for the sign wire format (pack / unpack-reduce).

These are the per-iteration hot spots of COCO-EF: every training step each
rank makes one pass over its model-sized accumulator to (a) compress it to
the wire format and (b) update the error vector.  Fusing the whole local
step (ef_sign_fused) turns three HBM round-trips (acc, C(acc), e') into one.

Tiling: the flat vector is processed as (rows of ROW_GROUPS groups) x
(group_size lanes).  group_size is a multiple of 32 (bit-pack word); the
production group (512) is also a multiple of the 128-lane vreg width:

  x block      (G_BLK, group)            f32   VMEM
  words block  (G_BLK, group // 32)      i32   VMEM  (u32 bit pattern)
  scales block (G_BLK, 1)                f32   VMEM
  gamma / mask                           f32   SMEM  (scalars)

Mosaic has no unsigned reductions, no uint32 -> f32 cast and no
lane-splitting reshape, so the kernels work in int32 and the wrappers
bitcast to the uint32 wire words outside the kernel (free in XLA):

  pack    bit j of word w is (x[32w + j] >= 0).  The 0/1 bits are summed
          against power-of-two weights on the MXU, one 16-bit half of the
          word per matmul: every product and partial sum is an integer
          below 2**16, exact in bf16 x bf16 -> f32, so the word is exactly
          the one `kernels.ref.sign_pack_ref` builds.
  unpack  int32 logical shifts and masks, then int32 -> f32.

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

G_BLK = 8  # groups per grid step

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole (k,) f32 scalar array


def _pack_weights(group_size: int) -> jnp.ndarray:
    """(2, group, group//32) bf16: [0] weights lane l by 2**(l % 32) into
    word l // 32 for bits 0..15, [1] by 2**(l % 32 - 16) for bits 16..31."""
    lane = np.arange(group_size)[:, None]
    word = np.arange(group_size // 32)[None, :]
    bit = lane % 32
    own = lane // 32 == word
    halves = [np.where(own & (bit // 16 == h), 2.0 ** (bit % 16), 0.0)
              for h in (0, 1)]
    return jnp.asarray(np.stack(halves), jnp.bfloat16)


def _pack_spec(group_size: int) -> pl.BlockSpec:
    # constant block index: the weights are fetched once and stay in VMEM
    return pl.BlockSpec((2, group_size, group_size // 32),
                        lambda i: (0, 0, 0))


def _pack_block(x_blk, pw_ref):
    """x_blk: (G_BLK, group) f32 -> (words (G_BLK, group//32) i32 holding
    the u32 bit pattern, scales (G_BLK, 1) f32)."""
    scales = jnp.mean(jnp.abs(x_blk), axis=-1, keepdims=True)     # (G,1)
    bits = (x_blk >= 0).astype(jnp.bfloat16)
    lo, hi = (jnp.dot(bits, pw_ref[h], preferred_element_type=jnp.float32)
              .astype(jnp.int32) for h in (0, 1))
    return lo | (hi << 16), scales


def _as_u32(words: jnp.ndarray) -> jnp.ndarray:
    return lax.bitcast_convert_type(words, jnp.uint32).reshape(-1)


def _sign_pack_kernel(x_ref, pw_ref, words_ref, scales_ref):
    words, scales = _pack_block(x_ref[...].astype(jnp.float32), pw_ref)
    words_ref[...] = words
    scales_ref[...] = scales


@functools.partial(jax.jit, static_argnames=("group_size", "interpret"))
def sign_pack(x: jnp.ndarray, group_size: int, interpret: bool = True
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (n,) f32, n % (G_BLK * group_size) == 0."""
    n = x.shape[0]
    if n % (G_BLK * group_size):
        raise ValueError(f"sign_pack needs n % (G_BLK*group_size) == 0, got "
                         f"n={n}, G_BLK={G_BLK}, group_size={group_size}")
    ng = n // group_size
    grid = (ng // G_BLK,)
    args, axes = vary_alike(x.reshape(ng, group_size),
                            _pack_weights(group_size))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    words, scales = pl.pallas_call(
        _sign_pack_kernel,
        name="sign_pack",
        grid=grid,
        in_specs=[pl.BlockSpec((G_BLK, group_size), lambda i: (i, 0)),
                  _pack_spec(group_size)],
        out_specs=[
            pl.BlockSpec((G_BLK, group_size // 32), lambda i: (i, 0)),
            pl.BlockSpec((G_BLK, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            sds((ng, group_size // 32), jnp.int32),
            sds((ng, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return _as_u32(words), scales.reshape(-1)


def _ef_fused_kernel(g_ref, e_ref, gamma_ref, mask_ref, pw_ref,
                     words_ref, scales_ref, *out_refs, want_c: bool):
    gamma = gamma_ref[0]
    mask = mask_ref[0]
    acc = gamma * g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    words, scales = _pack_block(acc, pw_ref)
    c = (jnp.where(acc >= 0, 1.0, -1.0) * scales)                  # (G, group)
    words_ref[...] = words
    scales_ref[...] = scales
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
    n = g.shape[0]
    if n % (G_BLK * group_size):
        raise ValueError(f"ef_sign_fused needs n % (G_BLK*group_size) == 0, "
                         f"got n={n}, G_BLK={G_BLK}, group_size={group_size}")
    ng = n // group_size
    grid = (ng // G_BLK,)
    args, axes = vary_alike(
        g.reshape(ng, group_size), e.reshape(ng, group_size),
        jnp.asarray(gamma, jnp.float32).reshape(1),
        jnp.asarray(mask_self, jnp.float32).reshape(1),
        _pack_weights(group_size))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    full = [pl.BlockSpec((G_BLK, group_size), lambda i: (i, 0)),
            sds((ng, group_size), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_ef_fused_kernel, want_c=want_c),
        name="ef_sign_fused",
        grid=grid,
        in_specs=[
            pl.BlockSpec((G_BLK, group_size), lambda i: (i, 0)),
            pl.BlockSpec((G_BLK, group_size), lambda i: (i, 0)),
            _SMEM,
            _SMEM,
            _pack_spec(group_size),
        ],
        out_specs=[
            pl.BlockSpec((G_BLK, group_size // 32), lambda i: (i, 0)),
            pl.BlockSpec((G_BLK, 1), lambda i: (i, 0)),
        ] + [full[0]] * (1 + want_c),
        out_shape=[
            sds((ng, group_size // 32), jnp.int32),
            sds((ng, 1), jnp.float32),
        ] + [full[1]] * (1 + want_c),
        interpret=interpret,
    )(*args)
    words, scales = outs[0], outs[1]
    c = outs[2].reshape(-1) if want_c else None
    return _as_u32(words), scales.reshape(-1), c, outs[-1].reshape(-1)


def _decode_reduce_kernel(words_ref, scales_ref, mask_ref, out_ref,
                          *, group_size: int, n_senders: int):
    acc = jnp.zeros(out_ref.shape, jnp.float32)                    # (G, group)
    for i in range(n_senders):                                     # static loop
        w = words_ref[i]                                           # (G, g/32)
        s = scales_ref[i]                                          # (G, 1)
        shift = lax.broadcasted_iota(jnp.int32, w.shape + (32,), 2)
        bits = lax.shift_right_logical(w[..., None], shift) & 1
        signs = bits.reshape(out_ref.shape).astype(jnp.float32) * 2.0 - 1.0
        acc = acc + mask_ref[i] * signs * s
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("group_size", "interpret"))
def sign_decode_reduce(words: jnp.ndarray, scales: jnp.ndarray,
                       mask: jnp.ndarray, group_size: int,
                       interpret: bool = True) -> jnp.ndarray:
    """Server-side decode + masked aggregate.
    words: (N, n/32) u32; scales: (N, n/g) f32; mask: (N,) f32 -> (n,)."""
    N = words.shape[0]
    n = words.shape[1] * 32
    if n % (G_BLK * group_size):
        raise ValueError(f"sign_decode_reduce needs n % (G_BLK*group_size) "
                         f"== 0, got n={n}, G_BLK={G_BLK}, "
                         f"group_size={group_size}")
    ng = n // group_size
    grid = (ng // G_BLK,)
    args, axes = vary_alike(
        lax.bitcast_convert_type(words, jnp.int32).reshape(
            N, ng, group_size // 32),
        scales.reshape(N, ng, 1), mask.astype(jnp.float32))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    out = pl.pallas_call(
        functools.partial(_decode_reduce_kernel, group_size=group_size,
                          n_senders=N),
        name="sign_decode_reduce",
        grid=grid,
        in_specs=[
            pl.BlockSpec((N, G_BLK, group_size // 32), lambda i: (0, i, 0)),
            pl.BlockSpec((N, G_BLK, 1), lambda i: (0, i, 0)),
            _SMEM,
        ],
        out_specs=pl.BlockSpec((G_BLK, group_size), lambda i: (i, 0)),
        out_shape=sds((ng, group_size), jnp.float32),
        interpret=interpret,
    )(*args)
    return out.reshape(-1)
