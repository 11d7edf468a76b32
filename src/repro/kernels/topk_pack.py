"""Pallas TPU kernels for the sparse (block top-K) wire format.

Mirrors kernels/sign_pack.py for the SparseWire of
`repro.core.collectives`: per contiguous block of `block_size` coords the
wire carries the k largest-|.| entries as (in-block indices, values
normalized by the per-block scale, the f32 scale).  Selection is
`topk_block.block_select` — a sort-free per-row threshold search on the
|x| bit patterns (31 monotone halving steps seeded by the block max) plus
one compaction pass, replacing the old k-round argmax whose vector
reductions grew linearly in k; tie-breaking matches
kernels/ref.topk_pack_ref (lax.top_k: first occurrence wins).

Tiling: the flat vector is processed as (rows of R_BLK blocks) x
(block_size lanes); block_size is a multiple of 128 in production so every
BlockSpec is VPU aligned:

  x block       (R_BLK, block_size)  f32  VMEM
  indices block (R_BLK, k)           i32  VMEM
  values block  (R_BLK, k)           f32  VMEM
  scales block  (R_BLK, 1)           f32  VMEM
  gamma / mask                       f32  SMEM  (scalars)

The narrow wire dtypes (uint16 indices, bf16 values) are cast OUTSIDE the
kernel by SparseWire.pack — Mosaic keeps 32-bit lanes internally.

On this CPU container the kernels run with interpret=True (pure-JAX
semantics) and are validated against kernels/ref.py; on real TPU the same
pallas_call lowers to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import vary_alike
from repro.kernels.topk_block import block_select

R_BLK = 8  # blocks (rows) per grid step

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole (k,) f32 scalar array


def _topk_pack_kernel(x_ref, idx_ref, val_ref, scale_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)
    idx, sval, scale = block_select(x, k)
    safe = jnp.where(scale == 0, 1.0, scale)
    idx_ref[...] = idx
    val_ref[...] = sval / safe
    scale_ref[...] = safe


@functools.partial(jax.jit, static_argnames=("k", "block_size", "interpret"))
def topk_pack(x: jnp.ndarray, k: int, block_size: int, interpret: bool = True
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (n,) f32, n % (R_BLK * block_size) == 0 ->
    (indices (n/B, k) i32, values (n/B, k) f32, scales (n/B,) f32)."""
    n = x.shape[0]
    rows = n // block_size
    if n % (R_BLK * block_size):
        raise ValueError(f"topk_pack needs n % (R_BLK*block_size) == 0, got "
                         f"n={n}, R_BLK={R_BLK}, block_size={block_size}")
    grid = (rows // R_BLK,)
    args, axes = vary_alike(x.reshape(rows, block_size))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    idx, val, scale = pl.pallas_call(
        functools.partial(_topk_pack_kernel, k=k),
        name="topk_pack",
        grid=grid,
        in_specs=[pl.BlockSpec((R_BLK, block_size), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((R_BLK, k), lambda i: (i, 0)),
            pl.BlockSpec((R_BLK, k), lambda i: (i, 0)),
            pl.BlockSpec((R_BLK, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            sds((rows, k), jnp.int32),
            sds((rows, k), jnp.float32),
            sds((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return idx, val, scale.reshape(-1)


def _scatter_rows(idx, sval, shape):
    """Dense (R, B) image of k kept entries per row: pos==idx_r selects."""
    pos = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    dense = jnp.zeros(shape, jnp.float32)
    for r in range(idx.shape[-1]):                             # static loop
        dense = dense + jnp.where(pos == idx[:, r:r + 1],
                                  sval[:, r:r + 1], 0.0)
    return dense


def _ef_topk_fused_kernel(g_ref, e_ref, gamma_ref, mask_ref,
                          idx_ref, val_ref, scale_ref, *out_refs,
                          k: int, want_c: bool, value_dtype: str):
    gamma = gamma_ref[0]
    mask = mask_ref[0]
    e = e_ref[...].astype(jnp.float32)
    acc = gamma * g_ref[...].astype(jnp.float32) + e                # (R, B)
    idx, sval, scale = block_select(acc, k)
    safe = jnp.where(scale == 0, 1.0, scale)
    # normalize -> wire precision -> denormalize IN-REGISTER: c is the
    # transmitted reconstruction (== topk_unpack of the payload), so the
    # error update tracks the wire without an unpack-of-pack round trip
    val = (sval / safe).astype(jnp.dtype(value_dtype)).astype(jnp.float32)
    c = _scatter_rows(idx, val * safe, acc.shape)
    idx_ref[...] = idx
    val_ref[...] = val
    scale_ref[...] = safe
    if want_c:
        out_refs[0][...] = c
    out_refs[-1][...] = jnp.where(mask > 0, acc - c, e)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_size", "want_c",
                                    "value_dtype", "interpret"))
def ef_topk_fused(g: jnp.ndarray, e: jnp.ndarray, gamma, mask_self,
                  k: int, block_size: int, want_c: bool = True,
                  value_dtype: str = "float32", interpret: bool = True):
    """Fused local COCO-EF step on the sparse wire: one HBM pass over g/e
    producing the wire payload (indices, values rounded to value_dtype,
    scales), the transmitted reconstruction C(acc) and the new error.
    g, e: (n,) f32; gamma, mask_self: scalars.
    Semantics match kernels.ref.ef_topk_fused_ref bit-for-bit.
    want_c=False skips the full-vector c store (the train path only ships
    the payload; a custom call's outputs are not DCE-able)."""
    n = g.shape[0]
    rows = n // block_size
    if n % (R_BLK * block_size):
        raise ValueError(f"ef_topk_fused needs n % (R_BLK*block_size) == 0, "
                         f"got n={n}, R_BLK={R_BLK}, block_size={block_size}")
    grid = (rows // R_BLK,)
    args, axes = vary_alike(
        g.reshape(rows, block_size), e.reshape(rows, block_size),
        jnp.asarray(gamma, jnp.float32).reshape(1),
        jnp.asarray(mask_self, jnp.float32).reshape(1))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    full = [pl.BlockSpec((R_BLK, block_size), lambda i: (i, 0)),
            sds((rows, block_size), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_ef_topk_fused_kernel, k=k, want_c=want_c,
                          value_dtype=value_dtype),
        name="ef_topk_fused",
        grid=grid,
        in_specs=[
            pl.BlockSpec((R_BLK, block_size), lambda i: (i, 0)),
            pl.BlockSpec((R_BLK, block_size), lambda i: (i, 0)),
            _SMEM,
            _SMEM,
        ],
        out_specs=[
            pl.BlockSpec((R_BLK, k), lambda i: (i, 0)),
            pl.BlockSpec((R_BLK, k), lambda i: (i, 0)),
            pl.BlockSpec((R_BLK, 1), lambda i: (i, 0)),
        ] + [full[0]] * (1 + want_c),
        out_shape=[
            sds((rows, k), jnp.int32),
            sds((rows, k), jnp.float32),
            sds((rows, 1), jnp.float32),
        ] + [full[1]] * (1 + want_c),
        interpret=interpret,
    )(*args)
    idx, val, scale = outs[0], outs[1], outs[2]
    c = outs[3].reshape(-1) if want_c else None
    return idx, val, scale.reshape(-1), c, outs[-1].reshape(-1)


def _topk_decode_reduce_kernel(idx_ref, val_ref, scale_ref, mask_ref, out_ref,
                               *, k: int, n_senders: int):
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for i in range(n_senders):                                   # static loop
        sv = val_ref[i] * scale_ref[i]                           # (R, k)
        acc = acc + mask_ref[i] * _scatter_rows(idx_ref[i], sv, out_ref.shape)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def topk_decode_reduce(indices: jnp.ndarray, values: jnp.ndarray,
                       scales: jnp.ndarray, mask: jnp.ndarray,
                       block_size: int, interpret: bool = True) -> jnp.ndarray:
    """Server-side sparse decode + masked aggregate.
    indices: (N, rows, k) i32; values: (N, rows, k) f32;
    scales: (N, rows) f32; mask: (N,) f32 -> (rows * block_size,)."""
    N, rows, k = indices.shape
    if rows % R_BLK:
        raise ValueError(f"topk_decode_reduce needs rows % R_BLK == 0, got "
                         f"rows={rows}, R_BLK={R_BLK}")
    grid = (rows // R_BLK,)
    args, axes = vary_alike(
        indices.astype(jnp.int32), values.astype(jnp.float32),
        scales.reshape(N, rows, 1).astype(jnp.float32),
        mask.astype(jnp.float32))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    out = pl.pallas_call(
        functools.partial(_topk_decode_reduce_kernel, k=k, n_senders=N),
        name="topk_decode_reduce",
        grid=grid,
        in_specs=[
            pl.BlockSpec((N, R_BLK, k), lambda i: (0, i, 0)),
            pl.BlockSpec((N, R_BLK, k), lambda i: (0, i, 0)),
            pl.BlockSpec((N, R_BLK, 1), lambda i: (0, i, 0)),
            _SMEM,
        ],
        out_specs=pl.BlockSpec((R_BLK, block_size), lambda i: (i, 0)),
        out_shape=sds((rows, block_size), jnp.float32),
        interpret=interpret,
    )(*args)
    return out.reshape(-1)
