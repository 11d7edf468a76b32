"""Pallas TPU kernels: block top-k selection with blocks on lanes.

`select_blocks` is THE in-kernel selection primitive of the sparse-wire
kernels (`topk_pack`, `ef_topk_fused`, and the `block_topk` sparsifier
here).  A kernel reads a tile of `tile_blocks(rows)` blocks (up to
`TILE_BLOCKS`, a multiple of 128) and transposes it in VMEM to
(block_size, blocks): each block's coordinates run down the sublanes and
the blocks run across the lanes.  Every per-block reduction of the
selection is then a fold of (8, blocks) slabs, i.e. elementwise VPU work
(a vreg-wise tree) plus one 8-sublane reduce, with TILE_BLOCKS
independent lanes in flight.  The earlier layout put 8 blocks on the
sublanes and their coordinates on the lanes, so the same search was a
serial chain of about a hundred dependent cross-lane (XLU) reductions
per 8-block grid step, with nothing to hide their latency (kernels/
topk_pack.py gives the cost on a TPU v5e before and after).

Per block the selection

  1. binary-searches the k-th largest |x| BIT PATTERN: IEEE f32
     magnitudes compare exactly like their int32 bit patterns, so 31
     monotone halving steps on [0, block_max_bits + 1] find the threshold
     exactly — denormals, zeros and duplicate values included;
  2. takes the k survivors in k rounds of (max magnitude, lowest
     position) over the coordinates at or above the threshold, which is
     `lax.top_k`'s order with first occurrence winning ties — so the
     threshold ties are cut by position without a prefix sum.

Everything is plain jnp — compares, where, sublane folds, static
reshapes, one `lax.fori_loop` — so the same function runs inside Pallas
kernel bodies (Mosaic on TPU, interpret mode here) and as a
host-traceable reference, bit-for-bit `kernels/ref.py` / `lax.top_k`.

The jnp hot path for the CPU lives in kernels/topk_fast.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.compat import vary_alike

TILE_BLOCKS = 1024  # blocks (lanes) per grid step of the selection kernels


def tile_blocks(rows: int, most: int = TILE_BLOCKS) -> int:
    """Blocks per grid step for `rows` blocks: `most` (a multiple of 128),
    clipped to the largest multiple of 128 that fits, or to `rows` itself
    below 128 (a block as wide as the array needs no lane alignment).  The
    last grid step may be ragged: its out-of-range lanes are never written
    back."""
    if rows < 128:
        return rows
    return min(most, rows - rows % 128)


def _sum(x):
    return jnp.sum(x, axis=0, keepdims=True)


def _max(x):
    return jnp.max(x, axis=0, keepdims=True)


def _min(x):
    return jnp.min(x, axis=0, keepdims=True)


def select_blocks(xt: jnp.ndarray, k: int):
    """xt: (B, T) f32, one block per column ->
    (idx (k, T) i32, sval (k, T) f32, scale (1, T) f32, rank (B, T) i32).

    Exact block top-|.|-k: row j of idx / sval is each block's j-th
    largest magnitude (first occurrence winning ties), elementwise
    identical to `lax.top_k` on |x| per block (and to
    kernels/ref.topk_pack_ref's selection); sval are the SIGNED kept
    values, bit for bit; scale is the block max |x|; rank is j at the
    coordinate of row j, -1 at the coordinates not kept."""
    B, T = xt.shape
    if not 0 < k <= B:
        raise ValueError(f"need 0 < k <= block width, got {k} / {B}")
    xbits = lax.bitcast_convert_type(xt, jnp.int32)
    # non-negative IEEE floats order like their int32 bit patterns
    bits = lax.bitcast_convert_type(jnp.abs(xt), jnp.int32)
    top = _max(bits)

    # count(bits >= lo) >= k > count(bits >= hi); 31 halvings of the
    # non-negative f32 bit range leave lo on the k-th largest pattern
    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        take = _sum((bits >= mid).astype(jnp.int32)) >= k
        return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

    thr, _ = lax.fori_loop(0, 31, body, (jnp.zeros_like(top), top + 1))

    # k rounds of (max magnitude, lowest position) over the candidates;
    # a coordinate below the threshold holds -1, the one taken in round j
    # holds -2 - j
    pos = lax.broadcasted_iota(jnp.int32, (B, T), 0)
    slot = lax.broadcasted_iota(jnp.int32, (k, T), 0)
    m = jnp.where(bits >= thr, bits, -1)
    idx = jnp.zeros((k, T), jnp.int32)
    sbits = jnp.zeros((k, T), jnp.int32)
    for j in range(k):                                  # static unrolled
        best = _max(m)
        first = _min(jnp.where(m == best, pos, B))
        take = pos == first
        idx = jnp.where(slot == j, first, idx)
        sbits = jnp.where(slot == j, _sum(jnp.where(take, xbits, 0)), sbits)
        m = jnp.where(take, -2 - j, m)
    return (idx, lax.bitcast_convert_type(sbits, jnp.float32),
            lax.bitcast_convert_type(top, jnp.float32),
            jnp.where(m < -1, -2 - m, -1))


def _topk_kernel(x_ref, o_ref, *, k: int):
    xt = x_ref[...].astype(jnp.float32).T               # (B, T)
    rank = select_blocks(xt, k)[3]
    o_ref[...] = jnp.where(rank >= 0, xt, 0.0).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "block_size", "interpret"))
def block_topk(x: jnp.ndarray, k: int, block_size: int,
               interpret: bool = True) -> jnp.ndarray:
    """x: (n,) with n % block_size == 0 -> sparsified (n,)."""
    rows = x.shape[0] // block_size
    tile = tile_blocks(rows)
    args, axes = vary_alike(x.reshape(rows, block_size))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    out = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        name="block_topk",
        grid=(pl.cdiv(rows, tile),),
        in_specs=[pl.BlockSpec((tile, block_size), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, block_size), lambda i: (i, 0)),
        out_shape=sds((rows, block_size), x.dtype),
        interpret=interpret,
    )(*args)
    return out.reshape(-1)
