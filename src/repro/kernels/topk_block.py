"""Pallas TPU kernels: block-select top-k (threshold search, sort-free).

`block_select` is THE in-kernel selection primitive for the sparse-wire
kernels (`topk_pack`, `ef_topk_fused`, and the `block_topk` sparsifier
here).  Instead of k rounds of (row-max, argmax) over the whole block —
whose vector-reduction count grows linearly in k — or `lax.top_k`'s full
sort (which Mosaic cannot lower inside a kernel body anyway), it

  1. binary-searches the k-th largest |x| BIT PATTERN per row: IEEE f32
     magnitudes compare exactly like their int32 bit patterns, so 31
     monotone halving steps on [0, block_max_bits + 1] find the threshold
     exactly — denormals, zeros and duplicate values included;
  2. cuts threshold ties by first-occurrence rank (a lane prefix sum), so
     the selected SET matches `lax.top_k` on |x| bit-for-bit;
  3. compacts the k survivors into slots in position order (prefix sum +
     per-slot one-hot reductions) and orders the k slots by
     (magnitude desc, position asc) with a k-round argmax over k lanes —
     k*k lane work where the old loop paid k*block_size.

Everything is plain VPU-friendly jnp — compares, where, sum/max
reductions, static lane shifts via concatenate, `lax.fori_loop` — so the
same function runs inside Pallas kernel bodies (Mosaic on TPU, interpret
mode here) and as a host-traceable reference.  Tie-breaking matches
kernels/ref.py / `lax.top_k` exactly (first occurrence wins), which is
what the reference-vs-mesh parity gate demands of every payload.

The full-sort perf story on CPU lives in kernels/topk_fast.py (the jnp
hot path); this module is the TPU/in-kernel side of ROADMAP open item 3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.compat import vary_alike

R_BLK = 8  # rows (blocks) per grid step


def _cumsum_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the last (lane) axis via log2 doubling.

    Static shift-and-add only (concatenate of a zero slab + a lane slice),
    because `jnp.cumsum` lowers to a serial loop / reduce_window that
    Mosaic does not support inside kernel bodies."""
    B = x.shape[-1]
    shift = 1
    while shift < B:
        z = jnp.zeros(x.shape[:-1] + (shift,), x.dtype)
        x = x + jnp.concatenate([z, x[..., :B - shift]], axis=-1)
        shift *= 2
    return x


def block_select_mask(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """(R, B) -> boolean keep-mask of each row's k largest-|.| entries,
    first occurrence winning magnitude ties (the `lax.top_k` set).

    Per-row threshold refinement: the binary search below maintains
    count(bits >= lo) >= k > count(bits >= hi), seeded by the block max
    (hi = max_bits + 1, lo = 0); 31 steps cover the full non-negative f32
    bit range, so `lo` lands exactly on the k-th largest magnitude's bit
    pattern.  Ties at the threshold are cut by first-occurrence rank."""
    if not 0 < k <= x.shape[-1]:
        raise ValueError(f"need 0 < k <= block width, got {k} / {x.shape[-1]}")
    mag = jnp.abs(x)
    # non-negative IEEE floats order like their int32 bit patterns
    bits = lax.bitcast_convert_type(mag, jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ge = jnp.sum((bits >= mid).astype(jnp.int32), axis=-1, keepdims=True)
        take = ge >= k
        return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

    lo0 = jnp.zeros(x.shape[:-1] + (1,), jnp.int32)
    hi0 = jnp.max(bits, axis=-1, keepdims=True) + 1
    thr, _ = lax.fori_loop(0, 31, body, (lo0, hi0))

    gt = bits > thr
    eq = bits == thr
    n_gt = jnp.sum(gt.astype(jnp.int32), axis=-1, keepdims=True)
    tie_rank = _cumsum_lanes(eq.astype(jnp.int32))      # 1-based among ties
    return gt | (eq & (tie_rank <= k - n_gt))


def block_select(x: jnp.ndarray, k: int):
    """x: (R, B) f32 -> (idx (R, k) i32, sval (R, k) f32, scale (R, 1) f32).

    Exact block top-|.|-k; indices in decreasing-magnitude order, first
    occurrence wins ties — elementwise identical to `lax.top_k` on |x|
    (and to kernels/ref.topk_pack_ref's selection).  sval are the SIGNED
    kept values, scale is the per-row max |x|."""
    R, B = x.shape
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sel = block_select_mask(x, k)
    pos = lax.broadcasted_iota(jnp.int32, (R, B), 1)

    # compact the k survivors into slots, in position order
    slot = _cumsum_lanes(sel.astype(jnp.int32)) - 1     # 0-based among kept
    idx_cols, val_cols = [], []
    for j in range(k):                                  # static unrolled
        oh = sel & (slot == j)
        idx_cols.append(jnp.sum(jnp.where(oh, pos, 0), axis=-1,
                                keepdims=True))
        val_cols.append(jnp.sum(jnp.where(oh, x, 0.0), axis=-1,
                                keepdims=True))
    idx_c = jnp.concatenate(idx_cols, axis=-1)          # (R, k), pos asc
    val_c = jnp.concatenate(val_cols, axis=-1)

    # order the k slots by (magnitude desc, position asc): slots are
    # already position-ascending, so first-slot-wins == lax.top_k ties.
    # k rounds over k lanes — negligible next to the B-lane stages above.
    cbits = lax.bitcast_convert_type(jnp.abs(val_c), jnp.int32)
    spos = lax.broadcasted_iota(jnp.int32, (R, k), 1)
    avail = jnp.ones((R, k), jnp.bool_)
    idx_cols, val_cols = [], []
    for _ in range(k):
        m = jnp.where(avail, cbits, -1)
        row_max = jnp.max(m, axis=-1, keepdims=True)
        first = jnp.min(jnp.where((m == row_max) & avail, spos, k),
                        axis=-1, keepdims=True)
        take = spos == first
        idx_cols.append(jnp.sum(jnp.where(take, idx_c, 0), axis=-1,
                                keepdims=True))
        val_cols.append(jnp.sum(jnp.where(take, val_c, 0.0), axis=-1,
                                keepdims=True))
        avail = avail & ~take
    return (jnp.concatenate(idx_cols, axis=-1),
            jnp.concatenate(val_cols, axis=-1), scale)


def _topk_kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)          # (R, B)
    keep = block_select_mask(x, k)
    o_ref[...] = jnp.where(keep, x, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "block_size", "interpret"))
def block_topk(x: jnp.ndarray, k: int, block_size: int,
               interpret: bool = True) -> jnp.ndarray:
    """x: (n,) with n % (R_BLK * block_size) == 0 -> sparsified (n,)."""
    n = x.shape[0]
    rows = n // block_size
    args, axes = vary_alike(x.reshape(rows, block_size))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=axes)
    out = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        name="block_topk",
        grid=(rows // R_BLK,),
        in_specs=[pl.BlockSpec((R_BLK, block_size), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((R_BLK, block_size), lambda i: (i, 0)),
        out_shape=sds((rows, block_size), x.dtype),
        interpret=interpret,
    )(*args)
    return out.reshape(-1)
