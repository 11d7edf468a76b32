"""Fast jnp hot path for the sparse wire: `lax.top_k` plus the fusion barrier.

THE perf bug this module fixes (jax 0.4.37, XLA:CPU — the backend both CI
and the bench host run): `lax.top_k` itself is cheap (~13 ms at n=1M,
K=16, B=512), but when its outputs are consumed inside the surrounding
fusion XLA re-materializes the sort once per consumer fusion.  The fused
EF local step traced at ~214 ms against ~18 ms of actual stage work — an
order-of-magnitude pathology that left `ef_topk_local_step` benching at
1.03x fused-over-unfused and made the fusion look useless.  Pinning an
`optimization_barrier` IMMEDIATELY AFTER the top_k forces a single
materialization of (values, indices) that every consumer then reads:
214 ms -> ~13 ms on the same input.  A barrier placed before the top_k
does nothing; the placement is the whole fix.

The barrier changes no values — every function here is bit-for-bit equal
to its kernels/ref.py counterpart, which deliberately stays barrier-free
as the semantic oracle.  `kernels.ops` dispatches the jnp backend here;
the Pallas kernels (topk_pack.py / topk_block.select_blocks) cover the
in-kernel TPU side with a sort-free threshold search.

Quantized-transmission semantics (`value_dtype`): the fused step emits
`val` as float32 holding value_dtype-ROUNDED numbers and builds `c` from
`val * scale` — exactly what a receiver reconstructs from the wire — so
the error update `e_new = acc - c` tracks the transmitted compression and
callers no longer need an unpack-of-pack round trip per bucket.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.kernels.ref import mul_add


def _barrier_top_k(mag: jnp.ndarray, k: int):
    """`lax.top_k` with the consumer-fusion barrier pinned on its outputs.

    One barrier per output, NOT `optimization_barrier((topv, idx))`: XLA's
    TupleSimplifier rewrites barrier(tuple(gte, gte)) into a barrier that
    consumes the TopK op directly, which crashes TopkDecomposer on the
    multi-device CPU path (it requires every TopK user to be a
    get-tuple-element)."""
    topv, idx = lax.top_k(mag, k)
    return lax.optimization_barrier(topv), lax.optimization_barrier(idx)


def topk_pack_fast(x: jnp.ndarray, k: int, block_size: int):
    """Bit-for-bit `ref.topk_pack_ref`, minus the re-run-the-sort fusions."""
    blocks = x.astype(jnp.float32).reshape(-1, block_size)
    topv, idx = _barrier_top_k(jnp.abs(blocks), k)
    sv = jnp.take_along_axis(blocks, idx, axis=-1)
    scale = topv[:, 0]
    safe = jnp.where(scale == 0.0, 1.0, scale)
    return idx.astype(jnp.int32), sv / safe[:, None], safe


def _scatter_blocks(idx: jnp.ndarray, sv: jnp.ndarray, rows: int,
                    block_size: int) -> jnp.ndarray:
    """Dense (rows*block_size,) with sv at per-block idx; `.at[].set` over
    a flat index — ~2x faster than a K-term where-accumulate on CPU."""
    base = jnp.arange(rows, dtype=jnp.int32)[:, None] * block_size
    flat_idx = (base + idx).reshape(-1)
    return jnp.zeros((rows * block_size,), jnp.float32).at[flat_idx].set(
        sv.reshape(-1))


def ef_topk_fused_fast(g: jnp.ndarray, e: jnp.ndarray, gamma, mask_self,
                       k: int, block_size: int,
                       value_dtype: str = "float32", want_c: bool = True):
    """Fused EF top-k local step, bit-for-bit `ref.ef_topk_fused_ref`.

    Returns (idx (R,k) i32, val (R,k) f32 value_dtype-rounded, scale (R,),
    c (n,) f32 or None, e_new (n,) f32) — `c` is the TRANSMITTED
    reconstruction (normalize -> value_dtype -> denormalize), so
    `c + e_new == acc` holds bit-exactly at kept coordinates (Sterbenz:
    c is within a factor of two of acc there, making `acc - c` exact)."""
    acc = mul_add(gamma, g, e)
    rows = acc.shape[0] // block_size
    accb = acc.reshape(rows, block_size)
    topv, idx = _barrier_top_k(jnp.abs(accb), k)
    sv = jnp.take_along_axis(accb, idx, axis=-1)
    scale = topv[:, 0]
    safe = jnp.where(scale == 0.0, 1.0, scale)
    val = (sv / safe[:, None]).astype(jnp.dtype(value_dtype)).astype(
        jnp.float32)
    c = _scatter_blocks(idx, val * safe[:, None], rows, block_size)
    e_new = jnp.where(mask_self > 0, acc - c, e)
    return (idx.astype(jnp.int32), val, safe, c if want_c else None, e_new)
