"""Pure-jnp oracles for every kernel in this package.

These define the semantics; the Pallas kernels must match them (allclose,
or bit-exact where noted) across the shape/dtype sweeps in
tests/test_kernels.py.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.compat import vma


def scan_zeros(shape, *operands, dtype=jnp.float32) -> jnp.ndarray:
    """Zeros that start a rank-order accumulation scan over `operands`.
    Inside a shard_map (check_vma on) the scan carry must vary over the
    same mesh axes as what the body adds into it; a bare `jnp.zeros`
    varies over none, so the zeros are cast to the operands' axes."""
    return lax.pcast(jnp.zeros(shape, dtype), tuple(sorted(vma(*operands))),
                     to="varying")


def mul_add(gamma, g: jnp.ndarray, e: jnp.ndarray) -> jnp.ndarray:
    """THE Algorithm-1 accumulate  acc = gamma * g + e  with the multiply
    and the add kept as two separately-rounded IEEE f32 ops (the
    optimization_barrier blocks XLA's FMA contraction).  Every
    implementation of the accumulate — the reference (N, D) EF loop, the
    jnp fused kernels here, and the per-rank-budget path of cocoef_update —
    routes through this one definition, so their accumulators agree
    BIT-FOR-BIT instead of drifting an FMA-ulp apart depending on the
    surrounding fusion (caught by repro.launch.parity)."""
    return jax.lax.optimization_barrier(
        gamma * g.astype(jnp.float32)) + e.astype(jnp.float32)


def sign_pack_ref(x: jnp.ndarray, group_size: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (n,) f32 -> (words (n/32,) u32, scales (n/g,) f32).
    scales = mean |x| per group; bit j of word w = x[32w+j] >= 0."""
    xf = x.astype(jnp.float32)
    scales = jnp.mean(jnp.abs(xf.reshape(-1, group_size)), axis=-1)
    bits = (xf >= 0).reshape(-1, 32).astype(jnp.uint32)
    words = (bits << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)
    return words, scales


def sign_unpack_ref(words: jnp.ndarray, scales: jnp.ndarray,
                    group_size: int) -> jnp.ndarray:
    bits = (words[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    signs = bits.astype(jnp.float32).reshape(-1, group_size) * 2.0 - 1.0
    # per-group scale via broadcast (jnp.repeat lowers to a scatter loop)
    return (signs * scales.astype(jnp.float32)[:, None]).reshape(-1)


def ef_sign_fused_ref(g: jnp.ndarray, e: jnp.ndarray, gamma, mask_self,
                      group_size: int):
    """Fused Algorithm-1 local step (one pass over the model-sized vectors):
      acc = gamma * g + e
      (words, scales) = sign_pack(acc)
      c = sign_unpack(words, scales)
      e_new = mask_self ? acc - c : e
    Returns (words, scales, c, e_new)."""
    ef = e.astype(jnp.float32)
    accg = mul_add(gamma, g, e).reshape(-1, group_size)
    scales = jnp.mean(jnp.abs(accg), axis=-1)
    bits = (accg.reshape(-1, 32) >= 0).astype(jnp.uint32)
    words = (bits << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)
    # c == sign_unpack_ref(words, scales) bit-for-bit, but straight from acc
    # (no bit unpack): sign(acc) * group scale — matches the Pallas kernel.
    # Staying 2D until the end keeps XLA's fusions on one layout.
    c = jnp.where(accg >= 0, 1.0, -1.0) * scales[:, None]
    e_new = jnp.where(mask_self > 0, accg - c,
                      ef.reshape(-1, group_size))
    return words, scales, c.reshape(-1), e_new.reshape(-1)


def sign_decode_reduce_ref(words: jnp.ndarray, scales: jnp.ndarray,
                           mask: jnp.ndarray, group_size: int) -> jnp.ndarray:
    """Server-side decode+aggregate: words (N, n/32), scales (N, n/g),
    mask (N,) -> sum_i mask_i * unpack(words_i, scales_i)   (n,)."""
    dec = jax.vmap(lambda w, s: sign_unpack_ref(w, s, group_size)
                   )(words, scales)
    return (mask[:, None] * dec).sum(0)


def sign_decode_reduce_scan(words: jnp.ndarray, scales: jnp.ndarray,
                            mask: jnp.ndarray, group_size: int) -> jnp.ndarray:
    """Streaming jnp implementation of `sign_decode_reduce_ref` — identical
    sender-order accumulation (bit-for-bit), but scans over senders so the
    (N, n) dense tensor is never materialized.  This is the backend's jnp
    fused decode path; the vmap oracle above stays the test reference."""
    n = words.shape[1] * 32

    def body(acc, inp):
        w, s, m = inp
        return acc + m * sign_unpack_ref(w, s, group_size), None
    return lax.scan(body, scan_zeros((n,), words, scales, mask),
                    (words, scales, mask))[0]


def topk_pack_ref(x: jnp.ndarray, k: int, block_size: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sparse (block top-K) wire pack — repro.core.collectives.SparseWire.

    x: (n,) -> (indices (n/B, k) i32 in decreasing-|.| order (first
    occurrence wins ties, matching lax.top_k / the Pallas kernel),
    values (n/B, k) f32 normalized by the block scale, scales (n/B,) f32 =
    per-block max |x| with 1.0 substituted for all-zero blocks)."""
    blocks = x.astype(jnp.float32).reshape(-1, block_size)
    mag = jnp.abs(blocks)
    topv, idx = jax.lax.top_k(mag, k)
    sv = jnp.take_along_axis(blocks, idx, axis=-1)
    scale = topv[:, 0]                     # block max |x| = first top-k value
    safe = jnp.where(scale == 0, 1.0, scale)
    return idx.astype(jnp.int32), sv / safe[:, None], safe


def ef_topk_fused_ref(g: jnp.ndarray, e: jnp.ndarray, gamma, mask_self,
                      k: int, block_size: int,
                      value_dtype: str = "float32"):
    """Fused Algorithm-1 local step on the sparse (block top-K) wire:
      acc = gamma * g + e
      (indices, values, scales) = topk_pack(acc), values rounded to
          value_dtype (the wire's payload precision), carried in f32
      c = scatter of values * scale — the TRANSMITTED reconstruction,
          i.e. exactly what `topk_unpack_ref` gives a receiver, so the
          error update tracks the wire and no unpack-of-pack is needed
      e_new = mask_self ? acc - c : e
    Returns (indices, values, scales, c, e_new).  `c + e_new == acc` stays
    bit-exact at kept coordinates: c is within a factor of two of acc
    there (value_dtype relative error << 1/2), so Sterbenz's lemma makes
    the subtraction exact and the sum rounds back to acc."""
    accb = mul_add(gamma, g, e).reshape(-1, block_size)
    mag = jnp.abs(accb)
    topv, idx = jax.lax.top_k(mag, k)
    sv = jnp.take_along_axis(accb, idx, axis=-1)
    scale = topv[:, 0]
    safe = jnp.where(scale == 0, 1.0, scale)
    val = (sv / safe[:, None]).astype(jnp.dtype(value_dtype)).astype(
        jnp.float32)
    nb = accb.shape[0]
    base = jnp.arange(nb, dtype=jnp.int32)[:, None] * block_size
    flat_idx = (base + idx).reshape(-1)
    c = jnp.zeros((nb * block_size,), jnp.float32
                  ).at[flat_idx].set((val * safe[:, None]).reshape(-1))
    acc = accb.reshape(-1)
    e_new = jnp.where(mask_self > 0, acc - c, e.astype(jnp.float32))
    return idx.astype(jnp.int32), val, safe, c, e_new


def dense_decode_reduce_ref(values: jnp.ndarray, mask: jnp.ndarray
                            ) -> jnp.ndarray:
    """Dense-wire decode+aggregate: values (N, n) any float dtype,
    mask (N,) -> sum_i mask_i * f32(values_i)   (n,)."""
    return (mask[:, None] * values.astype(jnp.float32)).sum(0)


def dense_decode_reduce_scan(values: jnp.ndarray, mask: jnp.ndarray
                             ) -> jnp.ndarray:
    """Streaming variant of `dense_decode_reduce_ref` with the SAME
    sender-order accumulation the sign/topk scan decoders use (XLA's .sum(0)
    may reduce pairwise — a different rounding).  This is the backend's jnp
    decode path so every wire aggregates in one canonical order."""
    n = values.shape[1]

    def body(acc, inp):
        v, m = inp
        return acc + m * v.astype(jnp.float32), None
    return lax.scan(body, scan_zeros((n,), values, mask),
                    (values, mask))[0]


def topk_unpack_ref(indices: jnp.ndarray, values: jnp.ndarray,
                    scales: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """Inverse of topk_pack_ref: scatter the kept entries back, flat (n,)."""
    nb = indices.shape[0]
    sv = values.astype(jnp.float32) * scales[:, None]
    base = jnp.arange(nb, dtype=jnp.int32)[:, None] * block_size
    flat_idx = (base + indices.astype(jnp.int32)).reshape(-1)
    return jnp.zeros((nb * block_size,), jnp.float32
                     ).at[flat_idx].set(sv.reshape(-1))


def topk_decode_reduce_ref(indices: jnp.ndarray, values: jnp.ndarray,
                           scales: jnp.ndarray, mask: jnp.ndarray,
                           block_size: int) -> jnp.ndarray:
    """Server-side sparse decode+aggregate: indices/values (N, n/B, k),
    scales (N, n/B), mask (N,) -> sum_i mask_i * unpack(payload_i)  (n,)."""
    dec = jax.vmap(lambda i, v, s: topk_unpack_ref(i, v, s, block_size)
                   )(indices, values, scales)
    return (mask[:, None] * dec).sum(0)


def topk_decode_reduce_scan(indices: jnp.ndarray, values: jnp.ndarray,
                            scales: jnp.ndarray, mask: jnp.ndarray,
                            block_size: int) -> jnp.ndarray:
    """Streaming jnp implementation of `topk_decode_reduce_ref` — identical
    sender-order accumulation (bit-for-bit) without the (N, n) dense
    tensor; the backend's jnp fused decode path."""
    n = indices.shape[1] * block_size

    def body(acc, inp):
        i, v, s, m = inp
        return acc + m * topk_unpack_ref(i, v, s, block_size), None
    return lax.scan(body, scan_zeros((n,), indices, values, scales, mask),
                    (indices, values, scales, mask))[0]


def block_topk_ref(x: jnp.ndarray, k: int, block_size: int) -> jnp.ndarray:
    """Block-local top-k sparsification (repro.core.compression.BlockTopK):
    keep the k largest-|.| entries of each contiguous block."""
    blocks = x.reshape(-1, block_size)
    topv = jax.lax.top_k(jnp.abs(blocks), k)[0]
    thr = topv[:, -1:]
    keep = jnp.abs(blocks) >= thr
    cum = jnp.cumsum(keep.astype(jnp.int32), axis=-1)
    keep = keep & (cum <= k)
    return jnp.where(keep, blocks, 0).reshape(x.shape)


def flash_attention_ref(q, k, v, softcap: float = 0.0, window: int = 0,
                        groups: int = 1):
    """q: (B,H,S,hd) pre-scaled; k,v: (B,Hkv,S,hd).  Causal+window+softcap."""
    B, H, S, hd = q.shape
    w = window if window > 0 else (1 << 30)
    kk = jnp.repeat(k, groups, axis=1)
    vv = jnp.repeat(v, groups, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32))
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(S)[None, :]
    keep = (kp <= qp) & (kp > qp - w)
    s = jnp.where(keep[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32)
                      ).astype(q.dtype)
