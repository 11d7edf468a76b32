"""Base layers: norms, RoPE, attention (GQA / MLA / sliding-window), MLPs.

Functional style: `init_*` build param dicts, `apply`-style functions are
pure.  Compute runs in cfg.dtype (bf16 on TPU), params stored in
cfg.param_dtype.  All shapes keep the head dimension explicit so the
partition rules in `repro.sharding.rules` can target them by name.

Attention has two entry points:
  attn_train(p, x, ...)                 full self-attention (train / prefill)
  attn_decode(p, x, cache, pos, ...)    one-step decode against a KV cache

KV caches are ring buffers: writes go to  pos % cache_len  and every entry
carries its absolute position (cache["pos"]), so a window-sized cache for
sliding-window layers and a full-length cache use the same code path.
"""
from __future__ import annotations

import math
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

BIG_WINDOW = 1 << 30  # "no window" sentinel usable as a traced value
NEG_INF = -1e30

# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / math.sqrt(max(1, in_axis_size))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: Optional[int] = None):
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.dtype(cfg.param_dtype))}
    if cfg.norm == "layer":
        p["bias"] = jnp.zeros((d,), jnp.dtype(cfg.param_dtype))
    return p


def apply_norm(p, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layer":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = (xf ** 2).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         freqs=None) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions broadcastable to (..., seq).
    freqs: the half head_dim inverse frequencies (default theta's)."""
    hd = x.shape[-1]
    half = hd // 2
    if freqs is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                 / half))
    ang = positions[..., None].astype(jnp.float32) * freqs        # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                              # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


YARN_BETA_FAST, YARN_BETA_SLOW = 32, 1   # rotations over the original length


def yarn_inv_freq(cfg: ModelConfig, dim: int) -> np.ndarray:
    """YaRN inverse frequencies of `dim` rotary dimensions (deepseek-v2's
    DeepseekV2YarnRotaryEmbedding): theta's frequencies where a pair turns
    fast (below pair `low`), theta's over `yarn_factor` where it turns
    slowly (above `high`), a linear ramp between."""
    base, f = cfg.rope_theta, cfg.yarn_factor

    def pair(rotations):      # the pair that turns `rotations` times
        return dim * math.log(cfg.yarn_original_max
                              / (rotations * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(pair(YARN_BETA_FAST)), 0)
    high = min(math.ceil(pair(YARN_BETA_SLOW)), dim - 1)
    pw = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 / (f * pw) * ramp + 1.0 / pw * (1.0 - ramp)).astype(
        np.float32)


# --------------------------------------------------------------------------
# GQA attention
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig):
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    d, qd = cfg.d_model, cfg.q_dim
    p = {
        "wq": dense_init(ks[0], (d, cfg.num_heads, cfg.head_dim), d, pd),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads, cfg.head_dim), d, pd),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads, cfg.head_dim), d, pd),
        "wo": dense_init(ks[3], (cfg.num_heads, cfg.head_dim, d), qd, pd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads, cfg.head_dim), pd)
        p["bk"] = jnp.zeros((cfg.num_kv_heads, cfg.head_dim), pd)
        p["bv"] = jnp.zeros((cfg.num_kv_heads, cfg.head_dim), pd)
    return p


def _qkv(p, x, cfg: ModelConfig, positions):
    ct = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(ct))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(ct))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(ct))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(ct)
        k = k + p["bk"].astype(ct)
        v = v + p["bv"].astype(ct)
    q = rope(q, positions, cfg.rope_theta) * (cfg.head_dim ** -0.5)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _softcap(scores, cap: float):
    return cap * jnp.tanh(scores / cap) if cap > 0 else scores


ATTN_CHUNK_MIN_S = 2048   # q-chunk long sequences (peak-memory: §Perf)
ATTN_CHUNK = 512


def _attn_core(q, k, v, cfg: ModelConfig, q_pos, k_pos, w_eff):
    """scores+softmax+values for one q block against full k/v."""
    B, Sq = q.shape[:2]
    ct = q.dtype
    groups = cfg.num_heads // cfg.num_kv_heads
    keep = (k_pos[None, :] <= q_pos[:, None]) & \
           (k_pos[None, :] > q_pos[:, None] - w_eff)              # (Sq, St)
    qh = q.reshape(B, Sq, cfg.num_kv_heads, groups, cfg.head_dim)
    scores = jnp.einsum("bsngk,btnk->bsngt", qh, k)
    scores = _softcap(scores, cfg.attn_softcap)
    scores = jnp.where(keep[None, :, None, None, :], scores, NEG_INF)
    wts = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(ct)
    out = jnp.einsum("bsngt,btnk->bsngk", wts, v)
    return out.reshape(B, Sq, cfg.num_heads, cfg.head_dim)


def attn_train(p, x, cfg: ModelConfig, window=0, return_kv: bool = False):
    """Full causal self-attention.  window: 0/BIG = global; may be traced
    (gemma2 alternation selects it per scanned layer).  return_kv=True also
    returns (k, v) in cache layout (B, Hkv, S, hd) for prefill.

    Long sequences are processed in q blocks (scan + per-block remat) so
    only one block's score matrix is ever live — an 8x peak-memory
    reduction at S=4096 (EXPERIMENTS.md §Perf).  The Pallas flash kernel
    (repro.kernels.flash_attention) replaces the block core on real TPU.
    """
    B, S, _ = x.shape
    pos = jnp.arange(S)[None]                                     # (1, S)
    q, k, v = _qkv(p, x, cfg, pos)
    w_eff = jnp.asarray(window if not isinstance(window, int) or window > 0
                        else BIG_WINDOW)
    k_pos = pos[0]
    # default "full": the q-chunked path was measured WORSE on the
    # trip-scaled cost model (k/v re-read + re-gathered per q block) —
    # EXPERIMENTS.md §Perf gemma2 iteration 2 (refuted); opt-in for
    # peak-constrained runs.
    mode = os.environ.get("REPRO_ATTN", "full")
    if mode == "chunked" and S >= ATTN_CHUNK_MIN_S and S % ATTN_CHUNK == 0:
        nblk = S // ATTN_CHUNK

        def block(_, qb_and_pos):
            qb, qp = qb_and_pos
            ob = _attn_core(qb, k, v, cfg, qp, k_pos, w_eff)
            return (), ob

        qb = q.reshape(B, nblk, ATTN_CHUNK, *q.shape[2:]).swapaxes(0, 1)
        qp = pos[0].reshape(nblk, ATTN_CHUNK)
        _, outs = jax.lax.scan(
            jax.checkpoint(block,
                           policy=jax.checkpoint_policies.nothing_saveable),
            (), (qb, qp))
        out = outs.swapaxes(0, 1).reshape(B, S, cfg.num_heads, cfg.head_dim)
    else:
        out = _attn_core(q, k, v, cfg, pos[0], k_pos, w_eff)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if return_kv:
        return y, (jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1))
    return y


def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, cache_len, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, cache_len, cfg.head_dim), dtype),
        "pos": jnp.full((cache_len,), -BIG_WINDOW, jnp.int32),
    }


def attn_decode(p, x, cfg: ModelConfig, cache: Dict[str, jnp.ndarray],
                pos, window=0):
    """One-step decode.  x: (B, 1, d); pos: scalar absolute position.
    Ring-buffer write at pos % cache_len."""
    B = x.shape[0]
    ct = x.dtype
    cache_len = cache["k"].shape[2]
    q, k, v = _qkv(p, x, cfg, jnp.full((1, 1), pos))
    slot = pos % cache_len
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], jnp.moveaxis(k, 2, 1).astype(cache["k"].dtype), slot, axis=2)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], jnp.moveaxis(v, 2, 1).astype(cache["v"].dtype), slot, axis=2)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.full((1,), pos, jnp.int32), slot, axis=0)
    w_eff = jnp.asarray(window if not isinstance(window, int) or window > 0
                        else BIG_WINDOW)
    keep = (cpos <= pos) & (cpos > pos - w_eff)                   # (T,)
    groups = cfg.num_heads // cfg.num_kv_heads
    qh = q.reshape(B, 1, cfg.num_kv_heads, groups, cfg.head_dim)
    scores = jnp.einsum("bsngk,bntk->bsngt", qh, ck.astype(ct))
    scores = _softcap(scores, cfg.attn_softcap)
    scores = jnp.where(keep[None, None, None, None, :], scores, NEG_INF)
    wts = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(ct)
    out = jnp.einsum("bsngt,bntk->bsngk", wts, cv.astype(ct))
    out = out.reshape(B, 1, cfg.num_heads, cfg.head_dim)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(ct))
    return y, {"k": ck, "v": cv, "pos": cpos}


def prefill_kv(p, x, cfg: ModelConfig, cache_len: int, dtype=jnp.bfloat16):
    """Build a cache from a full prefill pass (keeps the trailing cache_len
    positions when the prompt exceeds the ring)."""
    B, S, _ = x.shape
    pos = jnp.arange(S)[None]
    _, k, v = _qkv(p, x, cfg, pos)
    k = jnp.moveaxis(k, 2, 1)                                     # (B,H,S,hd)
    v = jnp.moveaxis(v, 2, 1)
    if S >= cache_len:
        sel = jnp.arange(S - cache_len, S)
    else:
        sel = jnp.arange(cache_len) % max(S, 1)
    ring_slot = sel % cache_len
    order = jnp.argsort(ring_slot)
    ck = k[:, :, sel[order]].astype(dtype)
    cv = v[:, :, sel[order]].astype(dtype)
    cpos = jnp.where(jnp.arange(cache_len) < min(S, cache_len),
                     sel[order], -BIG_WINDOW).astype(jnp.int32)
    return {"k": ck, "v": cv, "pos": cpos}


# --------------------------------------------------------------------------
# MLA (deepseek-v2): low-rank compressed KV with decoupled RoPE
# --------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig):
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    d, r = cfg.d_model, cfg.kv_lora_rank
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": dense_init(ks[0], (d, cfg.num_heads, qk), d, pd),
        "w_dkv": dense_init(ks[1], (d, r + cfg.qk_rope_dim), d, pd),
        "w_uk": dense_init(ks[2], (r, cfg.num_heads, cfg.qk_nope_dim), r, pd),
        "w_uv": dense_init(ks[3], (r, cfg.num_heads, cfg.v_head_dim), r, pd),
        "wo": dense_init(ks[4], (cfg.num_heads, cfg.v_head_dim, d),
                         cfg.num_heads * cfg.v_head_dim, pd),
        "kv_norm": jnp.ones((r,), pd),
    }


def _mla_rope(x, positions, cfg: ModelConfig):
    """RoPE of the decoupled rope dimensions, at YaRN's frequencies where
    the config sets yarn_factor (cos and sin keep scale 1: mscale equals
    mscale_all_dim)."""
    freqs = jnp.asarray(yarn_inv_freq(cfg, cfg.qk_rope_dim)) \
        if cfg.yarn_factor else None
    return rope(x, positions, cfg.rope_theta, freqs=freqs)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-0.5, times YaRN's (0.1 mscale ln factor + 1)^2."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor > 1:
        scale *= (0.1 * cfg.yarn_mscale * math.log(cfg.yarn_factor) + 1) ** 2
    return scale


def _mla_latent(p, x, cfg: ModelConfig, positions):
    """Compressed latent [c_kv ; k_rope]: (B, S, r + qk_rope)."""
    ct = x.dtype
    r = cfg.kv_lora_rank
    ckv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(ct))
    c, k_rope = ckv[..., :r], ckv[..., r:]
    cf = c.astype(jnp.float32)
    c = (cf * jax.lax.rsqrt((cf ** 2).mean(-1, keepdims=True) + 1e-6)
         * p["kv_norm"].astype(jnp.float32)).astype(ct)
    k_rope = _mla_rope(k_rope[..., None, :], positions, cfg)[..., 0, :]
    return jnp.concatenate([c, k_rope], axis=-1)


def _mla_attend(p, x, lat, cfg: ModelConfig, positions, keep):
    ct = x.dtype
    r = cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(ct))
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    q_rope = _mla_rope(q_rope, positions, cfg)
    c_all, krope_all = lat[..., :r], lat[..., r:]
    k_nope = jnp.einsum("btr,rhk->bthk", c_all, p["w_uk"].astype(ct))
    v = jnp.einsum("btr,rhk->bthk", c_all, p["w_uv"].astype(ct))
    scale = mla_softmax_scale(cfg)
    scores = (jnp.einsum("bshk,bthk->bsht", q_nope, k_nope)
              + jnp.einsum("bshk,btk->bsht", q_rope, krope_all)) * scale
    scores = jnp.where(keep[:, :, None, :], scores, NEG_INF)
    wts = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(ct)
    out = jnp.einsum("bsht,bthk->bshk", wts, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(ct))


def mla_train(p, x, cfg: ModelConfig, return_lat: bool = False):
    B, S, _ = x.shape
    pos = jnp.arange(S)[None]
    with jax.named_scope("mla"):
        lat = _mla_latent(p, x, cfg, pos)
        keep = (pos[0][None, :] <= pos[0][:, None])[None]         # (1,S,S)
        y = _mla_attend(p, x, lat, cfg, pos, keep)
    return (y, lat) if return_lat else y


def init_mla_cache(cfg: ModelConfig, batch: int, cache_len: int,
                   dtype=jnp.bfloat16):
    return {"lat": jnp.zeros((batch, cache_len,
                              cfg.kv_lora_rank + cfg.qk_rope_dim), dtype),
            "pos": jnp.full((cache_len,), -BIG_WINDOW, jnp.int32)}


def mla_decode(p, x, cfg: ModelConfig, cache, pos):
    cache_len = cache["lat"].shape[1]
    new_lat = _mla_latent(p, x, cfg, jnp.full((1, 1), pos))
    slot = pos % cache_len
    lat = jax.lax.dynamic_update_slice_in_dim(
        cache["lat"], new_lat.astype(cache["lat"].dtype), slot, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.full((1,), pos, jnp.int32), slot, axis=0)
    # exclude empty slots (pos == -BIG_WINDOW sentinel)
    keep = ((cpos <= pos) & (cpos > pos - BIG_WINDOW))[None, None]  # (1,1,T)
    y = _mla_attend(p, x, lat.astype(x.dtype), cfg, jnp.full((1, 1), pos), keep)
    return y, {"lat": lat, "pos": cpos}


def mla_prefill(p, x, cfg: ModelConfig, cache_len: int, dtype=jnp.bfloat16):
    B, S, _ = x.shape
    pos = jnp.arange(S)[None]
    lat = _mla_latent(p, x, cfg, pos)
    take = min(S, cache_len)
    out = jnp.zeros((B, cache_len, lat.shape[-1]), dtype)
    out = out.at[:, :take].set(lat[:, S - take:].astype(dtype))
    cpos = jnp.where(jnp.arange(cache_len) < take,
                     jnp.arange(cache_len) + (S - take), -BIG_WINDOW
                     ).astype(jnp.int32)
    return {"lat": out, "pos": cpos}


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    pd = jnp.dtype(cfg.param_dtype)
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    if cfg.mlp in ("swiglu", "geglu"):
        return {"w_gate": dense_init(ks[0], (d, ff), d, pd),
                "w_up": dense_init(ks[1], (d, ff), d, pd),
                "w_down": dense_init(ks[2], (ff, d), ff, pd)}
    return {"w_up": dense_init(ks[0], (d, ff), d, pd),
            "w_down": dense_init(ks[1], (ff, d), ff, pd)}


def apply_mlp(p, x, cfg: ModelConfig):
    ct = x.dtype
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"].astype(ct)) * (x @ p["w_up"].astype(ct))
    elif cfg.mlp == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"].astype(ct)) * (x @ p["w_up"].astype(ct))
    elif cfg.mlp == "relu2":
        h = jax.nn.relu(x @ p["w_up"].astype(ct)) ** 2
    else:  # gelu
        h = jax.nn.gelu(x @ p["w_up"].astype(ct))
    return h @ p["w_down"].astype(ct)


# --------------------------------------------------------------------------
# embeddings / head
# --------------------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig):
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 2)
    p = {}
    if cfg.input_mode == "tokens":
        p["tok"] = (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model))
                    .astype(pd))
    else:  # embeddings input: projection stub for the modality frontend
        p["proj"] = dense_init(ks[0], (cfg.d_model, cfg.d_model), cfg.d_model, pd)
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                               cfg.d_model, pd)
    return p


def embed(p, inputs, cfg: ModelConfig):
    ct = jnp.dtype(cfg.dtype)
    if cfg.input_mode == "tokens":
        x = p["tok"].astype(ct)[inputs]
        return x * (cfg.d_model ** 0.5) if cfg.name.startswith("gemma") else x
    return inputs.astype(ct) @ p["proj"].astype(ct)


def logits_from(p, x, cfg: ModelConfig):
    ct = x.dtype
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    logits = x @ w.astype(ct)
    if cfg.final_softcap > 0:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits
