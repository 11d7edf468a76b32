"""Decoder stacks for every assigned architecture family.

Stacks are built from scanned homogeneous layer groups (compile-time compact
HLO, remat-friendly):
  dense / moe      one scan over L stacked blocks
  deepseek         1 dense block + scan over (L-1) MLA+MoE blocks
  zamba2 (hybrid)  G groups of [scan over mamba2 layers] + shared attn block
  xlstm            G groups of [scan over mLSTM layers] + one sLSTM block

Each family provides train (full-sequence), prefill (train pass that also
emits caches) and decode (single-token) paths over the same parameters.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import ctx

from . import layers as L
from . import moe as MOE
from . import ssm as SSM
from . import xlstm as XL
from .config import ModelConfig




import os


def _res_strategy(cfg: ModelConfig) -> str:
    """Residual-stream sharding strategy (perf-iterated in EXPERIMENTS.md
    §Perf; override with REPRO_RES_SPEC=seq|channel|batch|none):
      seq      (B, S/model, d)  Megatron-SP — good for attention stacks
      channel  (B, S, d/model)  — naive; forces per-projection all-reduce
      batch    (B/model, S, d)  batch-parallel + FSDP-style weight gathers —
               the right shape for recurrent (conv/scan) families
    """
    env = os.environ.get("REPRO_RES_SPEC")
    if env:
        return env
    if cfg.family in ("hybrid", "xlstm"):
        return "batch"
    return "seq"


def _res(x, cfg: ModelConfig):
    """Residual-stream sharding constraint.  No-op outside a mesh context."""
    s = _res_strategy(cfg)
    if s == "none":
        return x
    if s == "batch":
        return ctx.constrain(x, ("model", "*", "*"))
    if s == "channel":
        return ctx.constrain(x, ("*", "*", "model"))
    return ctx.constrain(x, ("*", "model", "*"))

def _gb(blk, cfg: ModelConfig):
    """JIT weight gather (FSDP archs): see ctx.gather_block."""
    return ctx.gather_block(blk, jnp.dtype(cfg.dtype))


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat:
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    return fn


# ==========================================================================
# init
# ==========================================================================

def init_params(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {"embed": L.init_embedding(ks[0], cfg),
                              "final_norm": L.init_norm(cfg)}
    f = cfg.family

    if f in ("dense", "moe"):
        def one(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            blk = {"norm1": L.init_norm(cfg),
                   "attn": L.init_attention(k1, cfg),
                   "norm2": L.init_norm(cfg)}
            if f == "moe":
                blk["moe"] = MOE.init_moe(k2, cfg)
            else:
                blk["mlp"] = L.init_mlp(k3, cfg)
            return blk
        params["blocks"] = jax.vmap(one)(jax.random.split(ks[1], cfg.num_layers))

    elif f == "deepseek":
        k1, k2 = jax.random.split(ks[1])
        params["block0"] = {"norm1": L.init_norm(cfg),
                            "attn": L.init_mla(k1, cfg),
                            "norm2": L.init_norm(cfg),
                            "mlp": L.init_mlp(k2, cfg, cfg.dense_ff)}

        def one(k):
            k1, k2 = jax.random.split(k)
            return {"norm1": L.init_norm(cfg),
                    "attn": L.init_mla(k1, cfg),
                    "norm2": L.init_norm(cfg),
                    "moe": MOE.init_moe(k2, cfg)}
        params["blocks"] = jax.vmap(one)(
            jax.random.split(ks[2], cfg.num_layers - 1))

    elif f == "hybrid":
        per = cfg.hybrid_attn_period
        groups = cfg.num_layers // per

        def one(k):
            return {"norm1": L.init_norm(cfg), "mamba": SSM.init_mamba2(k, cfg)}
        params["blocks"] = jax.vmap(one)(
            jax.random.split(ks[1], cfg.num_layers))
        params["blocks"] = jax.tree.map(
            lambda x: x.reshape((groups, per) + x.shape[1:]), params["blocks"])
        k1, k2 = jax.random.split(ks[2])
        params["shared_attn"] = {"norm1": L.init_norm(cfg),
                                 "attn": L.init_attention(k1, cfg),
                                 "norm2": L.init_norm(cfg),
                                 "mlp": L.init_mlp(k2, cfg)}

    elif f == "xlstm":
        per = cfg.slstm_every
        groups = cfg.num_layers // per
        n_m = groups * (per - 1)

        def one_m(k):
            return {"norm1": L.init_norm(cfg), "mlstm": XL.init_mlstm(k, cfg)}

        def one_s(k):
            return {"norm1": L.init_norm(cfg), "slstm": XL.init_slstm(k, cfg)}
        m = jax.vmap(one_m)(jax.random.split(ks[1], n_m))
        params["mlstm_blocks"] = jax.tree.map(
            lambda x: x.reshape((groups, per - 1) + x.shape[1:]), m)
        params["slstm_blocks"] = jax.vmap(one_s)(
            jax.random.split(ks[2], groups))
    else:
        raise ValueError(f"unknown family {f}")
    return params


# ==========================================================================
# train / prefill forward
# ==========================================================================

def _layer_windows(cfg: ModelConfig, n: int) -> jnp.ndarray:
    """Per-layer attention windows (gemma2 local/global alternation)."""
    if cfg.local_global_period and cfg.sliding_window:
        idx = jnp.arange(n)
        return jnp.where(idx % cfg.local_global_period == 0,
                         cfg.sliding_window, L.BIG_WINDOW)
    if cfg.sliding_window:
        return jnp.full((n,), cfg.sliding_window)
    return jnp.full((n,), L.BIG_WINDOW)


def forward(params, inputs, cfg: ModelConfig, counters: bool = False):
    """inputs: tokens (B,S) int32 or embeddings (B,S,d).  Returns (B,S,d)
    final hidden states (normed) and the scalar MoE aux loss; with
    `counters`, also the rows routed to held experts over the MoE layers."""
    x = L.embed(_gb(params["embed"], cfg), inputs, cfg)
    f = cfg.family
    # (balance loss, [rows routed to held experts]), summed over layers
    stats = (jnp.zeros((), jnp.float32),) + (
        (jnp.zeros((), jnp.int32),) if counters else ())

    def add(stats, more):
        return tuple(a + b for a, b in zip(stats, more))

    if f in ("dense", "moe"):
        windows = _layer_windows(cfg, cfg.num_layers)

        def block(carry, scanned):
            x, stats = carry
            blk, win = scanned
            blk = _gb(blk, cfg)
            h = L.attn_train(blk["attn"], L.apply_norm(blk["norm1"], x, cfg),
                             cfg, window=win)
            x = x + h
            h2 = L.apply_norm(blk["norm2"], x, cfg)
            if f == "moe":
                h2, *more = MOE.apply_moe(blk["moe"], h2, cfg, counters)
                stats = add(stats, more)
            else:
                h2 = L.apply_mlp(blk["mlp"], h2, cfg)
            return (_res(x + h2, cfg), stats), None

        x = _res(x, cfg)
        (x, stats), _ = jax.lax.scan(_maybe_remat(block, cfg), (x, stats),
                                     (params["blocks"], windows))

    elif f == "deepseek":
        b0 = _gb(params["block0"], cfg)
        x = x + L.mla_train(b0["attn"], L.apply_norm(b0["norm1"], x, cfg), cfg)
        x = x + L.apply_mlp(b0["mlp"], L.apply_norm(b0["norm2"], x, cfg), cfg)

        def block(carry, blk):
            x, stats = carry
            blk = _gb(blk, cfg)
            x = x + L.mla_train(blk["attn"],
                                L.apply_norm(blk["norm1"], x, cfg), cfg)
            h, *more = MOE.apply_moe(blk["moe"],
                                     L.apply_norm(blk["norm2"], x, cfg), cfg,
                                     counters)
            return (_res(x + h, cfg), add(stats, more)), None

        x = _res(x, cfg)
        (x, stats), _ = jax.lax.scan(_maybe_remat(block, cfg), (x, stats),
                                     params["blocks"])

    elif f == "hybrid":
        def mamba_block(x, blk):
            blk = _gb(blk, cfg)
            h, _ = SSM.apply_mamba2(blk["mamba"],
                                    L.apply_norm(blk["norm1"], x, cfg), cfg)
            return _res(x + h, cfg), None
        sa = _gb(params["shared_attn"], cfg)
        groups = cfg.num_layers // cfg.hybrid_attn_period
        for g in range(groups):
            grp = jax.tree.map(lambda p: p[g], params["blocks"])
            x, _ = jax.lax.scan(_maybe_remat(mamba_block, cfg), x, grp)
            h = L.attn_train(sa["attn"], L.apply_norm(sa["norm1"], x, cfg), cfg)
            x = x + h
            x = x + L.apply_mlp(sa["mlp"], L.apply_norm(sa["norm2"], x, cfg), cfg)

    elif f == "xlstm":
        def m_block(x, blk):
            blk = _gb(blk, cfg)
            h, _ = XL.apply_mlstm(blk["mlstm"],
                                  L.apply_norm(blk["norm1"], x, cfg), cfg)
            return _res(x + h, cfg), None
        groups = cfg.num_layers // cfg.slstm_every
        for g in range(groups):
            grp = jax.tree.map(lambda p: p[g], params["mlstm_blocks"])
            x, _ = jax.lax.scan(_maybe_remat(m_block, cfg), x, grp)
            sb = jax.tree.map(lambda p: p[g], params["slstm_blocks"])
            h, _ = XL.apply_slstm(sb["slstm"],
                                  L.apply_norm(sb["norm1"], x, cfg), cfg)
            x = x + h
    else:
        raise ValueError(f)

    return (L.apply_norm(params["final_norm"], x, cfg),) + stats


def weighted_loss(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
                  counters: bool = False):
    """Coded training loss: sum_j w_j * mean-token-NLL(example j), plus
    `moe_aux_weight` times the MoE balance loss.  Returns (loss,
    per-example NLL), and with `counters` the rows routed to held experts.

    batch: {"inputs": tokens (B,S+1) or embeddings (B,S,d),
            "targets": (B,S) int32 (embeddings mode only),
            "weights": (B,) f32 coded weights 1/(d_k(1-p)) / subset_size}.
    """
    if cfg.input_mode == "tokens":
        inputs = batch["inputs"][:, :-1]
        targets = batch["inputs"][:, 1:]
    else:
        inputs = batch["inputs"]
        targets = batch["targets"]
    x, aux, *rows = forward(params, inputs, cfg, counters)
    logits = L.logits_from(_gb(params["embed"], cfg), x, cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    per_example = nll.mean(axis=-1)                       # (B,)
    loss = (per_example * batch["weights"]).sum()
    return (loss + cfg.moe_aux_weight * aux, per_example) + tuple(rows)


# ==========================================================================
# prefill (full pass that also emits serving caches)
# ==========================================================================

def prefill(params, inputs, cfg: ModelConfig, cache_dtype=jnp.bfloat16):
    """Full forward over the prompt, returning (last-token logits, caches).
    Cache length == prompt length (the decode step then appends)."""
    x = L.embed(params["embed"], inputs, cfg)
    f = cfg.family
    if cfg.input_mode == "tokens":
        B, S = inputs.shape
    else:
        B, S = inputs.shape[:2]
    arange_pos = jnp.arange(S, dtype=jnp.int32)

    if f in ("dense", "moe"):
        windows = _layer_windows(cfg, cfg.num_layers)

        def block(x, scanned):
            blk, win = scanned
            h, (k, v) = L.attn_train(blk["attn"],
                                     L.apply_norm(blk["norm1"], x, cfg),
                                     cfg, window=win, return_kv=True)
            x = x + h
            h2 = L.apply_norm(blk["norm2"], x, cfg)
            if f == "moe":
                h2, _ = MOE.apply_moe(blk["moe"], h2, cfg)
            else:
                h2 = L.apply_mlp(blk["mlp"], h2, cfg)
            return _res(x + h2, cfg), (k.astype(cache_dtype),
                                       v.astype(cache_dtype))

        x = _res(x, cfg)
        x, (ks, vs) = jax.lax.scan(block, x, (params["blocks"], windows))
        caches = {"kv": {"k": ks, "v": vs,
                         "pos": jnp.broadcast_to(arange_pos,
                                                 (cfg.num_layers, S))}}

    elif f == "deepseek":
        b0 = params["block0"]
        h, lat0 = L.mla_train(b0["attn"], L.apply_norm(b0["norm1"], x, cfg),
                              cfg, return_lat=True)
        x = x + h
        x = x + L.apply_mlp(b0["mlp"], L.apply_norm(b0["norm2"], x, cfg), cfg)

        def block(x, blk):
            h, lat = L.mla_train(blk["attn"],
                                 L.apply_norm(blk["norm1"], x, cfg), cfg,
                                 return_lat=True)
            x = x + h
            h2, _ = MOE.apply_moe(blk["moe"],
                                  L.apply_norm(blk["norm2"], x, cfg), cfg)
            return _res(x + h2, cfg), lat.astype(cache_dtype)

        x, lats = jax.lax.scan(block, x, params["blocks"])
        caches = {"mla0": {"lat": lat0.astype(cache_dtype), "pos": arange_pos},
                  "mla": {"lat": lats,
                          "pos": jnp.broadcast_to(arange_pos,
                                                  (cfg.num_layers - 1, S))}}

    elif f == "hybrid":
        def mamba_block(x, blk):
            h, st = SSM.apply_mamba2(blk["mamba"],
                                     L.apply_norm(blk["norm1"], x, cfg), cfg)
            return _res(x + h, cfg), st

        sa = params["shared_attn"]
        groups = cfg.num_layers // cfg.hybrid_attn_period
        ssm_states, kvs = [], []
        for g in range(groups):
            grp = jax.tree.map(lambda p: p[g], params["blocks"])
            x, st = jax.lax.scan(mamba_block, x, grp)
            ssm_states.append(st)
            h, (k, v) = L.attn_train(sa["attn"],
                                     L.apply_norm(sa["norm1"], x, cfg), cfg,
                                     return_kv=True)
            x = x + h
            x = x + L.apply_mlp(sa["mlp"], L.apply_norm(sa["norm2"], x, cfg),
                                cfg)
            kvs.append({"k": k.astype(cache_dtype), "v": v.astype(cache_dtype),
                        "pos": arange_pos})
        caches = {"ssm": jax.tree.map(lambda *xs: jnp.stack(xs), *ssm_states),
                  "kv": jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)}

    elif f == "xlstm":
        def m_block(x, blk):
            h, st = XL.apply_mlstm(blk["mlstm"],
                                   L.apply_norm(blk["norm1"], x, cfg), cfg)
            return _res(x + h, cfg), st

        groups = cfg.num_layers // cfg.slstm_every
        all_m, sstates = [], []
        for g in range(groups):
            grp = jax.tree.map(lambda p: p[g], params["mlstm_blocks"])
            x, st = jax.lax.scan(m_block, x, grp)
            all_m.append(st)
            sb = jax.tree.map(lambda p: p[g], params["slstm_blocks"])
            h, ss = XL.apply_slstm(sb["slstm"],
                                   L.apply_norm(sb["norm1"], x, cfg), cfg)
            x = x + h
            sstates.append(ss)
        caches = {"mlstm": jax.tree.map(lambda *xs: jnp.stack(xs), *all_m),
                  "slstm": jax.tree.map(lambda *xs: jnp.stack(xs), *sstates)}
    else:
        raise ValueError(f)

    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.logits_from(params["embed"], x[:, -1:], cfg)
    return logits[:, -1], caches


# ==========================================================================
# caches / decode
# ==========================================================================

def init_caches(cfg: ModelConfig, batch: int, cache_len: int,
                dtype=jnp.bfloat16):
    f = cfg.family
    if f in ("dense", "moe"):
        if cfg.local_global_period and cfg.sliding_window:
            lens = [min(cache_len, cfg.sliding_window)
                    if i % cfg.local_global_period == 0 else cache_len
                    for i in range(cfg.num_layers)]
            # ring caches sized per layer; cap globals at window for the
            # 500k cell (documented deviation) happens in the config shape
            ml = max(lens)
            caches = jax.vmap(lambda _: L.init_kv_cache(cfg, batch, ml, dtype)
                              )(jnp.arange(cfg.num_layers))
            return {"kv": caches}
        caches = jax.vmap(lambda _: L.init_kv_cache(cfg, batch, cache_len,
                                                    dtype))(
            jnp.arange(cfg.num_layers))
        return {"kv": caches}
    if f == "deepseek":
        c0 = L.init_mla_cache(cfg, batch, cache_len, dtype)
        cs = jax.vmap(lambda _: L.init_mla_cache(cfg, batch, cache_len, dtype)
                      )(jnp.arange(cfg.num_layers - 1))
        return {"mla0": c0, "mla": cs}
    if f == "hybrid":
        per = cfg.hybrid_attn_period
        groups = cfg.num_layers // per
        ssm = jax.vmap(lambda _: SSM.init_mamba2_cache(cfg, batch)
                       )(jnp.arange(cfg.num_layers))
        ssm = jax.tree.map(lambda x: x.reshape((groups, per) + x.shape[1:]), ssm)
        kv = jax.vmap(lambda _: L.init_kv_cache(cfg, batch, cache_len, dtype)
                      )(jnp.arange(groups))
        return {"ssm": ssm, "kv": kv}
    if f == "xlstm":
        per = cfg.slstm_every
        groups = cfg.num_layers // per
        m = jax.vmap(lambda _: XL.init_mlstm_cache(cfg, batch)
                     )(jnp.arange(groups * (per - 1)))
        m = jax.tree.map(lambda x: x.reshape((groups, per - 1) + x.shape[1:]), m)
        s = jax.vmap(lambda _: XL.init_slstm_cache(cfg, batch)
                     )(jnp.arange(groups))
        return {"mlstm": m, "slstm": s}
    raise ValueError(f)


def decode_step(params, caches, inputs, pos, cfg: ModelConfig):
    """One-token decode.  inputs: (B, 1) tokens or (B, 1, d) embeddings;
    pos: scalar absolute position.  Returns (logits (B, vocab), caches)."""
    x = L.embed(params["embed"], inputs, cfg)
    f = cfg.family

    if f in ("dense", "moe"):
        windows = _layer_windows(cfg, cfg.num_layers)

        def block(x, scanned):
            blk, cache, win = scanned
            h, new_cache = L.attn_decode(
                blk["attn"], L.apply_norm(blk["norm1"], x, cfg), cfg, cache,
                pos, window=win)
            x = x + h
            h2 = L.apply_norm(blk["norm2"], x, cfg)
            if f == "moe":
                h2, _ = MOE.apply_moe(blk["moe"], h2, cfg)
            else:
                h2 = L.apply_mlp(blk["mlp"], h2, cfg)
            return x + h2, new_cache

        x, kv = jax.lax.scan(block, x,
                             (params["blocks"], caches["kv"], windows))
        caches = {"kv": kv}

    elif f == "deepseek":
        b0 = params["block0"]
        h, c0 = L.mla_decode(b0["attn"], L.apply_norm(b0["norm1"], x, cfg),
                             cfg, caches["mla0"], pos)
        x = x + h
        x = x + L.apply_mlp(b0["mlp"], L.apply_norm(b0["norm2"], x, cfg), cfg)

        def block(x, scanned):
            blk, cache = scanned
            h, nc = L.mla_decode(blk["attn"],
                                 L.apply_norm(blk["norm1"], x, cfg), cfg,
                                 cache, pos)
            x = x + h
            h2, _ = MOE.apply_moe(blk["moe"],
                                  L.apply_norm(blk["norm2"], x, cfg), cfg)
            return x + h2, nc

        x, cs = jax.lax.scan(block, x, (params["blocks"], caches["mla"]))
        caches = {"mla0": c0, "mla": cs}

    elif f == "hybrid":
        def mamba_block(x, scanned):
            blk, (ssm_s, conv_s) = scanned
            h, (ns, ncv) = SSM.apply_mamba2(
                blk["mamba"], L.apply_norm(blk["norm1"], x, cfg), cfg,
                ssm_state=ssm_s, conv_state=conv_s)
            return x + h, (ns, ncv)

        sa = params["shared_attn"]
        groups = cfg.num_layers // cfg.hybrid_attn_period
        new_ssm, new_kv = [], []
        for g in range(groups):
            grp = jax.tree.map(lambda p: p[g], params["blocks"])
            grp_cache = jax.tree.map(lambda c: c[g], caches["ssm"])
            x, ns = jax.lax.scan(mamba_block, x, (grp, grp_cache))
            new_ssm.append(ns)
            kv_g = jax.tree.map(lambda c: c[g], caches["kv"])
            h, nkv = L.attn_decode(sa["attn"],
                                   L.apply_norm(sa["norm1"], x, cfg), cfg,
                                   kv_g, pos)
            x = x + h
            x = x + L.apply_mlp(sa["mlp"], L.apply_norm(sa["norm2"], x, cfg),
                                cfg)
            new_kv.append(nkv)
        caches = {"ssm": jax.tree.map(lambda *xs: jnp.stack(xs), *new_ssm),
                  "kv": jax.tree.map(lambda *xs: jnp.stack(xs), *new_kv)}

    elif f == "xlstm":
        def m_block(x, scanned):
            blk, st = scanned
            h, ns = XL.apply_mlstm(blk["mlstm"],
                                   L.apply_norm(blk["norm1"], x, cfg), cfg,
                                   state=st)
            return x + h, ns

        groups = cfg.num_layers // cfg.slstm_every
        new_m, new_s = [], []
        for g in range(groups):
            grp = jax.tree.map(lambda p: p[g], params["mlstm_blocks"])
            grp_c = jax.tree.map(lambda c: c[g], caches["mlstm"])
            x, nm = jax.lax.scan(m_block, x, (grp, grp_c))
            new_m.append(nm)
            sb = jax.tree.map(lambda p: p[g], params["slstm_blocks"])
            sc = jax.tree.map(lambda c: c[g], caches["slstm"])
            h, ns = XL.apply_slstm(sb["slstm"],
                                   L.apply_norm(sb["norm1"], x, cfg), cfg,
                                   state=sc)
            x = x + h
            new_s.append(ns)
        caches = {"mlstm": jax.tree.map(lambda *xs: jnp.stack(xs), *new_m),
                  "slstm": jax.tree.map(lambda *xs: jnp.stack(xs), *new_s)}
    else:
        raise ValueError(f)

    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.logits_from(params["embed"], x, cfg)
    return logits[:, -1], caches
