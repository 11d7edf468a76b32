"""Mixture-of-Experts layer: dropless routing to the experts held here.

The router scores every one of the `moe_experts` experts; this device
holds experts 0 .. `experts_held` - 1 (its share of an expert-parallel
deployment) and computes their part of the result for the tokens routed
to them:

  s = softmax(x W_r) over all experts (f32),   I(x) = top_k(s),
  g_e = s_e (or s_e / sum_{I(x)} s, with `moe_norm_topk`),
  y = sum_{e in I(x), e held} g_e W_down,e (silu(W_gate,e x) * W_up,e x)
      + shared(x).

No capacity and no dropped tokens: the (token, expert) assignments are
sorted by expert id, so the held experts' rows come first, grouped by
expert, and run through `jax.lax.ragged_dot` (a grouped matmul).  The
static row buffer is the worst case, T x min(k, held).  What the experts
held elsewhere would add is not computed here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from .config import ModelConfig
from .layers import dense_init


def _map_rule(fn):
    """A vmap rule that runs `fn` once per batch element (unbatched
    arguments broadcast): ragged_dot has no batching rule for a batched
    lhs and unbatched weights, which the train step's vmap over coding
    ranks makes."""
    def rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        out = jax.lax.map(lambda a: fn(*a), tuple(args))
        return out, jax.tree.map(lambda _: True, out)
    return rule


@custom_vmap
def _ragged(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes)


@custom_vmap
def _ragged_vjp(x, w, group_sizes, dy):
    return jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, group_sizes),
                   x, w)[1](dy)


_ragged.def_vmap(_map_rule(_ragged.fun))
_ragged_vjp.def_vmap(_map_rule(_ragged_vjp.fun))


def _grouped_rows(y, group_sizes):
    """y with its rows past sum(group_sizes) set to 0: on the TPU
    ragged_dot leaves them unwritten."""
    return jnp.where((jnp.arange(y.shape[0]) < group_sizes.sum())[:, None],
                     y, jnp.zeros((), y.dtype))


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    """(R, d) rows grouped by expert x (G, d, f) -> (R, f): rows
    [sum(gs[:g]), sum(gs[:g+1])) times w[g]; rows past sum(gs) give 0."""
    return _grouped_rows(_ragged(x, w, group_sizes), group_sizes)


def _gmm_fwd(x, w, group_sizes):
    return (_grouped_rows(_ragged(x, w, group_sizes), group_sizes),
            (x, w, group_sizes))


def _gmm_bwd(res, dy):
    x, w, group_sizes = res
    dx, dw = _ragged_vjp(x, w, group_sizes, dy)
    return _grouped_rows(dx, group_sizes), dw, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def init_moe(key, cfg: ModelConfig):
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    d, ff, E, H = cfg.d_model, cfg.moe_ff, cfg.moe_experts, cfg.experts_held
    p = {
        "router": dense_init(ks[0], (d, E), d, pd),
        "w_gate": dense_init(ks[1], (H, d, ff), d, pd),
        "w_up": dense_init(ks[2], (H, d, ff), d, pd),
        "w_down": dense_init(ks[3], (H, ff, d), ff, pd),
    }
    if cfg.moe_shared > 0:
        sff = ff * cfg.moe_shared
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": dense_init(kk[0], (d, sff), d, pd),
                       "w_up": dense_init(kk[1], (d, sff), d, pd),
                       "w_down": dense_init(kk[2], (sff, d), sff, pd)}
    return p


def apply_moe(p, x, cfg: ModelConfig, counters: bool = False):
    """x: (B, S, d) -> ((B, S, d), balance loss), and with `counters` the
    number of rows routed to the held experts as a third output.

    Balance loss (Switch-style over the whole batch): E * sum_e P_e f_e,
    P_e the mean router probability, f_e the share of tokens that picked
    expert e, over all E experts."""
    ct = x.dtype
    B, S, d = x.shape
    T = B * S
    E, H, k = cfg.moe_experts, cfg.experts_held, cfg.moe_top_k
    R = T * min(k, H)
    xt = x.reshape(T, d)

    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            logits = (xt.astype(jnp.float32)
                      @ p["router"].astype(jnp.float32))          # (T, E)
            probs = jax.nn.softmax(logits, axis=-1)
            gate_vals, gate_idx = jax.lax.top_k(probs, k)          # (T, k)
            if cfg.moe_norm_topk:
                gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)

        with jax.named_scope("dispatch"):
            # held experts have the lowest ids: after a stable sort their
            # assignments are the first rows, grouped by expert; a token
            # holds at most min(k, H) of them, so R rows hold them all
            eflat = gate_idx.reshape(-1)                           # (T*k,)
            rows = jnp.argsort(eflat, stable=True)[:R]
            expert = eflat[rows]
            held = expert < H
            token = rows // k
            group_sizes = jnp.bincount(jnp.where(held, expert, H),
                                       length=H + 1)[:H].astype(jnp.int32)
            xs = xt[token]                                         # (R, d)

        with jax.named_scope("experts"):
            h = (jax.nn.silu(grouped_matmul(xs, p["w_gate"].astype(ct),
                                            group_sizes))
                 * grouped_matmul(xs, p["w_up"].astype(ct), group_sizes))
            ys = grouped_matmul(h, p["w_down"].astype(ct), group_sizes)

        with jax.named_scope("combine"):
            gv = jnp.where(held, gate_vals.reshape(-1)[rows], 0.0).astype(ct)
            out = jnp.zeros((T, d), ct).at[token].add(ys * gv[:, None])

        if cfg.moe_shared > 0:
            with jax.named_scope("shared"):
                sp = p["shared"]
                hs = (jax.nn.silu(xt @ sp["w_gate"].astype(ct))
                      * (xt @ sp["w_up"].astype(ct)))
                out = out + hs @ sp["w_down"].astype(ct)

        with jax.named_scope("route"):
            counts = jnp.bincount(eflat, length=E).astype(jnp.float32)
            aux = E * jnp.sum(probs.mean(0) * counts / T)
    out = out.reshape(B, S, d)
    if counters:
        return out, aux, group_sizes.sum()
    return out, aux
