"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434) as the
configurations under bench/configs/ with "reference": "deepseek" run it:
one chip's share of an expert-parallel deployment.

A dense first layer, then MoE layers; every layer a pre-norm residual
pair of multi-head latent attention and a feed-forward part.  Written
from the equations, not from the program:

  MLA (no query compression): q = x W_q split into q_nope (128) and q_rope
    (64) per head; [c ; k_rope] = x W_dkv, c RMS-normed (r = 512);
    k_nope = c W_uk, v = c W_uv per head; k_rope shared by the heads.
    q_rope and k_rope are rotated by position with YaRN's frequencies;
    scores (q_nope . k_nope + q_rope . k_rope) * 192^-0.5 * mscale^2,
    causal softmax, then W_o.
  YaRN (rope_scaling): base 10000, factor 40, original length 4096; a
    dimension pair i turns with theta_i = 10000^(-2i/64) below the pair
    that makes beta_fast = 32 turns over the original length, with
    theta_i / 40 above the one that makes beta_slow = 1 turn, and a
    linear blend between; mscale = 0.1 * 0.707 * ln 40 + 1 (mscale_all_dim
    equals mscale, so cos and sin keep scale 1).
  MoE: s = softmax(x W_r) over all E experts, I = top-k of s, gates s_e
    (not renormalized); y = sum over the held experts e in I of
    s_e SwiGLU_e(x), plus the shared experts' SwiGLU of width
    n_shared * moe_ff.  Each held expert runs on every token and is
    multiplied by its gate where routed (zero elsewhere): no sort, no
    grouped matmul.
  Balance loss: E * sum_e P_e f_e over all E experts, P_e the mean router
    probability and f_e the share of tokens that picked e, over the
    batch; `moe_aux_weight` times its sum over the MoE layers is added to
    the mean next-token loss.

Rotations pair dimension i with i + 32 (rotate-half); the published code
pairs 2i with 2i + 1, which is a fixed relabeling of W_q's and W_dkv's
rope columns (the configuration lists it under `departures`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, block, fan_in_std, mm, rms_norm, row_nll


def _sizes(m: dict):
    return (m["d_model"], m["num_heads"], m["kv_lora_rank"],
            m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"])


def param_shapes(m: dict) -> dict:
    d, H, r, dn, dr, dv = _sizes(m)
    L = m["num_layers"] - 1
    E, held, ff = m["moe_experts"], m["moe_experts_held"], m["moe_ff"]
    sff, V, dff = ff * m["moe_shared"], m["vocab_size"], m["dense_ff"]

    def attn(lead):
        return {"attn/kv_norm": lead + (r,), "attn/w_dkv": lead + (d, r + dr),
                "attn/w_uk": lead + (r, H, dn), "attn/w_uv": lead + (r, H, dv),
                "attn/wo": lead + (H, dv, d), "attn/wq": lead + (d, H, dn + dr),
                "norm1/scale": lead + (d,), "norm2/scale": lead + (d,)}
    s = {"embed/head": (d, V), "embed/tok": (V, d), "final_norm/scale": (d,)}
    s.update({"block0/" + k: v for k, v in attn(()).items()})
    s.update({"block0/mlp/w_gate": (d, dff), "block0/mlp/w_up": (d, dff),
              "block0/mlp/w_down": (dff, d)})
    s.update({"blocks/" + k: v for k, v in attn((L,)).items()})
    s.update({"blocks/moe/router": (L, d, E),
              "blocks/moe/w_gate": (L, held, d, ff),
              "blocks/moe/w_up": (L, held, d, ff),
              "blocks/moe/w_down": (L, held, ff, d),
              "blocks/moe/shared/w_gate": (L, d, sff),
              "blocks/moe/shared/w_up": (L, d, sff),
              "blocks/moe/shared/w_down": (L, sff, d)})
    return s


def init_laws(m: dict) -> dict:
    """Normal(0, 1/fan_in) matrices (fan_in: the input axis), unit token
    embeddings, norm scales 1."""
    laws = {}
    for path, shape in param_shapes(m).items():
        leaf = path.rsplit("/", 1)[1]
        if leaf == "tok":
            laws[path] = ("normal", 1.0)
        elif leaf in ("scale", "kv_norm"):
            laws[path] = ("ones",)
        elif leaf in ("w_uk", "w_uv"):
            laws[path] = fan_in_std(shape[-3])
        elif leaf == "wo":
            laws[path] = fan_in_std(shape[-3] * shape[-2])
        elif leaf == "wq":
            laws[path] = fan_in_std(shape[-3])
        else:
            laws[path] = fan_in_std(shape[-2])
    return laws


BETA_FAST, BETA_SLOW = 32, 1     # rope_scaling's beta_fast, beta_slow


def yarn_frequencies(m: dict) -> np.ndarray:
    """YaRN's inverse frequency of each rope dimension pair (float64)."""
    dim, base, f = m["qk_rope_dim"], m["rope_theta"], m["yarn_factor"]
    plain = base ** (-np.arange(0, dim, 2) / dim)

    def pair_turning(n):     # the pair that turns n times over the original
        return dim * math.log(m["yarn_original_max"] / (2 * math.pi * n)) \
            / (2 * math.log(base))
    lo = max(math.floor(pair_turning(BETA_FAST)), 0)
    hi = min(math.ceil(pair_turning(BETA_SLOW)), dim - 1)
    blend = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return plain * (1 - blend) + plain / f * blend


def _rope(x, freqs):
    """x (S, H, hd): rotate dimension i with i + hd/2 by position."""
    S, _, hd = x.shape
    half = hd // 2
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(freqs, F32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla(p, x, m, low=None):
    d, H, r, dn, dr, dv = _sizes(m)
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    mscale = 0.1 * m["yarn_mscale"] * math.log(m["yarn_factor"]) + 1
    scale = (dn + dr) ** -0.5 * mscale ** 2
    freqs = yarn_frequencies(m)

    @jax.checkpoint
    def row(xr):
        q = mm("sd,dhk->shk", xr, p["wq"], low=low)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], freqs)
        ckv = mm("sd,dr->sr", xr, p["w_dkv"], low=low)
        c = rms_norm(ckv[:, :r], p["kv_norm"])
        k_rope = _rope(ckv[:, None, r:], freqs)[:, 0]
        k_nope = mm("sr,rhk->shk", c, p["w_uk"], low=low)
        v = mm("sr,rhk->shk", c, p["w_uv"], low=low)
        s = (mm("shk,thk->hst", q_nope, k_nope, low=low)
             + mm("shk,tk->hst", q_rope, k_rope, low=low)) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        o = mm("hst,thk->shk", jax.nn.softmax(s, -1), v, low=low)
        return mm("shk,hkd->sd", o, p["wo"], low=low)
    return jax.lax.map(row, x)


def swiglu(p, x, low=None):
    h = (jax.nn.silu(mm("sd,df->sf", x, p["w_gate"], low=low))
         * mm("sd,df->sf", x, p["w_up"], low=low))
    return mm("sf,fd->sd", h, p["w_down"], low=low)


def moe(p, x, m, low=None):
    """One MoE layer on x (B, S, d): the held experts' and the shared
    experts' output, and the layer's balance loss."""
    E, k, held = m["moe_experts"], m["moe_top_k"], m["moe_experts_held"]
    probs = jax.nn.softmax(mm("bsd,de->bse", x, p["router"], low=low), -1)
    top, idx = jax.lax.top_k(probs, k)                     # (B, S, k)
    if m["moe_norm_topk"]:
        top = top / top.sum(-1, keepdims=True)
    picked = jax.nn.one_hot(idx, E, dtype=F32)             # (B, S, k, E)
    gates = jnp.einsum("bsk,bske->bse", top, picked)[..., :held]
    shared = {n: p["shared/" + n] for n in ("w_gate", "w_up", "w_down")}

    @jax.checkpoint
    def row(args):
        xr, gr = args
        h = (jax.nn.silu(mm("sd,edf->esf", xr, p["w_gate"], low=low))
             * mm("sd,edf->esf", xr, p["w_up"], low=low))
        y = mm("esf,efd->esd", h, p["w_down"], low=low)
        return jnp.einsum("esd,se->sd", y, gr) + swiglu(shared, xr, low)
    T = x.shape[0] * x.shape[1]
    aux = E * jnp.sum(probs.reshape(T, E).mean(0)
                      * picked.sum((0, 1, 2)) / T)
    return jax.lax.map(row, (x, gates)), aux


def row_losses(params: dict, tokens, m: dict, low=None):
    """Mean next-token NLL of each row of tokens (B, S + 1), plus the
    weighted balance loss of the batch (the same for every row)."""
    x = params["embed/tok"][tokens[:, :-1]].astype(F32)

    def sub(blk, prefix):
        n = len(prefix)
        return {k[n:]: v for k, v in blk.items() if k.startswith(prefix)}

    @jax.checkpoint
    def dense_block(blk, x):
        x = x + mla(sub(blk, "attn/"), rms_norm(x, blk["norm1/scale"]), m,
                    low)
        h = rms_norm(x, blk["norm2/scale"])
        return x + jax.lax.map(lambda xr: swiglu(sub(blk, "mlp/"), xr, low),
                               h)

    @jax.checkpoint
    def moe_block(blk, x):
        x = x + mla(sub(blk, "attn/"), rms_norm(x, blk["norm1/scale"]), m,
                    low)
        y, aux = moe(sub(blk, "moe/"), rms_norm(x, blk["norm2/scale"]), m,
                     low)
        return x + y, aux

    x = dense_block(block(params, "block0", ()), x)
    aux = 0.0
    for layer in range(m["num_layers"] - 1):
        x, a = moe_block(block(params, "blocks", (layer,)), x)
        aux = aux + a
    nll = row_nll(x, tokens[:, 1:], params["final_norm/scale"],
                  params["embed/head"], low)
    return nll + m["moe_aux_weight"] * aux


def flops_per_token(m: dict, seq_len: int) -> float:
    """Model FLOPs per trained token on this chip, forward and backward
    (3 x forward), every matmul at 2 FLOPs per multiply-add: MLA's
    projections and the causal half of its scores and weighted values;
    the dense SwiGLU; per MoE layer the router, the shared experts, and
    the routed experts at k experts a token, of which held / E are here;
    the head.  Norms, rotations, softmax and gates are not counted."""
    d, H, r, dn, dr, dv = _sizes(m)
    E, k, held = m["moe_experts"], m["moe_top_k"], m["moe_experts_held"]
    ff, sff = m["moe_ff"], m["moe_ff"] * m["moe_shared"]
    mla_f = (2 * d * H * (dn + dr) + 2 * d * (r + dr) + 2 * r * H * (dn + dv)
             + 2 * H * dv * d + seq_len * H * (dn + dr + dv))
    dense = 3 * 2 * d * m["dense_ff"]
    moe_f = 2 * d * E + 3 * 2 * d * (sff + k * held / E * ff)
    L = m["num_layers"] - 1
    return 3.0 * ((L + 1) * mla_f + dense + L * moe_f
                  + 2 * d * m["vocab_size"])
