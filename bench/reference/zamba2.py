"""Plain float32 reference of the Zamba2 hybrid stack (arXiv:2411.15242) as
the configurations under bench/configs/ with "reference": "zamba2" run it.

Groups of `hybrid_attn_period` Mamba2 blocks, each followed by the one
shared attention block (attention, then a SwiGLU MLP, both pre-norm
residual).  Written from the equations, not from the program:

  Mamba2 (SSD, scalar A per head): h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,
    y_t = C_t . h_t + D x_t, computed in its quadratic (attention-like)
    form y_t = sum_{j<=t} (C_t . B_j) exp(sum_{j<l<=t} dt_l A) dt_j x_j;
    causal depthwise convolutions before it, a SiLU-gated RMS norm after.
  Attention: causal softmax attention with rotary position embeddings.

Departures from the published model, as the configuration file lists them:
one shared block (the paper alternates two), attention at width d_model
on the residual stream alone (the paper concatenates the input
embedding), and no LoRA adapters on the shared MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, block, fan_in_std, mm, rms_norm, row_nll

HEAD_BLOCK = 16          # SSD heads handled together (memory of the S x S maps)


def _sizes(m: dict):
    d, di, N = m["d_model"], m["d_inner"], m["ssm_state"]
    H = m["ssm_heads"] or di // 64
    return d, di, N, H, di // H, m["conv_width"]


def param_shapes(m: dict) -> dict:
    d, di, N, H, _, K = _sizes(m)
    per = m["hybrid_attn_period"]
    G = m["num_layers"] // per
    nh, hd, ff, V = m["num_heads"], m["head_dim"], m["d_ff"], m["vocab_size"]
    lay = (G, per)
    s = {"embed/head": (d, V), "embed/tok": (V, d), "final_norm/scale": (d,),
         "blocks/norm1/scale": lay + (d,)}
    for name, shape in (("A_log", (H,)), ("D", (H,)), ("conv_b_bc", (2 * N,)),
                        ("conv_b_x", (di,)), ("conv_bc", (K, 2 * N)),
                        ("conv_x", (K, di)), ("dt_bias", (H,)),
                        ("norm_scale", (di,)), ("w_B", (d, N)),
                        ("w_C", (d, N)), ("w_dt", (d, H)), ("w_out", (di, d)),
                        ("w_x", (d, di)), ("w_z", (d, di))):
        s["blocks/mamba/" + name] = lay + shape
    s.update({"shared_attn/attn/wq": (d, nh, hd),
              "shared_attn/attn/wk": (d, nh, hd),
              "shared_attn/attn/wv": (d, nh, hd),
              "shared_attn/attn/wo": (nh, hd, d),
              "shared_attn/mlp/w_gate": (d, ff),
              "shared_attn/mlp/w_up": (d, ff),
              "shared_attn/mlp/w_down": (ff, d),
              "shared_attn/norm1/scale": (d,),
              "shared_attn/norm2/scale": (d,)})
    return s


def init_laws(m: dict) -> dict:
    """Normal(0, 1/fan_in) matrices and convolution taps, unit token
    embeddings; A_log = 0 (A = -1), dt_bias 0, D 1, norm scales 1,
    convolution biases 0."""
    laws = {}
    for path, shape in param_shapes(m).items():
        leaf = path.rsplit("/", 1)[1]
        if leaf == "tok":
            laws[path] = ("normal", 1.0)
        elif leaf in ("scale", "norm_scale", "D"):
            laws[path] = ("ones",)
        elif leaf in ("A_log", "dt_bias", "conv_b_x", "conv_b_bc"):
            laws[path] = ("zeros",)
        elif leaf in ("wq", "wk", "wv"):
            laws[path] = fan_in_std(shape[-3])
        elif leaf == "wo":
            laws[path] = fan_in_std(shape[-3] * shape[-2])
        else:
            laws[path] = fan_in_std(shape[-2])
    return laws


def _causal_conv(u, w, b):
    """Depthwise causal convolution: out_t = sum_i u_{t-K+1+i} w_i + b."""
    K, S = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(up[:, i:i + S] * w[i] for i in range(K)) + b


def _ssd_row(xh, dt, la, Bv, Cv, low):
    """One row's SSD output (S, H, hd) in the quadratic form."""
    S, H, hd = xh.shape
    seg = jnp.cumsum(la, axis=0)                                    # (S,H)
    cb = mm("ik,jk->ij", Cv, Bv, low=low)
    causal = jnp.tril(jnp.ones((S, S), bool))[:, :, None]
    nb = H // HEAD_BLOCK

    @jax.checkpoint
    def heads(args):
        x_b, dt_b, seg_b = args                      # (S,h,hd), (S,h), (S,h)
        decay = jnp.exp(jnp.where(causal, seg_b[:, None] - seg_b[None],
                                  -jnp.inf))         # (i,j,h)
        w = cb[:, :, None] * decay * dt_b[None]
        return mm("ijh,jhd->ihd", w, x_b, low=low)

    def split(t):
        return jnp.moveaxis(t.reshape((S, nb, HEAD_BLOCK) + t.shape[2:]), 1, 0)

    ys = jax.lax.map(heads, (split(xh), split(dt), split(seg)))
    return jnp.moveaxis(ys, 0, 1).reshape(S, H, hd)


def mamba2(p, x, m, low=None):
    d, di, N, H, hd, K = _sizes(m)
    B, S, _ = x.shape
    z = mm("bsd,de->bse", x, p["w_z"], low=low)
    xs = mm("bsd,de->bse", x, p["w_x"], low=low)
    bc = jnp.concatenate([mm("bsd,dn->bsn", x, p["w_B"], low=low),
                          mm("bsd,dn->bsn", x, p["w_C"], low=low)], -1)
    dt = jax.nn.softplus(mm("bsd,dh->bsh", x, p["w_dt"], low=low)
                         + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xs = jax.nn.silu(_causal_conv(xs, p["conv_x"], p["conv_b_x"]))
    bc = jax.nn.silu(_causal_conv(bc, p["conv_bc"], p["conv_b_bc"]))
    xh = xs.reshape(B, S, H, hd)
    y = jax.lax.map(lambda a: _ssd_row(*a, low),
                    (xh, dt, dt * A, bc[..., :N], bc[..., N:]))
    y = (y + xh * p["D"][:, None]).reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm_scale"])
    return mm("bse,ed->bsd", y, p["w_out"], low=low)


def _rope(x, theta):
    """x (S, H, hd): rotate the two halves of each head by position."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, x, m, low=None):
    hd, theta = m["head_dim"], m["rope_theta"]
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def row(xr):
        q = _rope(mm("sd,dhk->shk", xr, p["wq"], low=low), theta) * hd ** -0.5
        k = _rope(mm("sd,dhk->shk", xr, p["wk"], low=low), theta)
        v = mm("sd,dhk->shk", xr, p["wv"], low=low)
        s = jnp.where(causal[None], mm("shk,thk->hst", q, k, low=low),
                      -jnp.inf)
        o = mm("hst,thk->shk", jax.nn.softmax(s, -1), v, low=low)
        return mm("shk,hkd->sd", o, p["wo"], low=low)
    return jax.lax.map(row, x)


def swiglu(p, x, low=None):
    h = (jax.nn.silu(mm("bsd,df->bsf", x, p["w_gate"], low=low))
         * mm("bsd,df->bsf", x, p["w_up"], low=low))
    return mm("bsf,fd->bsd", h, p["w_down"], low=low)


def row_losses(params: dict, tokens, m: dict, low=None):
    """Mean next-token NLL of each row of tokens (B, S + 1)."""
    per = m["hybrid_attn_period"]
    G = m["num_layers"] // per
    x = params["embed/tok"][tokens[:, :-1]].astype(F32)
    sa = block(params, "shared_attn", ())

    @jax.checkpoint
    def m_block(p, x):
        return x + mamba2(p, rms_norm(x, p["norm1/scale"]), m, low)

    @jax.checkpoint
    def shared(x):
        x = x + attention({k[5:]: v for k, v in sa.items()
                           if k.startswith("attn/")},
                          rms_norm(x, sa["norm1/scale"]), m, low)
        return x + swiglu({k[4:]: v for k, v in sa.items()
                           if k.startswith("mlp/")},
                          rms_norm(x, sa["norm2/scale"]), low)

    for g in range(G):
        for j in range(per):
            blk = block(params, "blocks", (g, j))
            x = m_block({"norm1/scale": blk["norm1/scale"],
                         **{k[6:]: v for k, v in blk.items()
                            if k.startswith("mamba/")}}, x)
        x = shared(x)
    return row_nll(x, tokens[:, 1:], params["final_norm/scale"],
                   params["embed/head"], low)


def flops_per_token(m: dict, seq_len: int, chunk: int = 64) -> float:
    """Model FLOPs per trained token, forward and backward (3 x forward).

    Every matmul at 2 FLOPs per multiply-add; the SSD in its chunked form
    (within a chunk of `chunk` tokens the causal half of C.B and of the
    weighted sum of x, across chunks the state update and read-out); the
    attention's causal half of q.k and of the weighted sum of v.
    Convolutions, norms and gates are not counted."""
    d, di, N, H, hd, K = _sizes(m)
    c = min(chunk, seq_len)
    nh, ahd, ff, V = m["num_heads"], m["head_dim"], m["d_ff"], m["vocab_size"]
    per = m["hybrid_attn_period"]
    G = m["num_layers"] // per
    mamba = (2 * d * (2 * di + 2 * N + H) + 2 * di * d
             + N * c + di * c + 4 * N * di)
    shared = (4 * 2 * d * nh * ahd            # q, k, v, o
              + 2 * seq_len * nh * ahd        # causal scores and values
              + 3 * 2 * d * ff)               # SwiGLU
    return 3.0 * (G * (per * mamba + shared) + 2 * d * V)
