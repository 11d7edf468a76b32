"""Plain float32 reference of the xLSTM stack (arXiv:2405.04517) as the
configurations under bench/configs/ with "reference": "xlstm" run it.

Periods of `slstm_every` blocks: `slstm_every - 1` mLSTM blocks, then one
sLSTM block, each a pre-norm residual block.  Written from the equations,
not from the program:

  mLSTM (parallel form, per head):  with F_t = sum_{l<=t} log f_l,
    D_tj = exp(F_t - F_j + i_j) for j <= t,
    h_t = sum_j D_tj (q_t . k_j) v_j / max(|sum_j D_tj (q_t . k_j)|, 1),
  computed stabilised by m_t = max_j log D_tj (numerator and denominator
  scaled by exp(-m_t), the floor by exp(-m_t) too).
  sLSTM: the exponentially gated scalar recurrence, stabilised by m.

Where the configuration departs from the paper (read from the program's
stated design and recorded in the configuration file): the mLSTM head
output is RMS-normed over all heads together, q/k/v are per-head
block-diagonal maps of the up-projected input, the sLSTM block has a full
(d, 4d) recurrent matrix and no feed-forward part, and the sLSTM state
starts at c = 0, n = 1, h = 0, m = 0 with h = o * c / max(n, 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, block, fan_in_std, mm, rms_norm, row_nll


def _sizes(m: dict):
    d, H = m["d_model"], m["num_heads"]
    di = int(d * m["proj_factor"])
    per = m["slstm_every"]
    return d, H, di, di // H, per, m["num_layers"] // per, m["vocab_size"]


def param_shapes(m: dict) -> dict:
    d, H, di, hd, per, G, V = _sizes(m)
    ml = (G, per - 1)
    s = {"embed/head": (d, V), "embed/tok": (V, d), "final_norm/scale": (d,),
         "mlstm_blocks/norm1/scale": ml + (d,),
         "slstm_blocks/norm1/scale": (G, d)}
    for name, shape in (("b_if", (2 * H,)), ("norm_scale", (di,)),
                        ("w_down", (di, d)), ("w_if", (di, 2 * H)),
                        ("w_k", (H, hd, hd)), ("w_q", (H, hd, hd)),
                        ("w_v", (H, hd, hd)), ("w_xin", (d, di)),
                        ("w_zgate", (d, di))):
        s["mlstm_blocks/mlstm/" + name] = ml + shape
    for name, shape in (("b", (4 * d,)), ("w_down", (d, d)),
                        ("w_h", (d, 4 * d)), ("w_x", (d, 4 * d))):
        s["slstm_blocks/slstm/" + name] = (G,) + shape
    return s


def init_laws(m: dict) -> dict:
    """Normal(0, 1/fan_in) matrices (fan_in = the second-last axis),
    unit token embeddings, norm scales 1, biases 0."""
    laws = {}
    for path, shape in param_shapes(m).items():
        leaf = path.rsplit("/", 1)[1]
        if leaf == "tok":
            laws[path] = ("normal", 1.0)
        elif leaf in ("scale", "norm_scale"):
            laws[path] = ("ones",)
        elif leaf in ("b", "b_if"):
            laws[path] = ("zeros",)
        else:
            laws[path] = fan_in_std(shape[-2])
    return laws


def mlstm(p, x, m, low=None):
    d, H, di, hd, *_ = _sizes(m)
    B, S, _ = x.shape
    xin = mm("bsd,de->bse", x, p["w_xin"], low=low)
    z = mm("bsd,de->bse", x, p["w_zgate"], low=low)
    xh = xin.reshape(B, S, H, hd)
    q = mm("bshd,hde->bshe", xh, p["w_q"], low=low)
    k = mm("bshd,hde->bshe", xh, p["w_k"], low=low) * hd ** -0.5
    v = mm("bshd,hde->bshe", xh, p["w_v"], low=low)
    gates = mm("bse,eg->bsg", xin, p["w_if"], low=low) + p["b_if"]
    ig, log_f = gates[..., :H], jax.nn.log_sigmoid(gates[..., H:])
    F = jnp.cumsum(log_f, axis=1)                                  # (B,S,H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    log_d = jnp.where(causal, F[:, :, None] - F[:, None] + ig[:, None],
                      -jnp.inf)                                    # (B,t,j,H)
    m_t = jnp.max(log_d, axis=2)                                   # (B,t,H)
    s = mm("bthd,bjhd->btjh", q, k, low=low) * jnp.exp(log_d - m_t[:, :, None])
    num = mm("btjh,bjhd->bthd", s, v, low=low)
    den = jnp.maximum(jnp.abs(s.sum(2)), jnp.exp(-m_t))
    y = (num / den[..., None]).reshape(B, S, di)
    y = rms_norm(y, p["norm_scale"]) * jax.nn.silu(z)
    return mm("bse,ed->bsd", y, p["w_down"], low=low)


def slstm(p, x, m, low=None):
    B, S, d = x.shape
    pre_x = mm("bsd,de->bse", x, p["w_x"], low=low)

    def step(carry, px):
        c, n, h, mx = carry
        pre = px + mm("bd,de->be", h, p["w_h"], low=low) + p["b"]
        i, f, zg, o = jnp.split(pre, 4, axis=-1)
        log_f = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(log_f + mx, i)
        i_s, f_s = jnp.exp(i - m_new), jnp.exp(log_f + mx - m_new)
        c = f_s * c + i_s * jnp.tanh(zg)
        n = f_s * n + i_s
        h = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1.0)
        return (c, n, h, m_new), h

    zero = jnp.zeros((B, d), F32)
    _, hs = jax.lax.scan(step, (zero, jnp.ones((B, d), F32), zero, zero),
                         jnp.moveaxis(pre_x, 1, 0))
    return mm("bsd,de->bse", jnp.moveaxis(hs, 0, 1), p["w_down"], low=low)


def row_losses(params: dict, tokens, m: dict, low=None):
    """Mean next-token NLL of each row of tokens (B, S + 1)."""
    *_, per, G, _ = _sizes(m)
    x = params["embed/tok"][tokens[:, :-1]].astype(F32)

    @jax.checkpoint
    def m_block(p, x):
        return x + mlstm(p, rms_norm(x, p["norm1/scale"]), m, low)

    @jax.checkpoint
    def s_block(p, x):
        return x + slstm(p, rms_norm(x, p["norm1/scale"]), m, low)

    for g in range(G):
        for j in range(per - 1):
            blk = block(params, "mlstm_blocks", (g, j))
            x = m_block({"norm1/scale": blk["norm1/scale"],
                         **{k[6:]: v for k, v in blk.items()
                            if k.startswith("mlstm/")}}, x)
        blk = block(params, "slstm_blocks", (g,))
        x = s_block({"norm1/scale": blk["norm1/scale"],
                     **{k[6:]: v for k, v in blk.items()
                        if k.startswith("slstm/")}}, x)
    return row_nll(x, tokens[:, 1:], params["final_norm/scale"],
                   params["embed/head"], low)


def flops_per_token(m: dict, seq_len: int, chunk: int = 256) -> float:
    """Model FLOPs per trained token, forward and backward (3 x forward).

    Every matmul of the forward pass at 2 FLOPs per multiply-add, plus the
    mLSTM's sequence mixing in its chunkwise form: within a chunk of
    `chunk` tokens the causal half of q.k and of the weighted sum of v,
    across chunks C q and the k v^T update.  Embedding lookups, norms and
    gates are not counted; remat's recomputation is not work."""
    d, H, di, hd, per, G, V = _sizes(m)
    c = min(chunk, seq_len)
    m_layer = (2 * d * di * 2          # w_xin, w_zgate
               + 3 * 2 * di * hd       # per-head q, k, v
               + 2 * di * 2 * H        # gates
               + 2 * di * d            # w_down
               + H * (2 * hd * c + 4 * hd * hd))   # sequence mixing
    s_layer = 2 * d * 4 * d * 2 + 2 * d * d        # w_x, w_h, w_down
    fwd = G * ((per - 1) * m_layer + s_layer) + 2 * d * V
    return 3.0 * fwd
