"""The xLSTM reference computed in bfloat16 wherever the xlstm
configuration states bfloat16: a witness of how far the configuration's
own rounding moves the numbers `correct` compares.  Not a reference.

bench/reference/xlstm.py with every value the program holds in bfloat16
rounded to it (its cotangent too, through the rounding's transpose): the
weights as the step reads them (the token embedding table too, so that
its gradient accumulates in bfloat16 as the program's does), the residual
stream, each norm's output, every matmul's operands and outputs (x @
w_xin, the gate and q, k, v projections, the down projections, the
logits), the gate pre-activations after their bias, the mLSTM head
outputs before and after their norm, the SiLU gate and its product, the
sLSTM input projection and hidden states.  What the program keeps in
float32 stays float32: the stabilised gating, the sLSTM cell, the
log-softmax.  Matmuls inside the mLSTM and the sLSTM recurrence take
bfloat16 operands, as float32 matmuls at the TPU's default precision do.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import F32, block, mm, rms_norm
from bench.reference.xlstm import _sizes, flops_per_token, init_laws, \
    param_shapes  # noqa: F401  (the witness stands where the reference does)

BF16 = jnp.bfloat16


def r(x):
    """x rounded to bfloat16, carried in float32."""
    return x.astype(BF16).astype(F32)


def _mm(eq, *ops):
    return r(mm(eq, *ops, low=BF16))


def mlstm(p, x, m):
    d, H, di, hd, *_ = _sizes(m)
    B, S, _ = x.shape
    xin = _mm("bsd,de->bse", x, p["w_xin"])
    z = _mm("bsd,de->bse", x, p["w_zgate"])
    xh = xin.reshape(B, S, H, hd)
    q = _mm("bshd,hde->bshe", xh, p["w_q"])
    k = _mm("bshd,hde->bshe", xh, p["w_k"]) * hd ** -0.5
    v = _mm("bshd,hde->bshe", xh, p["w_v"])
    gates = r(_mm("bse,eg->bsg", xin, p["w_if"]) + r(p["b_if"]))
    ig, log_f = gates[..., :H], jax.nn.log_sigmoid(gates[..., H:])
    F = jnp.cumsum(log_f, axis=1)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    log_d = jnp.where(causal, F[:, :, None] - F[:, None] + ig[:, None],
                      -jnp.inf)
    m_t = jnp.max(log_d, axis=2)
    s = mm("bthd,bjhd->btjh", q, k, low=BF16) * jnp.exp(
        log_d - m_t[:, :, None])
    num = mm("btjh,bjhd->bthd", s, v, low=BF16)
    den = jnp.maximum(jnp.abs(s.sum(2)), jnp.exp(-m_t))
    y = r((num / den[..., None]).reshape(B, S, di))
    y = r(rms_norm(y, r(p["norm_scale"])))
    y = r(y * r(jax.nn.silu(z)))
    return _mm("bse,ed->bsd", y, p["w_down"])


def slstm(p, x, m):
    B, S, d = x.shape
    pre_x = _mm("bsd,de->bse", x, p["w_x"])
    w_h, b = r(p["w_h"]), r(p["b"])

    def step(carry, px):
        c, n, h, mx = carry
        pre = px + mm("bd,de->be", h, w_h, low=BF16) + b
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
    return _mm("bsd,de->bse", r(jnp.moveaxis(hs, 0, 1)), p["w_down"])


def _norm(x, scale):
    return r(rms_norm(x, r(scale)))


def row_losses(params: dict, tokens, m: dict, low=None):
    """Mean next-token NLL of each row; `low` is ignored (the rounding is
    the configuration's)."""
    *_, per, G, _ = _sizes(m)
    # gathered from a bfloat16 table: the gradient accumulates in bfloat16
    x = params["embed/tok"].astype(BF16)[tokens[:, :-1]].astype(F32)

    @jax.checkpoint
    def m_block(p, x):
        return r(x + mlstm(p, _norm(x, p["norm1/scale"]), m))

    @jax.checkpoint
    def s_block(p, x):
        return r(x + slstm(p, _norm(x, p["norm1/scale"]), m))

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
    head, scale = params["embed/head"], params["final_norm/scale"]

    @jax.checkpoint
    def one(args):
        xr, tr = args
        logits = _mm("sd,dv->sv", _norm(xr, scale), head)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, tr[:, None], -1))
    return jax.lax.map(one, (x, tokens[:, 1:]))
