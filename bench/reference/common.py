"""Pieces shared by the plain references: matmuls with an optional lower
precision, RMS norm, per-leaf initial weights and the next-token loss.

Nothing here imports the program under test.  Parameters are dicts keyed
by '/'-joined paths ("embed/tok", "mlstm_blocks/mlstm/w_q", ...), and the
flat layout of stage 2 concatenates them in `leaf_order`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def lower(x, low):
    """x rounded to the dtype `low` and carried in f32 (None: unchanged)."""
    if low is None:
        return x.astype(F32)
    return x.astype(low).astype(F32)


def mm(eq: str, *ops, low=None):
    """einsum in f32; with `low`, every operand is rounded to it first."""
    return jnp.einsum(eq, *(lower(o, low) for o in ops),
                      preferred_element_type=F32)


def rms_norm(x, scale, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def leaf_order(shapes: dict) -> list:
    """Paths in the order of a nested dict's sorted keys, level by level."""
    return sorted(shapes, key=lambda p: p.split("/"))


def make_weights(shapes: dict, laws: dict, key) -> dict:
    """Weights from `key`: leaf i of `leaf_order` draws from fold_in(key, i).

    laws[path] is ("normal", std), ("ones",) or ("zeros",)."""
    out = {}
    for i, path in enumerate(leaf_order(shapes)):
        law, shape = laws[path], shapes[path]
        if law[0] == "normal":
            out[path] = jax.random.normal(jax.random.fold_in(key, i), shape,
                                          F32) * law[1]
        elif law[0] == "ones":
            out[path] = jnp.ones(shape, F32)
        else:
            out[path] = jnp.zeros(shape, F32)
    return out


def fan_in_std(n: int) -> tuple:
    return ("normal", 1.0 / math.sqrt(max(1, n)))


def row_nll(x, targets, norm_scale, head, low=None):
    """Per-row mean next-token NLL of final hidden states x (B, S, d).

    Rows go one at a time (each under remat) so that only one row's
    (S, vocab) logits are ever live."""
    @jax.checkpoint
    def one(args):
        xr, tr = args
        h = rms_norm(xr, norm_scale)
        logp = jax.nn.log_softmax(mm("sd,dv->sv", h, head, low=low), -1)
        return -jnp.mean(jnp.take_along_axis(logp, tr[:, None], -1))
    return jax.lax.map(one, (x, targets))


def block(params: dict, prefix: str, index: tuple) -> dict:
    """The leaves under `prefix`, indexed by `index` (one layer's slice)."""
    n = len(prefix) + 1
    return {p[n:]: v[index] for p, v in params.items()
            if p.startswith(prefix + "/")}
