"""Plain reference of the training step's stage 2 and of the first steps.

Algorithm 1 of COCO-EF for one coding rank (d = 1, no stragglers), on the
flat f32 vector that concatenates every parameter leaf in `leaf_order`,
zero-padded to whole compression groups:

  acc = lr * g + e;   c = C(acc);   e' = acc - c;   theta' = theta - c

C is the wire's compressor: `sign` keeps sign(acc) (0 counts as +) times
the mean |acc| of each group; `block_topk` keeps the k largest |acc| of
each block (the first of equal values wins) and zeroes the rest.

`observe` runs the first steps of training from the seeded weights on the
given token batches and returns what the benchmark compares: each step's
loss, per-leaf norms of the first update as the optimizer applied it
(theta_0 - theta_1) and of the change after the last step (theta_n -
theta_0), and per-leaf norms of the first raw gradient.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, leaf_order


def compress(acc, wire: dict):
    kind = wire["compressor"]
    if kind == "sign":
        g = acc.reshape(-1, wire["group_size"])
        scale = jnp.mean(jnp.abs(g), axis=-1, keepdims=True)
        return jnp.where(g >= 0, scale, -scale).reshape(-1)
    if kind == "block_topk":
        k, blk = wire["k_per_block"], wire["block_size"]
        x = acc.reshape(-1, blk)
        mag = jnp.abs(x)
        thr = jnp.sort(mag, axis=-1)[:, blk - k][:, None]
        above = mag > thr
        room = k - above.sum(-1, keepdims=True)
        tie = (mag == thr) & (jnp.cumsum(mag == thr, axis=-1) <= room)
        return jnp.where(above | tie, x, 0.0).reshape(-1)
    raise ValueError(f"no reference for compressor {kind!r}")


CHUNK = 1 << 20      # coordinates compressed at a time (whole groups and
                     # blocks; the zero padding compresses to zero)


def _leaf_norms(order, a: dict, b: dict):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a[p] - b[p])))
                      for p in order])


def observe(ref, m: dict, traffic: dict, weights, batches, *, low=None,
            rows=None) -> dict:
    """Run len(batches) reference steps.

    ref: the architecture's reference module; weights: () -> params dict
    (the seeded weights, made anew on each call); batches: token arrays
    (B, S + 1); low: dtype the matmul operands are rounded to (None: f32);
    rows: only these rows of each batch (the loss is their mean)."""
    shapes = ref.param_shapes(m)
    order = leaf_order(shapes)
    sizes = [math.prod(shapes[p]) for p in order]
    n = sum(sizes)
    n_pad = -(-n // CHUNK) * CHUNK
    lr = traffic["lr"]
    wire = traffic["wire"]

    def flat(tree):
        return jnp.pad(jnp.concatenate([tree[p].reshape(-1) for p in order]),
                       (0, n_pad - n))

    # three programs, so that the parameters, the gradient and the flat
    # vectors of stage 2 are never all live at once
    @jax.jit
    def gradient(params, toks):
        loss, grads = jax.value_and_grad(
            lambda p: jnp.mean(ref.row_losses(p, toks, m, low)))(params)
        g_norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(grads[p])))
                             for p in order])
        return loss, flat(grads), g_norms

    @partial(jax.jit, donate_argnums=(0, 1))
    def error_feedback(g, e):
        def one(a):
            c = compress(a, wire)
            return c, a - c
        c, e_new = jax.lax.map(one, (lr * g + e).reshape(-1, CHUNK))
        return c.reshape(-1), e_new.reshape(-1)

    @partial(jax.jit, donate_argnums=(0,))
    def apply(params, c):
        p_new, off = {}, 0
        for p, s in zip(order, sizes):
            p_new[p] = params[p] - c[off:off + s].reshape(shapes[p])
            off += s
        return p_new, _leaf_norms(order, params, p_new)

    with jax.default_matmul_precision("highest"):
        params = weights()
        e = jnp.zeros((n_pad,), F32)
        losses, seconds = [], []
        for t, toks in enumerate(batches):
            t0 = time.perf_counter()
            toks = jnp.asarray(np.asarray(toks) if rows is None
                               else np.asarray(toks)[rows])
            loss, g, g_norms = gradient(params, toks)
            losses.append(float(loss))
            seconds.append(time.perf_counter() - t0)
            c, e = error_feedback(g, e)
            del g
            params, upd = apply(params, c)
            del c
            jax.block_until_ready(params)
            seconds.append(time.perf_counter() - t0)
            if t == 0:
                first_update = np.asarray(upd, np.float64)
                first_grad = np.asarray(g_norms, np.float64)
        del e
        change = np.asarray(jax.jit(lambda a, b: _leaf_norms(order, a, b))(
            params, weights()), np.float64)
    return {"leaves": order, "losses": losses, "first_update": first_update,
            "change": change, "first_grad": first_grad, "seconds": seconds}
