"""Reference (simulation) implementations of COCO-EF and all baselines.

These operate on explicit (N, D) device-major arrays and follow Algorithm 1
of the paper line by line.  They are used for the paper-reproduction
experiments (Figs. 2-7) and as the oracle for the distributed runtime in
`repro.core.cocoef` / `repro.launch.train` (which must produce bitwise the
same model update for the same mask/keys).

Methods (Sec. V):
  cocoef_step        COCO-EF   (proposed; biased C + error feedback)
  coco_step          COCO      (proposed w/o error feedback; e_i ≡ 0)
  unbiased_step      Unbiased  (1-bit gradient coding [32] / rand-K variant)
  unbiased_diff_step Unbiased-diff (gradient-difference compression [23])
  uncompressed_step  SGC [31]  (no compression; delta = 0 bound of Sec. IV)
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.ref import mul_add, scan_zeros
from .compression import Compressor

__all__ = [
    "EFState",
    "DiffState",
    "cocoef_step",
    "coco_step",
    "unbiased_step",
    "unbiased_diff_step",
    "uncompressed_step",
]

GradFn = Callable[[jnp.ndarray], jnp.ndarray]  # theta (D,) -> per-subset grads (M, D)


class EFState(NamedTuple):
    """COCO-EF device state: theta (D,), error vectors e (N, D)."""

    theta: jnp.ndarray
    e: jnp.ndarray

    @staticmethod
    def init(theta: jnp.ndarray, num_devices: int) -> "EFState":
        return EFState(theta=theta,
                       e=jnp.zeros((num_devices,) + theta.shape, theta.dtype))


class DiffState(NamedTuple):
    """Gradient-difference compression state [23]: per-device reference h_i
    (N, D) and the server-side aggregate H = sum_i h_i (D,)."""

    theta: jnp.ndarray
    h: jnp.ndarray
    H: jnp.ndarray

    @staticmethod
    def init(theta: jnp.ndarray, num_devices: int) -> "DiffState":
        return DiffState(theta=theta,
                         h=jnp.zeros((num_devices,) + theta.shape, theta.dtype),
                         H=jnp.zeros_like(theta))


def _coded_gradients(grad_fn: GradFn, theta: jnp.ndarray,
                     W: jnp.ndarray) -> jnp.ndarray:
    """g_i = sum_k W[i,k] grad f_k(theta)   (eq. 3).  Returns (N, D)."""
    per_subset = grad_fn(theta)  # (M, D)
    return W @ per_subset


def _masked_sum(mask: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Server aggregate  sum_i mask_i * c_i  over the device axis (eq. 9),
    accumulated IN DEVICE ORDER (i = 0..N-1, lax.scan) — the SAME
    accumulation order the production collective's streaming decode_reduce
    uses, so the reference loop and the mesh `cocoef_update` agree
    BIT-FOR-BIT (the parity gate, repro.launch.parity) instead of up to
    f32 reduction-order ulps of `(m * c).sum(0)`."""
    m = mask.reshape((-1,) + (1,) * (c.ndim - 1)).astype(c.dtype)

    def body(acc, inp):
        mi, ci = inp
        return acc + mi * ci, None
    return lax.scan(body, scan_zeros(c.shape[1:], m, c, dtype=c.dtype),
                    (m, c))[0]


def _per_device_keys(key: Optional[jax.Array], step, n: int):
    if key is None:
        return None
    k = jax.random.fold_in(key, jnp.asarray(step, dtype=jnp.uint32))
    return jax.random.split(k, n)


@partial(jax.jit, static_argnames=("grad_fn", "compressor"))
def cocoef_step(state: EFState, grad_fn: GradFn, W: jnp.ndarray,
                mask: jnp.ndarray, gamma: float, compressor: Compressor,
                step: jax.Array | int = 0,
                key: Optional[jax.Array] = None) -> EFState:
    """One iteration of Algorithm 1 (COCO-EF).

    mask: (N,) float 0/1 straggler indicators I_i^t.
    gamma may be a traced scalar (supports decaying-lr experiments, Fig. 6).
    """
    g = _coded_gradients(grad_fn, state.theta, W)          # (N, D)
    # eq. (4) argument; mul_add = the ONE accumulate definition shared with
    # the production kernels (two-rounding f32, no FMA contraction) so the
    # parity gate can demand bit-for-bit trajectories
    acc = mul_add(gamma, g, state.e)
    keys = _per_device_keys(key, step, g.shape[0])
    if keys is None:
        c = jax.vmap(lambda v: compressor.apply(v))(acc)
    else:
        c = jax.vmap(lambda v, k: compressor.apply(v, k))(acc, keys)
    m = mask.reshape((-1,) + (1,) * (acc.ndim - 1))
    ghat = _masked_sum(mask, c)                            # eq. (9)
    theta = state.theta - ghat                             # eq. (10)
    e = jnp.where(m > 0, acc - c, state.e)                 # eq. (7) / frozen
    return EFState(theta=theta, e=e)


@partial(jax.jit, static_argnames=("grad_fn", "compressor"))
def coco_step(state: EFState, grad_fn: GradFn, W: jnp.ndarray,
              mask: jnp.ndarray, gamma: float, compressor: Compressor,
              step: jax.Array | int = 0,
              key: Optional[jax.Array] = None) -> EFState:
    """COCO: the proposed method with the error feedback disabled (e ≡ 0)."""
    g = _coded_gradients(grad_fn, state.theta, W)
    acc = gamma * g
    keys = _per_device_keys(key, step, g.shape[0])
    if keys is None:
        c = jax.vmap(lambda v: compressor.apply(v))(acc)
    else:
        c = jax.vmap(lambda v, k: compressor.apply(v, k))(acc, keys)
    theta = state.theta - _masked_sum(mask, c)
    return EFState(theta=theta, e=state.e)


@partial(jax.jit, static_argnames=("grad_fn", "compressor"))
def unbiased_step(state: EFState, grad_fn: GradFn, W: jnp.ndarray,
                  mask: jnp.ndarray, gamma: float, compressor: Compressor,
                  step: jax.Array | int = 0,
                  key: Optional[jax.Array] = None) -> EFState:
    """Unbiased baseline [32]: devices send Q(g_i) with an *unbiased* Q;
    server updates theta <- theta - gamma * sum_i I_i Q(g_i)."""
    g = _coded_gradients(grad_fn, state.theta, W)
    keys = _per_device_keys(key, step, g.shape[0])
    if keys is None:
        q = jax.vmap(lambda v: compressor.apply(v))(g)
    else:
        q = jax.vmap(lambda v, k: compressor.apply(v, k))(g, keys)
    theta = state.theta - gamma * _masked_sum(mask, q)
    return EFState(theta=theta, e=state.e)


@partial(jax.jit, static_argnames=("grad_fn", "compressor", "alpha"))
def unbiased_diff_step(state: DiffState, grad_fn: GradFn, W: jnp.ndarray,
                       mask: jnp.ndarray, gamma: float, compressor: Compressor,
                       step: jax.Array | int = 0,
                       key: Optional[jax.Array] = None,
                       alpha: float = 0.1) -> DiffState:
    """Unbiased-diff baseline: gradient-difference compression [23] (DIANA-
    style) on top of the coded gradients, with partial participation.

    Non-straggler i sends q_i = Q(g_i - h_i) and sets h_i <- h_i + alpha*q_i
    (alpha <= 1/(omega+1) is the standard DIANA reference step size; with
    alpha = 1 the high-variance 1-bit quantizer makes the reference diverge).
    The server holds H = sum_i h_i and computes
        ghat = H + sum_{non-straggler} q_i ,  H <- H + alpha * sum q_i,
    which equals sum_i h_i^{new} exactly.
    """
    g = _coded_gradients(grad_fn, state.theta, W)
    diff = g - state.h
    keys = _per_device_keys(key, step, g.shape[0])
    if keys is None:
        q = jax.vmap(lambda v: compressor.apply(v))(diff)
    else:
        q = jax.vmap(lambda v, k: compressor.apply(v, k))(diff, keys)
    m = mask.reshape((-1,) + (1,) * (g.ndim - 1))
    q_sum = _masked_sum(mask, q)
    ghat = state.H + q_sum
    theta = state.theta - gamma * ghat
    h = jnp.where(m > 0, state.h + alpha * q, state.h)
    return DiffState(theta=theta, h=h, H=state.H + alpha * q_sum)


@partial(jax.jit, static_argnames=("grad_fn",))
def uncompressed_step(state: EFState, grad_fn: GradFn, W: jnp.ndarray,
                      mask: jnp.ndarray, gamma: float,
                      step: jax.Array | int = 0) -> EFState:
    """Stochastic gradient coding [31]: dense coded vectors, no compression."""
    g = _coded_gradients(grad_fn, state.theta, W)
    theta = state.theta - gamma * _masked_sum(mask, g)
    return EFState(theta=theta, e=state.e)
