"""Public model facade: init / loss / prefill / decode per ModelConfig."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import transformer as T
from .config import ModelConfig

__all__ = ["Model"]


class Model:
    """Thin functional wrapper (no state) around the family dispatch."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---- params ----------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        return T.init_params(key, self.cfg)

    def param_shapes(self, key=None):
        k = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda: T.init_params(k, self.cfg))

    def num_params(self) -> int:
        import math
        shapes = self.param_shapes()
        return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))

    # ---- training --------------------------------------------------------
    def loss(self, params, batch, counters: bool = False) -> Tuple:
        return T.weighted_loss(params, batch, self.cfg, counters)

    def grad_fn(self):
        def f(params, batch):
            (loss, per_ex), g = jax.value_and_grad(
                lambda p: self.loss(p, batch), has_aux=True)(params)
            return g, loss, per_ex
        return f

    # ---- serving ---------------------------------------------------------
    def init_caches(self, batch: int, cache_len: int, dtype=jnp.bfloat16):
        return T.init_caches(self.cfg, batch, cache_len, dtype)

    def decode_step(self, params, caches, inputs, pos):
        return T.decode_step(params, caches, inputs, pos, self.cfg)

    def prefill(self, params, inputs, cache_dtype=jnp.bfloat16):
        return T.prefill(params, inputs, self.cfg, cache_dtype)

    def forward(self, params, inputs):
        return T.forward(params, inputs, self.cfg)
