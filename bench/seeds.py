"""Everything a run draws from `--seed`: independent keys for the weights,
the batches and the step, and the reference's own maker of the weights."""
from __future__ import annotations

import functools

import jax

from bench.reference.common import make_weights


def seed_keys(seed: int) -> dict:
    """Keys for weights, batches and the step, from any seed below 2**64."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
    return {n: jax.random.fold_in(base, i)
            for i, n in enumerate(("weights", "batches", "step"))}


def reference_weights(ref, sizes: dict, seed: int):
    """The reference's jitted maker of the seeded weights (a path dict on
    the default device), independent of the program's layout.  The key is
    an argument of the jitted maker, so that every seed shares its
    compiled program."""
    shapes, laws = ref.param_shapes(sizes), ref.init_laws(sizes)
    return functools.partial(
        jax.jit(lambda key: make_weights(shapes, laws, key)),
        seed_keys(seed)["weights"])
