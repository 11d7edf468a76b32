"""Jit'd public wrappers: pick the Pallas kernel or the jnp reference.

On TPU the Pallas path lowers to Mosaic; on CPU (this container) it runs in
interpret mode.  `use_pallas=False` (the default inside the dry-run
lowering) uses the jnp path — identical math, so roofline terms are
unaffected.  The jnp hot paths for the sparse wire live in topk_fast.py
(barrier-fixed `lax.top_k`); kernels/ref.py stays the barrier-free oracle
that everything is tested against."""
from __future__ import annotations

import warnings

import jax
from jax.experimental.pallas import tpu as pltpu

from . import (ref, sign_pack as sp, topk_block as tb, topk_fast as tf,
               topk_pack as tp)


def default_use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _interpret():
    """Mosaic compiles the kernels on a TPU.  Elsewhere they run in
    Pallas's TPU interpreter, which (unlike the HLO interpreter) traces
    inside a shard_map with check_vma on."""
    return False if default_use_pallas() else pltpu.InterpretParams()


BACKENDS = ("auto", "pallas", "jnp")


def backend_use_pallas(backend: str):
    """Map the train-path `backend` knob onto the `use_pallas` tristate.

    auto   -> None (Pallas on TPU, jnp reference elsewhere)
    pallas -> True (interpret mode off-TPU — the parity-suite setting)
    jnp    -> False
    """
    if backend == "auto":
        return None
    if backend == "pallas":
        return True
    if backend == "jnp":
        return False
    raise ValueError(f"unknown backend {backend!r}; have {BACKENDS}")


_fallback_warned = set()


def resolve_use_pallas(use_pallas, n: int, tile_elems: int, op: str = "",
                       dtype=None) -> bool:
    """Concrete kernel choice for a flat length `n`: the tristate
    `use_pallas` (None = Pallas iff on TPU) guarded by the kernel's row
    tile — shapes not divisible by `tile_elems` (G_BLK/R_BLK rows worth of
    elements) fall back to the jnp path, which has no tile.

    When Pallas was EXPLICITLY requested (`use_pallas=True`, i.e.
    backend="pallas") and the tile guard rejects the shape, warn once per
    (op, shape, dtype) — a silent fallback here used to make "pallas"
    benchmark numbers quietly measure the jnp path.  Keying on the shape
    alone used to swallow the warning when a LATER call hit the same
    shape through a different op or value dtype (e.g. the f32 sparse wire
    warned, then the bf16 one fell back silently); callers pass `op` and
    `dtype` so each distinct dispatch site gets its own warning."""
    use = default_use_pallas() if use_pallas is None else use_pallas
    fits = n % tile_elems == 0
    if use_pallas is True and not fits:
        key = (op, n, tile_elems, str(dtype))
        if key not in _fallback_warned:
            _fallback_warned.add(key)
            warnings.warn(
                f"backend='pallas' requested but n={n} is not a multiple of "
                f"the kernel tile ({tile_elems} elements); falling back to "
                f"the jnp path for {op or 'this op'} (warned once per "
                f"(op, shape, dtype))",
                RuntimeWarning, stacklevel=3)
    return bool(use) and fits


def sign_pack(x, group_size: int, use_pallas=None):
    use = default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return sp.sign_pack(x, group_size,
                            interpret=_interpret())
    return ref.sign_pack_ref(x, group_size)


def sign_unpack(words, scales, group_size: int):
    return ref.sign_unpack_ref(words, scales, group_size)


def ef_sign_fused(g, e, gamma, mask_self, group_size: int,
                  want_c: bool = True, use_pallas=None):
    use = default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return sp.ef_sign_fused(g, e, gamma, mask_self, group_size,
                                want_c=want_c,
                                interpret=_interpret())
    w, s, c, e_new = ref.ef_sign_fused_ref(g, e, gamma, mask_self, group_size)
    return w, s, (c if want_c else None), e_new


def sign_decode_reduce(words, scales, mask, group_size: int, use_pallas=None):
    use = default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return sp.sign_decode_reduce(words, scales, mask, group_size,
                                     interpret=_interpret())
    return ref.sign_decode_reduce_scan(words, scales, mask, group_size)


def ef_topk_fused(g, e, gamma, mask_self, k: int, block_size: int,
                  want_c: bool = True, value_dtype: str = "float32",
                  use_pallas=None):
    use = default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return tp.ef_topk_fused(g, e, gamma, mask_self, k, block_size,
                                want_c=want_c, value_dtype=value_dtype,
                                interpret=_interpret())
    return tf.ef_topk_fused_fast(g, e, gamma, mask_self, k, block_size,
                                 value_dtype=value_dtype, want_c=want_c)


def dense_decode_reduce(values, mask, use_pallas=None):
    # no Pallas variant: the payload carries no decode step to fuse with.
    # The scan variant keeps the canonical sender-order accumulation every
    # other wire's decode path uses (reference-vs-mesh parity).
    return ref.dense_decode_reduce_scan(values, mask)


def block_topk(x, k: int, block_size: int, use_pallas=None):
    use = default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return tb.block_topk(x, k, block_size,
                             interpret=_interpret())
    return ref.block_topk_ref(x, k, block_size)


def topk_pack(x, k: int, block_size: int, use_pallas=None):
    use = default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return tp.topk_pack(x, k, block_size,
                            interpret=_interpret())
    return tf.topk_pack_fast(x, k, block_size)


def topk_unpack(indices, values, scales, block_size: int):
    return ref.topk_unpack_ref(indices, values, scales, block_size)


def topk_decode_reduce(indices, values, scales, mask, block_size: int,
                       use_pallas=None):
    use = default_use_pallas() if use_pallas is None else use_pallas
    if use:
        return tp.topk_decode_reduce(indices, values, scales, mask,
                                     block_size,
                                     interpret=_interpret())
    return ref.topk_decode_reduce_scan(indices, values, scales, mask,
                                       block_size)
