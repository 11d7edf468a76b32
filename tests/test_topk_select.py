"""Block top-k selection: the sort-free threshold search must BE `lax.top_k`.

`kernels.topk_block.select_blocks` (binary search on IEEE bit patterns,
then k rounds of (max magnitude, first position), with blocks on lanes)
is the in-kernel selection primitive of every sparse-wire Pallas kernel,
and `kernels.topk_fast` is the barrier-fixed jnp hot path the train step
runs on CPU.  The reference-vs-mesh parity gate demands that all three
agree with `kernels/ref.py` (plain `lax.top_k`) BIT-FOR-BIT — indices,
tie ORDER, values, scale — so these tests drive the selection through
adversarial inputs: heavy magnitude ties, all-equal rows, all-zero rows,
denormals, and k == block width; and the kernels through block counts
that fill whole tiles, leave a ragged last grid step, or fall short of
one tile.

Also covered here: the transmitted-reconstruction conservation for
bfloat16 wire values (Sterbenz), and the warn-once guard on silent
pallas -> jnp tile fallbacks.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st
from jax import lax

from repro.kernels import ops, ref
from repro.kernels import topk_fast as tf
from repro.kernels.topk_block import TILE_BLOCKS, select_blocks
from repro.kernels.topk_pack import ef_topk_fused, topk_pack

KINDS = ("normal", "ties", "equal", "denormal", "zeros")


def _rows(kind: str, seed: int, R: int, B: int) -> jnp.ndarray:
    """(R, B) f32 rows engineered at the selection's corner cases."""
    x = jax.random.normal(jax.random.PRNGKey(seed * 7919 + B), (R, B))
    if kind == "ties":          # few distinct magnitudes -> threshold ties
        x = jnp.round(x * 3.0) / 3.0
    elif kind == "equal":       # every |x| identical -> pure tie-rank cut
        x = jnp.where(x >= 0, 1.0, -1.0)
    elif kind == "denormal":    # f32 subnormals (bit-pattern search floor)
        x = x * 1e-40
    elif kind == "zeros":       # zero rows + zero-riddled rows
        x = x.at[:, ::2].set(0.0).at[0].set(0.0)
    return x.astype(jnp.float32)


def _bitwise_equal(a, b, msg=""):
    """Equal bit patterns: -0.0 differs from 0.0, NaN payloads count."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, (msg, a.dtype, b.dtype)
    if a.dtype.kind == "f":
        a, b = a.view(f"u{a.itemsize}"), b.view(f"u{b.itemsize}")
    np.testing.assert_array_equal(a, b, msg)


_select = jax.jit(select_blocks, static_argnums=1)


@settings(max_examples=20, deadline=None)
@given(kind=st.sampled_from(KINDS), seed=st.integers(0, 100),
       k=st.sampled_from([1, 4, 16, 64]))
def test_block_select_is_lax_top_k(kind, seed, k):
    """Indices (incl. tie order), signed values, and scale all bitwise
    equal to the lax.top_k selection on |x| — for every adversarial row
    family, up to k == block width.  Blocks run along the lanes."""
    B = 64
    x = _rows(kind, seed, 8, B)
    idx, sval, scale, _ = _select(x.T, k)
    topv, tidx = lax.top_k(jnp.abs(x), k)
    _bitwise_equal(idx.T, tidx, kind)
    _bitwise_equal(sval.T, jnp.take_along_axis(x, tidx, -1), kind)
    _bitwise_equal(scale[0], jnp.max(jnp.abs(x), axis=-1), kind)


@settings(max_examples=20, deadline=None)
@given(kind=st.sampled_from(KINDS), seed=st.integers(0, 100),
       k=st.sampled_from([1, 7, 32, 128]))
def test_block_select_mask_is_exact_topk_set(kind, seed, k):
    """The rank image keeps exactly k coordinates per block, the SET
    lax.top_k selects (first occurrence winning ties), each ranked by
    its place in lax.top_k's order."""
    B = 128
    x = _rows(kind, seed, 8, B)
    rank = np.asarray(_select(x.T, k)[3]).T
    keep = rank >= 0
    assert (keep.sum(-1) == k).all()
    _, tidx = lax.top_k(jnp.abs(x), k)
    expect = np.full(rank.shape, -1)
    np.put_along_axis(expect, np.asarray(tidx),
                      np.broadcast_to(np.arange(k), tidx.shape), axis=-1)
    np.testing.assert_array_equal(rank, expect, kind)


@pytest.mark.parametrize("lanes", [1, 8, 128, 136])
def test_select_blocks_equals_lax_top_k_at_any_lane_count(lanes):
    """The blocks-on-lanes primitive is lax.top_k per column whatever the
    number of columns (one block, a partial vreg, whole and ragged lane
    tiles), on a mix of every corner-case family."""
    B, k = 256, 8
    x = jnp.concatenate([_rows(kind, lanes, lanes, B) for kind in KINDS])
    x = x[jnp.arange(lanes) * len(KINDS) % x.shape[0]]
    idx, sval, scale, rank = _select(x.T, k)
    topv, tidx = lax.top_k(jnp.abs(x), k)
    _bitwise_equal(idx.T, tidx)
    _bitwise_equal(sval.T, jnp.take_along_axis(x, tidx, -1))
    _bitwise_equal(scale[0], topv[:, 0])
    assert (np.asarray(rank >= 0).sum(0) == k).all()


def test_block_select_rejects_bad_k():
    x = jnp.ones((16, 2))
    for bad in (0, -1, 17):
        with pytest.raises(ValueError):
            select_blocks(x, bad)


# ---------------------------------------------------------------------------
# the fast (barrier) jnp path and the Pallas kernels vs the ref oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("value_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mask", [0.0, 1.0])
def test_fast_fused_step_bitwise_equals_ref(value_dtype, mask):
    """topk_fast.ef_topk_fused_fast (the CPU hot path with the fusion
    barrier) is bit-for-bit ref.ef_topk_fused_ref under jit."""
    n, k, block = 8 * 128 * 2, 8, 128
    g = jax.random.normal(jax.random.PRNGKey(10), (n,))
    e = jax.random.normal(jax.random.PRNGKey(11), (n,)) * 0.1
    fast = jax.jit(lambda a, b: tf.ef_topk_fused_fast(
        a, b, 0.01, mask, k, block, value_dtype=value_dtype))(g, e)
    orac = jax.jit(lambda a, b: ref.ef_topk_fused_ref(
        a, b, 0.01, mask, k, block, value_dtype=value_dtype))(g, e)
    for a, b in zip(fast, orac):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fast_pack_bitwise_equals_ref():
    n, k, block = 8 * 256, 8, 256
    x = _rows("ties", 3, n // block, block).reshape(-1)
    fast = jax.jit(lambda a: tf.topk_pack_fast(a, k, block))(x)
    orac = jax.jit(lambda a: ref.topk_pack_ref(a, k, block))(x)
    for a, b in zip(fast, orac):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# block counts: whole tiles, a ragged last grid step, fewer than one tile
WHOLE, RAGGED, SHORT = TILE_BLOCKS, TILE_BLOCKS + 8, 8
FUSED_CASES = (
    [(kind, k, "float32", 1.0, RAGGED) for kind in KINDS for k in (1, 8, 64)]
    + [("ties", 8, vd, mask, blocks) for vd in ("float32", "bfloat16")
       for mask in (0.0, 1.0) for blocks in (WHOLE, RAGGED, SHORT)])


@pytest.mark.parametrize("kind,k,value_dtype,mask,blocks", FUSED_CASES)
def test_pallas_fused_step_bitwise_equals_ref(kind, k, value_dtype, mask,
                                              blocks):
    """The Pallas kernel (select_blocks inside the kernel body, interpret
    mode on CPU) matches the jitted ref oracle bit for bit: every corner
    case family, k from 1 to 64, both wire dtypes, both masks, and block
    counts that fill, overrun (ragged last grid step) or fall short of
    one tile."""
    block = 256
    g = _rows(kind, k, blocks, block).reshape(-1)
    e = jax.random.normal(jax.random.PRNGKey(12), g.shape) * 0.1
    if kind in ("denormal", "zeros", "equal"):
        e = jnp.zeros_like(e)           # keep acc in the family's corner
    outs_k = ef_topk_fused(g, e, 1.0, mask, k, block,
                           value_dtype=value_dtype, interpret=True)
    outs_r = jax.jit(lambda a, b: ref.ef_topk_fused_ref(
        a, b, 1.0, mask, k, block, value_dtype=value_dtype))(g, e)
    for name, a, b in zip(("idx", "val", "scale", "c", "e_new"),
                          outs_k, outs_r):
        _bitwise_equal(a, b, f"{name} {kind}")


@pytest.mark.parametrize("kind,blocks", [("equal", SHORT), ("ties", RAGGED),
                                         ("zeros", RAGGED),
                                         ("denormal", WHOLE)])
def test_pallas_pack_bitwise_equals_ref_on_ties(kind, blocks):
    k, block = 4, 64
    x = _rows(kind, 9, blocks, block).reshape(-1)
    outs_k = topk_pack(x, k, block, interpret=True)
    outs_r = jax.jit(lambda a: ref.topk_pack_ref(a, k, block))(x)
    for a, b in zip(outs_k, outs_r):
        _bitwise_equal(a, b, kind)


def test_bf16_wire_conservation_sterbenz():
    """With bfloat16 wire values, c is the value_dtype-ROUNDED transmitted
    reconstruction, yet c + e_new still equals acc bit-for-bit: at kept
    coordinates c lands within a factor of two of acc, so the `acc - c`
    subtraction is exact (Sterbenz), and elsewhere c is zero."""
    n, k, block = 8 * 128, 8, 128
    gv = jax.random.normal(jax.random.PRNGKey(13), (n,))
    e = jax.random.normal(jax.random.PRNGKey(14), (n,)) * 0.1
    gamma = 0.05

    @jax.jit
    def step(a, b):
        acc = ref.mul_add(gamma, a, b)
        _, _, _, c, e_new = tf.ef_topk_fused_fast(
            a, b, gamma, 1.0, k, block, value_dtype="bfloat16")
        return acc, c, e_new

    acc, c, e_new = step(gv, e)
    np.testing.assert_array_equal(np.asarray(c) + np.asarray(e_new),
                                  np.asarray(acc))


def test_want_c_false_matches_want_c_true():
    """want_c=False must change nothing but drop c (the DCE path the wire
    uses when only the payload ships)."""
    n, k, block = 8 * 128, 4, 128
    g = jax.random.normal(jax.random.PRNGKey(15), (n,))
    e = jax.random.normal(jax.random.PRNGKey(16), (n,)) * 0.1
    for fn in (tf.ef_topk_fused_fast,
               lambda *a, **kw: ef_topk_fused(*a, interpret=True, **kw)):
        with_c = jax.jit(lambda a, b: fn(a, b, 0.01, 1.0, k, block,
                                         want_c=True))(g, e)
        no_c = jax.jit(lambda a, b: fn(a, b, 0.01, 1.0, k, block,
                                       want_c=False))(g, e)
        assert no_c[3] is None
        for i in (0, 1, 2, 4):
            np.testing.assert_array_equal(np.asarray(with_c[i]),
                                          np.asarray(no_c[i]))


# ---------------------------------------------------------------------------
# dispatch honesty: explicit-pallas tile fallback warns exactly once
# ---------------------------------------------------------------------------

def test_pallas_tile_fallback_warns_once_per_shape():
    n, tile = 4097, 4096            # unique (n, tile): the warn-set is
    #   process-global, so this pair must not be used by any other test
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert ops.resolve_use_pallas(True, n, tile) is False
        assert ops.resolve_use_pallas(True, n, tile) is False
    runtime = [x for x in w if issubclass(x.category, RuntimeWarning)]
    assert len(runtime) == 1
    assert "falling back" in str(runtime[0].message)
    # auto (None) and explicit jnp fallbacks stay silent — only a broken
    # EXPLICIT pallas request is worth a warning
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        assert ops.resolve_use_pallas(None, 4099, tile) in (False,)
        assert ops.resolve_use_pallas(False, 4099, tile) is False
    assert not [x for x in w2 if issubclass(x.category, RuntimeWarning)]
    # fitting shapes never warn and honor the request
    with warnings.catch_warnings(record=True) as w3:
        warnings.simplefilter("always")
        assert ops.resolve_use_pallas(True, 2 * tile, tile) is True
    assert not [x for x in w3 if issubclass(x.category, RuntimeWarning)]
