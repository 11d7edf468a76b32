"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ref
from repro.kernels.sign_pack import TILE_GROUPS, ef_sign_fused, \
    sign_decode_reduce, sign_pack
from repro.kernels.topk_block import block_topk

# group counts: whole grid tiles, one tile plus 8 groups (a ragged last
# grid step), fewer groups than one tile
WHOLE, RAGGED, SHORT = 2 * TILE_GROUPS, TILE_GROUPS + 8, 8
TILE_CASES = {"whole": WHOLE, "ragged": RAGGED, "short": SHORT}


@pytest.mark.parametrize("group", [128, 256, 512])
@pytest.mark.parametrize("blocks", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sign_pack_sweep(group, blocks, dtype):
    n = 8 * group * blocks
    x = (jax.random.normal(jax.random.PRNGKey(group + blocks), (n,)) * 2
         ).astype(dtype)
    w1, s1 = sign_pack(x, group, interpret=True)
    w2, s2 = ref.sign_pack_ref(x, group)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_pack_unpack_roundtrip_on_quantized():
    """unpack(pack(x)) equals sign(x)*scale -> packing a sign-quantized
    vector is lossless to ~1ulp."""
    n, g = 8 * 256, 256
    x = jax.random.normal(jax.random.PRNGKey(0), (n,))
    w, s = sign_pack(x, g, interpret=True)
    rt = ref.sign_unpack_ref(w, s, g)
    expected = np.where(np.asarray(x) >= 0, 1.0, -1.0) * \
        np.repeat(np.asarray(s), g)
    np.testing.assert_allclose(np.asarray(rt), expected, rtol=1e-6)


@pytest.mark.parametrize("group", [128, 512])
@pytest.mark.parametrize("mask", [0.0, 1.0])
def test_ef_fused_sweep(group, mask):
    n = 8 * group * 2
    g = jax.random.normal(jax.random.PRNGKey(1), (n,))
    e = jax.random.normal(jax.random.PRNGKey(2), (n,)) * 0.1
    outs_k = ef_sign_fused(g, e, 0.01, mask, group, interpret=True)
    outs_r = ref.ef_sign_fused_ref(g, e, 0.01, mask, group)
    for a, b in zip(outs_k, outs_r):
        if a.dtype == jnp.uint32:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def test_ef_fused_conservation():
    """words/scales decode + e_new reconstruct acc exactly (Algorithm 1)."""
    n, g = 8 * 256, 256
    gv = jax.random.normal(jax.random.PRNGKey(1), (n,))
    e = jax.random.normal(jax.random.PRNGKey(2), (n,)) * 0.1
    gamma = 0.05
    words, scales, c, e_new = ef_sign_fused(gv, e, gamma, 1.0, g,
                                            interpret=True)
    acc = gamma * np.asarray(gv) + np.asarray(e)
    np.testing.assert_allclose(np.asarray(c) + np.asarray(e_new), acc,
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("n_senders", [2, 4, 16])
def test_sign_decode_reduce(n_senders):
    n, g = 8 * 256, 256
    ws, ss = [], []
    for i in range(n_senders):
        x = jax.random.normal(jax.random.PRNGKey(i), (n,))
        w, s = ref.sign_pack_ref(x, g)
        ws.append(w)
        ss.append(s)
    words = jnp.stack(ws)
    scales = jnp.stack(ss)
    mask = (jnp.arange(n_senders) % 2).astype(jnp.float32)
    out_k = sign_decode_reduce(words, scales, mask, g, interpret=True)
    out_r = ref.sign_decode_reduce_ref(words, scales, mask, g)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("groups", TILE_CASES.values(), ids=TILE_CASES)
def test_sign_pack_tiles(groups):
    """The words are sign_pack_ref's bit for bit whether the group count
    fills whole grid tiles, leaves a ragged last grid step or falls short
    of one tile."""
    g = 512
    x = jax.random.normal(jax.random.PRNGKey(groups), (groups * g,))
    w1, s1 = sign_pack(x, g, interpret=True)
    w2, s2 = ref.sign_pack_ref(x, g)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


@pytest.mark.parametrize("groups", TILE_CASES.values(), ids=TILE_CASES)
@pytest.mark.parametrize("want_c", [True, False])
@pytest.mark.parametrize("mask", [0.0, 1.0])
def test_ef_fused_tiles(groups, want_c, mask):
    g = 512
    gv = jax.random.normal(jax.random.PRNGKey(groups + 1), (groups * g,))
    e = jax.random.normal(jax.random.PRNGKey(groups + 2), gv.shape) * 0.1
    outs_k = ef_sign_fused(gv, e, 0.01, mask, g, want_c=want_c,
                           interpret=True)
    outs_r = ref.ef_sign_fused_ref(gv, e, 0.01, mask, g)
    assert (outs_k[2] is None) == (not want_c)
    np.testing.assert_array_equal(np.asarray(outs_k[0]),
                                  np.asarray(outs_r[0]))
    for a, b in zip(outs_k[1:], outs_r[1:]):
        if a is not None:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("groups", TILE_CASES.values(), ids=TILE_CASES)
@pytest.mark.parametrize("n_senders", [1, 4])
def test_sign_decode_reduce_tiles(groups, n_senders):
    g = 512
    packs = [ref.sign_pack_ref(jax.random.normal(
        jax.random.PRNGKey(groups + i), (groups * g,)), g)
        for i in range(n_senders)]
    words = jnp.stack([w for w, _ in packs])
    scales = jnp.stack([s for _, s in packs])
    mask = (jnp.arange(n_senders) % 3 != 1).astype(jnp.float32)
    out_k = sign_decode_reduce(words, scales, mask, g, interpret=True)
    out_r = ref.sign_decode_reduce_ref(words, scales, mask, g)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("k,block", [(4, 128), (8, 256)])
@pytest.mark.parametrize("mask", [0.0, 1.0])
def test_ef_topk_fused_sweep(k, block, mask):
    from repro.kernels.topk_pack import ef_topk_fused
    n = 8 * block * 2
    g = jax.random.normal(jax.random.PRNGKey(3), (n,))
    e = jax.random.normal(jax.random.PRNGKey(4), (n,)) * 0.1
    outs_k = ef_topk_fused(g, e, 0.01, mask, k, block, interpret=True)
    # jit the oracle too: backend parity is a property of the compiled
    # programs (eager evaluation reassociates the accumulate by ~1 ulp)
    outs_r = jax.jit(lambda a, b: ref.ef_topk_fused_ref(a, b, 0.01, mask, k,
                                                        block))(g, e)
    for a, b in zip(outs_k, outs_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ef_topk_fused_conservation():
    """c + e_new reconstruct acc exactly.  `c` is the TRANSMITTED
    reconstruction (normalize -> value_dtype -> denormalize, what a
    receiver unpacks from the wire); at the default value_dtype="float32"
    the rounding is the identity, so c holds the exact kept values, and
    for narrower wire dtypes Sterbenz keeps `acc - c` exact anyway
    (tests/test_topk_select.py covers bfloat16)."""
    from repro.kernels.topk_pack import ef_topk_fused
    n, k, block = 8 * 128, 8, 128
    gv = jax.random.normal(jax.random.PRNGKey(5), (n,))
    e = jax.random.normal(jax.random.PRNGKey(6), (n,)) * 0.1
    gamma = 0.05
    idx, val, sc, c, e_new = ef_topk_fused(gv, e, gamma, 1.0, k, block,
                                           interpret=True)
    # jitted accumulate — XLA contracts gamma*g + e into an FMA, so the
    # bitwise-matching oracle must be compiled too
    acc = np.asarray(jax.jit(lambda a, b: jnp.float32(gamma) * a + b)(gv, e))
    np.testing.assert_array_equal(np.asarray(c) + np.asarray(e_new), acc)
    # payload agrees with the pack-only kernel on the same acc
    from repro.kernels.topk_pack import topk_pack
    i2, v2, s2 = topk_pack(jnp.asarray(acc), k, block, interpret=True)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(val), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(s2))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), k=st.sampled_from([4, 8, 16]),
       block=st.sampled_from([128, 256]))
def test_block_topk_sweep(seed, k, block):
    n = 8 * block
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    out_k = block_topk(x, k, block, interpret=True)
    out_r = ref.block_topk_ref(x, k, block)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    nnz = (np.asarray(out_k).reshape(-1, block) != 0).sum(-1)
    assert (nnz == k).all()


def test_block_topk_bf16():
    n, k, block = 8 * 128, 4, 128
    x = (jax.random.normal(jax.random.PRNGKey(5), (n,))).astype(jnp.bfloat16)
    out_k = block_topk(x, k, block, interpret=True)
    out_r = ref.block_topk_ref(x, k, block)
    np.testing.assert_array_equal(np.asarray(out_k.astype(jnp.float32)),
                                  np.asarray(out_r.astype(jnp.float32)))


@pytest.mark.parametrize("softcap,window,groups", [
    (0.0, 0, 1), (50.0, 0, 2), (0.0, 64, 2), (30.0, 32, 4)])
def test_flash_attention(softcap, window, groups):
    from repro.kernels.flash_attention import flash_attention
    B, Hkv, S, hd = 2, 2, 512, 64
    H = Hkv * groups
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, S, hd)) * hd ** -0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, S, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, S, hd))
    out_k = flash_attention(q, k, v, softcap=softcap, window=window,
                            groups=groups, interpret=True)
    out_r = ref.flash_attention_ref(q, k, v, softcap=softcap, window=window,
                                    groups=groups)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-4, atol=2e-5)
