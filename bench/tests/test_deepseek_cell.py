"""The deepseek configuration against its plain reference on the CPU: YaRN
as published, the program's loss and gradients equal the reference's on
seeded weights, and a tiny deepseek cell driven through the harness is
correct, while a step that leaves its state unchanged or trains on half
the batch is not."""
from __future__ import annotations

import dataclasses
import json
import math

import numpy as np
import pytest

from bench.tests.test_bench_cell import half_batch, unchanged
from bench.tests.tiny import REPO, write_json

import jax  # noqa: E402  (after tiny sets JAX_PLATFORMS)
import jax.numpy as jnp  # noqa: E402

SEED = 2**31 + 1015
CELL_CONFIG = REPO / "bench/configs/deepseek-v2-lite-l5e8.json"
# the cell's model at a tiny width: a dense layer and two MoE layers, 2 of
# 8 experts held, top-2, 2 shared experts, YaRN as published
TINY = dict(json.loads(CELL_CONFIG.read_text())["model"], num_layers=3,
            d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
            vocab_size=256, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
            v_head_dim=16, moe_experts=8, moe_experts_held=2, moe_top_k=2,
            moe_ff=32, dense_ff=128)
# readings of tiny sound runs on the CPU (bf16 program vs f32 reference,
# seeds 2**31 + 1015 and 1..3): update_gap 0.0018-0.0042, change_gap
# 0.0032-0.0054; half the batch reads 0.370 / 0.406, a state left
# unchanged 1
TINY_LIMITS = {"update_gap": 0.05, "change_gap": 0.05}


def make_root(tmp):
    sign = json.loads((REPO / "bench/traffic/sign.json").read_text())
    write_json(tmp / "bench/traffic/tiny-sign.json", dict(sign, seq_len=32))
    write_json(tmp / "bench/configs/ds-tiny.json", {
        "name": "ds-tiny", "registry": "deepseek-v2-lite-16b",
        "reference": "deepseek", "model": TINY})
    write_json(tmp / "bench/limits/ds-tiny.json", {"limits": TINY_LIMITS})
    write_json(tmp / "BENCHMARK.json", {
        "configs": [{"name": "ds-tiny", "file": "bench/configs/ds-tiny.json"}],
        "workloads": [{"name": "ds-tiny", "config": "ds-tiny",
                       "traffic": "tiny-sign", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "mfu", "unit": "%"}]})
    return tmp


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    from bench import spec
    return spec.load_cell("ds-tiny", make_root(tmp_path_factory.mktemp("r")))


def run(cell, fault=None):
    from bench.run import run_cell
    return run_cell(cell, SEED, 0.5, False, None, on_chip=False, fault=fault)


# ---- YaRN -----------------------------------------------------------------

def published_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inv_freq, transcribed."""
    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask, (low, high)


def test_yarn_frequencies_and_scale_as_published():
    from repro.configs import REGISTRY
    from repro.nn import layers as L

    from bench.reference import deepseek
    cfg = REGISTRY["deepseek-v2-lite-16b"].config
    want, (low, high) = published_inv_freq(64, 10000.0, 40.0, 4096, 32, 1)
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(L.yarn_inv_freq(cfg, 64), want, rtol=1e-6)
    m = json.loads(CELL_CONFIG.read_text())["model"]
    np.testing.assert_allclose(deepseek.yarn_frequencies(m), want, rtol=1e-6)
    # fast pairs keep theta's frequencies, slow ones are 40x slower
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(want[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(want[23:], plain[23:] / 40, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.26080, abs=1e-5)
    assert L.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.58963, rel=1e-5)


def old_rope(x, positions, theta):
    """rope() as it was before YaRN's frequencies could be handed in."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_plain_rope_unchanged_bit_for_bit(dtype):
    from repro.nn.layers import rope
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 4, 80), dtype)
    pos = jnp.arange(64)[None]
    got = jax.jit(lambda a: rope(a, pos, 10000.0))(x)
    want = jax.jit(lambda a: old_rope(a, pos, 10000.0))(x)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


# ---- the program against the reference ----------------------------------

def test_loss_and_gradients_equal_the_reference():
    """At float32 the program's loss (mean NLL plus the weighted balance
    loss) and every leaf's gradient equal the reference's."""
    from repro.configs import REGISTRY
    from repro.nn import Model

    from bench.program import path_name
    from bench.reference import deepseek
    from bench.reference.common import make_weights
    from bench.seeds import seed_keys
    arch = REGISTRY["deepseek-v2-lite-16b"]
    cfg = dataclasses.replace(arch.config, **dict(TINY, dtype="float32"))
    model = Model(cfg)
    shapes = deepseek.param_shapes(TINY)
    w = make_weights(shapes, deepseek.init_laws(TINY),
                     seed_keys(SEED)["weights"])
    flat, tdef = jax.tree_util.tree_flatten_with_path(model.param_shapes())
    paths = [path_name(p) for p, _ in flat]
    assert {p: tuple(l.shape) for p, (_, l) in zip(paths, flat)} == shapes
    params = jax.tree_util.tree_unflatten(tdef, [w[p] for p in paths])
    toks = jax.random.randint(jax.random.PRNGKey(7), (4, 33), 0, 256)
    batch = {"inputs": toks, "weights": jnp.full((4,), 0.25)}
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch)
        ref_loss, ref_g = jax.value_and_grad(
            lambda p: jnp.mean(deepseek.row_losses(p, toks, TINY)))(w)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for p, leaf in zip(paths, jax.tree.leaves(g)):
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(ref_g[p]),
                                   rtol=2e-3, atol=2e-6, err_msg=p)


def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"] is True, res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", [unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(cell, fault):
    res = run(cell, fault)
    assert res["correct"] is False
    assert [k for k, v in res["check"].items() if v["value"] > v["limit"]]
