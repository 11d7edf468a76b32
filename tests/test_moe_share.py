"""The expert layer at one device's share of an expert-parallel deployment
(nn/moe.py): it routes over every expert, computes its held experts' part
for the tokens routed to them without dropping any, and the parts that
all the shares give add up to the whole layer."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import moe as MOE
from repro.nn.config import ModelConfig

E, K, HELD, D, FF = 8, 2, 2, 64, 32
T = 48


def cfg(held=0, norm_topk=False):
    return ModelConfig(name="moe-share", family="moe", num_layers=1,
                       d_model=D, num_heads=4, num_kv_heads=4, d_ff=FF,
                       vocab_size=64, moe_experts=E, moe_experts_held=held,
                       moe_top_k=K, moe_norm_topk=norm_topk, moe_shared=2,
                       moe_ff=FF, dtype="float32")


def layer_params(seed=0):
    return MOE.init_moe(jax.random.PRNGKey(seed), cfg())


def inputs(seed=1, skew=0.0):
    """(1, T, D) tokens; `skew` adds a direction every token shares."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, T, D), jnp.float32)
    return x + skew * jnp.ones((D,), jnp.float32)


def explicit(p, x, held):
    """The layer written out token by token in numpy (float64): softmax
    gates over all experts, top-K, gates not renormalized, the held
    experts' SwiGLU weighted by their gates, plus the shared SwiGLU."""
    P = {k: np.asarray(v, np.float64) for k, v in p.items() if k != "shared"}
    S = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}
    silu = lambda a: a / (1 + np.exp(-a))      # noqa: E731
    out = []
    for t in np.asarray(x, np.float64).reshape(-1, D):
        z = t @ P["router"]
        s = np.exp(z - z.max())
        s /= s.sum()
        y = silu(t @ S["w_gate"]) * (t @ S["w_up"]) @ S["w_down"]
        for e in np.argsort(-s, kind="stable")[:K]:
            if e < held:
                y = y + s[e] * (silu(t @ P["w_gate"][e]) * (t @ P["w_up"][e])
                                @ P["w_down"][e])
        out.append(y)
    return np.stack(out).reshape(x.shape)


def share(p, j):
    """Device j's share: its experts 2j, 2j+1 and the router's columns
    permuted so that they come first (the router keeps all E outputs)."""
    perm = [2 * j, 2 * j + 1] + [e for e in range(E) if e // 2 != j]
    return dict(p, router=p["router"][:, perm],
                **{w: p[w][2 * j:2 * j + 2] for w in ("w_gate", "w_up",
                                                      "w_down")})


@pytest.mark.parametrize("skew", [0.0, 3.0], ids=["even", "skewed"])
def test_shares_add_up_to_the_whole_layer(skew):
    p, x = layer_params(), inputs(skew=skew)
    whole, _ = MOE.apply_moe(p, x, cfg())
    np.testing.assert_allclose(np.asarray(whole), explicit(p, x, E),
                               rtol=2e-5, atol=2e-5)
    parts = [MOE.apply_moe(share(p, j), x, cfg(held=HELD))[0]
             for j in range(E // HELD)]
    # each share adds the shared experts once; the sum counts them once
    only_shared = explicit(p, x, 0)
    total = sum(np.asarray(q, np.float64) for q in parts) \
        - (E // HELD - 1) * only_shared
    np.testing.assert_allclose(total, np.asarray(whole, np.float64),
                               rtol=2e-5, atol=2e-5)
    # share 0 alone is the held-experts formula at held = 2
    np.testing.assert_allclose(np.asarray(parts[0]), explicit(p, x, HELD),
                               rtol=2e-5, atol=2e-5)


def test_skewed_routing_drops_nothing():
    """With every token on the same experts, far past a capacity of
    1.25 x T K / E rows an expert, each routed token still counts."""
    p, x = layer_params(), inputs(skew=3.0)
    c = cfg(held=HELD)
    s = jax.nn.softmax(x.reshape(T, D) @ p["router"], -1)
    load = np.bincount(np.asarray(jax.lax.top_k(s, K)[1]).ravel(),
                       minlength=E)
    assert load.max() > math.ceil(1.25 * T * K / E)
    out, _, rows = MOE.apply_moe(share(p, 0), x, c, counters=True)
    assert int(rows) == int(load[:HELD].sum())
    np.testing.assert_allclose(np.asarray(out), explicit(p, x, HELD),
                               rtol=2e-5, atol=2e-5)


def test_renormalized_gates_are_another_layer():
    p, x = layer_params(), inputs()
    norm, _ = MOE.apply_moe(share(p, 0), x,
                            cfg(held=HELD, norm_topk=True))
    assert not np.allclose(np.asarray(norm), explicit(p, x, HELD),
                           rtol=1e-3, atol=1e-3)


def test_balance_loss_and_gradients_are_finite():
    p, x = share(layer_params(), 0), inputs(skew=1.0)

    def loss(p):
        y, aux = MOE.apply_moe(p, x, cfg(held=HELD))
        return jnp.sum(y ** 2) + aux
    g = jax.grad(loss)(p)
    assert all(np.isfinite(np.asarray(a)).all() for a in jax.tree.leaves(g))
    # the experts held elsewhere are not parameters here
    assert g["w_gate"].shape == (HELD, D, FF)
    # uniform router probabilities read K whatever the picks:
    # E * sum_e (1/E) (count_e / T), the counts summing to K T
    _, aux = MOE.apply_moe(p, jnp.zeros((1, T, D)), cfg(held=HELD))
    assert float(aux) == pytest.approx(K, rel=1e-6)
