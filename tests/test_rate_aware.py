"""Rate-aware gradient coding (ISSUE 4): the unbiasedness contract of the
per-rank encode weights, the greedy heterogeneity-aware allocator, per-rank
adaptive wire budgets (SparseWire + cost-model solver + per-rank
accounting), construction-time knob validation, and the single definition
of the all-straggler step semantics."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coding, compression as C, error_feedback as EF
from repro.core.collectives import SignWire, SparseWire
from repro.sim import (ComputeProfile, HeterogeneousRates, IIDBernoulli,
                       LinkProfile, MarkovBursty, StepTimer, TraceReplay,
                       get_straggler_process, solve_k_budgets)
from test_distributed import run_sub

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # benchmarks/


# ---------------------------------------------------------------------------
# encode weights: the unbiasedness contract
# ---------------------------------------------------------------------------

def test_encode_weights_uniform_rates_bit_for_bit():
    """rates == (1-p) * ones must reproduce eq. 3 BIT FOR BIT (the iid
    regression guarantee of the rate-aware generalization)."""
    alloc = coding.random_allocation(0, 24, 24, 3)
    for p in (0.0, 0.2, 0.37, 0.7):
        W_eq3 = np.asarray(coding.encode_weights(alloc, p))
        W_rate = np.asarray(coding.encode_weights(
            alloc, rates=np.full(24, 1.0 - p)))
        np.testing.assert_array_equal(W_eq3, W_rate)


@pytest.mark.parametrize("rates", [
    pytest.param(HeterogeneousRates.two_class(
        16, p_slow=0.8, p_fast=0.02, slow_fraction=0.3).rates(),
        id="two_class"),
    pytest.param(HeterogeneousRates.linear(16, 0.3, spread=0.9).rates(),
                 id="linear"),
    pytest.param(np.linspace(0.35, 1.0, 16), id="arbitrary"),
])
def test_rate_aware_weights_unbiased_closed_form(rates):
    """sum_i q_i W[i, k] == 1 for every subset k — the exact condition for
    E[sum_i I_i g_i] = grad F under independent per-rank participation."""
    alloc = coding.random_allocation(1, 16, 16, 3)
    W = np.asarray(coding.encode_weights(alloc, rates=rates), np.float64)
    coeff = np.asarray(rates, np.float64) @ W
    np.testing.assert_allclose(coeff, 1.0, rtol=1e-5)


def test_mean_rate_weights_provably_biased_under_two_class():
    """Eq. 3 with the scalar mean rate is NOT unbiased under a two-class
    fleet: some subset's expectation coefficient deviates from 1 by a
    closed-form margin (the PR-motivating bug)."""
    proc = HeterogeneousRates.two_class(16, p_slow=0.8, p_fast=0.02,
                                        slow_fraction=0.3)
    q = proc.rates()
    alloc = coding.random_allocation(1, 16, 16, 3)
    p_bar = float(1.0 - q.mean())
    W = np.asarray(coding.encode_weights(alloc, p_bar), np.float64)
    coeff = q @ W
    assert np.max(np.abs(coeff - 1.0)) > 0.1


@pytest.mark.parametrize("make,T,atol", [
    pytest.param(lambda: IIDBernoulli(num_devices=16, p=0.3), 1200, 0.5,
                 id="iid"),
    pytest.param(lambda: HeterogeneousRates.two_class(
        16, p_slow=0.8, p_fast=0.02, slow_fraction=0.3), 1200, 0.5,
        id="hetero_two_class"),
    pytest.param(lambda: HeterogeneousRates.linear(16, 0.3, spread=0.9),
                 1200, 0.5, id="hetero_linear"),
    # bursts correlate consecutive masks -> ~mean_burst x fewer effective
    # samples, hence the looser tolerance
    pytest.param(lambda: MarkovBursty(num_devices=16, p=0.3, mean_burst=4.0),
                 2400, 1.0, id="markov"),
])
def test_rate_aware_ghat_empirically_unbiased(make, T, atol, rng_key):
    """Property test of the whole aggregation: the mean over >= 1k sampled
    masks of ghat = sum_i I_i g_i matches the dense gradient under the
    rate-aware weights for EVERY straggler process — and provably does not
    under mean-rate eq. 3 for the two-class fleet."""
    proc = make()
    N, D = 16, 8
    alloc = coding.random_allocation(2, N, N, 3)
    rng = np.random.default_rng(3)
    grads = rng.normal(size=(N, D))               # per-subset gradients
    dense = grads.sum(0)                          # grad F
    tr = np.asarray(proc.sample_trace(rng_key, T), np.float64)  # (T, N)
    assert tr.shape[0] >= 1000

    W = np.asarray(coding.encode_weights(
        alloc, rates=np.asarray(proc.rates())), np.float64)
    ghat_mean = (tr @ (W @ grads)) .mean(axis=0)
    scale = np.abs(dense).max()
    np.testing.assert_allclose(ghat_mean, dense, atol=atol * scale / 10)

    if isinstance(proc, HeterogeneousRates) and len(set(proc.p_ranks)) > 1:
        p_bar = float(1.0 - proc.rates().mean())
        W_mean = np.asarray(coding.encode_weights(alloc, p_bar), np.float64)
        bias = np.abs((tr @ (W_mean @ grads)).mean(axis=0) - dense).max()
        assert bias > 2 * atol * scale / 10       # clearly outside tolerance


def test_trace_replay_rate_aware_exactly_unbiased_over_one_cycle(rng_key):
    """TraceReplay.rates() is the trace's empirical marginal, so averaging
    ghat over exactly one replay cycle recovers the dense gradient to f32
    weight precision — the strongest form of the contract."""
    rows = np.array(HeterogeneousRates.two_class(
        8, p_slow=0.7, p_fast=0.1).sample_trace(rng_key, 32))
    rows[0] = 1.0                                 # every rank covered
    proc = TraceReplay.from_array(rows)
    alloc = coding.random_allocation(4, 8, 8, 3)
    W = np.asarray(coding.encode_weights(
        alloc, rates=np.asarray(proc.rates())), np.float64)
    grads = np.random.default_rng(5).normal(size=(8, 5))
    tr = np.asarray(proc.sample_trace(rng_key, proc.length), np.float64)
    ghat_mean = (tr @ (W @ grads)).mean(axis=0)
    np.testing.assert_allclose(ghat_mean, grads.sum(0), rtol=1e-5)


def test_encode_weights_validation():
    alloc = coding.random_allocation(0, 8, 8, 2)
    with pytest.raises(ValueError):               # neither given
        coding.encode_weights(alloc)
    with pytest.raises(ValueError):               # both given
        coding.encode_weights(alloc, 0.1, rates=np.ones(8))
    with pytest.raises(ValueError):               # wrong length
        coding.encode_weights(alloc, rates=np.ones(5))
    with pytest.raises(ValueError):               # out of range
        coding.encode_weights(alloc, rates=np.full(8, 1.5))
    # a subset whose every holder has rate 0 has no unbiased weighting
    dead = np.ones(8)
    dead[np.nonzero(alloc.S[:, 0])[0]] = 0.0
    with pytest.raises(ValueError):
        coding.encode_weights(alloc, rates=dead)


# ---------------------------------------------------------------------------
# rate-aware allocator: greedy expected-coverage maximization
# ---------------------------------------------------------------------------

def test_rate_aware_allocation_budget_and_coverage():
    q = HeterogeneousRates.two_class(16, p_slow=0.8, p_fast=0.02,
                                     slow_fraction=0.3).rates()
    d = 3
    alloc = coding.rate_aware_allocation(q, 16, d)
    assert alloc.S.shape == (16, 16)
    assert int(alloc.S.sum()) == d * 16           # same replica budget
    assert (alloc.d >= 1).all()
    cov = coding.expected_coverage(alloc, q)
    cov_cyc = coding.expected_coverage(coding.cyclic_allocation(16, 16, d), q)
    assert cov.mean() > cov_cyc.mean()            # strictly better placement
    assert cov.min() >= cov_cyc.min()


def test_rate_aware_allocation_extra_redundancy_on_unreliable_ranks():
    """The redundancy concentrates where the fleet is weak: every subset
    homed on an unreliable rank acquires a reliable holder (cyclic leaves
    some covered only by slow ranks), subsets homed on slow ranks carry at
    least as many replicas as fast-homed ones, and the worst-subset
    coverage is lifted far above cyclic's."""
    N, d = 16, 3
    n_slow = 5
    q = np.array([0.2] * n_slow + [0.98] * (N - n_slow))
    alloc = coding.rate_aware_allocation(q, N, d)
    d_k = alloc.d
    assert d_k[:n_slow].mean() >= d_k[n_slow:].mean()
    assert d_k.max() > d_k.min()                  # non-uniform redundancy
    for k in range(n_slow):                       # slow-homed subsets get
        assert alloc.S[n_slow:, k].sum() >= 1     # a reliable holder
    cov = coding.expected_coverage(alloc, q)
    cov_cyc = coding.expected_coverage(coding.cyclic_allocation(N, N, d), q)
    assert cov.min() > 0.99 > cov_cyc.min()


def test_rate_aware_allocation_validation_and_determinism():
    with pytest.raises(ValueError):
        coding.rate_aware_allocation(np.array([0.5, 1.5]), 4, 2)
    with pytest.raises(ValueError):
        coding.rate_aware_allocation(np.array([]), 4, 2)
    q = np.linspace(0.3, 1.0, 8)
    a1 = coding.rate_aware_allocation(q, 8, 3)
    a2 = coding.rate_aware_allocation(q, 8, 3)
    np.testing.assert_array_equal(a1.S, a2.S)     # deterministic
    # uniform rates degrade gracefully to a valid balanced allocation
    u = coding.rate_aware_allocation(np.full(8, 0.7), 8, 3)
    assert int(u.S.sum()) == 24 and (u.d >= 1).all()


# ---------------------------------------------------------------------------
# per-rank adaptive wire budgets
# ---------------------------------------------------------------------------

def test_sparse_wire_per_rank_budget_semantics(rng_key):
    wire = SparseWire(k_per_block=(2, 8), block_size=64)
    assert wire.has_rank_budgets() and wire.k_max == 8
    assert not SparseWire(k_per_block=8).has_rank_budgets()
    x = jax.random.normal(rng_key, (256,))
    payload = wire.pack(x)
    assert payload[1].shape == (4, 8)             # k_max payload shape
    p0 = wire.apply_rank_budget(payload, 0)
    assert np.all(np.asarray(p0[1])[:, 2:] == 0)  # beyond budget zeroed
    np.testing.assert_array_equal(np.asarray(p0[1])[:, :2],
                                  np.asarray(payload[1])[:, :2])
    # the truncated payload decodes to exactly the scalar-k wire's roundtrip
    np.testing.assert_array_equal(np.asarray(wire.unpack(p0)),
                                  np.asarray(wire.for_rank(0).roundtrip(x)))
    # rank 1 keeps the full budget
    p1 = wire.apply_rank_budget(payload, 1)
    np.testing.assert_array_equal(np.asarray(p1[1]),
                                  np.asarray(payload[1]))
    # traced rank index (the shard_map path)
    p0j = jax.jit(lambda r: wire.apply_rank_budget(payload, r))(jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(p0j[1]), np.asarray(p0[1]))


def test_sparse_wire_per_rank_bytes_accounting():
    wire = SparseWire(k_per_block=(2, 4, 8, 16), block_size=512)
    n = 4096
    per = wire.rank_wire_bytes(n, 4)
    for i, k in enumerate((2, 4, 8, 16)):
        assert per[i] == SparseWire(k_per_block=k,
                                    block_size=512).wire_bytes(n)
    assert wire.wire_bytes(n) == per.max()        # shipped payload shape
    assert np.all(np.diff(per) > 0)               # monotone in budget
    with pytest.raises(ValueError):
        wire.rank_wire_bytes(n, 5)                # rank-count mismatch
    with pytest.raises(ValueError):
        SparseWire(k_per_block=(4, 0), block_size=64)   # bad budget
    with pytest.raises(ValueError):
        SparseWire(k_per_block=(), block_size=64)       # empty


def test_solve_k_budgets_slow_uplinks_send_less():
    link = LinkProfile(rank_bandwidth_gbps=(10.0, 5.0, 2.5, 20.0))
    n = 1 << 16
    ks = solve_k_budgets(n, 4, link, block_size=512, k_ref=8)
    assert ks == (8, 3, 1, 16)
    # equal-time property: every rank's uplink fits the reference deadline
    wire = SparseWire(k_per_block=ks, block_size=512)
    deadline = link.up_s(SparseWire(k_per_block=8,
                                    block_size=512).wire_bytes(n))
    up = link.up_s_ranks(wire.rank_wire_bytes(n, 4))
    assert np.all(up <= deadline + 1e-12)
    # uniform link reproduces the reference budget on every rank
    assert solve_k_budgets(n, 4, LinkProfile(), block_size=512,
                           k_ref=8) == (8,) * 4
    with pytest.raises(ValueError):
        solve_k_budgets(n + 1, 4, link, block_size=512)
    with pytest.raises(ValueError):
        solve_k_budgets(n, 4, link, deadline_s=0.0)


def test_link_profile_per_rank_validation():
    with pytest.raises(ValueError):
        LinkProfile(rank_bandwidth_gbps=(10.0, -1.0))
    with pytest.raises(ValueError):
        LinkProfile(bandwidth_gbps=0.0)
    link = LinkProfile(rank_bandwidth_gbps=(10.0, 5.0))
    with pytest.raises(ValueError):
        link.up_bandwidths(3)


def test_step_timer_per_rank_wire_and_link_accounting():
    """Phase-1 time = the slowest PARTICIPATING uplink (per-rank bytes over
    per-rank bandwidth); the bytes ledger charges each participant its own
    budgeted bytes."""
    wire = SparseWire(k_per_block=(2, 4, 8, 16), block_size=512)
    n = 4096
    link = LinkProfile(rank_bandwidth_gbps=(1.0, 2.0, 4.0, 8.0),
                       down_bandwidth_gbps=100.0, latency_s=1e-3)
    comp = ComputeProfile(grad_s=2e-3)
    timer = StepTimer(wire=wire, n=n, link=link, compute=comp)
    per = timer.bytes_up_ranks(4)
    up = link.up_s_ranks(per)
    down = link.down_s(timer.bytes_down())
    t_full = timer.step_time([1, 1, 1, 1])
    assert t_full == pytest.approx(2e-3 + up.max() + down)
    # masking out the slowest uplink removes it from the critical path
    slowest = int(np.argmax(up))
    m = np.ones(4)
    m[slowest] = 0.0
    rest = np.delete(up, slowest)
    assert timer.step_time(m) == pytest.approx(2e-3 + rest.max() + down)
    # ledger: each participant charges its own per-rank bytes
    tr = np.array([[1.0, 0.0, 1.0, 1.0]])
    _, b_up, _ = timer.steps(tr)
    assert b_up[0] == per[0] + per[2] + per[3]


def test_cocoef_update_per_rank_budgets_match_oracle():
    """cocoef_update with a per-rank k_per_block tuple must equal the
    manual oracle on a real mesh: each rank packs at k_max, zeroes values
    beyond ITS budget, and the truncation feeds its error vector."""
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from repro.core.cocoef import CocoEFConfig, cocoef_update
    from repro.core.collectives import SparseWire
    n, nd = 512, 4
    ks = (2, 4, 8, 16)
    cfg = CocoEFConfig(coding_axes=("data",), group_size=32,
                       compressor="block_topk", k_per_block=ks,
                       block_size=64, backend="jnp", mode="cocoef")
    mesh = make_mesh((4, 2), ("data", "model"))
    mask = jnp.array([1., 0., 1., 1.])
    g = jax.random.normal(jax.random.PRNGKey(1), (8 * n,))
    e = jax.random.normal(jax.random.PRNGKey(2), (8 * n,)) * 0.1
    gamma = 0.1

    f = shard_map(lambda gg, ee: cocoef_update(gg, ee, mask, gamma, cfg),
                  mesh, in_specs=(P(("data", "model")),) * 2,
                  out_specs=(P(("data", "model")),) * 2,
                  axis_names={"data", "model"})
    ghat, e_new = jax.jit(f)(g, e)

    # oracle: per coding rank, budget-k roundtrip + EF; ghat = masked sum
    wire = SparseWire(k_per_block=ks, block_size=64)
    acc = (gamma * g + e).reshape(nd, 2 * n)      # (rank, local on 2 shards)
    cs, e_ref = [], []
    for i in range(nd):
        c_i = wire.for_rank(i).roundtrip(acc[i])
        cs.append(c_i)
        e_ref.append(jnp.where(mask[i] > 0, acc[i] - c_i,
                               e.reshape(nd, 2 * n)[i]))
    ghat_ref = sum(m * c for m, c in zip(mask, cs))
    ghat2 = np.asarray(ghat).reshape(nd, 2 * n)
    for i in range(nd):
        assert np.allclose(ghat2[i], np.asarray(ghat_ref), atol=1e-5), i
    assert np.allclose(np.asarray(e_new).reshape(nd, 2 * n),
                       np.asarray(jnp.stack(e_ref)), atol=1e-6)

    # a budget tuple shorter than the coding-rank count must raise (jnp's
    # clamped indexing would otherwise silently reuse the last budget)
    bad = CocoEFConfig(coding_axes=("data",), group_size=32,
                       compressor="block_topk", k_per_block=(2, 4),
                       block_size=64, backend="jnp", mode="cocoef")
    fb = shard_map(lambda gg, ee: cocoef_update(gg, ee, mask, gamma, bad),
                   mesh, in_specs=(P(("data", "model")),) * 2,
                   out_specs=(P(("data", "model")),) * 2,
                   axis_names={"data", "model"})
    try:
        jax.jit(fb)(g, e)
        raise AssertionError("short per-rank budget tuple not caught")
    except ValueError as err:
        assert "per-rank budgets" in str(err)
    """, timeout=600)


# ---------------------------------------------------------------------------
# construction-time knob validation (TrainRun / registry / processes)
# ---------------------------------------------------------------------------

def test_train_run_validates_at_construction():
    from repro.launch.train import TrainRun
    TrainRun()                                        # defaults are valid
    with pytest.raises(ValueError):
        TrainRun(mode="nope")
    with pytest.raises(ValueError):
        TrainRun(straggler="bogus")
    with pytest.raises(ValueError):
        TrainRun(straggler_burst=0.5)
    with pytest.raises(ValueError):
        TrainRun(straggler_spread=-0.1)
    with pytest.raises(ValueError):
        TrainRun(backend="tpu")
    with pytest.raises(ValueError):
        TrainRun(num_buckets=0)
    with pytest.raises(ValueError):
        TrainRun(k_budgets=(4, 0, 2))


def test_straggler_knob_validation():
    with pytest.raises(ValueError):
        get_straggler_process("iid", 4, p=1.2)
    with pytest.raises(ValueError):
        IIDBernoulli(num_devices=4, p=-0.1)
    # spread that pushes a p_i out of [0, 1) fails loudly (used to be
    # silently clipped, surfacing later as biased marginals)
    with pytest.raises(ValueError):
        HeterogeneousRates.linear(8, 0.5, spread=1.5)
    with pytest.raises(ValueError):
        HeterogeneousRates.linear(8, 0.5, spread=-0.2)
    with pytest.raises(ValueError):
        get_straggler_process("hetero", 8, 0.6, spread=0.8)  # hi = 1.08
    # still-valid edges keep working
    assert HeterogeneousRates.linear(8, 0.4, spread=1.0).p_ranks[0] == 0.0


# ---------------------------------------------------------------------------
# all-straggler step: ONE semantics, end to end
# ---------------------------------------------------------------------------

def test_all_straggler_step_semantics(rng_key):
    """An all-zero mask row means: the server waits out the slowest
    compute window (timeout), zero uplink seconds AND bytes, the broadcast
    still goes out, the model update is ghat = 0, and every error vector
    is untouched — one definition across timer, trace, and dynamics."""
    rows = np.ones((6, 4))
    rows[2] = 0.0                                 # recorded total outage
    proc = TraceReplay.from_array(rows)
    comp = ComputeProfile(grad_s=3e-3, speed_factors=(1.0, 2.0, 1.0, 4.0))
    timer = StepTimer(wire=SignWire(group_size=32), n=1 << 10, compute=comp)
    tr = proc.sample_trace(rng_key, 6)
    times, b_up, b_down = timer.steps(tr)
    down = timer.link.down_s(timer.bytes_down())
    up = timer.link.up_s(timer.bytes_up())
    assert times[2] == pytest.approx(3e-3 * 4.0 + down)      # timeout+bcast
    assert times[0] == pytest.approx(3e-3 * 4.0 + up + down)
    assert b_up[2] == 0.0                                    # nothing sent
    assert b_down[2] == 4 * timer.bytes_down()               # still bcast

    # dynamics: reference COCO-EF step with the outage mask is a no-op on
    # theta AND on every error vector
    grad_fn_mat = np.random.default_rng(0).normal(size=(4, 6)).astype(
        np.float32)
    grad_fn = lambda th: jnp.asarray(grad_fn_mat) * (1.0 + 0.0 * th.sum())
    alloc = coding.cyclic_allocation(4, 4, 2)
    W = coding.encode_weights(alloc, rates=np.asarray(proc.rates()))
    st = EF.EFState.init(jnp.ones((6,)), 4)
    st = EF.cocoef_step(st, grad_fn, W, jnp.asarray(rows[0]), 0.1,
                        C.GroupedSign(group_size=2), step=0)
    st2 = EF.cocoef_step(st, grad_fn, W, jnp.asarray(rows[2]), 0.1,
                         C.GroupedSign(group_size=2), step=2)
    np.testing.assert_array_equal(np.asarray(st2.theta), np.asarray(st.theta))
    np.testing.assert_array_equal(np.asarray(st2.e), np.asarray(st.e))


def test_all_straggler_step_through_cocoef_update():
    """The production aggregation under an all-zero mask: ghat == 0 and the
    error state bit-for-bit unchanged, on a real mesh."""
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from repro.core.cocoef import CocoEFConfig, cocoef_update
    mesh = make_mesh((4, 2), ("data", "model"))
    n = 1024
    cfg = CocoEFConfig(coding_axes=("data",), group_size=32,
                       compressor="sign", backend="jnp")
    g = jax.random.normal(jax.random.PRNGKey(4), (8 * n,))
    e = jax.random.normal(jax.random.PRNGKey(5), (8 * n,)) * 0.1
    zero = jnp.zeros((4,))
    f = shard_map(lambda gg, ee: cocoef_update(gg, ee, zero, 0.1, cfg),
                  mesh, in_specs=(P(("data", "model")),) * 2,
                  out_specs=(P(("data", "model")),) * 2,
                  axis_names={"data", "model"})
    ghat, e_new = jax.jit(f)(g, e)
    assert np.all(np.asarray(ghat) == 0.0)
    assert np.array_equal(np.asarray(e_new), np.asarray(e))
    """, timeout=600)


# ---------------------------------------------------------------------------
# rate threading through the production setup
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fig9_smoke_rate_aware_no_later(tmp_path):
    """The fig9 acceptance contract: rate-aware COCO-EF reaches the target
    loss NO LATER than mean-rate under every non-iid process, and under
    markov (uniform rates) the two are bit-for-bit the same trajectory."""
    from benchmarks import fig9_hetero_sweep as f9
    res = f9.run(smoke=True, out_dir=tmp_path)
    assert (tmp_path / "fig9.json").exists()
    assert set(res["curves"]) == {"hetero", "markov", "trace"}
    for pname, s in res["summary"].items():
        t = s["time_to_target_s"]
        assert t["rate_aware"] is not None
        assert t["mean_rate"] is None or \
            t["rate_aware"] <= t["mean_rate"] + 1e-9, pname
        # the closed-form weight bias: zero for rate-aware, nonzero for
        # mean-rate exactly when the process is genuinely heterogeneous
        assert s["weight_bias_max"]["rate_aware"] < 1e-5
        if pname != "markov":
            assert s["weight_bias_max"]["mean_rate"] > 0.05
    m = res["curves"]["markov"]
    assert m["rate_aware"]["loss"] == m["mean_rate"]["loss"]


@pytest.mark.slow
def test_build_train_setup_threads_rates():
    """build_train_setup under a hetero process carries the process's
    per-rank rates into CocoEFConfig (rate_aware=True default) and drops
    them with rate_aware=False; k_budgets overrides k_per_block."""
    run_sub("""
    import dataclasses
    from repro.configs import REGISTRY
    from repro.configs.common import ShapeCfg
    from repro.launch.train import TrainRun, build_train_setup
    spec = REGISTRY["olmoe-1b-7b"]
    spec = dataclasses.replace(spec, coding=dataclasses.replace(
        spec.coding, group_size=32, block_size=64, k_per_block=8,
        straggler_p=0.2))
    shape = ShapeCfg("train", seq_len=64, global_batch=16)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

    setup = build_train_setup(spec, mesh, shape,
                              TrainRun(straggler="hetero",
                                       straggler_spread=0.5), smoke=True)
    proc = setup.straggler_process
    rates = setup.cocoef_cfg.straggler_rates
    assert rates is not None and len(rates) == setup.n_code
    np.testing.assert_allclose(rates, proc.rates())
    assert len(set(rates)) > 1            # genuinely per-rank

    off = build_train_setup(spec, mesh, shape,
                            TrainRun(straggler="hetero",
                                     rate_aware=False), smoke=True)
    assert off.cocoef_cfg.straggler_rates is None

    kb = build_train_setup(spec, mesh, shape,
                           TrainRun(compressor="block_topk",
                                    k_budgets=(2, 4, 8, 16)), smoke=True)
    assert kb.cocoef_cfg.k_per_block == (2, 4, 8, 16)
    try:
        build_train_setup(spec, mesh, shape,
                          TrainRun(compressor="block_topk",
                                   k_budgets=(2, 4)), smoke=True)
        raise AssertionError("k_budgets length mismatch not caught")
    except ValueError:
        pass
    try:
        build_train_setup(spec, mesh, shape,
                          TrainRun(k_budgets=(2, 4, 8, 16)), smoke=True)
        raise AssertionError("k_budgets on a non-sparse wire not caught")
    except ValueError:
        pass
    """, timeout=600)
