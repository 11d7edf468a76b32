"""Cluster-sim subsystem: straggler-process statistics (marginals,
burst-length law, determinism), the legacy bit-for-bit regression through
the cocoef_update mask-provider hook, the wire-aware cost model, and the
wire_bytes single-source-of-truth audit."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coding
from repro.core.collectives import DenseWire, SignWire, SparseWire
from repro.sim import (ComputeProfile, HeterogeneousRates, IIDBernoulli,
                       LinkProfile, MarkovBursty, StepTimer, TraceReplay,
                       get_straggler_process, simulate_run, time_to_target)
from test_distributed import run_sub

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # benchmarks/


# ---------------------------------------------------------------------------
# IIDBernoulli: the legacy eq.-(8) model, bit for bit
# ---------------------------------------------------------------------------

def test_iid_reproduces_legacy_mask_bit_for_bit(rng_key):
    N, p = 24, 0.3
    proc = IIDBernoulli(num_devices=N, p=p)
    for t in (0, 1, 7, 1234):
        np.testing.assert_array_equal(
            np.asarray(proc.mask(rng_key, t)),
            np.asarray(coding.straggler_mask(rng_key, t, N, p)))
    # traced step index too (the train path passes a traced scalar)
    m = jax.jit(lambda s: proc.mask(rng_key, s))(jnp.int32(7))
    np.testing.assert_array_equal(
        np.asarray(m), np.asarray(coding.straggler_mask(rng_key, 7, N, p)))


def test_iid_through_cocoef_update_hook_bit_for_bit():
    """cocoef_update(mask=None, mask_provider=IIDBernoulli.mask) must equal
    the legacy explicit-mask path exactly — ghat AND the new error state —
    on a real multi-device mesh, for several steps."""
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from repro.core.cocoef import CocoEFConfig, cocoef_update
    from repro.core import coding
    from repro.sim import IIDBernoulli
    mesh = make_mesh((4, 2), ("data", "model"))
    n, p = 1024, 0.4
    key = jax.random.PRNGKey(3)
    g = jax.random.normal(jax.random.PRNGKey(4), (8 * n,))
    e = jax.random.normal(jax.random.PRNGKey(5), (8 * n,)) * 0.1
    ccfg = CocoEFConfig(coding_axes=("data",), group_size=32,
                        compressor="sign", backend="jnp")
    proc = IIDBernoulli(num_devices=4, p=p)
    legacy = shard_map(
        lambda gg, ee, ss: cocoef_update(
            gg, ee, coding.straggler_mask(key, ss, 4, p), 0.1, ccfg),
        mesh, in_specs=(P(("data", "model")),) * 2 + (P(),),
        out_specs=(P(("data", "model")),) * 2,
        axis_names={"data", "model"})
    hooked = shard_map(
        lambda gg, ee, ss: cocoef_update(
            gg, ee, None, 0.1, ccfg, mask_provider=proc.mask, key=key,
            step=ss),
        mesh, in_specs=(P(("data", "model")),) * 2 + (P(),),
        out_specs=(P(("data", "model")),) * 2,
        axis_names={"data", "model"})
    jl, jh = jax.jit(legacy), jax.jit(hooked)
    for t in (0, 3, 17):
        (g1, e1), (g2, e2) = jl(g, e, jnp.int32(t)), jh(g, e, jnp.int32(t))
        assert np.array_equal(np.asarray(g1), np.asarray(g2)), t
        assert np.array_equal(np.asarray(e1), np.asarray(e2)), t
    """, timeout=600)


# ---------------------------------------------------------------------------
# marginal participation rates
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make,rank_atol", [
    pytest.param(lambda: IIDBernoulli(num_devices=16, p=0.3), 0.035,
                 id="iid"),
    # bursts correlate consecutive steps -> ~mean_burst x fewer effective
    # samples per rank, hence the looser per-rank tolerance
    pytest.param(lambda: MarkovBursty(num_devices=16, p=0.3, mean_burst=6.0),
                 0.12, id="markov"),
    pytest.param(lambda: HeterogeneousRates.linear(16, 0.3, spread=0.5),
                 0.035, id="hetero"),
])
def test_empirical_participation_matches_marginal(make, rank_atol, rng_key):
    proc = make()
    T = 3000
    tr = proc.sample_trace(rng_key, T)
    assert tr.shape == (T, 16)
    assert set(np.unique(tr)) <= {0.0, 1.0}
    np.testing.assert_allclose(tr.mean(axis=0), proc.rates(), atol=rank_atol)
    # fleet-wide marginal is tight for every process
    assert abs(tr.mean() - proc.rates().mean()) < 0.03


def test_hetero_per_rank_profile(rng_key):
    proc = HeterogeneousRates.linear(8, 0.4, spread=1.0)
    # p_i spans 0 .. 0.8 linearly: rank 0 never straggles, rank 7 often
    assert proc.p_ranks[0] == 0.0 and proc.p_ranks[-1] == pytest.approx(0.8)
    tr = proc.sample_trace(rng_key, 4000)
    rates = tr.mean(axis=0)
    assert rates[0] == 1.0
    assert np.all(np.diff(proc.rates()) < 0)           # monotone profile
    np.testing.assert_allclose(rates, proc.rates(), atol=0.05)
    two = HeterogeneousRates.two_class(8, p_slow=0.5, slow_fraction=0.25)
    assert two.p_ranks == (0.5, 0.5) + (0.0,) * 6


# ---------------------------------------------------------------------------
# MarkovBursty: burst structure
# ---------------------------------------------------------------------------

def _run_lengths(slow_col):
    runs, n = [], 0
    for v in slow_col:
        if v:
            n += 1
        elif n:
            runs.append(n)
            n = 0
    if n:
        runs.append(n)
    return runs


def test_markov_run_lengths_geometric(rng_key):
    burst = 6.0
    proc = MarkovBursty(num_devices=32, p=0.25, mean_burst=burst)
    tr = proc.sample_trace(rng_key, 4000)
    runs = np.array(sum((_run_lengths(1.0 - col) for col in tr.T), []))
    assert runs.size > 2000
    # Geometric(q = 1/burst): mean 1/q, survival P(L > k) = (1-q)^k
    assert abs(runs.mean() - burst) / burst < 0.15
    q = 1.0 / burst
    for k in range(1, 6):
        emp = (runs > k).mean()
        assert abs(emp - (1 - q) ** k) < 0.08, (k, emp)


def test_markov_mask_pure_and_jittable(rng_key):
    proc = MarkovBursty(num_devices=8, p=0.2, mean_burst=8.0)
    m1 = np.asarray(proc.mask(rng_key, 55))
    m2 = np.asarray(proc.mask(rng_key, 55))
    np.testing.assert_array_equal(m1, m2)
    m3 = np.asarray(jax.jit(lambda s: proc.mask(rng_key, s))(jnp.int32(55)))
    np.testing.assert_array_equal(m1, m3)
    # the sampled trace IS the per-step mask sequence (shared trace between
    # training dynamics and the cost model)
    tr = proc.sample_trace(rng_key, 60)
    np.testing.assert_array_equal(tr[55], m1)


def test_markov_rejects_infeasible_burst():
    with pytest.raises(ValueError):
        MarkovBursty(num_devices=4, p=0.9, mean_burst=1.5)


# ---------------------------------------------------------------------------
# TraceReplay: determinism + JSON roundtrip
# ---------------------------------------------------------------------------

def test_trace_replay_deterministic_and_cyclic(tmp_path):
    rows = np.array([[1, 0, 1], [0, 1, 1], [1, 1, 0], [1, 1, 1]])
    proc = TraceReplay.from_array(rows)
    # key-independent: every device/host derives the identical mask
    for t in range(8):
        a = np.asarray(proc.mask(jax.random.PRNGKey(0), t))
        b = np.asarray(proc.mask(jax.random.PRNGKey(999), t))
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, rows[t % 4])
    np.testing.assert_allclose(proc.rates(), rows.mean(0))
    # JSON roundtrip through the registry
    path = proc.to_json(tmp_path / "trace.json")
    again = get_straggler_process("trace", 3, trace=path)
    assert again == proc
    with pytest.raises(ValueError):
        get_straggler_process("trace", 5, trace=path)   # device mismatch
    with pytest.raises(ValueError):
        get_straggler_process("trace", 3)               # no path


def test_registry_names():
    assert isinstance(get_straggler_process("iid", 4, 0.1), IIDBernoulli)
    assert isinstance(get_straggler_process("markov", 4, 0.1), MarkovBursty)
    assert isinstance(get_straggler_process("hetero", 4, 0.1),
                      HeterogeneousRates)
    with pytest.raises(KeyError):
        get_straggler_process("nope", 4)


# ---------------------------------------------------------------------------
# cost model: wire-aware step times + ledger
# ---------------------------------------------------------------------------

def test_step_timer_wire_aware_ordering():
    """Compressed wires must yield strictly faster simulated steps than the
    dense f32 wire at production scale — the premise of fig8."""
    n = 1 << 22
    full = np.ones(8)
    t_sign = StepTimer(wire=SignWire(group_size=512), n=n).step_time(full)
    t_topk = StepTimer(wire=SparseWire(k_per_block=8, block_size=512),
                       n=n).step_time(full)
    t_dense = StepTimer(wire=DenseWire(), n=n).step_time(full)
    assert t_sign < t_dense and t_topk < t_dense


def test_step_timer_accounting_and_cutoff():
    link = LinkProfile(bandwidth_gbps=10.0, down_bandwidth_gbps=100.0,
                       latency_s=1e-3, server_fanin=0)
    comp = ComputeProfile(grad_s=4e-3, speed_factors=(1.0, 2.0, 1.0, 4.0))
    timer = StepTimer(wire=SignWire(group_size=512), n=1 << 20, link=link,
                      compute=comp)
    assert timer.bytes_up() == SignWire(group_size=512).wire_bytes(1 << 20)
    up = link.up_s(timer.bytes_up())
    down = link.down_s(timer.bytes_down())
    # straggler cutoff: masking out the slowest rank removes its compute
    t_all = timer.step_time([1, 1, 1, 1])
    t_cut = timer.step_time([1, 1, 1, 0])
    assert t_all == pytest.approx(4e-3 * 4.0 + up + down)
    assert t_cut == pytest.approx(4e-3 * 2.0 + up + down)
    assert t_cut < t_all
    # an all-straggler step burns the full compute window
    assert timer.step_time([0, 0, 0, 0]) == pytest.approx(
        4e-3 * 4.0 + down)
    # server fan-in serializes uplink waves
    fanin = StepTimer(wire=SignWire(group_size=512), n=1 << 20,
                      link=LinkProfile(bandwidth_gbps=10.0, latency_s=1e-3,
                                       server_fanin=2), compute=comp)
    assert fanin.step_time([1, 1, 1, 1]) == pytest.approx(
        4e-3 * 4.0 + 2 * fanin.link.up_s(fanin.bytes_up())
        + fanin.link.down_s(fanin.bytes_down()))


def test_simulate_run_ledger(rng_key):
    n = 1 << 20
    wire = SignWire(group_size=512)
    proc = IIDBernoulli(num_devices=8, p=0.25)
    timer = StepTimer(wire=wire, n=n)
    sim = simulate_run(proc, timer, 50, rng_key)
    assert sim.step_time_s.shape == (50,)
    assert np.all(np.diff(sim.cum_time_s) > 0)
    # ledger: uplink bytes = participants x wire_bytes(n), per step
    np.testing.assert_array_equal(
        sim.bytes_up, sim.participants * wire.wire_bytes(n))
    at = sim.at_steps([0, 49])
    assert at["time_s"][1] == pytest.approx(sim.total_time_s)
    assert at["bytes_up_cum"][1] == pytest.approx(sim.bytes_up.sum())


def test_time_to_target_interpolates():
    assert time_to_target([0.0, 1.0, 2.0], [4.0, 2.0, 1.0], 3.0) \
        == pytest.approx(0.5)
    assert time_to_target([0.0, 1.0], [4.0, 2.0], 4.5) == pytest.approx(0.0)
    assert time_to_target([0.0, 1.0], [4.0, 2.0], 1.0) is None


# ---------------------------------------------------------------------------
# wire_bytes single source of truth (ISSUE 3 audit)
# ---------------------------------------------------------------------------

def test_wire_bytes_audit_single_source_of_truth():
    """comm_volume's table, the packed payloads the collective transmits,
    and the cost model's uplink accounting all read the same
    WireFormat.wire_bytes."""
    from benchmarks import comm_volume
    audited = comm_volume.audit_wire_bytes()
    # every uniform wire in the table + the per-rank-budget sparse wire
    assert len(audited) == len(comm_volume.WIRE_TABLE) + 1
    # and the table rows themselves are wire_bytes verbatim
    for (name, nbytes, _), (_, wire) in zip(comm_volume.run_wires(),
                                            comm_volume.WIRE_TABLE):
        assert nbytes == wire.wire_bytes(comm_volume.N_MODEL), name


# ---------------------------------------------------------------------------
# fig8 smoke: the full (time, loss) pipeline end to end
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fig8_smoke_sign_dominates_dense(tmp_path, monkeypatch):
    from benchmarks import fig8_time_to_accuracy as f8
    monkeypatch.setattr(f8, "OUT", tmp_path)
    res = f8.run(smoke=True)
    assert (tmp_path / "fig8.json").exists()
    out = json.loads((tmp_path / "fig8.json").read_text())
    assert set(out["curves"]) >= {"iid", "markov", "hetero"}
    for pname, curves in out["curves"].items():
        assert set(curves) == set(f8.METHODS)
        for c in curves.values():
            assert len(c["time_s"]) == len(c["loss"]) == len(c["step"])
            assert all(t2 > t1 for t1, t2 in zip(c["time_s"],
                                                 c["time_s"][1:]))
        t2t = out["summary"][pname]["time_to_target_s"]
        # acceptance: COCO-EF(sign) strictly dominates dense SGC in
        # simulated time-to-target under the default link profile
        assert t2t["cocoef_sign"] is not None
        assert t2t["sgc_dense"] is None or \
            t2t["cocoef_sign"] < t2t["sgc_dense"]


# ---------------------------------------------------------------------------
# cost model: bucketed aggregation + overlap-aware pipelined schedule
# ---------------------------------------------------------------------------

def _agg_parts(timer, mask):
    """(compute, aggregation) split of one step for hand-derived checks."""
    t = timer.step_time(mask)
    comp = np.asarray(timer.compute.rank_seconds(len(mask)))
    m = np.asarray(mask, np.float64)
    t_comp = np.max(np.where(m > 0, comp, 0.0)) if m.sum() else comp.max()
    return t_comp, t - t_comp


def test_step_timer_bucket_defaults_reduce_to_old_formula():
    """num_buckets=1 / overlap=False / pack_s=0 (the defaults) must price
    exactly the pre-bucketing model: up + down with one latency each."""
    link = LinkProfile(bandwidth_gbps=10.0, down_bandwidth_gbps=100.0,
                       latency_s=1e-3, server_fanin=0)
    comp = ComputeProfile(grad_s=4e-3)
    wire = SignWire(group_size=512)
    base = StepTimer(wire=wire, n=1 << 20, link=link, compute=comp)
    expect = 4e-3 + link.up_s(base.bytes_up()) + link.down_s(
        base.bytes_down())
    assert base.step_time([1, 1, 1, 1]) == pytest.approx(expect)
    # B=1 makes the overlap flag a no-op by construction
    b1 = StepTimer(wire=wire, n=1 << 20, link=link, compute=comp,
                   num_buckets=1, overlap=True)
    assert b1.step_time([1, 1, 1, 1]) == pytest.approx(expect)


def test_step_timer_serial_buckets_add_per_message_latency_only():
    """Serial bucketing splits the transfers but pays the per-message
    latency once per bucket on each of uplink and downlink: exactly
    2*(B-1)*latency over the single-shot step (fanin off)."""
    link = LinkProfile(bandwidth_gbps=10.0, down_bandwidth_gbps=100.0,
                       latency_s=1e-3, server_fanin=0)
    comp = ComputeProfile(grad_s=4e-3)
    wire = SignWire(group_size=512)
    mask = [1, 1, 1, 1]
    t1 = StepTimer(wire=wire, n=1 << 20, link=link,
                   compute=comp).step_time(mask)
    for B in (2, 4, 8):
        tb = StepTimer(wire=wire, n=1 << 20, link=link, compute=comp,
                       num_buckets=B).step_time(mask)
        assert tb == pytest.approx(t1 + 2 * (B - 1) * link.latency_s)


def test_step_timer_overlap_pays_bottleneck_not_sum():
    """Pipelined schedule: t_agg = fill (one bucket through all stages)
    + (B-1) * bottleneck stage — checked against the closed form, and
    never worse than serial."""
    link = LinkProfile(bandwidth_gbps=10.0, down_bandwidth_gbps=100.0,
                       latency_s=1e-3, server_fanin=0)
    comp = ComputeProfile(grad_s=4e-3)
    wire = SignWire(group_size=512)
    mask = [1, 1, 1, 1]
    pack_s = 2e-3
    for B in (2, 4, 8):
        serial = StepTimer(wire=wire, n=1 << 20, link=link, compute=comp,
                           num_buckets=B, pack_s=pack_s)
        pipe = StepTimer(wire=wire, n=1 << 20, link=link, compute=comp,
                         num_buckets=B, overlap=True, pack_s=pack_s)
        lat = link.latency_s
        pack_b = pack_s / B
        up_b = lat + (link.up_s(serial.bytes_up()) - lat) / B
        down_b = lat + (link.down_s(serial.bytes_down()) - lat) / B
        expect_agg = pack_b + up_b + down_b \
            + (B - 1) * max(pack_b, up_b, down_b)
        _, agg = _agg_parts(pipe, mask)
        assert agg == pytest.approx(expect_agg)
        # overlap can only help: serial == B * (sum of per-bucket stages)
        assert pipe.step_time(mask) < serial.step_time(mask)


def test_step_timer_overlap_all_straggler_still_broadcasts():
    """A total outage under the pipelined schedule keeps the single
    all-straggler semantics: full compute window, ZERO uplink time, and
    the zero-aggregate broadcast still streams bucket by bucket."""
    link = LinkProfile(bandwidth_gbps=10.0, down_bandwidth_gbps=100.0,
                       latency_s=1e-3, server_fanin=0)
    comp = ComputeProfile(grad_s=4e-3)
    wire = SignWire(group_size=512)
    timer = StepTimer(wire=wire, n=1 << 20, link=link, compute=comp,
                      num_buckets=4, overlap=True)
    down_b = link.latency_s + (link.down_s(timer.bytes_down())
                               - link.latency_s) / 4
    assert timer.step_time([0, 0, 0, 0]) == pytest.approx(
        4e-3 + down_b + 3 * down_b)
    # and zero uplink bytes on the ledger, like the single-shot model
    _, b_up, _ = timer.steps(np.zeros((1, 4)))
    assert b_up[0] == 0.0
