"""Reference-vs-production Algorithm-1 parity (the fig10 parity gate).

The (N, D) reference EF loop (error_feedback.cocoef_step — what figs. 2-9
train) and the production mesh step (cocoef_update inside shard_map — what
launch.train runs) are two implementations of the paper's Algorithm 1.
These tests train BOTH on the same linreg task / allocation / masks / wire
and demand BIT-FOR-BIT identical theta and error-vector trajectories for a
whole trained run: any drift between the implementations becomes a test
failure instead of a silently wrong figure.

Multi-device (mesh side), so everything runs through the run_sub
subprocess harness of test_distributed."""
import pytest

from test_distributed import run_sub


def test_reference_vs_mesh_parity_sign_quick():
    """Fast tier-1 signal: the sign wire (the paper's compressor) stays
    bit-for-bit over a short trained run."""
    run_sub("""
    from repro.launch.parity import assert_parity, run_parity
    rep = run_parity("sign", T=10)
    assert_parity(rep)
    assert rep["loss_ref"] < rep["loss_start"], rep
    """, timeout=600)


@pytest.mark.parametrize("comp", ["sign", "block_topk", "identity"])
def test_one_rank_cocoef_matches_reference_loop(comp):
    """One coding rank (the one-chip train step) runs Algorithm 1 itself,
    error feedback and the wire over a size-1 all_to_all, and never
    straggles: bit-for-bit the N=1 reference EF loop, on both backends."""
    run_sub(f"""
    from repro.launch.parity import assert_parity, run_parity
    for backend in ("jnp", "pallas"):
        rep = run_parity({comp!r}, T=10, N=1, shards=1, d=1, p=0.0,
                         backend=backend)
        assert_parity(rep)
        assert rep["loss_ref"] < rep["loss_start"], (backend, rep)
    """, devices=1, timeout=600)


def test_dynamic_coding_state_parity_sign_quick():
    """Tier-1 elastic-plane gate: with the rate estimate pinned to the
    oracle rates, the dynamic CodingState trajectory (W recomputed by
    maybe_replan every step, fed as a jit argument) is bit-for-bit the
    static trajectory."""
    run_sub("""
    from repro.launch.parity import assert_parity, run_parity
    rep = run_parity("sign", T=10, dynamic_state=True)
    assert_parity(rep)
    assert rep["dynamic_state"], rep
    """, timeout=600)


@pytest.mark.slow
def test_dynamic_coding_state_parity_all_wires_schedules():
    """The elastic acceptance criterion in full: every parity wire x
    backend x bucket schedule stays bit-for-bit with the dynamic
    CodingState path."""
    run_sub("""
    from repro.launch.parity import (PARITY_COMPRESSORS, assert_parity,
                                     run_parity)
    for comp in PARITY_COMPRESSORS:
        rep = run_parity(comp, T=15, dynamic_state=True)
        assert_parity(rep)
    for comp in ("sign", "block_topk"):
        rep = run_parity(comp, T=8, backend="pallas", dynamic_state=True)
        assert_parity(rep)
        for sched in ("serial", "pipelined"):
            rep = run_parity(comp, T=8, num_buckets=2,
                             bucket_schedule=sched, dynamic_state=True)
            assert_parity(rep)
    """, timeout=900)


@pytest.mark.slow
def test_reference_vs_mesh_parity_all_wires_trained_run():
    """The full gate: sign / block_topk / dense (identity) wires, 25-step
    trained run, theta AND error vectors bit-for-bit at every step, with
    the loss actually decreasing (a trained run, not a fixed point)."""
    run_sub("""
    from repro.launch.parity import (PARITY_COMPRESSORS, assert_parity,
                                     run_parity)
    for comp in PARITY_COMPRESSORS:
        rep = run_parity(comp, T=25)
        assert_parity(rep)
        assert rep["loss_ref"] < rep["loss_start"], (comp, rep)
        assert rep["loss_mesh"] == rep["loss_ref"], (comp, rep)
    """, timeout=900)


@pytest.mark.slow
def test_parity_holds_on_pallas_backend():
    """The gate also holds with the mesh side running the Pallas kernels
    (interpret mode on CPU) — reference == jnp == pallas, one Algorithm 1
    across every execution backend."""
    run_sub("""
    from repro.launch.parity import assert_parity, run_parity
    for comp in ("sign", "block_topk"):
        rep = run_parity(comp, T=10, backend="pallas")
        assert_parity(rep)
    """, timeout=900)


@pytest.mark.slow
def test_parity_holds_through_bucketed_schedules():
    """The gate also holds with the mesh side splitting the flat vector
    into buckets, under BOTH issue orders: the (unbucketed) reference EF
    loop == the bucketed mesh step, serial or pipelined — overlap is a
    pure reordering, never a numerics change."""
    run_sub("""
    from repro.launch.parity import assert_parity, run_parity
    for comp in ("sign", "block_topk"):
        for sched in ("serial", "pipelined"):
            rep = run_parity(comp, T=10, num_buckets=2,
                             bucket_schedule=sched)
            assert_parity(rep)
            assert rep["loss_ref"] < rep["loss_start"], (comp, sched, rep)
    """, timeout=900)
