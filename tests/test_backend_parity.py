"""Backend-dispatch parity: backend="pallas" (interpret mode on CPU) must
match backend="jnp" bit-for-bit through the fused wire entry points, the
coded collective and cocoef_update, and the fused path must lower fewer
full-vector HBM round-trips than the unfused reference sequence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.collectives import DenseWire, SignWire, SparseWire
from repro.kernels import ref
from repro.launch import hlo_cost
from test_distributed import run_sub

WIRES = [
    pytest.param(SignWire(group_size=32), id="sign32"),
    pytest.param(SignWire(group_size=128), id="sign128"),
    pytest.param(SparseWire(k_per_block=4, block_size=64), id="sparse4of64"),
    pytest.param(SparseWire(k_per_block=8, block_size=128,
                            value_dtype="bfloat16"), id="sparse8of128bf16"),
    pytest.param(DenseWire(), id="dense_f32"),
    pytest.param(DenseWire(value_dtype="bfloat16"), id="dense_bf16"),
]


def _assert_trees_equal(a, b, ctx=""):
    for i, (x, y) in enumerate(zip(jax.tree.leaves(a), jax.tree.leaves(b))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{ctx} leaf {i}")


# ---------------------------------------------------------------------------
# single-device: fused_local_step / fused_pack / decode_reduce bit-for-bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("mask_self", [0.0, 1.0])
def test_fused_local_step_backends_agree(wire, mask_self):
    n = 16 * 128 * 2          # large enough to engage the Pallas tiles
    g = jax.random.normal(jax.random.PRNGKey(0), (n,))
    e = jax.random.normal(jax.random.PRNGKey(1), (n,)) * 0.1

    def step(up):
        return jax.jit(lambda gg, ee: wire.fused_local_step(
            gg, ee, 0.05, mask_self, use_pallas=up))(g, e)

    _assert_trees_equal(step(False), step(True), type(wire).__name__)


@pytest.mark.parametrize("wire", WIRES)
def test_fused_pack_and_decode_reduce_backends_agree(wire):
    n, n_senders = 16 * 128, 4
    xs = jax.random.normal(jax.random.PRNGKey(2), (n_senders, n))
    mask = (jnp.arange(n_senders) % 2).astype(jnp.float32)

    def both(up):
        pk = jax.jit(lambda x: wire.fused_pack(x, use_pallas=up))
        payloads = tuple(jnp.stack(ps) for ps in
                         zip(*[tuple(pk(x)) for x in xs]))
        out = jax.jit(lambda *p: wire.decode_reduce(p, mask, use_pallas=up)
                      )(*payloads)
        return payloads + (out,)

    _assert_trees_equal(both(False), both(True), type(wire).__name__)


# ---------------------------------------------------------------------------
# end-to-end: cocoef_update + coded collective, every wire x mask x buckets
# ---------------------------------------------------------------------------

def test_backend_parity_cocoef_update_sweep():
    """backend="pallas" == backend="jnp" bit-for-bit through cocoef_update
    (fused local step + two-phase coded collective) for every compressor
    x straggler mask x num_buckets, on an 8-device mesh."""
    run_sub("""
    import dataclasses
    from jax.sharding import PartitionSpec as P
    from repro.core.cocoef import CocoEFConfig, cocoef_update
    mesh = make_mesh((4, 2), ("data", "model"))
    masks = [jnp.ones((4,)), jnp.array([1., 0., 1., 1.]),
             jnp.array([0., 0., 1., 0.])]
    n = 2048   # per-device flat: multiple of 4 chunks * 64 block * 4 buckets
    gamma = 0.1
    g = jax.random.normal(jax.random.PRNGKey(2), (8 * n,))
    e = jax.random.normal(jax.random.PRNGKey(3), (8 * n,)) * 0.1
    cases = [("sign", "float32"), ("block_topk", "float32"),
             ("block_topk", "bfloat16"), ("topk", "float32"),
             ("identity", "float32")]
    for comp, wdt in cases:
        for num_buckets in (1, 4):
            outs = {}
            for backend in ("jnp", "pallas"):
                ccfg = CocoEFConfig(coding_axes=("data",), group_size=32,
                                    compressor=comp, block_size=64,
                                    k_per_block=4, topk_k=64,
                                    wire_dtype=wdt, num_buckets=num_buckets,
                                    backend=backend)
                f = shard_map(lambda gg, ee, mm: cocoef_update(
                                  gg, ee, mm, gamma, ccfg),
                              mesh, in_specs=(P(("data", "model")),) * 2
                              + (P(),),
                              out_specs=(P(("data", "model")),) * 2,
                              axis_names={"data", "model"})
                jf = jax.jit(f)
                outs[backend] = [jf(g, e, mask) for mask in masks]
            for (g1, e1), (g2, e2) in zip(outs["jnp"], outs["pallas"]):
                assert np.array_equal(np.asarray(g1), np.asarray(g2)), \
                    ("ghat", comp, wdt, num_buckets)
                assert np.array_equal(np.asarray(e1), np.asarray(e2)), \
                    ("e_new", comp, wdt, num_buckets)
    """, timeout=900)


def test_backend_parity_coco_mode():
    """coco (no-EF) routes through fused_pack: backends agree bit-for-bit."""
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from repro.core.cocoef import CocoEFConfig, cocoef_update
    mesh = make_mesh((4, 2), ("data", "model"))
    n = 2048
    g = jax.random.normal(jax.random.PRNGKey(4), (8 * n,))
    e = jnp.zeros((8 * n,))
    mask = jnp.array([1., 0., 1., 1.])
    for comp in ("sign", "block_topk"):
        outs = []
        for backend in ("jnp", "pallas"):
            ccfg = CocoEFConfig(coding_axes=("data",), group_size=32,
                                compressor=comp, block_size=64, k_per_block=4,
                                mode="coco", backend=backend)
            f = shard_map(lambda gg, ee: cocoef_update(gg, ee, mask, 0.1,
                                                       ccfg),
                          mesh, in_specs=(P(("data", "model")),) * 2,
                          out_specs=(P(("data", "model")),) * 2,
                          axis_names={"data", "model"})
            outs.append(jax.jit(f)(g, e))
        assert np.array_equal(np.asarray(outs[0][0]), np.asarray(outs[1][0])), comp
        assert np.array_equal(np.asarray(outs[0][1]), np.asarray(outs[1][1])), comp
    """, timeout=600)


# ---------------------------------------------------------------------------
# HLO cost: the fused path lowers fewer full-vector HBM round-trips
# ---------------------------------------------------------------------------

def _fullvec_writes(n, fn, *args):
    """Full-vector HBM round-trips of a jitted fn: executed ops in the
    ENTRY computation of the optimized HLO (hlo_cost's execution units)
    whose result materializes an f32 tensor of exactly n elements."""
    import math
    txt = jax.jit(fn).lower(*args).compile().as_text()
    comps = hlo_cost.parse_computations(txt)
    entry = None
    for raw in txt.splitlines():
        if raw.startswith("ENTRY"):
            entry = hlo_cost._COMP_HDR.match(raw.strip()).group(1)
            break
    cnt = 0
    for op in comps[entry].ops:
        if op.kind in hlo_cost._SKIP_KINDS:
            continue
        for dt, dims in hlo_cost._arrays(op.rtype):
            if dt == "f32" and math.prod(dims) == n:
                cnt += 1
    return cnt


def test_fused_local_step_fewer_hbm_roundtrips():
    """The fused local step must materialize fewer full-vector f32 tensors
    than the pre-backend-layer reference trace (accumulate, pack, unpack
    for c, error-update), both at equal jit scope and against the
    separately-jitted stage pipeline (whose jit boundaries each force a
    full-vector HBM round-trip)."""
    n, group = 1 << 22, 512
    gamma, mask_self = 0.01, 1.0
    g = jax.ShapeDtypeStruct((n,), jnp.float32)
    e = jax.ShapeDtypeStruct((n,), jnp.float32)

    fused = _fullvec_writes(
        n, lambda gg, ee: ref.ef_sign_fused_ref(gg, ee, gamma, mask_self,
                                                group), g, e)

    def old_local_step(gg, ee):      # the pre-PR cocoef_update local trace
        acc = gamma * gg + ee
        w, s = ref.sign_pack_ref(acc, group)
        c = ref.sign_unpack_ref(w, s, group)
        return w, s, c, jnp.where(mask_self > 0, acc - c, ee)

    reference = _fullvec_writes(n, old_local_step, g, e)
    assert fused < reference, (fused, reference)

    acc_t = jax.ShapeDtypeStruct((n,), jnp.float32)
    w_t = jax.ShapeDtypeStruct((n // 32,), jnp.uint32)
    s_t = jax.ShapeDtypeStruct((n // group,), jnp.float32)
    staged = (
        _fullvec_writes(n, lambda gg, ee: gamma * gg + ee, g, e)
        + _fullvec_writes(n, lambda a: ref.sign_pack_ref(a, group), acc_t)
        + _fullvec_writes(n, lambda w, s: ref.sign_unpack_ref(w, s, group),
                          w_t, s_t)
        + _fullvec_writes(n, lambda a, c, ee: jnp.where(mask_self > 0, a - c,
                                                        ee), acc_t, acc_t, e))
    assert fused < staged, (fused, staged)


def test_coco_mode_drops_dead_c_concat():
    """mode="coco" never materializes the reconstruction c: its traced
    program has exactly one full-vector concatenate per ghat (the bucket
    join) and no second one for c, and moves fewer bytes than cocoef."""
    run_sub("""
    import re
    from jax.sharding import PartitionSpec as P
    from repro.core.cocoef import CocoEFConfig, cocoef_update
    from repro.launch import hlo_cost
    mesh = make_mesh((4, 2), ("data", "model"))
    n = 2048
    mask = jnp.ones((4,))
    gs = jax.ShapeDtypeStruct((8 * n,), jnp.float32)
    def lowered(mode):
        ccfg = CocoEFConfig(coding_axes=("data",), group_size=32,
                            compressor="sign", mode=mode, num_buckets=4,
                            backend="jnp")
        f = shard_map(lambda gg, ee: cocoef_update(gg, ee, mask, 0.1, ccfg),
                      mesh, in_specs=(P(("data", "model")),) * 2,
                      out_specs=(P(("data", "model")),) * 2,
                      axis_names={"data", "model"})
        return jax.jit(f).lower(gs, gs)
    # trace-level: full-vector (512 = n/4 buckets) f32 concatenates
    def full_concats(low):
        txt = low.as_text()
        return len([l for l in txt.splitlines()
                    if "stablehlo.concatenate" in l
                    and re.search(r"-> tensor<2048xf32>", l)])
    n_coco = full_concats(lowered("coco"))
    n_cocoef = full_concats(lowered("cocoef"))
    assert n_coco == 1, n_coco            # ghat join only — no dead c join
    assert n_cocoef == 2, n_cocoef        # ghat join + new-error join
    # compiled: coco moves strictly fewer HBM bytes than cocoef
    b_coco = hlo_cost.analyze(lowered("coco").compile().as_text(), 8).bytes
    b_cocoef = hlo_cost.analyze(lowered("cocoef").compile().as_text(), 8).bytes
    assert b_coco < b_cocoef, (b_coco, b_cocoef)
    """, timeout=600)


# ---------------------------------------------------------------------------
# bucket schedule: pipelined (double-buffered overlap) == serial, bitwise
# ---------------------------------------------------------------------------

def test_schedule_parity_serial_vs_pipelined():
    """bucket_schedule="pipelined" issues bucket i's collective before
    running bucket i+1's fused local step (compute/comm overlap); it is
    the SAME ops in a different issue order, so it must stay bit-for-bit
    equal to the serial schedule for every mode x wire x wire-dtype x
    mask — including a total outage."""
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from repro.core.cocoef import CocoEFConfig, cocoef_update
    mesh = make_mesh((4, 2), ("data", "model"))
    n = 2048   # per-device flat: multiple of 4 chunks * 64 block * 4 buckets
    gamma = 0.1
    g = jax.random.normal(jax.random.PRNGKey(6), (8 * n,))
    e = jax.random.normal(jax.random.PRNGKey(7), (8 * n,)) * 0.1
    masks = [jnp.ones((4,)), jnp.array([1., 0., 1., 1.]), jnp.zeros((4,))]
    cases = [("cocoef", "sign", "float32"),
             ("cocoef", "block_topk", "float32"),
             ("cocoef", "block_topk", "bfloat16"),
             ("coco", "sign", "float32")]
    for mode, comp, wdt in cases:
        outs = {}
        for sched in ("serial", "pipelined"):
            ccfg = CocoEFConfig(coding_axes=("data",), group_size=32,
                                compressor=comp, block_size=64,
                                k_per_block=4, wire_dtype=wdt, mode=mode,
                                num_buckets=4, bucket_schedule=sched,
                                backend="jnp")
            f = shard_map(lambda gg, ee, mm: cocoef_update(
                              gg, ee, mm, gamma, ccfg),
                          mesh, in_specs=(P(("data", "model")),) * 2
                          + (P(),),
                          out_specs=(P(("data", "model")),) * 2,
                          axis_names={"data", "model"})
            jf = jax.jit(f)
            outs[sched] = [jf(g, e, m) for m in masks]
        for (g1, e1), (g2, e2) in zip(outs["serial"], outs["pipelined"]):
            assert np.array_equal(np.asarray(g1), np.asarray(g2)), \
                ("ghat", mode, comp, wdt)
            assert np.array_equal(np.asarray(e1), np.asarray(e2)), \
                ("e_new", mode, comp, wdt)
    """, timeout=900)


# ---------------------------------------------------------------------------
# dynamic coding plane: in-graph W fold == host-side W fold, bitwise
# ---------------------------------------------------------------------------

def test_elastic_weight_fold_matches_host_fold_bitwise():
    """The elastic step's in-graph per-example weights
    (take_along_axis(W/per_subset, subset_ids), scaled W a jit ARGUMENT)
    must be bit-for-bit the static batch maker's host-side numpy fold
    (W[i, sids] / per_subset baked into the batch).  The 1/per_subset
    division happens on the HOST on both sides — an in-graph
    divide-by-constant is strength-reduced by XLA to a reciprocal
    multiply, which this test catches for non-pow2 per_subset (3, 5)."""
    from repro.core import coding
    from repro.data import pipeline

    rng = np.random.default_rng(0)
    for N, d, per_subset in [(8, 2, 4), (8, 2, 3), (6, 3, 5)]:
        q = rng.uniform(0.3, 1.0, N)
        alloc = coding.rate_aware_allocation(q, N, d, exact_load=True)
        W = coding.encode_weights(alloc, rates=q)
        toks_s, wts_s = pipeline.coded_train_batch(
            jax.random.PRNGKey(1), 3, alloc, W, per_subset, 16, 97)
        toks_e, wts_e, sids = pipeline.elastic_train_batch(
            jax.random.PRNGKey(1), 3, alloc, per_subset, 16, 97)
        assert np.array_equal(np.asarray(toks_s), np.asarray(toks_e))
        W_scaled = jnp.asarray(np.asarray(W) / per_subset)

        @jax.jit
        def fold(Wt, sids, base):
            return base * jnp.take_along_axis(Wt, sids, axis=1)

        folded = fold(W_scaled, sids, wts_e)
        assert np.array_equal(np.asarray(folded), np.asarray(wts_s)), \
            (N, d, per_subset)
