"""Reference-vs-production parity gate for Algorithm 1.

The repo carries two implementations of the paper's Algorithm 1:

  * the (N, D) REFERENCE EF loop (`repro.core.error_feedback.cocoef_step`)
    that every paper figure (fig2-fig9) trains through, and
  * the PRODUCTION mesh step (`repro.core.cocoef.cocoef_update` inside the
    fully-manual shard_map of `repro.launch.train`) whose performance the
    kernel/cost-model numbers describe.

Nothing used to tie their dynamics together beyond one-step oracle checks,
so the two could silently diverge and every emitted figure would describe
an algorithm the production system does not run.  This module trains BOTH
on the same linreg task, with the same allocation/encode weights, the same
per-step straggler masks, and the same wire arithmetic — the reference
loop's compressor is `compression.WireCompressor(wire)`, i.e. bit-for-bit
the reconstruction the coded collective's receivers decode — and demands
the theta / error trajectories stay BIT-FOR-BIT identical for the whole
trained run.  Any drift between the two Algorithm-1 implementations is a
test failure (tests/test_algorithm_parity.py) and a benchmark failure
(benchmarks/fig10_model_zoo.py --parity) instead of a wrong figure.

Requires `N * shards` jax devices (set
`XLA_FLAGS=--xla_force_host_platform_device_count=...` before jax
initializes; the tests run this in a subprocess like tests/test_distributed).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import coding, error_feedback as EF
from repro.core.cocoef import CocoEFConfig, cocoef_update
from repro.core.compression import WireCompressor

__all__ = ["PARITY_COMPRESSORS", "run_parity", "assert_parity"]

# the wires the gate covers: sign / block top-K / dense (identity), the
# three wire families of the ISSUE.  Global top-K is excluded by design:
# its per-chunk block layout depends on the all_to_all chunk count, so the
# full-vector reference view and the per-device production view compress
# with different block boundaries (documented approximation).
PARITY_COMPRESSORS = ("sign", "block_topk", "identity")

# sign group 64: at 32, XLA:CPU sums the one-device reference loop's group
# mean (a vmap over a size-1 device axis, fused into its consumers) in
# another order than the mesh step does, one ulp apart
_GROUP, _BLOCK, _K = 64, 64, 4


def _records(theta: jnp.ndarray, e: np.ndarray) -> Dict[str, np.ndarray]:
    return {"theta": np.asarray(theta).copy(), "e": np.asarray(e).copy()}


def run_parity(compressor: str = "sign", T: int = 20, N: int = 4,
               shards: int = 2, dim: int = 1024, gamma: float = 2e-6,
               p: float = 0.25, d: int = 2, seed: int = 0,
               backend: str = "jnp", num_buckets: int = 1,
               bucket_schedule: str = "pipelined",
               dynamic_state: bool = False) -> Dict:
    """Train the reference EF loop and the mesh `cocoef_update` step on the
    same linreg task / masks / wire for `T` steps and compare trajectories.

    Returns a report dict; `bitexact` is True iff theta AND the error
    vectors match bit-for-bit at EVERY recorded step.

    dynamic_state=True runs a THIRD trajectory through the same mesh step
    with the encode weights coming from a live `core.coding_state`
    CodingPlan pinned to the oracle rates — W is recomputed by
    `maybe_replan` every step and fed as a jit ARGUMENT instead of a
    closure constant.  The elastic coding plane's acceptance criterion is
    that this trajectory is bit-for-bit the static one.
    """
    if compressor not in PARITY_COMPRESSORS:
        raise ValueError(f"parity covers {PARITY_COMPRESSORS}, "
                         f"got {compressor!r}")
    from repro.data import tasks   # lazy: keeps launch import-light

    n_loc = dim // shards
    ccfg = CocoEFConfig(coding_axes=("data",), group_size=_GROUP,
                        compressor=compressor, block_size=_BLOCK,
                        k_per_block=_K, backend=backend,
                        num_buckets=num_buckets,
                        bucket_schedule=bucket_schedule)
    wire = ccfg.wire_format(n_loc, N)
    wire.check(n_loc, N)               # dim must need no padding: the
    #   reference loop compresses the raw (dim,) vector, so any pad would
    #   change the group/block partition between the two sides
    comp = WireCompressor(wire=wire)

    grad_fn, loss_fn, theta0, _ = tasks.linreg_task(
        seed=seed, num_subsets=N, dim=dim)
    alloc = coding.cyclic_allocation(N, N, d)
    W = coding.encode_weights(alloc, p)

    mask_key = jax.random.PRNGKey(1000 + seed)
    masks = [coding.straggler_mask(mask_key, t, N, p) for t in range(T)]

    # ---- reference: the (N, D) EF loop of figs. 2-9 -----------------------
    st = EF.EFState.init(theta0, N)
    ref: List[Dict[str, np.ndarray]] = []
    for t in range(T):
        st = EF.cocoef_step(st, grad_fn, W, masks[t], gamma, comp, step=t)
        ref.append(_records(st.theta, st.e))

    # ---- production: cocoef_update inside shard_map on a (N, shards) mesh -
    mesh = compat.make_mesh((N, shards), ("data", "model"))

    def agg(gg, ee, mm):
        return cocoef_update(gg, ee, mm, gamma, ccfg)

    step_fn = jax.jit(compat.shard_map(
        agg, mesh,
        in_specs=(P(("data", "model")), P(("data", "model")), P()),
        out_specs=(P("model"), P(("data", "model"))),
        axis_names={"data", "model"}))
    coded = jax.jit(lambda th: W @ grad_fn(th))      # (N, dim), same eq. 3

    theta = np.asarray(theta0)
    e_flat = np.zeros((N * dim,), np.float32)
    mesh_rec: List[Dict[str, np.ndarray]] = []
    for t in range(T):
        # theta/e stay host-side between steps: feeding the sharded step
        # outputs back into `coded` would GSPMD-partition the stage-1
        # matmul and change its reduction order (not what the production
        # loop does either — stage 1 recomputes from replicated params)
        g = coded(jnp.asarray(theta))
        ghat, e_out = step_fn(g.reshape(-1), jnp.asarray(e_flat), masks[t])
        theta = theta - np.asarray(ghat)
        e_flat = np.asarray(e_out)
        mesh_rec.append(_records(theta, e_flat.reshape(N, dim)))

    # ---- dynamic CodingState: W from maybe_replan, as a jit argument ------
    dyn_rec: List[Dict[str, np.ndarray]] = []
    if dynamic_state:
        from repro.core.coding_state import CodingPlan, maybe_replan
        # oracle rates of the iid Bernoulli process: uniform 1-p, which
        # hits encode_weights' eq.-3 branch -> W identical to the static
        # encode_weights(alloc, p) above, every step, bit-for-bit
        oracle = np.full((N,), 1.0 - p)
        plan = CodingPlan.create(oracle, N, d, allocation=alloc)
        coded_dyn = jax.jit(lambda th, Wt: Wt @ grad_fn(th))
        theta = np.asarray(theta0)
        e_flat = np.zeros((N * dim,), np.float32)
        for t in range(T):
            cs, info = maybe_replan(plan, oracle)
            assert not info["reallocated"], "pinned rates must never drift"
            g = coded_dyn(jnp.asarray(theta), cs.W)
            ghat, e_out = step_fn(g.reshape(-1), jnp.asarray(e_flat),
                                  masks[t])
            theta = theta - np.asarray(ghat)
            e_flat = np.asarray(e_out)
            dyn_rec.append(_records(theta, e_flat.reshape(N, dim)))

    # ---- compare ----------------------------------------------------------
    first_div: Optional[Dict] = None
    max_dtheta = max_de = 0.0
    sides = [("mesh", mesh_rec)] + ([("dynamic", dyn_rec)]
                                    if dynamic_state else [])
    for t in range(T):
        for side, rec in sides:
            for field in ("theta", "e"):
                a, b = ref[t][field], rec[t][field]
                if not np.array_equal(a, b):
                    diff = float(np.max(np.abs(a - b)))
                    if field == "theta":
                        max_dtheta = max(max_dtheta, diff)
                    else:
                        max_de = max(max_de, diff)
                    if first_div is None:
                        first_div = {"step": t, "field": field,
                                     "side": side, "max_abs_diff": diff}
    return {
        "compressor": compressor, "wire": type(wire).__name__,
        "T": T, "N": N, "shards": shards, "dim": dim, "gamma": gamma,
        "p": p, "d": d, "backend": backend,
        "dynamic_state": dynamic_state,
        "bitexact": first_div is None,
        "first_divergence": first_div,
        "max_abs_diff_theta": max_dtheta,
        "max_abs_diff_e": max_de,
        "loss_start": float(loss_fn(theta0)),
        "loss_ref": float(loss_fn(ref[-1]["theta"])),
        "loss_mesh": float(loss_fn(mesh_rec[-1]["theta"])),
    }


def assert_parity(report: Dict) -> None:
    if not report["bitexact"]:
        raise AssertionError(
            f"reference EF loop and mesh cocoef_update DIVERGED on "
            f"{report['compressor']} ({report['wire']}): first at "
            f"step {report['first_divergence']['step']} in "
            f"{report['first_divergence']['field']} "
            f"(|diff| up to theta={report['max_abs_diff_theta']:.3e}, "
            f"e={report['max_abs_diff_e']:.3e}) — the two Algorithm-1 "
            f"implementations no longer agree")
