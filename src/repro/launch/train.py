"""Distributed COCO-EF training step on the production mesh.

Two-stage structure (DESIGN.md Sec. 2/5):

  Stage 1 — per-coding-rank coded gradients, plain GSPMD:
    the global batch carries a leading coding dimension (N_code, B_loc, ...)
    sharded over the coding axes; `vmap(grad)` over that dimension yields
    each rank's coded gradient  g_i = sum_{k in S_i} grad f_k / (d_k (1-p))
    (the per-example weights fold the coding weights, so the coded sum is a
    single weighted backward pass).  TP/FSDP sharding inside is handled by
    GSPMD via the rules in repro.sharding.rules + activation constraints.

  Stage 2 — Algorithm 1 aggregation, fully-manual shard_map:
    every device flattens its local gradient slice, applies
    error-feedback + biased sign compression, and participates in the
    two-phase wire-compressed collective (repro.core.collectives).  The
    server update theta <- theta - ghat runs redundantly (replicated) on
    every coding rank — bitwise identical to the paper's server.

`mode`: cocoef (paper) | coco (no EF ablation) | dense (SGC [31] baseline).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.common import ArchSpec, ShapeCfg
from repro.core import coding
from repro.core.coding_state import CodingPlan, CodingState
from repro.core.cocoef import (CocoEFConfig, FlatMeta, cocoef_update,
                               flatten_local, padded_size, unflatten_local)
from repro.core.plan import PlanSpec
from repro.nn import Model
from repro.obs.metrics import (MetricsFrame, frame_out_specs,
                               reduce_frame_grid)
from repro.obs.tracing import span
from repro.optim import OptimizerConfig, apply_update, init_opt_state, \
    lr_schedule
from repro.sharding import ctx, rules
from repro.sim import stragglers

__all__ = ["TrainRun", "build_train_setup", "setup_encode_weights",
           "elastic_coding_state", "batch_stream"]


@dataclasses.dataclass(frozen=True)
class TrainRun:
    mode: str = "cocoef"             # cocoef | coco | dense
    base_lr: float = 1e-3
    schedule: str = "constant"       # constant | rsqrt | cosine
    schedule_total: Optional[int] = None  # cosine: decay horizon (steps)
    warmup: int = 0
    optimizer: OptimizerConfig = OptimizerConfig()
    plan: Optional[PlanSpec] = None  # THE deployment config (core.plan):
    #   d, allocation mode, wire knobs, buckets, backend.  When set it is
    #   the single source of truth and the deprecated alias fields below
    #   (compressor / k_budgets / num_buckets / bucket_schedule / backend)
    #   must stay at their defaults; when None, `resolve_plan` assembles
    #   the identical PlanSpec from those aliases + spec.coding, so every
    #   pre-plan caller keeps working bit-for-bit
    compressor: Optional[str] = None  # DEPRECATED alias -> plan.compressor
    ef_dtype: str = "float32"
    phase2_dtype: str = "float32"
    phase2_sign: bool = False
    num_buckets: int = 1             # DEPRECATED alias -> plan.num_buckets
    bucket_schedule: str = "pipelined"  # DEPRECATED alias ->
    #   plan.bucket_schedule.  pipelined | serial bucket issue order
    #   (CocoEFConfig.bucket_schedule): pipelined double-buffers the
    #   per-bucket collectives so bucket i's wire transfer overlaps bucket
    #   i+1's fused local step; bit-for-bit equal to serial
    prefetch: int = 0                # host->device batches staged ahead of
    #   the step (data.pipeline.prefetch_to_device); 0 = synchronous.
    #   Opt-in: on XLA:CPU the worker thread's concurrent client calls can
    #   race the fake-device collective rendezvous (see prefetch_to_device)
    backend: str = "auto"            # DEPRECATED alias -> plan.backend
    #   (auto | pallas | jnp kernel dispatch)
    straggler: str = "iid"           # iid | markov | hetero | trace
    straggler_burst: float = 8.0     # markov: mean slow-burst length (steps)
    straggler_spread: float = 0.5    # hetero: p_i in p*(1 +/- spread)
    straggler_trace: Optional[str] = None  # trace: recorded-mask JSON or
    #   per-rank availability CSV path (sim.TraceReplay.from_file)
    rate_aware: bool = True          # encode weights from per-rank rates
    #   q_i (StragglerProcess.rates()) instead of the scalar mean rate p —
    #   identical to eq. 3 for uniform rates, unbiased under non-iid
    #   stragglers; False = the paper-faithful mean-rate eq. 3
    k_budgets: Optional[Tuple[int, ...]] = None
    #   DEPRECATED alias -> plan.k_per_block tuple: per-coding-rank
    #   block-top-K wire budgets (sim.solve_k_budgets); overrides
    #   spec.coding.k_per_block when compressor="block_topk"
    elastic: bool = False            # dynamic coding plane: the train step
    #   takes an explicit CodingState (rates_estimate, W, epoch) argument
    #   and folds W in-graph via the batch's subset_ids, so online rate
    #   estimates (obs.MetricsLogger.rates -> CodingPlan.maybe_replan) can
    #   update the encode weights every step without retracing; False = W
    #   baked into the batch weights at construction (the static path)
    replan_threshold: float = 0.1    # elastic: max |q_est - q_planned|
    #   before the host recomputes the allocation (epoch bump)
    seed: int = 0
    param_dtype: Optional[str] = None   # override cfg (e.g. "bfloat16")
    metrics: bool = False            # in-graph telemetry (repro.obs): the
    #   train step additionally returns metrics["telemetry"], the reduced
    #   MetricsFrame (per-rank wire bytes, participation, EF/compression
    #   norms).  Adds device-local FLOPs only — no host callbacks, no extra
    #   collectives; False traces the exact pre-telemetry HLO (pinned by
    #   tests/test_obs.py)

    def __post_init__(self):
        # validate at construction: bad straggler / coding knobs used to
        # surface as NaNs or cryptic shape errors deep inside jit
        if self.mode not in ("cocoef", "coco", "dense"):
            raise ValueError(f"unknown mode {self.mode!r}; "
                             f"have ('cocoef', 'coco', 'dense')")
        # schedule knobs validate at construction (lr_schedule re-checks):
        # a TrainRun that would die inside jit tracing is rejected here
        lr_schedule(self.schedule, self.base_lr, self.warmup,
                    self.schedule_total)
        if self.straggler not in stragglers.STRAGGLER_PROCESSES:
            raise ValueError(
                f"unknown straggler process {self.straggler!r}; "
                f"have {stragglers.STRAGGLER_PROCESSES}")
        if self.straggler_burst < 1.0:
            raise ValueError(f"straggler_burst={self.straggler_burst} must "
                             f"be >= 1 step")
        if self.straggler_spread < 0.0:
            raise ValueError(f"straggler_spread={self.straggler_spread} "
                             f"must be >= 0")
        if self.backend not in ("auto", "pallas", "jnp"):
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"have ('auto', 'pallas', 'jnp')")
        if self.num_buckets < 1:
            raise ValueError(f"num_buckets={self.num_buckets} must be >= 1")
        if self.bucket_schedule not in ("serial", "pipelined"):
            raise ValueError(f"unknown bucket_schedule "
                             f"{self.bucket_schedule!r}; have "
                             f"('serial', 'pipelined')")
        if self.prefetch < 0:
            raise ValueError(f"prefetch={self.prefetch} must be >= 0")
        if self.k_budgets is not None and \
                any(k < 1 for k in self.k_budgets):
            raise ValueError("every per-rank k budget must be >= 1")
        if self.k_budgets is not None and len(self.k_budgets) == 0:
            raise ValueError("k_budgets must be non-empty (one per-rank "
                             "block-top-K budget per coding rank)")
        if self.plan is not None:
            # the deprecated alias cluster and an explicit PlanSpec are
            # mutually exclusive: a plan that silently loses to a stray
            # alias would un-do the "one source of truth" guarantee
            _alias_defaults = {"compressor": None, "k_budgets": None,
                               "num_buckets": 1,
                               "bucket_schedule": "pipelined",
                               "backend": "auto"}
            clash = [f for f, dflt in _alias_defaults.items()
                     if getattr(self, f) != dflt]
            if clash:
                raise ValueError(
                    f"TrainRun(plan=...) conflicts with deprecated alias "
                    f"field(s) {clash}: the plan already carries those "
                    f"knobs — set them on the PlanSpec instead")
        if not self.replan_threshold > 0.0:
            raise ValueError(f"replan_threshold={self.replan_threshold} "
                             f"must be > 0")
        if self.elastic and self.prefetch:
            raise ValueError(
                "elastic runs need synchronous batches (prefetch=0): a "
                "replan changes the subset placement between batch "
                "generation and consumption")

    def resolve_plan(self, coding_cfg, n_code: int) -> PlanSpec:
        """The effective PlanSpec of this run on `n_code` coding ranks.

        With an explicit `plan`, binds/validates its `num_ranks` against the
        mesh.  Otherwise assembles the identical PlanSpec the pre-plan code
        path implied: deprecated alias fields override `coding_cfg`
        (configs.common.CodingCfg) exactly as `build_train_setup` used to do
        inline — the equivalence every legacy caller relies on."""
        m = max(n_code, 1)
        if self.plan is not None:
            if self.plan.num_ranks is None:
                return dataclasses.replace(self.plan, num_ranks=m)
            if self.plan.num_ranks != m:
                raise ValueError(
                    f"plan targets num_ranks={self.plan.num_ranks} coding "
                    f"ranks but the mesh has {m}")
            return self.plan
        comp = self.compressor or coding_cfg.compressor
        k_per_block = coding_cfg.k_per_block
        if self.k_budgets is not None:
            if comp != "block_topk":
                raise ValueError(
                    f"k_budgets rides the block-top-K sparse wire; the "
                    f"effective compressor is {comp!r} (pass "
                    f"compressor='block_topk' or drop k_budgets)")
            if len(self.k_budgets) != m:
                raise ValueError(f"k_budgets has {len(self.k_budgets)} "
                                 f"entries, the run has {m} coding ranks")
            k_per_block = self.k_budgets
        return PlanSpec(
            d=min(coding_cfg.redundancy, m), allocation="uniform",
            compressor=comp, group_size=coding_cfg.group_size,
            k_per_block=k_per_block, block_size=coding_cfg.block_size,
            topk_k=coding_cfg.topk_k, value_dtype=coding_cfg.wire_dtype,
            num_buckets=self.num_buckets,
            bucket_schedule=self.bucket_schedule, backend=self.backend,
            num_ranks=m)


@dataclasses.dataclass
class TrainSetup:
    """Everything needed to lower/run the step: shardings + callables."""
    mesh: Mesh
    model: Model
    coding_axes: Tuple[str, ...]
    n_code: int
    b_loc: int
    seq_len: int
    flat_pad: int
    param_specs: Any
    param_shardings: Any
    grads_shardings: Any
    state_sharding: NamedSharding
    batch_shardings: Any
    train_step: Any                  # jit-able fn
    input_specs: Any                 # () -> kwargs of ShapeDtypeStruct
    init_state: Any                  # (key) -> (params, e, opt) real arrays
    allocation: coding.Allocation
    cocoef_cfg: CocoEFConfig
    plan: PlanSpec = PlanSpec()      # the resolved deployment plan (num_ranks
    #   bound to the mesh); "the config priced is the config run": price
    #   StepTimer with plan.wire(...)/plan.rank_wire_bytes and you priced
    #   exactly what train_step ships
    straggler_process: Optional[stragglers.StragglerProcess] = None
    coding_plan: Optional[CodingPlan] = None   # elastic runs: the host-side
    #   replan controller; its CURRENT allocation is what the batch maker
    #   uses (setup.allocation stays the epoch-0 placement)
    per_subset: int = 1              # examples per subset (the batch-maker
    #   1/per_subset fold elastic_coding_state applies host-side)


def _local_flat_size(shapes_tree, specs_tree, mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 0
    for leaf, spec in zip(jax.tree.leaves(shapes_tree),
                          jax.tree.leaves(specs_tree, is_leaf=lambda s: isinstance(s, P))):
        n = 1
        for dim, entry in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if entry is None:
                n *= dim
            else:
                axes = (entry,) if isinstance(entry, str) else entry
                f = int(np.prod([sizes[a] for a in axes]))
                n *= dim // f
        total += n
    return total


def build_train_setup(spec: ArchSpec, mesh: Mesh, shape: ShapeCfg,
                      run: TrainRun = TrainRun(), smoke: bool = False,
                      mode: Optional[str] = None) -> TrainSetup:
    cfg = spec.smoke if smoke else spec.config
    if run.param_dtype:
        cfg = dataclasses.replace(cfg, param_dtype=run.param_dtype)
    mode = mode or run.mode
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    coding_axes = tuple(a for a in spec.coding.coding_axes
                        if a in mesh.axis_names)
    n_code = int(np.prod([axis_sizes[a] for a in coding_axes])) \
        if coding_axes else 1
    # one coding rank still runs the mode asked for (EF, the wire kernels,
    # the all_to_all over a size-1 axis), but never straggles: a lone rank
    # that straggles would stop training, so its p is 0 (and d is 1)
    p_strag = spec.coding.straggler_p if n_code > 1 else 0.0

    # ---- the effective deployment plan (single source of truth) ----------
    # `plan` carries every (d, wire, k, schedule, backend) knob from here
    # on; the deprecated TrainRun aliases and spec.coding were already
    # folded into it, so nothing below re-derives a knob from two places.
    plan = run.resolve_plan(spec.coding, n_code)

    # straggler process feeding the mask-provider hook (repro.sim): the
    # legacy fast path (iid with p=0 -> all-ones mask, no PRNG work) is
    # preserved by constructing no process at all in that case
    straggler_proc = None
    if n_code > 1 and (run.straggler != "iid" or p_strag > 0):
        straggler_proc = stragglers.get_straggler_process(
            run.straggler, n_code, p_strag, mean_burst=run.straggler_burst,
            spread=run.straggler_spread, trace=run.straggler_trace)

    # rate-aware encode weights: divide by the expected participating
    # holders sum_j S[j,k] q_j (unbiased for ANY per-rank rates) instead of
    # d_k (1-p); bit-for-bit eq. 3 when the rates are uniform (iid/markov)
    straggler_rates = None
    if run.rate_aware and straggler_proc is not None:
        straggler_rates = tuple(float(x) for x in straggler_proc.rates())

    # ---- gradient coding allocation (static, host-side) -------------------
    M = n_code                        # one subset per coding rank by default
    d = plan.d
    if n_code <= 1:
        alloc = coding.Allocation(S=np.ones((1, 1), np.int8))
    elif plan.allocation == "uniform":
        alloc = coding.cyclic_allocation(n_code, M, d)
    else:
        # heterogeneity-aware placement from the same rates the encode
        # weights use (planned rates when no process is attached)
        q = np.asarray(straggler_rates, np.float64) \
            if straggler_rates is not None \
            else np.full((n_code,), 1.0 - p_strag)
        alloc = coding.rate_aware_allocation(
            q, M, d, exact_load=(plan.allocation == "exact_load"))

    gb, seq = shape.global_batch, shape.seq_len
    per_subset = max(1, gb // M)
    b_loc = per_subset * d            # redundancy multiplies per-rank batch

    model = Model(cfg)
    pshapes = model.param_shapes()
    fsdp = spec.coding.fsdp
    pspecs = rules.param_specs(pshapes, cfg, mesh, fsdp=fsdp)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    gspecs = rules.grads_specs(pshapes, cfg, mesh, coding_axes, fsdp=fsdp)
    gshard = jax.tree.map(lambda s: NamedSharding(mesh, s), gspecs)

    # wire / compressor / schedule knobs all come from the resolved plan
    nd_chunk = axis_sizes[coding_axes[-1]] if coding_axes else 1

    cocoef_cfg = CocoEFConfig(
        coding_axes=coding_axes if coding_axes else ("data",),
        group_size=plan.group_size, straggler_p=p_strag,
        straggler_rates=straggler_rates, mode=mode,
        compressor=plan.compressor,
        topk_k=plan.topk_k, k_per_block=plan.k_per_block,
        block_size=plan.block_size, wire_dtype=plan.value_dtype,
        ef_dtype=run.ef_dtype, phase2_dtype=run.phase2_dtype,
        phase2_sign=run.phase2_sign, num_buckets=plan.num_buckets,
        bucket_schedule=plan.bucket_schedule, backend=plan.backend)

    # device-local flat size (uniform across devices by construction);
    # padding alignment comes from the active wire format, not just the
    # sign group (block top-K needs lcm(group, block))
    loc = _local_flat_size(pshapes, pspecs, mesh)
    flat_pad = padded_size(loc, nd_chunk, cocoef_cfg.pad_multiple,
                           plan.num_buckets)

    mesh_shape = tuple(mesh.devices.shape)
    state_shape = mesh_shape + (flat_pad,)
    state_spec = P(*mesh.axis_names, None)
    state_sharding = NamedSharding(mesh, state_spec)

    gamma_fn = lr_schedule(run.schedule, run.base_lr, run.warmup,
                           run.schedule_total)
    n_opt = len(init_opt_state(run.optimizer, 1))

    # ---- batch specs -------------------------------------------------------
    inner_axes = tuple(a for a in ("pod", "data")
                       if a in mesh.axis_names and a not in coding_axes)
    lead = (coding_axes if len(coding_axes) > 1 else
            (coding_axes[0] if coding_axes else None))
    inner = (inner_axes if len(inner_axes) > 1 else
             (inner_axes[0] if inner_axes else None))
    if cfg.input_mode == "tokens":
        batch_specs = {"inputs": P(lead, inner, None),
                       "weights": P(lead, inner)}
        batch_shapes = {"inputs": jax.ShapeDtypeStruct(
            (n_code, b_loc, seq + 1), jnp.int32),
            "weights": jax.ShapeDtypeStruct((n_code, b_loc), jnp.float32)}
    else:
        batch_specs = {"inputs": P(lead, inner, None, None),
                       "targets": P(lead, inner, None),
                       "weights": P(lead, inner)}
        batch_shapes = {
            "inputs": jax.ShapeDtypeStruct((n_code, b_loc, seq, cfg.d_model),
                                           jnp.bfloat16),
            "targets": jax.ShapeDtypeStruct((n_code, b_loc, seq), jnp.int32),
            "weights": jax.ShapeDtypeStruct((n_code, b_loc), jnp.float32)}
    if run.elastic:
        # per-example subset ids ride the batch (same layout as weights);
        # the step looks the live W up through them in-graph
        batch_specs["subset_ids"] = P(lead, inner)
        batch_shapes["subset_ids"] = jax.ShapeDtypeStruct(
            (n_code, b_loc), jnp.int32)
    batch_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                   batch_specs)

    # ---- dynamic coding plane (elastic runs) -------------------------------
    coding_plan = None
    if run.elastic:
        # initial estimate = whatever the static path would bake in, so
        # epoch 0 of the dynamic path is bit-for-bit the static path
        # (uniform rates hit encode_weights' eq.-3 branch)
        init_rates = np.asarray(straggler_rates, np.float64) \
            if straggler_rates is not None \
            else np.full((max(n_code, 1),), 1.0 - p_strag)
        coding_plan = CodingPlan.create(
            init_rates, M, d, drift_threshold=run.replan_threshold,
            exact_load=(plan.allocation != "rate_aware"), allocation=alloc)

    # =======================================================================
    # stage 2 body (fully manual)
    # =======================================================================
    all_axes = set(mesh.axis_names)
    n_leaves = len(jax.tree.leaves(pshapes))

    def agg_body(params, grads, e, opt, step, key, *rows):
        # every op of stage 2 carries "stage2/" in its op_name, and the flat
        # copies (leaves and state to flat vectors and back) carry
        # stage2/flatten or stage2/unflatten; the layout copies the
        # compiler adds around them carry no op_name at all
        with jax.named_scope("stage2"):
            # local leaf blocks; grads leaves carry leading coding dims of
            # size 1
            with jax.named_scope("flatten"):
                p_flat, p_meta = flatten_local(
                    jax.tree.leaves(params), nd_chunk,
                    cocoef_cfg.pad_multiple, plan.num_buckets)
                g_flat, _ = flatten_local(
                    jax.tree.leaves(grads), nd_chunk,
                    cocoef_cfg.pad_multiple, plan.num_buckets)
                e_loc = e.reshape(-1)
                opt_loc = tuple(o.reshape(-1) for o in opt)

            gamma = gamma_fn(step)
            mask_fn = straggler_proc.mask if straggler_proc is not None \
                else (lambda k, s: jnp.ones((max(n_code, 1),), jnp.float32))

            if run.metrics:
                ghat, e_new, frame = cocoef_update(
                    g_flat, e_loc, None, gamma, cocoef_cfg,
                    mask_provider=mask_fn, key=key, step=step,
                    want_metrics=True)
                p_new_flat, opt_new, onorms = apply_update(
                    run.optimizer, p_flat, ghat, opt_loc, step, gamma,
                    want_norms=True)
                frame = frame.replace(
                    update_norm_sq=onorms["update_norm_sq"],
                    param_norm_sq=onorms["param_norm_sq"],
                    moe_rows_held=rows[0].reshape(()).astype(jnp.float32))
            else:
                ghat, e_new = cocoef_update(g_flat, e_loc, None, gamma,
                                            cocoef_cfg, mask_provider=mask_fn,
                                            key=key, step=step)
                p_new_flat, opt_new = apply_update(
                    run.optimizer, p_flat, ghat, opt_loc, step, gamma)
            shape1 = (1,) * len(mesh_shape)
            with jax.named_scope("unflatten"):
                new_leaves = unflatten_local(p_new_flat, p_meta)
                params_new = jax.tree.unflatten(jax.tree.structure(params),
                                                new_leaves)
                out = (params_new, e_new.reshape(shape1 + (flat_pad,)),
                       tuple(o.reshape(shape1 + (flat_pad,))
                             for o in opt_new))
            if run.metrics:
                # grid-position dims of size 1 per leaf, so the replicated
                # frame lands as a (mesh..., leaf)-shaped output
                out += (jax.tree.map(lambda l: l.reshape(shape1 + l.shape),
                                     frame),)
            return out

    grads_in_specs = gspecs
    params_in_specs = pspecs
    opt_specs = tuple(state_spec for _ in range(n_opt))

    in_specs = (params_in_specs, grads_in_specs, state_spec, opt_specs,
                P(), P())
    out_specs = (params_in_specs, state_spec, opt_specs)
    if run.metrics:
        # stage 1's per-coding-rank counters ride in beside the gradients
        in_specs += (P(lead),)
        frame_abs = MetricsFrame.abstract(
            max(n_code, 1), plan.num_buckets).replace(
            moe_rows_held=jax.ShapeDtypeStruct((), jnp.float32))
        out_specs += (frame_out_specs(frame_abs, mesh.axis_names),)

    # check_vma stays off here: the flat vector joins model-sharded and
    # model-replicated leaves, so a replicated leaf's update is typed as
    # varying over `model` (and with model > 1 a sign group or top-k block
    # that straddles both kinds of leaf really does differ across model
    # shards).  Every other shard_map in the repo runs with the check on.
    agg = jax.shard_map(
        agg_body, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, axis_names=all_axes, check_vma=False)

    # =======================================================================
    # full train step
    # =======================================================================
    # FSDP archs: register ZeRO-3-style just-in-time weight gathering —
    # inside each layer scan the fsdp-sharded f32 weight slice is cast to
    # bf16 and re-constrained to its TP-only sharding, so the data-axis
    # all-gather moves bf16 weights instead of f32 activation partials
    # (EXPERIMENTS.md §Perf).
    weight_gather = None
    if fsdp:
        sizes_wg = dict(zip(mesh.axis_names, mesh.devices.shape))

        def weight_gather(tree, ct):
            from jax.sharding import PartitionSpec as _P

            def f(path, leaf):
                if leaf.ndim < 2:
                    return leaf
                spec = rules._check_divisible(
                    rules._leaf_rule(path, leaf, cfg, False), leaf.shape,
                    sizes_wg)
                # barrier: stop XLA hoisting the bf16 cast past the gather.
                # (Forcing reduce-scatter on the cotangent via custom_vjp
                # was tried and REFUTED: under remat the extra constraint
                # duplicates the per-layer grad all-reduce — §Perf.)
                w16 = jax.lax.optimization_barrier(leaf.astype(ct))
                return jax.lax.with_sharding_constraint(
                    w16, NamedSharding(mesh, _P(*spec)))
            return jax.tree_util.tree_map_with_path(f, tree)

    def base_step(params, e, opt, batch, step, key):
        def loss_one(p, b):
            loss, per_ex = model.loss(p, b)
            return loss

        def loss_rows(p, b):
            loss, _, rows = model.loss(p, b, counters=True)
            return loss, rows

        def grad_one(b):
            if run.metrics:
                (l, rows), g = jax.value_and_grad(
                    lambda p: loss_rows(p, b), has_aux=True)(params)
                return g, l, rows
            l, g = jax.value_and_grad(lambda p: loss_one(p, b))(params)
            return g, l

        with ctx.use_mesh(mesh, weight_gather=weight_gather):
            grads, losses, *rows = jax.vmap(grad_one)(batch)
        grads = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)), grads, gspecs)
        out = agg(params, grads, e, opt, step, key, *rows)
        params_new, e_new, opt_new = out[:3]
        metrics = {"loss": losses.mean()}
        if run.metrics:
            # grid-replicated frame -> per-coding-rank / global step
            # telemetry; runs outside the shard_map, adds no collectives
            metrics["telemetry"] = reduce_frame_grid(
                out[3], mesh.axis_names, coding_axes)
        return params_new, e_new, opt_new, metrics

    if run.elastic:
        def train_step(params, e, opt, batch, step, key, coding_state):
            # fold the LIVE encode weights in-graph.  coding_state.W here
            # is ALREADY W/per_subset (elastic_coding_state divides on the
            # host): the per-example weight must be the identical f32
            # value the static batch maker bakes in, and an in-graph
            # divide-by-constant is strength-reduced by XLA to a
            # reciprocal multiply (off by an ulp for non-pow2
            # per_subset).  W is a pytree leaf: new value, no retrace.
            coef = jnp.take_along_axis(
                coding_state.W, batch["subset_ids"], axis=1)
            b = {k: v for k, v in batch.items() if k != "subset_ids"}
            b["weights"] = b["weights"] * coef
            p_new, e_new, opt_new, metrics = base_step(params, e, opt, b,
                                                       step, key)
            # echo the plane's state so drivers can donate coding_state
            # (every leaf is an output -> XLA aliases the buffers)
            metrics = dict(metrics, coding_epoch=coding_state.epoch,
                           coding_W=coding_state.W,
                           rates_estimate=coding_state.rates_estimate)
            return p_new, e_new, opt_new, metrics
    else:
        train_step = base_step

    # ---- specs / init ------------------------------------------------------
    def input_specs():
        cs = {}
        if run.elastic:
            cs["coding_state"] = CodingState(
                rates_estimate=jax.ShapeDtypeStruct((max(n_code, 1),),
                                                    jnp.float32),
                W=jax.ShapeDtypeStruct((max(n_code, 1), M), jnp.float32),
                epoch=jax.ShapeDtypeStruct((), jnp.int32))
        return {
            "params": jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                pshapes, pshard),
            "e": jax.ShapeDtypeStruct(state_shape, jnp.dtype(run.ef_dtype),
                                      sharding=state_sharding),
            "opt": tuple(jax.ShapeDtypeStruct(state_shape, jnp.float32,
                                              sharding=state_sharding)
                         for _ in range(n_opt)),
            "batch": jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                batch_shapes, batch_shardings),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
            "key": jax.ShapeDtypeStruct((2,), jnp.uint32),
            **cs,
        }

    def init_state(key):
        params = jax.jit(model.init, out_shardings=pshard)(key)

        def zeros(dtype):
            # made in place on every device: the whole (n_code * flat,)
            # vector need not fit on one
            return jax.jit(lambda: jnp.zeros(state_shape, dtype),
                           out_shardings=state_sharding)()
        e = zeros(jnp.dtype(run.ef_dtype))
        opt = tuple(zeros(jnp.float32) for _ in range(n_opt))
        return params, e, opt

    return TrainSetup(
        mesh=mesh, model=model, coding_axes=coding_axes, n_code=n_code,
        b_loc=b_loc, seq_len=seq, flat_pad=flat_pad, param_specs=pspecs,
        param_shardings=pshard, grads_shardings=gshard,
        state_sharding=state_sharding, batch_shardings=batch_shardings,
        train_step=train_step, input_specs=input_specs, init_state=init_state,
        allocation=alloc, cocoef_cfg=cocoef_cfg, plan=plan,
        straggler_process=straggler_proc, coding_plan=coding_plan,
        per_subset=per_subset)


def setup_encode_weights(setup: TrainSetup) -> jnp.ndarray:
    """THE (N_code, M) encode weights the trainer aggregates with:
    rate-aware (per-rank q_i) when the setup carries straggler rates, else
    mean-rate eq. 3.  Every batch maker (make_batch_for_step, the fig10
    model-zoo sweep) must fold THIS W so stage 1 weights the examples with
    exactly the coding the stage-2 aggregation assumes."""
    if setup.cocoef_cfg.straggler_rates is not None:
        return coding.encode_weights(
            setup.allocation, rates=setup.cocoef_cfg.straggler_rates)
    return coding.encode_weights(setup.allocation,
                                 setup.cocoef_cfg.straggler_p)


def elastic_coding_state(setup: TrainSetup, rates=None):
    """One coding-plane control tick for the elastic train loop.

    Runs `CodingPlan.maybe_replan` on the latest rate estimates (None —
    e.g. `MetricsLogger.rates` before the first step — keeps the planned
    rates), then applies the batch maker's 1/per_subset fold HOST-side
    (numpy f32, the exact division the static path bakes into its batch
    weights; an in-graph divide would be strength-reduced by XLA and lose
    the last ulp).  Returns (CodingState ready to feed the jitted step,
    replan info dict for `MetricsLogger.log_replan`).
    """
    from repro.core import coding_state as cs
    if setup.coding_plan is None:
        raise ValueError("setup was built without TrainRun.elastic")
    st, info = cs.maybe_replan(setup.coding_plan, rates)
    W_scaled = jnp.asarray(np.asarray(st.W) / setup.per_subset)
    return st._replace(W=W_scaled), info


def make_batch_for_step(setup: TrainSetup, spec: ArchSpec, shape: ShapeCfg,
                        key, step: int, smoke: bool = False):
    """Materialize a real global batch (smoke/integration runs).

    Tokens and the coded per-example weights come from ONE batch maker —
    `data.pipeline.coded_train_batch` — so the W/per_subset folding that
    realizes eq. 3 in stage 1 lives in a single place (shared with the
    fig10 model-zoo sweep) and cannot drift between entry points."""
    from repro.data import pipeline

    cfg = spec.smoke if smoke else spec.config
    n_code, b_loc, seq = setup.n_code, setup.b_loc, setup.seq_len
    per_subset = max(1, shape.global_batch // setup.allocation.num_subsets)
    if setup.coding_plan is not None:
        # elastic: weights stay OUT of the batch (the step folds the live
        # CodingState.W in-graph via subset_ids); the plan's CURRENT
        # allocation decides the placement, so an epoch bump takes effect
        # at the next batch without retracing (uniform load keeps shapes)
        with span("repro.feed.tokens"):
            toks, wts, sids = pipeline.elastic_train_batch(
                key, step, setup.coding_plan.allocation, per_subset, seq,
                cfg.vocab_size)
        extra = {"subset_ids": sids}
    else:
        # W is made on the device, behind the step in flight: its host
        # copy waits for that step
        with span("repro.feed.weights"):
            W = np.asarray(setup_encode_weights(setup))
        with span("repro.feed.tokens"):
            toks, wts = pipeline.coded_train_batch(
                key, step, setup.allocation, W, per_subset, seq,
                cfg.vocab_size)
        extra = {}
    if cfg.input_mode == "tokens":
        return {"inputs": toks, "weights": wts, **extra}
    emb = jax.random.normal(key, (n_code, b_loc, seq, cfg.d_model),
                            jnp.bfloat16) * 0.02
    tgt = toks[..., :-1]
    return {"inputs": emb, "targets": tgt, "weights": wts, **extra}


def batch_stream(setup: TrainSetup, spec: ArchSpec, shape: ShapeCfg, key,
                 start_step: int = 0, smoke: bool = False, prefetch: int = 0):
    """Device-resident batch iterator for the serial train loop: yields the
    `make_batch_for_step` batches in step order, already `device_put`
    against `setup.batch_shardings`.

    With prefetch >= 1 a background thread stages that many batches ahead
    (`data.pipeline.prefetch_to_device`), so while the mesh executes step
    t the host is generating + transferring step t+1's coded batch — the
    host-side batch construction disappears from the step's critical path.
    prefetch=0 (the default) is a synchronous generate-then-put per pull
    (identical batches either way: the maker is deterministic in
    (key, step)).  Prefetch is OPT-IN here because on XLA:CPU fake
    devices the worker's concurrent client calls can race the in-process
    collective rendezvous of the mesh step — see prefetch_to_device."""
    from repro.data import pipeline

    def gen():
        t = start_step
        while True:
            yield make_batch_for_step(setup, spec, shape, key, t, smoke=smoke)
            t += 1

    def put(b):
        with span("repro.feed.put"):
            return jax.device_put(b, setup.batch_shardings)

    if prefetch < 1:
        return (put(b) for b in gen())
    return pipeline.prefetch_to_device(gen(), size=prefetch,
                                       shardings=setup.batch_shardings)
