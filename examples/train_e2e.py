"""End-to-end driver: distributed COCO-EF training of a transformer LM with
checkpoint/restart, on whatever devices exist.

Demonstrates the full production path: mesh -> sharding rules -> stage-1
coded gradients -> stage-2 wire-compressed aggregation -> server update ->
checkpoint -> crash-resume.  The mesh is (data=n, model=1) over the n
devices JAX finds; on the CPU that is 8 fake devices.

  PYTHONPATH=src python examples/train_e2e.py [--steps 60]
"""
import argparse
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# 8 fake devices where JAX runs on the CPU; no other platform reads this
jax.config.update("jax_num_cpu_devices", 8)

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import REGISTRY
from repro.configs.common import ShapeCfg
from repro.core.plan import PLAN_SCHEMA, PlanSpec
from repro.launch.train import (TrainRun, batch_stream, build_train_setup,
                                elastic_coding_state)

PLAN_N_WIRE = 1 << 16     # flat size the auto-planner prices wires at
                          # (matches the --rank-uplink-gbps budget solve)


def _load_plan(path: str) -> PlanSpec:
    """A saved plan: either a bare PlanSpec JSON (PlanSpec.save) or a
    planner emission whose "plan" field carries the winning spec."""
    obj = json.loads(Path(path).read_text())
    if isinstance(obj, dict) and obj.get("schema") != PLAN_SCHEMA \
            and "plan" in obj:
        obj = obj["plan"]
    return PlanSpec.from_dict(obj)


def _auto_plan(args, spec, n_code, trace_path, plan_out):
    """`--plan auto`: run the three-stage sim planner over THIS run's
    straggler profile, print the ranking, persist the emission (winner +
    ranking + provenance, CI schema-validates it), return the winner."""
    import sys
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                           / "benchmarks"))
    from _repro_common import run_metadata
    from repro.sim import get_straggler_process, plan_search
    p = spec.coding.straggler_p
    if args.straggler != "iid" or p > 0:
        proc = get_straggler_process(
            args.straggler, n_code, p, mean_burst=args.straggler_burst,
            spread=args.straggler_spread, trace=trace_path)
        res = plan_search(PLAN_N_WIRE, process=proc,
                          confirm_steps=120, seed=0)
    else:       # fully reliable fleet: rates-only search, no masks to sim
        res = plan_search(PLAN_N_WIRE, rates=np.ones((n_code,)),
                          confirm_steps=120, seed=0)
    print(f"planner: {res.num_enumerated} candidates -> "
          f"{res.pruned_to} confirmed; ranking:")
    for c in res.candidates[:res.pruned_to]:
        t2t = (f"{c.sim_time_to_target_s:.3f}s"
               if c.sim_time_to_target_s is not None else "never")
        print(f"  d={c.plan.d} {c.plan.compressor:10s} "
              f"alloc={c.plan.allocation:10s} score={c.score:.4f} "
              f"sim-t2t={t2t}")
    emission = {**res.to_dict(),
                "plan": res.best.plan.to_dict(),
                "meta": run_metadata(
                    arch=args.arch, straggler=args.straggler,
                    straggler_p=p, n_code=n_code, n_wire=PLAN_N_WIRE)}
    Path(plan_out).parent.mkdir(parents=True, exist_ok=True)
    Path(plan_out).write_text(json.dumps(emission, indent=1) + "\n")
    print(f"plan emission -> {plan_out}")
    return res.best.plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1b-7b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--compressor", default="sign",
                    choices=["sign", "block_topk", "topk", "identity"],
                    help="phase-1 wire compressor (WireFormat selection)")
    ap.add_argument("--num-buckets", type=int, default=1,
                    help="flat-vector buckets for comm overlap")
    ap.add_argument("--bucket-schedule", default="pipelined",
                    choices=["pipelined", "serial"],
                    help="per-bucket collective issue order: pipelined "
                         "double-buffers so bucket i's wire transfer "
                         "overlaps bucket i+1's compression (bit-for-bit "
                         "equal to serial)")
    ap.add_argument("--prefetch", type=int, default=0,
                    help="host->device batches staged ahead of the step "
                         "by a background thread (0 = synchronous; opt-in "
                         "on CPU fake devices, can race the in-process "
                         "collective rendezvous)")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "pallas", "jnp"],
                    help="fused-kernel dispatch for the wire hot path "
                         "(auto = Pallas on TPU, jnp reference elsewhere)")
    def _prob(s):
        v = float(s)
        if not 0.0 <= v < 1.0:
            raise argparse.ArgumentTypeError(
                f"straggle probability {v} must be in [0, 1)")
        return v

    ap.add_argument("--straggler", default="iid",
                    choices=["iid", "markov", "hetero", "trace"],
                    help="straggler process driving the per-step "
                         "participation masks (repro.sim)")
    ap.add_argument("--straggler-p", type=_prob, default=None,
                    help="override the arch's Bernoulli/stationary "
                         "straggle probability (in [0, 1))")
    ap.add_argument("--straggler-burst", type=float, default=8.0,
                    help="markov: mean slow-burst length in steps (>= 1)")
    ap.add_argument("--straggler-spread", type=float, default=0.5,
                    help="hetero: per-rank p_i in p*(1 +/- spread), every "
                         "p_i must land in [0, 1)")
    ap.add_argument("--straggler-trace", default=None,
                    help="recorded-mask JSON for --straggler trace "
                         "(default: synthesize a bursty trace and save it)")
    ap.add_argument("--elastic", action="store_true",
                    help="dynamic coding plane: a live CodingState (rate "
                         "estimates + encode weights) rides the jitted "
                         "step as a donated argument; masks observed on "
                         "the host feed an online RateEstimator, drift "
                         "past --replan-threshold regenerates the "
                         "allocation mid-run (epoch bump, no retrace)")
    ap.add_argument("--replan-threshold", type=float, default=0.1,
                    help="elastic: max |q_est - q_planned| tolerated "
                         "before rate_aware_allocation is re-run")
    ap.add_argument("--mean-rate-coding", action="store_true",
                    help="encode weights from the scalar mean rate p "
                         "(paper eq. 3) instead of the per-rank rates "
                         "q_i of the straggler process (rate-aware, "
                         "unbiased under non-iid stragglers; the default)")
    ap.add_argument("--rank-uplink-gbps", default=None,
                    help="comma-separated per-coding-rank uplink Gbit/s; "
                         "with --compressor block_topk, solves equal-time "
                         "per-rank wire budgets (sim.solve_k_budgets) so "
                         "slow-uplink ranks send fewer coords per block")
    ap.add_argument("--plan", default=None,
                    help="'auto' runs the sim planner (enumerate -> "
                         "analytic prune -> simulated confirm) over this "
                         "run's straggler profile, prints the ranking, and "
                         "trains the winner; a path loads a saved PlanSpec "
                         "JSON (PlanSpec.save or a planner emission). "
                         "Overrides --compressor/--num-buckets/"
                         "--bucket-schedule/--backend")
    ap.add_argument("--plan-out", default="/tmp/repro_e2e_plan.json",
                    help="where --plan auto writes the winner + ranking + "
                         "run_metadata provenance JSON")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    ap.add_argument("--metrics", action="store_true",
                    help="step-level telemetry (repro.obs): in-graph "
                         "MetricsFrame -> JSONL metrics + a Chrome trace "
                         "of measured host spans alongside the StepTimer-"
                         "PREDICTED schedule for the observed masks")
    ap.add_argument("--metrics-dir", default="/tmp/repro_e2e_metrics",
                    help="where --metrics writes metrics.jsonl + trace.json")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    enable_compile_cache()
    mesh = make_host_mesh()
    shape = ShapeCfg("train", seq_len=64, global_batch=16)
    spec = REGISTRY[args.arch]
    n_code = int(np.prod([mesh.shape[a] for a in spec.coding.coding_axes
                          if a in mesh.shape]))
    coding_over = dict(group_size=32, block_size=64, k_per_block=8)
    if args.straggler_p is not None:
        coding_over["straggler_p"] = args.straggler_p
    spec = dataclasses.replace(
        spec, coding=dataclasses.replace(spec.coding, **coding_over))

    trace_path = args.straggler_trace
    if args.straggler == "trace" and trace_path is None:
        # synthesize a bursty incident trace for the demo and replay it
        from repro.sim import MarkovBursty, TraceReplay
        p = spec.coding.straggler_p
        if args.straggler_p is None and p == 0:
            p = 0.2   # demo default; an explicit --straggler-p 0.0 stands
        proc = MarkovBursty(num_devices=n_code, p=p, mean_burst=6.0)
        trace = TraceReplay.from_array(
            proc.sample_trace(jax.random.PRNGKey(42), 128))
        trace_path = str(trace.to_json("/tmp/repro_e2e_trace.json"))
        print(f"synthesized bursty trace -> {trace_path}")

    k_budgets = None
    if args.rank_uplink_gbps:
        if args.compressor != "block_topk":
            ap.error("--rank-uplink-gbps needs --compressor block_topk "
                     "(per-rank budgets ride the sparse wire)")
        from repro.sim import LinkProfile, solve_k_budgets
        bws = tuple(float(b) for b in args.rank_uplink_gbps.split(","))
        link = LinkProfile(rank_bandwidth_gbps=bws)
        k_budgets = solve_k_budgets(
            1 << 16, len(bws), link, block_size=spec.coding.block_size,
            k_ref=spec.coding.k_per_block)
        print(f"per-rank wire budgets (equal-time): k={k_budgets} for "
              f"uplinks {bws} Gbit/s")

    plan = None
    if args.plan:
        if k_budgets is not None:
            ap.error("--rank-uplink-gbps solves k_budgets, which conflicts "
                     "with an explicit --plan (per-rank budgets live in "
                     "the plan's k_per_block)")
        if args.plan == "auto":
            plan = _auto_plan(args, spec, n_code, trace_path, args.plan_out)
        else:
            plan = _load_plan(args.plan)
        print(f"plan: d={plan.d} compressor={plan.compressor} "
              f"alloc={plan.allocation} buckets={plan.num_buckets} "
              f"({plan.bucket_schedule})")

    # with a plan, the ONE PlanSpec replaces the wire/bucket alias knobs
    # (TrainRun rejects mixing them)
    wire_kw = (dict(plan=plan) if plan is not None else
               dict(compressor=args.compressor,
                    num_buckets=args.num_buckets,
                    bucket_schedule=args.bucket_schedule,
                    backend=args.backend,
                    k_budgets=k_budgets))
    try:
        run = TrainRun(base_lr=5e-3, mode="cocoef",
                       prefetch=args.prefetch,
                       straggler=args.straggler,
                       straggler_burst=args.straggler_burst,
                       straggler_spread=args.straggler_spread,
                       straggler_trace=trace_path,
                       rate_aware=not args.mean_rate_coding,
                       elastic=args.elastic,
                       replan_threshold=args.replan_threshold,
                       metrics=args.metrics, **wire_kw)
        setup = build_train_setup(spec, mesh, shape, run, smoke=True)
    except ValueError as e:        # bad straggler/coding knobs fail HERE,
        ap.error(str(e))           # not as NaNs deep inside jit
    proc = setup.straggler_process
    rates = setup.cocoef_cfg.straggler_rates
    print(f"arch={args.arch} coding ranks={setup.n_code} "
          f"per-rank batch={setup.b_loc} local flat={setup.flat_pad} "
          f"straggler={type(proc).__name__ if proc else 'none'} "
          f"coding={'rate-aware q_i' if rates is not None else 'mean-rate p'}")

    estimator = state = None
    if args.elastic:
        from repro.core.coding_state import RateEstimator
        estimator = RateEstimator(setup.n_code)
        state, _ = elastic_coding_state(setup)   # epoch 0: planned rates
        print(f"elastic coding plane: replan threshold "
              f"{args.replan_threshold}, epoch 0 rates "
              f"{[round(float(x), 3) for x in state.rates_estimate]}")

    key = jax.random.PRNGKey(0)
    params, e, opt = setup.init_state(key)
    start = 0
    if latest_step(args.ckpt_dir) is not None:
        start, st = restore_checkpoint(
            args.ckpt_dir, {"params": params, "e": e},
            shardings={"params": setup.param_shardings})
        params, e = st["params"], st["e"]
        print(f"resumed from step {start}")

    logger = rec = None
    masks = []
    if args.metrics:
        import sys
        sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                               / "benchmarks"))
        from _repro_common import run_metadata
        from repro.obs import MetricsLogger, SpanRecorder, frame_to_host
        mdir = Path(args.metrics_dir)
        meta = run_metadata(
            arch=args.arch, steps=args.steps, seed=run.seed,
            mode=run.mode, compressor=setup.plan.compressor,
            num_buckets=setup.plan.num_buckets,
            bucket_schedule=setup.plan.bucket_schedule,
            backend_requested=setup.plan.backend,
            plan=setup.plan.to_dict(), straggler=args.straggler,
            straggler_p=spec.coding.straggler_p, prefetch=args.prefetch,
            rate_aware=run.rate_aware, n_code=setup.n_code,
            flat_pad=setup.flat_pad)
        logger = MetricsLogger(str(mdir / "metrics.jsonl"),
                               run_metadata=meta)
        rec = SpanRecorder()

    # elastic: coding_state is donated — every leaf is echoed through the
    # metrics dict, so XLA aliases the buffers for the next step's state
    jstep = jax.jit(setup.train_step, donate_argnums=(6,)) \
        if args.elastic else jax.jit(setup.train_step)
    # batches arrive device-resident, staged --prefetch steps ahead by the
    # background prefetcher while the mesh runs the current step
    batches = batch_stream(setup, spec, shape, key, start_step=start,
                           smoke=True, prefetch=run.prefetch)
    try:
        for t in range(start, args.steps):
            extra = (state,) if args.elastic else ()
            if rec is None:
                batch = next(batches)
                params, e, opt, m = jstep(params, e, opt, batch,
                                          jnp.int32(t), key, *extra)
            else:
                with rec.span("train/batch_wait", step=t):
                    batch = next(batches)
                if hasattr(batches, "stats"):
                    rec.counter("prefetch_depth", batches.stats.max_depth)
                with rec.span("train/step_dispatch", step=t):
                    params, e, opt, m = jstep(params, e, opt, batch,
                                              jnp.int32(t), key, *extra)
                with rec.span("train/result_fetch", step=t):
                    tel = frame_to_host(jax.device_get(m["telemetry"]))
                    loss = float(m["loss"])
                span_s = {s["name"]: s["t1"] - s["t0"]
                          for s in rec.spans[-3:]}
                logger.log_step(t, tel, loss=loss, spans=span_s)
                masks.append(tel["participation"])
            if args.elastic:
                # feed the plane: the mask the step just used is pure in
                # (key, t), so the host can observe it without telemetry
                obs = tel["participation"] if rec is not None else (
                    np.asarray(proc.mask(key, t)) if proc is not None
                    else np.ones((setup.n_code,)))
                estimator.update(obs)
                state, info = elastic_coding_state(setup, estimator.rates)
                if logger is not None:
                    logger.log_replan(t, info)
                if info["reallocated"]:
                    print(f"  replan @ step {t}: drift={info['drift']:.3f}"
                          f" -> allocation epoch {info['epoch']}")
            if t % 10 == 0 or t == args.steps - 1:
                print(f"step {t:4d} loss={float(m['loss']):.4f}")
            if (t + 1) % args.ckpt_every == 0:
                p = save_checkpoint(args.ckpt_dir, t + 1,
                                    {"params": params, "e": e})
                print(f"  checkpointed -> {p.name}")
    finally:
        if rec is not None and hasattr(batches, "stats"):
            logger.log_prefetch(batches.stats.snapshot())
        batches.close()     # stop + join the prefetch worker before exit

    if rec is not None:
        # Chrome trace: measured host spans (pid 0) + the StepTimer
        # PREDICTION for the same observed masks (pid 1) — open both in
        # chrome://tracing and compare lane by lane
        from repro.obs import span_events, steptimer_timeline, \
            write_chrome_trace
        from repro.sim import StepTimer
        # priced from setup.plan — the exact PlanSpec the step was built on
        wire = setup.plan.wire(setup.flat_pad // setup.plan.num_buckets, 1)
        timer = StepTimer(wire=wire, n=setup.flat_pad,
                          num_buckets=setup.plan.num_buckets,
                          overlap=setup.plan.overlap)
        sim_ev, sim_t = steptimer_timeline(
            timer, np.asarray(masks, np.float64), pid=1)
        events = span_events(rec.spans, pid=0, counters=rec.counters) \
            + sim_ev
        tpath = str(Path(args.metrics_dir) / "trace.json")
        write_chrome_trace(tpath, events, metadata=meta)
        logger.close()
        ew = logger.rates
        print(f"telemetry -> {logger.path} ({logger.steps_logged} steps); "
              f"trace -> {tpath}")
        print(f"EWMA participation rates: "
              f"{[round(float(x), 3) for x in ew]}")
        print(f"StepTimer-predicted mean step: {sim_t.mean()*1e3:.2f} ms "
              f"(simulated link; measured host spans in the trace)")


if __name__ == "__main__":
    main()
