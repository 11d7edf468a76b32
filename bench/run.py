#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload xlstm-sign --seed 7 --seconds 10 --trace 0

One process, one cell (an entry of BENCHMARK.json's `workloads`):

 1. Set-up: build the program's train step for the cell's configuration
    and traffic, make the weights on the device from the seed, compile the
    step through the persistent compile cache (.jax_cache/ in the
    checkout), and check that the compiled step runs the Mosaic kernels
    the traffic names.  Then the first steps, through the step's own call
    and feed: their losses, the first update and the change after them are
    kept for the check.  Set-up ends at the first timed step (`setup_s`).
 2. The window: steps dispatched back to back, each pulling its batch
    from the program's feed, with one step in flight; it lasts `--seconds`
    and ends when the last step dispatched has finished.  `tokens_per_s`
    is the unique tokens of every step of the window over its length.
    Compilations inside the window are counted; there must be none.
    With `--trace 1` the window is profiled and the per-layer metrics are
    read from the trace instead.
 3. The check: the program's state is freed, and the plain reference
    (bench/reference/) runs the same first steps from the same weights and
    tokens in float32; bench/check.py compares the two.

Exits non-zero, without a result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CHECKED_STEPS = 3
TRACE_DIR = ROOT / ".bench_trace"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Context:
    """What the per-layer metric readers (bench/metrics/*.py) read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _window(prog, seconds: float, trace: bool, compiles: list):
    import jax
    from jax.profiler import TraceAnnotation

    from bench.hostprobe import HostProbe
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    phases, losses = [], []
    # the set-up's objects leave the cyclic collector's reach, so that a
    # full collection inside the window scans only what the window makes
    gc.collect()
    gc.freeze()
    full_gcs = gc.get_stats()[2]["collections"]
    compiles.clear()
    with HostProbe() as probe:
        t0 = time.perf_counter()
        setup_s = time.time() - T_START
        with TraceAnnotation("bench.window"):
            prev = None
            while True:
                probe.tick()
                ti = time.perf_counter()
                with TraceAnnotation("bench.input"):
                    batch = prog.next_batch()
                td = time.perf_counter()
                with TraceAnnotation("bench.dispatch"):
                    m = prog.dispatch(batch)
                tw = time.perf_counter()
                if prev is not None:
                    with TraceAnnotation("bench.wait"):
                        losses.append(float(prev["loss"]))
                phases.append((td - ti, tw - td, time.perf_counter() - tw))
                prev = m
                if time.perf_counter() - t0 >= seconds:
                    break
            probe.tick()
            tw = time.perf_counter()
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready((prog.params, prog.e))
                losses.append(float(prev["loss"]))
            phases.append((0.0, 0.0, time.perf_counter() - tw))
            probe.tick()
        t1 = time.perf_counter()
    window_s = t1 - t0
    in_window = len(compiles)
    full_gcs = gc.get_stats()[2]["collections"] - full_gcs
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    # step i of the loop pulls batch i, dispatches step i and waits for
    # step i - 1; a last interval waits for the last step
    walls = [b[0] - a[0] for a, b in zip(probe.ticks, probe.ticks[1:])]
    slow = max(range(len(walls)), key=walls.__getitem__)
    typical = sorted(range(len(walls)), key=walls.__getitem__)[len(walls) // 2]
    stall = {"slowest": dict(probe.report(slow), phases_s=phases[slow]),
             "median": dict(probe.report(typical), phases_s=phases[typical])}
    return dict(setup_s=setup_s, window_s=window_s, losses=losses,
                compiles=in_window, full_gcs=full_gcs,
                slowest_s=walls[slow], stall=stall)


def _reduce_trace(prog):
    from bench import devtrace
    files = sorted(TRACE_DIR.rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    rec = devtrace.extract(str(files[-1]))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    hlo = prog.compiled.as_text()
    module = hlo.split("\n", 1)[0].split()[1].rstrip(",")
    return devtrace.Reduction(rec, devtrace.op_names(hlo), module)


def run_cell(cell, seed: int, seconds: float, trace: bool, peaks,
             on_chip: bool = True, fault=None) -> dict:
    """One run of `cell`; returns the result line's object.

    peaks: the chip's row of bench/peaks.json (None off the chip);
    on_chip: check that the compiled step runs the traffic's kernels;
    fault: wraps the compiled step's call (tests break the timed path)."""
    import jax

    from bench import check, roofline, spec
    from bench.program import Program, kernel_calls
    from bench.reference import stage2
    from bench.seeds import reference_weights

    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == BACKEND_COMPILE else None)
    ref = spec.reference(cell)
    sizes, traffic = cell.config["model"], cell.traffic
    log(f"[{cell.name}] seed {seed}; {time.time() - T_START:.2f} s since "
        f"start")

    prog = Program(cell, ref, seed)
    hlo = prog.compiled.as_text()
    kernels = kernel_calls(hlo)
    log(f"[{cell.name}] {cell.config['name']}: flat {prog.setup.flat_pad} "
        f"per rank, {prog.setup.n_code} coding ranks x {prog.setup.b_loc} "
        f"rows x {traffic['seq_len']}; compile {prog.compile_s:.2f} s; "
        f"Mosaic kernels {sorted(kernels)}")
    if on_chip:
        missing = set(traffic["kernels"]) - kernels
        if missing:
            raise SystemExit(f"[{cell.name}] the compiled step lacks the "
                             f"Mosaic kernels {sorted(missing)}: the wire "
                             f"fell back to its jnp path")
    if fault is not None:
        prog.call = fault(prog.compiled)
    log(f"[{cell.name}] compiled memory_analysis {json.dumps(prog.memory())}")

    # the checked first steps, through the window's own call and feed
    log(f"[{cell.name}] built and compiled {time.time() - T_START:.2f} s "
        f"since start")
    observed, batches = prog.first_steps(CHECKED_STEPS)
    log(f"[{cell.name}] checked steps: losses {observed['losses']}; "
        f"{time.time() - T_START:.2f} s since start")

    w = _window(prog, seconds, trace, compiles)
    steps = len(w["losses"])
    tokens_per_s = steps * cell.tokens_per_step / w["window_s"]
    failed = sum(1 for x in w["losses"] if not math.isfinite(x))
    log(f"[{cell.name}] window: {steps} steps in {w['window_s']:.3f} s, "
        f"{tokens_per_s:.1f} tokens/s; set-up {w['setup_s']:.2f} s; "
        f"compiles inside {w['compiles']}; full collections inside "
        f"{w['full_gcs']}; slowest step {w['slowest_s']:.3f} s; losses "
        f"{w['losses'][:3]} ...")
    log(f"[{cell.name}] host probe, slowest and median step of the window "
        f"(phases: input, dispatch, wait): {json.dumps(w['stall'])}")

    # the allocator's peak leaves out the region the runtime reserves for
    # the loaded programs' temporaries: the chip's peak is the two together
    stats = [d.memory_stats() or {} for d in jax.devices()[:cell.chips]]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    log(f"[{cell.name}] memory_stats after the window "
        f"{json.dumps(stats[0], sort_keys=True)}")

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    metrics, breakdown = {}, None
    if trace:
        red = _reduce_trace(prog)
        ctx = Context(
            reduction=red, compile_s=prog.compile_s, window_s=w["window_s"],
            tokens_per_s=tokens_per_s, chips=cell.chips, peaks=peaks,
            flops_per_token=ref.flops_per_token(sizes, traffic["seq_len"]),
            kernel_shapes=roofline.custom_calls(hlo),
            kernel_cost=spec.kernel_cost)
        for mdef in cell.per_layer:
            v = spec.metric_reader(mdef["name"]).read(ctx)
            if v is not None:
                metrics[mdef["name"]] = {"value": v, "unit": mdef["unit"]}
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.idle_gaps(10)}
        log(f"[{cell.name}] trace: {red.steps} steps, stages "
            f"{json.dumps(red.stage_s())}")
    else:
        values = {"tokens_per_s": tokens_per_s, "setup_s": w["setup_s"]}
        for mdef in cell.end_to_end:
            metrics[mdef["name"]] = {"value": values[mdef["name"]],
                                     "unit": mdef["unit"]}

    # the check: program state freed, then the plain reference
    prog.close()
    del prog
    gc.collect()
    t0 = time.perf_counter()
    expected = stage2.observe(ref, sizes, traffic,
                              reference_weights(ref, sizes, seed), batches)
    numbers = check.compare(observed, expected, cell.limits.get("groups"))
    numbers["compiles_in_window"] = w["compiles"]
    limits = dict(cell.limits["limits"], compiles_in_window=0)
    correct = check.verdict(numbers, limits) and failed == 0
    log(f"[{cell.name}] reference {time.perf_counter() - t0:.2f} s: losses "
        f"{expected['losses']} (step seconds "
        f"{[round(x, 2) for x in expected['seconds']]}); not compared "
        f"{ {k: v for k, v in numbers.items() if k not in limits} }; "
        f"worst leaves {check.worst_leaves(observed, expected)}")
    result = {"correct": bool(correct), "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                       for k in limits}
    for k in limits:
        log(f"check {k} {numbers[k]!r} limit {limits[k]!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    from bench import spec
    cell = spec.load_cell(args.workload)
    log(f"bench: compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
            f"nothing was run")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 2
    peaks = spec.peaks(devices[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
