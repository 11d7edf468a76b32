#!/usr/bin/env python3
"""Readings that set a cell's limits: the program on many seeds, and the
control and faults in the program's place.  Not part of a benchmark run.

    python bench/control.py --workload xlstm-sign --seeds 1,2,3 \
        [--controls 1,2,3] [--witness 1,2] [--leaves out.jsonl]

For every seed of --seeds: the program's first steps through its compiled
step and feed (as bench/run.py takes them, without the window), then the
float32 reference on the same weights and tokens; one JSON line with the
compared numbers ("program").  For every seed of --controls, in the
program's place against the same reference:

  control_fp8   the reference with every matmul operand rounded to
                float8_e4m3fn, the precision below the configuration's
                bfloat16 matmuls;
  half_batch    the reference on the first half of each batch's rows, the
                mean over those alone.

For every seed of --witness, the configuration's witness
(bench/witness/<reference>.py: the reference rounded to bfloat16 wherever
the configuration states it) in the program's place: how far the
configuration's rounding alone moves each number on that seed; and the
program against the witness ("program_vs_witness").  --leaves appends
every per-leaf norm behind the numbers to a JSON-lines file.

A step that returns its state unchanged reads 1 on every gap of the
update and the change by construction and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _norms(obs: dict) -> dict:
    return {k: [float(x) for x in obs[k]] for k in ("first_update", "change")}


def readings(cell, seed: int, with_program: bool, with_controls: bool,
             with_witness: bool):
    """One seed's numbers (the line printed) and per-leaf norms."""
    import importlib

    import jax.numpy as jnp

    from bench import check, spec
    from bench.program import Program
    from bench.reference import stage2
    from bench.seeds import reference_weights

    ref = spec.reference(cell)
    sizes, traffic = cell.config["model"], cell.traffic
    prog = Program(cell, ref, seed)
    observed, rows = prog.first_steps(3)
    prog.close()
    del prog
    gc.collect()
    weights = reference_weights(ref, sizes, seed)
    expected = stage2.observe(ref, sizes, traffic, weights, rows)
    out = {"seed": seed, "reference_losses": expected["losses"]}
    leaves = {"seed": seed, "leaves": expected["leaves"],
              "first_grad": [float(x) for x in expected["first_grad"]],
              "reference": _norms(expected)}
    others = {}
    if with_program:
        others["program"] = observed
        out["program_losses"] = observed["losses"]
    if with_controls:
        others["control_fp8"] = stage2.observe(
            ref, sizes, traffic, weights, rows, low=jnp.float8_e4m3fn)
        others["half_batch"] = stage2.observe(
            ref, sizes, traffic, weights, rows,
            rows=list(range(rows[0].shape[0] // 2)))
    if with_witness:
        witness = importlib.import_module(
            f"bench.witness.{cell.config['reference']}")
        others["witness"] = stage2.observe(witness, sizes, traffic, weights,
                                           rows)
    groups = cell.limits.get("groups")
    for name, obs in others.items():
        out[name] = check.compare(obs, expected, groups)
        out[name + "_worst"] = check.worst_leaves(obs, expected)
        leaves[name] = _norms(obs)
    if with_program and with_witness:
        out["program_vs_witness"] = check.compare(
            observed, others["witness"], groups)
        out["program_vs_witness_worst"] = check.worst_leaves(
            observed, others["witness"])
    return out, leaves


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--witness", default="")
    ap.add_argument("--leaves", default="")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    from bench import spec
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)
    lists = [[int(s) for s in a.split(",") if s]
             for a in (args.seeds, args.controls, args.witness)]
    for seed in dict.fromkeys(sum(lists, [])):
        out, leaves = readings(cell, seed, *(seed in x for x in lists))
        print(json.dumps(out), flush=True)
        if args.leaves:
            with open(args.leaves, "a") as f:
                f.write(json.dumps(leaves) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
