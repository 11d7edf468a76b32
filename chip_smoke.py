#!/usr/bin/env python3
"""Run the COCO-EF train step on a TPU and check what it computes.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the coded path across four chips

Both phases build the normal training path (`build_train_setup`, the jitted
`train_step` with params and e donated, `cocoef_update`, the Pallas wire
kernels) for xlstm-1.3b at its published widths, cut in depth to one
period of its block pattern (8 blocks: 7 mLSTM, 1 sLSTM), at sequence
length 2048 and global batch 4, with weights and tokens made from a seed.

One chip, for the sign wire and then the block top-K wire:
  * the compiled step must hold the EF kernel and the decode kernel as
    Mosaic custom calls (a shape that falls back to the jnp path fails);
  * 3 steps, each ending in `block_until_ready`, with finite loss;
  * params and e after one step must match the jnp backend's step on the
    same batch within `RTOL`.

`--four-chips` runs only the path across chips: a (data=4, model=1) mesh,
d=2, iid stragglers at p=0.1, the sign and block top-K steps against
`mode="dense"` (SGC) on the same batches, then the reference-vs-mesh parity
gate (`launch.parity.run_parity`) for every parity wire.

Everything runs in this one process.  The last line of stdout is a JSON
object naming the device.  With no TPU, or when any check fails, the script
exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "xlstm-1.3b"
NUM_LAYERS = 8            # one period of the 7:1 mLSTM/sLSTM block pattern
SEQ_LEN, GLOBAL_BATCH = 2048, 4
STEPS = 3
SEED = 0
WIRES = ("sign", "block_topk")
KERNELS = {"sign": ("ef_sign_fused", "sign_decode_reduce"),
           "block_topk": ("ef_topk_fused", "topk_decode_reduce")}
# Pallas vs jnp after one step.  The two backends pick the same sign bits
# and the same top-k indices, but the kernels sum a group's |acc| (the sign
# scale) and divide by a block's scale (top-k values) in their own order
# and rounding, so e and ghat agree to a few f32 ulps (~1e-7 relative),
# not bit for bit.  So e may differ by RTOL of its largest magnitude, and
# a parameter by RTOL of the largest update plus 2 ulps of itself (a few
# ulps of ghat can round p - ghat to the neighbouring float).  A wrong sign
# bit or index moves a coordinate by about its group's scale, which these
# bounds catch for every group above 1e-5 of the largest.
RTOL = 1e-5
FOUR_CHIP_P = 0.1
FOUR_CHIP_D = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def kernel_calls(hlo_text: str) -> set:
    """Names of the Mosaic kernels (tpu_custom_call) in a compiled step."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target="
        r"\"tpu_custom_call\"", hlo_text)}


def cut_spec():
    """xlstm-1.3b at every published width, depth cut to NUM_LAYERS."""
    from repro.configs import REGISTRY
    spec = REGISTRY[ARCH]
    cfg = spec.config
    log(f"model {ARCH}: num_layers {cfg.num_layers} -> {NUM_LAYERS} "
        f"(slstm_every={cfg.slstm_every}); d_model {cfg.d_model}, "
        f"{cfg.num_heads} heads x {cfg.head_dim}, vocab {cfg.vocab_size} "
        f"as published; seq {SEQ_LEN}, global batch {GLOBAL_BATCH}")
    return dataclasses.replace(spec, config=cfg.scaled(num_layers=NUM_LAYERS))


def plan_for(spec, compressor: str, d: int, backend: str):
    from repro.core.plan import PlanSpec
    c = spec.coding
    return PlanSpec(d=d, compressor=compressor, group_size=c.group_size,
                    k_per_block=c.k_per_block, block_size=c.block_size,
                    value_dtype=c.wire_dtype, backend=backend)


class Trainer:
    """One setup's compiled step, its state and its batches."""

    def __init__(self, spec, mesh, shape, run, label: str):
        import jax
        import jax.numpy as jnp
        from repro.launch.train import batch_stream, build_train_setup
        self.label = label
        self.setup = build_train_setup(spec, mesh, shape, run)
        self.key = jax.random.PRNGKey(SEED)
        self.params, self.e, self.opt = self.setup.init_state(self.key)
        self.batches = batch_stream(self.setup, spec, shape, self.key)
        self.step = 0
        batch = next(self.batches)
        t0 = time.perf_counter()
        lowered = jax.jit(self.setup.train_step,
                          donate_argnums=(0, 1)).lower(
            self.params, self.e, self.opt, batch, jnp.int32(0),
            self.key)
        self.compiled = lowered.compile()
        self.compile_s = time.perf_counter() - t0
        self._next = batch
        mem = self.compiled.memory_analysis()
        self.kernels = kernel_calls(self.compiled.as_text())
        log(f"[{label}] flat_pad {self.setup.flat_pad}, coding ranks "
            f"{self.setup.n_code}, per-rank batch {self.setup.b_loc}, "
            f"p {self.setup.cocoef_cfg.straggler_p}; compile "
            f"{self.compile_s:.1f} s; args "
            f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temp "
            f"{mem.temp_size_in_bytes / 2**30:.2f} GiB; kernels "
            f"{sorted(self.kernels)}")

    def run(self, steps: int) -> list:
        import jax
        import jax.numpy as jnp
        losses = []
        for _ in range(steps):
            batch = self._next if self._next is not None \
                else next(self.batches)
            self._next = None
            t0 = time.perf_counter()
            self.params, self.e, self.opt, m = self.compiled(
                self.params, self.e, self.opt, batch,
                jnp.int32(self.step), self.key)
            jax.block_until_ready((self.params, self.e, m))
            ms = (time.perf_counter() - t0) * 1e3
            losses.append(float(m["loss"]))
            log(f"[{self.label}] step {self.step}: loss {losses[-1]:.6f}, "
                f"{ms:.1f} ms (host clock, informational)")
            self.step += 1
        return losses

    def host_state(self):
        """Host copies of (params, e)."""
        import jax
        import numpy as np
        return jax.tree.map(np.array, self.params), np.array(self.e)

    def close(self) -> None:
        self.batches.close()
        del self.params, self.e, self.opt


def rel_err(a, b) -> float:
    """max |a - b| / max |b|."""
    import numpy as np
    scale = max(float(np.max(np.abs(b))), np.finfo(np.float32).tiny)
    return float(np.max(np.abs(a - b))) / scale


def param_err(p1, p1j, p0) -> float:
    """Largest |p1 - p1j| over its allowance (see RTOL); <= 1 passes."""
    import numpy as np
    upd = max(float(np.max(np.abs(b - z))) for b, z in zip(p1j, p0))
    return max(float(np.max(np.abs(a - b)
                            / (RTOL * upd + 2 * np.spacing(np.abs(b)))))
               for a, b in zip(p1, p1j))


def one_chip(failures: list) -> None:
    import jax
    import numpy as np
    from repro.compat import make_mesh
    from repro.configs.common import ShapeCfg
    from repro.launch.train import TrainRun

    spec = cut_spec()
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    shape = ShapeCfg("train", SEQ_LEN, GLOBAL_BATCH)
    p0 = None
    for wire in WIRES:
        run = TrainRun(mode="cocoef",
                       plan=plan_for(spec, wire, 1, "pallas"))
        tr = Trainer(spec, mesh, shape, run, f"{wire}/pallas")
        missing = set(KERNELS[wire]) - tr.kernels
        if missing:
            failures.append(f"{wire}: kernels {sorted(missing)} not in the "
                            f"compiled step")
        if p0 is None:
            p0 = jax.tree.map(np.array, tr.params)
        losses = tr.run(1)
        p1, e1 = tr.host_state()
        losses += tr.run(STEPS - 1)
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
        log(f"[{wire}/pallas] losses {losses}; peak_bytes_in_use {peak}")
        if not np.all(np.isfinite(losses)):
            failures.append(f"{wire}: non-finite loss {losses}")
        tr.close()

        ref = Trainer(spec, mesh, shape,
                      dataclasses.replace(run, plan=plan_for(spec, wire, 1,
                                                             "jnp")),
                      f"{wire}/jnp")
        ref_loss = ref.run(1)
        p1j, e1j = ref.host_state()
        ref.close()
        leaves = [jax.tree.leaves(t) for t in (p1, p1j, p0)]
        err_p = param_err(*leaves)
        err_e = rel_err(e1, e1j)
        ok = err_p <= 1 and err_e <= RTOL
        log(f"[{wire}] pallas vs jnp after one step: params {err_p:.3e} of "
            f"their allowance, e rel err {err_e:.3e} (RTOL {RTOL:g}); "
            f"max |dp| {max(np.max(np.abs(a - b)) for a, b in zip(*leaves[:2])):.3e}; "
            f"step-0 loss {losses[0]:.6f} vs {ref_loss[0]:.6f}: "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"{wire}: pallas vs jnp beyond RTOL "
                            f"(params {err_p:.3e}, e {err_e:.3e})")
        del p1, e1, p1j, e1j


def four_chips(failures: list) -> None:
    import numpy as np
    from repro.configs.common import ShapeCfg
    from repro.launch.mesh import make_host_mesh
    from repro.launch.parity import PARITY_COMPRESSORS, run_parity
    from repro.launch.train import TrainRun

    spec = cut_spec()
    spec = dataclasses.replace(spec, coding=dataclasses.replace(
        spec.coding, straggler_p=FOUR_CHIP_P))
    mesh = make_host_mesh()
    shape = ShapeCfg("train", SEQ_LEN, GLOBAL_BATCH)
    losses = {}
    for mode, wire in (("dense", "sign"), ("cocoef", "sign"),
                       ("cocoef", "block_topk")):
        run = TrainRun(mode=mode, straggler="iid",
                       plan=plan_for(spec, wire, FOUR_CHIP_D, "pallas"))
        label = "dense" if mode == "dense" else wire
        tr = Trainer(spec, mesh, shape, run, label)
        if mode != "dense":
            missing = set(KERNELS[wire]) - tr.kernels
            if missing:
                failures.append(f"{label}: kernels {sorted(missing)} not "
                                f"in the compiled step")
        losses[label] = tr.run(STEPS)
        tr.close()
        if not np.all(np.isfinite(losses[label])):
            failures.append(f"{label}: non-finite loss {losses[label]}")
    log(f"losses on the same batches: {json.dumps(losses)}")

    for comp in PARITY_COMPRESSORS:
        # dim 2048 puts a whole kernel tile in every all_to_all chunk, so
        # the decode kernels run (at the gate's default 1024 a chunk is
        # half a tile and falls back to the jnp path)
        rep = run_parity(comp, N=4, shards=1, dim=2048, backend="pallas")
        log(f"[parity {comp}] bitexact {rep['bitexact']}, max |dtheta| "
            f"{rep['max_abs_diff_theta']:.3e}, max |de| "
            f"{rep['max_abs_diff_e']:.3e}, first divergence "
            f"{rep['first_divergence']}, loss {rep['loss_start']:.6f} -> "
            f"ref {rep['loss_ref']:.6f} / mesh {rep['loss_mesh']:.6f}")
        if not (np.isfinite(rep["loss_mesh"])
                and rep["loss_mesh"] < rep["loss_start"]):
            failures.append(f"parity {comp}: mesh loss did not fall")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the coded path across four chips")
    args = ap.parse_args()

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    log(f"compile cache: {cache_dir}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")

    failures: list = []
    (four_chips if args.four_chips else one_chip)(failures)
    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
