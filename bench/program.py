"""The system under test, driven through its normal training path.

`build_train_setup`, the jitted `train_step` with params and e donated,
compiled ahead of time through the persistent compile cache, fed by the
program's own `batch_stream`.  The weights are the benchmark's: made on
the device from the seed by the configuration's reference init law, in
one jitted call, and handed to the program leaf by leaf by path.
"""
from __future__ import annotations

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import leaf_order, make_weights
from bench.seeds import seed_keys


def kernel_calls(hlo_text: str) -> set:
    """Names of the Mosaic kernels (tpu_custom_call) in a compiled step."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target="
        r"\"tpu_custom_call\"", hlo_text)}


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - b)))


class Program:
    """One cell's compiled step, its state and its feed."""

    def __init__(self, cell, ref, seed: int):
        from repro.compat import make_mesh
        from repro.configs import REGISTRY
        from repro.configs.common import ShapeCfg
        from repro.core.plan import PlanSpec
        from repro.launch.train import TrainRun, batch_stream, \
            build_train_setup

        t, w = cell.traffic, cell.traffic["wire"]
        arch = REGISTRY[cell.config["registry"]]
        self.model_sizes = cell.config["model"]
        spec = dataclasses.replace(
            arch, config=dataclasses.replace(arch.config, **self.model_sizes),
            coding=dataclasses.replace(arch.coding,
                                       straggler_p=t["straggler_p"]))
        mesh = make_mesh((cell.chips, 1), ("data", "model"),
                         devices=jax.devices()[:cell.chips])
        shape = ShapeCfg("train", t["seq_len"], cell.global_batch)
        c = arch.coding
        plan = PlanSpec(
            d=t["d"], compressor=w["compressor"],
            group_size=w.get("group_size", c.group_size),
            k_per_block=w.get("k_per_block", c.k_per_block),
            block_size=w.get("block_size", c.block_size),
            value_dtype=w.get("value_dtype", c.wire_dtype),
            backend=t["backend"])
        self.setup = s = build_train_setup(
            spec, mesh, shape, TrainRun(mode=t["mode"], base_lr=t["lr"],
                                        plan=plan, straggler=t["straggler"]))
        self.keys = seed_keys(seed)

        # the program's parameter tree must be the reference's, leaf by leaf
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(
            s.model.param_shapes())
        self.paths = [path_name(p) for p, _ in flat]
        ref_shapes = ref.param_shapes(self.model_sizes)
        got = {path_name(p): tuple(l.shape) for p, l in flat}
        if got != {k: tuple(v) for k, v in ref_shapes.items()}:
            raise ValueError(
                f"the program's parameters differ from the reference's: "
                f"{sorted(set(got.items()) ^ set(ref_shapes.items()))[:6]}")
        self.order = leaf_order(ref_shapes)
        laws = ref.init_laws(self.model_sizes)
        shard = jax.tree.leaves(s.param_shardings)

        # the key is an argument, not a constant: one compiled maker (and
        # one entry of the compile cache) serves every seed
        def weights(key):
            d = make_weights(ref_shapes, laws, key)
            return jax.tree_util.tree_unflatten(
                self.treedef, [d[p] for p in self.paths])
        placed = jax.tree_util.tree_unflatten(self.treedef, shard)
        self.params = jax.jit(weights, out_shardings=placed)(
            self.keys["weights"])
        state_shape = s.mesh.devices.shape + (s.flat_pad,)

        def zeros():
            return jax.jit(lambda: jnp.zeros(state_shape, jnp.float32),
                           out_shardings=s.state_sharding)()
        self.e = zeros()
        self.opt = tuple(zeros() for _ in s.input_specs()["opt"])
        self.batches = batch_stream(s, spec, shape, self.keys["batches"])
        self.step_key = self.keys["step"]
        self.step = 0

        t0 = time.perf_counter()
        specs = s.input_specs()
        self.compiled = jax.jit(s.train_step, donate_argnums=(0, 1)).lower(
            self.params, self.e, self.opt, specs["batch"], specs["step"],
            specs["key"]).compile()
        self.compile_s = time.perf_counter() - t0
        self.call = self.compiled
        self.order_idx = [self.paths.index(p) for p in self.order]

    def next_batch(self):
        return next(self.batches)

    def dispatch(self, batch):
        """Enqueue one step; returns its metrics (loss) without waiting."""
        self.params, self.e, self.opt, m = self.call(
            self.params, self.e, self.opt, batch, jnp.int32(self.step),
            self.step_key)
        self.step += 1
        return m

    def first_steps(self, n: int):
        """Run the first n steps through the step's own call and feed,
        waiting for each.  Returns what the check compares (each step's
        loss, per-leaf norms of theta_0 - theta_1 and of theta_n -
        theta_0) and the token rows each step trained on.  theta_0 is kept
        on the host, so that no second copy of the weights holds device
        memory next to the step's."""
        seq = self.setup.seq_len
        observed = {"losses": []}
        rows = []
        theta_0 = jax.device_get(jax.tree.leaves(self.params))
        for t in range(n):
            batch = self.next_batch()
            rows.append(np.asarray(batch["inputs"]).reshape(-1, seq + 1))
            m = self.dispatch(batch)
            observed["losses"].append(float(m["loss"]))
            if t == 0:
                observed["first_update"] = self._leaf_norms(theta_0)
        observed["change"] = self._leaf_norms(theta_0)
        return observed, rows

    def _leaf_norms(self, theta_0: list):
        """Per-leaf norms of theta_0 - the current parameters, in the
        reference's leaf order; theta_0 goes back to the device one leaf
        at a time."""
        now = jax.tree.leaves(self.params)
        return np.array([float(_diff_norm(now[i], theta_0[i]))
                         for i in self.order_idx], np.float64)

    def memory(self) -> dict:
        mem = self.compiled.memory_analysis()
        return {"argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "generated_code_bytes": int(mem.generated_code_size_in_bytes)}

    def close(self) -> None:
        self.batches.close()
        del self.params, self.e, self.opt, self.compiled, self.call
