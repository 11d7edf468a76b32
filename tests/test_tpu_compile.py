"""The wire kernels of the train path compile for a TPU v5e at real size.

Nothing runs: each kernel is lowered with `interpret=False` and compiled
for a described (not attached) v5e chip, which raises what the chip's
compiler would raise (an unsupported op, a misaligned tile, too much VMEM).
The size is the flat vector of `chip_smoke.py`: xlstm-1.3b at its published
widths cut to one 8-block period, and N=4 senders for the decode kernels;
`topk_pack`, which no train-path cell runs, shares `ef_topk_fused`'s
selection and tile and is compiled at the same size.  The sign kernels
are compiled at the deepseek-v2-lite cell's flat size too (one sender,
d=1); both sizes leave them a ragged last grid step.  The whole train
step of the deepseek-v2-lite cell (bench/configs/deepseek-v2-lite-l5e8.json:
MLA, the dropless expert layer's grouped matmuls, the sign wire) is
compiled at its real size too, as bench/program.py builds it.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU compiler's library,
and every test worker imports every test file.
"""
import dataclasses
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import REGISTRY
from repro.core.cocoef import CocoEFConfig, padded_size
from repro.kernels import sign_pack as sp, topk_pack as tp
from repro.nn import Model

N_SENDERS = 4
GROUP, BLOCK, K = 512, 256, 8     # xlstm-1.3b's coding plan


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def topo_devices(one_chip):
    return list(one_chip.device_set)


def _n_params(cfg) -> int:
    return sum(l.size for l in jax.tree.leaves(Model(cfg).param_shapes()))


def _deepseek_spec():
    root = Path(__file__).resolve().parents[1]
    model = json.loads((root / "bench/configs/deepseek-v2-lite-l5e8.json")
                       .read_text())["model"]
    arch = REGISTRY["deepseek-v2-lite-16b"]
    return dataclasses.replace(
        arch, config=dataclasses.replace(arch.config, **model),
        coding=dataclasses.replace(arch.coding, straggler_p=0.0))


@pytest.fixture(scope="module")
def flat_pad():
    spec = REGISTRY["xlstm-1.3b"]
    n = _n_params(spec.config.scaled(num_layers=8))
    pad = max(CocoEFConfig(compressor=c).pad_multiple
              for c in ("sign", "block_topk"))
    return padded_size(n, N_SENDERS, pad)


def test_pad_multiple_pins_the_flat_layout():
    """The kernels' grid tile is not the flat padding: every cell's flat
    size stays a multiple of 8 groups of 512 (sign) and of lcm(that, 8
    blocks of 256) (block top-K)."""
    assert CocoEFConfig(compressor="sign").pad_multiple == 4096
    assert CocoEFConfig(compressor="block_topk").pad_multiple == 4096


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, n, sh, senders=N_SENDERS):
    """(fn, abstract args) of one train-path kernel at flat size n."""
    vec, scalar = _sds((n,), jnp.float32, sh), _sds((), jnp.float32, sh)
    mask = _sds((senders,), jnp.float32, sh)
    chunk = n // senders
    if name == "ef_sign_fused":
        return (lambda g, e, a, m: sp.ef_sign_fused(
            g, e, a, m, GROUP, want_c=False, interpret=False),
            (vec, vec, scalar, scalar))
    if name == "sign_decode_reduce":
        return (lambda w, s, m: sp.sign_decode_reduce(
            w, s, m, GROUP, interpret=False),
            (_sds((senders, chunk // 32), jnp.uint32, sh),
             _sds((senders, chunk // GROUP), jnp.float32, sh), mask))
    if name == "ef_topk_fused":
        return (lambda g, e, a, m: tp.ef_topk_fused(
            g, e, a, m, K, BLOCK, want_c=False, interpret=False),
            (vec, vec, scalar, scalar))
    if name == "topk_pack":
        return (lambda x: tp.topk_pack(x, K, BLOCK, interpret=False), (vec,))
    rows = chunk // BLOCK
    return (lambda i, v, s, m: tp.topk_decode_reduce(
        i, v, s, m, BLOCK, interpret=False),
        (_sds((N_SENDERS, rows, K), jnp.uint16, sh),
         _sds((N_SENDERS, rows, K), jnp.float32, sh),
         _sds((N_SENDERS, rows), jnp.float32, sh), mask))


@pytest.mark.parametrize("kernel", ["ef_sign_fused", "sign_decode_reduce",
                                    "ef_topk_fused", "topk_decode_reduce",
                                    "topk_pack"])
def test_train_path_kernel_compiles_for_v5e(kernel, one_chip, flat_pad):
    if kernel in ("ef_sign_fused", "sign_decode_reduce"):
        assert (flat_pad // N_SENDERS // GROUP) % sp.TILE_GROUPS, \
            "the last grid step is not ragged"
    fn, args = _kernel_case(kernel, flat_pad, one_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text, kernel
    assert f"%{kernel}" in text, f"{kernel} is not the Mosaic call"


@pytest.mark.parametrize("kernel", ["ef_sign_fused", "sign_decode_reduce"])
def test_sign_kernels_compile_at_deepseek_size(kernel, one_chip):
    spec = _deepseek_spec()
    n = padded_size(_n_params(spec.config), 1,
                    CocoEFConfig(compressor="sign").pad_multiple)
    assert (n // GROUP) % sp.TILE_GROUPS, "the last grid step is not ragged"
    fn, args = _kernel_case(kernel, n, one_chip, senders=1)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text, kernel
    assert f"%{kernel}" in text, f"{kernel} is not the Mosaic call"


def test_deepseek_train_step_compiles_for_v5e(topo_devices, monkeypatch):
    from repro.compat import make_mesh
    from repro.configs.common import ShapeCfg
    from repro.core.plan import PlanSpec
    from repro.kernels import ops
    from repro.launch.train import TrainRun, build_train_setup
    root = Path(__file__).resolve().parents[1]
    traffic = json.loads((root / "bench/traffic/sign.json").read_text())
    # code that asks for the default backend sees the CPU here
    monkeypatch.setattr(ops, "default_use_pallas", lambda: True)
    spec = _deepseek_spec()
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo_devices[:1])
    plan = PlanSpec(d=1, compressor="sign", group_size=GROUP,
                    backend="pallas")
    setup = build_train_setup(
        spec, mesh, ShapeCfg("train", traffic["seq_len"],
                             traffic["rows_per_chip"]),
        TrainRun(mode="cocoef", base_lr=traffic["lr"], plan=plan))
    specs = setup.input_specs()
    compiled = jax.jit(setup.train_step, donate_argnums=(0, 1)).lower(
        specs["params"], specs["e"], specs["opt"], specs["batch"],
        specs["step"], specs["key"]).compile()
    text = compiled.as_text()
    for kernel in traffic["kernels"]:
        assert f"%{kernel}" in text, f"{kernel} is not a Mosaic call"
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < 16e9, used
