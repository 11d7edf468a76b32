"""The wire kernels of the train path compile for a TPU v5e at real size.

Nothing runs: each kernel is lowered with `interpret=False` and compiled
for a described (not attached) v5e chip, which raises what the chip's
compiler would raise (an unsupported op, a misaligned tile, too much VMEM).
The size is the flat vector of `chip_smoke.py`: xlstm-1.3b at its published
widths cut to one 8-block period, and N=4 senders for the decode kernels.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU compiler's library,
and every test worker imports every test file.
"""
import os

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
def flat_pad():
    spec = REGISTRY["xlstm-1.3b"]
    cfg = spec.config.scaled(num_layers=8)
    n = sum(l.size for l in jax.tree.leaves(Model(cfg).param_shapes()))
    pad = max(CocoEFConfig(compressor=c).pad_multiple
              for c in ("sign", "block_topk"))
    return padded_size(n, N_SENDERS, pad)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, n, sh):
    """(fn, abstract args) of one train-path kernel at flat size n."""
    vec, scalar = _sds((n,), jnp.float32, sh), _sds((), jnp.float32, sh)
    mask = _sds((N_SENDERS,), jnp.float32, sh)
    chunk = n // N_SENDERS
    if name == "ef_sign_fused":
        return (lambda g, e, a, m: sp.ef_sign_fused(
            g, e, a, m, GROUP, want_c=False, interpret=False),
            (vec, vec, scalar, scalar))
    if name == "sign_decode_reduce":
        return (lambda w, s, m: sp.sign_decode_reduce(
            w, s, m, GROUP, interpret=False),
            (_sds((N_SENDERS, chunk // 32), jnp.uint32, sh),
             _sds((N_SENDERS, chunk // GROUP), jnp.float32, sh), mask))
    if name == "ef_topk_fused":
        return (lambda g, e, a, m: tp.ef_topk_fused(
            g, e, a, m, K, BLOCK, want_c=False, interpret=False),
            (vec, vec, scalar, scalar))
    rows = chunk // BLOCK
    return (lambda i, v, s, m: tp.topk_decode_reduce(
        i, v, s, m, BLOCK, interpret=False),
        (_sds((N_SENDERS, rows, K), jnp.uint16, sh),
         _sds((N_SENDERS, rows, K), jnp.float32, sh),
         _sds((N_SENDERS, rows), jnp.float32, sh), mask))


@pytest.mark.parametrize("kernel", ["ef_sign_fused", "sign_decode_reduce",
                                    "ef_topk_fused", "topk_decode_reduce"])
def test_train_path_kernel_compiles_for_v5e(kernel, one_chip, flat_pad):
    fn, args = _kernel_case(kernel, flat_pad, one_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text, kernel
    assert f"%{kernel}" in text, f"{kernel} is not the Mosaic call"
