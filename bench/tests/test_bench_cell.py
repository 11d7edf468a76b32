"""A tiny cell driven through the harness on the CPU: a sound run is
correct; the timed path broken underneath, or the control in the
program's place, is not."""
from __future__ import annotations

import pytest

from bench.tests.tiny import make_root

import jax  # noqa: E402  (after tiny sets JAX_PLATFORMS)
import jax.numpy as jnp  # noqa: E402

SEED = 2**31 + 977          # above 32 signed bits, as the driver's are


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    from bench import spec
    return spec.load_cell("tiny", make_root(tmp_path_factory.mktemp("root")))


def run(cell, fault=None, trace=False):
    from bench.run import run_cell
    return run_cell(cell, SEED, 0.5, trace, None, on_chip=False, fault=fault)


def unchanged(call):
    """A step that returns its state unchanged (it still reports a loss)."""
    def step(params, e, opt, batch, i, key):
        out = call(jax.tree.map(jnp.copy, params), jnp.copy(e), opt, batch,
                   i, key)
        return params, e, opt, out[3]
    return step


def half_batch(call):
    """Half of the batch left out, the mean taken over the rest."""
    def step(params, e, opt, batch, i, key):
        w = batch["weights"]
        keep = w.shape[1] // 2
        return call(params, e, opt,
                    dict(batch, weights=w.at[:, keep:].set(0.0) * 2.0),
                    i, key)
    return step


def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"] is True, res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(res)[-1] == "check"


def test_traced_run_reads_its_per_layer_metrics(cell, tmp_path, monkeypatch):
    import bench.run
    monkeypatch.setattr(bench.run, "TRACE_DIR", tmp_path / "trace")
    res = run(cell, trace=True)
    assert res["correct"] is True, res["check"]
    # off the chip the trace holds no TPU plane: only the host clock reads
    assert set(res["metrics"]) == {"setup.compile_s"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0
    assert not (tmp_path / "trace").exists()


@pytest.mark.parametrize("fault", [unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(cell, fault):
    res = run(cell, fault)
    assert res["correct"] is False
    failed = [k for k, v in res["check"].items() if v["value"] > v["limit"]]
    assert failed, res["check"]


def test_control_in_lower_precision_is_not_correct(cell):
    """The reference with float8 matmul operands in the program's place."""
    from bench import check, spec
    from bench.program import Program
    from bench.reference import stage2
    from bench.seeds import reference_weights

    ref = spec.reference(cell)
    sizes = cell.config["model"]
    prog = Program(cell, ref, SEED)
    _, rows = prog.first_steps(3)
    prog.close()
    weights = reference_weights(ref, sizes, SEED)
    expected = stage2.observe(ref, sizes, cell.traffic, weights, rows)
    control = stage2.observe(ref, sizes, cell.traffic, weights, rows,
                             low=jnp.float8_e4m3fn)
    numbers = check.compare(control, expected)
    assert not check.verdict(numbers, cell.limits["limits"]), numbers
