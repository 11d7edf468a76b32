"""The harness finds configurations, cells, traffic, limits, per-layer
metrics, kernel costs and peaks by name, from files alone."""
from __future__ import annotations

import json

import pytest

from bench.tests.tiny import REPO, make_root, write_json


def test_new_cell_config_and_metric_found_by_name(tmp_path):
    from bench import spec
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # a configuration, a traffic mix, a cell and a metric, each a new file
    write_json(root / "bench/configs/xlstm-tiny2.json",
               {"name": "xlstm-tiny2", "registry": "xlstm-1.3b",
                "reference": "xlstm", "model": {"num_layers": 4}})
    write_json(root / "bench/traffic/tiny-topk.json",
               {"seq_len": 16, "rows_per_chip": 2, "d": 1})
    write_json(root / "bench/limits/tiny2.json", {"limits": {"loss_gap": 1}})
    (root / "bench/metrics").mkdir(parents=True)
    (root / "bench/metrics/new.metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.x\n")
    bench["configs"].append({"name": "xlstm-tiny2",
                             "file": "bench/configs/xlstm-tiny2.json"})
    bench["workloads"].append({"name": "tiny2", "config": "xlstm-tiny2",
                               "traffic": "tiny-topk", "chips": 1})
    bench["per_layer"].append({"name": "new.metric", "unit": "ms",
                               "workloads": ["tiny2"]})
    write_json(root / "BENCHMARK.json", bench)

    cell = spec.load_cell("tiny2", root)
    assert cell.config["model"] == {"num_layers": 4}
    assert cell.traffic["seq_len"] == 16 and cell.global_batch == 2
    assert cell.tokens_per_step == 32
    assert cell.limits["limits"] == {"loss_gap": 1}
    # a metric with a `workloads` key applies to those cells alone
    assert [m["name"] for m in cell.per_layer] == [
        "host.input_ms", "setup.compile_s", "new.metric"]
    reader = spec.metric_reader("new.metric", root)
    assert reader.read(type("Ctx", (), {"x": 1.5})()) == 3.0
    assert spec.reference(cell).__name__ == "bench.reference.xlstm"
    with pytest.raises(KeyError):
        spec.load_cell("absent", root)


def test_every_metric_and_kernel_of_the_benchmark_has_its_file():
    from bench import check, spec
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read), m["name"]
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        for k in cell.traffic["kernels"]:
            if any(m["name"] == f"{k}_roofline" for m in cell.per_layer):
                assert callable(spec.kernel_cost(k).cost)
        assert cell.limits["limits"]
        assert set(cell.limits["limits"]) <= check.names(cell.limits)


def test_peaks_known_device_and_unknown_is_an_error():
    from bench import spec
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4"):
        with pytest.raises(KeyError):
            spec.peaks(kind)


def test_kernel_guard_and_custom_call_shapes():
    from bench import roofline
    from bench.program import kernel_calls
    hlo = (
        '  %ef_sign_fused.2 = (u32[31], f32[2], f32[1024]{0}) custom-call('
        'f32[1024]{0} %g, f32[1024]{0} %e, f32[1]{0} %lr), '
        'custom_call_target="tpu_custom_call", backend_config={}\n'
        '  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop\n'
        '  %sign_decode_reduce = f32[1024]{0} custom-call(u32[1,32]{1,0} %w),'
        ' custom_call_target="tpu_custom_call"\n')
    assert kernel_calls(hlo) == {"ef_sign_fused", "sign_decode_reduce"}
    ops, res = roofline.custom_calls(hlo)["ef_sign_fused"]
    assert ops == [("f32", (1024,)), ("f32", (1024,)), ("f32", (1,))]
    assert res == [("u32", (31,)), ("f32", (2,)), ("f32", (1024,))]
    assert roofline.nbytes(ops) == 4 * 2049
