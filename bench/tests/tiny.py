"""A benchmark root in a temporary directory, with tiny cells that the CPU
tests drive through the harness exactly as bench/run.py drives a cell."""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

# readings of tiny sound runs on the CPU (bf16 program vs f32 reference):
# loss_gap ~3e-4, update_gap ~0.012, change_gap ~0.024
TINY_LIMITS = {"update_gap": 0.08, "change_gap": 0.15}


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def make_root(tmp: Path) -> Path:
    """BENCHMARK.json and the files of one tiny xlstm sign cell."""
    sign = json.loads((REPO / "bench/traffic/sign.json").read_text())
    write_json(tmp / "bench/traffic/tiny-sign.json", dict(sign, seq_len=32))
    write_json(tmp / "bench/configs/xlstm-tiny.json", {
        "name": "xlstm-tiny", "registry": "xlstm-1.3b", "reference": "xlstm",
        "model": {"num_layers": 2, "d_model": 64, "num_heads": 2,
                  "num_kv_heads": 2, "head_dim": 32, "vocab_size": 256,
                  "slstm_every": 2, "proj_factor": 2.0}})
    write_json(tmp / "bench/limits/tiny.json", {"limits": TINY_LIMITS})
    write_json(tmp / "BENCHMARK.json", {
        "configs": [{"name": "xlstm-tiny",
                     "file": "bench/configs/xlstm-tiny.json"}],
        "workloads": [{"name": "tiny", "config": "xlstm-tiny",
                       "traffic": "tiny-sign", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "host.input_ms", "unit": "ms"},
                      {"name": "setup.compile_s", "unit": "s"},
                      {"name": "mfu", "unit": "%",
                       "workloads": ["other-cell"]}]})
    return tmp
