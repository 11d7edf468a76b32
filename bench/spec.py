"""Everything the harness finds by name, from files under bench/.

A cell of BENCHMARK.json names a configuration and a traffic mix:

  bench/configs/<config>.json     sizes as run, source, cuts, reference
  bench/traffic/<traffic>.json    the training job: sequence, rows per
                                  chip, mode, wire knobs, d, stragglers,
                                  the Mosaic kernels the wire must run
  bench/limits/<cell>.json        the limit of each number `correct` compares
  bench/reference/<name>.py       the configuration's plain reference
  bench/metrics/<metric>.py       one reader per per-layer metric
  bench/kernels/<kernel>.py       operations and bytes of one kernel
  bench/peaks.json                the chip's peaks, keyed by device_kind

A later cell, configuration or metric is a new file, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import the file at `path` as a module called `name`."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    limits: dict            # bench/limits/<cell>.json
    end_to_end: list        # BENCHMARK.json metrics this cell reports
    per_layer: list

    @property
    def global_batch(self) -> int:
        t = self.traffic
        return t["rows_per_chip"] * self.chips // t["d"]

    @property
    def tokens_per_step(self) -> int:
        """Unique tokens of one step: the global batch times the sequence."""
        return self.global_batch * self.traffic["seq_len"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    configs = {c["name"]: c for c in bench["configs"]}
    bdir = root / "bench"
    return Cell(
        name=name, chips=entry["chips"],
        config=load_json(root / configs[entry["config"]]["file"]),
        traffic=load_json(bdir / "traffic" / f"{entry['traffic']}.json"),
        limits=load_json(bdir / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def reference(cell: Cell):
    """The configuration's plain reference module (bench/reference/*.py)."""
    return importlib.import_module(
        f"bench.reference.{cell.config['reference']}")


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def kernel_cost(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "kernels" / f"{name}.py",
                       "bench_kernel_" + name)


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
