"""The expert layer's and MLA's scopes and the per-layer metrics that read
them (`moe.ms`, `moe.dispatch_ms`, `mla.ms`): the scopes in a compiled
tiny deepseek step, the readers on hand-built records, and on a recorded
excerpt of the `dsv2lite-sign` cell's own chip trace
(bench/tests/data/dsv2lite_excerpt.json.gz: every op of one step of a
traced window on a TPU v5 lite)."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from bench.tests.tiny import REPO

DATA = Path(__file__).resolve().parent / "data"
READERS = ("moe.ms", "moe.dispatch_ms", "mla.ms")
STEP = "jit_base_step"
MS = 1_000_000                  # ns
# per step, in ms, op names as the compiled step gives them: forward in a
# scanned block, backward under transpose(jvp()), remat, the dense
# block's MLA as its outermost scope, and ops outside every scope
OPS = [
    ("fusion.1", "jit(base_step)/vmap(jvp())/while/body/closed_call/moe/"
                 "route/dot_general", 3),
    ("sort.2", "jit(base_step)/vmap(jvp())/while/body/closed_call/moe/"
               "dispatch/jit(argsort)/sort", 5),
    ("fusion.3", "jit(base_step)/vmap(jvp())/while/body/closed_call/moe/"
                 "experts/while/body/closed_call/ragged_dot_general", 20),
    ("scatter.4", "jit(base_step)/vmap(transpose(jvp()))/while/body/"
                  "closed_call/checkpoint/moe/combine/scatter-add", 7),
    ("fusion.5", "checkpoint/rematted_computation/moe/shared/dot_general", 11),
    ("fusion.6", "jit(base_step)/vmap(jvp(mla))/bsd,dhk->bshk/dot_general",
     13),
    ("fusion.7", "jit(base_step)/vmap(transpose(jvp()))/while/body/"
                 "closed_call/checkpoint/mla/bshk,btk->bsht/dot_general", 17),
    ("ragged-dot-none.2", "ragged-dot-none", 19),
    ("fusion.8", "jit(base_step)/vmap(jvp())/mlp/dot_general", 30),
    ("fusion.9", "jit(base_step)/stage2/wire/ef_sign_local_step", 40),
]


def record(devices: int, names=True):
    """Two steps on each device; the feed's program (another module)
    holds an op of the same HLO name as the route's fusion."""
    ops, modules = [], []
    for d in range(devices):
        dev = f"TPU:{d}"
        for t0 in (1000 * MS, 1300 * MS):
            modules.append([dev, STEP, t0, 200 * MS])
            t = t0
            for op, _, ms in OPS:
                ops.append([dev, op, t, ms * MS, STEP])
                t += ms * MS
            ops.append([dev, "fusion.1", t0 + 250 * MS, 9 * MS, "jit_feed"])
            modules.append([dev, "jit_feed", t0 + 250 * MS, 9 * MS])
    rec = {"device_ops": ops, "modules": modules,
           "host_spans": [["bench.window", 990 * MS, 600 * MS]]}
    return rec, {op: name for op, name, _ in OPS} if names else {}


def read(name, rec, names, module=STEP):
    from bench import devtrace, spec
    from bench.run import Context
    red = devtrace.Reduction(rec, names, module)
    return spec.metric_reader(name).read(Context(reduction=red))


@pytest.mark.parametrize("devices", [1, 2])
def test_readers_sum_the_scoped_ops(devices):
    rec, names = record(devices)
    # the compiler's ragged-dot ops, which lose the scope, count in moe.ms
    assert read("moe.ms", rec, names) == pytest.approx(
        3 + 5 + 20 + 7 + 11 + 19)
    assert read("moe.dispatch_ms", rec, names) == pytest.approx(3 + 5 + 7)
    assert read("mla.ms", rec, names) == pytest.approx(13 + 17)


def test_readers_find_nothing_without_the_scopes():
    rec, names = record(1)
    bare = {op: n.replace("/moe/", "/").replace("(mla)", "()")
            .replace("/mla/", "/") for op, n in names.items()}
    for name in READERS:
        assert read(name, rec, bare) is None, name
        assert read(name, {**rec, "modules": []}, names) is None, name
    from bench import spec
    from bench.run import Context
    for name in READERS:
        assert spec.metric_reader(name).read(Context(reduction=None)) is None


def test_readers_on_the_xlstm_excerpt():
    """A step with neither expert layers nor MLA reads nothing."""
    with gzip.open(DATA / "trace_excerpt.json.gz", "rt") as f:
        rec = json.load(f)
    for name in READERS:
        assert read(name, rec, rec["op_names"], rec["module"]) is None


def test_scopes_in_the_compiled_tiny_step(tmp_path):
    from bench import devtrace, scopes, spec
    from bench.program import Program
    from bench.tests.test_deepseek_cell import make_root
    cell = spec.load_cell("ds-tiny", make_root(tmp_path))
    prog = Program(cell, spec.reference(cell), 3)
    names = list(devtrace.op_names(prog.compiled.as_text()).values())
    prog.close()
    for scope in ("moe/route", "moe/dispatch", "moe/experts",
                  "moe/combine", "moe/shared", "mla"):
        seg = scopes.segment(scope)
        hits = [n for n in names if seg.search(n)]
        # forward, and backward under transpose(jvp())
        assert any("transpose(jvp" in n for n in hits), scope
        assert any("transpose(jvp" not in n for n in hits), scope


def test_readers_on_the_dsv2lite_excerpt():
    """One step of the cell on the chip: the expert layers' ops are the
    five sub-scopes and the compiler's ragged-dot ops, the dispatch is
    three of them, and MLA and the expert layers lie inside stage 1."""
    from bench import devtrace, spec
    from bench.run import Context
    from bench.scopes import scoped_ms
    with gzip.open(DATA / "dsv2lite_excerpt.json.gz", "rt") as f:
        rec = json.load(f)
    red = devtrace.Reduction(rec, rec["op_names"], rec["module"])
    assert red.steps == 1
    ctx = Context(reduction=red)
    got = {n: spec.metric_reader(n).read(ctx) for n in READERS}
    part = {s: scoped_ms(ctx, "moe", s) for s in (
        "moe/route", "moe/dispatch", "moe/experts", "moe/combine",
        "moe/shared")}
    ragged = scoped_ms(ctx, "moe", "no-such-scope", renamed="ragged-dot-")
    assert all(v > 0 for v in part.values()) and ragged > 0
    assert got["moe.ms"] == pytest.approx(sum(part.values()) + ragged)
    assert got["moe.dispatch_ms"] == pytest.approx(
        part["moe/route"] + part["moe/dispatch"] + part["moe/combine"])
    stage1 = spec.metric_reader("stage1.ms").read(ctx)
    assert 0 < got["moe.ms"] + got["mla.ms"] < stage1
    # the readings of this step (ms; TPU v5 lite, seed 3000001511)
    assert got["moe.ms"] == pytest.approx(109.591, abs=1e-3)
    assert got["moe.dispatch_ms"] == pytest.approx(36.467, abs=1e-3)
    assert got["mla.ms"] == pytest.approx(98.014, abs=1e-3)
