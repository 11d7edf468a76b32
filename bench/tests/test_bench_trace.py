"""The trace reduction and every per-layer metric's reader, on a recorded
excerpt of a chip trace (bench/tests/data/trace_excerpt.json.gz: every op
of one step of an xlstm-sign window on a TPU v5 lite, and of the gap
before it in which the feed made the step's batch)."""
from __future__ import annotations

import gzip
import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench.tests.tiny import REPO

DATA = Path(__file__).resolve().parent / "data" / "trace_excerpt.json.gz"
SLOT = 10                  # ns per slot of the painted timeline
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def rec():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def red(rec):
    from bench import devtrace
    return devtrace.Reduction(rec, rec["op_names"], rec["module"])


def category(rec, op):
    """The stage rule, written out again: other programs are input; the
    step's ops with vmap( in their op_name, or no jit( prefix, stage 1."""
    if rec["module"] not in op[4]:
        return "input"
    name = rec["op_names"].get(op[1])
    if name is None:
        return "unattributed"
    return "stage1" if ("vmap(" in name or not name.startswith("jit(")) \
        else "stage2"


def window(rec):
    (w,) = [s for s in rec["host_spans"] if s[0] == "bench.window"]
    return w[1], w[1] + w[2]


def painted(rec):
    """Each slot of the window painted with the category of the innermost
    op over it (ops sorted outer first; an op the op names do not place
    leaves its parent's paint, or paints "unattributed" where none)."""
    lo, hi = window(rec)
    cats = ["stage1", "stage2", "input", "unattributed"]
    t = np.full((hi - lo) // SLOT + 1, -1, np.int8)
    for o in sorted(rec["device_ops"], key=lambda o: (o[2], -o[3])):
        a = (max(o[2], lo) - lo) // SLOT
        b = (min(o[2] + o[3], hi) - lo) // SLOT
        c = category(rec, o)
        if c == "unattributed":
            seg = t[a:b]
            seg[seg < 0] = cats.index(c)
        else:
            t[a:b] = cats.index(c)
    return {c: int(np.sum(t == i)) * SLOT * 1e-9 for i, c in enumerate(cats)
            if np.any(t == i)}, int(np.sum(t >= 0)) * SLOT * 1e-9, t


def idle_in_painted(rec, t, span):
    """Seconds of idle slots that lie inside the host's spans `span`."""
    lo, _ = window(rec)
    inside = np.zeros(len(t), bool)
    for name, s, d in rec["host_spans"]:
        if name == span:
            a, b = (max(0, x - lo) // SLOT for x in (s, s + d))
            inside[a:b] = True
    return int(np.sum((t < 0) & inside)) * SLOT * 1e-9


def test_busy_and_stage_times_match_a_painted_timeline(rec, red):
    stages, busy, _ = painted(rec)
    tol = 2 * SLOT * 1e-9 * len(rec["device_ops"])
    assert red.busy_s() == pytest.approx(busy, abs=tol)
    lo, hi = window(rec)
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0 < red.busy_s() <= red.window_s
    got = red.stage_s()
    assert set(got) == set(stages)
    for k in stages:
        assert got[k] == pytest.approx(stages[k], abs=tol), k
    assert sum(got.values()) == pytest.approx(red.busy_s(), rel=1e-9)
    assert red.steps == 1
    assert set(stages) == {"stage1", "stage2", "input", "unattributed"}


def test_idle_inside_the_input_span(rec, red):
    _, busy, t = painted(rec)
    idle = red.idle_in("bench.input")
    assert idle == pytest.approx(idle_in_painted(rec, t, "bench.input"),
                                 abs=2 * SLOT * 1e-9 * len(rec["device_ops"]))
    # the gap before the step: the device waits on the feed there
    assert 0.005 < idle <= red.window_s - busy + 1e-9
    assert red.idle_in("bench.no_such_span") == 0


def test_kernel_time(rec, red):
    calls = [o for o in rec["device_ops"]
             if re.fullmatch(r"ef_sign_fused(\.\d+)?", o[1])]
    assert len(calls) == 1 and len(red.kernel_calls("ef_sign_fused")) == 1
    # a Mosaic kernel is one op with nothing nested in it
    assert red.kernel_s("ef_sign_fused") == pytest.approx(calls[0][3] * 1e-9)


def test_every_per_layer_reader_on_the_excerpt(rec, red):
    from bench import roofline, spec
    from bench.reference import xlstm
    from bench.run import Context
    sizes = json.loads(
        (REPO / "bench/configs/xlstm-1.3b-p8.json").read_text())["model"]
    hlo = "\n".join(rec["custom_call_lines"])
    ctx = Context(reduction=red, compile_s=4.5, window_s=red.window_s,
                  tokens_per_s=8192 / red.window_s,
                  chips=1, peaks=PEAKS,
                  flops_per_token=xlstm.flops_per_token(sizes, 2048),
                  kernel_shapes=roofline.custom_calls(hlo),
                  kernel_cost=spec.kernel_cost)
    stages = red.stage_s()
    ops, res = roofline.custom_calls(hlo)["ef_sign_fused"]
    n = max(int(np.prod(d)) for _, d in ops)
    byts = sum(roofline.DTYPE_BYTES[t] * int(np.prod(d)) for t, d in ops + res)
    calls = 1
    least = max(7.0 * n / PEAKS["bf16_flops_per_s"],
                byts / PEAKS["hbm_bytes_per_s"])
    want = {
        "setup.compile_s": 4.5,
        "host.input_ms": 1e3 * red.idle_in("bench.input") / red.steps,
        "device.idle_share": 100 * (1 - red.busy_s() / red.window_s),
        "mfu": 100 * xlstm.flops_per_token(sizes, 2048) * 8192
        / red.window_s / 197e12,
        "stage1.ms": 1e3 * stages["stage1"],
        "stage2.ms": 1e3 * stages["stage2"],
        "ef_sign_fused_roofline":
            100 * calls * least / red.kernel_s("ef_sign_fused"),
        "ef_topk_fused_roofline": None,     # no such kernel in this step
    }
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert {m["name"] for m in bench["per_layer"]} == set(want)
    for name, value in want.items():
        got = spec.metric_reader(name).read(ctx)
        if value is None:
            assert got is None, name
        else:
            assert got == pytest.approx(value, rel=1e-9), name
    assert 0 < want["ef_sign_fused_roofline"] <= 100


def test_breakdown(rec, red):
    top = red.top_ops(10)
    assert len(top) == 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    assert all(k.split(":")[0] in ("stage1", "stage2", "input",
                                   "unattributed") for k, _ in top)
    gaps = red.idle_gaps(10)
    assert all(g[0].startswith("bench.") and g[1] > 0 for g in gaps)
    assert sum(g[1] for g in red.idle_gaps(10 ** 6)) == pytest.approx(
        red.window_s - red.busy_s(), rel=1e-9)
