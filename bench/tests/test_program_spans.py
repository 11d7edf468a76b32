"""The program's own spans and scopes, and the per-layer metric that reads
them: the train step's `stage2/` scopes in a compiled tiny step, the
feed's `repro.feed.*` spans in a profiler trace of one pull, and
`stage2.flat_ms` on hand-built records and on the recorded excerpt."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench.tests.tiny import make_root

import jax  # noqa: E402  (after tiny sets JAX_PLATFORMS)

SEED = 2**31 + 977
EXCERPT = Path(__file__).resolve().parent / "data" / "trace_excerpt.json.gz"
FEED = ["repro.feed.weights", "repro.feed.tokens", "repro.feed.put"]


@pytest.fixture(scope="module")
def prog(tmp_path_factory):
    from bench import spec
    from bench.program import Program
    cell = spec.load_cell("tiny", make_root(tmp_path_factory.mktemp("root")))
    p = Program(cell, spec.reference(cell), SEED)
    yield p
    p.close()


def host_events(path: Path, prefix: str) -> list:
    """[(thread, name, start_ns, end_ns)] of the host events named
    `prefix`..., in the order they started; a thread is its line's place
    on its plane."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [((plane.name, i), ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events if ev.name.startswith(prefix)]
    return sorted(out, key=lambda e: e[2])


def test_stage2_scopes_name_the_flat_copies(prog):
    from bench import devtrace
    hlo = prog.compiled.as_text()
    names = devtrace.op_names(hlo)
    in_stage2 = {k: n for k, n in names.items() if "stage2/" in n}
    assert in_stage2
    # the stage rule reads every op under stage2/ as stage 2
    assert {devtrace.stage_of(n) for n in in_stage2.values()} == {"stage2"}
    # PR 8's wire/ and coded/ scopes now sit under stage2/
    for n in names.values():
        if "/wire/" in n or "/coded/" in n:
            assert n.startswith("jit(base_step)/stage2/"), n
    # outside the wire's kernels and the collective, stage 2's
    # concatenates are the two flattens (params, grads) and its slices
    # are the unflatten
    own = {k: n for k, n in in_stage2.items()
           if "/wire/" not in n and "/coded/" not in n}
    concats = sorted(n for n in own.values() if n.endswith("/concatenate"))
    assert concats == ["jit(base_step)/stage2/flatten/concatenate"] * 2
    slices = {n for n in own.values() if n.endswith("/dynamic_slice")}
    assert slices == {"jit(base_step)/stage2/unflatten/dynamic_slice"}
    # the step returns the loss and no other metric
    m = prog.dispatch(prog.next_batch())
    assert set(m) == {"loss"}
    assert np.isfinite(float(m["loss"]))


def test_feed_spans_in_a_profiler_trace(prog, tmp_path):
    from jax.profiler import TraceAnnotation

    from bench import devtrace
    jax.block_until_ready(prog.next_batch())     # the maker's eager ops
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("bench.input"):
            jax.block_until_ready(prog.next_batch())
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = host_events(path, "repro.")
    assert [e[1] for e in spans] == FEED
    # one after the other on the puller's thread, inside its bench.input
    assert len({e[0] for e in spans}) == 1
    assert all(a[3] <= b[2] for a, b in zip(spans, spans[1:]))
    (outer,) = host_events(path, "bench.input")
    assert outer[2] <= spans[0][2] and spans[-1][3] <= outer[3]
    # the benchmark's own spans are all that extract keeps as host spans
    rec = devtrace.extract(str(path))
    assert [s[0] for s in rec["host_spans"]] == ["bench.input"]


def test_prefetch_worker_puts_on_its_own_thread(tmp_path):
    from repro.data.pipeline import prefetch_to_device
    from repro.obs.tracing import span
    src = (np.full((4,), i, np.float32) for i in range(3))
    with jax.profiler.trace(str(tmp_path)):
        with span("repro.test.consumer"):
            stream = prefetch_to_device(src, size=1)
            got = [np.asarray(x) for x in stream]
    assert [int(x[0]) for x in got] == [0, 1, 2]
    (path,) = tmp_path.rglob("*.xplane.pb")
    puts = host_events(path, "repro.feed.put")
    (consumer,) = host_events(path, "repro.test.consumer")
    assert len(puts) == 3
    assert {e[0] for e in puts} != {consumer[0]}


# ---- stage2.flat_ms on hand-built records --------------------------------

STEP = "jit_base_step"
FLAT = "jit(base_step)/stage2/flatten/concatenate"
UNFLAT = "jit(base_step)/stage2/unflatten/dynamic_slice"
KERNEL = "jit(base_step)/stage2/wire/ef_sign_local_step/pallas_call"
STAGE1 = "jit(base_step)/vmap(jvp())/dot_general"
MS = 1_000_000                  # ns


def record(devices: int) -> dict:
    """Two steps on each device; per step (in ms): stage 1 for 60, the
    flatten's concatenate for 30, the unflatten's fusion for 20 with a
    metadata-less copy of 5 nested in it, the EF kernel for 40.  The
    feed's program (another module) has an op of the same HLO name as
    the concatenate, which is not the step's."""
    ops, modules = [], []
    for d in range(devices):
        dev = f"TPU:{d}"
        for t0 in (1000 * MS, 1200 * MS):
            modules.append([dev, STEP, t0, 150 * MS])
            ops += [[dev, "fusion.9", t0, 60 * MS, STEP],
                    [dev, "concatenate.6", t0 + 60 * MS, 30 * MS, STEP],
                    [dev, "fusion.1", t0 + 90 * MS, 20 * MS, STEP],
                    [dev, "copy.1", t0 + 95 * MS, 5 * MS, STEP],
                    [dev, "ef_sign_fused.1", t0 + 110 * MS, 40 * MS, STEP],
                    [dev, "concatenate.6", t0 + 160 * MS, 9 * MS,
                     "jit_concatenate"]]
            modules.append([dev, "jit_concatenate", t0 + 160 * MS, 9 * MS])
    rec = {"device_ops": ops, "modules": modules,
           "host_spans": [["bench.window", 990 * MS, 400 * MS],
                          ["bench.input", 1155 * MS, 40 * MS]]}
    return rec


def flat_ms(rec, names):
    from bench import devtrace, spec
    from bench.run import Context
    red = devtrace.Reduction(rec, names, STEP)
    return spec.metric_reader("stage2.flat_ms").read(Context(reduction=red))


@pytest.mark.parametrize("devices", [1, 2])
def test_flat_ms_reads_the_scoped_copies(devices):
    names = {"fusion.9": STAGE1, "concatenate.6": FLAT, "fusion.1": UNFLAT,
             "ef_sign_fused.1": KERNEL}
    # 30 of the concatenate, 20 - 5 of the fusion's self time; the copy
    # nested in it carries no op_name and is not counted
    assert flat_ms(record(devices), names) == pytest.approx(45.0)


def test_flat_ms_zero_and_none():
    scoped = {"fusion.9": STAGE1, "ef_sign_fused.1": KERNEL,
              "concatenate.6": "jit(base_step)/stage2/concatenate",
              "fusion.1": "jit(base_step)/stage2/dynamic_slice"}
    # stage2/ is there, neither sub-scope is
    assert flat_ms(record(1), scoped) == 0.0
    # no op of the step carries stage2/ (a program without the scopes)
    bare = {"fusion.9": STAGE1, "concatenate.6": "jit(base_step)/concatenate",
            "fusion.1": "jit(base_step)/dynamic_slice",
            "ef_sign_fused.1": "jit(base_step)/wire/ef_sign_local_step"}
    assert flat_ms(record(1), bare) is None
    assert flat_ms({**record(1), "modules": []}, bare) is None   # no step


def test_flat_ms_on_the_excerpt():
    """The recorded chip step predates the stage2/ scopes: the reader
    finds nothing to read there."""
    from bench import devtrace, spec
    from bench.run import Context
    with gzip.open(EXCERPT, "rt") as f:
        rec = json.load(f)
    red = devtrace.Reduction(rec, rec["op_names"], rec["module"])
    assert red.steps == 1
    reader = spec.metric_reader("stage2.flat_ms")
    assert reader.read(Context(reduction=red)) is None
    assert reader.read(Context(reduction=None)) is None
