"""Telemetry plane (repro.obs): schema, EWMA, traces, and the two hard
guarantees of the in-graph metrics path:

  1. DISABLED metrics cost nothing: `cocoef_update(want_metrics=False)`
     lowers to byte-identical HLO vs the pre-telemetry body, per wire
     format x backend (subprocess, 8 fake devices).
  2. ENABLED metrics add no collectives, and the per-rank wire-byte
     counters they report equal `WireFormat.rank_wire_bytes` == the
     `sim.StepTimer` uplink ledger == the packed payload
     (`benchmarks/comm_volume.audit_wire_bytes`) exactly.

Host-only pieces (logger / serve / trace export / timeline) run in the
main single-device process; everything needing >1 device runs in a
subprocess with xla_force_host_platform_device_count=8 (see conftest).
"""
import json
import os
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")
BENCH = str(Path(__file__).resolve().parents[1] / "benchmarks")


def run_sub(body: str, devices: int = 8, timeout: int = 600):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, shard_map
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBTEST-PASS")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "SUBTEST-PASS" in r.stdout


# ==========================================================================
# JSONL schema + logger
# ==========================================================================

def _train_step_telemetry(n=4, b=2):
    return {"participation": [1.0, 0.0, 1.0, 1.0][:n],
            "participants": 3.0,
            "wire_bytes_rank": [100.0] * n, "bytes_up_total": 300.0,
            "bucket_wire_bytes_rank": [[50.0] * b] * n,
            "bytes_down": 4096.0,
            "grad_norm_rank": [1.0] * n, "ef_norm_rank": [0.1] * n,
            "compress_cosine_rank": [0.9] * n,
            "compress_contraction_rank": [0.2] * n,
            "ghat_norm": 1.0, "update_norm": 0.01, "param_norm": 10.0}


def test_validate_record_rejects_malformed():
    from repro.obs import SCHEMA, validate_record
    ok = {"schema": SCHEMA, "kind": "run_meta", "meta": {"x": 1}}
    validate_record(ok)
    with pytest.raises(ValueError, match="schema"):
        validate_record({"schema": "repro.obs/v0", "kind": "run_meta",
                         "meta": {}})
    with pytest.raises(ValueError, match="kind"):
        validate_record({"schema": SCHEMA, "kind": "mystery"})
    with pytest.raises(ValueError, match="missing field"):
        validate_record({"schema": SCHEMA, "kind": "prefetch"})
    with pytest.raises(ValueError, match="must be dict"):
        validate_record({"schema": SCHEMA, "kind": "prefetch",
                         "stats": [1, 2]})
    # train_step per-rank lists must agree with participation's length
    rec = {"schema": SCHEMA, "kind": "train_step", "step": 0,
           "t_wall_s": 0.0, "ewma_participation": [1.0, 1.0, 1.0, 1.0],
           **_train_step_telemetry()}
    validate_record(rec)
    bad = dict(rec, wire_bytes_rank=[1.0, 2.0])
    with pytest.raises(ValueError, match="wire_bytes_rank"):
        validate_record(bad)
    # serve_summary histograms need p50/p99/mean/count
    with pytest.raises(ValueError, match="histogram keys"):
        validate_record({"schema": SCHEMA, "kind": "serve_summary",
                         "requests": 1, "queue_wait_ms": {"p50": 1.0},
                         "prefill_ms": {"p50": 0, "p99": 0, "mean": 0,
                                        "count": 0},
                         "decode_token_ms": {"p50": 0, "p99": 0, "mean": 0,
                                             "count": 0}})


def test_metrics_logger_jsonl_and_ewma(tmp_path):
    from repro.obs import MetricsLogger, read_jsonl, validate_record
    path = str(tmp_path / "m.jsonl")
    masks = [np.array([1.0, 0.0, 1.0, 1.0]), np.array([0.0, 1.0, 1.0, 1.0]),
             np.array([1.0, 1.0, 1.0, 0.0])]
    with MetricsLogger(path, run_metadata={"arch": "t"},
                       ewma_alpha=0.5) as lg:
        assert lg.rates is None
        for t, m in enumerate(masks):
            tel = _train_step_telemetry()
            tel["participation"] = m.tolist()
            lg.log_step(t, tel, loss=1.0 - 0.1 * t,
                        spans={"train/step_dispatch": 0.01})
        ew = lg.rates
        lg.log_prefetch({"size": 2, "put_count": 3, "get_count": 3,
                         "producer_wait_s": 0.0, "consumer_wait_s": 0.1,
                         "device_put_s": 0.01, "max_depth": 2,
                         "depth_sum": 4})
        assert lg.steps_logged == 3
    # bias-corrected EWMA: zero-init s_t = (1-a) s + a m, reported
    # s_t / (1 - (1-a)^t) — an exact weighted average of the masks seen
    # (for a=0.5, T=3: (m0 + 2 m1 + 4 m2) / 7)
    s = np.zeros_like(masks[0])
    for m in masks:
        s = 0.5 * s + 0.5 * m
    expect = s / (1.0 - 0.5 ** len(masks))
    np.testing.assert_allclose(expect, (masks[0] + 2 * masks[1]
                                        + 4 * masks[2]) / 7.0)
    np.testing.assert_allclose(ew, expect)
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == \
        ["run_meta", "train_step", "train_step", "train_step", "prefetch"]
    for r in recs:
        validate_record(r)     # every emitted line passes the schema gate
    np.testing.assert_allclose(recs[3]["ewma_participation"], expect)
    # at t=1 the correction makes the estimate exactly the first mask
    np.testing.assert_allclose(recs[1]["ewma_participation"], masks[0])
    assert recs[1]["loss"] == pytest.approx(1.0)
    # a malformed record never reaches the file, and closed loggers refuse
    with pytest.raises(ValueError):
        MetricsLogger(str(tmp_path / "x.jsonl")).write({"kind": "nope"})
    lg2 = MetricsLogger(str(tmp_path / "y.jsonl"))
    lg2.close()
    with pytest.raises(ValueError, match="closed"):
        lg2.log_prefetch({"size": 1})


def test_ewma_bias_correction_5step_regression(tmp_path):
    """Satellite regression pin: under a known-rate Bernoulli process the
    bias-corrected estimate after 5 steps is an exact weighted average of
    the observed masks, so its error against the empirical mean is bounded
    by the (small) geometric reweighting — NOT by step-0 noise, which
    dominated the first ~1/alpha steps under the old first-mask seeding."""
    from repro.obs import MetricsLogger
    rng = np.random.default_rng(7)
    q = np.array([0.9, 0.6, 0.3, 0.8])
    masks = (rng.uniform(size=(5, 4)) < q).astype(np.float64)
    a = 0.1
    with MetricsLogger(str(tmp_path / "m.jsonl"), ewma_alpha=a) as lg:
        for t, m in enumerate(masks):
            tel = _train_step_telemetry()
            tel["participation"] = m.tolist()
            lg.log_step(t, tel)
        est = lg.rates
    # closed form: weights (1-a)^(T-1-t) * a, normalized by 1-(1-a)^T
    w = a * (1.0 - a) ** np.arange(len(masks) - 1, -1, -1)
    expect = (w[:, None] * masks).sum(0) / (1.0 - (1.0 - a) ** len(masks))
    np.testing.assert_allclose(est, expect, rtol=1e-12)
    # with alpha=0.1 the corrected weights are within 34% of uniform over
    # 5 steps, so the estimate stays near the empirical mean...
    emp = masks.mean(0)
    assert np.max(np.abs(est - emp)) < 0.25
    # ...while the OLD seeded estimate is pinned to the first mask:
    # weight of m_0 is (1-a)^4 ~ 0.66, so a first-step outage drags a
    # q=0.9 rank's estimate below 0.7 for ~1/a steps
    seeded = masks[0].copy()
    for m in masks[1:]:
        seeded = (1.0 - a) * seeded + a * m
    assert np.max(np.abs(seeded - emp)) > np.max(np.abs(est - emp))


def test_logger_ewma_matches_rate_estimator():
    """The logger's inline bias correction and the standalone
    `core.coding_state.RateEstimator` are twin implementations (the
    logger cannot import core); they must agree bit-for-bit."""
    from repro.core.coding_state import RateEstimator
    from repro.obs import MetricsLogger
    import tempfile
    rng = np.random.default_rng(3)
    masks = (rng.uniform(size=(12, 4)) < 0.7).astype(np.float64)
    est = RateEstimator(4, alpha=0.2)
    with tempfile.TemporaryDirectory() as d:
        with MetricsLogger(d + "/m.jsonl", ewma_alpha=0.2) as lg:
            for t, m in enumerate(masks):
                tel = _train_step_telemetry()
                tel["participation"] = m.tolist()
                lg.log_step(t, tel)
                est.update(m)
                assert (lg.rates == est.rates).all()


def test_replan_record_schema(tmp_path):
    from repro.obs import MetricsLogger, read_jsonl, validate_record
    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path) as lg:
        rec = lg.log_replan(3, {"epoch": 1, "drift": 0.17,
                                "reallocated": True,
                                "rates_estimate": [0.9, 0.5]})
        validate_record(rec)
    recs = read_jsonl(path)
    assert recs[-1]["kind"] == "replan"
    assert recs[-1]["reallocated"] is True
    assert recs[-1]["epoch"] == 1
    with pytest.raises(ValueError, match="missing field"):
        validate_record({"schema": "repro.obs/v1", "kind": "replan",
                         "step": 1})


def test_serve_telemetry_percentiles_and_records(tmp_path):
    from repro.obs import MetricsLogger, ServeTelemetry, read_jsonl, \
        validate_record
    from repro.obs.logger import percentiles_ms
    assert percentiles_ms([]) == {"p50": 0.0, "p99": 0.0, "mean": 0.0,
                                  "count": 0}
    tel = ServeTelemetry()
    decode_s = [0.001 * (i + 1) for i in range(100)]   # 1..100 ms
    for s in decode_s:
        tel.add_decode_token(s)
    tel.add_prefill(0.050)
    for rid in range(4):
        tel.add_request(rid, queue_wait_s=0.010 * rid, prefill_s=0.05,
                        decode_s=0.2, tokens=8)
    s = tel.summary()
    assert s["requests"] == 4
    assert s["decode_token_ms"]["count"] == 100
    assert s["decode_token_ms"]["p50"] == pytest.approx(
        np.percentile(np.asarray(decode_s) * 1e3, 50))
    assert s["decode_token_ms"]["p99"] == pytest.approx(
        np.percentile(np.asarray(decode_s) * 1e3, 99))
    assert s["queue_wait_ms"]["p50"] == pytest.approx(15.0)
    with MetricsLogger(str(tmp_path / "s.jsonl"),
                       run_metadata={"path": "serve"}) as lg:
        tel.log_to(lg)
    recs = read_jsonl(str(tmp_path / "s.jsonl"))
    assert [r["kind"] for r in recs] == \
        ["run_meta"] + ["serve_request"] * 4 + ["serve_summary"]
    for r in recs:
        validate_record(r)
    assert "p50" in tel.format_summary()


# ==========================================================================
# span recorder + Chrome-trace export
# ==========================================================================

def test_span_recorder_and_chrome_trace_roundtrip(tmp_path):
    import time

    from repro.obs import SpanRecorder, span_events, validate_chrome_trace, \
        write_chrome_trace
    rec = SpanRecorder()
    with rec.span("phase/a", step=0):
        time.sleep(0.01)
    with rec.span("phase/b", tid="serve"):
        pass
    rec.counter("queue_depth", 2)
    assert rec.durations("phase/a")[0] >= 0.01
    assert set(rec.summary_s()) == {"phase/a", "phase/b"}
    path = str(tmp_path / "trace.json")
    obj = write_chrome_trace(path, span_events(rec.spans, pid=0,
                                               counters=rec.counters),
                             metadata={"arch": "t"})
    validate_chrome_trace(obj)
    loaded = json.load(open(path))
    assert loaded["otherData"]["schema"] == "repro.obs.trace/v1"
    kinds = [e["ph"] for e in loaded["traceEvents"]]
    assert kinds.count("X") == 2 and kinds.count("C") == 1
    ex = [e for e in loaded["traceEvents"] if e["ph"] == "X"][0]
    assert ex["tid"] == "host" and ex["args"]["step"] == 0


def test_validate_chrome_trace_rejects_malformed():
    from repro.obs import chrome_trace, validate_chrome_trace
    with pytest.raises(ValueError, match="schema"):
        validate_chrome_trace({"traceEvents": []})
    ok = lambda: chrome_trace([{"name": "x", "ph": "X", "ts": 0.0,
                                "dur": 1.0, "pid": 0, "tid": "t"}])
    validate_chrome_trace(ok())
    bad = ok()
    bad["traceEvents"][0]["ph"] = "Z"
    with pytest.raises(ValueError, match="ph"):
        validate_chrome_trace(bad)
    bad = ok()
    bad["traceEvents"][0]["ts"] = float("nan")
    with pytest.raises(ValueError, match="finite"):
        validate_chrome_trace(bad)
    bad = ok()
    del bad["traceEvents"][0]["tid"]
    with pytest.raises(ValueError, match="tid"):
        validate_chrome_trace(bad)
    bad = ok()
    bad["traceEvents"][0]["dur"] = -1.0
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(bad)


# ==========================================================================
# simulated StepTimer timeline == the cost model's closed form
# ==========================================================================

def _timeline_cases():
    from repro.core.collectives import DenseWire, SignWire, SparseWire
    return [
        ("sign serial B=1", SignWire(group_size=512), {}),
        ("sign serial B=4", SignWire(group_size=512),
         {"num_buckets": 4}),
        ("sign pipelined B=4", SignWire(group_size=512),
         {"num_buckets": 4, "overlap": True}),
        ("topk pipelined B=4 pack", SparseWire(k_per_block=8,
                                               block_size=512),
         {"num_buckets": 4, "overlap": True, "pack_s": 1e-3}),
        ("topk per-rank budgets", SparseWire(k_per_block=(2, 4, 8, 16),
                                             block_size=512), {}),
        ("dense serial B=2 pack", DenseWire(), {"num_buckets": 2,
                                                "pack_s": 5e-4}),
    ]


def test_steptimer_timeline_matches_cost_model():
    """The laid-out span extent of every simulated step equals
    `StepTimer.steps()` exactly — serial and pipelined bucket schedules,
    per-rank budgets, and the all-straggler timeout window included."""
    from repro.obs import chrome_trace, steptimer_timeline, \
        validate_chrome_trace
    from repro.sim import StepTimer
    trace = np.array([[1, 1, 1, 1],
                      [1, 0, 1, 1],
                      [0, 0, 0, 0],      # all-straggler: timeout window
                      [0, 1, 0, 0],
                      [1, 1, 0, 1]], np.float64)
    for name, wire, kw in _timeline_cases():
        timer = StepTimer(wire=wire, n=4096, **kw)
        events, ts = steptimer_timeline(timer, trace, pid=1)
        expect, _, _ = timer.steps(trace)
        np.testing.assert_allclose(ts, expect, rtol=1e-9, atol=1e-15,
                                   err_msg=name)
        obj = chrome_trace(events, {"case": name})
        validate_chrome_trace(obj)
        steps = [e for e in events if e["name"] == "step"]
        assert len(steps) == trace.shape[0], name
        # steps tile the timeline back to back, and the all-straggler row
        # renders a timeout (no uplink), participating rows compute lanes
        for t in range(1, len(steps)):
            assert steps[t]["ts"] == pytest.approx(
                steps[t - 1]["ts"] + steps[t - 1]["dur"]), name
        names_t2 = {e["name"] for e in events
                    if e["args"].get("step") == 2}
        assert "compute_timeout" in names_t2 and "uplink" not in names_t2
        assert "compute" not in names_t2, name
    with pytest.raises(ValueError, match=r"\(T, N\)"):
        steptimer_timeline(StepTimer(wire=_timeline_cases()[0][1], n=4096),
                           np.ones((4,)))


# ==========================================================================
# single source of truth: declared == packed == cost model (+ provenance)
# ==========================================================================

def test_wire_audit_and_run_metadata():
    sys.path.insert(0, BENCH)
    try:
        import _repro_common as R
        import comm_volume
    finally:
        sys.path.remove(BENCH)
    audited = comm_volume.audit_wire_bytes(n=4096)
    assert len(audited) == len(comm_volume.WIRE_TABLE) + 1   # + per-rank
    meta = R.run_metadata(trials=3, T=100)
    for k in ("git_sha", "jax_version", "python", "platform",
              "jax_backend", "device_count", "timestamp"):
        assert k in meta, k
    assert meta["trials"] == 3 and meta["T"] == 100
    json.dumps(meta)          # must be embeddable in results JSON


def test_rank_wire_bytes_linear_over_buckets():
    """Per-bucket accounting sums to the whole-vector accounting — the
    identity the in-graph per-bucket byte counters rely on."""
    from repro.core.collectives import DenseWire, SignWire, SparseWire
    n, N, B = 8192, 4, 4
    for wire in (SignWire(group_size=512),
                 SparseWire(k_per_block=8, block_size=512),
                 SparseWire(k_per_block=(2, 4, 8, 16), block_size=512),
                 DenseWire(value_dtype="bfloat16")):
        per_bucket = wire.rank_wire_bytes(n // B, N)
        np.testing.assert_array_equal(per_bucket * B,
                                      wire.rank_wire_bytes(n, N))


# ==========================================================================
# host-side grid reduction (pure-array semantics)
# ==========================================================================

def test_reduce_frame_grid_semantics():
    """Synthetic (2, 3) grid, coding over "data" (size 2), 3 model shards:
    corners dedupe replicated leaves, rank sums fold the model axis, byte
    counters scale by the shard count, and zero-acc ranks report cosine 0
    (no NaNs)."""
    import jax.numpy as jnp

    from repro.obs import MetricsFrame, frame_to_host, reduce_frame_grid
    grid = (2, 3)
    N, B = 2, 2
    rep = lambda v: jnp.broadcast_to(jnp.asarray(v, jnp.float32),
                                     grid + np.shape(v))
    dev = jnp.arange(6, dtype=jnp.float32).reshape(grid)   # distinct/device
    frame = MetricsFrame(
        participation=rep([1.0, 0.0]),
        wire_bytes_rank=rep([100.0, 0.0]),
        bucket_wire_bytes=rep([30.0, 20.0]),
        bytes_down=rep(7.0),
        grad_norm_sq=dev, ef_norm_sq=dev * 2,
        acc_norm_sq=jnp.stack([dev[0] * 0 + 4.0, dev[1] * 0.0]),
        c_norm_sq=jnp.stack([dev[0] * 0 + 1.0, dev[1] * 0.0]),
        acc_dot_c=jnp.stack([dev[0] * 0 + 2.0, dev[1] * 0.0]),
        ghat_norm_sq=rep(3.0), update_norm_sq=rep(5.0),
        param_norm_sq=rep(9.0))
    tel = frame_to_host(reduce_frame_grid(frame, ("data", "model"),
                                          ("data",)))
    assert tel["participation"] == [1.0, 0.0]
    assert tel["participants"] == 1.0
    # byte counters: per-device constants x 3 model shards
    assert tel["wire_bytes_rank"] == [300.0, 0.0]
    assert tel["bytes_up_total"] == 300.0
    assert tel["bytes_down"] == 21.0
    assert tel["bucket_wire_bytes_rank"] == [[90.0, 60.0], [90.0, 60.0]]
    # rank sums fold the model axis: rank 0 sees devices 0+1+2, rank 1 3+4+5
    np.testing.assert_allclose(tel["grad_norm_rank"],
                               [np.sqrt(0 + 1 + 2), np.sqrt(3 + 4 + 5)])
    np.testing.assert_allclose(tel["ef_norm_rank"],
                               [np.sqrt(6.0), np.sqrt(24.0)])
    # cosine/contraction per rank; the all-zero rank 1 reports 0, not NaN
    # rank 0: acc_sq=12, c_sq=3, dot=6 -> cos=1, contraction=(12+3-12)/12
    np.testing.assert_allclose(tel["compress_cosine_rank"], [1.0, 0.0])
    np.testing.assert_allclose(tel["compress_contraction_rank"],
                               [0.25, 0.0])
    # replicated-after-collective scalars: sum model, mean coding
    assert tel["ghat_norm"] == pytest.approx(np.sqrt(9.0))
    assert tel["update_norm"] == pytest.approx(np.sqrt(15.0))
    assert tel["param_norm"] == pytest.approx(np.sqrt(27.0))


# ==========================================================================
# resolve_use_pallas fallback warning: once per (op, shape, dtype)
# ==========================================================================

def test_resolve_use_pallas_rewarns_per_op_and_dtype():
    from repro.kernels import ops
    ops._fallback_warned.clear()
    try:
        with pytest.warns(RuntimeWarning, match="falling back"):
            assert ops.resolve_use_pallas(True, 100, 64, op="ef_sign_fused",
                                          dtype="float32") is False
        with warnings.catch_warnings():       # same key: silent
            warnings.simplefilter("error")
            ops.resolve_use_pallas(True, 100, 64, op="ef_sign_fused",
                                   dtype="float32")
        # the PR 8 bugfix: the same shape through a DIFFERENT op or value
        # dtype used to be swallowed by the shape-only key
        with pytest.warns(RuntimeWarning, match="ef_topk_fused"):
            ops.resolve_use_pallas(True, 100, 64, op="ef_topk_fused",
                                   dtype="float32")
        with pytest.warns(RuntimeWarning):
            ops.resolve_use_pallas(True, 100, 64, op="ef_sign_fused",
                                   dtype="bfloat16")
        with warnings.catch_warnings():       # no explicit request / fits
            warnings.simplefilter("error")
            assert ops.resolve_use_pallas(False, 100, 64, op="x") is False
            assert ops.resolve_use_pallas(True, 128, 64, op="x") is True
    finally:
        ops._fallback_warned.clear()


# ==========================================================================
# prefetch stats reach the JSONL plane
# ==========================================================================

def test_prefetch_stats_log_record(tmp_path):
    from repro.data import pipeline
    from repro.obs import MetricsLogger, read_jsonl, validate_record
    it = pipeline.prefetch_to_device(
        iter([np.zeros((2,), np.float32)] * 3), size=2)
    out = list(it)
    assert len(out) == 3
    with MetricsLogger(str(tmp_path / "p.jsonl")) as lg:
        rec = lg.log_prefetch(it.stats.snapshot())
    validate_record(rec)
    saved = read_jsonl(str(tmp_path / "p.jsonl"))[0]["stats"]
    assert saved["get_count"] == 3 and saved["put_count"] == 3
    assert saved["size"] == 2 and saved["max_depth"] <= 2


# ==========================================================================
# multi-device: HLO identity (disabled) + no extra collectives (enabled)
# ==========================================================================

def test_metrics_disabled_hlo_identical_per_wire_and_backend():
    """`cocoef_update` (metrics off) must lower to byte-identical text vs
    the pre-telemetry `_cocoef_update_impl` for every compressor x backend
    x mode, and the metrics-ON lowering must contain exactly the same
    collective ops (telemetry is device-local by construction)."""
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from repro.core.cocoef import (CocoEFConfig, cocoef_update,
                                   _cocoef_update_impl)
    from repro.obs.metrics import MetricsFrame, frame_out_specs

    mesh = make_mesh((4, 2), ("data", "model"))
    axis = {"data", "model"}
    mask = jnp.array([1., 0., 1., 1.])
    n = 2048
    spec = P(("data", "model"))
    gs = jax.ShapeDtypeStruct((8 * n,), jnp.float32)

    COLLECTIVES = ("all_to_all", "all_gather", "all_reduce",
                   "collective_permute", "reduce_scatter",
                   "collective_broadcast")

    def counts(txt):
        return {c: txt.count(c) for c in COLLECTIVES}

    cases = []
    for backend in ("jnp", "pallas"):
        for comp in ("sign", "block_topk", "topk", "identity"):
            cases.append(dict(compressor=comp, backend=backend))
    cases.append(dict(mode="coco"))
    cases.append(dict(mode="dense"))
    cases.append(dict(compressor="block_topk", num_buckets=4,
                      bucket_schedule="pipelined"))
    cases.append(dict(compressor="block_topk",
                      k_per_block=(1, 2, 4, 8)))

    for over in cases:
        cfg = CocoEFConfig(coding_axes=("data",), group_size=32,
                           block_size=64, k_per_block=over.pop(
                               "k_per_block", 4), **over)

        def lower2(fn):
            f = shard_map(lambda g, e: fn(g, e, mask, 0.05, cfg), mesh,
                          in_specs=(spec,) * 2, out_specs=(spec,) * 2,
                          axis_names=axis)
            return jax.jit(f).lower(gs, gs).as_text()

        off = lower2(cocoef_update)          # default want_metrics=False
        impl = lower2(_cocoef_update_impl)   # the pre-telemetry body
        assert off == impl, f"HLO drift with metrics disabled: {cfg}"

        def body_on(g, e):
            ghat, e_new, frame = cocoef_update(g, e, mask, 0.05, cfg,
                                               want_metrics=True)
            frame = jax.tree.map(lambda l: l.reshape((1, 1) + l.shape),
                                 frame)
            return ghat, e_new, frame
        fa = MetricsFrame.abstract(4, cfg.num_buckets)
        f_on = shard_map(body_on, mesh, in_specs=(spec,) * 2,
                         out_specs=(spec, spec,
                                    frame_out_specs(fa, mesh.axis_names)),
                         axis_names=axis)
        on = jax.jit(f_on).lower(gs, gs).as_text()
        assert counts(on) == counts(off), \\
            f"metrics added collectives: {cfg}: " \\
            f"{counts(on)} vs {counts(off)}"
    """)


def test_shard_map_per_rank_metrics_match_ledger():
    """Enabled metrics through the real mesh: per-rank wire bytes equal
    mask x `wire.rank_wire_bytes` x TP shards == the `sim.StepTimer`
    uplink ledger; norms/cosine/contraction match a host-side oracle of
    Algorithm 1's compression per coding rank."""
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from repro.core.cocoef import CocoEFConfig, cocoef_update
    from repro.core.collectives import DenseWire
    from repro.obs.metrics import (MetricsFrame, frame_out_specs,
                                   frame_to_host, reduce_frame_grid)
    from repro.sim import StepTimer

    mesh = make_mesh((4, 2), ("data", "model"))
    axis = {"data", "model"}
    N, TP, n = 4, 2, 2048
    gamma = 0.05
    mask = jnp.array([1., 0., 1., 1.])
    spec = P(("data", "model"))
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (8 * n,), jnp.float32)
    e0 = jax.random.normal(jax.random.PRNGKey(1), (8 * n,),
                           jnp.float32) * 0.1

    cases = [
        ("cocoef sign", CocoEFConfig(coding_axes=("data",), group_size=32)),
        ("cocoef topk B=4 pipelined",
         CocoEFConfig(coding_axes=("data",), group_size=32,
                      compressor="block_topk", block_size=64, k_per_block=4,
                      num_buckets=4)),
        ("cocoef topk per-rank budgets",
         CocoEFConfig(coding_axes=("data",), group_size=32,
                      compressor="block_topk", block_size=64,
                      k_per_block=(1, 2, 4, 8))),
        ("coco sign", CocoEFConfig(coding_axes=("data",), group_size=32,
                                   mode="coco")),
        ("dense", CocoEFConfig(coding_axes=("data",), group_size=32,
                               mode="dense")),
    ]
    for name, cfg in cases:
        B = cfg.num_buckets
        e = e0 * (0.0 if cfg.mode in ("coco", "dense") else 1.0)

        def body(g_, e_):
            ghat, e_new, frame = cocoef_update(g_, e_, mask, gamma, cfg,
                                               want_metrics=True)
            frame = jax.tree.map(lambda l: l.reshape((1, 1) + l.shape),
                                 frame)
            return ghat, e_new, frame
        fa = MetricsFrame.abstract(N, B)
        f = jax.jit(shard_map(
            body, mesh, in_specs=(spec,) * 2,
            out_specs=(spec, spec, frame_out_specs(fa, mesh.axis_names)),
            axis_names=axis))
        ghat, e_new, grid = f(g, e)
        tel = frame_to_host(jax.device_get(reduce_frame_grid(
            grid, mesh.axis_names, cfg.coding_axes)))

        assert tel["participation"] == [1., 0., 1., 1.], name
        assert tel["participants"] == 3.0, name

        # --- byte ledger: metrics == wire declaration == StepTimer ------
        wire = (DenseWire(value_dtype="float32") if cfg.mode == "dense"
                else cfg.wire_format(n // B, N))
        timer = StepTimer(wire=wire, n=n // B, num_buckets=B)
        per_rank = timer.bytes_up_ranks(N).astype(np.float64) * B
        expect_rank = np.asarray(mask) * per_rank * TP
        np.testing.assert_allclose(tel["wire_bytes_rank"], expect_rank,
                                   err_msg=name)
        assert tel["bytes_up_total"] == expect_rank.sum(), name
        # the StepTimer trace ledger prices the same step identically
        _, bytes_up, _ = StepTimer(wire=wire, n=n // B).steps(
            np.asarray(mask)[None, :] )
        assert tel["bytes_up_total"] == bytes_up[0] * B * TP, name
        bb = np.asarray(tel["bucket_wire_bytes_rank"])
        assert bb.shape == (N, B), name
        np.testing.assert_allclose(bb.sum(axis=1), expect_rank,
                                   err_msg=name)
        assert tel["bytes_down"] == n * 4 * TP, name

        # --- norms / compression quality vs a host oracle ---------------
        gr = np.asarray(g).reshape(N, TP * n)
        er = np.asarray(e).reshape(N, TP * n)
        np.testing.assert_allclose(tel["grad_norm_rank"],
                                   np.linalg.norm(gr, axis=1), rtol=1e-5,
                                   err_msg=name)
        enr = np.asarray(e_new).reshape(N, TP * n)
        np.testing.assert_allclose(tel["ef_norm_rank"],
                                   np.linalg.norm(enr, axis=1), rtol=1e-5,
                                   err_msg=name)
        acc_sq = np.zeros(N); c_sq = np.zeros(N); dot = np.zeros(N)
        for i in range(N):
            for j in range(TP):
                dev = slice((i * TP + j) * n, (i * TP + j + 1) * n)
                for acc_b in (gamma * np.asarray(g)[dev] +
                              np.asarray(e)[dev]).reshape(B, -1):
                    acc_b = jnp.asarray(acc_b, jnp.float32)
                    if cfg.mode == "dense":
                        c_b = acc_b
                    else:
                        w = cfg.wire_format(n // B, N)
                        c_b = w.unpack(w.apply_rank_budget(
                            w.fused_pack(acc_b, use_pallas=False), i))
                    c_b = np.asarray(c_b)
                    acc_b = np.asarray(acc_b)
                    acc_sq[i] += (acc_b * acc_b).sum()
                    c_sq[i] += (c_b * c_b).sum()
                    dot[i] += (acc_b * c_b).sum()
        cos = dot / np.maximum(np.sqrt(acc_sq) * np.sqrt(c_sq), 1e-30)
        contraction = (acc_sq + c_sq - 2 * dot) / np.maximum(acc_sq, 1e-30)
        np.testing.assert_allclose(tel["compress_cosine_rank"], cos,
                                   rtol=1e-4, err_msg=name)
        np.testing.assert_allclose(tel["compress_contraction_rank"],
                                   contraction, rtol=1e-3, atol=1e-6,
                                   err_msg=name)
        # ghat identical across coding ranks; its norm is the global one
        gh = np.asarray(ghat).reshape(N, TP * n)
        np.testing.assert_allclose(tel["ghat_norm"],
                                   np.linalg.norm(gh[0]), rtol=1e-5,
                                   err_msg=name)
        print(name, "OK")
    """)
