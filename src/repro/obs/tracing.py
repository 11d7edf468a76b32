"""Span tracing: in-graph named scopes + host spans on the profiler's clock.

Two complementary planes:

  * Device plane — `jax.named_scope` scopes: zero-cost HLO op metadata so
    profiler dumps (and `jax.profiler.trace`) show the stage-2 flat
    copies, pack / all_to_all / decode-reduce and optimizer phases of the
    coded step.  The scopes are applied unconditionally on the hot path —
    they change op *names* only, never the computation.

  * Host plane — `span(name)` is a bare `jax.profiler.TraceAnnotation`:
    the program's own host spans (names starting "repro.", such as the
    feed's `repro.feed.weights|tokens|put`) land in the profiler's trace
    on the same clock as the device events, and cost one enter/exit when
    the profiler is off.  `SpanRecorder` also keeps wall-clock spans in
    memory (batch wait, prefetch queue occupancy, step dispatch, the
    blocking result fetch) and renders them to Chrome-trace JSON via
    `repro.obs.trace_export.chrome_trace`; each of its spans enters
    `span`.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax

__all__ = ["span", "SpanRecorder"]


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span in the profiler's trace: `with span("repro.feed.put"):`.
    Records nothing itself; without a running profiler it costs one
    enter/exit."""
    return jax.profiler.TraceAnnotation(name)


class SpanRecorder:
    """Wall-clock host spans + counter samples for one run.

    spans:    [{"name", "tid", "t0", "t1", "args"}] seconds since `t0_s`
    counters: [{"name", "t", "value"}] point samples (queue depth etc.)
    """

    def __init__(self):
        self.t0_s = time.perf_counter()
        self.spans: List[dict] = []
        self.counters: List[dict] = []

    def now(self) -> float:
        return time.perf_counter() - self.t0_s

    @contextlib.contextmanager
    def span(self, name: str, tid: str = "host", **args):
        """Time a host-side phase; also a profiler span (`span`)."""
        t0 = self.now()
        with span(name):
            try:
                yield
            finally:
                self.spans.append({"name": name, "tid": tid, "t0": t0,
                                   "t1": self.now(),
                                   "args": {k: v for k, v in args.items()}})

    def counter(self, name: str, value: float) -> None:
        self.counters.append({"name": name, "t": self.now(),
                              "value": float(value)})

    def durations(self, name: Optional[str] = None) -> List[float]:
        """Span durations in seconds (optionally for one span name)."""
        return [s["t1"] - s["t0"] for s in self.spans
                if name is None or s["name"] == name]

    def summary_s(self) -> Dict[str, float]:
        """Total seconds per span name (the per-step host-phase budget)."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s["name"]] = out.get(s["name"], 0.0) + (s["t1"] - s["t0"])
        return out
