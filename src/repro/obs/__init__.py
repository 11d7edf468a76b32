"""Step-level telemetry plane (PR 8): in-graph metrics, span tracing, and
Chrome-trace timeline export for the train, sim, and serve paths.

  metrics       MetricsFrame pytree built inside the jitted step (no host
                callbacks, no extra collectives) + grid reduction helpers
  logger        MetricsLogger JSONL sink (schema repro.obs/v1), EWMA
                per-rank participation rates, record validation
  tracing       `span` (host spans on the profiler's clock) + SpanRecorder
  trace_export  Chrome-trace JSON for measured spans and simulated
                sim.StepTimer schedules (serial + pipelined buckets)
  serving       ServeTelemetry: queue wait + prefill/decode p50/p99

See src/repro/obs/README.md for the JSONL schema.
"""
from .logger import MetricsLogger, SCHEMA, read_jsonl, validate_record
from .metrics import (MetricsFrame, frame_out_specs, frame_to_host, norm_sq,
                      reduce_frame_grid)
from .serving import RequestRecord, ServeTelemetry
from .trace_export import (chrome_trace, span_events, steptimer_timeline,
                           validate_chrome_trace, write_chrome_trace)
from .tracing import SpanRecorder, span

__all__ = [
    "MetricsFrame", "frame_out_specs", "frame_to_host", "norm_sq",
    "reduce_frame_grid",
    "MetricsLogger", "SCHEMA", "read_jsonl", "validate_record",
    "SpanRecorder", "span",
    "chrome_trace", "span_events", "steptimer_timeline",
    "validate_chrome_trace", "write_chrome_trace",
    "ServeTelemetry", "RequestRecord",
]
