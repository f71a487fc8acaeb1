"""Request-scoped observability for the serving stack.

The debugging surface production LM serving systems are tuned off
(Orca's iteration-level scheduling, vLLM's continuous batching) is the
per-request stage breakdown: where did THIS request's latency go — the
queue, the prefill wave, a slow decode chunk, the harvest? Aggregate
Prometheus counters can't answer that; these modules can:

  * `tracing.py`  — `Span`/`Trace`/`Tracer`: a lock-safe in-process span
    pipeline. Trace IDs are minted at HTTP ingress and propagated through
    the batcher worker's admit→prefill→chunk→retire loop, recording
    per-stage wall time plus dispatch metadata (wave size, chunk index,
    compile events via `utils/compile_guard`). Finished traces land in a
    bounded ring buffer and export as Chrome/Perfetto `trace_event` JSON
    (`GET /debug/traces`, `serve.py --trace-dump`). A disabled tracer is
    zero-overhead: every call returns shared null singletons and the
    `spans_created` counter stays at zero (pinned by test).
  * `logging.py`  — `StructuredLog`: one JSON line per completed request
    (trace ID + stage breakdown + outcome) and lifecycle events,
    replacing ad-hoc prints in the serving path.
  * `profiler.py` — `ProfilerCapture`: on-demand `jax.profiler` capture
    behind `POST /debug/profile?seconds=N` (root-gated, single-flight,
    writes a TensorBoard trace dir) so a TPU hotspot can be captured
    from a live server without a restart.
  * `aggregate.py` — fleet trace export: the `x-dalle-trace` context
    codec (header parsed at POST /generate ingress, minted if absent, so
    a bench client's or router's span parents the server's root) and the
    `TraceExporter` background thread shipping finished traces as
    batched JSONL to a collector — bounded buffer, exponential backoff,
    drop-with-counter, NULL_EXPORTER zero-overhead when off, serving
    provably unaffected by collector health.
  * `collector.py` — the stitching `TraceCollector` service
    (`python -m dalle_pytorch_tpu.obs.collector`, embeddable
    in-process): joins spans from N processes on trace_id (out-of-order/
    duplicate/late tolerated via a grace window), assembles ONE merged
    Perfetto trace per request with one track per process identity
    (`GET /traces`), and folds traces into fleet-wide per-stage p50/p95
    + dominant-critical-path attribution (`GET /critical_path`).
  * `vitals.py`   — device telemetry and self-diagnosis: per-program
    `ProgramCostTable` (XLA cost/memory analysis captured at warmup →
    live MFU/bandwidth gauges and `GET /debug/programs`), the
    `EngineVitals` background sampler (`GET /debug/vitals` time-series),
    the `StallWatchdog` (stuck dispatch / stale queue head / frozen
    decode → structured `stall` events with a full `/debug/state` dump
    and worker stacks), and the `SLOTracker` (declarative latency
    targets, rolling-window burn rate, the /healthz `degraded` tier).

Stage timings also feed the `dalle_serving_stage_seconds{stage=}`
histogram family (`training/metrics.py`), so `/metrics` and the traces
agree on where the time went.
"""

from dalle_pytorch_tpu._lazy import lazy_exports

_EXPORTS = {
    "CollectorServer": "collector",
    "EngineVitals": "vitals",
    "NULL_EXPORTER": "tracing",
    "NULL_TRACE": "tracing",
    "NULL_VITALS": "vitals",
    "ProfilerBusy": "profiler",
    "ProfilerCapture": "profiler",
    "ProgramCostTable": "vitals",
    "SLOTarget": "vitals",
    "SLOTracker": "vitals",
    "Span": "tracing",
    "StallWatchdog": "vitals",
    "StructuredLog": "logging",
    "TRACE_HEADER": "aggregate",
    "Trace": "tracing",
    "TraceCollector": "collector",
    "TraceExporter": "aggregate",
    "Tracer": "tracing",
    "format_trace_header": "aggregate",
    "parse_trace_header": "aggregate",
}
__getattr__, __dir__ = lazy_exports(__name__, _EXPORTS)

__all__ = [
    "CollectorServer",
    "EngineVitals",
    "NULL_EXPORTER",
    "NULL_TRACE",
    "NULL_VITALS",
    "ProfilerBusy",
    "ProfilerCapture",
    "ProgramCostTable",
    "SLOTarget",
    "SLOTracker",
    "Span",
    "StallWatchdog",
    "StructuredLog",
    "TRACE_HEADER",
    "Trace",
    "TraceCollector",
    "TraceExporter",
    "Tracer",
    "format_trace_header",
    "parse_trace_header",
]
