"""Batched text→image generation service.

The serving layer the ROADMAP north star calls for: a dynamic request
queue feeding fixed-shape compiled sampler programs.

  * `engine.py`   — `GenerationEngine`: wraps the KV-cached sampler
    (`models/dalle.py:generate_images_cached_batched`) behind a fixed set
    of compiled batch shapes, pads partial batches, warms up compilation,
    and optionally CLIP-reranks results. `ContinuousEngine` +
    `SlotAllocator`: continuous batching — one persistent decode state of
    `max_batch` cache slots advanced in K-token chunks, prompts admitted
    into free slots at token boundaries in batched prefill waves
    (`models/dalle.py:prefill_into_slots` / `decode_image_chunk`).
  * `sharded.py`  — `ShardedContinuousEngine`: the same continuous
    engine spread over a `make_mesh` device mesh — params per
    `parallel/partition.py`'s rules, the slot KV cache head-split per
    `parallel/serving_partition.py`, the flash-decode kernel
    shard_map-split per head. Same program bodies, same serving
    surface, bit-identical tokens; `serve.py --mesh dp=1,tp=4`.
  * `batcher.py`  — `MicroBatcher`: bounded queue with dynamic
    micro-batching (flush on max-batch or deadline), backpressure via
    queue-full rejection, per-request timeout/cancellation, graceful
    drain. `ContinuousBatcher`: same queue surface, but an
    admit→chunk→retire worker loop over the slot cache, with decode-time
    priority preemption, chunk-boundary cancel/timeout retirement, and
    one bounded retry after a failed dispatch rebuilds engine state.
  * `qos.py`      — priority classes ("high"/"normal"/"low"), the
    `WeightedFairQueue` stride scheduler with per-tenant accounting,
    tenant quotas (`TenantQuotaError` → 429) and deadline-aware
    admission shedding (`ShedError` → 503 + Retry-After).
  * `migrate.py`  — decode-state checkpoints: `RowCheckpoint` /
    `RequestCheckpoint` + the fingerprint-stamped codec, the
    `CheckpointSpool` crash-beacon journal, and `MigratedError` (the
    chunk-boundary export of `drain?migrate=1`). A drained or crashed
    replica's in-flight requests MOVE — completed rows restore verbatim
    on the resuming replica, unfinished rows restart bit-identically —
    instead of being waited out or re-decoded from scratch.
  * `faults.py`   — `FaultInjector`: deterministic fail-Nth / stall-Nth
    / crash-Nth seam on engine dispatches plus compile-cache artifact
    corruption, for recovery-invariant tests and chaos drills (attach
    to `engine.faults` / `CompileCache.faults`).
  * `supervisor.py` — `ReplicaSupervisor`: crash-fast replica restart —
    spawn the serve.py subprocess, gate readiness on its real /healthz,
    restart abnormal exits with capped exponential backoff, hold down
    crash loops (N exits in a window) with a structured `crash_loop`
    event. `serve.py --supervise` or
    `python -m dalle_pytorch_tpu.serving.supervisor -- cmd...`; pair
    with `serve.py --compile_cache` so a restart rejoins in seconds.
  * `router.py`   — `FleetRouter` + `RouterServer`: ONE admission router
    in front of N replicas (`python -m dalle_pytorch_tpu.serving.router`
    / `serve.py --router --replicas ...`): /healthz-probed per-replica
    state (healthy / degraded-deprioritized / ejected) with a rolling
    error-rate circuit breaker, least-outstanding routing with QoS
    spillover and Retry-After class cooldowns, failover retries under a
    success-fraction retry budget (seed pinned at ingress, so
    re-dispatch is bit-identical), optional tail hedging, and graceful
    drain (`POST /admin/drain?replica=` — a rolling restart is a
    zero-error event).
  * `server.py`   — stdlib-only JSON HTTP API: POST /generate,
    GET /healthz (ok / degraded / 503 tiers), GET /metrics (Prometheus
    text format; `?exemplars=1` for OpenMetrics exemplars),
    GET /debug/traces (Perfetto export; `?trace_id=` exact lookup),
    GET /debug/vitals + /debug/programs + /debug/state (device
    telemetry, per-program cost/MFU table, engine-state dump —
    `obs/vitals.py`), POST /debug/profile (on-demand jax.profiler
    capture). Requests are traced end-to-end through the batcher by
    `dalle_pytorch_tpu/obs/` — trace ID minted at ingress, one span per
    stage, one structured JSON log line per completed request.

`serve.py` at the repo root is the CLI entrypoint; `generate.py` drives
the same `GenerationEngine` for one-shot CLI batches, so the two paths
cannot drift.
"""

from dalle_pytorch_tpu._lazy import lazy_exports

_EXPORTS = {
    "CheckpointCorrupt": "migrate",
    "CheckpointMismatch": "migrate",
    "CheckpointSpool": "migrate",
    "ContinuousBatcher": "batcher",
    "ContinuousEngine": "engine",
    "FaultInjector": "faults",
    "FleetRouter": "router",
    "GenerationEngine": "engine",
    "InjectedFault": "faults",
    "MicroBatcher": "batcher",
    "MigratedError": "migrate",
    "PRIORITY_CLASSES": "qos",
    "QuarantineTracker": "router",
    "QueueFullError": "batcher",
    "ReplicaSupervisor": "supervisor",
    "RequestCancelled": "batcher",
    "RequestCheckpoint": "migrate",
    "RequestTimeout": "batcher",
    "RetryBudget": "router",
    "RouterServer": "router",
    "RowCheckpoint": "migrate",
    "SampleSpec": "engine",
    "ServingServer": "server",
    "ShardedContinuousEngine": "sharded",
    "ShedError": "qos",
    "ShuttingDownError": "batcher",
    "SlotAllocator": "engine",
    "TenantQuotaError": "qos",
    "WeightedFairQueue": "qos",
    "build_serving_mesh": "sharded",
    "decode_checkpoint": "migrate",
    "encode_checkpoint": "migrate",
    "engine_from_checkpoint": "engine",
    "from_wire": "migrate",
    "parse_mesh_shape": "sharded",
    "request_fingerprint": "router",
    "to_wire": "migrate",
}
__getattr__, __dir__ = lazy_exports(__name__, _EXPORTS)

__all__ = [
    "CheckpointCorrupt",
    "CheckpointMismatch",
    "CheckpointSpool",
    "ContinuousBatcher",
    "ContinuousEngine",
    "FaultInjector",
    "MigratedError",
    "RequestCheckpoint",
    "RowCheckpoint",
    "decode_checkpoint",
    "encode_checkpoint",
    "from_wire",
    "to_wire",
    "GenerationEngine",
    "InjectedFault",
    "PRIORITY_CLASSES",
    "SampleSpec",
    "ShedError",
    "SlotAllocator",
    "TenantQuotaError",
    "WeightedFairQueue",
    "engine_from_checkpoint",
    "FleetRouter",
    "QuarantineTracker",
    "ReplicaSupervisor",
    "RetryBudget",
    "RouterServer",
    "request_fingerprint",
    "MicroBatcher",
    "QueueFullError",
    "RequestCancelled",
    "RequestTimeout",
    "ShuttingDownError",
    "ServingServer",
    "ShardedContinuousEngine",
    "build_serving_mesh",
    "parse_mesh_shape",
]
