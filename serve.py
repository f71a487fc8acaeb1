#!/usr/bin/env python
"""Serve a trained DALL-E checkpoint over HTTP with dynamic micro-batching.

The production face of `generate.py`: the same `GenerationEngine` (KV-cached
scan decode, fused dVAE pixel decode, optional CLIP rerank), fed by a
bounded request queue that coalesces concurrent callers into fixed-shape
compiled batches. See README "Serving" for the API and metrics reference.

    python serve.py --dalle_path checkpoints/dalle.npz --port 8000
    curl -s localhost:8000/generate -d '{"prompt": "small red circle"}'
    curl -s localhost:8000/metrics
"""

from __future__ import annotations

import argparse
import signal
import sys


def parse_tenant_weights(text):
    """'a=4,b=1' -> {"a": 4.0, "b": 1.0}; raises ValueError on junk."""
    out = {}
    for pair in (text or "").split(","):
        if not pair:
            continue
        tenant, sep, weight = pair.partition("=")
        if not sep or not tenant:
            raise ValueError(f"expected tenant=weight, got {pair!r}")
        w = float(weight)
        if w <= 0:
            raise ValueError(f"tenant {tenant!r} weight must be > 0")
        out[tenant] = w
    return out


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dalle_path", type=str, default=None,
                   help="DALL-E checkpoint to serve (required unless "
                   "--router)")
    p.add_argument("--router", action="store_true",
                   help="run the replica fleet ROUTER instead of an "
                   "engine replica: front the --replicas URLs with "
                   "health-aware routing, failover retries under a "
                   "success-fraction retry budget, optional hedging, "
                   "and graceful drain (POST /admin/drain?replica=). "
                   "No checkpoint loads in this mode")
    from dalle_pytorch_tpu.serving.router import add_router_args

    add_router_args(p, require_replicas=False)
    p.add_argument("--clip_path", type=str, default=None,
                   help="optional CLIP checkpoint enabling rerank=true requests")
    p.add_argument("--host", type=str, default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000, help="0 picks a free port")
    p.add_argument(
        "--batch_shapes", type=str, default="1,4,8",
        help="comma-separated compiled batch sizes; requests are padded up "
        "to the nearest shape (more shapes = less padding waste, more "
        "compiles at warmup)",
    )
    p.add_argument("--max_delay_ms", type=float, default=25.0,
                   help="micro-batch flush deadline from the oldest request")
    p.add_argument(
        "--engine", choices=("micro", "continuous"), default="micro",
        help="micro: padded micro-batches, one full decode scan per flush; "
        "continuous: token-boundary admission over cache slots (lower "
        "time-to-first-token under load; slot count = max of "
        "--batch_shapes; cond_scale must be 1)",
    )
    p.add_argument("--chunk_tokens", type=int, default=4,
                   help="continuous engine: tokens decoded per chunk "
                   "dispatch (smaller = faster admission/retirement, more "
                   "host round trips)")
    p.add_argument("--prefill_batch", type=int, default=4,
                   help="continuous engine: prompts admitted per prefill "
                   "dispatch (R pending requests cost ceil(R/prefill_batch) "
                   "dispatches at a chunk boundary; clamped to the slot "
                   "count)")
    p.add_argument(
        "--kv_layout", choices=("slot", "paged"), default="slot",
        help="continuous engine cache layout. slot: one full-length KV "
        "lane per slot (HBM = max_batch worst case); paged: block-paged "
        "pool + per-row page tables with content-hash prefix caching "
        "(HBM follows tokens actually held; repeat prompts admit with "
        "zero prefill dispatches)",
    )
    p.add_argument("--page_size", type=int, default=32,
                   help="paged layout: tokens per KV page (TPU wants a "
                   "multiple of 8 for the paged Pallas kernel)")
    p.add_argument("--kv_pages", type=int, default=None,
                   help="paged layout: physical pages in the pool "
                   "(default sizes the slotted worst case + one row of "
                   "prefix-cache headroom; size it DOWN to cap HBM — "
                   "admission then backpressures on free pages)")
    p.add_argument("--prefix_entries", type=int, default=64,
                   help="paged layout: prompts kept in the prefix cache "
                   "(0 disables prefix caching; LRU eviction)")
    p.add_argument("--mesh", type=str, default=None, metavar="AXES",
                   help="serve one engine SHARDED over a device mesh "
                   "(continuous engine, slot OR paged layout): axis=size "
                   "pairs over dp/fsdp/tp/sp, e.g. 'dp=1,tp=4'; one size "
                   "may be -1 to absorb the remaining devices. Params "
                   "shard per parallel/partition.py, the KV cache (slot "
                   "lanes or the paged page pool) over attention heads "
                   "(parallel/serving_partition.py); page tables stay "
                   "host-side. CPU smoke test: XLA_FLAGS="
                   "--xla_force_host_platform_device_count=8")
    p.add_argument("--kv_dtype", choices=("model", "int8"), default="model",
                   help="KV-cache storage dtype (continuous engine). "
                   "model: the model compute dtype (bit-identical "
                   "default); int8: pages/lanes stored quantized with "
                   "per-(position, head) fp32 scales, dequantized inside "
                   "the decode kernels — roughly 2x decode rows per HBM "
                   "byte (exactly 2D/(D+4) at head dim D) at a small "
                   "quantization error (tests/test_kv_quant.py bounds the "
                   "token drift on a toy; not measured on the chip)")
    p.add_argument("--decode_sparsity", choices=("causal", "policy"),
                   default="causal",
                   help="decode-attention sparsity (continuous engine). "
                   "causal: dense-causal flash decode, the bit-identical "
                   "default; policy: pattern-masked layers route through "
                   "the block-sparse flash kernel with per-slot KV-tile "
                   "bitmaps derived host-side from the model's static "
                   "attention layouts (serving/sparsity.py) and shipped "
                   "as traced data — dead tiles skip compute AND DMA, "
                   "zero extra compiled programs after warmup "
                   "(/metrics counts dalle_serving_kv_tiles_skipped_total; "
                   "not measured on the chip)")
    p.add_argument("--max_queue", type=int, default=64,
                   help="queue bound in rows; beyond it requests get 503")
    p.add_argument("--request_timeout_s", type=float, default=120.0)
    p.add_argument("--no_preempt", action="store_true",
                   help="disable decode-time priority preemption "
                   "(continuous engine): a high-priority request blocked "
                   "on slots then waits for natural completions instead "
                   "of reclaiming a low-priority slot at a chunk boundary")
    p.add_argument("--no_shed", action="store_true",
                   help="disable deadline-aware admission shedding "
                   "(continuous engine): requests whose estimated "
                   "completion exceeds their own timeout queue anyway "
                   "instead of getting an immediate 503 + Retry-After")
    p.add_argument("--tenant_quota_rows", type=int, default=None,
                   help="per-tenant cap on queued request rows; a tenant "
                   "past it gets 429 + Retry-After (default: no quota)")
    p.add_argument("--tenant_weights", type=str, default=None,
                   metavar="T=W,...",
                   help="proportional per-tenant admission shares within "
                   "each priority class, e.g. 'a=4,b=1' (a backlogged "
                   "weight-4 tenant gets ~4x the rows of a weight-1 "
                   "one; unlisted tenants weigh 1; weights are shares, "
                   "--tenant_quota_rows stays the hard cap)")
    p.add_argument("--replica_quarantine_after", type=int, default=2,
                   help="replica-side poison threshold: a request that "
                   "died in flight for this many CONSECUTIVE failed "
                   "engine dispatches gets a terminal 422 with the "
                   "incident ids instead of a failover-inviting 500 "
                   "(default 2 pairs with the batcher's one bounded "
                   "retry; 0 disables — distinct from the router-level "
                   "--quarantine_after, which tracks replica CRASHES)")
    p.add_argument("--reserve_slots", type=int, default=0,
                   help="cache slots reserved for priority 'high' "
                   "requests (continuous engine): high arrivals admit at "
                   "the next chunk boundary without waiting for a "
                   "preemption cycle, at the cost of idle slots when "
                   "there is no high traffic (default 0: work-conserving, "
                   "preemption alone reclaims capacity)")
    p.add_argument("--cond_scale", type=float, default=1.0)
    p.add_argument("--no_warmup", action="store_true",
                   help="skip compiling all batch shapes at startup (first "
                   "request per shape then pays compile latency)")
    p.add_argument("--compile_cache", type=str, default=None, metavar="DIR",
                   help="persistent compile cache: jax's XLA executable "
                   "store plus fingerprinted AOT artifacts for every "
                   "warmed program live under DIR, so a restarted "
                   "replica (same checkpoint/config/jax/mesh) warms up "
                   "in seconds instead of recompiling — a mismatched or "
                   "corrupt cache degrades to a normal cold boot, never "
                   "a failed one (counted in "
                   "dalle_boot_cache_{hits,misses,rejects}_total)")
    p.add_argument("--no_resume", action="store_true",
                   help="drop the mid-decode resume program from the "
                   "continuous engine's warmup ladder: migrated/preempted "
                   "rows then restart decode at position 0 (bit-identical "
                   "output, more re-decoded work) instead of resuming at "
                   "their checkpointed position via one teacher-forced "
                   "re-prefill dispatch")
    p.add_argument("--checkpoint_spool", type=str, default=None,
                   metavar="DIR",
                   help="arm the crash progress beacon: every "
                   "--spool_every chunks the continuous batcher journals "
                   "in-flight decode-state checkpoints to DIR (one "
                   "atomic bounded file); after a crash the supervisor "
                   "hands the journal to the fleet router so interrupted "
                   "requests resume instead of re-decoding from scratch")
    p.add_argument("--spool_every", type=int, default=8,
                   help="chunk boundaries between beacon writes (a hard "
                   "kill loses at most this many chunks of journaled "
                   "progress)")
    p.add_argument("--preview_every", type=int, default=4,
                   help="streaming /generate: decode chunks between "
                   "progressive preview events (partial token grid filled "
                   "with the mean codebook token, run through the warmed "
                   "fill+decode program, shipped as base64 PNG). 0 "
                   "disables previews — and drops the preview program "
                   "from the warmup ladder — while per-chunk progress "
                   "events still flow")
    p.add_argument("--spool_notify", type=str, default=None, metavar="URL",
                   help="with --supervise: fleet router base URL the "
                   "supervisor POSTs the spool to (/admin/spool) once "
                   "the restarted replica is ready")
    p.add_argument("--supervise", action="store_true",
                   help="run this replica under the crash-fast "
                   "supervisor: the server becomes a subprocess that is "
                   "restarted on abnormal exit with capped exponential "
                   "backoff and crash-loop hold-down, readiness gated "
                   "on its real /healthz (pair with --compile_cache so "
                   "restarts rejoin in seconds). Needs an explicit "
                   "--port")
    p.add_argument("--verbose", action="store_true", help="HTTP access logs")
    p.add_argument("--trace-dump", "--trace_dump", dest="trace_dump",
                   type=str, default=None, metavar="PATH",
                   help="write the request-trace ring buffer as Perfetto "
                   "trace_event JSON to PATH on drain/shutdown (the live "
                   "view is GET /debug/traces)")
    p.add_argument("--trace_ring", type=int, default=256,
                   help="how many recent request traces to keep in memory")
    p.add_argument("--trace_export", type=str, default=None, metavar="URL",
                   help="ship finished request traces to a fleet trace "
                   "collector (python -m dalle_pytorch_tpu.obs.collector) "
                   "at URL as batched JSONL — bounded buffer + backoff; "
                   "serving is unaffected when the collector is down")
    p.add_argument("--trace_site", type=str, default=None, metavar="NAME",
                   help="stable process identity for fleet traces and "
                   "request-log lines (one track per site in the "
                   "collector's merged view; default: hostname)")
    p.add_argument("--no_tracing", action="store_true",
                   help="disable the request span tracer entirely "
                   "(/debug/traces serves an empty trace; stage metrics "
                   "on /metrics still work)")
    p.add_argument("--profile_dir", type=str, default="profiles",
                   help="where POST /debug/profile?seconds=N writes its "
                   "TensorBoard trace directories")
    p.add_argument("--no_request_log", action="store_true",
                   help="suppress the structured JSON log line per "
                   "completed request")
    p.add_argument("--request_log_path", type=str, default=None,
                   metavar="FILE",
                   help="write structured JSONL to FILE instead of "
                   "stdout (append mode; lifecycle events included)")
    p.add_argument("--request_log_max_mb", type=float, default=None,
                   metavar="MB",
                   help="rotate --request_log_path once it exceeds MB "
                   "megabytes: the full file is renamed to FILE.1 "
                   "(keep one) and a fresh file is started, so disk "
                   "use stays bounded at ~2x the cap")
    p.add_argument("--no_vitals", action="store_true",
                   help="disable the engine-vitals sampler (and with it "
                   "the stall watchdog and SLO burn tracking); "
                   "/debug/vitals then serves an empty ring")
    p.add_argument("--vitals_interval_s", type=float, default=1.0,
                   help="seconds between vitals snapshots / watchdog "
                   "checks")
    p.add_argument("--no_program_costs", action="store_true",
                   help="skip per-program XLA cost capture at warmup "
                   "(saves one extra AOT compile per program; "
                   "/debug/programs and the MFU gauges then stay empty)")
    p.add_argument("--slo_ttft_ms", type=float, default=None,
                   help="time-to-first-token SLO target in ms "
                   "(continuous engine); burn rate over the rolling "
                   "window drives the /healthz degraded tier and "
                   "dalle_slo_burn_rate{slo=\"ttft\"}")
    p.add_argument("--slo_request_ms", type=float, default=None,
                   help="end-to-end request latency SLO target in ms")
    p.add_argument("--slo_objective", type=float, default=0.99,
                   help="fraction of requests that must meet each SLO "
                   "target (error budget = 1 - objective)")
    p.add_argument("--slo_window_s", type=float, default=300.0,
                   help="rolling window for SLO burn-rate computation")
    args = p.parse_args(argv)
    if args.supervise:
        if args.router:
            p.error("--supervise supervises an engine replica; run the "
                    "router under its own process manager")
        if args.port == 0:
            p.error("--supervise needs an explicit --port (the "
                    "supervisor probes http://host:port/healthz for "
                    "readiness; port 0 would pick a fresh one per "
                    "restart)")
    if args.spool_notify is not None and not args.supervise:
        p.error("--spool_notify is the supervisor's hand-off hook; it "
                "needs --supervise")
    if args.spool_notify is not None and args.checkpoint_spool is None:
        p.error("--spool_notify needs --checkpoint_spool (nothing to "
                "hand over otherwise)")
    if args.checkpoint_spool is not None and (
        args.router or args.engine != "continuous"
    ):
        p.error("--checkpoint_spool needs --engine continuous (the "
                "router and the micro engine hold no resumable decode "
                "state)")
    if args.spool_every < 1:
        p.error("--spool_every must be >= 1")
    if args.preview_every < 0:
        p.error("--preview_every must be >= 0 (0 disables previews)")
    if args.request_log_max_mb is not None:
        if args.request_log_path is None:
            p.error("--request_log_max_mb rotates a log file; it needs "
                    "--request_log_path")
        if args.request_log_max_mb <= 0:
            p.error("--request_log_max_mb must be > 0")
    if args.router:
        if not args.replicas:
            p.error("--router needs --replicas URL[,URL...]")
        if args.dalle_path is not None:
            p.error("--router does not load a checkpoint; drop "
                    "--dalle_path (replicas load their own)")
        if args.no_tracing and args.trace_export is not None:
            p.error("--trace_export needs the span tracer; drop "
                    "--no_tracing")
        return args
    if args.dalle_path is None:
        p.error("--dalle_path is required (unless running --router)")
    if args.replicas is not None:
        p.error("--replicas only applies with --router")
    try:
        args.tenant_weights = parse_tenant_weights(args.tenant_weights) or None
    except ValueError as exc:
        p.error(f"bad --tenant_weights: {exc}")
    if args.mesh is not None:
        # fail at parse time, not after the checkpoint loads: both the
        # engine mode and the mesh string itself (slot AND paged layouts
        # both shard — the paged pool head-splits, tables stay host-side)
        if args.engine != "continuous":
            p.error("--mesh needs --engine continuous")
        from dalle_pytorch_tpu.serving.sharded import parse_mesh_shape

        try:
            parse_mesh_shape(args.mesh)
        except (AssertionError, ValueError) as exc:
            p.error(f"bad --mesh {args.mesh!r}: {exc}")
    if args.no_vitals and (
        args.slo_ttft_ms is not None or args.slo_request_ms is not None
    ):
        # the sampler thread drives SLO burn updates; without it the
        # gauge would sit at 0 forever — fail loudly, not silently
        p.error("--slo_ttft_ms/--slo_request_ms need the vitals sampler; "
                "drop --no_vitals")
    if args.tenant_quota_rows is not None and args.tenant_quota_rows < 1:
        p.error("--tenant_quota_rows must be >= 1 (omit it for no quota)")
    if args.replica_quarantine_after < 0:
        p.error("--replica_quarantine_after must be >= 0 (0 disables)")
    max_shape = max(
        (int(b) for b in args.batch_shapes.split(",") if b), default=1
    )
    if not 0 <= args.reserve_slots < max_shape:
        p.error(f"--reserve_slots must be in [0, {max_shape - 1}] so at "
                "least one slot stays usable by every class")
    if args.trace_export is not None and args.no_tracing:
        # the exporter ships finished traces; a disabled tracer never
        # finishes any — fail loudly, not with a silently idle exporter
        p.error("--trace_export needs the span tracer; drop --no_tracing")
    return args


import contextlib


@contextlib.contextmanager
def _null_phase(name):
    """Boot-phase timer stand-in when no compile cache is configured."""
    yield


def run_router(args):
    """`serve.py --router`: the fleet admission router in front of N
    replicas — no jax, no checkpoint, stdlib HTTP only. One shared run
    loop with `python -m dalle_pytorch_tpu.serving.router`."""
    from dalle_pytorch_tpu.obs.logging import StructuredLog
    from dalle_pytorch_tpu.serving.router import run_router_server

    log = StructuredLog(component="dalle.router", site=args.trace_site,
                        path=args.request_log_path,
                        max_mb=args.request_log_max_mb)
    return run_router_server(args, log=log)


def main(argv=None):
    args = parse_args(argv)
    if args.router:
        return run_router(args)
    if args.supervise:
        # BEFORE the jax import: the supervisor process only spawns and
        # probes — the child pays the runtime, and pays it again per
        # restart (which is exactly what --compile_cache amortizes)
        from dalle_pytorch_tpu.serving.supervisor import supervise_serve

        return supervise_serve(args, argv)
    import jax
    import os as _os

    from dalle_pytorch_tpu.obs import (
        EngineVitals, ProfilerCapture, ProgramCostTable, SLOTarget,
        SLOTracker, StallWatchdog, StructuredLog, TraceExporter, Tracer,
    )
    from dalle_pytorch_tpu.serving import ServingServer, engine_from_checkpoint
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry
    from dalle_pytorch_tpu.utils import compile_guard
    from dalle_pytorch_tpu.utils.compile_cache import (
        CompileCache, boot_fingerprint, enable_xla_cache,
    )
    from dalle_pytorch_tpu.utils.device import bytes_in_use_per_device, log_device

    log_device()

    # structured JSONL on stdout replaces the old ad-hoc status prints;
    # the one surviving print is the "[serve] listening" readiness line,
    # which orchestrators pattern-match. --no_request_log drops only the
    # per-request lines; lifecycle events (warmup, trace_dump, shutdown)
    # always flow. --trace_site stamps every line's process identity so
    # fleet logs merge and join against collector traces by trace_id.
    log = StructuredLog(site=args.trace_site, path=args.request_log_path,
                        max_mb=args.request_log_max_mb)

    registry = MetricsRegistry()
    cache = None
    if args.compile_cache:
        # install BEFORE anything compiles: the persistent XLA store must
        # see the warmup ladder's compiles (and serve them back next boot)
        cache = CompileCache(
            args.compile_cache, registry=registry, log=log
        ).install()
    else:
        enable_xla_cache()

    batch_shapes = tuple(int(b) for b in args.batch_shapes.split(",") if b)
    phases = cache.boot_phase if cache is not None else _null_phase
    with phases("checkpoint"):
        engine = engine_from_checkpoint(
            args.dalle_path,
            clip_path=args.clip_path,
            batch_shapes=batch_shapes,
            cond_scale=args.cond_scale,
            registry=registry,
            mode=args.engine,
            chunk_tokens=args.chunk_tokens,
            prefill_batch=args.prefill_batch,
            kv_layout=args.kv_layout,
            page_size=args.page_size,
            kv_pages=args.kv_pages,
            prefix_entries=args.prefix_entries,
            mesh=args.mesh,
            kv_dtype=args.kv_dtype,
            decode_sparsity=args.decode_sparsity,
            resume_enabled=not args.no_resume,
            # --preview_every 0 drops the preview fill+decode program
            # from the warmup ladder entirely (micro engines never
            # stream, so the knob is continuous-only either way)
            preview_enabled=args.preview_every > 0,
        )
    # after placement: a sharded engine that piled everything on the
    # first device shows here, per device
    log.event("placement", bytes_in_use=bytes_in_use_per_device())
    if cache is not None:
        # identity of this compiled-ladder universe: any drift (jax
        # upgrade, backend, mesh, model config, new program) turns the
        # on-disk artifacts into counted misses and the boot goes cold
        with phases("plan"):
            cache.bind(
                boot_fingerprint(
                    backend=jax.default_backend(),
                    mesh_shape=args.mesh,
                    model_config=engine.cfg,
                    programs=engine.program_ladder(),
                ),
                engine.program_ladder(),
            )
            cache.plan_boot()
        engine.compile_cache = cache
    if not args.no_program_costs:
        # attach BEFORE warmup: capture happens while the ladder compiles
        # (one extra AOT compile per program — the price of
        # /debug/programs rows and live MFU gauges)
        engine.cost_table = ProgramCostTable(registry=engine.registry)
    if not args.no_warmup:
        log.event("warmup_start", batch_shapes=list(engine.batch_shapes))
        with compile_guard.track_compiles() as tally:
            with phases("warmup"):
                engine.warmup()
        # compiles vs cache_hits is the warm-boot receipt: a second boot
        # against a matching cache logs uncached_compiles=0 (pinned by
        # the slow-tier recovery test)
        log.event(
            "warmup_done",
            compiled_shapes=list(engine.stats.compiled_shapes),
            compiles=tally.count,
            cache_hits=tally.cache_hits,
            uncached_compiles=tally.uncached,
            boot_cache_mode=cache.plan["mode"] if cache is not None else None,
            boot_seconds=dict(cache.boot_seconds) if cache is not None else None,
        )

    compiles_at_ready = compile_guard.compile_count()

    crash_spec = _os.environ.get("DALLE_SERVE_CRASH")
    if crash_spec:
        # chaos-only seam (recovery drills, the supervised-restart
        # bench): hard-abort this replica at the Nth dispatch of a named
        # program, e.g. DALLE_SERVE_CRASH=chunk:3
        from dalle_pytorch_tpu.serving import FaultInjector

        prog, _, nth = crash_spec.partition(":")
        engine.faults = FaultInjector().crash_nth(prog, int(nth or 1))
        log.event("chaos_crash_armed", program=prog, nth=int(nth or 1))

    slo_targets = []
    if args.slo_ttft_ms is not None:
        slo_targets.append(SLOTarget(
            "ttft", args.slo_ttft_ms / 1000.0,
            histogram="dalle_serving_ttft_seconds",
            objective=args.slo_objective,
        ))
    if args.slo_request_ms is not None:
        slo_targets.append(SLOTarget(
            "request", args.slo_request_ms / 1000.0,
            histogram="dalle_serving_request_latency_seconds",
            objective=args.slo_objective,
        ))
    vitals = EngineVitals(
        enabled=not args.no_vitals,
        interval_s=args.vitals_interval_s,
        registry=engine.registry,
        log=log,
        watchdog=StallWatchdog(
            registry=engine.registry,
            # a queued head older than the request timeout should already
            # have been failed by the worker; half of it is "stale"
            queue_age_budget_s=args.request_timeout_s / 2.0,
        ),
        slo=(
            SLOTracker(
                slo_targets, registry=engine.registry,
                window_s=args.slo_window_s,
            )
            if slo_targets else None
        ),
    )

    exporter = None
    if args.trace_export is not None:
        # the exporter registers its drop/sent/retry counters on the
        # engine registry so /metrics carries fleet-export health
        exporter = TraceExporter(
            args.trace_export, site=args.trace_site,
            registry=engine.registry,
        )
        log.event("trace_export", url=exporter.url, site=exporter.site)

    server = ServingServer(
        engine,
        host=args.host,
        port=args.port,
        max_delay_ms=args.max_delay_ms,
        max_queue_rows=args.max_queue,
        request_timeout_s=args.request_timeout_s,
        verbose=args.verbose,
        tracer=Tracer(
            enabled=not args.no_tracing, max_traces=args.trace_ring
        ),
        exporter=exporter,
        log=log,
        log_requests=not args.no_request_log,
        profiler=ProfilerCapture(out_dir=args.profile_dir),
        trace_dump_path=args.trace_dump,
        vitals=vitals,
        tenant_quota_rows=args.tenant_quota_rows,
        tenant_weights=args.tenant_weights,
        preempt=not args.no_preempt,
        deadline_shed=not args.no_shed,
        reserve_slots=args.reserve_slots,
        quarantine_after=args.replica_quarantine_after,
        checkpoint_spool=args.checkpoint_spool,
        spool_every=args.spool_every,
        preview_every=args.preview_every,
    )

    import threading

    stopped = threading.Event()

    def _shutdown():
        server.shutdown()  # drains the queue, then stops the listener
        stopped.set()

    stopping = threading.Event()

    def _stop(signum, frame):
        if stopping.is_set():  # second signal: drain is wedged, force quit
            print("[serve] second signal: exiting immediately", flush=True)
            import os

            os._exit(1)
        stopping.set()
        print(f"[serve] signal {signum}: draining queue and shutting down",
              flush=True)
        # shutdown() joins the serve loop; run it off the main thread, which
        # is blocked inside serve_forever
        threading.Thread(target=_shutdown, daemon=True).start()

    signal.signal(signal.SIGINT, _stop)
    signal.signal(signal.SIGTERM, _stop)

    # parseable readiness line: tests and orchestrators wait for it
    print(f"[serve] listening on http://{args.host}:{server.port} "
          f"(engine={args.engine}, shapes={engine.batch_shapes}, "
          f"max_delay_ms={args.max_delay_ms}, max_queue={args.max_queue})",
          flush=True)
    server.serve_forever()
    stopped.wait(timeout=60)  # let the drain finish before exiting
    # the steady-state contract, said by the process itself: nothing
    # compiled between the readiness line and shutdown
    log.event(
        "served",
        compiles_while_serving=compile_guard.compile_count() - compiles_at_ready,
    )
    print("[serve] shutdown complete", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
