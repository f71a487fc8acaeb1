"""Serving benchmark: closed-loop sweep and open-loop engine comparison.

Closed-loop mode (default, BENCH_* contract): drives the real
`GenerationEngine` + `MicroBatcher` (no HTTP, no checkpoint — a tiny
randomly-initialized model) with N closed-loop client threads, sweeping N.
Each client submits one request after another, so offered load scales with
concurrency and the micro-batcher's deadline-or-capacity policy determines
how many rows coalesce per dispatch. Prints ONE JSON line with the sweep
and a headline req/s at the top concurrency.

Open-loop mode (`--mode open-loop`): Poisson arrivals at a fixed rate
against BOTH engines — the padded micro-batch `GenerationEngine` and the
continuous-batching `ContinuousEngine` — over the SAME toy weights and the
SAME pre-drawn arrival schedule. Emits one JSON line per engine with
sustained req/s and time-to-first-token percentiles; the continuous line
carries the micro-relative ratios. This is the acceptance instrument for
the continuous-batching PR: token-boundary admission must show >= 1.5x
sustained req/s or <= 0.5x p95 TTFT at equal load.

Env overrides: SERVE_SWEEP ("1,4,8" client counts), SERVE_REQUESTS (per
client, default 8), SERVE_BATCH_SHAPES ("1,4,8"), SERVE_DELAY_MS (25),
SERVE_DIM/SERVE_DEPTH/SERVE_FMAP/SERVE_TEXT_SEQ for the toy model;
open-loop: SERVE_RATE_RPS (default auto-calibrated), SERVE_OPEN_SECONDS
(10), SERVE_CHUNK_TOKENS (4), SERVE_PREFILL_BATCH (4), SERVE_ARRIVAL_SEED
(0). The continuous JSON line reports admission-dispatch accounting
(prefill_dispatches / prefill_rows_per_dispatch) so the batched-prefill
amortization is visible in the output. Both open-loop lines carry a
`stages` per-stage breakdown ({stage: {mean_ms, count}} deltas from the
`dalle_serving_stage_seconds` family over the measured window only), so
a TTFT regression is attributable to queue vs prefill vs chunk without
re-running under a tracer. The continuous line additionally carries a
`vitals` block (obs/vitals.py sampler over the measured window only:
mean/peak slots_active — blocks too on the paged layout — plus per-
program MFU where the cost table measured a synced dispatch).

Paged KV cache (`--kv_layout paged`, SERVE_PAGE_SIZE / SERVE_KV_PAGES):
the continuous engine becomes `PagedContinuousEngine` and its line gains
`block_occupancy` (measured-window peak pages vs the slotted layout's
always-resident worst case) and `prefix_cache` / `prefix_hit_rate` with
hit-vs-cold TTFT splits. `--prompt_reuse P` (SERVE_PROMPT_REUSE) makes P
of the arrivals repeat a prompt from a Zipf-ish popularity pool — the
workload on which prefix caching turns repeat admissions into
near-zero-cost TTFT; both engines replay the identical prompt schedule.

Mesh-sharded serving (`--mesh tp=2`, SERVE_MESH): the continuous side
runs as `ShardedContinuousEngine` — or `ShardedPagedContinuousEngine`
when combined with `--kv_layout paged` (the page pool head-splits, page
tables stay host-side) — over a `make_mesh` device mesh, and its JSON
line gains a `mesh` block — axis sizes, per-device state-buffer bytes,
and the per-device memory PEAK over the measured window. On CPU pair it
with XLA_FLAGS=--xla_force_host_platform_device_count=8.

Quantized KV cache (`--kv_dtype int8`, SERVE_KV_DTYPE): the continuous
engine stores its KV pages/lanes as int8 with per-(position, head)
scales (dequantized inside the decode kernels), and its JSON line gains
a `quality` block: the SAME (prompt, seed) rows generated through the
bf16 micro engine and the quantized engine, scored by a toy CLIP —
clip_mean_ref / clip_mean_quantized / clip_delta_mean put the quality
cost beside the `kv_bytes_per_slot` capacity win (speed AND quality,
never speed alone).

Priority mix (`--priority_mix P`, SERVE_PRIORITY_MIX): the QoS acceptance
instrument. Open-loop Poisson arrivals at an OVERLOAD rate
(SERVE_PRIORITY_OVERLOAD x the continuous engine's measured saturation,
default 1.3) against ONE continuous batcher with preemption + deadline
shedding on; each arrival is "high" with probability P, "low" otherwise
(bimodal). The JSON line reports per-class completion and TTFT
percentiles, the preemption/resumption/shed counter families, and
`high_ttft_p95_ratio_vs_unloaded` — high-priority p95 TTFT against the
same batcher's measured UNLOADED baseline. The QoS claim is that ratio
staying small (the low class absorbs the overload via preemption and
shedding) while low-class p95 degrades.

Fleet mode (`--replicas N`, SERVE_REPLICAS): robustness instrument for
the replica router. N in-process continuous replicas behind a real
`FleetRouter` take the same open-loop Poisson schedule twice over HTTP —
once healthy, once with one replica HARD-KILLED 30% into the window. The
JSON line reports both windows' completion and latency percentiles, the
p95 killed-vs-healthy ratio, and the router's failover/hedge/ejection
accounting; the headline value is the killed-window completion fraction
(the chaos claim is 1.0 — failover retries absorb the crash).
SERVE_FLEET_SECONDS (6) / SERVE_FLEET_RPS (auto) / SERVE_FLEET_SLOTS (4)
/ SERVE_HEDGE_MS (off) size it.

Streaming previews (`--stream`, SERVE_STREAM=1): the progressive-preview
acceptance instrument. One continuous engine warmed WITH the preview
fill-decode program (`preview_enabled=True`), open-loop Poisson arrivals
each submitted with a live `RequestStream` — the same object an SSE
client hangs on — so every chunk boundary emits progress and every
SERVE_PREVIEW_EVERY (default 1) chunks pays the snapshot + preview
dispatch. The JSON line reports TTFP (time-to-first-preview) p50/p95
alongside TTFT and the headline `ttfp_p95_chunk_periods`: the p95 gap
between first preview and first token in measured chunk periods, which
the streaming PR accepts at <= ~2 (one period to reach a boundary, one
for the preview dispatch riding it). SERVE_STREAM_SECONDS (8) sizes the
window.

Fleet tracing (`--trace_export`, SERVE_TRACE_EXPORT=1): every measured
request is traced client-side (the bench plays the ingress role) and
shipped through a real `TraceExporter` to an in-process
`CollectorServer` — the same export path a serving replica uses — and
each engine's JSON line gains a `critical_path` block: per-stage fleet
p50/p95 and dominant-critical-path stage attribution over the measured
window only (tracers attach after calibration; the collector resets
between engines).
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

METRIC = "serving_rps_top_concurrency"
UNIT = "req/s"


def build_toy(sparse=False):
    """Shared toy model/VAE weights so both engines serve identical work.

    `sparse=True` (the --decode_sparsity policy bench) gives the toy a
    pattern to exploit: alternating full/axial_row layers, the flash
    attention impl (sparse decode rides the flash kernel), and a KV tile
    width small enough relative to the toy's cache (SERVE_SPARSE_BLOCK,
    default 16) that the axial layer's dead tiles actually skip — the
    production default DECODE_SPARSE_BLOCK=128 would be one tile on a
    toy-sized cache and skip nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile

    from dalle_pytorch_tpu.models.dalle import DALLE
    from dalle_pytorch_tpu.models.dvae import DiscreteVAE

    dim = int(os.environ.get("SERVE_DIM", "64"))
    depth = int(os.environ.get("SERVE_DEPTH", "2"))
    fmap = int(os.environ.get("SERVE_FMAP", "4"))
    text_seq = int(os.environ.get("SERVE_TEXT_SEQ", "16"))

    vae = DiscreteVAE(
        image_size=4 * fmap, num_layers=2, num_tokens=64,
        codebook_dim=32, hidden_dim=16,
    )
    vae_params = jax.jit(vae.init)(
        jax.random.PRNGKey(1), jnp.zeros((1, 4 * fmap, 4 * fmap, 3))
    )["params"]

    sparse_kw = {}
    if sparse:
        sparse_kw = dict(
            attn_types=("full", "axial_row"),
            attn_impl="flash",
            decode_sparse_block=int(
                os.environ.get("SERVE_SPARSE_BLOCK", "16")
            ),
        )
    model = DALLE(
        dim=dim, depth=depth, heads=2, dim_head=dim // 2,
        num_image_tokens=64, image_fmap_size=fmap,
        num_text_tokens=256, text_seq_len=text_seq,
        shift_tokens=False, rotary_emb=True, **sparse_kw,
    )
    text = jnp.zeros((1, text_seq), jnp.int32)
    tokens = jnp.zeros((1, fmap * fmap), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), text, tokens)
    return model, params, vae, vae_params, np.zeros(text_seq, np.int32)


def build_engine():
    from dalle_pytorch_tpu.serving.engine import GenerationEngine

    shapes = tuple(
        int(b) for b in os.environ.get("SERVE_BATCH_SHAPES", "1,4,8").split(",")
    )
    model, params, vae, vae_params, text_ids = build_toy()
    engine = GenerationEngine(
        model=model, variables=params, vae=vae, vae_params=vae_params,
        batch_shapes=shapes,
    )
    return engine, text_ids


def run_level(engine, text_ids, concurrency: int, requests_per_client: int,
              delay_ms: float):
    import numpy as np

    from dalle_pytorch_tpu.serving.batcher import MicroBatcher
    from dalle_pytorch_tpu.serving.engine import SampleSpec
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry

    registry = MetricsRegistry()
    batcher = MicroBatcher(
        engine, max_delay_ms=delay_ms,
        max_queue_rows=max(64, 4 * concurrency), registry=registry,
    )
    latencies, errors = [], []
    lock = threading.Lock()

    def client(cid: int):
        for i in range(requests_per_client):
            t0 = time.perf_counter()
            try:
                req = batcher.submit(
                    [SampleSpec(text_ids, seed=cid * 10_000 + i)],
                    timeout_s=120.0,
                )
                req.future.result(timeout=120.0)
            except Exception as e:  # noqa: BLE001 — recorded, not fatal
                with lock:
                    errors.append(repr(e))
                continue
            with lock:
                latencies.append(time.perf_counter() - t0)

    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(concurrency)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    batcher.shutdown(drain=True)

    occ = registry.get("dalle_serving_batch_occupancy_rows")
    lat = sorted(latencies)
    done = len(lat)
    return {
        "concurrency": concurrency,
        "requests": done,
        "errors": len(errors),
        "wall_s": round(wall, 3),
        "rps": round(done / wall, 3) if wall > 0 else None,
        # rows actually flushed through the engine (1 per request today,
        # but counted from the occupancy histogram so multi-image requests
        # stay honest)
        "images_per_s": round(occ.sum / wall, 3) if wall > 0 else None,
        "p50_ms": round(_percentile(lat, 0.5) * 1000, 1) if done else None,
        "p95_ms": round(_percentile(lat, 0.95) * 1000, 1) if done else None,
        "mean_batch_occupancy": round(occ.mean(), 2),
        "batches": int(occ.count),
    }


def _percentile(values, q):
    # canonical nearest-rank impl lives in obs/collector.py (the
    # /critical_path endpoint); deferred import keeps this module's
    # import cheap — by first call the engines imported jax anyway
    from dalle_pytorch_tpu.obs.collector import _percentile as impl

    return impl(values, q)


def _stage_snapshot(registry):
    """(sum, count) per stage label of the batcher's stage family — taken
    before a measured window so the breakdown excludes warmup and the
    saturation-calibration flood."""
    fam = registry.get("dalle_serving_stage_seconds")
    if fam is None:
        return {}
    return {label: (child.sum, child.count) for label, child in fam.items()}


def _stage_breakdown(registry, before):
    """Per-stage deltas since `before` as {stage: {mean_ms, count}} — the
    JSON-line view of where a request's wall time went (queue vs
    prefill/chunk/harvest vs the micro engine's generate)."""
    fam = registry.get("dalle_serving_stage_seconds")
    if fam is None:
        return {}
    out = {}
    for label, child in fam.items():
        s0, c0 = before.get(label, (0.0, 0))
        dc = child.count - c0
        if dc > 0:
            out[label] = {
                "mean_ms": round(1000.0 * (child.sum - s0) / dc, 3),
                "count": int(dc),
            }
    return out


def run_open_loop(batcher, text_ids, arrivals, seeds, timeout_s=120.0,
                  texts=None, tracer=None):
    """Replay a pre-drawn Poisson arrival schedule against one batcher.

    `arrivals` are offsets (seconds) from the run start; both engines see
    the identical schedule and per-request seeds, so "at the same Poisson
    arrival rate" is literal. `texts` optionally carries one prompt per
    arrival (the `--prompt_reuse` schedule); default is `text_ids` for
    every request. Returns sustained req/s (completions over the span from
    first submit to last completion) and TTFT percentiles from
    `GenRequest.first_token_at` (micro-batch: batch completion — its first
    token only exists once the full scan finishes; continuous: the first
    chunk boundary after admission). When the engine reports prefix-cache
    admissions (`GenRequest.prefix_hit`, paged engine only), the stats
    split TTFT by hit vs cold so the cache's win is measured on ONE run,
    not across runs.

    `tracer` (--trace_export) mints one client-side trace per arrival —
    the bench plays the fleet ingress role: its root span parents the
    batcher's queue/prefill/chunk/harvest spans, and finish() at
    completion ships the trace to the in-process collector, so the JSON
    line's `critical_path` block covers exactly the measured window.
    """
    from dalle_pytorch_tpu.obs.tracing import NULL_TRACE
    from dalle_pytorch_tpu.serving.engine import SampleSpec

    submitted, rejected = [], 0
    t_start = time.monotonic()
    for i, (offset, seed) in enumerate(zip(arrivals, seeds)):
        delay = t_start + offset - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        ids = text_ids if texts is None else texts[i]
        trace = (
            tracer.start_trace("request", arrival=i) if tracer is not None
            else NULL_TRACE
        )
        try:
            req = batcher.submit(
                [SampleSpec(ids, seed=int(seed))], timeout_s=timeout_s,
                trace=trace,
            )
            submitted.append((time.monotonic(), req))
        except Exception:  # queue-full backpressure counts against the engine
            trace.finish("rejected")
            rejected += 1

    ttfts, errors = [], 0
    hit_ttfts, cold_ttfts, hit_known = [], [], 0
    last_done = time.monotonic()
    for t_submit, req in submitted:
        try:
            req.future.result(timeout=timeout_s)
        except Exception:
            req.trace.finish("error")
            errors += 1
            continue
        req.trace.finish("ok")
        last_done = max(last_done, time.monotonic())
        if req.first_token_at is not None:
            ttft = req.first_token_at - t_submit
            ttfts.append(ttft)
            if req.prefix_hit is not None:
                hit_known += 1
                (hit_ttfts if req.prefix_hit else cold_ttfts).append(ttft)
    # sustained rate over submit-to-last-completion: the queue backlog an
    # engine builds up during the arrival window is paid for, not free
    wall = last_done - t_start
    completed = len(submitted) - errors
    span = max(wall, 1e-9)
    out = {
        "offered": len(arrivals),
        "submitted": len(submitted),
        "rejected": rejected,
        "completed": completed,
        "errors": errors,
        "wall_s": round(wall, 3),
        "rps": round(completed / span, 3),
        "ttft_p50_ms": round(1000 * _percentile(ttfts, 0.5), 1) if ttfts else None,
        "ttft_p95_ms": round(1000 * _percentile(ttfts, 0.95), 1) if ttfts else None,
        "ttft_mean_ms": round(1000 * sum(ttfts) / len(ttfts), 1) if ttfts else None,
    }
    if hit_known:
        out["prefix_hit_rate"] = round(len(hit_ttfts) / hit_known, 3)
        if hit_ttfts:
            out["ttft_prefix_hit_p50_ms"] = round(
                1000 * _percentile(hit_ttfts, 0.5), 1
            )
            out["ttft_prefix_hit_mean_ms"] = round(
                1000 * sum(hit_ttfts) / len(hit_ttfts), 1
            )
        if cold_ttfts:
            out["ttft_cold_p50_ms"] = round(
                1000 * _percentile(cold_ttfts, 0.5), 1
            )
            out["ttft_cold_mean_ms"] = round(
                1000 * sum(cold_ttfts) / len(cold_ttfts), 1
            )
    return out


def draw_prompt_schedule(rng, n, text_seq, num_text_tokens, prompt_reuse,
                         pool_size=8):
    """One prompt per arrival: with probability `prompt_reuse`, a draw from
    a small popularity pool (Zipf-ish 1/rank weights — a few prompts take
    most of the repeat traffic, like prompt templates / n-samples fan-out
    in production mixes); otherwise a fresh unique prompt. 0 makes every
    prompt unique — deliberately cache-cold (the pre-paging bench repeated
    ONE prompt for every arrival, which would be a 100% prefix-hit
    workload)."""
    import numpy as np

    weights = 1.0 / np.arange(1, pool_size + 1)
    weights /= weights.sum()
    popular = [
        rng.integers(1, num_text_tokens, size=text_seq).astype(np.int32)
        for _ in range(pool_size)
    ]
    return [
        popular[rng.choice(pool_size, p=weights)]
        if prompt_reuse > 0 and rng.random() < prompt_reuse
        else rng.integers(1, num_text_tokens, size=text_seq).astype(np.int32)
        for _ in range(n)
    ]


def _sustained_rps(batcher, text_ids, seconds=2.5, clients=16,
                   make_text=None):
    """Closed-loop flood: measured saturation throughput of one batcher.

    More robust than timing a single scan — on a shared/noisy host a
    one-shot measurement can be off by 3x, and an open-loop rate derived
    from it lands past saturation, where the bench measures queue buildup
    instead of admission policy.

    `make_text(cid, i)` supplies a DISTINCT prompt per submission so a
    prefix-caching engine calibrates on the COLD admission path — one
    repeated prompt would measure the ~100% hit path and inflate the cap
    the open-loop rate derives from; None floods `text_ids`.
    """
    import threading as _th

    from dalle_pytorch_tpu.serving.engine import SampleSpec

    done = []
    stop = time.monotonic() + seconds
    lock = _th.Lock()

    def client(cid):
        i = 0
        while time.monotonic() < stop:
            ids = text_ids if make_text is None else make_text(cid, i)
            try:
                req = batcher.submit(
                    [SampleSpec(ids, seed=1_000_000 + cid * 10_000 + i)],
                    timeout_s=60.0,
                )
                req.future.result(timeout=60.0)
                with lock:
                    done.append(1)
            except Exception:
                time.sleep(0.01)  # backpressure: retry
            i += 1

    threads = [
        _th.Thread(target=client, args=(c,)) for c in range(clients)
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return len(done) / max(time.monotonic() - t0, 1e-9)


def _kv_quality_block(model, micro, cont, n=4, label="quantized"):
    """CLIP-score parity of a degraded decode path, reported BESIDE the
    speed numbers: the same (prompt, seed) rows generate through the
    bf16 micro engine (the reference — a bf16 continuous engine is
    bit-identical to it by the composition-invariance contract) and the
    continuous engine under test, and one toy CLIP (fixed init) scores
    both image sets against their prompts. `clip_delta_mean` is
    `label` minus reference — ~0 means the variant paid no quality for
    its win (int8: ~2x capacity; policy sparsity: skipped KV tiles).
    Runs AFTER the measured window on already-warm programs; the
    token-agreement fraction is reported too (both variants are
    different numerical paths, so tokens MAY diverge — the CLIP delta
    is the acceptance metric, not token identity)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_pytorch_tpu.models.clip import CLIP, clip_scores
    from dalle_pytorch_tpu.serving.engine import SampleSpec

    n = max(1, min(n, cont.max_batch))
    rng = np.random.default_rng(1234)
    texts = rng.integers(
        1, model.num_text_tokens, size=(n, model.text_seq_len)
    ).astype(np.int32)
    specs = [SampleSpec(texts[i], seed=9000 + i) for i in range(n)]

    ref_toks, ref_px = micro.generate(specs)
    for i, sp in enumerate(specs):
        cont.prefill_slot(i, sp)
    for _ in range(4 * model.image_seq_len):
        pos, act = cont.step_chunk()
        if (pos[act] >= cont.image_seq_len).all():
            break
    q_toks = np.asarray(cont.harvest(list(range(n))))
    cont.release(list(range(n)))
    q_px = cont.decode_pixels(q_toks)

    image_size = int(np.asarray(ref_px).shape[1])
    clip = CLIP(
        dim_text=32, dim_image=32, dim_latent=16,
        num_text_tokens=model.num_text_tokens,
        text_enc_depth=1, text_seq_len=model.text_seq_len, text_heads=2,
        visual_enc_depth=1, visual_heads=2,
        visual_image_size=image_size,
        visual_patch_size=max(1, image_size // 4),
    )
    cv = clip.init(
        jax.random.PRNGKey(7), jnp.asarray(texts), jnp.asarray(ref_px)
    )
    ref_s = np.asarray(
        clip_scores(clip, cv, jnp.asarray(texts), jnp.asarray(ref_px))
    )
    q_s = np.asarray(
        clip_scores(clip, cv, jnp.asarray(texts), jnp.asarray(q_px))
    )
    return {
        "rows": int(n),
        "token_agreement": round(
            float((np.asarray(ref_toks)[:n] == q_toks[:n]).mean()), 4
        ),
        "clip_mean_ref": round(float(ref_s.mean()), 5),
        f"clip_mean_{label}": round(float(q_s.mean()), 5),
        "clip_delta_mean": round(float((q_s - ref_s).mean()), 5),
    }


def main_open_loop(prompt_reuse=0.0, kv_layout="slot", mesh=None,
                   trace_export=False, kv_dtype="model",
                   decode_sparsity="causal"):
    import jax
    import numpy as np

    from dalle_pytorch_tpu.serving.batcher import ContinuousBatcher, MicroBatcher
    from dalle_pytorch_tpu.serving.engine import (
        ContinuousEngine, GenerationEngine, PagedContinuousEngine, SampleSpec,
    )
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry

    kv_dt = None if kv_dtype in (None, "model") else str(kv_dtype)
    sparse = decode_sparsity not in (None, "causal")

    # open-loop defaults use a LARGER toy than the closed-loop sweep
    # (dim 128 / depth 3 / 8x8 grid = 64 image tokens): on the tiny model
    # host dispatch overhead dominates decode compute, which is the
    # opposite of the regime continuous batching targets (a real
    # accelerator is decode-bound) and makes the comparison measure Python
    # loop costs instead of admission policy. Still overridable via env.
    os.environ.setdefault("SERVE_DIM", "128")
    os.environ.setdefault("SERVE_DEPTH", "3")
    os.environ.setdefault("SERVE_FMAP", "8")
    shapes = tuple(
        int(b) for b in os.environ.get("SERVE_BATCH_SHAPES", "1,4,8").split(",")
    )
    delay_ms = float(os.environ.get("SERVE_DELAY_MS", "25"))
    chunk_tokens = int(os.environ.get("SERVE_CHUNK_TOKENS", "8"))
    duration_s = float(os.environ.get("SERVE_OPEN_SECONDS", "10"))
    max_batch = max(shapes)

    model, params, vae, vae_params, text_ids = build_toy(sparse=sparse)

    micro = GenerationEngine(
        model=model, variables=params, vae=vae, vae_params=vae_params,
        batch_shapes=shapes, registry=MetricsRegistry(),
    )
    micro.warmup()
    mb = MicroBatcher(
        micro, max_delay_ms=delay_ms,
        max_queue_rows=max(64, 4 * max_batch), registry=micro.registry,
    )

    prefill_batch = int(os.environ.get("SERVE_PREFILL_BATCH", "4"))
    page_size = int(os.environ.get("SERVE_PAGE_SIZE", "16"))
    cont_kw = dict(
        model=model, variables=params, vae=vae, vae_params=vae_params,
        max_batch=max_batch, chunk_tokens=chunk_tokens,
        prefill_batch=prefill_batch, registry=MetricsRegistry(),
        kv_dtype=kv_dt,
        decode_sparsity="policy" if sparse else "causal",
    )
    if kv_layout == "paged":
        kv_pages_env = os.environ.get("SERVE_KV_PAGES")
        cont_kw.update(
            page_size=page_size,
            kv_pages=int(kv_pages_env) if kv_pages_env else None,
        )
        if mesh is not None:
            from dalle_pytorch_tpu.serving.sharded import (
                ShardedPagedContinuousEngine,
            )

            cont = ShardedPagedContinuousEngine(mesh_shape=mesh, **cont_kw)
        else:
            cont = PagedContinuousEngine(**cont_kw)
    elif mesh is not None:
        from dalle_pytorch_tpu.serving.sharded import ShardedContinuousEngine

        cont = ShardedContinuousEngine(mesh_shape=mesh, **cont_kw)
    else:
        cont = ContinuousEngine(**cont_kw)
    # per-program cost capture (obs/vitals.py) before warmup so the
    # continuous line can report live MFU over the measured window
    from dalle_pytorch_tpu.obs import EngineVitals, ProgramCostTable

    cont.cost_table = ProgramCostTable(registry=cont.registry)
    cont.warmup()
    cb = ContinuousBatcher(
        cont, max_queue_rows=max(64, 4 * max_batch), registry=cont.registry,
    )

    # offered load: ~40% of the SLOWER engine's measured saturation
    # throughput — loaded enough that the micro engine must coalesce
    # several rows per flush (arrivals genuinely wait behind in-flight
    # scans), with enough margin that neither engine crosses into
    # saturation even if the host slows down between calibration and run
    # (past saturation the bench measures queue buildup, not admission
    # policy). Override with SERVE_RATE_RPS to sweep the load axis.
    def _unique_text(cid, i):
        # distinct per submission: both caps measure COLD admissions, so
        # they stay comparable across --kv_layout runs (a repeated prompt
        # would calibrate the paged engine on its ~100% prefix-hit path)
        r = np.random.default_rng([cid, i])
        return r.integers(
            1, model.num_text_tokens, size=model.text_seq_len
        ).astype(np.int32)

    micro_cap = _sustained_rps(mb, text_ids, make_text=_unique_text)
    cont_cap = _sustained_rps(cb, text_ids, make_text=_unique_text)
    rate = float(
        os.environ.get("SERVE_RATE_RPS", 0.4 * min(micro_cap, cont_cap))
    )

    rng = np.random.default_rng(int(os.environ.get("SERVE_ARRIVAL_SEED", "0")))
    gaps = rng.exponential(1.0 / rate, size=int(rate * duration_s) + 1)
    arrivals = np.cumsum(gaps)
    arrivals = arrivals[arrivals < duration_s]
    seeds = rng.integers(0, 2**31 - 1, size=len(arrivals))
    # one prompt per arrival, IDENTICAL for both engines — with
    # --prompt_reuse > 0 repeat prompts hit the paged engine's prefix cache
    # while the micro/slotted path pays a full prefill either way, so the
    # hit-vs-cold TTFT split isolates the cache's win on one schedule
    texts = draw_prompt_schedule(
        rng, len(arrivals), model.text_seq_len, model.num_text_tokens,
        prompt_reuse,
    )

    common = {
        "metric": "serving_openloop_rps",
        "unit": UNIT,
        "device": jax.devices()[0].platform,
        "mode": "open-loop",
        "rate_rps": round(rate, 3),
        "duration_s": duration_s,
        "batch_shapes": list(shapes),
        "prompt_reuse": prompt_reuse,
        "micro_saturation_rps": round(micro_cap, 3),
        "continuous_saturation_rps": round(cont_cap, 3),
    }

    # --trace_export: an in-process collector (real HTTP on port 0) plus
    # one tracer+exporter per engine run — the bench exercises the SAME
    # export path a fleet replica uses, and each line's `critical_path`
    # block folds exactly the traces of its measured window (tracers are
    # created after calibration; the collector resets between engines)
    collector_srv = None
    if trace_export:
        from dalle_pytorch_tpu.obs import CollectorServer, TraceExporter, Tracer

        collector_srv = CollectorServer(grace_s=0.05).start()

    def _traced_run(batcher, site, **kw):
        """One open-loop replay, optionally traced+exported; returns
        (stats, critical_path block or None)."""
        if collector_srv is None:
            return run_open_loop(batcher, text_ids, arrivals, seeds, **kw), None
        tracer = Tracer(max_traces=len(arrivals) + 8)
        exporter = TraceExporter(collector_srv.url, site=site).attach(tracer)
        stats = run_open_loop(
            batcher, text_ids, arrivals, seeds, tracer=tracer, **kw
        )
        exporter.flush()
        exporter.stop(final_flush=False)
        block = collector_srv.collector.critical_path()
        collector_srv.collector.reset()
        return stats, block

    micro_stages0 = _stage_snapshot(micro.registry)
    micro_stats, micro_cp = _traced_run(mb, "bench-micro", texts=texts)
    mb.shutdown(drain=True)
    micro_line = {
        **common, "engine": "micro", "value": micro_stats["rps"],
        "max_delay_ms": delay_ms, **micro_stats,
        "stages": _stage_breakdown(micro.registry, micro_stages0),
    }
    if micro_cp is not None:
        micro_line["critical_path"] = micro_cp
    print(json.dumps(micro_line), flush=True)

    # admission-dispatch accounting: how well batched prefill amortized the
    # per-row admission cost over the MEASURED window (warmup is excluded by
    # the engine's counter tagging; the saturation-calibration flood is
    # excluded by snapshotting here). rows/dispatch == prefill_batch means
    # every wave ran full; 1.0 means arrivals were too sparse to coalesce.
    pf_rows0 = cont.registry.get("dalle_serving_prefills_total").value
    pf_disp0 = cont.registry.get(
        "dalle_serving_prefill_dispatches_total"
    ).value
    # sparsity tile accounting, windowed like the prefill counters
    tiles_read0 = cont.registry.get(
        "dalle_serving_kv_tiles_read_total"
    ).value
    tiles_skip0 = cont.registry.get(
        "dalle_serving_kv_tiles_skipped_total"
    ).value
    cont_stages0 = _stage_snapshot(cont.registry)
    # vitals sampled over the MEASURED window only: the ring starts empty
    # here (after calibration), stops before the JSON line renders
    vitals = EngineVitals(interval_s=0.05, max_samples=4096)
    vitals.bind(engine=cont, batcher=cb)
    vitals.start()
    if kv_layout == "paged":
        # measured-window occupancy: the saturation-calibration flood above
        # already pushed the pool to ITS peak, so restart the watermark (and
        # hit/miss tallies) at the live level before the schedule replays
        cont.kv.pool.peak_allocated = cont.kv.pool.n_allocated
        hits0, misses0 = cont.kv.cache.hits, cont.kv.cache.misses
        evictions0 = cont.kv.cache.evictions
    cont_stats, cont_cp = _traced_run(cb, "bench-continuous", texts=texts)
    vitals.stop()
    cb.shutdown(drain=True)
    # mean/peak occupancy + per-program MFU over the measured window
    vitals_block = vitals.window_summary()
    mfu = {
        row["program"]: row["mfu"]
        for row in cont.cost_table.rows()
        if row.get("mfu") is not None
    }
    if mfu:
        vitals_block["mfu"] = mfu
    pf_rows = (
        cont.registry.get("dalle_serving_prefills_total").value - pf_rows0
    )
    pf_disp = (
        cont.registry.get("dalle_serving_prefill_dispatches_total").value
        - pf_disp0
    )
    cont_line = {
        **common, "engine": "continuous", "value": cont_stats["rps"],
        "kv_layout": kv_layout,
        "kv_dtype": kv_dt or "model",
        "kv_bytes_per_slot": int(cont.kv_bytes_per_slot()),
        "chunk_tokens": chunk_tokens,
        "prefill_batch": cont.prefill_batch,
        "prefill_rows": int(pf_rows),
        "prefill_dispatches": int(pf_disp),
        "prefill_rows_per_dispatch": (
            round(pf_rows / pf_disp, 2) if pf_disp else None
        ),
        **cont_stats,
        "stages": _stage_breakdown(cont.registry, cont_stages0),
        "vitals": vitals_block,
    }
    if cont_cp is not None:
        cont_line["critical_path"] = cont_cp
    if mesh is not None:
        # mesh shape + per-device memory PEAK over the measured window
        # (from the sampler's per-device memory_stats; empty on backends
        # without memory stats — the live state-buffer split from
        # mesh_detail still names each shard's share)
        peaks = {}
        for snap in vitals.recent():
            for dev, stats in (
                snap.get("memory_stats_per_device") or {}
            ).items():
                peaks[dev] = max(
                    peaks.get(dev, 0), stats.get("bytes_in_use", 0)
                )
        cont_line["mesh"] = {
            **cont.mesh_detail(),
            "per_device_peak_bytes": peaks,
        }
    if kv_layout == "paged":
        # HBM story: pages the measured window ACTUALLY peaked at vs the
        # slotted layout's always-resident worst case (max_batch full-length
        # lanes). peak_fraction_of_slotted < 1.0 is the paged win — cache
        # positions the slotted engine pins but this run never touched.
        slotted_pages = cont.max_batch * cont.kv.pages_per_row
        cache = cont.kv.cache
        cont_line["block_occupancy"] = {
            "page_size": cont.page_size,
            "pages_total": cont.kv.pool.n_pages - 1,
            "pages_peak": int(cont.kv.pool.peak_allocated),
            "pages_slotted_equiv": slotted_pages,
            "peak_fraction_of_slotted": round(
                cont.kv.pool.peak_allocated / slotted_pages, 3
            ),
        }
        window_hits = cache.hits - hits0
        window_misses = cache.misses - misses0
        admitted = window_hits + window_misses
        cont_line["prefix_cache"] = {
            "entries": len(cache),
            "hits": int(window_hits),
            "misses": int(window_misses),
            "hit_rate": round(window_hits / admitted, 3) if admitted else None,
            # windowed like hits/misses: the saturation-calibration flood
            # can evict against a capped pool before the schedule replays
            "evictions": int(cache.evictions - evictions0),
        }
    if sparse:
        # per-line tile accounting over the measured window: skipped > 0
        # is the policy actually buying DMA/compute (vs length skip
        # alone), read gives the denominator for the skip fraction
        tiles_read = int(
            cont.registry.get("dalle_serving_kv_tiles_read_total").value
            - tiles_read0
        )
        tiles_skip = int(
            cont.registry.get("dalle_serving_kv_tiles_skipped_total").value
            - tiles_skip0
        )
        cont_line["decode_sparsity"] = "policy"
        cont_line["kv_tiles_read"] = tiles_read
        cont_line["kv_tiles_skipped"] = tiles_skip
        total = tiles_read + tiles_skip
        cont_line["kv_tile_skip_fraction"] = (
            round(tiles_skip / total, 4) if total else None
        )
        sp_detail = cont.sparsity_detail() or {}
        cont_line["sparsity"] = {
            k: sp_detail[k]
            for k in (
                "block", "n_blocks", "patterned_layers",
                "static_dead_tile_frac",
            )
            if k in sp_detail
        }
    if kv_dt is not None or sparse:
        # quality beside speed: the degraded decode path's CLIP-score
        # cost on the SAME (prompt, seed) rows, scored against the bf16
        # micro engine's output (bit-identical to a bf16 continuous
        # engine by the composition-invariance contract; the micro
        # engine decodes patterned layers through the dense masked path,
        # so for sparse runs it doubles as the exact-mask oracle)
        cont_line["quality"] = _kv_quality_block(
            model, micro, cont,
            label="sparse" if kv_dt is None else "quantized",
        )
    if micro_stats["rps"]:
        cont_line["rps_ratio_vs_micro"] = round(
            cont_stats["rps"] / micro_stats["rps"], 3
        )
    if micro_stats["ttft_p95_ms"] and cont_stats["ttft_p95_ms"]:
        cont_line["ttft_p95_ratio_vs_micro"] = round(
            cont_stats["ttft_p95_ms"] / micro_stats["ttft_p95_ms"], 3
        )
    print(json.dumps(cont_line), flush=True)
    if collector_srv is not None:
        collector_srv.shutdown()


def run_stream_open_loop(batcher, arrivals, seeds, texts, timeout_s=120.0):
    """Replay a Poisson schedule with a live event stream per request.

    Every submit carries a `RequestStream` (the same object the SSE
    handler hangs a client on), so the batcher's chunk-boundary callback
    emits progress events and — every `preview_every` chunks — pays the
    snapshot + preview fill-decode dispatch. TTFP (time-to-first-preview)
    is stamped the moment `preview()` lands the event in the ring: that
    is when an attached SSE reader would wake, so it times exactly what a
    streaming client sees minus PNG encoding (the server's cost, not the
    engine's). Returns TTFT percentiles like `run_open_loop` plus
    ttfp_p50/p95/mean and per-stream event accounting.
    """
    from dalle_pytorch_tpu.serving.engine import SampleSpec
    from dalle_pytorch_tpu.serving.streaming import RequestStream

    class _TimedStream(RequestStream):
        # bench-side stamps: the batcher worker calls progress()/preview()
        # at chunk boundaries, so monotonic-on-emit is reader-visible time
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.first_progress_at = None
            self.first_preview_at = None

        def progress(self, chunk, **data):
            ok = super().progress(chunk, **data)
            if ok and self.first_progress_at is None:
                self.first_progress_at = time.monotonic()
            return ok

        def preview(self, chunk, **data):
            ok = super().preview(chunk, **data)
            if ok and self.first_preview_at is None:
                self.first_preview_at = time.monotonic()
            return ok

    submitted, rejected = [], 0
    t_start = time.monotonic()
    for i, (offset, seed) in enumerate(zip(arrivals, seeds)):
        delay = t_start + offset - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        stream = _TimedStream(key=f"bench-stream-{i}")
        try:
            req = batcher.submit(
                [SampleSpec(texts[i], seed=int(seed))], timeout_s=timeout_s,
                stream=stream,
            )
            submitted.append((time.monotonic(), req, stream))
        except Exception:  # queue-full backpressure counts against the engine
            rejected += 1

    ttfts, ttfps, errors = [], [], 0
    previews_total = progress_total = 0
    last_done = time.monotonic()
    for t_submit, req, stream in submitted:
        try:
            req.future.result(timeout=timeout_s)
        except Exception:
            errors += 1
            continue
        last_done = max(last_done, time.monotonic())
        if req.first_token_at is not None:
            ttfts.append(req.first_token_at - t_submit)
        if stream.first_preview_at is not None:
            ttfps.append(stream.first_preview_at - t_submit)
        previews_total += stream.previews_sent
        progress_total += stream.events_emitted - stream.previews_sent
    wall = last_done - t_start
    completed = len(submitted) - errors
    span = max(wall, 1e-9)
    return {
        "offered": len(arrivals),
        "submitted": len(submitted),
        "rejected": rejected,
        "completed": completed,
        "errors": errors,
        "wall_s": round(wall, 3),
        "rps": round(completed / span, 3),
        "ttft_p50_ms": round(1000 * _percentile(ttfts, 0.5), 1) if ttfts else None,
        "ttft_p95_ms": round(1000 * _percentile(ttfts, 0.95), 1) if ttfts else None,
        "ttfp_p50_ms": round(1000 * _percentile(ttfps, 0.5), 1) if ttfps else None,
        "ttfp_p95_ms": round(1000 * _percentile(ttfps, 0.95), 1) if ttfps else None,
        "ttfp_mean_ms": round(1000 * sum(ttfps) / len(ttfps), 1) if ttfps else None,
        "streams_with_preview": len(ttfps),
        "previews_total": int(previews_total),
        "progress_events_total": int(progress_total),
    }


def main_stream_bench(kv_layout="slot"):
    """`--stream`: the streaming-previews acceptance instrument.

    One continuous engine with the preview fill-decode program warmed
    (`preview_enabled=True`), one open-loop Poisson replay where every
    request carries a live event stream. The headline is p95 TTFP in
    chunk periods (`ttfp_p95_chunk_periods`): a preview is one snapshot +
    one extra compiled dispatch at a chunk boundary, so time-to-first-
    pixels should sit within ~2 chunk periods of admission — the
    acceptance bound — while full-image TTFT is the whole decode away.
    SERVE_PREVIEW_EVERY (default 1) sets the preview cadence;
    SERVE_STREAM_SECONDS (default 8) the window.
    """
    import jax
    import numpy as np

    from dalle_pytorch_tpu.serving.batcher import ContinuousBatcher
    from dalle_pytorch_tpu.serving.engine import (
        ContinuousEngine, PagedContinuousEngine,
    )
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry

    os.environ.setdefault("SERVE_DIM", "128")
    os.environ.setdefault("SERVE_DEPTH", "3")
    os.environ.setdefault("SERVE_FMAP", "8")
    shapes = tuple(
        int(b) for b in os.environ.get("SERVE_BATCH_SHAPES", "1,4,8").split(",")
    )
    chunk_tokens = int(os.environ.get("SERVE_CHUNK_TOKENS", "8"))
    duration_s = float(os.environ.get("SERVE_STREAM_SECONDS", "8"))
    preview_every = int(os.environ.get("SERVE_PREVIEW_EVERY", "1"))
    prefill_batch = int(os.environ.get("SERVE_PREFILL_BATCH", "4"))
    max_batch = max(shapes)

    model, params, vae, vae_params, text_ids = build_toy()
    engine_kw = dict(
        model=model, variables=params, vae=vae, vae_params=vae_params,
        max_batch=max_batch, chunk_tokens=chunk_tokens,
        prefill_batch=prefill_batch, registry=MetricsRegistry(),
        preview_enabled=True,
    )
    if kv_layout == "paged":
        cont = PagedContinuousEngine(
            page_size=int(os.environ.get("SERVE_PAGE_SIZE", "16")),
            **engine_kw,
        )
    else:
        cont = ContinuousEngine(**engine_kw)
    from dalle_pytorch_tpu.obs import ProgramCostTable

    cont.cost_table = ProgramCostTable(registry=cont.registry)
    cont.warmup()
    cb = ContinuousBatcher(
        cont, max_queue_rows=max(64, 4 * max_batch), registry=cont.registry,
        preview_every=preview_every,
    )

    def _unique_text(cid, i):
        r = np.random.default_rng([cid, i])
        return r.integers(
            1, model.num_text_tokens, size=model.text_seq_len
        ).astype(np.int32)

    cap = _sustained_rps(cb, text_ids, make_text=_unique_text)
    rate = float(os.environ.get("SERVE_RATE_RPS", 0.4 * cap))
    rng = np.random.default_rng(int(os.environ.get("SERVE_ARRIVAL_SEED", "0")))
    gaps = rng.exponential(1.0 / rate, size=int(rate * duration_s) + 1)
    arrivals = np.cumsum(gaps)
    arrivals = arrivals[arrivals < duration_s]
    seeds = rng.integers(0, 2**31 - 1, size=len(arrivals))
    texts = draw_prompt_schedule(
        rng, len(arrivals), model.text_seq_len, model.num_text_tokens, 0.0,
    )

    stages0 = _stage_snapshot(cont.registry)
    stats = run_stream_open_loop(cb, arrivals, seeds, texts)
    cb.shutdown(drain=True)
    stages = _stage_breakdown(cont.registry, stages0)
    line = {
        "metric": "serving_stream_ttfp",
        "unit": "ms",
        "device": jax.devices()[0].platform,
        "mode": "stream",
        "engine": "continuous",
        "kv_layout": kv_layout,
        "value": stats["ttfp_p95_ms"],
        "rate_rps": round(rate, 3),
        "duration_s": duration_s,
        "chunk_tokens": chunk_tokens,
        "preview_every": preview_every,
        "continuous_saturation_rps": round(cap, 3),
        **stats,
        "stream_events": _class_counter_values(
            cont.registry, "dalle_serving_stream_events_total"
        ),
        "stages": stages,
    }
    # the acceptance bound: first preview within ~2 chunk periods of the
    # request's first decode work (one period to REACH a boundary, one for
    # the snapshot + preview dispatch riding it); the chunk period is
    # measured from this window's own stage breakdown
    chunk_ms = (stages.get("chunk") or {}).get("mean_ms")
    if chunk_ms and stats["ttfp_p95_ms"] and stats["ttft_p95_ms"]:
        line["chunk_period_ms"] = chunk_ms
        # queueing + prefill delay is TTFT-side, common to both numbers;
        # the preview machinery's own cost is the gap between first
        # preview and first token, which is what the bound polices
        ttfp_over_ttft_ms = stats["ttfp_p95_ms"] - stats["ttft_p95_ms"]
        line["ttfp_p95_minus_ttft_p95_ms"] = round(ttfp_over_ttft_ms, 1)
        line["ttfp_p95_chunk_periods"] = round(
            max(ttfp_over_ttft_ms, 0.0) / chunk_ms, 2
        )
    print(json.dumps(line), flush=True)


def _class_counter_values(registry, name):
    """{label: value} of a counter family (empty when never registered)."""
    fam = registry.get(name)
    if fam is None:
        return {}
    return {label: int(child.value) for label, child in fam.items()}


def _ttft_stats(ttfts):
    if not ttfts:
        return {"ttft_p50_ms": None, "ttft_p95_ms": None}
    return {
        "ttft_p50_ms": round(1000 * _percentile(ttfts, 0.5), 1),
        "ttft_p95_ms": round(1000 * _percentile(ttfts, 0.95), 1),
    }


def run_priority_open_loop(batcher, arrivals, seeds, texts, priorities,
                           timeout_s):
    """Replay a Poisson schedule with per-arrival priority classes.

    Returns {class: stats} with offered/shed/rejected/completed counts
    and TTFT percentiles per class. Sheds (`ShedError`) and queue-full
    rejects are counted separately: under deliberate overload both are
    CORRECT behavior for the low class, and the bench line must show
    which mechanism absorbed the excess."""
    from dalle_pytorch_tpu.serving.engine import SampleSpec
    from dalle_pytorch_tpu.serving.qos import ShedError, TenantQuotaError

    per_class = {
        c: {"offered": 0, "shed": 0, "rejected": 0, "errors": 0,
            "completed": 0, "ttfts": []}
        for c in set(priorities)
    }
    submitted = []
    t_start = time.monotonic()
    for i, (offset, seed) in enumerate(zip(arrivals, seeds)):
        delay = t_start + offset - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        cls = priorities[i]
        stats = per_class[cls]
        stats["offered"] += 1
        try:
            req = batcher.submit(
                [SampleSpec(texts[i], seed=int(seed))],
                timeout_s=timeout_s, priority=cls,
            )
            submitted.append((time.monotonic(), cls, req))
        except (ShedError, TenantQuotaError):
            stats["shed"] += 1
        except Exception:
            stats["rejected"] += 1
    for t_submit, cls, req in submitted:
        stats = per_class[cls]
        try:
            req.future.result(timeout=timeout_s + 30.0)
        except Exception:
            stats["errors"] += 1
            continue
        stats["completed"] += 1
        if req.first_token_at is not None:
            stats["ttfts"].append(req.first_token_at - t_submit)
    out = {}
    for cls, stats in per_class.items():
        ttfts = stats.pop("ttfts")
        out[cls] = {**stats, **_ttft_stats(ttfts)}
    return out


def main_priority_mix(mix, kv_layout="slot", prompt_reuse=0.0):
    """`--priority_mix`: QoS under deliberate overload, one JSON line."""
    import jax
    import numpy as np

    from dalle_pytorch_tpu.serving.batcher import ContinuousBatcher
    from dalle_pytorch_tpu.serving.engine import (
        ContinuousEngine, PagedContinuousEngine, SampleSpec,
    )
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry

    assert 0.0 < mix < 1.0, "--priority_mix is the HIGH-class fraction"
    os.environ.setdefault("SERVE_DIM", "128")
    os.environ.setdefault("SERVE_DEPTH", "3")
    os.environ.setdefault("SERVE_FMAP", "8")
    shapes = tuple(
        int(b) for b in os.environ.get("SERVE_BATCH_SHAPES", "1,4,8").split(",")
    )
    max_batch = max(shapes)
    chunk_tokens = int(os.environ.get("SERVE_CHUNK_TOKENS", "8"))
    duration_s = float(os.environ.get("SERVE_OPEN_SECONDS", "10"))
    overload = float(os.environ.get("SERVE_PRIORITY_OVERLOAD", "1.3"))
    timeout_s = float(os.environ.get("SERVE_PRIORITY_TIMEOUT", "30"))

    model, params, vae, vae_params, text_ids = build_toy()
    prefill_batch = int(os.environ.get("SERVE_PREFILL_BATCH", "4"))
    if kv_layout == "paged":
        kv_pages_env = os.environ.get("SERVE_KV_PAGES")
        cont = PagedContinuousEngine(
            model=model, variables=params, vae=vae, vae_params=vae_params,
            max_batch=max_batch, chunk_tokens=chunk_tokens,
            prefill_batch=prefill_batch, registry=MetricsRegistry(),
            page_size=int(os.environ.get("SERVE_PAGE_SIZE", "16")),
            kv_pages=int(kv_pages_env) if kv_pages_env else None,
        )
    else:
        cont = ContinuousEngine(
            model=model, variables=params, vae=vae, vae_params=vae_params,
            max_batch=max_batch, chunk_tokens=chunk_tokens,
            prefill_batch=prefill_batch, registry=MetricsRegistry(),
        )
    cont.warmup()
    # one slot held for the high class (SERVE_PRIORITY_RESERVE): a high
    # arrival then admits at the next chunk boundary without waiting for
    # a preemption cycle — the config the QoS acceptance ratio is stated
    # for (preemption alone still bounds the tail, just one boundary
    # later; set 0 to measure the fully work-conserving policy)
    reserve = int(os.environ.get("SERVE_PRIORITY_RESERVE", "1"))
    cb = ContinuousBatcher(
        cont, max_queue_rows=max(64, 4 * max_batch), registry=cont.registry,
        preempt=True, deadline_shed=True,
        reserve_slots=min(reserve, max_batch - 1),
    )

    cap = _sustained_rps(
        cb, text_ids,
        make_text=lambda cid, i: np.random.default_rng([cid, i]).integers(
            1, model.num_text_tokens, size=model.text_seq_len
        ).astype(np.int32),
    )
    rate = float(os.environ.get("SERVE_RATE_RPS", 0) or overload * cap)

    rng = np.random.default_rng(int(os.environ.get("SERVE_ARRIVAL_SEED", "0")))

    # unloaded high-priority baseline: the SAME Poisson arrival process
    # at a light rate (default 15% of saturation), all high class — the
    # denominator of the acceptance ratio. Open-loop, not sequential-idle
    # probing: an idle probe always catches the worker parked and
    # measures the best case, while every real arrival pays the
    # mid-chunk admission wait — the ratio must compare like with like.
    base_frac = float(os.environ.get("SERVE_PRIORITY_BASELINE_FRACTION",
                                     "0.15"))
    base_rate = max(base_frac * cap, 1.0)
    base_dur = min(duration_s, 5.0)
    base_gaps = rng.exponential(1.0 / base_rate,
                                size=int(base_rate * base_dur) + 1)
    base_arrivals = np.cumsum(base_gaps)
    base_arrivals = base_arrivals[base_arrivals < base_dur]
    base_seeds = rng.integers(0, 2**31 - 1, size=len(base_arrivals))
    base_texts = draw_prompt_schedule(
        rng, len(base_arrivals), model.text_seq_len, model.num_text_tokens,
        prompt_reuse,
    )
    unloaded = run_priority_open_loop(
        cb, base_arrivals, base_seeds, base_texts,
        ["high"] * len(base_arrivals), timeout_s,
    )["high"]
    gaps = rng.exponential(1.0 / rate, size=int(rate * duration_s) + 1)
    arrivals = np.cumsum(gaps)
    arrivals = arrivals[arrivals < duration_s]
    seeds = rng.integers(0, 2**31 - 1, size=len(arrivals))
    texts = draw_prompt_schedule(
        rng, len(arrivals), model.text_seq_len, model.num_text_tokens,
        prompt_reuse,
    )
    priorities = [
        "high" if rng.random() < mix else "low" for _ in arrivals
    ]

    # counter snapshots so the line reports the measured window only
    pre = {
        name: _class_counter_values(cont.registry, f"dalle_serving_{name}")
        for name in ("preemptions_total", "resumptions_total", "shed_total")
    }
    classes = run_priority_open_loop(
        cb, arrivals, seeds, texts, priorities, timeout_s
    )
    cb.shutdown(drain=True)

    def window(name):
        now = _class_counter_values(cont.registry, f"dalle_serving_{name}")
        return {
            label: now.get(label, 0) - pre[name].get(label, 0)
            for label in now
        }

    line = {
        "metric": "serving_priority_mix",
        "unit": "ratio",
        "device": jax.devices()[0].platform,
        "mode": "open-loop",
        "engine": "continuous",
        "kv_layout": kv_layout,
        "priority_mix": mix,
        "rate_rps": round(rate, 3),
        "saturation_rps": round(cap, 3),
        "overload_factor": overload,
        "duration_s": duration_s,
        "request_timeout_s": timeout_s,
        "ttft_unloaded_p50_ms": unloaded["ttft_p50_ms"],
        "ttft_unloaded_p95_ms": unloaded["ttft_p95_ms"],
        "classes": classes,
        "preemptions": window("preemptions_total"),
        "resumptions": window("resumptions_total"),
        "shed": window("shed_total"),
        "dispatch_retries": int(
            cont.registry.get("dalle_serving_dispatch_retries_total").value
        ),
    }
    high = classes.get("high") or {}
    if high.get("ttft_p95_ms") and unloaded["ttft_p95_ms"]:
        line["high_ttft_p95_ratio_vs_unloaded"] = round(
            high["ttft_p95_ms"] / unloaded["ttft_p95_ms"], 3
        )
        line["value"] = line["high_ttft_p95_ratio_vs_unloaded"]
    else:
        line["value"] = None
    low = classes.get("low") or {}
    if high.get("ttft_p95_ms") and low.get("ttft_p95_ms"):
        line["low_ttft_p95_ratio_vs_high"] = round(
            low["ttft_p95_ms"] / high["ttft_p95_ms"], 3
        )
    print(json.dumps(line), flush=True)


def fleet_request(port, body, timeout=30.0, headers=None):
    """One HTTP POST /generate against the router. NEVER raises: a
    router-down window must record an error outcome in the load loop,
    not crash the bench (tests/test_router.py pins this)."""
    import urllib.error
    import urllib.request

    t0 = time.monotonic()
    out = {"ok": False, "status": None, "error": None, "payload": None}
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json", **(headers or {})},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            out["status"] = resp.status
            out["payload"] = json.loads(resp.read())
            out["ok"] = resp.status == 200
    except urllib.error.HTTPError as exc:
        out["status"] = exc.code
        out["error"] = f"http {exc.code}"
        try:
            exc.read()
        except Exception:
            pass
    except Exception as exc:
        out["error"] = repr(exc)
    out["latency_s"] = time.monotonic() - t0
    return out


def run_fleet_window(port, arrivals, seeds, timeout_s=60.0, on_offset=None,
                     tenant_of=None):
    """Open-loop Poisson replay through the router over HTTP: each
    arrival fires a client thread (open-loop — a slow fleet cannot slow
    the arrival process). `on_offset` is the chaos hook: (offset_s,
    callable) runs once when the schedule passes that offset — the bench
    kills a replica with it mid-window. `tenant_of` (index -> tenant
    string) stamps each request with a tenant so the usage ledger has
    something to attribute. Returns completion counts and latency
    percentiles."""
    results = [None] * len(arrivals)
    threads = []
    fired = threading.Event()
    t_start = time.monotonic()
    for i, (offset, seed) in enumerate(zip(arrivals, seeds)):
        delay = t_start + offset - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        if (
            on_offset is not None and not fired.is_set()
            and offset >= on_offset[0]
        ):
            fired.set()
            # off the arrival thread: a blocking kill (server shutdown
            # joins worker threads) must not stall the Poisson schedule
            threading.Thread(target=on_offset[1], daemon=True).start()

        def client(i=i, seed=seed):
            body = {"prompt": f"fleet bench {seed}", "seed": int(seed),
                    "timeout_s": timeout_s}
            if tenant_of is not None:
                body["tenant"] = tenant_of(i)
            results[i] = fleet_request(port, body, timeout=timeout_s + 5.0)

        t = threading.Thread(target=client, daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=timeout_s + 10.0)
    done = [r for r in results if r is not None]
    lat = sorted(r["latency_s"] for r in done if r["ok"])
    completed = sum(1 for r in done if r["ok"])
    wall = time.monotonic() - t_start
    return {
        "offered": len(arrivals),
        "completed": completed,
        "errors": len(arrivals) - completed,
        "wall_s": round(wall, 3),
        "rps": round(completed / max(wall, 1e-9), 3),
        "latency_p50_ms": (
            round(1000 * _percentile(lat, 0.5), 1) if lat else None
        ),
        "latency_p95_ms": (
            round(1000 * _percentile(lat, 0.95), 1) if lat else None
        ),
    }


def _fleet_block(scraper, router):
    """The telemetry-plane slice of the fleet bench line: one final
    scrape sweep (the killed replica shows up stale), then the capacity
    model's goodput/suggested-replicas read and the usage ledger's
    per-tenant chip-second attribution."""
    scraper.scrape_once()
    cap = scraper.capacity_report()
    usage = router.usage.summary()
    return {
        "goodput_fraction": cap["goodput"]["fraction"],
        "wasted_tokens": cap["goodput"]["wasted_tokens"],
        "suggested_replicas": cap["suggested_replicas"],
        "fresh_replicas": cap["fresh_replicas"],
        "scrape_generations": {
            name: {"generation": s.generation, "stale": s.stale}
            for name, s in sorted(scraper.snapshot().items())
        },
        "chip_seconds_by_tenant": {
            f'{r["tenant"]}/{r["priority"]}': r["chip_seconds"]
            for r in usage["tenants"]
        },
        "chip_seconds_total": usage["totals"]["chip_seconds"],
    }


def main_fleet(n_replicas, hedge_after_ms=None):
    """`--replicas N` fleet mode: N in-process continuous-engine
    replicas behind a real `FleetRouter`, open-loop load over HTTP, one
    replica HARD-KILLED mid-window — one JSON line with the healthy
    window, the chaos window (must still complete 100%), and the
    router's failover/hedge accounting."""
    import numpy as np

    from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer
    from dalle_pytorch_tpu.obs.fleetmetrics import FleetScraper
    from dalle_pytorch_tpu.serving.engine import ContinuousEngine
    from dalle_pytorch_tpu.serving.router import FleetRouter, RouterServer
    from dalle_pytorch_tpu.serving.server import ServingServer
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry

    assert n_replicas >= 2, "--replicas needs >= 2 (one gets killed)"
    chunk_tokens = int(os.environ.get("SERVE_CHUNK_TOKENS", "4"))
    max_batch = int(os.environ.get("SERVE_FLEET_SLOTS", "4"))
    duration_s = float(os.environ.get("SERVE_FLEET_SECONDS", "6"))
    model, params, vae, vae_params, _text_ids = build_toy()

    servers = []
    for _ in range(n_replicas):
        eng = ContinuousEngine(
            model=model, variables=params, vae=vae, vae_params=vae_params,
            max_batch=max_batch, chunk_tokens=chunk_tokens,
            prefill_batch=max_batch, registry=MetricsRegistry(),
        )
        eng.tokenizer = ByteTokenizer()
        servers.append(
            ServingServer(
                eng, port=0, request_timeout_s=120,
                max_queue_rows=max(64, 8 * max_batch),
            ).start()
        )
    router = FleetRouter(
        [f"r{i}=http://127.0.0.1:{s.port}" for i, s in enumerate(servers)],
        registry=MetricsRegistry(),
        hedge_after_ms=hedge_after_ms,
        probe_interval_s=0.25,
    )
    scraper = FleetScraper(
        [(rep.name, rep.url) for rep in router.replicas],
        registry=router.registry, usage=router.usage, interval_s=0.5,
    )
    front = RouterServer(router, port=0, fleet=scraper).start()
    port = front.port

    # warm every replica (compile + one real request) and calibrate the
    # offered rate off the measured warm latency: ~40% of the fleet's
    # rough capacity (max_batch rows per image-time per replica)
    warm_lat = []
    for i in range(n_replicas * 3):
        out = fleet_request(port, {"prompt": "warm", "seed": 10_000 + i})
        assert out["ok"], f"warmup request failed: {out}"
        warm_lat.append(out["latency_s"])
    # rate off the WARM single-request latency (last round only — the
    # first pays compiles), derated to 25% of the optimistic
    # slots-per-image-time fleet capacity: this is a ROBUSTNESS
    # instrument, so the healthy window must complete 100% and the chaos
    # claim isolates the kill, not queue-full backpressure
    image_s = max(min(warm_lat[-n_replicas:]), 1e-3)
    rate = 0.25 * n_replicas * max_batch / image_s
    rate = float(os.environ.get("SERVE_FLEET_RPS", rate))

    rng = np.random.default_rng(int(os.environ.get("SERVE_ARRIVAL_SEED", "0")))
    n = max(4, int(rate * duration_s))
    arrivals = np.sort(rng.uniform(0.0, duration_s, size=n))
    seeds = rng.integers(0, 2**31 - 1, size=n)

    reg = router.registry

    def _fam(name):
        fam = reg.get(name)
        if fam is None:
            return {}
        if hasattr(fam, "items"):
            return {label: int(c.value) for label, c in fam.items()}
        return {"total": int(fam.value)}

    # alternate two tenants so the usage ledger's chip-second
    # attribution has something to split
    tenant_of = lambda i: "tenant-a" if i % 2 == 0 else "tenant-b"

    healthy = run_fleet_window(port, arrivals, seeds, tenant_of=tenant_of)

    # snapshot AFTER the healthy window: the router block must describe
    # the chaos window it is printed next to, not fold in warmup and
    # healthy-window traffic
    fam_names = (
        "dalle_router_requests_total", "dalle_router_failovers_total",
        "dalle_router_hedges_total", "dalle_router_hedge_wins_total",
        "dalle_router_ejections_total", "dalle_router_unroutable_total",
    )
    before = {name: _fam(name) for name in fam_names}

    kill_at = 0.3 * duration_s

    def kill():
        servers[0].shutdown(drain=False)

    killed = run_fleet_window(
        port, arrivals, seeds + 1, on_offset=(kill_at, kill),
        tenant_of=tenant_of,
    )

    def _delta(name):
        prev = before[name]
        return {
            label: v - prev.get(label, 0)
            for label, v in _fam(name).items()
        }

    per_replica = _delta("dalle_router_requests_total")
    total_reqs = max(1, sum(per_replica.values()))
    line = {
        "bench": "serving_fleet",
        "engine": "continuous",
        "replicas": n_replicas,
        "max_batch": max_batch,
        "chunk_tokens": chunk_tokens,
        "rate_rps": round(rate, 3),
        "killed_replica": "r0",
        "kill_at_s": round(kill_at, 3),
        "healthy": healthy,
        "killed": killed,
        "router": {
            # killed-window DELTAS: what the chaos cost, not lifetime
            "failovers": _delta("dalle_router_failovers_total"),
            "hedges": _delta("dalle_router_hedges_total").get("total", 0),
            "hedge_wins": _delta("dalle_router_hedge_wins_total").get(
                "total", 0
            ),
            "ejections": _delta("dalle_router_ejections_total"),
            "unroutable": _delta("dalle_router_unroutable_total").get(
                "total", 0
            ),
            "retry_budget": round(router.budget.balance, 2),
            "per_replica_share": {
                name: round(v / total_reqs, 3)
                for name, v in per_replica.items()
            },
        },
        "fleet": _fleet_block(scraper, router),
        "p95_killed_vs_healthy": (
            round(killed["latency_p95_ms"] / healthy["latency_p95_ms"], 3)
            if killed["latency_p95_ms"] and healthy["latency_p95_ms"]
            else None
        ),
        "value": killed["completed"] / max(1, killed["offered"]),
        "metric": "fleet_completion_with_replica_killed",
        "unit": "fraction",
    }
    print(json.dumps(line), flush=True)

    front.shutdown()
    for s in servers[1:]:
        s.shutdown()


def main_drain_bench():
    """`--drain_bench`: rolling drain of one of two replicas mid-window,
    three flavors over identical Poisson schedules of 2-row requests:

      * `migrate`  — `drain?migrate=1`: the replica exports decode-state
        checkpoints at a chunk boundary; the router re-dispatches each
        in-flight request as a RESUME on the healthy replica.
      * `wait`     — the PR 12 graceful drain: stop admissions, wait out
        every outstanding row (zero re-decode, but the drain takes a
        full decode).
      * `failover` — the non-migrating baseline: a dispatch failure
        (FaultInjector) destroys the replica's decode state mid-window;
        recovery re-admits everything in flight FROM SCRATCH (the PR 11
        bounded retry) — the re-decode cost migration exists to cut.

    One JSON line: per-flavor client-visible errors, drain wall time,
    decoded/resumed token counters, and `re_decoded` (tokens decoded
    beyond what the completed requests strictly needed). The acceptance
    claim is migrate: zero errors, re_decoded strictly below kill's,
    drain wall far below wait's.
    """
    import numpy as np

    from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer
    from dalle_pytorch_tpu.serving.engine import ContinuousEngine
    from dalle_pytorch_tpu.serving.router import FleetRouter, RouterServer
    from dalle_pytorch_tpu.serving.server import ServingServer
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry

    # a bigger toy image than the other modes: the instrument measures
    # WORK IN FLIGHT at drain time, so decode must take long enough for
    # the drain to catch requests mid-image
    os.environ.setdefault("SERVE_FMAP", "8")
    chunk_tokens = int(os.environ.get("SERVE_CHUNK_TOKENS", "1"))
    max_batch = int(os.environ.get("SERVE_FLEET_SLOTS", "4"))
    duration_s = float(os.environ.get("SERVE_DRAIN_SECONDS", "8"))
    num_images = 2
    model, params, vae, vae_params, _text_ids = build_toy()
    image_seq = model.image_seq_len

    def build_fleet():
        servers = []
        for _ in range(2):
            eng = ContinuousEngine(
                model=model, variables=params, vae=vae,
                vae_params=vae_params, max_batch=max_batch,
                chunk_tokens=chunk_tokens, prefill_batch=max_batch,
                registry=MetricsRegistry(), resume_enabled=True,
            )
            eng.tokenizer = ByteTokenizer()
            servers.append(
                ServingServer(
                    eng, port=0, request_timeout_s=120,
                    max_queue_rows=max(64, 8 * max_batch),
                ).start()
            )
        router = FleetRouter(
            [f"r{i}=http://127.0.0.1:{s.port}"
             for i, s in enumerate(servers)],
            registry=MetricsRegistry(), probe_interval_s=0.25,
        )
        front = RouterServer(router, port=0).start()
        return servers, router, front

    servers, router, front = build_fleet()
    port = front.port

    warm_lat = []
    for i in range(6):
        out = fleet_request(
            port, {"prompt": "warm", "seed": 10_000 + i,
                   "num_images": num_images},
        )
        assert out["ok"], f"warmup request failed: {out}"
        warm_lat.append(out["latency_s"])
    image_s = max(min(warm_lat[-2:]), 1e-3)
    # 40% of optimistic fleet capacity: high enough that the drained
    # replica holds real in-flight work, low enough that the surviving
    # replica can absorb the post-drain window without shedding
    rate = 0.3 * 2 * max_batch / num_images / image_s
    rate = float(os.environ.get("SERVE_DRAIN_RPS", rate))
    rng = np.random.default_rng(
        int(os.environ.get("SERVE_ARRIVAL_SEED", "0"))
    )
    n = max(4, int(rate * duration_s))
    arrivals = np.sort(rng.uniform(0.0, duration_s, size=n))
    base_seeds = rng.integers(0, 2**30 - 1, size=n)
    drain_at = 0.3 * duration_s

    def counters(which):
        out = 0
        for s in servers:
            c = s.registry.get(f"dalle_serving_{which}_tokens_total")
            out += int(c.value) if c is not None else 0
        return out

    def run_window(mode, seeds):
        before_dec = counters("decoded")
        before_res = counters("resumed")
        drain_wall = {}

        def trigger():
            # wait (bounded) for r0 to actually HOLD work, so every
            # flavor measures a loaded-replica drain, not an empty one
            rep0 = router._find("r0")
            t_deadline = time.monotonic() + 5.0
            while rep0.outstanding_rows == 0 \
                    and time.monotonic() < t_deadline:
                time.sleep(0.002)
            drain_wall["caught_rows"] = rep0.outstanding_rows
            t0 = time.monotonic()
            if mode == "migrate":
                router.drain("r0", wait_s=60.0, migrate=True)
            elif mode == "wait":
                router.drain("r0", wait_s=120.0, propagate=True)
            else:
                # failover baseline: one injected chunk-dispatch failure
                # destroys r0's donated decode state; every in-flight
                # request suspends and re-admits FROM SCRATCH (the PR 11
                # bounded retry) — the exact re-decode a crash costs
                # today, without migration
                from dalle_pytorch_tpu.serving.faults import FaultInjector

                servers[0].engine.faults = FaultInjector().fail_nth(
                    "chunk", 1
                )
            drain_wall["s"] = time.monotonic() - t0

        results = [None] * len(arrivals)
        threads = []
        fired = threading.Event()
        trigger_thread = None
        t_start = time.monotonic()
        for i, (offset, seed) in enumerate(zip(arrivals, seeds)):
            delay = t_start + offset - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if not fired.is_set() and offset >= drain_at:
                fired.set()
                trigger_thread = threading.Thread(
                    target=trigger, daemon=True
                )
                trigger_thread.start()

            def client(i=i, seed=seed):
                results[i] = fleet_request(
                    port,
                    {"prompt": f"drain bench {seed}", "seed": int(seed),
                     "num_images": num_images, "timeout_s": 90},
                    timeout=95.0,
                )

            t = threading.Thread(target=client, daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=120.0)
        if trigger_thread is not None:
            # the wait-drain blocks until outstanding hits zero — join it
            # so drain_wall_s is the real number, not a race with the
            # last client's completion
            trigger_thread.join(timeout=150.0)
        done = [r for r in results if r is not None]
        completed = sum(1 for r in done if r["ok"])
        lat = sorted(r["latency_s"] for r in done if r["ok"])
        errors_by = {}
        for r in done:
            if not r["ok"]:
                key = str(r["status"] or r["error"])
                errors_by[key] = errors_by.get(key, 0) + 1
        decoded = counters("decoded") - before_dec
        resumed = counters("resumed") - before_res
        needed = completed * num_images * image_seq
        return {
            "offered": len(arrivals),
            "completed": completed,
            "errors": len(arrivals) - completed,
            "errors_by": errors_by,
            "drain_caught_rows": drain_wall.get("caught_rows", 0),
            "drain_wall_s": round(drain_wall.get("s", 0.0), 3),
            "decoded_tokens": decoded,
            "resumed_tokens": resumed,
            "needed_tokens": needed,
            # decode work beyond what the completed requests strictly
            # required — the lost-work number migration exists to cut
            "re_decoded_tokens": max(0, decoded - needed),
            "latency_p95_ms": (
                round(1000 * _percentile(lat, 0.95), 1) if lat else None
            ),
        }

    windows = {}
    windows["migrate"] = run_window("migrate", base_seeds)
    router.undrain("r0", propagate=True)
    # let the half-open trial re-admit r0 before the next window
    for i in range(4):
        fleet_request(port, {"prompt": "rejoin", "seed": 20_000 + i,
                             "num_images": num_images})
    windows["wait"] = run_window("wait", base_seeds + 1)
    router.undrain("r0", propagate=True)
    for i in range(4):
        fleet_request(port, {"prompt": "rejoin2", "seed": 30_000 + i,
                             "num_images": num_images})
    windows["failover"] = run_window("failover", base_seeds + 2)

    migs = router.registry.get("dalle_router_migrations_total")
    line = {
        "bench": "serving_drain",
        "engine": "continuous",
        "max_batch": max_batch,
        "chunk_tokens": chunk_tokens,
        "num_images": num_images,
        "rate_rps": round(rate, 3),
        "drain_at_s": round(drain_at, 3),
        "windows": windows,
        "router_migrations": (
            {label: int(c.value) for label, c in migs.items()}
            if migs is not None else {}
        ),
        "value": (
            1.0 if windows["migrate"]["errors"] == 0
            and windows["migrate"]["re_decoded_tokens"]
            < max(1, windows["failover"]["re_decoded_tokens"])
            else 0.0
        ),
        "metric": "migrating_drain_zero_error_and_less_redecode",
        "unit": "bool",
    }
    print(json.dumps(line), flush=True)

    front.shutdown()
    for s in servers:
        s.shutdown()


def _toy_checkpoint(path):
    """A loadable single-file DALLE checkpoint with randomly initialized
    toy weights — the restart bench measures BOOT cost (checkpoint load +
    compile), which does not care whether the model was trained."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dvae import DiscreteVAE
    from dalle_pytorch_tpu.training.config import TrainConfig
    from dalle_pytorch_tpu.training.pipeline import (
        build_tokenizer,
        dalle_from_config,
        dvae_hparams,
        save_dalle_checkpoint,
    )

    cfg = TrainConfig()
    cfg.model.dim = int(os.environ.get("SERVE_DIM", "64"))
    cfg.model.depth = int(os.environ.get("SERVE_DEPTH", "2"))
    cfg.model.heads = 2
    cfg.model.dim_head = cfg.model.dim // 2
    cfg.model.text_seq_len = int(os.environ.get("SERVE_TEXT_SEQ", "16"))
    cfg.model.shift_tokens = False
    cfg.model.rotary_emb = True
    fmap = int(os.environ.get("SERVE_FMAP", "4"))
    vae = DiscreteVAE(
        image_size=4 * fmap, num_layers=2, num_tokens=64,
        codebook_dim=32, hidden_dim=16,
    )
    vae_params = jax.jit(vae.init)(
        jax.random.PRNGKey(1), jnp.zeros((1, 4 * fmap, 4 * fmap, 3))
    )["params"]
    tokenizer = build_tokenizer(cfg)
    model = dalle_from_config(
        cfg, num_image_tokens=vae.num_tokens, image_fmap_size=fmap,
        vocab_size=max(tokenizer.vocab_size, 1),
    )
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.model.text_seq_len), jnp.int32),
        jnp.zeros((1, fmap * fmap), jnp.int32),
    )
    save_dalle_checkpoint(
        str(path), cfg, variables["params"], vae_params, 0,
        "DiscreteVAE", vae_hparams=dvae_hparams(vae),
    )
    return path


class _ReplicaProc:
    """One serve.py subprocess with its stdout harvested into structured
    log records (the boot bench reads warmup_done; the supervised bench
    reads replica_start/replica_ready pids and timings)."""

    def __init__(self, argv, env=None):
        import subprocess
        import sys

        self.t0 = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable] + argv, text=True, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        self.lines = []
        self.events = []
        self._lock = threading.Lock()
        self.ready_at = None
        self.port = None
        self._ready = threading.Event()
        self._reader = threading.Thread(target=self._pump, daemon=True)
        self._reader.start()

    def _pump(self):
        for line in self.proc.stdout:
            with self._lock:
                self.lines.append(line)
            if "listening on http://" in line:
                self.ready_at = time.perf_counter()
                self.port = int(
                    line.split("http://")[1].split()[0].rsplit(":", 1)[1]
                )
                self._ready.set()
            elif line.startswith("{"):
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                with self._lock:
                    self.events.append(rec)
        self._ready.set()  # EOF: unblock waiters (boot failed)

    def wait_ready(self, timeout=600.0):
        ok = self._ready.wait(timeout) and self.port is not None
        with self._lock:
            tail = "".join(self.lines[-40:])
        assert ok, "replica never came up:\n" + tail
        return self.ready_at - self.t0

    def event(self, name, default=None):
        with self._lock:
            events = list(self.events)
        for rec in reversed(events):
            if rec.get("event") == name:
                return rec
        return default

    def stop(self, sig=None):
        import signal as _signal

        if self.proc.poll() is None:
            self.proc.send_signal(sig or _signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except Exception:
                self.proc.kill()
        self._reader.join(timeout=5)


def _serve_argv(ckpt, cache_dir, port, chunk_tokens):
    from pathlib import Path

    return [
        str(Path(__file__).parent / "serve.py"),
        "--dalle_path", str(ckpt), "--port", str(port),
        "--engine", "continuous", "--batch_shapes", "1,4",
        "--chunk_tokens", str(chunk_tokens),
        "--compile_cache", str(cache_dir),
        "--no_request_log",
    ]


def main_restart_bench():
    """`--restart_bench`: two JSON lines.

    1. serving_restart — boot-to-first-token of the SAME checkpoint,
       cold compile cache vs warm (the crash-fast recovery claim: a
       restarted replica's boot cost is cache load, not XLA).
    2. serving_supervised_restart — a 2-replica fleet behind a real
       router, replica 0 under `serve.py --supervise` with a warm
       cache; its serving child is SIGKILLed mid-window; the line
       reports completion (must be 1.0), the supervisor's restart
       count, the child's time-to-ready, and the router's
       ejected->half_open->healthy rejoin accounting.
    """
    import os as _os
    import shutil
    import signal as _signal
    import subprocess
    import sys

    import numpy as np

    from dalle_pytorch_tpu.serving.router import FleetRouter, RouterServer
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry
    from dalle_pytorch_tpu.utils.compile_cache import xla_cache_dir

    # One process per chip: this parent never imports jax. The checkpoint
    # is built by a child that exits before the first replica starts, and
    # the replicas run on whatever platform the environment selects.
    # NOTE the supervised half runs TWO replicas at once: it needs one
    # device per replica (or the CPU); on a one-chip machine the second
    # replica cannot get the chip.
    chunk_tokens = int(_os.environ.get("SERVE_CHUNK_TOKENS", "4"))
    # a fixed place beside the XLA cache (the path is part of the cache
    # key, so it must not move between runs), wiped so boot one is cold
    work = xla_cache_dir() / "restart_bench"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    ckpt = work / "dalle.npz"
    subprocess.run(
        [sys.executable, __file__, "--make_toy_checkpoint", str(ckpt)],
        check=True,
    )
    assert "jax" not in sys.modules, "the restart-bench parent imported jax"
    cache_dir = work / "compile_cache"
    env = dict(_os.environ)

    def boot_once():
        rep = _ReplicaProc(
            _serve_argv(ckpt, cache_dir, 0, chunk_tokens), env=env
        )
        boot_s = rep.wait_ready()
        t0 = time.perf_counter()
        out = fleet_request(
            rep.port, {"prompt": "restart bench", "seed": 1234},
            timeout=300,
        )
        assert out["ok"], out
        first_s = time.perf_counter() - t0
        warmup = rep.event("warmup_done", {})
        rep.stop()
        return {
            "boot_s": round(boot_s, 2),
            "first_request_s": round(first_s, 3),
            "boot_to_first_token_s": round(boot_s + first_s, 2),
            "compiles": warmup.get("compiles"),
            "cache_hits": warmup.get("cache_hits"),
            "uncached_compiles": warmup.get("uncached_compiles"),
            "boot_cache_mode": warmup.get("boot_cache_mode"),
            "boot_seconds": warmup.get("boot_seconds"),
        }

    cold = boot_once()
    warm = boot_once()
    print(json.dumps({
        "bench": "serving_restart",
        "engine": "continuous",
        "chunk_tokens": chunk_tokens,
        "cold": cold,
        "warm": warm,
        "boot_speedup": round(
            cold["boot_to_first_token_s"]
            / max(warm["boot_to_first_token_s"], 1e-6), 2,
        ),
        "value": warm["boot_to_first_token_s"],
        "metric": "warm_boot_to_first_token_seconds",
        "unit": "s",
    }), flush=True)

    # ---- supervised kill -> restart -> rejoin window -------------------
    duration_s = float(_os.environ.get("SERVE_RESTART_SECONDS", "30"))
    import socket as _socket

    probe = _socket.socket()
    probe.bind(("127.0.0.1", 0))
    r0_port = probe.getsockname()[1]
    probe.close()
    sup = _ReplicaProc(
        _serve_argv(ckpt, cache_dir, r0_port, chunk_tokens)
        + ["--supervise"],
        env=env,
    )
    r1 = _ReplicaProc(
        _serve_argv(ckpt, cache_dir, 0, chunk_tokens), env=env
    )
    sup.wait_ready()
    r1.wait_ready()
    router = FleetRouter(
        [
            f"r0=http://127.0.0.1:{r0_port}",
            f"r1=http://127.0.0.1:{r1.port}",
        ],
        registry=MetricsRegistry(),
        probe_interval_s=0.25,
    )
    front = RouterServer(router, port=0).start()
    try:
        warm_lat = []
        for i in range(6):
            out = fleet_request(
                front.port, {"prompt": "warm", "seed": 20_000 + i},
                timeout=300,
            )
            assert out["ok"], out
            warm_lat.append(out["latency_s"])
        image_s = max(min(warm_lat[-2:]), 1e-3)
        rate = 0.25 * 2 * 4 / image_s  # 25% of optimistic fleet capacity
        rate = float(_os.environ.get("SERVE_RESTART_RPS", rate))
        rng = np.random.default_rng(0)
        n = max(8, int(rate * duration_s))
        arrivals = np.sort(rng.uniform(0.0, duration_s, size=n))
        seeds = rng.integers(0, 2**31 - 1, size=n)

        start_rec = sup.event("replica_start")
        child_pid = int(start_rec["pid"])
        kill_at = 0.25 * duration_s

        def kill():
            _os.kill(child_pid, _signal.SIGKILL)

        window = run_fleet_window(
            front.port, arrivals, seeds, timeout_s=120.0,
            on_offset=(kill_at, kill),
        )
        # wait out the rejoin so the attribution below is complete
        deadline = time.monotonic() + 120
        rep0 = router.replicas[0]
        while rep0.restarts < 1 and time.monotonic() < deadline:
            fleet_request(
                front.port,
                {"prompt": "rejoin", "seed": int(time.monotonic() * 1e3)},
                timeout=300,
            )
            time.sleep(0.25)
        ready = sup.event("replica_ready", {})
        line = {
            "bench": "serving_supervised_restart",
            "engine": "continuous",
            "replicas": 2,
            "rate_rps": round(rate, 3),
            "duration_s": duration_s,
            "kill_at_s": round(kill_at, 2),
            "window": window,
            "supervisor": {
                "restarts": int(ready.get("restarts", 0)),
                "time_to_ready_s": ready.get("time_to_ready_s"),
            },
            "router": {
                "r0_restarts": rep0.restarts,
                "r0_rejoin_s": (
                    round(rep0.last_rejoin_s, 2)
                    if rep0.last_rejoin_s is not None else None
                ),
                "r0_down_reason": rep0.last_down_reason,
                "r0_state": rep0.state(),
            },
            "value": window["completed"] / max(1, window["offered"]),
            "metric": "supervised_restart_completion",
            "unit": "fraction",
        }
        print(json.dumps(line), flush=True)
    finally:
        front.shutdown()
        sup.stop()
        r1.stop()


def main_closed_loop():
    sweep = [
        int(c) for c in os.environ.get("SERVE_SWEEP", "1,4,8").split(",")
    ]
    requests_per_client = int(os.environ.get("SERVE_REQUESTS", "8"))
    delay_ms = float(os.environ.get("SERVE_DELAY_MS", "25"))

    engine, text_ids = build_engine()
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0

    results = [
        run_level(engine, text_ids, c, requests_per_client, delay_ms)
        for c in sweep
    ]
    top = results[-1]
    import jax

    record = {
        "metric": METRIC,
        "value": top["rps"],
        "unit": UNIT,
        "ok": all(r["errors"] == 0 for r in results),
        "device": jax.devices()[0].platform,
        "warmup_s": round(warmup_s, 2),
        "compiled_shapes": list(engine.stats.compiled_shapes),
        "max_delay_ms": delay_ms,
        "requests_per_client": requests_per_client,
        "sweep": results,
    }
    print(json.dumps(record))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--mode", choices=("closed-loop", "open-loop"),
        default=os.environ.get("SERVE_MODE", "closed-loop"),
    )
    p.add_argument(
        "--prompt_reuse", type=float,
        default=float(os.environ.get("SERVE_PROMPT_REUSE", "0")),
        help="open-loop: probability an arrival repeats a prompt from a "
        "Zipf-ish popularity pool instead of drawing a unique one "
        "(repeat prompts are the prefix cache's workload; 0 = legacy "
        "all-unique mix)",
    )
    p.add_argument(
        "--kv_layout", choices=("slot", "paged"),
        default=os.environ.get("SERVE_KV_LAYOUT", "slot"),
        help="open-loop: continuous engine cache layout (paged adds "
        "block_occupancy + prefix-cache stats and hit-vs-cold TTFT "
        "splits to its JSON line; SERVE_PAGE_SIZE / SERVE_KV_PAGES size "
        "the pool)",
    )
    p.add_argument(
        "--mesh", type=str, default=os.environ.get("SERVE_MESH") or None,
        help="open-loop: run the continuous side as a mesh-sharded "
        "engine (axis=size pairs over dp/fsdp/tp/sp, e.g. 'tp=2'); the "
        "JSON line gains a `mesh` block with axis sizes and per-device "
        "memory peaks (slot and paged layouts both shard)",
    )
    p.add_argument(
        "--kv_dtype", choices=("model", "int8"),
        default=os.environ.get("SERVE_KV_DTYPE", "model"),
        help="open-loop: continuous-engine KV-cache storage dtype; int8 "
        "stores pages/lanes quantized (per-(position, head) scales, "
        "in-kernel dequant) and adds a `quality` block — toy-CLIP score "
        "mean/delta vs the bf16 reference on the same (prompt, seed) "
        "rows — beside kv_bytes_per_slot",
    )
    p.add_argument(
        "--decode_sparsity", choices=("causal", "policy"),
        default=os.environ.get("SERVE_DECODE_SPARSITY", "causal"),
        help="open-loop: continuous-engine decode-attention sparsity; "
        "policy builds the toy with alternating full/axial layers and "
        "routes masked rows through the block-sparse flash kernel "
        "(serving/sparsity.py bitmaps, SERVE_SPARSE_BLOCK tile width) — "
        "the JSON line gains kv_tiles_read/kv_tiles_skipped/"
        "kv_tile_skip_fraction and the toy-CLIP `quality` block vs the "
        "dense-masked reference",
    )
    p.add_argument(
        "--priority_mix", type=float,
        default=(
            float(os.environ["SERVE_PRIORITY_MIX"])
            if os.environ.get("SERVE_PRIORITY_MIX") else None
        ),
        help="open-loop QoS mode: fraction of arrivals submitted as "
        "priority 'high' (the rest 'low'), replayed at an OVERLOAD rate "
        "(SERVE_PRIORITY_OVERLOAD x measured saturation) against one "
        "continuous batcher with preemption + deadline shedding; the "
        "JSON line reports per-class TTFT percentiles, preemption/"
        "resumption/shed counts, and high-vs-unloaded p95 ratio",
    )
    p.add_argument(
        "--replicas", type=int,
        default=int(os.environ.get("SERVE_REPLICAS", "0")),
        help="fleet mode: N in-process continuous replicas behind a real "
        "FleetRouter, open-loop HTTP load, one replica hard-killed "
        "mid-window; the JSON line carries the healthy vs killed-window "
        "latency and the router's failover/hedge accounting "
        "(SERVE_FLEET_SECONDS / SERVE_FLEET_RPS / SERVE_HEDGE_MS)",
    )
    p.add_argument(
        "--drain_bench", action="store_true",
        default=os.environ.get("SERVE_DRAIN_BENCH", "0") in ("1", "true"),
        help="zero-lost-work drain mode: two continuous replicas behind "
        "a real router, one drained mid-window three ways — "
        "drain?migrate=1 (decode-state checkpoints re-dispatched as "
        "resumes), graceful wait-drain, and an injected state-loss "
        "failure (the non-migrating failover baseline); one JSON line "
        "with per-flavor errors, drain wall "
        "time, and re-decoded token counts "
        "(SERVE_DRAIN_SECONDS / SERVE_DRAIN_RPS)",
    )
    p.add_argument(
        "--restart_bench", action="store_true",
        default=os.environ.get("SERVE_RESTART_BENCH", "0") in ("1", "true"),
        help="crash-fast recovery mode: (1) boot-to-first-token of the "
        "same checkpoint cold vs warm compile cache, (2) a supervised "
        "replica SIGKILLed mid-window behind a real router — restart, "
        "half-open rejoin, completion fraction; one JSON line each "
        "(SERVE_RESTART_SECONDS / SERVE_RESTART_RPS)",
    )
    p.add_argument(
        "--stream", action="store_true",
        default=os.environ.get("SERVE_STREAM", "0") in ("1", "true"),
        help="streaming-previews mode: one continuous engine with the "
        "preview fill-decode program warmed, open-loop arrivals each "
        "carrying a live event stream; the JSON line reports TTFP "
        "(time-to-first-preview) p50/p95 alongside TTFT and the "
        "headline ttfp_p95_chunk_periods acceptance ratio "
        "(SERVE_PREVIEW_EVERY / SERVE_STREAM_SECONDS)",
    )
    p.add_argument(
        "--trace_export", action="store_true",
        default=os.environ.get("SERVE_TRACE_EXPORT", "0") in ("1", "true"),
        help="open-loop: trace every measured request through an "
        "in-process fleet collector (obs/collector.py) and add a "
        "`critical_path` block — per-stage fleet p50/p95 plus dominant-"
        "stage attribution over the measured window only — to each "
        "engine's JSON line",
    )
    p.add_argument("--make_toy_checkpoint", metavar="PATH", default=None,
                   help=argparse.SUPPRESS)  # --restart_bench's builder child
    args = p.parse_args()
    if args.make_toy_checkpoint:
        _toy_checkpoint(args.make_toy_checkpoint)
    elif args.stream:
        main_stream_bench(kv_layout=args.kv_layout)
    elif args.drain_bench:
        main_drain_bench()
    elif args.restart_bench:
        main_restart_bench()
    elif args.replicas:
        hedge = os.environ.get("SERVE_HEDGE_MS")
        main_fleet(
            args.replicas,
            hedge_after_ms=float(hedge) if hedge else None,
        )
    elif args.mode == "open-loop" and args.priority_mix is not None:
        main_priority_mix(
            args.priority_mix, kv_layout=args.kv_layout,
            prompt_reuse=args.prompt_reuse,
        )
    elif args.mode == "open-loop":
        main_open_loop(
            prompt_reuse=args.prompt_reuse, kv_layout=args.kv_layout,
            mesh=args.mesh, trace_export=args.trace_export,
            kv_dtype=args.kv_dtype,
            decode_sparsity=args.decode_sparsity,
        )
    else:
        main_closed_loop()


if __name__ == "__main__":
    main()
