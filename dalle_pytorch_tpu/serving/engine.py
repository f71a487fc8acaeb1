"""GenerationEngine: fixed-shape compiled sampling behind a dynamic API.

XLA compiles one program per input shape, so a serving layer must not let
arbitrary request counts reach the sampler — every distinct batch size
would trigger a fresh (expensive, possibly remote) compile. The engine
therefore owns a small ladder of batch shapes (default {1, 4, 8}), rounds
every micro-batch UP to the nearest rung by padding with copies of row 0,
and slices the padding back off. Per-request sampling parameters (seed /
temperature / top-k) ride along as traced arrays
(`models/dalle.py:generate_images_cached_batched`), so the padded rows
cost compute but never another compile, and a request's RNG stream is
independent of which batch it lands in.

`warmup()` runs one dummy batch per rung at startup so the first real
request never pays compilation latency; compile-cache hits/misses are
counted into the shared metrics registry.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

from dalle_pytorch_tpu.obs.tracing import host_span


@dataclass
class SampleSpec:
    """One batch row: a tokenized prompt plus its sampling parameters.

    `top_k` follows the CLI/reference convention: the FRACTION of the
    vocabulary to drop (0.9 keeps the top 10%).

    `resume_tokens`/`resume_pos` carry a mid-decode resume prefix
    (decode-state migration / preemption): when `resume_pos > 0` and the
    engine supports resume, admission re-prefills the prefix in one
    teacher-forced dispatch and decode continues from `resume_pos`
    instead of position 0. Engines without resume support ignore the
    fields — decode restarts at 0, which regenerates the identical
    tokens ((seed, position)-keyed RNG), just paying the re-decode.
    """

    text_ids: np.ndarray  # [text_seq_len] int32
    seed: int = 0
    temperature: float = 1.0
    top_k: float = 0.9
    resume_tokens: Optional[np.ndarray] = None  # [resume_pos] int32
    resume_pos: int = 0


@dataclass
class EngineStats:
    compiled_shapes: Tuple[int, ...] = ()
    batches: int = 0
    rows_generated: int = 0
    rows_padded: int = 0
    # warmup dispatches count here ONLY (plus the compile hit/miss/seconds
    # metrics) so traffic stats stay pure request accounting
    warmup_batches: int = 0


class GenerationEngine:
    """Batched text→image generation over a fixed ladder of compiled shapes.

    Parameters
    ----------
    model, variables : the DALLE module and its checkpoint params.
    vae, vae_params : optional pixel decoder. A `DiscreteVAE` is fused into
        the sampler program (tokens AND pixels from one dispatch); any
        other object with a host-side `.decode(tokens)` is applied after
        sampling; None returns tokens only.
    batch_shapes : compiled batch sizes, ascending after dedup. Requests
        larger than the top rung are the batcher's problem (it never
        assembles more rows than `max_batch`).
    cond_scale : classifier-free guidance scale, engine-wide (a per-request
        scale would double the compiled-shape ladder; revisit if needed).
    clip, clip_params : optional CLIP reranker (`models/clip.py:rerank`).
    tokenizer : host-side tokenizer; required for `tokenize()` / reranking.
    registry : MetricsRegistry for compile/warmup counters.
    """

    def __init__(
        self,
        model,
        variables,
        vae=None,
        vae_params=None,
        batch_shapes: Sequence[int] = (1, 4, 8),
        cond_scale: float = 1.0,
        clip=None,
        clip_params=None,
        tokenizer=None,
        registry=None,
        cfg=None,
    ):
        assert batch_shapes, "need at least one compiled batch shape"
        self.model = model
        self.variables = variables
        self.vae = vae
        self.vae_params = vae_params
        self.batch_shapes = tuple(sorted(set(int(b) for b in batch_shapes)))
        assert all(b >= 1 for b in self.batch_shapes)
        self.max_batch = self.batch_shapes[-1]
        self.cond_scale = float(cond_scale)
        self.clip = clip
        self.clip_params = clip_params
        self.tokenizer = tokenizer
        self.cfg = cfg
        self._warm = set()
        self._lock = threading.Lock()  # one sampler dispatch at a time
        self.stats = EngineStats(compiled_shapes=())
        # device-telemetry seams (obs/vitals.py), both inert by default:
        # `vitals` is the dispatch clock the sampler thread reads (the
        # shared no-op singleton until an EngineVitals binds itself);
        # `cost_table` opts warmup into per-program cost capture (one
        # extra AOT compile per program) — attach BEFORE warmup()
        from dalle_pytorch_tpu.obs.vitals import NULL_VITALS

        self.vitals = NULL_VITALS
        self.cost_table = None
        # persistent compile cache (utils/compile_cache.py): when a
        # CompileCache is attached BEFORE warmup, every program in the
        # warmup ladder exports its AOT executable into the cache's
        # artifact store (sharing the cost table's one extra compile),
        # so the NEXT boot of this config is warm
        self.compile_cache = None
        # fault-injection seam (serving/faults.py): every dispatch calls
        # `_fault_point(program)`, a no-op until a test/chaos harness sets
        # a FaultInjector here — the injected failure then takes the SAME
        # recovery path (donated-state rebuild, batcher retry/fail-fast)
        # a real XLA error would
        self.faults = None
        if registry is None:
            from dalle_pytorch_tpu.training.metrics import MetricsRegistry

            registry = MetricsRegistry()
        self.registry = registry
        self._compile_miss = registry.counter(
            "dalle_serving_engine_compile_misses_total",
            "sampler dispatches that had to compile a new batch shape",
        )
        self._compile_hit = registry.counter(
            "dalle_serving_engine_compile_hits_total",
            "sampler dispatches served by an already-compiled batch shape",
        )
        self._compile_seconds = registry.histogram(
            "dalle_serving_engine_compile_seconds",
            "wall time of compiling (warmup) dispatches",
        )

    def _fault_point(self, name: str) -> None:
        """Dispatch-site hook for the fault injector (inert when none is
        attached). Sits INSIDE each dispatch's vitals bracket — and inside
        `_replace_state`'s try for the donated ops — so an injected fault
        is indistinguishable from a real dispatch failure downstream."""
        if self.faults is not None:
            self.faults.on_dispatch(name)

    # -------------------------------------------------------------- vitals

    def _capture_cost(self, name: str, fn, *args) -> None:
        """The warmup AOT ladder: lower + compile `fn(*args)` ONCE and
        feed every attached consumer — the `ProgramCostTable` records the
        XLA cost/memory analysis, the `CompileCache` exports the
        serialized executable as the warm-boot artifact. AOT lowering
        wraps the already-jitted model op in an outer `jax.jit` —
        params/state ride as REAL arguments, never closure constants, so
        the lowered HLO matches the dispatched program's traffic.
        Warmup-only by construction (every call site is gated on its
        `_warmup` flag): the `.compile()` here is one extra backend
        compile that must never land on the serving path (and is itself
        a persistent-cache hit on a warm boot). Failures are recorded on
        the consumers, never raised — a backend without cost analysis or
        executable serialization must not break warmup."""
        table, cache = self.cost_table, self.compile_cache
        need_cost = table is not None and not table.has(name)
        need_export = cache is not None and cache.wants(name)
        if not (need_cost or need_export):
            return
        import jax

        # mesh-sharded engines hand their device labels through so the
        # table can attribute per-partition cost where jax exposes it
        # (ProgramCostTable.add; global-row fallback otherwise)
        mesh = getattr(self, "mesh", None)
        devices = (
            [f"{d.platform}:{d.id}" for d in mesh.devices.flat]
            if mesh is not None else None
        )
        try:
            compiled = jax.jit(fn).lower(*args).compile()
        except Exception as exc:
            if need_cost:
                table.record_error(name, exc)
            if need_export:
                cache.record_error(name, exc)
            return
        if need_cost:
            try:
                table.add(name, compiled, devices=devices)
            except Exception as exc:
                table.record_error(name, exc)
        if need_export:
            cache.export(name, compiled)

    def program_ladder(self) -> Tuple[str, ...]:
        """Names of every program `warmup()` compiles — the fixed-shape
        contract surface. The boot fingerprint hashes this list, so an
        engine growing a program invalidates stale warm-cache claims."""
        return tuple(f"generate:{b}" for b in self.batch_shapes)

    def resume_fingerprint(self) -> str:
        """Build identity a decode-state checkpoint must match to resume
        here (serving/migrate.py): `utils/compile_cache.boot_fingerprint`
        over jax version / backend / model config / program ladder, plus
        the model's repr (directly-constructed engines carry cfg=None,
        and two different toy models must not cross-resume). Computed
        once — a checkpoint from any drifted build becomes a counted
        clean-restart, never a corrupt resume."""
        if getattr(self, "_resume_fingerprint", None) is None:
            import jax

            from dalle_pytorch_tpu.utils.compile_cache import boot_fingerprint

            self._resume_fingerprint = boot_fingerprint(
                backend=jax.default_backend(),
                model_config=self.cfg,
                programs=self.program_ladder(),
                extra={"model": repr(self.model)},
            )
        return self._resume_fingerprint

    def state_dump(self) -> dict:
        """Host-side engine state for `/debug/state` and stall reports.
        Lock-free reads of host counters — a stalled engine holds its
        dispatch lock, and the dump must still render."""
        return {
            "engine": type(self).__name__,
            "batch_shapes": list(self.batch_shapes),
            "compiled_shapes": list(self.stats.compiled_shapes),
            "batches": self.stats.batches,
            "rows_generated": self.stats.rows_generated,
            "warmup_batches": self.stats.warmup_batches,
        }

    # ------------------------------------------------------------- shapes

    def pick_shape(self, n: int) -> int:
        """Smallest compiled rung that fits n rows."""
        assert 1 <= n <= self.max_batch, (
            f"batch of {n} rows exceeds the engine's max shape "
            f"{self.max_batch}; the batcher must cap at max_batch"
        )
        for b in self.batch_shapes:
            if n <= b:
                return b
        return self.max_batch  # unreachable given the assert

    @property
    def image_seq_len(self) -> int:
        return self.model.image_seq_len

    def _keep_k(self, top_k: float) -> int:
        """Fractional drop threshold -> per-row keep count, matching
        `ops/sampling.py:top_k_filter` exactly so engine results agree with
        the static-parameter sampler's filtering rule."""
        v = self.model.total_tokens
        frac = min(max(float(top_k), 0.0), 1.0)
        return max(int((1.0 - frac) * v), 1)

    # ----------------------------------------------------------- generate

    def tokenize(self, prompt: str) -> np.ndarray:
        assert self.tokenizer is not None, "engine built without a tokenizer"
        ids = self.tokenizer.tokenize(
            prompt, self.model.text_seq_len, truncate_text=True
        )
        return np.asarray(ids[0], dtype=np.int32)

    def warmup(self, shapes: Optional[Sequence[int]] = None) -> None:
        """Compile every batch rung up front (one dummy batch each).

        Warmup dispatches are tagged so they count only toward the compile
        metrics (hits/misses/seconds) and `stats.warmup_batches` — never
        toward `batches`/`rows_generated`/`rows_padded`, which dashboards
        read as real traffic."""
        text_seq = self.model.text_seq_len
        for b in shapes or self.batch_shapes:
            dummy = [
                SampleSpec(np.zeros(text_seq, np.int32), seed=i)
                for i in range(b)
            ]
            self.generate(dummy, _warmup=True)

    def generate(self, specs: Sequence[SampleSpec], _warmup: bool = False):
        """Run one micro-batch. Returns (tokens [n, image_seq_len] np.int32,
        pixels [n, H, W, 3] float in [0, 1] or None)."""
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import generate_images_cached_batched
        from dalle_pytorch_tpu.models.dvae import DiscreteVAE

        n = len(specs)
        shape = self.pick_shape(n)
        pad = shape - n
        rows = list(specs) + [specs[0]] * pad

        text = np.stack([np.asarray(s.text_ids, np.int32) for s in rows])
        assert text.shape == (shape, self.model.text_seq_len), (
            f"prompt rows must be [{self.model.text_seq_len}] token ids, "
            f"got batch {text.shape}"
        )
        seeds = np.asarray([int(s.seed) & 0x7FFFFFFF for s in rows], np.int32)
        temps = np.asarray([s.temperature for s in rows], np.float32)
        keep = np.asarray([self._keep_k(s.top_k) for s in rows], np.int32)

        fused = isinstance(self.vae, DiscreteVAE)
        prog = f"generate:{shape}"
        with self._lock:
            is_warm = shape in self._warm
            (self._compile_hit if is_warm else self._compile_miss).inc()
            t0 = time.perf_counter()
            self.vitals.dispatch_begin(prog)
            try:
                self._fault_point(prog)
                out = generate_images_cached_batched(
                    self.model, self.variables, jnp.asarray(text),
                    seeds, temps, keep,
                    cond_scale=self.cond_scale,
                    vae=self.vae if fused else None,
                    vae_params=self.vae_params if fused else None,
                )
                if fused:
                    toks, pixels = out
                    toks = np.asarray(toks)
                    pixels = np.asarray(pixels) * 0.5 + 0.5  # un-normalize
                else:
                    toks = np.asarray(out)
                    pixels = None
            finally:
                wall = time.perf_counter() - t0
                self.vitals.dispatch_end(prog, wall)
            if is_warm and self.cost_table is not None:
                # the np.asarray above synced the dispatch, so this wall
                # is real execution time — MFU-grade. Compiling (cold)
                # dispatches are excluded: their wall is compile latency.
                self.cost_table.record_wall(prog, wall)
            if not is_warm:
                self._compile_seconds.observe(time.perf_counter() - t0)
                self._warm.add(shape)
                self.stats.compiled_shapes = tuple(sorted(self._warm))
            if _warmup:
                # AFTER the dispatch, never before: lowering the sampler
                # inside an outer trace before its closure cache is
                # populated would bake tracers into `_jitted_sampler`'s
                # lru_cache (builders materialize constants at
                # closure-build time)
                self._capture_cost(
                    prog,
                    lambda v, vp, t, s, tm, k: (
                        generate_images_cached_batched(
                            self.model, v, t, s, tm, k,
                            cond_scale=self.cond_scale,
                            vae=self.vae if fused else None, vae_params=vp,
                        )
                    ),
                    self.variables, self.vae_params if fused else None,
                    jnp.asarray(text), seeds, temps, keep,
                )
                self.stats.warmup_batches += 1
            else:
                self.stats.batches += 1
                self.stats.rows_generated += n
                self.stats.rows_padded += pad

        toks = toks[:n]
        if pixels is None and self.vae is not None:
            # pretrained wrappers decode host-side to [0, 1] already;
            # decode only the real rows — padding never leaves the sampler
            pixels = np.asarray(self.vae.decode(toks))
        else:
            pixels = None if pixels is None else pixels[:n]
        if pixels is not None:
            pixels = np.clip(pixels, 0.0, 1.0)
        return toks, pixels

    # ------------------------------------------------------------- rerank

    def rerank(self, prompt: str, images: np.ndarray):
        """Sort one request's images best-first by CLIP similarity.

        Returns (sorted_images, scores, order) where `order` maps the
        sorted position back to the original row index — callers carrying
        parallel arrays (tokens, seeds) must apply it too. Identity with
        zero scores when no CLIP checkpoint is loaded.
        """
        if self.clip is None:
            return (
                images,
                np.zeros(len(images), np.float32),
                np.arange(len(images)),
            )
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.clip import rerank as clip_rerank

        assert self.tokenizer is not None, "reranking needs a tokenizer"
        # mismatches would fail silently (XLA gather clamps OOB indices)
        assert images.shape[1] == self.clip.visual_image_size, (
            f"CLIP checkpoint expects {self.clip.visual_image_size}px images "
            f"but the VAE decodes {images.shape[1]}px"
        )
        assert self.tokenizer.vocab_size <= self.clip.num_text_tokens, (
            f"tokenizer vocab {self.tokenizer.vocab_size} exceeds CLIP "
            f"num_text_tokens {self.clip.num_text_tokens}"
        )
        clip_ids = self.tokenizer.tokenize(
            prompt, self.clip.text_seq_len, truncate_text=True
        )
        sorted_imgs, scores, order = clip_rerank(
            self.clip,
            {"params": self.clip_params},
            jnp.asarray(clip_ids),
            jnp.asarray(images),
            text_mask=jnp.asarray(clip_ids != 0),
        )
        return np.asarray(sorted_imgs), np.asarray(scores), np.asarray(order)


def _pack_prefill_rows(rows, keep_k_of):
    """Host-side packing of (slot, SampleSpec) pairs into the batched
    prefill's dispatch arrays. Pure request-dataclass reads — deliberately
    outside the hotloop-marked engine methods, which must stay free of
    anything TL002 could mistake for a device sync."""
    texts = np.stack([np.asarray(spec.text_ids, np.int32) for _, spec in rows])
    slots = np.asarray([s for s, _ in rows], np.int32)
    seeds = np.asarray(
        [int(spec.seed) & 0x7FFFFFFF for _, spec in rows], np.int32
    )
    temps = np.asarray([spec.temperature for _, spec in rows], np.float32)
    keep = np.asarray([keep_k_of(spec.top_k) for _, spec in rows], np.int32)
    return texts, slots, seeds, temps, keep


class SlotAllocator:
    """Host-side allocator for the continuous engine's fixed cache slots.

    Slots are just integers [0, n_slots); the decode program's batch rows.
    `alloc` hands out the lowest free slot (deterministic, test-friendly)
    and never aliases: a slot stays owned until `free`d. Exhaustion returns
    None — the batcher keeps the request queued until a retirement frees a
    slot. Not thread-safe by itself; the batcher worker is the only caller.
    """

    def __init__(self, n_slots: int):
        assert n_slots >= 1
        self.n_slots = int(n_slots)
        self._free = sorted(range(self.n_slots), reverse=True)
        self._in_use: set = set()

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.pop()
        self._in_use.add(slot)
        return slot

    def free(self, slot: int) -> None:
        assert slot in self._in_use, f"slot {slot} is not allocated"
        self._in_use.remove(slot)
        self._free.append(slot)
        self._free.sort(reverse=True)

    @property
    def n_active(self) -> int:
        return len(self._in_use)

    @property
    def n_free(self) -> int:
        return len(self._free)


class ContinuousEngine(GenerationEngine):
    """Continuous-batching decode: token-boundary admission over cache slots.

    Where `GenerationEngine.generate` runs a whole `image_seq_len` decode
    scan per micro-batch (a request arriving just after a flush waits an
    entire pass for its first token), this engine keeps ONE persistent
    decode state of `max_batch` cache slots and advances every live slot by
    `chunk_tokens` per jitted dispatch. The batcher admits prompts into
    free slots (one prefill dispatch each) and retires finished rows at
    chunk boundaries, so occupancy backfills mid-flight and time-to-first-
    token is bounded by ~one chunk instead of up to two full passes.

    Fixed-shape discipline is preserved: exactly four compiled programs —
    batched prefill (batch `prefill_batch`, slot indices traced), chunk
    step (batch `max_batch`), slot release, pixel decode (batch
    `max_batch`) — regardless of load. `chunk_tokens` is the latency/throughput knob: smaller chunks
    admit and retire sooner (lower TTFT) but pay more host round trips per
    image. `prefill_batch` is the admission-amortization knob: R pending
    requests at a chunk boundary cost ceil(R / prefill_batch) prefill
    dispatches (padded by repeating a real row — same trade as the
    micro-batch engine's padded rungs) instead of R batch-1 dispatches.

    Classifier-free guidance is engine-wide OFF here (cond_scale=1): a
    guided continuous batch needs a paired null-stream slot per row —
    doubling the decode program — so guided serving stays on the
    micro-batch engine for now.
    """

    def __init__(
        self,
        model,
        variables,
        vae=None,
        vae_params=None,
        max_batch: int = 8,
        chunk_tokens: int = 4,
        prefill_batch: int = 4,
        cond_scale: float = 1.0,
        clip=None,
        clip_params=None,
        tokenizer=None,
        registry=None,
        cfg=None,
        resume_enabled: bool = False,
        preview_enabled: bool = False,
        kv_dtype=None,
        decode_sparsity: str = "causal",
    ):
        assert float(cond_scale) == 1.0, (
            "ContinuousEngine does not support classifier-free guidance yet "
            "(a per-slot null stream would double the decode program); use "
            "the micro-batch GenerationEngine for cond_scale != 1"
        )
        assert int(chunk_tokens) >= 1
        assert decode_sparsity in ("causal", "policy"), (
            f"unknown decode_sparsity {decode_sparsity!r}; "
            "use 'causal' (dense-causal flash, the bit-identity default) "
            "or 'policy' (block-sparse flash from the model's static "
            "attention layouts)"
        )
        self.decode_sparsity = str(decode_sparsity)
        # int8 KV cache (--kv_dtype int8): clone the model so every slot-op
        # builder (they key the jit cache on the model) sees the quantized
        # cache layout; None keeps the bit-identical default path
        if kv_dtype is not None and getattr(model, "kv_dtype", None) is None:
            model = model.clone(kv_dtype=str(kv_dtype))
        # block-sparse decode (--decode_sparsity policy): bake the tile
        # width into the model clone (same builder-cache reasoning as
        # kv_dtype — and the boot fingerprint hashes the model repr, so a
        # sparse boot never resumes a causal compile cache); the bitmaps
        # themselves stay TRACED data, built per dispatch by the policy
        if (
            self.decode_sparsity == "policy"
            and getattr(model, "decode_sparse_block", None) is None
        ):
            from dalle_pytorch_tpu.models.attention import (
                DECODE_SPARSE_BLOCK,
            )

            model = model.clone(decode_sparse_block=DECODE_SPARSE_BLOCK)
        super().__init__(
            model=model,
            variables=variables,
            vae=vae,
            vae_params=vae_params,
            batch_shapes=(int(max_batch),),
            cond_scale=1.0,
            clip=clip,
            clip_params=clip_params,
            tokenizer=tokenizer,
            registry=registry,
            cfg=cfg,
        )
        # decode-state resume (serving/migrate.py): one extra compiled
        # program (teacher-forced re-prefill of prompt + generated
        # prefix) that admits a migrated/preempted row at its OWN
        # position instead of 0. Opt-in: the ladder, warmup and boot
        # fingerprint grow the `resume` program only when enabled.
        self.resume_enabled = bool(resume_enabled)
        # progressive previews (serving/streaming.py): one extra compiled
        # fill+decode program — undecoded grid positions filled with the
        # mean-codebook token, then the standard pixel decode — shared by
        # every streaming request. Opt-in like `resume`: the ladder,
        # warmup and boot fingerprint grow the `preview` program only
        # when enabled (serving boots enable it by default).
        self.preview_enabled = bool(preview_enabled)
        self.chunk_tokens = int(chunk_tokens)
        # admission never spans more slots than exist; 1 degrades to the
        # per-row admission of PR 2
        self.prefill_batch = max(1, min(int(prefill_batch), self.max_batch))
        #: host-side tile-liveness policy (None on the causal path): turns
        #: the model's static attention layouts into per-slot KV-tile
        #: bitmaps the chunk/prefill dispatches carry as traced data
        self._sparsity = None
        if self.decode_sparsity == "policy":
            from dalle_pytorch_tpu.serving.sparsity import (
                DecodeSparsityPolicy,
            )

            self._sparsity = DecodeSparsityPolicy(
                self.model, self.chunk_tokens, self.max_batch
            )
        self._state = self._fresh_state()
        self._m_slots = self.registry.gauge(
            "dalle_serving_slots_active",
            "continuous-engine cache slots currently decoding",
        )
        self._m_chunks = self.registry.counter(
            "dalle_serving_chunks_total",
            "decode chunk dispatches by the continuous engine",
        )
        # the micro-batcher observes the same series per flushed batch;
        # here a "batch" is one chunk dispatch, so mean occupancy is
        # _sum / _count on either engine
        self._m_occupancy = self.registry.histogram(
            "dalle_serving_batch_occupancy_rows",
            "live rows per decode chunk dispatch (continuous engine)",
            buckets=tuple(
                float(b) for b in range(1, min(self.max_batch, 32) + 1)
            ),
        )
        self._m_prefills = self.registry.counter(
            "dalle_serving_prefills_total",
            "prompts prefilled into cache slots",
        )
        self._m_prefill_dispatches = self.registry.counter(
            "dalle_serving_prefill_dispatches_total",
            "batched prefill dispatches (each admits up to prefill_batch "
            "rows in one fixed-shape program)",
        )
        self._m_kv_bytes_slot = self.registry.gauge(
            "dalle_serving_kv_bytes_per_slot",
            "HBM bytes of KV cache (K/V + quantization scales) backing one "
            "decode slot — pool-sizing honesty: pages alone undercount the "
            "capacity win when --kv_dtype int8 shrinks each page",
        )
        self._m_kv_bytes_slot.set(self.kv_bytes_per_slot())
        self._m_kv_tiles_read = self.registry.counter(
            "dalle_serving_kv_tiles_read_total",
            "KV tiles the block-sparse decode kernel read (per chunk "
            "dispatch, summed over live rows and layers; zero on "
            "--decode_sparsity causal)",
        )
        self._m_kv_tiles_skipped = self.registry.counter(
            "dalle_serving_kv_tiles_skipped_total",
            "KV tiles the sparsity policy skipped that the length skip "
            "alone would have read — the policy's own DMA/compute savings",
        )
        self._decode_pixels_jit = None
        self._preview_jit = None
        self._preview_fill = None
        #: monotonic chunk-dispatch index (non-warmup), read by the
        #: batcher as span metadata so a trace's chunk spans can be lined
        #: up against engine-side dispatch accounting
        self.chunk_index = 0

    # --------------------------------------------------------- slot ops
    # All device work is serialized under the inherited engine lock; the
    # continuous batcher's single worker thread is the only caller.

    def _fresh_state(self):
        """Clean empty slot state — the subclass hook the paged engine
        overrides (rebuilding its host-side page tables alongside)."""
        from dalle_pytorch_tpu.models.dalle import init_slot_state

        # host mirrors of (img_pos, active), updated at every admission/
        # chunk/release: the sparsity policy derives each dispatch's tile
        # bitmaps from them without an extra device sync (the paged
        # subclass keeps the same pair for its allocator)
        self._host_pos = np.zeros(self.max_batch, np.int64)
        self._host_active = np.zeros(self.max_batch, bool)
        return init_slot_state(self.model, self.max_batch)

    def _kv_cache_bytes(self) -> int:
        """Total bytes of the K/V leaves (values + quantization scales)
        in the live decode state."""
        from dalle_pytorch_tpu.models.decode_cache import kv_bytes

        return int(kv_bytes(self._state["cache"]))

    def kv_bytes_per_slot(self) -> int:
        """K/V (+ scale) bytes backing ONE decode slot. int8 pages cut
        this ~2x vs fp32 (the per-position fp32 scale adds 4 bytes per
        dim_head values), which is the slots-per-HBM-byte win the
        `dalle_serving_kv_bytes_per_slot` gauge makes visible."""
        return self._kv_cache_bytes() // self.max_batch

    def _replace_state(self, op, fault_tag: Optional[str] = None) -> None:
        """Run one state-transforming dispatch. The slot ops DONATE the
        state buffers (models/dalle.py), so on failure the old state is
        unusable — rebuild a clean empty one rather than bricking the
        engine (the batcher fails or retries the in-flight requests
        either way). `fault_tag` names the dispatch for the fault-
        injection seam; injected faults raise inside this try so they
        exercise the SAME rebuild path. Caller holds the lock."""
        try:
            if fault_tag is not None:
                self._fault_point(fault_tag)
            self._state = op(self._state)
        except BaseException:
            self._state = self._fresh_state()
            raise

    def _prefill_bitmap_kw(self) -> dict:
        """`block_bitmap=` kwarg for one prefill-shaped dispatch (empty on
        the causal path) — shared by the slotted/paged dispatch seams and
        their warmup cost captures so all four lower the same program."""
        if self._sparsity is None:
            return {}
        return {
            "block_bitmap": self._sparsity.prefill_bitmaps(
                self.prefill_batch
            )
        }

    def _chunk_bitmap_kw(self) -> dict:
        """`block_bitmap=` kwarg for one chunk dispatch, derived from the
        host position/liveness mirrors as of the chunk start."""
        if self._sparsity is None:
            return {}
        return {
            "block_bitmap": self._sparsity.chunk_bitmaps(
                self._host_pos, self._host_active
            )
        }

    def _prefill_op(self, s, texts, slots, seeds, temps, keep):
        """One batched-prefill dispatch over state `s` (subclass hook —
        the sharded engine runs its sharding-pinned program here)."""
        from dalle_pytorch_tpu.models.dalle import prefill_into_slots

        return prefill_into_slots(
            self.model, self.variables, s, texts, slots, seeds, temps,
            keep, **self._prefill_bitmap_kw(),
        )

    def _release_op(self, s, mask):
        """One slot-release dispatch (same subclass seam)."""
        from dalle_pytorch_tpu.models.dalle import release_slots

        return release_slots(self.model, s, mask)

    def prefill_slots(  # tracelint: hotloop
        self,
        assignments: Sequence[Tuple[int, SampleSpec]],
        _warmup: bool = False,
    ) -> None:
        """Admit up to `prefill_batch` (slot, prompt) pairs in ONE
        fixed-shape dispatch. Short batches pad by repeating the first
        pair — the duplicate rows re-write the same slot with identical
        content (see `models/dalle.py:prefill_into_slots`), so every
        admission, single or batched, runs the SAME compiled program."""
        n = len(assignments)
        assert 1 <= n <= self.prefill_batch, (
            f"{n} assignments exceed prefill_batch={self.prefill_batch}; "
            "the batcher must split admission waves"
        )
        rows = list(assignments) + [assignments[0]] * (self.prefill_batch - n)
        texts, slots, seeds, temps, keep = _pack_prefill_rows(
            rows, self._keep_k
        )
        assert texts.shape == (self.prefill_batch, self.model.text_seq_len), (
            f"prompt rows must be [{self.model.text_seq_len}] token ids, "
            f"got batch {texts.shape}"
        )
        with self._lock:
            t0 = time.perf_counter()
            self.vitals.dispatch_begin("prefill")
            try:
                with host_span("serve.prefill", rows=n):
                    self._replace_state(lambda s: self._prefill_op(
                        s, texts, slots, seeds, temps, keep,
                    ), fault_tag="prefill")
            finally:
                wall = time.perf_counter() - t0
                self.vitals.dispatch_end("prefill", wall)
            for slot, _spec in assignments:
                self._host_pos[int(slot)] = 0
                self._host_active[int(slot)] = True
            if _warmup:
                # after the dispatch (see GenerationEngine.generate: a
                # pre-dispatch lowering would poison the sampler cache)
                from dalle_pytorch_tpu.models.dalle import prefill_into_slots

                spkw = self._prefill_bitmap_kw()
                self._capture_cost(
                    "prefill",
                    lambda v, s, t, sl, se, tm, k: prefill_into_slots(
                        self.model, v, s, t, sl, se, tm, k, **spkw,
                    ),
                    self.variables, self._state, texts, slots, seeds,
                    temps, keep,
                )
            if not _warmup:
                if self.cost_table is not None:
                    # async dispatch: this wall is host-side only, kept
                    # for the watchdog baseline but never exported as MFU
                    self.cost_table.record_wall("prefill", wall, synced=False)
                self._m_prefills.inc(n)
                self._m_prefill_dispatches.inc()

    def prefill_slot(  # tracelint: hotloop
        self, slot: int, spec: SampleSpec, _warmup: bool = False
    ) -> None:
        """Admit one prompt into `slot` — a 1-row `prefill_slots` wave
        (padded to the fixed prefill shape; no extra compiled program)."""
        self.prefill_slots([(slot, spec)], _warmup=_warmup)

    # ---------------------------------------------------- mid-decode resume

    @property
    def supports_resume(self) -> bool:
        """True when `resume_slots` may be called (the batcher's gate:
        without it, resume-prefixed specs fall back to a position-0
        prefill — bit-identical, just re-decoded)."""
        return self.resume_enabled

    def _pack_resume_rows(self, rows):
        """Resume-prefix arrays for one padded wave: [R, image_seq_len]
        token buffer (zeros beyond each prefix) + [R] positions."""
        img_tokens = np.zeros(
            (len(rows), self.image_seq_len), np.int32
        )
        img_pos = np.zeros(len(rows), np.int32)
        for r, (_slot, spec) in enumerate(rows):
            k = min(
                max(0, int(getattr(spec, "resume_pos", 0) or 0)),
                self.image_seq_len - 1,
            )
            toks = getattr(spec, "resume_tokens", None)
            if toks is None:
                k = 0
            else:
                toks = np.asarray(toks, np.int32)
                k = min(k, len(toks))
                img_tokens[r, :k] = toks[:k]
            img_pos[r] = k
        return img_tokens, img_pos

    def _resume_op(self, s, texts, img_tokens, img_pos, slots, seeds,
                   temps, keep):
        """One teacher-forced resume dispatch (subclass seam, like
        `_prefill_op`)."""
        from dalle_pytorch_tpu.models.dalle import resume_into_slots

        return resume_into_slots(
            self.model, self.variables, s, texts, img_tokens, img_pos,
            slots, seeds, temps, keep,
        )

    def resume_slots(  # tracelint: hotloop
        self,
        assignments: Sequence[Tuple[int, SampleSpec]],
        _warmup: bool = False,
    ) -> None:
        """Admit up to `prefill_batch` mid-decode rows — specs carrying
        `resume_tokens`/`resume_pos` — in ONE teacher-forced re-prefill
        dispatch: decode continues from each row's own position instead
        of 0 (`models/dalle.py:resume_into_slots`). Short waves pad by
        repeating the first pair, exactly like `prefill_slots`."""
        assert self.supports_resume, (
            "resume_slots on an engine built without resume_enabled — "
            "the program is not in the warmup ladder and would "
            "cold-compile mid-traffic"
        )
        n = len(assignments)
        assert 1 <= n <= self.prefill_batch, (
            f"{n} assignments exceed prefill_batch={self.prefill_batch}; "
            "the batcher must split admission waves"
        )
        rows = list(assignments) + [assignments[0]] * (self.prefill_batch - n)
        texts, slots, seeds, temps, keep = _pack_prefill_rows(
            rows, self._keep_k
        )
        img_tokens, img_pos = self._pack_resume_rows(rows)
        with self._lock:
            t0 = time.perf_counter()
            self.vitals.dispatch_begin("resume")
            try:
                with host_span("serve.resume", rows=n):
                    self._replace_state(lambda s: self._resume_op(
                        s, texts, img_tokens, img_pos, slots, seeds, temps,
                        keep,
                    ), fault_tag="resume")
            finally:
                wall = time.perf_counter() - t0
                self.vitals.dispatch_end("resume", wall)
            for (slot, _spec), p in zip(assignments, img_pos[:n]):
                self._host_pos[int(slot)] = int(p)
                self._host_active[int(slot)] = True
            if _warmup:
                from dalle_pytorch_tpu.models.dalle import resume_into_slots

                self._capture_cost(
                    "resume",
                    lambda v, s, t, it, ip, sl, se, tm, k: resume_into_slots(
                        self.model, v, s, t, it, ip, sl, se, tm, k,
                    ),
                    self.variables, self._state, texts, img_tokens,
                    img_pos, slots, seeds, temps, keep,
                )
            if not _warmup:
                if self.cost_table is not None:
                    self.cost_table.record_wall("resume", wall, synced=False)
                self._m_prefills.inc(n)
                self._m_prefill_dispatches.inc()

    def _pre_chunk(self) -> None:
        """Subclass hook before the chunk dispatch (the paged engine tops
        up decode pages here)."""

    def _chunk_op(self, s):
        from dalle_pytorch_tpu.models.dalle import decode_image_chunk

        return decode_image_chunk(
            self.model, self.variables, s, self.chunk_tokens,
            **self._chunk_bitmap_kw(),
        )

    def _post_chunk(self, pos, act) -> None:
        """Mirror the chunk snapshot host-side — the sparsity policy (and
        the paged allocator, which extends this) read positions without
        another device sync."""
        self._host_pos[: len(pos)] = pos
        self._host_active[: len(act)] = np.asarray(act, bool)

    def step_chunk(self, _warmup: bool = False):  # tracelint: hotloop
        """Advance all live slots by `chunk_tokens`; returns the post-chunk
        (img_pos, active) host snapshot the batcher retires against."""
        import jax

        self._pre_chunk()
        with self._lock:
            t0 = time.perf_counter()
            self.vitals.dispatch_begin("chunk")
            # live rows at the chunk's start: the span's `rows=` and the
            # occupancy histogram read the same number
            rows = int(np.count_nonzero(self._host_active))
            try:
                with host_span("serve.chunk", rows=rows):
                    self._replace_state(self._chunk_op, fault_tag="chunk")
                if not _warmup:
                    self._m_occupancy.observe(rows)
                    self._m_chunks.inc()
                    self.chunk_index += 1
                    self.stats.batches += 1
                    if self._sparsity is not None:
                        # mirrors are still the chunk-START snapshot here
                        # (post_chunk runs below), i.e. exactly what the
                        # dispatch's bitmap was derived from
                        read, skipped = self._sparsity.count_tiles(
                            self._host_pos, self._host_active
                        )
                        self._m_kv_tiles_read.inc(read)
                        self._m_kv_tiles_skipped.inc(skipped)
                # the chunk boundary IS the designed sync point: retirement
                # decisions need the positions on the host, and fusing both
                # small arrays into one transfer keeps it to a single round trip
                pos, act = jax.device_get(  # tracelint: disable=TL002 -- chunk-boundary snapshot is the one designed sync of the decode loop (single fused transfer)
                    (self._state["img_pos"], self._state["active"])
                )
            finally:
                wall = time.perf_counter() - t0
                self.vitals.dispatch_end("chunk", wall)
            if _warmup:
                # after the dispatch (see GenerationEngine.generate: a
                # pre-dispatch lowering would poison the sampler cache)
                self._capture_chunk_cost()
            elif self.cost_table is not None:
                # the device_get above synced the chunk program, so this
                # wall is MFU-grade execution time
                self.cost_table.record_wall("chunk", wall)
        self._post_chunk(pos, act)
        return pos, act

    def _capture_chunk_cost(self) -> None:
        """Warmup-time cost capture of the chunk program (subclass hook —
        the paged engine lowers its paged variant). Caller holds the
        lock."""
        from dalle_pytorch_tpu.models.dalle import decode_image_chunk

        spkw = self._chunk_bitmap_kw()
        self._capture_cost(
            "chunk",
            lambda v, s: decode_image_chunk(
                self.model, v, s, self.chunk_tokens, **spkw,
            ),
            self.variables, self._state,
        )

    def _read_token_rows(self, slots: Sequence[int]) -> np.ndarray:  # tracelint: hotloop
        """Host copy of `slots`' token rows — the one transfer shared by
        harvest and the preemption snapshot."""
        import jax

        with self._lock:
            t0 = time.perf_counter()
            self.vitals.dispatch_begin("harvest")
            try:
                self._fault_point("harvest")
                # one explicit fixed-shape transfer of the whole token buffer,
                # sliced on the host: a device-side gather of just the finished
                # rows would compile one program PER finished-count (1..max_batch)
                # and break the exactly-the-warmup-set compile discipline that
                # tests/test_continuous.py pins with assert_no_recompiles
                with host_span("serve.harvest", rows=len(slots)):
                    toks = jax.device_get(self._state["img_tokens"])  # tracelint: disable=TL002 -- retirement harvest is a designed sync; fixed-shape transfer beats a per-count compiled gather
            finally:
                self.vitals.dispatch_end(
                    "harvest", time.perf_counter() - t0
                )
        return toks[list(slots)].astype(np.int32)

    def harvest(self, slots: Sequence[int]) -> np.ndarray:
        """Finished slots' tokens [len(slots), image_seq_len] (host copy)."""
        toks = self._read_token_rows(slots)
        with self._lock:
            self.stats.rows_generated += len(list(slots))
        return toks

    def snapshot_rows(self, slots: Sequence[int]) -> np.ndarray:
        """`harvest` minus the traffic accounting: the preemption path's
        host copy of generated-so-far tokens. A preempted row is NOT a
        generated row — it will decode again from position 0 on resume —
        so this must not move `rows_generated` (dashboards read that as
        completed work)."""
        return self._read_token_rows(slots)

    def release(self, slots: Sequence[int]) -> None:  # tracelint: hotloop
        """Deactivate `slots` so the chunk step stops touching them — after
        harvest, or wholesale on an error reset (which must not count
        toward `rows_generated`; only harvests do)."""
        mask = np.zeros(self.max_batch, bool)
        mask[list(slots)] = True
        with self._lock:
            t0 = time.perf_counter()
            self.vitals.dispatch_begin("release")
            try:
                self._replace_state(
                    lambda s: self._release_op(s, mask), fault_tag="release"
                )
            finally:
                self.vitals.dispatch_end(
                    "release", time.perf_counter() - t0
                )
            self._host_active[mask] = False
            self._host_pos[mask] = 0

    def decode_pixels(self, tokens: np.ndarray) -> Optional[np.ndarray]:  # tracelint: hotloop
        """Pixels [n, H, W, 3] in [0, 1] for harvested token rows, via ONE
        compiled shape (pad to max_batch, slice) — or None without a VAE."""
        if self.vae is None:
            return None
        from dalle_pytorch_tpu.models.dvae import DiscreteVAE

        n = len(tokens)
        if not isinstance(self.vae, DiscreteVAE):
            # tracelint: disable=TL002 -- pretrained-wrapper decode is host-side by contract; its output leaves the device here by design
            return np.clip(np.asarray(self.vae.decode(tokens)), 0.0, 1.0)
        import jax
        import jax.numpy as jnp

        if self._decode_pixels_jit is None:
            vae, vae_params = self.vae, self.vae_params
            self._decode_pixels_jit = jax.jit(
                lambda t: vae.apply(
                    {"params": vae_params}, t, method=DiscreteVAE.decode
                )
            )
        pad = self.max_batch - (n % self.max_batch or self.max_batch)
        padded = np.concatenate(
            [tokens, np.zeros((pad, tokens.shape[1]), np.int32)]
        )
        outs = []
        with self._lock:
            t0 = time.perf_counter()
            self.vitals.dispatch_begin("decode_pixels")
            try:
                self._fault_point("decode_pixels")
                for i in range(0, len(padded), self.max_batch):
                    outs.append(
                        np.asarray(  # tracelint: disable=TL002 -- pixel harvest is the terminal sync of the retire path; rows leave the device here by design
                            self._decode_pixels_jit(
                                jnp.asarray(padded[i : i + self.max_batch])
                            )
                        )
                    )
            finally:
                wall = time.perf_counter() - t0
                self.vitals.dispatch_end("decode_pixels", wall)
            if self.cost_table is not None and len(padded) == self.max_batch:
                # np.asarray synced; single-dispatch calls only, so the
                # wall maps to ONE program execution
                self.cost_table.record_wall("decode_pixels", wall)
        pixels = np.concatenate(outs)[:n] * 0.5 + 0.5
        return np.clip(pixels, 0.0, 1.0)

    # ---------------------------------------------------------- previews

    def preview_fill_token(self) -> int:
        """Codebook index used to fill undecoded grid positions in a
        progressive preview: the entry nearest the mean codebook vector
        (a neutral canvas rather than whatever index 0 happens to look
        like). Host-side, computed once; falls back to 0 when the
        codebook is not readable (pretrained wrappers)."""
        if self._preview_fill is None:
            tok = 0
            try:
                emb = np.asarray(
                    self.vae_params["codebook"]["embedding"], np.float32
                )
                tok = int(np.argmin(
                    np.linalg.norm(emb - emb.mean(axis=0), axis=-1)
                ))
            except Exception:
                pass
            self._preview_fill = tok
        return self._preview_fill

    def _preview_fn(self):
        """Body of the fill+decode program: mask undecoded positions,
        fill with the mean-codebook token, run the standard VAE decode —
        fused so a streaming preview wave pays ONE dispatch (the
        fused-dispatch pattern of the pixel-decode program)."""
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dvae import DiscreteVAE

        vae, vae_params = self.vae, self.vae_params
        fill = self.preview_fill_token()
        seq = self.image_seq_len

        def fn(toks, pos):
            mask = jnp.arange(seq)[None, :] < pos[:, None]
            filled = jnp.where(mask, toks, jnp.int32(fill))
            return vae.apply(
                {"params": vae_params}, filled, method=DiscreteVAE.decode
            )

        return fn

    def preview_pixels(  # tracelint: hotloop
        self, tokens: np.ndarray, positions: np.ndarray
    ) -> Optional[np.ndarray]:
        """Progressive-preview pixels [n, H, W, 3] in [0, 1] for partial
        token rows (`snapshot_rows` output) with per-row decode
        positions: undecoded grid positions are filled with the mean-
        codebook token and the whole grid decodes through ONE compiled
        fill+decode shape (pad to max_batch, slice) shared by every
        streaming request — or None without a VAE. The program must be
        warmed (`preview_enabled`) before serving traffic reaches it."""
        if self.vae is None:
            return None
        from dalle_pytorch_tpu.models.dvae import DiscreteVAE

        tokens = np.asarray(tokens, np.int32)
        positions = np.asarray(positions, np.int32)
        n = len(tokens)
        if not isinstance(self.vae, DiscreteVAE):
            # pretrained wrappers decode host-side; fill host-side too
            mask = np.arange(tokens.shape[1])[None, :] < positions[:, None]
            filled = np.where(
                mask, tokens, np.int32(self.preview_fill_token())
            ).astype(np.int32)
            # tracelint: disable=TL002 -- pretrained-wrapper decode is host-side by contract; its output leaves the device here by design
            return np.clip(np.asarray(self.vae.decode(filled)), 0.0, 1.0)
        import jax
        import jax.numpy as jnp

        if self._preview_jit is None:
            self._preview_jit = jax.jit(self._preview_fn())
        pad = self.max_batch - (n % self.max_batch or self.max_batch)
        ptoks = np.concatenate(
            [tokens, np.zeros((pad, tokens.shape[1]), np.int32)]
        )
        ppos = np.concatenate([positions, np.zeros(pad, np.int32)])
        outs = []
        with self._lock:
            t0 = time.perf_counter()
            self.vitals.dispatch_begin("preview")
            try:
                self._fault_point("preview")
                for i in range(0, len(ptoks), self.max_batch):
                    outs.append(
                        np.asarray(  # tracelint: disable=TL002 -- preview pixels ship as a host-side stream event; rows leave the device here by design
                            self._preview_jit(
                                jnp.asarray(ptoks[i : i + self.max_batch]),
                                jnp.asarray(ppos[i : i + self.max_batch]),
                            )
                        )
                    )
            finally:
                wall = time.perf_counter() - t0
                self.vitals.dispatch_end("preview", wall)
            if self.cost_table is not None and len(ptoks) == self.max_batch:
                # np.asarray synced; single-dispatch calls only, so the
                # wall maps to ONE program execution
                self.cost_table.record_wall("preview", wall)
        pixels = np.concatenate(outs)[:n] * 0.5 + 0.5
        return np.clip(pixels, 0.0, 1.0)

    def _warmup_preview(self) -> None:
        """Dispatch + AOT-capture the fill+decode program during warmup
        (after the pixel-decode capture, same post-dispatch ordering).
        No-op unless previews are enabled AND the fused decode exists."""
        if not (self.preview_enabled and self._has_fused_pixel_decode()):
            return
        self.preview_pixels(
            np.zeros((1, self.image_seq_len), np.int32),
            np.zeros(1, np.int32),
        )
        self._capture_preview_cost()

    def _capture_preview_cost(self) -> None:
        """Like `_capture_decode_pixels_cost`: the preview jit exists
        only after the warmup dispatch built it."""
        if self._preview_jit is None:
            return
        import jax.numpy as jnp

        self._capture_cost(
            "preview",
            lambda t, p: self._preview_jit(t, p),
            jnp.zeros((self.max_batch, self.image_seq_len), jnp.int32),
            jnp.zeros((self.max_batch,), jnp.int32),
        )

    def slots_active_gauge(self, n: int) -> None:
        self._m_slots.set(n)

    # ----------------------------------------------------------- warmup

    def warmup(self, shapes: Optional[Sequence[int]] = None) -> None:
        """Compile the full fixed-shape program set (batched prefill at
        `prefill_batch` — the one program every admission wave runs —
        chunk, slot release, pixel decode) with dummy traffic, then reset
        the slot state. Counts only toward compile metrics +
        `stats.warmup_batches` (same tagging contract as the micro-batch
        engine). Warming ALL of the steady-state programs — release
        included — is load-bearing: tests/test_continuous.py pins with
        `assert_no_recompiles` that a post-warmup serve cycle compiles
        nothing."""
        t0 = time.perf_counter()
        dummy = SampleSpec(
            np.zeros(self.model.text_seq_len, np.int32), seed=0
        )
        self._compile_miss.inc()
        self.prefill_slot(0, dummy, _warmup=True)
        if self.resume_enabled:
            # the resume program warms in slot 1 when there is one; a
            # 1-slot engine recycles slot 0 (same idiom as the paged
            # engine's hit-admit warmup)
            res_slot = 1 if self.max_batch > 1 else 0
            if res_slot == 0:
                self.release([0])
            self.resume_slots(
                [(res_slot, SampleSpec(
                    np.zeros(self.model.text_seq_len, np.int32), seed=0,
                    resume_tokens=np.zeros(1, np.int32), resume_pos=1,
                ))],
                _warmup=True,
            )
        self.step_chunk(_warmup=True)
        self.release([s for s in (0, 1) if s < self.max_batch])
        # cost capture AFTER each program's first dispatch (a pre-dispatch
        # lowering would poison the sampler closure cache with tracers)
        self._capture_release_cost()
        self.decode_pixels(
            np.zeros((1, self.image_seq_len), np.int32)
        )
        self._capture_decode_pixels_cost()
        self._warmup_preview()
        with self._lock:
            # _fresh_state, not init_slot_state directly: subclasses
            # rebuild host-side managers alongside the device state
            self._state = self._fresh_state()
            self.stats.warmup_batches += 1
            self._compile_seconds.observe(time.perf_counter() - t0)
            self._warm.add(self.max_batch)
            self.stats.compiled_shapes = tuple(sorted(self._warm))

    def _capture_release_cost(self) -> None:
        from dalle_pytorch_tpu.models.dalle import release_slots

        mask = np.zeros(self.max_batch, bool)
        mask[0] = True
        self._capture_cost(
            "release",
            lambda s, m: release_slots(self.model, s, m),
            self._state, mask,
        )

    def _capture_decode_pixels_cost(self) -> None:
        """The pixel-decode jit exists only after the warmup decode built
        it (and only for the fused DiscreteVAE path). Routed through the
        shared AOT ladder so the compile cache exports this program too."""
        if self._decode_pixels_jit is None:
            return
        import jax.numpy as jnp

        self._capture_cost(
            "decode_pixels",
            lambda t: self._decode_pixels_jit(t),
            jnp.zeros((self.max_batch, self.image_seq_len), jnp.int32),
        )

    def program_ladder(self) -> Tuple[str, ...]:
        out = ["prefill"]
        if self.resume_enabled:
            out.append("resume")
        out += ["chunk", "release"]
        if self._has_fused_pixel_decode():
            out.append("decode_pixels")
            if self.preview_enabled:
                out.append("preview")
        return tuple(out)

    def _has_fused_pixel_decode(self) -> bool:
        """Only a fused DiscreteVAE builds the jitted pixel-decode
        program; pretrained wrappers decode host-side and a VAE-less
        engine returns tokens only — neither compiles anything, so the
        ladder (and the boot fingerprint) must not claim the program."""
        from dalle_pytorch_tpu.models.dvae import DiscreteVAE

        return isinstance(self.vae, DiscreteVAE)

    # -------------------------------------------------------- observability

    def sparsity_detail(self) -> Optional[dict]:
        """Decode-sparsity snapshot for `/healthz` (None on the causal
        path, so the server omits the block entirely — same getattr
        contract as `kv_detail`/`mesh_detail`)."""
        if self._sparsity is None:
            return None
        out = {"mode": "policy"}
        out.update(self._sparsity.detail())
        out["kv_tiles_read"] = int(self._m_kv_tiles_read.value)
        out["kv_tiles_skipped"] = int(self._m_kv_tiles_skipped.value)
        return out

    def state_dump(self) -> dict:
        """Host-side engine state for `/debug/state` and stall reports —
        deliberately lock-free (a stalled engine is holding its dispatch
        lock, and the dump must still render)."""
        out = super().state_dump()
        out.update(
            max_batch=self.max_batch,
            chunk_tokens=self.chunk_tokens,
            prefill_batch=self.prefill_batch,
            chunk_index=self.chunk_index,
            dispatch_inflight=(
                self.vitals.inflight() if self.vitals else None
            ),
        )
        return out


class PagedContinuousEngine(ContinuousEngine):
    """Continuous batching over a BLOCK-PAGED KV cache with prefix caching.

    Same serving surface and decode semantics as `ContinuousEngine` (one
    shared chunk-program body — `models/dalle.py:_make_chunk_fn` — keeps
    paged output bit-for-bit identical to slotted, pinned by
    tests/test_paging.py), but K/V lives in a pool of `kv_pages` pages of
    `page_size` tokens with host-owned per-row page tables
    (`serving/paging.py`):

      * HBM follows tokens actually held, not `max_batch` worst-case
        lanes — `kv_pages` can be sized below the slotted footprint and
        concurrency is then bounded by real occupancy (admission reserves
        a row's worst case so lazy per-chunk allocation never deadlocks;
        the batcher keeps requests queued while `can_admit` is false).
      * identical caption prefixes share immutable prefill pages
        (content-hash chain lookup, refcounted, copy-on-write at the
        divergence block), and a FULL-prompt hit admits with ZERO
        transformer dispatches — the cached sidecar (pending logits +
        shift rings) restores the row via one tiny fixed-shape program
        (`admit_cached_prefix`), so repeat prompts cost near-zero TTFT.

    Compiled-program set (all warmed, zero recompiles on a warm server):
    paged batched prefill, sidecar slice, cached-prefix admit, paged
    chunk, slot release, pixel decode. Page tables enter every dispatch as
    traced host data, so no allocation decision ever compiles.
    """

    def __init__(
        self,
        model,
        variables,
        vae=None,
        vae_params=None,
        max_batch: int = 8,
        chunk_tokens: int = 4,
        prefill_batch: int = 4,
        cond_scale: float = 1.0,
        clip=None,
        clip_params=None,
        tokenizer=None,
        registry=None,
        cfg=None,
        page_size: int = 32,
        kv_pages: Optional[int] = None,
        prefix_entries: int = 64,
        resume_enabled: bool = False,
        preview_enabled: bool = False,
        kv_dtype=None,
        decode_sparsity: str = "causal",
    ):
        self.page_size = int(page_size)
        assert self.page_size >= 1
        max_positions = model.total_seq_len + 1
        pages_per_row = -(-max_positions // self.page_size)
        if kv_pages is None:
            # worst case (every slot at full length, nothing shared) plus
            # the garbage page and one row of prefix-cache headroom: the
            # DEFAULT never admits worse than slotted; the HBM win comes
            # from sizing kv_pages down and from prefix sharing
            kv_pages = int(max_batch) * pages_per_row + 1 + pages_per_row
        self.kv_pages = int(kv_pages)
        self.prefix_entries = int(prefix_entries)
        self._text_positions = model.text_seq_len + 1
        super().__init__(
            model=model,
            variables=variables,
            vae=vae,
            vae_params=vae_params,
            max_batch=max_batch,
            chunk_tokens=chunk_tokens,
            prefill_batch=prefill_batch,
            cond_scale=cond_scale,
            clip=clip,
            clip_params=clip_params,
            tokenizer=tokenizer,
            registry=registry,
            cfg=cfg,
            resume_enabled=resume_enabled,
            preview_enabled=preview_enabled,
            kv_dtype=kv_dtype,
            decode_sparsity=decode_sparsity,
        )
        assert self.kv.can_ever_admit(1), (
            f"kv_pages={self.kv_pages} cannot hold a single row "
            f"({self.kv.pages_per_row} pages + the garbage page)"
        )
        self._m_blocks_active = self.registry.gauge(
            "dalle_serving_blocks_active",
            "KV pages currently allocated (tokens actually held, incl. "
            "prefix-cache snapshots)",
        )
        self._m_blocks_free = self.registry.gauge(
            "dalle_serving_blocks_free", "KV pages free in the pool"
        )
        self._m_prefix_hits = self.registry.counter(
            "dalle_serving_prefix_cache_hits_total",
            "admissions served from the prefix cache with zero prefill "
            "dispatches",
        )
        self._m_prefix_misses = self.registry.counter(
            "dalle_serving_prefix_cache_misses_total",
            "admissions that ran a prefill dispatch",
        )
        self._m_prefix_evictions = self.registry.counter(
            "dalle_serving_prefix_cache_evictions_total",
            "prefix-cache entries evicted (LRU)",
        )
        #: per-wave admission stats the batcher reads for span metadata /
        #: per-request prefix_hit flags ({"prefix_hits", "hit_slots",
        #: "prefix_blocks_reused", "suffix_tokens_computed", "dispatches"})
        self.last_admission_stats: Optional[dict] = None
        self._update_block_gauges()

    # ------------------------------------------------------- host plumbing

    def _fresh_state(self):
        """Paged device state + rebuilt host managers, together: after a
        failed donated dispatch the pages buffer is gone, so every page
        table, refcount, and cached prefix referring into it is garbage
        too."""
        from dalle_pytorch_tpu.models.dalle import init_paged_slot_state
        from dalle_pytorch_tpu.serving.paging import PagedKVManager

        self.kv = PagedKVManager(
            n_rows=self.max_batch,
            page_size=self.page_size,
            max_positions=self.model.total_seq_len + 1,
            text_positions=self._text_positions,
            n_pages=self.kv_pages,
            max_entries=self.prefix_entries,
            on_evict=lambda: self._m_prefix_evictions.inc(),
        )
        self._host_pos = np.zeros(self.max_batch, np.int64)
        self._host_active = np.zeros(self.max_batch, bool)
        return init_paged_slot_state(
            self.model, self.max_batch, self.kv_pages, self.page_size
        )

    def _update_block_gauges(self) -> None:
        self._m_blocks_active.set(self.kv.blocks_active)
        self._m_blocks_free.set(self.kv.blocks_free)

    def can_admit(self, specs: Sequence[SampleSpec]) -> bool:
        """Free + evictable pages cover this request's worst case on top
        of live rows' reservations (the batcher keeps it queued
        otherwise — block exhaustion is backpressure, not corruption)."""
        return self.kv.can_admit(
            [np.asarray(s.text_ids, np.int32) for s in specs]
        )

    def admission_headroom(self) -> int:
        """Pages available for new admissions — the batcher snapshots
        this once per wave and debits `admission_demand` per popped head
        (same verdict as a union `can_admit`, without re-deriving earlier
        heads' demand on every pop)."""
        return self.kv.admission_headroom()

    def admission_demand(self, specs: Sequence[SampleSpec]) -> int:
        """Worst-case page demand of one request's rows. Resume rows
        (mid-decode migration) are charged the FULL per-row worst case
        even when their prompt is prefix-cached: `admit_resume`
        allocates fresh pages — the resume dispatch rewrites every page
        it maps with the row's own mid-decode K/V, which must never land
        on content other rows share."""
        total = 0
        for s in specs:
            if self.supports_resume and getattr(s, "resume_pos", 0):
                total += self.kv.pages_per_row
            else:
                total += self.kv.row_demand(
                    np.asarray(s.text_ids, np.int32)
                )
        return total

    def can_ever_admit(self, specs: Sequence[SampleSpec]) -> bool:
        """False when the request could not fit an EMPTY pool — submit
        should reject it outright rather than queue it forever."""
        return self.kv.can_ever_admit(len(specs))

    def kv_page_bytes(self) -> int:
        """Bytes of ONE physical page across all layers (K + V + any
        quantization scales) — what a `dalle_serving_blocks_*` page is
        actually worth in HBM at the engine's kv dtype."""
        return self._kv_cache_bytes() // self.kv_pages

    def kv_bytes_per_slot(self) -> int:
        """Worst-case bytes one row can pin: its full page complement.
        (The pool is shared — prefix hits pin less — but sizing honesty
        wants the bound, not the average.)"""
        return self.kv_page_bytes() * self.kv.pages_per_row

    def kv_detail(self) -> dict:
        """Block-pool + prefix-cache snapshot for /healthz."""
        cache = self.kv.cache
        kv_dt = getattr(self.model, "kv_dtype", None)
        return {
            "layout": "paged",
            "page_size": self.page_size,
            "pages_per_row": self.kv.pages_per_row,
            "dtype": str(kv_dt) if kv_dt is not None else str(
                np.dtype(self.model.dtype).name
            ),
            "bytes_per_page": self.kv_page_bytes(),
            "blocks_total": self.kv.pool.n_pages - 1,
            "blocks_active": self.kv.blocks_active,
            "blocks_free": self.kv.blocks_free,
            "prefix_cache": {
                "entries": len(cache),
                "hits": cache.hits,
                "misses": cache.misses,
                "evictions": cache.evictions,
                # seen-keys Bloom digest for the fleet scraper: the
                # prefix-affinity signal a future placer intersects
                "bloom": cache.bloom_digest(),
            },
        }

    # ------------------------------------------------------------ slot ops
    # The three paged model ops run behind subclass seams (like
    # `_prefill_op`/`_chunk_op`/`_release_op` on the slotted engine) so
    # the sharded paged engine can pin out_shardings on the whole ladder.

    def _paged_prefill_op(self, s, texts, slots, seeds, temps, keep,
                          page_rows, partial_dst):
        from dalle_pytorch_tpu.models.dalle import prefill_into_slots_paged

        return prefill_into_slots_paged(
            self.model, self.variables, s, texts, slots, seeds, temps,
            keep, page_rows, partial_dst, self.page_size,
            **self._prefill_bitmap_kw(),
        )

    def _admit_hit_op(self, s, slot, sidecar, seed, temperature, keep_k,
                      partial_src, partial_dst):
        from dalle_pytorch_tpu.models.dalle import admit_cached_prefix

        return admit_cached_prefix(
            self.model, s, slot, sidecar, seed, temperature, keep_k,
            partial_src, partial_dst, self.page_size,
        )

    def _paged_resume_op(self, s, texts, img_tokens, img_pos, slots,
                         seeds, temps, keep, page_rows):
        from dalle_pytorch_tpu.models.dalle import resume_into_slots_paged

        return resume_into_slots_paged(
            self.model, self.variables, s, texts, img_tokens, img_pos,
            slots, seeds, temps, keep, page_rows, self.page_size,
        )

    def protect_admission_wave(self, assignments) -> set:
        """Pin every full-prompt hit entry of one budgeted admission wave
        against eviction until `unprotect_admission_wave`. The batcher
        budgets the WHOLE wave against one headroom snapshot but
        dispatches it in `prefill_batch`-sized `prefill_slots` splits; an
        earlier split's allocation cascade evicting an entry a later
        split's request was budgeted against (at `pages_per_row - saved`)
        would demote that hit to a full prefill and overdraw the
        reservation by `saved` pages. Returns the keys actually added
        (pass them back verbatim)."""
        if not self.kv.cache.enabled:
            return set()
        keys = []
        for _slot, spec in assignments:
            entry = self.kv.cache.peek_full(
                np.asarray(spec.text_ids, np.int32)
            )
            if entry is not None:
                keys.append(entry.key)
        return self.kv.cache.protect(keys)

    def unprotect_admission_wave(self, keys) -> None:
        self.kv.cache.unprotect(keys)

    def prefill_slots(  # tracelint: hotloop
        self,
        assignments: Sequence[Tuple[int, SampleSpec]],
        _warmup: bool = False,
    ) -> None:
        """Paged admission wave: full-prompt prefix hits admit via the
        cached sidecar (zero prefill dispatches); the rest run ONE batched
        paged prefill, mapping any cached prefix blocks into their page
        tables instead of allocating (the dispatch rewrites shared pages
        with bit-identical content — prefill K/V is batch-composition
        invariant) and registering fresh prompts into the cache."""
        n = len(assignments)
        assert 1 <= n <= self.prefill_batch, (
            f"{n} assignments exceed prefill_batch={self.prefill_batch}; "
            "the batcher must split admission waves"
        )
        stats = {
            "wave_rows": n,
            "prefix_hits": 0,
            "hit_slots": [],
            "prefix_blocks_reused": 0,
            "suffix_tokens_computed": 0,
            "dispatches": 0,
        }
        hits, misses = [], []
        for slot, spec in assignments:
            entry = (
                self.kv.cache.lookup_full(np.asarray(spec.text_ids, np.int32))
                if self.kv.cache.enabled
                else None
            )
            if entry is not None:
                hits.append((slot, spec, entry))
            else:
                misses.append((slot, spec))

        # Hit entries are PROTECTED for the rest of the wave: the batcher
        # budgeted each hit at `pages_per_row - saved`, so another row's
        # allocation cascade evicting the entry mid-wave would demote the
        # hit to a full prefill that consumes `saved` more pages than
        # were charged — the reservation invariant would be short by
        # exactly that, and a later `ensure` would hit the allocator's
        # exhaustion assert mid-decode. A batcher wave larger than
        # `prefill_batch` arrives as several `prefill_slots` calls but was
        # budgeted as ONE wave, so the batcher pins the whole wave's hit
        # entries via `protect_admission_wave` around the splits; this
        # per-split pin (unprotecting only what IT added) covers direct
        # callers. Hits also run BEFORE the miss batch so no dispatch
        # ever reads a page its entry no longer owns; the revalidation
        # below is a backstop for unbudgeted callers racing the
        # protection (it cannot fire for waves admitted through
        # can_admit/admission_headroom and wave-protected end to end).
        added = self.kv.cache.protect(entry.key for _, _, entry in hits)
        t0 = time.perf_counter()
        self.vitals.dispatch_begin("prefill")
        try:
            with host_span("serve.prefill", rows=n):
                self._admit_wave(hits, misses, stats, _warmup)
        finally:
            wall = time.perf_counter() - t0
            self.vitals.dispatch_end("prefill", wall)
            self.kv.cache.unprotect(added)
        if not _warmup and self.cost_table is not None:
            self.cost_table.record_wall("prefill", wall, synced=False)

        self.last_admission_stats = stats
        self._update_block_gauges()

    def _admit_wave(self, hits, misses, stats, _warmup) -> None:
        from dalle_pytorch_tpu.models.dalle import (
            admit_cached_prefix,
            prefill_into_slots_paged,
            slice_prefix_sidecar,
        )

        for slot, spec, entry in hits:
            ids = np.asarray(spec.text_ids, np.int32)
            if self.kv.cache.lookup_full(ids) is not entry:
                misses.append((slot, spec))  # evicted mid-wave: full prefill
                continue
            partial_src, pdst = self.kv.admit_hit(slot, entry)
            with self._lock:
                self._replace_state(
                    lambda s, slot=slot, spec=spec, entry=entry,
                    partial_src=partial_src, pdst=pdst: self._admit_hit_op(
                        s, slot, entry.sidecar,
                        int(spec.seed) & 0x7FFFFFFF, spec.temperature,
                        self._keep_k(spec.top_k), partial_src, pdst,
                    ),
                    fault_tag="admit_hit",
                )
                if not _warmup:
                    self._m_prefix_hits.inc()
            if _warmup:
                # after the dispatch (see GenerationEngine.generate: a
                # pre-dispatch lowering would poison the sampler cache)
                self._capture_cost(
                    "admit_hit",
                    lambda s, sl, sc, se, tm, k, src, dst: (
                        admit_cached_prefix(
                            self.model, s, sl, sc, se, tm, k, src, dst,
                            self.page_size,
                        )
                    ),
                    self._state, slot, entry.sidecar,
                    int(spec.seed) & 0x7FFFFFFF, spec.temperature,
                    self._keep_k(spec.top_k), partial_src, pdst,
                )
            self._host_pos[slot] = 0
            self._host_active[slot] = True
            if not _warmup:
                self.kv.cache.hits += 1
            stats["prefix_hits"] += 1
            stats["hit_slots"].append(slot)
            stats["prefix_blocks_reused"] += self.kv.n_full_blocks

        if misses:
            rows = list(misses) + [misses[0]] * (self.prefill_batch - len(misses))
            texts, slots, seeds, temps, keep = _pack_prefill_rows(
                rows, self._keep_k
            )
            assert texts.shape == (
                self.prefill_batch, self.model.text_seq_len,
            ), f"prompt rows must be [{self.model.text_seq_len}] token ids"
            page_rows = np.zeros(
                (self.prefill_batch, self.kv.n_text_pages), np.int32
            )
            partial_dst = np.zeros(self.prefill_batch, np.int32)
            pending = []  # (prefill row index, registration token)
            reg_seen = set()  # same prompt twice in ONE wave registers once
            # wave-local {chain hash: page}: rows admitted later in this
            # wave map earlier rows' pages for identical leading blocks
            # instead of allocating twins (which the registration index
            # could not content-address)
            wave_blocks: dict = {}
            for i, (slot, spec) in enumerate(misses):
                ids = np.asarray(spec.text_ids, np.int32)
                ids_key = ids.tobytes()
                row_pages, pdst, shared_n, token = self.kv.admit_miss(
                    slot, ids, register=ids_key not in reg_seen,
                    pending_blocks=wave_blocks,
                )
                reg_seen.add(ids_key)
                page_rows[i] = row_pages
                partial_dst[i] = pdst
                if token is not None:
                    pending.append((i, token))
                stats["prefix_blocks_reused"] += shared_n
                stats["suffix_tokens_computed"] += (
                    self._text_positions - shared_n * self.page_size
                )
            # padding rows rewrite row 0's pages with identical content;
            # their snapshot write goes to the garbage page
            for i in range(len(misses), self.prefill_batch):
                page_rows[i] = page_rows[0]

            sidecars = {}

            def op(s):
                new_s, sidecar = self._paged_prefill_op(
                    s, texts, slots, seeds, temps, keep, page_rows,
                    partial_dst,
                )
                sidecars["wave"] = sidecar
                return new_s

            with self._lock:
                # on failure _replace_state rebuilds state AND (via
                # _fresh_state) the kv manager, so the half-done host
                # mappings above are discarded wholesale
                self._replace_state(op, fault_tag="prefill")
                if not _warmup:
                    self._m_prefills.inc(len(misses))
                    self._m_prefill_dispatches.inc()
                    self._m_prefix_misses.inc(len(misses))
            if _warmup:
                # after the dispatch (see GenerationEngine.generate: a
                # pre-dispatch lowering would poison the sampler cache)
                spkw = self._prefill_bitmap_kw()
                self._capture_cost(
                    "prefill",
                    lambda v, s, t, sl, se, tm, k, pr, pd: (
                        prefill_into_slots_paged(
                            self.model, v, s, t, sl, se, tm, k, pr, pd,
                            self.page_size, **spkw,
                        )
                    ),
                    self.variables, self._state, texts, slots, seeds,
                    temps, keep, page_rows, partial_dst,
                )
            for i, token in pending:
                self.kv.finish_register(
                    token,
                    slice_prefix_sidecar(self.model, sidecars["wave"], i),
                )
            for slot, _spec in misses:
                self._host_pos[slot] = 0
                self._host_active[slot] = True
            if not _warmup:
                self.kv.cache.misses += len(misses)
            stats["dispatches"] += 1

    def resume_slots(  # tracelint: hotloop
        self,
        assignments: Sequence[Tuple[int, SampleSpec]],
        _warmup: bool = False,
    ) -> None:
        """Paged mid-decode admission: fresh pages cover each row's
        prompt + generated prefix (`PagedKVManager.admit_resume` — no
        prefix sharing, see `admission_demand`), then ONE teacher-forced
        `resume_into_slots_paged` dispatch writes them; blocks beyond
        the prefix stay on the garbage page until `ensure` maps them
        ahead of decode as usual."""
        assert self.supports_resume, (
            "resume_slots on an engine built without resume_enabled — "
            "the program is not in the warmup ladder and would "
            "cold-compile mid-traffic"
        )
        n = len(assignments)
        assert 1 <= n <= self.prefill_batch, (
            f"{n} assignments exceed prefill_batch={self.prefill_batch}; "
            "the batcher must split admission waves"
        )
        rows = list(assignments) + [assignments[0]] * (self.prefill_batch - n)
        texts, slots, seeds, temps, keep = _pack_prefill_rows(
            rows, self._keep_k
        )
        img_tokens, img_pos = self._pack_resume_rows(rows)
        page_rows = np.zeros(
            (self.prefill_batch, self.kv.pages_per_row), np.int32
        )
        mapped: set = set()
        for r, (slot, _spec) in enumerate(rows):
            if slot in mapped:  # padding repeats a real (slot, spec) pair
                page_rows[r] = page_rows[0]
                continue
            mapped.add(slot)
            self.kv.admit_resume(
                slot, self._text_positions + int(img_pos[r])
            )
            page_rows[r] = self.kv.table[slot]
        t0 = time.perf_counter()
        self.vitals.dispatch_begin("resume")
        try:
            from dalle_pytorch_tpu.models.dalle import resume_into_slots_paged

            with self._lock:
                # on failure _replace_state rebuilds state AND (via
                # _fresh_state) the kv manager, discarding the mappings
                with host_span("serve.resume", rows=n):
                    self._replace_state(lambda s: self._paged_resume_op(
                        s, texts, img_tokens, img_pos, slots, seeds, temps,
                        keep, page_rows,
                    ), fault_tag="resume")
                if _warmup:
                    self._capture_cost(
                        "resume",
                        lambda v, s, t, it, ip, sl, se, tm, k, pr: (
                            resume_into_slots_paged(
                                self.model, v, s, t, it, ip, sl, se, tm,
                                k, pr, self.page_size,
                            )
                        ),
                        self.variables, self._state, texts, img_tokens,
                        img_pos, slots, seeds, temps, keep, page_rows,
                    )
        finally:
            wall = time.perf_counter() - t0
            self.vitals.dispatch_end("resume", wall)
        for (slot, _spec), pos in zip(assignments, img_pos[:n]):
            self._host_pos[slot] = int(pos)
            self._host_active[slot] = True
        if not _warmup:
            if self.cost_table is not None:
                self.cost_table.record_wall("resume", wall, synced=False)
            self._m_prefills.inc(n)
            self._m_prefill_dispatches.inc()
        self._update_block_gauges()

    def _pre_chunk(self) -> None:
        # lazy decode-page allocation: the table must cover every live
        # row's writes for this chunk before the dispatch reads it
        # (reserved at admission, so this cannot fail mid-decode)
        for slot in range(self.max_batch):
            if self._host_active[slot]:
                end = min(
                    self._text_positions
                    + int(self._host_pos[slot])
                    + self.chunk_tokens,
                    self.kv.max_positions,
                )
                self.kv.ensure(slot, -(-end // self.page_size))

    def _chunk_op(self, s):
        from dalle_pytorch_tpu.models.dalle import decode_image_chunk_paged

        return decode_image_chunk_paged(
            self.model, self.variables, s, self.chunk_tokens,
            self.kv.table, **self._chunk_bitmap_kw(),
        )

    def _post_chunk(self, pos, act) -> None:
        super()._post_chunk(pos, act)
        self._update_block_gauges()

    def release(self, slots: Sequence[int]) -> None:  # tracelint: hotloop
        # snapshot BEFORE the base release clears the host mirrors: pages
        # must be freed exactly for the rows that were live
        was_active = {int(s): bool(self._host_active[int(s)]) for s in slots}
        super().release(slots)
        for s in slots:
            s = int(s)
            if was_active[s]:
                self.kv.release(s)
        self._update_block_gauges()

    # ------------------------------------------------------------- warmup

    def warmup(self, shapes: Optional[Sequence[int]] = None) -> None:
        """Compile the paged program set: batched prefill (+ the sidecar
        slice its registration runs), the cached-prefix admit, chunk,
        release, pixel decode — then reset device AND host paging state.
        The second dummy wave is a deliberate full-prefix hit so the admit
        program is warm before the first real repeat prompt."""
        t0 = time.perf_counter()
        dummy = SampleSpec(
            np.zeros(self.model.text_seq_len, np.int32), seed=0
        )
        self._compile_miss.inc()
        self.prefill_slots([(0, dummy)], _warmup=True)
        if self.kv.cache.enabled:
            # the hit-admit program warms in slot 1 when there is one; a
            # 1-slot engine recycles slot 0 (released first — a live slot
            # can't be mapped twice)
            hit_slot = 1 if self.max_batch > 1 else 0
            if hit_slot == 0:
                self.release([0])
            self.prefill_slots([(hit_slot, dummy)], _warmup=True)  # prefix hit
        if self.resume_enabled:
            # the resume program warms in the next free slot; small
            # engines recycle slot 0 (released first)
            res_slot = 2 if self.max_batch > 2 else 0
            if res_slot == 0:
                self.release([0])
            self.resume_slots(
                [(res_slot, SampleSpec(
                    np.zeros(self.model.text_seq_len, np.int32), seed=0,
                    resume_tokens=np.zeros(1, np.int32), resume_pos=1,
                ))],
                _warmup=True,
            )
        self.step_chunk(_warmup=True)
        self.release([s for s in (0, 1, 2) if s < self.max_batch])
        # capture after the first release dispatch, like the other
        # programs (pre-dispatch lowering poisons the sampler cache)
        self._capture_release_cost()
        self.decode_pixels(
            np.zeros((1, self.image_seq_len), np.int32)
        )
        self._capture_decode_pixels_cost()
        self._warmup_preview()
        with self._lock:
            self._state = self._fresh_state()
            self.stats.warmup_batches += 1
            self._compile_seconds.observe(time.perf_counter() - t0)
            self._warm.add(self.max_batch)
            self.stats.compiled_shapes = tuple(sorted(self._warm))
        self._update_block_gauges()

    def _capture_chunk_cost(self) -> None:
        from dalle_pytorch_tpu.models.dalle import decode_image_chunk_paged

        spkw = self._chunk_bitmap_kw()
        self._capture_cost(
            "chunk",
            lambda v, s, t: decode_image_chunk_paged(
                self.model, v, s, self.chunk_tokens, t, **spkw,
            ),
            self.variables, self._state, self.kv.table,
        )

    def program_ladder(self) -> Tuple[str, ...]:
        out = ["prefill"]
        if self.kv.cache.enabled:
            out.append("admit_hit")
        if self.resume_enabled:
            out.append("resume")
        out += ["chunk", "release"]
        if self._has_fused_pixel_decode():
            out.append("decode_pixels")
            if self.preview_enabled:
                out.append("preview")
        return tuple(out)

    def state_dump(self) -> dict:
        out = super().state_dump()
        out["kv"] = self.kv.debug_dump()
        return out


def engine_from_checkpoint(
    dalle_path: str,
    clip_path: Optional[str] = None,
    batch_shapes: Sequence[int] = (1, 4, 8),
    cond_scale: float = 1.0,
    registry=None,
    mode: str = "micro",
    chunk_tokens: int = 4,
    prefill_batch: int = 4,
    kv_layout: str = "slot",
    page_size: int = 32,
    kv_pages: Optional[int] = None,
    prefix_entries: int = 64,
    mesh=None,
    resume_enabled: Optional[bool] = None,
    preview_enabled: Optional[bool] = None,
    kv_dtype: Optional[str] = None,
    decode_sparsity: Optional[str] = None,
):
    """Build a serving engine from a single-file DALLE checkpoint.

    `mode="micro"` (default) returns the padded-micro-batch
    `GenerationEngine`; `mode="continuous"` returns a `ContinuousEngine`
    whose slot count is the largest entry of `batch_shapes` —
    `kv_layout="paged"` upgrades it to the block-paged
    `PagedContinuousEngine` (`page_size` tokens per page, `kv_pages` pool
    size or None for the slotted-equivalent worst case, `prefix_entries`
    cached prompts). `mesh` (a `parse_mesh_shape` string/dict, or a ready
    jax Mesh) selects the mesh-sharded `ShardedContinuousEngine`
    (`kv_layout="paged"` upgrades it to `ShardedPagedContinuousEngine`:
    the paged pool head-splits over `tp`, page tables stay host-side).
    `kv_dtype="int8"` stores KV pages quantized with per-(position, head)
    scales; `None`/"model" keeps the model dtype.
    `decode_sparsity="policy"` routes pattern-masked decode rows through
    the block-sparse flash kernel, bitmaps derived host-side from the
    model's static attention layouts (`serving/sparsity.py`);
    `None`/"causal" keeps the bit-identical dense-causal default
    (continuous engines only). The loading
    sequence (VAE reconstruction, tokenizer, ring-attention downgrade for
    decode) was lifted from `generate.py`, which now calls this instead —
    CLI and server share one code path by construction.
    """
    assert mode in ("micro", "continuous"), f"unknown engine mode {mode!r}"
    assert mesh is None or mode == "continuous", (
        "--mesh needs the continuous engine (slot or paged kv layout)"
    )
    assert decode_sparsity in (None, "causal") or mode == "continuous", (
        "--decode_sparsity policy needs the continuous engine (the "
        "micro-batch sampler has no per-slot bitmap plumbing)"
    )
    from pathlib import Path

    from dalle_pytorch_tpu.training.pipeline import (
        build_tokenizer, dalle_from_config, dvae_from_hparams,
        load_dalle_checkpoint,
    )

    ckpt_path = Path(dalle_path)
    assert ckpt_path.exists(), f"trained DALL-E {ckpt_path} must exist"
    cfg, dalle_params, vae_params, meta, _ = load_dalle_checkpoint(str(ckpt_path))

    assert meta.get("vae_class_name") == "DiscreteVAE" or vae_params is None, (
        "checkpoint was trained with a pretrained VAE wrapper; provide it"
    )
    if vae_params is None:
        from dalle_pytorch_tpu.training.pipeline import build_vae

        vae, vae_params = build_vae(cfg)
    else:
        assert meta.get("vae_hparams"), "checkpoint missing vae_hparams"
        vae = dvae_from_hparams(meta["vae_hparams"])
    fmap = vae.image_size // (2 ** vae.num_layers)

    tokenizer = build_tokenizer(cfg)
    if cfg.model.attn_impl == "ring":
        # ring attention is a training-time layout (sequence sharded over
        # the mesh sp axis); KV-cached decode never runs it, so a
        # ring-trained checkpoint generates with the dense/auto kernel
        cfg.model.attn_impl = "auto"
    model = dalle_from_config(
        cfg, num_image_tokens=vae.num_tokens, image_fmap_size=fmap,
        vocab_size=max(tokenizer.vocab_size, 1),
    )
    if kv_dtype not in (None, "model"):
        # quantized KV store: every engine (and the micro path) reads the
        # model field, so one clone here covers all modes uniformly
        model = model.clone(kv_dtype=str(kv_dtype))

    clip = clip_params = None
    if clip_path:
        from dalle_pytorch_tpu.training.pipeline import load_clip_checkpoint

        clip, clip_params = load_clip_checkpoint(clip_path)

    common = dict(
        model=model,
        variables={"params": dalle_params},
        vae=vae,
        vae_params=vae_params,
        cond_scale=cond_scale,
        clip=clip,
        clip_params=clip_params,
        tokenizer=tokenizer,
        registry=registry,
        cfg=cfg,
    )
    if mode == "continuous":
        assert kv_layout in ("slot", "paged"), f"unknown kv_layout {kv_layout!r}"
        cls = PagedContinuousEngine if kv_layout == "paged" else ContinuousEngine
        paged_kw = (
            dict(
                page_size=page_size,
                kv_pages=kv_pages,
                prefix_entries=prefix_entries,
            )
            if kv_layout == "paged"
            else {}
        )
        # decode-state resume (mid-decode migration) defaults ON for
        # serving boots — the sharded engines pin the resume program's
        # out_shardings, so mesh boots keep it too
        paged_kw["resume_enabled"] = (
            True if resume_enabled is None else bool(resume_enabled)
        )
        if mesh is not None:
            from dalle_pytorch_tpu.serving.sharded import (
                ShardedContinuousEngine, ShardedPagedContinuousEngine,
            )

            cls = (
                ShardedPagedContinuousEngine
                if kv_layout == "paged"
                else ShardedContinuousEngine
            )
            try:
                from jax.sharding import Mesh

                is_mesh = isinstance(mesh, Mesh)
            except Exception:  # pragma: no cover - jax always importable here
                is_mesh = False
            paged_kw.update(
                dict(mesh=mesh) if is_mesh else dict(mesh_shape=mesh)
            )
        # progressive-preview decode (streaming) defaults ON for serving
        # boots on every continuous engine — the preview program rides
        # the replicated VAE, so the sharded engine warms it too
        paged_kw["preview_enabled"] = (
            True if preview_enabled is None else bool(preview_enabled)
        )
        paged_kw["decode_sparsity"] = (
            "causal" if decode_sparsity is None else str(decode_sparsity)
        )
        return cls(
            max_batch=max(int(b) for b in batch_shapes),
            chunk_tokens=chunk_tokens,
            prefill_batch=prefill_batch,
            **paged_kw,
            **common,
        )
    return GenerationEngine(batch_shapes=batch_shapes, **common)
