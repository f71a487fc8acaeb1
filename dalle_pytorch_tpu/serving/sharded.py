"""Mesh-sharded continuous serving: one engine across a TPU mesh.

`ContinuousEngine` is single-device: a model whose params don't fit one
chip's HBM — or a slot cache sized for more concurrency than one chip
holds — cannot serve at all. `ShardedContinuousEngine` spreads BOTH over
a `make_mesh` device mesh (jax.sharding / GSPMD, the pjit programming
model of "Scalable Training of Language Models using JAX pjit and
TPUv4", PAPERS.md):

  * params are placed with `NamedSharding` per `parallel/partition.py`'s
    training rules (megatron column/row splits over `tp`, embeddings
    vocab-parallel) — one rule table for train AND serve;
  * the persistent slot state is placed per
    `parallel/serving_partition.py`: KV cache split over attention heads
    on the `tp` axis, pending-logits rows vocab-split, per-row control
    scalars replicated;
  * the steady-state programs (batched prefill, resume, chunk, release,
    pixel decode) are the SAME program bodies the single-device engine
    runs (`models/dalle.py` builders) — re-jitted here with explicit
    `out_shardings` pinned to the canonical state shardings, so the
    sharding of the donated state reaches a fixed point at the FIRST
    dispatch and the warm server's zero-recompile contract survives
    (GSPMD-propagated output shardings drifting between dispatches would
    re-key the jit cache);
  * when the flash-decode kernel is active, `Attention` dispatches it
    through `ops/pallas_decode.py:sharded_flash_decode_attention` —
    shard_map over the mesh's tp axis, heads split, exactly the
    SNIPPETS.md [1] pattern (a Pallas call is a single-device program
    GSPMD cannot partition).

The engine seam is the whole point: `prefill_slots` / `step_chunk` /
`harvest` / `release` keep their signatures, so the continuous batcher,
the HTTP server, tracing, vitals, and warmup/cost-capture all work
unchanged — `serve.py --mesh dp=1,tp=4` is the only switch.

Correctness pin: the head/vocab splits introduce no cross-device
reduction inside attention itself, and the decode-composition-invariance
contract extends across the mesh — a >=2-device CPU mesh
(`--xla_force_host_platform_device_count`) produces bit-identical tokens
to the single-device engine for the same specs/seeds
(tests/test_sharded.py).

`ShardedPagedContinuousEngine` extends the same placement to the paged
layout: the physical page POOL head-splits over `tp` (each shard holds
its heads' slice of every page), while page tables, refcounts, and the
prefix-cache index stay host-side numpy — page bookkeeping is
device-count-independent, so the paged admission/eviction logic runs
verbatim. The whole paged ladder (prefill + sidecar, cached-prefix
admit, resume, chunk, release) is pinned with `out_shardings` like the
slotted programs. The page axis itself must NEVER shard: a page is the
unit of host-side allocation, and splitting it would put half of each
page's tokens on the wrong device (tracelint TL008 flags specs that
try).
"""

from __future__ import annotations

from typing import Optional, Union

import numpy as np

from dalle_pytorch_tpu.serving.engine import (
    ContinuousEngine,
    PagedContinuousEngine,
)

#: the 4-axis `make_mesh` vocabulary, re-declared so `parse_mesh_shape`
#: stays importable without paying a jax init (`parallel/mesh.py` imports
#: jax at module top; serve.py validates --mesh at argparse time) —
#: pinned in lockstep with `parallel.mesh.MESH_AXES` by
#: tests/test_sharded.py
MESH_AXES = ("dp", "fsdp", "tp", "sp")


def parse_mesh_shape(spec: Optional[str]) -> dict:
    """`--mesh dp=2,tp=4`-style flag -> {axis: size}. Axes are the
    4-axis `make_mesh` vocabulary (dp, fsdp, tp, sp); omitted axes get
    size 1; at most one size may be -1 to absorb the remaining devices.
    Empty/None defaults to everything on the model axis (tp=-1)."""
    if not spec:
        return {"tp": -1}
    out: dict = {}
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        assert "=" in part, (
            f"mesh axis {part!r} must be axis=size (e.g. dp=1,tp=4)"
        )
        k, v = part.split("=", 1)
        k = k.strip()
        assert k in MESH_AXES, f"unknown mesh axis {k!r}; use one of {MESH_AXES}"
        size = int(v)
        assert size == -1 or size >= 1, (
            f"mesh axis {k}={size}: sizes must be >= 1 (or -1 to absorb "
            "the remaining devices)"
        )
        out[k] = size
    return out


def build_serving_mesh(shape: Union[str, dict, None] = None, devices=None):
    """Resolve a mesh-shape request against the visible devices and build
    the 4-axis mesh. A -1 size absorbs the remaining devices; a product
    smaller than the device count uses the first `product` devices (the
    `make_pp_mesh` convention, so `tp=2` works on an 8-device test
    host)."""
    import jax

    from dalle_pytorch_tpu.parallel.mesh import make_mesh

    shape = dict(
        parse_mesh_shape(shape) if shape is None or isinstance(shape, str)
        else shape
    )
    for k, v in shape.items():  # dict callers bypass parse_mesh_shape
        assert v == -1 or v >= 1, f"mesh axis {k}={v}: sizes must be >= 1"
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    neg = [k for k, v in shape.items() if v == -1]
    assert len(neg) <= 1, f"at most one mesh axis may be -1, got {shape}"
    fixed = 1
    for k, v in shape.items():
        if v != -1:
            fixed *= v
    if neg:
        assert n % fixed == 0, (
            f"{n} devices not divisible by the fixed axes {fixed}"
        )
        shape[neg[0]] = n // fixed
        fixed = n
    assert fixed <= n, f"mesh {shape} needs {fixed} devices, have {n}"
    kw = {a: shape.get(a, 1) for a in MESH_AXES}
    return make_mesh(devices=devices[:fixed], **kw)


class _MeshServingMixin:
    """Mesh plumbing shared by the slotted and paged sharded engines:
    placement at load, state placement, the pinned-program cache, the
    (layout-independent) release program, and the per-shard
    observability block. Each concrete engine supplies its own pinned
    admission/chunk programs — the bodies differ per layout but the jit
    wrapper discipline (donate the state, pin out_shardings to the
    canonical state shardings) is identical."""

    def _init_mesh(self, model, variables, vae_params, mesh, mesh_shape,
                   model_axis):
        """Resolve the mesh, clone the model's decode-kernel mesh handle,
        and place params/VAE — returns the (possibly cloned/placed)
        triple for the engine __init__ to forward to its base class."""
        import jax

        from dalle_pytorch_tpu.parallel.serving_partition import (
            replicated_shardings,
            serving_variables_shardings,
        )

        if mesh is None:
            mesh = build_serving_mesh(mesh_shape)
        self.mesh = mesh
        self.model_axis = model_axis
        assert model_axis in mesh.axis_names, (
            f"mesh {dict(mesh.shape)} lacks the model axis {model_axis!r}"
        )
        #: per-program jitted dispatchers with out_shardings pinned to the
        #: canonical state shardings (built lazily on first dispatch)
        self._sharded_programs: dict = {}
        self._state_shardings = None
        # hand the mesh AND the head axis to the flash-decode dispatch
        # (no-op for models whose cached path stays dense) — the kernel
        # must split over the same axis the KV-cache shardings use;
        # callers that pre-set their own decode_mesh keep it
        if getattr(model, "decode_mesh", None) is None:
            model = model.clone(
                decode_mesh=mesh, decode_heads_axis=model_axis
            )
        # placement at load: params tensor-sharded per partition.py, VAE
        # replicated (the pixel decode is tiny next to the trunk)
        variables = jax.device_put(
            variables, serving_variables_shardings(variables, mesh)
        )
        if vae_params is not None:
            vae_params = jax.device_put(
                vae_params, replicated_shardings(vae_params, mesh)
            )
        return model, variables, vae_params

    # ---------------------------------------------------------- placement

    def _fresh_state(self):
        """Clean decode state placed under the serving_partition
        shardings (KV heads over the model axis — slot lanes and the
        paged pool alike —, control scalars replicated). The paged base
        rebuilds its host-side page tables inside super()._fresh_state();
        they are plain numpy and never placed."""
        import jax

        from dalle_pytorch_tpu.parallel.serving_partition import (
            decode_state_shardings,
        )

        state = super()._fresh_state()
        if self._state_shardings is None:
            self._state_shardings = decode_state_shardings(
                state, self.mesh, self.model_axis
            )
        return jax.device_put(state, self._state_shardings)

    def _sharded_program(self, name: str, build):
        fn = self._sharded_programs.get(name)
        if fn is None:
            from dalle_pytorch_tpu.obs import scopes

            # remembered at its first dispatch with the shardings its
            # arguments carry, under the builder's own program name
            fn = scopes.remembering(build())
            self._sharded_programs[name] = fn
        return fn

    def _replicated_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    # ----------------------------------------------------------- slot ops
    # The program BODIES are models/dalle.py's — only the jit wrapper
    # differs: out_shardings pinned to the canonical state shardings so
    # the donated state's sharding is a fixed point from dispatch one
    # (unpinned, GSPMD may hand back a drifted sharding that re-keys the
    # jit cache on the next dispatch — a silent warm-path recompile).

    def _release_op(self, s, mask):
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import _release_builder

        fn = self._sharded_program(
            "release",
            lambda: jax.jit(
                _release_builder(self.model, ()),
                donate_argnums=(0,),
                out_shardings=self._state_shardings,
            ),
        )
        return fn(s, jnp.asarray(mask, jnp.bool_))

    # ------------------------------------------------------ observability

    def mesh_detail(self) -> dict:
        """Mesh geometry + per-device buffer accounting for `/healthz`,
        `state_dump()`, and the bench's JSON line — the block that lets a
        stall event or a capacity dashboard name the SICK SHARD instead
        of "the engine". Host-side metadata reads only; a leaf whose
        buffer was just donated away reports as skipped rather than
        raising (the dump must render while the engine is wedged)."""
        per_dev: dict = {}
        leaves = []
        try:
            import jax

            leaves = jax.tree_util.tree_leaves((self._state, self.variables))
        except Exception:
            pass
        for leaf in leaves:
            shards = getattr(leaf, "addressable_shards", None)
            if shards is None:
                continue
            try:
                for shard in shards:
                    key = f"{shard.device.platform}:{shard.device.id}"
                    nbytes = getattr(shard.data, "nbytes", None)
                    if nbytes is None:
                        nbytes = int(
                            np.prod(shard.data.shape)
                        ) * shard.data.dtype.itemsize
                    per_dev[key] = per_dev.get(key, 0) + int(nbytes)
            except Exception:
                continue  # donated-away buffer mid-dispatch: skip the leaf
        return {
            "axes": {k: int(v) for k, v in dict(self.mesh.shape).items()},
            "devices": int(self.mesh.devices.size),
            "model_axis": self.model_axis,
            "per_device_state_bytes": per_dev,
        }

    def state_dump(self) -> dict:
        out = super().state_dump()
        out["mesh"] = self.mesh_detail()
        return out


class ShardedContinuousEngine(_MeshServingMixin, ContinuousEngine):
    """Continuous batching with params + slot KV cache sharded over a
    device mesh. Same serving surface as `ContinuousEngine` (the batcher,
    server, tracing, and vitals layers don't know the difference); same
    decode numerics (bit-identical tokens — the test-pinned contract).

    `mesh` is a ready `jax.sharding.Mesh`, or pass `mesh_shape` (a
    `parse_mesh_shape` string/dict) to build one over the visible
    devices. `model_axis` names the axis heads/vocab shard over
    (default "tp").
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
        mesh=None,
        mesh_shape: Union[str, dict, None] = None,
        model_axis: str = "tp",  # serving_partition.SERVING_MODEL_AXIS
        resume_enabled: bool = False,
        preview_enabled: bool = False,
        kv_dtype=None,
        decode_sparsity: str = "causal",
    ):
        model, variables, vae_params = self._init_mesh(
            model, variables, vae_params, mesh, mesh_shape, model_axis
        )
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

    # ----------------------------------------------------------- slot ops

    def _prefill_op(self, s, texts, slots, seeds, temps, keep):
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import _prefill_slots_builder

        sparse = self._sparsity is not None
        key = (
            (self.prefill_batch, "sparse") if sparse
            else (self.prefill_batch,)
        )
        fn = self._sharded_program(
            "prefill",
            lambda: jax.jit(
                _prefill_slots_builder(self.model, key),
                donate_argnums=(1,),
                out_shardings=self._state_shardings,
            ),
        )
        args = [
            self.variables, s, jnp.asarray(texts, jnp.int32),
            jnp.asarray(slots, jnp.int32), jnp.asarray(seeds, jnp.int32),
            jnp.asarray(temps, jnp.float32), jnp.asarray(keep, jnp.int32),
        ]
        if sparse:
            # bitmap rides replicated (it is per-row control data, tiny
            # next to the KV it gates; GSPMD replicates uncommitted hosts
            # arrays) — the per-head split happens inside the shard_map
            args.append(jnp.asarray(
                self._sparsity.prefill_bitmaps(self.prefill_batch),
                jnp.int32,
            ))
        return fn(*args)

    def _resume_op(self, s, texts, img_tokens, img_pos, slots, seeds,
                   temps, keep):
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import _resume_slots_builder

        fn = self._sharded_program(
            "resume",
            lambda: jax.jit(
                _resume_slots_builder(self.model, (self.prefill_batch,)),
                donate_argnums=(1,),
                out_shardings=self._state_shardings,
            ),
        )
        return fn(
            self.variables, s, jnp.asarray(texts, jnp.int32),
            jnp.asarray(img_tokens, jnp.int32),
            jnp.asarray(img_pos, jnp.int32),
            jnp.asarray(slots, jnp.int32), jnp.asarray(seeds, jnp.int32),
            jnp.asarray(temps, jnp.float32), jnp.asarray(keep, jnp.int32),
        )

    def _chunk_op(self, s):
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import _chunk_builder

        sparse = self._sparsity is not None
        key = (
            (self.chunk_tokens, "sparse") if sparse
            else (self.chunk_tokens,)
        )
        fn = self._sharded_program(
            "chunk",
            lambda: jax.jit(
                _chunk_builder(self.model, key),
                donate_argnums=(1,),
                out_shardings=self._state_shardings,
            ),
        )
        if sparse:
            return fn(self.variables, s, jnp.asarray(
                self._sparsity.chunk_bitmaps(
                    self._host_pos, self._host_active
                ),
                jnp.int32,
            ))
        return fn(self.variables, s)


class ShardedPagedContinuousEngine(_MeshServingMixin, PagedContinuousEngine):
    """Paged continuous batching over a device mesh: the physical page
    pool head-splits over the model axis (each shard holds its heads'
    slice of EVERY page), page tables / refcounts / the prefix-cache
    index stay host-side numpy and run verbatim. The whole paged program
    ladder — batched prefill (+ sidecar), cached-prefix admit, resume,
    chunk, release — is re-jitted with out_shardings pinned to the
    canonical state shardings, so the warm server's zero-recompile
    contract holds exactly as on the slotted sharded engine.

    The page axis NEVER shards (a page is the host allocator's unit;
    `parallel/serving_partition.py` keeps it whole and tracelint TL008
    flags shard_map specs that split it)."""

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
        mesh=None,
        mesh_shape: Union[str, dict, None] = None,
        model_axis: str = "tp",  # serving_partition.SERVING_MODEL_AXIS
        resume_enabled: bool = False,
        preview_enabled: bool = False,
        kv_dtype=None,
        decode_sparsity: str = "causal",
    ):
        model, variables, vae_params = self._init_mesh(
            model, variables, vae_params, mesh, mesh_shape, model_axis
        )
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
            page_size=page_size,
            kv_pages=kv_pages,
            prefix_entries=prefix_entries,
            resume_enabled=resume_enabled,
            preview_enabled=preview_enabled,
            kv_dtype=kv_dtype,
            decode_sparsity=decode_sparsity,
        )

    # ----------------------------------------------------------- slot ops
    # Pinned versions of the paged seams. The prefill program returns
    # (state, sidecar): the state pins to the canonical shardings, the
    # sidecar (pending logits + shift rings, consumed host-side by the
    # prefix-cache registration) replicates — a pytree-prefix
    # out_shardings covers both.

    def _paged_prefill_op(self, s, texts, slots, seeds, temps, keep,
                          page_rows, partial_dst):
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import (
            _prefill_slots_paged_builder,
        )

        n_text_pages = int(np.asarray(page_rows).shape[1])
        sparse = self._sparsity is not None
        key = (self.prefill_batch, self.page_size, n_text_pages)
        if sparse:
            key = key + ("sparse",)
        fn = self._sharded_program(
            "prefill",
            lambda: jax.jit(
                _prefill_slots_paged_builder(self.model, key),
                donate_argnums=(1,),
                out_shardings=(
                    self._state_shardings, self._replicated_sharding(),
                ),
            ),
        )
        args = [
            self.variables, s, jnp.asarray(texts, jnp.int32),
            jnp.asarray(slots, jnp.int32), jnp.asarray(seeds, jnp.int32),
            jnp.asarray(temps, jnp.float32), jnp.asarray(keep, jnp.int32),
            jnp.asarray(page_rows, jnp.int32),
            jnp.asarray(partial_dst, jnp.int32),
        ]
        if sparse:
            args.append(jnp.asarray(
                self._sparsity.prefill_bitmaps(self.prefill_batch),
                jnp.int32,
            ))
        return fn(*args)

    def _admit_hit_op(self, s, slot, sidecar, seed, temperature, keep_k,
                      partial_src, partial_dst):
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import _admit_prefix_builder

        fn = self._sharded_program(
            "admit_hit",
            lambda: jax.jit(
                _admit_prefix_builder(self.model, (self.page_size,)),
                donate_argnums=(0,),
                out_shardings=self._state_shardings,
            ),
        )
        return fn(
            s, jnp.int32(slot), sidecar, jnp.int32(seed),
            jnp.float32(temperature), jnp.int32(keep_k),
            jnp.int32(partial_src), jnp.int32(partial_dst),
        )

    def _paged_resume_op(self, s, texts, img_tokens, img_pos, slots,
                         seeds, temps, keep, page_rows):
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import (
            _resume_slots_paged_builder,
        )

        n_pages_row = int(np.asarray(page_rows).shape[1])
        fn = self._sharded_program(
            "resume",
            lambda: jax.jit(
                _resume_slots_paged_builder(
                    self.model,
                    (self.prefill_batch, self.page_size, n_pages_row),
                ),
                donate_argnums=(1,),
                out_shardings=self._state_shardings,
            ),
        )
        return fn(
            self.variables, s, jnp.asarray(texts, jnp.int32),
            jnp.asarray(img_tokens, jnp.int32),
            jnp.asarray(img_pos, jnp.int32),
            jnp.asarray(slots, jnp.int32), jnp.asarray(seeds, jnp.int32),
            jnp.asarray(temps, jnp.float32), jnp.asarray(keep, jnp.int32),
            jnp.asarray(page_rows, jnp.int32),
        )

    def _chunk_op(self, s):
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import _chunk_paged_builder

        sparse = self._sparsity is not None
        key = (
            (self.chunk_tokens, "sparse") if sparse
            else (self.chunk_tokens,)
        )
        fn = self._sharded_program(
            "chunk",
            lambda: jax.jit(
                _chunk_paged_builder(self.model, key),
                donate_argnums=(1,),
                out_shardings=self._state_shardings,
            ),
        )
        args = [self.variables, s, jnp.asarray(self.kv.table, jnp.int32)]
        if sparse:
            args.append(jnp.asarray(
                self._sparsity.chunk_bitmaps(
                    self._host_pos, self._host_active
                ),
                jnp.int32,
            ))
        return fn(*args)
