#!/usr/bin/env python
"""Generate images from a trained DALL-E checkpoint.

Equivalent of `/root/reference/generate.py`: loads the single-file
checkpoint (hparams + weights + frozen-VAE weights), verifies the VAE
class matches (`generate.py:101`), splits prompts on '|', optionally
completes the text first (--gentxt, `:116-118`), samples image tokens with
top-k 0.9 + temperature, decodes through the VAE and writes PNGs per
prompt directory (`:134-143`).

Sampling runs through the serving `GenerationEngine`
(`dalle_pytorch_tpu/serving/engine.py`) — the same padded fixed-shape
batching + fused dVAE decode + CLIP rerank code path `serve.py` exposes
over HTTP, so the CLI dogfoods the production path. `--no_cache` keeps the
full-reforward sampling oracle for correctness spot checks.
"""

from __future__ import annotations

import argparse
from pathlib import Path

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dalle_path", type=str, required=True)
    p.add_argument("--text", type=str, required=True, help="'|'-separated prompts")
    p.add_argument("--num_images", type=int, default=16)
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--top_k", type=float, default=0.9)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--cond_scale", type=float, default=1.0)
    p.add_argument("--outputs_dir", type=str, default="outputs")
    p.add_argument(
        "--clip_path",
        type=str,
        default=None,
        help="CLIP checkpoint; generations are reranked by similarity "
        "(`dalle_pytorch.py:569-571`) and saved best-first",
    )
    p.add_argument("--gentxt", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--no_cache",
        action="store_true",
        help="use the full-reforward sampling oracle instead of KV-cached decode",
    )
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import generate_images, generate_texts
    from dalle_pytorch_tpu.models.dvae import DiscreteVAE
    from dalle_pytorch_tpu.serving.engine import SampleSpec, engine_from_checkpoint
    from dalle_pytorch_tpu.utils.device import log_device
    from dalle_pytorch_tpu.utils.images import save_image_grid, to_uint8

    log_device()

    # one compiled shape: the CLI always dispatches full --batch_size
    # batches (the engine pads the final partial chunk)
    engine = engine_from_checkpoint(
        args.dalle_path,
        clip_path=args.clip_path,
        batch_shapes=(args.batch_size,),
        cond_scale=args.cond_scale,
    )
    model, variables, vae = engine.model, engine.variables, engine.vae
    tokenizer, cfg = engine.tokenizer, engine.cfg
    rng = jax.random.PRNGKey(args.seed)

    from PIL import Image

    dvae_decode = None
    # spread the user seed so --seed N and --seed N+1 give fully disjoint
    # per-image seed ranges (engine rows are seeded individually; plain
    # consecutive bases would make adjacent runs share most images)
    next_seed = (args.seed * 1_000_003) & 0x7FFFFFFF

    for raw_prompt in args.text.split("|"):
        prompt = raw_prompt.strip()
        if args.gentxt:
            ids = tokenizer.tokenize(prompt, cfg.model.text_seq_len, truncate_text=True)
            prefix_len = int((ids[0] != 0).sum())
            rng, r = jax.random.split(rng)
            completed = generate_texts(
                model, variables, r, jnp.asarray(ids), prefix_len=prefix_len
            )
            prompt = tokenizer.decode(
                completed[0],
                pad_tokens=set(
                    range(model.total_text_tokens - model.text_seq_len,
                          model.total_text_tokens)
                ),
            )
            print(f"completed text: {prompt!r}")

        text_ids = engine.tokenize(prompt)

        images = []
        for start in range(0, args.num_images, args.batch_size):
            n = min(args.batch_size, args.num_images - start)
            if args.no_cache:
                # full-reforward oracle, bypassing the engine on purpose
                chunk = jnp.asarray(np.repeat(text_ids[None], n, axis=0))
                rng, r = jax.random.split(rng)
                toks = generate_images(
                    model, variables, r, chunk,
                    filter_thres=args.top_k, temperature=args.temperature,
                    cond_scale=args.cond_scale,
                )
                if isinstance(vae, DiscreteVAE):
                    if dvae_decode is None:
                        # jit once: eager decode dispatches per-op (slow on
                        # remote backends); shapes are fixed across chunks
                        dvae_decode = jax.jit(
                            lambda p, t: vae.apply(
                                {"params": p}, t, method=DiscreteVAE.decode
                            )
                        )
                    imgs = dvae_decode(engine.vae_params, toks)
                    images.append(np.asarray(imgs) * 0.5 + 0.5)  # un-normalize
                else:  # pretrained wrappers decode to [0,1] already
                    images.append(np.asarray(vae.decode(toks)))
                continue
            specs = [
                SampleSpec(
                    text_ids=text_ids,
                    seed=next_seed + i,
                    temperature=args.temperature,
                    top_k=args.top_k,
                )
                for i in range(n)
            ]
            next_seed += n
            _, pixels = engine.generate(specs)
            assert pixels is not None, "checkpoint has no VAE to decode pixels"
            images.append(pixels)
        images = np.concatenate(images, axis=0)

        if engine.clip is not None:
            images, scores, _ = engine.rerank(prompt, images)
            print("clip scores (best first):", np.asarray(scores)[:8])

        safe = "".join(c if c.isalnum() or c in " -." else "" for c in prompt)
        out_dir = Path(args.outputs_dir) / (safe.strip().replace(" ", "_")[:100] or "prompt")
        out_dir.mkdir(parents=True, exist_ok=True)
        for i, img in enumerate(images):
            Image.fromarray(to_uint8(img)).save(out_dir / f"{i}.png")
        save_image_grid(images, out_dir / "grid.png")
        print(f"created {len(images)} images at {out_dir}")
    from dalle_pytorch_tpu.utils.compile_guard import log_compiles

    log_compiles()


if __name__ == "__main__":
    main()
