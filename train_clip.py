#!/usr/bin/env python
"""Train a CLIP reranker on a text-image dataset.

The reference provides the CLIP model (`dalle_pytorch.py:274-350`) and uses
it to rerank generations (`dalle_pytorch.py:569-571`, `generate.py` via
`--clip_path` here) but ships no trainer for it; this CLI completes the
loop so reranking works end-to-end. Dataset arguments mirror
train_dalle.py: `rainbow:N`, cub200, mnist, or an image folder.
"""

from __future__ import annotations

import argparse


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--image_text_folder", type=str, required=True)
    p.add_argument("--output", type=str, default="clip.npz")
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--learning_rate", type=float, default=3e-4)
    p.add_argument("--image_size", type=int, default=128)
    p.add_argument("--patch_size", type=int, default=16)
    p.add_argument("--text_seq_len", type=int, default=64)
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--dim_latent", type=int, default=256)
    p.add_argument("--depth", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--bpe_path", type=str, default=None)
    p.add_argument(
        "--executor", choices=("unrolled", "scan"), default="unrolled",
        help="layer executor for both encoders; scan compiles one layer "
        "body instead of depth copies (models/transformer.py)",
    )
    p.add_argument(
        "--steps_per_dispatch", type=int, default=1,
        help="optimizer steps scanned into one device dispatch "
        "(host-loop elimination; see training/steps.py make_multi_step)",
    )
    p.add_argument("--debug", action="store_true")
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax.numpy as jnp
    import numpy as np

    from dalle_pytorch_tpu.models.clip import CLIP
    from dalle_pytorch_tpu.parallel import initialize_distributed

    # multi-host rendezvous (launch.py env vars / TPU pod auto); no-op
    # single-host. Must run before the first device query.
    initialize_distributed()
    from dalle_pytorch_tpu.training.config import TrainConfig
    from dalle_pytorch_tpu.training.steps import (
        TrainState, make_optimizer, make_clip_train_step, make_multi_step,
        stack_batches, window_iter, window_keys,
    )
    from dalle_pytorch_tpu.training.pipeline import (
        build_dataset, build_tokenizer, save_clip_checkpoint,
    )
    from dalle_pytorch_tpu.training.metrics import MetricsLogger, ThroughputMeter

    # reuse the shared dataset dispatch (rainbow:N / folders / tar shards)
    cfg = TrainConfig()
    cfg.image_text_folder = args.image_text_folder
    cfg.bpe_path = args.bpe_path
    cfg.truncate_captions = True
    cfg.model.text_seq_len = args.text_seq_len
    tokenizer = build_tokenizer(cfg)
    data = build_dataset(cfg, tokenizer, args.image_size)
    batches = lambda seed: data.batches(args.batch_size, shuffle_seed=seed)
    print(f"{len(data)} text-image pairs for training")

    clip = CLIP(
        dim_text=args.dim,
        dim_image=args.dim,
        dim_latent=args.dim_latent,
        num_text_tokens=max(tokenizer.vocab_size, 1),
        text_enc_depth=args.depth,
        text_seq_len=args.text_seq_len,
        text_heads=args.heads,
        visual_enc_depth=args.depth,
        visual_heads=args.heads,
        visual_image_size=args.image_size,
        visual_patch_size=args.patch_size,
        executor=args.executor,
    )
    text0 = jnp.ones((2, args.text_seq_len), jnp.int32)
    img0 = jnp.zeros((2, args.image_size, args.image_size, 3))
    params = jax.jit(clip.init)(jax.random.PRNGKey(0), text0, img0)["params"]
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"{n_params:,} parameters")

    state = TrainState.create(
        apply_fn=clip.apply, params=params,
        tx=make_optimizer(args.learning_rate, clip_grad_norm=1.0),
    )
    raw_step = make_clip_train_step(clip)
    step_fn = jax.jit(raw_step)
    spd = max(1, args.steps_per_dispatch)
    multi_fn = jax.jit(make_multi_step(raw_step, spd)) if spd > 1 else None
    logger = MetricsLogger(project="clip_tpu", config=vars(args),
                           debug=args.debug)
    meter = ThroughputMeter()

    rng = jax.random.PRNGKey(1)
    global_step = 0
    for epoch in range(args.epochs):
        for win in window_iter(batches(epoch), spd):
            prev_step = global_step
            # fold_in(step) keys (make_multi_step's prescription, as in
            # train_dalle.py): stream depends only on global_step, so runs
            # are invariant to --steps_per_dispatch and epoch tails
            if multi_fn is not None and len(win) == spd:
                stacked = stack_batches([
                    {"text": b["text"], "images": b["images"]} for b in win
                ])
                state, m = multi_fn(
                    state,
                    {k: jnp.asarray(v) for k, v in stacked.items()},
                    window_keys(rng, global_step, spd),
                )
                global_step += spd
            else:
                for batch in win:  # spd==1 or epoch tail: per-step replay
                    r = jax.random.fold_in(rng, global_step)
                    state, m = step_fn(
                        state,
                        {"text": jnp.asarray(batch["text"]),
                         "images": jnp.asarray(batch["images"])},
                        r,
                    )
                    global_step += 1
            if global_step // 10 > prev_step // 10:
                loss = float(m["loss"])
                print(f"epoch {epoch} step {global_step}: loss {loss:.4f}")
                logger.log({"loss": loss, "epoch": epoch}, step=global_step)
                sps = meter.update(global_step, args.batch_size)
                if sps:
                    logger.log({"samples_per_sec": sps}, step=global_step)
        save_clip_checkpoint(args.output, clip, state.params)
        print(f"epoch {epoch} done; checkpoint -> {args.output}")
    logger.finish()


if __name__ == "__main__":
    main()
