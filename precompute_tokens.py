#!/usr/bin/env python
"""Precompute frozen-VAE image tokens for a dataset (offline pass).

The reference encodes images through the frozen VAE inside every training
forward (`/root/reference/dalle_pytorch/dalle_pytorch.py:619-627`), paying
the encoder cost each step. The better TPU pattern (SURVEY.md §7 hard
parts) is to run the encode ONCE offline and train the transformer from
tokens — this CLI produces that artifact:

  python precompute_tokens.py --image_text_folder data/ --vae_path vae.npz \\
      --output tokens.npz
  python train_dalle.py --tokens_path tokens.npz --vae_path vae.npz ...

The .npz stores raw captions (tokenized at train time with whatever
tokenizer the run selects) plus int32 image tokens and the VAE geometry.
"""

from __future__ import annotations

import argparse


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--image_text_folder", type=str, required=True)
    p.add_argument("--vae_path", type=str, default=None)
    p.add_argument("--taming", action="store_true")
    p.add_argument("--vqgan_model_path", type=str, default=None)
    p.add_argument("--vqgan_config_path", type=str, default=None)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--output", type=str, default="tokens.npz")
    # tokenizer flags only affect the dataset's tokenize pass (which this
    # CLI ignores — captions are stored RAW); exposed so folder modes that
    # tokenize eagerly never error on long captions with exotic vocabs
    p.add_argument("--bpe_path", type=str, default=None)
    p.add_argument("--native", action="store_true")
    p.add_argument("--hug", action="store_true")
    p.add_argument("--chinese", action="store_true")
    p.add_argument("--yttm", action="store_true")
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax.numpy as jnp
    import numpy as np

    from dalle_pytorch_tpu.models.dvae import DiscreteVAE
    from dalle_pytorch_tpu.training.config import TrainConfig
    from dalle_pytorch_tpu.training.pipeline import (
        build_dataset, build_tokenizer, load_vae_checkpoint,
    )

    if args.taming:
        from dalle_pytorch_tpu.models.vae_io import VQGanVAE

        vae = VQGanVAE(args.vqgan_model_path, args.vqgan_config_path)
        vae_params = None
        vae_class = "VQGanVAE"
        encode = vae.get_codebook_indices
    else:
        assert args.vae_path, "--vae_path or --taming required"
        vae, vae_params = load_vae_checkpoint(args.vae_path)
        vae_class = "DiscreteVAE"
        encode = jax.jit(
            lambda imgs: vae.apply(
                {"params": vae_params}, imgs,
                method=DiscreteVAE.get_codebook_indices,
            )
        )

    cfg = TrainConfig()
    cfg.image_text_folder = args.image_text_folder
    cfg.truncate_captions = True
    for flag in ("bpe_path", "native", "hug", "chinese", "yttm"):
        if getattr(args, flag):
            setattr(cfg, flag, getattr(args, flag))
    tokenizer = build_tokenizer(cfg)
    dataset = build_dataset(cfg, tokenizer, image_size=vae.image_size)
    print(f"encoding {len(dataset)} samples at {vae.image_size}px")

    captions, token_chunks = [], []
    # every dataset's batch stream carries RAW caption strings — stored
    # verbatim, so the artifact is tokenizer-agnostic and lossless
    # (train-time runs tokenize them with whatever tokenizer they select)
    n_done = 0
    for batch in dataset.batches(args.batch_size, shuffle_seed=None,
                                 drop_last=False):
        toks = np.asarray(encode(jnp.asarray(batch["images"])), np.int32)
        token_chunks.append(toks)
        captions.extend(batch["captions"])
        n_done += toks.shape[0]
        if n_done % (args.batch_size * 10) < args.batch_size:
            print(f"  {n_done} done")

    image_tokens = np.concatenate(token_chunks, axis=0)
    np.savez_compressed(
        args.output,
        captions=np.array(captions),
        image_tokens=image_tokens,
        num_tokens=vae.num_tokens,
        image_size=vae.image_size,
        num_layers=vae.num_layers,
        vae_class_name=vae_class,
    )
    print(f"wrote {image_tokens.shape[0]} x {image_tokens.shape[1]} tokens "
          f"-> {args.output}")


if __name__ == "__main__":
    main()
