#!/usr/bin/env python
"""End-to-end rainbow example: dVAE -> DALLE -> exact token accuracy.

Script equivalent of the reference's `examples/rainbow_dalle.ipynb` (the
de-facto integration test of the reference, SURVEY.md §4): render a
synthetic dataset of colored shapes with compositional captions, train the
DiscreteVAE, inspect reconstructions, train DALLE on a train split, and
measure exact image-token-sequence accuracy on train vs. held-out captions
(the notebook reports 1.0 train / ~0.3 test at convergence; reach it by
raising --vae-steps/--dalle-steps). Like the notebook's 9,216-variation
cross-product, the dataset is caption-unique up to 9,216 samples — each
caption determines its image exactly, which is what makes exact-match 1.0
reachable. Past that count combos repeat with un-captioned jitter and
per-token accuracy becomes the cleaner signal.

Run (CPU ok for small settings):
  python examples/rainbow_dalle.py --num-samples 512 --dalle-steps 300
"""

from __future__ import annotations

import argparse
import itertools
import sys
import time
from pathlib import Path

import numpy as np

# runnable as `python examples/rainbow_dalle.py` without installing
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-samples", type=int, default=512)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--train-frac", type=float, default=0.7)
    p.add_argument("--vae-steps", type=int, default=300)
    p.add_argument("--dalle-steps", type=int, default=300)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--eval-samples", type=int, default=16)
    p.add_argument("--out-dir", type=str, default="rainbow_out")
    p.add_argument(
        "--steps-per-dispatch", type=int, default=1,
        help="optimizer steps scanned into one device dispatch for BOTH "
        "training loops (make_multi_step). Essential on synchronous-"
        "dispatch backends: at ~2s per dispatch round trip the 5500-step "
        "notebook-scale run cannot finish per-step, but 16 steps/dispatch "
        "brings it to minutes",
    )
    p.add_argument("--cpu", action="store_true", help="force CPU platform")
    return p.parse_args()


def main():
    args = parse_args()
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax

    from dalle_pytorch_tpu.data.rainbow import RainbowDataset
    from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer
    from dalle_pytorch_tpu.models.dvae import DiscreteVAE
    from dalle_pytorch_tpu.models.dalle import DALLE, generate_images_cached
    from dalle_pytorch_tpu.training.steps import (
        TrainState, make_optimizer, make_vae_train_step, make_dalle_train_step,
        make_multi_step, stack_batches, window_iter, window_keys,
    )
    from dalle_pytorch_tpu.utils.images import save_image_grid

    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    tokenizer = ByteTokenizer()
    # captions run up to 54 bytes ("small outline striped magenta rectangle
    # rotated thrice"); 64 keeps every caption un-truncated — truncation
    # would collapse distinct captions onto identical token sequences and
    # silently cap exact-match below 1.0
    text_seq_len = 64

    data = RainbowDataset(num_samples=args.num_samples, image_size=args.image_size)
    n_train = int(len(data) * args.train_frac)
    print(f"{len(data)} samples ({n_train} train), e.g. {data.caption(0)!r}")

    # ---------------------------------------------------------------- dVAE
    vae = DiscreteVAE(
        image_size=args.image_size, num_layers=2, num_tokens=256,
        codebook_dim=128, hidden_dim=64,
    )
    imgs0 = np.stack([data.image(i) for i in range(args.batch_size)])
    vparams = jax.jit(vae.init)(jax.random.PRNGKey(0), imgs0)["params"]
    vstate = TrainState.create(
        apply_fn=vae.apply, params=vparams, tx=make_optimizer(3e-4)
    )
    vstep = jax.jit(make_vae_train_step(vae))
    spd = max(1, args.steps_per_dispatch)
    vstep_multi = (
        jax.jit(make_multi_step(make_vae_train_step(vae), spd)) if spd > 1 else None
    )

    def vae_stream():
        epoch = 0
        while True:
            for b in data.batches(args.batch_size, tokenizer, text_seq_len,
                                  shuffle_seed=epoch):
                yield b
            epoch += 1

    # fold_in(step) keys, as make_multi_step prescribes: the random stream
    # is a pure function of the step index, so it is invariant to
    # --steps-per-dispatch. The temperature anneal below is applied at
    # window granularity (full-window decay up front), so temp can differ
    # from a per-step run by up to spd-1 decay factors mid-window
    vae_rng = jax.random.PRNGKey(1)
    t0, step = time.time(), 0
    temp = 1.0
    for win in window_iter(
        itertools.islice(vae_stream(), args.vae_steps), spd
    ):
        prev = step
        keys = window_keys(vae_rng, step, len(win))
        if vstep_multi is not None and len(win) == spd:
            # per-window anneal: the product of n per-step decays applied
            # up front (`train_vae.py:278` semantics at window granularity)
            temp = max(temp * float(np.exp(-1e-3 * len(win))), 0.5)
            vstate, m = vstep_multi(
                vstate,
                jnp.asarray(stack_batches([b["images"] for b in win])),
                keys, jnp.float32(temp),
            )
            step += len(win)
        else:
            for b, r in zip(win, keys):
                # gumbel temperature annealing (`train_vae.py:278` semantics)
                temp = max(temp * np.exp(-1e-3), 0.5)
                vstate, m = vstep(vstate, jnp.asarray(b["images"]), r,
                                  jnp.float32(temp))
                step += 1
        if step // 100 > prev // 100:
            print(f"vae step {step}: loss {float(m['loss']):.4f}")
    print(f"dVAE trained in {time.time()-t0:.0f}s")

    # hard reconstructions (codebook roundtrip), like notebook cells 20-22
    toks = vae.apply({"params": vstate.params}, imgs0,
                     method=DiscreteVAE.get_codebook_indices)
    recon = vae.apply({"params": vstate.params}, toks, method=DiscreteVAE.decode)

    # the decoder works in normalized space (its loss targets norm(img));
    # denormalize to image space before comparing / saving
    means = np.asarray(vae.normalization[0][:3])
    stds = np.asarray(vae.normalization[1][:3])
    denorm = lambda x: np.asarray(x) * stds + means
    mse = float(np.mean((denorm(recon) - imgs0) ** 2))
    print(f"hard-recon MSE: {mse:.4f}; codebook usage: "
          f"{len(np.unique(np.asarray(toks)))}/{vae.num_tokens}")
    save_image_grid(denorm(recon), out_dir / "recon.png")

    # --------------------------------------------------------------- DALLE
    fmap = vae.fmap_size
    model = DALLE(
        dim=128, depth=4, heads=4, dim_head=32,
        num_image_tokens=vae.num_tokens, image_fmap_size=fmap,
        num_text_tokens=tokenizer.vocab_size, text_seq_len=text_seq_len,
        shift_tokens=True, rotary_emb=True,
    )
    text0 = jnp.asarray(tokenizer.tokenize(
        [data.caption(i) for i in range(2)], text_seq_len, truncate_text=True))
    dparams = jax.jit(model.init)(jax.random.PRNGKey(2), text0, toks[:2])["params"]
    dstate = TrainState.create(
        apply_fn=model.apply, params=dparams,
        tx=make_optimizer(3e-4, clip_grad_norm=0.5),
    )
    dstep = jax.jit(make_dalle_train_step(model, vae=vae))
    dstep_multi = (
        jax.jit(make_multi_step(make_dalle_train_step(model, vae=vae), spd))
        if spd > 1 else None
    )

    def dalle_batch(step):
        # draw minibatches from the train split only; the tail of the
        # dataset stays held out for the accuracy bar below
        sel = np.random.RandomState(step).choice(
            n_train, size=min(args.batch_size, n_train), replace=False
        )
        return {
            "text": np.asarray(tokenizer.tokenize(
                [data.caption(int(i)) for i in sel], text_seq_len,
                truncate_text=True)),
            "images": np.stack([data.image(int(i)) for i in sel]),
        }

    t0 = time.time()
    dalle_rng = jax.random.PRNGKey(3)
    step = 0
    for win in window_iter(
        (dalle_batch(s) for s in range(1, args.dalle_steps + 1)), spd
    ):
        prev = step
        keys = window_keys(dalle_rng, step, len(win))
        if dstep_multi is not None and len(win) == spd:
            stacked = stack_batches(win)
            dstate, m = dstep_multi(
                dstate,
                {k: jnp.asarray(v) for k, v in stacked.items()},
                keys, vstate.params,
            )
            step += len(win)
        else:
            for batch, r in zip(win, keys):
                dstate, m = dstep(
                    dstate,
                    {k: jnp.asarray(v) for k, v in batch.items()}, r,
                    vstate.params,
                )
                step += 1
        if step // 100 > prev // 100:
            print(f"dalle step {step}: loss {float(m['loss']):.4f}")
    print(f"DALLE trained in {time.time()-t0:.0f}s")

    # ------------------------- exact token accuracy (notebook cells 43-44)
    def exact_accuracy(indices):
        texts = [data.caption(i) for i in indices]
        gt_imgs = np.stack([data.image(i) for i in indices])
        gt = np.asarray(vae.apply({"params": vstate.params}, gt_imgs,
                                  method=DiscreteVAE.get_codebook_indices))
        ids = jnp.asarray(tokenizer.tokenize(texts, text_seq_len,
                                             truncate_text=True))
        # near-greedy sampling for determinism
        sampled = generate_images_cached(
            model, {"params": dstate.params}, jax.random.PRNGKey(9), ids,
            temperature=1e-4, filter_thres=0.999,
        )
        sampled = np.asarray(sampled)
        exact = float((sampled == gt).all(axis=1).mean())
        per_tok = float((sampled == gt).mean())
        return exact, per_tok, sampled

    train_idx = list(range(min(args.eval_samples, n_train)))
    test_idx = list(range(n_train, min(n_train + args.eval_samples, len(data))))
    tr_exact, tr_tok, sampled = exact_accuracy(train_idx)
    report = f"train: exact {tr_exact:.2f}, per-token {tr_tok:.3f}"
    te_exact = te_tok = None
    if test_idx:
        te_exact, te_tok, _ = exact_accuracy(test_idx)
        report += f" | test: exact {te_exact:.2f}, per-token {te_tok:.3f}"
    print(report)
    print("(reference notebook bar at convergence: exact 1.0 train / ~0.3 test)")
    # machine-readable line
    import json

    print(json.dumps({
        "metric": "rainbow_convergence",
        "num_samples": len(data),
        "dalle_steps": args.dalle_steps,
        "train_exact": round(tr_exact, 4),
        "train_per_token": round(tr_tok, 4),
        "test_exact": None if te_exact is None else round(te_exact, 4),
        "test_per_token": None if te_tok is None else round(te_tok, 4),
        "device": jax.devices()[0].device_kind,
        "notebook_bar": "train exact 1.0 / test ~0.3",
    }))

    gen = vae.apply({"params": vstate.params}, jnp.asarray(sampled),
                    method=DiscreteVAE.decode)
    save_image_grid(denorm(gen), out_dir / "generated.png")
    print(f"wrote {out_dir}/recon.png and {out_dir}/generated.png")


if __name__ == "__main__":
    main()
