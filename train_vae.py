#!/usr/bin/env python
"""Train the DiscreteVAE (TPU-native train_vae).

Equivalent of `/root/reference/train_vae.py`: dVAE training with gumbel
temperature annealing (`:278`), exponential LR decay (`:158`),
reconstruction grids + codebook-usage histogram every 100 steps
(`:252-271`), per-epoch checkpoints. The whole optimizer step is one jitted
XLA program, sharded over the data axes of the device mesh.

Usage:
  python train_vae.py --image_folder <dir|rainbow[:N]> [--config cfg.yaml]
      [--set vae.num_tokens=1024] [--set learning_rate=1e-3] ...
"""

from __future__ import annotations

import argparse
import math
import time
from pathlib import Path

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", type=str, default=None, help="YAML config file")
    p.add_argument("--image_folder", type=str, default=None)
    p.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="config override, e.g. --set vae.num_tokens=1024",
    )
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--batch_size", type=int, default=None)
    p.add_argument("--learning_rate", type=float, default=None)
    p.add_argument("--output", type=str, default="vae.npz")
    p.add_argument("--lr_decay_rate", type=float, default=0.98)
    p.add_argument("--debug", action="store_true")
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax.numpy as jnp

    from dalle_pytorch_tpu.parallel import (
        make_mesh, batch_sharding, state_shardings, is_root, put_host_batch,
        gather_to_host,
    )
    from dalle_pytorch_tpu.parallel import initialize_distributed

    # multi-host rendezvous (launch.py env vars / TPU pod auto); no-op
    # single-host. Must run before the first device query.
    initialize_distributed()
    from dalle_pytorch_tpu.utils.device import log_device

    log_device()
    from dalle_pytorch_tpu.training import (
        TrainState, make_optimizer, make_vae_train_step, make_multi_step,
        window_keys,
        stack_batches, window_iter, ExponentialDecay, set_learning_rate,
        get_learning_rate,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P
    from dalle_pytorch_tpu.training.config import load_config
    from dalle_pytorch_tpu.training.metrics import MetricsLogger, ThroughputMeter
    from dalle_pytorch_tpu.training.pipeline import (
        build_tokenizer, build_dataset, vae_from_config, save_vae_checkpoint,
    )

    cfg = load_config(args.config, args.set)
    for k in ("epochs", "batch_size", "learning_rate"):
        v = getattr(args, k)
        if v is not None:
            setattr(cfg, k, v)
    if args.image_folder:
        cfg.image_text_folder = args.image_folder
    if args.debug:
        cfg.debug = True

    vae = vae_from_config(cfg.vae)
    tokenizer = build_tokenizer(cfg)
    dataset = build_dataset(cfg, tokenizer, image_size=cfg.vae.image_size)
    print(f"{len(dataset)} images for training")

    rng = jax.random.PRNGKey(cfg.seed)
    rng, init_rng, gumbel_rng = jax.random.split(rng, 3)
    sample = jnp.zeros((1, cfg.vae.image_size, cfg.vae.image_size, cfg.vae.channels))
    params = vae.init({"params": init_rng, "gumbel": gumbel_rng}, sample)["params"]
    state = TrainState.create(
        apply_fn=vae.apply, params=params, tx=make_optimizer(cfg.learning_rate)
    )

    mesh = make_mesh(
        dp=cfg.mesh.dp, fsdp=cfg.mesh.fsdp, tp=cfg.mesh.tp, sp=cfg.mesh.sp
    )
    state_sh = state_shardings(state, mesh)
    img_sh = batch_sharding(mesh, extra_dims=3)
    state = jax.device_put(state, state_sh)
    raw_step = make_vae_train_step(vae, grad_accum=cfg.ga_steps)
    step_fn = jax.jit(
        raw_step,
        in_shardings=(state_sh, img_sh, None, None),
        out_shardings=(state_sh, None),
        donate_argnums=0,
    )
    # steps_per_dispatch>1: scan T steps into one dispatch (see
    # train_dalle.py). The gumbel temp rides as a per-dispatch constant,
    # updated per crossed 100-step boundary AFTER the window — so when
    # steps_per_dispatch does not divide 100, up to spd-1 steps of the
    # crossing window still run at the previous temperature/LR relative
    # to a single-step run (window-granularity anneal).
    steps_per_dispatch = max(1, int(cfg.steps_per_dispatch))
    multi_fn = None
    if steps_per_dispatch > 1:
        win_img_sh = NamedSharding(mesh, P(None, *img_sh.spec))
        multi_fn = jax.jit(
            make_multi_step(raw_step, steps_per_dispatch),
            in_shardings=(state_sh, win_img_sh, None, None),
            out_shardings=(state_sh, None),
            donate_argnums=0,
        )

    logger = MetricsLogger(
        project=cfg.project, config={"cli": "train_vae"},
        enabled=is_root(), debug=cfg.debug, out_dir=str(Path(cfg.output_dir) / "vae_logs"),
    )
    meter = ThroughputMeter()
    sched = ExponentialDecay(gamma=args.lr_decay_rate) if cfg.lr_decay else None

    temp = cfg.vae.temperature
    global_step = 0
    last_params_h = None
    shard = (jax.process_index(), jax.process_count())
    from dalle_pytorch_tpu.data.prefetch import Prefetcher

    for epoch in range(cfg.epochs):
        # background batch assembly + device transfer ahead of the step
        # (same input/compute overlap as train_dalle.py)
        def assemble(b):
            # (device_batch, host-local head) — recon-grid logging must not
            # fetch the global array (non-addressable on multi-host)
            return put_host_batch(b["images"], img_sh), np.asarray(b["images"][:4])

        def assemble_window(win):
            if len(win) < steps_per_dispatch:  # epoch tail: per-step replay
                return [assemble(b) for b in win], None
            stacked = stack_batches([b["images"] for b in win])
            return (
                put_host_batch(stacked, win_img_sh),
                np.asarray(win[0]["images"][:4]),
            )

        raw_batches = dataset.batches(
            cfg.batch_size, shuffle_seed=epoch, shard=shard
        )
        if steps_per_dispatch > 1:
            batch_iter = Prefetcher(
                window_iter(raw_batches, steps_per_dispatch),
                transform=assemble_window, depth=cfg.prefetch_depth,
            )
        else:
            batch_iter = Prefetcher(
                raw_batches, transform=assemble, depth=cfg.prefetch_depth
            )
        try:
            for images, images_head in batch_iter:
                prev_step = global_step
                # fold_in(step) keys (make_multi_step's prescription, as in
                # train_dalle.py): the stream is a pure function of the
                # global step, so runs are reproducible across
                # steps_per_dispatch settings and epoch tails
                if multi_fn is not None and not isinstance(images, list):
                    keys = window_keys(rng, global_step, steps_per_dispatch)
                    state, metrics = multi_fn(state, images, keys, jnp.float32(temp))
                    r = keys[-1]  # for the recon-grid gumbel sample below
                    global_step += steps_per_dispatch
                else:
                    singles = (
                        images if isinstance(images, list)
                        else [(images, images_head)]
                    )
                    for img_b, head_b in singles:
                        images_head = head_b
                        r = jax.random.fold_in(rng, global_step)
                        state, metrics = step_fn(state, img_b, r, jnp.float32(temp))
                        global_step += 1

                def crossed(interval):
                    return bool(interval) and (
                        global_step // interval > prev_step // interval
                    )

                log = {}
                if crossed(100):
                    # recon grids: soft (gumbel) + hard (argmax->decode),
                    # computed from the host-local head rows
                    k = images_head.shape[0]
                    head = jnp.asarray(images_head)
                    soft = vae.apply(
                        {"params": state.params}, head, temp=temp,
                        rngs={"gumbel": r},
                    )
                    codes = vae.apply(
                        {"params": state.params}, head,
                        method=type(vae).get_codebook_indices,
                    )
                    hard = vae.apply({"params": state.params}, codes, method=type(vae).decode)
                    # codebook usage histogram (`train_vae.py:256-260`)
                    usage = np.bincount(
                        np.asarray(codes).ravel(), minlength=cfg.vae.num_tokens
                    )
                    grid = np.concatenate(
                        [images_head, np.asarray(soft) * 0.5 + 0.5,
                         np.asarray(hard) * 0.5 + 0.5], axis=0
                    )
                    logger.log_images(grid, "orig | soft | hard", "recons", global_step)
                    # temperature anneal (`train_vae.py:278`) + LR decay:
                    # one application PER crossed 100-step boundary (a
                    # steps_per_dispatch>100 window can span several), each
                    # at its boundary's step value, so the schedule matches
                    # a single-step run regardless of window size
                    for boundary in range(
                        prev_step // 100 + 1, global_step // 100 + 1
                    ):
                        temp = max(
                            temp * math.exp(-cfg.vae.anneal_rate * boundary * 100),
                            cfg.vae.temp_min,
                        )
                        if sched is not None:
                            state = set_learning_rate(
                                state, sched.step(0.0, get_learning_rate(state))
                            )
                    log.update(
                        temperature=temp,
                        lr=get_learning_rate(state),
                        codebook_usage_frac=float((usage > 0).mean()),
                    )

                rate = meter.update(global_step, cfg.batch_size)
                if rate is not None:
                    log["sample_per_sec"] = rate
                if crossed(cfg.log_every_n_steps):
                    log["loss"] = float(metrics["loss"])
                    print(epoch, global_step, f"loss - {log['loss']:.5f}")
                if log:
                    logger.log(log, step=global_step)

        finally:
            batch_iter.close()

        last_params_h = gather_to_host(state.params)  # collective; all hosts
        if is_root():
            save_vae_checkpoint(args.output, vae, last_params_h, epoch)
            print(f"epoch {epoch} done; checkpoint -> {args.output}")
            # per-epoch model artifact (`train_vae.py:305-310`)
            logger.log_model_artifact(args.output, "trained-vae")

    if last_params_h is None:  # epochs == 0: the loop never gathered
        last_params_h = gather_to_host(state.params)
    if is_root():
        save_vae_checkpoint(args.output, vae, last_params_h, cfg.epochs)
    logger.finish()
    from dalle_pytorch_tpu.utils.compile_guard import log_compiles

    log_compiles()


if __name__ == "__main__":
    main()
