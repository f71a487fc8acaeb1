#!/usr/bin/env python
"""Train DALL-E (TPU-native train_dalle).

Equivalent of `/root/reference/train_dalle.py`: resumes/builds the frozen
VAE and the DALLE transformer, streams host-sharded batches, runs the
jitted+sharded train step (forward and optional inverse objectives,
`:509-518`), logs loss/throughput/samples, checkpoints with rotation, and
steps a plateau LR scheduler per epoch (`:344-353,589-590`).

Usage:
  python train_dalle.py --image_text_folder <dir|rainbow[:N]|shards.tar>
      [--config cfg.yaml] [--exp ff] [--vae_path vae.npz]
      [--set model.depth=4] [--set mesh.fsdp=2] ...
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", type=str, default=None)
    p.add_argument("--image_text_folder", type=str, default=None)
    p.add_argument("--tokens_path", type=str, default=None,
                   help="precompute_tokens.py artifact; trains from tokens")
    p.add_argument("--vae_path", type=str, default=None)
    p.add_argument("--dalle_path", type=str, default=None, help="resume checkpoint")
    p.add_argument(
        "--resume", action="store_true",
        help="resume full train state from the latest Orbax step checkpoint "
             "in output_dir (preemption recovery)",
    )
    p.add_argument("--taming", action="store_true")
    p.add_argument("--exp", type=str, default=None, choices=["f", "ff", "r", "ro"])
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--batch_size", type=int, default=None)
    p.add_argument("--learning_rate", type=float, default=None)
    p.add_argument("--debug", action="store_true")
    p.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="config override, e.g. --set model.depth=4",
    )
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import generate_images
    from dalle_pytorch_tpu.models.dvae import DiscreteVAE
    from dalle_pytorch_tpu.parallel import (
        MESH_AXES, make_mesh, batch_sharding, state_shardings,
        partition_params, is_root, put_host_batch, gather_to_host,
    )
    from dalle_pytorch_tpu.parallel import initialize_distributed

    # multi-host rendezvous (launch.py env vars / TPU pod auto); no-op
    # single-host. Must run before the first device query.
    initialize_distributed()
    from dalle_pytorch_tpu.utils.device import log_device, log_placement

    device = log_device()
    from dalle_pytorch_tpu.training import (
        TrainState, make_optimizer, make_dalle_train_step, make_multi_step,
        window_keys,
        stack_batches, window_iter, ReduceLROnPlateau, set_learning_rate,
        get_learning_rate,
    )
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from dalle_pytorch_tpu.data.prefetch import Prefetcher
    from dalle_pytorch_tpu.obs import scopes
    from dalle_pytorch_tpu.obs.tracing import host_span
    from dalle_pytorch_tpu.training.config import load_config
    from dalle_pytorch_tpu.training.checkpoint import CheckpointManager
    from dalle_pytorch_tpu.training.metrics import (
        MetricsLogger, ThroughputMeter, ProfilerHook,
    )
    from dalle_pytorch_tpu.training.pipeline import (
        build_tokenizer, build_dataset, build_vae, dalle_from_config,
        save_dalle_checkpoint, load_dalle_checkpoint, restore_opt_state,
    )
    from dalle_pytorch_tpu.utils import param_count

    cfg = load_config(args.config, args.set)
    resume_meta = None
    opt_leaves_resume = None
    if args.dalle_path:  # RESUME (`train_dalle.py:139-161`)
        cfg, dalle_params_resume, vae_params_resume, resume_meta, \
            opt_leaves_resume = load_dalle_checkpoint(args.dalle_path)
        for ov in args.set:
            k, v = ov.split("=", 1)
            from dalle_pytorch_tpu.training.config import _set_dotted

            _set_dotted(cfg, k.strip(), v.strip())
    for k in ("epochs", "batch_size", "learning_rate", "image_text_folder",
              "tokens_path", "vae_path", "exp"):
        v = getattr(args, k)
        if v is not None:
            setattr(cfg, k, v)
    if args.taming:
        cfg.taming = True
    if args.debug:
        cfg.debug = True
    cfg.resolve()

    tokenizer = build_tokenizer(cfg)
    vae, vae_params = build_vae(cfg)
    if args.dalle_path and vae_params_resume is not None:
        vae_params = vae_params_resume
    image_fmap_size = vae.image_size // (2 ** vae.num_layers)
    if cfg.tokens_path:
        # offline-precomputed tokens (precompute_tokens.py): the train step
        # skips the VAE encode entirely — the better TPU pattern
        from dalle_pytorch_tpu.data.loader import TokenDataset

        dataset = TokenDataset(
            cfg.tokens_path, tokenizer, cfg.model.text_seq_len
        )
        assert dataset.num_tokens == vae.num_tokens, (
            f"tokens were precomputed with a {dataset.num_tokens}-code VAE "
            f"but --vae_path has {vae.num_tokens}"
        )
        assert dataset.image_tokens.shape[1] == image_fmap_size**2, (
            f"tokens artifact has {dataset.image_tokens.shape[1]} tokens per "
            f"image (VAE {dataset.image_size}px/{dataset.num_layers} layers) "
            f"but the model expects {image_fmap_size}^2 = {image_fmap_size**2} "
            f"— wrong --tokens_path for this VAE?"
        )
    else:
        dataset = build_dataset(cfg, tokenizer, image_size=vae.image_size)
    try:
        print(f"{len(dataset)} image-text pairs for training")
    except TypeError:  # streaming tar shards have no cheap length
        print("streaming dataset for training (length unknown)")

    # mesh before model: attn_impl="ring" (mesh.sp > 1) shards the model's
    # attention over the sp axis, so the model needs the mesh at build time
    pp = max(1, int(getattr(cfg.mesh, "pp", 1)))
    if pp > 1:
        # pipeline parallelism: pure-pp 5-axis mesh (dp/fsdp/tp/sp all 1,
        # 'pp' carrying the stages) so the standard batch/state shardings
        # (replication here) and gpipe's 'pp' ppermute share one mesh
        if cfg.model.executor != "scan":
            raise ValueError(
                "mesh.pp > 1 requires model.executor=scan (the pipeline "
                "runs the depth-stacked scan layout)"
            )
        if cfg.model.attn_dropout or cfg.model.ff_dropout:
            raise ValueError(
                "mesh.pp > 1 requires attn_dropout=ff_dropout=0: the pp "
                "trunk is deterministic by design (models/dalle.py); use "
                "dp/fsdp/tp for dropout training"
            )
        if cfg.mode == "forward_reverse_partial":
            raise ValueError(
                "mesh.pp > 1 cannot run forward_reverse_partial (the "
                "pipeline owns the layer order; reversed-order execution "
                "is a sequential-trunk feature)"
            )
        if cfg.model.depth % pp:
            raise ValueError(f"model.depth={cfg.model.depth} not divisible by mesh.pp={pp}")
        micro = max(1, int(cfg.mesh.pp_micro))
        if (cfg.batch_size // max(1, cfg.ga_steps)) % micro:
            raise ValueError(
                f"mesh.pp_micro={micro} must divide the per-accum-step "
                f"batch ({cfg.batch_size}//{cfg.ga_steps}); lower pp_micro "
                "or raise batch_size"
            )
        if cfg.mesh.fsdp != 1 or cfg.mesh.tp != 1 or cfg.mesh.sp != 1 or (
            cfg.mesh.dp not in (1, -1)
        ):
            raise ValueError(
                "mesh.pp > 1 is a pure-pp mesh: set dp/fsdp/tp/sp to 1 "
                "(compose dp x pp via parallel/gpipe.pipeline_layers)"
            )
        devices = jax.devices()
        if pp > len(devices):
            raise ValueError(f"mesh.pp={pp} > {len(devices)} devices")
        if pp < len(devices):
            print(
                f"WARNING: mesh.pp={pp} uses {pp} of {len(devices)} devices"
                " — the rest sit idle (pure-pp mesh; compose dp x pp via "
                "parallel/gpipe.pipeline_layers for full utilization)"
            )
        mesh = Mesh(
            np.asarray(devices[:pp]).reshape(1, 1, 1, 1, pp),
            MESH_AXES + ("pp",),
        )
    else:
        devices = jax.devices()
        want = cfg.mesh.dp * cfg.mesh.fsdp * cfg.mesh.tp * cfg.mesh.sp
        if 0 < want < len(devices):
            # a fully explicit mesh smaller than the host (the pp branch's
            # and build_serving_mesh's convention): e.g. mesh.dp=1 runs the
            # single-device reference on a four-chip host
            print(f"WARNING: mesh uses {want} of {len(devices)} devices — "
                  "the rest sit idle (set mesh.dp=-1 to absorb them)")
            devices = devices[:want]
        mesh = make_mesh(
            dp=cfg.mesh.dp, fsdp=cfg.mesh.fsdp, tp=cfg.mesh.tp, sp=cfg.mesh.sp,
            devices=devices,
        )
    model = dalle_from_config(
        cfg,
        num_image_tokens=vae.num_tokens,
        image_fmap_size=image_fmap_size,
        vocab_size=max(tokenizer.vocab_size, 1),
        sp_mesh=mesh,
    )

    # pipeline-parallel trunk: built OUTSIDE model.apply (flax intercepts
    # module construction inside a parent scope); the train step feeds it
    # the live transformer params each call
    pp_trunk = None
    if pp > 1:
        from dalle_pytorch_tpu.models.transformer import (
            Transformer, make_pipeline_trunk,
        )

        pp_trunk = make_pipeline_trunk(
            Transformer(**model.transformer_kwargs()), mesh, n_micro=micro
        )

    rng = jax.random.PRNGKey(cfg.seed)
    rng, init_rng = jax.random.split(rng)
    t0 = jnp.zeros((1, cfg.model.text_seq_len), jnp.int32)
    i0 = jnp.zeros((1, image_fmap_size**2), jnp.int32)
    params = model.init(init_rng, t0, i0)["params"]
    if args.dalle_path:
        params = dalle_params_resume
    print(f"{param_count(params):,} parameters")

    state = TrainState.create(
        apply_fn=model.apply, params=params,
        tx=make_optimizer(cfg.learning_rate, clip_grad_norm=cfg.clip_grad_norm),
    )
    resume_train = (resume_meta or {}).get("train", {})
    if opt_leaves_resume is not None:
        # full-state resume: Adam moments + injected lr + step counter come
        # back exactly (`/root/reference/train_dalle.py:330-338`)
        state = state.replace(
            opt_state=restore_opt_state(state.opt_state, opt_leaves_resume),
            step=int(resume_train.get("global_step", 0)),
        )

    state_sh = state_shardings(state, mesh)
    txt_sh = batch_sharding(mesh, extra_dims=1)
    state = jax.device_put(state, state_sh)
    log_placement(mesh.shape)

    in_step_encode = isinstance(vae, DiscreteVAE) and not cfg.tokens_path
    if in_step_encode:
        img_sh = batch_sharding(mesh, extra_dims=3)
        vae_sh = partition_params(vae_params, mesh)
        vae_params = jax.device_put(vae_params, vae_sh)
        batch_shardings = {"text": txt_sh, "images": img_sh}
        raw_step = make_dalle_train_step(
            model, vae=vae, mode=cfg.mode, grad_accum=cfg.ga_steps,
            null_cond_prob=cfg.null_cond_prob, pp_trunk=pp_trunk,
        )
        extra_shardings = (vae_sh,)
    else:
        # pretrained torch-backed VAE: encode on host, feed tokens
        batch_shardings = {"text": txt_sh, "image_tokens": txt_sh}
        raw_step = make_dalle_train_step(
            model, mode=cfg.mode, grad_accum=cfg.ga_steps,
            null_cond_prob=cfg.null_cond_prob, pp_trunk=pp_trunk,
        )
        extra_shardings = ()
    # remembered at its first dispatch, shardings and all, so that a
    # profiler capture of this run can be reduced by component (obs/scopes.py)
    step_fn = scopes.remembering(jax.jit(
        raw_step,
        in_shardings=(state_sh, batch_shardings, None) + extra_shardings,
        out_shardings=(state_sh, None),
        donate_argnums=0,
    ))
    # steps_per_dispatch>1: scan T optimizer steps into one dispatch
    # (make_multi_step) — host-loop elimination; window batches get a
    # leading unsharded step axis on top of the per-step batch specs
    steps_per_dispatch = max(1, int(cfg.steps_per_dispatch))
    multi_fn = None
    if steps_per_dispatch > 1:
        win_shardings = jax.tree.map(
            lambda sh: NamedSharding(mesh, P(None, *sh.spec)),
            batch_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )
        multi_fn = scopes.remembering(jax.jit(
            make_multi_step(raw_step, steps_per_dispatch),
            in_shardings=(state_sh, win_shardings, None) + extra_shardings,
            out_shardings=(state_sh, None),
            donate_argnums=0,
        ))

    run_dir = Path(cfg.output_dir)
    ckpt = CheckpointManager(run_dir / "dalle_ckpt", keep_n=cfg.keep_n_checkpoints)
    orbax_resume_meta = None
    if args.resume:
        restored, orbax_resume_meta, rstep = ckpt.restore(state)
        if restored is not None:
            state = restored
            print(f"resumed full train state from Orbax step {rstep}")
        else:
            print("no Orbax checkpoint found in output_dir; starting fresh")
    logger = MetricsLogger(
        project=cfg.wandb_name, config={"cli": "train_dalle"},
        enabled=is_root(), debug=cfg.debug, out_dir=str(run_dir / "logs"),
        entity=cfg.wandb_entity,
    )
    from dalle_pytorch_tpu.utils.flops import (
        dalle_train_flops_per_sample, lookup_peaks, mfu as flops_mfu,
    )

    # utilization is only defined against a chip with a published peak
    log_mfu = lookup_peaks(device["kind"]) is not None

    # mode-aware: forward_forward / forward_reverse_partial run two full
    # fwd+bwd passes per sample, so the MFU numerator counts both
    flops_per_sample = dalle_train_flops_per_sample(model, mode=cfg.mode)
    dvae_decode = None  # lazily-jitted sample decode
    meter = ThroughputMeter()
    profiler = ProfilerHook(cfg.flops_profiler)
    plateau = ReduceLROnPlateau() if cfg.lr_decay else None
    if plateau is not None and resume_train.get("plateau"):
        # scheduler state resumes too (`train_dalle.py:354-355`)
        plateau.load_state_dict(resume_train["plateau"])

    from dalle_pytorch_tpu.training.pipeline import dvae_hparams

    def export(path: Path, epoch: int):
        # gather_to_host is a COLLECTIVE when params/opt are sharded
        # across hosts (fsdp/tp) — every process runs it; only root writes
        params_h = gather_to_host(state.params)
        vae_h = None if not in_step_encode else gather_to_host(vae_params)
        opt_h = gather_to_host(state.opt_state)
        if is_root():
            save_dalle_checkpoint(
                str(path), cfg, params_h,
                vae_h,
                epoch, type(vae).__name__,
                vae_hparams=dvae_hparams(vae) if in_step_encode else None,
                opt_state=opt_h,
                train_meta={
                    "global_step": global_step,
                    "plateau": plateau.state_dict() if plateau else None,
                },
            )

    # fail-early smoke save (`train_dalle.py:488-491`)
    out_file = run_dir / f"{cfg.dalle_output_file_name}.npz"
    resume_epoch = (resume_meta or {}).get("epoch", 0)
    global_step = int(resume_train.get("global_step", 0))
    if orbax_resume_meta:
        resume_epoch = int(orbax_resume_meta.get("epoch", resume_epoch))
        global_step = int(orbax_resume_meta.get("step", global_step))
        if plateau is not None and orbax_resume_meta.get("plateau"):
            plateau.load_state_dict(orbax_resume_meta["plateau"])
    export(out_file, resume_epoch)
    shard = (jax.process_index(), jax.process_count())
    stop = False
    # mid-epoch resume: skip the batches the checkpointed run already
    # consumed this epoch, so resume ≡ uninterrupted (no double-training)
    skip_batches = int((orbax_resume_meta or {}).get("epoch_batch", 0))
    for epoch in range(resume_epoch, cfg.epochs):
        if stop:
            break
        epoch_losses = []
        last_loss = None
        epoch_batch = 0
        def host_arrays(batch):
            """Per-batch host-side prep: captions split off (the device
            pytree must match the step's in_shardings), sample-logging head
            row fetched while host-local, torch-backed VAE encoded."""
            caps = batch.get("captions")
            # host-local head row for root-only sample logging: the global
            # dev batch spans non-addressable devices on multi-host, so it
            # cannot be fetched there
            text_head = np.asarray(batch["text"][:1])
            if in_step_encode:
                host = {"text": batch["text"], "images": batch["images"]}
            else:
                if "image_tokens" in batch:  # precomputed (TokenDataset)
                    tokens = batch["image_tokens"]
                else:  # pretrained torch-backed VAE: host-side encode
                    tokens = vae.get_codebook_indices(jnp.asarray(batch["images"]))
                host = {"text": batch["text"], "image_tokens": tokens}
            return host, caps, text_head

        def assemble(batch):
            """Host->device batch assembly, run ahead of the step in the
            prefetch thread so decode/tokenize/transfer overlap compute
            (the DataLoader-workers equivalent, ref `:309-316`)."""
            host, caps, text_head = host_arrays(batch)
            dev = {
                k: put_host_batch(v, batch_shardings[k]) for k, v in host.items()
            }
            return dev, caps, text_head

        def assemble_window(win):
            """steps_per_dispatch batches -> one [T, ...] device window
            (one transfer per dispatch). An epoch-tail window shorter than
            T is assembled per-batch and replayed through the single-step
            program — same RNG/cadence semantics, no second window-sized
            compile."""
            if len(win) < steps_per_dispatch:
                return [assemble(b) for b in win], None, None
            hosts, caps, heads = zip(*[host_arrays(b) for b in win])
            stacked = stack_batches(list(hosts))
            dev = {
                k: put_host_batch(v, win_shardings[k]) for k, v in stacked.items()
            }
            return dev, caps[0], heads[0]

        raw_batches = dataset.batches(
            cfg.batch_size, shuffle_seed=cfg.seed + epoch, shard=shard,
            start_batch=skip_batches if epoch == resume_epoch else 0,
        )
        if steps_per_dispatch > 1:
            batch_iter = Prefetcher(
                window_iter(raw_batches, steps_per_dispatch),
                transform=assemble_window,
                depth=cfg.prefetch_depth,
            )
        else:
            batch_iter = Prefetcher(
                raw_batches, transform=assemble, depth=cfg.prefetch_depth
            )
        if epoch == resume_epoch and skip_batches:
            epoch_batch = skip_batches
            # carry the interrupted epoch's loss history so the epoch-end
            # plateau step sees the same inputs as an uninterrupted run —
            # even when the skip consumes the whole epoch
            epoch_losses = list(orbax_resume_meta.get("epoch_losses") or [])
            if orbax_resume_meta.get("last_loss") is not None:
                last_loss = float(orbax_resume_meta["last_loss"])
        try:
            for dev_batch, captions, text_head in batch_iter:
                profiler.before_step(global_step)
                prev_step = global_step
                # fold_in(global_step), not sequential split: the key stream
                # is a pure function of the step index, so a mid-epoch
                # resume replays the exact dropout/null-cond randomness an
                # uninterrupted run would use — and the multi-step window
                # passes the SAME per-step folded keys stacked, so
                # steps_per_dispatch never changes the randomness
                if multi_fn is not None and not isinstance(dev_batch, list):
                    keys = window_keys(rng, global_step, steps_per_dispatch)
                    with host_span("train.dispatch", steps=steps_per_dispatch):
                        if in_step_encode:
                            state, metrics = multi_fn(state, dev_batch, keys, vae_params)
                        else:
                            state, metrics = multi_fn(state, dev_batch, keys)
                    global_step += steps_per_dispatch
                    epoch_batch += steps_per_dispatch
                else:
                    singles = (
                        dev_batch if isinstance(dev_batch, list)
                        else [(dev_batch, captions, text_head)]
                    )
                    for dev_b, caps_i, head_i in singles:
                        captions, text_head = caps_i, head_i
                        r = jax.random.fold_in(rng, global_step)
                        with host_span("train.dispatch", steps=1):
                            if in_step_encode:
                                state, metrics = step_fn(state, dev_b, r, vae_params)
                            else:
                                state, metrics = step_fn(state, dev_b, r)
                        global_step += 1
                        epoch_batch += 1

                def crossed(interval):
                    # cadences fire on interval CROSSINGS so a >1-step
                    # dispatch can't step over them; with stride 1 this is
                    # exactly the old `global_step % interval == 0`
                    return bool(interval) and (
                        global_step // interval > prev_step // interval
                    )

                last_loss = metrics["loss"]  # lazy device scalar; no sync here
                log = {}
                if crossed(cfg.log_every_n_steps):
                    with host_span("train.log_sync"):  # the loop's one sync
                        step_loss = float(last_loss)
                    epoch_losses.append(step_loss)
                    log.update(
                        epoch=epoch, iter=global_step, loss=step_loss,
                        forward_loss=float(metrics.get("forward_loss", 0.0)),
                        inverse_loss=float(metrics.get("inverse_loss", 0.0)),
                    )
                    if "accuracy" in metrics:
                        log["accuracy"] = float(metrics["accuracy"])
                    print(epoch, global_step, f"loss - {step_loss:.5f}")

                if crossed(cfg.save_every_n_steps):
                    # pass the sharded state directly: Orbax handles
                    # cross-host-sharded arrays natively (and copies to
                    # host before its async write), where device_get would
                    # raise on non-addressable fsdp/tp shards
                    with host_span("train.checkpoint", step=global_step):
                        ckpt.save(
                            global_step, state,
                            metadata={
                                "epoch": epoch, "step": global_step,
                                "epoch_batch": epoch_batch,
                                "epoch_losses": epoch_losses,
                                "last_loss": (
                                    float(last_loss)
                                    if last_loss is not None else None
                                ),
                                "plateau": (
                                    plateau.state_dict() if plateau else None
                                ),
                            },
                        )

                # ALL processes run the sampling computation (it is an
                # SPMD program over the sharded params); only the logger
                # (enabled on root) writes the image
                if crossed(cfg.log_images_freq):
                    # in-loop sample generation in EVERY configuration —
                    # trainable dVAE, precomputed tokens, VQGAN/OpenAI — like
                    # the reference (`train_dalle.py:564-576`)
                    # (disjoint from the train-step keys: extra fold_in tag)
                    gr = jax.random.fold_in(jax.random.fold_in(rng, global_step), 1)
                    with host_span("train.sample", step=global_step):
                        toks = generate_images(
                            model, {"params": state.params},
                            gr, jnp.asarray(text_head), filter_thres=0.9,
                        )
                    if isinstance(vae, DiscreteVAE):
                        if dvae_decode is None:
                            dvae_decode = jax.jit(lambda p, t: vae.apply(
                                {"params": p}, t, method=DiscreteVAE.decode))
                        image = np.asarray(
                            dvae_decode(vae_params, toks)
                        ) * 0.5 + 0.5  # dVAE decodes to [-1, 1]
                    else:  # pretrained wrappers decode straight to [0, 1]
                        image = np.asarray(vae.decode(toks))
                    caption = (captions or [None])[0] or tokenizer.decode(
                        text_head[0]
                    )
                    logger.log_images(image, caption, "image", global_step)

                rate = meter.update(global_step, cfg.batch_size)
                if rate is not None:
                    log["sample_per_sec"] = rate
                    # input-boundedness: share of wall time blocked on the host
                    # pipeline (~0 = fully overlapped)
                    log["input_wait_frac"] = round(batch_iter.wait_fraction, 4)
                    # live MFU vs this chip's bf16 peak (reference logs
                    # only sample_per_sec)
                    # rate is PER-PROCESS samples/s (each host iterates its
                    # own data shard), so normalize by the local chip count
                    if log_mfu:
                        log["mfu"] = round(
                            flops_mfu(rate, flops_per_sample, device["kind"],
                                      jax.local_device_count()), 4)
                    print(epoch, global_step, f"sample_per_sec - {rate:.2f}")
                if log:
                    logger.log(log, step=global_step)
                if profiler.after_step(global_step):
                    print("Profiler has finished running. Stopping training early.")
                    stop = True
                    break

        except jax.errors.JaxRuntimeError as exc:
            if "RESOURCE_EXHAUSTED" not in str(exc):
                raise
            # said once, clearly; the trainer never retries smaller itself
            raise SystemExit(
                f"train step does not fit device memory at batch_size="
                f"{cfg.batch_size}, ga_steps={cfg.ga_steps}, model.reversible="
                f"{cfg.model.reversible}, model.remat_policy="
                f"{cfg.model.remat_policy}: set model.reversible=true "
                "(recompute activations) with a leaner model.remat_policy "
                "(flash_residuals, then nothing_saveable: every layer keeps "
                "less for its backward), raise ga_steps (smaller "
                "microbatches) or lower batch_size. XLA said: "
                + str(exc).splitlines()[0]
            ) from exc
        finally:
            batch_iter.close()

        if plateau is not None and last_loss is not None:
            # epoch-average of the sampled losses (+ the final step), the
            # reference's scheduler signal (`train_dalle.py:589-590`)
            epoch_losses.append(float(last_loss))
            new_lr = plateau.step(
                float(np.mean(epoch_losses)), get_learning_rate(state)
            )
            state = set_learning_rate(state, new_lr)
        # epoch+1: this epoch is DONE — a --dalle_path resume starts the
        # next one (epoch would retrain data the restored Adam already saw)
        export(out_file, epoch + 1)
        logger.log_model_artifact(out_file)  # `train_dalle.py:481-484`

    export(out_file, cfg.epochs)
    ckpt.wait()
    logger.finish()
    print(f"final checkpoint -> {out_file}")
    from dalle_pytorch_tpu.utils.compile_guard import log_compiles

    log_compiles()


if __name__ == "__main__":
    main()
