#!/usr/bin/env python
"""Train a causal language model (`models/lm.py:CausalLM`) over the shared
trunk: RMS norm, grouped K/V heads, window and full attention layers, routed
SwiGLU experts of which this process holds a share.

The same pieces as `train_dalle.py`: `TrainState`, `make_optimizer` (clip by
global norm, then Adam, here with a linear warm-up), per-layer remat,
`Prefetcher`, `CheckpointManager`, `MetricsLogger`. Token ids come from a
file (`--tokens ids.npy`: a 1-D int array, cut into rows of `--seq_len`, no
packing) or are seeded (`--tokens seeded:<exponent>`: Zipf over the
vocabulary held).

    python train_lm.py --tokens seeded:1.0 --steps 20 --set hidden_size=128 num_experts=4
    python train_lm.py --config benchmark/configs/mellum2-12b-ep4.json --tokens ids.npy

The model is described in a published `config.json`'s keys, which
`CausalLM.from_config` reads: `--config` names a file that holds them
(DEFAULT_CONFIG, a small model, without it), `--set key=value` replaces one
of them, or one of the `program` group's (`dtype`, `attn_impl`, `executor`,
`moe_buffer_rows`). This script trains; `generate_lm.py` decodes such a model
through its cache (grouped K/V heads, window rings beside full K/V: PR 37);
serving it is not built (ROADMAP.md).
"""

from __future__ import annotations

import argparse
import json

_ROPE = {"rope_type": "default", "rope_theta": 500000.0}
DEFAULT_CONFIG = dict(
    vocab_size=8192, hidden_size=256, num_hidden_layers=4, num_attention_heads=8,
    head_dim=32, num_key_value_heads=2, sliding_window=128,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"], num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=128, rms_norm_eps=1e-6, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False,
    rope_parameters={"sliding_attention": _ROPE, "full_attention": _ROPE},
)
PROGRAM_KEYS = ("dtype", "attn_impl", "executor", "moe_buffer_rows")


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--tokens", required=True, help="ids.npy, or seeded:<zipf exponent>")
    p.add_argument("--config", default=None, help="a file of published config.json keys")
    p.add_argument("--set", nargs="*", default=[], metavar="KEY=VALUE",
                   help="a key of the configuration, or of its `program` group")
    p.add_argument("--seq_len", type=int, default=1024)
    p.add_argument("--batch_size", type=int, default=4)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--learning_rate", type=float, default=3e-4)
    p.add_argument("--warmup_steps", type=int, default=2000,
                   help="the rate rises linearly over this many steps (0: none)")
    p.add_argument("--clip_grad_norm", type=float, default=0.5)
    p.add_argument("--moe_buffer_factor", type=float, default=2.0,
                   help="rows of a routed layer's buffer over the mean assignments to the "
                        "experts held, where the configuration gives no `moe_buffer_rows`")
    p.add_argument("--no_remat", action="store_true", help="keep activations (more memory)")
    p.add_argument("--prefetch_depth", type=int, default=2)
    p.add_argument("--checkpoint_dir", default=None)
    p.add_argument("--save_every", type=int, default=0)
    p.add_argument("--keep_n_checkpoints", type=int, default=None)
    p.add_argument("--log_every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--debug", action="store_true")
    return p.parse_args(argv)


def build_model(args):
    """(CausalLM, what it was built from)."""
    from dalle_pytorch_tpu.models.lm import CausalLM

    cfg = dict(DEFAULT_CONFIG)
    if args.config:
        with open(args.config) as f:
            cfg = json.load(f)
    if "hybrid_override_pattern" in cfg or "cca_time0" in cfg:
        from dalle_pytorch_tpu.models.lm import FORWARD_ONLY, FORWARD_ONLY_CCA

        raise NotImplementedError(FORWARD_ONLY_CCA if "cca_time0" in cfg else FORWARD_ONLY)
    program = {"reversible": not args.no_remat}
    for kv in args.set:
        key, text = kv.split("=", 1)
        try:
            value = json.loads(text)
        except ValueError:
            value = text
        if key in PROGRAM_KEYS:
            program[key] = value
        elif key in cfg:
            cfg[key] = value
        else:
            raise SystemExit(f"unknown option {key!r} (have: {', '.join([*cfg, *PROGRAM_KEYS])})")
    depth = cfg["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types"):  # a period, repeated to the depth
        cfg[key] = [cfg[key][i % len(cfg[key])] for i in range(depth)]
    if "moe_buffer_rows" not in {**cfg.get("program", {}), **program}:
        total = cfg.get("published", {}).get("num_experts", cfg["num_experts"])
        assignments = args.batch_size * args.seq_len * cfg["num_experts_per_tok"]
        program["moe_buffer_rows"] = min(assignments, int(
            args.moe_buffer_factor * assignments * cfg["num_experts"] / total))
    mdl = CausalLM.from_config(cfg, args.seq_len, **program)
    return mdl, {"config": args.config or "DEFAULT_CONFIG", "set": args.set, **program}


def token_batches(args, vocab: int):
    """Yields {"tokens": [batch, seq_len] int32} for ever."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    if args.tokens.startswith("seeded:"):
        # id r with probability proportional to (r + 1)^-exponent
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(args.tokens.split(":", 1)[1])
        cdf = np.cumsum(p / p.sum())
        while True:
            ranks = np.searchsorted(cdf, rng.random((args.batch_size, args.seq_len)))
            yield {"tokens": np.minimum(ranks, vocab - 1).astype(np.int32)}
    ids = np.load(args.tokens).astype(np.int32).ravel()
    if ids.size < args.seq_len or ids.min() < 0 or ids.max() >= vocab:
        raise SystemExit(f"{args.tokens}: need >= {args.seq_len} ids in [0, {vocab})")
    rows = ids[: ids.size // args.seq_len * args.seq_len].reshape(-1, args.seq_len)
    while True:
        order = rng.permutation(len(rows))
        for start in range(0, len(order) - args.batch_size + 1, args.batch_size):
            yield {"tokens": rows[order[start:start + args.batch_size]]}
        if len(order) < args.batch_size:
            raise SystemExit(f"{args.tokens}: {len(rows)} rows, batch {args.batch_size}")


def main(argv=None):
    args = parse_args(argv)
    import jax

    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax.numpy as jnp

    from dalle_pytorch_tpu.data.prefetch import Prefetcher
    from dalle_pytorch_tpu.training import TrainState, make_lm_train_step, make_optimizer
    from dalle_pytorch_tpu.training.checkpoint import CheckpointManager
    from dalle_pytorch_tpu.training.metrics import MetricsLogger, ThroughputMeter
    from dalle_pytorch_tpu.utils.compile_guard import log_compiles

    mdl, options = build_model(args)
    tokens0 = jnp.zeros((1, args.seq_len), jnp.int32)
    params = jax.jit(mdl.init)(jax.random.PRNGKey(args.seed), tokens0)["params"]
    print(f"{sum(p.size for p in jax.tree_util.tree_leaves(params)):,} parameters")
    state = TrainState.create(
        apply_fn=mdl.apply, params=params,
        tx=make_optimizer(args.learning_rate, clip_grad_norm=args.clip_grad_norm,
                          warmup_steps=args.warmup_steps),
    )
    ckpt, start = None, 0
    if args.checkpoint_dir:
        ckpt = CheckpointManager(args.checkpoint_dir, keep_n=args.keep_n_checkpoints)
        restored, _, step = ckpt.restore(state)
        if restored is not None:
            state, start = restored, int(step)
            print(f"resumed from step {start}")
    step_fn = jax.jit(make_lm_train_step(mdl), donate_argnums=0)
    feed = Prefetcher(
        token_batches(args, mdl.num_tokens),
        transform=lambda b: {k: jax.device_put(v) for k, v in b.items()},
        depth=args.prefetch_depth,
    )
    logger = MetricsLogger(project="lm_tpu", config={**vars(args), "model": options},
                           debug=args.debug)
    meter = ThroughputMeter()
    rng = jax.random.PRNGKey(args.seed + 1)
    try:
        for step in range(start + 1, args.steps + 1):
            state, m = step_fn(state, next(feed), jax.random.fold_in(rng, step))
            if step % args.log_every == 0 or step == args.steps:
                row = {"loss": float(m["loss"])}
                if "moe_dropped" in m:  # an assignment past a layer's buffer is lost
                    load = jnp.asarray(m["moe_load"], jnp.float32)
                    row.update(
                        moe_dropped=int(jnp.sum(m["moe_dropped"])),
                        moe_moved=int(jnp.sum(m["moe_moved"])),
                        expert_load_max_over_mean=float(jnp.mean(
                            load.max(-1) / jnp.maximum(load.mean(-1), 1e-9))))
                print(f"step {step}: " + " ".join(f"{k} {v:.4g}" for k, v in row.items()))
                logger.log(row, step=step)
                rate = meter.update(step, args.batch_size * args.seq_len)
                if rate:
                    logger.log({"tokens_per_sec": rate}, step=step)
            if ckpt and args.save_every and step % args.save_every == 0:
                ckpt.save(step, state, metadata={"model": options, "seq_len": args.seq_len})
        if ckpt:
            ckpt.save(args.steps, state, metadata={"model": options, "seq_len": args.seq_len})
            ckpt.wait()
    finally:
        feed.close()
        logger.finish()
        if ckpt:
            ckpt.close()
    log_compiles()


if __name__ == "__main__":
    main()
