#!/usr/bin/env python
"""Generate token ids with a causal language model (`models/lm.py:CausalLM`)
whose trunk decodes through a cache. Five families of published configs do:
latent attention (a compressed K/V cache), leading dense layers, a shared
expert beside sigmoid-routed ones of which this process holds a share, and
with `index_topk` a lightning indexer in every layer that selects the cached
positions a query attends (`--config benchmark/configs/deepseek-v32-exp-ep16.json`;
`--prefill_chunk` puts a long prompt in through the chunked prefill); gated
delta-rule linear layers among full ones (a recurrent state beside K/V in one
cache, `--config benchmark/configs/olmo-hybrid-7b-pp2.json`); and window and
full layers over grouped K/V heads (window rings beside full K/V, every row
at its own position, `--config benchmark/configs/k-exaone-236b-ep8.json`),
whose multi-token module drafts a token a row a step for a two-position
verify step; and `nemotron_h` (`hybrid_override_pattern`: every layer ONE
sublayer, a Mamba-2 mixer, an attention over grouped K/V heads or ungated
relu2 experts; a state-space state beside K/V in a per-row cache, `--config
benchmark/configs/nemotron3-nano-30b-ep2.json`, or on the CPU `--config
benchmark/configs/_tiny-nemotron-h.json --prompts seeded:3 --batch 2
--prompt_len 40 --max_new_tokens 6`); and `zaya` (`cca_time0`: attention in a
compressed latent whose q and k pass two causal convolutions, a per-row cache
of 2 K/V heads and the last position's tail, ONE of 16 experts a token behind a
router that is an MLP carrying its state from layer to layer, a scaled
residual, a head that is the embedding, `--config
benchmark/configs/zaya1-8b-pp2.json`, or on the CPU `--config
benchmark/configs/_tiny-zaya.json --prompts seeded:3 --batch 2 --prompt_len 40
--max_new_tokens 6`). Each with parameters stored in bf16.

The sampler `generate.py` uses for DALL-E, for token sequences: every prompt
but its last token is prefilled into a decode cache
(`models/lm.py:prefill_cached`), then ONE dispatch runs the whole token loop
(`generate_tokens_cached`): the last prompt token is fed, every step samples
(top `1 - filter_thres` of the vocabulary, Gumbel noise at `temperature`),
the cache is written in place. A model that drafts for itself runs
`--max_new_tokens` VERIFY steps, each of which emits one token or two; the
first `--max_new_tokens` a row are given, with the steps' `accepted` drafts
beside them. Token ids in, token ids out: no tokenizer.

    python generate_lm.py --prompts seeded:7 --batch 4 --prompt_len 512 --max_new_tokens 64
    python generate_lm.py --config benchmark/configs/pangu-ultra-moe-ep16.json \\
        --prompts ids.npy --max_new_tokens 256 --out answers.json

The model is described in a published `config.json`'s keys, which
`CausalLM.from_config` reads: `--config` names a file that holds them
(DEFAULT_CONFIG, a small model, without it), `--set key=value` replaces one
of them, or one of the `program` group's (`dtype`, `weights_dtype`,
`attn_impl`, `moe_buffer_rows`). Prompts are a file (`ids.npy`: an int array
[rows, length], every row a whole prompt) or seeded (`seeded:<seed>`: uniform
ids, `--batch` rows of `--prompt_len`). Weights come from `--weights` (a
`save_params_npz` file of this model's parameter tree) or, without one, from
the model's own seeded init: the output then shows the path, not a language.
Not served: slots, pages and the engine keep the DALL-E cache (ROADMAP.md).
"""

from __future__ import annotations

import argparse
import json

DEFAULT_CONFIG = dict(
    vocab_size=8192, hidden_size=256, num_hidden_layers=4, num_attention_heads=8,
    q_lora_rank=96, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    first_k_dense_replace=1, intermediate_size=512, moe_intermediate_size=128,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, sandwich_norm=True, rms_norm_eps=1e-5, rope_theta=25600000,
    hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
)
PROGRAM_KEYS = ("dtype", "weights_dtype", "attn_impl", "moe_buffer_rows")


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--prompts", required=True, help="ids.npy [rows, length], or seeded:<seed>")
    p.add_argument("--config", default=None, help="a file of published config.json keys")
    p.add_argument("--set", nargs="*", default=[], metavar="KEY=VALUE",
                   help="replace a config key or a program option")
    p.add_argument("--weights", default=None, help="params.npz of this model (save_params_npz)")
    p.add_argument("--batch", type=int, default=4, help="rows of seeded prompts")
    p.add_argument("--prompt_len", type=int, default=64, help="length of seeded prompts")
    p.add_argument("--max_new_tokens", type=int, default=64)
    p.add_argument("--filter_thres", type=float, default=0.9,
                   help="the share of the vocabulary a step drops; 1.0 is greedy")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--prefill_rows", type=int, default=4, help="prompts a prefill dispatch takes")
    p.add_argument("--prefill_chunk", type=int, default=None,
                   help="tokens of a prompt a prefill dispatch takes: a trunk of latent "
                        "layers alone (any other is refused); the whole prompt where left out")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None, help="write {tokens, ...} here as JSON (else stdout)")
    return p.parse_args(argv)


def read_config(args):
    """(the published keys, the program's options) from --config and --set."""
    cfg = dict(DEFAULT_CONFIG)
    if args.config:
        with open(args.config) as f:
            cfg = json.load(f)
    program = {}
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
    families = {"kv_lora_rank", "linear_key_head_dim", "layer_types", "hybrid_override_pattern",
                "cca_time0"}
    if not families & set(cfg):
        raise SystemExit("generation is built for the latent-attention trunk (kv_lora_rank ...), "
                         "for linear and full layers (linear_key_head_dim ...), for window "
                         "and full ones (layer_types ...), for layers of one sublayer "
                         "(hybrid_override_pattern ...) and for convolved latent ones "
                         "(cca_time0 ...)")
    return cfg, program


def build_model(args, cfg: dict, program: dict, prompt_len: int, rows: int):
    """(CausalLM sized for the prompts and the new tokens, what it was built from)."""
    from dalle_pytorch_tpu.models.lm import CausalLM

    # a step of a model that drafts for itself takes two positions
    per_step = 1 + int(cfg.get("num_nextn_predict_layers", 0))
    if "moe_buffer_rows" not in program and "num_experts_per_tok" in cfg:
        # every assignment a prefill dispatch or a step can make: no routing drops a token
        dispatch = max(min(prompt_len - 1, args.prefill_chunk or prompt_len), 1)  # tokens a row
        program["moe_buffer_rows"] = cfg["num_experts_per_tok"] * max(
            min(args.prefill_rows, rows) * dispatch, rows * per_step)
    mdl = CausalLM.from_config(cfg, prompt_len + per_step * args.max_new_tokens, **program)
    return mdl, {"config": args.config or "DEFAULT_CONFIG", "set": args.set, **program}


def load_prompts(args, vocab: int):
    import numpy as np

    if args.prompts.startswith("seeded:"):
        rng = np.random.default_rng(int(args.prompts.split(":", 1)[1]))
        return rng.integers(0, vocab, (args.batch, args.prompt_len), dtype=np.int32)
    ids = np.load(args.prompts).astype(np.int32)
    if ids.ndim != 2 or ids.min() < 0 or ids.max() >= vocab:
        raise SystemExit(f"{args.prompts}: need [rows, length] ids in [0, {vocab})")
    return ids


def main(argv=None):
    args = parse_args(argv)
    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_pytorch_tpu.models.lm import generate_tokens_cached, prefill_cached
    from dalle_pytorch_tpu.utils.compile_guard import log_compiles

    cfg, program = read_config(args)
    prompts = load_prompts(args, cfg["vocab_size"])
    rows, prompt_len = prompts.shape
    mdl, options = build_model(args, cfg, program, prompt_len, rows)
    key = jax.random.PRNGKey(args.seed % (2**31 - 1))
    if args.weights:
        from dalle_pytorch_tpu.training.checkpoint import load_params_npz

        variables = {"params": load_params_npz(args.weights)[0]}
    else:
        init = jax.jit(mdl.init)(jax.random.fold_in(key, 1), jnp.zeros((1, 8), jnp.int32))
        variables = {"params": init["params"]}  # not the counts its own pass left in `stats`
    cache, dropped = mdl.init_cache(rows), 0
    if mdl.draft_layers and prompt_len < 2:
        raise SystemExit("a model that drafts for itself takes prompts of two tokens or more: "
                         "its module starts from the state of the token before the last")
    if prompt_len > 1:
        for r in range(0, rows, args.prefill_rows):
            cache, counts = prefill_cached(
                mdl, variables, jnp.asarray(prompts[r:r + args.prefill_rows, :-1]), cache, r,
                chunk=args.prefill_chunk)
            dropped += int(np.sum(counts.get("moe_dropped", 0)))
    tokens, _, counts, _ = generate_tokens_cached(
        mdl, variables, key, cache, jnp.asarray(prompts[:, -1:]), args.max_new_tokens,
        filter_thres=args.filter_thres, temperature=args.temperature, start=prompt_len - 1)
    dropped += int(np.sum(counts.get("moe_dropped", 0)))
    result = {"tokens": np.asarray(tokens)[:, :args.max_new_tokens].tolist(), "model": options,
              "moe_dropped": dropped}
    if "dsa_selected" in counts:  # learned sparse attention: positions a row-step a layer
        per = rows * args.max_new_tokens
        result.update(scored_per_row_step=(np.asarray(counts["dsa_scored"]) / per).tolist(),
                      selected_per_row_step=(np.asarray(counts["dsa_selected"]) / per).tolist())
    if "verify_steps" in counts:  # verify steps: a row emitted at least one token a step
        result.update(verify_steps=counts["verify_steps"],
                      accepted=np.asarray(counts["accepted"]).tolist())
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f)
    else:
        print(json.dumps(result))
    log_compiles()


if __name__ == "__main__":
    main()
