"""sha256 of the lowered text of the decode and train programs, for one tree:
the proof that a refactor moved no program (PERF.md section 6, PR 29).

For `_tiny` and `_tiny-scan` x attn_impl x patterned layers: the train step
with and without remat, both cached samplers, the slot ladder and its paged
twins, each with and without int8 K/V and the policy bitmap. Lowers with
abstract arguments only: nothing runs (about 100 s on the CPU).

usage: JAX_PLATFORMS=cpu python scripts/lowered_digests.py <tree> [<dump dir>] > out.json
       (once for a `git archive` of the parent, once for `.`; compare the two files)
"""
import hashlib
import json
import os
import re
import sys

tree = os.path.abspath(sys.argv[1])
sys.path.insert(0, tree)
os.chdir(tree)
dump = sys.argv[2] if len(sys.argv) > 2 else None

import jax
import jax.numpy as jnp

from benchmark import build
from dalle_pytorch_tpu.models import dalle as D

assert D.__file__.startswith(tree), D.__file__

out = {}


def digest(name, lowered):
    # a private function's name ends in a count of the process's (`@_pad_335`):
    # dropped, so that a program lowered EARLIER that traces one function more
    # does not read as a change to every program after it
    text = re.sub(r"(@\w+?)_\d+\b", r"\1", lowered.as_text())
    out[name] = [hashlib.sha256(text.encode()).hexdigest()[:16], len(text)]
    if dump:
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(dump, name.replace("/", "_") + ".txt"), "w") as f:
            f.write(text)
    print(name, out[name], file=sys.stderr, flush=True)


def lower(builder, model, key, *args):
    fn = builder(model, key)
    jitted = jax.jit(fn, donate_argnums=getattr(builder, "_donate_argnums", ()))
    return jitted.lower(*args)


S = jax.ShapeDtypeStruct
i32, f32 = jnp.int32, jnp.float32


def abstract(f, *a, **k):
    return jax.eval_shape(lambda: f(*a, **k))


for cfg_name in ("_tiny", "_tiny-scan"):
    cfg = json.load(open(f"benchmark/configs/{cfg_name}.json"))
    # (d) the train step, with and without remat
    from dalle_pytorch_tpu.training import TrainState, make_dalle_train_step, make_optimizer

    for remat in (True, False):
        mdl = build.model(cfg, reversible=remat, reversible_impl="remat")
        params = jax.eval_shape(
            mdl.init, jax.random.PRNGKey(0),
            jnp.zeros((1, mdl.text_seq_len), i32), jnp.zeros((1, mdl.image_seq_len), i32),
        )["params"]
        state = jax.eval_shape(
            lambda p: TrainState.create(
                apply_fn=mdl.apply, params=p, tx=make_optimizer(3e-4, clip_grad_norm=0.5)
            ), params,
        )
        batch = {"text": S((4, mdl.text_seq_len), i32), "image_tokens": S((4, mdl.image_seq_len), i32)}
        step = jax.jit(make_dalle_train_step(mdl), donate_argnums=0)
        digest(f"{cfg_name}/train{'.remat' if remat else ''}",
               step.lower(state, batch, S((2,), jnp.uint32)))

    for attn_impl in ("flash", "dense"):
        for types in (None, ("full", "axial_row")):
            if types and attn_impl == "flash" and cfg["model"]["executor"] == "scan":
                continue  # refused by the scan executor
            base = build.model(cfg, attn_impl=attn_impl).clone(attn_types=types)
            tag = f"{cfg_name}/{attn_impl}{'.pat' if types else ''}"
            variables = {"params": jax.eval_shape(
                base.init, jax.random.PRNGKey(0),
                jnp.zeros((1, base.text_seq_len), i32), jnp.zeros((1, base.image_seq_len), i32),
            )["params"]}
            B, R, MB, CH, PS = 2, 2, 4, 3, 4
            text = S((B, base.text_seq_len), i32)
            rng = S((2,), jnp.uint32)
            # (a), (b) the samplers
            for cs in (1.0, 2.0):
                digest(f"{tag}/sample_cached.cs{cs}", lower(
                    D._cached_sampler_builder, base, (0.9, 1.0, cs, None, None),
                    variables, rng, text))
            digest(f"{tag}/sample_cached_batched", lower(
                D._batched_sampler_builder, base, (1.0, None),
                variables, text, S((B,), i32), S((B,), f32), S((B,), i32)))
            for kv in (None, "int8"):
                for sparse in (False, True):
                    m = base.clone(kv_dtype=kv, decode_sparse_block=8 if sparse else None)
                    t2 = f"{tag}/kv{kv}{'.sparse' if sparse else ''}"
                    max_len = m.total_seq_len + 1
                    nb = -(-max_len // 8)
                    texts = S((R, m.text_seq_len), i32)
                    vR_i, vR_f = S((R,), i32), S((R,), f32)
                    img = S((R, m.image_seq_len), i32)
                    # (c) slots
                    st = abstract(D.init_slot_state, m, MB)
                    sk = ("sparse",) if sparse else ()
                    sa_p = (S((m.depth, R, nb), i32),) if sparse else ()
                    sa_c = (S((m.depth, MB, nb), i32),) if sparse else ()
                    digest(f"{t2}/slots_prefill", lower(
                        D._prefill_slots_builder, m, (R,) + sk,
                        variables, st, texts, vR_i, vR_i, vR_f, vR_i, *sa_p))
                    if not sparse:
                        digest(f"{t2}/slots_resume", lower(
                            D._resume_slots_builder, m, (R,),
                            variables, st, texts, img, vR_i, vR_i, vR_i, vR_f, vR_i))
                        digest(f"{t2}/slots_release", lower(
                            D._release_builder, m, (), st, S((MB,), jnp.bool_)))
                    digest(f"{t2}/slots_chunk", lower(
                        D._chunk_builder, m, (CH,) + sk, variables, st, *sa_c))
                    # paged twins
                    n_pages = 1 + MB * -(-max_len // PS)
                    pst = abstract(D.init_paged_slot_state, m, MB, n_pages, PS)
                    n_text_pages = -(-(m.text_seq_len + 1) // PS)
                    n_pages_row = -(-max_len // PS)
                    lo = lower(
                        D._prefill_slots_paged_builder, m, (R, PS, n_text_pages) + sk,
                        variables, pst, texts, vR_i, vR_i, vR_f, vR_i,
                        S((R, n_text_pages), i32), vR_i, *sa_p)
                    digest(f"{t2}/slots_prefill_paged", lo)
                    if not sparse:
                        digest(f"{t2}/slots_resume_paged", lower(
                            D._resume_slots_paged_builder, m, (R, PS, n_pages_row),
                            variables, pst, texts, img, vR_i, vR_i, vR_i, vR_f, vR_i,
                            S((R, n_pages_row), i32)))
                        sidecar = lo.out_info[1]
                        sidecar = jax.tree.map(lambda x: S(x.shape, x.dtype), sidecar)
                        digest(f"{t2}/sidecar_slice", lower(
                            D._slice_sidecar_builder, m, (), sidecar, S((), i32)))
                        one = jax.tree.map(lambda x: S(x.shape[1:], x.dtype), sidecar)
                        sc_i, sc_f = S((), i32), S((), f32)
                        for ps in (PS, 3):  # 9 % 3 == 0: no partial block
                            pst2 = abstract(D.init_paged_slot_state, m, MB, n_pages, ps)
                            digest(f"{t2}/prefix_admit.ps{ps}", lower(
                                D._admit_prefix_builder, m, (ps,),
                                pst2, sc_i, one, sc_i, sc_f, sc_i, sc_i, sc_i))
                    digest(f"{t2}/slots_chunk_paged", lower(
                        D._chunk_paged_builder, m, (CH,) + sk,
                        variables, pst, S((MB, n_pages_row), i32), *sa_c))

json.dump(out, sys.stdout, indent=1, sort_keys=True)
