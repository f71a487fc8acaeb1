"""Jitted train-step factories for the three model families.

Equivalent of the reference hot loops (`/root/reference/train_dalle.py:
494-592`, `train_vae.py:230-303`) — but each whole step (frozen-VAE encode,
forward(s), backward, clip, Adam update) is ONE compiled XLA program, pjit-
shardable over the mesh. Gradient averaging across data-parallel shards is
implicit (XLA inserts the psum); the reference's explicit
`average_all(loss)` (`deepspeed_backend.py:165-171`) becomes a jnp.mean the
compiler lowers to the same collective.

Feature mapping:
  * `--fp16` + apex AMP (`train_dalle.py:326-327,382-388`) -> bf16 compute
    dtype on the model, fp32 params/optimizer (no loss scaling needed);
  * DeepSpeed `ga_steps` (`train_dalle.py:380`) -> lax.scan microbatching
    inside the step (`grad_accum`);
  * `clip_grad_norm_` (`train_dalle.py:526`) -> optax.clip_by_global_norm;
  * the fork's objective modes (`train_dalle.py:513-518`,
    `config/config.yaml:13`): forward_only / forward_forward /
    forward_reverse_partial; reverse_only (named in `config/exp/ro.yaml`
    but unhandled by the reference trainer) is implemented here as the
    inverse objective alone.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax.training import train_state

from dalle_pytorch_tpu.models.dvae import DiscreteVAE
from dalle_pytorch_tpu.obs import scopes

MODES = ("forward_only", "forward_forward", "forward_reverse_partial", "reverse_only")


class TrainState(train_state.TrainState):
    pass


def make_optimizer(
    learning_rate: float, clip_grad_norm: Optional[float] = None, warmup_steps: int = 0
) -> optax.GradientTransformation:
    """Adam with optional global-norm clipping; lr is a mutable hyperparam
    (host-side schedulers rewrite it, see lr.py). With `warmup_steps` the
    rate rises linearly inside the step, `learning_rate * k / warmup_steps`
    at update k, and stays at `learning_rate` from there on (the injected
    value is then the schedule's at every update: a host-side scheduler's
    rewrite would be overwritten, so a trainer uses one or the other)."""

    def build(learning_rate):
        steps = []
        if clip_grad_norm is not None:
            steps.append(optax.clip_by_global_norm(clip_grad_norm))
        steps.append(optax.adam(learning_rate))
        return optax.chain(*steps)

    if warmup_steps:
        peak = learning_rate
        learning_rate = lambda count: peak * jnp.minimum(1.0, (count + 1) / warmup_steps)
    return optax.inject_hyperparams(build)(learning_rate=learning_rate)


def get_learning_rate(state: TrainState) -> float:
    return float(state.opt_state.hyperparams["learning_rate"])


def set_learning_rate(state: TrainState, lr: float) -> TrainState:
    opt_state = state.opt_state
    hyper = dict(opt_state.hyperparams)
    hyper["learning_rate"] = jnp.asarray(lr, jnp.float32)
    return state.replace(opt_state=opt_state._replace(hyperparams=hyper))


def _update(state: TrainState, grads) -> TrainState:
    """Gradient clipping and the Adam update, named: no flax module bounds
    them, so without the scope their operations carry no path at all."""
    with jax.named_scope("optimizer"):
        return state.apply_gradients(grads=grads)


def _accumulate(loss_and_metrics_fn, params, batches, rng, accum: int):
    """Scan `accum` microbatches, averaging grads and metrics."""

    def micro(carry, inp):
        g_acc, m_acc = carry
        mb, r = inp
        (_, metrics), grads = jax.value_and_grad(
            loss_and_metrics_fn, has_aux=True
        )(params, mb, r)
        g_acc = jax.tree.map(jnp.add, g_acc, grads)
        m_acc = jax.tree.map(jnp.add, m_acc, metrics)
        return (g_acc, m_acc), None

    rngs = jax.random.split(rng, accum)
    mb0 = jax.tree.map(lambda x: x[0], batches)
    (_, m0), g0 = jax.value_and_grad(loss_and_metrics_fn, has_aux=True)(
        params, mb0, rngs[0]
    )
    if accum == 1:
        return g0, m0
    rest = jax.tree.map(lambda x: x[1:], batches)
    (g, m), _ = jax.lax.scan(micro, (g0, m0), (rest, rngs[1:]))
    scale = 1.0 / accum
    return jax.tree.map(lambda x: x * scale, g), jax.tree.map(lambda x: x * scale, m)


def _microbatch(batch, accum: int):
    """[B, ...] -> [accum, B/accum, ...] for every leaf."""
    if accum == 1:
        return jax.tree.map(lambda x: x[None], batch)
    return jax.tree.map(
        lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]), batch
    )


def make_vae_train_step(vae: DiscreteVAE, grad_accum: int = 1) -> Callable:
    """step(state, images, rng, temp) -> (state, metrics).

    Mirrors the dVAE hot loop (`train_vae.py:234-248`); `temp` is the
    annealed gumbel temperature (`train_vae.py:278`), traced so annealing
    doesn't recompile.
    """

    def loss_fn(params, images, rng, temp):
        loss = vae.apply(
            {"params": params}, images, return_loss=True, temp=temp,
            rngs={"gumbel": rng},
        )
        return loss, {"loss": loss}

    def vae_step(state: TrainState, images, rng, temp):
        fn = lambda p, mb, r: loss_fn(p, mb, r, temp)
        grads, metrics = _accumulate(
            fn, state.params, _microbatch(images, grad_accum), rng, grad_accum
        )
        return _update(state, grads), metrics

    return vae_step


def make_dalle_train_step(
    model,
    vae: Optional[DiscreteVAE] = None,
    mode: str = "forward_only",
    grad_accum: int = 1,
    null_cond_prob: float = 0.0,
    pp_trunk: Optional[Callable] = None,
) -> Callable:
    """step(state, batch, rng[, vae_params]) -> (state, metrics).

    batch: {"text": [B, T] ids, "images": [B, H, W, C]} when a trainable-
    frozen `vae` is supplied (in-step encode, reference
    `dalle_pytorch.py:619-627`), else {"text", "image_tokens": [B, N]}
    (the better TPU pattern: tokens precomputed offline).

    Loss composition per the fork trainer (`train_dalle.py:509-518`):
    forward loss always except reverse_only; inverse loss added for
    forward_forward (same layer order) / forward_reverse_partial
    (reversed layer order).

    `pp_trunk` (optional): the `run(tparams, x)` closure from
    `make_pipeline_trunk` — the transformer trunk executes pipeline-
    parallel over the mesh 'pp' axis instead of on-module. The pp trunk
    is deterministic by design (no dropout; models/dalle.py asserts) and
    owns the layer order, so reversed-layer modes are rejected.
    """
    assert mode in MODES, f"mode must be one of {MODES}"
    if pp_trunk is not None:
        assert mode != "forward_reverse_partial", (
            "pipeline parallelism cannot run reversed layer order "
            "(trunk_fn owns the layer order); use forward_only / "
            "forward_forward / reverse_only"
        )

    def encode(vae_params, batch):
        if vae is not None and "image_tokens" not in batch:
            return jax.lax.stop_gradient(
                vae.apply(
                    {"params": vae_params},
                    batch["images"],
                    method=DiscreteVAE.get_codebook_indices,
                )
            )
        return batch["image_tokens"]

    def loss_fn(params, batch, rng, vae_params):
        text = batch["text"]
        tokens = encode(vae_params, batch)
        drop_rng, null_rng = jax.random.split(rng)
        rngs = {"dropout": drop_rng, "null_cond": null_rng}
        shared = dict(
            return_loss=True, null_cond_prob=null_cond_prob,
            deterministic=False, rngs=rngs,
        )
        if pp_trunk is not None:
            # deterministic by design: dropout layers are hard-disabled
            # under the pp trunk (config validation requires zero dropout
            # rates); null-cond CFG randomness still applies — it acts on
            # the embeddings before the trunk
            shared.update(
                deterministic=True, rngs={"null_cond": null_rng},
                trunk_fn=lambda h: pp_trunk(params["transformer"], h),
            )
        apply = lambda **kw: model.apply(
            {"params": params}, text, tokens, **shared, **kw
        )

        metrics = {}
        if mode == "reverse_only":
            loss, acc = apply(inverse_mapping=True)
            metrics.update(inverse_loss=loss, accuracy=acc, forward_loss=0.0)
        else:
            loss, _ = apply()
            metrics["forward_loss"] = loss
            if mode in ("forward_forward", "forward_reverse_partial"):
                inv_loss, acc = apply(
                    inverse_mapping=True,
                    reverse_model=(mode == "forward_reverse_partial"),
                )
                loss = loss + inv_loss
                metrics.update(inverse_loss=inv_loss, accuracy=acc)
        metrics["loss"] = loss
        return loss, metrics

    def step(state: TrainState, batch, rng, vae_params=None):
        # runs while the step is TRACED, once per compile and never per
        # step: the shapes it is traced at, to be lowered again as the
        # trainer jits it (obs/scopes.py)
        args = (state, batch, rng) + (() if vae_params is None else (vae_params,))
        scopes.remember("step", step, args, donate_argnums=0)
        fn = lambda p, mb, r: loss_fn(p, mb, r, vae_params)
        grads, metrics = _accumulate(
            fn, state.params, _microbatch(batch, grad_accum), rng, grad_accum
        )
        return _update(state, grads), metrics

    return step


def make_lm_train_step(model, grad_accum: int = 1) -> Callable:
    """step(state, batch, rng) -> (state, metrics) for a `CausalLM`.

    batch: {"tokens": [B, N] ids}; the loss is the model's next-token
    cross-entropy. Built from the same `_accumulate`, `_update` and
    `TrainState` as the DALL-E step. Where the trunk has routed layers the
    metrics carry what they counted, per layer: `moe_load` [depth, held],
    `moe_rows`, `moe_dropped` and `moe_moved` [depth] (an assignment past a
    layer's buffer is a dropped token: callers require 0; the buffer rows a
    pass walked: the rows present, rounded up to the chunk). A model with a
    state-space mixer or ungated experts (neither has a backward), or of
    convolved latent attention (no gradient held to its reference), is refused
    by name (models/lm.py:forward_only).
    """
    from dalle_pytorch_tpu.models.lm import forward_only

    why = forward_only(model.plan())
    if why:
        raise NotImplementedError(why)

    def loss_fn(params, batch, rng):
        del rng  # no dropout in this trunk; the signature is the trainers'
        loss, aux = model.apply(
            {"params": params}, batch["tokens"], return_loss=True, mutable=["stats"]
        )
        metrics = {"loss": loss}
        layers = aux.get("stats", {}).get("transformer", {})
        if layers:
            for name in ("moe_load", "moe_rows", "moe_dropped", "moe_moved"):
                metrics[name] = jnp.stack(
                    [layers[f"ff_{i}"][name] for i in range(model.depth)]
                )
        return loss, metrics

    def lm_step(state: TrainState, batch, rng):
        scopes.remember("lm_step", lm_step, (state, batch, rng), donate_argnums=0)
        grads, metrics = _accumulate(
            loss_fn, state.params, _microbatch(batch, grad_accum), rng, grad_accum
        )
        return _update(state, grads), metrics

    return lm_step


def make_multi_step(step_fn: Callable, n_steps: int) -> Callable:
    """Wrap a train step so `n_steps` optimizer steps run in ONE dispatch.

    multi(state, batches, rngs, *extras) -> (state, mean_metrics)

    `batches` is the per-step batch pytree with a leading [n_steps, ...]
    axis on every leaf; `rngs` is an [n_steps] stack of PRNG keys (callers
    that fold per-global-step — `train_dalle.py`'s
    `fold_in(rng, global_step)` — pass the same folded keys stacked, so
    the key stream is bit-identical to n_steps separate dispatches and
    mid-run resume replays exactly). `*extras` (frozen VAE params, gumbel
    temp) are per-dispatch constants, closed over the whole scan — with
    multi-stepping, schedules that anneal such extras move at dispatch
    granularity instead of step granularity.

    Why this exists: the host loop pays one dispatch round trip per jitted
    call, and wherever dispatch is synchronous (e.g. a profiling setup that
    forces readbacks) or the step is very short, that round trip bounds
    throughput no matter how fast the compiled step is. Scanning the step
    body amortizes one round trip over `n_steps` real optimizer steps —
    the same host-loop-elimination trick production TPU trainers (t5x et
    al.) use. Compiled size stays ~one step (scan compiles the body once).

    The reference has no analogue: its hot loop is host-driven per step
    (`/root/reference/train_dalle.py:494-592`), which CUDA hides via async
    launch queues; XLA's equivalent is putting the loop on device.

    Returned metrics are the mean over the inner steps (the per-step
    stream is still observable by lowering n_steps).
    """
    assert n_steps >= 1

    def multi(state: TrainState, batches, rngs, *extras):
        def body(st, inp):
            b, r = inp
            st, metrics = step_fn(st, b, r, *extras)
            return st, metrics

        state, metrics = jax.lax.scan(body, state, (batches, rngs))
        return state, jax.tree.map(lambda x: jnp.mean(x, axis=0), metrics)

    return multi


def window_keys(rng, start_step: int, n: int):
    """[n]-stacked `fold_in(rng, start_step + i)` keys — the per-global-step
    stream `make_multi_step` prescribes. One shared helper so every
    windowed trainer derives the identical stream: a pure function of the
    step index, invariant to steps_per_dispatch, epoch tails, and resume."""
    return jnp.stack(
        [jax.random.fold_in(rng, start_step + i) for i in range(n)]
    )


def stack_batches(batches: list):
    """Stack a list of per-step batch pytrees into the [n_steps, ...]
    layout `make_multi_step` consumes (one host->device transfer for the
    whole window instead of one per step)."""
    import numpy as np

    return jax.tree.map(lambda *xs: np.stack(xs), *batches)


def window_iter(it, n: int):
    """Group an iterator into lists of `n` (the final group may be
    shorter — trainers replay such epoch tails through their single-step
    program). Shared by every steps_per_dispatch trainer loop."""
    buf = []
    for b in it:
        buf.append(b)
        if len(buf) == n:
            yield buf
            buf = []
    if buf:
        yield buf


def make_clip_train_step(clip_model, grad_accum: int = 1) -> Callable:
    """step(state, batch{text,images}, rng) -> (state, metrics)."""

    def loss_fn(params, batch, rng):
        loss = clip_model.apply(
            {"params": params}, batch["text"], batch["images"],
            text_mask=batch.get("text_mask"), return_loss=True,
            deterministic=False, rngs={"dropout": rng},
        )
        return loss, {"loss": loss}

    def clip_step(state: TrainState, batch, rng):
        grads, metrics = _accumulate(
            loss_fn, state.params, _microbatch(batch, grad_accum), rng, grad_accum
        )
        return _update(state, grads), metrics

    return clip_step
