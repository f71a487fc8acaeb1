"""GPipe-style pipeline parallelism over a `pp` mesh axis.

The reference has no pipeline parallelism at all (SURVEY.md §2.2 row PP:
"none") — its depth scaling is reversibility + DeepSpeed ZeRO. On TPU the
idiomatic construction is SPMD: shard the depth-stacked layer parameters
over a `pp` mesh axis and move ACTIVATIONS between stages with
`lax.ppermute` inside `shard_map`, exactly like ring attention moves K/V
blocks (`parallel/ring.py`). XLA lowers the permute onto ICI
neighbor links; the schedule below is classic GPipe: M microbatches flow
through P stages in M + P - 1 ticks, each stage running its local slice
of layers per tick (bubble fraction (P-1)/(M+P-1)).

Everything is a pure jittable function — `jax.grad` differentiates
straight through the schedule (ppermute's transpose is the reverse
permutation; the backward pipeline runs automatically in reverse), so a
training step needs no hand-written backward schedule.

Scope: a generic engine over any `layer_fn(layer_params, x) -> x` whose
parameters are depth-stacked pytrees ([depth, ...] leaves — the same
layout the scan executor trains and checkpoints,
`models/transformer.py` `executor="scan"`). Numerical parity with
sequential execution (fwd AND grads) is pinned by
`tests/test_gpipe.py` on a virtual 8-device CPU mesh.
"""

from __future__ import annotations

from typing import Callable

import jax

import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_pp_mesh(pp: int, devices=None) -> Mesh:
    """1-axis ('pp',) mesh over the first `pp` devices."""
    devices = list(devices if devices is not None else jax.devices())
    assert pp <= len(devices), f"pp={pp} > {len(devices)} devices"
    return Mesh(np.asarray(devices[:pp]), ("pp",))


def stage_params_sharding(mesh: Mesh, params):
    """Shardings placing depth-stacked [P*L, ...] leaves over the pp axis
    (leading axis split across stages). Routed through the same
    divisibility fallback as every other placement (tracelint TL020): a
    leaf whose leading dim does not divide by pp replicates instead of
    sharding unevenly — unreachable for the [P, L, ...] stacks
    `gpipe_apply` reshapes, but callers can hand arbitrary pytrees."""
    from dalle_pytorch_tpu.parallel.partition import _divisible

    return jax.tree.map(
        lambda leaf: NamedSharding(
            mesh, _divisible(P("pp"), leaf.shape, mesh)
        ),
        params,
    )


def pipeline_layers(
    layer_fn: Callable,
    stage_params,
    microbatches: jax.Array,
    *,
    axis_name: str,
    n_micro: int,
    aux=None,
):
    """The inside-shard_map GPipe stage program (ring.py pattern: a pure
    per-device function parameterized by `axis_name`, so it composes with
    ANY caller mesh that carries a pipeline axis — alongside dp/fsdp/tp
    axes in a pjit train step, not only the standalone mesh
    `gpipe_apply` builds).

    stage_params: THIS stage's [L, ...] layer slice
    microbatches: [n_micro, mb, ...] (replicated; only stage 0 reads them)
    aux:          optional pytree of per-microbatch side inputs with
                  [n_micro, ...] leaves, replicated on every stage (e.g.
                  a key-padding mask). Each stage indexes the slot it is
                  CURRENTLY processing (microbatch t - stage), so aux
                  rides the schedule without any extra permute; when
                  given, layers are called layer_fn(lp, x, aux_slot).
    returns       [n_micro, mb, ...] outputs — valid on the LAST stage
                  (other stages return zeros; callers either slice the
                  stage axis outside or mask-psum).

    Memory note: the [n_micro, mb, ...] input stack, the aux pytree, and
    the output buffer are replicated on EVERY stage (in_specs P()), and
    dead schedule slots still execute full layer compute on zeros — so
    per-stage activation memory scales with the whole global batch,
    O(n_micro). This favors throughput at the current scale; if pp is
    ever used for *memory* scaling, move injection/collection to
    stage-local slices instead.
    """
    n_stages = lax.axis_size(axis_name)
    p = lax.axis_index(axis_name)
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
    ticks = n_micro + n_stages - 1

    def run_stage(h, aux_slot):
        def body(h, lp):
            if aux is None:
                return layer_fn(lp, h), None
            return layer_fn(lp, h, aux_slot), None

        h, _ = lax.scan(body, h, stage_params)
        return h

    zeros_mb = jnp.zeros_like(microbatches[0])
    outs0 = jnp.zeros_like(microbatches)

    def tick(carry, t):
        recv, outs = carry
        # stage 0 injects microbatch t (clipped; the tail ticks feed
        # zeros through dead slots), later stages process what the
        # previous stage sent last tick
        feed = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, n_micro - 1), keepdims=False
        )
        feed = jnp.where(t < n_micro, feed, zeros_mb)
        h = jnp.where(p == 0, feed, recv)
        # the microbatch THIS stage processes this tick
        mb_idx = jnp.clip(t - p, 0, n_micro - 1)
        aux_slot = (
            None if aux is None else jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, mb_idx, keepdims=False),
                aux,
            )
        )
        y = run_stage(h, aux_slot)
        recv_next = lax.ppermute(y, axis_name, fwd_perm)
        # last stage emits microbatch t-(P-1) at tick t
        out_idx = t - (n_stages - 1)
        valid = jnp.logical_and(out_idx >= 0, p == n_stages - 1)
        upd = lax.dynamic_update_index_in_dim(
            outs, y, jnp.clip(out_idx, 0, n_micro - 1), axis=0
        )
        outs = jnp.where(valid, upd, outs)
        return (recv_next, outs), None

    (_, outs), _ = lax.scan(tick, (zeros_mb, outs0), jnp.arange(ticks))
    return outs


def gpipe_apply(
    mesh: Mesh,
    params,
    layer_fn: Callable,
    x: jax.Array,
    n_micro: int,
    aux=None,
):
    """Run `depth` layers of `layer_fn` over `x`, pipelined over mesh
    axis 'pp' (standalone-mesh convenience wrapper around
    `pipeline_layers`).

    params: pytree with [depth, ...] leaves, depth = P * layers_per_stage
    x:      [batch, ...] activations, batch % n_micro == 0
    aux:    optional pytree of batch-leading side inputs ([batch, ...]
            leaves, e.g. a key mask), microbatched alongside x and fed to
            layer_fn(lp, x, aux_slot)
    returns [batch, ...] output, numerically equal to the sequential
            lax.scan over all `depth` layers.
    """
    pp = mesh.shape["pp"]
    depth = jax.tree.leaves(params)[0].shape[0]
    assert depth % pp == 0, f"depth {depth} not divisible by pp={pp}"
    batch = x.shape[0]
    assert batch % n_micro == 0, f"batch {batch} % n_micro {n_micro} != 0"

    def micro(a):
        return a.reshape(n_micro, batch // n_micro, *a.shape[1:])

    if pp == 1:
        def body(h, lp):
            if aux is None:
                return layer_fn(lp, h), None
            return layer_fn(lp, h, aux), None

        out, _ = lax.scan(body, x, params)
        return out

    # [depth, ...] -> [P, L, ...] so shard_map splits the stage axis
    staged = jax.tree.map(
        lambda a: a.reshape(pp, depth // pp, *a.shape[1:]), params
    )
    mb = micro(x)
    mb_aux = None if aux is None else jax.tree.map(micro, aux)

    def stage_fn(params_local, mb_local, aux_local):
        # shard_map hands each device its [1, L, ...] slice
        my_layers = jax.tree.map(lambda a: a[0], params_local)
        outs = pipeline_layers(
            layer_fn, my_layers, mb_local, axis_name="pp",
            n_micro=n_micro, aux=aux_local,
        )
        # leading stage axis for the out_spec; caller takes the last stage
        return outs[None]

    if mb_aux is None:
        sharded = shard_map(
            lambda p_, m_: stage_fn(p_, m_, None),
            mesh=mesh,
            in_specs=(P("pp"), P()),
            out_specs=P("pp"),
            check_vma=False,
        )
        outs = sharded(staged, mb)
    else:
        sharded = shard_map(
            stage_fn,
            mesh=mesh,
            in_specs=(P("pp"), P(), P()),
            out_specs=P("pp"),
            check_vma=False,
        )
        outs = sharded(staged, mb, mb_aux)
    return outs[-1].reshape(batch, *x.shape[1:])
