"""Device-mesh management: the TPU-native replacement for the reference's
entire distributed-backend layer.

The reference abstracts NCCL/MPI process groups behind a pluggable backend
registry (`/root/reference/dalle_pytorch/distributed_utils.py`,
`distributed_backends/*.py`: DeepSpeed, Horovod, Dummy). On TPU the whole
layer collapses into a `jax.sharding.Mesh` + pjit: XLA emits the
collectives (psum over ICI within a slice, DCN across slices), gradient
averaging is implicit in sharded autodiff, and the "backend" selection
becomes mesh-axis sizing.

Axis vocabulary (mesh is always 4-D; unused axes have size 1):

  dp    pure data parallelism (params replicated)       — DeepSpeed/Horovod DP
  fsdp  data parallelism with sharded params/opt state   — ZeRO-1/2/3
  tp    tensor (megatron-style) parallelism              — (reference: none)
  sp    sequence/context parallelism (ring attention)    — (reference: none)

Process-level helpers mirror the reference ABC's surface
(`distributed_backend.py:12-178`): `is_root` ≈ rank 0 gating for logging,
`is_local_root` ≈ per-host download coordination, `host_barrier` ≈
`local_barrier` (used by pretrained-VAE loading, `vae.py:69-95`).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MESH_AXES = ("dp", "fsdp", "tp", "sp")


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host init (once per host, before any jax call).

    Replaces `deepspeed.init_distributed()` / `hvd.init()`
    (`deepspeed_backend.py:36-39`, `horovod_backend.py`). Rendezvous info
    comes from (in precedence order) explicit arguments, the
    DALLE_TPU_COORDINATOR / DALLE_TPU_NUM_PROCS / DALLE_TPU_PROC_ID env
    vars set by `launch.py`, or — when DALLE_TPU_DIST=1 — TPU-pod
    auto-detection. With none of those present this is a no-op, so the
    trainers can call it unconditionally.
    """
    import os

    env = os.environ
    if coordinator_address is None:
        coordinator_address = env.get("DALLE_TPU_COORDINATOR")
    if num_processes is None and "DALLE_TPU_NUM_PROCS" in env:
        num_processes = int(env["DALLE_TPU_NUM_PROCS"])
    if process_id is None and "DALLE_TPU_PROC_ID" in env:
        process_id = int(env["DALLE_TPU_PROC_ID"])

    if num_processes is not None and num_processes <= 1:
        return
    if coordinator_address is None and num_processes is None:
        if env.get("DALLE_TPU_DIST") == "1":
            # TPU pod: everything auto-detected from the metadata service
            jax.distributed.initialize()
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_root() -> bool:
    """Global rank-0 check (reference `is_root_worker`)."""
    return jax.process_index() == 0


def is_local_root() -> bool:
    """First process on this host (reference `is_local_root_worker`).

    JAX is one process per host on TPU, so every process is its host's
    root; kept for API parity with multi-process-per-host setups.
    """
    return int(os.environ.get("LOCAL_PROCESS_ID", "0")) == 0


def host_barrier(name: str = "barrier") -> None:
    """Cross-host sync (reference `local_barrier`, `vae.py:69-95`)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def make_mesh(
    dp: int = -1,
    fsdp: int = 1,
    tp: int = 1,
    sp: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the 4-axis device mesh. dp=-1 absorbs the remaining devices.

    Axis order (dp, fsdp, tp, sp) places tp/sp innermost so their
    collectives ride the fastest ICI links; dp outermost so cross-slice
    (DCN) traffic is limited to gradient all-reduce.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    fixed = fsdp * tp * sp
    if dp == -1:
        assert n % fixed == 0, f"{n} devices not divisible by fsdp*tp*sp={fixed}"
        dp = n // fixed
    assert dp * fixed == n, f"mesh {dp}x{fsdp}x{tp}x{sp} != {n} devices"
    dev_array = np.asarray(devices).reshape(dp, fsdp, tp, sp)
    return Mesh(dev_array, MESH_AXES)


def batch_spec(extra_dims: int = 1) -> P:
    """PartitionSpec for a batch tensor: batch over (dp, fsdp), rest replicated.

    Sharding the batch over fsdp too is what turns parameter sharding into
    ZeRO-style data parallelism rather than pure model parallelism.
    """
    return P(("dp", "fsdp"), *([None] * extra_dims))


def batch_sharding(mesh: Mesh, extra_dims: int = 1) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(extra_dims))


def gather_to_host(tree):
    """Fetch a (possibly cross-host-sharded) pytree to host numpy arrays.

    Single-process: device_get. Multi-host: leaves that span
    non-addressable devices (fsdp/tp across hosts) are allgathered first —
    a COLLECTIVE, so every process must call this (root-gate the
    subsequent save, not the gather). Returns the full global value on
    every host.
    """
    if jax.process_count() == 1:
        return jax.device_get(tree)
    from jax.experimental import multihost_utils

    def one(x):
        if getattr(x, "is_fully_replicated", False):
            return jax.device_get(x)  # local replica is the global value
        if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return jax.device_get(x)

    return jax.tree_util.tree_map(one, tree)


def put_host_batch(x, sharding: NamedSharding):
    """Device-put a HOST-LOCAL batch shard under a global batch sharding.

    Single-process: plain device_put. Multi-host: each process holds only
    its own data shard (`host_shard_order`), and `jax.device_put` requires
    the same global value everywhere — the correct assembly is
    `make_array_from_process_local_data`, which treats `x` as this
    process's addressable rows of the [global_batch, ...] array.
    """
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_process_local_data(sharding, np.asarray(x))
