"""TL019 negatives: matching specs, unknowns, and cold paths."""

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from dalle_pytorch_tpu.parallel.mesh import make_mesh


def _impl(x):
    return x


def _k(rows):
    return rows


mesh = make_mesh()

run_tp = jax.jit(
    _impl,
    in_shardings=(P(None, "tp"),),
    out_shardings=P(None, "tp"),
)

kernel = shard_map(_k, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"))


# tracelint: hotloop
def step(batch):
    # placement matches the program's declared input: no reshard
    x = jax.device_put(batch, P(None, "tp"))
    return run_tp(x)


# tracelint: hotloop
def opaque(batch, sharding):
    # symbol vs literal: UNKNOWN, the lint stays silent
    y = jax.device_put(batch, sharding)
    return run_tp(y)


def cold(batch):
    # mismatch, but not hotloop-reachable: a one-off reshard is fine
    z = jax.device_put(batch, P("dp"))
    return run_tp(z)


# tracelint: hotloop
def unplaced(batch):
    # no recorded placement for `batch`: nothing to compare
    return kernel(batch)
