"""Device milliseconds a step by component and phase (forward | remat |
backward) of a TRACED `flagship.train` run, for PERF.md section 5's table.

The traced run's record (`benchmark/out/flagship.train-<seed>-trace.json`,
`chiprun_out/ab<prefix>/<side>-flagship.train-<seed>-1.json` after
`scripts/chip_ab_train.sh`) holds every device operation's seconds but only
the shares of the join; here the cell's step is compiled for a described v5e
from `<tree>` (no chip: the compile numbers its instructions as the chip's
does, so 99.99% of the time is placed) and joined again, phase by phase.
Also prints the compiler's plan of the step and the largest rows of one phase.

usage: python scripts/train_phase_table.py <tree> <traced record> [<phase to list: remat>]
       (the parent's archive with the parent's record, `.` with the change's)
"""
import json, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"; os.environ.setdefault("TPU_LOG_DIR", "disabled")
tree = os.path.abspath(sys.argv[1]); record = os.path.abspath(sys.argv[2])
listed = sys.argv[3] if len(sys.argv) > 3 else "remat"
sys.path.insert(0, tree); os.chdir(tree)
import jax, jax.numpy as jnp
from jax.experimental import topologies
jax.config.update("jax_enable_compilation_cache", False)
from dalle_pytorch_tpu.ops import pallas_attention
assert pallas_attention.__file__.startswith(tree), pallas_attention.__file__
pallas_attention._use_interpret = lambda: False  # the kernels Mosaic's, as on the chip
from dalle_pytorch_tpu.obs import scopes
from dalle_pytorch_tpu.training import TrainState, make_optimizer
from benchmark.loops import train

on = jax.sharding.SingleDeviceSharding(
    topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
w = json.load(open("benchmark/workloads/flagship.train.json"))
prog = train.Program(json.load(open(f"benchmark/configs/{w['config']}.json")), w["job"])
mdl, opt = prog.mdl, prog.opt


def init_state():
    params = mdl.init(jax.random.PRNGKey(0), jnp.zeros((1, mdl.text_seq_len), jnp.int32),
                      jnp.zeros((1, mdl.image_seq_len), jnp.int32))["params"]
    return TrainState.create(apply_fn=mdl.apply, params=params, tx=make_optimizer(
        opt["learning_rate"], clip_grad_norm=opt["clip_grad_norm"]))


shape = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on), t)
compiled = prog.step.lower(
    shape(jax.eval_shape(init_state)), shape({k: jnp.asarray(v) for k, v in prog.host_batch(1, 0).items()}),
    jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=on)).compile()
mem = compiled.memory_analysis()
print(f"[plan] {mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes:,}"
      f" bytes (temporaries {mem.temp_size_in_bytes:,})")
parsed = scopes.parse(compiled.as_text()); table = scopes.classify(parsed)
d = json.load(open(record)); steps = d["counters"]["traced_steps"]; ops = d["reduced"]["ops"]
joined = scopes.join(ops, table)
print(f"[join] placed {100 * joined['placed_share']:.4f}% of {joined['total_s']:.4f} s, {steps} steps")
ms = lambda s: 1e3 * s / steps
for comp, by in sorted(joined["seconds"].items(), key=lambda kv: -sum(kv[1].values())):
    f, r, b = (ms(by.get(p, 0.0)) for p in ("fwd", "remat", "bwd"))
    print(f"{comp:12s} {f + r + b:8.2f} = {f:7.2f} + {r:6.2f} + {b:7.2f}")
phases = {p: ms(sum(by.get(p, 0.0) for by in joined["seconds"].values())) for p in scopes.PHASES}
print("[phases]", {p: round(v, 2) for p, v in phases.items()}, "step", round(sum(phases.values()), 2))
rows = {}
for name, row in ops.items():
    got = scopes.instruction(name)
    entry = table.get(got[0]) if got and got[1] not in scopes.CONTAINERS else None
    if entry is None or entry[1] != got[2] or entry[3] != listed:
        continue
    leaf = re.sub(r"_\d+\b", "_N", "/".join((parsed[got[0]][2] or "").split("/")[-3:]))
    key = (entry[2], got[1], leaf, re.sub(r"\{[^}]*\}", "", got[2])[:72])
    rows[key] = rows.get(key, 0.0) + ms(float(row["seconds"]))
for key, t in sorted(rows.items(), key=lambda kv: -kv[1])[:16]:
    print(f"[{listed}] {t:6.2f}  {' | '.join(key)}")
