"""sha256 of the lowered text of the rehearsal language-model programs of one
tree (`_tiny-pangu`, `_tiny-olmo`, `_tiny-kexaone`, `_tiny-deepseek-v32`: both
samplers and the prefill, which of the last is a chunk and the copies;
`_tiny-nemotron-h`, `_tiny-zaya`: both per-row samplers and the prefill of
each document length; `_tiny-mellum`: the loss and its gradient), beside
`lowered_digests.py`'s DALL-E programs. A cell whose workload file the tree
lacks is left out.

With `--real`, instead: the REAL language-model cells' programs lowered for a
described v5e with their kernels Mosaic's (`pangu.decode.8k`,
`olmohybrid.decode.512`, `kexaone.decode.16k`, `deepseek32.decode.32k`,
`nemotron3.decode.8k`, `zaya1.decode.8k`: both samplers and the prefill;
`mellum2.train.8k`: the loss and its gradient),
which a change to shared code has to leave as they were (the `_tiny` buffers
are re-tiled by a change to the grouped products' tiles: PR 38). 40 s a tree.

usage: python scripts/lowered_digests_lm.py <tree> [--real] > out.json   (parent, then `.`; compare)
"""
import hashlib, json, os, sys
tree = os.path.abspath(sys.argv[1]); sys.path.insert(0, tree); os.chdir(tree)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from dalle_pytorch_tpu.models import lm
assert lm.__file__.startswith(tree), lm.__file__
real = "--real" in sys.argv[2:]
out, on = {}, None
def digest(name, lowered):
    text = lowered.as_text()
    out[name] = [hashlib.sha256(text.encode()).hexdigest()[:16], len(text), text.count("tpu_custom_call")]
shape = lambda tree_: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on), tree_)
if real:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    import importlib
    on = jax.sharding.SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    for name in ("grouped_matmul", "latent_decode", "pallas_attention", "delta_step", "index_score",
                 "grouped_decode", "ssm_step"):
        try:
            importlib.import_module(f"dalle_pytorch_tpu.ops.{name}")._use_interpret = lambda: False
        except ImportError:  # a tree older than the kernel
            pass
    # a kernel's payload carries the source lines it was traced from: dropped, so
    # that lines added above a kernel's in its file do not read as another kernel
    from jax._src import tpu_custom_call
    from jaxlib.mlir.passmanager import PassManager
    serialize = tpu_custom_call._lower_mosaic_module_to_asm
    def without_locations(module, **kw):
        with module.context:
            PassManager.parse("builtin.module(strip-debuginfo)").run(module.operation)
        return serialize(module, **kw)
    tpu_custom_call._lower_mosaic_module_to_asm = without_locations
cells = (("pangu.decode.8k", "olmohybrid.decode.512", "kexaone.decode.16k", "deepseek32.decode.32k",
          "nemotron3.decode.8k", "zaya1.decode.8k")
         if real else ("_tiny.generate_lm", "_tiny.generate_hybrid", "_tiny.generate_kexaone",
                       "_tiny.generate_deepseek_v32", "_tiny.generate_nemotron_h", "_tiny.generate_zaya"))
for cell in cells:
    if not os.path.exists(f"benchmark/workloads/{cell}.json"):
        continue  # a tree older than the cell
    w = json.load(open(f"benchmark/workloads/{cell}.json")); job = w["job"]
    cfg = json.load(open(f"benchmark/configs/{w['config']}.json"))
    docs, b = job["document_tokens"], job["sessions"]
    docs = docs if isinstance(docs, list) else [docs]  # rows of several lengths: a prefill a length
    doc = max(docs)
    verify = w["kind"] == "generate_kexaone"  # steps of two positions, every row at its own
    steps = job["steps"] if verify else job["question_tokens"] + job["answer_tokens"]
    block = job.get("cache_block", 1)
    length = -(-(doc + (2 if verify else 1) * steps) // block) * block
    mdl = lm.CausalLM.from_config(cfg, length, **job.get("model", {}))
    variables = shape(jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    cache = shape(jax.eval_shape(lambda: mdl.init_cache(b)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=on)
    per_row = getattr(mdl, "per_row", verify)  # the sampler `generate_tokens_cached` picks
    for thres in (1.0, 0.9):
        f = (lm._verify_sampler_builder(mdl, (steps, thres, 1.0, 2, None)) if per_row
             else lm._sampler_builder(mdl, (steps, thres, 1.0, 2)))
        digest(f"{cell}/sampler/{thres}", jax.jit(f, donate_argnums=(2,)).lower(
            variables, jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=on), cache,
            i32(b, job["question_tokens"]), i32(b) if per_row else i32()))
    if "prefill_chunk" in job:  # a document in chunks, then its copies
        fresh = shape(jax.eval_shape(lambda: mdl.init_cache(1, doc)))
        digest(f"{cell}/extend", jax.jit(lm._extend_builder(mdl, ()), donate_argnums=(2,)).lower(
            variables, i32(1, job["prefill_chunk"]), fresh))
        digest(f"{cell}/place", jax.jit(lm._place_builder(mdl, ()), donate_argnums=(0,)).lower(
            cache, fresh, i32(1)))
        continue
    f = lm._prefill_builder(mdl, ())
    own_rows = "prefill_rows" not in job  # `prefill_tokens` ids a dispatch, each row to a row of its own
    for n in docs:
        rows = max(1, job["prefill_tokens"] // n) if own_rows else job["prefill_rows"]
        digest(f"{cell}/prefill" + (f"/{n}" if len(docs) > 1 else ""),
               jax.jit(f, donate_argnums=(2,)).lower(
                   variables, i32(rows, n), cache, i32(rows) if own_rows else i32()))
# the loss and its gradient of _tiny-mellum, or of the real cell
cell = "mellum2.train.8k" if real else "_tiny.train_lm"
w = json.load(open(f"benchmark/workloads/{cell}.json")); job = w["job"]
cfg = json.load(open(f"benchmark/configs/{w['config']}.json"))
mdl = lm.CausalLM.from_config(cfg, job["seq_len"], **job.get("model", {}))
tok = jnp.zeros((job["batch"], job["seq_len"]), jnp.int32)
digest(f"{cell}/loss_grad", jax.jit(jax.grad(lambda p, t: mdl.apply({"params": p}, t, return_loss=True, mutable=["stats"])[0])).lower(
    shape(jax.eval_shape(mdl.init, jax.random.PRNGKey(0), tok))["params"], shape(tok)))
print(json.dumps(out, indent=1))
