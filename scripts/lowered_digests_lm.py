"""sha256 of the lowered text of the rehearsal language-model programs of one
tree (`_tiny-pangu` and `_tiny-olmo`: both samplers and the prefill; `_tiny-mellum`:
the loss and its gradient), beside `lowered_digests.py`'s DALL-E programs.

usage: python scripts/lowered_digests_lm.py <tree> > out.json   (parent, then `.`; compare)
"""
import hashlib, json, os, sys
tree = os.path.abspath(sys.argv[1]); sys.path.insert(0, tree); os.chdir(tree)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from dalle_pytorch_tpu.models import lm
assert lm.__file__.startswith(tree), lm.__file__
out = {}
def digest(name, lowered):
    text = lowered.as_text()
    out[name] = [hashlib.sha256(text.encode()).hexdigest()[:16], len(text)]
shape = lambda tree_: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree_)
for cell in ("_tiny.generate_lm", "_tiny.generate_hybrid"):
    w = json.load(open(f"benchmark/workloads/{cell}.json")); job = w["job"]
    cfg = json.load(open(f"benchmark/configs/{w['config']}.json"))
    doc, steps, b = job["document_tokens"], job["question_tokens"] + job["answer_tokens"], job["sessions"]
    mdl = lm.CausalLM.from_config(cfg, doc + steps, **job.get("model", {}))
    variables = shape(jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    cache = shape(jax.eval_shape(lambda: mdl.init_cache(b)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    for thres in (1.0, 0.9):
        f = lm._sampler_builder(mdl, (steps, thres, 1.0, 2))
        digest(f"{cell}/sampler/{thres}", jax.jit(f, donate_argnums=(2,)).lower(
            variables, jax.ShapeDtypeStruct((2,), jnp.uint32), cache, i32(b, job["question_tokens"]), i32()))
    f = lm._prefill_builder(mdl, ())
    digest(f"{cell}/prefill", jax.jit(f, donate_argnums=(2,)).lower(
        variables, i32(job["prefill_rows"], doc), cache, i32()))
# the train step of _tiny-mellum
from dalle_pytorch_tpu.training import steps as S
w = json.load(open("benchmark/workloads/_tiny.train_lm.json")); job = w["job"]
cfg = json.load(open(f"benchmark/configs/{w['config']}.json"))
mdl = lm.CausalLM.from_config(cfg, job["seq_len"], **job.get("model", {}))
tok = jnp.zeros((job["batch"], job["seq_len"]), jnp.int32)
digest("_tiny.train_lm/loss_grad", jax.jit(jax.grad(lambda p, t: mdl.apply({"params": p}, t, return_loss=True, mutable=["stats"])[0])).lower(
    shape(jax.eval_shape(mdl.init, jax.random.PRNGKey(0), tok))["params"], shape(tok)))
print(json.dumps(out, indent=1))
