"""Device time by the program's own names.

The device trace names an operation by XLA's instruction name
(`fusion.2525`, `copy.399`) and nothing else. The compiled program's text
carries, on most instructions, `metadata={op_name="..."}`: the path of
flax module scopes and `jax.named_scope`s the operation was traced under
(`jit(step)/jvp(DALLE)/transformer/.../attn_3/to_qkv/dot_general`). This
module keeps the table from the one to the other, and the rules that turn
a path into one of the program's components:

  `remember(name, fn, args)`  the first time a program is dispatched (or
      traced): keep the function and the shapes of its arguments. One dict
      lookup per later call, one `tree.map` per program per process.
  `table(name)`  on demand, never in a timed window: lower and compile
      again from the remembered shapes (a persistent-cache load where the
      cache is on), parse the text, classify, cache.
  `parse(text)` / `component(op_name, opcode, instruction)`  pure text.
  `join(trace_ops, table)`  device time by (component, phase), matched on
      instruction name AND result shape; what does not match is `unjoined`,
      which is not `unscoped`.

The names live in `ops/` (the kernels' `name=`), `models/` and
`training/steps.py` (`jax.named_scope`); the rules live HERE, so a refactor
that moves a name moves its rule in the same commit. No second tracer: the
profiler's trace is the only clock.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Dict, Iterable, Optional, Tuple

from dalle_pytorch_tpu.utils import compile_guard

COMPONENTS = (
    "attn_kernel", "attn_proj", "attn_glue", "attend", "cache_read",
    "cache_write", "ff", "norm_resid", "embed", "head", "loss", "optimizer",
    "sample", "pixels", "moe_router", "moe_dispatch", "moe_experts", "unscoped",
    "mla_attend", "mla_proj", "moe_shared",
    "delta_step", "delta_proj", "delta_chunk", "state_restore",
    "window_attend", "global_attend",
    "dsa_index_proj", "dsa_index", "dsa_select",
    "ssm_step", "ssm_proj", "ssm_chunk",
    "cca_mix", "router_mlp",
)
# `mtp`: what a multi-token module runs (models/lm.py:CausalLM.draft_step and
# the draft's argmax), whatever its component: a phase, as `remat` is
PHASES = ("fwd", "bwd", "remat", "mtp")

# the names the program gives its seven Pallas attention kernels (`name=` in
# ops/pallas_attention.py and ops/pallas_decode.py). The chip names the
# custom call after the innermost scope, which is the kernel's name, so
# these also find a kernel by its INSTRUCTION name (`%dq_flash.7`).
KERNELS = (
    "fwd_flash", "dq_flash", "dkv_flash",
    "decode_slots", "decode_sparse", "decode_paged", "decode_sparse_paged",
)

# the grouped products of a routed layer (`name=` in ops/grouped_matmul.py),
# known by their instruction names like the seven above
EXPERT_KERNELS = ("gmm_fwd", "gmm_dlhs", "gmm_drhs")

# the latent decode attention (`name=` in ops/latent_decode.py)
LATENT_KERNEL = "decode_latent"

# the gated delta rule's token step (`name=` in ops/delta_step.py)
DELTA_KERNEL = "delta_step"

# a Mamba-2 layer's token step (`name=` in ops/ssm_step.py)
SSM_KERNEL = "ssm_step"

# the lightning indexer's score over a row's live positions (`name=` in
# ops/index_score.py)
INDEX_KERNEL = "dsa_index"

# a full layer's cached step of grouped K/V heads (`name=` in
# ops/grouped_decode.py)
GROUPED_KERNEL = "decode_grouped"

CONTAINERS = ("while", "conditional", "call")  # their bodies are events too


def _E(alt: str) -> str:
    """A pattern that matches one whole element of a path."""
    return rf"(^|/)({alt})(/|$)"


# (component, pattern on the op_name path), first match wins. A path element
# is a flax module name, a flax method scope (`transformer._shift`) or a
# `jax.named_scope` of this repo.
RULES: Tuple[Tuple[str, "re.Pattern"], ...] = tuple(
    (name, re.compile(pat)) for name, pat in (
        ("attn_kernel", _E("|".join(KERNELS))),
        ("optimizer", _E("optimizer")),
        # before `loss`: the vocab-chunked loss holds the head's matmul
        ("head", r"(^|/)(DALLE\.to_logits|logits_\w+)(/|$)"),
        ("loss", _E("loss")),
        # `verify`: a verify step's accept test and index moves (models/lm.py)
        ("sample", _E("sample|rng_split|verify")),
        ("pixels", r"(^|/)DiscreteVAE\."),
        ("cache_read", _E("cache_read")),
        ("cache_write", _E("cache_write")),
        # latent attention's two halves (models/attention.py:LatentAttention),
        # before the attention rules they would otherwise fall under: scores,
        # softmax and weights x latent, with the kernel by name; and the down-
        # and up-projections, the two absorbed products, the norms of both
        # latents and the rotary. The latent's write is `cache_write`, above
        ("mla_attend", _E("mla_attend|" + LATENT_KERNEL)),
        ("mla_proj", _E("mla_proj")),
        # a lightning indexer beside it (learned sparse attention): its three
        # projections, the key's norm and the rotary; the score of every live
        # position, with the kernel by name; the threshold by counting and the
        # compaction to indices. The attend over the selected positions, with
        # their fetch, is `mla_attend`, above; the key's write `cache_write`
        ("dsa_index_proj", _E("dsa_index_proj")),
        ("dsa_index", _E(INDEX_KERNEL)),
        ("dsa_select", _E("dsa_select")),
        # a gated delta-rule layer (models/attention.py:GatedDeltaAttention),
        # before `attn_proj`, whose `to_out` it also has: the token step's
        # state update, with the kernel by name; the chunked prefill form;
        # the projections, the convolution, the norms and the gate. And the
        # copy that starts a turn from the snapshot (models/decode_cache.py)
        ("delta_step", _E(DELTA_KERNEL)),
        ("delta_chunk", _E("delta_chunk")),
        ("delta_proj", _E("delta_proj")),
        ("state_restore", _E("state_restore")),
        # a Mamba-2 mixer (models/attention.py:Mamba2Mixer), before
        # `attn_proj`, whose `to_out` it also has: the token step's state
        # update, with the kernel by name; the chunked prefill form; the two
        # projections, the convolution, the step's per-column operands, the
        # gate and the group norm
        ("ssm_step", _E(SSM_KERNEL)),
        ("ssm_chunk", _E("ssm_chunk")),
        ("ssm_proj", _E("ssm_proj")),
        # convolved latent attention (models/attention.py:ConvLatentAttention),
        # before the attention rules its module's name would fall under: the
        # value shift, the two convolutions, the q-k mean, the unit norm and
        # tau. Its projections are `to_qkv` and `to_out` (`attn_proj`), its
        # cached attend `global_attend`, its rotary glue. And a router that is
        # an MLP with a carried state (models/moe.py), before `ff`
        ("cca_mix", _E("cca_mix")),
        ("router_mlp", _E("router_mlp")),
        # a scan's own slicing (`dynamic_index_in_dim` of the stacked
        # parameters, LayerScale vectors and the layer index) and its counter
        # are nobody's: no owner. The cached scan carries the depth-stacked
        # decode cache and is named as a whole (`cached_scan`); what moves
        # the cache inside its body is under `cache_read` / `cache_write`
        ("unscoped", r"(^|/)(scan_stack|cached_scan)/while/(body|cond)/[\w\-]+$"),
        # a cached step of grouped K/V heads (models/attention.py:
        # Attention._cached_grouped): scores, softmax and weights x values
        # over a window layer's ring, or over a full layer's live K/V, with
        # the kernel by name
        ("window_attend", _E("window_attend")),
        ("global_attend", _E("global_attend|" + GROUPED_KERNEL)),
        ("attend", _E("attend")),
        ("attn_proj", _E("to_qkv|to_out")),
        # a routed layer's three parts (models/moe.py), before `ff`, whose
        # module they live in: the router's product, softmax and top-k; the
        # sort, the two gathers and the weights; the grouped products
        ("moe_router", _E("moe_router")),
        ("moe_dispatch", _E("moe_dispatch")),
        ("moe_shared", _E("moe_shared")),
        ("moe_experts", _E("moe_experts|" + "|".join(EXPERT_KERNELS))),
        ("embed", r"(^|/)(DALLE\.embed_text|text_emb|image_emb|token_emb|\w*pos_emb)(/|$)"),
        ("norm_resid", r"(^|/)(\w*norms?_\w+|norm_by_max)(/|$)"),
        # token shift is glue by the issue's definition, wherever it runs
        ("attn_glue", r"(^|/)(token_shift|transformer\._shift|pattern_mask)(/|$)"),
        # with the multi-token module's 2 dim -> dim projection
        ("ff", r"(^|/)(ff(_\d+)?|mtp_proj)(/|$)"),
        # under an attention module and neither kernel nor projection:
        # rotary, padding for the kernel, head transposes, masks
        ("attn_glue", r"(^|/)attn(_\d+)?(/|$)"),
        # what is left of a layer: residual adds and LayerScale
        ("norm_resid", r"(^|/)(transformer|layers)(/|$)"),
    )
)
REMAT_MARK = "rematted_computation"  # jax.checkpoint's name for a recompute
BWD_MARK = "transpose("
MTP_MARK = re.compile(_E("mtp"))  # the scope everything of the module runs under


def component(op_name: Optional[str], opcode: str = "",
              instruction_name: str = "") -> Tuple[str, str]:
    """(component, phase) of one instruction. `unscoped`: no `op_name`, or
    no rule; never folded into a neighbour. A kernel is also known by its
    instruction name, which is what survives where metadata does not."""
    base = instruction_name.lstrip("%").rsplit(".", 1)[0]
    if base in KERNELS:
        found = "attn_kernel"
    elif base in EXPERT_KERNELS:
        found = "moe_experts"
    elif base == LATENT_KERNEL:
        found = "mla_attend"
    elif base == DELTA_KERNEL:
        found = "delta_step"
    elif base == SSM_KERNEL:
        found = "ssm_step"
    elif base == INDEX_KERNEL:
        found = "dsa_index"
    elif base == GROUPED_KERNEL:
        found = "global_attend"
    elif not op_name or opcode in CONTAINERS:  # loop control has no owner
        return "unscoped", "fwd"
    else:
        found = next((c for c, pat in RULES if pat.search(op_name)), "unscoped")
    op_name = op_name or ""
    if REMAT_MARK in op_name:
        return found, "remat"
    if MTP_MARK.search(op_name):
        return found, "mtp"
    return found, "bwd" if BWD_MARK in op_name else "fwd"


# ------------------------------------------------------------------ parsing

_HEAD = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$")
_INSIDE = re.compile(r"(?:calls|to_apply)=(%?[\w.\-]+)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_COMMENT = re.compile(r"/\*.*?\*/")
_ARRAY = re.compile(r"[\w\-]+\[[^\]]*\](\{[^{}]*\})?")


def _shape_end(s: str, i: int) -> int:
    """End of the result shape that starts at s[i]: a balanced tuple, or
    `dtype[dims]` with its `{layout}`; -1 if the text is cut short."""
    if i < len(s) and s[i] == "(":
        depth = 0
        for j in range(i, len(s)):
            if s[j] == "(":
                depth += 1
            elif s[j] == ")":
                depth -= 1
                if depth == 0:
                    return j + 1
        return -1
    m = _ARRAY.match(s, i)
    return m.end() if m else -1


def bare_shape(shape: str) -> str:
    """`bf16[2,16,64]{1,0,2:T(2,128)(2,1)S(1)}` -> `bf16[2,16,64]`: layouts,
    comments and spaces out, so that a shape reads the same on both sides."""
    return re.sub(r"\s+", "", _LAYOUT.sub("", _COMMENT.sub("", shape)))


def instruction(line: str) -> Optional[Tuple[str, str, str]]:
    """(instruction name, opcode, bare result shape) of one line of HLO
    text, or of an operation's name in a TPU trace (the profiler names an
    operation by its whole line); None where the line is no instruction."""
    head = _HEAD.match(line)
    if not head:
        return None
    end = _shape_end(line, head.end())
    if end < 0:
        return None
    op = _OPCODE.match(line, end)
    if not op:
        return None
    return head.group(1).lstrip("%"), op.group(1), bare_shape(line[head.end():end])


def parse(hlo_text: str) -> Dict[str, Tuple[str, str, Optional[str]]]:
    """{instruction name: (opcode, bare result shape, op_name or None)} over
    every computation of the module but the fused ones (the trace shows a
    fusion, not its inside) and the reducers. Pure text."""
    lines = hlo_text.splitlines()
    # computations that are the inside of one instruction: a fusion's
    # `calls=`, and a `to_apply=` of anything but a `call`
    inside = set()
    for line in lines:
        if "calls=" in line or "to_apply=" in line:
            got = instruction(line)
            if got is not None and got[1] != "call":
                inside.update(m.lstrip("%") for m in _INSIDE.findall(line))
    out: Dict[str, Tuple[str, str, Optional[str]]] = {}
    skipping = False
    for line in lines:
        if not line.startswith(" "):
            comp = _COMPUTATION.match(line)
            if comp:
                skipping = comp.group(1).lstrip("%") in inside
            continue
        if skipping:
            continue
        got = instruction(line)
        if got is None:
            continue
        name, opcode, shape = got
        meta = _OP_NAME.search(line)
        out[name] = (opcode, shape, meta.group(1) if meta else None)
    return out


def classify(parsed: Dict[str, tuple]) -> Dict[str, list]:
    """parse()'s table with the rules applied: {instruction: [opcode, shape,
    component, phase]}, the form `join` takes and a recording stores."""
    return {
        name: [opcode, shape, *component(op_name, opcode, name)]
        for name, (opcode, shape, op_name) in parsed.items()
    }


# ------------------------------------------------------------------ the join


def join(trace_ops: Dict[str, dict], table: Dict[str, list]) -> dict:
    """Device time by (component, phase).

    `trace_ops`: {operation name in the trace: {"seconds": s, ...}} as
    `benchmark/trace/reduce.py` gives it. A loop or a branch is left out,
    so that it is not counted over its body: what it adds of its own is the
    loop control (0.1% of the generate cell), and the reduction's
    `self_seconds` cannot say it (an operation that starts at the instant
    another ends is subtracted from it as if nested, and a loop cut by the
    window's edge keeps the time of the body outside it). Every other
    operation counts with its whole duration, and their sum is the
    device's busy time. An operation is placed only where its instruction
    name AND its result shape are the table's; the rest, which is what
    another program ran or what the table does not know, is `unjoined`.
    Returns {"total_s", "placed_s", "unjoined_s", "placed_share",
    "seconds": {component: {phase: s}}}."""
    seconds: Dict[str, Dict[str, float]] = {}
    total = placed = 0.0
    for name, row in trace_ops.items():
        got = instruction(name)
        if got is not None and got[1] in CONTAINERS:
            continue
        t = float(row["seconds"])
        total += t
        entry = table.get(got[0]) if got else None
        if entry is None or entry[1] != got[2]:
            continue
        placed += t
        by_phase = seconds.setdefault(entry[2], {})
        by_phase[entry[3]] = by_phase.get(entry[3], 0.0) + t
    return {
        "total_s": total, "placed_s": placed, "unjoined_s": total - placed,
        "placed_share": placed / total if total > 0 else 0.0,
        "seconds": seconds,
    }


def share(joined: dict, components: Iterable[str], phase: Optional[str] = None) -> float:
    """Percent of the PLACED device time in `components` (all of them when
    empty), in one phase or in all."""
    wanted = set(components) or set(joined["seconds"])
    took = sum(
        s for c, by_phase in joined["seconds"].items() if c in wanted
        for p, s in by_phase.items() if phase is None or p == phase
    )
    return 100.0 * took / joined["placed_s"] if joined["placed_s"] > 0 else 0.0


# ------------------------------------------------------------------ programs

KEPT = 64  # programs held at most (oldest out first): a function is held
# with its closure, so a process that builds programs without end (the test
# suite) must not keep them all

_lock = threading.Lock()
_quiet = threading.local()
_programs: Dict[object, dict] = {}  # (name, id(fn)) -> {"name", "fn", "specs", "jit", "table"}
remembered = 0  # programs remembered (tests pin: once per program)
lowered = 0  # tables built (tests pin: 0 until `table()` is asked)


def _spec(x):
    import jax

    aval = x.aval if hasattr(x, "aval") else jax.api_util.shaped_abstractify(x)
    # a sharding is kept only where it says something (more than one
    # device): pinning one device changes the lowering's compile-cache key,
    # and the second compile is then a real one, not a cache load
    sharded = (isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)
               and len(x.sharding.device_set) > 1)
    return jax.ShapeDtypeStruct(
        aval.shape, aval.dtype, sharding=x.sharding if sharded else None,
        weak_type=getattr(aval, "weak_type", False),
    )


def remember(name: str, fn, args, **jit_kwargs) -> None:
    """Keep `fn` (a jitted function, or a plain one to be jitted with
    `jit_kwargs`) and the shapes of `args`, the first time it is seen; a
    dict lookup afterwards. Arguments may be arrays or tracers (a step
    remembers itself while it is being traced, which happens once per
    compile). Shardings are kept where the arguments have them. Inside the
    first dispatch of a `remembering` program nothing is remembered: what
    is traced there is that program."""
    key = (name, id(fn))
    if key in _programs or getattr(_quiet, "on", False):
        return
    import jax

    global remembered
    specs = jax.tree.map(_spec, tuple(args))
    with _lock:
        if key in _programs:
            return
        # the function is held, so its id is not reused while it is here
        _programs[key] = {"name": name, "fn": fn, "specs": specs,
                          "jit": jit_kwargs, "table": None}
        while len(_programs) > KEPT:
            del _programs[next(iter(_programs))]
        remembered += 1


class remembering:
    """A jitted function that remembers itself at its first dispatch, and
    tells the compile ledger of every dispatch (`compile_guard.dispatched`:
    when the call began and returned, and what to wait on for when the device
    was done); with the ledger's stamping off a later call costs one
    attribute test, with it on two clock reads and a record besides."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.name = jitted.__name__  # what XLA calls the module, less `jit_`
        self._seen = False

    def __call__(self, *args):
        if not compile_guard.stamping:
            return self._dispatch(*args)
        first, start = not self._seen, time.time()
        out = self._dispatch(*args)
        compile_guard.dispatched(self.name, id(self), first, start, time.time(), args, out)
        return out

    def _dispatch(self, *args):
        if self._seen:
            return self.jitted(*args)
        self._seen = True
        remember(self.name, self.jitted, args)
        _quiet.on = True
        try:
            return self.jitted(*args)
        finally:
            _quiet.on = False

    def __getattr__(self, attr):  # lower, trace, clear_cache ...
        return getattr(self.jitted, attr)


def names() -> list:
    """The remembered programs' names, in the order they were first seen."""
    return [p["name"] for p in list(_programs.values())]


def _build(p: dict) -> Dict[str, list]:
    import jax

    global lowered
    fn = p["fn"]
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn, **p["jit"])
    lowered += 1
    try:
        # the ledger keeps this second pass apart from the program's own
        with compile_guard.attributed("scope_table"):
            text = fn.lower(*p["specs"]).compile().as_text()
    except Exception as exc:  # a reader reports nothing; it never raises
        p["error"] = f"{type(exc).__name__}: {exc}"[:500]
        return {}
    return classify(parse(text))


def tables(name: str) -> list:
    """The table of every remembered program of this name (one name may
    hold several: a sampler compiled for two sampling settings). Built on
    first use, from the remembered shapes, and kept."""
    out = []
    for p in [p for p in list(_programs.values()) if p["name"] == name]:
        if p["table"] is None:
            p["table"] = _build(p)
        out.append(p["table"])
    return out


def table(name: str) -> Optional[Dict[str, list]]:
    """The first remembered program of this name, or None."""
    found = tables(name)
    return found[0] if found else None


def best_join(trace_ops: Dict[str, dict], name: str) -> Optional[dict]:
    """`join` against each program of this name; the one that places most.
    The join is checked, not trusted: the caller reads `placed_share`."""
    joins = [join(trace_ops, t) for t in tables(name)]
    return max(joins, key=lambda j: j["placed_s"]) if joins else None


def keep_table(name: str, table: Dict[str, list]) -> None:
    """Hold a table that was made elsewhere under a program's name: a
    recording (tests), or one written out by the process that ran the
    program, to reduce its profiler capture offline."""
    with _lock:
        _programs[(name, id(table))] = {"name": name, "fn": None, "specs": (),
                                        "jit": {}, "table": table}


def forget() -> None:
    """Drop every remembered program and both counters (tests)."""
    global remembered, lowered
    with _lock:
        _programs.clear()
        remembered = lowered = 0
