"""Generation with the window-and-full language model and its multi-token
module, at a small size on the CPU (`benchmark/configs/_tiny-kexaone.json`:
hidden 64, 4 query heads over 2 K/V heads of 16, window 8, layers L L L G L,
one dense layer + four routed, 8 experts of which 4 are held with 2 a token +
1 shared, a score-correction bias, vocabulary 64, one drafting block),
float32, against the plain reference (`benchmark/reference/kexaone_ref.py`)
and against the loop's own one-token form."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build_kexaone
from benchmark.reference import kexaone_ref
from dalle_pytorch_tpu.models import decode_cache, lm, moe
from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached

ROOT = Path(__file__).resolve().parent.parent
SEED, PROMPT, WINDOW = 5, 20, 8


def _cfg(name="_tiny-kexaone"):
    with open(ROOT / "benchmark" / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def pair(cfg):
    """(program model, its seeded variables)."""
    mdl = CausalLM.from_config(cfg, 96)
    return mdl, build_kexaone.seeded_variables(cfg, mdl, SEED)


def _tokens(rows=3, seed=0, n=40, vocab=64):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (rows, n)), jnp.int32)


def _prefilled(mdl, variables, tokens):
    cache = mdl.init_cache(tokens.shape[0])
    return prefill_cached(mdl, variables, tokens, cache)[0]


def test_from_config_reads_the_published_file():
    """Every width as published; the share's parameters are the file's
    `parameters_here`, counted by the program's own init and by the reference."""
    cfg = _cfg("k-exaone-236b-ep8")
    mdl = CausalLM.from_config(cfg, 64)
    trunk = dict(mdl.trunk)
    assert (mdl.dim, mdl.heads, mdl.dim_head, mdl.depth, mdl.draft_layers) == (6144, 64, 128, 5, 1)
    assert trunk["attn_types"] == ("window", "window", "window", "full", "window")
    assert trunk["ff_kinds"] == ("swiglu",) + ("swiglu_experts",) * 4
    assert (trunk["kv_heads"], trunk["window"], trunk["experts_total"]) == (8, 128, 128)
    assert (trunk["moe_score"], trunk["routed_scale"], trunk["shared_dim"]) == ("sigmoid", 2.5, 2048)
    assert trunk["moe_score_bias"] and set(trunk["rotary_specs"]) == {"window"}
    assert mdl.param_dtype == jnp.bfloat16 and mdl.per_row
    shapes = jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    count = sum(x.size for x in jax.tree.leaves(shapes["params"]))
    assert count == kexaone_ref.n_params(cfg) and round(count / 1e6) == 4543
    cache = jax.eval_shape(lambda: mdl.init_cache(2, 512))
    assert cache["layer_0"]["attn"]["k"].shape == (2, 8, 128 + 1, 128)  # a ring
    assert cache["layer_0"]["attn"]["k_at"].shape == (2, 8, 128 + 1, 128)  # and its snapshot
    assert cache["layer_3"]["attn"]["k"].shape == (2, 8, 512, 128)  # full K/V, 8 heads
    assert cache["layer_5"]["hidden"].shape == (2, 6144)  # the module's own layer


def test_logits_and_drafts_match_the_reference(cfg, pair):
    mdl, variables = pair
    tokens = _tokens()
    want = kexaone_ref.forward(cfg, SEED, tokens, start=1)
    np.testing.assert_allclose(mdl.apply(variables, tokens)[:, 1:], want["logits"], atol=3e-5)
    got = mdl.apply(variables, tokens, method=CausalLM.draft_logits)
    np.testing.assert_allclose(got, want["draft"], atol=3e-5)
    layer = kexaone_ref.dims(cfg)["kinds"].index("routed")
    got = mdl.apply(variables, tokens, layer, method=CausalLM.route_choices)
    assert np.array_equal(np.sort(got[:, 1:], -1), np.sort(want["choices"], -1))


def test_prefill_then_verify_steps_match_the_reference(cfg, pair):
    """Through the cache (rings of exactly window + 1, full K/V of 2 heads,
    the module's own layer): both positions of every verify step and the
    module's logits against the reference's full forward, past the ring's
    length."""
    mdl, variables = pair
    tokens = _tokens(seed=1, n=PROMPT + 25)
    want = kexaone_ref.forward(cfg, SEED, tokens, start=PROMPT)
    cache = _prefilled(mdl, variables, tokens[:, :PROMPT])
    assert cache["layer_0"]["attn"]["k"].shape[2] == WINDOW + 1
    at = decode_cache.layer_key(mdl.depth)
    module = cache.pop(at)
    run = lambda method, *a: mdl.apply(variables, *a, method=method, mutable=["stats"])[0]
    rows = jnp.full((tokens.shape[0],), PROMPT, jnp.int32)
    module = decode_cache.set_index({at: module}, rows - 1)[at]
    first, module = run(CausalLM.draft_step, tokens[:, PROMPT:PROMPT + 1],
                        module["hidden"][:, None], module)
    np.testing.assert_allclose(first[:, 0], want["draft"][:, 0], atol=3e-5)
    cache = decode_cache.set_index(cache, rows)
    for p in range(PROMPT, PROMPT + 22, 2):
        logits, hidden, cache = run(CausalLM.verify_step, tokens[:, p:p + 2], cache)
        np.testing.assert_allclose(logits, want["logits"][:, p - PROMPT:p - PROMPT + 2], atol=3e-5)
        drafts, module = run(CausalLM.draft_step, tokens[:, p + 1:p + 3], hidden, module)
        np.testing.assert_allclose(
            drafts, want["draft"][:, p - PROMPT + 1:p - PROMPT + 3], atol=3e-5)
    assert cache["layer_3"]["attn"]["index"].tolist() == [PROMPT + 22] * 3


def test_a_rejected_drafts_position_matches_the_reference_and_leaves_the_cache(cfg, pair):
    """Verify steps whose draft is WRONG, one position kept a step: the second
    position's logits are the reference's for the sequence with the draft put
    in (`forward(drafts=)`), and after the index alone drops it the next
    step's first position is the reference's still, past the ring's length."""
    mdl, variables = pair
    tokens = _tokens(seed=8, n=PROMPT + 14)
    drafts = (tokens[:, PROMPT + 1:] + 1 + jnp.arange(13)[None] % 5) % 64  # never the token
    drafts = jnp.concatenate([drafts, drafts[:, :1]], axis=1)  # [3, 14], entry j at PROMPT + j + 1
    want = kexaone_ref.forward(cfg, SEED, tokens, start=PROMPT, drafts=drafts)
    cache = _prefilled(mdl, variables, tokens[:, :PROMPT])
    cache.pop(decode_cache.layer_key(mdl.depth))
    run = lambda method, *a: mdl.apply(variables, *a, method=method, mutable=["stats"])[0]
    for j in range(14):
        at = jnp.full((3,), PROMPT + j, jnp.int32)
        cache = decode_cache.set_index(cache, at)  # drops the last step's second position
        fed = jnp.stack([tokens[:, PROMPT + j], drafts[:, j]], axis=1)
        logits, _, cache = run(CausalLM.verify_step, fed, cache)
        np.testing.assert_allclose(logits[:, 0], want["logits"][:, j], atol=3e-5)
        np.testing.assert_allclose(logits[:, 1], want["second"][:, j], atol=3e-5)
    assert float(np.abs(want["second"][:, :-1] - want["logits"][:, 1:]).max()) > 0.05


def test_a_longer_chunk_is_a_start_or_is_refused(cfg, pair):
    """A chunk longer than a step goes into a cache only as the START of its
    rows' sequences, which the prefill says; onto what a cache holds it is
    refused, not answered from the chunk alone. A start may be as short as a
    step: a prompt of two tokens, then verify steps, against the reference."""
    mdl, variables = pair
    tokens = _tokens(seed=7, n=8)
    cache = _prefilled(mdl, variables, tokens[:, :2])
    cache.pop(decode_cache.layer_key(mdl.depth))
    cache = decode_cache.set_index(cache, jnp.full((3,), 2, jnp.int32))
    run = lambda toks: mdl.apply(variables, toks, cache, method=CausalLM.verify_step,
                                 mutable=["stats"])[0]
    with pytest.raises(NotImplementedError, match="start the rows' sequences"):
        run(tokens[:, 2:5])
    want = kexaone_ref.forward(cfg, SEED, tokens[:, :4], start=2)
    np.testing.assert_allclose(run(tokens[:, 2:4])[0], want["logits"], atol=3e-5)


@pytest.fixture(scope="module")
def one_token(pair):
    """The one-token sampler's turn over a prompt, greedy and sampled: the
    same loop without a module, one position a step: (prompts, forced,
    {filter_thres: tokens [B, 48]})."""
    mdl, variables = pair
    plain = mdl.clone(draft_layers=0)
    tokens, forced = _tokens(seed=2, n=PROMPT), _tokens(seed=3, n=3)
    out = {}
    for thres in (1.0, 0.9):
        cache = _prefilled(mdl, variables, tokens)
        sampler = jax.jit(lm._verify_sampler_builder(plain, (48, thres, 1.0, 0, None)))
        toks, _, counts, cache = sampler(variables, jax.random.PRNGKey(11), cache, forced,
                                         jnp.full((3,), PROMPT, jnp.int32))
        assert counts["emitted"].tolist() == [48] * 3
        out[thres] = np.asarray(toks)
    return tokens, forced, out


def _drafter(kind, known):
    """A drafter for the loop: the module's own draft (None), an oracle that
    knows what the one-token sampler emitted, or a coin between the oracle and
    a token that is surely wrong."""
    if kind == "module":
        return None
    known = jnp.asarray(known)
    rows = jnp.arange(known.shape[0])

    def oracle(draft, at):
        return known[rows, jnp.clip(at - PROMPT - 1, 0, known.shape[1] - 1)]

    if kind == "oracle":
        return oracle
    return lambda draft, at: jnp.where((at * 7 + rows) % 3 == 0, (oracle(draft, at) + 1) % 64,
                                       oracle(draft, at))


@pytest.mark.parametrize("thres", [1.0, 0.9], ids=["greedy", "top_k"])
@pytest.mark.parametrize("kind", ["module", "oracle", "coin"])
def test_verify_steps_emit_the_one_token_samplers_sequence(pair, one_token, kind, thres):
    """Whatever the drafts, the emitted tokens are the one-token sampler's
    and every live cache position holds what a prefill of the row's whole
    sequence writes there: keys are folded from the position, and a rejected
    position leaves a full layer by the row's index and a ring of exactly
    window + 1 because its slot held a position no query sees any more; the
    turn is longer than the ring."""
    mdl, variables = pair
    tokens, forced, runs = one_token
    known = runs[thres]
    steps = 24
    cache = _prefilled(mdl, variables, tokens)  # rings of exactly window + 1
    sampler = jax.jit(lm._verify_sampler_builder(
        mdl, (steps, thres, 1.0, 2, _drafter(kind, known))))
    toks, lg, counts, cache = sampler(variables, jax.random.PRNGKey(11), cache, forced,
                                      jnp.full((3,), PROMPT, jnp.int32))
    toks, emitted = np.asarray(toks), np.asarray(counts["emitted"])
    accepted = np.asarray(counts["accepted"])
    assert (emitted == steps + accepted).all() and emitted.min() > WINDOW + 1
    for r in range(3):
        assert toks[r, :emitted[r]].tolist() == known[r, :emitted[r]].tolist()
        assert toks[r, :2].tolist() == forced[r, 1:].tolist()
    if kind == "oracle":
        assert accepted.tolist() == [steps] * 3
    elif kind == "coin":
        assert 0 < accepted.sum() < 3 * steps
    else:
        assert accepted.sum() <= 3
    assert lg["logits"].shape == (steps, 2, 2, 64) and lg["draft"].shape == (steps, 2, 64)
    assert np.asarray(lg["accepted"]).sum(0).tolist() == accepted[:2].tolist()
    # the draft a step fed is what it kept or turned down
    fed_next = np.asarray(lg["drafted"])
    if kind == "oracle":
        assert (fed_next[:, 0] == known[0, 2 * np.arange(steps)]).all()
    # every live position of every layer, against a prefill of the row's sequence
    kinds = dict(mdl.trunk)["attn_types"]
    for r in range(3):
        pos = PROMPT + emitted[r]
        whole = jnp.concatenate([tokens[r], forced[r, :1], jnp.asarray(toks[r, :emitted[r] - 1])])
        assert whole.shape[0] == pos
        want_cache = _prefilled(mdl, variables, whole[None])
        for i, layer_kind in enumerate(kinds):
            got, want = (c[decode_cache.layer_key(i)]["attn"] for c in (cache, want_cache))
            assert int(got["index"][r]) == pos
            live = np.arange(pos - WINDOW + 1, pos) if layer_kind == "window" else np.arange(pos)
            for leaf in ("k", "v"):
                a = np.asarray(got[leaf])[r][:, live % got[leaf].shape[2]]
                b = np.asarray(want[leaf])[0][:, live % want[leaf].shape[2]]
                np.testing.assert_allclose(a, b, atol=2e-5)


def test_a_turn_after_going_back_is_the_first_turn_again(pair):
    """A turn longer than the rings overwrites every slot of them; the next
    turn starts from their snapshot and reads what the first read: same
    tokens and logits, through `generate_tokens_cached` (which donates its
    cache)."""
    mdl, variables = pair
    tokens, forced = _tokens(seed=4, n=PROMPT), _tokens(seed=5, n=2)
    cache = _prefilled(mdl, variables, tokens)
    turns = []
    for _ in range(2):
        toks, lg, counts, cache = generate_tokens_cached(
            mdl, variables, jax.random.PRNGKey(3), cache, forced, 14, filter_thres=0.9,
            logit_rows=1, start=PROMPT)
        turns.append((np.asarray(toks), np.asarray(lg["logits"]), np.asarray(counts["emitted"])))
    assert np.array_equal(turns[0][0], turns[1][0]) and np.array_equal(turns[0][2], turns[1][2])
    np.testing.assert_array_equal(turns[0][1], turns[1][1])
    assert counts["verify_steps"] == 14 and counts["ring_slots"] == WINDOW + 1 < 14
    # 4 window layers x (k, v) x (running, kept) x 3 rows x 2 heads x slots x 16 x float32
    assert counts["ring_bytes"] == 4 * 2 * 2 * 3 * 2 * (WINDOW + 1) * 16 * 4


def test_ring_positions_and_the_fresh_roll():
    last = jnp.asarray([4, 9, 23])
    held = np.asarray(decode_cache.ring_positions(last, 9))
    assert held[0].tolist() == [0, 1, 2, 3, 4, -4, -3, -2, -1]  # negative: never written
    assert held[1].tolist() == [9, 1, 2, 3, 4, 5, 6, 7, 8]
    assert sorted(held[2].tolist()) == list(range(15, 24)) and (held[2] % 9 == np.arange(9)).all()
    vals = jnp.arange(2 * 1 * 13 * 1, dtype=jnp.float32).reshape(2, 1, 13, 1)
    ring = {"k": jnp.zeros((2, 1, 9, 1)), "index": jnp.zeros((2,), jnp.int32)}
    out = np.asarray(decode_cache.write_ring(ring, {"k": vals}, start=True)["k"])
    for p in range(4, 13):  # the last nine positions, each at p mod 9
        assert out[0, 0, p % 9, 0] == p and out[1, 0, p % 9, 0] == 13 + p
    ring = {"k": jnp.asarray(out), "index": jnp.asarray([13, 13])}
    step = np.asarray(decode_cache.write_ring(
        ring, {"k": -jnp.ones((2, 1, 2, 1))}, start=False)["k"])
    assert step[0, 0, 13 % 9, 0] == -1 and step[0, 0, 14 % 9, 0] == -1 and step[0, 0, 6, 0] == 6


def test_a_route_flips_when_the_bias_is_left_out(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(seed=6)
    layer = kexaone_ref.dims(cfg)["kinds"].index("routed")
    with_bias = mdl.apply(variables, tokens, layer, method=CausalLM.route_choices)
    params = jax.tree.map(lambda x: x, variables["params"])
    params["transformer"][f"ff_{layer}"]["router_bias"] = jnp.zeros((8,))
    without = mdl.apply({"params": params}, tokens, layer, method=CausalLM.route_choices)
    flipped = (np.sort(with_bias, -1) != np.sort(without, -1)).any(-1).mean()
    assert 0.02 < flipped < 0.9
    # the weights are the chosen experts' own scores, not score + bias
    probs = jnp.asarray([[0.9, 0.5, 0.4, 0.1]])
    r = moe.route(probs, 2, (0, 4), 8, bias=jnp.asarray([0.0, 0.0, 0.2, 0.0]))
    assert sorted(r["experts"][0].tolist()) == [0, 2]
    np.testing.assert_allclose(sorted(r["weights"][0].tolist()), [0.4 / 1.3, 0.9 / 1.3], rtol=1e-6)


def test_the_new_scopes_name_the_new_work(pair):
    """`window_attend`, `global_attend`, `verify` and the phase `mtp` are in
    the lowered sampler, and the rules place them."""
    from dalle_pytorch_tpu.obs import scopes

    mdl, variables = pair
    cache = jax.eval_shape(lambda: mdl.init_cache(2))
    text = jax.jit(lm._verify_sampler_builder(mdl, (4, 0.9, 1.0, 1, None))).lower(
        variables, jax.random.PRNGKey(0), cache, jnp.zeros((2, 2), jnp.int32),
        jnp.zeros((2,), jnp.int32)).compile().as_text()
    table = scopes.classify(scopes.parse(text))
    found = {(c, p) for _, _, c, p in table.values()}
    for want in (("window_attend", "fwd"), ("global_attend", "fwd"), ("global_attend", "mtp"),
                 ("moe_experts", "mtp"), ("sample", "fwd"), ("sample", "mtp"), ("ff", "mtp"),
                 ("head", "mtp"), ("cache_write", "fwd")):
        assert want in found, want
    assert scopes.component("jit(lm_sample)/while/body/sample/verify/eq", "fusion", "f.1") == (
        "sample", "fwd")
    assert scopes.component("jit(f)/CausalLM/mtp/mtp_proj/dot_general", "fusion", "f.2") == (
        "ff", "mtp")


def test_generate_lm_cli_decodes_the_configuration(tmp_path, capsys):
    import generate_lm

    out = tmp_path / "answers.json"
    generate_lm.main(["--config", str(ROOT / "benchmark/configs/_tiny-kexaone.json"),
                      "--prompts", "seeded:3", "--batch", "2", "--prompt_len", "16",
                      "--max_new_tokens", "6", "--out", str(out)])
    got = json.loads(out.read_text())
    assert np.asarray(got["tokens"]).shape == (2, 6) and got["moe_dropped"] == 0
    assert got["verify_steps"] == 6 and len(got["accepted"]) == 2


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(cfg):
    """Expert parallelism's contract (model-configs guide, section 4): the
    parts that the two shares of the rehearsal's 8 experts give (experts 0-3,
    4-7; the published model: 8 shares of 16), with what every chip computes
    alike, the shared expert, counted once, are the uncut reference's layer;
    the bias steers the choice on every chip alike. Through the PROGRAM's
    routed layer."""
    whole = dict(cfg, num_experts=8)  # the reference holds every expert
    d = kexaone_ref.dims(whole)
    lp = kexaone_ref.init_layer(whole, SEED, 1)
    y = jax.random.normal(jax.random.PRNGKey(3), (32, d["dim"]))
    b = kexaone_ref._rms(y, lp["norm_ff_g"], d["eps"])
    weights, _ = kexaone_ref.route(b, lp["router_w"], lp["router_b"], d)
    uncut = kexaone_ref.shared_expert(b, lp) + kexaone_ref.routed_experts(
        b, weights, lp, d, held=(0, 8))
    shared = {"shared_gate": lp["sh_gate_w"], "shared_up": lp["sh_up_w"],
              "shared_out": lp["sh_down_w"]}
    routed, with_shared = [], []
    for first in (0, 4):
        params = {"router": lp["router_w"], "router_bias": lp["router_b"],
                  "w_gate": lp["gate_w"][first:first + 4], "w_up": lp["up_w"][first:first + 4],
                  "w_out": lp["down_w"][first:first + 4]}
        kw = dict(dim=d["dim"], expert_dim=d["expert_dim"], experts_total=8, experts_per_token=2,
                  experts_held=(first, 4), buffer_rows=64, score="sigmoid", routed_scale=2.5,
                  score_bias=True)
        routed.append(moe.RoutedExperts(**kw).apply({"params": params}, b[None])[0])
        with_shared.append(moe.RoutedExperts(**kw, shared_dim=d["shared_dim"]).apply(
            {"params": {**params, **shared}}, b[None])[0])
    the_shared = with_shared[0] - routed[0]  # what every chip computes alike
    np.testing.assert_allclose(the_shared, kexaone_ref.shared_expert(b, lp), atol=2e-5)
    np.testing.assert_allclose(sum(routed) + the_shared, uncut, atol=3e-5)
    # and without the bias the uncut layer is another: it steers real choices
    plain, _ = kexaone_ref.route(b, lp["router_w"], None, d)
    assert float(jnp.abs(plain - weights).max()) > 0.1
