"""The routed layer's moves, sized by the rows present (`models/moe.py`),
against the formulas they replaced, kept here as the plain reference: the
buffer is `h[assign // k]` over all of its rows, the way back a gather of
every slot's row and a weighted sum over all slots, the experts a dense
product per row, and JAX differentiates the lot.

The router is steered: a token's first 8 features ARE its logits, so a case
says how many assignments the held experts get (`kept`), at the module's own
chunk. NaN is poured into every buffer row past `kept` after each grouped
product, forward and transposed: what is there may never be read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dalle_pytorch_tpu.models import moe

DIM, WIDTH, EXPERTS, PER_TOKEN, HELD = 16, 8, 8, 2, (2, 4)
TOKENS = 2560  # two chunks of tokens, the second one overhanging
CHUNK = moe.CHUNK_ROWS
ROOM = TOKENS * PER_TOKEN  # every assignment there can be
# (the assignments the held experts get, the buffer's rows)
CASES = {
    "none": (0, ROOM), "one": (1, ROOM), "a_chunk_less_one": (CHUNK - 1, ROOM),
    "a_chunk": (CHUNK, ROOM), "a_chunk_and_one": (CHUNK + 1, ROOM),
    "two_chunks_and_some": (2 * CHUNK + 300, ROOM), "full": (ROOM, ROOM),
    "overhang": (3000, 3000), "overflow": (3300, 3000),
}


def _inputs(assignments, dtype, seed=0):
    """x [1, T, DIM] whose first `assignments` (token, slot) choices, in
    token order, fall on held experts (which one varies by token), the rest
    on the others; and the layer's parameters, the router reading the logits
    off the first features."""
    rng = np.random.default_rng(seed)
    first, count = HELD
    others = [e for e in range(EXPERTS) if not first <= e < first + count]
    logits = rng.normal(size=(TOKENS, EXPERTS)).astype(np.float32) * 0.1
    for t in range(TOKENS):
        held_here = min(max(assignments - PER_TOKEN * t, 0), PER_TOKEN)
        chosen = [first + (t + j) % count for j in range(held_here)]
        chosen += [others[(t + j) % len(others)] for j in range(PER_TOKEN - held_here)]
        for j, e in enumerate(chosen):
            logits[t, e] = 4.0 + j + 0.5 * rng.random()
    x = rng.normal(size=(TOKENS, DIM)).astype(np.float32)
    x[:, :EXPERTS] = logits
    router = np.zeros((DIM, EXPERTS), np.float32)
    router[:EXPERTS] = np.eye(EXPERTS)
    router += rng.normal(size=router.shape).astype(np.float32) * 1e-3
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = {
        "router": jnp.asarray(router),
        "w_gate": moe._fan_in(key[0], (count, DIM, WIDTH)),
        "w_up": moe._fan_in(key[1], (count, DIM, WIDTH)),
        "w_out": moe._fan_in(key[2], (count, WIDTH, DIM)),
    }
    return jnp.asarray(x, dtype)[None], params


def _layer(buffer_rows):
    return moe.RoutedExperts(dim=DIM, expert_dim=WIDTH, experts_total=EXPERTS,
                             experts_per_token=PER_TOKEN, experts_held=HELD,
                             buffer_rows=buffer_rows)


def _reference(params, x, buffer_rows):
    """The layer as it was before its moves followed `kept`."""
    h = x.reshape(-1, x.shape[-1])
    first, count = HELD
    probs = jax.nn.softmax(jnp.dot(h.astype(jnp.float32), params["router"],
                                   precision=lax.Precision.HIGHEST), axis=-1)
    top, experts = lax.top_k(probs, PER_TOKEN)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    local = experts - first
    key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    kept = jnp.minimum(jnp.sum(key < count), buffer_rows)
    assign = order[:buffer_rows]
    live = (jnp.arange(buffer_rows) < kept)[:, None]
    pos = jnp.argsort(order)
    pos = jnp.where(pos < kept, pos, buffer_rows).reshape(experts.shape)

    def product(rows, w):  # each row by its own expert's matrix, float32 sums
        # cast after the gather: the matrices' gradient then sums over rows in float32
        mine = w[jnp.minimum(key[assign], count - 1)].astype(rows.dtype)
        return jnp.einsum("rk,rkn->rn", rows, mine,
                          preferred_element_type=jnp.float32).astype(rows.dtype)

    rows = jnp.where(live, h[assign // PER_TOKEN], 0)
    rows = product(jax.nn.silu(product(rows, params["w_gate"])) * product(rows, params["w_up"]),
                   params["w_out"])
    picked = rows[jnp.minimum(pos, buffer_rows - 1).reshape(-1)].reshape(*pos.shape, -1)
    picked = jnp.where((pos < buffer_rows)[..., None], picked, 0).astype(jnp.float32)
    y = jnp.sum(picked * weights[..., None], axis=1).astype(rows.dtype)
    return y.reshape(x.shape)


@pytest.fixture
def poisoned(monkeypatch):
    """NaN in every row past the groups of whatever a grouped product
    returns: the kernels leave those rows unwritten."""
    def pour(out, sizes):
        return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None], out, jnp.nan)

    real, real_t = moe.grouped_matmul, moe.grouped_matmul_dlhs
    monkeypatch.setattr(moe, "grouped_matmul", lambda l, r, s: pour(real(l, r, s), s))
    monkeypatch.setattr(moe, "grouped_matmul_dlhs", lambda r, s, d: pour(real_t(r, s, d), s))


def _loss(fn):
    # a loss whose cotangent differs by token and feature
    def loss(params, x):
        y = fn(params, x).astype(jnp.float32)
        return jnp.sum(y * jnp.sin(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)), y
    return jax.value_and_grad(loss, (0, 1), has_aux=True)


@pytest.fixture(scope="module")
def programs():
    """One compiled program a (buffer, dtype): a case is its input alone."""
    made = {}

    def get(buffer_rows, dtype):
        if (buffer_rows, dtype) not in made:
            layer = _layer(buffer_rows)
            made[buffer_rows, dtype] = (
                jax.jit(_loss(lambda p, x: layer.apply({"params": p}, x))),
                # not compiled: XLA would keep float32 where the formulas round to bf16
                _loss(lambda p, x: _reference(p, x, buffer_rows)),
                jax.jit(lambda p, x: layer.apply({"params": p}, x, mutable=["stats"])[1]["stats"]))
        return made[buffer_rows, dtype]

    return get


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_moves_equal_the_formulas_they_replaced(case, dtype, poisoned, programs):
    """Output, d x and every parameter's gradient (the router's is d weights
    through the softmax), at each number of rows present."""
    assignments, buffer_rows = CASES[case]
    x, params = _inputs(assignments, dtype)
    new, old, stats = programs(buffer_rows, dtype)
    stats = stats(params, x)
    assert int(stats["moe_rows"]) == assignments
    assert int(stats["moe_dropped"]) == max(assignments - buffer_rows, 0)
    ((_, got), got_grads), ((_, want), want_grads) = new(params, x), old(params, x)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    got_grads, want_grads = jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)
    for g, w in zip(got_grads, want_grads):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * max(float(np.abs(w).max()), 1e-3))


@pytest.mark.parametrize("case", list(CASES))
def test_d_weights_equals_the_formula_it_replaced(case, poisoned):
    """`to_tokens` alone: d rows and d weights for given rows, weights and
    cotangent, NaN in the rows past `kept`."""
    assignments, buffer_rows = CASES[case]
    x, params = _inputs(assignments, "float32", seed=1)
    h = x[0]
    probs = jax.nn.softmax(h @ params["router"], axis=-1)
    r = moe.route(probs, PER_TOKEN, HELD, buffer_rows)
    live = (jnp.arange(buffer_rows) < r["kept"])[:, None]
    rows = jnp.where(live, jax.random.normal(jax.random.PRNGKey(2), (buffer_rows, DIM)), jnp.nan)
    d_y = jax.random.normal(jax.random.PRNGKey(3), (TOKENS, DIM))

    def old(rows, weights):
        n = rows.shape[0]
        picked = rows[jnp.minimum(r["pos"], n - 1).reshape(-1)].reshape(*r["pos"].shape, -1)
        picked = jnp.where((r["pos"] < n)[..., None], picked, 0)
        return jnp.sum(picked * weights[..., None], axis=1)

    new = lambda rows, weights: moe.to_tokens(rows, weights, r["assign"], r["kept"], r["pos"], r["back"])
    got, got_vjp = jax.vjp(new, rows, r["weights"])
    want, want_vjp = jax.vjp(old, jnp.where(live, rows, 0), r["weights"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    (got_rows, got_w), (want_rows, want_w) = got_vjp(d_y), want_vjp(d_y)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(jnp.where(live, got_rows, 0), want_rows, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", list(CASES))
def test_moved_counts_the_rows_a_pass_walks(case, programs):
    """`moe_moved`: `kept` rounded up to the chunk, at most the buffer; and
    the three older counters are what they were."""
    assignments, buffer_rows = CASES[case]
    x, params = _inputs(assignments, "float32")
    stats = programs(buffer_rows, "float32")[2](params, x)
    kept = min(assignments, buffer_rows)
    assert int(stats["moe_moved"]) == min(-(-kept // CHUNK) * CHUNK, buffer_rows)
    assert kept <= int(stats["moe_moved"]) <= buffer_rows
    assert int(stats["moe_rows"]) == assignments == int(np.sum(stats["moe_load"]))
    assert int(stats["moe_dropped"]) == assignments - kept
    assert np.asarray(stats["moe_load"]).shape == (HELD[1],)


def test_the_way_back_walks_the_present_assignments_only():
    """`_ranked`: tokens in the order of how many present assignments they
    have, each one's present slots first and in slot order; `holders[j]`
    tokens have a rank-j one."""
    pos = jnp.asarray([[9, 2], [9, 9], [0, 1], [3, 9]], jnp.int32)  # 9: none here
    back = jax.tree.map(np.asarray, moe._ranked(pos, 9))
    assert back["holders"].tolist() == [3, 1]
    order = np.argsort(back["token"])  # the token that stands i-th
    assert order.tolist() == [2, 0, 3, 1]
    assert back["row"].reshape(2, 4).tolist() == [[0, 2, 3, 9], [1, 9, 9, 9]]
    assert back["slot"].reshape(2, 4)[0, :3].tolist() == [4, 1, 6] and back["slot"][4] == 5
