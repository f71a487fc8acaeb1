"""The decode cache's format, pinned: what `models/decode_cache.py` makes
(key order, shapes, dtypes: the table below was copied from what the two
makers it replaced returned), and that every helper reads the layout off
the tree: per-layer and stacked caches go through the same calls."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.models import decode_cache as dc
from dalle_pytorch_tpu.models.decode_cache import PER_LAYER, STACKED
from dalle_pytorch_tpu.models.transformer import Transformer

DEPTH, BATCH, HEADS, DH, DIM, FMAP = 2, 3, 2, 4, 8, 3
MAX_LEN, N_PAGES, PAGE = 11, 5, 4

# ONE layer's leaves, in key order: (path, shape, dtype). `index` sits
# between v and the scales; the rings come after `attn`.
KV_SHAPE = {"slots": (BATCH, HEADS, MAX_LEN, DH), "paged": (N_PAGES, HEADS, PAGE, DH)}


def layer_table(store, kv_dtype, shift):
    kv = KV_SHAPE[store]
    kv_dt = "int8" if kv_dtype else "bfloat16"
    rows = [("attn/k", kv, kv_dt), ("attn/v", kv, kv_dt), ("attn/index", (BATCH,), "int32")]
    if kv_dtype:
        rows += [("attn/k_scale", kv[:-1], "float32"), ("attn/v_scale", kv[:-1], "float32")]
    if shift:
        rows += [
            ("shift_attn", (BATCH, FMAP, DIM), "bfloat16"),
            ("shift_ff", (BATCH, FMAP, DIM), "bfloat16"),
        ]
    return rows


def expected(layout, store, kv_dtype, shift):
    rows = layer_table(store, kv_dtype, shift)
    if layout == STACKED:  # the same leaves under a leading depth axis
        return [(path, (DEPTH,) + shape, dt) for path, shape, dt in rows]
    return [  # a dict of `depth` of them
        (f"layer_{i}/{path}", shape, dt) for i in range(DEPTH) for path, shape, dt in rows
    ]


def flat(tree, prefix=""):
    """(path, shape, dtype) in the dicts' own (insertion) order."""
    out = []
    for name, sub in tree.items():
        if isinstance(sub, dict):
            out += flat(sub, f"{prefix}{name}/")
        else:
            out.append((prefix + name, tuple(sub.shape), str(sub.dtype)))
    return out


def make(layout, store="slots", kv_dtype=None, shift=True, per_row=True):
    where = dict(max_len=MAX_LEN, per_row=per_row) if store == "slots" else dict(
        pages=(N_PAGES, PAGE)
    )
    return dc.make(
        layout, DEPTH, batch=BATCH, heads=HEADS, dim_head=DH, dim=DIM,
        image_fmap_size=FMAP, shift_tokens=shift, dtype=jnp.bfloat16,
        kv_dtype=kv_dtype, **where,
    )


LAYOUTS = pytest.mark.parametrize("layout", [PER_LAYER, STACKED])


@LAYOUTS
@pytest.mark.parametrize("store", ["slots", "paged"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("shift", [False, True])
def test_make_pins_the_format(layout, store, kv_dtype, shift):
    cache = make(layout, store, kv_dtype, shift)
    assert flat(cache) == expected(layout, store, kv_dtype, shift)
    assert dc.layout_of(cache) == layout
    assert dc.depth_of(cache) == DEPTH
    assert dc.batch_axis(cache) == (1 if layout == STACKED else 0)
    assert all(not np.asarray(leaf, np.float32).any() for leaf in jax.tree.leaves(cache))
    kv = np.prod(KV_SHAPE[store]) * DEPTH * 2
    assert dc.kv_bytes(cache) == (kv * 1 + kv // DH * 4 if kv_dtype else kv * 2)


@LAYOUTS
def test_a_lockstep_cache_has_a_scalar_index_a_layer(layout):
    cache = make(layout, per_row=False)
    shapes = {shape for path, shape, _ in flat(cache) if path.endswith("index")}
    assert shapes == {(DEPTH,) if layout == STACKED else ()}


@pytest.mark.parametrize("executor,layout", [("unrolled", PER_LAYER), ("scan", STACKED)])
def test_the_trunk_names_its_executors_layout(executor, layout):
    trunk = Transformer(
        dim=DIM, depth=DEPTH, seq_len=MAX_LEN - 1, heads=HEADS, dim_head=DH,
        image_fmap_size=FMAP, shift_tokens=True, executor=executor,
    )
    assert trunk.cache_layout == layout
    cache = trunk.init_cache(BATCH, MAX_LEN, jnp.bfloat16, per_row=True)
    assert flat(cache) == expected(layout, "slots", None, True)
    paged = trunk.init_cache(
        BATCH, MAX_LEN, jnp.bfloat16, pages=(N_PAGES, PAGE), kv_dtype="int8"
    )
    assert flat(paged) == expected(layout, "paged", "int8", True)


@LAYOUTS
@pytest.mark.parametrize("store", ["slots", "paged"])
def test_side_leaves_go_in_and_come_out(layout, store):
    cache = make(layout, store, "int8")
    table = jnp.arange(BATCH * 3, dtype=jnp.int32).reshape(BATCH, 3)
    bitmaps = jnp.arange(DEPTH * BATCH * 2, dtype=jnp.int32).reshape(DEPTH, BATCH, 2)
    end = jnp.array([4, 5, 6], jnp.int32)
    full = dc.with_side(cache, page_table=table, block_bitmap=bitmaps, ring_end=end)
    for i in range(DEPTH):
        if layout == STACKED:
            attn, top = jax.tree.map(lambda x: x[i], full["attn"]), full
            got_end = top["ring_end"][i]
        else:
            attn, got_end = full[f"layer_{i}"]["attn"], full[f"layer_{i}"]["ring_end"]
        np.testing.assert_array_equal(attn["page_table"], table)
        np.testing.assert_array_equal(attn["block_bitmap"], bitmaps[i])  # layer i's own
        np.testing.assert_array_equal(got_end, end)
    back = dc.without_side(full, "page_table", "block_bitmap", "ring_end")
    assert flat(back) == flat(cache)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(cache)):
        assert a is b  # the leaves themselves, untouched
    # one name at a time, and a name that is not there
    assert "page_table" in str(flat(dc.without_side(full, "block_bitmap")))
    assert flat(dc.without_side(cache, "page_table")) == flat(cache)


def test_bitmaps_follow_the_layer_number_not_the_dict_order():
    """A tree that has been through jit is sorted layer_0, layer_1,
    layer_10, ...: layer 10 still gets row 10."""
    depth = 12
    cache = dc.make(
        PER_LAYER, depth, batch=1, max_len=2, heads=1, dim_head=1, dim=1, per_row=True
    )
    cache = jax.jit(lambda c: c)(cache)
    assert list(cache)[2] == "layer_10"
    bitmaps = jnp.arange(depth, dtype=jnp.int32).reshape(depth, 1, 1)
    full = dc.with_side(cache, block_bitmap=bitmaps)
    for i in range(depth):
        assert int(full[f"layer_{i}"]["attn"]["block_bitmap"][0, 0]) == i


@LAYOUTS
def test_set_index_stamps_every_layer(layout):
    pos = jnp.array([7, 2, 9], jnp.int32)
    cache = dc.set_index(make(layout, kv_dtype="int8"), pos)
    assert flat(cache) == expected(layout, "slots", "int8", True)
    if layout == STACKED:
        np.testing.assert_array_equal(cache["attn"]["index"], np.tile(pos, (DEPTH, 1)))
    else:
        for i in range(DEPTH):
            np.testing.assert_array_equal(cache[f"layer_{i}"]["attn"]["index"], pos)


@LAYOUTS
def test_extract_rings_is_row_major(layout):
    cache = make(layout)
    fill = lambda leaf: jnp.arange(leaf.size, dtype=jnp.float32).reshape(leaf.shape)
    cache = jax.tree.map(fill, cache)
    rings = dc.extract_rings(cache)
    assert all(leaf.shape[0] == BATCH for leaf in jax.tree.leaves(rings))
    for r in range(BATCH):
        for i in range(DEPTH):
            if layout == STACKED:
                got, want = rings["shift_ff"][r, i], cache["shift_ff"][i, r]
            else:
                got, want = rings[f"layer_{i}"]["shift_ff"][r], cache[f"layer_{i}"]["shift_ff"][r]
            np.testing.assert_array_equal(got, want)
    assert dc.extract_rings(make(layout, shift=False)) == {}


@LAYOUTS
@pytest.mark.parametrize("name", ["k", "k_scale"])
def test_row_block_pads_the_last_block_on_the_sequence_axis(layout, name):
    cache = make(layout, kv_dtype="int8")
    stacked = layout == STACKED
    leaf = (cache if stacked else cache["layer_1"])["attn"][name]
    leaf = (jnp.arange(leaf.size) % 100 + 1).astype(leaf.dtype).reshape(leaf.shape)
    seq_ax = -1 if name == "k_scale" else -2
    row = np.asarray(leaf[:, 1] if stacked else leaf[1])
    for j, (lo, hi) in enumerate([(0, 4), (4, 8), (8, 11)]):  # MAX_LEN 11, PAGE 4
        blk = np.asarray(dc.row_block(leaf, 1, j, PAGE, name=name, stacked=stacked))
        assert blk.shape[seq_ax] == PAGE and blk.ndim == row.ndim
        np.testing.assert_array_equal(
            np.take(blk, range(hi - lo), axis=seq_ax), np.take(row, range(lo, hi), axis=seq_ax)
        )
        assert not np.take(blk, range(hi - lo, PAGE), axis=seq_ax).any()
    past = dc.row_block(leaf, 1, 3, PAGE, name=name, stacked=stacked)  # wholly past the end
    assert past.shape == blk.shape and not np.asarray(past).any()


@LAYOUTS
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_scatter_rows_into_slots_and_into_pages(layout, kv_dtype):
    """Row r of a fresh lockstep-shaped cache lands in slot slots[r] of the
    slot state, or block by block in the pages it was given; `index` is
    left alone."""
    stacked = layout == STACKED
    fresh = dc.make(
        layout, DEPTH, batch=2, max_len=MAX_LEN, heads=HEADS, dim_head=DH, dim=DIM,
        image_fmap_size=FMAP, shift_tokens=True, dtype=jnp.bfloat16, kv_dtype=kv_dtype,
    )
    fresh = jax.tree.map(
        lambda leaf: (jnp.arange(leaf.size) % 97 + 1).astype(leaf.dtype).reshape(leaf.shape),
        fresh,
    )
    slots = jnp.array([2, 0], jnp.int32)
    rows = lambda leaf: np.moveaxis(np.asarray(leaf, np.float32), 1 if stacked else 0, 0)

    state = make(layout, "slots", kv_dtype)
    out = dc.scatter_rows(state, fresh, slots)
    assert sorted(flat(out)) == sorted(flat(state))  # tree_map sorts the keys
    for (path, s_leaf), f_leaf in zip(
        jax.tree_util.tree_leaves_with_path(out), jax.tree.leaves(fresh)
    ):
        if path[-1].key == "index":
            assert not np.asarray(s_leaf).any()
            continue
        np.testing.assert_array_equal(rows(s_leaf)[2], rows(f_leaf)[0])
        np.testing.assert_array_equal(rows(s_leaf)[0], rows(f_leaf)[1])
        assert not rows(s_leaf)[1].any()

    paged = make(layout, "paged", kv_dtype)
    page_rows = jnp.array([[1, 2, 3], [4, 2, 0]], jnp.int32)  # row 1's last block: garbage
    out = dc.scatter_rows(paged, fresh, slots, pages=(page_rows, PAGE, jnp.array([0, 0])))
    assert sorted(flat(out)) == sorted(flat(paged))
    attn = out["attn"] if stacked else out["layer_1"]["attn"]
    f_attn = fresh["attn"] if stacked else fresh["layer_1"]["attn"]
    pool, lanes = rows(attn["k"]), rows(f_attn["k"])  # [P, (L,) H, page, dh], [R, (L,) H, S, dh]
    np.testing.assert_array_equal(pool[1], lanes[0][..., 0:4, :])
    np.testing.assert_array_equal(pool[4], lanes[1][..., 0:4, :])
    np.testing.assert_array_equal(pool[3][..., :3, :], lanes[0][..., 8:11, :])
    assert not pool[3][..., 3:, :].any()  # the last block, padded
    np.testing.assert_array_equal(pool[2], lanes[1][..., 4:8, :])  # the later row wins
    ring = rows(out["shift_attn"] if stacked else out["layer_1"]["shift_attn"])
    f_ring = rows(fresh["shift_attn"] if stacked else fresh["layer_1"]["shift_attn"])
    np.testing.assert_array_equal(ring[2], f_ring[0])


@LAYOUTS
def test_restore_prefix_copies_a_page_and_writes_one_rows_rings(layout):
    stacked = layout == STACKED
    state = jax.tree.map(
        lambda leaf: (jnp.arange(leaf.size) % 89 + 1).astype(leaf.dtype).reshape(leaf.shape),
        make(layout, "paged"),
    )
    fresh = jax.tree.map(lambda leaf: leaf + 100, make(layout, "slots"))
    one = jax.tree.map(lambda a: a[1], dc.extract_rings(fresh))  # row 1 of the sidecar
    out = dc.restore_prefix(state, one, jnp.int32(2), page_copy=(jnp.int32(4), jnp.int32(1)))
    axis = 1 if stacked else 0
    for (path, new), old in zip(
        jax.tree_util.tree_leaves_with_path(out), jax.tree.leaves(state)
    ):
        name = path[-1].key
        new, old = np.moveaxis(np.asarray(new, np.float32), axis, 0), np.moveaxis(
            np.asarray(old, np.float32), axis, 0
        )
        if name in ("k", "v"):
            np.testing.assert_array_equal(new[1], old[4])
            np.testing.assert_array_equal(new[[0, 2, 3, 4]], old[[0, 2, 3, 4]])
        elif name in dc.RING_KEYS:
            assert (new[2] == 100).all() and (new[[0, 1]] == old[[0, 1]]).all()
        else:
            np.testing.assert_array_equal(new, old)
    same = dc.restore_prefix(state, one, jnp.int32(2))
    k = lambda c: (c if stacked else c["layer_0"])["attn"]["k"]
    assert k(same) is k(state)  # no page copy asked for: the pool untouched


# a latent layer's leaves in key order, by whether it carries an indexer: the
# two leaves of PR 31, or (PR 41) a position's latent and rotary key in ONE row
LATENT_TABLE = {
    None: [("attn/latent", (BATCH, MAX_LEN, 512), "bfloat16"),
           ("attn/rope", (BATCH, 64, MAX_LEN), "bfloat16"), ("attn/index", (), "int32")],
    128: [("attn/rows", (BATCH, MAX_LEN, 640), "bfloat16"),
          ("attn/index_k", (BATCH, MAX_LEN, 128), "bfloat16"), ("attn/index", (), "int32")],
}


def latent(index_dim, latent_dim=512, rope_dim=64):
    return dc.make(PER_LAYER, DEPTH, kind="latent", batch=BATCH, max_len=MAX_LEN, heads=HEADS,
                   dim_head=DH, dim=DIM, latent_dim=latent_dim, rope_dim=rope_dim,
                   index_dim=index_dim, dtype=jnp.bfloat16)


@pytest.mark.parametrize("index_dim", [None, 128])
def test_an_indexed_latent_layer_keeps_rows_and_an_unindexed_one_its_two_leaves(index_dim):
    cache = latent(index_dim)
    assert flat(cache) == [(f"layer_{i}/{path}", shape, dt) for i in range(DEPTH)
                           for path, shape, dt in LATENT_TABLE[index_dim]]
    numbers = 640 + 128 if index_dim else 512 + 64
    assert dc.kv_bytes(cache) == DEPTH * BATCH * MAX_LEN * numbers * 2
    assert dc.max_len(cache) == MAX_LEN and dc.latent_leaf(cache).shape[0] == BATCH


@pytest.mark.parametrize("latent_dim,rope_dim,width", [(512, 64, 640), (16, 8, 128),
                                                       (192, 64, 256)])
def test_a_row_is_declared_in_whole_tiles_of_lanes(latent_dim, rope_dim, width):
    attn = latent(8, latent_dim, rope_dim)["layer_0"]["attn"]
    assert attn["rows"].shape == (BATCH, MAX_LEN, width) and width % dc.ROW_TILE == 0


@pytest.mark.parametrize("index_dim", [None, 8])
def test_write_scatter_and_snapshot_follow_a_latent_layers_leaves(index_dim):
    """A chunk goes in from `index` on, along the rotary leaf's last axis and
    every other leaf's middle one; a row's columns past its numbers stay zero;
    `scatter_rows` copies rows of every leaf whole; `snapshot` and `restore`
    leave a cache with no state and no ring as it is."""
    cache = dc.set_index(latent(index_dim, 3, 2), jnp.asarray(4))
    attn = cache["layer_1"]["attn"]
    ones = lambda *shape: jnp.ones(shape, jnp.bfloat16)
    chunk = ({"rows": ones(BATCH, 2, 5), "index_k": 3 * ones(BATCH, 2, 8)} if index_dim else
             {"latent": ones(BATCH, 2, 3), "rope": 2 * ones(BATCH, 2, 2)})
    written, length = dc.write(attn, chunk, None)
    assert length == MAX_LEN and set(written) == set(chunk)
    at = np.zeros(MAX_LEN)
    at[4:6] = 1
    if index_dim:
        np.testing.assert_array_equal(np.asarray(written["rows"][1, :, 4], np.float32), at)
        assert not np.asarray(written["rows"][:, :, 5:], np.float32).any()
        np.testing.assert_array_equal(np.asarray(written["index_k"][2, :, 7], np.float32), 3 * at)
    else:
        np.testing.assert_array_equal(np.asarray(written["latent"][1, :, 2], np.float32), at)
        np.testing.assert_array_equal(np.asarray(written["rope"][2, 1], np.float32), 2 * at)
    fresh = {name: {"attn": {**layer["attn"], **written}} for name, layer in cache.items()}
    placed = dc.scatter_rows(latent(index_dim, 3, 2),
                             jax.tree.map(lambda x: x[1:2] if x.ndim else x, fresh),
                             jnp.asarray([2], jnp.int32))
    for name, leaf in placed["layer_0"]["attn"].items():
        if name != "index":
            np.testing.assert_array_equal(np.asarray(leaf[2], np.float32),
                                          np.asarray(written[name][1], np.float32))
            assert not np.asarray(leaf[:2], np.float32).any()
    same = jax.tree.map(lambda a, b: a is b, dc.snapshot(cache), cache)
    assert all(jax.tree.leaves(same)) and dc.restore(cache)[0] is cache
