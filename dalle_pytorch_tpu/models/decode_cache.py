"""The decode cache: the one module that knows how it is laid out.

A decode cache is a pytree of fixed-shape leaves. ONE layer holds

    {"attn": {"k", "v", "index" [, "k_scale", "v_scale"]}
     [, "shift_attn", "shift_ff"]}

  * `k`, `v`: K/V lanes [B, H, L, dh], or a page pool [P, H, page, dh]
    shared by all rows (a host-side page table maps each row's logical
    blocks to pages; page 0 is the serving layer's garbage page);
  * `index`: the next position to write: a scalar (the batch decodes in
    lockstep) or [B] (every row at its OWN position: the continuous-batching
    slot cache);
  * `k_scale`, `v_scale`: float32 per-(position, head) scales of an int8
    K/V store, the K/V shape without its last axis;
  * `shift_attn`, `shift_ff`: the token-shift rings [B, fmap, dim].

A LATENT layer (`layer_spec(kind="latent")`: latent attention, whose keys
and values are expanded from one compressed vector a position) holds, in
place of `k` and `v`, two leaves without a heads axis:

  * `latent` [B, L, latent_dim]: the normed compressed K/V of each position;
  * `rope` [B, rope_dim, L]: the one rotated key that all heads share, the
    positions on its LAST axis.

Two leaves and not one of latent_dim + rope_dim: the values' product reads
the latent alone over every live position, and a slice of a wider leaf would
be a copy of it at every step. The rotary key lies transposed because the
chip lays a last axis out in tiles of 128 lanes: 64 numbers there are stored
as 128, or (the compiler's choice for such a leaf) transposed, and copied
back at every call of a kernel that wants them as declared (139 MB a layer a
step at 64 x 8,480: PERF.md, PR 31). With the positions last, a position costs its
latent_dim + rope_dim numbers and no more. Per-layer layout, scalar `index`.

An INDEXED latent layer (`index_dim`: the layer selects the positions it
attends by a learned score, a lightning indexer beside the latent attention)
never runs that product over its cache: a token step reads the few positions
its selection names, FETCHED, and a fetch costs by the rows it touches, not by
their bytes (27 ns a position a leaf on the chip, the 128-byte rotary key the
dearer of the two: PERF.md, PR 41). So it keeps a position's numbers in ONE
row, and a position is fetched once:

  * `rows` [B, L, latent_dim + rope_dim rounded up to `ROW_TILE`]: a
    position's latent, then its rotary key, then zeros up to whole tiles of
    128 lanes (576 numbers declared as 640: the chip stores a last axis of 576
    so in any case, and the fetch out of a leaf declared as it is stored read
    6% less than out of one declared 576 wide: PERF.md, PR 41);
  * `index_k` [B, L, index_dim]: the indexer's one key a position, positions
    in the middle likewise: 128 numbers are a whole tile of lanes, so nothing
    is padded, the score kernel streams [block, 128] slabs as the latent's
    kernel streams its own (ops/index_score.py), and `write` puts a chunk's
    keys where it puts its rows.

Which of the two a layer holds is read off its leaves (`ROWS in attn`), as the
layout is read off the tree; `ops/sparse_latent_decode.py:split_rows` cuts
rows, fetched ones or a block of the leaf, into what the products take.

A RECURRENT layer (`layer_spec(kind="recurrent")`: linear attention by the
gated delta rule, models/attention.py:GatedDeltaAttention, or a Mamba-2
state-space mixer, `Mamba2Mixer`, whose state is [state size, heads x head
width] and whose ring is `conv_dim` columns wide) holds no position
at all but what the sequence so far has been folded into:

  * `state` float32 [B, key_dim, H * value_dim]: each head's matrix
    [key_dim, value_dim], a row's heads side by side along the last axis
    (`pack_state` / `running_state`). Not [B, H, key_dim, value_dim]: the
    chip lays a last axis out in tiles of 128 lanes, so 192 numbers there
    are stored, read and written as 256 (a third more of the largest
    traffic of a step, and 1.0 GB more at 56 rows x 30 heads x 12 layers
    with the snapshot; the compiler's plan, PERF.md, PR 33); 30 x 192 are
    45 whole tiles;
  * `conv` float32 [B, taps - 1, columns]: the ring of the short
    convolution's last inputs (float32 like the state: the token step's
    convolution reads the numbers the chunked prefill read);
  * `state_at`, `conv_at`: the SNAPSHOT, the two leaves above as they stood
    at the position `set_index` goes back to.

A WINDOW layer (`layer_spec(kind="window")`: attention whose query at t sees
key p iff 0 <= t - p < window) holds `k`, `v` [B, kv_heads, ring, dh], a RING:
position p lies at slot `p mod ring`, and a slot is read under the window's
mask by the TRUE position it holds, which is the newest one written there
(`ring_positions`). `ring = window + draft`: the window and the `draft`
further positions a step may write beyond its first (a verify step of two
writes t and t + 1). `index` is per row. Full K/V layers (`kind="heads"`,
whose `heads` is the K/V head count) share a cache with it; the stacked layout
does not hold it. Beside the ring lie `k_at`, `v_at`, its SNAPSHOT: the ring
as it stood where the session's document ended.

A CONVOLVED layer (`layer_spec(kind="cca")`: attention in a compressed latent
whose q and k pass two causal convolutions of two taps and whose values are
half the position before's, models/attention.py:ConvLatentAttention) holds full
`k`, `v` [B, kv_heads, L, dh] at a per-row `index` like any grouped layer, and
beside them what its next position needs of the last one:

  * `tail` [B, tail_dim], in the cache's dtype: the last position's q and k
    latents before the convolutions, the same between the two, and the half of
    its value projection that the next position's values take;
  * `tail_at`: its SNAPSHOT, the tail at the position `set_index` goes back to
    (a turn's first step convolves with the document's last position).

A position that a verify step wrote and then REJECTED leaves every kind of
K/V layer with no snapshot and no copy. A full layer: by its row's `index`
alone, because what lies at or past it is masked and overwritten. A ring:
because the slot the rejected position t + 1 took held position t + 1 - ring
<= t - window, which had already left the window of every query at t or
later; the next step writes t + 1 again, and until then the slot is masked
as lying in the future. A TURN's rewind is another matter: a turn longer than
the ring overwrites every slot, the positions the document's last query sees
among them, so going back to the document's end restores the ring from its
snapshot (`restore`: `ring` positions a layer, 2 MB a session where the full
layers hold 67).

A K/V layer is rewound by its `index` alone, because what lies past it is
masked and overwritten. A state cannot be rewound: the tokens of a turn are
folded in. So a cache that takes further turns over one document keeps each
row's state at its document's end beside the running one: `snapshot(cache)`
copies running to kept (after a prefill), `restore(cache)` kept to running
(before a turn; it also hands the kept leaves out of the tree while a token
loop runs, and `snapshot(cache, kept)` puts them back), and those two
functions alone know the pair; a window layer's ring is kept by them likewise.
Both leave a cache with neither kind of layer as it is. Per-layer layout; a
recurrent layer's `index` is scalar, or per row where the stack keeps every row
at its own position (a layer of grouped K/V heads beside it): the state itself
stands at no position, so rows whose documents differ in length share a cache
and a step, and `restore` selects a row's leaves as it selects a ring's.

and a cache holds `depth` layers in one of two LAYOUTS:

  * PER_LAYER: a dict of `depth` such layers under `layer_{i}` (the unrolled
    executor walks them in Python); the layers may differ in KIND
    (`make(..., kinds=[...])`: recurrent and K/V layers in one tree);
  * STACKED: the same leaves under a leading `depth` axis (they ride the
    scan executor's carry; each layer writes and reads at its own index).

The layout is read off the tree (a stacked cache has `attn` at its top), so
nothing below takes an executor or a depth. Per-call SIDE leaves ride the
tree while a program runs and are stripped from what it returns:
`page_table` [B, n_pages] and `block_bitmap` [depth, B, nb] inside `attn`,
`ring_end` [B] beside it.

Arrows point one way: attention, transformer, dalle, serving/ and
parallel/serving_partition.py import this module; it imports none of them.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

STACKED, PER_LAYER = "stacked", "per_layer"

ATTN = "attn"
K, V, K_SCALE, V_SCALE, INDEX = "k", "v", "k_scale", "v_scale", "index"
SCALE_KEYS = (K_SCALE, V_SCALE)
LATENT, ROPE = "latent", "rope"
# an indexed latent layer's leaves: a position's latent and rotary key in one
# row, and the indexer's key
ROWS, INDEX_K = "rows", "index_k"
ROW_TILE = 128  # a row is declared in whole tiles of lanes, as the chip stores it
LATENT_KEYS = (LATENT, ROPE, ROWS, INDEX_K)
STATE, CONV = "state", "conv"
# a recurrent layer's running leaves and, beside each, its snapshot
SNAPSHOT = {STATE: "state_at", CONV: "conv_at"}
RING_SNAPSHOT = {K: "k_at", V: "v_at"}  # a window layer's ring, kept likewise
TAIL = "tail"  # a convolved layer's last position, and its snapshot
TAIL_SNAPSHOT = {TAIL: "tail_at"}
KV_KEYS = (K, V) + SCALE_KEYS
RING_KEYS = ("shift_attn", "shift_ff")
PAGE_TABLE, BLOCK_BITMAP, RING_END = "page_table", "block_bitmap", "ring_end"
HIDDEN = "hidden"  # a drafting block's layer: the row's last prompt state
# the stacked layout's layer coordinate, beside the stacked K/V a layer's
# attention is handed (see `layer_view`)
LAYER = "layer"


def layer_key(i: int) -> str:
    """The per-layer layout's name for layer i."""
    return f"layer_{i}"


def _layer_number(name: str) -> int:
    return int(name.rsplit("_", 1)[1])


# ------------------------------------------------------------------ making


def kv_store_dtype(dtype, kv_dtype):
    """(storage dtype, has-scale-leaves) for a K/V store request.
    `kv_dtype=None` stores K/V at the cache `dtype` with no scale leaves."""
    if kv_dtype is None:
        return dtype, False
    assert str(kv_dtype) == "int8", f"unsupported kv_dtype: {kv_dtype!r}"
    return jnp.int8, True


def layer_spec(
    *,
    batch: int,
    heads: int,
    dim_head: int,
    dim: int,
    max_len: Optional[int] = None,
    pages: Optional[tuple] = None,
    per_row: bool = False,
    image_fmap_size: Optional[int] = None,
    shift_tokens: bool = False,
    dtype=jnp.float32,
    kv_dtype=None,
    kind: str = "heads",
    latent_dim: Optional[int] = None,
    rope_dim: Optional[int] = None,
    index_dim: Optional[int] = None,
    key_dim: Optional[int] = None,
    value_dim: Optional[int] = None,
    conv_taps: Optional[int] = None,
    linear_heads: Optional[int] = None,
    conv_dim: Optional[int] = None,
    ring: Optional[int] = None,
    hidden: bool = False,
    tail_dim: Optional[int] = None,
) -> dict:
    """ONE layer's leaves as `jax.ShapeDtypeStruct`s, from the geometry.

    `kind="latent"`: `latent` [batch, max_len, latent_dim] and `rope`
    [batch, rope_dim, max_len] with a scalar `index`; with `index_dim` in
    their place `rows` [batch, max_len, latent_dim + rope_dim rounded up to
    `ROW_TILE`] and `index_k` [batch, max_len, index_dim]; and nothing else
    (no pages, no int8 store, no rings). `kind="recurrent"`: `state`
    [batch, key_dim, linear_heads * value_dim] and `conv` [batch, conv_taps - 1,
    linear_heads * (2 key_dim + value_dim)] (`conv_dim` columns where given:
    a state-space mixer's), both float32, their snapshot beside them, and an
    `index` that is scalar or, with `per_row`, [batch]. `kind="window"`: K/V [batch, heads,
    ring, dim_head] read as a ring (`ring_positions`), their snapshot beside
    them, `index` per row. `kind="cca"`: full K/V lanes, `tail` [batch,
    tail_dim] in the cache dtype and its snapshot, `index` per row.
    `hidden`: beside `attn`, a leaf `hidden` [batch, dim] (a drafting
    block's layer keeps the trunk's last hidden state of each row's prompt
    there: what its next position is made from). Otherwise:

    K/V are lanes [batch, heads, max_len, dim_head], or with `pages =
    (n_pages, page_size)` a pool [n_pages, heads, page_size, dim_head]
    (then `index` is per row: paged rows never decode in lockstep).
    `per_row` sizes `index` [batch] instead of scalar. `kv_dtype="int8"`
    stores K/V quantized beside float32 scales; rings and index keep their
    dtypes."""
    spec = jax.ShapeDtypeStruct
    if kind == "latent":
        assert pages is None and kv_dtype is None and not per_row and not shift_tokens, (
            "a latent cache is lanes in the cache dtype, decoded in lockstep")
        if index_dim:
            width = -(-(latent_dim + rope_dim) // ROW_TILE) * ROW_TILE
            leaves = {ROWS: spec((batch, max_len, width), dtype),
                      INDEX_K: spec((batch, max_len, index_dim), dtype)}
        else:
            leaves = {LATENT: spec((batch, max_len, latent_dim), dtype),
                      ROPE: spec((batch, rope_dim, max_len), dtype)}
        return {ATTN: {**leaves, INDEX: spec((), jnp.int32)}}
    if kind == "recurrent":
        assert pages is None and kv_dtype is None and not shift_tokens, (
            "a recurrent cache is one float32 state a row")
        columns = conv_dim or linear_heads * (2 * key_dim + value_dim)
        running = {
            STATE: spec((batch, key_dim, linear_heads * value_dim), jnp.float32),
            CONV: spec((batch, conv_taps - 1, columns), jnp.float32),
        }
        return {ATTN: {**running, **{SNAPSHOT[n]: s for n, s in running.items()},
                       INDEX: spec((batch,) if per_row else (), jnp.int32)}}
    if kind == "window":
        assert pages is None and kv_dtype is None and per_row and not shift_tokens, (
            "a ring is lanes in the cache dtype, every row at its own position")
        max_len = ring
    elif kind == "cca":
        assert pages is None and kv_dtype is None and per_row and not shift_tokens, (
            "a convolved layer is lanes in the cache dtype, every row at its own position")
    else:
        assert kind == "heads", f"unknown cache kind {kind!r}"
    rows, length = (batch, max_len) if pages is None else pages
    kv_dt, scaled = kv_store_dtype(dtype, kv_dtype)
    attn = {
        K: spec((rows, heads, length, dim_head), kv_dt),
        V: spec((rows, heads, length, dim_head), kv_dt),
        INDEX: spec((batch,) if per_row or pages is not None else (), jnp.int32),
    }
    if scaled:
        attn[K_SCALE] = spec((rows, heads, length), jnp.float32)
        attn[V_SCALE] = spec((rows, heads, length), jnp.float32)
    if kind == "window":
        attn.update({at: attn[n] for n, at in RING_SNAPSHOT.items()})
    if kind == "cca":
        attn[TAIL] = attn[TAIL_SNAPSHOT[TAIL]] = spec((batch, tail_dim), dtype)
    layer = {ATTN: attn}
    if hidden:
        layer[HIDDEN] = spec((batch, dim), dtype)
    if shift_tokens:
        assert image_fmap_size is not None
        for name in RING_KEYS:
            layer[name] = spec((batch, image_fmap_size, dim), dtype)
    return layer


def _zeros(spec: dict, lead: tuple) -> dict:
    # insertion order kept (jax.tree.map would sort the keys)
    return {
        name: _zeros(s, lead) if isinstance(s, dict) else jnp.zeros(lead + s.shape, s.dtype)
        for name, s in spec.items()
    }


def make(layout: str, depth: int, kinds=None, **geometry) -> dict:
    """A zeroed cache of `depth` layers of `layer_spec(**geometry)`: the
    layout applied in this one place. `kinds`: the kind of EACH layer, where
    they differ (else `geometry`'s one `kind` in every layer); a layer of kind
    `none` (no mixer: a feed-forward alone) holds nothing and has no entry."""
    kind = geometry.pop("kind", "heads")
    kinds = tuple(kinds) if kinds else (kind,) * depth
    assert len(kinds) == depth, f"{len(kinds)} kinds for {depth} layers"
    if layout == STACKED:
        assert set(kinds) == {"heads"}, (
            f"the stacked layout holds {depth} equal K/V layers under one depth axis; "
            f"a cache with {sorted(set(kinds))} layers is per layer (the unrolled executor)")
        return _zeros(layer_spec(kind="heads", **geometry), (depth,))
    assert layout == PER_LAYER, f"unknown cache layout {layout!r}"
    specs = {kind: layer_spec(kind=kind, **geometry) for kind in set(kinds) - {"none"}}
    return {layer_key(i): _zeros(specs[kind], ()) for i, kind in enumerate(kinds)
            if kind != "none"}


# ------------------------------------------------- reading the layout back


def layout_of(cache: dict) -> str:
    return STACKED if ATTN in cache else PER_LAYER


def depth_of(cache: dict) -> int:
    if layout_of(cache) == STACKED:
        return cache[ATTN][INDEX].shape[0]
    return len(cache)


def batch_axis(cache: dict) -> int:
    """The axis of a leaf that counts rows (or pages, for a K/V pool)."""
    return 1 if layout_of(cache) == STACKED else 0


def _map_layers(fn, cache: dict) -> dict:
    """`fn(layer, put)` over every layer-shaped dict of the cache: the whole
    stacked tree once, or each `layer_{i}`. `put(x, per_layer=False)` turns
    a value for the layers into the leaf that dict takes: x for all of them
    (broadcast under a depth axis when stacked), or x[i] of a [depth, ...]
    table."""
    if layout_of(cache) == STACKED:
        depth = depth_of(cache)
        return fn(cache, lambda x, per_layer=False: (
            x if per_layer else jnp.broadcast_to(x, (depth,) + x.shape)
        ))
    # by name, not by position: a tree that has been through jit is sorted
    # layer_0, layer_1, layer_10, ...
    return {
        name: fn(layer, lambda x, per_layer=False, i=_layer_number(name): (
            x[i] if per_layer else x
        ))
        for name, layer in cache.items()
    }


def set_index(cache: dict, pos: jnp.ndarray) -> dict:
    """Overwrite every layer's `index` with `pos`.

    Layers advance in lockstep, so the per-layer indices are copies of one
    logical position; the continuous-batching chunk loop keeps it as
    explicit per-slot state (`img_pos`) and stamps it in before each step,
    which is also how retired slots stay frozen."""
    return _map_layers(
        lambda layer, put: {
            **layer, ATTN: {**layer[ATTN], INDEX: put(pos).astype(jnp.int32)}
        },
        cache,
    )


def _kept_pairs(attn: dict) -> dict:
    """{running leaf: its snapshot leaf} of a layer that keeps one."""
    pairs = SNAPSHOT if STATE in attn else TAIL_SNAPSHOT if TAIL in attn else RING_SNAPSHOT
    return pairs if all(at in attn for at in pairs.values()) else {}


def snapshot(cache: dict, kept: Optional[dict] = None) -> dict:
    """The cache with every recurrent or window layer's snapshot leaves:
    `kept`, what `restore` took out of it, or (left out) the running state
    and ring as they stand: a session's document is in."""
    if layout_of(cache) == STACKED:  # K/V layers alone
        return cache

    def keep(name, layer):
        attn = layer[ATTN]
        if kept is not None:
            return {**layer, ATTN: {**attn, **kept.get(name, {})}}
        return {**layer, ATTN: {**attn, **{at: attn[n] for n, at in _kept_pairs(attn).items()}}}

    return {name: keep(name, layer) for name, layer in cache.items()}


def restore(cache: dict):
    """`(cache, kept)`: every recurrent layer's running state and ring, and
    every window layer's K/V ring, as `snapshot` kept them (a turn starts
    where the document ended), and the
    snapshot leaves themselves taken OUT of the tree, to be put back by
    `snapshot(cache, kept)`: a token loop carries what it writes, and a leaf
    that rides its carry untouched costs a buffer of its own. The one device
    copy is the running leaves'; the full K/V layers' part of going back is
    `set_index`, and costs nothing."""
    if layout_of(cache) == STACKED:  # K/V layers alone
        return cache, {}
    kept, out = {}, {}
    with jax.named_scope("state_restore"):
        for name, layer in cache.items():
            attn = layer[ATTN]
            pairs = _kept_pairs(attn)
            if not pairs:
                out[name] = layer
                continue
            kept[name] = {at: attn[at] for at in pairs.values()}
            # a per-row index (a window layer's always) selects a row's leaves
            rows = ((lambda x, leaf: x[(slice(None),) + (None,) * (leaf.ndim - 1)])
                    if attn[INDEX].ndim else (lambda x, leaf: x))
            out[name] = {**layer, ATTN: {
                **{n: leaf for n, leaf in attn.items() if n not in kept[name]},
                # a select on the index's sign, which is never negative: an
                # operation of its own, under this scope's name in the trace,
                # that the compiler can neither drop nor hand to its own
                # unnamed copies, and that reads the running leaf, so a
                # donated cache's buffer is the copy's target and is paired
                # with its own output
                **{n: jnp.where(rows(attn[INDEX] >= 0, attn[n]), attn[at], attn[n])
                   for n, at in pairs.items()}}}
    return (out, kept) if kept else (cache, kept)


def with_side(cache: dict, page_table=None, block_bitmap=None, ring_end=None) -> dict:
    """The cache with per-call side leaves injected into every layer:

    `page_table` [B, n_pages]: K/V are a page pool and this maps each row's
    logical blocks to pages (host state, traced data: one program whatever
    is mapped). `block_bitmap` [depth, B, nb]: the decode-sparsity policy's
    per-layer KV tile bitmaps (nonzero = may be read). `ring_end` [B]: the
    per-row resume window `shift_with_ring` rebuilds rings below
    (`decode_resume`)."""
    table, bitmaps, end = (
        None if x is None else jnp.asarray(x, jnp.int32)
        for x in (page_table, block_bitmap, ring_end)
    )

    def inject(layer, put):
        attn = dict(layer[ATTN])
        if table is not None:
            attn[PAGE_TABLE] = put(table)
        if bitmaps is not None:
            attn[BLOCK_BITMAP] = put(bitmaps, per_layer=True)
        layer = {**layer, ATTN: attn}
        if end is not None:
            layer[RING_END] = put(end)
        return layer

    return _map_layers(inject, cache)


def without_side(cache: dict, *names: str) -> dict:
    """The cache without the named side leaves (the persistent donated
    state keeps its side-free shape: tables are host state)."""
    return _map_layers(
        lambda layer, put: {
            **{n: leaf for n, leaf in layer.items() if n not in names},
            ATTN: {n: leaf for n, leaf in layer[ATTN].items() if n not in names},
        },
        cache,
    )


def extract_rings(cache: dict) -> dict:
    """Row-major token-shift rings of a fresh prefill cache, in the cache's
    own tree shape (stacked: [R, depth, fmap, dim] per ring): the part of a
    prefix's post-prefill state that is not page-addressable. Empty when
    the model shifts no tokens."""
    if layout_of(cache) == STACKED:
        return {n: jnp.moveaxis(cache[n], 1, 0) for n in RING_KEYS if n in cache}
    out = {}
    for name, layer in cache.items():
        rings = {n: layer[n] for n in RING_KEYS if n in layer}
        if rings:
            out[name] = rings
    return out


# ------------------------------------- a fresh cache's rows into the state


def leaf_name(path) -> str:
    """Last mapping key of a tree path ('k', 'img_pos', ...)."""
    for p in reversed(path):
        key = getattr(p, "key", None)
        if key is not None:
            return str(key)
    return ""


def kv_bytes(cache: dict) -> int:
    """Bytes of the K/V leaves, quantization scales included (of a latent
    layer: its latent and its shared rotary key, as two leaves or as rows of
    both, and, where it has one, its indexer's key)."""
    return sum(
        leaf.size * leaf.dtype.itemsize
        for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
        if leaf_name(path) in KV_KEYS + LATENT_KEYS
    )


def latent_leaf(cache: dict):
    """A latent cache's first leaf that holds a latent a position, alone or
    in a row with its rotary key: [B, L, ...]."""
    return next(leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
                if leaf_name(path) in (LATENT, ROWS))


def max_len(cache: dict) -> int:
    """Positions a latent cache holds a row."""
    return latent_leaf(cache).shape[1]


def pack_state(state):
    """Heads' matrices [B, H, key_dim, value_dim] as the `state` leaf keeps
    them: [B, key_dim, H * value_dim]."""
    b, h, dk, dv = state.shape
    return state.transpose(0, 2, 1, 3).reshape(b, dk, h * dv)


def running_state(cache: dict, layer: int, heads: int, rows: Optional[int] = None):
    """Recurrent layer `layer`'s running state, a matrix a head: [B, heads,
    key_dim, value_dim], of the first `rows` rows where given."""
    leaf = cache[layer_key(layer)][ATTN][STATE][:rows]
    b, dk, _ = leaf.shape
    return leaf.reshape(b, dk, heads, -1).transpose(0, 2, 1, 3)


def state_bytes(cache: dict) -> int:
    """Bytes of the recurrent layers' leaves and the convolved layers' tails,
    running and kept."""
    names = (*SNAPSHOT, *SNAPSHOT.values(), *TAIL_SNAPSHOT, *TAIL_SNAPSHOT.values())
    return sum(
        leaf.size * leaf.dtype.itemsize
        for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
        if leaf_name(path) in names
    )


def row_block(leaf, r: int, j: int, page_size: int, *, name: str, stacked: bool):
    """Row r's block j of a K/V lane leaf (`name` tells K/V from a scale
    leaf, whose sequence axis is last), zero-padded to `page_size` on the
    sequence axis past the leaf's end (static shapes throughout)."""
    row = leaf[:, r] if stacked else leaf[r]
    seq_ax = row.ndim - (1 if name in SCALE_KEYS else 2)
    max_len = row.shape[seq_ax]
    lo = j * page_size
    hi = min(lo + page_size, max_len)
    if hi <= lo:
        shape = list(row.shape)
        shape[seq_ax] = page_size
        return jnp.zeros(shape, row.dtype)
    blk = lax.slice_in_dim(row, lo, hi, axis=seq_ax)
    if hi - lo < page_size:
        pad = [(0, 0)] * row.ndim
        pad[seq_ax] = (0, page_size - (hi - lo))
        blk = jnp.pad(blk, pad)
    return blk


def scatter_rows(state_cache: dict, fresh: dict, slots, pages=None) -> dict:
    """Row r of the fresh prefill cache written into slot `slots[r]` of the
    persistent one, leaf by leaf. `index` leaves are not scattered: the
    chunk step stamps every layer's index from the per-slot `img_pos`
    (`set_index`).

    `pages = (page_rows [R, n], page_size, extra [R] or None)`: the state's
    K/V are a page pool, and row r's block j goes to page `page_rows[r, j]`
    (+ its last block once more to `extra[r]`: the prefix cache's snapshot
    of the divergence block; page 0 absorbs it for rows not registering)."""
    stacked = layout_of(state_cache) == STACKED
    axis = batch_axis(state_cache)
    n_rows = slots.shape[0]

    def put_page(out, blk, page):
        blk = blk[:, None] if stacked else blk[None]
        start = ((0, page) if stacked else (page,)) + (0,) * (blk.ndim - axis - 1)
        return lax.dynamic_update_slice(out, blk, start)

    def write(path, s_leaf, p_leaf):
        name = leaf_name(path)
        if name == INDEX:
            return s_leaf
        out = s_leaf
        if pages is not None and name in KV_KEYS:
            page_rows, page_size, extra = pages
            block = lambda r, j: row_block(
                p_leaf, r, j, page_size, name=name, stacked=stacked
            ).astype(out.dtype)
            for r in range(n_rows):
                for j in range(page_rows.shape[1]):
                    out = put_page(out, block(r, j), page_rows[r, j])
                if extra is not None:
                    out = put_page(out, block(r, page_rows.shape[1] - 1), extra[r])
            return out
        for r in range(n_rows):
            p_row = lax.dynamic_slice_in_dim(p_leaf, r, 1, axis=axis)
            out = lax.dynamic_update_slice_in_dim(
                out, p_row.astype(out.dtype), slots[r], axis=axis
            )
        return out

    return jax.tree_util.tree_map_with_path(write, state_cache, fresh)


def restore_prefix(state_cache: dict, rings: dict, slot, page_copy=None) -> dict:
    """A cached prefix's non-page-addressable state into `slot` of a paged
    cache: its rings (one row of `extract_rings`), and with `page_copy =
    (src, dst)` the pool's page src copied to dst in every K/V leaf (the
    divergence block, copy-on-write). `index` is stamped every chunk."""
    axis = batch_axis(state_cache)

    def upd(path, leaf):
        name = leaf_name(path)
        if name in KV_KEYS:
            if page_copy is None:
                return leaf
            src, dst = page_copy
            blk = lax.dynamic_slice_in_dim(leaf, src, 1, axis=axis)
            return lax.dynamic_update_slice_in_dim(leaf, blk, dst, axis=axis)
        if name in RING_KEYS:
            node = rings
            for p in path:
                node = node[p.key]
            return lax.dynamic_update_slice_in_dim(
                leaf, jnp.expand_dims(node, axis).astype(leaf.dtype), slot, axis=axis
            )
        return leaf

    return jax.tree_util.tree_map_with_path(upd, state_cache)


# --------------------------------------- one layer's access, inside a step


def view(buf: jnp.ndarray, layer) -> jnp.ndarray:
    """This layer's leaf as its reader takes it: the leaf itself, or
    `buf[layer]` of a depth-stacked one."""
    if layer is None:
        return buf
    with jax.named_scope("cache_read"):
        return lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


def layer_view(stack: dict, layer) -> dict:
    """One layer's cache out of a stacked one: the small leaves (index,
    rings, side leaves) sliced at `layer`, K/V and their scales left
    stacked beside the `layer` coordinate: attention writes its chunk into
    them at [layer] and reads `leaf[layer]` as a view."""
    attn = {
        name: leaf if name in KV_KEYS else view(leaf, layer)
        for name, leaf in stack[ATTN].items()
    }
    rest = {name: view(leaf, layer) for name, leaf in stack.items() if name != ATTN}
    return {ATTN: {**attn, LAYER: layer}, **rest}


def layer_store(stack: dict, layer, attn_cache: dict, rings: dict) -> dict:
    """The stacked cache after one layer: K/V as attention left them
    (already written in place), the layer's new index and rings written
    back at `layer`; what a layer only reads stays as it was."""
    with jax.named_scope("cache_write"):
        put = lambda leaf, new: lax.dynamic_update_index_in_dim(
            leaf, new.astype(leaf.dtype), layer, 0
        )
        attn = {
            **stack[ATTN],
            **{name: attn_cache[name] for name in KV_KEYS if name in attn_cache},
            INDEX: put(stack[ATTN][INDEX], attn_cache[INDEX]),
        }
        ring_stacks = {name: put(stack[name], ring) for name, ring in rings.items()}
    return {**stack, ATTN: attn, **ring_stacks}


def _write_lanes(buf, val, index, layer):
    """val [B,H,n,D] into buf [B,H,S,D] at sequence position `index` (the
    scale leaves one rank lower), a scalar or per row [B]; with `layer`,
    buf is the depth-stacked leaf and the write lands at `buf[layer]`, in
    place, the whole stack coming back."""
    tail = (0,) * (val.ndim - 3)  # (0,) for K/V, () for their scales
    val = val.astype(buf.dtype)
    if layer is not None:
        val = val[None]
    if jnp.ndim(index) == 0:
        start = (0, 0, index) + tail
        return lax.dynamic_update_slice(
            buf, val, start if layer is None else (layer,) + start
        )
    if layer is None:
        return jax.vmap(
            lambda b, v, i: lax.dynamic_update_slice(b, v, (0, i) + tail)
        )(buf, val, index)
    return jax.vmap(
        lambda b, v, i: lax.dynamic_update_slice(b, v, (layer, 0, i) + tail),
        in_axes=(1, 1, 0), out_axes=1,
    )(buf, val, index)


def ring_positions(last: jnp.ndarray, ring: int) -> jnp.ndarray:
    """[B, ring] int32: the position each slot of a ring holds once positions
    up to `last` [B] are written: the newest p <= last with p mod ring == slot
    (negative: the slot was never written)."""
    slots = jnp.arange(ring, dtype=jnp.int32)
    return last[:, None] - (last[:, None] - slots) % ring


def write_ring(attn_cache: dict, vals: dict, start: bool) -> dict:
    """A window layer's chunk `vals` (k, v [B, H, n, D]) written into its
    ring, position p at slot p mod ring. A step's few positions go in from
    each row's own `index` on: a select over the ring's slots, one pass over
    a leaf that is a window long. A chunk that STARTS every row's sequence
    (positions 0..n-1, as a prefill's does) lays out what of it the ring can
    hold, its last `ring` positions, by one static roll. Returns the written
    leaves by name."""
    index = attn_cache[INDEX]
    out = {}
    with jax.named_scope("cache_write"):
        for name, val in vals.items():
            buf = attn_cache[name]
            ring, n = buf.shape[2], val.shape[2]
            val = val.astype(buf.dtype)
            if start:
                if n >= ring:
                    buf = jnp.roll(val[:, :, n - ring:], (n - ring) % ring, axis=2)
                else:
                    buf = lax.dynamic_update_slice(buf, val, (0, 0, 0, 0))
            else:
                slots = jnp.arange(ring, dtype=index.dtype)
                for j in range(n):
                    here = slots == ((index + j) % ring)[:, None]  # [B, ring]
                    buf = jnp.where(here[:, None, :, None], val[:, :, j:j + 1], buf)
            out[name] = buf
    return out


def write_rows(attn_cache: dict, vals: dict, start: bool) -> dict:
    """A full K/V layer's chunk `vals` (k, v [B, H, n, D]) written along its
    lanes: from position 0 on where it STARTS every row's sequence, else from
    each row's own `index` on, in place, one `dynamic_update_slice` a row.
    (Batched over the rows, XLA makes a loop of it whose body carries no
    name, so that its time belongs to nobody in the trace; a `fori_loop` over
    the rows sends the described v5e's compiler into a RET_CHECK: PERF.md,
    PR 37.) Returns the written leaves by name."""
    index = attn_cache[INDEX]
    out = {}
    with jax.named_scope("cache_write"):
        for name, val in vals.items():
            buf = attn_cache[name]
            val = val.astype(buf.dtype)
            if start:
                buf = lax.dynamic_update_slice(buf, val, (0, 0, 0, 0))
            else:
                for r in range(val.shape[0]):
                    buf = lax.dynamic_update_slice(buf, val[r:r + 1], (r, 0, index[r], 0))
            out[name] = buf
    return out


def write(attn_cache: dict, vals: dict, seq_cap: int):
    """One chunk written into a layer's attention cache at its `index`:
    `vals` are the chunk's leaves by name (k, v [B,H,n,D] and, for an int8
    store, their scales [B,H,n]). Returns (the written leaves by name, the
    cache length attention sees).

    Lanes take the chunk at [.., index:index+n] (per row for a [B] index).
    A paged cache (it carries `page_table`) takes position p of row b at
    page `table[b, p // page_size]`, offset `p % page_size`; its virtual
    length is the slotted cache's (`seq_cap`), and finished rows clamp to
    the spare last position as the lanes' dynamic_update_slice does. A
    stacked leaf (the cache carries `layer`) is written at [layer]. A latent
    layer's `vals` are `latent` [B, n, latent_dim] and `rope` [B, rope_dim, n],
    or of an indexed one `rows` [B, n, latent_dim + rope_dim] (the leaf's
    columns past those stay the zeros they were made) and `index_k` [B, n,
    index_dim]."""
    index, layer = attn_cache[INDEX], attn_cache.get(LAYER)
    if LATENT in attn_cache or ROWS in attn_cache:
        # a latent layer: the chunk's n positions from `index` on, along
        # the rotary key's last axis and every other leaf's middle one
        assert jnp.ndim(index) == 0 and layer is None, "a latent cache decodes in lockstep"
        with jax.named_scope("cache_write"):
            out = {
                name: lax.dynamic_update_slice(
                    attn_cache[name], val.astype(attn_cache[name].dtype),
                    (0, 0, index) if name == ROPE else (0, index, 0))
                for name, val in vals.items()
            }
        return out, out[LATENT if LATENT in out else ROWS].shape[1]
    n = vals[K].shape[2]
    if PAGE_TABLE not in attn_cache:
        with jax.named_scope("cache_write"):
            out = {
                name: _write_lanes(attn_cache[name], val, index, layer)
                for name, val in vals.items()
            }
        return out, out[K].shape[-2]
    assert jnp.ndim(index) == 1, "paged caches always carry per-row indices"
    table = attn_cache[PAGE_TABLE]
    page_size = attn_cache[K].shape[-2]
    max_len = min(table.shape[-1] * page_size, seq_cap)
    pos = jnp.minimum(index[:, None] + jnp.arange(n), max_len - 1)  # [B, n]
    page = jnp.take_along_axis(table, pos // page_size, axis=1)
    off = pos % page_size
    at = (page, slice(None), off, slice(None))
    if layer is not None:
        at = (layer,) + at
    with jax.named_scope("cache_write"):
        out = {}
        for name, val in vals.items():
            scale = name in SCALE_KEYS
            leaf = attn_cache[name]
            out[name] = leaf.at[at[:-1] if scale else at].set(
                val.transpose(0, 2, 1) if scale
                else val.transpose(0, 2, 1, 3).astype(leaf.dtype)
            )
    return out, max_len
