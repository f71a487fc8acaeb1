"""TL007 negative (block-sparse decode): the bitmap rides as TRACED data
— the engine derives it host-side per chunk and threads it in as an
argument (models/decode_cache.py:with_side), so inside the scan body it
is already a tracer; or it is built ONCE outside the body and closed over
as a device array. Both are the shipped pattern and must stay clean."""

import numpy as np
import jax.numpy as jnp
from jax import lax


def chunk_traced_bitmap(state, toks, block_bitmap):
    def body_traced_bitmap(carry, tok):
        rows = jnp.asarray(block_bitmap)  # traced argument, not a constant
        return carry + rows[0, 0, 0], tok

    return lax.scan(body_traced_bitmap, state, toks)


def chunk_hoisted_bitmap(state, toks):
    bitmap = jnp.asarray(np.ones((16, 8, 16), np.int32))  # once, closed over

    def body_hoisted_bitmap(carry, tok):
        return carry + bitmap[0, 0, 0], tok

    return lax.scan(body_hoisted_bitmap, state, toks)
