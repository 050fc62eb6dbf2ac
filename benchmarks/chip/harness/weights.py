"""Seeded weights: the keys and the draws that every model family shares.

A family (``families/<name>.py``) lays out its own leaves; this module
gives it the keys and the exact draws to make them from.  Each leaf is
drawn from its own key: the member's key folded with the leaf's index,
and for a per-layer leaf with the layer (``leaf_key``).  A vocabulary
table (embedding, output head) comes in blocks of ``VOCAB_BLOCK`` rows,
each from its own key.  So a whole tree can be made in one jitted call on
the device (to serve), and any one layer or block of rows can be made
again alone, with the same bits (for the reference, layer by layer).

Values are uniform with the stated standard deviation, built from the
random bits by exact float steps (an integer times a power of two, minus
one, times a constant), so no fusion or sharding of the computation can
change a bit of them.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 128
CHUNK_BLOCKS = 64          # vocab blocks made together


def seed_key(seed: int):
    """A threefry key from any whole number (it may exceed 64 bits or be
    negative)."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32), impl="threefry2x32")


def member_key(seed: int, member: str):
    return jax.random.fold_in(seed_key(seed), zlib.crc32(member.encode())
                              & 0x7FFFFFFF)


def leaf_key(key, index: int, layer=None):
    """The key of a family's leaf ``index``; of its ``layer`` for a
    per-layer leaf (``layer`` may be traced)."""
    k = jax.random.fold_in(key, index)
    return k if layer is None else jax.random.fold_in(k, layer)


def _unit(key, shape):
    """Uniform on [-1, 1) with 2**-23 resolution, bit-exact everywhere."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    return (bits >> 8).astype(jnp.float32) * (2.0 ** -23) - 1.0


def draw(key, shape, std):
    return _unit(key, shape) * (math.sqrt(3.0) * std)


def norm(key, shape):
    """A norm's gain: 1 +- 0.1."""
    return 1.0 + 0.1 * _unit(key, shape)


def fan_in(key, shape):
    """A matmul weight, std 1/sqrt(its first dim)."""
    return draw(key, shape, 1.0 / math.sqrt(shape[0]))


def vocab_blocks(rows: int) -> int:
    if rows % VOCAB_BLOCK:
        raise ValueError(f"vocab {rows} is not a multiple of {VOCAB_BLOCK}")
    return rows // VOCAB_BLOCK


def vocab_chunks(rows: int) -> int:
    return -(-vocab_blocks(rows) // CHUNK_BLOCKS)


def block_rows(key, first_block, nblocks: int, width: int, std,
               dtype=jnp.bfloat16):
    """Rows [first_block*VOCAB_BLOCK, +nblocks*VOCAB_BLOCK) of a table
    whose every block of rows is drawn from ``key`` folded with the
    block's index."""
    blocks = first_block + jnp.arange(nblocks)
    rows = jax.vmap(lambda b: draw(jax.random.fold_in(key, b),
                                   (VOCAB_BLOCK, width), std))(blocks)
    return rows.reshape(nblocks * VOCAB_BLOCK, width).astype(dtype)


def chunk_rows(key, chunk, width: int, std, dtype=jnp.bfloat16):
    """CHUNK_BLOCKS blocks of rows from block chunk*CHUNK_BLOCKS on; the
    last chunk runs past the table, and its extra rows are unused."""
    return block_rows(key, chunk * CHUNK_BLOCKS, CHUNK_BLOCKS, width, std,
                      dtype)


def table(key, rows: int, width: int, std, dtype=jnp.bfloat16):
    """The whole (rows, width) table, made one chunk after another
    (``lax.map``), so the temporaries are one chunk's."""
    r = jax.lax.map(lambda c: chunk_rows(key, c, width, std, dtype),
                    jnp.arange(vocab_chunks(rows)))
    return r.reshape(-1, width)[:rows]
