"""Pure-jnp oracle for single-token GQA decode attention over a KV cache."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_reference(q, k, v, kv_len):
    """q: (B, Hq, hd); k/v: (B, Skv, Hkv, hd); kv_len: (B,) valid prefix.

    Returns (B, Hq, hd)."""
    B, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, hd)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qf, k.astype(jnp.float32))
    logits = logits / math.sqrt(hd)
    mask = jnp.arange(Skv)[None] < kv_len[:, None]          # (B, Skv)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Hq, hd).astype(q.dtype)


def gather_kv(pool, tbl):
    """Per-row contiguous view (B, max_blocks*blk, [H,] d) of a pool
    whose block-token axis is second-minor: (num_blocks, [H,] blk, d)."""
    g = jnp.moveaxis(pool[tbl], -2, 2)          # (B, mb, blk, [H,] d)
    return g.reshape((tbl.shape[0], -1) + g.shape[3:])


def paged_decode_reference(q, kpool, vpool, tbl, kv_len):
    """Oracle for block-table decode: gather per-row KV views from the
    physical pool (kpool/vpool: (num_blocks, Hkv, block_tokens, hd);
    tbl: (B, max_blocks) int32), then standard masked decode attention."""
    return decode_reference(q, gather_kv(kpool, tbl), gather_kv(vpool, tbl),
                            kv_len)


def paged_mla_decode_reference(q_lat, q_rope, ckv_pool, krope_pool, tbl,
                               kv_len, *, scale):
    """Oracle for absorbed-latent MLA block-table decode.

    q_lat: (B, H, r); q_rope: (B, H, rh); pools: (num_blocks, blk, r|rh);
    returns the latent context ctx = softmax(scores) @ ckv, (B, H, r)."""
    ckv = gather_kv(ckv_pool, tbl).astype(jnp.float32)     # (B, S, r)
    kr = gather_kv(krope_pool, tbl).astype(jnp.float32)    # (B, S, rh)
    s = jnp.einsum("bhr,bkr->bhk", q_lat.astype(jnp.float32), ckv)
    s += jnp.einsum("bhr,bkr->bhk", q_rope.astype(jnp.float32), kr)
    s *= scale
    mask = jnp.arange(ckv.shape[1])[None] < kv_len[:, None]   # (B, S)
    s = jnp.where(mask[:, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkr->bhr", probs, ckv).astype(q_lat.dtype)
