"""jit'd public wrapper for the flash-attention kernel.

Handles (B, S, H, hd) layout folding, GQA head mapping and dtype dispatch.
The kernel is compiled when lowered for a TPU and interpreted when lowered
for the CPU (``repro.kernels.platform``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import \
    flash_attention_folded


@functools.partial(jax.jit, static_argnames=(
    "causal", "sliding_window", "q_offset", "block_q", "block_k"))
def flash_attention(q, k, v, lens: Optional[jax.Array] = None, *,
                    causal: bool = False, sliding_window: int = 0,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128):
    """q: (B, Sq, Hq, hd); k/v: (B, Skv, Hkv, hd); lens: (B,) valid KV len.

    Returns (B, Sq, Hq, hd) in q.dtype."""
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    lens_i = None if lens is None else lens.astype(jnp.int32)
    out = flash_attention_folded(
        qf, kf, vf, lens_i, causal=causal, window=sliding_window,
        q_offset=q_offset, block_q=block_q, block_k=block_k)
    return out.reshape(B, Hq, Sq, hd).transpose(0, 2, 1, 3)
