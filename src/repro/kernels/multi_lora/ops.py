"""jit'd wrapper: per-row multi-adapter LoRA delta (+ optional fused base)."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.multi_lora.multi_lora import multi_lora_pallas


@functools.partial(jax.jit, static_argnames=("scale", "block_n"))
def multi_lora(x, a, b, task_ids, w: Optional[jax.Array] = None, *,
               scale: float = 1.0, block_n: int = 128):
    """x: (N, din); a: (T, din, r); b: (T, r, dout); task_ids: (N,) int32.

    Returns (N, dout) = [x @ w +] scale * B[t] (A[t] x)  per row."""
    T = a.shape[0]
    onehot = jax.nn.one_hot(task_ids, T, dtype=x.dtype)
    delta = multi_lora_pallas(x, a, b, onehot, scale=scale,
                              block_n=block_n)
    if w is not None:
        return x @ w + delta
    return delta
