"""Batched multi-adapter LoRA kernel (Punica/S-LoRA BGMV, TPU adaptation).

The paper's §9 serves n classification tasks from one frozen base; its
baseline runs one forward pass *per task*.  Folding the tasks into the batch
dimension requires applying a per-row adapter: y[n] += B[t[n]] (A[t[n]] x[n]).
On GPU this is the BGMV gather kernel; the TPU adaptation avoids per-row
weight gathers (bad for the MXU) by iterating tasks on the inner sequential
grid axis and accumulating mask-weighted dense tiles:

  grid = (batch_blocks, T);   acc += mask[:, t] * (x_blk @ A[t] @ B[t])

Each (x_blk, A[t], B[t]) tile is MXU-shaped; with T ~ 6-10 adapters of rank
16-64 the redundant work is r*T/din << 1 of the base matmul it replaces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call


def _kernel(x_ref, a_ref, b_ref, m_ref, o_ref, acc_scr, *,
            n_tasks: int, scale: float):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)               # (bn, din)
    a = a_ref[0].astype(jnp.float32)                 # (din, r)
    b = b_ref[0].astype(jnp.float32)                 # (r, dout)
    mask = m_ref[...].astype(jnp.float32)            # (bn, 1)
    h = jax.lax.dot_general(x, a, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(h, b, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    acc_scr[...] += y * mask

    @pl.when(t == n_tasks - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] * scale).astype(o_ref.dtype)


def multi_lora_pallas(x, a, b, task_onehot, *, scale: float = 1.0,
                      block_n: int = 128):
    """x: (N, din); a: (T, din, r); b: (T, r, dout); task_onehot: (N, T)."""
    N, din = x.shape
    T, _, r = a.shape
    dout = b.shape[2]
    block_n = min(block_n, N)
    grid = (pl.cdiv(N, block_n), T)

    return pallas_call(
        functools.partial(_kernel, n_tasks=T, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, din), lambda ni, t: (ni, 0)),
            pl.BlockSpec((1, din, r), lambda ni, t: (t, 0, 0)),
            pl.BlockSpec((1, r, dout), lambda ni, t: (t, 0, 0)),
            pl.BlockSpec((block_n, 1), lambda ni, t: (ni, t)),
        ],
        out_specs=pl.BlockSpec((block_n, dout), lambda ni, t: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((N, dout), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, dout), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x, a, b, task_onehot)
