"""TPU flash attention (Pallas): tiled online-softmax with causal /
sliding-window / length masking and GQA head folding.

This is the TPU adaptation of the paper's §16.3 Composable-Kernel flash
attention: the CK ``window_size`` parameters become block-index predicates
over the Pallas grid, the dense [S,S] mask is never materialized (mask bits
are recomputed from iota inside each (bq, bk) tile), and working memory is
O(block) in VMEM instead of O(S^2) in HBM.

Grid: (B*Hq, num_q_blocks, num_kv_blocks); the kv dimension is the inner
sequential ("arbitrary") axis, with running (m, l, acc) kept in VMEM scratch.
Fully-masked tiles are skipped via ``pl.when`` (MXU work elided; see
DESIGN.md for the DMA-skipping variant trade-off).

Layouts: q (BH, Sq, hd); k/v (BHkv, Skv, hd).  ``ops.py`` handles the
(B, S, H, hd) <-> (BH, S, hd) folding and the XLA fallback.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NEG_INF = -1e30


def _kernel(lens_ref,                      # scalar prefetch: (B,) int32
            q_ref, k_ref, v_ref,           # VMEM blocks
            o_ref,                         # output block
            m_scr, l_scr, acc_scr,         # VMEM scratch
            *, scale: float, causal: bool, window: int, grid_k: int,
            block_q: int, block_k: int, hq: int, group: int,
            q_offset: int, use_lens: bool):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # ---- block-level skip predicate (causal / sliding window) ------------
    q_lo = qi * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_k
    k_hi = k_lo + block_k - 1
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_lo <= q_hi)
    if window > 0:
        run = jnp.logical_and(run, k_hi > q_lo - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols > rows - window
        if use_lens:
            b = bh // hq
            mask &= cols < lens_ref[b]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                              # (bq, 128)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)        # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])    # (bq, 1)
        p = jnp.exp(s - m_new[:, :1])                    # (bq, bk)
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == grid_k - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_folded(q, k, v, lens, *, causal: bool, window: int,
                           q_offset: int, block_q: int = 128,
                           block_k: int = 128):
    """q: (BH, Sq, hd); k/v: (BHkv, Skv, hd); lens: (B,) int32 or None."""
    BH, Sq, hd = q.shape
    BHkv, Skv, _ = k.shape
    group = BH // BHkv
    b_count = 1 if lens is None else lens.shape[0]
    hq = BH // b_count
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    grid = (BH, pl.cdiv(Sq, block_q), pl.cdiv(Skv, block_k))
    use_lens = lens is not None
    if lens is None:
        lens = jnp.zeros((1,), jnp.int32)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        grid_k=grid[2], block_q=block_q, block_k=block_k, hq=hq,
        group=group, q_offset=q_offset, use_lens=use_lens)

    def q_map(bh, qi, ki, lens_ref):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki, lens_ref):
        return (bh // group, ki, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )
    return pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(lens, q, k, v)
