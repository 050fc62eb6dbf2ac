"""Decode attention over a KV cache, Pallas-backed.

``flash_decode`` is the contiguous-cache GQA decode: the Sq=G
specialization of the folded flash-attention kernel (the G grouped q-heads
of one KV head become the q-tile rows, padded to the 8-sublane minimum).

The paged kernels read the physical block pool DIRECTLY through each
row's block table: the table and per-row ``kv_len`` ride the
scalar-prefetch channel (``pltpu.PrefetchScalarGridSpec``), so the KV
BlockSpec index map resolves ``tbl[row, ki]`` on the scalar core one grid
step ahead of the compute — only the row's LIVE physical blocks are ever
DMA'd HBM->VMEM, and nothing materializes a ``(B, max_blocks *
block_tokens, ...)`` gathered view.  Grid steps past a row's last live
block clamp their index map to that block — Pallas skips the copy for a
repeated block index — and skip their compute via ``pl.when``; a row with
``kv_len == 0`` contributes exact zeros.  There is one kernel per
attention kind, written for W query positions per row (speculative
verify); a decode step calls it with W=1:

* GQA (``paged_flash_verify``): the pool is head-major, ``(num_blocks,
  Hkv, block_tokens, hd)``, so one grid step DMAs a whole page — every KV
  head — as ``(Hkv, blk, hd)`` tiles whose last two dims are the pool's
  own (Mosaic's block-shape rule).
* MLA (``paged_flash_verify_mla``): the absorbed-latent cache
  ``(num_blocks, block_tokens, r)`` is one latent "head" shared by every
  query head.

Both take the pools of every layer, stacked ``(L, num_blocks, ...)`` as
the model's layer loop carries them, and the layer to read as a third
scalar-prefetch operand that the KV index map returns as the leading
block index: no layer's pool is sliced out before the call.

Numerics: on CPU the paged kernels are TOKEN-EXACT against the XLA decode
path (``layers.sdpa`` / ``layers.mla_attention``) — greedy sampling flips
on last-ulp logit ties, so "close" is not enough there.  They therefore
stash per-block scores and values in VMEM scratch while streaming, and
run ONE full softmax + PV contraction at the final grid step with the op
order of the XLA path, including the cast of the probabilities to the
value dtype before the PV product (an online-softmax f32 accumulation
diverges by ~4e-3 on bf16 configs, enough to flip argmax).  Scores are
stashed token-major, ``(S, rows)``, so each block lands at a sublane
offset that is a multiple of the block size (a lane-major stash at
offset ``ki * blk`` is refused by Mosaic: lane offsets must be multiples
of 128); the final step transposes the stash back to ``(rows, S)``.  The
scratch is O(max_blocks * block_tokens) per program — serving contexts
are VMEM-resident; a long-context deployment would trade this
bit-exactness for a streaming online softmax.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.flash_attention import \
    flash_attention_folded
from repro.kernels.platform import pallas_call

NEG_INF = -1e30
_LANES = 128
# Mosaic's default scoped-VMEM budget; a kernel asks for its scratch and
# double-buffered blocks on top of it (capped below v5e's 128 MiB VMEM)
_VMEM_DEFAULT = 16 << 20
_VMEM_CAP = 100 << 20


@functools.partial(jax.jit, static_argnames=("block_k",))
def flash_decode(q, k, v, kv_len, *, block_k: int = 128):
    """q: (B, Hq, hd); k/v: (B, Skv, Hkv, hd); kv_len: (B,) int32.

    Returns (B, Hq, hd)."""
    B, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    Gp = max(8, G)  # pad sublanes

    qf = q.reshape(B, Hkv, G, hd).reshape(B * Hkv, G, hd)
    if Gp != G:
        qf = jnp.pad(qf, ((0, 0), (0, Gp - G), (0, 0)))
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)

    out = flash_attention_folded(
        qf, kf, vf, kv_len.astype(jnp.int32), causal=False, window=0,
        q_offset=0, block_q=Gp, block_k=block_k)
    out = out[:, :G, :].reshape(B, Hkv, G, hd).reshape(B, Hq, hd)
    return out


def _tile_bytes(shape, dtype) -> int:
    """VMEM bytes of an array in (sublane, 128-lane) tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // itemsize)
    *lead, rows, cols = shape
    return (math.prod(lead) * -(-rows // sub) * sub
            * -(-cols // _LANES) * _LANES * itemsize)


def _vmem_limit(scratch, blocks) -> int:
    need = sum(_tile_bytes(s, d) for s, d in scratch) + \
        2 * sum(_tile_bytes(s, d) for s, d in blocks)
    return min(_VMEM_CAP, _VMEM_DEFAULT + need)


def _scalar(layer):
    """The layer index as a scalar-prefetch operand."""
    return jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


def _live_block(lens_ref, b, ki, blk):
    """Clamp grid step ``ki`` to the row's last live block: repeated block
    indices make the Pallas pipeline skip the (re-)fetch, so padding-tail
    iterations cost neither DMA nor (via ``pl.when``) compute."""
    live = jax.lax.div(lens_ref[b] + (blk - 1), blk)
    return jnp.clip(ki, 0, jnp.maximum(live - 1, 0))


def _masked_scores(s, ki, kvl, *, blk, w, rows_per_pos):
    """Mask a (W*rows_per_pos, blk) score tile: q row i is position
    t = i // rows_per_pos, whose causal frontier is ``kvl - w + t + 1``
    — the exact column set a plain decode step at depth pos+t sees, so
    per-position outputs equal W successive decode steps bitwise (masked
    columns underflow to exact 0 probability)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(cols < kvl - w + rows // rows_per_pos + 1, s, NEG_INF)


def _softmax_pv(s_t, vals, out_dtype):
    """Final step, in the XLA path's op order: f32 softmax over the full
    (masked) row, probs quantized to the value dtype, one PV dot.
    ``s_t`` is the token-major (S, rows) stash; kv_len == 0 rows give
    uniform probs x all-zero values == exact zeros."""
    probs = jax.nn.softmax(s_t.T, axis=-1)
    probs = probs.astype(vals.dtype).astype(jnp.float32)
    return jax.lax.dot_general(
        probs, vals.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out_dtype)


# ---------------------------------------------------------------------------
# block-table GQA verify / decode kernel
# ---------------------------------------------------------------------------

def _paged_kernel(tbl_ref, lens_ref, layer_ref,   # scalar prefetch
                  q_ref, k_ref, v_ref,      # VMEM blocks
                  o_ref,                    # output block
                  s_scr, v_scr,             # VMEM scratch
                  *, scale: float, blk: int, grid_k: int, hkv: int,
                  w: int, gp: int, hd: int):
    """One (row, page) grid step over every KV head of the page: the
    W*Gp q rows of a head share each streamed block, so speculation adds
    no KV traffic."""
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        # never-stashed columns must read as masked scores and zero values
        s_scr[...] = jnp.full_like(s_scr, NEG_INF)
        v_scr[...] = jnp.zeros_like(v_scr)

    kvl = lens_ref[b]

    @pl.when(ki * blk < kvl)                # dead tail blocks: no compute
    def _stash():
        at = pl.ds(pl.multiple_of(ki * blk, blk), blk)
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)         # (W*Gp, hd)
            k = k_ref[0, h, :, :hd].astype(jnp.float32)  # (blk, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = _masked_scores(s * scale, ki, kvl, blk=blk, w=w,
                               rows_per_pos=gp)
            s_scr[h, at, :] = s.T
            v_scr[h, at, :] = v_ref[0, h, :, :hd]

    @pl.when(ki == grid_k - 1)
    def _finish():
        for h in range(hkv):
            o_ref[0, h] = _softmax_pv(s_scr[h], v_scr[h], o_ref.dtype)


@jax.jit
def paged_flash_verify(q, kpool, vpool, tbl, kv_len, layer):
    """Attention for W query positions per row over the paged pool.

    q: (B, W, Hq, hd) — the pending token plus W-1 draft positions (W=1
    for a decode step), whose KV the caller has already written at
    ``kv_len - W .. kv_len - 1``;
    kpool/vpool: (L, num_blocks, Hkv, block_tokens, >= hd), the pools
    of every layer (the minor dim may be padded past hd), of which the
    int32 scalar ``layer`` is read; tbl: (B, max_blocks) int32; kv_len:
    (B,) int32 TOTAL length including the W new entries.  Returns (B, W,
    Hq, hd).  Position t masks to its own frontier ``kv_len - W + t + 1``.
    """
    B, W, Hq, hd = q.shape
    Hkv, blk, hp = kpool.shape[2:]
    max_blocks = tbl.shape[1]
    S = max_blocks * blk
    G = Hq // Hkv
    Gp = max(8, G)
    R = W * Gp

    qf = q.reshape(B, W, Hkv, G, hd).transpose(0, 2, 1, 3, 4)
    if Gp != G:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, 0), (0, Gp - G), (0, 0)))
    qf = qf.reshape(B, Hkv, R, hd)

    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(hd),
                               blk=blk, grid_k=max_blocks, hkv=Hkv,
                               w=W, gp=Gp, hd=hd)

    def q_map(b, ki, tbl_ref, lens_ref, layer_ref):
        return (b, 0, 0, 0)

    def kv_map(b, ki, tbl_ref, lens_ref, layer_ref):
        return (layer_ref[0], tbl_ref[b, _live_block(lens_ref, b, ki, blk)],
                0, 0, 0)

    scratch = [((Hkv, S, R), jnp.float32), ((Hkv, S, hd), vpool.dtype)]
    blocks = [((Hkv, R, hd), q.dtype)] * 2 + \
        [((Hkv, blk, hp), kpool.dtype)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, Hkv, R, hd), q_map),
            pl.BlockSpec((None, 1, Hkv, blk, hp), kv_map),
            pl.BlockSpec((None, 1, Hkv, blk, hp), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hkv, R, hd), q_map),
        scratch_shapes=[pltpu.VMEM(s, d) for s, d in scratch],
    )
    out = pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(scratch, blocks)),
    )(tbl.astype(jnp.int32), kv_len.astype(jnp.int32), _scalar(layer), qf,
      kpool, vpool)
    out = out.reshape(B, Hkv, W, Gp, hd)[:, :, :, :G]
    return out.transpose(0, 2, 1, 3, 4).reshape(B, W, Hq, hd)


# ---------------------------------------------------------------------------
# block-table MLA (absorbed-latent) verify / decode kernel
# ---------------------------------------------------------------------------

def _paged_mla_kernel(tbl_ref, lens_ref, layer_ref,
                      ql_ref, qr_ref, ckv_ref, kr_ref,
                      o_ref,
                      s_scr, ckv_scr,
                      *, scale: float, blk: int, grid_k: int,
                      w: int, hp: int, r: int, rh: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        s_scr[...] = jnp.full_like(s_scr, NEG_INF)
        ckv_scr[...] = jnp.zeros_like(ckv_scr)

    kvl = lens_ref[b]

    @pl.when(ki * blk < kvl)
    def _stash():
        ql = ql_ref[0].astype(jnp.float32)          # (W*Hp, r)
        qr = qr_ref[0].astype(jnp.float32)          # (W*Hp, rh)
        ckv = ckv_ref[0, :, :r].astype(jnp.float32)     # (blk, r)
        kr = kr_ref[0, :, :rh].astype(jnp.float32)      # (blk, rh)
        s = jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        s = _masked_scores(s * scale, ki, kvl, blk=blk, w=w, rows_per_pos=hp)
        at = pl.ds(pl.multiple_of(ki * blk, blk), blk)
        s_scr[at, :] = s.T
        ckv_scr[at, :] = ckv_ref[0, :, :r]

    @pl.when(ki == grid_k - 1)
    def _finish():
        # identical op order to layers.mla_attention (probs quantized to
        # the cache dtype, one latent-context contraction), a lane tile
        # of q rows at a time to bound the (rows, S) temporaries
        rows = s_scr.shape[1]
        for r0 in range(0, rows, _LANES):
            r1 = min(rows, r0 + _LANES)
            o_ref[0, r0:r1] = _softmax_pv(s_scr[:, r0:r1], ckv_scr[...],
                                          o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_flash_verify_mla(q_lat, q_rope, ckv_pool, krope_pool, tbl, kv_len,
                           layer, *, scale: float):
    """Absorbed-latent MLA attention for W positions per row over the
    paged compressed cache.

    q_lat: (B, W, H, r) latent queries (q_nope @ Wk_b); q_rope: (B, W, H,
    rh); ckv_pool: (L, num_blocks, block_tokens, >= r) and krope_pool:
    (L, num_blocks, block_tokens, >= rh), every layer's (minor dims may
    be padded), of which the int32 scalar ``layer`` is read; tbl: (B,
    max_blocks) int32; kv_len: (B,) TOTAL length including the W freshly
    written latents.  Returns the latent context ctx = attn @ ckv, shape
    (B, W, H, r) — the caller applies Wv_b / wo.  ``scale`` is
    1/sqrt(nope_hd + rope_hd).
    """
    B, W, H, r = q_lat.shape
    rh = q_rope.shape[-1]
    blk, rp = ckv_pool.shape[2:]
    rhp = krope_pool.shape[-1]
    max_blocks = tbl.shape[1]
    S = max_blocks * blk
    Hp = max(8, H)
    R = W * Hp

    ql, qr = q_lat, q_rope
    if Hp != H:
        ql = jnp.pad(ql, ((0, 0), (0, 0), (0, Hp - H), (0, 0)))
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Hp - H), (0, 0)))
    ql = ql.reshape(B, R, r)
    qr = qr.reshape(B, R, rh)

    kernel = functools.partial(_paged_mla_kernel, scale=scale, blk=blk,
                               grid_k=max_blocks, w=W, hp=Hp, r=r, rh=rh)

    def q_map(b, ki, tbl_ref, lens_ref, layer_ref):
        return (b, 0, 0)

    def kv_map(b, ki, tbl_ref, lens_ref, layer_ref):
        return (layer_ref[0], tbl_ref[b, _live_block(lens_ref, b, ki, blk)],
                0, 0)

    scratch = [((S, R), jnp.float32), ((S, r), ckv_pool.dtype)]
    blocks = [((R, r), q_lat.dtype)] * 2 + [((R, rh), q_rope.dtype),
                                            ((blk, rp), ckv_pool.dtype),
                                            ((blk, rhp), krope_pool.dtype)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, R, r), q_map),
            pl.BlockSpec((1, R, rh), q_map),
            pl.BlockSpec((None, 1, blk, rp), kv_map),
            pl.BlockSpec((None, 1, blk, rhp), kv_map),
        ],
        out_specs=pl.BlockSpec((1, R, r), q_map),
        scratch_shapes=[pltpu.VMEM(s, d) for s, d in scratch],
    )
    ctx = pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(scratch, blocks)),
    )(tbl.astype(jnp.int32), kv_len.astype(jnp.int32), _scalar(layer), ql,
      qr, ckv_pool, krope_pool)
    return ctx.reshape(B, W, Hp, r)[:, :, :H, :]
