"""Unified fleet-model stack: init / forward / prefill / decode for every
assigned architecture, driven by the ``LayerGroup``/``BlockSpec`` config.

Layer groups are scanned (``jax.lax.scan``) over their repeat dimension with
the period unrolled inside the scan body, so a 100-layer model lowers to a
compact HLO loop — essential for 512-device dry-run compile times.

Modes
  full     training forward, no cache
  prefill  full-sequence forward that also writes the serving cache
  decode   one-token step against the cache

Caches mirror the param tree: ``cache["g{i}"]["b{j}"]`` holds the stateful
block's state stacked over the group's repeat dim; ``cache["pos"]`` is the
current length (scalar int32, shared across the batch).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.config import BlockSpec, LayerGroup, ModelConfig
from repro.sharding import constrain

Array = jax.Array


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ===========================================================================
# init
# ===========================================================================

def _block_init(key, cfg: ModelConfig, spec: BlockSpec, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"norm1": jnp.ones((cfg.d_model,), dtype)}
    if spec.mixer in ("attn", "bidir_attn", "cross_attn"):
        p["attn"] = L.attn_init(ks[0], cfg, dtype)
    elif spec.mixer == "mla":
        p["mla"] = L.mla_init(ks[0], cfg, dtype)
    elif spec.mixer == "mamba":
        p["mamba"] = S.mamba_init(ks[0], cfg, dtype)
    elif spec.mixer == "mlstm":
        p["mlstm"] = S.mlstm_init(ks[0], cfg, dtype)
    elif spec.mixer == "slstm":
        p["slstm"] = S.slstm_init(ks[0], cfg, dtype)
    if spec.ffn == "dense":
        p["norm2"] = jnp.ones((cfg.d_model,), dtype)
        p["ffn"] = L.ffn_init(ks[1], cfg, dtype)
    elif spec.ffn == "moe":
        p["norm2"] = jnp.ones((cfg.d_model,), dtype)
        p["moe"] = M.moe_init(ks[1], cfg, dtype)
    return p


def _group_init(key, cfg: ModelConfig, group: LayerGroup, dtype) -> dict:
    def one(k):
        kk = jax.random.split(k, len(group.period))
        return {f"b{i}": _block_init(kk[i], cfg, spec, dtype)
                for i, spec in enumerate(group.period)}
    return jax.vmap(one)(jax.random.split(key, group.repeats))


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = _dtype(cfg)
    ks = jax.random.split(key, 4 + len(cfg.groups) + len(cfg.encoder_groups))
    params = {
        "embed": L.dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype,
                              scale=0.02),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                         dtype)
    for i, g in enumerate(cfg.groups):
        params[f"g{i}"] = _group_init(ks[4 + i], cfg, g, dtype)
    for i, g in enumerate(cfg.encoder_groups):
        params[f"enc_g{i}"] = _group_init(
            ks[4 + len(cfg.groups) + i], cfg, g, dtype)
    if cfg.encoder_groups:
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), dtype)
    return params


# ===========================================================================
# cache
# ===========================================================================

def _block_cache(cfg: ModelConfig, spec: BlockSpec, batch: int, max_seq: int,
                 dtype):
    if spec.mixer in ("attn", "bidir_attn"):
        kv = (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    if spec.mixer == "cross_attn":
        kv = (batch, cfg.cross_ctx_len, cfg.n_kv_heads, cfg.head_dim)
        return {"ck": jnp.zeros(kv, dtype), "cv": jnp.zeros(kv, dtype)}
    if spec.mixer == "mla":
        return {
            "ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_seq, cfg.rope_head_dim), dtype),
        }
    if spec.mixer == "mamba":
        return S.mamba_zero_state(cfg, batch, dtype)
    if spec.mixer == "mlstm":
        return S.mlstm_zero_state(cfg, batch, dtype)
    if spec.mixer == "slstm":
        return S.slstm_zero_state(cfg, batch, dtype)
    return None


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    dtype = _dtype(cfg)
    cache = {"pos": jnp.zeros((), jnp.int32)}

    def stack(tree, n):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), tree)

    for i, g in enumerate(cfg.groups):
        gc = {}
        for j, spec in enumerate(g.period):
            bc = _block_cache(cfg, spec, batch, max_seq, dtype)
            gc[f"b{j}"] = None if bc is None else stack(bc, g.repeats)
        cache[f"g{i}"] = gc
    return cache


# --- paged (block) KV cache -------------------------------------------------

def paged_supported(cfg: ModelConfig) -> bool:
    """Paged KV works for pure attention/MLA stacks: recurrent (SSM)
    state is per-slot, not per-position, and cross-attention context is
    per-request — neither pages."""
    return (not cfg.cross_ctx_len and not cfg.encoder_groups and
            all(s.mixer in ("attn", "mla")
                for g in cfg.groups for s in g.period))


def _lanes(width: int) -> int:
    """A pool's minor dim, padded to whole 128-lane tiles.  A TPU lays an
    array whose minor dim is narrower out with another dim minor (here
    the block index), which pages cannot be read from: the paged kernels
    take row-major operands, so every step would relayout every pool.
    Readers slice the real width back out."""
    return -(-width // 128) * 128


def _block_paged_cache(cfg: ModelConfig, spec: BlockSpec, repeats: int,
                       num_blocks: int, block_tokens: int, dtype):
    # the block-token axis is second-minor in every pool: GQA pools are
    # head-major so one page of one KV head is a contiguous (blk, hd)
    # tile (the paged Pallas kernels DMA whole pages of every head)
    lead = (repeats, num_blocks)
    if spec.mixer == "attn":
        kv = lead + (cfg.n_kv_heads, block_tokens, _lanes(cfg.head_dim))
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    if spec.mixer == "mla":
        return {
            "ckv": jnp.zeros(lead + (block_tokens, _lanes(cfg.kv_lora_rank)),
                             dtype),
            "krope": jnp.zeros(
                lead + (block_tokens, _lanes(cfg.rope_head_dim)), dtype),
        }
    raise ValueError(f"paged cache unsupported for mixer {spec.mixer!r}")


def init_paged_cache(cfg: ModelConfig, slots: int, max_seq: int,
                     num_blocks: int, block_tokens: int) -> dict:
    """Paged serving cache: KV leaves are physical block pools
    ``(repeats, num_blocks, [Hkv,] block_tokens, d)`` shared by every slot
    (``d`` padded to whole 128-lane tiles, ``_lanes``);
    ``cache["tbl"]`` (slots, max_seq // block_tokens) maps each slot's
    logical blocks to physical ones (0 = the reserved trash block).  The
    per-slot table width equals the contiguous ``max_seq``, so a gathered
    per-row KV view has exactly the contiguous layout — paged decode is
    bit-identical to the contiguous path."""
    if max_seq % block_tokens:
        raise ValueError("max_seq must be a multiple of block_tokens")
    dtype = _dtype(cfg)
    cache = {"pos": jnp.zeros((slots,), jnp.int32),
             "tbl": jnp.zeros((slots, max_seq // block_tokens), jnp.int32)}
    for i, g in enumerate(cfg.groups):
        cache[f"g{i}"] = {
            f"b{j}": _block_paged_cache(cfg, spec, g.repeats, num_blocks,
                                        block_tokens, dtype)
            for j, spec in enumerate(g.period)}
    return cache


# ===========================================================================
# block application
# ===========================================================================

def _cache_write(buf: Array, val: Array, pos) -> Array:
    """Write ``val`` (B, 1, ...) into ``buf`` (B, S, ...) at sequence
    position ``pos`` — a scalar (uniform across the batch) or a (B,) vector
    of per-row positions (continuous-batching decode, where every row sits
    at its own depth in the sequence)."""
    val = val.astype(buf.dtype)
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(
            buf, val, (0, pos) + (0,) * (buf.ndim - 2))
    return jax.vmap(
        lambda b, v, p: jax.lax.dynamic_update_slice(
            b, v, (p,) + (0,) * (b.ndim - 1)))(buf, val, pos)

def _paged_write(pool: Array, val: Array, tbl: Array, pos, layer) -> Array:
    """Write ``val`` (B, T, [H,] d) into layer ``layer`` of the block
    pools ``(L, num_blocks, [H,] block_tokens, d)`` at each row's
    absolute positions ``pos .. pos+T`` through its block-table row
    ``tbl`` (B, max_blocks).  Positions past the table (or rows whose
    table maps to 0) land in the reserved trash block — freed slots and
    pad tails write garbage somewhere harmless instead of into live rows.

    Whole blocks are stored: the blocks a row's run touches are read,
    its tokens merged in, and one scatter puts them back.  A block's
    window spans the pool's trailing dims, so the scatter updates the
    stacked pools in place in their own layout (a token-sized window
    makes the compiler relayout the pools around it)."""
    blk = pool.shape[-2]
    B, T = val.shape[:2]
    mb = tbl.shape[1]
    tail = (1,) * (val.ndim - 2)
    val = jnp.pad(val.astype(pool.dtype), [(0, 0)] * (val.ndim - 1)
                  + [(0, pool.shape[-1] - val.shape[-1])])
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    off = pos % blk
    nb = (T + blk - 2) // blk + 1          # blocks a T-token run can touch
    lb = pos[:, None] // blk + jnp.arange(nb, dtype=jnp.int32)[None, :]
    phys = jnp.take_along_axis(tbl, jnp.clip(lb, 0, mb - 1), axis=1)
    cols = jnp.arange(nb * blk, dtype=jnp.int32)[None, :]
    live = (cols >= off[:, None]) & (cols < off[:, None] + T)
    new = jnp.take_along_axis(
        val, jnp.clip(cols - off[:, None], 0, T - 1).reshape((B, -1) + tail),
        axis=1)
    old = jnp.moveaxis(pool[layer, phys], -2, 2).reshape(new.shape)
    blocks = jnp.where(live.reshape(live.shape + tail), new, old)
    blocks = jnp.moveaxis(blocks.reshape((B * nb, blk) + val.shape[2:]), 1,
                          -2)
    # a block the run leaves untouched, or one past the table, is not
    # stored back: it goes to the trash block
    touched = live.reshape(B, nb, blk).any(-1) & (lb < mb)
    phys = jnp.where(touched, phys, 0).reshape(-1)
    return pool.at[layer, phys].set(blocks, mode="promise_in_bounds")


def _paged_view(pool: Array, tbl: Array, layer, width: int) -> Array:
    """Gather each row's contiguous-layout KV view (B, max_blocks*blk,
    [H,] width) of layer ``layer`` straight from the stacked pools
    through its block table (one gather: no layer slice is
    materialized); ``width`` is the real minor dim (``_lanes``)."""
    g = jnp.moveaxis(pool[layer, tbl][..., :width], -2, 2)
    return g.reshape((tbl.shape[0], -1) + g.shape[3:])


def _sdpa_impl(cfg, q, k, v, **kw):
    if cfg.attn_impl == "blocked" and q.shape[1] > 1:
        kw.pop("logit_dtype", None)
        return L.sdpa_blocked(q, k, v, block=cfg.attn_block, **kw)
    if cfg.attn_impl == "pallas" and q.shape[1] > 1:
        from repro.kernels.flash_attention.ops import flash_attention
        kv_len = kw.pop("kv_len", None)
        lens = None
        if kv_len is not None:
            lens = jnp.full((q.shape[0],), kv_len, jnp.int32)
        return flash_attention(q, k, v, lens, causal=kw.get("causal", False),
                               sliding_window=kw.get("sliding_window", 0),
                               q_offset=kw.get("q_offset", 0))
    return L.sdpa(q, k, v, **kw)


def _self_attn(cfg, p, h, rope, mode, bcache, pos, bidir=False, tbl=None,
               layer=None, paged_fresh=False):
    """Self-attention in all three modes.  Returns (out, new_cache).

    ``tbl`` (B, max_blocks) switches the cache to the paged layout: KV
    leaves are the physical block pools of every layer of the group,
    ``layer`` says which is this block's, and reads/writes go through
    each row's block table.  ``paged_fresh`` marks a from-scratch paged
    prefill (no cached prefix): attention then runs on the local K/V
    exactly like the contiguous path — bit-identical first token — and
    only the WRITES go through the table.  A paged suffix prefill
    (``pos`` = per-row start offsets) instead attends over the gathered
    view so cached prefix blocks are genuinely reused, never recomputed.
    """
    x = L.rms_norm(h, p["norm1"], cfg.norm_eps)
    q, k, v = L.attn_qkv(p["attn"], cfg, x, x, rope, rope)
    causal = not bidir
    if mode == "full" or bcache is None:
        out = _sdpa_impl(cfg, q, k, v, causal=causal,
                         sliding_window=cfg.sliding_window)
        new_cache = None
        if mode == "prefill" and bcache is not None:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    bcache["k"], k.astype(bcache["k"].dtype), (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    bcache["v"], v.astype(bcache["v"].dtype), (0, 0, 0, 0)),
            }
        return h + L.attn_out(p["attn"], out), new_cache
    if mode == "prefill":
        if tbl is not None:
            kpool = _paged_write(bcache["k"], k, tbl, pos, layer)
            vpool = _paged_write(bcache["v"], v, tbl, pos, layer)
            if paged_fresh:
                out = _sdpa_impl(cfg, q, k, v, causal=causal,
                                 sliding_window=cfg.sliding_window)
            else:
                hd = cfg.head_dim
                out = L.sdpa(q, _paged_view(kpool, tbl, layer, hd),
                             _paged_view(vpool, tbl, layer, hd), causal=causal,
                             sliding_window=cfg.sliding_window, q_offset=pos)
            return h + L.attn_out(p["attn"], out), {"k": kpool, "v": vpool}
        out = _sdpa_impl(cfg, q, k, v, causal=causal,
                         sliding_window=cfg.sliding_window)
        new_cache = {
            "k": jax.lax.dynamic_update_slice(
                bcache["k"], k.astype(bcache["k"].dtype), (0, pos, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                bcache["v"], v.astype(bcache["v"].dtype), (0, pos, 0, 0)),
        }
        return h + L.attn_out(p["attn"], out), new_cache
    if tbl is not None:
        # paged decode (W=1) or speculative verify (W tokens per row at
        # positions pos..pos+W-1): the new KV is written, then position t
        # attends to ik <= pos+t — the set a decode step at kv_len=pos+t+1
        # sees, so every verify position is bitwise-identical to the
        # non-speculative decode path's.
        W = q.shape[1]
        kpool = _paged_write(bcache["k"], k, tbl, pos, layer)
        vpool = _paged_write(bcache["v"], v, tbl, pos, layer)
        if cfg.decode_impl == "flash_paged":
            from repro.kernels.flash_decode.ops import paged_flash_verify
            out = paged_flash_verify(q, kpool, vpool, tbl, pos + W, layer)
        else:
            hd = cfg.head_dim
            out = L.sdpa(q, _paged_view(kpool, tbl, layer, hd),
                         _paged_view(vpool, tbl, layer, hd), causal=True,
                         q_offset=pos, kv_len=pos + W, sliding_window=0)
        return h + L.attn_out(p["attn"], out), {"k": kpool, "v": vpool}
    # contiguous decode (pos: scalar, or (B,) per-row positions)
    if cfg.decode_impl == "shardmap" and jnp.ndim(pos) == 0:
        from repro.models import smdec
        res = smdec.gqa_decode_sm(cfg, q, k, v, bcache["k"], bcache["v"],
                                  pos)
        if res is not None:
            out, ck, cv = res
            return h + L.attn_out(p["attn"], out), {"k": ck, "v": cv}
    ck = _cache_write(bcache["k"], k, pos)
    cv = _cache_write(bcache["v"], v, pos)
    out = L.sdpa(q, ck, cv, causal=False, q_offset=pos, kv_len=pos + 1,
                 sliding_window=0)
    return h + L.attn_out(p["attn"], out), {"k": ck, "v": cv}


def _cross_attn(cfg, p, h, cross_ctx, mode, bcache):
    x = L.rms_norm(h, p["norm1"], cfg.norm_eps)
    if mode == "decode":
        k = bcache["ck"]
        v = bcache["cv"]
        B, Sq, _ = x.shape
        q = (x @ p["attn"]["wq"]).reshape(B, Sq, cfg.n_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = L.rms_norm(q, p["attn"]["q_norm"], cfg.norm_eps)
        out = L.sdpa(q, k, v, causal=False)
        return h + L.attn_out(p["attn"], out), bcache
    q, k, v = L.attn_qkv(p["attn"], cfg, x, cross_ctx, None, None)
    out = L.sdpa(q, k, v, causal=False)
    new_cache = None
    if mode == "prefill" and bcache is not None:
        new_cache = {"ck": k.astype(bcache["ck"].dtype),
                     "cv": v.astype(bcache["cv"].dtype)}
    return h + L.attn_out(p["attn"], out), new_cache


def _mla_attn(cfg, p, h, rope, mode, bcache, pos, tbl=None, layer=None,
              paged_fresh=False):
    x = L.rms_norm(h, p["norm1"], cfg.norm_eps)
    mp = p["mla"]
    q_nope, q_rope = L.mla_q(mp, cfg, x, rope)
    c_kv, k_rope = L.mla_kv_latent(mp, cfg, x, rope)
    if mode == "full" or bcache is None:
        out = _mla_naive(cfg, mp, q_nope, q_rope, c_kv, k_rope)
        return h + out, None
    if mode == "prefill":
        if tbl is not None:
            ckv_p = _paged_write(bcache["ckv"], c_kv, tbl, pos, layer)
            krope_p = _paged_write(bcache["krope"], k_rope, tbl, pos, layer)
            if paged_fresh:
                out = _mla_naive(cfg, mp, q_nope, q_rope, c_kv, k_rope)
            else:
                # EXPANDED form over the gathered view, not the absorbed
                # mla_attention: the absorbed path reassociates the latent
                # matmul ((q@wk_b)·ckv vs q·(ckv@wk_b)), and that last-ulp
                # logit difference flips greedy argmax on near-ties —
                # paged suffix tokens must equal the contiguous path's
                out = _mla_naive(
                    cfg, mp, q_nope, q_rope,
                    _paged_view(ckv_p, tbl, layer, cfg.kv_lora_rank),
                    _paged_view(krope_p, tbl, layer, cfg.rope_head_dim),
                    q_offset=pos)
            return h + out, {"ckv": ckv_p, "krope": krope_p}
        new_cache = {
            "ckv": jax.lax.dynamic_update_slice(
                bcache["ckv"], c_kv.astype(bcache["ckv"].dtype), (0, pos, 0)),
            "krope": jax.lax.dynamic_update_slice(
                bcache["krope"], k_rope.astype(bcache["krope"].dtype),
                (0, pos, 0)),
        }
        out = _mla_naive(cfg, mp, q_nope, q_rope, c_kv, k_rope)
        return h + out, new_cache
    # decode / verify: absorbed latent attention against the compressed
    # cache.  Paged: decode (W=1) or speculative verify (W positions per
    # row), both through the SAME absorbed program (NOT _mla_naive — its
    # reassociated latent matmul flips greedy argmax on near-ties), so
    # verify position t is bitwise-identical to a decode step at
    # kv_len = pos + t + 1.
    if tbl is not None:
        W = q_nope.shape[1]
        ckv_p = _paged_write(bcache["ckv"], c_kv, tbl, pos, layer)
        krope_p = _paged_write(bcache["krope"], k_rope, tbl, pos, layer)
        if cfg.decode_impl == "flash_paged":
            from repro.kernels.flash_decode.ops import paged_flash_verify_mla
            B, Sq, H, _ = q_nope.shape
            q_lat = jnp.einsum("bqhn,hrn->bqhr", q_nope, mp["wk_b"])
            ctx = paged_flash_verify_mla(
                q_lat, q_rope, ckv_p, krope_p, tbl, pos + W, layer,
                scale=1.0 / math.sqrt(cfg.nope_head_dim + cfg.rope_head_dim))
            out = jnp.einsum("bqhr,hrv->bqhv", ctx, mp["wv_b"])
            out = out.reshape(B, Sq, H * cfg.v_head_dim) @ mp["wo"]
        else:
            out = L.mla_attention(
                mp, cfg, q_nope, q_rope,
                _paged_view(ckv_p, tbl, layer, cfg.kv_lora_rank),
                _paged_view(krope_p, tbl, layer, cfg.rope_head_dim),
                causal=True, q_offset=pos, kv_len=pos + W)
        return h + out, {"ckv": ckv_p, "krope": krope_p}
    # contiguous decode (pos: scalar, or (B,) per-row positions)
    if cfg.decode_impl == "shardmap" and jnp.ndim(pos) == 0:
        from repro.models import smdec
        B, Sq, H, _ = q_nope.shape
        q_lat = jnp.einsum("bqhn,hrn->bqhr", q_nope, mp["wk_b"])
        res = smdec.mla_decode_sm(cfg, q_lat, q_rope, c_kv, k_rope,
                                  bcache["ckv"], bcache["krope"], pos)
        if res is not None:
            ctx, ckv, krope = res
            out = jnp.einsum("bqhr,hrv->bqhv", ctx, mp["wv_b"])
            out = out.reshape(B, Sq, H * cfg.v_head_dim) @ mp["wo"]
            return h + out, {"ckv": ckv, "krope": krope}
    ckv = _cache_write(bcache["ckv"], c_kv, pos)
    krope = _cache_write(bcache["krope"], k_rope, pos)
    out = L.mla_attention(mp, cfg, q_nope, q_rope, ckv, krope,
                          causal=False, q_offset=pos, kv_len=pos + 1)
    return h + out, {"ckv": ckv, "krope": krope}


def _mla_naive(cfg, mp, q_nope, q_rope, c_kv, k_rope, q_offset=None):
    """Prefill/train MLA: expand latents to per-head K/V, standard SDPA
    (compute-optimal when S is large; decode uses the absorbed path).
    ``q_offset`` ((B,) per-row start positions) is the paged-suffix case:
    K/V come from the gathered block view, queries sit at an offset."""
    B, Sq, H, _ = q_nope.shape
    k_nope = jnp.einsum("bsr,hrn->bshn", c_kv, mp["wk_b"])
    v = jnp.einsum("bsr,hrv->bshv", c_kv, mp["wv_b"])
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                (B, k_rope.shape[1], H, cfg.rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h.astype(k_nope.dtype)], axis=-1)
    if q_offset is not None:
        out = L.sdpa(q, k, v, causal=True, q_offset=q_offset)
    else:
        out = _sdpa_impl(cfg, q, k, v, causal=True)
    return out.reshape(B, Sq, H * cfg.v_head_dim) @ mp["wo"]


def _apply_block(cfg: ModelConfig, spec: BlockSpec, p: dict, h: Array, *,
                 rope, cross_ctx, mode: str, bcache, pos, moe_impl: str,
                 tbl=None, layer=None, paged_fresh=False):
    new_cache, aux = bcache, (jnp.zeros((), jnp.float32),) * 2

    if spec.mixer == "attn":
        h, new_cache = _self_attn(cfg, p, h, rope, mode, bcache, pos,
                                  tbl=tbl, layer=layer,
                                  paged_fresh=paged_fresh)
    elif spec.mixer == "bidir_attn":
        h, new_cache = _self_attn(cfg, p, h, rope, mode, bcache, pos,
                                  bidir=True)
    elif spec.mixer == "cross_attn":
        h, new_cache = _cross_attn(cfg, p, h, cross_ctx, mode, bcache)
    elif spec.mixer == "mla":
        h, new_cache = _mla_attn(cfg, p, h, rope, mode, bcache, pos,
                                 tbl=tbl, layer=layer,
                                 paged_fresh=paged_fresh)
    elif spec.mixer in ("mamba", "mlstm", "slstm"):
        x = L.rms_norm(h, p["norm1"], cfg.norm_eps)
        fwd = {"mamba": (S.mamba_forward, S.mamba_step),
               "mlstm": (S.mlstm_forward, S.mlstm_step),
               "slstm": (S.slstm_forward, S.slstm_step)}[spec.mixer]
        key = spec.mixer
        if mode == "decode":
            y, new_cache = fwd[1](p[key], cfg, x, bcache)
        else:
            y, new_cache = fwd[0](p[key], cfg, x, state=None,
                                  return_state=(mode == "prefill"))
            if mode == "prefill" and new_cache is None:
                new_cache = bcache
        h = h + y
    h = constrain(h, "act.res")

    if spec.ffn == "dense":
        x = L.rms_norm(h, p["norm2"], cfg.norm_eps)
        h = h + L.ffn_apply(p["ffn"], x)
    elif spec.ffn == "moe":
        x = L.rms_norm(h, p["norm2"], cfg.norm_eps)
        y, moe_aux = M.moe_apply(p["moe"], cfg, x, moe_impl)
        h = h + y
        aux = (moe_aux["moe_lb"], moe_aux["moe_z"])
    h = constrain(h, "act.res")
    return h, new_cache, aux


# ===========================================================================
# stack
# ===========================================================================

def _run_groups(cfg: ModelConfig, params: dict, h: Array, groups, prefix, *,
                rope, cross_ctx, mode, cache, pos, moe_impl, remat,
                bidir_override=False, tbl=None, paged_fresh=False):
    lb_total = jnp.zeros((), jnp.float32)
    z_total = jnp.zeros((), jnp.float32)
    new_cache = {}

    for i, g in enumerate(groups):
        gp = params[f"{prefix}{i}"]
        gc = cache.get(f"g{i}") if cache is not None else None
        # paged block pools ride in the carry, indexed by the scanned
        # layer number, so each layer updates them in place; as scan
        # xs/ys every step would restack a copy of every layer's pool
        paged = gc is not None and tbl is not None

        def body(carry, xs, _g=g, _paged=paged):
            h, lb, z, pools = carry
            bp, per_layer = xs
            bc, layer = (pools, per_layer) if _paged else (per_layer, None)
            out_cache = {}
            for j, spec in enumerate(_g.period):
                bcj = bc[f"b{j}"] if bc is not None else None
                h, ncj, (alb, az) = _apply_block(
                    cfg, spec, bp[f"b{j}"], h, rope=rope, cross_ctx=cross_ctx,
                    mode=mode, bcache=bcj, pos=pos, moe_impl=moe_impl,
                    tbl=tbl, layer=layer, paged_fresh=paged_fresh)
                lb, z = lb + alb, z + az
                out_cache[f"b{j}"] = ncj
            if _paged:
                return (h, lb, z, out_cache), None
            return (h, lb, z, None), out_cache

        if remat and mode == "full":
            body = jax.checkpoint(body)

        if paged:
            xs = (gp, jnp.arange(g.repeats, dtype=jnp.int32))
            (h, lb_total, z_total, new_cache[f"g{i}"]), _ = jax.lax.scan(
                body, (h, lb_total, z_total, gc), xs)
        else:
            (h, lb_total, z_total, _), ys = jax.lax.scan(
                body, (h, lb_total, z_total, None), (gp, gc))
            if gc is not None:
                new_cache[f"g{i}"] = ys
    return h, new_cache, {"moe_lb": lb_total, "moe_z": z_total}


def _encode(cfg: ModelConfig, params: dict, frames: Array, moe_impl: str,
            remat: bool) -> Array:
    """Run the encoder stack over stub frame embeddings (whisper)."""
    Sf = frames.shape[1]
    rope = L.rope_tables(jnp.arange(Sf), cfg.head_dim, cfg.rope_theta)
    h, _, _ = _run_groups(cfg, params, frames, cfg.encoder_groups, "enc_g",
                          rope=rope, cross_ctx=None, mode="full", cache=None,
                          pos=0, moe_impl=moe_impl, remat=remat)
    return L.rms_norm(h, params["enc_final_norm"], cfg.norm_eps)


def _prepare_cross(cfg: ModelConfig, params: dict, cross_ctx, moe_impl, remat):
    if cross_ctx is None:
        return None
    if cfg.is_encoder_decoder:
        return _encode(cfg, params, cross_ctx, moe_impl, remat)
    return cross_ctx  # vision: pre-embedded patches (stub frontend)


def _logits(cfg: ModelConfig, params: dict, h: Array) -> Array:
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


# ===========================================================================
# public API
# ===========================================================================

def forward(cfg: ModelConfig, params: dict, tokens: Array,
            cross_ctx: Optional[Array] = None, *, moe_impl: str = "gshard",
            remat: bool = False) -> Tuple[Array, dict]:
    """Training forward: tokens (B,S) -> (logits (B,S,V), aux)."""
    h = params["embed"][tokens]
    h = constrain(h, "act.res")
    rope_dim = cfg.rope_head_dim if cfg.is_mla else cfg.head_dim
    rope = L.rope_tables(jnp.arange(tokens.shape[1]), rope_dim, cfg.rope_theta)
    cross = _prepare_cross(cfg, params, cross_ctx, moe_impl, remat)
    h, _, aux = _run_groups(cfg, params, h, cfg.groups, "g", rope=rope,
                            cross_ctx=cross, mode="full", cache=None, pos=0,
                            moe_impl=moe_impl, remat=remat)
    return _logits(cfg, params, h), aux


def prefill(cfg: ModelConfig, params: dict, tokens: Array, cache: dict,
            cross_ctx: Optional[Array] = None, *, moe_impl: str = "gshard",
            lens: Optional[Array] = None, start: Optional[Array] = None,
            tbl: Optional[Array] = None,
            paged_fresh: bool = False) -> Tuple[Array, dict]:
    """Prefill from position 0: returns (last-token logits (B,V), cache).

    ``lens`` (B,) gives each row's real prompt length when rows are
    right-padded to a common width: logits are gathered at ``lens - 1``
    (each row's last REAL token) instead of the padded final position, so
    a short row's next token is never conditioned on pad embeddings.

    Paged mode (``tbl`` (B, max_blocks) given): ``cache`` is the shared
    block-pool pytree and writes scatter through each row's block table.
    ``start`` (B,) is the absolute position of ``tokens[:, 0]`` — for a
    prefix-cache hit only the unmatched SUFFIX is passed in, rope phases
    are offset by ``start`` and attention reads the cached prefix blocks
    through the table (``paged_fresh=True`` marks a no-prefix prefill,
    which keeps the contiguous-identical local attention path)."""
    h = params["embed"][tokens]
    h = constrain(h, "act.res")
    Sq = tokens.shape[1]
    rope_dim = cfg.rope_head_dim if cfg.is_mla else cfg.head_dim
    if tbl is not None:
        pos = (jnp.zeros((tokens.shape[0],), jnp.int32) if start is None
               else jnp.asarray(start, jnp.int32))
        positions = pos[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None, :]
        rope = L.rope_tables(positions, rope_dim, cfg.rope_theta)
        h, new_cache, _ = _run_groups(
            cfg, params, h, cfg.groups, "g", rope=rope, cross_ctx=None,
            mode="prefill", cache=cache, pos=pos, moe_impl=moe_impl,
            remat=False, tbl=tbl, paged_fresh=paged_fresh)
        # pos/tbl are scheduler-owned in paged mode: carry them through
        new_cache["pos"] = cache["pos"]
        new_cache["tbl"] = cache["tbl"]
        if lens is None:
            h_last = h[:, -1:, :]
        else:
            idx = jnp.asarray(lens, jnp.int32) - 1
            h_last = jnp.take_along_axis(
                h, jnp.broadcast_to(idx[:, None, None],
                                    (h.shape[0], 1, h.shape[2])), axis=1)
        return _logits(cfg, params, h_last)[:, 0, :], new_cache
    rope = L.rope_tables(jnp.arange(Sq), rope_dim, cfg.rope_theta)
    cross = _prepare_cross(cfg, params, cross_ctx, moe_impl, False)
    h, new_cache, _ = _run_groups(cfg, params, h, cfg.groups, "g", rope=rope,
                                  cross_ctx=cross, mode="prefill", cache=cache,
                                  pos=0, moe_impl=moe_impl, remat=False)
    new_cache["pos"] = jnp.asarray(Sq, jnp.int32)
    if lens is None:
        h_last = h[:, -1:, :]
    else:
        idx = jnp.asarray(lens, jnp.int32) - 1                   # (B,)
        h_last = jnp.take_along_axis(
            h, jnp.broadcast_to(idx[:, None, None],
                                (h.shape[0], 1, h.shape[2])), axis=1)
    logits = _logits(cfg, params, h_last)[:, 0, :]
    return logits, new_cache


def decode_step(cfg: ModelConfig, params: dict, tokens: Array, cache: dict,
                *, moe_impl: str = "gshard") -> Tuple[Array, dict]:
    """One decode step: tokens (B,1) + cache -> (logits (B,V), cache).

    ``cache["pos"]`` is either a scalar (every row at the same depth — the
    legacy uniform path) or a (B,) vector of per-row positions, in which
    case each row's KV write, rope phase, and attention mask use that
    row's own depth (continuous batching: rows prefilled at different
    times decode side by side)."""
    pos = cache["pos"]
    tbl = cache.get("tbl")          # present iff the cache is paged
    h = params["embed"][tokens]
    rope_dim = cfg.rope_head_dim if cfg.is_mla else cfg.head_dim
    rope_pos = pos[None] if jnp.ndim(pos) == 0 else pos[:, None]  # (B,1)
    rope = L.rope_tables(rope_pos, rope_dim, cfg.rope_theta)
    h, new_cache, _ = _run_groups(cfg, params, h, cfg.groups, "g", rope=rope,
                                  cross_ctx=None, mode="decode", cache=cache,
                                  pos=pos, moe_impl=moe_impl, remat=False,
                                  tbl=tbl)
    new_cache["pos"] = pos + 1
    if tbl is not None:
        new_cache["tbl"] = tbl
    logits = _logits(cfg, params, h)[:, 0, :]
    return logits, new_cache


def verify(cfg: ModelConfig, params: dict, tokens: Array, cache: dict,
           *, moe_impl: str = "gshard") -> Tuple[Array, dict]:
    """Speculative-decoding verify: tokens (B,W) + paged cache ->
    (logits (B,W,V), cache).

    Feeds W tokens per row (the pending token followed by W-1 draft
    proposals) at positions ``pos..pos+W-1``, writing all W KV entries
    through the block table and returning logits at EVERY position.
    Attention at position t masks to ``kv <= pos+t`` — the same set a
    plain decode step sees at depth pos+t — and runs the same decode
    program (absorbed MLA, 0 sliding window), so row t's logits are
    bitwise-identical to the non-speculative path's.  KV written past
    the accepted prefix is simply stale: it sits beyond the new ``pos``
    and is overwritten before it can ever be attended to, so rollback
    costs nothing.  ``pos``/``tbl`` are scheduler-owned and carried
    through unchanged."""
    pos = cache["pos"]
    tbl = cache["tbl"]
    W = tokens.shape[1]
    h = params["embed"][tokens]
    rope_dim = cfg.rope_head_dim if cfg.is_mla else cfg.head_dim
    positions = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    rope = L.rope_tables(positions, rope_dim, cfg.rope_theta)
    h, new_cache, _ = _run_groups(cfg, params, h, cfg.groups, "g", rope=rope,
                                  cross_ctx=None, mode="verify", cache=cache,
                                  pos=pos, moe_impl=moe_impl, remat=False,
                                  tbl=tbl)
    new_cache["pos"] = pos
    new_cache["tbl"] = tbl
    return _logits(cfg, params, h), new_cache


def loss_fn(cfg: ModelConfig, params: dict, tokens: Array, labels: Array,
            cross_ctx: Optional[Array] = None, *, moe_impl: str = "gshard",
            remat: bool = True, lb_coef: float = 0.01, z_coef: float = 1e-3):
    """Next-token cross entropy (+ MoE aux losses).  labels: (B,S) int32,
    -100 entries are masked."""
    logits, aux = forward(cfg, params, tokens, cross_ctx, moe_impl=moe_impl,
                          remat=remat)
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    n_moe = max(1, sum(1 for g in cfg.groups for s in g.period
                       if s.ffn == "moe") )
    total = ce + lb_coef * aux["moe_lb"] / n_moe + z_coef * aux["moe_z"] / n_moe
    metrics = {"ce": ce, "moe_lb": aux["moe_lb"] / n_moe,
               "moe_z": aux["moe_z"] / n_moe}
    return total, metrics
