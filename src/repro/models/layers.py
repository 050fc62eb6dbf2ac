"""Layer primitives shared by every fleet architecture.

All functions are pure; parameters are plain dicts of jnp arrays.  Matmul
compute runs in the config dtype (bf16 by default); softmax, norms and
recurrent states run in fp32.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

Array = jax.Array


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None) -> Array:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_tables(positions: Array, dim: int, theta: float) -> Tuple[Array, Array]:
    """positions: int32 (...,) -> cos/sin tables (..., dim//2) in fp32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (B, S, H, hd); cos/sin: (S, hd//2) or (B, S, hd//2)."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    if cos.ndim == 2:  # (S, hd//2) -> broadcast over batch, heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:              # (B, S, hd//2)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention core
# ---------------------------------------------------------------------------

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def sdpa_blocked(q: Array, k: Array, v: Array, *, causal: bool,
                 sliding_window: int = 0, q_offset=0,
                 kv_len: Optional[Array] = None, block: int = 512) -> Array:
    """Flash-form attention in XLA ops: lax.scan over KV blocks with online
    softmax.  Never materializes (Sq, Skv) probabilities — live memory is
    O(Sq * block) — at identical matmul FLOPs to the einsum path.  This is
    the XLA-analyzable counterpart of kernels/flash_attention (which is the
    real-TPU hot path)."""
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if Skv % block != 0:
        return sdpa(q, k, v, causal=causal, sliding_window=sliding_window,
                    q_offset=q_offset, kv_len=kv_len)
    nb = Skv // block
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, Hkv, G, hd)
    kb = k.reshape(B, nb, block, Hkv, k.shape[-1]).swapaxes(0, 1)
    vb = v.reshape(B, nb, block, Hkv, v.shape[-1]).swapaxes(0, 1)
    iq = jnp.arange(Sq) + q_offset                       # (Sq,)

    def body(carry, xs):
        m, l, acc = carry                                # (B,Hkv,G,Sq) ...
        kc, vc, bi = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        ik = bi * block + jnp.arange(block)              # (block,)
        mask = jnp.ones((Sq, block), bool)
        if causal:
            mask &= ik[None, :] <= iq[:, None]
        if sliding_window > 0:
            mask &= ik[None, :] > iq[:, None] - sliding_window
        if kv_len is not None:
            mask &= ik[None, :] < kv_len
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    hv = v.shape[-1]
    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, hv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, hv) \
        .astype(q.dtype)


def _attn_mask(B: int, Sq: int, Skv: int, *, causal: bool,
               sliding_window: int = 0, q_offset=0,
               kv_len: Optional[Array] = None):
    """Attention mask shared by ``sdpa`` and ``mla_attention``.

    ``q_offset`` / ``kv_len`` may be scalars (uniform across the batch) or
    (B,) vectors (per-row positions, continuous-batching decode).  Returns
    ``(mask, per_row)``: ``mask`` is (Sq, Skv) when ``per_row`` is False
    and (B, Sq, Skv) when True — the caller inserts its own head axes
    (``mask[:, None, ...]`` vs ``mask[None, ...]``) before masking logits.
    """
    per_row = jnp.ndim(q_offset) > 0 or (
        kv_len is not None and jnp.ndim(kv_len) > 0)
    if per_row:
        off = jnp.broadcast_to(jnp.asarray(q_offset), (B,))
        iq = off[:, None, None] + jnp.arange(Sq)[None, :, None]  # (B,Sq,1)
        ik = jnp.arange(Skv)[None, None, :]                      # (1,1,Skv)
        mask = jnp.ones((B, Sq, Skv), dtype=bool)
    else:
        iq = jnp.arange(Sq)[:, None] + q_offset          # (Sq, 1) absolute
        ik = jnp.arange(Skv)[None, :]                    # (1, Skv)
        mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= ik <= iq
    if sliding_window > 0:
        mask &= ik > iq - sliding_window
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        if per_row:
            kl = jnp.broadcast_to(kl, (B,))[:, None, None]
        mask &= ik < kl
    return mask, per_row


def sdpa(q: Array, k: Array, v: Array, *, causal: bool,
         sliding_window: int = 0, q_offset=0, kv_len: Optional[Array] = None,
         logit_dtype=jnp.float32) -> Array:
    """Grouped-query attention.

    q: (B, Sq, Hq, hd); k/v: (B, Skv, Hkv, hd); Hq = G * Hkv.
    ``q_offset``: absolute position of q[0] for causal masking against a
    cache — an int, a traced scalar, or a (B,) vector of per-row positions.
    ``kv_len``: valid KV prefix length (decode), scalar or (B,).
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, Sq, Hkv, G, hd)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=logit_dtype) * scale

    mask, per_row = _attn_mask(B, Sq, Skv, causal=causal,
                               sliding_window=sliding_window,
                               q_offset=q_offset, kv_len=kv_len)
    mask = mask[:, None, None] if per_row else mask[None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, Hq, v.shape[-1])


# ---------------------------------------------------------------------------
# GQA attention block (self / cross / bidirectional)
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, dtype) -> dict:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype),
        "wk": dense_init(ks[1], (d, Hkv * hd), dtype),
        "wv": dense_init(ks[2], (d, Hkv * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attn_qkv(p: dict, cfg: ModelConfig, x: Array, kv_x: Array,
             rope: Optional[Tuple[Array, Array]],
             kv_rope: Optional[Tuple[Array, Array]]):
    B, Sq, d = x.shape
    Skv = kv_x.shape[1]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, Sq, H, hd)
    k = (kv_x @ p["wk"]).reshape(B, Skv, Hkv, hd)
    v = (kv_x @ p["wv"]).reshape(B, Skv, Hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope is not None:
        q = apply_rope(q, *rope)
    if kv_rope is not None:
        k = apply_rope(k, *kv_rope)
    return q, k, v


def attn_out(p: dict, out: Array) -> Array:
    B, S, H, hd = out.shape
    return out.reshape(B, S, H * hd) @ p["wo"]


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig, dtype) -> dict:
    d, H = cfg.d_model, cfg.n_heads
    nh, rh, vh = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], (d, cfg.q_lora_rank), dtype)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), dtype)
        p["wq_b"] = dense_init(ks[1], (cfg.q_lora_rank, H * (nh + rh)), dtype)
    else:
        p["wq"] = dense_init(ks[0], (d, H * (nh + rh)), dtype)
    p["wkv_a"] = dense_init(ks[2], (d, cfg.kv_lora_rank + rh), dtype)
    p["kv_norm"] = jnp.ones((cfg.kv_lora_rank,), dtype)
    # up-projections, stored head-major for the absorbed decode path
    p["wk_b"] = dense_init(ks[3], (H, cfg.kv_lora_rank, nh), dtype)
    p["wv_b"] = dense_init(ks[4], (H, cfg.kv_lora_rank, vh), dtype)
    p["wo"] = dense_init(ks[5], (H * vh, d), dtype)
    return p


def mla_q(p: dict, cfg: ModelConfig, x: Array, rope):
    B, S, _ = x.shape
    H, nh, rh = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        q = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, S, H, nh + rh)
    q_nope, q_rope = q[..., :nh], q[..., nh:]
    q_rope = apply_rope(q_rope, *rope)
    return q_nope, q_rope


def mla_kv_latent(p: dict, cfg: ModelConfig, x: Array, rope):
    """Compressed KV: returns (c_kv (B,S,r), k_rope (B,S,rh)) — the cache."""
    kv = x @ p["wkv_a"]
    c_kv = rms_norm(kv[..., : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kv[..., cfg.kv_lora_rank:]
    k_rope = apply_rope(k_rope[:, :, None, :], *rope)[:, :, 0, :]
    return c_kv, k_rope


def mla_attention(p: dict, cfg: ModelConfig, q_nope, q_rope, c_kv, k_rope,
                  *, causal: bool, q_offset=0, kv_len=None) -> Array:
    """Absorbed-latent attention (used for both full-seq and decode).

    q_nope: (B,Sq,H,nh); q_rope: (B,Sq,H,rh); c_kv: (B,Skv,r); k_rope: (B,Skv,rh)
    score[h] = (q_nope[h] @ Wk_b[h]) . c_kv  +  q_rope . k_rope
    out[h]   = (attn @ c_kv) @ Wv_b[h]
    """
    B, Sq, H, _ = q_nope.shape
    scale = 1.0 / math.sqrt(cfg.nope_head_dim + cfg.rope_head_dim)

    q_lat = jnp.einsum("bqhn,hrn->bqhr", q_nope, p["wk_b"])      # (B,Sq,H,r)
    ctx = mla_latent_context(q_lat, q_rope, c_kv, k_rope, scale=scale,
                             causal=causal, q_offset=q_offset, kv_len=kv_len)
    out = jnp.einsum("bqhr,hrv->bqhv", ctx, p["wv_b"])            # (B,Sq,H,vh)
    return out.reshape(B, Sq, H * cfg.v_head_dim) @ p["wo"]


def mla_latent_context(q_lat, q_rope, c_kv, k_rope, *, scale: float,
                       causal: bool, q_offset=0, kv_len=None) -> Array:
    """The latent context ``softmax(scores) @ c_kv``, (B,Sq,H,r), of
    absorbed-latent attention — what the paged MLA kernel returns."""
    B, Sq = q_lat.shape[:2]
    Skv = c_kv.shape[1]
    s_lat = jnp.einsum("bqhr,bkr->bhqk", q_lat, c_kv,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope,
                        preferred_element_type=jnp.float32)
    logits = (s_lat + s_rope) * scale

    mask, per_row = _attn_mask(B, Sq, Skv, causal=causal,
                               q_offset=q_offset, kv_len=kv_len)
    mask = mask[:, None] if per_row else mask[None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(c_kv.dtype)
    return jnp.einsum("bhqk,bkr->bqhr", probs, c_kv)


# ---------------------------------------------------------------------------
# dense SwiGLU FFN
# ---------------------------------------------------------------------------

def ffn_init(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None) -> dict:
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (cfg.d_model, d_ff), dtype),
        "w_up": dense_init(ks[1], (cfg.d_model, d_ff), dtype),
        "w_down": dense_init(ks[2], (d_ff, cfg.d_model), dtype),
    }


def ffn_apply(p: dict, x: Array) -> Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
