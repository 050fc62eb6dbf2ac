"""Plain float32 reference of a dense grouped-query-attention decoder.

Pre-norm RMSNorm blocks: attention with rotary phases on each head (the
rotate-half convention, over the whole head), optional RMSNorm on q and k
per head, causal softmax over all earlier positions, then a SwiGLU MLP.
The output head is the embedding (tied) or its own matrix.  This is
Llama / SmolLM and Qwen3 as published; for GLM-4 it is the model as the
fleet runs it (no qkv bias, rotary over the whole head), which the
configuration file lists among its changed keys.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no cache, no
kernels, no batching of rows.  It runs one sequence at a time, padded to a
fixed length (causal attention makes the padding invisible to the real
positions), and layer by layer, making each layer's weights again from the
seed, so that it fits beside nothing else.  The weights' layout is the
benchmark's own, from this architecture's family (``families/dense_gqa.py``).

``mode="int8"`` is the control: the same computation with int8 where the
configuration serves bfloat16.  Every matmul weight is rounded to int8 per
output channel, and every tensor the served program keeps in bfloat16 (a
matmul's input and output, q, k and v, the attention probabilities, the
residual stream after each add) is rounded to int8 per row.
"""

from __future__ import annotations

import math
import sys
from pathlib import Path
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
from harness import spec  # noqa: E402
from harness import weights as W  # noqa: E402

FAM = spec.load_family("dense_gqa", BENCH)

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    """Symmetric int8 rounding of x along ``axis`` (returned as float)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


class Reference:
    def __init__(self, m: dict, key, *, max_seq: int, mode: str = "f32"):
        if mode not in ("f32", "int8"):
            raise ValueError(f"unknown reference mode {mode!r}")
        self.m, self.key, self.S, self.mode = m, key, max_seq, mode
        self.dt = jnp.dtype(m["dtype"])     # the served weights' type
        d, H = m["hidden_size"], m["num_attention_heads"]
        self.hd = m.get("head_dim") or d // H
        self.eps = m["rms_norm_eps"]
        self.nchunks = FAM.vocab_chunks(m)
        self._layer_w = jax.jit(self._layer_weights)
        self._layer = jax.jit(self._layer_fn)
        self._embed = jax.jit(self._embed_chunk)
        self._head = jax.jit(self._head_chunk)

    # -- pieces --------------------------------------------------------------

    def lo(self, x):
        """A tensor as the served program stores it, one precision down."""
        return _q8(x, -1) if self.mode == "int8" else x

    def mm(self, x, w):
        if self.mode == "int8":
            x, w = _q8(x, -1), _q8(w, 0)
        return self.lo(jnp.matmul(x, w, precision=HI))

    def rms(self, x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.eps) * g

    def rope(self, x, cos, sin):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _layer_weights(self, layer):
        w = FAM.layer_weights(self.m, self.key, layer, self.dt)
        return jax.tree.map(lambda a: a.astype(jnp.float32), w)

    def _rows(self, leaf, chunk):
        return FAM.vocab_chunk(self.m, self.key, leaf, chunk,
                               self.dt).astype(jnp.float32)

    def _embed_chunk(self, chunk, ids, h):
        lo = chunk * W.CHUNK_BLOCKS * W.VOCAB_BLOCK
        rows = self._rows("embed", chunk)
        local = ids - lo
        inside = (local >= 0) & (local < rows.shape[0])
        got = rows[jnp.clip(local, 0, rows.shape[0] - 1)]
        return jnp.where(inside[:, None], got, h)

    def _head_chunk(self, chunk, x):
        leaf = "embed" if self.m.get("tie_word_embeddings") else "lm_head"
        return self.mm(x, self._rows(leaf, chunk).T)

    def _layer_fn(self, w, h):
        m, hd = self.m, self.hd
        S = h.shape[0]
        H, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
        inv = 1.0 / (m["rope_theta"] ** (jnp.arange(0, hd, 2,
                                                      dtype=jnp.float32) / hd))
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x = self.lo(self.rms(h, w["attn_norm"]))
        q = self.mm(x, w["wq"]).reshape(S, H, hd)
        k = self.mm(x, w["wk"]).reshape(S, Hkv, hd)
        v = self.mm(x, w["wv"]).reshape(S, Hkv, hd)
        if m.get("qk_norm"):
            q, k = self.rms(q, w["q_norm"]), self.rms(k, w["k_norm"])
        q, k = self.lo(self.rope(q, cos, sin)), self.lo(self.rope(k, cos, sin))
        G = H // Hkv
        qg = q.reshape(S, Hkv, G, hd)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) / math.sqrt(hd)
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        p = self.lo(jax.nn.softmax(s, axis=-1))
        o = self.lo(jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI))
        h = self.lo(h + self.mm(o.reshape(S, H * hd), w["wo"]))
        x = self.lo(self.rms(h, w["mlp_norm"]))
        g = self.mm(x, w["w_gate"])
        u = self.mm(x, w["w_up"])
        return self.lo(h + self.mm(self.lo(jax.nn.silu(g) * u), w["w_down"]))

    # -- public ---------------------------------------------------------------

    def logits_at(self, seqs: Sequence[np.ndarray],
                  positions: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Logits (len(pos), V) float32 at ``positions`` of each token
        sequence (each at most ``max_seq`` long)."""
        m, S = self.m, self.S
        hs = []
        for toks in seqs:
            if len(toks) > S:
                raise ValueError(f"sequence of {len(toks)} > {S}")
            ids = np.zeros(S, np.int32)
            ids[:len(toks)] = toks
            ids = jnp.asarray(ids)
            h = jnp.zeros((S, m["hidden_size"]), jnp.float32)
            for c in range(self.nchunks):
                h = self._embed(c, ids, h)
            hs.append(self.lo(h))
        for layer in range(m["num_hidden_layers"]):
            w = self._layer_w(layer)
            hs = [self._layer(w, h) for h in hs]
        g = FAM.final_norm(m, self.key, self.dt).astype(jnp.float32)
        x = jnp.concatenate([self.lo(self.rms(h[jnp.asarray(p)], g))
                             for h, p in zip(hs, positions)])
        logits = jnp.concatenate([self._head(c, x)
                                  for c in range(self.nchunks)], axis=1)
        logits = np.asarray(logits[:, :m["vocab_size"]])
        out, i = [], 0
        for p in positions:
            out.append(logits[i:i + len(p)])
            i += len(p)
        return out
