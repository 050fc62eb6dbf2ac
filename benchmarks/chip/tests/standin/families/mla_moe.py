"""A stand-in family for CPU tests: DeepSeek-V2's block as the program
runs it, multi-head latent attention over routed and shared experts,
behind dense first layers.

It shows that an architecture comes to the harness as files alone: this
module beside a configuration and a traffic mix is all that a cell of a
new architecture needs to be built, weighted, checked, served and
counted (``test_chipbench_family.py``).  No reference is here, so no run
judges it; a configuration that is measured brings
``references/<name>.py`` with its family.

``m`` is a model entry in DeepSeek-V2's Hugging Face key names.
"""

from __future__ import annotations

import math
from typing import Iterable

import jax
import jax.numpy as jnp

from harness import spec
from harness import weights as W

MODEL_FIELDS = (("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
                ("d_ff", "intermediate_size"),
                ("d_ff_expert", "moe_intermediate_size"),
                ("n_experts", "n_routed_experts"),
                ("n_shared_experts", "n_shared_experts"),
                ("moe_top_k", "num_experts_per_tok"),
                ("q_lora_rank", "q_lora_rank"),
                ("kv_lora_rank", "kv_lora_rank"),
                ("nope_head_dim", "qk_nope_head_dim"),
                ("rope_head_dim", "qk_rope_head_dim"),
                ("v_head_dim", "v_head_dim"), ("vocab_size", "vocab_size"),
                ("rope_theta", "rope_theta"), ("norm_eps", "rms_norm_eps"),
                ("tie_embeddings", "tie_word_embeddings"), ("dtype", "dtype"))

LAYER_LEAVES = ("norm1", "mla.wq_a", "mla.q_norm", "mla.wq_b", "mla.wkv_a",
                "mla.kv_norm", "mla.wk_b", "mla.wv_b", "mla.wo", "norm2",
                "ffn.w_gate", "ffn.w_up", "ffn.w_down", "moe.router",
                "moe.w_gate", "moe.w_up", "moe.w_down", "moe.shared.w_gate",
                "moe.shared.w_up", "moe.shared.w_down")
TOP_LEAVES = ("embed", "final_norm", "lm_head")


def blocks(m: dict):
    """(ffn kind, layers) of each run of like layers, first to last."""
    k = m["first_k_dense_replace"]
    return tuple((f, n) for f, n in (("dense", k),
                                     ("moe", m["num_hidden_layers"] - k))
                 if n)


def check(arch_cfg, m: dict, arch: str):
    bad = []
    runs = [(tuple((s.mixer, s.ffn) for s in g.period), g.repeats)
            for g in arch_cfg.groups]
    if runs != [((("mla", f),), n) for f, n in blocks(m)]:
        bad.append(f"layers {runs} are not {blocks(m)} of latent attention")
    bad += spec.differing(arch_cfg, m, MODEL_FIELDS)
    if bad:
        raise spec.ConfigMismatch(f"{arch}: the program does not run the "
                                  "configured model: " + "; ".join(bad))


def block_shapes(m: dict, ffn: str) -> dict:
    d, H = m["hidden_size"], m["num_attention_heads"]
    nh, rh, vh = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    out = {"norm1": (d,), "norm2": (d,),
           "mla": {"wq_a": (d, qr), "q_norm": (qr,), "wq_b": (qr, H * (nh + rh)),
                   "wkv_a": (d, kvr + rh), "kv_norm": (kvr,),
                   "wk_b": (H, kvr, nh), "wv_b": (H, kvr, vh),
                   "wo": (H * vh, d)}}
    if ffn == "dense":
        F = m["intermediate_size"]
        out["ffn"] = {"w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)}
    else:
        E, f = m["n_routed_experts"], m["moe_intermediate_size"]
        fs = m["n_shared_experts"] * f
        out["moe"] = {"router": (d, E), "w_gate": (E, d, f), "w_up": (E, d, f),
                      "w_down": (E, f, d),
                      "shared": {"w_gate": (d, fs), "w_up": (d, fs),
                                 "w_down": (fs, d)}}
    return out


def _leaf(key, name: str, shape, dtype):
    if len(shape) == 1:                # every vector is a norm's gain
        return W.norm(key, shape).astype(dtype)
    if name == "moe.router":           # the program keeps it in float32
        return W.draw(key, shape, 0.02)
    return W.draw(key, shape, 1.0 / math.sqrt(shape[-2])).astype(dtype)


def layer_weights(m: dict, key, layer, ffn: str, dtype=jnp.bfloat16) -> dict:
    """One block's weights (``layer``, counted over the whole stack, may
    be traced)."""
    def fill(shapes, prefix):
        return {k: fill(v, prefix + k + ".") if isinstance(v, dict) else
                _leaf(W.leaf_key(key, LAYER_LEAVES.index(prefix + k), layer),
                      prefix + k, v, dtype)
                for k, v in shapes.items()}
    return fill(block_shapes(m, ffn), "")


def _top_key(key, leaf: str):
    return W.leaf_key(key, len(LAYER_LEAVES) + TOP_LEAVES.index(leaf))


def program_params(m: dict, key, dtype):
    V, d = m["vocab_size"], m["hidden_size"]
    p = {"embed": W.table(_top_key(key, "embed"), V, d, 0.02, dtype),
         "final_norm": W.norm(_top_key(key, "final_norm"), (d,)).astype(dtype)}
    if not m["tie_word_embeddings"]:
        p["lm_head"] = W.table(_top_key(key, "lm_head"), V, d,
                               1.0 / math.sqrt(d), dtype).T
    first = 0
    for i, (ffn, n) in enumerate(blocks(m)):
        p[f"g{i}"] = {"b0": jax.lax.map(
            lambda l, ffn=ffn, first=first: layer_weights(m, key, first + l,
                                                          ffn, dtype),
            jnp.arange(n))}
        first += n
    return p


# -- work counts (absorbed latent decode) -------------------------------------

def _attn_params(m: dict) -> int:
    d, H = m["hidden_size"], m["num_attention_heads"]
    nh, rh, vh = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    return (d * qr + qr * H * (nh + rh) + d * (kvr + rh)
            + H * kvr * (nh + vh) + H * vh * d)


def matmul_params(m: dict) -> int:
    """Weights a decoded token multiplies through: every layer's attention
    and dense MLP, or its router, chosen and shared experts; the head."""
    d = m["hidden_size"]
    ffn = {"dense": 3 * d * m["intermediate_size"],
           "moe": 3 * d * m["moe_intermediate_size"]
           * (m["num_experts_per_tok"] + m["n_shared_experts"])
           + d * m["n_routed_experts"]}
    return sum(n * (_attn_params(m) + ffn[f]) for f, n in blocks(m)) \
        + m["vocab_size"] * d


def _attn_flops(m: dict, kv_len: int) -> int:
    """One layer, one query token over kv_len latent rows: scores over
    the latent and its rotary part, values over the latent."""
    kvr = m["kv_lora_rank"]
    return 2 * m["num_attention_heads"] * (2 * kvr + m["qk_rope_head_dim"]) \
        * kv_len


def decode_step_flops(m: dict, kv_lens: Iterable[int]) -> int:
    return sum(2 * matmul_params(m) + attention_layers(m)
               * _attn_flops(m, int(n)) for n in kv_lens)


def attention_layers(m: dict) -> int:
    return m["num_hidden_layers"]


def paged_attention_cost(m: dict, kv_lens: Iterable[int],
                         block_tokens: int, dtype_bytes: int = 2):
    """(flops, bytes) of one layer's paged latent attention: the absorbed
    query and the latent output of every head, and the live pages of the
    one latent pool (latent and rotary part)."""
    H, kvr, rh = m["num_attention_heads"], m["kv_lora_rank"], \
        m["qk_rope_head_dim"]
    flops = nbytes = 0
    for n in kv_lens:
        n = int(n)
        flops += _attn_flops(m, n)
        nbytes += H * (2 * kvr + rh) * dtype_bytes
        nbytes += math.ceil(n / block_tokens) * block_tokens * (kvr + rh) \
            * dtype_bytes
    return flops, nbytes
