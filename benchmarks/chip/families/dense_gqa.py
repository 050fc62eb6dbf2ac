"""The dense grouped-query-attention decoder: Llama / SmolLM, Qwen3, and
GLM-4 as the fleet runs it.

What the harness has to know of one architecture lives in its family
module, found by the configuration's ``reference`` name
(``families/<name>.py`` beside ``references/<name>.py``):

``check``            the program must run the model the configuration
                     file states (``spec.ConfigMismatch`` where it does not);
``entry_for``        the configuration entry of a program's model (CPU
                     tests serve the program's reduced presets);
the weight layout    the benchmark's own seeded leaves, made with the
                     shared keys and draws of ``harness.weights``:
                     ``LAYER_LEAVES``, ``layer_shapes``, ``layer_weights``,
                     ``vocab_rows``, ``vocab_chunk``, ``final_norm``,
                     ``all_weights``;
``program_params``   that layout in the program's parameter tree;
the work counts      operations and bytes from shapes alone, which the
                     per-layer readers turn into utilisations:
                     ``decode_step_flops``, ``paged_attention_cost``,
                     ``attention_layers``.

``m`` is a model entry of a configuration file (Hugging Face key names).
"""

from __future__ import annotations

import math
from typing import Iterable

import jax
import jax.numpy as jnp

from harness import spec
from harness import weights as W

# the program's configuration fields each configuration file states, by
# the program's name and the file's (Hugging Face) name
MODEL_FIELDS = (("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
                ("n_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
                ("d_ff", "intermediate_size"), ("vocab_size", "vocab_size"),
                ("rope_theta", "rope_theta"), ("norm_eps", "rms_norm_eps"),
                ("qk_norm", "qk_norm"),
                ("tie_embeddings", "tie_word_embeddings"), ("dtype", "dtype"))

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                "mlp_norm", "w_gate", "w_up", "w_down")
TOP_LEAVES = ("embed", "final_norm", "lm_head")


def _layers(arch_cfg) -> int:
    return sum(g.repeats * len(g.period) for g in arch_cfg.groups)


def check(arch_cfg, m: dict, arch: str):
    """The program must run the model the configuration file states."""
    groups = arch_cfg.groups
    bad = []
    if _layers(arch_cfg) != m["num_hidden_layers"]:
        bad.append(f"layers {_layers(arch_cfg)} != {m['num_hidden_layers']}")
    if len(groups) != 1 or len(groups[0].period) != 1 or \
            groups[0].period[0].mixer != "attn" or \
            groups[0].period[0].ffn != "dense":
        bad.append("not a stack of dense attention blocks")
    bad += spec.differing(arch_cfg, m, MODEL_FIELDS)
    if bad:
        raise spec.ConfigMismatch(f"{arch}: the program does not run the "
                                  "configured model: " + "; ".join(bad))


def entry_for(arch_cfg, dtype: str = "bfloat16") -> dict:
    """The configuration entry that states the program's ``arch_cfg``."""
    m = {theirs: getattr(arch_cfg, ours) for ours, theirs in MODEL_FIELDS}
    m.update(num_hidden_layers=_layers(arch_cfg), dtype=dtype)
    return m


# -- seeded weights, in the benchmark's own layout ----------------------------

def layer_shapes(m: dict) -> dict:
    d, H, Hkv = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hd = m.get("head_dim") or d // H
    F = m["intermediate_size"]
    out = {"attn_norm": (d,), "wq": (d, H * hd), "wk": (d, Hkv * hd),
           "wv": (d, Hkv * hd), "wo": (H * hd, d), "mlp_norm": (d,),
           "w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)}
    if m.get("qk_norm"):
        out["q_norm"] = (hd,)
        out["k_norm"] = (hd,)
    return out


def _leaf(key, name: str, shape):
    return W.norm(key, shape) if name.endswith("norm") else W.fan_in(key, shape)


def layer_weights(m: dict, key, layer, dtype=jnp.bfloat16) -> dict:
    """One decoder layer's weights (``layer`` may be traced)."""
    return {n: _leaf(W.leaf_key(key, LAYER_LEAVES.index(n), layer), n, s)
            .astype(dtype) for n, s in layer_shapes(m).items()}


def _top_key(key, leaf: str):
    return W.leaf_key(key, len(LAYER_LEAVES) + TOP_LEAVES.index(leaf))


def _row_std(m: dict, leaf: str) -> float:
    return 0.02 if leaf == "embed" else 1.0 / math.sqrt(m["hidden_size"])


def vocab_rows(m: dict, key, leaf: str, first_block, nblocks: int,
               dtype=jnp.bfloat16):
    """Rows [first_block*VOCAB_BLOCK, +nblocks*VOCAB_BLOCK) of the
    embedding or of the output head (stored (V, d), row per token)."""
    return W.block_rows(_top_key(key, leaf), first_block, nblocks,
                        m["hidden_size"], _row_std(m, leaf), dtype)


def vocab_chunks(m: dict) -> int:
    return W.vocab_chunks(m["vocab_size"])


def vocab_chunk(m: dict, key, leaf: str, chunk, dtype=jnp.bfloat16):
    return W.chunk_rows(_top_key(key, leaf), chunk, m["hidden_size"],
                        _row_std(m, leaf), dtype)


def final_norm(m: dict, key, dtype=jnp.bfloat16):
    return W.norm(_top_key(key, "final_norm"),
                  (m["hidden_size"],)).astype(dtype)


def all_weights(m: dict, key, dtype=jnp.bfloat16) -> dict:
    """The whole tree: top leaves, and every layer leaf stacked (L, ...).
    Layers are made one after another (``lax.map``), so the temporaries
    are one layer's or one chunk's."""
    V, d = m["vocab_size"], m["hidden_size"]

    def rows(leaf):
        return W.table(_top_key(key, leaf), V, d, _row_std(m, leaf), dtype)
    w = {"embed": rows("embed"), "final_norm": final_norm(m, key, dtype),
         "layers": jax.lax.map(lambda l: layer_weights(m, key, l, dtype),
                               jnp.arange(m["num_hidden_layers"]))}
    if not m.get("tie_word_embeddings"):
        w["lm_head"] = rows("lm_head")
    return w


def program_params(m: dict, key, dtype):
    """The benchmark's weights in the program's parameter layout."""
    w = all_weights(m, key, dtype)
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
            if k in lw}
    p = {"embed": w["embed"], "final_norm": w["final_norm"],
         "g0": {"b0": {"norm1": lw["attn_norm"], "attn": attn,
                       "norm2": lw["mlp_norm"],
                       "ffn": {"w_gate": lw["w_gate"], "w_up": lw["w_up"],
                               "w_down": lw["w_down"]}}}}
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"].T
    return p


# -- work counts: what the algorithm requires, not what an implementation
# happens to do (a kernel that pads its heads or reads dead pages does more
# than this and reads the lower share of its roofline for it) ---------------

def dims(m: dict) -> dict:
    d = m["hidden_size"]
    H = m["num_attention_heads"]
    Hkv = m["num_key_value_heads"]
    hd = m.get("head_dim") or d // H
    return dict(d=d, H=H, Hkv=Hkv, hd=hd, F=m["intermediate_size"],
                L=m["num_hidden_layers"], V=m["vocab_size"])


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one decoder layer: the
    q/k/v/o projections and the gated MLP."""
    x = dims(m)
    attn = x["d"] * x["H"] * x["hd"] * 2 + x["d"] * x["Hkv"] * x["hd"] * 2
    return attn + 3 * x["d"] * x["F"]


def matmul_params(m: dict) -> int:
    """All weights a decoded token multiplies through: every layer plus
    the output head (the embedding lookup multiplies nothing)."""
    x = dims(m)
    return x["L"] * layer_matmul_params(m) + x["V"] * x["d"]


def attention_flops(m: dict, kv_len: int) -> int:
    """One query token attending over kv_len keys, all layers: q.k and
    p.v, 2 flops a multiply-add each."""
    x = dims(m)
    return x["L"] * 4 * x["H"] * x["hd"] * kv_len


def decode_token_flops(m: dict, kv_len: int) -> int:
    return 2 * matmul_params(m) + attention_flops(m, kv_len)


def decode_step_flops(m: dict, kv_lens: Iterable[int]) -> int:
    """A decode step over the live rows, each at its own kv length
    (including the token written this step)."""
    return sum(decode_token_flops(m, int(n)) for n in kv_lens)


def attention_layers(m: dict) -> int:
    """Layers whose decode step makes one paged attention call each."""
    return dims(m)["L"]


def paged_attention_cost(m: dict, kv_lens: Iterable[int],
                         block_tokens: int, dtype_bytes: int = 2):
    """(flops, bytes) of ONE layer's paged decode attention over the live
    rows: q and out of every head, and the live pages of every KV head,
    keys and values."""
    x = dims(m)
    flops = nbytes = 0
    for n in kv_lens:
        n = int(n)
        pages = math.ceil(n / block_tokens)
        flops += 4 * x["H"] * x["hd"] * n
        nbytes += 2 * x["H"] * x["hd"] * dtype_bytes          # q, out
        nbytes += 2 * x["Hkv"] * pages * block_tokens * x["hd"] * dtype_bytes
    return flops, nbytes
