"""Mixture-of-Experts FFN.

Two interchangeable implementations (``cfg-independent``, selected by the
runtime ``moe_impl`` flag threaded through the model):

* ``gshard``  — capacity-based dispatch/combine einsums over token groups
  (GShard-style).  SPMD-robust under pjit at 512 devices; pays a dispatch
  einsum overhead of roughly the useful expert FLOPs (recorded as "waste" in
  the roofline's MODEL_FLOPS/HLO_FLOPs ratio — hillclimb target).
* ``ep_sort`` — shard_map expert parallelism: experts local to each "model"
  shard, tokens (replicated across that axis) are sorted/gathered into
  per-expert slots locally, computed with batched matmuls, scattered back and
  psum-combined.  No dispatch einsum; dropless up to the per-shard capacity.

Routing: softmax -> top-k -> renormalized top-k probs (+ optional shared
experts, DeepSeek-style).  Aux losses: load-balance + router z-loss.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init

Array = jax.Array

GROUP_SIZE = 256  # tokens per dispatch group (gshard impl)


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32, scale=0.02),
        "w_gate": dense_init(ks[1], (E, d, f), dtype),
        "w_up": dense_init(ks[2], (E, d, f), dtype),
        "w_down": dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, (d, fs), dtype),
            "w_up": dense_init(k2, (d, fs), dtype),
            "w_down": dense_init(k3, (fs, d), dtype),
        }
    return p


def _route(p: dict, cfg: ModelConfig, x: Array):
    """x: (N, d) -> (topk_idx (N,k), topk_prob (N,k), aux dict)."""
    logits = x.astype(jnp.float32) @ p["router"]          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_prob, topk_idx = jax.lax.top_k(probs, cfg.moe_top_k)
    topk_prob = topk_prob / jnp.clip(topk_prob.sum(-1, keepdims=True), 1e-9)

    # aux losses (Switch-style load balance + z-loss)
    E = cfg.n_experts
    me = probs.mean(axis=0)                                # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[topk_idx.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return topk_idx, topk_prob, {"moe_lb": lb_loss, "moe_z": z_loss}


def _shared_expert(p: dict, x: Array) -> Array:
    sp = p["shared"]
    return (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]


def _expert_ffn(p: dict, xs: Array) -> Array:
    """xs: (E, C, d) -> (E, C, d), batched per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xs, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


# ---------------------------------------------------------------------------
# gshard capacity dispatch (baseline)
# ---------------------------------------------------------------------------

def moe_gshard(p: dict, cfg: ModelConfig, x: Array) -> Tuple[Array, dict]:
    """x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    gs = GROUP_SIZE if S % GROUP_SIZE == 0 else S
    G = B * S // gs
    xg = x.reshape(G, gs, d)

    idx, prob, aux = _route(p, cfg, xg.reshape(-1, d))
    k, E = cfg.moe_top_k, cfg.n_experts
    idx = idx.reshape(G, gs, k)
    prob = prob.reshape(G, gs, k)

    C = max(1, math.ceil(gs * k / E * cfg.moe_capacity_factor))

    # position of each (token, slot) in its expert queue, per group
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)         # (G,gs,k,E)
    flat = onehot.reshape(G, gs * k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                      # 0-indexed
    pos_e = (pos.reshape(G, gs, k, E) * onehot).sum(-1)        # (G,gs,k)
    keep = (pos_e < C).astype(jnp.float32)
    pos_oh = jax.nn.one_hot(pos_e.astype(jnp.int32), C, dtype=jnp.float32)

    # combine/dispatch tensors (G, gs, E, C)
    combine = jnp.einsum("gsk,gske,gskc->gsec", prob * keep, onehot, pos_oh)
    dispatch = (combine > 0).astype(x.dtype)

    expert_in = jnp.einsum("gsec,gsd->gecd", dispatch, xg)      # (G,E,C,d)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"])
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    out = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), expert_out)

    out = out.reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + _shared_expert(p, x)
    return out, aux


# ---------------------------------------------------------------------------
# sort/gather expert compute (no dispatch einsum) — local core + shard_map EP
# ---------------------------------------------------------------------------

def _sort_core(w_gate, w_up, w_down, xf: Array, idx, prob, E_total: int,
               E_loc: int, e_offset, C: int) -> Array:
    """Routed-expert compute for the experts in [e_offset, e_offset+E_loc).

    xf: (N, d) tokens; idx/prob: (N, k) global routing; weights are the local
    slice (E_loc, ...).  Returns the (N, d) partial output (zeros for tokens
    whose experts live elsewhere).
    """
    N, d = xf.shape
    k = idx.shape[1]
    e_local = idx - e_offset                                    # (N, k)
    here = (e_local >= 0) & (e_local < E_loc)

    flat_e = jnp.where(here, e_local, E_loc).reshape(-1)        # (N*k,)
    flat_tok = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    flat_prob = jnp.where(here, prob, 0.0).reshape(-1)

    onehot = jax.nn.one_hot(flat_e, E_loc, dtype=jnp.int32)     # (N*k, E_loc)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_e = (pos * onehot).sum(-1)                              # (N*k,)
    keep = (pos_e < C) & (flat_e < E_loc)

    slot = jnp.where(keep, flat_e * C + pos_e, E_loc * C)
    table = jnp.full((E_loc * C + 1,), N, dtype=jnp.int32)
    table = table.at[slot].set(flat_tok, mode="drop")
    table = table[: E_loc * C].reshape(E_loc, C)
    wtable = jnp.zeros((E_loc * C + 1,), jnp.float32)
    wtable = wtable.at[slot].set(flat_prob, mode="drop")
    wtable = wtable[: E_loc * C].reshape(E_loc, C)

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    expert_in = jnp.take(xpad, table, axis=0)                   # (E_loc, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_down)
    weighted = expert_out * wtable[..., None].astype(expert_out.dtype)

    out = jnp.zeros((N + 1, d), xf.dtype)
    out = out.at[table.reshape(-1)].add(
        weighted.reshape(-1, d).astype(xf.dtype), mode="drop")
    return out[:N]


def _capacity(n_tokens: int, k: int, E: int, cf: float) -> int:
    return max(4, math.ceil(n_tokens * k / E * cf))


def moe_sort_local(p: dict, cfg: ModelConfig, x: Array) -> Tuple[Array, dict]:
    """Single-shard sort/gather MoE (all experts local)."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    idx, prob, aux = _route(p, cfg, xf)
    E = cfg.n_experts
    C = _capacity(xf.shape[0], cfg.moe_top_k, E, cfg.moe_capacity_factor)
    out = _sort_core(p["w_gate"], p["w_up"], p["w_down"], xf, idx, prob,
                     E, E, 0, C).reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + _shared_expert(p, x)
    return out, aux


def moe_ep(p: dict, cfg: ModelConfig, x: Array) -> Tuple[Array, dict]:
    """shard_map expert parallelism over the "model" mesh axis.

    Tokens are replicated across "model" (residual activations are
    batch-sharded only), experts are sharded over "model"; each shard
    computes its local experts' contribution and the results psum over
    "model" — the same reduction TP already performs, so EP adds *no*
    all-to-all and no dispatch einsum.  Falls back to the local path when no
    mesh is installed (unit tests, single host).
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding.ctx import current_rules
    from repro.sharding.rules import batch_axes

    rules, mesh = current_rules()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_sort_local(p, cfg, x)

    B, S, d = x.shape
    E = cfg.n_experts
    msize = mesh.shape["model"]
    if E % msize != 0:
        return moe_sort_local(p, cfg, x)
    E_loc = E // msize
    dp = batch_axes(mesh)
    n_dp = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        n_dp *= mesh.shape[a]
    if B % n_dp != 0:
        dp = None           # small batch: tokens replicated over data too
        n_dp = 1
    N_loc = max(1, B * S // n_dp)
    C = _capacity(N_loc, cfg.moe_top_k, E, cfg.moe_capacity_factor)

    def body(xl, wg, wu, wd, router):
        # ZeRO-3: expert weights arrive f-sharded over "data"; gather the
        # full local experts (grad transposes to the matching reduce-scatter)
        wg = jax.lax.all_gather(wg, "data", axis=2, tiled=True)
        wu = jax.lax.all_gather(wu, "data", axis=2, tiled=True)
        wd = jax.lax.all_gather(wd, "data", axis=1, tiled=True)
        Bl, Sl, _ = xl.shape
        xf = xl.reshape(-1, d)
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        tp, ti = jax.lax.top_k(probs, cfg.moe_top_k)
        tp = tp / jnp.clip(tp.sum(-1, keepdims=True), 1e-9)
        off = jax.lax.axis_index("model") * E_loc
        out = _sort_core(wg, wu, wd, xf, ti, tp, E, E_loc, off, C)
        out = jax.lax.psum(out, "model")
        # aux losses — identical on every model shard (router replicated)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[ti.reshape(-1)].add(1.0)
        ce = ce / jnp.maximum(ce.sum(), 1.0)
        lb = E * jnp.sum(me * ce)
        zl = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return out.reshape(Bl, Sl, d), lb, zl

    xspec = P(dp, None, None)
    e_up = P("model", None, "data")   # (E, d, f): f FSDP-sharded
    e_dn = P("model", "data", None)   # (E, f, d)
    out, lb, zl = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, e_up, e_up, e_dn, P(None, None)),
        out_specs=(xspec, P(), P()),
        check_vma=False,
    )(x, p["w_gate"], p["w_up"], p["w_down"], p["router"])
    aux = {"moe_lb": lb, "moe_z": zl}
    if cfg.n_shared_experts:
        out = out + _shared_expert(p, x)
    return out, aux


def moe_ep_serve(p: dict, cfg: ModelConfig, x: Array) -> Tuple[Array, dict]:
    """Weights-stationary EP for decode (§Perf lever `moe_ws`).

    The training-path EP all-gathers each expert's FSDP-sharded f-dim every
    layer — correct when activations dwarf weights, but at decode (a few
    tokens vs GBs of experts) it makes every step re-stream the full expert
    weights.  Here weights never move: the *tokens* are all-gathered across
    "data" (KBs), every shard computes its local (E_loc, f_loc) slice for
    all tokens, and partial outputs psum over ("data", "model").  Per-step
    expert weight traffic drops from |experts| to |experts| / (data*model).
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding.ctx import current_rules
    from repro.sharding.rules import batch_axes

    rules, mesh = current_rules()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_sort_local(p, cfg, x)
    B, S, d = x.shape
    E = cfg.n_experts
    msize = mesh.shape["model"]
    f = cfg.d_ff_expert
    dsize = mesh.shape.get("data", 1)
    if E % msize != 0 or f % dsize != 0:
        return moe_ep(p, cfg, x)
    E_loc = E // msize
    dp = batch_axes(mesh)
    n_dp = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        n_dp *= mesh.shape[a]
    if B % n_dp != 0:
        dp = None
        n_dp = 1
    N_full = B * S
    C = _capacity(N_full, cfg.moe_top_k, E, cfg.moe_capacity_factor)

    dp_axes = tuple(dp) if isinstance(dp, tuple) else \
        ((dp,) if dp is not None else ())

    def body(xl, wg, wu, wd, router):
        # gather the (tiny) token shard across data -> full token set
        if n_dp > 1:
            xl = jax.lax.all_gather(xl, dp_axes, axis=0, tiled=True)
        Bf, Sf, _ = xl.shape
        xf = xl.reshape(-1, d)
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        tp, ti = jax.lax.top_k(probs, cfg.moe_top_k)
        tp = tp / jnp.clip(tp.sum(-1, keepdims=True), 1e-9)
        off = jax.lax.axis_index("model") * E_loc
        # weights-stationary expert compute on the local f slice
        out = _sort_core(wg, wu, wd, xf, ti, tp, E, E_loc, off, C)
        # combine f-slices (sharded over "data") and experts (over "model");
        # "pod" replicas computed identical partials within their pod group
        out = jax.lax.psum(out, ("data", "model"))
        out = out.reshape(Bf, Sf, d)
        if n_dp > 1:
            j = jnp.zeros((), jnp.int32)
            for a in dp_axes:
                j = j * mesh.shape[a] + jax.lax.axis_index(a)
            out = jax.lax.dynamic_slice_in_dim(out, j * (Bf // n_dp),
                                               Bf // n_dp, axis=0)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[ti.reshape(-1)].add(1.0)
        ce = ce / jnp.maximum(ce.sum(), 1.0)
        lb = E * jnp.sum(me * ce)
        zl = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return out, lb, zl

    xspec = P(dp, None, None)
    e_up = P("model", None, "data")
    e_dn = P("model", "data", None)
    out, lb, zl = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, e_up, e_up, e_dn, P(None, None)),
        out_specs=(xspec, P(), P()),
        check_vma=False,
    )(x, p["w_gate"], p["w_up"], p["w_down"], p["router"])
    aux = {"moe_lb": lb, "moe_z": zl}
    if cfg.n_shared_experts:
        out = out + _shared_expert(p, x)
    return out, aux


def moe_apply(p: dict, cfg: ModelConfig, x: Array, impl: str) -> Tuple[Array, dict]:
    if impl == "gshard":
        return moe_gshard(p, cfg, x)
    if impl == "sort":
        return moe_sort_local(p, cfg, x)
    if impl == "ep":
        return moe_ep(p, cfg, x)
    if impl == "ep_serve":
        return moe_ep_serve(p, cfg, x)
    raise ValueError(f"unknown moe impl {impl!r}")
