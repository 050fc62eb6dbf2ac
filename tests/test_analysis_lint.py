"""Engine lint plane (repro.analysis.jaxpr_lint) applied to the real
hot paths: the jitted decision gate, both paged flash-decode kernels,
and the batched-MLP selection trainer's recompile behaviour."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (RecompileGuard, jit_cache_size, lint_fn,
                            lint_jaxpr, walk_eqns)
from repro.core.decision import and_, build_decision_gate, leaf, not_
from repro.core.types import Decision, ModelRef

L = lambda i: leaf("keyword", f"s{i}")          # noqa: E731


def _gate_and_batch(B=4):
    ds = [Decision("a", and_(L(0), L(1)), [ModelRef("m1")], priority=9),
          Decision("b", not_(L(0)), [ModelRef("m2")], priority=5),
          Decision("c", L(2), [ModelRef("m3")], priority=5)]
    gate, keys = build_decision_gate(ds)
    N = len(keys)
    rng = np.random.default_rng(0)
    match = (rng.random((B, N)) > 0.5).astype(np.float32)
    conf = rng.random((B, N)).astype(np.float32)
    return gate, jnp.asarray(match), jnp.asarray(conf)


# ---------------------------------------------------------------------------
# the lint passes themselves (positive + negative)
# ---------------------------------------------------------------------------

def test_walk_eqns_recurses_into_cond_branches():
    def f(x):
        return jax.lax.cond(x.sum() > 0, lambda v: v * 2.0,
                            lambda v: v - 1.0, x)
    jaxpr = jax.make_jaxpr(f)(jnp.ones((3,)))
    prims = {e.primitive.name for e in walk_eqns(jaxpr.jaxpr)}
    # the branch bodies' arithmetic is visible, not just the cond itself
    assert "cond" in prims
    assert {"mul", "sub"} <= prims


def test_lint_flags_host_callback():
    def noisy(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2

    findings = lint_fn(noisy, jnp.ones((4,)))
    assert any(f.rule == "host-callback" for f in findings)
    clean = lint_fn(lambda x: x * 2, jnp.ones((4,)))
    assert clean == []


def test_lint_flags_materialized_intermediate():
    def blowup(x):                       # (8,) -> (8, 8, 8) intermediate
        y = x[:, None, None] * x[None, :, None] * x[None, None, :]
        return y.sum()

    findings = lint_fn(blowup, jnp.ones((8,)), max_intermediate_elems=64)
    assert any(f.rule == "materialized-intermediate" and f.shape == (8, 8, 8)
               for f in findings)
    assert lint_fn(blowup, jnp.ones((8,)),
                   max_intermediate_elems=1024) == []


def test_lint_flags_banned_leading_shape():
    def gathered(tbl, pool):             # (B, S, d): the PR-8 anti-pattern
        return pool[tbl].sum(axis=1)

    B, S, d = 3, 64, 8
    tbl = jnp.zeros((B, S), jnp.int32)
    pool = jnp.zeros((100, d), jnp.float32)
    findings = lint_fn(gathered, tbl, pool,
                       banned_leading_shapes=[(B, S)])
    assert any(f.rule == "banned-shape" for f in findings)


# ---------------------------------------------------------------------------
# applied to the real hot paths
# ---------------------------------------------------------------------------

def test_decision_gate_is_lint_clean():
    gate, match, conf = _gate_and_batch()
    findings = lint_fn(gate, match, conf,
                       max_intermediate_elems=1 << 16)
    assert findings == [], [str(f) for f in findings]


def test_paged_flash_decode_kernels_are_lint_clean():
    """Decode is the verify kernels at W=1, reading layer 1 of two."""
    from repro.kernels.flash_decode.ops import (paged_flash_verify,
                                                paged_flash_verify_mla)
    B, nb, max_blocks, blk, Hq, Hkv, hd = 3, 10, 4, 16, 8, 2, 64
    S = max_blocks * blk
    q = jnp.zeros((B, 1, Hq, hd), jnp.float32)
    kpool = jnp.zeros((2, nb, Hkv, blk, hd), jnp.float32)
    vpool = jnp.zeros((2, nb, Hkv, blk, hd), jnp.float32)
    tbl = jnp.zeros((B, max_blocks), jnp.int32)
    kv_len = jnp.zeros((B,), jnp.int32)
    findings = lint_fn(paged_flash_verify, q, kpool, vpool, tbl, kv_len, 1,
                       banned_leading_shapes=[(B, S), (B * 2, S)])
    assert findings == [], [str(f) for f in findings]

    r, rh = 64, 32
    ql = jnp.zeros((B, 1, Hq, r), jnp.float32)
    qr = jnp.zeros((B, 1, Hq, rh), jnp.float32)
    ckv = jnp.zeros((2, nb, blk, r), jnp.float32)
    kr = jnp.zeros((2, nb, blk, rh), jnp.float32)
    findings = lint_fn(
        paged_flash_verify_mla, ql, qr, ckv, kr, tbl, kv_len, 1,
        banned_leading_shapes=[(B, S), (B * 2, S)],
        scale=1.0 / np.sqrt(96.0))
    assert findings == [], [str(f) for f in findings]


def test_paged_flash_verify_kernels_are_lint_clean():
    """At W>1 the kernels keep the decode contract: no gathered (B,
    max_blocks*block_tokens, ...) KV copy and no host callbacks — W
    rides the q tile, not extra KV traffic."""
    from repro.kernels.flash_decode.ops import (paged_flash_verify,
                                                paged_flash_verify_mla)
    B, nb, max_blocks, blk, Hq, Hkv, hd, W = 3, 10, 4, 16, 8, 2, 64, 5
    S = max_blocks * blk
    q = jnp.zeros((B, W, Hq, hd), jnp.float32)
    kpool = jnp.zeros((2, nb, Hkv, blk, hd), jnp.float32)
    vpool = jnp.zeros((2, nb, Hkv, blk, hd), jnp.float32)
    tbl = jnp.zeros((B, max_blocks), jnp.int32)
    kv_len = jnp.zeros((B,), jnp.int32)
    findings = lint_fn(paged_flash_verify, q, kpool, vpool, tbl, kv_len, 1,
                       banned_leading_shapes=[(B, S), (B * 2, S)])
    assert findings == [], [str(f) for f in findings]

    r, rh = 64, 32
    ql = jnp.zeros((B, W, Hq, r), jnp.float32)
    qr = jnp.zeros((B, W, Hq, rh), jnp.float32)
    ckv = jnp.zeros((2, nb, blk, r), jnp.float32)
    kr = jnp.zeros((2, nb, blk, rh), jnp.float32)
    findings = lint_fn(
        paged_flash_verify_mla, ql, qr, ckv, kr, tbl, kv_len, 1,
        banned_leading_shapes=[(B, S), (B * 2, S)],
        scale=1.0 / np.sqrt(96.0))
    assert findings == [], [str(f) for f in findings]


def test_lint_jaxpr_accepts_closed_and_raw():
    gate, match, conf = _gate_and_batch()
    closed = jax.make_jaxpr(gate)(match, conf)
    assert lint_jaxpr(closed) == lint_jaxpr(closed.jaxpr)


# ---------------------------------------------------------------------------
# recompile accounting: warmed shape buckets never miss the jit cache
# ---------------------------------------------------------------------------

def test_jit_cache_size_probe():
    @jax.jit
    def f(x):
        return x + 1

    assert jit_cache_size(f) == 0
    f(jnp.ones((2,)))
    assert jit_cache_size(f) == 1
    f(jnp.ones((2,)))                    # same bucket: no new entry
    assert jit_cache_size(f) == 1
    f(jnp.ones((3,)))                    # new shape bucket
    assert jit_cache_size(f) == 2
    assert jit_cache_size(lambda x: x) == -1   # plain fn: no cache


def test_recompile_guard_detects_miss():
    @jax.jit
    def f(x):
        return x * 2

    f(jnp.ones((2,)))
    guard = RecompileGuard({"f": f})
    f(jnp.ones((2,)))
    guard.assert_no_recompiles()
    f(jnp.ones((5,)))                    # unseen bucket -> miss
    assert guard.misses() == {"f": 1}
    with pytest.raises(AssertionError, match="unexpected jit recompiles"):
        guard.assert_no_recompiles()


def test_decision_gate_no_recompile_across_warm_buckets():
    gate, match, conf = _gate_and_batch(B=4)
    gate2, match8, conf8 = _gate_and_batch(B=8)
    # warm both batch buckets
    gate(match, conf)
    gate(match8, conf8)
    guard = RecompileGuard({"gate": gate})
    for _ in range(3):                   # replay: zero new compiles
        gate(match, conf)
        gate(match8, conf8)
    guard.assert_no_recompiles()


def test_verify_kernels_no_recompile_across_warm_width_buckets():
    """Replayed verify widths (the adaptive scheduler only issues
    W in {1, k+1}) never miss the jit cache once warmed, whichever layer
    of the stacked pools a call reads."""
    from repro.kernels.flash_decode.ops import (paged_flash_verify,
                                                paged_flash_verify_mla)
    B, nb, max_blocks, blk, Hq, Hkv, hd = 2, 6, 2, 16, 8, 2, 64
    kpool = jnp.zeros((2, nb, Hkv, blk, hd), jnp.float32)
    vpool = jnp.zeros((2, nb, Hkv, blk, hd), jnp.float32)
    ckv = jnp.zeros((2, nb, blk, 64), jnp.float32)
    kr = jnp.zeros((2, nb, blk, 32), jnp.float32)
    tbl = jnp.zeros((B, max_blocks), jnp.int32)
    kv_len = jnp.full((B,), 8, jnp.int32)

    def gqa(W, layer=0):
        return paged_flash_verify(jnp.zeros((B, W, Hq, hd), jnp.float32),
                                  kpool, vpool, tbl, kv_len, layer)

    def mla(W, layer=0):
        return paged_flash_verify_mla(
            jnp.zeros((B, W, Hq, 64), jnp.float32),
            jnp.zeros((B, W, Hq, 32), jnp.float32),
            ckv, kr, tbl, kv_len, layer, scale=0.1)

    for W in (1, 5):                     # warm both width buckets
        gqa(W)
        mla(W)
    guard = RecompileGuard({"verify": paged_flash_verify,
                            "verify_mla": paged_flash_verify_mla})
    for layer in (0, 1, 1):
        for W in (1, 5):
            gqa(W, layer)
            mla(W, layer)
    guard.assert_no_recompiles()


def test_mlp_select_many_no_recompile_per_batch():
    """The old _mlp_many re-created jax.jit(value_and_grad(loss)) per
    call, recompiling the train step on EVERY batch.  The hoisted
    module-level step must hit its cache on every warmed bucket."""
    from repro.classifiers.backend import HashBackend
    from repro.core.selection import SelectionContext, select_many
    from repro.core.selection.algorithms import (RoutingRecord,
                                                 _mlp_train_step)
    from repro.core.types import ModelProfile

    be = HashBackend()
    ctx = SelectionContext(profiles={
        "cheap": ModelProfile("cheap", quality=0.4),
        "big": ModelProfile("big", quality=0.9)})
    for i, e in enumerate(be.embed([f"solve equation {i} algebra"
                                    for i in range(8)])):
        ctx.add_record(RoutingRecord(e, 0, "big", 0.9))
        ctx.add_record(RoutingRecord(e, 0, "cheap", 0.2))
    E_q = np.asarray(be.embed(["solve equation 99", "debug function 99"]))
    zs = [0, 1]
    cfg = {"steps": 4}

    select_many("mlp", E_q, zs, ["cheap", "big"], ctx, cfg)   # warm
    step = _mlp_train_step()
    assert jit_cache_size(step) >= 1
    guard = RecompileGuard({"mlp_train_step": step})
    for _ in range(3):                   # identical record shapes: no miss
        select_many("mlp", E_q, zs, ["cheap", "big"], ctx, cfg)
    guard.assert_no_recompiles()
