"""Prefix caching + paged KV pool: chained-hash trie properties, BlockPool
refcount/COW/eviction invariants, paged-vs-contiguous decode equivalence
(randomized admission sweeps on attn and MLA+MoE archs), the zero-reprefill
guarantee for fully-cached prefixes, freed-slot decode masking, the paged
flash-decode kernel, and the router-side prefix-affinity term (DSL knob,
selection override, endpoint preference vs sticky sessions).

Randomized sweeps use seeded ``random.Random`` (hypothesis is not in the
image); failures reproduce deterministically from the printed seed."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.prefix import (BLOCK_TOKENS, PrefixIndex, chain_hashes,
                               text_block_hashes)
from repro.serving.paged import TRASH_BLOCK, BlockPool

ATTN_ARCH = "smollm-360m"
MLA_ARCH = "deepseek-v2-236b"


# ---------------------------------------------------------------------------
# chained block hashes
# ---------------------------------------------------------------------------

def test_chain_hashes_prefix_property():
    """hash[i] identifies the whole (i+1)-block prefix: equal prefixes give
    equal chains, and one differing token breaks every later hash."""
    rnd = random.Random(0)
    ids = [rnd.randrange(4096) for _ in range(10 * 16)]
    full = chain_hashes(ids, 16)
    assert len(full) == 10
    for k in (1, 3, 7):
        assert chain_hashes(ids[:k * 16], 16) == full[:k]
    # partial tail block is never hashed
    assert chain_hashes(ids[:16 + 7], 16) == full[:1]
    assert chain_hashes(ids[:15], 16) == []
    mut = list(ids)
    mut[3 * 16] ^= 1
    other = chain_hashes(mut, 16)
    assert other[:3] == full[:3]
    assert all(a != b for a, b in zip(other[3:], full[3:]))


def test_text_block_hashes_deterministic():
    text = " ".join(f"word{i}" for i in range(40))
    a, b = text_block_hashes(text), text_block_hashes(text)
    assert a == b and len(a) == 40 // BLOCK_TOKENS
    assert text_block_hashes("short prompt") == []


# ---------------------------------------------------------------------------
# PrefixIndex (router-side trie)
# ---------------------------------------------------------------------------

def test_prefix_index_longest_match_per_holder():
    idx = PrefixIndex()
    h = chain_hashes(list(range(5 * 16)), 16)
    idx.insert("a", h[:2])
    idx.insert("b", h[:5])
    m = idx.match(h)
    assert m == {"a": 2, "b": 5}
    # holder restriction prunes the walk
    assert idx.match(h, holders={"a"}) == {"a": 2}
    assert idx.match(h, holders={"nobody"}) == {}
    # a divergent chain matches nothing
    assert idx.match(chain_hashes(list(range(1, 5 * 16 + 1)), 16)) == {}


def test_prefix_index_eviction_and_remove_holder():
    idx = PrefixIndex(max_nodes=8)
    chains = [chain_hashes([s * 1000 + i for i in range(4 * 16)], 16)
              for s in range(5)]
    for i, c in enumerate(chains):
        idx.insert(f"h{i}", c)
    assert len(idx) <= 8 and idx.evictions > 0
    # the most recent insert always survives eviction
    assert idx.match(chains[-1]) == {"h4": 4}
    idx.remove_holder("h4")
    assert idx.match(chains[-1]) == {}


def test_prefix_index_random_sweep():
    """Property sweep: match() depth equals the longest common leading
    block run between the query and any insert attributed to the holder."""
    for seed in range(3):
        rnd = random.Random(seed)
        idx = PrefixIndex()
        base = [rnd.randrange(4096) for _ in range(8 * 16)]
        inserted = {}
        for hld in "abcd":
            depth = rnd.randrange(1, 9)
            inserted[hld] = depth
            idx.insert(hld, chain_hashes(base[:depth * 16], 16))
        q = chain_hashes(base, 16)
        m = idx.match(q)
        assert m == inserted, (seed, m, inserted)


# ---------------------------------------------------------------------------
# BlockPool: refcount / COW / LRU invariants
# ---------------------------------------------------------------------------

def test_blockpool_admit_match_release_cycle():
    pool = BlockPool(num_blocks=9, block_tokens=4)
    h = chain_hashes(list(range(12)), 4)          # 3 full blocks
    row = pool.admit([], 3, new_hashes=h)
    assert row is not None and TRASH_BLOCK not in row
    assert all(pool.ref(b) == 1 for b in row)
    assert pool.match(h) == 3                     # eager registration
    # a second admission of the same prompt refs the SAME blocks
    row2 = pool.admit(h, 3)
    assert row2 == row and all(pool.ref(b) == 2 for b in row)
    pool.release(row2)
    pool.release(row, full_hashes=h)
    # ref 0 + hashed: retained for future matches, still matchable
    assert all(pool.ref(b) == 0 for b in row)
    assert pool.match(h) == 3
    assert pool.stats.hit_blocks == 3 and pool.stats.miss_blocks == 3


def test_blockpool_cow_semantics():
    pool = BlockPool(num_blocks=9, block_tokens=4)
    h = chain_hashes(list(range(8)), 4)           # 2 full blocks
    row = pool.admit([], 3, new_hashes=h)         # block 3 unhashed (tail)
    # fresh blocks are exempt even though hash-registered
    assert pool.ensure_writable(row, 0, exempt=set(row)) == []
    # ref==1 and unhashed: in-place write allowed
    assert pool.ensure_writable(row, 2) == []
    # hashed blocks must COW for a non-exempt writer
    copies = pool.ensure_writable(row, 0)
    assert len(copies) == 2 and pool.stats.cow_copies == 2
    for src, dst in copies:
        assert pool.ref(dst) == 1
        assert dst in row and src not in row      # row remapped in place
    assert pool.match(h) == 2                     # originals stay matchable


def test_blockpool_shared_block_cow_and_pinning():
    pool = BlockPool(num_blocks=9, block_tokens=4)
    h = chain_hashes(list(range(8)), 4)
    row_a = pool.admit([], 2, new_hashes=h)
    row_b = pool.admit(h, 2)                      # full prefix hit
    assert row_b == row_a and all(pool.ref(b) == 2 for b in row_a)
    copies = pool.ensure_writable(row_b, 1)       # writer forks the tail
    assert len(copies) == 1 and row_b[1] != row_a[1]
    assert pool.ref(row_a[1]) == 1                # a's view un-forked


def test_blockpool_eviction_never_corrupts_live_row():
    pool = BlockPool(num_blocks=6, block_tokens=4)   # 5 usable blocks
    h_live = chain_hashes(list(range(8)), 4)
    live = pool.admit([], 2, new_hashes=h_live)      # pinned (ref 1)
    # churn through the remaining capacity so LRU eviction must trigger
    for s in range(4):
        h = chain_hashes([100 * (s + 1) + i for i in range(8)], 4)
        row = pool.admit([], 2, new_hashes=h)
        if row is None:                              # pool full of pinned rows
            continue
        pool.release(row, full_hashes=h)
        assert not set(row) & set(live), "evictor handed out a pinned block"
    assert all(pool.ref(b) == 1 for b in live)       # live row untouched
    assert pool.match(h_live) == 2
    assert pool.stats.evictions > 0


def test_blockpool_oom_returns_none():
    pool = BlockPool(num_blocks=4, block_tokens=4)   # 3 usable
    row = pool.admit([], 3)
    assert row is not None
    assert pool.admit([], 1) is None                 # all pinned: stall
    pool.release(row)
    assert pool.admit([], 1) is not None


def test_blockpool_random_refcount_sweep():
    """Random admit/release/COW interleavings: refcounts never go negative
    (asserted internally), pinned blocks never re-allocated, and the sum
    of refs equals the live-row multiset."""
    for seed in range(3):
        rnd = random.Random(seed)
        pool = BlockPool(num_blocks=20, block_tokens=4)
        live = []
        for _ in range(60):
            if live and rnd.random() < 0.4:
                row, h = live.pop(rnd.randrange(len(live)))
                pool.release(row, full_hashes=h)
                continue
            nb = rnd.randrange(1, 4)
            ids = [rnd.randrange(50) for _ in range(nb * 4)]
            h = chain_hashes(ids, 4)
            matched = pool.match(h)
            row = pool.admit(h[:matched], nb, new_hashes=h[matched:])
            if row is None:
                continue
            if rnd.random() < 0.3:
                pool.ensure_writable(row, rnd.randrange(nb),
                                     exempt=set(row[matched:]))
            live.append((row, h))
        want = {}
        for row, _ in live:
            for b in row:
                want[b] = want.get(b, 0) + 1
        got = {b: pool.ref(b) for b in range(pool.num_blocks)
               if pool.ref(b) > 0}
        assert got == want, (seed, got, want)


# ---------------------------------------------------------------------------
# paged flash-decode kernel
# ---------------------------------------------------------------------------

def test_paged_flash_decode_vs_references(rng, stacked):
    from repro.kernels.flash_decode import (decode_reference, gather_kv,
                                            paged_decode_reference,
                                            paged_flash_verify)
    B, nb, blk, Hq, Hkv, hd = 3, 10, 16, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((B, Hq, hd)), jnp.float32)
    kpool = jnp.asarray(rng.standard_normal((nb, Hkv, blk, hd)), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((nb, Hkv, blk, hd)), jnp.float32)
    # each row maps 4 blocks, deliberately scattered and overlapping
    tbl = jnp.asarray([[1, 5, 2, 9], [3, 1, 7, 4], [8, 6, 1, 2]], jnp.int32)
    kv_len = jnp.asarray([64, 50, 17], jnp.int32)
    out = paged_flash_verify(q[:, None], stacked(kpool), stacked(vpool), tbl,
                             kv_len, stacked.layer)[:, 0]
    ref = paged_decode_reference(q, kpool, vpool, tbl, kv_len)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # the paged path equals the contiguous oracle on the gathered view
    kg, vg = gather_kv(kpool, tbl), gather_kv(vpool, tbl)
    ref2 = decode_reference(q, kg, vg, kv_len)
    np.testing.assert_allclose(ref, ref2, atol=0, rtol=0)


# ---------------------------------------------------------------------------
# fleet equivalence: paged vs contiguous
# ---------------------------------------------------------------------------

def _mk_fleet(arch, paged, **kw):
    from repro.serving.fleet import LocalFleet
    kw.setdefault("batch", 3)
    kw.setdefault("gen_tokens", 6)
    return LocalFleet([arch], reduced=True, paged=paged, **kw)


@pytest.fixture(scope="module")
def attn_pair():
    return _mk_fleet(ATTN_ARCH, False), _mk_fleet(ATTN_ARCH, True)


@pytest.fixture(scope="module")
def mla_pair():
    return _mk_fleet(MLA_ARCH, False), _mk_fleet(MLA_ARCH, True)


@pytest.fixture(scope="module")
def deep_attn_pair():
    """Six layers in the group: each step's layer scan carries the pools
    through six in-place updates."""
    deep = {ATTN_ARCH: {"depth_mult": 3}}
    return (_mk_fleet(ATTN_ARCH, False, arch_overrides=deep),
            _mk_fleet(ATTN_ARCH, True, arch_overrides=deep))


def _rand_prompts(rnd, n, shared=None):
    out = []
    for _ in range(n):
        L = rnd.randrange(1, 90)
        body = " ".join(f"w{rnd.randrange(500)}" for _ in range(L))
        if shared and rnd.random() < 0.6:
            body = shared + " " + body
        out.append(body)
    return out


@pytest.mark.parametrize("pair_fx", ["attn_pair", "mla_pair",
                                     "deep_attn_pair"])
def test_paged_tokens_match_contiguous_random_sweep(pair_fx, request):
    """The acceptance bar: random admission orders and prompt lengths
    (incl. shared prefixes, so the cached suffix-prefill path is hit)
    produce IDENTICAL tokens on the paged and contiguous fleets."""
    contig, paged = request.getfixturevalue(pair_fx)
    arch = list(contig.members)[0]
    shared = " ".join(f"sys{i}" for i in range(40))   # 2+ full blocks
    for seed in range(2):
        rnd = random.Random(seed)
        prompts = _rand_prompts(rnd, 7, shared=shared)
        a = contig.generate(arch, prompts)
        b = paged.generate(arch, prompts)
        for i, (x, y) in enumerate(zip(a, b)):
            assert x["tokens"] == y["tokens"], (seed, i, prompts[i])
    st = paged.schedulers[arch].pool.stats
    assert st.hit_blocks > 0 and st.cached_tokens > 0, st.as_dict()


def test_repeat_prompt_served_from_cache_same_tokens(attn_pair):
    contig, paged = attn_pair
    prompt = " ".join(f"tok{i}" for i in range(50))
    base = contig.generate(ATTN_ARCH, [prompt])[0]["tokens"]
    first = paged.generate(ATTN_ARCH, [prompt])[0]["tokens"]
    st0 = dict(paged.schedulers[ATTN_ARCH].pool.stats.as_dict())
    again = paged.generate(ATTN_ARCH, [prompt])[0]["tokens"]
    st1 = paged.schedulers[ATTN_ARCH].pool.stats.as_dict()
    assert base == first == again
    assert st1["hit_blocks"] > st0["hit_blocks"]
    assert st1["cached_tokens"] > st0["cached_tokens"]


def test_fully_cached_prefix_zero_blocks_reprefilled(attn_pair):
    """Spy on the member's paged prefill programs: a fully-cached prompt
    must take the suffix path with exactly ONE recomputed token (the
    sampled position) — zero full blocks re-prefilled."""
    _, paged = attn_pair
    m = paged.members[ATTN_ARCH]
    sched = paged.schedulers[ATTN_ARCH]
    calls = {"fresh": [], "suffix": []}
    real_fresh, real_suffix = m.prefill_paged_fresh, m.prefill_paged_suffix

    def spy(name, fn):
        def wrapped(params, toks, lens, start, tbl, cache):
            calls[name].append((int(np.asarray(lens)[0]),
                                int(np.asarray(start)[0])))
            return fn(params, toks, lens, start, tbl, cache)
        return wrapped

    m.prefill_paged_fresh = spy("fresh", real_fresh)
    m.prefill_paged_suffix = spy("suffix", real_suffix)
    n = 48
    try:
        prompt = " ".join(f"cachehit{i}" for i in range(n))   # 3 full blocks
        paged.generate(ATTN_ARCH, [prompt])
        assert len(calls["fresh"]) == 1 and not calls["suffix"]
        paged.generate(ATTN_ARCH, [prompt])
        assert len(calls["suffix"]) == 1
        suffix_len, start = calls["suffix"][0]
        assert start == n - 1 and suffix_len == 1   # one token, zero blocks
    finally:
        m.prefill_paged_fresh = real_fresh
        m.prefill_paged_suffix = real_suffix
    seq = list(sched._finished.values())[-1]
    assert seq.prefill_tokens == 1
    assert seq.cached_tokens == n - 1


def test_freed_slot_lanes_masked_out_of_decode(attn_pair):
    """Mixed generation lengths leave freed slots in the decode batch;
    they must be masked (counted in masked_slot_steps), never sampled
    into a sequence (scheduler asserts), and paged freed rows point at
    the trash block."""
    _, paged = attn_pair
    lane = paged.lanes[ATTN_ARCH]
    sched = paged.schedulers[ATTN_ARCH]
    before = sched.masked_slot_steps
    outs = paged.generate(ATTN_ARCH, ["aa bb cc", "dd ee", "ff gg hh ii"],
                          max_new=None)
    # force staggered finishes: one short row leaves its slot dead while
    # the longer rows keep decoding
    short = paged.generate(ATTN_ARCH, ["solo row"], max_new=2)
    for i in range(2):
        sched.submit(np.asarray([5 + i], np.int32), max_new=2 + 3 * i)
    while lane.pending:
        lane.step()
    assert sched.masked_slot_steps > before
    assert all(len(o["tokens"]) == 6 for o in outs)
    assert len(short[0]["tokens"]) == 2
    # freed paged lanes are trash-mapped
    for slot in range(sched.slots):
        if sched.active[slot] is None:
            assert (sched.tbl[slot] == TRASH_BLOCK).all()


def test_paged_auto_gates_unsupported_archs():
    from repro.configs import get_reduced
    from repro.models import model as MD
    assert MD.paged_supported(get_reduced(ATTN_ARCH))
    assert MD.paged_supported(get_reduced(MLA_ARCH))
    assert not MD.paged_supported(get_reduced("jamba-v0.1-52b"))   # SSM
    assert not MD.paged_supported(get_reduced("whisper-tiny"))     # cross


# ---------------------------------------------------------------------------
# router-side prefix affinity
# ---------------------------------------------------------------------------

ROUTER_DSL = """
SIGNAL keyword code { keywords: ["code", "python"] }

ROUTE coding {
  PRIORITY 10
  WHEN keyword("code")
  MODEL "model-a", "model-b"
  ALGORITHM elo
}

BACKEND ep1 vllm { address: "127.0.0.1", port: 8001,
                   models: ["model-a", "model-b"] }
BACKEND ep2 vllm { address: "127.0.0.1", port: 8002,
                   models: ["model-a", "model-b"] }

GLOBAL { default_model: "model-a", prefix_affinity: 0.6 }
"""


def test_prefix_affinity_dsl_round_trip():
    from repro.core.dsl.compiler import compile_source
    from repro.core.dsl.decompiler import decompile
    cfg, _ = compile_source(ROUTER_DSL)
    assert cfg.prefix_affinity == 0.6
    cfg2, _ = compile_source(decompile(cfg))
    assert cfg2.prefix_affinity == 0.6
    # default stays off and is not emitted
    cfg3, _ = compile_source("GLOBAL { default_model: \"m\" }")
    assert cfg3.prefix_affinity == 0.0
    assert "prefix_affinity" not in decompile(cfg3)


def _affinity_router():
    from repro.core.dsl.compiler import compile_source
    from repro.core.router import SemanticRouter
    cfg, _ = compile_source(ROUTER_DSL)
    return SemanticRouter(cfg)


def test_prefix_affinity_overrides_selection_and_endpoint():
    from repro.core.types import Message, Request
    router = _affinity_router()
    prompt = " ".join(f"w{i} code python" for i in range(40))
    _, o1 = router.route(Request(messages=[Message("user", prompt)]))
    assert o1.decision == "coding"
    # seed a fresh index attributing the prefix to the OTHER model/ep2
    other = "model-b" if o1.model == "model-a" else "model-a"
    router.prefix_index = PrefixIndex()
    h = text_block_hashes(prompt)
    assert h, "prompt must span full blocks"
    router.prefix_index.insert(other, h)
    router.prefix_index.insert("ep:ep2", h)
    _, o2 = router.route(Request(messages=[Message("user", prompt)]))
    assert o2.model == other          # affinity overrode the algorithm pick
    assert o2.endpoint == "ep2"       # and dispatch preferred the holder
    # dispatch feeds the index back: the winner deepens its claim
    assert router.prefix_index.match(h, holders={other})[other] == len(h)


def test_prefix_affinity_conflict_with_sticky_session_recorded():
    from repro.core.observability import METRICS
    from repro.core.types import Message, Request
    router = _affinity_router()
    prompt = " ".join(f"w{i} code python" for i in range(40))
    h = text_block_hashes(prompt)
    router.prefix_index.insert("model-a", h)
    router.prefix_index.insert("ep:ep2", h)
    base = sum(v for k, v in METRICS.counters.items()
               if "affinity_conflict_total" in str(k))
    # pick a session whose sticky hash maps AWAY from ep2
    ep_router = router.endpoint_router
    session = next(
        s for s in (f"sess-{i}" for i in range(64))
        if ep_router._weighted_pick(
            ep_router.serving("model-a", "text"), s).name != "ep2")
    _, o = router.route(Request(messages=[Message("user", prompt)],
                                user=session))
    assert o.endpoint == "ep2"        # prefix holder wins over stickiness
    now = sum(v for k, v in METRICS.counters.items()
              if "affinity_conflict_total" in str(k))
    assert now > base


def test_prefix_affinity_off_by_default_no_hashing():
    """affinity 0.0: no index feeding, no preference — existing routing
    behavior is untouched."""
    from repro.core.dsl.compiler import compile_source
    from repro.core.router import SemanticRouter
    from repro.core.types import Message, Request
    cfg, _ = compile_source(ROUTER_DSL.replace(
        "prefix_affinity: 0.6", "prefix_affinity: 0.0"))
    router = SemanticRouter(cfg)
    prompt = " ".join(f"w{i} code python" for i in range(40))
    router.route(Request(messages=[Message("user", prompt)]))
    assert len(router.prefix_index) == 0


def test_resolve_prefer_respects_health():
    from repro.core.types import Endpoint
    from repro.core.providers import EndpointRouter
    eps = [Endpoint("e1", "vllm", models=["m"]),
           Endpoint("e2", "vllm", models=["m"])]
    r = EndpointRouter(eps, cooldown_s=9999.0)
    assert r.resolve("m", prefer="e2").name == "e2"
    for _ in range(3):
        r.mark_failure(eps[1])
    # a circuit-broken preferred endpoint is skipped, not forced
    assert r.resolve("m", prefer="e2").name == "e1"
