"""Draft-model speculative decoding on the text lane (PR 10).

Covers the tentpole and its satellites:

* kernel sweeps: ``paged_flash_verify`` / ``paged_flash_verify_mla``
  at width W match W successive W=1 calls (decode steps) BITWISE (each
  verify position's causal frontier equals the corresponding decode
  step's ``kv_len``);
* fleet-level token-exactness: the speculative path emits IDENTICAL
  tokens to the non-speculative path on xla AND flash_paged decode, on
  the GQA arch AND the MLA+MoE arch;
* rollback invariants: a seeded property-style sweep over random
  acceptance patterns (scripted proposal corruption) stays token-exact
  with ``BlockPool.live_refs() == 0`` after every drain; preemption
  parking a mid-speculation row resumes bitwise-exactly;
* adaptive k: an adversarial (always-rejected) draft backs the lane off
  to plain decode, probe rounds re-test it, and recovery re-enables
  speculation — token-exact throughout;
* construction validation: ``LocalFleet(decode_impl=..., speculative=...)``
  raise clear errors for unknown impls / invalid SpecConfigs BEFORE any
  model is built;
* DSL: ``GLOBAL speculative { ... }`` compiles, survives the
  decompile/compile round trip, and misspelled keys get quickfixes;
* observability: the overload probe surfaces acceptance EWMA and
  accepted tokens per step from speculating lanes.

The acceptance-pattern sweep is hypothesis-style but driven by seeded
``random.Random`` — the container image does not ship the hypothesis
package, and the invariant (token-exact under ANY acceptance pattern)
is what matters, not the shrinker.
"""

import random

import jax.numpy as jnp
import numpy as np
import pytest

ATTN_ARCH = "smollm-360m"
MLA_ARCH = "deepseek-v2-236b"
MISALIGNED_DRAFT = "qwen3-1.7b"
VOCAB = 256                              # every reduced config's vocab

PROMPTS = [
    " ".join(f"sys{i}" for i in range(20)) + " question one",
    "a lone unshared prompt",
    " ".join(f"sys{i}" for i in range(20)) + " question two longer tail",
    "tiny",
]


def _mk_fleet(arch, **kw):
    from repro.serving.fleet import LocalFleet
    kw.setdefault("reduced", True)
    kw.setdefault("batch", 2)
    kw.setdefault("gen_tokens", 6)
    kw.setdefault("warmup", False)
    return LocalFleet([arch], **kw)


def _spec(draft, **kw):
    from repro.serving.scheduler import SpecConfig
    return SpecConfig(draft_arch=draft, **kw)


@pytest.fixture(scope="module")
def ref_tokens():
    """Per-arch plain (non-speculative, xla) reference generations."""
    cache = {}

    def get(arch):
        if arch not in cache:
            fleet = _mk_fleet(arch, paged=True)
            cache[arch] = [r["tokens"]
                           for r in fleet.generate(arch, PROMPTS)]
        return cache[arch]
    return get


# ---------------------------------------------------------------------------
# kernel level: verify == W successive decode steps, bitwise
# ---------------------------------------------------------------------------

def _tbl_and_lens(rng, *, B, nb, max_blocks, blk, W):
    tbl = jnp.asarray(rng.randint(1, nb, size=(B, max_blocks)), jnp.int32)
    kv_len = jnp.asarray(rng.randint(W, max_blocks * blk + 1, size=(B,)),
                         jnp.int32)
    return tbl, kv_len


def test_paged_flash_verify_bitwise_matches_decode_steps(rng, stacked):
    from repro.kernels.flash_decode import paged_flash_verify
    B, nb, max_blocks, blk, Hq, Hkv, hd, W = 4, 12, 4, 16, 8, 2, 64, 3
    kpool = stacked(jnp.asarray(rng.standard_normal((nb, Hkv, blk, hd)),
                                jnp.float32))
    vpool = stacked(jnp.asarray(rng.standard_normal((nb, Hkv, blk, hd)),
                                jnp.float32))
    tbl, kv_len = _tbl_and_lens(rng, B=B, nb=nb, max_blocks=max_blocks,
                                blk=blk, W=W)
    q = jnp.asarray(rng.standard_normal((B, W, Hq, hd)), jnp.float32)
    out = np.asarray(paged_flash_verify(q, kpool, vpool, tbl, kv_len,
                                        stacked.layer))
    assert out.shape == (B, W, Hq, hd)
    for t in range(W):
        # position t's frontier == the decode step that would see
        # kv_len - (W - 1 - t) written entries
        step = np.asarray(paged_flash_verify(
            q[:, t:t + 1], kpool, vpool, tbl, kv_len - (W - 1 - t),
            stacked.layer))
        np.testing.assert_array_equal(out[:, t], step[:, 0],
                                      err_msg=f"t={t}")


def test_paged_flash_verify_mla_bitwise_matches_decode_steps(rng, stacked):
    from repro.kernels.flash_decode import paged_flash_verify_mla
    B, nb, max_blocks, blk, H, r, rh, W = 3, 10, 4, 16, 8, 64, 32, 4
    scale = 1.0 / np.sqrt(96.0)
    ckv = stacked(jnp.asarray(rng.standard_normal((nb, blk, r)), jnp.float32))
    kr = stacked(jnp.asarray(rng.standard_normal((nb, blk, rh)), jnp.float32))
    tbl, kv_len = _tbl_and_lens(rng, B=B, nb=nb, max_blocks=max_blocks,
                                blk=blk, W=W)
    ql = jnp.asarray(rng.standard_normal((B, W, H, r)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((B, W, H, rh)), jnp.float32)
    out = np.asarray(paged_flash_verify_mla(ql, qr, ckv, kr, tbl, kv_len,
                                            stacked.layer, scale=scale))
    assert out.shape == (B, W, H, r)
    for t in range(W):
        step = np.asarray(paged_flash_verify_mla(
            ql[:, t:t + 1], qr[:, t:t + 1], ckv, kr, tbl,
            kv_len - (W - 1 - t), stacked.layer, scale=scale))
        np.testing.assert_array_equal(out[:, t], step[:, 0],
                                      err_msg=f"t={t}")


# ---------------------------------------------------------------------------
# fleet level: speculative == plain, token-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,decode_impl", [
    (ATTN_ARCH, "xla"), (ATTN_ARCH, "flash_paged"),
    (MLA_ARCH, "xla"), (MLA_ARCH, "flash_paged"),
])
def test_spec_decode_tokens_match_plain(arch, decode_impl, ref_tokens):
    """An aligned draft (same arch, same init key => identical weights)
    accepts everything; output must STILL be produced by the verify
    path and equal the plain fleet's bitwise."""
    fleet = _mk_fleet(arch, paged=True, decode_impl=decode_impl,
                      speculative=_spec(arch, k=4))
    out = [r["tokens"] for r in fleet.generate(arch, PROMPTS)]
    assert out == ref_tokens(arch)
    sched = fleet.schedulers[arch]
    assert sched.spec_rounds > 0
    assert sched.spec_offered > 0
    assert sched.spec_accepted == sched.spec_offered   # aligned draft
    assert sched.spec_tokens_per_round > 1.0
    assert sched.pool.live_refs() == 0


@pytest.mark.parametrize("decode_impl", ["xla", "flash_paged"])
def test_spec_deep_target_token_exact(decode_impl):
    """A six-layer target verifies the proposals of its two-layer draft:
    the verify step and the fused draft scan each carry their own pools
    through their layer loops, and the output equals the plain six-layer
    fleet's bitwise."""
    deep = {ATTN_ARCH: {"depth_mult": 3}}
    plain = _mk_fleet(ATTN_ARCH, paged=True, decode_impl=decode_impl,
                      arch_overrides=deep)
    want = [r["tokens"] for r in plain.generate(ATTN_ARCH, PROMPTS)]
    fleet = _mk_fleet(ATTN_ARCH, paged=True, decode_impl=decode_impl,
                      speculative=_spec(ATTN_ARCH, k=3), arch_overrides=deep)
    assert [r["tokens"] for r in fleet.generate(ATTN_ARCH, PROMPTS)] == want
    sched = fleet.schedulers[ATTN_ARCH]
    assert sched.spec_rounds > 0
    assert sched.pool.live_refs() == 0


def test_spec_misaligned_draft_token_exact_and_backs_off(ref_tokens):
    """A draft with different weights proposes garbage: adaptive k must
    fall back to plain decode after the opening probe rounds, and the
    output stays token-exact regardless."""
    fleet = _mk_fleet(ATTN_ARCH, paged=True,
                      speculative=_spec(MISALIGNED_DRAFT, k=4))
    out = [r["tokens"] for r in fleet.generate(ATTN_ARCH, PROMPTS)]
    assert out == ref_tokens(ATTN_ARCH)
    sched = fleet.schedulers[ATTN_ARCH]
    assert sched.spec_rounds >= 1                      # it did try
    assert sched.spec_accepted < sched.spec_offered    # and got rejected
    # backed off: far fewer wide rounds than engine decode rounds
    assert sched.spec_rounds < sched.decode_steps
    assert sched.pool.live_refs() == 0


# ---------------------------------------------------------------------------
# rollback invariants: random acceptance patterns (property-style sweep)
# ---------------------------------------------------------------------------

def test_spec_random_acceptance_patterns_token_exact(ref_tokens):
    """Scripted proposal corruption drives ARBITRARY acceptance patterns
    through the verify/rollback path: every corrupted position forces a
    rejection there (aligned draft => uncorrupted proposals are exactly
    the target's outputs).  Output must be token-exact and the pool
    refcount-clean for every pattern."""
    fleet = _mk_fleet(ATTN_ARCH, paged=True,
                      speculative=_spec(ATTN_ARCH, k=4, adaptive=False))
    sched = fleet.schedulers[ATTN_ARCH]
    dw = sched.drafter
    orig = dw.propose
    ref = ref_tokens(ATTN_ARCH)
    try:
        for seed in range(6):
            rnd = random.Random(seed)

            def corrupt(live, W, _rnd=rnd):
                props = orig(live, W).copy()
                for i in live:
                    for j in range(W - 1):
                        if _rnd.random() < 0.45:
                            props[i, j] = (int(props[i, j]) + 1) % VOCAB
                return props

            dw.propose = corrupt
            out = [r["tokens"] for r in fleet.generate(ATTN_ARCH, PROMPTS)]
            assert out == ref, f"seed={seed}"
            assert sched.pool.live_refs() == 0, f"seed={seed}"
        assert 0 < sched.spec_accepted < sched.spec_offered
    finally:
        dw.propose = orig


def test_spec_preempt_mid_speculation_park_resume_exact():
    """A hi-prio arrival parks a row BETWEEN speculative rounds (its
    pending-token KV may already be written by a verify): the resumed
    row must finish bitwise-identical to an uninterrupted run and the
    pool must end refcount-clean."""
    plain = _mk_fleet(ATTN_ARCH, paged=True, max_seq=64, kv_blocks=8)
    # k=2: wide rounds emit at most 3 tokens, so the lo rows are still
    # mid-speculation (not finished) when the hi-prio arrival lands
    spec = _mk_fleet(ATTN_ARCH, paged=True, max_seq=64, kv_blocks=8,
                     speculative=_spec(ATTN_ARCH, k=2))
    ids = {"lo1": np.arange(4, 44, dtype=np.int32),
           "lo2": np.arange(50, 90, dtype=np.int32),
           "hi": np.arange(100, 157, dtype=np.int32)}
    ref = {}
    for name, arr in ids.items():
        rid = plain.schedulers[ATTN_ARCH].submit(arr.copy(), max_new=6)
        ref[name] = list({s.rid: s for s in
                          plain.schedulers[ATTN_ARCH].drain()}[rid].out)

    sched = spec.schedulers[ATTN_ARCH]
    rids = {name: sched.submit(ids[name].copy(), max_new=6,
                               priority=10 if name == "hi" else 0)
            for name in ("lo1", "lo2")}
    sched.step()                           # both admitted, speculating
    assert sched.spec_rounds >= 1
    rids["hi"] = sched.submit(ids["hi"].copy(), max_new=6, priority=10)
    sched.step()                           # eviction parks one victim
    assert sched.preempted == 1
    done = {s.rid: s for s in sched.drain()}
    done.update({s.rid: s for s in (sched.result(r) for r in rids.values())
                 if s is not None})
    for name, rid in rids.items():
        assert list(done[rid].out) == ref[name], name
    assert sum(s.parks > 0 for s in done.values()) == 1
    assert sched.pool.live_refs() == 0


def test_spec_adaptive_backoff_then_probe_recovery(ref_tokens):
    """Always-rejected proposals collapse the acceptance EWMA below
    ``min_accept`` => plain decode except probe rounds, whose cadence
    backs off exponentially (cap 8x probe_every) while every probe
    keeps failing.  Restoring the (aligned) draft lets a probe round —
    due within 8*probe_every rounds — lift the EWMA back over the
    threshold and speculation resumes.  Token-exact in both regimes."""
    fleet = _mk_fleet(ATTN_ARCH, paged=True,
                      speculative=_spec(ATTN_ARCH, k=4, adaptive=True,
                                        probe_every=4))
    sched = fleet.schedulers[ATTN_ARCH]
    dw = sched.drafter
    orig = dw.propose

    def reject_all(live, W):
        props = orig(live, W).copy()
        return (props + 1) % VOCAB

    ref = ref_tokens(ATTN_ARCH)
    try:
        dw.propose = reject_all
        out = [r["tokens"] for r in fleet.generate(ATTN_ARCH, PROMPTS)]
        assert out == ref
        used = [i for i in range(sched.slots) if dw.ewma[i] < 1.0]
        assert used and all(dw.ewma[i] < dw.spec.min_accept for i in used)
        assert sched.spec_rounds < sched.decode_steps      # backed off
        assert dw.probe_scale > 1                          # cadence backed off
    finally:
        dw.propose = orig
    rounds0, accepted0 = sched.spec_rounds, sched.spec_accepted
    # the next probe may be up to 8*probe_every rounds out; keep decoding
    # (token-exact throughout) until it fires and recovers the lane
    for _ in range(6):
        out = [r["tokens"] for r in fleet.generate(ATTN_ARCH, PROMPTS)]
        assert out == ref
        if sched.spec_accepted > accepted0:
            break
    assert sched.spec_accepted > accepted0                 # probes re-enabled
    assert sched.spec_rounds > rounds0
    assert dw.probe_scale == 1                             # cadence snapped back
    assert any(dw.ewma[i] >= dw.spec.min_accept
               for i in range(sched.slots))
    assert sched.pool.live_refs() == 0


# ---------------------------------------------------------------------------
# satellite: construction-time validation
# ---------------------------------------------------------------------------

def test_decode_impl_validated_at_construction():
    from repro.serving.fleet import LocalFleet
    with pytest.raises(ValueError, match=r"flash_paged"):
        LocalFleet([ATTN_ARCH], reduced=True, paged=True,
                   decode_impl="flashy_paged")
    # the paged Pallas kernels run on one device: no model-axis sharding
    with pytest.raises(ValueError, match=r"model_axis=1"):
        LocalFleet([ATTN_ARCH], reduced=True, paged=True,
                   decode_impl="flash_paged", model_axis=2)


def test_speculative_validated_at_construction():
    from repro.serving.fleet import LocalFleet
    mk = lambda **kw: LocalFleet([ATTN_ARCH], reduced=True, paged=True,
                                 **kw)                      # noqa: E731
    with pytest.raises(ValueError, match="SpecConfig"):
        mk(speculative={"draft_arch": ATTN_ARCH})
    with pytest.raises(ValueError, match="paged"):
        LocalFleet([ATTN_ARCH], reduced=True, paged=False,
                   speculative=_spec(ATTN_ARCH))
    with pytest.raises(ValueError, match="draft_arch"):
        mk(speculative=_spec("no-such-arch"))
    with pytest.raises(ValueError, match="draft_arch"):
        mk(speculative=_spec("whisper-tiny"))   # audio: not a text draft
    with pytest.raises(ValueError, match="k must be >= 1"):
        mk(speculative=_spec(ATTN_ARCH, k=0))
    with pytest.raises(ValueError, match="probe_every"):
        mk(speculative=_spec(ATTN_ARCH, probe_every=0))
    with pytest.raises(ValueError, match="alpha"):
        mk(speculative=_spec(ATTN_ARCH, alpha=0.0))
    with pytest.raises(ValueError, match="min_accept"):
        mk(speculative=_spec(ATTN_ARCH, min_accept=1.5))


def test_arch_overrides_validated_at_construction():
    from repro.serving.fleet import LocalFleet
    mk = lambda ov: LocalFleet([ATTN_ARCH], reduced=True,
                               arch_overrides=ov)           # noqa: E731
    with pytest.raises(ValueError, match="dict"):
        mk([ATTN_ARCH])
    with pytest.raises(ValueError, match="not a fleet member"):
        mk({"no-such-arch": {"depth_mult": 2}})
    with pytest.raises(ValueError, match="unknown ModelConfig field"):
        mk({ATTN_ARCH: {"layerz": 12}})
    with pytest.raises(ValueError, match="depth_mult"):
        mk({ATTN_ARCH: {"depth_mult": 0}})


def test_arch_overrides_deepen_target_only():
    """``depth_mult`` multiplies the member's layer repeats but leaves
    the speculative draft at its registry depth (that asymmetry is the
    whole point: a cheap draft in front of a deep target)."""
    fleet = _mk_fleet(ATTN_ARCH, speculative=_spec(ATTN_ARCH, k=2),
                      arch_overrides={ATTN_ARCH: {"depth_mult": 3}})
    m = fleet.members[ATTN_ARCH]
    dw = fleet.schedulers[ATTN_ARCH].drafter
    depth = lambda c: sum(g.repeats * len(g.period)
                          for g in c.groups)                # noqa: E731
    assert depth(m.cfg) == 3 * depth(dw.rt.cfg)
    out = fleet.generate(ATTN_ARCH, PROMPTS[:2], max_new=4)
    assert all(len(r["tokens"]) == 4 for r in out)
    assert fleet.schedulers[ATTN_ARCH].pool.live_refs() == 0


# ---------------------------------------------------------------------------
# satellite: DSL GLOBAL speculative
# ---------------------------------------------------------------------------

DSL_SPEC = '''
SIGNAL keyword urgent {{ operator: "any", keywords: ["urgent"] }}
ROUTE r1 {{
  PRIORITY 10
  WHEN keyword("urgent")
  MODEL "smollm"
}}
GLOBAL {{
  default_model: "smollm",
  strategy: "priority",
  speculative: {{ {body} }},
  model_profiles: {{
    "smollm": {{ cost_per_mtok: 0.05, quality: 0.4, arch: "smollm-360m" }}
  }}
}}
'''


def test_dsl_speculative_round_trip():
    from repro.core.dsl import compile_source
    from repro.core.dsl.decompiler import decompile
    src = DSL_SPEC.format(
        body='draft_model: "smollm", k: 8, adaptive: false, probe_every: 32')
    cfg, diags = compile_source(src)
    assert not diags, diags
    sp = cfg.speculative
    assert (sp.draft_model, sp.k, sp.adaptive, sp.probe_every) == \
        ("smollm", 8, False, 32)
    cfg2, diags2 = compile_source(decompile(cfg))
    assert not diags2, diags2
    assert cfg2.speculative == cfg.speculative
    # defaults are elided on the way out but survive the round trip
    cfg3, _ = compile_source(DSL_SPEC.format(body='draft_model: "smollm"'))
    cfg4, _ = compile_source(decompile(cfg3))
    assert cfg4.speculative == cfg3.speculative
    assert cfg4.speculative.k == 4 and cfg4.speculative.adaptive


def test_dsl_speculative_diagnostics():
    from repro.core.dsl import compile_source
    _, diags = compile_source(DSL_SPEC.format(body='kk: 8, k: 0'))
    msgs = [str(d) for d in diags]
    assert any("unknown key 'kk'" in m and "'k'" in m for m in msgs), msgs
    assert any("draft_model is required" in m for m in msgs), msgs
    assert any("k 0 must be >= 1" in m for m in msgs), msgs
    # a well-formed block is diagnostic-free
    _, diags = compile_source(
        DSL_SPEC.format(body='draft_model: "smollm", k: 2'))
    assert not diags, diags


# ---------------------------------------------------------------------------
# satellite: overload probe surfaces speculative health
# ---------------------------------------------------------------------------

def test_overload_probe_reports_spec_health():
    from repro.serving.overload import fleet_probe
    fleet = _mk_fleet(ATTN_ARCH, paged=True,
                      speculative=_spec(ATTN_ARCH, k=4))
    probe = fleet_probe(fleet)
    assert probe().spec_tokens_per_step == 0.0     # nothing decoded yet
    fleet.generate(ATTN_ARCH, PROMPTS)
    load = probe()
    assert load.spec_accept_ewma > 0.9             # aligned draft
    assert load.spec_tokens_per_step > 1.0         # beats plain decode
    merged = probe()
    merged.merge(load)
    assert merged.spec_tokens_per_step == load.spec_tokens_per_step
