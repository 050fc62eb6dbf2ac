"""Disaggregated prefill/decode + block-table flash-decode kernels.

Covers the PR-8 tentpole and its satellites:

* kernel sweeps: random block tables (shared + trash blocks), ragged
  ``kv_len`` including zero-length masked lanes, GQA and MLA variants vs
  the gather-based oracles; on a TPU, both paged kernels (decode W=1,
  verify W=3) compiled equal the XLA path bitwise and the interpreted
  kernels within the chip-vs-host arithmetic gap;
* the non-materialization guarantee: the paged kernels never build the
  ``(B, max_blocks*block_tokens, ...)`` gathered KV tensor;
* fleet-level token-exactness of ``decode_impl="flash_paged"`` against
  the default XLA decode path on attn AND MLA+MoE archs;
* disaggregated admission: chunked prefill interleaves with decode steps
  (in-flight rows keep producing tokens while a long prefill is in
  flight) and chunked == monolithic token-exactness;
* satellite regressions: no park when the pool cannot admit the arrival
  even after eviction; head/tail prompt truncation parity between the
  contiguous and paged prefill paths; the async front-end failing (not
  hanging) unmatched futures on a short router response; the TTFT
  overload probe seeing queued-but-stalled requests.
"""

import functools
import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ATTN_ARCH = "smollm-360m"
MLA_ARCH = "deepseek-v2-236b"


@pytest.fixture(scope="module")
def tpu():
    """The TPU this process drives; asked inside the fixture, never at
    import, so collecting this file touches no accelerator."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        pytest.skip("compiled-mode parity needs a TPU")
    return dev


# ---------------------------------------------------------------------------
# kernel-level sweeps
# ---------------------------------------------------------------------------

def _rand_case(rnd, *, B, nb, max_blocks, blk, zero_rows=True):
    """Random block table with shared and trash blocks + ragged kv_len."""
    tbl = np.zeros((B, max_blocks), np.int32)
    kv_len = np.zeros((B,), np.int32)
    shared = rnd.randrange(1, nb)            # one block many rows share
    for b in range(B):
        if zero_rows and rnd.random() < 0.25:
            kv_len[b] = 0                    # fully-masked lane: all trash
            continue
        live = rnd.randrange(1, max_blocks + 1)
        kv_len[b] = rnd.randrange((live - 1) * blk + 1, live * blk + 1)
        for i in range(live):
            tbl[b, i] = shared if rnd.random() < 0.3 \
                else rnd.randrange(1, nb)
        # dead tail entries deliberately left at 0 (trash)
    return jnp.asarray(tbl), jnp.asarray(kv_len)


def test_paged_flash_decode_kernel_sweep(rng, stacked):
    """Random tables/lengths vs the gather oracle.  Zero-length lanes are
    checked against the kernel's contract (exact zeros) separately — the
    oracle's all-masked softmax degenerates to a uniform average."""
    from repro.kernels.flash_decode import (paged_decode_reference,
                                            paged_flash_verify)
    B, nb, max_blocks, blk, Hq, Hkv, hd = 5, 12, 4, 16, 8, 2, 64
    kpool = jnp.asarray(rng.standard_normal((nb, Hkv, blk, hd)), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((nb, Hkv, blk, hd)), jnp.float32)
    for seed in range(4):
        rnd = random.Random(seed)
        tbl, kv_len = _rand_case(rnd, B=B, nb=nb, max_blocks=max_blocks,
                                 blk=blk)
        q = jnp.asarray(rng.standard_normal((B, Hq, hd)), jnp.float32)
        out = np.asarray(paged_flash_verify(
            q[:, None], stacked(kpool), stacked(vpool), tbl, kv_len,
            stacked.layer))[:, 0]
        ref = np.asarray(paged_decode_reference(q, kpool, vpool, tbl,
                                                kv_len))
        lens = np.asarray(kv_len)
        live = lens > 0
        np.testing.assert_allclose(out[live], ref[live], atol=2e-5,
                                   rtol=2e-5, err_msg=f"seed={seed}")
        assert (out[~live] == 0.0).all(), f"seed={seed}: kv_len==0 lanes"


def test_paged_flash_decode_mla_kernel_sweep(rng, stacked):
    from repro.kernels.flash_decode import (paged_flash_verify_mla,
                                            paged_mla_decode_reference)
    B, nb, max_blocks, blk, H, r, rh = 4, 10, 4, 16, 8, 64, 32
    scale = 1.0 / np.sqrt(96.0)
    ckv = jnp.asarray(rng.standard_normal((nb, blk, r)), jnp.float32)
    kr = jnp.asarray(rng.standard_normal((nb, blk, rh)), jnp.float32)
    for seed in range(4):
        rnd = random.Random(100 + seed)
        tbl, kv_len = _rand_case(rnd, B=B, nb=nb, max_blocks=max_blocks,
                                 blk=blk)
        ql = jnp.asarray(rng.standard_normal((B, H, r)), jnp.float32)
        qr = jnp.asarray(rng.standard_normal((B, H, rh)), jnp.float32)
        out = np.asarray(paged_flash_verify_mla(
            ql[:, None], qr[:, None], stacked(ckv), stacked(kr), tbl, kv_len,
            stacked.layer, scale=scale))[:, 0]
        ref = np.asarray(paged_mla_decode_reference(ql, qr, ckv, kr, tbl,
                                                    kv_len, scale=scale))
        lens = np.asarray(kv_len)
        live = lens > 0
        np.testing.assert_allclose(out[live], ref[live], atol=2e-5,
                                   rtol=2e-5, err_msg=f"seed={seed}")
        assert (out[~live] == 0.0).all(), f"seed={seed}"


SAME_PLATFORM_ATOL = 2 ** -8
CROSS_PLATFORM_ATOL = 2 ** -7


def _xla_gqa(q, kpool, vpool, tbl, kv_len):
    """The XLA serving path of a paged GQA decode/verify step."""
    from repro.kernels.flash_decode import gather_kv
    from repro.models import layers as L
    return L.sdpa(q, gather_kv(kpool, tbl), gather_kv(vpool, tbl),
                  causal=True, q_offset=kv_len - q.shape[1], kv_len=kv_len,
                  sliding_window=0)


def _xla_mla(ql, qr, ckv, kr, tbl, kv_len, *, scale):
    """The XLA serving path's latent context of a paged MLA step."""
    from repro.kernels.flash_decode import gather_kv
    from repro.models import layers as L
    return L.mla_latent_context(ql, qr, gather_kv(ckv, tbl),
                                gather_kv(kr, tbl), scale=scale, causal=True,
                                q_offset=kv_len - ql.shape[1], kv_len=kv_len)


def test_paged_flash_decode_compiled_matches_interpret(rng, tpu, stacked):
    """Both paged kernels at W=1 (decode) and W=3 (verify) on bf16
    inputs, the serving dtype.

    The kernel keeps the XLA serving path's op order, so on each platform
    it must match that path to within ``SAME_PLATFORM_ATOL``: a summation
    order that moves one probability across a bf16 rounding boundary
    flips an output's last bit (2^-9 at |out| < 1: the interpreted GQA
    verify kernel on this input, on CPU); on a TPU v5e 20 random inputs
    per case read 0.  An off-by-one frontier at kv_len 50 moves outputs
    by ~1/50, a wrong page by O(1).  Compiled vs interpreted compares
    XLA:TPU with XLA:CPU arithmetic (exp, reduction order) on top: the
    same 80 inputs differed by at most 2^-7, hence ``CROSS_PLATFORM_ATOL``.
    f32 inputs would measure the chip's default matmul precision instead
    (bf16 operands; 8.4e-3).  The kernels read one layer of stacked
    pools; the XLA path reads that layer's pools."""
    from repro.kernels.flash_decode import (paged_flash_verify,
                                            paged_flash_verify_mla)
    B, nb, max_blocks, blk, Hq, Hkv, hd = 3, 8, 4, 16, 8, 2, 64
    r, rh = 64, 32
    scale = 1.0 / np.sqrt(96.0)
    tbl, kv_len = _rand_case(random.Random(7), B=B, nb=nb,
                             max_blocks=max_blocks, blk=blk)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    kpool, vpool = f(nb, Hkv, blk, hd), f(nb, Hkv, blk, hd)
    ckv, kr = f(nb, blk, r), f(nb, blk, rh)
    cpu = jax.devices("cpu")[0]
    for W in (1, 3):
        lens = jnp.maximum(kv_len, W)        # verify needs W written entries
        qg, ql, qr = f(B, W, Hq, hd), f(B, W, Hq, r), f(B, W, Hq, rh)
        cases = {
            f"gqa W={W}": (paged_flash_verify, _xla_gqa,
                           (qg, stacked(kpool), stacked(vpool)),
                           (qg, kpool, vpool), {}),
            f"mla W={W}": (paged_flash_verify_mla, _xla_mla,
                           (ql, qr, stacked(ckv), stacked(kr)),
                           (ql, qr, ckv, kr), {"scale": scale}),
        }
        for name, (fn, xla, kargs, args, kw) in cases.items():
            kargs = kargs + (tbl, lens, stacked.layer)
            compiled = np.asarray(fn(*jax.device_put(kargs, tpu), **kw),
                                  np.float32)
            interpreted = np.asarray(fn(*jax.device_put(kargs, cpu), **kw),
                                     np.float32)
            on_tpu = jax.device_put(args + (tbl, lens), tpu)
            on_cpu = jax.device_put(args + (tbl, lens), cpu)
            xla_tpu = jax.jit(functools.partial(xla, **kw))(*on_tpu)
            xla_cpu = jax.jit(functools.partial(xla, **kw))(*on_cpu)
            np.testing.assert_allclose(
                compiled, np.asarray(xla_tpu, np.float32),
                atol=SAME_PLATFORM_ATOL, rtol=0, err_msg=f"{name} TPU")
            np.testing.assert_allclose(
                interpreted, np.asarray(xla_cpu, np.float32),
                atol=SAME_PLATFORM_ATOL, rtol=0, err_msg=f"{name} CPU")
            np.testing.assert_allclose(compiled, interpreted,
                                       atol=CROSS_PLATFORM_ATOL, rtol=0,
                                       err_msg=name)


def _gathered_shapes(B, max_blocks, blk):
    """Shapes a gather-based fallback would materialize."""
    S = max_blocks * blk
    return {(B, S), (B * 2, S)}         # (B, S, ...) in any head folding


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield from _walk_eqns(inner)


def test_paged_flash_decode_never_materializes_gathered_kv(rng, stacked):
    """The acceptance assert: no intermediate in the kernel's program has
    the (B, max_blocks*block_tokens, ...) gathered-KV shape — KV moves
    block-by-block through the scalar-prefetched table, never as a
    per-row contiguous copy."""
    from repro.kernels.flash_decode.ops import paged_flash_verify
    B, nb, max_blocks, blk, Hq, Hkv, hd = 3, 10, 4, 16, 8, 2, 64
    q = jnp.zeros((B, 1, Hq, hd), jnp.float32)
    kpool = stacked(jnp.zeros((nb, Hkv, blk, hd), jnp.float32))
    vpool = stacked(jnp.zeros((nb, Hkv, blk, hd), jnp.float32))
    tbl = jnp.zeros((B, max_blocks), jnp.int32)
    kv_len = jnp.zeros((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: paged_flash_verify(*a))(
        q, kpool, vpool, tbl, kv_len, stacked.layer)
    bad = _gathered_shapes(B, max_blocks, blk)
    for eqn in _walk_eqns(jaxpr.jaxpr):
        for var in eqn.outvars:
            shape = getattr(getattr(var, "aval", None), "shape", ())
            assert tuple(shape[:2]) not in bad, \
                f"gathered KV materialized: {eqn.primitive} -> {shape}"


# ---------------------------------------------------------------------------
# fleet-level: flash_paged decode token-exactness
# ---------------------------------------------------------------------------

def _mk_fleet(arch, **kw):
    from repro.serving.fleet import LocalFleet
    kw.setdefault("reduced", True)
    kw.setdefault("batch", 2)
    kw.setdefault("gen_tokens", 6)
    return LocalFleet([arch], **kw)


@pytest.mark.parametrize("arch,depth", [(ATTN_ARCH, 1), (MLA_ARCH, 1),
                                        (ATTN_ARCH, 3)],
                         ids=["attn", "mla", "attn_six_layers"])
def test_flash_paged_decode_tokens_match_xla(arch, depth):
    """decode_impl="flash_paged" produces IDENTICAL tokens to the default
    XLA paged decode (which test_prefix_paged pins against the contiguous
    cache) — on the GQA arch and the MLA+MoE arch, and with the GQA
    arch's layers tripled (the kernel reads each of six layers' pools)."""
    deep = {arch: {"depth_mult": depth}}
    base = _mk_fleet(arch, paged=True, warmup=False, arch_overrides=deep)
    flash = _mk_fleet(arch, paged=True, decode_impl="flash_paged",
                      warmup=False, arch_overrides=deep)
    shared = " ".join(f"sys{i}" for i in range(20))
    prompts = [shared + " question one", "a lone unshared prompt",
               shared + " question two with a longer tail of words",
               "tiny"]
    a = base.generate(arch, prompts)
    b = flash.generate(arch, prompts)
    for i, (x, y) in enumerate(zip(a, b)):
        assert x["tokens"] == y["tokens"], (i, prompts[i])
    assert len(a) == len(prompts)


# ---------------------------------------------------------------------------
# disaggregated prefill/decode e2e
# ---------------------------------------------------------------------------

def test_decode_proceeds_while_long_prefill_in_flight():
    """The tentpole behavior: with chunked prefill and a budget of one
    chunk per step, an in-flight decode row keeps producing tokens on
    every step a long prompt's prefill is still incomplete — admission no
    longer stalls the decode batch for the whole prefill.  The chunked
    prompt's tokens equal the monolithic path's (dropless MoE + suffix
    program make chunking token-exact)."""
    mono = _mk_fleet(ATTN_ARCH, paged=True, gen_tokens=12, warmup=False)
    fleet = _mk_fleet(ATTN_ARCH, paged=True, gen_tokens=12, warmup=False,
                      prefill_chunk=16, prefill_budget=1)
    arch = ATTN_ARCH
    sched = fleet.schedulers[arch]
    lane = fleet.lanes[arch]
    long_prompt = " ".join(f"w{i}" for i in range(56))
    ref_tokens = mono.generate(arch, [long_prompt])[0]["tokens"]

    short_rid = lane.submit("short seed prompt", max_new=12)
    sched.step()                         # idle admission: short is decoding
    short = next(s for s in sched.active if s is not None)
    assert short.rid == short_rid and len(short.out) == 2

    long_rid = lane.submit(long_prompt, max_new=12)
    pre_calls = sched.prefill.prefills
    inflight_decode_steps = 0
    for _ in range(64):
        before = len(short.out)
        sched.step()
        if sched.prefill.current is not None and len(short.out) > before:
            inflight_decode_steps += 1   # decode advanced mid-prefill
        if any(s is not None and s.rid == long_rid for s in sched.active):
            break
    else:
        pytest.fail("long prompt never admitted")
    # 56 prompt tokens in 16-token chunks = 4 prefill calls, and the short
    # row decoded through at least the 3 steps where a chunk was pending
    assert sched.prefill.prefills - pre_calls == 4
    assert inflight_decode_steps >= 3
    done = {s.rid: s for s in sched.drain()}
    done.update({s.rid: s
                 for s in (sched.result(short_rid), sched.result(long_rid))
                 if s is not None})
    assert list(done[long_rid].out) == ref_tokens
    assert len(done[short_rid].out) == 12


# ---------------------------------------------------------------------------
# satellite 1: no park when eviction cannot make the admission fit
# ---------------------------------------------------------------------------

def test_no_park_when_pool_cannot_fit_arrival_even_after_eviction():
    """A hi-prio arrival that needs more blocks than free + the victim's
    releasable blocks must NOT park the victim (it would lose decode
    progress for nothing).  Pool: 6 usable blocks, two live rows of 3 —
    the arrival needs 4, eviction frees at most 3."""
    fleet = _mk_fleet(ATTN_ARCH, paged=True, max_seq=64, kv_blocks=7,
                      warmup=False)
    sched = fleet.schedulers[ATTN_ARCH]
    sched.submit(np.arange(4, 44, dtype=np.int32), max_new=6)
    sched.submit(np.arange(50, 90, dtype=np.int32), max_new=6)
    sched.step()                          # both admitted: 3 blocks each
    assert sum(s is not None for s in sched.active) == 2
    assert sched.pool.free_blocks == 0
    sched.submit(np.arange(100, 157, dtype=np.int32), max_new=6,
                 priority=10)             # needs 4 blocks: can never fit now
    outs_before = [len(s.out) for s in sched.active]
    for _ in range(2):
        sched.step()
    # the regression: the old admission parked the victim FIRST, then
    # failed the prefill — progress lost for nothing
    assert sched.preempted == 0
    assert all(s is not None and len(s.out) > o
               for s, o in zip(sched.active, outs_before))
    assert len(sched.queue) == 1          # hi-prio arrival still waiting
    done = {s.rid: s for s in sched.drain()}
    assert all(len(s.out) == 6 for s in done.values())
    assert sched.pool.live_refs() == 0


def test_park_fires_when_eviction_does_make_arrival_fit():
    """Same geometry with one more block: free(1) + releasable(3) covers
    the arrival's 4, so the victim IS parked and the arrival admitted
    promptly, finishing before the victim resumes."""
    fleet = _mk_fleet(ATTN_ARCH, paged=True, max_seq=64, kv_blocks=8,
                      warmup=False)
    sched = fleet.schedulers[ATTN_ARCH]
    lo1 = sched.submit(np.arange(4, 44, dtype=np.int32), max_new=6)
    lo2 = sched.submit(np.arange(50, 90, dtype=np.int32), max_new=6)
    sched.step()
    assert sched.pool.free_blocks == 1
    hi = sched.submit(np.arange(100, 157, dtype=np.int32), max_new=6,
                      priority=10)
    sched.step()
    assert sched.preempted == 1
    assert any(s is not None and s.rid == hi for s in sched.active)
    done = {s.rid: s for s in sched.drain()}
    assert all(len(s.out) == 6 for s in done.values())
    parked = [s for s in done.values() if s.parks > 0]
    assert len(parked) == 1 and parked[0].rid in (lo1, lo2)
    assert done[hi].t_done < parked[0].t_done
    assert sched.pool.live_refs() == 0


# ---------------------------------------------------------------------------
# satellite 2: over-long prompts keep the tail on BOTH cache layouts
# ---------------------------------------------------------------------------

def test_overlong_prompt_truncation_paged_matches_contiguous():
    """BUGFIX: the contiguous admission kept the HEAD of an over-long
    prompt while the paged admission kept the TAIL — same request, two
    different effective prompts.  Both now keep the tail (the newest
    context), so tokens match across layouts at n > prompt_cap."""
    contig = _mk_fleet(ATTN_ARCH, paged=False, max_seq=64, warmup=False)
    paged = _mk_fleet(ATTN_ARCH, paged=True, max_seq=64, warmup=False)
    cap = contig.members[ATTN_ARCH].prompt_cap
    ids = np.asarray([4 + (i * 37) % 500 for i in range(cap + 30)],
                     np.int32)
    assert len(ids) > cap
    rid_c = contig.schedulers[ATTN_ARCH].submit(ids.copy(), max_new=6)
    rid_p = paged.schedulers[ATTN_ARCH].submit(ids.copy(), max_new=6)
    out_c = {s.rid: s for s in contig.schedulers[ATTN_ARCH].drain()}
    out_p = {s.rid: s for s in paged.schedulers[ATTN_ARCH].drain()}
    assert list(out_c[rid_c].out) == list(out_p[rid_p].out)
    # and the effective prompt is the TAIL
    np.testing.assert_array_equal(out_c[rid_c].ids, ids[-cap:])
    np.testing.assert_array_equal(out_p[rid_p].ids, ids[-cap:])


# ---------------------------------------------------------------------------
# satellite 3: short route_batch response fails futures instead of hanging
# ---------------------------------------------------------------------------

class _ShortRouter:
    """Returns one fewer response than requests (a buggy/lossy router)."""

    def route_batch(self, reqs):
        return [(f"resp:{r}", f"out:{r}") for r in reqs[:-1]]


def test_frontend_short_router_response_fails_unmatched_futures():
    """BUGFIX: zip() silently dropped the unmatched futures — callers
    blocked forever.  Matched futures still deliver; unmatched ones get a
    RuntimeError promptly."""
    from repro.serving.frontend import AsyncFrontend
    fe = AsyncFrontend(_ShortRouter(), window_ms=60.0, max_batch=8)
    futs = [fe.submit(f"r{i}") for i in range(3)]
    assert futs[0].result(timeout=5) == ("resp:r0", "out:r0")
    assert futs[1].result(timeout=5) == ("resp:r1", "out:r1")
    with pytest.raises(RuntimeError, match="2 responses for 3 requests"):
        futs[2].result(timeout=5)
    fe.close()


# ---------------------------------------------------------------------------
# satellite 4: overload probe sees stalled (unserved) requests
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_fleet():
    return _mk_fleet(ATTN_ARCH, paged=True, max_seq=64, warmup=False)


def test_ttft_ewma_not_reset_by_zero_sample(small_fleet):
    """BUGFIX: the ``== 0.0`` sentinel treated a genuinely-zero EWMA as
    "no data", so the next sample overwrote the average instead of
    blending."""
    sched = small_fleet.schedulers[ATTN_ARCH]
    sched.ttft_ewma, sched.ttft_samples = 0.0, 0
    sched._note_ttft(0.0)                 # genuinely-zero first sample
    assert sched.ttft_ewma == 0.0 and sched.ttft_samples == 1
    sched._note_ttft(100.0)
    assert sched.ttft_samples == 2
    assert sched.ttft_ewma == pytest.approx(20.0)   # blended, not reset


def test_overload_probe_sees_queued_stall_before_first_token(small_fleet):
    """BUGFIX: ``ttft_ewma`` only updated when a request produced its
    first token, so a stalled lane kept reporting the old optimistic
    TTFT.  The probe now floors it by the oldest waiting request's age
    and counts prefilling/ready requests in queue depth."""
    from repro.serving.overload import fleet_probe
    sched = small_fleet.schedulers[ATTN_ARCH]
    sched.ttft_ewma, sched.ttft_samples = 1.0, 1    # served fast so far
    probe = fleet_probe(small_fleet)
    sched.submit(np.arange(4, 20, dtype=np.int32), max_new=2)
    time.sleep(0.05)                      # request ages without any step
    load = probe()
    assert load.queue_depth >= 1
    assert load.ttft_ewma_ms >= 40.0, load.ttft_ewma_ms
    sched.drain()                         # serve it: probe relaxes again
    assert probe().queue_depth == 0
