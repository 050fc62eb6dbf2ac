"""Continuous-batching scheduler + async front-end + decode-correctness
bugfix tests: slot admission/eviction, per-row positions, padded-vs-exact
decode equivalence, overflow queueing, arrival-window coalescing, compile
warmup, and the EmbeddingPlan pending-dedupe fix."""

import threading
import time

import numpy as np
import pytest

ARCH = "smollm-360m"


@pytest.fixture(scope="module")
def fleet():
    from repro.serving.fleet import LocalFleet
    return LocalFleet([ARCH], reduced=True, batch=3, gen_tokens=6)


# ---------------------------------------------------------------------------
# scheduler mechanics
# ---------------------------------------------------------------------------

def test_slot_admission_eviction_and_per_row_positions(fleet):
    """More prompts than slots: the first wave fills every slot, the
    overflow prompt waits in the queue and is admitted into a freed slot;
    per-slot positions advance only for live rows."""
    sched = fleet.schedulers[ARCH]
    m = fleet.members[ARCH]
    prompts = ["one two three", "a much longer prompt with many words here",
               "short", "late arrival prompt"]
    rids = fleet._submit(ARCH, prompts)
    assert len(sched.queue) == 4

    done = sched.step()                       # admit 3, first decode step
    assert not done
    assert sum(s is not None for s in sched.active) == 3
    assert len(sched.queue) == 1              # overflow queued, not dropped
    # per-row positions: each admitted row sits at its own prompt depth,
    # +1 after the first shared decode step (one hash token per word)
    for slot, want in zip(range(3), [3, 8, 1]):
        assert sched.pos[slot] == want + 1, (slot, sched.pos)
    assert all(len(sched.active[s].out) == 2 for s in range(3))

    seqs = fleet._drain({ARCH: rids})       # keyed (arch, rid) across lanes
    assert sorted(r for _, r in seqs) == sorted(rids)
    assert all(len(s.out) == 6 for s in seqs.values())
    # eviction + reuse: the late arrival decoded in a recycled slot
    assert seqs[(ARCH, rids[3])].slot in (0, 1, 2)
    assert all(s is None for s in sched.active)
    assert (sched.pos == 0).all()


def test_overflow_prompts_never_dropped(fleet):
    """BUGFIX: the old generate() silently truncated prompts[:batch];
    now every prompt beyond the slot count is queued and served."""
    n = 2 * fleet.members[ARCH].batch + 1
    outs = fleet.generate(ARCH, [f"overflow prompt number {i}" for i in range(n)])
    assert len(outs) == n
    assert all(len(o["tokens"]) == 6 for o in outs)
    # later prompts waited for slots: ttft is monotone-ish, never absent
    assert all(o["ttft_ms"] > 0 for o in outs)


def test_mixed_length_batch_matches_solo_decode(fleet):
    """BUGFIX (decode equivalence): a short prompt in a mixed-length
    batch produces exactly the tokens it produces alone — rows no longer
    decode from pad tokens or a uniform batch-max position."""
    short = "hi there"
    longer = ("prove the convergence of the geometric series using real "
              "analysis and derive the bound")
    solo = fleet.generate(ARCH, [short])[0]["tokens"]
    mixed = fleet.generate(ARCH, [longer, short, longer + " again"])
    assert mixed[1]["tokens"] == solo
    # and the long row is unaffected by its neighbours too
    solo_long = fleet.generate(ARCH, [longer])[0]["tokens"]
    assert mixed[0]["tokens"] == solo_long


def test_warmup_excludes_compile_from_latency(fleet):
    """BUGFIX: JIT compile happens at construction (warmup), so serving
    ttft_ms reflects step time, not XLA compilation, and latency-aware
    selection is not skewed against the first model used."""
    m = fleet.members[ARCH]
    assert m.warmup_ms > 0
    out = fleet.generate(ARCH, ["a fresh first call after warmup"])[0]
    # compile took hundreds of ms; a warmed step is orders faster
    assert out["ttft_ms"] < m.warmup_ms / 2
    assert out["service_ms"] >= out["ttft_ms"]


def test_transport_reports_per_request_service_time(fleet):
    """The provider payload carries per-request service time so the
    pipeline attributes real per-request latency (not batch wall clock)
    to latency-aware selection."""
    call = fleet.call_fn({"m": ARCH})
    payloads = [{"model": "m", "messages": [{"role": "user",
                                             "content": f"q {i}"}]}
                for i in range(2)]
    outs = call.batch_call(None, payloads, [{}] * 2)
    assert len(outs) == 2
    for o in outs:
        assert o["usage"]["vsr_service_ms"] > 0
        assert o["usage"]["vsr_ttft_ms"] > 0
        assert o["usage"]["completion_tokens"] == 6


@pytest.mark.parametrize("text", ["hello", "the same long prompt " * 12])
def test_routed_request_splits_service_time_into_fleet_spans(fleet, text):
    """A routed request's outcome trace carries the fleet's split of its
    service time under ``upstream``: queue + prefill is the transport's
    TTFT, and + decode its service time.  A long prompt is sent twice, so
    the second admission reuses the first one's cached blocks."""
    from repro.core.dsl import compile_source
    from repro.core.router import SemanticRouter
    from repro.core.types import Message, Request
    cfg, _ = compile_source(
        'GLOBAL { default_model: "m", model_profiles: '
        '{ "m": { arch: "smollm-360m" } } }')
    router = SemanticRouter(cfg, call_fn=fleet.call_fn({"m": ARCH}))
    cached = fleet.lanes[ARCH].sched.cached_tokens
    for _ in range(1 if text == "hello" else 2):
        resp, out = router.route(Request(messages=[Message("user", text)]))
    assert (fleet.lanes[ARCH].sched.cached_tokens > cached) == \
        (text != "hello")
    ms = {s["span"]: s["ms"] for s in out.trace}
    u = resp.usage
    assert ms["fleet:queue"] == u["vsr_queue_ms"] >= 0
    assert ms["fleet:prefill"] == u["vsr_prefill_ms"] > 0
    assert ms["fleet:queue"] + ms["fleet:prefill"] == \
        pytest.approx(u["vsr_ttft_ms"], abs=0.01)
    assert ms["fleet:queue"] + ms["fleet:prefill"] + ms["fleet:decode"] == \
        pytest.approx(u["vsr_service_ms"], abs=0.01)
    names = [s["span"] for s in out.trace]
    i = names.index("upstream")
    assert names[i + 1:i + 4] == ["fleet:queue", "fleet:prefill",
                                  "fleet:decode"]
    router.close()


# ---------------------------------------------------------------------------
# async front-end
# ---------------------------------------------------------------------------

class _StubRouter:
    """Records route_batch() batch sizes; echoes per-request results."""

    def __init__(self, delay_s=0.0):
        self.batches = []
        self.delay_s = delay_s

    def route_batch(self, reqs):
        self.batches.append(len(reqs))
        if self.delay_s:
            time.sleep(self.delay_s)
        return [(f"resp:{r}", f"out:{r}") for r in reqs]


def test_frontend_coalesces_staggered_arrivals():
    """Requests arriving within the window share one route_batch();
    every future resolves to ITS OWN result."""
    from repro.serving.frontend import AsyncFrontend
    router = _StubRouter()
    fe = AsyncFrontend(router, window_ms=80.0, max_batch=32)
    futs = {}
    for i in range(8):
        futs[i] = fe.submit(f"r{i}")
        time.sleep(0.005)                   # staggered but inside window
    for i, f in futs.items():
        assert f.result(timeout=5) == (f"resp:r{i}", f"out:r{i}")
    fe.close()
    assert router.batches, "no batch dispatched"
    assert len(router.batches) < 8          # coalesced
    assert sum(router.batches) == 8         # nothing lost or duplicated


def test_frontend_window_bounds_lone_request_latency():
    from repro.serving.frontend import AsyncFrontend
    router = _StubRouter()
    fe = AsyncFrontend(router, window_ms=30.0)
    t0 = time.perf_counter()
    assert fe.submit("solo").result(timeout=5)[0] == "resp:solo"
    assert time.perf_counter() - t0 < 2.0
    fe.close()
    assert router.batches == [1]


def test_frontend_concurrent_submitters():
    from repro.serving.frontend import AsyncFrontend
    router = _StubRouter(delay_s=0.01)
    fe = AsyncFrontend(router, window_ms=20.0, max_batch=8)
    results = {}

    def worker(i):
        results[i] = fe.submit(f"w{i}").result(timeout=10)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fe.close()
    assert len(results) == 12
    assert all(results[i] == (f"resp:w{i}", f"out:w{i}") for i in range(12))
    assert sum(router.batches) == 12


def test_frontend_close_rejects_new_work():
    from repro.serving.frontend import AsyncFrontend
    fe = AsyncFrontend(_StubRouter(), window_ms=5.0)
    fe.close()
    with pytest.raises(RuntimeError):
        fe.submit("late")


# ---------------------------------------------------------------------------
# EmbeddingPlan pending-dedupe bugfix
# ---------------------------------------------------------------------------

def test_embedding_plan_pending_dedupe_and_clear():
    """BUGFIX: duplicate register() calls must not grow the base call,
    and texts embedded by an early prime() must never be re-sent by a
    later miss-triggered fill."""
    from repro.core.pipeline import EmbeddingPlan
    sent = []

    def base(texts):
        sent.append(list(texts))
        return np.zeros((len(texts), 4), np.float32)

    plan = EmbeddingPlan(base)
    plan.register(["a", "b"])
    plan.register(["a", "b"])               # duplicates: must not re-pend
    assert plan._pending == ["a", "b"]
    plan.prime(["a"])                       # fills a AND pending b, clears
    assert plan.base_calls == 1 and sorted(sent[0]) == ["a", "b"]
    assert plan._pending == []
    plan.embed(["c"])                       # miss: must NOT re-send a or b
    assert plan.base_calls == 2 and sent[1] == ["c"]
    plan.register(["a"])                    # already memoized: no-op
    assert plan._pending == []
    plan.embed(["a"])                       # pure memo hit
    assert plan.base_calls == 2
