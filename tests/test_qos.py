"""SLO-aware QoS control plane: DSL SLO/overload round-trip, admission
control (shed / degrade / premium pass), priority admission + preemption
with token-exact park/resume on attn and MLA+MoE archs, BlockPool leak
checks, the frontend queue bound, overload detector state machine,
fleet autoscaler hook, and the legacy FIFO byte-compat guarantee."""

import pytest

from repro.core.observability import METRICS
from repro.core.types import (Message, OverloadPolicy, Request,
                              RouterOverloadError)

ATTN_ARCH = "smollm-360m"
MLA_ARCH = "deepseek-v2-236b"

QOS_DSL = """
SIGNAL keyword urgent { keywords: ["urgent"] }

ROUTE premium (description = "interactive tier") {
  PRIORITY 10
  WHEN keyword("urgent")
  MODEL "large"
  SLO { class: "premium", priority: 100, ttft_ms: 500.0 }
}

ROUTE bulk (description = "degradable tier") {
  PRIORITY 1
  WHEN keyword("urgent")
  MODEL "large"
  SLO { class: "batch", degrade_to: "small" }
}

BACKEND ep0 vllm { address: "127.0.0.1", port: 8000,
                   models: ["large", "small"] }

GLOBAL { default_model: "small",
         overload: { queue_depth: 4, shed_below: 100,
                     retry_after_s: 0.5, default_class: "best_effort" } }
"""


def _req(text, **md):
    return Request(messages=[Message("user", text)], metadata=md)


def _counter(prefix):
    return sum(v for k, v in METRICS.counters.items()
               if k.split("{")[0] == prefix)


class _ForcedDetector:
    """Detector stand-in pinned to one state; records sample calls."""

    def __init__(self, state):
        self.state = state
        self.samples = 0

    def sample(self, policy=None, force=False):
        self.samples += 1
        return self.state


# ---------------------------------------------------------------------------
# DSL: SLO blocks + GLOBAL overload round-trip
# ---------------------------------------------------------------------------

def test_slo_dsl_round_trip():
    from repro.core.dsl.compiler import compile_source
    from repro.core.dsl.decompiler import decompile
    cfg, diags = compile_source(QOS_DSL)
    assert not [d for d in diags if d.level <= 2]
    prem = next(d for d in cfg.decisions if d.name == "premium")
    assert prem.slo.cls == "premium" and prem.slo.priority == 100
    assert prem.slo.ttft_ms == 500.0
    bulk = next(d for d in cfg.decisions if d.name == "bulk")
    assert bulk.slo.degrade_to == "small"
    assert cfg.overload.queue_depth == 4
    assert cfg.overload.shed_below == 100
    assert cfg.overload.retry_after_s == 0.5
    assert cfg.overload.default_class == "best_effort"

    cfg2, diags2 = compile_source(decompile(cfg))
    assert not [d for d in diags2 if d.level <= 2]
    assert cfg2.overload == cfg.overload
    for d1, d2 in zip(cfg.decisions, cfg2.decisions):
        assert d1.slo == d2.slo, d1.name


def test_slo_defaults_fixed_point():
    """An all-defaults ``SLO {}`` / ``overload: {}`` survives the
    decompile → recompile round trip as defaults."""
    from repro.core.dsl.compiler import compile_source
    from repro.core.dsl.decompiler import decompile
    from repro.core.types import SLOSpec
    src = """
ROUTE r { WHEN keyword("k") MODEL "m" SLO {} }
SIGNAL keyword k { keywords: ["x"] }
GLOBAL { default_model: "m", overload: {} }
"""
    cfg, _ = compile_source(src)
    assert cfg.decisions[0].slo == SLOSpec()
    assert cfg.overload == OverloadPolicy()
    cfg2, _ = compile_source(decompile(cfg))
    assert cfg2.decisions[0].slo == SLOSpec()
    assert cfg2.overload == OverloadPolicy()


def test_legacy_config_decompiles_without_slo():
    from repro.core.dsl.compiler import compile_source
    from repro.core.dsl.decompiler import decompile
    src = """
SIGNAL keyword k { keywords: ["x"] }
ROUTE r { WHEN keyword("k") MODEL "m" }
GLOBAL { default_model: "m" }
"""
    cfg, _ = compile_source(src)
    assert cfg.overload is None and cfg.decisions[0].slo is None
    text = decompile(cfg)
    assert "SLO" not in text and "overload" not in text


def test_validate_flags_bad_slo_and_overload_keys():
    from repro.core.dsl.compiler import compile_source
    src = """
SIGNAL keyword k { keywords: ["x"] }
ROUTE r { WHEN keyword("k") MODEL "m"
          SLO { clazz: "premium", priority: -3, ttft_ms: -1.0 } }
GLOBAL { default_model: "m",
         overload: { queue_dept: 9, slot_occupancy: 1.5 } }
"""
    _, diags = compile_source(src, strict=False)
    msgs = " | ".join(d.message for d in diags)
    assert "clazz" in msgs            # unknown SLO key (with quickfix)
    assert "priority" in msgs         # negative priority
    assert "ttft_ms" in msgs
    assert "queue_dept" in msgs       # unknown overload key
    assert "slot_occupancy" in msgs   # out of [0, 1]


def test_request_slo_resolution():
    from repro.core.dsl.compiler import compile_source
    from repro.core.program import RouterProgram
    cfg, _ = compile_source(QOS_DSL)
    prog = RouterProgram(cfg)
    assert prog.has_slo
    assert prog.request_slo(_req("x", slo="premium")).priority == 100
    assert prog.request_slo(
        Request(messages=[Message("user", "x")],
                headers={"X-VSR-SLO": "batch"})).degrade_to == "small"
    # unknown class name still yields a spec carrying that class
    assert prog.request_slo(_req("x", slo="mystery")).cls == "mystery"
    # no markers at all -> the policy's default class
    assert prog.request_slo(_req("x")).cls == "best_effort"


# ---------------------------------------------------------------------------
# admission control (pre-signal shed / degrade)
# ---------------------------------------------------------------------------

def _qos_router(state):
    from repro.core.dsl.compiler import compile_source
    from repro.core.router import SemanticRouter
    cfg, _ = compile_source(QOS_DSL)
    r = SemanticRouter(cfg)
    r.overload = _ForcedDetector(state)
    return r


def test_admission_sheds_best_effort_at_overload():
    r = _qos_router("overload")
    shed0 = _counter("admission_rejected_total")
    with pytest.raises(RouterOverloadError) as ei:
        r.route(_req("hello there"))          # default class: best_effort
    assert ei.value.retry_after_s == 0.5
    assert ei.value.slo_class == "best_effort"
    assert _counter("admission_rejected_total") == shed0 + 1

    # batch path returns a per-request error response instead of raising
    resp, out = r.route_batch([_req("hello there")])[0]
    assert resp.headers["x-vsr-error"] == "overload"
    assert resp.headers["retry-after"] == "0.5"
    assert resp.headers["x-vsr-slo"] == "best_effort"
    assert out.model == ""


def test_admission_degrades_batch_class_and_passes_premium():
    r = _qos_router("overload")
    deg0 = _counter("admission_degraded_total")
    resp, out = r.route(_req("urgent bulk job", slo="batch"))
    assert out.model == "small"               # degraded off the premium pick
    assert resp.headers["x-vsr-degraded"] == "small"
    assert _counter("admission_degraded_total") == deg0 + 1
    # degraded rows skip signal extraction entirely
    assert not out.signals.matches

    resp, out = r.route(_req("urgent question", slo="premium"))
    assert out.decision == "premium" and out.model == "large"
    assert "x-vsr-degraded" not in resp.headers


def test_admission_busy_degrades_but_never_sheds():
    r = _qos_router("busy")
    _, out = r.route(_req("urgent bulk job", slo="batch"))
    assert out.model == "small"
    # shed-only class passes at busy — shedding needs full overload
    resp, out = r.route(_req("plain question"))
    assert resp.headers.get("x-vsr-error") is None
    assert out.model == "small"               # default model, served


def test_mixed_batch_rows_stay_aligned():
    """Shed + degraded + premium in ONE batch: every response lands on
    its own request (DecisionPlan row alignment with short rows)."""
    r = _qos_router("overload")
    pairs = r.route_batch([
        _req("plain question one"),                    # shed
        _req("urgent question", slo="premium"),        # served premium
        _req("urgent bulk job", slo="batch"),          # degraded
        _req("plain question two"),                    # shed
    ])
    assert pairs[0][0].headers.get("x-vsr-error") == "overload"
    assert pairs[1][1].decision == "premium"
    assert pairs[2][0].headers.get("x-vsr-degraded") == "small"
    assert pairs[3][0].headers.get("x-vsr-error") == "overload"


def test_legacy_policy_is_untouched_by_detector():
    """A policy with NO SLO config behaves identically with a detector
    screaming overload: nothing shed, nothing degraded, detector never
    even sampled (spy), no QoS metadata written."""
    from repro.core.dsl.compiler import compile_source
    from repro.core.router import SemanticRouter
    src = """
SIGNAL keyword k { keywords: ["urgent"] }
ROUTE r { WHEN keyword("k") MODEL "m" }
BACKEND ep0 vllm { address: "127.0.0.1", port: 8000, models: ["m"] }
GLOBAL { default_model: "m" }
"""
    cfg, _ = compile_source(src)
    r = SemanticRouter(cfg)
    det = _ForcedDetector("overload")
    r.overload = det
    shed0 = _counter("admission_rejected_total")
    deg0 = _counter("admission_degraded_total")
    req = _req("urgent request")
    resp, out = r.route(req)
    assert out.decision == "r" and out.model == "m"
    assert det.samples == 0                   # admission never consulted it
    assert "x-vsr-error" not in resp.headers
    assert "slo_priority" not in req.metadata
    assert _counter("admission_rejected_total") == shed0
    assert _counter("admission_degraded_total") == deg0


def test_provider_payload_carries_qos_fields():
    from repro.core.providers import to_provider_payload
    from repro.core.types import Endpoint
    ep = Endpoint("ep0", "vllm")
    plain = to_provider_payload(_req("x"), ep, "m")
    assert "vsr_priority" not in plain        # legacy payloads unchanged
    qos = to_provider_payload(
        _req("x", slo_priority=100, slo_class="premium"), ep, "m")
    assert qos["vsr_priority"] == 100 and qos["vsr_slo"] == "premium"


# ---------------------------------------------------------------------------
# scheduler: priority admission + preemption park/resume
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def attn_fleet():
    from repro.serving.fleet import LocalFleet
    return LocalFleet([ATTN_ARCH], reduced=True, paged=True, batch=3,
                      gen_tokens=6)


@pytest.fixture(scope="module")
def mla_fleet():
    from repro.serving.fleet import LocalFleet
    return LocalFleet([MLA_ARCH], reduced=True, paged=True, batch=3,
                      gen_tokens=6)


def test_priority_queue_order_and_fifo_within_class(attn_fleet):
    sched = attn_fleet.schedulers[ATTN_ARCH]
    lane = attn_fleet.lanes[ATTN_ARCH]
    rids = [lane.submit(f"prompt number {i}", priority=p)
            for i, p in enumerate([0, 5, 0, 10, 5])]
    got = [(s.priority, s.rid) for s in sched.queue]
    # descending priority; FIFO among equals (5@idx1 before 5@idx4)
    assert got == [(10, rids[3]), (5, rids[1]), (5, rids[4]),
                   (0, rids[0]), (0, rids[2])]
    while sched.pending:
        lane.step()
    assert sched.pool.live_refs() == 0


def test_all_zero_priorities_keep_fifo_and_never_preempt(attn_fleet):
    sched = attn_fleet.schedulers[ATTN_ARCH]
    lane = attn_fleet.lanes[ATTN_ARCH]
    pre0 = sched.preempted
    rids = [lane.submit(f"legacy request {i}") for i in range(6)]
    assert [s.rid for s in sched.queue] == rids     # submission order
    while sched.pending:
        lane.step()
    assert sched.preempted == pre0


def _preempt_roundtrip(fleet, arch):
    """Fill slots with low-priority rows, land a VIP, assert the parked
    victim resumes token-exactly vs an uninterrupted reference and the
    pool leaks nothing."""
    lane = fleet.lanes[arch]
    sched = fleet.schedulers[arch]
    victims = [f"background analysis over corpus {i} with clauses {i}"
               for i in range(3)]
    ref = [o["tokens"] for o in fleet.generate(arch, victims, max_new=6)]

    pre0, parks0 = sched.preempted, _counter("preemptions_total")
    rids = [lane.submit(p, max_new=6, priority=0, slo="batch")
            for p in victims]
    lane.step()                      # victims decode a couple of tokens
    lane.step()
    begun = {s.rid: s.t_begin for s in sched.active if s is not None}
    hi = lane.submit("urgent vip request", max_new=2, priority=100,
                     slo="premium")
    finished = {}
    while sched.pending:
        for seq in lane.step():
            finished[seq.rid] = seq
    assert sched.preempted == pre0 + 1
    assert _counter("preemptions_total") == parks0 + 1
    victim = next(s for s in finished.values() if s.parks > 0)
    assert victim.priority == 0
    # a resume keeps the time its first admission left the queue
    assert 0 < victim.t_begin == begun[victim.rid] <= victim.t_first
    assert finished[hi].out          # VIP actually produced tokens
    for rid, want in zip(rids, ref):
        assert list(finished[rid].out) == want, \
            f"park/resume diverged on {arch} rid={rid}"
    assert sched.pool.live_refs() == 0, "BlockPool leaked references"


def test_preemption_token_exact_attn(attn_fleet):
    _preempt_roundtrip(attn_fleet, ATTN_ARCH)


def test_preemption_token_exact_mla_moe(mla_fleet):
    _preempt_roundtrip(mla_fleet, MLA_ARCH)


# ---------------------------------------------------------------------------
# frontend queue bound
# ---------------------------------------------------------------------------

class _SlowRouter:
    def route_batch(self, reqs):
        import time
        time.sleep(0.05)
        return [("resp", "out") for _ in reqs]


def test_frontend_queue_bound_sheds_with_retry_after():
    from repro.serving.frontend import AsyncFrontend
    fe = AsyncFrontend(_SlowRouter(), window_ms=1.0, max_batch=1,
                       max_depth=2)
    shed0 = _counter("admission_rejected_total")
    futs, err = [], None
    try:
        for i in range(50):
            futs.append(fe.submit(_req(f"r{i}")))
    except RouterOverloadError as e:
        err = e
    assert err is not None, "bounded queue never pushed back"
    assert err.retry_after_s >= 0.05
    assert _counter("admission_rejected_total") > shed0
    for f in futs:                   # accepted work still completes
        assert f.result(timeout=10)[0] == "resp"
    fe.close()


# ---------------------------------------------------------------------------
# overload detector + autoscaler
# ---------------------------------------------------------------------------

def test_detector_grades_and_hysteresis():
    from repro.serving.overload import EngineLoad, OverloadDetector
    load = EngineLoad(queue_depth=0, active_slots=0, slots=4,
                      free_blocks=90, total_blocks=100)
    det = OverloadDetector(interval_s=0.0)
    det.add_probe(lambda: EngineLoad(**vars(load)))
    pol = OverloadPolicy(queue_depth=8, slot_occupancy=0.9,
                         free_block_frac=0.05)
    assert det.sample(pol, force=True) == "ok"
    load.queue_depth = 4             # half the shed threshold -> busy
    assert det.sample(pol, force=True) == "busy"
    load.queue_depth = 8
    assert det.sample(pol, force=True) == "overload"
    # low KV headroom alone is an overload signal too
    load.queue_depth, load.free_blocks = 0, 3
    assert det.sample(pol, force=True) == "overload"
    # de-escalation needs two consecutive quiet samples (hysteresis)
    load.free_blocks = 90
    assert det.sample(pol, force=True) == "overload"
    assert det.sample(pol, force=True) == "ok"
    assert METRICS.gauges.get("overload_state") == 0
    assert "vsr_overload_state 0" in METRICS.scrape()


class _FakeSched:
    def __init__(self, active, slots, queue):
        self.active = [object()] * active + [None] * (slots - active)
        self.slots = slots
        self.queue = [None] * queue


class _FakeFleet:
    def __init__(self):
        self.schedulers = {"base": _FakeSched(3, 3, 6)}
        self.archs = ["base"]
        self.events = []

    def add_member(self, arch, *, warmup=True):
        self.schedulers[arch] = _FakeSched(0, 3, 0)
        self.events.append(("add", arch))
        return True

    def remove_member(self, arch):
        self.schedulers.pop(arch)
        self.events.append(("remove", arch))
        return True


def test_autoscaler_spins_standby_up_then_down():
    from repro.serving.overload import FleetAutoscaler
    fleet = _FakeFleet()
    scaler = FleetAutoscaler(fleet, ["aux-7b"], cooldown_s=5.0)
    acts = scaler.poll(now=100.0)
    assert [(a.direction, a.arch) for a in acts] == [("up", "aux-7b")]
    assert "aux-7b" in fleet.schedulers
    assert scaler.poll(now=101.0) == []       # cooldown holds
    # spun-up member idles -> scaled back down, returned to standby
    acts = scaler.poll(now=200.0)
    assert [(a.direction, a.arch) for a in acts] == [("down", "aux-7b")]
    assert fleet.events == [("add", "aux-7b"), ("remove", "aux-7b")]
    assert scaler.standby == ["aux-7b"]


# ---------------------------------------------------------------------------
# bench registry
# ---------------------------------------------------------------------------

def test_bench_registry_covers_qos_suites():
    from benchmarks.run import ALIASES, get_suites
    suites = get_suites()
    for key in ("decision", "prefix", "slo"):
        assert key in suites and callable(suites[key])
    assert ALIASES["t_decision_overhead"] == "decision"
    assert ALIASES["t_prefix_cache"] == "prefix"
    assert ALIASES["t_slo_burst"] == "slo"
