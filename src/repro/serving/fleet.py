"""LocalFleet: in-process Mixture-of-Modality backends for router serving.

The fleet is a set of **backend lanes** (:class:`BackendLane` protocol),
one per member arch, each with its own batch semantics:

* :class:`ARLane` — the continuous-batching autoregressive text lane: a
  slot-based :class:`DecodeScheduler` (`serving/scheduler.py`) admits new
  prompts into free slots of the in-flight decode batch (jitted single-row
  prefill + per-row-position decode).
* :class:`AudioLane` — transcription over an encoder/decoder config
  (``whisper-tiny``): the request payload is the *audio* (stub frontend —
  deterministic pseudo frame embeddings), fed as per-request
  cross-attention context to the same slot scheduler; output is a
  transcript payload.
* :class:`DiffusionLane` — a non-autoregressive fixed-step iterative
  denoiser stub with image-out payloads.  Slots hold latents at different
  denoise depths; one ``step()`` advances every active latent by one
  jitted iteration — the lane-level analogue of per-row-position decode.

``LocalFleet`` owns a per-lane scheduler map and ``_drain`` interleaves
steps across ALL involved lanes, so one ``batch_call`` carrying mixed
text/image/audio requests makes progress on every modality concurrently.
``call_fn`` adapts the fleet to the router's provider transport so the
whole §12 pipeline — signals, decisions, plugins, selection, endpoint
failover — executes against real JAX steps.  Content is synthetic (hash
tokenizer, random weights); the systems path (slot admission, per-row
positions, cross-lane interleaving, per-request latency metrics) is real.

Concurrency: the fleet lock covers ONLY submission and bookkeeping.
Draining happens outside it — per-lane step locks serialize the jitted
steps while concurrent callers' requests share the same slot pools
(continuous batching ACROSS callers), and whichever thread steps a lane
publishes every finished request to a shared results table for the other
callers to collect.  (Holding one lock across the whole drain made any
single ``generate()`` block every concurrent ``batch_call``.)

Sharding: ``model_axis > 1`` builds every member's params and decode
state sharded over the mesh's "model" axis under ``sharding/rules.py``
(via ``launch/mesh.make_host_mesh``), so large configs (e.g.
``qwen3-moe-235b`` reduced shapes) span multiple devices/hosts.
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field, fields as dc_fields
from dataclasses import replace as dc_replace
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced, list_archs
from repro.launch.mesh import make_host_mesh
from repro.models import model as MD
from repro.serving import serve_lib
from repro.serving.paged import PoolStats
from repro.serving.scheduler import (PREFILL_BUCKETS, DecodeScheduler,
                                     SpecConfig, SpecRuntime)
from repro.sharding import rules as R
from repro.sharding.ctx import sharding_rules

SSM_MIXERS = ("mamba", "mlstm", "slstm")

# decode attention paths a ModelConfig/LocalFleet may select; anything
# else used to fall through to the XLA path silently deep in a lane step
VALID_DECODE_IMPLS = ("xla", "flash_paged", "shardmap")

# non-AR diffusion stub archs servable as image lanes (not ModelConfigs —
# the denoiser is the lane itself)
DIFFUSION_ARCHS: Dict[str, dict] = {
    "sd-tiny": dict(hw=8, steps=8),
}


def _validate_decode_impl(decode_impl: Optional[str], model_axis: int):
    if decode_impl is not None and decode_impl not in VALID_DECODE_IMPLS:
        raise ValueError(
            f"unknown decode_impl {decode_impl!r}; valid: "
            + ", ".join(VALID_DECODE_IMPLS))
    if decode_impl == "flash_paged" and model_axis > 1:
        # Mosaic kernels are not partitioned by the compiler
        raise ValueError("decode_impl='flash_paged' runs the paged Pallas "
                         "kernels on one device; use model_axis=1 or the "
                         "XLA decode path")


def _spec_draft_archs() -> List[str]:
    """Archs usable as a speculative draft: AR text models the paged
    cache supports (pure attention/MLA stacks)."""
    out = []
    for a in list_archs():
        cfg = get_reduced(a)
        if cfg.family != "audio" and MD.paged_supported(cfg):
            out.append(a)
    return out


def _validate_speculative(spec: Optional[SpecConfig], *, paged: object):
    if spec is None:
        return
    if not isinstance(spec, SpecConfig):
        raise ValueError(
            f"speculative= expects a SpecConfig, got {type(spec).__name__}")
    if paged is False:
        raise ValueError("speculative decoding requires the paged KV "
                         "cache (paged='auto' or True)")
    valid = _spec_draft_archs()
    if spec.draft_arch not in valid:
        raise ValueError(
            f"unknown/unsupported speculative draft_arch "
            f"{spec.draft_arch!r}; valid: " + ", ".join(sorted(valid)))
    if spec.k < 1:
        raise ValueError(f"speculative k must be >= 1, got {spec.k}")
    if spec.probe_every < 1:
        raise ValueError(
            f"speculative probe_every must be >= 1, got {spec.probe_every}")
    if not (0.0 < spec.alpha <= 1.0):
        raise ValueError(
            f"speculative alpha must be in (0, 1], got {spec.alpha}")
    if not (0.0 <= spec.min_accept <= 1.0):
        raise ValueError(
            f"speculative min_accept must be in [0, 1], "
            f"got {spec.min_accept}")


def _validate_arch_overrides(overrides, archs: List[str]):
    """``arch_overrides`` maps a fleet arch to ModelConfig field
    overrides applied on top of its (reduced) registry config — plus the
    synthetic ``depth_mult`` key, which multiplies every layer group's
    ``repeats`` (benchmarks use it to open a real depth gap between a
    target and its speculative draft).  Validated at construction so a
    typo'd field fails with the arch named, not deep inside a lane
    build."""
    if overrides is None:
        return
    if not isinstance(overrides, dict):
        raise ValueError(
            f"arch_overrides expects a dict of arch -> field overrides, "
            f"got {type(overrides).__name__}")
    for arch, ov in overrides.items():
        if arch not in archs:
            raise ValueError(
                f"arch_overrides names {arch!r} which is not a fleet "
                f"member; fleet archs: " + ", ".join(archs))
        if arch in DIFFUSION_ARCHS:
            raise ValueError(
                f"arch_overrides cannot target diffusion lane {arch!r} "
                f"(no ModelConfig)")
        if not isinstance(ov, dict):
            raise ValueError(
                f"arch_overrides[{arch!r}] expects a dict of ModelConfig "
                f"fields, got {type(ov).__name__}")
        known = {f.name for f in dc_fields(get_reduced(arch))}
        for key in ov:
            if key != "depth_mult" and key not in known:
                raise ValueError(
                    f"arch_overrides[{arch!r}]: unknown ModelConfig "
                    f"field {key!r}")
        if "depth_mult" in ov and int(ov["depth_mult"]) < 1:
            raise ValueError(
                f"arch_overrides[{arch!r}]: depth_mult must be >= 1, "
                f"got {ov['depth_mult']}")


def _apply_arch_overrides(cfg, ov: dict):
    ov = dict(ov)
    mult = int(ov.pop("depth_mult", 1) or 1)
    if mult > 1:
        cfg = cfg.replace(groups=tuple(
            dc_replace(g, repeats=g.repeats * mult) for g in cfg.groups))
    if ov:
        cfg = cfg.replace(**ov)
    return cfg


def hash_tokens(text: str, vocab: int, max_len: int) -> np.ndarray:
    ids = []
    for w in text.lower().split():
        h = hashlib.blake2s(w.encode(), digest_size=4).digest()
        ids.append(4 + int.from_bytes(h, "little") % (vocab - 4))
    # over-long prompts keep the TAIL: with joined multi-turn conversations
    # the newest turns (the current question) must survive truncation, not
    # the oldest history
    return np.asarray(ids[-max_len:] or [4], np.int32)


def _seed_of(text: str) -> int:
    return int.from_bytes(
        hashlib.blake2s(text.encode(), digest_size=4).digest(), "little")


@dataclass
class MemberStats:
    """Serving stats shared by every lane's member record."""
    calls: int = field(default=0, kw_only=True)       # drains served
    tokens_out: int = field(default=0, kw_only=True)  # work units produced
    prompts_in: int = field(default=0, kw_only=True)  # real requests served
    warmup_ms: float = field(default=0.0, kw_only=True)  # JIT compile wall

    @property
    def slots_per_call(self) -> float:
        """Mean real prompts per generate()/batch_call drain.  A drain
        admits any number of prompts through the slot pool, so this
        measures batching depth per upstream call (it can exceed the
        physical slot count); the lane's ``occupancy`` is the per-step
        slot utilisation."""
        return self.prompts_in / max(1, self.calls)


@dataclass
class FleetMember(MemberStats):
    arch: str
    cfg: object
    params: object
    prefill_row: object          # jitted (params, toks(1,L), lens, cache1)
    decode_rows: object          # jitted (params, toks(B,1), cache) per-row
    merge_row: object            # jitted slot admission into the cache pool
    batch: int                   # decode slots
    max_seq: int
    prompt_cap: int              # longest admissible prompt
    exact_prefill: bool          # SSM state: no pad-bucketing allowed
    # paged KV pool (prefix caching) — None/False for contiguous members
    paged: bool = False
    prefill_paged_fresh: object = None   # jitted no-prefix paged admission
    prefill_paged_suffix: object = None  # jitted suffix-only paged admission
    copy_block: object = None            # jitted COW block copy
    block_tokens: int = 16
    num_blocks: int = 0                  # physical blocks incl. trash block
    spec: object = None                  # SpecRuntime (speculative decoding)


@dataclass
class DiffusionMember(MemberStats):
    """Member record for a non-AR diffusion lane (no params/config — the
    denoiser lives on the lane; ``tokens_out`` counts denoise
    slot-iterations)."""
    arch: str
    batch: int


# ---------------------------------------------------------------------------
# backend lanes
# ---------------------------------------------------------------------------

class BackendLane:
    """Protocol for one execution lane of the Mixture-of-Modality fleet.

    ``modality``    lane type: "text" | "image" | "audio".
    ``submit(prompt, max_new=, priority=, slo=) -> rid``   queue one
                    request payload; ``priority`` orders scheduler
                    admission and arms preemption on AR lanes (lanes
                    without a priority queue may ignore it).
    ``step() -> [finished]``              advance the lane's batch one
                                          iteration; finished jobs carry
                                          ``.rid`` and timing fields.
    ``pending``     queued + in-flight count.
    ``result(job) -> dict``               transport payload: ``content``,
                                          ``tokens``, ``ttft_ms``,
                                          ``tpot_ms``, ``service_ms``,
                                          ``lane``, plus modality extras
                                          (``image`` / ``transcript``).
    ``warmup()``    pre-compile every production step; must not pollute
                    serving stats.
    ``occupancy``   mean active slots per step.
    """

    modality = "text"

    def submit(self, prompt: str, max_new: Optional[int] = None,
               priority: int = 0, slo: str = "") -> int:
        raise NotImplementedError

    def step(self) -> List[object]:
        raise NotImplementedError

    @property
    def pending(self) -> int:
        raise NotImplementedError

    def result(self, job) -> dict:
        raise NotImplementedError

    def warmup(self):
        raise NotImplementedError


class ARLane(BackendLane):
    """Continuous-batching autoregressive lane over one fleet member."""

    modality = "text"

    def __init__(self, fleet: "LocalFleet", member: FleetMember):
        self.fleet = fleet
        self.m = member
        self.sched = fleet._make_scheduler(member)

    def submit(self, prompt: str, max_new: Optional[int] = None,
               priority: int = 0, slo: str = "") -> int:
        m = self.m
        return self.sched.submit(
            hash_tokens(prompt, m.cfg.vocab_size, m.prompt_cap),
            max_new=max_new, priority=priority, slo=slo)

    @property
    def pending(self) -> int:
        return self.sched.pending

    def step(self):
        with sharding_rules(self.fleet.mesh,
                            R.act_rules(self.fleet.mesh, self.m.batch)):
            return self.sched.step()

    def result(self, seq) -> dict:
        m = self.m
        return {
            "content": (f"[{m.arch}] {len(seq.out)} tokens: "
                        + " ".join(str(x) for x in seq.out[:10])),
            "tokens": list(seq.out),
            "ttft_ms": seq.ttft_ms,
            "tpot_ms": seq.tpot_ms,
            "service_ms": (seq.t_done - seq.t_submit) * 1e3,
            # ttft = queue (submit -> prefill begins) + prefill (-> first
            # token)
            "queue_ms": (seq.t_begin - seq.t_submit) * 1e3,
            "prefill_ms": (seq.t_first - seq.t_begin) * 1e3,
            "lane": self.modality,
        }

    @property
    def occupancy(self) -> float:
        return self.sched.occupancy

    def _warmup_widths(self) -> List[int]:
        m = self.m
        if m.exact_prefill:
            return [4]
        return [b for b in PREFILL_BUCKETS if b <= m.prompt_cap] + \
            [m.prompt_cap]

    def warmup(self):
        """Compile every production step at construction: one throwaway
        request per prompt-length bucket runs the real admit+decode path,
        so serving-time ``ttft_ms`` never includes XLA compile time and
        latency-aware selection is not biased against the first model
        used.  (Exact-length archs compile per prompt length by design;
        their decode/merge — the steady-state cost — still pre-compiles.)"""
        m, sched = self.m, self.sched
        t0 = time.perf_counter()
        widths = list(dict.fromkeys(self._warmup_widths()))
        for wi, w in enumerate(widths):
            # distinct fill per width: every bucket exercises the FRESH
            # prefill path (a shared fill would prefix-match under paged
            # KV and skip straight to the suffix program)
            self._warmup_submit(w, fill=4 + wi)
        while self.pending:
            self.step()
        if getattr(sched, "paged", False):
            # re-submit the smallest bucket: a fully-cached prompt
            # compiles the 16-wide suffix-prefill program AND the COW
            # block copy
            self._warmup_submit(widths[0], fill=4)
            # partially-matched prompts (one cached block + a longer
            # unique tail) compile the remaining suffix widths, so a
            # cache hit on a long prompt never pays XLA compile time
            blk = m.block_tokens
            prev = widths[0]
            for wi, w in enumerate(widths[1:]):
                tail = min(prev + 1, m.prompt_cap - blk)
                if tail <= 0:
                    break
                ids = np.concatenate([np.full((blk,), 4, np.int32),
                                      np.full((tail,), 90 + wi, np.int32)])
                self.sched.submit(ids, max_new=2)
                prev = w
            while self.pending:
                self.step()
        if getattr(sched, "drafter", None) is not None:
            # the spec drains above compiled the wide verify, the fused
            # draft scan, and the FRESH draft catch-up prefills; a lane
            # that backs off accumulates draft lag and its probe rounds
            # catch up through SUFFIX prefills at arbitrary width
            # buckets — compile the whole ladder now against the trash
            # block so no serving-time probe ever pays XLA compile
            dw = sched.drafter
            trow = jnp.zeros((1, sched.tbl.shape[1]), jnp.int32)
            with sharding_rules(self.fleet.mesh,
                                R.act_rules(self.fleet.mesh, m.batch)):
                for fn, start in ((dw.rt.prefill_fresh, 0),
                                  (dw.rt.prefill_suffix, m.block_tokens)):
                    for w in widths:
                        _, dw.cache = fn(
                            dw.rt.params, jnp.zeros((1, w), jnp.int32),
                            jnp.asarray([min(2, w)], np.int32),
                            jnp.asarray([start], np.int32),
                            trow, dw.cache)
            # the adaptive fallback (plain decode when acceptance
            # collapses) must compile now too, not on the first
            # backed-off serving round
            sched.spec_enabled = False
            self._warmup_submit(4, fill=7)
            while self.pending:
                self.step()
            sched.spec_enabled = True
        m.warmup_ms = (time.perf_counter() - t0) * 1e3
        # warmup traffic must not pollute serving stats
        m.tokens_out = m.prompts_in = 0
        sched.admitted = sched.decode_steps = sched.slot_steps = 0
        sched.masked_slot_steps = 0
        sched.prefill_tokens = sched.cached_tokens = 0
        sched.preempted = 0
        sched.ttft_ewma = 0.0
        sched.ttft_samples = 0
        sched.prefill.prefills = 0
        if getattr(sched, "paged", False):
            sched.pool.stats = PoolStats()
        if getattr(sched, "drafter", None) is not None:
            sched.drafter.reset_stats()
            sched.spec_rounds = sched.spec_offered = 0
            sched.spec_accepted = sched.spec_emitted = 0
            sched.spec_acceptance_ewma = 0.0
        sched._finished.clear()

    def _warmup_submit(self, width: int, fill: int = 4):
        self.sched.submit(np.full((width,), fill, np.int32), max_new=2)


class AudioLane(ARLane):
    """Transcription lane: the request payload is the audio (stub conv
    frontend — deterministic pseudo frame embeddings hashed from the
    payload), attended by the decoder as per-request cross-attention
    context; the decoder starts from a BOS token and emits the
    transcript."""

    modality = "audio"

    def _frames(self, payload: str):
        cfg = self.m.cfg
        rng = np.random.default_rng(_seed_of(payload))
        f = rng.standard_normal((1, cfg.cross_ctx_len, cfg.d_model))
        return jnp.asarray(f, jnp.dtype(cfg.dtype))

    def submit(self, prompt: str, max_new: Optional[int] = None,
               priority: int = 0, slo: str = "") -> int:
        return self.sched.submit(np.asarray([4], np.int32), max_new=max_new,
                                 cross=self._frames(prompt),
                                 priority=priority, slo=slo)

    def _warmup_widths(self) -> List[int]:
        # audio requests always decode from a 1-token BOS prompt
        return [1]

    def _warmup_submit(self, width: int, fill: int = 4):
        self.sched.submit(np.full((width,), fill, np.int32), max_new=2,
                          cross=self._frames("warmup"))

    def result(self, seq) -> dict:
        out = super().result(seq)
        transcript = " ".join(f"tok{t}" for t in seq.out)
        out["content"] = (f"[{self.m.arch}] transcript "
                          f"{len(seq.out)} tokens: {transcript[:80]}")
        out["transcript"] = transcript
        return out


@dataclass
class DiffusionJob:
    """One queued / in-flight / finished image request."""
    rid: int
    prompt: str
    t_submit: float
    slot: int = -1
    steps_done: int = 0
    t_first: float = 0.0         # first denoise iteration wall clock
    t_done: float = 0.0
    image: Optional[np.ndarray] = None

    @property
    def ttft_ms(self) -> float:
        return (self.t_first - self.t_submit) * 1e3

    @property
    def tpot_ms(self) -> float:
        if self.steps_done <= 1:
            return 0.0
        return (self.t_done - self.t_first) * 1e3 / (self.steps_done - 1)


class DiffusionLane(BackendLane):
    """Fixed-step iterative denoiser stub (non-autoregressive lane).

    Own batch semantics: a fixed pool of latent slots where each slot sits
    at its OWN denoise depth (``t_idx`` per slot); every ``step()`` admits
    queued prompts into free slots (prompt-seeded noise latent) and runs
    ONE jitted denoise iteration over all slots.  A latent that reaches
    ``steps`` iterations is quantized to a uint8 image payload and its
    slot freed — the image analogue of continuous-batching decode."""

    modality = "image"

    def __init__(self, member: DiffusionMember, *, hw: int = 8,
                 steps: int = 8):
        self.m = member
        self.hw = hw
        self.steps = steps
        self.slots = member.batch
        self.latents = jnp.zeros((self.slots, hw, hw), jnp.float32)
        self.t_idx = np.zeros((self.slots,), np.int32)
        self.active: List[Optional[DiffusionJob]] = [None] * self.slots
        self.queue: Deque[DiffusionJob] = deque()
        self._rid = 0
        self.decode_steps = 0
        self.slot_steps = 0
        n = float(steps)

        def denoise(lat, t):
            # per-slot sigma schedule: sigma_t = 1 - t/N; the "noise
            # prediction" is the latent's high-frequency residual, so the
            # fixed-point is a smoothed (structured) image
            sig = (1.0 - t.astype(jnp.float32) / n)[:, None, None]
            blur = (jnp.roll(lat, 1, 1) + jnp.roll(lat, -1, 1) +
                    jnp.roll(lat, 1, 2) + jnp.roll(lat, -1, 2)) / 4.0
            eps_hat = lat - blur
            return lat - sig * eps_hat

        self._denoise = jax.jit(denoise, donate_argnums=(0,))

    # -- protocol -----------------------------------------------------------

    def submit(self, prompt: str, max_new: Optional[int] = None,
               priority: int = 0, slo: str = "") -> int:
        # the denoiser's fixed-step FIFO has no priority queue; QoS
        # ordering applies to AR lanes
        self._rid += 1
        self.queue.append(DiffusionJob(self._rid, prompt,
                                       time.perf_counter()))
        return self._rid

    @property
    def pending(self) -> int:
        return len(self.queue) + sum(j is not None for j in self.active)

    def _init_latent(self, prompt: str) -> np.ndarray:
        rng = np.random.default_rng(_seed_of(prompt))
        return rng.standard_normal((self.hw, self.hw)).astype(np.float32)

    def step(self) -> List[DiffusionJob]:
        done: List[DiffusionJob] = []
        while self.queue and None in self.active:
            slot = self.active.index(None)
            job = self.queue.popleft()
            job.slot = slot
            self.latents = self.latents.at[slot].set(
                jnp.asarray(self._init_latent(job.prompt)))
            self.t_idx[slot] = 0
            self.active[slot] = job
            self.m.prompts_in += 1
        live = [i for i, j in enumerate(self.active) if j is not None]
        if not live:
            return done
        # a snapshot: the loop below bumps t_idx in place while the step
        # may still be queued, and on CPU a numpy argument is read when
        # the step runs, not when it is dispatched
        self.latents = self._denoise(self.latents,
                                     jnp.asarray(self.t_idx.copy()))
        now = time.perf_counter()
        self.decode_steps += 1
        self.slot_steps += len(live)
        self.m.tokens_out += len(live)
        for i in live:
            job = self.active[i]
            job.steps_done += 1
            self.t_idx[i] += 1
            if job.t_first == 0.0:
                job.t_first = now
            if job.steps_done >= self.steps:
                job.t_done = now
                lat = np.asarray(self.latents[i])
                span = float(lat.max() - lat.min()) or 1.0
                job.image = np.clip((lat - lat.min()) / span * 255.0,
                                    0, 255).astype(np.uint8)
                self.active[i] = None
                self.t_idx[i] = 0
                done.append(job)
        return done

    def result(self, job: DiffusionJob) -> dict:
        sig = hashlib.blake2s(job.image.tobytes(),
                              digest_size=4).hexdigest()
        return {
            "content": (f"[{self.m.arch}] image {self.hw}x{self.hw} "
                        f"steps={job.steps_done} sig={sig}"),
            "image": {"hw": self.hw, "sig": sig,
                      "data": job.image.flatten().tolist()},
            "tokens": [],
            "ttft_ms": job.ttft_ms,
            "tpot_ms": job.tpot_ms,
            "service_ms": (job.t_done - job.t_submit) * 1e3,
            "lane": self.modality,
        }

    @property
    def occupancy(self) -> float:
        return self.slot_steps / max(1, self.decode_steps)

    def warmup(self):
        t0 = time.perf_counter()
        self.submit("warmup")
        while self.pending:
            self.step()
        self.m.warmup_ms = (time.perf_counter() - t0) * 1e3
        self.m.tokens_out = self.m.prompts_in = 0
        self.decode_steps = self.slot_steps = 0


# ---------------------------------------------------------------------------
# fleet
# ---------------------------------------------------------------------------

class LocalFleet:
    def __init__(self, archs: List[str], *, reduced: bool = True,
                 batch: int = 4, max_seq: int = 160, gen_tokens: int = 16,
                 moe_impl: str = "ep", seed: int = 0, warmup: bool = True,
                 model_axis: int = 1, paged: object = "auto",
                 block_tokens: int = 16, kv_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_budget: Optional[int] = 1,
                 prefill_lookahead: int = 0,
                 decode_impl: Optional[str] = None,
                 speculative: Optional[SpecConfig] = None,
                 arch_overrides: Optional[Dict[str, dict]] = None,
                 devices: Optional[List[object]] = None):
        """``paged`` selects the KV layout per member: "auto" (default)
        pages every arch the paged cache supports (pure attention/MLA
        stacks — SSM and cross-attention members stay contiguous), True
        requires it (raises for unsupported archs), False keeps the
        contiguous PR-2 cache everywhere.  ``kv_blocks`` overrides the
        physical pool size (default: one full table per slot + headroom
        for retained prefix blocks).

        Disaggregated prefill/decode knobs: ``prefill_chunk`` caps the
        tokens per paged admission-prefill call (None = whole suffix in
        one call), ``prefill_budget`` caps prefill calls interleaved per
        decode step while the batch is live (None = unbounded, the legacy
        admit-everything cadence), ``prefill_lookahead`` lets the prefill
        worker run that many admissions ahead of free slots.
        ``decode_impl`` overrides the model's decode attention path
        (e.g. "flash_paged" for the block-table Pallas decode kernel).

        ``speculative`` enables draft-model speculative decoding on every
        paged text lane: ``SpecConfig.draft_arch`` proposes ``k`` tokens
        per round, the lane's member verifies all k+1 positions in one
        wide forward, and greedy acceptance keeps output token-exact vs
        the non-speculative path (see ``DecodeScheduler._decode_spec``).

        ``arch_overrides`` maps member archs to ModelConfig field
        overrides (plus ``depth_mult``, which multiplies layer-group
        repeats) applied on top of the registry config before build —
        the speculative-decoding benchmark deepens its target with it.

        ``devices`` are the devices the fleet's mesh spans (default: all
        local devices)."""
        _validate_decode_impl(decode_impl, model_axis)
        _validate_speculative(speculative, paged=paged)
        _validate_arch_overrides(arch_overrides, list(archs))
        self.mesh = make_host_mesh(model=model_axis, devices=devices)
        self.model_axis = model_axis
        self.gen_tokens = gen_tokens
        self.members: Dict[str, object] = {}
        self.lanes: Dict[str, BackendLane] = {}
        # AR/audio decode schedulers by arch (back-compat alias into lanes)
        self.schedulers: Dict[str, DecodeScheduler] = {}
        # the fleet lock covers submission/bookkeeping ONLY; draining runs
        # outside it (see _drain) so concurrent callers batch together
        self._lock = threading.RLock()
        self._step_locks: Dict[str, threading.Lock] = {}
        self._done: "OrderedDict[Tuple[str, int], object]" = OrderedDict()
        self._done_cv = threading.Condition()
        self._done_cap = 4096
        self._waiting: set = set()       # keys some drain is waiting on
        self._key = jax.random.PRNGKey(seed)
        # build options retained so the autoscaler can construct standby
        # members later with identical shapes/seeding
        self._build = dict(reduced=reduced, batch=batch, max_seq=max_seq,
                           moe_impl=moe_impl, paged=paged,
                           block_tokens=block_tokens, kv_blocks=kv_blocks,
                           decode_impl=decode_impl, speculative=speculative,
                           arch_overrides=arch_overrides or {})
        self._sched_opts = dict(prefill_chunk=prefill_chunk,
                                prefill_budget=prefill_budget,
                                prefill_lookahead=prefill_lookahead)
        self.archs = list(archs)         # base membership: never scaled below
        for arch in archs:
            self.add_member(arch, warmup=warmup)

    def _build_lane(self, arch: str) -> Tuple[object, BackendLane]:
        """Construct one member + lane (params init, jitted serve steps,
        paged pool sizing).  Pure build — no registration, no warmup."""
        b = self._build
        reduced, batch, max_seq = b["reduced"], b["batch"], b["max_seq"]
        moe_impl, paged = b["moe_impl"], b["paged"]
        block_tokens, kv_blocks = b["block_tokens"], b["kv_blocks"]
        if arch in DIFFUSION_ARCHS:
            member: object = DiffusionMember(arch, batch=batch)
            lane: BackendLane = DiffusionLane(member,
                                              **DIFFUSION_ARCHS[arch])
            return member, lane
        cfg = get_reduced(arch) if reduced else get_config(arch)
        if b["arch_overrides"].get(arch):
            cfg = _apply_arch_overrides(cfg, b["arch_overrides"][arch])
        if b["decode_impl"] is not None:
            cfg = cfg.replace(decode_impl=b["decode_impl"])
        if cfg.n_experts:
            # serving is dropless: capacity >= the per-call token
            # count, so expert keep/drop never depends on which
            # other tokens share the dispatch group.  Capacity
            # drops would make a 16-wide paged suffix prefill
            # diverge from the same tokens inside a 64-wide
            # contiguous prefill (different queue population)
            cfg = cfg.replace(moe_capacity_factor=max(
                cfg.moe_capacity_factor,
                cfg.n_experts / max(1, cfg.moe_top_k)))
        with sharding_rules(self.mesh,
                            R.act_rules(self.mesh, batch)):
            pre_row, dec, merge = serve_lib.build_row_serve_steps(
                cfg, moe_impl=moe_impl)
            sh = serve_lib.serve_shardings(cfg, self.mesh, batch,
                                           max_seq)
            params = jax.jit(
                lambda k, c=cfg: MD.init_params(c, k),
                out_shardings=sh["param_sharding"])(self._key)
        exact = any(s.mixer in SSM_MIXERS
                    for g in cfg.groups for s in g.period)
        can_page = (MD.paged_supported(cfg)
                    and max_seq % block_tokens == 0)
        if paged is True and not can_page:
            raise ValueError(
                f"{arch}: paged KV unsupported (SSM/cross-attn "
                f"state or max_seq % block_tokens != 0)")
        use_paged = can_page if paged == "auto" else bool(paged)
        pf = ps = cpb = None
        nblk = 0
        if use_paged:
            with sharding_rules(self.mesh,
                                R.act_rules(self.mesh, batch)):
                pf, ps, dec, cpb = serve_lib.build_paged_serve_steps(
                    cfg, moe_impl=moe_impl)
            bpr = max_seq // block_tokens
            # 1 trash + a full table per slot + retained-prefix
            # headroom (~4 rows) for the cross-request hit rate
            nblk = kv_blocks or (1 + (batch + 4) * bpr)
        spec_rt = None
        if b["speculative"] is not None and use_paged \
                and cfg.family != "audio":
            spec_rt = self._build_spec_runtime(
                b["speculative"], cfg, batch, max_seq, nblk, block_tokens,
                moe_impl)
        member = FleetMember(arch, cfg, params, pre_row, dec, merge,
                             batch, max_seq,
                             prompt_cap=max_seq - self.gen_tokens - 1,
                             exact_prefill=exact,
                             paged=use_paged,
                             prefill_paged_fresh=pf,
                             prefill_paged_suffix=ps,
                             copy_block=cpb,
                             block_tokens=block_tokens,
                             num_blocks=nblk,
                             spec=spec_rt)
        lane_cls = AudioLane if cfg.family == "audio" else ARLane
        return member, lane_cls(self, member)

    def _build_spec_runtime(self, spec: SpecConfig, target_cfg, batch: int,
                            max_seq: int, num_blocks: int, block_tokens: int,
                            moe_impl: str) -> SpecRuntime:
        """Draft model + jitted speculative steps for one paged lane.

        The draft initializes from the fleet's OWN key — the same key
        every member's params come from — so a draft_arch that names
        another fleet member proposes with byte-identical weights to
        that member.  Its paged cache reuses the TARGET pool's geometry
        (same slots/table/blocks), so the scheduler's one block table
        indexes both pools and speculation adds zero BlockPool state.
        The draft always decodes through the XLA path: its tokens only
        seed proposals, so the cheapest dispatch wins and the target's
        ``decode_impl`` choice stays independent."""
        b = self._build
        draft_cfg = (get_reduced(spec.draft_arch) if b["reduced"]
                     else get_config(spec.draft_arch))
        draft_cfg = draft_cfg.replace(decode_impl="xla")
        if draft_cfg.n_experts:
            # dropless, same as the serving members (see _build_lane)
            draft_cfg = draft_cfg.replace(moe_capacity_factor=max(
                draft_cfg.moe_capacity_factor,
                draft_cfg.n_experts / max(1, draft_cfg.moe_top_k)))
        if draft_cfg.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"speculative draft_arch {spec.draft_arch!r} vocab "
                f"({draft_cfg.vocab_size}) != target vocab "
                f"({target_cfg.vocab_size})")
        with sharding_rules(self.mesh, R.act_rules(self.mesh, batch)):
            dsh = serve_lib.serve_shardings(draft_cfg, self.mesh, batch,
                                            max_seq)
            draft_params = jax.jit(
                lambda k, c=draft_cfg: MD.init_params(c, k),
                out_shardings=dsh["param_sharding"])(self._key)
            steps = serve_lib.build_spec_steps(target_cfg, draft_cfg,
                                               moe_impl=moe_impl)
        init_cache = lambda slots, c=draft_cfg: MD.init_paged_cache(
            c, slots, max_seq, num_blocks, block_tokens)
        return SpecRuntime(cfg=draft_cfg, params=draft_params,
                           verify=steps["verify"],
                           draft_propose=steps["draft_propose"],
                           prefill_fresh=steps["draft_prefill_fresh"],
                           prefill_suffix=steps["draft_prefill_suffix"],
                           init_cache_fn=init_cache, spec=spec)

    def add_member(self, arch: str, *, warmup: bool = True) -> bool:
        """Build, warm up, and register one member (the autoscaler's
        scale-up hook).  Construction and warmup run OUTSIDE the fleet
        lock — they take seconds of XLA compile and must not stall
        serving; registration is atomic and race-checked."""
        with self._lock:
            if arch in self.members:
                return False
        member, lane = self._build_lane(arch)
        if warmup:
            lane.warmup()
        with self._lock:
            if arch in self.members:     # raced with a concurrent add
                return False
            self.members[arch] = member
            self.lanes[arch] = lane
            if isinstance(lane, ARLane):
                self.schedulers[arch] = lane.sched
            self._step_locks[arch] = threading.Lock()
        return True

    def remove_member(self, arch: str) -> bool:
        """Deregister an idle member (the autoscaler's scale-down hook).
        Refuses while the lane has queued or in-flight work; base members
        are the autoscaler's responsibility to exempt."""
        with self._lock:
            lane = self.lanes.get(arch)
            if lane is None or lane.pending:
                return False
            del self.members[arch]
            del self.lanes[arch]
            self.schedulers.pop(arch, None)
            self._step_locks.pop(arch, None)
        return True

    def modality_of(self, arch: str) -> str:
        return self.lanes[arch].modality

    def _make_scheduler(self, m: FleetMember) -> DecodeScheduler:
        make_cross = None
        if m.cfg.cross_ctx_len:
            make_cross = lambda b, cfg=m.cfg: jnp.zeros(
                (b, cfg.cross_ctx_len, cfg.d_model), jnp.dtype(cfg.dtype))
        if getattr(m, "paged", False):
            init_cache = lambda b, cfg=m.cfg: MD.init_paged_cache(
                cfg, m.batch, m.max_seq, m.num_blocks, m.block_tokens)
        else:
            init_cache = lambda b, cfg=m.cfg: MD.init_cache(
                cfg, b, m.max_seq)
        return DecodeScheduler(
            m, gen_tokens=self.gen_tokens,
            init_cache_fn=init_cache,
            make_cross_fn=make_cross,
            spec=getattr(m, "spec", None), **self._sched_opts)

    # -- generation ---------------------------------------------------------

    def generate(self, arch: str, prompts: List[str],
                 max_new: Optional[int] = None, priority: int = 0,
                 slo: str = "") -> List[dict]:
        """Greedy generation (or image/transcript synthesis) via the
        arch's lane.  Any number of prompts is accepted: overflow beyond
        the slot count is queued and admitted as slots free (never
        silently dropped).  Only submission holds the fleet lock, so
        concurrent callers' requests share the in-flight batch."""
        with self._lock:
            self.members[arch].calls += 1
            rids = self._submit(arch, prompts, max_new,
                                priority=priority, slo=slo)
        seqs = self._drain({arch: rids})
        lane = self.lanes[arch]
        return [lane.result(seqs[(arch, r)]) for r in rids]

    def _submit(self, arch: str, prompts: List[str],
                max_new: Optional[int] = None, *, priority: int = 0,
                slo: str = "") -> List[int]:
        lane = self.lanes[arch]
        return [lane.submit(p, max_new=max_new, priority=priority, slo=slo)
                for p in prompts]

    def _drain(self, rids_by_arch: Dict[str, List[int]]
               ) -> Dict[Tuple[str, int], object]:
        """Interleave steps across every involved lane until all request
        ids have finished — cross-lane (text/image/audio) progress under
        one drain.  Runs WITHOUT the fleet lock: per-lane step locks
        serialize the jitted steps, and any thread stepping a lane
        publishes every request it finishes (its own or a concurrent
        caller's) to the shared results table, waking waiters."""
        all_keys = {(a, r) for a, rids in rids_by_arch.items() for r in rids}
        want = set(all_keys)
        seqs: Dict[Tuple[str, int], object] = {}
        with self._done_cv:
            # results a live drain waits on are exempt from table eviction
            # (an abandoned caller's results age out; ours must not)
            self._waiting |= want
        try:
            while want:
                stepped = False
                for arch in rids_by_arch:
                    if not any(k[0] == arch for k in want):
                        continue
                    lock = self._step_locks[arch]
                    if not lock.acquire(blocking=False):
                        continue    # another caller is stepping this lane
                    try:
                        lane = self.lanes[arch]
                        if lane.pending:
                            finished = lane.step()
                            stepped = True
                        else:
                            finished = []
                    finally:
                        lock.release()
                    if finished:
                        with self._done_cv:
                            for seq in finished:
                                self._done[(arch, seq.rid)] = seq
                            if len(self._done) > self._done_cap:
                                for k in list(self._done):
                                    if len(self._done) <= self._done_cap:
                                        break
                                    if k not in self._waiting:
                                        del self._done[k]
                            self._done_cv.notify_all()
                with self._done_cv:
                    ready = want & self._done.keys()
                    for k in ready:
                        seqs[k] = self._done.pop(k)
                    want -= ready
                    if want and not stepped and not ready:
                        # nothing runnable here: another caller is stepping
                        # our lanes — wait for it to publish our results
                        self._done_cv.wait(0.002)
        finally:
            with self._done_cv:
                self._waiting -= all_keys
        return seqs

    # -- router transport -----------------------------------------------------
    def call_fn(self, model_to_arch: Dict[str, str]):
        """Router transport over the modality lanes: the returned callable
        serves single requests; its ``batch_call`` attribute submits every
        payload to its arch's lane up front and drains them together, so
        same-arch requests share steps (the slot pool is the batching
        boundary) and different-lane sub-batches progress interleaved."""

        def _resolve(payload):
            model = payload.get("model") or payload.get("modelId", "")
            arch = model_to_arch.get(model, model)
            if arch not in self.members:
                raise RuntimeError(f"fleet has no backend for {model!r}")
            msgs = payload.get("messages") or \
                payload.get("body", {}).get("messages") or []
            # the WHOLE conversation feeds generation — feeding only
            # msgs[-1] silently dropped multi-turn context from both the
            # scheduler prompt and usage accounting
            prompt = "\n".join(m["content"] for m in msgs)
            # QoS sidecar fields attached by to_provider_payload: the
            # scheduler orders admission by priority / preempts on it
            prio = int(payload.get("vsr_priority", 0) or 0)
            slo = str(payload.get("vsr_slo", "") or "")
            return model, arch, prompt, prio, slo

        def _wrap(model, prompt, out):
            message = {"content": out["content"]}
            for extra in ("image", "transcript"):
                if extra in out:
                    message[extra] = out[extra]
            # prompt_tokens counts the JOINED conversation, same text the
            # scheduler generated from
            usage = {"prompt_tokens": len(prompt) // 4,
                     "completion_tokens": len(out["tokens"]),
                     # per-request transport service time: the pipeline
                     # attributes THIS to latency-aware selection instead
                     # of batch wall clock
                     "vsr_service_ms": round(out["service_ms"], 3),
                     "vsr_ttft_ms": round(out["ttft_ms"], 3),
                     "vsr_lane": out.get("lane", "text")}
            if "queue_ms" in out:        # autoregressive lanes
                usage["vsr_queue_ms"] = round(out["queue_ms"], 3)
                usage["vsr_prefill_ms"] = round(out["prefill_ms"], 3)
            return {"choices": [{"message": message,
                                 "finish_reason": "stop"}],
                    "model": model, "usage": usage}

        def call(ep, payload, headers):
            model, arch, prompt, prio, slo = _resolve(payload)
            out = self.generate(arch, [prompt], priority=prio, slo=slo)[0]
            return _wrap(model, prompt, out)

        def batch_call(ep, payloads, headers_list):
            resolved = [_resolve(p) for p in payloads]
            with self._lock:
                rids_by_arch: Dict[str, List[int]] = {}
                rid_of: List[int] = []
                for model, arch, prompt, prio, slo in resolved:
                    rid = self._submit(arch, [prompt],
                                       priority=prio, slo=slo)[0]
                    rids_by_arch.setdefault(arch, []).append(rid)
                    rid_of.append(rid)
                for arch in rids_by_arch:
                    self.members[arch].calls += 1
            seqs = self._drain(rids_by_arch)
            return [_wrap(model, prompt,
                          self.lanes[arch].result(seqs[(arch, rid)]))
                    for (model, arch, prompt, _pr, _sl), rid
                    in zip(resolved, rid_of)]

        call.batch_call = batch_call
        return call
