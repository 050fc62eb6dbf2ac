"""The system under test: the router and its fleet, as the program builds
them, with the benchmark's weights put in and the cell's shapes warmed up.

This is the one module that touches the program.  It takes from it the
entry point (``build_router``), the fleet's members and lanes, and their
counters; it hands it weights made from the seed.  What is particular to
an architecture (which configuration keys the program must match, which
leaves make its parameter tree) is asked of the configuration's family
(``families/<reference>.py``); nothing here names a key or a leaf.  Spans
around the calls into each layer are the benchmark's own
``TraceAnnotation``s, wrapped around those calls from outside: the
program is not edited.
"""

from __future__ import annotations

import gc
import threading
from dataclasses import dataclass, field
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import rules, spec
from harness import weights as W
from harness.traffic import Schedule, prompt_cap

WARM_SEED = 0x5EED       # warm-up prompts: fixed, and unlike any traffic


@dataclass
class Taps:
    """What the wrappers record: finished sequences (always), and while
    ``tracing`` is set, the work of each decode and prefill call."""
    tracing: bool = False
    finished: List[tuple] = field(default_factory=list)     # (arch, seq)
    decode: List[tuple] = field(default_factory=list)       # (arch, kv_lens)
    prefill: List[tuple] = field(default_factory=list)      # (arch, tokens)
    lock: threading.Lock = field(default_factory=threading.Lock)


def _shape_tree(tree):
    return jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tree)


def install_weights(fleet, config: dict, family, seed: int):
    """Replace each member's parameters by the benchmark's, laid out by
    the configuration's ``family``, made on the device in one jitted call
    per member, sharded as the program shards its own."""
    for model, prof in config["router"]["profiles"].items():
        arch = prof["arch"]
        m = config["models"][model]
        member = fleet.members[arch]
        shard = jax.tree.map(lambda x: x.sharding, member.params)
        want = _shape_tree(member.params)
        member.params = None
        gc.collect()
        dtype = jnp.dtype(m["dtype"])
        make = jax.jit(lambda k, m=m: family.program_params(m, k, dtype),
                       out_shardings=shard)
        got = jax.eval_shape(make, W.member_key(seed, model))
        if _shape_tree(got) != want:
            raise spec.ConfigMismatch(f"{arch}: benchmark weights do not "
                                      "fit the program's parameter tree")
        member.params = make(W.member_key(seed, model))
    jax.block_until_ready([mm.params for mm in fleet.members.values()])


def build(config: dict, traffic: dict, family, devices, *,
          reduced: bool = False, **fleet_opts):
    """Router + fleet through the program's entry point, no warm-up, and
    each member checked by the configuration's ``family`` against the
    model its configuration states.
    ``reduced`` serves the program's small presets and ``fleet_opts`` go
    to the fleet as they are (CPU tests only)."""
    from repro.launch.serve import build_router
    f = config["fleet"]
    router, fleet = build_router(
        rules.render_dsl(config["router"]), reduced=reduced,
        gen_tokens=traffic["gen_tokens"], batch=f["batch"],
        max_seq=f["max_seq"], block_tokens=f["block_tokens"],
        decode_impl=f.get("decode_impl"), model_axis=f["model_axis"],
        devices=devices, seed=0, warmup=False, **fleet_opts)
    for model, prof in config["router"]["profiles"].items():
        arch = prof["arch"]
        family.check(fleet.members[arch].cfg, config["models"][model], arch)
    return router, fleet


def _drain(lane):
    while lane.pending:
        lane.step()


def warm_up(fleet, config: dict, traffic: dict, sched: Schedule):
    """Compile and run once every shape this cell's traffic uses: the
    fresh admission prefill at each width its prompts fall in, and the
    decode step."""
    from repro.serving.scheduler import bucket_len
    cap = prompt_cap(config, traffic)
    rng = np.random.default_rng(WARM_SEED)
    r = config["router"]
    archs = {rules.arch_of(r, x) for x in rules.models(r)}
    fresh = {bucket_len(q.tokens, cap, exact=False) for q in sched.requests}
    for arch in sorted(archs):
        lane = fleet.lanes[arch]
        vocab = fleet.members[arch].cfg.vocab_size
        for w in sorted(fresh):
            ids = rng.integers(4, vocab, size=min(w, cap)).astype(np.int32)
            lane.sched.submit(ids, max_new=2)
        _drain(lane)


def counters(fleet) -> Dict[str, dict]:
    out = {}
    for arch, lane in fleet.lanes.items():
        s = lane.sched
        out[arch] = dict(tokens_out=fleet.members[arch].tokens_out,
                         decode_steps=s.decode_steps, slot_steps=s.slot_steps,
                         prefill_tokens=s.prefill_tokens,
                         cached_tokens=s.cached_tokens, preempted=s.preempted,
                         slots=s.slots)
    return out


def tokens_out(fleet) -> int:
    return sum(m.tokens_out for m in fleet.members.values())


def tap(fleet, taps: Taps, *, annotate: bool):
    """Wrap each lane's ``step`` (finished sequences are kept for the
    check) and, with ``annotate``, each member's decode and prefill
    callables in ``TraceAnnotation`` spans that also record their work
    while ``taps.tracing`` is set."""
    from jax.profiler import TraceAnnotation

    for arch, lane in fleet.lanes.items():
        step = lane.step

        def lane_step(step=step, arch=arch):
            if annotate:
                with TraceAnnotation(f"bench:lane.step:{arch}"):
                    done = step()
            else:
                done = step()
            if done:
                with taps.lock:
                    taps.finished.extend((arch, s) for s in done)
            return done
        lane.step = lane_step
        if not annotate:
            continue
        m, sched = fleet.members[arch], lane.sched
        dec = m.decode_rows

        def decode_rows(*a, dec=dec, arch=arch, sched=sched):
            if taps.tracing:
                live = [i for i, s in enumerate(sched.active) if s is not None]
                taps.decode.append((arch, [int(sched.pos[i]) + 1
                                           for i in live]))
            with TraceAnnotation(f"bench:decode:{arch}"):
                return dec(*a)
        m.decode_rows = decode_rows
        for attr in ("prefill_paged_fresh", "prefill_paged_suffix"):
            fn = getattr(m, attr)

            def prefill(*a, fn=fn, arch=arch):
                if taps.tracing:
                    taps.prefill.append((arch, int(np.asarray(a[2])[0])))
                with TraceAnnotation(f"bench:prefill:{arch}"):
                    return fn(*a)
            setattr(m, attr, prefill)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def free(router, fleet):
    """Drop the program's device state so the reference has the chip."""
    for arch, lane in list(fleet.lanes.items()):
        sched = getattr(lane, "sched", None)
        if sched is not None:
            sched.cache = None
        fleet.members[arch].params = None
    fleet.lanes.clear()
    fleet.members.clear()
    del router
    gc.collect()
