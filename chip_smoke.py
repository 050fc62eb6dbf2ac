#!/usr/bin/env python3
"""Smoke run of the semantic router and its model fleet on a TPU.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the sharded path, on four chips

One chip: ``build_router`` serves smollm-360m (dense GQA, 32 layers,
d_model 960) and qwen3-1.7b (28 layers, d_model 2048) at their published
widths with random weights from a fixed seed, paged KV at batch 8 and
max_seq 2048, and ``decode_impl="flash_paged"`` (the paged Pallas decode
kernel).  A DSL with one decision per model routes 16 requests through
``router.route_batch``.  The script then checks, per member, that the
compiled decode step holds the Mosaic kernel (``tpu_custom_call``) and
that its logits equal the XLA decode path's on the same paged cache
(``PAGED_TOL``) with the same greedy token in every row.

Four chips: glm4-9b at published widths (about 18.8 GB of bf16 weights,
more than one 16 GB chip holds) served 4-way over the mesh "model" axis
through the same entry point, plus a reference: glm4-9b cut to
``CUT_LAYERS`` layers, run on one chip and 4-way sharded, whose decode
logits must agree within ``SHARDED_TOL``.

Every phase prints what it measured; any failure exits non-zero.  The
last line of a passing run is one JSON object naming the device.  There
is no CPU fallback: without a TPU the script fails at once.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

BATCH = 8
MAX_SEQ = 2048
GEN_TOKENS = 16
SEED = 0
CUT_LAYERS = 4
# max |got - ref| over max |ref| of a decode step's logits.
# flash_paged vs XLA decode read the very same paged cache, and the kernel
# keeps the XLA path's op order, so they must agree bit for bit (they read
# 0.0 on TPU v5e at kv lengths 4..1501); any off-by-one frontier or wrong
# page shows.
PAGED_TOL = 0.0
# 4-way tensor-parallel partial sums vs one chip differ in summation order,
# which a bf16 residual stream carries to the logits: glm4-9b cut to 4
# layers reads 1.22e-2 on TPU v5e; a wrong shard or mask moves them by O(1).
SHARDED_TOL = 5e-2

SMOKE_DSL = '''
SIGNAL keyword reasoning { operator: "any", keywords: ["prove", "derive", "integral", "theorem"] }
SIGNAL keyword chat { operator: "any", keywords: ["hello", "recipe", "weather", "thanks"] }

ROUTE reasoning (description = "multi-step reasoning to the larger dense model") {
  PRIORITY 200
  WHEN keyword("reasoning")
  MODEL "qwen3"
}

ROUTE chat (description = "small talk to the small dense model") {
  PRIORITY 100
  WHEN keyword("chat")
  MODEL "smollm"
}

BACKEND local_pool vllm { address: "127.0.0.1", port: 8000 }
GLOBAL {
  default_model: "smollm",
  strategy: "priority",
  model_profiles: {
    "qwen3": { cost_per_mtok: 0.3, quality: 0.65, arch: "qwen3-1.7b" },
    "smollm": { cost_per_mtok: 0.05, quality: 0.4, arch: "smollm-360m" }
  }
}
'''

GLM_DSL = '''
SIGNAL keyword reasoning { operator: "any", keywords: ["prove", "derive", "integral", "theorem"] }

ROUTE reasoning (description = "reasoning to the sharded large model") {
  PRIORITY 200
  WHEN keyword("reasoning")
  MODEL "glm4"
}

BACKEND local_pool vllm { address: "127.0.0.1", port: 8000 }
GLOBAL {
  default_model: "glm4",
  strategy: "priority",
  model_profiles: {
    "glm4": { cost_per_mtok: 0.9, quality: 0.8, arch: "glm4-9b" }
  }
}
'''

REASONING = ["Prove that the square root of two is irrational",
             "Derive the gradient of softmax cross entropy step by step",
             "Evaluate the integral of x squared times sine x",
             "State and prove the fundamental theorem of arithmetic"]
CHAT = ["hello there, how are you today",
        "Share a recipe for a quick weeknight pasta",
        "What will the weather be like this weekend",
        "thanks for the help earlier, one more question"]


class SmokeFailure(RuntimeError):
    pass


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name):
    t0 = time.perf_counter()
    print(f"== {name}", flush=True)
    try:
        yield
    except Exception:
        traceback.print_exc()
        print(f"FAIL in phase {name!r}", flush=True)
        sys.exit(1)
    print(f"   {name}: {time.perf_counter() - t0:.2f}s", flush=True)


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def bytes_by_device(tree) -> dict:
    """Bytes of ``tree``'s arrays held on each device (shards counted
    where they live; a replicated leaf counts on every device)."""
    import jax
    out = {}
    for x in jax.tree.leaves(tree):
        for s in x.addressable_shards:
            out[s.device.id] = out.get(s.device.id, 0) + s.data.nbytes
    return out


def requests(texts, n):
    from repro.core.types import Message, Request
    return [Request(messages=[Message("user", texts[i % len(texts)])],
                    user=f"user{i % 3}") for i in range(n)]


def serve(router, reqs, want_tokens):
    """Route ``reqs`` in one ``route_batch``; every request must come
    back generated by a fleet member.  Returns {model: requests}."""
    results = router.route_batch(reqs)
    require(len(results) == len(reqs),
            f"{len(results)} responses for {len(reqs)} requests")
    per_model = {}
    for i, (resp, out) in enumerate(results):
        ntok = int(resp.usage.get("completion_tokens", 0))
        print(f"   [{i:02d}] decision={out.decision or '-':10s} "
              f"model={out.model:8s} finish={resp.finish_reason} "
              f"tokens={ntok}", flush=True)
        require(resp.finish_reason == "stop" and ntok == want_tokens,
                f"request {i} not answered: {resp.finish_reason} "
                f"{resp.content[:200]}")
        per_model[out.model] = per_model.get(out.model, 0) + 1
    return per_model


def admit_rows(lane, prompts):
    """Submit ``prompts`` to a text lane and step it until every prompt
    holds a decode slot (admission prefill done, decoding under way)."""
    sched = lane.sched
    for p in prompts:
        lane.submit(p, max_new=GEN_TOKENS)
    for _ in range(64 * len(prompts)):
        if all(s is not None for s in sched.active[:len(prompts)]):
            return
        lane.step()
    raise SmokeFailure("admitted rows never filled the decode batch")


def decode_logits(fleet, arch, decode_impl):
    """Logits of one decode step over the lane's live paged cache (no
    donation, so both attention paths read the very same cache)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as MD
    from repro.sharding import rules as R
    from repro.sharding.ctx import sharding_rules
    m, sched = fleet.members[arch], fleet.lanes[arch].sched
    cfg = m.cfg.replace(decode_impl=decode_impl)
    cache = dict(sched.cache, pos=jnp.asarray(sched.pos, jnp.int32),
                 tbl=jnp.asarray(sched.tbl))
    toks = jnp.asarray(sched.last_tok[:, None])
    fn = jax.jit(lambda p, t, c: MD.decode_step(cfg, p, t, c,
                                                moe_impl="ep")[0])
    with sharding_rules(fleet.mesh, R.act_rules(fleet.mesh, m.batch)):
        return np.asarray(fn(m.params, toks, cache), np.float32)


def compare_logits(name, got, ref, tol, *, same_greedy):
    """Fails unless max|got - ref| / max|ref| <= ``tol`` and, with
    ``same_greedy``, every row's argmax agrees."""
    import numpy as np
    err = float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    print(f"   {name}: max|d|/max|ref| = {err:.3e} (tol {tol:.0e}), "
          f"greedy agreement {agree:.3f} over {got.shape[0]} rows, "
          f"max|ref| = {np.abs(ref).max():.3f}", flush=True)
    require(np.isfinite(got).all() and np.isfinite(ref).all(),
            f"{name}: non-finite logits")
    require(err <= tol, f"{name}: logits differ by {err:.3e}")
    require(agree == 1.0 or not same_greedy,
            f"{name}: greedy tokens differ in {1 - agree:.3f} of the rows")
    return err, agree


def check_prompts():
    """Prompts of 3 to ~1500 tokens: one block, many blocks, and the
    widest prefill bucket."""
    words = [f"w{i}" for i in range(1600)]
    return [" ".join(words[:n]) for n in (3, 17, 40, 64, 100, 300, 700,
                                          1500)]


def run_one_chip(devices, *, reduced=False, max_seq=MAX_SEQ):
    """The main path on one device.  Returns a report dict."""
    import jax
    from repro.launch.serve import build_router
    report = {}
    with phase("build router + fleet (params, paged pools, warmup)"):
        t0 = time.perf_counter()
        router, fleet = build_router(
            SMOKE_DSL, reduced=reduced, gen_tokens=GEN_TOKENS, batch=BATCH,
            max_seq=max_seq, decode_impl="flash_paged", devices=devices,
            seed=SEED)
        report["build_s"] = time.perf_counter() - t0
        for arch, m in fleet.members.items():
            pool = {k: v for k, v in fleet.lanes[arch].sched.cache.items()
                    if k.startswith("g")}
            print(f"   {arch}: layers={sum(g.repeats for g in m.cfg.groups)}"
                  f" d_model={m.cfg.d_model} heads={m.cfg.n_heads}/"
                  f"{m.cfg.n_kv_heads} vocab={m.cfg.vocab_size} "
                  f"param_bytes={tree_bytes(m.params)} "
                  f"pool_bytes={tree_bytes(pool)} blocks={m.num_blocks} "
                  f"warmup_s={m.warmup_ms / 1e3:.2f}", flush=True)
        print(f"   build+warmup total {report['build_s']:.2f}s", flush=True)
    with phase("route 16 requests through route_batch"):
        t0 = time.perf_counter()
        per_model = serve(router, requests(REASONING, 8)
                          + requests(CHAT, 8), GEN_TOKENS)
        print(f"   requests per model: {per_model}", flush=True)
        require(per_model.get("qwen3", 0) >= 1
                and per_model.get("smollm", 0) >= 1,
                f"both models must get traffic: {per_model}")
        report["tokens"] = sum(m.tokens_out for m in fleet.members.values())
        report["requests"] = sum(per_model.values())
        print(f"   tokens generated {report['tokens']} in "
              f"{time.perf_counter() - t0:.2f}s", flush=True)
    report["custom_calls"], report["logit_err"] = {}, {}
    for arch, m in fleet.members.items():
        with phase(f"{arch}: compiled flash_paged decode step"):
            sched = fleet.lanes[arch].sched
            toks = jax.numpy.asarray(sched.last_tok[:, None])
            text = m.decode_rows.lower(m.params, toks,
                                       sched.cache).compile().as_text()
            n = text.count("tpu_custom_call")
            report["custom_calls"][arch] = n
            print(f"   tpu_custom_call count: {n}", flush=True)
        with phase(f"{arch}: flash_paged vs XLA decode logits"):
            lane = fleet.lanes[arch]
            admit_rows(lane, check_prompts())
            print(f"   kv lengths {lane.sched.pos.tolist()}", flush=True)
            flash = decode_logits(fleet, arch, "flash_paged")
            xla = decode_logits(fleet, arch, "xla")
            report["logit_err"][arch] = compare_logits(
                arch, flash, xla, PAGED_TOL, same_greedy=True)
            lane.sched.drain()
    stats = devices[0].memory_stats() or {}
    report["peak_bytes"] = stats.get("peak_bytes_in_use")
    print(f"peak_bytes_in_use {report['peak_bytes']} "
          f"bytes_limit {stats.get('bytes_limit')}", flush=True)
    return report


def _cut_glm(reduced):
    from dataclasses import replace
    from repro.configs import get_config, get_reduced
    cfg = get_reduced("glm4-9b") if reduced else get_config("glm4-9b")
    layers = 1 if reduced else CUT_LAYERS
    return {"glm4-9b": {"groups": tuple(replace(g, repeats=layers)
                                        for g in cfg.groups)}}


def run_four_chips(devices, *, reduced=False, max_seq=MAX_SEQ):
    """glm4-9b 4-way over the model axis, and its depth-cut reference."""
    from repro.launch.serve import build_router
    from repro.serving.fleet import LocalFleet
    arch = "glm4-9b"
    report = {}
    with phase(f"reference: {arch} cut in depth, 1 chip vs 4-way sharded"):
        opts = dict(reduced=reduced, batch=BATCH, max_seq=max_seq,
                    gen_tokens=GEN_TOKENS, seed=SEED, warmup=False,
                    arch_overrides=_cut_glm(reduced))
        one = LocalFleet([arch], devices=devices[:1], **opts)
        four = LocalFleet([arch], devices=devices, model_axis=len(devices),
                          **opts)
        prompts = check_prompts()
        for f in (one, four):
            admit_rows(f.lanes[arch], prompts)
        ref = decode_logits(one, arch, "xla")
        got = decode_logits(four, arch, "xla")
        report["logit_err"] = compare_logits(
            f"{arch} cut 4-way vs 1 chip", got, ref, SHARDED_TOL,
            same_greedy=False)
        outs = [f.lanes[arch].sched.drain() for f in (one, four)]
        toks = [{s.rid: list(s.out) for s in o} for o in outs]
        for rid in sorted(toks[0]):
            print(f"   rid {rid}: 1 chip {toks[0][rid][:8]} | "
                  f"4-way {toks[1].get(rid, [])[:8]}", flush=True)
        del one, four
        gc.collect()
    with phase(f"{arch} at published widths, model_axis={len(devices)}"):
        t0 = time.perf_counter()
        router, fleet = build_router(
            GLM_DSL, reduced=reduced, gen_tokens=GEN_TOKENS, batch=BATCH,
            max_seq=max_seq, model_axis=len(devices), devices=devices,
            seed=SEED)
        m = fleet.members[arch]
        total = tree_bytes(m.params)
        per_dev = bytes_by_device(m.params)
        print(f"   build+warmup {time.perf_counter() - t0:.2f}s "
              f"layers={sum(g.repeats for g in m.cfg.groups)} "
              f"d_model={m.cfg.d_model} param_bytes={total}", flush=True)
        print(f"   param bytes by device: {per_dev}", flush=True)
        # a member that landed wholly on one device would hold all of it
        worst = max(per_dev.values()) / total
        require(len(per_dev) == len(devices) and worst < 0.5,
                f"params not spread over the mesh: {per_dev}")
        per_model = serve(router, requests(REASONING, BATCH), GEN_TOKENS)
        print(f"   requests per model: {per_model}", flush=True)
        pool = {k: v for k, v in fleet.lanes[arch].sched.cache.items()
                if k.startswith("g")}
        print(f"   pool bytes by device: {bytes_by_device(pool)}",
              flush=True)
        for d in devices:
            st = d.memory_stats() or {}
            print(f"   device {d.id}: peak_bytes_in_use "
                  f"{st.get('peak_bytes_in_use')}", flush=True)
        report["requests"] = sum(per_model.values())
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the fleet on one chip; 4: glm4-9b sharded "
                         "over four chips and its reference")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"FAIL: no TPU found — JAX sees {len(devices)} "
              f"{devices[0].platform} device(s); chip_smoke.py needs a TPU "
              "chip and has no CPU fallback", file=sys.stderr)
        return 2
    if not (SRC / "repro").is_dir():
        print(f"FAIL: the repro sources are not at {SRC}", file=sys.stderr)
        return 2
    require(len(devices) >= args.chips,
            f"--chips {args.chips} needs {args.chips} devices, "
            f"have {len(devices)}")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)

    use = devices[:args.chips]
    if args.chips == 1:
        report = run_one_chip(use)
        with phase("chip checks"):
            require(all(n > 0 for n in report["custom_calls"].values()),
                    f"decode step without a Mosaic kernel: "
                    f"{report['custom_calls']}")
            require(report["requests"] >= 8, "fewer than 8 requests")
    else:
        run_four_chips(use)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(use)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
