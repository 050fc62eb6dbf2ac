"""One run of one cell: set up, drive the open-loop window, drain, check,
and print the result line.

    set-up   build the router and fleet through the program's entry point,
             put in the weights made from the seed, warm up the cell's own
             shapes (and fill the cache the traffic needs): ``setup_s``,
             from process start to the first request's due time;
    window   ``--seconds`` of open-loop traffic; with ``--trace 1`` the
             profiler records a slice of it;
    drain    wait for every request sent, at most ``DRAIN_S`` past the
             window;
    check    free the program's device state, run the plain reference
             over a sample of the served requests, compare.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from harness import check, spec, system
from harness import trace as T
from harness import weights as W
from harness.client import OpenLoop
from harness.peaks import peaks_for
from harness.traffic import Schedule, generate, prompt_cap

DRAIN_S = 60.0
TRACE_AT = 0.4            # traced slice starts this far into the window
TRACE_S = 6.0             # and lasts at most this long
FAILED_MS = 1e9           # latency a failed request counts with


class NoAccelerator(RuntimeError):
    pass


def devices_for(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX sees {len(devs)} "
                            f"{devs[0].platform} device(s); this benchmark "
                            "measures the chip and has no CPU fallback")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


def make_request(messages):
    from repro.core.types import Message, Request
    return Request(messages=[Message(role, text) for role, text in messages])


@dataclass
class Bench:
    cell: spec.Cell
    devices: list
    router: object
    fleet: object
    taps: system.Taps
    annotate: bool

    @property
    def models_by_arch(self) -> Dict[str, dict]:
        r = self.cell.config["router"]
        return {p["arch"]: self.cell.config["models"][m]
                for m, p in r["profiles"].items()}

    def family_of(self, arch: str):
        """The family that counts the work of the member serving ``arch``.
        Every member of a configuration is of the one family its
        ``reference`` names; readers ask per member all the same, so that
        they hold for any configuration."""
        return self.cell.family


def prepare(cell: spec.Cell, seed: int, devices, *, annotate: bool) -> Bench:
    router, fleet = system.build(cell.config, cell.traffic, cell.family,
                                 devices)
    system.install_weights(fleet, cell.config, cell.family, seed)
    taps = system.Taps()
    system.tap(fleet, taps, annotate=annotate)
    return Bench(cell, devices, router, fleet, taps, annotate)


@dataclass
class Window:
    records: list
    t0: float
    tok_rate: float
    delta: Dict[str, dict]
    drained: bool
    lateness_ms: List[float] = field(default_factory=list)
    trace_dir: Optional[str] = None
    decode_calls: list = field(default_factory=list)
    prefill_calls: list = field(default_factory=list)


def _route_fn(bench: Bench):
    if not bench.annotate:
        return bench.router.route
    from jax.profiler import TraceAnnotation

    def route(req):
        with TraceAnnotation("bench:router.route"):
            return bench.router.route(req)
    return route


def _delta(a: Dict[str, dict], b: Dict[str, dict]) -> Dict[str, dict]:
    return {k: {c: (b[k][c] - a[k][c]) if c != "slots" else b[k][c]
                for c in b[k]} for k in b}


def run_window(bench: Bench, sched: Schedule, *, trace: bool = False,
               drain_s: float = DRAIN_S) -> Window:
    fleet, taps = bench.fleet, bench.taps
    loop = OpenLoop(_route_fn(bench), make_request, sched)
    seconds = sched.seconds
    state = {}
    tracer = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        state["trace_dir"] = tdir
        tracer = threading.Thread(target=_trace_slice,
                                  args=(bench, loop, seconds, tdir),
                                  daemon=True)

    def on_tick(now):
        if "end" not in state and now >= loop.t0 + seconds:
            state["end"] = (now, system.tokens_out(fleet),
                            system.counters(fleet))

    taps.decode.clear()
    taps.prefill.clear()
    c0 = system.counters(fleet)
    tok0 = system.tokens_out(fleet)
    t0 = time.perf_counter()
    if tracer is not None:
        loop.t0 = t0
        tracer.start()
    loop.run(t0, on_tick)
    t_end, tok1, c1 = state["end"]
    drained = loop.drain(t0 + seconds + drain_s)
    if tracer is not None:
        tracer.join()
    late = [(r.t_call - (t0 + r.due)) * 1e3 for r in loop.records
            if r.t_call]
    return Window(loop.records, t0, (tok1 - tok0) / (t_end - t0),
                  _delta(c0, c1), drained, late, state.get("trace_dir"),
                  list(taps.decode), list(taps.prefill))


def _trace_slice(bench: Bench, loop: OpenLoop, seconds: float, tdir: str):
    import jax
    from jax.profiler import TraceAnnotation
    length = min(TRACE_S, 0.25 * seconds)
    wait = loop.t0 + TRACE_AT * seconds - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    jax.profiler.start_trace(tdir)
    try:
        with TraceAnnotation(T.WINDOW_SPAN):
            bench.taps.tracing = True
            time.sleep(length)
            bench.taps.tracing = False
    finally:
        jax.profiler.stop_trace()


def pct(vals, q) -> float:
    return float(np.percentile(np.asarray(vals, float), q))


def ttfts_ms(records, t0: float) -> List[float]:
    """Due time to first token: the response time less the program's own
    first-token-to-last-token time (the fleet reports t_first only as
    vsr_ttft_ms from submission), so the response side's stages count."""
    return [(r.t_resp - t0 - r.due) * 1e3 - (r.service_ms - r.prog_ttft_ms)
            if r.done and not r.error and r.tokens > 0 else FAILED_MS
            for r in records]


def tpots_ms(records) -> List[float]:
    return [(r.service_ms - r.prog_ttft_ms) / (r.tokens - 1)
            for r in records if r.done and not r.error and r.tokens > 1]


def end_to_end(win: Window, setup_s: float) -> Dict[str, float]:
    recs = win.records
    tt = ttfts_ms(recs, win.t0)
    tp = tpots_ms(recs) or [FAILED_MS]
    return {"setup_s": setup_s,
            "ttft_p50_ms": pct(tt, 50), "ttft_p90_ms": pct(tt, 90),
            "tpot_p90_ms": pct(tp, 90), "out_tok_s": win.tok_rate}


def layer_context(bench: Bench, win: Window, tr: Optional[T.Trace]):
    f = bench.cell.config["fleet"]
    return SimpleNamespace(
        records=win.records,
        ok=[r for r in win.records if r.done and not r.error],
        delta=win.delta, trace=tr, decode_calls=win.decode_calls,
        prefill_calls=win.prefill_calls,
        models_by_arch=bench.models_by_arch, family_of=bench.family_of,
        block_tokens=f["block_tokens"], chips=len(bench.devices),
        peak=peaks_for(bench.devices[0].device_kind))


def correctness(bench: Bench, win: Window, seed: int, *,
                modes=("f32",)) -> Dict[str, dict]:
    """Per model, the reference's logits over a sample of the served
    requests (one list per mode), with the inputs that made them."""
    cfg, tr = bench.cell.config, bench.cell.traffic
    ref_mod = spec.load_reference(cfg["reference"])
    served = check.served_by_prompt(bench.taps.finished)
    cap = prompt_cap(cfg, tr)
    out = {}
    for model, prof in cfg["router"]["profiles"].items():
        recs = check.sample(win.records, model,
                            cfg["correct"]["sample_per_model"], seed)
        if not recs:
            continue
        m = cfg["models"][model]
        seqs, pos, toks = check.gap_inputs(recs, m, prof["arch"], served,
                                           cap, tr["gen_tokens"])
        res = {"toks": toks, "served_tokens": sum(len(t) for t in toks)}
        for mode in modes:
            ref = ref_mod.Reference(m, W.member_key(seed, model),
                                    max_seq=cfg["fleet"]["max_seq"], mode=mode)
            res[mode] = ref.logits_at(seqs, pos)
        out[model] = res
    return out


def judge(bench: Bench, win: Window, seed: int, *, free: bool = True,
          refs: Optional[Dict[str, dict]] = None, control: bool = False):
    """(correct, checks): each number compared beside its limit.  With
    ``free`` the program's device state is dropped before the reference
    runs (it then has the chip to itself).  ``refs`` are the reference's
    readings where the caller has them already.  With ``control`` each
    served token is replaced by the one the int8 control puts first at
    its position (``refs`` must hold the "int8" mode): the verdict a
    lower-precision program would get at the same limits."""
    cell = bench.cell
    fails = check.failures(win.records, cell.traffic["gen_tokens"])
    routes = check.route_mismatches(win.records, cell.config["router"])
    checks = {"failed": {"value": len(fails), "limit": 0},
              "route_mismatch": {"value": len(routes), "limit": 0}}
    for line in (fails + routes)[:20]:
        print("  " + line, file=sys.stderr)
    if free:
        system.free(bench.router, bench.fleet)
        bench.router = bench.fleet = None
    limits = cell.config["correct"]["mean_logit_gap"]
    if refs is None:
        try:
            refs = correctness(bench, win, seed)
        except LookupError as e:
            print(f"  {e}", file=sys.stderr)
            refs = {}
            checks["mean_logit_gap"] = {"value": FAILED_MS, "limit": 0}
    for model, res in refs.items():
        print(f"  {model}: {res['served_tokens']} served tokens compared",
              file=sys.stderr)
        toks = check.control_tokens(res["int8"]) if control else res["toks"]
        checks[f"mean_logit_gap.{model}"] = {
            "value": check.mean_gap(res["f32"], toks), "limit": limits[model]}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def main(argv: List[str], t_process: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    cell = spec.load_cell(a.workload)
    try:
        devices = devices_for(cell.chips)
    except NoAccelerator as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)

    sched = generate(cell.traffic, cell.config, a.seed, a.seconds)
    bench = prepare(cell, a.seed, devices, annotate=bool(a.trace))
    system.warm_up(bench.fleet, cell.config, cell.traffic, sched)
    setup_s = time.perf_counter() - t_process
    win = run_window(bench, sched, trace=bool(a.trace))
    print(f"window: {len(win.records)} requests sent, drained "
          f"{win.drained}; generator lateness ms p50 "
          f"{pct(win.lateness_ms, 50):.3f} p99 {pct(win.lateness_ms, 99):.3f}"
          f" max {max(win.lateness_ms):.3f}", flush=True)
    mem = system.memory_peak(devices)

    result = {"correct": False, "attempted": len(win.records), "failed": 0,
              "metrics": {}, "device": {
                  "platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": mem}}
    if a.trace:
        xp = T.find_xplane(win.trace_dir)
        tr = T.load(xp, [d.id for d in devices]).clip() if xp else None
        ctx = layer_context(bench, win, tr)
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            result["device"]["busy_s"] = T.busy_s(tr)
            result["device"]["window_s"] = tr.window_s
            result["breakdown"] = {"device_ops": T.top_ops(tr),
                                   "idle_gaps": T.idle_gaps(tr)}
        shutil.rmtree(win.trace_dir, ignore_errors=True)
    else:
        e2e = end_to_end(win, setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    result["correct"], checks = judge(bench, win, a.seed)
    result["failed"] = checks["failed"]["value"]
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    if not win.drained:
        # client threads still block inside the freed fleet: leave now
        os._exit(0)
    return 0
