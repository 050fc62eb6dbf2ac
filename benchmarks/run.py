# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only t4,...] [--json-out F]

``--only`` accepts suite keys or benchmark module names
(``t_prefix_cache`` resolves to ``prefix``, etc.).  ``--json-out``
additionally writes every executed suite's rows to one JSON file
(``{suite: [{name, us_per_call, derived}, ...]}``) — the machine-readable
convention shared with the standalone ``BENCH_*.json`` reports, so CI
uploads a single artifact covering both.
"""

import argparse
import json
import sys
import traceback

# module-name spellings accepted by --only alongside the short suite keys
ALIASES = {
    "t_decision_overhead": "decision",
    "t_prefix_cache": "prefix",
    "t_slo_burst": "slo",
    "t_disagg_decode": "disagg",
    "t_spec_decode": "spec",
}


def _prefix_rows():
    from benchmarks import t_prefix_cache
    r = t_prefix_cache.run(n=8)
    return [
        ("prefix_cache_ttft", r["paged"]["mean_ttft_ms"] * 1e3,
         f"speedup={r['ttft_speedup']:.2f}x "
         f"prefill_token_reduction={r['prefill_token_reduction']:.2f}"),
    ]


def _slo_rows():
    from benchmarks import t_slo_burst
    return t_slo_burst.rows(t_slo_burst.run(burst_n=24, premium_n=4))


def _disagg_rows():
    from benchmarks import t_disagg_decode
    return t_disagg_decode.rows(t_disagg_decode.run(long_n=3))


def _spec_rows():
    from benchmarks import t_spec_decode
    return t_spec_decode.rows(
        t_spec_decode.run(n=4, gen=24, ks=(4,), distill_steps=300))


def get_suites():
    """Suite-key -> zero-arg callable returning (name, us, derived) rows.

    Every module under benchmarks/ that a paper table cites must have a
    key here — CI greps this registry against the directory listing.
    """
    from benchmarks import (roofline_table, t4_signal_latency,
                            t5_attention_scaling, t8_lora_memory,
                            t9_scenarios, t_batch_throughput,
                            t_cache_effectiveness, t_continuous_batching,
                            t_decision_overhead, t_halugate_cost,
                            t_multimodal_fleet)
    return {
        "t4": t4_signal_latency.run,
        "t5": t5_attention_scaling.run,
        "t8": t8_lora_memory.run,
        "t9": t9_scenarios.run,
        "decision": t_decision_overhead.run,
        "cache": t_cache_effectiveness.run,
        "halugate": t_halugate_cost.run,
        "batch": t_batch_throughput.run,
        "contbatch": t_continuous_batching.run,
        "multimodal": lambda: t_multimodal_fleet.run()[0],
        "roofline": roofline_table.run,
        "prefix": _prefix_rows,
        "slo": _slo_rows,
        "disagg": _disagg_rows,
        "spec": _spec_rows,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--json-out", default="",
                    help="also write executed suites' rows to this JSON file")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    suites = get_suites()
    only = None
    if args.only:
        only = {ALIASES.get(k, k) for k in args.only.split(",")}
        unknown = only - suites.keys()
        if unknown:
            sys.exit(f"unknown suite(s): {sorted(unknown)}; "
                     f"known: {sorted(suites) + sorted(ALIASES)}")
    print("name,us_per_call,derived")
    failures = 0
    report = {}
    for key, fn in suites.items():
        if only and key not in only:
            continue
        try:
            rows = list(fn())
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}")
            report[key] = [{"name": name, "us_per_call": round(us, 1),
                            "derived": derived}
                           for name, us, derived in rows]
        except Exception:
            failures += 1
            traceback.print_exc()
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=2)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
