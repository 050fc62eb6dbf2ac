"""Mixture-of-Modality fleet: mixed text/image/audio traffic routed by the
``modality`` signal to three backend lanes (AR text, diffusion stub,
whisper transcription) of ONE LocalFleet.

Two measurements:

1. **one route_batch** of mixed requests — the acceptance scenario: all
   three lanes are exercised inside a single ``route_batch()`` call, with
   per-lane TTFT / service time reported from the transport's per-request
   usage fields (``vsr_lane`` / ``vsr_ttft_ms`` / ``vsr_service_ms``).
2. **staggered arrival stream** — mixed arrivals submitted on a clock and
   coalesced into route_batch windows; per-lane throughput.

  PYTHONPATH=src python -m benchmarks.t_multimodal_fleet [--smoke]
"""

import argparse
import time

TEXT_PROMPTS = [
    "solve the integral of x^2 and prove the series converges",
    "debug this python function, the api returns a 500 error",
    "summarize the incident report for tonight",
]
IMAGE_PROMPTS = [
    "draw an illustration of a fox in a forest",
    "generate an image of a sailboat logo",
    "render a sketch of the city skyline",
]
AUDIO_PROMPTS = [
    "transcribe this voice memo from the standup",
    "please transcribe the attached podcast recording",
    "transcription of the spoken interview audio",
]


def _mixed(n):
    """Round-robin text/image/audio prompts, n total."""
    out = []
    pools = (TEXT_PROMPTS, IMAGE_PROMPTS, AUDIO_PROMPTS)
    for i in range(n):
        pool = pools[i % 3]
        out.append(pool[(i // 3) % len(pool)] + f" (case {i})")
    return out


def _lane_stats(results):
    """Per-lane (count, mean ttft ms, mean service ms) from responses."""
    stats = {}
    for resp, _out in results:
        lane = resp.usage.get("vsr_lane", "text")
        s = stats.setdefault(lane, {"n": 0, "ttft": 0.0, "service": 0.0})
        s["n"] += 1
        s["ttft"] += float(resp.usage.get("vsr_ttft_ms", 0.0))
        s["service"] += float(resp.usage.get("vsr_service_ms", 0.0))
    return {lane: (s["n"], s["ttft"] / s["n"], s["service"] / s["n"])
            for lane, s in stats.items()}


def run(n=12, gen_tokens=8, stream_batches=3):
    from repro.core.types import Message, Request
    from repro.launch.serve import build_dsl, build_router

    router, fleet = build_router(
        build_dsl(("text", "image", "audio")), reduced=True,
        gen_tokens=gen_tokens)
    reqs = [Request(messages=[Message("user", p)], user=f"user{i % 3}")
            for i, p in enumerate(_mixed(n))]

    # 1 — acceptance scenario: ONE route_batch over all three lanes
    t0 = time.perf_counter()
    results = router.route_batch(reqs)
    batch_s = time.perf_counter() - t0
    stats = _lane_stats(results)
    rows = []
    for lane in ("text", "image", "audio"):
        cnt, ttft, service = stats.get(lane, (0, 0.0, 0.0))
        rows.append((f"mm_batch_{lane}", ttft * 1e3,
                     f"n={cnt} mean_ttft_ms={ttft:.2f} "
                     f"mean_service_ms={service:.2f}"))
    rows.append(("mm_batch_total", batch_s * 1e6,
                 f"requests={n} lanes={len(stats)} "
                 f"qps={n / batch_s:.1f}"))

    # 2 — staggered arrival stream coalesced into route_batch windows
    t0 = time.perf_counter()
    served = 0
    lane_n = {}
    for b in range(stream_batches):
        window = [Request(messages=[Message("user", p)],
                          user=f"user{(served + i) % 3}")
                  for i, p in enumerate(_mixed(n))]
        for resp, _out in router.route_batch(window):
            lane_n[resp.usage.get("vsr_lane", "text")] = \
                lane_n.get(resp.usage.get("vsr_lane", "text"), 0) + 1
        served += len(window)
    stream_s = time.perf_counter() - t0
    rows.append(("mm_stream_qps", stream_s / max(1, served) * 1e6,
                 f"requests={served} qps={served / stream_s:.1f} "
                 f"per_lane={sorted(lane_n.items())}"))
    lanes_hit = len(stats)
    return rows, lanes_hit


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run (fewer requests / tokens)")
    ap.add_argument("--requests", type=int, default=0)
    args = ap.parse_args(argv)
    n = args.requests or (6 if args.smoke else 12)
    rows, lanes_hit = run(n=n, gen_tokens=4 if args.smoke else 8,
                          stream_batches=1 if args.smoke else 3)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    ok = lanes_hit == 3
    print(f"three lanes exercised in one route_batch: {ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
