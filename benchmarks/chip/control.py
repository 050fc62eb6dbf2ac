#!/usr/bin/env python3
"""Readings that a cell's logit-gap limits are set from.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 11,12,13 [--seconds 20]

One process builds the cell once.  For each seed it puts in that seed's
weights, drives a short open-loop window at the cell's own load, drains,
and over the same sample of served requests that a run compares it
reads each model's mean logit gap two ways, through the run's own
comparison (``judge``) at the cell's limits:

``program``  the mean gap between the served tokens' logits and the
             float32 reference's best: what a sound run reads (the lower
             reading);
``control``  the mean gap of the tokens that the reference computed in int8
             (W8A8, the precision below the bfloat16 served) puts first:
             what a lower-precision path would read (the upper reading).

Each seed's line says whether the run came out correct and whether the
control would have.  A limit lies between the largest ``program`` and the
smallest ``control`` reading.  The reference runs once the program's
device state is freed, after every seed's window, as in a run.  The
benchmark's own runs never run this.  It needs the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    a = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from harness import cell as C
    from harness import spec, system
    from harness.traffic import generate

    cell = spec.load_cell(a.workload)
    devices = C.devices_for(cell.chips)
    seeds = [int(x) for x in a.seeds.split(",")]
    bench = C.prepare(cell, seeds[0], devices, annotate=False)
    runs = []
    for seed in seeds:
        system.install_weights(bench.fleet, cell.config, cell.family, seed)
        sched = generate(cell.traffic, cell.config, seed, a.seconds)
        system.warm_up(bench.fleet, cell.config, cell.traffic, sched)
        bench.taps.finished.clear()
        win = C.run_window(bench, sched)
        runs.append((seed, win, list(bench.taps.finished)))
    # the reference runs once the program's device state is freed, as in
    # a run
    system.free(bench.router, bench.fleet)
    bench.router = bench.fleet = None
    lows, highs = {}, {}
    for seed, win, finished in runs:
        bench.taps.finished = finished
        refs = C.correctness(bench, win, seed, modes=("f32", "int8"))
        ok, checks = C.judge(bench, win, seed, free=False, refs=refs)
        ok_c, checks_c = C.judge(bench, win, seed, free=False, refs=refs,
                                 control=True)
        row = {"seed": seed, "sent": len(win.records), "correct": ok,
               "control_correct": ok_c}
        for k in checks:
            if not k.startswith("mean_logit_gap."):
                continue
            p, c = checks[k]["value"], checks_c[k]["value"]
            row[k] = {"program": p, "control": c,
                      "limit": checks[k]["limit"]}
            lows[k] = max(lows.get(k, 0.0), p)
            highs[k] = min(highs.get(k, float("inf")), c)
        row["served_tokens"] = {m: r["served_tokens"] for m, r in refs.items()}
        row["failed"] = checks["failed"]["value"]
        row["route_mismatch"] = checks["route_mismatch"]["value"]
        print("CONTROL " + json.dumps(row), flush=True)
    print("READINGS " + json.dumps({"lower": lows, "upper": highs}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
