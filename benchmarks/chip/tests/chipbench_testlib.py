"""Small stand-ins for the benchmark's cells, for CPU tests.

A small cell keeps a configuration's router, fleet layout and traffic
shape, with the program's reduced model presets in place of the published
widths, short prompts and few output tokens.  The harness is driven
through its own functions; only its look for a chip is left out.
"""

from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

MAX_SEQ = 160
GEN = 8


DENSE = spec.load_family("dense_gqa")


def small_model(arch: str, family=DENSE, dtype: str = "bfloat16") -> dict:
    """The configuration entry of the program's reduced preset of
    ``arch``, as ``family`` states a model."""
    from repro.configs import get_reduced
    return family.entry_for(get_reduced(arch), dtype)


def dense_cfg(layers: int, **fields):
    """A program model of ``layers`` dense attention blocks."""
    from repro.models.config import BlockSpec, LayerGroup, ModelConfig
    return ModelConfig(name="stand-in", family="dense",
                       groups=(LayerGroup((BlockSpec("attn", "dense"),),
                                          layers),), **fields)


# At the reduced presets (CPU run): sound runs' mean logit gap read
# 0-0.00007 a model, the planted faults' 0.031-0.42.
SMALL_LIMIT = 0.005


def small_config(name: str, limit: float = SMALL_LIMIT) -> dict:
    cfg = copy.deepcopy(spec.load_config(name))
    family = spec.load_family(cfg["reference"])
    for model, prof in cfg["router"]["profiles"].items():
        cfg["models"][model] = small_model(prof["arch"], family)
    cfg["fleet"].update(batch=4, max_seq=MAX_SEQ, model_axis=1)
    if cfg["fleet"]["decode_impl"] != "flash_paged":
        cfg["fleet"]["decode_impl"] = "xla"
    cfg["correct"] = {"sample_per_model": 2,
                      "mean_logit_gap": {m: limit for m in cfg["models"]}}
    return cfg


def small_traffic(name: str) -> dict:
    tr = copy.deepcopy(spec.load_traffic(name))
    tr["gen_tokens"] = GEN
    tr["rate_rps"] = 4.0
    tr["prompt_tokens"] = {"dist": "lognormal", "median": 24,
                           "sigma": 0.8, "min": 4, "max": 120}
    return tr


def small_cell(workload: str, limit: float = SMALL_LIMIT) -> spec.Cell:
    import json
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = {x["name"]: x for x in bench["workloads"]}[workload]
    cfg = small_config(w["config"], limit)
    return spec.Cell(name=workload, chips=1, config=cfg,
                     traffic=small_traffic(w["traffic"]),
                     end_to_end=bench["end_to_end"], per_layer=[],
                     family=spec.load_family(cfg["reference"]))


# A mid-size dense GQA member the CPU can serve: width 256, 4 layers,
# vocab 2048, where an int8 path's logits already part from bfloat16's.
MID_FIELDS = dict(d_model=256, d_ff=512, n_heads=8, n_kv_heads=4,
                  head_dim=32, vocab_size=2048, depth_mult=2)


def mid_cell(workload: str, limit: float) -> spec.Cell:
    """A small cell whose members are served at ``MID_FIELDS`` widths (the
    reduced presets' two layers doubled), 32 tokens out and four answers
    a model compared: 128 served tokens a model."""
    from repro.configs import get_reduced
    from repro.serving.fleet import _apply_arch_overrides
    cell = small_cell(workload, limit)
    for model, prof in cell.config["router"]["profiles"].items():
        mid = _apply_arch_overrides(get_reduced(prof["arch"]), MID_FIELDS)
        cell.config["models"][model] = cell.family.entry_for(mid)
    cell.config["correct"]["sample_per_model"] = 4
    cell.traffic["gen_tokens"] = 32
    return cell


def mid_overrides(cell) -> dict:
    return {p["arch"]: dict(MID_FIELDS)
            for p in cell.config["router"]["profiles"].values()}


def prepare_small(cell, seed: int, **fleet_opts):
    """The harness's set-up on the CPU's one device, at reduced presets
    (``fleet_opts`` as ``system.build`` takes them)."""
    import jax
    from harness import cell as C
    from harness import system
    router, fleet = system.build(cell.config, cell.traffic, cell.family,
                                 jax.devices()[:1], reduced=True,
                                 **fleet_opts)
    system.install_weights(fleet, cell.config, cell.family, seed)
    taps = system.Taps()
    system.tap(fleet, taps, annotate=False)
    return C.Bench(cell, jax.devices()[:1], router, fleet, taps, False)
