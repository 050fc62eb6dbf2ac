"""Find a cell's configuration, traffic mix and per-layer metric readers.

Everything is looked up by name from ``BENCHMARK.json`` at the checkout
root: a configuration is ``configs/<name>.json``, a traffic mix is
``traffic/<name>.json``, a per-layer metric is ``metrics/<name>.py``, and
a configuration's architecture is named by its ``reference``: its plain
reference is ``references/<reference>.py`` and its family (the checks,
weight layout and work counts of that architecture) is
``families/<reference>.py``.  A later change adds a cell, a mix, a metric
or an architecture by adding such files and an entry in
``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


class SpecError(RuntimeError):
    pass


class ConfigMismatch(RuntimeError):
    """The program does not run the model a configuration states."""


def differing(arch_cfg, m: dict, fields) -> List[str]:
    """Each (program field, file key) pair whose values differ between the
    program's model config and a configuration's model entry (a float to
    within 1e-12 of the file's); a key the file leaves out reads False."""
    out = []
    for ours, theirs in fields:
        a, b = getattr(arch_cfg, ours), m.get(theirs, False)
        if (a != b) if not isinstance(a, float) else abs(a - b) > 1e-12 * abs(b):
            out.append(f"{ours} {a!r} != {theirs} {b!r}")
    return out


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    family: object
    readers: Dict[str, object] = field(default_factory=dict)


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import one file as a module (metric readers have dots in their
    names, so they are loaded by path, not by import name)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    cfg = load_json(bench_dir / "configs" / f"{name}.json")
    if cfg.get("name") != name:
        raise SpecError(f"configs/{name}.json names itself {cfg.get('name')!r}")
    return cfg


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    tr = load_json(bench_dir / "traffic" / f"{name}.json")
    tr.setdefault("name", name)
    return tr


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """A per-layer metric's reader: a module with ``read(ctx) -> float |
    None`` (None: nothing to read in this run)."""
    mod = load_module(bench_dir / "metrics" / f"{name}.py",
                      "chipbench_metric_" + name.replace(".", "_"))
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metrics/{name}.py has no read(ctx)")
    return mod


def load_reference(name: str, bench_dir: Path = BENCH_DIR):
    return load_module(bench_dir / "references" / f"{name}.py",
                       "chipbench_reference_" + name)


def load_family(name: str, bench_dir: Path = BENCH_DIR):
    """An architecture's family: a module with ``check``,
    ``program_params`` and the work counts its readers use (see
    ``families/dense_gqa.py``)."""
    return load_module(bench_dir / "families" / f"{name}.py",
                       "chipbench_family_" + name)


def reports(metric: dict, cell: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell in wl


def load_cell(cell: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if cell not in by_name:
        raise SpecError(f"no workload {cell!r} in BENCHMARK.json; have "
                        + ", ".join(sorted(by_name)))
    w = by_name[cell]
    config = load_config(w["config"], bench_dir)
    c = Cell(name=cell, chips=int(w["chips"]), config=config,
             traffic=load_traffic(w["traffic"], bench_dir),
             end_to_end=[m for m in bench["end_to_end"] if reports(m, cell)],
             per_layer=[m for m in bench["per_layer"] if reports(m, cell)],
             family=load_family(config["reference"], bench_dir))
    for m in c.per_layer:
        c.readers[m["name"]] = load_reader(m["name"], bench_dir)
    return c
