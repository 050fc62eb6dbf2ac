"""The program's own spans in a profiler trace, and the numbers read from
them.

The program opens ``vsr:<name>`` spans on the profiler's clock
(``repro.core.observability.trace_span``): ``vsr:sched.step`` around each
lane step, inside it the prefill and decode phases, and a span whose name
ends in ``.fetch`` wherever the host waits for a device result.  ``load``
reads them from a ``.xplane.pb`` with the host line (OS thread) each was
recorded on; Python thread names do not reach the trace, so a line is
known by its index.
"""

from __future__ import annotations

import functools
import glob
import os
import tempfile
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from harness import trace as T

PREFIX = "vsr:"
STEP = "vsr:sched.step"
FETCH = ".fetch"
# where ``cell.run_window`` has the profiler write a traced slice
TRACE_DIRS = "chipbench-trace-*"


@dataclass
class Span(T.Event):
    line: int = 0                      # host line (thread) it was recorded on


@dataclass
class Slice:
    """A traced slice as the program's spans see it."""
    spans: List[Span]
    window: Optional[Tuple[float, float]] = None   # trace's clock, s


@functools.lru_cache(maxsize=4)
def load(path: str) -> Slice:
    """Every ``vsr:`` host span of a trace file and its ``bench:window``,
    on the trace's clock in seconds (each file is read once)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, out, line = None, [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, e.start_ns * 1e-9,
                                    e.end_ns * 1e-9, line))
                elif e.name == T.WINDOW_SPAN:
                    window = (e.start_ns * 1e-9, e.end_ns * 1e-9)
            line += 1
    return Slice(out, window)


def of(ctx) -> Optional[Slice]:
    """The traced slice of the run a metric reader is given (None in a
    run with no trace): the file in the newest ``chipbench-trace-*``
    directory, which ``cell.run_window`` made for this run and removes
    only after the readers ran."""
    if ctx.trace is None:
        return None
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIRS))
    if not dirs:
        return None
    path = T.find_xplane(max(dirs, key=os.path.getmtime))
    return load(path) if path else None


def _by_line(spans: List[Span]) -> Dict[int, List[Span]]:
    out: Dict[int, List[Span]] = {}
    for s in spans:
        out.setdefault(s.line, []).append(s)
    return out


def step_self_ms(spans: List[Span], window: Tuple[float, float]
                 ) -> List[float]:
    """For every lane step wholly inside ``window``: its length less the
    time its thread spent in ``*.fetch`` spans within it, in ms."""
    lo, hi = window
    out = []
    for line in _by_line(spans).values():
        fetches = [f for f in line if f.name.endswith(FETCH)]
        for st in line:
            if st.name != STEP or st.start < lo or st.end > hi:
                continue
            waits = [(f.start, f.end) for f in fetches
                     if f.start >= st.start and f.end <= st.end]
            out.append((st.end - st.start - T.length(waits)) * 1e3)
    return out


def idle_outside_step_share(tr: T.Trace, spans: List[Span]
                            ) -> Optional[float]:
    """Per device, the share of the window in which no operation ran and
    no thread was inside a lane step, averaged over the devices."""
    if tr.window_s <= 0 or not tr.ops:
        return None
    lo, hi = tr.window
    steps = [(max(s.start, lo), min(s.end, hi)) for s in spans
             if s.name == STEP and s.end > lo and s.start < hi]
    covered = sum(T.length([(e.start, e.end) for e in evs] + steps)
                  for evs in tr.ops.values()) / len(tr.ops)
    return 1.0 - covered / tr.window_s


def gap_name(spans: List[Span], t: float) -> str:
    """What the host was doing at ``t``: the narrowest program span open
    on a line that is inside a lane step, or, with no step open,
    ``idle: no lane step (<narrowest program span on any line>)``."""
    open_ = [s for s in spans if s.start <= t <= s.end]
    lines = {s.line for s in open_ if s.name == STEP}
    narrowest = functools.partial(min, key=lambda s: s.end - s.start)
    if lines:
        return narrowest([s for s in open_ if s.line in lines]).name
    inner = narrowest(open_).name if open_ else "no program span"
    return f"idle: no lane step ({inner})"


def idle_by_phase(tr: T.Trace, spans: List[Span]) -> List[list]:
    """The first device's idle time in the window, each gap between its
    operations named by ``gap_name`` at its middle: ``[name, seconds]``,
    most first."""
    if not tr.ops:
        return []
    busy = T.merge([(e.start, e.end) for e in tr.ops[min(tr.ops)]])
    lo, hi = tr.window
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    agg: Dict[str, float] = {}
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            name = gap_name(spans, (s + e) / 2)
            agg[name] = agg.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])]
