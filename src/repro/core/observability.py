"""Observability (§14): metrics taxonomy + hierarchical span tracing.

Prometheus-style counters/histograms (in-process; the export surface is a
text scrape endpoint format) and an OpenTelemetry-like span model with the
paper's hierarchy: root -> signal spans -> decision span -> plugin spans ->
upstream span.

``trace_span`` puts a span on the profiler's clock (``vsr:<name>``): the
pipeline's stages and the fleet's lane step open one, so a profiler
trace shows which host phase the device waited on.  With no profiler
session active a span costs one native call.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

from jax.profiler import TraceAnnotation

# recent values a histogram keeps for ``percentile``; its count and sum
# cover every value observed
HIST_RECENT = 4096


class Hist:
    """Exact count and sum of every observation, plus the most recent
    ``HIST_RECENT`` values."""
    __slots__ = ("count", "sum", "recent")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.recent: Deque[float] = deque(maxlen=HIST_RECENT)

    def add(self, value: float):
        self.count += 1
        self.sum += value
        self.recent.append(value)


class Metrics:
    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = defaultdict(float)
        self.hists: Dict[str, Hist] = defaultdict(Hist)
        self.gauges: Dict[str, float] = {}

    def inc(self, name: str, value: float = 1.0, **labels):
        with self._lock:
            self.counters[self._key(name, labels)] += value

    def observe(self, name: str, value: float, **labels):
        with self._lock:
            self.hists[self._key(name, labels)].add(value)

    def gauge(self, name: str, value: float, **labels):
        """Set-to-latest metric (e.g. overload state, queue depth)."""
        with self._lock:
            self.gauges[self._key(name, labels)] = value

    @staticmethod
    def _key(name, labels):
        if not labels:
            return name
        lab = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
        return f"{name}{{{lab}}}"

    def percentile(self, name: str, p: float, **labels) -> float:
        """``p``-th percentile of the histogram's recent values."""
        h = self.hists.get(self._key(name, labels))
        vals = sorted(h.recent) if h is not None else []
        if not vals:
            return 0.0
        idx = min(len(vals) - 1, int(p / 100 * len(vals)))
        return vals[idx]

    def scrape(self) -> str:
        """Prometheus text exposition format."""
        lines = []
        for k, v in sorted(self.counters.items()):
            lines.append(f"vsr_{k} {v}")
        for k, v in sorted(self.gauges.items()):
            lines.append(f"vsr_{k} {v}")
        for k, h in sorted(self.hists.items()):
            base, _, lab = k.partition("{")
            lab = ("{" + lab) if lab else ""
            lines.append(f"vsr_{base}_count{lab} {h.count}")
            lines.append(f"vsr_{base}_sum{lab} {h.sum:.6f}")
        return "\n".join(lines)


@dataclass
class Span:
    name: str
    start: float = field(default_factory=time.perf_counter)
    end: Optional[float] = None
    attributes: Dict[str, Any] = field(default_factory=dict)
    children: List["Span"] = field(default_factory=list)

    def child(self, name: str, **attrs) -> "Span":
        s = Span(name, attributes=attrs)
        self.children.append(s)
        return s

    def finish(self, **attrs):
        self.end = time.perf_counter()
        self.attributes.update(attrs)
        return self

    @property
    def duration_ms(self) -> float:
        return ((self.end or time.perf_counter()) - self.start) * 1e3

    def flatten(self, depth=0):
        yield depth, self
        for c in self.children:
            yield from c.flatten(depth + 1)


METRICS = Metrics()


def trace_span(name: str, **meta) -> TraceAnnotation:
    """Context manager: a span named ``vsr:<name>`` on the profiler's
    clock, with ``meta`` (arch, step, live rows, rid, ...) as its stats.
    The stats are encoded only while a profiler session records.  A span
    whose name ends in ``.fetch`` is the host blocked on a device
    result."""
    return TraceAnnotation("vsr:" + name, **meta)


@contextmanager
def stage_scope(parent: Optional[Span], name: str, *,
                metric: str = "stage_latency_ms", **attrs):
    """Span + latency-histogram scope for one pipeline stage.

    Creates ``name`` as a child of ``parent`` (or a standalone root span
    when ``parent`` is None), finishes it on exit, and records the stage
    duration into ``metric`` labelled by the stage name.  The stage also
    opens ``vsr:<name>`` on the profiler's clock."""
    span = parent.child(name, **attrs) if parent is not None \
        else Span(name, attributes=dict(attrs))
    try:
        with trace_span(name):
            yield span
    finally:
        span.finish()
        METRICS.observe(metric, span.duration_ms,
                        stage=name.removeprefix("stage:"))
