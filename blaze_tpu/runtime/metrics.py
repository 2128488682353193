"""Metrics: per-operator counters/timers mirrored into a tree that the
JVM side walks into Spark SQL UI metrics.

≙ reference MetricNode (spark-extension MetricNode.scala:21-41) and the
native mirror walk (blaze/src/metrics.rs:21-57).  The default metric
set matches NativeHelper.getDefaultNativeMetrics (NativeHelper.scala:
92-122): elapsed_compute, output_rows, spill counts/sizes, io times.

Thread safety: operators execute concurrently (exchange map fan-out,
worker threads, the memory manager spilling one consumer from another
task's thread), and ``values[name] = values.get(name, 0) + v`` is a
read-modify-write race under concurrency — both ``MetricsSet`` updates
and ``MetricNode.child`` growth take a per-instance lock.  The gateway
metrics-callback seam is unchanged: callbacks still read ``values`` /
walk ``foreach`` exactly as before.

Metric NAMES are API: dashboards scrape them from the monitor's
``/metrics`` endpoint and the JVM side maps them into SQLMetrics, so
every name the tree may contain is pinned by the golden registry
``metric_names.json`` next to this file (:func:`load_metric_names`) —
tier-1 gates the drift both ways, mirroring the ``trace_schema.json``
pattern for event shapes.
"""

from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Set

from ..analysis.locks import make_lock
from . import lockset

METRIC_NAMES_PATH = os.path.join(
    os.path.dirname(__file__), "metric_names.json")


def _remove_by_identity(items: list, obj: object) -> bool:
    """Remove ``obj`` from ``items`` comparing by IDENTITY, not
    equality — THE shared helper for capture/sink/scope lists (the
    PR 3 bug class, now one definition): ``list.remove`` compares by
    VALUE, so a nested scope holding an EQUAL-content entry (two empty
    capture dicts, two equal counter snapshots) would evict the OUTER
    scope's entry and silently stop its accumulation.  Returns True
    when found."""
    for i, x in enumerate(items):
        if x is obj:
            del items[i]
            return True
    return False


def load_metric_names() -> Dict[str, List[str]]:
    """The golden metric-name registry, grouped by producer
    (operator_metrics / scheduler_counters / dispatch_counters)."""
    with open(METRIC_NAMES_PATH) as f:
        return json.load(f)


def registered_metric_names() -> Set[str]:
    """Flat union of every registered counter/gauge name."""
    reg = load_metric_names()
    return {n for k, names in reg.items() if k != "title" for n in names}


class MetricsSet:
    """Counters + timers for one operator instance (thread-safe)."""

    #: guarded-by declaration (analysis/guarded.py): operators share
    #: one set across worker threads, and values[name] = get + v is a
    #: read-modify-write race off-lock
    GUARDED_BY = {"values": "metrics.set"}
    GUARDED_REFS = ("values",)

    def __init__(self):
        self.values: Dict[str, int] = {}
        self._lock = make_lock("metrics.set")

    def add(self, name: str, v: int = 1) -> None:
        with self._lock:
            lockset.check(self, "values")
            self.values[name] = self.values.get(name, 0) + int(v)

    def set(self, name: str, v: int) -> None:
        with self._lock:
            lockset.check(self, "values")
            self.values[name] = int(v)

    def get(self, name: str) -> int:
        with self._lock:
            lockset.check(self, "values")
            return self.values.get(name, 0)

    def snapshot(self) -> Dict[str, int]:
        """Point-in-time copy (trace task_plan events, tests)."""
        with self._lock:
            lockset.check(self, "values")
            return dict(self.values)

    def merge(self, other: "MetricsSet") -> None:
        """Fold another set's counters into this one.  Concurrency in
        the runtime is handled by the per-instance lock (operators
        share one set across worker threads); this helper is for
        consumers aggregating sets they collected themselves."""
        for k, v in other.snapshot().items():
            self.add(k, v)

    @contextmanager
    def timer(self, name: str, span=None):
        """Accumulates nanoseconds under ``name`` (elapsed_compute etc.).
        With a ``trace.span`` the timer opens it around the block and
        adds the span's own nanoseconds: one clock for both."""
        if span is not None:
            try:
                with span:
                    yield
            finally:
                self.add(name, span.ns)
            return
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.add(name, time.perf_counter_ns() - t0)


class MetricNode:
    """Tree mirroring the plan tree; ``child(i)`` descends.  The JVM
    gateway registers a callback per node to push values into
    SQLMetrics; standalone runs just read the tree."""

    #: children grow concurrently (exchange fan-out tasks descending
    #: into fresh stage nodes) — list append/len is guarded
    GUARDED_BY = {"children": "metrics.node"}
    GUARDED_REFS = ("children",)

    def __init__(self, metrics: Optional[MetricsSet] = None, children: Optional[List["MetricNode"]] = None):
        self.metrics = metrics or MetricsSet()
        self.children = children or []
        self._lock = make_lock("metrics.node")

    def child(self, i: int) -> "MetricNode":
        with self._lock:
            lockset.check(self, "children")
            while len(self.children) <= i:
                self.children.append(MetricNode())
            return self.children[i]

    def foreach(self, fn, path=()):
        # the child-list snapshot is taken under the lock (a concurrent
        # child() append mid-iteration raced the bare list read); fn
        # runs OUTSIDE it — callbacks may emit, and holding a lock
        # across emission is the emit-under-lock class
        with self._lock:
            lockset.check(self, "children")
            kids = list(self.children)
        fn(path, self.metrics)
        for i, c in enumerate(kids):
            c.foreach(fn, path + (i,))

    def flatten(self) -> Dict[str, int]:
        out: Dict[str, int] = {}

        def visit(path, ms):
            for k, v in ms.snapshot().items():
                out[".".join(map(str, path)) + ":" + k] = v

        self.foreach(visit)
        return out
