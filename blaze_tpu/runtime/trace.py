"""Query-level tracing + structured JSONL event log.

≙ Spark's ``EventLoggingListener`` + SQL-tab timeline, sized for this
engine: the reference's only observability surface is the MetricNode
tree walked into Spark SQL UI metrics (MetricNode.scala:21-41,
metrics.rs:21-57) — flat counters, no timeline, no attribution.  This
module adds the missing dynamics layer as a span/event stream:

    query -> stage -> task attempt -> operator kernel

Every event is one JSON object per line with ``ts`` (epoch seconds)
and ``type``; the golden schema lives in ``trace_schema.json`` next to
this file and `tests/test_trace.py` fails tier-1 on drift.

The split that matters on TPU rides on the kernel events: with tracing
active, every instrumented jit call (runtime.dispatch wrappers, applied
centrally in kernel_cache.cached_kernel) is timed as

- ``dispatch_overhead_ns`` — host time to trace/launch the program
  (async dispatch returns before the device runs),
- ``device_time_ns``      — block-until-ready drain after the launch,
- ``compile_ns``          — launch time of calls that triggered a
  fresh XLA compile (the whole pre-block wall is the compile bill),

attributed to the operator kernel label that issued the program (the
structural head of its kernel-cache key: "agg", "filter",
"fused_stage", "shuffle_pids", ...).  Blocking per program serializes
the device — that is the point of a profile, and the reason tracing is
OFF by default: the disarmed check is one module-global bool read per
kernel call (``_KERNEL_TIMING``) and one per lifecycle site
(``enabled()``), with zero allocation.

Host spans (:class:`span`) are a different thing from events and are
always on: a ``jax.profiler.TraceAnnotation`` on the profiler's clock
plus ``<name>_ns``/``<name>_n`` in the dispatch tally, so a
``dispatch.capture()`` reads where a query's host time went with this
log disarmed.  ``_ns`` is wall time, and wall time cannot tell a thread
that computes from one that waits, so the program's two hand-off
queues say who waited for whom with spans of their own, opened only
where a thread was about to block: ``pipeline_wait`` /
``pipeline_full`` (runtime/pipeline.py: the task thread waiting for its
scan's producer, the producer for the task thread) and
``inserter_full`` / ``inserter_drain`` (parallel/shuffle.py: the map
task's thread waiting for the exchange stager).
``spark.blaze.trace.sampleRate=0`` arms the log and the per-label
attribution without any block-until-ready.

Consumers: the stage scheduler emits lifecycle events
(stage submit/complete, task attempt start/end/retry/timeout,
fetch-failure -> map-stage rerun), runtime.faults records each injected
fault, runtime.memmgr contributes watermark gauges + spill events,
parallel.shuffle / parallel.rss contribute bytes/blocks moved, and
``python -m blaze_tpu --report <eventlog>`` (runtime/trace_report.py)
renders the per-query profile.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import json
import os
import re
import tempfile
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from .. import conf
from ..analysis.locks import make_lock
from . import lockset
from .metrics import _remove_by_identity

# ------------------------------------------------------------- registry

#: every event type this module may emit — MUST stay in lockstep with
#: trace_schema.json (tests/test_trace.py gates the drift both ways)
EVENT_TYPES = frozenset({
    "query_start", "query_end",
    "query_cancel_requested", "query_cancelled",
    "stage_submit", "stage_complete",
    "task_attempt_start", "task_attempt_end",
    "task_retry", "task_timeout",
    "fetch_failure", "map_stage_rerun",
    "speculative_attempt_start",
    "speculative_attempt_won", "speculative_attempt_lost",
    "task_kernels", "task_plan",
    "stage_progress", "task_heartbeat",
    "fault_injected", "straggler_injected",
    "worker_lost", "worker_blacklisted", "pool_degraded",
    "worker_telemetry",
    "slo_alert_firing", "slo_alert_resolved",
    "oom_recovery",
    "block_corruption", "disk_pressure",
    "mem_watermark", "spill",
    "shuffle_write", "shuffle_fetch", "rss_push",
    "plan_cache", "result_cache",
    "stats_skew_detected", "stats_persisted", "stats_reused",
})

SCHEMA_PATH = os.path.join(os.path.dirname(__file__), "trace_schema.json")

# --------------------------------------------------------------- state

_lock = make_lock("trace.log")
#: kernel sinks get their OWN lock: record_kernel runs once per traced
#: XLA program and must never contend with event-file IO under _lock
#: (it is the ONE lock events may be recorded under — the
#: lock.emit-under-lock lint rule exempts it by name)
_sink_lock = make_lock("trace.sink")
_loaded = False
_armed = False          # event-log emission on (conf spark.blaze.trace.enabled)
_dir = ""               # resolved event-log directory
_path: Optional[str] = None   # current log file (None = process default)
_default_path: Optional[str] = None
_seq = 0                # per-process query-log sequence number
# one cached append handle for the active log file: per-event
# open/close would serialize every emitter behind syscalls under _lock
_file = None            # (path, handle)

_KERNEL_SINKS: List[Dict[str, Dict[str, int]]] = []
#: read lock-free on the dispatch hot path: True only while at least
#: one kernel_capture() scope is active (bench profiling or an armed
#: traced run) — False keeps instrumented kernels on the pre-existing
#: non-blocking path
_KERNEL_TIMING = False

#: kernel-attribution sampling (conf spark.blaze.trace.sampleRate):
#: block-until-ready-time every Nth instrumented program instead of all
#: of them, so attribution is cheap enough to leave armed in production
_sample_rate = 1
_sample_counter = 0
_sample_lock = make_lock("trace.sample")

#: per-path rollover segment counters for the size-capped event log
#: (conf spark.blaze.eventLog.maxBytes)
_segments: Dict[str, int] = {}
_max_bytes = 0

# introspection counters for the overhead-gating regression test
_events_emitted = 0
_spans_opened = 0

_LOG = lockset.module_guard(__name__)

#: guarded-by declaration (analysis/guarded.py): the event-log file
#: state is shared by every emitting thread; _armed/_dir/_sample_rate/
#: _max_bytes are load-once config reads (off-lock by design, like the
#: _KERNEL_TIMING hot-path bool) and stay undeclared
GUARDED_BY = {"_file": "trace.log",
              "_path": "trace.log",
              "_default_path": "trace.log",
              "_seq": "trace.log",
              "_segments": "trace.log",
              "_events_emitted": "trace.log",
              "_spans_opened": "trace.log",
              "_KERNEL_SINKS": "trace.sink",
              "_sample_counter": "trace.sample"}
GUARDED_REFS = ("_segments", "_KERNEL_SINKS")
LOCK_FREE = {
    "_current_path": "derived single-reference pointer, atomically "
                     "swapped under trace.log at every _path/"
                     "_default_path write site; the bare read cannot "
                     "tear, and locking it would queue memmgr's "
                     "per-batch accounting (which reads it while "
                     "holding memmgr.manager) behind event-file IO",
}

#: _path or _default_path, maintained at every write site — the value
#: current_path() serves without taking the log lock
_current_path: Optional[str] = None

# ------------------------------------------- trace context (W3C style)

#: the distributed-tracing identity every event this context emits
#: carries: ``(trace_id, span_id)`` — a 32-hex W3C trace id minted
#: once per query (or accepted from an upstream ``traceparent``) and
#: the current span's 16-hex id.  A ContextVar so concurrent service
#: queries on different threads never cross-attribute, and the
#: speculation runner's ``contextvars.copy_context`` attempt threads
#: inherit it for free.
_TRACE_CTX: "contextvars.ContextVar[Optional[Tuple[str, str]]]" = \
    contextvars.ContextVar("blaze_trace_ctx", default=None)

_TRACEPARENT_RE = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$")


def new_trace_id() -> str:
    """A fresh 32-hex W3C trace id."""
    return uuid.uuid4().hex


def span_id_for(trace_id: str, path: str) -> str:
    """Deterministic 16-hex span id for a span ``path`` (e.g.
    ``query:q6`` / ``stage:0`` / ``task:0.1#a0``) within a trace.
    Deterministic ON PURPOSE: the driver and a worker subprocess
    derive identical span ids from the shared trace id, so the OTLP
    conversion of independently-written event-log segments reassembles
    into ONE parent-linked tree without any cross-process id
    handshake."""
    return hashlib.sha256(f"{trace_id}/{path}".encode()).hexdigest()[:16]


def current_trace_context() -> Optional[Tuple[str, str]]:
    """``(trace_id, span_id)`` of the query running on this context
    (None outside a traced query span)."""
    return _TRACE_CTX.get()


def set_trace_context(trace_id: str, span_id: str):
    """Install an explicit trace context (worker subprocesses restore
    the driver's from ``BLAZE_TRACEPARENT``); returns the reset
    token."""
    return _TRACE_CTX.set((trace_id, span_id))


def reset_trace_context(token) -> None:
    _TRACE_CTX.reset(token)


def format_traceparent(trace_id: str, span_id: str) -> str:
    """W3C ``traceparent`` header value (version 00, sampled)."""
    return f"00-{trace_id}-{span_id}-01"


def current_traceparent() -> Optional[str]:
    """The ambient trace context as a ``traceparent`` header value —
    what the driver hands a worker subprocess (env) or a client sends
    ``POST /service/submit`` (header)."""
    ctx = _TRACE_CTX.get()
    if ctx is None:
        return None
    return format_traceparent(ctx[0], ctx[1])


def parse_traceparent(value: str) -> Optional[Tuple[str, str]]:
    """``(trace_id, parent_span_id)`` from a W3C ``traceparent`` header
    value; None when malformed (a bad header must degrade to a fresh
    trace, never kill the submission)."""
    m = _TRACEPARENT_RE.match(value.strip().lower()) if value else None
    if m is None:
        return None
    return m.group(1), m.group(2)


def _load() -> None:
    global _loaded, _armed, _dir, _sample_rate, _max_bytes
    with _lock:
        _armed = bool(conf.TRACE_ENABLE.get())
        d = str(conf.EVENT_LOG_DIR.get() or "")
        _dir = d or os.path.join(tempfile.gettempdir(), "blaze_eventlog")
        # 0 = attribute launches and compiles per label and never
        # block (the armed mode a benchmark cell can run in)
        _sample_rate = max(0, int(conf.TRACE_SAMPLE_RATE.get()))
        _max_bytes = max(0, int(conf.EVENT_LOG_MAX_BYTES.get()))
        _loaded = True


def enabled() -> bool:
    """Event-log emission armed?  Lazily loads conf once; call
    :func:`reset` after flipping ``spark.blaze.trace.enabled``."""
    if not _loaded:
        _load()
    return _armed


def reset() -> None:
    """(Re)load arming + directory from conf and forget the current log
    file and counters — call after changing trace conf keys."""
    global _path, _default_path, _events_emitted, _spans_opened, _seq, _file
    global _sample_counter, _current_path
    _load()
    with _lock:
        _path = None
        _default_path = None
        _current_path = None
        _events_emitted = 0
        _spans_opened = 0
        _seq = 0
        _segments.clear()
        if _file is not None:
            _file[1].close()
            _file = None
    with _sample_lock:
        _sample_counter = 0


def counters() -> Dict[str, int]:
    """Introspection for the gating tests: how many events/spans this
    process has produced since the last :func:`reset`."""
    with _lock:
        return {"events": _events_emitted, "spans": _spans_opened}


def log_dir() -> str:
    if not _loaded:
        _load()
    os.makedirs(_dir, exist_ok=True)
    return _dir


def current_path() -> Optional[str]:
    """The file events are being appended to right now (None when no
    event has been written and no query span is open).  Served from a
    derived single-reference pointer swapped under the log lock at
    every write site (LOCK_FREE-declared): callers include memmgr's
    per-batch accounting while holding memmgr.manager, and taking the
    log lock here would queue that hot path behind event-file IO."""
    return _current_path


# ------------------------------------------------------------- emission

def emit(etype: str, **fields: Any) -> None:
    """Append one event to the active log file.  No-op when tracing is
    disarmed; unknown event types raise (schema drift must fail loudly,
    not mint unvalidatable lines)."""
    if not enabled():
        return
    if etype not in EVENT_TYPES:
        raise ValueError(f"unregistered trace event type {etype!r}")
    global _events_emitted, _default_path, _current_path
    rec = {"ts": time.time(), "type": etype}
    # every event carries the ambient W3C trace id (when a traced
    # query span is open on this context), so driver, worker
    # subprocess, and service segments of one query stitch into a
    # single trace — the cross-process reconciliation key
    ctx = _TRACE_CTX.get()
    if ctx is not None and "trace_id" not in fields:
        rec["trace_id"] = ctx[0]
    rec.update(fields)
    line = json.dumps(rec, default=str)
    global _file
    with _lock:
        lockset.check(_LOG, "_file", "_path", "_default_path",
                      "_events_emitted", "_segments")
        path = _path
        if path is None:
            if _default_path is None:
                _default_path = os.path.join(
                    _dir, f"blaze-{os.getpid()}.jsonl")
                _current_path = _path or _default_path
                os.makedirs(_dir, exist_ok=True)
            path = _default_path
        if _file is None or _file[0] != path:
            if _file is not None:
                _file[1].close()
            _file = (path, open(path, "a"))
        _file[1].write(line + "\n")
        _file[1].flush()  # whole lines reach readers/crash dumps now
        _events_emitted += 1
        # size-capped rollover (spark.blaze.eventLog.maxBytes): the
        # full file becomes the next numbered segment and the base
        # path reopens fresh, so the active file never grows unbounded
        # and read_event_log() reassembles the set in order
        if _max_bytes > 0 and _file[1].tell() >= _max_bytes:
            _file[1].close()
            _file = None
            # never clobber a segment from an earlier life of this
            # path (reset() clears the in-memory counter but the same
            # query_id + pid regenerates the same file name): probe
            # past any .segN already on disk before renaming
            k = _segments.get(path, 0) + 1
            while os.path.exists(f"{path}.seg{k}"):
                k += 1
            _segments[path] = k
            try:
                os.replace(path, f"{path}.seg{k}")
            except OSError:
                pass  # rollover is best-effort; appending continues


@contextlib.contextmanager
def query(query_id: str, trace_id: Optional[str] = None,
          parent_span_id: Optional[str] = None) -> Iterator[Optional[str]]:
    """Scope one traced query: opens a fresh JSONL file under the
    event-log dir, emits query_start/query_end around the body, and
    yields the file path (None when tracing is disarmed).

    ``trace_id`` continues an upstream trace (a ``traceparent`` header
    on the service endpoint, a driver's context in a worker); omitted,
    a fresh W3C trace id is minted.  Either way the context is
    installed for the scope's duration, so EVERY event emitted under
    it — scheduler lifecycle, task heartbeats, shuffle/memory events —
    carries the same ``trace_id``.  ``parent_span_id`` (from the same
    traceparent) links the exported OTLP root span under the caller's
    span."""
    if not enabled():
        yield None
        return
    trace_id = trace_id or new_trace_id()
    ctx_token = _TRACE_CTX.set(
        (trace_id, span_id_for(trace_id, f"query:{query_id}")))
    global _path, _seq, _spans_opened, _current_path
    with _lock:
        lockset.check(_LOG, "_path", "_seq", "_spans_opened")
        _seq += 1
        safe = "".join(c if c.isalnum() or c in "._-" else "_" for c in query_id)
        os.makedirs(_dir, exist_ok=True)
        # never REUSE an existing file: reset() zeroes the sequence
        # counter, so a repeated query id after a reset (chaos sweeps
        # re-arming tracing per seed) would otherwise APPEND to the
        # previous run's log — two trace ids in one file, a torn
        # reconciliation for both runs
        path = os.path.join(_dir, f"{safe}-{os.getpid()}-{_seq}.jsonl")
        while os.path.exists(path):
            _seq += 1
            path = os.path.join(_dir, f"{safe}-{os.getpid()}-{_seq}.jsonl")
        prev = _path
        _path = path
        _current_path = _path or _default_path
        _spans_opened += 1
    t0 = time.perf_counter_ns()
    # the device kind the query's programs will run on, stamped into
    # the log: an event log analyzed OFFLINE (another machine, a CI
    # box) must be judged against the roofline of the hardware that
    # RAN it, not the analyzer's (runtime/perf.py prefers this stamp)
    from . import perf as _perf

    fields: Dict[str, Any] = {"query_id": query_id,
                              "device_kind": _perf.current_device_kind()}
    if parent_span_id:
        fields["parent_span_id"] = parent_span_id
    emit("query_start", **fields)
    status = "ok"
    try:
        yield path
    except BaseException as exc:
        from .context import QueryCancelledError, QueryDeadlineError

        status = ("deadline_exceeded"
                  if isinstance(exc, QueryDeadlineError) else
                  "cancelled" if isinstance(exc, QueryCancelledError)
                  else "failed")
        raise
    finally:
        emit("query_end", query_id=query_id, status=status,
             wall_ns=time.perf_counter_ns() - t0)
        _TRACE_CTX.reset(ctx_token)
        with _lock:
            _path = prev
            _current_path = _path or _default_path


# ---------------------------------------------------------- host spans

def annotation(name: str, **ids: Any) -> TraceAnnotation:
    """``jax.profiler.TraceAnnotation("blaze:<name>", **ids)``: under a
    profiler session the block lands in the same ``.xplane.pb`` as the
    ``XLA Ops`` line, on the profiler's one clock, nested under whatever
    its thread already has open (``stage``/``partition``/``attempt`` are
    the shared ids); with no session it is a flag check.  Alone it marks
    the enclosing spans no counter is read from (``task``,
    ``join_build``): they give a trace its ids and nesting."""
    return TraceAnnotation("blaze:" + name, **ids)


class span:
    """One host span of the program: ``with trace.span("task_decode"):``.

    It does two things and nothing else: it opens :func:`annotation`,
    and on exit it adds the elapsed host nanoseconds to the dispatch
    tally as ``<name>_ns`` and 1 to ``<name>_n`` (one lock
    acquisition), so every ``dispatch.capture()`` sees where a query's
    host time went with the event log off.  ``ns`` holds the elapsed
    time after exit: ``MetricsSet.timer(name, span)`` reads it, so a
    boundary that has both has one clock.

    Never leave one open across a ``yield`` to the consumer."""

    __slots__ = ("_name", "_ann", "_t0", "ns")

    def __init__(self, name: str, **ids: Any):
        self._name = name
        self._ann = annotation(name, **ids)
        self.ns = 0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        from .dispatch import record_span  # dispatch imports this module

        self.ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        record_span(self._name, self.ns)


def read_scalar(x) -> int:
    """``int(x)`` of a device scalar under the ``device_read`` span:
    the host blocks here until the program that produces ``x`` has
    run — the round trips that pick an ``out_cap`` or a row count."""
    with span("device_read"):
        return int(x)


# -------------------------------------------------- kernel attribution

@contextlib.contextmanager
def kernel_capture() -> Iterator[Dict[str, Dict[str, int]]]:
    """Scope that accumulates per-kernel-label cost while active:
    ``{label: {programs, device_ns, dispatch_ns, compile_ns}}``.

    Activating ANY capture flips instrumented kernels onto the timed
    block-until-ready path (runtime.dispatch), device-serializing
    execution for the duration — profiling changes what it measures,
    the same way Spark's spark.python.profile does.  Nested/concurrent
    captures each get the full counts (scheduler per stage, run_task
    per attempt, bench per profile pass)."""
    global _KERNEL_TIMING
    # the perf estimator only ever runs under an active capture, and
    # dispatch reads its _ARMED bool directly for hot-path cheapness:
    # capture entry is therefore the choke point that must resolve the
    # lazy conf load, or spark.blaze.perf.estimates=false would be
    # silently ignored on every production traced path
    from . import perf as _perf

    _perf.enabled()
    sink: Dict[str, Dict[str, int]] = {}
    with _sink_lock:
        lockset.check(_LOG, "_KERNEL_SINKS")
        _KERNEL_SINKS.append(sink)
        _KERNEL_TIMING = True
    try:
        yield sink
    finally:
        with _sink_lock:
            # identity removal (metrics._remove_by_identity — the ONE
            # shared definition): list.remove compares dicts by VALUE,
            # so a nested capture with equal contents (e.g. two empty
            # sinks) would evict the outer scope's dict instead
            _remove_by_identity(_KERNEL_SINKS, sink)
            _KERNEL_TIMING = bool(_KERNEL_SINKS)


def sample_kernel() -> bool:
    """Should THIS instrumented program pay the block-until-ready
    device timing?  True for every call at sampleRate=1 (the default
    full-fidelity profile); at N>1 true for every Nth program, so an
    armed production trace costs one device serialization per N
    programs instead of per program; never at 0 (launches and
    compiles still attribute per label, the device is not serialised
    and its time reads as not sampled)."""
    rate = _sample_rate
    if rate == 1:
        return True
    if rate <= 0:
        return False
    global _sample_counter
    with _sample_lock:
        lockset.check(_LOG, "_sample_counter")
        _sample_counter += 1
        return _sample_counter % rate == 1


def record_kernel(label: str, device_ns: int, dispatch_ns: int,
                  compile_ns: int, timed: bool = True,
                  bytes_est: int = 0, flops_est: int = 0) -> None:
    """Dispatch-wrapper callback: land one program's cost on every
    active capture under its operator kernel label.  ``timed`` False =
    a sampled-out program (launch overhead attributed, device drain
    not measured); consumers scale device time by programs/timed.
    ``bytes_est``/``flops_est`` are the perf estimator's bytes-moved /
    flops guesses for the program (runtime/perf.py — 0 when the
    estimator is disarmed), the roofline numerators ``--report`` and
    ``--explain`` judge against the device peak table."""
    with _sink_lock:
        lockset.check(_LOG, "_KERNEL_SINKS")
        for sink in _KERNEL_SINKS:
            agg = sink.get(label)
            if agg is None:
                agg = sink[label] = {
                    "programs": 0, "device_ns": 0,
                    "dispatch_ns": 0, "compile_ns": 0, "timed": 0,
                    "bytes_est": 0, "flops_est": 0,
                }
            agg["programs"] += 1
            agg["device_ns"] += int(device_ns)
            agg["dispatch_ns"] += int(dispatch_ns)
            agg["compile_ns"] += int(compile_ns)
            agg["timed"] += 1 if timed else 0
            agg["bytes_est"] += int(bytes_est)
            agg["flops_est"] += int(flops_est)


def snapshot_kernels(sink: Dict[str, Dict[str, int]]) -> Dict[str, Dict[str, int]]:
    """Locked copy of a LIVE kernel capture — mid-flight consumers
    (the task heartbeat surfacing device/dispatch splits in /queries)
    must not iterate a dict the async stager or a sibling attempt is
    concurrently growing under ``_sink_lock``."""
    with _sink_lock:
        lockset.check(_LOG, "_KERNEL_SINKS")
        return {k: dict(v) for k, v in sink.items()}


def scaled_device_ns(v: Dict[str, int]) -> int:
    """A kernel entry's device time scaled back up by the sampling
    factor (programs/timed) — the estimate ``--report`` renders and
    span totals carry.  Entries with no timed program contribute 0.
    On a genuinely async device the sampled drain also waits out
    unsampled programs queued ahead of it, so this is an UPPER BOUND
    on true device time, not an unbiased estimate."""
    timed = v.get("timed", v.get("programs", 0))
    if not timed:
        return 0
    return int(round(v["device_ns"] * (v["programs"] / timed)))


def sum_kernels(sink: Dict[str, Dict[str, int]]) -> Dict[str, int]:
    """Collapse a kernel capture into the per-span totals the event
    schema carries (device time scaled by the sampling factor)."""
    return {
        "programs": sum(v["programs"] for v in sink.values()),
        "device_time_ns": sum(scaled_device_ns(v) for v in sink.values()),
        "dispatch_overhead_ns": sum(v["dispatch_ns"] for v in sink.values()),
        "compile_ns": sum(v["compile_ns"] for v in sink.values()),
        # roofline numerators (runtime/perf.py estimator; 0 disarmed)
        "hbm_bytes_est": sum(v.get("bytes_est", 0) for v in sink.values()),
        "flops_est": sum(v.get("flops_est", 0) for v in sink.values()),
    }


# ------------------------------------------------------------- reading

def read_events(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL event log.  A torn line (a crash mid-append — the
    writer flushes whole lines, but the filesystem makes no promise)
    is SKIPPED with a warning instead of raising, so a post-crash
    ``--report`` still renders everything the log did capture."""
    import logging

    out: List[Dict[str, Any]] = []
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                logging.getLogger(__name__).warning(
                    "skipping torn/unparseable event-log line %s:%d "
                    "(crash mid-append?)", path, i)
                continue
    return out


def read_event_log(path: str) -> List[Dict[str, Any]]:
    """Read a possibly ROTATED event log: the numbered segments a
    size-capped log rolled over (<path>.seg1, .seg2, ... oldest first)
    followed by the active file.  A log that never rotated reads
    exactly like :func:`read_events` (including OSError on a missing
    path)."""
    segs: List[str] = []
    k = 1
    while os.path.exists(f"{path}.seg{k}"):
        segs.append(f"{path}.seg{k}")
        k += 1
    if not segs:
        return read_events(path)
    out: List[Dict[str, Any]] = []
    for seg in segs:
        out.extend(read_events(seg))
    if os.path.exists(path):
        out.extend(read_events(path))
    return out


def load_schema() -> Dict[str, Any]:
    """The golden per-event-type JSON schema (trace_schema.json)."""
    with open(SCHEMA_PATH) as f:
        return json.load(f)


def plan_tree(plan) -> Dict[str, Any]:
    """Plan-annotated metrics tree for the ``task_plan`` event: the
    executed plan instance's per-node MetricsSet snapshots, nested the
    way MetricNode mirrors the plan (MetricNode.scala:21-41)."""
    return {
        "op": plan.name(),
        "metrics": plan.metrics.snapshot(),
        "children": [plan_tree(c) for c in plan.children],
    }
