"""Dispatch observability: count XLA program launches and compiles.

The q01 regression (VERDICT r5) was invisible in-repo: the pipeline
issued ~a hundred XLA programs per batch, each paying the remote
chip's ~70-80 ms per-program turnaround, and nothing in the metrics
tree said so.  Every jitted operator kernel (they all register through
``runtime.kernel_cache.cached_kernel``) is wrapped here so that

- ``xla_dispatches``   — program launches (one per kernel call),
- ``launch_ns``/``launch_n`` — host time inside those calls (the
                         enqueue, not the device's run) and their
                         count, which equals ``xla_dispatches``,
- ``xla_compiles``     — calls that triggered a fresh XLA compile
                         (detected via the jit cache-size delta),
- ``compile_ms``       — host time of those compiling calls (printed
                         by ``--warmup``'s cold line and
                         ``chip_smoke.py``; ``--report`` prints the
                         kernel capture's ``compile_ns`` instead),
- ``<span>_ns``/``<span>_n`` — host time and openings of every
                         ``trace.span`` (:func:`record_span`), with the
                         bytes ``h2d_bytes``/``shuffle_bytes_written``.
                         The wait spans of the two hand-off queues are
                         among them: ``pipeline_wait``/``pipeline_full``
                         (runtime/pipeline.py) and ``inserter_full``/
                         ``inserter_drain`` (parallel/shuffle.py),
- ``pipeline_items``/``pipeline_producer_ns`` — items a pipelined
                         stream handed over and its producer thread's
                         life, one record a stream each (a stream
                         with a ``tally`` name of its own records
                         under it: the Parquet scan's decode hand-over
                         is ``decode_wait``/``decode_full``/
                         ``decode_items``/``decode_producer_ns``);
                         ``inserter_items`` — batches a map task put to
                         its exchange stager, one record a task;
                         ``exchange_d2h_ns`` — the exchange writer's
                         ``device_read``, a part of ``exchange_write_ns``,
- ``fused_stage_len``  — LONGEST fused segment built (a max-gauge via
                         :func:`record_max`, recorded by ``ops.fusion``
                         — plans are rebuilt per task/iteration, so a
                         sum would just count rebuilds),

accumulate into (a) a process-global tally and (b) every active
:func:`capture` scope.  The scheduler opens a capture per stage and
mirrors the counters into its MetricNode; ``bench/run.py`` opens one
around its measured window; the dispatch-budget regression test opens
one around a warm q01 run and asserts the collapse holds.

Compiles-in-trace caveat: a jitted kernel called INSIDE another trace
(the agg update program inlines the reduce + merge kernels) does not
dispatch — composition sites call the raw function kept on
``wrapper.__wrapped__`` so inlined calls are never miscounted.

Tracing integration (runtime/trace.py): while a trace kernel capture
is active (``trace._KERNEL_TIMING``), every wrapped call additionally
times the device-side drain with ``jax.block_until_ready`` and lands
``device_ns`` / ``dispatch_ns`` / ``compile_ns`` on the operator
kernel label that issued the program.  Disarmed (the default), the
check is one module-global bool read and the pre-existing non-blocking
path runs unchanged — asserted structurally by tests/test_trace.py.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Iterator, List

from ..analysis.locks import make_lock
from . import lockset, perf, trace
from .metrics import _remove_by_identity

_LOCK = make_lock("dispatch.counters")
_GLOBAL: Dict[str, int] = {}
_CAPTURES: List[Dict[str, int]] = []
_TALLY = lockset.module_guard(__name__)

#: guarded-by declaration (analysis/guarded.py): every kernel call on
#: every thread lands here, and capture registration races the
#: recording hot path
GUARDED_BY = {"_GLOBAL": "dispatch.counters",
              "_CAPTURES": "dispatch.counters"}
GUARDED_REFS = ("_GLOBAL", "_CAPTURES")


def record(name: str, v: int = 1) -> None:
    """Add ``v`` under ``name`` globally and in every active capture."""
    with _LOCK:
        lockset.check(_TALLY, "_GLOBAL", "_CAPTURES")
        _GLOBAL[name] = _GLOBAL.get(name, 0) + int(v)
        for c in _CAPTURES:
            c[name] = c.get(name, 0) + int(v)


def record_span(name: str, ns: int, n: int = 1, **more: int) -> None:
    """Host time in the tally: ``<name>_ns`` += ``ns``, ``<name>_n`` +=
    ``n`` and every ``more`` counter (what the time was spent on: a
    launch is a dispatch, a staged batch is bytes), under ONE lock
    acquisition.  ``trace.span`` closes here."""
    k_ns, k_n = name + "_ns", name + "_n"
    with _LOCK:
        lockset.check(_TALLY, "_GLOBAL", "_CAPTURES")
        for c in (_GLOBAL, *_CAPTURES):
            c[k_ns] = c.get(k_ns, 0) + ns
            c[k_n] = c.get(k_n, 0) + n
            for k, v in more.items():
                c[k] = c.get(k, 0) + v


def record_max(name: str, v: int) -> None:
    """Max-gauge variant of :func:`record` — for values that describe
    a structure (longest fused-chain length) rather than an event
    count, so per-task plan rebuilds don't inflate them."""
    with _LOCK:
        lockset.check(_TALLY, "_GLOBAL", "_CAPTURES")
        _GLOBAL[name] = max(_GLOBAL.get(name, 0), int(v))
        for c in _CAPTURES:
            c[name] = max(c.get(name, 0), int(v))


#: counter names that are max-gauges — consumers merging capture dicts
#: into MetricsSets must max() these instead of add()ing them
MAX_GAUGES = frozenset({"fused_stage_len"})


def counters() -> Dict[str, int]:
    """Snapshot of the process-global tally."""
    with _LOCK:
        return dict(_GLOBAL)


def reset() -> None:
    with _LOCK:
        _GLOBAL.clear()


@contextlib.contextmanager
def capture() -> Iterator[Dict[str, int]]:
    """Scope that accumulates every :func:`record` made while active.
    Nested/concurrent captures each get the full counts (the scheduler
    captures per stage while bench captures per query)."""
    c: Dict[str, int] = {}
    with _LOCK:
        lockset.check(_TALLY, "_CAPTURES")
        _CAPTURES.append(c)
    try:
        yield c
    finally:
        with _LOCK:
            # identity removal (metrics._remove_by_identity — the ONE
            # shared definition): list.remove compares dicts by VALUE,
            # so a nested capture holding equal counts (common: a
            # stage capture inside a query capture that has seen
            # nothing else) would evict the OUTER dict and silently
            # stop its accumulation for the rest of the scope
            _remove_by_identity(_CAPTURES, c)


def _oom_call(fn: Callable, label: str, *a, **k):
    """Run one instrumented program launch under the device-OOM
    recovery guard (rung 1 of the degradation ladder, runtime/oom.py):
    a ``RESOURCE_EXHAUSTED`` failure force-spills every memmgr-tracked
    consumer and re-runs the program ONCE; a second exhaustion
    propagates to the operator-level rungs (batch downshift, eager
    fallback).  The ``kernel.dispatch`` fault site is probed inside
    the guard, so an injected ``@oom`` rule exercises exactly this
    path.  The no-fault, no-OOM cost is one disarmed ``faults.hit``
    bool read and one try frame."""
    from . import faults

    try:
        faults.hit("kernel.dispatch", detail=label)
        return fn(*a, **k)
    except Exception as exc:  # noqa: BLE001 — classified below
        from . import oom

        if not oom.is_resource_exhausted(exc):
            raise
        oom.recover_spill(label)
    # retry outside the handler: a second RESOURCE_EXHAUSTED must reach
    # the caller's downshift/eager rungs, not recurse into spilling
    faults.hit("kernel.dispatch", detail=label)
    return fn(*a, **k)


def instrument(fn: Callable, label: str = "kernel") -> Callable:
    """Wrap a jitted callable so every call records a dispatch and
    cache-missing calls record a compile + its wall time.  ``label``
    names the operator kernel (the structural head of its kernel-cache
    key) for trace attribution.

    The raw function stays reachable as ``wrapper.__wrapped__`` for
    in-trace composition (calling the wrapper during tracing would
    count phantom dispatches for inlined sub-programs)."""
    size = getattr(fn, "_cache_size", None)
    if size is None:  # not a jit function (host helper): count calls only
        def plain(*a, **k):
            t0 = time.perf_counter_ns()
            out = fn(*a, **k)
            ns = time.perf_counter_ns() - t0
            record_span("launch", ns, xla_dispatches=1)
            if not trace._KERNEL_TIMING:
                return out
            bytes_est = flops_est = 0
            if perf._ARMED:  # one bool read disarmed (perf contract)
                bytes_est, flops_est = perf._estimate(a, k, out)
                record("hbm_bytes_est", bytes_est)
                record("flops_est", flops_est)
            trace.record_kernel(label, 0, ns, 0,
                                bytes_est=bytes_est, flops_est=flops_est)
            return out

        plain.__wrapped__ = fn
        return plain

    # compile detection is a monotone high-water mark on the jit cache
    # size, advanced under a lock: two threads cold-hitting the same
    # kernel concurrently (exchange map fan-out) both observe the size
    # step, but only the first to claim it records the compile —
    # otherwise xla_compiles/compile_ms over-count by the thread count
    state = {"seen": size()}
    state_lock = make_lock("dispatch.kernel_state")

    def wrapper(*a, **k):
        if not trace._KERNEL_TIMING:  # pre-existing non-blocking path
            # host time inside the call, in the same tally update as
            # the dispatch.  Counters only: JAX's own PjitFunction
            # event already marks the call in a trace
            t0 = time.perf_counter_ns()
            out = _oom_call(fn, label, *a, **k)
            ns = time.perf_counter_ns() - t0
            record_span("launch", ns, xla_dispatches=1)
            after = size()
            if after > state["seen"]:
                with state_lock:
                    delta = after - state["seen"]
                    if delta > 0:
                        state["seen"] = after
                        record("xla_compiles", delta)
                        record("compile_ms", int(ns / 1e6))
            return out
        # traced: split the call into launch vs device drain.  Async
        # dispatch returns once the program is enqueued, so the
        # pre-block wall is host/launch overhead (or the XLA compile,
        # when this call stepped the jit cache) and the block is the
        # device execution bill for THIS program — serializing the
        # device is the cost of attribution, paid only under capture.
        # Under spark.blaze.trace.sampleRate=N only every Nth program
        # pays the block (trace.sample_kernel); unsampled calls still
        # count and still attribute their launch overhead, and the
        # report scales device time back up by programs/timed.  At
        # sampleRate=0 no program blocks: the event log is armed and
        # the device is not serialised.
        import jax

        t0 = time.perf_counter_ns()
        out = _oom_call(fn, label, *a, **k)
        t1 = time.perf_counter_ns()
        ns = t1 - t0
        record_span("launch", ns, xla_dispatches=1)
        after = size()
        compiled = False
        if after > state["seen"]:
            with state_lock:
                delta = after - state["seen"]
                if delta > 0:
                    state["seen"] = after
                    compiled = True
                    record("xla_compiles", delta)
                    record("compile_ms", int(ns / 1e6))
        timed = trace.sample_kernel()
        if timed:
            jax.block_until_ready(out)
            device_ns = time.perf_counter_ns() - t1
        else:
            device_ns = 0
        # bytes-moved / flops estimates for the roofline attribution
        # (runtime/perf.py) — computed only under an active kernel
        # capture, and only when the estimator is armed: disarmed cost
        # is this one module-global bool read, like _KERNEL_TIMING
        bytes_est = flops_est = 0
        if perf._ARMED:
            bytes_est, flops_est = perf._estimate(a, k, out)
            record("hbm_bytes_est", bytes_est)
            record("flops_est", flops_est)
        trace.record_kernel(
            label,
            device_ns=device_ns,
            dispatch_ns=0 if compiled else ns,
            compile_ns=ns if compiled else 0,
            timed=timed,
            bytes_est=bytes_est,
            flops_est=flops_est,
        )
        return out

    wrapper.__wrapped__ = fn
    return wrapper


def raw(fn: Callable) -> Callable:
    """The uninstrumented jit function behind ``instrument``'s wrapper
    (identity for plain functions) — use when composing kernels inside
    another trace."""
    return getattr(fn, "__wrapped__", fn)
