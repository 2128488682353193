"""Pipelined batch streams: a producer thread drives the upstream
generator into a bounded queue so host staging (file decode, serde,
slicing) overlaps downstream device compute.

≙ reference NativeExecutionRuntime (blaze/src/rt.rs:100-133): a tokio
task drives the plan stream into a ``sync_channel(1)`` while the
consumer pulls — same bounded-channel shape, with the same error and
cancellation contract (producer errors surface at the consumer;
consumer teardown or task cancellation stops the producer promptly).

Both sides say when they waited for the other, with a ``trace.span``
opened only where a hand-over was about to block: ``pipeline_wait`` on
the consumer's thread (the queue was empty: the producer sets the
pace), ``pipeline_full`` on the producer's (the queue was full: the
consumer does).  Whether or not anything blocked, every stream records
``pipeline_items`` (items handed to the consumer) and
``pipeline_producer_ns`` (the producer thread's life, first
``next(stream)`` to last ``put``) once, and its producer thread lives
inside a ``blaze:<name>_producer`` annotation (``name`` is the stream's:
``blaze:parquet_scan_producer``) that carries the task's ``stage`` /
``partition``.

A stream may itself consume a pipelined stream: hand-overs compose, each
with a thread and a bound of its own, and a second one names its tally
(``tally="decode"``: ``decode_wait`` / ``decode_full`` / ``decode_items``
/ ``decode_producer_ns``) so that ``pipeline_*`` stays the task-facing
hand-over's alone.  Who runs where in the Parquet scan
(``ops/parquet_scan.ParquetScanExec.execute``):

- ``blaze-parquet_decode``: open, choice of row groups, decode and
  conversion, piece by piece; every use of an Arrow file, its close too.
  Waits in ``decode_full`` for
- ``blaze-parquet_scan``: the pieces' host batches (``scan_slice``; the
  packing of short pieces, ``scan_coalesce``) and their staging
  (``scan_stage``).  Waits in ``decode_wait`` for the
  thread above, in ``pipeline_full`` for
- the task thread: launches, reads, exchange.  Waits in
  ``pipeline_wait`` for the thread above.

A producer that ends, at the stream's end or told to stop, closes the
generator it drove, on its own thread; closing the outermost consumer
stops each producer in turn, and cancelling the task all at once.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Iterable, Iterator

from .. import conf
from . import dispatch, trace

_DONE = object()


def pipelined(stream: Iterable, ctx, depth: int = 2, name: str = "pipeline",
              tally: str = "pipeline") -> Iterator:
    """Run ``stream`` in a producer thread behind a ``depth``-bounded
    queue.  Ordering is preserved; exceptions re-raise at the consumer;
    closing the consumer (or cancelling the task) stops the producer
    within one poll interval, and the producer closes ``stream`` as it
    ends: a generator's ``finally`` blocks run on the thread that ran
    it.  ``name`` names the thread and its annotation, ``tally`` the
    hand-over's spans and counters (``<tally>_wait`` / ``_full`` /
    ``_items`` / ``_producer_ns``)."""
    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()

    def halted() -> bool:
        return stop.is_set() or not ctx.is_task_running()

    def put(item) -> bool:
        if halted():
            return False
        try:
            q.put_nowait(item)
            return True
        except queue.Full:
            pass
        with trace.span(tally + "_full", stream=name):
            while not halted():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

    def produce():
        t0 = time.perf_counter_ns()
        with trace.annotation(name + "_producer", stage=ctx.stage_id, partition=ctx.partition):
            try:
                try:
                    for item in stream:
                        if not put(item):
                            return
                finally:
                    # a stream left early ends here, not where the last
                    # reference to it happens to be dropped
                    close = getattr(stream, "close", None)
                    if close is not None:
                        close()
                put(_DONE)
            except BaseException as e:  # noqa: BLE001 — forwarded, not swallowed
                put(e)
            finally:
                dispatch.record(tally + "_producer_ns", time.perf_counter_ns() - t0)

    t = threading.Thread(target=produce, name=f"blaze-{name}", daemon=True)

    def get():
        """The next item; ``_DONE`` where the task was cancelled."""
        try:
            return q.get_nowait()
        except queue.Empty:
            pass
        with trace.span(tally + "_wait", stream=name):
            while True:
                try:
                    return q.get(timeout=0.05)
                except queue.Empty:
                    if not ctx.is_task_running():
                        return _DONE

    def consume():
        # start lazily: a stream that is never iterated must not leak a
        # producer thread (its finally below would never run)
        t.start()
        items = 0
        try:
            while True:
                item = get()  # its wait span is closed before the yield
                if item is _DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                items += 1
                yield item
        finally:
            stop.set()
            dispatch.record(tally + "_items", items)

    return consume()


def maybe_pipelined(stream: Iterable, ctx, name: str = "pipeline",
                    tally: str = "pipeline") -> Iterator:
    """Pipeline behind ``spark.blaze.pipeline.depth`` (0 disables)."""
    depth = int(conf.PIPELINE_DEPTH.get())
    if depth <= 0:
        return iter(stream)
    return pipelined(stream, ctx, depth, name, tally)
