"""Pipelined runtime: bounded-queue producer thread.

≙ reference rt.rs:100-133 (tokio stream drive into sync_channel(1)) —
ordering, error propagation, cancellation, bounded buffering, and
actual producer/consumer overlap.
"""

import threading
import time

import pytest

from blaze_tpu import conf
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.runtime.pipeline import maybe_pipelined, pipelined


def test_ordering_preserved():
    ctx = TaskContext(0, 1)
    out = list(pipelined(iter(range(100)), ctx, depth=3))
    assert out == list(range(100))


def test_error_propagates_at_consumer():
    ctx = TaskContext(0, 1)

    def gen():
        yield 1
        yield 2
        raise ValueError("boom in producer")

    it = pipelined(gen(), ctx, depth=2)
    assert next(it) == 1
    assert next(it) == 2
    with pytest.raises(ValueError, match="boom in producer"):
        next(it)


def test_bounded_queue_limits_producer():
    """The producer cannot run ahead more than depth items."""
    ctx = TaskContext(0, 1)
    produced = []

    def gen():
        for i in range(50):
            produced.append(i)
            yield i

    it = pipelined(gen(), ctx, depth=2)
    first = next(it)
    time.sleep(0.3)  # give the producer every chance to run ahead
    # at most: 1 consumed + 2 queued + 1 blocked-in-hand (+1 slack)
    assert first == 0
    assert len(produced) <= 5, produced


def test_consumer_close_stops_producer():
    ctx = TaskContext(0, 1)
    produced = []

    def gen():
        for i in range(10_000):
            produced.append(i)
            yield i

    it = pipelined(gen(), ctx, depth=1)
    assert next(it) == 0
    it.close()
    time.sleep(0.3)
    snapshot = len(produced)
    time.sleep(0.3)
    # production has STALLED after close (stop flag observed)
    assert len(produced) == snapshot
    assert snapshot < 10_000


def test_never_iterated_stream_starts_no_producer():
    """A pipelined stream that is never consumed must not leak a
    producer thread (lazy start)."""
    ctx = TaskContext(0, 1)
    produced = []

    def gen():
        for i in range(100):
            produced.append(i)
            yield i

    _ = pipelined(gen(), ctx, depth=1)
    time.sleep(0.2)
    assert produced == []  # producer never started


def test_task_cancellation_stops_both_sides():
    ctx = TaskContext(0, 1)

    def gen():
        for i in range(10_000):
            yield i
            time.sleep(0.001)

    it = pipelined(gen(), ctx, depth=1)
    assert next(it) == 0
    ctx.cancel()
    out = list(it)  # drains quickly and ends instead of blocking
    assert len(out) < 10_000


def test_overlap_actually_happens():
    """Producer staging and consumer 'compute' run concurrently: total
    wall time is well under the serial sum."""
    ctx = TaskContext(0, 1)
    n, d = 10, 0.02

    def gen():
        for i in range(n):
            time.sleep(d)  # host staging
            yield i

    t0 = time.perf_counter()
    for _ in pipelined(gen(), ctx, depth=2):
        time.sleep(d)  # device compute
    elapsed = time.perf_counter() - t0
    serial = 2 * n * d
    assert elapsed < serial * 0.8, f"no overlap: {elapsed:.3f}s vs serial {serial:.3f}s"


def test_conf_toggle():
    ctx = TaskContext(0, 1)
    old = conf.PIPELINE_DEPTH.get()
    try:
        conf.PIPELINE_DEPTH.set(0)
        it = maybe_pipelined(iter([1, 2, 3]), ctx)
        assert list(it) == [1, 2, 3]
        conf.PIPELINE_DEPTH.set(2)
        it = maybe_pipelined(iter([1, 2, 3]), ctx)
        assert list(it) == [1, 2, 3]
    finally:
        conf.PIPELINE_DEPTH.set(old)


def test_scan_through_pipeline(tmp_path):
    """ParquetScanExec output is identical with and without pipelining."""
    import pyarrow as pa
    import pyarrow.parquet as papq

    from blaze_tpu.batch import batch_to_pydict, concat_batches
    from blaze_tpu.ops import ParquetScanExec
    from blaze_tpu.schema import DataType, Field, Schema

    path = tmp_path / "p.parquet"
    papq.write_table(
        pa.table({"x": pa.array(list(range(5000)), pa.int64())}), path,
        row_group_size=512, compression="snappy",
    )
    schema = Schema([Field("x", DataType.int64())])

    def run():
        scan = ParquetScanExec([[str(path)]], schema)
        out = list(scan.execute(0, TaskContext(0, 1)))
        return batch_to_pydict(concat_batches(out))["x"]

    old = conf.PIPELINE_DEPTH.get()
    try:
        conf.PIPELINE_DEPTH.set(2)
        piped = run()
        conf.PIPELINE_DEPTH.set(0)
        sync = run()
    finally:
        conf.PIPELINE_DEPTH.set(old)
    assert piped == sync == list(range(5000))


# --------------------------------------------------------------------
# Who waits for whom (PR 38): a wait span on either side of the queue,
# opened only where the hand-over was about to block, and the stream's
# items and its producer's life whether or not anything blocked.

def _join_producers(*names):
    """The producer records its life as its thread ends: wait for it."""
    for t in threading.enumerate():
        if t.name in {f"blaze-{name}" for name in names}:
            t.join(10)
            assert not t.is_alive(), t.name


def _drive(gen, name, depth, consumer_sleep=0.0, take=None):
    from blaze_tpu.runtime import dispatch

    got = []
    with dispatch.capture() as c:
        it = pipelined(gen, TaskContext(0, 1), depth=depth, name=name)
        try:
            for item in it:
                got.append(item)
                if take is not None and len(got) == take:
                    break
                time.sleep(consumer_sleep)
        finally:
            it.close()
            _join_producers(name)
    return got, c


def test_a_slow_producer_makes_the_consumer_wait_and_never_blocks_itself():
    def gen():
        for i in range(8):
            time.sleep(0.02)
            yield i

    got, c = _drive(gen(), "slowprod", depth=2)
    assert got == list(range(8))
    # one wait an item, less what a loaded host lets the producer run ahead
    assert c["pipeline_wait_n"] >= 3
    assert c["pipeline_wait_ns"] >= 3 * 0.015e9
    assert "pipeline_full_n" not in c and "pipeline_full_ns" not in c


def test_a_slow_consumer_blocks_the_producer_and_hardly_waits_itself():
    got, c = _drive(iter(range(12)), "slowcons", depth=1, consumer_sleep=0.02)
    assert got == list(range(12))
    assert c["pipeline_full_n"] >= 4
    assert c["pipeline_full_ns"] >= 4 * 0.015e9
    # the first hand-over, while the producer thread starts, and what a
    # loaded host adds
    assert c.get("pipeline_wait_n", 0) <= 3
    assert c.get("pipeline_wait_ns", 0) < c["pipeline_full_ns"] / 2
    # the producer's life holds its waits: one thread, one clock
    assert c["pipeline_full_ns"] <= c["pipeline_producer_ns"]


@pytest.mark.parametrize("how,want", [("to_the_end", 6), ("consumer_closes_early", 3),
                                      ("producer_raises", 4)])
def test_a_stream_records_its_items_and_its_producers_life_once(how, want, monkeypatch):
    from blaze_tpu.runtime import dispatch

    records = []
    real = dispatch.record

    def spy(name, v=1):
        if name.startswith("pipeline_"):
            records.append((name, v))
        real(name, v)

    def gen():
        for i in range(6):
            if how == "producer_raises" and i == 4:
                raise ValueError("boom in producer")
            yield i

    monkeypatch.setattr(dispatch, "record", spy)
    if how == "producer_raises":
        with pytest.raises(ValueError, match="boom in producer"):
            _drive(gen(), "once", depth=2)
    else:
        got, _ = _drive(gen(), "once", depth=2,
                        take=want if how == "consumer_closes_early" else None)
        assert got == list(range(want))
    assert sorted(n for n, _ in records) == ["pipeline_items", "pipeline_producer_ns"]
    assert dict(records)["pipeline_items"] == want
    assert dict(records)["pipeline_producer_ns"] > 0


def test_no_wait_span_is_open_across_a_yield():
    """The consumer's own time between two items is not a wait: the
    producer is long done, the consumer sleeps, and pipeline_wait holds
    none of it."""
    got, c = _drive(iter(range(4)), "acrossyield", depth=8, consumer_sleep=0.05)
    assert got == list(range(4))
    assert c["pipeline_items"] == 4
    assert c.get("pipeline_wait_n", 0) <= 1
    assert c.get("pipeline_wait_ns", 0) < 0.04e9 < 4 * 0.05e9
    assert "pipeline_full_n" not in c


def test_the_producer_thread_lives_inside_an_annotation_named_for_its_stream(monkeypatch):
    """blaze:<name>_producer with the task's stage and partition, opened
    on the producer's thread, and its wait span inside it."""
    from blaze_tpu.runtime import trace

    opened = []
    real = trace.annotation

    def spy(name, **ids):
        opened.append((name, ids, threading.current_thread().name))
        return real(name, **ids)

    monkeypatch.setattr(trace, "annotation", spy)
    it = pipelined(iter(range(6)), TaskContext(2, 4, stage_id=7), depth=1, name="some_scan")
    try:
        for _ in it:
            time.sleep(0.01)
    finally:
        it.close()
        _join_producers("some_scan")
    lives = [o for o in opened if o[0].endswith("_producer")]
    assert lives == [("some_scan_producer", {"stage": 7, "partition": 2}, "blaze-some_scan")]
    full = [o for o in opened if o[0] == "pipeline_full"]
    assert full and all(o[1:] == ({"stream": "some_scan"}, "blaze-some_scan") for o in full)
    assert opened.index(lives[0]) < opened.index(full[0])


# --------------------------------------------------------------------
# Hand-overs compose (PR 39): a pipelined stream consumes a pipelined
# stream, each with a thread, a bound and a tally of its own, and the
# error and teardown contract holds through both.

def _stack(gen, ctx, depth=2):
    """``gen`` behind two hand-overs, as the Parquet scan stacks them."""
    inner = pipelined(gen, ctx, depth=depth, name="inner", tally="decode")
    return pipelined((x for x in inner), ctx, depth=depth, name="outer")


def test_a_named_tally_keeps_its_counters_out_of_pipeline_keys():
    from blaze_tpu.runtime import dispatch

    with dispatch.capture() as c:
        it = pipelined(iter(range(9)), TaskContext(0, 1), depth=1, name="named", tally="decode")
        got = []
        for x in it:
            got.append(x)
            time.sleep(0.005)
        _join_producers("named")
    assert got == list(range(9))
    assert c["decode_items"] == 9 and c["decode_producer_ns"] > 0
    assert c.get("decode_full_n", 0) >= 1  # depth 1 and a slow consumer: the producer met its bound
    assert not [k for k in c if k.startswith("pipeline_")]


def test_two_stacked_streams_tally_apart_and_keep_the_order():
    from blaze_tpu.runtime import dispatch

    with dispatch.capture() as c:
        got = list(_stack(iter(range(200)), TaskContext(0, 1)))
        _join_producers("inner", "outer")
    assert got == list(range(200))
    assert c["decode_items"] == c["pipeline_items"] == 200
    assert c["decode_producer_ns"] > 0 and c["pipeline_producer_ns"] > 0


@pytest.mark.parametrize("error", [ValueError, OSError, AssertionError])
def test_an_error_crosses_two_hand_overs_with_its_type(error):
    def gen():
        yield 1
        raise error("boom two threads down")

    it = _stack(gen(), TaskContext(0, 1))
    assert next(it) == 1
    with pytest.raises(error, match="boom two threads down") as caught:
        next(it)
    assert type(caught.value) is error
    _join_producers("inner", "outer")


@pytest.mark.parametrize("how", ["consumer_closes", "task_cancelled"])
def test_leaving_two_stacked_streams_stops_both_threads(how):
    ctx = TaskContext(0, 1)
    produced = []

    def gen():
        for i in range(100_000):
            produced.append(i)
            yield i

    it = _stack(gen(), ctx, depth=1)
    assert next(it) == 0
    if how == "consumer_closes":
        it.close()
    else:
        ctx.cancel()
        assert len(list(it)) < 100_000
    _join_producers("inner", "outer")
    assert len(produced) < 100_000


def test_a_producer_closes_the_generator_it_drove_on_its_own_thread():
    """A stream the consumer leaves early ends where it ran: its finally
    blocks (a file's close) are the producer thread's, not those of
    whichever thread drops the last reference."""
    ended = []

    def gen():
        try:
            yield from range(10_000)
        finally:
            ended.append(threading.current_thread().name)

    it = pipelined(gen(), TaskContext(0, 1), depth=1, name="closer")
    assert next(it) == 0
    it.close()
    _join_producers("closer")
    assert ended == ["blaze-closer"]
    # and through two hand-overs, innermost generator on the innermost thread
    ended.clear()
    it = _stack(gen(), TaskContext(0, 1), depth=1)
    assert next(it) == 0
    it.close()
    _join_producers("inner", "outer")
    assert ended == ["blaze-inner"]


def test_depth_zero_stacks_nothing(monkeypatch):
    """maybe_pipelined with depth 0, twice: the plain iterator, on the
    calling thread, and no hand-over tallied."""
    from blaze_tpu.runtime import dispatch

    monkeypatch.setattr("blaze_tpu.conf.PIPELINE_DEPTH.get", lambda: 0)
    seen = []

    def gen():
        for i in range(5):
            seen.append(threading.current_thread().name)
            yield i

    with dispatch.capture() as c:
        inner = maybe_pipelined(gen(), TaskContext(0, 1), "inner", tally="decode")
        got = list(maybe_pipelined((x for x in inner), TaskContext(0, 1), "outer"))
    assert got == list(range(5)) and set(seen) == {threading.current_thread().name}
    assert not [k for k in c if k.startswith(("pipeline_", "decode_"))]
