"""Shuffle: wire-format roundtrips, hash-partition writer/reader
end-to-end, broadcast exchange, ICI all-to-all path.

≙ reference batch/scalar serde roundtrip tests + the shuffle halves of
the TPC-DS differential suite (SURVEY.md §4)."""

import numpy as np
import pytest

import jax

from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
from blaze_tpu.exprs import col
from blaze_tpu.io import deserialize_batch, serialize_batch
from blaze_tpu.io.ipc_compression import compress_frame, decompress_frame
from blaze_tpu.ops import AggExec, AggFunction, AggMode, GroupingExpr, MemoryScanExec
from blaze_tpu.parallel import (
    BroadcastExchangeExec,
    HashPartitioning,
    NativeShuffleExchangeExec,
)
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema

SCHEMA = Schema([
    Field("k", DataType.int64()),
    Field("s", DataType.string(16)),
    Field("d", DataType.decimal(12, 2)),
])


def make_batch(n, seed=0):
    rng = np.random.RandomState(seed)
    return batch_from_pydict(
        {
            "k": [int(v) if v % 7 else None for v in rng.randint(0, 50, n)],
            "s": [f"row{v}" if v % 5 else None for v in rng.randint(0, 99, n)],
            "d": [round(float(v), 2) for v in rng.uniform(-100, 100, n)],
        },
        SCHEMA,
    )


def test_batch_serde_roundtrip():
    b = make_batch(37)
    data = serialize_batch(b)
    b2 = deserialize_batch(data, SCHEMA)
    assert batch_to_pydict(b2) == batch_to_pydict(b)


def test_frame_roundtrip():
    payload = b"hello world" * 1000
    assert decompress_frame(compress_frame(payload)) == payload
    # incompressible stays raw
    raw = bytes(np.random.RandomState(0).bytes(100))
    assert decompress_frame(compress_frame(raw)) == raw


@pytest.mark.parametrize("in_process", [True, False])
def test_shuffle_exchange_end_to_end(in_process):
    """Both exchange data planes: the device-resident in-process fast
    path and the .data/.index file shuffle (the cross-process tier)."""
    from blaze_tpu import conf

    n_parts_in, n_parts_out = 3, 4
    batches = [[make_batch(50, seed=i)] for i in range(n_parts_in)]
    src = MemoryScanExec(batches, SCHEMA)
    old = conf.EXCHANGE_IN_PROCESS.get()
    conf.EXCHANGE_IN_PROCESS.set(in_process)
    try:
        _run_exchange_end_to_end(batches, src, n_parts_out)
    finally:
        conf.EXCHANGE_IN_PROCESS.set(old)


def _run_exchange_end_to_end(batches, src, n_parts_out):
    ex = NativeShuffleExchangeExec(src, HashPartitioning([col("k")], n_parts_out))

    all_rows = []
    seen_keys_per_part = []
    for p in range(n_parts_out):
        ctx = TaskContext(p, n_parts_out)
        keys = set()
        for b in ex.execute(p, ctx):
            d = batch_to_pydict(b)
            keys.update(d["k"])
            all_rows.extend(zip(d["k"], d["s"], d["d"]))
        seen_keys_per_part.append(keys)
    # row multiset preserved
    expected = []
    for part in batches:
        for b in part:
            d = batch_to_pydict(b)
            expected.extend(zip(d["k"], d["s"], d["d"]))
    key_of = lambda r: tuple((v is None, v) for v in r)
    assert sorted(all_rows, key=key_of) == sorted(expected, key=key_of)
    # co-partitioning: each key appears in exactly one output partition
    for i in range(n_parts_out):
        for j in range(i + 1, n_parts_out):
            assert not (seen_keys_per_part[i] & seen_keys_per_part[j])


def test_shuffle_plus_final_agg():
    """partial agg -> hash exchange on group key -> final agg ==
    the canonical two-stage group-by (TPC-H q01 shape)."""
    n_parts = 3
    batches = [[make_batch(80, seed=10 + i)] for i in range(n_parts)]
    src = MemoryScanExec(batches, SCHEMA)
    part = AggExec(
        src, AggMode.PARTIAL,
        [GroupingExpr(col("k"), "k")],
        [AggFunction("sum", col("d"), "sd"), AggFunction("count_star", None, "n")],
    )
    ex = NativeShuffleExchangeExec(part, HashPartitioning([col("k")], 4))
    final = AggExec(
        ex, AggMode.FINAL,
        [GroupingExpr(col("k"), "k")],
        part.aggs,
    )
    got = {}
    for p in range(4):
        for b in final.execute(p, TaskContext(p, 4)):
            d = batch_to_pydict(b)
            for k, sd, n in zip(d["k"], d["sd"], d["n"]):
                assert k not in got, "group split across partitions"
                got[k] = (sd, n)
    # oracle: plain python
    exp = {}
    for part_b in batches:
        for b in part_b:
            d = batch_to_pydict(b)
            for k, dd in zip(d["k"], d["d"]):
                s, c = exp.get(k, (0, 0))
                exp[k] = (s + (dd if dd is not None else 0), c + 1)
    assert set(got) == set(exp)
    for k in exp:
        assert got[k][1] == exp[k][1]
        assert got[k][0] == exp[k][0]


def test_broadcast_exchange_replicates():
    src = MemoryScanExec([[make_batch(10, seed=1)], [make_batch(5, seed=2)]], SCHEMA)
    bx = BroadcastExchangeExec(src)
    rows1 = sum(b.num_rows for b in bx.execute(0, TaskContext(0, 1)))
    rows2 = sum(b.num_rows for b in bx.execute(0, TaskContext(0, 1)))
    assert rows1 == rows2 == 15


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs virtual multi-device mesh")
def test_ici_all_to_all_exchange():
    from blaze_tpu.parallel.ici import ici_shuffle
    from blaze_tpu.parallel.mesh import make_mesh

    n_dev = 4
    mesh = make_mesh(n_dev)
    cap = 64
    schema = Schema([Field("k", DataType.int64()), Field("v", DataType.int64())])
    rng = np.random.RandomState(3)
    ks = rng.randint(0, 1000, n_dev * cap)
    per_shard_rows = np.full(n_dev, cap, np.int32)
    # make some rows padding on each shard
    per_shard_rows[1] = 30
    batch = batch_from_pydict(
        {"k": ks.tolist(), "v": list(range(n_dev * cap))}, schema, capacity=n_dev * cap
    )
    out_cols, totals = ici_shuffle(mesh, batch, per_shard_rows, [col("k")])
    totals = np.asarray(totals)
    total_rows = int(totals.sum())
    assert total_rows == cap * (n_dev - 1) + 30
    # verify each received row landed on the right device
    from blaze_tpu.exprs.hash import murmur3_columns, pmod
    from blaze_tpu.batch import Column

    k_all = np.asarray(out_cols[0].data)      # (n_dev * local_out,)
    valid = np.asarray(out_cols[0].validity)
    local_out = k_all.shape[0] // n_dev
    for d in range(n_dev):
        seg = k_all[d * local_out : (d + 1) * local_out]
        vmask = valid[d * local_out : (d + 1) * local_out]
        kept = seg[vmask]
        if kept.size:
            c = Column(DataType.int64(), kept.astype(np.int64), np.ones(kept.size, bool))
            pids = np.asarray(pmod(murmur3_columns([c]), n_dev))
            assert (pids == d).all()


def test_range_partitioned_global_sort():
    """RangePartitioning exchange + per-partition sorts == global sort:
    partitions hold disjoint key ranges in partition order (incl. nulls
    and string keys)."""
    from blaze_tpu.ops import SortExec, SortField
    from blaze_tpu.parallel import RangePartitioning

    n_parts_in, n_out = 3, 4
    batches = [[make_batch(60, seed=20 + i)] for i in range(n_parts_in)]
    src = MemoryScanExec(batches, SCHEMA)
    fields = [SortField(col("k"), ascending=True, nulls_first=True),
              SortField(col("s"), ascending=False, nulls_first=False)]
    ex = NativeShuffleExchangeExec(src, RangePartitioning(fields, n_out))
    # per-partition sort, then concatenate partitions in order
    srt = SortExec(ex, fields)
    rows = []
    for p in range(n_out):
        for b in srt.execute(p, TaskContext(p, n_out)):
            d = batch_to_pydict(b)
            rows.extend(zip(d["k"], d["s"], d["d"]))
    # oracle: global sort of all input rows by the same keys
    allrows = []
    for part in batches:
        for b in part:
            d = batch_to_pydict(b)
            allrows.extend(zip(d["k"], d["s"], d["d"]))

    # compare the primary-key order and the row multiset (secondary
    # tie-break details differ between python and engine comparators)
    ks = [r[0] for r in rows]
    exp_ks = sorted((r[0] for r in allrows), key=lambda v: (v is not None, v))
    assert ks == exp_ks
    key_of = lambda r: tuple((v is None, v) for v in r)
    assert sorted(rows, key=key_of) == sorted(allrows, key=key_of)


def test_range_partitioning_mixed_string_widths():
    """Range keys over string columns whose physical padded widths
    differ per batch (runtime-width strings): word counts are aligned
    per field, so ordering stays correct."""
    from blaze_tpu.batch import Column, RecordBatch
    from blaze_tpu.ops import SortExec, SortField
    from blaze_tpu.parallel import RangePartitioning

    schema = Schema([Field("s", DataType.string(16))])

    def batch_with_width(strings, width):
        n = len(strings)
        data = np.zeros((n, width), np.uint8)
        lengths = np.zeros(n, np.int32)
        for i, t in enumerate(strings):
            b = t.encode()
            data[i, : len(b)] = np.frombuffer(b, np.uint8)
            lengths[i] = len(b)
        col_ = Column(DataType.string(16), data, np.ones(n, bool), lengths)
        return RecordBatch(schema, [col_], n)

    b1 = batch_with_width(["apple", "zebra", "mango"], 8)       # 1 data word
    b2 = batch_with_width(["banana", "cherry", "apricots"], 16)  # 2 data words
    src = MemoryScanExec([[b1], [b2]], schema)
    ex = NativeShuffleExchangeExec(src, RangePartitioning([SortField(col("s"))], 2))
    srt = SortExec(ex, [SortField(col("s"))])
    got = []
    for p in range(2):
        for b in srt.execute(p, TaskContext(p, 2)):
            got.extend(batch_to_pydict(b)["s"])
    assert got == sorted(["apple", "zebra", "mango", "banana", "cherry", "apricots"])

    # DESCENDING with prefix-related keys across widths: inverted
    # padding words (~0) must not disagree with a narrower batch's
    # normalized words (regression: zero-word alignment broke this)
    b1 = batch_with_width(["applepie", "zebra", "aaa"], 8)
    b2 = batch_with_width(["applepieX", "applepie", "mango"], 16)
    src = MemoryScanExec([[b1], [b2]], schema)
    fields_d = [SortField(col("s"), ascending=False)]
    ex = NativeShuffleExchangeExec(src, RangePartitioning(fields_d, 2))
    srt = SortExec(ex, fields_d)
    got = []
    for p in range(2):
        for b in srt.execute(p, TaskContext(p, 2)):
            got.extend(batch_to_pydict(b)["s"])
    assert got == sorted(
        ["applepie", "zebra", "aaa", "applepieX", "applepie", "mango"], reverse=True
    )


def test_inprocess_exchange_hbm_budget_fallback():
    """A stage output beyond the HBM budget falls back to the spillable
    file shuffle instead of accumulating device-resident."""
    from blaze_tpu import conf

    n_parts_in, n_parts_out = 3, 4
    batches = [[make_batch(50, seed=i)] for i in range(n_parts_in)]
    src = MemoryScanExec(batches, SCHEMA)
    old = conf.DEVICE_MEMORY_BUDGET.get()
    conf.DEVICE_MEMORY_BUDGET.set(1024)  # absurdly small
    try:
        ex = NativeShuffleExchangeExec(src, HashPartitioning([col("k")], n_parts_out))
        _run_exchange_end_to_end(batches, src, n_parts_out)
        # the helper builds its own exchange; run this one too to see
        # the fallback flag flip
        rows = 0
        for p in range(n_parts_out):
            for b in ex.execute(p, TaskContext(p, n_parts_out)):
                rows += b.num_rows
        assert ex._hbm_fallback
        assert rows == sum(b.num_rows for part in batches for b in part)
    finally:
        conf.DEVICE_MEMORY_BUDGET.set(old)


def test_range_partitioning_across_serde_file_shuffle():
    """Range-partitioned global sort through the STAGE SCHEDULER: the
    scheduler's driver-side boundary pass fills the partitioning's
    boundary words, every map task crosses the TaskDefinition protobuf
    boundary, and the shuffle rides real .data/.index files — the
    distributed path the in-process exchange cannot cover
    (≙ Spark's RangePartitioner sample job + ShuffleDependency)."""
    from blaze_tpu import conf
    from blaze_tpu.ops import SortExec, SortField
    from blaze_tpu.parallel import RangePartitioning
    from blaze_tpu.runtime.scheduler import run_stages, split_stages

    old = conf.EXCHANGE_IN_PROCESS.get()
    conf.EXCHANGE_IN_PROCESS.set(False)  # force the file-shuffle tier
    try:
        n_parts_in, n_out = 3, 4
        batches = [[make_batch(60, seed=40 + i)] for i in range(n_parts_in)]
        src = MemoryScanExec(batches, SCHEMA)
        fields = [SortField(col("k"), ascending=True, nulls_first=True)]
        ex = NativeShuffleExchangeExec(src, RangePartitioning(fields, n_out))
        plan = SortExec(ex, fields)
        stages, manager = split_stages(plan)
        rows = []
        for b in run_stages(stages, manager):
            d = batch_to_pydict(b)
            rows.extend(zip(d["k"], d["s"], d["d"]))
        allrows = []
        for part in batches:
            for b in part:
                d = batch_to_pydict(b)
                allrows.extend(zip(d["k"], d["s"], d["d"]))
        ks = [r[0] for r in rows]
        exp_ks = sorted((r[0] for r in allrows), key=lambda v: (v is not None, v))
        assert ks == exp_ks, "global order broken across the serde boundary"
        key_of = lambda r: tuple((v is None, v) for v in r)
        assert sorted(rows, key=key_of) == sorted(allrows, key=key_of)
        # the boundary pass must have filled serializable boundaries
        assert ex.partitioning.boundaries is not None
    finally:
        conf.EXCHANGE_IN_PROCESS.set(old)


# ------------------------------- the write loop's device staging ring


@pytest.fixture(scope="module")
def tpch_data():
    from blaze_tpu.tpch.datagen import generate_all

    return generate_all(0.01)


def _agg_write(data, tmp, tag):
    """FINAL(PARTIAL(lineitem by l_returnflag)) under an optimized hash
    shuffle writer; returns (writer, data_path, index_path)."""
    import os

    from blaze_tpu.ops.fusion import optimize_plan
    from blaze_tpu.parallel.shuffle import ShuffleWriterExec
    from blaze_tpu.tpch import TPCH_SCHEMAS
    from blaze_tpu.tpch.datagen import table_to_batches

    sch = TPCH_SCHEMAS["lineitem"]
    scan = MemoryScanExec(
        table_to_batches(data["lineitem"], sch, 1, batch_rows=2048), sch)
    groupings = [GroupingExpr(col("l_returnflag"), "l_returnflag")]
    aggs = [AggFunction("sum", col("l_quantity"), "sum_qty"),
            AggFunction("count_star", None, "cnt")]
    partial = AggExec(scan, AggMode.PARTIAL, groupings, aggs)
    final = AggExec(partial, AggMode.FINAL, groupings, aggs)
    data_path = os.path.join(tmp, f"{tag}.data")
    index_path = os.path.join(tmp, f"{tag}.index")
    writer = optimize_plan(ShuffleWriterExec(
        final, HashPartitioning([col("l_returnflag")], 3),
        data_path, index_path))
    return writer, data_path, index_path


def _hash_write(data, tmp, tag):
    writer, data_path, index_path = _agg_write(data, tmp, tag)
    list(writer.execute(0, TaskContext(0, 1)))
    with open(data_path, "rb") as f, open(index_path, "rb") as g:
        return f.read(), g.read()


def test_abort_mid_stream_drops_ring_without_commit(tpch_data, tmp_path):
    """A task killed mid-stream (injected non-OOM fault — the same
    seam a ctx cancel rides) drops the device ring and aborts the
    async writer: nothing commits, and a fresh run afterwards still
    produces the canonical bytes (no poisoned process state)."""
    import os

    from blaze_tpu import conf
    from blaze_tpu.runtime import faults

    tmp = str(tmp_path)
    plain_blob, plain_idx = _hash_write(tpch_data, tmp, "plain")
    conf.FAULTS_SPEC.set("kernel.dispatch@4@a0")
    faults.reset()
    try:
        writer, data_path, index_path = _agg_write(tpch_data, tmp, "m")
        with pytest.raises(faults.InjectedFault):
            list(writer.execute(0, TaskContext(0, 1)))
        assert not os.path.exists(data_path), \
            "aborted task committed a partial .data file"
        assert not os.path.exists(index_path)
        conf.FAULTS_SPEC.set("")
        faults.reset()
        # the seam leaks nothing into process state: a clean run after
        # the abort still commits the canonical bytes
        blob2, idx2 = _hash_write(tpch_data, tmp, "again")
    finally:
        conf.FAULTS_SPEC.set("")
        faults.reset()
    assert blob2 == plain_blob and idx2 == plain_idx


def test_device_ring_fifo_and_overlap_metric():
    from blaze_tpu.batch import DeviceRing
    from blaze_tpu.runtime import dispatch

    ring = DeviceRing()
    with dispatch.capture() as cap:
        out = []
        for i in range(5):
            out.extend(ring.put(i))
        out.extend(ring.flush())
    assert out == [0, 1, 2, 3, 4], "ring must preserve FIFO order"
    assert len(ring) == 0
    assert cap.get("double_buffer_overlap_ns", 0) > 0
    ring.put(9)
    ring.drop()
    assert len(ring) == 0 and ring.flush() == []


# ------------------- the map task's thread waiting for its stager (PR 38)


def test_a_stalled_stager_shows_as_the_tasks_wait():
    """depth 1 and a stager that takes 30 ms a batch: the third put finds
    the queue full (inserter_full), close() waits out the rest
    (inserter_drain), inserter_items counts the puts, and the stager's
    D2H is the exchange_d2h_ns share of its exchange_write."""
    import time

    from blaze_tpu.parallel.shuffle import ShuffleRepartitioner, _AsyncInserter
    from blaze_tpu.runtime import dispatch
    from blaze_tpu.runtime.metrics import MetricsSet

    schema = Schema([Field("x", DataType.int64())])
    rep = ShuffleRepartitioner(schema, 1, MetricsSet())
    real_insert = rep.insert_sorted

    def slow_insert(host, counts):
        time.sleep(0.03)
        real_insert(host, counts)

    rep.insert_sorted = slow_insert
    b = batch_from_pydict({"x": [1, 2, 3, 4]}, schema)
    with dispatch.capture() as c:
        ins = _AsyncInserter(rep, schema, depth=1, metrics=MetricsSet(), stage=7, partition=2)
        for _ in range(5):
            ins.put((list(b.columns), np.array([4]), 4))
        ins.close()
    assert not ins._thread.is_alive()
    assert c["inserter_items"] == 5 == c["exchange_write_n"] == c["device_read_n"]
    # three of the five puts find the queue full, less what a loaded host
    # lets the stager catch up
    assert c["inserter_full_n"] >= 2 and c["inserter_drain_n"] == 1
    assert c["inserter_full_ns"] >= 0.025e9 and c["inserter_drain_ns"] >= 0.025e9
    assert 0 < c["exchange_d2h_ns"] == c["device_read_ns"] < c["exchange_write_ns"]
    # what the task waited for is inside what the stager did
    waited = c["inserter_full_ns"] + c["inserter_drain_ns"]
    assert waited <= c["exchange_write_ns"] * 1.5


@pytest.mark.parametrize("async_write", [True, False])
def test_a_map_task_tallies_its_stager_only_where_it_has_one(tpch_data, tmp_path, async_write):
    from blaze_tpu import conf
    from blaze_tpu.runtime import dispatch

    old = conf.SHUFFLE_ASYNC_WRITE.get()
    conf.SHUFFLE_ASYNC_WRITE.set(async_write)
    try:
        writer, _, _ = _agg_write(tpch_data, str(tmp_path), f"w{int(async_write)}")
        with dispatch.capture() as c:
            list(writer.execute(0, TaskContext(0, 1)))
    finally:
        conf.SHUFFLE_ASYNC_WRITE.set(old)
    inserter = {k for k in c if k.startswith("inserter_")}
    if async_write:
        # every batch staged plus the commit is an exchange_write
        assert c["inserter_items"] == c["exchange_write_n"] - 1 > 0
        assert c["inserter_drain_n"] == 1
        assert inserter >= {"inserter_items", "inserter_drain_ns", "inserter_drain_n"}
    else:
        assert not inserter
    assert 0 < c["exchange_d2h_ns"] <= c["device_read_ns"]
    assert c["exchange_d2h_ns"] < c["exchange_write_ns"]


def test_the_stager_thread_lives_inside_an_annotation_with_its_tasks_ids(monkeypatch):
    """blaze:exchange_stager carries what the writer handed the inserter;
    the stager's exchange_write spans open inside it, on its thread, and
    the task's drain on the task's."""
    import threading

    from blaze_tpu.parallel.shuffle import ShuffleRepartitioner, _AsyncInserter
    from blaze_tpu.runtime import trace
    from blaze_tpu.runtime.metrics import MetricsSet

    opened = []
    real = trace.annotation

    def spy(name, **ids):
        opened.append((name, ids, threading.current_thread().name))
        return real(name, **ids)

    monkeypatch.setattr(trace, "annotation", spy)
    schema = Schema([Field("x", DataType.int64())])
    rep = ShuffleRepartitioner(schema, 1, MetricsSet())
    b = batch_from_pydict({"x": [1, 2, 3, 4]}, schema)
    ins = _AsyncInserter(rep, schema, depth=2, metrics=MetricsSet(), stage=7, partition=2)
    for _ in range(3):
        ins.put((list(b.columns), np.array([4]), 4))
    ins.close()
    me = threading.current_thread().name
    by_name = {}
    for name, ids, thread in opened:
        by_name.setdefault(name, []).append((ids, thread))
    assert by_name["exchange_stager"] == [({"stage": 7, "partition": 2}, "shuffle-async-insert")]
    assert by_name["exchange_write"] == [({}, "shuffle-async-insert")] * 3
    assert by_name["inserter_drain"] == [({}, me)]
    assert {t for _, t in by_name.get("inserter_full", [])} <= {me}
    names = [o[0] for o in opened]
    assert names.index("exchange_stager") < names.index("exchange_write")
