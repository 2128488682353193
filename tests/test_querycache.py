"""Serving-scale query cache hierarchy (runtime/querycache.py):

1. **Plan cache / literal slots** — parameter-shifted variants of one
   plan shape share a fingerprint and ONE compiled fused program: the
   warm shifted run is gated at ZERO xla compiles.
2. **Result cache invalidation** — any source mutation (MemoryScan
   append/replace epoch bump, parquet/ORC file rewrite) changes the
   source version inside the fingerprint, so a stale entry is never
   served and a post-mutation run is byte-identical to a fresh one.
3. **Concurrency** — invalidate-during-hit races run under the armed
   lockset + lock-order checkers; every hit returns the complete row
   set for the epoch its fingerprint named.
"""

import threading

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu import conf
from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
from blaze_tpu.exprs import col, lit
from blaze_tpu.ops import MemoryScanExec, ParquetScanExec
from blaze_tpu.ops.filter import FilterExec
from blaze_tpu.ops.fusion import optimize_plan
from blaze_tpu.ops.orc_scan import OrcScanExec
from blaze_tpu.ops.project import ProjectExec
from blaze_tpu.runtime import dispatch, lockset, querycache
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema

SCHEMA = Schema([Field("k", DataType.int64()),
                 Field("v", DataType.float64())])


@pytest.fixture(autouse=True)
def _fresh_cache():
    querycache.reset_for_tests()
    yield
    querycache.reset_for_tests()


def _batch(seed: int, n: int = 256):
    rng = np.random.RandomState(seed)
    return batch_from_pydict(
        {"k": rng.randint(0, 50, n).tolist(),
         "v": (rng.rand(n) * 100).round(3).tolist()}, SCHEMA)


def _param_plan(scan, thresh: float, factor: float):
    f = FilterExec(scan, col("v") > lit(float(thresh)))
    p = ProjectExec(f, [col("k").alias("k"),
                        (col("v") * lit(float(factor))).alias("v2")])
    return p


def _run(plan):
    out = []
    for part in range(plan.num_partitions()):
        for b in plan.execute(part, TaskContext(part,
                                                plan.num_partitions())):
            out.append(b)
    return out


def _rows(batches):
    rows = []
    for b in batches:
        d = batch_to_pydict(b)
        names = sorted(d)
        rows.extend(zip(*[d[n] for n in names]))
    return sorted(rows, key=repr)


# ------------------------------------------------ 1. plan cache / slots

def test_parameter_shift_zero_recompiles():
    """WHERE v > 5 and WHERE v > 9 (and a shifted projection factor)
    share one fused program: the second variant's warm run must not
    compile anything — the tentpole's program-reuse claim as a
    dispatch-budget gate."""
    scan = MemoryScanExec([[_batch(0)]], SCHEMA)
    base = optimize_plan(_param_plan(scan, 5.0, 2.0))
    _run(base)  # cold: compiles allowed
    with dispatch.capture() as warm:
        shifted = optimize_plan(_param_plan(scan, 9.0, 3.0))
        got = _rows(_run(shifted))
    assert warm.get("xla_compiles", 0) == 0, (
        f"literal shift recompiled: {warm}")
    # and the shifted program computed the SHIFTED answer
    d = batch_to_pydict(_batch(0))
    want = sorted(((k, round(v * 3.0, 10)) for k, v in zip(d["k"], d["v"])
                   if v > 9.0), key=repr)
    assert [(k, round(v, 10)) for k, v in got] == want


def test_shifted_literals_share_fingerprint_distinct_slots():
    scan = MemoryScanExec([[_batch(1)]], SCHEMA)
    fa = querycache.plan_fingerprint(optimize_plan(_param_plan(scan, 5.0, 2.0)))
    fb = querycache.plan_fingerprint(optimize_plan(_param_plan(scan, 9.0, 2.0)))
    assert fa is not None and fb is not None
    assert fa.exact and fb.exact
    assert fa.digest == fb.digest, "literal shift changed the digest"
    assert fa.slots != fb.slots
    assert fa.result_key() != fb.result_key()


def test_structural_literal_args_never_become_slots():
    """Type-determining literal args (decimal precision/scale, slice
    bounds) are read with ``.value`` at trace time — slotification must
    leave them as ``Lit`` while still slotting true data literals."""
    from blaze_tpu.exprs.compile import infer_dtype, slotify_literals
    from blaze_tpu.exprs.ir import Lit, ScalarFunc, Slot

    e = ScalarFunc("check_overflow",
                   [col("v") * lit(1.5), lit(12), lit(2)])
    (new,), vals = slotify_literals([e])
    assert isinstance(new.args[1], Lit) and isinstance(new.args[2], Lit)
    assert isinstance(new.args[0].right, Slot), "data literal must slot"
    assert len(vals) == 1 and float(vals[0]) == 1.5
    # type inference still works on the slotified tree
    t = infer_dtype(new, SCHEMA)
    assert t.is_decimal and t.precision == 12 and t.scale == 2


def test_decimal_literal_from_plan_bytes_slots_unscaled_once():
    """A decimal literal decoded from TaskDefinition bytes is ALREADY
    unscaled (``RawUnscaled``).  Its slot must ship that value as is:
    scaling it again inflated ``1 - l_discount`` to ``100 - l_discount``
    in every non-absorbed Project/Filter of the served path (q03's
    revenue through the scheduler was 100x off, found on the PR 22
    bring-up).  The baked constant, the slot and the serializer share
    one definition."""
    from blaze_tpu.exprs.compile import (RawUnscaled, decimal_unscaled,
                                         expr_key, slotify_literals)
    from blaze_tpu.schema import DataType
    from blaze_tpu.serde.from_proto import expr_from_proto
    from blaze_tpu.serde.to_proto import expr_to_proto

    dec = DataType.decimal(12, 2)
    logical = lit(1, dec) - col("d")
    decoded = expr_from_proto(expr_to_proto(logical))
    assert isinstance(decoded.left.value, RawUnscaled)
    (_,), vals_logical = slotify_literals([logical])
    (_,), vals_decoded = slotify_literals([decoded])
    assert [int(v) for v in vals_logical] == [100]
    assert [int(v) for v in vals_decoded] == [100]
    # ...and out again: a second serde hop does not scale it either
    assert expr_to_proto(decoded).SerializeToString() \
        == expr_to_proto(logical).SerializeToString()
    for value, want in ((1, 100), ("0.05", 5), (0.07, 7),
                        (RawUnscaled(100), 100)):
        assert decimal_unscaled(value, 2) == want
    # a baked-constant kernel is never shared between the logical 100
    # and the unscaled 100: their structural keys differ
    assert expr_key(lit(100, dec)) != expr_key(lit(RawUnscaled(100), dec))


def test_result_cache_never_serves_other_slot_values():
    """Same digest, different slot values: the result key differs, so
    a WHERE v > 5 entry can never answer WHERE v > 9."""
    scan = MemoryScanExec([[_batch(2)]], SCHEMA)
    plan_a = optimize_plan(_param_plan(scan, 5.0, 2.0))
    fa = querycache.plan_fingerprint(plan_a)
    rc = querycache.result_cache()
    assert rc.store(fa, _run(plan_a))
    assert rc.lookup(fa) is not None
    fb = querycache.plan_fingerprint(optimize_plan(_param_plan(scan, 9.0, 2.0)))
    assert rc.lookup(fb) is None


# ------------------------------------------- 2. source-version changes

def _store_and_check_roundtrip(plan):
    fp = querycache.plan_fingerprint(plan)
    assert fp is not None and fp.exact, "plan must be exactly cacheable"
    rc = querycache.result_cache()
    fresh = _run(plan)
    assert rc.store(fp, fresh)
    got = rc.lookup(fp)
    assert got is not None
    assert _rows(got) == _rows(fresh)
    return fp, rc


def test_memoryscan_append_invalidates():
    scan = MemoryScanExec([[_batch(3)]], SCHEMA)
    plan = optimize_plan(_param_plan(scan, 10.0, 2.0))
    fp, rc = _store_and_check_roundtrip(plan)
    before = dispatch.counters().get("result_cache_invalidations", 0)
    scan.append(0, _batch(4))
    fp2 = querycache.plan_fingerprint(plan)
    assert fp2.digest == fp.digest and fp2.sources != fp.sources
    # the stale entry is dropped at lookup, never served
    assert rc.lookup(fp2) is None
    assert dispatch.counters()["result_cache_invalidations"] == before + 1
    # post-mutation recompute is byte-identical to a fresh run
    fresh = _run(plan)
    assert rc.store(fp2, fresh)
    assert _rows(rc.lookup(fp2)) == _rows(fresh)
    assert len(_rows(fresh)) > len(_rows(_run(
        optimize_plan(_param_plan(MemoryScanExec([[_batch(3)]], SCHEMA),
                                  10.0, 2.0)))))


def test_memoryscan_replace_invalidates():
    scan = MemoryScanExec([[_batch(5)]], SCHEMA)
    plan = optimize_plan(_param_plan(scan, 10.0, 2.0))
    fp, rc = _store_and_check_roundtrip(plan)
    scan.replace([[_batch(6)]])
    fp2 = querycache.plan_fingerprint(plan)
    assert fp2.sources != fp.sources
    assert rc.lookup(fp2) is None
    assert _rows(_run(plan)) == _rows(_run(optimize_plan(_param_plan(
        MemoryScanExec([[_batch(6)]], SCHEMA), 10.0, 2.0))))


def _write_file(path, n, writer):
    t = pa.table({"x": pa.array(list(range(n)), pa.int64())})
    writer(t, str(path))
    return Schema([Field("x", DataType.int64())])


def _file_scan_case(tmp_path, cls, writer, fname):
    """Shared body: rewrite-the-file invalidation for a file scan."""
    path = tmp_path / fname
    schema = _write_file(path, 300, writer)
    plan = cls([[str(path)]], schema)
    fp, rc = _store_and_check_roundtrip(plan)
    # rewrite with different content (size changes with row count, so
    # the (mtime_ns, size) version moves even on coarse-mtime
    # filesystems)
    _write_file(path, 450, writer)
    fp2 = querycache.plan_fingerprint(plan)
    assert fp2 is not None and fp2.sources != fp.sources
    assert rc.lookup(fp2) is None, "stale file-scan result served"
    fresh = _run(plan)
    assert sorted(x for r in _rows(fresh) for x in r) == list(range(450))
    assert rc.store(fp2, fresh)
    assert _rows(rc.lookup(fp2)) == _rows(fresh)


def test_parquet_rewrite_invalidates(tmp_path):
    import pyarrow.parquet as papq

    _file_scan_case(tmp_path, ParquetScanExec,
                    lambda t, p: papq.write_table(t, p), "t.parquet")


def test_orc_rewrite_invalidates(tmp_path):
    from pyarrow import orc as paorc

    _file_scan_case(tmp_path, OrcScanExec,
                    lambda t, p: paorc.write_table(t, p), "t.orc")


def test_deleted_source_file_is_uncacheable(tmp_path):
    import pyarrow.parquet as papq

    path = tmp_path / "gone.parquet"
    schema = _write_file(path, 10, lambda t, p: papq.write_table(t, p))
    plan = ParquetScanExec([[str(path)]], schema)
    assert querycache.plan_fingerprint(plan) is not None
    path.unlink()
    assert querycache.plan_fingerprint(plan) is None


# ------------------------------------------------- 3. budget mechanics

def test_lru_eviction_respects_byte_budget():
    rc = querycache.result_cache()
    scans = [MemoryScanExec([[_batch(10 + i, n=512)]], SCHEMA)
             for i in range(3)]
    plans = [optimize_plan(_param_plan(s, 0.0, 2.0)) for s in scans]
    fps = [querycache.plan_fingerprint(p) for p in plans]
    results = [_run(p) for p in plans]
    one = querycache._batches_nbytes([b.to_host() for b in results[0]])
    prev = conf.CACHE_RESULT_MAX_BYTES.get()
    conf.CACHE_RESULT_MAX_BYTES.set(int(one * 2.5))
    try:
        for fp, res in zip(fps, results):
            assert rc.store(fp, res)
        # budget fits ~2.5 entries: the LRU-coldest (first) was evicted
        assert rc.lookup(fps[0]) is None
        assert rc.lookup(fps[2]) is not None
        assert dispatch.counters().get("result_cache_evictions", 0) >= 1
        assert rc.stats()["total_bytes"] <= int(one * 2.5)
    finally:
        conf.CACHE_RESULT_MAX_BYTES.set(prev)


def test_oversized_entry_refused():
    rc = querycache.result_cache()
    scan = MemoryScanExec([[_batch(20, n=512)]], SCHEMA)
    plan = optimize_plan(_param_plan(scan, 0.0, 2.0))
    fp = querycache.plan_fingerprint(plan)
    prev = conf.CACHE_RESULT_MAX_ENTRY_BYTES.get()
    conf.CACHE_RESULT_MAX_ENTRY_BYTES.set(64)
    try:
        assert not rc.store(fp, _run(plan))
        assert rc.stats()["entries"] == 0
    finally:
        conf.CACHE_RESULT_MAX_ENTRY_BYTES.set(prev)


def test_spill_promote_roundtrip():
    """A spilled entry (memmgr pressure path) is promoted back on hit,
    byte-identical — the one-shot spill cursor is drained exactly once
    under the cache lock."""
    rc = querycache.result_cache()
    scan = MemoryScanExec([[_batch(21)]], SCHEMA)
    plan = optimize_plan(_param_plan(scan, 0.0, 2.0))
    fp = querycache.plan_fingerprint(plan)
    fresh = _run(plan)
    assert rc.store(fp, fresh)
    freed = rc._consumer.spill()
    assert freed > 0
    assert rc.stats()["resident_bytes"] == 0
    assert dispatch.counters().get("result_cache_spills", 0) >= 1
    got = rc.lookup(fp)
    assert got is not None and _rows(got) == _rows(fresh)
    assert rc.stats()["resident_bytes"] > 0  # promoted back
    # a second hit serves from RAM again
    assert _rows(rc.lookup(fp)) == _rows(fresh)


# --------------------------------------------- 4. concurrency, armed

def test_invalidate_during_hit_race_armed():
    """Readers hammer lookup() while a writer appends to the source
    and stores fresh results — under the armed lockset + lock-order
    checkers.  Every hit must return the COMPLETE row set of the epoch
    its fingerprint named: a fingerprint taken before the append may
    legitimately hit the old entry, but a post-append fingerprint must
    never see old rows."""
    from blaze_tpu.analysis import locks

    scan = MemoryScanExec([[_batch(30, n=128)]], SCHEMA)

    def plan():
        return optimize_plan(_param_plan(scan, 0.0, 2.0))

    rc = querycache.result_cache()
    expected = {}  # epoch -> sorted rows

    def publish():
        p = plan()
        fp = querycache.plan_fingerprint(p)
        rows = _run(p)
        expected[scan.epoch] = _rows(rows)
        assert rc.store(fp, rows)

    publish()
    errors = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                fp = querycache.plan_fingerprint(plan())
                got = rc.lookup(fp)
                if got is None:
                    continue
                epoch = fp.sources[0][2]
                want = expected.get(epoch)
                # expected[] is written before store() on the writer
                # thread, so a hit's epoch is always published
                if want is None or _rows(got) != want:
                    errors.append(
                        f"hit for epoch {epoch} served wrong rows")
                    return
        except Exception as e:  # noqa: BLE001 — surfaced to the assert
            errors.append(f"{type(e).__name__}: {e}")

    conf.VERIFY_LOCKS.set(True)
    locks.refresh()
    conf.VERIFY_LOCKSET.set(True)
    lockset.refresh()
    threads = [threading.Thread(target=reader) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        for i in range(6):
            scan.append(0, _batch(31 + i, n=64))
            publish()
        stop.set()
        for t in threads:
            t.join(10)
    finally:
        stop.set()
        conf.VERIFY_LOCKS.set(False)
        locks.refresh()
        conf.VERIFY_LOCKSET.set(False)
        lockset.refresh()
        for t in threads:
            t.join(10)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    # the stale-drop path fired at least once across the appends
    assert dispatch.counters().get("result_cache_invalidations", 0) >= 1
