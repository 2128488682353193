"""Elastic worker-host pool: placement, liveness, lost-worker
recovery, blacklisting, and degradation (runtime/hostpool.py + the
scheduler's ``pool=`` placement seam).

Tier-1 (NOT slow-marked): the pooled workers are tiny ``--serve``
subprocesses over a parquet two-stage hash query, so the suite runs in
seconds.  Covers the ROADMAP item-1 done-evidence — a deterministic
2-process exchange smoke over framed shuffle blocks, byte-identical
with the in-process run — plus the worker-kill recovery contract:
``@kill`` SIGKILLs a pooled worker mid-stage, the dead worker's
committed map outputs partially re-run on survivors
(``map_tasks_rerun`` strictly less than ``n_tasks``), repeat offenders
blacklist, and a fully-collapsed pool degrades to in-process execution
instead of failing the query.
"""

import os

import numpy as np
import pytest

from blaze_tpu import conf
from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
from blaze_tpu.ops import MemoryScanExec, ParquetScanExec, ParquetSinkExec
from blaze_tpu.parallel.shuffle import LocalShuffleManager
from blaze_tpu.runtime import dispatch, faults, ledger
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.runtime.hostpool import (
    HostPool, WorkerLostError, WorkerTaskError, WorkerTaskFatalError,
)
from blaze_tpu.runtime.metrics import MetricNode
from blaze_tpu.runtime.retry import FATAL, RETRY, classify
from blaze_tpu.runtime.scheduler import run_stages, split_stages
from blaze_tpu.runtime import worker as worker_mod
from blaze_tpu.schema import DataType, Field, Schema
from blaze_tpu.spark import BlazeSparkSession

import spark_fixtures as F


@pytest.fixture(autouse=True)
def _own_tempdir(tmp_path, monkeypatch):
    """``ledger.leak_audit()`` globs the temp directory for spill files,
    and the test workers share one: a sibling's live spill would read
    as this test's leak.  Each test here gets its own (pooled workers
    inherit ``TMPDIR``); the audit itself is as strict as it was."""
    import tempfile

    own = tmp_path / "tmp"
    own.mkdir()
    monkeypatch.setenv("TMPDIR", str(own))
    monkeypatch.setattr(tempfile, "tempdir", None)  # gettempdir() re-reads

SCHEMA = Schema([
    Field("l_quantity", DataType.int64()),
    Field("l_extendedprice", DataType.int64()),
    Field("l_discount", DataType.int64()),
])


@pytest.fixture(autouse=True)
def _clean_state():
    conf.FAULTS_SPEC.set("")
    conf.TASK_RETRY_BACKOFF.set(0.0)
    faults.reset()
    yield
    conf.FAULTS_SPEC.set("")
    conf.TASK_RETRY_BACKOFF.set(0.1)
    conf.HOST_BLACKLIST_MAX_FAILURES.set(2)
    faults.reset()


def _write_parquet_inputs(tmp_path, n_files=3, rows=120):
    rng = np.random.RandomState(7)
    files, data = [], {"l_quantity": [], "l_extendedprice": [],
                       "l_discount": []}
    for i in range(n_files):
        d = {
            "l_quantity": [int(v) for v in rng.randint(1, 50, rows)],
            "l_extendedprice": [int(v) for v in rng.randint(100, 10000, rows)],
            "l_discount": [int(v) for v in rng.randint(0, 10, rows)],
        }
        for k in data:
            data[k].extend(d[k])
        src = MemoryScanExec([[batch_from_pydict(d, SCHEMA)]], SCHEMA)
        path = str(tmp_path / f"lineitem_{i}.parquet")
        sink = ParquetSinkExec(src, path)
        for _ in sink.execute(0, TaskContext(0, 1)):
            pass
        files.append(sink.written_files[0] if sink.written_files else path)
    return files, data


def _two_stage_plan(files):
    """scan -> filter -> project -> partial agg -> exchange -> final
    agg: one map task per parquet file, a real framed-block shuffle in
    the middle — the plan ships to pooled workers (no driver-process
    resources)."""
    scan = ParquetScanExec([[f] for f in files], SCHEMA)
    sess = BlazeSparkSession()
    sess.register_table("lineitem", scan)
    s = F.scan("lineitem", [F.attr("l_quantity", 1),
                            F.attr("l_extendedprice", 2),
                            F.attr("l_discount", 3)])
    f = F.filter_(
        F.binop("And",
                F.binop("LessThan", F.attr("l_quantity", 1), F.lit(24, "long")),
                F.binop("GreaterThanOrEqual", F.attr("l_discount", 3),
                        F.lit(5, "long"))),
        s,
    )
    pr = F.project(
        [F.alias(F.binop("Multiply", F.attr("l_extendedprice", 2),
                         F.attr("l_discount", 3)), "rev", 10)],
        f,
    )
    partial = F.hash_agg([], [F.agg_expr(F.sum_(F.attr("rev", 10)),
                                         "Partial", 20)], pr)
    ex = F.shuffle(F.single_partition(), partial)
    final = F.hash_agg(
        [], [F.agg_expr(F.sum_(F.attr("rev", 10)), "Final", 20)], ex,
        result=[F.alias(F.attr("s", 20), "revenue", 21)],
    )
    return sess, F.flatten(final)


def _run(sess, plan_json, root, pool=None, metrics=None):
    plan = sess.plan(plan_json)
    stages, manager = split_stages(plan, LocalShuffleManager(str(root)))
    rows = []
    for b in run_stages(stages, manager, metrics=metrics, pool=pool):
        d = batch_to_pydict(b)
        rows.extend(zip(*[d[k] for k in sorted(d)]))
    return sorted(rows)


# ------------------------------------------------- faults grammar

def test_kill_modifier_parse_format_roundtrip():
    rules = faults.parse_spec("worker.task@3@kill,shuffle.fetch@1@a0@kill")
    assert faults.format_spec(rules) == \
        "worker.task@3@kill,shuffle.fetch@1@a0@kill"


def test_worker_task_site_registered():
    assert "worker.task" in faults.SITES


# ------------------------------------------------- typed errors

def test_hostpool_error_dispositions():
    assert classify(WorkerLostError("w0", "sigkill")) == RETRY
    assert classify(WorkerTaskError("ValueError", "boom")) == RETRY
    assert classify(WorkerTaskFatalError("AssertionError", "bug")) == FATAL


def test_worker_lost_error_carries_sorted_lost_outputs():
    e = WorkerLostError("w1", "exit status 1",
                        lost_outputs={3: [2, 0], 1: []})
    assert e.lost_outputs == {3: [0, 2]}
    assert "w1" in str(e) and "exit status 1" in str(e)


# ------------------------------------------------- exchange smoke

def test_two_process_exchange_byte_identical(tmp_path):
    """ROADMAP item 1 done-evidence: TWO pooled worker processes run
    the map stage, exchanging through framed shuffle blocks in the
    shared root; the reduce side sees byte-identical results vs the
    in-process run, and every map output is pool-committed."""
    files, data = _write_parquet_inputs(tmp_path)
    sess, plan_json = _two_stage_plan(files)
    expected = _run(sess, plan_json, tmp_path / "shuffle_local")

    m = MetricNode()
    with HostPool(2) as pool:
        got = _run(sess, plan_json, tmp_path / "shuffle_pool",
                   pool=pool, metrics=m)
        # the map stage genuinely ran ON the pool: all 3 map outputs
        # are owned by pooled workers, none fell back to local
        assert pool.owned_map_outputs() == 3
        assert pool.blacklisted() == []
        assert not pool.degraded()
    assert got == expected
    assert m.metrics.get("worker_lost") in (None, 0)
    assert ledger.leak_audit() == []


def test_memory_scan_plans_fall_back_to_local(tmp_path):
    """A memory-scan plan serializes driver-process resources a pooled
    worker can never read: placement must fall back to in-process
    execution, byte-identical, with zero driver-side resource leaks."""
    d = {"l_quantity": [1, 30], "l_extendedprice": [10, 20],
         "l_discount": [7, 8]}
    scan = MemoryScanExec([[batch_from_pydict(d, SCHEMA)]], SCHEMA)
    sess = BlazeSparkSession()
    sess.register_table("lineitem", scan)
    s = F.scan("lineitem", [F.attr("l_quantity", 1),
                            F.attr("l_extendedprice", 2),
                            F.attr("l_discount", 3)])
    partial = F.hash_agg([], [F.agg_expr(F.sum_(F.attr("l_extendedprice", 2)),
                                         "Partial", 20)], s)
    ex = F.shuffle(F.single_partition(), partial)
    final = F.hash_agg(
        [], [F.agg_expr(F.sum_(F.attr("l_extendedprice", 2)), "Final", 20)],
        ex, result=[F.alias(F.attr("s", 20), "total", 21)],
    )
    plan_json = F.flatten(final)
    expected = _run(sess, plan_json, tmp_path / "a")
    with HostPool(1) as pool:
        got = _run(sess, plan_json, tmp_path / "b", pool=pool)
        assert pool.owned_map_outputs() == 0  # everything ran local
    assert got == expected
    assert ledger.leak_audit() == []


# ------------------------------------------------- lost-worker recovery

def test_worker_kill_partial_rerun_and_blacklist(tmp_path):
    """SIGKILL a pooled worker as it starts its SECOND job: its FIRST
    job's committed map output is invalidated and re-run via the
    partial-rerun path (map_tasks_rerun < n_tasks), the slot
    blacklists at maxFailures=1, total collapse degrades to local, and
    the result stays byte-identical."""
    files, data = _write_parquet_inputs(tmp_path)
    sess, plan_json = _two_stage_plan(files)
    expected = _run(sess, plan_json, tmp_path / "shuffle_base")

    conf.HOST_BLACKLIST_MAX_FAILURES.set(1)
    kills_before = dispatch.counters().get("workers_blacklisted", 0)
    m = MetricNode()
    # per-process schedule: a map job probes worker.task once at job
    # start (the writer plan yields no batches), so hit 1 (first job)
    # passes and hit 2 (second job's start) SIGKILLs — each worker
    # dies exactly when it already owns one committed map output
    with HostPool(2, env={"BLAZE_FAULTS_SPEC": "worker.task@2@kill"}) as pool:
        got = _run(sess, plan_json, tmp_path / "shuffle_kill",
                   pool=pool, metrics=m)
        assert pool.blacklisted() == ["w0", "w1"]
        assert pool.degraded()
    assert got == expected
    sched = m.metrics
    assert sched.get("worker_lost") == 2
    # partial, not full: each death lost exactly ONE committed map
    # output, and each regeneration re-ran exactly that one task —
    # strictly fewer than the stage's 3 tasks
    reruns = sched.get("map_stage_reruns")
    assert reruns == 2
    assert sched.get("map_tasks_rerun") == reruns
    assert dispatch.counters().get("workers_blacklisted", 0) \
        - kills_before == 2
    assert ledger.leak_audit() == []


# ------------------------------------------------- cancel reaches the pool

def test_cancel_kills_inflight_pooled_worker():
    """cancel_query must reach a job IN FLIGHT on a pooled worker: the
    wait loop's cancel checkpoint kills the bound worker's process
    group (it cannot see the driver's scope event), accounts the kill
    (``worker_kills``), raises the typed cancel error — and charges the
    slot NO blacklist failure."""
    from blaze_tpu.runtime.context import QueryCancelledError, cancel_scope

    kills_before = dispatch.counters().get("worker_kills", 0)
    # the worker stalls 5s at job start, so it can neither reply nor
    # die before the driver's 50ms cancel checkpoint fires
    with HostPool(1, env={"BLAZE_FAULTS_SPEC":
                          "worker.task@1@slow5000"}) as pool:
        with cancel_scope("q_pool_cancel") as scope:
            scope.cancel()
            with pytest.raises(QueryCancelledError):
                pool.run_task({"partition": 0, "attempt": 0}, "w0")
        assert pool.blacklisted() == []
        assert pool.lost_counts() == {}
    assert dispatch.counters().get("worker_kills", 0) - kills_before == 1
    assert ledger.leak_audit() == []


# ------------------------------------------------- run_worker_with_retry

class _FakeProc:
    """Stands in for the worker subprocess: writes a typed exit record
    next to the spec (like a cleanly-failing worker) and exits with
    the given status."""

    def __init__(self, record, returncode, spec_path):
        self.returncode = returncode
        self.pid = os.getpid()
        if record is not None:
            import json as _json

            with open(worker_mod.exit_record_path(spec_path), "w") as f:
                _json.dump(record, f)

    def communicate(self, timeout=None):
        return b"", b"synthetic failure"


def _patch_popen(monkeypatch, script):
    """``script`` = list of (exit_record | None, returncode) per spawn;
    returns the call-count list."""
    calls = []

    def fake_popen(cmd, **kwargs):
        spec_path = cmd[-1]
        record, rc = script[min(len(calls), len(script) - 1)]
        calls.append(spec_path)
        return _FakeProc(record, rc, spec_path)

    import subprocess as _sp

    monkeypatch.setattr(_sp, "Popen", fake_popen)
    return calls


def test_fatal_classified_worker_exit_does_not_respawn(tmp_path, monkeypatch):
    """The FATAL-respawn fix: a worker whose typed exit record says
    FATAL (here a QueryCancelledError serialized back from the worker)
    raises the REAL typed error after ONE spawn instead of burning the
    retry budget resurrecting a cancelled query."""
    from blaze_tpu.runtime.context import QueryCancelledError

    calls = _patch_popen(monkeypatch, [
        ({"error_type": "QueryCancelledError", "disposition": "fatal",
          "message": "query q7 cancelled", "query_id": "q7",
          "reason": "cancel"}, 1),
    ])
    with pytest.raises(QueryCancelledError) as ei:
        worker_mod.run_worker_with_retry(
            {"partition": 0}, str(tmp_path), "t0", max_attempts=4)
    assert ei.value.query_id == "q7"
    assert len(calls) == 1


def test_fatal_exit_record_raises_typed_wrapper(tmp_path, monkeypatch):
    calls = _patch_popen(monkeypatch, [
        ({"error_type": "AssertionError", "disposition": "fatal",
          "message": "invariant broke"}, 1),
    ])
    with pytest.raises(WorkerTaskFatalError, match="AssertionError"):
        worker_mod.run_worker_with_retry(
            {"partition": 0}, str(tmp_path), "t1", max_attempts=4)
    assert len(calls) == 1


def test_retry_classified_worker_exit_respawns(tmp_path, monkeypatch):
    """A RETRY-classified exit keeps the old behavior: fresh spawn
    with a fresh attempt id, success on the second."""
    calls = _patch_popen(monkeypatch, [
        ({"error_type": "InjectedFault", "disposition": "retry",
          "message": "seeded crash"}, 1),
        (None, 0),
    ])
    attempt = worker_mod.run_worker_with_retry(
        {"partition": 0}, str(tmp_path), "t2", max_attempts=4)
    assert attempt == 1
    assert len(calls) == 2


def test_exit_record_roundtrip(tmp_path):
    spec_path = str(tmp_path / "spec.json")
    try:
        raise ValueError("bad input")
    except ValueError as e:
        worker_mod._write_exit_record(spec_path, e)
    rec = worker_mod.read_exit_record(spec_path)
    assert rec["error_type"] == "ValueError"
    assert rec["disposition"] == RETRY
    assert "bad input" in rec["message"]
    assert worker_mod.read_exit_record(str(tmp_path / "missing.json")) is None
