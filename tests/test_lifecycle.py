"""Query lifecycle robustness (ISSUE 9): end-to-end cancellation,
deadlines, and graceful degradation under memory pressure.

1. **CancelScope units**: first-cancel-wins, fan-out into attached
   attempt events, deadline expiry raising the typed error with the
   stage/task frontier, registry lookup via ``cancel_query``.
2. **OOM ladder**: the ``@oom`` faults grammar, RESOURCE_EXHAUSTED
   classification, batch splitting, the FusedStageExec rungs
   (downshift -> eager -> DeviceOomError) each byte-identical to the
   undisturbed run, the tier-5 fused-write fallback, and an injected
   mid-query OOM absorbed end-to-end through the scheduler.
3. **Cancellation end-to-end**: an external ``cancel_query`` against a
   live scheduler run returns QueryCancelledError promptly, the
   registry shows the terminal status, the event log pairs
   ``query_cancel_requested`` with ``query_cancelled``, and nothing
   leaks — no attempt thread, no ``.inprogress`` shuffle temp, no
   ``blaze_spill_*`` file (the cancellation resource leak, fixed).
4. **Interleaving** (test_guarded.py style): a query cancel racing the
   winner attempt's shuffle commit — the commit is all-or-nothing,
   never a partial file.
5. **Surfacing**: /queries//metrics/--watch terminal statuses and
   degradation counters, with the finished-query gauge rule intact.
"""

import glob
import json
import os
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from blaze_tpu import conf
from blaze_tpu.runtime import (dispatch, errors, faults, ledger, monitor,
                               oom, trace)
from blaze_tpu.runtime.context import (
    CancelScope, QueryCancelledError, QueryDeadlineError, cancel_query,
    cancel_scope, current_cancel_scope,
)
from blaze_tpu.runtime.retry import FATAL, RETRY, classify
from blaze_tpu.runtime.scheduler import run_stages, split_stages

import spark_fixtures as F  # noqa: E402
from test_spark_convert import make_session, q6_like_plan  # noqa: E402


# the one leak oracle (runtime/ledger.py) — the hand-rolled sweep this
# suite used to carry moved there (ISSUE 15 consolidation)
_attempt_threads = ledger.attempt_threads


@pytest.fixture(autouse=True)
def _clean_lifecycle():
    """Every scenario starts with no faults, no deadline, the default
    ladder depth, and leaves nothing armed, registered, or running.
    The whole suite runs with the error-escape recorder AND the
    per-query resource ledger armed (spark.blaze.verify.errors): a
    FATAL-class error absorbed at an audited broad-except site, or a
    spill/temp/registration/lease still live at query end, fails the
    test that caused it."""
    conf.FAULTS_SPEC.set("")
    conf.TASK_RETRY_BACKOFF.set(0.0)
    conf.QUERY_TIMEOUT_MS.set(0)
    faults.reset()
    conf.VERIFY_ERRORS.set(True)
    errors.refresh()
    ledger.refresh()
    yield
    escaped = errors.escapes()
    leaked = ledger.leaks()
    conf.VERIFY_ERRORS.set(False)
    errors.refresh()
    ledger.refresh()
    assert escaped == [], (
        "FATAL-class error absorbed at an audited site: "
        + "; ".join(escaped))
    assert leaked == [], "resource-ledger leaks: " + "; ".join(leaked)
    conf.FAULTS_SPEC.set("")
    conf.TASK_RETRY_BACKOFF.set(0.1)
    conf.QUERY_TIMEOUT_MS.set(0)
    conf.OOM_MAX_DOWNSHIFTS.set(2)
    conf.TRACE_ENABLE.set(False)
    conf.EVENT_LOG_DIR.set("")
    conf.MONITOR_ENABLE.set(False)
    conf.MONITOR_HEARTBEAT_MS.set(1000)
    faults.reset()
    trace.reset()
    monitor.reset()
    deadline = time.monotonic() + 10
    while _attempt_threads() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert _attempt_threads() == [], "leaked attempt threads"


def _scheduler_rows(sess, plan_json):
    plan = sess.plan(plan_json)
    stages, manager = split_stages(plan)
    out = []
    for b in run_stages(stages, manager):
        out.append(b)
    return out, manager


# ------------------------------------------------- 1. CancelScope units

def test_cancel_scope_first_cancel_wins_and_fans_out():
    scope = CancelScope("q0")
    attached = threading.Event()
    scope.attach(attached)
    assert scope.cancel("cancel") is True
    assert scope.cancel("deadline") is False  # idempotent, reason kept
    assert scope.reason == "cancel" and scope.cancelled
    assert attached.is_set()
    # attaching to an already-cancelled scope fires immediately
    late = threading.Event()
    scope.attach(late)
    assert late.is_set()
    with pytest.raises(QueryCancelledError) as ei:
        scope.check(3, 1)
    assert ei.value.stage_id == 3 and ei.value.task == 1
    assert ei.value.query_id == "q0"


def test_cancel_scope_deadline_raises_typed_with_frontier():
    scope = CancelScope("qd", timeout_ms=1)
    time.sleep(0.01)
    with pytest.raises(QueryDeadlineError) as ei:
        scope.check(2, 0)
    assert ei.value.reason == "deadline"
    assert ei.value.timeout_ms == 1
    assert ei.value.stage_id == 2 and ei.value.task == 0
    # a deadline IS a cancel: one except clause catches both
    assert isinstance(ei.value, QueryCancelledError)


def test_cancel_query_reaches_registered_scope_only():
    assert cancel_query("nope") is False
    with cancel_scope("q_reg", timeout_ms=0) as scope:
        assert current_cancel_scope() is scope
        assert cancel_query("q_reg") is True
        assert scope.cancelled
        assert cancel_query("q_reg") is True  # idempotent
    assert cancel_query("q_reg") is False  # unregistered on exit


def test_classification_cancel_fatal_oom_retryable():
    assert classify(QueryCancelledError("q")) == FATAL
    assert classify(QueryDeadlineError("q", 5)) == FATAL
    assert classify(oom.DeviceOomError("fused_stage")) == RETRY


# ------------------------------------------------ 2. OOM ladder pieces

def test_oom_faults_grammar():
    rules = faults.parse_spec("kernel.dispatch@3@oom,task.compute@1@a0")
    assert rules[0] == ("kernel.dispatch", 3, None, None, True)
    assert rules[1] == ("task.compute", 1, 0, None, False)
    assert faults.format_spec(rules) == \
        "kernel.dispatch@3@oom,task.compute@1@a0"
    with pytest.raises(ValueError):
        faults.parse_spec("task.compute@1@oom@slow100")  # exclusive
    with pytest.raises(ValueError):
        faults.parse_spec("task.compute@1@oom@oom")
    spec = faults.random_spec(11, n_faults=0, n_ooms=2)
    assert spec.count("@oom") == 2 and "kernel.dispatch@" in spec


def test_injected_oom_is_resource_exhausted():
    exc = faults.InjectedOom("kernel.dispatch", 1)
    assert oom.is_resource_exhausted(exc)
    assert oom.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating ..."))
    assert not oom.is_resource_exhausted(RuntimeError("boom"))
    assert not oom.is_resource_exhausted(MemoryError())  # host OOM: FATAL


def test_split_batch_halves_preserve_rows():
    from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("x", DataType.int64())])
    b = batch_from_pydict({"x": list(range(11))}, schema)
    pieces = oom.split_batch(b)
    assert [p.num_rows for p in pieces] == [5, 6]
    got = [v for p in pieces for v in batch_to_pydict(p)["x"]]
    assert got == list(range(11))
    one = batch_from_pydict({"x": [7]}, schema)
    assert oom.split_batch(one) == [one]


def _fused_chain_plan(n_rows=600, parts=2, batches=1):
    """scan -> filter -> project collapsed into one FusedStageExec;
    ``batches`` batches of ``n_rows // parts`` rows a partition."""
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.ir import Alias, BinOp, Lit
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.ops.filter import FilterExec
    from blaze_tpu.ops.fusion import FusedStageExec, fuse_traceable_chains
    from blaze_tpu.ops.project import ProjectExec
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("x", DataType.int64()),
                     Field("y", DataType.int64())])
    rng = np.random.RandomState(3)
    per = n_rows // parts
    scan = MemoryScanExec([
        [batch_from_pydict(
            {"x": [int(v) for v in rng.randint(0, 100, per)],
             "y": [int(v) for v in rng.randint(0, 100, per)]}, schema)
         for _ in range(batches)]
        for _ in range(parts)
    ], schema)
    f = FilterExec(scan, BinOp(">", col("x"), Lit(20, DataType.int64())))
    p = ProjectExec(f, [col("x"),
                        Alias(BinOp("+", col("y"), Lit(1, DataType.int64())),
                              "y1")], ["x", "y1"])
    plan = fuse_traceable_chains(p)
    assert isinstance(plan, FusedStageExec)
    return plan


def _drive(plan):
    from blaze_tpu.batch import batch_to_pydict
    from blaze_tpu.runtime.context import TaskContext

    rows = {"x": [], "y1": []}
    for part in range(plan.num_partitions()):
        for b in plan.execute(part, TaskContext(part, plan.num_partitions())):
            d = batch_to_pydict(b)
            for k in rows:
                rows[k].extend(d[k])
    return rows


def _flaky_kernel(plan, fail_calls):
    """Replace the fused program with one that raises
    RESOURCE_EXHAUSTED on the given 1-based call numbers."""
    real = plan._kernel
    calls = {"n": 0}

    def flaky(cols, num_rows):
        calls["n"] += 1
        if calls["n"] in fail_calls:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected test OOM")
        return real(cols, num_rows)

    plan._kernel = flaky
    return calls


def test_fused_stage_downshift_identical():
    baseline = _drive(_fused_chain_plan())
    plan = _fused_chain_plan()
    _flaky_kernel(plan, {1})  # first batch OOMs once -> split in half
    with dispatch.capture() as cap:
        got = _drive(plan)
    assert got == baseline
    assert cap.get("batch_downshifts") == 1
    assert not cap.get("eager_fallbacks")


def test_fused_stage_eager_fallback_identical():
    baseline = _drive(_fused_chain_plan())
    conf.OOM_MAX_DOWNSHIFTS.set(0)  # rung 2 disabled -> straight to eager
    plan = _fused_chain_plan()
    _flaky_kernel(plan, {1})
    with dispatch.capture() as cap:
        got = _drive(plan)
    assert got == baseline
    assert cap.get("eager_fallbacks") == 1
    assert not cap.get("batch_downshifts")


def test_fused_stage_ladder_exhausted_raises_device_oom():
    conf.OOM_MAX_DOWNSHIFTS.set(0)
    plan = _fused_chain_plan()
    _flaky_kernel(plan, set(range(1, 100)))

    def eager_boom(batch):
        raise RuntimeError("RESOURCE_EXHAUSTED: still too big")

    plan._eager_run = eager_boom
    with pytest.raises(oom.DeviceOomError):
        _drive(plan)


def test_fused_stage_non_oom_errors_propagate_unladdered():
    plan = _fused_chain_plan()
    real = plan._kernel
    plan._kernel = lambda cols, n: (_ for _ in ()).throw(
        ValueError("not an OOM"))
    with pytest.raises(ValueError):
        _drive(plan)
    plan._kernel = real


def test_fused_write_oom_falls_back_byte_identical(tmp_path):
    """Tier-5 fused shuffle write: an OOM mid-stream decomposes to the
    per-kernel path (absorbed chain transforms still applied) and the
    committed .data/.index files are byte-identical to the fused
    run's."""
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.fusion import optimize_plan
    from blaze_tpu.parallel.shuffle import HashPartitioning, ShuffleWriterExec
    from blaze_tpu.runtime.context import TaskContext

    def write(tag, sabotage):
        plan = _fused_chain_plan()
        data = str(tmp_path / f"{tag}.data")
        index = str(tmp_path / f"{tag}.index")
        w = optimize_plan(ShuffleWriterExec(
            plan, HashPartitioning([col("x")], 4), data, index))
        assert w._fused_write is not None and w._fused_fns
        if sabotage:
            real = w._fused_write
            state = {"n": 0}

            def flaky(*a):
                state["n"] += 1
                if state["n"] == 1:
                    raise RuntimeError("RESOURCE_EXHAUSTED: injected")
                return real(*a)

            w._fused_write = flaky
        list(w.execute(0, TaskContext(0, 1)))
        return open(data, "rb").read(), open(index, "rb").read()

    clean = write("clean", sabotage=False)
    with dispatch.capture() as cap:
        degraded = write("degraded", sabotage=True)
    assert degraded == clean
    assert cap.get("eager_fallbacks") == 1


def _partitioning(kind, schema, n_out=3):
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.sort import SortField
    from blaze_tpu.parallel.exchange import _build_range_kernels
    from blaze_tpu.parallel.shuffle import (
        HashPartitioning, RangePartitioning, RoundRobinPartitioning)

    if kind == "hash":
        return HashPartitioning([col("x")], n_out)
    if kind == "round-robin":
        return RoundRobinPartitioning(n_out)
    part = RangePartitioning([SortField(col("x"))], n_out)
    # x is uniform on [0, 100): the cut points' own key words are the
    # boundaries (any consistent set is; the driver's pass samples them)
    cuts = batch_from_pydict({"x": [33, 66], "y1": [0, 0]}, schema)
    key_words, _, _ = _build_range_kernels(schema, part.fields, n_out)
    part.boundaries = tuple(
        np.asarray(w)[:n_out - 1]
        for w in key_words(tuple(cuts.columns), cuts.num_rows))
    return part


@pytest.mark.parametrize("ooms,recoveries,eager", [
    (1, 1, 0),  # spill, retry the same program, stay fused
    (2, 1, 1),  # the retry fails too: per-kernel path for the rest
], ids=["once", "twice"])
@pytest.mark.parametrize("kind", ["hash", "round-robin", "range"])
def test_fused_shuffle_write_oom_ladder_byte_identical(tmp_path, kind, ooms,
                                                       recoveries, eager):
    """The fused write's own ladder, by an injected ``@oom`` at the
    SECOND batch's launch (the round-robin offset has advanced on the
    device by then): one exhaustion spills and re-runs the program;
    a second one decomposes to the per-kernel path for the rest of
    the stream, resyncing the round-robin offset to the host.  Either
    way the committed bytes and index are the unfused writer's."""
    from blaze_tpu.ops.fusion import optimize_plan
    from blaze_tpu.parallel.shuffle import ShuffleWriterExec
    from blaze_tpu.runtime.context import TaskContext

    def write(tag):
        data = str(tmp_path / f"{tag}.data")
        index = str(tmp_path / f"{tag}.index")
        plan = plans.pop()
        w = optimize_plan(ShuffleWriterExec(
            plan, _partitioning(kind, plan.schema), data, index))
        list(w.execute(0, TaskContext(0, 1)))
        with open(data, "rb") as f, open(index, "rb") as g:
            return f.read(), g.read(), w

    # the chain fuses while fusion is on; only the WRITER runs unfused
    plans = [_fused_chain_plan(n_rows=300, parts=1, batches=4)
             for _ in range(2)]
    conf.FUSION_ENABLE.set(False)
    try:
        *unfused, wu = write("unfused")
    finally:
        conf.FUSION_ENABLE.set(True)
    assert wu._fused_write is None
    # the stream's only launches are the fused writes, one a batch:
    # hit 2 is batch 2's launch and hit 3 its retry after the spill
    conf.FAULTS_SPEC.set(",".join(
        f"kernel.dispatch@{2 + i}@oom" for i in range(ooms)))
    faults.reset()
    with dispatch.capture() as cap:
        *degraded, w = write("degraded")
    assert w._fused_write is not None and w._fused_fns
    assert degraded == unfused
    assert cap.get("oom_recoveries", 0) == recoveries, cap
    assert cap.get("eager_fallbacks", 0) == eager, cap
    assert not cap.get("batch_downshifts"), cap


def test_device_oom_error_not_reabsorbed_as_resource_exhausted():
    """The OOM ladder's TERMINAL verdict must not re-enter the ladder:
    DeviceOomError classifies non-absorbable even though its message
    embeds the cause's RESOURCE_EXHAUSTED text."""
    err = oom.DeviceOomError(
        "fused_write: RESOURCE_EXHAUSTED: out of memory")
    assert not oom.is_resource_exhausted(err)
    assert oom.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory"))


def test_injected_oom_absorbed_end_to_end():
    """The acceptance shape: a seeded ``kernel.dispatch@N@oom`` on a
    scheduler run resolves via the ladder with byte-identical results,
    and the event log pairs the ``kind=oom`` fault with its
    ``oom_recovery``."""
    from blaze_tpu.runtime import trace_report

    sess, _ = make_session()
    baseline, _ = _scheduler_rows(sess, F.flatten(q6_like_plan()))
    base_rows = [b.num_rows for b in baseline]

    conf.TRACE_ENABLE.set(True)
    trace.reset()
    conf.FAULTS_SPEC.set("kernel.dispatch@2@oom")
    faults.reset()
    try:
        with dispatch.capture() as cap:
            with monitor.query_span("oom_e2e", mode="scheduler") as log:
                got, _ = _scheduler_rows(sess, F.flatten(q6_like_plan()))
    finally:
        conf.FAULTS_SPEC.set("")
        faults.reset()
        conf.TRACE_ENABLE.set(False)
        trace.reset()
    assert [b.num_rows for b in got] == base_rows
    from blaze_tpu.batch import batch_to_pydict

    assert [batch_to_pydict(b) for b in got] == \
        [batch_to_pydict(b) for b in baseline]
    assert cap.get("oom_recoveries", 0) >= 1
    events = trace.read_event_log(log)
    oom_faults = [e for e in events if e["type"] == "fault_injected"
                  and e.get("kind") == "oom"]
    assert len(oom_faults) == 1
    rec = trace_report.reconcile_faults(events)
    assert rec["reconciled"], rec["unpaired"]
    assert any(e["type"] == "oom_recovery" and e["action"] == "spill"
               for e in events)


# ------------------------------- 3. resource reclamation (the leak fix)

def test_repartitioner_release_reclaims_spill_files(monkeypatch):
    """The cancellation resource leak: a non-committing attempt's spill
    FILES must be reclaimed at rollback, not at process exit."""
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.parallel import shuffle as shuffle_mod
    from blaze_tpu.runtime.memmgr import FileSpill
    from blaze_tpu.runtime.metrics import MetricsSet
    from blaze_tpu.schema import DataType, Field, Schema

    made = []

    def file_spill(codec=None):
        sp = FileSpill("zlib")
        made.append(sp.path)
        return sp

    monkeypatch.setattr(shuffle_mod, "try_new_spill", file_spill)
    schema = Schema([Field("x", DataType.int64())])
    rep = shuffle_mod.ShuffleRepartitioner(schema, 2, MetricsSet())
    b = batch_from_pydict({"x": list(range(64))}, schema).to_host()
    rep.insert_sorted(b, np.array([32, 32]))
    assert rep.spill() > 0
    assert made and all(os.path.exists(p) for p in made)
    rep.release()
    assert not any(os.path.exists(p) for p in made), "spill files leaked"
    # idempotent — a second release (post-commit path) is a no-op
    rep.release()


def test_writer_releases_spills_on_cancel(monkeypatch, tmp_path):
    """A cancelled map attempt (mid-stream cancel event) exits without
    committing AND without leaking its spill files."""
    from blaze_tpu.exprs import col
    from blaze_tpu.parallel import shuffle as shuffle_mod
    from blaze_tpu.parallel.shuffle import HashPartitioning, ShuffleWriterExec
    from blaze_tpu.runtime.context import TaskContext
    from blaze_tpu.runtime.memmgr import FileSpill

    made = []

    def file_spill(codec=None):
        sp = FileSpill("zlib")
        made.append(sp.path)
        return sp

    monkeypatch.setattr(shuffle_mod, "try_new_spill", file_spill)
    conf.SHUFFLE_ASYNC_WRITE.set(False)
    try:
        plan = _fused_chain_plan()
        data = str(tmp_path / "c.data")
        w = ShuffleWriterExec(plan, HashPartitioning([col("x")], 4),
                              data, str(tmp_path / "c.index"))
        cancel = threading.Event()
        ctx = TaskContext(0, 1, cancel_event=cancel)
        stream = w.execute(0, ctx)
        # drive the side-effect stream with a spill forced mid-flight,
        # then cancel before the commit
        rep_holder = {}
        real_insert = shuffle_mod._insert_host

        def spilling_insert(rep, schema, item):
            rep_holder["rep"] = rep
            real_insert(rep, schema, item)
            rep.spill()
            cancel.set()

        monkeypatch.setattr(shuffle_mod, "_insert_host", spilling_insert)
        list(stream)
        assert made, "test never spilled"
        assert not any(os.path.exists(p) for p in made), "spill files leaked"
        assert not os.path.exists(data), "cancelled attempt committed"
    finally:
        conf.SHUFFLE_ASYNC_WRITE.set(True)


def test_manager_sweep_inprogress_units(tmp_path):
    from blaze_tpu.parallel.shuffle import LocalShuffleManager

    mgr = LocalShuffleManager(str(tmp_path))
    for fn in ("shuffle_0_1.data.inprogress.a2",
               "shuffle_0_1.index.inprogress.a2",
               "shuffle_0_2.data.inprogress.a0",
               "shuffle_1_0.data.inprogress.a1",
               "shuffle_0_1.data"):
        (tmp_path / fn).write_bytes(b"x")
    # exact (shuffle, map, attempt): only that attempt's temps go
    assert mgr.sweep_inprogress(0, 1, 2) == 2
    assert (tmp_path / "shuffle_0_2.data.inprogress.a0").exists()
    assert (tmp_path / "shuffle_0_1.data").exists()  # committed: kept
    # everything in-progress
    assert mgr.sweep_inprogress() == 2
    assert (tmp_path / "shuffle_0_1.data").exists()


# ------------------------------------ 4. cancellation end-to-end + HTTP

def _slow_spec(ms=250):
    return f"task.compute@1@slow{ms},task.compute@3@slow{ms}"


def test_external_cancel_mid_query_reconciles():
    sess, _ = make_session()
    conf.TRACE_ENABLE.set(True)
    trace.reset()
    conf.MONITOR_ENABLE.set(True)
    conf.MONITOR_HEARTBEAT_MS.set(50)
    monitor.reset()
    conf.FAULTS_SPEC.set(_slow_spec())
    faults.reset()
    spills_before = set(glob.glob(ledger.spill_glob()))
    state = {}

    def run():
        try:
            with monitor.query_span("cxl_e2e", mode="scheduler") as lp:
                state["log"] = lp
                plan = sess.plan(F.flatten(q6_like_plan()))
                stages, mgr = split_stages(plan)
                state["root"] = mgr.root
                for b in run_stages(stages, mgr):
                    pass
        except BaseException as e:  # noqa: BLE001
            state["exc"] = e

    t = threading.Thread(target=run)
    t.start()
    for _ in range(400):  # wait until the scope is registered
        if cancel_query("cxl_e2e"):
            break
        time.sleep(0.005)
    t0 = time.monotonic()
    t.join(15)
    latency = time.monotonic() - t0
    assert not t.is_alive()
    assert isinstance(state.get("exc"), QueryCancelledError), state.get("exc")
    # prompt: well inside 2x the slow-fault sleep + heartbeat slack
    assert latency < 2.0, latency
    # registry terminal status
    snap = monitor.snapshot()
    q = next(x for x in snap["queries"] if x["query_id"] == "cxl_e2e")
    assert q["status"] == "cancelled"
    # event pairing
    from blaze_tpu.runtime import trace_report

    events = trace.read_event_log(state["log"])
    cxl = trace_report.reconcile_cancellation(events)
    assert cxl["requested"] == 1 and cxl["cancelled"] == 1
    assert cxl["reconciled"]
    end = next(e for e in events if e["type"] == "query_end")
    assert end["status"] == "cancelled"
    # zero leaks: threads, shuffle temps, spill files, ledger — the
    # one oracle (runtime/ledger.py) the chaos arms share
    assert ledger.leak_audit(shuffle_root=state["root"],
                             spills_before=spills_before) == []


def test_query_deadline_end_to_end():
    sess, _ = make_session()
    conf.QUERY_TIMEOUT_MS.set(120)
    conf.FAULTS_SPEC.set(_slow_spec(300))
    faults.reset()
    conf.MONITOR_ENABLE.set(True)
    monitor.reset()
    with pytest.raises(QueryDeadlineError) as ei:
        with monitor.query_span("ddl_e2e", mode="scheduler"):
            rows, _ = _scheduler_rows(sess, F.flatten(q6_like_plan()))
    assert ei.value.reason == "deadline"
    assert ei.value.stage_id is not None  # frontier recorded
    snap = monitor.snapshot()
    q = next(x for x in snap["queries"] if x["query_id"] == "ddl_e2e")
    assert q["status"] == "deadline_exceeded"


def test_http_cancel_endpoint(tmp_path):
    sess, _ = make_session()
    conf.MONITOR_ENABLE.set(True)
    conf.MONITOR_PORT.set(0)
    conf.MONITOR_HEARTBEAT_MS.set(50)
    monitor.reset()
    srv = monitor.ensure_server()
    try:
        # unknown query: 404, cancelled=false
        req = urllib.request.Request(
            srv.url + "/queries/ghost/cancel", method="POST", data=b"")
        try:
            urllib.request.urlopen(req, timeout=10)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
        conf.FAULTS_SPEC.set(_slow_spec())
        faults.reset()
        state = {}

        def run():
            try:
                with monitor.query_span("http_cxl", mode="scheduler"):
                    _scheduler_rows(sess, F.flatten(q6_like_plan()))
            except BaseException as e:  # noqa: BLE001
                state["exc"] = e

        t = threading.Thread(target=run)
        t.start()
        code = None
        for _ in range(400):
            req = urllib.request.Request(
                srv.url + "/queries/http_cxl/cancel", method="POST",
                data=b"")
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    code = r.status
                    body = json.loads(r.read())
                    break
            except urllib.error.HTTPError:
                time.sleep(0.005)  # scope not registered yet
        t.join(15)
        assert code == 200 and body == {"query_id": "http_cxl",
                                        "cancelled": True}
        assert isinstance(state.get("exc"), QueryCancelledError)
    finally:
        monitor.shutdown_server()
        conf.MONITOR_PORT.set(4048)
        assert monitor.monitor_threads() == []


# ---------------------- 5. cancel vs winner-commit interleaving (S3)

def _commit_barrier_writer(tmp_path, monkeypatch, tag):
    from blaze_tpu.exprs import col
    from blaze_tpu.parallel.shuffle import HashPartitioning, ShuffleWriterExec

    plan = _fused_chain_plan()
    data = str(tmp_path / f"{tag}.data")
    index = str(tmp_path / f"{tag}.index")
    w = ShuffleWriterExec(plan, HashPartitioning([col("x")], 4), data, index)
    return w, data, index


def test_cancel_racing_winner_commit_is_all_or_nothing(tmp_path,
                                                       monkeypatch):
    """S3 interleaving: the cancel lands while the winner attempt is
    INSIDE write_output — past its last cooperative check.  The commit
    must complete fully (both files, readable, complete rows); a
    partial shuffle file must never appear.  Armed lock-order +
    lockset checkers stay quiet."""
    from blaze_tpu.analysis import locks as alocks
    from blaze_tpu.parallel.shuffle import ShuffleRepartitioner
    from blaze_tpu.runtime import lockset
    from blaze_tpu.runtime.context import TaskContext

    alocks.arm(True)
    lockset.arm(True)
    try:
        w, data, index = _commit_barrier_writer(tmp_path, monkeypatch, "win")
        in_commit = threading.Barrier(2, timeout=10)
        cancel_landed = threading.Barrier(2, timeout=10)
        cancel = threading.Event()
        real = ShuffleRepartitioner.write_output

        def gated(self, dp, ip):
            in_commit.wait()      # driver: commit has started
            cancel_landed.wait()  # driver has fired the cancel
            return real(self, dp, ip)

        monkeypatch.setattr(ShuffleRepartitioner, "write_output", gated)
        errs = []

        def winner():
            try:
                list(w.execute(0, TaskContext(0, 1, cancel_event=cancel)))
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        t = threading.Thread(target=winner)
        t.start()
        in_commit.wait()
        cancel.set()              # the query cancel, mid-commit
        cancel_landed.wait()
        t.join(15)
        assert not t.is_alive() and not errs, errs
        # FULL commit: both files present, index consistent, rows whole
        assert os.path.exists(data) and os.path.exists(index)
        assert not any(".inprogress" in f for f in os.listdir(tmp_path))
        import struct

        raw = open(index, "rb").read()
        offsets = struct.unpack(f"<{len(raw) // 8}Q", raw)
        assert offsets[-1] == os.path.getsize(data)
        assert w.partition_lengths is not None
        assert sum(w.partition_lengths) == os.path.getsize(data)
    finally:
        alocks.arm(False)
        lockset.arm(False)


def test_cancel_before_commit_rolls_back_fully(tmp_path, monkeypatch):
    """S3 inverse interleaving: the cancel lands BEFORE the winner's
    commit check — the attempt must publish NOTHING (no data, no
    index, no .inprogress temp)."""
    from blaze_tpu.parallel import shuffle as shuffle_mod
    from blaze_tpu.runtime.context import TaskContext

    w, data, index = _commit_barrier_writer(tmp_path, monkeypatch, "lose")
    cancel = threading.Event()
    real_insert = shuffle_mod._insert_host
    conf.SHUFFLE_ASYNC_WRITE.set(False)
    try:
        def cancelling_insert(rep, schema, item):
            real_insert(rep, schema, item)
            cancel.set()          # lands between batches, pre-commit

        monkeypatch.setattr(shuffle_mod, "_insert_host", cancelling_insert)
        list(w.execute(0, TaskContext(0, 1, cancel_event=cancel)))
    finally:
        conf.SHUFFLE_ASYNC_WRITE.set(True)
    assert not os.path.exists(data) and not os.path.exists(index)
    assert not any(".inprogress" in f for f in os.listdir(tmp_path))
    assert w.partition_lengths is None


def test_cancel_during_result_drain_never_returns_truncated_ok():
    """Regression (review finding): the cooperative operator seams STOP
    yielding on cancel instead of raising, so a cancel landing while
    the final result task drains used to end the stream quietly and
    hand the caller a silently TRUNCATED row set with status ok.  The
    post-loop checkpoint must surface QueryCancelledError instead."""
    sess, _ = make_session(partitions=1)
    plan_json = F.flatten(q6_like_plan())
    # warm every kernel so the map stage is milliseconds
    _scheduler_rows(sess, plan_json)
    # hit 2 = the RESULT task's decode (1 map task + 1 result task):
    # the sleep guarantees the cancel lands before its plan drive,
    # so the cancelled agg yields NOTHING and the loop ends quietly
    conf.FAULTS_SPEC.set("task.compute@2@slow600")
    faults.reset()
    state = {}

    def run():
        try:
            with monitor.query_span("trunc_cxl", mode="scheduler"):
                state["out"] = _scheduler_rows(sess, plan_json)[0]
        except BaseException as e:  # noqa: BLE001
            state["exc"] = e

    t = threading.Thread(target=run)
    t.start()
    for _ in range(400):
        if cancel_query("trunc_cxl"):
            break
        time.sleep(0.005)
    t.join(15)
    assert not t.is_alive()
    # the one unacceptable outcome is a quiet return (truncated "ok")
    assert "out" not in state, "cancelled query returned truncated rows"
    assert isinstance(state.get("exc"), QueryCancelledError), \
        state.get("exc")


def test_cancel_reaches_concurrent_speculative_attempts():
    """A query cancel mid-stage with the concurrent attempt runner live
    (speculation armed) must stop ALL racing attempts: each attempt's
    private cancel event is attached to the scope, the runner's poll
    loop is a checkpoint, and every attempt thread joins — the
    regression for the res_scope/CancelScope shadowing bug where
    concurrent attempts never saw the query cancel."""
    sess, _ = make_session()
    conf.SPECULATION_ENABLE.set(True)
    conf.SPECULATION_WEDGE_MS.set(10_000)  # runner on, wedge quiet
    conf.STAGE_TASK_CONCURRENCY.set(2)
    conf.FAULTS_SPEC.set(_slow_spec(400))
    faults.reset()
    state = {}
    try:
        def run():
            try:
                with monitor.query_span("spec_cxl", mode="scheduler"):
                    _scheduler_rows(sess, F.flatten(q6_like_plan()))
            except BaseException as e:  # noqa: BLE001
                state["exc"] = e

        t = threading.Thread(target=run)
        t.start()
        for _ in range(400):
            if cancel_query("spec_cxl"):
                break
            time.sleep(0.005)
        t.join(15)
        assert not t.is_alive()
        assert isinstance(state.get("exc"), QueryCancelledError), \
            state.get("exc")
    finally:
        conf.SPECULATION_ENABLE.set(False)
        conf.SPECULATION_WEDGE_MS.set(0)
        conf.STAGE_TASK_CONCURRENCY.set(1)
    deadline = time.monotonic() + 10
    while _attempt_threads() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert _attempt_threads() == []


# ----------------------------- 6. surfacing: /metrics, --watch, status

def test_prometheus_terminal_and_degradation_rules():
    """Finished queries keep the PR 5 heartbeat-age rule (no
    forever-climbing gauge) and export their frozen degradation
    counters; terminal statuses surface in /queries and --watch."""
    conf.MONITOR_ENABLE.set(True)
    monitor.reset()
    with monitor.query("prom_q", mode="scheduler"):
        monitor.stage_started(0, "map", 2)
        monitor.stage_progress_update(
            0, rows=10, bytes_=100, batches=1, tasks_done=1,
            counters={"xla_dispatches": 4, "oom_recoveries": 2,
                      "batch_downshifts": 1, "eager_fallbacks": 0})
        monitor.stage_finished(0, "ok",
                               counters={"xla_dispatches": 4,
                                         "oom_recoveries": 2,
                                         "batch_downshifts": 1})
    text = monitor.render_prometheus()
    assert ('blaze_query_stage_oom_recoveries'
            '{query="prom_q",stage="0"} 2') in text
    assert ('blaze_query_stage_batch_downshifts'
            '{query="prom_q",stage="0"} 1') in text
    # zero-valued per-stage series are omitted; finished query exports
    # no heartbeat age (the forever-climbing gauge rule)
    assert "blaze_query_stage_eager_fallbacks" not in text
    assert 'blaze_query_heartbeat_age_seconds{query="prom_q"}' not in text
    snap = monitor.snapshot()
    q = next(x for x in snap["queries"] if x["query_id"] == "prom_q")
    assert q["status"] == "done"
    frame = monitor.render_watch(snap)
    assert "DONE" in frame
    assert "oom 2 spill/1 downshift/0 eager" in frame


def test_watch_surfaces_cancelled_status():
    conf.MONITOR_ENABLE.set(True)
    monitor.reset()
    with pytest.raises(QueryCancelledError):
        with monitor.query("watch_cxl"):
            raise QueryCancelledError("watch_cxl")
    frame = monitor.render_watch(monitor.snapshot())
    assert "CANCELL" in frame.upper()
