"""Query-level tracing + structured event log (tier-1, CPU backend).

1. **Reconciliation** (acceptance): a warm TPC-H q01 run with tracing
   enabled produces a JSONL event log whose per-stage
   ``device_time_ns + dispatch_overhead_ns + compile_ns`` never
   exceeds the measured stage wall (no double counting), and
   reconciles with it within 20% on the stage that carries the
   query's compute (tiny stages are fixed host overhead — proto
   serde, file IO — by construction, not kernel cost).
2. **Report**: ``python -m blaze_tpu --report`` renders the
   plan-annotated profile from that log.
3. **Chaos recovery pairing** (acceptance): a seeded fault spec run
   yields an event log where every injected fault pairs with its
   recovery event (task retry or map-stage rerun).
4. **Overhead gating**: with ``spark.blaze.trace.enabled=false`` the
   dispatch hot path takes the pre-existing code path — no span
   allocation, no kernel-timing callback — asserted structurally.
5. **Schema**: every event type round-trips through the golden JSON
   schema (trace_schema.json); schema drift fails tier-1.
6. **MetricsSet/MetricNode thread safety** (regression): concurrent
   add()/child() from worker threads must not lose updates.
"""

import json
import os
import threading

import jsonschema
import pytest

from blaze_tpu import conf
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.runtime import dispatch, trace, trace_report
from blaze_tpu.runtime.scheduler import run_stages, split_stages
from blaze_tpu.tpch import TPCH_SCHEMAS, build_query
from blaze_tpu.tpch.datagen import generate_all, table_to_batches

SCALE = 0.05
BATCH_ROWS = 65536


@pytest.fixture(scope="module")
def data():
    return generate_all(SCALE)


def _scans(data, n_parts=1, batch_rows=BATCH_ROWS):
    return {
        name: MemoryScanExec(
            table_to_batches(data[name], TPCH_SCHEMAS[name], n_parts,
                             batch_rows=batch_rows),
            TPCH_SCHEMAS[name],
        )
        for name in TPCH_SCHEMAS
    }


def _run_traced(data, q, tmp_path, n_parts=1, runs=2, query_id=None,
                batch_rows=BATCH_ROWS):
    """Run ``q`` through the stage scheduler ``runs`` times with
    tracing armed; returns the LAST run's event list (warm when
    runs >= 2: kernels compiled + persistent caches populated)."""
    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    try:
        for _ in range(runs):
            with trace.query(query_id or f"trace_{q}") as path:
                stages, manager = split_stages(
                    build_query(q, _scans(data, n_parts, batch_rows), n_parts))
                rows = sum(b.num_rows for b in run_stages(stages, manager))
        assert rows > 0 and path is not None
        return trace.read_events(path), path
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        trace.reset()


# --------------------------------------------------- 1. reconciliation

def test_q01_stage_time_reconciles_with_event_log(data, tmp_path):
    events, _ = _run_traced(data, "q1", tmp_path)
    stages = [e for e in events if e["type"] == "stage_complete"]
    assert stages, "no stage_complete events in the log"
    total_wall = sum(e["wall_ns"] for e in stages)
    for e in stages:
        attributed = (e["device_time_ns"] + e["dispatch_overhead_ns"]
                      + e["compile_ns"])
        # the split is measured INSIDE the stage wall: exceeding it by
        # more than clock noise means double counting
        assert attributed <= e["wall_ns"] * 1.2, (
            f"stage {e['stage_id']}: attributed {attributed} > "
            f"1.2x wall {e['wall_ns']}")
    # the stage carrying the query's compute must reconcile two-sided:
    # its wall is kernel-dominated, so the attribution must account
    # for >= 80% of it (the dispatch-floor story is judgeable)
    major = max(stages, key=lambda e: e["wall_ns"])
    assert major["wall_ns"] >= 0.5 * total_wall, (
        "expected one compute-dominant stage in warm q01")
    attributed = (major["device_time_ns"] + major["dispatch_overhead_ns"]
                  + major["compile_ns"])
    assert attributed >= 0.8 * major["wall_ns"], (
        f"dominant stage {major['stage_id']} attributes only "
        f"{attributed / major['wall_ns']:.0%} of its wall "
        f"(device {major['device_time_ns']}, dispatch "
        f"{major['dispatch_overhead_ns']}, compile {major['compile_ns']}, "
        f"wall {major['wall_ns']})")
    assert major["programs"] > 0


def test_trace_covers_lifecycle_and_attribution(data, tmp_path):
    events, _ = _run_traced(data, "q1", tmp_path)
    types = {e["type"] for e in events}
    assert {"query_start", "query_end", "stage_submit", "stage_complete",
            "task_attempt_start", "task_attempt_end", "task_kernels",
            "task_plan", "shuffle_write", "shuffle_fetch"} <= types
    # kernel costs land on operator labels, not one anonymous bucket
    kernels = [e for e in events if e["type"] == "task_kernels"]
    labels = {lbl for e in kernels for lbl in e["kernels"]}
    assert "agg_update" in labels or "agg" in labels
    # the plan-annotated tree carries per-node metrics
    plans = [e for e in events if e["type"] == "task_plan"]
    assert any("AggExec" in json.dumps(e["plan"]) for e in plans)
    assert any(e["plan"]["metrics"] or any(
        c["metrics"] for c in e["plan"]["children"]) for e in plans)


# ----------------------------------------------------------- 2. report

def test_report_cli_renders_profile(data, tmp_path):
    _, path = _run_traced(data, "q1", tmp_path, runs=1)
    import contextlib
    import io

    from blaze_tpu.__main__ import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(["--report", path])
    assert rc == 0
    out = buf.getvalue()
    assert "stage timeline" in out
    assert "dispatch" in out and "device" in out
    assert "plan (stage" in out and "AggExec" in out
    assert "shuffle write" in out


def test_report_cli_missing_log(tmp_path):
    from blaze_tpu.__main__ import main

    assert main(["--report", str(tmp_path / "nope.jsonl")]) == 2


# -------------------------------------------- 3. chaos recovery pairing

def test_chaos_event_log_pairs_faults_with_recovery(data, tmp_path):
    """Acceptance: a seeded fault spec leaves an event log containing
    every injected fault paired with its recovery event — a plain task
    retry for compute/write faults, a map-stage rerun for the fetch
    fault."""
    from blaze_tpu.runtime import faults

    conf.FAULTS_SPEC.set("task.compute@1@a0,shuffle.fetch@2@a0")
    conf.TASK_RETRY_BACKOFF.set(0.0)
    faults.reset()
    try:
        events, _ = _run_traced(data, "q6", tmp_path, n_parts=2, runs=1,
                                query_id="chaos_q6", batch_rows=16384)
    finally:
        conf.FAULTS_SPEC.set("")
        conf.TASK_RETRY_BACKOFF.set(0.1)
        faults.reset()
    injected = [e for e in events if e["type"] == "fault_injected"]
    assert len(injected) == 2, f"expected both faults to fire: {injected}"
    assert {e["site"] for e in injected} == {"task.compute", "shuffle.fetch"}
    rec = trace_report.reconcile_faults(events)
    assert rec["reconciled"], (
        f"unpaired faults: {rec['unpaired']} "
        f"(recoveries seen: {rec['recoveries']})")
    # the fetch fault's recovery must be the map-stage rerun tier
    assert any(e["type"] == "map_stage_rerun" for e in events)
    assert any(e["type"] == "task_retry" for e in events)
    assert any(e["type"] == "fetch_failure" for e in events)


def test_reconcile_flags_unrecovered_fault():
    events = [
        {"ts": 1.0, "type": "fault_injected", "site": "task.compute",
         "hit": 1, "attempt": 0},
        {"ts": 2.0, "type": "task_retry", "stage_id": 0, "task": 0,
         "attempt": 1, "reason": "InjectedFault"},
        {"ts": 3.0, "type": "fault_injected", "site": "shuffle.write",
         "hit": 1, "attempt": 0},
    ]
    rec = trace_report.reconcile_faults(events)
    assert rec["injected"] == 2 and rec["recoveries"] == 1
    assert not rec["reconciled"]
    assert rec["unpaired"][0]["site"] == "shuffle.write"


# ------------------------------------------------- 4. overhead gating

def test_disabled_trace_keeps_pre_existing_dispatch_path(data, monkeypatch):
    """With spark.blaze.trace.enabled=false the per-batch hot path must
    be byte-for-byte the pre-existing one: no kernel-timing callback
    (record_kernel poisoned — a single traced jit call would raise),
    no block_until_ready, no span or event allocation.  Lifecycle
    sites still CALL trace.emit, but the disarmed emit is a bool-check
    no-op: zero events/spans after a full scheduler run."""
    conf.TRACE_ENABLE.set(False)
    trace.reset()
    assert not trace.enabled()
    assert trace._KERNEL_TIMING is False

    def poisoned(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("kernel timing entered with tracing disabled")

    monkeypatch.setattr(trace, "record_kernel", poisoned)
    stages, manager = split_stages(build_query("q6", _scans(data), 1))
    rows = sum(b.num_rows for b in run_stages(stages, manager))
    assert rows > 0
    assert trace.counters() == {"events": 0, "spans": 0}
    assert trace.current_path() is None  # no log file was even named


def test_emit_is_noop_when_disarmed(tmp_path):
    conf.TRACE_ENABLE.set(False)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    trace.emit("query_start", query_id="x")
    assert trace.counters()["events"] == 0
    assert list(tmp_path.iterdir()) == []
    conf.EVENT_LOG_DIR.set("")
    trace.reset()


def test_nested_kernel_captures_keep_identity():
    """Regression: sink removal must be by identity — equal (e.g.
    empty) dicts from nested captures must not evict each other."""
    with trace.kernel_capture() as outer:
        with trace.kernel_capture() as inner:
            pass
        assert trace._KERNEL_TIMING is True
        trace.record_kernel("k", 10, 2, 0)
    assert trace._KERNEL_TIMING is False
    assert outer["k"]["programs"] == 1 and outer["k"]["device_ns"] == 10
    assert inner == {}


def test_nested_dispatch_captures_keep_identity():
    with dispatch.capture() as outer:
        with dispatch.capture() as inner:
            pass
        dispatch.record("xla_dispatches")
    assert outer.get("xla_dispatches") == 1
    assert inner == {}


# ------------------------------------------------------- 5. schema

def _synthetic_events():
    """One representative instance of every event type the runtime can
    emit, produced through the real emit path (round-trip: emit ->
    JSONL -> parse -> validate)."""
    return [
        ("query_start", {"query_id": "q"}),
        ("query_end", {"query_id": "q", "status": "ok", "wall_ns": 5}),
        ("stage_submit", {"stage_id": 0, "kind": "map", "n_tasks": 2,
                          "shuffle_id": 0}),
        ("stage_complete", {"stage_id": 0, "kind": "map", "n_tasks": 2,
                            "shuffle_id": None, "status": "ok",
                            "wall_ns": 9, "programs": 1,
                            "device_time_ns": 4, "dispatch_overhead_ns": 2,
                            "compile_ns": 0,
                            "kernels": {"agg": {"programs": 1,
                                                "device_ns": 4,
                                                "dispatch_ns": 2,
                                                "compile_ns": 0}},
                            "counters": {"xla_dispatches": 1}}),
        ("task_attempt_start", {"stage_id": 0, "task": 0, "attempt": 0}),
        ("task_attempt_end", {"stage_id": 0, "task": 0, "attempt": 0,
                              "status": "failed", "error": "boom"}),
        ("task_retry", {"stage_id": 0, "task": 0, "attempt": 1,
                        "reason": "InjectedFault"}),
        ("task_timeout", {"stage_id": 0, "task": 0, "attempt": 0}),
        ("fetch_failure", {"stage_id": 1, "task": 0, "shuffle_id": 0}),
        ("map_stage_rerun", {"stage_id": 0, "shuffle_id": 0,
                             "map_ids": [1]}),
        ("speculative_attempt_start", {"stage_id": 0, "task": 1,
                                       "attempt": 100, "reason": "slow"}),
        ("speculative_attempt_won", {"stage_id": 0, "task": 1,
                                     "attempt": 100}),
        ("speculative_attempt_lost", {"stage_id": 0, "task": 2,
                                      "attempt": 101}),
        ("task_kernels", {"task_id": "task_0_0", "stage_id": 0,
                          "partition": 0, "attempt": 0, "wall_ns": 9,
                          "programs": 1, "device_time_ns": 4,
                          "dispatch_overhead_ns": 2, "compile_ns": 0,
                          "kernels": {"filter": {"programs": 1,
                                                 "device_ns": 4,
                                                 "dispatch_ns": 2,
                                                 "compile_ns": 0}}}),
        ("task_plan", {"task_id": "task_0_0", "stage_id": 0,
                       "partition": 0, "attempt": 0,
                       "plan": {"op": "FilterExec",
                                "metrics": {"output_rows": 3},
                                "children": [{"op": "MemoryScanExec",
                                              "metrics": {},
                                              "children": []}]}}),
        ("stage_progress", {"stage_id": 0, "kind": "map", "rows": 100,
                            "bytes": 4096, "batches": 2, "tasks_done": 1,
                            "n_tasks": 2, "elapsed_ns": 7,
                            "counters": {"xla_dispatches": 3},
                            "attempts": {"task_attempts": 1}}),
        ("task_heartbeat", {"task_id": "task_0_0", "stage_id": 0,
                            "partition": 0, "attempt": 0, "rows": 10,
                            "batches": 1, "elapsed_ns": 5,
                            "progress_rows": 10,
                            "metrics": {"output_rows": 10}}),
        ("query_cancel_requested", {"query_id": "q", "reason": "cancel"}),
        ("query_cancelled", {"query_id": "q", "reason": "deadline",
                             "stage_id": 1, "task": 0}),
        ("oom_recovery", {"label": "fused_stage", "action": "downshift",
                          "rows": 4096, "depth": 1}),
        ("fault_injected", {"site": "shuffle.fetch", "hit": 2,
                            "attempt": 0, "detail": "shuffle_0"}),
        ("straggler_injected", {"site": "shuffle.write", "hit": 1,
                                "attempt": 0, "slow_ms": 400,
                                "detail": "/tmp/x.data"}),
        ("worker_lost", {"worker": "w0", "reason": "killed by signal 9",
                         "stage_id": 0, "task": 2, "lost_maps": 1}),
        ("worker_blacklisted", {"worker": "w0", "failures": 2,
                                "reason": "heartbeat silent for 1200ms"}),
        ("pool_degraded", {"reason": "all workers dead or blacklisted",
                           "stage_id": 0, "task": 2}),
        ("block_corruption", {"site": "shuffle.fetch",
                              "resource": "shuffle_0",
                              "path": "/tmp/shuffle_0_1.data",
                              "detail": "crc32 mismatch",
                              "quarantined": True}),
        ("disk_pressure", {"action": "retry", "site": "shuffle.write",
                           "detail": "/tmp/shuffle_0_1.data"}),
        ("mem_watermark", {"used": 1024, "total": 4096}),
        ("spill", {"consumer": "shuffle", "bytes": 512}),
        ("shuffle_write", {"bytes": 100, "blocks": 2, "attempt": 0,
                           "path": "/tmp/x.data"}),
        ("shuffle_fetch", {"resource": "shuffle_0", "partition": 1,
                           "bytes": 100, "blocks": 2}),
        ("rss_push", {"resource": "rss_0", "partition": 0, "bytes": 7,
                      "blocks": 1}),
        ("plan_cache", {"action": "hit", "fingerprint": "ab12" * 8}),
        ("result_cache", {"action": "invalidate",
                          "fingerprint": "cd34" * 8, "bytes": 2048}),
        ("worker_telemetry", {"worker": "w0", "pid": 4242, "jobs_ok": 3,
                              "jobs_failed": 1, "rows": 640, "bytes": 5120,
                              "device_ns": 900, "dispatch_ns": 300,
                              "compile_ns": 0, "mem_peak": 1 << 20,
                              "eventlog": "/tmp/w0.jsonl"}),
        ("slo_alert_firing", {"pool": "etl", "slo": "latency",
                              "burn_fast": 14.4, "burn_slow": 6.0,
                              "window_sec": 3600.0, "objective": 0.99,
                              "threshold": 250.0}),
        ("slo_alert_resolved", {"pool": "etl", "slo": "latency",
                                "burn_fast": 0.0, "burn_slow": 0.5,
                                "fired_for_s": 12.5}),
        ("stats_skew_detected", {"exchange": "shuffle_0",
                                 "op": "ShuffleWriterExec[HashPartitioning]",
                                 "partition": 3, "rows": 9000,
                                 "bytes": 72000, "ratio": 6.5,
                                 "partitions": 8}),
        ("stats_persisted", {"fingerprint": "ab" * 32, "nodes": 4}),
        ("stats_reused", {"fingerprint": "ab" * 32, "nodes": 4}),
    ]


def test_every_event_type_roundtrips_golden_schema(tmp_path):
    schema = trace.load_schema()
    synth = _synthetic_events()
    # registry, golden schema, and synthetic coverage in lockstep:
    # adding/removing an event type without updating all three is drift
    assert set(schema["events"]) == set(trace.EVENT_TYPES)
    assert {t for t, _ in synth} == set(trace.EVENT_TYPES)

    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    try:
        with trace.query("schema_check") as path:
            for etype, fields in synth:
                if etype in ("query_start", "query_end"):
                    continue  # emitted by the query span itself
                trace.emit(etype, **fields)
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        trace.reset()
    events = trace.read_events(path)
    assert {e["type"] for e in events} == set(trace.EVENT_TYPES)
    for e in events:
        jsonschema.validate(e, schema["events"][e["type"]])


def test_real_run_events_validate_against_schema(data, tmp_path):
    schema = trace.load_schema()
    events, _ = _run_traced(data, "q1", tmp_path, runs=1, n_parts=2,
                            batch_rows=16384)
    assert events
    for e in events:
        assert e["type"] in schema["events"], f"undeclared type {e['type']}"
        jsonschema.validate(e, schema["events"][e["type"]])


def test_unregistered_event_type_raises(tmp_path):
    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    try:
        with pytest.raises(ValueError, match="unregistered"):
            trace.emit("not_a_real_event", x=1)
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        trace.reset()


# ------------------------------------- 6. metrics thread safety

def test_metrics_set_concurrent_add():
    from blaze_tpu.runtime.metrics import MetricsSet

    ms = MetricsSet()
    n_threads, n_iters = 8, 2000

    def worker():
        for _ in range(n_iters):
            ms.add("output_rows", 1)
            ms.add("bytes", 3)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ms.get("output_rows") == n_threads * n_iters
    assert ms.get("bytes") == 3 * n_threads * n_iters


def test_metric_node_concurrent_child_growth():
    from blaze_tpu.runtime.metrics import MetricNode

    node = MetricNode()
    errs = []

    def worker(i):
        try:
            for j in range(300):
                node.child(j % 17).metrics.add("c", 1)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(node.children) == 17
    total = sum(c.metrics.get("c") for c in node.children)
    assert total == 8 * 300


def test_metrics_merge():
    from blaze_tpu.runtime.metrics import MetricsSet

    a, b = MetricsSet(), MetricsSet()
    a.add("rows", 2)
    b.add("rows", 3)
    b.add("bytes", 7)
    a.merge(b)
    assert a.snapshot() == {"rows": 5, "bytes": 7}


# ------------------------------------- 7. sampling + log rotation

def test_trace_sample_rate_times_every_nth_program(data, tmp_path):
    """spark.blaze.trace.sampleRate=N: with tracing armed, only every
    Nth instrumented program pays the block-until-ready device drain;
    unsampled calls still count programs and launch overhead, and
    sum_kernels scales the device total by programs/timed."""
    from blaze_tpu.ops.fusion import optimize_plan
    from blaze_tpu.runtime.context import TaskContext

    def run_once():
        plan = optimize_plan(build_query("q6", _scans(data, 1, 8192), 1))
        for p in range(plan.num_partitions()):
            for _ in plan.execute(p, TaskContext(p, plan.num_partitions())):
                pass

    run_once()  # warm: compiles out of the way
    conf.TRACE_SAMPLE_RATE.set(4)
    trace.reset()
    try:
        with trace.kernel_capture() as kc:
            run_once()
    finally:
        conf.TRACE_SAMPLE_RATE.set(1)
        trace.reset()
    programs = sum(v["programs"] for v in kc.values())
    timed = sum(v["timed"] for v in kc.values())
    assert programs > 4
    assert 0 < timed < programs, (programs, timed)
    # scaling: the span total estimates full-fidelity device time
    raw = sum(v["device_ns"] for v in kc.values())
    scaled = trace.sum_kernels(kc)["device_time_ns"]
    assert scaled >= raw
    # the per-label scaler round-trips programs/timed
    for v in kc.values():
        if v["timed"]:
            assert trace.scaled_device_ns(v) >= v["device_ns"]


def test_trace_sample_rate_one_times_everything(data, tmp_path):
    """The default sampleRate=1 keeps full-fidelity attribution:
    every program timed (the pre-existing contract)."""
    from blaze_tpu.ops.fusion import optimize_plan
    from blaze_tpu.runtime.context import TaskContext

    plan = optimize_plan(build_query("q6", _scans(data, 1, 8192), 1))
    trace.reset()
    with trace.kernel_capture() as kc:
        for p in range(plan.num_partitions()):
            for _ in plan.execute(p, TaskContext(p, plan.num_partitions())):
                pass
    for label, v in kc.items():
        assert v["timed"] == v["programs"], (label, v)


def test_event_log_rotation_and_rotated_report(tmp_path):
    """spark.blaze.eventLog.maxBytes: the active file rolls over into
    numbered segments; read_event_log reassembles the set in emission
    order and --report renders from it."""
    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    conf.EVENT_LOG_MAX_BYTES.set(1500)
    trace.reset()
    try:
        with trace.query("rotation_check") as path:
            for i in range(200):
                trace.emit("mem_watermark", used=i, total=4096)
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        conf.EVENT_LOG_MAX_BYTES.set(0)
        trace.reset()
    segs = sorted(p for p in os.listdir(tmp_path) if ".seg" in p)
    assert segs, "no rollover segments despite the 1.5 KB cap"
    for seg in segs:
        assert os.path.getsize(os.path.join(tmp_path, seg)) >= 1500
    events = trace.read_event_log(path)
    watermarks = [e for e in events if e["type"] == "mem_watermark"]
    assert len(watermarks) == 200
    # emission order survives the segment stitching
    assert [e["used"] for e in watermarks] == list(range(200))
    # the active (last) file stays under the cap + one event of slack
    assert os.path.getsize(path) < 1500 + 200
    # the CLI renders the rotated set
    from blaze_tpu.__main__ import main

    assert main(["--report", path]) == 0


def test_event_log_no_rotation_by_default(tmp_path):
    """maxBytes=0 (default): one unbounded file, no segments."""
    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    try:
        with trace.query("no_rotation") as path:
            for i in range(50):
                trace.emit("mem_watermark", used=i, total=4096)
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        trace.reset()
    assert not [p for p in os.listdir(tmp_path) if ".seg" in p]
    assert trace.read_event_log(path) == trace.read_events(path)


def test_event_log_rotation_never_clobbers_prior_segments(tmp_path):
    """Regression, twice over: reset() clears the in-memory sequence
    AND segment counters while the same query_id + pid regenerates the
    same log name.  The span allocator now probes past files already
    on disk, so a re-run gets a FRESH file — the stronger contract: no
    clobbered segments AND no two runs (two trace ids) appended into
    one log, which tore the OTLP single-trace-per-export invariant on
    every chaos sweep past seed 1.  Both runs' events must survive in
    full, each in its own file set."""
    def run_once():
        conf.TRACE_ENABLE.set(True)
        conf.EVENT_LOG_DIR.set(str(tmp_path))
        conf.EVENT_LOG_MAX_BYTES.set(1000)
        trace.reset()
        try:
            with trace.query("clobber_check") as path:
                for i in range(60):
                    trace.emit("mem_watermark", used=i, total=4096)
        finally:
            conf.TRACE_ENABLE.set(False)
            conf.EVENT_LOG_DIR.set("")
            conf.EVENT_LOG_MAX_BYTES.set(0)
            trace.reset()
        return path

    p1 = run_once()
    p2 = run_once()
    assert p1 != p2, (
        "a re-run after reset() must get a fresh log file, never "
        "append a second trace into the first run's")
    for p in (p1, p2):
        events = trace.read_event_log(p)
        watermarks = [e for e in events if e["type"] == "mem_watermark"]
        assert len(watermarks) == 60, (
            f"rollover clobbered earlier segments: "
            f"{len(watermarks)}/60 events in {p}")
        # exactly ONE trace id per file — the OTLP export invariant
        assert len({e["trace_id"] for e in events if "trace_id" in e}) == 1


# ------------------------- 8. host spans + host-time counters (PR 27)

def _host_nbytes(batch):
    """What to_device() stages for one host batch, reckoned apart from
    RecordBatch.host_nbytes: every numpy buffer's nbytes."""
    import numpy as np

    return sum(a.nbytes for c in batch.columns
               for a in (c.data, c.validity, c.lengths)
               if isinstance(a, np.ndarray))


#: query -> the tables its plan scans
_SCANNED = {"q6": ("lineitem",), "q3": ("customer", "orders", "lineitem")}


@pytest.mark.parametrize("q", sorted(_SCANNED))
def test_span_counters_with_tracing_off(data, q, monkeypatch):
    """A scheduler run under dispatch.capture() with tracing OFF tallies
    the host spans exactly: one task_decode per task, one scan_stage per
    source batch with its bytes from shapes, one launch per dispatch,
    and what the exchanges wrote is what they read."""
    conf.TRACE_ENABLE.set(False)
    trace.reset()

    def poisoned(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("kernel timing entered with tracing disabled")

    monkeypatch.setattr(trace, "record_kernel", poisoned)
    n_parts = 2
    scans = _scans(data, n_parts, 16384)
    host_batches = [b for t in _SCANNED[q] for p in scans[t]._partitions for b in p]
    stages, manager = split_stages(build_query(q, scans, n_parts))
    with dispatch.capture() as c:
        rows = sum(b.num_rows for b in run_stages(stages, manager, max_task_attempts=1))
    assert rows > 0
    n_tasks = sum(s.n_tasks for s in stages)
    assert c["task_decode_n"] == n_tasks > 1
    assert c["scan_stage_n"] == len(host_batches) > n_parts
    assert c["h2d_bytes"] == sum(_host_nbytes(b) for b in host_batches) > 0
    assert c["launch_n"] == c["xla_dispatches"] > 0
    assert c["shuffle_bytes_written"] > 0
    assert c["exchange_write_n"] > 0 and c["exchange_read_n"] > 0
    assert c["device_read_n"] > 0
    spans = {k for k in c if k.endswith("_ns") and k[:-3] + "_n" in c}
    # task and join_build are annotations only: no metric reads them
    joins = {"join_probe_ns", "broadcast_build_ns"} if q == "q3" else set()
    # a map task always drains its stager; a put blocks only on a full queue
    assert spans - {"inserter_full_ns"} == joins | {
        "task_decode_ns", "scan_stage_ns", "launch_ns", "device_read_ns",
        "exchange_write_ns", "exchange_read_ns", "inserter_drain_ns"}
    assert c["inserter_items"] > 0
    assert 0 < c["exchange_d2h_ns"] <= c["device_read_ns"]
    if joins:
        # one broadcast, built by the first probe task and found in the
        # executor's cache by the others; every probe counts its rows
        assert (c["broadcast_build_n"], c["join_map_builds"]) == (1, 1)
        assert c["join_map_cache_hits"] == n_parts - 1
        assert c["join_probe_n"] > 0 and c["join_probe_rows_in"] >= c["join_rows_out"] > 0
        # uniform hashes: a handful of steps a probe, not log2(cap) + 1
        assert 0 < c["join_search_steps"] <= 6 * c["join_probe_n"]
    for k in spans:
        assert c[k] > 0, k
    # tracing stayed off: the span is not an event and not a query span
    assert trace.counters() == {"events": 0, "spans": 0}
    assert trace.current_path() is None


def test_span_is_disarmed_safe_and_tallies_its_host_time():
    conf.TRACE_ENABLE.set(False)
    trace.reset()
    with dispatch.capture() as c:
        with trace.span("task_decode", stage=3, partition=1, attempt=0) as sp:
            pass
        assert trace.read_scalar(7) == 7
        with trace.annotation("task", stage=3, partition=1, attempt=0):
            pass  # an annotation alone tallies nothing
    assert (c["task_decode_n"], c["task_decode_ns"]) == (1, sp.ns)
    assert sp.ns > 0
    assert c["device_read_n"] == 1 and c["device_read_ns"] > 0
    assert set(c) == {"task_decode_ns", "task_decode_n",
                      "device_read_ns", "device_read_n"}
    assert trace.counters() == {"events": 0, "spans": 0}


def test_timer_that_opens_a_span_has_one_clock():
    """MetricsSet.timer(name, span): the operator's timer and the tally
    hold the same nanoseconds, when the body raises too."""
    from blaze_tpu.runtime.metrics import MetricsSet

    m = MetricsSet()
    with dispatch.capture() as c:
        with m.timer("output_io_time", trace.span("exchange_write")):
            pass
        first = c["exchange_write_ns"]
        assert m.get("output_io_time") == first > 0
        with pytest.raises(KeyError):
            with m.timer("output_io_time", trace.span("exchange_write")):
                raise KeyError("x")
    assert m.get("output_io_time") == c["exchange_write_ns"] > first
    assert c["exchange_write_n"] == 2


def test_staged_scan_sums_locally_and_records_once(data):
    """The per-batch site: ExecNode._staged reaches the tally when its
    stream ends, with its batches, their bytes and the nanoseconds the
    scan's own input_io_time gets."""
    scan = _scans(data, 1, 16384)["lineitem"]
    host = scan._partitions[0]
    with dispatch.capture() as c:
        it = scan._staged(host)
        next(it)
        assert "scan_stage_n" not in c
        assert sum(1 for _ in it) == len(host) - 1
    assert c["scan_stage_n"] == len(host) > 1
    assert c["h2d_bytes"] == sum(_host_nbytes(b) for b in host)
    assert c["scan_stage_ns"] == scan.metrics.get("input_io_time") > 0


def _q6_memory_scans(data, n_parts):
    """lineitem as q6's column-pruned scan hands it over: its four
    columns, none of them NULL; 16,384-row batches, so every partition
    ends in a partial one."""
    from blaze_tpu.schema import Schema

    cols = ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate")
    schema = Schema([f for f in TPCH_SCHEMAS["lineitem"].fields if f.name in cols])
    assert len(schema.fields) == len(cols)
    return {"lineitem": MemoryScanExec(
        table_to_batches(data["lineitem"], schema, n_parts, batch_rows=16384), schema)}


def test_staged_q6_shares_its_row_masks_and_counts_them(data):
    """A q6 run from memory: each of the four columns' validity takes a
    shared row mask, full batch or partial; what was transferred plus
    what was shared is what to_device() was handed, and a partial
    batch's one mask; h2d_bytes still counts every host array."""
    import numpy as np

    n_parts = 2
    scans = _q6_memory_scans(data, n_parts)
    host = [b for p in scans["lineitem"]._partitions for b in p]
    partial = sum(b.num_rows < b.capacity for b in host)
    assert 0 < partial < len(host)
    handed = sum(isinstance(a, np.ndarray) for b in host for c in b.columns
                 for a in (c.data, c.validity, c.lengths))
    stages, manager = split_stages(build_query("q6", scans, n_parts))
    with dispatch.capture() as c:
        assert sum(b.num_rows for b in run_stages(stages, manager, max_task_attempts=1)) > 0
    assert c["scan_stage_n"] == len(host)
    assert c["h2d_masks_shared"] == 4 * len(host)
    assert c["h2d_arrays"] + c["h2d_masks_shared"] == handed + partial
    assert c["h2d_arrays"] == 4 * len(host) + partial
    assert c["h2d_bytes"] == sum(_host_nbytes(b) for b in host)


def test_shared_row_masks_compile_nothing_new(data, monkeypatch):
    """Warmed by batches staged column by column — every validity its
    own transfer — a run over shared row masks launches the same
    programs with the same argument signatures, and so compiles nothing;
    nor does a second run over shared masks."""
    from blaze_tpu.batch import RecordBatch

    def each_column(b):
        return RecordBatch(b.schema, [c.to_device() for c in b.columns], b.num_rows), 0, 0

    def run():
        stages, manager = split_stages(build_query("q6", _q6_memory_scans(data, 2), 2))
        with dispatch.capture() as c:
            assert sum(b.num_rows for b in run_stages(stages, manager, max_task_attempts=1)) > 0
        return c

    with monkeypatch.context() as m:
        m.setattr(RecordBatch, "to_device_counted", each_column)
        assert run()["h2d_masks_shared"] == 0
    for _ in range(2):
        c = run()
        assert c["h2d_masks_shared"] > 0
        assert c.get("xla_compiles", 0) == 0


def test_span_closes_and_tallies_when_its_body_raises():
    with dispatch.capture() as c:
        with pytest.raises(KeyError):
            with trace.span("exchange_read"):
                raise KeyError("x")
    assert c["exchange_read_n"] == 1 and c["exchange_read_ns"] > 0


def test_joiner_kernels_are_under_the_dispatch_counters():
    """candidate/probe/compact launches are counted, attributed to
    their own labels, and a repeated probe compiles nothing."""
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.joins.core import JoinerState, JoinType, cached_joiner
    from blaze_tpu.schema import DataType, Field, Schema

    ls = Schema([Field("k", DataType.int64()), Field("a", DataType.int64())])
    rs = Schema([Field("k2", DataType.int64()), Field("b", DataType.int64())])
    probe = batch_from_pydict({"k": [1, 2, 3, 4], "a": [10, 20, 30, 40]}, ls)
    build = batch_from_pydict({"k2": [2, 4, 4], "b": [1, 2, 3]}, rs)
    j = cached_joiner(ls, rs, [col("k")], [col("k2")], JoinType.LEFT, True)
    jmap = j.build_map(build)

    def probe_once():
        out = j.probe_batch(jmap, probe, JoinerState())
        return out.num_rows

    assert probe_once() == 5  # 2->1, 4->2 matches, 1 and 3 unmatched
    with dispatch.capture() as c, trace.kernel_capture() as kc:
        assert probe_once() == 5
    assert {"join_candidate", "join_probe", "join_compact"} <= set(kc)
    assert c["xla_dispatches"] >= 3 and c["launch_n"] == c["xla_dispatches"]
    assert c.get("xla_compiles", 0) == 0
    # a second probe of the map: the pair count and the candidate
    # total in one read (the bucket is the first probe's), then the
    # unmatched count
    assert c["device_read_n"] == 2
    assert (c["join_outcap_predicted"], c.get("join_outcap_redo", 0)) == (1, 0)
    # the search's steps ride the candidate total's read: build key 4
    # twice is the largest bucket, two steps
    assert (c["join_probe_n"], c["join_search_steps"]) == (1, 2)
    # a map's first probe: candidate total, pair count, unmatched count
    jmap = j.build_map(build)
    with dispatch.capture() as c:
        assert probe_once() == 5
    assert c.get("xla_compiles", 0) == 0
    assert c["device_read_n"] == 3 and "join_outcap_predicted" not in c
    assert (c["join_probe_n"], c["join_search_steps"]) == (1, 2)


def test_q03_joiner_launches_counted_and_warm_run_compiles_nothing(data):
    def run_once():
        stages, manager = split_stages(build_query("q3", _scans(data, 2, 16384), 2))
        with dispatch.capture() as c, trace.kernel_capture() as kc:
            rows = sum(b.num_rows for b in run_stages(stages, manager, max_task_attempts=1))
        assert rows > 0
        return c, kc

    run_once()
    c, kc = run_once()
    assert c.get("xla_compiles", 0) == 0
    joiner = sum(kc[k]["programs"] for k in ("join_candidate", "join_probe"))
    assert joiner > 0
    # every program the kernel capture attributes is a counted dispatch
    assert sum(v["programs"] for v in kc.values()) == c["xla_dispatches"]


def test_sample_rate_zero_arms_the_log_without_blocking(data, tmp_path, monkeypatch):
    """spark.blaze.trace.sampleRate=0: the event log is written, every
    launch and compile is attributed per label, and nothing calls
    block_until_ready (poisoned)."""
    import jax

    _run_traced(data, "q6", tmp_path / "warm", runs=1)  # compiles out of the way

    def poisoned(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("block_until_ready at sampleRate=0")

    conf.TRACE_SAMPLE_RATE.set(0)
    monkeypatch.setattr(jax, "block_until_ready", poisoned)
    try:
        events, path = _run_traced(data, "q6", tmp_path / "armed", runs=1)
    finally:
        conf.TRACE_SAMPLE_RATE.set(1)
        trace.reset()
    kernels = [e for e in events if e["type"] == "task_kernels"]
    assert kernels
    assert sum(e["programs"] for e in kernels) > 0
    assert sum(e["dispatch_overhead_ns"] for e in kernels) > 0
    assert all(e["device_time_ns"] == 0 for e in kernels)
    for e in kernels:
        assert all(v["timed"] == 0 for v in e["kernels"].values())
    # the report says so, and does not print a measured 0
    text = trace_report.render(trace.read_event_log(path))
    assert "not sampled" in text
    from blaze_tpu.runtime import perf

    explained = perf.render_explain(events)
    assert "perf: n/a" in explained and "device=not sampled" in explained
    assert "hbm_util=n/a" in explained and "mfu_est=n/a" in explained
