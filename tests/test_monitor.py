"""Live query monitoring (tier-1, CPU backend).

1. **Live progress** (acceptance): with the monitor armed, a query
   running on a background thread is observable MID-FLIGHT via
   ``/queries`` — stage rows strictly increase across polls — and
   ``/metrics`` parses as Prometheus text exposition format.
2. **Structural no-op** (acceptance): with
   ``spark.blaze.monitor.enabled=false`` (the default) no server or
   thread is created and the heartbeat path never reaches the
   registry or the emitter (poisoned, like the trace-off gate).
3. **Gateway-path spans** (acceptance): ``session.execute`` (the
   non-scheduler path) produces query -> stage spans in the event log
   that ``--report`` and ``--report --json`` render with the same
   shape as scheduler-path runs.
4. **Heartbeats**: stage_progress / task_heartbeat events round-trip
   the golden event schema from a REAL run (the synthetic lockstep
   lives in test_trace.py).
5. **Metric-name registry**: metric_names.json pins every
   counter/gauge name, gated both ways (source literal -> registry,
   registry -> source literal) plus a dynamic subset check.
6. **--report --json**: golden top-level/stage/kernel keys.
7. **Server lifecycle**: endpoints, clean shutdown, no thread leak
   (the chaos CLI runs the same gate via ``--chaos --monitor``).
"""

import json
import os
import re
import threading
import time
import urllib.error
import urllib.request

import jsonschema
import pytest

import spark_fixtures as F
from blaze_tpu import conf
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.runtime import monitor, trace, trace_report
from blaze_tpu.runtime.metrics import registered_metric_names
from blaze_tpu.runtime.scheduler import run_stages, split_stages
from blaze_tpu.schema import DataType, Field, Schema
from blaze_tpu.spark import BlazeSparkSession
from blaze_tpu.tpch import TPCH_SCHEMAS, build_query
from blaze_tpu.tpch.datagen import generate_all, table_to_batches

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True, scope="module")
def _lock_order_assertions():
    """The monitor suite exercises every background-thread subsystem
    (HTTP handler threads, heartbeat TLS, scheduler fan-out), so the
    whole module runs with the runtime lock-order assertion armed
    (analysis/locks.py): an inverted acquisition raises LockOrderError
    in the test instead of deadlocking rarely in production."""
    from blaze_tpu.analysis import locks as lock_verify

    conf.VERIFY_LOCKS.set(True)
    lock_verify.refresh()
    yield
    conf.VERIFY_LOCKS.set(False)
    lock_verify.refresh()


@pytest.fixture(scope="module")
def data():
    return generate_all(0.02)


def _scans(data, n_parts=2, batch_rows=16384):
    return {
        name: MemoryScanExec(
            table_to_batches(data[name], TPCH_SCHEMAS[name], n_parts,
                             batch_rows=batch_rows),
            TPCH_SCHEMAS[name],
        )
        for name in TPCH_SCHEMAS
    }


@pytest.fixture
def armed_monitor():
    """Monitor armed on an ephemeral port with a fast heartbeat; the
    server (if started) and all conf restored afterwards."""
    conf.MONITOR_ENABLE.set(True)
    conf.MONITOR_PORT.set(0)
    conf.MONITOR_HEARTBEAT_MS.set(1)
    monitor.reset()
    try:
        yield monitor
    finally:
        monitor.shutdown_server()
        conf.MONITOR_ENABLE.set(False)
        conf.MONITOR_PORT.set(4048)
        conf.MONITOR_HEARTBEAT_MS.set(1000)
        monitor.reset()
        assert monitor.monitor_threads() == []


def _get(url, path):
    with urllib.request.urlopen(url + path, timeout=10) as r:
        body = r.read()
        return r.status, r.headers.get("Content-Type", ""), body


# ---- Prometheus text exposition parser (format contract, no client lib)

_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                      # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\""          # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\")*\})?"     # more labels
    r" -?[0-9.eE+\-Na-n]+( [0-9]+)?"                   # value [timestamp]
    # OpenMetrics exemplar on histogram buckets: " # {labels} value [ts]"
    r"( # \{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\"\}"
    r" -?[0-9.eE+-]+( [0-9.]+)?)?$")
_PROM_COMMENT = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+$")


def _assert_prometheus(text: str) -> dict:
    """Validate text exposition format line-by-line; returns
    {family: [sample lines]}.  Prometheus REJECTS a scrape containing
    duplicate name+label samples, so uniqueness is part of the format
    contract."""
    families = {}
    seen = set()
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            assert _PROM_COMMENT.match(line), f"bad comment line: {line!r}"
            continue
        assert _PROM_SAMPLE.match(line), f"bad sample line: {line!r}"
        series = line.rsplit(" ", 1)[0]
        assert series not in seen, f"duplicate series: {series!r}"
        seen.add(series)
        families.setdefault(line.split("{")[0].split(" ")[0], []).append(line)
    assert families, "no samples rendered"
    return families


# ------------------------------------------------- 1. live progress

class SlowScanExec(MemoryScanExec):
    """A scan that sleeps between batches — the observable slow query
    for the mid-flight poll test."""

    def __init__(self, partitions, schema, delay_s: float):
        super().__init__(partitions, schema)
        self._delay = delay_s

    def execute(self, partition, ctx):
        def stream():
            if partition < len(self._partitions):
                for b in self._partitions[partition]:
                    time.sleep(self._delay)
                    self.metrics.add("output_rows", b.num_rows)
                    monitor.tick()
                    yield b.to_device()

        return stream()


def _slow_session(n_rows=2000, n_batches=20, delay_s=0.02):
    schema = Schema([Field("v", DataType.int64())])
    per = n_rows // n_batches
    from blaze_tpu.batch import batch_from_pydict

    parts = [[batch_from_pydict({"v": list(range(i * per, (i + 1) * per))},
                                schema) for i in range(n_batches)]]
    sess = BlazeSparkSession()
    sess.register_table("slow", SlowScanExec(parts, schema, delay_s))
    plan = F.flatten(F.scan("slow", [F.attr("v", 1)]))
    return sess, plan, n_rows


def test_live_progress_visible_mid_flight(armed_monitor):
    """Acceptance: a background-thread query's stage progress strictly
    increases across /queries polls while it runs, and /metrics parses
    as Prometheus text format mid-flight."""
    srv = monitor.ensure_server()
    assert srv is not None and srv.port > 0
    sess, plan, n_rows = _slow_session()
    done = threading.Event()
    result = {}

    def run():
        try:
            result["out"] = sess.execute(plan, query_id="slow_poll_test")
        finally:
            done.set()

    t = threading.Thread(target=run)
    t.start()
    samples = []
    try:
        deadline = time.monotonic() + 30
        while not done.is_set() and time.monotonic() < deadline:
            _, _, body = _get(srv.url, "/queries")
            snap = json.loads(body)
            for q in snap["queries"]:
                if q["query_id"] == "slow_poll_test" and q["stages"]:
                    samples.append(q["stages"][0]["rows"])
            if len([s for s in samples if s > 0]) >= 3 and len(set(samples)) >= 3:
                break
            time.sleep(0.02)
    finally:
        t.join(timeout=60)
    assert done.is_set(), "slow query never finished"
    assert len(result["out"]["v"]) == n_rows
    # mid-flight observability: at least two strictly increasing
    # nonzero row counts BEFORE completion-time totals
    increasing = [s for s in samples if 0 < s < n_rows]
    assert len(set(increasing)) >= 2, (
        f"no mid-flight progress observed: samples={samples}")
    assert sorted(samples) == samples, f"progress regressed: {samples}"
    # /metrics parses mid-run state too
    _, ctype, body = _get(srv.url, "/metrics")
    assert ctype.startswith("text/plain")
    fams = _assert_prometheus(body.decode())
    assert "blaze_query_stage_rows" in fams
    assert "blaze_monitor_queries" in fams


def test_queries_endpoint_scheduler_run(data, armed_monitor):
    """A scheduler-path run registers per-stage live state: map stages
    carry task heartbeat rows, the result stage carries driver rows,
    and the recovery tallies ride on the query entry."""
    srv = monitor.ensure_server()
    with monitor.query_span("mon_q1", mode="scheduler"):
        stages, mgr = split_stages(build_query("q1", _scans(data), 2))
        rows = sum(b.num_rows for b in run_stages(stages, mgr))
    assert rows > 0
    _, _, body = _get(srv.url, "/queries")
    snap = json.loads(body)
    q = next(q for q in snap["queries"] if q["query_id"] == "mon_q1")
    assert q["status"] == "done" and q["mode"] == "scheduler"
    assert q["attempts"].get("task_attempts", 0) >= 3
    kinds = {s["kind"] for s in q["stages"]}
    assert "map" in kinds and "result" in kinds
    result_stage = next(s for s in q["stages"] if s["kind"] == "result")
    assert result_stage["rows"] == rows
    assert result_stage["tasks_done"] == result_stage["n_tasks"]
    map_stage = next(s for s in q["stages"] if s["kind"] == "map")
    # task heartbeats reported operator rows for driver-invisible maps
    assert map_stage["task_rows"] > 0
    # ...and NOT inflated by the operator-chain depth (progress_rows is
    # the widest single node, never the tree sum): bounded by the
    # source table size
    n_lineitem = next(iter(data["lineitem"].values()))[0].shape[0]
    assert map_stage["task_rows"] <= n_lineitem, (
        map_stage["task_rows"], n_lineitem)
    assert map_stage["counters"].get("xla_dispatches", 0) > 0
    # memory block present
    assert set(snap["memory"]) == {"used", "total"}
    # /metrics reports the SAME row semantics for the map stage (the
    # driver-observed 0 would be indistinguishable from a wedged stage)
    line = next(
        l for l in monitor.render_prometheus().splitlines()
        if l.startswith("blaze_query_stage_rows")
        and 'query="mon_q1"' in l
        and f'stage="{map_stage["stage_id"]}"' in l)
    assert int(float(line.rsplit(" ", 1)[1])) == max(
        map_stage["rows"], map_stage["task_rows"]) > 0


def test_metrics_endpoint_renders_scheduler_tree(data, armed_monitor):
    srv = monitor.ensure_server()
    with monitor.query_span("mon_q6", mode="scheduler"):
        stages, mgr = split_stages(build_query("q6", _scans(data), 2))
        assert sum(b.num_rows for b in run_stages(stages, mgr)) > 0
    _, _, body = _get(srv.url, "/metrics")
    fams = _assert_prometheus(body.decode())
    # scheduler root counters + per-stage labeled samples
    assert "blaze_scheduler_task_attempts" in fams
    assert any(f.startswith("blaze_stage_") for f in fams)
    stage_samples = [s for f, ss in fams.items() if f.startswith("blaze_stage_")
                     for s in ss]
    assert any('stage="' in s for s in stage_samples)
    # every rendered scheduler/stage/dispatch name is a registered one;
    # histogram families are registered under their FULL name (plus the
    # _bucket/_sum/_count sample suffixes the exposition format adds)
    registered = registered_metric_names()
    hist_fams = {n + suffix for n in registered
                 for suffix in ("", "_bucket", "_sum", "_count")}
    for fam in fams:
        if fam in hist_fams:
            continue
        for prefix in ("blaze_scheduler_", "blaze_stage_"):
            if fam.startswith(prefix):
                assert fam[len(prefix):] in registered, fam


def test_metrics_no_duplicate_series_for_repeated_query(armed_monitor):
    """Regression: the registry keeps every RUN of a query (unique
    keys), but /metrics labels series by query_id — repeated runs must
    export the latest only, or the whole scrape is rejected."""
    srv = monitor.ensure_server()
    for _ in range(2):
        with monitor.query_span("dup_q", mode="in-process"):
            with monitor.stage_span(0, "result", 1):
                pass
    _, _, body = _get(srv.url, "/queries")
    runs = [q for q in json.loads(body)["queries"]
            if q["query_id"] == "dup_q"]
    assert len(runs) == 2, "history must stay visible in /queries"
    _, _, body = _get(srv.url, "/metrics")
    _assert_prometheus(body.decode())  # uniqueness asserted in helper


def test_heartbeat_age_gauge_only_for_running_queries(armed_monitor):
    """Regression: a finished query's last_beat is frozen, so its
    heartbeat age climbs forever — exporting it would fire any
    wedge-detection alert on every NORMAL completion.  The gauge must
    cover running queries only (elapsed stays for both)."""
    srv = monitor.ensure_server()
    with monitor.query_span("hb_done", mode="in-process"):
        with monitor.stage_span(0, "result", 1):
            pass
    with monitor.query_span("hb_live", mode="in-process"):
        _, _, body = _get(srv.url, "/metrics")
        fams = _assert_prometheus(body.decode())
        ages = fams.get("blaze_query_heartbeat_age_seconds", [])
        assert any('query="hb_live"' in s for s in ages)
        assert not any('query="hb_done"' in s for s in ages)
        # elapsed is a plain duration, not a wedge signal: both export
        elapsed = fams["blaze_query_elapsed_seconds"]
        assert any('query="hb_done"' in s for s in elapsed)


def test_gateway_task_span_lands_task_identity(armed_monitor, tmp_path):
    """gateway.task_span brackets an FFI drive in the scheduler's
    task-attempt event shape and lands the task_id + rows in the live
    registry."""
    from blaze_tpu import gateway
    from blaze_tpu.batch import batch_from_pydict

    srv = monitor.ensure_server()
    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    try:
        with gateway.query_span("ffi_q") as path:
            with gateway.task_span("task_ffi_0", partition=0) as progress:
                schema = Schema([Field("v", DataType.int64())])
                progress.add_batch(
                    batch_from_pydict({"v": [1, 2, 3]}, schema))
        events = trace.read_event_log(path)
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        trace.reset()
    types = [e["type"] for e in events]
    for t in ("query_start", "task_attempt_start", "stage_submit",
              "stage_complete", "task_attempt_end", "query_end"):
        assert t in types, f"missing {t}: {types}"
    schema_doc = trace.load_schema()
    for e in events:
        jsonschema.validate(e, schema_doc["events"][e["type"]])
    _, _, body = _get(srv.url, "/queries")
    q = next(q for q in json.loads(body)["queries"]
             if q["query_id"] == "ffi_q")
    task = q["stages"][0]["tasks"]["0"]
    assert task["task_id"] == "task_ffi_0"
    assert task["rows"] == 3
    # a bare task_span (no enclosing query-level stage) still counts
    # its own completion — 0/1 forever would read as a stuck drive
    assert q["stages"][0]["tasks_done"] == 1


def test_gateway_multi_task_query_opens_one_stage_span(armed_monitor,
                                                      tmp_path):
    """Regression: task_spans nested in a query_span share ONE stage
    span — a 2-task FFI drive must not reset the registry stage or
    emit duplicate stage_submit/stage_complete pairs for stage 0."""
    from blaze_tpu import gateway
    from blaze_tpu.batch import batch_from_pydict

    srv = monitor.ensure_server()
    schema = Schema([Field("v", DataType.int64())])
    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    try:
        with gateway.query_span("ffi_multi", n_tasks=2) as path:
            for part, vals in ((0, [1, 2]), (1, [3, 4, 5])):
                with gateway.task_span(f"t_{part}", partition=part) as p:
                    p.add_batch(batch_from_pydict({"v": vals}, schema))
        events = trace.read_event_log(path)
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        trace.reset()
    types = [e["type"] for e in events]
    assert types.count("stage_submit") == 1
    assert types.count("stage_complete") == 1
    assert types.count("task_attempt_start") == 2
    # --report sees ONE stage-0 timeline row, like a scheduler log
    assert len(trace_report.render_json(events)["stages"]) == 1
    _, _, body = _get(srv.url, "/queries")
    q = next(q for q in json.loads(body)["queries"]
             if q["query_id"] == "ffi_multi")
    stage = q["stages"][0]
    assert stage["tasks_done"] == 2 and stage["n_tasks"] == 2
    assert stage["rows"] == 5  # both tasks' batches, not just the last
    assert {t["task_id"] for t in stage["tasks"].values()} == {"t_0", "t_1"}
    assert stage["tasks"]["1"]["rows"] == 3  # per-task delta, not total


def test_gateway_task_span_default_partition_stays_unique(armed_monitor):
    """Regression: the registry keys tasks by partition; a caller that
    omits it (JNI drives don't always know an index) must still get
    one entry PER task, not every task collapsed onto partition 0."""
    from blaze_tpu import gateway
    from blaze_tpu.batch import batch_from_pydict

    srv = monitor.ensure_server()
    schema = Schema([Field("v", DataType.int64())])
    with gateway.query_span("ffi_nopart", n_tasks=3):
        for i, vals in enumerate(([1], [2, 3], [4, 5, 6])):
            with gateway.task_span(f"t_{i}") as p:
                p.add_batch(batch_from_pydict({"v": vals}, schema))
    _, _, body = _get(srv.url, "/queries")
    q = next(q for q in json.loads(body)["queries"]
             if q["query_id"] == "ffi_nopart")
    stage = q["stages"][0]
    assert {t["task_id"] for t in stage["tasks"].values()} == {
        "t_0", "t_1", "t_2"}
    assert {t["rows"] for t in stage["tasks"].values()} == {1, 2, 3}


def test_ffi_export_accounting_scoped_to_gateway_span(armed_monitor):
    """Regression: export_batch_ffi feeds the ACTIVE gateway span's
    progress only — exports outside one (udf_bridge shipping UDF
    argument batches) must not mint phantom registry rows."""
    from blaze_tpu import gateway

    assert getattr(gateway._gw_tls, "progress", None) is None
    with monitor.query("no_gw_span", mode="in-process"):
        # a monitored non-gateway query leaves no export target
        assert getattr(gateway._gw_tls, "progress", None) is None
    with gateway.query_span("scoped_gw"):
        shared = gateway._gw_tls.progress
        assert shared is not None and shared.armed
        with gateway.task_span("t0") as p:
            assert p is shared  # task spans share the query stage
    assert getattr(gateway._gw_tls, "progress", None) is None
    snap = monitor.snapshot()
    no_span = next(q for q in snap["queries"]
                   if q["query_id"] == "no_gw_span")
    assert no_span["stages"] == []  # no phantom stage


def test_udf_argument_export_not_counted_as_progress(armed_monitor):
    """Regression: UDF *argument* batches cross export_batch_ffi INSIDE
    the task drive — i.e. inside an active gateway span — and must not
    be counted as query output (a UDF projection over N rows would
    report ~2N).  udf_bridge.evaluate suppresses span accounting for
    its whole FFI round-trip."""
    import inspect

    from blaze_tpu import gateway
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.spark import udf_bridge

    schema = Schema([Field("v", DataType.int64())])
    b = batch_from_pydict({"v": [1, 2, 3]}, schema)
    with gateway.query_span("udf_gw"):
        progress = gateway._gw_tls.progress
        gateway._count_span_progress(b)        # the unsuppressed path
        assert progress.rows == 3
        # the evaluator's RESULT export counts like any other, so
        # evaluate suppresses the whole round-trip
        with gateway.suppressed_span_progress():
            gateway._count_span_progress(b)
        assert progress.rows == 3              # intermediates uncounted
        assert gateway._gw_tls.progress is progress  # span restored
    # the call site contract: evaluate's argument AND evaluator-result
    # exports are intermediates, not output
    src = inspect.getsource(udf_bridge.evaluate)
    assert "suppressed_span_progress" in src
    snap = monitor.snapshot()
    q = next(q for q in snap["queries"] if q["query_id"] == "udf_gw")
    assert q["stages"][0]["rows"] == 3


def test_retry_rolls_back_partial_attempt_progress(armed_monitor):
    """Regression: StageProgress is cumulative across a stage; a failed
    attempt's partially-drained batches must roll back or the retry
    re-counts them (rows doubled exactly in the failure scenarios the
    monitor exists to make trustworthy)."""
    from blaze_tpu.batch import batch_from_pydict

    schema = Schema([Field("v", DataType.int64())])
    batches = [batch_from_pydict({"v": [1, 2, 3]}, schema)
               for _ in range(3)]
    with monitor.query("retry_q", mode="scheduler"):
        progress = monitor.StageProgress(0, "broadcast", 1)
        assert progress.armed
        mark = progress.mark()
        for b in batches:        # attempt 0: drains 3 batches, fails
            progress.add_batch(b)
        progress.rollback(mark)
        for b in batches:        # attempt 1: succeeds
            progress.add_batch(b)
        progress.task_done()
        progress.flush(force=True)
    snap = monitor.snapshot()
    st = next(q for q in snap["queries"]
              if q["query_id"] == "retry_q")["stages"][0]
    assert st["rows"] == 9       # not 18
    assert st["batches"] == 3    # not 6
    assert st["tasks_done"] == 1
    # disarmed: both are one-attribute-read no-ops
    disarmed = monitor.StageProgress(0, "map", 1)
    disarmed.armed = False
    assert disarmed.mark() is None
    disarmed.rollback(None)


def test_failed_attempt_task_beat_is_discarded(armed_monitor):
    """Regression: a failed attempt's registry heartbeat must go with
    its rollback — a retry faster than the heartbeat interval never
    beats again, so the stale entry's rows would inflate task_rows
    (and /queries, --watch, blaze_query_stage_rows) forever."""
    with monitor.query("beat_rb_q", mode="scheduler"):
        monitor.stage_started(0, "map", 2)
        monitor.task_beat(0, 0, 0, rows=10_000, batches=3,
                          progress_rows=10_000, task_id="t0")
        monitor.task_discard(0, 0)        # scheduler rollback path
        monitor.task_beat(0, 1, 0, rows=5, batches=1, progress_rows=5,
                          task_id="t1")   # an unrelated healthy task
    snap = monitor.snapshot()
    st = next(q for q in snap["queries"]
              if q["query_id"] == "beat_rb_q")["stages"][0]
    assert "0" not in st["tasks"]         # the failed beat is gone
    assert st["task_rows"] == 5           # not 10005


def test_abandoned_stream_leaves_no_stale_task_beat(data, armed_monitor):
    """Regression: the instrumented task stream activates its
    heartbeat TLS only while the plan drive runs (inside next()), not
    across yields — abandoning a half-consumed result stream must not
    leave a stale callback that would cross-attribute the dead task's
    beats into the next query on this thread."""
    plan = build_query("q6", _scans(data), 2)
    stages, manager = split_stages(plan)
    with monitor.query("abandoned_q", mode="scheduler"):
        gen = run_stages(stages, manager)
        next(gen)  # partially consume, keep the reference (no GC)
        assert getattr(monitor._tls, "task_beat", None) is None
    gen.close()
    assert getattr(monitor._tls, "task_beat", None) is None


def test_disarmed_stage_span_registers_no_dispatch_capture():
    """Regression: with tracing and the monitor both off, stage_span
    (the session.execute / in-process CLI / gateway wrapper) must not
    register a dispatch capture nobody reads — per-dispatch capture
    updates on previously capture-free paths break the structural
    no-op contract.  The scheduler opts back in: its MetricNode
    publishes dispatch counters even with observability off."""
    from blaze_tpu.runtime import dispatch

    conf.MONITOR_ENABLE.set(False)
    monitor.reset()
    assert not monitor.enabled() and not trace.enabled()
    n0 = len(dispatch._CAPTURES)
    with monitor.stage_span(0, "result", 1) as p:
        assert p.counters is None
        assert len(dispatch._CAPTURES) == n0
    with monitor.stage_span(0, "result", 1, capture_dispatch=True) as p:
        assert isinstance(p.counters, dict)
        assert len(dispatch._CAPTURES) == n0 + 1
    assert len(dispatch._CAPTURES) == n0


def test_server_handler_threads_are_named_and_tracked(armed_monitor):
    """Regression: stdlib block_on_close tracks only NON-daemon
    threads, so with daemon handlers it joins nothing — the server
    tracks its own named handler threads and server_close joins them
    (a survivor shows up in monitor_threads() by name)."""
    import socketserver

    srv = monitor.ensure_server()
    _get(srv.url, "/healthz")
    assert any(t.name == "blaze-monitor-handler"
               for t in srv._httpd._handlers)
    # a scraper disconnect mid-response must not traceback-spam the
    # monitored workload's stderr (default handle_error prints one)
    assert (type(srv._httpd).handle_error
            is not socketserver.BaseServer.handle_error)
    monitor.shutdown_server()
    assert monitor.monitor_threads() == []


def test_healthz_and_404(armed_monitor):
    srv = monitor.ensure_server()
    status, _, body = _get(srv.url, "/healthz")
    assert status == 200 and json.loads(body)["status"] == "ok"
    with pytest.raises(urllib.error.HTTPError):
        _get(srv.url, "/nope")


def test_server_bind_conflict_falls_back_to_ephemeral(armed_monitor):
    """Regression: a bind failure on the configured port must not take
    down the monitored run — the server falls back to an ephemeral
    port (observability never kills the workload it observes)."""
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    taken = sock.getsockname()[1]
    try:
        conf.MONITOR_PORT.set(taken)
        monitor.reset()
        srv = monitor.ensure_server()
        assert srv is not None and srv.port != taken
        _get(srv.url, "/healthz")
    finally:
        sock.close()


def test_rerun_progress_does_not_clobber_stage_counters(armed_monitor):
    """Regression: the map-rerun path's StageProgress has no dispatch
    capture; its flushes must not overwrite the counters the original
    stage span recorded with an empty dict."""
    with monitor.query("rr_q", mode="scheduler"):
        monitor.stage_started(0, "map", 2)
        monitor.stage_progress_update(
            0, rows=10, bytes_=0, batches=1, tasks_done=1,
            counters={"xla_dispatches": 7})
        rerun = monitor.StageProgress(0, "map", 2)  # counters=None
        assert rerun.armed
        rerun.task_done()
        rerun.flush(force=True)
    snap = monitor.snapshot()
    st = next(q for q in snap["queries"]
              if q["query_id"] == "rr_q")["stages"][0]
    assert st["counters"] == {"xla_dispatches": 7}


def test_server_shutdown_leaves_no_threads(armed_monitor):
    srv = monitor.ensure_server()
    _get(srv.url, "/healthz")
    assert monitor.monitor_threads()
    monitor.shutdown_server()
    assert monitor.monitor_threads() == []
    # idempotent
    monitor.shutdown_server()


# ---------------------------------------------- 2. structural no-op

def test_monitor_off_is_structural_noop(data, monkeypatch):
    """With spark.blaze.monitor.enabled=false (default) a full
    scheduler run must never reach the registry writers, the heartbeat
    emitter, or the server — poisoned like the trace-off gate."""
    conf.MONITOR_ENABLE.set(False)
    conf.TRACE_ENABLE.set(False)
    monitor.reset()
    trace.reset()
    assert not monitor.enabled()

    def poisoned(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("monitor path entered while disarmed")

    # the registry writers and the heartbeat ticker must be
    # structurally unreachable (lifecycle sites may still CALL the
    # disarmed StageProgress methods — those return on one bool read)
    for fn in ("stage_started", "stage_finished", "stage_progress_update",
               "task_beat"):
        monkeypatch.setattr(monitor, fn, poisoned)
    monkeypatch.setattr(monitor._TaskBeatState, "tick", poisoned)

    stages, mgr = split_stages(build_query("q6", _scans(data), 2))
    rows = sum(b.num_rows for b in run_stages(stages, mgr))
    assert rows > 0
    assert monitor.counters() == {"updates": 0, "queries": 0}
    assert trace.counters() == {"events": 0, "spans": 0}
    assert monitor.server_port() is None
    assert monitor.monitor_threads() == []
    # the in-process gateway path is a no-op too
    sess, plan, n_rows = _slow_session(n_rows=100, n_batches=2, delay_s=0)
    assert len(sess.execute(plan)["v"]) == 100
    assert monitor.counters() == {"updates": 0, "queries": 0}


def test_stage_progress_disarmed_add_batch_is_cheap(data):
    """Disarmed StageProgress never materializes counters/heartbeat
    state — add_batch returns on the armed check alone."""
    conf.MONITOR_ENABLE.set(False)
    conf.TRACE_ENABLE.set(False)
    monitor.reset()
    trace.reset()
    p = monitor.StageProgress(0, "result", 1)
    assert not p.armed
    p.add_batch(object())  # would raise on .num_rows if armed
    p.task_done()
    p.flush(force=True)


# ------------------------------------------- 3. gateway-path spans

def _traced_events(tmp_path, fn, query_suffix=""):
    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    try:
        fn()
        # the query span restored the previous (None) path; find the
        # file the run wrote
        files = sorted(
            (os.path.join(tmp_path, f) for f in os.listdir(tmp_path)
             if query_suffix in f and f.endswith(".jsonl")),
            key=os.path.getmtime)
        return trace.read_event_log(files[-1])
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        trace.reset()


def test_session_execute_produces_query_stage_spans(data, tmp_path):
    """Acceptance: the non-scheduler session.execute path leaves a
    query -> stage -> kernel span tree in the event log."""
    sess, plan, n_rows = _slow_session(n_rows=200, n_batches=4, delay_s=0)

    def run():
        out = sess.execute(plan, query_id="gw_span_q")
        assert len(out["v"]) == n_rows

    events = _traced_events(tmp_path, run, "gw_span_q")
    types = [e["type"] for e in events]
    assert types[0] == "query_start" and types[-1] == "query_end"
    assert "stage_submit" in types and "stage_complete" in types
    comp = next(e for e in events if e["type"] == "stage_complete")
    assert comp["kind"] == "result" and comp["status"] == "ok"
    assert comp["programs"] >= 0 and "kernels" in comp
    schema = trace.load_schema()
    for e in events:
        jsonschema.validate(e, schema["events"][e["type"]])


def test_gateway_and_scheduler_reports_render_identically(data, tmp_path):
    """Acceptance: --report and --report --json render gateway-path
    logs with the same structure as scheduler-path logs (stage
    timeline present, same JSON stage keys)."""
    import contextlib
    import io

    from blaze_tpu.__main__ import main

    sess, plan, _ = _slow_session(n_rows=200, n_batches=4, delay_s=0)

    def run_gateway():
        sess.execute(plan, query_id="gw_report_q")

    def run_scheduler():
        with monitor.query_span("sched_report_q", mode="scheduler"):
            stages, mgr = split_stages(build_query("q6", _scans(data), 2))
            assert sum(b.num_rows for b in run_stages(stages, mgr)) > 0

    gw_dir = tmp_path / "gw"
    sched_dir = tmp_path / "sched"
    gw_dir.mkdir()
    sched_dir.mkdir()
    gw_events = _traced_events(gw_dir, run_gateway, "gw_report_q")
    sched_events = _traced_events(sched_dir, run_scheduler, "sched_report_q")

    docs = {}
    for label, events in (("gw", gw_events), ("sched", sched_events)):
        text = trace_report.render(events)
        assert "stage timeline" in text
        assert "device" in text and "dispatch" in text
        docs[label] = trace_report.render_json(events)
    assert set(docs["gw"]) == set(docs["sched"])
    for doc in docs.values():
        assert doc["stages"], "no stage rows in JSON profile"
    gw_keys = set(docs["gw"]["stages"][0])
    sched_keys = set(docs["sched"]["stages"][0])
    assert gw_keys == sched_keys
    # the CLI path: text + --json written from the same log
    gw_log = sorted((str(p) for p in gw_dir.iterdir()
                     if str(p).endswith(".jsonl")), key=os.path.getmtime)[-1]
    out_json = str(tmp_path / "profile.json")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(["--report", gw_log, "--json", out_json])
    assert rc == 0
    assert "stage timeline" in buf.getvalue()
    with open(out_json) as f:
        disk_doc = json.load(f)
    assert set(disk_doc) == set(docs["gw"])


# ------------------------------------------------- 4. heartbeats

def test_heartbeat_events_roundtrip_schema_from_real_run(data, tmp_path):
    """A traced scheduler run with a fast heartbeat produces
    stage_progress AND task_heartbeat events that validate against the
    golden schema, with monotone per-task rows."""
    conf.MONITOR_HEARTBEAT_MS.set(1)
    monitor.reset()
    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    try:
        with trace.query("hb_q1") as path:
            stages, mgr = split_stages(
                build_query("q1", _scans(data, 2, 4096), 2))
            assert sum(b.num_rows for b in run_stages(stages, mgr)) > 0
        events = trace.read_event_log(path)
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        trace.reset()
        conf.MONITOR_HEARTBEAT_MS.set(1000)
        monitor.reset()
    schema = trace.load_schema()
    beats = [e for e in events if e["type"] == "task_heartbeat"]
    progress = [e for e in events if e["type"] == "stage_progress"]
    assert beats, "no task_heartbeat events despite 1ms cadence"
    assert progress, "no stage_progress events despite 1ms cadence"
    for e in beats + progress:
        jsonschema.validate(e, schema["events"][e["type"]])
    # map-task heartbeats carry operator metrics even with zero
    # driver-yielded rows
    map_beats = [e for e in beats if e["rows"] == 0]
    assert any(e["metrics"].get("output_rows", 0) > 0 for e in map_beats)
    # progress_rows = widest single node <= tree-summed output_rows
    for e in beats:
        assert 0 <= e["progress_rows"] <= e["metrics"].get("output_rows", 0)
    # per-(stage, task, attempt) heartbeat metrics are monotone
    by_task = {}
    for e in beats:
        key = (e["stage_id"], e["partition"], e["attempt"])
        prev = by_task.get(key, -1)
        cur = e["metrics"].get("output_rows", 0)
        assert cur >= prev, f"heartbeat regressed for {key}"
        by_task[key] = cur


def test_heartbeat_cadence_is_bounded(data, tmp_path):
    """At the default 1000ms cadence this fast q6 run emits (almost)
    no heartbeats — the events are interval-gated, not per-batch."""
    conf.MONITOR_HEARTBEAT_MS.set(60000)
    monitor.reset()
    conf.TRACE_ENABLE.set(True)
    conf.EVENT_LOG_DIR.set(str(tmp_path))
    trace.reset()
    try:
        with trace.query("fast_q6") as path:
            stages, mgr = split_stages(build_query("q6", _scans(data), 2))
            assert sum(b.num_rows for b in run_stages(stages, mgr)) > 0
        events = trace.read_event_log(path)
    finally:
        conf.TRACE_ENABLE.set(False)
        conf.EVENT_LOG_DIR.set("")
        trace.reset()
        conf.MONITOR_HEARTBEAT_MS.set(1000)
        monitor.reset()
    assert not [e for e in events if e["type"] == "task_heartbeat"]
    # stage_progress still appears exactly once per stage: the forced
    # final flush on stage close
    prog = [e for e in events if e["type"] == "stage_progress"]
    stages_seen = {e["stage_id"] for e in prog}
    assert len(prog) == len(stages_seen)


# ------------------------------------- 5. metric-name golden registry

def _source_metric_literals():
    """Every metric-name string literal in blaze_tpu source: first-arg
    literals of MetricsSet.add/set/timer and dispatch.record/record_max
    (+ counter= kwargs), plus the histogram/timer observation sites
    (observe_hist / record_timer) that carry full family names, plus
    what a host span tallies: ``trace.span("x")`` / ``record_span("x")``
    give ``x_ns`` and ``x_n``, ``record_span("x", ns, y=v, z=w)`` ``y``
    and ``z``, and ``tally="x"`` (a hand-over of ``pipelined``)
    ``x_wait_ns`` / ``_n``, ``x_full_ns`` / ``_n``, ``x_items`` and
    ``x_producer_ns``."""
    names = set()
    hist_re = re.compile(
        r'(?:observe_hist|record_timer)\(\s*"([a-z][a-z_0-9]*)"')
    pkg = os.path.join(REPO, "blaze_tpu")
    for root, _, files in os.walk(pkg):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            with open(os.path.join(root, fname)) as f:
                src = f.read()
            for m in hist_re.finditer(src):
                names.add(m.group(1))
            if fname == "monitor.py":
                # its _PromDoc.add calls carry derived FAMILY names
                # (blaze_query_*...), not tree metric names — EXCEPT
                # the fleet/SLO gauge families, which are registered
                # verbatim (worker_gauges / pool_gauges / slo_gauges),
                # and the runtime-stats drift gauges (stats_gauges)
                for m in re.finditer(
                        r'\.add\(\s*"(blaze_(?:worker|pool|slo|'
                        r'query_qerror|stage_skew)_'
                        r'[a-z_0-9]*)"', src):
                    names.add(m.group(1))
                continue
            for m in re.finditer(
                    r'(?:\.(?:add|set|timer)\(|record\(|record_max\(|counter=)'
                    r'\s*"([a-z][a-z_0-9]*)"', src):
                names.add(m.group(1))
            for m in re.finditer(
                    r'\b(?:span|record_span)\(\s*"([a-z][a-z_0-9]*)"', src):
                names.update((m.group(1) + "_ns", m.group(1) + "_n"))
            for m in re.finditer(r'\brecord_span\("[a-z_]+",([^)]*)\)', src):
                names.update(re.findall(r'\b([a-z][a-z_0-9]*)=(?!=)', m.group(1)))
            # a hand-over of runtime/pipeline.pipelined tallies under its
            # ``tally`` name: two wait spans and two counters
            for m in re.finditer(r'\btally(?:: str)? ?= ?"([a-z][a-z_0-9]*)"', src):
                names.update(m.group(1) + tail for tail in (
                    "_wait_ns", "_wait_n", "_full_ns", "_full_n", "_items", "_producer_ns"))
    return names


def test_metric_names_registry_covers_source_literals():
    """Drift gate, way 1: every metric-name literal recorded anywhere
    in the source must be registered — a NEW metric lands in
    metric_names.json or fails tier-1."""
    registered = registered_metric_names()
    unregistered = _source_metric_literals() - registered
    assert not unregistered, (
        f"unregistered metric names (add them to "
        f"runtime/metric_names.json): {sorted(unregistered)}")


def test_metric_names_registry_has_no_stale_entries():
    """Drift gate, way 2: every registered name still appears as a
    literal in the source — a silent rename leaves a stale registry
    entry and fails tier-1 (dashboards keyed on the old name break)."""
    stale = registered_metric_names() - _source_metric_literals()
    assert not stale, (
        f"registered metric names no longer produced anywhere "
        f"(renamed without updating runtime/metric_names.json?): "
        f"{sorted(stale)}")


def test_metric_tree_names_are_registered_at_runtime(data):
    """Dynamic subset check: every name a real scheduler run lands in
    the MetricNode tree (operator metrics + mirrored dispatch
    counters) is registered."""
    from blaze_tpu.runtime import scheduler

    stages, mgr = split_stages(build_query("q1", _scans(data), 2))
    assert sum(b.num_rows for b in run_stages(stages, mgr)) > 0
    registered = registered_metric_names()
    flat = scheduler.LAST_RUN_METRICS.flatten()
    produced = {k.split(":", 1)[1] for k in flat}
    assert produced, "no metrics produced"
    unknown = produced - registered
    assert not unknown, f"unregistered runtime metric names: {sorted(unknown)}"


def test_metric_names_registry_shape():
    from blaze_tpu.runtime.metrics import load_metric_names

    reg = load_metric_names()
    assert {"operator_metrics", "scheduler_counters",
            "dispatch_counters"} <= set(reg)
    flat = registered_metric_names()
    assert "output_rows" in flat and "xla_dispatches" in flat


# --------------------------------------------- 6. --report --json keys

GOLDEN_TOP_KEYS = {"query", "events", "stages", "totals", "kernels",
                   "plans", "data_movement", "memory", "recovery",
                   "progress"}
GOLDEN_STAGE_KEYS = {"stage_id", "kind", "n_tasks", "status", "start_s",
                     "wall_ns", "programs", "device_time_ns",
                     "dispatch_overhead_ns", "compile_ns", "counters"}
GOLDEN_KERNEL_KEYS = {"programs", "device_ns", "device_ns_scaled",
                      "dispatch_ns", "compile_ns", "timed", "sampled"}


def test_report_json_golden_keys(data, tmp_path):
    """The JSON profile shape is API for dashboards: pinned top-level,
    per-stage, and per-kernel keys (add keys freely, never rename)."""
    def run():
        with monitor.query_span("json_q1", mode="scheduler"):
            stages, mgr = split_stages(build_query("q1", _scans(data), 2))
            assert sum(b.num_rows for b in run_stages(stages, mgr)) > 0

    events = _traced_events(tmp_path, run, "json_q1")
    doc = trace_report.render_json(events)
    assert GOLDEN_TOP_KEYS <= set(doc)
    for s in doc["stages"]:
        assert GOLDEN_STAGE_KEYS <= set(s)
    assert doc["kernels"], "no kernel table"
    for v in doc["kernels"].values():
        assert GOLDEN_KERNEL_KEYS <= set(v)
    assert doc["query"]["ids"] == ["json_q1"]
    assert doc["recovery"]["reconciled"] is True
    assert doc["totals"]["wall_ns"] > 0
    # the document is JSON-serializable as-is
    json.dumps(doc)


def test_report_json_recovery_section(tmp_path):
    events = [
        {"ts": 1.0, "type": "fault_injected", "site": "task.compute",
         "hit": 1, "attempt": 0},
        {"ts": 2.0, "type": "task_retry", "stage_id": 0, "task": 0,
         "attempt": 1, "reason": "InjectedFault"},
    ]
    doc = trace_report.render_json(events)
    assert doc["recovery"]["injected"] == 1
    assert doc["recovery"]["recoveries"] == 1
    assert doc["recovery"]["reconciled"] is True
    assert doc["recovery"]["incidents"][0]["type"] == "fault_injected"


# ------------------------------------------------- 7. CLI + watch

def test_render_watch_table():
    snap = {
        "ts": 0.0,
        "queries": [{
            "query_id": "tpch_q1", "mode": "scheduler", "status": "running",
            "started_at": 0.0, "elapsed_s": 3.2, "heartbeat_age_s": 0.1,
            "attempts": {"task_attempts": 5, "task_retries": 1,
                         "fetch_failures": 0},
            "mem_peak_bytes": 1024,
            "stages": [
                {"stage_id": 0, "kind": "map", "status": "ok", "n_tasks": 2,
                 "tasks_done": 2, "rows": 0, "bytes": 0, "batches": 0,
                 "task_rows": 123456, "tasks": {},
                 "counters": {"xla_dispatches": 34},
                 "elapsed_s": 2.1, "heartbeat_age_s": 0.1},
                {"stage_id": 1, "kind": "result", "status": "running",
                 "n_tasks": 1, "tasks_done": 0, "rows": 42, "bytes": 2048,
                 "batches": 1, "task_rows": 42, "tasks": {},
                 "counters": {}, "elapsed_s": 1.0, "heartbeat_age_s": 5.0},
            ],
        }],
        "memory": {"used": 512, "total": 4096},
    }
    out = monitor.render_watch(snap, "http://127.0.0.1:9")
    assert "tpch_q1" in out and "RUNNING" in out
    assert "123,456" in out          # map progress from task heartbeats
    assert "attempts 5 retries 1" in out
    assert "5.0s" in out             # the wedge detector column
    empty = monitor.render_watch({"queries": [], "memory": {}})
    assert "no queries" in empty


def test_watch_cli_polls_live_server(armed_monitor, capsys):
    from blaze_tpu.__main__ import _watch

    srv = monitor.ensure_server()
    with monitor.query_span("watch_q", mode="in-process"):
        pass
    rc = _watch(str(srv.port), interval=0.01, polls=2)
    assert rc == 0
    out = capsys.readouterr().out
    assert "watch_q" in out or "queries 1" in out


def test_watch_cli_unreachable():
    from blaze_tpu.__main__ import _watch

    rc = _watch("http://127.0.0.1:1", interval=0.01, polls=1)
    assert rc == 1


def test_json_without_report_is_a_usage_error(capsys):
    from blaze_tpu.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main(["tpch", "q6", "--json", "/tmp/out.json"])
    assert exc.value.code == 2
    assert "--json requires --report" in capsys.readouterr().err


def test_chaos_cli_with_monitor_shuts_down_cleanly(data):
    """Satellite: --chaos --monitor runs the fault smoke with the
    monitor armed and asserts the server shut down without leaking a
    thread (exit 0 = chaos reconciled AND clean shutdown)."""
    from blaze_tpu.__main__ import main

    before = len(monitor.monitor_threads())
    assert before == 0
    rc = main(["tpch", "q6", "--chaos", "--monitor", "--monitor-port", "0",
               "--scale", "0.002", "--parts", "2", "--chaos-faults", "2"])
    assert rc == 0
    assert monitor.monitor_threads() == []
    conf.MONITOR_ENABLE.set(False)
    monitor.reset()
