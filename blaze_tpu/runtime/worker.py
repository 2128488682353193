"""Standalone task worker: one process = one task attempt.

≙ a Spark executor running one task of a Blaze stage
(``BlazeCallNativeWrapper`` decoding TaskDefinition bytes +
``BlazeBlockStoreShuffleReaderBase`` registering fetched blocks): the
worker re-creates the shuffle manager over the SHARED shuffle root,
registers its partition's reduce blocks in the resources map, decodes
the TaskDefinition, drives the plan, and (for result stages) writes
output batches as length-prefixed serde frames for the driver.

Job spec (JSON file, path in argv[1]):

    {"task_def": "<base64 TaskDefinition bytes>",
     "partition": N,
     "attempt": 0,
     "shuffle_root": "/dir/shared/across/workers",
     "readers": [{"resource_id": "shuffle_7", "shuffle_id": 7, "n_maps": 3}],
     "output": "/path/result.frames" | null}

Crash-safety contract with the driver: the result file is written to
``<output>.inprogress`` and renamed into place only after the plan
drains completely, so a worker that dies mid-task (nonzero exit, OOM
kill, injected fault) leaves either nothing or a complete file — never
a silently-truncated frame sequence.  :func:`run_worker_with_retry` is
the driver half: it spawns the worker, detects nonzero exit / missing
output, and re-attempts under the task retry policy with a fresh
attempt id (fault injection via ``BLAZE_FAULTS_SPEC`` reaches the
worker through the environment; attempt-gated specs — ``@a0`` — make a
crashed first attempt recover deterministically).

Observability: with ``BLAZE_TRACE_ENABLED`` in the environment the
worker's ``run_task`` stream emits ``task_heartbeat`` events into the
worker's own event log (runtime/trace.py default path).  The LIVE
monitor (runtime/monitor.py) is deliberately disarmed in workers — the
driver owns the registry and the /metrics server; a task subprocess
has nobody to serve.

Used by the multi-process testenv suite (tests/test_testenv.py) — the
repo's analogue of the reference's ``dev/testenv`` pseudo-distributed
sandbox (SURVEY §4 tier 3).

Pooled mode: ``python -m blaze_tpu.runtime.worker --serve`` turns the
one-shot worker into a LONG-LIVED pool member (runtime/hostpool.py is
the driver half).  Job specs arrive as checksummed IPC frames (the
PR 13 wire format: ``io/ipc_compression.py`` raw-codec frames with the
per-frame trailer) carrying JSON on stdin; replies — ``ready``,
periodic ``hb`` heartbeats at ``spark.blaze.pool.heartbeatMs``, and
per-job ``done`` records — go back the same way on stdout.  A failed
job serializes its TYPED identity (class name, ``retry.classify``
disposition, and FetchFailedError's resource/map-id fields) so the
driver reconstructs a real typed error instead of guessing from an
exit status; the process keeps serving.  fd 1 is re-pointed at stderr
once the protocol stream is claimed, so stray library prints can never
corrupt the frame stream.
"""

from __future__ import annotations

import base64
import json
import struct
import sys


def _configure_worker_process() -> None:
    """One-time worker-process setup shared by the one-shot and
    ``--serve`` modes: JAX platform config, live-monitor disarm, and
    trace-context restore from ``BLAZE_TRACEPARENT``."""
    import os

    import jax

    # honor the launcher's JAX_PLATFORMS; with none set a worker is
    # HOST-SIDE (cpu): a chip belongs to one process, and the driver
    # that spawned this worker holds it
    jax.config.update(
        "jax_platforms", os.environ.get("JAX_PLATFORMS") or "cpu"
    )
    jax.config.update("jax_enable_x64", True)

    from . import monitor

    # one process = one task attempt: the DRIVER owns the live monitor
    # (registry + /metrics server); a task subprocess inheriting
    # BLAZE_MONITOR_ENABLED must not pay the registry path for a
    # registry nobody serves.  Tracing is unaffected: with
    # BLAZE_TRACE_ENABLED set, run_task's instrumented stream still
    # heartbeats task progress into this worker's own event log.
    os.environ.pop("BLAZE_MONITOR_ENABLED", None)
    from .. import conf

    conf.MONITOR_ENABLE.set(False)
    monitor.reset()

    # cross-process compile-cache inheritance: this process resolves
    # the same directory as its driver (inherited
    # JAX_COMPILATION_CACHE_DIR, the host pool's forwarded
    # BLAZE_XLA_CACHEDIR alias of spark.blaze.xla.cacheDir, or the
    # in-checkout default), so a cache primed by ``--warmup`` serves
    # this process's cold compiles as deserializations instead of
    # fresh XLA compiles.
    from .kernel_cache import enable_persistent_cache

    enable_persistent_cache()

    # cross-process trace-context propagation: the driver's W3C
    # traceparent (BLAZE_TRACEPARENT — run_worker_with_retry and the
    # host pool set it; a job spec's own key wins later) restores the
    # SAME trace id in this subprocess, so the heartbeat/kernel events
    # landing in the worker's own event log reconcile with the
    # driver's segments into one distributed trace
    # (trace_report.merge_event_logs, the OTLP export).  A malformed
    # value degrades to an uncorrelated log, never a dead worker.
    from . import trace

    tp = str(os.environ.get("BLAZE_TRACEPARENT", "") or "")
    ctx = trace.parse_traceparent(tp) if tp else None
    if ctx is not None:
        trace.set_trace_context(*ctx)


def _execute_spec(spec: dict) -> dict:
    """Run ONE job spec to completion in this process: register the
    reduce-block readers, decode the TaskDefinition, drive the plan,
    and (result stages) commit the output frames by atomic rename.
    Shared by the one-shot :func:`main` and the pooled :func:`serve`
    loop.  The ``worker.task`` fault site is probed at job start and
    per output batch — the ``@kill`` modifier's home turf.  Returns
    the job's output tallies (``rows`` produced, serialized result
    ``bytes``) — the pooled serve loop folds them into the telemetry
    payloads its heartbeats carry back to the driver."""
    import os

    from ..io.batch_serde import serialize_batch
    from ..parallel.shuffle import LocalShuffleManager
    from ..serde.from_proto import run_task
    from . import faults
    from .context import RESOURCES, current_cancel_scope

    partition = int(spec["partition"])
    attempt = int(spec.get("attempt", 0))
    tp = str(spec.get("traceparent") or "")
    if tp:
        from . import trace

        ctx = trace.parse_traceparent(tp)
        if ctx is not None:
            trace.set_trace_context(*ctx)
    faults.hit("worker.task", attempt=attempt, detail=f"p{partition}")
    staged_keys = []
    if spec.get("readers"):
        mgr = LocalShuffleManager(spec["shuffle_root"])
        for r in spec["readers"]:
            key = f"{r['resource_id']}.{partition}"
            RESOURCES.put(
                key,
                mgr.reduce_blocks(int(r["shuffle_id"]), int(r["n_maps"]), partition),
            )
            staged_keys.append(key)
    td = base64.b64decode(spec["task_def"])
    out_path = spec.get("output")
    rows = 0
    out_bytes = 0
    try:
        if out_path:
            # write-then-rename: a crashed attempt leaves no final
            # file, so the driver's partial-output detection is just
            # existence.  Frames are standard checksummed IPC frames
            # (codec raw + per-frame trailer, conf
            # spark.blaze.io.checksum) closed by a block trailer, so
            # the DRIVER verifies the committed bytes
            # (verify_result_file) before trusting them — rename alone
            # proves completeness, not integrity.
            from . import integrity
            from ..io.ipc_compression import block_trailer, compress_frame

            algo = integrity.frame_algo()
            # ATTEMPT-QUALIFIED temp (the shuffle writers' contract,
            # was a bare .inprogress): a wedge-respawned attempt racing
            # a not-yet-dead predecessor process no longer interleaves
            # writes into ONE shared temp — with checksums off that
            # interleaving committed silently torn frames.  Surfaced by
            # the commit.guard / resource-ledger audit
            # (analysis/errflow.py).
            tmp = out_path + f".inprogress.a{attempt}"
            count = 0
            xor = 0
            try:
                with open(tmp, "wb") as f:
                    for batch in run_task(td, task_attempt_id=attempt):
                        faults.hit("worker.task", attempt=attempt,
                                   detail=f"p{partition}#batch")
                        frame = compress_frame(serialize_batch(batch),
                                               codec="raw",
                                               checksum_algo=algo)
                        if algo is not None:
                            xor ^= struct.unpack("<BI", frame[-5:])[1]
                        f.write(frame)
                        count += 1
                        rows += int(getattr(batch, "num_rows", 0) or 0)
                        out_bytes += len(frame)
                    if algo is not None:
                        f.write(block_trailer(count, xor, algo))
            except BaseException:
                # a failed attempt's temp used to survive until the
                # age-gated orphan sweep (resource.path-leak class):
                # the driver only checks the FINAL path, so unlink the
                # staging debris before the failure propagates
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            if faults.corrupt("worker.result", attempt=attempt,
                              detail=out_path):
                # @corrupt: post-write bit-rot on the committed result
                # — the driver's verification, not this worker, must
                # catch it
                integrity.flip_byte_in_file(tmp)
            # commit guard: a cancel landing between the drain loop and
            # the rename must not promote the loser's temp over output
            # a winner may re-commit — raise here and the BaseException
            # arm below unlinks the staging debris instead.  In a
            # subprocess the ambient scope is absent (the driver kills
            # the process group at its own cancel checkpoint); this
            # covers in-process callers and keeps the rename behind a
            # cancellation check.
            scope = current_cancel_scope()
            if scope is not None:
                scope.raise_cancelled()
            os.replace(tmp, out_path)
        else:
            for batch in run_task(td, task_attempt_id=attempt):
                faults.hit("worker.task", attempt=attempt,
                           detail=f"p{partition}#batch")
                rows += int(getattr(batch, "num_rows", 0) or 0)
    except BaseException:
        # a failed job must not leave its reader registrations staged:
        # a long-lived serve worker re-registers the same keys on the
        # retried job (RESOURCES.get pops, so only the FAILED path
        # leaks them)
        for key in staged_keys:
            RESOURCES.discard(key)
        raise
    return {"rows": rows, "bytes": out_bytes}


def _describe_error(exc: BaseException) -> dict:
    """Serialize a job failure's TYPED identity for the driver: class
    name, ``retry.classify`` disposition, message, and — for
    ``FetchFailedError`` — the resource/partition/map-id fields the
    partial-rerun path needs to rebuild a REAL fetch failure on the
    driver side.  ``QueryCancelledError`` carries its query id/reason
    so a cancelled worker job round-trips as the same terminal error."""
    from .context import QueryCancelledError
    from .retry import FetchFailedError, classify

    d = {
        "error_type": type(exc).__name__,
        "disposition": classify(exc),
        "message": str(exc)[:500],
    }
    if isinstance(exc, FetchFailedError):
        d["resource_id"] = exc.resource_id
        d["partition"] = exc.partition
        if exc.map_ids is not None:
            d["map_ids"] = list(exc.map_ids)
    if isinstance(exc, QueryCancelledError):
        d["query_id"] = exc.query_id
        d["reason"] = getattr(exc, "reason", "cancel")
    return d


def exit_record_path(spec_path: str) -> str:
    return spec_path + ".exit.json"


def _write_exit_record(spec_path: str, exc: BaseException) -> None:
    """Persist the one-shot worker's typed failure next to its spec so
    the driver (:func:`run_worker_with_retry`) can route the exit
    through ``retry.classify`` instead of blindly re-spawning — the
    FATAL-respawn fix: a ``QueryCancelledError`` serialized back from
    the worker must not burn retry attempts resurrecting a cancelled
    query.  Write-then-rename so the driver never reads a torn
    record; best-effort (a worker that cannot write still exits
    nonzero and the driver falls back to exit-status classing)."""
    import os

    tmp = exit_record_path(spec_path) + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(_describe_error(exc), f)
        os.replace(tmp, exit_record_path(spec_path))
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def read_exit_record(spec_path: str) -> dict | None:
    """Driver side: the worker's typed exit record, or None when the
    worker died without writing one (SIGKILL, crash before the except
    handler)."""
    try:
        with open(exit_record_path(spec_path)) as f:
            rec = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    return rec if isinstance(rec, dict) else None


def main(spec_path: str) -> int:
    _configure_worker_process()
    with open(spec_path) as f:
        spec = json.load(f)
    try:
        _execute_spec(spec)
    except BaseException as e:
        _write_exit_record(spec_path, e)
        raise
    return 0


#: telemetry payload protocol version the serve loop speaks: ``hb`` /
#: ``done`` frames carrying telemetry stamp ``"v": TELEMETRY_VERSION``
#: next to the ``"tm"`` delta dict.  The driver folds only versions it
#: knows; an OLD worker sending bare payload-free frames (no ``v``)
#: still interops — liveness and job routing never depended on ``tm``.
TELEMETRY_VERSION = 1


def serve() -> int:
    """Long-lived pooled-worker loop (driven by runtime/hostpool.py):
    read framed JSON job specs from stdin, execute each via
    :func:`_execute_spec`, and reply with framed JSON ``done`` records
    — a failed job serializes its typed identity and the process KEEPS
    SERVING.  A daemon heartbeat thread emits ``hb`` frames every
    ``spark.blaze.pool.heartbeatMs`` so the driver's liveness layer
    distinguishes a busy worker from a dead one.  EOF on stdin (or a
    ``shutdown`` message) ends the loop.

    Telemetry: every ``hb``/``done`` frame carries an INCREMENTAL
    payload (``v``/``tm`` keys — dispatch-counter deltas, rows/bytes
    produced, jobs ok/failed, kernel device/dispatch/compile splits
    when tracing is armed, the mem watermark, and this worker's
    event-log path) so the driver's monitor registry aggregates the
    fleet without a second channel.  A frame whose delta is empty is
    sent in the OLD payload-free shape — the version-gate path an old
    worker binary exercises permanently."""
    import os
    import threading

    _configure_worker_process()

    from .. import conf
    from ..io.ipc_compression import IpcFrameReader, compress_frame
    from . import dispatch, integrity, trace
    from . import monitor as _monitor

    # claim the REAL stdout fd for the framed protocol and re-point
    # fd 1 at stderr: a stray print from any library would otherwise
    # land mid-frame and corrupt the stream
    proto = os.fdopen(os.dup(1), "wb", buffering=0)
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    algo = integrity.frame_algo()
    wlock = threading.Lock()

    def send(obj: dict) -> None:
        frame = compress_frame(json.dumps(obj).encode(), codec="raw",
                               checksum_algo=algo)
        with wlock:
            proto.write(frame)

    # --- incremental telemetry state: cumulative tallies plus the
    # last-SENT snapshot; each frame carries only the delta, so the
    # driver folds additively and a dropped worker loses at most one
    # heartbeat's worth.  mem_peak rides as an absolute (driver keeps
    # the max); the event-log path rides once per change.
    tlock = threading.Lock()
    tally = {"rows": 0, "bytes": 0, "jobs_ok": 0, "jobs_failed": 0,
             "device_ns": 0, "dispatch_ns": 0, "compile_ns": 0}
    sent = dict(tally)
    sent_counters: dict = {}
    sent_mem = -1
    sent_log = ""

    def _telemetry() -> dict | None:
        """The incremental ``tm`` payload since the last frame that
        carried one, or None when nothing changed (the frame then goes
        out in the old payload-free shape)."""
        nonlocal sent, sent_counters, sent_mem, sent_log
        cur = dispatch.counters()
        mem = _monitor._mem_used()
        log = trace.current_path() or ""
        with tlock:
            tm: dict = {}
            dc = {k: v - sent_counters.get(k, 0) for k, v in cur.items()
                  if v - sent_counters.get(k, 0)}
            if dc:
                tm["counters"] = dc
            for k in tally:
                d = tally[k] - sent[k]
                if d:
                    tm[k] = d
            if mem != sent_mem:
                tm["mem_peak"] = mem
            if log and log != sent_log:
                tm["eventlog"] = log
            if not tm:
                return None
            sent = dict(tally)
            sent_counters = dict(cur)
            sent_mem = mem
            if log:
                sent_log = log
            return tm

    def _stamp(msg: dict) -> dict:
        tm = _telemetry()
        if tm is not None:
            msg["v"] = TELEMETRY_VERSION
            msg["tm"] = tm
        return msg

    hb_s = max(0.005, int(conf.POOL_HEARTBEAT_MS.get()) / 1000.0)
    stop = threading.Event()

    def _beat() -> None:
        while not stop.wait(hb_s):
            try:
                send(_stamp({"t": "hb", "pid": os.getpid()}))
            except OSError:
                return  # driver went away; the job loop sees EOF too

    threading.Thread(target=_beat, daemon=True,
                     name=f"blaze-pool-beat-{os.getpid()}").start()
    send({"t": "ready", "pid": os.getpid()})
    try:
        for payload in IpcFrameReader(sys.stdin.buffer, site="pool.frame"):
            msg = json.loads(payload.decode())
            if msg.get("t") == "shutdown":
                break
            job_id = msg.get("job_id")
            try:
                # kernel split attribution only when tracing is armed:
                # an active capture device-serializes execution (the
                # stage_span contract), so the untraced pool stays on
                # the async path
                if trace.enabled():
                    with trace.kernel_capture() as sink:
                        out = _execute_spec(msg)
                    ksum = trace.sum_kernels(sink)
                else:
                    out = _execute_spec(msg)
                    ksum = None
            except BaseException as e:
                with tlock:
                    tally["jobs_failed"] += 1
                reply = _stamp({"t": "done", "job_id": job_id,
                                "status": "error", "pid": os.getpid()})
                reply.update(_describe_error(e))
                send(reply)
                if isinstance(e, (KeyboardInterrupt, SystemExit,
                                  GeneratorExit)):
                    raise
            else:
                with tlock:
                    tally["jobs_ok"] += 1
                    tally["rows"] += int(out.get("rows", 0))
                    tally["bytes"] += int(out.get("bytes", 0))
                    if ksum is not None:
                        tally["device_ns"] += ksum["device_time_ns"]
                        tally["dispatch_ns"] += ksum["dispatch_overhead_ns"]
                        tally["compile_ns"] += ksum["compile_ns"]
                send(_stamp({"t": "done", "job_id": job_id, "status": "ok",
                             "pid": os.getpid()}))
    finally:
        stop.set()
    return 0


def read_result_frames(path: str, schema=None):
    """Read a worker's committed result file: yields decoded serde
    frame payloads (or deserialized batches when ``schema`` is given),
    verifying per-frame checksums and the block trailer — typed
    ``BlockCorruptionError`` on any mismatch.  The ONE reader the
    driver, the testenv suites, and :func:`verify_result_file`
    share."""
    from ..io.batch_serde import deserialize_batch
    from ..io.ipc_compression import IpcFrameReader

    with open(path, "rb") as f:
        for payload in IpcFrameReader(f, site="worker.result", path=path):
            yield deserialize_batch(payload, schema) if schema is not None \
                else payload


def verify_result_file(path: str) -> int:
    """Driver-side integrity gate on a committed worker result: walk
    every frame (checksums + block trailer) without keeping payloads.
    Returns the frame count; raises ``BlockCorruptionError`` on
    corruption — the caller treats it as a failed attempt and retries
    with fresh output."""
    n = 0
    for _ in read_result_frames(path):
        n += 1
    return n


def run_worker_with_retry(
    spec: dict,
    spec_dir: str,
    tag: str,
    max_attempts: int | None = None,
    env: dict | None = None,
    timeout: float = 300.0,
):
    """Driver-side fault-tolerant worker launch (the testenv analogue
    of the in-process scheduler's task retry loop).

    Spawns ``python -m blaze_tpu.runtime.worker`` on ``spec`` (in its
    OWN process group) and re-attempts — with a fresh attempt id in the
    spec, so attempt-gated fault schedules and TaskContext attempt ids
    stay truthful — when the process exits nonzero OR the promised
    output file is missing (a worker killed before the atomic rename).
    Raises ``TaskRetriesExhausted`` after the budget, naming the last
    exit status.  Returns the completed attempt number.

    Cancellation: the poll loop is a cooperative checkpoint on the
    ambient :class:`CancelScope` — a cancelled query TERMINATES the
    worker's process group (SIGTERM, then SIGKILL), sweeps its
    ``.inprogress.a<N>`` staging temp, accounts the kill
    (``worker_kills`` dispatch counter + resource ledger), and raises
    the typed cancel error.  Previously the driver blocked in
    ``subprocess.run`` and a cancelled query's worker computed to
    completion.

    Typed exits: a worker that fails CLEANLY writes a
    ``<spec>.exit.json`` record (class name + ``retry.classify``
    disposition); a FATAL-classified record (e.g. a
    ``QueryCancelledError`` serialized back from the worker) raises
    immediately instead of burning the retry budget re-running a
    deterministic terminal failure.
    """
    import glob
    import os
    import subprocess
    import time as _time

    from . import dispatch, ledger, trace
    from .context import QueryCancelledError, current_cancel_scope
    from .retry import FATAL, RetryPolicy, TaskRetriesExhausted

    policy = RetryPolicy.from_conf()
    if max_attempts is not None:
        policy = policy.with_max_attempts(max_attempts)
    run_env = dict(os.environ)
    if env:
        run_env.update(env)
    run_env.setdefault("JAX_PLATFORMS", "cpu")
    # thread the driver's trace context into the worker (spec key wins,
    # then the driver's ambient traced-query span) so every attempt's
    # subprocess events carry the same trace id
    tp = str(spec.get("traceparent") or "") or trace.current_traceparent()
    if tp:
        run_env.setdefault("BLAZE_TRACEPARENT", tp)

    out_path = spec.get("output")

    def _sweep_inprogress() -> None:
        # a KILLED worker (cancel, timeout, OOM kill) could not run its
        # own temp cleanup: sweep the attempt's .inprogress staging
        # debris driver-side (the worker-side unlink covers clean
        # failures; this covers the crash edge)
        if out_path:
            for stale in glob.glob(out_path + ".inprogress*"):
                try:
                    os.unlink(stale)
                except OSError:
                    pass

    last_failure: Exception | None = None
    for attempt in range(policy.max_attempts):
        spec_attempt = dict(spec, attempt=attempt)
        spec_path = os.path.join(spec_dir, f"spec_{tag}_a{attempt}.json")
        with open(spec_path, "w") as f:
            json.dump(spec_attempt, f)
        stderr_tail = ""
        reason = None
        scope = current_cancel_scope()
        # start_new_session: the worker leads its own process group so
        # a cancel kills it AND any children it spawned in one signal
        proc = subprocess.Popen(
            [sys.executable, "-m", "blaze_tpu.runtime.worker", spec_path],
            env=run_env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            start_new_session=True,
        )
        proc_key = f"worker_proc:{tag}:a{attempt}"
        ledger.acquire("scoped", proc_key)
        deadline = _time.monotonic() + timeout
        try:
            while True:
                try:
                    # communicate (not wait) drains the pipes, so a
                    # chatty worker can never deadlock on a full pipe
                    _, stderr_b = proc.communicate(timeout=0.05)
                    stderr_tail = (stderr_b or b"").decode(
                        errors="replace")[-500:]
                    break
                except subprocess.TimeoutExpired:
                    pass
                if scope is not None and scope.cancelled:
                    # the cancel checkpoint: the subprocess cannot see
                    # the driver's scope event, so reach it by signal
                    terminate_process_group(proc)
                    proc.communicate()
                    _sweep_inprogress()
                    dispatch.record("worker_kills")
                    scope.raise_cancelled()
                if _time.monotonic() > deadline:
                    # a wedged worker: kill the group, count one
                    # failed attempt like any crash
                    terminate_process_group(proc)
                    _, stderr_b = proc.communicate()
                    stderr_tail = (stderr_b or b"").decode(
                        errors="replace")[-500:]
                    reason = f"hung past {timeout}s and was killed"
                    break
        finally:
            ledger.release("scoped", proc_key)
        if reason is None:
            if proc.returncode == 0 and (not out_path
                                         or os.path.exists(out_path)):
                if not out_path:
                    return attempt
                # the committed file exists — but rename proves only
                # COMPLETENESS.  Verify the bytes (per-frame checksums
                # + block trailer) before trusting them: a corrupt
                # result is a failed attempt, not a silent wrong answer
                from .integrity import BlockCorruptionError

                try:
                    verify_result_file(out_path)
                    return attempt
                except BlockCorruptionError as e:
                    dispatch.record("corruption_detected")
                    trace.emit("block_corruption", site="worker.result",
                               path=out_path, detail=str(e)[:300])
                    try:
                        os.unlink(out_path)  # never serve corrupt bytes
                    except OSError:
                        pass
                    reason = ("committed output failed checksum "
                              f"verification: {e}")
                    stderr_tail = ""
            else:
                reason = (
                    f"exit status {proc.returncode}"
                    if proc.returncode != 0
                    else "worker exited 0 but produced no committed output"
                )
                if proc.returncode != 0:
                    # route the worker's TYPED exit through the
                    # classifier before deciding to re-spawn: a
                    # FATAL-classified failure re-runs deterministically
                    # and must propagate, not retry
                    rec = read_exit_record(spec_path)
                    if rec and rec.get("disposition") == FATAL:
                        _sweep_inprogress()
                        if rec.get("error_type") == "QueryCancelledError":
                            raise QueryCancelledError(
                                str(rec.get("query_id") or "worker"),
                                reason=str(rec.get("reason") or "cancel"))
                        from .hostpool import WorkerTaskFatalError

                        raise WorkerTaskFatalError(
                            str(rec.get("error_type") or "Exception"),
                            str(rec.get("message") or ""))
        last_failure = RuntimeError(
            f"worker attempt {attempt} failed ({reason}): " + stderr_tail
        )
        _sweep_inprogress()
        if attempt + 1 < policy.max_attempts:  # no sleep after the last one
            policy.sleep_before_retry(0, int(spec.get("partition", 0)), attempt)
    raise TaskRetriesExhausted(
        0, int(spec.get("partition", 0)), policy.max_attempts,
        last_failure or RuntimeError("no attempts ran"),
    )


def terminate_process_group(proc) -> None:
    """Terminate a worker subprocess and everything in its process
    group: SIGTERM first (a clean shutdown window), escalate to
    SIGKILL if the group is still alive half a second later.  Safe on
    an already-dead process."""
    import os
    import signal
    import subprocess

    try:
        pgid = os.getpgid(proc.pid)
    except (OSError, ProcessLookupError):
        pgid = None
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            if pgid is not None:
                os.killpg(pgid, sig)
            else:
                proc.send_signal(sig)
        except (OSError, ProcessLookupError):
            return
        try:
            proc.wait(timeout=0.5)
            return
        except subprocess.TimeoutExpired:
            continue


if __name__ == "__main__":
    if sys.argv[1:] and sys.argv[1] == "--serve":
        sys.exit(serve())
    sys.exit(main(sys.argv[1]))
