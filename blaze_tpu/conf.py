"""Configuration knobs, mirroring the reference's single-source-of-truth
Spark conf pattern.

The reference exposes an enum of ``spark.blaze.*`` knobs on the JVM side
(``spark-extension/.../BlazeConf.java:22-76``) and mirrors each one into
native code with live JNI static calls
(``native-engine/blaze-jni-bridge/src/conf.rs:19-91``).  Here the conf
is a process-global key→value store that the JVM gateway (when embedded
under Spark) populates from the SparkConf over JNI, and that tests /
standalone runs populate directly.  Defaults match the reference where
the knob has a reference equivalent.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict

from .analysis.locks import make_lock

# innermost subsystem lock of the declared hierarchy (analysis/locks.py):
# every subsystem reads conf while holding its own locks, never vice versa
_lock = make_lock("conf.store")
_values: Dict[str, Any] = {}

# guarded-by declaration (analysis/guarded.py): the live conf store is
# read from every subsystem's threads and written by the gateway/tests
GUARDED_BY = {"_values": "conf.store"}
GUARDED_REFS = ("_values",)


class ConfEntry:
    """One typed knob.  ``.get()`` reads the live value (env var override
    ``BLAZE_<NAME>`` > programmatic set > default), like the reference's
    ``define_conf!`` macro reads SparkConf through a JNI static."""

    def __init__(self, key: str, default: Any, parse: Callable[[str], Any]):
        self.key = key
        self.default = default
        self._parse = parse
        self._env_key = (
            "BLAZE_" + key.replace("spark.blaze.", "").replace(".", "_").upper()
        )

    def get(self) -> Any:
        env_key = self._env_key
        if env_key in os.environ:
            return self._parse(os.environ[env_key])
        with _lock:
            return _values.get(self.key, self.default)

    def set(self, value: Any) -> None:
        with _lock:
            _values[self.key] = value


def _bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


# ≙ BlazeConf.java defaults: BATCH_SIZE 10000, etc.
BATCH_SIZE = ConfEntry("spark.blaze.batchSize", 8192, int)
ENABLE_PARTIAL_AGG_SKIPPING = ConfEntry("spark.blaze.partialAggSkipping.enable", True, _bool)
PARTIAL_AGG_SKIPPING_RATIO = ConfEntry("spark.blaze.partialAggSkipping.ratio", 0.8, float)
PARTIAL_AGG_SKIPPING_MIN_ROWS = ConfEntry("spark.blaze.partialAggSkipping.minRows", 20000, int)
SPILL_COMPRESSION_CODEC = ConfEntry("spark.blaze.spill.compression.codec", "zlib", str)
IO_COMPRESSION_CODEC = ConfEntry("spark.io.compression.codec", "zlib", str)
IGNORE_CORRUPT_FILES = ConfEntry("spark.files.ignoreCorruptFiles", False, _bool)
PARQUET_FILTER_PUSHDOWN = ConfEntry("spark.blaze.parquet.enable.pageFiltering", True, _bool)
# TPU-only: hand-written pallas kernels for hot loops (kernels/); the
# pure-XLA path is always kept as fallback
PALLAS_ENABLE = ConfEntry("spark.blaze.tpu.pallas.enable", True, _bool)
# pickled UDF/UDTF payloads in TaskDefinitions execute arbitrary code at
# deserialization (round-1 advisor finding): a gateway deployed across a
# trust boundary must run with this OFF and register generators by name
ALLOW_PICKLED_UDFS = ConfEntry("spark.blaze.udf.allowPickled", True, _bool)
# fixed per-group element budget for collect_list/collect_set results
# (the reference's lists are unbounded; the padded device layout is not —
# elements past the budget are SILENTLY DROPPED: raise this knob when a
# query's groups can exceed it)
COLLECT_MAX_ELEMS = ConfEntry("spark.blaze.collect.maxElems", 64, int)
# bounded producer queue depth between host staging and device compute
# (≙ rt.rs sync_channel(1) + tokio stream drive); 0 = synchronous
PIPELINE_DEPTH = ConfEntry("spark.blaze.pipeline.depth", 2, int)
RSS_FETCH_BARRIER_TIMEOUT = ConfEntry("spark.blaze.rss.fetchBarrierTimeout", 120.0, float)
# Double-buffered shuffle write: the map task hands each batch's
# pid-sorted device output to a host staging thread (device->host
# transfer + per-pid slicing + memmgr-tracked buffering) while the next
# batch's program is already dispatched.  Off = the synchronous path.
SHUFFLE_ASYNC_WRITE = ConfEntry("spark.blaze.shuffle.asyncWrite", True, _bool)
# Bounded handoff queue depth for the async shuffle writer (device
# outputs in flight to the host stager; producer blocks when full).
SHUFFLE_ASYNC_QUEUE_DEPTH = ConfEntry("spark.blaze.shuffle.asyncWrite.queueDepth", 2, int)

# Fault-tolerant stage execution (runtime/retry.py + scheduler loop).
# ≙ spark.task.maxFailures: total attempts per task, 1 = fail fast.
TASK_MAX_ATTEMPTS = ConfEntry("spark.blaze.task.maxAttempts", 4, int)
# first retry delay (seconds); doubles per attempt with deterministic
# jitter (retry.py RetryPolicy.backoff).  0 disables backoff sleeps.
TASK_RETRY_BACKOFF = ConfEntry("spark.blaze.task.retryBackoff", 0.1, float)
# per-task wall-clock budget (seconds), checked between output batches;
# 0 = unlimited.  A timed-out attempt is retried like any failure.
TASK_TIMEOUT = ConfEntry("spark.blaze.task.timeout", 0.0, float)
# fetch-failure recoveries (upstream map-stage regenerations) allowed
# per fetching task before the failure is terminal
STAGE_MAX_ATTEMPTS = ConfEntry("spark.blaze.stage.maxAttempts", 4, int)
# Concurrent tasks per non-result stage in the scheduler (1 = the
# strictly serial pre-speculation behavior, which keeps fault-injection
# hit ordering deterministic; speculation/wedge detection force the
# concurrent attempt runner regardless).
STAGE_TASK_CONCURRENCY = ConfEntry("spark.blaze.stage.taskConcurrency", 1, int)
# Per-QUERY wall-clock budget (ms), enforced by the query CancelScope
# (runtime/context.py): every cooperative checkpoint (scheduler drain,
# result-batch pull, attempt launch, the concurrent runner's poll
# loop) checks the deadline, and expiry cancels every live attempt and
# raises QueryDeadlineError carrying the stage/task frontier.  The
# per-TASK half of the clock is spark.blaze.task.timeout /
# spark.blaze.task.wedgeMs; this is the per-query half.  0 = unlimited.
QUERY_TIMEOUT_MS = ConfEntry("spark.blaze.query.timeoutMs", 0, int)
# Heartbeat-age wedge detection on the plain (non-speculative) retry
# path, in ms: a task whose monitor heartbeat age exceeds this is
# cancelled cooperatively and RETRIED like a timeout — covering the
# blind spot where the cooperative drain deadline only fires between
# driver-observed batches, so a task wedged inside its first batch
# would hang forever.  0 = off.  Must exceed
# spark.blaze.monitor.heartbeatMs or healthy tasks look wedged.
TASK_WEDGE_MS = ConfEntry("spark.blaze.task.wedgeMs", 0, int)

# Speculative execution (runtime/speculation.py, ≙ spark.speculation):
# once a quantile of a stage's tasks have finished, a task running
# longer than multiplier x their median runtime (or whose heartbeat age
# crosses wedgeMs) gets ONE backup attempt racing it through the
# attempt-id commit seams (atomic-rename shuffle commit / RSS
# close-abort); first completion wins, the loser is cancelled
# cooperatively and its progress/heartbeat state rolled back.
SPECULATION_ENABLE = ConfEntry("spark.blaze.speculation.enabled", False, _bool)
# backup launches when runtime > multiplier x median(completed sibling
# durations) — ≙ spark.speculation.multiplier
SPECULATION_MULTIPLIER = ConfEntry("spark.blaze.speculation.multiplier", 1.5, float)
# fraction of the stage's tasks that must have completed before
# duration-based speculation engages — ≙ spark.speculation.quantile
SPECULATION_QUANTILE = ConfEntry("spark.blaze.speculation.quantile", 0.75, float)
# minimum runtime (seconds) before a task may be speculated — keeps
# short tasks from ever paying the backup cost
SPECULATION_MIN_RUNTIME = ConfEntry("spark.blaze.speculation.minRuntime", 0.1, float)
# heartbeat-age wedge trigger for speculation, in ms: a running task
# whose last beat is older than this gets its backup immediately,
# without waiting for the duration quantile (0 = duration-only)
SPECULATION_WEDGE_MS = ConfEntry("spark.blaze.speculation.wedgeMs", 0, int)
# deterministic fault-injection schedule (runtime/faults.py grammar,
# e.g. "shuffle.fetch@2,task.compute@1@a0"); empty = no injection.
# Env override BLAZE_FAULTS_SPEC reaches worker subprocesses too.
FAULTS_SPEC = ConfEntry("spark.blaze.faults.spec", "", str)

# Elastic worker-host pool (runtime/hostpool.py): persistent
# worker.py --serve processes the scheduler binds map tasks to.
# Number of pooled workers; 0 = pool disabled, everything in-process.
POOL_WORKERS = ConfEntry("spark.blaze.pool.workers", 0, int)
# pooled-worker heartbeat interval (ms) on the serve protocol's stdout
# frame stream — the liveness signal hostpool.heartbeat_ages() reads
# (same age mechanism as spark.blaze.monitor.heartbeatMs)
POOL_HEARTBEAT_MS = ConfEntry("spark.blaze.pool.heartbeatMs", 50, int)
# heartbeat silence (ms) past which a READY pooled worker is declared
# lost and its map outputs invalidated for partial rerun.  Must exceed
# spark.blaze.pool.heartbeatMs by a healthy margin.
POOL_LIVENESS_TIMEOUT_MS = ConfEntry(
    "spark.blaze.pool.livenessTimeoutMs", 10000, int)
# worker-slot failures inside the decay window before the slot is
# BLACKLISTED (no respawn) — ≙ spark.blacklist.* node blacklisting
HOST_BLACKLIST_MAX_FAILURES = ConfEntry(
    "spark.blaze.host.blacklist.maxFailures", 2, int)
# sliding decay window (seconds) for blacklist failure counts; a
# blacklisted slot is re-admitted once its count decays below the
# threshold — ≙ spark.blacklist.timeout
HOST_BLACKLIST_DECAY_SEC = ConfEntry(
    "spark.blaze.host.blacklist.decaySec", 60.0, float)

# End-to-end data integrity (runtime/integrity.py): checksum algorithm
# stamped on every framed block that crosses a process or disk boundary
# (shuffle map outputs, spill frames, RSS pushes, broadcast blobs,
# worker result frames) and verified at every read boundary — a
# mismatch raises typed BlockCorruptionError and rides the existing
# recovery ladder (fetch-failure map rerun / task retry / quarantine).
# Values: "crc32" (zlib-backed, C speed — the default), "crc32c"
# (Castagnoli, byte-interoperable with hardware CRC32C, pure-python
# table), "xxh32" (the LZ4-frame hash), "off" (no stamping, no
# verification).  Checksums are host-side over already-staged bytes:
# no device syncs, so the warm dispatch budget is untouched.
IO_CHECKSUM = ConfEntry("spark.blaze.io.checksum", "crc32", str)
# Orphan sweep on startup: a LocalShuffleManager re-opened over an
# EXISTING root (a restarted driver / a worker joining a shared root)
# reclaims `.inprogress` staging temps and blaze_spill_ files older
# than this many seconds — debris of a crashed prior process that
# would otherwise leak the dead run's disk.  0 disables the sweep.
ORPHAN_SWEEP_AGE = ConfEntry("spark.blaze.shuffle.orphanSweepAgeSec", 1800, int)
# Disk-pressure ladder (runtime/diskmgr.py): ENOSPC/EIO during a spill
# or shuffle write first RECLAIMS reclaimable disk — stale
# `.inprogress` temps and orphaned spill files older than this many
# seconds in the registered shuffle roots and the spill temp dir —
# before retrying the write, falling back to host RAM (bounded by the
# memmgr quota), or raising typed retryable DiskExhaustedError.
DISK_RECLAIM_AGE = ConfEntry("spark.blaze.disk.reclaimAgeSec", 300, int)

# Graceful degradation under device memory pressure (runtime/oom.py):
# an XLA RESOURCE_EXHAUSTED caught at the dispatch choke point first
# sheds host-staging pressure (memmgr force-spill) and retries; a
# fused-stage program that still OOMs halves its batch and re-runs,
# recursively up to this many times, before falling back to the eager
# per-operator path — only then does the attempt fail (retryable).
OOM_MAX_DOWNSHIFTS = ConfEntry("spark.blaze.oom.maxDownshifts", 2, int)

# Query-level tracing + structured event log (runtime/trace.py).
# OFF (default) keeps the dispatch hot path on the pre-existing code
# path — no span allocation, no block-until-ready timing per kernel.
# ON: scheduler/task/operator lifecycle events + per-kernel
# device/dispatch/compile attribution append to a JSONL event log
# (≙ Spark's spark.eventLog.enabled + EventLoggingListener).
TRACE_ENABLE = ConfEntry("spark.blaze.trace.enabled", False, _bool)
# Event-log directory (≙ spark.eventLog.dir); empty = a blaze_eventlog
# dir under the system temp dir.  One JSONL file per traced query.
EVENT_LOG_DIR = ConfEntry("spark.blaze.eventLog.dir", "", str)
# Size cap per event-log file (bytes): a full file rolls over into a
# numbered segment (<path>.seg1, .seg2, ...) so long-running services
# never grow one unbounded JSONL (≙ spark.eventLog.rolling.maxFileSize).
# 0 = unbounded.  --report reads a rotated set transparently.
EVENT_LOG_MAX_BYTES = ConfEntry("spark.blaze.eventLog.maxBytes", 0, int)
# Kernel-attribution sampling: with tracing armed, block-until-ready
# time every Nth instrumented program instead of all of them (attributed
# device times are scaled back up by the sampling factor in --report),
# so attribution is cheap enough to leave on in production.  1 = time
# every program (the full-fidelity profile default); 0 = never block:
# launches and compiles still attribute per label and --report prints
# device time as "not sampled" (the armed mode a benchmark cell can run
# in).  Caveat: on a device that truly queues async work, the sampled
# program's drain also waits out the N-1 unsampled programs queued
# ahead of it, so the scaled device time is an UPPER BOUND, not an
# unbiased estimate (the report flags it '~').
TRACE_SAMPLE_RATE = ConfEntry("spark.blaze.trace.sampleRate", 1, int)

# OpenTelemetry export (runtime/otel.py): map each traced query's
# event log onto an OTLP/JSON span tree (query -> stage -> task ->
# kernel, one W3C trace id end to end) at query-span exit.  OFF
# (default) is a structural no-op exactly like trace.enabled: one bool
# read at span exit, no conversion, no file, no thread.  Requires
# tracing armed (the event log is the source).
OTEL_ENABLE = ConfEntry("spark.blaze.otel.enabled", False, _bool)
# File sink directory for the exported OTLP/JSON documents (one
# <query>-<pid>-spans.json per traced query); empty = a blaze_otel dir
# under the system temp dir.
OTEL_DIR = ConfEntry("spark.blaze.otel.dir", "", str)
# Best-effort OTLP/HTTP push target (e.g. an OpenTelemetry collector's
# http://host:4318/v1/traces): when set, exported span documents are
# also queued to a daemon push loop (blaze-otel-push, next to the
# statsd pusher) that POSTs them with a short timeout — a dead
# collector costs nothing and never blocks the workload.  Empty
# (default) = file sink only, no socket, no thread.
OTEL_ENDPOINT = ConfEntry("spark.blaze.otel.endpoint", "", str)
# Push-loop flush cadence (ms) for the OTLP HTTP exporter.
OTEL_FLUSH_MS = ConfEntry("spark.blaze.otel.flushMs", 1000, int)

# Multi-tenant query service (runtime/service.py): admission control,
# fair-share scheduling, per-pool quotas, backpressure, supervision.
# Queries RUNNING concurrently once admitted (each interleaves its
# stages through the one-device-lease fair-share gate below).
SERVICE_MAX_CONCURRENT = ConfEntry("spark.blaze.service.maxConcurrent", 2, int)
# Submissions waiting for a run slot beyond the running set; PAST this
# bound a submission is SHED with a typed retryable QueryRejectedError
# (HTTP 429 on the service endpoint) instead of accepted-and-wedged.
SERVICE_MAX_QUEUED = ConfEntry("spark.blaze.service.maxQueued", 16, int)
# A QUEUED submission still waiting after this long is shed with
# QueryRejectedError(reason="queue_timeout") — bounded queueing delay
# instead of unbounded head-of-line blocking.  0 = wait forever.
SERVICE_QUEUE_TIMEOUT_MS = ConfEntry("spark.blaze.service.queueTimeoutMs", 0, int)
# Supervisor wedge reaping: a RUNNING service query whose monitor
# heartbeat age exceeds this is cancelled (reason="wedged") — the
# query-level analogue of spark.blaze.task.wedgeMs, read from the live
# registry's heartbeat-age signal (needs the monitor armed).  0 = off.
SERVICE_WEDGE_MS = ConfEntry("spark.blaze.service.wedgeMs", 0, int)
# Bounded result handoff between a service query's worker (producer)
# and the submitter consuming QueryHandle.batches(): a slow consumer
# BLOCKS the producer (which releases its device-lease turn first)
# instead of ballooning host buffers — the exchange backpressure.
SERVICE_RESULT_QUEUE_DEPTH = ConfEntry("spark.blaze.service.resultQueueDepth", 8, int)
# Per-pool knobs ride the registered dynamic prefix
# spark.blaze.service.pool.<name>.weight (fair-share weight, default 1)
# and spark.blaze.service.pool.<name>.quota (host-staging bytes budget,
# 0/unset = unlimited) — read via get_conf, like spark.blaze.enable.*.

# Serving-scale cache hierarchy (runtime/querycache.py).  Level 1,
# the PLAN cache: literal leaves canonicalize into slots
# (exprs.compile.slotify_literals) so parameter-shifted variants of one
# query shape share one plan fingerprint and ONE compiled fused program
# — the slot values ride as traced kernel arguments.  Off: literals
# bake into kernel keys again (every shifted variant recompiles).
CACHE_PLAN_ENABLED = ConfEntry("spark.blaze.cache.plan.enabled", True, _bool)
# Level 2, the RESULT cache: the service memoizes final result batches
# keyed by (plan fingerprint, slot values, source version); a hit is
# served host-side WITHOUT taking a fair-share device-lease turn.  Any
# source append/rewrite changes the version and invalidates exactly
# the dependent entries.
CACHE_RESULT_ENABLED = ConfEntry("spark.blaze.cache.result.enabled", True, _bool)
# Byte budget for cached result batches (LRU evicts past it), tracked
# through the memmgr as an UNOWNED consumer — watermark pressure spills
# cold entries down the diskmgr ladder, never a quota neighbor's memory.
CACHE_RESULT_MAX_BYTES = ConfEntry("spark.blaze.cache.result.maxBytes", 64 << 20, int)
# Per-entry cap: a single query result larger than this is never
# admitted (one giant result must not evict the whole working set).
CACHE_RESULT_MAX_ENTRY_BYTES = ConfEntry(
    "spark.blaze.cache.result.maxEntryBytes", 8 << 20, int)

# Live query monitoring (runtime/monitor.py).  OFF (default): no HTTP
# server, no background thread, and the heartbeat path is a structural
# no-op exactly like spark.blaze.trace.enabled=false.  ON: an in-process
# registry tracks per-query -> per-stage live state and a background
# HTTP server exposes /metrics (Prometheus text exposition rendered
# from the scheduler MetricNode tree + dispatch counters) and /queries
# (JSON live state) — ≙ the reference's metrics plumbed into the LIVE
# Spark UI while the query runs, not only post-hoc (SURVEY).
MONITOR_ENABLE = ConfEntry("spark.blaze.monitor.enabled", False, _bool)
# Port for the monitor HTTP server; 0 = pick a free ephemeral port
# (the bound port is logged and available via monitor.server_port()).
MONITOR_PORT = ConfEntry("spark.blaze.monitor.port", 4048, int)
# Progress-heartbeat cadence (ms): the scheduler and run_task emit
# stage_progress / task_heartbeat events at most this often, into the
# event log (when tracing is armed) and the live registry (when the
# monitor is armed).  Smaller = fresher /queries, more events.
MONITOR_HEARTBEAT_MS = ConfEntry("spark.blaze.monitor.heartbeatMs", 1000, int)
# Historical retention beyond the in-memory last-64 ring: when set,
# every FINISHED query's registry summary is appended to a JSONL
# history file under this directory (size-capped rollover like the
# event log), and /queries?all=1 serves the merged history.  Empty =
# in-memory ring only (the pre-existing behavior).
MONITOR_HISTORY_DIR = ConfEntry("spark.blaze.monitor.historyDir", "", str)
# Size cap (bytes) per history file before it rolls into a numbered
# .segN segment (same rollover contract as spark.blaze.eventLog.maxBytes).
MONITOR_HISTORY_MAX_BYTES = ConfEntry("spark.blaze.monitor.historyMaxBytes", 4 << 20, int)
# Push exporter: "host:port" arms a best-effort statsd UDP push loop
# (gauge lines derived from the same rendering as /metrics, pushed
# every heartbeat interval) so ops without a Prometheus scraper still
# get the numbers.  Empty (default) = structural no-op: no socket, no
# thread.
MONITOR_STATSD = ConfEntry("spark.blaze.monitor.statsd", "", str)

# SLO layer (runtime/slo.py): per-pool latency/error objectives
# declared as dynamic conf keys
# (spark.blaze.slo.pool.<name>.latencyP99Ms / .errorRate /
# .targetWindowSec) evaluated as MULTI-WINDOW BURN RATES over the
# observed per-pool latency/error stream — the SRE-workbook alerting
# shape: fire only when BOTH the fast and the slow window burn the
# error budget faster than the threshold, resolve only after the burn
# stays below it for a hold count (flap suppression).  Disarmed
# (default) the whole layer is a structural no-op: one bool read per
# query end, no state, no thread.
SLO_ENABLE = ConfEntry("spark.blaze.slo.enabled", False, _bool)
# Minimum interval (ms) between burn-rate evaluations — observe() and
# the /slo + /metrics render paths drive evaluation opportunistically
# (no background thread); this throttles the work, not the data.
SLO_EVAL_INTERVAL_MS = ConfEntry("spark.blaze.slo.evalIntervalMs", 200, int)
# Burn-rate threshold: an alert FIRES when both windows consume error
# budget at >= this multiple of the sustainable rate (1.0 = exactly
# exhausting the budget over the target window).
SLO_FIRE_BURN_RATE = ConfEntry("spark.blaze.slo.fireBurnRate", 1.0, float)
# Consecutive below-threshold evaluations required before a firing
# alert RESOLVES — the flap suppressor.
SLO_RESOLVE_HOLD_EVALS = ConfEntry("spark.blaze.slo.resolveHoldEvals", 2, int)

# Incident debug bundles (runtime/bundle.py, `--debug-bundle <dir>` /
# POST /queries/<id>/bundle): conf keys whose NAME matches any of
# these comma-separated lowercase substrings have their VALUE redacted
# in the bundle's conf dump (secrets never leave the host in a
# forensics snapshot).
BUNDLE_REDACT = ConfEntry(
    "spark.blaze.bundle.redactPatterns",
    "password,secret,token,credential,key.material", str)

# Whole-stage program fusion (ops/fusion.py): collapse traceable
# operator chains / agg pre-filters / final-agg sorts into single XLA
# programs.  OFF runs every operator as its own dispatch — the
# correctness fallback the fused-vs-unfused differential tests pin.
FUSION_ENABLE = ConfEntry("spark.blaze.fusion.enabled", True, _bool)
# Grouped/scalar aggs fold the per-batch reduce AND the accumulator
# merge into ONE jitted update program over stacked state (agg.py) —
# the q01 dispatch collapse.  OFF = reduce + concat + merge as
# separate programs (the pending-list doubling path).
FUSED_AGG_UPDATE = ConfEntry("spark.blaze.tpu.fusedAggUpdate", True, _bool)
# Persistent XLA compilation cache directory (jax_compilation_cache_dir).
# JAX_COMPILATION_CACHE_DIR, when set, wins and this key is not read;
# empty falls to <checkout>/.jax_cache (runtime/kernel_cache.py
# enable_persistent_cache is the one place that decides).  Pre-warm
# once per image with `python -m blaze_tpu --warmup` so first compiles
# are never paid inside a query.  Env: BLAZE_XLA_CACHEDIR.
XLA_CACHE_DIR = ConfEntry("spark.blaze.xla.cacheDir", "", str)

# TPU-specific knobs (no reference equivalent).
# Grouped-agg segment reduces via segmented associative scans + cumsum
# differences + gathers (scatter-free).  Off = jax.ops.segment_* +
# jnp.nonzero (scatter-based — a cliff on XLA:TPU).
SEG_SCAN_REDUCE = ConfEntry("spark.blaze.tpu.segScanReduce", True, _bool)
# PARTIAL grouped aggs sort ONE u32 key hash instead of every 64-bit
# key word (boundaries still compare full words; hash-collision
# duplicate groups are re-merged downstream)
AGG_HASH_SORT_PARTIAL = ConfEntry("spark.blaze.tpu.aggHashSortPartial", True, _bool)
# In-process exchanges keep partition buffers device-resident (HBM)
# instead of round-tripping IPC files through the host — every host
# sync drains the device queue and pays a D2H + H2D copy.  The file
# shuffle remains the cross-process / spill path (turn this off to
# force it, e.g. when a stage's output exceeds HBM).
EXCHANGE_IN_PROCESS = ConfEntry("spark.blaze.exchange.inProcess", True, _bool)
# AQE-style dynamic join selection in the stage scheduler (the
# reference inherits this from Spark AQE): off by default — the
# scheduler re-plans shuffle joins as broadcast joins when a side's
# materialized map output is under the threshold
ADAPTIVE_JOIN_ENABLE = ConfEntry("spark.blaze.enable.adaptiveJoin", False, _bool)
ADAPTIVE_BROADCAST_THRESHOLD = ConfEntry(
    "spark.blaze.adaptiveBroadcastThreshold", 10 << 20, int)
DEVICE_MEMORY_BUDGET = ConfEntry("spark.blaze.tpu.hbmBudget", 8 << 30, int)
HOST_SPILL_BUDGET = ConfEntry("spark.blaze.tpu.hostSpillBudget", 4 << 30, int)
MIN_CAPACITY = ConfEntry("spark.blaze.tpu.minBatchCapacity", 1024, int)

# Performance introspection (runtime/perf.py): EXPLAIN ANALYZE and
# per-kernel roofline/MFU attribution.
# Bytes-moved / flops estimation at the dispatch choke point — armed it
# runs ONLY while a trace kernel capture is active (the same scope that
# pays block-until-ready timing); disarmed it is one module-global bool
# read per traced call, exactly the spark.blaze.trace.enabled contract,
# and the untraced hot path never sees it at all.
PERF_ESTIMATES = ConfEntry("spark.blaze.perf.estimates", True, _bool)
# Override path for the per-device-kind peak table (empty = the
# packaged runtime/device_peaks.json).
PERF_PEAKS = ConfEntry("spark.blaze.perf.peaks", "", str)

# Runtime statistics observatory (runtime/stats.py): cardinality
# estimates stamped at optimize_plan, per-partition exchange
# histograms, Q-error drift reporting, and partition-skew findings.
# Disarmed cost is one module-global bool read per hook (the
# trace.enabled() contract).
STATS_ENABLED = ConfEntry("spark.blaze.stats.enabled", True, _bool)
# Per-group-key NDV HyperLogLog sketches on agg output streams —
# separately gated: updating a sketch reads column values back to the
# host, which the counter-only stats path never does.
STATS_SKETCHES = ConfEntry("spark.blaze.stats.sketches", False, _bool)
# Persistent stats store keyed by the plan fingerprint digest,
# versioned by source versions exactly like the result cache: observed
# actuals written at query-span exit, consulted by the estimator on
# the next run so warm estimates converge on actuals.
STATS_STORE_ENABLED = ConfEntry("spark.blaze.stats.store.enabled", True, _bool)
# Store directory (empty = <tmpdir>/blaze-stats-<uid>).
STATS_STORE_DIR = ConfEntry("spark.blaze.stats.store.dir", "", str)
# A partition is a skew finding when its rows are at least skewRatio x
# the median partition AND at least skewMinRows absolute — the floor
# keeps toy exchanges from alerting on noise.
STATS_SKEW_RATIO = ConfEntry("spark.blaze.stats.skewRatio", 4.0, float)
STATS_SKEW_MIN_ROWS = ConfEntry("spark.blaze.stats.skewMinRows", 4096, int)

# Static analysis & verification (blaze_tpu/analysis/).
# Plan verifier: run the rule-based structural checker
# (analysis/plan_verify.py — schema edges, partitioning/ordering
# prerequisites, fusion invariants) over every physical plan after
# ops/fusion.optimize_plan and before execution.  Off by default on
# the production hot path; FORCED ON in tests (conftest) and --chaos.
VERIFY_PLAN = ConfEntry("spark.blaze.verify.plan", False, _bool)
# Runtime lock-order assertion (analysis/locks.py): while armed, every
# acquisition of a hierarchy lock asserts strictly inward order and
# raises LockOrderError on inversion — the would-be deadlock surfaces
# deterministically instead of as a rare hang.  Armed in --chaos and
# the monitor/fault test suites; disarmed cost is one bool read.
VERIFY_LOCKS = ConfEntry("spark.blaze.verify.locks", False, _bool)
# Eraser-style dynamic lockset checker (runtime/lockset.py): while
# armed, every instrumented guarded-state access records the thread's
# held lockset, and a per-(object, attribute) empty intersection after
# the state is seen from >=2 threads raises LocksetViolation — the
# data race the static guarded-by pass (analysis/guarded.py) cannot
# see through dynamic dispatch surfaces deterministically.  Armed in
# --chaos / --chaos-seeds and the concurrency suites; disarmed cost is
# one bool read per instrumented access.
VERIFY_LOCKSET = ConfEntry("spark.blaze.verify.lockset", False, _bool)
# Error-escape recorder + per-query resource ledger (runtime/errors.py
# + runtime/ledger.py): while armed, every AUDITED broad-except site
# records a FATAL-class control-flow error it absorbs (the escape
# survives the swallow — lockset.reported()-style gate), and every
# tracked resource (spill files, .inprogress shuffle temps, scoped
# resource registrations, device-lease turns) must be released by
# query end or the leak is recorded and fails the run.  Armed in
# --chaos / --chaos-seeds and the faults/lifecycle/service suites;
# disarmed cost is one bool read per hook.
VERIFY_ERRORS = ConfEntry("spark.blaze.verify.errors", False, _bool)

# Per-operator enable flags, ≙ BlazeConverters.scala:82-120
# (spark.blaze.enable.scan / .project / .filter / ...).
_OP_FLAGS: Dict[str, ConfEntry] = {}


def op_enabled(name: str) -> bool:
    entry = _OP_FLAGS.get(name)
    if entry is None:
        entry = ConfEntry(f"spark.blaze.enable.{name}", True, _bool)
        _OP_FLAGS[name] = entry
    return entry.get()


CONF_NAMES_PATH = os.path.join(
    os.path.dirname(__file__), "runtime", "conf_names.json")


def load_conf_names() -> Dict[str, Any]:
    """The golden conf-name registry (runtime/conf_names.json,
    mirroring metric_names.json): every ``spark.blaze.*`` key this
    engine reads, plus the dynamic per-operator prefix.  Conf KEYS are
    API — deployment configs and docs reference them by string, so a
    silent rename strands every existing setting.  The drift is gated
    both ways by analysis/lint.py (``conf.*`` rules) in tier-1."""
    import json

    with open(CONF_NAMES_PATH) as f:
        return json.load(f)


def registered_conf_keys() -> set:
    """Flat set of every registered conf key."""
    return set(load_conf_names().get("keys", []))


def declared_entries() -> Dict[str, "ConfEntry"]:
    """Every module-level ConfEntry declared here, by key (the live
    half the registry mirrors; op_enabled's dynamic family is covered
    by the registry's ``dynamic_prefixes``)."""
    import sys

    mod = sys.modules[__name__]
    return {
        v.key: v for v in vars(mod).values() if isinstance(v, ConfEntry)
    }


def set_conf(key: str, value: Any) -> None:
    """Entry point for the gateway / tests to inject Spark conf values."""
    with _lock:
        _values[key] = value


def all_values() -> Dict[str, Any]:
    """Every explicitly-set conf value (static AND dynamic keys) — the
    debug bundle's conf dump source: declared entries cover defaults,
    but only this store knows the dynamic key families (per-pool SLO
    objectives, op toggles) an incident was running with."""
    with _lock:
        return dict(_values)


def get_conf(key: str, default: Any = None) -> Any:
    with _lock:
        return _values.get(key, default)
