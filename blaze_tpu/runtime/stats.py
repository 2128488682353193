"""Runtime statistics observatory: estimates vs. actuals, skew, and a
fingerprint-keyed persistent stats store.

≙ the statistics half of Spark's adaptive execution loop: Blaze plumbs
native operator metrics up to the Spark UI and inherits AQE, which
re-plans from *observed* shuffle statistics.  This engine already
observes actual cardinalities mid-query (every operator's
``_record_batch`` lands ``output_rows``/``output_bytes`` in its
MetricsSet, and the shuffle seams record bytes moved); what was missing
is the other half of the loop — *estimates* to compare them against,
per-partition skew detection on the exchanges, and persistence of
observed statistics across runs.  This module adds all three:

- **Estimator** (:func:`annotate`, called at the ``optimize_plan``
  choke point): a bottom-up cardinality walk over the optimized plan —
  source row counts from parquet/ORC footers and MemoryScan lengths,
  default selectivities for filter (x0.25) / grouped agg (x0.1) /
  joins (max of inputs) — stamping ``est_rows``/``est_bytes`` into
  every node's MetricsSet, so the estimates ride the existing
  ``task_plan`` metric snapshots into the event log with zero schema
  change.  Where the stats store holds actuals for the plan's
  fingerprint, the stored actuals REPLACE the cold estimates (the warm
  run converges on observed truth and emits ``stats_reused``).
- **Actuals**: per-partition rows/bytes histograms on every exchange
  (:func:`note_exchange`, fed by the in-process exchange
  materializers and the file shuffle writer's commit) and per-group-key
  NDV HyperLogLog sketches on agg output streams
  (:func:`sketch_stream`, behind ``spark.blaze.stats.sketches``).
- **Drift + skew** (:func:`flush`, called at query-span exit): merges
  the per-task plan instances per fingerprint digest, computes
  per-node Q-error ``max(est/act, act/est)``, scans the exchange
  histograms for a hot partition (ratio vs. median over
  ``spark.blaze.stats.skewRatio`` with at least ``skewMinRows`` rows)
  and emits one typed ``stats_skew_detected`` event per skewed
  exchange — the signal a future adaptive PR splits on.
- **Store**: exact-fingerprint digests with observed actuals persist
  as ``<digest>.json`` under ``spark.blaze.stats.store.dir`` (same
  ``.inprogress`` + ``os.replace`` commit and source-version
  invalidation discipline as the result cache), consulted by the
  estimator on the next run.

Armed/disarmed follows the house ``trace.enabled()`` contract: every
hook starts with one module-global bool read
(``spark.blaze.stats.enabled``; sketches separately behind
``spark.blaze.stats.sketches``), and the disarmed path touches no
plan, metric, or sketch state at all.  The ``stats.registry`` lock is
held for dict/array arithmetic only — all trace emission, dispatch
counter bumps, and store IO happen strictly outside it.
"""

from __future__ import annotations

import json
import math
import os
import re
import tempfile
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from .. import conf
from ..analysis.locks import make_lock
from . import lockset

# --------------------------------------------------------------- state

_lock = make_lock("stats.registry")
_LOG = lockset.module_guard(__name__)

_loaded = False
_ARMED = False          # spark.blaze.stats.enabled
_SKETCHES = False       # spark.blaze.stats.sketches
_STORE_ON = False       # spark.blaze.stats.store.enabled
_STORE_DIR = ""         # resolved store directory
_SKEW_RATIO = 4.0       # spark.blaze.stats.skewRatio
_SKEW_MIN = 4096        # spark.blaze.stats.skewMinRows

#: annotated live plan instances awaiting flush: (digest-key, exact,
#: sources, mem_rows, plan) — optimize_plan runs per TASK, so one
#: query registers several instances of the same digest; flush merges
#: them (actuals sum, estimates max)
_live: List[tuple] = []
_LIVE_CAP = 256

#: per-exchange partition histograms: key -> {"op", "rows", "bytes"}
#: (int64 arrays, one slot per output partition, merged across map
#: tasks of the same shuffle)
_exchanges: Dict[str, Dict[str, Any]] = {}
_EXCHANGE_CAP = 256

#: last flush summary + recent skew findings (monitor /stats surface)
_last: Optional[Dict[str, Any]] = None
_findings: "deque[Dict[str, Any]]" = deque(maxlen=32)

#: (path, mtime_ns, size) -> (rows, bytes) parquet/ORC footer cache —
#: optimize_plan runs per task; the footer must not be re-read per task
_footer_cache: Dict[tuple, Tuple[int, int]] = {}
_FOOTER_CAP = 1024

#: digest -> store record (or None for a known miss) — bounds store
#: file reads to one per digest per process
_store_cache: Dict[str, Optional[Dict[str, Any]]] = {}
_STORE_CACHE_CAP = 256
#: distinguishes "digest never looked up" from "known miss" (None)
_MISSING = object()

GUARDED_BY = {"_live": "stats.registry",
              "_exchanges": "stats.registry",
              "_last": "stats.registry",
              "_findings": "stats.registry",
              "_footer_cache": "stats.registry",
              "_store_cache": "stats.registry"}
GUARDED_REFS = ("_live", "_exchanges", "_findings")
LOCK_FREE = {
    "_ARMED": "single bool flipped at quiescent points (load/refresh); "
              "readers see a stale value for at most one access",
    "_SKETCHES": "same one-shot contract as _ARMED",
    "_STORE_ON": "same one-shot contract as _ARMED",
    "_STORE_DIR": "single str swapped at load/refresh",
    "_SKEW_RATIO": "single float swapped at load/refresh",
    "_SKEW_MIN": "single int swapped at load/refresh",
    "_loaded": "same one-shot latch pattern as trace._loaded",
}

STATS_STORE_VERSION = 1


class StatsStoreCorruptError(RuntimeError):
    """A persisted stats-store entry failed to parse or validate.
    FATAL-class for the retry ladder (a corrupt artifact is never
    retryable); the estimator's lookup path handles it narrowly by
    dropping the entry and counting ``stats_store_invalidations``."""


# ------------------------------------------------------------- arming

def _load() -> None:
    global _loaded, _ARMED, _SKETCHES, _STORE_ON, _STORE_DIR
    global _SKEW_RATIO, _SKEW_MIN
    _ARMED = bool(conf.STATS_ENABLED.get())
    _SKETCHES = bool(conf.STATS_SKETCHES.get())
    _STORE_ON = bool(conf.STATS_STORE_ENABLED.get())
    d = str(conf.STATS_STORE_DIR.get())
    if not d:
        uid = os.getuid() if hasattr(os, "getuid") else 0
        d = os.path.join(tempfile.gettempdir(), f"blaze-stats-{uid}")
    _STORE_DIR = d
    _SKEW_RATIO = float(conf.STATS_SKEW_RATIO.get())
    _SKEW_MIN = int(conf.STATS_SKEW_MIN_ROWS.get())
    _loaded = True


def enabled() -> bool:
    """Stats collection armed?  Disarmed cost is one module-global
    bool read — the ``trace.enabled()`` contract."""
    if not _loaded:
        _load()
    return _ARMED


def sketches_enabled() -> bool:
    """NDV sketching armed?  Requires stats collection on as well."""
    if not _loaded:
        _load()
    return _ARMED and _SKETCHES


def refresh() -> None:
    """Re-read the ``spark.blaze.stats.*`` confs (tests / --chaos)."""
    _load()


def reset() -> None:
    """Drop all pending state and caches, then re-read conf."""
    global _exchanges, _last
    with _lock:
        lockset.check(_LOG, "_live", "_exchanges", "_last", "_findings",
                      "_footer_cache", "_store_cache")
        _live.clear()
        _exchanges = {}
        _last = None
        _findings.clear()
        _footer_cache.clear()
        _store_cache.clear()
    _load()


def discard_pending() -> None:
    """Forget annotated plans / exchange histograms accumulated since
    the last flush without reporting them (warm-up passes)."""
    global _exchanges
    with _lock:
        lockset.check(_LOG, "_live", "_exchanges")
        _live.clear()
        _exchanges = {}


# ------------------------------------------------- HyperLogLog sketch

_HLL_P = 12
_HLL_M = 1 << _HLL_P


def _clz64(x: np.ndarray) -> np.ndarray:
    """Vectorized count-leading-zeros over uint64 (values are never 0
    here: the caller ORs in a low bit)."""
    x = x.copy()
    n = np.zeros(x.shape, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for s in (32, 16, 8, 4, 2, 1):
            mask = x < (np.uint64(1) << np.uint64(64 - s))
            n[mask] += np.uint64(s)
            x[mask] = x[mask] << np.uint64(s)
    return n


class HyperLogLog:
    """Streaming distinct-count sketch (p=12, 4096 uint8 registers,
    ~1.6% standard error).  Update/merge are pure numpy; serializes to
    a plain int list for the JSON stats store."""

    __slots__ = ("registers",)

    def __init__(self, registers: Optional[np.ndarray] = None):
        self.registers = (np.zeros(_HLL_M, dtype=np.uint8)
                          if registers is None else registers)

    def update_hashed(self, h: np.ndarray) -> None:
        """Fold a batch of already-hashed uint64 values in."""
        if h.size == 0:
            return
        idx = (h >> np.uint64(64 - _HLL_P)).astype(np.int64)
        with np.errstate(over="ignore"):
            w = (h << np.uint64(_HLL_P)) | np.uint64(1)
        rank = np.minimum(_clz64(w) + np.uint64(1),
                          np.uint64(64 - _HLL_P + 1)).astype(np.uint8)
        np.maximum.at(self.registers, idx, rank)

    def merge(self, other: "HyperLogLog") -> None:
        self.registers = np.maximum(self.registers, other.registers)

    def estimate(self) -> float:
        m = float(_HLL_M)
        alpha = 0.7213 / (1.0 + 1.079 / m)
        denom = float(np.sum(np.ldexp(1.0, -self.registers.astype(np.int64))))
        est = alpha * m * m / denom
        zeros = int(np.count_nonzero(self.registers == 0))
        if est <= 2.5 * m and zeros:
            est = m * math.log(m / zeros)
        return est

    def to_list(self) -> List[int]:
        return self.registers.tolist()

    @classmethod
    def from_list(cls, regs: List[int]) -> "HyperLogLog":
        a = np.asarray(regs, dtype=np.uint8)
        if a.shape != (_HLL_M,):
            raise StatsStoreCorruptError(
                f"HLL register list has shape {a.shape}, want ({_HLL_M},)")
        return cls(a)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer — avalanches raw column values so the HLL
    register index and rank bits are both well distributed."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def group_key_hash(batch, n_cols: int) -> np.ndarray:
    """uint64 hash of the first ``n_cols`` columns of ``batch`` (the
    agg output layout puts the grouping keys first).  Non-numeric
    columns are skipped; an all-skipped row set hashes empty."""
    n = batch.num_rows
    h: Optional[np.ndarray] = None
    for col in batch.columns[:n_cols]:
        data = getattr(col, "data", None)
        if data is None:
            continue
        a = np.asarray(data)[:n]
        if a.dtype.kind in "iub":
            v = a.astype(np.int64, copy=False).view(np.uint64)
        elif a.dtype.kind == "f":
            v = a.astype(np.float64).view(np.uint64)
        else:
            continue
        mixed = _mix64(v)
        h = mixed if h is None else _mix64(h ^ mixed)
    return h if h is not None else np.empty(0, dtype=np.uint64)


def sketch_stream(node, n_keys: int, stream) -> Iterator:
    """Wrap an agg output stream with per-group-key NDV sketching.
    Each partition stream folds into a LOCAL sketch and merges it into
    the node's sketch under the stats lock only at stream end — one
    plan instance executes multiple partitions concurrently."""
    local = HyperLogLog()

    def gen():
        try:
            for b in stream:
                if b.num_rows:
                    local.update_hashed(group_key_hash(b, n_keys))
                yield b
        finally:
            with _lock:
                hll = getattr(node, "_stats_hll", None)
                if hll is None:
                    node._stats_hll = local
                else:
                    hll.merge(local)

    return gen()


# ----------------------------------------------------------- estimator

#: default selectivities — deliberately crude: the point of the
#: observatory is to MEASURE how wrong they are (Q-error) and replace
#: them with persisted actuals on the next run
FILTER_SELECTIVITY = 0.25
AGG_SELECTIVITY = 0.1

_PASS_THROUGH = frozenset({
    "ProjectExec", "RenameColumnsExec", "CoalesceBatchesExec",
    "SortExec", "BufferPartitionExec", "DebugExec",
    "NativeShuffleExchangeExec", "IciShuffleExchangeExec",
    "BroadcastExchangeExec", "ShuffleWriterExec", "RssShuffleWriterExec",
    "IpcWriterExec", "ParquetSinkExec", "BroadcastJoinBuildHashMapExec",
    "WindowExec", "GenerateExec", "ExpandExec",
})
_JOINS = frozenset({"BroadcastJoinExec", "HashJoinExec",
                    "SortMergeJoinExec"})
_AGGS = frozenset({"AggExec", "ObjectAggExec", "BloomFilterAggExec"})


def _footer(entry) -> Optional[Tuple[int, int]]:
    """(rows, bytes) for one entry of a scan's file group from the
    file's footer — a parquet/ORC file, or a byte range of a parquet
    file, which has the rows and compressed bytes of the row groups the
    range reads (a file in four ranges counts once) — cached by
    (entry, mtime_ns, size) so per-task optimize_plan never re-reads a
    footer it has already paid for."""
    from ..ops.parquet_scan import FileSplit, entry_path, split_row_groups

    path = entry_path(entry)
    try:
        st = os.stat(path)
    except OSError:
        return None
    key = (entry, st.st_mtime_ns, st.st_size)
    with _lock:
        lockset.check(_LOG, "_footer_cache")
        if key in _footer_cache:
            return _footer_cache[key]
    try:
        if path.endswith(".orc"):
            from ..io.orc import read_metadata
        else:
            from ..io.parquet import read_metadata
        meta = read_metadata(path)
        if isinstance(entry, FileSplit):
            mine = split_row_groups(entry, meta.row_groups)
            val = (sum(rg.rows for rg in mine), sum(rg.total_comp for rg in mine))
        else:
            val = (int(meta.num_rows), int(st.st_size))
    except Exception as e:  # noqa: BLE001 — an unreadable footer only
        # degrades the ESTIMATE; the scan itself will surface the real
        # typed error when it reads the file
        from . import errors

        errors.reraise_control(e)
        return None
    with _lock:
        lockset.check(_LOG, "_footer_cache")
        if len(_footer_cache) >= _FOOTER_CAP:
            _footer_cache.clear()
        _footer_cache[key] = val
    return val


def _walk_est(node, path: str, out: Dict[str, Tuple[int, int]],
              mem_rows: Dict[str, int]) -> Optional[Tuple[float, float]]:
    """Bottom-up cold estimate: returns (rows, bytes) or None when the
    subtree contains an unestimable leaf (IpcReaderExec, unknown)."""
    name = type(node).__name__
    kids = [_walk_est(c, f"{path}.{i}", out, mem_rows)
            for i, c in enumerate(node.children)]
    est: Optional[Tuple[float, float]] = None
    if name == "MemoryScanExec":
        rows = sum(b.num_rows for p in node._partitions for b in p)
        bts = sum(b.memory_size() for p in node._partitions for b in p)
        mem_rows[str(node.source_id)] = int(rows)
        est = (float(rows), float(bts))
    elif name in ("ParquetScanExec", "OrcScanExec"):
        rows = bts = 0
        ok = True
        for g in node.file_groups:
            for p in g:
                meta = _footer(p)
                if meta is None:
                    ok = False
                    break
                rows += meta[0]
                bts += meta[1]
            if not ok:
                break
        est = (float(rows), float(bts)) if ok else None
    elif name == "EmptyPartitionsExec":
        est = (0.0, 0.0)
    elif name == "FilterExec":
        if kids and kids[0] is not None:
            r, b = kids[0]
            est = (r * FILTER_SELECTIVITY, b * FILTER_SELECTIVITY)
    elif name == "FusedStageExec":
        if kids and kids[0] is not None:
            sel = 1.0
            for op in getattr(node, "ops", ()):
                if type(op).__name__ == "FilterExec":
                    sel *= FILTER_SELECTIVITY
            r, b = kids[0]
            est = (r * sel, b * sel)
    elif name in _AGGS:
        if kids and kids[0] is not None:
            r, b = kids[0]
            width = (b / r) if r > 0 else 8.0 * max(
                1, len(getattr(node.schema, "fields", ()) or ()))
            if not getattr(node, "groupings", None):
                est = (1.0, width)
            else:
                rows = max(1.0, r * AGG_SELECTIVITY)
                est = (rows, rows * width)
    elif name in _JOINS:
        if len(kids) == 2 and all(k is not None for k in kids):
            est = max(kids, key=lambda k: k[0])
    elif name == "LimitExec":
        if kids and kids[0] is not None:
            r, b = kids[0]
            rows = min(r, float(node.limit))
            est = (rows, b * (rows / r) if r > 0 else 0.0)
    elif name == "UnionExec":
        if kids and all(k is not None for k in kids):
            est = (sum(k[0] for k in kids), sum(k[1] for k in kids))
    elif name in _PASS_THROUGH:
        if len(kids) == 1 and kids[0] is not None:
            est = kids[0]
    # IpcReaderExec and unknown leaves: no cold estimate — the node
    # (and everything above it that depends on it) is left unstamped
    if est is not None:
        out[path] = (int(round(est[0])), int(round(est[1])))
    return est


def _stamp(node, path: str, est: Dict[str, Tuple[int, int]]) -> None:
    v = est.get(path)
    if v is not None:
        node.metrics.set("est_rows", int(v[0]))
        node.metrics.set("est_bytes", int(v[1]))
    for i, c in enumerate(node.children):
        _stamp(c, f"{path}.{i}", est)


def _baseline(node, path: str, out: Dict[str, Tuple[int, int]]) -> None:
    """Per-node output_rows/output_bytes at registration time: leaf
    instances (a served MemoryScanExec) are REUSED across plan builds,
    so actuals at flush are deltas from this baseline, not absolute
    snapshots."""
    m = node.metrics.snapshot()
    out[path] = (int(m.get("output_rows", 0)), int(m.get("output_bytes", 0)))
    for i, c in enumerate(node.children):
        _baseline(c, f"{path}.{i}", out)


def annotate(plan, fp) -> None:
    """Estimator entry point, called from ``optimize_plan`` right
    after ``record_plan``: compute cold estimates, overlay persisted
    actuals for the plan's fingerprint when the store has them, stamp
    ``est_rows``/``est_bytes`` into every node's MetricsSet, and
    register the instance for actuals collection at flush."""
    if not enabled():
        return
    est: Dict[str, Tuple[int, int]] = {}
    mem_rows: Dict[str, int] = {}
    _walk_est(plan, "0", est, mem_rows)
    stored = None
    if fp is not None and fp.exact:
        stored = _store_lookup(fp, mem_rows)
    if stored is not None:
        for path, rec in stored.get("nodes", {}).items():
            rows = rec.get("rows")
            if rows is not None and int(rows) > 0:
                est[path] = (int(rows), int(rec.get("bytes") or 0))
    _stamp(plan, "0", est)
    mem_key = tuple(sorted(mem_rows.items()))
    if fp is not None:
        key = (fp.digest, bool(fp.exact),
               tuple(tuple(s) for s in fp.sources), mem_key)
    else:
        key = (None, False, (), mem_key)
    base: Dict[str, Tuple[int, int]] = {}
    _baseline(plan, "0", base)
    with _lock:
        lockset.check(_LOG, "_live")
        if len(_live) < _LIVE_CAP:
            _live.append((key, plan, base))


# ----------------------------------------------- exchange histograms

_SHUFFLE_KEY_RE = re.compile(r"(shuffle_\d+)_\d+(?:\.data)?$")


def exchange_key(path: str) -> str:
    """Merge key for one logical exchange from a map-output path:
    ``.../shuffle_3_7.data -> shuffle_3`` (all map tasks of a shuffle
    fold into one histogram)."""
    m = _SHUFFLE_KEY_RE.search(os.path.basename(path))
    return m.group(1) if m else os.path.basename(path)


def note_exchange(key: str, op: str, rows, bytes_) -> None:
    """Fold one materialization's per-partition rows/bytes into the
    exchange histogram for ``key``.  Called under the producing
    exchange's own lock on some paths — this function only does array
    arithmetic under ``stats.registry`` (emission happens at flush)."""
    r = np.asarray(rows, dtype=np.int64)
    b = np.asarray(bytes_, dtype=np.int64)
    n = max(len(r), len(b))
    if n == 0:
        return
    if len(r) < n:
        r = np.pad(r, (0, n - len(r)))
    if len(b) < n:
        b = np.pad(b, (0, n - len(b)))
    with _lock:
        lockset.check(_LOG, "_exchanges")
        e = _exchanges.get(key)
        if e is None:
            if len(_exchanges) >= _EXCHANGE_CAP:
                return
            _exchanges[key] = {"op": op, "rows": r.copy(), "bytes": b.copy()}
            return
        if len(e["rows"]) < n:
            e["rows"] = np.pad(e["rows"], (0, n - len(e["rows"])))
            e["bytes"] = np.pad(e["bytes"], (0, n - len(e["bytes"])))
        e["rows"][:n] += r
        e["bytes"][:n] += b


# ---------------------------------------------------------- the store

def store_dir() -> str:
    if not _loaded:
        _load()
    return _STORE_DIR


def store_path(digest: str) -> str:
    return os.path.join(store_dir(), f"{digest}.json")


def _store_load(digest: str) -> Optional[Dict[str, Any]]:
    """Raw store read: None for a miss, a validated record, or
    StatsStoreCorruptError for anything unparseable/misshapen."""
    path = store_path(digest)
    try:
        with open(path) as f:
            raw = f.read()
    except OSError:
        return None
    try:
        rec = json.loads(raw)
    except ValueError as e:
        raise StatsStoreCorruptError(
            f"stats store entry {path} is not valid JSON") from e
    if (not isinstance(rec, dict)
            or rec.get("version") != STATS_STORE_VERSION
            or rec.get("digest") != digest
            or not isinstance(rec.get("nodes"), dict)
            or not isinstance(rec.get("sources"), list)
            or not isinstance(rec.get("mem_rows"), dict)):
        raise StatsStoreCorruptError(
            f"stats store entry {path} failed shape validation")
    return rec


def _store_lookup(fp, mem_rows: Dict[str, int]) -> Optional[Dict[str, Any]]:
    """Persisted actuals for ``fp``, validated against the CURRENT
    source versions (and observed MemoryScan row counts) exactly like
    the result cache — a stale or corrupt entry is dropped and counted
    as an invalidation.  Cached per digest; every reuse (cached loads
    included) emits ``stats_reused``, so a traced run that warmed the
    cache in an earlier untraced pass still logs its reuse."""
    if not _STORE_ON:
        return None
    from . import dispatch, trace

    digest = fp.digest
    with _lock:
        lockset.check(_LOG, "_store_cache")
        cached = _store_cache.get(digest, _MISSING)
    if cached is not _MISSING:
        if cached is not None:
            trace.emit("stats_reused", fingerprint=digest,
                       nodes=len(cached["nodes"]))
        return cached

    rec: Optional[Dict[str, Any]] = None
    invalid = False
    try:
        rec = _store_load(digest)
    except StatsStoreCorruptError:
        # narrow, deliberate: a corrupt entry is dropped and counted;
        # the estimator falls back to cold estimates
        invalid = True
        rec = None
    if rec is not None:
        want_sources = [list(s) for s in fp.sources]
        if (rec.get("sources") != want_sources
                or {str(k): int(v) for k, v in rec["mem_rows"].items()}
                != {str(k): int(v) for k, v in mem_rows.items()}):
            invalid = True
            rec = None
    if invalid:
        try:
            os.remove(store_path(digest))
        except OSError:
            pass
        dispatch.record("stats_store_invalidations")
    if rec is not None:
        dispatch.record("stats_store_hits")
        trace.emit("stats_reused", fingerprint=digest,
                   nodes=len(rec["nodes"]))
    else:
        dispatch.record("stats_store_misses")
    with _lock:
        lockset.check(_LOG, "_store_cache")
        if len(_store_cache) >= _STORE_CACHE_CAP:
            _store_cache.clear()
        _store_cache[digest] = rec
    return rec


def _store_write(digest: str, sources: tuple, mem_rows: Dict[str, int],
                 nodes: Dict[str, Dict[str, Any]]) -> bool:
    """Commit one digest's observed actuals: ``.inprogress`` temp +
    ``os.replace``, refused when the query's cancel scope already
    fired (a cancelled loser must not overwrite a winner's entry)."""
    from . import dispatch, trace
    from .context import current_cancel_scope

    rec = {"version": STATS_STORE_VERSION, "digest": digest,
           "sources": [list(s) for s in sources],
           "mem_rows": dict(mem_rows), "nodes": nodes}
    d = store_dir()
    tmp = os.path.join(d, f"{digest}.json.inprogress")
    try:
        os.makedirs(d, exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(rec, f)
        scope = current_cancel_scope()
        if scope is not None and scope.cancelled:
            os.remove(tmp)
            return False
        os.replace(tmp, store_path(digest))
    except OSError:
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False
    with _lock:
        lockset.check(_LOG, "_store_cache")
        _store_cache[digest] = rec
    dispatch.record("stats_store_stores")
    trace.emit("stats_persisted", fingerprint=digest, nodes=len(nodes))
    return True


# --------------------------------------------------------------- flush

def _collect(node, path: str, out: Dict[str, Dict[str, Any]],
             base: Dict[str, Tuple[int, int]]) -> None:
    m = node.metrics.snapshot()
    rec = out.get(path)
    if rec is None:
        rec = out[path] = {"op": node.name(), "est": None, "est_bytes": None,
                           "act": 0, "bytes": 0, "hll": None}
    if "est_rows" in m:
        rec["est"] = max(rec["est"] or 0, int(m["est_rows"]))
        rec["est_bytes"] = max(rec["est_bytes"] or 0,
                               int(m.get("est_bytes", 0)))
    b = base.get(path, (0, 0))
    rec["act"] += max(0, int(m.get("output_rows", 0)) - b[0])
    rec["bytes"] += max(0, int(m.get("output_bytes", 0)) - b[1])
    # sketches: consume-and-clear — flush runs at query-span exit with
    # every stream of this query drained, and a reused instance must
    # not double-report into the next query's flush
    hll = getattr(node, "_stats_hll", None)
    if hll is not None:
        node._stats_hll = None
        if rec["hll"] is None:
            rec["hll"] = HyperLogLog()
        rec["hll"].merge(hll)
    for i, c in enumerate(node.children):
        _collect(c, f"{path}.{i}", out, base)


def q_error(est: float, act: float) -> Optional[float]:
    """``max(est/act, act/est)`` — the standard symmetric cardinality
    drift measure; None when either side is unobserved (zero)."""
    if est <= 0 or act <= 0:
        return None
    return max(est / act, act / est)


def flush(query_id: str) -> Optional[Dict[str, Any]]:
    """Query-span exit: merge the live plan instances per digest,
    compute Q-error and skew findings, emit the typed events, persist
    exact digests with observed actuals, and stamp the monitor entry.
    Returns the summary (also served at ``/stats``)."""
    global _exchanges, _last
    if not enabled():
        return None
    with _lock:
        lockset.check(_LOG, "_live", "_exchanges")
        live = list(_live)
        _live.clear()
        exch = _exchanges
        _exchanges = {}
    if not live and not exch:
        return None

    # ---- merge plan instances per digest (act sums, est maxes)
    merged: Dict[tuple, Dict[str, Dict[str, Any]]] = {}
    for (key, plan, base) in live:
        nodes = merged.get(key)
        if nodes is None:
            nodes = merged[key] = {}
        _collect(plan, "0", nodes, base)

    qerror_max: Optional[float] = None
    drift: List[Dict[str, Any]] = []
    for (digest, exact, sources, mem_rows), nodes in merged.items():
        for path, rec in nodes.items():
            q = q_error(float(rec["est"] or 0), float(rec["act"]))
            if q is None:
                continue
            rec["q"] = q
            if qerror_max is None or q > qerror_max:
                qerror_max = q
            drift.append({"op": rec["op"], "path": path,
                          "est_rows": int(rec["est"]),
                          "act_rows": int(rec["act"]),
                          "q_error": round(q, 3)})
    drift.sort(key=lambda d: -d["q_error"])

    # ---- skew scan over the merged exchange histograms
    findings: List[Dict[str, Any]] = []
    skew_ratio: Optional[float] = None
    for key, e in exch.items():
        rows = e["rows"]
        if len(rows) < 2 or not rows.any():
            continue
        hot = int(np.argmax(rows))
        med = float(np.median(rows))
        ratio = float(rows[hot]) / max(med, 1.0)
        if skew_ratio is None or ratio > skew_ratio:
            skew_ratio = ratio
        if int(rows[hot]) >= _SKEW_MIN and ratio >= _SKEW_RATIO:
            findings.append({
                "exchange": key, "op": e["op"], "partition": hot,
                "rows": int(rows[hot]), "bytes": int(e["bytes"][hot]),
                "ratio": round(ratio, 2), "partitions": int(len(rows)),
            })

    # ---- emission + persistence, strictly outside the stats lock
    from . import dispatch, trace

    for f in findings:
        dispatch.record("stats_skew_findings")
        trace.emit("stats_skew_detected", **f)
    persisted = 0
    if _STORE_ON:
        for (digest, exact, sources, mem_rows), nodes in merged.items():
            if digest is None or not exact:
                continue
            total_act = sum(r["act"] for r in nodes.values())
            if total_act <= 0:
                continue  # e.g. served from the result cache: nothing
                # observed this run, keep the previous entry
            out_nodes = {}
            for path, rec in nodes.items():
                nrec: Dict[str, Any] = {"op": rec["op"],
                                        "rows": int(rec["act"]),
                                        "bytes": int(rec["bytes"])}
                if rec["hll"] is not None:
                    nrec["ndv"] = int(round(rec["hll"].estimate()))
                    nrec["hll"] = rec["hll"].to_list()
                out_nodes[path] = nrec
            if _store_write(digest, sources, dict(mem_rows), out_nodes):
                persisted += 1

    summary = {
        "query_id": query_id,
        "qerror_max": round(qerror_max, 3) if qerror_max is not None
        else None,
        "skew_ratio": round(skew_ratio, 2) if skew_ratio is not None
        else None,
        "nodes": len(drift),
        "drift": drift[:8],
        "findings": findings,
        "persisted": persisted,
    }
    try:
        from . import monitor

        monitor.note_query_stats(summary["qerror_max"],
                                 summary["skew_ratio"])
    except Exception as e:  # noqa: BLE001 — the monitor may be torn
        # down mid-flush; stats must still land in the summary
        from . import errors

        errors.reraise_control(e)
    with _lock:
        lockset.check(_LOG, "_last", "_findings")
        _last = summary
        _findings.extend(findings)
    return summary


# ------------------------------------------------------- introspection

def last_query_stats() -> Optional[Dict[str, Any]]:
    with _lock:
        lockset.check(_LOG, "_last")
        return dict(_last) if _last is not None else None


def recent_findings() -> List[Dict[str, Any]]:
    with _lock:
        lockset.check(_LOG, "_findings")
        return [dict(f) for f in _findings]


def snapshot() -> Dict[str, Any]:
    """The ``/stats`` endpoint document."""
    if not _loaded:
        _load()
    with _lock:
        lockset.check(_LOG, "_live", "_exchanges", "_last", "_findings")
        return {
            "enabled": _ARMED,
            "sketches": _SKETCHES,
            "store": {"enabled": _STORE_ON, "dir": _STORE_DIR},
            "skew": {"ratio": _SKEW_RATIO, "min_rows": _SKEW_MIN},
            "last": dict(_last) if _last is not None else None,
            "findings": [dict(f) for f in _findings],
            "pending_plans": len(_live),
            "pending_exchanges": len(_exchanges),
        }
