"""Serving-scale query cache hierarchy.

Two levels, both keyed by a deterministic **plan fingerprint**:

- **Level 1 — plan cache** (program reuse): operators canonicalize
  literal leaves into parameter ``Slot``s (exprs/compile.py
  ``slotify_literals``), so ``WHERE price > 5`` and ``WHERE price > 9``
  share one kernel-cache key and one compiled XLA program; the shifted
  values travel as traced scalars (``trace_slots`` contract,
  ops/base.py).  This module's part is the bookkeeping: the fingerprint
  computed at the ``ops.fusion.optimize_plan`` choke point identifies a
  plan STRUCTURE, and :func:`record_plan` counts whether that structure
  was seen before (hit = the kernel cache already holds its programs).

- **Level 2 — result cache** (:class:`ResultCache`): memoizes final
  result batches keyed by ``(fingerprint, slot values, source
  version)``.  The source version is derived from scan inputs — file
  ``(path, mtime_ns, size)`` for parquet/ORC, ``(source_id, epoch)``
  for memory tables — so any append or rewrite changes the key and the
  stale entry is dropped (invalidated), never served.  The cache is a
  byte-budgeted LRU registered as a :class:`memmgr.MemConsumer` OUTSIDE
  any owner scope (its memory is shared infrastructure, never metered
  against a pool quota); under host-memory pressure entries spill into
  the ``memmgr.try_new_spill`` ladder (host RAM half-budget, then disk
  with the diskmgr pressure ladder) and are promoted back on hit.

``QueryService`` consults the result cache BEFORE taking a
``FairShareGate`` device-lease turn — a hit is served entirely
off-device (zero lease turns, zero dispatches).
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import List, Optional, Tuple

from .. import conf
from .errors import reraise_control


# ---------------------------------------------------------------------
# plan fingerprint
# ---------------------------------------------------------------------

@dataclass(frozen=True)
class Fingerprint:
    """Canonical identity of a physical plan.

    ``digest``  — sha256 over the canonical structure (slot-blind: the
                  parameter VALUES are excluded, so literal-shifted
                  variants share a digest — the whole point).
    ``slots``   — the slot values in walk order (numpy scalars / python
                  scalars; the Level-2 key discriminator).
    ``sources`` — scan-version entries, e.g. ``("mem", id, epoch)`` or
                  ``("file", path, mtime_ns, size)``.
    ``exact``   — True only when every node had an exact structural
                  handler AND every leaf source is versioned; required
                  for result caching (an approximate fingerprint may
                  collide, which is fine for counters but would serve
                  WRONG ROWS from the result cache).
    """

    digest: str
    slots: tuple
    sources: tuple
    exact: bool

    @property
    def result_cacheable(self) -> bool:
        return self.exact

    def result_key(self) -> tuple:
        return (self.digest, self.slots, self.sources)


class _Uncacheable(Exception):
    """Internal: plan contains a node that cannot be fingerprinted at
    all (opaque identity-keyed state, e.g. a python UDF)."""


def _node_part(node, slots: list, sources: list, exact: list):
    """One node's canonical structure fragment.  Exact handlers append
    source-version entries for leaves and slot values for slotified
    operators; unknown node types fall back to a deterministic
    (class-name, schema) shape and clear ``exact`` — still useful for
    plan-cache counting and warmup stability, never for result reuse."""
    from ..ops.filter import FilterExec
    from ..ops.memory_scan import MemoryScanExec
    from ..ops.project import ProjectExec
    from .kernel_cache import key_cacheable, schema_key

    name = type(node).__name__

    if isinstance(node, MemoryScanExec):
        sources.append(("mem", node.source_id, node.epoch))
        return ("memscan", node.source_id, schema_key(node.schema))

    if name in ("ParquetScanExec", "OrcScanExec"):
        import os

        from ..exprs.compile import expr_key

        from ..ops.parquet_scan import entry_path

        # an entry is a path or a FileSplit: a range and a partitioned
        # file's values are part of the key
        paths = tuple(tuple(g) for g in node.file_groups)
        for g in node.file_groups:
            for p in map(entry_path, g):
                try:
                    st = os.stat(p)
                except OSError:
                    raise _Uncacheable(p)
                sources.append(("file", p, st.st_mtime_ns, st.st_size))
        pred = getattr(node, "predicate", None)
        return (name, paths, schema_key(node.schema),
                None if pred is None else expr_key(pred), node.batch_rows)

    if isinstance(node, FilterExec):
        if node._host_parts:
            raise _Uncacheable("host-fallback filter")
        slots.extend(node.trace_slots())
        return node._key

    if isinstance(node, ProjectExec):
        key = node.trace_key()
        if key is None:
            raise _Uncacheable("host-fallback project")
        slots.extend(node.trace_slots())
        return key

    if name == "FusedStageExec":
        slots.extend(node.trace_slots())
        return node.trace_key()

    if name == "ExpandExec":
        key = node.trace_key()
        if key is None:
            raise _Uncacheable("host-fallback expand")
        slots.extend(node.trace_slots())
        return key

    if name == "BufferPartitionExec":
        return ("buffer",)

    if name == "SortExec":
        from ..ops.sort import sort_fields_key

        return ("sort", schema_key(node.children[0].schema),
                sort_fields_key(node.fields), node.fetch)

    if name == "LimitExec":
        return ("limit", node.limit)

    if name == "RenameColumnsExec":
        return ("rename", tuple(node.schema.names))

    if name == "CoalesceBatchesExec":
        return ("coalesce", node.target_rows)

    if name == "UnionExec":
        return ("union", len(node.children))

    if name == "AggExec":
        from ..exprs.compile import expr_key
        from ..ops.sort import sort_fields_key

        key = (
            "agg", str(node.mode), schema_key(node.children[0].schema),
            tuple((g.name, expr_key(g.expr)) for g in node.groupings),
            tuple((a.fn, a.name, None if a.expr is None else expr_key(a.expr))
                  for a in node.aggs),
            None if node.pre_filter is None else expr_key(node.pre_filter),
            None if node.post_sort is None else sort_fields_key(node.post_sort),
            node.post_fetch,
        )
        if not key_cacheable(key):
            raise _Uncacheable("opaque agg expr")
        return key

    if name in ("NativeShuffleExchangeExec", "ShuffleWriterExec",
                "RssShuffleWriterExec", "IciShuffleExchangeExec"):
        # structural only: shuffle ids and staging paths are per-run
        return (name, _partitioning_part(node.partitioning))

    if name == "IpcReaderExec":
        # a stage subplan's shuffle input: deterministic structure, but
        # its CONTENT is another stage's output — not a versioned
        # source, so result-exactness is off (plan-cache counting of
        # reduce-stage programs still works)
        exact[0] = False
        return ("ipc_reader", schema_key(node.schema), node.num_partitions())

    # deterministic fallback: enough for plan-cache tallies and warmup
    # fingerprint-stability checks, never for result reuse
    exact[0] = False
    try:
        sk = schema_key(node.schema)
    except Exception as e:  # noqa: BLE001 — schema optional on exotic nodes
        reraise_control(e)
        sk = None
    return ("~" + name, sk)


def _partitioning_part(part) -> tuple:
    from ..exprs.compile import expr_key

    name = type(part).__name__
    exprs = getattr(part, "exprs", None)
    fields = getattr(part, "fields", None)
    return (
        name, part.num_partitions,
        None if exprs is None else tuple(expr_key(e) for e in exprs),
        None if fields is None else tuple(
            (expr_key(f.expr), f.ascending, f.nulls_first) for f in fields),
    )


def plan_fingerprint(plan) -> Optional[Fingerprint]:
    """Fingerprint a physical plan (optimized or not).  Returns None
    when the plan embeds un-keyable state (python UDFs, broadcast
    identities) — fail-closed: such plans are simply uncacheable."""
    slots: list = []
    sources: list = []
    exact = [True]

    def walk(node) -> tuple:
        part = _node_part(node, slots, sources, exact)
        return (part, tuple(walk(c) for c in node.children))

    try:
        shape = walk(plan)
    except _Uncacheable:
        return None
    except Exception as e:  # noqa: BLE001 — fail-closed, audited below
        # a handler tripping over an unexpected attribute must never
        # break query execution — the plan is just uncacheable; but a
        # control-flow error (cancel, deadline, verifier finding)
        # must keep propagating, not vanish into "cache miss"
        reraise_control(e)
        return None
    from .kernel_cache import key_cacheable

    if not key_cacheable(shape):
        return None
    digest = hashlib.sha256(repr(shape).encode()).hexdigest()[:32]
    return Fingerprint(digest, tuple(slots), tuple(sources),
                       exact=bool(exact[0]))


# ---------------------------------------------------------------------
# Level 1: plan-cache bookkeeping
# ---------------------------------------------------------------------

_plan_lock = threading.Lock()  # leaf: guards only the seen-digest set
_plan_seen: "OrderedDict[str, int]" = OrderedDict()
_PLAN_SEEN_CAP = 4096


def record_plan(plan) -> Optional[Fingerprint]:
    """Fingerprint ``plan`` and count a plan-cache hit (structure seen
    before — its compiled programs are already in the kernel cache,
    parameter shifts included) or miss (first sighting: this execution
    pays the compiles).  Called at the ``optimize_plan`` choke point;
    returns the fingerprint for downstream reuse, or None when
    unfingerprintable or the plan cache is disabled."""
    if not bool(conf.CACHE_PLAN_ENABLED.get()):
        return None
    fp = plan_fingerprint(plan)
    if fp is None:
        return None
    with _plan_lock:
        hit = fp.digest in _plan_seen
        _plan_seen[fp.digest] = _plan_seen.get(fp.digest, 0) + 1
        _plan_seen.move_to_end(fp.digest)
        while len(_plan_seen) > _PLAN_SEEN_CAP:
            _plan_seen.popitem(last=False)
    from . import dispatch, trace

    if hit:
        dispatch.record("plan_cache_hits")
    else:
        dispatch.record("plan_cache_misses")
    trace.emit("plan_cache", action="hit" if hit else "miss",
               fingerprint=fp.digest)
    return fp


def plan_cache_stats() -> dict:
    with _plan_lock:
        return {"distinct_plans": len(_plan_seen)}


# ---------------------------------------------------------------------
# Level 2: result cache
# ---------------------------------------------------------------------

class _Entry:
    __slots__ = ("schema", "nbytes", "batches", "spill", "counts")

    def __init__(self, schema, nbytes: int, batches, counts):
        self.schema = schema
        self.nbytes = nbytes
        self.batches = batches    # host batches, or None when spilled
        self.spill = None         # memmgr.Spill when spilled
        self.counts = counts      # per-batch row counts (spill serde)


def _batches_nbytes(batches) -> int:
    total = 0
    for b in batches:
        for c in b.columns:
            total += getattr(c.data, "nbytes", 0)
            total += getattr(c.validity, "nbytes", 0)
            if c.lengths is not None:
                total += getattr(c.lengths, "nbytes", 0)
    return total


def _storable(batches) -> bool:
    from ..schema import TypeKind

    return all(
        f.dtype.kind != TypeKind.OPAQUE
        for b in batches for f in b.schema.fields)


class ResultCache:
    """Byte-budgeted LRU over final query results (Level 2).

    memmgr contract: registered as a consumer outside any owner scope
    (``_owner`` None — infrastructure memory, never a pool-quota
    neighbor).  ``spill()`` serializes the LRU-coldest entries into the
    ``try_new_spill`` ladder and reports their bytes freed; a hit on a
    spilled entry promotes it back to RAM.  The cache's OWN budget
    (``spark.blaze.cache.result.maxBytes``) bounds resident + spilled
    bytes together via LRU eviction."""

    name = "result_cache"

    #: guarded-by declaration (analysis/guarded.py)
    GUARDED_BY = {"_entries": "querycache.state",
                  "_resident_bytes": "querycache.state",
                  "_total_bytes": "querycache.state"}
    GUARDED_REFS = ("_entries",)

    def __init__(self):
        from ..analysis.locks import make_lock
        from .memmgr import MemConsumer

        # composition over inheritance for the consumer half so this
        # module stays importable without a jax-initialized memmgr
        class _Consumer(MemConsumer):
            name = "result_cache"

            def __init__(c):
                super().__init__()

            def spill(c) -> int:
                return self._spill_coldest()

        self._lock = make_lock("querycache.state")
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        self._resident_bytes = 0
        self._total_bytes = 0
        self._consumer = _Consumer()

    # ------------------------------------------------------ helpers

    def _register(self) -> None:
        if self._consumer._manager is not None:
            return
        from .memmgr import MemManager

        # registered from here — NEVER inside a query's owner scope
        # — so the ambient owner tag is None and this memory is
        # invisible to pool-quota accounting
        mgr = MemManager.get()
        mgr.register_consumer(self._consumer)
        try:
            # joining a manager (first store, or re-joining after a
            # test-harness memmgr reset): publish the bytes already
            # resident so the pressure ledger starts consistent
            # instead of inheriting whatever a torn-down manager
            # last recorded for this consumer
            with self._lock:
                self._consumer.set_mem_used_no_trigger(
                    self._resident_bytes)
        except BaseException:
            # a consumer the manager can see but whose accounting
            # never initialized must not stay registered — it would
            # wedge spill-pressure arithmetic for every other consumer
            mgr.unregister_consumer(self._consumer)
            raise

    def _emit(self, action: str, fp_digest: str, nbytes: int = 0) -> None:
        """One counter + one trace event per cache transition.  The
        dispatch.record calls carry LITERAL names (the metric-name
        drift gate regex-scans source for them)."""
        from . import dispatch, trace

        if action == "hit":
            dispatch.record("result_cache_hits")
        elif action == "miss":
            dispatch.record("result_cache_misses")
        elif action == "store":
            dispatch.record("result_cache_stores")
        elif action == "invalidate":
            dispatch.record("result_cache_invalidations")
        elif action == "evict":
            dispatch.record("result_cache_evictions")
        elif action == "spill":
            dispatch.record("result_cache_spills")
        trace.emit("result_cache", action=action,
                   fingerprint=fp_digest, bytes=int(nbytes))

    # ------------------------------------------------------ core API

    def lookup(self, fp: Fingerprint):
        """Return the cached host batches for ``fp`` (exact key:
        digest + slot values + source versions), or None.  A same-
        structure entry whose source version moved on is dropped here —
        the invalidation the counters and trace surface."""
        if not bool(conf.CACHE_RESULT_ENABLED.get()) or not fp.exact:
            return None
        key = fp.result_key()
        stale_bytes = 0
        result = None
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
                if e.batches is None and e.spill is not None:
                    self._promote_locked(e)
                result = None if e.batches is None else list(e.batches)
            else:
                # drop superseded versions of the same (digest, slots)
                stale = [k for k in self._entries
                         if k[0] == key[0] and k[1] == key[1]]
                for k in stale:
                    stale_bytes += self._drop_locked(k)
        if stale_bytes:
            self._emit("invalidate", fp.digest, stale_bytes)
            self._consumer.trigger_spill_check()
        if result is not None:
            self._emit("hit", fp.digest, _batches_nbytes(result))
            return result
        self._emit("miss", fp.digest)
        return None

    def store(self, fp: Fingerprint, batches) -> bool:
        """Memoize a query's final host batches under ``fp``.  Refused
        (False) for non-exact fingerprints, opaque columns, or entries
        over ``spark.blaze.cache.result.maxEntryBytes``."""
        if not bool(conf.CACHE_RESULT_ENABLED.get()) or not fp.exact:
            return False
        if not batches or not _storable(batches):
            return False
        batches = [b.to_host() for b in batches]
        nbytes = _batches_nbytes(batches)
        if nbytes > int(conf.CACHE_RESULT_MAX_ENTRY_BYTES.get()):
            return False
        self._register()
        key = fp.result_key()
        budget = int(conf.CACHE_RESULT_MAX_BYTES.get())
        evicted: List[Tuple[str, int]] = []
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._release_locked(old)
            e = _Entry(batches[0].schema, nbytes, batches,
                       tuple(b.num_rows for b in batches))
            self._entries[key] = e
            self._resident_bytes += nbytes
            self._total_bytes += nbytes
            while self._total_bytes > budget and len(self._entries) > 1:
                k, _ = next(iter(self._entries.items()))
                evicted.append((k[0], self._drop_locked(k)))
            self._consumer.set_mem_used_no_trigger(self._resident_bytes)
        for digest, freed in evicted:
            self._emit("evict", digest, freed)
        self._emit("store", fp.digest, nbytes)
        self._consumer.trigger_spill_check()
        return True

    def invalidate_all(self) -> int:
        """Drop every entry (test/ops hook); returns bytes freed."""
        with self._lock:
            freed = self._total_bytes
            for k in list(self._entries):
                self._drop_locked(k)
            self._consumer.set_mem_used_no_trigger(0)
        return freed

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "resident_bytes": self._resident_bytes,
                "total_bytes": self._total_bytes,
            }

    # --------------------------------------------- locked internals

    def _drop_locked(self, key) -> int:
        e = self._entries.pop(key)
        self._release_locked(e)
        return e.nbytes

    def _release_locked(self, e: _Entry) -> None:
        if e.batches is not None:
            self._resident_bytes -= e.nbytes
        if e.spill is not None:
            e.spill.release()
            e.spill = None
        e.batches = None
        self._total_bytes -= e.nbytes
        self._consumer.set_mem_used_no_trigger(self._resident_bytes)

    def _promote_locked(self, e: _Entry) -> None:
        """Rehydrate a spilled entry (hit path).  Frame reads and
        deserialization run under the cache lock: spill streams are
        one-shot cursors, so a concurrent reader must never interleave.
        Only inner-ranked locks (memmgr.manager, integrity.state,
        diskmgr.state, ledger.state) are reachable from here."""
        from ..io.batch_serde import deserialize_batch

        batches = []
        while True:
            frame = e.spill.read_frame()
            if frame is None:
                break
            batches.append(deserialize_batch(frame, e.schema))
        e.spill.release()
        e.spill = None
        e.batches = batches
        self._resident_bytes += e.nbytes
        self._consumer.set_mem_used_no_trigger(self._resident_bytes)

    def _spill_coldest(self) -> int:
        """memmgr spill hook: serialize resident entries, LRU-coldest
        first, into the spill ladder until half the resident bytes are
        off-RAM.  Serialization runs under the cache lock (see
        _promote_locked for the lock-order argument); the spill write
        path is deliberately emission-free (memmgr.FileSpill)."""
        from ..io.batch_serde import serialize_batch
        from .memmgr import try_new_spill

        freed = 0
        spilled: List[Tuple[str, int]] = []
        with self._lock:
            target = self._resident_bytes // 2
            for key, e in list(self._entries.items()):
                if freed >= target or self._resident_bytes == 0:
                    break
                if e.batches is None:
                    continue
                sp = try_new_spill()
                for b in e.batches:
                    sp.write_frame(serialize_batch(b))
                sp.complete()
                e.spill = sp
                e.batches = None
                self._resident_bytes -= e.nbytes
                freed += e.nbytes
                spilled.append((key[0], e.nbytes))
            self._consumer.set_mem_used_no_trigger(self._resident_bytes)
        for digest, nbytes in spilled:
            self._emit("spill", digest, nbytes)
        return freed


_result_cache: Optional[ResultCache] = None
_result_cache_lock = threading.Lock()


def result_cache() -> ResultCache:
    """The process-wide result cache singleton."""
    global _result_cache
    with _result_cache_lock:
        if _result_cache is None:
            _result_cache = ResultCache()
        return _result_cache


def cache_stats() -> dict:
    """Both cache levels in one introspection block: L1/L2 sizes plus
    the lifetime counters — the service's stats() "cache" section
    (/queries), the --watch cache line, and the EXPLAIN header all
    render from this one shape."""
    from . import dispatch

    c = dispatch.counters()
    return {
        "plan": plan_cache_stats(),
        "result": result_cache().stats(),
        "counters": {k: c.get(k, 0) for k in (
            "plan_cache_hits", "plan_cache_misses",
            "result_cache_hits", "result_cache_misses",
            "result_cache_stores", "result_cache_invalidations",
            "result_cache_evictions", "result_cache_spills")},
    }


class ResultTee:
    """Miss-path collector for the service: tees a query's emitted
    result batches into host copies and stores them on clean
    completion.  Collection is abandoned (not the query) the moment
    the accumulated size crosses ``maxEntryBytes`` — a huge result
    never doubles its own residency just to be refused at store."""

    __slots__ = ("_fp", "_batches", "_nbytes", "_cap")

    def __init__(self, fp: Optional[Fingerprint]):
        armed = (fp is not None and fp.result_cacheable
                 and bool(conf.CACHE_RESULT_ENABLED.get()))
        self._fp = fp
        self._batches: Optional[list] = [] if armed else None
        self._nbytes = 0
        self._cap = int(conf.CACHE_RESULT_MAX_ENTRY_BYTES.get())

    def add(self, batch) -> None:
        if self._batches is None:
            return
        if not _storable([batch]):
            self._batches = None
            return
        host = batch.to_host()
        self._nbytes += _batches_nbytes([host])
        if self._nbytes > self._cap:
            self._batches = None
            return
        self._batches.append(host)

    def commit(self) -> bool:
        """Store the collected batches (call only on CLEAN completion —
        a cancelled or failed query's partial tee must be dropped)."""
        if self._batches is None or not self._batches:
            return False
        return result_cache().store(self._fp, self._batches)


def reset_for_tests() -> None:
    """Drop both cache levels (test isolation)."""
    global _result_cache
    with _plan_lock:
        _plan_seen.clear()
    with _result_cache_lock:
        rc, _result_cache = _result_cache, None
    if rc is not None:
        rc.invalidate_all()
        if rc._consumer._manager is not None:
            rc._consumer._manager.unregister_consumer(rc._consumer)
