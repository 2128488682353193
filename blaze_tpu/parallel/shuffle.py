"""Native shuffle.

≙ reference shuffle core (shuffle/mod.rs:49-137 ShuffleRepartitioner,
sort_repartitioner.rs, shuffle_writer_exec.rs, ipc_reader_exec.rs) and
the JVM plumbing (BlazeShuffleManager, BlazeShuffleWriterBase,
BlazeBlockStoreShuffleReaderBase).

Spark-exactness: partition ids are murmur3(seed42) pmod N — computed on
device (exprs/hash.py, golden-tested), so a map stage can feed vanilla
Spark reducers and vice versa.

Writer pipeline per batch (SortShuffleRepartitioner equivalent):
device kernel sorts rows by pid and returns per-pid counts; the host
slices the sorted staging buffer per pid and appends to per-partition
buffers, spilling serialized frames under memory pressure; finish
concatenates buffers+spills per pid into ``.data`` and writes the
``.index`` offsets (BlazeShuffleWriterBase.nativeShuffleWrite parses
the same file pair).
"""

from __future__ import annotations

import os
import queue
import re
import struct
import tempfile
import threading
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import conf
from ..batch import Column, RecordBatch, bucket_capacity, concat_batches
from ..exprs.compile import lower
from ..exprs.hash import murmur3_columns, pmod
from ..exprs.ir import Expr
from ..io.batch_serde import deserialize_batch, serialize_batch
from ..io.ipc_compression import (
    IpcFrameReader, IpcFrameWriter, compress_frame, iter_blob_frames,
)
from ..ops.base import BatchStream, ExecNode
from ..runtime import monitor
from ..runtime import diskmgr, dispatch, faults, integrity, ledger, lockset, trace
from ..runtime.context import TaskContext
from ..runtime.diskmgr import DiskExhaustedError
from ..runtime.integrity import BlockCorruptionError
from ..runtime.memmgr import MemConsumer, Spill, try_new_spill
from ..runtime.retry import FetchFailedError
from ..schema import Schema


# ------------------------------------------------------------ partitioning

class Partitioning:
    """Base marker; subclasses carry num_partitions."""

    num_partitions: int = 1


@dataclass
class SinglePartitioning(Partitioning):
    num_partitions: int = 1


@dataclass
class HashPartitioning(Partitioning):
    """murmur3(seed42) pmod — Spark HashPartitioning exact."""

    exprs: Sequence[Expr]
    num_partitions: int


@dataclass
class RoundRobinPartitioning(Partitioning):
    num_partitions: int = 1


@dataclass
class RangePartitioning(Partitioning):
    """Range partitioning on sort keys (Spark's global-sort exchange):
    partition p holds rows in [boundary_{p-1}, boundary_p) of the key
    order, so per-partition sorts + ordered partition reads give a
    total order.  Boundaries are exact order-statistic rows computed
    device-side by the in-process exchange (Spark samples; with the
    map output already in HBM the exact quantiles are as cheap).

    ``boundaries``: optional precomputed boundary ORDER WORDS (tuple of
    uint64 arrays, one per key word, each (num_partitions-1,)) — the
    scheduler's driver-side sampling pass fills this in so map tasks on
    the file-shuffle/serde path can assign pids locally (≙ Spark's
    RangePartitioner sample job shipped inside the ShuffleDependency)."""

    fields: Sequence  # SortField
    num_partitions: int
    boundaries: Optional[tuple] = None


def _sort_by_pid_body(cols, pids, n_out, num_rows):
    """Sort rows by partition id; returns (sorted cols, counts[n_out],
    sort permutation).  A plain traceable function so the fused
    shuffle-write program (tier 5) can inline it after the map chain
    and pid computation; jitted standalone as :func:`_sort_by_pid`."""
    cap = pids.shape[0]
    live = jnp.arange(cap) < num_rows
    key = jnp.where(live, pids.astype(jnp.uint32), jnp.uint32(n_out))
    row_idx = jnp.arange(cap, dtype=jnp.int32)
    skey, sidx = jax.lax.sort((key, row_idx), num_keys=1, is_stable=True)
    sorted_cols = tuple(c.take(sidx) for c in cols)
    return sorted_cols, _sorted_counts(skey, n_out), sidx


def _sorted_counts(skey, n_out):
    """Rows a partition (int64[n_out]) of pid-sorted keys, dead rows
    carrying ``n_out`` after every live one: where each partition's run
    starts, differenced.  Not a ``segment_sum`` of the live rows: that is
    a scatter-add of every row, which the TPU runs nearly serially."""
    starts = jnp.searchsorted(skey, jnp.arange(n_out + 1, dtype=skey.dtype),
                              side="left", method="scan_unrolled")
    return jnp.diff(starts).astype(jnp.int64)


def _build_pid_sort_kernel():
    return partial(jax.jit, static_argnames=("n_out",))(_sort_by_pid_body)


_PID_SORT_KERNEL = None


def _sort_by_pid(cols, pids, n_out, num_rows):
    """The standalone (unfused) pid sort, registered through
    kernel_cache so its dispatches/compiles are counted and it rides
    the persistent compile cache like every other kernel (a bare
    module-level ``jax.jit`` is invisible to both — the
    ``jit.uncached`` lint rule now pins this).  Memoized at module
    level after the first resolution: the key is constant, and
    re-resolving through the process-wide registry lock per batch
    would serialize concurrent map tasks on it."""
    global _PID_SORT_KERNEL
    kernel = _PID_SORT_KERNEL
    if kernel is None:
        from ..runtime.kernel_cache import cached_kernel

        kernel = _PID_SORT_KERNEL = cached_kernel(
            ("shuffle_pid_sort",), _build_pid_sort_kernel)
    return kernel(cols, pids, n_out=n_out, num_rows=num_rows)


def non_opaque_cols(schema: Schema, cols) -> tuple:
    """Subset of columns that can enter jitted kernels (opaque python
    object columns are host-only — ≙ UserDefinedArray, uda.rs)."""
    from ..batch import split_opaque_indexes

    dev_idx, _ = split_opaque_indexes(schema)
    return tuple(cols[i] for i in dev_idx)


def sort_cols_by_pid(schema: Schema, cols, pids, n_out: int, num_rows: int):
    """Pid-sort a batch's columns, routing OPAQUE columns host-side
    around the jitted kernel (one sidx sync when any are present).
    Returns (sorted cols in schema order, counts)."""
    from ..batch import split_opaque_indexes

    dev_idx, opq = split_opaque_indexes(schema)
    if not opq:
        s, counts, _ = _sort_by_pid(tuple(cols), pids, n_out, num_rows)
        return list(s), counts
    s_dev, counts, sidx = _sort_by_pid(
        tuple(cols[i] for i in dev_idx), pids, n_out, num_rows
    )
    h = np.asarray(sidx)
    out: List = [None] * len(cols)
    for j, i in enumerate(dev_idx):
        out[i] = s_dev[j]
    for i in opq:
        out[i] = cols[i].take(h)
    return out, counts


# ------------------------------------------------------------- repartition

class ShuffleRepartitioner(MemConsumer):
    """Buffers rows per output partition; spills serialized frames.
    ≙ SortShuffleRepartitioner (sort_repartitioner.rs:47-318)."""

    name = "shuffle"

    #: guarded-by declaration (analysis/guarded.py): the async stager,
    #: the map-task producer, and the memory manager's cross-thread
    #: spills all mutate the staged buffers
    GUARDED_BY = {"_buffers": "shuffle.repartitioner",
                  "_buffered_bytes": "shuffle.repartitioner",
                  "_spills": "shuffle.repartitioner",
                  "_part_rows": "shuffle.repartitioner"}
    GUARDED_REFS = ("_buffers", "_spills", "_part_rows")

    def __init__(self, schema: Schema, n_out: int, metrics, task_attempt_id: int = 0):
        super().__init__()
        self.schema = schema
        self.n_out = n_out
        self.metrics = metrics
        self.task_attempt_id = task_attempt_id
        from ..analysis.locks import make_lock

        self._buffers: List[List[RecordBatch]] = [[] for _ in range(n_out)]
        self._buffered_bytes = 0
        # per-partition row tally across the whole map task (spills
        # included) — the runtime-stats skew histogram's raw input
        self._part_rows = np.zeros(n_out, dtype=np.int64)
        self._spills: List[Tuple[Spill, List[Tuple[int, int]]]] = []  # (spill, [(pid, nframes)])
        # commit replayability marker for _commit_with_recovery: True
        # once write_output has consumed spill frames (written only by
        # the committing task's own thread)
        self._commit_drained = False
        # the lock the async stager, map-task producer, and the memory
        # manager's cross-thread spills share — ranked in the declared
        # hierarchy (analysis/locks.py) OUTSIDE memmgr/metrics/trace
        self._lock = make_lock("shuffle.repartitioner")

    def insert_sorted(self, sorted_batch_host: RecordBatch, counts: np.ndarray) -> None:
        """Append per-pid slices of a pid-sorted host batch.

        Holds the consumer lock: the memory manager may invoke
        ``spill()`` from ANOTHER map task's thread at any moment, and
        an append racing the spill's read-then-clear silently DROPS the
        batch (observed as wrong counts at SF0.1 under a capped
        budget)."""

        def slice_col(c: Column, lo: int, hi: int) -> Column:
            s = lambda a: None if a is None else np.asarray(a)[lo:hi]
            return Column(
                c.dtype, s(c.data), s(c.validity), s(c.lengths),
                None if c.children is None
                else tuple(slice_col(k, lo, hi) for k in c.children),
            )

        offsets = np.concatenate([[0], np.cumsum(counts)])
        cols = sorted_batch_host.columns
        with self._lock:
            lockset.check(self, "_buffers", "_buffered_bytes", "_part_rows")
            for pid in range(self.n_out):
                lo, hi = int(offsets[pid]), int(offsets[pid + 1])
                if hi == lo:
                    continue
                b = RecordBatch(self.schema, [slice_col(c, lo, hi) for c in cols], hi - lo)
                self._buffers[pid].append(b)
                self._buffered_bytes += b.memory_size()
                self._part_rows[pid] += hi - lo
            buffered = self._buffered_bytes
        self.update_mem_used(buffered)

    def spill(self) -> int:
        # the spill.write fault probe fires BEFORE the consumer lock:
        # an injected spill failure still aborts cleanly (rows kept,
        # task retries), and the probe's trace emission no longer rides
        # three helper hops inside the critical section (the
        # lock.emit-under-lock waiver this used to need is gone).  The
        # @corrupt probe likewise fires out here (it emits when it
        # matches); the flip itself is armed on the Spill and applied
        # post-encode inside.  The probe only counts when there is
        # observably SOMETHING to spill — memmgr documents that a
        # concurrent spill of an already-drained victim "finds no
        # state and returns 0", and such a benign empty call must not
        # consume (and vacuously emit) a corruption rule whose hit
        # number means "the Nth spill that wrote frames".  The locked
        # peek is stale only against that same benign concurrent drain.
        faults.hit("spill.write")
        with self._lock:
            lockset.check(self, "_buffered_bytes")
            has_rows = self._buffered_bytes > 0
        corrupt_next = has_rows and faults.corrupt("spill.write")
        with self._lock:
            lockset.check(self, "_buffers", "_buffered_bytes", "_spills")
            if self._buffered_bytes == 0:
                return 0
            sp = try_new_spill()
            if corrupt_next:
                sp.corrupt_next_frame()
            manifest: List[Tuple[int, int]] = []
            try:
                for pid in range(self.n_out):
                    if not self._buffers[pid]:
                        continue
                    merged = _host_concat(self._buffers[pid], self.schema)
                    sp.write_frame(serialize_batch(merged))
                    manifest.append((pid, 1))
                sp.complete()
            except BaseException:
                # spill-abort: release the partial spill and KEEP the
                # in-memory buffers (cleared only after complete()
                # succeeds) so a failed spill never loses rows — the
                # triggering task fails cleanly and its retry still
                # sees every inserted batch
                sp.release()
                raise
            for pid, _ in manifest:
                self._buffers[pid] = []
            self._spills.append((sp, manifest))
            freed = self._buffered_bytes
            self._buffered_bytes = 0
            # no-trigger accounting while our own lock is held: the
            # full update_mem_used would run the watermark check, which
            # spills OTHER consumers while we hold this one's lock —
            # consumer-lock -> consumer-lock is a deadlock cycle with a
            # concurrent spill running the opposite direction (the
            # lock-order checker, analysis/locks.py, flags exactly
            # this).  Usage only DECREASED, so no check is owed anyway.
            self.set_mem_used_no_trigger(0)
            self.metrics.add("spill_count", 1)
            self.metrics.add("spilled_bytes", freed)
            return freed

    def partition_rows(self) -> np.ndarray:
        """Per-partition row tally for the whole map task (spills
        included) — consumed by the runtime-stats skew histogram after
        a successful commit."""
        with self._lock:
            lockset.check(self, "_part_rows")
            return self._part_rows.copy()

    def release(self) -> None:
        """Teardown for an attempt that will NOT commit (failed,
        cancelled, or a speculative loser): drop the staged buffers and
        release every spill this repartitioner still holds.  Spill
        files were previously reclaimed only when ``write_output``
        drained them — a cancelled attempt's ``blaze_spill_*`` temp
        files survived until process exit (the cancellation resource
        leak).  Idempotent; a no-op after a successful commit."""
        with self._lock:
            lockset.check(self, "_buffers", "_buffered_bytes", "_spills")
            spills, self._spills = self._spills, []
            self._buffers = [[] for _ in range(self.n_out)]
            self._buffered_bytes = 0
            # no-trigger accounting under our own lock, same contract
            # as spill(): usage only decreases, no watermark check owed
            self.set_mem_used_no_trigger(0)
        for sp, _ in spills:
            sp.release()

    def write_output(self, data_path: str, index_path: str) -> List[int]:
        """Merge memory + spills per pid into .data/.index.  Returns
        partition lengths.  Holds the lock across the whole drain so a
        late memory-manager spill cannot move buffers out mid-write.
        The fault-injection sites and every trace emission live OUTSIDE
        the lock: emission does file IO and can raise, and holding an
        operator lock across either is the PR 3 deadlock class the
        ``lock.emit-under-lock`` lint rule pins.

        Disk-pressure ladder: the spills are drained into memory ONCE
        (:meth:`_drain_spills_locked`), so an ``ENOSPC``/``EIO`` from
        the file write can safely reclaim stale staging debris and
        retry the file half without losing spilled rows; a second
        failure escalates to typed retryable ``DiskExhaustedError``
        (the task retry rebuilds everything)."""
        self._commit_drained = False
        faults.hit("shuffle.write", attempt=self.task_attempt_id, detail=data_path)
        recovered = False
        with self._lock:
            lockset.check(self, "_buffers", "_buffered_bytes", "_spills")
            self._commit_drained = True  # spill frames consumed below:
            # a failure past this point is not replayable in-place
            spilled = self._drain_spills_locked()
            try:
                lengths = self._write_files(spilled, data_path, index_path)
            except OSError as e:
                if not diskmgr.is_disk_pressure(e):
                    raise
                # rung 2, reclaim + one retry (emission-free under the
                # lock; the recovery event lands after release below)
                diskmgr.reclaim(extra_roots=[os.path.dirname(data_path)
                                             or "."])
                try:
                    lengths = self._write_files(spilled, data_path,
                                                index_path)
                    recovered = True
                except OSError as e2:
                    if not diskmgr.is_disk_pressure(e2):
                        raise
                    raise DiskExhaustedError("shuffle.write", e2) from e2
        if recovered:
            diskmgr.record_recovery()
            trace.emit("disk_pressure", action="retry",
                       site="shuffle.write", detail=data_path)
        if faults.corrupt("shuffle.write", attempt=self.task_attempt_id,
                          detail=data_path):
            # @corrupt: post-commit bit-rot on the COMMITTED data file
            # — the reduce-side checksum verification, not this writer,
            # must catch it (zero silent wrong results).  Probed AFTER
            # the rename so the hit number means "the Nth block that
            # actually committed" (a failed commit never consumes — or
            # vacuously emits — a corruption rule).
            integrity.flip_byte_in_file(data_path)
        nbytes = sum(lengths)
        dispatch.record("shuffle_bytes_written", nbytes)
        trace.emit("shuffle_write", bytes=nbytes,
                   blocks=sum(1 for ln in lengths if ln),
                   attempt=self.task_attempt_id, path=data_path)
        return lengths

    def _drain_spills_locked(self) -> Dict[int, List[RecordBatch]]:
        # decode spills back per pid (read once, in insertion order)
        spilled: Dict[int, List[RecordBatch]] = {}
        for sp, manifest in self._spills:
            for pid, nframes in manifest:
                for _ in range(nframes):
                    frame = sp.read_frame()
                    assert frame is not None
                    spilled.setdefault(pid, []).append(deserialize_batch(frame, self.schema))
            sp.release()
        self._spills = []  # drained: the teardown release() owes nothing
        return spilled

    def _write_files(self, spilled: Dict[int, List[RecordBatch]],
                     data_path: str, index_path: str) -> List[int]:
        lengths: List[int] = []
        offsets = [0]
        codec = str(conf.IO_COMPRESSION_CODEC.get())
        # commit/abort contract (≙ RssPartitionWriterBase.abort, and
        # Spark's shuffle IndexShuffleBlockResolver writing tmp files
        # then renaming): stage both files under .inprogress names and
        # rename on success — index LAST, since reduce_blocks keys on
        # index existence.  A failed attempt leaves no committed
        # output, so its retry can never double-count toward the
        # reduce barrier and readers never see a torn file.  The temp
        # names are ATTEMPT-QUALIFIED: a speculative backup racing the
        # original writes the same final paths, and a shared temp name
        # would let one attempt's abort unlink the other's staging
        # mid-write — with unique temps the two atomic renames commute
        # (first commit wins; the loser re-replaces with byte-identical
        # content or is cancelled before reaching here).
        suffix = f".inprogress.a{self.task_attempt_id}"
        tmp_data, tmp_index = data_path + suffix, index_path + suffix
        # resource-ledger tracking (runtime/ledger.py): both staging
        # temps must be GONE by the end of this function — renamed into
        # place on commit, unlinked on abort — so the finally releases
        # unconditionally and a leak shows up at query end instead
        ledger.acquire("inprogress", tmp_data)
        ledger.acquire("inprogress", tmp_index)
        try:
            with open(tmp_data, "wb") as f:
                w = IpcFrameWriter(f, codec)
                for pid in range(self.n_out):
                    start = w.bytes_written
                    parts = spilled.get(pid, []) + self._buffers[pid]
                    if parts:
                        merged = _host_concat(parts, self.schema)
                        w.write(serialize_batch(merged))
                    lengths.append(w.bytes_written - start)
                    offsets.append(w.bytes_written)
            with open(tmp_index, "wb") as f:
                for off in offsets:
                    f.write(struct.pack("<Q", off))
            os.replace(tmp_data, data_path)
            os.replace(tmp_index, index_path)
        except BaseException:
            for p in (tmp_data, tmp_index):
                try:
                    os.unlink(p)
                except OSError:
                    pass
            raise
        finally:
            ledger.release("inprogress", tmp_data)
            ledger.release("inprogress", tmp_index)
        return lengths


def _host_concat(batches: List[RecordBatch], schema: Schema) -> RecordBatch:
    if len(batches) == 1:
        b = batches[0]
        return b
    return concat_batches(batches).to_host()


def _commit_with_recovery(rep: "ShuffleRepartitioner", data_path: str,
                          index_path: str) -> List[int]:
    """Drive the map-output commit with the storage-failure handlers
    that must live OUTSIDE the repartitioner lock:

    - a corrupt SPILL frame surfacing during the drain
      (``BlockCorruptionError``) is counted and leaves a
      ``block_corruption`` event before propagating — the task retry
      rebuilds the consumer's state from its (still-buffered) input;
    - disk pressure raised BEFORE any spill was drained (the
      ``shuffle.write@N@enospc`` entry probe fires at write_output's
      first line) reclaims, records the recovery, and retries the
      whole commit once — nothing was consumed, so the retry sees
      every row.  Mid-write pressure is handled INSIDE write_output
      (drain-once + file-half retry) and escalates as the typed
      ``DiskExhaustedError``, which is deliberately NOT retried here.
    """
    try:
        # the corruption accounting wraps BOTH commit attempts: a
        # corrupt spill frame surfacing inside the disk-retry path
        # (sibling except clauses don't catch each other) must still
        # be counted and leave its detection event
        return _commit_with_disk_retry(rep, data_path, index_path)
    except BlockCorruptionError as e:
        dispatch.record("corruption_detected")
        trace.emit("block_corruption", site="spill.read",
                   path=e.path, detail=str(e)[:300],
                   attempt=rep.task_attempt_id)
        raise


def _commit_with_disk_retry(rep: "ShuffleRepartitioner", data_path: str,
                            index_path: str) -> List[int]:
    try:
        return rep.write_output(data_path, index_path)
    except OSError as e:
        if not diskmgr.is_disk_pressure(e) \
                or getattr(rep, "_commit_drained", True):
            # not pressure, or the commit already consumed its spill
            # frames: an in-place retry would silently drop them —
            # escalate to the task retry, which rebuilds everything
            raise
        diskmgr.reclaim(extra_roots=[os.path.dirname(data_path) or "."])
        diskmgr.record_recovery()
        trace.emit("disk_pressure", action="retry", site="shuffle.write",
                   detail=data_path)
        return rep.write_output(data_path, index_path)


# ------------------------------------------------------------------- execs

def _hash_pids_body(schema, exprs, n_out):
    """The Spark-exact hash partition-id computation (murmur3 seed42
    pmod) as a plain traceable body — ONE definition shared by the
    standalone pid kernel and the tier-5 fused write program, so fused
    and unfused map tasks can never place a row differently."""

    def pids(cols, num_rows):
        cap = cols[0].validity.shape[0]
        env = {f.name: c for f, c in zip(schema.fields, cols)}
        key_cols = [lower(e, schema, env, cap) for e in exprs]
        return pmod(murmur3_columns(key_cols), n_out)

    return pids


def _build_pid_kernels(schema, exprs, n_out):
    hash_pids = jax.jit(_hash_pids_body(schema, exprs, n_out))

    @jax.jit
    def hash_pids_pallas(cols, num_rows):
        # whole pipeline (expr lowering, word-plane split, fused
        # kernel) traced once per shape bucket, like the XLA path
        from ..kernels import pallas_ops

        cap = cols[0].validity.shape[0]
        env = {f.name: c for f, c in zip(schema.fields, cols)}
        planes, widths, valids = [], [], []
        for e in exprs:
            c = lower(e, schema, env, cap)
            p, w = pallas_ops.column_word_planes(c)
            planes += p
            widths.append(w)
            valids.append(c.validity)
        return pallas_ops.murmur3_pids(planes, widths, valids, n_out)

    return hash_pids, hash_pids_pallas


def _build_fused_write_kernel(out_schema, fns, pid_mode, exprs, n_out,
                              slot_counts=()):
    """ONE program per map-stage batch (fusion tier 5): the traceable
    map chain, the partition-id computation, the pid sort, and the
    per-partition counts, all in a single XLA executable.  The
    unfused path pays chain + hash + sort dispatches per batch; over a
    remote chip each is ~70-80 ms of turnaround.  ``fns`` are the
    chain's trace transforms bottom->top (may be empty: a bare writer
    still folds hash+sort into one program); ``pid_mode`` is "hash"
    (murmur3 pmod over ``exprs``), "rr" (round-robin, offset passed as
    a traced arg), or "range" (boundary bsearch; ``exprs`` carries the
    SortFields and the driver-computed boundary word arrays arrive as
    TRACED args, so shifted boundaries reuse the compiled program).
    ``slot_counts`` gives each fn's slotified-literal count
    (trace_slots contract, ops/base.py): the caller appends the
    flattened slot values after the input columns and the chain deals
    each transform its own group, so parameter-shifted chains reuse
    this one program."""
    n_slots = sum(slot_counts)

    def chain(cols, n):
        cols = tuple(cols)
        slots = cols[len(cols) - n_slots:] if n_slots else ()
        cols = cols[:len(cols) - n_slots] if n_slots else cols
        i = 0
        for fn, cnt in zip(fns, slot_counts):
            cols, n = fn(tuple(cols) + slots[i:i + cnt], n)
            i += cnt
        return cols, n

    if pid_mode == "hash":
        pid_body = _hash_pids_body(out_schema, exprs, n_out)

        def body(cols, num_rows):
            cols, n = chain(cols, num_rows)
            pids = pid_body(cols, n)
            sorted_cols, counts, _ = _sort_by_pid_body(tuple(cols), pids, n_out, n)
            return sorted_cols, counts

        return jax.jit(body)

    if pid_mode == "range":
        from .exchange import _build_range_kernels

        # plain @jax.jit kernels: nested jit inlines into THIS program
        # (the instrumented copies on the writer instance serve the
        # unfused/degraded path and would count phantom dispatches)
        key_words, _, pids_fn = _build_range_kernels(out_schema, exprs, n_out)

        def range_body(cols, num_rows, boundaries):
            cols, n = chain(cols, num_rows)
            words = key_words(tuple(cols), n)
            pids = pids_fn(words, boundaries)
            sorted_cols, counts, _ = _sort_by_pid_body(tuple(cols), pids, n_out, n)
            return sorted_cols, counts

        return jax.jit(range_body)

    def rr_body(cols, num_rows, rr):
        cols, n = chain(cols, num_rows)
        cap = cols[0].validity.shape[0]
        pids = (jnp.arange(cap, dtype=jnp.int32) + rr) % n_out
        sorted_cols, counts, _ = _sort_by_pid_body(tuple(cols), pids, n_out, n)
        # next batch's offset stays DEVICE-RESIDENT (the post-chain
        # live count is a traced scalar): syncing it per batch would
        # stall the dispatch loop one RTT between programs
        next_rr = (rr + jnp.int32(n)) % jnp.int32(n_out)
        return sorted_cols, counts, next_rr

    return jax.jit(rr_body)


def _insert_host(rep: "ShuffleRepartitioner", schema: Schema, item) -> None:
    """Stage one batch's pid-sorted device output into the
    repartitioner: device->host transfer, per-pid slicing, buffering
    under memmgr accounting.  ``item`` = (cols, counts, num_rows);
    num_rows None means "resolve from counts" (the fused write path:
    the live row count after the fused chain IS the counts total)."""
    cols, counts, n = item
    with trace.span("device_read") as read:
        counts = np.asarray(counts)
        if n is None:
            n = int(counts.sum())
        host = RecordBatch(schema, list(cols), n).to_host()
    # this read's part of the enclosing exchange_write: the wait for the
    # map body's program plus the transfer
    dispatch.record("exchange_d2h_ns", read.ns)
    rep.insert_sorted(host, counts)


class _AsyncInserter:
    """Double-buffered shuffle write (conf
    ``spark.blaze.shuffle.asyncWrite``): batch N's device output is
    transferred/sliced/buffered on this thread while batch N+1's
    program is already dispatched on the caller's.  Bounded queue
    (``...asyncWrite.queueDepth``) so device outputs in flight stay
    capped; staging errors surface on the producer at the next put()
    or at close().  The repartitioner's own lock makes insert_sorted
    safe against concurrent memmgr spills, so commit-by-rename
    semantics in write_output are untouched.

    The caller's thread says when it waited for the stager: a
    ``trace.span("inserter_full")`` around a ``put()`` that found the
    queue full, ``trace.span("inserter_drain")`` around ``close()``'s
    flush and join, and ``inserter_items`` (batches put, one record at
    ``close()``, whether or not a put blocked).  The stager's own
    ``exchange_write`` spans lie inside a ``blaze:exchange_stager``
    annotation that carries the ``ids`` (the task's ``stage`` /
    ``partition``) of the writer that created it."""

    _DONE = object()

    #: audited deliberately-unlocked state (analysis/guarded.py): one
    #: writer each, reader tolerates staleness by a bounded window
    LOCK_FREE = {
        "_errs": "appended only by the stager thread; the producer's "
                 "racy emptiness read delays surfacing by at most one "
                 "put(), and close() re-checks after the join barrier",
        "_aborted": "written only by the producer in abort(); the "
                    "stager's racy read can at worst stage one batch "
                    "into a repartitioner whose output is discarded",
    }

    def __init__(self, rep: "ShuffleRepartitioner", schema: Schema,
                 depth: int, metrics, **ids):
        self._rep = rep
        self._schema = schema
        self._metrics = metrics
        self._ids = ids
        self._items = 0  # the producer's alone
        self._q: "queue.Queue" = queue.Queue(max(1, depth))
        self._errs: List[BaseException] = []
        self._aborted = False
        # the stager runs under a COPY of the creating task's context
        # (like the speculation runner's attempt threads): the memmgr
        # accounting it lands — mem_watermark/spill trace events, the
        # owner-tag quota hook — attributes to the owning query's
        # trace id and monitor entry instead of a context-less thread
        import contextvars

        ctx = contextvars.copy_context()
        self._thread = threading.Thread(
            target=lambda: ctx.run(self._drain),
            name="shuffle-async-insert", daemon=True
        )
        self._thread.start()

    def _drain(self) -> None:
        with trace.annotation("exchange_stager", **self._ids):
            while True:
                item = self._q.get()
                if item is _AsyncInserter._DONE:
                    return
                if self._errs or self._aborted:
                    continue  # task failing/cancelled: discard, don't stage
                try:
                    with self._metrics.timer("shuffle_host_stage_time",
                                             trace.span("exchange_write")):
                        _insert_host(self._rep, self._schema, item)
                except BaseException as e:  # noqa: BLE001 — surfaced to producer
                    self._errs.append(e)

    def put(self, item) -> None:
        if self._errs:
            raise self._errs[0]
        self._items += 1
        try:
            self._q.put_nowait(item)
        except queue.Full:
            with trace.span("inserter_full"):
                self._q.put(item)

    def close(self) -> None:
        """Flush and join; re-raises any staging error — MUST happen
        before write_output so every inserted batch reaches the file."""
        with trace.span("inserter_drain"):
            self._q.put(self._DONE)
            self._thread.join()
        dispatch.record("inserter_items", self._items)
        if self._errs:
            raise self._errs[0]

    def abort(self) -> None:
        """Failure/cancellation teardown: stop the stager without
        raising (the original error is already propagating) and skip
        still-queued batches — their transfers would feed a
        repartitioner whose output is being discarded."""
        self._aborted = True
        self._q.put(self._DONE)  # worker always drains, so this returns
        self._thread.join()


class ShuffleWriterExec(ExecNode):
    """Runs the child and writes this map task's partitioned output.
    ≙ shuffle_writer_exec.rs:52-186 (Single vs Sort repartitioner
    selection) — the output stream is empty (side effect only), like
    the reference's shuffle-write plans."""

    def __init__(self, child: ExecNode, partitioning: Partitioning, data_path: str, index_path: str):
        super().__init__([child])
        self.partitioning = partitioning
        self.data_path = data_path
        self.index_path = index_path
        self.partition_lengths: Optional[List[int]] = None
        # fusion tier 5 (absorb_traceable_chain): one program per batch
        # covering chain + pids + pid-sort + counts
        self._fused_write = None
        self._fused_fns: List = []
        self._fused_fn_keys: tuple = ()
        self._fused_slot_args: tuple = ()   # flattened, chain order
        self._fused_slot_groups: tuple = ()  # per-op, for the eager rung
        self._eager_chain = None  # per-op fallback kernels (OOM rung 3)
        self._out_schema: Optional[Schema] = None
        if isinstance(partitioning, HashPartitioning):
            from ..batch import split_opaque_indexes

            # pid kernels see only the non-opaque columns (keys are
            # never opaque; opaque columns bypass jit entirely)
            dev_idx, _ = split_opaque_indexes(child.schema)
            schema = Schema([child.schema.fields[i] for i in dev_idx])
            exprs = list(partitioning.exprs)
            n_out = partitioning.num_partitions

            from ..exprs.compile import expr_key
            from ..runtime.kernel_cache import cached_kernel, schema_key

            self._hash_pids_xla, self._hash_pids_pallas = cached_kernel(
                ("shuffle_pids", schema_key(schema),
                 tuple(expr_key(e) for e in exprs), n_out),
                lambda: _build_pid_kernels(schema, exprs, n_out),
            )
            # pallas fast path: decided HERE from the key types (string
            # and nested keys have no word-plane form and hash with
            # XLA) — dispatch on type, so nothing downstream needs to
            # catch a kernel failure and mistake it for one
            from ..exprs.compile import infer_dtype
            from ..kernels.pallas_ops import key_type_supported

            self._pallas_pids = bool(conf.PALLAS_ENABLE.get()) and all(
                key_type_supported(infer_dtype(e, schema)) for e in exprs)
        elif isinstance(partitioning, RangePartitioning):
            from ..exprs.compile import expr_key
            from ..runtime.kernel_cache import cached_kernel, schema_key
            from .exchange import _build_range_kernels

            self._range_kernels = cached_kernel(
                ("shuffle_range", schema_key(child.schema),
                 tuple((expr_key(f.expr), f.ascending, f.nulls_first)
                       for f in partitioning.fields),
                 partitioning.num_partitions),
                lambda: _build_range_kernels(
                    child.schema, partitioning.fields, partitioning.num_partitions
                ),
            )

    def _range_pids(self, cols, num_rows, boundaries):
        """``boundaries`` are the stream-hoisted device arrays (one
        ``jnp.asarray`` per stream, not per batch — the per-batch
        conversion re-staged the boundary words on every dispatch)."""
        key_words, _, pids_fn = self._range_kernels
        words = key_words(tuple(cols), num_rows)
        return pids_fn(words, boundaries)

    def _hash_pids(self, cols, num_rows):
        """Partition ids of one batch.  No ``except``: a Pallas
        lowering or compile failure raises — the XLA path is for key
        types and backends the kernel does not serve, not for a kernel
        that broke."""
        if self._pallas_pids:
            from ..kernels import pallas_ops

            if pallas_ops.available():
                return self._hash_pids_pallas(cols, num_rows)
        return self._hash_pids_xla(cols, num_rows)

    @property
    def schema(self) -> Schema:
        # after tier-5 absorption the chain nodes are gone from the
        # tree; the writer's output schema stays the CHAIN's output
        return self._out_schema if self._out_schema is not None else self.children[0].schema

    # ------------------------------------- tier-5 fused shuffle write

    def absorb_traceable_chain(self) -> None:
        """Fold the traceable chain feeding this writer (often one
        FusedStageExec — its trace contract composes its ops) plus the
        partition-id computation, pid sort, and per-partition counts
        into ONE cached program per batch (``ops.fusion`` tier 5).
        Applies to hash, round-robin, and range partitioning over >1
        output partitions with no opaque (host-only) columns (range
        passes the driver-computed boundary words as TRACED args);
        single-partition writes move nothing worth fusing.

        Blocking-boundary fusion: when the node under the chain is a
        FINAL agg (with no fused fetch clamp), its finalize program
        becomes the chain's BOTTOM transform — the agg then emits its
        RAW state batch (``emit_state``) and the finalize, the map
        chain, the pids, and the pid sort all run as the ONE per-batch
        program, with no intermediate finalized batch crossing the
        host boundary.  Idempotent; a no-op when the gate fails (the
        per-kernel path below runs unchanged — the fallback the
        differential tests pin)."""
        from ..batch import split_opaque_indexes

        if self._fused_write is not None:
            return
        part = self.partitioning
        n_out = part.num_partitions
        if (
            not isinstance(part, (HashPartitioning, RoundRobinPartitioning,
                                  RangePartitioning))
            or n_out <= 1
        ):
            return
        from ..ops.fusion import traceable_chain_from

        ops, cur, buffered = traceable_chain_from(self.children[0])
        out_schema = self.children[0].schema
        bottom = cur if ops else self.children[0]
        if (
            split_opaque_indexes(out_schema)[1]
            or split_opaque_indexes(bottom.schema)[1]
        ):
            return  # opaque python columns never enter jitted programs

        from ..exprs.compile import expr_key
        from ..runtime.kernel_cache import cached_kernel, schema_key

        fns = [op.trace_fn() for op in reversed(ops)]  # bottom -> top
        keys = tuple(op.trace_key() for op in reversed(ops))
        # slot structure is a function of the op keys (slotified expr
        # keys pin where every slot sits), so caching on `keys` alone
        # stays sound; only the VALUES differ across shifted variants
        slot_groups = tuple(op.trace_slots() for op in reversed(ops))
        slot_counts = tuple(len(g) for g in slot_groups)

        from ..ops.agg import AggExec, AggMode

        agg = None
        if (
            isinstance(bottom, AggExec)
            and bottom.mode == AggMode.FINAL
            and bottom.post_fetch is None
            and not split_opaque_indexes(bottom._state_schema)[1]
        ):
            # the finalize (with any fused post_sort inside it) joins
            # the chain as its bottom transform over the STATE schema;
            # pid exprs still evaluate over the chain OUTPUT schema
            agg = bottom
            fin_raw = dispatch.raw(agg._finalize_kernel)
            fns = [lambda cols, n, _f=fin_raw: (_f(cols, n), n)] + fns
            keys = (("agg_finalize",) + agg._kernel_key,) + keys
            slot_groups = ((),) + slot_groups
            slot_counts = (0,) + slot_counts

        if isinstance(part, HashPartitioning):
            exprs = list(part.exprs)
            key = ("fused_shuffle_write", "hash", schema_key(out_schema),
                   keys, tuple(expr_key(e) for e in exprs), n_out)
            mode, pid_arg = "hash", exprs
        elif isinstance(part, RangePartitioning):
            fields = list(part.fields)
            key = ("fused_shuffle_write", "range", schema_key(out_schema),
                   keys,
                   tuple((expr_key(f.expr), f.ascending, f.nulls_first)
                         for f in fields),
                   n_out)
            mode, pid_arg = "range", fields
        else:
            key = ("fused_shuffle_write", "rr", schema_key(out_schema),
                   keys, n_out)
            mode, pid_arg = "rr", None
        builder = lambda: _build_fused_write_kernel(  # noqa: E731
            out_schema, fns, mode, pid_arg, n_out, slot_counts)
        if agg is not None:
            agg.emit_state = True
        self._fused_write = cached_kernel(key, builder)
        self._fused_fns = fns
        self._fused_fn_keys = keys
        self._fused_slot_args = tuple(v for g in slot_groups for v in g)
        self._fused_slot_groups = slot_groups
        self._out_schema = out_schema
        if ops:
            from ..ops.fusion import BufferPartitionExec

            self.children[0] = BufferPartitionExec(cur) if buffered else cur
            dispatch.record_max("fused_stage_len", len(ops) + 1)

    def _degraded_chain(self, cols, num_rows):
        """Apply the absorbed map chain as per-operator programs — the
        OOM ladder's eager rung for the tier-5 fused write (the fused
        program is gone, but the chain's TRANSFORMS must still apply or
        the fallback would write untransformed rows).  Returns
        ``(cols, n)`` with the live count synced to host (the unfused
        pid path needs it as a plain int)."""
        if self._eager_chain is None:
            from ..runtime.oom import build_eager_kernels

            self._eager_chain = build_eager_kernels(
                list(zip(self._fused_fn_keys, self._fused_fns)))
        for kernel, slots in zip(self._eager_chain,
                                 self._fused_slot_groups):
            cols, num_rows = kernel(tuple(cols) + slots, num_rows)
        return list(cols), int(num_rows)

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        if (
            isinstance(self.partitioning, RangePartitioning)
            and self.partitioning.boundaries is None
        ):
            raise NotImplementedError(
                "range partitioning needs global boundaries: run the "
                "scheduler's boundary pass (or the in-process exchange)"
            )

        def stream():
            from ..batch import DeviceRing
            from ..runtime import oom as _oom

            n_out = self.partitioning.num_partitions
            out_schema = self.schema
            rep = ShuffleRepartitioner(
                out_schema, n_out, self.metrics, ctx.task_attempt_id
            )
            ctx.mem.register_consumer(rep)
            inserter: Optional[_AsyncInserter] = None
            ring: Optional[DeviceRing] = None
            committed = False
            try:
                if bool(conf.SHUFFLE_ASYNC_WRITE.get()):
                    inserter = _AsyncInserter(
                        rep, out_schema,
                        int(conf.SHUFFLE_ASYNC_QUEUE_DEPTH.get()), self.metrics,
                        stage=ctx.stage_id, partition=ctx.partition,
                    )
                    # two-slot device staging ring: batch N's pid-sorted
                    # output stays device-resident while batch N+1's
                    # program dispatches; only then does N's host
                    # transfer start on the inserter thread
                    ring = DeviceRing()
                rr = 0
                rr_dev = jnp.int32(0)  # fused RR offset, device-resident
                use_fused = self._fused_write is not None
                # stream-hoisted per-batch invariant: boundary device
                # arrays are resolved ONCE here, not inside the
                # dispatch loop
                boundaries_dev = None
                if (
                    isinstance(self.partitioning, RangePartitioning)
                    and self.partitioning.boundaries is not None
                ):
                    boundaries_dev = tuple(
                        jnp.asarray(b) for b in self.partitioning.boundaries)
                for batch in self.children[0].execute(partition, ctx):
                    if not ctx.is_task_running():
                        return
                    # heartbeat hookpoint: the map task's write loop is
                    # the longest driver-invisible stretch of a query
                    monitor.tick()
                    item = None
                    if use_fused:
                        # tier 5: ONE program returns the chain output
                        # already pid-sorted plus per-pid counts
                        try:
                            with self.metrics.timer("elapsed_compute"):
                                part_t = self.partitioning
                                if isinstance(part_t, RoundRobinPartitioning):
                                    sorted_cols, counts, rr_dev = self._fused_write(
                                        tuple(batch.columns) + self._fused_slot_args,
                                        batch.num_rows, rr_dev
                                    )
                                elif isinstance(part_t, RangePartitioning):
                                    sorted_cols, counts = self._fused_write(
                                        tuple(batch.columns) + self._fused_slot_args,
                                        batch.num_rows, boundaries_dev
                                    )
                                else:
                                    sorted_cols, counts = self._fused_write(
                                        tuple(batch.columns) + self._fused_slot_args,
                                        batch.num_rows
                                    )
                            item = (list(sorted_cols), counts, None)
                        except Exception as exc:  # noqa: BLE001
                            if not _oom.is_resource_exhausted(exc):
                                raise
                            # OOM ladder (spill+retry already ran at the
                            # dispatch choke point): decompose to the
                            # per-kernel path for the REST of the stream
                            _oom.record_eager_fallback("fused_shuffle_write")
                            use_fused = False
                            if isinstance(self.partitioning,
                                          RoundRobinPartitioning):
                                # resync the device-resident offset so
                                # the host-side path continues exactly
                                rr = trace.read_scalar(rr_dev)
                    if item is None:
                        with self.metrics.timer("elapsed_compute"):
                            cols, n = list(batch.columns), batch.num_rows
                            if self._fused_write is not None:
                                # the absorbed chain's transforms still
                                # apply, one program per op
                                cols, n = self._degraded_chain(
                                    tuple(cols), n)
                            cap = cols[0].validity.shape[0] if cols \
                                else batch.capacity
                            if isinstance(self.partitioning, HashPartitioning) and n_out > 1:
                                pids = self._hash_pids(
                                    non_opaque_cols(out_schema, cols), n,
                                )
                            elif isinstance(self.partitioning, RangePartitioning) and n_out > 1:
                                if boundaries_dev is None:
                                    boundaries_dev = tuple(
                                        jnp.asarray(b)
                                        for b in self.partitioning.boundaries)
                                pids = self._range_pids(cols, n, boundaries_dev)
                            elif isinstance(self.partitioning, RoundRobinPartitioning) and n_out > 1:
                                pids = (jnp.arange(cap, dtype=jnp.int32) + rr) % n_out
                                rr = (rr + n) % n_out
                            else:
                                pids = jnp.zeros(cap, jnp.int32)
                            sorted_cols, counts = sort_cols_by_pid(
                                out_schema, cols, pids, n_out, n
                            )
                        item = (list(sorted_cols), counts, n)
                    if inserter is not None:
                        # overlap: host staging of batch N runs on the
                        # inserter thread while batch N+1 dispatches;
                        # the ring holds the newest output device-side
                        # so the NEXT program is enqueued before this
                        # one's transfer begins
                        for due in ring.put(item):
                            inserter.put(due)
                    else:
                        with trace.span("exchange_write"):
                            _insert_host(rep, out_schema, item)
                if inserter is not None:
                    for due in ring.flush():
                        inserter.put(due)
                    inserter.close()
                    inserter = None
                if not ctx.is_task_running():
                    # cancelled (a speculative loser): a cooperatively
                    # exiting CHILD yields nothing, so the per-batch
                    # check above never fires — committing here would
                    # overwrite the winner's committed output with an
                    # empty/partial one (chaos-sweep-found)
                    return
                with self.metrics.timer("output_io_time",
                                        trace.span("exchange_write")):
                    self.partition_lengths = _commit_with_recovery(
                        rep, self.data_path, self.index_path)
                self.metrics.add("data_size", sum(self.partition_lengths))
                committed = True
                # per-partition histogram for the runtime-stats skew
                # scan: all map tasks of one shuffle fold into one
                # histogram keyed off the map-output path
                from ..runtime import stats as _stats

                if _stats.enabled():
                    _stats.note_exchange(
                        _stats.exchange_key(self.data_path),
                        f"{self.name()}"
                        f"[{type(self.partitioning).__name__}]",
                        rep.partition_rows(), self.partition_lengths)
            finally:
                if inserter is not None:
                    # cancel/failure mid-ring: the ringed device outputs
                    # feed a repartitioner being discarded — drop them
                    # instead of staging (chaos cancel-storm arm)
                    if ring is not None:
                        ring.drop()
                    inserter.abort()
                if not committed:
                    # failed OR cancelled attempt: reclaim the staged
                    # buffers and any spill FILES now — they were
                    # previously only reclaimed at process exit (the
                    # cancellation resource leak)
                    rep.release()
                ctx.mem.unregister_consumer(rep)
            return
            yield  # pragma: no cover — empty stream marker

        return stream()


BlockObject = Union[bytes, Tuple[str, int, int]]  # bytes | (path, offset, length)

_MAP_FILE_RE = re.compile(r"shuffle_\d+_(\d+)\.data$")


def block_map_id(block: "BlockObject") -> Optional[int]:
    """The producing MAP TASK id of a file-backed shuffle block (parsed
    from the ``shuffle_<sid>_<mapid>.data`` naming contract of
    :class:`LocalShuffleManager`), or None for in-memory blocks — the
    attribution that lets a fetch failure name exactly which map
    outputs to regenerate instead of re-running the whole stage."""
    if isinstance(block, bytes):
        return None
    m = _MAP_FILE_RE.search(os.path.basename(block[0]))
    return int(m.group(1)) if m else None


class IpcReaderExec(ExecNode):
    """Shuffle-read source: pulls BlockObjects from the resources map
    and streams decompressed batches.  ≙ ipc_reader_exec.rs:59-461 +
    BlazeBlockStoreShuffleReaderBase.readIpc."""

    def __init__(self, schema: Schema, resource_id: str, num_partitions: int = 1):
        super().__init__([])
        self._schema = schema
        self.resource_id = resource_id
        self._num_partitions = num_partitions

    @property
    def schema(self) -> Schema:
        return self._schema

    def num_partitions(self) -> int:
        return self._num_partitions

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        def stream():
            blocks = ctx.resources.get(f"{self.resource_id}.{partition}")
            fetched = {"bytes": 0, "blocks": 0}
            try:
                yield from self._read_blocks(blocks, partition, ctx, fetched)
            finally:
                # emitted on ANY exit — a limit above the exchange can
                # close the stream early, and the successfully-read
                # blocks counted so far were still fetched
                if fetched["blocks"]:
                    trace.emit("shuffle_fetch", resource=self.resource_id,
                               partition=partition, bytes=fetched["bytes"],
                               blocks=fetched["blocks"])

        return stream()

    def _fetch_failed(self, block, partition: int,
                      e: BaseException) -> FetchFailedError:
        """Wrap bad producer bytes as the typed fetch failure, with the
        integrity bookkeeping: a checksum-verified corruption counts
        ``corruption_detected`` and leaves a ``block_corruption``
        event; a file-backed block that has now failed TWICE at the
        same path is QUARANTINED (renamed ``.corrupt``, kept for
        forensics, its ``.index`` dropped) so recovery regenerates it
        in full instead of a third identical failure."""
        mid = block_map_id(block)
        path = None if isinstance(block, bytes) else block[0]
        site = ("broadcast.fetch"
                if self.resource_id.startswith("broadcast_")
                else "shuffle.fetch")
        if isinstance(e, BlockCorruptionError):
            dispatch.record("corruption_detected")
            quarantined = False
            if path is not None and integrity.note_corruption(path) >= 2:
                quarantined = integrity.quarantine(path) is not None
                if quarantined:
                    dispatch.record("blocks_quarantined")
            trace.emit("block_corruption", site=site,
                       resource=self.resource_id, path=path,
                       detail=str(e)[:300], quarantined=quarantined)
        return FetchFailedError(
            self.resource_id, partition, cause=e,
            map_ids=None if mid is None else [mid],
        )

    def _read_blocks(self, blocks, partition: int, ctx: TaskContext,
                     fetched: dict) -> BatchStream:
        for block in blocks:
            with self.metrics.timer("shuffle_read_total_time",
                                    trace.span("exchange_read")):
                faults.hit(
                    "shuffle.fetch",
                    attempt=ctx.task_attempt_id,
                    detail=self.resource_id,
                )
                payloads: List[bytes] = []
                try:
                    if isinstance(block, bytes):
                        # the shared verified walker: flagged frames
                        # checksum-verify, a block trailer (broadcast
                        # blobs carry one) is checked and consumed
                        payloads.extend(iter_blob_frames(
                            block, site=self.resource_id))
                    else:
                        path, offset, length = block
                        with open(path, "rb") as f:
                            f.seek(offset)
                            payloads.extend(IpcFrameReader(
                                f, length, site=self.resource_id,
                                path=path))
                except (OSError, struct.error, ValueError, EOFError) as e:
                    # missing/torn/corrupt block: surface as a
                    # typed fetch failure so the scheduler knows to
                    # regenerate the producing map stage rather
                    # than uselessly re-running this reader against
                    # the same bad bytes (≙ FetchFailedException);
                    # the block path names the producing map task, so
                    # recovery can re-run JUST that one
                    raise self._fetch_failed(block, partition, e) from e
                # counted only once the block's payloads are in hand:
                # a failed fetch must not report bytes it never read
                fetched["blocks"] += 1
                fetched["bytes"] += (
                    len(block) if isinstance(block, bytes) else block[2]
                )
            for p in payloads:
                # decode + H2D of one payload: the span again, closed
                # before the yield
                with trace.span("exchange_read"):
                    try:
                        # decode stays streaming (one payload at a
                        # time) but INSIDE the fetch guard: a
                        # committed-but-corrupt block can survive
                        # decompress and only fail here — still bad
                        # producer bytes, not a transient compute error
                        b = deserialize_batch(p, self._schema)
                    except (struct.error, ValueError, EOFError) as e:
                        raise self._fetch_failed(block, partition, e) from e
                    if not b.num_rows:
                        continue
                    self._record_batch(b)
                    b = b.to_device()
                yield b


class LocalShuffleManager:
    """Standalone shuffle service over a local directory — the testenv
    analogue of BlazeShuffleManager + IndexShuffleBlockResolver."""

    def __init__(self, root: Optional[str] = None):
        fresh = root is None
        self.root = root or tempfile.mkdtemp(prefix="blaze_shuffle_")
        pre_existing = not fresh and os.path.isdir(self.root)
        os.makedirs(self.root, exist_ok=True)
        # the disk-pressure ladder's reclaim sweeps registered roots
        diskmgr.register_root(self.root)
        if pre_existing:
            # orphan sweep on startup: a manager re-opened over an
            # EXISTING root (restarted driver, worker joining a shared
            # root) reclaims a crashed prior process's debris —
            # age-gated so a LIVE neighbor's staging temps survive
            self.sweep_orphans()

    def sweep_orphans(self, max_age_s: Optional[float] = None) -> int:
        """Age-gated startup reclamation: stale ``.inprogress`` staging
        temps under this root plus orphaned ``blaze_spill_`` files in
        the spill temp dir (conf ``spark.blaze.shuffle.orphanSweepAgeSec``;
        0 disables).  Quarantined ``.corrupt`` files are forensic
        evidence and always survive.  Returns files removed."""
        age = float(conf.ORPHAN_SWEEP_AGE.get()) if max_age_s is None \
            else max_age_s
        if age <= 0:
            return 0
        import time as _time

        cutoff = _time.time() - age
        removed = 0
        try:
            names = os.listdir(self.root)
        except OSError:
            return 0
        for fn in names:
            if ".inprogress" not in fn or fn.endswith(".corrupt"):
                continue
            path = os.path.join(self.root, fn)
            try:
                if os.path.getmtime(path) <= cutoff:
                    os.unlink(path)
                    removed += 1
            except OSError:
                continue
        removed += diskmgr.sweep_stale_spills(age)
        return removed

    def map_output_paths(self, shuffle_id: int, map_id: int) -> Tuple[str, str]:
        base = os.path.join(self.root, f"shuffle_{shuffle_id}_{map_id}")
        return base + ".data", base + ".index"

    def invalidate(self, shuffle_id: int,
                   map_ids: Optional[Sequence[int]] = None) -> int:
        """Drop map outputs (and in-progress temps) of a shuffle — the
        driver's response to a FetchFailedError before re-running the
        producing map stage (≙ DAGScheduler unregistering a dead
        executor's map outputs).  ``map_ids`` restricts the drop to
        those map tasks' outputs (partial re-run: only the missing
        producers are regenerated, the surviving outputs keep feeding
        the reduce barrier).  Quarantined ``.corrupt`` files are kept
        for forensics.  Returns files removed."""
        removed = 0
        if map_ids is not None:
            prefixes = tuple(
                f"shuffle_{shuffle_id}_{m}." for m in map_ids)
        else:
            prefixes = (f"shuffle_{shuffle_id}_",)
        try:
            names = os.listdir(self.root)
        except OSError:
            return 0
        for fn in names:
            if fn.startswith(prefixes) and not fn.endswith(".corrupt"):
                try:
                    os.unlink(os.path.join(self.root, fn))
                    removed += 1
                except OSError:
                    pass
        return removed

    def sweep_inprogress(self, shuffle_id: Optional[int] = None,
                         map_id: Optional[int] = None,
                         attempt: Optional[int] = None) -> int:
        """Remove attempt-qualified ``.inprogress`` staging temps — the
        rollback half of the commit-by-rename contract: a failed or
        cancelled attempt's own except-handler normally unlinks them,
        but an ABANDONED attempt (wedged past cooperation, killed
        worker) leaves its temps behind, and they were previously only
        reclaimed at process exit.  The scheduler sweeps a specific
        (shuffle, map, attempt) in each attempt's rollback path and
        everything on query cancellation.  Returns files removed."""
        if shuffle_id is None:
            prefix = "shuffle_"
        elif map_id is None:
            prefix = f"shuffle_{shuffle_id}_"
        else:
            prefix = f"shuffle_{shuffle_id}_{map_id}."
        asuffix = f".inprogress.a{attempt}" if attempt is not None else None
        removed = 0
        try:
            names = os.listdir(self.root)
        except OSError:
            return 0
        for fn in names:
            if not fn.startswith(prefix) or ".inprogress" not in fn \
                    or fn.endswith(".corrupt"):
                continue
            if asuffix is not None and not fn.endswith(asuffix):
                continue
            try:
                os.unlink(os.path.join(self.root, fn))
                removed += 1
            except OSError:
                pass
        return removed

    def reduce_blocks(self, shuffle_id: int, num_maps: int, reduce_id: int) -> List[BlockObject]:
        blocks: List[BlockObject] = []
        for m in range(num_maps):
            data, index = self.map_output_paths(shuffle_id, m)
            if not os.path.exists(index):
                continue
            with open(index, "rb") as f:
                raw = f.read()
            offsets = struct.unpack(f"<{len(raw)//8}Q", raw)
            lo, hi = offsets[reduce_id], offsets[reduce_id + 1]
            if hi > lo:
                blocks.append((data, lo, hi - lo))
        return blocks
