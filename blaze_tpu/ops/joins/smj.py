"""Sort-merge join — cursor-windowed streaming merge.

≙ reference SortMergeJoinExec (sort_merge_join_exec.rs:58-309) +
joins/stream_cursor.rs:38: both sides arrive key-sorted (the planner
inserts SortExec, like Spark's EnsureRequirements), and the build
(right) side is held only as a **sliding window** of batches whose key
ranges overlap the current probe batch — bounded memory for arbitrarily
large sides.  The window is a MemConsumer: under memory-manager
pressure its resident batches spill to the Spill tier and are reloaded
on demand.  The verified sorted-key-table Joiner core does the inner
window matching; build-preserved rows (right/full outer, right
semi/anti) are emitted at window EVICTION time, when their keys can no
longer match any future probe batch.

Ascending key order is required (Spark's SMJ requirement);
``nulls_first`` must match the upstream sort option.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ...batch import RecordBatch, concat_batches
from ...exprs.compile import lower
from ...exprs.ir import Expr
from ...io.batch_serde import deserialize_batch, serialize_batch
from ...runtime import faults
from ...runtime.context import TaskContext
from ...runtime.memmgr import MemConsumer, Spill, try_new_spill
from ...schema import Schema
from ..base import BatchStream, ExecNode
from .core import JoinerState, JoinMap, JoinType, cached_joiner

Key = Tuple


def _cmp_val(x, y, nulls_first: bool) -> int:
    if x is None and y is None:
        return 0
    if x is None:
        return -1 if nulls_first else 1
    if y is None:
        return 1 if nulls_first else -1
    if x < y:
        return -1
    if x > y:
        return 1
    return 0


def _cmp_key(a: Key, b: Key, nulls_first: bool) -> int:
    for x, y in zip(a, b):
        c = _cmp_val(x, y, nulls_first)
        if c:
            return c
    return 0


def _boundary_keys(batch: RecordBatch, schema: Schema, keys: Sequence[Expr]) -> Tuple[Key, Key]:
    """(first_row_key, last_row_key) of a non-empty batch as python
    tuples (None = null) — drives the host-side cursor comparisons.
    Only the two boundary rows cross device->host (key exprs evaluate
    once over the batch, then a 2-row gather precedes the sync)."""
    env = {f.name: c for f, c in zip(schema.fields, batch.columns)}
    edge = jnp.asarray([0, batch.num_rows - 1], jnp.int32)
    cols = [lower(e, schema, env, batch.capacity).take(edge) for e in keys]
    first: List = []
    last: List = []
    for c in cols:
        ch = c.to_host()
        for idx, out in ((0, first), (1, last)):
            if not ch.validity[idx]:
                out.append(None)
            elif ch.dtype.is_string:
                out.append(bytes(ch.data[idx][: int(ch.lengths[idx])]))
            else:
                out.append(ch.data[idx].item())
    return tuple(first), tuple(last)


@dataclass
class _Entry:
    rows: int
    first_key: Key
    last_key: Key
    matched: np.ndarray                   # (rows,) build-matched flags
    batch: Optional[RecordBatch]          # None while spilled
    spill: Optional[Spill] = None
    mem: int = 0


class _Window(MemConsumer):
    """Sliding window of build-side batches (≙ stream_cursor.rs buffered
    batches), spillable under pressure."""

    name = "smj_window"

    def __init__(self, schema: Schema, metrics):
        super().__init__()
        self.schema = schema
        self.metrics = metrics
        self.entries: List[_Entry] = []
        self._lock = threading.RLock()

    def _resident(self) -> int:
        return sum(e.mem for e in self.entries if e.batch is not None)

    def add(self, entry: _Entry) -> None:
        with self._lock:
            entry.mem = entry.batch.memory_size()
            self.entries.append(entry)
            self.set_mem_used_no_trigger(self._resident())
        self.trigger_spill_check()

    def spill(self) -> int:
        # fault probe at the spill entry, outside the window lock (see
        # ShuffleRepartitioner.spill) — this is what retired the
        # _Window.spill emit-under-lock waiver
        faults.hit("spill.write")
        with self._lock:
            freed = 0
            for e in self.entries:
                if e.batch is None:
                    continue
                sp = try_new_spill()
                try:
                    sp.write_frame(serialize_batch(e.batch))
                    sp.complete()
                except BaseException:
                    # keep the entry's in-memory batch (spill-abort
                    # contract) and never leak the temp file
                    sp.release()
                    raise
                e.spill = sp
                e.batch = None
                freed += e.mem
            if freed:
                self.metrics.add("spill_count", 1)
                self.metrics.add("spilled_bytes", freed)
            self.set_mem_used_no_trigger(0)
            return freed

    def materialize(self) -> List[RecordBatch]:
        """Reload every spilled entry; returns the window's batches in
        order."""
        with self._lock:
            for e in self.entries:
                if e.batch is None:
                    payload = e.spill.read_frame()
                    assert payload is not None
                    e.batch = deserialize_batch(payload, self.schema).to_device()
                    e.spill.release()
                    e.spill = None
            self.set_mem_used_no_trigger(self._resident())
            out = [e.batch for e in self.entries]
        self.trigger_spill_check()
        return out

    def evict_lt(self, key: Key, nulls_first: bool, reload: bool) -> List[_Entry]:
        """Pop leading entries whose whole key range is below ``key``.
        ``reload=False`` (probe-preserved joins never emit evicted rows)
        releases spilled entries without the wasted deserialize."""
        out: List[_Entry] = []
        with self._lock:
            while self.entries and _cmp_key(self.entries[0].last_key, key, nulls_first) < 0:
                e = self.entries.pop(0)
                if e.batch is None:
                    if reload:
                        payload = e.spill.read_frame()
                        e.batch = deserialize_batch(payload, self.schema).to_device()
                    e.spill.release()
                    e.spill = None
                out.append(e)
            self.set_mem_used_no_trigger(self._resident())
        return out

    def fold_matched(self, matched: np.ndarray) -> None:
        """Scatter concat-aligned matched flags back per entry."""
        off = 0
        with self._lock:
            for e in self.entries:
                e.matched |= matched[off : off + e.rows]
                off += e.rows

    def take_all(self, reload: bool) -> List[Tuple[RecordBatch, np.ndarray]]:
        """Atomically drain the window (final flush): reload spilled
        entries if requested, clear accounting, return (batch, matched)
        pairs.  Done under the lock so a concurrent manager-driven
        spill() cannot interleave and leak fresh Spill objects."""
        with self._lock:
            out = []
            for e in self.entries:
                if e.batch is None and reload:
                    payload = e.spill.read_frame()
                    e.batch = deserialize_batch(payload, self.schema).to_device()
                if e.spill is not None:
                    e.spill.release()
                    e.spill = None
                out.append((e.batch, e.matched))
            self.entries = []
            self.set_mem_used_no_trigger(0)
            return out


class SortMergeJoinExec(ExecNode):
    """children = [left, right]; both key-sorted ascending upstream."""

    def __init__(
        self,
        left: ExecNode,
        right: ExecNode,
        left_keys: Sequence[Expr],
        right_keys: Sequence[Expr],
        join_type: JoinType,
        nulls_first: bool = True,
    ):
        super().__init__([left, right])
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.nulls_first = nulls_first
        # probe = left (preserves left order); build = right
        self._joiner = cached_joiner(
            left.schema, right.schema, left_keys, right_keys, join_type,
            probe_is_left=True,
        )
        self._build_preserved = join_type in (
            JoinType.FULL, JoinType.RIGHT, JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI,
        )

    @property
    def schema(self) -> Schema:
        return self._joiner.out_schema

    def num_partitions(self) -> int:
        return self.children[0].num_partitions()

    def required_child_orderings(self):
        """Static-analysis contract: the streaming merge is only
        correct over inputs key-sorted ASCENDING in join-key order —
        each child must be downstream of a sort whose ``(expr_key,
        ascending)`` prefix equals the join keys
        (analysis/plan_verify.py rule ``order.smj``)."""
        from ...exprs.compile import expr_key

        return [tuple((expr_key(e), True) for e in self.left_keys),
                tuple((expr_key(e), True) for e in self.right_keys)]

    # ------------------------------------------------------- emission

    def _emit_entry(self, batch: RecordBatch, matched_rows: np.ndarray) -> Optional[RecordBatch]:
        """Build-preserved output for an evicted/final window entry."""
        if not self._build_preserved:
            return None
        m = np.zeros(batch.capacity, np.bool_)
        m[: matched_rows.shape[0]] = matched_rows
        state = JoinerState()
        state.matched_build = jnp.asarray(m)
        # finish() reads the batch and its row count only: no key table
        zeros = jnp.zeros(batch.capacity, jnp.int32)
        fake = JoinMap(zeros.astype(jnp.uint64), zeros, zeros, zeros, jnp.int32(0),
                       batch.num_rows, batch)
        return self._joiner.finish(fake, state)

    def _empty_build(self) -> RecordBatch:
        from ...batch import batch_from_pydict

        right = self.children[1]
        return batch_from_pydict({f.name: [] for f in right.schema.fields}, right.schema)

    # ------------------------------------------------------ execution

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        def stream():
            left, right = self.children
            right_iter: Iterator[RecordBatch] = iter(right.execute(partition, ctx))
            window = _Window(right.schema, self.metrics)
            ctx.mem.register_consumer(window)
            right_done = False
            jmap: Optional[JoinMap] = None
            dirty = True
            nf = self.nulls_first
            try:
                for pbatch in left.execute(partition, ctx):
                    if not ctx.is_task_running():
                        return
                    if pbatch.num_rows == 0:
                        continue
                    pmin, pmax = _boundary_keys(pbatch, left.schema, self.left_keys)
                    # evict entries that can never match again
                    for e in window.evict_lt(pmin, nf, reload=self._build_preserved):
                        dirty = True
                        if self._build_preserved:
                            tail = self._emit_entry(e.batch, e.matched)
                            if tail is not None and tail.num_rows:
                                self._record_batch(tail)
                                yield tail
                    # pull right batches overlapping this probe range
                    while not right_done and (
                        not window.entries
                        or _cmp_key(window.entries[-1].last_key, pmax, nf) <= 0
                    ):
                        rb = next(right_iter, None)
                        if rb is None:
                            right_done = True
                            break
                        if rb.num_rows == 0:
                            continue
                        fk, lk = _boundary_keys(rb, right.schema, self.right_keys)
                        window.add(
                            _Entry(rb.num_rows, fk, lk, np.zeros(rb.num_rows, np.bool_), rb)
                        )
                        dirty = True
                    if dirty:
                        with self.metrics.timer("build_time"):
                            batches = window.materialize()
                            data = (
                                concat_batches(batches).to_device()
                                if batches else self._empty_build()
                            )
                            jmap = self._joiner.build_map(data)
                        dirty = False
                    st = JoinerState()
                    with self.metrics.timer("probe_time"):
                        out = self._joiner.probe_batch(jmap, pbatch, st)
                    if st.matched_build is not None:
                        window.fold_matched(np.asarray(st.matched_build))
                    if out is not None and out.num_rows:
                        self._record_batch(out)
                        yield out
                # probe exhausted: flush the window atomically
                for b, m in window.take_all(reload=self._build_preserved):
                    if not self._build_preserved:
                        continue
                    tail = self._emit_entry(b, m)
                    if tail is not None and tail.num_rows:
                        self._record_batch(tail)
                        yield tail
                # ...and every never-pulled right batch (all unmatched)
                if self._build_preserved:
                    while True:
                        rb = next(right_iter, None)
                        if rb is None:
                            break
                        if rb.num_rows == 0:
                            continue
                        tail = self._emit_entry(rb, np.zeros(rb.num_rows, np.bool_))
                        if tail is not None and tail.num_rows:
                            self._record_batch(tail)
                            yield tail
            finally:
                ctx.mem.unregister_consumer(window)

        return stream()
