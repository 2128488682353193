"""External sort.

≙ reference SortExec (sort_exec.rs:80-1455: key-prefix rows, level
spills, LoserTree merge, fuzz-tested).  TPU design: sort keys encode
into **order-preserving uint64 words** (sign-flip ints, IEEE trick for
floats, big-endian packed strings, per-key null-rank word honoring
asc/desc × nulls first/last), and ``lax.sort`` does a lexicographic
multi-operand sort on device.  Buffered input stays on host (staging
RAM, tracked by the memory manager); the in-budget case is one device
sort over the concatenated buffer.

Out-of-core path (≙ sort_exec.rs spills + LoserTree merge): when the
memory manager calls ``spill()``, the buffered batches are sorted on
device into a run, and the run's batches are written to a Spill frame
by frame **together with their already-encoded key words** — the merge
then never re-stages spilled data to the device.  Output is a k-way
streaming merge (heap over (key words, run index); ties break toward
the earlier run, keeping the sort stable).  fetch=k (TakeOrdered)
prunes batches and runs to k rows, bounding memory at k rows per run.
"""

from __future__ import annotations

import heapq
import struct
import threading
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import conf
from ..batch import Column, RecordBatch, _pad_1d, bucket_capacity, concat_batches
from ..exprs.compile import lower
from ..exprs.ir import Expr
from ..io.batch_serde import deserialize_batch, serialize_batch
from ..runtime import faults, trace
from ..runtime.context import TaskContext
from ..runtime.memmgr import MemConsumer, Spill, try_new_spill
from ..schema import Schema
from .base import BatchStream, ExecNode


@dataclass
class SortField:
    expr: Expr
    ascending: bool = True
    nulls_first: bool = True


def order_words(c: Column, ascending: bool, nulls_first: bool) -> List[jnp.ndarray]:
    """Order-preserving uint64 words for one sort key column."""
    words: List[jnp.ndarray] = []
    null_rank = jnp.where(c.validity, jnp.uint64(1), jnp.uint64(0))
    if not nulls_first:
        null_rank = null_rank ^ jnp.uint64(1)
    words.append(null_rank)
    vals: List[jnp.ndarray] = []
    if c.dtype.is_string:
        n, w = c.data.shape
        nw = (w + 7) // 8
        data = c.data if nw * 8 == w else jnp.pad(c.data, ((0, 0), (0, nw * 8 - w)))
        b = data.reshape(n, nw, 8).astype(jnp.uint64)
        for k in range(nw):
            word = b[:, k, 0] << jnp.uint64(56)
            for j in range(1, 8):
                word = word | (b[:, k, j] << jnp.uint64(8 * (7 - j)))
            vals.append(word)
    elif c.dtype.is_float:
        from ..exprs.hash import f64_raw_bits

        bits = (
            c.data.view(jnp.int32).astype(jnp.int64)
            if c.data.dtype == jnp.float32
            else f64_raw_bits(c.data)  # TPU has no f64 bitcast lowering
        )
        u = bits.view(jnp.uint64)
        flipped = jnp.where(
            bits >= 0, u ^ jnp.uint64(0x8000000000000000), ~u
        )
        vals.append(flipped)
    else:
        u = c.data.astype(jnp.int64).view(jnp.uint64)
        vals.append(u ^ jnp.uint64(0x8000000000000000))
    if not ascending:
        vals = [~v for v in vals]
    # null rows: neutral value words so they cluster deterministically
    vals = [jnp.where(c.validity, v, jnp.uint64(0)) for v in vals]
    words.extend(vals)
    return words


def sort_indices(
    key_cols: Sequence[Column],
    fields: Sequence[SortField],
    num_rows,
) -> jnp.ndarray:
    """Stable sorted row order (padding rows sort last)."""
    cap = key_cols[0].validity.shape[0]
    live = jnp.arange(cap) < num_rows
    words: List[jnp.ndarray] = [live.astype(jnp.uint64) ^ jnp.uint64(1)]
    for c, f in zip(key_cols, fields):
        for w in order_words(c, f.ascending, f.nulls_first):
            words.append(jnp.where(live, w, jnp.uint64(0)))
    row_idx = jnp.arange(cap, dtype=jnp.int32)
    out = jax.lax.sort(tuple(words) + (row_idx,), num_keys=len(words), is_stable=True)
    return out[-1]


def apply_sort(
    cols: Tuple[Column, ...],
    schema: Schema,
    fields: Sequence[SortField],
    num_rows,
) -> Tuple[Column, ...]:
    """Sort a column tuple by ``fields`` — TRACE-SHARED body: both
    SortExec's standalone kernel and fused programs (AggExec's
    finalize-with-post_sort) inline this, so a sort folded into a
    bigger program is byte-identical to the standalone operator.
    ``num_rows`` may be a traced scalar; padding rows sort last."""
    env = {f.name: c for f, c in zip(schema.fields, cols)}
    cap = cols[0].validity.shape[0]
    key_cols = [lower(f.expr, schema, env, cap) for f in fields]
    idx = sort_indices(key_cols, fields, num_rows)
    return tuple(c.take(idx) for c in cols)


def sort_fields_key(fields: Sequence[SortField]) -> Tuple:
    """Structural cache-key fragment for a sort-field list
    (kernel_cache conventions)."""
    from ..exprs.compile import expr_key

    return tuple((expr_key(f.expr), f.ascending, f.nulls_first) for f in fields)


def _slice_host_batch(b: RecordBatch, start: int, n: int) -> RecordBatch:
    """Host-side row slice [start, start+n) of a host batch."""
    cap = bucket_capacity(n)
    cols = []
    for c in b.columns:
        data = _pad_1d(np.asarray(c.data)[start : start + n], cap)
        val = _pad_1d(np.asarray(c.validity)[start : start + n], cap)
        ln = None if c.lengths is None else _pad_1d(np.asarray(c.lengths)[start : start + n], cap)
        cols.append(Column(c.dtype, data, val, ln))
    return RecordBatch(b.schema, cols, n)


# One spilled chunk: [u32 batch_nbytes][batch][u32 n][u32 W][words n*W u64]
def _encode_chunk(batch: RecordBatch, words: np.ndarray) -> bytes:
    bb = serialize_batch(batch)
    n, w = words.shape
    return struct.pack("<I", len(bb)) + bb + struct.pack("<II", n, w) + words.tobytes()


def _decode_chunk(payload: bytes, schema: Schema) -> Tuple[RecordBatch, np.ndarray]:
    (bn,) = struct.unpack_from("<I", payload, 0)
    batch = deserialize_batch(payload[4 : 4 + bn], schema)
    n, w = struct.unpack_from("<II", payload, 4 + bn)
    words = np.frombuffer(payload, np.uint64, n * w, 4 + bn + 8).reshape(n, w)
    return batch, words


class _SortState(MemConsumer):
    """Buffered input batches + spilled sorted runs; the memory manager
    triggers ``spill()`` under pressure (≙ sort_exec.rs:173 LevelSpill,
    flattened to one level — runs merge in a single k-way pass)."""

    name = "sort"

    def __init__(self, exec_: "SortExec"):
        super().__init__()
        self.exec = exec_
        self.buffered: List[RecordBatch] = []
        self.spills: List[Spill] = []
        self._lock = threading.Lock()
        self._quiesced = threading.Condition(self._lock)
        self._frozen = False
        self._inflight = 0  # spills writing runs outside the lock

    def add(self, batch: RecordBatch) -> None:
        with self._lock:
            self.buffered.append(batch)
            total = sum(b.memory_size() for b in self.buffered)
        self.update_mem_used(total)

    def freeze(self) -> Tuple[List[RecordBatch], List[Spill]]:
        """Snapshot state for the output merge and stop accepting
        spills — a spill landing after the merge sources are built
        would create a run the merge never reads.  Waits out any spill
        already past the buffer claim (its run MUST reach the merge)."""
        with self._quiesced:
            self._frozen = True
            self._quiesced.wait_for(lambda: self._inflight == 0)
            return list(self.buffered), list(self.spills)

    def spill(self) -> int:
        # fault probe at the spill entry, outside the state lock (the
        # probe's trace emission must never ride inside a critical
        # section — the lock.emit-under-lock class)
        faults.hit("spill.write")
        with self._lock:
            if self._frozen or not self.buffered:
                return 0
            batches, self.buffered = self.buffered, []
            self._inflight += 1
        freed = sum(b.memory_size() for b in batches)
        try:
            sp = self.exec._write_run(batches)
            with self._quiesced:
                self.spills.append(sp)
        finally:
            with self._quiesced:
                self._inflight -= 1
                self._quiesced.notify_all()
        self.update_mem_used(0)
        return freed


class SortExec(ExecNode):
    def __init__(self, child: ExecNode, fields: Sequence[SortField], fetch: Optional[int] = None):
        super().__init__([child])
        self.fields = list(fields)
        self.fetch = fetch
        in_schema = child.schema
        fields_ = self.fields

        def build():
            @jax.jit
            def kernel(cols: Tuple[Column, ...], num_rows):
                return apply_sort(cols, in_schema, fields_, num_rows)

            @jax.jit
            def key_words(cols: Tuple[Column, ...], num_rows):
                env = {f.name: c for f, c in zip(in_schema.fields, cols)}
                cap = cols[0].validity.shape[0]
                key_cols = [lower(f.expr, in_schema, env, cap) for f in fields_]
                words: List[jnp.ndarray] = []
                for c, f in zip(key_cols, fields_):
                    words.extend(order_words(c, f.ascending, f.nulls_first))
                return jnp.stack(words, axis=1)  # (cap, W)

            return kernel, key_words

        from ..runtime.kernel_cache import cached_kernel, schema_key

        self._kernel, self._key_words = cached_kernel(
            ("sort", schema_key(in_schema), sort_fields_key(fields_)),
            build,
        )

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def provided_ordering(self):
        """Static-analysis contract: downstream sort-consumers (SMJ,
        window) are satisfied by this node's key order.  Each entry is
        ``(expr_key, ascending)`` — direction is part of the order a
        streaming merge relies on."""
        from ..exprs.compile import expr_key

        return tuple((expr_key(f.expr), bool(f.ascending))
                     for f in self.fields)

    def name(self) -> str:
        k = f", fetch={self.fetch}" if self.fetch is not None else ""
        return f"SortExec({len(self.fields)} keys{k})"

    def _sorted_batch(self, batch: RecordBatch, limit: Optional[int]) -> RecordBatch:
        cols = self._kernel(tuple(batch.columns), batch.num_rows)
        n = batch.num_rows if limit is None else min(batch.num_rows, limit)
        return RecordBatch(batch.schema, list(cols), n)

    # ------------------------------------------------------ run spilling

    def _write_run(self, batches: List[RecordBatch]) -> Spill:
        """Sort the given batches into one run and spill it with its
        key words."""
        with self.metrics.timer("sort_time"):
            merged = concat_batches(batches)
            run = self._sorted_batch(merged.to_device(), self.fetch)
            words = self._key_words(tuple(run.columns), run.num_rows)
        with trace.span("device_read"):
            words_all = np.asarray(words)
            host = run.to_host()
        sp = try_new_spill()
        bs = int(conf.BATCH_SIZE.get())
        try:
            for start in range(0, run.num_rows, bs):
                n = min(bs, run.num_rows - start)
                chunk = _slice_host_batch(host, start, n)
                sp.write_frame(
                    _encode_chunk(chunk, words_all[start : start + n]))
            sp.complete()
        except BaseException:
            # a failed run write must not leak the spill's temp file:
            # the task fails/retries, but the blaze_spill_* file used
            # to survive until process exit (resource.path-leak class,
            # surfaced by analysis/errflow.py; the shuffle
            # repartitioner's spill-abort already did this)
            sp.release()
            raise
        self.metrics.add("spill_count", 1)
        self.metrics.add("spilled_bytes", sp.size)
        return sp

    def _mem_run_chunks(
        self, batches: List[RecordBatch]
    ) -> Iterator[Tuple[RecordBatch, np.ndarray]]:
        merged = concat_batches(batches)
        run = self._sorted_batch(merged.to_device(), self.fetch)
        words = self._key_words(tuple(run.columns), run.num_rows)
        with trace.span("device_read"):
            words_all = np.asarray(words)
            host = run.to_host()
        bs = int(conf.BATCH_SIZE.get())
        for start in range(0, run.num_rows, bs):
            n = min(bs, run.num_rows - start)
            yield _slice_host_batch(host, start, n), words_all[start : start + n]

    @staticmethod
    def _spill_chunks(sp: Spill, schema: Schema) -> Iterator[Tuple[RecordBatch, np.ndarray]]:
        while True:
            payload = sp.read_frame()
            if payload is None:
                return
            yield _decode_chunk(payload, schema)

    # --------------------------------------------------------- k-way merge

    def _merge(
        self,
        sources: List[Iterator[Tuple[RecordBatch, np.ndarray]]],
        limit: Optional[int],
        ctx: TaskContext,
    ) -> BatchStream:
        """Streaming merge: heap of (key-word tuple, source index);
        stable because ties pop the earlier source first (runs are
        created in input order)."""
        cursors: List[Optional[Tuple[Iterator, RecordBatch, np.ndarray, int]]] = []
        heap: List[Tuple[tuple, int]] = []

        def advance(i: int, it, batch, words, pos) -> None:
            if batch is not None and pos < batch.num_rows:
                cursors[i] = (it, batch, words, pos)
                heapq.heappush(heap, (tuple(words[pos]), i))
                return
            nxt = next(it, None)
            if nxt is None:
                cursors[i] = None
                return
            b, w = nxt
            cursors[i] = (it, b, w, 0)
            heapq.heappush(heap, (tuple(w[0]), i))

        for i, src in enumerate(sources):
            cursors.append(None)
            advance(i, src, None, None, 0)

        bs = int(conf.BATCH_SIZE.get())
        picks: List[Tuple[RecordBatch, int]] = []
        emitted = 0

        def flush() -> RecordBatch:
            nonlocal picks
            out = self._materialize(picks)
            picks = []
            return out

        while heap:
            if not ctx.is_task_running():
                return
            _, i = heapq.heappop(heap)
            it, batch, words, pos = cursors[i]
            picks.append((batch, pos))
            emitted += 1
            advance(i, it, batch, words, pos + 1)
            if limit is not None and emitted >= limit:
                break
            if len(picks) >= bs:
                yield flush()
        if picks:
            yield flush()

    def _materialize(self, picks: List[Tuple[RecordBatch, int]]) -> RecordBatch:
        """Gather picked rows (in order) into one batch — vectorized
        per source batch."""
        n = len(picks)
        cap = bucket_capacity(n)
        by_src: Dict[int, Tuple[RecordBatch, List[int], List[int]]] = {}
        for pos, (batch, row) in enumerate(picks):
            entry = by_src.get(id(batch))
            if entry is None:
                entry = (batch, [], [])
                by_src[id(batch)] = entry
            entry[1].append(pos)
            entry[2].append(row)

        schema = self.schema
        cols: List[Column] = []
        for ci, f in enumerate(schema.fields):
            if f.dtype.is_string:
                width = max(
                    np.asarray(b.columns[ci].data).shape[1] for b, _, _ in by_src.values()
                )
                data = np.zeros((cap, width), np.uint8)
                lens = np.zeros(cap, np.int32)
            else:
                data = np.zeros(cap, f.dtype.np_dtype)
                lens = None
            val = np.zeros(cap, np.bool_)
            for b, positions, rows in by_src.values():
                src = b.columns[ci]
                pos_a = np.asarray(positions)
                row_a = np.asarray(rows)
                d = np.asarray(src.data)[row_a]
                if f.dtype.is_string:
                    data[pos_a, : d.shape[1]] = d
                    lens[pos_a] = np.asarray(src.lengths)[row_a]
                else:
                    data[pos_a] = d
                val[pos_a] = np.asarray(src.validity)[row_a]
            cols.append(Column(f.dtype, data, val, lens).to_device())
        return RecordBatch(schema, cols, n)

    # ------------------------------------------------------------ execute

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        child_stream = self.children[0].execute(partition, ctx)

        def stream():
            state = _SortState(self)
            ctx.mem.register_consumer(state)
            try:
                for batch in child_stream:
                    if not ctx.is_task_running():
                        return
                    if self.fetch is not None and batch.num_rows > self.fetch:
                        with self.metrics.timer("sort_time"):
                            batch = self._sorted_batch(batch, self.fetch)
                    with trace.span("device_read"):
                        host = batch.to_host()
                    state.add(host)
                buffered, spills = state.freeze()
                if not buffered and not spills:
                    return
                if not spills:
                    # in-budget: one device sort over the whole buffer
                    with self.metrics.timer("sort_time"):
                        merged = concat_batches(buffered)
                        out = self._sorted_batch(merged.to_device(), self.fetch)
                    self._record_batch(out)
                    yield out
                    return
                sources = [self._spill_chunks(sp, self.schema) for sp in spills]
                if buffered:
                    sources.append(self._mem_run_chunks(buffered))
                for out in self._merge(sources, self.fetch, ctx):
                    self._record_batch(out)
                    yield out
            finally:
                for sp in state.freeze()[1]:
                    sp.release()
                ctx.mem.unregister_consumer(state)

        return stream()
