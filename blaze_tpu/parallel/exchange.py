"""Shuffle exchange plan node.

≙ reference NativeShuffleExchangeBase.doExecuteNative
(NativeShuffleExchangeBase.scala:100-156): the map side runs
ShuffleWriterExec per upstream partition (one "task" each, writing
.data/.index through the shuffle manager), the reduce side registers
block iterators in the resources map and reads them back through
IpcReaderExec — the exact JNI rendezvous pattern, minus the JVM.
"""

from __future__ import annotations

import itertools
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

from ..ops.base import BatchStream, ExecNode
from ..runtime.context import RESOURCES, TaskContext
from ..runtime.errors import reraise_control
from ..runtime.metrics import MetricNode
from ..schema import Schema
from .shuffle import (
    HashPartitioning,
    IpcReaderExec,
    LocalShuffleManager,
    Partitioning,
    ShuffleWriterExec,
)

_shuffle_ids = itertools.count()
_default_manager: Optional[LocalShuffleManager] = None
_mgr_lock = threading.Lock()

# set ONCE, process-wide, never restored: XLA/LLVM compile recursion
# can overflow the 8 MB default thread stack, and a set/restore pair
# around each pool races sibling exchanges (stacks are virtual memory,
# so the cost of the deep default is address space only)
_STACK_DEEPENED = False
_STACK_LOCK = threading.Lock()


def _ensure_deep_thread_stacks() -> None:
    global _STACK_DEEPENED
    with _STACK_LOCK:
        if not _STACK_DEEPENED:
            try:
                threading.stack_size(64 << 20)
            except (ValueError, RuntimeError) as e:
                reraise_control(e)
            _STACK_DEEPENED = True


def _warm_then_map(fn, n_maps: int, max_workers: int):
    """Run map task 0 to completion INLINE, then the rest in a pool.

    Two pool threads cache-missing the same jitted kernel compile it
    concurrently, and jaxlib's CPU backend_compile_and_load races
    itself into a segfault (observed deterministically 44 tests into
    the combined differential suites, two threads inside the same
    probe_batch compile).  Task 0 compiles every kernel on this plan's
    path once; the remaining tasks then hit jax's executable cache."""
    _ensure_deep_thread_stacks()
    first = fn(0)
    with ThreadPoolExecutor(max_workers=max_workers) as pool:
        rest = list(pool.map(fn, range(1, n_maps)))
    return [first] + rest


def default_shuffle_manager() -> LocalShuffleManager:
    global _default_manager
    with _mgr_lock:
        if _default_manager is None:
            _default_manager = LocalShuffleManager()
        return _default_manager


class _HbmBudgetExceeded(Exception):
    """In-process materialization would exceed the HBM budget; the
    caller falls back to the spillable file shuffle."""


class _BudgetTracker:
    """Thread-safe device-memory estimate for an in-process
    materialization.  ``multiplier`` accounts for the path's resident
    copies (sorted copy = 2x; range also holds key words ~= 3x).
    ``strict=False`` logs instead of raising (paths with no fallback
    tier)."""

    def __init__(self, budget: int, multiplier: int, strict: bool):
        self._budget = budget
        self._multiplier = multiplier
        self._strict = strict
        self._bytes = 0
        self._lock = threading.Lock()
        self._warned = False

    def add(self, nbytes: int) -> None:
        with self._lock:
            self._bytes += nbytes
            over = self._bytes * self._multiplier > self._budget
            warned = self._warned
            if over:
                self._warned = True
        if over:
            if self._strict:
                raise _HbmBudgetExceeded
            if not warned:
                import logging

                logging.getLogger(__name__).warning(
                    "range exchange exceeds the HBM budget (%d bytes "
                    "buffered, x%d resident); no spill tier for range "
                    "partitioning yet — raise spark.blaze.tpu.hbmBudget "
                    "or reduce the stage output",
                    self._bytes, self._multiplier,
                )



def _split_pending(pending, n_out: int):
    """Shared tail of the in-process materializations: ONE host sync
    for all pid counts, device slices per partition, then coalesce each
    partition to a single batch (per-program launch overhead makes
    fewer, larger batches win)."""
    import jax.numpy as jnp
    import numpy as np

    from ..batch import concat_batches, slice_rows_device

    out = [[] for _ in range(n_out)]
    if pending:
        all_counts = np.asarray(jnp.stack([c for _, c in pending]))
        for i, counts in enumerate(all_counts):
            sorted_batch, _ = pending[i]
            pending[i] = None  # release the pre-slice copy eagerly
            offs = np.concatenate([[0], np.cumsum(counts)])
            for pid in range(n_out):
                lo, hi = int(offs[pid]), int(offs[pid + 1])
                if hi > lo:
                    out[pid].append(slice_rows_device(sorted_batch, lo, hi - lo))
        for pid in range(n_out):
            if len(out[pid]) > 1:
                out[pid] = [concat_batches(out[pid])]
    return out


def _build_range_kernels(schema: Schema, fields, n_out: int):
    """Device kernels for range partitioning: order-word extraction,
    exact order-statistic boundaries, lexicographic pid assignment."""
    import jax
    import jax.numpy as jnp

    from ..exprs.compile import lower
    from ..ops.sort import order_words

    @jax.jit
    def key_words(cols, num_rows):
        """Order words with a SCHEMA-STATIC count: string key columns
        normalize to their dtype width before word extraction (physical
        padded widths vary per batch; naive cross-batch alignment with
        zero words breaks DESCENDING keys, whose padding bytes invert
        to ~0)."""
        from ..batch import Column

        cap = cols[0].validity.shape[0]
        env = {f.name: c for f, c in zip(schema.fields, cols)}
        live = jnp.arange(cap) < num_rows
        words = []
        for f in fields:
            c = lower(f.expr, schema, env, cap)
            if c.dtype.is_string:
                w_phys, w_decl = c.data.shape[-1], c.dtype.string_width
                assert w_phys <= w_decl, (
                    f"string key physical width {w_phys} exceeds dtype "
                    f"width {w_decl}"
                )
                if w_phys < w_decl:
                    c = Column(
                        c.dtype,
                        jnp.pad(c.data, ((0, 0), (0, w_decl - w_phys))),
                        c.validity, c.lengths,
                    )
            ws = order_words(c, f.ascending, f.nulls_first)
            # dead padding rows sort AFTER every live row
            words.extend(jnp.where(live, w, ~jnp.uint64(0)) for w in ws)
        return tuple(words)

    @jax.jit
    def boundaries_at(cat_words, positions):
        s = jax.lax.sort(cat_words, num_keys=len(cat_words))
        return tuple(jnp.take(w, positions) for w in s)

    @jax.jit
    def pids(words, boundaries):
        cap = words[0].shape[0]
        pid = jnp.zeros(cap, jnp.int32)
        for bi in range(n_out - 1):
            ge = jnp.zeros(cap, jnp.bool_)   # row > boundary so far
            eq = jnp.ones(cap, jnp.bool_)    # equal prefix so far
            for w, bw in zip(words, boundaries):
                b = bw[bi]
                ge = ge | (eq & (w > b))
                eq = eq & (w == b)
            pid = pid + (ge | eq).astype(jnp.int32)
        return pid

    return key_words, boundaries_at, pids


class NativeShuffleExchangeExec(ExecNode):
    def __init__(
        self,
        child: ExecNode,
        partitioning: Partitioning,
        manager: Optional[LocalShuffleManager] = None,
        parallel_map_tasks: int = 4,
    ):
        super().__init__([child])
        self.partitioning = partitioning
        self.manager = manager or default_shuffle_manager()
        self.shuffle_id = next(_shuffle_ids)
        self.parallel_map_tasks = parallel_map_tasks
        self._materialized = False
        self._hbm_fallback = False
        self._lock = threading.Lock()
        self._reader = IpcReaderExec(
            child.schema,
            f"shuffle_{self.shuffle_id}",
            partitioning.num_partitions,
        )

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def num_partitions(self) -> int:
        return self.partitioning.num_partitions

    def _run_map_task(self, map_id: int) -> None:
        data, index = self.manager.map_output_paths(self.shuffle_id, map_id)
        writer = ShuffleWriterExec(self.children[0], self.partitioning, data, index)
        writer.metrics = self.metrics  # share metric set across map tasks
        ctx = TaskContext(map_id, self.children[0].num_partitions())
        for _ in writer.execute(map_id, ctx):
            pass

    # ------------------------------------------------- in-process fast path

    def _materialize_inprocess(self, caller_ctx: TaskContext) -> None:
        """Map-side repartition keeping every partition buffer
        device-resident (HBM), no IPC files, and at most ONE host sync
        for the whole exchange (the per-batch pid counts, deferred and
        fetched in a single transfer).

        Rationale: a host roundtrip drains the device queue, so the
        file shuffle's per-batch to_host() serializes the pipeline on
        D2H latency.  This path is the single-process
        analogue of the ICI all-to-all exchange (parallel/ici.py) the
        same way the reference's local-dir shuffle is the testenv
        analogue of Spark block-store shuffle.  The file path remains
        for cross-process stages and for stage outputs beyond the HBM
        budget (spark.blaze.exchange.inProcess=false): this path keeps
        the whole stage output device-resident and does NOT spill.
        """
        import jax.numpy as jnp

        from .. import conf
        from ..batch import RecordBatch
        from .shuffle import (
            RangePartitioning, RoundRobinPartitioning, non_opaque_cols,
            sort_cols_by_pid,
        )

        child = self.children[0]
        n_out = self.partitioning.num_partitions
        n_maps = child.num_partitions()
        is_hash = isinstance(self.partitioning, HashPartitioning) and n_out > 1
        is_rr = isinstance(self.partitioning, RoundRobinPartitioning) and n_out > 1
        is_range = isinstance(self.partitioning, RangePartitioning) and n_out > 1
        if is_range:
            self._materialize_range(caller_ctx)
            return
        writer = None
        if is_hash:
            # reuse the writer's cached pid kernels (murmur3 pmod)
            writer = ShuffleWriterExec(
                child, self.partitioning, "/dev/null", "/dev/null"
            )
            writer.metrics = self.metrics

        cancelled = False
        tracker = _BudgetTracker(
            int(conf.DEVICE_MEMORY_BUDGET.get()), multiplier=2, strict=True
        )

        def run_map(m: int):
            """One map task: returns [(sorted device batch, counts)] or
            plain device batches when n_out == 1.  Device work enqueues
            async; host-bound scan/decode parallelizes across maps."""
            nonlocal cancelled
            ctx = TaskContext(m, n_maps)
            local = []
            rr = m  # stagger round-robin start per map task
            for batch in child.execute(m, ctx):
                if not caller_ctx.is_task_running():
                    cancelled = True
                    return local
                tracker.add(batch.memory_size())
                b = batch.to_device()
                if n_out == 1:
                    local.append((b, None))
                    continue
                with self.metrics.timer("elapsed_compute"):
                    if is_hash:
                        pids = writer._hash_pids(
                            non_opaque_cols(self.schema, b.columns), b.num_rows
                        )
                    elif is_rr:
                        pids = (jnp.arange(b.capacity, dtype=jnp.int32) + rr) % n_out
                        rr = (rr + b.num_rows) % n_out
                    else:
                        pids = jnp.zeros(b.capacity, jnp.int32)
                    sorted_cols, counts = sort_cols_by_pid(
                        self.schema, b.columns, pids, n_out, b.num_rows
                    )
                local.append(
                    (RecordBatch(self.schema, list(sorted_cols), b.num_rows), counts)
                )
            return local

        if self.parallel_map_tasks > 1 and n_maps > 1:
            per_map = _warm_then_map(run_map, n_maps, self.parallel_map_tasks)
        else:
            per_map = [run_map(m) for m in range(n_maps)]
        if cancelled:
            # do NOT cache a truncated shuffle: the cancelled caller's
            # output is discarded anyway, and a later retry must
            # re-materialize from scratch
            return

        pending = [pair for chunk in per_map for pair in chunk]
        del per_map
        if n_out == 1:
            from ..batch import concat_batches

            out: List[List] = [[b for b, _ in pending]]
            if len(out[0]) > 1:  # coalesce: one downstream program, not N
                out[0] = [concat_batches(out[0])]
        else:
            out = _split_pending(pending, n_out)
        self._inproc_outputs = out
        self._note_stats(out)

    def _note_stats(self, out: List[List]) -> None:
        """Per-partition rows/bytes histogram for the runtime-stats
        skew scan (runtime/stats.py) — counter arithmetic only, no
        host sync (memory_size reads buffer shapes, not data)."""
        from ..runtime import stats as _stats

        if not _stats.enabled():
            return
        _stats.note_exchange(
            f"shuffle_{self.shuffle_id}",
            f"{self.name()}[{type(self.partitioning).__name__}]",
            [sum(b.num_rows for b in part) for part in out],
            [sum(b.memory_size() for b in part) for part in out])

    def materialize(self) -> None:
        """Run all map tasks once (the stage boundary)."""
        with self._lock:
            if self._materialized:
                return
            n_maps = self.children[0].num_partitions()
            if self.parallel_map_tasks > 1 and n_maps > 1:
                _warm_then_map(self._run_map_task, n_maps, self.parallel_map_tasks)
            else:
                for m in range(n_maps):
                    self._run_map_task(m)
            self._materialized = True

    def _materialize_range(self, caller_ctx: TaskContext) -> None:
        """Range repartition (global-sort exchange): collect the map
        output device-resident, compute exact order-statistic boundary
        rows from the full key distribution (ONE multi-word sort), then
        assign pids by lexicographic comparison against the boundaries
        and split like the hash path.  Reduce partitions hold disjoint
        key ranges in partition order, so per-partition sorts compose
        into a total order."""
        import jax.numpy as jnp

        from .. import conf
        from ..batch import RecordBatch
        from ..exprs.compile import expr_key
        from ..runtime.kernel_cache import cached_kernel, schema_key

        from ..batch import split_opaque_indexes
        from .shuffle import sort_cols_by_pid

        child = self.children[0]
        n_out = self.partitioning.num_partitions
        n_maps = child.num_partitions()
        fields = list(self.partitioning.fields)
        # kernels see only jit-capable columns (sort keys never opaque)
        dev_idx, _ = split_opaque_indexes(child.schema)
        schema = Schema([child.schema.fields[i] for i in dev_idx])

        key_words, boundaries_at, pids_fn = cached_kernel(
            (
                "range_pids", schema_key(schema), n_out,
                tuple((expr_key(f.expr), f.ascending, f.nulls_first) for f in fields),
            ),
            lambda: _build_range_kernels(schema, fields, n_out),
        )

        cancelled = False

        # no strict raise: the file shuffle cannot do range
        # partitioning, so there is no fallback tier — warn instead
        tracker = _BudgetTracker(
            int(conf.DEVICE_MEMORY_BUDGET.get()), multiplier=3, strict=False
        )

        def collect_map(m: int):
            nonlocal cancelled
            ctx = TaskContext(m, n_maps)
            local = []
            for batch in child.execute(m, ctx):
                if not caller_ctx.is_task_running():
                    cancelled = True
                    return local
                tracker.add(batch.memory_size())
                b = batch.to_device()
                local.append(
                    (b, key_words(tuple(b.columns[i] for i in dev_idx), b.num_rows))
                )
            return local

        if self.parallel_map_tasks > 1 and n_maps > 1:
            per_map = _warm_then_map(collect_map, n_maps, self.parallel_map_tasks)
        else:
            per_map = [collect_map(m) for m in range(n_maps)]
        if cancelled:
            return
        batches = [b for chunk in per_map for b, _ in chunk]
        per_batch_words = [w for chunk in per_map for _, w in chunk]
        del per_map
        out: List[List] = [[] for _ in range(n_out)]
        if batches:
            n_words = len(per_batch_words[0])
            cat = tuple(
                jnp.concatenate([w[k] for w in per_batch_words])
                for k in range(n_words)
            )
            total_live = sum(b.num_rows for b in batches)
            # boundary b_i = first row of partition i+1 (rows >= b_i go
            # right), so position is (total*(i+1))//n_out — NOT -1,
            # which would push every partition's last row rightward
            positions = jnp.asarray(
                [
                    min(total_live - 1, (total_live * (i + 1)) // n_out)
                    for i in range(n_out - 1)
                ],
                dtype=jnp.int32,
            )
            boundaries = boundaries_at(cat, positions)
            del cat
            pending = []
            for b, words in zip(batches, per_batch_words):
                with self.metrics.timer("elapsed_compute"):
                    pids = pids_fn(words, boundaries)
                    sorted_cols, counts = sort_cols_by_pid(
                        self.schema, b.columns, pids, n_out, b.num_rows
                    )
                pending.append(
                    (RecordBatch(self.schema, list(sorted_cols), b.num_rows), counts)
                )
            # originals and key words are consumed; release before the
            # sliced copies materialize (halves peak HBM)
            del batches, per_batch_words
            out = _split_pending(pending, n_out)
        self._inproc_outputs = out
        self._note_stats(out)

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        from .. import conf

        def file_stream():
            from ..runtime.retry import FetchFailedError

            n_maps = self.children[0].num_partitions()
            # one local fetch-failure recovery tier (the in-process
            # analogue of the scheduler's map-stage regeneration): a
            # missing/torn/injected-bad block invalidates this
            # exchange's map outputs and re-runs its own map tasks once
            # before the error becomes terminal.  Reads that already
            # yielded batches can't be retried mid-stream — only a
            # failure before the first yield recovers here; later ones
            # propagate to the task-level retry.
            for recovery in range(2):
                self.materialize()
                blocks = self.manager.reduce_blocks(
                    self.shuffle_id, n_maps, partition
                )
                ctx.resources.put(
                    f"shuffle_{self.shuffle_id}.{partition}", blocks
                )
                reader = self._reader.execute(partition, ctx)
                yielded = False
                try:
                    for b in reader:
                        yielded = True
                        yield b
                    return
                except FetchFailedError:
                    ctx.resources.discard(
                        f"shuffle_{self.shuffle_id}.{partition}"
                    )
                    if yielded or recovery == 1:
                        raise
                    with self._lock:
                        self.manager.invalidate(self.shuffle_id)
                        self._materialized = False

        if bool(conf.EXCHANGE_IN_PROCESS.get()) and not self._hbm_fallback:
            def inproc_stream():
                with self._lock:
                    if (
                        getattr(self, "_inproc_outputs", None) is None
                        and not self._hbm_fallback
                    ):
                        try:
                            self._materialize_inprocess(ctx)
                        except _HbmBudgetExceeded:
                            import logging

                            logging.getLogger(__name__).info(
                                "exchange %s: stage output exceeds the HBM "
                                "budget; falling back to the file shuffle",
                                self.shuffle_id,
                            )
                            self._hbm_fallback = True
                    outputs = getattr(self, "_inproc_outputs", None)
                if self._hbm_fallback:
                    yield from file_stream()
                    return
                if outputs is None:  # materialization cancelled
                    return
                # non-destructive read: a task retry can re-execute the
                # partition (parity with the file path, whose blocks
                # stay on disk).  The HBM retention for the plan's
                # lifetime is the documented cost of this path.
                for b in outputs[partition]:
                    self._record_batch(b)
                    yield b

            return inproc_stream()

        return file_stream()
