"""ExecNode: the operator interface.

≙ DataFusion's ``ExecutionPlan`` as used by the reference
(from_proto.rs builds ``Arc<dyn ExecutionPlan>`` trees;
datafusion-ext-plans implements them).  Differences, TPU-first:

- ``execute`` returns a plain python iterator of RecordBatches; the
  task runtime (runtime/task.py) drives it through a bounded channel
  on a worker thread (≙ tokio + sync_channel(1), rt.rs:100-133).
- the hot math lives in jitted per-batch kernels; the iterator layer
  only sequences device calls and host IO.
"""

from __future__ import annotations

import time
from typing import Iterator, List, Optional, Sequence

from ..batch import RecordBatch
from ..runtime import dispatch, monitor, trace
from ..runtime.context import TaskContext
from ..runtime.metrics import MetricsSet
from ..schema import Schema

BatchStream = Iterator[RecordBatch]


class ExecNode:
    """Base physical operator."""

    def __init__(self, children: Sequence["ExecNode"]):
        self.children: List[ExecNode] = list(children)
        self.metrics = MetricsSet()

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        raise NotImplementedError

    # ------------------------------------------------- tracing contract
    #
    # Whole-stage program fusion (ops/fusion.py) composes consecutive
    # unary operators into ONE jitted per-batch program: every operator
    # boundary otherwise costs an XLA dispatch + a materialized
    # intermediate (an HBM write and read-back between two programs).

    def trace_fn(self):
        """Pure per-batch transform ``(cols, num_rows) -> (cols,
        num_rows)`` safe to inline inside an enclosing ``jax.jit``
        (``num_rows`` may be a traced scalar; all intermediates stay on
        device), or ``None`` when this operator cannot be traced
        (blocking, stateful across batches, multi-child, or
        host-dependent).  The returned closure must capture only
        schemas / expression IR — never the child subtree (fused
        programs are cached process-wide, kernel_cache rules apply)."""
        return None

    def trace_key(self):
        """Structural cache key for :meth:`trace_fn` (kernel_cache
        conventions: schema signature + expression keys).  Required
        non-None whenever trace_fn returns a function."""
        return None

    def trace_slots(self) -> tuple:
        """Slot values (numpy scalars) for this operator's slotified
        literals (exprs.compile.slotify_literals) — the parameters that
        let `WHERE price > 5` and `WHERE price > 9` share one compiled
        program.  CONTRACT: when non-empty, the transform returned by
        :meth:`trace_fn` expects exactly ``len(trace_slots())`` traced
        scalars appended at the TAIL of its ``cols`` tuple (after the
        schema columns) and slices them off itself; callers — the
        standalone execute, FusedStageExec, the fused shuffle write,
        and the eager OOM rung — append the values per call.  The
        values are DATA, never part of :meth:`trace_key`."""
        return ()

    @property
    def trace_changes_count(self) -> bool:
        """True when the traced transform can change ``num_rows`` (a
        filter compacts); the fused stage then syncs the one count
        scalar per batch, exactly like the standalone operator."""
        return False

    @property
    def trace_requires_buffer(self) -> bool:
        """True when the traced transform is only exact over the WHOLE
        partition in one batch (WindowExec: partition segments span
        batch boundaries).  Fusion then plants a buffering node below
        the fused program — the same concat-the-partition semantics the
        operator's own execute uses — instead of applying it per
        streamed batch."""
        return False

    @property
    def has_kernel(self) -> bool:
        """False when this operator issues no device program of its own
        (pure column selects); fusion only builds a combined program
        when it replaces at least two real kernels."""
        return True

    # ------------------------------------- static-analysis contract
    #
    # Declarations the plan verifier (analysis/plan_verify.py, conf
    # spark.blaze.verify.plan) checks over every optimized plan: the
    # rewrite tiers rely on these prerequisites holding, and a rewrite
    # that breaks one produces wrong ANSWERS, not errors.

    def required_child_distribution(self):
        """None, or ``("hash", frozenset(expr_keys))``: the child
        subtree must deliver co-partitioning on these keys (a FINAL
        grouped agg needs every row of a group in one partition) —
        rule ``dist.final-agg``."""
        return None

    def required_child_orderings(self):
        """Per-child ordering prerequisite: None (no requirement) or a
        tuple of expr_keys the child stream must be key-sorted on
        (prefix match; the EMPTY tuple means 'must be downstream of
        some sort', the relaxed form) — rules ``order.*``."""
        return [None] * len(self.children)

    def provided_ordering(self):
        """expr_keys this node's OUTPUT is sorted on (() = none):
        SortExec declares its fields, a FINAL agg its fused
        ``post_sort``."""
        return ()

    @property
    def preserves_ordering(self) -> bool:
        """True when this unary op passes its child's sort order
        through (filters compact in order; sorts/aggs/exchanges
        destroy or replace it)."""
        return False

    def num_partitions(self) -> int:
        """Output partitioning degree (propagates from children by
        default)."""
        if self.children:
            return self.children[0].num_partitions()
        return 1

    def _record_batch(self, b) -> None:
        """Land one output batch's rows/bytes/batches on this node's
        MetricsSet — the per-node annotation EXPLAIN ANALYZE
        (runtime/perf.py) renders.  ``nbytes`` is an attribute read
        per column buffer, never a device sync."""
        self.metrics.add("output_rows", b.num_rows)
        self.metrics.add("output_batches")
        self.metrics.add(
            "output_bytes",
            sum(getattr(c.data, "nbytes", 0) for c in b.columns))

    def _staged(self, host_batches) -> BatchStream:
        """A scan's H2D staging: each host batch through ``to_device()``
        under a ``scan_stage`` annotation — host time in the enqueue,
        not the transfer.  The one per-batch site: it sums locally and
        reaches the tally once, when the stream ends (``scan_stage_ns``,
        ``scan_stage_n`` batches, ``h2d_bytes`` from shapes — every host
        array handed over, a validity left untransferred included —,
        ``h2d_arrays`` transferred, ``h2d_masks_shared`` validities that
        took a shared row mask), with the same nanoseconds as this
        node's ``input_io_time``."""
        ns = n = nbytes = arrays = shared = 0
        try:
            for b in host_batches:
                with trace.annotation("scan_stage"):
                    t0 = time.perf_counter_ns()
                    out, k, s = b.to_device_counted()
                    ns += time.perf_counter_ns() - t0
                n += 1
                nbytes += b.host_nbytes()
                arrays += k
                shared += s
                yield out
        finally:
            if n:
                dispatch.record_span("scan_stage", ns, n, h2d_bytes=nbytes,
                                     h2d_arrays=arrays, h2d_masks_shared=shared)
                self.metrics.add("input_io_time", ns)

    def _count_output(self, stream: BatchStream) -> BatchStream:
        for b in stream:
            self._record_batch(b)
            # heartbeat hookpoint: a task whose plan never yields to
            # the driver (map stages feed the shuffle writer) still
            # beats from inside the operator drive; one thread-local
            # read when no instrumented task is active
            monitor.tick()
            yield b

    def name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + self.name() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def collect(self, ctx: Optional[TaskContext] = None) -> List[RecordBatch]:
        """Run all partitions serially and collect (test helper)."""
        out: List[RecordBatch] = []
        n = self.num_partitions()
        for p in range(n):
            c = ctx or TaskContext(p, n)
            out.extend(self.execute(p, c))
        return out
