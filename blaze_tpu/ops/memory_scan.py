"""In-memory table source.

≙ DataFusion's MemoryExec, which the reference uses as its unit-test
fixture source (SURVEY.md §4: "operator tests with MemoryExec
fixtures"); also the execution-side of ConvertToNative/FFIReaderExec
when batches are handed over pre-staged.
"""

from __future__ import annotations

import itertools
from typing import List, Optional, Sequence

from ..batch import RecordBatch
from ..runtime.context import TaskContext
from ..schema import Schema
from .base import BatchStream, ExecNode

#: process-global id source for memory tables — a fresh MemoryScanExec
#: is a fresh SOURCE for result-cache versioning (querycache), so two
#: scans over coincidentally-equal data never share cached results
_source_ids = itertools.count(1)


class MemoryScanExec(ExecNode):
    def __init__(self, partitions: Sequence[Sequence[RecordBatch]], schema: Optional[Schema] = None):
        super().__init__([])
        self._partitions: List[List[RecordBatch]] = [list(p) for p in partitions]
        if schema is None:
            first = next((b for p in self._partitions for b in p), None)
            assert first is not None, "schema required for empty MemoryScanExec"
            schema = first.schema
        self._schema = schema
        # result-cache source version (runtime/querycache.py): the
        # (source_id, epoch) pair is this table's data identity — any
        # mutation bumps the epoch, invalidating exactly the cached
        # results derived from it
        self.source_id: int = next(_source_ids)
        self.epoch: int = 0

    @property
    def schema(self) -> Schema:
        return self._schema

    def num_partitions(self) -> int:
        return max(1, len(self._partitions))

    # --------------------------------------------- table mutation API
    #
    # serving-mode tables mutate between queries (appends, compaction
    # rewrites); both paths bump the epoch so the result cache drops
    # dependent entries instead of serving stale rows.

    def append(self, partition: int, batch: RecordBatch) -> None:
        """Append one batch to ``partition`` (extending the partition
        list for a new partition index) and bump the source epoch."""
        while len(self._partitions) <= partition:
            self._partitions.append([])
        self._partitions[partition].append(batch)
        self.epoch += 1

    def replace(self, partitions: Sequence[Sequence[RecordBatch]]) -> None:
        """Replace the table's contents wholesale (a compaction or
        rewrite) and bump the source epoch."""
        self._partitions = [list(p) for p in partitions]
        self.epoch += 1

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        from ..runtime import monitor

        def stream():
            if partition < len(self._partitions):
                # device staging is the scan's own work: input_io_time
                # lets EXPLAIN ANALYZE attribute host time in the
                # enqueue (to_device() is asynchronous: not the
                # transfer) to this node
                for out in self._staged(self._partitions[partition]):
                    self._record_batch(out)
                    # heartbeat hookpoint: every plan bottoms out in a
                    # scan, so a task beats per source batch even when
                    # fused operators above yield nothing to the driver
                    monitor.tick()
                    yield out

        return stream()
