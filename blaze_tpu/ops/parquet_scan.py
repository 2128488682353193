"""Parquet scan.

≙ reference ParquetExec (parquet_exec.rs:65-418): per-partition file
groups, projected read schema, and statistics-based pruning driven by
pushed-down predicates (the row-group granularity of the reference's
page filtering, conf spark.blaze.parquet.enable.pageFiltering).
Missing columns materialize as nulls and matching is by name —
Spark-compatible schema adaption (scan/mod.rs:28-187).
"""

from __future__ import annotations

import collections
import datetime
import struct
from typing import List, NamedTuple, Optional, Sequence, Union

import numpy as np

from .. import conf
from ..batch import Column, RecordBatch, _pad_1d, bucket_capacity
from ..exprs.compile import infer_lit_dtype
from ..exprs.ir import BinOp, Col, Expr, Lit
from ..io import parquet as pq
from ..runtime import dispatch, trace
from ..runtime.context import TaskContext
from ..runtime.errors import reraise_control
from ..schema import DataType, Schema, TypeKind
from .base import BatchStream, ExecNode


class FileSplit(NamedTuple):
    """``length`` bytes of ``path`` from ``start``: one piece of a file
    as Spark hands it to a task (a ``PartitionedFile``).  The pieces of
    a file tile it, and a row group belongs to the piece that holds its
    midpoint, so each is read once.  ≙ the ``range {start, end}`` the
    reference's ``NativeParquetScanBase`` puts on each file."""

    path: str
    start: int
    length: int


#: an entry of a scan's file group: a path is the whole file
FileEntry = Union[str, FileSplit]


def entry_path(entry: FileEntry) -> str:
    return entry.path if isinstance(entry, FileSplit) else entry


def split_row_groups(entry: FileEntry,
                     row_groups: Sequence[pq.RowGroupMeta]) -> List[pq.RowGroupMeta]:
    """Those of a file's row groups that ``entry`` reads: all of them
    for a path, for a split those whose midpoint lies in its range
    (parquet-mr's ``RangeMetadataFilter``)."""
    if not isinstance(entry, FileSplit):
        return list(row_groups)
    end = entry.start + entry.length
    return [rg for rg in row_groups if entry.start <= rg.midpoint < end]


def _lit_physical(value, dtype: DataType):
    """Literal -> comparable physical value (matching chunk stats)."""
    if dtype.is_decimal:
        if isinstance(value, float):
            return int(round(value * 10**dtype.scale))
        if isinstance(value, str):
            from decimal import Decimal

            return int(Decimal(value).scaleb(dtype.scale).to_integral_value())
        return int(value) * 10**dtype.scale
    if dtype.kind == TypeKind.DATE32:
        if isinstance(value, str):
            value = datetime.date.fromisoformat(value)
        if isinstance(value, datetime.date):
            return (value - datetime.date(1970, 1, 1)).days
        return int(value)
    if dtype.is_string:
        return value.encode("utf-8") if isinstance(value, str) else bytes(value)
    return value


def _prune_conjuncts(predicate: Optional[Expr]) -> List:
    """Extract (col, op, physical literal) conjuncts usable against
    row-group min/max stats."""
    out = []

    def walk(e: Optional[Expr]):
        if e is None:
            return
        if isinstance(e, BinOp):
            if e.op == "and":
                walk(e.left)
                walk(e.right)
                return
            if e.op in ("<", "<=", ">", ">=", "=="):
                l, r = e.left, e.right
                if isinstance(l, Col) and isinstance(r, Lit) and r.value is not None:
                    t = infer_lit_dtype(r.value, r.dtype)
                    out.append((l.name, e.op, _lit_physical(r.value, t)))
                elif isinstance(r, Col) and isinstance(l, Lit) and l.value is not None:
                    flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "==": "=="}
                    t = infer_lit_dtype(l.value, l.dtype)
                    out.append((r.name, flip[e.op], _lit_physical(l.value, t)))

    walk(predicate)
    return out


def _maybe_match(chunk: pq.ChunkMeta, dtype: DataType, op: str, lit_v) -> bool:
    if chunk.min_value is None or chunk.max_value is None:
        return True
    try:
        if chunk.phys == pq.T_FLBA:
            # FLBA stats (decimal): big-endian signed
            lo = int.from_bytes(chunk.min_value, "big", signed=True)
            hi = int.from_bytes(chunk.max_value, "big", signed=True)
        else:
            lo = pq._stat_value(dtype, chunk.min_value)
            hi = pq._stat_value(dtype, chunk.max_value)
    except (struct.error, ValueError) as e:
        reraise_control(e)
        return True
    try:
        if op == "<":
            return lo < lit_v
        if op == "<=":
            return lo <= lit_v
        if op == ">":
            return hi > lit_v
        if op == ">=":
            return hi >= lit_v
        if op == "==":
            return lo <= lit_v <= hi
    except TypeError:
        return True
    return True


class ParquetScanExec(ExecNode):
    def __init__(
        self,
        file_groups: Sequence[Sequence[FileEntry]],
        schema: Schema,
        predicate: Optional[Expr] = None,
        batch_rows: int = 0,
    ):
        super().__init__([])
        # one group a task; an entry is a path (the whole file) or a
        # FileSplit (the row groups whose midpoint lies in its range)
        self.file_groups = [list(g) for g in file_groups]
        self._schema = schema
        self.predicate = predicate
        # what the plan states travels with it (serde); 0 = it states
        # none, and the executor's spark.blaze.batchSize decides
        self.stated_batch_rows = int(batch_rows)
        self.batch_rows = self.stated_batch_rows or int(conf.BATCH_SIZE.get())
        self._conjuncts = _prune_conjuncts(predicate) if bool(
            conf.PARQUET_FILTER_PUSHDOWN.get()
        ) else []

    @property
    def schema(self) -> Schema:
        return self._schema

    def num_partitions(self) -> int:
        return max(1, len(self.file_groups))

    def _null_column(self, dtype: DataType, cap: int) -> Column:
        if dtype.is_string:
            return Column(
                dtype,
                np.zeros((cap, dtype.string_width), np.uint8),
                np.zeros(cap, np.bool_),
                np.zeros(cap, np.int32),
            )
        return Column(dtype, np.zeros(cap, dtype.np_dtype), np.zeros(cap, np.bool_))

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        files = self.file_groups[partition] if partition < len(self.file_groups) else []

        def stream():
            for entry in files:
                path = entry_path(entry)
                # one task's open of one file: its footer, which of its
                # row groups are this entry's, and Arrow's reader over it
                # where pyarrow imports (closed with the entry: no handle,
                # footer or decoded array outlives its task)
                with trace.span("scan_open"):
                    try:
                        row_groups = pq.read_metadata(path).row_groups
                        arrow_file = pq.open_arrow_file(path, self._schema.fields, row_groups)
                    except Exception:
                        if bool(conf.IGNORE_CORRUPT_FILES.get()):
                            self.metrics.add("skipped_corrupt_files", 1)
                            continue
                        raise
                    mine = split_row_groups(entry, row_groups)
                dispatch.record("scan_splits")
                dispatch.record("scan_row_groups_other_split", len(row_groups) - len(mine))
                try:
                    yield from self._row_group_batches(path, mine, arrow_file)
                finally:
                    if arrow_file is not None:
                        arrow_file.close(force=True)

        from ..runtime.pipeline import maybe_pipelined

        # file decode overlaps downstream device compute (≙ rt.rs:100-133)
        return maybe_pipelined(self._staged(stream()), ctx, "parquet_scan")

    def _row_group_batches(self, path: str, row_groups: Sequence[pq.RowGroupMeta], arrow_file):
        """The batches of one entry's row groups, each decoded once."""
        for rg in row_groups:
            if rg.rows == 0:
                continue
            pruned = False
            for name, op, lit_v in self._conjuncts:
                ch = rg.chunks.get(name)
                if ch is None:
                    continue
                fld = next((f for f in self._schema.fields if f.name == name), None)
                if fld is None:
                    # predicate column pruned from the read
                    # schema: stats pruning just skips it
                    continue
                if not _maybe_match(ch, fld.dtype, op, lit_v):
                    pruned = True
                    break
            if pruned:
                self.metrics.add("pruned_row_groups", 1)
                self.metrics.add("pruned_rows", rg.rows)
                dispatch.record("scan_row_groups_pruned")
                continue
            # one row group's fetch + decompress + decode, every
            # column straight into arrays of the row group's capacity:
            # through Arrow's reader in one call outside the GIL, then
            # converted whole, where ``arrow_file`` is there and takes
            # the chunk, page by page in this module's decoder else;
            # once a row group, in the producer thread where the scan
            # is pipelined
            decoded = collections.Counter()
            with self.metrics.timer("input_io_time", trace.span("scan_decode")):
                cap = bucket_capacity(rg.rows)
                fields = self._schema.fields
                chunks = pq.read_row_group(path, rg, fields, cap,
                                           arrow_file=arrow_file, tally=decoded)
                # schema adaption: missing column -> null
                cols: List[Column] = [
                    self._null_column(f.dtype, cap) if arrays is None else Column(f.dtype, *arrays)
                    for f, arrays in zip(fields, chunks)]
            dispatch.record("scan_file_bytes", sum(
                rg.chunks[f.name].total_comp for f in fields if f.name in rg.chunks))
            dispatch.record("scan_row_groups")
            dispatch.record("scan_chunks", decoded["chunks"])
            dispatch.record("scan_chunks_native", decoded["chunks_native"])
            # pages the page decoder walked: none where Arrow took every chunk
            dispatch.record("scan_pages", decoded["pages"])
            dispatch.record("scan_pages_python_codec", decoded["pages_python_codec"])
            # emit in batch_rows slices to bound device batches
            full = RecordBatch(self._schema, cols, rg.rows)
            if rg.rows <= self.batch_rows:
                self.metrics.add("output_rows", rg.rows)
                yield full
            else:
                host = full
                for s in range(0, rg.rows, self.batch_rows):
                    # one sliced batch's construction, on the producer
                    # thread where the scan is pipelined; closed before
                    # the yield
                    with trace.span("scan_slice"):
                        e = min(s + self.batch_rows, rg.rows)
                        scap = bucket_capacity(e - s)
                        sl: List[Column] = []
                        for c in host.columns:
                            d = np.asarray(c.data)[s:e]
                            sl.append(
                                Column(
                                    c.dtype,
                                    _pad_1d(np.ascontiguousarray(d), scap),
                                    _pad_1d(np.asarray(c.validity)[s:e], scap),
                                    None
                                    if c.lengths is None
                                    else _pad_1d(np.asarray(c.lengths)[s:e], scap),
                                )
                            )
                        b = RecordBatch(self._schema, sl, e - s)
                    self._record_batch(b)
                    yield b
