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
import contextlib
import datetime
import time
from fractions import Fraction
from typing import Any, List, NamedTuple, Optional, Sequence, Union

import numpy as np

from .. import conf
from ..batch import Column, RecordBatch, _pad_1d, bucket_capacity
from ..exprs.compile import decimal_unscaled, infer_lit_dtype
from ..exprs.ir import BinOp, Col, Expr, InList, IsNotNull, Lit
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
    reference's ``NativeParquetScanBase`` puts on each file.

    ``values``: for a file of a Hive-partitioned table, its directory's
    value of each field of the scan's partition schema, in that order
    (≙ ``PartitionedFile.partition_values``) — what the column's array
    holds (an int for an integer, a date's days or a decimal's unscaled
    digits, bytes for a string), None for
    ``__HIVE_DEFAULT_PARTITION__``.  Typed, as Spark's
    ``PartitionedFile.partitionValues`` are: the driver that listed the
    directories parsed their names, and no executor parses one again."""

    path: str
    start: int
    length: int
    values: tuple = ()


#: an entry of a scan's file group: a path is the whole file
FileEntry = Union[str, FileSplit]


def entry_path(entry: FileEntry) -> str:
    return entry.path if isinstance(entry, FileSplit) else entry


def entry_values(entry: FileEntry) -> tuple:
    return entry.values if isinstance(entry, FileSplit) else ()


def split_row_groups(entry: FileEntry,
                     row_groups: Sequence[pq.RowGroupMeta]) -> List[pq.RowGroupMeta]:
    """Those of a file's row groups that ``entry`` reads: all of them
    for a path, for a split those whose midpoint lies in its range
    (parquet-mr's ``RangeMetadataFilter``)."""
    if not isinstance(entry, FileSplit):
        return list(row_groups)
    end = entry.start + entry.length
    return [rg for rg in row_groups if entry.start <= rg.midpoint < end]


#: a comparison of a column with a literal, read from the column's side
_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "==": "==", "!=": "!="}
_EPOCH = datetime.date(1970, 1, 1)


class Conjunct(NamedTuple):
    """One conjunct of a scan's predicate that a chunk's statistics can
    rule out: ``name <op> value`` for ``op`` in ``_FLIP``, ``value`` in
    the column's units (_lit_physical); ``op`` "in" with ``value`` the
    tuple of such values; ``op`` "notnull" with none."""

    name: str
    op: str
    value: Any


def _lit_physical(lit: Lit, dtype: DataType):
    """``lit`` in the units its column's statistics are ordered in, as
    the engine compares it: a decimal column's unscaled digits, exactly
    — a Fraction where the literal holds more digits than the column's
    scale — an integer as itself, a date's days since the epoch, a
    string's UTF-8 bytes.  None where that cannot be said exactly: a
    float, whose comparison with an exact column rounds; a literal of
    another kind than its column; a string as long as the column's
    width, since the column holds its values cut there; a column of any
    other kind."""
    if isinstance(lit.value, bool):
        return None
    try:
        return _in_units(lit.value, infer_lit_dtype(lit.value, lit.dtype), dtype)
    except (TypeError, ValueError, ArithmeticError) as e:
        reraise_control(e)
        return None


def _in_units(value, t: DataType, dtype: DataType):
    if dtype.is_decimal or dtype.is_integer:
        if t.is_decimal:
            exact = Fraction(decimal_unscaled(value, t.scale), 10**t.scale)
        elif t.is_integer and isinstance(value, int):
            exact = Fraction(value)
        else:
            return None
        if dtype.is_decimal:
            exact *= 10**dtype.scale
        return exact.numerator if exact.denominator == 1 else exact
    if dtype.kind == TypeKind.DATE32:
        if t.kind != TypeKind.DATE32 or isinstance(value, datetime.datetime):
            return None
        if isinstance(value, str):
            value = datetime.date.fromisoformat(value)
        if isinstance(value, datetime.date):
            return (value - _EPOCH).days
        return value if isinstance(value, int) else None
    if dtype.is_string and isinstance(value, (str, bytes)):
        b = value.encode("utf-8") if isinstance(value, str) else bytes(value)
        # shorter than the width, it orders against a cut value as against the whole
        return b if len(b) < dtype.string_width else None
    return None


def _conjunct(e: Expr, dtypes: dict) -> Optional[Conjunct]:
    if isinstance(e, IsNotNull) and isinstance(e.child, Col) and e.child.name in dtypes:
        return Conjunct(e.child.name, "notnull", None)
    if (isinstance(e, InList) and not e.negated and isinstance(e.child, Col)
            and e.child.name in dtypes and all(isinstance(v, Lit) for v in e.values)):
        # a NULL of the list passes no row
        values = [_lit_physical(v, dtypes[e.child.name]) for v in e.values if v.value is not None]
        if any(v is None for v in values):
            return None
        return Conjunct(e.child.name, "in", tuple(values))
    if isinstance(e, BinOp) and e.op in _FLIP:
        column, lit, op = e.left, e.right, e.op
        if isinstance(column, Lit) and isinstance(lit, Col):
            column, lit, op = lit, column, _FLIP[op]
        if (isinstance(column, Col) and isinstance(lit, Lit) and lit.value is not None
                and column.name in dtypes):
            value = _lit_physical(lit, dtypes[column.name])
            if value is not None:
                return Conjunct(column.name, op, value)
    return None


def _prune_conjuncts(predicate: Optional[Expr], schema: Schema) -> List[Conjunct]:
    """The conjuncts of ``predicate`` — through AND, and nothing else —
    over columns of ``schema`` that statistics can rule out: a column
    compared with a literal on either side, a column IN literals,
    IsNotNull of a column.  Anything else (OR, NOT, a cast of the
    column, a function) and a literal _lit_physical cannot state
    exactly is left out: it rules out no row group, and the filter
    above the scan applies it still."""
    dtypes = {f.name: f.dtype for f in schema.fields}
    out, todo = [], [] if predicate is None else [predicate]
    while todo:
        e = todo.pop()
        if isinstance(e, BinOp) and e.op == "and":
            todo += [e.right, e.left]
        elif (c := _conjunct(e, dtypes)) is not None:
            out.append(c)
    return out


def _ordered_by(dtype: DataType) -> tuple:
    """The physical types whose statistics order a column of ``dtype``
    in _lit_physical's units."""
    if dtype.is_decimal:
        return pq.T_INT32, pq.T_INT64, pq.T_FLBA
    if dtype.is_integer:
        return pq.T_INT32, pq.T_INT64
    if dtype.kind == TypeKind.DATE32:
        return (pq.T_INT32,)
    return (pq.T_BYTE_ARRAY,) if dtype.is_string else ()


def _rules_out(c: Conjunct, chunk: pq.ChunkMeta, rows: int, dtype: DataType) -> bool:
    """Whether ``chunk``'s statistics prove that none of the ``rows`` of
    its row group passes ``c``.  A NULL passes none of the conjuncts, so
    a chunk of NULLs alone is ruled out by each; past that, its min and
    max, where its physical type orders them as ``dtype``'s values."""
    if chunk.null_count is not None and chunk.null_count >= rows:
        return True
    bounds = pq.chunk_bounds(chunk)
    if c.op == "notnull" or bounds is None or chunk.phys not in _ordered_by(dtype):
        return False
    lo, hi = bounds
    v = c.value
    if c.op == "in":
        return all(x < lo or x > hi for x in v)
    if c.op == "<":
        return lo >= v
    if c.op == "<=":
        return lo > v
    if c.op == ">":
        return hi <= v
    if c.op == ">=":
        return hi < v
    if c.op == "==":
        return v < lo or v > hi
    return lo == hi == v  # !=


class ParquetScanExec(ExecNode):
    def __init__(
        self,
        file_groups: Sequence[Sequence[FileEntry]],
        schema: Schema,
        predicate: Optional[Expr] = None,
        batch_rows: int = 0,
        partition_schema: Optional[Schema] = None,
    ):
        super().__init__([])
        # one group a task; an entry is a path (the whole file) or a
        # FileSplit (the row groups whose midpoint lies in its range)
        self.file_groups = [list(g) for g in file_groups]
        # what the files hold of the table; the columns that live in the
        # paths follow it in the output, each file's value repeated
        self._schema = schema
        self.partition_schema = partition_schema or Schema([])
        n_values = len(self.partition_schema.fields)
        for entry in (e for g in self.file_groups for e in g):
            if len(entry_values(entry)) != n_values:
                raise ValueError(f"{entry!r} carries no value for each of the partition "
                                 f"columns {self.partition_schema.names}")
        self._out_schema = Schema(list(schema.fields) + list(self.partition_schema.fields))
        self.predicate = predicate
        # what the plan states travels with it (serde); 0 = it states
        # none, and the executor's spark.blaze.batchSize decides
        self.stated_batch_rows = int(batch_rows)
        self.batch_rows = self.stated_batch_rows or int(conf.BATCH_SIZE.get())
        # over the files' columns: a partition column has no statistics
        self._conjuncts = _prune_conjuncts(predicate, schema) if bool(
            conf.PARQUET_FILTER_PUSHDOWN.get()
        ) else []
        self._stat_columns = frozenset(c.name for c in self._conjuncts)

    @property
    def schema(self) -> Schema:
        return self._out_schema

    def num_partitions(self) -> int:
        return max(1, len(self.file_groups))

    def with_predicate(self, predicate: Expr) -> "ParquetScanExec":
        """A copy of this scan with ``predicate`` ANDed to its own: the
        row groups whose statistics rule it out are never fetched.  This
        scan is left as it is."""
        if self.predicate is not None:
            predicate = BinOp("and", self.predicate, predicate)
        return ParquetScanExec(self.file_groups, self._schema, predicate, self.stated_batch_rows,
                               self.partition_schema)

    def narrowed(self, names: Sequence[str]) -> "ParquetScanExec":
        """This scan reading ``names`` alone: fewer chunks of each file,
        and of a partitioned table's files the values of the partition
        columns among them."""
        kept = [i for i, f in enumerate(self.partition_schema.fields) if f.name in names]
        groups = self.file_groups
        if len(kept) < len(self.partition_schema.fields):
            groups = [[e._replace(values=tuple(e.values[i] for i in kept)) for e in g]
                      for g in groups]
        return ParquetScanExec(
            groups, Schema([f for f in self._schema.fields if f.name in names]),
            self.predicate, self.stated_batch_rows,
            Schema([self.partition_schema.fields[i] for i in kept]))

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
        """Three threads a task where the scan is pipelined
        (``spark.blaze.pipeline.depth`` > 0), each at most ``depth``
        hand-overs ahead of the next: the DECODE thread
        (``blaze-parquet_decode``) opens each entry, chooses and prunes
        its row groups and decodes them piece by piece (_pieces; every
        use of an Arrow ``ParquetFile``, its close too, is this
        thread's); the STAGING thread (``blaze-parquet_scan``) makes each
        piece's host batches and stages them (_batches, _staged); the
        task thread launches.  With depth 0 all of it runs on the
        calling thread."""
        from ..runtime.pipeline import maybe_pipelined

        files = self.file_groups[partition] if partition < len(self.file_groups) else []
        # the decode's own hand-over tallies as decode_wait / decode_full /
        # decode_items; pipeline_* stay the task-facing hand-over's alone
        pieces = maybe_pipelined(self._pieces(files), ctx, "parquet_decode", tally="decode")
        # file decode overlaps staging, staging overlaps downstream
        # device compute (≙ rt.rs:100-133)
        return maybe_pipelined(self._staged(self._batches(pieces)), ctx, "parquet_scan")

    def _pieces(self, files: Sequence[FileEntry]):
        """The decoded pieces of one task's entries, file by file, row
        group by row group: ``(chunks, lo, hi, cut)`` — rows ``[lo, hi)``
        of ``chunks`` (pq.read_row_group_pieces' for the columns the
        file holds, then the entry's partition values, each repeated)
        and whether the row group is cut into more than one batch."""
        for entry in files:
            path = entry_path(entry)
            # one task's open of one file: its footer, which of its
            # row groups are this entry's, and Arrow's reader over it
            # where pyarrow imports (closed with the entry, on this
            # thread: no handle, footer or decoded array outlives its task)
            with trace.span("scan_open"):
                arrow_file = None
                try:
                    arrow_file = pq.open_arrow_file(path, self._schema.fields)
                    # the footer Arrow parsed, with the statistics the
                    # predicate reads; the thrift reader's where Arrow
                    # does not take the file
                    row_groups = None
                    if arrow_file is not None:
                        row_groups = pq.arrow_row_groups(arrow_file, self._stat_columns)
                    if row_groups is None:
                        row_groups = pq.read_metadata(path).row_groups
                except Exception:
                    if arrow_file is not None:
                        arrow_file.close(force=True)
                    if bool(conf.IGNORE_CORRUPT_FILES.get()):
                        self.metrics.add("skipped_corrupt_files", 1)
                        continue
                    raise
                mine = split_row_groups(entry, row_groups)
            values = entry_values(entry)
            dispatch.record("scan_splits")
            dispatch.record("scan_partition_files", bool(values))
            dispatch.record("scan_row_groups_other_split", len(row_groups) - len(mine))
            try:
                kept = [rg for rg in mine if rg.rows]
                if self._conjuncts:
                    # the statistics' verdict on the entry's row groups,
                    # before one of them is fetched
                    with trace.span("scan_prune"):
                        kept = [rg for rg in kept if not self._pruned(rg)]
                chosen = sum(rg.rows for rg in mine)
                dispatch.record("scan_rows_chosen", chosen)
                dispatch.record("scan_rows_pruned", chosen - sum(rg.rows for rg in kept))
                for rg in kept:
                    for chunks, lo, hi, cut in self._row_group_pieces(path, rg, arrow_file):
                        yield chunks + self._partition_arrays(values, hi), lo, hi, cut
            finally:
                if arrow_file is not None:
                    arrow_file.close(force=True)

    def _partition_arrays(self, values: tuple, rows: int) -> list:
        """A piece's partition columns as decoded chunks come —
        ``(data, validity, lengths|None)`` at the capacity of ``rows``,
        the piece's end in its arrays: each of the file's ``values``
        repeated, a None all null."""
        cap = bucket_capacity(rows)
        out = []
        for f, value in zip(self.partition_schema.fields, values):
            col = self._null_column(f.dtype, cap)
            if value is not None:
                col.validity[:rows] = True
                if f.dtype.is_string:
                    col.data[:rows, :len(value)] = np.frombuffer(value, np.uint8)
                    col.lengths[:rows] = len(value)
                else:
                    col.data[:rows] = value
            out.append((col.data, col.validity, col.lengths))
        return out

    def _pruned(self, rg: pq.RowGroupMeta) -> bool:
        """Whether the chunk statistics rule the row group out, decided
        before it is opened."""
        for c in self._conjuncts:
            ch = rg.chunks.get(c.name)  # none: a column the file lacks, with no statistics
            if ch is not None and _rules_out(c, ch, rg.rows, self._schema.field(c.name).dtype):
                self.metrics.add("pruned_row_groups", 1)
                self.metrics.add("pruned_rows", rg.rows)
                dispatch.record("scan_row_groups_pruned")
                return True
        return False

    def _row_group_pieces(self, path: str, rg: pq.RowGroupMeta, arrow_file):
        """One row group's fetch + decompress + decode, each row once,
        on the decode thread where the scan is pipelined.  Where
        ``arrow_file`` takes every chunk, a stream: Arrow's reader
        decodes page by page, outside the GIL, as each piece of
        ``batch_rows`` rows is pulled, and the piece is converted
        straight into arrays of its own capacity — handed on while the
        next is decoded.  Else ONE piece: the whole row group through
        pq.read_row_group, chunk by chunk in this module's page decoder
        where Arrow does not take it.  ``scan_decode`` is an annotation a
        piece and ONE tally a row group: its pieces' nanoseconds summed,
        as _staged does for a stream."""
        decoded = collections.Counter()
        cut = rg.rows > self.batch_rows
        pieces = pq.read_row_group_pieces(path, rg, self._schema.fields, self.batch_rows,
                                          bucket_capacity, arrow_file=arrow_file, tally=decoded)
        ns = 0
        try:
            while True:
                with trace.annotation("scan_decode"):
                    t0 = time.perf_counter_ns()
                    try:
                        piece = next(pieces, None)
                    finally:
                        ns += time.perf_counter_ns() - t0
                if piece is None:
                    break
                yield (*piece, cut)
        finally:
            dispatch.record_span("scan_decode", ns)
            self.metrics.add("input_io_time", ns)
        dispatch.record("scan_file_bytes", sum(
            rg.chunks[f.name].total_comp for f in self._schema.fields if f.name in rg.chunks))
        dispatch.record("scan_row_groups")
        dispatch.record("scan_row_groups_streamed", decoded["streamed"])
        dispatch.record("scan_pieces", decoded["pieces"])
        dispatch.record("scan_chunks", decoded["chunks"])
        dispatch.record("scan_chunks_native", decoded["chunks_native"])
        # pages the page decoder walked: none where Arrow took every chunk
        dispatch.record("scan_pages", decoded["pages"])
        dispatch.record("scan_pages_python_codec", decoded["pages_python_codec"])

    def _batches(self, pieces):
        """The pieces as host batches of at most ``batch_rows`` rows, on
        the staging thread where the scan is pipelined.  ONE rule packs
        them: whole pieces join the open batch while it stays within
        ``batch_rows`` rows — across row groups and across the files of
        the task — and the piece that would take it past that closes it;
        a piece is never split to fill one.  So a piece of ``batch_rows``
        rows passes through as its arrays are, a task of many small
        files stages a batch or two and not one a file, and what is held
        open is under ``batch_rows`` rows.  A row group decoded whole and
        longer than a batch is sliced.  ``scan_slice`` is around each
        batch of a row group that is cut into several, ``scan_coalesce``
        around the assembly of a batch of several pieces, both closed
        before the yield."""
        held, held_rows = [], 0
        tally = collections.Counter()
        try:
            for chunks, lo, hi, cut in pieces:
                if held and held_rows + hi - lo > self.batch_rows:
                    yield self._packed(held, tally)
                    held, held_rows = [], 0
                if hi - lo < self.batch_rows:
                    held.append((chunks, lo, hi, cut, lo == 0))
                    held_rows += hi - lo
                    continue
                for s in range(lo, hi, self.batch_rows):
                    e = min(s + self.batch_rows, hi)
                    yield self._packed([(chunks, s, e, cut, (s, e) == (0, hi))], tally)
            if held:
                yield self._packed(held, tally)
        finally:
            # what the staged batches held and what they could have: a
            # reader of the counters sees no configuration
            dispatch.record("scan_rows", tally["rows"])
            dispatch.record("scan_rows_budget", tally["batches"] * self.batch_rows)
            dispatch.record("scan_pieces_packed", tally["pieces_packed"])

    def _packed(self, held, tally) -> RecordBatch:
        """One host batch of ``held`` — rows ``[lo, hi)`` of ``chunks``
        each, in order, as ``(chunks, lo, hi, cut, whole)``, ``whole``
        where those rows are all the arrays hold: a single one is its
        rows as _host_batch hands them on; several are copied side by
        side into arrays of their rows' capacity."""
        if len(held) == 1:
            chunks, lo, hi, cut, whole = held[0]
            with trace.span("scan_slice") if cut else contextlib.nullcontext():
                b = self._host_batch(chunks, lo, hi, whole)
        else:
            with trace.span("scan_coalesce"):
                rows = sum(hi - lo for _, lo, hi, _, _ in held)
                cols = [self._null_column(f.dtype, bucket_capacity(rows))
                        for f in self._out_schema.fields]
                at = 0
                for chunks, lo, hi, _, _ in held:
                    for col, arrays in zip(cols, chunks):
                        if arrays is not None:  # a column the file lacks stays null
                            for to, a in zip((col.data, col.validity, col.lengths), arrays):
                                if a is not None:
                                    to[at:at + hi - lo] = a[lo:hi]
                    at += hi - lo
                b = RecordBatch(self._out_schema, cols, rows)
            tally["pieces_packed"] += len(held)
        tally["rows"] += b.num_rows
        tally["batches"] += 1
        self._record_batch(b)
        return b

    def _host_batch(self, chunks, lo: int, hi: int, whole: bool) -> RecordBatch:
        """Rows ``[lo, hi)`` of decoded ``chunks`` at their own capacity:
        the arrays themselves where the rows are all they hold
        (``whole``), a padded copy else; schema adaption: a missing
        column is nulls."""
        cap = bucket_capacity(hi - lo)
        cols: List[Column] = []
        for f, arrays in zip(self._out_schema.fields, chunks):
            if arrays is None:
                cols.append(self._null_column(f.dtype, cap))
                continue
            if not whole:
                arrays = [None if a is None else _pad_1d(np.ascontiguousarray(a[lo:hi]), cap)
                          for a in arrays]
            cols.append(Column(f.dtype, *arrays))
        return RecordBatch(self._out_schema, cols, hi - lo)
