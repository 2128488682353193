"""ORC scan.

≙ reference OrcExec (orc_exec.rs:53-285): per-partition file groups,
projected read schema with by-name adaption (missing columns -> null),
and stripe pruning from the file's stripe-level column statistics —
the ORC analogue of ParquetScanExec's row-group pruning.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .. import conf
from ..batch import Column, RecordBatch, _pad_1d, bucket_capacity
from ..exprs.ir import Expr
from ..io import orc
from ..runtime.context import TaskContext
from ..schema import DataType, Schema, TypeKind
from .base import BatchStream, ExecNode
from .parquet_scan import FileSplit, _prune_conjuncts


def _stat_comparable(dtype: DataType, v):
    if v is None:
        return None
    if dtype.is_string and isinstance(v, (bytes, bytearray)):
        # predicate literals are python str: decode so comparisons in
        # _stripe_maybe_match actually fire instead of raising TypeError
        return bytes(v).decode("utf-8", "surrogateescape")
    return v


def _stripe_maybe_match(stats, dtype: DataType, op: str, lit_v) -> bool:
    mn, mx, _ = stats
    lo = _stat_comparable(dtype, mn)
    hi = _stat_comparable(dtype, mx)
    if lo is None or hi is None:
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


class OrcScanExec(ExecNode):
    def __init__(
        self,
        file_groups: Sequence[Sequence[str]],
        schema: Schema,
        predicate: Optional[Expr] = None,
        batch_rows: int = 0,
    ):
        super().__init__([])
        self.file_groups = [list(g) for g in file_groups]
        for entry in (e for g in self.file_groups for e in g):
            if isinstance(entry, FileSplit):
                # stripes are not yet chosen by range: reading the file
                # whole would count its rows once a split
                raise NotImplementedError(f"OrcScanExec reads whole files, got {entry}")
        self._schema = schema
        self.predicate = predicate
        self.stated_batch_rows = int(batch_rows)  # as ParquetScanExec's
        self.batch_rows = self.stated_batch_rows or int(conf.BATCH_SIZE.get())
        self._conjuncts = _prune_conjuncts(predicate, schema)

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
        if dtype.kind.name == "ARRAY":
            elem = self._null_column(dtype.elem, cap * dtype.max_elems)
            elem = Column(
                dtype.elem,
                None if elem.data is None else elem.data.reshape(
                    (cap, dtype.max_elems) + elem.data.shape[1:]),
                elem.validity.reshape(cap, dtype.max_elems),
                None if elem.lengths is None else elem.lengths.reshape(
                    cap, dtype.max_elems),
            )
            return Column(dtype, None, np.zeros(cap, np.bool_),
                          np.zeros(cap, np.int32), (elem,))
        return Column(dtype, np.zeros(cap, dtype.np_dtype), np.zeros(cap, np.bool_))

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        files = self.file_groups[partition] if partition < len(self.file_groups) else []

        def stream():
            max_w = max(
                [f.dtype.string_width for f in self._schema.fields if f.dtype.is_string],
                default=64,
            )
            max_elems = max(
                [f.dtype.max_elems for f in self._schema.fields
                 if f.dtype.kind.name == "ARRAY"], default=16,
            )
            for path in files:
                try:
                    meta = orc.read_metadata(path, list_elems=max_elems,
                                             string_width=max_w)
                except Exception:
                    if bool(conf.IGNORE_CORRUPT_FILES.get()):
                        self.metrics.add("skipped_corrupt_files", 1)
                        continue
                    raise
                file_fields = {f.name: f for f in meta.schema.fields}
                for stripe in meta.stripes:
                    if stripe.rows == 0:
                        continue
                    pruned = False
                    for name, op, lit_v in self._conjuncts:
                        st = stripe.stats.get(name)
                        if st is None or name not in file_fields:
                            continue
                        fld = next((f for f in self._schema.fields if f.name == name), None)
                        if fld is None:
                            continue  # predicate column pruned from read schema
                        if not _stripe_maybe_match(st, fld.dtype, op, lit_v):
                            pruned = True
                            break
                    if pruned:
                        self.metrics.add("pruned_stripes", 1)
                        self.metrics.add("pruned_rows", stripe.rows)
                        continue
                    with self.metrics.timer("input_io_time"):
                        raw = orc.read_stripe(path, meta, stripe)
                    rows = stripe.rows
                    for s in range(0, rows, self.batch_rows):
                        e = min(s + self.batch_rows, rows)
                        cap = bucket_capacity(e - s)
                        cols: List[Column] = []
                        for f in self._schema.fields:
                            if f.name not in raw:
                                cols.append(self._null_column(f.dtype, cap))
                                continue
                            if (len(raw[f.name]) == 2
                                    and raw[f.name][0] == "py"):
                                # compound column decoded to python
                                # values; build the padded nested
                                # Column through the canonical path
                                from ..batch import column_from_pylist

                                _, vals = raw[f.name]
                                cols.append(column_from_pylist(
                                    f.dtype, list(vals[s:e]), capacity=cap))
                                continue
                            if len(raw[f.name]) == 4:
                                # LIST column: (None, validity, lengths,
                                # (elem_data, elem_valid)) from the reader
                                _, validity, lengths, (ed, ev) = raw[f.name]
                                m = f.dtype.max_elems
                                if int(np.max(lengths[s:e], initial=0)) > m:
                                    # read_metadata decodes with ONE
                                    # uniform cap (the widest field);
                                    # a narrower declared field must
                                    # gate, not silently truncate
                                    raise NotImplementedError(
                                        f"ORC subset: list length "
                                        f"{int(np.max(lengths[s:e]))} exceeds "
                                        f"max_elems {m} for {f.name!r}")
                                ed2 = np.zeros((cap, m), f.dtype.elem.np_dtype)
                                ev2 = np.zeros((cap, m), np.bool_)
                                k = min(m, ed.shape[1])
                                ed2[: e - s, :k] = ed[s:e, :k].astype(
                                    f.dtype.elem.np_dtype, copy=False)
                                ev2[: e - s, :k] = ev[s:e, :k]
                                elem = Column(f.dtype.elem, ed2, ev2)
                                cols.append(Column(
                                    f.dtype, None,
                                    _pad_1d(validity[s:e], cap),
                                    _pad_1d(np.minimum(lengths[s:e], m), cap),
                                    (elem,),
                                ))
                                continue
                            data, validity, lengths = raw[f.name]
                            if f.dtype.is_string:
                                d = np.zeros((cap, f.dtype.string_width), np.uint8)
                                seg = data[s:e]
                                d[: e - s, : min(seg.shape[1], f.dtype.string_width)] = seg[
                                    :, : f.dtype.string_width
                                ]
                                cols.append(
                                    Column(
                                        f.dtype,
                                        d,
                                        _pad_1d(validity[s:e], cap),
                                        _pad_1d(
                                            np.minimum(lengths[s:e], f.dtype.string_width), cap
                                        ),
                                    )
                                )
                            else:
                                cols.append(
                                    Column(
                                        f.dtype,
                                        _pad_1d(
                                            data[s:e].astype(f.dtype.np_dtype, copy=False), cap
                                        ),
                                        _pad_1d(validity[s:e], cap),
                                    )
                                )
                        b = RecordBatch(self._schema, cols, e - s)
                        self._record_batch(b)
                        yield b

        from ..runtime.pipeline import maybe_pipelined

        # file decode overlaps downstream device compute (≙ rt.rs:100-133)
        return maybe_pipelined(self._staged(stream()), ctx, "orc_scan")
