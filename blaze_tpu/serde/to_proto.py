"""ExecNode/Expr trees -> protobuf.

≙ the JVM side of the reference's serde (NativeConverters.scala
convertExpr/convertDataType + the per-plan-node proto builders in
spark-extension/.../blaze/plan/*.scala).  In-process this is used by
tests (roundtrip) and by the standalone scheduler when shipping task
plans to worker processes.
"""

from __future__ import annotations

import contextlib
import datetime
import pickle
from typing import Optional

from ..exprs.ir import (
    Alias, BinOp, Case, Cast, Col, Expr, GetIndexedField, GetMapValue,
    GetStructField, InList, IsNotNull, IsNull, Like, Lit, NamedStruct, Not,
    ScalarFunc, SparkUdfWrapper,
)
from ..schema import DataType, Field, Schema, TypeKind
from . import plan_pb2 as pb


import contextvars
import itertools

# itertools.count.__next__ is atomic under the GIL, so concurrent
# serializations (exchange map threads, parallel task-def building)
# never mint the same resource id
_memscan_rids = itertools.count()

# When set (scheduler retry path), every resource id staged during
# serialization is appended here so a failed attempt can discard its
# one-shot resources instead of leaking them in the process-global map.
STAGED_RIDS: contextvars.ContextVar = contextvars.ContextVar(
    "blaze_staged_rids", default=None
)

# The partition a task's plan is being serialised for (task_definition
# sets it), None for a whole plan.  A file scan reached from the root
# through operators that run every child at their own partition then
# carries that partition's file group alone, the other groups empty in
# their places (≙ NativeParquetScanBase: a task's native plan names its
# own FilePartition's files).  What a task decodes, fingerprints and
# estimates is then its own files, not the table's: a date-partitioned
# table lists hundreds of files a query, and every task paid two stats
# a file of all of them (PERF.md section 6, PR 40).
TASK_PARTITION: contextvars.ContextVar = contextvars.ContextVar(
    "blaze_task_partition", default=None
)
#: operators whose execute(p) runs each child at p and no other
#: partition; under any other, a scan keeps every group
_CHILDREN_AT_OWN_PARTITION = frozenset({
    "ParquetScanExec", "OrcScanExec", "ProjectExec", "FilterExec", "AggExec", "SortExec",
    "LimitExec", "RenameColumnsExec", "DebugExec", "CoalesceBatchesExec", "ExpandExec",
    "GenerateExec", "WindowExec", "HashJoinExec", "SortMergeJoinExec", "ShuffleWriterExec",
    "IpcWriterExec", "BroadcastJoinExec",  # its probe side; its build side is read whole
})


@contextlib.contextmanager
def _for_partition(partition: Optional[int]):
    token = TASK_PARTITION.set(partition)
    try:
        yield
    finally:
        TASK_PARTITION.reset(token)


def dtype_to_proto(t: DataType) -> pb.DataTypeProto:
    out = pb.DataTypeProto(
        kind=t.kind.value, precision=t.precision, scale=t.scale,
        string_width=t.string_width, max_elems=t.max_elems,
    )
    if t.elem is not None:
        out.elem.CopyFrom(dtype_to_proto(t.elem))
    if t.key is not None:
        out.key.CopyFrom(dtype_to_proto(t.key))
    if t.value is not None:
        out.value.CopyFrom(dtype_to_proto(t.value))
    if t.struct_fields is not None:
        for f in t.struct_fields:
            out.struct_fields.append(
                pb.FieldProto(name=f.name, dtype=dtype_to_proto(f.dtype), nullable=f.nullable)
            )
    return out


def schema_to_proto(s: Schema) -> pb.SchemaProto:
    return pb.SchemaProto(
        fields=[
            pb.FieldProto(name=f.name, dtype=dtype_to_proto(f.dtype), nullable=f.nullable)
            for f in s.fields
        ]
    )


def _lit_to_proto(e: Lit) -> pb.LiteralValue:
    from ..exprs.compile import decimal_unscaled, infer_lit_dtype

    t = infer_lit_dtype(e.value, e.dtype)
    out = pb.LiteralValue(dtype=dtype_to_proto(t))
    v = e.value
    if v is None:
        out.is_null = True
    elif t.kind == TypeKind.BOOL:
        out.bool_value = bool(v)
    elif t.is_string:
        out.bytes_value = v.encode("utf-8") if isinstance(v, str) else bytes(v)
    elif t.is_float:
        out.float_value = float(v)
    elif t.is_decimal:
        out.int_value = decimal_unscaled(v, t.scale)
    elif t.kind == TypeKind.DATE32:
        if isinstance(v, str):
            v = datetime.date.fromisoformat(v)
        if isinstance(v, datetime.date):
            v = (v - datetime.date(1970, 1, 1)).days
        out.int_value = int(v)
    else:
        out.int_value = int(v)
    return out


def _partition_value_to_proto(v, t: DataType) -> pb.LiteralValue:
    """A file's value of a partition column (``FileSplit.values``: what
    the column's array holds, None for a null) as a typed literal."""
    out = pb.LiteralValue(dtype=dtype_to_proto(t))
    if v is None:
        out.is_null = True
    elif t.kind == TypeKind.BOOL:
        out.bool_value = bool(v)
    elif t.is_string:
        out.bytes_value = bytes(v)
    elif t.is_float:
        out.float_value = float(v)
    else:
        out.int_value = int(v)
    return out


def expr_to_proto(e: Expr) -> pb.ExprNode:
    n = pb.ExprNode()
    if isinstance(e, Col):
        n.column = e.name
    elif isinstance(e, Lit):
        n.literal.CopyFrom(_lit_to_proto(e))
    elif isinstance(e, Alias):
        n.alias.child.CopyFrom(expr_to_proto(e.child))
        n.alias.name = e.name
    elif isinstance(e, BinOp):
        n.binary.op = e.op
        n.binary.left.CopyFrom(expr_to_proto(e.left))
        n.binary.right.CopyFrom(expr_to_proto(e.right))
    elif isinstance(e, Not):
        getattr(n, "not").CopyFrom(expr_to_proto(e.child))
    elif isinstance(e, IsNull):
        n.is_null.CopyFrom(expr_to_proto(e.child))
    elif isinstance(e, IsNotNull):
        n.is_not_null.CopyFrom(expr_to_proto(e.child))
    elif isinstance(e, Cast):
        n.cast.child.CopyFrom(expr_to_proto(e.child))
        n.cast.to.CopyFrom(dtype_to_proto(e.to))
    elif isinstance(e, Case):
        for c, v in e.branches:
            b = n.case.branches.add()
            b.condition.CopyFrom(expr_to_proto(c))
            b.value.CopyFrom(expr_to_proto(v))
        if e.else_ is not None:
            n.case.has_else = True
            n.case.else_expr.CopyFrom(expr_to_proto(e.else_))
    elif isinstance(e, InList):
        n.in_list.child.CopyFrom(expr_to_proto(e.child))
        for v in e.values:
            n.in_list.values.add().CopyFrom(expr_to_proto(v))
        n.in_list.negated = e.negated
    elif isinstance(e, Like):
        n.like.child.CopyFrom(expr_to_proto(e.child))
        n.like.pattern = e.pattern
        n.like.negated = e.negated
    elif isinstance(e, ScalarFunc):
        n.scalar_func.name = e.name
        for a in e.args:
            n.scalar_func.args.add().CopyFrom(expr_to_proto(a))
    elif isinstance(e, GetIndexedField):
        n.get_indexed_field.child.CopyFrom(expr_to_proto(e.child))
        n.get_indexed_field.index = e.index
    elif isinstance(e, GetMapValue):
        n.get_map_value.child.CopyFrom(expr_to_proto(e.child))
        n.get_map_value.key.CopyFrom(_lit_to_proto(Lit(e.key)))
    elif isinstance(e, GetStructField):
        n.get_struct_field.child.CopyFrom(expr_to_proto(e.child))
        n.get_struct_field.name = e.name
    elif isinstance(e, NamedStruct):
        n.named_struct.names.extend(e.names)
        for a in e.exprs:
            n.named_struct.exprs.add().CopyFrom(expr_to_proto(a))
    elif isinstance(e, SparkUdfWrapper):
        n.spark_udf_wrapper.serialized = e.serialized
        n.spark_udf_wrapper.dtype.CopyFrom(dtype_to_proto(e.dtype))
        for a in e.args:
            n.spark_udf_wrapper.args.add().CopyFrom(expr_to_proto(a))
        n.spark_udf_wrapper.expr_string = e.expr_string
    else:
        raise NotImplementedError(f"to_proto for {type(e).__name__}")
    return n


def _partitioning_to_proto(p) -> pb.PartitioningProto:
    from ..parallel.shuffle import (
        HashPartitioning, RangePartitioning, RoundRobinPartitioning,
    )

    out = pb.PartitioningProto(num_partitions=p.num_partitions)
    if isinstance(p, HashPartitioning):
        out.kind = pb.PartitioningProto.HASH
        for e in p.exprs:
            out.exprs.add().CopyFrom(expr_to_proto(e))
    elif isinstance(p, RoundRobinPartitioning):
        out.kind = pb.PartitioningProto.ROUND_ROBIN
    elif isinstance(p, RangePartitioning):
        if p.boundaries is None:
            # boundaries come from the scheduler's driver-side sampling
            # pass (≙ Spark's RangePartitioner sample job); a map task
            # cannot compute global boundaries alone
            raise NotImplementedError(
                "range partitioning crosses the serde boundary only "
                "with precomputed boundaries (scheduler boundary pass)"
            )
        out.kind = pb.PartitioningProto.RANGE
        for f in p.fields:
            fp = out.sort_fields.add()
            fp.expr.CopyFrom(expr_to_proto(f.expr))
            fp.ascending = f.ascending
            fp.nulls_first = f.nulls_first
        out.num_boundary_words = len(p.boundaries)
        import numpy as _np

        for w in p.boundaries:
            out.boundary_words.extend(int(v) for v in _np.asarray(w, _np.uint64))
    else:
        out.kind = pb.PartitioningProto.SINGLE
    return out


def plan_to_proto(node) -> pb.PhysicalPlanNode:
    if TASK_PARTITION.get() is None or type(node).__name__ in _CHILDREN_AT_OWN_PARTITION:
        return _node_to_proto(node)
    with _for_partition(None):
        return _node_to_proto(node)


def _node_to_proto(node) -> pb.PhysicalPlanNode:
    from ..ops import (
        AggExec, CoalesceBatchesExec, DebugExec, EmptyPartitionsExec, ExpandExec,
        FilterExec, GenerateExec, LimitExec, MemoryScanExec, OrcScanExec,
        ParquetScanExec, ProjectExec, RenameColumnsExec, SortExec, UnionExec,
        WindowExec,
    )
    from ..ops.joins import (
        BroadcastJoinBuildHashMapExec,
        BroadcastJoinExec,
        HashJoinExec,
        SortMergeJoinExec,
    )
    from ..ops.parquet_scan import FileSplit, entry_path
    from ..parallel.broadcast import IpcWriterExec
    from ..parallel.shuffle import IpcReaderExec, ShuffleWriterExec
    from ..runtime.context import RESOURCES

    out = pb.PhysicalPlanNode()
    if isinstance(node, MemoryScanExec):
        # stage partitions under a resources-map id so the decoded plan
        # finds them (≙ FFIReader export).  The id must be unique PER
        # SERIALIZATION: resources pop on read, and one plan node is
        # serialized once per task (N tasks = N gets).  A serialized
        # plan that is never executed strands its entry until process
        # exit — callers (scheduler) serialize exactly what they run.
        # the s<source_id>e<epoch> segment carries the table's data
        # identity (querycache source versioning) across the serde
        # boundary: every task rebuild of this scan re-adopts the
        # ORIGINAL source id + epoch (serde/from_proto.py parses it
        # back), so all tasks of a stage share one plan fingerprint
        # and the stats store folds their actuals into one entry
        rid = (f"memscan_s{node.source_id}e{node.epoch}"
               f"_{id(node)}_{next(_memscan_rids)}")
        RESOURCES.put(rid, node._partitions)
        staged = STAGED_RIDS.get()
        if staged is not None:
            staged.append(rid)
        out.memory_scan.resource_id = rid
        out.memory_scan.schema.CopyFrom(schema_to_proto(node.schema))
        out.memory_scan.num_partitions = node.num_partitions()
    elif isinstance(node, (ParquetScanExec, OrcScanExec)):
        sub = out.parquet_scan if isinstance(node, ParquetScanExec) else out.orc_scan
        # what the files hold: a partitioned table's path columns travel apart
        sub.schema.CopyFrom(schema_to_proto(node._schema))
        groups, own = node.file_groups, TASK_PARTITION.get()
        if own is not None:
            groups = [g if p == own else [] for p, g in enumerate(groups)]
        for g in groups:
            sub.file_groups.append(";".join(entry_path(e) for e in g))
        if any(isinstance(e, FileSplit) for g in node.file_groups for e in g):
            # OrcScanExec admits no split, so this is a Parquet scan's
            for g in groups:
                ranges = sub.file_ranges.add()
                for e in g:
                    ranged = isinstance(e, FileSplit)
                    ranges.start.append(e.start if ranged else 0)
                    ranges.length.append(e.length if ranged else -1)
        if isinstance(node, ParquetScanExec) and node.partition_schema.fields:
            sub.partition_schema.CopyFrom(schema_to_proto(node.partition_schema))
            for g in groups:
                files = sub.partition_values.add().files
                for e in g:
                    files.add().values.extend(
                        _partition_value_to_proto(v, f.dtype)
                        for v, f in zip(e.values, node.partition_schema.fields, strict=True))
        if node.predicate is not None:
            sub.predicate.add().CopyFrom(expr_to_proto(node.predicate))
        sub.batch_rows = node.stated_batch_rows
    elif isinstance(node, ProjectExec):
        out.project.input.CopyFrom(plan_to_proto(node.children[0]))
        for e in node.exprs:
            out.project.exprs.add().CopyFrom(expr_to_proto(e))
        out.project.names.extend(node.names)
    elif isinstance(node, FilterExec):
        out.filter.input.CopyFrom(plan_to_proto(node.children[0]))
        out.filter.predicate.CopyFrom(expr_to_proto(node.predicate))
        if node.project is not None:
            proj_exprs, proj_names = node.project
            for e in proj_exprs:
                out.filter.project_exprs.add().CopyFrom(expr_to_proto(e))
            out.filter.project_names.extend(proj_names)
    elif isinstance(node, AggExec):
        out.agg.input.CopyFrom(plan_to_proto(node.children[0]))
        out.agg.mode = node.mode.value
        for g in node.groupings:
            ge = out.agg.groupings.add()
            ge.expr.CopyFrom(expr_to_proto(g.expr))
            ge.name = g.name
        for a in node.aggs:
            ap = out.agg.aggs.add()
            ap.fn = a.fn
            ap.name = a.name
            if a.expr is not None:
                ap.has_expr = True
                ap.expr.CopyFrom(expr_to_proto(a.expr))
        out.agg.supports_partial_skipping = node.supports_partial_skipping
    elif isinstance(node, SortExec):
        out.sort.input.CopyFrom(plan_to_proto(node.children[0]))
        for f in node.fields:
            fp = out.sort.fields.add()
            fp.expr.CopyFrom(expr_to_proto(f.expr))
            fp.ascending = f.ascending
            fp.nulls_first = f.nulls_first
        if node.fetch is not None:
            out.sort.has_fetch = True
            out.sort.fetch = node.fetch
    elif isinstance(node, LimitExec):
        out.limit.input.CopyFrom(plan_to_proto(node.children[0]))
        out.limit.limit = node.limit
    elif isinstance(node, UnionExec):
        for c in node.children:
            out.union.inputs.add().CopyFrom(plan_to_proto(c))
    elif isinstance(node, RenameColumnsExec):
        out.rename_columns.input.CopyFrom(plan_to_proto(node.children[0]))
        out.rename_columns.names.extend(node.schema.names)
    elif isinstance(node, EmptyPartitionsExec):
        out.empty_partitions.schema.CopyFrom(schema_to_proto(node.schema))
        out.empty_partitions.num_partitions = node.num_partitions()
    elif isinstance(node, DebugExec):
        out.debug.input.CopyFrom(plan_to_proto(node.children[0]))
        out.debug.tag = node.tag
        out.debug.verbose = node.verbose
    elif isinstance(node, CoalesceBatchesExec):
        out.coalesce_batches.input.CopyFrom(plan_to_proto(node.children[0]))
        out.coalesce_batches.target_rows = node.target_rows
    elif isinstance(node, ShuffleWriterExec):
        out.shuffle_writer.input.CopyFrom(plan_to_proto(node.children[0]))
        out.shuffle_writer.partitioning.CopyFrom(_partitioning_to_proto(node.partitioning))
        out.shuffle_writer.output_data_file = node.data_path
        out.shuffle_writer.output_index_file = node.index_path
    elif isinstance(node, IpcReaderExec):
        out.ipc_reader.schema.CopyFrom(schema_to_proto(node.schema))
        out.ipc_reader.ipc_provider_resource_id = node.resource_id
        out.ipc_reader.num_partitions = node.num_partitions()
    elif isinstance(node, IpcWriterExec):
        out.ipc_writer.input.CopyFrom(plan_to_proto(node.children[0]))
        out.ipc_writer.ipc_consumer_resource_id = node.resource_id
    elif isinstance(node, (BroadcastJoinExec, HashJoinExec)):
        dst = out.broadcast_join if isinstance(node, BroadcastJoinExec) else out.hash_join
        # a broadcast join collects every partition of its build side
        with _for_partition(None) if isinstance(node, BroadcastJoinExec) else contextlib.nullcontext():
            dst.build.CopyFrom(plan_to_proto(node.children[0]))
        dst.probe.CopyFrom(plan_to_proto(node.children[1]))
        for e in node.build_keys:
            dst.build_keys.add().CopyFrom(expr_to_proto(e))
        for e in node.probe_keys:
            dst.probe_keys.add().CopyFrom(expr_to_proto(e))
        dst.join_type = pb.JoinTypeProto.Value(node.join_type.name)
        dst.build_is_left = node.build_is_left
        if isinstance(node, BroadcastJoinExec):
            dst.build_data_schema.CopyFrom(schema_to_proto(node.build_data_schema))
            if node.cached_build_id:
                dst.cached_build_id = node.cached_build_id
    elif isinstance(node, BroadcastJoinBuildHashMapExec):
        out.broadcast_join_build_hash_map.input.CopyFrom(plan_to_proto(node.children[0]))
        for e in node.keys:
            out.broadcast_join_build_hash_map.keys.add().CopyFrom(expr_to_proto(e))
    elif type(node).__name__ == "ObjectAggExec":
        out.object_agg.input.CopyFrom(plan_to_proto(node.children[0]))
        out.object_agg.mode = node.mode.value
        for g in node.groupings:
            ne = out.object_agg.groupings.add()
            ne.expr.CopyFrom(expr_to_proto(g.expr))
            ne.name = g.name
        out.object_agg.udafs_payload = pickle.dumps(node.udafs)
    elif type(node).__name__ == "BloomFilterAggExec":
        out.bloom_filter_agg.input.CopyFrom(plan_to_proto(node.children[0]))
        if node.expr is not None:
            out.bloom_filter_agg.has_expr = True
            out.bloom_filter_agg.expr.CopyFrom(expr_to_proto(node.expr))
        out.bloom_filter_agg.name = node.agg_name
        out.bloom_filter_agg.mode = node.mode.value
        out.bloom_filter_agg.expected_items = node.expected_items
        out.bloom_filter_agg.num_bits = node.num_bits
    elif isinstance(node, SortMergeJoinExec):
        out.sort_merge_join.left.CopyFrom(plan_to_proto(node.children[0]))
        out.sort_merge_join.right.CopyFrom(plan_to_proto(node.children[1]))
        for e in node.left_keys:
            out.sort_merge_join.left_keys.add().CopyFrom(expr_to_proto(e))
        for e in node.right_keys:
            out.sort_merge_join.right_keys.add().CopyFrom(expr_to_proto(e))
        out.sort_merge_join.join_type = pb.JoinTypeProto.Value(node.join_type.name)
        out.sort_merge_join.nulls_last = not node.nulls_first
    elif isinstance(node, WindowExec):
        out.window.input.CopyFrom(plan_to_proto(node.children[0]))
        for f in node.functions:
            fp = out.window.functions.add()
            fp.kind = f.kind
            fp.name = f.name
            if f.expr is not None:
                fp.has_expr = True
                fp.expr.CopyFrom(expr_to_proto(f.expr))
            fp.whole_partition = f.whole_partition
            fp.offset = f.offset
            fp.ignore_nulls = f.ignore_nulls
            if f.rows_frame is not None:
                fp.has_rows_frame = True
                p_, q_ = f.rows_frame
                fp.frame_preceding = -1 if p_ is None else p_
                fp.frame_following = -1 if q_ is None else q_
            if f.range_frame is not None:
                fp.has_range_frame = True
                x_, y_ = f.range_frame
                fp.range_preceding = -1 if x_ is None else x_
                fp.range_following = -1 if y_ is None else y_
        for e in node.partition_by:
            out.window.partition_by.add().CopyFrom(expr_to_proto(e))
        for f in node.order_by:
            fp = out.window.order_by.add()
            fp.expr.CopyFrom(expr_to_proto(f.expr))
            fp.ascending = f.ascending
            fp.nulls_first = f.nulls_first
    elif isinstance(node, ExpandExec):
        out.expand.input.CopyFrom(plan_to_proto(node.children[0]))
        for proj in node._projects:
            ep = out.expand.projections.add()
            for e in proj.exprs:
                ep.exprs.add().CopyFrom(expr_to_proto(e))
        out.expand.names.extend(node.schema.names)
    elif isinstance(node, GenerateExec):
        from ..ops.generate import NativeGenerator

        out.generate.input.CopyFrom(plan_to_proto(node.children[0]))
        if isinstance(node.generator, NativeGenerator):
            out.generate.native_kind = node.generator.kind
            out.generate.native_expr.CopyFrom(expr_to_proto(node.generator.expr))
        else:
            out.generate.generator_payload = pickle.dumps(node.generator)
        for e in node.input_exprs:
            out.generate.input_exprs.add().CopyFrom(expr_to_proto(e))
        for f in node.gen_fields:
            out.generate.gen_fields.add().CopyFrom(
                pb.FieldProto(name=f.name, dtype=dtype_to_proto(f.dtype), nullable=f.nullable)
            )
        out.generate.outer = node.outer
        out.generate.keep_input = node.keep_input
    else:
        raise NotImplementedError(f"to_proto for {type(node).__name__}")
    return out


def task_definition(plan, task_id: str, stage_id: int, partition: int) -> bytes:
    with _for_partition(partition):
        td = pb.TaskDefinition(
            task_id=task_id, stage_id=stage_id, partition=partition,
            plan=plan_to_proto(plan),
        )
    return td.SerializeToString()
