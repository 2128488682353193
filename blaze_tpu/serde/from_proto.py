"""protobuf -> ExecNode/Expr trees + task runner.

≙ reference blaze-serde/src/from_proto.rs:125-1283 (recursive
ExecutionPlan builder) plus the task entry half of blaze/src/exec.rs
(decode TaskDefinition -> build plan -> run).
"""

from __future__ import annotations

import logging
import pickle
import re
from typing import List, Optional

from ..exprs.ir import (
    Alias, BinOp, Case, Cast, Col, Expr, GetIndexedField, GetMapValue,
    GetStructField, InList, IsNotNull, IsNull, Like, Lit, NamedStruct, Not,
    ScalarFunc, SparkUdfWrapper,
)
from ..exprs.compile import RawUnscaled
from ..schema import DataType, Field, Schema, TypeKind
from . import plan_pb2 as pb

_log = logging.getLogger("blaze_tpu.task")


def dtype_from_proto(t: pb.DataTypeProto) -> DataType:
    kind = TypeKind(t.kind)
    if kind == TypeKind.DECIMAL:
        return DataType.decimal(t.precision, t.scale)
    if kind in (TypeKind.STRING, TypeKind.BINARY):
        return DataType(kind, string_width=t.string_width or 64)
    if kind == TypeKind.ARRAY:
        return DataType.array(dtype_from_proto(t.elem), t.max_elems)
    if kind == TypeKind.MAP:
        return DataType.map(dtype_from_proto(t.key), dtype_from_proto(t.value), t.max_elems)
    if kind == TypeKind.STRUCT:
        return DataType.struct(
            [Field(f.name, dtype_from_proto(f.dtype), f.nullable) for f in t.struct_fields]
        )
    return DataType(kind)


def schema_from_proto(s: pb.SchemaProto) -> Schema:
    return Schema(
        [Field(f.name, dtype_from_proto(f.dtype), f.nullable) for f in s.fields]
    )


def _lit_from_proto(l: pb.LiteralValue) -> Lit:
    t = dtype_from_proto(l.dtype)
    if l.is_null:
        return Lit(None, t)
    kind = l.WhichOneof("value")
    if kind == "bool_value":
        return Lit(l.bool_value, t)
    if kind == "float_value":
        return Lit(l.float_value, t)
    if kind == "bytes_value":
        v = l.bytes_value
        return Lit(v.decode("utf-8") if t.kind == TypeKind.STRING else v, t)
    # int_value: decimals arrive unscaled; Lit stores logical values, so
    # mark the int as already-unscaled
    if t.is_decimal:
        return Lit(RawUnscaled(l.int_value), t)
    return Lit(l.int_value, t)


def _partition_value_from_proto(l: pb.LiteralValue):
    """to_proto._partition_value_to_proto's value back."""
    return None if l.is_null else getattr(l, l.WhichOneof("value"))


def expr_from_proto(n: pb.ExprNode) -> Expr:
    kind = n.WhichOneof("expr")
    if kind == "column":
        return Col(n.column)
    if kind == "literal":
        return _lit_from_proto(n.literal)
    if kind == "alias":
        return Alias(expr_from_proto(n.alias.child), n.alias.name)
    if kind == "binary":
        return BinOp(n.binary.op, expr_from_proto(n.binary.left), expr_from_proto(n.binary.right))
    if kind == "not":
        return Not(expr_from_proto(getattr(n, "not")))
    if kind == "is_null":
        return IsNull(expr_from_proto(n.is_null))
    if kind == "is_not_null":
        return IsNotNull(expr_from_proto(n.is_not_null))
    if kind == "cast":
        return Cast(expr_from_proto(n.cast.child), dtype_from_proto(n.cast.to))
    if kind == "case":
        branches = [
            (expr_from_proto(b.condition), expr_from_proto(b.value)) for b in n.case.branches
        ]
        else_ = expr_from_proto(n.case.else_expr) if n.case.has_else else None
        return Case(branches, else_)
    if kind == "in_list":
        return InList(
            expr_from_proto(n.in_list.child),
            [expr_from_proto(v) for v in n.in_list.values],
            n.in_list.negated,
        )
    if kind == "like":
        return Like(expr_from_proto(n.like.child), n.like.pattern, n.like.negated)
    if kind == "scalar_func":
        return ScalarFunc(n.scalar_func.name, [expr_from_proto(a) for a in n.scalar_func.args])
    if kind == "get_indexed_field":
        return GetIndexedField(expr_from_proto(n.get_indexed_field.child), n.get_indexed_field.index)
    if kind == "get_map_value":
        key = _lit_from_proto(n.get_map_value.key).value
        return GetMapValue(expr_from_proto(n.get_map_value.child), key)
    if kind == "get_struct_field":
        return GetStructField(expr_from_proto(n.get_struct_field.child), n.get_struct_field.name)
    if kind == "named_struct":
        return NamedStruct(
            list(n.named_struct.names), [expr_from_proto(e) for e in n.named_struct.exprs]
        )
    if kind == "spark_udf_wrapper":
        w = n.spark_udf_wrapper
        return SparkUdfWrapper(
            bytes(w.serialized),
            [expr_from_proto(a) for a in w.args],
            dtype_from_proto(w.dtype),
            w.expr_string,
        )
    raise NotImplementedError(f"from_proto expr {kind}")


def _partitioning_from_proto(p: pb.PartitioningProto):
    from ..parallel.shuffle import (
        HashPartitioning, RangePartitioning, RoundRobinPartitioning,
        SinglePartitioning,
    )

    if p.kind == pb.PartitioningProto.HASH:
        return HashPartitioning([expr_from_proto(e) for e in p.exprs], p.num_partitions)
    if p.kind == pb.PartitioningProto.ROUND_ROBIN:
        return RoundRobinPartitioning(p.num_partitions)
    if p.kind == pb.PartitioningProto.RANGE:
        import numpy as np

        from ..ops import SortField

        fields = [
            SortField(expr_from_proto(f.expr), f.ascending, f.nulls_first)
            for f in p.sort_fields
        ]
        nw = int(p.num_boundary_words)
        flat = np.array(list(p.boundary_words), np.uint64)
        per = len(flat) // nw if nw else 0
        boundaries = tuple(flat[i * per:(i + 1) * per] for i in range(nw))
        return RangePartitioning(fields, p.num_partitions, boundaries=boundaries)
    return SinglePartitioning(p.num_partitions)


def plan_from_proto(n: pb.PhysicalPlanNode):
    from ..ops import (
        AggExec, AggFunction, AggMode, CoalesceBatchesExec, DebugExec,
        EmptyPartitionsExec, ExpandExec, FilterExec, GenerateExec, GroupingExpr,
        LimitExec, MemoryScanExec, ProjectExec, RenameColumnsExec, SortExec,
        SortField, UnionExec, WindowExec, WindowFunction,
    )
    from ..ops.joins import BroadcastJoinExec, HashJoinExec, JoinType, SortMergeJoinExec
    from ..parallel.broadcast import IpcWriterExec
    from ..parallel.shuffle import IpcReaderExec, ShuffleWriterExec
    from ..runtime.context import RESOURCES

    kind = n.WhichOneof("node")
    if kind == "memory_scan":
        rid = n.memory_scan.resource_id
        parts = RESOURCES.get(rid)
        scan = MemoryScanExec(parts, schema_from_proto(n.memory_scan.schema))
        # re-adopt the ORIGINAL table's source identity from the rid
        # (serde/to_proto.py encodes s<source_id>e<epoch>): a rebuilt
        # scan is the SAME data source, not a fresh one — without this
        # every task of a stage would mint its own source id, split
        # the stage's plan fingerprint per task, and scatter the stats
        # store's actuals across per-task entries
        m = re.match(r"memscan_s(\d+)e(\d+)_", rid)
        if m:
            scan.source_id = int(m.group(1))
            scan.epoch = int(m.group(2))
        return scan
    if kind in ("parquet_scan", "orc_scan"):
        s = n.parquet_scan if kind == "parquet_scan" else n.orc_scan
        pred = None
        for e in s.predicate:
            sub = expr_from_proto(e)
            pred = sub if pred is None else (pred & sub)
        groups = [g.split(";") if g else [] for g in s.file_groups]
        if kind == "parquet_scan":
            from ..ops import FileSplit, ParquetScanExec

            if s.file_ranges:  # none: a scan of whole files, or bytes from before the ranges
                groups = [[path if length < 0 else FileSplit(path, start, length)
                           for path, start, length in zip(g, r.start, r.length, strict=True)]
                          for g, r in zip(groups, s.file_ranges, strict=True)]
            if s.partition_values:  # none: the table is not partitioned
                # a file with values is a FileSplit, so file_ranges held its range
                groups = [[e._replace(values=tuple(map(_partition_value_from_proto, f.values)))
                           for e, f in zip(g, p.files, strict=True)]
                          for g, p in zip(groups, s.partition_values, strict=True)]
            return ParquetScanExec(groups, schema_from_proto(s.schema), pred, s.batch_rows,
                                   schema_from_proto(s.partition_schema))
        from ..ops.orc_scan import OrcScanExec

        return OrcScanExec(groups, schema_from_proto(s.schema), pred, s.batch_rows)
    if kind == "project":
        p = n.project
        return ProjectExec(plan_from_proto(p.input), [expr_from_proto(e) for e in p.exprs], list(p.names))
    if kind == "filter":
        project = None
        if n.filter.project_exprs:
            project = (
                [expr_from_proto(e) for e in n.filter.project_exprs],
                list(n.filter.project_names),
            )
        return FilterExec(
            plan_from_proto(n.filter.input), expr_from_proto(n.filter.predicate), project
        )
    if kind == "agg":
        a = n.agg
        return AggExec(
            plan_from_proto(a.input),
            AggMode(a.mode),
            [GroupingExpr(expr_from_proto(g.expr), g.name) for g in a.groupings],
            [
                AggFunction(f.fn, expr_from_proto(f.expr) if f.has_expr else None, f.name)
                for f in a.aggs
            ],
            supports_partial_skipping=a.supports_partial_skipping,
        )
    if kind == "sort":
        s = n.sort
        return SortExec(
            plan_from_proto(s.input),
            [SortField(expr_from_proto(f.expr), f.ascending, f.nulls_first) for f in s.fields],
            fetch=s.fetch if s.has_fetch else None,
        )
    if kind == "limit":
        return LimitExec(plan_from_proto(n.limit.input), n.limit.limit)
    if kind == "union":
        return UnionExec([plan_from_proto(c) for c in n.union.inputs])
    if kind == "rename_columns":
        return RenameColumnsExec(plan_from_proto(n.rename_columns.input), list(n.rename_columns.names))
    if kind == "empty_partitions":
        return EmptyPartitionsExec(
            schema_from_proto(n.empty_partitions.schema), n.empty_partitions.num_partitions
        )
    if kind == "debug":
        return DebugExec(plan_from_proto(n.debug.input), n.debug.tag, n.debug.verbose)
    if kind == "coalesce_batches":
        return CoalesceBatchesExec(
            plan_from_proto(n.coalesce_batches.input), n.coalesce_batches.target_rows
        )
    if kind == "shuffle_writer":
        w = n.shuffle_writer
        return ShuffleWriterExec(
            plan_from_proto(w.input), _partitioning_from_proto(w.partitioning),
            w.output_data_file, w.output_index_file,
        )
    if kind == "ipc_reader":
        r = n.ipc_reader
        return IpcReaderExec(schema_from_proto(r.schema), r.ipc_provider_resource_id, r.num_partitions)
    if kind == "ipc_writer":
        return IpcWriterExec(plan_from_proto(n.ipc_writer.input), n.ipc_writer.ipc_consumer_resource_id)
    if kind in ("broadcast_join", "hash_join"):
        j = n.broadcast_join if kind == "broadcast_join" else n.hash_join
        cls = BroadcastJoinExec if kind == "broadcast_join" else HashJoinExec
        extra = {}
        if kind == "broadcast_join":
            if j.build_data_schema.fields:
                extra["build_data_schema"] = schema_from_proto(j.build_data_schema)
            if j.cached_build_id:
                extra["cached_build_id"] = j.cached_build_id
        return cls(
            plan_from_proto(j.build), plan_from_proto(j.probe),
            [expr_from_proto(e) for e in j.build_keys],
            [expr_from_proto(e) for e in j.probe_keys],
            JoinType[pb.JoinTypeProto.Name(j.join_type)],
            j.build_is_left,
            **extra,
        )
    if kind == "broadcast_join_build_hash_map":
        from ..ops.joins import BroadcastJoinBuildHashMapExec

        b = n.broadcast_join_build_hash_map
        return BroadcastJoinBuildHashMapExec(
            plan_from_proto(b.input), [expr_from_proto(e) for e in b.keys]
        )
    if kind == "sort_merge_join":
        j = n.sort_merge_join
        return SortMergeJoinExec(
            plan_from_proto(j.left), plan_from_proto(j.right),
            [expr_from_proto(e) for e in j.left_keys],
            [expr_from_proto(e) for e in j.right_keys],
            JoinType[pb.JoinTypeProto.Name(j.join_type)],
            nulls_first=not j.nulls_last,
        )
    if kind == "window":
        w = n.window
        return WindowExec(
            plan_from_proto(w.input),
            [
                WindowFunction(
                    f.kind, f.name,
                    expr_from_proto(f.expr) if f.has_expr else None,
                    f.whole_partition,
                    # lead/lag: 0 is a legal offset (current row);
                    # other kinds never read it (default 1)
                    offset=f.offset if f.kind in ("lead", "lag") else (f.offset or 1),
                    rows_frame=(
                        (None if f.frame_preceding < 0 else f.frame_preceding,
                         None if f.frame_following < 0 else f.frame_following)
                        if f.has_rows_frame else None
                    ),
                    ignore_nulls=f.ignore_nulls,
                    range_frame=(
                        (None if f.range_preceding < 0 else f.range_preceding,
                         None if f.range_following < 0 else f.range_following)
                        if f.has_range_frame else None
                    ),
                )
                for f in w.functions
            ],
            [expr_from_proto(e) for e in w.partition_by],
            [SortField(expr_from_proto(f.expr), f.ascending, f.nulls_first) for f in w.order_by],
        )
    if kind == "expand":
        e = n.expand
        return ExpandExec(
            plan_from_proto(e.input),
            [[expr_from_proto(x) for x in p.exprs] for p in e.projections],
            list(e.names),
        )
    if kind == "generate":
        g = n.generate
        if g.native_kind:
            from ..ops.generate import NativeGenerator

            gen = NativeGenerator(g.native_kind, expr_from_proto(g.native_expr))
        else:
            from .. import conf

            if not bool(conf.ALLOW_PICKLED_UDFS.get()):
                raise PermissionError(
                    "pickled generator payload rejected: "
                    "spark.blaze.udf.allowPickled is false"
                )
            gen = pickle.loads(g.generator_payload)
        return GenerateExec(
            plan_from_proto(g.input),
            gen,
            [expr_from_proto(e) for e in g.input_exprs],
            [Field(f.name, dtype_from_proto(f.dtype), f.nullable) for f in g.gen_fields],
            g.outer,
            g.keep_input,
        )
    if kind == "object_agg":
        from .. import conf
        from ..ops.agg import GroupingExpr
        from ..ops.object_agg import ObjectAggExec

        o = n.object_agg
        if not bool(conf.ALLOW_PICKLED_UDFS.get()):
            raise PermissionError(
                "pickled UDAF payload rejected: set spark.blaze.udf.allowPickled"
            )
        return ObjectAggExec(
            plan_from_proto(o.input),
            AggMode(o.mode),
            [GroupingExpr(expr_from_proto(g.expr), g.name) for g in o.groupings],
            pickle.loads(o.udafs_payload),
        )
    if kind == "bloom_filter_agg":
        from ..ops.bloom_agg import BloomFilterAggExec

        b = n.bloom_filter_agg
        return BloomFilterAggExec(
            plan_from_proto(b.input),
            expr_from_proto(b.expr) if b.has_expr else None,
            b.name, AggMode(b.mode), b.expected_items, b.num_bits or None,
        )
    raise NotImplementedError(f"from_proto node {kind}")


def run_task(task_def_bytes: bytes, task_attempt_id: int = 0,
             resources=None, cancel_event=None, on_beat=None):
    """Decode a TaskDefinition and drive its plan for its partition —
    the python mirror of the gateway's callNative entry
    (≙ blaze/src/exec.rs:46-142).  ``task_attempt_id`` threads the
    scheduler's attempt counter into the TaskContext (and the fault
    injector), so retried attempts are distinguishable at every site.

    Speculation/wedge plumbing (runtime/speculation.py): ``resources``
    swaps in a per-attempt ScopedResources view so concurrent attempts
    of one task never steal each other's one-shot registrations,
    ``cancel_event`` lets the driver cancel a losing attempt
    cooperatively, and ``on_beat`` is a liveness callback fired at the
    heartbeat cadence from inside the plan drive — the wedge detector's
    clock, armed even when tracing and the monitor are off."""
    from ..ops.fusion import optimize_plan
    from ..runtime import faults, trace
    from ..runtime.context import TaskContext

    with trace.span("task_decode"):
        td = pb.TaskDefinition()
        td.ParseFromString(task_def_bytes)
        faults.hit("task.compute", attempt=task_attempt_id, detail=td.task_id)
        plan = optimize_plan(plan_from_proto(td.plan))
    if _log.isEnabledFor(logging.DEBUG):
        # ≙ the reference's native plan display at task start
        # (blaze/src/exec.rs:101-106)
        _log.debug("task %s partition %d plan:\n%s",
                   td.task_id, td.partition, plan.tree_string())
    ctx = TaskContext(
        td.partition, max(plan.num_partitions(), td.partition + 1),
        stage_id=td.stage_id, task_attempt_id=task_attempt_id,
        resources=resources, cancel_event=cancel_event,
    )
    stream = plan.execute(td.partition, ctx)
    from ..runtime import monitor

    if not trace.enabled() and not monitor.enabled() and on_beat is None:
        return stream
    return _instrumented_task_stream(stream, plan, td, task_attempt_id,
                                     on_beat=on_beat)


def _instrumented_task_stream(stream, plan, td, attempt: int, on_beat=None):
    """Observability-armed task drive.  With tracing armed, a kernel
    capture attributes every XLA program issued while this attempt runs
    to its operator label, and on completion the attempt emits its
    kernel split (``task_kernels``) plus the plan-annotated metrics
    tree (``task_plan`` — the executed plan instance's per-node
    MetricsSet, the per-attempt analogue of the MetricNode walk the JVM
    gateway does).  With tracing OR the live monitor armed, the stream
    additionally heartbeats: at most once per
    ``spark.blaze.monitor.heartbeatMs`` a ``task_heartbeat`` event
    (event log) / registry beat (/queries) carries rows-so-far plus an
    incremental snapshot of the plan root's MetricsSet, so a slow task
    is visibly alive mid-flight.  Monitor-only arming deliberately
    skips the kernel capture — that would flip the block-until-ready
    timing path and serialize the device just to watch progress."""
    import contextlib as _contextlib
    import time as _time

    from ..runtime import monitor, trace

    traced = trace.enabled()
    mon = monitor.enabled()
    t0 = _time.perf_counter_ns()
    rows = 0
    batches = 0

    def _tree_metrics(node, out, max_rows):
        for k, v in node.metrics.snapshot().items():
            if isinstance(v, int):
                out[k] = out.get(k, 0) + v
                if k == "output_rows":
                    max_rows = max(max_rows, v)
        for c in node.children:
            max_rows = _tree_metrics(c, out, max_rows)
        return max_rows

    def beat() -> None:
        # incremental MetricsSet snapshot SUMMED over the plan tree
        # (per-operator rows/timers so far) — output_rows there counts
        # every operator boundary, so the chain-depth-independent live
        # row count is progress_rows: the widest single node's rows
        if on_beat is not None:
            on_beat()
        if not traced and not mon:
            return  # wedge-clock-only arming: no snapshot walk owed
        metrics: dict = {}
        progress_rows = _tree_metrics(plan, metrics, 0)
        now = _time.perf_counter_ns()
        # the PR 3 kernel-sink split for this attempt so far — where
        # the task's wall is going (device compute vs dispatch
        # overhead), live in /queries and the heartbeat event; only
        # tracing arms the capture, so monitor-only runs report 0/0
        # rather than paying the block-until-ready path
        device_ns = dispatch_ns = 0
        ksnap = None
        if traced and kc:
            ksnap = trace.snapshot_kernels(kc)
            split = trace.sum_kernels(ksnap)
            device_ns = split["device_time_ns"]
            dispatch_ns = split["dispatch_overhead_ns"]
        if traced:
            trace.emit(
                "task_heartbeat", task_id=td.task_id, stage_id=td.stage_id,
                partition=td.partition, attempt=attempt, rows=rows,
                batches=batches, elapsed_ns=now - t0,
                progress_rows=progress_rows, metrics=metrics,
                device_ns=device_ns, dispatch_ns=dispatch_ns,
            )
        if mon:
            monitor.task_beat(td.stage_id, td.partition, attempt,
                              rows=rows, batches=batches, metrics=metrics,
                              progress_rows=progress_rows,
                              task_id=td.task_id,
                              device_ns=device_ns, dispatch_ns=dispatch_ns,
                              # per-label sink snapshot: the live flame
                              # profile's source (/queries/<id>/profile)
                              kernels=ksnap)

    kc_scope = trace.kernel_capture() if traced else _contextlib.nullcontext({})
    # the beat fires from monitor.tick() — called per operator output
    # batch inside the plan drive (ops/base._count_output), so a map
    # task that yields nothing to the driver still heartbeats — and
    # from the driver-side loop below for result streams.  The beat
    # state is active ONLY while the plan drive runs (inside next()),
    # never across a yield: an abandoned half-consumed stream must not
    # leave a stale callback cross-attributing this task's beats into
    # the next query on the consumer's thread.
    beat_state = monitor.new_task_beat(beat)
    with kc_scope as kc:
        try:
            it = iter(stream)
            while True:
                prev = monitor.activate_beat(beat_state)
                try:
                    b = next(it)
                except StopIteration:
                    break
                finally:
                    monitor.deactivate_beat(prev)
                rows += b.num_rows
                batches += 1
                beat_state.tick()
                yield b
            if mon:
                # FINAL beat, interval-ungated: a task faster than the
                # heartbeat period would otherwise never land its rows
                # or kernel split in the registry at all (a failed
                # attempt's entry is discarded by the scheduler's
                # rollback hook right after this unwinds, so only the
                # completed drive beats here)
                beat()
        finally:
            if traced:
                trace.emit(
                    "task_kernels", task_id=td.task_id, stage_id=td.stage_id,
                    partition=td.partition, attempt=attempt,
                    wall_ns=_time.perf_counter_ns() - t0, kernels=kc,
                    **trace.sum_kernels(kc),
                )
                trace.emit(
                    "task_plan", task_id=td.task_id, stage_id=td.stage_id,
                    partition=td.partition, attempt=attempt,
                    plan=trace.plan_tree(plan),
                )
