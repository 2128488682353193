"""Per-operator Spark physical plan -> ExecNode conversion.

≙ reference ``BlazeConverters.scala:126-850`` (``convertSparkPlan`` +
one ``convertXxxExec`` per operator, each gated by its
``spark.blaze.enable.<op>`` flag) and the proto-building plan bases in
``spark-extension/.../blaze/plan/*.scala``.

Naming discipline: every intermediate column is ``#<exprId>`` (the
reference binds attributes by exprId the same way); the session layer
renames the root back to user-facing names.  Scans resolve through the
:class:`ConversionContext` catalog — the analogue of the JVM reading
``HadoopFsRelation`` file listings at plan time, which catalyst's
``toJSON`` cannot carry.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .. import conf
from ..exprs.ir import Alias, Col, Expr, and_
from ..ops import (
    AggExec, AggFunction, AggMode, ExecNode, ExpandExec, FilterExec,
    GenerateExec, GroupingExpr, LimitExec, MemoryScanExec, ParquetScanExec,
    ProjectExec, RenameColumnsExec, SortExec, SortField, UnionExec, WindowExec,
    WindowFunction,
)
from ..ops.generate import NativeGenerator, json_tuple_generator
from ..ops.joins import BroadcastJoinExec, HashJoinExec, JoinType, SortMergeJoinExec
from ..parallel import (
    BroadcastExchangeExec, HashPartitioning, NativeShuffleExchangeExec,
    RoundRobinPartitioning, SinglePartitioning,
)
from ..schema import DataType, Field, Schema
from .expr_converter import (
    UnsupportedSparkExpr, convert_expr, convert_expr_with_fallback,
)
from ..runtime import dispatch
from ..runtime.errors import reraise_control
from .plan_json import SparkNode, expr_id


class UnsupportedSparkExec(Exception):
    """Raised when a plan node cannot be converted; the strategy layer
    catches it and falls back for the subtree (≙ the reference's
    ``NeverConvert`` tagging + ``convertToNative`` wrapping)."""


class ConversionContext:
    """State threaded through conversion.

    - ``catalog``: table name -> ExecNode producing the table (built by
      the session from parquet/orc paths or staged memory batches)
    - ``default_parallelism``: partition count for exchanges whose
      JSON lacks one
    - ``host_fallback``: optional callback ``(SparkNode) -> ExecNode``
      executing an unconvertible subtree host-side (the ConvertToNative
      seam; tests stub it the way testenv stubs the JVM)
    """

    def __init__(
        self,
        catalog: Optional[Dict[str, ExecNode]] = None,
        default_parallelism: int = 4,
        host_fallback: Optional[Callable[[SparkNode], ExecNode]] = None,
    ):
        self.catalog = catalog or {}
        self.default_parallelism = default_parallelism
        self.host_fallback = host_fallback

    def convert(self, node: SparkNode) -> ExecNode:
        """Child-conversion hook.  The plain context recurses directly;
        the strategy layer overrides this to consult its tags and
        insert fallback boundaries (≙ convertSparkPlan's per-child
        dispatch in BlazeConverters.scala:149)."""
        return convert_exec(node, self)


# ----------------------------------------------------------------- helpers

def _named_expr(n: SparkNode) -> Tuple[Expr, str]:
    """NamedExpression -> (expr, #id name)."""
    if n.name == "AttributeReference":
        eid = expr_id(n.fields.get("exprId"))
        name = f"#{eid}" if eid is not None else n.fields.get("name", "?")
        return Col(name), name
    if n.name == "Alias":
        eid = expr_id(n.fields.get("exprId"))
        name = f"#{eid}" if eid is not None else n.fields.get("name", "?")
        return convert_expr_with_fallback(n.children[0]), name
    e = convert_expr_with_fallback(n)
    return e, f"_c{id(n) & 0xffff}"


def _attr_user_name(n: SparkNode) -> str:
    return str(n.fields.get("name", "?"))


_PASS_THROUGH = {
    "WholeStageCodegenExec", "InputAdapter", "AdaptiveSparkPlanExec",
    "ShuffleQueryStageExec", "BroadcastQueryStageExec", "ReusedExchangeExec",
    "ResultQueryStageExec", "ColumnarToRowExec",
}


# column-preserving execs a NAMING walk may also step through (a real
# Spark dump's root is often Sort-over-Exchange above the naming agg)
_NAME_TRANSPARENT = {"SortExec", "ShuffleExchangeExec", "CoalesceExec"}


def output_attrs(node: SparkNode) -> List[Tuple[str, str]]:
    """Best-effort [(#id, user name)] for a plan node's output — used
    for the root rename back to user-facing names."""
    while node.name in (_PASS_THROUGH | _NAME_TRANSPARENT) and node.children:
        node = node.child(0)
    key = {
        "ProjectExec": "projectList",
        "HashAggregateExec": "resultExpressions",
        "SortAggregateExec": "resultExpressions",
        "ObjectHashAggregateExec": "resultExpressions",
        "TakeOrderedAndProjectExec": "projectList",
        "FileSourceScanExec": "output",
    }.get(node.name)
    attrs = node.expr_list(key) if key else []
    out = []
    for a in attrs:
        eid = expr_id(a.fields.get("exprId"))
        out.append((f"#{eid}" if eid is not None else a.fields.get("name", "?"),
                    _attr_user_name(a)))
    return out


_JOIN_TYPES = {
    "Inner": JoinType.INNER,
    "LeftOuter": JoinType.LEFT,
    "RightOuter": JoinType.RIGHT,
    "FullOuter": JoinType.FULL,
    "LeftSemi": JoinType.LEFT_SEMI,
    "LeftAnti": JoinType.LEFT_ANTI,
    "Cross": JoinType.INNER,
}


def _join_type(node: SparkNode) -> JoinType:
    v = node.fields.get("joinType")
    s = v if isinstance(v, str) else node.string("joinType")
    if s in _JOIN_TYPES:
        return _JOIN_TYPES[s]
    if s.startswith("ExistenceJoin"):
        return JoinType.EXISTENCE
    raise UnsupportedSparkExec(f"join type {s!r}")


def _existence_name(node: SparkNode) -> Optional[str]:
    """``#id`` of the exists attribute an ``ExistenceJoin(exists)``
    appends — catalyst serializes the join type as a product object
    carrying the attribute (``plans/joinTypes.scala``); downstream
    expressions reference it by that exprId."""
    v = node.fields.get("joinType")
    if isinstance(v, dict) and v.get("exists") is not None:
        try:
            a = _parse_sub(v["exists"])
        except Exception as e:  # noqa: BLE001 — optional-field probe
            reraise_control(e)
            return None
        eid = expr_id(a.fields.get("exprId"))
        if eid is not None:
            return f"#{eid}"
    return None


def _wrap_existence(out: ExecNode, node: SparkNode, jt: JoinType) -> ExecNode:
    """Rename the appended existence column (engine default
    ``exists#0``) to the catalyst exprId name so downstream filters
    resolve it."""
    if jt != JoinType.EXISTENCE:
        return out
    name = _existence_name(node)
    if name is None:
        # without the exprId, downstream references to the exists flag
        # cannot resolve — fall back via the strategy seam rather than
        # emit a plan that fails at execution
        raise UnsupportedSparkExec("ExistenceJoin without exists attribute")
    names = [f.name for f in out.schema.fields]
    names[-1] = name
    return RenameColumnsExec(out, names)


def _sort_fields(orders: Sequence[SparkNode]) -> List[SortField]:
    out = []
    for o in orders:
        if o.name != "SortOrder":
            raise UnsupportedSparkExec(f"expected SortOrder, got {o.name}")
        asc = o.string("direction", "Ascending") == "Ascending"
        nulls_first = o.string("nullOrdering", "") == "NullsFirst" or (
            "nullOrdering" not in o.fields and asc  # Spark default: nulls first iff asc
        )
        out.append(SortField(convert_expr_with_fallback(o.children[0]), asc, nulls_first))
    return out


_AGG_FNS = {
    "Sum": "sum", "Average": "avg", "Min": "min", "Max": "max",
    "First": "first", "CollectList": "collect_list",
    "CollectSet": "collect_set",
    "StddevSamp": "stddev_samp", "VarianceSamp": "var_samp",
}


def _agg_function(agg_expr: SparkNode) -> AggFunction:
    """AggregateExpression -> engine AggFunction named #<resultId>
    (resultIds are stable across the partial/final split, which keeps
    the state-column names aligned between the two stages)."""
    # silently dropping either of these would return plausible wrong
    # numbers: FILTER (WHERE ...) restricts which rows aggregate, and
    # isDistinct survives into physical plans when Spark's distinct
    # rewrite leaves a single distinct group intact — gate so the
    # strategy layer falls back the subtree instead
    if agg_expr.fields.get("isDistinct") in (True, "true"):
        raise UnsupportedSparkExec("distinct aggregate expression")
    if agg_expr.fields.get("filter") not in (None, "null", []):
        raise UnsupportedSparkExec("AggregateExpression FILTER clause")
    fn_node = agg_expr.children[0]
    rid = expr_id(agg_expr.fields.get("resultId"))
    name = f"#{rid}" if rid is not None else f"agg_{fn_node.name.lower()}"
    cls = fn_node.name
    if cls == "Count":
        kids = fn_node.children
        if not kids or (len(kids) == 1 and kids[0].name == "Literal"):
            return AggFunction("count_star", None, name)
        return AggFunction("count", convert_expr_with_fallback(kids[0]), name)
    if cls == "First":
        ignore = fn_node.fields.get("ignoreNulls")
        if ignore is None and len(fn_node.children) > 1:
            lit = fn_node.children[1]
            ignore = str(lit.fields.get("value", "false")).lower() == "true"
        fn = "first_ignores_null" if ignore else "first"
        return AggFunction(fn, convert_expr_with_fallback(fn_node.children[0]), name)
    if cls in _AGG_FNS:
        return AggFunction(_AGG_FNS[cls], convert_expr_with_fallback(fn_node.children[0]), name)
    raise UnsupportedSparkExec(f"aggregate function {cls}")


# sentinel for Spark's Complete mode, which has no engine AggMode —
# _convert_agg lowers it to an in-partition PARTIAL->FINAL stack
_COMPLETE = object()


def _agg_mode(agg_exprs: Sequence[SparkNode]):
    modes = {a.string("mode", "Partial") for a in agg_exprs}
    if modes <= {"Partial"}:
        return AggMode.PARTIAL
    if modes <= {"PartialMerge"}:
        return AggMode.PARTIAL_MERGE
    if modes == {"Complete"}:
        # Complete = raw rows in, final values out, single stage.  The
        # converter lowers it as an in-partition PARTIAL->FINAL stack
        # (sound because Spark only plans Complete where the child
        # already satisfies the group-by distribution requirement).
        # The reference instead refuses (NativeAggBase.scala:126).
        return _COMPLETE
    if "Complete" in modes:
        # mixed Final+Complete (AQE distinct rewrites): the Complete
        # functions would be treated as state-merging over raw rows
        raise UnsupportedSparkExec(f"mixed aggregate modes {modes}")
    if modes <= {"Final"}:
        return AggMode.FINAL
    raise UnsupportedSparkExec(f"mixed aggregate modes {modes}")


# --------------------------------------------------------------- converters

def convert_exec(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    """Recursive conversion; raises UnsupportedSparkExec/-Expr upward
    so the strategy can tag the subtree NeverConvert."""
    name = node.name
    # pass-through wrappers (codegen/AQE adapters have no native
    # analogue); _PASS_THROUGH is the single authoritative list, shared
    # with output_attrs' root-rename walk
    if name == "CollectLimitExec":
        child = ctx.convert(node.child(0))
        limit = int(node.fields.get("limit", 0) or 0)
        single = NativeShuffleExchangeExec(child, SinglePartitioning())
        return LimitExec(single, limit) if limit > 0 else single
    if name in _PASS_THROUGH:
        return ctx.convert(node.child(0))

    op_flag = {
        "FileSourceScanExec": "scan", "ProjectExec": "project",
        "FilterExec": "filter", "SortExec": "sort",
        "HashAggregateExec": "aggr", "SortAggregateExec": "aggr",
        "ObjectHashAggregateExec": "aggr",
        "ShuffleExchangeExec": "shuffle", "BroadcastExchangeExec": "broadcast",
        "BroadcastHashJoinExec": "bhj", "ShuffledHashJoinExec": "shj",
        "SortMergeJoinExec": "smj", "WindowExec": "window",
        "GenerateExec": "generate", "ExpandExec": "expand",
        "UnionExec": "union", "GlobalLimitExec": "limit",
        "LocalLimitExec": "limit", "TakeOrderedAndProjectExec": "takeOrdered",
    }.get(name)
    if op_flag is not None and not conf.op_enabled(op_flag):
        raise UnsupportedSparkExec(f"{name} disabled by spark.blaze.enable.{op_flag}")

    fn = _CONVERTERS.get(name)
    if fn is None:
        raise UnsupportedSparkExec(f"no converter for {name}")
    return fn(node, ctx)


def _filter_columns(filters: List[SparkNode]) -> List[str]:
    """The columns a scan's partition filters name.  The inside of a
    ``DynamicPruningExpression`` is Spark's — the pruning key and the
    broadcast subquery that fills it at run time — and is not read: the
    rule that plants one (``PartitionPruning``) does so on a partition
    column only."""
    names, todo = [], list(filters)
    while todo:
        e = todo.pop()
        if e.name == "AttributeReference":
            names.append(_attr_user_name(e))
        elif e.name != "DynamicPruningExpression":
            todo.extend(e.children)
    return names


def _convert_scan(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    """FileSourceScanExec: resolve the relation through the catalog
    (≙ NativeParquetScanBase building FileGroups from the relation),
    project/rename to the scan's output attributes."""
    ident = node.fields.get("tableIdentifier")
    table = None
    if isinstance(ident, dict):
        table = ident.get("table")
    elif isinstance(ident, str) and ident:
        table = ident.split(".")[-1]
    if table is None or table not in ctx.catalog:
        raise UnsupportedSparkExec(f"scan relation {ident!r} not in catalog")
    scan = ctx.catalog[table]
    # partition filters are enforced at the scan in Spark (FilterExec
    # above the scan re-applies only the data filters), and where Spark
    # enforces them is the LISTING: FileSourceScanExec's
    # dynamicallySelectedPartitions keeps the directories that pass, so
    # a relation registered with a partition schema was handed the
    # selected files (as NativeParquetScanBase is) and the filters are
    # spent.  One that names a data column, or a relation whose files
    # were not listed under partition columns, cannot have been applied
    # that way — dropping it would return rows it rules out: fall back
    partition_columns = getattr(scan, "partition_schema", Schema([])).names
    filters = node.expr_list("partitionFilters")
    if filters and not partition_columns:
        raise UnsupportedSparkExec(
            f"FileSourceScanExec of {table!r} with {len(filters)} partitionFilters, and the "
            f"relation is registered with no partition schema")
    for column in _filter_columns(filters):
        if column not in partition_columns:
            raise UnsupportedSparkExec(
                f"FileSourceScanExec of {table!r}: partition filter on {column!r}, which is "
                f"no partition column of the registered relation {partition_columns}")
    attrs = node.expr_list("output")
    exprs, names = [], []
    for a in attrs:
        user = _attr_user_name(a)
        eid = expr_id(a.fields.get("exprId"))
        if user not in scan.schema.names:
            raise UnsupportedSparkExec(f"column {user!r} not in table {table!r}")
        exprs.append(Col(user))
        names.append(f"#{eid}" if eid is not None else user)
    if isinstance(scan, ParquetScanExec):
        scan = _pushed_down(scan, node, attrs)
    return ProjectExec(scan, exprs, names)


def _pushed_down(scan: ParquetScanExec, node: SparkNode,
                 attrs: List[SparkNode]) -> ParquetScanExec:
    """A copy of ``scan`` whose predicate is the scan node's
    ``dataFilters``, ANDed (≙ ``NativeParquetScanBase`` handing them to
    the native scan as its pruning predicate, as parquet-mr is handed
    them under ``spark.sql.parquet.filterPushdown``): the row groups
    whose statistics rule them out are never read.  A filter that does
    not lower is left out, never a reason to fall back: the FilterExec
    above keeps every one, as Spark's does."""
    try:
        filters = node.expr_list("dataFilters")
    except Exception as e:  # noqa: BLE001 — a field that does not parse prunes nothing
        reraise_control(e)
        filters = []
    by_id = {expr_id(a.fields.get("exprId")): _attr_user_name(a) for a in attrs}
    pushed = []
    for f in filters:
        try:
            pushed.append(convert_expr(_by_column_name(f, by_id)))
        except Exception as e:  # noqa: BLE001 — what does not lower prunes nothing
            reraise_control(e)
            dispatch.record("scan_conjuncts_dropped")
            continue
        dispatch.record("scan_conjuncts_pushed")
    return scan.with_predicate(and_(*pushed)) if pushed else scan


def _by_column_name(node: SparkNode, by_id: Dict[Optional[int], str]) -> SparkNode:
    """``node`` with each attribute named as the scan's column it is,
    not by its exprId.  A subquery is not run a second time to prune."""
    if "Subquery" in node.name or node.name == "DynamicPruningExpression":
        raise UnsupportedSparkExpr(f"{node.name} in a data filter")
    if node.name == "AttributeReference":
        eid = expr_id(node.fields.get("exprId"))
        name = node.fields.get("name") if eid is None else by_id.get(eid)
        if name not in by_id.values():
            raise UnsupportedSparkExpr(f"attribute {name!r} #{eid} is no column of the scan")
        return SparkNode(node.cls, {"name": name})
    return SparkNode(node.cls, node.fields, [_by_column_name(c, by_id) for c in node.children])


def _convert_project(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    exprs, names = [], []
    for p in node.expr_list("projectList"):
        e, n = _named_expr(p)
        exprs.append(e)
        names.append(n)
    return ProjectExec(child, exprs, names)


def _convert_filter(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    cond = node.expr("condition")
    if cond is None:
        raise UnsupportedSparkExec("FilterExec without condition")
    return FilterExec(child, convert_expr_with_fallback(cond))


def _convert_agg(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    agg_exprs = node.expr_list("aggregateExpressions")
    mode = _agg_mode(agg_exprs)
    groupings = []
    for g in node.expr_list("groupingExpressions"):
        e, n = _named_expr(g)
        groupings.append(GroupingExpr(e, n))
    if not agg_exprs and not groupings:
        # a DISTINCT stage has groupings; a global agg has aggregate
        # expressions; BOTH empty only happens when a degraded dump
        # nulled the field — converting would mint a zero-column agg
        # that silently produces nothing (fuzz-pinned)
        raise UnsupportedSparkExec(
            f"{node.name} with neither grouping nor aggregate "
            f"expressions (gutted dump field?)")
    aggs = [_agg_function(a) for a in agg_exprs]
    if mode is _COMPLETE:
        partial = AggExec(child, AggMode.PARTIAL, groupings, aggs)
        out: ExecNode = AggExec(
            partial, AggMode.FINAL,
            [GroupingExpr(Col(g.name), g.name) for g in groupings], aggs,
        )
        mode = AggMode.FINAL
    else:
        # DISTINCT plans carry NO aggregateExpressions on either stage,
        # so both classify as PARTIAL (no mode field to read).  That is
        # value-correct — grouping-only PARTIAL and FINAL both emit the
        # deduped keys — but partial-agg SKIPPING must stay off: the
        # post-shuffle stage skipping would stream batch-local rows and
        # leak cross-batch duplicates into the DISTINCT result.
        out = AggExec(
            child, mode, groupings, aggs,
            initial_input_buffer_offset=int(node.fields.get("initialInputBufferOffset", 0) or 0),
            supports_partial_skipping=(mode == AggMode.PARTIAL and bool(aggs)),
        )
    if mode in (AggMode.FINAL,):
        if ("resultExpressions" in node.fields
                and node.fields["resultExpressions"] is None):
            # required in catalyst; null only happens in a degraded
            # dump — converting anyway would silently drop the result
            # projection and rename (fuzz-pinned)
            raise UnsupportedSparkExec(
                f"{node.name} FINAL with resultExpressions degraded "
                f"to null")
        res = node.expr_list("resultExpressions")
        if res:
            exprs, names = [], []
            for p in res:
                e, n = _named_expr(p)
                exprs.append(e)
                names.append(n)
            out = ProjectExec(out, exprs, names)
    return out


def _convert_sort(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    fields = _sort_fields(node.expr_list("sortOrder"))
    return SortExec(child, fields)


def _partitioning(node: SparkNode, ctx: ConversionContext):
    v = node.fields.get("outputPartitioning")
    if v is None:
        return SinglePartitioning()
    if isinstance(v, list):  # HashPartitioning is an Expression tree
        p = node.expr("outputPartitioning")
        if p.name == "HashPartitioning":
            n_out = int(p.fields.get("numPartitions", ctx.default_parallelism))
            return HashPartitioning([convert_expr_with_fallback(k) for k in p.children], n_out)
        if p.name == "RangePartitioning":
            from ..parallel import RangePartitioning

            # in-process exchanges compute exact boundaries on device;
            # the file-shuffle path gets them from the scheduler's
            # driver-side sampling pass (run_stages boundary pass)
            n_out = int(p.fields.get("numPartitions", ctx.default_parallelism))
            return RangePartitioning(_sort_fields(p.children), n_out)
        raise UnsupportedSparkExec(f"partitioning {p.name}")
    if isinstance(v, dict):
        cls = v.get("product-class", "")
        if cls.endswith("SinglePartition$") or cls.endswith("SinglePartition"):
            return SinglePartitioning()
        if "RoundRobinPartitioning" in cls:
            return RoundRobinPartitioning(int(v.get("numPartitions", ctx.default_parallelism)))
    if isinstance(v, str) and "SinglePartition" in v:
        return SinglePartitioning()
    raise UnsupportedSparkExec(f"partitioning {v!r}")


def _convert_shuffle(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    return NativeShuffleExchangeExec(child, _partitioning(node, ctx))


def _convert_broadcast(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    return BroadcastExchangeExec(child)


def _join_sides(node: SparkNode, ctx: ConversionContext):
    left = ctx.convert(node.child(0))
    right = ctx.convert(node.child(1))
    lkeys = [convert_expr(k) for k in node.expr_list("leftKeys")]
    rkeys = [convert_expr(k) for k in node.expr_list("rightKeys")]
    cond = node.fields.get("condition")
    cond_e = convert_expr_with_fallback(node.expr("condition")) if cond else None
    return left, right, lkeys, rkeys, cond_e


def _wrap_condition(out: ExecNode, cond_e, jt: JoinType) -> ExecNode:
    # non-equi residual: post-join filter.  Sound ONLY for inner joins
    # — for outer joins the condition decides matching (failed matches
    # must still emit null-extended), and for semi/anti/existence the
    # join output can't even reference the probe side's filter columns.
    # The reference refuses any condition outright
    # (BlazeConverters.scala `assert condition.isEmpty`); we accept the
    # inner case and fall back otherwise.
    if cond_e is None:
        return out
    if jt != JoinType.INNER:
        raise UnsupportedSparkExec(f"join condition on {jt.name} join")
    return FilterExec(out, cond_e)


def _convert_bhj(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    left, right, lkeys, rkeys, cond_e = _join_sides(node, ctx)
    jt = _join_type(node)
    build_left = node.string("buildSide", "BuildRight") == "BuildLeft"
    if build_left:
        out = BroadcastJoinExec(left, right, lkeys, rkeys, jt, build_is_left=True)
    else:
        out = BroadcastJoinExec(right, left, rkeys, lkeys, jt, build_is_left=False)
    return _wrap_condition(_wrap_existence(out, node, jt), cond_e, jt)


def _convert_shj(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    left, right, lkeys, rkeys, cond_e = _join_sides(node, ctx)
    jt = _join_type(node)
    build_left = node.string("buildSide", "BuildLeft") == "BuildLeft"
    if build_left:
        out = HashJoinExec(left, right, lkeys, rkeys, jt, build_is_left=True)
    else:
        out = HashJoinExec(right, left, rkeys, lkeys, jt, build_is_left=False)
    return _wrap_condition(_wrap_existence(out, node, jt), cond_e, jt)


def _convert_smj(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    left, right, lkeys, rkeys, cond_e = _join_sides(node, ctx)
    jt = _join_type(node)
    out = SortMergeJoinExec(left, right, lkeys, rkeys, jt)
    return _wrap_condition(_wrap_existence(out, node, jt), cond_e, jt)


def _convert_window(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    part_by = [convert_expr(p) for p in node.expr_list("partitionSpec")]
    order_by = _sort_fields(node.expr_list("orderSpec"))
    functions: List[WindowFunction] = []
    for w in node.expr_list("windowExpression"):
        if w.name != "Alias" or w.children[0].name != "WindowExpression":
            raise UnsupportedSparkExec("window expression shape")
        eid = expr_id(w.fields.get("exprId"))
        out_name = f"#{eid}" if eid is not None else w.fields.get("name", "w")
        wexpr = w.children[0]
        wf = wexpr.children[0]
        whole, rows_frame, range_frame = _window_frame(wexpr)
        cls = wf.name
        if cls == "RowNumber":
            functions.append(WindowFunction("row_number", out_name))
        elif cls == "Rank":
            functions.append(WindowFunction("rank", out_name))
        elif cls == "DenseRank":
            functions.append(WindowFunction("dense_rank", out_name))
        elif cls == "NTile":
            b = wf.children[0] if wf.children else None
            if b is None or b.name != "Literal":
                raise UnsupportedSparkExec("ntile with non-literal buckets")
            functions.append(
                WindowFunction("ntile", out_name, offset=int(b.fields.get("value", 1)))
            )
        elif cls in ("Lead", "Lag"):
            ignore = bool(wf.fields.get("ignoreNulls"))
            off_node = wf.children[1] if len(wf.children) > 1 else None
            if off_node is None or off_node.name != "Literal":
                raise UnsupportedSparkExec(f"{cls} with non-literal offset")
            default = wf.children[2] if len(wf.children) > 2 else None
            if default is not None and not (
                default.name == "Literal" and default.fields.get("value") is None
            ):
                raise UnsupportedSparkExec(f"{cls} with non-null default")
            functions.append(
                WindowFunction(
                    cls.lower(), out_name, convert_expr_with_fallback(wf.children[0]),
                    offset=int(off_node.fields.get("value", 1)),
                    ignore_nulls=ignore,
                )
            )
        elif cls == "NthValue":
            if wf.fields.get("ignoreNulls"):
                raise UnsupportedSparkExec("nth_value IGNORE NULLS")
            if rows_frame is not None or range_frame is not None:
                # the engine evaluates nth_value over the running /
                # whole-partition frames only; silently dropping an
                # explicit frame would return plausible wrong values
                raise UnsupportedSparkExec("nth_value with an explicit frame")
            k = wf.children[1] if len(wf.children) > 1 else None
            if k is None or k.name != "Literal":
                raise UnsupportedSparkExec("nth_value with non-literal n")
            functions.append(
                WindowFunction(
                    "nth_value", out_name, convert_expr_with_fallback(wf.children[0]),
                    offset=int(k.fields.get("value", 1)),
                    whole_partition=whole,
                )
            )
        elif cls == "AggregateExpression":
            a = _agg_function(wf)
            if a.fn == "first_ignores_null":
                raise UnsupportedSparkExec("first(ignoreNulls) over a window")
            kind = {"count_star": "count", "first": "first_value"}.get(a.fn, a.fn)
            if rows_frame is not None:
                # raise the FALLBACK exception, not the engine's
                # NotImplementedError, so the strategy tags NEVER
                # instead of aborting the conversion
                if kind in ("min", "max") and None in rows_frame:
                    raise UnsupportedSparkExec(
                        "unbounded ROWS min/max window frame"
                    )
                if kind not in ("sum", "count", "avg", "min", "max"):
                    raise UnsupportedSparkExec(
                        f"ROWS frame for window aggregate {kind!r}"
                    )
            if range_frame is not None:
                if kind not in ("sum", "count", "avg", "min", "max"):
                    raise UnsupportedSparkExec(
                        f"RANGE frame for window aggregate {kind!r}"
                    )
                if len(node.expr_list("orderSpec")) != 1:
                    raise UnsupportedSparkExec(
                        "RANGE offset frame with multiple order keys"
                    )
            functions.append(
                WindowFunction(kind, out_name, a.expr,
                               whole_partition=whole, rows_frame=rows_frame,
                               range_frame=range_frame)
            )
        else:
            raise UnsupportedSparkExec(f"window function {cls}")
    try:
        return WindowExec(child, functions, part_by, order_by)
    except NotImplementedError as e:
        # engine-side refusals (e.g. RANGE frame over a non-integral
        # order key) must become strategy fallbacks, not crashes
        raise UnsupportedSparkExec(str(e))


def _window_frame(wexpr: SparkNode):
    """(whole_partition, rows_frame, range_frame) from a
    WindowExpression's WindowSpecDefinition -> SpecifiedWindowFrame
    (catalyst encodes bounds as UnboundedPreceding/Following/CurrentRow
    case objects or count/value literals; preceding bounds are
    negative)."""
    if len(wexpr.children) < 2:
        return False, None, None
    spec = wexpr.children[1]
    frame = next((c for c in spec.children if c.name == "SpecifiedWindowFrame"), None)
    if frame is None:
        return False, None, None

    def bound(b: SparkNode):
        # catalyst case objects serialize with a trailing "$"
        # (``UnboundedPreceding$``) — accept both spellings
        nm = b.name.rstrip("$")
        if nm in ("UnboundedPreceding", "UnboundedFollowing"):
            return "unbounded"
        if nm == "CurrentRow":
            return 0
        # only INTEGRAL literal bounds convert: decimal-string values
        # ("10.50") and interval bounds would either crash int() or be
        # silently misread in unscaled units — fall back instead
        if b.name == "Literal":
            try:
                return int(str(b.fields.get("value", 0)))
            except (TypeError, ValueError):
                raise UnsupportedSparkExec(
                    f"non-integral window frame bound {b.fields.get('value')!r}"
                )
        if b.name == "UnaryMinus" and b.children and b.children[0].name == "Literal":
            try:
                return -int(str(b.children[0].fields.get("value", 0)))
            except (TypeError, ValueError):
                raise UnsupportedSparkExec("non-integral window frame bound")
        raise UnsupportedSparkExec(f"window frame bound {b.name}")

    lower = bound(frame.children[0])
    upper = bound(frame.children[1])
    ftype = frame.string("frameType", "RangeFrame")
    if lower == "unbounded" and upper == "unbounded":
        return True, None, None
    if ftype.startswith("Range"):
        if lower == "unbounded" and upper == 0:
            return False, None, None  # the engine's default running frame
        # RANGE with value offsets: (preceding, following), None =
        # unbounded side (engine: per-partition binary search)
        x_ = None if lower == "unbounded" else max(-lower, 0)
        y_ = None if upper == "unbounded" else max(upper, 0)
        if isinstance(lower, int) and lower > 0:
            raise UnsupportedSparkExec("RANGE frame starting after current row")
        if isinstance(upper, int) and upper < 0:
            raise UnsupportedSparkExec("RANGE frame ending before current row")
        return False, None, (x_, y_)
    # RowFrame: engine bounds are (preceding, following), non-negative
    p_ = None if lower == "unbounded" else max(-lower, 0)
    q_ = None if upper == "unbounded" else max(upper, 0)
    if isinstance(lower, int) and lower > 0:
        raise UnsupportedSparkExec("ROWS frame starting after current row")
    if isinstance(upper, int) and upper < 0:
        raise UnsupportedSparkExec("ROWS frame ending before current row")
    return False, (p_, q_), None


def _convert_generate(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    gen = node.expr("generator")
    if gen is None:
        raise UnsupportedSparkExec("GenerateExec without generator")
    outer = bool(node.fields.get("outer", False))
    def rename_gen_outputs(out: ExecNode) -> ExecNode:
        gout = node.expr_list("generatorOutput")
        if gout:
            base = [f.name for f in child.schema.fields]
            gen_names = []
            for a in gout:
                eid = expr_id(a.fields.get("exprId"))
                gen_names.append(f"#{eid}" if eid is not None else _attr_user_name(a))
            out = RenameColumnsExec(out, base + gen_names)
        return out

    if gen.name in ("Explode", "PosExplode"):
        kind = "explode" if gen.name == "Explode" else "pos_explode"
        spec = NativeGenerator(kind, convert_expr(gen.children[0]))
        return rename_gen_outputs(GenerateExec(child, spec, [], outer=outer))
    if gen.name == "JsonTuple":
        # children = [json expr, field-name literals...]
        names = []
        for k in gen.children[1:]:
            if k.name != "Literal":
                raise UnsupportedSparkExec("json_tuple with non-literal field")
            names.append(str(k.fields.get("value")))
        json_expr = convert_expr(gen.children[0])
        # extracted values are substrings of the input document, so its
        # width bounds the field width
        from ..exprs.compile import infer_dtype

        in_t = infer_dtype(json_expr, child.schema)
        width = in_t.string_width if in_t.is_string else 64
        out = GenerateExec(
            child,
            json_tuple_generator(names),
            [json_expr],
            [Field(f"c{i}", DataType.string(width)) for i in range(len(names))],
            outer=outer,
        )
        return rename_gen_outputs(out)
    raise UnsupportedSparkExec(f"generator {gen.name}")


def _convert_expand(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    raw = node.fields.get("projections")
    if not isinstance(raw, list):
        raise UnsupportedSparkExec("ExpandExec projections missing")
    projections = []
    for proj in raw:
        projections.append([convert_expr(_parse_sub(e)) for e in proj])
    # Spark's rollup/cube projections null out grouped-away columns
    # with bare untyped nulls (StringType has no width, DecimalType may
    # be widened); the engine's ExpandExec requires every projection to
    # agree on physical dtypes, so retype null literals to the column
    # type the first (full) projection implies.
    from ..exprs.compile import infer_dtype
    from ..exprs.ir import Lit as _Lit

    if projections:
        base_types = [infer_dtype(e, child.schema) for e in projections[0]]
        for proj in projections[1:]:
            for i, e in enumerate(proj):
                if isinstance(e, _Lit) and e.value is None and i < len(base_types):
                    proj[i] = _Lit(None, base_types[i])
    names = []
    for a in node.expr_list("output"):
        eid = expr_id(a.fields.get("exprId"))
        names.append(f"#{eid}" if eid is not None else _attr_user_name(a))
    return ExpandExec(child, projections, names)


def _parse_sub(e):
    from .plan_json import _parse_tree

    return _parse_tree(e)


def _convert_union(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    return UnionExec([ctx.convert(c) for c in node.children])


def _convert_limit(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    limit = int(node.fields.get("limit", 0) or 0)
    return LimitExec(child, limit)


def _convert_take_ordered(node: SparkNode, ctx: ConversionContext) -> ExecNode:
    child = ctx.convert(node.child(0))
    limit = int(node.fields.get("limit", 0) or 0)
    fields = _sort_fields(node.expr_list("sortOrder"))
    single = NativeShuffleExchangeExec(child, SinglePartitioning())
    out: ExecNode = SortExec(single, fields, fetch=limit)
    out = LimitExec(out, limit)
    proj = node.expr_list("projectList")
    if proj:
        exprs, names = [], []
        for p in proj:
            e, n = _named_expr(p)
            exprs.append(e)
            names.append(n)
        out = ProjectExec(out, exprs, names)
    return out


_CONVERTERS: Dict[str, Callable[[SparkNode, ConversionContext], ExecNode]] = {
    "FileSourceScanExec": _convert_scan,
    "ProjectExec": _convert_project,
    "FilterExec": _convert_filter,
    "HashAggregateExec": _convert_agg,
    "SortAggregateExec": _convert_agg,
    "ObjectHashAggregateExec": _convert_agg,
    "SortExec": _convert_sort,
    "ShuffleExchangeExec": _convert_shuffle,
    "BroadcastExchangeExec": _convert_broadcast,
    "BroadcastHashJoinExec": _convert_bhj,
    "ShuffledHashJoinExec": _convert_shj,
    "SortMergeJoinExec": _convert_smj,
    "WindowExec": _convert_window,
    "GenerateExec": _convert_generate,
    "ExpandExec": _convert_expand,
    "UnionExec": _convert_union,
    "GlobalLimitExec": _convert_limit,
    "LocalLimitExec": _convert_limit,
    "TakeOrderedAndProjectExec": _convert_take_ordered,
}
