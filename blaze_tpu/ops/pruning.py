"""Column pruning: narrow each operator's input to the columns it
actually uses.

≙ reference ``common/column_pruning.rs`` (ExecuteWithColumnPruning) and
the projected read schemas its scans take.  Name-based column
resolution makes the rewrite safe: any operator keeps working as long
as the names it references survive.  Scans are narrowed AT THE SOURCE
(fewer columns decoded / transferred); other children get a zero-cost
select (ProjectExec's all-Col fast path — a host-side list pick).

Apply with ``prune_columns(plan)`` after building a plan (run_task does
this for every decoded task).  Unknown operator types conservatively
require all of their children's columns.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Set

from ..exprs.ir import (
    Alias,
    BinOp,
    Case,
    Cast,
    Col,
    Expr,
    GetIndexedField,
    GetMapValue,
    GetStructField,
    InList,
    IsNotNull,
    IsNull,
    Like,
    NamedStruct,
    Not,
    PythonUdf,
    ScalarFunc,
    SparkUdfWrapper,
)


def expr_columns(e: Expr) -> Set[str]:
    """All column names a tree references."""
    out: Set[str] = set()

    def walk(x: Expr) -> None:
        if isinstance(x, Col):
            out.add(x.name)
        elif isinstance(x, Alias):
            walk(x.child)
        elif isinstance(x, BinOp):
            walk(x.left)
            walk(x.right)
        elif isinstance(x, (Not, IsNull, IsNotNull, Like)):
            walk(x.child)
        elif isinstance(x, Cast):
            walk(x.child)
        elif isinstance(x, Case):
            for c, v in x.branches:
                walk(c)
                walk(v)
            if x.else_ is not None:
                walk(x.else_)
        elif isinstance(x, InList):
            walk(x.child)
            for v in x.values:
                walk(v)
        elif isinstance(x, (ScalarFunc, PythonUdf, SparkUdfWrapper)):
            for a in x.args:
                walk(a)
        elif isinstance(x, GetIndexedField):
            walk(x.child)
        elif isinstance(x, GetMapValue):
            walk(x.child)
        elif isinstance(x, GetStructField):
            walk(x.child)
        elif isinstance(x, NamedStruct):
            for a in x.exprs:
                walk(a)

    walk(e)
    return out


def _narrow(child, needed: Set[str]):
    """Narrow ``child`` to ``needed`` columns (preserving its column
    order); scans are narrowed at the source, everything else gets the
    zero-cost select."""
    from .memory_scan import MemoryScanExec
    from .orc_scan import OrcScanExec
    from .parquet_scan import ParquetScanExec
    from .project import ProjectExec
    from ..schema import Schema

    if not needed <= set(child.schema.names):
        # a needed name the child cannot provide (e.g. a map-mode
        # broadcast build side): leave untouched
        return child
    names = [n for n in child.schema.names if n in needed]
    if not names and child.schema.names:
        # an all-literal consumer (q28/q90-style scalar projections)
        # references NO columns, but batches still carry row counts and
        # capacities through their columns — keep one anchor column
        names = [child.schema.names[0]]
    if len(names) == len(child.schema.names):
        return child
    if isinstance(child, ParquetScanExec):
        return child.narrowed(names)
    if isinstance(child, OrcScanExec):
        narrowed = Schema([child.schema.field(n) for n in names])
        return OrcScanExec(
            child.file_groups, narrowed, child.predicate, child.stated_batch_rows
        )
    return ProjectExec(child, [Col(n) for n in names], names)


def prune_columns(plan, required: Optional[Set[str]] = None):
    """Rewrite ``plan`` so every operator receives only the columns it
    (or its ancestors) need.  Returns the (possibly replaced) root."""
    from ..parallel.exchange import NativeShuffleExchangeExec
    from ..parallel.shuffle import HashPartitioning
    from .agg import AggExec, AggMode
    from .coalesce import CoalesceBatchesExec
    from .filter import FilterExec
    from .joins import BroadcastJoinExec, HashJoinExec, SortMergeJoinExec
    from .limit import LimitExec
    from .project import ProjectExec
    from .sort import SortExec
    from .union import UnionExec

    all_names = set(plan.schema.names)
    req = set(required) if required is not None else all_names

    if isinstance(plan, ProjectExec):
        kept = [
            (e, n) for e, n in zip(plan.exprs, plan.names)
            if required is None or n in req
        ] or list(zip(plan.exprs, plan.names))[:1]  # keep at least one
        child_req = set()
        for e, _ in kept:
            child_req |= expr_columns(e)
        child = prune_columns(plan.children[0], child_req)
        return ProjectExec(
            _narrow(child, child_req), [e for e, _ in kept], [n for _, n in kept]
        )

    if isinstance(plan, FilterExec):
        child_req = expr_columns(plan.predicate)
        project = plan.project
        if project is not None:
            proj_exprs, proj_names = project
            kept = [
                (e, n) for e, n in zip(proj_exprs, proj_names)
                if required is None or n in req
            ] or list(zip(proj_exprs, proj_names))[:1]
            project = ([e for e, _ in kept], [n for _, n in kept])
            for e, _ in kept:
                child_req |= expr_columns(e)
        else:
            child_req |= req
        child = prune_columns(plan.children[0], child_req)
        return FilterExec(_narrow(child, child_req), plan.predicate, project)

    if isinstance(plan, AggExec):
        if plan.mode != AggMode.PARTIAL:
            child_req = set(plan.children[0].schema.names)  # state cols
        else:
            child_req = set()
            for g in plan.groupings:
                child_req |= expr_columns(g.expr)
            for a in plan.aggs:
                if a.expr is not None:
                    child_req |= expr_columns(a.expr)
            if plan.pre_filter is not None:  # fused filter predicate
                child_req |= expr_columns(plan.pre_filter)
            if not child_req and plan.children[0].schema.names:
                # count(*)-only: the kernels still need one column for
                # capacity/liveness — keep the narrowest anchor
                child_req = {plan.children[0].schema.names[0]}
        child = prune_columns(plan.children[0], child_req)
        return AggExec(
            _narrow(child, child_req), plan.mode, plan.groupings, plan.aggs,
            supports_partial_skipping=plan.supports_partial_skipping,
            pre_filter=plan.pre_filter,
            post_sort=plan.post_sort, post_fetch=plan.post_fetch,
        )

    if isinstance(plan, SortExec):
        child_req = req | {c for f in plan.fields for c in expr_columns(f.expr)}
        child = prune_columns(plan.children[0], child_req)
        return SortExec(_narrow(child, child_req), plan.fields, plan.fetch)

    if isinstance(plan, NativeShuffleExchangeExec):
        child_req = set(req)
        if isinstance(plan.partitioning, HashPartitioning):
            for e in plan.partitioning.exprs:
                child_req |= expr_columns(e)
        child = prune_columns(plan.children[0], child_req)
        return NativeShuffleExchangeExec(
            _narrow(child, child_req), plan.partitioning, plan.manager,
            plan.parallel_map_tasks,
        )

    if isinstance(plan, (HashJoinExec, BroadcastJoinExec, SortMergeJoinExec)):
        if isinstance(plan, SortMergeJoinExec):
            sides = [plan.children[0], plan.children[1]]
            key_sets = [plan.left_keys, plan.right_keys]
        else:
            sides = [plan.children[0], plan.children[1]]
            key_sets = [plan.build_keys, plan.probe_keys]
        side_names = [set(s.schema.names) for s in sides]
        if side_names[0] & side_names[1]:
            return plan  # ambiguous names: leave untouched
        new_sides = []
        for side, keys, names in zip(sides, key_sets, side_names):
            side_req = (req & names) | {
                c for e in keys for c in expr_columns(e)
            }
            child = prune_columns(side, side_req)
            new_sides.append(_narrow(child, side_req))
        if isinstance(plan, SortMergeJoinExec):
            return SortMergeJoinExec(
                new_sides[0], new_sides[1], plan.left_keys, plan.right_keys,
                plan.join_type, plan.nulls_first,
            )
        extra = {}
        if isinstance(plan, BroadcastJoinExec):
            extra["cached_build_id"] = plan.cached_build_id
            if plan._map_mode:
                # map-mode build side was left untouched (_narrow guard);
                # keep its explicit data schema
                extra["build_data_schema"] = plan.build_data_schema
            # non-map-mode: let the new join derive the (narrowed)
            # build schema from its rebuilt build side
        return type(plan)(
            new_sides[0], new_sides[1], plan.build_keys, plan.probe_keys,
            plan.join_type, plan.build_is_left, **extra,
        )

    if isinstance(plan, UnionExec):
        return UnionExec([
            _narrow(prune_columns(c, set(req)), set(req)) for c in plan.children
        ]) if req != all_names else plan

    if isinstance(plan, (LimitExec, CoalesceBatchesExec)):
        child = prune_columns(plan.children[0], req)
        plan.children[0] = _narrow(child, req)
        return plan

    # unknown operator: recurse requiring everything from its children
    for i, c in enumerate(list(plan.children)):
        plan.children[i] = prune_columns(c, None)
    return plan
