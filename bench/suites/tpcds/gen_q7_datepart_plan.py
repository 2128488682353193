#!/usr/bin/env python3
"""Writes ``q7.datepart.plan.json``: ``gen_q7_plan``'s q7 with the
``store_sales`` scan as Spark 3.5.1 plans it over the table its own
TPC-DS tooling lays out (``GenTPCDSData --partitionTables``:
``store_sales`` partitioned by ``ss_sold_date_sk``) — which is the
layout Spark's ``approved-plans-v1_4/q7`` is for:

    Filter [isnotnull(ss_cdemo_sk), isnotnull(ss_item_sk), isnotnull(ss_promo_sk)]
      ColumnarToRow
        Scan parquet store_sales [ss_item_sk, ss_cdemo_sk, ss_promo_sk, ss_quantity,
                                  ss_list_price, ss_sales_price, ss_coupon_amt, ss_sold_date_sk]
          PartitionFilters: [isnotnull(ss_sold_date_sk),
                             dynamicpruningexpression(ss_sold_date_sk IN dynamicpruning)]
          PushedFilters: [IsNotNull(ss_cdemo_sk), IsNotNull(ss_item_sk), IsNotNull(ss_promo_sk)]
          ReadSchema: the seven data columns

The partition column comes last in ``output`` and is not in
``requiredSchema``; the two filters on it are ``partitionFilters`` and
leave the ``FilterExec`` above.  The pruning subquery is the broadcast
of ``date_dim`` filtered to ``d_year = 2000`` (a copy of the plan's own
``date_dim`` side: Spark reuses one exchange for both, ``assumed`` in
the configuration).  A scan's ``relation`` is null in a ``toJSON`` dump
(``HadoopFsRelation`` is not serialisable), so no dump states a
partition schema: the program has it from the relation registered.
Everything else is ``gen_q7_plan``'s tree, node for node.

    python3 bench/suites/tpcds/gen_q7_datepart_plan.py   # rewrites q7.datepart.plan.json
"""

import json
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.suites.tpcds import gen_q7_plan as g  # noqa: E402

FACT, PARTITION_COLUMN = "store_sales", "ss_sold_date_sk"


def table_of(node):
    """The table of the one scan under a chain of single children, or None."""
    while len(node["_children"]) == 1:
        node = node["_children"][0]
    return (node.get("tableIdentifier") or {}).get("table")


def find(node, cls, table):
    """(parent, index) of the first ``cls`` node over ``table``'s scan."""
    for i, child in enumerate(node["_children"]):
        if child["_cls"].endswith(cls) and table_of(child) == table:
            return node, i
        if (found := find(child, cls, table)) is not None:
            return found
    return None


def dynamic_pruning(key, build_key, broadcast_side):
    """``DynamicPruningExpression(InSubqueryExec(key, SubqueryBroadcastExec))``
    as ``PlanDynamicPruningFilters`` leaves it where the join's build
    side is a broadcast that can be reused."""
    subquery = g.T(g.P + "SubqueryBroadcastExec", [broadcast_side], name="dynamicpruning#9",
                   index=0, buildKeys=[g.flatten(build_key)], child=0)
    in_subquery = g.T(g.P + "InSubqueryExec", [key], child=0, plan=g.flatten(subquery),
                      exprId=g.eid(9), shouldBroadcast=True, resultBroadcast=None, result=None)
    return g.T(g.X + "DynamicPruningExpression", [in_subquery], child=0)


def partitioned_scan(broadcast_side):
    """``store_sales`` under its data filters: ``gen_q7_plan.scan``'s
    node, the partition column last in ``output``, out of
    ``requiredSchema``, and the two filters on it ``partitionFilters``.
    The attributes are ``gen_q7_plan.q7``'s, exprId for exprId."""
    d72 = "decimal(7,2)"
    key = g.attr(PARTITION_COLUMN, 1, "long", FACT)
    item, cdemo, promo = (g.attr(name, i, "long", FACT) for name, i in (
        ("ss_item_sk", 2), ("ss_cdemo_sk", 3), ("ss_promo_sk", 4)))
    measures = [g.attr("ss_quantity", 5, "integer", FACT), g.attr("ss_list_price", 6, d72, FACT),
                g.attr("ss_sales_price", 7, d72, FACT), g.attr("ss_coupon_amt", 8, d72, FACT)]
    data_filters = [g.is_not_null(k) for k in (cdemo, item, promo)]
    columnar_to_row = g.scan(FACT, [item, cdemo, promo] + measures + [key], data_filters)
    node = columnar_to_row["_children"][0]["_children"][0]
    read = node["requiredSchema"]["fields"]
    node["requiredSchema"]["fields"] = [f for f in read if f["name"] != PARTITION_COLUMN]
    node["partitionFilters"] = [
        g.flatten(g.is_not_null(key)),
        g.flatten(dynamic_pruning(key, g.attr("d_date_sk", 21, "long", "date_dim"), broadcast_side))]
    return g.filter_(data_filters, columnar_to_row)


def q7_datepart():
    tree = g.q7()
    # the build side of the date join: an InputAdapter over its BroadcastExchange
    join, j = find(tree, "InputAdapter", "date_dim")
    parent, i = find(tree, "FilterExec", FACT)
    parent["_children"][i] = partitioned_scan(join["_children"][j]["_children"][0])
    return tree


def main():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "q7.datepart.plan.json")
    dump = g.flatten(q7_datepart())
    with open(path, "w") as f:
        json.dump(dump, f)
    print(path, os.path.getsize(path), "bytes,", len(dump), "plan nodes")


if __name__ == "__main__":
    main()
