#!/usr/bin/env python3
"""Writes ``q7.plan.json``: TPC-DS q7 as Spark 3.5.1 plans it, in the
encoding of ``df.queryExecution.executedPlan.toJSON`` — a preorder node
array with child-index fields, ``product-class`` case objects, jvmId'ed
``ExprId``s, table-qualified attributes, ``WholeStageCodegenExec`` /
``InputAdapter`` / ``ColumnarToRowExec`` wrappers and
``FileSourceScanExec`` nodes with ``requiredSchema`` / ``dataFilters``
(the conventions of ``tests/fixtures/gen_spark351_dumps.py``, whose q6
shape was checked against a live Spark 3.5.1 dump).  Authored from the
query's text and from the shape of Spark's own
``tpcds-plan-stability/approved-plans-v1_4/q7``; not emitted from the
program's IR, and it imports nothing of the program.

The shape: ``store_sales`` filtered ``isnotnull`` on its four foreign
keys -> BroadcastHashJoin (Inner, BuildRight) with the filtered
``customer_demographics`` -> Project -> BHJ ``date_dim`` (d_year = 2000)
-> Project -> BHJ ``item`` -> Project -> BHJ ``promotion`` -> Project ->
partial HashAggregate -> hash Exchange on ``i_item_id`` -> final
HashAggregate -> TakeOrderedAndProject(100).  Spark's
``DecimalAggregates`` rule is applied: a decimal(7,2) average arrives as
``avg(UnscaledValue(x))`` and leaves as ``cast((avg / 100.0) as
decimal(11,6))``.  Left out (``assumed`` in the configuration): the
dynamic-partition-pruning subquery on ``ss_sold_date_sk`` (the table is
not partitioned here, so all four ``isnotnull`` are data filters and
``date_dim`` has an exchange of its own, not a ReusedExchange) and an
``AdaptiveSparkPlanExec`` wrapper.

    python3 bench/suites/tpcds/gen_q7_plan.py      # rewrites q7.plan.json
"""

import json
import os

X = "org.apache.spark.sql.catalyst.expressions."
A = X + "aggregate."
P = "org.apache.spark.sql.execution."
PHYS = "org.apache.spark.sql.catalyst.plans.physical."
JVM = "5d0c9a1e-7b3f-4c62-a8d4-91e2f6b07c35"
LEGACY = {"product-class": X + "EvalMode$LEGACY$"}
SHUFFLE_PARTITIONS = 4  # the configuration's partitions


def T(cls, children=(), **fields):
    return {"_cls": cls, "_children": list(children), **fields}


def flatten(t):
    out = []

    def go(n):
        fields = {k: v for k, v in n.items() if k not in ("_cls", "_children")}
        out.append({"class": n["_cls"], "num-children": len(n["_children"]), **fields})
        for c in n["_children"]:
            go(c)

    go(t)
    return out


def eid(i):
    return {"product-class": X + "ExprId", "id": i, "jvmId": JVM}


def attr(name, i, dtype, table=None):
    return T(X + "AttributeReference", name=name, dataType=dtype, nullable=True, metadata={},
             exprId=eid(i), qualifier=["spark_catalog", "default", table] if table else [])


def lit(value, dtype):
    return T(X + "Literal", value=str(value), dataType=dtype)


def alias(child, name, i):
    return T(X + "Alias", [child], name=name, exprId=eid(i), qualifier=[],
             explicitMetadata=None, nonInheritableMetadataKeys=[])


def binop(cls, left, right, eval_mode=False):
    return T(X + cls, [left, right], left=0, right=1, **({"evalMode": LEGACY} if eval_mode else {}))


def eq(a, text):
    return binop("EqualTo", a, lit(text, "string"))


def is_not_null(child):
    return T(X + "IsNotNull", [child], child=0)


def and_all(preds):
    out = preds[0]
    for p in preds[1:]:
        out = binop("And", out, p)
    return out


def cast(child, to):
    return T(X + "Cast", [child], child=0, dataType=to, timeZoneId="Etc/UTC", evalMode=LEGACY)


def unscaled(child):
    return T(X + "UnscaledValue", [child], child=0)


def avg(child):
    return T(A + "Average", [child], child=0, evalMode=LEGACY)


def agg_expr(fn, mode, result_id):
    return T(A + "AggregateExpression", [fn], aggregateFunction=0,
             mode={"product-class": A + mode + "$"}, isDistinct=False, filter=None,
             resultId=eid(result_id))


def sort_order(child):
    return T(X + "SortOrder", [child], child=0,
             direction={"product-class": X + "Ascending$"},
             nullOrdering={"product-class": X + "NullsFirst$"}, sameOrderExpressions=[])


def wsc(child, stage_id):
    return T(P + "WholeStageCodegenExec", [child], child=0, codegenStageId=stage_id)


def input_adapter(child):
    return T(P + "InputAdapter", [child], child=0)


def scan(table, attrs, data_filters):
    """The pruned Parquet scan under its ColumnarToRow, as whole-stage
    codegen wraps it."""
    fields = [{"name": a["name"], "type": a["dataType"], "nullable": True, "metadata": {}}
              for a in attrs]
    node = T(P + "FileSourceScanExec", relation=None, output=[flatten(a) for a in attrs],
             requiredSchema={"type": "struct", "fields": fields}, partitionFilters=[],
             optionalBucketSet=None, optionalNumCoalescedBuckets=None,
             dataFilters=[flatten(f) for f in data_filters],
             tableIdentifier={"product-class": "org.apache.spark.sql.catalyst.TableIdentifier",
                              "table": table, "database": "default"},
             disableBucketedScan=False)
    return T(P + "ColumnarToRowExec", [input_adapter(node)], child=0)


def filter_(preds, child):
    return T(P + "FilterExec", [child], condition=flatten(and_all(preds)), child=0)


def project(plist, child):
    return T(P + "ProjectExec", [child], projectList=[flatten(p) for p in plist], child=0)


def broadcast(child, key, stage_id):
    return input_adapter(T(
        P + "exchange.BroadcastExchangeExec", [wsc(child, stage_id)],
        mode={"product-class": P + "joins.HashedRelationBroadcastMode",
              "key": [flatten(key)], "isNullAware": False},
        child=0))


def bhj_build_right(stream_key, build_key, stream, build):
    return T(P + "joins.BroadcastHashJoinExec", [stream, build],
             leftKeys=[flatten(stream_key)], rightKeys=[flatten(build_key)],
             joinType={"product-class": "org.apache.spark.sql.catalyst.plans.Inner$"},
             buildSide={"product-class": P + "joins.BuildRight$"},
             condition=None, left=0, right=1, isNullAwareAntiJoin=False)


def hash_agg(groupings, aggs, child, result, partial):
    return T(P + "aggregate.HashAggregateExec", [child],
             requiredChildDistributionExpressions=None if partial else [flatten(g) for g in groupings],
             isStreaming=False, numShufflePartitions=None,
             groupingExpressions=[flatten(g) for g in groupings],
             aggregateExpressions=[flatten(a) for a in aggs], aggregateAttributes=[],
             initialInputBufferOffset=0 if partial else len(groupings),
             resultExpressions=[flatten(r) for r in result], child=0)


def q7():
    ss, cd, dd, it, pr = "store_sales", "customer_demographics", "date_dim", "item", "promotion"
    d72 = "decimal(7,2)"
    ss_date = attr("ss_sold_date_sk", 1, "long", ss)
    ss_item = attr("ss_item_sk", 2, "long", ss)
    ss_cdemo = attr("ss_cdemo_sk", 3, "long", ss)
    ss_promo = attr("ss_promo_sk", 4, "long", ss)
    ss_qty = attr("ss_quantity", 5, "integer", ss)
    ss_list = attr("ss_list_price", 6, d72, ss)
    ss_sales = attr("ss_sales_price", 7, d72, ss)
    ss_coupon = attr("ss_coupon_amt", 8, d72, ss)
    cd_sk = attr("cd_demo_sk", 11, "long", cd)
    cd_gender = attr("cd_gender", 12, "string", cd)
    cd_marital = attr("cd_marital_status", 13, "string", cd)
    cd_edu = attr("cd_education_status", 14, "string", cd)
    d_sk = attr("d_date_sk", 21, "long", dd)
    d_year = attr("d_year", 22, "integer", dd)
    i_sk = attr("i_item_sk", 31, "long", it)
    i_id = attr("i_item_id", 32, "string", it)
    p_sk = attr("p_promo_sk", 41, "long", pr)
    p_email = attr("p_channel_email", 42, "string", pr)
    p_event = attr("p_channel_event", 43, "string", pr)
    measures = [ss_qty, ss_list, ss_sales, ss_coupon]

    ss_preds = [is_not_null(k) for k in (ss_cdemo, ss_date, ss_item, ss_promo)]
    sales = filter_(ss_preds, scan(
        ss, [ss_date, ss_item, ss_cdemo, ss_promo] + measures, ss_preds))

    cd_preds = [is_not_null(cd_gender), is_not_null(cd_marital), is_not_null(cd_edu),
                eq(cd_gender, "M"), eq(cd_marital, "S"), eq(cd_edu, "College"),
                is_not_null(cd_sk)]
    cd_side = project([cd_sk], filter_(cd_preds, scan(
        cd, [cd_sk, cd_gender, cd_marital, cd_edu], cd_preds)))
    d_preds = [is_not_null(d_year), binop("EqualTo", d_year, lit(2000, "integer")),
               is_not_null(d_sk)]
    d_side = project([d_sk], filter_(d_preds, scan(dd, [d_sk, d_year], d_preds)))
    i_preds = [is_not_null(i_sk)]
    i_side = filter_(i_preds, scan(it, [i_sk, i_id], i_preds))
    p_preds = [binop("Or", eq(p_email, "N"), eq(p_event, "N")), is_not_null(p_sk)]
    p_side = project([p_sk], filter_(p_preds, scan(pr, [p_sk, p_email, p_event], p_preds)))

    j = bhj_build_right(ss_cdemo, cd_sk, sales, broadcast(cd_side, cd_sk, 1))
    j = project([ss_date, ss_item, ss_promo] + measures, j)
    j = bhj_build_right(ss_date, d_sk, j, broadcast(d_side, d_sk, 2))
    j = project([ss_item, ss_promo] + measures, j)
    j = bhj_build_right(ss_item, i_sk, j, broadcast(i_side, i_sk, 3))
    j = project([ss_promo] + measures + [i_id], j)
    j = bhj_build_right(ss_promo, p_sk, j, broadcast(p_side, p_sk, 4))
    j = project(measures + [i_id], j)

    # DecimalAggregates: avg(decimal(7,2)) -> avg(UnscaledValue(x)),
    # a double, divided by 10^2 and cast to decimal(11,6) in the result
    fns = [("agg1", avg(ss_qty), 51, None),
           ("agg2", avg(unscaled(ss_list)), 52, ss_list),
           ("agg3", avg(unscaled(ss_coupon)), 53, ss_coupon),
           ("agg4", avg(unscaled(ss_sales)), 54, ss_sales)]
    partial = hash_agg([i_id], [agg_expr(fn, "Partial", rid) for _, fn, rid, _ in fns], j,
                       result=[i_id], partial=True)
    exchange = T(P + "exchange.ShuffleExchangeExec", [wsc(partial, 5)],
                 outputPartitioning=flatten(T(PHYS + "HashPartitioning", [i_id],
                                              numPartitions=SHUFFLE_PARTITIONS)),
                 child=0, shuffleOrigin={"product-class": P + "exchange.ENSURE_REQUIREMENTS$"},
                 advisoryPartitionSize=None)
    results, outputs = [i_id], [i_id]
    for k, (name, _, rid, decimal_of) in enumerate(fns):
        of = f"UnscaledValue({decimal_of['name']})" if decimal_of else "ss_quantity"
        value = attr(f"avg({of})", rid, "double")
        if decimal_of:
            value = cast(binop("Divide", value, lit(100.0, "double"), True), "decimal(11,6)")
        results.append(alias(value, name, 61 + k))
        outputs.append(attr(name, 61 + k, "decimal(11,6)" if decimal_of else "double"))
    final = hash_agg([i_id], [agg_expr(fn, "Final", rid) for _, fn, rid, _ in fns],
                     input_adapter(exchange), result=results, partial=False)
    return T(P + "TakeOrderedAndProjectExec", [wsc(final, 6)], limit=100,
             sortOrder=[flatten(sort_order(i_id))], projectList=[flatten(a) for a in outputs],
             child=0, offset=0)


def main():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "q7.plan.json")
    dump = flatten(q7())
    with open(path, "w") as f:
        json.dump(dump, f)
    print(path, os.path.getsize(path), "bytes,", len(dump), "plan nodes")


if __name__ == "__main__":
    main()
