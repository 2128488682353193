"""TPC-DS differentials through full Spark conversion — round-5 widening.

Extends test_spark_tpcds.py's 13-query slice toward the reference
gate's breadth (``tpcds-reusable.yml:83-143``): every test here
authors a catalyst ``toJSON`` physical-plan dump, crosses strategy +
expression conversion, executes via BOTH the in-process collect path
and the stage scheduler (TaskDefinition protobuf bytes + shuffle
files), and validates against the independent numpy oracles.

Dual-shape: each join-bearing plan is parametrized over the broadcast
shape AND the forced sort-merge shape (``SortMergeJoinExec`` over
sorted shuffles) — the reference CI runs every query twice, with
broadcast joins and with ``autoBroadcastJoinThreshold=-1``
(``tpcds-reusable.yml:123-143``).
"""

import json

import pytest

from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.spark import BlazeSparkSession
from blaze_tpu.tpcds import TPCDS_SCHEMAS
from blaze_tpu.tpcds import oracle as O
from blaze_tpu.tpcds.datagen import generate_all
from blaze_tpu.tpch.datagen import table_to_batches

import spark_fixtures as F
from test_spark_tpcds import (
    N_PARTS,
    a,
    and_,
    ar,
    in_,
    i32,
    ne,
    or_,
    s,
    distinct,
    two_stage,
)
from test_tpcds import (
    _check_brand_report,
    _check_class_share,
    _check_demo_avgs,
    _check_inv_price,
    _check_ship_lag,
    _check_ticket_report,
)

pytestmark = pytest.mark.slow

SCALE = 0.002

_SINCE_CLEAR = {"n": 0}


@pytest.fixture(autouse=True)
def _clear_caches_every_few_tests():
    """Same jaxlib compiled-program ceiling mitigation as
    test_tpcds.py — this module's dual-shape matrix compiles a lot of
    distinct programs."""
    yield
    _SINCE_CLEAR["n"] += 1
    if _SINCE_CLEAR["n"] % 8 == 0:
        import jax

        from blaze_tpu.ops.joins.broadcast import clear_join_map_cache
        from blaze_tpu.runtime.kernel_cache import clear_kernel_cache

        clear_kernel_cache()
        clear_join_map_cache()
        jax.clear_caches()


@pytest.fixture(scope="module")
def data():
    return generate_all(SCALE)


@pytest.fixture(scope="module")
def sess(data):
    sess = BlazeSparkSession(default_parallelism=N_PARTS)
    for name in TPCDS_SCHEMAS:
        sess.register_table(
            name,
            MemoryScanExec(
                table_to_batches(data[name], TPCDS_SCHEMAS[name], N_PARTS,
                                 batch_rows=4096),
                TPCDS_SCHEMAS[name],
            ),
        )
    return sess


@pytest.fixture(params=["bhj", "smj"])
def strategy(request):
    return request.param


def _ss(keys, child):
    """Sorted shuffle: the child of each forced-SMJ side."""
    return F.sort([F.sort_order(k) for k in keys],
                  F.shuffle(F.hash_partitioning(keys, N_PARTS), child),
                  global_=False)


def join(strategy, build, probe, bkeys, pkeys, jt="Inner",
         build_side="left", condition=None):
    """Strategy-parameterized equi-join: BroadcastHashJoin with the
    dimension side broadcast, or the forced-SMJ shape
    (SortMergeJoin over sorted hash shuffles) that the reference CI's
    autoBroadcastJoinThreshold=-1 run plans."""
    if strategy == "bhj":
        if build_side == "left":
            return F.bhj(bkeys, pkeys, jt, "left", F.broadcast(build),
                         probe, condition=condition)
        return F.bhj(pkeys, bkeys, jt, "right", probe, F.broadcast(build),
                     condition=condition)
    if build_side == "left":
        return F.smj(bkeys, pkeys, jt, _ss(bkeys, build), _ss(pkeys, probe),
                     condition=condition)
    return F.smj(pkeys, bkeys, jt, _ss(pkeys, probe), _ss(bkeys, build),
                 condition=condition)


def _execute_both(sess, plan):
    js = json.dumps(F.flatten(plan))
    got = sess.execute(js)
    got_sched = sess.execute_distributed(js)
    rows = sorted(
        zip(*got.values()), key=lambda r: tuple((v is None, v) for v in r)
    ) if got else []
    rows_sched = sorted(
        zip(*got_sched.values()), key=lambda r: tuple((v is None, v) for v in r)
    ) if got_sched else []
    assert rows == rows_sched, "in-process vs scheduler mismatch"
    return got


# ------------------------------------------------ q7/q26 demographic averages

def _demo_avg_plan(st, fact, cdemo_c, date_c, promo_c, item_c, qty_c,
                   list_c, coupon_c, sales_c):
    """q7/q26 in the simplified dump: build-left joins, decimal ``avg``.
    Spark 3.5.1 emits neither; its true shape for q7 (BuildRight,
    ``avg(UnscaledValue(x)) / 100.0`` cast to decimal(11,6)) is the
    benchmark's ``bench/suites/tpcds/q7.plan.json``, driven in tier-1 by
    ``tests/bench_harness/test_bench_tpcds_q7.py``."""
    cd = F.project(
        [a("cd_demo_sk")],
        F.filter_(
            and_(F.binop("EqualTo", a("cd_gender"), s("M")),
                 F.binop("EqualTo", a("cd_marital_status"), s("S")),
                 F.binop("EqualTo", a("cd_education_status"), s("College"))),
            F.scan("customer_demographics",
                   [a("cd_demo_sk"), a("cd_gender"), a("cd_marital_status"),
                    a("cd_education_status")]),
        ),
    )
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    pr = F.project(
        [a("p_promo_sk")],
        F.filter_(
            or_(F.binop("EqualTo", a("p_channel_email"), s("N")),
                F.binop("EqualTo", a("p_channel_event"), s("N"))),
            F.scan("promotion", [a("p_promo_sk"), a("p_channel_email"),
                                 a("p_channel_event")]),
        ),
    )
    sl = F.scan(fact, [a(cdemo_c), a(date_c), a(promo_c), a(item_c),
                       a(qty_c), a(list_c), a(coupon_c), a(sales_c)])
    j = join(st, cd, sl, [a("cd_demo_sk")], [a(cdemo_c)])
    j = join(st, dt, j, [a("d_date_sk")], [a(date_c)])
    j = join(st, pr, j, [a("p_promo_sk")], [a(promo_c)])
    it = F.scan("item", [a("i_item_sk"), a("i_item_id")])
    j = join(st, it, j, [a("i_item_sk")], [a(item_c)])
    agg = two_stage(
        [a("i_item_id")],
        [(F.avg(a(qty_c)), 501), (F.avg(a(list_c)), 502),
         (F.avg(a(coupon_c)), 503), (F.avg(a(sales_c)), 504)],
        j,
    )
    return F.take_ordered(
        100, [F.sort_order(a("i_item_id"))],
        [a("i_item_id"),
         F.alias(ar("agg1", 501, "double"), "agg1", 511),
         F.alias(ar("agg2", 502, "decimal(11,6)"), "agg2", 512),
         F.alias(ar("agg3", 503, "decimal(11,6)"), "agg3", 513),
         F.alias(ar("agg4", 504, "decimal(11,6)"), "agg4", 514)],
        agg,
    )


def test_spark_q7(sess, data, strategy):
    got = _execute_both(sess, _demo_avg_plan(
        strategy, "store_sales", "ss_cdemo_sk", "ss_sold_date_sk",
        "ss_promo_sk", "ss_item_sk", "ss_quantity", "ss_list_price",
        "ss_coupon_amt", "ss_sales_price"))
    _check_demo_avgs(got, O.oracle_q7(data))


def test_spark_q26(sess, data, strategy):
    got = _execute_both(sess, _demo_avg_plan(
        strategy, "catalog_sales", "cs_bill_cdemo_sk", "cs_sold_date_sk",
        "cs_promo_sk", "cs_item_sk", "cs_quantity", "cs_list_price",
        "cs_coupon_amt", "cs_sales_price"))
    _check_demo_avgs(got, O.oracle_q26(data))


# ------------------------------------------- q19 star + non-equi zip residual

def test_spark_q19(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(and_(F.binop("EqualTo", a("d_moy"), i32(11)),
                       F.binop("EqualTo", a("d_year"), i32(1998))),
                  F.scan("date_dim", [a("d_date_sk"), a("d_moy"), a("d_year")])),
    )
    it = F.project(
        [a("i_item_sk"), a("i_brand_id"), a("i_brand"), a("i_manufact_id"),
         a("i_manufact")],
        F.filter_(F.binop("EqualTo", a("i_manager_id"), i32(8)),
                  F.scan("item", [a("i_item_sk"), a("i_brand_id"), a("i_brand"),
                                  a("i_manufact_id"), a("i_manufact"),
                                  a("i_manager_id")])),
    )
    cust = F.scan("customer", [a("c_customer_sk"), a("c_current_addr_sk")])
    addr = F.scan("customer_address", [a("ca_address_sk"), a("ca_zip")])
    st_ = F.scan("store", [a("s_store_sk"), a("s_zip")])
    sl = F.scan("store_sales", [a("ss_sold_date_sk"), a("ss_item_sk"),
                                a("ss_customer_sk"), a("ss_store_sk"),
                                a("ss_ext_sales_price")])
    j = join(strategy, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("ss_item_sk")])
    j = join(strategy, cust, j, [a("c_customer_sk")], [a("ss_customer_sk")])
    j = join(strategy, addr, j, [a("ca_address_sk")], [a("c_current_addr_sk")])
    j = join(strategy, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    sub = lambda c: F.T(F.X + "Substring", [c, i32(1), i32(5)])
    j = F.filter_(ne(sub(a("ca_zip")), sub(a("s_zip"))), j)
    agg = two_stage(
        [a("i_brand_id"), a("i_brand"), a("i_manufact_id"), a("i_manufact")],
        [(F.sum_(a("ss_ext_sales_price")), 501)],
        j,
    )
    price = ar("ext_price", 501, "decimal(17,2)")
    plan = F.take_ordered(
        100,
        [F.sort_order(price, asc=False), F.sort_order(a("i_brand")),
         F.sort_order(a("i_brand_id")), F.sort_order(a("i_manufact_id")),
         F.sort_order(a("i_manufact"))],
        [F.alias(a("i_brand_id"), "brand_id", 510),
         F.alias(a("i_brand"), "brand", 511),
         F.alias(a("i_manufact_id"), "manufact_id", 512),
         F.alias(a("i_manufact"), "manufact", 513),
         F.alias(price, "ext_price", 514)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q19(data)
    assert exp, "q19 oracle empty"
    rows = {
        (bid, b, mid, m): v
        for bid, b, mid, m, v in zip(got["brand_id"], got["brand"],
                                     got["manufact_id"], got["manufact"],
                                     got["ext_price"])
    }
    if len(exp) <= 100:
        assert rows == exp
    else:
        assert set(rows.items()) <= set(exp.items())
    assert got["ext_price"] == sorted(got["ext_price"], reverse=True)


# ----------------------------------------------- q34/q73 ticket-count reports

def _ticket_plan(st, dom_pred, buy_potentials, cnt_lo, cnt_hi, ratio, orders):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(
            and_(dom_pred,
                 in_(a("d_year"), 1999, 2000, 2001, dtype="integer")),
            F.scan("date_dim", [a("d_date_sk"), a("d_dom"), a("d_year")]),
        ),
    )
    bp = in_(a("hd_buy_potential"), *buy_potentials)
    ratio_e = F.binop(
        "GreaterThan",
        F.binop("Divide", F.cast(a("hd_dep_count"), "double"),
                F.cast(a("hd_vehicle_count"), "double")),
        F.lit(ratio, "double"),
    )
    hd = F.project(
        [a("hd_demo_sk")],
        F.filter_(
            and_(bp, F.binop("GreaterThan", a("hd_vehicle_count"), i32(0)),
                 ratio_e),
            F.scan("household_demographics",
                   [a("hd_demo_sk"), a("hd_buy_potential"), a("hd_dep_count"),
                    a("hd_vehicle_count")]),
        ),
    )
    st_ = F.project(
        [a("s_store_sk")],
        F.filter_(
            in_(a("s_county"), "Williamson County", "Franklin Parish",
                "Bronx County", "Orange County"),
            F.scan("store", [a("s_store_sk"), a("s_county")]),
        ),
    )
    sl = F.scan("store_sales", [a("ss_sold_date_sk"), a("ss_hdemo_sk"),
                                a("ss_store_sk"), a("ss_ticket_number"),
                                a("ss_customer_sk")])
    j = join(st, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(st, hd, j, [a("hd_demo_sk")], [a("ss_hdemo_sk")])
    j = join(st, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    agg = two_stage(
        [a("ss_ticket_number"), a("ss_customer_sk")],
        [(F.count(), 501)],
        j,
    )
    cnt = ar("cnt", 501, "long")
    having = F.filter_(
        and_(F.binop("GreaterThanOrEqual", cnt, F.lit(cnt_lo, "long")),
             F.binop("LessThanOrEqual", cnt, F.lit(cnt_hi, "long"))),
        agg,
    )
    cust = F.scan("customer", [a("c_customer_sk"), a("c_salutation"),
                               a("c_first_name"), a("c_last_name"),
                               a("c_preferred_cust_flag")])
    j2 = join(st, cust, having, [a("c_customer_sk")], [a("ss_customer_sk")])
    proj = [a("c_salutation"), a("c_first_name"), a("c_last_name"),
            a("c_preferred_cust_flag"), a("ss_ticket_number"),
            a("ss_customer_sk"), F.alias(cnt, "cnt", 510)]
    single = F.shuffle(F.single_partition(), F.project(proj, j2))
    return F.sort(orders, single)


def test_spark_q34(ticket_sess, ticket_data, strategy):
    dom = or_(
        and_(F.binop("GreaterThanOrEqual", a("d_dom"), i32(1)),
             F.binop("LessThanOrEqual", a("d_dom"), i32(3))),
        and_(F.binop("GreaterThanOrEqual", a("d_dom"), i32(25)),
             F.binop("LessThanOrEqual", a("d_dom"), i32(28))),
    )
    plan = _ticket_plan(
        strategy, dom, (">10000", "Unknown"), 15, 20, 1.2,
        [F.sort_order(a("c_last_name")), F.sort_order(a("c_first_name")),
         F.sort_order(a("c_salutation")),
         F.sort_order(a("c_preferred_cust_flag"), asc=False),
         F.sort_order(a("ss_ticket_number"))],
    )
    got = _execute_both(ticket_sess, plan)
    _check_ticket_report(got, O.oracle_q34(ticket_data))


def test_spark_q73(ticket_sess, ticket_data, strategy):
    dom = and_(F.binop("GreaterThanOrEqual", a("d_dom"), i32(1)),
               F.binop("LessThanOrEqual", a("d_dom"), i32(2)))
    plan = _ticket_plan(
        strategy, dom, (">10000", "Unknown"), 1, 5, 1.0,
        [F.sort_order(ar("cnt", 510, "long"), asc=False),
         F.sort_order(a("c_last_name"))],
    )
    got = _execute_both(ticket_sess, plan)
    _check_ticket_report(got, O.oracle_q73(ticket_data))


@pytest.fixture(scope="module")
def ticket_data():
    return generate_all(0.01)


@pytest.fixture(scope="module")
def ticket_sess(ticket_data):
    sess = BlazeSparkSession(default_parallelism=N_PARTS)
    for name in TPCDS_SCHEMAS:
        sess.register_table(
            name,
            MemoryScanExec(
                table_to_batches(ticket_data[name], TPCDS_SCHEMAS[name],
                                 N_PARTS, batch_rows=4096),
                TPCDS_SCHEMAS[name],
            ),
        )
    return sess


# --------------------------------------------------------- q43 dow pivot

_DOW = ("sun", "mon", "tue", "wed", "thu", "fri", "sat")


def test_spark_q43(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk"), a("d_dow")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_dow"), a("d_year")])),
    )
    st_ = F.scan("store", [a("s_store_sk"), a("s_store_name")])
    sl = F.scan("store_sales", [a("ss_sold_date_sk"), a("ss_store_sk"),
                                a("ss_sales_price")])
    j = join(strategy, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(strategy, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    pivots = [
        F.alias(
            F.T(F.X + "CaseWhen",
                [F.binop("EqualTo", a("d_dow"), i32(k)), a("ss_sales_price")]),
            f"{nm}_v", 520 + k)
        for k, nm in enumerate(_DOW)
    ]
    proj = F.project([a("s_store_name")] + pivots, j)
    agg = two_stage(
        [a("s_store_name")],
        [(F.sum_(ar(f"{nm}_v", 520 + k, "decimal(7,2)")), 501 + k)
         for k, nm in enumerate(_DOW)],
        proj,
    )
    plan = F.take_ordered(
        100, [F.sort_order(a("s_store_name"))],
        [a("s_store_name")]
        + [F.alias(ar(f"{nm}_sales", 501 + k, "decimal(17,2)"),
                   f"{nm}_sales", 540 + k)
           for k, nm in enumerate(_DOW)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q43(data)
    assert exp, "q43 oracle empty"
    assert got["s_store_name"] == sorted(got["s_store_name"])
    for i, nm in enumerate(got["s_store_name"]):
        for k, d in enumerate(_DOW):
            assert (got[f"{d}_sales"][i] or 0) == exp[nm][k], (nm, d)


# ------------------------------------------------------------ q96 count star

def test_spark_q96(sess, data, strategy):
    td = F.project(
        [a("t_time_sk")],
        F.filter_(and_(F.binop("EqualTo", a("t_hour"), i32(20)),
                       F.binop("GreaterThanOrEqual", a("t_minute"), i32(30))),
                  F.scan("time_dim", [a("t_time_sk"), a("t_hour"),
                                      a("t_minute")])),
    )
    hd = F.project(
        [a("hd_demo_sk")],
        F.filter_(F.binop("EqualTo", a("hd_dep_count"), i32(7)),
                  F.scan("household_demographics",
                         [a("hd_demo_sk"), a("hd_dep_count")])),
    )
    st_ = F.project(
        [a("s_store_sk")],
        F.filter_(F.binop("EqualTo", a("s_store_name"), s("ese")),
                  F.scan("store", [a("s_store_sk"), a("s_store_name")])),
    )
    sl = F.scan("store_sales", [a("ss_sold_time_sk"), a("ss_hdemo_sk"),
                                a("ss_store_sk")])
    j = join(strategy, td, sl, [a("t_time_sk")], [a("ss_sold_time_sk")])
    j = join(strategy, hd, j, [a("hd_demo_sk")], [a("ss_hdemo_sk")])
    j = join(strategy, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    plan = two_stage([], [(F.count(), 501)], j,
                     result=[F.alias(ar("cnt", 501, "long"), "cnt", 510)])
    got = _execute_both(sess, plan)
    assert got["cnt"] == [O.oracle_q96(data)]


# ---------------------------------------------------- q62/q99 ship-lag pivot

_LAG = ("d30", "d60", "d90", "d120", "dmore")


def _ship_lag_plan(st, fact, sold_c, ship_c, wh_c, sm_c, dim_tab, dim_sk,
                   dim_name, dim_fk):
    dt = F.project(
        [a("d_date_sk"), a("d_date")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2001)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_date"),
                                      a("d_year")])),
    )
    d2sk = ar("d_date_sk", 601, "long")
    d2date = ar("d_date", 602, "date")
    d2 = F.project(
        [F.alias(d2sk, "d2_sk", 603), F.alias(d2date, "ship_date", 604)],
        F.scan("date_dim", [d2sk, d2date]),
    )
    wh = F.scan("warehouse", [a("w_warehouse_sk"), a("w_warehouse_name")])
    sm = F.scan("ship_mode", [a("sm_ship_mode_sk"), a("sm_type")])
    dim = F.scan(dim_tab, [a(dim_sk), a(dim_name)])
    sl = F.scan(fact, [a(sold_c), a(ship_c), a(wh_c), a(sm_c), a(dim_fk)])
    j = join(st, dt, sl, [a("d_date_sk")], [a(sold_c)])
    j = join(st, d2, j, [ar("d2_sk", 603, "long")], [a(ship_c)])
    j = join(st, wh, j, [a("w_warehouse_sk")], [a(wh_c)])
    j = join(st, sm, j, [a("sm_ship_mode_sk")], [a(sm_c)])
    j = join(st, dim, j, [a(dim_sk)], [a(dim_fk)])
    lag = F.binop("Subtract",
                  F.cast(ar("ship_date", 604, "date"), "long"),
                  F.cast(a("d_date"), "long"))
    base = F.project(
        [a("w_warehouse_name"), a("sm_type"), a(dim_name),
         F.alias(lag, "lag", 610)],
        j,
    )
    lag_a = ar("lag", 610, "long")
    one, zero = F.lit(1, "long"), F.lit(0, "long")

    def le(n):
        return F.binop("LessThanOrEqual", lag_a, F.lit(n, "long"))

    def gt(n):
        return F.binop("GreaterThan", lag_a, F.lit(n, "long"))

    buckets = [
        F.T(F.X + "CaseWhen", [le(30), one, zero]),
        F.T(F.X + "CaseWhen", [and_(gt(30), le(60)), one, zero]),
        F.T(F.X + "CaseWhen", [and_(gt(60), le(90)), one, zero]),
        F.T(F.X + "CaseWhen", [and_(gt(90), le(120)), one, zero]),
        F.T(F.X + "CaseWhen", [gt(120), one, zero]),
    ]
    proj = F.project(
        [a("w_warehouse_name"), a("sm_type"), a(dim_name)]
        + [F.alias(b, nm, 620 + k) for k, (nm, b) in
           enumerate(zip(_LAG, buckets))],
        base,
    )
    agg = two_stage(
        [a("w_warehouse_name"), a("sm_type"), a(dim_name)],
        [(F.sum_(ar(nm, 620 + k, "long")), 501 + k)
         for k, nm in enumerate(_LAG)],
        proj,
    )
    return F.take_ordered(
        100,
        [F.sort_order(a("w_warehouse_name")), F.sort_order(a("sm_type")),
         F.sort_order(a(dim_name))],
        [a("w_warehouse_name"), a("sm_type"), a(dim_name)]
        + [F.alias(ar(nm, 501 + k, "long"), nm, 640 + k)
           for k, nm in enumerate(_LAG)],
        agg,
    )


def test_spark_q62(sess, data, strategy):
    got = _execute_both(sess, _ship_lag_plan(
        strategy, "web_sales", "ws_sold_date_sk", "ws_ship_date_sk",
        "ws_warehouse_sk", "ws_ship_mode_sk", "web_site", "web_site_sk",
        "web_name", "ws_web_site_sk"))
    _check_ship_lag(got, O.oracle_q62(data), "web_name")


def test_spark_q99(sess, data, strategy):
    got = _execute_both(sess, _ship_lag_plan(
        strategy, "catalog_sales", "cs_sold_date_sk", "cs_ship_date_sk",
        "cs_warehouse_sk", "cs_ship_mode_sk", "call_center",
        "cc_call_center_sk", "cc_name", "cs_call_center_sk"))
    _check_ship_lag(got, O.oracle_q99(data), "cc_name")


# ------------------------------------- big-side joins (SHJ under bhj variant)

def big_join(strategy, left, right, lk, rk, jt="Inner", build_side="right",
             condition=None):
    """Fact-fact join: ShuffledHashJoin in the broadcast variant (the
    reference plans large-large equi-joins off the broadcast path too),
    SortMergeJoin in the forced-SMJ variant."""
    if strategy == "bhj":
        return F.shj(
            lk, rk, jt, build_side,
            F.shuffle(F.hash_partitioning(lk, N_PARTS), left),
            F.shuffle(F.hash_partitioning(rk, N_PARTS), right),
            condition=condition)
    return F.smj(lk, rk, jt, _ss(lk, left), _ss(rk, right),
                 condition=condition)


# ----------------------------------------------- q25/q29 provenance chain

def _srcandc_join_plan(st):
    d1 = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    d2sk, d2y = ar("d_date_sk", 601, "long"), ar("d_year", 602, "integer")
    d2 = F.project(
        [F.alias(d2sk, "d2_sk", 603)],
        F.filter_(and_(F.binop("GreaterThanOrEqual", d2y, i32(2000)),
                       F.binop("LessThanOrEqual", d2y, i32(2002))),
                  F.scan("date_dim", [d2sk, d2y])),
    )
    d3sk, d3y = ar("d_date_sk", 605, "long"), ar("d_year", 606, "integer")
    d3 = F.project(
        [F.alias(d3sk, "d3_sk", 607)],
        F.filter_(and_(F.binop("GreaterThanOrEqual", d3y, i32(2000)),
                       F.binop("LessThanOrEqual", d3y, i32(2002))),
                  F.scan("date_dim", [d3sk, d3y])),
    )
    sl = F.scan("store_sales",
                [a("ss_sold_date_sk"), a("ss_item_sk"), a("ss_ticket_number"),
                 a("ss_customer_sk"), a("ss_store_sk"), a("ss_net_profit"),
                 a("ss_quantity")])
    j = join(st, d1, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    sr = F.scan("store_returns",
                [a("sr_item_sk"), a("sr_ticket_number"), a("sr_customer_sk"),
                 a("sr_returned_date_sk"), a("sr_net_loss"),
                 a("sr_return_quantity")])
    j = big_join(st, j, sr, [a("ss_item_sk"), a("ss_ticket_number")],
                 [a("sr_item_sk"), a("sr_ticket_number")])
    j = join(st, d2, j, [ar("d2_sk", 603, "long")], [a("sr_returned_date_sk")])
    cs = F.scan("catalog_sales",
                [a("cs_sold_date_sk"), a("cs_bill_customer_sk"),
                 a("cs_item_sk"), a("cs_net_profit"), a("cs_quantity")])
    j = big_join(st, j, cs, [a("sr_customer_sk"), a("sr_item_sk")],
                 [a("cs_bill_customer_sk"), a("cs_item_sk")],
                 build_side="left")
    j = join(st, d3, j, [ar("d3_sk", 607, "long")], [a("cs_sold_date_sk")])
    st_ = F.scan("store", [a("s_store_sk"), a("s_store_name")])
    j = join(st, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    it = F.scan("item", [a("i_item_sk"), a("i_item_id"), a("i_item_desc")])
    j = join(st, it, j, [a("i_item_sk")], [a("ss_item_sk")])
    return j


def _srcandc_plan(st, sums, sum_names, sum_dtype, cast_long):
    j = _srcandc_join_plan(st)
    sum_in = [F.cast(a(c), "long") if cast_long else a(c) for c in sums]
    agg = two_stage(
        [a("i_item_id"), a("i_item_desc"), a("s_store_name")],
        [(F.sum_(e), 501 + k) for k, e in enumerate(sum_in)],
        j,
    )
    return F.take_ordered(
        100,
        [F.sort_order(a("i_item_id")), F.sort_order(a("i_item_desc")),
         F.sort_order(a("s_store_name"))],
        [a("i_item_id"), a("i_item_desc"), a("s_store_name")]
        + [F.alias(ar(nm, 501 + k, sum_dtype), nm, 510 + k)
           for k, nm in enumerate(sum_names)],
        agg,
    )


def test_spark_q25(sess, data, strategy):
    got = _execute_both(sess, _srcandc_plan(
        strategy, ("ss_net_profit", "sr_net_loss", "cs_net_profit"),
        ("store_sales_profit", "store_returns_loss", "catalog_sales_profit"),
        "decimal(17,2)", cast_long=False))
    from test_tpcds import _check_srcandc
    _check_srcandc(got, O.oracle_q25(data),
                   ["store_sales_profit", "store_returns_loss",
                    "catalog_sales_profit"])


def test_spark_q29(sess, data, strategy):
    got = _execute_both(sess, _srcandc_plan(
        strategy, ("ss_quantity", "sr_return_quantity", "cs_quantity"),
        ("store_sales_quantity", "store_returns_quantity",
         "catalog_sales_quantity"),
        "long", cast_long=True))
    from test_tpcds import _check_srcandc
    _check_srcandc(got, O.oracle_q29(data),
                   ["store_sales_quantity", "store_returns_quantity",
                    "catalog_sales_quantity"])


# ----------------------------------------------- q46/q68 city ticket reports

def _city_ticket_plan(st, hd_pred, amt_c, extra_c, extra_out):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(in_(a("d_dow"), 6, 0, dtype="integer"),
                  F.scan("date_dim", [a("d_date_sk"), a("d_dow")])),
    )
    st_ = F.project(
        [a("s_store_sk")],
        F.filter_(in_(a("s_city"), "Midway", "Fairview"),
                  F.scan("store", [a("s_store_sk"), a("s_city")])),
    )
    hd = F.project(
        [a("hd_demo_sk")],
        F.filter_(hd_pred,
                  F.scan("household_demographics",
                         [a("hd_demo_sk"), a("hd_dep_count"),
                          a("hd_vehicle_count")])),
    )
    ca = F.scan("customer_address", [a("ca_address_sk"), a("ca_city")])
    sl = F.scan("store_sales",
                [a("ss_sold_date_sk"), a("ss_store_sk"), a("ss_hdemo_sk"),
                 a("ss_addr_sk"), a("ss_ticket_number"), a("ss_customer_sk"),
                 a(amt_c), a(extra_c)])
    j = join(st, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(st, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    j = join(st, hd, j, [a("hd_demo_sk")], [a("ss_hdemo_sk")])
    j = join(st, ca, j, [a("ca_address_sk")], [a("ss_addr_sk")])
    bought = ar("bought_city", 615, "string")
    proj = F.project(
        [a("ss_ticket_number"), a("ss_customer_sk"),
         F.alias(a("ca_city"), "bought_city", 615), a(amt_c), a(extra_c)],
        j,
    )
    agg = two_stage(
        [a("ss_ticket_number"), a("ss_customer_sk"), bought],
        [(F.sum_(a(amt_c)), 501), (F.sum_(a(extra_c)), 502)],
        proj,
    )
    cu = F.scan("customer", [a("c_customer_sk"), a("c_last_name"),
                             a("c_first_name"), a("c_current_addr_sk")])
    j2 = join(st, cu, agg, [a("c_customer_sk")], [a("ss_customer_sk")])
    ca2sk, ca2city = ar("ca_address_sk", 611, "long"), ar("ca_city", 612, "string")
    ca2 = F.project(
        [F.alias(ca2sk, "cur_addr_sk", 613),
         F.alias(ca2city, "current_city", 614)],
        F.scan("customer_address", [ca2sk, ca2city]),
    )
    cur_city = ar("current_city", 614, "string")
    j2 = join(st, ca2, j2, [ar("cur_addr_sk", 613, "long")],
              [a("c_current_addr_sk")])
    f = F.filter_(ne(cur_city, bought), j2)
    amt = ar("amt", 501, "decimal(17,2)")
    extra = ar("extra", 502, "decimal(17,2)")
    return F.take_ordered(
        100,
        [F.sort_order(a("c_last_name")), F.sort_order(a("c_first_name")),
         F.sort_order(cur_city), F.sort_order(bought),
         F.sort_order(a("ss_ticket_number"))],
        [a("c_last_name"), a("c_first_name"), cur_city, bought,
         a("ss_ticket_number"), F.alias(amt, "amt", 520),
         F.alias(extra, extra_out, 521)],
        f,
    )


def test_spark_q46(sess, data, strategy):
    from test_tpcds import _check_city_tickets
    hd_pred = or_(F.binop("EqualTo", a("hd_dep_count"), i32(4)),
                  F.binop("EqualTo", a("hd_vehicle_count"), i32(3)))
    got = _execute_both(sess, _city_ticket_plan(
        strategy, hd_pred, "ss_coupon_amt", "ss_net_profit",
        "sum_ss_net_profit"))
    _check_city_tickets(got, O.oracle_q46(data), ["amt", "sum_ss_net_profit"])


def test_spark_q68(sess, data, strategy):
    from test_tpcds import _check_city_tickets
    hd_pred = or_(F.binop("EqualTo", a("hd_dep_count"), i32(5)),
                  F.binop("EqualTo", a("hd_vehicle_count"), i32(3)))
    got = _execute_both(sess, _city_ticket_plan(
        strategy, hd_pred, "ss_ext_sales_price", "ss_ext_list_price",
        "sum_ss_ext_list_price"))
    _check_city_tickets(got, O.oracle_q68(data),
                        ["amt", "sum_ss_ext_list_price"])


# --------------------------------------------------- q79 Monday big-household

def test_spark_q79(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(and_(F.binop("EqualTo", a("d_dow"), i32(1)),
                       F.binop("GreaterThanOrEqual", a("d_year"), i32(1998)),
                       F.binop("LessThanOrEqual", a("d_year"), i32(2000))),
                  F.scan("date_dim", [a("d_date_sk"), a("d_dow"), a("d_year")])),
    )
    hd = F.project(
        [a("hd_demo_sk")],
        F.filter_(or_(F.binop("EqualTo", a("hd_dep_count"), i32(6)),
                      F.binop("GreaterThan", a("hd_vehicle_count"), i32(2))),
                  F.scan("household_demographics",
                         [a("hd_demo_sk"), a("hd_dep_count"),
                          a("hd_vehicle_count")])),
    )
    st_ = F.scan("store", [a("s_store_sk"), a("s_city")])
    sl = F.scan("store_sales",
                [a("ss_sold_date_sk"), a("ss_hdemo_sk"), a("ss_store_sk"),
                 a("ss_ticket_number"), a("ss_customer_sk"),
                 a("ss_coupon_amt"), a("ss_net_profit")])
    j = join(strategy, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(strategy, hd, j, [a("hd_demo_sk")], [a("ss_hdemo_sk")])
    j = join(strategy, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    agg = two_stage(
        [a("ss_ticket_number"), a("ss_customer_sk"), a("s_city")],
        [(F.sum_(a("ss_coupon_amt")), 501), (F.sum_(a("ss_net_profit")), 502)],
        j,
    )
    cu = F.scan("customer", [a("c_customer_sk"), a("c_last_name"),
                             a("c_first_name")])
    j2 = join(strategy, cu, agg, [a("c_customer_sk")], [a("ss_customer_sk")])
    amt = ar("amt", 501, "decimal(17,2)")
    profit = ar("profit", 502, "decimal(17,2)")
    plan = F.take_ordered(
        100,
        [F.sort_order(a("c_last_name")), F.sort_order(a("c_first_name")),
         F.sort_order(a("s_city")), F.sort_order(profit),
         F.sort_order(a("ss_ticket_number"))],
        [a("c_last_name"), a("c_first_name"), a("s_city"),
         a("ss_ticket_number"), F.alias(amt, "amt", 520),
         F.alias(profit, "profit", 521)],
        j2,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q79(data)
    assert exp, "q79 oracle empty"
    n = len(got["ss_ticket_number"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["c_last_name"][i], got["c_first_name"][i],
               got["s_city"][i], got["ss_ticket_number"][i])
        assert key in exp, key
        assert (got["amt"][i], got["profit"][i]) == exp[key], key


# ------------------------------------------------------ q91 call-center loss

def test_spark_q91(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    cr = F.scan("catalog_returns",
                [a("cr_returned_date_sk"), a("cr_returning_customer_sk"),
                 a("cr_call_center_sk"), a("cr_net_loss")])
    j = join(strategy, dt, cr, [a("d_date_sk")], [a("cr_returned_date_sk")])
    cc = F.scan("call_center", [a("cc_call_center_sk"), a("cc_name")])
    j = join(strategy, cc, j, [a("cc_call_center_sk")],
             [a("cr_call_center_sk")])
    cu = F.scan("customer", [a("c_customer_sk"), a("c_current_cdemo_sk")])
    j = join(strategy, cu, j, [a("c_customer_sk")],
             [a("cr_returning_customer_sk")])
    cd = F.project(
        [a("cd_demo_sk"), a("cd_marital_status"), a("cd_education_status")],
        F.filter_(
            or_(and_(F.binop("EqualTo", a("cd_marital_status"), s("M")),
                     F.binop("EqualTo", a("cd_education_status"), s("Unknown"))),
                and_(F.binop("EqualTo", a("cd_marital_status"), s("W")),
                     F.binop("EqualTo", a("cd_education_status"),
                             s("Advanced Degree")))),
            F.scan("customer_demographics",
                   [a("cd_demo_sk"), a("cd_marital_status"),
                    a("cd_education_status")]),
        ),
    )
    j = join(strategy, cd, j, [a("cd_demo_sk")], [a("c_current_cdemo_sk")])
    agg = two_stage(
        [a("cc_name"), a("cd_marital_status"), a("cd_education_status")],
        [(F.sum_(a("cr_net_loss")), 501)],
        j,
    )
    loss = ar("returns_loss", 501, "decimal(17,2)")
    plan = F.take_ordered(
        100,
        [F.sort_order(loss, asc=False), F.sort_order(a("cc_name"))],
        [a("cc_name"), a("cd_marital_status"), a("cd_education_status"),
         F.alias(loss, "returns_loss", 510)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q91(data)
    assert exp, "q91 oracle empty"
    n = len(got["cc_name"])
    assert n == min(len(exp), 100)
    rows = {
        (got["cc_name"][i], got["cd_marital_status"][i],
         got["cd_education_status"][i]): got["returns_loss"][i]
        for i in range(n)
    }
    if len(exp) <= 100:
        assert rows == exp
    else:
        assert all(exp.get(k) == v for k, v in rows.items())
    assert got["returns_loss"] == sorted(got["returns_loss"], reverse=True)


# ---------------------------------------------- q93 LEFT join + CASE netting

def test_spark_q93(sess, data, strategy):
    sl = F.scan("store_sales",
                [a("ss_item_sk"), a("ss_ticket_number"), a("ss_customer_sk"),
                 a("ss_quantity"), a("ss_sales_price")])
    sr = F.scan("store_returns",
                [a("sr_item_sk"), a("sr_ticket_number"), a("sr_reason_sk"),
                 a("sr_return_quantity")])
    j = big_join(strategy, sl, sr,
                 [a("ss_item_sk"), a("ss_ticket_number")],
                 [a("sr_item_sk"), a("sr_ticket_number")], jt="LeftOuter")
    reason = F.project(
        [a("r_reason_sk")],
        F.filter_(F.binop("EqualTo", a("r_reason_desc"), s("Stopped working")),
                  F.scan("reason", [a("r_reason_sk"), a("r_reason_desc")])),
    )
    j = join(strategy, reason, j, [a("r_reason_sk")], [a("sr_reason_sk")])
    act = F.T(
        F.X + "CaseWhen",
        [F.un("IsNotNull", a("sr_return_quantity")),
         F.binop("Multiply",
                 F.cast(F.binop("Subtract", a("ss_quantity"),
                                a("sr_return_quantity")), "long"),
                 a("ss_sales_price")),
         F.binop("Multiply", F.cast(a("ss_quantity"), "long"),
                 a("ss_sales_price"))],
    )
    proj = F.project(
        [a("ss_customer_sk"), F.alias(act, "act_sales", 520)],
        j,
    )
    agg = two_stage(
        [a("ss_customer_sk")],
        [(F.sum_(ar("act_sales", 520, "decimal(17,2)")), 501)],
        proj,
    )
    sumsales = ar("sumsales", 501, "decimal(27,2)")
    plan = F.take_ordered(
        100,
        [F.sort_order(sumsales), F.sort_order(a("ss_customer_sk"))],
        [a("ss_customer_sk"), F.alias(sumsales, "sumsales", 510)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q93(data)
    assert exp, "q93 oracle empty"
    rows = dict(zip(got["ss_customer_sk"], got["sumsales"]))
    assert len(rows) == len(got["ss_customer_sk"])
    for k, v in rows.items():
        assert exp.get(k) == v, k
    assert len(rows) == min(len(exp), 100)
    assert got["sumsales"] == sorted(got["sumsales"])


# ------------------------------------------------- q97 FULL-outer overlap

def test_spark_q97(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )

    def pairs(fact, date_c, cust_c, item_c, pc, pi, cid, iid):
        sl = F.scan(fact, [a(date_c), a(cust_c), a(item_c)])
        j = join(strategy, dt, sl, [a("d_date_sk")], [a(date_c)])
        proj = F.project(
            [F.alias(a(cust_c), pc, cid), F.alias(a(item_c), pi, iid)], j)
        return two_stage([ar(pc, cid, "long"), ar(pi, iid, "long")], [], proj)

    ss = pairs("store_sales", "ss_sold_date_sk", "ss_customer_sk",
               "ss_item_sk", "sc", "si", 620, 621)
    cs = pairs("catalog_sales", "cs_sold_date_sk", "cs_bill_customer_sk",
               "cs_item_sk", "cc", "ci", 622, 623)
    sc, si = ar("sc", 620, "long"), ar("si", 621, "long")
    cc, ci = ar("cc", 622, "long"), ar("ci", 623, "long")
    j = big_join(strategy, ss, cs, [sc, si], [cc, ci], jt="FullOuter")
    one, zero = F.lit(1, "long"), F.lit(0, "long")
    flags = F.project(
        [F.alias(F.T(F.X + "CaseWhen",
                     [and_(F.un("IsNotNull", sc), F.un("IsNull", cc)), one,
                      zero]), "store_only", 630),
         F.alias(F.T(F.X + "CaseWhen",
                     [and_(F.un("IsNull", sc), F.un("IsNotNull", cc)), one,
                      zero]), "catalog_only", 631),
         F.alias(F.T(F.X + "CaseWhen",
                     [and_(F.un("IsNotNull", sc), F.un("IsNotNull", cc)), one,
                      zero]), "store_and_catalog", 632)],
        j,
    )
    plan = two_stage(
        [],
        [(F.sum_(ar("store_only", 630, "long")), 501),
         (F.sum_(ar("catalog_only", 631, "long")), 502),
         (F.sum_(ar("store_and_catalog", 632, "long")), 503)],
        flags,
        result=[F.alias(ar("store_only", 501, "long"), "store_only", 510),
                F.alias(ar("catalog_only", 502, "long"), "catalog_only", 511),
                F.alias(ar("store_and_catalog", 503, "long"),
                        "store_and_catalog", 512)],
    )
    got = _execute_both(sess, plan)
    so, co, both = O.oracle_q97(data)
    assert (got["store_only"], got["catalog_only"],
            got["store_and_catalog"]) == ([so], [co], [both])


# ------------------------------------------------- q65 aggregation over agg

def test_spark_q65(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    sl = F.scan("store_sales",
                [a("ss_sold_date_sk"), a("ss_store_sk"), a("ss_item_sk"),
                 a("ss_sales_price")])
    j = join(strategy, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    per_item = two_stage(
        [a("ss_store_sk"), a("ss_item_sk")],
        [(F.sum_(a("ss_sales_price")), 501)],
        j,
    )
    revenue = ar("revenue", 501, "decimal(17,2)")
    sb = F.project(
        [F.alias(a("ss_store_sk"), "sb_store_sk", 520), revenue], per_item)
    per_store = two_stage(
        [ar("sb_store_sk", 520, "long")],
        [(F.avg(revenue), 502)],
        sb,
    )
    ave = ar("ave", 502, "decimal(21,6)")
    jj = join(strategy, per_store, per_item,
              [ar("sb_store_sk", 520, "long")], [a("ss_store_sk")])
    low = F.filter_(
        F.binop("LessThanOrEqual", F.cast(revenue, "double"),
                F.binop("Multiply", F.cast(ave, "double"),
                        F.lit(0.1, "double"))),
        jj,
    )
    st_ = F.scan("store", [a("s_store_sk"), a("s_store_name")])
    it = F.scan("item", [a("i_item_sk"), a("i_item_desc"),
                         a("i_current_price"), a("i_brand")])
    out = join(strategy, st_, low, [a("s_store_sk")], [a("ss_store_sk")])
    out = join(strategy, it, out, [a("i_item_sk")], [a("ss_item_sk")])
    plan = F.take_ordered(
        100,
        [F.sort_order(a("s_store_name")), F.sort_order(a("i_item_desc"))],
        [a("s_store_name"), a("i_item_desc"),
         F.alias(revenue, "revenue", 530), a("i_current_price"), a("i_brand")],
        out,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q65(data)
    rows = list(zip(got["s_store_name"], got["i_item_desc"], got["revenue"],
                    got["i_current_price"], got["i_brand"]))
    assert rows, "q65 returned no rows"
    import collections
    if len(exp) <= 100:
        assert collections.Counter(rows) == collections.Counter(exp.values())
    else:
        assert not (collections.Counter(rows) - collections.Counter(exp.values()))
    keys = [(r[0], r[1]) for r in rows]
    assert keys == sorted(keys)


# ------------------------------------------------------ q50 return-lag pivot

def test_spark_q50(sess, data, strategy):
    sl = F.scan("store_sales",
                [a("ss_item_sk"), a("ss_ticket_number"), a("ss_customer_sk"),
                 a("ss_store_sk"), a("ss_sold_date_sk")])
    sr = F.scan("store_returns",
                [a("sr_item_sk"), a("sr_ticket_number"), a("sr_customer_sk"),
                 a("sr_returned_date_sk")])
    j = big_join(strategy, sl, sr,
                 [a("ss_item_sk"), a("ss_ticket_number"), a("ss_customer_sk")],
                 [a("sr_item_sk"), a("sr_ticket_number"), a("sr_customer_sk")])
    d1 = F.scan("date_dim", [a("d_date_sk"), a("d_date")])
    d2sk = ar("d_date_sk", 601, "long")
    d2date = ar("d_date", 602, "date")
    d2y, d2m = ar("d_year", 603, "integer"), ar("d_moy", 604, "integer")
    d2 = F.project(
        [F.alias(d2sk, "d2_sk", 605), F.alias(d2date, "ret_date", 606)],
        F.filter_(and_(F.binop("EqualTo", d2y, i32(2001)),
                       F.binop("EqualTo", d2m, i32(8))),
                  F.scan("date_dim", [d2sk, d2date, d2y, d2m])),
    )
    j = join(strategy, d1, j, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(strategy, d2, j, [ar("d2_sk", 605, "long")],
             [a("sr_returned_date_sk")])
    st_ = F.scan("store", [a("s_store_sk"), a("s_store_name"), a("s_county"),
                           a("s_state"), a("s_zip")])
    j = join(strategy, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    lag = F.binop("Subtract", F.cast(ar("ret_date", 606, "date"), "long"),
                  F.cast(a("d_date"), "long"))
    base = F.project(
        [a("s_store_name"), a("s_county"), a("s_state"), a("s_zip"),
         F.alias(lag, "lag", 610)],
        j,
    )
    lag_a = ar("lag", 610, "long")
    one, zero = F.lit(1, "long"), F.lit(0, "long")

    def le(n):
        return F.binop("LessThanOrEqual", lag_a, F.lit(n, "long"))

    def gt(n):
        return F.binop("GreaterThan", lag_a, F.lit(n, "long"))

    buckets = [
        F.T(F.X + "CaseWhen", [le(30), one, zero]),
        F.T(F.X + "CaseWhen", [and_(gt(30), le(60)), one, zero]),
        F.T(F.X + "CaseWhen", [and_(gt(60), le(90)), one, zero]),
        F.T(F.X + "CaseWhen", [and_(gt(90), le(120)), one, zero]),
        F.T(F.X + "CaseWhen", [gt(120), one, zero]),
    ]
    proj = F.project(
        [a("s_store_name"), a("s_county"), a("s_state"), a("s_zip")]
        + [F.alias(b, nm, 620 + k)
           for k, (nm, b) in enumerate(zip(_LAG, buckets))],
        base,
    )
    agg = two_stage(
        [a("s_store_name"), a("s_county"), a("s_state"), a("s_zip")],
        [(F.sum_(ar(nm, 620 + k, "long")), 501 + k)
         for k, nm in enumerate(_LAG)],
        proj,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(a("s_store_name")), F.sort_order(a("s_county")),
         F.sort_order(a("s_state")), F.sort_order(a("s_zip"))],
        [a("s_store_name"), a("s_county"), a("s_state"), a("s_zip")]
        + [F.alias(ar(nm, 501 + k, "long"), nm, 640 + k)
           for k, nm in enumerate(_LAG)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q50(data)
    assert exp, "q50 oracle empty"
    n = len(got["s_store_name"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["s_store_name"][i], got["s_county"][i], got["s_state"][i],
               got["s_zip"][i])
        assert key in exp, key
        assert tuple(got[b][i] for b in _LAG) == exp[key], key


# ------------------------------------------------- q23a/b best-customer CTEs

def _scalar_subquery(subplan, eid):
    return F.T(F.X + "ScalarSubquery", plan=F.flatten(subplan), exprId=F.eid(eid))


def _q23_frequent_items_plan(st):
    """Items sold >4 times in one (year*12+moy) cell, 1998-2002
    (mirrors queries._q23_frequent_items: no year slice)."""
    dt = F.scan("date_dim", [a("d_date_sk"), a("d_year"), a("d_moy")])
    sl = F.scan("store_sales", [a("ss_sold_date_sk"), a("ss_item_sk")])
    j = join(st, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    it = F.scan("item", [a("i_item_sk"), a("i_item_desc")])
    j = join(st, it, j, [a("i_item_sk")], [a("ss_item_sk")])
    itemdesc = F.T(F.X + "Substring", [a("i_item_desc"), i32(1), i32(30)])
    cell = F.binop("Add", F.binop("Multiply", a("d_year"), i32(12)), a("d_moy"))
    proj = F.project(
        [a("i_item_sk"), F.alias(itemdesc, "itemdesc", 701),
         F.alias(cell, "cell", 702)],
        j,
    )
    cells = two_stage(
        [a("i_item_sk"), ar("itemdesc", 701, "string"),
         ar("cell", 702, "integer")],
        [(F.count(), 703)],
        proj,
    )
    hot = F.filter_(
        F.binop("GreaterThan", ar("cnt", 703, "long"), F.lit(4, "long")),
        cells,
    )
    return two_stage([a("i_item_sk")], [], F.project([a("i_item_sk")], hot))


def _q23_best_customers_plan(st):
    spend = F.binop("Multiply", F.cast(a("ss_quantity"), "long"),
                    a("ss_sales_price"))
    sl = F.project(
        [a("ss_customer_sk"), F.alias(spend, "spend", 710)],
        F.scan("store_sales", [a("ss_customer_sk"), a("ss_quantity"),
                               a("ss_sales_price")]),
    )
    per_cust = two_stage(
        [a("ss_customer_sk")],
        [(F.sum_(ar("spend", 710, "decimal(17,2)")), 711)],
        sl,
    )
    csales = ar("csales", 711, "decimal(27,2)")
    cmax = two_stage([], [(F.max_(csales), 712)], per_cust,
                     result=[F.alias(ar("mx", 712, "decimal(27,2)"),
                                     "tpcds_cmax", 713)])
    best = F.filter_(
        F.binop("GreaterThan", F.cast(csales, "double"),
                F.binop("Multiply", F.lit(0.5, "double"),
                        F.cast(_scalar_subquery(cmax, 714), "double"))),
        per_cust,
    )
    return F.project([a("ss_customer_sk")], best)


def _q23_month_sales_plan(st, fact, date_c, item_c, cust_c, qty_c, price_c,
                          hot, best, names):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(and_(F.binop("EqualTo", a("d_year"), i32(2000)),
                       F.binop("EqualTo", a("d_moy"), i32(5))),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"), a("d_moy")])),
    )
    fc = F.scan(fact, [a(date_c), a(item_c), a(cust_c), a(qty_c), a(price_c)])
    j = join(st, dt, fc, [a("d_date_sk")], [a(date_c)])
    j = join(st, hot, j, [a("i_item_sk")], [a(item_c)], jt="LeftSemi",
             build_side="right")
    j = join(st, best, j, [a("ss_customer_sk")], [a(cust_c)], jt="LeftSemi",
             build_side="right")
    sales = F.binop("Multiply", F.cast(a(qty_c), "long"), a(price_c))
    if names:
        cu = F.scan("customer", [a("c_customer_sk"), a("c_last_name"),
                                 a("c_first_name")])
        j = join(st, cu, j, [a("c_customer_sk")], [a(cust_c)])
        return F.project(
            [a("c_last_name"), a("c_first_name"),
             F.alias(sales, "sales", 720)], j)
    return F.project([F.alias(sales, "sales", 720)], j)


def _q23_rows_plan(st, names):
    hot = _q23_frequent_items_plan(st)
    best = _q23_best_customers_plan(st)
    return F.union([
        _q23_month_sales_plan(st, "catalog_sales", "cs_sold_date_sk",
                              "cs_item_sk", "cs_bill_customer_sk",
                              "cs_quantity", "cs_list_price", hot, best, names),
        _q23_month_sales_plan(st, "web_sales", "ws_sold_date_sk",
                              "ws_item_sk", "ws_bill_customer_sk",
                              "ws_quantity", "ws_list_price", hot, best, names),
    ])


def test_spark_q23a(sess, data, strategy):
    rows = _q23_rows_plan(strategy, names=False)
    plan = two_stage(
        [], [(F.sum_(ar("sales", 720, "decimal(17,2)")), 501)], rows,
        result=[F.alias(ar("sum_sales", 501, "decimal(27,2)"),
                        "sum_sales", 510)],
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q23a(data)
    assert exp is not None, "q23a oracle empty"
    assert got["sum_sales"] == [exp]


def test_spark_q23b(sess, data, strategy):
    rows = _q23_rows_plan(strategy, names=True)
    agg = two_stage(
        [a("c_last_name"), a("c_first_name")],
        [(F.sum_(ar("sales", 720, "decimal(17,2)")), 501)],
        rows,
    )
    sales = ar("sales", 501, "decimal(27,2)")
    plan = F.take_ordered(
        100,
        [F.sort_order(sales, asc=False), F.sort_order(a("c_last_name")),
         F.sort_order(a("c_first_name"))],
        [a("c_last_name"), a("c_first_name"), F.alias(sales, "sales", 510)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q23b(data)
    assert exp, "q23b oracle empty"
    rows_g = {
        (l, f): v for l, f, v in
        zip(got["c_last_name"], got["c_first_name"], got["sales"])
    }
    if len(exp) <= 100:
        assert rows_g == exp
    else:
        assert all(exp.get(k) == v for k, v in rows_g.items())
    assert got["sales"] == sorted(got["sales"], reverse=True)


# ------------------------------------------------- q24a/b returned netpaid

def _q24_ssales_plan(st):
    sl = F.scan("store_sales",
                [a("ss_item_sk"), a("ss_ticket_number"), a("ss_store_sk"),
                 a("ss_customer_sk"), a("ss_net_paid")])
    sr = F.scan("store_returns", [a("sr_item_sk"), a("sr_ticket_number")])
    j = big_join(st, sl, sr, [a("ss_item_sk"), a("ss_ticket_number")],
                 [a("sr_item_sk"), a("sr_ticket_number")])
    st_ = F.project(
        [a("s_store_sk"), a("s_store_name"), a("s_county")],
        F.filter_(F.binop("EqualTo", a("s_market_id"), i32(8)),
                  F.scan("store", [a("s_store_sk"), a("s_store_name"),
                                   a("s_county"), a("s_market_id")])),
    )
    j = join(st, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    cu = F.scan("customer", [a("c_customer_sk"), a("c_last_name"),
                             a("c_first_name"), a("c_current_addr_sk")])
    j = join(st, cu, j, [a("c_customer_sk")], [a("ss_customer_sk")])
    ca = F.scan("customer_address", [a("ca_address_sk"), a("ca_county")])
    j = join(st, ca, j, [a("ca_address_sk")], [a("c_current_addr_sk")])
    j = F.filter_(F.binop("EqualTo", a("ca_county"), a("s_county")), j)
    it = F.scan("item", [a("i_item_sk"), a("i_color")])
    j = join(st, it, j, [a("i_item_sk")], [a("ss_item_sk")])
    return two_stage(
        [a("c_last_name"), a("c_first_name"), a("s_store_name"), a("i_color")],
        [(F.sum_(a("ss_net_paid")), 730)],
        j,
    )


def _q24_plan(st, color):
    netpaid = ar("netpaid", 730, "decimal(17,2)")
    avg_all = two_stage(
        [], [(F.avg(netpaid), 731)], _q24_ssales_plan(st),
        result=[F.alias(ar("avg_netpaid", 731, "decimal(21,6)"),
                        "avg_netpaid", 732)],
    )
    cells = F.filter_(F.binop("EqualTo", a("i_color"), s(color)),
                      _q24_ssales_plan(st))
    agg = two_stage(
        [a("c_last_name"), a("c_first_name"), a("s_store_name")],
        [(F.sum_(netpaid), 733)],
        cells,
    )
    paid = ar("paid", 733, "decimal(27,2)")
    f = F.filter_(
        F.binop("GreaterThan", F.cast(paid, "double"),
                F.binop("Multiply", F.lit(0.05, "double"),
                        F.cast(_scalar_subquery(avg_all, 734), "double"))),
        agg,
    )
    single = F.shuffle(F.single_partition(),
                       F.project([a("c_last_name"), a("c_first_name"),
                                  a("s_store_name"),
                                  F.alias(paid, "paid", 735)], f))
    return F.sort(
        [F.sort_order(a("c_last_name")), F.sort_order(a("c_first_name")),
         F.sort_order(a("s_store_name"))],
        single,
    )


def _check_q24_rows(got, exp):
    assert exp, "q24 oracle empty"
    rows = {
        (l, f, st_): v for l, f, st_, v in
        zip(got["c_last_name"], got["c_first_name"], got["s_store_name"],
            got["paid"])
    }
    assert rows == exp
    keys = list(zip(got["c_last_name"], got["c_first_name"],
                    got["s_store_name"]))
    assert keys == sorted(keys)


def test_spark_q24a(ticket_sess, ticket_data, strategy):
    got = _execute_both(ticket_sess, _q24_plan(strategy, "peach"))
    _check_q24_rows(got, O.oracle_q24a(ticket_data))


def test_spark_q24b(ticket_sess, ticket_data, strategy):
    got = _execute_both(ticket_sess, _q24_plan(strategy, "saddle"))
    _check_q24_rows(got, O.oracle_q24b(ticket_data))


# ------------------------------------------------------- q72 inventory giant

def test_spark_q72(sess, data, strategy):
    hd = F.project(
        [a("hd_demo_sk")],
        F.filter_(F.binop("EqualTo", a("hd_buy_potential"), s(">10000")),
                  F.scan("household_demographics",
                         [a("hd_demo_sk"), a("hd_buy_potential")])),
    )
    cd = F.project(
        [a("cd_demo_sk")],
        F.filter_(F.binop("EqualTo", a("cd_marital_status"), s("D")),
                  F.scan("customer_demographics",
                         [a("cd_demo_sk"), a("cd_marital_status")])),
    )
    d1 = F.scan("date_dim", [a("d_date_sk"), a("d_date"), a("d_week_seq")])
    d3sk, d3date = ar("d_date_sk", 601, "long"), ar("d_date", 602, "date")
    d3 = F.project(
        [F.alias(d3sk, "d3_date_sk", 603), F.alias(d3date, "d3_date", 604)],
        F.scan("date_dim", [d3sk, d3date]),
    )
    d2sk, d2wk = ar("d_date_sk", 605, "long"), ar("d_week_seq", 606, "integer")
    d2 = F.project(
        [F.alias(d2sk, "d2_date_sk", 607), F.alias(d2wk, "d2_week_seq", 608)],
        F.scan("date_dim", [d2sk, d2wk]),
    )
    cs = F.scan("catalog_sales",
                [a("cs_sold_date_sk"), a("cs_ship_date_sk"), a("cs_item_sk"),
                 a("cs_bill_cdemo_sk"), a("cs_bill_hdemo_sk"),
                 a("cs_quantity")])
    j = join(strategy, hd, cs, [a("hd_demo_sk")], [a("cs_bill_hdemo_sk")])
    j = join(strategy, cd, j, [a("cd_demo_sk")], [a("cs_bill_cdemo_sk")])
    j = join(strategy, d1, j, [a("d_date_sk")], [a("cs_sold_date_sk")])
    j = join(strategy, d3, j, [ar("d3_date_sk", 603, "long")],
             [a("cs_ship_date_sk")])
    j = F.filter_(
        F.binop("GreaterThan", F.cast(ar("d3_date", 604, "date"), "long"),
                F.binop("Add", F.cast(a("d_date"), "long"),
                        F.lit(5, "long"))),
        j,
    )
    inv = F.scan("inventory",
                 [a("inv_date_sk"), a("inv_item_sk"), a("inv_warehouse_sk"),
                  a("inv_quantity_on_hand")])
    j = big_join(strategy, j, inv, [a("cs_item_sk")], [a("inv_item_sk")],
                 build_side="left")
    j = join(strategy, d2, j, [ar("d2_date_sk", 607, "long")],
             [a("inv_date_sk")])
    j = F.filter_(
        and_(F.binop("EqualTo", ar("d2_week_seq", 608, "integer"),
                     a("d_week_seq")),
             F.binop("LessThan", a("inv_quantity_on_hand"),
                     a("cs_quantity"))),
        j,
    )
    it = F.scan("item", [a("i_item_sk"), a("i_item_desc")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("cs_item_sk")])
    wh = F.scan("warehouse", [a("w_warehouse_sk"), a("w_warehouse_name")])
    j = join(strategy, wh, j, [a("w_warehouse_sk")], [a("inv_warehouse_sk")])
    agg = two_stage(
        [a("i_item_desc"), a("w_warehouse_name"), a("d_week_seq")],
        [(F.count(), 501)],
        j,
    )
    no_promo = ar("no_promo", 501, "long")
    plan = F.take_ordered(
        100,
        [F.sort_order(no_promo, asc=False), F.sort_order(a("i_item_desc")),
         F.sort_order(a("w_warehouse_name")), F.sort_order(a("d_week_seq"))],
        [a("i_item_desc"), a("w_warehouse_name"), a("d_week_seq"),
         F.alias(no_promo, "no_promo", 510)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q72(data)
    assert exp, "q72 oracle empty"
    rows = {
        (d, w, wk): c for d, w, wk, c in
        zip(got["i_item_desc"], got["w_warehouse_name"], got["d_week_seq"],
            got["no_promo"])
    }
    for k, v in rows.items():
        assert exp.get(k) == v, k
    assert len(rows) == min(len(exp), 100)
    assert got["no_promo"] == sorted(got["no_promo"], reverse=True)


# ----------------------------------------------------- q67 rollup-rank giant

def test_spark_q67(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk"), a("d_year"), a("d_qoy"), a("d_moy")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"), a("d_qoy"),
                                      a("d_moy")])),
    )
    st_ = F.scan("store", [a("s_store_sk"), a("s_store_name")])
    it = F.scan("item", [a("i_item_sk"), a("i_category"), a("i_class"),
                         a("i_brand"), a("i_item_id")])
    sl = F.scan("store_sales",
                [a("ss_sold_date_sk"), a("ss_store_sk"), a("ss_item_sk"),
                 a("ss_quantity"), a("ss_sales_price")])
    j = join(strategy, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(strategy, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("ss_item_sk")])
    val = F.binop("Multiply", F.cast(a("ss_quantity"), "long"),
                  a("ss_sales_price"))
    base = F.project(
        [a("i_category"), a("i_class"), a("i_brand"), a("i_item_id"),
         a("d_year"), a("d_qoy"), a("d_moy"), a("s_store_name"),
         F.alias(val, "val", 700)],
        j,
    )
    dims = [("i_category", "string"), ("i_class", "string"),
            ("i_brand", "string"), ("i_item_id", "string"),
            ("d_year", "integer"), ("d_qoy", "integer"),
            ("d_moy", "integer"), ("s_store_name", "string")]
    val_a = ar("val", 700, "decimal(17,2)")
    exp_attrs = [ar(nm, 701 + k, dt_) for k, (nm, dt_) in enumerate(dims)]
    exp_gid = ar("g_id", 709, "integer")
    projections = []
    for level in range(8, -1, -1):
        row = [val_a]
        for k, (nm, dt_) in enumerate(dims):
            row.append(a(nm) if k < level else F.lit(None, dt_))
        row.append(F.lit(8 - level, "integer"))
        projections.append(row)
    expand = F.expand(projections, [val_a] + exp_attrs + [exp_gid], base)
    agg = two_stage(
        exp_attrs + [exp_gid],
        [(F.sum_(val_a), 501)],
        expand,
    )
    sumsales = ar("sumsales", 501, "decimal(27,2)")
    cat = exp_attrs[0]
    ex = F.shuffle(F.hash_partitioning([cat], N_PARTS), agg)
    srt = F.sort([F.sort_order(cat), F.sort_order(sumsales, asc=False)],
                 ex, global_=False)
    w = F.window(
        [F.window_expr(F.rank_fn([F.sort_order(sumsales, asc=False)]),
                       F.window_spec([cat],
                                     [F.sort_order(sumsales, asc=False)]),
                       "rk", 520)],
        [cat], [F.sort_order(sumsales, asc=False)], srt,
    )
    rk = ar("rk", 520, "integer")
    f = F.filter_(F.binop("LessThanOrEqual", rk, i32(100)), w)
    plan = F.take_ordered(
        100,
        [F.sort_order(cat), F.sort_order(rk),
         F.sort_order(sumsales, asc=False)],
        [F.alias(e, nm, 530 + k)
         for k, (e, (nm, _)) in enumerate(zip(exp_attrs, dims))]
        + [F.alias(exp_gid, "g_id", 540), F.alias(sumsales, "sumsales", 541),
           F.alias(rk, "rk", 542)],
        f,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q67(data)
    assert exp, "q67 oracle empty"
    n = len(got["i_category"])
    assert n == min(len(exp), 100)
    dim_names = [d[0] for d in dims]
    for i in range(n):
        key = tuple(got[d][i] for d in dim_names) + (got["g_id"][i],)
        assert key in exp, key
        v, rk_e = exp[key]
        assert (got["sumsales"][i], got["rk"][i]) == (v, rk_e), key
    order = [((0, "") if got["i_category"][i] is None
              else (1, got["i_category"][i]), got["rk"][i]) for i in range(n)]
    assert order == sorted(order)


# ------------------------------------------------- q75 cross-channel YoY

def _q75_channel_plan(st, fact, date_c, item_c, qty_c, amt_c, rtab, r_item_c,
                      r_key2_c, key2_c, r_qty_c, r_amt_c):
    dt = F.scan("date_dim", [a("d_date_sk"), a("d_year")])
    it = F.project(
        [a("i_item_sk"), a("i_brand_id"), a("i_class_id"), a("i_category_id"),
         a("i_manufact_id")],
        F.filter_(F.binop("EqualTo", a("i_category"), s("Books")),
                  F.scan("item", [a("i_item_sk"), a("i_brand_id"),
                                  a("i_class_id"), a("i_category_id"),
                                  a("i_manufact_id"), a("i_category")])),
    )
    sl = F.scan(fact, [a(date_c), a(item_c), a(key2_c), a(qty_c), a(amt_c)])
    j = join(st, dt, sl, [a("d_date_sk")], [a(date_c)])
    j = join(st, it, j, [a("i_item_sk")], [a(item_c)])
    ret = F.scan(rtab, [a(r_item_c), a(r_key2_c), a(r_qty_c), a(r_amt_c)])
    j = big_join(st, j, ret, [a(item_c), a(key2_c)],
                 [a(r_item_c), a(r_key2_c)], jt="LeftOuter")
    qty_net = F.binop(
        "Subtract", F.cast(a(qty_c), "long"),
        F.T(F.X + "CaseWhen",
            [F.un("IsNotNull", a(r_qty_c)), F.cast(a(r_qty_c), "long"),
             F.lit(0, "long")]),
    )
    dz = F.lit(0, "decimal(8,2)")
    amt_net = F.binop(
        "Subtract", F.binop("Add", a(amt_c), dz),
        F.T(F.X + "CaseWhen",
            [F.un("IsNotNull", a(r_amt_c)), F.binop("Add", a(r_amt_c), dz),
             dz]),
    )
    return F.project(
        [a("d_year"), a("i_brand_id"), a("i_class_id"), a("i_category_id"),
         a("i_manufact_id"), F.alias(qty_net, "qty", 750),
         F.alias(amt_net, "amt", 751)],
        j,
    )


def test_spark_q75(ticket_sess, ticket_data, strategy):
    rows = F.union([
        _q75_channel_plan(strategy, "store_sales", "ss_sold_date_sk",
                          "ss_item_sk", "ss_quantity", "ss_ext_sales_price",
                          "store_returns", "sr_item_sk", "sr_ticket_number",
                          "ss_ticket_number", "sr_return_quantity",
                          "sr_return_amt"),
        _q75_channel_plan(strategy, "catalog_sales", "cs_sold_date_sk",
                          "cs_item_sk", "cs_quantity", "cs_ext_sales_price",
                          "catalog_returns", "cr_item_sk", "cr_order_number",
                          "cs_order_number", "cr_return_quantity",
                          "cr_return_amount"),
        _q75_channel_plan(strategy, "web_sales", "ws_sold_date_sk",
                          "ws_item_sk", "ws_quantity", "ws_ext_sales_price",
                          "web_returns", "wr_item_sk", "wr_order_number",
                          "ws_order_number", "wr_return_quantity",
                          "wr_return_amt"),
    ])
    ids = ["i_brand_id", "i_class_id", "i_category_id", "i_manufact_id"]
    agg = two_stage(
        [a("d_year")] + [a(c) for c in ids],
        [(F.sum_(ar("qty", 750, "long")), 501),
         (F.sum_(ar("amt", 751, "decimal(18,2)")), 502)],
        rows,
    )
    cnt = ar("sales_cnt", 501, "long")
    amt = ar("sales_amt", 502, "decimal(28,2)")
    curr = F.project(
        [a(c) for c in ids]
        + [F.alias(cnt, "curr_cnt", 760), F.alias(amt, "curr_amt", 761)],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2002)), agg),
    )
    prev = F.project(
        [F.alias(a(c), f"p_{c}", 770 + k) for k, c in enumerate(ids)]
        + [F.alias(cnt, "prev_cnt", 762), F.alias(amt, "prev_amt", 763)],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2001)), agg),
    )
    j = big_join(strategy, curr, prev, [a(c) for c in ids],
                 [ar(f"p_{c}", 770 + k, "integer")
                  for k, c in enumerate(ids)])
    curr_cnt = ar("curr_cnt", 760, "long")
    prev_cnt = ar("prev_cnt", 762, "long")
    curr_amt = ar("curr_amt", 761, "decimal(28,2)")
    prev_amt = ar("prev_amt", 763, "decimal(28,2)")
    f = F.filter_(
        and_(F.binop("GreaterThan", F.cast(prev_cnt, "double"),
                     F.lit(0.0, "double")),
             F.binop("LessThan",
                     F.binop("Divide", F.cast(curr_cnt, "double"),
                             F.cast(prev_cnt, "double")),
                     F.lit(0.9, "double"))),
        j,
    )
    cnt_diff = F.binop("Subtract", curr_cnt, prev_cnt)
    amt_diff = F.binop("Subtract", curr_amt, prev_amt)
    proj = F.project(
        [F.alias(F.lit(2001, "integer"), "prev_year", 780),
         F.alias(F.lit(2002, "integer"), "year", 781)]
        + [a(c) for c in ids]
        + [F.alias(cnt_diff, "sales_cnt_diff", 782),
           F.alias(amt_diff, "sales_amt_diff", 783)],
        f,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(ar("sales_cnt_diff", 782, "long")),
         F.sort_order(ar("sales_amt_diff", 783, "decimal(28,2)"))],
        [ar("prev_year", 780, "integer"), ar("year", 781, "integer")]
        + [a(c) for c in ids]
        + [ar("sales_cnt_diff", 782, "long"),
           ar("sales_amt_diff", 783, "decimal(28,2)")],
        proj,
    )
    got = _execute_both(ticket_sess, plan)
    exp = O.oracle_q75(ticket_data)
    assert exp, "q75 oracle empty"
    rows_g = {
        (b, c, cat, m): (cd, ad) for b, c, cat, m, cd, ad in
        zip(got["i_brand_id"], got["i_class_id"], got["i_category_id"],
            got["i_manufact_id"], got["sales_cnt_diff"],
            got["sales_amt_diff"])
    }
    if len(exp) <= 100:
        assert rows_g == exp
    else:
        assert all(exp.get(k) == v for k, v in rows_g.items())
    assert got["sales_cnt_diff"] == sorted(got["sales_cnt_diff"])
    assert all(y == 2002 for y in got["year"])


# ------------------------------------------------- q78 channel loyalty

def _q78_channel_plan(st, fact, date_c, item_c, cust_c, qty_c, wc_c, sp_c,
                      rtab, r_item_c, r_key2_c, key2_c, pre, base_id):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    sl = F.scan(fact, [a(date_c), a(item_c), a(cust_c), a(key2_c), a(qty_c),
                       a(wc_c), a(sp_c)])
    j = join(st, dt, sl, [a("d_date_sk")], [a(date_c)])
    ret = F.scan(rtab, [a(r_item_c), a(r_key2_c)])
    j = big_join(st, j, ret, [a(item_c), a(key2_c)],
                 [a(r_item_c), a(r_key2_c)], jt="LeftAnti")
    proj = F.project(
        [F.alias(a(item_c), f"{pre}_item_sk", base_id),
         F.alias(a(cust_c), f"{pre}_customer_sk", base_id + 1),
         F.alias(F.cast(a(qty_c), "long"), "q", base_id + 2),
         a(wc_c), a(sp_c)],
        j,
    )
    return two_stage(
        [ar(f"{pre}_item_sk", base_id, "long"),
         ar(f"{pre}_customer_sk", base_id + 1, "long")],
        [(F.sum_(ar("q", base_id + 2, "long")), base_id + 3),
         (F.sum_(a(wc_c)), base_id + 4), (F.sum_(a(sp_c)), base_id + 5)],
        proj,
    )


def test_spark_q78(sess, data, strategy):
    ss = _q78_channel_plan(strategy, "store_sales", "ss_sold_date_sk",
                           "ss_item_sk", "ss_customer_sk", "ss_quantity",
                           "ss_wholesale_cost", "ss_sales_price",
                           "store_returns", "sr_item_sk", "sr_ticket_number",
                           "ss_ticket_number", "ss", 800)
    ws = _q78_channel_plan(strategy, "web_sales", "ws_sold_date_sk",
                           "ws_item_sk", "ws_bill_customer_sk", "ws_quantity",
                           "ws_wholesale_cost", "ws_sales_price",
                           "web_returns", "wr_item_sk", "wr_order_number",
                           "ws_order_number", "ws", 810)
    cs = _q78_channel_plan(strategy, "catalog_sales", "cs_sold_date_sk",
                           "cs_item_sk", "cs_bill_customer_sk", "cs_quantity",
                           "cs_wholesale_cost", "cs_sales_price",
                           "catalog_returns", "cr_item_sk", "cr_order_number",
                           "cs_order_number", "cs", 820)
    ss_i, ss_c = ar("ss_item_sk", 800, "long"), ar("ss_customer_sk", 801, "long")
    ws_i, ws_c = ar("ws_item_sk", 810, "long"), ar("ws_customer_sk", 811, "long")
    cs_i, cs_c = ar("cs_item_sk", 820, "long"), ar("cs_customer_sk", 821, "long")
    ss_qty = ar("ss_qty", 803, "long")
    ws_qty = ar("ws_qty", 813, "long")
    cs_qty = ar("cs_qty", 823, "long")
    j = big_join(strategy, ss, ws, [ss_i, ss_c], [ws_i, ws_c], jt="LeftOuter")
    j = big_join(strategy, j, cs, [ss_i, ss_c], [cs_i, cs_c], jt="LeftOuter")

    def czero(c):
        return F.T(F.X + "CaseWhen",
                   [F.un("IsNotNull", c), c, F.lit(0, "long")])

    f = F.filter_(
        or_(F.binop("GreaterThan", czero(ws_qty), F.lit(0, "long")),
            F.binop("GreaterThan", czero(cs_qty), F.lit(0, "long"))),
        j,
    )
    other = F.cast(F.binop("Add", czero(ws_qty), czero(cs_qty)), "double")
    den = F.T(F.X + "CaseWhen",
              [F.binop("GreaterThan", other, F.lit(0.0, "double")), other,
               F.lit(1.0, "double")])
    ratio = F.binop("Divide", F.cast(ss_qty, "double"), den)
    other_q = F.binop("Add", czero(ws_qty), czero(cs_qty))
    proj = F.project(
        [ss_i, ss_c, ss_qty, ar("ss_wc", 804, "decimal(17,2)"),
         ar("ss_sp", 805, "decimal(17,2)"),
         F.alias(ratio, "ratio", 830),
         F.alias(other_q, "other_chan_qty", 831)],
        f,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(ss_qty, asc=False), F.sort_order(ss_i),
         F.sort_order(ss_c)],
        [ss_i, ss_c, ss_qty, ar("ss_wc", 804, "decimal(17,2)"),
         ar("ss_sp", 805, "decimal(17,2)"), ar("ratio", 830, "double"),
         ar("other_chan_qty", 831, "long")],
        proj,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q78(data)
    assert exp, "q78 oracle empty"
    n = len(got["ss_item_sk"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["ss_item_sk"][i], got["ss_customer_sk"][i])
        assert key in exp, key
        q, w_, sp_, ratio_e, other_e = exp[key]
        assert (got["ss_qty"][i], got["ss_wc"][i], got["ss_sp"][i]) == (q, w_, sp_), key
        assert abs(got["ratio"][i] - ratio_e) < 1e-12, key
        assert got["other_chan_qty"][i] == other_e, key
    assert got["ss_qty"] == sorted(got["ss_qty"], reverse=True)


# ------------------------------------------------- q14a/b INTERSECT giants

def _q14_cross_items_plan(st):
    def triples(fact, date_c, item_c):
        dt = F.project(
            [a("d_date_sk")],
            F.filter_(and_(F.binop("GreaterThanOrEqual", a("d_year"), i32(1998)),
                           F.binop("LessThanOrEqual", a("d_year"), i32(2000))),
                      F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
        )
        it = F.scan("item", [a("i_item_sk"), a("i_brand_id"), a("i_class_id"),
                             a("i_category_id")])
        sl = F.scan(fact, [a(date_c), a(item_c)])
        j = join(st, dt, sl, [a("d_date_sk")], [a(date_c)])
        j = join(st, it, j, [a("i_item_sk")], [a(item_c)])
        return two_stage(
            [a("i_brand_id"), a("i_class_id"), a("i_category_id")], [], j)

    ss = triples("store_sales", "ss_sold_date_sk", "ss_item_sk")
    cs = triples("catalog_sales", "cs_sold_date_sk", "cs_item_sk")
    ws = triples("web_sales", "ws_sold_date_sk", "ws_item_sk")
    keys = [a("i_brand_id"), a("i_class_id"), a("i_category_id")]
    inter = join(st, cs, ss, keys, keys, jt="LeftSemi", build_side="right")
    inter = join(st, ws, inter, keys, keys, jt="LeftSemi", build_side="right")
    items = F.scan("item", [a("i_item_sk"), a("i_brand_id"), a("i_class_id"),
                            a("i_category_id")])
    hot = join(st, inter, items, keys, keys, jt="LeftSemi", build_side="right")
    return F.project([a("i_item_sk")], hot)


def _q14_avg_sales_plan(st):
    branches = []
    for k, (fact, date_c, q_c, p_c) in enumerate((
        ("store_sales", "ss_sold_date_sk", "ss_quantity", "ss_list_price"),
        ("catalog_sales", "cs_sold_date_sk", "cs_quantity", "cs_list_price"),
        ("web_sales", "ws_sold_date_sk", "ws_quantity", "ws_list_price"),
    )):
        dt = F.project(
            [a("d_date_sk")],
            F.filter_(and_(F.binop("GreaterThanOrEqual", a("d_year"), i32(1998)),
                           F.binop("LessThanOrEqual", a("d_year"), i32(2000))),
                      F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
        )
        sl = F.scan(fact, [a(date_c), a(q_c), a(p_c)])
        j = join(st, dt, sl, [a("d_date_sk")], [a(date_c)])
        v = F.binop("Multiply", F.cast(a(q_c), "long"), a(p_c))
        branches.append(F.project([F.alias(v, "v", 900)], j))
    return two_stage(
        [], [(F.avg(ar("v", 900, "decimal(17,2)")), 901)],
        F.union(branches),
        result=[F.alias(ar("average_sales", 901, "decimal(21,6)"),
                        "average_sales", 902)],
    )


def _q14_cells_plan(st, fact, date_c, item_c, q_c, p_c, cross, avg_sub, year):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(and_(F.binop("EqualTo", a("d_year"), i32(year)),
                       F.binop("EqualTo", a("d_moy"), i32(11))),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"),
                                      a("d_moy")])),
    )
    it = F.scan("item", [a("i_item_sk"), a("i_brand_id"), a("i_class_id"),
                         a("i_category_id")])
    sl = F.scan(fact, [a(date_c), a(item_c), a(q_c), a(p_c)])
    j = join(st, dt, sl, [a("d_date_sk")], [a(date_c)])
    j = join(st, cross, j, [a("i_item_sk")], [a(item_c)], jt="LeftSemi",
             build_side="right")
    j = join(st, it, j, [a("i_item_sk")], [a(item_c)])
    v = F.binop("Multiply", F.cast(a(q_c), "long"), a(p_c))
    proj = F.project(
        [a("i_brand_id"), a("i_class_id"), a("i_category_id"),
         F.alias(v, "v", 910)],
        j,
    )
    agg = two_stage(
        [a("i_brand_id"), a("i_class_id"), a("i_category_id")],
        [(F.sum_(ar("v", 910, "decimal(17,2)")), 911), (F.count(), 912)],
        proj,
    )
    return F.filter_(
        F.binop("GreaterThan",
                F.cast(ar("sales", 911, "decimal(27,2)"), "double"),
                F.cast(avg_sub, "double")),
        agg,
    )


def test_spark_q14a(sess, data, strategy):
    cross = _q14_cross_items_plan(strategy)
    avg_plan = _q14_avg_sales_plan(strategy)
    sales = ar("sales", 911, "decimal(27,2)")
    number = ar("number_sales", 912, "long")
    branches = []
    for k, (name, fact, date_c, item_c, q_c, p_c) in enumerate((
        ("store", "store_sales", "ss_sold_date_sk", "ss_item_sk",
         "ss_quantity", "ss_list_price"),
        ("catalog", "catalog_sales", "cs_sold_date_sk", "cs_item_sk",
         "cs_quantity", "cs_list_price"),
        ("web", "web_sales", "ws_sold_date_sk", "ws_item_sk",
         "ws_quantity", "ws_list_price"),
    )):
        cells = _q14_cells_plan(strategy, fact, date_c, item_c, q_c, p_c,
                                cross, _scalar_subquery(avg_plan, 920 + k),
                                2002)
        branches.append(F.project(
            [F.alias(F.lit(name, "string"), "channel", 930),
             a("i_brand_id"), a("i_class_id"), a("i_category_id"),
             sales, number],
            cells,
        ))
    u = F.union(branches)
    chan = ar("channel", 930, "string")
    dims = [(chan, "string"), (a("i_brand_id"), "integer"),
            (a("i_class_id"), "integer"), (a("i_category_id"), "integer")]
    exp_attrs = [ar(["channel", "i_brand_id", "i_class_id",
                     "i_category_id"][k], 940 + k, dt_)
                 for k, (_, dt_) in enumerate(dims)]
    exp_gid = ar("g_id", 944, "integer")
    projections = []
    for level in range(4, -1, -1):
        row = [sales, number]
        for k, (e, dt_) in enumerate(dims):
            row.append(e if k < level else F.lit(None, dt_))
        row.append(F.lit(4 - level, "integer"))
        projections.append(row)
    expand = F.expand(projections, [sales, number] + exp_attrs + [exp_gid], u)
    agg = two_stage(
        exp_attrs + [exp_gid],
        [(F.sum_(sales), 950), (F.sum_(number), 951)],
        expand,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(e) for e in exp_attrs] + [F.sort_order(exp_gid)],
        [F.alias(exp_attrs[0], "channel", 960),
         F.alias(exp_attrs[1], "i_brand_id", 961),
         F.alias(exp_attrs[2], "i_class_id", 962),
         F.alias(exp_attrs[3], "i_category_id", 963),
         F.alias(exp_gid, "g_id", 964),
         F.alias(ar("sum_sales", 950, "decimal(37,2)"), "sum_sales", 965),
         F.alias(ar("sum_number_sales", 951, "long"),
                 "sum_number_sales", 966)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q14a(data)
    assert exp, "q14a oracle empty"
    n = len(got["channel"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["channel"][i], got["i_brand_id"][i], got["i_class_id"][i],
               got["i_category_id"][i])
        assert key in exp, key
        assert (got["sum_sales"][i], got["sum_number_sales"][i]) == exp[key], key
    from test_tpcds import _nf
    order = [tuple(_nf(got[c][i]) for c in
                   ("channel", "i_brand_id", "i_class_id", "i_category_id"))
             for i in range(n)]
    assert order == sorted(order)


def test_spark_q14b(sess, data, strategy):
    cross = _q14_cross_items_plan(strategy)
    avg_plan = _q14_avg_sales_plan(strategy)
    ty = _q14_cells_plan(strategy, "store_sales", "ss_sold_date_sk",
                         "ss_item_sk", "ss_quantity", "ss_list_price",
                         cross, _scalar_subquery(avg_plan, 920), 2002)
    ly = _q14_cells_plan(strategy, "store_sales", "ss_sold_date_sk",
                         "ss_item_sk", "ss_quantity", "ss_list_price",
                         cross, _scalar_subquery(avg_plan, 921), 2001)
    sales = ar("sales", 911, "decimal(27,2)")
    number = ar("number_sales", 912, "long")
    ly = F.project(
        [F.alias(a("i_brand_id"), "l_brand_id", 970),
         F.alias(a("i_class_id"), "l_class_id", 971),
         F.alias(a("i_category_id"), "l_category_id", 972),
         F.alias(sales, "last_sales", 973),
         F.alias(number, "last_number_sales", 974)],
        ly,
    )
    j = big_join(strategy, ty, ly,
                 [a("i_brand_id"), a("i_class_id"), a("i_category_id")],
                 [ar("l_brand_id", 970, "integer"),
                  ar("l_class_id", 971, "integer"),
                  ar("l_category_id", 972, "integer")])
    last_sales = ar("last_sales", 973, "decimal(27,2)")
    f = F.filter_(
        F.binop("GreaterThan", F.cast(sales, "double"),
                F.cast(last_sales, "double")),
        j,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(a("i_brand_id")), F.sort_order(a("i_class_id")),
         F.sort_order(a("i_category_id"))],
        [a("i_brand_id"), a("i_class_id"), a("i_category_id"),
         F.alias(sales, "sales", 980), F.alias(number, "number_sales", 981),
         F.alias(last_sales, "last_sales", 982),
         F.alias(ar("last_number_sales", 974, "long"),
                 "last_number_sales", 983)],
        f,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q14b(data)
    assert exp, "q14b oracle empty"
    rows_g = {
        (b, c, cat): (s_, ns, ls, lns) for b, c, cat, s_, ns, ls, lns in
        zip(got["i_brand_id"], got["i_class_id"], got["i_category_id"],
            got["sales"], got["number_sales"], got["last_sales"],
            got["last_number_sales"])
    }
    if len(exp) <= 100:
        assert rows_g == exp
    else:
        assert all(exp.get(k) == v for k, v in rows_g.items())


# ------------------------------------------------- q64 cross-year self-join

def _q64_cross_sales_plan(st, year):
    sl = F.scan("store_sales",
                [a("ss_item_sk"), a("ss_ticket_number"), a("ss_store_sk"),
                 a("ss_sold_date_sk"), a("ss_wholesale_cost"),
                 a("ss_list_price"), a("ss_coupon_amt")])
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(year)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    sl = join(st, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    sr = F.scan("store_returns", [a("sr_item_sk"), a("sr_ticket_number")])
    j = big_join(st, sl, sr, [a("ss_item_sk"), a("ss_ticket_number")],
                 [a("sr_item_sk"), a("sr_ticket_number")])
    it = F.project(
        [a("i_item_sk"), a("i_item_id")],
        F.filter_(
            in_(a("i_color"), "purple", "burlywood", "indian", "spring",
                "floral", "medium", "peach", "saddle", "navy", "slate"),
            F.scan("item", [a("i_item_sk"), a("i_item_id"), a("i_color")]),
        ),
    )
    j = join(st, it, j, [a("i_item_sk")], [a("ss_item_sk")])
    st2 = F.scan("store", [a("s_store_sk"), a("s_store_name"), a("s_zip")])
    j = join(st, st2, j, [a("s_store_sk")], [a("ss_store_sk")])
    return two_stage(
        [a("i_item_id"), a("s_store_name"), a("s_zip")],
        [(F.count(), 851), (F.sum_(a("ss_wholesale_cost")), 852),
         (F.sum_(a("ss_list_price")), 853), (F.sum_(a("ss_coupon_amt")), 854)],
        j,
    )


def test_spark_q64(sess, data, strategy):
    cnt = ar("cnt", 851, "long")
    s1 = ar("s1", 852, "decimal(17,2)")
    s2 = ar("s2", 853, "decimal(17,2)")
    s3 = ar("s3", 854, "decimal(17,2)")
    cs1 = _q64_cross_sales_plan(strategy, 2001)
    cs2 = F.project(
        [F.alias(a("i_item_id"), "r_item_id", 860),
         F.alias(a("s_store_name"), "r_store_name", 861),
         F.alias(a("s_zip"), "r_zip", 862),
         F.alias(cnt, "cnt2", 863), F.alias(s1, "s1_2", 864),
         F.alias(s2, "s2_2", 865), F.alias(s3, "s3_2", 866)],
        _q64_cross_sales_plan(strategy, 2002),
    )
    j = big_join(strategy, cs1, cs2,
                 [a("i_item_id"), a("s_store_name"), a("s_zip")],
                 [ar("r_item_id", 860, "string"),
                  ar("r_store_name", 861, "string"),
                  ar("r_zip", 862, "string")])
    cnt2 = ar("cnt2", 863, "long")
    f = F.filter_(F.binop("LessThanOrEqual", cnt2, cnt), j)
    plan = F.take_ordered(
        100,
        [F.sort_order(s1, asc=False), F.sort_order(a("i_item_id")),
         F.sort_order(a("s_store_name")), F.sort_order(a("s_zip"))],
        [a("i_item_id"), a("s_store_name"), a("s_zip"),
         F.alias(cnt, "cnt", 870), F.alias(s1, "s1", 871),
         F.alias(s2, "s2", 872), F.alias(s3, "s3", 873),
         F.alias(cnt2, "cnt2", 874),
         F.alias(ar("s1_2", 864, "decimal(17,2)"), "s1_2", 875),
         F.alias(ar("s2_2", 865, "decimal(17,2)"), "s2_2", 876),
         F.alias(ar("s3_2", 866, "decimal(17,2)"), "s3_2", 877)],
        f,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q64(data)
    assert exp, "q64 oracle empty"
    rows_g = {
        (i, st_, z): (c1, x, y, zz, c2, d, e, f_) for
        i, st_, z, c1, x, y, zz, c2, d, e, f_ in
        zip(got["i_item_id"], got["s_store_name"], got["s_zip"], got["cnt"],
            got["s1"], got["s2"], got["s3"], got["cnt2"], got["s1_2"],
            got["s2_2"], got["s3_2"])
    }
    if len(exp) <= 100:
        assert rows_g == exp
    else:
        assert all(exp.get(k) == v for k, v in rows_g.items())
    assert got["s1"] == sorted(got["s1"], reverse=True)


# ---------------------- q51: FULL OUTER of two cumulative-window streams

def _q51_chan(strategy, fact, date_c, item_c, price_c, px, b):
    """One channel's per-item daily running-sum stream: the FIRST
    running-frame (order-by default RANGE up->CURRENT ROW) window
    through the conversion layer."""
    dt = F.project(
        [a("d_date_sk"), a("d_date")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_date"), a("d_year")])),
    )
    sl = F.scan(fact, [a(date_c), a(item_c), a(price_c)])
    j = join(strategy, dt, sl, [a("d_date_sk")], [a(date_c)])
    agg = two_stage([a(item_c), a("d_date")], [(F.sum_(a(price_c)), b)], j)
    sales = ar("sales", b, "decimal(17,2)")
    ex = F.shuffle(F.hash_partitioning([a(item_c)], N_PARTS), agg)
    srt = F.sort([F.sort_order(a(item_c)), F.sort_order(a("d_date"))], ex,
                 global_=False)
    w = F.window(
        [F.window_expr(F.window_agg(F.sum_(sales)),
                       F.window_spec([a(item_c)], [F.sort_order(a("d_date"))]),
                       "cume", b + 1)],
        [a(item_c)], [F.sort_order(a("d_date"))], srt,
    )
    return F.project(
        [F.alias(a(item_c), f"{px}_item_sk", b + 2),
         F.alias(a("d_date"), f"{px}_date", b + 3),
         F.alias(ar("cume", b + 1, "decimal(27,2)"), f"{px}_cume", b + 4)],
        w,
    )


def test_spark_q51(sess, data, strategy):
    web = _q51_chan(strategy, "web_sales", "ws_sold_date_sk", "ws_item_sk",
                    "ws_sales_price", "w", 9001)
    store = _q51_chan(strategy, "store_sales", "ss_sold_date_sk", "ss_item_sk",
                      "ss_sales_price", "s", 9011)
    wi, wd = ar("w_item_sk", 9003), ar("w_date", 9004, "date")
    wc = ar("w_cume", 9005, "decimal(27,2)")
    si, sd = ar("s_item_sk", 9013), ar("s_date", 9014, "date")
    sc = ar("s_cume", 9015, "decimal(27,2)")
    j = big_join(strategy, web, store, [wi, wd], [si, sd], jt="FullOuter")
    item = F.alias(F.T(F.X + "Coalesce", [wi, si]), "item_sk", 9021)
    dd = F.alias(F.T(F.X + "Coalesce", [wd, sd]), "d_date", 9022)
    proj = F.project([item, dd, wc, sc], j)
    item_a, dd_a = ar("item_sk", 9021), ar("d_date", 9022, "date")
    ex = F.shuffle(F.hash_partitioning([item_a], N_PARTS), proj)
    srt = F.sort([F.sort_order(item_a), F.sort_order(dd_a)], ex, global_=False)
    # running maxes carry each channel's cumulative value across the
    # FULL OUTER join's null gaps
    w2 = F.window(
        [F.window_expr(F.window_agg(F.max_(wc)),
                       F.window_spec([item_a], [F.sort_order(dd_a)]),
                       "web_cumulative", 9023),
         F.window_expr(F.window_agg(F.max_(sc)),
                       F.window_spec([item_a], [F.sort_order(dd_a)]),
                       "store_cumulative", 9024)],
        [item_a], [F.sort_order(dd_a)], srt,
    )
    wcu = ar("web_cumulative", 9023, "decimal(27,2)")
    scu = ar("store_cumulative", 9024, "decimal(27,2)")
    filt = F.filter_(F.binop("GreaterThan", wcu, scu), w2)
    plan = F.take_ordered(
        100, [F.sort_order(item_a), F.sort_order(dd_a)],
        [item_a, dd_a, wcu, scu], filt,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q51(data)
    assert exp, "q51 oracle empty"
    n = len(got["item_sk"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["item_sk"][i], got["d_date"][i])
        assert key in exp, key
        assert (got["web_cumulative"][i], got["store_cumulative"][i]) == exp[key], key
    keys = list(zip(got["item_sk"], got["d_date"]))
    assert keys == sorted(keys)
    if len(exp) > 100:
        assert keys == sorted(exp)[:100]


# ------------------------- q44: rank-paired best/worst items by profit

def test_spark_q44(sess, data, strategy):
    """Two rank() windows (asc/desc) over per-item average profit above
    90% of a scalar-subquery baseline, joined ON THE RANK — the rank
    self-pairing + second item scan with fresh exprIds exercise window
    output flowing into join keys through conversion."""
    store = F.lit(4, "long")
    scan_cols = [a("ss_item_sk"), a("ss_net_profit"), a("ss_store_sk"),
                 a("ss_addr_sk")]
    base = F.project(
        [a("ss_item_sk"), a("ss_net_profit")],
        F.filter_(F.binop("EqualTo", a("ss_store_sk"), store),
                  F.scan("store_sales", scan_cols)),
    )
    per_item = two_stage([a("ss_item_sk")],
                         [(F.avg(a("ss_net_profit")), 9101)], base)
    rank_col = ar("rank_col", 9101, "decimal(11,6)")
    null_addr = F.project(
        [a("ss_net_profit")],
        F.filter_(and_(F.binop("EqualTo", a("ss_store_sk"), store),
                       F.binop("EqualTo", a("ss_addr_sk"), F.lit(-1, "long"))),
                  F.scan("store_sales", scan_cols)),
    )
    thr_plan = two_stage([], [(F.avg(a("ss_net_profit")), 9102)], null_addr)
    keep = F.filter_(
        F.binop(
            "GreaterThan", F.cast(rank_col, "double"),
            F.binop("Multiply", F.lit(0.9, "double"),
                    F.cast(_scalar_subquery(thr_plan, 9102), "double")),
        ),
        per_item,
    )
    single = F.shuffle(F.single_partition(), keep)

    def ranked(asc, item_alias, rnk_alias, b):
        o = [F.sort_order(rank_col, asc=asc)]
        srt = F.sort(o, single, global_=False)
        w = F.window(
            [F.window_expr(F.rank_fn([rank_col]), F.window_spec([], o),
                           "rnk", b)],
            [], o, srt,
        )
        f = F.filter_(F.binop("LessThanOrEqual", ar("rnk", b),
                              F.lit(10, "integer")), w)
        return F.project(
            [F.alias(a("ss_item_sk"), item_alias, b + 1),
             F.alias(ar("rnk", b), rnk_alias, b + 2)], f)

    asc = ranked(True, "best_sk", "rnk", 9103)
    desc = ranked(False, "worst_sk", "rnk_d", 9106)
    rnk_a, rnkd_a = ar("rnk", 9105, "integer"), ar("rnk_d", 9108, "integer")
    best_a, worst_a = ar("best_sk", 9104), ar("worst_sk", 9107)
    j = big_join(strategy, asc, desc, [rnk_a], [rnkd_a])
    i1 = F.scan("item", [a("i_item_sk"), a("i_item_id")])
    j = join(strategy, i1, j, [a("i_item_sk")], [best_a])
    i2sk, i2id = ar("i_item_sk", 9121), ar("i_item_id", 9122, "string")
    i2 = F.scan("item", [i2sk, i2id])
    j = join(strategy, i2, j, [i2sk], [worst_a])
    plan = F.take_ordered(
        100, [F.sort_order(rnk_a)],
        [rnk_a, F.alias(a("i_item_id"), "best_name", 9131),
         F.alias(i2id, "worst_name", 9132)], j)
    got = _execute_both(sess, plan)
    exp = O.oracle_q44(data)
    assert exp, "q44 oracle empty"
    rows = set(zip(got["rnk"], got["best_name"], got["worst_name"]))
    assert len(got["rnk"]) == min(len(exp), 100)
    assert rows == exp if len(exp) <= 100 else rows <= exp
    assert got["rnk"] == sorted(got["rnk"])


# ----------------- q9: five CASE buckets over 15 scalar subqueries

def test_spark_q9(sess, data, strategy):
    """Fifteen ScalarSubqueries (count/avg/avg per quantity band)
    inside five CaseWhen branches, projected over the 1-row reason
    slice — the heaviest driver-side subquery resolution shape in the
    matrix (≙ SparkScalarSubqueryWrapperExpr evaluation)."""
    from blaze_tpu.tpcds.queries import Q9_THRESHOLDS

    if strategy == "smj":
        pytest.skip("no joins in q9: the strategy axis is vacuous")

    def band_plan(lo, hi, agg_fn, rid):
        band = F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("ss_quantity"), i32(lo)),
                 F.binop("LessThanOrEqual", a("ss_quantity"), i32(hi))),
            F.scan("store_sales", [a("ss_quantity"), a("ss_ext_discount_amt"),
                                   a("ss_net_profit")]),
        )
        return two_stage([], [(agg_fn, rid)], band)

    exprs = []
    for b, thresh in enumerate(Q9_THRESHOLDS):
        lo, hi = 20 * b + 1, 20 * (b + 1)
        rid = 9200 + b * 10
        cnt = _scalar_subquery(band_plan(lo, hi, F.count(), rid), rid)
        avg_disc = _scalar_subquery(
            band_plan(lo, hi, F.avg(a("ss_ext_discount_amt")), rid + 1), rid + 1)
        avg_profit = _scalar_subquery(
            band_plan(lo, hi, F.avg(a("ss_net_profit")), rid + 2), rid + 2)
        case = F.T(
            F.X + "CaseWhen",
            [F.binop("GreaterThan", cnt, F.lit(thresh, "long")),
             avg_disc, avg_profit],
        )
        exprs.append(F.alias(case, f"bucket{b + 1}", 9300 + b))
    src = F.filter_(F.binop("EqualTo", a("r_reason_sk"), F.lit(1, "long")),
                    F.scan("reason", [a("r_reason_sk"), a("r_reason_desc")]))
    plan = F.project(exprs, src)
    got = _execute_both(sess, plan)
    exp = O.oracle_q9(data, Q9_THRESHOLDS)
    assert len(got["bucket1"]) == 1
    for b in range(len(Q9_THRESHOLDS)):
        g = got[f"bucket{b + 1}"][0]
        assert abs(g - exp[b]) <= 1, (b, g, exp[b])


# --------------------------------------- q3 brand report (ticket slice)

def test_spark_q3(ticket_sess, ticket_data, strategy):
    """Star join + brand rollup (manufact 128 only appears at the 0.01
    datagen slice, same as test_tpcds.test_q3)."""
    dt = F.project(
        [a("d_date_sk"), a("d_year")],
        F.filter_(F.binop("EqualTo", a("d_moy"), i32(11)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"), a("d_moy")])),
    )
    sales = F.scan("store_sales", [a("ss_sold_date_sk"), a("ss_item_sk"),
                                   a("ss_ext_sales_price")])
    it = F.project(
        [a("i_item_sk"), a("i_brand_id"), a("i_brand")],
        F.filter_(F.binop("EqualTo", a("i_manufact_id"), i32(128)),
                  F.scan("item", [a("i_item_sk"), a("i_brand_id"), a("i_brand"),
                                  a("i_manufact_id")])),
    )
    j = join(strategy, dt, sales, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("ss_item_sk")])
    agg = two_stage([a("d_year"), a("i_brand_id"), a("i_brand")],
                    [(F.sum_(a("ss_ext_sales_price")), 501)], j)
    sum_agg = ar("sum_agg", 501, "decimal(17,2)")
    plan = F.take_ordered(
        100,
        [F.sort_order(a("d_year")), F.sort_order(sum_agg, asc=False),
         F.sort_order(a("i_brand_id"))],
        [F.alias(a("d_year"), "d_year", 510),
         F.alias(a("i_brand_id"), "brand_id", 511),
         F.alias(a("i_brand"), "brand", 512),
         F.alias(sum_agg, "sum_agg", 513)],
        agg,
    )
    got = _execute_both(ticket_sess, plan)
    exp = O.oracle_q3(ticket_data)
    assert exp, "q3 oracle matched no rows"
    _check_brand_report(got, exp, "sum_agg")
    assert got["d_year"] == sorted(got["d_year"])


# --------------------------- q12/q20 class-share reports (q98's twins)

def _class_share_plan(st, fact, date_c, item_c, price_c):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("d_date"),
                         F.lit("1999-02-22", "date")),
                 F.binop("LessThanOrEqual", a("d_date"),
                         F.lit("1999-03-24", "date"))),
            F.scan("date_dim", [a("d_date_sk"), a("d_date")]),
        ),
    )
    it = F.project(
        [a("i_item_sk"), a("i_item_id"), a("i_item_desc"), a("i_category"),
         a("i_class"), a("i_current_price")],
        F.filter_(
            in_(a("i_category"), "Sports", "Books", "Home"),
            F.scan("item", [a("i_item_sk"), a("i_item_id"), a("i_item_desc"),
                            a("i_class"), a("i_category"), a("i_current_price")]),
        ),
    )
    sales = F.scan(fact, [a(date_c), a(item_c), a(price_c)])
    j = join(st, dt, sales, [a("d_date_sk")], [a(date_c)])
    j = join(st, it, j, [a("i_item_sk")], [a(item_c)])
    agg = two_stage(
        [a("i_item_id"), a("i_item_desc"), a("i_category"), a("i_class"),
         a("i_current_price")],
        [(F.sum_(a(price_c)), 501)],
        j,
    )
    itemrev = ar("itemrevenue", 501, "decimal(17,2)")
    single = F.shuffle(F.single_partition(), agg)
    pre = F.sort([F.sort_order(a("i_class"))], single)
    w = F.window(
        [F.window_expr(
            F.window_agg(F.sum_(itemrev)),
            F.window_spec([a("i_class")], [], F.window_frame("up", "uf", row=True)),
            "class_revenue", 502)],
        [a("i_class")],
        [],
        pre,
    )
    class_rev = ar("class_revenue", 502, "decimal(27,2)")
    ratio = F.binop(
        "Divide",
        F.binop("Multiply", F.cast(itemrev, "double"), F.lit(100.0, "double")),
        F.cast(class_rev, "double"),
    )
    proj = F.project(
        [a("i_item_id"), a("i_item_desc"), a("i_category"), a("i_class"),
         a("i_current_price"), itemrev,
         F.alias(ratio, "revenueratio", 510)],
        w,
    )
    ratio_o = ar("revenueratio", 510, "double")
    sorted_ = F.sort(
        [F.sort_order(a("i_category")), F.sort_order(a("i_class")),
         F.sort_order(a("i_item_id")), F.sort_order(a("i_item_desc")),
         F.sort_order(ratio_o)],
        F.shuffle(F.single_partition(), proj),
    )
    return F.project(
        [F.alias(a("i_item_id"), "i_item_id", 520),
         F.alias(a("i_item_desc"), "i_item_desc", 521),
         F.alias(a("i_category"), "i_category", 522),
         F.alias(a("i_class"), "i_class", 523),
         F.alias(a("i_current_price"), "i_current_price", 524),
         F.alias(itemrev, "itemrevenue", 525),
         F.alias(ratio_o, "revenueratio", 526)],
        sorted_,
    )


def test_spark_q20(sess, data, strategy):
    plan = _class_share_plan(strategy, "catalog_sales", "cs_sold_date_sk",
                             "cs_item_sk", "cs_ext_sales_price")
    got = _execute_both(sess, plan)
    _check_class_share(got, O.oracle_q20(data))


def test_spark_q12(sess, data, strategy):
    plan = _class_share_plan(strategy, "web_sales", "ws_sold_date_sk",
                             "ws_item_sk", "ws_ext_sales_price")
    got = _execute_both(sess, plan)
    _check_class_share(got, O.oracle_q12(data))


# ------------------------------ q37/q82 inventory price-band items

def _inv_price_plan(st, fact, item_c):
    """Items in a price band with healthy inventory that also sold in
    the channel: bcast date window, strategy-shaped item<->inventory
    join, LEFT SEMI against the fact, grouping-only (DISTINCT) agg."""
    dec = "decimal(7,2)"
    it = F.project(
        [a("i_item_sk"), a("i_item_id"), a("i_item_desc"), a("i_current_price")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("i_current_price"),
                         F.lit("30", dec)),
                 F.binop("LessThanOrEqual", a("i_current_price"),
                         F.lit("60", dec))),
            F.scan("item", [a("i_item_sk"), a("i_item_id"), a("i_item_desc"),
                            a("i_current_price")]),
        ),
    )
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("d_date"),
                         F.lit("2000-02-01", "date")),
                 F.binop("LessThan", a("d_date"), F.lit("2000-04-01", "date"))),
            F.scan("date_dim", [a("d_date_sk"), a("d_date")]),
        ),
    )
    inv = F.project(
        [a("inv_date_sk"), a("inv_item_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("inv_quantity_on_hand"), i32(100)),
                 F.binop("LessThanOrEqual", a("inv_quantity_on_hand"), i32(500))),
            F.scan("inventory", [a("inv_date_sk"), a("inv_item_sk"),
                                 a("inv_quantity_on_hand")]),
        ),
    )
    j = join(st, dt, inv, [a("d_date_sk")], [a("inv_date_sk")])
    j = join(st, it, j, [a("i_item_sk")], [a("inv_item_sk")])
    sold = F.scan(fact, [a(item_c)])
    j = join(st, sold, j, [a(item_c)], [a("i_item_sk")], jt="LeftSemi",
             build_side="right")
    agg = distinct([a("i_item_id"), a("i_item_desc"), a("i_current_price")], j)
    return F.take_ordered(
        100, [F.sort_order(a("i_item_id"))],
        [F.alias(a("i_item_id"), "i_item_id", 530),
         F.alias(a("i_item_desc"), "i_item_desc", 531),
         F.alias(a("i_current_price"), "i_current_price", 532)],
        agg,
    )


def test_spark_q37(sess, data, strategy):
    got = _execute_both(sess, _inv_price_plan(strategy, "catalog_sales",
                                              "cs_item_sk"))
    _check_inv_price(got, O.oracle_q37(data))


def test_spark_q82(sess, data, strategy):
    got = _execute_both(sess, _inv_price_plan(strategy, "store_sales",
                                              "ss_item_sk"))
    _check_inv_price(got, O.oracle_q82(data))


# ------------------- q32/q92 excess discount (decorrelated per-item avg)

def _excess_discount_plan(st, fact, date_c, item_c, amt_c):
    from blaze_tpu.tpcds.queries import Q32_MFG_MAX

    dt = F.project(
        [a("d_date_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("d_date"),
                         F.lit("2000-01-27", "date")),
                 F.binop("LessThanOrEqual", a("d_date"),
                         F.lit("2000-04-26", "date"))),
            F.scan("date_dim", [a("d_date_sk"), a("d_date")]),
        ),
    )
    sl = F.scan(fact, [a(date_c), a(item_c), a(amt_c)])
    j = join(st, dt, sl, [a("d_date_sk")], [a(date_c)])
    src = F.project([F.alias(a(item_c), "avg_item_sk", 520), a(amt_c)], j)
    per_item = two_stage([ar("avg_item_sk", 520, "long")],
                         [(F.avg(a(amt_c)), 501)], src)
    avg_amt = ar("avg_amt", 501, "decimal(11,6)")
    jj = join(st, per_item, j, [ar("avg_item_sk", 520, "long")], [a(item_c)])
    keep = F.binop(
        "GreaterThan", F.cast(a(amt_c), "double"),
        F.binop("Multiply", F.cast(avg_amt, "double"), F.lit(1.3, "double")))
    f = F.filter_(keep, jj)
    it_p = F.project(
        [a("i_item_sk")],
        F.filter_(F.binop("LessThanOrEqual", a("i_manufact_id"),
                          i32(Q32_MFG_MAX)),
                  F.scan("item", [a("i_item_sk"), a("i_manufact_id")])),
    )
    f = join(st, it_p, f, [a("i_item_sk")], [a(item_c)], jt="LeftSemi",
             build_side="right")
    agg = two_stage([], [(F.sum_(a(amt_c)), 502)], f)
    return F.project(
        [F.alias(ar("excess", 502, "decimal(17,2)"), "excess_discount", 530)],
        agg,
    )


def test_spark_q32(sess, data, strategy):
    got = _execute_both(sess, _excess_discount_plan(
        strategy, "catalog_sales", "cs_sold_date_sk", "cs_item_sk",
        "cs_ext_discount_amt"))
    exp = O.oracle_q32(data)
    assert exp is not None, "q32 slice matched no rows"
    assert got["excess_discount"] == [exp]


def test_spark_q92(sess, data, strategy):
    got = _execute_both(sess, _excess_discount_plan(
        strategy, "web_sales", "ws_sold_date_sk", "ws_item_sk",
        "ws_ext_discount_amt"))
    exp = O.oracle_q92(data)
    assert exp is not None, "q92 slice matched no rows"
    assert got["excess_discount"] == [exp]


# -------------------------- q15 OR-of-unlike-predicates zip report

def test_spark_q15(sess, data, strategy):
    from blaze_tpu.tpcds.queries import Q15_ZIPS

    dt = F.project(
        [a("d_date_sk")],
        F.filter_(and_(F.binop("EqualTo", a("d_qoy"), i32(2)),
                       F.binop("EqualTo", a("d_year"), i32(2001))),
                  F.scan("date_dim", [a("d_date_sk"), a("d_qoy"), a("d_year")])),
    )
    cust = F.scan("customer", [a("c_customer_sk"), a("c_current_addr_sk")])
    ca = F.scan("customer_address",
                [a("ca_address_sk"), a("ca_zip"), a("ca_state")])
    sl = F.scan("catalog_sales",
                [a("cs_sold_date_sk"), a("cs_bill_customer_sk"),
                 a("cs_sales_price")])
    j = join(strategy, dt, sl, [a("d_date_sk")], [a("cs_sold_date_sk")])
    j = join(strategy, cust, j, [a("c_customer_sk")], [a("cs_bill_customer_sk")])
    j = join(strategy, ca, j, [a("ca_address_sk")], [a("c_current_addr_sk")])
    zip5 = F.T(F.X + "Substring", [a("ca_zip"), i32(1), i32(5)])
    keep = or_(
        in_(zip5, *Q15_ZIPS),
        in_(a("ca_state"), "TN", "GA", "OH"),
        F.binop("GreaterThan", a("cs_sales_price"),
                F.lit("250", "decimal(7,2)")),
    )
    f = F.filter_(keep, j)
    agg = two_stage([a("ca_zip")], [(F.sum_(a("cs_sales_price")), 501)], f)
    plan = F.take_ordered(
        100, [F.sort_order(a("ca_zip"))],
        [F.alias(a("ca_zip"), "ca_zip", 510),
         F.alias(ar("sum_price", 501, "decimal(17,2)"), "sum_price", 511)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q15(data)
    assert exp, "q15 oracle matched no rows"
    rows = dict(zip(got["ca_zip"], got["sum_price"]))
    for k, v in rows.items():
        assert exp.get(k) == v, k
    assert len(rows) == min(len(exp), 100)
    assert got["ca_zip"] == sorted(got["ca_zip"])


# ---------------- q88/q90/q61 scalar-subquery cross-join one-row reports

def test_spark_q88(sess, data, strategy):
    """Eight half-hour store traffic counts: the spec's cross join of
    eight scalar COUNT subqueries, each a 3-join star under the
    strategy shape, resolved driver-side."""
    hd = F.project(
        [a("hd_demo_sk")],
        F.filter_(
            or_(and_(F.binop("EqualTo", a("hd_dep_count"), i32(4)),
                     F.binop("LessThanOrEqual", a("hd_vehicle_count"), i32(6))),
                and_(F.binop("EqualTo", a("hd_dep_count"), i32(2)),
                     F.binop("LessThanOrEqual", a("hd_vehicle_count"), i32(4))),
                and_(F.binop("EqualTo", a("hd_dep_count"), i32(0)),
                     F.binop("LessThanOrEqual", a("hd_vehicle_count"), i32(2)))),
            F.scan("household_demographics",
                   [a("hd_demo_sk"), a("hd_dep_count"), a("hd_vehicle_count")]),
        ),
    )
    st_p = F.project(
        [a("s_store_sk")],
        F.filter_(F.binop("EqualTo", a("s_store_name"), s("ese")),
                  F.scan("store", [a("s_store_sk"), a("s_store_name")])),
    )
    exprs = []
    for k in range(8):
        h, half = divmod(k + 17, 2)
        tpred = (F.binop("GreaterThanOrEqual", a("t_minute"), i32(30)) if half
                 else F.binop("LessThan", a("t_minute"), i32(30)))
        td = F.project(
            [a("t_time_sk")],
            F.filter_(and_(F.binop("EqualTo", a("t_hour"), i32(h)), tpred),
                      F.scan("time_dim", [a("t_time_sk"), a("t_hour"),
                                          a("t_minute")])),
        )
        sl = F.scan("store_sales", [a("ss_sold_time_sk"), a("ss_hdemo_sk"),
                                    a("ss_store_sk")])
        j = join(strategy, td, sl, [a("t_time_sk")], [a("ss_sold_time_sk")])
        j = join(strategy, hd, j, [a("hd_demo_sk")], [a("ss_hdemo_sk")])
        j = join(strategy, st_p, j, [a("s_store_sk")], [a("ss_store_sk")])
        cnt_plan = two_stage([], [(F.count(), 601 + k)], j)
        exprs.append(F.alias(
            _scalar_subquery(cnt_plan, 601 + k),
            f"h{h}_{30 if half else 0}", 620 + k))
    src = F.filter_(F.binop("EqualTo", a("r_reason_sk"), F.lit(1, "long")),
                    F.scan("reason", [a("r_reason_sk")]))
    got = _execute_both(sess, F.project(exprs, src))
    exp = O.oracle_q88(data)
    row = [got[k][0] for k in got]
    assert row == exp, (row, exp)
    assert sum(exp) > 0, "q88 slice matched no rows"


def test_spark_q90(sess, data, strategy):
    """AM/PM web-sales count ratio: two scalar subqueries + CaseWhen
    zero guard."""
    wp = F.project(
        [a("wp_web_page_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("wp_char_count"), i32(2000)),
                 F.binop("LessThanOrEqual", a("wp_char_count"), i32(6000))),
            F.scan("web_page", [a("wp_web_page_sk"), a("wp_char_count")]),
        ),
    )

    def half_count(lo, hi, rid):
        td = F.project(
            [a("t_time_sk")],
            F.filter_(
                and_(F.binop("GreaterThanOrEqual", a("t_hour"), i32(lo)),
                     F.binop("LessThanOrEqual", a("t_hour"), i32(hi))),
                F.scan("time_dim", [a("t_time_sk"), a("t_hour")]),
            ),
        )
        ws = F.scan("web_sales", [a("ws_sold_time_sk"), a("ws_web_page_sk")])
        j = join(strategy, td, ws, [a("t_time_sk")], [a("ws_sold_time_sk")])
        j = join(strategy, wp, j, [a("wp_web_page_sk")], [a("ws_web_page_sk")])
        return _scalar_subquery(two_stage([], [(F.count(), rid)], j), rid)

    am = half_count(8, 9, 651)
    pm = half_count(19, 20, 652)
    amf = F.cast(am, "double")
    pmf = F.cast(pm, "double")
    den = F.T(F.X + "CaseWhen",
              [F.binop("GreaterThan", pmf, F.lit(0.0, "double")), pmf,
               F.lit(1.0, "double")])
    one_row = two_stage([], [(F.count(), 653)],
                        F.scan("web_page", [a("wp_web_page_sk")]))
    plan = F.project(
        [F.alias(amf, "am_count", 660),
         F.alias(pmf, "pm_count", 661),
         F.alias(F.binop("Divide", amf, den), "am_pm_ratio", 662)],
        one_row,
    )
    got = _execute_both(sess, plan)
    am_e, pm_e, ratio_e = O.oracle_q90(data)
    assert got["am_count"] == [float(am_e)]
    assert got["pm_count"] == [float(pm_e)]
    assert abs(got["am_pm_ratio"][0] - ratio_e) < 1e-12


def test_spark_q61(ticket_sess, ticket_data, strategy):
    """Promotional vs total revenue: two 4/5-join scalar-subquery
    aggregates (LEFT SEMI address filter inside) and their ratio."""
    def revenue(with_promo, rid):
        dt = F.project(
            [a("d_date_sk")],
            F.filter_(and_(F.binop("EqualTo", a("d_year"), i32(1998)),
                           F.binop("EqualTo", a("d_moy"), i32(11))),
                      F.scan("date_dim", [a("d_date_sk"), a("d_year"),
                                          a("d_moy")])),
        )
        st_p = F.scan("store", [a("s_store_sk")])
        it = F.project(
            [a("i_item_sk")],
            F.filter_(F.binop("EqualTo", a("i_category"), s("Jewelry")),
                      F.scan("item", [a("i_item_sk"), a("i_category")])),
        )
        ca = F.project(
            [a("ca_address_sk")],
            F.filter_(F.binop("EqualTo", a("ca_gmt_offset"),
                              F.lit("-5", "decimal(5,2)")),
                      F.scan("customer_address",
                             [a("ca_address_sk"), a("ca_gmt_offset")])),
        )
        cust = F.scan("customer", [a("c_customer_sk"), a("c_current_addr_sk")])
        cust = join(strategy, ca, cust, [a("ca_address_sk")],
                    [a("c_current_addr_sk")], jt="LeftSemi",
                    build_side="right")
        sl = F.scan("store_sales",
                    [a("ss_sold_date_sk"), a("ss_store_sk"), a("ss_item_sk"),
                     a("ss_customer_sk"), a("ss_promo_sk"),
                     a("ss_ext_sales_price")])
        j = join(strategy, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
        j = join(strategy, st_p, j, [a("s_store_sk")], [a("ss_store_sk")])
        j = join(strategy, it, j, [a("i_item_sk")], [a("ss_item_sk")])
        j = join(strategy, cust, j, [a("c_customer_sk")], [a("ss_customer_sk")])
        if with_promo:
            pr = F.project(
                [a("p_promo_sk")],
                F.filter_(or_(F.binop("EqualTo", a("p_channel_email"), s("Y")),
                              F.binop("EqualTo", a("p_channel_event"), s("Y"))),
                          F.scan("promotion", [a("p_promo_sk"),
                                               a("p_channel_email"),
                                               a("p_channel_event")])),
            )
            j = join(strategy, pr, j, [a("p_promo_sk")], [a("ss_promo_sk")])
        return _scalar_subquery(
            two_stage([], [(F.sum_(a("ss_ext_sales_price")), rid)], j), rid)

    promo = revenue(True, 671)
    total = revenue(False, 672)
    ratio = F.binop(
        "Divide",
        F.binop("Multiply", F.cast(promo, "double"), F.lit(100.0, "double")),
        F.cast(total, "double"))
    src = F.filter_(F.binop("EqualTo", a("r_reason_sk"), F.lit(1, "long")),
                    F.scan("reason", [a("r_reason_sk")]))
    plan = F.project(
        [F.alias(promo, "promotions", 680),
         F.alias(total, "total", 681),
         F.alias(ratio, "promo_pct", 682)],
        src,
    )
    got = _execute_both(ticket_sess, plan)
    promo_e, total_e = O.oracle_q61(ticket_data)
    assert total_e > 0, "q61 slice matched no rows"
    assert got["promotions"] == [promo_e]
    assert got["total"] == [total_e]
    exp_pct = (promo_e / 100.0) * 100.0 / (total_e / 100.0)
    assert abs(got["promo_pct"][0] - exp_pct) < 1e-9


# ----------------------- q41 manufact EXISTS rewritten as semi join

def test_spark_q41(sess, data, strategy):
    combo = or_(
        and_(in_(a("i_color"), "powder", "navy"),
             in_(a("i_units"), "Each", "Dozen")),
        and_(in_(a("i_color"), "peach", "saddle"),
             in_(a("i_units"), "Case", "Pallet")),
    )
    qual = F.project(
        [F.alias(a("i_manufact"), "qual_manufact", 690)],
        F.filter_(combo, F.scan("item", [a("i_manufact"), a("i_color"),
                                         a("i_units")])),
    )
    qm = ar("qual_manufact", 690, "string")
    manufacts = distinct([qm], qual)
    i1 = F.project(
        [a("i_manufact"), a("i_item_id")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("i_manufact_id"), i32(50)),
                 F.binop("LessThanOrEqual", a("i_manufact_id"), i32(120))),
            F.scan("item", [a("i_manufact"), a("i_item_id"),
                            a("i_manufact_id")]),
        ),
    )
    j = join(strategy, manufacts, i1, [qm], [a("i_manufact")], jt="LeftSemi",
             build_side="right")
    dis = distinct([a("i_item_id")], F.project([a("i_item_id")], j))
    plan = F.take_ordered(
        100, [F.sort_order(a("i_item_id"))],
        [F.alias(a("i_item_id"), "i_item_id", 695)], dis)
    got = _execute_both(sess, plan)
    exp = O.oracle_q41(data)
    assert exp, "q41 oracle empty"
    assert got["i_item_id"] == exp[:100]


# -------------------- q45 zip-list OR hot-item-subquery web revenue

def test_spark_q45(sess, data, strategy):
    """The item IN-subquery is evaluated driver-side into literals
    (the engine's q45 does the same via _collect_column)."""
    import numpy as np

    hot_sks = (2, 3, 5, 7, 11, 13, 17, 19, 23, 29)
    ids, lens = data["item"]["i_item_id"]
    sks = data["item"]["i_item_sk"][0]
    hot_ids = sorted({
        bytes(ids[i][:lens[i]]).decode()
        for i in range(sks.shape[0]) if int(sks[i]) in hot_sks})
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(and_(F.binop("EqualTo", a("d_year"), i32(2000)),
                       F.binop("EqualTo", a("d_qoy"), i32(2))),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"), a("d_qoy")])),
    )
    ws = F.scan("web_sales", [a("ws_sold_date_sk"), a("ws_item_sk"),
                              a("ws_bill_customer_sk"), a("ws_sales_price")])
    j = join(strategy, dt, ws, [a("d_date_sk")], [a("ws_sold_date_sk")])
    cu = F.scan("customer", [a("c_customer_sk"), a("c_current_addr_sk")])
    j = join(strategy, cu, j, [a("c_customer_sk")], [a("ws_bill_customer_sk")])
    ca = F.scan("customer_address", [a("ca_address_sk"), a("ca_city"),
                                     a("ca_zip")])
    j = join(strategy, ca, j, [a("ca_address_sk")], [a("c_current_addr_sk")])
    it = F.scan("item", [a("i_item_sk"), a("i_item_id")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("ws_item_sk")])
    zips = ("35000", "35137", "60031", "60062", "60093")
    zip5 = F.T(F.X + "Substring", [a("ca_zip"), i32(1), i32(5)])
    pred = in_(zip5, *zips)
    if hot_ids:
        pred = or_(pred, in_(a("i_item_id"), *hot_ids))
    f = F.filter_(pred, j)
    agg = two_stage([a("ca_zip"), a("ca_city")],
                    [(F.sum_(a("ws_sales_price")), 501)], f)
    plan = F.take_ordered(
        100, [F.sort_order(a("ca_zip")), F.sort_order(a("ca_city"))],
        [F.alias(a("ca_zip"), "ca_zip", 510),
         F.alias(a("ca_city"), "ca_city", 511),
         F.alias(ar("sum_sales", 501, "decimal(17,2)"), "sum_sales", 512)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q45(data)
    assert exp, "q45 oracle empty"
    n = len(got["ca_zip"])
    assert n == min(len(exp), 100)
    rows = {(got["ca_zip"][i], got["ca_city"][i]): got["sum_sales"][i]
            for i in range(n)}
    assert rows == exp if len(exp) <= 100 else all(
        exp.get(k) == v for k, v in rows.items())


# -------------- q76 missing-dimension-key channel union (sentinel FKs)

def test_spark_q76(sess, data, strategy):
    dt = F.scan("date_dim", [a("d_date_sk"), a("d_year"), a("d_qoy")])
    it = F.scan("item", [a("i_item_sk"), a("i_category")])

    def channel(fact, date_c, item_c, null_c, price_c, name):
        f = F.filter_(F.binop("EqualTo", a(null_c), F.lit(-1, "long")),
                      F.scan(fact, [a(date_c), a(item_c), a(null_c),
                                    a(price_c)]))
        j = join(strategy, dt, f, [a("d_date_sk")], [a(date_c)])
        j = join(strategy, it, j, [a("i_item_sk")], [a(item_c)])
        return F.project(
            [F.alias(F.lit(name, "string"), "channel", 740),
             F.alias(F.lit(null_c, "string"), "col_name", 741),
             a("d_year"), a("d_qoy"), a("i_category"),
             F.alias(a(price_c), "ext_sales_price", 742)],
            j,
        )

    u = F.union([
        channel("store_sales", "ss_sold_date_sk", "ss_item_sk",
                "ss_customer_sk", "ss_ext_sales_price", "store"),
        channel("web_sales", "ws_sold_date_sk", "ws_item_sk",
                "ws_promo_sk", "ws_ext_sales_price", "web"),
        channel("catalog_sales", "cs_sold_date_sk", "cs_item_sk",
                "cs_bill_customer_sk", "cs_ext_sales_price", "catalog"),
    ])
    groups = [ar("channel", 740, "string"), ar("col_name", 741, "string"),
              a("d_year"), a("d_qoy"), a("i_category")]
    agg = two_stage(
        groups,
        [(F.count(), 501), (F.sum_(ar("ext_sales_price", 742,
                                      "decimal(7,2)")), 502)],
        u,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(g) for g in groups],
        [F.alias(ar("channel", 740, "string"), "channel", 750),
         F.alias(ar("col_name", 741, "string"), "col_name", 751),
         F.alias(a("d_year"), "d_year", 752),
         F.alias(a("d_qoy"), "d_qoy", 753),
         F.alias(a("i_category"), "i_category", 754),
         F.alias(ar("sales_cnt", 501, "long"), "sales_cnt", 755),
         F.alias(ar("sales_amt", 502, "decimal(17,2)"), "sales_amt", 756)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q76(data)
    assert exp, "q76 oracle empty"
    n = len(got["channel"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["channel"][i], got["col_name"][i], got["d_year"][i],
               got["d_qoy"][i], got["i_category"][i])
        assert key in exp, key
        assert (got["sales_cnt"][i], got["sales_amt"][i]) == exp[key], key


# --------------- q33/q56/q60 three-channel union by filtered item set

def _channel_by_item_plan(st, fact, date_c, item_c, addr_c, price_c, *,
                          group_col, gdtype, item_filter, year, moy):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(and_(F.binop("EqualTo", a("d_year"), i32(year)),
                       F.binop("EqualTo", a("d_moy"), i32(moy))),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"), a("d_moy")])),
    )
    ca = F.project(
        [a("ca_address_sk")],
        F.filter_(F.binop("EqualTo", a("ca_gmt_offset"),
                          F.lit("-5", "decimal(5,2)")),
                  F.scan("customer_address",
                         [a("ca_address_sk"), a("ca_gmt_offset")])),
    )
    ids = distinct(
        [ar("id_set", 760, gdtype)],
        F.project([F.alias(a(group_col), "id_set", 760)],
                  F.filter_(item_filter,
                            F.scan("item", [a(group_col), a("i_category"),
                                            a("i_color")]))),
    )
    it = F.scan("item", [a("i_item_sk"), a(group_col)])
    it_f = join(st, ids, it, [ar("id_set", 760, gdtype)], [a(group_col)],
                jt="LeftSemi", build_side="right")
    sl = F.scan(fact, [a(date_c), a(item_c), a(addr_c), a(price_c)])
    j = join(st, dt, sl, [a("d_date_sk")], [a(date_c)])
    j = join(st, ca, j, [a("ca_address_sk")], [a(addr_c)])
    j = join(st, it_f, j, [a("i_item_sk")], [a(item_c)])
    return F.project(
        [a(group_col), F.alias(a(price_c), "sales_price", 761)], j)


def _three_channel_union_plan(st, *, group_col, gdtype, item_filter, year,
                              moy):
    arms = [
        _channel_by_item_plan(st, s_, d_, i_, ad, p_, group_col=group_col,
                              gdtype=gdtype, item_filter=item_filter,
                              year=year, moy=moy)
        for s_, d_, i_, ad, p_ in [
            ("store_sales", "ss_sold_date_sk", "ss_item_sk", "ss_addr_sk",
             "ss_ext_sales_price"),
            ("catalog_sales", "cs_sold_date_sk", "cs_item_sk",
             "cs_bill_addr_sk", "cs_ext_sales_price"),
            ("web_sales", "ws_sold_date_sk", "ws_item_sk", "ws_bill_addr_sk",
             "ws_ext_sales_price"),
        ]
    ]
    u = F.union(arms)
    agg = two_stage(
        [a(group_col)],
        [(F.sum_(ar("sales_price", 761, "decimal(7,2)")), 501)], u)
    total = ar("total_sales", 501, "decimal(17,2)")
    return F.take_ordered(
        100, [F.sort_order(total), F.sort_order(a(group_col))],
        [F.alias(a(group_col), group_col, 770),
         F.alias(total, "total_sales", 771)],
        agg,
    )


def test_spark_q33(sess, data, strategy):
    plan = _three_channel_union_plan(
        strategy, group_col="i_manufact_id", gdtype="integer",
        item_filter=F.binop("EqualTo", a("i_category"), s("Electronics")),
        year=1998, moy=5)
    got = _execute_both(sess, plan)
    exp = O.oracle_q33(data)
    rows = dict(zip(got["i_manufact_id"], got["total_sales"]))
    assert rows, "q33 returned no rows"
    for k, v in rows.items():
        assert exp.get(k) == v, k
    assert len(rows) == min(len(exp), 100)


def test_spark_q56(sess, data, strategy):
    plan = _three_channel_union_plan(
        strategy, group_col="i_item_id", gdtype="string",
        item_filter=in_(a("i_color"), "slate", "blanched", "burnished"),
        year=2000, moy=2)
    got = _execute_both(sess, plan)
    exp = O.oracle_q56(data)
    rows = dict(zip(got["i_item_id"], got["total_sales"]))
    assert rows, "q56 returned no rows"
    for k, v in rows.items():
        assert exp.get(k) == v, k
    assert len(rows) == min(len(exp), 100)


def test_spark_q60(sess, data, strategy):
    plan = _three_channel_union_plan(
        strategy, group_col="i_item_id", gdtype="string",
        item_filter=F.binop("EqualTo", a("i_category"), s("Music")),
        year=1999, moy=9)
    got = _execute_both(sess, plan)
    exp = O.oracle_q60(data)
    rows = dict(zip(got["i_item_id"], got["total_sales"]))
    assert rows, "q60 returned no rows"
    for k, v in rows.items():
        assert exp.get(k) == v, k
    assert len(rows) == min(len(exp), 100)


# ------------------- q13/q48 OR-of-bands star join (ticket slice)

def _q13_source_plan(st):
    from blaze_tpu.tpcds.queries import Q13_BANDS, Q13_STATE_BANDS

    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2001)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    st_p = F.scan("store", [a("s_store_sk")])
    cd_p = F.scan("customer_demographics",
                  [a("cd_demo_sk"), a("cd_marital_status"),
                   a("cd_education_status")])
    hd_p = F.scan("household_demographics",
                  [a("hd_demo_sk"), a("hd_dep_count")])
    ca_p = F.scan("customer_address", [a("ca_address_sk"), a("ca_state")])
    sl = F.scan("store_sales",
                [a("ss_sold_date_sk"), a("ss_store_sk"), a("ss_cdemo_sk"),
                 a("ss_hdemo_sk"), a("ss_addr_sk"), a("ss_quantity"),
                 a("ss_sales_price"), a("ss_ext_sales_price"),
                 a("ss_ext_discount_amt"), a("ss_net_profit")])
    j = join(st, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(st, st_p, j, [a("s_store_sk")], [a("ss_store_sk")])
    j = join(st, cd_p, j, [a("cd_demo_sk")], [a("ss_cdemo_sk")])
    j = join(st, hd_p, j, [a("hd_demo_sk")], [a("ss_hdemo_sk")])
    j = join(st, ca_p, j, [a("ca_address_sk")], [a("ss_addr_sk")])
    dec = "decimal(7,2)"
    demo = or_(*[
        and_(F.binop("EqualTo", a("cd_marital_status"), s(ms)),
             F.binop("EqualTo", a("cd_education_status"), s(ed)),
             F.binop("GreaterThanOrEqual", a("ss_sales_price"),
                     F.lit(str(lo), dec)),
             F.binop("LessThanOrEqual", a("ss_sales_price"),
                     F.lit(str(hi), dec)),
             F.binop("EqualTo", a("hd_dep_count"), i32(dep)))
        for ms, ed, lo, hi, dep in Q13_BANDS])
    geo = or_(*[
        and_(in_(a("ca_state"), *states),
             F.binop("GreaterThanOrEqual", a("ss_net_profit"),
                     F.lit(str(lo), dec)),
             F.binop("LessThanOrEqual", a("ss_net_profit"),
                     F.lit(str(hi), dec)))
        for states, lo, hi in Q13_STATE_BANDS])
    return F.filter_(and_(demo, geo), j)


def test_spark_q13(ticket_sess, ticket_data, strategy):
    agg = two_stage(
        [],
        [(F.avg(a("ss_quantity")), 501),
         (F.avg(a("ss_ext_sales_price")), 502),
         (F.avg(a("ss_ext_discount_amt")), 503),
         (F.count(), 504)],
        _q13_source_plan(strategy),
    )
    plan = F.project(
        [F.alias(ar("avg_qty", 501, "double"), "avg_qty", 510),
         F.alias(ar("avg_ext_sales", 502, "decimal(11,6)"),
                 "avg_ext_sales", 511),
         F.alias(ar("avg_ext_disc", 503, "decimal(11,6)"),
                 "avg_ext_disc", 512),
         F.alias(ar("cnt", 504, "long"), "cnt", 513)],
        agg,
    )
    got = _execute_both(ticket_sess, plan)
    exp = O.oracle_q13(ticket_data)
    assert exp is not None, "q13 bands matched no rows"
    assert got["cnt"] == [exp["cnt"]]
    assert abs(got["avg_qty"][0] - exp["avg_qty"]) < 1e-9
    assert got["avg_ext_sales"] == [exp["avg_ext_sales"]]
    assert got["avg_ext_disc"] == [exp["avg_ext_disc"]]


def test_spark_q48(ticket_sess, ticket_data, strategy):
    agg = two_stage([], [(F.sum_(a("ss_quantity")), 501)],
                    _q13_source_plan(strategy))
    plan = F.project(
        [F.alias(ar("qty_sum", 501, "long"), "qty_sum", 510)], agg)
    got = _execute_both(ticket_sess, plan)
    assert got["qty_sum"] == [O.oracle_q48(ticket_data)]


# ------------- q53/q63 manufacturer window-average ratio reports

def _manufact_window_plan(st, group_col, avg_name, order_cols):
    it = F.project(
        [a("i_item_sk"), a("i_manufact_id")],
        F.filter_(
            or_(and_(in_(a("i_category"), "Books", "Children", "Electronics"),
                     in_(a("i_class"), "personal", "self-help", "reference")),
                and_(in_(a("i_category"), "Women", "Music", "Men"),
                     in_(a("i_class"), "accessories", "classical",
                         "fragrances"))),
            F.scan("item", [a("i_item_sk"), a("i_manufact_id"), a("i_class"),
                            a("i_category")]),
        ),
    )
    dt = F.project(
        [a("d_date_sk"), a(group_col)],
        F.filter_(F.T(F.X + "In", [a("d_year"), i32(1999), i32(2000)]),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"),
                                      a(group_col)])),
    )
    st_p = F.scan("store", [a("s_store_sk")])
    sl = F.scan("store_sales", [a("ss_sold_date_sk"), a("ss_item_sk"),
                                a("ss_store_sk"), a("ss_sales_price")])
    j = join(st, it, sl, [a("i_item_sk")], [a("ss_item_sk")])
    j = join(st, dt, j, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(st, st_p, j, [a("s_store_sk")], [a("ss_store_sk")])
    agg = two_stage([a("i_manufact_id"), a(group_col)],
                    [(F.sum_(a("ss_sales_price")), 501)], j)
    sum_sales = ar("sum_sales", 501, "decimal(17,2)")
    single = F.shuffle(F.single_partition(), agg)
    pre = F.sort([F.sort_order(a("i_manufact_id"))], single)
    w = F.window(
        [F.window_expr(
            F.window_agg(F.avg(sum_sales)),
            F.window_spec([a("i_manufact_id")], [],
                          F.window_frame("up", "uf", row=True)),
            avg_name, 502)],
        [a("i_manufact_id")],
        [],
        pre,
    )
    avg_a = ar(avg_name, 502, "decimal(21,6)")
    sum_f = F.cast(sum_sales, "double")
    avg_f = F.cast(avg_a, "double")
    ratio = F.T(
        F.X + "CaseWhen",
        [F.binop("GreaterThan", avg_f, F.lit(0.0, "double")),
         F.binop("Divide", F.un("Abs", F.binop("Subtract", sum_f, avg_f)),
                 avg_f)],
    )
    filt = F.filter_(F.binop("GreaterThan", ratio, F.lit(0.1, "double")), w)
    attr_of = {"i_manufact_id": a("i_manufact_id"), group_col: a(group_col),
               "sum_sales": sum_sales, avg_name: avg_a}
    return F.take_ordered(
        100,
        [F.sort_order(attr_of[c]) for c in order_cols],
        [F.alias(a("i_manufact_id"), "i_manufact_id", 510),
         F.alias(a(group_col), group_col, 511),
         F.alias(sum_sales, "sum_sales", 512),
         F.alias(avg_a, avg_name, 513)],
        filt,
    )


def test_spark_q53(sess, data, strategy):
    from test_tpcds import _check_manufact_window

    order = ["avg_quarterly_sales", "sum_sales", "i_manufact_id"]
    plan = _manufact_window_plan(strategy, "d_qoy", "avg_quarterly_sales",
                                 order)
    got = _execute_both(sess, plan)
    _check_manufact_window(got, O.oracle_q53(data), "d_qoy",
                           "avg_quarterly_sales", order)


def test_spark_q63(sess, data, strategy):
    from test_tpcds import _check_manufact_window

    order = ["i_manufact_id", "avg_monthly_sales", "sum_sales"]
    plan = _manufact_window_plan(strategy, "d_moy", "avg_monthly_sales",
                                 order)
    got = _execute_both(sess, plan)
    _check_manufact_window(got, O.oracle_q63(data), "d_moy",
                           "avg_monthly_sales", order)


# --------------- q21/q40 inventory/sales before-after pivot reports

def test_spark_q21(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk"), a("d_date")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("d_date"),
                         F.lit("2000-02-10", "date")),
                 F.binop("LessThanOrEqual", a("d_date"),
                         F.lit("2000-04-10", "date"))),
            F.scan("date_dim", [a("d_date_sk"), a("d_date")]),
        ),
    )
    dec = "decimal(7,2)"
    it = F.project(
        [a("i_item_sk"), a("i_item_id")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("i_current_price"),
                         F.lit("20", dec)),
                 F.binop("LessThanOrEqual", a("i_current_price"),
                         F.lit("50", dec))),
            F.scan("item", [a("i_item_sk"), a("i_item_id"),
                            a("i_current_price")]),
        ),
    )
    wh = F.scan("warehouse", [a("w_warehouse_sk"), a("w_warehouse_name")])
    inv = F.scan("inventory", [a("inv_date_sk"), a("inv_item_sk"),
                               a("inv_warehouse_sk"),
                               a("inv_quantity_on_hand")])
    j = join(strategy, dt, inv, [a("d_date_sk")], [a("inv_date_sk")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("inv_item_sk")])
    j = join(strategy, wh, j, [a("w_warehouse_sk")], [a("inv_warehouse_sk")])
    pivot = F.lit("2000-03-11", "date")
    qoh = F.cast(a("inv_quantity_on_hand"), "long")
    zero = F.lit(0, "long")
    before = F.T(F.X + "CaseWhen",
                 [F.binop("LessThan", a("d_date"), pivot), qoh, zero])
    after = F.T(F.X + "CaseWhen",
                [F.binop("GreaterThanOrEqual", a("d_date"), pivot), qoh, zero])
    proj = F.project(
        [a("w_warehouse_name"), a("i_item_id"),
         F.alias(before, "b", 520), F.alias(after, "a", 521)], j)
    agg = two_stage(
        [a("w_warehouse_name"), a("i_item_id")],
        [(F.sum_(ar("b", 520, "long")), 501),
         (F.sum_(ar("a", 521, "long")), 502)],
        proj,
    )
    bf = F.cast(ar("inv_before", 501, "long"), "double")
    af = F.cast(ar("inv_after", 502, "long"), "double")
    ratio = F.binop("Divide", af, bf)
    f = F.filter_(
        and_(F.binop("GreaterThan", bf, F.lit(0.0, "double")),
             F.binop("GreaterThanOrEqual", ratio,
                     F.lit(2.0 / 3.0, "double")),
             F.binop("LessThanOrEqual", ratio, F.lit(1.5, "double"))),
        agg,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(a("w_warehouse_name")), F.sort_order(a("i_item_id"))],
        [F.alias(a("w_warehouse_name"), "w_warehouse_name", 530),
         F.alias(a("i_item_id"), "i_item_id", 531),
         F.alias(ar("inv_before", 501, "long"), "inv_before", 532),
         F.alias(ar("inv_after", 502, "long"), "inv_after", 533)],
        f,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q21(data)
    assert exp, "q21 oracle empty"
    n = len(got["w_warehouse_name"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["w_warehouse_name"][i], got["i_item_id"][i])
        assert key in exp, key
        assert (got["inv_before"][i], got["inv_after"][i]) == exp[key], key
    keys = [(got["w_warehouse_name"][i], got["i_item_id"][i])
            for i in range(n)]
    assert keys == sorted(keys)


def test_spark_q40(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk"), a("d_date")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("d_date"),
                         F.lit("2000-02-10", "date")),
                 F.binop("LessThanOrEqual", a("d_date"),
                         F.lit("2000-04-10", "date"))),
            F.scan("date_dim", [a("d_date_sk"), a("d_date")]),
        ),
    )
    dec = "decimal(7,2)"
    it = F.project(
        [a("i_item_sk"), a("i_item_id")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("i_current_price"),
                         F.lit("20", dec)),
                 F.binop("LessThanOrEqual", a("i_current_price"),
                         F.lit("50", dec))),
            F.scan("item", [a("i_item_sk"), a("i_item_id"),
                            a("i_current_price")]),
        ),
    )
    wh = F.scan("warehouse", [a("w_warehouse_sk"), a("w_state")])
    cs = F.scan("catalog_sales",
                [a("cs_sold_date_sk"), a("cs_item_sk"), a("cs_order_number"),
                 a("cs_warehouse_sk"), a("cs_sales_price")])
    j = join(strategy, dt, cs, [a("d_date_sk")], [a("cs_sold_date_sk")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("cs_item_sk")])
    j = join(strategy, wh, j, [a("w_warehouse_sk")], [a("cs_warehouse_sk")])
    cr = F.scan("catalog_returns", [a("cr_item_sk"), a("cr_order_number"),
                                    a("cr_refunded_cash")])
    j = join(strategy, cr, j, [a("cr_item_sk"), a("cr_order_number")],
             [a("cs_item_sk"), a("cs_order_number")], jt="LeftOuter",
             build_side="right")
    dz = F.lit("0", dec)
    net_sales = F.binop("Add", a("cs_sales_price"), dz)  # decimal(8,2)
    refund = F.T(
        F.X + "CaseWhen",
        [F.un("IsNotNull", a("cr_refunded_cash")),
         F.binop("Add", a("cr_refunded_cash"), dz),
         F.binop("Add", dz, dz)],
    )
    net = F.binop("Subtract", net_sales, refund)
    pivot = F.lit("2000-03-11", "date")
    before = F.T(F.X + "CaseWhen",
                 [F.binop("LessThan", a("d_date"), pivot), net])
    after = F.T(F.X + "CaseWhen",
                [F.binop("GreaterThanOrEqual", a("d_date"), pivot), net])
    proj = F.project(
        [a("w_state"), a("i_item_id"),
         F.alias(before, "b", 520), F.alias(after, "a", 521)], j)
    agg = two_stage(
        [a("w_state"), a("i_item_id")],
        [(F.sum_(ar("b", 520, "decimal(9,2)")), 501),
         (F.sum_(ar("a", 521, "decimal(9,2)")), 502)],
        proj,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(a("w_state")), F.sort_order(a("i_item_id"))],
        [F.alias(a("w_state"), "w_state", 530),
         F.alias(a("i_item_id"), "i_item_id", 531),
         F.alias(ar("sales_before", 501, "decimal(19,2)"), "sales_before", 532),
         F.alias(ar("sales_after", 502, "decimal(19,2)"), "sales_after", 533)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q40(data)
    assert exp, "q40 oracle empty"
    n = len(got["w_state"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["w_state"][i], got["i_item_id"][i])
        assert key in exp, key
        assert (got["sales_before"][i], got["sales_after"][i]) == exp[key], key


# ---------------- q28 six price-band buckets (scalar-subquery trios)

def test_spark_q28(sess, data, strategy):
    """avg/count/count-distinct per band, each a driver-resolved
    scalar subquery; the DISTINCT count is a grouping-only agg under a
    count (the shape Spark plans instead of a distinct aggregate)."""
    if strategy == "smj":
        pytest.skip("no joins in q28: the strategy axis is vacuous")
    bands = [
        ("B1", 0, 5, 0, 10, 0, 50),
        ("B2", 6, 10, 10, 20, 50, 100),
        ("B3", 11, 15, 20, 30, 100, 150),
        ("B4", 16, 20, 30, 40, 150, 200),
        ("B5", 21, 25, 40, 50, 200, 250),
        ("B6", 26, 30, 50, 60, 250, 300),
    ]
    dec = "decimal(7,2)"
    exprs = []
    rid = 801
    for bi, (name, q_lo, q_hi, c_lo, c_hi, w_lo, w_hi) in enumerate(bands):
        pred = and_(
            F.binop("GreaterThanOrEqual", a("ss_quantity"), i32(q_lo)),
            F.binop("LessThanOrEqual", a("ss_quantity"), i32(q_hi)),
            or_(
                and_(F.binop("GreaterThanOrEqual", a("ss_list_price"),
                             F.lit(str(c_lo), dec)),
                     F.binop("LessThanOrEqual", a("ss_list_price"),
                             F.lit(str(c_lo + 10), dec))),
                and_(F.binop("GreaterThanOrEqual", a("ss_coupon_amt"),
                             F.lit(str(w_lo), dec)),
                     F.binop("LessThanOrEqual", a("ss_coupon_amt"),
                             F.lit(str(w_lo + 1000), dec))),
                and_(F.binop("GreaterThanOrEqual", a("ss_wholesale_cost"),
                             F.lit(str(c_hi), dec)),
                     F.binop("LessThanOrEqual", a("ss_wholesale_cost"),
                             F.lit(str(c_hi + 20), dec))),
            ),
        )
        lp = F.project(
            [a("ss_list_price")],
            F.filter_(pred, F.scan(
                "store_sales",
                [a("ss_quantity"), a("ss_list_price"), a("ss_coupon_amt"),
                 a("ss_wholesale_cost")])),
        )
        avg_sq = _scalar_subquery(
            two_stage([], [(F.avg(a("ss_list_price")), rid)], lp), rid)
        cnt_sq = _scalar_subquery(
            two_stage([], [(F.count(), rid + 1)], lp), rid + 1)
        dis = distinct([a("ss_list_price")], lp)
        cntd_sq = _scalar_subquery(
            two_stage([], [(F.count(), rid + 2)], dis), rid + 2)
        exprs += [
            F.alias(avg_sq, f"{name}_lp", 850 + bi * 3),
            F.alias(cnt_sq, f"{name}_cnt", 851 + bi * 3),
            F.alias(cntd_sq, f"{name}_cntd", 852 + bi * 3),
        ]
        rid += 3
    src = F.filter_(F.binop("EqualTo", a("r_reason_sk"), F.lit(1, "long")),
                    F.scan("reason", [a("r_reason_sk")]))
    got = _execute_both(sess, F.project(exprs, src))
    exp = O.oracle_q28(data)
    for name, (avg_u, cnt, cntd) in exp.items():
        assert got[f"{name}_lp"] == [avg_u], name
        assert got[f"{name}_cnt"] == [cnt], name
        assert got[f"{name}_cntd"] == [cntd], name


# ------------- q1/q30/q81 returns-above-location-average family

def _returns_above_avg_plan(st, *, rtab, r_cust, r_amt, r_date, r_loc,
                            loc_tab=None, loc_sk=None, loc_filter_col=None,
                            loc_filter_val=None, names=False):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    rt = F.scan(rtab, [a(r_date), a(r_cust), a(r_loc), a(r_amt)])
    j = join(st, dt, rt, [a("d_date_sk")], [a(r_date)])
    if loc_tab is not None:
        loc = F.project(
            [a(loc_sk)],
            F.filter_(F.binop("EqualTo", a(loc_filter_col),
                              s(loc_filter_val)),
                      F.scan(loc_tab, [a(loc_sk), a(loc_filter_col)])),
        )
        j = join(st, loc, j, [a(loc_sk)], [a(r_loc)])
    per_cust = two_stage(
        [a(r_cust), a(r_loc)], [(F.sum_(a(r_amt)), 501)],
        F.project([a(r_cust), a(r_loc), a(r_amt)], j))
    total = ar("ctr_total_return", 501, "decimal(17,2)")
    loc_avg_src = F.project(
        [F.alias(a(r_loc), "avg_loc_sk", 520), total], per_cust)
    loc_avg = two_stage(
        [ar("avg_loc_sk", 520, "long")], [(F.avg(total), 502)], loc_avg_src)
    avg_r = ar("avg_return", 502, "decimal(21,6)")
    j2 = join(st, loc_avg, per_cust, [ar("avg_loc_sk", 520, "long")],
              [a(r_loc)])
    f = F.filter_(
        F.binop("GreaterThan", F.cast(total, "double"),
                F.binop("Multiply", F.lit(1.2, "double"),
                        F.cast(avg_r, "double"))),
        j2,
    )
    cu_cols = [a("c_customer_sk"), a("c_customer_id")] + (
        [a("c_first_name"), a("c_last_name")] if names else [])
    cu = F.scan("customer", cu_cols)
    j3 = join(st, cu, f, [a("c_customer_sk")], [a(r_cust)])
    if names:
        return F.take_ordered(
            100,
            [F.sort_order(a("c_customer_id")), F.sort_order(total)],
            [F.alias(a("c_customer_id"), "c_customer_id", 530),
             F.alias(a("c_first_name"), "c_first_name", 531),
             F.alias(a("c_last_name"), "c_last_name", 532),
             F.alias(total, "ctr_total_return", 533)],
            j3,
        )
    return F.take_ordered(
        100, [F.sort_order(a("c_customer_id"))],
        [F.alias(a("c_customer_id"), "c_customer_id", 530)], j3)


def test_spark_q1(sess, data, strategy):
    plan = _returns_above_avg_plan(
        strategy, rtab="store_returns", r_cust="sr_customer_sk",
        r_amt="sr_return_amt", r_date="sr_returned_date_sk",
        r_loc="sr_store_sk", loc_tab="store", loc_sk="s_store_sk",
        loc_filter_col="s_state", loc_filter_val="TN")
    got = _execute_both(sess, plan)
    exp = O.oracle_q1(data)
    assert exp, "q1 oracle empty"
    assert len(got["c_customer_id"]) == min(len(exp), 100)
    assert set(got["c_customer_id"]) == exp if len(exp) <= 100 else set(
        got["c_customer_id"]) <= exp
    assert got["c_customer_id"] == sorted(got["c_customer_id"])


def test_spark_q30(sess, data, strategy):
    from test_tpcds import _check_returns_family

    plan = _returns_above_avg_plan(
        strategy, rtab="web_returns", r_cust="wr_returning_customer_sk",
        r_amt="wr_return_amt", r_date="wr_returned_date_sk",
        r_loc="wr_web_page_sk", names=True)
    got = _execute_both(sess, plan)
    _check_returns_family(got, O.oracle_q30(data))


def test_spark_q81(sess, data, strategy):
    from test_tpcds import _check_returns_family

    plan = _returns_above_avg_plan(
        strategy, rtab="catalog_returns", r_cust="cr_returning_customer_sk",
        r_amt="cr_return_amount", r_date="cr_returned_date_sk",
        r_loc="cr_call_center_sk", names=True)
    got = _execute_both(sess, plan)
    _check_returns_family(got, O.oracle_q81(data))


# ------------------ q17 quantity-spread statistics over the chain

def test_spark_q17(sess, data, strategy):
    j = _srcandc_join_plan(strategy)
    qs = [("ss_quantity", "store"), ("sr_return_quantity", "returns"),
          ("cs_quantity", "catalog")]
    aggs = []
    rid = 501
    for src, nm in qs:
        e = F.cast(a(src), "long")
        aggs += [(F.count(e), rid), (F.avg(e), rid + 1),
                 (F.T(F.A + "StddevSamp", [e]), rid + 2)]
        rid += 3
    agg = two_stage(
        [a("i_item_id"), a("i_item_desc"), a("s_store_name")], aggs, j)
    outs = [a("i_item_id"), a("i_item_desc"), a("s_store_name")]
    oid = 530
    names = []
    rid = 501
    for _, nm in qs:
        cnt = ar(f"{nm}_qty_count", rid, "long")
        avg = ar(f"{nm}_qty_avg", rid + 1, "double")
        sd = ar(f"{nm}_qty_stdev", rid + 2, "double")
        cov = F.T(F.X + "CaseWhen",
                  [F.binop("GreaterThan", avg, F.lit(0.0, "double")),
                   F.binop("Divide", sd, avg)])
        outs += [F.alias(cnt, f"{nm}_qty_count", oid),
                 F.alias(avg, f"{nm}_qty_avg", oid + 1),
                 F.alias(sd, f"{nm}_qty_stdev", oid + 2),
                 F.alias(cov, f"{nm}_qty_cov", oid + 3)]
        names += [f"{nm}_qty_count", f"{nm}_qty_avg", f"{nm}_qty_stdev",
                  f"{nm}_qty_cov"]
        rid += 3
        oid += 4
    plan = F.take_ordered(
        100,
        [F.sort_order(a("i_item_id")), F.sort_order(a("i_item_desc")),
         F.sort_order(a("s_store_name"))],
        outs,
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q17(data)
    assert exp, "q17 oracle empty"
    n = len(got["i_item_id"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["i_item_id"][i], got["i_item_desc"][i],
               got["s_store_name"][i])
        assert key in exp, key
        for k, nm in enumerate(("store", "returns", "catalog")):
            cnt, mean, sd, cov = exp[key][k]
            assert got[f"{nm}_qty_count"][i] == cnt, (key, nm)
            assert abs(got[f"{nm}_qty_avg"][i] - mean) < 1e-9, (key, nm)
            for gv, ev in ((got[f"{nm}_qty_stdev"][i], sd),
                           (got[f"{nm}_qty_cov"][i], cov)):
                if ev is None:
                    assert gv is None, (key, nm)
                else:
                    assert gv is not None and abs(gv - ev) < 1e-9, (key, nm)


# ---------------- q22 product-hierarchy inventory ROLLUP (5 levels)

def test_spark_q22(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    it = F.scan("item", [a("i_item_sk"), a("i_item_id"), a("i_brand"),
                         a("i_class"), a("i_category")])
    inv = F.scan("inventory", [a("inv_date_sk"), a("inv_item_sk"),
                               a("inv_quantity_on_hand")])
    j = join(strategy, dt, inv, [a("d_date_sk")], [a("inv_date_sk")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("inv_item_sk")])
    dims = ["i_item_id", "i_brand", "i_class", "i_category"]
    null_s = F.lit(None, "string")
    exp_dims = [ar(d, 520 + k, "string") for k, d in enumerate(dims)]
    exp_gid = ar("g_id", 524, "integer")
    vals = [a("inv_quantity_on_hand")]
    rows = []
    for level in range(4, -1, -1):
        row = list(vals)
        for k, d in enumerate(dims):
            row.append(a(d) if k < level else null_s)
        row.append(F.lit(4 - level, "integer"))
        rows.append(row)
    expand = F.expand(rows, vals + exp_dims + [exp_gid], j)
    agg = two_stage(
        exp_dims + [exp_gid],
        [(F.avg(a("inv_quantity_on_hand")), 501)],
        expand,
    )
    qoh = ar("qoh", 501, "double")
    plan = F.take_ordered(
        100,
        [F.sort_order(qoh)] + [F.sort_order(d) for d in exp_dims],
        [F.alias(d, dims[k], 540 + k) for k, d in enumerate(exp_dims)]
        + [F.alias(exp_gid, "g_id", 544), F.alias(qoh, "qoh", 545)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q22(data)
    assert exp, "q22 oracle empty"
    n = len(got["i_item_id"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["i_item_id"][i], got["i_brand"][i], got["i_class"][i],
               got["i_category"][i], got["g_id"][i])
        assert key in exp, key
        assert abs(got["qoh"][i] - exp[key]) < 1e-9, key
    assert got["qoh"] == sorted(got["qoh"])


# ------------- q94/q95/q16 multi-warehouse ship reports

def _multi_wh_orders_plan(st, fact, order_c, wh_c, base_id):
    pairs = distinct([a(order_c), a(wh_c)],
                     F.project([a(order_c), a(wh_c)], F.scan(fact, [a(order_c), a(wh_c)])))
    per_order = two_stage(
        [a(order_c)], [(F.count(), base_id)],
        F.project([a(order_c)], pairs))
    hot = F.filter_(
        F.binop("GreaterThan", ar("wh_cnt", base_id, "long"),
                F.lit(1, "long")),
        per_order,
    )
    return F.project([F.alias(a(order_c), "hot_order", base_id + 1)], hot)


def _ship_report_plan(st, rows, order_c, ship_c, profit_c):
    per_order = two_stage(
        [a(order_c)],
        [(F.sum_(a(ship_c)), 551), (F.sum_(a(profit_c)), 552)],
        rows,
    )
    agg = two_stage(
        [],
        [(F.count(), 553),
         (F.sum_(ar("s1", 551, "decimal(17,2)")), 554),
         (F.sum_(ar("p1", 552, "decimal(17,2)")), 555)],
        per_order,
    )
    return F.project(
        [F.alias(ar("order_count", 553, "long"), "order_count", 560),
         F.alias(ar("total_shipping_cost", 554, "decimal(27,2)"),
                 "total_shipping_cost", 561),
         F.alias(ar("total_net_profit", 555, "decimal(27,2)"),
                 "total_net_profit", 562)],
        agg,
    )


def _q94_shape_plan(st, returns_jt):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("d_date"),
                         F.lit("1999-02-01", "date")),
                 F.binop("LessThanOrEqual", a("d_date"),
                         F.lit("1999-12-31", "date"))),
            F.scan("date_dim", [a("d_date_sk"), a("d_date")]),
        ),
    )
    ca = F.project(
        [a("ca_address_sk")],
        F.filter_(F.binop("EqualTo", a("ca_state"), s("TN")),
                  F.scan("customer_address", [a("ca_address_sk"),
                                              a("ca_state")])),
    )
    site = F.project(
        [a("web_site_sk")],
        F.filter_(F.binop("EqualTo", a("web_company_name"), s("pri")),
                  F.scan("web_site", [a("web_site_sk"),
                                      a("web_company_name")])),
    )
    ws1 = F.scan("web_sales",
                 [a("ws_ship_date_sk"), a("ws_ship_addr_sk"),
                  a("ws_web_site_sk"), a("ws_order_number"),
                  a("ws_ext_ship_cost"), a("ws_net_profit")])
    j = join(st, dt, ws1, [a("d_date_sk")], [a("ws_ship_date_sk")])
    j = join(st, ca, j, [a("ca_address_sk")], [a("ws_ship_addr_sk")])
    j = join(st, site, j, [a("web_site_sk")], [a("ws_web_site_sk")])
    hot = _multi_wh_orders_plan(st, "web_sales", "ws_order_number",
                                "ws_warehouse_sk", 540)
    j = join(st, hot, j, [ar("hot_order", 541, "long")],
             [a("ws_order_number")], jt="LeftSemi", build_side="right")
    wr = F.scan("web_returns", [a("wr_order_number")])
    j = join(st, wr, j, [a("wr_order_number")], [a("ws_order_number")],
             jt=returns_jt, build_side="right")
    return _ship_report_plan(st, j, "ws_order_number", "ws_ext_ship_cost",
                             "ws_net_profit")


def test_spark_q94(sess, data, strategy):
    from test_tpcds import _check_ship_report

    got = _execute_both(sess, _q94_shape_plan(strategy, "LeftAnti"))
    _check_ship_report(got, O.oracle_q94(data))


def test_spark_q95(sess, data, strategy):
    from test_tpcds import _check_ship_report

    got = _execute_both(sess, _q94_shape_plan(strategy, "LeftSemi"))
    _check_ship_report(got, O.oracle_q95(data))


def test_spark_q16(sess, data, strategy):
    from test_tpcds import _check_ship_report

    dt = F.project(
        [a("d_date_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("d_date"),
                         F.lit("2002-02-01", "date")),
                 F.binop("LessThanOrEqual", a("d_date"),
                         F.lit("2002-12-31", "date"))),
            F.scan("date_dim", [a("d_date_sk"), a("d_date")]),
        ),
    )
    ca = F.project(
        [a("ca_address_sk")],
        F.filter_(F.binop("EqualTo", a("ca_state"), s("GA")),
                  F.scan("customer_address", [a("ca_address_sk"),
                                              a("ca_state")])),
    )
    cc = F.project(
        [a("cc_call_center_sk")],
        F.filter_(F.binop("EqualTo", a("cc_county"), s("Williamson County")),
                  F.scan("call_center", [a("cc_call_center_sk"),
                                         a("cc_county")])),
    )
    cs1 = F.scan("catalog_sales",
                 [a("cs_ship_date_sk"), a("cs_ship_addr_sk"),
                  a("cs_call_center_sk"), a("cs_order_number"),
                  a("cs_ext_ship_cost"), a("cs_net_profit")])
    j = join(strategy, dt, cs1, [a("d_date_sk")], [a("cs_ship_date_sk")])
    j = join(strategy, ca, j, [a("ca_address_sk")], [a("cs_ship_addr_sk")])
    j = join(strategy, cc, j, [a("cc_call_center_sk")],
             [a("cs_call_center_sk")])
    hot = _multi_wh_orders_plan(strategy, "catalog_sales", "cs_order_number",
                                "cs_warehouse_sk", 545)
    j = join(strategy, hot, j, [ar("hot_order", 546, "long")],
             [a("cs_order_number")], jt="LeftSemi", build_side="right")
    cr = F.scan("catalog_returns", [a("cr_order_number")])
    j = join(strategy, cr, j, [a("cr_order_number")], [a("cs_order_number")],
             jt="LeftAnti", build_side="right")
    got = _execute_both(
        sess, _ship_report_plan(strategy, j, "cs_order_number",
                                "cs_ext_ship_cost", "cs_net_profit"))
    _check_ship_report(got, O.oracle_q16(data))


# -------------------- q2/q59 weekly dow-pivot year-over-year ratios

_DOW7 = ("sun", "mon", "tue", "wed", "thu", "fri", "sat")


def _dow_pivot_plan(group_attrs, price_attr, rows, base_rid):
    """CASE-pivot 7 dow sums grouped by group_attrs (q43's shape)."""
    pivots = [
        F.alias(F.T(F.X + "CaseWhen",
                    [F.binop("EqualTo", a("d_dow"), i32(k)), price_attr]),
                f"{nm}_v", base_rid + k)
        for k, nm in enumerate(_DOW7)
    ]
    proj = F.project(list(group_attrs) + pivots, rows)
    return two_stage(
        list(group_attrs),
        [(F.sum_(ar(f"{nm}_v", base_rid + k, "decimal(7,2)")),
          base_rid + 10 + k) for k, nm in enumerate(_DOW7)],
        proj,
    )


def _week_set_plan(year, out_name, out_id):
    y = F.filter_(F.binop("EqualTo", a("d_year"), i32(year)),
                  F.scan("date_dim", [a("d_week_seq"), a("d_year")]))
    return distinct(
        [ar(out_name, out_id, "integer")],
        F.project([F.alias(a("d_week_seq"), out_name, out_id)], y))


def _dow_ratios(base_rid, rid2_base, out_base):
    outs = []
    for k, nm in enumerate(_DOW7):
        num = F.cast(ar(f"{nm}1", base_rid + k, "decimal(17,2)"), "double")
        den = F.cast(ar(f"{nm}2", rid2_base + k, "decimal(17,2)"), "double")
        den = F.T(F.X + "CaseWhen",
                  [F.binop("GreaterThan", den, F.lit(0.0, "double")), den,
                   F.lit(1.0, "double")])
        outs.append(F.alias(F.binop("Divide", num, den), f"{nm}_ratio",
                            out_base + k))
    return outs


def test_spark_q2(sess, data, strategy):
    from test_tpcds import _check_weekly_ratios

    dt = F.scan("date_dim", [a("d_date_sk"), a("d_week_seq"), a("d_dow")])
    sold = ar("sold_date_sk", 901, "long")
    price = ar("sales_price", 902, "decimal(7,2)")
    branches = [
        F.project([F.alias(a(date_c), "sold_date_sk", 901),
                   F.alias(a(price_c), "sales_price", 902)],
                  F.scan(fact, [a(date_c), a(price_c)]))
        for fact, date_c, price_c in (
            ("web_sales", "ws_sold_date_sk", "ws_ext_sales_price"),
            ("catalog_sales", "cs_sold_date_sk", "cs_ext_sales_price"),
        )
    ]
    u = F.union(branches)
    j = join(strategy, dt, u, [a("d_date_sk")], [sold])
    wk = _dow_pivot_plan([a("d_week_seq")], price, j, 910)
    wk1 = join(strategy, _week_set_plan(2001, "wk1", 930), wk,
               [ar("wk1", 930, "integer")], [a("d_week_seq")],
               jt="LeftSemi", build_side="right")
    wk1 = F.project(
        [a("d_week_seq")] + [
            F.alias(ar(f"{nm}_sales", 920 + k, "decimal(17,2)"),
                    f"{nm}1", 940 + k)
            for k, nm in enumerate(_DOW7)],
        wk1,
    )
    wk2 = join(strategy, _week_set_plan(2002, "wk2", 931), wk,
               [ar("wk2", 931, "integer")], [a("d_week_seq")],
               jt="LeftSemi", build_side="right")
    wk2 = F.project(
        [F.alias(F.binop("Subtract", a("d_week_seq"), i32(52)),
                 "wk_m52", 950)] + [
            F.alias(ar(f"{nm}_sales", 920 + k, "decimal(17,2)"),
                    f"{nm}2", 951 + k)
            for k, nm in enumerate(_DOW7)],
        wk2,
    )
    j2 = big_join(strategy, wk1, wk2, [a("d_week_seq")],
                  [ar("wk_m52", 950, "integer")])
    plan = F.take_ordered(
        100, [F.sort_order(a("d_week_seq"))],
        [F.alias(a("d_week_seq"), "d_week_seq", 970)]
        + _dow_ratios(940, 951, 971),
        j2,
    )
    got = _execute_both(sess, plan)
    _check_weekly_ratios(got, O.oracle_q2(data), ["d_week_seq"])


def test_spark_q59(sess, data, strategy):
    from test_tpcds import _check_weekly_ratios

    dt = F.scan("date_dim", [a("d_date_sk"), a("d_week_seq"), a("d_dow")])
    sl = F.scan("store_sales", [a("ss_sold_date_sk"), a("ss_store_sk"),
                                a("ss_sales_price")])
    j = join(strategy, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    wk = _dow_pivot_plan([a("ss_store_sk"), a("d_week_seq")],
                         a("ss_sales_price"), j, 910)
    st_ = F.scan("store", [a("s_store_sk"), a("s_store_name")])
    wk = join(strategy, st_, wk, [a("s_store_sk")], [a("ss_store_sk")])
    wk1 = join(strategy, _week_set_plan(2001, "wk1", 930), wk,
               [ar("wk1", 930, "integer")], [a("d_week_seq")],
               jt="LeftSemi", build_side="right")
    wk1 = F.project(
        [a("s_store_name"), a("ss_store_sk"), a("d_week_seq")] + [
            F.alias(ar(f"{nm}_sales", 920 + k, "decimal(17,2)"),
                    f"{nm}1", 940 + k)
            for k, nm in enumerate(_DOW7)],
        wk1,
    )
    wk2 = join(strategy, _week_set_plan(2002, "wk2", 931), wk,
               [ar("wk2", 931, "integer")], [a("d_week_seq")],
               jt="LeftSemi", build_side="right")
    wk2 = F.project(
        [F.alias(a("ss_store_sk"), "store2", 949),
         F.alias(F.binop("Subtract", a("d_week_seq"), i32(52)),
                 "wk_m52", 950)] + [
            F.alias(ar(f"{nm}_sales", 920 + k, "decimal(17,2)"),
                    f"{nm}2", 951 + k)
            for k, nm in enumerate(_DOW7)],
        wk2,
    )
    j2 = big_join(strategy, wk1, wk2,
                  [a("ss_store_sk"), a("d_week_seq")],
                  [ar("store2", 949, "long"), ar("wk_m52", 950, "integer")])
    plan = F.take_ordered(
        100,
        [F.sort_order(a("s_store_name")), F.sort_order(a("d_week_seq"))],
        [F.alias(a("s_store_name"), "s_store_name", 969),
         F.alias(a("d_week_seq"), "d_week_seq", 970)]
        + _dow_ratios(940, 951, 971),
        j2,
    )
    got = _execute_both(sess, plan)
    _check_weekly_ratios(got, O.oracle_q59(data),
                         ["s_store_name", "d_week_seq"])


# --------------- q74/q11 year-over-year customer growth family

def _yoy_customer_plan(st, *, store_measure, store_cols, web_measure,
                       web_cols, y1, y2, out_cols, sum_dtype):
    def slice_(fact, date_c, cust_c, cols, measure, year, base, names=False):
        dt = F.project(
            [a("d_date_sk")],
            F.filter_(F.binop("EqualTo", a("d_year"), i32(year)),
                      F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
        )
        fc = F.scan(fact, [a(date_c), a(cust_c)] + [a(c) for c in cols])
        cust_cols = [a("c_customer_sk")] + (
            [a("c_customer_id"), a("c_first_name"), a("c_last_name"),
             a("c_preferred_cust_flag")] if names else [])
        cu = F.scan("customer", cust_cols)
        j = join(st, dt, fc, [a("d_date_sk")], [a(date_c)])
        j = join(st, cu, j, [a("c_customer_sk")], [a(cust_c)])
        groups = [a("c_customer_sk")] + (
            [a(c) for c in ("c_customer_id", "c_first_name", "c_last_name",
                            "c_preferred_cust_flag")] if names else [])
        yt = two_stage(groups, [(F.sum_(measure), base)], j)
        keep = [F.alias(a("c_customer_sk"), f"sk{base}", base + 1),
                F.alias(ar("year_total", base, sum_dtype), f"yt{base}",
                        base + 2)]
        if names:
            keep += [a(c) for c in
                     ("c_customer_id", "c_first_name", "c_last_name",
                      "c_preferred_cust_flag")]
        return F.project(keep, yt)

    s1 = slice_("store_sales", "ss_sold_date_sk", "ss_customer_sk",
                store_cols, store_measure("ss"), y1, 1000)
    s2 = slice_("store_sales", "ss_sold_date_sk", "ss_customer_sk",
                store_cols, store_measure("ss"), y2, 1010, names=True)
    w1 = slice_("web_sales", "ws_sold_date_sk", "ws_bill_customer_sk",
                web_cols, web_measure("ws"), y1, 1020)
    w2 = slice_("web_sales", "ws_sold_date_sk", "ws_bill_customer_sk",
                web_cols, web_measure("ws"), y2, 1030)
    sk = lambda b: ar(f"sk{b}", b + 1, "long")
    yt = lambda b: ar(f"yt{b}", b + 2, sum_dtype)
    j = join(st, s1, s2, [sk(1000)], [sk(1010)])
    j = join(st, w1, j, [sk(1020)], [sk(1010)])
    j = join(st, w2, j, [sk(1030)], [sk(1010)])
    fl = lambda e: F.cast(e, "double")
    f = F.filter_(
        and_(F.binop("GreaterThan", fl(yt(1000)), F.lit(0.0, "double")),
             F.binop("GreaterThan", fl(yt(1020)), F.lit(0.0, "double")),
             F.binop("GreaterThan",
                     F.binop("Divide", fl(yt(1030)), fl(yt(1020))),
                     F.binop("Divide", fl(yt(1010)), fl(yt(1000))))),
        j,
    )
    return F.take_ordered(
        100, [F.sort_order(a(out_cols[0]))],
        [F.alias(a(c), c, 1050 + i) for i, c in enumerate(out_cols)],
        f,
    )


def test_spark_q74(sess, data, strategy):
    from test_tpcds import _check_yoy_customer

    plan = _yoy_customer_plan(
        strategy,
        store_measure=lambda p: a("ss_net_paid"),
        store_cols=["ss_net_paid"],
        web_measure=lambda p: a("ws_net_paid"),
        web_cols=["ws_net_paid"],
        y1=1999, y2=2000,
        out_cols=["c_customer_id", "c_first_name", "c_last_name"],
        sum_dtype="decimal(17,2)")
    got = _execute_both(sess, plan)
    _check_yoy_customer(got, O.oracle_q74(data),
                        ["c_customer_id", "c_first_name", "c_last_name"])


def test_spark_q11(sess, data, strategy):
    from test_tpcds import _check_yoy_customer

    plan = _yoy_customer_plan(
        strategy,
        store_measure=lambda p: F.binop(
            "Subtract", a("ss_ext_list_price"), a("ss_ext_discount_amt")),
        store_cols=["ss_ext_list_price", "ss_ext_discount_amt"],
        web_measure=lambda p: F.binop(
            "Subtract", a("ws_ext_list_price"), a("ws_ext_discount_amt")),
        web_cols=["ws_ext_list_price", "ws_ext_discount_amt"],
        y1=2000, y2=2001,
        out_cols=["c_customer_id", "c_preferred_cust_flag", "c_first_name",
                  "c_last_name"],
        sum_dtype="decimal(18,2)")
    got = _execute_both(sess, plan)
    _check_yoy_customer(got, O.oracle_q11(data),
                        ["c_customer_id", "c_preferred_cust_flag",
                         "c_first_name", "c_last_name"])


# ---------------- q18 catalog demographic averages geography rollup

def test_spark_q18(sess, data, strategy):
    cd = F.project(
        [a("cd_demo_sk"), a("cd_dep_count")],
        F.filter_(and_(F.binop("EqualTo", a("cd_gender"), s("F")),
                       F.binop("EqualTo", a("cd_education_status"),
                               s("College"))),
                  F.scan("customer_demographics",
                         [a("cd_demo_sk"), a("cd_gender"),
                          a("cd_education_status"), a("cd_dep_count")])),
    )
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2001)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    cu = F.project(
        [a("c_customer_sk"), a("c_current_addr_sk"), a("c_birth_year")],
        F.filter_(and_(F.binop("GreaterThanOrEqual", a("c_birth_year"),
                               i32(1966)),
                       F.binop("LessThanOrEqual", a("c_birth_year"),
                               i32(1980))),
                  F.scan("customer", [a("c_customer_sk"),
                                      a("c_current_addr_sk"),
                                      a("c_birth_year")])),
    )
    ca = F.scan("customer_address", [a("ca_address_sk"), a("ca_county"),
                                     a("ca_state")])
    it = F.scan("item", [a("i_item_sk"), a("i_item_id")])
    cs = F.scan("catalog_sales",
                [a("cs_sold_date_sk"), a("cs_item_sk"),
                 a("cs_bill_customer_sk"), a("cs_bill_cdemo_sk"),
                 a("cs_quantity"), a("cs_list_price"), a("cs_coupon_amt"),
                 a("cs_sales_price"), a("cs_net_profit")])
    j = join(strategy, dt, cs, [a("d_date_sk")], [a("cs_sold_date_sk")])
    j = join(strategy, cd, j, [a("cd_demo_sk")], [a("cs_bill_cdemo_sk")])
    j = join(strategy, cu, j, [a("c_customer_sk")], [a("cs_bill_customer_sk")])
    j = join(strategy, ca, j, [a("ca_address_sk")], [a("c_current_addr_sk")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("cs_item_sk")])
    measures = [("cs_quantity", "agg1"), ("cs_list_price", "agg2"),
                ("cs_coupon_amt", "agg3"), ("cs_sales_price", "agg4"),
                ("cs_net_profit", "agg5"), ("c_birth_year", "agg6"),
                ("cd_dep_count", "agg7")]
    base = F.project(
        [F.alias(F.cast(a(src), "double"), nm, 1100 + k)
         for k, (src, nm) in enumerate(measures)]
        + [a("i_item_id"), a("ca_county"), a("ca_state")],
        j,
    )
    meas_attrs = [ar(nm, 1100 + k, "double")
                  for k, (_, nm) in enumerate(measures)]
    dims = ["i_item_id", "ca_county", "ca_state"]
    null_s = F.lit(None, "string")
    exp_dims = [ar(d, 1110 + k, "string") for k, d in enumerate(dims)]
    exp_gid = ar("g_id", 1113, "long")
    rows = []
    for level in range(3, -1, -1):
        row = list(meas_attrs)
        for k, d in enumerate(dims):
            row.append(a(d) if k < level else null_s)
        row.append(F.lit(3 - level, "long"))
        rows.append(row)
    expand = F.expand(rows, meas_attrs + exp_dims + [exp_gid], base)
    agg = two_stage(
        exp_dims + [exp_gid],
        [(F.avg(m), 1120 + k) for k, m in enumerate(meas_attrs)],
        expand,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(exp_dims[1]), F.sort_order(exp_dims[2]),
         F.sort_order(exp_dims[0]), F.sort_order(exp_gid)],
        [F.alias(exp_dims[0], "i_item_id", 1130),
         F.alias(exp_dims[1], "ca_county", 1131),
         F.alias(exp_dims[2], "ca_state", 1132),
         F.alias(exp_gid, "g_id", 1133)]
        + [F.alias(ar(nm, 1120 + k, "double"), nm, 1134 + k)
           for k, (_, nm) in enumerate(measures)],
        agg,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q18(data)
    assert exp, "q18 oracle empty"
    n = len(got["i_item_id"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = (got["i_item_id"][i], got["ca_county"][i], got["ca_state"][i],
               got["g_id"][i])
        assert key in exp, key
        for k in range(7):
            assert abs(got[f"agg{k+1}"][i] - exp[key][k]) < 1e-9, (key, k)


# ---------------- q83 three-channel return shares

def test_spark_q83(sess, data, strategy):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2000)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year")])),
    )
    it = F.scan("item", [a("i_item_sk"), a("i_item_id")])

    def channel(rtab, r_date, r_item, r_qty, nm, base):
        rt = F.scan(rtab, [a(r_date), a(r_item), a(r_qty)])
        j = join(strategy, dt, rt, [a("d_date_sk")], [a(r_date)])
        j = join(strategy, it, j, [a("i_item_sk")], [a(r_item)])
        src = F.project(
            [F.alias(a("i_item_id"), f"{nm}_item_id", base),
             F.alias(F.cast(a(r_qty), "long"), "q", base + 1)], j)
        return two_stage(
            [ar(f"{nm}_item_id", base, "string")],
            [(F.sum_(ar("q", base + 1, "long")), base + 2)],
            src,
        )

    sr = channel("store_returns", "sr_returned_date_sk", "sr_item_sk",
                 "sr_return_quantity", "sr", 1200)
    cr = channel("catalog_returns", "cr_returned_date_sk", "cr_item_sk",
                 "cr_return_quantity", "cr", 1210)
    wr = channel("web_returns", "wr_returned_date_sk", "wr_item_sk",
                 "wr_return_quantity", "wr", 1220)
    sid = ar("sr_item_id", 1200, "string")
    j = big_join(strategy, sr, cr, [sid], [ar("cr_item_id", 1210, "string")])
    j = big_join(strategy, j, wr, [sid], [ar("wr_item_id", 1220, "string")])
    qty = {nm: ar(f"{nm}_qty", base + 2, "long")
           for nm, base in (("sr", 1200), ("cr", 1210), ("wr", 1220))}
    total = F.cast(
        F.binop("Add", F.binop("Add", qty["sr"], qty["cr"]), qty["wr"]),
        "double")
    outs = [F.alias(sid, "item_id", 1230),
            F.alias(qty["sr"], "sr_qty", 1231),
            F.alias(qty["cr"], "cr_qty", 1232),
            F.alias(qty["wr"], "wr_qty", 1233)]
    for k, nm in enumerate(("sr", "cr", "wr")):
        outs.append(F.alias(
            F.binop("Multiply",
                    F.binop("Divide", F.cast(qty[nm], "double"), total),
                    F.lit(100.0, "double")),
            f"{nm}_dev", 1234 + k))
    outs.append(F.alias(F.binop("Divide", total, F.lit(3.0, "double")),
                        "average", 1237))
    plan = F.take_ordered(
        100,
        [F.sort_order(sid), F.sort_order(qty["sr"])],
        outs,
        j,
    )
    got = _execute_both(sess, plan)
    exp = O.oracle_q83(data)
    assert exp, "q83 oracle empty"
    n = len(got["item_id"])
    assert n == min(len(exp), 100)
    for i in range(n):
        key = got["item_id"][i]
        assert key in exp, key
        a_, b_, c_, da, db, dc, avg = exp[key]
        assert (got["sr_qty"][i], got["cr_qty"][i],
                got["wr_qty"][i]) == (a_, b_, c_), key
        assert abs(got["sr_dev"][i] - da) < 1e-9
        assert abs(got["cr_dev"][i] - db) < 1e-9
        assert abs(got["wr_dev"][i] - dc) < 1e-9
        assert abs(got["average"][i] - avg) < 1e-9


# ---------------- q84 income-band returning customers

def test_spark_q84(ticket_sess, ticket_data, strategy):
    ca = F.project(
        [a("ca_address_sk")],
        F.filter_(F.binop("EqualTo", a("ca_city"), s("Midway")),
                  F.scan("customer_address", [a("ca_address_sk"),
                                              a("ca_city")])),
    )
    cust = F.scan("customer", [
        a("c_customer_id"), a("c_first_name"), a("c_last_name"),
        a("c_current_addr_sk"), a("c_current_cdemo_sk"),
        a("c_current_hdemo_sk")])
    j = join(strategy, ca, cust, [a("ca_address_sk")],
             [a("c_current_addr_sk")])
    ib = F.project(
        [a("ib_income_band_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("ib_lower_bound"),
                         i32(38128)),
                 F.binop("LessThanOrEqual", a("ib_upper_bound"),
                         i32(38128 + 50000))),
            F.scan("income_band", [a("ib_income_band_sk"),
                                   a("ib_lower_bound"),
                                   a("ib_upper_bound")])),
    )
    hd = F.scan("household_demographics", [a("hd_demo_sk"),
                                           a("hd_income_band_sk")])
    hd = join(strategy, ib, hd, [a("ib_income_band_sk")],
              [a("hd_income_band_sk")])
    hd = F.project([a("hd_demo_sk")], hd)
    j = join(strategy, hd, j, [a("hd_demo_sk")], [a("c_current_hdemo_sk")])
    cd = F.scan("customer_demographics", [a("cd_demo_sk")])
    j = join(strategy, cd, j, [a("cd_demo_sk")], [a("c_current_cdemo_sk")])
    sr = F.scan("store_returns", [a("sr_cdemo_sk")])
    j = big_join(strategy, j, sr, [a("cd_demo_sk")], [a("sr_cdemo_sk")],
                 build_side="left")
    name = F.T(F.X + "Concat",
               [a("c_last_name"), F.lit(", ", "string"), a("c_first_name")])
    plan = F.take_ordered(
        100, [F.sort_order(a("c_customer_id"))],
        [F.alias(a("c_customer_id"), "customer_id", 1250),
         F.alias(name, "customername", 1251)],
        j,
    )
    got = _execute_both(ticket_sess, plan)
    exp = O.oracle_q84(ticket_data)
    assert exp, "q84 oracle empty"
    rows = sorted(zip(got["customer_id"], got["customername"]))
    assert rows == exp
    assert got["customer_id"] == sorted(got["customer_id"])


# ------------- q57 catalog year-over-year window (q47's twin)

def test_spark_q57(sess, data, strategy):
    from test_tpcds import _check_yoy

    year = 1999
    dt = F.project(
        [a("d_date_sk"), a("d_year"), a("d_moy")],
        F.filter_(
            or_(
                F.binop("EqualTo", a("d_year"), i32(year)),
                and_(F.binop("EqualTo", a("d_year"), i32(year - 1)),
                     F.binop("EqualTo", a("d_moy"), i32(12))),
                and_(F.binop("EqualTo", a("d_year"), i32(year + 1)),
                     F.binop("EqualTo", a("d_moy"), i32(1))),
            ),
            F.scan("date_dim", [a("d_date_sk"), a("d_year"), a("d_moy")]),
        ),
    )
    cc = F.scan("call_center", [a("cc_call_center_sk"), a("cc_name")])
    it = F.scan("item", [a("i_item_sk"), a("i_brand"), a("i_category")])
    sales = F.scan(
        "catalog_sales",
        [a("cs_sold_date_sk"), a("cs_item_sk"), a("cs_call_center_sk"),
         a("cs_sales_price")],
    )
    j = join(strategy, dt, sales, [a("d_date_sk")], [a("cs_sold_date_sk")])
    j = join(strategy, cc, j, [a("cc_call_center_sk")],
             [a("cs_call_center_sk")])
    j = join(strategy, it, j, [a("i_item_sk")], [a("cs_item_sk")])
    part = [a("i_category"), a("i_brand"), a("cc_name")]
    agg = two_stage(
        part + [a("d_year"), a("d_moy")],
        [(F.sum_(a("cs_sales_price")), 501)],
        j,
    )
    sum_sales = ar("sum_sales", 501, "decimal(17,2)")
    single = F.shuffle(F.single_partition(), agg)
    pre = F.sort(
        [F.sort_order(p) for p in part]
        + [F.sort_order(a("d_year")), F.sort_order(a("d_moy"))],
        single,
    )
    w_avg = F.window(
        [F.window_expr(
            F.window_agg(F.avg(sum_sales)),
            F.window_spec(part + [a("d_year")], [],
                          F.window_frame("up", "uf", row=True)),
            "avg_monthly_sales", 502)],
        part + [a("d_year")],
        [],
        pre,
    )
    orders = [F.sort_order(a("d_year")), F.sort_order(a("d_moy"))]
    w = F.window(
        [F.window_expr(F.lag_fn(sum_sales), F.window_spec(part, orders),
                       "psum", 503),
         F.window_expr(F.lead_fn(sum_sales), F.window_spec(part, orders),
                       "nsum", 504)],
        part,
        orders,
        w_avg,
    )
    avg_m = ar("avg_monthly_sales", 502, "decimal(11,6)")
    sum_f = F.cast(sum_sales, "double")
    avg_f = F.cast(avg_m, "double")
    filt = F.filter_(
        and_(
            F.binop("EqualTo", a("d_year"), i32(year)),
            F.binop("GreaterThan", avg_m, i32(0)),
            F.binop(
                "GreaterThan",
                F.binop("Divide",
                        F.un("Abs", F.binop("Subtract", sum_f, avg_f)),
                        avg_f),
                F.lit(0.1, "double"),
            ),
        ),
        w,
    )
    proj = F.project(
        [a("i_category"), a("i_brand"), a("cc_name"),
         a("d_year"), a("d_moy"), sum_sales, avg_m,
         ar("psum", 503, "decimal(17,2)"), ar("nsum", 504, "decimal(17,2)"),
         F.alias(F.binop("Subtract", sum_f, avg_f), "delta", 510)],
        filt,
    )
    plan = F.take_ordered(
        100,
        [F.sort_order(ar("delta", 510, "double")), F.sort_order(a("d_moy"))],
        [F.alias(a("i_category"), "i_category", 520),
         F.alias(a("i_brand"), "i_brand", 521),
         F.alias(a("cc_name"), "cc_name", 522),
         F.alias(a("d_year"), "d_year", 524),
         F.alias(a("d_moy"), "d_moy", 525),
         F.alias(sum_sales, "sum_sales", 526),
         F.alias(avg_m, "avg_monthly_sales", 527),
         F.alias(ar("psum", 503, "decimal(17,2)"), "psum", 528),
         F.alias(ar("nsum", 504, "decimal(17,2)"), "nsum", 529)],
        proj,
    )
    got = _execute_both(sess, plan)
    _check_yoy(got, O.oracle_q57(data), ("cc_name",))


# ------------- q39a/b inventory cov month-over-month self-join

def _q39_monthly_cov_plan(st, moy, base):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(and_(F.binop("EqualTo", a("d_year"), i32(2001)),
                       F.binop("EqualTo", a("d_moy"), i32(moy))),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"),
                                      a("d_moy")])),
    )
    inv = F.scan("inventory", [a("inv_date_sk"), a("inv_item_sk"),
                               a("inv_warehouse_sk"),
                               a("inv_quantity_on_hand")])
    j = join(st, dt, inv, [a("d_date_sk")], [a("inv_date_sk")])
    wh = F.scan("warehouse", [a("w_warehouse_sk"), a("w_warehouse_name")])
    j = join(st, wh, j, [a("w_warehouse_sk")], [a("inv_warehouse_sk")])
    agg = two_stage(
        [a("w_warehouse_name"), a("inv_item_sk")],
        [(F.avg(a("inv_quantity_on_hand")), base),
         (F.T(F.A + "StddevSamp", [a("inv_quantity_on_hand")]), base + 1)],
        j,
    )
    mean = ar("mean", base, "double")
    stdev = ar("stdev", base + 1, "double")
    cov = F.T(F.X + "CaseWhen",
              [F.binop("GreaterThan", mean, F.lit(0.0, "double")),
               F.binop("Divide", stdev, mean)])
    return F.project(
        [a("w_warehouse_name"), a("inv_item_sk"), mean,
         F.alias(cov, "cov", base + 2)], agg)


def _q39_plan(st, thr1, thr2):
    m1 = F.filter_(
        F.binop("GreaterThan", ar("cov", 1302, "double"),
                F.lit(thr1, "double")),
        _q39_monthly_cov_plan(st, 1, 1300))
    m2 = F.filter_(
        F.binop("GreaterThan", ar("cov", 1312, "double"),
                F.lit(thr2, "double")),
        _q39_monthly_cov_plan(st, 2, 1310))
    m2 = F.project(
        [F.alias(a("w_warehouse_name"), "w2", 1320),
         F.alias(a("inv_item_sk"), "i2", 1321),
         F.alias(ar("mean", 1310, "double"), "mean2", 1322),
         F.alias(ar("cov", 1312, "double"), "cov2", 1323)],
        m2,
    )
    j = big_join(st, m1, m2, [a("w_warehouse_name"), a("inv_item_sk")],
                 [ar("w2", 1320, "string"), ar("i2", 1321, "long")])
    return F.take_ordered(
        100,
        [F.sort_order(a("w_warehouse_name")), F.sort_order(a("inv_item_sk"))],
        [F.alias(a("w_warehouse_name"), "w_warehouse_name", 1330),
         F.alias(a("inv_item_sk"), "inv_item_sk", 1331),
         F.alias(ar("mean", 1300, "double"), "mean", 1332),
         F.alias(ar("cov", 1302, "double"), "cov", 1333),
         F.alias(ar("mean2", 1322, "double"), "mean2", 1334),
         F.alias(ar("cov2", 1323, "double"), "cov2", 1335)],
        j,
    )


def test_spark_q39a(sess, data, strategy):
    from test_tpcds import _check_q39

    got = _execute_both(sess, _q39_plan(strategy, 0.7, 0.7))
    _check_q39(got, O.oracle_q39a(data))


def test_spark_q39b(sess, data, strategy):
    from test_tpcds import _check_q39

    got = _execute_both(sess, _q39_plan(strategy, 0.85, 0.7))
    _check_q39(got, O.oracle_q39b(data))


# ------------- q49 worst return ratios double-ranked per channel

def _q49_channel_plan(st, channel, fact, ret, s_item, s_ord, s_qty, s_paid,
                      s_profit, r_item, r_ord, r_qty, r_amt, date_c, base):
    dt = F.project(
        [a("d_date_sk")],
        F.filter_(and_(F.binop("EqualTo", a("d_year"), i32(2001)),
                       F.binop("EqualTo", a("d_moy"), i32(12))),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"),
                                      a("d_moy")])),
    )
    sl = F.project(
        [a(date_c), a(s_item), a(s_ord), a(s_qty), a(s_paid)],
        F.filter_(
            and_(F.binop("GreaterThan", F.cast(a(s_profit), "double"),
                         F.lit(1.0, "double")),
                 F.binop("GreaterThan", F.cast(a(s_paid), "double"),
                         F.lit(0.0, "double")),
                 F.binop("GreaterThan", a(s_qty), i32(0))),
            F.scan(fact, [a(date_c), a(s_item), a(s_ord), a(s_qty),
                          a(s_paid), a(s_profit)]),
        ),
    )
    j = join(st, dt, sl, [a("d_date_sk")], [a(date_c)])
    rt = F.project(
        [a(r_item), a(r_ord), a(r_qty), a(r_amt)],
        F.filter_(F.binop("GreaterThan", F.cast(a(r_amt), "double"),
                          F.lit(250.0, "double")),
                  F.scan(ret, [a(r_item), a(r_ord), a(r_qty), a(r_amt)])),
    )
    j = big_join(st, j, rt, [a(s_ord), a(s_item)], [a(r_ord), a(r_item)])
    src = F.project(
        [F.alias(a(s_item), "item", base), a(r_qty), a(s_qty), a(r_amt),
         a(s_paid)], j)
    agg = two_stage(
        [ar("item", base, "long")],
        [(F.sum_(a(r_qty)), base + 1), (F.sum_(a(s_qty)), base + 2),
         (F.sum_(a(r_amt)), base + 3), (F.sum_(a(s_paid)), base + 4)],
        src,
    )
    f64 = "double"
    rr = F.binop("Divide",
                 F.cast(ar("ret_q", base + 1, "long"), f64),
                 F.cast(ar("qty", base + 2, "long"), f64))
    cur = F.binop("Divide",
                  F.cast(ar("ret_amt", base + 3, "decimal(17,2)"), f64),
                  F.cast(ar("paid", base + 4, "decimal(17,2)"), f64))
    ratios = F.project(
        [ar("item", base, "long"), F.alias(rr, "return_ratio", base + 5),
         F.alias(cur, "currency_ratio", base + 6)],
        agg,
    )
    rr_a = ar("return_ratio", base + 5, f64)
    cur_a = ar("currency_ratio", base + 6, f64)
    single = F.shuffle(F.single_partition(), ratios)
    s1 = F.sort([F.sort_order(rr_a)], single)
    w1 = F.window(
        [F.window_expr(F.rank_fn([rr_a]), F.window_spec([], [F.sort_order(rr_a)]),
                       "return_rank", base + 7)],
        [], [F.sort_order(rr_a)], s1)
    s2 = F.sort([F.sort_order(cur_a)], w1)
    w2 = F.window(
        [F.window_expr(F.rank_fn([cur_a]),
                       F.window_spec([], [F.sort_order(cur_a)]),
                       "currency_rank", base + 8)],
        [], [F.sort_order(cur_a)], s2)
    rrank = ar("return_rank", base + 7, "integer")
    crank = ar("currency_rank", base + 8, "integer")
    f = F.filter_(
        or_(F.binop("LessThanOrEqual", rrank, i32(10)),
            F.binop("LessThanOrEqual", crank, i32(10))),
        w2,
    )
    # union arms share output exprIds (1400-1404)
    return F.project(
        [F.alias(F.lit(channel, "string"), "channel", 1400),
         F.alias(ar("item", base, "long"), "item", 1401),
         F.alias(rr_a, "return_ratio", 1402),
         F.alias(rrank, "return_rank", 1403),
         F.alias(crank, "currency_rank", 1404)],
        f,
    )


def test_spark_q49(ticket_sess, ticket_data, strategy):
    web = _q49_channel_plan(
        strategy, "web", "web_sales", "web_returns", "ws_item_sk",
        "ws_order_number", "ws_quantity", "ws_net_paid", "ws_net_profit",
        "wr_item_sk", "wr_order_number", "wr_return_quantity",
        "wr_return_amt", "ws_sold_date_sk", 1410)
    cat = _q49_channel_plan(
        strategy, "catalog", "catalog_sales", "catalog_returns", "cs_item_sk",
        "cs_order_number", "cs_quantity", "cs_net_paid", "cs_net_profit",
        "cr_item_sk", "cr_order_number", "cr_return_quantity",
        "cr_return_amount", "cs_sold_date_sk", 1430)
    store = _q49_channel_plan(
        strategy, "store", "store_sales", "store_returns", "ss_item_sk",
        "ss_ticket_number", "ss_quantity", "ss_net_paid", "ss_net_profit",
        "sr_item_sk", "sr_ticket_number", "sr_return_quantity",
        "sr_return_amt", "ss_sold_date_sk", 1450)
    u = F.union([web, cat, store])
    ch = ar("channel", 1400, "string")
    rrank = ar("return_rank", 1403, "integer")
    crank = ar("currency_rank", 1404, "integer")
    plan = F.take_ordered(
        100,
        [F.sort_order(ch), F.sort_order(rrank), F.sort_order(crank)],
        [F.alias(ch, "channel", 1470),
         F.alias(ar("item", 1401, "long"), "item", 1471),
         F.alias(ar("return_ratio", 1402, "double"), "return_ratio", 1472),
         F.alias(rrank, "return_rank", 1473),
         F.alias(crank, "currency_rank", 1474)],
        u,
    )
    got = _execute_both(ticket_sess, plan)
    exp = O.oracle_q49(ticket_data)
    assert exp, "q49 oracle empty"
    rows = set(zip(got["channel"], got["item"], got["return_ratio"],
                   got["return_rank"], got["currency_rank"]))
    assert rows == exp
    keys = list(zip(got["channel"], got["return_rank"],
                    got["currency_rank"]))
    assert keys == sorted(keys)


# ------------------ q5 channel sales/returns/profit ROLLUP

def _channel_report_tail_plan(st, union_plan):
    """Shared q5-family tail: ROLLUP(channel, id) via Expand + two-stage
    agg, ORDER BY channel, id NULLS FIRST LIMIT 100.  Union arms must
    alias (channel 1500, id 1501, sales 1502, returns 1503,
    profit 1504)."""
    ch = ar("channel", 1500, "string")
    idc = ar("id", 1501, "string")
    sales = ar("sales", 1502, "decimal(8,2)")
    rets = ar("returns", 1503, "decimal(8,2)")
    prof = ar("profit", 1504, "decimal(9,2)")
    null_s = F.lit(None, "string")
    exp_ch = ar("channel", 1510, "string")
    exp_id = ar("id", 1511, "string")
    exp_gid = ar("g_id", 1512, "integer")
    vals = [sales, rets, prof]
    expand = F.expand(
        [
            vals + [ch, idc, F.lit(0, "integer")],
            vals + [ch, null_s, F.lit(1, "integer")],
            vals + [null_s, null_s, F.lit(3, "integer")],
        ],
        vals + [exp_ch, exp_id, exp_gid],
        union_plan,
    )
    agg = two_stage(
        [exp_ch, exp_id, exp_gid],
        [(F.sum_(sales), 1520), (F.sum_(rets), 1521), (F.sum_(prof), 1522)],
        expand,
    )
    return F.take_ordered(
        100,
        [F.sort_order(exp_ch), F.sort_order(exp_id)],
        [F.alias(exp_ch, "channel", 1530), F.alias(exp_id, "id", 1531),
         F.alias(ar("sales", 1520, "decimal(18,2)"), "sales", 1532),
         F.alias(ar("returns", 1521, "decimal(18,2)"), "returns", 1533),
         F.alias(ar("profit", 1522, "decimal(19,2)"), "profit", 1534)],
        agg,
    )


def test_spark_q5(sess, data, strategy):
    from test_tpcds import _check_channel_report

    dt = F.project(
        [a("d_date_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("d_date"),
                         F.lit("2000-08-23", "date")),
                 F.binop("LessThanOrEqual", a("d_date"),
                         F.lit("2000-09-05", "date"))),
            F.scan("date_dim", [a("d_date_sk"), a("d_date")]),
        ),
    )
    dz = F.lit("0", "decimal(7,2)")

    def d8(e):
        return F.binop("Add", e, dz)

    def neg(e):
        return F.binop("Subtract", dz, e)

    def arm(id_expr, sales_e, ret_e, prof_e, src):
        return F.project(
            [F.alias(id_expr, "id", 1501), F.alias(sales_e, "sales", 1502),
             F.alias(ret_e, "returns", 1503), F.alias(prof_e, "profit", 1504)],
            src,
        )

    def tag(plan, channel):
        return F.project(
            [F.alias(F.lit(channel, "string"), "channel", 1500),
             ar("id", 1501, "string"), ar("sales", 1502, "decimal(8,2)"),
             ar("returns", 1503, "decimal(8,2)"),
             ar("profit", 1504, "decimal(9,2)")],
            plan,
        )

    # store channel
    st_ = F.scan("store", [a("s_store_sk"), a("s_store_name")])
    sl = F.scan("store_sales", [a("ss_sold_date_sk"), a("ss_store_sk"),
                                a("ss_ext_sales_price"), a("ss_net_profit")])
    j = join(strategy, dt, sl, [a("d_date_sk")], [a("ss_sold_date_sk")])
    j = join(strategy, st_, j, [a("s_store_sk")], [a("ss_store_sk")])
    s_sales = arm(a("s_store_name"), d8(a("ss_ext_sales_price")), d8(dz),
                  d8(a("ss_net_profit")), j)
    sr = F.scan("store_returns", [a("sr_returned_date_sk"), a("sr_store_sk"),
                                  a("sr_return_amt"), a("sr_net_loss")])
    jr = join(strategy, dt, sr, [a("d_date_sk")], [a("sr_returned_date_sk")])
    jr = join(strategy, st_, jr, [a("s_store_sk")], [a("sr_store_sk")])
    s_ret = arm(a("s_store_name"), d8(dz), d8(a("sr_return_amt")),
                neg(a("sr_net_loss")), jr)
    store_rows = tag(F.union([s_sales, s_ret]), "store channel")

    # catalog channel
    cp = F.scan("catalog_page", [a("cp_catalog_page_sk"),
                                 a("cp_catalog_page_id")])
    cl = F.scan("catalog_sales", [a("cs_sold_date_sk"), a("cs_catalog_page_sk"),
                                  a("cs_ext_sales_price"), a("cs_net_profit")])
    j = join(strategy, dt, cl, [a("d_date_sk")], [a("cs_sold_date_sk")])
    j = join(strategy, cp, j, [a("cp_catalog_page_sk")],
             [a("cs_catalog_page_sk")])
    c_sales = arm(a("cp_catalog_page_id"), d8(a("cs_ext_sales_price")),
                  d8(dz), d8(a("cs_net_profit")), j)
    cr = F.scan("catalog_returns",
                [a("cr_returned_date_sk"), a("cr_catalog_page_sk"),
                 a("cr_return_amount"), a("cr_net_loss")])
    jr = join(strategy, dt, cr, [a("d_date_sk")], [a("cr_returned_date_sk")])
    jr = join(strategy, cp, jr, [a("cp_catalog_page_sk")],
              [a("cr_catalog_page_sk")])
    c_ret = arm(a("cp_catalog_page_id"), d8(dz), d8(a("cr_return_amount")),
                neg(a("cr_net_loss")), jr)
    cat_rows = tag(F.union([c_sales, c_ret]), "catalog channel")

    # web channel (returns recover the site via (item, order))
    wsit = F.scan("web_site", [a("web_site_sk"), a("web_name")])
    wl = F.scan("web_sales", [a("ws_sold_date_sk"), a("ws_web_site_sk"),
                              a("ws_ext_sales_price"), a("ws_net_profit")])
    j = join(strategy, dt, wl, [a("d_date_sk")], [a("ws_sold_date_sk")])
    j = join(strategy, wsit, j, [a("web_site_sk")], [a("ws_web_site_sk")])
    w_sales = arm(a("web_name"), d8(a("ws_ext_sales_price")), d8(dz),
                  d8(a("ws_net_profit")), j)
    wr = F.scan("web_returns",
                [a("wr_returned_date_sk"), a("wr_item_sk"),
                 a("wr_order_number"), a("wr_return_amt"), a("wr_net_loss")])
    jr = join(strategy, dt, wr, [a("d_date_sk")], [a("wr_returned_date_sk")])
    ws_keys = F.scan("web_sales", [a("ws_item_sk"), a("ws_order_number"),
                                   a("ws_web_site_sk")])
    jr = big_join(strategy, jr, ws_keys,
                  [a("wr_item_sk"), a("wr_order_number")],
                  [a("ws_item_sk"), a("ws_order_number")])
    jr = join(strategy, wsit, jr, [a("web_site_sk")], [a("ws_web_site_sk")])
    w_ret = arm(a("web_name"), d8(dz), d8(a("wr_return_amt")),
                neg(a("wr_net_loss")), jr)
    web_rows = tag(F.union([w_sales, w_ret]), "web channel")

    plan = _channel_report_tail_plan(
        strategy, F.union([store_rows, cat_rows, web_rows]))
    got = _execute_both(sess, plan)
    _check_channel_report(got, O.oracle_q5(data))


# --------------- q31 county store-vs-web quarterly growth

def test_spark_q31(ticket_sess, ticket_data, strategy):
    def channel(fact, date_c, addr_c, price_c, qoy, base):
        dt = F.project(
            [a("d_date_sk")],
            F.filter_(and_(F.binop("EqualTo", a("d_year"), i32(2000)),
                           F.binop("EqualTo", a("d_qoy"), i32(qoy))),
                      F.scan("date_dim", [a("d_date_sk"), a("d_year"),
                                          a("d_qoy")])),
        )
        sl = F.scan(fact, [a(date_c), a(addr_c), a(price_c)])
        j = join(strategy, dt, sl, [a("d_date_sk")], [a(date_c)])
        ca = F.scan("customer_address", [a("ca_address_sk"), a("ca_county")])
        j = join(strategy, ca, j, [a("ca_address_sk")], [a(addr_c)])
        src = F.project(
            [F.alias(a("ca_county"), "county", base), a(price_c)], j)
        return two_stage(
            [ar("county", base, "string")],
            [(F.sum_(a(price_c)), base + 1)], src)

    b = {}
    for k, (pre, fact, date_c, addr_c, price_c) in enumerate((
        ("ss1", "store_sales", "ss_sold_date_sk", "ss_addr_sk",
         "ss_ext_sales_price"),
        ("ss2", "store_sales", "ss_sold_date_sk", "ss_addr_sk",
         "ss_ext_sales_price"),
        ("ss3", "store_sales", "ss_sold_date_sk", "ss_addr_sk",
         "ss_ext_sales_price"),
        ("ws1", "web_sales", "ws_sold_date_sk", "ws_bill_addr_sk",
         "ws_ext_sales_price"),
        ("ws2", "web_sales", "ws_sold_date_sk", "ws_bill_addr_sk",
         "ws_ext_sales_price"),
        ("ws3", "web_sales", "ws_sold_date_sk", "ws_bill_addr_sk",
         "ws_ext_sales_price"),
    )):
        b[pre] = (channel(fact, date_c, addr_c, price_c, int(pre[-1]),
                          1600 + 10 * k), 1600 + 10 * k)

    j, _ = b["ss1"]
    county = ar("county", 1600, "string")
    for pre in ("ss2", "ss3", "ws1", "ws2", "ws3"):
        arm_plan, base = b[pre]
        j = big_join(strategy, j, arm_plan, [county],
                     [ar("county", base, "string")])
    sales = {pre: ar("sales", base + 1, "decimal(17,2)")
             for pre, (_, base) in b.items()}
    fl = lambda e: F.cast(e, "double")

    def ratio(num, den):
        return F.binop("Divide", fl(num), fl(den))

    def guarded(num, den):
        return F.T(F.X + "CaseWhen",
                   [F.binop("GreaterThan", fl(den), F.lit(0.0, "double")),
                    ratio(num, den)])

    web12 = guarded(sales["ws2"], sales["ws1"])
    store12 = guarded(sales["ss2"], sales["ss1"])
    web23 = guarded(sales["ws3"], sales["ws2"])
    store23 = guarded(sales["ss3"], sales["ss2"])
    f = F.filter_(
        or_(F.binop("GreaterThan", web12, store12),
            F.binop("GreaterThan", web23, store23)),
        j,
    )
    plan = F.take_ordered(
        100, [F.sort_order(county)],
        [F.alias(county, "ca_county", 1700),
         F.alias(F.lit(2000, "integer"), "d_year", 1701),
         F.alias(ratio(sales["ws2"], sales["ws1"]), "web_q1_q2_increase", 1702),
         F.alias(ratio(sales["ss2"], sales["ss1"]), "store_q1_q2_increase", 1703),
         F.alias(ratio(sales["ws3"], sales["ws2"]), "web_q2_q3_increase", 1704),
         F.alias(ratio(sales["ss3"], sales["ss2"]), "store_q2_q3_increase", 1705)],
        f,
    )
    got = _execute_both(ticket_sess, plan)
    exp = O.oracle_q31(ticket_data)
    assert exp, "q31 oracle empty"
    rows = {
        c: (w12, s12, w23, s23)
        for c, w12, s12, w23, s23 in zip(
            got["ca_county"], got["web_q1_q2_increase"],
            got["store_q1_q2_increase"], got["web_q2_q3_increase"],
            got["store_q2_q3_increase"])
    }
    assert set(rows) == set(exp)
    for c, vals in rows.items():
        assert vals == pytest.approx(exp[c], rel=1e-12), c
    assert got["d_year"] == [2000] * len(rows)


# ----------- q58 cross-channel items sold evenly (month window)

def test_spark_q58(ticket_sess, ticket_data, strategy):
    wk = distinct(
        [ar("wk_sel", 1800, "integer")],
        F.project([F.alias(a("d_month_seq"), "wk_sel", 1800)],
                  F.filter_(F.binop("EqualTo", a("d_date"),
                                    F.lit("2000-01-03", "date")),
                            F.scan("date_dim", [a("d_date"),
                                                a("d_month_seq")]))),
    )
    wk_seq = _scalar_subquery(wk, 1800)

    def channel(fact, item_c, date_c, price_c, base):
        dd = F.project(
            [a("d_date_sk")],
            F.filter_(F.binop("EqualTo", a("d_month_seq"), wk_seq),
                      F.scan("date_dim", [a("d_date_sk"), a("d_month_seq")])),
        )
        sl = F.scan(fact, [a(date_c), a(item_c), a(price_c)])
        j = join(strategy, dd, sl, [a("d_date_sk")], [a(date_c)])
        it = F.scan("item", [a("i_item_sk"), a("i_item_id")])
        j = join(strategy, it, j, [a("i_item_sk")], [a(item_c)])
        src = F.project(
            [F.alias(a("i_item_id"), "item_id", base), a(price_c)], j)
        return two_stage(
            [ar("item_id", base, "string")],
            [(F.sum_(a(price_c)), base + 1)], src)

    ss_items = channel("store_sales", "ss_item_sk", "ss_sold_date_sk",
                       "ss_ext_sales_price", 1810)
    cs_items = channel("catalog_sales", "cs_item_sk", "cs_sold_date_sk",
                       "cs_ext_sales_price", 1820)
    ws_items = channel("web_sales", "ws_item_sk", "ws_sold_date_sk",
                       "ws_ext_sales_price", 1830)
    iid = ar("item_id", 1810, "string")
    j = big_join(strategy, ss_items, cs_items, [iid],
                 [ar("item_id", 1820, "string")])
    j = big_join(strategy, j, ws_items, [iid],
                 [ar("item_id", 1830, "string")])
    rev = {p: ar("rev", b + 1, "decimal(17,2)")
           for p, b in (("ss", 1810), ("cs", 1820), ("ws", 1830))}
    fl = lambda e: F.cast(e, "double")

    def near(x, y):
        return and_(
            F.binop("GreaterThanOrEqual", fl(x),
                    F.binop("Multiply", F.lit(0.25, "double"), fl(y))),
            F.binop("LessThanOrEqual", fl(x),
                    F.binop("Multiply", F.lit(4.0, "double"), fl(y))))

    f = F.filter_(
        and_(near(rev["ss"], rev["cs"]), near(rev["ss"], rev["ws"]),
             near(rev["cs"], rev["ss"]), near(rev["cs"], rev["ws"]),
             near(rev["ws"], rev["ss"]), near(rev["ws"], rev["cs"])),
        j,
    )
    total = F.binop("Add", F.binop("Add", fl(rev["ss"]), fl(rev["cs"])),
                    fl(rev["ws"]))

    def dev(x):
        return F.binop(
            "Multiply",
            F.binop("Divide", F.binop("Divide", fl(x), total),
                    F.lit(3.0, "double")),
            F.lit(100.0, "double"))

    plan = F.take_ordered(
        100,
        [F.sort_order(iid), F.sort_order(rev["ss"])],
        [F.alias(iid, "item_id", 1840),
         F.alias(rev["ss"], "ss_item_rev", 1841),
         F.alias(dev(rev["ss"]), "ss_dev", 1842),
         F.alias(rev["cs"], "cs_item_rev", 1843),
         F.alias(dev(rev["cs"]), "cs_dev", 1844),
         F.alias(rev["ws"], "ws_item_rev", 1845),
         F.alias(dev(rev["ws"]), "ws_dev", 1846),
         F.alias(F.binop("Divide", total, F.lit(3.0, "double")),
                 "average", 1847)],
        f,
    )
    got = _execute_both(ticket_sess, plan)
    exp = O.oracle_q58(ticket_data)
    assert exp, "q58 oracle empty"
    rows = {
        i_: (sr, sd, cr, cd, wr, wd, avg)
        for i_, sr, sd, cr, cd, wr, wd, avg in zip(
            got["item_id"], got["ss_item_rev"], got["ss_dev"],
            got["cs_item_rev"], got["cs_dev"], got["ws_item_rev"],
            got["ws_dev"], got["average"])
    }
    assert set(rows) == set(exp)
    for i_, (sr, sd, cr, cd, wr, wd, avg) in rows.items():
        e = exp[i_]
        assert (sr, cr, wr) == (e[0], e[2], e[4]), i_
        assert (sd, cd, wd, avg) == pytest.approx(
            (e[1], e[3], e[5], e[6]), rel=1e-12), i_
    assert got["item_id"] == sorted(got["item_id"])


# ------------- q71 brand sales by meal-time minute

def test_spark_q71(ticket_sess, ticket_data, strategy):
    it = F.project(
        [a("i_item_sk"), a("i_brand_id"), a("i_brand")],
        F.filter_(F.binop("EqualTo", a("i_manager_id"), i32(1)),
                  F.scan("item", [a("i_item_sk"), a("i_brand_id"),
                                  a("i_brand"), a("i_manager_id")])),
    )
    parts = []
    for fact, price_c, date_c, item_c, time_c in (
        ("web_sales", "ws_ext_sales_price", "ws_sold_date_sk",
         "ws_item_sk", "ws_sold_time_sk"),
        ("catalog_sales", "cs_ext_sales_price", "cs_sold_date_sk",
         "cs_item_sk", "cs_sold_time_sk"),
        ("store_sales", "ss_ext_sales_price", "ss_sold_date_sk",
         "ss_item_sk", "ss_sold_time_sk"),
    ):
        dt = F.project(
            [a("d_date_sk")],
            F.filter_(and_(F.binop("EqualTo", a("d_moy"), i32(11)),
                           F.binop("EqualTo", a("d_year"), i32(1999))),
                      F.scan("date_dim", [a("d_date_sk"), a("d_moy"),
                                          a("d_year")])),
        )
        sl = F.project(
            [F.alias(a(price_c), "ext_price_v", 1900),
             F.alias(a(date_c), "sold_date_sk", 1901),
             F.alias(a(item_c), "sold_item_sk", 1902),
             F.alias(a(time_c), "time_sk", 1903)],
            F.scan(fact, [a(price_c), a(date_c), a(item_c), a(time_c)]))
        parts.append(join(strategy, dt, sl, [a("d_date_sk")],
                          [ar("sold_date_sk", 1901, "long")]))
    u = F.union(parts)
    j = join(strategy, it, u, [a("i_item_sk")],
             [ar("sold_item_sk", 1902, "long")])
    tm = F.project(
        [a("t_time_sk"), a("t_hour"), a("t_minute")],
        F.filter_(or_(F.binop("EqualTo", a("t_meal_time"), s("breakfast")),
                      F.binop("EqualTo", a("t_meal_time"), s("dinner"))),
                  F.scan("time_dim", [a("t_time_sk"), a("t_hour"),
                                      a("t_minute"), a("t_meal_time")])),
    )
    j = join(strategy, tm, j, [a("t_time_sk")], [ar("time_sk", 1903, "long")])
    agg = two_stage(
        [a("i_brand_id"), a("i_brand"), a("t_hour"), a("t_minute")],
        [(F.sum_(ar("ext_price_v", 1900, "decimal(7,2)")), 1910)],
        j,
    )
    price = ar("ext_price", 1910, "decimal(17,2)")
    plan = F.take_ordered(
        100,
        [F.sort_order(price, asc=False), F.sort_order(a("i_brand_id"))],
        [F.alias(a("i_brand_id"), "brand_id", 1920),
         F.alias(a("i_brand"), "brand", 1921),
         F.alias(a("t_hour"), "t_hour", 1922),
         F.alias(a("t_minute"), "t_minute", 1923),
         F.alias(price, "ext_price", 1924)],
        agg,
    )
    got = _execute_both(ticket_sess, plan)
    exp = O.oracle_q71(ticket_data)
    assert exp, "q71 oracle empty"
    rows = dict(zip(zip(got["brand_id"], got["brand"], got["t_hour"],
                        got["t_minute"]), got["ext_price"]))
    assert rows == exp
    keys = list(zip([-p for p in got["ext_price"]], got["brand_id"]))
    assert keys == sorted(keys)


# ------------- q66 warehouse monthly sales/net pivot

_Q66_MONTHS = ("jan", "feb", "mar", "apr", "may", "jun", "jul", "aug",
               "sep", "oct", "nov", "dec")
_Q66_KEYS = ("w_warehouse_name", "w_warehouse_sq_ft", "w_city", "w_county",
             "w_state", "w_country")


def _q66_channel_plan(st, fact, wh_c, date_c, time_c, mode_c, qty_c,
                      sales_c, net_c):
    dt = F.project(
        [a("d_date_sk"), a("d_moy")],
        F.filter_(F.binop("EqualTo", a("d_year"), i32(2001)),
                  F.scan("date_dim", [a("d_date_sk"), a("d_year"),
                                      a("d_moy")])),
    )
    tm = F.project(
        [a("t_time_sk")],
        F.filter_(and_(F.binop("GreaterThanOrEqual", a("t_time"),
                               F.lit(30838, "long")),
                       F.binop("LessThanOrEqual", a("t_time"),
                               F.lit(30838 + 28800, "long"))),
                  F.scan("time_dim", [a("t_time_sk"), a("t_time")])),
    )
    sm = F.project(
        [a("sm_ship_mode_sk")],
        F.filter_(in_(a("sm_carrier"), "DHL", "BARIAN"),
                  F.scan("ship_mode", [a("sm_ship_mode_sk"),
                                       a("sm_carrier")])),
    )
    sl = F.scan(fact, [a(wh_c), a(date_c), a(time_c), a(mode_c), a(qty_c),
                       a(sales_c), a(net_c)])
    j = join(st, dt, sl, [a("d_date_sk")], [a(date_c)])
    j = join(st, tm, j, [a("t_time_sk")], [a(time_c)])
    j = join(st, sm, j, [a("sm_ship_mode_sk")], [a(mode_c)])
    wh = F.scan("warehouse", [a("w_warehouse_sk")] + [a(k) for k in _Q66_KEYS])
    j = join(st, wh, j, [a("w_warehouse_sk")], [a(wh_c)])
    qdec = F.cast(a(qty_c), "decimal(10,0)")
    sales = F.binop("Multiply", a(sales_c), qdec)
    net = F.binop("Multiply", a(net_c), qdec)
    pivots = []
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        pivots.append(F.alias(
            F.T(F.X + "CaseWhen",
                [F.binop("EqualTo", a("d_moy"), i32(m)), sales]),
            f"{nm}_sales_v", 2000 + m))
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        pivots.append(F.alias(
            F.T(F.X + "CaseWhen",
                [F.binop("EqualTo", a("d_moy"), i32(m)), net]),
            f"{nm}_net_v", 2020 + m))
    proj = F.project([a(k) for k in _Q66_KEYS] + pivots, j)
    agg = two_stage(
        [a(k) for k in _Q66_KEYS],
        [(F.sum_(ar(f"{nm}_sales_v", 2000 + m, "decimal(18,2)")), 2040 + m)
         for m, nm in enumerate(_Q66_MONTHS, start=1)]
        + [(F.sum_(ar(f"{nm}_net_v", 2020 + m, "decimal(18,2)")), 2060 + m)
           for m, nm in enumerate(_Q66_MONTHS, start=1)],
        proj,
    )
    outs = [a(k) for k in _Q66_KEYS] + [
        F.alias(F.lit("DHL,BARIAN", "string"), "ship_carriers", 2080),
        F.alias(F.lit(2001, "integer"), "year", 2081),
    ]
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        outs.append(F.alias(ar(f"{nm}_sales", 2040 + m, "decimal(28,2)"),
                            f"{nm}_sales", 2100 + m))
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        outs.append(F.alias(
            F.binop("Divide",
                    F.cast(ar(f"{nm}_sales", 2040 + m, "decimal(28,2)"),
                           "double"),
                    F.cast(a("w_warehouse_sq_ft"), "double")),
            f"{nm}_sales_per_sq_foot", 2120 + m))
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        outs.append(F.alias(ar(f"{nm}_net", 2060 + m, "decimal(28,2)"),
                            f"{nm}_net", 2140 + m))
    return F.project(outs, agg)


def test_spark_q66(sess, data, strategy):
    web = _q66_channel_plan(
        strategy, "web_sales", "ws_warehouse_sk", "ws_sold_date_sk",
        "ws_sold_time_sk", "ws_ship_mode_sk", "ws_quantity",
        "ws_ext_sales_price", "ws_net_paid")
    cat = _q66_channel_plan(
        strategy, "catalog_sales", "cs_warehouse_sk", "cs_sold_date_sk",
        "cs_sold_time_sk", "cs_ship_mode_sk", "cs_quantity",
        "cs_sales_price", "cs_net_paid_inc_tax")
    u = F.union([web, cat])
    groups = [a(k) for k in _Q66_KEYS] + [
        ar("ship_carriers", 2080, "string"), ar("year", 2081, "integer")]
    aggs = []
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        aggs.append((F.sum_(ar(f"{nm}_sales", 2100 + m, "decimal(28,2)")),
                     2200 + m))
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        aggs.append((F.sum_(
            ar(f"{nm}_sales_per_sq_foot", 2120 + m, "double")), 2220 + m))
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        aggs.append((F.sum_(ar(f"{nm}_net", 2140 + m, "decimal(28,2)")),
                     2240 + m))
    agg = two_stage(groups, aggs, u)
    outs = [F.alias(a(k), k, 2300 + i) for i, k in enumerate(_Q66_KEYS)]
    outs += [F.alias(ar("ship_carriers", 2080, "string"), "ship_carriers",
                     2310),
             F.alias(ar("year", 2081, "integer"), "year", 2311)]
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        outs.append(F.alias(ar(f"{nm}_sales", 2200 + m, "decimal(38,2)"),
                            f"{nm}_sales", 2320 + m))
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        outs.append(F.alias(ar(f"{nm}_sales_per_sq_foot", 2220 + m, "double"),
                            f"{nm}_sales_per_sq_foot", 2340 + m))
    for m, nm in enumerate(_Q66_MONTHS, start=1):
        outs.append(F.alias(ar(f"{nm}_net", 2240 + m, "decimal(38,2)"),
                            f"{nm}_net", 2360 + m))
    plan = F.take_ordered(
        100, [F.sort_order(a("w_warehouse_name"))], outs, agg)
    got = _execute_both(sess, plan)
    exp = O.oracle_q66(data)
    assert exp, "q66 oracle empty"
    assert got["w_warehouse_name"] == sorted(exp)
    for i, name in enumerate(got["w_warehouse_name"]):
        sq_ft, city, cty, state, country, sales_e, ratios, nets = exp[name]
        assert (got["w_warehouse_sq_ft"][i], got["w_city"][i],
                got["w_county"][i], got["w_state"][i],
                got["w_country"][i]) == (sq_ft, city, cty, state, country)
        assert got["ship_carriers"][i] == "DHL,BARIAN"
        assert got["year"][i] == 2001
        for m, nm in enumerate(_Q66_MONTHS):
            assert got[f"{nm}_sales"][i] == sales_e[m], (name, nm)
            assert got[f"{nm}_net"][i] == nets[m], (name, nm)
            g = got[f"{nm}_sales_per_sq_foot"][i]
            if ratios[m] is None:
                assert g is None, (name, nm)
            else:
                assert g == pytest.approx(ratios[m], rel=1e-12), (name, nm)


# ------------- q80 per-item channel totals net of returns

def test_spark_q80(sess, data, strategy):
    from test_tpcds import _check_channel_report

    dt = F.project(
        [a("d_date_sk")],
        F.filter_(
            and_(F.binop("GreaterThanOrEqual", a("d_date"),
                         F.lit("2000-08-03", "date")),
                 F.binop("LessThanOrEqual", a("d_date"),
                         F.lit("2000-09-01", "date"))),
            F.scan("date_dim", [a("d_date_sk"), a("d_date")]),
        ),
    )
    dz = F.lit("0", "decimal(7,2)")
    it_p = F.project(
        [a("i_item_sk"), a("i_item_id")],
        F.filter_(F.binop("GreaterThan", a("i_current_price"),
                          F.lit("50", "decimal(7,2)")),
                  F.scan("item", [a("i_item_sk"), a("i_item_id"),
                                  a("i_current_price")])),
    )
    pr_p = F.project(
        [a("p_promo_sk")],
        F.filter_(F.binop("EqualTo", a("p_channel_email"), s("N")),
                  F.scan("promotion", [a("p_promo_sk"),
                                       a("p_channel_email")])),
    )

    def d8(e):
        return F.binop("Add", e, dz)

    def co0(e):
        return F.T(F.X + "CaseWhen",
                   [F.un("IsNotNull", e), F.binop("Add", e, dz),
                    F.binop("Add", dz, dz)])

    def channel(fact, ret, fact_cols, ret_cols, skeys, rkeys, date_c,
                item_c, promo_c, price_c, profit_c, ramt_c, rloss_c, name):
        sl = F.scan(fact, [a(c) for c in fact_cols])
        rt = F.scan(ret, [a(c) for c in ret_cols])
        j = join(strategy, dt, sl, [a("d_date_sk")], [a(date_c)])
        j = join(strategy, it_p, j, [a("i_item_sk")], [a(item_c)])
        j = join(strategy, pr_p, j, [a("p_promo_sk")], [a(promo_c)])
        j = join(strategy, rt, j, [a(k) for k in rkeys],
                 [a(k) for k in skeys], jt="LeftOuter", build_side="right")
        return F.project(
            [F.alias(F.lit(name, "string"), "channel", 1500),
             F.alias(a("i_item_id"), "id", 1501),
             F.alias(d8(a(price_c)), "sales", 1502),
             F.alias(co0(a(ramt_c)), "returns", 1503),
             F.alias(F.binop("Subtract", d8(a(profit_c)), co0(a(rloss_c))),
                     "profit", 1504)],
            j,
        )

    store_rows = channel(
        "store_sales", "store_returns",
        ["ss_sold_date_sk", "ss_item_sk", "ss_promo_sk", "ss_ticket_number",
         "ss_ext_sales_price", "ss_net_profit"],
        ["sr_item_sk", "sr_ticket_number", "sr_return_amt", "sr_net_loss"],
        ["ss_item_sk", "ss_ticket_number"],
        ["sr_item_sk", "sr_ticket_number"],
        "ss_sold_date_sk", "ss_item_sk", "ss_promo_sk",
        "ss_ext_sales_price", "ss_net_profit", "sr_return_amt",
        "sr_net_loss", "store channel")
    cat_rows = channel(
        "catalog_sales", "catalog_returns",
        ["cs_sold_date_sk", "cs_item_sk", "cs_promo_sk", "cs_order_number",
         "cs_ext_sales_price", "cs_net_profit"],
        ["cr_item_sk", "cr_order_number", "cr_return_amount", "cr_net_loss"],
        ["cs_item_sk", "cs_order_number"],
        ["cr_item_sk", "cr_order_number"],
        "cs_sold_date_sk", "cs_item_sk", "cs_promo_sk",
        "cs_ext_sales_price", "cs_net_profit", "cr_return_amount",
        "cr_net_loss", "catalog channel")
    web_rows = channel(
        "web_sales", "web_returns",
        ["ws_sold_date_sk", "ws_item_sk", "ws_promo_sk", "ws_order_number",
         "ws_ext_sales_price", "ws_net_profit"],
        ["wr_item_sk", "wr_order_number", "wr_return_amt", "wr_net_loss"],
        ["ws_item_sk", "ws_order_number"],
        ["wr_item_sk", "wr_order_number"],
        "ws_sold_date_sk", "ws_item_sk", "ws_promo_sk",
        "ws_ext_sales_price", "ws_net_profit", "wr_return_amt",
        "wr_net_loss", "web channel")

    # q80's id is a string item_id; profit subtracts the loss coalesce,
    # widening to decimal(9,2) — reuse the q5 rollup tail by aliasing
    # profit down into the same slot types
    plan = _channel_report_tail_plan(
        strategy, F.union([store_rows, cat_rows, web_rows]))
    got = _execute_both(sess, plan)
    _check_channel_report(got, O.oracle_q80(data))
