"""The cell ``tpcds_q07_sf1`` by its files alone (PR 30): the seeded
TPC-DS generator, q7's integer reference and its float32 control, the
plan in Spark 3.5.1's ``toJSON`` encoding, and the program's spans and
counters that the cell's per-layer metrics read.  Everything runs on the
CPU at a test's scale; the SF1 numbers are asserted from the
generator's constants, not by generating SF1."""

import importlib
import json
import os

import numpy as np
import pytest

from bench import compare, run
from bench.suites.tpcds import datagen, gen_q7_plan, q7

CELL = "tpcds_q07_sf1"
SCALE = 0.05       # 144,020 sales, 96,040 demographics, 450 item ids: the LIMIT cuts
BATCH_ROWS = 8192  # five batches a partition of sales at that scale: one set of programs for every test here
SEEDS = (2**31 + 29, 20011129)
PLAN = os.path.join(os.path.dirname(gen_q7_plan.__file__), "q7.plan.json")


def _tables(seed, scale=SCALE):
    return {t: datagen.generate_table(t, scale, seed, cols) for t, cols in q7.COLUMNS.items()}


def _config(**changes):
    manifest, _, config, traffic = run.resolve(CELL)
    return manifest, dict(config, **{"scale": SCALE, "batch_rows": BATCH_ROWS, **changes}), traffic


# ------------------------------------------------------------ the cell

@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_reads_correct_past_the_look_for_a_chip(seed):
    manifest, config, traffic = _config()
    assert (config["suite"], config["entry"]) == ("tpcds", "catalyst")
    out = run.measure(CELL, manifest, config, traffic, seed, 0.3, 0, run.device_stamp())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert {k: v["value"] for k, v in out["compared"].items()} == {"queries_wrong": 0, "cells_wrong": 0}
    assert set(out["metrics"]) == {"query_s", "query_p95_s", "setup_s"}
    assert out["info"]["rows"]["store_sales"] == datagen.rows("store_sales", SCALE)


# ------------------------------------------- the reference and control

@pytest.mark.parametrize("seed", SEEDS + (3,))
def test_reference_passes_and_float32_control_reads_wrong_under_the_tolerance(seed):
    tables = _tables(seed)
    expected = q7.oracle(tables)
    assert len(expected["i_item_id"]) == q7.LIMIT
    assert expected["i_item_id"] == sorted(expected["i_item_id"])
    assert all(type(v) is float for v in expected["agg1"])
    assert all(type(v) is int for c in ("agg2", "agg3", "agg4") for v in expected[c])
    assert set(q7.TOLERANCE) == {"agg1"}  # a double; no decimal column has one
    _, ok = compare.compare([expected], expected, q7.canonical, q7.TOLERANCE)
    assert ok
    control = q7.control(tables)
    compared, ok = compare.compare([control], expected, q7.canonical, q7.TOLERANCE)
    assert not ok and compared["queries_wrong"]["value"] == 1
    # by the decimal averages AND by the double
    assert compare.cells_wrong({"agg1": control["agg1"]}, {"agg1": expected["agg1"]}, q7.TOLERANCE) > 0
    assert compare.cells_wrong({"agg2": control["agg2"]}, {"agg2": expected["agg2"]}) > 0


def _with(tables, table, column, entry):
    out = dict(tables)
    out[table] = dict(tables[table], **{column: entry})
    return out


def _null_key_joins(tables):
    data, lengths, validity = tables["store_sales"]["ss_cdemo_sk"]
    return _with(tables, "store_sales", "ss_cdemo_sk", (data, lengths, np.ones_like(validity)))


def _date_filter_dropped(tables):
    year, lengths = tables["date_dim"]["d_year"]
    return _with(tables, "date_dim", "d_year", (np.full_like(year, 2000), lengths))


def _sixth_digit(expected):
    return dict(expected, agg3=[expected["agg3"][0] + 1] + expected["agg3"][1:])


def _double_off(rel):
    return lambda expected: dict(expected, agg1=[v * (1 + rel) for v in expected["agg1"]])


@pytest.mark.parametrize("fault,wrong", [
    (lambda e, t: q7.oracle(_null_key_joins(t)), True),
    (lambda e, t: q7.oracle(_date_filter_dropped(t)), True),
    (lambda e, t: _sixth_digit(e), True),
    (lambda e, t: _double_off(1e-6)(e), True),
    (lambda e, t: _double_off(1e-12)(e), False),  # the emulated division's room
], ids=["null_key_joins", "date_filter_dropped", "sixth_digit_off_by_one",
        "agg1_off_1e-6", "agg1_off_1e-12"])
def test_planted_fault_reads_wrong(fault, wrong):
    tables = _tables(SEEDS[0])
    expected = q7.oracle(tables)
    compared, ok = compare.compare([fault(expected, tables)], expected, q7.canonical, q7.TOLERANCE)
    assert ok is (not wrong)
    assert (compared["cells_wrong"]["value"] > 0) is wrong


# -------------------------------------------------------- the generator

def test_sf1_cardinalities_and_null_shares_from_the_generators_constants():
    assert datagen.ROWS_SF1 == {"store_sales": 2_880_404, "customer_demographics": 1_920_800,
                                "date_dim": 73_049, "item": 18_000, "promotion": 300}
    assert {t: datagen.rows(t, 1.0) for t in datagen.ROWS_SF1} == datagen.ROWS_SF1
    assert int(np.prod(datagen.CD_RADICES)) == 1_920_800 and datagen.CD_RADICES[:3] == (2, 5, 7)
    assert datagen.NULL_SHARE == 0.045 and set(datagen.SS_NULLABLE) == set(q7.COLUMNS["store_sales"][:4])
    assert (datagen.SOLD_FIRST, datagen.SOLD_LAST) == (2_450_816, 2_452_642)
    with pytest.raises(ValueError):
        datagen.rows("item", 2.0)
    # 44 batches of store_sales and 32 of customer_demographics at the cell's 4 x 65,536
    per_part = lambda t: -(-(datagen.ROWS_SF1[t] // 4) // 65536)
    assert (per_part("store_sales"), per_part("customer_demographics")) == (11, 8)


def test_generator_shapes_at_a_tests_scale():
    seed = SEEDS[0]
    ss = datagen.generate_table("store_sales", SCALE, seed)
    n = datagen.rows("store_sales", SCALE)
    for column in datagen.SS_NULLABLE:
        data, lengths, validity = ss[column]
        assert data.shape == validity.shape == (n,) and lengths is None
        assert abs((~validity).mean() - datagen.NULL_SHARE) < 0.005, column
        assert data[~validity].min() >= 1  # an ordinary key lies under a NULL
    assert ss["ss_sold_date_sk"][0].min() >= datagen.SOLD_FIRST
    assert ss["ss_sold_date_sk"][0].max() <= datagen.SOLD_LAST
    assert 1 <= ss["ss_quantity"][0].min() and ss["ss_quantity"][0].max() <= 100
    for c in ("ss_list_price", "ss_sales_price", "ss_coupon_amt"):
        assert 0 <= ss[c][0].min() and ss[c][0].max() < 10**7  # decimal(7,2)
    assert (ss["ss_sales_price"][0] <= ss["ss_list_price"][0]).all()
    # a pruned table is a projection of the full one, and the seed matters
    pruned = _tables(seed)["store_sales"]
    assert sorted(pruned) == sorted(q7.COLUMNS["store_sales"])
    assert all((pruned[c][0] == ss[c][0]).all() for c in pruned)
    other = datagen.generate_table("store_sales", SCALE, seed + 1, ["ss_item_sk"])
    assert (other["ss_item_sk"][0] != ss["ss_item_sk"][0]).any()

    cd = datagen.generate_table("customer_demographics", SCALE, seed)
    assert cd["cd_demo_sk"][0].shape[0] % datagen.CD_PERIOD == 0
    assert (cd["cd_demo_sk"][0] == np.arange(1, cd["cd_demo_sk"][0].shape[0] + 1)).all()
    m, s, college = (q7._is(cd, c, v) for c, v in (("cd_gender", b"M"), ("cd_marital_status", b"S"),
                                                    ("cd_education_status", b"College")))
    assert (m & s & college).sum() * datagen.CD_PERIOD == cd["cd_demo_sk"][0].shape[0]
    dd = datagen.generate_table("date_dim", SCALE, seed)
    assert dd["d_date_sk"][0][0] == 2_415_022 and dd["d_year"][0][0] == 1900
    assert (dd["d_year"][0] == 2000).sum() == 366 and dd["d_year"][0][-1] == 2100
    in_2000 = dd["d_date_sk"][0][dd["d_year"][0] == 2000]
    assert datagen.SOLD_FIRST < in_2000.min() and in_2000.max() < datagen.SOLD_LAST
    item = datagen.generate_table("item", SCALE, seed)
    ids = set(map(bytes, item["i_item_id"][0]))
    assert len(ids) * 2 == item["i_item_sk"][0].shape[0] and b"AAAAAAAABAAAAAAA" in ids


# -------------------------------------------------------------- the plan

def _walk(nodes):
    """Every node of a dump, the expression trees in its fields too."""
    for node in nodes:
        yield node
        for value in node.values():
            if isinstance(value, list):
                for v in value:
                    if isinstance(v, dict) and "class" in v:
                        yield from _walk([v])
                    elif isinstance(v, list):
                        yield from _walk([x for x in v if isinstance(x, dict) and "class" in x])


def test_plan_is_sparks_shape_over_the_cells_columns():
    with open(PLAN) as f:
        dump = json.load(f)
    assert dump == gen_q7_plan.flatten(gen_q7_plan.q7())  # the file is what its generator writes
    short = lambda n: n["class"].rsplit(".", 1)[-1]
    scans = [n for n in dump if short(n) == "FileSourceScanExec"]
    # the build sides in Spark's order, each scan over the columns the cell stages
    assert [s["tableIdentifier"]["table"] for s in scans] == [
        "store_sales", "customer_demographics", "date_dim", "item", "promotion"]
    for s in scans:
        table = s["tableIdentifier"]["table"]
        assert [f["name"] for f in s["requiredSchema"]["fields"]] == q7.COLUMNS[table]
    columns = {c for cols in q7.COLUMNS.values() for c in cols}
    named = {n["name"] for n in _walk(dump) if short(n) == "AttributeReference" and n["qualifier"]}
    assert named == columns
    joins = [n for n in dump if short(n) == "BroadcastHashJoinExec"]
    assert len(joins) == 4
    assert all(j["buildSide"]["product-class"].endswith("BuildRight$") for j in joins)
    classes = [short(n) for n in _walk(dump)]
    assert classes.count("UnscaledValue") == 6  # three averages, partial and final
    assert classes.count("Divide") == 3 and classes.count("IsNotNull") >= 4
    assert [short(n) for n in dump][:2] == ["TakeOrderedAndProjectExec", "WholeStageCodegenExec"]


def test_converted_tree_holds_four_broadcast_joins_built_on_the_right():
    from blaze_tpu.ops.joins import BroadcastJoinExec
    from blaze_tpu.parallel import BroadcastExchangeExec

    _, config, traffic = _config(scale=0.001)
    cell = run.Cell(config, traffic, SEEDS[0])
    tree = cell.plan()

    def joins(node):
        for c in node.children:
            yield from joins(c)
        if isinstance(node, BroadcastJoinExec):
            yield node

    found = list(joins(tree))
    assert len(found) == 4
    for j in found:
        assert j.build_is_left is False and isinstance(j.children[0], BroadcastExchangeExec)
    # innermost first: demographics, date, item, promotion
    assert [j.children[0].schema.names[0] for j in found] == ["#11", "#21", "#31", "#41"]
    assert tree.schema.names == list(q7.OUT)


# ------------------------------------------- the spans and their readers

def test_one_query_tallies_the_probes_builds_and_rows_the_reference_implies():
    """join_probe_n: the non-empty batches reaching each join;
    broadcast_build_n and the cache counters: four broadcasts, each built
    by the first probe task and found by the other partitions';
    join_rows_out: the reference's own rows after each join."""
    from blaze_tpu.runtime import dispatch

    _, config, traffic = _config()
    n_parts, batch_rows = config["partitions"], config["batch_rows"]
    cell = run.Cell(config, traffic, SEEDS[1])
    with dispatch.capture() as c:
        got, _ = cell.query()
    assert got == q7.oracle(cell.tables)

    ss = cell.tables["store_sales"]
    not_null = np.logical_and.reduce([ss[k][2] for k in datagen.SS_NULLABLE])
    after = q7.join_masks(cell.tables)
    into = [not_null] + after[:3]  # the rows that reach each join
    n = not_null.shape[0]
    probes = 0
    for p in range(n_parts):
        lo, hi = p * n // n_parts, (p + 1) * n // n_parts
        for s in range(lo, hi, batch_rows):
            probes += sum(1 for alive in into if alive[s:min(s + batch_rows, hi)].any())
    assert c["join_probe_n"] == probes > 4 * n_parts
    assert c["join_probe_rows_in"] == sum(int(m.sum()) for m in into)
    assert c["join_rows_out"] == sum(int(m.sum()) for m in after) > 0
    assert (c["broadcast_build_n"], c["join_map_builds"]) == (4, 4)
    assert c["join_map_cache_hits"] == 4 * (n_parts - 1)
    assert c["plan_convert_n"] == 1 and 0 < c["plan_convert_ns"] < c["join_probe_ns"]
    assert c["broadcast_build_ns"] > 0 and c["device_read_n"] >= 2 * probes


#: metric -> (counter it reads, counter value, queries, the stated quotient)
CASES = {
    "broadcast_build_ms": ("broadcast_build_ns", 600_000_000, 3, 200.0),
    "join_probe_ms": ("join_probe_ns", 9_000_000_000, 2, 4500.0),
    "join_probes": ("join_probe_n", 528, 3, 176.0),
    "plan_convert_ms": ("plan_convert_ns", 24_000_000, 4, 6.0),
}


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_returns_its_counter_per_query_or_nothing(metric):
    counter, value, queries, want = CASES[metric]
    reader = importlib.import_module("bench.metrics." + metric)
    run_ = {"queries": queries, "counters": {counter: value}, "plan_s": [], "trace": None,
            "memory_peak_bytes": None, "least_bytes": 1, "peak": {"hbm_bytes_per_s": 1.0}}
    assert reader.read(run_) == pytest.approx(want)
    # the parent's program has no such counter: nothing, not a 0; nor with no query done
    assert reader.read(dict(run_, counters={"xla_dispatches": 500})) is None
    assert reader.read(dict(run_, queries=0)) is None
    assert reader.read(dict(run_, counters={}, queries=0)) is None


def test_the_new_metrics_are_read_where_the_joiner_runs():
    manifest, *_ = run.resolve(CELL)
    joiner = {"broadcast_build_ms", "join_probe_ms", "join_probes"}
    for cell in ("tpch_q06_sf1", "tpch_q01_sf1", "tpch_q03_sf0.5", CELL):
        readers = set(run.metric_readers(manifest, cell))
        assert (joiner <= readers) == (cell in ("tpch_q03_sf0.5", CELL)), cell
        assert ("plan_convert_ms" in readers) == (cell == CELL)
        assert joiner <= readers or not (joiner & readers)
