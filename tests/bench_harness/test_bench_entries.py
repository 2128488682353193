"""A configuration states how its plans arrive (``entry``) and, by its
``suite``, which of the program's table catalogs its tables come from.
Both entries are driven here on the CPU past the look for a chip, from
files that ``bench/run.py`` does not name: ``bench/entries/<entry>.py``
and, for ``catalyst``, ``bench/suites/tpch/q6.plan.json``."""

import pytest

from bench import entries, run

SCALE = 0.01
SEED = 2**31 + 29
ENTRIES = ("builder", "catalyst")


def _config(entry):
    manifest, _, config, traffic = run.resolve("tpch_q06_sf1")
    return manifest, dict(config, scale=SCALE, entry=entry), traffic


@pytest.mark.parametrize("entry", ENTRIES)
def test_a_run_through_the_entry_reads_correct(entry):
    manifest, config, traffic = _config(entry)
    out = run.measure("tpch_q06_sf1", manifest, config, traffic, SEED, 0.3, 0, run.device_stamp())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert {k: v["value"] for k, v in out["compared"].items()} == {"queries_wrong": 0, "cells_wrong": 0}
    assert set(out["metrics"]) == {"query_s", "query_p95_s", "setup_s"}


def test_both_entries_give_the_same_result_by_different_plans():
    got, trees = {}, {}
    for entry in ENTRIES:
        _, config, traffic = _config(entry)
        cell = run.Cell(config, traffic, SEED)
        trees[entry] = cell.plan().tree_string()
        got[entry], plan_s = cell.query()
        assert plan_s > 0
        assert got[entry] == cell.module.oracle(cell.tables)
        # a fresh tree per query, over the same scans
        assert cell.plan() is not cell.plan()
    assert got["builder"] == got["catalyst"]
    # the converted plan is the front door's, not the hand-built one
    assert "RenameColumnsExec" in trees["catalyst"] and "RenameColumnsExec" not in trees["builder"]


def test_an_unknown_entry_is_refused():
    _, config, traffic = _config("no_such_entry")
    with pytest.raises(ModuleNotFoundError):
        run.Cell(config, traffic, SEED)


@pytest.mark.parametrize("suite,table,columns", [
    ("tpch", "lineitem", ["l_shipdate", "l_quantity"]),
    # TPC-DS q07's eight columns of its fact table, and a dimension's string key
    ("tpcds", "store_sales", ["ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk", "ss_promo_sk",
                              "ss_quantity", "ss_list_price", "ss_sales_price", "ss_coupon_amt"]),
    ("tpcds", "item", ["i_item_sk", "i_item_id"]),
])
def test_schema_lookup_is_the_suites_and_prunes_to_columns(suite, table, columns):
    schema = entries.pruned_schema(suite, table, columns)
    assert sorted(schema.names) == sorted(columns)
    with pytest.raises(AssertionError):
        entries.pruned_schema(suite, table, columns + ["no_such_column"])
