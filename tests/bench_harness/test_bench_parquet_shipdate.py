"""The cell ``tpch_q06_sf1_parquet_shipdate`` by its files alone:
``lineitem`` as Spark writes it clustered by ``l_shipdate`` — one file
a range, sorted within it — and q6 through the converted dump,
whose scan prunes the row groups its ``dataFilters`` rule out, against
the reference on the CPU at a test's scale; the configuration, the
range writer and the two readers.  Every manifest entry is found by
name."""

import importlib
import os

import numpy as np
import pyarrow.parquet as papq
import pytest

from bench import compare, entries, run
from bench.entries import catalyst_parquet, catalyst_parquet_shipdate as shipdate
from bench.suites.tpch import datagen, q6

CELL = "tpch_q06_sf1_parquet_shipdate"
CONFIG = "tpch-sf1-p4-parquet-shipdate"
BYPASS = "tpch-sf1-p4-parquet"
METRICS = {"scan_skipped_pct": ("%", "higher", "program_counter"),
           "scan_prune_ms": ("ms", "lower", "program_span")}
#: the per-layer metrics without a list: read in every cell that reports query_s, this one too
UNLISTED = {"plan_ms", "programs_per_query", "warm_compiles", "kernels_roofline", "device_idle_pct",
            "device_peak_mb", "task_decode_ms", "scan_stage_ms", "h2d_mb", "launch_ms",
            "device_read_ms", "exchange_backpressure_ms"}
SCALE = 0.01        # ~60,000 rows of lineitem, ~15,000 a file
BATCH_ROWS = 8192
SEED = 2**31 + 43
Q6_FROM, Q6_TO = datagen._days(1994, 1, 1), datagen._days(1995, 1, 1)


def _named(entries_, name):
    (found,) = [e for e in entries_ if e["name"] == name]
    return found


def _config(**changes):
    manifest, _, config, traffic = run.resolve(CELL)
    return manifest, dict(config, **{"scale": SCALE, "batch_rows": BATCH_ROWS, **changes}), traffic


# ------------------------------------------------------------ the cell

def test_the_cell_resolves_by_name_through_its_own_entry():
    manifest, entry, config, traffic = run.resolve(CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "q06_closed1_t2", 1)
    assert len(entry["why"]) <= 200
    assert (config["suite"], config["entry"], config["scale"]) == ("tpch", "catalyst_parquet_shipdate", 1.0)
    assert (traffic["query"], traffic["traced_queries"]) == ("q6", 2)
    assert importlib.import_module("bench.entries." + config["entry"]) is shipdate
    listed = _named(manifest["configs"], CONFIG)
    assert listed["source"] == config["source"] and len(listed["source"]) <= 200
    assert (listed["file"], listed["reduced"]) == (f"bench/configs/{CONFIG}.json", ["scale"])
    # the bypass's deployment word for word, but for how the rows lie in the files
    bypass = run.read_json("bench", "configs", BYPASS + ".json")
    for key in ("suite", "schema", "scale", "partitions", "batch_rows", "chips", "reduced", "reduced_why"):
        assert config[key] == bypass[key], key
    assert {k: config["guarantees"][k] for k in ("results", "path")} == {
        k: bypass["guarantees"][k] for k in ("results", "path")}
    for key in ("codec", "pages", "encoding", "types", "nullability"):
        assert config["layout"][key].startswith(bypass["layout"][key].split(";")[0]), key
    assert config["layout"]["row_group_rows"] == catalyst_parquet.WRITER["row_group_size"]
    assert {"range_bounds", "ties", "page_index", "columns", "writer"} <= set(config["assumed"])
    assert "pruned wrongly fails the cell" in config["guarantees"]["scan"]


def test_the_two_metrics_are_read_in_this_cell_only():
    manifest, *_ = run.resolve(CELL)
    for name, (unit, better, source) in METRICS.items():
        assert _named(manifest["per_layer"], name) == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "operators", "moves": "query_s", "workloads": [CELL]}
    for cell in (w["name"] for w in manifest["workloads"]):
        readers = set(run.metric_readers(manifest, cell))
        assert (set(METRICS) <= readers) == (cell == CELL), cell
        assert UNLISTED <= readers, cell
    # the unlisted ones stay unlisted: this cell reports them as the seven before it do
    assert all("workloads" not in _named(manifest["per_layer"], name) for name in UNLISTED)
    assert [w["name"] for w in manifest["workloads"]].index(CELL) == 7


# --------------------------------------------------------- the ranges

@pytest.mark.parametrize("keys, n, files", [
    ([3, 1, 9, 1, 2, 1, 3, 2, 1, 3], 4, [[1, 1, 1, 1], [2, 2], [3, 3, 3], [9]]),
    # a value that fills two quarters stays in one file: a range may stay empty
    ([5] * 6 + [1, 7], 4, [[1, 5, 5, 5, 5, 5, 5], [7]]),
    (list(range(8)), 4, [[0, 1], [2, 3], [4, 5], [6, 7]]),
])
def test_the_ranges_split_no_value_and_keep_ties_in_table_order(keys, n, files):
    keys = np.asarray(keys)
    bounds = shipdate.range_bounds(keys, n)
    part = np.searchsorted(bounds, keys, side="left")
    got = [sorted(keys[part == p].tolist()) for p in range(n) if (part == p).any()]
    assert got == files
    assert len(set(keys.tolist())) == sum(len(set(f)) for f in got)  # no value in two files


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """The seeded lineitem and the range files the entry writes of it,
    row groups cut short so that each file has several."""
    tables = {t: datagen.generate_table(t, SCALE, SEED, cols) for t, cols in q6.COLUMNS.items()}
    scans = entries.memory_scans("tpch", tables, q6.COLUMNS, 4, BATCH_ROWS)
    directory = str(tmp_path_factory.mktemp("files") / "lineitem")
    writer = catalyst_parquet.WRITER
    catalyst_parquet.WRITER = dict(writer, row_group_size=4096)
    try:
        paths = shipdate.write_ranges(scans["lineitem"], directory, "l_shipdate", 4)
    finally:
        catalyst_parquet.WRITER = writer
    return tables["lineitem"], paths


def test_the_files_hold_disjoint_sorted_date_ranges(written):
    table, paths = written
    assert [os.path.basename(p) for p in paths] == [f"part-0000{p}.snappy.parquet" for p in range(4)]
    days = [papq.read_table(p).column("l_shipdate").cast("int32").to_numpy() for p in paths]
    assert all((np.diff(d) >= 0).all() for d in days)  # sorted within a file
    assert all(a[-1] < b[0] for a, b in zip(days, days[1:]))  # a date in one file alone
    n = table["l_shipdate"][0].shape[0]
    assert all(abs(len(d) - n / 4) < n / 40 for d in days)  # quarters, but for the last date's ties
    # every row once, in l_shipdate order, ties in table order
    order = np.argsort(table["l_shipdate"][0], kind="stable")
    got = [papq.read_table(p) for p in paths]
    for name in ("l_quantity", "l_discount", "l_extendedprice"):
        unscaled = [int(v.scaleb(2)) for t in got for v in t.column(name).to_pylist()]
        assert unscaled == table[name][0][order].tolist(), name


def test_each_row_group_states_its_dates(written):
    """pyarrow writes every chunk's statistics, as parquet-mr does: a
    row group's min and max of l_shipdate are the ends of its rows."""
    _, paths = written
    for p in paths:
        f = papq.ParquetFile(p)
        assert f.metadata.num_row_groups >= 3
        for g in range(f.metadata.num_row_groups):
            days = f.read_row_group(g).column("l_shipdate").cast("int32").to_numpy()
            stats = f.metadata.row_group(g).column(3).statistics
            assert (stats.min_raw, stats.max_raw, stats.null_count) == (days[0], days[-1], 0)


# ------------------------------------------ the query and its counters

def _ruled_out(paths):
    """Row groups (and their rows) whose l_shipdate range misses 1994."""
    groups = rows = 0
    for p in paths:
        meta = papq.ParquetFile(p).metadata
        for g in range(meta.num_row_groups):
            s = meta.row_group(g).column(3).statistics
            if s.max_raw < Q6_FROM or s.min_raw >= Q6_TO:
                groups += 1
                rows += meta.row_group(g).num_rows
    return groups, rows


@pytest.mark.parametrize("pushdown", [True, False], ids=["pushed_down", "as_the_parent"])
def test_one_query_is_the_references_with_the_row_groups_pruned(monkeypatch, pushdown):
    from blaze_tpu import conf
    from blaze_tpu.ops import ParquetScanExec
    from blaze_tpu.runtime import dispatch

    monkeypatch.setattr(catalyst_parquet, "WRITER", dict(catalyst_parquet.WRITER, row_group_size=4096))
    monkeypatch.setattr(conf.PARQUET_FILTER_PUSHDOWN, "get", lambda: pushdown)
    _, config, traffic = _config()
    cell = run.Cell(config, traffic, SEED)
    scan = cell.plan()
    while scan.children:
        scan = scan.children[0]
    assert isinstance(scan, ParquetScanExec) and scan.predicate is not None
    paths = [path for group in scan.file_groups for path in group]
    assert len(paths) == 4 and all(len(g) == 1 for g in scan.file_groups)  # one file a task
    root = os.path.dirname(os.path.dirname(paths[0]))
    groups, rows = _ruled_out(paths)
    assert groups >= 6
    del scan

    with dispatch.capture() as c:
        got, _ = cell.query()
    expected = q6.oracle(cell.tables)
    assert got == expected and expected["revenue"][0] > 0
    _, control_ok = compare.compare([q6.control(cell.tables)], expected, q6.canonical)
    assert not control_ok
    assert c["scan_conjuncts_pushed"] == 5 and c["scan_rows_chosen"] == cell.rows["lineitem"]
    assert c.get("scan_row_groups_pruned", 0) == (groups if pushdown else 0)
    assert c["scan_rows_pruned"] == (rows if pushdown else 0)
    assert c["scan_rows"] == cell.rows["lineitem"] - c["scan_rows_pruned"]
    assert ("scan_prune_n" in c) == pushdown

    cell.release()
    assert not os.path.exists(root)


def test_the_cell_reads_correct_past_the_look_for_a_chip():
    manifest, config, traffic = _config()
    out = run.measure(CELL, manifest, config, traffic, SEED + 1, 0.3, 0, run.device_stamp())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert {k: v["value"] for k, v in out["compared"].items()} == {"queries_wrong": 0, "cells_wrong": 0}
    assert set(out["metrics"]) == {"query_s", "query_p95_s", "setup_s"}
    counters = out["info"]["counters"]
    # a file is one row group at this scale, and one of the four holds 1994
    assert counters["scan_row_groups_pruned"] == 3 * out["attempted"]
    assert counters["scan_row_groups"] == out["attempted"]


# ---------------------------------------------------------- the readers

#: metric -> (counters, queries, the stated value)
CASES = {
    "scan_skipped_pct": ({"scan_rows_pruned": 4_501_723 * 9, "scan_rows_chosen": 6_000_910 * 9}, 9,
                         100.0 * 4_501_723 / 6_000_910),
    "scan_prune_ms": ({"scan_prune_ns": 1_800_000, "scan_prune_n": 36}, 9, 0.2),
}


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_returns_its_quotient_or_nothing(metric):
    counters, queries, want = CASES[metric]
    reader = importlib.import_module("bench.metrics." + metric)
    run_ = {"queries": queries, "counters": counters, "plan_s": [], "trace": None,
            "memory_peak_bytes": None, "least_bytes": 1, "peak": {"hbm_bytes_per_s": 1.0}}
    assert reader.read(run_) == pytest.approx(want)
    # the parent's program has no such counter: nothing, not a 0; nor with no query done
    assert reader.read(dict(run_, counters={"scan_decode_ns": 5, "scan_row_groups": 7})) is None
    assert reader.read(dict(run_, queries=0)) is None
