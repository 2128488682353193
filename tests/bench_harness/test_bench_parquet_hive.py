"""The cell ``tpcds_q07_sf1_parquet_datepart`` by its files alone (PR
40): ``store_sales`` Hive-partitioned by ``ss_sold_date_sk`` as Spark's
TPC-DS tooling lays it out, pruned by the entry as Spark's driver prunes
it, packed by Spark's three functions; q7 through the converted dump
against the reference on the CPU at a test's scale; the dump, the
configuration and the three readers.  Every manifest entry is found by
name."""

import datetime
import importlib
import json
import os

import numpy as np
import pyarrow.parquet as papq
import pytest

from bench import compare, entries, run
from bench.entries import catalyst_parquet, catalyst_parquet_hive as hive
from bench.entries import catalyst_parquet_1file as one_file
from bench.suites.tpcds import datagen, gen_q7_datepart_plan, gen_q7_plan, q7

CELL = "tpcds_q07_sf1_parquet_datepart"
CONFIG = "tpcds-sf1-p4-parquet-datepart"
METRICS = {"scan_batch_fill_pct": ("%", "higher", "program_counter"),
           "scan_coalesce_ms": ("ms", "lower", "program_span"),
           "scan_open_per_file_ms": ("ms", "lower", "program_span")}
SCALE = 0.05       # 144,020 sales: ~79 rows a sold date
BATCH_ROWS = 8192  # tpcds_q07_sf1's tests': one set of programs
SEED = 2**31 + 40
OPEN_COST = 4096   # Spark's 4 MB, shrunk with the files: ~4 KB here
KEY = "ss_sold_date_sk"
DIRECTORY = os.path.dirname(gen_q7_plan.__file__)
#: d_date_sk of 2000-01-01 .. 2000-12-31, from the calendar and the generator's origin
YEAR_2000 = {datagen.DATE_SK0 + (datetime.date(2000, 1, 1) - datagen.DATE0).days + i for i in range(366)}


def _named(entries_, name):
    (found,) = [e for e in entries_ if e["name"] == name]
    return found


def _config(**changes):
    manifest, _, config, traffic = run.resolve(CELL)
    return manifest, dict(config, **{"scale": SCALE, "batch_rows": BATCH_ROWS, **changes}), traffic


@pytest.fixture
def test_scale(monkeypatch):
    """Spark's open cost comes down with the files; the formula, the
    packing and the writer stay."""
    monkeypatch.setattr(one_file, "OPEN_COST_IN_BYTES", OPEN_COST)


def _scans(node, out=None):
    out = [] if out is None else out
    if type(node).__name__ == "ParquetScanExec":
        out.append(node)
    for child in node.children:
        _scans(child, out)
    return out


# ------------------------------------------------------------ the cell

def test_the_cell_resolves_by_name_through_its_own_entry():
    manifest, entry, config, traffic = run.resolve(CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "q07_closed1", 1)
    assert len(entry["why"]) <= 200
    assert (config["suite"], config["entry"], config["scale"]) == ("tpcds", "catalyst_parquet_hive", 1.0)
    assert (traffic["query"], traffic["traced_queries"]) == ("q7", 2)
    assert importlib.import_module("bench.entries." + config["entry"]) is hive
    listed = _named(manifest["configs"], CONFIG)
    assert listed["source"] == config["source"] and len(listed["source"]) <= 200
    assert (listed["file"], listed["reduced"]) == (f"bench/configs/{CONFIG}.json", ["scale"])
    # tpcds-sf1-p4's deployment, but for where the tables live
    memory = run.read_json("bench", "configs", "tpcds-sf1-p4.json")
    for key in ("suite", "schema", "scale", "partitions", "batch_rows", "chips", "reduced"):
        assert config[key] == memory[key], key
    assert {k: config["guarantees"][k] for k in ("results", "path")} == memory["guarantees"]
    assert "pruned partition" in config["guarantees"]["scan"]
    assert config["assumed"]["generator"] == memory["assumed"]["generator"]
    assert "files and not their number" in config["reduced_why"]


def test_the_three_metrics_are_read_in_this_cell_only():
    manifest, *_ = run.resolve(CELL)
    for name, (unit, better, source) in METRICS.items():
        assert _named(manifest["per_layer"], name) == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "operators", "moves": "query_s", "workloads": [CELL]}
    for cell in (w["name"] for w in manifest["workloads"]):
        assert (set(METRICS) <= set(run.metric_readers(manifest, cell))) == (cell == CELL), cell
    # the unlisted ones read this cell as they read every cell
    assert {"plan_ms", "programs_per_query", "warm_compiles", "kernels_roofline", "device_idle_pct",
            "device_peak_mb", "task_decode_ms", "scan_stage_ms", "h2d_mb", "launch_ms",
            "device_read_ms", "exchange_backpressure_ms"} <= set(run.metric_readers(manifest, CELL))


# ------------------------------------------------------------ the dump

def test_the_dump_is_q7s_but_for_the_partitioned_scan_and_the_filter_over_it():
    with open(os.path.join(DIRECTORY, "q7.plan.json")) as f:
        plain = json.load(f)
    with open(os.path.join(DIRECTORY, "q7.datepart.plan.json")) as f:
        text = f.read()
    dump = json.loads(text)
    assert text == json.dumps(gen_q7_plan.flatten(gen_q7_datepart_plan.q7_datepart()))
    differ = [i for i, (a, b) in enumerate(zip(plain, dump)) if a != b]
    assert len(plain) == len(dump) == 50
    assert [dump[i]["class"].rsplit(".", 1)[1] for i in differ] == ["FilterExec", "FileSourceScanExec"]
    scan = dump[differ[1]]
    assert scan["tableIdentifier"]["table"] == "store_sales" and scan["relation"] is None
    assert [a[0]["name"] for a in scan["output"]] == q7.COLUMNS["store_sales"][1:] + [KEY]
    assert [f["name"] for f in scan["requiredSchema"]["fields"]] == q7.COLUMNS["store_sales"][1:]
    not_null, pruning = scan["partitionFilters"]
    assert [e["class"].rsplit(".", 1)[1] for e in not_null] == ["IsNotNull", "AttributeReference"]
    assert not_null[1]["name"] == KEY
    assert [e["class"].rsplit(".", 1)[1] for e in pruning] == [
        "DynamicPruningExpression", "InSubqueryExec", "AttributeReference"]
    assert pruning[2]["name"] == KEY and pruning[2]["exprId"] == not_null[1]["exprId"]
    subquery = pruning[1]["plan"]
    assert subquery[0]["class"].endswith("SubqueryBroadcastExec")
    assert any(n["class"].endswith("FileSourceScanExec") and n["tableIdentifier"]["table"] == "date_dim"
               for n in subquery)
    # the three keys that stay in the file are the data filters, and the FilterExec's condition
    assert [[e.get("name") for e in f if "name" in e] for f in scan["dataFilters"]] == [
        ["ss_cdemo_sk"], ["ss_item_sk"], ["ss_promo_sk"]]
    condition = dump[differ[0]]["condition"]
    assert sorted(e["name"] for e in condition if "name" in e) == ["ss_cdemo_sk", "ss_item_sk", "ss_promo_sk"]
    # every other scan of the dump is unpartitioned, as before
    assert sum(1 for n in dump if n["class"].endswith("FileSourceScanExec") and n["partitionFilters"]) == 1


# ------------------------------------------- Spark's packing, by hand

MB, KB = 1 << 20, 1 << 10
#: files (bytes each), cores -> maxSplitBytes and the files of each
#: partition, worked by hand from Spark 3.5.1's three functions
PACKINGS = {
    # the cell at SF1: (42 KB + 4 MB) x 366 / 4 = 387 MB, so 128 MB; a file in weighs 4 MB + 42 KB:
    # 31 in make 125.3 MB and the 32nd fits (125.3 + 0.04 <= 128), 32 in make 129.3 and the 33rd does not
    "the_cells_366_files": ([42_271] * 366, 4, 128 * MB, [32] * 11 + [14]),
    "its_smallest_files": ([39_163] * 366, 4, 128 * MB, [32] * 11 + [14]),
    "its_largest_files": ([45_346] * 366, 4, 128 * MB, [32] * 11 + [14]),
    # a day's partitions on 4 cores: (24 x (1 MB + 4 MB)) / 4 = 30 MB; 5 MB a file in, the 7th would make 31
    "24_files_of_1mb": ([MB] * 24, 4, 30 * MB, [6, 6, 6, 6]),
    # never under the open cost: 3 small files weigh 12 MB + 3 KB, / 4 = 3 MB -> 4 MB; a file in weighs
    # 4 MB + 1 KB already, so each is a partition of its own
    "3_files_of_1kb": ([KB] * 3, 4, 4 * MB, [1, 1, 1]),
    # 1 TB's files of a date are large: 60 MB + 4 MB each, x 366 / 4 -> 128 MB; 64 MB in, the second would make 124
    "366_files_of_60mb": ([60 * MB] * 366, 4, 128 * MB, [2] * 183),
}


@pytest.mark.parametrize("case", sorted(PACKINGS))
def test_the_packing_is_sparks_three_functions(case):
    sizes, cores, max_split, want = PACKINGS[case]
    files = [(f"/t/{KEY}={2451545 + i}/part-00000.snappy.parquet", size) for i, size in enumerate(sizes)]
    assert one_file.max_split_bytes(sizes, cores) == max_split
    planned = one_file.plan_splits(files, cores)
    assert [len(pieces) for pieces in planned] == want
    # a file under maxSplitBytes is one whole piece, read by exactly one task
    pieces = [piece for pieces in planned for piece in pieces]
    assert sorted(pieces) == sorted((path, 0, size) for path, size in files)


def test_the_configuration_states_the_layout_the_planner_gives():
    config = run.read_json("bench", "configs", CONFIG + ".json")
    layout, splits = config["layout"], config["layout"]["splits"]
    assert (splits["spark.sql.files.maxPartitionBytes"], splits["spark.sql.files.openCostInBytes"]) == (
        one_file.MAX_PARTITION_BYTES, one_file.OPEN_COST_IN_BYTES)
    assert splits["spark.sql.files.minPartitionNum"] == config["partitions"] == 4
    pruning = layout["pruning"]
    assert (pruning["directories_before"], pruning["directories_after"]) == (1828, 366)
    assert pruning["directories_before"] == datagen.SOLD_LAST - datagen.SOLD_FIRST + 1 + 1  # and the NULL one
    assert pruning["directories_after"] == pruning["files_read"] == len(YEAR_2000)
    assert datagen.SOLD_FIRST < min(YEAR_2000) and max(YEAR_2000) < datagen.SOLD_LAST
    # the bytes read over the files read, packed: the 12 tasks stated
    mean = pruning["bytes_read"] // pruning["files_read"]
    assert one_file.max_split_bytes([mean] * 366, 4) == splits["max_split_bytes"]
    assert [len(p) for p in one_file.plan_splits([(str(i), mean) for i in range(366)], 4)] == (
        splits["files_of_partition"])
    assert len(splits["files_of_partition"]) == len(splits["rows_of_partition"]) == 12
    assert sum(splits["rows_of_partition"]) == pruning["rows_read"]
    assert max(splits["rows_of_partition"]) <= config["batch_rows"]  # a task's files fit one batch
    assert layout["row_group_rows"] == catalyst_parquet.WRITER["row_group_size"]
    memory = run.read_json("bench", "configs", "tpcds-sf1-p4.json")
    assert "store_sales IS partitioned here" in config["assumed"]["differs_from_tpcds-sf1-p4"]
    assert "not partitioned here" in memory["assumed"]["plan"]  # the assumption this one lifts


# ---------------------------------------------------------- the layout

@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """The seeded tables, their scans, and ``store_sales`` as the entry
    writes it."""
    tables = {t: datagen.generate_table(t, SCALE, SEED, cols) for t, cols in q7.COLUMNS.items()}
    scans = entries.memory_scans("tpcds", tables, q7.COLUMNS, 4, BATCH_ROWS)
    directory = str(tmp_path_factory.mktemp("files") / "store_sales")
    hive.write_partitioned(scans["store_sales"], directory, KEY)
    return tables, scans, directory


def _file(directory, name):
    return os.path.join(directory, name, "part-00000.snappy.parquet")


def _one_directory_a_sold_date_and_one_for_the_nulls(tables, scans, directory):
    key, _, valid = tables["store_sales"][KEY]
    names = sorted(os.listdir(directory))
    assert names == sorted([f"{KEY}={v}" for v in np.unique(key[valid])]
                           + [f"{KEY}={hive.NULL_DIRECTORY}"])
    assert len(names) == datagen.SOLD_LAST - datagen.SOLD_FIRST + 2  # every date is drawn at this scale
    assert all(os.listdir(os.path.join(directory, n)) == ["part-00000.snappy.parquet"] for n in names)


def _the_partition_column_is_in_the_path_and_not_in_the_file(tables, scans, directory):
    for name in os.listdir(directory)[:40]:
        assert papq.ParquetFile(_file(directory, name)).schema.names == q7.COLUMNS["store_sales"][1:]


def _rows_of_a_date_in_table_order(tables, scans, directory):
    sales = tables["store_sales"]
    key, _, valid = sales[KEY]
    for value in (int(key[valid][0]), min(YEAR_2000), None):
        rows = np.flatnonzero(~valid if value is None else valid & (key == value))
        got = papq.read_table(_file(directory, f"{KEY}={hive.NULL_DIRECTORY if value is None else value}"))
        assert got.num_rows == len(rows) > 0
        for name in ("ss_item_sk", "ss_cdemo_sk", "ss_promo_sk"):
            data, _, ok = sales[name]
            assert got.column(name).to_pylist() == [int(v) if o else None for v, o in zip(data[rows], ok[rows])]
        assert got.column("ss_quantity").to_pylist() == sales["ss_quantity"][0][rows].tolist()
        for name in ("ss_list_price", "ss_sales_price", "ss_coupon_amt"):
            assert [int(v.scaleb(2)) for v in got.column(name).to_pylist()] == sales[name][0][rows].tolist()


def _spark_types_snappy_and_real_nulls(tables, scans, directory):
    nulls = 0
    for name in sorted(os.listdir(directory))[:60]:
        f = papq.ParquetFile(_file(directory, name))
        assert f.metadata.num_row_groups == 1
        for i, column in enumerate(f.schema.names):
            col, chunk = f.schema.column(i), f.metadata.row_group(0).column(i)
            assert col.max_definition_level == 1 and chunk.compression == "SNAPPY"  # OPTIONAL
            if column.endswith("_sk"):
                assert col.physical_type == "INT64"
                nulls += chunk.statistics.null_count
            elif column == "ss_quantity":
                assert col.physical_type == "INT32" and chunk.statistics.null_count == 0
            else:
                assert col.physical_type == "INT32" and col.logical_type.type == "DECIMAL"
                assert (col.precision, col.scale) == (7, 2) and chunk.statistics.null_count == 0
    assert nulls > 0  # definition levels with both values


def _the_listing_keeps_the_year_2000_and_never_the_nulls(tables, scans, directory):
    selected = hive.selected_values(hive.DYNAMIC_PRUNING["tpcds", "q7"]["store_sales"], scans)
    assert selected == YEAR_2000  # from date_dim's d_year, by the entry's own numpy
    kept = hive.list_partitions(directory, selected)
    assert [value for _, _, value in kept] == sorted(YEAR_2000)
    assert all(path == _file(directory, f"{KEY}={value}") and size == os.path.getsize(path)
               for path, size, value in kept)
    # what the reference joins: the sales of the kept directories are q7's date join, row for row
    key, _, valid = tables["store_sales"][KEY]
    assert sum(papq.ParquetFile(p).metadata.num_rows for p, _, _ in kept) == int(
        (valid & np.isin(key, list(YEAR_2000))).sum())


@pytest.mark.parametrize("check", [
    _one_directory_a_sold_date_and_one_for_the_nulls,
    _the_partition_column_is_in_the_path_and_not_in_the_file, _rows_of_a_date_in_table_order,
    _spark_types_snappy_and_real_nulls, _the_listing_keeps_the_year_2000_and_never_the_nulls],
    ids=lambda f: f.__name__.lstrip("_"))
def test_the_table_is_laid_out_and_listed_as_stated(written, check):
    check(*written)


# ------------------------------------------ the query and its counters

def test_one_query_is_the_references_over_the_pruned_packed_files(test_scale):
    from blaze_tpu.ops import FileSplit, ParquetScanExec
    from blaze_tpu.runtime import dispatch
    from blaze_tpu.serde.from_proto import plan_from_proto
    from blaze_tpu.serde.to_proto import plan_to_proto

    _, config, traffic = _config()
    cell = run.Cell(config, traffic, SEED)
    scans = {tuple(s.schema.names): s for s in _scans(cell.plan())}
    sales = scans[tuple(q7.COLUMNS["store_sales"][1:] + [KEY])]
    assert isinstance(sales, ParquetScanExec) and sales.partition_schema.names == [KEY]
    assert sales.stated_batch_rows == BATCH_ROWS
    files = [e for g in sales.file_groups for e in g]
    assert len(files) == 366 and all(type(e) is FileSplit and e.start == 0 for e in files)
    assert sorted(e.values for e in files) == [(v,) for v in sorted(YEAR_2000)]
    assert all(e.path.endswith(f"{KEY}={e.values[0]}/part-00000.snappy.parquet") for e in files)
    assert all(e.length == os.path.getsize(e.path) for e in files)
    root = os.path.dirname(os.path.dirname(os.path.dirname(files[0].path)))
    # as many tasks as Spark's three functions give, by hand: next fit, largest first
    max_split = one_file.max_split_bytes([e.length for e in files], 4)
    tasks, weight = [0], 0
    for length in sorted((e.length for e in files), reverse=True):
        if tasks[-1] and weight + length > max_split:
            tasks.append(0)
            weight = 0
        tasks[-1] += 1
        weight += length + OPEN_COST
    assert [len(g) for g in sales.file_groups] == tasks and len(tasks) > 4
    # the partition schema and every file's value survive the wire
    back = plan_from_proto(plan_to_proto(sales))
    assert back.file_groups == sales.file_groups and back.partition_schema.names == [KEY]
    dimension_ranges = sum(len(g) for names, s in scans.items() if s is not sales for g in s.file_groups)
    del scans, sales, back

    with dispatch.capture() as c:
        got, _ = cell.query()
    expected = q7.oracle(cell.tables)
    compared, ok = compare.compare([got], expected, q7.canonical, q7.TOLERANCE)
    assert ok and len(expected["i_item_id"]) == q7.LIMIT, compared
    for column in ("i_item_id", "agg2", "agg3", "agg4"):  # exact everywhere but the double
        assert got[column] == expected[column], column
    _, control_ok = compare.compare([q7.control(cell.tables)], expected, q7.canonical, q7.TOLERANCE)
    assert not control_ok
    assert c["scan_partition_files"] == 366 and c["scan_splits"] == 366 + dimension_ranges
    assert c["scan_chunks_native"] == c["scan_chunks"] and c["scan_pages"] == 0
    key, _, valid = cell.tables["store_sales"][KEY]
    kept_rows = int((valid & np.isin(key, list(YEAR_2000))).sum())
    assert c["scan_rows"] == kept_rows + sum(n for t, n in cell.rows.items() if t != "store_sales")
    assert c["scan_pieces_packed"] >= 366 - len(tasks) and c["scan_coalesce_n"] >= len(tasks) - 1
    assert 100.0 * c["scan_rows"] / c["scan_rows_budget"] > 60
    # the four joins probe the packed batches: a few a task, not one a file
    assert c["join_probe_n"] < 4 * 2 * len(tasks)

    cell.release()
    assert not os.path.exists(root)


def test_the_cell_reads_correct_past_the_look_for_a_chip(test_scale):
    manifest, config, traffic = _config()
    out = run.measure(CELL, manifest, config, traffic, SEED + 1, 0.3, 0, run.device_stamp())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert {k: v["value"] for k, v in out["compared"].items()} == {"queries_wrong": 0, "cells_wrong": 0}
    assert set(out["metrics"]) == {"query_s", "query_p95_s", "setup_s"}
    counters = out["info"]["counters"]
    assert counters["scan_partition_files"] == 366 * out["attempted"]
    assert out["info"]["rows"]["store_sales"] == datagen.rows("store_sales", SCALE)


def test_a_program_without_partition_values_stops_the_entry_at_set_up(monkeypatch):
    import collections

    import blaze_tpu.ops

    monkeypatch.setattr(blaze_tpu.ops, "FileSplit",
                        collections.namedtuple("FileSplit", "path start length"))
    with pytest.raises(ImportError, match="partition values"):
        hive.source("tpcds", "q7", {}, 4)


# ---------------------------------------------------------- the readers

#: metric -> (counters, queries, the stated value)
CASES = {
    "scan_batch_fill_pct": ({"scan_rows": 2_563_175 * 13, "scan_rows_budget": 2_843_864 * 13}, 13,
                            100.0 * 2_563_175 / 2_843_864),
    "scan_coalesce_ms": ({"scan_coalesce_ns": 195_000_000, "scan_coalesce_n": 156}, 13, 15.0),
    "scan_open_per_file_ms": ({"scan_open_ns": 9_646_000_000, "scan_splits": 371 * 13}, 13, 2.0),
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
