"""The cell ``tpch_q06_sf1_parquet`` by its files alone (PR 34): the
entry that writes the seeded tables as Spark 3.5.1 would and scans them
back, the layout of what it writes, the program's ``scan_decode`` span
and counters, and the two readers over them.  Everything runs on the CPU
at a test's scale; the files are pyarrow's, read back with pyarrow."""

import importlib
import os

import pyarrow.parquet as papq
import pytest

from bench import compare, entries, run
from bench.entries import catalyst_parquet
from bench.suites.tpch import datagen, q6

CELL = "tpch_q06_sf1_parquet"
SCALE = 0.01       # ~60,000 rows of lineitem, ~15,000 a partition
BATCH_ROWS = 8192  # two batches a partition at that scale
SEED = 2**31 + 11
DECIMALS = ["l_quantity", "l_extendedprice", "l_discount"]


def _config(**changes):
    manifest, _, config, traffic = run.resolve(CELL)
    return manifest, dict(config, **{"scale": SCALE, "batch_rows": BATCH_ROWS, **changes}), traffic


def _file_scan(node):
    while node.children:
        node = node.children[0]
    return node


# ------------------------------------------------------------ the cell

def test_the_cell_resolves_by_name_through_its_own_entry():
    manifest, entry, config, traffic = run.resolve(CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("tpch-sf1-p4-parquet", "q06_closed1_t2", 1)
    assert (config["suite"], config["entry"], config["scale"]) == ("tpch", "catalyst_parquet", 1.0)
    assert (config["partitions"], config["batch_rows"]) == (4, 65536)
    assert (traffic["query"], traffic["traced_queries"]) == ("q6", 2)
    assert callable(importlib.import_module("bench.entries." + config["entry"]).source)
    # the in-memory deployment word for word, but for what this one states of its files
    memory = run.read_json("bench", "configs", "tpch-sf1-p4.json")
    for key in ("suite", "schema", "scale", "partitions", "batch_rows", "chips", "reduced", "reduced_why"):
        assert config[key] == memory[key], key
    assert {k: config["guarantees"][k] for k in memory["guarantees"]} == memory["guarantees"]
    assert set(config["guarantees"]) - set(memory["guarantees"]) == {"scan"}
    assert config["layout"]["row_group_rows"] == catalyst_parquet.WRITER["row_group_size"] == 1_048_576
    # the two new metrics are this cell's alone; the unlisted ones read it as every cell
    for cell in (w["name"] for w in manifest["workloads"]):
        readers = set(run.metric_readers(manifest, cell))
        assert ({"scan_decode_ms", "scan_file_mb"} <= readers) == (cell == CELL), cell
        assert {"scan_stage_ms", "h2d_mb", "launch_ms", "programs_per_query", "warm_compiles",
                "kernels_roofline", "device_idle_pct"} <= readers


def test_the_manifest_grew_at_its_ends_and_kept_what_it_had():
    """New entries go last in their lists, so PR 33's
    ``test_search_steps_are_read_in_the_join_cells_only`` — which takes
    ``per_layer[-1]`` for ``join_search_steps`` — fails from this PR on;
    what it checked is checked here by name."""
    manifest, *_ = run.resolve(CELL)
    assert [c["name"] for c in manifest["configs"]][-1] == "tpch-sf1-p4-parquet"
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert [m["name"] for m in manifest["per_layer"]][-3:] == [
        "join_search_steps", "scan_decode_ms", "scan_file_mb"]
    steps = next(m for m in manifest["per_layer"] if m["name"] == "join_search_steps")
    assert steps == {"name": "join_search_steps", "unit": "count", "better": "lower",
                     "source": "program_counter", "layer": "operators", "moves": "query_s",
                     "workloads": ["tpcds_q07_sf1", "tpch_q03_sf0.5"]}
    for cell in (w["name"] for w in manifest["workloads"]):
        assert ("join_search_steps" in run.metric_readers(manifest, cell)) == (cell in steps["workloads"])
    for name, unit, source in (("scan_decode_ms", "ms", "program_span"),
                               ("scan_file_mb", "MB", "program_counter")):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                         "layer": "operators", "moves": "query_s", "workloads": [CELL]}


def test_the_cell_reads_correct_past_the_look_for_a_chip():
    manifest, config, traffic = _config()
    out = run.measure(CELL, manifest, config, traffic, SEED, 0.3, 0, run.device_stamp())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert {k: v["value"] for k, v in out["compared"].items()} == {"queries_wrong": 0, "cells_wrong": 0}
    assert set(out["metrics"]) == {"query_s", "query_p95_s", "setup_s"}
    assert out["info"]["counters"]["scan_row_groups"] == 4 * out["attempted"]


# ------------------------------------------------------------ the files

@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """The seeded tables, and the files the entry writes of them."""
    tables = {t: datagen.generate_table(t, SCALE, SEED, cols) for t, cols in q6.COLUMNS.items()}
    scans = entries.memory_scans("tpch", tables, q6.COLUMNS, 4, BATCH_ROWS)
    directory = str(tmp_path_factory.mktemp("files") / "lineitem")
    paths = catalyst_parquet.write_partitions(scans["lineitem"], directory)
    return tables["lineitem"], paths


def _one_file_a_partition(table, paths, chunks):
    assert [os.path.basename(p) for p in paths] == [f"part-0000{p}.snappy.parquet" for p in range(4)]
    n = table["l_shipdate"][0].shape[0]
    assert [papq.ParquetFile(p).metadata.num_rows for p in paths] == [
        (p + 1) * n // 4 - p * n // 4 for p in range(4)]
    assert all(os.path.getsize(p) < 128 << 20 for p in paths)  # one split each


def _snappy(table, paths, chunks):
    assert {c.compression for c in chunks} == {"SNAPPY"}


def _physical_and_logical_types(table, paths, chunks):
    schema = papq.ParquetFile(paths[0]).schema
    for i in range(len(schema.names)):
        column = schema.column(i)
        if column.name in DECIMALS:
            assert column.physical_type == "INT64" and column.logical_type.type == "DECIMAL"
            assert (column.precision, column.scale) == (12, 2)
        else:
            assert column.name == "l_shipdate"
            assert column.physical_type == "INT32" and column.logical_type.type == "DATE"
    assert schema.names == q6.COLUMNS["lineitem"]


def _optional_columns_without_nulls(table, paths, chunks):
    schema = papq.ParquetFile(paths[0]).schema
    assert all(schema.column(i).max_definition_level == 1 for i in range(len(schema.names)))
    assert all(c.statistics.null_count == 0 for c in chunks)


def _dictionary_pages(table, paths, chunks):
    for c in chunks:
        if c.path_in_schema in ("l_quantity", "l_discount", "l_shipdate"):
            assert c.has_dictionary_page and "RLE_DICTIONARY" in c.encodings, c.path_in_schema


def _row_groups(table, paths, chunks):
    for p in paths:
        meta = papq.ParquetFile(p).metadata
        assert meta.num_row_groups == -(-meta.num_rows // 1_048_576) == 1
        assert meta.row_group(0).num_columns == 4


def _rows_in_order(table, paths, chunks):
    n = table["l_shipdate"][0].shape[0]
    for p, path in enumerate(paths):
        lo, hi = p * n // 4, (p + 1) * n // 4
        got = papq.read_table(path)
        for name in DECIMALS:
            unscaled = [int(v.scaleb(2)) for v in got.column(name).to_pylist()]
            assert unscaled == table[name][0][lo:hi].tolist(), name
        days = got.column("l_shipdate").cast("int32").to_numpy()
        assert (days == table["l_shipdate"][0][lo:hi]).all()


@pytest.mark.parametrize("check", [
    _one_file_a_partition, _snappy, _physical_and_logical_types, _optional_columns_without_nulls,
    _dictionary_pages, _row_groups, _rows_in_order], ids=lambda f: f.__name__.lstrip("_"))
def test_files_carry_the_stated_layout(written, check):
    table, paths = written
    chunks = [papq.ParquetFile(p).metadata.row_group(g).column(c)
              for p in paths for g in range(papq.ParquetFile(p).metadata.num_row_groups) for c in range(4)]
    check(table, paths, chunks)


def test_a_row_group_is_cut_at_the_stated_length(tmp_path, monkeypatch):
    """What the SF1 files have and a test's scale has not: a second row
    group.  The writer's own options over a partition of 5,000 rows,
    the length alone brought down."""
    tables = {"lineitem": datagen.generate_table("lineitem", 0.004, SEED, q6.COLUMNS["lineitem"])}
    scans = entries.memory_scans("tpch", tables, q6.COLUMNS, 4, BATCH_ROWS)
    monkeypatch.setattr(catalyst_parquet, "WRITER", dict(catalyst_parquet.WRITER, row_group_size=4096))
    paths = catalyst_parquet.write_partitions(scans["lineitem"], str(tmp_path / "lineitem"))
    meta = papq.ParquetFile(paths[0]).metadata
    assert meta.num_rows > 4096 and meta.num_row_groups == 2
    assert meta.row_group(0).num_rows == 4096


def test_a_string_column_is_written_from_its_padded_bytes(tmp_path):
    """q1 over the same files is the next cell: its two flags are strings."""
    columns = {"lineitem": ["l_returnflag", "l_shipdate"]}
    tables = {"lineitem": datagen.generate_table("lineitem", 0.002, SEED, columns["lineitem"])}
    scans = entries.memory_scans("tpch", tables, columns, 2, BATCH_ROWS)
    paths = catalyst_parquet.write_partitions(scans["lineitem"], str(tmp_path / "lineitem"))
    data, lengths = tables["lineitem"]["l_returnflag"][:2]
    want = [bytes(row[:n]).decode() for row, n in zip(data, lengths)]
    got = [v for p in paths for v in papq.read_table(p).column("l_returnflag").to_pylist()]
    assert got == want and set(want) <= {"A", "N", "R"}


# ------------------------------------------ the query and its counters

def test_one_query_is_the_references_and_tallies_what_the_files_hold():
    from blaze_tpu.ops import ParquetScanExec
    from blaze_tpu.runtime import dispatch

    _, config, traffic = _config()
    cell = run.Cell(config, traffic, SEED)
    scan = _file_scan(cell.plan())
    assert isinstance(scan, ParquetScanExec) and scan.stated_batch_rows == BATCH_ROWS
    files = [path for group in scan.file_groups for path in group]
    root = os.path.dirname(os.path.dirname(files[0]))
    assert len(files) == config["partitions"] and all(os.path.isfile(f) for f in files)
    metas = [papq.ParquetFile(f).metadata for f in files]
    row_groups = sum(m.num_row_groups for m in metas)
    chunk_bytes = sum(m.row_group(g).column(c).total_compressed_size
                      for m in metas for g in range(m.num_row_groups) for c in range(4))
    batches = sum(-(-m.num_rows // BATCH_ROWS) for m in metas)
    del scan

    with dispatch.capture() as c:
        got, _ = cell.query()
    expected = q6.oracle(cell.tables)
    assert got == expected and expected["revenue"][0] > 0
    _, control_ok = compare.compare([q6.control(cell.tables)], expected, q6.canonical)
    assert not control_ok
    assert (c["scan_row_groups"], c["scan_decode_n"]) == (row_groups, row_groups)
    assert c["scan_file_bytes"] == chunk_bytes > 0
    assert c["scan_decode_ns"] > 0 and "scan_row_groups_pruned" not in c
    # every task came out of TaskDefinition bytes and scanned in the batches the plan states
    assert c["scan_stage_n"] == batches == 2 * config["partitions"]
    assert c["h2d_bytes"] == batches * BATCH_ROWS * (3 * 8 + 4 + 4)

    cell.release()
    assert not os.path.exists(root)


#: metric -> (counter it reads, counter value, queries, the stated quotient)
CASES = {
    "scan_decode_ms": ("scan_decode_ns", 13_800_000_000, 8, 1725.0),
    "scan_file_mb": ("scan_file_bytes", 264_579_328, 8, 33.072416),
}


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_returns_its_counter_per_query_or_nothing(metric):
    counter, value, queries, want = CASES[metric]
    reader = importlib.import_module("bench.metrics." + metric)
    run_ = {"queries": queries, "counters": {counter: value}, "plan_s": [], "trace": None,
            "memory_peak_bytes": None, "least_bytes": 1, "peak": {"hbm_bytes_per_s": 1.0}}
    assert reader.read(run_) == pytest.approx(want)
    # the parent's program has no such counter: nothing, not a 0; nor with no query done
    assert reader.read(dict(run_, counters={"scan_stage_ns": 5, "h2d_bytes": 7})) is None
    assert reader.read(dict(run_, queries=0)) is None
