"""The cell ``tpch_q01_sf1_parquet`` by its files alone (PR 36): one
file a table, cut into the byte ranges Spark 3.5.1 would hand its tasks.
The split planner against hand-worked cases of Spark's formula, the
layout of the file, one query on the CPU at a test's scale against the
reference, and the two readers.  Every manifest entry is found by name."""

import importlib
import os

import pyarrow.parquet as papq
import pytest

from bench import compare, entries, run
from bench.entries import catalyst_parquet, catalyst_parquet_1file as one_file
from bench.suites.tpch import datagen, q1

CELL = "tpch_q01_sf1_parquet"
CONFIG = "tpch-sf1-p4-parquet-1file"
SCALE = 0.01       # ~60,000 rows of lineitem
BATCH_ROWS = 8192
ROW_GROUP_ROWS = 8192  # eight row groups at that scale
SEED = 2**31 + 36
MB = 1 << 20
FLAGS = ["l_returnflag", "l_linestatus"]


def _named(entries_, name):
    (found,) = [e for e in entries_ if e["name"] == name]
    return found


def _config(**changes):
    manifest, _, config, traffic = run.resolve(CELL)
    return manifest, dict(config, **{"scale": SCALE, "batch_rows": BATCH_ROWS, **changes}), traffic


@pytest.fixture
def test_scale(monkeypatch):
    """What SF1 has and a test's scale has not: a file several times
    ``maxSplitBytes`` and several row groups long.  The writer's row
    group and Spark's open cost come down with the table; the formula,
    the packing and the writer's other options stay."""
    monkeypatch.setattr(catalyst_parquet, "WRITER",
                        dict(catalyst_parquet.WRITER, row_group_size=ROW_GROUP_ROWS))
    monkeypatch.setattr(one_file, "OPEN_COST_IN_BYTES", 1024)


def _file_scan(node):
    while node.children:
        node = node.children[0]
    return node


# ------------------------------------------------------------ the cell

def test_the_cell_resolves_by_name_through_its_own_entry():
    manifest, entry, config, traffic = run.resolve(CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "q01_closed1", 1)
    assert (config["suite"], config["entry"], config["scale"]) == ("tpch", "catalyst_parquet_1file", 1.0)
    assert (config["partitions"], config["batch_rows"]) == (4, 65536)
    assert (traffic["query"], traffic["traced_queries"]) == ("q1", 2)
    assert importlib.import_module("bench.entries." + config["entry"]) is one_file
    listed = _named(manifest["configs"], CONFIG)
    assert listed["source"] == config["source"] and len(listed["source"]) <= 200
    assert (listed["file"], listed["reduced"]) == (f"bench/configs/{CONFIG}.json", ["scale"])
    # the four-file deployment word for word, but for what this one states of its file
    files = run.read_json("bench", "configs", "tpch-sf1-p4-parquet.json")
    for key in ("suite", "schema", "scale", "partitions", "batch_rows", "chips", "reduced", "reduced_why"):
        assert config[key] == files[key], key
    assert {k: config["guarantees"][k] for k in ("results", "path")} == {
        k: files["guarantees"][k] for k in ("results", "path")}
    for key in ("codec", "pages", "encoding", "row_group_rows", "nullability"):
        assert config["layout"][key] == files["layout"][key], key
    assert config["layout"]["row_group_rows"] == catalyst_parquet.WRITER["row_group_size"]


def test_the_two_metrics_are_read_in_the_file_cells_only():
    manifest, *_ = run.resolve(CELL)
    file_cells = {CELL, "tpch_q06_sf1_parquet"}
    for name, unit, source in (("scan_open_ms", "ms", "program_span"),
                               ("scan_splits", "count", "program_counter")):
        assert _named(manifest["per_layer"], name) == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "operators", "moves": "query_s", "workloads": sorted(file_cells)}
    for cell in (w["name"] for w in manifest["workloads"]):
        readers = set(run.metric_readers(manifest, cell))
        assert ({"scan_open_ms", "scan_splits"} <= readers) == (cell in file_cells), cell
    # the unlisted ones read this cell as they read every cell
    assert {"plan_ms", "programs_per_query", "warm_compiles", "kernels_roofline", "device_idle_pct",
            "device_peak_mb", "task_decode_ms", "scan_stage_ms", "h2d_mb", "launch_ms",
            "device_read_ms"} <= set(run.metric_readers(manifest, CELL))


# ------------------------------------------------- Spark's split planning

#: files (bytes each), cores -> maxSplitBytes and the partitions' pieces as
#: (file, start, length), worked by hand from Spark 3.5.1's three functions
PLANS = {
    # (37 MB + 4 MB) / 4: three pieces of 10.25 MB and the 6.25 MB left, one a partition
    "one_37mb_file": ([37 * MB], 4, 41 * MB // 4,
                      [[(0, 0, 10747904)], [(0, 10747904, 10747904)], [(0, 21495808, 10747904)],
                       [(0, 32243712, 6553600)]]),
    # tpch-sf1-p4-parquet's layout: 4 x (8.27 + 4) / 4 = 12.27 MB holds a file, not two
    "four_8mb_files": ([8_671_723] * 4, 4, 8_671_723 + 4 * MB,
                       [[(0, 0, 8_671_723)], [(1, 0, 8_671_723)], [(2, 0, 8_671_723)], [(3, 0, 8_671_723)]]),
    # (300 + 4) / 4 = 76 MB: maxPartitionBytes is not reached
    "one_300mb_file": ([300 * MB], 4, 76 * MB,
                       [[(0, 0, 76 * MB)], [(0, 76 * MB, 76 * MB)], [(0, 152 * MB, 76 * MB)],
                        [(0, 228 * MB, 72 * MB)]]),
    # ... and here it is: 128 MB pieces, the 104 MB left last
    "one_1gb_file_on_4_cores": ([1000 * MB], 4, 128 * MB,
                                [[(0, i * 128 * MB, 128 * MB)] for i in range(7)]
                                + [[(0, 896 * MB, 104 * MB)]]),
    # never under the open cost: a 4 KB file is one split
    "one_4kb_file": ([4096], 4, 4 * MB, [[(0, 0, 4096)]]),
    # small files pack: (1 + 4) x 10 / 4 = 12.5 MB, and a file in weighs 5 MB, so three fit (10 + 1 <= 12.5)
    "ten_1mb_files": ([MB] * 10, 4, 50 * MB // 4,
                      [[(0, 0, MB), (1, 0, MB), (2, 0, MB)], [(3, 0, MB), (4, 0, MB), (5, 0, MB)],
                       [(6, 0, MB), (7, 0, MB), (8, 0, MB)], [(9, 0, MB)]]),
    # largest piece first, whatever the order of the files
    "a_small_and_a_large_file": ([MB, 20 * MB], 2, 29 * MB // 2,
                                 [[(1, 0, 15204352)], [(1, 15204352, 5767168), (0, 0, MB)]]),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_the_planner_reproduces_sparks_formula(case):
    sizes, cores, max_split, want = PLANS[case]
    files = [(f"/t/part-{i:05d}.snappy.parquet", size) for i, size in enumerate(sizes)]
    assert one_file.max_split_bytes(sizes, cores) == max_split
    got = one_file.plan_splits(files, cores)
    assert got == [[(files[i][0], start, length) for i, start, length in pieces] for pieces in want]
    # the pieces of a file tile it
    for path, size in files:
        mine = sorted((s, n) for pieces in got for p, s, n in pieces if p == path)
        assert mine[0][0] == 0 and sum(n for _, n in mine) == size
        assert all(a[0] + a[1] == b[0] for a, b in zip(mine, mine[1:]))


def test_the_configuration_states_the_splits_the_planner_gives():
    """``layout.splits`` was read off the SF1 file at one seed: the
    planner gives those ranges for that length, and the midpoints stated
    fall in them as stated."""
    splits = run.read_json("bench", "configs", CONFIG + ".json")["layout"]["splits"]
    assert (splits["spark.sql.files.maxPartitionBytes"], splits["spark.sql.files.openCostInBytes"]) == (
        one_file.MAX_PARTITION_BYTES, one_file.OPEN_COST_IN_BYTES)
    cores = splits["spark.sql.files.minPartitionNum"]
    assert cores == run.read_json("bench", "configs", CONFIG + ".json")["partitions"] == 4
    assert one_file.max_split_bytes([splits["file_bytes"]], cores) == splits["max_split_bytes"]
    planned = one_file.plan_splits([("f", splits["file_bytes"])], cores)
    assert [[[s, n] for _, s, n in pieces] for pieces in planned] == [
        [piece] for piece in splits["split_start_length"]]
    assert [[g for g, mid in enumerate(splits["row_group_midpoints"]) if s <= mid < s + n]
            for s, n in splits["split_start_length"]] == splits["row_groups_of_split"]
    assert sorted(g for gs in splits["row_groups_of_split"] for g in gs) == list(range(6))
    assert sum(splits["row_group_rows"]) > 5_990_000 and max(splits["row_group_rows"]) == 1_048_576


# ------------------------------------------------------------ the file

@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """The seeded table, and the one file the entry writes of it."""
    tables = {t: datagen.generate_table(t, SCALE, SEED, cols) for t, cols in q1.COLUMNS.items()}
    scans = entries.memory_scans("tpch", tables, q1.COLUMNS, 4, BATCH_ROWS)
    directory = str(tmp_path_factory.mktemp("files") / "lineitem")
    path = one_file.write_one_file(scans["lineitem"], directory)
    return tables["lineitem"], path


def _one_file(table, path, meta, chunks):
    assert os.listdir(os.path.dirname(path)) == ["part-00000.snappy.parquet"]
    assert meta.num_rows == table["l_shipdate"][0].shape[0] > 50_000


def _snappy(table, path, meta, chunks):
    assert {c.compression for c in chunks} == {"SNAPPY"}


def _seven_columns_in_spark_types(table, path, meta, chunks):
    schema = papq.ParquetFile(path).schema
    assert schema.names == q1.COLUMNS["lineitem"] and meta.row_group(0).num_columns == 7
    for i, name in enumerate(schema.names):
        column = schema.column(i)
        assert column.max_definition_level == 1  # OPTIONAL
        if name in FLAGS:
            assert column.physical_type == "BYTE_ARRAY" and column.logical_type.type == "STRING"
        elif name == "l_shipdate":
            assert column.physical_type == "INT32" and column.logical_type.type == "DATE"
        else:
            assert column.physical_type == "INT64" and column.logical_type.type == "DECIMAL"
            assert (column.precision, column.scale) == (12, 2)


def _flags_are_dictionary_strings(table, path, meta, chunks):
    flags = [c for c in chunks if c.path_in_schema in FLAGS]
    assert len(flags) == 2 * meta.num_row_groups
    for c in flags:
        assert c.has_dictionary_page and "RLE_DICTIONARY" in c.encodings
        assert c.statistics.null_count == 0


def _row_groups_of_the_writers_length(table, path, meta, chunks):
    # the writer's own 1,048,576 rows hold a test's table whole
    assert catalyst_parquet.WRITER["row_group_size"] == 1_048_576
    assert meta.num_row_groups == -(-meta.num_rows // 1_048_576) == 1


def _rows_in_partition_order(table, path, meta, chunks):
    got = papq.read_table(path)
    for name in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        assert [int(v.scaleb(2)) for v in got.column(name).to_pylist()] == table[name][0].tolist(), name
    assert (got.column("l_shipdate").cast("int32").to_numpy() == table["l_shipdate"][0]).all()
    for name in FLAGS:
        data, lengths = table[name][:2]
        assert got.column(name).to_pylist() == [bytes(r[:n]).decode() for r, n in zip(data, lengths)]


@pytest.mark.parametrize("check", [
    _one_file, _snappy, _seven_columns_in_spark_types, _flags_are_dictionary_strings,
    _row_groups_of_the_writers_length, _rows_in_partition_order], ids=lambda f: f.__name__.lstrip("_"))
def test_the_file_carries_the_stated_layout(written, check):
    table, path = written
    meta = papq.ParquetFile(path).metadata
    chunks = [meta.row_group(g).column(c) for g in range(meta.num_row_groups) for c in range(7)]
    check(table, path, meta, chunks)


# ------------------------------------------ the query and its counters

def test_one_query_is_the_references_and_reads_every_row_group_once(test_scale):
    from blaze_tpu.ops import FileSplit, ParquetScanExec
    from blaze_tpu.runtime import dispatch

    _, config, traffic = _config()
    cell = run.Cell(config, traffic, SEED)
    scan = _file_scan(cell.plan())
    assert isinstance(scan, ParquetScanExec) and scan.stated_batch_rows == BATCH_ROWS
    # four FilePartitions of one PartitionedFile each, tiling the one file
    splits = [entry for group in scan.file_groups for entry in group]
    assert len(scan.file_groups) == len(splits) == 4 and all(type(s) is FileSplit for s in splits)
    (path,) = {s.path for s in splits}
    root = os.path.dirname(os.path.dirname(path))
    size = os.path.getsize(path)
    assert [s.start for s in splits] == [sum(t.length for t in splits[:i]) for i in range(4)]
    assert sum(s.length for s in splits) == size
    assert splits[0].length == one_file.max_split_bytes([size], 4) == (size + 1024) // 4
    meta = papq.ParquetFile(path).metadata
    row_groups = meta.num_row_groups
    assert row_groups == -(-meta.num_rows // ROW_GROUP_ROWS) == 8
    chunk_bytes = sum(meta.row_group(g).column(c).total_compressed_size
                      for g in range(row_groups) for c in range(7))
    del scan

    with dispatch.capture() as c:
        got, _ = cell.query()
    expected = q1.oracle(cell.tables)
    assert got == expected and len(expected["count_order"]) == 4
    assert sum(expected["count_order"]) > 0.9 * meta.num_rows
    _, control_ok = compare.compare([q1.control(cell.tables)], expected, q1.canonical)
    assert not control_ok
    assert (c["scan_splits"], c["scan_open_n"]) == (4, 4) and c["scan_open_ns"] > 0
    assert (c["scan_row_groups"], c["scan_decode_n"]) == (row_groups, row_groups)
    assert c["scan_row_groups_other_split"] == 4 * row_groups - row_groups
    assert c["scan_file_bytes"] == chunk_bytes > 0 and "scan_row_groups_pruned" not in c
    # every row reached the dense grouped update, from TaskDefinition bytes
    assert c["agg_dense_updates"] > 0 and c.get("agg_grouped_updates", 0) == c["agg_dense_updates"]

    cell.release()
    assert not os.path.exists(root)


def test_the_cell_reads_correct_past_the_look_for_a_chip(test_scale):
    manifest, config, traffic = _config()
    out = run.measure(CELL, manifest, config, traffic, SEED, 0.3, 0, run.device_stamp())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert {k: v["value"] for k, v in out["compared"].items()} == {"queries_wrong": 0, "cells_wrong": 0}
    assert set(out["metrics"]) == {"query_s", "query_p95_s", "setup_s"}
    counters = out["info"]["counters"]
    assert counters["scan_splits"] == 4 * out["attempted"]
    assert counters["scan_row_groups"] == 8 * out["attempted"]
    assert counters["scan_row_groups_other_split"] == 24 * out["attempted"]


def test_q1_through_the_catalyst_entry_from_memory_scans_is_the_references_too():
    """The same dump over ``MemoryScanExec``s (``entry: catalyst``): the
    converted q1 — decimal promotions, ``aggregate.Average``, the range
    exchange under the global sort — without a file in the way."""
    _, config, traffic = _config(entry="catalyst")
    cell = run.Cell(config, traffic, SEED)
    got, _ = cell.query()
    assert got == q1.oracle(cell.tables)


#: metric -> (counter it reads, counter value, queries, the stated quotient)
CASES = {
    "scan_open_ms": ("scan_open_ns", 54_000_000, 18, 3.0),
    "scan_splits": ("scan_splits", 72, 18, 4.0),
}


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_returns_its_counter_per_query_or_nothing(metric):
    counter, value, queries, want = CASES[metric]
    reader = importlib.import_module("bench.metrics." + metric)
    run_ = {"queries": queries, "counters": {counter: value}, "plan_s": [], "trace": None,
            "memory_peak_bytes": None, "least_bytes": 1, "peak": {"hbm_bytes_per_s": 1.0}}
    assert reader.read(run_) == pytest.approx(want)
    # the parent's program has no such counter: nothing, not a 0; nor with no query done
    assert reader.read(dict(run_, counters={"scan_decode_ns": 5, "scan_row_groups": 7})) is None
    assert reader.read(dict(run_, queries=0)) is None
