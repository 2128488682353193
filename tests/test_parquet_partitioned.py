"""A Hive-partitioned table through ``ParquetScanExec`` (PR 40): a file
of a scan's group may carry its directory's values of the partition
columns, which follow the file's columns in the output; pieces shorter
than a batch — small files, short row groups — are packed on the host
before they are staged; both survive the wire; the catalyst scan with
``partitionFilters`` converts where Spark's listing has applied them."""

import os

import numpy as np
import pytest

from blaze_tpu.batch import batch_to_pydict, bucket_capacity
from blaze_tpu.exprs.ir import BinOp, Col, Lit
from blaze_tpu.io import parquet as pq
from blaze_tpu.ops import FileSplit, ParquetScanExec
from blaze_tpu.runtime import dispatch
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema
from blaze_tpu.serde import plan_pb2 as pb
from blaze_tpu.serde.from_proto import plan_from_proto, run_task
from blaze_tpu.serde.to_proto import plan_to_proto

pa = pytest.importorskip("pyarrow")
import pyarrow.parquet as papq  # noqa: E402

READ = Schema([Field("k", DataType.int64()), Field("price", DataType.decimal(7, 2)),
               Field("qty", DataType.int32())])
BY_DAY = Schema([Field("day", DataType.int64())])
NULL_SHARE = 0.045


def _write(path, rows, seed):
    """``rows`` rows of READ's columns as Spark writes them: the decimal
    of seven digits INT32, the key really NULL in 4.5% of rows.  Returns
    them as python rows."""
    rng = np.random.RandomState(seed)
    k = rng.randint(1, 1 << 40, rows)
    null = rng.rand(rows) < NULL_SHARE
    cents = rng.randint(0, 9_999_999, rows).astype(np.int64)
    qty = rng.randint(1, 101, rows).astype(np.int32)
    words = np.zeros((rows, 2), np.int64)
    words[:, 0] = cents
    price = pa.Array.from_buffers(pa.decimal128(7, 2), rows, [None, pa.py_buffer(words)])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    papq.write_table(pa.table({"k": pa.array(k, mask=null), "price": price, "qty": pa.array(qty)}),
                     path, compression="snappy", store_decimal_as_integer=True)
    return [(None if n else int(a), int(c), int(q)) for a, n, c, q in zip(k, null, cents, qty)]


def _layout(tmp_path, values, rows_of, seed=40):
    """``<tmp>/t/day=<value>/part-00000.parquet`` a value (None: the
    Hive default directory), as whole-file splits with their values, and
    the table's rows in that order with the directory's value last."""
    splits, want = [], []
    for i, (value, rows) in enumerate(zip(values, rows_of)):
        name = "__HIVE_DEFAULT_PARTITION__" if value is None else str(value)
        path = str(tmp_path / "t" / f"day={name}" / "part-00000.parquet")
        want += [r + (value,) for r in _write(path, rows, seed + i)]
        splits.append(FileSplit(path, 0, os.path.getsize(path), (value,)))
    return splits, want


def _rows(batches):
    out = []
    for b in batches:
        d = batch_to_pydict(b)
        out += list(zip(*(d[name] for name in b.schema.names)))
    return out


def _drive(scan, partition=0):
    return list(scan.execute(partition, TaskContext(partition, scan.num_partitions())))


# ------------------------------------------------- partition values

@pytest.mark.parametrize("depth", [0, 2], ids=["one_thread", "pipelined"])
def test_every_row_comes_once_with_its_directorys_value(tmp_path, monkeypatch, depth):
    monkeypatch.setattr("blaze_tpu.conf.PIPELINE_DEPTH.get", lambda: depth)
    splits, want = _layout(tmp_path, [2451545, 2451546, None, 2451548], [700, 650, 300, 720])
    scan = ParquetScanExec([splits[:3], splits[3:]], READ, batch_rows=1024,
                           partition_schema=BY_DAY)
    assert scan.schema.names == ["k", "price", "qty", "day"] and scan.num_partitions() == 2
    with dispatch.capture() as c:
        got = _rows(_drive(scan, 0)) + _rows(_drive(scan, 1))
    assert got == want
    assert any(r[0] is None for r in got) and {r[3] for r in got} == {2451545, 2451546, None, 2451548}
    assert (c["scan_partition_files"], c["scan_splits"], c["scan_rows"]) == (4, 4, len(want))
    assert c["scan_chunks_native"] == c["scan_chunks"] == 12 and c["scan_pages"] == 0


def test_a_string_partition_column_and_two_columns_a_directory(tmp_path):
    path = str(tmp_path / "t" / "region=emea" / "day=7" / "part-00000.parquet")
    rows = _write(path, 90, 3)
    by = Schema([Field("region", DataType.string(8)), Field("day", DataType.date32())])
    scan = ParquetScanExec([[FileSplit(path, 0, os.path.getsize(path), (b"emea", 7))]], READ,
                           partition_schema=by)
    assert _rows(_drive(scan)) == [r + ("emea", 7) for r in rows]


def test_an_entry_without_its_values_is_refused(tmp_path):
    splits, _ = _layout(tmp_path, [1], [10])
    with pytest.raises(ValueError, match="partition"):
        ParquetScanExec([[splits[0].path]], READ, partition_schema=BY_DAY)
    with pytest.raises(ValueError, match="partition"):
        ParquetScanExec([[splits[0]]], READ)  # values, and no column to put them in


def test_from_task_definition_bytes_the_values_arrive(tmp_path):
    splits, want = _layout(tmp_path, [5, None], [40, 30])
    scan = ParquetScanExec([splits], READ, batch_rows=64, partition_schema=BY_DAY)
    task = pb.TaskDefinition(task_id="t", stage_id=0, partition=0, plan=plan_to_proto(scan))
    assert _rows(run_task(task.SerializeToString())) == want


def test_the_orc_scan_refuses_a_partitioned_entry(tmp_path):
    from blaze_tpu.ops.orc_scan import OrcScanExec

    with pytest.raises(NotImplementedError):
        OrcScanExec([[FileSplit("/t/day=1/f.orc", 0, 10, (1,))]], READ)


# ------------------------------------------------------- the decoders

@pytest.mark.parametrize("column", ["price", "k"], ids=["int32_decimal", "nulls_4.5_percent"])
def test_arrows_reader_and_the_page_decoder_give_the_same_arrays(tmp_path, column):
    path = str(tmp_path / "f.parquet")
    rows = _write(path, 5_000, 11)
    meta = pq.read_metadata(path)
    (rg,) = meta.row_groups
    field = READ.field(column)
    assert rg.chunks["price"].phys == pq.T_INT32 and rg.chunks["k"].max_def == 1
    cap = bucket_capacity(rg.rows)
    (ours,) = pq.read_row_group(path, rg, [field], cap)
    arrow_file = pq.open_arrow_file(path, [field])
    try:
        import collections

        tally = collections.Counter()
        (theirs,) = pq.read_row_group(path, rg, [field], cap, arrow_file=arrow_file, tally=tally)
    finally:
        arrow_file.close(force=True)
    assert tally["chunks_native"] == 1  # Arrow took it: the comparison is not of one decoder with itself
    for a, b in zip(ours[:2], theirs[:2]):
        assert a.dtype == b.dtype and a.shape == b.shape == (cap,) and a.tobytes() == b.tobytes()
    i = READ.names.index(column)
    data, validity = theirs[:2]
    assert [int(v) if ok else None for v, ok in zip(data[:rg.rows], validity[:rg.rows])] == [r[i] for r in rows]
    if column == "k":
        assert 0.03 < 1 - validity[:rg.rows].mean() < 0.06



# ------------------------------------------------- what one file costs

def _without_statistics(row_groups):
    import dataclasses

    return [dataclasses.replace(rg, chunks={
        name: dataclasses.replace(c, min_value=None, max_value=None, null_count=None)
        for name, c in rg.chunks.items()}) for rg in row_groups]


@pytest.mark.parametrize("codec,spelt", [("snappy", True), ("zstd", True), ("gzip", True),
                                         ("none", True), ("lz4", False)])
@pytest.mark.parametrize("page_version", ["1.0", "2.0"])
def test_arrows_footer_gives_the_thrift_readers_row_groups(tmp_path, codec, spelt, page_version):
    """The footer Arrow parsed at the open, as this module's RowGroupMeta:
    read_metadata's but for the statistics, midpoints included — or
    None, where pyarrow's name of the codec is not one codec's."""
    path = str(tmp_path / "f.parquet")
    rng = np.random.RandomState(5)
    table = pa.table({"k": pa.array(rng.randint(0, 50, 3_000), mask=rng.rand(3_000) < 0.1),
                      "s": pa.array([f"v{i % 13}" for i in range(3_000)]),
                      "x": pa.array(rng.rand(3_000)), "r": pa.array(np.arange(3_000))},
                     schema=pa.schema([pa.field("k", pa.int64()), pa.field("s", pa.string()),
                                       pa.field("x", pa.float64()),
                                       pa.field("r", pa.int64(), nullable=False)]))
    papq.write_table(table, path, compression=codec, row_group_size=1_100,
                     data_page_version=page_version)
    arrow_file = pq.open_arrow_file(path, [])
    try:
        got = pq.arrow_row_groups(arrow_file)
    finally:
        arrow_file.close(force=True)
    want = pq.read_metadata(path).row_groups
    if not spelt:
        assert got is None
        return
    assert got == _without_statistics(want) and len(got) == 3
    assert [rg.midpoint for rg in got] == [rg.midpoint for rg in want]
    assert got[0].chunks["r"].max_def == 0 and got[0].chunks["k"].max_def == 1


def test_arrows_footer_of_a_file_this_module_wrote(tmp_path):
    from blaze_tpu.io.parquet import write_parquet

    path = str(tmp_path / "ours.parquet")
    schema = Schema([Field("i", DataType.int64()), Field("d", DataType.decimal(30, 2))])
    write_parquet(path, schema, {"i": (np.arange(100), None, None),
                                 "d": (np.arange(100) * 7, None, None)}, row_group_rows=40)
    arrow_file = pq.open_arrow_file(path, schema.fields)
    try:
        assert pq.arrow_row_groups(arrow_file) == _without_statistics(pq.read_metadata(path).row_groups)
    finally:
        arrow_file.close(force=True)


def _count_opens(monkeypatch):
    from blaze_tpu.io import fs

    opened = []
    real = fs.LocalFileSystem.open

    def counted(self, path):
        opened.append(path)
        return real(self, path)

    monkeypatch.setattr(fs.LocalFileSystem, "open", counted)
    return opened


def test_a_small_file_is_opened_once_and_its_footer_parsed_once(tmp_path, monkeypatch):
    """One open and one read a file no longer than WHOLE_FILE_BYTES,
    Arrow reading it from memory; the thrift reader parses no footer,
    whether the scan prunes by no statistic or by some: Arrow's footer
    carries those the predicate reads."""
    monkeypatch.setattr("blaze_tpu.conf.PIPELINE_DEPTH.get", lambda: 0)
    splits, want = _layout(tmp_path, [1, 2, 3], [500, 400, 300])
    assert all(os.path.getsize(s.path) <= pq.WHOLE_FILE_BYTES for s in splits)
    opened = _count_opens(monkeypatch)
    parsed = []
    read_metadata = pq.read_metadata
    monkeypatch.setattr(pq, "read_metadata", lambda path: parsed.append(path) or read_metadata(path))
    scan = ParquetScanExec([splits], READ, batch_rows=1024, partition_schema=BY_DAY)
    assert _rows(_drive(scan)) == want
    assert opened == [s.path for s in splits] and parsed == []
    del opened[:]
    pruning = ParquetScanExec([splits], READ, BinOp(">=", Col("qty"), Lit(0)), batch_rows=1024,
                              partition_schema=BY_DAY)
    assert _rows(_drive(pruning)) == want
    assert parsed == [] and opened == [s.path for s in splits]


def test_a_longer_file_is_read_through_the_file_system(tmp_path, monkeypatch):
    monkeypatch.setattr("blaze_tpu.conf.PIPELINE_DEPTH.get", lambda: 0)
    monkeypatch.setattr(pq, "WHOLE_FILE_BYTES", 1024)
    splits, want = _layout(tmp_path, [1], [500])
    assert os.path.getsize(splits[0].path) > 1024
    opened = _count_opens(monkeypatch)
    files = []
    open_arrow_file = pq.open_arrow_file
    monkeypatch.setattr(pq, "open_arrow_file",
                        lambda *a: files.append(open_arrow_file(*a)) or files[-1])
    scan = ParquetScanExec([splits], READ, batch_rows=1024, partition_schema=BY_DAY)
    assert _rows(_drive(scan)) == want
    assert opened == [splits[0].path] and files[0].closed  # the file under Arrow went with it


@pytest.mark.parametrize("rows,batch_rows,threads", [(500, 1024, False), (1024, 1024, False),
                                                     (3_000, 1024, True)])
def test_arrows_pool_is_woken_for_a_row_group_of_several_pieces_alone(
        tmp_path, monkeypatch, rows, batch_rows, threads):
    monkeypatch.setattr("blaze_tpu.conf.PIPELINE_DEPTH.get", lambda: 0)
    splits, want = _layout(tmp_path, [1], [rows])
    asked = []
    iter_batches = papq.ParquetFile.iter_batches

    def watched(self, *args, **kwargs):
        asked.append(kwargs["use_threads"])
        return iter_batches(self, *args, **kwargs)

    monkeypatch.setattr(papq.ParquetFile, "iter_batches", watched)
    scan = ParquetScanExec([splits], READ, batch_rows=batch_rows, partition_schema=BY_DAY)
    assert _rows(_drive(scan)) == want and asked == [threads]


# --------------------------------------------------------- the packing

@pytest.mark.parametrize("files,batch_rows", [(32, 65536), (32, 4096), (7, 1024), (1, 1024)])
def test_small_files_of_a_task_are_packed_into_full_batches_in_order(tmp_path, files, batch_rows):
    rng = np.random.RandomState(files)
    rows_of = rng.randint(300, 420, files).tolist()
    splits, want = _layout(tmp_path, list(range(100, 100 + files)), rows_of)
    scan = ParquetScanExec([splits], READ, batch_rows=batch_rows, partition_schema=BY_DAY)
    with dispatch.capture() as c:
        batches = _drive(scan)
    assert _rows(batches) == want  # order kept, nothing twice
    total = sum(rows_of)
    assert -(-total // batch_rows) <= len(batches) <= -(-total // batch_rows) + 1
    assert all(b.num_rows <= batch_rows and b.capacity == bucket_capacity(b.num_rows) for b in batches)
    # next fit by hand: a file joins the open batch while it fits
    fit = [[]]
    for n in rows_of:
        if fit[-1] and sum(fit[-1]) + n > batch_rows:
            fit.append([])
        fit[-1].append(n)
    assert [b.num_rows for b in batches] == [sum(files_in) for files_in in fit]
    assert (c["scan_rows"], c["scan_rows_budget"]) == (total, len(batches) * batch_rows)
    assert c["scan_stage_n"] == c["pipeline_items"] == len(batches) and c["scan_pieces"] == files
    packed = [files_in for files_in in fit if len(files_in) > 1]
    assert c.get("scan_coalesce_n", 0) == len(packed)
    assert c["scan_pieces_packed"] == sum(map(len, packed))


def test_a_piece_of_batch_rows_rows_passes_through_untouched(tmp_path, monkeypatch):
    """Two row groups of exactly ``batch_rows`` rows and a tail: three
    batches, the first two the decoder's arrays themselves, no
    ``scan_coalesce``."""
    path = str(tmp_path / "f.parquet")
    n, batch_rows = 2 * 1024 + 100, 1024
    papq.write_table(pa.table({"k": pa.array(np.arange(n))}), path, row_group_size=batch_rows)
    handed = []
    real = pq._from_arrow

    def spy(column, dtype, capacity):
        out = real(column, dtype, capacity)
        handed.append(out[0])
        return out

    monkeypatch.setattr(pq, "_from_arrow", spy)
    monkeypatch.setattr("blaze_tpu.conf.PIPELINE_DEPTH.get", lambda: 0)
    scan = ParquetScanExec([[path]], Schema([Field("k", DataType.int64())]), batch_rows=batch_rows)
    staged = []
    monkeypatch.setattr(scan, "_staged", lambda host: (staged.append(b) or b for b in host))
    with dispatch.capture() as c:
        assert [b.num_rows for b in _drive(scan)] == [1024, 1024, 100]
    assert [b.columns[0].data is a for b, a in zip(staged, handed)] == [True, True, True]
    assert "scan_coalesce_n" not in c and c["scan_pieces_packed"] == 0
    assert (c["scan_rows"], c["scan_rows_budget"]) == (n, 3 * batch_rows)


def test_a_column_one_file_lacks_is_null_in_its_rows_of_the_packed_batch(tmp_path):
    full = str(tmp_path / "a.parquet")
    lacks = str(tmp_path / "b.parquet")
    papq.write_table(pa.table({"k": pa.array([1, 2, 3]), "qty": pa.array([7, 8, 9], pa.int32())}), full)
    papq.write_table(pa.table({"k": pa.array([4, 5])}), lacks)
    schema = Schema([Field("k", DataType.int64()), Field("qty", DataType.int32())])
    (batch,) = _drive(ParquetScanExec([[full, lacks, full]], schema, batch_rows=1024))
    assert _rows([batch]) == [(1, 7), (2, 8), (3, 9), (4, None), (5, None), (1, 7), (2, 8), (3, 9)]


# ------------------------------------------------------------ the wire

#: ``plan_to_proto(...)`` of the two scans below as the parent commit
#: (PR 39) serialised them: a plan without partition values is the same bytes
PARENT_BYTES = {
    "ranged": "12770a1e0a0b0a016b12040805204018010a0f0a016412080808100718022040180112192f742f612e70617271"
              "7565743b2f742f622e70617271756574120c2f742f632e706172717565741a161a140a013e12030a016b1a0a12"
              "080a040804204020052080402a090a020004120301c8012a060a0100120101",
    "whole": "12570a1e0a0b0a016b12040805204018010a0f0a0164120808081007180220401801120c2f742f612e706172717565"
             "74120c2f742f632e706172717565741a161a140a013e12030a016b1a0a12080a04080420402005208040",
}
UNPARTITIONED = {
    "ranged": [["/t/a.parquet", FileSplit("/t/b.parquet", 4, 100)], ["/t/c.parquet"]],
    "whole": [["/t/a.parquet"], ["/t/c.parquet"]],
}


@pytest.mark.parametrize("case", sorted(PARENT_BYTES))
def test_a_plan_without_partition_values_serialises_byte_for_byte_as_before(case):
    schema = Schema([Field("k", DataType.int64()), Field("d", DataType.decimal(7, 2))])
    scan = ParquetScanExec(UNPARTITIONED[case], schema, BinOp(">", Col("k"), Lit(5)), 8192)
    wire = plan_to_proto(scan).SerializeToString()
    assert wire.hex() == PARENT_BYTES[case]
    back = plan_from_proto(pb.PhysicalPlanNode.FromString(wire))
    assert back.file_groups == UNPARTITIONED[case] and back.partition_schema.names == []
    assert back.schema.names == ["k", "d"]


def test_partition_schema_and_values_survive_the_round_trip():
    by = Schema([Field("day", DataType.int64()), Field("region", DataType.string(8)),
                 Field("rate", DataType.decimal(7, 2)), Field("open", DataType.bool_()),
                 Field("score", DataType.float64())])
    groups = [[FileSplit("/t/day=1/a.parquet", 0, 77, (1, b"emea", 1250, True, 0.5)),
               FileSplit("/t/day=2/b.parquet", 10, 90, (2, b"", -3, False, -1.0))],
              [FileSplit("/t/day=__HIVE_DEFAULT_PARTITION__/c.parquet", 0, 5, (None,) * 5)]]
    scan = ParquetScanExec(groups, READ, batch_rows=512, partition_schema=by)
    node = plan_to_proto(scan)
    back = plan_from_proto(pb.PhysicalPlanNode.FromString(node.SerializeToString()))
    assert back.file_groups == groups and all(type(e) is FileSplit for g in back.file_groups for e in g)
    assert [(f.name, f.dtype) for f in back.partition_schema.fields] == [(f.name, f.dtype) for f in by.fields]
    assert back.schema.names == READ.names + by.names and back.stated_batch_rows == 512
    assert [f.name for f in node.parquet_scan.schema.fields] == READ.names  # what the files hold
    null = node.parquet_scan.partition_values[1].files[0].values
    assert len(null) == 5 and all(v.is_null for v in null)
    assert plan_to_proto(back).SerializeToString() == node.SerializeToString()


def _decoded_scans(task_bytes):
    td = pb.TaskDefinition.FromString(task_bytes)
    found, todo = [], [plan_from_proto(td.plan)]
    while todo:
        node = todo.pop()
        todo.extend(node.children)
        if isinstance(node, ParquetScanExec):
            found.append(node)
    return found


def test_a_tasks_plan_carries_its_own_file_group_alone(tmp_path):
    """≙ NativeParquetScanBase: what a task decodes, fingerprints and
    estimates is its own files, the other groups empty in their places."""
    from blaze_tpu.ops import FilterExec, ProjectExec
    from blaze_tpu.serde.to_proto import task_definition

    splits, want = _layout(tmp_path, [1, 2, None, 4, 5], [30, 40, 20, 10, 25])
    groups = [splits[:2], splits[2:3], splits[3:]]
    scan = ParquetScanExec(groups, READ, batch_rows=64, partition_schema=BY_DAY)
    plan = ProjectExec(FilterExec(scan, BinOp(">", Col("qty"), Lit(0))), [Col("k"), Col("day")], ["k", "day"])
    rows, at = [], 0
    for p, group in enumerate(groups):
        task = task_definition(plan, f"t{p}", 0, p)
        (decoded,) = _decoded_scans(task)
        assert decoded.file_groups == [g if q == p else [] for q, g in enumerate(groups)]
        assert decoded.num_partitions() == 3 and decoded.partition_schema.names == ["day"]
        n = sum(int(papq.ParquetFile(e.path).metadata.num_rows) for e in group)
        assert _rows(run_task(task)) == [(r[0], r[3]) for r in want[at:at + n]]
        at += n
    # a plan serialised for no task keeps every group
    assert plan_from_proto(plan_to_proto(plan)).children[0].children[0].file_groups == groups


def test_a_scan_that_may_run_at_another_partition_keeps_every_group(tmp_path):
    from blaze_tpu.ops import ProjectExec, UnionExec
    from blaze_tpu.ops.joins import BroadcastJoinExec, JoinType
    from blaze_tpu.serde.to_proto import task_definition

    splits, _ = _layout(tmp_path, [1, 2, 3], [10, 10, 10])
    groups = [[s] for s in splits]
    scan = lambda: ParquetScanExec(groups, READ, partition_schema=BY_DAY)
    # UnionExec is not among the operators known to run a child at their own partition alone
    for decoded in _decoded_scans(task_definition(UnionExec([scan(), scan()]), "t", 0, 1)):
        assert decoded.file_groups == groups
    # a broadcast join reads its build side whole and its probe side at the task's partition
    join = BroadcastJoinExec(ProjectExec(scan(), [Col("k")], ["b"]), scan(), [Col("b")], [Col("k")],
                             JoinType.INNER, build_is_left=True)
    probe, build = sorted(_decoded_scans(task_definition(join, "t", 0, 1)),
                          key=lambda s: sum(map(len, s.file_groups)))
    assert probe.file_groups == [[], groups[1], []] and build.file_groups == groups


# ------------------------------------- the other readers of file_groups

def test_column_pruning_narrows_the_partition_columns_too(tmp_path):
    from blaze_tpu.ops import ProjectExec
    from blaze_tpu.ops.pruning import prune_columns

    splits, want = _layout(tmp_path, [3, 4], [50, 60])
    scan = ParquetScanExec([splits], READ, batch_rows=1024, partition_schema=BY_DAY)
    for names, indices in ((["qty", "day"], (2, 3)), (["k"], (0,)), (["day"], (3,))):
        pruned = prune_columns(ProjectExec(scan, [Col(n) for n in names], names))
        leaf = pruned
        while leaf.children:
            leaf = leaf.children[0]
        assert isinstance(leaf, ParquetScanExec) and leaf.schema.names == names
        assert leaf.partition_schema.names == [n for n in names if n == "day"]
        assert all(len(e.values) == len(leaf.partition_schema.fields) for e in leaf.file_groups[0])
        assert _rows(_drive(pruned)) == [tuple(r[i] for i in indices) for r in want]


def test_the_plan_cache_and_the_estimator_take_a_partitioned_entry(tmp_path):
    from blaze_tpu.runtime import querycache, stats

    splits, _ = _layout(tmp_path, [3, None], [50, 60])
    assert [stats._footer(s)[0] for s in splits] == [50, 60]
    prints = []
    for values in ((3, None), (3, 5), (3, None)):
        groups = [[s._replace(values=(v,)) for s, v in zip(splits, values)]]
        prints.append(querycache.plan_fingerprint(
            ParquetScanExec(groups, READ, partition_schema=BY_DAY)))
    # a file's values are part of what the plan reads: another value, another plan
    assert all(p is not None and p.exact for p in prints)
    assert prints[0].digest == prints[2].digest != prints[1].digest
    unpartitioned = querycache.plan_fingerprint(ParquetScanExec([[s.path for s in splits]], READ))
    assert unpartitioned.digest not in {p.digest for p in prints}


# ---------------------------------------------------- the conversion

def _catalyst_scan(partition_filters, output=("k", "qty", "day")):
    import spark_fixtures as F

    from blaze_tpu.spark.plan_json import _parse_tree

    attrs = {"k": F.attr("k", 1), "price": F.attr("price", 2, "decimal(7,2)"),
             "qty": F.attr("qty", 3, "integer"), "day": F.attr("day", 4)}
    node = F.scan("t", [attrs[n] for n in output])
    node["partitionFilters"] = [F.flatten(f(attrs)) for f in partition_filters]
    return _parse_tree(F.flatten(node))


def _is_not_null(name):
    import spark_fixtures as F

    return lambda attrs: F.un("IsNotNull", attrs[name])


def _dynamic_pruning(name):
    import spark_fixtures as F

    # the subquery's inside is Spark's: here it even names a data column of another table
    inside = F.T("org.apache.spark.sql.execution.InSubqueryExec",
                 [F.attr(name, 4)], plan=F.flatten(F.scan("dim", [F.attr("d_key", 9)])))
    return lambda attrs: F.T(F.X + "DynamicPruningExpression", [inside])


def _registered(tmp_path, partitioned=True):
    splits, want = _layout(tmp_path, [3, None, 4], [20, 10, 30])
    if partitioned:  # Spark's listing under isnotnull(day): the NULL directory is not handed over
        return ParquetScanExec([[splits[0], splits[2]]], READ, partition_schema=BY_DAY), \
            [r for r in want if r[3] is not None]
    return ParquetScanExec([[s.path for s in splits]], READ), want


def test_a_scan_with_partition_filters_converts_over_a_partitioned_relation(tmp_path):
    from blaze_tpu.spark.converters import ConversionContext, convert_exec

    scan, want = _registered(tmp_path)
    node = _catalyst_scan([_is_not_null("day"), _dynamic_pruning("day")])
    plan = convert_exec(node, ConversionContext({"t": scan}))
    assert plan.schema.names == ["#1", "#3", "#4"]  # data and partition columns resolve alike
    assert _rows(_drive(plan)) == [(r[0], r[2], r[3]) for r in want]


@pytest.mark.parametrize("filters,partitioned,names", [
    ([_is_not_null("day"), _is_not_null("k")], True, "'k'"),
    ([_is_not_null("day")], False, "no partition schema"),
    ([_dynamic_pruning("day")], False, "no partition schema"),
], ids=["filter_on_a_data_column", "relation_without_partition_schema", "pruning_alone_without_one"])
def test_a_partition_filter_the_listing_cannot_have_applied_still_falls_back(
        tmp_path, filters, partitioned, names):
    from blaze_tpu.spark.converters import ConversionContext, UnsupportedSparkExec, convert_exec

    scan, _ = _registered(tmp_path, partitioned)
    with pytest.raises(UnsupportedSparkExec, match=names):
        convert_exec(_catalyst_scan(filters), ConversionContext({"t": scan}))


def test_a_scan_without_partition_filters_converts_as_before(tmp_path):
    from blaze_tpu.spark.converters import ConversionContext, convert_exec

    scan, want = _registered(tmp_path, partitioned=False)
    plan = convert_exec(_catalyst_scan([], output=("k", "qty")), ConversionContext({"t": scan}))
    assert _rows(_drive(plan)) == [(r[0], r[2]) for r in want]
