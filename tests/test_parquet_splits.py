"""A Parquet scan as Spark plans it (PR 36): a file cut into byte
ranges, a row group read by the one task whose range holds its midpoint.

The plain reference of the mechanism is parquet-mr's rule computed here
from ``pyarrow.parquet.ParquetFile(...).metadata`` alone; the rows are
held to what pyarrow reads back.  Every scan below runs from
``TaskDefinition`` bytes, as a stage's tasks do."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from blaze_tpu.batch import batch_to_pydict
from blaze_tpu.exprs import col, lit
from blaze_tpu.io import parquet as pq
from blaze_tpu.ops import FileSplit, OrcScanExec, ParquetScanExec
from blaze_tpu.runtime import dispatch
from blaze_tpu.schema import DataType, Field, Schema
from blaze_tpu.serde import plan_pb2 as pb
from blaze_tpu.serde.from_proto import plan_from_proto, run_task
from blaze_tpu.serde.to_proto import plan_to_proto, schema_to_proto, task_definition

SCHEMA = Schema([
    Field("id", DataType.int64()),
    Field("flag", DataType.string(8)),
    Field("noise", DataType.int64()),
])
ROWS = 20_000
ROW_GROUP_ROWS = 3_000  # seven row groups, the last of 2,000 rows


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """(path, the file as pyarrow reads it back, its footer).  ``noise``
    is random in the file's first half and constant in its second, so
    the row groups differ in compressed size and the midpoints are not
    evenly spaced."""
    rng = np.random.RandomState(36)
    noise = rng.randint(0, 1 << 62, ROWS)
    noise[ROWS // 2:] = 7
    table = pa.table({
        "id": pa.array(np.arange(ROWS, dtype=np.int64)),
        "flag": pa.array([("A", "N", "R")[i % 3] for i in range(ROWS)]),
        "noise": pa.array(noise),
    })
    path = str(tmp_path_factory.mktemp("splits") / "part-00000.snappy.parquet")
    papq.write_table(table, path, compression="snappy", row_group_size=ROW_GROUP_ROWS,
                     data_page_size=8 << 10)
    meta = papq.ParquetFile(path).metadata
    assert meta.num_row_groups == 7
    return path, papq.read_table(path).to_pydict(), meta


def midpoints(meta):
    """parquet-mr's ``filterFileMetaDataByMidpoint``, from pyarrow's
    footer: the first chunk's first page (its dictionary page where it
    has one) plus half the row group's compressed bytes."""
    out = []
    for g in range(meta.num_row_groups):
        rg = meta.row_group(g)
        first = rg.column(0)
        start = first.data_page_offset
        if first.has_dictionary_page and first.dictionary_page_offset:
            start = min(start, first.dictionary_page_offset)
        size = sum(rg.column(c).total_compressed_size for c in range(rg.num_columns))
        out.append(start + size // 2)
    return out


def tile(size, pieces):
    """``size`` bytes in ``pieces`` contiguous ranges (the last takes the remainder)."""
    step = size // pieces
    return [(i * step, step if i < pieces - 1 else size - i * step) for i in range(pieces)]


def run_split_tasks(scan):
    """Each partition of ``scan`` as its own task from TaskDefinition
    bytes: (rows of each task as a pydict, the counters of all)."""
    out = []
    with dispatch.capture() as c:
        for p in range(scan.num_partitions()):
            got = {name: [] for name in scan.schema.names}
            for b in run_task(task_definition(scan, f"t{p}", 0, p)):
                for k, v in batch_to_pydict(b).items():
                    got[k].extend(v)
            out.append(got)
    return out, dict(c)


# ------------------------------------------------------ the midpoint rule

def test_the_footer_reader_gives_the_midpoint_pyarrows_footer_gives(written):
    path, _, meta = written
    row_groups = pq.read_metadata(path).row_groups
    assert [rg.midpoint for rg in row_groups] == midpoints(meta)
    assert [rg.total_comp for rg in row_groups] == [
        sum(meta.row_group(g).column(c).total_compressed_size for c in range(3)) for g in range(7)]
    # uneven on purpose: the random half's row groups are several times the constant half's
    assert row_groups[0].total_comp > 2 * row_groups[6].total_comp


@pytest.mark.parametrize("pieces", [2, 3, 4, 7, 40])
def test_splits_that_tile_a_file_read_every_row_group_once(written, pieces):
    path, table, meta = written
    ranges = tile(os.path.getsize(path), pieces)
    if pieces == 40:  # pieces smaller than a row group: most hold no midpoint
        assert max(length for _, length in ranges) < min(
            meta.row_group(g).total_byte_size for g in range(7))
    scan = ParquetScanExec([[FileSplit(path, s, n)] for s, n in ranges], SCHEMA, batch_rows=1024)
    per_task, c = run_split_tasks(scan)

    # the assignment is the reference's: row group g to the one range holding its midpoint
    want = [[g for g, m in enumerate(midpoints(meta)) if s <= m < s + n] for s, n in ranges]
    assert sorted(g for gs in want for g in gs) == list(range(7))
    bounds = np.cumsum([0] + [meta.row_group(g).num_rows for g in range(7)])
    for got, groups in zip(per_task, want):
        ids = [i for g in groups for i in range(bounds[g], bounds[g + 1])]
        assert got["id"] == ids
    # together the splits are the file, value for value, in order
    for name in SCHEMA.names:
        assert [v for got in per_task for v in got[name]] == table[name], name

    assert c["scan_splits"] == c["scan_open_n"] == pieces
    assert c["scan_row_groups"] == c["scan_decode_n"] == 7
    assert c["scan_row_groups_other_split"] == pieces * 7 - 7
    assert c["scan_open_ns"] > 0


def test_a_split_holding_no_midpoint_yields_no_batch(written):
    path, _, meta = written
    first = midpoints(meta)[0]
    scan = ParquetScanExec([[FileSplit(path, 0, first)], [FileSplit(path, first, 1)]], SCHEMA)
    (before, at), c = run_split_tasks(scan)
    # [start, start + length): the midpoint's own byte is in, the byte before it is not
    assert before["id"] == [] and at["id"] == list(range(ROW_GROUP_ROWS))
    assert (c["scan_splits"], c["scan_row_groups"], c["scan_row_groups_other_split"]) == (2, 1, 13)

    with dispatch.capture() as c:
        assert list(run_task(task_definition(scan, "t0", 0, 0))) == []
    assert (c["scan_splits"], c["scan_row_groups_other_split"]) == (1, 7)
    assert "scan_row_groups" not in c and "scan_decode_n" not in c


def test_a_whole_file_entry_reads_as_before_and_beside_a_split(written):
    path, table, meta = written
    (got,), c = run_split_tasks(ParquetScanExec([[path]], SCHEMA))
    assert got == table
    assert (c["scan_splits"], c["scan_row_groups"], c["scan_row_groups_other_split"]) == (1, 7, 0)

    half = midpoints(meta)[3]  # row groups 0..2 lie before it
    (got,), c = run_split_tasks(ParquetScanExec([[FileSplit(path, 0, half), path]], SCHEMA))
    assert got["id"] == list(range(3 * ROW_GROUP_ROWS)) + table["id"]
    assert (c["scan_splits"], c["scan_row_groups"], c["scan_row_groups_other_split"]) == (2, 10, 4)


def test_stats_pruning_applies_after_the_range(written):
    path, _, meta = written
    third = midpoints(meta)[2]  # the split's own: row groups 0 and 1
    scan = ParquetScanExec([[FileSplit(path, 0, third)]], SCHEMA,
                           predicate=col("id") >= lit(ROW_GROUP_ROWS))
    (got,), c = run_split_tasks(scan)
    assert got["id"] == list(range(ROW_GROUP_ROWS, 2 * ROW_GROUP_ROWS))
    assert (c["scan_row_groups"], c["scan_row_groups_pruned"], c["scan_row_groups_other_split"]) == (1, 1, 5)


# ---------------------------------------------------------------- the wire

GROUPS = {
    "paths_only": [["/t/a.parquet", "/t/b.parquet"], ["/t/c.parquet"], []],
    "ranges_only": [[FileSplit("/t/a.parquet", 0, 10_485_760)], [FileSplit("/t/a.parquet", 10_485_760, 5)]],
    "mixed": [[FileSplit("/t/a.parquet", 2**33, 2**40), "/t/b.parquet"], [], ["/t/c.parquet"]],
    "empty_range": [[FileSplit("/t/a.parquet", 0, 0)]],
}


@pytest.mark.parametrize("case", sorted(GROUPS))
def test_groups_survive_the_round_trip_through_bytes(case):
    scan = ParquetScanExec(GROUPS[case], SCHEMA, batch_rows=4096)
    node = pb.PhysicalPlanNode()
    node.ParseFromString(plan_to_proto(scan).SerializeToString())
    back = plan_from_proto(node)
    assert back.file_groups == GROUPS[case]
    assert [[type(e) for e in g] for g in back.file_groups] == [[type(e) for e in g] for g in GROUPS[case]]
    assert back.stated_batch_rows == 4096
    assert plan_to_proto(back).SerializeToString() == plan_to_proto(scan).SerializeToString()


def test_bytes_from_before_the_ranges_decode_to_whole_files():
    """A ``ParquetScanNode`` as the parent commit wrote it: fields 1 to
    4, ``';'``-joined paths.  A scan of whole files still writes exactly
    those bytes."""
    old = pb.PhysicalPlanNode()
    old.parquet_scan.schema.CopyFrom(schema_to_proto(SCHEMA))
    old.parquet_scan.file_groups.extend(["/t/a.parquet;/t/b.parquet", "/t/c.parquet", ""])
    old.parquet_scan.batch_rows = 4096
    back = plan_from_proto(old)
    assert back.file_groups == GROUPS["paths_only"]
    assert all(type(e) is str for g in back.file_groups for e in g)
    scan = ParquetScanExec(GROUPS["paths_only"], SCHEMA, batch_rows=4096)
    assert plan_to_proto(scan).SerializeToString() == old.SerializeToString()


# ------------------------------------- the other readers of file_groups

def _four_ranges(path):
    return [[FileSplit(path, s, n)] for s, n in tile(os.path.getsize(path), 4)]


def test_the_footer_estimate_counts_a_file_in_four_ranges_once(written):
    from blaze_tpu.runtime import stats

    path, _, meta = written
    est = {}
    stats._walk_est(ParquetScanExec(_four_ranges(path), SCHEMA), "0", est, {})
    rows, nbytes = est["0"]
    assert rows == ROWS
    assert nbytes == sum(meta.row_group(g).column(c).total_compressed_size
                         for g in range(7) for c in range(3))
    whole = {}
    stats._walk_est(ParquetScanExec([[path]], SCHEMA), "0", whole, {})
    assert whole["0"] == (ROWS, os.path.getsize(path))


def test_the_range_is_part_of_the_plan_fingerprint(written):
    from blaze_tpu.runtime.querycache import plan_fingerprint

    path, _, _ = written
    size = os.path.getsize(path)
    a = plan_fingerprint(ParquetScanExec([[FileSplit(path, 0, size // 2)]], SCHEMA))
    again = plan_fingerprint(ParquetScanExec([[FileSplit(path, 0, size // 2)]], SCHEMA))
    b = plan_fingerprint(ParquetScanExec([[FileSplit(path, size // 2, size - size // 2)]], SCHEMA))
    whole = plan_fingerprint(ParquetScanExec([[path]], SCHEMA))
    assert a.digest == again.digest and len({a.digest, b.digest, whole.digest}) == 3
    # the file's version is its path's, whatever the range
    assert a.sources == b.sources == whole.sources and a.sources[0][:2] == ("file", path)
    assert plan_fingerprint(ParquetScanExec([[FileSplit(path + ".gone", 0, 1)]], SCHEMA)) is None


def test_column_pruning_keeps_the_ranges(written):
    from blaze_tpu.ops import ProjectExec
    from blaze_tpu.ops.pruning import prune_columns

    path, table, _ = written
    groups = _four_ranges(path)
    plan = prune_columns(ProjectExec(ParquetScanExec(groups, SCHEMA, batch_rows=2048), [col("id")], ["id"]))
    scan = plan.children[0]
    assert isinstance(scan, ParquetScanExec) and scan.schema.names == ["id"]
    assert scan.file_groups == groups and scan.stated_batch_rows == 2048
    per_task, c = run_split_tasks(plan)
    assert [v for got in per_task for v in got["id"]] == table["id"]
    assert (c["scan_splits"], c["scan_row_groups"]) == (4, 7)


def test_an_orc_scan_refuses_a_range():
    with pytest.raises(NotImplementedError, match="whole files"):
        OrcScanExec([["/t/a.orc"], [FileSplit("/t/b.orc", 0, 100)]], SCHEMA)
    assert OrcScanExec([["/t/a.orc"]], SCHEMA).file_groups == [["/t/a.orc"]]
