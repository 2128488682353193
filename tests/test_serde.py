"""Plan serde roundtrips — ≙ reference blaze-serde scalar/plan decode
tests + the TaskDefinition entry path."""

import numpy as np
import pytest

from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
from blaze_tpu.exprs import col, lit
from blaze_tpu.exprs.ir import Case, Like, func
from blaze_tpu.ops import (
    AggExec, AggFunction, AggMode, FilterExec, GroupingExpr, LimitExec,
    MemoryScanExec, ProjectExec, SortExec, SortField,
)
from blaze_tpu.ops.joins import HashJoinExec, JoinType
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema
from blaze_tpu.serde import plan_from_proto, plan_to_proto, run_task, task_definition
from blaze_tpu.serde import plan_pb2


SCHEMA = Schema([
    Field("k", DataType.int64()),
    Field("s", DataType.string(16)),
    Field("d", DataType.decimal(12, 2)),
])


def _mem(data, schema):
    return MemoryScanExec([[batch_from_pydict(data, schema)]], schema)


def _collect(plan):
    out = {f.name: [] for f in plan.schema.fields}
    for p in range(plan.num_partitions()):
        for b in plan.execute(p, TaskContext(p, plan.num_partitions())):
            d = batch_to_pydict(b)
            for k in out:
                out[k].extend(d[k])
    return out


def test_expr_plan_roundtrip_filter_project():
    src = _mem({"k": [1, 2, 3, None], "s": ["aa", "bb", "ab", None], "d": [1.5, 2.0, -3.25, 0.5]}, SCHEMA)
    plan = ProjectExec(
        FilterExec(src, (col("k") > lit(1)) & Like(col("s"), "a%") | col("k").is_null()),
        [col("k"), (col("d") * lit("2", DataType.decimal(3, 0))).alias("dd"),
         Case([(col("k") == lit(3), lit("three"))], lit("other")).alias("c")],
    )
    data = plan_to_proto(plan).SerializeToString()
    decoded = plan_from_proto(_parse(data))
    got = _collect(decoded)
    assert got["k"] == [3, None]
    assert got["dd"] == [-650, 100]
    assert got["c"] == ["three", "other"]


def _parse(data):
    n = plan_pb2.PhysicalPlanNode()
    n.ParseFromString(data)
    return n


def test_agg_sort_limit_roundtrip():
    src = _mem({"k": [1, 1, 2, 2, 2], "s": ["a"] * 5, "d": [1.0, 2.0, 3.0, 4.0, 5.0]}, SCHEMA)
    agg = AggExec(
        src, AggMode.PARTIAL,
        [GroupingExpr(col("k"), "k")],
        [AggFunction("sum", col("d"), "sd"), AggFunction("count_star", None, "n")],
    )
    final = AggExec(
        MemoryScanExec([agg.collect()], agg.schema), AggMode.FINAL,
        [GroupingExpr(col("k"), "k")], agg.aggs,
    )
    plan = LimitExec(SortExec(final, [SortField(col("sd"), ascending=False)]), 1)
    decoded = plan_from_proto(_parse(plan_to_proto(plan).SerializeToString()))
    got = _collect(decoded)
    assert got["k"] == [2] and got["sd"] == [1200] and got["n"] == [3]


def test_join_roundtrip():
    l = _mem({"k": [1, 2, 3], "s": ["a", "b", "c"], "d": [1.0, 2.0, 3.0]}, SCHEMA)
    r_schema = Schema([Field("k2", DataType.int64()), Field("v", DataType.int64())])
    r = MemoryScanExec([[batch_from_pydict({"k2": [2, 3, 4], "v": [20, 30, 40]}, r_schema)]], r_schema)
    plan = HashJoinExec(r, l, [col("k2")], [col("k")], JoinType.INNER, build_is_left=False)
    decoded = plan_from_proto(_parse(plan_to_proto(plan).SerializeToString()))
    got = _collect(decoded)
    assert sorted(got["k"]) == [2, 3]
    assert sorted(got["v"]) == [20, 30]


def test_task_definition_entry():
    src = _mem({"k": [5, 6], "s": ["x", "y"], "d": [1.0, 2.0]}, SCHEMA)
    plan = ProjectExec(src, [(col("k") + lit(1)).alias("k1")])
    td = task_definition(plan, task_id="t-0", stage_id=1, partition=0)
    batches = list(run_task(td))
    assert batch_to_pydict(batches[0])["k1"] == [6, 7]


def _identity_generator(row):
    return [row]


def test_pickled_generator_gate():
    """spark.blaze.udf.allowPickled=false rejects pickled payloads at
    decode (the gateway's trust-boundary hardening)."""
    import pytest

    from blaze_tpu import conf
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.ops.generate import GenerateExec
    from blaze_tpu.schema import DataType, Field, Schema
    from blaze_tpu.serde.from_proto import plan_from_proto
    from blaze_tpu.serde.to_proto import plan_to_proto

    schema = Schema([Field("j", DataType.string(32))])
    b = batch_from_pydict({"j": ['{"a":1}']}, schema)
    g = GenerateExec(
        MemoryScanExec([[b]], schema), _identity_generator,
        [__import__("blaze_tpu.exprs", fromlist=["col"]).col("j")],
        [Field("a", DataType.string(16))],
    )
    proto = plan_to_proto(g)
    old = conf.ALLOW_PICKLED_UDFS.get()
    try:
        conf.ALLOW_PICKLED_UDFS.set(False)
        with pytest.raises(PermissionError, match="allowPickled"):
            plan_from_proto(proto)
        conf.ALLOW_PICKLED_UDFS.set(True)
        assert plan_from_proto(proto) is not None
    finally:
        conf.ALLOW_PICKLED_UDFS.set(old)


def _scan_file(tmp_path, kind, rows):
    """One file of ``rows`` int64 keys in the scan's own format."""
    schema = Schema([Field("k", DataType.int64())])
    columns = {"k": (np.arange(rows, dtype=np.int64), None, None)}
    path = str(tmp_path / f"t.{kind}")
    if kind == "parquet":
        from blaze_tpu.io.parquet import write_parquet
        from blaze_tpu.ops import ParquetScanExec as scan_type

        write_parquet(path, schema, columns)
    else:
        from blaze_tpu.io.orc import write_orc
        from blaze_tpu.ops import OrcScanExec as scan_type

        write_orc(path, schema, columns)
    return scan_type, path, schema


@pytest.mark.parametrize("kind", ["parquet", "orc"])
def test_file_scan_batch_rows_travels_with_the_plan(tmp_path, monkeypatch, kind):
    """A file scan decoded from TaskDefinition bytes scans in the batch
    length its plan states; one that states none reads the executor's
    spark.blaze.batchSize, as bytes written before the field decode."""
    from blaze_tpu.ops.pruning import prune_columns

    scan_type, path, schema = _scan_file(tmp_path, kind, rows=1000)
    monkeypatch.setenv("BLAZE_BATCHSIZE", "128")  # the executor's conf, not the plan's
    stated = plan_from_proto(_parse(plan_to_proto(scan_type([[path]], schema, batch_rows=300)).SerializeToString()))
    assert type(stated) is scan_type
    assert (stated.batch_rows, stated.stated_batch_rows) == (300, 300)

    unstated = plan_to_proto(scan_type([[path]], schema))
    sub = unstated.parquet_scan if kind == "parquet" else unstated.orc_scan
    before = type(sub)(schema=sub.schema, file_groups=sub.file_groups)  # what the parent wrote
    assert sub.batch_rows == 0 and sub.SerializeToString() == before.SerializeToString()
    decoded = plan_from_proto(_parse(unstated.SerializeToString()))
    assert (decoded.batch_rows, decoded.stated_batch_rows) == (128, 0)
    assert [b.num_rows for b in decoded.execute(0, TaskContext(0, 1))] == [128] * 7 + [104]

    # column pruning rebuilds a scan: what the plan stated survives, and nothing it did not
    wide = Schema([Field("k", DataType.int64()), Field("absent", DataType.int64())])
    for told, want in ((300, 300), (0, 0)):
        project = ProjectExec(scan_type([[path]], wide, batch_rows=told), [col("k")])
        narrowed = prune_columns(project).children[0]
        assert type(narrowed) is scan_type and narrowed.schema.names == ["k"]
        assert narrowed.stated_batch_rows == want

    td = task_definition(scan_type([[path]], schema, batch_rows=300), task_id="t-0", stage_id=0, partition=0)
    batches = list(run_task(td))
    assert [b.num_rows for b in batches] == [300, 300, 300, 100]
    assert [v for b in batches for v in batch_to_pydict(b)["k"]] == list(range(1000))
