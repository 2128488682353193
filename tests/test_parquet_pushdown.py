"""Row-group pruning by a pushed-down predicate, against a plain
reference.

Seeded files of six row groups of ten rows, written by pyarrow (as Spark
writes them: statistics in the footer), each row group cut so that a
predicate meets an edge there: a max equal to the literal, a chunk of
NULLs alone, a chunk without statistics, negative decimals, a literal
of another scale or typed as an integer, dates, strings with a common
prefix, IN lists.  Every predicate is read with the push-down and
without; what each returns, filtered by numpy, must be numpy's own
selection over all the rows, and the row groups read must be the ones
the statistics cannot rule out.  Then the footer (one parse, Arrow's),
and the converter: a catalyst scan's ``dataFilters`` become the
predicate of a COPY of a registered ``ParquetScanExec``, survive the
``TaskDefinition`` wire, and leave every other relation as it was."""

import datetime
import decimal
import json
import os
from fractions import Fraction

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from blaze_tpu import conf
from blaze_tpu.batch import batch_to_pydict, concat_batches
from blaze_tpu.exprs import col, lit
from blaze_tpu.exprs.ir import BinOp, Cast, InList, IsNotNull, Lit, Not
from blaze_tpu.io import parquet as pq
from blaze_tpu.ops import FilterExec, MemoryScanExec, ParquetScanExec, ProjectExec
from blaze_tpu.ops.parquet_scan import Conjunct, _lit_physical, _prune_conjuncts
from blaze_tpu.ops.pruning import expr_columns
from blaze_tpu.runtime import dispatch
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema

GROUPS, ROWS = 6, 10
DAY0 = datetime.date(1994, 1, 1)
EPOCH = datetime.date(1970, 1, 1)

SCHEMA = Schema([
    Field("i", DataType.int64()),        # the row's number: which rows came back
    Field("rg", DataType.int32()),       # its row group, one value a group
    Field("k", DataType.int64()),        # 10 g + 1 .. 10 g + 10; one NULL in group 2
    Field("n", DataType.int64()),        # NULL in every row of group 3
    Field("d", DataType.decimal(12, 2)),  # -3.00 + g .. -2.01 + g: negative to group 2
    Field("day", DataType.date32()),     # 1994-01-01 + 30 g, every 3rd day to + 27
    Field("s", DataType.string(16)),     # item-0000 .. item-0059
    Field("f", DataType.float64()),      # a float with a NaN: prunes nothing
    Field("u", DataType.int64()),        # written without statistics
])


def _columns():
    """The rows, as numpy: ``name -> (values, valid)``; decimals
    unscaled, dates in days, strings as str."""
    rng = np.random.RandomState(43)
    g = np.repeat(np.arange(GROUPS), ROWS)
    j = np.tile(np.arange(ROWS), GROUPS)
    ones = np.ones(GROUPS * ROWS, bool)
    k_valid = ones.copy()
    k_valid[2 * ROWS + 4] = False
    f = rng.rand(GROUPS * ROWS)
    f[7] = np.nan
    return {
        "i": (np.arange(GROUPS * ROWS), ones),
        "rg": (g, ones),
        "k": (10 * g + j + 1, k_valid),
        "n": (rng.randint(0, 100, GROUPS * ROWS), g != 3),
        "d": (-300 + 100 * g + 11 * j, ones),
        "day": ((DAY0 - EPOCH).days + 30 * g + 3 * j, ones),
        "s": (np.array([f"item-{10 * a + b:04d}" for a, b in zip(g, j)], object), ones),
        "f": (f, ones),
        "u": (rng.randint(0, 100, GROUPS * ROWS), ones),
    }


def _arrow(name, values, valid):
    mask = ~valid
    if name == "d":
        return pa.array([decimal.Decimal(int(v)).scaleb(-2) if ok else None
                         for v, ok in zip(values, valid)], pa.decimal128(12, 2))
    if name == "day":
        return pa.array(values.astype(np.int32), pa.int32(), mask=mask).cast(pa.date32())
    if name == "s":
        return pa.array(list(values), pa.string(), mask=mask)
    if name == "rg":
        return pa.array(values.astype(np.int32), pa.int32(), mask=mask)
    return pa.array(values, pa.float64() if name == "f" else pa.int64(), mask=mask)


@pytest.fixture(scope="module", params=["int64_decimals", "flba_decimals"])
def written(request, tmp_path_factory):
    """One file of the rows, decimals as Spark writes them (INT64) or as
    pyarrow's default does (FIXED_LEN_BYTE_ARRAY)."""
    cols = _columns()
    path = str(tmp_path_factory.mktemp("pushdown") / f"{request.param}.parquet")
    table = pa.table({name: _arrow(name, *cols[name]) for name in SCHEMA.names})
    papq.write_table(table, path, row_group_size=ROWS, compression="snappy",
                     write_statistics=[n for n in SCHEMA.names if n != "u"],
                     store_decimal_as_integer=request.param == "int64_decimals")
    return path, cols


@pytest.fixture(params=["arrow_footer", "thrift_footer"])
def footer(request, monkeypatch):
    """Arrow's footer, or the thrift reader's where Arrow does not take
    the file (the page decoder then decodes it too)."""
    if request.param == "thrift_footer":
        monkeypatch.setattr(pq, "_arrow_reader", lambda: None)
    return request.param


def _lit_date(days):
    return lit(EPOCH + datetime.timedelta(days=days))


D = lambda text, precision, scale: lit(text, DataType.decimal(precision, scale))  # noqa: E731
DAY_27 = (DAY0 - EPOCH).days + 27   # group 0's last day
MID = datetime.date(1994, 2, 1)     # group 1's second day (+31)

#: name -> (predicate, numpy's rows, the row groups the statistics cannot rule out)
CASES = {
    # a max equal to the literal: > rules group 0 out, >= does not
    "k_gt_max": (col("k") > lit(10), lambda c: c["k"][0] > 10, {1, 2, 3, 4, 5}),
    "k_ge_max": (col("k") >= lit(10), lambda c: c["k"][0] >= 10, {0, 1, 2, 3, 4, 5}),
    # a min equal to the literal: < rules group 1 out, <= does not
    "k_lt_min": (col("k") < lit(11), lambda c: c["k"][0] < 11, {0}),
    "k_le_min": (col("k") <= lit(11), lambda c: c["k"][0] <= 11, {0, 1}),
    "k_eq": (col("k") == lit(37), lambda c: c["k"][0] == 37, {3}),
    "k_lit_left": (lit(10) < col("k"), lambda c: 10 < c["k"][0], {1, 2, 3, 4, 5}),
    "k_eq_null_group": (col("k") == lit(25), lambda c: c["k"][0] == 25, {2}),
    # every row of group 3 is NULL
    "n_notnull": (IsNotNull(col("n")), lambda c: c["n"][1], {0, 1, 2, 4, 5}),
    "n_compared": (col("n") >= lit(0), lambda c: c["n"][0] >= 0, {0, 1, 2, 4, 5}),
    "k_notnull": (IsNotNull(col("k")), lambda c: c["k"][1], {0, 1, 2, 3, 4, 5}),
    # no statistics: nothing pruned, whatever the literal
    "u_no_statistics": (col("u") > lit(1000), lambda c: c["u"][0] > 1000, {0, 1, 2, 3, 4, 5}),
    # a float leaves its NaNs out of min and max: nothing pruned
    "f_float": (col("f") > lit(2.0), lambda c: c["f"][0] > 2.0, {0, 1, 2, 3, 4, 5}),
    # negative decimals; an integer literal is in units, not unscaled digits
    "d_lt_int": (col("d") < lit(-2), lambda c: c["d"][0] < -200, {0}),
    "d_le_int": (col("d") <= lit(-2), lambda c: c["d"][0] <= -200, {0, 1}),
    "d_ge_int": (col("d") >= lit(1), lambda c: c["d"][0] >= 100, {4, 5}),
    # a literal of another scale, compared exactly: rounded to the
    # column's scale, -1.995 would be -2.00 and group 1's -2.00 lost
    "d_lt_finer_scale": (col("d") < D("-1.995", 5, 3), lambda c: c["d"][0] < -199.5, {0, 1}),
    "d_gt_finer_scale": (col("d") > D("-1.005", 5, 3), lambda c: c["d"][0] > -100.5, {2, 3, 4, 5}),
    "d_eq_coarser_scale": (col("d") == D("0.5", 3, 1), lambda c: c["d"][0] == 50, {3}),
    "d_eq_unheld": (col("d") == D("0.555", 5, 3), lambda c: c["d"][0] * 10 == 555, {3}),
    "d_gt_decimal_int": (col("d") > D("2", 5, 0), lambda c: c["d"][0] > 200, {5}),
    # dates: as a date, as an ISO string typed date, as days typed date
    "day_gt_last": (col("day") > _lit_date(DAY_27), lambda c: c["day"][0] > DAY_27, {1, 2, 3, 4, 5}),
    "day_ge_last": (col("day") >= lit(str(EPOCH + datetime.timedelta(DAY_27)), DataType.date32()),
                    lambda c: c["day"][0] >= DAY_27, {0, 1, 2, 3, 4, 5}),
    "day_lt_days": (col("day") < lit((MID - EPOCH).days, DataType.date32()),
                    lambda c: c["day"][0] < (MID - EPOCH).days, {0, 1}),
    # strings of one prefix: bytes, ordered byte by byte
    "s_eq": (col("s") == lit("item-0015"), lambda c: c["s"][0] == "item-0015", {1}),
    "s_lt_prefix": (col("s") < lit("item-"), lambda c: c["s"][0] < "item-", set()),
    "s_ge_max": (col("s") >= lit("item-0059"), lambda c: c["s"][0] >= "item-0059", {5}),
    "s_gt_max": (col("s") > lit("item-0059"), lambda c: c["s"][0] > "item-0059", set()),
    "s_longer_than_width": (col("s") == lit("item-0015-and-more"), lambda c: c["s"][0] == "item-0015-and-more",
                            {0, 1, 2, 3, 4, 5}),
    # IN lists: a group is ruled out where every value lies outside it
    "k_in": (col("k").isin(5, 37, 1000), lambda c: np.isin(c["k"][0], [5, 37, 1000]), {0, 3}),
    "k_in_null": (InList(col("k"), [Lit(None), lit(15)]), lambda c: c["k"][0] == 15, {1}),
    "s_in": (col("s").isin("item-0003", "zzz"), lambda c: np.isin(c["s"][0], ["item-0003", "zzz"]), {0}),
    "d_in": (InList(col("d"), [D("-2.50", 5, 2), lit(9)]), lambda c: c["d"][0] == -250, {0}),
    "k_not_in": (InList(col("k"), [lit(5)], negated=True), lambda c: c["k"][0] != 5, {0, 1, 2, 3, 4, 5}),
    "rg_ne": (col("rg") != lit(2), lambda c: c["rg"][0] != 2, {0, 1, 3, 4, 5}),
    # AND prunes by each side; OR, NOT and a cast of the column by nothing
    "and": ((col("k") >= lit(21)) & (col("s") <= lit("item-0045")),
            lambda c: (c["k"][0] >= 21) & (c["s"][0] <= "item-0045"), {2, 3, 4}),
    "or": ((col("k") < lit(5)) | (col("k") > lit(55)),
           lambda c: (c["k"][0] < 5) | (c["k"][0] > 55), {0, 1, 2, 3, 4, 5}),
    "not": (Not(col("k") < lit(5)), lambda c: ~(c["k"][0] < 5), {0, 1, 2, 3, 4, 5}),
    "cast_column": (Cast(col("k"), DataType.float64()) > lit(100.0), lambda c: c["k"][0] > 100,
                    {0, 1, 2, 3, 4, 5}),
}


def _read(path, predicate):
    """The scan's rows (ids) and its counters."""
    scan = ParquetScanExec([[path]], SCHEMA, predicate, batch_rows=16)
    with dispatch.capture() as c:
        batches = list(scan.execute(0, TaskContext(0, 1)))
    got = batch_to_pydict(concat_batches(batches)) if batches else {"i": [], "rg": []}
    return got, dict(c)


@pytest.mark.parametrize("case", sorted(CASES))
def test_pruning_keeps_every_row_that_passes(written, footer, case):
    path, cols = written
    predicate, numpy_rows, kept = CASES[case]
    valid_all = np.ones(GROUPS * ROWS, bool)
    for name in expr_columns(predicate):  # NULL passes no predicate here
        valid_all &= cols[name][1]
    with np.errstate(invalid="ignore"):
        want = np.flatnonzero(valid_all & numpy_rows(cols))
    for pushed in (predicate, None):
        got, counters = _read(path, pushed)
        ids = np.asarray(got["i"], np.int64)
        assert set(want) <= set(ids), (case, pushed is not None)  # not one passing row lost
        assert np.array_equal(np.intersect1d(ids, want), want)
        groups = set(got["rg"])
        assert groups == (kept if pushed is not None else set(range(GROUPS))), case
        assert counters.get("scan_row_groups_pruned", 0) == GROUPS - len(groups)
        assert counters["scan_rows_chosen"] == GROUPS * ROWS
        assert counters["scan_rows_pruned"] == (GROUPS - len(groups)) * ROWS
        assert ("scan_prune_n" in counters) == (pushed is not None and bool(
            _prune_conjuncts(pushed, SCHEMA)))


def test_pushdown_off_reads_every_row_group(written, monkeypatch):
    path, _ = written
    monkeypatch.setattr(conf.PARQUET_FILTER_PUSHDOWN, "get", lambda: False)
    got, counters = _read(path, col("k") < lit(11))
    assert set(got["rg"]) == set(range(GROUPS)) and "scan_prune_n" not in counters


# ------------------------------------------------ literals in column units

@pytest.mark.parametrize("literal, dtype, want", [
    (lit(24), DataType.decimal(12, 2), 2400),                       # integer: units, not digits
    (lit("23.995", DataType.decimal(5, 3)), DataType.decimal(12, 2), Fraction(4799, 2)),
    (lit("0.06", DataType.decimal(3, 2)), DataType.decimal(12, 2), 6),
    (lit("0.5", DataType.decimal(3, 1)), DataType.int64(), Fraction(1, 2)),
    (lit(0.05, DataType.decimal(3, 2)), DataType.decimal(12, 2), 5),  # the engine's own rounding
    (lit(0.05), DataType.decimal(12, 2), None),                     # a float rounds: no
    (lit(7), DataType.float64(), None),                             # a float column: no
    (lit(True), DataType.int64(), None),
    (lit(datetime.date(1994, 1, 1)), DataType.date32(), 8766),
    (lit("1995-01-01", DataType.date32()), DataType.date32(), 9131),
    (lit(8766, DataType.date32()), DataType.date32(), 8766),
    (lit(8766), DataType.date32(), None),                           # an integer is no date
    (lit(datetime.datetime(1994, 1, 1)), DataType.date32(), None),
    (lit("é"), DataType.string(8), "é".encode()),
    (lit("x" * 8), DataType.string(8), None),                      # the column cuts at its width
    (lit("abc"), DataType.int64(), None),
])
def test_a_literal_in_its_columns_units(literal, dtype, want):
    got = _lit_physical(literal, dtype)
    assert got == want and type(got) is type(want)


def test_conjuncts_walk_and_alone_over_the_files_columns():
    schema = Schema([Field("a", DataType.int64()), Field("b", DataType.string(8))])
    predicate = ((col("a") > lit(1)) & IsNotNull(col("b"))) & (
        (col("zz") == lit(3)) & BinOp("and", col("b").isin("x", "y"), (col("a") < lit(0)) | (col("a") > lit(9))))
    assert _prune_conjuncts(predicate, schema) == [
        Conjunct("a", ">", 1), Conjunct("b", "notnull", None), Conjunct("b", "in", (b"x", b"y"))]
    assert _prune_conjuncts(None, schema) == []


# ----------------------------------------------------------------- footer

def test_a_scan_that_prunes_parses_each_footer_once(written, monkeypatch):
    """Arrow's footer carries the statistics the predicate reads: the
    thrift reader is never called for a file Arrow opens."""
    path, _ = written
    calls = []
    thrift = pq.read_metadata
    monkeypatch.setattr(pq, "read_metadata", lambda p: calls.append(p) or thrift(p))
    got, counters = _read(path, (col("k") >= lit(21)) & (col("d") < lit(1)))
    assert set(got["rg"]) == {2, 3} and counters["scan_row_groups_pruned"] == 4
    assert calls == [] and counters["scan_open_n"] == 1
    # where Arrow does not take the file, the thrift reader does, once
    monkeypatch.setattr(pq, "_arrow_reader", lambda: None)
    got, _ = _read(path, (col("k") >= lit(21)) & (col("d") < lit(1)))
    assert set(got["rg"]) == {2, 3} and calls == [path]


def test_arrows_statistics_are_the_thrift_readers(written):
    path, _ = written
    f = pq.open_arrow_file(path, SCHEMA.fields)
    try:
        ours = pq.arrow_row_groups(f, SCHEMA.names)
        bare = pq.arrow_row_groups(f)
    finally:
        f.close(force=True)
    thrift = pq.read_metadata(path).row_groups
    assert len(ours) == len(thrift) == GROUPS
    for a, t, b in zip(ours, thrift, bare):
        for name in SCHEMA.names:
            ca, ct, cb = a.chunks[name], t.chunks[name], b.chunks[name]
            if name == "f":  # a float's min and max are not carried: they prune nothing
                assert (ca.min_value, ca.null_count) == (None, ct.null_count)
                continue
            assert (ca.min_value, ca.max_value, ca.null_count) == (ct.min_value, ct.max_value, ct.null_count)
            assert pq.chunk_bounds(ca) == pq.chunk_bounds(ct)
            assert (cb.min_value, cb.null_count) == (None, None)  # not asked for: not read
    assert pq.chunk_bounds(ours[0].chunks["u"]) is None
    assert ours[3].chunks["n"].null_count == ROWS and pq.chunk_bounds(ours[3].chunks["n"]) is None


def test_the_programs_own_writer_states_its_sort_order(tmp_path):
    """Without ``column_orders`` a reader leaves ``min_value`` and
    ``max_value`` unread: Arrow would prune nothing in such a file."""
    path = str(tmp_path / "own.parquet")
    schema = Schema([Field("x", DataType.int64()), Field("s", DataType.string(8))])
    pq.write_parquet(path, schema, {"x": (np.arange(20), None, None),
                                    "s": (np.frombuffer(b"ab" * 20, np.uint8).reshape(20, 2).repeat(4, 1),
                                          None, np.full(20, 8, np.int32))}, row_group_rows=10)
    meta = papq.ParquetFile(path).metadata
    assert meta.row_group(1).column(0).statistics.min_raw == 10
    assert meta.row_group(0).column(1).statistics.has_min_max
    scan = ParquetScanExec([[path]], schema, col("x") >= lit(10))
    assert sum(b.num_rows for b in scan.execute(0, TaskContext(0, 1))) == 10


# -------------------------------------------------------------- converter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
Q6 = os.path.join(ROOT, "bench", "suites", "tpch", "q6.plan.json")
Q6_COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]


def _q6(scan):
    from blaze_tpu.spark.session import BlazeSparkSession

    session = BlazeSparkSession(default_parallelism=2)
    session.register_table("lineitem", scan)
    with open(Q6) as f:
        text = f.read()
    with dispatch.capture() as c:
        plan = session.plan(text)
    return plan, dict(c)


def _nodes(node):
    yield node
    for child in node.children:
        yield from _nodes(child)


def _q6_schema():
    from blaze_tpu.tpch import TPCH_SCHEMAS

    return Schema([f for f in TPCH_SCHEMAS["lineitem"].fields if f.name in Q6_COLUMNS])


def test_q6s_data_filters_become_a_copy_of_the_scans_predicate(tmp_path):
    from blaze_tpu.serde.from_proto import plan_from_proto
    from blaze_tpu.serde.to_proto import plan_to_proto

    registered = ParquetScanExec([[str(tmp_path / "a.parquet")]], _q6_schema(), batch_rows=4096)
    plan, counters = _q6(registered)
    nodes = list(_nodes(plan))
    (scan,) = [n for n in nodes if isinstance(n, ParquetScanExec)]
    assert scan is not registered and registered.predicate is None  # the catalog's is left alone
    assert (scan.file_groups, scan.stated_batch_rows) == (registered.file_groups, 4096)
    assert counters["scan_conjuncts_pushed"] == 5 and "scan_conjuncts_dropped" not in counters
    days = lambda y: (datetime.date(y, 1, 1) - EPOCH).days  # noqa: E731
    want = [Conjunct("l_shipdate", "notnull", None), Conjunct("l_discount", "notnull", None),
            Conjunct("l_quantity", "notnull", None), Conjunct("l_shipdate", ">=", days(1994)),
            Conjunct("l_shipdate", "<", days(1995))]
    assert scan._conjuncts == want
    # Spark's FilterExec stays above the scan, whole
    filters = [n for n in nodes if isinstance(n, FilterExec)]
    assert len(filters) == 1 and any(isinstance(n, ProjectExec) and n.children[0] is scan for n in nodes)
    # the predicate travels with the task
    back = plan_from_proto(plan_to_proto(scan))
    assert back.predicate is not None and back._conjuncts == want


def test_the_same_dump_over_memory_converts_as_before():
    registered = MemoryScanExec([[]], _q6_schema())
    plan, counters = _q6(registered)
    scans = [n for n in _nodes(plan) if not n.children]
    assert scans == [registered]
    assert not any(k.startswith("scan_conjuncts") for k in counters)


def test_a_filter_that_does_not_lower_is_dropped_and_the_rest_pushed(tmp_path):
    from blaze_tpu.spark import converters
    from blaze_tpu.spark.plan_json import parse_plan_json

    with open(Q6) as f:
        dump = json.load(f)
    (i,) = [n for n, d in enumerate(dump) if d["class"].endswith("FileSourceScanExec")]
    scan_node = dict(dump[i])
    # an expression the converter has no lowering for, and one over an attribute not of the scan
    scan_node["dataFilters"] = scan_node["dataFilters"][:1] + [
        [{"class": "org.apache.spark.sql.catalyst.expressions.NoSuchExpression", "num-children": 0}],
        [{"class": "org.apache.spark.sql.catalyst.expressions.IsNotNull", "num-children": 1, "child": 0},
         {"class": "org.apache.spark.sql.catalyst.expressions.AttributeReference", "num-children": 0,
          "name": "x", "dataType": "long", "exprId": {"id": 999999}}]]
    node = parse_plan_json([scan_node])
    registered = ParquetScanExec([[str(tmp_path / "a.parquet")]], _q6_schema())
    ctx = converters.ConversionContext({"lineitem": registered})
    with dispatch.capture() as c:
        out = converters.convert_exec(node, ctx)
    assert (c["scan_conjuncts_pushed"], c["scan_conjuncts_dropped"]) == (1, 2)
    assert out.children[0]._conjuncts == [Conjunct("l_shipdate", "notnull", None)]
