"""Real-world Parquet decode: files written by pyarrow (the stand-in
for Spark/arrow writers) with dictionary encoding, snappy/zstd/gzip/lz4
codecs, data page v1+v2, required + optional columns, FLBA decimals and
multiple pages per chunk — read through ParquetScanExec with pruning.

≙ reference parquet_exec.rs:65-418 (arrow-rs readers handle all of
this natively; round-1 VERDICT item #7 flagged our subset).
"""

import datetime
import decimal

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from blaze_tpu.batch import batch_to_pydict, concat_batches
from blaze_tpu.exprs import col, lit
from blaze_tpu.io import parquet as pq
from blaze_tpu.ops import MemoryScanExec, ParquetScanExec
from blaze_tpu.runtime import dispatch
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema
from test_parquet import decoders_agree

N = 500


def _table():
    rng = np.random.RandomState(11)
    ints = rng.randint(-1000, 1000, N)
    return pa.table(
        {
            "i32": pa.array(
                [None if i % 7 == 0 else int(ints[i]) for i in range(N)], pa.int32()
            ),
            "i64": pa.array([int(x) * 10_000_000_000 for x in ints], pa.int64()),
            "f64": pa.array(
                [None if i % 11 == 0 else float(ints[i]) / 3 for i in range(N)],
                pa.float64(),
            ),
            "s": pa.array(
                [None if i % 5 == 0 else f"val_{ints[i] % 37}" for i in range(N)],
                pa.string(),
            ),
            "b": pa.array([bool(ints[i] % 2) for i in range(N)], pa.bool_()),
            "d": pa.array(
                [datetime.date(2020, 1, 1) + datetime.timedelta(days=int(x) % 365) for x in ints],
                pa.date32(),
            ),
            "dec": pa.array(
                [decimal.Decimal(int(x)) / 100 for x in ints], pa.decimal128(12, 2)
            ),
        }
    )


SCHEMA = Schema(
    [
        Field("i32", DataType.int32()),
        Field("i64", DataType.int64()),
        Field("f64", DataType.float64()),
        Field("s", DataType.string(16)),
        Field("b", DataType.bool_()),
        Field("d", DataType.date32()),
        Field("dec", DataType.decimal(12, 2)),
    ]
)


def _read_ours(path, predicate=None):
    scan = ParquetScanExec([[str(path)]], SCHEMA, predicate)
    out = []
    for b in scan.execute(0, TaskContext(0, 1)):
        out.append(b)
    return batch_to_pydict(concat_batches(out)) if out else {f.name: [] for f in SCHEMA.fields}, scan


def _expected(table):
    d = table.to_pydict()
    exp = dict(d)
    exp["d"] = [None if v is None else (v - datetime.date(1970, 1, 1)).days for v in d["d"]]
    exp["dec"] = [None if v is None else int(v.scaleb(2)) for v in d["dec"]]
    return exp


def _assert_equal(got, exp):
    for k, want in exp.items():
        g = got[k]
        if k == "f64":
            for a, b in zip(g, want):
                assert (a is None) == (b is None) and (a is None or abs(a - b) < 1e-9), k
        else:
            assert g == want, f"column {k}"


WRITERS = [
    ("snappy", True, "1.0"),
    ("snappy", False, "1.0"),
    ("zstd", True, "1.0"),
    ("gzip", True, "1.0"),
    ("none", True, "1.0"),
    ("snappy", True, "2.0"),
    ("zstd", False, "2.0"),
    ("lz4", True, "1.0"),
]


def _write(tmp_path, codec, dictionary, page_version):
    table = _table()
    path = tmp_path / f"t_{codec}_{dictionary}_{page_version}.parquet"
    papq.write_table(
        table, path,
        compression=codec if codec != "none" else "NONE",
        use_dictionary=dictionary,
        data_page_version=page_version,
        row_group_size=200,            # multiple row groups
        data_page_size=1024,           # many small pages per chunk
        write_statistics=True,
    )
    return table, path


@pytest.mark.parametrize("codec,dictionary,page_version", WRITERS)
def test_pyarrow_roundtrip(tmp_path, codec, dictionary, page_version):
    table, path = _write(tmp_path, codec, dictionary, page_version)
    got, _ = _read_ours(path)
    _assert_equal(got, _expected(table))


@pytest.mark.parametrize("codec,dictionary,page_version", WRITERS)
def test_arrow_reader_equals_the_page_decoder(tmp_path, codec, dictionary, page_version):
    """Six of the seven columns come through Arrow's reader, bit for bit
    what the page decoder gives; ``dec`` is FIXED_LEN_BYTE_ARRAY here
    (pyarrow's default for a decimal) and stays the page decoder's."""
    _, path = _write(tmp_path, codec, dictionary, page_version)
    tally = decoders_agree(str(path), SCHEMA)
    assert (tally["chunks"], tally["chunks_native"]) == (3 * 7, 3 * 6)
    assert tally["pages"] >= 3  # the three dec chunks' pages


def _left_to_the_page_decoder(case):
    """(table as pyarrow writes it, writer options, the schema asked for,
    the values expected) for a chunk Arrow's reader is not given or
    whose Arrow type is not the requested one."""
    micros = [1_600_000_000_000_000 + 1_000_003 * i for i in range(300)]
    if case == "int96_timestamp":
        return (pa.table({"c": pa.array(micros, pa.timestamp("us"))}),
                dict(use_deprecated_int96_timestamps=True), DataType.timestamp(), micros)
    if case == "flba_decimal":
        unscaled = [i * 12_345 - 900_000 for i in range(300)]
        return (pa.table({"c": pa.array([decimal.Decimal(v).scaleb(-2) for v in unscaled],
                                        pa.decimal128(12, 2))}), {}, DataType.decimal(12, 2), unscaled)
    if case == "int32_read_as_int64":  # schema adaption: the file's type widens to the requested one
        values = list(range(-150, 150))
        return pa.table({"c": pa.array(values, pa.int32())}), {}, DataType.int64(), values
    if case == "decimal_of_another_scale":  # the unscaled integer, whatever the file calls its scale
        unscaled = [i * 7 for i in range(300)]
        return (pa.table({"c": pa.array([decimal.Decimal(v).scaleb(-4) for v in unscaled],
                                        pa.decimal128(12, 4))}),
                dict(store_decimal_as_integer=True), DataType.decimal(12, 2), unscaled)
    raise KeyError(case)


@pytest.mark.parametrize("case", ["int96_timestamp", "flba_decimal", "int32_read_as_int64",
                                  "decimal_of_another_scale"])
def test_what_arrow_does_not_take_goes_through_the_page_decoder(tmp_path, case):
    table, writer, dtype, want = _left_to_the_page_decoder(case)
    table = table.append_column("k", pa.array(range(300), pa.int64()))  # a chunk Arrow does take, beside it
    path = str(tmp_path / f"{case}.parquet")
    papq.write_table(table, path, row_group_size=100, **writer)
    schema = Schema([Field("c", dtype), Field("k", DataType.int64())])
    tally = decoders_agree(path, schema)
    assert (tally["chunks"], tally["chunks_native"]) == (6, 3) and tally["pages"] >= 3
    with dispatch.capture() as c:
        got, _ = _read_ours_with_schema(path, schema)
    assert got == {"c": want, "k": list(range(300))}
    assert (c["scan_chunks"], c["scan_chunks_native"]) == (6, 3)
    assert c["scan_pages"] == tally["pages"] and c["scan_row_groups"] == 3


def test_required_columns(tmp_path):
    """REQUIRED (non-nullable) columns carry no def levels."""
    table = pa.table(
        {"r": pa.array(list(range(50)), pa.int64())},
        schema=pa.schema([pa.field("r", pa.int64(), nullable=False)]),
    )
    path = tmp_path / "req.parquet"
    papq.write_table(table, path, compression="snappy")
    schema = Schema([Field("r", DataType.int64())])
    scan = ParquetScanExec([[str(path)]], schema)
    out = list(scan.execute(0, TaskContext(0, 1)))
    d = batch_to_pydict(concat_batches(out))
    assert d["r"] == list(range(50))
    tally = decoders_agree(str(path), schema)  # no definition levels for either decoder
    assert tally["chunks_native"] == tally["chunks"] == 1
    assert pq.read_metadata(str(path)).row_groups[0].chunks["r"].max_def == 0


def test_row_group_pruning_on_real_file(tmp_path):
    table = pa.table({"x": pa.array(list(range(1000)), pa.int64())})
    path = tmp_path / "pruned.parquet"
    papq.write_table(table, path, row_group_size=100, compression="snappy")
    pred = col("x") >= lit(950)
    got, scan = _read_ours_with_schema(path, Schema([Field("x", DataType.int64())]), pred)
    # pruning is row-group granular; residual filtering is FilterExec's
    # job — the group containing 950 survives whole
    assert got["x"] == list(range(900, 1000))
    assert scan.metrics.get("pruned_row_groups") == 9


def _read_ours_with_schema(path, schema, predicate=None):
    scan = ParquetScanExec([[str(path)]], schema, predicate)
    out = []
    for b in scan.execute(0, TaskContext(0, 1)):
        out.append(b)
    return batch_to_pydict(concat_batches(out)) if out else {f.name: [] for f in schema.fields}, scan


def test_missing_column_schema_adaption(tmp_path):
    table = pa.table({"a": pa.array([1, 2, 3], pa.int64())})
    path = tmp_path / "missing.parquet"
    papq.write_table(table, path)
    schema = Schema([Field("a", DataType.int64()), Field("zzz", DataType.string(8))])
    got, _ = _read_ours_with_schema(path, schema)
    assert got["a"] == [1, 2, 3]
    assert got["zzz"] == [None, None, None]


def test_decimal_pruning_flba_stats(tmp_path):
    vals = [decimal.Decimal(i) / 100 for i in range(-500, 500)]
    table = pa.table({"dec": pa.array(vals, pa.decimal128(12, 2))})
    path = tmp_path / "dec.parquet"
    papq.write_table(table, path, row_group_size=250)
    schema = Schema([Field("dec", DataType.decimal(12, 2))])
    dec_lit = lit("4.0", DataType.decimal(12, 2))
    got, scan = _read_ours_with_schema(path, schema, col("dec") >= dec_lit)
    # last row group (unscaled 250..499) survives whole; first three pruned
    assert got["dec"] == list(range(250, 500))
    assert scan.metrics.get("pruned_row_groups") == 3


@pytest.mark.parametrize("batch_rows", [64, 200, 1000])
def test_int64_decimal_row_group_longer_than_a_batch(tmp_path, batch_rows):
    """Spark's default for a decimal of 18 digits or fewer
    (writeLegacyFormat=false): INT64 annotated DECIMAL, not FLBA.  A row
    group longer than ``batch_rows`` comes out in slices of that length,
    equal to the file's rows and in their order."""
    unscaled = np.random.RandomState(5).randint(-10**11, 10**11, 700)
    table = pa.table({
        "dec": pa.array([decimal.Decimal(int(x)).scaleb(-2) for x in unscaled], pa.decimal128(12, 2)),
        "d": pa.array(np.arange(700, dtype=np.int32) + 8000, pa.int32()).cast(pa.date32()),
    })
    path = tmp_path / "int64_decimal.parquet"
    papq.write_table(table, path, compression="snappy", use_dictionary=True, data_page_version="1.0",
                     row_group_size=500, store_decimal_as_integer=True)
    assert papq.ParquetFile(path).schema.column(0).physical_type == "INT64"
    schema = Schema([Field("dec", DataType.decimal(12, 2)), Field("d", DataType.date32())])
    scan = ParquetScanExec([[str(path)]], schema, batch_rows=batch_rows)
    batches = list(scan.execute(0, TaskContext(0, 1)))
    # two row groups of 500 and 200 rows, each cut on its own; where both are
    # shorter than a batch they are packed into one (PR 40)
    want = [min(batch_rows, g - s) for g in (500, 200) for s in range(0, g, batch_rows)]
    if batch_rows == 1000:
        want = [700]
    assert [b.num_rows for b in batches] == want
    got = {"dec": [], "d": []}
    for b in batches:
        for k, v in batch_to_pydict(b).items():
            got[k].extend(v)
    assert got["dec"] == [int(x) for x in unscaled]
    assert got["d"] == list(range(8000, 8700))
