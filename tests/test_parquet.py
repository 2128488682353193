"""Parquet subset: write/read roundtrips, scan exec with row-group
pruning, sink with dynamic partitioning.

≙ the reference's parquet path (parquet_exec.rs scan + page filtering,
parquet_sink_exec.rs incl. hive dynamic partitions)."""

import collections
import datetime
import glob
import os

import numpy as np
import pytest

from blaze_tpu.batch import batch_from_pydict, batch_to_pydict, bucket_capacity, concat_batches
from blaze_tpu.exprs import col, lit
from blaze_tpu.io import parquet as pq
from blaze_tpu.ops import FileSplit, MemoryScanExec, ParquetScanExec, ParquetSinkExec
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema

SCHEMA = Schema([
    Field("i", DataType.int64()),
    Field("s", DataType.string(16)),
    Field("d", DataType.decimal(12, 2)),
    Field("day", DataType.date32()),
    Field("f", DataType.float64()),
    Field("b", DataType.bool_()),
])


def _cols(n, base=0):
    rng = np.random.RandomState(42 + base)
    data = np.arange(base, base + n, dtype=np.int64)
    validity = (data % 7 != 3)
    svals = np.zeros((n, 16), np.uint8)
    slens = np.zeros(n, np.int32)
    for i in range(n):
        b = f"row-{base + i}".encode()
        svals[i, : len(b)] = np.frombuffer(b, np.uint8)
        slens[i] = len(b)
    return {
        "i": (data, validity, None),
        "s": (svals, np.ones(n, bool), slens),
        "d": (data * 100 + 25, None, None),
        "day": ((data % 3000).astype(np.int32), None, None),
        "f": (rng.uniform(-1, 1, n), None, None),
        "b": ((data % 2 == 0), None, None),
    }


def test_roundtrip(tmp_path):
    path = str(tmp_path / "t.parquet")
    pq.write_parquet(path, SCHEMA, _cols(100), row_group_rows=40)
    meta = pq.read_metadata(path)
    assert meta.num_rows == 100
    assert len(meta.row_groups) == 3
    total = 0
    for rg in meta.row_groups:
        ch = rg.chunks["i"]
        data, validity, _ = pq.read_column_chunk(path, ch, DataType.int64())
        expected = np.arange(total, total + rg.rows)
        vmask = expected % 7 != 3
        assert (validity == vmask).all()
        assert (data[validity] == expected[vmask]).all()
        sdata, svalid, slen = pq.read_column_chunk(path, rg.chunks["s"], DataType.string(16))
        assert bytes(sdata[0][: slen[0]]) == f"row-{total}".encode()
        total += rg.rows
    assert total == 100


def test_scan_exec_and_pruning(tmp_path):
    p1 = str(tmp_path / "a.parquet")
    p2 = str(tmp_path / "b.parquet")
    pq.write_parquet(p1, SCHEMA, _cols(50, base=0), row_group_rows=25)
    pq.write_parquet(p2, SCHEMA, _cols(50, base=1000), row_group_rows=25)
    pred = col("i") >= lit(1000)
    scan = ParquetScanExec([[p1], [p2]], SCHEMA, predicate=pred)
    rows = 0
    for p in range(scan.num_partitions()):
        for b in scan.execute(p, TaskContext(p, 2)):
            rows += b.num_rows
    # both row groups of file a pruned by stats
    assert scan.metrics.get("pruned_row_groups") == 2
    assert rows == 50  # only file b's rows survive (a fully pruned)


def test_scan_missing_column_nulls(tmp_path):
    path = str(tmp_path / "t.parquet")
    pq.write_parquet(path, SCHEMA, _cols(10))
    wider = Schema(list(SCHEMA.fields) + [Field("extra", DataType.int32())])
    scan = ParquetScanExec([[path]], wider)
    batches = list(scan.execute(0, TaskContext(0, 1)))
    d = batch_to_pydict(batches[0])
    assert d["extra"] == [None] * 10


def test_sink_roundtrip(tmp_path):
    out = str(tmp_path / "out")
    schema = Schema([Field("k", DataType.int64()), Field("s", DataType.string(8))])
    src = MemoryScanExec(
        [[batch_from_pydict({"k": [1, 2, None], "s": ["a", None, "c"]}, schema)]], schema
    )
    sink = ParquetSinkExec(src, out)
    list(sink.execute(0, TaskContext(0, 1)))
    files = glob.glob(out + "/*.parquet")
    assert len(files) == 1
    scan = ParquetScanExec([files], schema)
    d = batch_to_pydict(list(scan.execute(0, TaskContext(0, 1)))[0])
    assert d == {"k": [1, 2, None], "s": ["a", None, "c"]}


def test_sink_dynamic_partitions(tmp_path):
    out = str(tmp_path / "out")
    schema = Schema([Field("k", DataType.int64()), Field("g", DataType.string(8))])
    src = MemoryScanExec(
        [[batch_from_pydict({"k": [1, 2, 3, 4], "g": ["x", "y", "x", "y"]}, schema)]], schema
    )
    sink = ParquetSinkExec(src, out, partition_columns=["g"])
    list(sink.execute(0, TaskContext(0, 1)))
    assert sorted(os.listdir(out)) == ["g=x", "g=y"]
    sub = Schema([Field("k", DataType.int64())])
    fx = glob.glob(out + "/g=x/*.parquet")
    scan = ParquetScanExec([fx], sub)
    d = batch_to_pydict(list(scan.execute(0, TaskContext(0, 1)))[0])
    assert sorted(d["k"]) == [1, 3]


@pytest.mark.parametrize("codec", [
    pq.CODEC_SNAPPY, pq.CODEC_ZSTD, pq.CODEC_LZ4_RAW, pq.CODEC_UNCOMPRESSED])
def test_writer_codecs_roundtrip(tmp_path, codec):
    """Snappy (Spark's parquet default) / zstd / lz4_raw pages: our
    reader and pyarrow both read them back exactly."""
    paq = pytest.importorskip("pyarrow.parquet")

    path = str(tmp_path / f"c{codec}.parquet")
    n = 500
    pq.write_parquet(path, SCHEMA, _cols(n), row_group_rows=200, codec=codec)

    scan = ParquetScanExec([[path]], SCHEMA)
    out = [b for b in scan.execute(0, TaskContext(0, 1))]
    d = batch_to_pydict(out[0]) if len(out) == 1 else batch_to_pydict(
        __import__("blaze_tpu.batch", fromlist=["concat_batches"]).concat_batches(out))
    data = np.arange(n, dtype=np.int64)
    vmask = data % 7 != 3
    assert d["i"] == [None if not vmask[i] else int(data[i]) for i in range(n)]
    assert d["s"] == [f"row-{i}" for i in range(n)]

    t = paq.read_table(path)
    got_i = t.column("i").to_pylist()
    assert got_i == [None if not vmask[i] else int(data[i]) for i in range(n)]
    assert t.column("s").to_pylist() == [f"row-{i}" for i in range(n)]
    assert t.column("b").to_pylist() == [bool(i % 2 == 0) for i in range(n)]


# --------------------------------------------------------------------
# The reader's whole-array decode (PR 35): the hybrid RLE / bit-packed
# decode, definition levels, the snappy codec choice, capacity, and
# pyarrow-written files held to pyarrow's own read.

def _varint(v):
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _hybrid_encode(runs, bit_width):
    """runs: ("rle", value, count) | ("bp", values), values padded with
    zeros to whole groups of 8 — the parquet-format hybrid encoding."""
    out = bytearray()
    for run in runs:
        if run[0] == "rle":
            _, value, count = run
            out += _varint(count << 1) + int(value).to_bytes((bit_width + 7) // 8, "little")
        else:
            values = list(run[1]) + [0] * (-len(run[1]) % 8)
            out += _varint((len(values) // 8) << 1 | 1)
            bits = 0
            for i, v in enumerate(values):
                bits |= int(v) << (i * bit_width)
            out += bits.to_bytes(len(values) * bit_width // 8, "little")
    return bytes(out)


def _hybrid_decode_scalar(data, bit_width, num_values):
    """The reference decoder: one Python step a value, bignum bit picks."""
    out = []
    pos = 0
    mask = (1 << bit_width) - 1
    while len(out) < num_values and pos < len(data):
        hdr = shift = 0
        while True:
            b = data[pos]
            pos += 1
            hdr |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        if hdr & 1:
            nbytes = (hdr >> 1) * bit_width
            bits = int.from_bytes(data[pos : pos + nbytes], "little")
            pos += nbytes
            out += [(bits >> (i * bit_width)) & mask for i in range((hdr >> 1) * 8)]
        else:
            nbytes = (bit_width + 7) // 8
            out += [int.from_bytes(data[pos : pos + nbytes], "little") & mask] * (hdr >> 1)
            pos += nbytes
    out = (out + [0] * num_values)[:num_values]
    # the decoder's result is int32: width 32 wraps as two's complement
    return np.array(out, np.int64).astype(np.int32)


def _hybrid_runs(kind, bit_width, rng):
    top = 1 << bit_width
    values = lambda n: [int(v) for v in rng.randint(0, top, n, dtype=np.int64)]
    if kind == "bit_packed":  # parquet-mr's shape: 504 a run, the last one short
        return [("bp", values(504)), ("bp", values(504)), ("bp", values(203))]
    if kind == "rle":
        return [("rle", top - 1, 300), ("rle", 0, 1), ("rle", values(1)[0], 77)]
    return [("rle", values(1)[0], 9), ("bp", values(504)), ("rle", top - 1, 130),
            ("bp", values(16)), ("bp", values(40)), ("rle", 0, 5), ("bp", values(3))]


@pytest.mark.parametrize("kind", ["bit_packed", "rle", "mixed"])
@pytest.mark.parametrize("bit_width", range(1, 33))
def test_hybrid_decode_matches_the_scalar_decoder(bit_width, kind):
    runs = _hybrid_runs(kind, bit_width, np.random.RandomState(1000 * bit_width + len(kind)))
    data = _hybrid_encode(runs, bit_width)
    total = sum(r[2] if r[0] == "rle" else len(r[1]) for r in runs)
    assert total % 8  # the last group is cut short by num_values
    for n in (total, total - 1, 8, 1, 0):
        got = pq._rle_bp_decode(data, bit_width, n)
        assert got.dtype == np.int32 and got.shape == (n,)
        assert (got == _hybrid_decode_scalar(data, bit_width, n)).all(), n


def test_hybrid_decode_edges():
    # width 0 holds no bytes a value; a buffer that ends early reads zeros
    assert (pq._rle_bp_decode(b"", 0, 5) == 0).all()
    data = _hybrid_encode([("bp", [5, 6, 7, 1, 2, 3, 4, 5]), ("rle", 3, 4)], 3)
    assert pq._rle_bp_decode(data, 3, 20).tolist() == [5, 6, 7, 1, 2, 3, 4, 5, 3, 3, 3, 3] + [0] * 8
    # a last bit-packed group whose bytes were cut short (old writers)
    assert pq._rle_bp_decode(data[:3], 3, 8).tolist() == _hybrid_decode_scalar(data[:3], 3, 8).tolist()
    # a run header of more than one varint byte
    long_run = _hybrid_encode([("rle", 1, 100_000), ("bp", [1, 0, 1])], 1)
    assert pq._rle_bp_decode(long_run, 1, 100_003).sum() == 100_002


def _chunk_pages(path, chunk):
    """(header dict, decompressed v1 payload) of every page of a chunk."""
    from blaze_tpu.io.thrift_compact import CompactReader

    with open(path, "rb") as f:
        f.seek(chunk.offset)
        blob = f.read(chunk.total_comp)
    pos = 0
    while pos < len(blob):
        r = CompactReader(memoryview(blob)[pos:])
        ph = r.read_struct()
        raw = blob[pos + r.pos : pos + r.pos + ph[3]]
        pos += r.pos + ph[3]
        yield ph, raw


def _dictionary_file(tmp_path, n=150_000, **kw):
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as papq

    rng = np.random.RandomState(5)
    path = str(tmp_path / "dict.parquet")
    # ~78,000 distinct values: the dictionary stays under its 1 MB page
    # and the indices reach 17 bits, the cell's l_extendedprice shape
    values = rng.randint(0, 100_000, n) * 1_000_003
    papq.write_table(pa.table({"v": pa.array(values, pa.int64())}), path,
                     compression="snappy", use_dictionary=True, data_page_size=64 << 10, **kw)
    return path, values


def test_pyarrow_index_pages_are_504_value_runs(tmp_path):
    """A pyarrow (as a parquet-mr) dictionary-index page is bit-packed
    runs of 504 values: the decode must not pay one numpy call a run."""
    path, values = _dictionary_file(tmp_path)
    chunk = pq.read_metadata(path).row_groups[0].chunks["v"]
    widths = []
    for ph, raw in _chunk_pages(path, chunk):
        if ph.get(1) != pq.PAGE_DATA:
            continue
        payload = pq._snappy_decompress(raw)
        (def_len,) = np.frombuffer(payload[:4], "<u4")
        body = payload[4 + int(def_len):]
        bit_width, nv = body[0], ph[5][1]
        widths.append(bit_width)  # grows with the dictionary, page by page
        assert body[1] == 63 << 1 | 1  # the first run: 63 groups of 8, 504 values
        got = pq._rle_bp_decode(body[1:], bit_width, nv)
        assert (got == _hybrid_decode_scalar(body[1:], bit_width, nv)).all()
    assert len(widths) > 1 and max(widths) == 17
    data, validity, _ = pq.read_column_chunk(path, chunk, DataType.int64())
    assert validity.all() and (data == values).all()


def _nulls_file(tmp_path, page_version, n=30_000):
    """Nulls scattered (bit-packed levels) and in long stretches (RLE
    levels), v1 (u32-prefixed, compressed) and v2 (bare, uncompressed)."""
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as papq

    rng = np.random.RandomState(9)
    valid = rng.rand(n) > 0.045          # tpcds's 4.5%-NULL foreign keys
    valid[5_000:9_000] = False
    valid[20_000:26_000] = True
    keys = rng.randint(0, 2_000, n)
    path = str(tmp_path / f"nulls{page_version}.parquet")
    papq.write_table(pa.table({"k": pa.array(keys, pa.int64(), mask=~valid)}), path,
                     compression="snappy", data_page_version=page_version, data_page_size=16 << 10)
    return path, keys, valid


@pytest.mark.parametrize("page_version", ["1.0", "2.0"])
def test_definition_levels_with_nulls(tmp_path, page_version):
    import pyarrow.parquet as papq

    path, keys, valid = _nulls_file(tmp_path, page_version)
    chunk = pq.read_metadata(path).row_groups[0].chunks["k"]
    data, validity, _ = pq.read_column_chunk(path, chunk, DataType.int64())
    assert (validity == valid).all()
    assert (data[valid] == keys[valid]).all() and (data[~valid] == 0).all()
    assert papq.read_table(path).column("k").to_pylist() == [
        int(k) if v else None for k, v in zip(keys, valid)]


def test_snappy_library_and_pure_python_agree(tmp_path, monkeypatch):
    pa = pytest.importorskip("pyarrow")
    path, values = _dictionary_file(tmp_path)
    chunk = pq.read_metadata(path).row_groups[0].chunks["v"]
    assert pq._snappy_library() is not None
    blocks = [raw for _, raw in _chunk_pages(path, chunk)]
    # this module's own encoder, with overlapping copies (offset < length)
    rng = np.random.RandomState(3)
    for src in (b"a" * 5000, b"abc" * 700 + bytes(rng.randint(0, 4, 3000).astype(np.uint8)),
                bytes(rng.randint(0, 256, 4000).astype(np.uint8)), b"", b"xyz"):
        blocks.append(pq._snappy_compress(src))
        assert pq._snappy_decompress(blocks[-1]) == src
    for block in blocks:
        want = pa.Codec("snappy").decompress(block, len(pq._snappy_decompress(block)), asbytes=True)
        assert pq.snappy_decompress(block) == pq._snappy_decompress(block) == want
    # where no library imports, the same pages decode through the fallback
    whole = pq.read_column_chunk(path, chunk, DataType.int64())
    monkeypatch.setattr(pq, "_snappy_library", lambda: None)
    fallback = pq.read_column_chunk(path, chunk, DataType.int64())
    assert (whole[0] == fallback[0]).all() and (fallback[0] == values).all()
    with pytest.raises(ValueError, match="copy offset"):
        pq._snappy_decompress(bytes([8, 0x01 | (4 << 2), 9]))  # copies from before the start


def _mixed_file(tmp_path, n=40_000):
    """The shapes a Spark table holds, as pyarrow writes them: dictionary
    int64 decimal, date, dictionary string, a column whose dictionary
    outgrows its page (PLAIN fallback), a column with nulls."""
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as papq
    from bench.entries import catalyst_parquet

    rng = np.random.RandomState(21)
    schema = Schema([
        Field("price", DataType.decimal(12, 2)),
        Field("day", DataType.date32()),
        Field("flag", DataType.string(8)),
        Field("wide", DataType.int64()),
        Field("maybe", DataType.int32()),
    ])
    price = rng.randint(0, 5_000, n).astype(np.int64) * 2_099 + 90_001  # 40 KB of dictionary
    day = rng.randint(8_000, 10_600, n).astype(np.int32)
    words = [b"A", b"N", b"R", b"RETURN", b""]
    pick = rng.randint(0, len(words), n)
    flag = np.zeros((n, 8), np.uint8)
    flag_len = np.array([len(words[i]) for i in pick], np.int32)
    for i, w in enumerate(words):
        flag[pick == i, : len(w)] = np.frombuffer(w, np.uint8)
    wide = rng.randint(0, 1 << 62, n).astype(np.int64)   # all distinct: PLAIN
    maybe = rng.randint(-50, 50, n).astype(np.int32)
    maybe_valid = rng.rand(n) > 0.3
    ones = np.ones(n, bool)
    cols = {"price": (price, ones, None), "day": (day, ones, None), "flag": (flag, ones, flag_len),
            "wide": (wide, ones, None), "maybe": (maybe, maybe_valid, None)}
    arrays = [catalyst_parquet.arrow_array(f.dtype, *cols[f.name]) for f in schema.fields]
    path = str(tmp_path / "mixed.parquet")
    papq.write_table(pa.Table.from_arrays(arrays, names=schema.names), path,
                     **dict(catalyst_parquet.WRITER, data_page_size=32 << 10, row_group_size=25_000,
                            dictionary_pagesize_limit=64 << 10))
    return path, schema, cols


def _as_stored(value):
    """A pyarrow python value as the engine stores it."""
    if isinstance(value, datetime.date):
        return (value - datetime.date(1970, 1, 1)).days
    if hasattr(value, "scaleb"):  # decimal(12, 2): the unscaled integer
        return int(value.scaleb(2))
    return value


@pytest.mark.parametrize("column", ["price", "day", "flag", "wide", "maybe"])
def test_pyarrow_file_reads_as_pyarrow_reads_it(tmp_path, column):
    import pyarrow.parquet as papq

    path, schema, cols = _mixed_file(tmp_path)
    dtype = next(f.dtype for f in schema.fields if f.name == column)
    page_encodings = {ph[5][2] for ph, _ in _chunk_pages(path, pq.read_metadata(path).row_groups[0].chunks[column])
                      if ph.get(1) == pq.PAGE_DATA}
    # every chunk starts dictionary-encoded; only `wide` outgrows its dictionary page
    assert page_encodings == ({pq.ENC_RLE_DICT, pq.ENC_PLAIN} if column == "wide" else {pq.ENC_RLE_DICT})
    theirs = [_as_stored(v) for v in papq.read_table(path, columns=[column]).column(column).to_pylist()]
    ours = []
    for rg in pq.read_metadata(path).row_groups:
        data, validity, lengths = pq.read_column_chunk(path, rg.chunks[column], dtype)
        assert not data[~validity].any()  # a null row holds zeros
        if dtype.is_string:
            ours += [bytes(data[i, : lengths[i]]).decode() if validity[i] else None for i in range(rg.rows)]
        else:
            ours += [v if ok else None for v, ok in zip(data.tolist(), validity.tolist())]
    assert ours == theirs
    source, valid, _ = cols[column]
    if not dtype.is_string:
        assert ours == [v if ok else None for v, ok in zip(source.tolist(), valid.tolist())]


def test_capacity_pads_what_the_chunk_holds(tmp_path):
    path, schema, _ = _mixed_file(tmp_path)
    rg = pq.read_metadata(path).row_groups[1]
    assert rg.rows == 15_000
    for f in schema.fields:
        plain = pq.read_column_chunk(path, rg.chunks[f.name], f.dtype)
        padded = pq.read_column_chunk(path, rg.chunks[f.name], f.dtype, capacity=16_384)
        for a, b in zip(plain, padded):
            if a is None:
                assert b is None
                continue
            assert b.dtype == a.dtype and b.shape == (16_384,) + a.shape[1:]
            assert (b[: rg.rows] == a).all() and not b[rg.rows :].any()
    with pytest.raises(ValueError, match="capacity"):
        pq.read_column_chunk(path, rg.chunks["day"], DataType.date32(), capacity=rg.rows - 1)


@pytest.mark.parametrize("decoder", ["arrow_reader", "page_decoder_snappy_library",
                                     "page_decoder_pure_python"])
def test_scan_counts_pages_and_the_codec_that_ran(tmp_path, monkeypatch, decoder):
    """One scan_decode span a row group and every chunk in scan_chunks;
    scan_chunks_native those Arrow's reader decoded, scan_pages the
    pages the page decoder walked (none where Arrow took every chunk),
    scan_pages_python_codec those only where no snappy library is."""
    import pyarrow.parquet as papq

    from blaze_tpu.runtime import dispatch

    path, schema, cols = _mixed_file(tmp_path)
    if decoder != "arrow_reader":
        monkeypatch.setattr(pq, "_arrow_reader", lambda: None)
    if decoder == "page_decoder_pure_python":
        monkeypatch.setattr(pq, "_snappy_library", lambda: None)
    md = papq.ParquetFile(path).metadata
    scan = ParquetScanExec([[path]], schema, batch_rows=8192)
    with dispatch.capture() as c:
        rows = sum(b.num_rows for b in scan.execute(0, TaskContext(0, 1)))
    assert rows == md.num_rows == 40_000
    assert c["scan_decode_n"] == c["scan_row_groups"] == md.num_row_groups == 2
    assert c["scan_file_bytes"] == sum(
        md.row_group(r).column(i).total_compressed_size
        for r in range(2) for i in range(len(schema.fields)))
    pages = sum(1 for rg in pq.read_metadata(path).row_groups
                for ch in rg.chunks.values() for _ in _chunk_pages(path, ch))
    assert pages > 20
    assert c["scan_chunks"] == 2 * len(schema.fields) == 10
    assert c["scan_chunks_native"] == (10 if decoder == "arrow_reader" else 0)
    assert c["scan_pages"] == (0 if decoder == "arrow_reader" else pages)
    assert c["scan_pages_python_codec"] == (pages if decoder == "page_decoder_pure_python" else 0)


@pytest.mark.parametrize("batch_rows,sliced", [(8192, True), (1 << 20, False)])
def test_scan_slices_under_a_span_and_hands_every_batch_over(tmp_path, batch_rows, sliced):
    """One scan_slice span a batch cut from a row group, none where row
    groups shorter than a batch are packed into one (PR 40); the
    pipelined scan hands over, and stages, exactly the batches it made."""
    from blaze_tpu.runtime import dispatch

    path, schema, _ = _mixed_file(tmp_path)
    rows = [rg.rows for rg in pq.read_metadata(path).row_groups]
    scan = ParquetScanExec([[path]], schema, batch_rows=batch_rows)
    with dispatch.capture() as c:
        batches = list(scan.execute(0, TaskContext(0, 1)))
    want = sum(-(-r // batch_rows) for r in rows) if sliced else 1
    assert len(batches) == want == c["pipeline_items"] == c["scan_stage_n"]
    assert c.get("scan_slice_n", 0) == (want if sliced else 0)
    assert c["scan_rows"] == sum(rows) and c["scan_rows_budget"] == want * batch_rows
    if sliced:
        assert want > len(rows) and c["scan_slice_ns"] > 0
        # each row group's tail is a piece of its own: two never fit a batch here
        assert (c.get("scan_coalesce_n", 0), c["scan_pieces_packed"]) == (0, 0)
    else:
        assert "scan_slice_ns" not in c
        assert (c["scan_coalesce_n"], c["scan_pieces_packed"]) == (1, len(rows))


def test_scan_batches_equal_the_decoded_row_group(tmp_path):
    """Decoding at the row group's capacity changes no batch: the scan's
    slices hold the file's rows, padding zero and invalid."""
    path, schema, cols = _mixed_file(tmp_path)
    scan = ParquetScanExec([[path]], schema, batch_rows=8192)
    got = batch_to_pydict(concat_batches(list(scan.execute(0, TaskContext(0, 1)))))
    assert got["maybe"] == [int(v) if ok else None for v, ok in zip(cols["maybe"][0], cols["maybe"][1])]
    assert got["wide"] == cols["wide"][0].tolist()
    assert got["day"] == cols["day"][0].tolist()


# --------------------------------------------------------------------
# Arrow's column reader behind read_row_group (PR 37): whatever it
# decodes equals the page decoder's arrays bit for bit, and whatever it
# does not take goes through the page decoder, chunk by chunk, counted.

def decoders_agree(path, schema, capacity=None):
    """Every row group of ``path`` through read_row_group twice — the
    page decoder alone, then with Arrow's reader — equal in dtype, shape
    and every byte, padding included.  Returns the second run's tally."""
    meta = pq.read_metadata(path)
    arrow_file = pq.open_arrow_file(path, schema.fields)
    assert arrow_file is not None
    tally = collections.Counter()
    try:
        for rg in meta.row_groups:
            cap = capacity or bucket_capacity(rg.rows)
            ours = pq.read_row_group(path, rg, schema.fields, cap)
            theirs = pq.read_row_group(path, rg, schema.fields, cap, arrow_file=arrow_file, tally=tally)
            for f, mine, arrows in zip(schema.fields, ours, theirs):
                assert (mine is None) == (arrows is None) == (f.name not in rg.chunks), f.name
                for a, b in zip(mine or (), arrows or ()):
                    assert (a is None) == (b is None), f.name
                    if a is not None:
                        assert a.dtype == b.dtype and a.shape == b.shape == (cap,) + a.shape[1:], f.name
                        assert a.tobytes() == b.tobytes(), f.name
    finally:
        arrow_file.close(force=True)
    return tally


def _typed_file(tmp_path, **writer):
    """Every type the reader maps, with NULLs in each, in two row groups."""
    pa = pytest.importorskip("pyarrow")
    import decimal

    import pyarrow.parquet as papq

    n = 3_000
    rng = np.random.RandomState(37)
    ints = rng.randint(-100, 100, n)
    mask = rng.rand(n) < 0.2
    words = ["", "a", "bb", "delivered", "x" * 8, "ÿ"]
    arrays = {
        "b": pa.array(ints % 3 == 0, pa.bool_(), mask=mask),
        "i8": pa.array(ints, pa.int8(), mask=mask),
        "i16": pa.array(ints * 300, pa.int16(), mask=mask),
        "i32": pa.array(ints * 70_000, pa.int32(), mask=mask),
        "i64": pa.array(ints.astype(np.int64) << 40, pa.int64(), mask=mask),
        "f32": pa.array(ints / 7, pa.float32(), mask=mask),
        "f64": pa.array(np.where(ints == 5, np.nan, ints / 3), pa.float64(), mask=mask),
        "day": pa.array(ints + 9_000, pa.int32(), mask=mask).cast(pa.date32()),
        "ts": pa.array(ints.astype(np.int64) * 10**9, pa.timestamp("us"), mask=mask),
        "ts_utc": pa.array(ints.astype(np.int64) * 10**9, pa.timestamp("us", tz="UTC"), mask=mask),
        "dec": pa.array([None if m else decimal.Decimal(int(v)).scaleb(-2) for v, m in zip(ints * 10**9, mask)],
                        pa.decimal128(12, 2)),
        "s": pa.array([None if m else words[v % len(words)] for v, m in zip(ints, mask)], pa.string()),
        "bin": pa.array([None if m else words[v % len(words)].encode() for v, m in zip(ints, mask)],
                        pa.binary()),
        "big_s": pa.array([None if m else words[v % len(words)] for v, m in zip(ints, mask)],
                          pa.large_string()),
    }
    schema = Schema([
        Field("b", DataType.bool_()), Field("i8", DataType.int8()), Field("i16", DataType.int16()),
        Field("i32", DataType.int32()), Field("i64", DataType.int64()), Field("f32", DataType.float32()),
        Field("f64", DataType.float64()), Field("day", DataType.date32()),
        Field("ts", DataType.timestamp()), Field("ts_utc", DataType.timestamp()),
        Field("dec", DataType.decimal(12, 2)), Field("s", DataType.string(16)),
        Field("bin", DataType.binary(8)), Field("big_s", DataType.string(8)),
    ])
    path = str(tmp_path / "typed.parquet")
    papq.write_table(pa.table(arrays), path, row_group_size=2_000, data_page_size=4 << 10,
                     store_decimal_as_integer=True, **writer)
    return path, schema


def _long_strings_file(tmp_path, use_dictionary):
    """Strings up to 40 bytes, some NULL, one row group: wider than any
    width they are read at, PLAIN or dictionary-encoded."""
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as papq

    rng = np.random.RandomState(8)
    values = [None if i % 11 == 0 else "k%d-" % (i % 50) + "z" * int(rng.randint(0, 36)) for i in range(5_000)]
    path = str(tmp_path / f"long{use_dictionary}.parquet")
    papq.write_table(pa.table({"s": pa.array(values, pa.string())}), path,
                     use_dictionary=use_dictionary, data_page_size=8 << 10)
    return path, values


def _own_writer_file(tmp_path, codec):
    path = str(tmp_path / f"own{codec}.parquet")
    pq.write_parquet(path, SCHEMA, _cols(500), row_group_rows=200, codec=codec)
    return path, SCHEMA


CORPUS = {
    # the cell's own shapes; the second row group holds 15,000 rows at capacity 16,384
    "mixed": lambda tmp: _mixed_file(tmp)[:2],
    "dictionary_int64": lambda tmp: (_dictionary_file(tmp)[0], Schema([Field("v", DataType.int64())])),
    "nulls_v1": lambda tmp: (_nulls_file(tmp, "1.0")[0], Schema([Field("k", DataType.int64())])),
    "nulls_v2": lambda tmp: (_nulls_file(tmp, "2.0")[0], Schema([Field("k", DataType.int64())])),
    "typed_dictionary": lambda tmp: _typed_file(tmp, use_dictionary=True, compression="snappy"),
    "typed_plain_v2_zstd": lambda tmp: _typed_file(tmp, use_dictionary=False, compression="zstd",
                                                   data_page_version="2.0"),
    "typed_gzip": lambda tmp: _typed_file(tmp, use_dictionary=True, compression="gzip"),
    "typed_uncompressed": lambda tmp: _typed_file(tmp, use_dictionary=False, compression="NONE"),
    "own_writer_snappy": lambda tmp: _own_writer_file(tmp, pq.CODEC_SNAPPY),
    "own_writer_gzip": lambda tmp: _own_writer_file(tmp, pq.CODEC_GZIP),
    "own_writer_zstd": lambda tmp: _own_writer_file(tmp, pq.CODEC_ZSTD),
    "own_writer_lz4_raw": lambda tmp: _own_writer_file(tmp, pq.CODEC_LZ4_RAW),
    "own_writer_uncompressed": lambda tmp: _own_writer_file(tmp, pq.CODEC_UNCOMPRESSED),
}


@pytest.mark.parametrize("case", sorted(CORPUS))
def test_arrow_reader_equals_the_page_decoder(tmp_path, case):
    path, schema = CORPUS[case](tmp_path)
    tally = decoders_agree(path, schema)
    chunks = sum(len(rg.chunks) for rg in pq.read_metadata(path).row_groups)
    # every chunk of these files pairs with its requested type: none is left to the page decoder
    assert tally["chunks_native"] == tally["chunks"] == chunks > 0
    assert tally["pages"] == 0


@pytest.mark.parametrize("use_dictionary", [True, False])
@pytest.mark.parametrize("width", [4, 8, 12, 64])
def test_strings_longer_than_the_width_are_cut_the_same(tmp_path, width, use_dictionary):
    """A word a row where the width is a word's (4, 8), opaque rows
    where it is not (12), and a width no value reaches (64)."""
    path, values = _long_strings_file(tmp_path, use_dictionary)
    schema = Schema([Field("s", DataType.string(width))])
    tally = decoders_agree(path, schema, capacity=8_192)  # the row group holds 5,000
    assert tally["chunks_native"] == tally["chunks"] == 1
    rg = pq.read_metadata(path).row_groups[0]
    arrow_file = pq.open_arrow_file(path, schema.fields)
    try:
        ((data, validity, lengths),) = pq.read_row_group(path, rg, schema.fields, 8_192, arrow_file=arrow_file)
    finally:
        arrow_file.close(force=True)
    assert data.shape == (8_192, width) and not data[5_000:].any() and not validity[5_000:].any()
    got = [bytes(data[i, : lengths[i]]) if validity[i] else None for i in range(5_000)]
    assert got == [None if v is None else v.encode()[:width] for v in values]


def test_a_capacity_under_the_row_group_is_refused(tmp_path):
    path, schema, _ = _mixed_file(tmp_path)
    rg = pq.read_metadata(path).row_groups[1]
    with pytest.raises(ValueError, match="capacity"):
        pq.read_row_group(path, rg, schema.fields, rg.rows - 1)


def test_a_failed_arrow_read_leaves_the_row_group_to_the_page_decoder(tmp_path):
    """What Arrow's reader refuses, the page decoder reads — or says
    what is wrong with."""
    pa = pytest.importorskip("pyarrow")

    class Refusing:
        def read_row_group(self, *args, **kwargs):
            raise pa.ArrowNotImplementedError("not this encoding")

    path, schema, _ = _mixed_file(tmp_path)
    tally = collections.Counter()
    for rg in pq.read_metadata(path).row_groups:
        cap = bucket_capacity(rg.rows)
        ours = pq.read_row_group(path, rg, schema.fields, cap)
        fell_back = pq.read_row_group(path, rg, schema.fields, cap, arrow_file=Refusing(), tally=tally)
        assert all(a.tobytes() == b.tobytes() for x, y in zip(ours, fell_back) for a, b in zip(x, y)
                   if a is not None)
    assert tally["chunks"] == 10 and tally["chunks_native"] == 0 and tally["pages"] > 20


def _scan_arrays(scan):
    """Every batch of every partition: (rows, each column's buffers as bytes)."""
    out = []
    for p in range(scan.num_partitions()):
        for b in scan.execute(p, TaskContext(p, scan.num_partitions())):
            out.append((b.num_rows, [None if a is None else np.asarray(a).tobytes()
                                     for c in b.columns for a in (c.data, c.validity, c.lengths)]))
    return out


@pytest.mark.parametrize("entries", ["whole_file", "byte_ranges"])
def test_a_scan_without_the_library_gives_the_same_batches(tmp_path, monkeypatch, entries):
    """The chooser returning None is today's scan: the same batches, byte
    for byte, from a path and from the FileSplit ranges of one file."""
    from blaze_tpu.runtime import dispatch

    path, schema, _ = _mixed_file(tmp_path)
    wider = Schema(list(schema.fields) + [Field("absent", DataType.string(8))])
    if entries == "whole_file":
        groups = [[path]]
    else:  # one row group a range: the second starts at the second one's midpoint
        cut = pq.read_metadata(path).row_groups[1].midpoint
        groups = [[FileSplit(path, 0, cut)], [FileSplit(path, cut, os.path.getsize(path) - cut)]]
    with dispatch.capture() as c:
        native = _scan_arrays(ParquetScanExec(groups, wider, batch_rows=8192))
    assert c["scan_chunks_native"] == c["scan_chunks"] == 10 and c["scan_pages"] == 0
    monkeypatch.setattr(pq, "_arrow_reader", lambda: None)
    with dispatch.capture() as c:
        plain = _scan_arrays(ParquetScanExec(groups, wider, batch_rows=8192))
    assert c["scan_chunks"] == 10 and c["scan_chunks_native"] == 0 and c["scan_pages"] > 20
    assert native == plain and sum(rows for rows, _ in native) == 40_000


def test_a_scan_closes_what_it_opened(tmp_path, monkeypatch):
    """Arrow's reader and the file under it go with the entry, also where
    the consumer stops early: nothing of a file outlives its task."""
    path, schema, _ = _mixed_file(tmp_path)
    opened = []
    open_arrow_file = pq.open_arrow_file
    monkeypatch.setattr(pq, "open_arrow_file", lambda *a: opened.append(open_arrow_file(*a)) or opened[-1])
    monkeypatch.setattr("blaze_tpu.conf.PIPELINE_DEPTH.get", lambda: 0)  # the stream itself, no producer thread
    scan = ParquetScanExec([[path], [path]], schema, batch_rows=8192)
    assert sum(b.num_rows for b in scan.execute(0, TaskContext(0, 2))) == 40_000
    stream = scan.execute(1, TaskContext(1, 2))
    next(stream)
    stream.close()
    assert len(opened) == 2 and all(f.closed for f in opened)


def test_arrow_outlives_the_producer_threads_of_a_process_first_scans(tmp_path):
    """A process whose first use of pyarrow is a scan: each scan's
    producer thread ends with its task, and Arrow must still read on the
    next one (pyarrow is imported with io/parquet, on the importing
    thread: imported first by a thread that ends, it segfaults the next
    thread that reads a file through it)."""
    import subprocess
    import sys

    script = f"""
import sys
import numpy as np
from blaze_tpu.io import parquet as pq
from blaze_tpu.ops import ParquetScanExec
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema

schema = Schema([Field("k", DataType.int64()), Field("s", DataType.string(8))])
words = np.zeros((300, 8), np.uint8)
words[:, 0] = 97 + np.arange(300) % 3
rows = 0
for i in range(3):
    path = {str(tmp_path)!r} + "/f%d.parquet" % i
    pq.write_parquet(path, schema, {{"k": (np.arange(300), None, None),
                                    "s": (words, None, np.ones(300, np.int32))}}, row_group_rows=100)
    scan = ParquetScanExec([[path]], schema)  # pipelined: a producer thread a task
    rows += sum(b.num_rows for b in scan.execute(0, TaskContext(0, 1)))
import pyarrow.parquet
rows += pyarrow.parquet.read_table(path).num_rows
print("rows", rows)
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0 and done.stdout.split()[-2:] == ["rows", "1200"], done.stderr[-2000:]


# --------------------------------------------------------------------
# The scan decodes as a stream (PR 39): a row group Arrow takes whole is
# read piece by piece on a decode thread of its own, any other as one
# piece through read_row_group — and either way the batches are the
# whole-row-group read's, batch for batch and byte for byte.

def _not_taken_file(tmp_path, how):
    """Two row groups of 5,000 and 2,000 rows with one chunk Arrow's
    reader is not given (INT96) or whose type is not the requested one
    (int32 read as int64), beside two it does take."""
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as papq

    n = 7_000
    rng = np.random.RandomState(5)
    k = pa.array(rng.randint(0, 1 << 40, n), pa.int64(), mask=rng.rand(n) < 0.1)
    s = pa.array([None if i % 13 == 0 else "w%d" % (i % 40) for i in range(n)], pa.string())
    if how == "int96_chunk":
        c, dtype = pa.array(np.arange(n) * 1_000_003 + 1_600_000_000_000_000, pa.timestamp("us")), DataType.timestamp()
        writer = dict(use_deprecated_int96_timestamps=True)
    else:
        c, dtype, writer = pa.array(rng.randint(-9, 9, n), pa.int32()), DataType.int64(), {}
    path = str(tmp_path / f"{how}.parquet")
    papq.write_table(pa.table({"k": k, "c": c, "s": s}), path, row_group_size=5_000, **writer)
    return path, Schema([Field("k", DataType.int64()), Field("c", dtype), Field("s", DataType.string(8))])


STREAMED = dict(CORPUS)  # every chunk of these is Arrow's: each row group is read as a stream
WHOLE = {how: (lambda tmp, how=how: _not_taken_file(tmp, how)) for how in ("int96_chunk", "other_type_chunk")}


def _whole_read_batches(path, schema, batch_rows):
    """The reference: each row group decoded WHOLE by the page decoder
    alone, cut in ``batch_rows`` steps by hand, and (PR 40) neighbouring
    cuts shorter than ``batch_rows`` joined while they fit one batch —
    (rows, capacity, every buffer's bytes) a batch."""
    cuts = []  # each: every buffer's own rows, unpadded
    for rg in pq.read_metadata(path).row_groups:
        chunks = pq.read_row_group(path, rg, schema.fields, bucket_capacity(rg.rows))
        for s in range(0, rg.rows, batch_rows):
            e = min(s + batch_rows, rg.rows)
            cuts.append([None if a is None else a[s:e] for arrays in chunks for a in arrays])
    joined = []
    for cut in cuts:
        rows = len(cut[1])  # a validity
        if joined and len(joined[-1][1]) + rows <= batch_rows and rows < batch_rows \
                and len(joined[-1][1]) < batch_rows:
            joined[-1] = [None if a is None else np.concatenate([a, b]) for a, b in zip(joined[-1], cut)]
        else:
            joined.append(cut)
    out = []
    for cut in joined:
        rows = len(cut[1])
        cap = bucket_capacity(rows)
        buffers = []
        for a in cut:
            if a is None:
                buffers.append(None)
                continue
            padded = np.zeros((cap,) + a.shape[1:], a.dtype)
            padded[:rows] = a
            buffers.append((padded.dtype, padded.shape, padded.tobytes()))
        out.append((rows, cap, buffers))
    return out


def _scan_batches(scan, partition=0):
    """What the consumer of one task's scan gets, in _whole_read_batches' form."""
    out = []
    for b in scan.execute(partition, TaskContext(partition, scan.num_partitions())):
        buffers = []
        for c in b.columns:
            for a in (c.data, c.validity, c.lengths):
                a = None if a is None else np.asarray(a)
                buffers.append(None if a is None else (a.dtype, a.shape, a.tobytes()))
        out.append((b.num_rows, b.capacity, buffers))
    return out


def _scan_threads_ended():
    import threading

    for t in threading.enumerate():
        if t.name.startswith("blaze-"):
            t.join(10)
            assert not t.is_alive(), t.name


# a tail piece in every row group; a row group no longer than a batch
@pytest.mark.parametrize("batch_rows", [1024, 1 << 20])
@pytest.mark.parametrize("case", sorted(STREAMED) + sorted(WHOLE))
def test_the_streamed_scan_hands_on_the_whole_reads_batches(tmp_path, case, batch_rows):
    from blaze_tpu.runtime import dispatch

    path, schema = {**STREAMED, **WHOLE}[case](tmp_path)
    row_groups = pq.read_metadata(path).row_groups
    want = _whole_read_batches(path, schema, batch_rows)
    with dispatch.capture() as c:
        got = _scan_batches(ParquetScanExec([[path]], schema, batch_rows=batch_rows))
    unpacked = sum(-(-rg.rows // batch_rows) for rg in row_groups)
    # row groups under a batch each join: the whole file is one batch of 2^20 rows
    assert len(got) == len(want) <= unpacked and (batch_rows == 1024 or len(want) == 1)
    assert c["scan_rows"] == sum(rg.rows for rg in row_groups)
    assert c["scan_rows_budget"] == len(want) * batch_rows
    for k, (mine, theirs) in enumerate(zip(got, want)):
        assert mine[:2] == theirs[:2], k
        assert mine[2] == theirs[2], k
    assert c["scan_decode_n"] == c["scan_row_groups"] == len(row_groups)
    assert c["scan_row_groups_streamed"] == (len(row_groups) if case in STREAMED else 0)
    assert c["scan_pieces"] == c["decode_items"] == (unpacked if case in STREAMED else len(row_groups))
    assert c["scan_pieces_packed"] == (0 if len(want) == unpacked else c["scan_pieces"] - len(want) + c["scan_coalesce_n"])
    assert c["pipeline_items"] == c["scan_stage_n"] == len(want)


@pytest.mark.parametrize("how", ["streamed", "int96_chunk", "no_pyarrow"])
def test_one_scans_counters_close_and_the_two_hand_overs_tally_apart(tmp_path, monkeypatch, how):
    """scan_decode is ONE tally a row group however many pieces it came
    in; pipeline_* count the task-facing hand-over alone, decode_* the
    decode thread's; scan_slice stays one a batch of a row group that is
    cut into several."""
    from blaze_tpu.runtime import dispatch

    if how == "int96_chunk":
        path, schema = _not_taken_file(tmp_path, how)
        rows, batch_rows = [5_000, 2_000], 2_048
    else:
        path, schema, _ = _mixed_file(tmp_path)
        rows, batch_rows = [25_000, 15_000], 8_192
    if how == "no_pyarrow":
        monkeypatch.setattr(pq, "_arrow_reader", lambda: None)
    batches = sum(-(-r // batch_rows) for r in rows)
    with dispatch.capture() as c:
        got = sum(b.num_rows for b in ParquetScanExec([[path]], schema, batch_rows=batch_rows).execute(
            0, TaskContext(0, 1)))
        _scan_threads_ended()  # a producer records its life as its thread ends
    assert got == sum(rows)
    assert c["scan_decode_n"] == c["scan_row_groups"] == 2 and c["scan_decode_ns"] > 0
    assert c["pipeline_items"] == c["scan_stage_n"] == batches
    # none for a row group no longer than a batch (the INT96 file's second)
    assert c["scan_slice_n"] == sum(-(-r // batch_rows) for r in rows if r > batch_rows)
    streamed = how == "streamed"
    assert c["scan_row_groups_streamed"] == (2 if streamed else 0)
    assert c["scan_pieces"] == c["decode_items"] == (batches if streamed else 2)
    assert c["scan_chunks_native"] == {"streamed": 10, "int96_chunk": 4, "no_pyarrow": 0}[how]
    assert c["decode_producer_ns"] > 0 and c["pipeline_producer_ns"] > 0
    hand_over = {"_items", "_producer_ns", "_wait_ns", "_wait_n", "_full_ns", "_full_n"}
    for tally in ("pipeline", "decode"):
        assert {k[len(tally):] for k in c if k.startswith(tally + "_")} <= hand_over, tally
    # the decode thread's life holds its decode and its waits: one thread, one clock
    assert c["scan_decode_ns"] + c.get("decode_full_ns", 0) <= c["decode_producer_ns"]


class _WatchedArrowFile:
    """Arrow's ParquetFile with every use noted beside the thread that
    made it, each pull of a stream's batch too; ``fail_after`` makes the
    stream raise as Arrow would after that many batches, ``batch_size``
    hands batches of another length than was asked."""

    def __init__(self, arrow_file, uses, fail_after=None, batch_size=None):
        self._f, self._uses = arrow_file, uses
        self._fail_after, self._batch_size = fail_after, batch_size

    def _note(self, what):
        import threading

        self._uses.append((what, threading.current_thread().name))

    @property
    def closed(self):
        return self._f.closed

    @property
    def schema_arrow(self):
        return self._f.schema_arrow

    @property
    def metadata(self):
        return self._f.metadata

    def read_row_group(self, *args, **kwargs):
        self._note("read_row_group")
        return self._f.read_row_group(*args, **kwargs)

    def iter_batches(self, batch_size, **kwargs):
        import pyarrow

        self._note("iter_batches")
        for k, batch in enumerate(self._f.iter_batches(batch_size=self._batch_size or batch_size, **kwargs)):
            if k == self._fail_after:
                raise pyarrow.ArrowInvalid("a page that does not decode")
            self._note("next")
            yield batch

    def close(self, force=False):
        self._note("close")
        self._f.close(force=force)


def _watch_opens(monkeypatch, **how):
    """Every Arrow file the scan opens from here on is watched; (the
    files, their uses, the threads that opened them)."""
    import threading

    files, uses, openers = [], [], []
    open_arrow_file = pq.open_arrow_file

    def opened(*args):
        openers.append(threading.current_thread().name)
        files.append(_WatchedArrowFile(open_arrow_file(*args), uses, **how))
        return files[-1]

    monkeypatch.setattr(pq, "open_arrow_file", opened)
    return files, uses, openers


def test_an_arrow_file_is_one_threads_from_open_to_close(tmp_path, monkeypatch):
    path, schema, _ = _mixed_file(tmp_path)
    files, uses, openers = _watch_opens(monkeypatch)
    scan = ParquetScanExec([[path, path]], schema, batch_rows=8192)
    assert sum(b.num_rows for b in scan.execute(0, TaskContext(0, 1))) == 80_000
    _scan_threads_ended()
    assert len(files) == 2 and all(f.closed for f in files)
    assert [w for w, _ in uses].count("iter_batches") == 4 and ("read_row_group" not in dict(uses))
    assert set(openers) | {t for _, t in uses} == {"blaze-parquet_decode"}


def test_depth_zero_runs_the_whole_scan_on_the_calling_thread(tmp_path, monkeypatch):
    import threading

    from blaze_tpu.runtime import dispatch

    path, schema, _ = _mixed_file(tmp_path)
    files, uses, openers = _watch_opens(monkeypatch)
    monkeypatch.setattr("blaze_tpu.conf.PIPELINE_DEPTH.get", lambda: 0)
    started, start = [], threading.Thread.start
    monkeypatch.setattr(threading.Thread, "start", lambda self: started.append(self.name) or start(self))
    with dispatch.capture() as c:
        got = _scan_batches(ParquetScanExec([[path]], schema, batch_rows=8192))
    assert got == _whole_read_batches(path, schema, 8192)
    assert not [name for name in started if name.startswith("blaze-")]
    assert set(openers) | {t for _, t in uses} == {threading.current_thread().name}
    assert files[0].closed and c["scan_row_groups_streamed"] == 2 and c["scan_pieces"] == 6
    assert not [k for k in c if k.startswith(("pipeline_", "decode_"))]


@pytest.mark.parametrize("fail_after", [0, 1, 3])
def test_a_stream_arrow_fails_in_is_finished_by_the_whole_read(tmp_path, monkeypatch, fail_after):
    """From the piece on at which Arrow's call fails, the rest of the row
    group is ONE piece through read_row_group, each row handed on once."""
    from blaze_tpu.runtime import dispatch

    path, schema, _ = _mixed_file(tmp_path)
    _watch_opens(monkeypatch, fail_after=fail_after)
    with dispatch.capture() as c:
        got = _scan_batches(ParquetScanExec([[path]], schema, batch_rows=8192))
    assert got == _whole_read_batches(path, schema, 8192)
    # 25,000 rows are 4 pieces and 15,000 are 2: only a row group with more pieces than that streams to its end
    assert c["scan_row_groups_streamed"] == (1 if fail_after == 3 else 0)
    assert c["scan_pieces"] == {0: 2, 1: 4, 3: 6}[fail_after]
    assert c["scan_decode_n"] == c["scan_row_groups"] == 2 and c["pipeline_items"] == 6
    assert c["scan_chunks"] == 10 and c["scan_chunks_native"] == 10  # the whole read is Arrow's too


@pytest.mark.parametrize("arrow_hands", [1_000, 8_192, 20_000])
def test_pieces_of_another_length_are_cut_again(tmp_path, monkeypatch, arrow_hands):
    path, schema, _ = _mixed_file(tmp_path)
    _watch_opens(monkeypatch, batch_size=arrow_hands)
    got = _scan_batches(ParquetScanExec([[path]], schema, batch_rows=8192))
    assert [rows for rows, _, _ in got] == [8192, 8192, 8192, 424, 8192, 6808]
    assert got == _whole_read_batches(path, schema, 8192)


def _broken_file(tmp_path, how):
    """_mixed_file's file, its footer cut off (nothing opens it) or the
    second row group's first pages overwritten (the first row group
    decodes, the second does not)."""
    path, schema, _ = _mixed_file(tmp_path)
    with open(path, "rb") as f:
        data = bytearray(f.read())
    if how == "truncated":
        data = data[: len(data) // 2]
    else:
        start = min(ch.offset for ch in pq.read_metadata(path).row_groups[1].chunks.values())
        data[start + 40 : start + 4_000] = bytes(3_960)
    with open(path, "wb") as f:
        f.write(data)
    return path, schema


@pytest.mark.parametrize("how", ["truncated", "corrupt_second_row_group"])
def test_a_decode_error_reaches_the_consumer_as_it_was_raised(tmp_path, monkeypatch, how):
    """Through both hand-overs an error keeps the type and the message it
    has with no thread between: the batches before it arrive first, and
    neither thread nor file outlives it."""
    path, schema = _broken_file(tmp_path, how)
    files, _, _ = _watch_opens(monkeypatch)

    def drive():
        got = []
        with pytest.raises(Exception) as caught:
            for b in ParquetScanExec([[path]], schema, batch_rows=8192).execute(0, TaskContext(0, 1)):
                got.append(b.num_rows)
        _scan_threads_ended()
        return got, caught.value

    piped_rows, piped = drive()
    with monkeypatch.context() as m:
        m.setattr("blaze_tpu.conf.PIPELINE_DEPTH.get", lambda: 0)
        sync_rows, sync = drive()
    assert type(piped) is type(sync) and str(piped) == str(sync)
    # the first row group's tail of 424 rows was held open for the piece that failed
    assert piped_rows == sync_rows == ([] if how == "truncated" else [8192, 8192, 8192])
    assert all(f.closed for f in files) and len(files) == (0 if how == "truncated" else 2)


@pytest.mark.parametrize("how", ["consumer_closes", "task_cancelled"])
def test_leaving_a_scan_mid_row_group_leaves_no_thread_and_no_open_file(tmp_path, monkeypatch, how):
    path, schema, _ = _mixed_file(tmp_path)
    files, uses, _ = _watch_opens(monkeypatch)
    ctx = TaskContext(0, 1)
    stream = ParquetScanExec([[path, path, path]], schema, batch_rows=1024).execute(0, ctx)
    assert next(stream).num_rows == 1024  # 25 pieces a first row group: the decode thread is inside it
    if how == "consumer_closes":
        stream.close()
    else:
        ctx.cancel()
        assert sum(b.num_rows for b in stream) < 3 * 40_000
    _scan_threads_ended()
    assert 1 <= len(files) < 3 and all(f.closed for f in files)
    assert uses[-1] == ("close", "blaze-parquet_decode")
