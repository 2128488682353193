"""Spark-interop IPC codecs (zstd/lz4 frames) + the FileSystem seam.

≙ reference common/ipc_compression.rs:30-335 (zstd level 1 / LZ4 frame
per spark.io.compression.codec) and datafusion-ext-commons/src/
hadoop_fs.rs:26-160 (all scan IO through registered FS callbacks).
"""

import io
import os

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu import conf
from blaze_tpu.io.ipc_compression import (
    compress_frame,
    decompress_frame,
    lz4_frame_compress,
    lz4_frame_decompress,
)

PAYLOAD = (b"the quick brown fox " * 500) + bytes(range(256)) * 10


@pytest.mark.parametrize("codec", ["zlib", "zstd", "lz4", "raw"])
def test_frame_roundtrip(codec):
    frame = compress_frame(PAYLOAD, codec)
    assert decompress_frame(frame) == PAYLOAD


def test_zstd_interop_with_zstandard_frames():
    """Frames from any standard zstd writer decode (the reference's
    zstd::Encoder emits the same format)."""
    import struct

    import zstandard

    comp = zstandard.ZstdCompressor(level=1).compress(PAYLOAD)
    frame = struct.pack("<IB", len(comp), 2) + comp
    assert decompress_frame(frame) == PAYLOAD


def test_lz4_frame_interop_with_pyarrow():
    """Our LZ4 frames decode with pyarrow's LZ4 frame codec, and
    pyarrow-compressed frames decode with ours — the reference's
    lz4_flex frames are the same format."""
    codec = pa.Codec("lz4")
    # ours -> pyarrow
    ours = lz4_frame_compress(PAYLOAD)
    assert codec.decompress(ours, decompressed_size=len(PAYLOAD)).to_pybytes() == PAYLOAD
    # pyarrow -> ours (compressed blocks, possibly linked)
    theirs = codec.compress(PAYLOAD).to_pybytes()
    assert lz4_frame_decompress(theirs) == PAYLOAD


def test_shuffle_file_with_zstd_codec(tmp_path):
    """End-to-end: shuffle .data files written under
    spark.io.compression.codec=zstd read back correctly."""
    from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.parallel.exchange import NativeShuffleExchangeExec
    from blaze_tpu.parallel.shuffle import HashPartitioning
    from blaze_tpu.runtime.context import TaskContext
    from blaze_tpu.schema import DataType, Field, Schema

    old = conf.IO_COMPRESSION_CODEC.get()
    try:
        conf.IO_COMPRESSION_CODEC.set("zstd")
        schema = Schema([Field("k", DataType.int64()), Field("v", DataType.string(8))])
        data = {"k": list(range(64)), "v": [f"s{i}" for i in range(64)]}
        b = batch_from_pydict(data, schema)
        ex = NativeShuffleExchangeExec(MemoryScanExec([[b]], schema), HashPartitioning([col("k")], 4))
        rows = []
        for p in range(4):
            for ob in ex.execute(p, TaskContext(p, 4)):
                d = batch_to_pydict(ob)
                rows += list(zip(d["k"], d["v"]))
        assert sorted(rows) == sorted(zip(data["k"], data["v"]))
    finally:
        conf.IO_COMPRESSION_CODEC.set(old)


# ------------------------------------------------------------- FS seam

def test_local_fs_and_scheme_resolution(tmp_path):
    from blaze_tpu.io.fs import get_fs

    p = tmp_path / "x.bin"
    fs = get_fs(str(p))
    with fs.create(str(p)) as f:
        f.write(b"hello")
    assert fs.exists(str(p)) and fs.size(str(p)) == 5
    with fs.open(f"file://{p}") as f:
        assert f.read() == b"hello"


def test_callback_fs_parquet_scan(tmp_path):
    """A parquet scan through a registered callback FS — the
    positioned-read contract of hadoop_fs.rs (reads cross the callback
    per seek window, no local path ever opened)."""
    import pyarrow.parquet as papq

    from blaze_tpu.batch import batch_to_pydict, concat_batches
    from blaze_tpu.io.fs import CallbackFileSystem, register_fs, unregister_fs
    from blaze_tpu.ops import ParquetScanExec
    from blaze_tpu.runtime.context import TaskContext
    from blaze_tpu.schema import DataType, Field, Schema

    local = tmp_path / "remote.parquet"
    table = pa.table({"x": pa.array(list(range(100)), pa.int64())})
    papq.write_table(table, local, compression="snappy")
    blob = local.read_bytes()

    calls = {"n": 0}

    def open_cb(path):
        assert path.startswith("mockfs://")

        def pread(pos, n):
            calls["n"] += 1
            return blob[pos : pos + n]

        return pread, len(blob)

    register_fs("mockfs", CallbackFileSystem(open_cb))
    try:
        scan = ParquetScanExec([["mockfs://bucket/remote.parquet"]],
                               Schema([Field("x", DataType.int64())]))
        out = list(scan.execute(0, TaskContext(0, 1)))
        d = batch_to_pydict(concat_batches(out))
        assert d["x"] == list(range(100))
        # footer + data crossed the callback: in ONE read, the file being no longer
        # than io/parquet.WHOLE_FILE_BYTES (PR 40)
        assert calls["n"] == 1
    finally:
        unregister_fs("mockfs")


def test_callback_fs_orc_scan(tmp_path):
    from pyarrow import orc as paorc

    from blaze_tpu.batch import batch_to_pydict, concat_batches
    from blaze_tpu.io.fs import CallbackFileSystem, register_fs, unregister_fs
    from blaze_tpu.ops.orc_scan import OrcScanExec
    from blaze_tpu.runtime.context import TaskContext
    from blaze_tpu.schema import DataType, Field, Schema

    local = tmp_path / "remote.orc"
    table = pa.table({"x": pa.array(list(range(77)), pa.int64())})
    paorc.write_table(table, local, compression="zlib")
    blob = local.read_bytes()

    def open_cb(path):
        def pread(pos, n):
            return blob[pos : pos + n]

        return pread, len(blob)

    register_fs("mockfs", CallbackFileSystem(open_cb))
    try:
        scan = OrcScanExec([["mockfs://b/remote.orc"]], Schema([Field("x", DataType.int64())]))
        out = list(scan.execute(0, TaskContext(0, 1)))
        d = batch_to_pydict(concat_batches(out))
        assert d["x"] == list(range(77))
    finally:
        unregister_fs("mockfs")
