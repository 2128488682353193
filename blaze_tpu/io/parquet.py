"""Self-contained Parquet subset writer/reader.

≙ the file-format half of the reference's ParquetExec/ParquetSinkExec
(parquet_exec.rs:65-418, parquet_sink_exec.rs) — implemented from the
public parquet-format spec.  The file format is read and written here,
and pyarrow (in the image, optional) is used where it imports: its
snappy codec for the page decoder (snappy_decompress; ZSTD pages go to
``zstandard``), and its C++ column reader for a scan's row groups
(read_row_group), held to the page decoder's arrays bit for bit:

- written files: PAR1 magic, one DATA_PAGE v1 per column chunk per row
  group, PLAIN encoding, RLE/bit-packed definition levels for OPTIONAL
  columns, UNCOMPRESSED / GZIP / SNAPPY (Spark's default, pure-python
  LZ77) / ZSTD / LZ4_RAW pages, thrift-compact FileMetaData with
  min/max statistics per chunk.
- reader: decodes that subset and what parquet-mr / pyarrow write with
  the same encodings (dictionary pages, RLE/bit-packed indices, v1 and
  v2 data pages) by array operations over a whole page — no numpy call
  a run, no Python step a bit or byte — and prunes row groups with the
  pushed-down predicate over chunk statistics — the row-group
  granularity of the reference's page filtering
  (spark.blaze.parquet.enable.pageFiltering).
- read_row_group: one row group's chunks fetched, decompressed and
  decoded by Arrow's reader in ONE call that releases the GIL, each
  column then converted whole to read_column_chunk's arrays; a chunk
  Arrow's reader does not take (INT96, FIXED_LEN_BYTE_ARRAY, a file
  type other than the requested one), and every chunk where pyarrow
  does not import, goes through read_column_chunk — ≙ the reference's
  ParquetExec decoding through the arrow-rs ``parquet`` crate.
- read_row_group_pieces: the same row group as a stream — where Arrow
  takes every chunk, its reader decodes page by page as each piece of a
  batch's rows is pulled and the piece is converted by the same code
  into arrays of its own capacity, so a scan hands a batch on after a
  sixteenth of the decode; any other row group is read_row_group's, as
  one piece.

Physical mapping: BOOLEAN (bit-packed) <- bool; INT32 <- int8/16/32 +
DATE; INT64 <- int64/timestamp/decimal(<=18) [ConvertedType DECIMAL];
FLOAT/DOUBLE; BYTE_ARRAY(UTF8) <- string.
"""

from __future__ import annotations

import collections
import functools
import gzip
import os
import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..schema import DataType, Field, Schema, TypeKind
from .thrift_compact import (
    CT_BINARY, CT_I32, CT_I64, CT_STRUCT, CompactReader, CompactWriter,
)

try:
    # with this module, on the thread that imports it, never first on a
    # scan's producer thread: a thread that imports pyarrow and ends with
    # its task takes Arrow's default memory pool with it, and the next
    # thread to read a file through Arrow segfaults (pyarrow 25.0.0)
    import pyarrow.parquet as _pyarrow_parquet
except ImportError:
    _pyarrow_parquet = None

MAGIC = b"PAR1"

# parquet physical types
T_BOOLEAN, T_INT32, T_INT64, T_INT96, T_FLOAT, T_DOUBLE, T_BYTE_ARRAY, T_FLBA = range(8)
# converted types
CONV_UTF8, CONV_DECIMAL, CONV_DATE, CONV_TS_MICROS = 0, 5, 6, 10
# codecs (parquet CompressionCodec enum)
CODEC_UNCOMPRESSED, CODEC_SNAPPY, CODEC_GZIP = 0, 1, 2
CODEC_LZO, CODEC_BROTLI, CODEC_LZ4, CODEC_ZSTD, CODEC_LZ4_RAW = 3, 4, 5, 6, 7
# page types
PAGE_DATA, PAGE_INDEX, PAGE_DICT, PAGE_DATA_V2 = 0, 1, 2, 3
# encodings
ENC_PLAIN, ENC_PLAIN_DICT, ENC_RLE, ENC_RLE_DICT = 0, 2, 3, 8


@functools.lru_cache(maxsize=None)
def _snappy_library():
    """pyarrow's snappy codec where pyarrow imports, else None: the one
    place that chooses (Parquet pages and ORC chunks both come here)."""
    if _pyarrow_parquet is None:
        return None
    import pyarrow

    return pyarrow.Codec("snappy") if pyarrow.Codec.is_available("snappy") else None


def snappy_decompress(src: bytes) -> bytes:
    """Snappy raw-block decode: the codec library's where one imports,
    the pure-python _snappy_decompress where none does."""
    codec = _snappy_library()
    if codec is None:
        return _snappy_decompress(src)
    total, _ = _uvarint(src, 0)  # the preamble: the length the library asks for
    return codec.decompress(src, total, asbytes=True)


def _uvarint(buf: bytes, pos: int) -> Tuple[int, int]:
    """(value, position after it) of the LEB128 varint at ``pos``."""
    value = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _snappy_decompress(src: bytes) -> bytes:
    """Pure-python snappy raw-block decode, snappy_decompress's fallback:
    one Python step a tag, never one a byte.  (Appending to the output
    is the fastest form CPython has for the short copies real pages are
    made of: a presized output written through memoryview slices
    measured a third slower.)"""
    total, pos = _uvarint(src, 0)  # the uncompressed length
    out = bytearray()
    n = len(src)
    while pos < n:
        tag = src[pos]
        pos += 1
        t = tag & 3
        if t == 0:  # literal
            ln = (tag >> 2) + 1
            if ln > 60:
                extra = ln - 60
                ln = int.from_bytes(src[pos : pos + extra], "little") + 1
                pos += extra
            out += src[pos : pos + ln]
            pos += ln
            continue
        if t == 1:
            ln = ((tag >> 2) & 7) + 4
            off = ((tag >> 5) << 8) | src[pos]
            pos += 1
        elif t == 2:
            ln = (tag >> 2) + 1
            off = src[pos] | (src[pos + 1] << 8)
            pos += 2
        else:
            ln = (tag >> 2) + 1
            off = int.from_bytes(src[pos : pos + 4], "little")
            pos += 4
        start = len(out) - off
        if start < 0 or off == 0:
            raise ValueError(f"snappy: copy offset {off} at output byte {len(out)}")
        if off >= ln:
            out += out[start : start + ln]
        else:  # overlapping copy: the last `off` bytes, repeated
            out += (out[start:] * (ln // off + 1))[:ln]
    if len(out) != total:
        raise ValueError(f"snappy: decoded {len(out)} bytes, expected {total}")
    return bytes(out)


def _snappy_compress(src: bytes) -> bytes:
    """Pure-python snappy raw-block encode: greedy LZ77 over a 4-byte
    hash table, the inverse of _snappy_decompress (differential-tested
    against it and against the ORC C++ reader via pyarrow).  Callers
    pass bounded chunks (ORC framing: 64 KiB, parquet pages ~1 MiB), so
    2-byte literal lengths and 2-byte copy offsets always suffice; the
    4-byte copy form is still emitted for completeness when an offset
    exceeds 64 KiB."""
    n = len(src)
    out = bytearray()
    v = n
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)

    def emit_literal(lo: int, hi: int) -> None:
        ln = hi - lo
        while ln > 0:
            take = min(ln, 1 << 16)
            if take <= 60:
                out.append((take - 1) << 2)
            elif take <= 0x100:
                out.append(60 << 2)
                out.append(take - 1)
            else:
                out.append(61 << 2)
                out.extend((take - 1).to_bytes(2, "little"))
            out.extend(src[lo : lo + take])
            lo += take
            ln -= take

    def emit_copy(off: int, ln: int) -> None:
        while ln > 0:
            take = min(ln, 64)
            if 4 <= take <= 11 and off < 2048:
                out.append(1 | ((take - 4) << 2) | ((off >> 8) << 5))
                out.append(off & 0xFF)
            elif off <= 0xFFFF:
                out.append(2 | ((take - 1) << 2))
                out.extend(off.to_bytes(2, "little"))
            else:
                out.append(3 | ((take - 1) << 2))
                out.extend(off.to_bytes(4, "little"))
            ln -= take

    table: dict = {}
    i = 0
    lit = 0
    limit = n - 3
    while i < limit:
        key = src[i : i + 4]
        j = table.get(key)
        table[key] = i
        if j is None:
            i += 1
            continue
        # extend the match (source-vs-source compare is exact: emitted
        # output always equals the src prefix, overlap included)
        L = 4
        max_l = n - i
        while L < max_l:
            step = min(512, max_l - L)
            if src[i + L : i + L + step] == src[j + L : j + L + step]:
                L += step
                continue
            while L < max_l and src[i + L] == src[j + L]:
                L += 1
            break
        emit_literal(lit, i)
        emit_copy(i - j, L)
        # index the match tail so immediately-following repeats hit
        if i + L < limit:
            table[src[i + L - 1 : i + L + 3]] = i + L - 1
        i += L
        lit = i
    emit_literal(lit, n)
    return bytes(out)


def _lz4_block_decompress(src: bytes) -> bytes:
    """LZ4 raw-block decode (canonical impl in io.ipc_compression)."""
    from .ipc_compression import lz4_block_decompress

    return lz4_block_decompress(src)


def _decompress(payload: bytes, codec: int, uncompressed_size: int) -> bytes:
    if codec == CODEC_UNCOMPRESSED:
        return payload
    if codec == CODEC_GZIP:
        return gzip.decompress(payload)
    if codec == CODEC_SNAPPY:
        return snappy_decompress(payload)
    if codec == CODEC_ZSTD:
        import zstandard

        return zstandard.ZstdDecompressor().decompress(
            payload, max_output_size=max(uncompressed_size, 1)
        )
    if codec == CODEC_LZ4_RAW:
        return _lz4_block_decompress(payload)
    if codec == CODEC_LZ4:
        # hadoop framing: [u32be total][u32be block_len][block]...
        out = bytearray()
        pos = 0
        while pos < len(payload):
            total = int.from_bytes(payload[pos : pos + 4], "big")
            pos += 4
            got = 0
            while got < total:
                blen = int.from_bytes(payload[pos : pos + 4], "big")
                pos += 4
                piece = _lz4_block_decompress(payload[pos : pos + blen])
                pos += blen
                got += len(piece)
                out += piece
        return bytes(out)
    raise NotImplementedError(f"parquet codec {codec}")


def _rle_bp_decode(data: bytes, bit_width: int, num_values: int) -> np.ndarray:
    """General RLE / bit-packed hybrid decode -> int32 values.

    One pass over the run headers records where each run lies; the
    bit-packed payloads of the whole buffer are then unpacked together
    and the RLE runs filled by one ``np.repeat``: no numpy call a run."""
    if bit_width == 0:
        return np.zeros(num_values, np.int32)
    mask = (1 << bit_width) - 1
    byte_w = (bit_width + 7) // 8
    counts: List[int] = []  # values a run holds, in run order
    values: List[int] = []  # an RLE run's value; -1 for a bit-packed run
    payloads = []           # the bit-packed runs' bytes, in run order
    pos = 0
    filled = 0
    n = len(data)
    while filled < num_values and pos < n:
        hdr = data[pos]
        pos += 1
        if hdr & 0x80:  # a varint of more than one byte
            hdr &= 0x7F
            shift = 7
            while True:
                b = data[pos]
                pos += 1
                hdr |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        count = hdr >> 1
        if hdr & 1:  # bit-packed groups of 8
            nbytes = count * bit_width
            payloads.append(data[pos : pos + nbytes])
            pos += nbytes
            count *= 8
            values.append(-1)
        else:
            values.append(int.from_bytes(data[pos : pos + byte_w], "little") & mask)
            pos += byte_w
            count = min(count, num_values - filled)  # a corrupt header sizes nothing
        counts.append(count)
        filled += count
    if len(payloads) == len(counts):  # no RLE run: the payloads are the values
        out = _unpack_groups(payloads, bit_width)
    else:
        runs = np.array(values, np.int64)
        out = np.repeat(runs.astype(np.int32), counts)
        if payloads:
            out[np.repeat(runs < 0, counts)] = _unpack_groups(payloads, bit_width)
    if filled < num_values:  # the buffer ended early: zeros, as for rows never written
        out = np.concatenate([out, np.zeros(num_values - filled, np.int32)])
    return out[:num_values]


def _unpack_groups(payloads: list, bit_width: int) -> np.ndarray:
    """Bit-packed runs' bytes (groups of 8 values, ``bit_width`` bytes a
    group, LSB first) -> int32, eight values a group, the runs in order.
    Value ``j`` of every group starts ``j * bit_width`` bits into it: one
    little-endian word read at that byte of each group, shifted and
    masked — eight strided array expressions however many groups."""
    word = np.dtype("<u8" if bit_width > 24 else "<u4")
    # the zero tail: a last group cut short reads zeros, and the last
    # group's last word stays inside the buffer
    tail = bit_width + word.itemsize
    buf = b"".join([*payloads, bytes(tail)])
    groups = -(-(len(buf) - tail) // bit_width)
    out = np.empty((groups, 8), np.int32)
    mask = word.type((1 << bit_width) - 1)
    for j in range(8):
        byte, shift = divmod(j * bit_width, 8)
        words = np.ndarray((groups,), word, buf, offset=byte, strides=(bit_width,))
        out[:, j] = (words >> word.type(shift)) & mask
    return out.reshape(-1)


def _physical(dtype: DataType) -> int:
    k = dtype.kind
    if k == TypeKind.BOOL:
        return T_BOOLEAN
    if k in (TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.DATE32):
        return T_INT32
    if k in (TypeKind.INT64, TypeKind.TIMESTAMP, TypeKind.DECIMAL):
        return T_INT64
    if k == TypeKind.FLOAT32:
        return T_FLOAT
    if k == TypeKind.FLOAT64:
        return T_DOUBLE
    if dtype.is_string:
        return T_BYTE_ARRAY
    raise NotImplementedError(f"parquet type for {dtype!r}")


def _rle_encode_defs(validity: np.ndarray) -> bytes:
    """RLE runs of the 1-bit definition levels (bit width 1)."""
    out = bytearray()
    n = len(validity)
    i = 0
    while i < n:
        v = validity[i]
        j = i
        while j < n and validity[j] == v:
            j += 1
        run = j - i
        # RLE run: varint(count << 1), then the value in 1 byte (bit width 1)
        hdr = run << 1
        while True:
            byte = hdr & 0x7F
            hdr >>= 7
            if hdr:
                out.append(byte | 0x80)
            else:
                out.append(byte)
                break
        out.append(1 if v else 0)
        i = j
    return bytes(out)


def _rle_decode_defs(data: bytes, num_values: int) -> np.ndarray:
    """1-bit definition levels -> validity: the hybrid decode at width 1."""
    return _rle_bp_decode(data, 1, num_values).astype(np.bool_)


def _plain_encode(dtype: DataType, data: np.ndarray, validity: np.ndarray,
                  lengths: Optional[np.ndarray]) -> bytes:
    """PLAIN values for non-null rows only."""
    phys = _physical(dtype)
    nn = validity.astype(bool)
    if phys == T_BOOLEAN:
        vals = data[nn].astype(np.bool_)
        return np.packbits(vals, bitorder="little").tobytes()
    if phys == T_INT32:
        return data[nn].astype("<i4").tobytes()
    if phys == T_INT64:
        return data[nn].astype("<i8").tobytes()
    if phys == T_FLOAT:
        return data[nn].astype("<f4").tobytes()
    if phys == T_DOUBLE:
        return data[nn].astype("<f8").tobytes()
    # byte array: u32 length + bytes per value
    out = bytearray()
    idx = np.nonzero(nn)[0]
    for i in idx:
        ln = int(lengths[i])
        out += struct.pack("<I", ln)
        out += data[i, :ln].tobytes()
    return bytes(out)


def _flba_to_int64(raw: bytes, count: int, type_length: int) -> np.ndarray:
    """FIXED_LEN_BYTE_ARRAY big-endian two's-complement -> int64 (the
    Spark/pyarrow decimal physical encoding)."""
    out = np.zeros(count, np.int64)
    for i in range(count):
        b = raw[i * type_length : (i + 1) * type_length]
        out[i] = int.from_bytes(b, "big", signed=True)
    return out


def _plain_decode(phys: int, raw: bytes, count: int, width: int, type_length: int = 0):
    """PLAIN decode of ``count`` values by the FILE's physical type — a
    dictionary page's entries or a data page's non-null values, densely;
    the caller adapts to the requested logical dtype (schema adaption).
    Byte arrays come back as (data (count, width), lengths)."""
    if phys == T_BOOLEAN:
        return np.unpackbits(np.frombuffer(raw, np.uint8), bitorder="little")[:count].astype(np.bool_)
    np_map = {T_INT32: "<i4", T_INT64: "<i8", T_FLOAT: "<f4", T_DOUBLE: "<f8"}
    if phys in np_map:
        return np.frombuffer(raw, np_map[phys], count=count)
    if phys == T_FLBA:
        return _flba_to_int64(raw, count, type_length)
    if phys == T_INT96:
        # legacy Spark timestamps: 8B nanos-of-day LE + 4B julian day
        out = np.zeros(count, np.int64)
        for i in range(count):
            nanos = int.from_bytes(raw[i * 12 : i * 12 + 8], "little")
            julian = int.from_bytes(raw[i * 12 + 8 : i * 12 + 12], "little")
            out[i] = (julian - 2440588) * 86_400_000_000 + nanos // 1000
        return out
    data = np.zeros((count, width), np.uint8)
    lengths = np.zeros(count, np.int32)
    pos = 0
    for i in range(count):
        (ln,) = struct.unpack_from("<I", raw, pos)
        pos += 4
        lengths[i] = min(ln, width)
        data[i, : lengths[i]] = np.frombuffer(raw, np.uint8, count=lengths[i], offset=pos)
        pos += ln
    return data, lengths


def _stat_bytes(dtype: DataType, v) -> bytes:
    phys = _physical(dtype)
    if phys == T_INT32:
        return struct.pack("<i", int(v))
    if phys == T_INT64:
        return struct.pack("<q", int(v))
    if phys == T_FLOAT:
        return struct.pack("<f", float(v))
    if phys == T_DOUBLE:
        return struct.pack("<d", float(v))
    if phys == T_BOOLEAN:
        return struct.pack("<?", bool(v))
    return bytes(v)  # byte array: raw bytes


_STAT_INT = {T_INT32: struct.Struct("<i"), T_INT64: struct.Struct("<q")}


def chunk_bounds(chunk: "ChunkMeta") -> Optional[Tuple]:
    """A chunk's ``(min, max)`` as the values its physical type orders:
    ints for INT32 and INT64, an int for a FIXED_LEN_BYTE_ARRAY (a
    decimal's big-endian two's complement), bytes for a BYTE_ARRAY
    (ordered byte by byte, unsigned).  None where the chunk states none,
    or its type is one whose statistics prune nothing here: a float's
    leave its NaNs out, a boolean and an INT96 are not compared."""
    lo, hi = chunk.min_value, chunk.max_value
    if lo is None or hi is None:
        return None
    if chunk.phys in _STAT_INT:
        s = _STAT_INT[chunk.phys]
        if len(lo) != s.size or len(hi) != s.size:
            return None
        return s.unpack(lo)[0], s.unpack(hi)[0]
    if chunk.phys == T_FLBA:
        return int.from_bytes(lo, "big", signed=True), int.from_bytes(hi, "big", signed=True)
    if chunk.phys == T_BYTE_ARRAY:
        return bytes(lo), bytes(hi)
    return None


# ------------------------------------------------------------------ writer

def write_parquet(
    path: str,
    schema: Schema,
    columns: Dict[str, Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]],
    row_group_rows: int = 1 << 20,
    codec: int = CODEC_GZIP,
):
    """columns: name -> (data, validity|None, lengths|None) host arrays."""
    from .fs import get_fs

    n = next(iter(columns.values()))[0].shape[0]
    f = get_fs(path).create(path)
    f.write(MAGIC)
    row_groups: List[dict] = []
    for rg_start in range(0, max(n, 1), row_group_rows):
        rg_end = min(rg_start + row_group_rows, n)
        rg_rows = rg_end - rg_start
        chunks = []
        total_bytes = 0
        for fld in schema.fields:
            data, validity, lengths = columns[fld.name]
            v = (
                validity[rg_start:rg_end].astype(bool)
                if validity is not None
                else np.ones(rg_rows, bool)
            )
            d = data[rg_start:rg_end]
            l = lengths[rg_start:rg_end] if lengths is not None else None
            defs = _rle_encode_defs(v)
            values = _plain_encode(fld.dtype, d, v, l)
            payload = struct.pack("<I", len(defs)) + defs + values
            if codec == CODEC_GZIP:
                comp = gzip.compress(payload, 1)
            elif codec == CODEC_SNAPPY:  # Spark's parquet default codec
                comp = _snappy_compress(payload)
            elif codec == CODEC_ZSTD:
                import zstandard

                comp = zstandard.ZstdCompressor().compress(payload)
            elif codec == CODEC_LZ4_RAW:
                from .ipc_compression import lz4_block_compress

                comp = lz4_block_compress(payload)
            elif codec == CODEC_UNCOMPRESSED:
                comp = payload
            else:
                raise NotImplementedError(f"parquet writer codec {codec}")
            # min/max over non-null rows
            stats = None
            if v.any():
                if fld.dtype.is_string:
                    vals = [d[i, : l[i]].tobytes() for i in np.nonzero(v)[0]]
                    stats = (min(vals), max(vals))
                else:
                    nn = d[v]
                    stats = (nn.min(), nn.max())
            ph = CompactWriter()
            ph.write_i(1, 0)                        # type = DATA_PAGE
            ph.write_i(2, len(payload))             # uncompressed size
            ph.write_i(3, len(comp))                # compressed size
            ph.begin_struct(5)                      # data_page_header
            ph.write_i(1, rg_rows)                  # num_values
            ph.write_i(2, 0)                        # encoding PLAIN
            ph.write_i(3, 3)                        # def levels RLE
            ph.write_i(4, 3)                        # rep levels RLE
            ph.end_struct()
            ph.buf.append(0)                        # end PageHeader struct
            header = ph.getvalue()
            offset = f.tell()
            f.write(header)
            f.write(comp)
            chunk_bytes = len(header) + len(comp)
            total_bytes += chunk_bytes
            chunks.append(
                dict(
                    field=fld, offset=offset, num_values=rg_rows,
                    total_comp=chunk_bytes, total_uncomp=len(header) + len(payload),
                    stats=stats, null_count=int((~v).sum()), codec=codec,
                )
            )
        row_groups.append(dict(chunks=chunks, rows=rg_rows, bytes=total_bytes))
        if n == 0:
            break

    # FileMetaData
    w = CompactWriter()
    w.write_i(1, 1)  # version
    # schema: root element + one per field
    w.begin_list(2, CT_STRUCT, len(schema.fields) + 1)
    w.list_elem_struct_begin()
    _w_string(w, 4, "schema")
    w.write_i(5, len(schema.fields))  # num_children
    w.list_elem_struct_end()
    for fld in schema.fields:
        w.list_elem_struct_begin()
        w.write_i(1, _physical(fld.dtype))
        w.write_i(3, 1)  # always OPTIONAL: def levels are always written
        _w_string(w, 4, fld.name)
        conv = None
        if fld.dtype.kind == TypeKind.STRING:
            conv = CONV_UTF8
        elif fld.dtype.is_decimal:
            conv = CONV_DECIMAL
        elif fld.dtype.kind == TypeKind.DATE32:
            conv = CONV_DATE
        elif fld.dtype.kind == TypeKind.TIMESTAMP:
            conv = CONV_TS_MICROS
        if conv is not None:
            w.write_i(6, conv)
        if fld.dtype.is_decimal:
            w.write_i(7, fld.dtype.scale)
            w.write_i(8, fld.dtype.precision)
        w.list_elem_struct_end()
    w.write_i64(3, n)  # num_rows
    w.begin_list(4, CT_STRUCT, len(row_groups))
    for rg in row_groups:
        w.list_elem_struct_begin()
        w.begin_list(1, CT_STRUCT, len(rg["chunks"]))
        for ch in rg["chunks"]:
            w.list_elem_struct_begin()
            w.write_i64(2, ch["offset"])  # file_offset
            w.begin_struct(3)             # ColumnMetaData
            w.write_i(1, _physical(ch["field"].dtype))
            w.begin_list(2, CT_I32, 2)
            w.list_elem_varint(0)  # PLAIN
            w.list_elem_varint(3)  # RLE
            w.begin_list(3, CT_BINARY, 1)
            w.list_elem_binary(ch["field"].name.encode())
            w.write_i(4, ch["codec"])
            w.write_i64(5, ch["num_values"])
            w.write_i64(6, ch["total_uncomp"])
            w.write_i64(7, ch["total_comp"])
            w.write_i64(9, ch["offset"])  # data_page_offset
            if ch["stats"] is not None:
                w.begin_struct(12)
                w.write_i(3, ch["null_count"], CT_I64)  # null_count: i64 per spec
                # use modern min_value/max_value fields
                w.write_binary(5, _stat_bytes(ch["field"].dtype, ch["stats"][1]))
                w.write_binary(6, _stat_bytes(ch["field"].dtype, ch["stats"][0]))
                w.end_struct()
            w.end_struct()
            w.list_elem_struct_end()
        w.write_i64(2, rg["bytes"])
        w.write_i64(3, rg["rows"])
        w.list_elem_struct_end()
    _w_string(w, 6, "blaze-tpu parquet 0.1")
    # column_orders: every column's statistics in its type's own order
    # (TypeDefinedOrder), without which a reader leaves min_value and
    # max_value unread
    w.begin_list(7, CT_STRUCT, len(schema.fields))
    for _ in schema.fields:
        w.list_elem_struct_begin()
        w.begin_struct(1)
        w.end_struct()
        w.list_elem_struct_end()
    w.buf.append(0)  # FileMetaData stop

    meta = w.getvalue()
    f.write(meta)
    f.write(struct.pack("<I", len(meta)))
    f.write(MAGIC)
    f.close()


def _w_string(w: CompactWriter, fid: int, s: str):
    w.write_binary(fid, s.encode("utf-8"))


# ------------------------------------------------------------------ reader

@dataclass
class ChunkMeta:
    name: str
    phys: int
    codec: int
    num_values: int
    offset: int                      # first page (dict page if present)
    total_comp: int
    min_value: Optional[bytes] = None
    max_value: Optional[bytes] = None
    null_count: Optional[int] = None
    max_def: int = 1                 # 0 = REQUIRED column (no def levels)
    type_length: int = 0             # FLBA byte width


@dataclass
class RowGroupMeta:
    rows: int
    chunks: Dict[str, ChunkMeta]      # in the file's column order
    total_comp: int = 0               # compressed bytes of all its chunks
    index: int = 0                    # its place among the file's row groups

    @property
    def midpoint(self) -> int:
        """The byte by which parquet-mr gives the row group to one
        split of its file (``filterFileMetaDataByMidpoint``): the first
        chunk's first page plus half the compressed size."""
        first = next(iter(self.chunks.values())).offset if self.chunks else 0
        return first + self.total_comp // 2


@dataclass
class ParquetFileMeta:
    num_rows: int
    schema_elements: List[dict]
    row_groups: List[RowGroupMeta]


def read_metadata(path: str) -> ParquetFileMeta:
    from .fs import get_fs

    with get_fs(path).open(path) as f:
        f.seek(-8, os.SEEK_END)
        tail = f.read(8)
        assert tail[4:] == MAGIC, "not a parquet file"
        meta_len = struct.unpack("<I", tail[:4])[0]
        f.seek(-8 - meta_len, os.SEEK_END)
        meta = f.read(meta_len)
    r = CompactReader(meta)
    fm = r.read_struct()
    schema_elems = [dict(e) for e in fm.get(2, [])]
    # leaf nullability + FLBA width by name
    repetition: Dict[str, int] = {}
    type_lengths: Dict[str, int] = {}
    for e in schema_elems:
        if e.get(5):  # has children -> group node (root)
            continue
        nm = e.get(4, b"?")
        nm = nm.decode() if isinstance(nm, (bytes, bytearray)) else str(nm)
        repetition[nm] = e.get(3, 1)
        type_lengths[nm] = e.get(2, 0)
    rgs: List[RowGroupMeta] = []
    for rg in fm.get(4, []):
        chunks: Dict[str, ChunkMeta] = {}
        for ch in rg.get(1, []):
            md = ch.get(3, {})
            name = b"/".join(md.get(3, [b"?"])).decode()
            stats = md.get(12, {})
            data_off = md.get(9, md.get(2, ch.get(2, 0)))
            dict_off = md.get(11)  # dictionary_page_offset
            first = min(data_off, dict_off) if dict_off else data_off
            nc = stats.get(3)  # null_count: i64 (spec); old subset files: 8B binary
            if isinstance(nc, (bytes, bytearray)) and len(nc) == 8:
                nc = struct.unpack("<q", bytes(nc))[0]
            elif not isinstance(nc, int):
                nc = None
            # min/max: prefer modern min_value/max_value (5/6), fall
            # back to deprecated max/min (1/2), which writers ordered as
            # signed values: right for the integer types alone
            phys = md.get(1, 0)
            mx, mn = stats.get(5), stats.get(6)
            if mx is None and mn is None and phys in _STAT_INT:
                mx, mn = stats.get(1), stats.get(2)
            chunks[name] = ChunkMeta(
                name=name,
                phys=phys,
                codec=md.get(4, 0),
                num_values=md.get(5, 0),
                offset=first,
                total_comp=md.get(7, 0),
                min_value=bytes(mn) if mn is not None else None,
                max_value=bytes(mx) if mx is not None else None,
                null_count=nc,
                max_def=0 if repetition.get(name) == 0 else 1,
                type_length=type_lengths.get(name, 0),
            )
        total_comp = rg.get(6)  # total_compressed_size: optional in the format
        if total_comp is None:
            total_comp = sum(c.total_comp for c in chunks.values())
        rgs.append(RowGroupMeta(rows=rg.get(3, 0), chunks=chunks, total_comp=total_comp,
                                index=len(rgs)))
    return ParquetFileMeta(num_rows=fm.get(3, 0), schema_elements=schema_elems, row_groups=rgs)


def _python_codec(codec: int) -> bool:
    """Whether this process decompresses ``codec``'s pages in pure python."""
    return codec in (CODEC_LZ4, CODEC_LZ4_RAW) or (
        codec == CODEC_SNAPPY and _snappy_library() is None)


def read_column_chunk(path: str, chunk: ChunkMeta, dtype: DataType,
                      capacity: Optional[int] = None,
                      tally: Optional[collections.Counter] = None):
    """Decode a full column chunk: every page (v1/v2), PLAIN or
    dictionary encodings, all supported codecs.  Returns
    (data, validity, lengths|None) numpy arrays of ``capacity`` rows
    (chunk.num_values where none is given); rows past chunk.num_values
    are zero and invalid.  ``tally`` counts what was decoded: ``pages``
    (data and dictionary pages) and ``pages_python_codec`` (those a
    pure-python decoder decompressed).  ≙ the arrow-rs page machinery
    behind parquet_exec.rs:65-418."""
    from .fs import get_fs

    with get_fs(path).open(path) as f:
        f.seek(chunk.offset)
        blob = f.read(chunk.total_comp if chunk.total_comp else None)

    n_total = chunk.num_values
    cap = n_total if capacity is None else capacity
    if cap < n_total:
        raise ValueError(f"capacity {cap} under the chunk's {n_total} values")
    width = dtype.string_width if dtype.is_string else 0
    validity = np.zeros(cap, np.bool_)
    if dtype.is_string:
        data = np.zeros((cap, width), np.uint8)
        lengths = np.zeros(cap, np.int32)
    else:
        data = np.zeros(cap, dtype.np_dtype)
        lengths = None
    dict_table = None  # (values[, lengths]) from the dictionary page
    python_codec = _python_codec(chunk.codec)

    def decompress(raw, uncompressed_size: int):
        if tally is not None:
            tally["pages_python_codec"] += python_codec
        return _decompress(raw, chunk.codec, uncompressed_size)

    def emit_values(encoding: int, values, page_valid: Optional[np.ndarray], nv: int, row0: int):
        """One data page's values into rows row0..row0+nv; ``page_valid``
        None = every row valid (the values go straight to their rows)."""
        sl = slice(row0, row0 + nv)
        if page_valid is None:
            validity[sl] = True
            nn = nv
            where = ...
        else:
            validity[sl] = page_valid
            nn = int(page_valid.sum())
            where = page_valid
            if nn == 0:
                return
        if encoding in (ENC_PLAIN_DICT, ENC_RLE_DICT):
            idx = _rle_bp_decode(values[1:], values[0], nn)
            if dtype.is_string:
                dvals, dlens = dict_table
                data[sl][where] = dvals[idx]
                lengths[sl][where] = dlens[idx]
            else:
                data[sl][where] = dict_table[idx]
        elif encoding == ENC_RLE and chunk.phys == T_BOOLEAN:
            # v2 booleans: u32 length + RLE/bit-packed hybrid, width 1
            (rl,) = struct.unpack_from("<I", values, 0)
            data[sl][where] = _rle_bp_decode(values[4 : 4 + rl], 1, nn)
        elif encoding != ENC_PLAIN:
            # gated, not silently wrong: DELTA_* / BYTE_STREAM_SPLIT
            raise NotImplementedError(f"parquet page encoding {encoding}")
        elif dtype.is_string:
            d, l = _plain_decode(chunk.phys, values, nn, width)
            data[sl][where] = d
            lengths[sl][where] = l
        else:  # by the file's physical type; the assignment adapts to dtype
            data[sl][where] = _plain_decode(chunk.phys, values, nn, width, chunk.type_length)

    pos = 0
    decoded = 0
    blob_len = len(blob)
    view = memoryview(blob)
    while decoded < n_total and pos < blob_len:
        r = CompactReader(view[pos:])
        ph = r.read_struct()
        header_len = r.pos
        ptype = ph.get(1, PAGE_DATA)
        uncomp_size = ph.get(2, 0)
        comp_size = ph.get(3, uncomp_size)
        page_raw = view[pos + header_len : pos + header_len + comp_size]
        pos += header_len + comp_size
        if ptype not in (PAGE_DICT, PAGE_DATA, PAGE_DATA_V2):
            continue  # index or unknown page: skip
        if tally is not None:
            tally["pages"] += 1
        if ptype == PAGE_DICT:
            dh = ph.get(7, {})
            count = dh.get(1, 0)
            payload = decompress(page_raw, uncomp_size)
            dict_table = _plain_decode(chunk.phys, payload, count, width or 64, chunk.type_length)
            continue
        page_valid = None
        if ptype == PAGE_DATA:
            dph = ph.get(5, {})
            nv = dph.get(1, 0)
            encoding = dph.get(2, ENC_PLAIN)
            values = decompress(page_raw, uncomp_size)
            if chunk.max_def > 0:
                (def_len,) = struct.unpack_from("<I", values, 0)
                page_valid = _rle_decode_defs(values[4 : 4 + def_len], nv)
                values = values[4 + def_len :]
        else:
            dph = ph.get(8, {})
            nv = dph.get(1, 0)
            encoding = dph.get(4, ENC_PLAIN)
            def_len = dph.get(5, 0)
            rep_len = dph.get(6, 0)
            levels = page_raw[: rep_len + def_len]  # NEVER compressed
            values = page_raw[rep_len + def_len :]
            if dph.get(7, True):  # is_compressed
                values = decompress(values, max(uncomp_size - rep_len - def_len, 1))
            if chunk.max_def > 0 and def_len:
                # v2 def levels: RLE hybrid WITHOUT the u32 length prefix
                page_valid = _rle_decode_defs(levels[rep_len:], nv)
        if page_valid is not None and page_valid.all():
            page_valid = None
        emit_values(encoding, values, page_valid, nv, decoded)
        decoded += nv
    return data, validity, lengths


# ------------------------------------------------- Arrow's column reader

def _arrow_reader():
    """``pyarrow.parquet`` where it imports, else None: the one place
    that chooses whether Arrow's C++ reader decodes a scan's row groups
    or the page decoder above decodes every chunk."""
    return _pyarrow_parquet


#: a file no longer than this is fetched whole, in one read, and Arrow
#: reads it from memory: Arrow's own reader asks for this much of a
#: file's end to find the footer (``kDefaultFooterReadSize``), so such a
#: file costs that one read either way — and then no second open, and no
#: read that calls back into python
WHOLE_FILE_BYTES = 64 * 1024


def open_arrow_file(path: str, fields: Sequence[Field]):
    """One task's open of one file for read_row_group: Arrow's
    ``ParquetFile`` over ``get_fs(path).open(path)`` — over the file's
    bytes where it is no longer than WHOLE_FILE_BYTES —, the file's
    string columns among ``fields`` left as indices + dictionary; None
    where pyarrow does not import or the file holds no row group.  The
    footer is parsed once, here, by Arrow (arrow_row_groups hands it on
    as this module's RowGroupMeta).  The caller closes it
    (``close(force=True)`` closes the file under it too)."""
    lib = _arrow_reader()
    if lib is None:
        return None
    import pyarrow

    from .fs import get_fs

    f = get_fs(path).open(path)
    try:
        if f.seek(0, os.SEEK_END) <= WHOLE_FILE_BYTES:
            f.seek(0)
            whole = f.read()
            f.close()
            f = pyarrow.BufferReader(whole)
        arrow_file = lib.ParquetFile(f)
        held = {c.path: c.physical_type for c in map(arrow_file.schema.column,
                                                     range(arrow_file.metadata.num_columns))}
        as_dictionary = [x.name for x in fields
                         if x.dtype.is_string and held.get(x.name) == "BYTE_ARRAY"]
        if as_dictionary:  # said when the reader is made; the parsed footer is handed over
            arrow_file = lib.ParquetFile(f, metadata=arrow_file.metadata,
                                         read_dictionary=as_dictionary)
        if arrow_file.metadata.num_row_groups:
            return arrow_file
    except BaseException:
        f.close()
        raise
    f.close()
    return None


_ARROW_PHYSICAL = {"BOOLEAN": T_BOOLEAN, "INT32": T_INT32, "INT64": T_INT64, "INT96": T_INT96,
                   "FLOAT": T_FLOAT, "DOUBLE": T_DOUBLE, "BYTE_ARRAY": T_BYTE_ARRAY,
                   "FIXED_LEN_BYTE_ARRAY": T_FLBA}
# pyarrow's names of the codecs; "LZ4" is its name for the format's LZ4_RAW and for
# its LZ4 both, so a file of either is read_metadata's
_ARROW_CODEC = {"UNCOMPRESSED": CODEC_UNCOMPRESSED, "SNAPPY": CODEC_SNAPPY, "GZIP": CODEC_GZIP,
                "ZSTD": CODEC_ZSTD}


#: a min or max as Arrow hands it over (``Statistics.min_raw``) -> the
#: footer's bytes of it, as read_metadata holds them
_ARROW_STAT_BYTES = {T_INT32: _STAT_INT[T_INT32].pack, T_INT64: _STAT_INT[T_INT64].pack,
                     T_BYTE_ARRAY: bytes, T_FLBA: bytes}


def arrow_row_groups(arrow_file, statistics: Sequence[str] = ()) -> Optional[List[RowGroupMeta]]:
    """read_metadata's row groups from the footer Arrow parsed when
    ``arrow_file`` (open_arrow_file's) was opened — a small file's
    footer costs the thrift reader above more than its pages cost
    Arrow.  The chunks of the columns named in ``statistics`` carry
    their min, max and null count as read_metadata would; Arrow
    withholds a min and max whose sort order the footer does not state
    (an old writer's strings), and such a chunk has none.  None where
    the file has what this does not spell (a nested column, a codec or
    type unknown here)."""
    md = arrow_file.metadata
    columns = [md.schema.column(j) for j in range(md.num_columns)]
    if any(c.max_repetition_level or c.max_definition_level > 1 or c.path != c.name
           or c.physical_type not in _ARROW_PHYSICAL for c in columns):
        return None
    out: List[RowGroupMeta] = []
    for g in range(md.num_row_groups):
        rg = md.row_group(g)
        chunks: Dict[str, ChunkMeta] = {}
        for j, col in enumerate(columns):
            c = rg.column(j)
            if c.compression not in _ARROW_CODEC:
                return None
            phys = _ARROW_PHYSICAL[col.physical_type]
            first = c.data_page_offset
            if c.dictionary_page_offset:
                first = min(first, c.dictionary_page_offset)
            chunk = chunks[col.path] = ChunkMeta(
                name=col.path, phys=phys, codec=_ARROW_CODEC[c.compression],
                num_values=c.num_values, offset=first, total_comp=c.total_compressed_size,
                max_def=col.max_definition_level,
                type_length=col.length if phys == T_FLBA else 0)
            if col.path in statistics:
                _arrow_statistics(c.statistics, chunk)
        out.append(RowGroupMeta(rows=rg.num_rows, chunks=chunks, index=g,
                                total_comp=sum(c.total_comp for c in chunks.values())))
    return out


def _arrow_statistics(stats, chunk: ChunkMeta) -> None:
    """Arrow's statistics of one chunk into ``chunk``, in the footer's
    encoding."""
    if stats is None:
        return
    if stats.has_null_count:
        chunk.null_count = stats.null_count
    encode = _ARROW_STAT_BYTES.get(chunk.phys)
    if encode is not None and stats.has_min_max:
        chunk.min_value, chunk.max_value = encode(stats.min_raw), encode(stats.max_raw)


def read_row_group(path: str, row_group: RowGroupMeta, fields: Sequence[Field], capacity: int,
                   arrow_file=None, tally: Optional[collections.Counter] = None):
    """Decode one row group's chunks of ``fields``: for each field, in
    order, read_column_chunk's ``(data, validity, lengths|None)`` at
    ``capacity`` rows, or None for a field the file does not hold.

    With ``arrow_file`` (open_arrow_file's), Arrow's reader fetches,
    decompresses and decodes the chunks in one call, outside the GIL,
    and each column is converted whole; a chunk it is not asked for
    (INT96, FIXED_LEN_BYTE_ARRAY), one whose Arrow type is not the
    requested type's, and all of them where that call fails, are
    decoded by read_column_chunk — which is also what says what is
    wrong with a corrupt chunk.  ``tally`` counts ``chunks`` and
    ``chunks_native`` besides read_column_chunk's pages."""
    if capacity < row_group.rows:
        raise ValueError(f"capacity {capacity} under the row group's {row_group.rows} rows")
    held = [(i, f, row_group.chunks[f.name]) for i, f in enumerate(fields)
            if f.name in row_group.chunks]
    table = None
    asked = [f.name for _, f, chunk in held if chunk.phys not in (T_INT96, T_FLBA)]
    if arrow_file is not None and asked:
        import pyarrow

        try:
            # use_threads: the columns decode side by side on Arrow's pool; on
            # the chip machine every window read better with it than any
            # without (PERF.md §6, PR 37)
            table = arrow_file.read_row_group(row_group.index, columns=asked, use_threads=True)
        except (pyarrow.ArrowException, OSError):
            pass  # the page decoder reads these chunks, or says what is wrong with them
    out: List[Optional[tuple]] = [None] * len(fields)
    for i, f, chunk in held:
        arrays = None
        if table is not None and f.name in asked:
            arrays = _from_arrow(table.column(f.name), f.dtype, capacity)
        if arrays is None:
            arrays = read_column_chunk(path, chunk, f.dtype, capacity=capacity, tally=tally)
        elif tally is not None:
            tally["chunks_native"] += 1
        if tally is not None:
            tally["chunks"] += 1
        out[i] = arrays
    return out


def read_row_group_pieces(path: str, row_group: RowGroupMeta, fields: Sequence[Field],
                          piece_rows: int, capacity, arrow_file=None,
                          tally: Optional[collections.Counter] = None):
    """Decode one row group as a stream of pieces: yields ``(chunks, lo,
    hi)``, ``chunks`` as read_row_group returns them and rows ``[lo,
    hi)`` of them the piece's own, the pieces tiling the row group in
    order.

    Where ``arrow_file`` takes every chunk of ``fields`` the row group
    holds (none INT96 or FIXED_LEN_BYTE_ARRAY, each Arrow type the
    requested type's: what the footer and Arrow's schema say before
    anything is read), Arrow's reader decodes page by page as it is
    pulled and a piece is the next ``piece_rows`` rows (the last: what
    is left) converted straight into arrays of ``capacity(rows)``:
    ``(chunks, 0, rows)``, and nothing of the row group's size is ever
    allocated.  Any other row group, and the rest of one from the piece
    on at which Arrow's call fails, is ONE piece: read_row_group's whole
    row group at ``capacity(row_group.rows)`` — ``(chunks, rows already
    handed on, row_group.rows)``.  ``tally`` as read_row_group's, and
    ``pieces`` and ``streamed`` (a row group streamed to its end)."""
    held = [f for f in fields if f.name in row_group.chunks]
    handed_on = 0
    if held and _streams(arrow_file, row_group, held):
        import pyarrow

        # use_threads: the columns of a piece decode side by side on Arrow's pool;
        # on the chip machine every window read better with it, in both file
        # cells, though a piece is a sixteenth of a row group (PERF.md §6, PR 39).
        # A row group that is one piece has nothing to run ahead of: waking the
        # pool for its few pages costs more than they do, the more so where the
        # machine's cores are busy (PERF.md §6, PR 40)
        pieces = _recut(arrow_file.iter_batches(
            batch_size=piece_rows, row_groups=[row_group.index],
            columns=[f.name for f in held], use_threads=row_group.rows > piece_rows), piece_rows)
        while True:
            try:
                batches = next(pieces, None)
            except (pyarrow.ArrowException, OSError):
                break  # the page decoder reads the rest, or says what is wrong with it
            if batches is None:
                if tally is not None:
                    tally["chunks"] += len(held)
                    tally["chunks_native"] += len(held)
                    tally["streamed"] += 1
                return
            table = pyarrow.Table.from_batches(batches)
            rows = table.num_rows
            cap = capacity(rows)
            chunks = [_from_arrow(table.column(f.name), f.dtype, cap) if f in held else None
                      for f in fields]
            if tally is not None:
                tally["pieces"] += 1
            yield chunks, 0, rows
            handed_on += rows
    chunks = read_row_group(path, row_group, fields, capacity(row_group.rows),
                            arrow_file=arrow_file, tally=tally)
    if tally is not None:
        tally["pieces"] += 1
    yield chunks, handed_on, row_group.rows


def _streams(arrow_file, row_group: RowGroupMeta, held: Sequence[Field]) -> bool:
    """Whether Arrow's reader takes every chunk of ``held``, so that the
    row group can be read as a stream of its batches."""
    if arrow_file is None:
        return False
    schema = arrow_file.schema_arrow
    for f in held:
        i = schema.get_field_index(f.name)
        if (row_group.chunks[f.name].phys in (T_INT96, T_FLBA) or i < 0
                or _arrow_layout(schema.field(i).type, f.dtype) is None):
            return False
    return True


def _recut(batches, rows: int):
    """Arrow's record batches as lists of slices of exactly ``rows`` rows
    together, the last what is left: Arrow hands back the batch size it
    is asked for, and where it does not, nothing downstream sees it."""
    held, n = [], 0
    for batch in batches:
        while batch.num_rows:
            part = batch.slice(0, rows - n)
            held.append(part)
            n += part.num_rows
            batch = batch.slice(part.num_rows)
            if n == rows:
                yield held
                held, n = [], 0
    if held:
        yield held


_ARROW_FIXED = {  # requested kind -> the pyarrow.types test of the one Arrow type it is read from
    TypeKind.INT8: "is_int8", TypeKind.INT16: "is_int16", TypeKind.INT32: "is_int32",
    TypeKind.INT64: "is_int64", TypeKind.FLOAT32: "is_float32", TypeKind.FLOAT64: "is_float64",
    TypeKind.DATE32: "is_date32",
}


def _arrow_layout(t, dtype: DataType) -> Optional[str]:
    """How _from_arrow reads a column of Arrow type ``t`` into the
    requested ``dtype``, or None where the two do not pair (a file type
    that differs from the requested one: the page decoder adapts it)."""
    from pyarrow import types

    k = dtype.kind
    if k in _ARROW_FIXED:
        return "fixed" if getattr(types, _ARROW_FIXED[k])(t) else None
    if k == TypeKind.TIMESTAMP:
        return "fixed" if types.is_timestamp(t) and t.unit == "us" else None
    if k == TypeKind.BOOL:
        return "bits" if types.is_boolean(t) else None
    if k == TypeKind.DECIMAL:
        taken = (types.is_decimal128(t) and t.precision <= 18
                 and (t.precision, t.scale) == (dtype.precision, dtype.scale))
        return "decimal128" if taken else None
    if dtype.is_string:
        if types.is_dictionary(t):
            return "dictionary" if _is_bytes(t.value_type) else None
        return "bytes" if _is_bytes(t) else None
    return None


def _is_bytes(t) -> bool:
    from pyarrow import types

    return (types.is_string(t) or types.is_binary(t)
            or types.is_large_string(t) or types.is_large_binary(t))


def _arrow_bits(buf, offset: int, n: int) -> np.ndarray:
    """``n`` bits of an Arrow bitmap from bit ``offset`` -> bool."""
    first, bit = divmod(offset, 8)
    bits = np.unpackbits(np.frombuffer(buf, np.uint8, offset=first), count=bit + n,
                         bitorder="little")
    return bits[bit:].view(np.bool_)


def _arrow_bytes(arr, width: int, data: np.ndarray, lengths: np.ndarray) -> None:
    """An Arrow string / binary array's values into ``data`` (len(arr),
    width), zero-padded, each cut to its first ``width`` bytes, and
    ``lengths``: offsets and chars by one mask, no Python step a row."""
    from pyarrow import types

    n = len(arr)
    large = types.is_large_string(arr.type) or types.is_large_binary(arr.type)
    odt = np.dtype(np.int64 if large else np.int32)
    _, offsets_buf, chars_buf = arr.buffers()
    offsets = np.frombuffer(offsets_buf, odt, count=n + 1, offset=arr.offset * odt.itemsize)
    full = np.diff(offsets)
    start, total = int(offsets[0]), int(offsets[-1] - offsets[0])
    chars = (np.frombuffer(chars_buf, np.uint8, count=total, offset=start) if total
             else np.zeros(0, np.uint8))
    np.minimum(full, width, out=lengths, casting="unsafe")
    if total and int(full.max()) > width:  # a value longer than the width keeps its head
        within = np.arange(total) - np.repeat(offsets[:-1] - start, full)
        chars = chars[within < width]
    data[np.arange(width) < lengths[:, None]] = chars


def _arrow_dictionary(arr, valid: Optional[np.ndarray], width: int,
                      data: np.ndarray, lengths: np.ndarray) -> None:
    """An Arrow dictionary<string> array's rows into ``data`` (len(arr),
    width) and ``lengths``: the (small) dictionary padded once, one zero
    entry after it for the null rows, then ONE gather of whole rows and
    one of lengths."""
    n = len(arr)
    entries = arr.dictionary
    k = len(entries)
    table = np.zeros((k + 1, width), np.uint8)
    table_lengths = np.zeros(k + 1, np.int32)
    if k:
        _arrow_bytes(entries, width, table[:k], table_lengths[:k])
    idt = np.dtype(arr.type.index_type.to_pandas_dtype())
    idx = np.frombuffer(arr.buffers()[1], idt, count=n, offset=arr.offset * idt.itemsize)
    if valid is not None:
        idx = np.where(valid, idx, k)
    # a row is one word where the width is a word's, else `width` opaque bytes
    word = np.dtype(f"u{width}" if width in (1, 2, 4, 8) else (np.void, width))
    np.take(table.view(word)[:, 0], idx, out=data.view(word)[:, 0], mode="clip")
    np.take(table_lengths, idx, out=lengths, mode="clip")


def _from_arrow(column, dtype: DataType, capacity: int):
    """A row group's column as Arrow decoded it (a ChunkedArray) ->
    read_column_chunk's (data, validity, lengths|None) at ``capacity``
    rows, by array operations over whole chunks; None where its Arrow
    type and ``dtype`` do not pair (_arrow_layout)."""
    layout = _arrow_layout(column.type, dtype)
    if layout is None:
        return None
    width = dtype.string_width if dtype.is_string else 0
    validity = np.zeros(capacity, np.bool_)
    if dtype.is_string:
        data = np.zeros((capacity, width), np.uint8)
        lengths = np.zeros(capacity, np.int32)
    else:
        data = np.zeros(capacity, dtype.np_dtype)
        lengths = None
    row = 0
    for arr in column.chunks:
        n = len(arr)
        if n == 0:
            continue
        sl = slice(row, row + n)
        row += n
        buffers = arr.buffers()
        valid = None if arr.null_count == 0 else _arrow_bits(buffers[0], arr.offset, n)
        validity[sl] = True if valid is None else valid
        if layout == "dictionary":
            _arrow_dictionary(arr, valid, width, data[sl], lengths[sl])
            continue
        if layout == "bytes":
            _arrow_bytes(arr, width, data[sl], lengths[sl])
            if valid is not None:
                lengths[sl][~valid] = 0
        elif layout == "bits":
            data[sl] = _arrow_bits(buffers[1], arr.offset, n)
        elif layout == "decimal128":  # little-endian int128: the low word is the value
            words = np.frombuffer(buffers[1], np.int64, count=2 * n, offset=16 * arr.offset)
            data[sl] = words.reshape(n, 2)[:, 0]
        else:
            item = data.dtype.itemsize
            data[sl] = np.frombuffer(buffers[1], data.dtype, count=n, offset=arr.offset * item)
        if valid is not None:
            data[sl][~valid] = 0
    return data, validity, lengths
