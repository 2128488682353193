"""Self-contained ORC subset writer/reader.

≙ the file-format half of the reference's OrcExec (orc_exec.rs:53-285,
which scans ORC through a forked orc-rust) — implemented from the
public ORC v1 spec (no pyorc; snappy chunks go through
parquet.snappy_decompress, the one place that chooses a codec library):

- file layout: "ORC" header, stripes (data streams + protobuf
  StripeFooter), protobuf Metadata (stripe-level column statistics),
  protobuf Footer (types/stripes/counts), PostScript, 1-byte
  postscript length.
- encodings (all DIRECT, compression NONE): PRESENT = bit-packed
  bool + byte-RLE; ints/dates = signed RLEv1 (zigzag varints);
  int8 = byte-RLE; bool = bit-packed byte-RLE; float/double = raw
  IEEE LE; string = LENGTH (unsigned RLEv1) + concatenated DATA;
  decimal(<=18) = unbounded zigzag varint DATA + signed RLEv1 scale
  SECONDARY.
- reader: REAL-WORLD files too (round-2): compressed streams
  (zlib/snappy/lz4/zstd chunked framing), RLEv2 integers (short
  repeat / direct / patched base / delta), DIRECT_V2 and
  DICTIONARY(_V2) string encodings — what ORC C++ (pyarrow/Spark)
  writers actually emit — plus the subset our writer produces.
  Stripe statistics drive predicate pruning (the stripe granularity
  of the reference's ORC scan pushdown).

Compound types: LIST of primitive reads keep a vectorized fast path
(LENGTH stream + child PRESENT/DATA, rectangularized to the declared
max_elems); MAP/STRUCT/nested LIST read through a recursive
python-value decoder.  The writer mirrors the full set: flat columns
and LIST-of-primitive via numpy tuples, and MAP/STRUCT/nested LIST
fields as plain python value lists (the same shape the reader's
compound path returns) through a recursive encoder.  TIMESTAMP is
covered at both levels (top-level vectorized + compound py-value,
int64 unix-µs lane).  Remaining gate (not silently wrong): BINARY.
"""

from __future__ import annotations

import struct
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..schema import DataType, Field, Schema, TypeKind

MAGIC = b"ORC"

# Type.kind enum
K_BOOLEAN, K_BYTE, K_SHORT, K_INT, K_LONG, K_FLOAT, K_DOUBLE, K_STRING = range(8)
K_BINARY = 8
K_TIMESTAMP = 9
K_LIST = 10
K_MAP = 11
K_STRUCT = 12
K_DECIMAL = 14
K_DATE = 15

# ORC timestamps are seconds relative to 2015-01-01 00:00:00 UTC plus
# a nanosecond stream with decimal-trailing-zero packing
ORC_TS_EPOCH = 1420070400

# Stream.kind enum
S_PRESENT, S_DATA, S_LENGTH = 0, 1, 2
S_DICTIONARY_DATA = 3
S_SECONDARY = 5

# ColumnEncoding.kind enum
E_DIRECT, E_DICTIONARY, E_DIRECT_V2, E_DICTIONARY_V2 = 0, 1, 2, 3

# CompressionKind
C_NONE, C_ZLIB, C_SNAPPY, C_LZO, C_LZ4, C_ZSTD = range(6)


def orc_decompress(buf: bytes, kind: int) -> bytes:
    """ORC chunked stream framing: repeated [u24le (len<<1 | original)]
    [chunk]; `original` chunks are stored verbatim."""
    if kind == C_NONE or not buf:
        return buf
    out = bytearray()
    pos = 0
    n = len(buf)
    while pos + 3 <= n:
        h = buf[pos] | (buf[pos + 1] << 8) | (buf[pos + 2] << 16)
        pos += 3
        orig = h & 1
        ln = h >> 1
        chunk = buf[pos : pos + ln]
        pos += ln
        if orig:
            out += chunk
        elif kind == C_ZLIB:
            out += zlib.decompress(chunk, -15)  # raw deflate
        elif kind == C_SNAPPY:
            from .parquet import snappy_decompress

            out += snappy_decompress(chunk)
        elif kind == C_LZ4:
            from .parquet import _lz4_block_decompress

            out += _lz4_block_decompress(chunk)
        elif kind == C_ZSTD:
            import zstandard

            out += zstandard.ZstdDecompressor().decompress(
                chunk, max_output_size=1 << 26
            )
        else:
            raise NotImplementedError(f"ORC compression kind {kind}")
    return bytes(out)


def orc_compress(data: bytes, kind: int, block: int = 65536) -> bytes:
    """Writer half of the chunked framing: split into <= ``block``-byte
    chunks, compress each (zlib raw-deflate, zstd, snappy, or lz4
    raw-block), store verbatim (original bit) when compression does not
    shrink the chunk — the exact format orc_decompress consumes and ORC
    C++ readers expect."""
    if kind == C_NONE or not data:
        return data
    if kind not in (C_ZLIB, C_ZSTD, C_SNAPPY, C_LZ4):
        raise NotImplementedError(f"ORC writer compression kind {kind}")
    if kind == C_ZSTD:
        import zstandard

        zc = zstandard.ZstdCompressor()
    out = bytearray()
    for pos in range(0, len(data), block):
        chunk = data[pos : pos + block]
        if kind == C_ZSTD:
            comp = zc.compress(chunk)
        elif kind == C_SNAPPY:
            from .parquet import _snappy_compress

            comp = _snappy_compress(chunk)
        elif kind == C_LZ4:
            from .ipc_compression import lz4_block_compress

            comp = lz4_block_compress(chunk)
        else:
            co = zlib.compressobj(6, zlib.DEFLATED, -15)
            comp = co.compress(chunk) + co.flush()
        if len(comp) < len(chunk):
            h = len(comp) << 1
            out += bytes([h & 0xFF, (h >> 8) & 0xFF, (h >> 16) & 0xFF])
            out += comp
        else:
            h = (len(chunk) << 1) | 1
            out += bytes([h & 0xFF, (h >> 8) & 0xFF, (h >> 16) & 0xFF])
            out += chunk
    return bytes(out)


# ------------------------------------------------------------- RLE v2

_RLEV2_WIDTHS = [
    1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16,
    17, 18, 19, 20, 21, 22, 23, 24, 26, 28, 30, 32, 40, 48, 56, 64,
]


def _w_decode(code: int, delta: bool = False) -> int:
    if delta and code == 0:
        return 0
    return _RLEV2_WIDTHS[code]


def _unpack_be(data, pos: int, width: int, count: int) -> Tuple[np.ndarray, int]:
    """MSB-first bit-unpack `count` unsigned values of `width` bits."""
    if width == 0 or count == 0:
        return np.zeros(count, np.int64), pos
    nbytes = (width * count + 7) // 8
    bits = np.unpackbits(np.frombuffer(data, np.uint8, nbytes, pos))
    vals = np.zeros(count, np.uint64)
    b = bits[: width * count].reshape(count, width).astype(np.uint64)
    for j in range(width):
        vals = (vals << np.uint64(1)) | b[:, j]
    return vals.view(np.int64), pos + nbytes


def _wrap_u64(v):
    """Unsigned->signed int64 wrap for "unsigned" RLE streams.

    ORC C++ packs signed values (e.g. pre-epoch packed nanos) into
    unsigned streams as their two's-complement uint64 image; a python
    varint/big-endian decode hands back the raw >= 2**63 integer, which
    overflows an int64 slice-assign.  Every unsigned decode path wraps
    through here — RLEv1 literal + run base and RLEv2 SHORT_REPEAT +
    DELTA base as scalars, RLEv2 DIRECT vectorized (a uint64 ndarray
    image reinterpreted as its two's-complement int64 view)."""
    if isinstance(v, np.ndarray):
        return v.astype(np.uint64, copy=False).view(np.int64)
    return v - (1 << 64) if v >= 1 << 63 else v


def _rlev2_decode(data: bytes, count: int, signed: bool) -> np.ndarray:
    """ORC RLEv2: short-repeat / direct / patched-base / delta runs."""
    out = np.zeros(count, np.int64)
    n = 0
    pos = 0

    def uv():
        nonlocal pos
        v = 0
        shift = 0
        while True:
            b = data[pos]
            pos += 1
            v |= (b & 0x7F) << shift
            if not b & 0x80:
                return v
            shift += 7

    def sv():  # signed varint (zigzag)
        u = uv()
        return (u >> 1) ^ -(u & 1)

    while n < count:
        b0 = data[pos]
        pos += 1
        enc = b0 >> 6
        if enc == 0:  # SHORT_REPEAT
            width = ((b0 >> 3) & 7) + 1
            run = (b0 & 7) + 3
            v = int.from_bytes(data[pos : pos + width], "big")
            pos += width
            v = (v >> 1) ^ -(v & 1) if signed else _wrap_u64(v)
            out[n : n + run] = v
            n += run
        elif enc == 1:  # DIRECT
            width = _w_decode((b0 >> 1) & 0x1F)
            run = ((b0 & 1) << 8 | data[pos]) + 1
            pos += 1
            vals, pos = _unpack_be(data, pos, width, run)
            if signed:
                u = vals.view(np.uint64)
                vals = ((u >> np.uint64(1)).astype(np.int64)) ^ -(
                    (u & np.uint64(1)).astype(np.int64)
                )
            else:
                # explicit uint64->int64 wrap through the shared helper
                # (ADVICE r5: no more relying on numpy's reinterpret
                # happening implicitly in the slice-assign below)
                vals = _wrap_u64(vals.view(np.uint64))
            out[n : n + run] = vals
            n += run
        elif enc == 2:  # PATCHED_BASE
            width = _w_decode((b0 >> 1) & 0x1F)
            run = ((b0 & 1) << 8 | data[pos]) + 1
            pos += 1
            b2 = data[pos]
            b3 = data[pos + 1]
            pos += 2
            bw = ((b2 >> 5) & 7) + 1           # base width bytes
            pw = _w_decode(b2 & 0x1F)          # patch width
            pgw = ((b3 >> 5) & 7) + 1          # patch gap width
            pll = b3 & 0x1F                    # patch list length
            base = int.from_bytes(data[pos : pos + bw], "big")
            pos += bw
            sign_mask = 1 << (bw * 8 - 1)
            if base & sign_mask:               # sign-magnitude
                base = -(base & (sign_mask - 1))
            vals, pos = _unpack_be(data, pos, width, run)
            vals = vals.copy()
            if pll:
                # patch entries are (gap,patch) pairs packed at the
                # CLOSEST FIXED width >= pgw+pw (ORC getClosestFixedBits)
                raw_bits = pgw + pw
                patch_bits = next(w for w in _RLEV2_WIDTHS if w >= raw_bits)
                entries, pos = _unpack_be(data, pos, patch_bits, pll)
                idx = 0
                for e in entries.view(np.uint64):
                    gap = int(e >> np.uint64(pw))
                    patch = int(e & ((np.uint64(1) << np.uint64(pw)) - np.uint64(1)))
                    idx += gap
                    vals[idx] |= patch << width
            out[n : n + run] = vals + base
            n += run
        else:  # DELTA
            width = _w_decode((b0 >> 1) & 0x1F, delta=True)
            run = ((b0 & 1) << 8 | data[pos]) + 1
            pos += 1
            base = sv() if signed else _wrap_u64(uv())
            if run == 1:
                out[n] = base
                n += 1
                continue
            delta0 = sv()
            inc = np.zeros(run, np.int64)
            inc[0] = base
            inc[1] = delta0
            if run > 2:
                if width:
                    mags, pos = _unpack_be(data, pos, width, run - 2)
                else:
                    mags = np.full(run - 2, abs(delta0), np.int64)
                inc[2:] = mags if delta0 >= 0 else -mags
            out[n : n + run] = np.cumsum(inc)
            n += run
    return out


def _orc_kind(dtype: DataType) -> int:
    k = dtype.kind
    if k == TypeKind.BOOL:
        return K_BOOLEAN
    if k == TypeKind.INT8:
        return K_BYTE
    if k == TypeKind.INT16:
        return K_SHORT
    if k == TypeKind.INT32:
        return K_INT
    if k == TypeKind.INT64:
        return K_LONG
    if k == TypeKind.FLOAT32:
        return K_FLOAT
    if k == TypeKind.FLOAT64:
        return K_DOUBLE
    if k == TypeKind.DATE32:
        return K_DATE
    if k == TypeKind.DECIMAL:
        return K_DECIMAL
    if k == TypeKind.TIMESTAMP:
        return K_TIMESTAMP
    if dtype.is_string:
        return K_STRING
    raise NotImplementedError(f"ORC subset: unsupported type {dtype!r}")


# ------------------------------------------------------------- protobuf

def _uvarint(v: int) -> bytes:
    out = bytearray()
    v = int(v)
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _zz(v: int) -> int:
    v = int(v)
    return (v << 1) ^ (v >> 63)


def _unzz(u: int) -> int:
    return (u >> 1) ^ -(u & 1)


class PbWriter:
    def __init__(self):
        self.buf = bytearray()

    def varint(self, fid: int, v: int):
        self.buf += _uvarint(fid << 3 | 0)
        self.buf += _uvarint(v)

    def bytes_(self, fid: int, b: bytes):
        self.buf += _uvarint(fid << 3 | 2)
        self.buf += _uvarint(len(b))
        self.buf += b

    def string(self, fid: int, s: str):
        self.bytes_(fid, s.encode("utf-8"))

    def msg(self, fid: int, w: "PbWriter"):
        self.bytes_(fid, bytes(w.buf))

    def double(self, fid: int, v: float):
        self.buf += _uvarint(fid << 3 | 1)
        self.buf += struct.pack("<d", v)

    def getvalue(self) -> bytes:
        return bytes(self.buf)


class PbReader:
    def __init__(self, data: bytes):
        self.data = data
        self.pos = 0

    def _uv(self) -> int:
        v = 0
        shift = 0
        while True:
            b = self.data[self.pos]
            self.pos += 1
            v |= (b & 0x7F) << shift
            if not b & 0x80:
                return v
            shift += 7

    def fields(self):
        """Yields (field_id, wire_type, value)."""
        while self.pos < len(self.data):
            tag = self._uv()
            fid, wt = tag >> 3, tag & 7
            if wt == 0:
                yield fid, wt, self._uv()
            elif wt == 1:
                v = struct.unpack_from("<d", self.data, self.pos)[0]
                self.pos += 8
                yield fid, wt, v
            elif wt == 2:
                ln = self._uv()
                yield fid, wt, self.data[self.pos : self.pos + ln]
                self.pos += ln
            elif wt == 5:
                v = struct.unpack_from("<f", self.data, self.pos)[0]
                self.pos += 4
                yield fid, wt, v
            else:
                raise ValueError(f"orc: unsupported protobuf wire type {wt}")


# ----------------------------------------------------------- encodings

def _byte_rle_encode(data: bytes) -> bytes:
    """ORC byte RLE: runs [n-3, byte] for 3..130 repeats, literal
    groups [-(n), n bytes]."""
    out = bytearray()
    i = 0
    n = len(data)
    while i < n:
        run = 1
        while i + run < n and run < 130 and data[i + run] == data[i]:
            run += 1
        if run >= 3:
            out.append(run - 3)
            out.append(data[i])
            i += run
            continue
        # literal group: scan ahead until a >=3 run starts
        j = i
        while j < n and j - i < 128:
            r = 1
            while j + r < n and r < 3 and data[j + r] == data[j]:
                r += 1
            if r >= 3:
                break
            j += 1
        out.append(256 - (j - i))
        out += data[i:j]
        i = j
    return bytes(out)


def _byte_rle_decode(data: bytes, count: int) -> bytes:
    out = bytearray()
    i = 0
    while len(out) < count:
        h = data[i]
        i += 1
        if h < 128:
            out += bytes([data[i]]) * (h + 3)
            i += 1
        else:
            ln = 256 - h
            out += data[i : i + ln]
            i += ln
    return bytes(out[:count])


def _bool_encode(bits: np.ndarray) -> bytes:
    packed = np.packbits(bits.astype(np.uint8))  # MSB-first, ORC order
    return _byte_rle_encode(packed.tobytes())


def _bool_decode(data: bytes, count: int) -> np.ndarray:
    nbytes = (count + 7) // 8
    raw = _byte_rle_decode(data, nbytes)
    return np.unpackbits(np.frombuffer(raw, np.uint8))[:count].astype(bool)


def _rlev1_encode(values: np.ndarray, signed: bool) -> bytes:
    """Literal groups only (spec-valid; the reader handles runs too)."""
    out = bytearray()
    vals = [int(v) for v in values]
    for i in range(0, len(vals), 128):
        group = vals[i : i + 128]
        out.append(256 - len(group))
        for v in group:
            out += _uvarint(_zz(v) if signed else v)
    return bytes(out)


def _rlev1_decode(data: bytes, count: int, signed: bool) -> np.ndarray:
    out = np.empty(count, np.int64)
    n = 0
    pos = 0

    def uv():
        nonlocal pos
        v = 0
        shift = 0
        while True:
            b = data[pos]
            pos += 1
            v |= (b & 0x7F) << shift
            if not b & 0x80:
                return v
            shift += 7

    while n < count:
        h = data[pos]
        pos += 1
        if h < 128:  # run: h+3 values, delta int8, base varint
            ln = h + 3
            delta = struct.unpack_from("<b", data, pos)[0]
            pos += 1
            base = uv()
            base = _unzz(base) if signed else _wrap_u64(base)
            for k in range(ln):
                out[n] = base + k * delta
                n += 1
        else:
            ln = 256 - h
            for _ in range(ln):
                v = uv()
                out[n] = _unzz(v) if signed else _wrap_u64(v)
                n += 1
    return out


# --------------------------------------------------------------- writer

@dataclass
class _Stream:
    kind: int
    column: int
    data: bytes


def _pack_nanos(nanos: np.ndarray) -> np.ndarray:
    """ORC nanosecond packing (java formatNanos): values divisible by
    100 are divided down and the low 3 bits store zeros-1 (so c=1 means
    100 removed, c=7 means 10^8); c=0 means nothing removed."""
    out = np.zeros(nanos.shape[0], np.int64)
    for i, n in enumerate(np.asarray(nanos, np.int64)):
        n = int(n)
        if n == 0:
            continue
        if n % 100 != 0:
            out[i] = n << 3
            continue
        n //= 100
        c = 1
        while n % 10 == 0 and c < 7:
            n //= 10
            c += 1
        out[i] = (n << 3) | c
    return out


def _unpack_nanos(packed: np.ndarray) -> np.ndarray:
    """Inverse (java parseNanos): multiply by 10^(c+1) when c != 0."""
    c = packed & 7
    base = packed >> 3
    mult = np.where(c == 0, 1, 10 ** (c + 1)).astype(np.int64)
    return (base * mult).astype(np.int64)


def _encode_ts_streams(micros: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 unix-µs -> (DATA rel-seconds, SECONDARY packed nanos) —
    the single writer-side split, shared by every TIMESTAMP site.

    Uses the ORC C++ convention (verified against pyarrow's writer):
    seconds are TRUNC-TOWARD-ZERO unix seconds shifted to the 2015
    epoch, and nanos carry the SIGNED sub-second remainder (negative
    for pre-epoch fractions: -1µs -> secs 0, nanos -1000), wrapped to
    uint64 for the unsigned SECONDARY stream.  The Java writers' form
    (floor seconds, nanos in [0, 1e9)) is ambiguous in the second
    before the unix epoch — trunc secs 0 there is indistinguishable
    from a genuine +0.x value — so the C++ form is the one that
    roundtrips every value; the reader handles both."""
    micros = np.asarray(micros, np.int64)
    secs = np.where(micros < 0, -((-micros) // 1_000_000),
                    micros // 1_000_000)
    nanos = (micros - secs * 1_000_000) * 1000
    return secs - ORC_TS_EPOCH, _pack_nanos(nanos).view(np.uint64)


def _decode_ts_micros(rel: np.ndarray, packed: np.ndarray) -> np.ndarray:
    """(DATA rel-seconds, SECONDARY packed nanos) -> int64 unix-µs —
    the single reader-side join, shared by every TIMESTAMP site.
    Handles both writer conventions: signed-remainder nanos (ORC C++)
    fall through untouched; Java floor-second files carry positive
    nanos and need the seconds re-floored below zero."""
    nanos = _unpack_nanos(np.asarray(packed, np.int64))
    secs = np.asarray(rel, np.int64) + ORC_TS_EPOCH
    secs = np.where((secs < 0) & (nanos > 999_999), secs - 1, secs)
    return secs * 1_000_000 + nanos // 1000


def _encode_column(
    col_id: int, dtype: DataType, data: np.ndarray, validity: np.ndarray,
    lengths: Optional[np.ndarray],
) -> List[_Stream]:
    streams: List[_Stream] = []
    has_nulls = not bool(validity.all())
    if has_nulls:
        streams.append(_Stream(S_PRESENT, col_id, _bool_encode(validity)))
    live = validity.astype(bool)
    k = dtype.kind
    if k == TypeKind.BOOL:
        streams.append(_Stream(S_DATA, col_id, _bool_encode(data[live].astype(bool))))
    elif k == TypeKind.INT8:
        streams.append(_Stream(S_DATA, col_id, _byte_rle_encode(
            data[live].astype(np.int8).tobytes())))
    elif k in (TypeKind.INT16, TypeKind.INT32, TypeKind.INT64, TypeKind.DATE32):
        streams.append(_Stream(S_DATA, col_id, _rlev1_encode(data[live], signed=True)))
    elif k in (TypeKind.FLOAT32, TypeKind.FLOAT64):
        streams.append(_Stream(S_DATA, col_id, np.ascontiguousarray(data[live]).tobytes()))
    elif k == TypeKind.DECIMAL:
        body = bytearray()
        for v in data[live]:
            body += _uvarint(_zz(int(v)))
        streams.append(_Stream(S_DATA, col_id, bytes(body)))
        streams.append(_Stream(S_SECONDARY, col_id, _rlev1_encode(
            np.full(int(live.sum()), dtype.scale, np.int64), signed=True)))
    elif k == TypeKind.TIMESTAMP:
        rel, packed = _encode_ts_streams(data[live])
        streams.append(_Stream(S_DATA, col_id, _rlev1_encode(rel, signed=True)))
        streams.append(_Stream(S_SECONDARY, col_id, _rlev1_encode(
            packed, signed=False)))
    elif dtype.is_string:
        ln = lengths[live]
        streams.append(_Stream(S_LENGTH, col_id, _rlev1_encode(ln, signed=False)))
        body = bytearray()
        d = data[live]
        for i in range(d.shape[0]):
            body += bytes(d[i, : ln[i]])
        streams.append(_Stream(S_DATA, col_id, bytes(body)))
    else:
        raise NotImplementedError(f"ORC subset: {dtype!r}")
    return streams


def _encode_list_column(
    col_id: int, dtype: DataType, validity: np.ndarray,
    lengths: np.ndarray, edata: np.ndarray, evalid: np.ndarray,
) -> List[_Stream]:
    """LIST of primitive: LENGTH at the list column, flattened child
    PRESENT/DATA at col_id+1 (the writer's preorder child id)."""
    if dtype.elem.is_nested or dtype.elem.is_string:
        raise NotImplementedError(f"ORC subset writer: {dtype!r}")
    streams: List[_Stream] = []
    live = validity.astype(bool)
    if not bool(live.all()):
        streams.append(_Stream(S_PRESENT, col_id, _bool_encode(validity)))
    ln = lengths[live].astype(np.int64)
    streams.append(_Stream(S_LENGTH, col_id, _rlev1_encode(ln, signed=False)))
    flat_v: List[np.ndarray] = []
    flat_d: List[np.ndarray] = []
    for i in np.flatnonzero(live):
        L = int(lengths[i])
        flat_v.append(evalid[i, :L])
        flat_d.append(edata[i, :L])
    ev = np.concatenate(flat_v) if flat_v else np.zeros(0, bool)
    ed = np.concatenate(flat_d) if flat_d else np.zeros(0, dtype.elem.np_dtype)
    streams.extend(_encode_column(col_id + 1, dtype.elem, ed, ev, None))
    return streams


def _type_size(dt: DataType) -> int:
    """Number of preorder type-tree slots this type consumes."""
    if dt.kind == TypeKind.ARRAY:
        return 1 + _type_size(dt.elem)
    if dt.kind == TypeKind.MAP:
        return 1 + _type_size(dt.key) + _type_size(dt.value)
    if dt.kind == TypeKind.STRUCT:
        return 1 + sum(_type_size(f.dtype) for f in dt.struct_fields)
    return 1


def _is_compound(dt: DataType) -> bool:
    """Columns that take the recursive python-value path, on BOTH the
    writer and reader sides (one predicate so they can never
    disagree on dispatch): maps, structs, and lists whose elements
    are nested or strings (flat lists keep the vectorized path)."""
    return dt.kind in (TypeKind.MAP, TypeKind.STRUCT) or (
        dt.kind == TypeKind.ARRAY and (dt.elem.is_nested or dt.elem.is_string)
    )


def _encode_pyvalues(
    col_id: int, dtype: DataType, vals: list,
    counts: Dict[int, Tuple[int, bool]],
) -> List[_Stream]:
    """Recursive encoder for compound columns fed as python values —
    the exact shape the reader's compound path (`decode_nested`)
    produces: None for null, list per ARRAY slot, dict per MAP/STRUCT
    slot.  Mirrors the reader's conventions: PRESENT per nesting
    level, children carry one entry per non-null parent slot (per
    element for LIST/MAP)."""
    streams: List[_Stream] = []
    validity = np.array([v is not None for v in vals], bool)
    live = [v for v in vals if v is not None]
    counts[col_id] = (len(live), len(live) < len(vals))
    if not bool(validity.all()):
        streams.append(_Stream(S_PRESENT, col_id, _bool_encode(validity)))
    k = dtype.kind
    if k == TypeKind.ARRAY:
        ln = np.array([len(v) for v in live], np.int64)
        streams.append(_Stream(S_LENGTH, col_id, _rlev1_encode(ln, signed=False)))
        streams.extend(_encode_pyvalues(
            col_id + 1, dtype.elem, [e for v in live for e in v], counts))
        return streams
    if k == TypeKind.MAP:
        ln = np.array([len(v) for v in live], np.int64)
        streams.append(_Stream(S_LENGTH, col_id, _rlev1_encode(ln, signed=False)))
        streams.extend(_encode_pyvalues(
            col_id + 1, dtype.key, [e for v in live for e in v.keys()], counts))
        streams.extend(_encode_pyvalues(
            col_id + 1 + _type_size(dtype.key), dtype.value,
            [e for v in live for e in v.values()], counts))
        return streams
    if k == TypeKind.STRUCT:
        sub = col_id + 1
        for f in dtype.struct_fields:
            streams.extend(_encode_pyvalues(
                sub, f.dtype, [v[f.name] for v in live], counts))
            sub += _type_size(f.dtype)
        return streams
    if dtype.is_string:
        bodies = [s.encode() if isinstance(s, str) else bytes(s) for s in live]
        streams.append(_Stream(S_LENGTH, col_id, _rlev1_encode(
            np.array([len(b) for b in bodies], np.int64), signed=False)))
        streams.append(_Stream(S_DATA, col_id, b"".join(bodies)))
        return streams
    if k == TypeKind.BOOL:
        streams.append(_Stream(S_DATA, col_id, _bool_encode(
            np.array([bool(v) for v in live], bool))))
        return streams
    if k == TypeKind.DECIMAL:
        import decimal as _dec

        body = bytearray()
        for v in live:
            scaled = _dec.Decimal(v).scaleb(dtype.scale)
            if scaled != scaled.to_integral_value():
                # same gate as the reader's _rescale_decimals: a value
                # with more fractional digits than the declared scale
                # cannot be represented exactly — never truncate
                raise NotImplementedError(
                    f"ORC subset: decimal value {v} exceeds the "
                    f"declared scale {dtype.scale}")
            body += _uvarint(_zz(int(scaled)))
        streams.append(_Stream(S_DATA, col_id, bytes(body)))
        streams.append(_Stream(S_SECONDARY, col_id, _rlev1_encode(
            np.full(len(live), dtype.scale, np.int64), signed=True)))
        return streams
    if k == TypeKind.INT8:
        streams.append(_Stream(S_DATA, col_id, _byte_rle_encode(
            np.array(live, np.int8).tobytes())))
        return streams
    if k in (TypeKind.INT16, TypeKind.INT32, TypeKind.INT64, TypeKind.DATE32):
        streams.append(_Stream(S_DATA, col_id, _rlev1_encode(
            np.array([int(v) for v in live], np.int64), signed=True)))
        return streams
    if k in (TypeKind.FLOAT32, TypeKind.FLOAT64):
        streams.append(_Stream(S_DATA, col_id, np.ascontiguousarray(
            np.array(live, dtype.np_dtype)).tobytes()))
        return streams
    if k == TypeKind.TIMESTAMP:
        # values are int64 unix microseconds (the engine's physical
        # timestamp lane)
        rel, packed = _encode_ts_streams(
            np.array([int(v) for v in live], np.int64))
        streams.append(_Stream(S_DATA, col_id, _rlev1_encode(rel, signed=True)))
        streams.append(_Stream(S_SECONDARY, col_id, _rlev1_encode(
            packed, signed=False)))
        return streams
    raise NotImplementedError(f"ORC subset writer: compound element {dtype!r}")


def _col_stats(dtype: DataType, data, validity, lengths) -> "PbWriter":
    w = PbWriter()
    live = validity.astype(bool)
    nvals = int(live.sum())
    w.varint(1, nvals)
    if nvals:
        k = dtype.kind
        if k in (TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64,
                 TypeKind.DECIMAL):
            s = PbWriter()
            s.varint(1, _zz(int(data[live].min())) )
            s.varint(2, _zz(int(data[live].max())))
            # sint64 via zigzag: IntegerStatistics min/max are sint64
            w.msg(2, s)
        elif k in (TypeKind.FLOAT32, TypeKind.FLOAT64):
            s = PbWriter()
            s.double(1, float(data[live].min()))
            s.double(2, float(data[live].max()))
            w.msg(3, s)
        elif dtype.is_string:
            vals = [bytes(data[i, : lengths[i]]) for i in np.flatnonzero(live)]
            s = PbWriter()
            s.bytes_(1, min(vals))
            s.bytes_(2, max(vals))
            w.msg(4, s)
        elif k == TypeKind.DATE32:
            s = PbWriter()
            s.varint(1, _zz(int(data[live].min())))
            s.varint(2, _zz(int(data[live].max())))
            w.msg(7, s)
    w.varint(10, 0 if bool(live.all()) else 1)  # hasNull
    return w


def write_orc(
    path: str,
    schema: Schema,
    columns: Dict[str, Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]],
    stripe_rows: int = 65536,
    compression: str = "none",
) -> None:
    """columns: name -> (data, validity|None, lengths|None for strings).
    ARRAY-of-primitive fields instead take the reader's 4-tuple shape:
    (None, validity|None, lengths, (elem_data_2d, elem_valid_2d)).
    MAP/STRUCT/nested-LIST fields take a plain python value list
    (None/list/dict per row — the reader's compound-path shape).
    ``compression``: "none", "zlib" (Spark's ORC default), "zstd",
    "snappy", or "lz4" — every stream, stripe footer, Metadata and
    Footer region gets the chunked [u24 header][block] framing; the
    PostScript stays raw."""
    comp_kind = {"none": C_NONE, "zlib": C_ZLIB, "zstd": C_ZSTD,
                 "snappy": C_SNAPPY, "lz4": C_LZ4}[compression]
    any_name = next(iter(columns))
    any_col = columns[any_name]
    any_dt = schema.field(any_name).dtype
    if _is_compound(any_dt):
        n = len(any_col)
    elif any_dt.kind == TypeKind.ARRAY:
        n = any_col[2].shape[0]  # 4-tuple shape: lengths carries rows
    else:
        n = any_col[0].shape[0]
    from .fs import get_fs

    # preorder type ids: root = 0; compound fields consume one slot per
    # nested type-tree node
    field_type_ids: List[int] = []
    _next = 1
    for _fld in schema.fields:
        field_type_ids.append(_next)
        _next += _type_size(_fld.dtype)
    total_type_ids = _next

    with get_fs(path).create(path) as f:
        f.write(MAGIC)
        stripe_infos: List[Tuple[int, int, int, int]] = []  # offset, dataLen, footLen, rows
        stripe_stats: List[List[bytes]] = []
        for start in range(0, max(n, 1), stripe_rows):
            rows = min(stripe_rows, n - start)
            if rows <= 0 and n > 0:
                break
            offset = f.tell()
            streams: List[_Stream] = []
            stats_msgs: List[bytes] = []
            # root struct stats
            root = PbWriter()
            root.varint(1, rows)
            root.varint(10, 0)
            stats_msgs.append(root.getvalue())
            for ci, fld in zip(field_type_ids, schema.fields):
                if _is_compound(fld.dtype):
                    vals = columns[fld.name][start : start + rows]
                    counts: Dict[int, Tuple[int, bool]] = {}
                    streams.extend(_encode_pyvalues(ci, fld.dtype, vals, counts))
                    for slot in range(ci, ci + _type_size(fld.dtype)):
                        nvals, had_null = counts.get(slot, (0, False))
                        cw = PbWriter()
                        cw.varint(1, nvals)
                        cw.varint(10, 1 if had_null else 0)
                        stats_msgs.append(cw.getvalue())
                    continue
                if fld.dtype.kind == TypeKind.ARRAY:
                    _, validity, lengths, (edata, evalid) = columns[fld.name]
                    if validity is None:
                        validity = np.ones(lengths.shape[0], bool)
                    sl = slice(start, start + rows)
                    streams.extend(_encode_list_column(
                        ci, fld.dtype, validity[sl], lengths[sl],
                        edata[sl], evalid[sl]))
                    # truthful per-slot stats (SARG readers prune
                    # `IS NULL` stripes on hasNull): parent slot =
                    # live rows; child slot = live elements within
                    # live rows' lengths
                    v_sl, ln_sl, ev_sl = validity[sl], lengths[sl], evalid[sl]
                    within = (np.arange(ev_sl.shape[1])[None, :]
                              < ln_sl[:, None]) & v_sl[:, None]
                    live_elems = within & ev_sl
                    for nvals, had_null in (
                        (int(v_sl.sum()), not bool(v_sl.all())),
                        (int(live_elems.sum()),
                         bool((within & ~ev_sl).any())),
                    ):
                        cw = PbWriter()
                        cw.varint(1, nvals)
                        cw.varint(10, 1 if had_null else 0)
                        stats_msgs.append(cw.getvalue())
                    continue
                data, validity, lengths = columns[fld.name]
                if validity is None:
                    validity = np.ones(data.shape[0], bool)
                sl = slice(start, start + rows)
                d, v = data[sl], validity[sl]
                ln = None if lengths is None else lengths[sl]
                streams.extend(_encode_column(ci, fld.dtype, d, v, ln))
                stats_msgs.append(_col_stats(fld.dtype, d, v, ln).getvalue())
            # stream lengths in the stripe footer are the COMPRESSED
            # on-disk lengths (readers slice the data region by them,
            # then undo the chunked framing per stream)
            wire = [orc_compress(s.data, comp_kind) for s in streams]
            data_len = 0
            for w in wire:
                f.write(w)
                data_len += len(w)
            sf = PbWriter()
            for s, w in zip(streams, wire):
                m = PbWriter()
                m.varint(1, s.kind)
                m.varint(2, s.column)
                m.varint(3, len(w))
                sf.msg(1, m)
            for _ in range(total_type_ids):
                enc = PbWriter()
                enc.varint(1, 0)  # DIRECT
                sf.msg(2, enc)
            foot = orc_compress(sf.getvalue(), comp_kind)
            f.write(foot)
            stripe_infos.append((offset, data_len, len(foot), rows))
            stripe_stats.append(stats_msgs)
            if n == 0:
                break

        # Metadata: per-stripe column statistics
        md = PbWriter()
        for msgs in stripe_stats:
            ss = PbWriter()
            for m in msgs:
                ss.bytes_(1, m)
            md.msg(1, ss)
        md_bytes = orc_compress(md.getvalue(), comp_kind)
        f.write(md_bytes)

        # Footer
        ft = PbWriter()
        ft.varint(1, 3)  # headerLength ("ORC")
        content_len = stripe_infos[-1][0] + stripe_infos[-1][1] + stripe_infos[-1][2] if stripe_infos else 3
        ft.varint(2, content_len)
        for off, dl, fl, rows in stripe_infos:
            si = PbWriter()
            si.varint(1, off)
            si.varint(2, 0)   # indexLength (no row index in subset)
            si.varint(3, dl)
            si.varint(4, fl)
            si.varint(5, rows)
            ft.msg(3, si)
        root_t = PbWriter()
        root_t.varint(1, K_STRUCT)
        for tid in field_type_ids:
            root_t.varint(2, tid)
        for fld in schema.fields:
            root_t.string(3, fld.name)
        ft.msg(4, root_t)

        def emit_type(dt: DataType, tid: int) -> None:
            t = PbWriter()
            if dt.kind == TypeKind.ARRAY:
                t.varint(1, K_LIST)
                t.varint(2, tid + 1)
                ft.msg(4, t)
                emit_type(dt.elem, tid + 1)
                return
            if dt.kind == TypeKind.MAP:
                t.varint(1, K_MAP)
                kid, vid = tid + 1, tid + 1 + _type_size(dt.key)
                t.varint(2, kid)
                t.varint(2, vid)
                ft.msg(4, t)
                emit_type(dt.key, kid)
                emit_type(dt.value, vid)
                return
            if dt.kind == TypeKind.STRUCT:
                t.varint(1, K_STRUCT)
                sub = tid + 1
                for f2 in dt.struct_fields:
                    t.varint(2, sub)
                    sub += _type_size(f2.dtype)
                for f2 in dt.struct_fields:
                    t.string(3, f2.name)
                ft.msg(4, t)
                sub = tid + 1
                for f2 in dt.struct_fields:
                    emit_type(f2.dtype, sub)
                    sub += _type_size(f2.dtype)
                return
            t.varint(1, _orc_kind(dt))
            if dt.is_decimal:
                t.varint(5, dt.precision)
                t.varint(6, dt.scale)
            ft.msg(4, t)

        for tid, fld in zip(field_type_ids, schema.fields):
            emit_type(fld.dtype, tid)
        ft.varint(6, n)  # numberOfRows
        ft_bytes = orc_compress(ft.getvalue(), comp_kind)
        f.write(ft_bytes)

        ps = PbWriter()
        ps.varint(1, len(ft_bytes))
        ps.varint(2, comp_kind)
        ps.varint(3, 65536)
        ps.bytes_(4, _uvarint(0) + _uvarint(12))  # version [0, 12] packed
        ps.varint(5, len(md_bytes))
        ps.varint(6, 1)
        ps.string(8000, "ORC")
        ps_bytes = ps.getvalue()
        f.write(ps_bytes)
        assert len(ps_bytes) < 256
        f.write(bytes([len(ps_bytes)]))


# --------------------------------------------------------------- reader

@dataclass
class StripeInfo:
    offset: int
    data_length: int
    footer_length: int
    rows: int
    # per-column stats: name -> (min, max, has_null) python values
    stats: Dict[str, Tuple] = field(default_factory=dict)


@dataclass
class OrcFileMeta:
    schema: Schema
    stripes: List[StripeInfo]
    num_rows: int
    compression: int = C_NONE
    # per top-level field: its column id in the flattened type tree
    # (flat files: 1..n; a LIST field consumes its child's id too)
    field_ids: List[int] = None
    # field name -> element column id (LIST fields only)
    child_ids: dict = None
    # type id -> (kind, subtype ids): the full flattened type tree,
    # needed to walk MAP/STRUCT/nested-LIST columns
    type_tree: dict = None


def _decode_type(b: bytes) -> Tuple[int, List[int], List[str], int, int]:
    kind = 0
    subtypes: List[int] = []
    names: List[str] = []
    precision = scale = 0
    for fid, wt, v in PbReader(b).fields():
        if fid == 1:
            kind = v
        elif fid == 2:
            if isinstance(v, (bytes, bytearray)):
                # packed repeated uint32 (ORC C++ writers)
                pos = 0
                while pos < len(v):
                    u = 0
                    shift = 0
                    while True:
                        byte = v[pos]
                        pos += 1
                        u |= (byte & 0x7F) << shift
                        if not byte & 0x80:
                            break
                        shift += 7
                    subtypes.append(u)
            else:
                subtypes.append(v)
        elif fid == 3:
            names.append(v.decode("utf-8"))
        elif fid == 5:
            precision = v
        elif fid == 6:
            scale = v
    return kind, subtypes, names, precision, scale


_KIND_TO_DTYPE = {
    K_BOOLEAN: DataType.bool_(),
    K_BYTE: DataType.int8(),
    K_SHORT: DataType.int16(),
    K_INT: DataType.int32(),
    K_LONG: DataType.int64(),
    K_FLOAT: DataType.float32(),
    K_DOUBLE: DataType.float64(),
    K_DATE: DataType.date32(),
    K_TIMESTAMP: DataType.timestamp(),
}


def _decode_col_stats(b: bytes):
    mn = mx = None
    has_null = False
    for fid, wt, v in PbReader(b).fields():
        if fid == 10:
            has_null = bool(v)
        elif fid in (2, 7):  # IntegerStatistics / DateStatistics
            for f2, _, v2 in PbReader(v).fields():
                if f2 == 1:
                    mn = _unzz(v2)
                elif f2 == 2:
                    mx = _unzz(v2)
        elif fid == 3:  # DoubleStatistics
            for f2, _, v2 in PbReader(v).fields():
                if f2 == 1:
                    mn = v2
                elif f2 == 2:
                    mx = v2
        elif fid == 4:  # StringStatistics
            for f2, _, v2 in PbReader(v).fields():
                if f2 == 1:
                    mn = v2
                elif f2 == 2:
                    mx = v2
    return mn, mx, has_null


def read_metadata(path: str, list_elems: int = 16, string_width: int = 64) -> OrcFileMeta:
    from .fs import get_fs

    with get_fs(path).open(path) as f:
        f.seek(0, 2)
        size = f.tell()
        f.seek(size - 1)
        ps_len = f.read(1)[0]
        f.seek(size - 1 - ps_len)
        ps = f.read(ps_len)
        footer_len = md_len = 0
        magic = None
        compression = 0
        for fid, wt, v in PbReader(ps).fields():
            if fid == 1:
                footer_len = v
            elif fid == 2:
                compression = v
            elif fid == 5:
                md_len = v
            elif fid == 8000:
                magic = v
        if magic != b"ORC":
            raise ValueError(f"{path}: not an ORC file")
        f.seek(size - 1 - ps_len - footer_len)
        footer = orc_decompress(f.read(footer_len), compression)
        f.seek(size - 1 - ps_len - footer_len - md_len)
        md = orc_decompress(f.read(md_len), compression)

    stripes: List[StripeInfo] = []
    types: List[bytes] = []
    num_rows = 0
    for fid, wt, v in PbReader(footer).fields():
        if fid == 3:
            off = il = dl = fl = rows = 0
            for f2, _, v2 in PbReader(v).fields():
                if f2 == 1:
                    off = v2
                elif f2 == 2:
                    il = v2
                elif f2 == 3:
                    dl = v2
                elif f2 == 4:
                    fl = v2
                elif f2 == 5:
                    rows = v2
            stripes.append(StripeInfo(off + il, dl, fl, rows))
        elif fid == 4:
            types.append(v)
        elif fid == 6:
            num_rows = v

    kind0, subtypes, names, _, _ = _decode_type(types[0])
    if kind0 != K_STRUCT:
        raise NotImplementedError("ORC subset: root must be a struct")
    fields = []
    field_ids: List[int] = []
    child_ids: dict = {}

    def prim_dtype(kind, precision, scale):
        if kind == K_DECIMAL:
            return DataType.decimal(precision or 18, scale)
        if kind == K_STRING:
            return DataType.string(string_width)
        if kind in _KIND_TO_DTYPE:
            return _KIND_TO_DTYPE[kind]
        raise NotImplementedError(f"ORC subset: type kind {kind}")

    type_tree: dict = {}

    def full_dtype(tid: int) -> DataType:
        kind, subs, cnames, precision, scale = _decode_type(types[tid])
        type_tree[tid] = (kind, list(subs))
        if kind == K_LIST:
            return DataType.array(full_dtype(subs[0]), list_elems)
        if kind == K_MAP:
            return DataType.map(full_dtype(subs[0]), full_dtype(subs[1]),
                                list_elems)
        if kind == K_STRUCT:
            return DataType.struct(
                [Field(n, full_dtype(s2)) for n, s2 in zip(cnames, subs)])
        return prim_dtype(kind, precision, scale)

    for name, st in zip(names, subtypes):
        kind, subs, _, precision, scale = _decode_type(types[st])
        field_ids.append(st)
        dt = full_dtype(st)
        if kind == K_LIST and not (dt.elem.is_nested or dt.elem.is_string):
            # flat LIST keeps the vectorized fast path in read_stripe
            child_ids[name] = subs[0]
        fields.append(Field(name, dt))
    schema = Schema(fields)

    # stripe statistics from the Metadata section
    stripe_stats: List[List[bytes]] = []
    for fid, wt, v in PbReader(md).fields():
        if fid == 1:
            cols = [v2 for f2, _, v2 in PbReader(v).fields() if f2 == 1]
            stripe_stats.append(cols)
    for si, st in enumerate(stripes):
        if si < len(stripe_stats):
            cols = stripe_stats[si]
            for ci, fld in zip(field_ids, schema.fields):
                if ci < len(cols):
                    st.stats[fld.name] = _decode_col_stats(cols[ci])
    return OrcFileMeta(schema, stripes, num_rows, compression,
                       field_ids=field_ids, child_ids=child_ids,
                       type_tree=type_tree)


S_ROW_INDEX, S_BLOOM_FILTER, S_BLOOM_FILTER_UTF8 = 6, 7, 8


def _rescale_decimals(vals: np.ndarray, scales: np.ndarray,
                      declared: int) -> np.ndarray:
    """Align per-value decimal scales (the SECONDARY stream) to the
    declared type scale.  Writers normally emit the declared scale for
    every value, but the spec allows differing ones; a value with MORE
    fractional digits than the declared type cannot be represented
    exactly and is gated."""
    scales = np.asarray(scales[: vals.size], np.int64)
    if np.all(scales == declared):
        return vals
    if int(scales.max(initial=declared)) > declared:
        raise NotImplementedError(
            f"ORC subset: decimal value scale {int(scales.max())} exceeds "
            f"the declared scale {declared}"
        )
    return vals * (10 ** (declared - scales)).astype(np.int64)


def _varint_stream_decode(raw: bytes, nvals: int) -> np.ndarray:
    """Unbounded zigzag varints (decimal DATA stream)."""
    vals = np.empty(nvals, np.int64)
    pos = 0
    for i in range(nvals):
        v = 0
        shift = 0
        while True:
            b = raw[pos]
            pos += 1
            v |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
        vals[i] = _unzz(v)
    return vals


def read_stripe(
    path: str, meta: OrcFileMeta, stripe: StripeInfo
) -> Dict[str, Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]]:
    """name -> (data, validity, lengths|None); strings return (rows, W)
    uint8 data at the column's declared width.

    Handles DIRECT (RLEv1) and DIRECT_V2 (RLEv2) integer encodings,
    DICTIONARY(_V2) strings, and per-stream compressed framing."""
    from .fs import get_fs

    comp = meta.compression
    with get_fs(path).open(path) as f:
        f.seek(stripe.offset)
        blob = f.read(stripe.data_length)
        foot = orc_decompress(f.read(stripe.footer_length), comp)
    streams: List[Tuple[int, int, int]] = []  # kind, column, length
    encodings: List[Tuple[int, int]] = []     # (encoding kind, dict size)
    for fid, wt, v in PbReader(foot).fields():
        if fid == 1:
            kind = column = length = 0
            for f2, _, v2 in PbReader(v).fields():
                if f2 == 1:
                    kind = v2
                elif f2 == 2:
                    column = v2
                elif f2 == 3:
                    length = v2
            streams.append((kind, column, length))
        elif fid == 2:
            ek = ds = 0
            for f2, _, v2 in PbReader(v).fields():
                if f2 == 1:
                    ek = v2
                elif f2 == 2:
                    ds = v2
            encodings.append((ek, ds))

    # data-region streams appear in file order; index-region streams
    # (ROW_INDEX/BLOOM) precede them and are NOT in our blob
    per_col: Dict[int, Dict[int, bytes]] = {}
    off = 0
    for kind, column, length in streams:
        if kind in (S_ROW_INDEX, S_BLOOM_FILTER, S_BLOOM_FILTER_UTF8):
            continue
        per_col.setdefault(column, {})[kind] = blob[off : off + length]
        off += length

    def dec(ci: int, kind: int) -> bytes:
        return orc_decompress(per_col.get(ci, {}).get(kind, b""), comp)

    def int_decode(raw: bytes, nvals: int, signed: bool, enc: int) -> np.ndarray:
        if enc in (E_DIRECT_V2, E_DICTIONARY_V2):
            return _rlev2_decode(raw, nvals, signed)
        return _rlev1_decode(raw, nvals, signed)

    tree = meta.type_tree or {}

    def decode_nested(tid: int, dtype: DataType, count: int) -> list:
        """Recursive python-value decode for compound columns
        (MAP/STRUCT/nested LIST/list-of-string) — each nesting level
        carries its own PRESENT stream; children hold one entry per
        non-null parent slot (LIST/MAP: per element)."""
        stt = per_col.get(tid, {})
        encn = encodings[tid][0] if tid < len(encodings) else E_DIRECT
        dsz = encodings[tid][1] if tid < len(encodings) else 0
        validity = (
            _bool_decode(dec(tid, S_PRESENT), count)
            if S_PRESENT in stt
            else np.ones(count, bool)
        )
        nv = int(validity.sum())
        k = dtype.kind

        def scatter(vals: list) -> list:
            it = iter(vals)
            return [next(it) if ok else None for ok in validity]

        if k == TypeKind.ARRAY:
            ln = int_decode(dec(tid, S_LENGTH), nv, False, encn)
            elems = decode_nested(tree[tid][1][0], dtype.elem, int(ln.sum()))
            vals, pos = [], 0
            for L in ln:
                vals.append(elems[pos : pos + int(L)])
                pos += int(L)
            return scatter(vals)
        if k == TypeKind.MAP:
            ln = int_decode(dec(tid, S_LENGTH), nv, False, encn)
            total = int(ln.sum())
            keys = decode_nested(tree[tid][1][0], dtype.key, total)
            mvals = decode_nested(tree[tid][1][1], dtype.value, total)
            vals, pos = [], 0
            for L in ln:
                vals.append(dict(zip(keys[pos : pos + int(L)],
                                     mvals[pos : pos + int(L)])))
                pos += int(L)
            return scatter(vals)
        if k == TypeKind.STRUCT:
            kids = [
                decode_nested(s2, f2.dtype, nv)
                for s2, f2 in zip(tree[tid][1], dtype.struct_fields)
            ]
            vals = [
                {f2.name: kid[j] for f2, kid in zip(dtype.struct_fields, kids)}
                for j in range(nv)
            ]
            return scatter(vals)
        if dtype.is_string:
            if encn in (E_DICTIONARY, E_DICTIONARY_V2):
                dlen = int_decode(dec(tid, S_LENGTH), dsz, False, encn)
                dbody = dec(tid, S_DICTIONARY_DATA)
                offs = np.concatenate([[0], np.cumsum(dlen)])
                words = [
                    bytes(dbody[int(offs[i]) : int(offs[i + 1])]).decode()
                    for i in range(dsz)
                ]
                indices = int_decode(dec(tid, S_DATA), nv, False, encn)
                return scatter([words[int(i)] for i in indices])
            ln = int_decode(dec(tid, S_LENGTH), nv, False, encn)
            body = dec(tid, S_DATA)
            vals, pos = [], 0
            for L in ln:
                vals.append(bytes(body[pos : pos + int(L)]).decode())
                pos += int(L)
            return scatter(vals)
        if k == TypeKind.BOOL:
            return scatter([bool(v) for v in _bool_decode(dec(tid, S_DATA), nv)])
        if k == TypeKind.DECIMAL:
            import decimal as _dec

            unscaled = _varint_stream_decode(dec(tid, S_DATA), nv)
            unscaled = _rescale_decimals(
                unscaled, int_decode(dec(tid, S_SECONDARY), nv, True, encn),
                dtype.scale)
            q = _dec.Decimal(1).scaleb(-dtype.scale)
            return scatter([_dec.Decimal(int(v)).scaleb(-dtype.scale)
                            .quantize(q) for v in unscaled])
        if k in (TypeKind.INT8,):
            return scatter([int(v) for v in np.frombuffer(
                _byte_rle_decode(dec(tid, S_DATA), nv), np.int8)])
        if k in (TypeKind.INT16, TypeKind.INT32, TypeKind.INT64,
                 TypeKind.DATE32):
            return scatter([int(v) for v in
                            int_decode(dec(tid, S_DATA), nv, True, encn)])
        if k in (TypeKind.FLOAT32, TypeKind.FLOAT64):
            return scatter([float(v) for v in np.frombuffer(
                dec(tid, S_DATA), dtype.np_dtype, nv)])
        if k == TypeKind.TIMESTAMP:
            return scatter([int(v) for v in _decode_ts_micros(
                int_decode(dec(tid, S_DATA), nv, True, encn),
                int_decode(dec(tid, S_SECONDARY), nv, False, encn))])
        raise NotImplementedError(f"ORC subset: nested element {dtype!r}")

    rows = stripe.rows
    out = {}
    ids = meta.field_ids or list(range(1, len(meta.schema.fields) + 1))
    for ci, fld in zip(ids, meta.schema.fields):
        st = per_col.get(ci, {})
        enc = encodings[ci][0] if ci < len(encodings) else E_DIRECT
        dict_size = encodings[ci][1] if ci < len(encodings) else 0
        if _is_compound(fld.dtype):
            # compound columns (maps, structs, nested/str lists):
            # recursive python-value decode (incl. its own PRESENT);
            # the scan layer builds the padded nested Column via
            # column_from_pylist
            out[fld.name] = ("py", decode_nested(ci, fld.dtype, rows))
            continue
        validity = (
            _bool_decode(dec(ci, S_PRESENT), rows)
            if S_PRESENT in st
            else np.ones(rows, bool)
        )
        nvals = int(validity.sum())
        k = fld.dtype.kind
        lengths = None
        if k == TypeKind.BOOL:
            vals = _bool_decode(dec(ci, S_DATA), nvals)
            data = np.zeros(rows, bool)
            data[validity] = vals
        elif k == TypeKind.INT8:
            vals = np.frombuffer(_byte_rle_decode(dec(ci, S_DATA), nvals), np.int8)
            data = np.zeros(rows, np.int8)
            data[validity] = vals
        elif k in (TypeKind.INT16, TypeKind.INT32, TypeKind.INT64, TypeKind.DATE32,
                   TypeKind.DECIMAL):
            if k == TypeKind.DECIMAL:
                vals = _varint_stream_decode(dec(ci, S_DATA), nvals)
                vals = _rescale_decimals(
                    vals, int_decode(dec(ci, S_SECONDARY), nvals, True, enc),
                    fld.dtype.scale)
            else:
                vals = int_decode(dec(ci, S_DATA), nvals, True, enc)
            data = np.zeros(rows, fld.dtype.np_dtype)
            data[validity] = vals.astype(fld.dtype.np_dtype)
        elif k == TypeKind.TIMESTAMP:
            vals = _decode_ts_micros(
                int_decode(dec(ci, S_DATA), nvals, True, enc),
                int_decode(dec(ci, S_SECONDARY), nvals, False, enc))
            data = np.zeros(rows, np.int64)
            data[validity] = vals
        elif k in (TypeKind.FLOAT32, TypeKind.FLOAT64):
            vals = np.frombuffer(dec(ci, S_DATA), fld.dtype.np_dtype, nvals)
            data = np.zeros(rows, fld.dtype.np_dtype)
            data[validity] = vals
        elif fld.dtype.is_string:
            w = fld.dtype.string_width
            data = np.zeros((rows, w), np.uint8)
            lengths = np.zeros(rows, np.int32)
            idxs = np.flatnonzero(validity)
            if enc in (E_DICTIONARY, E_DICTIONARY_V2):
                dlen = int_decode(dec(ci, S_LENGTH), dict_size, False, enc)
                dbody = dec(ci, S_DICTIONARY_DATA)
                offs = np.concatenate([[0], np.cumsum(dlen)])
                indices = int_decode(dec(ci, S_DATA), nvals, False, enc)
                for j, i in enumerate(idxs):
                    di = int(indices[j])
                    L = int(dlen[di])
                    data[i, : min(L, w)] = np.frombuffer(
                        dbody, np.uint8, min(L, w), int(offs[di])
                    )
                    lengths[i] = min(L, w)
            else:
                ln = int_decode(dec(ci, S_LENGTH), nvals, False, enc)
                body = dec(ci, S_DATA)
                pos = 0
                for j, i in enumerate(idxs):
                    L = int(ln[j])
                    data[i, : min(L, w)] = np.frombuffer(body, np.uint8, min(L, w), pos)
                    lengths[i] = min(L, w)
                    pos += L
        elif fld.dtype.kind == TypeKind.ARRAY:
            # LIST of primitive: LENGTH stream at the list column,
            # PRESENT+DATA at the child column id; rectangularized to
            # the declared max_elems
            et = fld.dtype.elem
            m = fld.dtype.max_elems
            cid = (meta.child_ids or {}).get(fld.name, ci + 1)
            ln = int_decode(dec(ci, S_LENGTH), nvals, False, enc)
            if ln.size and int(ln.max()) > m:
                # gated, not silently wrong: a list longer than the
                # padded layout's declared cap cannot be represented
                raise NotImplementedError(
                    f"ORC subset: list length {int(ln.max())} exceeds the "
                    f"declared max_elems {m} for {fld.name!r}; re-read with "
                    f"a wider ARRAY type"
                )
            lengths = np.zeros(rows, np.int32)
            lengths[validity] = ln.astype(np.int32)
            total = int(ln.sum())
            cst = per_col.get(cid, {})
            cenc = encodings[cid][0] if cid < len(encodings) else E_DIRECT
            evalid = (
                _bool_decode(dec(cid, S_PRESENT), total)
                if S_PRESENT in cst
                else np.ones(total, bool)
            )
            cn = int(evalid.sum())
            ek = et.kind
            if ek in (TypeKind.INT16, TypeKind.INT32, TypeKind.INT64,
                      TypeKind.DATE32, TypeKind.DECIMAL):
                if ek == TypeKind.DECIMAL:
                    cvals = _varint_stream_decode(dec(cid, S_DATA), cn)
                    cvals = _rescale_decimals(
                        cvals,
                        int_decode(dec(cid, S_SECONDARY), cn, True, cenc),
                        et.scale)
                else:
                    cvals = int_decode(dec(cid, S_DATA), cn, True, cenc)
            elif ek in (TypeKind.FLOAT32, TypeKind.FLOAT64):
                cvals = np.frombuffer(dec(cid, S_DATA), et.np_dtype, cn)
            elif ek == TypeKind.TIMESTAMP:
                cvals = _decode_ts_micros(
                    int_decode(dec(cid, S_DATA), cn, True, cenc),
                    int_decode(dec(cid, S_SECONDARY), cn, False, cenc))
            else:
                raise NotImplementedError(f"ORC subset: list element {et!r}")
            flat = np.zeros(total, et.np_dtype)
            flat[evalid] = cvals.astype(et.np_dtype, copy=False)
            edata = np.zeros((rows, m), et.np_dtype)
            evalid2 = np.zeros((rows, m), bool)
            pos = 0
            for j, r in enumerate(np.flatnonzero(validity)):
                L = int(ln[j])
                k = min(L, m)
                edata[r, :k] = flat[pos : pos + k]
                evalid2[r, :k] = evalid[pos : pos + k]
                pos += L
            out[fld.name] = (None, validity, np.minimum(lengths, m),
                             (edata, evalid2))
            continue
        else:
            raise NotImplementedError(f"ORC subset: {fld.dtype!r}")
        out[fld.name] = (data, validity, lengths)
    return out
