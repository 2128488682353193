"""Columnar batches: the unit of data flow between operators.

The reference streams Arrow ``RecordBatch``es between DataFusion
operators and coalesces them to ``batch_size``
(``datafusion-ext-commons/src/streams/coalesce_stream.rs``).  Here a
batch is a set of dense JAX arrays padded to a *bucketed capacity*:

- ``num_rows`` is a host-side int; rows ``[num_rows, capacity)`` are
  padding (validity False, data zeroed).
- capacities are powers of two (>= conf.MIN_CAPACITY), so each operator
  kernel is compiled for at most log2(max/min) shapes — XLA requires
  static shapes and this is the shape-bucketing strategy from
  SURVEY.md §7.
- all device code must treat padding as absent: kernels either mask by
  ``valid_mask()`` or rely on zeroed padding being a no-op (e.g. sums).

Columns are plain pytrees (data, validity[, lengths]) so whole batches
can flow through ``jax.jit`` boundaries without host sync; ``num_rows``
stays static.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import conf
from .schema import DataType, Field, Schema, TypeKind, string_width_for

Array = Union[jnp.ndarray, np.ndarray]


def bucket_capacity(n: int) -> int:
    """Round row count up to the capacity bucket (power of two)."""
    cap = int(conf.MIN_CAPACITY.get())
    while cap < n:
        cap *= 2
    return cap


@jax.tree_util.register_pytree_node_class
@dataclass
class Column:
    """One column: data + validity (+ byte lengths for strings;
    + children for nested types).

    ``dtype`` is static metadata (pytree aux), buffers are leaves.

    Nested layouts (fixed max-elements ``M = dtype.max_elems``, padded —
    the TPU-first re-design of Arrow's variable-length List/Map/Struct,
    ≙ the reference's nested Arrow columns in blaze.proto:738-941):

    - ARRAY(T, M):  ``data=None``, ``validity (cap,)`` row validity,
      ``lengths (cap,)`` element counts, ``children=(elem,)`` where
      ``elem`` is a Column of T whose buffers carry a leading element
      axis: data ``(cap, M)`` (strings ``(cap, M, W)``), validity
      ``(cap, M)`` element validity, lengths ``(cap, M)`` for strings.
    - MAP(K, V, M): like ARRAY with ``children=(keys, values)`` sharing
      ``lengths``; keys are never null per Spark map semantics.
    - STRUCT(fields): ``data=None``, ``validity (cap,)``,
      ``children`` = one regular Column per field.
    """

    dtype: DataType
    data: Optional[Array]             # (cap,) / (cap, W) strings / None nested
    validity: Array                   # bool (cap,)
    lengths: Optional[Array] = None   # int32: (cap,) strings+array/map counts
    children: Optional[Tuple["Column", ...]] = None  # nested types only

    # -- pytree protocol (None slots are empty subtrees; child Columns
    # flatten recursively) --
    def tree_flatten(self):
        return (self.data, self.validity, self.lengths, self.children), self.dtype

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        data, validity, lengths, children = leaves
        return cls(aux, data, validity, lengths, children)

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    def to_device(self) -> "Column":
        if self.dtype.kind == TypeKind.OPAQUE:
            # opaque python objects never leave the host
            # (≙ UserDefinedArray's JVM-object storage, uda.rs:25)
            return self
        as_j = lambda a: None if a is None else (a if isinstance(a, jnp.ndarray) else jnp.asarray(a))
        return Column(
            self.dtype,
            as_j(self.data),
            as_j(self.validity),
            as_j(self.lengths),
            None if self.children is None else tuple(c.to_device() for c in self.children),
        )

    def to_host(self) -> "Column":
        as_n = lambda a: None if a is None else np.asarray(a)
        return Column(
            self.dtype,
            as_n(self.data),
            as_n(self.validity),
            as_n(self.lengths),
            None if self.children is None else tuple(c.to_host() for c in self.children),
        )

    def take(self, indices: Array) -> "Column":
        """Gather rows by index (indices must point at valid rows or be
        masked by the caller).  Nested children carry a leading row
        axis, so the same axis-0 gather applies recursively."""
        if self.dtype.kind == TypeKind.OPAQUE:
            h = np.asarray(indices)
            return Column(
                self.dtype,
                np.take(self.data, h, axis=0),
                np.take(np.asarray(self.validity), h),
            )
        idx = indices
        g = lambda a: None if a is None else jnp.take(a, idx, axis=0)
        return Column(
            self.dtype,
            g(self.data),
            g(self.validity),
            g(self.lengths),
            None if self.children is None else tuple(c.take(idx) for c in self.children),
        )


#: The device row mask of a FULL batch, all True, one a capacity for
#: the process: ``RecordBatch.to_device_counted`` gives it to every
#: column whose validity equals the row mask.  One array serves as the
#: validity of many columns and batches because nothing in this package
#: donates a buffer to a program or deletes an array: keep it so.  The
#: capacity buckets bound the dict; scan threads stage side by side.
_FULL_MASKS: Dict[int, jnp.ndarray] = {}
_FULL_MASKS_LOCK = threading.Lock()


def _full_mask(cap: int) -> jnp.ndarray:
    m = _FULL_MASKS.get(cap)
    if m is None:
        with _FULL_MASKS_LOCK:
            m = _FULL_MASKS.get(cap)
            if m is None:
                # uncommitted on the default device, as every staged
                # buffer is: programs see the signature they always saw
                m = _FULL_MASKS[cap] = jnp.asarray(np.ones(cap, np.bool_))
    return m


def _is_row_mask(v, n: int) -> bool:
    """True when host validity ``v`` is ``arange(cap) < n``."""
    return (type(v) is np.ndarray and v.ndim == 1 and v.dtype == np.bool_
            and bool(v[:n].all()) and not v[n:].any())


def _host_arrays(c: Column) -> int:
    """Host buffers ``c.to_device()`` transfers, children's included."""
    if c.dtype.kind == TypeKind.OPAQUE:
        return 0
    k = sum(a is not None and not isinstance(a, jnp.ndarray)
            for a in (c.data, c.validity, c.lengths))
    return k + sum(_host_arrays(ch) for ch in c.children or ())


def _pad_1d(a: np.ndarray, cap: int) -> np.ndarray:
    if a.shape[0] == cap:
        return a
    out = np.zeros((cap,) + a.shape[1:], dtype=a.dtype)
    out[: a.shape[0]] = a
    return out


def column_from_numpy(
    dtype: DataType,
    values: np.ndarray,
    validity: Optional[np.ndarray] = None,
    capacity: Optional[int] = None,
) -> Column:
    n = values.shape[0]
    cap = capacity or bucket_capacity(n)
    if validity is None:
        validity = np.ones(n, dtype=np.bool_)
    validity = _pad_1d(validity.astype(np.bool_), cap)
    if dtype.is_string:
        raise ValueError("use column_from_strings for string columns")
    data = _pad_1d(values.astype(dtype.np_dtype, copy=False), cap)
    # zero out invalid rows so padded/invalid data never leaks into kernels
    data = np.where(validity, data, np.zeros((), dtype=data.dtype))
    return Column(dtype, data, validity)


def column_from_strings(
    values: Sequence[Optional[Union[str, bytes]]],
    width: Optional[int] = None,
    capacity: Optional[int] = None,
    dtype: Optional[DataType] = None,
) -> Column:
    bs = [
        (v.encode("utf-8") if isinstance(v, str) else v) if v is not None else b""
        for v in values
    ]
    n = len(bs)
    if width is None:
        width = (
            dtype.string_width
            if dtype is not None
            else string_width_for(max((len(b) for b in bs), default=1))
        )
    if any(len(b) > width for b in bs):
        raise ValueError(f"string longer than column width {width}")
    if dtype is None:
        dtype = DataType.string(width)
    cap = capacity or bucket_capacity(n)
    data = np.zeros((cap, width), dtype=np.uint8)
    lengths = np.zeros(cap, dtype=np.int32)
    validity = np.zeros(cap, dtype=np.bool_)
    for i, (v, b) in enumerate(zip(values, bs)):
        if v is None:
            continue
        validity[i] = True
        lengths[i] = len(b)
        data[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
    return Column(dtype, data, validity, lengths)


def _reshape_leading(col: Column, cap: int, m: int) -> Column:
    """Reshape a flat (cap*m, ...) column into element layout (cap, m, ...)."""
    rs = lambda a: None if a is None else np.asarray(a).reshape((cap, m) + a.shape[1:])
    return Column(
        col.dtype,
        rs(col.data),
        rs(col.validity),
        rs(col.lengths),
        None if col.children is None else tuple(_reshape_leading(c, cap, m) for c in col.children),
    )


def _flatten_leading(col: Column) -> Column:
    """Inverse of _reshape_leading: (cap, m, ...) -> (cap*m, ...)."""
    fl = lambda a: None if a is None else np.asarray(a).reshape((-1,) + a.shape[2:])
    return Column(
        col.dtype,
        fl(col.data),
        fl(col.validity),
        fl(col.lengths),
        None if col.children is None else tuple(_flatten_leading(c) for c in col.children),
    )


def _scalar_to_physical(dtype: DataType, v):
    if v is None:
        return 0
    if dtype.is_decimal:
        return int(round(v * 10**dtype.scale))
    if dtype.kind == TypeKind.BOOL:
        return bool(v)
    if dtype.kind == TypeKind.DATE32 and not isinstance(v, (int, np.integer)):
        import datetime

        if isinstance(v, str):
            v = datetime.date.fromisoformat(v)
        return (v - datetime.date(1970, 1, 1)).days
    return v


def column_from_pylist(dtype: DataType, values: Sequence, capacity: Optional[int] = None) -> Column:
    """Build a host column of any type (nested included) from python
    values.  None = null; arrays are python lists, maps are dicts
    (insertion-ordered), structs are dicts keyed by field name."""
    n = len(values)
    cap = capacity or bucket_capacity(n)
    k = dtype.kind
    if k == TypeKind.ARRAY:
        m = dtype.max_elems
        validity = np.zeros(cap, np.bool_)
        lengths = np.zeros(cap, np.int32)
        flat: List = [None] * (cap * m)
        for i, v in enumerate(values):
            if v is None:
                continue
            if len(v) > m:
                raise ValueError(f"array of {len(v)} elements exceeds max_elems {m}")
            validity[i] = True
            lengths[i] = len(v)
            for j, e in enumerate(v):
                flat[i * m + j] = e
        elem = _reshape_leading(column_from_pylist(dtype.elem, flat, capacity=cap * m), cap, m)
        return Column(dtype, None, validity, lengths, (elem,))
    if k == TypeKind.MAP:
        m = dtype.max_elems
        validity = np.zeros(cap, np.bool_)
        lengths = np.zeros(cap, np.int32)
        fkeys: List = [None] * (cap * m)
        fvals: List = [None] * (cap * m)
        for i, v in enumerate(values):
            if v is None:
                continue
            items = list(v.items()) if isinstance(v, dict) else list(v)
            if len(items) > m:
                raise ValueError(f"map of {len(items)} entries exceeds max_elems {m}")
            validity[i] = True
            lengths[i] = len(items)
            for j, (kk, vv) in enumerate(items):
                fkeys[i * m + j] = kk
                fvals[i * m + j] = vv
        keys = _reshape_leading(column_from_pylist(dtype.key, fkeys, capacity=cap * m), cap, m)
        vals = _reshape_leading(column_from_pylist(dtype.value, fvals, capacity=cap * m), cap, m)
        return Column(dtype, None, validity, lengths, (keys, vals))
    if k == TypeKind.STRUCT:
        validity = np.array([v is not None for v in values] + [False] * (cap - n), np.bool_)
        children = []
        for f in dtype.struct_fields:
            child_vals = [None if v is None else v.get(f.name) for v in values]
            children.append(column_from_pylist(f.dtype, child_vals, capacity=cap))
        return Column(dtype, None, validity, None, tuple(children))
    if dtype.is_string:
        return column_from_strings(values, dtype=dtype, capacity=cap)
    if k == TypeKind.OPAQUE:
        validity = np.array([v is not None for v in values] + [False] * (cap - n), np.bool_)
        objs = np.empty(cap, dtype=object)
        for i, v in enumerate(values):
            objs[i] = v
        return Column(dtype, objs, validity)
    validity = np.array([v is not None for v in values] + [False] * (cap - n), np.bool_)
    vals = np.array(
        [_scalar_to_physical(dtype, v) for v in values] + [0] * (cap - n),
        dtype=dtype.np_dtype,
    )
    return column_from_numpy(dtype, vals[:n], validity[:n], cap)


def column_to_pylist(col: Column, num_rows: int) -> List:
    """Materialize any column (nested included) as python values.
    Decimals come back unscaled (exact ints), same as batch_to_pydict."""
    c = col.to_host()
    dtype = c.dtype
    k = dtype.kind
    if k == TypeKind.ARRAY:
        m = dtype.max_elems
        elems = column_to_pylist(_flatten_leading(c.children[0]), num_rows * m)
        out: List = []
        for i in range(num_rows):
            if not c.validity[i]:
                out.append(None)
            else:
                out.append([elems[i * m + j] for j in range(int(c.lengths[i]))])
        return out
    if k == TypeKind.MAP:
        m = dtype.max_elems
        keys = column_to_pylist(_flatten_leading(c.children[0]), num_rows * m)
        vals = column_to_pylist(_flatten_leading(c.children[1]), num_rows * m)
        out = []
        for i in range(num_rows):
            if not c.validity[i]:
                out.append(None)
            else:
                out.append(
                    {keys[i * m + j]: vals[i * m + j] for j in range(int(c.lengths[i]))}
                )
        return out
    if k == TypeKind.STRUCT:
        kids = [column_to_pylist(ch, num_rows) for ch in c.children]
        out = []
        for i in range(num_rows):
            if not c.validity[i]:
                out.append(None)
            else:
                out.append({f.name: kids[fi][i] for fi, f in enumerate(dtype.struct_fields)})
        return out
    if dtype.kind == TypeKind.BINARY:
        # raw bytes — utf-8 decoding would corrupt binary payloads
        out = []
        for i in range(num_rows):
            if not c.validity[i]:
                out.append(None)
            else:
                out.append(bytes(np.asarray(c.data)[i, : int(c.lengths[i])]))
        return out
    if dtype.is_string:
        return strings_to_list(c, num_rows)
    if k == TypeKind.OPAQUE:
        return [
            (c.data[i] if c.validity[i] else None) for i in range(num_rows)
        ]
    out = []
    for i in range(num_rows):
        if not c.validity[i]:
            out.append(None)
        elif dtype.kind == TypeKind.BOOL:
            out.append(bool(c.data[i]))
        elif dtype.is_float:
            out.append(float(c.data[i]))
        else:
            out.append(int(c.data[i]))
    return out


def strings_to_list(col: Column, num_rows: int) -> List[Optional[str]]:
    data = np.asarray(col.data)
    lengths = np.asarray(col.lengths)
    validity = np.asarray(col.validity)
    out: List[Optional[str]] = []
    for i in range(num_rows):
        if not validity[i]:
            out.append(None)
        else:
            out.append(bytes(data[i, : lengths[i]]).decode("utf-8", errors="replace"))
    return out


@jax.tree_util.register_pytree_node_class
@dataclass
class RecordBatch:
    """A set of equally-sized columns.  ``schema``/``num_rows`` are
    static pytree aux data; columns are leaves."""

    schema: Schema
    columns: List[Column]
    num_rows: int

    def tree_flatten(self):
        return tuple(self.columns), (self.schema, self.num_rows)

    @classmethod
    def tree_unflatten(cls, aux, children):
        schema, num_rows = aux
        return cls(schema, list(children), num_rows)

    @property
    def capacity(self) -> int:
        if not self.columns:
            return bucket_capacity(self.num_rows)
        return self.columns[0].capacity

    def column(self, name: str) -> Column:
        return self.columns[self.schema.index(name)]

    def valid_mask(self) -> jnp.ndarray:
        """bool (cap,): True for real (non-padding) rows."""
        cap = self.capacity
        return jnp.arange(cap) < self.num_rows

    def to_device(self) -> "RecordBatch":
        return self.to_device_counted()[0]

    def to_device_counted(self) -> Tuple["RecordBatch", int, int]:
        """``to_device()`` with what it cost: (device batch, arrays
        transferred, validity arrays that took a shared row mask).

        A top-level column that is neither nested nor opaque, whose host
        validity equals the batch's row mask ``arange(cap) < num_rows``
        (every real row valid, every padding row not), transfers no
        validity of its own: in a full batch it takes the one device
        mask of its capacity (:func:`_full_mask`), in a partial batch
        the batch's such columns share one mask transferred once.  Data,
        lengths and every other column transfer as ``Column.to_device``
        does."""
        cap, n = self.capacity, self.num_rows
        mask = None
        arrays = shared = 0
        cols = []
        for c in self.columns:
            if (c.children is None and c.dtype.kind != TypeKind.OPAQUE
                    and _is_row_mask(c.validity, n)):
                if mask is None:
                    if n == cap:
                        mask = _full_mask(cap)
                    else:
                        mask = jnp.asarray(np.arange(cap) < n)
                        arrays += 1
                shared += 1
                c = replace(c, validity=mask)
            arrays += _host_arrays(c)
            cols.append(c.to_device())
        return RecordBatch(self.schema, cols, n), arrays, shared

    def host_nbytes(self) -> int:
        """Bytes ``to_device()`` would stage: the ``nbytes`` of every
        numpy buffer, from shapes (opaque columns never leave the host;
        a device-resident buffer is not staged again).  Runs once per
        scanned batch: a plain loop over ``type(a) is ndarray``."""
        n = 0
        stack = list(self.columns)
        while stack:
            c = stack.pop()
            if c.dtype.kind == TypeKind.OPAQUE:
                continue
            for a in (c.data, c.validity, c.lengths):
                if type(a) is np.ndarray:
                    n += a.nbytes
            if c.children is not None:
                stack.extend(c.children)
        return n

    def to_host(self) -> "RecordBatch":
        return RecordBatch(self.schema, [c.to_host() for c in self.columns], self.num_rows)

    def select(self, names: Sequence[str]) -> "RecordBatch":
        cols = [self.column(n) for n in names]
        fields = [self.schema.field(n) for n in names]
        return RecordBatch(Schema(fields), cols, self.num_rows)

    def take(self, indices: Array, num_rows: int) -> "RecordBatch":
        return RecordBatch(self.schema, [c.take(indices) for c in self.columns], num_rows)

    def with_capacity(self, cap: int) -> "RecordBatch":
        """Pad or shrink buffers to capacity ``cap`` (>= num_rows)."""
        assert cap >= self.num_rows

        def fix_col(c: Column) -> Column:
            # every buffer (children's included) shares the leading row axis
            cur = c.capacity
            if cur == cap:
                return c

            def fix(a):
                if a is None:
                    return None
                if cur < cap:
                    pad = [(0, cap - cur)] + [(0, 0)] * (a.ndim - 1)
                    return jnp.pad(a, pad)
                return a[:cap]

            return Column(c.dtype, fix(c.data), fix(c.validity), fix(c.lengths),
                          None if c.children is None else tuple(fix_col(k) for k in c.children))

        return RecordBatch(self.schema, [fix_col(c) for c in self.columns], self.num_rows)

    def memory_size(self) -> int:
        """Deep buffer size in bytes (≙ datafusion-ext-commons
        array_size.rs, which drives spill decisions)."""

        def col_size(c: Column) -> int:
            s = 0
            if c.data is not None:
                s += c.data.size * c.data.dtype.itemsize
            s += c.validity.size
            if c.lengths is not None:
                s += c.lengths.size * 4
            if c.children is not None:
                s += sum(col_size(k) for k in c.children)
            return s

        return sum(col_size(c) for c in self.columns)


def batch_from_pydict(
    data: Dict[str, Sequence],
    schema: Schema,
    capacity: Optional[int] = None,
) -> RecordBatch:
    """Build a device batch from python lists (None = null).  Test/IO
    helper — the hot path stages numpy buffers directly."""
    n = len(next(iter(data.values()))) if data else 0
    cap = capacity or bucket_capacity(n)
    cols: List[Column] = []
    for f in schema.fields:
        values = data[f.name]
        assert len(values) == n
        cols.append(column_from_pylist(f.dtype, values, capacity=cap))
    return RecordBatch(schema, [c.to_device() for c in cols], n)


def batch_to_pydict(batch: RecordBatch) -> Dict[str, List]:
    """Materialize a batch on host as python values (None = null),
    decimals unscaled->float is NOT done: decimals come back as ints
    scaled by 10^scale to stay exact."""
    b = batch.to_host()
    out: Dict[str, List] = {}
    for f, c in zip(b.schema.fields, b.columns):
        out[f.name] = column_to_pylist(c, b.num_rows)
    return out


def _child_types(dtype: DataType) -> List[DataType]:
    """Nested child column types in children-tuple order."""
    if dtype.kind == TypeKind.ARRAY:
        return [dtype.elem]
    if dtype.kind == TypeKind.MAP:
        return [dtype.key, dtype.value]
    return [f.dtype for f in dtype.struct_fields]


def _concat_host_cols(
    dtype: DataType, parts: List[Column], ns: List[int], cap: int
) -> Column:
    """Concatenate column parts (host) along the row axis, padding to
    ``cap``.  Nested children share the leading row axis, so recursion
    is uniform; top-level strings additionally merge differing padded
    widths (element strings have dtype-fixed width)."""
    validity = _pad_1d(
        np.concatenate([np.asarray(c.validity)[:n] for c, n in zip(parts, ns)]), cap
    )
    lengths = None
    if parts[0].lengths is not None:
        lengths = _pad_1d(
            np.concatenate([np.asarray(c.lengths)[:n] for c, n in zip(parts, ns)]), cap
        )
    if dtype.is_nested:
        children = tuple(
            _concat_host_cols(kt, [c.children[ki] for c in parts], ns, cap)
            for ki, kt in enumerate(_child_types(dtype))
        )
        return Column(dtype, None, validity, lengths, children)
    if dtype.is_string:
        # padded widths can differ per batch at ANY nesting depth (a
        # runtime-width string column survives as a struct child or
        # array element): merge into the max width along the last axis
        parts_data = [np.asarray(c.data)[:n] for c, n in zip(parts, ns)]
        width = max(p.shape[-1] for p in parts_data)
        mid = parts_data[0].shape[1:-1]
        data = np.zeros((cap,) + mid + (width,), dtype=np.uint8)
        off = 0
        for p in parts_data:
            data[off : off + p.shape[0], ..., : p.shape[-1]] = p
            off += p.shape[0]
        return Column(dtype, data, validity, lengths)
    data = _pad_1d(
        np.concatenate([np.asarray(c.data)[:n] for c, n in zip(parts, ns)]), cap
    )
    return Column(dtype, data, validity, lengths)


def split_opaque_indexes(schema: Schema):
    """(device-capable indexes, opaque indexes) for a schema — OPAQUE
    python-object columns are host-only and must bypass every jitted
    kernel (≙ UserDefinedArray, uda.rs)."""
    opq = [i for i, f in enumerate(schema.fields) if f.dtype.kind == TypeKind.OPAQUE]
    opq_set = set(opq)
    dev = [i for i in range(len(schema.fields)) if i not in opq_set]
    return dev, opq


def _col_on_device(c: Column) -> bool:
    import jax

    leaves = jax.tree_util.tree_leaves(c)
    return all(isinstance(a, jax.Array) for a in leaves)


def _concat_device_cols(
    dtype: DataType, parts: List[Column], ns, cap: int
) -> Column:
    """Device-side concatenation along the row axis, padded to ``cap``.

    Stays fully async (no host sync): each host roundtrip drains the
    device queue, so merge cascades (agg state re-reduce, coalesce)
    must never leave HBM.  ``ns`` entries may be
    TRACED scalars (row counts are data-dependent after a shuffle):
    concatenation is a masked gather over traced offsets, so one
    compiled program covers every row-count combination of the same
    capacities."""
    offs = [jnp.int32(0)]
    for n in ns:
        offs.append(offs[-1] + jnp.int32(n))
    r = jnp.arange(cap, dtype=jnp.int32)

    def cat(arrs, pad_width=None):
        out = None
        for j, a in enumerate(arrs):
            if pad_width is not None and a.shape[-1] < pad_width:
                padding = [(0, 0)] * (a.ndim - 1) + [(0, pad_width - a.shape[-1])]
                a = jnp.pad(a, padding)
            in_mask = (r >= offs[j]) & (r < offs[j + 1])
            src = jnp.clip(r - offs[j], 0, a.shape[0] - 1)
            g = jnp.take(a, src, axis=0)
            mask = in_mask.reshape((cap,) + (1,) * (a.ndim - 1))
            contrib = jnp.where(mask, g, jnp.zeros((), a.dtype))
            if out is None:
                out = contrib
            elif a.dtype == jnp.bool_:
                out = out | contrib
            else:
                out = out + contrib
        return out

    validity = cat([c.validity for c in parts])
    lengths = None if parts[0].lengths is None else cat([c.lengths for c in parts])
    if dtype.is_nested:
        children = tuple(
            _concat_device_cols(kt, [c.children[ki] for c in parts], ns, cap)
            for ki, kt in enumerate(_child_types(dtype))
        )
        return Column(dtype, None, validity, lengths, children)
    if dtype.is_string:
        width = max(c.data.shape[-1] for c in parts)
        return Column(dtype, cat([c.data for c in parts], pad_width=width), validity, lengths)
    return Column(dtype, cat([c.data for c in parts]), validity, lengths)


def _mask_dead_rows(c: Column, live) -> Column:
    """Enforce the padding invariant on rows where ``live`` is False:
    validity False, lengths zero — fully recursive (every nested
    child's buffers lead with the row axis, so ``live`` broadcasts
    across the trailing element axes).  Mirrors
    ops/filter.compact_columns' treatment at the top level."""

    def live_as(arr):
        """``live`` broadcast over ``arr``'s trailing element axes."""
        return live.reshape(live.shape + (1,) * (arr.ndim - 1))

    return Column(
        c.dtype,
        c.data,
        c.validity & live_as(c.validity),
        None if c.lengths is None else jnp.where(live_as(c.lengths), c.lengths, 0),
        None
        if c.children is None
        else tuple(_mask_dead_rows(k, live) for k in c.children),
    )


def head_rows(c: Column, cap: int) -> Column:
    """First ``cap`` rows of a (compacted) column — TRACE-ONLY helper
    for programs that shrink an intermediate back to its caller-visible
    capacity (the fused agg update slices the merged accumulator to the
    stacked-state bucket).  Recursive over nested children (every
    buffer leads with the row axis); the caller guarantees rows past
    its live count are already padding-masked."""

    def h(a):
        return None if a is None else a[:cap]

    return Column(
        c.dtype,
        h(c.data),
        h(c.validity),
        h(c.lengths),
        None if c.children is None else tuple(head_rows(k, cap) for k in c.children),
    )


def slice_rows_device(batch: RecordBatch, lo: int, n: int) -> RecordBatch:
    """Device-side row-range slice ``[lo, lo+n)`` re-padded to its own
    bucket capacity (async — no host transfer).  Used by the in-process
    exchange to split a pid-sorted batch into per-partition batches.
    One cached executable per (schema, in-cap, out-cap) bucket; lo and
    n ride as traced scalars so every partition slice of every batch
    reuses the same program."""
    from .runtime.kernel_cache import cached_kernel, schema_key

    cap = bucket_capacity(max(n, 1))
    in_cap = batch.capacity
    dev_idx, opq = split_opaque_indexes(batch.schema)
    dev_fields = [batch.schema.fields[i] for i in dev_idx]
    dev_cols_in = tuple(batch.columns[i] for i in dev_idx)
    widths = tuple(c.data.shape[1:] for c in dev_cols_in if c.data is not None)

    def build():
        @jax.jit
        def kernel(cols, lo_, n_):
            idx = jnp.minimum(jnp.arange(cap, dtype=jnp.int32) + lo_, in_cap - 1)
            live = jnp.arange(cap) < n_
            return tuple(_mask_dead_rows(c.take(idx), live) for c in cols)

        return kernel

    kernel = cached_kernel(
        ("slice_rows", schema_key(Schema(dev_fields)), in_cap, cap, widths), build
    )
    dev_out = list(kernel(dev_cols_in, lo, n))
    cols: List[Optional[Column]] = [None] * len(batch.columns)
    for j, i in enumerate(dev_idx):
        cols[i] = dev_out[j]
    for i in opq:  # host-side slice+pad of opaque object columns
        c = batch.columns[i]
        data = np.empty(cap, dtype=object)
        validity = np.zeros(cap, np.bool_)
        data[:n] = np.asarray(c.data)[lo : lo + n]
        validity[:n] = np.asarray(c.validity)[lo : lo + n]
        cols[i] = Column(c.dtype, data, validity)
    return RecordBatch(batch.schema, cols, n)


def concat_batches(batches: Sequence[RecordBatch]) -> RecordBatch:
    """Concatenation (coalesce path): device-side when every input
    buffer is already a device array (no sync), host-side otherwise.

    The device path compiles ONE cached XLA executable per (schema,
    input shapes) bucket: a chain of eager slice/pad/concat ops would
    cost a dispatch each, and per-dispatch launch overhead dominates
    merge cascades."""
    assert batches
    schema = batches[0].schema
    n = sum(b.num_rows for b in batches)
    cap = bucket_capacity(n)
    ns = [b.num_rows for b in batches]
    on_device = all(_col_on_device(c) for b in batches for c in b.columns)
    if on_device:
        from .runtime.kernel_cache import cached_kernel, schema_key

        caps = tuple(b.capacity for b in batches)
        widths = tuple(
            tuple(c.data.shape[1:] for c in b.columns if c.data is not None)
            for b in batches
        )
        dtypes = tuple(f.dtype for f in schema.fields)

        def build():
            @jax.jit
            def kernel(cols_per_batch, ns_traced):
                out = []
                for ci, t in enumerate(dtypes):
                    parts = [cols[ci] for cols in cols_per_batch]
                    out.append(_concat_device_cols(t, parts, list(ns_traced), cap))
                return tuple(out)

            return kernel

        # row counts ride as TRACED scalars: shuffle partition sizes
        # are data-dependent, and a key per (ns) combination would
        # compile (and cache forever) a fresh executable per call
        kernel = cached_kernel(
            ("concat", schema_key(schema), caps, cap, widths), build
        )
        cols = list(
            kernel(
                tuple(tuple(b.columns) for b in batches),
                tuple(jnp.int32(x) for x in ns),
            )
        )
        return RecordBatch(schema, cols, n)
    cols: List[Column] = []
    for ci, f in enumerate(schema.fields):
        parts = [b.columns[ci].to_host() for b in batches]
        cols.append(_concat_host_cols(f.dtype, parts, ns, cap).to_device())
    return RecordBatch(schema, cols, n)


class DeviceRing:
    """Two-slot device staging ring: the fused shuffle write pushes
    each batch's device outputs here and only converts the OLDEST slot
    to host bytes once the next batch's program is already dispatched —
    batch N's device→host drain overlaps batch N+1's launch.  FIFO, so the
    staged byte stream is identical to the synchronous path.

    ``put`` returns the items now due for host staging (0 or 1);
    ``flush`` returns the stragglers at stream end; ``drop`` discards
    the slots without staging (cancel/abort — the commit guard already
    ensures nothing partial was published).  Single-producer by
    design: it lives inside one map task's write loop."""

    def __init__(self, depth: int = 2):
        self._depth = max(1, int(depth))
        self._slots: List = []  # (push_ns, item), oldest first

    def put(self, item) -> List:
        import time as _time

        from .runtime import dispatch

        self._slots.append((_time.perf_counter_ns(), item))
        due = []
        while len(self._slots) >= self._depth:
            pushed, oldest = self._slots.pop(0)
            # overlap = time the slot sat while later work dispatched
            dispatch.record("double_buffer_overlap_ns",
                            _time.perf_counter_ns() - pushed)
            due.append(oldest)
        return due

    def flush(self) -> List:
        out = [item for _, item in self._slots]
        self._slots = []
        return out

    def drop(self) -> None:
        self._slots = []

    def __len__(self) -> int:
        return len(self._slots)
