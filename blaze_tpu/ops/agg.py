"""Hash aggregation, TPU-style.

≙ reference AggExec + agg/ (agg_exec.rs:59, agg_table.rs, acc.rs —
~5,600 LoC of hash-table aggregation with radix buckets and spill).
The TPU design replaces the hash table with an **exact sort+segment
reduce**: XLA has no efficient scatter-with-collision-resolution, but
``lax.sort`` over multiple key operands is fast and
collision-free:

1. encode group keys into equality-preserving uint64 words
2. ``lax.sort`` rows lexicographically by those words (row idx payload)
3. segment boundaries where any word changes; seg_id = cumsum
4. per-agg ``segment_sum/min/max`` with ``indices_are_sorted=True``
5. compact boundary rows -> one output row per distinct group

The same kernel shape serves Partial (raw inputs), PartialMerge/Final
(state inputs) — only the reduce ops differ.  Cross-batch state lives
in ONE device-resident accumulator batch, re-reduced with amortized
doubling (pending list merges when pending rows >= accumulated rows),
so per-input-batch cost stays O(batch log batch) amortized.

Modes mirror agg/mod.rs:58-82 (Partial/PartialMerge/Final); partial-agg
skipping mirrors agg_table.rs:147 + BlazeConf partialAggSkipping: when
the observed group/row ratio stays above the threshold past minRows,
Partial stops aggregating and emits row-wise states directly.

Spill: when the memory manager asks, the accumulator is staged to a
Spill and merged back chunk-wise at finish (associative re-reduce).
"""

from __future__ import annotations

import enum
import itertools
import threading
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import conf
from ..batch import Column, RecordBatch, bucket_capacity, concat_batches, head_rows
from ..exprs.compile import infer_dtype, lower
from ..exprs.ir import Expr
from ..io.batch_serde import deserialize_batch, serialize_batch
from ..runtime import faults, trace
from ..runtime.context import TaskContext
from ..runtime.memmgr import MemConsumer, MemManager, Spill, try_new_spill
from ..schema import (
    DataType,
    Field,
    Schema,
    TypeKind,
    decimal_avg_agg_type,
    decimal_sum_agg_type,
)
from .base import BatchStream, ExecNode
from .filter import compact_columns, kept_indices


class AggMode(enum.Enum):
    PARTIAL = 0
    PARTIAL_MERGE = 1
    FINAL = 2


@dataclass
class GroupingExpr:
    expr: Expr
    name: str


@dataclass
class AggFunction:
    """One aggregate call.  ``fn`` in sum/count/count_star/avg/min/max/
    first/first_ignores_null (≙ agg/mod.rs:84-97 create_agg)."""

    fn: str
    expr: Optional[Expr]
    name: str


# ---------------------------------------------------------------- typing

def sum_result_type(t: DataType) -> DataType:
    if t.is_decimal:
        return decimal_sum_agg_type(t)
    if t.is_float:
        return DataType.float64()
    return DataType.int64()


def agg_result_type(fn: str, in_t: Optional[DataType]) -> DataType:
    if fn in ("count", "count_star"):
        return DataType.int64()
    if fn == "sum":
        return sum_result_type(in_t)
    if fn == "avg":
        if in_t.is_decimal:
            return decimal_avg_agg_type(in_t)
        return DataType.float64()
    if fn in ("stddev_samp", "var_samp"):
        return DataType.float64()
    if fn in ("collect_list", "collect_set"):
        if fn == "collect_set" and in_t.is_nested:
            # set dedup encodes elements into equality-preserving
            # uint64 sort words (_value_words): lists, lists-of-lists,
            # lists-of-structs and lists-of-strings all encode, any
            # width (wide ARRAY levels take extra flag words).  MAP
            # elements are rejected per Spark's own CollectSet rule
            # ("collect_set() cannot have map type data"); a total
            # word-count bound keeps lax.sort operand counts sane.
            if not _collect_set_elem_supported(in_t):
                raise NotImplementedError(
                    f"collect_set over {in_t!r} (MAP elements are "
                    "rejected by Spark semantics; or total sort-word "
                    "count exceeds the 128-word bound)"
                )
        return DataType.array(in_t, int(conf.COLLECT_MAX_ELEMS.get()))
    return in_t  # min/max/first


def sum_is_wide(in_t: Optional[DataType]) -> bool:
    """True when the sum accumulator can exceed int64 (decimal sums
    with result precision > 18): accumulate in TWO radix-2^32 limbs —
    value = hi*2^32 + lo, both int64, summed independently (redundant
    representation: no carry propagation until finalize), exactly the
    int128 accumulation the reference gets from Arrow decimal128."""
    return in_t is not None and in_t.is_decimal and sum_result_type(in_t).precision > 18


def agg_state_fields(fn: str, in_t: Optional[DataType], name: str) -> List[Field]:
    if fn in ("count", "count_star"):
        return [Field(f"{name}#count", DataType.int64())]
    if fn == "sum":
        if sum_is_wide(in_t):
            # hi limb carries the state decimal scale; the LO limb name
            # carries the true input precision (the hi precision
            # saturates at 38 for inputs >= p29, so "-10" recovery
            # alone would be lossy there)
            return [
                Field(f"{name}#sum_hi", sum_result_type(in_t)),
                Field(f"{name}#sum_lo{in_t.precision}", DataType.int64()),
                Field(f"{name}#nonnull", DataType.int64()),
            ]
        return [
            Field(f"{name}#sum", sum_result_type(in_t)),
            Field(f"{name}#nonnull", DataType.int64()),
        ]
    if fn == "avg":
        if sum_is_wide(in_t):
            return [
                Field(f"{name}#sum_hi", sum_result_type(in_t)),
                Field(f"{name}#sum_lo{in_t.precision}", DataType.int64()),
                Field(f"{name}#count", DataType.int64()),
            ]
        return [
            Field(f"{name}#sum", sum_result_type(in_t)),
            Field(f"{name}#count", DataType.int64()),
        ]
    if fn in ("min", "max", "first", "first_ignores_null"):
        return [Field(f"{name}#value", in_t)]
    if fn in ("stddev_samp", "var_samp"):
        # (count, sum, centered M2) in float64 — per-batch deviations
        # + the Chan parallel-variance merge, cancellation-safe like
        # the reference's Welford-merging variance accumulator
        return [
            Field(f"{name}#cnt", DataType.int64()),
            Field(f"{name}#fsum", DataType.float64()),
            Field(f"{name}#m2", DataType.float64()),
        ]
    if fn in ("collect_list", "collect_set"):
        return [Field(f"{name}#list", agg_result_type(fn, in_t))]
    raise NotImplementedError(f"agg fn {fn}")


#: group slots of the sort-free dense update: a stream whose PROVEN
#: group count is at most this folds each batch into the accumulator by
#: key match + masked reduces (``DenseSegs``) instead of sort + gather
#: + scan.  Fixed from one reading on the chip (PERF.md section 6, PR 28).
DENSE_SLOTS = 16


def dense_eligible(fn: str, in_t: Optional[DataType]) -> bool:
    """True when the aggregate's reduction is exact in any order, so a
    masked tree reduce gives the sort path's result bit for bit:
    counts, and sums, averages, min and max over integers, decimals,
    dates and timestamps (wide or not).  Anything over floats (a sum
    rounds by order; min/max pick -0.0 or 0.0 by order), variances,
    ``first*``, ``collect_*`` and string min/max depend on row order or
    on the segment layout."""
    if fn in ("count", "count_star"):
        return True
    if fn in ("sum", "avg"):
        return in_t.is_integer or in_t.is_decimal
    if fn in ("min", "max"):
        return (in_t.is_integer or in_t.is_decimal
                or in_t.kind in (TypeKind.DATE32, TypeKind.TIMESTAMP))
    return False


# ------------------------------------------------------- key word encode

def encode_key_words(cols: Sequence[Column]) -> List[jnp.ndarray]:
    """Equality-preserving uint64 words per group column: a null word,
    then the value words (strings: zero-padded bytes as words +
    length)."""
    words: List[jnp.ndarray] = []
    for c in cols:
        words.append((~c.validity).astype(jnp.uint64))
        if c.dtype.is_string:
            n, w = c.data.shape
            words.append(c.lengths.astype(jnp.uint64))
            nw = (w + 7) // 8
            data = c.data if nw * 8 == w else jnp.pad(c.data, ((0, 0), (0, nw * 8 - w)))
            b = data.reshape(n, nw, 8).astype(jnp.uint64)
            for k in range(nw):
                word = b[:, k, 0] << jnp.uint64(56)
                for j in range(1, 8):
                    word = word | (b[:, k, j] << jnp.uint64(8 * (7 - j)))
                words.append(jnp.where(c.validity, word, jnp.uint64(0)))
        elif c.dtype.is_float:
            from ..exprs.hash import f64_raw_bits

            d = jnp.where(c.data == 0, jnp.zeros((), c.data.dtype), c.data)  # -0.0 -> 0.0
            d = jnp.where(jnp.isnan(d), jnp.full((), jnp.nan, c.data.dtype), d)  # canonical NaN
            bits = d.view(jnp.int32) if c.data.dtype == jnp.float32 else f64_raw_bits(d)
            words.append(jnp.where(c.validity, bits.astype(jnp.int64).view(jnp.uint64), jnp.uint64(0)))
        else:
            words.append(
                jnp.where(c.validity, c.data.astype(jnp.int64).view(jnp.uint64), jnp.uint64(0))
            )
    return words


# ------------------------------------------------------- segment reduces

# ``seg is None`` selects the GLOBAL (single-segment) fast path: a
# plain tree reduction.  segment_* with num_segments=1 lowers to a
# scatter, which XLA:TPU executes orders of magnitude slower than a
# reduce — the no-groupings agg was 70x off the chip's reduce speed.
#
# ``seg`` may also be a :class:`SortedSegs`: rows sorted by group with
# known boundary structure.  Reduces then run as segmented
# associative scans + cumsum-difference + gathers — NO scatter at all
# (jax.ops.segment_* and jnp.nonzero's bincount both lower to scatter,
# the other TPU cliff).
#
# The third kind is :class:`DenseSegs`: rows in ANY order, each tagged
# with one of a few known groups — masked tree reductions per group.


@dataclass
class SortedSegs:
    """Segment structure of a group-sorted row block.

    - ``seg``: (cap,) int32 group id per row (0..n_out-1, clipped)
    - ``boundary``: (cap,) bool, True at each segment's first row
    - ``starts``: (cap,) int32, row index of group g's first row
    - ``ends``: (cap,) int32, row index of group g's last row
    (entries past n_out are garbage; callers mask with out_live)
    """

    seg: jnp.ndarray
    boundary: jnp.ndarray
    starts: jnp.ndarray
    ends: jnp.ndarray


@dataclass
class DenseSegs:
    """Rows matched against ``k`` known groups, in any order.

    - ``slot``: (cap,) int32, the row's group in ``[0, k)``, or ``k``
      for a row that is dead or matched none
    - ``k``: static slot count

    Reduces are a (k, cap) broadcast compare + select + tree reduce
    along rows: no sort, no gather, no scan, no scatter.  Only for
    reductions that are exact in any order (``dense_eligible``)."""

    slot: jnp.ndarray
    k: int


def _dense_reduce(op, values, seg: DenseSegs, fill):
    hit = seg.slot[None, :] == jnp.arange(seg.k, dtype=seg.slot.dtype)[:, None]
    return op(jnp.where(hit, values[None, :], fill), axis=1)


def _extreme(dt, largest: bool):
    """The value every other one of dtype ``dt`` reduces past."""
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.array(jnp.inf if largest else -jnp.inf, dt)
    info = jnp.iinfo(dt)
    return jnp.array(info.max if largest else info.min, dt)


def _segscan(op, vals, flags):
    """Segmented inclusive scan: at row i, reduce of ``vals`` from i's
    segment start through i.  Hillis-Steele log-depth doubling over the
    (value, boundary-flag) monoid, built from CONTIGUOUS pad+slice
    shifts and elementwise ops only.

    Deliberately NOT ``lax.associative_scan``: its recursive even/odd
    decomposition emits strided slices + interleaves whose TPU compile
    was pathological when last read on a chip (pre-PR-1, at the 4M
    bucket: compile in tens of minutes, execution in tens of seconds
    per call; not re-measured since); the doubling form is contiguous
    shifts only."""
    n = vals.shape[0]
    v, f = vals, flags
    d = 1
    while d < n:
        # shift right by d: element i combines with i-d
        pv = jnp.concatenate([v[:1].repeat(d, axis=0), v[:-d]])
        pf = jnp.concatenate([jnp.ones(d, dtype=f.dtype), f[:-d]])
        keep = f  # a boundary inside (i-d, i] blocks the carry
        v = jnp.where(keep, v, op(pv, v))
        f = f | pf
        d <<= 1
    return v


def build_sorted_segs(boundary, s_live) -> SortedSegs:
    """Derive SortedSegs from boundary flags over group-sorted rows
    (dead rows sort AFTER live ones).  Uses one single-operand u32 sort
    for end-position compaction instead of jnp.nonzero (whose bincount
    is a scatter)."""
    cap = boundary.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    seg = jnp.clip(jnp.cumsum(boundary.astype(jnp.int32)) - 1, 0, cap - 1)
    nxt_boundary = jnp.roll(boundary, -1).at[-1].set(True)
    nxt_dead = jnp.roll(~s_live, -1).at[-1].set(True)
    ends_mask = s_live & (nxt_boundary | nxt_dead)
    ends_pos = jnp.where(ends_mask, idx, jnp.int32(cap))
    ends = jnp.clip(jax.lax.sort((ends_pos,), num_keys=1)[0], 0, cap - 1)
    # last boundary at-or-before each row == the row's segment start;
    # boundary indices are monotone, so a PLAIN cummax is exact (no
    # segmented scan needed — one native TPU op)
    start_at_row = jax.lax.cummax(jnp.where(boundary, idx, jnp.int32(-1)))
    starts = jnp.clip(jnp.take(start_at_row, ends), 0, cap - 1)
    return SortedSegs(seg=seg, boundary=boundary, starts=starts, ends=ends)


def _seg_min_reduce(values, seg, cap):
    """Raw per-segment min with the global fast path — use THIS (or
    _seg_max_reduce) for any new reduce; never call jax.ops.segment_*
    directly (seg=None must stay a tree reduce, not a scatter)."""
    if seg is None:
        return jnp.min(values, keepdims=True)
    if isinstance(seg, DenseSegs):
        return _dense_reduce(jnp.min, values, seg, _extreme(values.dtype, True))
    if isinstance(seg, SortedSegs):
        return jnp.take(_segscan(jnp.minimum, values, seg.boundary), seg.ends)
    return jax.ops.segment_min(values, seg, num_segments=cap, indices_are_sorted=True)


def _seg_max_reduce(values, seg, cap):
    if seg is None:
        return jnp.max(values, keepdims=True)
    if isinstance(seg, DenseSegs):
        return _dense_reduce(jnp.max, values, seg, _extreme(values.dtype, False))
    if isinstance(seg, SortedSegs):
        return jnp.take(_segscan(jnp.maximum, values, seg.boundary), seg.ends)
    return jax.ops.segment_max(values, seg, num_segments=cap, indices_are_sorted=True)


def _seg_sum(values, valid, seg, cap):
    z = jnp.where(valid, values, jnp.zeros((), values.dtype))
    if seg is None:
        return jnp.sum(z, keepdims=True)
    if isinstance(seg, DenseSegs):
        return _dense_reduce(jnp.sum, z, seg, jnp.zeros((), z.dtype))
    if isinstance(seg, SortedSegs):
        if jnp.issubdtype(z.dtype, jnp.floating):
            # floats: a global-cumsum difference catastrophically
            # cancels when a small group follows a large prefix, so
            # accumulate WITHIN each segment (error scales with the
            # group's own magnitude, matching segment_sum)
            return jnp.take(_segscan(jnp.add, z, seg.boundary), seg.ends)
        # ints/decimals: cumsum difference is exact (wraparound
        # cancels in the subtraction) — gathers only
        incl = jnp.cumsum(z)
        return (
            jnp.take(incl, seg.ends)
            - jnp.take(incl, seg.starts)
            + jnp.take(z, seg.starts)
        )
    return jax.ops.segment_sum(z, seg, num_segments=cap, indices_are_sorted=True)


def _seg_count(valid, seg, cap):
    return _seg_sum(valid.astype(jnp.int64), jnp.ones_like(valid), seg, cap)


def _seg_minmax(values, valid, seg, cap, is_min: bool):
    z = jnp.where(valid, values, _extreme(values.dtype, is_min))
    return (_seg_min_reduce if is_min else _seg_max_reduce)(z, seg, cap)


def _seg_first(values, valid, seg, cap, ignore_nulls: bool):
    n = values.shape[0]
    pick = valid if ignore_nulls else jnp.ones_like(valid)
    idx = jnp.where(pick, jnp.arange(n), n)
    first_idx = _seg_min_reduce(idx, seg, cap)
    safe = jnp.clip(first_idx, 0, n - 1)
    has = first_idx < n
    return jnp.take(values, safe, axis=0), jnp.take(valid, safe) & has, has


def _seg_gather_first(v: Column, pick, seg, cap: int) -> Column:
    """Gather the first row per segment where ``pick`` holds."""
    n = v.validity.shape[0]
    idx = jnp.where(pick, jnp.arange(n), n)
    first = _seg_min_reduce(idx, seg, cap)
    has = first < n
    out = v.take(jnp.clip(first, 0, n - 1))
    return Column(v.dtype, out.data, out.validity & has,
                  None if out.lengths is None else jnp.where(has, out.lengths, 0))


def _seg_string_minmax(v: Column, seg, cap: int, is_min: bool) -> Column:
    """Lexicographic per-segment min/max over a string column: W/8
    tie-break passes of segment_min over order-preserving words, then a
    first-candidate gather (rows arrive segment-sorted)."""
    from .sort import order_words

    words = order_words(v, ascending=is_min, nulls_first=False)[1:]  # value words
    cand = v.validity
    sentinel = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    for word in words:
        masked = jnp.where(cand, word, sentinel)
        m = _seg_min_reduce(masked, seg, cap)
        if seg is None:
            per_row = m[0]
        elif isinstance(seg, SortedSegs):
            per_row = jnp.take(m, seg.seg)
        else:
            per_row = jnp.take(m, seg)
        cand = cand & (word == per_row)
    return _seg_gather_first(v, cand, seg, cap)


# ------------------------------------------------- collect_list/set

def _seg_first_row(seg, cap, n):
    """Index of each segment's first row, mapped back per row."""
    arange = jnp.arange(n, dtype=jnp.int32)
    first = jax.ops.segment_min(arange, seg, num_segments=cap, indices_are_sorted=True)
    return jnp.clip(jnp.take(first, seg), 0, n - 1)


def _scatter_elem_col(c: Column, tgt, pos, cap: int, m: int, n_lead: int,
                      top_validity=None) -> Column:
    """Scatter a column's rows/elements into a (cap, m)-leading output
    at ``[tgt, pos]`` — recursive over nested children, so any element
    dtype collects (arrays of arrays/maps/structs included).

    ``n_lead``: leading axes of the SOURCE arrays (1 = one entry per
    input row, 2 = per (row, element) in merge mode)."""

    def sc(arr, dtype):
        if arr is None:
            return None
        out = jnp.zeros((cap, m) + arr.shape[n_lead:], dtype)
        return out.at[tgt, pos].set(arr, mode="drop")

    validity = (
        top_validity
        if top_validity is not None
        else sc(c.validity, jnp.bool_)
    )
    return Column(
        c.dtype,
        sc(c.data, c.data.dtype) if c.data is not None else None,
        validity,
        sc(c.lengths, jnp.int32) if c.lengths is not None else None,
        None if c.children is None else tuple(
            _scatter_elem_col(k, tgt, pos, cap, m, n_lead) for k in c.children
        ),
    )


def _collect_reduce(v: Column, arr_t: DataType, seg, cap: int, merging: bool) -> Column:
    """Segment-collect into the fixed max-elements ARRAY layout
    (≙ reference agg/collect.rs collect_list/collect_set accs).  Nulls
    are skipped (Spark semantics); elements past ``max_elems`` are
    DROPPED — the padded layout's documented deviation from the
    reference's unbounded lists.  Element scatter recurses over nested
    children, so nested element types collect too."""
    elem_t = arr_t.elem
    m = arr_t.max_elems
    n = v.validity.shape[0]
    if not merging:
        valid = v.validity
        cv = jnp.cumsum(valid.astype(jnp.int32))
        prefix = cv - valid.astype(jnp.int32)  # exclusive count of valid rows
        base = jnp.take(prefix, _seg_first_row(seg, cap, n))
        pos = prefix - base                    # within-segment rank among valid
        emit = valid & (pos < m)
        tgt = jnp.where(emit, seg, cap)        # cap = dropped (out of bounds)
        counts = jnp.clip(_seg_count(valid, seg, cap), 0, m).astype(jnp.int32)
        ev = jnp.arange(m)[None, :] < counts[:, None]
        elem = _scatter_elem_col(v, tgt, pos, cap, m, 1, top_validity=ev)
        return Column(arr_t, None, jnp.ones(cap, jnp.bool_), counts, (elem,))
    # merging: v is an ARRAY state column (rows sorted by group)
    rc = jnp.where(v.validity, v.lengths, 0).astype(jnp.int32)
    cum = jnp.cumsum(rc)
    excl = cum - rc
    base = jnp.take(excl, _seg_first_row(seg, cap, n))
    start = excl - base                        # offset of this row's elems in its group
    elem = v.children[0]
    within = jnp.arange(m)[None, :] < rc[:, None]
    pos2 = start[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]
    seg2 = jnp.broadcast_to(seg[:, None], (n, m))
    tgt = jnp.where(within & (pos2 < m), seg2, cap)
    counts = jnp.clip(
        jax.ops.segment_sum(rc, seg, num_segments=cap, indices_are_sorted=True), 0, m
    ).astype(jnp.int32)
    ev = jnp.arange(m)[None, :] < counts[:, None]
    out_elem = _scatter_elem_col(elem, tgt, pos2, cap, m, 2, top_validity=ev)
    return Column(arr_t, None, jnp.ones(cap, jnp.bool_), counts, (out_elem,))


def _canon_float_bits(data):
    """Equality-canonical float bits: -0.0 -> 0.0, all NaNs -> one
    payload; f32 views as i32, f64 through the raw-bits helper."""
    from ..exprs.hash import f64_raw_bits

    d = jnp.where(data == 0, jnp.zeros((), data.dtype), data)
    d = jnp.where(jnp.isnan(d), jnp.full((), jnp.nan, data.dtype), d)
    return d.view(jnp.int32) if data.dtype == jnp.float32 else f64_raw_bits(d)


def _value_words(dtype: DataType, col: Column, live) -> List[jnp.ndarray]:
    """Recursive equality-preserving uint64 words for values of any
    supported nesting, each word shaped like ``live`` (the liveness
    mask at this level).  A trailing element axis is flattened into
    max_elems separate words, so total key count stays static."""
    if dtype.kind == TypeKind.ARRAY:
        m = dtype.max_elems
        child = col.children[0]
        words = [jnp.where(live, col.lengths, 0).astype(jnp.uint64)]
        inner_live = (
            jnp.arange(m)[(None,) * live.ndim] < col.lengths[..., None]
        ) & live[..., None]
        lv = inner_live & child.validity
        # element-validity flags, 64 bits per word (levels wider than
        # 64 spill into additional flag words)
        for base in range(0, m, 64):
            flags = jnp.zeros(live.shape, jnp.uint64)
            for j in range(base, min(base + 64, m)):
                flags = flags | (
                    lv[..., j].astype(jnp.uint64) << jnp.uint64(j - base))
            words.append(flags)
        for w in _value_words(dtype.elem, child, lv):
            for j in range(m):
                words.append(w[..., j])
        return words
    if dtype.kind == TypeKind.STRUCT:
        words = []
        for f, ch in zip(dtype.struct_fields, col.children):
            lv = live & ch.validity
            words.append(lv.astype(jnp.uint64))  # per-field null flag
            words.extend(_value_words(f.dtype, ch, lv))
        return words
    if dtype.is_string:
        w_ = col.data.shape[-1]
        nw = (w_ + 7) // 8
        d = col.data
        if nw * 8 != w_:
            pad = [(0, 0)] * (d.ndim - 1) + [(0, nw * 8 - w_)]
            d = jnp.pad(d, pad)
        b = d.reshape(live.shape + (nw, 8)).astype(jnp.uint64)
        words = [jnp.where(live, col.lengths, 0).astype(jnp.uint64)]
        for k in range(nw):
            word = b[..., k, 0] << jnp.uint64(56)
            for j in range(1, 8):
                word = word | (b[..., k, j] << jnp.uint64(8 * (7 - j)))
            words.append(jnp.where(live, word, jnp.uint64(0)))
        return words
    bits = _canon_float_bits(col.data) if dtype.is_float else col.data
    bits = bits.astype(jnp.int64).view(jnp.uint64)
    return [jnp.where(live, bits, jnp.uint64(0))]


def _word_count(dtype: DataType) -> int:
    """Sort words _value_words emits per value (the ARRAY levels
    multiply: each child word splits into max_elems words)."""
    if dtype.kind == TypeKind.ARRAY:
        return 1 + (dtype.max_elems + 63) // 64 + (
            dtype.max_elems * _word_count(dtype.elem))
    if dtype.kind == TypeKind.STRUCT:
        return sum(1 + _word_count(f.dtype) for f in dtype.struct_fields)
    if dtype.is_string:
        return 1 + (dtype.string_width + 7) // 8
    return 1


def _collect_set_elem_supported(dtype: DataType) -> bool:
    """Element types the sort-word dedup can encode: primitives,
    strings, and ARRAY/STRUCT nestings thereof (ARRAY levels wider
    than 64 use extra validity-flag words) with a bounded TOTAL word
    count (the levels multiply; lax.sort with thousands of operands
    would blow up compile rather than fail cleanly).  MAP elements are
    rejected because Spark itself rejects them: CollectSet refuses any
    input type containing a MapType ("collect_set() cannot have map
    type data"), so the gate IS the reference semantics."""
    def ok(t: DataType) -> bool:
        if t.kind == TypeKind.ARRAY:
            return ok(t.elem)
        if t.kind == TypeKind.STRUCT:
            return all(ok(f.dtype) for f in t.struct_fields)
        if t.kind in (TypeKind.MAP, TypeKind.OPAQUE):
            return False
        return True

    return ok(dtype) and _word_count(dtype) <= 128


def _elem_sort_words(elem: Column, within) -> List[jnp.ndarray]:
    """Equality-preserving uint64 sort words along the element axis
    (dead slots first key = 1 so they sort last)."""
    words: List[jnp.ndarray] = [(~within).astype(jnp.uint64)]
    if elem.dtype.is_string:
        cap, m, w = elem.data.shape
        words.append(jnp.where(within, elem.lengths, 0).astype(jnp.uint64))
        nw = (w + 7) // 8
        d = elem.data if nw * 8 == w else jnp.pad(elem.data, ((0, 0), (0, 0), (0, nw * 8 - w)))
        b = d.reshape(cap, m, nw, 8).astype(jnp.uint64)
        for k in range(nw):
            word = b[:, :, k, 0] << jnp.uint64(56)
            for j in range(1, 8):
                word = word | (b[:, :, k, j] << jnp.uint64(8 * (7 - j)))
            words.append(jnp.where(within, word, jnp.uint64(0)))
    elif elem.dtype.is_float:
        bits = _canon_float_bits(elem.data)
        words.append(
            jnp.where(within, bits.astype(jnp.int64).view(jnp.uint64), jnp.uint64(0))
        )
    elif elem.dtype.is_nested:
        # nested elements (lists, lists-of-lists, lists-of-structs,
        # lists-of-strings): recursive equality-word encoding
        words.extend(_value_words(elem.dtype, elem, within))
    else:
        words.append(
            jnp.where(within, elem.data.astype(jnp.int64).view(jnp.uint64), jnp.uint64(0))
        )
    return words


def _dedup_array_state(col: Column) -> Column:
    """Per-row element dedup (collect_set): sort elements within each
    row, drop adjacent duplicates, recompact."""
    arr_t = col.dtype
    elem_t = arr_t.elem
    elem = col.children[0]
    m = arr_t.max_elems
    cap = col.validity.shape[0]
    within = jnp.arange(m)[None, :] < col.lengths[:, None]
    words = _elem_sort_words(elem, within)
    payload = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None, :], (cap, m))
    sorted_ = jax.lax.sort(tuple(words) + (payload,), dimension=1, num_keys=len(words))
    s_words, s_idx = sorted_[:-1], sorted_[-1]
    s_within = jnp.take_along_axis(within, s_idx, axis=1)
    changed = jnp.zeros((cap, m), jnp.bool_)
    for wv in s_words:
        changed = changed | (wv != jnp.roll(wv, 1, axis=1))
    changed = changed.at[:, 0].set(True)
    keep = s_within & changed
    new_pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    counts = jnp.sum(keep.astype(jnp.int32), axis=1)
    rows2 = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32)[:, None], (cap, m))
    tgt = jnp.where(keep, rows2, cap)
    ev = jnp.arange(m)[None, :] < counts[:, None]
    if elem_t.is_string:
        w = elem.data.shape[-1]
        g_data = jnp.take_along_axis(elem.data, s_idx[:, :, None], axis=1)
        g_len = jnp.take_along_axis(elem.lengths, s_idx, axis=1)
        data = jnp.zeros((cap, m, w), jnp.uint8).at[tgt, new_pos].set(g_data, mode="drop")
        lengths = jnp.zeros((cap, m), jnp.int32).at[tgt, new_pos].set(g_len, mode="drop")
        out_elem = Column(elem_t, data, ev, lengths)
    elif elem_t.is_nested:
        # nested elements: recursive permute (gather by s_idx along the
        # element axis) + compacting scatter of every buffer level
        def reorder(c: Column, valid_override=None) -> Column:
            def move(a):
                if a is None:
                    return None
                ix = s_idx
                for _ in range(a.ndim - 2):
                    ix = ix[..., None]
                g = jnp.take_along_axis(a, ix, axis=1)
                return jnp.zeros(a.shape, a.dtype).at[tgt, new_pos].set(
                    g, mode="drop")

            return Column(
                c.dtype,
                move(c.data),
                move(c.validity) if valid_override is None else valid_override,
                move(c.lengths),
                None if c.children is None else tuple(
                    reorder(ch) for ch in c.children),
            )

        out_elem = reorder(elem, valid_override=ev)
    else:
        g_data = jnp.take_along_axis(elem.data, s_idx, axis=1)
        data = jnp.zeros((cap, m), elem.data.dtype).at[tgt, new_pos].set(g_data, mode="drop")
        out_elem = Column(elem_t, data, ev)
    return Column(arr_t, None, col.validity, counts, (out_elem,))


# ---------------------------------------------------------------- AggExec

class AggExec(ExecNode):
    def __init__(
        self,
        child: ExecNode,
        mode: AggMode,
        groupings: Sequence[GroupingExpr],
        aggs: Sequence[AggFunction],
        initial_input_buffer_offset: int = 0,
        supports_partial_skipping: bool = False,
        pre_filter: Optional[Expr] = None,
        post_sort: Optional[Sequence] = None,
        post_fetch: Optional[int] = None,
        dup_groups_ok: bool = False,
    ):
        super().__init__([child])
        self.mode = mode
        # the state-merging twin of a PARTIAL agg (_StateMerger): its
        # output is re-merged by every later stage, so it may emit
        # hash-split duplicate groups exactly like PARTIAL itself
        self._dup_groups_ok = dup_groups_ok
        # stage fusion may fold a downstream Sort(+Limit) into the
        # finalize program (FINAL mode emits one blocking batch per
        # partition, so an in-program key sort over it is exact):
        # post_sort = SortFields over the OUTPUT schema, post_fetch =
        # host-side row clamp after the sorted finalize
        assert post_sort is None or mode == AggMode.FINAL
        self.post_sort = list(post_sort) if post_sort else None
        self.post_fetch = post_fetch
        self.groupings = list(groupings)
        # brickhouse names are aliases (≙ agg/mod.rs:84-97 create_agg
        # mapping BrickhouseCollect/BrickhouseCombineUnique)
        _ALIAS = {"count0": "count_star", "brickhouse_collect": "collect_list",
                  "brickhouse_combine_unique": "collect_set"}
        self.aggs = [
            AggFunction(_ALIAS.get(a.fn, a.fn), a.expr, a.name) for a in aggs
        ]
        # fused pre-aggregation predicate (stage fusion: a FilterExec
        # collapsed into this kernel; rows failing it never aggregate)
        self.pre_filter = pre_filter
        self.supports_partial_skipping = supports_partial_skipping
        # tier-5 blocking-boundary fusion (shuffle write absorbing this
        # FINAL agg's finalize as its chain bottom): when set, _finish
        # emits the RAW state batch and the writer's fused program
        # applies the finalize — no finalized intermediate batch
        self.emit_state = False

        in_schema = child.schema
        # input value types of each agg (for PARTIAL: from expr; for
        # merge modes: recover from the state columns in in_schema)
        self._in_types: List[Optional[DataType]] = []
        for a in self.aggs:
            if mode == AggMode.PARTIAL:
                self._in_types.append(None if a.expr is None else infer_dtype(a.expr, in_schema))
            else:
                if a.fn in ("count", "count_star"):
                    self._in_types.append(None)
                elif a.fn in ("sum", "avg"):
                    # state sum column carries the sum type; recover in_t
                    # (wide decimal sums split into #sum_hi/#sum_loP limbs,
                    # P = the TRUE input precision: the hi precision
                    # saturates at 38 for inputs >= p29, so the plain
                    # "-10" inversion is lossy there and would skew the
                    # final avg result type vs Spark's).  BOTH sum and avg
                    # states carry decimal(p+10, s), so both subtract 10 —
                    # recovering p+10 as the input precision would flip
                    # sum_is_wide() against the partial stage's layout
                    if f"{a.name}#sum" in in_schema.names:
                        st = in_schema.field(f"{a.name}#sum").dtype
                        true_p = max(1, st.precision - 10)
                    else:
                        st = in_schema.field(f"{a.name}#sum_hi").dtype
                        lo_prefix = f"{a.name}#sum_lo"
                        true_p = next(
                            (
                                int(nm[len(lo_prefix):])
                                for nm in in_schema.names
                                if nm.startswith(lo_prefix)
                                and nm[len(lo_prefix):].isdigit()
                            ),
                            max(1, st.precision - 10),
                        )
                    if st.is_decimal:
                        self._in_types.append(DataType.decimal(true_p, st.scale))
                    else:
                        self._in_types.append(st)
                elif a.fn in ("collect_list", "collect_set"):
                    self._in_types.append(in_schema.field(f"{a.name}#list").dtype.elem)
                elif a.fn in ("stddev_samp", "var_samp"):
                    self._in_types.append(DataType.float64())
                else:
                    self._in_types.append(in_schema.field(f"{a.name}#value").dtype)

        group_fields = [
            Field(g.name, infer_dtype(g.expr, in_schema)) for g in self.groupings
        ]
        state_fields: List[Field] = []
        for a, t in zip(self.aggs, self._in_types):
            fields = agg_state_fields(a.fn, t, a.name)
            if mode != AggMode.PARTIAL and a.fn in ("collect_list", "collect_set"):
                # preserve the incoming state's element budget exactly
                # (conf may differ between stages)
                fields = [Field(f"{a.name}#list", in_schema.field(f"{a.name}#list").dtype)]
            state_fields.extend(fields)
        self._state_schema = Schema(group_fields + state_fields)

        if mode == AggMode.FINAL:
            out_fields = group_fields + [
                Field(
                    a.name,
                    self._state_schema.field(f"{a.name}#list").dtype
                    if a.fn in ("collect_list", "collect_set")
                    else agg_result_type(a.fn, t),
                )
                for a, t in zip(self.aggs, self._in_types)
            ]
            self._schema = Schema(out_fields)
        else:
            self._schema = self._state_schema

        # the sort-free dense update serves this agg (the stream's
        # proven group count decides per batch, _FusedGroupedUpdate)
        self._dense_ok = bool(self.groupings) and all(
            dense_eligible(a.fn, t) for a, t in zip(self.aggs, self._in_types))
        self._merger: Optional["_StateMerger"] = None
        self._update_k = None
        self._dense_k = None
        from ..exprs.compile import expr_key
        from ..runtime.kernel_cache import cached_kernel, schema_key
        from .sort import sort_fields_key

        kernel_key = (
            "agg", mode.value, schema_key(in_schema), schema_key(self._state_schema),
            None if self.pre_filter is None else expr_key(self.pre_filter),
            tuple((expr_key(g.expr), g.name) for g in self.groupings),
            tuple((a.fn, None if a.expr is None else expr_key(a.expr), a.name)
                  for a in self.aggs),
            bool(conf.SEG_SCAN_REDUCE.get()),
            bool(conf.AGG_HASH_SORT_PARTIAL.get()), self._dup_groups_ok,
            None if self.post_sort is None else sort_fields_key(self.post_sort),
        )
        self._kernel_key = kernel_key
        self._grouped_kernel, self._scalar_kernel, self._finalize_kernel = cached_kernel(
            kernel_key, lambda: self._build_kernels(in_schema)
        )

    @property
    def schema(self) -> Schema:
        return self._schema

    # ------------------------------------- static-analysis contract

    def required_child_distribution(self):
        """A grouped FINAL agg needs every row of a group co-located:
        its feeding exchange must hash on (a subset of) the group keys
        (analysis/plan_verify.py rule ``dist.final-agg``); ungrouped
        FINAL needs exactly one partition (``dist.final-scalar``)."""
        if self.mode != AggMode.FINAL or not self.groupings:
            return None
        from ..exprs.compile import expr_key

        return ("hash", frozenset(expr_key(g.expr) for g in self.groupings))

    def provided_ordering(self):
        """A fused ``post_sort`` finalize satisfies downstream
        sort-consumers exactly like the SortExec it absorbed —
        ``(expr_key, ascending)`` entries, direction included."""
        if not self.post_sort:
            return ()
        from ..exprs.compile import expr_key

        return tuple((expr_key(f.expr), bool(f.ascending))
                     for f in self.post_sort)

    # -------------------------------------------------------- kernels

    def _build_kernels(self, in_schema: Schema, dense: bool = False):
        """(grouped_kernel, scalar_kernel, finalize_kernel) — or, with
        ``dense``, the one ``dense_update`` program over the same input
        evaluation and reduce expressions."""
        groupings = self.groupings
        aggs = self.aggs
        mode = self.mode
        pre_filter = self.pre_filter
        post_sort = self.post_sort
        out_schema = self._schema
        n_groups_cols = len(groupings)
        state_schema = self._state_schema
        in_types = list(self._in_types)  # NEVER capture self below: the
        # kernels are cached process-wide and must not pin this exec's
        # child subtree (scanned data) alive
        use_segscan = bool(conf.SEG_SCAN_REDUCE.get())  # in kernel_key
        # exactness: only PARTIAL — and the twin that merges a PARTIAL
        # agg's own accumulators — may emit hash-split duplicate groups
        # (every later stage re-merges); FINAL/PARTIAL_MERGE sort the
        # full key words.  The one-u32-key sort is also what the chip's
        # compiler can afford: its sort compile time grows with rows x
        # operands, minutes for four u64 key words at 16k rows
        use_hash_sort = bool(conf.AGG_HASH_SORT_PARTIAL.get()) and (
            self.mode == AggMode.PARTIAL or self._dup_groups_ok)

        def eval_inputs(cols: Tuple[Column, ...], schema: Schema):
            env = {f.name: c for f, c in zip(schema.fields, cols)}
            n = cols[0].validity.shape[0] if cols else 0
            key_cols = [lower(g.expr, schema, env, n) for g in groupings]
            return env, key_cols, n

        def partial_inputs(env, schema, n) -> List[List[Column]]:
            """Per-agg list of raw input columns (PARTIAL mode).
            count(*) gets a synthetic all-valid bool column so the
            liveness masking applied to sorted inputs covers it too."""
            out = []
            for a in aggs:
                if a.expr is None:
                    ones = jnp.ones(n, jnp.bool_)
                    out.append([Column(DataType.bool_(), ones, ones)])
                else:
                    out.append([lower(a.expr, schema, env, n)])
            return out

        def state_inputs(env) -> List[List[Column]]:
            out = []
            for a, t in zip(aggs, in_types):
                fields = agg_state_fields(a.fn, t, a.name)
                out.append([env[f.name] for f in fields])
            return out

        def reduce_one(
            a: AggFunction,
            in_t: Optional[DataType],
            inputs: List[Column],
            seg,
            cap: int,
            merging: bool,
        ) -> List[Column]:
            """Produce the state columns (length cap, indexed by seg id)."""
            if a.fn in ("count", "count_star"):
                c = inputs[0]
                if merging:
                    s = _seg_sum(c.data, c.validity, seg, cap)
                else:
                    s = _seg_count(c.validity, seg, cap)
                return [Column(DataType.int64(), s, jnp.ones(cap, jnp.bool_))]
            if a.fn in ("sum", "avg"):
                sum_t = sum_result_type(in_t)
                ones = jnp.ones(cap, jnp.bool_)
                if sum_is_wide(in_t):
                    # radix-2^32 limbs summed independently (redundant
                    # carry-free int128 accumulation; finalize combines)
                    if merging:
                        hc, lc, cc = inputs
                        hi_in, lo_in, hval = hc.data, lc.data, hc.validity
                        cval, cdata = cc.validity, cc.data
                    else:
                        v = inputs[0]
                        hi_in = v.data >> jnp.int64(32)
                        lo_in = v.data & jnp.int64(0xFFFFFFFF)
                        hval = v.validity
                        cval, cdata = v.validity, None
                    s_hi = _seg_sum(hi_in, hval, seg, cap)
                    s_lo = _seg_sum(lo_in, hval, seg, cap)
                    c = (
                        _seg_sum(cdata, cval, seg, cap)
                        if merging else _seg_count(cval, seg, cap)
                    )
                    return [
                        Column(sum_t, s_hi, ones),
                        Column(DataType.int64(), s_lo, ones),
                        Column(DataType.int64(), c, ones),
                    ]
                if merging:
                    sc, cc = inputs
                    s = _seg_sum(sc.data, sc.validity, seg, cap)
                    c = _seg_sum(cc.data, cc.validity, seg, cap)
                else:
                    v = inputs[0]
                    vv = v.data.astype(sum_t.np_dtype)
                    s = _seg_sum(vv, v.validity, seg, cap)
                    c = _seg_count(v.validity, seg, cap)
                return [
                    Column(sum_t, s, ones),
                    Column(DataType.int64(), c, ones),
                ]
            if a.fn in ("min", "max"):
                v = inputs[0]
                if v.dtype.is_string:
                    return [_seg_string_minmax(v, seg, cap, a.fn == "min")]
                vals = _seg_minmax(v.data, v.validity, seg, cap, a.fn == "min")
                # ``> 0``, not a cast: a dense slot that no row of the
                # batch matched reduces to the fill (INT32_MIN), which
                # a cast would read as "has a value"
                has = _seg_max_reduce(v.validity.astype(jnp.int32), seg, cap) > 0
                return [Column(v.dtype, jnp.where(has, vals, jnp.zeros((), vals.dtype)), has)]
            if a.fn in ("first", "first_ignores_null"):
                v = inputs[0]
                ignore = a.fn == "first_ignores_null" or mode != AggMode.PARTIAL
                if v.dtype.is_string:
                    pick = v.validity if ignore else jnp.ones_like(v.validity)
                    return [_seg_gather_first(v, pick, seg, cap)]
                vals, valid, has = _seg_first(v.data, v.validity, seg, cap, ignore)
                return [Column(v.dtype, jnp.where(valid, vals, jnp.zeros((), vals.dtype)), valid)]
            if a.fn in ("stddev_samp", "var_samp"):
                ones = jnp.ones(cap, jnp.bool_)
                if merging:
                    # parallel-variance merge in DEVIATION scale:
                    # M2 = sum(M2_i) + sum(n_i * (mean_i - mean)^2) —
                    # no large-square cancellation (mean_i - mean is
                    # deviation-sized), unlike the sum-of-squares form
                    cc, sc, mc = inputs
                    cnt = _seg_sum(cc.data, cc.validity, seg, cap)
                    fs = _seg_sum(sc.data, sc.validity, seg, cap)
                    nf = cnt.astype(jnp.float64)
                    mean_tot = fs / jnp.where(cnt > 0, nf, 1.0)
                    if seg is None:
                        mean_row = mean_tot[0]
                    elif isinstance(seg, SortedSegs):
                        mean_row = jnp.take(mean_tot, seg.seg)
                    else:
                        mean_row = jnp.take(mean_tot, seg)
                    nf_i = cc.data.astype(jnp.float64)
                    mean_i = sc.data / jnp.where(cc.data > 0, nf_i, 1.0)
                    d = mean_i - mean_row
                    term = jnp.where(cc.data > 0, nf_i * d * d, 0.0)
                    m2 = _seg_sum(mc.data + term, mc.validity, seg, cap)
                else:
                    v = inputs[0]
                    f = v.data.astype(jnp.float64)
                    if v.dtype.is_decimal:
                        # decimals carry the UNSCALED int64; rescale or
                        # every moment would be off by 10^scale
                        f = f / float(10 ** v.dtype.scale)
                    cnt = _seg_count(v.validity, seg, cap)
                    fs = _seg_sum(f, v.validity, seg, cap)
                    nf = cnt.astype(jnp.float64)
                    mean = fs / jnp.where(cnt > 0, nf, 1.0)
                    if seg is None:
                        mean_row = mean[0]
                    elif isinstance(seg, SortedSegs):
                        mean_row = jnp.take(mean, seg.seg)
                    else:
                        mean_row = jnp.take(mean, seg)
                    dev = f - mean_row
                    m2 = _seg_sum(dev * dev, v.validity, seg, cap)
                return [
                    Column(DataType.int64(), cnt, ones),
                    Column(DataType.float64(), fs, ones),
                    Column(DataType.float64(), m2, ones),
                ]
            if a.fn in ("collect_list", "collect_set"):
                arr_t = state_schema.field(f"{a.name}#list").dtype
                if seg is None:  # collect keeps the segment machinery
                    seg = jnp.zeros(inputs[0].validity.shape[0], jnp.int32)
                elif isinstance(seg, SortedSegs):
                    seg = seg.seg
                out = _collect_reduce(inputs[0], arr_t, seg, cap, merging)
                if a.fn == "collect_set":
                    out = _dedup_array_state(out)
                return [out]
            raise NotImplementedError(a.fn)

        merging = mode != AggMode.PARTIAL

        def live_rows(env, cap: int, num_rows):
            live = jnp.arange(cap) < num_rows
            if pre_filter is not None:
                pf = lower(pre_filter, in_schema, env, cap)
                live = live & pf.validity & pf.data.astype(jnp.bool_)
            return live

        @jax.jit
        def grouped_kernel(cols: Tuple[Column, ...], num_rows):
            schema = in_schema
            env, key_cols, _ = eval_inputs(cols, schema)
            cap = cols[0].validity.shape[0]
            live = live_rows(env, cap, num_rows)
            key_words = [
                jnp.where(live, w, jnp.uint64(0)) for w in encode_key_words(key_cols)
            ]
            row_idx = jnp.arange(cap, dtype=jnp.int32)
            if use_hash_sort:
                # PARTIAL-mode fast path: sort ONE u32 hash key instead
                # of every 64-bit key word.  Hash collisions between
                # distinct keys may split a group into multiple
                # segments (boundaries compare the FULL words, so
                # distinct keys never merge); duplicate partial states
                # are legal — the merge stage re-reduces them.
                h = jnp.full(cap, 2166136261, jnp.uint32)
                for w in key_words:
                    for half in (w.astype(jnp.uint32), (w >> jnp.uint64(32)).astype(jnp.uint32)):
                        h = (h ^ half) * jnp.uint32(16777619)
                key = jnp.where(live, h & jnp.uint32(0x7FFFFFFF), jnp.uint32(0xFFFFFFFF))
                _, s_idx = jax.lax.sort((key, row_idx), num_keys=1)
                s_live = jnp.take(live, s_idx)
                # full key words join the stacked u64 gather below;
                # boundaries compare sorted words against their roll
                changed = None
            else:
                words = [live.astype(jnp.uint64) ^ jnp.uint64(1)] + key_words
                sorted_ops = jax.lax.sort(tuple(words) + (row_idx,), num_keys=len(words))
                s_words, s_idx = sorted_ops[:-1], sorted_ops[-1]
                s_live = jnp.take(live, s_idx)
                changed = jnp.zeros(cap, jnp.bool_)
                for w in s_words:
                    changed = changed | (w != jnp.roll(w, 1))
                changed = changed.at[0].set(True)

            # sort every flat payload column with ONE stacked row
            # gather per dtype group — TPU gathers cost per ROW, not
            # per element (~131 ms per 1M-row gather on the real chip,
            # .bench_q1diag.log), so 20 per-column takes collapse into
            # ~4 matrix takes
            inputs = partial_inputs(env, schema, cap) if not merging else state_inputs(env)
            flat_cols = [c for ins in inputs for c in ins] + list(key_cols)
            groups: Dict = {}
            if changed is None:  # hash path: key words ride the gather
                for wi, w in enumerate(key_words):
                    groups.setdefault(("d", "uint64"), []).append(
                        (("kw", wi), "kw", w))
            for ci, c in enumerate(flat_cols):
                if c.children is not None or c.data.ndim > 2:
                    continue  # nested: per-column take fallback below
                groups.setdefault(("v", jnp.bool_.__name__), []).append(
                    (ci, "validity", c.validity))
                if c.data.ndim == 1:
                    groups.setdefault(("d", str(c.data.dtype)), []).append(
                        (ci, "data", c.data))
                else:  # (cap, W) u8 string payload: W lanes
                    for lane in range(c.data.shape[1]):
                        groups.setdefault(("d", str(c.data.dtype)), []).append(
                            ((ci, lane), "lane", c.data[:, lane]))
                if c.lengths is not None:
                    groups.setdefault(("l", str(c.lengths.dtype)), []).append(
                        (ci, "lengths", c.lengths))
            sorted_parts: Dict = {}
            for _, entries in groups.items():
                mat = jnp.stack([e[2] for e in entries], axis=1)
                smat = jnp.take(mat, s_idx, axis=0)
                for k2, (tag, kind, _) in enumerate(entries):
                    sorted_parts[(tag, kind)] = smat[:, k2]
            if changed is None:  # hash path boundary from sorted words
                changed = jnp.zeros(cap, jnp.bool_)
                for wi in range(len(key_words)):
                    sw = sorted_parts[(("kw", wi), "kw")]
                    changed = changed | (sw != jnp.roll(sw, 1))
                changed = changed.at[0].set(True)

            sorted_flat: List[Column] = []
            for ci, c in enumerate(flat_cols):
                if c.children is not None or c.data.ndim > 2:
                    g = c.take(s_idx)
                    sorted_flat.append(Column(
                        g.dtype, g.data, g.validity & s_live, g.lengths,
                        g.children))
                    continue
                valid = sorted_parts[(ci, "validity")] & s_live
                if c.data.ndim == 1:
                    data = sorted_parts[(ci, "data")]
                else:
                    data = jnp.stack(
                        [sorted_parts[((ci, lane), "lane")]
                         for lane in range(c.data.shape[1])], axis=1)
                lengths = (sorted_parts[(ci, "lengths")]
                           if c.lengths is not None else None)
                sorted_flat.append(Column(c.dtype, data, valid, lengths))
            n_inputs = sum(len(ins) for ins in inputs)
            sorted_inputs = []
            k = 0
            for ins in inputs:
                sorted_inputs.append(sorted_flat[k : k + len(ins)])
                k += len(ins)
            sorted_keys = sorted_flat[n_inputs:]
            boundary = s_live & (changed | ~jnp.roll(s_live, 1))
            boundary = boundary.at[0].set(s_live[0])
            n_out = jnp.sum(boundary.astype(jnp.int32))
            if use_segscan:
                seg = build_sorted_segs(boundary, s_live)
            else:
                seg = jnp.clip(jnp.cumsum(boundary.astype(jnp.int32)) - 1, 0, cap - 1)

            # agg inputs arrived in sorted order via the stacked
            # gathers (nested children fell back to take(s_idx))
            state_cols: List[Column] = []
            for a, t, ins in zip(aggs, in_types, sorted_inputs):
                state_cols.extend(reduce_one(a, t, ins, seg, cap, merging))

            # group key columns: already sorted; gather at boundaries
            if use_segscan:
                b_idx = seg.starts
            else:
                b_idx, _ = kept_indices(boundary)
            out_live = jnp.arange(cap) < n_out
            group_out: List[Column] = []
            for skc in sorted_keys:
                g = skc.take(b_idx)
                group_out.append(
                    Column(g.dtype, g.data, g.validity & out_live,
                           None if g.lengths is None else jnp.where(out_live, g.lengths, 0))
                )
            # state columns: indexed by seg id == output row already
            state_out = [
                Column(c.dtype, c.data, c.validity & out_live,
                       None if c.lengths is None else jnp.where(out_live, c.lengths, 0),
                       c.children)
                for c in state_cols
            ]
            return tuple(group_out + state_out), n_out


        @jax.jit
        def scalar_kernel(cols: Tuple[Column, ...], num_rows):
            """No-groups fast path: one jitted masked reduction, state
            is a 1-row batch."""
            schema = in_schema
            env, _, _ = eval_inputs(cols, schema)
            cap = cols[0].validity.shape[0]
            live = live_rows(env, cap, num_rows)
            seg = None  # global reduce fast path (no scatter)
            inputs = partial_inputs(env, schema, cap) if not merging else state_inputs(env)
            masked = [
                [Column(c.dtype, c.data, c.validity & live, c.lengths, c.children) for c in ins]
                for ins in inputs
            ]
            state_cols: List[Column] = []
            for a, t, ins in zip(aggs, in_types, masked):
                state_cols.extend(reduce_one(a, t, ins, seg, 1, merging))
            return tuple(state_cols)

        if dense:
            k = DENSE_SLOTS

            @jax.jit
            def dense_update(acc_cols, acc_n, in_cols, in_n):
                """Fold one batch into an accumulator that already holds
                its groups, without sorting: match every row against the
                accumulator's first ``k`` keys, reduce per slot under masks
                (``DenseSegs``), then combine accumulator and partial slot
                by slot with the merge form of the same ``reduce_one``.
                Returns (state columns at the accumulator's capacity, the
                unchanged group count — or capacity + 1, the overflow the
                driver rolls back, when a live row matched no held key:
                the batch brought a new group, or one past slot ``k``).
                Group-key columns are not returned: no group is added."""
                env, key_cols, cap = eval_inputs(in_cols, in_schema)
                live = live_rows(env, cap, in_n)
                slots = jnp.arange(k, dtype=jnp.int32)
                held = slots < acc_n
                match = held[:, None] & live[None, :]
                for a_key, b_key in zip(acc_cols[:n_groups_cols], key_cols):
                    # per column: a string key's word count follows its
                    # width bucket, which the batch and the seed may not
                    # share (missing words are zero padding)
                    for wa, wb in itertools.zip_longest(
                            encode_key_words([head_rows(a_key, k)]),
                            encode_key_words([b_key])):
                        match = match & (
                            (jnp.uint64(0) if wa is None else wa[:, None])
                            == (jnp.uint64(0) if wb is None else wb[None, :]))
                slot = jnp.min(jnp.where(match, slots[:, None], jnp.int32(k)), axis=0)
                n_miss = jnp.sum(live & (slot == k))

                inputs = partial_inputs(env, in_schema, cap) if not merging else state_inputs(env)
                seg = DenseSegs(slot, k)
                # accumulator rows [0, k) over partial rows [0, k), slot by slot
                both, held2 = DenseSegs(jnp.tile(slots, 2), k), jnp.tile(held, 2)
                out: List[Column] = []
                at = n_groups_cols
                for a, t, ins in zip(aggs, in_types, inputs):
                    part = reduce_one(a, t, ins, seg, k, merging)
                    acc = acc_cols[at:at + len(part)]
                    at += len(part)
                    merged = reduce_one(a, t, [
                        Column(p.dtype, jnp.concatenate([c.data[:k], p.data]),
                               jnp.concatenate([c.validity[:k], p.validity]) & held2)
                        for c, p in zip(acc, part)], both, k, True)
                    out.extend(
                        Column(m.dtype, jnp.concatenate([m.data, c.data[k:]]),
                               jnp.concatenate([m.validity & held, c.validity[k:]]))
                        for c, m in zip(acc, merged))
                cap_a = acc_cols[0].validity.shape[0]
                return tuple(out), jnp.where(n_miss > 0, cap_a + 1, acc_n).astype(jnp.int32)

            return dense_update

        # finalization: state batch -> output batch (FINAL mode)

        def combine_limbs(hi, lo):
            """(hi*2^32 + lo) limbs -> int128 (hi64, lo64)."""
            from ..exprs import int128 as I

            h128 = (hi >> jnp.int64(32), (hi << jnp.int64(32)).view(jnp.uint64))
            return I.add(*h128, *I.from_i64(lo))

        @jax.jit
        def finalize_kernel(cols: Tuple[Column, ...], num_rows):
            from ..exprs import int128 as I

            env = {f.name: c for f, c in zip(state_schema.fields, cols)}
            out: List[Column] = [env[g.name] for g in groupings]
            for a, t in zip(aggs, in_types):
                if a.fn in ("count", "count_star"):
                    out.append(env[f"{a.name}#count"])
                elif a.fn == "sum":
                    if sum_is_wide(t):
                        hc = env[f"{a.name}#sum_hi"]
                        lc = env[f"{a.name}#sum_lo{t.precision}"]
                        nn = env[f"{a.name}#nonnull"]
                        vh, vl = combine_limbs(hc.data, lc.data)
                        data, fits = I.to_i64(vh, vl)
                        # values beyond int64 overflow to NULL (Spark
                        # nulls beyond precision 38; our representable
                        # domain ends at 2^63-1 ≈ 19 digits)
                        out.append(Column(hc.dtype, data, hc.validity & fits & (nn.data > 0)))
                    else:
                        s = env[f"{a.name}#sum"]
                        nn = env[f"{a.name}#nonnull"]
                        out.append(Column(s.dtype, s.data, s.validity & (nn.data > 0)))
                elif a.fn == "avg":
                    res_t = agg_result_type("avg", t)
                    if sum_is_wide(t):
                        hc = env[f"{a.name}#sum_hi"]
                        lc = env[f"{a.name}#sum_lo{t.precision}"]
                        c = env[f"{a.name}#count"]
                        valid = hc.validity & (c.data > 0)
                        den = jnp.where(c.data == 0, jnp.int64(1), c.data)
                        vh, vl = combine_limbs(hc.data, lc.data)
                        vh, vl = I.mul_pow10(vh, vl, res_t.scale - hc.dtype.scale)
                        q, fits = I.div_round_half_up(vh, vl, den)
                        out.append(Column(res_t, q, valid & fits))
                        continue
                    s = env[f"{a.name}#sum"]
                    c = env[f"{a.name}#count"]
                    valid = s.validity & (c.data > 0)
                    den = jnp.where(c.data == 0, jnp.int64(1), c.data)
                    if res_t.is_decimal:
                        shift = res_t.scale - s.dtype.scale
                        if s.dtype.precision + shift <= 18:
                            num = s.data * jnp.int64(10**shift)
                            half = den // 2
                            adj = jnp.where(num >= 0, num + half, num - half)
                            q = jnp.where(adj >= 0, adj // den, -((-adj) // den))
                        else:
                            # shifted sum may exceed int64: exact int128
                            vh, vl = I.mul_pow10(*I.from_i64(s.data), shift)
                            q, fits = I.div_round_half_up(vh, vl, den)
                            valid = valid & fits
                        out.append(Column(res_t, q, valid))
                    else:
                        out.append(
                            Column(res_t, s.data.astype(jnp.float64) / den.astype(jnp.float64), valid)
                        )
                elif a.fn in ("stddev_samp", "var_samp"):
                    cnt = env[f"{a.name}#cnt"].data
                    m2 = env[f"{a.name}#m2"].data
                    nf = cnt.astype(jnp.float64)
                    den = jnp.where(cnt > 1, nf - 1.0, 1.0)
                    var = jnp.maximum(m2, 0.0) / den
                    val = jnp.sqrt(var) if a.fn == "stddev_samp" else var
                    out.append(Column(DataType.float64(), val, cnt > 1))
                elif a.fn in ("collect_list", "collect_set"):
                    out.append(env[f"{a.name}#list"])
                else:
                    out.append(env[f"{a.name}#value"])
            if post_sort is not None:
                # the fused downstream sort: FINAL emits one blocking
                # batch, so the key sort runs INSIDE this program —
                # no extra dispatch, no host round trip between the
                # final merge and the ordered result
                from .sort import apply_sort

                out = list(apply_sort(tuple(out), out_schema, post_sort, num_rows))
            return tuple(out)

        return grouped_kernel, scalar_kernel, finalize_kernel

    # ------------------------------------------------------ execution

    def _reduce_batch(self, batch: RecordBatch, in_schema: Schema) -> RecordBatch:
        """One device reduce of a batch against schema -> state batch."""
        if self.groupings:
            cols, n_out = self._grouped_kernel(tuple(batch.columns), batch.num_rows)
            return RecordBatch(self._state_schema, list(cols), trace.read_scalar(n_out))
        cols = self._scalar_kernel(tuple(batch.columns), batch.num_rows)
        return RecordBatch(self._state_schema, list(cols), 1)

    def _update_kernels(self):
        """(grouped_update, scalar_update): the whole-stage update
        programs — per input batch, ONE jitted program reduces the
        batch AND folds it into the stacked accumulator state (the
        reduce and merge kernels inline into a single XLA executable;
        the concat between them is traced, not dispatched).  This is
        the q01 dispatch collapse: the eager path cost one program per
        reduce plus ~#state-buffers programs per concat+merge cascade.

        grouped_update(acc_cols, acc_n, in_cols, in_n, out_cap) ->
        (state cols sliced to the STATIC ``out_cap``, merged group
        count — exact up to out_cap, and over it whenever the batch
        overflowed the bucket); when the count exceeds out_cap the
        caller redoes the batch through the eager reduce+merge, which
        re-buckets the grown accumulator to a power-of-two capacity.
        scalar_update(acc_cols, in_cols, in_n) -> 1-row state cols."""
        if self._update_k is None:
            from ..batch import _concat_device_cols
            from ..runtime import dispatch
            from ..runtime.kernel_cache import cached_kernel

            twin = _StateMerger.for_agg(self)._twin
            # raw (uninstrumented) kernels: inlined sub-programs are
            # not dispatches
            reduce_g = dispatch.raw(self._grouped_kernel)
            reduce_s = dispatch.raw(self._scalar_kernel)
            merge_g = dispatch.raw(twin._grouped_kernel)
            merge_s = dispatch.raw(twin._scalar_kernel)
            state_schema = self._state_schema

            def build():
                @partial(jax.jit, static_argnums=(4,))
                def grouped_update(acc_cols, acc_n, in_cols, in_n, out_cap):
                    part_cols, part_n = reduce_g(in_cols, in_n)
                    # merge the accumulator with the partial's first
                    # out_cap rows, not its whole batch-sized buffer:
                    # any more groups than that overflow the bucket
                    # anyway.  Merging at cap_a + batch capacity
                    # (66,560 rows for q01) sorted a batch of padding
                    # per update, and the v5e compiler does not survive
                    # that program (SIGSEGV in its HLO passes; the
                    # merge alone compiles for minutes)
                    part_cols = tuple(head_rows(p, out_cap) for p in part_cols)
                    kept = jnp.minimum(part_n, out_cap)
                    cap_a = acc_cols[0].validity.shape[0]
                    comb = tuple(
                        _concat_device_cols(
                            f.dtype, [a, p], [acc_n, kept], cap_a + out_cap
                        )
                        for f, a, p in zip(state_schema.fields, acc_cols, part_cols)
                    )
                    merged, m_n = merge_g(comb, acc_n + kept)
                    # a truncated partial (part_n > out_cap) reports a
                    # count over out_cap: the caller's overflow check
                    # then redoes the batch through the eager path
                    # int32 like the seed count: the next update takes
                    # this scalar as acc_n, and a second dtype would be
                    # a second compile of the same program
                    return (tuple(head_rows(c, out_cap) for c in merged),
                            jnp.maximum(m_n, part_n).astype(jnp.int32))

                @jax.jit
                def scalar_update(acc_cols, in_cols, in_n):
                    part_cols = reduce_s(in_cols, in_n)
                    comb = tuple(
                        _concat_device_cols(f.dtype, [a, p], [1, 1], 2)
                        for f, a, p in zip(state_schema.fields, acc_cols, part_cols)
                    )
                    return merge_s(comb, 2)

                return grouped_update, scalar_update

            self._update_k = cached_kernel(
                ("agg_update",) + self._kernel_key, build
            )
        return self._update_k

    def _dense_update_kernel(self):
        """dense_update(acc_cols, acc_n, in_cols, in_n): the sort-free
        twin of ``grouped_update`` (``_build_kernels``), a program and
        a dispatch label of its own."""
        if self._dense_k is None:
            from ..runtime.kernel_cache import cached_kernel

            in_schema = self.children[0].schema
            self._dense_k = cached_kernel(
                ("agg_dense_update",) + self._kernel_key,
                lambda: self._build_kernels(in_schema, dense=True))
        return self._dense_k

    def _fused_scalar_update(self, batch: RecordBatch, in_schema: Schema,
                             consumer: "_AggConsumer") -> None:
        """No-groupings fused update: the 1-row state never syncs."""
        acc = consumer.take_state()
        if acc is None:
            consumer.set_state(self._reduce_batch(batch, in_schema))
            return
        _, scalar_update = self._update_kernels()
        cols = scalar_update(
            tuple(acc.columns), tuple(batch.columns), batch.num_rows
        )
        consumer.set_state(RecordBatch(self._state_schema, list(cols), 1))

    def _merge_states(self, states: List[RecordBatch]) -> Optional[RecordBatch]:
        """Associative re-reduce of state batches (merge mode kernel on
        the state schema)."""
        if not states:
            return None
        if len(states) == 1:
            return states[0]
        merged_input = concat_batches(states)
        merger = _StateMerger.for_agg(self)
        return merger.reduce(merged_input)

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        child_stream = self.children[0].execute(partition, ctx)
        in_schema = self.children[0].schema

        def stream():
            merger = _StateMerger.for_agg(self)
            pending: List[RecordBatch] = []
            pending_rows = 0
            consumer = _AggConsumer(self, ctx)
            ctx.mem.register_consumer(consumer)
            in_rows = 0
            skipping = False
            fused_update = bool(conf.FUSED_AGG_UPDATE.get())
            fctx = (
                _FusedGroupedUpdate(self, consumer, in_schema)
                if fused_update and self.groupings else None
            )
            try:
                for batch in child_stream:
                    if not ctx.is_task_running():
                        return
                    in_rows += batch.num_rows
                    part: Optional[RecordBatch] = None
                    # the consumer OWNS the accumulator: a spill() from
                    # the memory manager atomically moves it out, and a
                    # take_state() here starts a fresh accumulation
                    # (re-merging a spilled state would double-count it)
                    if fused_update and not skipping:
                        with self.metrics.timer("elapsed_compute"):
                            if fctx is not None:
                                updated = fctx.update(batch)
                            else:
                                self._fused_scalar_update(batch, in_schema, consumer)
                                updated = True
                    else:
                        updated = False
                    if not updated:
                        with self.metrics.timer("elapsed_compute"):
                            part = self._reduce_batch(batch, in_schema)
                    acc_rows_hint = consumer.state_rows
                    if (
                        self.mode == AggMode.PARTIAL
                        and self.supports_partial_skipping
                        and self.groupings
                        and not skipping
                        and bool(conf.ENABLE_PARTIAL_AGG_SKIPPING.get())
                        and in_rows >= int(conf.PARTIAL_AGG_SKIPPING_MIN_ROWS.get())
                    ):
                        acc_rows = acc_rows_hint + pending_rows + (
                            0 if part is None else part.num_rows
                        )
                        if acc_rows / max(1, in_rows) > float(conf.PARTIAL_AGG_SKIPPING_RATIO.get()):
                            skipping = True
                            self.metrics.add("partial_skipped", 1)
                    if updated:
                        continue  # batch already folded into the accumulator
                    if skipping:
                        # stream states through; downstream merge finishes
                        self._record_batch(part)
                        yield part
                        continue
                    pending.append(part)
                    pending_rows += part.num_rows
                    if acc_rows_hint == 0 or pending_rows >= max(acc_rows_hint, 4096):
                        acc = consumer.take_state()
                        group = ([acc] if acc else []) + pending
                        with self.metrics.timer("elapsed_compute"):
                            acc = self._merge_states(group) if len(group) > 1 else group[0]
                        pending, pending_rows = [], 0
                        consumer.set_state(acc)
                # finish: merge residue + spills
                if fctx is not None:
                    fctx.finish()  # resolve the deferred overflow check
                final_acc = consumer.take_state()
                tail = ([final_acc] if final_acc else []) + pending
                tail += consumer.drain_spills()
                final_state = self._merge_states(tail) if tail else None
                if final_state is not None and final_state.num_rows > 0:
                    out = self._finish(final_state)
                    self._record_batch(out)
                    yield out
                elif not self.groupings:
                    # empty input, global agg still emits one row
                    empty = RecordBatch(
                        in_schema,
                        list(_empty_batch(in_schema).columns),
                        0,
                    )
                    part = self._reduce_batch(empty.to_device(), in_schema)
                    out = self._finish(part)
                    self._record_batch(out)
                    yield out
            finally:
                ctx.mem.unregister_consumer(consumer)

        out_stream = stream()
        # per-group-key NDV sketching (runtime/stats.py, behind
        # spark.blaze.stats.sketches): the output layout puts the
        # grouping keys first, so the sketch hashes exactly those
        # columns.  Disarmed cost is the one sketches_enabled() read.
        if self.groupings:
            from ..runtime import stats as _stats

            if _stats.sketches_enabled():
                out_stream = _stats.sketch_stream(
                    self, len(self.groupings), out_stream)
        return out_stream

    def _finish(self, state: RecordBatch) -> RecordBatch:
        if self.mode == AggMode.FINAL:
            if self.emit_state:
                # boundary fusion: the downstream fused shuffle write
                # owns the finalize (absorb_traceable_chain) — hand it
                # the raw state
                return state
            cols = self._finalize_kernel(tuple(state.columns), state.num_rows)
            n = state.num_rows
            if self.post_fetch is not None:
                # fused Limit/fetch: rows past n are padding after the
                # in-program post_sort, so a host-side clamp suffices
                n = min(n, self.post_fetch)
            return RecordBatch(self._schema, list(cols), n)
        return state


def _empty_batch(schema: Schema) -> RecordBatch:
    from ..batch import batch_from_pydict

    return batch_from_pydict({f.name: [] for f in schema.fields}, schema, capacity=int(conf.MIN_CAPACITY.get()))


class _StateMerger:
    """Merge-mode reducer over the state schema (sum of sums etc.).
    Built lazily per AggExec INSTANCE (never keyed by id(): ids recycle
    after GC and a stale twin silently merges with the wrong schema);
    the merge kernels live in a PARTIAL_MERGE-mode twin on the state
    schema."""

    def __init__(self, agg: "AggExec"):
        class _Src(ExecNode):
            def __init__(self, schema):
                super().__init__([])
                self._s = schema

            @property
            def schema(self):
                return self._s

        self._twin = AggExec(
            _Src(agg._state_schema),
            AggMode.PARTIAL_MERGE,
            [GroupingExpr(_col(g.name), g.name) for g in agg.groupings],
            agg.aggs,
            dup_groups_ok=agg.mode == AggMode.PARTIAL,
        )

    @classmethod
    def for_agg(cls, agg: "AggExec") -> "_StateMerger":
        if agg._merger is None:
            agg._merger = cls(agg)
        return agg._merger

    def reduce(self, state_batch: RecordBatch) -> RecordBatch:
        return self._twin._reduce_batch(state_batch.to_device(), state_batch.schema)


def _col(name):
    from ..exprs.ir import Col

    return Col(name)


class _LazyAccState:
    """Accumulator columns with a DEVICE-RESIDENT occupancy count
    (``n_dev``: the int32 scalar the update program returned, never
    fetched on the per-batch path).  ``hint`` is the last host-known
    count — exact once the deferred overflow check resolved
    (``pending_check`` False), a stale-by-one heuristic before that
    (partial-skipping ratio, merge thresholds).  ``materialize()``
    produces a plain RecordBatch, syncing the scalar only when the
    check is still outstanding."""

    __slots__ = ("schema", "cols", "n_dev", "hint", "pending_check")

    def __init__(self, schema: Schema, cols, n_dev, hint: int):
        self.schema = schema
        self.cols = list(cols)
        self.n_dev = n_dev
        self.hint = int(hint)
        self.pending_check = True

    @property
    def capacity(self) -> int:
        return int(self.cols[0].validity.shape[0])

    @property
    def num_rows(self) -> int:
        return self.hint

    def memory_size(self) -> int:
        return RecordBatch(self.schema, self.cols, self.hint).memory_size()

    def materialize(self) -> RecordBatch:
        n = self.hint if not self.pending_check else trace.read_scalar(self.n_dev)
        return RecordBatch(self.schema, list(self.cols), n)


class _FusedGroupedUpdate:
    """Drives the grouped single-program update with the accumulator
    count kept device-resident: batch N+1's program is dispatched
    against batch N's DEVICE count scalar, and N's overflow check
    (``merged groups > bucket capacity``) syncs only AFTER that
    dispatch — so the fused path never stalls the dispatch pipeline on
    a per-batch scalar fetch (over a remote chip the old ``int(m_n)``
    cost a full RTT between every two update programs).

    Rollback: a detected overflow means the checked state AND the
    just-dispatched update consuming it are both invalid.  The driver
    retains the last PROVEN state and the one input batch in flight,
    and rebuilds both steps through the eager reduce+merge (which
    re-buckets the grown accumulator) — the pre-existing overflow
    semantics, paid only when cardinality actually outgrows the bucket.

    Which program: while the last PROVEN group count fits
    ``DENSE_SLOTS`` and every aggregate is order-free
    (``dense_eligible``), the batch goes to ``dense_update`` — matched
    against the accumulator's own keys, no sort; a key it does not hold
    comes back as an overflow and takes the rollback above.  Past the
    slots, after such a miss (keys that arrive over time would pay a
    rollback each), or with an order-sensitive aggregate:
    ``grouped_update``.

    Observability (runtime.dispatch counters):
    ``fused_agg_deferred_syncs`` — post-dispatch count fetches (the
    happy path), ``fused_agg_stall_syncs`` — fetches that DID gate a
    dispatch (mode switches; zero on the steady-state path, pinned by
    tests), ``fused_agg_rollbacks`` — overflow rebuilds (dense misses
    among them), ``agg_grouped_updates`` — update programs launched,
    ``agg_dense_updates`` — those that were ``dense_update``."""

    def __init__(self, agg: "AggExec", consumer: "_AggConsumer",
                 in_schema: Schema):
        self._agg = agg
        self._consumer = consumer
        self._in_schema = in_schema
        self._good: Optional[Tuple[tuple, int]] = None  # (cols, n) proven
        # (input state, input batch, produced state, bucket capacity)
        self._pending = None
        # a dense update met a key the accumulator lacked: this stream's
        # keys arrive over time, so it stays on the sort update, which
        # takes a new key in with no rollback — one miss a stream at most
        self._dense_missed = False

    def update(self, batch: RecordBatch) -> bool:
        """Fold one input batch into the accumulator; False = this
        batch must take the eager pending/doubling path (accumulator
        outgrew one batch bucket)."""
        from ..batch import slice_rows_device
        from ..runtime import dispatch

        agg = self._agg
        consumer = self._consumer
        st = consumer.take_state_any()
        if st is None:
            # seed (or post-spill restart): reduce, shrink to its own
            # bucket so steady-state updates sort 2x acc_cap rows, not
            # 2x batch_cap (q01: 4 groups -> min capacity)
            self._pending = None
            part = agg._reduce_batch(batch, self._in_schema)
            cap = bucket_capacity(max(part.num_rows, 1))
            if cap < part.capacity:
                part = slice_rows_device(part, 0, part.num_rows)
            consumer.set_state(part)
            self._good = (tuple(part.columns), part.num_rows)
            return True
        if st.capacity > batch.capacity:
            resolved = self._resolve_to_batch(st, counter="fused_agg_stall_syncs")
            if resolved is not None:
                consumer.set_state(resolved)
            return False
        out_cap = st.capacity
        if isinstance(st, _LazyAccState):
            acc_cols, acc_n = tuple(st.cols), st.n_dev
        else:
            # a plain RecordBatch entering the fused path (the eager
            # pending-merge interleave, a post-rollback resume) is
            # proven by construction: it MUST become the rollback base,
            # or an overflow after the resume would rebuild from a
            # stale accumulator and silently drop its merged groups
            self._good = (tuple(st.columns), st.num_rows)
            acc_cols, acc_n = tuple(st.columns), jnp.int32(st.num_rows)
        good_n = self._good[1] if self._good is not None else out_cap
        # few PROVEN groups and order-free aggregates: match the batch
        # against the accumulator's keys instead of sorting it.  A key
        # the accumulator lacks comes back as an overflow, like a full
        # bucket, and rolls back through the same deferred check
        # (out_cap: minBatchCapacity may be set under the slot count)
        dense = (agg._dense_ok and not self._dense_missed
                 and good_n <= DENSE_SLOTS <= out_cap)
        if dense:
            state_cols, m_n = agg._dense_update_kernel()(
                acc_cols, acc_n, tuple(batch.columns), batch.num_rows
            )
            cols = acc_cols[:len(agg.groupings)] + state_cols
            hint = good_n
            dispatch.record("agg_dense_updates")
        else:
            grouped_update, _ = agg._update_kernels()
            cols, m_n = grouped_update(
                acc_cols, acc_n, tuple(batch.columns), batch.num_rows, out_cap
            )
            hint = min(good_n + batch.num_rows, out_cap)
        dispatch.record("agg_grouped_updates")
        new = _LazyAccState(agg._state_schema, cols, m_n, hint=hint)
        consumer.set_state(new)
        prev, self._pending = self._pending, (st, batch, new, out_cap, dense)
        if prev is not None:
            # deferred: the fetched program precedes the one just
            # dispatched in device queue order — no pipeline stall
            self._resolve(prev, counter="fused_agg_deferred_syncs")
        return True

    def finish(self) -> None:
        """Resolve the outstanding check before the stream's finish
        path materializes the state (once per stream, not per batch)."""
        st = self._consumer.take_state_any()
        if st is None:
            self._pending = None
            return
        resolved = self._resolve_to_batch(st, counter="fused_agg_finish_syncs")
        if resolved is not None:
            self._consumer.set_state(resolved)

    # ----------------------------------------------------- internals

    def _resolve(self, pending, counter: str) -> None:
        from ..runtime import dispatch

        in_st, in_batch, out_st, out_cap, dense = pending
        n = trace.read_scalar(out_st.n_dev)
        dispatch.record(counter)
        if n <= out_cap:
            out_st.hint = n
            out_st.pending_check = False
            self._good = (tuple(out_st.cols), n)
            return
        # overflow: rebuild from the last proven state through the
        # eager reduce+merge (re-buckets to a power-of-two capacity,
        # preserving the shape-bucketing invariant), replaying the
        # overflowed input batch AND — when a later update already
        # consumed the invalid state — the in-flight batch after it
        dispatch.record("fused_agg_rollbacks")
        self._dense_missed = self._dense_missed or dense
        agg = self._agg
        good_cols, good_n = self._good
        acc = RecordBatch(agg._state_schema, list(good_cols), good_n)
        part = agg._reduce_batch(in_batch, self._in_schema)
        acc = agg._merge_states([acc, part])
        cur = self._pending
        if cur is not None and cur[2] is not out_st:
            part2 = agg._reduce_batch(cur[1], self._in_schema)
            acc = agg._merge_states([acc, part2])
        self._pending = None
        self._good = (tuple(acc.columns), acc.num_rows)
        self._consumer.set_state(acc)

    def _resolve_to_batch(self, st, counter: str) -> Optional[RecordBatch]:
        """Resolve ``st`` (the consumer's newest state) into a plain
        RecordBatch, running the outstanding overflow check first.
        None = the state ended up in a spill (a rollback re-seats the
        rebuilt accumulator in the consumer, where a concurrent memmgr
        spill may legitimately claim it — the final merge then reads
        it back through drain_spills)."""
        if self._pending is not None:
            pending, self._pending = self._pending, None
            self._resolve(pending, counter=counter)
            if pending[2] is st and pending[2].pending_check:
                # the check rolled the state back: the consumer holds
                # the rebuilt accumulator (unless a spill just took it)
                replaced = self._consumer.take_state_any()
                assert replaced is None or isinstance(replaced, RecordBatch)
                return replaced
        if isinstance(st, _LazyAccState):
            return st.materialize()
        return st


class _AggConsumer(MemConsumer):
    """OWNS the in-flight accumulator state; on pressure, serializes it
    to a Spill (host-RAM or disk tier) and clears it, so the exec
    restarts accumulation — never re-merging a spilled state
    (≙ agg spill path agg_table.rs:343-375, flattened: whole-state
    chunks re-reduced at finish)."""

    name = "agg"

    def __init__(self, agg: AggExec, ctx: TaskContext):
        super().__init__()
        self._agg = agg
        self._state: Optional[RecordBatch] = None
        self._spills: List[Spill] = []
        self._lock = threading.Lock()
        self._quiesced = threading.Condition(self._lock)
        self._inflight = 0      # spills serializing outside the lock
        self._closed = False    # drain started: no further spills

    @property
    def state_rows(self) -> int:
        s = self._state
        return s.num_rows if s is not None else 0

    def take_state(self) -> Optional[RecordBatch]:
        """Atomically claim the accumulator for merging.  A concurrent
        spill() (MemManager serving another thread's pressure) either
        runs before (state already spilled, returns None here) or after
        set_state() — never both paths on the same state, which would
        double-count it.  Device-count states resolve to plain batches
        here (callers on this path need the host row count)."""
        with self._lock:
            s, self._state = self._state, None
        if isinstance(s, _LazyAccState):
            assert not s.pending_check, (
                "fused-update state taken with its overflow check "
                "unresolved (resolve via _FusedGroupedUpdate first)"
            )
            s = s.materialize()
        return s

    def take_state_any(self):
        """Claim the accumulator WITHOUT materializing: the fused
        update path keeps the occupancy count device-resident."""
        with self._lock:
            s, self._state = self._state, None
            return s

    def set_state(self, state) -> None:
        # state handoff and accounting are atomic w.r.t. spill(): a
        # spill landing between them would otherwise leave mem_used
        # reporting phantom memory after the state was already cleared
        with self._lock:
            self._state = state
            self.set_mem_used_no_trigger(state.memory_size())
        self.trigger_spill_check()

    def spill(self) -> int:
        # fault probe at the spill entry, outside the state lock (see
        # _SortState.spill)
        faults.hit("spill.write")
        with self._lock:
            if self._closed:
                # finish() is draining: a spill landing now would
                # append AFTER the drain cleared the list and the
                # state would be silently LOST (observed as missing
                # distinct rows at SF0.1 under a capped budget)
                return 0
            state = self._state
            if state is None:
                return 0
            if isinstance(state, _LazyAccState) and state.pending_check:
                # the deferred overflow check hasn't resolved: this
                # state may be invalid, and spilling it would bake the
                # corruption into the final merge.  It is at most one
                # batch bucket anyway — let pressure fall on the big
                # consumers for this one batch.
                return 0
            self._state = None
            freed = state.memory_size()
            self.set_mem_used_no_trigger(0)
            self._inflight += 1
        # serialize outside the lock: this thread owns `state` now
        if isinstance(state, _LazyAccState):
            state = state.materialize()
        try:
            sp = try_new_spill()
            try:
                sp.write_frame(serialize_batch(state))
                sp.complete()
            except BaseException:
                # never leak the spill's temp file on a failed write
                # (the task retry rebuilds the accumulator state, but
                # the blaze_spill_* file would survive to process exit)
                sp.release()
                raise
            with self._quiesced:
                self._spills.append(sp)
        finally:
            # ALWAYS release the in-flight slot, or a spill error
            # would leave drain_spills() waiting forever
            with self._quiesced:
                self._inflight -= 1
                self._quiesced.notify_all()
        self._agg.metrics.add("spill_count", 1)
        self._agg.metrics.add("spilled_bytes", sp.size)
        return freed

    def drain_spills(self) -> List[RecordBatch]:
        # close the consumer to new spills, then wait out any spill
        # already past the state-claim (it still owns an accumulator
        # chunk that MUST reach the final merge)
        with self._quiesced:
            self._closed = True
            self._quiesced.wait_for(lambda: self._inflight == 0)
            spills, self._spills = self._spills, []
        out: List[RecordBatch] = []
        for sp in spills:
            while True:
                payload = sp.read_frame()
                if payload is None:
                    break
                out.append(deserialize_batch(payload, self._agg._state_schema).to_device())
            sp.release()
        return out
