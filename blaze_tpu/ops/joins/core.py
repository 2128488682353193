"""Join core: sorted-key-table build/probe with exact verification.

≙ reference join_hash_map.rs (open-addressing u32 map with raw-bytes
serialization for broadcast) — rebuilt for XLA: no pointer chasing, no
per-row probe loops; everything is sort, cumulative scans, gather and
ONE binary search of the key table per probe batch.  The keys are
hashes, uniform in their high bits, so the search starts inside the
bucket of the probe key's hash prefix (bucket offsets kept in the map)
and runs as many steps as the map's largest bucket needs; the upper
bound of a candidate range is its run length, kept in the map too.  The
map itself is a pytree of five device arrays beside the build batch,
trivially serializable/broadcastable like the reference's raw-bytes map.

All kernels are per-Joiner jitted closures — Exprs never appear as jit
static arguments (Expr.__eq__ builds IR nodes, which poisons any
hash-keyed cache comparison).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...batch import Column, RecordBatch, bucket_capacity, concat_batches
from ...exprs.compile import lower
from ...exprs.hash import xxhash64_columns
from ...exprs.ir import Expr
from ...runtime import dispatch, trace
from ...schema import DataType, Field, Schema
from ..filter import compact_columns


class JoinType(enum.Enum):
    INNER = "inner"
    LEFT = "left"
    RIGHT = "right"
    FULL = "full"
    LEFT_SEMI = "left_semi"
    LEFT_ANTI = "left_anti"
    RIGHT_SEMI = "right_semi"
    RIGHT_ANTI = "right_anti"
    EXISTENCE = "existence"


@jax.tree_util.register_pytree_node_class
@dataclass
class JoinMap:
    """Sorted build-side key table + the build batch it indexes.

    Raw-bytes serializable (≙ join_hash_map.rs:290-454): the serialized
    form carries the sorted table, its run lengths, its bucket offsets
    AND the data batch, so a probe-side executor rebuilds it with buffer
    copies only — no re-sort, no key re-hash, no run-length or bucket
    pass.

    Beside the pytree, on the host: the largest candidate total the map
    has shown for each probe-batch capacity, which picks the probe's
    output bucket before that batch's own total is read.  Neither a
    leaf nor serialized: a rebuilt or deserialized map starts empty."""

    sorted_keys: jnp.ndarray     # uint64 (cap,) sorted
    sorted_rows: jnp.ndarray     # int32 (cap,) original row per key
    run_lens: jnp.ndarray        # int32 (cap,) positions j >= i holding sorted_keys[i]
    bucket_offsets: jnp.ndarray  # int32 (2**b + 1,) where each hash prefix starts; [-1] = live keys
    max_bucket: jnp.ndarray      # int32 () live keys in the largest bucket
    num_rows: int                # live build rows (static)
    batch: RecordBatch           # build-side data

    #: guarded-by declaration (analysis/guarded.py): the tasks that
    #: share a broadcast map raise its peaks without a lock
    LOCK_FREE = {"_candidate_peaks": "max-only update: a lost race keeps "
                                     "a lower peak, which costs one probe "
                                     "re-launch, never a row"}

    def __post_init__(self):
        self._candidate_peaks: Dict[int, int] = {}

    def candidate_peak(self, probe_capacity: int) -> Optional[int]:
        """The largest candidate total a probe batch of this capacity
        has shown against this map; None before the first."""
        return self._candidate_peaks.get(probe_capacity)

    def raise_candidate_peak(self, probe_capacity: int, total: int) -> None:
        if total > self._candidate_peaks.get(probe_capacity, -1):
            self._candidate_peaks[probe_capacity] = total

    def tree_flatten(self):
        return ((self.sorted_keys, self.sorted_rows, self.run_lens,
                 self.bucket_offsets, self.max_bucket, self.batch), (self.num_rows,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        *index, batch = children
        return cls(*index, aux[0], batch)

    def serialize(self) -> bytes:
        import struct

        from ...io.batch_serde import serialize_batch

        sk = np.asarray(self.sorted_keys, dtype=np.uint64)
        sr = np.asarray(self.sorted_rows, dtype=np.int32)
        rl = np.asarray(self.run_lens, dtype=np.int32)
        bo = np.asarray(self.bucket_offsets, dtype=np.int32)
        head = struct.pack("<IIII", self.num_rows, sk.shape[0], bo.shape[0],
                           int(self.max_bucket))
        return (head + sk.tobytes() + sr.tobytes() + rl.tobytes() + bo.tobytes()
                + serialize_batch(self.batch))

    @classmethod
    def deserialize(cls, data: bytes, build_schema: Schema) -> "JoinMap":
        import struct

        from ...io.batch_serde import deserialize_batch

        num_rows, cap, n_offsets, max_bucket = struct.unpack_from("<IIII", data, 0)
        off = 16
        sk = np.frombuffer(data, np.uint64, cap, off).copy()
        off += 8 * cap
        sr = np.frombuffer(data, np.int32, cap, off).copy()
        off += 4 * cap
        rl = np.frombuffer(data, np.int32, cap, off).copy()
        off += 4 * cap
        bo = np.frombuffer(data, np.int32, n_offsets, off).copy()
        off += 4 * n_offsets
        # memoryview slice: no second full-payload copy
        batch = (
            deserialize_batch(memoryview(data)[off:], build_schema)
            .with_capacity(cap)
            .to_device()
        )
        return cls(jnp.asarray(sk), jnp.asarray(sr), jnp.asarray(rl), jnp.asarray(bo),
                   jnp.int32(max_bucket), num_rows, batch)


def make_build_kernel(build_schema: Schema, build_keys: Sequence[Expr]):
    """Jitted sorted-key-table builder over the build schema (shared by
    Joiner and BroadcastJoinBuildHashMapExec); cached process-wide."""
    from ...exprs.compile import expr_key
    from ...runtime.kernel_cache import cached_kernel, schema_key

    build_keys = list(build_keys)
    key = ("join_build_kernel", schema_key(build_schema),
           tuple(expr_key(e) for e in build_keys))
    return cached_kernel(key, lambda: _make_build_kernel_impl(build_schema, build_keys))


def _make_build_kernel_impl(build_schema: Schema, build_keys):

    @jax.jit
    def build_kernel(cols: Tuple[Column, ...], num_rows):
        cap = cols[0].validity.shape[0]
        env = {f.name: c for f, c in zip(build_schema.fields, cols)}
        key_cols = [lower(e, build_schema, env, cap) for e in build_keys]
        live = jnp.arange(cap) < num_rows
        keys = jnp.where(live, _key_hash(key_cols), _SENTINEL)
        rows = jnp.arange(cap, dtype=jnp.int32)
        sorted_keys, sorted_rows = jax.lax.sort((keys, rows), num_keys=1)
        return (sorted_keys, sorted_rows, run_lengths(sorted_keys),
                *bucket_offsets(sorted_keys))

    return build_kernel


def run_lengths(sorted_keys) -> jnp.ndarray:
    """int32 (cap,): for every position i of a sorted key table, how
    many positions j >= i hold the same key — at a run's start, the
    run's length.  From the sorted keys alone: run starts by neighbour
    compare, the next start by a reverse cumulative minimum."""
    cap = sorted_keys.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    starts_here = jnp.where(sorted_keys[1:] != sorted_keys[:-1], pos[1:], cap)
    next_start = jax.lax.cummin(
        jnp.append(starts_here, jnp.int32(cap)), reverse=True)
    return next_start - pos


#: prefix bits beyond log2(cap).  At half a live key a bucket on average
#: the largest bucket of 2^15–2^19 uniform keys holds 5–7: 3 steps
#: whatever the keys.  One bit less holds 7–9 (3 or 4 steps, by the
#: keys: a step is ~0.94 ms of a 65,536-row probe on a v5e), one bit
#: more still 5 (3 steps) for twice the bytes.
_EXTRA_PREFIX_BITS = 1


def bucket_offsets(sorted_keys) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(offsets, max_bucket) of a sorted key table.  ``offsets`` is
    int32 (2**b + 1,): for each value of a key's top ``b`` bits the
    position where that prefix starts, and in the last slot the count
    of live keys — the sentinel tail (dead rows, NULL keys) lies in no
    bucket.  ``b`` follows the table's capacity alone.  ``max_bucket``
    is the largest bucket's live keys, which bounds the probe's search.
    From the sorted keys alone: every position scatters into its
    prefix's slot by minimum (the tail into the last), and an empty
    bucket takes the next start by a reverse cumulative minimum."""
    cap = sorted_keys.shape[0]
    b = (cap - 1).bit_length() + _EXTRA_PREFIX_BITS
    prefix = (sorted_keys >> np.uint64(64 - b)).astype(jnp.int32)
    slot = jnp.where(sorted_keys != _SENTINEL, prefix, 1 << b)
    starts = jnp.full((1 << b) + 1, cap, jnp.int32).at[slot].min(
        jnp.arange(cap, dtype=jnp.int32), indices_are_sorted=True)
    offsets = jax.lax.cummin(starts, reverse=True)
    return offsets, jnp.max(offsets[1:] - offsets[:-1])


def build_join_map(batch: RecordBatch, build_kernel) -> JoinMap:
    index = build_kernel(tuple(batch.columns), batch.num_rows)
    return JoinMap(*index, batch.num_rows, batch)


_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


def _key_hash(cols: Sequence[Column]) -> jnp.ndarray:
    """uint64 key hash; rows with ANY null key get the sentinel (null
    never equals null in join equality)."""
    h = xxhash64_columns(cols).view(jnp.uint64)
    all_valid = cols[0].validity
    for c in cols[1:]:
        all_valid = all_valid & c.validity
    return jnp.where(all_valid, h, _SENTINEL)


def probe_counts(jmap_keys, run_lens, offsets, max_bucket, probe_keys):
    """(lo, counts, steps) of candidate ranges per probe row: ONE left
    binary search of the sorted key table, begun inside the bucket of
    the probe key's hash prefix.  Every key of a smaller prefix sorts
    before ``offsets[p]`` and the bucket holds at most ``max_bucket``
    keys, so the left search of ``[offsets[p], offsets[p] + max_bucket)``
    (cut at the live keys' end) is the search of the whole table, closed
    in ``steps`` = bit length of ``max_bucket`` steps: 3 over uniform
    hashes, log2(cap) + 1 where the table is one run of one key.
    Sentinel probes (NULL keys, a batch's dead rows) start closed at
    the live keys' end.  Where the key found at ``lo`` is the probe's,
    the range is that key's run (``run_lens``, built with the table),
    else it is empty.  ``lo == cap`` clips to the last key, which a
    probe above every key cannot equal."""
    cap = jmap_keys.shape[0]
    b = (offsets.shape[0] - 1).bit_length() - 1
    is_sent = probe_keys == _SENTINEL
    n_live = offsets[-1]
    prefix = (probe_keys >> np.uint64(64 - b)).astype(jnp.int32)
    lo = jnp.where(is_sent, n_live, offsets[prefix])
    hi = jnp.minimum(lo + max_bucket, n_live)
    steps = 32 - jax.lax.clz(max_bucket)

    def step(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) >> 1
        right = (lo < hi) & (jmap_keys[jnp.minimum(mid, cap - 1)] < probe_keys)
        return jnp.where(right, mid + 1, lo), jnp.where(right, hi, jnp.minimum(mid, hi))

    lo, _ = jax.lax.fori_loop(0, steps, step, (lo, hi))
    at = jnp.minimum(lo, cap - 1)
    found = (jmap_keys[at] == probe_keys) & ~is_sent
    return lo, jnp.where(found, run_lens[at], 0), steps


def expand_pairs(lo, counts, out_cap: int):
    """Two-phase expansion: (probe_row, build_pos) pairs for all
    candidate matches, padded to out_cap."""
    offsets = jnp.cumsum(counts)
    total = offsets[-1] if counts.shape[0] else jnp.int64(0)
    out_i = jnp.arange(out_cap)
    probe_row = jnp.searchsorted(offsets, out_i, side="right")
    probe_row = jnp.clip(probe_row, 0, counts.shape[0] - 1)
    prev_off = offsets[probe_row] - counts[probe_row]
    build_pos = lo[probe_row] + (out_i - prev_off)
    live = out_i < total
    return probe_row.astype(jnp.int32), build_pos.astype(jnp.int32), live


def _eq_col(a: Column, b: Column):
    """Join-key equality (null != null)."""
    from ...exprs import strings as S

    if a.dtype.is_string:
        v = S.str_eq(a, b)
    else:
        ca, cb = a.data, b.data
        if ca.dtype != cb.dtype:
            wide = jnp.promote_types(ca.dtype, cb.dtype)
            ca, cb = ca.astype(wide), cb.astype(wide)
        v = ca == cb
    return v & a.validity & b.validity


def _null_columns(schema: Schema, cap: int) -> List[Column]:
    cols = []
    for f in schema.fields:
        if f.dtype.is_string:
            cols.append(
                Column(
                    f.dtype,
                    jnp.zeros((cap, f.dtype.string_width), jnp.uint8),
                    jnp.zeros(cap, jnp.bool_),
                    jnp.zeros(cap, jnp.int32),
                )
            )
        else:
            cols.append(Column(f.dtype, jnp.zeros(cap, f.dtype.np_dtype), jnp.zeros(cap, jnp.bool_)))
    return cols


def cached_joiner(
    probe_schema: Schema,
    build_schema: Schema,
    probe_key_exprs: Sequence[Expr],
    build_key_exprs: Sequence[Expr],
    join_type: "JoinType",
    probe_is_left: bool,
    existence_col: str = "exists#0",
) -> "Joiner":
    """Process-wide Joiner cache: a Joiner owns 4 jitted kernels and no
    data, and plans are rebuilt per task — sharing avoids a full XLA
    recompile of build/probe kernels for every task."""
    from ...exprs.compile import expr_key
    from ...runtime.kernel_cache import cached_kernel, schema_key

    key = (
        "joiner", schema_key(probe_schema), schema_key(build_schema),
        tuple(expr_key(e) for e in probe_key_exprs),
        tuple(expr_key(e) for e in build_key_exprs),
        join_type.value, probe_is_left, existence_col,
    )
    return cached_kernel(key, lambda: Joiner(
        probe_schema, build_schema, probe_key_exprs, build_key_exprs,
        join_type, probe_is_left, existence_col,
    ))


class JoinerState:
    """Per-execution mutable state (matched-build flags accumulate
    across probe batches)."""

    def __init__(self):
        self.matched_build = None


class Joiner:
    """Build/probe driver for one join exec instance.  Kernels compile
    once per (schema, capacity) via instance-owned jitted closures.  A
    probe batch searches the key table once, in the candidate program,
    which hands ``lo``/``counts`` to the probe program on the device.
    The probe program's output bucket comes from the largest candidate
    total the map has shown at the batch's capacity, and the host reads
    this batch's total with the count of rows it emits; the first batch
    of a capacity reads its total first, to pick the bucket.  The
    unmatched count follows where the probe side is preserved."""

    def __init__(
        self,
        probe_schema: Schema,
        build_schema: Schema,
        probe_key_exprs: Sequence[Expr],
        build_key_exprs: Sequence[Expr],
        join_type: JoinType,
        probe_is_left: bool,
        existence_col: str = "exists#0",
    ):
        self.probe_schema = probe_schema
        self.build_schema = build_schema
        self.probe_keys = list(probe_key_exprs)
        self.build_keys = list(build_key_exprs)
        self.join_type = join_type
        self.probe_is_left = probe_is_left
        self.existence_col = existence_col

        jt = join_type
        build_outer = (
            jt == JoinType.FULL
            or (jt == JoinType.RIGHT and probe_is_left)
            or (jt == JoinType.LEFT and not probe_is_left)
        )
        self._build_outer = build_outer
        self._need_matched = build_outer or jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI)
        self._probe_outer = (
            jt == JoinType.FULL
            or (jt == JoinType.LEFT and probe_is_left)
            or (jt == JoinType.RIGHT and not probe_is_left)
        )

        if jt == JoinType.EXISTENCE:
            self.out_schema = Schema(
                list(probe_schema.fields) + [Field(existence_col, DataType.bool_())]
            )
        elif jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            self.out_schema = probe_schema
        elif jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            self.out_schema = build_schema
        else:
            left = probe_schema if probe_is_left else build_schema
            right = build_schema if probe_is_left else probe_schema
            self.out_schema = Schema(list(left.fields) + list(right.fields))

        build_keys = self.build_keys
        probe_keys = self.probe_keys

        self._build_kernel = make_build_kernel(build_schema, build_keys)

        @jax.jit
        def candidate_kernel(cols, jmap_keys, run_lens, offsets, max_bucket, num_rows):
            cap = cols[0].validity.shape[0]
            env = {f.name: c for f, c in zip(probe_schema.fields, cols)}
            key_cols = [lower(e, probe_schema, env, cap) for e in probe_keys]
            live = jnp.arange(cap) < num_rows
            pkeys = jnp.where(live, _key_hash(key_cols), _SENTINEL)
            lo, counts, steps = probe_counts(jmap_keys, run_lens, offsets, max_bucket, pkeys)
            # the candidate total and the search's steps, one read
            return jnp.stack([jnp.sum(counts), steps]), lo, counts

        # under the dispatch counters like every cached kernel: the
        # Joiner object is what cached_kernel holds, so its jitted
        # closures are wrapped here
        self._candidate_kernel = dispatch.instrument(candidate_kernel, "join_candidate")

        from functools import partial

        @partial(jax.jit, static_argnames=("out_cap",))
        def probe_kernel(probe_cols, jmap: JoinMap, lo, counts, out_cap: int):
            # lo/counts are the candidate program's, still on the
            # device: the key table is not searched again here, and the
            # key columns are lowered for the exact verification only
            cap = probe_cols[0].validity.shape[0]
            env = {f.name: c for f, c in zip(probe_schema.fields, probe_cols)}
            probe_key_cols = [lower(e, probe_schema, env, cap) for e in probe_keys]
            p_idx, b_pos, pair_live = expand_pairs(lo, counts, out_cap)
            b_idx = jnp.take(jmap.sorted_rows, jnp.clip(b_pos, 0, jmap.sorted_rows.shape[0] - 1))

            benv = {f.name: c for f, c in zip(jmap.batch.schema.fields, jmap.batch.columns)}
            bcap = jmap.batch.capacity
            build_key_cols = [lower(e, build_schema, benv, bcap) for e in build_keys]
            keep = pair_live
            for pk, bk in zip(probe_key_cols, build_key_cols):
                keep = keep & _eq_col(pk.take(p_idx), bk.take(b_idx))

            vcounts = jax.ops.segment_sum(
                keep.astype(jnp.int32), p_idx, num_segments=cap, indices_are_sorted=True
            )
            matched_build = jnp.zeros(bcap, jnp.bool_).at[b_idx].max(keep)

            probe_g = tuple(c.take(p_idx) for c in probe_cols)
            build_g = tuple(c.take(b_idx) for c in jmap.batch.columns)
            all_cols, pair_count = compact_columns(probe_g + build_g, keep)
            return all_cols, pair_count, vcounts, matched_build

        self._probe_kernel = dispatch.instrument(probe_kernel, "join_probe")

        @jax.jit
        def compact_kernel(cols, keep):
            return compact_columns(cols, keep)

        self._compact_kernel = dispatch.instrument(compact_kernel, "join_compact")

    # ------------------------------------------------------------ build

    def build_map(self, batch: RecordBatch) -> JoinMap:
        with trace.annotation("join_build"):
            return build_join_map(batch, self._build_kernel)

    # ------------------------------------------------------------ probe

    def probe_batch(
        self, jmap: JoinMap, batch: RecordBatch, state: JoinerState
    ) -> Optional[RecordBatch]:
        """One probe batch against the map, under the ``join_probe``
        span (its ``device_read`` round trips nest inside); the rows in
        and out are host-known already, so counting them reads nothing
        more from the device."""
        with trace.span("join_probe"):
            out = self._probe_batch(jmap, batch, state)
        dispatch.record("join_probe_rows_in", batch.num_rows)
        dispatch.record("join_rows_out", out.num_rows if out is not None else 0)
        return out

    def _probe_batch(
        self, jmap: JoinMap, batch: RecordBatch, state: JoinerState
    ) -> Optional[RecordBatch]:
        jt = self.join_type
        head, lo, counts = self._candidate_kernel(
            tuple(batch.columns), jmap.sorted_keys, jmap.run_lens,
            jmap.bucket_offsets, jmap.max_bucket, batch.num_rows)
        peak = jmap.candidate_peak(batch.capacity)
        if peak is None:
            with trace.span("device_read"):  # the round trip that picks out_cap
                cand, steps = np.asarray(head).tolist()
            out_cols, count, vcounts, matched = self._launch_probe(
                jmap, batch, lo, counts, bucket_capacity(max(1, cand)))
            n = None if count is None else trace.read_scalar(count)
        else:
            # the bucket this map needed at this capacity before: the
            # probe program queues behind the candidate program, and the
            # candidate total rides the read of what the probe emits
            dispatch.record("join_outcap_predicted")
            out_cap = bucket_capacity(max(1, peak))
            out_cols, count, vcounts, matched = self._launch_probe(
                jmap, batch, lo, counts, out_cap)
            with trace.span("device_read"):
                got_head, got = jax.device_get((head, count))
            cand, steps = got_head.tolist()
            n = None if got is None else int(got)
            if cand > out_cap:
                # the pairs were cut at out_cap: nothing of that launch
                # is used; the search's lo/counts serve the re-launch
                dispatch.record("join_outcap_redo")
                out_cols, count, vcounts, matched = self._launch_probe(
                    jmap, batch, lo, counts, bucket_capacity(cand))
                n = None if count is None else trace.read_scalar(count)
        dispatch.record("join_search_steps", steps)
        jmap.raise_candidate_peak(batch.capacity, cand)
        if self._need_matched:
            state.matched_build = (
                matched if state.matched_build is None else (state.matched_build | matched)
            )

        if jt == JoinType.EXISTENCE:
            has = vcounts > 0
            cols = list(batch.columns) + [Column(DataType.bool_(), has, jnp.ones_like(has))]
            return RecordBatch(self.out_schema, cols, batch.num_rows)
        if jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            return None  # emitted from build side at finish
        if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            return RecordBatch(self.out_schema, list(out_cols), n) if n else None

        parts: List[RecordBatch] = []
        if n:
            np_ = len(batch.columns)
            probe_side = list(out_cols[:np_])
            build_side = list(out_cols[np_:])
            cols = probe_side + build_side if self.probe_is_left else build_side + probe_side
            parts.append(RecordBatch(self.out_schema, cols, n))
        if self._probe_outer:
            live = jnp.arange(batch.capacity) < batch.num_rows
            un_cols, un_count = self._compact_kernel(tuple(batch.columns), (vcounts == 0) & live)
            un = trace.read_scalar(un_count)
            if un:
                nulls = _null_columns(self.build_schema, batch.capacity)
                cols = (list(un_cols) + nulls) if self.probe_is_left else (nulls + list(un_cols))
                parts.append(RecordBatch(self.out_schema, cols, un))
        if not parts:
            return None
        return parts[0] if len(parts) == 1 else concat_batches(parts)

    def _launch_probe(self, jmap: JoinMap, batch: RecordBatch, lo, counts, out_cap: int):
        """The probe program at ``out_cap`` and, for a left semi/anti
        join, the compaction it feeds: (the columns emitted, the device
        count of their rows — None where the join reads none —, the
        per-probe-row match counts, the build rows matched)."""
        jt = self.join_type
        out_cols, count, vcounts, matched = self._probe_kernel(
            tuple(batch.columns), jmap, lo, counts, out_cap)
        if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            has = vcounts > 0
            live = jnp.arange(batch.capacity) < batch.num_rows
            want = has if jt == JoinType.LEFT_SEMI else ~has
            out_cols, count = self._compact_kernel(tuple(batch.columns), want & live)
        elif jt in (JoinType.EXISTENCE, JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            count = None
        return out_cols, count, vcounts, matched

    def finish(self, jmap: JoinMap, state: JoinerState) -> Optional[RecordBatch]:
        """Emit build-side rows for right/full outer and build-side
        semi/anti (probe side exhausted)."""
        jt = self.join_type
        if not (self._build_outer or jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI)):
            return None
        matched = state.matched_build
        if matched is None:
            matched = jnp.zeros(jmap.batch.capacity, jnp.bool_)
        live = jnp.arange(jmap.batch.capacity) < jmap.num_rows
        if jt == JoinType.RIGHT_SEMI:
            want = matched & live
        else:  # RIGHT_ANTI or build-preserved outer
            want = ~matched & live
        out_cols, count = self._compact_kernel(tuple(jmap.batch.columns), want)
        n = trace.read_scalar(count)
        if not n:
            return None
        if jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            return RecordBatch(self.out_schema, list(out_cols), n)
        nulls = _null_columns(self.probe_schema, jmap.batch.capacity)
        cols = (nulls + list(out_cols)) if self.probe_is_left else (list(out_cols) + nulls)
        return RecordBatch(self.out_schema, cols, n)
