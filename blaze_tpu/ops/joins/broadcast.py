"""Broadcast hash join.

≙ reference BroadcastJoinExec (broadcast_join_exec.rs:76-567) +
BroadcastJoinBuildHashMapExec (broadcast_join_build_hash_map_exec.rs:41):
the build side is either raw replicated batches (map built locally) or a
pre-built SERIALIZED JoinMap riding the broadcast IPC path as a one-row
binary batch; probe executors rebuild it with buffer copies only and
cache it per executor keyed by the broadcast id
(≙ get_cached_join_hash_map, broadcast_join_exec.rs:456-560).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

from ...batch import RecordBatch, column_from_strings, concat_batches
from ...exprs.ir import Expr
from ...runtime import dispatch, trace
from ...runtime.context import TaskContext
from ...schema import DataType, Field, Schema
from ..base import BatchStream, ExecNode
from .core import JoinerState, JoinMap, JoinType, build_join_map, cached_joiner, make_build_kernel

MAP_COL = "join_map#bytes"


def _is_map_schema(s: Schema) -> bool:
    return len(s.fields) == 1 and s.fields[0].name == MAP_COL


def _collect_child_batch(child: ExecNode, partitions, ctx: TaskContext) -> RecordBatch:
    """Drain the given partitions of ``child`` into one device batch
    (empty-schema batch when nothing arrives).  Cancellation RAISES —
    a silently truncated build side would be memoized into the payload
    / per-executor map caches and poison every later task."""
    from ...runtime.context import TaskCancelled

    batches: List[RecordBatch] = []
    for p in partitions:
        # the child drives under a DERIVED context: the task's
        # resources view must reach the broadcast reader (an
        # attempt-scoped registration is invisible to the global map)
        # and cancellation must propagate into the drain
        for b in child.execute(p, ctx.child_context(p, child.num_partitions())):
            if not ctx.is_task_running():
                raise TaskCancelled("broadcast build drain cancelled")
            batches.append(b)
    if batches:
        return concat_batches(batches).to_device()
    from ...batch import batch_from_pydict

    return batch_from_pydict({f.name: [] for f in child.schema.fields}, child.schema)


class BroadcastJoinBuildHashMapExec(ExecNode):
    """Drains its child (the broadcast build side), builds the
    serializable JoinMap ONCE, and emits it as a single-row binary
    batch — so the *map*, not the raw rows, is what gets broadcast
    (≙ broadcast_join_build_hash_map_exec.rs:41 + the raw-bytes map
    serde in join_hash_map.rs:290)."""

    def __init__(self, child: ExecNode, keys: Sequence[Expr]):
        super().__init__([child])
        self.keys = list(keys)
        self._build_kernel = make_build_kernel(child.schema, self.keys)
        self._payload: Optional[bytes] = None
        self._lock = threading.Lock()

    @property
    def data_schema(self) -> Schema:
        return self.children[0].schema

    @property
    def schema(self) -> Schema:
        # NOMINAL width: the payload column's true width is chosen per
        # batch at emit time (the serde wire format carries it); nothing
        # may size buffers from this declared dtype
        return Schema([Field(MAP_COL, DataType.binary(8))])

    def num_partitions(self) -> int:
        return 1

    def _build_payload(self, ctx: TaskContext) -> bytes:
        # hold the lock across the build: concurrent first callers must
        # not each drain the child and build the map redundantly
        with self._lock:
            if self._payload is None:
                child = self.children[0]
                data = _collect_child_batch(child, range(child.num_partitions()), ctx)
                with self.metrics.timer("build_hash_map_time", trace.span("broadcast_build")):
                    self._payload = build_join_map(data, self._build_kernel).serialize()
            return self._payload

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        def stream():
            payload = self._build_payload(ctx)
            # chunk the payload over MIN_CAPACITY rows: a one-row batch
            # would be bucket-padded to MIN_CAPACITY rows downstream,
            # inflating a w-byte map to 1024*w; chunked, padding waste
            # is bounded by one row's width
            from ... import conf

            n_rows = int(conf.MIN_CAPACITY.get())
            w = max(8, -(-len(payload) // n_rows))
            chunks = [payload[i * w : (i + 1) * w] for i in range(n_rows)]
            col = column_from_strings(chunks, width=w, capacity=n_rows,
                                      dtype=DataType.binary(w))
            self.metrics.add("output_rows", n_rows)
            yield RecordBatch(self.schema, [col], n_rows)

        return stream()


# per-executor (process-wide) map cache keyed by broadcast id — survives
# plan re-instantiation and task retries within the executor lifetime
# (≙ broadcast_join_exec.rs:456-560 per-executor cache keyed by the
# broadcast's unique id).  Bounded LRU: each entry pins a full
# device-resident build batch, so old broadcasts must age out.
_MAP_CACHE: "OrderedDict[str, JoinMap]" = OrderedDict()
_MAP_CACHE_LOCK = threading.Lock()
_MAP_CACHE_MAX = 8


def _cache_get(key: str) -> Optional[JoinMap]:
    with _MAP_CACHE_LOCK:
        m = _MAP_CACHE.get(key)
        if m is not None:
            _MAP_CACHE.move_to_end(key)
        return m


def _cache_put(key: str, m: JoinMap) -> None:
    with _MAP_CACHE_LOCK:
        _MAP_CACHE[key] = m
        _MAP_CACHE.move_to_end(key)
        while len(_MAP_CACHE) > _MAP_CACHE_MAX:
            _MAP_CACHE.popitem(last=False)


def clear_join_map_cache() -> None:
    with _MAP_CACHE_LOCK:
        _MAP_CACHE.clear()


class BroadcastJoinExec(ExecNode):
    def __init__(
        self,
        build: ExecNode,
        probe: ExecNode,
        build_keys: Sequence[Expr],
        probe_keys: Sequence[Expr],
        join_type: JoinType,
        build_is_left: bool,
        build_data_schema: Optional[Schema] = None,
        cached_build_id: Optional[str] = None,
    ):
        super().__init__([build, probe])
        self.build_keys = list(build_keys)
        self.probe_keys = list(probe_keys)
        self.join_type = join_type
        self.build_is_left = build_is_left
        self._map_mode = _is_map_schema(build.schema)
        if self._map_mode and build_data_schema is None:
            # recover the data schema from a BuildHashMap node in the
            # build subtree (it may sit under a BroadcastExchange)
            node = build
            while node is not None and not isinstance(node, BroadcastJoinBuildHashMapExec):
                node = node.children[0] if node.children else None
            if node is None:
                raise ValueError("map-mode build side requires build_data_schema")
            build_data_schema = node.data_schema
        self.build_data_schema = build_data_schema or build.schema
        self.cached_build_id = cached_build_id
        self._joiner = cached_joiner(
            probe.schema, self.build_data_schema, probe_keys, build_keys, join_type,
            probe_is_left=not build_is_left,
        )
        # per-instance cached map, built once across all probe partitions
        self._cached_map: Optional[JoinMap] = None
        self._map_lock = threading.Lock()

    @property
    def schema(self) -> Schema:
        return self._joiner.out_schema

    def num_partitions(self) -> int:
        return self.children[1].num_partitions()

    def _read_map_payload(self, ctx: TaskContext) -> bytes:
        parts: List[bytes] = []
        for b in self.children[0].execute(0, ctx):
            c = b.columns[0].to_host()
            for i in range(b.num_rows):
                parts.append(bytes(c.data[i, : int(c.lengths[i])]))
        assert parts, "broadcast build produced no join-map payload"
        return b"".join(parts)

    def _get_map(self, ctx: TaskContext) -> JoinMap:
        with self._map_lock:
            if self._cached_map is not None:
                return self._cached_map
        cache_key = None
        if self.cached_build_id is not None:
            # the build schema is part of the key: two joins sharing a
            # broadcast id may have been column-pruned differently
            from ...runtime.kernel_cache import schema_key as _sk

            cache_key = f"{self.cached_build_id}|{hash(_sk(self.build_data_schema))}"
            m = _cache_get(cache_key)
            if m is not None:
                self.metrics.add("hashmap_cache_hit", 1)
                dispatch.record("join_map_cache_hits")
                with self._map_lock:
                    self._cached_map = m
                return m
        # a miss: this task turns the broadcast into the executor's map
        with self.metrics.timer("build_hash_map_time", trace.span("broadcast_build")):
            if self._map_mode:
                # O(1) rebuild: buffer copies only, no re-sort/re-hash
                m = JoinMap.deserialize(self._read_map_payload(ctx), self.build_data_schema)
            else:
                # broadcast child is replicated: read partition 0
                data = _collect_child_batch(self.children[0], [0], ctx)
                m = self._joiner.build_map(data)
        dispatch.record("join_map_builds")
        with self._map_lock:
            self._cached_map = m
        if self.cached_build_id is not None:
            _cache_put(cache_key, m)
        return m

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        def stream():
            jmap = self._get_map(ctx)
            state = JoinerState()
            for batch in self.children[1].execute(partition, ctx):
                if not ctx.is_task_running():
                    return
                with self.metrics.timer("probe_time"):
                    out = self._joiner.probe_batch(jmap, batch, state)
                if out is not None and out.num_rows:
                    self._record_batch(out)
                    yield out
            # build-preserved sides are only correct when this executor
            # sees every probe partition (standalone runs); Spark-mode
            # planning must route such joins to the shuffled-hash path
            if partition == self.num_partitions() - 1 or self.num_partitions() == 1:
                tail = self._joiner.finish(jmap, state)
                if tail is not None:
                    self._record_batch(tail)
                    yield tail

        return stream()
