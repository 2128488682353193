"""Serializable broadcast JoinHashMap.

≙ reference joins/join_hash_map.rs:290-454 (raw-bytes map serde),
broadcast_join_build_hash_map_exec.rs:41, and the per-executor cache
keyed by broadcast id (broadcast_join_exec.rs:456-560): the MAP is what
crosses the broadcast, probe executors rebuild it with buffer copies
only, and re-instantiated plans hit the executor-wide cache.
"""

import jax
import numpy as np
import pytest

from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
from blaze_tpu.exprs import col
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.ops.joins import (
    BroadcastJoinBuildHashMapExec,
    BroadcastJoinExec,
    JoinMap,
    JoinType,
    clear_join_map_cache,
)
from blaze_tpu.ops.joins.core import build_join_map, make_build_kernel
from blaze_tpu.parallel.broadcast import BroadcastExchangeExec
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema

BUILD_SCHEMA = Schema([Field("k", DataType.int64()), Field("b", DataType.string(8))])
PROBE_SCHEMA = Schema([Field("k", DataType.int64()), Field("p", DataType.int32())])

BUILD_DATA = {"k": [1, 2, 2, None, 5], "b": ["x", "y", "yy", "n", "z"]}
PROBE_DATA = {"k": [2, 1, 7, None, 5, 2], "p": [10, 20, 30, 40, 50, 60]}


def _build_exec():
    return MemoryScanExec([[batch_from_pydict(BUILD_DATA, BUILD_SCHEMA)]], BUILD_SCHEMA)


def _probe_exec():
    return MemoryScanExec([[batch_from_pydict(PROBE_DATA, PROBE_SCHEMA)]], PROBE_SCHEMA)


def _run(join: BroadcastJoinExec):
    rows = []
    for p in range(join.num_partitions()):
        for b in join.execute(p, TaskContext(p, join.num_partitions())):
            d = batch_to_pydict(b)
            rows += list(zip(*[d[f.name] for f in join.schema.fields]))
    return sorted(rows, key=repr)


def _map_build_side():
    """BroadcastExchange(BuildHashMap(build)) — the serialized map rides
    the normal broadcast IPC path as a one-row binary batch."""
    return BroadcastExchangeExec(BroadcastJoinBuildHashMapExec(_build_exec(), [col("k")]))


@pytest.mark.parametrize(
    "jt", [JoinType.INNER, JoinType.LEFT, JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
           JoinType.EXISTENCE, JoinType.RIGHT, JoinType.FULL, JoinType.RIGHT_SEMI,
           JoinType.RIGHT_ANTI]
)
def test_map_mode_matches_legacy(jt):
    clear_join_map_cache()
    legacy = BroadcastJoinExec(
        _build_exec(), _probe_exec(), [col("k")], [col("k")], jt, build_is_left=False
    )
    mapped = BroadcastJoinExec(
        _map_build_side(), _probe_exec(), [col("k")], [col("k")], jt,
        build_is_left=False, cached_build_id="bc_test_1",
    )
    assert _run(mapped) == _run(legacy)


def test_serialize_deserialize_roundtrip():
    kern = make_build_kernel(BUILD_SCHEMA, [col("k")])
    jmap = build_join_map(batch_from_pydict(BUILD_DATA, BUILD_SCHEMA), kern)
    rt = JoinMap.deserialize(jmap.serialize(), BUILD_SCHEMA)
    assert rt.num_rows == jmap.num_rows
    np.testing.assert_array_equal(np.asarray(rt.sorted_keys), np.asarray(jmap.sorted_keys))
    np.testing.assert_array_equal(np.asarray(rt.sorted_rows), np.asarray(jmap.sorted_rows))
    for field in ("run_lens", "bucket_offsets", "max_bucket"):
        got, built = getattr(rt, field), getattr(jmap, field)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(built))
        assert (got.dtype, got.shape) == (built.dtype, built.shape), field
    assert batch_to_pydict(rt.batch) == batch_to_pydict(jmap.batch)


def test_serialized_map_carries_its_run_lengths():
    """The wire form holds run_lens as built (keys 2,2 one run, the
    NULL key and the dead rows the sentinel run): the reading side
    copies buffers, it computes nothing."""
    from blaze_tpu.ops.joins.core import run_lengths

    kern = make_build_kernel(BUILD_SCHEMA, [col("k")])
    jmap = build_join_map(batch_from_pydict(BUILD_DATA, BUILD_SCHEMA), kern)
    cap = jmap.sorted_keys.shape[0]
    rt = JoinMap.deserialize(jmap.serialize(), BUILD_SCHEMA)
    got = np.asarray(rt.run_lens)
    np.testing.assert_array_equal(got, np.asarray(run_lengths(rt.sorted_keys)))
    assert sorted(got[:4].tolist()) == [1, 1, 1, 2]  # 1, 2, 2, 5 in hash order
    assert got[4] == cap - 4 and got[-1] == 1
    leaves = jax.tree_util.tree_leaves(rt)
    assert any(leaf is rt.run_lens for leaf in leaves)


def test_serialized_map_carries_its_bucket_offsets():
    """The wire form holds the bucket offsets and the largest bucket as
    built (4 live keys, 2 of them one key; the NULL key and the dead
    rows in no bucket), as pytree leaves: the reading side computes
    nothing, and the candidate program takes them like the built map's."""
    from blaze_tpu.ops.joins.core import bucket_offsets

    kern = make_build_kernel(BUILD_SCHEMA, [col("k")])
    jmap = build_join_map(batch_from_pydict(BUILD_DATA, BUILD_SCHEMA), kern)
    rt = JoinMap.deserialize(jmap.serialize(), BUILD_SCHEMA)
    offsets, max_bucket = bucket_offsets(rt.sorted_keys)
    np.testing.assert_array_equal(np.asarray(rt.bucket_offsets), np.asarray(offsets))
    got = np.asarray(rt.bucket_offsets)
    assert (got[0], got[-1]) == (0, 4) and (np.diff(got) >= 0).all()
    assert int(rt.max_bucket) == int(max_bucket) == np.diff(got).max() >= 2
    leaves = jax.tree_util.tree_leaves(rt)
    assert any(leaf is rt.bucket_offsets for leaf in leaves)
    assert any(leaf is rt.max_bucket for leaf in leaves)


@pytest.mark.parametrize("jt", [JoinType.INNER, JoinType.LEFT, JoinType.FULL])
def test_probe_through_a_deserialized_map_equals_the_built_one(jt):
    from blaze_tpu.ops.joins.core import Joiner, JoinerState
    from blaze_tpu.runtime import dispatch

    j = Joiner(PROBE_SCHEMA, BUILD_SCHEMA, [col("k")], [col("k")], jt, True)
    jmap = j.build_map(batch_from_pydict(BUILD_DATA, BUILD_SCHEMA))
    rt = JoinMap.deserialize(jmap.serialize(), BUILD_SCHEMA)
    probe = batch_from_pydict(PROBE_DATA, PROBE_SCHEMA)

    def rows(m):
        state = JoinerState()
        parts = [j.probe_batch(m, probe, state), j.finish(m, state)]
        return [batch_to_pydict(b) for b in parts if b is not None]

    built = rows(jmap)
    with dispatch.capture() as c:
        assert rows(rt) == built != []
    assert c.get("xla_compiles", 0) == 0  # the copied map runs the built map's programs


def test_per_executor_cache_hit():
    clear_join_map_cache()
    build = _map_build_side()

    def mk():
        return BroadcastJoinExec(
            build, _probe_exec(), [col("k")], [col("k")], JoinType.INNER,
            build_is_left=False, cached_build_id="bc_cache_test",
        )

    first = mk()
    out1 = _run(first)
    # a RE-INSTANTIATED plan (new exec object, e.g. task retry /
    # re-planning) must hit the executor-wide cache, not rebuild
    second = mk()
    out2 = _run(second)
    assert out1 == out2
    assert second.metrics.get("hashmap_cache_hit") >= 1
    assert first.metrics.get("hashmap_cache_hit") == 0


def test_map_mode_proto_roundtrip():
    clear_join_map_cache()
    from blaze_tpu.serde.from_proto import plan_from_proto
    from blaze_tpu.serde.to_proto import plan_to_proto

    mapped = BroadcastJoinExec(
        BroadcastJoinBuildHashMapExec(_build_exec(), [col("k")]),
        _probe_exec(), [col("k")], [col("k")], JoinType.INNER,
        build_is_left=False, cached_build_id="bc_proto_test",
    )
    rt = plan_from_proto(plan_to_proto(mapped))
    assert _run(rt) == _run(mapped)
