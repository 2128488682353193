"""The Joiner's candidate search: one left binary search of the sorted
key table per probe batch, in the candidate program alone, begun inside
the bucket of the probe key's hash prefix; the bucket offsets and a
range's length (the key's run length) are what the build kernel kept in
the JoinMap.  The probe's output bucket comes from the largest candidate
total the map has shown at the batch's capacity, checked in the read of
what the probe emits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
from blaze_tpu.exprs import col
from blaze_tpu.ops.joins.core import (
    _SENTINEL,
    Joiner,
    JoinerState,
    JoinMap,
    JoinType,
    bucket_offsets,
    build_join_map,
    expand_pairs,
    make_build_kernel,
    probe_counts,
    run_lengths,
)
from blaze_tpu.runtime import dispatch
from blaze_tpu.schema import DataType, Field, Schema

SENT = np.uint64(_SENTINEL)


def _np_run_lens(sorted_keys: np.ndarray) -> np.ndarray:
    """Loop reference: positions j >= i holding sorted_keys[i]."""
    out = np.empty(sorted_keys.shape[0], np.int32)
    run = 0
    for i in range(sorted_keys.shape[0] - 1, -1, -1):
        same = i + 1 < sorted_keys.shape[0] and sorted_keys[i + 1] == sorted_keys[i]
        run = run + 1 if same else 1
        out[i] = run
    return out


def _table(rng, cap: int, live: int, distinct: int) -> np.ndarray:
    """Sorted uint64 table: ``live`` keys drawn from ``distinct``
    values (duplicate runs), the rest the sentinel tail."""
    pool = rng.integers(1, 2**63, size=max(distinct, 1), dtype=np.uint64)
    keys = np.full(cap, SENT, np.uint64)
    keys[:live] = rng.choice(pool, size=live)
    return np.sort(keys)


def _probes(rng, table: np.ndarray, n: int) -> np.ndarray:
    """Present keys, absent keys, sentinel probes, keys under the
    smallest and above the largest non-sentinel key."""
    live = table[table != SENT]
    present = rng.choice(live, size=n) if live.size else np.zeros(n, np.uint64)
    absent = rng.integers(1, 2**63, size=n, dtype=np.uint64)
    p = np.where(rng.random(n) < 0.5, present, absent)
    p[rng.random(n) < 0.1] = SENT
    p[0], p[1], p[2] = np.uint64(0), SENT - np.uint64(1), SENT
    return p


TABLES = {
    "duplicates_sentinel_tail": dict(live_share=0.7, distinct_share=0.2),
    "unique_keys": dict(live_share=0.9, distinct_share=4.0),
    "one_long_run": dict(live_share=0.5, distinct_share=0.0),
    "full_no_tail": dict(live_share=1.0, distinct_share=0.3),
    "all_sentinel": dict(live_share=0.0, distinct_share=0.0),
    "one_live_row": dict(live_share=0.0, distinct_share=0.0, live=1),
    # a table of a fixed size whatever the cap: alone under the small
    # cap (no tail, not a power of two), in its bucket under the large
    "over_4096": dict(live=4097, distinct_share=0.2),
    # q7's filtered customer_demographics: unique keys, NULL probes
    "q7_hot_map": dict(live=27_440, distinct_share=4.0),
}


def _search(table: np.ndarray, probes: np.ndarray):
    """``probe_counts`` through the table's own bucket offsets."""
    keys = jnp.asarray(table)
    offsets, max_bucket = bucket_offsets(keys)
    return probe_counts(keys, run_lengths(keys), offsets, max_bucket, jnp.asarray(probes))


def _assert_two_sided(table: np.ndarray, probes: np.ndarray):
    """``lo``/``counts`` are numpy's left search and right - left."""
    lo, counts, _ = _search(table, probes)
    want_lo = np.searchsorted(table, probes, side="left")
    want = np.searchsorted(table, probes, side="right") - want_lo
    want[probes == SENT] = 0
    np.testing.assert_array_equal(np.asarray(lo), want_lo)
    np.testing.assert_array_equal(np.asarray(counts), want)
    assert lo.dtype == jnp.int32 and counts.dtype == jnp.int32
    return want


@pytest.mark.parametrize("cap", [1024, 32768])
@pytest.mark.parametrize("shape", sorted(TABLES))
def test_probe_counts_equals_the_two_sided_search(cap, shape):
    spec = TABLES[shape]
    rng = np.random.default_rng(cap + len(shape))
    live = spec.get("live", int(cap * spec.get("live_share", 0)))
    cap = max(cap, live)
    table = _table(rng, cap, live, int(live * spec["distinct_share"]))
    want = _assert_two_sided(table, _probes(rng, table, 4096))
    if live:
        assert want.max() >= 1  # the case does find something


def _sorted_with_tail(live_keys, cap: int) -> np.ndarray:
    table = np.full(cap, SENT, np.uint64)
    table[: len(live_keys)] = np.sort(np.asarray(live_keys, np.uint64))
    return table


def _crowded_bucket(rng, cap):
    """300 distinct keys (some twice) under ONE top-b-bit prefix, a few
    uniform keys around them."""
    low = rng.choice(2**20, size=300, replace=False).astype(np.uint64)
    crowd = (np.uint64(0x5A5A) << np.uint64(48)) | low
    others = rng.integers(1, 2**63, size=200, dtype=np.uint64)
    table = _sorted_with_tail(np.concatenate([crowd, crowd[:40], others]), cap)
    probes = np.concatenate([crowd, crowd + np.uint64(1), others,
                             [crowd.min() - np.uint64(1), crowd.max() + np.uint64(1)]])
    return table, probes


def _all_ones_prefix(rng, cap):
    """Live keys whose prefix is all ones, right under the sentinel
    tail: the last bucket ends at the live keys, not at the capacity."""
    top = SENT - np.arange(1, 40, dtype=np.uint64) * np.uint64(3)
    others = rng.integers(1, 2**63, size=cap // 2, dtype=np.uint64)
    table = _sorted_with_tail(np.concatenate([top, top[:5], others]), cap)
    probes = np.concatenate([top, top + np.uint64(1), top - np.uint64(1),
                             [SENT, SENT - np.uint64(1)], others[:100]])
    return table, probes


def _empty_buckets_around(rng, cap):
    """Three far-apart clusters: every probe's prefix has empty buckets
    on both sides, and most probes fall into an empty bucket."""
    bases = np.array([1 << 40, 1 << 62, (1 << 63) + (1 << 61)], np.uint64)
    live = (bases[:, None] + np.arange(0, 50, dtype=np.uint64)[None, :] * np.uint64(7)).ravel()
    table = _sorted_with_tail(live, cap)
    absent = rng.integers(1, 2**63, size=500, dtype=np.uint64) * np.uint64(2)
    probes = np.concatenate([live, live + np.uint64(1), absent, [np.uint64(0)]])
    return table, probes


def _dead_rows_are_sentinels(rng, cap):
    """A probe batch as the candidate program sees it: 300 live rows,
    the dead rows behind them all sentinel probes."""
    table = _table(rng, cap, int(cap * 0.8), cap)
    probes = np.full(4096, SENT, np.uint64)
    probes[:300] = rng.choice(table[table != SENT], size=300)
    return table, probes


BUCKET_CASES = {
    "crowded_bucket": _crowded_bucket,
    "all_ones_prefix_beside_the_tail": _all_ones_prefix,
    "empty_buckets_around": _empty_buckets_around,
    "dead_rows_are_sentinel_probes": _dead_rows_are_sentinels,
}


@pytest.mark.parametrize("cap", [1024, 32768])
@pytest.mark.parametrize("case", sorted(BUCKET_CASES))
def test_bucketed_search_equals_the_two_sided_search(cap, case):
    table, probes = BUCKET_CASES[case](np.random.default_rng(cap + len(case)), cap)
    assert (table[1:] >= table[:-1]).all() and table[-1] == SENT
    want = _assert_two_sided(table, probes.astype(np.uint64))
    assert want.max() >= 1


def test_search_at_min_capacity():
    """The smallest table the engine builds: ``cap`` at MIN_CAPACITY."""
    from blaze_tpu import conf

    cap = int(conf.MIN_CAPACITY.get())
    rng = np.random.default_rng(cap)
    for live, distinct in ((cap, cap), (cap // 2, 5), (3, 3)):
        table = _table(rng, cap, live, distinct)
        _assert_two_sided(table, _probes(rng, table, cap))


@pytest.mark.parametrize("cap", [1024, 32768])
def test_run_lengths_against_a_loop(cap):
    rng = np.random.default_rng(cap)
    for live, distinct in ((cap, 7), (cap // 3, cap), (0, 0), (1, 1)):
        table = _table(rng, cap, live, distinct)
        np.testing.assert_array_equal(
            np.asarray(run_lengths(jnp.asarray(table))), _np_run_lens(table))


def _np_offsets(sorted_keys: np.ndarray, b: int):
    """Loop reference: where each b-bit prefix starts among the live
    keys, then the live keys' count; the largest bucket."""
    live = [int(k) for k in sorted_keys if k != SENT]
    offsets, at = [], 0
    for p in range(1 << b):
        while at < len(live) and (live[at] >> (64 - b)) < p:
            at += 1
        offsets.append(at)
    offsets.append(len(live))
    return np.array(offsets, np.int32), max(np.diff(offsets))


@pytest.mark.parametrize("cap", [1024, 8192])
def test_bucket_offsets_against_a_loop(cap):
    rng = np.random.default_rng(cap)
    for live, distinct in ((cap, cap), (cap // 3, 7), (0, 0), (1, 1)):
        table = _table(rng, cap, live, distinct)
        offsets, max_bucket = bucket_offsets(jnp.asarray(table))
        b = (offsets.shape[0] - 1).bit_length() - 1
        assert offsets.shape[0] == (1 << b) + 1 and (1 << b) >= cap
        want, want_max = _np_offsets(table, b)
        np.testing.assert_array_equal(np.asarray(offsets), want)
        assert int(max_bucket) == want_max
        assert offsets.dtype == jnp.int32 and max_bucket.dtype == jnp.int32
    crowd, _ = _crowded_bucket(rng, cap)
    offsets, max_bucket = bucket_offsets(jnp.asarray(crowd))
    want, want_max = _np_offsets(crowd, (offsets.shape[0] - 1).bit_length() - 1)
    np.testing.assert_array_equal(np.asarray(offsets), want)
    assert int(max_bucket) == want_max >= 340  # the crowd, its 40 repeats


STEP_CAP = 32768
OLD_STEPS = 16  # the whole-table search this one replaced: log2(cap) + 1


@pytest.mark.parametrize("shape,live,distinct,want", [
    # uniform hashes: the largest bucket's bit length, a handful
    ("uniform", STEP_CAP, 4 * STEP_CAP, None),
    # half the table one run of one key: one step under the old search
    ("one_long_run", STEP_CAP // 2, 1, OLD_STEPS - 1),
    # the whole table one run: the old search's steps, never more
    ("one_run_fills_the_table", STEP_CAP, 1, OLD_STEPS),
    ("all_sentinel", 0, 0, 0),
])
def test_search_steps_follow_the_largest_bucket(shape, live, distinct, want):
    rng = np.random.default_rng(len(shape))
    table = _table(rng, STEP_CAP, live, distinct)
    _, _, steps = _search(table, _probes(rng, table, 4096))
    largest = int(bucket_offsets(jnp.asarray(table))[1])
    assert int(steps) == largest.bit_length()
    if want is None:
        assert int(steps) <= min(int(np.ceil(np.log2(largest + 1))) + 1, 6)
    else:
        assert int(steps) == want


BUILD = Schema([Field("k", DataType.int64()), Field("b", DataType.int32())])
PROBE = Schema([Field("k", DataType.int64()), Field("p", DataType.int32())])


def test_build_kernel_returns_the_tables_run_lengths_and_bucket_offsets():
    rng = np.random.default_rng(31)
    n = 700  # capacity 1,024: a sentinel tail of dead rows behind the NULL keys
    keys = [None if rng.random() < 0.1 else int(k) for k in rng.integers(0, 40, n)]
    batch = batch_from_pydict({"k": keys, "b": list(range(n))}, BUILD)
    jmap = build_join_map(batch, make_build_kernel(BUILD, [col("k")]))

    sk = np.asarray(jmap.sorted_keys)
    assert sk.shape[0] > n and (sk[1:] >= sk[:-1]).all()
    np.testing.assert_array_equal(np.asarray(jmap.run_lens), _np_run_lens(sk))
    # NULL keys and dead rows share the sentinel run at the end
    dead = sk.shape[0] - n + sum(k is None for k in keys)
    assert np.asarray(jmap.run_lens)[sk.shape[0] - dead] == dead
    # the offsets are the sorted table's, and the tail lies in no bucket
    offsets, max_bucket = bucket_offsets(jmap.sorted_keys)
    np.testing.assert_array_equal(np.asarray(jmap.bucket_offsets), np.asarray(offsets))
    assert int(jmap.max_bucket) == int(max_bucket) >= 1
    assert int(jmap.bucket_offsets[-1]) == sk.shape[0] - dead


def _loops(jaxpr) -> int:
    """``scan``/``while`` equations of a jaxpr, nested jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in ("scan", "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _loops(sub)
    return n


def test_the_key_table_is_searched_once_a_probe_batch():
    """candidate_kernel holds the one search; probe_kernel holds only
    expand_pairs' search over the output slots."""
    j = Joiner(PROBE, BUILD, [col("k")], [col("k")], JoinType.INNER, True)
    build = batch_from_pydict({"k": [2, 4, 4], "b": [1, 2, 3]}, BUILD)
    probe = batch_from_pydict({"k": [1, 2, 3, 4], "p": [10, 20, 30, 40]}, PROBE)
    jmap = j.build_map(build)
    cols = tuple(probe.columns)

    candidate = j._candidate_kernel.__wrapped__
    index = (jmap.sorted_keys, jmap.run_lens, jmap.bucket_offsets, jmap.max_bucket)
    cand = jax.make_jaxpr(candidate)(cols, *index, probe.num_rows)
    assert _loops(cand.jaxpr) == 1

    (total, steps), lo, counts = candidate(cols, *index, probe.num_rows)
    assert int(total) == 3
    assert int(steps) == 2  # the largest bucket holds key 4 twice
    expand = jax.make_jaxpr(lambda a, b: expand_pairs(a, b, 1024))(lo, counts)
    assert _loops(expand.jaxpr) == 1
    probe_jaxpr = jax.make_jaxpr(
        lambda c, m, a, b: j._probe_kernel.__wrapped__(c, m, a, b, out_cap=1024)
    )(cols, jmap, lo, counts)
    assert _loops(probe_jaxpr.jaxpr) == 1


# ------------------------------------------ the output bucket from the map

PROBE_P = Schema([Field("pk", DataType.int64()), Field("p", DataType.int32())])
BUILD_B = Schema([Field("bk", DataType.int64()), Field("b", DataType.int32())])

#: join type (the probe side is the left) -> (reads of a map's first
#: batch at a capacity, reads of a later one): the candidate total, then
#: what the probe emits, then the unmatched rows where the probe side is
#: kept; a later batch reads the total with what the probe emits.
#: EXISTENCE reads the total alone either way, before or after the probe
PREDICTED_READS = {
    "inner": (JoinType.INNER, 2, 1),
    "left_probe_preserved": (JoinType.LEFT, 3, 2),
    "right_build_preserved": (JoinType.RIGHT, 2, 1),
    "full": (JoinType.FULL, 3, 2),
    "left_semi": (JoinType.LEFT_SEMI, 2, 1),
    "left_anti": (JoinType.LEFT_ANTI, 2, 1),
    "existence": (JoinType.EXISTENCE, 1, 1),
}


def _run_map_build():
    """Key 7 a run of 600 rows, keys 10..109 once, NULL keys beside."""
    keys = [7] * 600 + list(range(10, 110)) + [None] * 9
    return batch_from_pydict({"bk": keys, "b": list(range(len(keys)))}, BUILD_B)


def _probe_batch(keys):
    return batch_from_pydict({"pk": keys, "p": list(range(len(keys)))}, PROBE_P)


def _rows(out):
    if out is None:
        return []
    d = batch_to_pydict(out)
    return list(zip(*d.values()))


def _probe_counted(j, jmap, batch, state):
    with dispatch.capture() as c:
        out = j.probe_batch(jmap, batch, state)
    return _rows(out), c


def _two_read_rows(j, build, batches):
    """Each batch probed on a fresh map (the two-read path), one state
    across them, then what ``finish`` emits."""
    state, rows = JoinerState(), []
    for b in batches:
        out, c = _probe_counted(j, j.build_map(build), b, state)
        assert c.get("join_outcap_predicted", 0) == 0
        rows.append(out)
    return rows, _rows(j.finish(j.build_map(build), state))


@pytest.mark.parametrize("case", sorted(PREDICTED_READS))
def test_a_later_batch_reads_the_candidate_total_with_the_probes_count(case):
    jt, first_reads, later_reads = PREDICTED_READS[case]
    j = Joiner(PROBE_P, BUILD_B, [col("pk")], [col("bk")], jt, True)
    build = _run_map_build()
    # 603 and 602 candidates: both inside the first batch's bucket
    batches = [_probe_batch([7, 11, 12, None, 60]), _probe_batch([7, 13, None, 99, 300])]
    jmap, state = j.build_map(build), JoinerState()
    (rows1, c1), (rows2, c2) = (_probe_counted(j, jmap, b, state) for b in batches)
    assert c1["device_read_n"] == first_reads and c1.get("join_outcap_predicted", 0) == 0
    assert c2["device_read_n"] == later_reads
    assert (c2["join_outcap_predicted"], c2.get("join_outcap_redo", 0)) == (1, 0)
    assert c2["join_search_steps"] == c1["join_search_steps"] > 0
    assert jmap.candidate_peak(batches[0].capacity) == 603
    want, want_tail = _two_read_rows(j, build, batches)
    assert [rows1, rows2] == want
    assert _rows(j.finish(jmap, state)) == want_tail
    if case == "inner":
        assert len(rows2) == 602  # 600 pairs of key 7, keys 13 and 99 once


@pytest.mark.parametrize("case", sorted(PREDICTED_READS))
def test_a_batch_that_outgrows_the_bucket_is_probed_again(case):
    """The second batch's 1,801 candidates outgrow the first batch's
    1,024-row bucket: the cut launch is dropped, the probe re-launched
    at 2,048 from the same search, and the rows are the two-read path's
    — NULL probe keys and a run of 600 build rows among them."""
    jt, first_reads, _ = PREDICTED_READS[case]
    j = Joiner(PROBE_P, BUILD_B, [col("pk")], [col("bk")], jt, True)
    build = _run_map_build()
    batches = [_probe_batch([7, 11, 12, None, 60]), _probe_batch([7, 7, None, 13, 7, 200])]
    jmap, state = j.build_map(build), JoinerState()
    _probe_counted(j, jmap, batches[0], state)
    rows2, c2 = _probe_counted(j, jmap, batches[1], state)
    assert (c2["join_outcap_predicted"], c2["join_outcap_redo"]) == (1, 1)
    # the total rides the first read, the re-launch's count is read alone
    assert c2["device_read_n"] == first_reads
    assert c2["join_search_steps"] > 0
    assert jmap.candidate_peak(batches[1].capacity) == 1801
    want, want_tail = _two_read_rows(j, build, batches)
    assert rows2 == want[1]
    assert _rows(j.finish(jmap, state)) == want_tail
    if case == "inner":
        assert len(rows2) == 1801
    # the peak only rises: a smaller batch after it takes the wider bucket
    _, c3 = _probe_counted(j, jmap, batches[0], JoinerState())
    assert (c3["join_outcap_predicted"], c3.get("join_outcap_redo", 0)) == (1, 0)
    assert jmap.candidate_peak(batches[0].capacity) == 1801


def test_the_candidate_peaks_are_not_serialized():
    j = Joiner(PROBE_P, BUILD_B, [col("pk")], [col("bk")], JoinType.INNER, True)
    jmap = j.build_map(_run_map_build())
    batch = _probe_batch([7, 11, 12, None, 60])
    _probe_counted(j, jmap, batch, JoinerState())
    assert jmap.candidate_peak(batch.capacity) == 603
    back = JoinMap.deserialize(jmap.serialize(), BUILD_B)
    assert back.candidate_peak(batch.capacity) is None
    # nor a pytree leaf: a map rebuilt from its leaves starts empty too
    leaves, tree = jax.tree_util.tree_flatten(jmap)
    assert jax.tree_util.tree_unflatten(tree, leaves).candidate_peak(batch.capacity) is None
    rows, c = _probe_counted(j, back, batch, JoinerState())
    assert c.get("join_outcap_predicted", 0) == 0 and c["device_read_n"] == 2
    assert rows == _probe_counted(j, jmap, batch, JoinerState())[0]
