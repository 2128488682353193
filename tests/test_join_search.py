"""The Joiner's candidate search: one left ``searchsorted`` of the
sorted key table per probe batch, in the candidate program alone; a
range's length is the run length the build kernel kept in the JoinMap.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.batch import batch_from_pydict
from blaze_tpu.exprs import col
from blaze_tpu.ops.joins.core import (
    _SENTINEL,
    JoinType,
    Joiner,
    build_join_map,
    expand_pairs,
    make_build_kernel,
    probe_counts,
    run_lengths,
)
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


@pytest.mark.parametrize("cap", [1024, 32768])
@pytest.mark.parametrize("shape", sorted(TABLES))
def test_probe_counts_equals_the_two_sided_search(cap, shape):
    spec = TABLES[shape]
    rng = np.random.default_rng(cap + len(shape))
    live = spec.get("live", int(cap * spec.get("live_share", 0)))
    cap = max(cap, live)
    table = _table(rng, cap, live, int(live * spec["distinct_share"]))
    probes = _probes(rng, table, 4096)

    lo, counts = probe_counts(jnp.asarray(table), run_lengths(jnp.asarray(table)),
                              jnp.asarray(probes))

    want_lo = np.searchsorted(table, probes, side="left")
    want = np.searchsorted(table, probes, side="right") - want_lo
    want[probes == SENT] = 0
    np.testing.assert_array_equal(np.asarray(lo), want_lo)
    np.testing.assert_array_equal(np.asarray(counts), want)
    assert lo.dtype == jnp.int32 and counts.dtype == jnp.int32
    if live:
        assert want.max() >= 1  # the case does find something


@pytest.mark.parametrize("cap", [1024, 32768])
def test_run_lengths_against_a_loop(cap):
    rng = np.random.default_rng(cap)
    for live, distinct in ((cap, 7), (cap // 3, cap), (0, 0), (1, 1)):
        table = _table(rng, cap, live, distinct)
        np.testing.assert_array_equal(
            np.asarray(run_lengths(jnp.asarray(table))), _np_run_lens(table))


BUILD = Schema([Field("k", DataType.int64()), Field("b", DataType.int32())])
PROBE = Schema([Field("k", DataType.int64()), Field("p", DataType.int32())])


def test_build_kernel_returns_the_tables_run_lengths():
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
    cand = jax.make_jaxpr(candidate)(cols, jmap.sorted_keys, jmap.run_lens, probe.num_rows)
    assert _loops(cand.jaxpr) == 1

    total, lo, counts = candidate(cols, jmap.sorted_keys, jmap.run_lens, probe.num_rows)
    assert int(total) == 3
    expand = jax.make_jaxpr(lambda a, b: expand_pairs(a, b, 1024))(lo, counts)
    assert _loops(expand.jaxpr) == 1
    probe_jaxpr = jax.make_jaxpr(
        lambda c, m, a, b: j._probe_kernel.__wrapped__(c, m, a, b, out_cap=1024)
    )(cols, jmap, lo, counts)
    assert _loops(probe_jaxpr.jaxpr) == 1
