"""Pallas kernel tests (interpret mode on the CPU mesh — forced: off a
TPU nothing but ``force_interpret(True)`` interprets a kernel).

Differential oracles: the pure-XLA implementations in exprs/hash.py
(themselves validated against Spark golden vectors in test_hash.py)
and numpy.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from blaze_tpu.batch import column_from_numpy
from blaze_tpu.exprs.hash import murmur3_columns, pmod
from blaze_tpu.kernels import fused_group_sums, murmur3_pids, pid_histogram
from blaze_tpu.kernels.pallas_ops import column_word_planes
from blaze_tpu.schema import DataType


def _ref_pids(cols, n_parts):
    return np.asarray(pmod(murmur3_columns(cols), n_parts))


def test_murmur3_pids_i64_matches_xla(interpret):
    rng = np.random.default_rng(0)
    n = 3000  # not a multiple of the 1024-row tile
    keys = rng.integers(-(2**62), 2**62, n, dtype=np.int64)
    col = column_from_numpy(DataType.int64(), keys, capacity=n)
    planes, w = column_word_planes(col.to_device())
    got = np.asarray(
        murmur3_pids(planes, [w], [jnp.asarray(col.validity)], 200)
    )
    np.testing.assert_array_equal(got, _ref_pids([col.to_device()], 200))


def test_murmur3_pids_multi_col_with_nulls(interpret):
    rng = np.random.default_rng(1)
    n = 1500
    a = rng.integers(-(2**31), 2**31, n, dtype=np.int32)
    b = rng.integers(-(2**62), 2**62, n, dtype=np.int64)
    valid_a = rng.random(n) > 0.2
    ca = column_from_numpy(DataType.int32(), a, valid_a, capacity=n).to_device()
    cb = column_from_numpy(DataType.int64(), b, capacity=n).to_device()
    pa, wa = column_word_planes(ca)
    pb, wb = column_word_planes(cb)
    got = np.asarray(
        murmur3_pids(
            pa + pb, [wa, wb], [jnp.asarray(ca.validity), jnp.asarray(cb.validity)], 17
        )
    )
    np.testing.assert_array_equal(got, _ref_pids([ca, cb], 17))


@pytest.mark.parametrize(
    "dtype,gen",
    [
        (DataType.int32(), lambda rng, n: rng.integers(-(2**31), 2**31, n).astype(np.int32)),
        (DataType.float64(), lambda rng, n: np.concatenate([[0.0, -0.0, 1.5], rng.random(n - 3)])),
        (DataType.float32(), lambda rng, n: np.concatenate([[0.0, -0.0], rng.random(n - 2)]).astype(np.float32)),
        (DataType.decimal(12, 2), lambda rng, n: rng.integers(-(2**40), 2**40, n)),
        (DataType.date32(), lambda rng, n: rng.integers(0, 20000, n).astype(np.int32)),
        (DataType.bool_(), lambda rng, n: rng.integers(0, 2, n).astype(np.bool_)),
    ],
    ids=["int32", "float64", "float32", "decimal", "date32", "bool"],
)
def test_murmur3_pids_every_key_dtype(interpret, dtype, gen):
    """Every column_word_planes branch must agree with the XLA hash —
    partition ids are a Spark-compat correctness gate."""
    rng = np.random.default_rng(7)
    n = 1100
    vals = gen(rng, n)
    valid = rng.random(n) > 0.15
    col = column_from_numpy(dtype, vals, valid, capacity=n).to_device()
    planes, w = column_word_planes(col)
    got = np.asarray(murmur3_pids(planes, [w], [jnp.asarray(col.validity)], 31))
    np.testing.assert_array_equal(got, _ref_pids([col], 31))


def test_pid_histogram_matches_bincount(interpret):
    rng = np.random.default_rng(2)
    n, p = 5000, 37
    pids = rng.integers(0, p, n).astype(np.int32)
    got = np.asarray(pid_histogram(jnp.asarray(pids), p))
    np.testing.assert_array_equal(got, np.bincount(pids, minlength=p))


def test_fused_group_sums_with_filtered_rows(interpret):
    rng = np.random.default_rng(3)
    n, g, k = 4000, 6, 3
    gids = rng.integers(-1, g, n).astype(np.int32)  # -1 = filtered out
    vals = [rng.random(n).astype(np.float32) for _ in range(k)]
    got = np.asarray(fused_group_sums(jnp.asarray(gids), [jnp.asarray(v) for v in vals], g))
    want = np.zeros((k, g), np.float32)
    for j in range(g):
        m = gids == j
        for i in range(k):
            want[i, j] = vals[i][m].sum(dtype=np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-5)


def test_fused_group_sums_counts(interpret):
    # count(*) per group = sum of a ones column
    gids = np.array([0, 1, 1, 2, -1, 2, 2], np.int32)
    ones = jnp.ones(7, jnp.float32)
    got = np.asarray(fused_group_sums(jnp.asarray(gids), [ones], 3))
    np.testing.assert_array_equal(got[0], [1, 2, 3])


def test_shuffle_writer_uses_pallas_pid_path():
    """End-to-end shuffle through the pallas partition-id fast path
    (forced interpret mode off-TPU) must equal the XLA path."""
    from blaze_tpu.kernels import pallas_ops
    from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.parallel import HashPartitioning, NativeShuffleExchangeExec
    from blaze_tpu.runtime.context import TaskContext
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("k", DataType.int64()), Field("v", DataType.int32())])
    batches = [
        [
            batch_from_pydict(
                {
                    "k": [int(v) if v % 9 else None for v in range(200 * i, 200 * i + 120)],
                    "v": list(range(120)),
                },
                schema,
            )
        ]
        for i in range(2)
    ]

    def run():
        src = MemoryScanExec(batches, schema)
        ex = NativeShuffleExchangeExec(src, HashPartitioning([col("k")], 3))
        out = {}
        for p in range(3):
            rows = []
            for b in ex.execute(p, TaskContext(p, 3)):
                d = batch_to_pydict(b)
                rows.extend(zip(d["k"], d["v"]))
            out[p] = sorted(rows, key=lambda r: (r[0] is None, r[0], r[1]))
        return out

    want = run()
    # count kernel invocations so a silent fallback to the XLA path
    # can't masquerade as coverage
    calls = {"n": 0}
    real = pallas_ops.murmur3_pids

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    pallas_ops.force_interpret(True)
    pallas_ops.murmur3_pids = counting
    try:
        got = run()
    finally:
        pallas_ops.murmur3_pids = real
        pallas_ops.force_interpret(False)
    assert got == want
    assert calls["n"] > 0, "pallas pid path was never taken"
