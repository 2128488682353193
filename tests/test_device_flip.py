"""Majority-device flip gates (tier-1, CPU backend).

The PR-19 acceptance surface: with the batch autotuner armed, warm
q01/q06 spend more wall time ON the device than in the host dispatch
loop; the tier-5 fused shuffle write absorbs the blocking boundary
above it (agg finalize, range partitioning); donated double-buffered
staging changes WHEN buffers die, never WHAT bytes commit; and the
dispatch-driven batch autotuner converges inside its configured bounds
and backs off under memory pressure.

Every path here is a differential against the plain (donation off,
autotune off, fusion off) execution — byte-identical committed shuffle
files, or value-identical query output where coalescing legitimately
reassociates float reductions.
"""

import os
import tempfile

import numpy as np
import pytest

from blaze_tpu import conf
from blaze_tpu.batch import batch_to_pydict
from blaze_tpu.exprs import col
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.ops.agg import AggExec, AggFunction, AggMode, GroupingExpr
from blaze_tpu.ops.fusion import optimize_plan
from blaze_tpu.ops.sort import SortField
from blaze_tpu.parallel.shuffle import (
    HashPartitioning, RangePartitioning, ShuffleWriterExec,
)
from blaze_tpu.runtime import dispatch, faults, trace
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.tpch import TPCH_SCHEMAS, build_query
from blaze_tpu.tpch.datagen import generate_all, table_to_batches

SCALE = 0.01
BATCH_ROWS = 4096


@pytest.fixture(scope="module")
def data():
    return generate_all(SCALE)


@pytest.fixture(scope="module")
def flip_data():
    # the majority-device gate needs enough per-bucket device work to
    # rise above CPU-backend timer noise; datagen at 0.05 is <1s
    return generate_all(0.05)


def _scans(data, batch_rows=BATCH_ROWS, n_parts=1):
    return {
        name: MemoryScanExec(
            table_to_batches(data[name], TPCH_SCHEMAS[name], n_parts,
                             batch_rows=batch_rows),
            TPCH_SCHEMAS[name],
        )
        for name in TPCH_SCHEMAS
    }


def _run(plan):
    out = {f.name: [] for f in plan.schema.fields}
    for p in range(plan.num_partitions()):
        for b in plan.execute(p, TaskContext(p, plan.num_partitions())):
            d = batch_to_pydict(b)
            for k in out:
                out[k].extend(d[k])
    return out


# --------------------------------- 1. warm majority-device budget


@pytest.mark.parametrize("q", ["q1", "q6"])
def test_warm_query_majority_device_with_autotune(flip_data, q):
    """With the autotuner armed (exactly how --perfcheck measures),
    the warm steady state spends more time on the device than in the
    dispatch loop.  Totals are SUMMED over several warm passes — a
    single pass at test scale is at the mercy of one slow dispatch."""
    def run_once():
        plan = optimize_plan(build_query(q, _scans(flip_data), 1))
        rows = 0
        for p in range(plan.num_partitions()):
            for b in plan.execute(p, TaskContext(p, plan.num_partitions())):
                rows += b.num_rows
        assert rows > 0

    dispatch.autotune_force(True)
    try:
        # pin the controller at its dispatch-bound fixed point, exactly
        # how --perfcheck measures: timing-driven convergence on a
        # loaded CPU host can break early (a window the share coin-flip
        # called device-majority) and then grow DURING the measured
        # passes — a fresh bucket shape there recompiles and breaks the
        # zero-warm-compile assertion.  At the cap further observations
        # cannot move the target, so the cold pass below compiles the
        # final shapes and the measurement is stable.
        dispatch.autotune_saturate(q)
        run_once()  # cold: compiles allowed
        device_ns = dispatch_ns = 0
        with dispatch.capture() as warm:
            for _ in range(3):
                with trace.profile_kernels() as prof:
                    run_once()
                k = trace.sum_kernels(prof)
                device_ns += k["device_time_ns"]
                dispatch_ns += k["dispatch_overhead_ns"]
    finally:
        dispatch.autotune_force(None)
    assert warm.get("xla_compiles", 0) == 0, (
        f"warm {q} recompiled after convergence: {warm}")
    assert device_ns > dispatch_ns, (
        f"warm {q} is dispatch-bound: device {device_ns / 1e6:.2f}ms vs "
        f"dispatch {dispatch_ns / 1e6:.2f}ms over 3 passes")


def test_autotuned_q1_matches_plain_results(data):
    """Coalescing reassociates float reductions, so the differential is
    value-level (allclose), plus bit-determinism: two autotuned runs
    from a reset controller produce identical bytes."""
    def rows_of():
        d = _run(optimize_plan(build_query("q1", _scans(data), 1)))
        return {k: np.asarray(v) for k, v in d.items()}

    plain = rows_of()
    dispatch.autotune_force(True)
    try:
        # saturate both runs: a timing-converged target can differ run
        # to run, and a different coalesce width reassociates float
        # reductions differently — the byte-determinism half would
        # then compare two legitimately different groupings
        dispatch.autotune_saturate("q1")
        with trace.profile_kernels():
            tuned_a = rows_of()
        dispatch.autotune_reset()
        dispatch.autotune_saturate("q1")
        with trace.profile_kernels():
            tuned_b = rows_of()
    finally:
        dispatch.autotune_force(None)
    assert set(plain) == set(tuned_a)
    for k in plain:
        if plain[k].dtype.kind == "f":
            np.testing.assert_allclose(tuned_a[k], plain[k], rtol=1e-9)
            np.testing.assert_array_equal(tuned_a[k], tuned_b[k])
        else:
            np.testing.assert_array_equal(tuned_a[k], plain[k])
            np.testing.assert_array_equal(tuned_a[k], tuned_b[k])


# --------------------------------- 2. autotune controller units


def _autotune_bounds_conf(lo, hi, step, window):
    conf.BATCH_AUTOTUNE_MIN_ROWS.set(lo)
    conf.BATCH_AUTOTUNE_MAX_ROWS.set(hi)
    conf.BATCH_AUTOTUNE_STEP.set(step)
    conf.BATCH_AUTOTUNE_WINDOW.set(window)


def _restore_autotune_conf():
    for e in (conf.BATCH_AUTOTUNE_MIN_ROWS, conf.BATCH_AUTOTUNE_MAX_ROWS,
              conf.BATCH_AUTOTUNE_STEP, conf.BATCH_AUTOTUNE_WINDOW,
              conf.BATCH_AUTOTUNE_TARGET_SHARE):
        e.set(e.default)


def test_autotune_disabled_is_structural_noop():
    dispatch.autotune_force(None)
    prior = conf.BATCH_AUTOTUNE.get()
    conf.BATCH_AUTOTUNE.set(False)
    try:
        assert dispatch.autotune_target_rows() == 0
        with dispatch.capture() as cap:
            dispatch.autotune_memory_pushback("x")
        assert not cap.get("autotune_adjustments")
    finally:
        conf.BATCH_AUTOTUNE.set(prior)


def test_autotune_grows_by_step_within_bounds():
    """Dispatch-bound observations grow the target lo -> lo*step -> cap
    (maxRows), one decision per window, each counted and traced."""
    dispatch.autotune_force(True)
    _autotune_bounds_conf(100, 1000, 4, 2)
    try:
        assert dispatch.autotune_target_rows() == 100
        with dispatch.capture() as cap:
            # window=2: two observations per decision, 10% device share
            for _ in range(2):
                dispatch.autotune_observe("k", device_ns=1, dispatch_ns=9)
            assert dispatch.autotune_target_rows() == 400
            for _ in range(2):
                dispatch.autotune_observe("k", device_ns=1, dispatch_ns=9)
            assert dispatch.autotune_target_rows() == 1000  # capped
            for _ in range(2):
                dispatch.autotune_observe("k", device_ns=1, dispatch_ns=9)
            assert dispatch.autotune_target_rows() == 1000  # stays capped
        assert cap.get("autotune_adjustments") == 2
    finally:
        _restore_autotune_conf()
        dispatch.autotune_force(None)


def test_autotune_stops_growing_past_target_share():
    dispatch.autotune_force(True)
    _autotune_bounds_conf(100, 100000, 4, 1)
    try:
        dispatch.autotune_observe("k", device_ns=9, dispatch_ns=1)
        assert dispatch.autotune_target_rows() == 100, \
            "majority-device window must not grow the bucket"
    finally:
        _restore_autotune_conf()
        dispatch.autotune_force(None)


def test_autotune_memory_pushback_halves_and_caps_regrowth():
    dispatch.autotune_force(True)
    _autotune_bounds_conf(100, 100000, 4, 1)
    try:
        dispatch.autotune_observe("k", device_ns=0, dispatch_ns=10)
        dispatch.autotune_observe("k", device_ns=0, dispatch_ns=10)
        grown = dispatch.autotune_target_rows()
        assert grown == 1600
        with dispatch.capture() as cap:
            dispatch.autotune_memory_pushback("k")
        assert cap.get("autotune_adjustments", 0) >= 1
        halved = dispatch.autotune_target_rows()
        assert halved < grown
        # regrowth is CAPPED below the size that exhausted the device
        for _ in range(20):
            dispatch.autotune_observe("k", device_ns=0, dispatch_ns=10)
        assert dispatch.autotune_target_rows() < grown
    finally:
        _restore_autotune_conf()
        dispatch.autotune_force(None)


# ------------------- 3. blocking-boundary fusion into the fused write


def _agg_plan(data):
    groupings = [GroupingExpr(col("l_returnflag"), "l_returnflag")]
    aggs = [AggFunction("sum", col("l_quantity"), "sum_qty"),
            AggFunction("count_star", None, "cnt")]
    scan = _scans(data, batch_rows=2048)["lineitem"]
    partial = AggExec(scan, AggMode.PARTIAL, groupings, aggs)
    return AggExec(partial, AggMode.FINAL, groupings, aggs)


def _write_once(plan_fn, partitioning_fn, boundaries=None):
    d = tempfile.mkdtemp(prefix="blaze_flip_")
    data_path = os.path.join(d, "m.data")
    index_path = os.path.join(d, "m.index")
    writer = optimize_plan(ShuffleWriterExec(
        plan_fn(), partitioning_fn(), data_path, index_path))
    if boundaries is not None:
        writer.partitioning.boundaries = boundaries
    list(writer.execute(0, TaskContext(0, 1)))
    with open(data_path, "rb") as f:
        blob = f.read()
    with open(index_path, "rb") as f:
        idx = f.read()
    return blob, idx, writer


def test_agg_finalize_absorbed_into_fused_write_byte_identical(data):
    """A FINAL agg feeding a hash shuffle write runs its finalize
    kernel INSIDE the tier-5 fused program (no device round-trip at
    the blocking boundary) and commits identical bytes to the unfused
    finalize-then-write path."""
    blob_f, idx_f, w = _write_once(
        lambda: _agg_plan(data),
        lambda: HashPartitioning([col("l_returnflag")], 3))
    assert w._fused_write is not None, "agg chain not absorbed"
    assert any(isinstance(k, tuple) and k and k[0] == "agg_finalize"
               for k in w._fused_fn_keys), w._fused_fn_keys
    conf.FUSION_ENABLE.set(False)
    try:
        blob_u, idx_u, wu = _write_once(
            lambda: _agg_plan(data),
            lambda: HashPartitioning([col("l_returnflag")], 3))
        assert wu._fused_write is None
    finally:
        conf.FUSION_ENABLE.set(True)
    assert blob_f == blob_u and idx_f == idx_u


def _range_boundaries(data, fields, n_out):
    import jax.numpy as jnp

    from blaze_tpu.parallel.exchange import _build_range_kernels

    sch = TPCH_SCHEMAS["lineitem"]
    kw, bat, _ = _build_range_kernels(sch, fields, n_out)
    scan = _scans(data, batch_rows=2048)["lineitem"]
    batches = list(scan.execute(0, TaskContext(0, 1)))
    words = [kw(tuple(b.columns), b.num_rows) for b in batches]
    cat = tuple(jnp.concatenate([w[i] for w in words])
                for i in range(len(words[0])))
    total = sum(b.num_rows for b in batches)
    positions = jnp.asarray([total * (i + 1) // n_out
                             for i in range(n_out - 1)])
    return tuple(np.asarray(b) for b in bat(cat, positions))


def test_range_partitioned_fused_write_byte_identical(data):
    """Range partitioning fuses with the boundary arrays as TRACED
    args (not baked constants): the fused program and the eager
    key-words/pids path commit identical files."""
    fields = [SortField(col("l_orderkey"))]
    bounds = _range_boundaries(data, fields, 3)
    blob_f, idx_f, w = _write_once(
        lambda: optimize_plan(_scans(data, batch_rows=2048)["lineitem"]),
        lambda: RangePartitioning(fields, 3), boundaries=bounds)
    assert w._fused_write is not None, "range write not absorbed"
    conf.FUSION_ENABLE.set(False)
    try:
        blob_u, idx_u, wu = _write_once(
            lambda: _scans(data, batch_rows=2048)["lineitem"],
            lambda: RangePartitioning(fields, 3), boundaries=bounds)
        assert wu._fused_write is None
    finally:
        conf.FUSION_ENABLE.set(True)
    assert blob_f == blob_u and idx_f == idx_u


# --------------------- 4. donated double-buffered staging seams


def _hash_write(data):
    return _write_once(lambda: _agg_plan(data),
                       lambda: HashPartitioning([col("l_returnflag")], 3))


def test_donated_write_fires_and_stays_byte_identical(data):
    plain_blob, plain_idx, _ = _hash_write(data)
    conf.DONATE_BUFFERS.set(True)
    try:
        with dispatch.capture() as cap:
            blob_d, idx_d, _ = _hash_write(data)
    finally:
        conf.DONATE_BUFFERS.set(False)
    assert cap.get("donated_buffers", 0) > 0, (
        f"no batch took the donated twin: {cap}")
    assert blob_d == plain_blob and idx_d == plain_idx


def test_donated_write_sync_staging_byte_identical(data):
    """Donation with the synchronous writer (no inserter, no device
    ring) — the donated kernel itself is staging-agnostic."""
    plain_blob, plain_idx, _ = _hash_write(data)
    conf.DONATE_BUFFERS.set(True)
    conf.SHUFFLE_ASYNC_WRITE.set(False)
    try:
        blob_d, idx_d, _ = _hash_write(data)
    finally:
        conf.SHUFFLE_ASYNC_WRITE.set(True)
        conf.DONATE_BUFFERS.set(False)
    assert blob_d == plain_blob and idx_d == plain_idx


def test_donated_write_unfused_path_byte_identical(data):
    """Fusion off: no fused write exists, donation has nothing to bind
    to, and the conf being on must not perturb the eager path."""
    plain_blob, plain_idx, _ = _hash_write(data)
    conf.DONATE_BUFFERS.set(True)
    conf.FUSION_ENABLE.set(False)
    try:
        blob_d, idx_d, w = _hash_write(data)
        assert w._fused_write is None
    finally:
        conf.FUSION_ENABLE.set(True)
        conf.DONATE_BUFFERS.set(False)
    assert blob_d == plain_blob and idx_d == plain_idx


def test_donated_write_oom_downshift_byte_identical(data):
    """An injected device OOM under donation decomposes to the eager
    per-kernel path with the batch's inputs INTACT (injected faults
    raise before the donating call) — committed bytes unchanged."""
    plain_blob, plain_idx, _ = _hash_write(data)
    conf.DONATE_BUFFERS.set(True)
    conf.FAULTS_SPEC.set("kernel.dispatch@3@oom")
    faults.reset()
    try:
        with dispatch.capture() as cap:
            blob_d, idx_d, _ = _hash_write(data)
    finally:
        conf.FAULTS_SPEC.set("")
        faults.reset()
        conf.DONATE_BUFFERS.set(False)
    assert (cap.get("oom_recoveries", 0) + cap.get("batch_downshifts", 0)
            + cap.get("eager_fallbacks", 0)) > 0, (
        f"the injected OOM never reached the ladder: {cap}")
    assert blob_d == plain_blob and idx_d == plain_idx


def test_device_oom_error_not_reabsorbed_as_resource_exhausted():
    """The OOM ladder's TERMINAL verdict must not re-enter the ladder:
    a donating program's inputs may already be dead, so DeviceOomError
    classifies non-absorbable even though its message embeds the
    cause's RESOURCE_EXHAUSTED text."""
    from blaze_tpu.runtime import oom

    err = oom.DeviceOomError(
        "fused_write: RESOURCE_EXHAUSTED: out of memory")
    assert not oom.is_resource_exhausted(err)
    assert oom.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory"))


def test_abort_mid_stream_drops_ring_without_commit(data):
    """A task killed mid-stream (injected non-OOM fault — the same
    seam a ctx cancel rides) drops the device ring and aborts the
    async writer: nothing commits, and a fresh run afterwards still
    produces the canonical bytes (no poisoned process state)."""
    plain_blob, plain_idx, _ = _hash_write(data)
    conf.DONATE_BUFFERS.set(True)
    conf.FAULTS_SPEC.set("kernel.dispatch@4@a0")
    faults.reset()
    try:
        d = tempfile.mkdtemp(prefix="blaze_cancel_")
        data_path = os.path.join(d, "m.data")
        index_path = os.path.join(d, "m.index")
        writer = optimize_plan(ShuffleWriterExec(
            _agg_plan(data), HashPartitioning([col("l_returnflag")], 3),
            data_path, index_path))
        with pytest.raises(faults.InjectedFault):
            list(writer.execute(0, TaskContext(0, 1)))
        assert not os.path.exists(data_path), \
            "aborted task committed a partial .data file"
        assert not os.path.exists(index_path)
        conf.FAULTS_SPEC.set("")
        faults.reset()
        # the seam leaks nothing into process state: a clean run after
        # the abort still commits the canonical bytes
        blob2, idx2, _ = _hash_write(data)
    finally:
        conf.FAULTS_SPEC.set("")
        faults.reset()
        conf.DONATE_BUFFERS.set(False)
    assert blob2 == plain_blob and idx2 == plain_idx


def test_device_ring_fifo_and_overlap_metric():
    from blaze_tpu.batch import DeviceRing

    ring = DeviceRing()
    with dispatch.capture() as cap:
        out = []
        for i in range(5):
            out.extend(ring.put(i))
        out.extend(ring.flush())
    assert out == [0, 1, 2, 3, 4], "ring must preserve FIFO order"
    assert len(ring) == 0
    assert cap.get("double_buffer_overlap_ns", 0) > 0
    ring.put(9)
    ring.drop()
    assert len(ring) == 0 and ring.flush() == []


# ----------------------------- 5. pallas hash-join probe kernel


def test_sorted_lookup_matches_searchsorted(interpret):
    from blaze_tpu.kernels import pallas_ops

    rng = np.random.default_rng(11)
    for t_n, p_n in ((17, 100), (1024, 3000),
                     (pallas_ops.SORTED_LOOKUP_MAX_TABLE, 257)):
        table = np.sort(rng.integers(0, 2**63, t_n, dtype=np.uint64))
        # duplicates + exact hits + misses + extremes
        probes = np.concatenate([
            rng.choice(table, p_n // 2),
            rng.integers(0, 2**63, p_n - p_n // 2, dtype=np.uint64),
            np.asarray([0, 2**64 - 2], dtype=np.uint64),
        ])
        import jax.numpy as jnp

        lo, hi = pallas_ops.sorted_lookup(jnp.asarray(table),
                                          jnp.asarray(probes))
        np.testing.assert_array_equal(
            np.asarray(lo), np.searchsorted(table, probes, side="left"))
        np.testing.assert_array_equal(
            np.asarray(hi), np.searchsorted(table, probes, side="right"))


@pytest.mark.parametrize("q", ["q12", "q14"])
def test_pallas_join_probe_differential(data, q):
    """spark.blaze.tpu.pallas.joinProbe (forced interpret off-TPU):
    join results identical to the XLA searchsorted probe path."""
    from blaze_tpu.kernels import pallas_ops

    def rows_of():
        d = _run(optimize_plan(build_query(q, _scans(data), 1)))
        return sorted(zip(*d.values()), key=repr)

    plain = rows_of()
    pallas_ops.force_interpret(True)
    conf.PALLAS_JOIN_PROBE.set(True)
    try:
        got = rows_of()
    finally:
        conf.PALLAS_JOIN_PROBE.set(False)
        pallas_ops.force_interpret(False)
    assert got == plain
