"""Whole-stage fusion regression gates (tier-1, CPU backend).

1. **Dispatch budget**: warm TPC-H q01 must execute in <= 8 XLA
   dispatches per input batch with ZERO recompiles on the second run —
   the q01 collapse (ISSUE 2) that future PRs must not silently
   re-fragment.  A warm hash-shuffle MAP stage over a traceable chain
   through the stage scheduler must execute <= 2 dispatches per batch
   (ISSUE 4's fused shuffle write), also with zero warm recompiles.
2. **Fused-vs-unfused differential**: every tier-1 sample query must
   produce identical results with ``spark.blaze.fusion.enabled=false``
   (the per-operator fallback path stays correct) — including
   generate/expand/window chains and the fused shuffle write, whose
   ``.data``/``.index`` output (spill path included) must be
   byte-identical to the unfused writer's.
3. **Observability plumbing**: the scheduler MetricNode carries the
   ``xla_dispatches`` / ``xla_compiles`` / ``compile_ms`` /
   ``fused_stage_len`` counters per stage.
4. **Deferred agg count**: the fused agg update keeps its accumulator
   occupancy count device-resident — zero scalar syncs gate a dispatch
   on the warm q01 steady state (``fused_agg_stall_syncs``).
"""

import os
import tempfile

import numpy as np
import pytest

from blaze_tpu import conf
from blaze_tpu.batch import batch_to_pydict
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.ops.fusion import optimize_plan
from blaze_tpu.runtime import dispatch
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.tpch import TPCH_SCHEMAS, build_query
from blaze_tpu.tpch.datagen import generate_all, table_to_batches

SCALE = 0.01
BATCH_ROWS = 4096
DISPATCH_BUDGET = 8  # per warm input batch (acceptance criterion)


@pytest.fixture(scope="module")
def data():
    return generate_all(SCALE)


def _scans(data, batch_rows=BATCH_ROWS, n_parts=1):
    return {
        name: MemoryScanExec(
            table_to_batches(data[name], TPCH_SCHEMAS[name], n_parts,
                             batch_rows=batch_rows),
            TPCH_SCHEMAS[name],
        )
        for name in TPCH_SCHEMAS
    }


def _optimized(q, data, n_parts=1):
    return optimize_plan(build_query(q, _scans(data, n_parts=n_parts), n_parts))


def _run(plan):
    out = {f.name: [] for f in plan.schema.fields}
    for p in range(plan.num_partitions()):
        for b in plan.execute(p, TaskContext(p, plan.num_partitions())):
            d = batch_to_pydict(b)
            for k in out:
                out[k].extend(d[k])
    return out


def _rows(d):
    return sorted(zip(*d.values()), key=repr)


def test_q1_warm_dispatch_budget(data):
    """Warm q01: <= 8 dispatches per input batch, zero recompiles.
    Plans are rebuilt between runs exactly like run_task rebuilds them
    per task — the budget holds because kernels are cached
    process-wide, not per exec instance."""
    n_rows = len(data["lineitem"]["l_quantity"][0])
    n_batches = (n_rows + BATCH_ROWS - 1) // BATCH_ROWS
    assert n_batches >= 4, "scale too small to exercise the per-batch loop"

    _run(_optimized("q1", data))  # cold: compiles allowed
    with dispatch.capture() as warm:
        _run(_optimized("q1", data))

    assert warm.get("xla_compiles", 0) == 0, (
        f"warm q01 recompiled: {warm}")
    per_batch = warm.get("xla_dispatches", 0) / n_batches
    assert per_batch <= DISPATCH_BUDGET, (
        f"warm q01 issued {warm.get('xla_dispatches', 0)} dispatches over "
        f"{n_batches} batches ({per_batch:.1f}/batch > {DISPATCH_BUDGET})")


@pytest.mark.parametrize("q", ["q1", "q6", "q12", "q14", "q19"])
def test_zero_recompiles_across_plan_rebuilds(data, q):
    """Same-bucket batches never recompile even across fresh plan
    builds (the kernel-cache + shape-bucketing contract the persistent
    compile cache depends on), and two warm runs launch the same
    number of programs: a count that moves between identical runs is
    a launch that depends on something other than the plan and data."""
    _run(_optimized(q, data))
    warm = []
    for _ in range(2):
        with dispatch.capture() as cap:
            _run(_optimized(q, data))
        warm.append(cap)
    assert [c.get("xla_compiles", 0) for c in warm] == [0, 0], warm
    assert warm[0].get("xla_dispatches", 0) > 0
    assert warm[0]["xla_dispatches"] == warm[1]["xla_dispatches"], warm


@pytest.mark.parametrize("q", ["q1", "q6", "q19", "q12", "q14"])
def test_fused_vs_unfused_differential_tpch(data, q):
    """spark.blaze.fusion.enabled=false must be result-identical —
    the fallback path every fusion tier rests on."""
    fused = _rows(_run(_optimized(q, data, n_parts=2)))
    conf.FUSION_ENABLE.set(False)
    try:
        unfused = _rows(_run(_optimized(q, data, n_parts=2)))
    finally:
        conf.FUSION_ENABLE.set(True)
    assert fused == unfused


def test_fused_vs_unfused_differential_tpcds():
    from blaze_tpu.tpcds import TPCDS_SCHEMAS, generate_all as ds_gen
    from blaze_tpu.tpcds import build_query as ds_build

    data = ds_gen(0.002)
    def scans():
        return {
            name: MemoryScanExec(
                table_to_batches(data[name], TPCDS_SCHEMAS[name], 1,
                                 batch_rows=BATCH_ROWS),
                TPCDS_SCHEMAS[name],
            )
            for name in TPCDS_SCHEMAS
        }

    def run(q):
        return _rows(_run(optimize_plan(ds_build(q, scans(), 1))))

    for q in ("q3", "q55"):
        fused = run(q)
        conf.FUSION_ENABLE.set(False)
        try:
            unfused = run(q)
        finally:
            conf.FUSION_ENABLE.set(True)
        assert fused == unfused, q


def test_fused_agg_update_off_differential(data):
    """The single-program agg update (spark.blaze.tpu.fusedAggUpdate)
    must agree with the eager pending/doubling path."""
    fused = _rows(_run(_optimized("q1", data)))
    conf.FUSED_AGG_UPDATE.set(False)
    try:
        eager = _rows(_run(_optimized("q1", data)))
    finally:
        conf.FUSED_AGG_UPDATE.set(True)
    assert fused == eager


def test_fused_update_overflow_falls_back_to_eager(data):
    """All-distinct keys overflow the fused update's stacked-state
    bucket on batch 2 (triggering the eager re-merge, which must
    re-bucket to a power-of-two capacity) and push the accumulator
    past one batch bucket (triggering the pending/doubling fallback
    on later batches) — both rare paths stay exact."""
    import numpy as np

    from blaze_tpu.exprs import col
    from blaze_tpu.ops import AggExec, AggFunction, AggMode, GroupingExpr
    from blaze_tpu.schema import DataType, Field, Schema

    n = 5 * 2048
    schema = Schema([Field("k", DataType.int64()), Field("v", DataType.int64())])
    table = {"k": (np.arange(n, dtype=np.int64), None),
             "v": (np.full(n, 3, dtype=np.int64), None)}
    scan = MemoryScanExec(
        table_to_batches(table, schema, 1, batch_rows=2048), schema)
    agg = AggExec(scan, AggMode.PARTIAL, [GroupingExpr(col("k"), "k")],
                  [AggFunction("sum", col("v"), "s")])
    seen = {}
    for b in agg.execute(0, TaskContext(0, 1)):
        d = batch_to_pydict(b)
        for k, s in zip(d["k"], d["s#sum"]):
            seen[k] = seen.get(k, 0) + s
    assert len(seen) == n and all(v == 3 for v in seen.values())


def test_fused_update_merges_at_twice_the_bucket_not_the_batch():
    """The update program merges accumulator + the partial's first
    ``out_cap`` rows (2 x out_cap), never accumulator + a whole
    batch-sized buffer: that program sorted a batch of padding per
    update and the v5e compiler did not survive it at 1,024 + 65,536
    rows (PR 22).  The count it returns is int32 like the seed count —
    a second dtype is a second compile of the same program — and the
    twin that merges a PARTIAL agg's accumulators sorts ONE hash key
    (its duplicate groups are re-merged downstream); a FINAL agg's
    twin stays exact."""
    import jax
    import jax.numpy as jnp

    from blaze_tpu.batch import Column
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import AggExec, AggFunction, AggMode, GroupingExpr
    from blaze_tpu.ops.agg import _StateMerger
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("k", DataType.int64()), Field("v", DataType.int64())])
    scan = MemoryScanExec([[]], schema)
    partial = AggExec(scan, AggMode.PARTIAL, [GroupingExpr(col("k"), "k")],
                      [AggFunction("sum", col("v"), "s")])

    def shapes(sch, cap):
        return tuple(Column(f.dtype,
                            jax.ShapeDtypeStruct((cap,), f.dtype.np_dtype),
                            jax.ShapeDtypeStruct((cap,), jnp.bool_))
                     for f in sch.fields)

    acc_cap, batch_cap = 1024, 8192
    update = dispatch.raw(partial._update_kernels()[0])
    args = (shapes(partial._state_schema, acc_cap),
            jax.ShapeDtypeStruct((), jnp.int32), shapes(schema, batch_cap),
            batch_cap, acc_cap)
    hlo = update.lower(*args).as_text()
    assert f"tensor<{2 * acc_cap}x" in hlo
    assert f"tensor<{acc_cap + batch_cap}x" not in hlo
    cols, count = jax.eval_shape(update, *args)
    assert count.dtype == jnp.int32 and count.shape == ()
    assert {c.validity.shape for c in cols} == {(acc_cap,)}

    assert _StateMerger.for_agg(partial)._twin._dup_groups_ok
    final = AggExec(MemoryScanExec([[]], partial.schema), AggMode.FINAL,
                    [GroupingExpr(col("k"), "k")],
                    [AggFunction("sum", col("v"), "s")])
    assert not _StateMerger.for_agg(final)._twin._dup_groups_ok
    assert partial._kernel_key != _StateMerger.for_agg(partial)._twin._kernel_key


def test_fused_update_rollback_after_eager_interleave_exact():
    """Regression: when the fused path resumes from a state the EAGER
    pending-merge built (a plain RecordBatch), that state must become
    the overflow-rollback base — rebuilding from the pre-merge
    accumulator silently dropped the eager-merged groups (9000 of
    14000 keys surviving in the repro)."""
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import AggExec, AggFunction, AggMode, GroupingExpr
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("k", DataType.int64()), Field("v", DataType.int64())])

    def mk(keys):
        return batch_from_pydict({"k": list(keys), "v": [3] * len(keys)}, schema)

    # seed 2000 distinct (cap 2048); five cap-1024 batches force the
    # stall path then the eager pending merge (5000 rows >= 4096); a
    # cap-8192 batch resumes the fused path and overflows it
    batches = [mk(range(0, 2000))]
    base = 2000
    for _ in range(5):
        batches.append(mk(range(base, base + 1000)))
        base += 1000
    batches.append(mk(range(base, base + 7000)))
    base += 7000

    scan = MemoryScanExec([batches], schema)
    agg = AggExec(scan, AggMode.PARTIAL, [GroupingExpr(col("k"), "k")],
                  [AggFunction("sum", col("v"), "s")])
    seen = {}
    with dispatch.capture() as cap:
        for b in agg.execute(0, TaskContext(0, 1)):
            d = batch_to_pydict(b)
            for k, s in zip(d["k"], d["s#sum"]):
                seen[k] = seen.get(k, 0) + s
    assert cap.get("fused_agg_rollbacks", 0) >= 1, (
        f"scenario no longer reaches the resumed-overflow rollback: {cap}")
    assert len(seen) == base and all(v == 3 for v in seen.values())


def test_fused_agg_update_no_per_batch_stall(data):
    """The warm q01 fused update never blocks a dispatch on the
    accumulator count: the occupancy scalar stays device-resident, its
    overflow check resolves AFTER the next batch's program is already
    in the device queue (``fused_agg_deferred_syncs``), and no batch
    forces a pre-dispatch fetch or an overflow rollback."""
    _run(_optimized("q1", data))  # warm the kernels
    with dispatch.capture() as warm:
        _run(_optimized("q1", data))
    assert warm.get("fused_agg_deferred_syncs", 0) > 0, warm
    assert warm.get("fused_agg_stall_syncs", 0) == 0, warm
    assert warm.get("fused_agg_rollbacks", 0) == 0, warm


# ------------------------------------ fused shuffle write (tier 5)


def _shuffle_chain_plan(data, n_parts=1):
    """lineitem scan -> filter -> compute projection: the traceable map
    chain a hash shuffle write absorbs."""
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.ir import Alias, BinOp, Lit
    from blaze_tpu.ops.filter import FilterExec
    from blaze_tpu.ops.project import ProjectExec
    from blaze_tpu.schema import DataType

    scan = _scans(data, batch_rows=2048, n_parts=n_parts)["lineitem"]
    f = FilterExec(scan, BinOp(">", col("l_quantity"),
                               Lit(10.0, DataType.float64())))
    return ProjectExec(
        f,
        [col("l_orderkey"),
         Alias(BinOp("+", col("l_linenumber"), Lit(1, DataType.int32())), "ln1"),
         col("l_returnflag")],
        ["l_orderkey", "ln1", "l_returnflag"],
    )


def _write_shuffle(data, n_out=4, budget=None):
    """Run one optimized ShuffleWriterExec map task; returns the
    committed (.data bytes, .index bytes, partition_lengths,
    spill_count)."""
    from blaze_tpu.exprs import col
    from blaze_tpu.parallel.shuffle import HashPartitioning, ShuffleWriterExec
    from blaze_tpu.runtime.memmgr import MemManager

    d = tempfile.mkdtemp(prefix="blaze_fused_write_")
    data_path, index_path = os.path.join(d, "m.data"), os.path.join(d, "m.index")
    writer = optimize_plan(ShuffleWriterExec(
        _shuffle_chain_plan(data), HashPartitioning([col("l_orderkey")], n_out),
        data_path, index_path,
    ))
    if budget is not None:
        MemManager._global = None
        MemManager.init(budget)
    try:
        list(writer.execute(0, TaskContext(0, 1)))
    finally:
        if budget is not None:
            MemManager._global = None
            MemManager.init(int(conf.HOST_SPILL_BUDGET.get()))
    with open(data_path, "rb") as f:
        blob = f.read()
    with open(index_path, "rb") as f:
        idx = f.read()
    spills = writer.metrics.get("spill_count")
    lengths = writer.partition_lengths
    return blob, idx, lengths, spills


def test_fused_shuffle_write_byte_identical(data):
    """Tier 5 differential: hash pids, per-partition counts, and the
    committed .data/.index pair are byte-identical between the fused
    one-program writer and the unfused chain+hash+sort path."""
    blob_f, idx_f, lengths_f, _ = _write_shuffle(data)
    conf.FUSION_ENABLE.set(False)
    try:
        blob_u, idx_u, lengths_u, _ = _write_shuffle(data)
    finally:
        conf.FUSION_ENABLE.set(True)
    assert lengths_f == lengths_u
    assert blob_f == blob_u and idx_f == idx_u


def test_fused_shuffle_write_spill_path_byte_identical(data):
    """The spill path (memory pressure mid-map) commits the same bytes
    fused and unfused — the async double-buffered writer preserves
    insertion order and the commit-by-rename contract."""
    blob_f, idx_f, _, spills_f = _write_shuffle(data, budget=60_000)
    assert spills_f > 0, "budget too high to force the spill path"
    conf.FUSION_ENABLE.set(False)
    try:
        blob_u, idx_u, _, spills_u = _write_shuffle(data, budget=60_000)
    finally:
        conf.FUSION_ENABLE.set(True)
    assert spills_u > 0
    assert blob_f == blob_u and idx_f == idx_u


def test_fused_shuffle_write_sync_writer_byte_identical(data):
    """spark.blaze.shuffle.asyncWrite=false (the synchronous staging
    path) commits identical bytes."""
    blob_a, idx_a, _, _ = _write_shuffle(data)
    conf.SHUFFLE_ASYNC_WRITE.set(False)
    try:
        blob_s, idx_s, _, _ = _write_shuffle(data)
    finally:
        conf.SHUFFLE_ASYNC_WRITE.set(True)
    assert blob_a == blob_s and idx_a == idx_s


def test_shuffle_map_stage_warm_dispatch_budget(data):
    """A warm hash-shuffle map stage over a traceable chain, through
    the stage scheduler (TaskDefinition bytes), executes <= 2 XLA
    dispatches per input batch with zero warm recompiles — the
    ISSUE 4 acceptance criterion (one fused chain+pids+sort+counts
    program per batch, plus slack for per-task constants)."""
    from blaze_tpu.exprs import col
    from blaze_tpu.parallel import HashPartitioning, NativeShuffleExchangeExec
    from blaze_tpu.runtime.metrics import MetricNode
    from blaze_tpu.runtime.scheduler import run_stages, split_stages

    n_parts = 2
    n_rows = len(data["lineitem"]["l_quantity"][0])
    batch_rows = 2048
    # map tasks see ceil(rows_in_part / batch_rows) batches each
    per_part = (n_rows + n_parts - 1) // n_parts
    n_batches = n_parts * ((per_part + batch_rows - 1) // batch_rows)
    assert n_batches >= 4

    def run_once():
        plan = NativeShuffleExchangeExec(
            _shuffle_chain_plan(data, n_parts=n_parts),
            HashPartitioning([col("l_orderkey")], 3),
        )
        stages, manager = split_stages(plan)
        node = MetricNode()
        rows = 0
        for b in run_stages(stages, manager, metrics=node):
            rows += b.num_rows
        assert rows > 0
        return node

    run_once()  # cold: compiles allowed
    node = run_once()
    map_stage = node.child(0).metrics
    assert map_stage.get("xla_compiles") == 0, "warm map stage recompiled"
    per_batch = map_stage.get("xla_dispatches") / n_batches
    assert per_batch <= 2, (
        f"warm map stage issued {map_stage.get('xla_dispatches')} dispatches "
        f"over {n_batches} batches ({per_batch:.2f}/batch > 2)")


# --------------------------- generate / expand / window chains


def _rows_of(plan):
    return _rows(_run(plan))


def test_fused_vs_unfused_generate_chain():
    """explode -> filter -> compute projection collapses into one
    FusedStageExec program; fusion off must match row-for-row."""
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.ir import Alias, BinOp, Lit
    from blaze_tpu.ops.filter import FilterExec
    from blaze_tpu.ops.fusion import FusedStageExec
    from blaze_tpu.ops.generate import GenerateExec, NativeGenerator
    from blaze_tpu.ops.project import ProjectExec
    from blaze_tpu.schema import DataType, Field, Schema

    arr_t = DataType.array(DataType.int64(), 4)
    schema = Schema([Field("k", DataType.int64()), Field("xs", arr_t)])
    rows = {"k": list(range(40)),
            "xs": [[i, i + 1, i + 2][: (i % 4)] or None for i in range(40)]}

    def plan():
        scan = MemoryScanExec([[batch_from_pydict(rows, schema)]], schema)
        g = GenerateExec(scan, NativeGenerator("explode", col("xs")), [col("xs")])
        f = FilterExec(g, BinOp(">", col("col"), Lit(5, DataType.int64())))
        return optimize_plan(ProjectExec(
            f, [col("k"), Alias(BinOp("+", col("col"), Lit(1, DataType.int64())), "c1")],
            ["k", "c1"]))

    fused_plan = plan()
    assert isinstance(fused_plan, FusedStageExec), fused_plan.tree_string()
    fused = _rows_of(fused_plan)
    assert fused
    conf.FUSION_ENABLE.set(False)
    try:
        unfused = _rows_of(plan())
    finally:
        conf.FUSION_ENABLE.set(True)
    assert fused == unfused


def test_fused_vs_unfused_expand_chain():
    """expand (grouping-sets style projections) -> filter fuses into
    one program emitting all P projections compacted to a prefix."""
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.ir import BinOp, Lit
    from blaze_tpu.ops.expand import ExpandExec
    from blaze_tpu.ops.filter import FilterExec
    from blaze_tpu.ops.fusion import FusedStageExec
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("k", DataType.int64())])
    rows = {"k": list(range(50))}

    def plan():
        scan = MemoryScanExec([[batch_from_pydict(rows, schema)]], schema)
        e = ExpandExec(
            scan,
            [[col("k"), Lit(0, DataType.int64())],
             [BinOp("*", col("k"), Lit(2, DataType.int64())), Lit(1, DataType.int64())]],
            ["v", "tag"],
        )
        return optimize_plan(
            FilterExec(e, BinOp(">", col("v"), Lit(10, DataType.int64()))))

    fused_plan = plan()
    assert isinstance(fused_plan, FusedStageExec), fused_plan.tree_string()
    fused = _rows_of(fused_plan)
    assert fused
    conf.FUSION_ENABLE.set(False)
    try:
        unfused = _rows_of(plan())
    finally:
        conf.FUSION_ENABLE.set(True)
    assert fused == unfused


def test_fused_vs_unfused_window_shuffle_write():
    """A window map-side feeding a hash shuffle write: the writer
    absorbs the window kernel (partition-buffered bottom) + pids +
    sort into one program; files byte-identical to the unfused path."""
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.sort import SortField
    from blaze_tpu.ops.window import WindowExec, WindowFunction
    from blaze_tpu.parallel.shuffle import HashPartitioning, ShuffleWriterExec
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("g", DataType.int64()), Field("v", DataType.int64())])
    rows = {"g": sorted(i % 5 for i in range(200)),
            "v": [i * 7 % 13 for i in range(200)]}

    def write():
        d = tempfile.mkdtemp(prefix="blaze_window_write_")
        data_path, index_path = os.path.join(d, "m.data"), os.path.join(d, "m.index")
        scan = MemoryScanExec([[batch_from_pydict(rows, schema)]], schema)
        w = WindowExec(scan, [WindowFunction("row_number", "rn")],
                       [col("g")], [SortField(col("v"), True, True)])
        writer = optimize_plan(ShuffleWriterExec(
            w, HashPartitioning([col("g")], 3), data_path, index_path))
        list(writer.execute(0, TaskContext(0, 1)))
        with open(data_path, "rb") as f:
            blob = f.read()
        with open(index_path, "rb") as f:
            idx = f.read()
        return blob, idx, writer

    blob_f, idx_f, writer = write()
    assert writer._fused_write is not None, "window chain not absorbed"
    conf.FUSION_ENABLE.set(False)
    try:
        blob_u, idx_u, writer_u = write()
        assert writer_u._fused_write is None
    finally:
        conf.FUSION_ENABLE.set(True)
    assert blob_f == blob_u and idx_f == idx_u


def test_fused_vs_unfused_round_robin_write(data):
    """Round-robin partitioning fuses too (pids from a traced offset);
    byte-identical to the unfused arange/sort path."""
    from blaze_tpu.parallel.shuffle import RoundRobinPartitioning, ShuffleWriterExec

    def write():
        d = tempfile.mkdtemp(prefix="blaze_rr_write_")
        data_path, index_path = os.path.join(d, "m.data"), os.path.join(d, "m.index")
        writer = optimize_plan(ShuffleWriterExec(
            _shuffle_chain_plan(data), RoundRobinPartitioning(3),
            data_path, index_path))
        list(writer.execute(0, TaskContext(0, 1)))
        with open(data_path, "rb") as f:
            blob = f.read()
        with open(index_path, "rb") as f:
            idx = f.read()
        return blob, idx

    blob_f, idx_f = write()
    conf.FUSION_ENABLE.set(False)
    try:
        blob_u, idx_u = write()
    finally:
        conf.FUSION_ENABLE.set(True)
    assert blob_f == blob_u and idx_f == idx_u


def _agg_plan(data):
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.agg import AggExec, AggFunction, AggMode, GroupingExpr

    groupings = [GroupingExpr(col("l_returnflag"), "l_returnflag")]
    aggs = [AggFunction("sum", col("l_quantity"), "sum_qty"),
            AggFunction("count_star", None, "cnt")]
    scan = _scans(data, batch_rows=2048)["lineitem"]
    partial = AggExec(scan, AggMode.PARTIAL, groupings, aggs)
    return AggExec(partial, AggMode.FINAL, groupings, aggs)


def _write_once(plan_fn, partitioning_fn, boundaries=None):
    from blaze_tpu.parallel.shuffle import ShuffleWriterExec

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
    from blaze_tpu.exprs import col
    from blaze_tpu.parallel.shuffle import HashPartitioning

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
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.sort import SortField
    from blaze_tpu.parallel.shuffle import RangePartitioning

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


def test_scheduler_stage_dispatch_counters(data):
    """Per-stage dispatch observability flows through the scheduler
    MetricNode (root totals + per-stage children)."""
    from blaze_tpu.runtime.scheduler import run_stages, split_stages

    plan = build_query("q6", _scans(data, n_parts=2), 2)
    stages, manager = split_stages(plan)
    from blaze_tpu.runtime.metrics import MetricNode

    node = MetricNode()
    rows = 0
    for b in run_stages(stages, manager, metrics=node):
        rows += b.num_rows
    assert rows > 0
    root = node.metrics
    assert root.get("xla_dispatches") > 0
    assert root.get("fused_stage_len") > 0  # run_task fused the map side
    per_stage = [c.metrics.get("xla_dispatches") for c in node.children]
    assert sum(per_stage) == root.get("xla_dispatches")


# ------------------------- dense grouped update: engagement and bypass


def _agg_stream(key_batches, aggs=(("sum", "v"),), v_type=None):
    """PARTIAL agg over one partition of (k, v=3) batches, one per key
    list: ({key: [state values...]} summed over the emitted state rows,
    the dispatch tally)."""
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import AggExec, AggFunction, AggMode, GroupingExpr
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("k", DataType.int64()),
                     Field("v", v_type or DataType.int64())])
    batches = [batch_from_pydict({"k": list(keys), "v": [3] * len(keys)}, schema)
               for keys in key_batches]
    agg = AggExec(MemoryScanExec([batches], schema), AggMode.PARTIAL,
                  [GroupingExpr(col("k"), "k")],
                  [AggFunction(fn, col(c), f"a{i}") for i, (fn, c) in enumerate(aggs)])
    seen = {}
    with dispatch.capture() as tally:
        for b in agg.execute(0, TaskContext(0, 1)):
            d = batch_to_pydict(b)
            names = [n for n in d if n != "k"]
            for i, k in enumerate(d["k"]):
                got = seen.setdefault(k, [0] * len(names))
                for j, n in enumerate(names):
                    got[j] += d[n][i]
    return seen, tally


def test_q1_warm_updates_are_all_dense(data):
    """Warm q01 (4 groups, order-free aggregates): every fused grouped
    update takes the sort-free dense program — no rollback, no stall,
    no warm compile, and still one program a batch."""
    n_rows = len(data["lineitem"]["l_quantity"][0])
    n_batches = (n_rows + BATCH_ROWS - 1) // BATCH_ROWS
    _run(_optimized("q1", data))
    with dispatch.capture() as warm:
        _run(_optimized("q1", data))
    assert warm.get("agg_dense_updates", 0) == warm.get("agg_grouped_updates", 0) > 0, warm
    assert warm.get("fused_agg_rollbacks", 0) == 0, warm
    assert warm.get("fused_agg_stall_syncs", 0) == 0, warm
    assert warm.get("xla_compiles", 0) == 0, warm
    assert warm.get("xla_dispatches", 0) / n_batches <= DISPATCH_BUDGET, warm


def test_high_ndv_stream_never_takes_the_dense_update():
    seen, tally = _agg_stream([range(2000)] * 4)
    assert tally.get("agg_grouped_updates", 0) == 3, tally
    assert tally.get("agg_dense_updates", 0) == 0, tally
    assert len(seen) == 2000 and all(v == [12, 4] for v in seen.values())


def test_q3_through_the_scheduler_bypasses_the_dense_update(data):
    """q03's aggregates group by order key: thousands of groups after
    the seed, so its programs are the ones it had — none is the dense
    update, and none is compiled for it."""
    from blaze_tpu.runtime.scheduler import run_stages, split_stages

    labels = set()
    real = dispatch._oom_call

    def recording(fn, label, *a, **k):
        labels.add(label)
        return real(fn, label, *a, **k)

    dispatch._oom_call = recording
    try:
        with dispatch.capture() as tally:
            stages, manager = split_stages(build_query("q3", _scans(data, n_parts=2), 2))
            assert sum(b.num_rows for b in run_stages(stages, manager)) > 0
    finally:
        dispatch._oom_call = real
    assert tally.get("agg_grouped_updates", 0) > 0, tally
    assert tally.get("agg_dense_updates", 0) == 0, tally
    assert "agg_update" in labels and "agg_dense_update" not in labels, sorted(labels)


@pytest.mark.parametrize("key_batches,dense,rollbacks", [
    # a key that first appears in batch 3 of a 2-group stream: the
    # deferred check reads the miss as an overflow and rolls back
    # through the eager reduce+merge (batch 4, launched dense behind
    # it, is replayed); a stream that missed once stays on the sort
    # update, which takes a new key in without a rollback
    ([[1, 2] * 50, [2, 1] * 50, [1, 7, 2] * 30, [7, 1] * 40, [2, 7] * 40], 3, 1),
    # keys that arrive over time (input clustered by the group key):
    # one rollback for the stream, not one per new key
    ([[1] * 100, [1] * 100, [1, 2] * 50, [2, 3] * 50, [3, 4] * 50, [4, 5] * 50,
      [5, 6] * 50], 3, 1),
    # a stream that grows past the slots: batch 3 brings 20 new keys
    # (batch 4 is launched dense behind it, before the deferred check,
    # and replayed), the rollback proves 22 groups and dense stops
    ([[1, 2] * 50, [2, 1] * 50, list(range(1, 23)), [1, 2] * 50, list(range(1, 23)),
      [2, 1] * 50], 3, 1),
], ids=["miss_rolls_back", "clustered_keys_roll_back_once", "growth_past_slots_stops_dense"])
def test_dense_update_miss_is_the_overflow_rollback(key_batches, dense, rollbacks):
    seen, tally = _agg_stream(key_batches)
    assert tally.get("agg_grouped_updates", 0) == len(key_batches) - 1, tally
    assert tally.get("agg_dense_updates", 0) == dense, tally
    assert tally.get("fused_agg_rollbacks", 0) == rollbacks, tally
    assert tally.get("fused_agg_stall_syncs", 0) == 0, tally
    want = {}
    for keys in key_batches:
        for k in keys:
            want[k] = want.get(k, 0) + 3
    assert {k: v[0] for k, v in seen.items()} == want
    assert all(v[1] * 3 == v[0] for v in seen.values())  # the #nonnull counts


@pytest.mark.parametrize("aggs,v_type", [
    ((("stddev_samp", "v"),), None),
    ((("sum", "v"),), "float64"),
    ((("min", "v"), ("max", "v")), "float64"),
    ((("count", "v"), ("first", "v")), None),
], ids=["stddev_samp", "float_sum", "float_min_max", "first_beside_count"])
def test_order_sensitive_aggregate_keeps_the_sort_update(aggs, v_type):
    """Three groups, but an aggregate whose reduction depends on row
    order or association: the stream stays on the sort program."""
    from blaze_tpu.schema import DataType

    _, tally = _agg_stream([[1, 2, 3] * 40] * 4, aggs=aggs,
                           v_type=v_type and getattr(DataType, v_type)())
    assert tally.get("agg_grouped_updates", 0) == 3, tally
    assert tally.get("agg_dense_updates", 0) == 0, tally
