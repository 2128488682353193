"""Compile the chip path for the chip, without the chip.

The TPU compiler is installed here and compiles for a DESCRIBED
``v5e:2x2`` topology: what it refuses here (a kernel over its VMEM, an
op Mosaic cannot legalize, an x64 leak into a Pallas trace) it would
refuse on the machine with the chip, and interpret mode shows none of
it.  Nothing runs, so nothing here is a result or a time.

This is the ONE file that describes the topology, and it does so inside
a module-scoped fixture — never at import, never in ``conftest.py``:
only one process at a time may load the TPU's library, and every xdist
worker imports every test file.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from blaze_tpu.kernels import pallas_ops

ROWS = (8192, 1 << 20)
CAPACITY = 65536  # the CLI's / chip_smoke.py's batch capacity


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler, nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the first described chip, with the persistent
    compilation cache off around every compile of this module: an
    entry compiled for an unattached chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


@pytest.fixture
def as_chip(monkeypatch):
    """Code that asks ``jax.default_backend()`` sees the CPU here and
    would trace its CPU branch (``exprs/hash.f64_raw_bits``): steer it
    in the test, and drop traces cached under the CPU answer."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compile(fn, one_chip, *args, **kwargs):
    """Lower + compile ``fn`` for the described chip; the shape leaves
    of ``args`` are placed on it, everything else passes as is."""

    def place(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        return x

    args, kwargs = jax.tree_util.tree_map(place, (args, kwargs))
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jitted.lower(*args, **kwargs).compile()


def _shape(n, dtype):
    return jax.ShapeDtypeStruct((n,), dtype)


# ------------------------------------------------------ Pallas kernels

def _murmur3(n):
    return (lambda lo, hi, v: pallas_ops.murmur3_pids([lo, hi], [2], [v], 200),
            (_shape(n, jnp.uint32), _shape(n, jnp.uint32),
             _shape(n, jnp.bool_)))


def _histogram(n):
    return (lambda p: pallas_ops.pid_histogram(p, 200),
            (_shape(n, jnp.int32),))


def _group_sums(n):
    return (lambda g, a, b: pallas_ops.fused_group_sums(g, [a, b], 4),
            (_shape(n, jnp.int32), _shape(n, jnp.float32),
             _shape(n, jnp.float32)))


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize(
    "kernel", [_murmur3, _histogram, _group_sums],
    ids=["murmur3_pids", "pid_histogram", "fused_group_sums"])
def test_pallas_kernel_compiles_for_the_chip(one_chip, kernel, rows):
    assert not pallas_ops._interpret()
    fn, shapes = kernel(rows)
    compiled = _compile(fn, one_chip, *shapes)
    # the Mosaic kernel itself is in the program, not an interpretation
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("key_types", [("int64",), ("int32", "int64", "date32")])
def test_pallas_pid_kernel_inside_the_x64_shuffle_jit(one_chip, key_types):
    """``murmur3_pids`` as the shuffle writer builds it: inside an x64
    ``jax.jit`` over expression lowering and the int64 word-plane
    split (parallel/shuffle.py ``_build_pid_kernels``)."""
    from blaze_tpu.batch import Column
    from blaze_tpu.exprs import col
    from blaze_tpu.parallel.shuffle import _build_pid_kernels
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field(f"k{i}", getattr(DataType, t)())
                     for i, t in enumerate(key_types)])
    cols = tuple(
        Column(f.dtype, _shape(CAPACITY, f.dtype.np_dtype),
               _shape(CAPACITY, jnp.bool_))
        for f in schema.fields)
    _, hash_pids_pallas = _build_pid_kernels(
        schema, [col(f.name) for f in schema.fields], 200)
    compiled = _compile(hash_pids_pallas, one_chip, cols, CAPACITY)
    assert "tpu_custom_call" in compiled.as_text()


# ----------------------------------------------- the engine's programs

def test_double_bits_trace_the_chip_branch(one_chip, as_chip, monkeypatch):
    """``exprs/hash.f64_raw_bits`` takes an arithmetic decomposition on
    the TPU (no 64-bit bitcast there) that no CPU test executes; every
    hashed, sorted or grouped double goes through it on the chip."""
    from blaze_tpu.batch import Column
    from blaze_tpu.exprs import hash as H
    from blaze_tpu.schema import DataType

    taken = []
    real = H._f64_bits
    monkeypatch.setattr(H, "_f64_bits",
                        lambda d: taken.append(1) or real(d))

    def pids(data, validity):
        c = Column(DataType.float64(), data, validity)
        return H.pmod(H.murmur3_columns([c]), 200)

    _compile(pids, one_chip, _shape(CAPACITY, jnp.float64),
             _shape(CAPACITY, jnp.bool_))
    assert taken, "the TPU branch of f64_raw_bits was not traced"


@contextlib.contextmanager
def _launches():
    """Every jitted program launched inside, captured at the dispatch
    seam with its arrays as shapes: {key: (label, fn, args, kwargs)}."""
    from blaze_tpu.runtime import dispatch

    def spec(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    seen = {}
    real = dispatch._oom_call

    def recording(fn, label, *a, **k):
        out = real(fn, label, *a, **k)
        if hasattr(fn, "lower"):
            shapes = jax.tree_util.tree_map(spec, (a, k))
            seen.setdefault((id(fn), str(shapes)), (label, fn) + shapes)
        return out

    dispatch._oom_call = recording
    try:
        yield seen
    finally:
        dispatch._oom_call = real


@pytest.fixture(scope="module")
def programs():
    """Every jitted program q06, q01 and q03 launch through the
    scheduler path (TaskDefinition bytes per stage) at the CLI's batch
    capacity, captured at the dispatch seam while the queries run here
    on the CPU at scale 0.02 — two lineitem batches, so the fused agg
    UPDATE programs (accumulator + batch) are among them:
    {query: [(label, fn, args, kwargs)]}.  q01's four groups take the
    dense update; ``q1_sort_update`` is q01 over a lineitem of 20 line
    statuses, 60 groups: the sort update every stream of more groups
    than the dense slots launches, at q01's own shapes."""
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.runtime.scheduler import run_stages, split_stages
    from blaze_tpu.tpch import TPCH_SCHEMAS, build_query
    from blaze_tpu.tpch.datagen import generate_all, table_to_batches

    data = generate_all(0.02)
    scans = {
        name: MemoryScanExec(
            table_to_batches(data[name], TPCH_SCHEMAS[name], 1,
                             batch_rows=CAPACITY),
            TPCH_SCHEMAS[name])
        for name in TPCH_SCHEMAS
    }
    status, lengths = data["lineitem"]["l_linestatus"]
    status = status.copy()
    status[:, 0] = ord("A") + np.arange(len(status)) % 20
    many = dict(data["lineitem"], l_linestatus=(status, lengths))
    many_groups = dict(scans, lineitem=MemoryScanExec(
        table_to_batches(many, TPCH_SCHEMAS["lineitem"], 1, batch_rows=CAPACITY),
        TPCH_SCHEMAS["lineitem"]))
    out = {}
    with _launches() as seen:
        for name, q, tables in (("q6", "q6", scans), ("q1", "q1", scans), ("q3", "q3", scans),
                                ("q1_sort_update", "q1", many_groups)):
            before = set(seen)
            stages, manager = split_stages(build_query(q, tables, 1))
            assert sum(b.num_rows for b in run_stages(stages, manager)) > 0
            out[name] = [seen[k] for k in seen if k not in before]
    return out


#: programs whose row compaction and partition counts lower to no scatter
ROW_BOOKKEEPING = {"filter", "fused_stage", "shuffle_pid_sort", "fused_shuffle_write"}


@pytest.mark.parametrize("query,labels", [
    ("q6", {"agg", "agg_update"}),
    ("q1", {"agg", "agg_dense_update", "sort"}),
    ("q3", {"filter", "join_build_kernel", "shuffle_pid_sort", "agg",
            "sort"}),
    ("q1_sort_update", {"agg_update"}),
])
def test_query_programs_compile_for_the_chip(one_chip, programs, as_chip,
                                             query, labels):
    """The fused q06 stage, the q01 dense agg update (4 groups: the
    sort-free program at batch capacity 65,536) and q03's
    filter/join-build/shuffle-write/sort programs, each at the shapes
    the scheduler path really launched — x64 and all; the filter and
    shuffle-write programs among them with no scatter.  (The q01 sort
    update that merged at accumulator + batch capacity crashed this
    compiler; interpret mode and the CPU never noticed.)"""
    found = programs[query]
    assert labels <= {p[0] for p in found}, sorted({p[0] for p in found})
    widest = 0
    for label, fn, args, kwargs in found:
        compiled = _compile(fn, one_chip, *args, **kwargs)
        if label in ROW_BOOKKEEPING:
            # kept rows by a sort, partition counts by a search: the
            # TPU runs a scatter nearly serially, whatever the mask holds
            assert "scatter(" not in compiled.as_text(), label
        widest = max([widest] + [
            x.shape[0] for x in jax.tree_util.tree_leaves((args, kwargs))
            if isinstance(x, jax.ShapeDtypeStruct) and x.shape])
    assert widest == CAPACITY  # the scan-side program ran at full capacity


# ------------------------- operators no benchmark query launches

SMALLEST = 1000  # rows under the smallest capacity bucket (1,024)
SORTING = 8192   # a sort compiles in time linear in rows x operands


def _one_batch(schema, rows):
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.ops import MemoryScanExec

    return MemoryScanExec([[batch_from_pydict(rows, schema)]], schema)


def _window_plan():
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.sort import SortField
    from blaze_tpu.ops.window import WindowExec, WindowFunction
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("g", DataType.int64()), Field("v", DataType.int64())])
    scan = _one_batch(schema, {"g": sorted(i % 5 for i in range(SORTING)),
                               "v": [i * 7 % 13 for i in range(SORTING)]})
    return WindowExec(scan, [WindowFunction("row_number", "rn")],
                      [col("g")], [SortField(col("v"), True, True)])


def _sort_merge_join_plan():
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import SortExec, SortField
    from blaze_tpu.ops.joins import SortMergeJoinExec
    from blaze_tpu.ops.joins.core import JoinType
    from blaze_tpu.schema import DataType, Field, Schema

    left = Schema([Field("k", DataType.int64()), Field("a", DataType.int32())])
    right = Schema([Field("sk", DataType.int64()), Field("b", DataType.int32())])
    n = SORTING
    l_scan = _one_batch(left, {"k": [i * 3 % n for i in range(n)],
                               "a": list(range(n))})
    r_scan = _one_batch(right, {"sk": [i * 5 % n for i in range(n // 2)],
                                "b": list(range(n // 2))})
    return SortMergeJoinExec(
        SortExec(l_scan, [SortField(col("k"))]),
        SortExec(r_scan, [SortField(col("sk"))]),
        [col("k")], [col("sk")], JoinType.INNER)


def _expand_plan():
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.ir import BinOp, Lit
    from blaze_tpu.ops.expand import ExpandExec
    from blaze_tpu.ops.filter import FilterExec
    from blaze_tpu.schema import DataType, Field, Schema

    i64 = DataType.int64()
    scan = _one_batch(Schema([Field("k", i64)]), {"k": list(range(SMALLEST))})
    e = ExpandExec(
        scan,
        [[col("k"), Lit(0, i64)],
         [BinOp("*", col("k"), Lit(2, i64)), Lit(1, i64)]],
        ["v", "tag"])
    return FilterExec(e, BinOp(">", col("v"), Lit(10, i64)))


def _generate_plan():
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.ir import Alias, BinOp, Lit
    from blaze_tpu.ops.filter import FilterExec
    from blaze_tpu.ops.generate import GenerateExec, NativeGenerator
    from blaze_tpu.ops.project import ProjectExec
    from blaze_tpu.schema import DataType, Field, Schema

    i64 = DataType.int64()
    schema = Schema([Field("k", i64), Field("xs", DataType.array(i64, 4))])
    scan = _one_batch(schema, {
        "k": list(range(SMALLEST)),
        "xs": [[i, i + 1, i + 2][: (i % 4)] or None for i in range(SMALLEST)]})
    g = GenerateExec(scan, NativeGenerator("explode", col("xs")), [col("xs")])
    f = FilterExec(g, BinOp(">", col("col"), Lit(5, i64)))
    return ProjectExec(
        f, [col("k"), Alias(BinOp("+", col("col"), Lit(1, i64)), "c1")],
        ["k", "c1"])


def _launch_plan(plan_fn):
    from blaze_tpu.ops.fusion import optimize_plan
    from blaze_tpu.runtime.context import TaskContext

    plan = optimize_plan(plan_fn())
    with _launches() as seen:
        assert sum(b.num_rows for b in plan.execute(0, TaskContext(0, 1))) > 0
    return list(seen.values())


Q7_HOT_MAP_ROWS = 27_440  # q7's filtered customer_demographics build side


def _launch_q7_hot_probe():
    """One full-size probe batch (65,536 rows, NULL keys among them)
    against a map of q7's hot size, through ``Joiner.probe_batch``."""
    from blaze_tpu.batch import batch_from_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.joins.core import Joiner, JoinerState, JoinType
    from blaze_tpu.schema import DataType, Field, Schema

    rng = np.random.default_rng(7)
    build = Schema([Field("k", DataType.int64()), Field("b", DataType.int32())])
    probe = Schema([Field("k", DataType.int64()), Field("p", DataType.int32())])
    joiner = Joiner(probe, build, [col("k")], [col("k")], JoinType.INNER, True)
    keys = rng.permutation(1_920_800)[:Q7_HOT_MAP_ROWS]
    jmap = joiner.build_map(batch_from_pydict(
        {"k": [int(k) for k in keys], "b": list(range(Q7_HOT_MAP_ROWS))}, build))
    probes = rng.integers(0, 1_920_800, CAPACITY)
    batch = batch_from_pydict(
        {"k": [None if i % 50 == 0 else int(k) for i, k in enumerate(probes)],
         "p": list(range(CAPACITY))}, probe)
    with _launches() as seen:
        assert joiner.probe_batch(jmap, batch, JoinerState()).num_rows > 0
    return list(seen.values())


@pytest.mark.parametrize("launch,labels,widest", [
    (lambda: _launch_plan(_window_plan), {"window"}, SORTING),
    (lambda: _launch_plan(_sort_merge_join_plan),
     {"sort", "join_build_kernel", "join_candidate", "join_probe"}, SORTING),
    (lambda: _launch_plan(_expand_plan), {"fused_stage"}, 1024),
    (lambda: _launch_plan(_generate_plan), {"fused_stage"}, 1024),
    (_launch_q7_hot_probe, {"join_candidate", "join_probe"}, CAPACITY),
], ids=["window", "sort_merge_join", "expand", "generate", "join_q7_hot_map"])
def test_operator_programs_compile_for_the_chip(one_chip, as_chip, launch,
                                                labels, widest):
    """Window, sort-merge join, expand and generate — operators none of
    q06/q01/q03 plans — and the Joiner's candidate and probe programs
    at the shape q7 launches most (a 27,440-row map in its 32,768
    bucket, 65,536 probe rows): run here on the CPU, each program
    captured at the dispatch seam and compiled for the chip at the
    shapes it really launched."""
    found = launch()
    assert labels <= {p[0] for p in found}, sorted({p[0] for p in found})
    seen_widest = 0
    for label, fn, args, kwargs in found:
        _compile(fn, one_chip, *args, **kwargs)
        # batch capacities are powers of two; a JoinMap's bucket
        # offsets (2**b + 1 slots) are no capacity
        seen_widest = max([seen_widest] + [
            x.shape[0] for x in jax.tree_util.tree_leaves((args, kwargs))
            if isinstance(x, jax.ShapeDtypeStruct) and x.shape
            and x.shape[0] & (x.shape[0] - 1) == 0])
    assert seen_widest == widest
