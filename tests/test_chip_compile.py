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


def _sorted_lookup(n):
    # at the table limit the kernel advertises
    return (pallas_ops.sorted_lookup,
            (_shape(pallas_ops.SORTED_LOOKUP_MAX_TABLE, jnp.uint64),
             _shape(n, jnp.uint64)))


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize(
    "kernel", [_murmur3, _histogram, _group_sums, _sorted_lookup],
    ids=["murmur3_pids", "pid_histogram", "fused_group_sums",
         "sorted_lookup_max_table"])
def test_pallas_kernel_compiles_for_the_chip(one_chip, kernel, rows):
    assert not pallas_ops._interpret()
    fn, shapes = kernel(rows)
    compiled = _compile(fn, one_chip, *shapes)
    # the Mosaic kernel itself is in the program, not an interpretation
    assert "tpu_custom_call" in compiled.as_text()


def test_sorted_lookup_over_its_limit_is_a_compiler_refusal(one_chip):
    """Twice the advertised table does not fit scoped VMEM.  The
    refusal is RESOURCE_EXHAUSTED by status — and must NOT read as
    device OOM, or the ladder would spill, halve and fall to the eager
    rung around a kernel that cannot be built."""
    from blaze_tpu.runtime.oom import is_resource_exhausted

    with pytest.raises(Exception, match="vmem") as refused:
        _compile(pallas_ops.sorted_lookup, one_chip,
                 _shape(2 * pallas_ops.SORTED_LOOKUP_MAX_TABLE, jnp.uint64),
                 _shape(ROWS[0], jnp.uint64))
    assert "RESOURCE_EXHAUSTED" in str(refused.value)
    assert not is_resource_exhausted(refused.value)


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
    from blaze_tpu.runtime import dispatch
    from blaze_tpu.runtime.scheduler import run_stages, split_stages
    from blaze_tpu.tpch import TPCH_SCHEMAS, build_query
    from blaze_tpu.tpch.datagen import generate_all, table_to_batches

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
    dispatch._oom_call = recording
    try:
        for name, q, tables in (("q6", "q6", scans), ("q1", "q1", scans), ("q3", "q3", scans),
                                ("q1_sort_update", "q1", many_groups)):
            before = set(seen)
            stages, manager = split_stages(build_query(q, tables, 1))
            assert sum(b.num_rows for b in run_stages(stages, manager)) > 0
            out[name] = [seen[k] for k in seen if k not in before]
    finally:
        dispatch._oom_call = real
    return out


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
    the scheduler path really launched — x64 and all.  (The q01 sort
    update that merged at accumulator + batch capacity crashed this
    compiler; interpret mode and the CPU never noticed.)"""
    found = programs[query]
    assert labels <= {p[0] for p in found}, sorted({p[0] for p in found})
    widest = 0
    for label, fn, args, kwargs in found:
        _compile(fn, one_chip, *args, **kwargs)
        widest = max([widest] + [
            x.shape[0] for x in jax.tree_util.tree_leaves((args, kwargs))
            if isinstance(x, jax.ShapeDtypeStruct) and x.shape])
    assert widest == CAPACITY  # the scan-side program ran at full capacity
