"""Row compaction and exchange partition counts against numpy.

``ops/filter.kept_indices`` / ``compact_columns`` (every filter, join
probe, sort-path aggregate and ICI bucket compacts through them) and
``parallel/shuffle._sort_by_pid_body`` (every exchange writer's pid
sort): their results against a numpy reference over masks from empty to
full at capacities 1 to 65,536, and their traces free of any scatter —
the TPU runs a scatter nearly serially, whatever the mask holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.batch import Column
from blaze_tpu.ops.filter import compact_columns, kept_indices
from blaze_tpu.parallel.shuffle import _sort_by_pid_body
from blaze_tpu.schema import DataType, Field

CAPS = (1, 1024, 65536)
MASKS = ("all", "none", "one", "alternating", "p10", "p50", "p90")


def _mask(kind, cap, rng):
    if kind == "all":
        return np.ones(cap, np.bool_)
    if kind == "none":
        return np.zeros(cap, np.bool_)
    if kind == "one":
        m = np.zeros(cap, np.bool_)
        m[cap // 3] = True
        return m
    if kind == "alternating":
        return np.arange(cap) % 2 == 0
    return rng.random(cap) < int(kind[1:]) / 100


def _columns(cap, rng):
    """One column of each layout: flat with NULLs, string (data + byte
    lengths), array (row lengths + a child with an element axis) and
    struct (children with the row axis alone)."""
    valid = lambda *shape: rng.random(shape) < 0.8  # noqa: E731
    i64 = Column(DataType.int64(), rng.integers(-1 << 40, 1 << 40, cap), valid(cap))
    s = Column(DataType.string(8), rng.integers(0, 256, (cap, 8)).astype(np.uint8),
               valid(cap), rng.integers(0, 9, cap).astype(np.int32))
    elem = Column(DataType.int64(), rng.integers(0, 1000, (cap, 3)), valid(cap, 3))
    arr = Column(DataType.array(DataType.int64(), 3), None, valid(cap),
                 rng.integers(0, 4, cap).astype(np.int32), (elem,))
    st_type = DataType.struct([Field("a", DataType.int32()), Field("b", DataType.float64())])
    st = Column(st_type, None, valid(cap), None, (
        Column(DataType.int32(), rng.integers(0, 100, cap).astype(np.int32), valid(cap)),
        Column(DataType.float64(), rng.random(cap), valid(cap))))
    host = (i64, s, arr, st)
    return host, jax.tree_util.tree_map(jnp.asarray, host)


def _leaves(c):
    return [x for x in (c.data, c.validity, c.lengths) if x is not None] + [
        x for k in c.children or () for x in _leaves(k)]


@pytest.mark.parametrize("cap", CAPS)
@pytest.mark.parametrize("kind", MASKS)
def test_compaction_matches_numpy(kind, cap):
    rng = np.random.default_rng(cap * 31 + MASKS.index(kind))
    keep = _mask(kind, cap, rng)
    kept = np.flatnonzero(keep)
    n = len(kept)

    idx, count = jax.jit(kept_indices)(jnp.asarray(keep))
    assert idx.dtype == jnp.int32 and count.dtype == jnp.int32
    assert int(count) == n
    np.testing.assert_array_equal(np.asarray(idx)[:n], kept)  # ascending: row order holds
    np.testing.assert_array_equal(np.asarray(idx)[n:], 0)

    host, dev = _columns(cap, rng)
    out, count = jax.jit(compact_columns)(dev, jnp.asarray(keep))
    assert count.dtype == jnp.int32 and int(count) == n
    for h, o in zip(host, out):
        assert o.dtype == h.dtype
        # every buffer, nested children's included: the kept rows, in order
        for hb, ob in zip(_leaves(h), _leaves(o)):
            np.testing.assert_array_equal(np.asarray(ob)[:n], np.asarray(hb)[kept])
        # the rows past the count are padding: invalid, no elements
        assert not np.asarray(o.validity)[n:].any()
        if o.lengths is not None:
            np.testing.assert_array_equal(np.asarray(o.lengths)[n:], 0)


@pytest.mark.parametrize("cap", CAPS)
@pytest.mark.parametrize("n_out", (1, 4, 200))
@pytest.mark.parametrize("live", ("none", "some", "all"))
def test_pid_sort_counts_match_bincount(cap, n_out, live):
    rng = np.random.default_rng(cap + n_out)
    num_rows = {"none": 0, "some": cap // 2 + 1 if cap > 1 else 1, "all": cap}[live]
    pids = rng.integers(0, n_out, cap).astype(np.int32)
    vals = Column(DataType.int64(), np.arange(cap, dtype=np.int64), np.ones(cap, np.bool_))
    sort = jax.jit(_sort_by_pid_body, static_argnames=("n_out",))
    (sorted_vals,), counts, sidx = sort((jax.tree_util.tree_map(jnp.asarray, vals),),
                                        jnp.asarray(pids), n_out=n_out, num_rows=num_rows)
    assert counts.dtype == jnp.int64
    # the padding rows past num_rows count nowhere
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.bincount(pids[:num_rows], minlength=n_out))
    # the live rows sorted by pid, stable within a partition, then the padding
    order = np.argsort(np.where(np.arange(cap) < num_rows, pids, n_out), kind="stable")
    np.testing.assert_array_equal(np.asarray(sidx), order)
    np.testing.assert_array_equal(np.asarray(sorted_vals.data), order)


def _primitives(jaxpr):
    """Every primitive of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("program", ("kept_indices", "compact_columns", "sort_by_pid"))
def test_row_bookkeeping_traces_no_scatter(program):
    cap = 65536
    keep = jax.ShapeDtypeStruct((cap,), jnp.bool_)
    cols = (Column(DataType.int64(), jax.ShapeDtypeStruct((cap,), jnp.int64), keep),
            Column(DataType.string(8), jax.ShapeDtypeStruct((cap, 8), jnp.uint8), keep,
                   jax.ShapeDtypeStruct((cap,), jnp.int32)))
    fn, args = {
        "kept_indices": (kept_indices, (keep,)),
        "compact_columns": (compact_columns, (cols, keep)),
        "sort_by_pid": (lambda c, p, n: _sort_by_pid_body(c, p, 4, n),
                        (cols, jax.ShapeDtypeStruct((cap,), jnp.int32), jnp.int32(cap))),
    }[program]
    prims = set(_primitives(jax.make_jaxpr(fn)(*args).jaxpr))
    assert "sort" in prims
    assert not {p for p in prims if p.startswith("scatter")}, sorted(prims)
