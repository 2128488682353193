"""Distributed differential tests: TPC-H q1/q3/q6 end-to-end over the
8-virtual-device CPU mesh through the REAL exchange paths —

1. the ICI fast path (``lax.all_to_all`` over a ``jax.sharding.Mesh``
   via IciShuffleExchangeExec), and
2. the LocalShuffleManager file path under a capped memory budget
   (shuffle spills forced),

both validated against the numpy oracles.  This is the repo's analogue
of the reference's pseudo-distributed testenv (dev/testenv/).
"""

import numpy as np
import pytest

from blaze_tpu import conf
from blaze_tpu.batch import batch_to_pydict
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.parallel.ici import use_ici_exchanges
from blaze_tpu.parallel.mesh import make_mesh
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.runtime.memmgr import MemManager
from blaze_tpu.tpch import TPCH_SCHEMAS, build_query
from blaze_tpu.tpch import oracle as O
from blaze_tpu.tpch.datagen import generate_all, table_to_batches

pytestmark = pytest.mark.slow

SCALE = 0.002
N_PARTS = 8  # == mesh size


@pytest.fixture(scope="module")
def data():
    return generate_all(SCALE)


def _scans(data):
    return {
        name: MemoryScanExec(
            table_to_batches(data[name], TPCH_SCHEMAS[name], N_PARTS, batch_rows=2048),
            TPCH_SCHEMAS[name],
        )
        for name in TPCH_SCHEMAS
    }


def run(plan):
    out = {f.name: [] for f in plan.schema.fields}
    for p in range(plan.num_partitions()):
        for b in plan.execute(p, TaskContext(p, plan.num_partitions())):
            d = batch_to_pydict(b)
            for k in out:
                out[k].extend(d[k])
    return out


def _rows(d, fields):
    return sorted(zip(*[d[f] for f in fields]), key=repr)


@pytest.mark.parametrize("q", ["q1", "q6", "q3"])
def test_ici_mesh_matches_file_shuffle_and_oracle(data, q):
    mesh = make_mesh(8)
    file_path = run(build_query(q, _scans(data), N_PARTS))
    ici_plan = use_ici_exchanges(build_query(q, _scans(data), N_PARTS), mesh)
    ici_path = run(ici_plan)
    fields = list(file_path.keys())
    assert _rows(ici_path, fields) == _rows(file_path, fields)


def test_q1_ici_against_oracle(data):
    mesh = make_mesh(8)
    got = run(use_ici_exchanges(build_query("q1", _scans(data), N_PARTS), mesh))
    exp = O.oracle_q1(data)
    keys = list(zip(got["l_returnflag"], got["l_linestatus"]))
    assert set(keys) == set(exp)
    for i, k in enumerate(keys):
        e = exp[k]
        for m in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge", "count_order"):
            assert got[m][i] == e[m], (k, m)


def test_q6_ici_against_oracle(data):
    mesh = make_mesh(8)
    got = run(use_ici_exchanges(build_query("q6", _scans(data), N_PARTS), mesh))
    assert got["revenue"] == [O.oracle_q6(data)]


def test_q6_file_shuffle_spill_path(data):
    """The LocalShuffleManager path under a tiny budget: spills fire
    and the result still matches the oracle."""
    try:
        MemManager._global = None
        MemManager.init(50_000)
        plan = build_query("q6", _scans(data), N_PARTS)
        got = run(plan)
        assert got["revenue"] == [O.oracle_q6(data)]
    finally:
        MemManager._global = None
        MemManager.init(int(conf.HOST_SPILL_BUDGET.get()))
