"""The reader of ``agg_dense_share`` (PR 28): the dense updates as a
share of the fused grouped updates, and nothing where the program has
no such counter (the parent commit) or launched no grouped update."""

import pytest

from bench import run
from bench.metrics import agg_dense_share


def _run(queries, counters):
    return {"queries": queries, "counters": counters, "plan_s": [], "trace": None,
            "memory_peak_bytes": None, "least_bytes": 1, "peak": {"hbm_bytes_per_s": 1.0}}


@pytest.mark.parametrize("counters,want", [
    ({"agg_grouped_updates": 528, "agg_dense_updates": 528}, 100.0),
    ({"agg_grouped_updates": 92, "agg_dense_updates": 69}, 75.0),
    ({"agg_grouped_updates": 96, "xla_dispatches": 2104}, 0.0),  # high NDV: none dense
])
def test_reader_returns_the_dense_share(counters, want):
    assert agg_dense_share.read(_run(6, counters)) == pytest.approx(want)


@pytest.mark.parametrize("queries,counters", [
    (5, {"xla_dispatches": 500, "fused_agg_rollbacks": 3}),  # the parent: no such counter
    (5, {"agg_grouped_updates": 0, "agg_dense_updates": 0}),
    (0, {"agg_grouped_updates": 92, "agg_dense_updates": 92}),  # no query completed
])
def test_reader_returns_nothing_without_grouped_updates(queries, counters):
    assert agg_dense_share.read(_run(queries, counters)) is None


def test_dense_share_is_read_in_the_grouped_cells_only():
    manifest, *_ = run.resolve("tpch_q01_sf1")
    for cell in ("tpch_q06_sf1", "tpch_q01_sf1", "tpch_q03_sf0.5"):
        assert ("agg_dense_share" in run.metric_readers(manifest, cell)) == (cell != "tpch_q06_sf1")
