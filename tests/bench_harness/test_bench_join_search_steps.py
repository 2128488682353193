"""The reader of ``join_search_steps`` (PR 33): the candidate program's
loop steps a probe batch, and nothing where the program has no such
counter (the parent commit) or the window probed nothing."""

import pytest

from bench import run
from bench.metrics import join_search_steps


def _run(queries, counters):
    return {"queries": queries, "counters": counters, "plan_s": [], "trace": None,
            "memory_peak_bytes": None, "least_bytes": 1, "peak": {"hbm_bytes_per_s": 1.0}}


@pytest.mark.parametrize("counters,want", [
    ({"join_probe_n": 336, "join_search_steps": 1008}, 3.0),
    ({"join_probe_n": 1936, "join_search_steps": 6292}, 3.25),  # small maps beside the hot one
    ({"join_probe_n": 28, "join_search_steps": 0}, 0.0),  # every map empty: no step, still a reading
])
def test_reader_returns_the_mean_steps_a_probe(counters, want):
    assert join_search_steps.read(_run(11, counters)) == pytest.approx(want)


@pytest.mark.parametrize("queries,counters", [
    (11, {"join_probe_n": 1936, "join_probe_ns": 10**9}),  # the parent: no such counter
    (11, {"xla_dispatches": 1111}),  # no join in the query
    (11, {"join_probe_n": 0, "join_search_steps": 0}),
    (0, {"join_probe_n": 28, "join_search_steps": 84}),  # no query completed
])
def test_reader_returns_nothing_without_the_counter_or_probes(queries, counters):
    assert join_search_steps.read(_run(queries, counters)) is None


def test_search_steps_are_read_in_the_join_cells_only():
    manifest, *_ = run.resolve("tpch_q03_sf0.5")
    entry = manifest["per_layer"][-1]
    assert entry == {"name": "join_search_steps", "unit": "count", "better": "lower",
                     "source": "program_counter", "layer": "operators", "moves": "query_s",
                     "workloads": ["tpcds_q07_sf1", "tpch_q03_sf0.5"]}
    for cell in ("tpch_q06_sf1", "tpch_q01_sf1", "tpch_q03_sf0.5", "tpcds_q07_sf1"):
        assert ("join_search_steps" in run.metric_readers(manifest, cell)) == (
            cell in entry["workloads"])
