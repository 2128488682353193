"""The readers of the program's span counters (PR 27): each returns its
counter per completed query, in the manifest's unit, and nothing where
the program has no such counter (a parent commit from before the span)."""

import importlib

import pytest

from bench import run

#: metric -> (counter it reads, counter value, queries, the stated quotient)
CASES = {
    "task_decode_ms": ("task_decode_ns", 84_000_000, 4, 21.0),
    "scan_stage_ms": ("scan_stage_ns", 9_000_000, 3, 3.0),
    "h2d_mb": ("h2d_bytes", 336_000_000, 2, 168.0),
    "launch_ms": ("launch_ns", 5_000_000, 10, 0.5),
    "device_read_ms": ("device_read_ns", 1_060_000_000, 2, 530.0),
    "exchange_write_ms": ("exchange_write_ns", 7_000_000, 7, 1.0),
    "exchange_read_ms": ("exchange_read_ns", 12_000_000, 8, 1.5),
    "shuffle_mb": ("shuffle_bytes_written", 25_000_000, 8, 3.125),
}


def _run(queries, counters):
    return {"queries": queries, "counters": counters, "plan_s": [], "trace": None,
            "memory_peak_bytes": None, "least_bytes": 1, "peak": {"hbm_bytes_per_s": 1.0}}


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_returns_its_counter_per_query(metric):
    counter, value, queries, want = CASES[metric]
    reader = importlib.import_module("bench.metrics." + metric)
    assert reader.read(_run(queries, {counter: value})) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_returns_nothing_without_its_counter(metric):
    """The parent's program has no such counter: no metric, not a 0.
    Nor where no query completed."""
    counter, value, _, _ = CASES[metric]
    reader = importlib.import_module("bench.metrics." + metric)
    others = {c: v for c, v, _, _ in CASES.values() if c != counter}
    assert reader.read(_run(5, dict(others, xla_dispatches=500))) is None
    assert reader.read(_run(0, {counter: value})) is None


def test_exchange_metrics_are_read_in_the_join_cell_only():
    manifest, *_ = run.resolve("tpch_q03_sf0.5")
    exchange = {"exchange_write_ms", "exchange_read_ms", "shuffle_mb"}
    everywhere = {"task_decode_ms", "scan_stage_ms", "h2d_mb", "launch_ms", "device_read_ms"}
    for cell in ("tpch_q06_sf1", "tpch_q01_sf1", "tpch_q03_sf0.5"):
        readers = set(run.metric_readers(manifest, cell))
        assert everywhere <= readers
        assert (exchange <= readers) == (cell == "tpch_q03_sf0.5")
        assert exchange <= readers or not (exchange & readers)
