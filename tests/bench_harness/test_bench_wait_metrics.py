"""The readers of the program's wait spans (PR 38): who waited for whom
at the program's two hand-off queues, and what the scan's slicing costs.
Each returns its quotient per completed query, nothing where the
program has no marker counter (a parent commit from before the spans),
and 0.0 — not nothing — where the marker is there and nothing waited."""

import importlib

import pytest

from bench import run

FILE_CELLS = ["tpch_q06_sf1_parquet", "tpch_q01_sf1_parquet"]

#: metric -> (marker counter, counters of a run that waited, queries, the stated quotient)
CASES = {
    "scan_wait_ms": ("pipeline_items", {"pipeline_items": 184, "pipeline_wait_ns": 1_800_000_000,
                                        "pipeline_wait_n": 150}, 2, 900.0),
    "scan_backpressure_ms": ("pipeline_items", {"pipeline_items": 184, "pipeline_full_ns": 30_000_000,
                                                "pipeline_full_n": 9}, 2, 15.0),
    "scan_slice_ms": ("scan_slice_ns", {"scan_slice_ns": 600_000_000, "scan_slice_n": 276}, 3, 200.0),
    "exchange_backpressure_ms": ("inserter_items", {"inserter_items": 88, "inserter_full_ns": 700_000_000,
                                                    "inserter_drain_ns": 100_000_000}, 4, 200.0),
}

#: metric -> what is in the counters where its layer ran and nothing waited
QUIET = {
    "scan_wait_ms": {"pipeline_items": 92, "pipeline_producer_ns": 5},
    "scan_backpressure_ms": {"pipeline_items": 92, "pipeline_wait_ns": 7},
    "exchange_backpressure_ms": {"inserter_items": 8},
}


def _run(queries, counters):
    return {"queries": queries, "counters": counters, "plan_s": [], "trace": None,
            "memory_peak_bytes": None, "least_bytes": 1, "peak": {"hbm_bytes_per_s": 1.0}}


def _reader(metric):
    return importlib.import_module("bench.metrics." + metric)


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_returns_its_quotient_per_query(metric):
    _, counters, queries, want = CASES[metric]
    assert _reader(metric).read(_run(queries, counters)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_returns_nothing_without_its_marker_counter(metric):
    """The parent's program has no such counter: no metric, not a 0 —
    though every other counter is there.  Nor where no query completed."""
    marker, counters, _, _ = CASES[metric]
    parent = {"launch_ns": 5, "launch_n": 1, "scan_stage_ns": 5, "scan_stage_n": 1,
              "exchange_write_ns": 5, "device_read_ns": 5, "xla_dispatches": 500}
    assert marker not in parent
    assert _reader(metric).read(_run(5, parent)) is None
    assert _reader(metric).read(_run(0, counters)) is None


@pytest.mark.parametrize("metric", sorted(QUIET))
def test_reader_returns_zero_where_its_layer_ran_and_nothing_waited(metric):
    got = _reader(metric).read(_run(3, QUIET[metric]))
    assert got == 0.0 and got is not None


def test_the_manifest_lists_the_four_by_name_where_the_program_can_report_them():
    manifest, *_ = run.resolve("tpch_q06_sf1")
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in CASES:
        entry = by_name[name]
        assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
            "ms", "lower", "program_span", "operators", "query_s")
        assert _reader(name).LAYER == entry["layer"] and _reader(name).MOVES == entry["moves"]
        if name.startswith("scan_"):
            assert entry["workloads"] == FILE_CELLS
        else:  # every cell writes an exchange
            assert "workloads" not in entry
    for cell in (w["name"] for w in manifest["workloads"]):
        readers = set(run.metric_readers(manifest, cell))
        assert "exchange_backpressure_ms" in readers
        assert ({"scan_wait_ms", "scan_backpressure_ms", "scan_slice_ms"} <= readers) == (cell in FILE_CELLS)
