"""The comparison that decides ``correct`` fails what it has to fail.

* the control: the reference carried in float32, put in the program's
  place, reads wrong cells on every seed (it was read on the chip at
  the cells' own sizes too: ``PERF.md``);
* the rest of a run, driven on the CPU past the look for a chip, with
  the timed path broken underneath: half of the scan's batches left
  out, and an answer altered where it is produced.
"""

import importlib

import pytest

from bench import compare, run
from bench.suites.tpch import datagen

SCALE = 0.01
SEEDS = (3, 2**31 + 11, 19940204)


@pytest.mark.parametrize("query", ["q6", "q1", "q3"])
def test_reference_passes_and_float32_control_fails(query):
    mod = importlib.import_module("bench.suites.tpch." + query)
    for seed in SEEDS:
        tables = {t: datagen.generate_table(t, SCALE, seed, cols) for t, cols in mod.COLUMNS.items()}
        expected = mod.oracle(tables)
        compared, ok = compare.compare([expected, expected], expected, mod.canonical)
        assert ok and all(c["value"] == 0 for c in compared.values())
        compared, ok = compare.compare([expected, mod.control(tables)], expected, mod.canonical)
        assert not ok
        assert compared["queries_wrong"]["value"] == 1 and compared["cells_wrong"]["value"] >= 1
    # a query that never answered is a wrong one, with all its cells
    compared, ok = compare.compare([None], expected, mod.canonical)
    cells = sum(len(v) for v in expected.values())
    assert not ok and compared["cells_wrong"]["value"] == cells


def test_datagen_is_seeded_and_prunes_to_a_projection():
    cols = ["l_shipdate", "l_returnflag", "l_tax"]
    a = datagen.generate_table("lineitem", SCALE, 2**31 + 5, cols)
    b = datagen.generate_table("lineitem", SCALE, 2**31 + 5)
    assert sorted(a) == sorted(cols) and len(b) == 16
    for c in cols:
        assert (a[c][0] == b[c][0]).all()
    other = datagen.generate_table("lineitem", SCALE, 2**31 + 6, cols)
    assert other["l_tax"][0].shape != a["l_tax"][0].shape or (other["l_tax"][0] != a["l_tax"][0]).any()
    full = datagen.generate_table("orders", SCALE, 7)
    part = datagen.generate_table("orders", SCALE, 7, ["o_orderkey", "o_totalprice"])
    assert (part["o_totalprice"][0] == full["o_totalprice"][0]).all()


def _measure(seed=2**31 + 21):
    """The rest of a run after the look for a chip, q6 at a test's size."""
    manifest, _, config, traffic = run.resolve("tpch_q06_sf1")
    config = dict(config, scale=SCALE)
    return run.measure("tpch_q06_sf1", manifest, config, traffic, seed, 0.3, 0,
                       run.device_stamp())


def test_sound_run_reads_correct():
    out = _measure()
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "compared"
    assert {k: v["value"] for k, v in out["compared"].items()} == {"queries_wrong": 0, "cells_wrong": 0}
    assert set(out["metrics"]) == {"query_s", "query_p95_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"  # stamped as it ran: no chip's name on a CPU run
    # the allocator's free pages go back inside set-up, after the warm-up query
    parts = list(out["info"]["setup_parts"])
    assert parts[-2:] == ["first_query", "heap_trim"]
    assert out["metrics"]["setup_s"]["value"] >= sum(out["info"]["setup_parts"].values()) - 1e-6
    assert len(out["info"]["latency_s"]["each"]) == out["attempted"]


def test_half_of_the_batches_left_out_reads_not_correct(monkeypatch):
    from blaze_tpu.tpch import datagen as program_datagen

    whole = program_datagen.table_to_batches

    def half(*args, **kwargs):
        parts = whole(*args, **kwargs)
        return [p if i % 2 == 0 else [] for i, p in enumerate(parts)]

    monkeypatch.setattr(program_datagen, "table_to_batches", half)
    out = _measure()
    assert out["correct"] is False
    assert out["compared"]["queries_wrong"]["value"] == out["attempted"]


def test_an_answer_altered_where_it_is_produced_reads_not_correct(monkeypatch):
    from blaze_tpu import batch

    honest = batch.batch_to_pydict
    calls = []

    def altered(b):
        got = honest(b)
        calls.append(1)
        if len(calls) == 2:  # the window's first query; the first call is the warm-up's
            got = {k: [v[0] + 1] + v[1:] if v else v for k, v in got.items()}
        return got

    monkeypatch.setattr(batch, "batch_to_pydict", altered)
    out = _measure()
    assert out["correct"] is False
    assert out["compared"]["queries_wrong"]["value"] == 1
    assert out["compared"]["cells_wrong"]["value"] == 1


def test_a_query_that_raises_counts_as_failed_and_wrong(monkeypatch):
    from blaze_tpu import batch

    def broken(b):
        raise RuntimeError("planted")

    monkeypatch.setattr(batch, "batch_to_pydict", broken)
    out = _measure()
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
