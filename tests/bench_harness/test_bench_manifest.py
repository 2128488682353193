"""``BENCHMARK.json`` and every file it names resolve by name alone, so
a later PR adds a cell, a configuration or a metric with new files and
new entries only."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import least_bytes, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_manifest_has_the_contract_keys_and_legal_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    names = [m["name"] for m in METRICS] + CELLS + [c["name"] for c in MANIFEST["configs"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in MANIFEST["workloads"]]:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES, m
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace"), m
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    for entry in MANIFEST["configs"] + MANIFEST["workloads"]:
        for key in ("why", "source"):
            assert 1 <= len(entry.get(key, "x")) <= 200 and "\n" not in entry.get(key, ""), entry
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in MANIFEST["workloads"]} == {c["name"] for c in MANIFEST["configs"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    manifest, entry, config, traffic = run.resolve(cell)
    assert entry["chips"] == 1
    listed = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    assert listed["file"] == f"bench/configs/{entry['config']}.json"
    assert listed["reduced"] == config["reduced"] == ["scale"]
    assert (traffic["loop"], traffic["clients"]) == ("closed", 1)
    assert traffic["traced_queries"] >= 1
    # how the plans arrive is the configuration's, stated and not defaulted
    assert callable(importlib.import_module("bench.entries." + config["entry"]).source)
    query = importlib.import_module(f"bench.suites.{config['suite']}.{traffic['query']}")
    with open(os.path.join(ROOT, config["schema"])) as f:
        tables = json.load(f)["tables"]
    for table, columns in query.COLUMNS.items():
        assert least_bytes.row_bytes(tables, table, columns) > 0
    for fn in ("oracle", "control", "canonical"):
        assert callable(getattr(query, fn))
    # every per-layer metric this cell reports has a reader of that name
    assert set(run.metric_readers(manifest, cell)) == {
        m["name"] for m in manifest["per_layer"] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("listed", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_states_its_suite_schema_and_entry(listed):
    with open(os.path.join(ROOT, listed["file"])) as f:
        config = json.load(f)
    for key in ("suite", "schema", "entry", "scale", "partitions", "batch_rows"):
        assert key in config, key
    assert os.path.isfile(os.path.join(ROOT, "bench", "entries", config["entry"] + ".py"))
    assert os.path.isfile(os.path.join(ROOT, "bench", "suites", config["suite"], "datagen.py"))
    assert os.path.isfile(os.path.join(ROOT, config["schema"]))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_reader_agrees_with_its_manifest_entry(metric):
    reader = importlib.import_module("bench.metrics." + metric["name"])
    assert (reader.LAYER, reader.MOVES) == (metric["layer"], metric["moves"])
    assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    # nothing to read gives nothing, never a 0
    empty = {"queries": 0, "counters": {}, "plan_s": [], "trace": None,
             "memory_peak_bytes": None, "least_bytes": 1, "peak": {"hbm_bytes_per_s": 1.0}}
    assert reader.read(empty) is None


def test_least_bytes_of_q06_at_sf1():
    with open(os.path.join(ROOT, "bench", "suites", "tpch", "schema.json")) as f:
        tables = json.load(f)["tables"]
    from bench.suites.tpch import q1, q3, q6

    # three scaled-int64 decimals and one date32: 28 B a row
    assert least_bytes.row_bytes(tables, "lineitem", q6.COLUMNS["lineitem"]) == 28
    assert least_bytes.least_bytes(tables, q6.COLUMNS, {"lineitem": 5_996_672}) == 167_906_816
    # q1 adds l_tax and two string(8) flags at 8 + 4 each
    assert least_bytes.row_bytes(tables, "lineitem", q1.COLUMNS["lineitem"]) == 60
    assert [least_bytes.row_bytes(tables, t, c) for t, c in q3.COLUMNS.items()] == [28, 24, 28]
    with pytest.raises(ValueError):
        least_bytes.type_width("varchar")
