"""``"entry": "builder"``: the plan is the program's own hand-built
``ExecNode`` tree, ``blaze_tpu.<suite>.build_query(query, scans,
n_parts)`` — what ``chip_smoke.py`` proved on the chip (PR 22)."""

import importlib


def source(suite, query, scans, n_parts):
    build_query = importlib.import_module("blaze_tpu." + suite).build_query
    return lambda: build_query(query, scans, n_parts)
