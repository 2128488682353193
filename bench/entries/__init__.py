"""How a deployment's plans arrive: one module per value of a
configuration's ``entry`` key, found by that name.

An entry is a plan source and nothing else.  Its one function,
``source(suite, query, scans, n_parts)``, is called once at set-up and
returns a function of no arguments that ``Cell.query()`` calls inside
its ``plan`` span, once per query, for a fresh ``ExecNode`` tree over
the same scans.  What follows the plan — ``split_stages``,
``run_stages``, the D2H, the comparison — is one code path whatever the
entry.

This module holds what every entry starts from: the program's declared
schemas for the suite's tables, pruned to the columns the query reads,
and the host-resident scans over them.
"""

import importlib


def pruned_schema(suite, table, columns):
    """The program's schema of ``table`` in ``blaze_tpu.<suite>`` (its
    ``<SUITE>_SCHEMAS`` map), cut to ``columns`` in the table's order."""
    from blaze_tpu.schema import Schema

    package = importlib.import_module("blaze_tpu." + suite)
    declared = getattr(package, suite.upper() + "_SCHEMAS")[table]
    schema = Schema([f for f in declared.fields if f.name in columns])
    assert len(schema.fields) == len(columns), (suite, table, columns)
    return schema


def memory_scans(suite, tables, columns, n_parts, batch_rows):
    """table -> ``MemoryScanExec`` over its host batches.  Each scan
    carries the columns the query references, as a column-pruned Spark
    scan would hand them over; the batches stay on the host and the scan
    stages them H2D on every query."""
    from blaze_tpu.ops import MemoryScanExec
    # the program's one host-batch builder; its TPC-DS tests take it from here too
    from blaze_tpu.tpch.datagen import table_to_batches

    scans = {}
    for t, cols in columns.items():
        schema = pruned_schema(suite, t, cols)
        scans[t] = MemoryScanExec(
            table_to_batches(tables[t], schema, n_parts, batch_rows=batch_rows), schema)
    return scans
