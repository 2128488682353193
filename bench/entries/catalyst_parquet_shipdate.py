"""``"entry": "catalyst_parquet_shipdate"``: the ``catalyst_parquet``
entry over a fact table that Spark wrote clustered by a date, as
``df.repartitionByRange(n, col).sortWithinPartitions(col).write`` leaves
it — the layout of an ``INSERT ... SELECT ... ORDER BY <date>``, a Delta
``OPTIMIZE ZORDER BY`` or an Iceberg sort order.  At set-up the table's
seeded rows are written as one file a range of ``CLUSTER_BY``'s column,
each sorted by it, under ``catalyst_parquet.WRITER``: ``n_parts``
ranges of as many rows each, with bounds computed exactly where Spark's
``RangePartitioner`` samples them, a value never split between two
files, ties in table order.  pyarrow writes each chunk's statistics, as
parquet-mr does, so a row group's min and max of the column are the
ends of the rows it holds.  One file is one task.

The plan is the same ``<query>.plan.json`` as ``catalyst_parquet``'s;
the reference never reads a file, so it holds the scan to every row of
every row group that the query's filters keep.
"""

import os
import shutil
import tempfile
import weakref

import numpy as np

from bench.entries import catalyst, catalyst_parquet

#: suite -> table -> the column its files are ranged and sorted by
CLUSTER_BY = {"tpch": {"lineitem": "l_shipdate"}}


def range_bounds(keys, n):
    """The ``n - 1`` upper bounds of ``RangePartitioner``'s ranges, exact:
    range ``p`` holds the keys above bound ``p - 1`` up to bound ``p``,
    each bound the key at the end of the ``p``-th ``n``-th of the
    sorted keys."""
    ordered = np.sort(keys, kind="stable")
    return ordered[[(p + 1) * len(ordered) // n - 1 for p in range(n - 1)]]


def write_ranges(scan, directory, column, n):
    """``scan``'s rows as ``<directory>/part-0000p.snappy.parquet``, one
    file a range of ``column`` (NULLs first, as ascending order puts
    them), each sorted by it; a range without rows has no file.  The
    paths, in range order."""
    import pyarrow as pa
    import pyarrow.parquet as papq

    batches = [b for part in scan._partitions for b in part]
    whole = {f.name: tuple(catalyst_parquet._whole(batches, i, buffer)
                           if buffer != "lengths" or f.dtype.is_string else None
                           for buffer in ("data", "validity", "lengths"))
             for i, f in enumerate(scan.schema.fields)}
    data, valid, _ = whole[column]
    key = np.where(valid, data.astype(np.int64), np.iinfo(np.int64).min)
    order = np.argsort(key, kind="stable")
    part = np.searchsorted(range_bounds(key, n), key[order], side="left")
    table = pa.Table.from_arrays(
        [catalyst_parquet.arrow_array(f.dtype, *(None if a is None else a[order] for a in whole[f.name]))
         for f in scan.schema.fields], names=scan.schema.names)
    os.makedirs(directory)
    paths = []
    for p, lo, hi in zip(range(n), np.searchsorted(part, range(n)), np.searchsorted(part, range(1, n + 1))):
        if hi > lo:
            paths.append(os.path.join(directory, f"part-{p:05d}.snappy.parquet"))
            papq.write_table(table.slice(lo, hi - lo), paths[-1], **catalyst_parquet.WRITER)
    return paths


def source(suite, query, scans, n_parts):
    from blaze_tpu.ops import ParquetScanExec

    root = tempfile.mkdtemp(prefix="bench_parquet_shipdate_")
    file_scans = {}
    for table, scan in scans.items():
        paths = write_ranges(scan, os.path.join(root, table), CLUSTER_BY[suite][table], n_parts)
        # the configuration's batch_rows: the longest batch the harness cut
        batch_rows = max(b.num_rows for part in scan._partitions for b in part)
        # one file a task: each lies under spark.sql.files.maxPartitionBytes
        file_scans[table] = ParquetScanExec([[path] for path in paths], scan.schema,
                                            batch_rows=batch_rows)
    plan = catalyst.source(suite, query, file_scans, n_parts)
    weakref.finalize(plan, shutil.rmtree, root, ignore_errors=True)
    return plan
