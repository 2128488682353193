"""``"entry": "catalyst_parquet_hive"``: the ``catalyst_parquet_1file``
entry over a star schema laid out as Spark's own TPC-DS tooling lays it
out (``GenTPCDSData --partitionTables --clusterByPartitionColumns``):
every dimension ONE file, a fact table Hive-partitioned by its date key
— ``<table>/<column>=<value>/part-00000.snappy.parquet``, each
partition's rows clustered into one file in table order, the partition
column in the path and NOT in the file, its NULLs under
``<column>=__HIVE_DEFAULT_PARTITION__`` — all under
``catalyst_parquet.WRITER``.

This entry stands for Spark's DRIVER.  A ``FileSourceScanExec`` applies
its ``partitionFilters`` when it LISTS the files
(``dynamicallySelectedPartitions``), so its tasks are handed the
selected files and no others: here ``isnotnull`` drops the NULL
directory, and the dynamic-pruning filter keeps the directories whose
value the pruning subquery's broadcast holds (``DYNAMIC_PRUNING``, read
off the seeded dimension's host batches by this module's own numpy,
never from the query module's reference).  The kept files are packed by
``catalyst_parquet_1file.plan_splits`` — a file under ``maxSplitBytes``
is one whole piece — into ``FilePartition``s, and each piece goes to
the program as a ``FileSplit`` carrying its directory's typed value, as
a ``PartitionedFile`` carries ``partitionValues``.  Spark does this for
every query; here it is done once, at set-up (``assumed``).

The plan is ``<query>.datepart.plan.json``: the dump whose fact scan has
``partitionFilters``.  The reference never reads a file, so it holds the
scan to every row of a kept partition once and to none of a pruned one.
Of the program this module imports ``ParquetScanExec`` and its entry
type alone.
"""

import os
import shutil
import tempfile
import weakref

import numpy as np

from bench.entries import catalyst, catalyst_parquet
from bench.entries import catalyst_parquet_1file as one_file

#: Spark's ``TPCDSBase.tablePartitionColumns``
PARTITION_COLUMNS = {
    "catalog_sales": "cs_sold_date_sk", "catalog_returns": "cr_returned_date_sk",
    "inventory": "inv_date_sk", "store_sales": "ss_sold_date_sk",
    "store_returns": "sr_returned_date_sk", "web_sales": "ws_sold_date_sk",
    "web_returns": "wr_returned_date_sk",
}
#: what Hive names the directory of a NULL partition value
NULL_DIRECTORY = "__HIVE_DEFAULT_PARTITION__"
#: (suite, query) -> fact table -> its scan's dynamic-pruning subquery:
#: the dimension, its join key, and the equalities the dimension's side
#: of the plan filters it by
DYNAMIC_PRUNING = {
    ("tpcds", "q7"): {"store_sales": ("date_dim", "d_date_sk", {"d_year": 2000})},
}


def column(scan, name, buffer="data"):
    """One column of a host-resident scan, whole, padding dropped."""
    i = scan.schema.names.index(name)
    batches = [b for part in scan._partitions for b in part]
    return catalyst_parquet._whole(batches, i, buffer)


def write_partitioned(scan, directory, partition_column):
    """``scan``'s rows as ``<directory>/<partition_column>=<value>/
    part-00000.snappy.parquet``, one file a value, rows in table order,
    the partition column left out of the files."""
    import pyarrow as pa
    import pyarrow.parquet as papq

    key, valid = column(scan, partition_column), column(scan, partition_column, "validity")
    data = [f for f in scan.schema.fields if f.name != partition_column]
    whole = pa.Table.from_arrays(
        [catalyst_parquet.arrow_array(f.dtype, column(scan, f.name), column(scan, f.name, "validity"),
                                      column(scan, f.name, "lengths") if f.dtype.is_string else None)
         for f in data], names=[f.name for f in data])
    # each value's rows together and in table order, the NULLs as one value more
    null = int(key.max()) + 1
    group = np.where(valid, key, null)
    order = np.argsort(group, kind="stable")
    whole, group = whole.take(order), group[order]
    starts = np.flatnonzero(np.r_[True, group[1:] != group[:-1]])
    for lo, hi in zip(starts, np.r_[starts[1:], len(group)]):
        value = NULL_DIRECTORY if group[lo] == null else group[lo]
        partition = os.path.join(directory, f"{partition_column}={value}")
        os.makedirs(partition)
        papq.write_table(whole.slice(lo, hi - lo),
                         os.path.join(partition, "part-00000.snappy.parquet"),
                         **catalyst_parquet.WRITER)


def selected_values(pruning, scans):
    """The dynamic-pruning subquery's result: the dimension's join keys
    of the rows that pass its filters."""
    dimension, key, equalities = pruning
    keys = column(scans[dimension], key)
    keep = np.ones(len(keys), bool)
    for name, value in equalities.items():
        keep &= column(scans[dimension], name) == value
    return set(keys[keep].tolist())


def list_partitions(directory, selected):
    """Spark's listing under the scan's partition filters: (path, bytes,
    value) of each directory whose value is not NULL (``isnotnull``) and
    in ``selected`` (the dynamic pruning), in path order."""
    kept = []
    for name in sorted(os.listdir(directory)):
        text = name.split("=", 1)[1]
        if text == NULL_DIRECTORY or int(text) not in selected:
            continue
        path = os.path.join(directory, name, "part-00000.snappy.parquet")
        kept.append((path, os.path.getsize(path), int(text)))
    return kept


def source(suite, query, scans, n_parts):
    # a program whose scan entries carry no partition values stops here,
    # in set-up, before a file is written
    from blaze_tpu.ops import FileSplit, ParquetScanExec

    if "values" not in FileSplit._fields:
        raise ImportError("this program's FileSplit carries no partition values")

    root = tempfile.mkdtemp(prefix="bench_parquet_hive_")
    file_scans = {}
    for table, scan in scans.items():
        directory = os.path.join(root, table)
        # the configuration's batch_rows: the longest batch the harness cut
        batch_rows = max(b.num_rows for part in scan._partitions for b in part)
        if table not in PARTITION_COLUMNS:
            path = one_file.write_one_file(scan, directory)
            partitions = one_file.plan_splits([(path, os.path.getsize(path))], n_parts)
            file_scans[table] = ParquetScanExec(
                [[FileSplit(*piece) for piece in pieces] for pieces in partitions],
                scan.schema, batch_rows=batch_rows)
            continue
        partition_column = PARTITION_COLUMNS[table]
        schema_of = type(scan.schema)
        write_partitioned(scan, directory, partition_column)
        kept = list_partitions(
            directory, selected_values(DYNAMIC_PRUNING[suite, query][table], scans))
        value_of = {path: value for path, _, value in kept}
        partitions = one_file.plan_splits([(path, size) for path, size, _ in kept], n_parts)
        file_scans[table] = ParquetScanExec(
            [[FileSplit(*piece, (value_of[piece[0]],)) for piece in pieces] for pieces in partitions],
            schema_of([f for f in scan.schema.fields if f.name != partition_column]),
            batch_rows=batch_rows,
            partition_schema=schema_of([scan.schema.field(partition_column)]))
    plan = catalyst.source(suite, query + ".datepart", file_scans, n_parts)
    weakref.finalize(plan, shutil.rmtree, root, ignore_errors=True)
    return plan
