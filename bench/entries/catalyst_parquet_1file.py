"""``"entry": "catalyst_parquet_1file"``: the ``catalyst_parquet`` entry
over a table that ONE task wrote — ``<table>/part-00000.snappy.parquet``,
every partition's rows in order, padding dropped, under the same
``catalyst_parquet.WRITER`` — and that Spark therefore cuts into byte
ranges when it reads it.

A ``FileSourceScanExec``'s tasks get no files: they get
``FilePartition``s of ``PartitionedFile(path, start, length)``.  This
entry stands for Spark there, as pyarrow stands for parquet-mr: it plans
the splits by Spark 3.5.1's three functions (below, in their order) under
Spark's defaults, with ``spark.sql.files.minPartitionNum`` the
configuration's ``partitions``, and hands the program one
``FilePartition`` a task as ``FileSplit``s.  Which row groups a range
reads is the program's business (parquet-mr's midpoint rule); the
reference never reads the file, so it holds the scan to every row once.

The planner is the benchmark's own and imports nothing of the program.
"""

import os
import shutil
import tempfile
import types
import weakref

from bench.entries import catalyst, catalyst_parquet

#: spark.sql.files.maxPartitionBytes and spark.sql.files.openCostInBytes,
#: Spark 3.5.1's defaults (its SQL performance-tuning page)
MAX_PARTITION_BYTES = 128 << 20
OPEN_COST_IN_BYTES = 4 << 20


def max_split_bytes(sizes, min_partition_num):
    """``FilePartition.maxSplitBytes``: a core's share of the bytes to
    read, each file weighing its open cost more, held between the open
    cost and ``maxPartitionBytes``.  Long division, as Scala's."""
    bytes_per_core = sum(size + OPEN_COST_IN_BYTES for size in sizes) // min_partition_num
    return min(MAX_PARTITION_BYTES, max(OPEN_COST_IN_BYTES, bytes_per_core))


def split_files(files, max_split):
    """``PartitionedFileUtil.splitFiles`` over every (path, size) — all
    splittable, as Parquet is — then ``createReadRDD``'s order: the
    largest piece first (a stable sort: a file's equal pieces stay in
    offset order).  Pieces are (path, start, length)."""
    pieces = [(path, start, min(max_split, size - start))
              for path, size in files for start in range(0, size, max_split)]
    return sorted(pieces, key=lambda piece: -piece[2])


def file_partitions(pieces, max_split):
    """``FilePartition.getFilePartitions``: next fit decreasing — a
    piece that would take the open partition past ``max_split`` closes
    it; a piece weighs its open cost more once it is in."""
    partitions, current, size = [], [], 0
    for piece in pieces:
        if current and size + piece[2] > max_split:
            partitions.append(current)
            current, size = [], 0
        current.append(piece)
        size += piece[2] + OPEN_COST_IN_BYTES
    if current:
        partitions.append(current)
    return partitions


def plan_splits(files, min_partition_num):
    """The ``FilePartition``s Spark 3.5.1 reads ``files`` — (path, size)
    pairs — in: a list of pieces a task."""
    max_split = max_split_bytes([size for _, size in files], min_partition_num)
    return file_partitions(split_files(files, max_split), max_split)


def write_one_file(scan, directory):
    """All of ``scan``'s partitions, in order, as the one file
    ``<directory>/part-00000.snappy.parquet`` — what
    ``catalyst_parquet.write_partitions`` writes of a scan of one
    partition; its path."""
    whole = types.SimpleNamespace(
        schema=scan.schema, _partitions=[[b for part in scan._partitions for b in part]])
    (path,) = catalyst_parquet.write_partitions(whole, directory)
    return path


def source(suite, query, scans, n_parts):
    # a program without byte ranges stops here, in set-up: it has no way
    # to read a range, and must not read the file whole once a task
    from blaze_tpu.ops import FileSplit, ParquetScanExec

    root = tempfile.mkdtemp(prefix="bench_parquet_1file_")
    file_scans = {}
    for table, scan in scans.items():
        path = write_one_file(scan, os.path.join(root, table))
        # the configuration's batch_rows: the longest batch the harness cut
        batch_rows = max(b.num_rows for part in scan._partitions for b in part)
        partitions = plan_splits([(path, os.path.getsize(path))], n_parts)
        file_scans[table] = ParquetScanExec(
            [[FileSplit(*piece) for piece in pieces] for pieces in partitions],
            scan.schema, batch_rows=batch_rows)
    plan = catalyst.source(suite, query, file_scans, n_parts)
    weakref.finalize(plan, shutil.rmtree, root, ignore_errors=True)
    return plan
