"""``"entry": "catalyst_parquet"``: the ``catalyst`` entry over tables
that live as Parquet files, as every Spark table does.  At set-up each
table's seeded rows are written, one file a partition, the way Spark
3.5.1 writes them by default (``WRITER`` below; the configuration's
``layout`` states the same); on every query the program's
``ParquetScanExec`` opens, fetches, decompresses and decodes them again.

The files are the yardstick's: ``pyarrow.parquet`` writes them, never
the program's own ``io/parquet.write_parquet``.  The rows are those of
the ``MemoryScanExec`` the harness hands over — partition by partition,
in order, padding dropped — so the reference, which never reads the
files, holds the scan to every row.  The directory goes when the plan
source does (``Cell.release()``), at process exit at the latest.
"""

import os
import shutil
import tempfile
import weakref

import numpy as np

from bench.entries import catalyst

#: Spark 3.5.1's defaults: spark.sql.parquet.compression.codec=snappy,
#: parquet.page.size 1 MB in format v1, dictionary on (a chunk whose
#: dictionary outgrows its page falls back to PLAIN), a decimal of 18
#: digits or fewer as INT64 (writeLegacyFormat=false); row groups of
#: 1,048,576 rows are what parquet.block.size = 128 MB holds of the
#: 16-column lineitem at ~125 B a row
WRITER = dict(compression="snappy", use_dictionary=True, data_page_version="1.0",
              data_page_size=1 << 20, row_group_size=1_048_576, store_decimal_as_integer=True)


def arrow_array(dtype, data, validity, lengths):
    """One column of a partition, padding already dropped, as the arrow
    array whose Parquet form is Spark's: decimal(p<=18) over the unscaled
    int64, date over int32 days, a string from its padded bytes."""
    import pyarrow as pa

    n = data.shape[0]
    nulls = None if validity.all() else pa.py_buffer(np.packbits(validity, bitorder="little"))
    if dtype.is_decimal:
        words = np.empty((n, 2), np.int64)  # little-endian int128: the low word, then its sign
        words[:, 0] = data
        words[:, 1] = data >> 63
        return pa.Array.from_buffers(pa.decimal128(dtype.precision, dtype.scale), n,
                                     [nulls, pa.py_buffer(words)])
    if dtype.is_string:
        offsets = np.zeros(n + 1, np.int32)
        np.cumsum(lengths, out=offsets[1:])
        chars = data[np.arange(data.shape[1]) < lengths[:, None]]
        return pa.StringArray.from_buffers(n, pa.py_buffer(offsets), pa.py_buffer(chars), nulls)
    flat = pa.array(data, mask=None if nulls is None else ~validity)
    return flat.cast(pa.date32()) if dtype.kind.name == "DATE32" else flat


def _whole(batches, i, buffer):
    """Column ``i``'s ``buffer`` over a partition's batches, padding dropped."""
    return np.concatenate([np.asarray(getattr(b.columns[i], buffer))[:b.num_rows] for b in batches])


def write_partitions(scan, directory):
    """``scan``'s partitions as ``<directory>/part-0000p.snappy.parquet``;
    the paths, in partition order."""
    import pyarrow as pa
    import pyarrow.parquet as papq

    os.makedirs(directory)
    paths = []
    for p, batches in enumerate(scan._partitions):
        arrays = [arrow_array(f.dtype, _whole(batches, i, "data"), _whole(batches, i, "validity"),
                              _whole(batches, i, "lengths") if f.dtype.is_string else None)
                  for i, f in enumerate(scan.schema.fields)]
        paths.append(os.path.join(directory, f"part-{p:05d}.snappy.parquet"))
        papq.write_table(pa.Table.from_arrays(arrays, names=scan.schema.names), paths[-1], **WRITER)
    return paths


def source(suite, query, scans, n_parts):
    from blaze_tpu.ops import ParquetScanExec

    root = tempfile.mkdtemp(prefix="bench_parquet_")
    file_scans = {}
    for table, scan in scans.items():
        paths = write_partitions(scan, os.path.join(root, table))
        # the configuration's batch_rows: the longest batch the harness cut
        batch_rows = max(b.num_rows for part in scan._partitions for b in part)
        # one file a task: each lies under spark.sql.files.maxPartitionBytes
        file_scans[table] = ParquetScanExec([[path] for path in paths], scan.schema,
                                            batch_rows=batch_rows)
    plan = catalyst.source(suite, query, file_scans, n_parts)
    weakref.finalize(plan, shutil.rmtree, root, ignore_errors=True)
    return plan
