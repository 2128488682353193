"""Host milliseconds a query's file scans spend cutting decoded row
groups into batches: the program's ``scan_slice`` span, one per sliced
batch (``ParquetScanExec._row_group_batches``: every column sliced and
padded to the batch's capacity).  Nothing where the program has no such
span (the parent) or no row group was sliced."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "scan_slice_ns", 1e-6)
