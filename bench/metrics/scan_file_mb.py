"""Megabytes of file a query's scans fetch: the program's
``scan_file_bytes`` counter, the compressed length of every column chunk
read, from the files' own metadata.  Exact for a seed."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "scan_file_bytes", 1e-6)
