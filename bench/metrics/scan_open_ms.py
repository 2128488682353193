"""Host milliseconds a query's scan tasks spend opening their files: the
program's ``scan_open`` span, one per entry of a task's file group
(``ParquetScanExec``: the read of the file's footer and the choice of
the row groups that are this entry's — all of them for a whole file, by
the midpoint rule for a byte range).  A file in four ranges is opened
four times a query.  Nothing where the program has no such span (the
parent) or scans no file."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "scan_open_ns", 1e-6)
