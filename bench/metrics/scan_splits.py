"""File splits a query's scan tasks open: the program's ``scan_splits``
counter, one per entry of a task's file group, whole file or byte
range.  It is the plan's (Spark's ``FilePartition``s: files cut at
``maxSplitBytes`` and packed), so a change to it is a change of the
split planning, not of the reader.  Nothing where the program has no
such counter (the parent) or scans no file."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "scan_splits", 1)
