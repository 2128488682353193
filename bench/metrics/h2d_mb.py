"""Megabytes a query's scans stage host to device: the program's
``h2d_bytes`` counter, the ``nbytes`` of every host array handed to
``to_device()`` (data, validity, lengths), from shapes."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "h2d_bytes", 1e-6)
