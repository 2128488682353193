"""Megabytes a query writes into its exchanges: the program's
``shuffle_bytes_written`` counter (committed map-output partition
lengths plus broadcast blobs)."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "shuffle_bytes_written", 1e-6)
