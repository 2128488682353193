"""Host milliseconds a query spends writing its exchanges: the program's
``exchange_write`` span (D2H, per-partition slicing, serialise and file
write of every map task, and every broadcast blob)."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "exchange_write_ns", 1e-6)
