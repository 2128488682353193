"""Host milliseconds a query spends reading its exchanges: the program's
``exchange_read`` span in ``IpcReaderExec`` (fetch, checksum, decode
and H2D of every reduce partition's blocks)."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "exchange_read_ns", 1e-6)
