"""Host milliseconds a query spends inside ``to_device()`` in its scans:
the program's ``scan_stage`` span, one per source batch.  The enqueue,
not the transfer: ``to_device()`` is asynchronous."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "scan_stage_ns", 1e-6)
