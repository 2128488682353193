"""Host milliseconds a query's file scans spend joining pieces shorter
than a batch — small files, short row groups — into one host batch
before it is staged: the program's ``scan_coalesce`` span, one a packed
batch, on the staging thread.  What the packing costs; what it saves is
in ``scan_stage_ms`` and in every per-batch cost above the scan.
Nothing where the program has no such span (the parent), scans no file
or packed no batch."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "scan_coalesce_ns", 1e-6)
