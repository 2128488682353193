"""Host milliseconds a query's scan producer threads spend waiting for
their consumer: the program's ``pipeline_full`` span, opened on the
producer thread by ``runtime/pipeline.pipelined`` only where the
hand-off queue was full — the task thread (launch, reads, exchange)
sets the pace there.  0 where a pipelined scan ran and never blocked;
nothing where the program has no ``pipeline_items`` counter (the
parent) or no scan is pipelined (a cell that scans memory)."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    if per_query(run, "pipeline_items", 1) is None:
        return None
    return run["counters"].get("pipeline_full_ns", 0) / run["queries"] * 1e-6
