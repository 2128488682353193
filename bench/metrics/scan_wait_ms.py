"""Host milliseconds a query's scan tasks spend waiting for their scan's
producer thread: the program's ``pipeline_wait`` span, opened on the
task thread by ``runtime/pipeline.pipelined`` only where the hand-off
queue was empty — the producer (open, decode, slice, stage) sets the
pace there.  0 where a pipelined scan ran and never waited; nothing
where the program has no ``pipeline_items`` counter (the parent) or no
scan is pipelined (a cell that scans memory)."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    if per_query(run, "pipeline_items", 1) is None:
        return None
    return run["counters"].get("pipeline_wait_ns", 0) / run["queries"] * 1e-6
