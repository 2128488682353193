"""Host milliseconds a query's map tasks spend waiting for their
exchange stager thread: the program's ``inserter_full`` span (a put that
found the stager's queue full) plus ``inserter_drain`` (the flush and
join before the commit), both on the map task's thread — the part of
``exchange_write`` that is on the query's critical path.  0 where a
stager ran and the task never waited; nothing where the program has no
``inserter_items`` counter (the parent)."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    if per_query(run, "inserter_items", 1) is None:
        return None
    c = run["counters"]
    return (c.get("inserter_full_ns", 0) + c.get("inserter_drain_ns", 0)) / run["queries"] * 1e-6
