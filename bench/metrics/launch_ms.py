"""Host milliseconds a query spends inside the jitted call of its
instrumented programs: the program's ``launch`` span in
``runtime/dispatch.py`` (argument handling and enqueue; the device runs
later)."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "launch_ns", 1e-6)
