"""Host milliseconds a query spends blocked turning a device value into
a host value inside an operator: the program's ``device_read`` span
(the join's candidate and pair counts, the aggregate's group counts, the
exchange writer's D2H)."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "device_read_ns", 1e-6)
