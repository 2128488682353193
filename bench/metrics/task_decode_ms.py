"""Host milliseconds a query spends decoding its tasks: the program's
``task_decode`` span (``serde/from_proto.run_task``: ``ParseFromString``,
``plan_from_proto``, ``optimize_plan``), summed over the window's tasks."""

from bench.metrics._per_query import per_query

LAYER = "entry and planning"
MOVES = "query_s"


def read(run):
    return per_query(run, "task_decode_ns", 1e-6)
