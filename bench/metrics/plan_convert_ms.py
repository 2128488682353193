"""Host milliseconds a query spends converting the plan Spark sent: the
program's ``plan_convert`` span inside ``BlazeSparkSession.plan`` (parse
of the catalyst dump, strategy, conversion).  ``plan_ms`` holds it and
``split_stages``; only a cell whose entry is ``catalyst`` has it."""

from bench.metrics._per_query import per_query

LAYER = "entry and planning"
MOVES = "query_s"


def read(run):
    return per_query(run, "plan_convert_ns", 1e-6)
