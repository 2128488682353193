"""Host milliseconds a query spends probing join maps: the program's
``join_probe`` span around ``Joiner.probe_batch`` — the candidate count,
its read (which picks the output capacity), the probe program and the
read of the pair count.  The ``device_read`` spans lie inside it."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "join_probe_ns", 1e-6)
