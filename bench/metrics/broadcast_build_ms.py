"""Host milliseconds a query spends turning its broadcasts into join
maps: the program's ``broadcast_build`` span — in the task that first
needs a broadcast side, reading its blobs back (decode + H2D), joining
them into one batch and building the sorted key table; where the plan
broadcasts a pre-built map, building and serialising it and copying it
back out.  Tasks that find the map in the per-executor cache open no
span."""

from bench.metrics._per_query import per_query

LAYER = "operators"
MOVES = "query_s"


def read(run):
    return per_query(run, "broadcast_build_ns", 1e-6)
