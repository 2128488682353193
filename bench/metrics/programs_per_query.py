"""XLA programs launched per query: the program's ``xla_dispatches``
counter over the window.  An exact count, and a lower bound where a
query joins: the Joiner's own jitted kernels are not counted."""

LAYER = "operators"
MOVES = "query_s"


def read(run):
    if not run["queries"] or "xla_dispatches" not in run["counters"]:
        return None
    return run["counters"]["xla_dispatches"] / run["queries"]
