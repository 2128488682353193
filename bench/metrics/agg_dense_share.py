"""Share of the fused grouped-aggregate updates that took the sort-free
dense program: the program's ``agg_dense_updates`` over its
``agg_grouped_updates`` in the window.  100 where every batch after a
task's seed matched the accumulator's keys (few groups), 0 where the
stream proved more groups than the dense slots hold."""

LAYER = "operators"
MOVES = "query_s"


def read(run):
    updates = run["counters"].get("agg_grouped_updates", 0)
    if not run["queries"] or not updates:
        return None
    return 100.0 * run["counters"].get("agg_dense_updates", 0) / updates
