"""The share of the rows a query's file scans chose that their row
groups' statistics ruled out before a page was fetched: the program's
``scan_rows_pruned`` over its ``scan_rows_chosen`` (the rows of the row
groups each entry of a scan's file groups holds, before pruning), in
the window.  A table clustered by the column a query filters reads high
here; one whose every row group spans the filter's range reads 0.
Nothing where the program has no such counters (the parent) or chose no
row."""

LAYER = "operators"
MOVES = "query_s"


def read(run):
    chosen = run["counters"].get("scan_rows_chosen", 0)
    if not run["queries"] or not chosen or "scan_rows_pruned" not in run["counters"]:
        return None
    return 100.0 * run["counters"]["scan_rows_pruned"] / chosen
