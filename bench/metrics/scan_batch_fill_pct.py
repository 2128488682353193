"""How full the batches a query's file scans staged were: the program's
``scan_rows`` (rows in the batches ``ParquetScanExec`` staged) over its
``scan_rows_budget`` (those batches x the scan's ``batch_rows``: what
they could have held), in the window.  Every staged batch pays the same
fixed host cost and every operator above a launch or two, so small
files staged one batch a file read low here (about 2% a batch of 1,500
rows at 65,536) and packed ones high.  Nothing where the program has no
such counters (the parent) or staged no file batch."""

LAYER = "operators"
MOVES = "query_s"


def read(run):
    budget = run["counters"].get("scan_rows_budget", 0)
    if not run["queries"] or not budget:
        return None
    return 100.0 * run["counters"].get("scan_rows", 0) / budget
