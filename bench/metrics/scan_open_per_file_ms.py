"""Host milliseconds one more file costs a scan before a row of it is
decoded: the program's ``scan_open`` span (footer, Arrow's reader over
the file, the choice of row groups) over its ``scan_splits`` (entries
opened), in the window.  A table of a few large files pays it a handful
of times a query; a date-partitioned one pays it once a date.  Nothing
where the program has no such counters (the parent) or scans no file."""

LAYER = "operators"
MOVES = "query_s"


def read(run):
    opened = run["counters"].get("scan_splits", 0)
    if not run["queries"] or not opened or "scan_open_ns" not in run["counters"]:
        return None
    return run["counters"]["scan_open_ns"] / opened * 1e-6
