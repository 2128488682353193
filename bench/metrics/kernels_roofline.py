"""All of a query's kernels against the HBM roofline: the time the
chip needs to read the query's least bytes once at its peak bandwidth,
over the time it was busy per query in the trace.  Bandwidth-bound by
construction (scans, filters, sums and sorts do next to no arithmetic
per byte), and it reads the same work whatever implements it."""

LAYER = "kernels"
MOVES = "query_s"


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"] or trace["busy_s"] <= 0:
        return None
    least_s = run["least_bytes"] / run["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (trace["busy_s"] / trace["queries"])
