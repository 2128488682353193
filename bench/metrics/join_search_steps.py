"""Steps of the Joiner's one binary search a probe batch: the program's
``join_search_steps`` (the loop steps its candidate program ran, summed
over probes) over its ``join_probe_n``.  The search starts inside the
bucket of the probe key's hash prefix, so the mean follows the maps'
largest buckets: 3 over uniform hashes, log2(capacity) + 1 where a map
is one run of one key or the program searches the whole table."""

LAYER = "operators"
MOVES = "query_s"


def read(run):
    probes = run["counters"].get("join_probe_n", 0)
    if not run["queries"] or not probes or "join_search_steps" not in run["counters"]:
        return None
    return run["counters"]["join_search_steps"] / probes
