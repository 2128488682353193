"""Share of the traced queries' span in which no operation ran on the
chip: 1 - (union of device-op intervals) / span."""

LAYER = "device"
MOVES = "query_s"


def read(run):
    trace = run["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
