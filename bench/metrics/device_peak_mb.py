"""Peak device memory after the window:
``memory_stats()["peak_bytes_in_use"]``, in MB of 10^6 bytes."""

LAYER = "device"
MOVES = "query_s"


def read(run):
    if run["memory_peak_bytes"] is None:
        return None
    return run["memory_peak_bytes"] / 1e6
