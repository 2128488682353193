"""What the readers of the program's span counters share: a counter
from ``dispatch.capture()`` over the window, per completed query."""


def per_query(run, counter, scale):
    """``counter`` / queries * ``scale``; None where the program has no
    such counter (a parent commit from before the span) or no query
    completed."""
    if not run["queries"] or counter not in run["counters"]:
        return None
    return run["counters"][counter] / run["queries"] * scale
