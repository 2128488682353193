"""Programs compiled inside the window (``xla_compiles``).  Set-up
warms every shape, so this reads 0; more means a shape the warm-up
query never met."""

LAYER = "operators"
MOVES = "query_s"


def read(run):
    if not run["queries"]:
        return None
    return run["counters"].get("xla_compiles", 0)
