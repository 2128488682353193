"""Host milliseconds a query spends before its first task: the
harness's own clock around ``build_query`` + ``split_stages``, as a
mean over the window's queries."""

LAYER = "entry and planning"
MOVES = "query_s"


def read(run):
    if not run["plan_s"]:
        return None
    return 1e3 * sum(run["plan_s"]) / len(run["plan_s"])
