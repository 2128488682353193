"""The comparison that decides ``correct``.

Every query the window finished left its result as python values (the
D2H of ``batch_to_pydict``).  Each is set against the reference's
result for the same seeded data, cell by cell and exactly: decimals
are unscaled integers on both sides, so there is no tolerance and the
limit of every number compared is 0.
"""

LIMITS = {"queries_wrong": 0, "cells_wrong": 0}


def cells_wrong(got, expected):
    """How many cells of ``got`` differ from ``expected`` (both column ->
    list of python values, rows in canonical order).  A row or a column
    that one side lacks counts with all its cells."""
    if got is None:
        got = {}
    n_exp = len(next(iter(expected.values())))
    n_got = max((len(v) for v in got.values()), default=0)
    wrong = 0
    for name in set(expected) | set(got):
        a, b = got.get(name), expected.get(name)
        if a is None or b is None:
            wrong += max(n_exp, n_got)
            continue
        wrong += abs(len(a) - len(b)) + sum(
            1 for x, y in zip(a, b) if type(x) is not type(y) or x != y)
    return wrong


def compare(results, expected, canonical):
    """``results``: one entry per query the window started — its result,
    or None where it raised.  Returns the numbers compared, each beside
    its limit, and whether all of them hold."""
    per_query = [cells_wrong(canonical(r) if r is not None else None, expected) for r in results]
    numbers = {
        "queries_wrong": sum(1 for w in per_query if w),
        "cells_wrong": sum(per_query),
    }
    compared = {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}
    return compared, all(v <= LIMITS[k] for k, v in numbers.items())
