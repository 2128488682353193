"""The comparison that decides ``correct``.

Every query the window finished left its result as python values (the
D2H of ``batch_to_pydict``).  Each is set against the reference's
result for the same seeded data, cell by cell and exactly: decimals
are unscaled integers on both sides, so there is no tolerance and the
limit of every number compared is 0.

The one exception is a float column (a Spark ``double``, such as the
``avg`` of an int column): a float64 division on the TPU is emulated
and may differ from numpy's in the last place.  A query module may
state ``TOLERANCE = {column: {"rel": r, "abs": a, "why": "..."}}`` for
such a column, and a cell of it counts as wrong where the two floats
differ by more than ``a + r * |reference|``.  Only where both values
are python floats: a float against an int or a None is wrong as ever,
and a tolerance named for a column whose reference values are not
floats raises.  The limits stay 0: a cell is wrong or it is not.
"""

LIMITS = {"queries_wrong": 0, "cells_wrong": 0}


def _differ(x, y, tol):
    """Whether one cell ``x`` differs from the reference's ``y``."""
    if type(x) is not type(y):
        return True
    if tol is not None and type(y) is float:
        return not (x == y or abs(x - y) <= tol["abs"] + tol["rel"] * abs(y))
    return x != y


def cells_wrong(got, expected, tolerance=None):
    """How many cells of ``got`` differ from ``expected`` (both column ->
    list of python values, rows in canonical order).  A row or a column
    that one side lacks counts with all its cells.  ``tolerance``: the
    query module's ``TOLERANCE``, or None."""
    tolerance = tolerance or {}
    for name in tolerance:
        if not all(type(y) is float or y is None for y in expected[name]):
            raise TypeError(f"a tolerance is for a float column; the reference's {name!r} is not one")
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
        tol = tolerance.get(name)
        wrong += abs(len(a) - len(b)) + sum(1 for x, y in zip(a, b) if _differ(x, y, tol))
    return wrong


def compare(results, expected, canonical, tolerance=None):
    """``results``: one entry per query the window started — its result,
    or None where it raised.  Returns the numbers compared, each beside
    its limit, and whether all of them hold."""
    per_query = [cells_wrong(canonical(r) if r is not None else None, expected, tolerance)
                 for r in results]
    numbers = {
        "queries_wrong": sum(1 for w in per_query if w),
        "cells_wrong": sum(per_query),
    }
    compared = {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}
    return compared, all(v <= LIMITS[k] for k, v in numbers.items())
