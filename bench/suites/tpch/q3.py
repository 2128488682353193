"""TPC-H Q3: shipping priority — customer x orders x lineitem, revenue
per order, the ten largest."""

import numpy as np

from .datagen import _days

COLUMNS = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"],
}

OUT = ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
SEGMENT = b"BUILDING"
CUTOFF = _days(1995, 3, 15)


def _top10(tables, dtype):
    """Revenue (scale 4) per qualifying order, summed in ``dtype``."""
    cu, orders, li = tables["customer"], tables["orders"], tables["lineitem"]
    seg, seg_len = cu["c_mktsegment"]
    want = np.zeros(seg.shape[1], np.uint8)
    want[: len(SEGMENT)] = np.frombuffer(SEGMENT, np.uint8)
    building = (seg_len == len(SEGMENT)) & (seg == want).all(axis=1)
    om = (orders["o_orderdate"][0] < CUTOFF) & np.isin(orders["o_custkey"][0], cu["c_custkey"][0][building])
    okeys = orders["o_orderkey"][0][om]  # ascending, unique
    odate = orders["o_orderdate"][0][om]
    oprio = orders["o_shippriority"][0][om]

    lm = li["l_shipdate"][0] > CUTOFF
    lkey = li["l_orderkey"][0][lm]
    rev = li["l_extendedprice"][0][lm].astype(dtype) * (100 - li["l_discount"][0][lm]).astype(dtype)
    pos = np.minimum(np.searchsorted(okeys, lkey), max(okeys.shape[0] - 1, 0))
    hit = (okeys[pos] == lkey) if okeys.shape[0] else np.zeros(lkey.shape[0], bool)
    total = np.zeros(okeys.shape[0], dtype)
    np.add.at(total, pos[hit], rev[hit])
    has = np.bincount(pos[hit], minlength=okeys.shape[0]) > 0

    rows = [(int(k), int(r), int(d), int(p))
            for k, r, d, p in zip(okeys[has], total[has], odate[has], oprio[has])]
    rows.sort(key=lambda t: (-t[1], t[2], t[0]))
    return {name: [r[i] for r in rows[:10]] for i, name in enumerate(OUT)}


def oracle(tables):
    """Unscaled int64 throughout: a line's revenue is under 2^27 and an
    order has at most seven lines."""
    return _top10(tables, np.int64)


def control(tables):
    return _top10(tables, np.float32)


def canonical(result):
    """The query orders by revenue (descending) and order date; rows
    that tie on both may come in either order, so ties go by order key
    here.  A result whose revenue is NOT descending keeps its own order
    and so differs from the reference row by row."""
    rows = list(zip(*(result[name] for name in OUT)))
    if all(a[1] >= b[1] for a, b in zip(rows, rows[1:])):
        rows.sort(key=lambda t: (-t[1], t[2], t[0]))
    return {name: [r[i] for r in rows] for i, name in enumerate(OUT)}
