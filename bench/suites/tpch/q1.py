"""TPC-H Q1: pricing summary report — sums, exact decimal averages and
a count over the (returnflag, linestatus) groups of ``lineitem``."""

import numpy as np

from .datagen import _days

COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                        "l_returnflag", "l_linestatus", "l_shipdate"]}

MEASURES = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
            "avg_qty", "avg_price", "avg_disc", "count_order")


def _groups(li):
    """(mask of the rows the query keeps, group code per row).  Both
    flags are one character wide in the spec, so the first byte is the
    whole value."""
    for c in ("l_returnflag", "l_linestatus"):
        assert (li[c][1] == 1).all(), c
    mask = li["l_shipdate"][0] <= _days(1998, 9, 2)
    code = li["l_returnflag"][0][:, 0].astype(np.int32) * 256 + li["l_linestatus"][0][:, 0]
    return mask, code


def _exact_sum(values) -> int:
    """Sum of non-negative int64 as a python int: in int64 where the
    bound on the total shows it cannot wrap, else as bignums."""
    if values.shape[0] == 0:
        return 0
    if int(values.max()) * values.shape[0] < 2**62:
        return int(values.sum())
    return int(values.astype(object).sum())


def _result(rows):
    rows.sort(key=lambda r: (r["l_returnflag"], r["l_linestatus"]))
    return {k: [r[k] for r in rows] for k in ("l_returnflag", "l_linestatus") + MEASURES}


def oracle(tables):
    """Sums of decimals as unscaled integers (disc_price at scale 4,
    charge at scale 6); averages as Spark's decimal avg does them:
    sum x 10^4 over the count, HALF_UP, exact in integers."""
    li = tables["lineitem"]
    mask, code = _groups(li)
    qty, ext = li["l_quantity"][0], li["l_extendedprice"][0]
    disc, tax = li["l_discount"][0], li["l_tax"][0]
    disc_price = ext * (100 - disc)     # under 2^27 a row
    charge = disc_price * (100 + tax)   # under 2^34 a row
    rows = []
    for g in np.unique(code[mask]):
        m = mask & (code == g)
        n = int(m.sum())
        sums = dict(sum_qty=_exact_sum(qty[m]), sum_base_price=_exact_sum(ext[m]),
                    sum_disc_price=_exact_sum(disc_price[m]), sum_charge=_exact_sum(charge[m]))
        avg = lambda s: (s * 10**4 + n // 2) // n  # measures are non-negative
        rows.append(dict(l_returnflag=chr(g // 256), l_linestatus=chr(g % 256), **sums,
                         avg_qty=avg(sums["sum_qty"]), avg_price=avg(sums["sum_base_price"]),
                         avg_disc=avg(_exact_sum(disc[m])), count_order=n))
    return _result(rows)


def control(tables):
    """Every sum and average carried in float32."""
    li = tables["lineitem"]
    mask, code = _groups(li)
    f32 = lambda name: li[name][0].astype(np.float32)
    qty, ext, disc, tax = f32("l_quantity"), f32("l_extendedprice"), f32("l_discount"), f32("l_tax")
    disc_price = ext * (np.float32(100) - disc)
    charge = disc_price * (np.float32(100) + tax)
    rows = []
    for g in np.unique(code[mask]):
        m = mask & (code == g)
        n = int(m.sum())
        total = lambda v: v[m].sum(dtype=np.float32)
        avg = lambda v: int(np.floor(total(v) / np.float32(n) * np.float32(10**4) + np.float32(0.5)))
        rows.append(dict(l_returnflag=chr(g // 256), l_linestatus=chr(g % 256),
                         sum_qty=int(total(qty)), sum_base_price=int(total(ext)),
                         sum_disc_price=int(total(disc_price)), sum_charge=int(total(charge)),
                         avg_qty=avg(qty), avg_price=avg(ext), avg_disc=avg(disc), count_order=n))
    return _result(rows)


def canonical(result):
    """The query orders its rows itself; they are compared as they come."""
    return result
