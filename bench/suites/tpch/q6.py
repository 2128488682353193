"""TPC-H Q6: forecasting revenue change — one global sum over a
filtered ``lineitem``."""

import numpy as np

from .datagen import _days

#: what a column-pruned Spark scan would hand the engine
COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]}


def _selected(li):
    return (
        (li["l_shipdate"][0] >= _days(1994, 1, 1))
        & (li["l_shipdate"][0] < _days(1995, 1, 1))
        & (li["l_discount"][0] >= 5)
        & (li["l_discount"][0] <= 7)
        & (li["l_quantity"][0] < 2400)
    )


def oracle(tables):
    """decimal(12,2) x decimal(12,2) as unscaled int64, summed exactly."""
    li = tables["lineitem"]
    m = _selected(li)
    return {"revenue": [int((li["l_extendedprice"][0][m] * li["l_discount"][0][m]).sum())]}


def control(tables):
    """The same sum carried in float32, the chip's native width."""
    li = tables["lineitem"]
    m = _selected(li)
    rev = li["l_extendedprice"][0][m].astype(np.float32) * li["l_discount"][0][m].astype(np.float32)
    return {"revenue": [int(rev.sum(dtype=np.float32))]}


def canonical(result):
    return result
