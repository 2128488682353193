"""TPC-DS Q7 (``query7.tpl``): average quantity, list price, coupon
amount and sales price by ``i_item_id``, of store sales to single male
college-educated customers in 2000 under promotions with no e-mail or no
event channel; the first 100 by ``i_item_id``.

The reference is plain numpy and python integers.  Why exact integers
are Spark's answer: Spark 3.5.1 plans ``avg`` of a decimal(7,2) as
``cast((avg(UnscaledValue(x)) / 100.0) as decimal(11,6))`` (its
``DecimalAggregates`` rule), so it divides doubles — but the cast goes
through the double's shortest decimal representation and rounds that
HALF_UP to six digits.  The doubles carry 15 significant digits of a
value under 10^5 with six decimals, so the cast differs from the exact
HALF_UP of sum x 10^4 / n only where that quotient lies exactly on a
half; 2 x 10^4 = 2^5 x 5^4, so a tie needs a group of 32 k rows, and q7's
groups at SF1 have one to a handful.  ``agg1`` is ``avg(ss_quantity)``,
a double: the sum over the count as a python float.
"""

import numpy as np

COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk", "ss_promo_sk",
                    "ss_quantity", "ss_list_price", "ss_sales_price", "ss_coupon_amt"],
    "customer_demographics": ["cd_demo_sk", "cd_gender", "cd_marital_status",
                              "cd_education_status"],
    "date_dim": ["d_date_sk", "d_year"],
    "item": ["i_item_sk", "i_item_id"],
    "promotion": ["p_promo_sk", "p_channel_email", "p_channel_event"],
}

OUT = ("i_item_id", "agg1", "agg2", "agg3", "agg4")
#: agg2..agg4 in the order the query lists them
MEASURES = ("ss_list_price", "ss_coupon_amt", "ss_sales_price")
LIMIT = 100

TOLERANCE = {
    "agg1": {"rel": 1e-9, "abs": 0,
             "why": "avg(ss_quantity) is a Spark double: sum / count in float64, which the TPU "
                    "emulates and may round in the last places; float32 is 6e-8 away"},
}


def _is(table, column, text):
    data, lengths = table[column][:2]
    want = np.zeros(data.shape[1], np.uint8)
    want[: len(text)] = np.frombuffer(text, np.uint8)
    return (lengths == len(text)) & (data == want).all(axis=1)


def join_masks(tables):
    """The rows of ``store_sales`` alive after each of the four joins,
    in the plan's order (demographics, date, item, promotion), as four
    cumulative masks.  A NULL key joins nothing."""
    ss, cd = tables["store_sales"], tables["customer_demographics"]
    dd, it, pr = tables["date_dim"], tables["item"], tables["promotion"]
    cd_keys = cd["cd_demo_sk"][0][_is(cd, "cd_gender", b"M") & _is(cd, "cd_marital_status", b"S")
                                  & _is(cd, "cd_education_status", b"College")]
    d_keys = dd["d_date_sk"][0][dd["d_year"][0] == 2000]
    p_keys = pr["p_promo_sk"][0][_is(pr, "p_channel_email", b"N") | _is(pr, "p_channel_event", b"N")]
    sides = (("ss_cdemo_sk", cd_keys), ("ss_sold_date_sk", d_keys),
             ("ss_item_sk", it["i_item_sk"][0]), ("ss_promo_sk", p_keys))
    # Spark's filter under the first join: all four keys not NULL
    alive = np.ones(ss["ss_item_sk"][0].shape[0], bool)
    for column, _ in sides:
        alive &= ss[column][2]
    masks = []
    for column, keys in sides:
        alive = alive & np.isin(ss[column][0], keys)
        masks.append(alive)
    return masks


def _groups(tables):
    """(item id bytes, rows of store_sales in that group) in the
    query's order, the first ``LIMIT``."""
    ss, it = tables["store_sales"], tables["item"]
    rows = np.flatnonzero(join_masks(tables)[-1])
    isk = it["i_item_sk"][0]  # ascending, unique
    ids = it["i_item_id"][0][np.searchsorted(isk, ss["ss_item_sk"][0][rows])]
    by_id = {}
    for r, key in zip(rows.tolist(), map(bytes, ids)):
        by_id.setdefault(key, []).append(r)
    return sorted(by_id.items())[:LIMIT]


def _result(rows):
    return {name: [r[i] for r in rows] for i, name in enumerate(OUT)}


def oracle(tables):
    """Integer sums and counts per group; ``agg2..4`` the exact HALF_UP
    of sum x 10^4 / n as unscaled decimal(11,6)."""
    ss = tables["store_sales"]
    out = []
    for key, rows in _groups(tables):
        n = len(rows)
        avgs = [(int(ss[c][0][rows].sum()) * 10**4 * 2 + n) // (2 * n) for c in MEASURES]  # >= 0
        out.append((key.decode(), int(ss["ss_quantity"][0][rows].sum()) / n, *avgs))
    return _result(out)


def control(tables):
    """Every sum, division and the cast carried in float32."""
    ss = tables["store_sales"]
    f32 = np.float32
    out = []
    for key, rows in _groups(tables):
        n = f32(len(rows))
        total = lambda c: ss[c][0][rows].astype(f32).sum(dtype=f32)
        avgs = [int(np.floor(total(c) / n / f32(100) * f32(10**6) + f32(0.5))) for c in MEASURES]
        out.append((key.decode(), float(total("ss_quantity") / n), *avgs))
    return _result(out)


def canonical(result):
    """The query orders by ``i_item_id``, which is unique in its
    result: rows are compared as they come."""
    return result
