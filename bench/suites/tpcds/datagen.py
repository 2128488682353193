"""The benchmark's own seeded TPC-DS generator (vectorized numpy), for
the tables its queries read: ``store_sales``, ``customer_demographics``,
``date_dim``, ``item``, ``promotion``.  It imports nothing of the
program and is not a copy of the program's ``tpcds/datagen.py`` (whose
``customer_demographics`` has 280 rows and whose NULL keys are -1).

Physical form as ``bench/suites/tpch/datagen.py``: decimals as unscaled
int64, strings as ``(N, W)`` uint8 + lengths; a nullable column is
``(data, lengths, validity)`` with REAL validity (what lies under a
NULL is an ordinary key, so a program that ignored validity would join
it and read wrong).

At scale 1 every table has the specification's SF1 cardinality
(``ROWS_SF1``).  Scales above 1 are refused: the specification scales
its dimensions by a table, not by a factor.  Below 1, for tests on the
CPU only, ``store_sales`` and ``item`` shrink with the scale and
``customer_demographics`` keeps the first rows of its cross product (a
multiple of 70, so the q7 slice is still one row in 70); ``date_dim``
and ``promotion`` keep their size.  SF1 is untouched by that.

Not dsdgen-exact; every departure is listed in the configuration's
``assumed``.  Each group of columns draws from a child stream of its
own, so a pruned table is a projection of the full one for the same
seed, and a column added later moves no column that is here.
"""

from __future__ import annotations

import datetime
import zlib
from typing import Dict, Optional, Tuple

import numpy as np

#: rows at scale factor 1 (TPC-DS specification v3, table 3-2)
ROWS_SF1 = {
    "store_sales": 2_880_404,
    "customer_demographics": 1_920_800,
    "date_dim": 73_049,
    "item": 18_000,
    "promotion": 300,
}

#: customer_demographics is the full cross product of its attributes;
#: cd_demo_sk - 1 is the mixed-radix number of the row, first attribute
#: fastest (dsdgen's order)
GENDERS = ["M", "F"]
MARITAL = ["M", "S", "D", "W", "U"]
EDUCATION = ["Primary", "Secondary", "College", "2 yr Degree", "4 yr Degree",
             "Advanced Degree", "Unknown"]
CREDIT = ["Good", "Low Risk", "High Risk", "Unknown"]
CD_RADICES = (len(GENDERS), len(MARITAL), len(EDUCATION), 20, len(CREDIT), 7, 7, 7)
assert int(np.prod(CD_RADICES)) == ROWS_SF1["customer_demographics"]
#: rows in one period of the three string attributes
CD_PERIOD = len(GENDERS) * len(MARITAL) * len(EDUCATION)

#: date_dim: d_date_sk is the Julian day number; the table runs from
#: 1900-01-02 for 73,049 days (to 2100-01-01)
DATE_SK0 = 2_415_022
DATE0 = datetime.date(1900, 1, 2)
#: store_sales are sold on 1998-01-02 .. 2003-01-02 (5 years: SF1's span)
SOLD_FIRST = DATE_SK0 + (datetime.date(1998, 1, 2) - DATE0).days
SOLD_LAST = DATE_SK0 + (datetime.date(2003, 1, 2) - DATE0).days

#: dsdgen's NULLs (nulls.c ``nullSet``): a row of store_sales is picked
#: with probability NULL_ROW_BP / 10000; a picked row draws a random
#: bit mask, and each nullable column whose bit is set is NULL.  So a
#: nullable column is NULL in NULL_ROW_BP / 2 of 10000 rows: 4.5%.
NULL_ROW_BP = 900
NULL_SHARE = NULL_ROW_BP / 2 / 10000
#: the nullable foreign keys and the mask bit each reads
SS_NULLABLE = {"ss_sold_date_sk": 0, "ss_item_sk": 1, "ss_cdemo_sk": 2, "ss_promo_sk": 3}

HostTable = Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]


def rows(table: str, scale: float) -> int:
    """Rows of ``table`` at ``scale`` (see the module docstring for
    what shrinks below 1)."""
    if scale > 1:
        raise ValueError("scales above 1 need the specification's dimension table; not here")
    full = ROWS_SF1[table]
    if scale == 1 or table in ("date_dim", "promotion"):
        return full
    if table == "customer_demographics":
        return max(CD_PERIOD, int(full * scale) // CD_PERIOD * CD_PERIOD)
    if table == "item":
        return max(2, int(full * scale) // 2 * 2)
    return max(1, int(full * scale))


def _encode_options(options, width):
    data = np.zeros((len(options), width), np.uint8)
    lengths = np.zeros(len(options), np.int32)
    for i, s in enumerate(options):
        b = s.encode()
        data[i, : len(b)] = np.frombuffer(b, np.uint8)
        lengths[i] = len(b)
    return data, lengths


def _pick(options, width, idx):
    data, lengths = _encode_options(options, width)
    return data[idx], lengths[idx]


def business_key(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """dsdgen's 16-character business key (``mk_bkey``): one letter
    A..P per 4 bits, the high word's eight first (all A here), then the
    low word's, least significant first."""
    data = np.full((keys.shape[0], 16), ord("A"), np.uint8)
    for i in range(8):
        data[:, 8 + i] += ((keys >> (4 * i)) & 15).astype(np.uint8)
    return data, np.full(keys.shape[0], 16, np.int32)


def generate_table(name: str, scale: float, seed: int = 20011129, columns=None) -> HostTable:
    """One table from ``seed``; ``columns`` (a collection of names, or
    None for all) says which of its columns to materialize."""
    table = _generate(name, scale, seed, (lambda c: True) if columns is None else columns.__contains__)
    return {k: v for k, v in table.items() if v is not None and (columns is None or k in columns)}


def _generate(name, scale, seed, want):
    rng = np.random.RandomState((seed + zlib.crc32(name.encode())) % (2**31))
    n = rows(name, scale)
    if name == "customer_demographics":
        # no draw: the table is its cross product
        sk = np.arange(1, n + 1, dtype=np.int64)
        digits, rest = [], sk - 1
        for radix in CD_RADICES:
            digits.append(rest % radix)
            rest = rest // radix
        i32 = lambda a: (a.astype(np.int32), None)
        return {
            "cd_demo_sk": (sk, None),
            "cd_gender": _pick(GENDERS, 8, digits[0]) if want("cd_gender") else None,
            "cd_marital_status": _pick(MARITAL, 8, digits[1]) if want("cd_marital_status") else None,
            "cd_education_status": (_pick(EDUCATION, 24, digits[2])
                                    if want("cd_education_status") else None),
            "cd_purchase_estimate": i32((digits[3] + 1) * 500),
            "cd_credit_rating": _pick(CREDIT, 16, digits[4]) if want("cd_credit_rating") else None,
            "cd_dep_count": i32(digits[5]),
            "cd_dep_employed_count": i32(digits[6]),
            "cd_dep_college_count": i32(digits[7]),
        }
    if name == "date_dim":
        day = np.arange(n)
        dates = np.datetime64(DATE0) + day
        year = dates.astype("datetime64[Y]").astype(np.int64) + 1970
        month = dates.astype("datetime64[M]").astype(np.int64) % 12 + 1
        return {
            "d_date_sk": ((DATE_SK0 + day).astype(np.int64), None),
            "d_date": ((dates - np.datetime64("1970-01-01")).astype(np.int32), None),
            "d_year": (year.astype(np.int32), None),
            "d_moy": (month.astype(np.int32), None),
        }
    if name == "item":
        sk = np.arange(1, n + 1, dtype=np.int64)
        # a slowly changing dimension: two revisions of each business key
        return {
            "i_item_sk": (sk, None),
            "i_item_id": business_key((sk + 1) // 2) if want("i_item_id") else None,
        }
    if name == "promotion":
        sk = np.arange(1, n + 1, dtype=np.int64)
        flag = lambda: _pick(["N", "Y"], 8, (rng.randint(0, 4, n) == 0).astype(np.int64))
        return {
            "p_promo_sk": (sk, None),
            "p_channel_email": flag(),
            "p_channel_event": flag(),
        }
    # rows() has refused every other name
    return _gen_store_sales(rng, n, scale)


def _gen_store_sales(rng, n, scale):
    # one child stream per group of columns, 16 reserved: a later column
    # takes the next free one and moves none of these
    child = [np.random.RandomState(s) for s in rng.randint(2**31, size=16)]
    i64 = lambda a: a.astype(np.int64, copy=False)

    picked = child[0].randint(0, 10000, n) < NULL_ROW_BP
    mask = child[0].randint(1, 2**31, n)

    def fk(name, values):
        return (i64(values), None, ~(picked & ((mask >> SS_NULLABLE[name]) & 1).astype(bool)))

    sold = child[1].randint(SOLD_FIRST, SOLD_LAST + 1, n)
    item = child[2].randint(1, rows("item", scale) + 1, n)
    cdemo = child[3].randint(1, rows("customer_demographics", scale) + 1, n)
    promo = child[4].randint(1, rows("promotion", scale) + 1, n)

    # dsdgen's set_pricing for store_sales, in cents: wholesale 1.00 ..
    # 100.00, list = wholesale marked up 0..200%, sales = list less a
    # discount of 0..100%, a coupon on one sale in five for 0..100% of
    # the extended sales price
    price = child[5]
    quantity = price.randint(1, 101, n)
    wholesale = i64(price.randint(100, 10001, n))
    list_price = wholesale * (100 + price.randint(0, 201, n)) // 100
    sales_price = list_price * (100 - price.randint(0, 101, n)) // 100
    ext_sales = sales_price * quantity
    coupon = np.where(price.randint(1, 101, n) <= 20, ext_sales * price.randint(0, 101, n) // 100, 0)
    return {
        "ss_sold_date_sk": fk("ss_sold_date_sk", sold),
        "ss_item_sk": fk("ss_item_sk", item),
        "ss_cdemo_sk": fk("ss_cdemo_sk", cdemo),
        "ss_promo_sk": fk("ss_promo_sk", promo),
        "ss_quantity": (quantity.astype(np.int32), None),
        "ss_wholesale_cost": (wholesale, None),
        "ss_list_price": (i64(list_price), None),
        "ss_sales_price": (i64(sales_price), None),
        "ss_ext_sales_price": (i64(ext_sales), None),
        "ss_coupon_amt": (i64(coupon), None),
    }
