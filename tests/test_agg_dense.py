"""The sort-free dense grouped update against the sort path, bit for bit.

Each case streams the same batches twice through PARTIAL -> FINAL: once
as they are — every group is in the seed batch, the proven group count
is under ``DENSE_SLOTS`` and every update takes ``dense_update`` — and
once behind a seed batch of ``DENSE_SLOTS + 4`` other keys, which keeps
the stream on ``grouped_update`` by the test's own construction.  The
rows of the groups both runs share are compared as python values, and
against a plain python fold where the aggregate has one.

A held slot that no row of a batch matches is the one state the dense
segments have and the sorted ones never do (an empty segment): the
``absent_*`` and ``all_null_*`` cases leave groups out of later batches
and give a group nothing but NULLs, for every aggregate the dense path
admits.
"""

import numpy as np
import pytest

from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
from blaze_tpu.exprs import col, lit
from blaze_tpu.ops import AggExec, AggFunction, AggMode, GroupingExpr, MemoryScanExec
from blaze_tpu.ops.agg import DENSE_SLOTS
from blaze_tpu.runtime import dispatch
from blaze_tpu.runtime.context import TaskContext
from blaze_tpu.schema import DataType, Field, Schema

ROWS = 300  # a batch; the capacity bucket is 1,024, so every batch is padded
N_BATCHES = 5
WIDE = DataType.decimal(15, 2)  # sums to decimal(25, 2): two int64 limbs


def _values(rng, n, nulls, bound):
    v = [int(x) for x in rng.integers(-bound, bound, n)]
    if nulls:
        v = [None if rng.random() < 0.2 else x for x in v]
    return v


def _case(key_types, keys, extras, aggs, v_type=DataType.int64(), nulls=False,
          pre_filter=None, tail=ROWS, absent=None, all_null=(), bound=10**11):
    """``absent``: {index into keys: the batches (after the seed) that
    hold no row of that group}; ``all_null``: indexes of the groups
    whose every value is NULL; ``bound``: |v| stays under it."""
    return dict(key_types=key_types, keys=keys, extras=extras, aggs=aggs, v_type=v_type,
                nulls=nulls, pre_filter=pre_filter, tail=tail, absent=absent or {},
                all_null=all_null, bound=bound)


INT_KEYS = [(1,), (2,), (3,)]
INT_EXTRAS = [(10_000 + i,) for i in range(DENSE_SLOTS + 4)]
INT = [DataType.int64()]
FLAGS = [("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]  # q01's own groups

CASES = {
    "int64_sum": _case(INT, INT_KEYS, INT_EXTRAS, [("sum", "v")]),
    "wide_decimal_sum": _case(INT, INT_KEYS, INT_EXTRAS, [("sum", "v")], v_type=WIDE),
    "wide_avg": _case(INT, INT_KEYS, INT_EXTRAS, [("avg", "v")], v_type=WIDE, nulls=True),
    "count_with_nulls": _case(INT, INT_KEYS, INT_EXTRAS, [("count", "v")], nulls=True),
    "count_star": _case(INT, INT_KEYS, INT_EXTRAS, [("count_star", None)], nulls=True),
    "numeric_min": _case(INT, INT_KEYS, INT_EXTRAS, [("min", "v")], nulls=True),
    "numeric_max": _case(INT, INT_KEYS, INT_EXTRAS, [("max", "v")], v_type=WIDE, nulls=True),
    "null_group_keys": _case(INT, INT_KEYS + [(None,)], INT_EXTRAS,
                             [("sum", "v"), ("count_star", None)]),
    "string_group_keys": _case(
        [DataType.string(8), DataType.string(8)], FLAGS,
        [("z%02d" % i, "zz") for i in range(DENSE_SLOTS + 4)],
        [("sum", "v"), ("avg", "v"), ("count_star", None)], v_type=WIDE),
    "padded_tail_rows": _case(INT, INT_KEYS, INT_EXTRAS,
                              [("sum", "v"), ("min", "v"), ("count", "v")], nulls=True, tail=77),
    "pre_filter": _case(INT, INT_KEYS, INT_EXTRAS, [("sum", "v"), ("count_star", None)],
                        pre_filter=col("v") > lit(0)),
    "every_slot_held": _case(INT, [(i,) for i in range(DENSE_SLOTS)], INT_EXTRAS,
                             [("sum", "v"), ("max", "v")]),
    # groups that skip batches: empty slots in those updates
    "absent_group_sums_and_counts": _case(
        INT, INT_KEYS, INT_EXTRAS,
        [("sum", "v"), ("avg", "v"), ("count", "v"), ("count_star", None)],
        nulls=True, absent={0: [1, 3], 2: [2, 3, 4]}),
    "absent_group_wide_sum_avg": _case(
        INT, INT_KEYS, INT_EXTRAS, [("sum", "v"), ("avg", "v")], v_type=WIDE,
        nulls=True, absent={1: [1, 2], 2: [4]}),
    "absent_group_min_max": _case(
        INT, INT_KEYS, INT_EXTRAS, [("min", "v"), ("max", "v")],
        nulls=True, absent={0: [1, 3], 2: [2, 3, 4]}),
    # a group with nothing but NULLs: min/max/sum stay NULL, whether
    # the group is in the batch or not
    "all_null_group_min_max": _case(
        INT, INT_KEYS, INT_EXTRAS, [("min", "v"), ("max", "v")],
        nulls=True, all_null=[1]),
    "all_null_absent_group_min_max": _case(
        INT, INT_KEYS, INT_EXTRAS, [("min", "v"), ("max", "v")],
        nulls=True, all_null=[1], absent={1: [1, 3], 0: [2]}),
    "all_null_absent_group_wide_min_max_sum": _case(
        INT, INT_KEYS + [(None,)], INT_EXTRAS, [("min", "v"), ("max", "v"), ("sum", "v")],
        v_type=WIDE, nulls=True, all_null=[0, 3], absent={0: [2, 4], 3: [1, 2, 3]}),
    "all_null_absent_group_sums_and_counts": _case(
        INT, INT_KEYS, INT_EXTRAS,
        [("sum", "v"), ("avg", "v"), ("count", "v"), ("count_star", None)],
        nulls=True, all_null=[2], absent={2: [1, 4]}),
    "date_min_max_absent_and_all_null": _case(
        INT, INT_KEYS, INT_EXTRAS, [("min", "v"), ("max", "v"), ("count", "v")],
        v_type=DataType.date32(), nulls=True, all_null=[0], absent={0: [3], 1: [1, 2]},
        bound=20_000),
}


def _schema(case):
    return Schema([Field(f"k{i}", t) for i, t in enumerate(case["key_types"])]
                  + [Field("v", case["v_type"])])


def _batches(case, seed):
    """Batches whose first rows are one group apiece, every group but
    the case's ``absent`` ones (the seed batch holds them all); the
    last batch has ``tail`` rows."""
    rng = np.random.default_rng(seed)
    keys, schema = case["keys"], _schema(case)
    out = []
    for b in range(N_BATCHES):
        n = case["tail"] if b == N_BATCHES - 1 else ROWS
        here = [i for i in range(len(keys)) if b not in case["absent"].get(i, ())]
        picks = here + [here[int(x)] for x in rng.integers(0, len(here), n - len(here))]
        data = {f"k{i}": [keys[p][i] for p in picks] for i in range(len(case["key_types"]))}
        data["v"] = [None if p in case["all_null"] else v
                     for p, v in zip(picks, _values(rng, n, case["nulls"], case["bound"]))]
        out.append(batch_from_pydict(data, schema))
    return out


def _extras_batch(case):
    extras, schema = case["extras"], _schema(case)
    data = {f"k{i}": [e[i] for e in extras] for i in range(len(case["key_types"]))}
    data["v"] = [7] * len(extras)
    return batch_from_pydict(data, schema)


def _aggs(case):
    return [AggFunction(fn, None if c is None else col(c), f"a{i}")
            for i, (fn, c) in enumerate(case["aggs"])]


def _run(case, batches):
    """PARTIAL -> FINAL over one partition: {group key: aggregate row}
    and the dispatch tally."""
    n_keys = len(case["key_types"])
    groupings = [GroupingExpr(col(f"k{i}"), f"k{i}") for i in range(n_keys)]
    partial = AggExec(MemoryScanExec([batches], _schema(case)), AggMode.PARTIAL, groupings,
                      _aggs(case), pre_filter=case["pre_filter"])
    final = AggExec(partial, AggMode.FINAL, groupings, _aggs(case))
    rows = {}
    with dispatch.capture() as tally:
        for b in final.execute(0, TaskContext(0, 1)):
            d = batch_to_pydict(b)
            for row in zip(*d.values()):
                assert row[:n_keys] not in rows, "FINAL emitted a group twice"
                rows[row[:n_keys]] = row[n_keys:]
    return rows, tally


def _reference(case, batches):
    """A python fold of the aggregates that have a plain one (not avg:
    its rounding is the finalize kernel's, the same on both paths)."""
    n_keys = len(case["key_types"])
    groups = {}
    for b in batches:
        d = batch_to_pydict(b)
        for row in zip(*d.values()):
            v = row[n_keys]
            if case["pre_filter"] is not None and not (v is not None and v > 0):
                continue
            groups.setdefault(row[:n_keys], []).append(v)
    fold = {
        "sum": lambda vs: sum(x for x in vs if x is not None) if any(x is not None for x in vs) else None,
        "count": lambda vs: sum(x is not None for x in vs),
        "count_star": len,
        "min": lambda vs: min((x for x in vs if x is not None), default=None),
        "max": lambda vs: max((x for x in vs if x is not None), default=None),
    }
    return {k: tuple(fold[fn](vs) if fn in fold else None for fn, _ in case["aggs"])
            for k, vs in groups.items()}


@pytest.mark.parametrize("name", sorted(CASES))
def test_dense_update_equals_sort_update(name):
    case = CASES[name]
    batches = _batches(case, seed=sorted(CASES).index(name))

    dense, tally = _run(case, batches)
    assert tally.get("agg_dense_updates", 0) == tally.get("agg_grouped_updates", 0) == N_BATCHES - 1, tally
    assert tally.get("fused_agg_rollbacks", 0) == 0, tally

    behind_extras, tally = _run(case, [_extras_batch(case)] + batches)
    assert tally.get("agg_dense_updates", 0) == 0, tally
    assert tally.get("agg_grouped_updates", 0) == N_BATCHES, tally
    sort = {k: v for k, v in behind_extras.items() if k not in set(case["extras"])}
    assert len(sort) == len(behind_extras) - len(case["extras"])

    assert set(dense) == set(case["keys"])
    assert dense == sort
    for key, want in _reference(case, batches).items():
        got = [g for g, (fn, _) in zip(dense[key], case["aggs"]) if fn != "avg"]
        assert got == [w for w, (fn, _) in zip(want, case["aggs"]) if fn != "avg"], (key, dense[key], want)


@pytest.mark.parametrize("name", [
    "string_group_keys", "absent_group_sums_and_counts", "absent_group_min_max",
    "all_null_absent_group_min_max", "all_null_absent_group_wide_min_max_sum"])
def test_dense_update_in_the_merge_modes(name):
    """FINAL over several PARTIAL state batches of the same few groups
    folds them densely too (the merge form of the same reduces) — a
    group that a batch lacks is missing from that state batch — and
    agrees with the sort path behind a seed of other keys."""
    case = CASES[name]
    n_keys = len(case["key_types"])
    groupings = [GroupingExpr(col(f"k{i}"), f"k{i}") for i in range(n_keys)]

    def states(batches):
        out = []
        for b in batches:
            partial = AggExec(MemoryScanExec([[b]], _schema(case)), AggMode.PARTIAL, groupings,
                              _aggs(case))
            out.extend(partial.execute(0, TaskContext(0, 1)))
        return out

    def final(state_batches):
        agg = AggExec(MemoryScanExec([state_batches]), AggMode.FINAL, groupings, _aggs(case))
        rows = {}
        with dispatch.capture() as tally:
            for b in agg.execute(0, TaskContext(0, 1)):
                d = batch_to_pydict(b)
                rows.update({r[:n_keys]: r[n_keys:] for r in zip(*d.values())})
        return rows, tally

    batches = _batches(case, seed=99)
    dense, tally = final(states(batches))
    assert tally.get("agg_dense_updates", 0) == N_BATCHES - 1, tally
    sort, tally = final(states([_extras_batch(case)] + batches))
    assert tally.get("agg_dense_updates", 0) == 0 and tally.get("agg_grouped_updates", 0) > 0, tally
    assert dense == {k: v for k, v in sort.items() if k in set(case["keys"])}
    assert dense == _run(case, batches)[0]
