"""``compare``: exact for every column but a float one whose query
module states a ``TOLERANCE``; the limits stay 0."""

import pytest

from bench import compare

EXPECTED = {"k": ["a", "b"], "n": [3, 4], "avg": [2.5, 1e6 / 3], "dec": [12345, None]}
TOLERANCE = {"avg": {"rel": 1e-9, "abs": 0.0, "why": "float64 division, emulated on the chip"}}


def _with(column, row, value):
    got = {k: list(v) for k, v in EXPECTED.items()}
    got[column][row] = value
    return got


def test_limits_are_unchanged():
    assert compare.LIMITS == {"queries_wrong": 0, "cells_wrong": 0}


@pytest.mark.parametrize("column,row,value,tolerance,wrong", [
    # no TOLERANCE: exact, the type test included
    ("avg", 1, 1e6 / 3 * (1 + 1e-12), None, 1),
    ("avg", 0, 2.5, None, 0),
    ("n", 0, 3.0, None, 1),            # a float against an int still counts
    ("avg", 0, 2, None, 1),            # and an int against a float
    # a float column inside its tolerance passes, outside it fails
    ("avg", 1, 1e6 / 3 * (1 + 1e-12), TOLERANCE, 0),
    ("avg", 1, 1e6 / 3 * (1 - 5e-10), TOLERANCE, 0),
    ("avg", 1, 1e6 / 3 * (1 + 1e-8), TOLERANCE, 1),
    ("avg", 0, 2.5 + 1e-6, TOLERANCE, 1),
    ("avg", 0, float("nan"), TOLERANCE, 1),
    # only where both are floats: None and int stay wrong under a tolerance
    ("avg", 0, None, TOLERANCE, 1),
    ("avg", 0, 2, TOLERANCE, 1),
    # every other column stays exact beside a tolerance
    ("n", 1, 5, TOLERANCE, 1),
    ("dec", 0, 12346, TOLERANCE, 1),
    ("k", 0, "A", TOLERANCE, 1),
])
def test_cells_wrong_with_and_without_a_tolerance(column, row, value, tolerance, wrong):
    assert compare.cells_wrong(_with(column, row, value), EXPECTED, tolerance) == wrong
    compared, ok = compare.compare([_with(column, row, value)], EXPECTED, lambda r: r, tolerance)
    assert ok is (wrong == 0) and compared["cells_wrong"] == {"value": wrong, "limit": 0}


@pytest.mark.parametrize("column", ["n", "dec", "k"])
def test_a_tolerance_on_a_column_that_is_not_float_raises(column):
    tolerance = {column: {"rel": 1e-9, "abs": 1.0, "why": "decimals stay exact"}}
    with pytest.raises(TypeError):
        compare.cells_wrong(EXPECTED, EXPECTED, tolerance)
    with pytest.raises(TypeError):
        compare.compare([EXPECTED], EXPECTED, lambda r: r, tolerance)


def test_a_null_in_a_float_column_is_compared_as_it_is():
    expected = {"avg": [None, 1.5]}
    assert compare.cells_wrong({"avg": [None, 1.5]}, expected, TOLERANCE) == 0
    assert compare.cells_wrong({"avg": [0.0, 1.5]}, expected, TOLERANCE) == 1
