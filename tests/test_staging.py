"""Host-to-device staging of a whole batch: ``RecordBatch.to_device()``
transfers no validity for a column whose every real row is valid and
every padding row is not.  Such columns take one shared device row mask:
the process's mask of the capacity in a full batch, one mask transferred
for the batch in a partial one.  Everything else stages as
``Column.to_device()`` does."""

import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu import batch as batch_mod
from blaze_tpu.batch import (
    Column, RecordBatch, column_from_numpy, column_from_pylist,
    column_from_strings)
from blaze_tpu.schema import DataType, Field, Schema

CAP = 1024


def _flat(n, cap=CAP, null_at=None):
    """Three flat columns of ``n`` rows at capacity ``cap``: int64,
    float64, string; ``null_at`` makes that row of the int64 NULL."""
    valid = np.ones(n, np.bool_)
    if null_at is not None:
        valid[null_at] = False
    schema = Schema([Field("i", DataType.int64()), Field("f", DataType.float64()),
                     Field("s", DataType.string(8))])
    cols = [
        column_from_numpy(DataType.int64(), np.arange(n), valid, cap),
        column_from_numpy(DataType.float64(), np.arange(n) * 0.5, None, cap),
        column_from_strings([f"r{i}" for i in range(n)], width=8, capacity=cap),
    ]
    return RecordBatch(schema, cols, n)


def _nested(n, cap=CAP):
    """An array, a struct and an opaque column, every row valid."""
    arr = DataType.array(DataType.int32(), 4)
    st = DataType.struct([Field("a", DataType.int64())])
    schema = Schema([Field("x", arr), Field("t", st), Field("o", DataType.opaque()),
                     Field("i", DataType.int64())])
    cols = [
        column_from_pylist(arr, [[i, i + 1] for i in range(n)], capacity=cap),
        column_from_pylist(st, [{"a": i} for i in range(n)], capacity=cap),
        column_from_pylist(DataType.opaque(), [("obj", i) for i in range(n)], capacity=cap),
        column_from_numpy(DataType.int64(), np.arange(n), None, cap),
    ]
    return RecordBatch(schema, cols, n)


def _same_buffers(a: Column, b: Column):
    for x, y in ((a.data, b.data), (a.validity, b.validity), (a.lengths, b.lengths)):
        assert (x is None) == (y is None)
        if x is not None:
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and x.shape == y.shape
            assert np.array_equal(x, y)
    assert (a.children is None) == (b.children is None)
    for ca, cb in zip(a.children or (), b.children or ()):
        _same_buffers(ca, cb)


def test_full_batch_columns_take_the_cached_mask_of_their_capacity():
    host = _flat(CAP)
    dev, arrays, shared = host.to_device_counted()
    mask = batch_mod._full_mask(CAP)
    assert all(c.validity is mask for c in dev.columns)
    # data of three columns + the string's lengths; no validity, no mask
    assert (arrays, shared) == (4, 3)
    # a second batch of the same capacity takes the same array
    assert host.to_device().columns[0].validity is mask
    assert isinstance(mask, jnp.ndarray) and mask.dtype == jnp.bool_
    assert mask.shape == (CAP,) and bool(mask.all())


def test_a_column_with_a_null_keeps_its_own_validity():
    host = _flat(CAP, null_at=7)
    dev, arrays, shared = host.to_device_counted()
    mask = batch_mod._full_mask(CAP)
    assert dev.columns[0].validity is not mask
    assert np.array_equal(np.asarray(dev.columns[0].validity), host.columns[0].validity)
    assert dev.columns[1].validity is mask and dev.columns[2].validity is mask
    assert (arrays, shared) == (5, 2)


def test_partial_batch_shares_one_transferred_row_mask():
    n = 300
    host = _flat(n)
    dev, arrays, shared = host.to_device_counted()
    mask = dev.columns[0].validity
    assert all(c.validity is mask for c in dev.columns)
    assert mask is not batch_mod._full_mask(CAP)
    assert np.array_equal(np.asarray(mask), np.arange(CAP) < n)
    # four data/lengths buffers + the one mask
    assert (arrays, shared) == (5, 3)


def test_a_padding_row_set_valid_by_hand_is_not_all_valid():
    n = 300
    host = _flat(n)
    host.columns[1].validity[n + 5] = True
    dev, arrays, shared = host.to_device_counted()
    assert dev.columns[1].validity is not dev.columns[0].validity
    assert np.array_equal(np.asarray(dev.columns[1].validity), host.columns[1].validity)
    assert dev.columns[0].validity is dev.columns[2].validity
    assert (arrays, shared) == (6, 2)


@pytest.mark.parametrize("n", [CAP, 300])
def test_nested_and_opaque_columns_are_unchanged(n):
    host = _nested(n)
    dev, arrays, shared = host.to_device_counted()
    x, t, o, i = dev.columns
    assert o is host.columns[2]  # opaque objects never leave the host
    for c in (x, t):
        assert c.validity is not i.validity
        assert isinstance(c.validity, jnp.ndarray)
    # array: validity + lengths + element data + element validity;
    # struct: validity + field data + field validity; int64: data
    assert arrays == 4 + 3 + 1 + (n < CAP)
    assert shared == 1
    assert (i.validity is batch_mod._full_mask(CAP)) == (n == CAP)


def test_column_to_device_alone_transfers_its_own_validity():
    c = _flat(CAP).columns[0]
    assert c.to_device().validity is not batch_mod._full_mask(CAP)


@pytest.mark.parametrize("build", [
    lambda: _flat(CAP), lambda: _flat(300), lambda: _flat(CAP, null_at=0),
    lambda: _flat(0), lambda: _nested(CAP), lambda: _nested(300)],
    ids=["full", "partial", "null", "empty", "nested_full", "nested_partial"])
def test_to_host_of_a_staged_batch_equals_its_host_batch(build):
    host = build()
    back = host.to_device().to_host()
    assert back.num_rows == host.num_rows and back.schema == host.schema
    for a, b in zip(back.columns, host.columns):
        _same_buffers(a, b)
