"""Columns of keys, as the state layer's tables and the timer store
both take them: an int64 `ndarray` where the keys are integers a
native table can hold, else a list of Python objects with `dict`
equality (``1``, ``1.0`` and ``True`` are one key).

`state/slot_index.py` (the tables of the `tpu` backend) and
`streaming/timers.py` (the timer runs) read keys through these, so the
two agree on which keys go as a column."""

from __future__ import annotations

from typing import Optional

import numpy as np

from flink_tpu import native

INT64_MIN, INT64_MAX = -(1 << 63), (1 << 63) - 1


def object_column(values, n: int) -> np.ndarray:
    """`values` as object[n], each element the object it was (a tuple
    key stays one cell)."""
    return np.fromiter(values, object, n)


def key_column(keys):
    """A column of keys as the int64 array it is, else as it came (an
    array of another kind as a list of Python objects): an integer
    `ndarray`, or a list numpy reads as one of ``dtype.kind == "i"``
    (Python ints, bools among them, that fit int64; a float, a string,
    a tuple, a mixed list or an int beyond int64 gives another dtype).
    A list whose first key is no int is not read at all, and nothing
    is where no integer table could take the column."""
    if isinstance(keys, np.ndarray):
        if keys.ndim == 1 and keys.dtype.kind == "i" \
                and native.available():
            return np.ascontiguousarray(keys, np.int64)
        return keys.tolist()
    if not keys or not isinstance(keys[0], (int, np.integer)) \
            or not native.available():
        return keys
    column = int_column(keys, kinds="i")
    return keys if column is None else column


def int_column(keys, kinds: str = "iub") -> Optional[np.ndarray]:
    """`keys` as an int64 column if numpy reads them as one of
    `kinds`, else None."""
    if isinstance(keys, np.ndarray):
        column = keys
    else:
        try:
            column = np.array(keys)
        except ValueError:  # ragged: a tuple among scalars
            return None
    if column.ndim != 1 or column.dtype.kind not in kinds \
            or column.dtype == np.uint64:
        return None
    return np.ascontiguousarray(column, np.int64)


def int_key(key) -> Optional[int]:
    """The int64 that `key` equals as a dict key (``1.0`` and ``True``
    are ``1``), None if there is none."""
    if type(key) is not int:
        try:
            whole = int(key)
        except (TypeError, ValueError, OverflowError):
            return None
        if whole != key:
            return None
        key = whole
    return key if INT64_MIN <= key <= INT64_MAX else None


def as_list(keys):
    """A column of keys as the list of Python objects a dict takes."""
    return keys.tolist() if isinstance(keys, np.ndarray) else keys
