"""Rows of one state component as the cells that differ from its fill.

A sketch that has seen n values holds at most a few n cells of its
thousands that are not the accumulator's initial value, so a snapshot
of a table of sketches is mostly fill.  :class:`SparseRows` keeps, for
a column of rows of one shape, dtype and fill, the number of cells per
row that differ from the fill (``counts``), their flat positions in
the row (``cells``, ascending inside a row) and their values
(``vals``), rows end to end.  It restores bit for bit: a row is the
fill with its cells set.  (A float cell that holds -0.0 over a fill of
0.0, or NaN over NaN, compares equal or unequal to its fill as IEEE
says: a -0.0 would come back as 0.0.  No aggregate of this package
keeps such a cell; :func:`from_dense` is exact for integer dtypes.)

It reads like the array it stands for where that is cheap
(``len``, ``shape``, ``dtype``, ``nbytes``, ``rows[i]``) and turns
into it on ``np.asarray``: the heap backend and the offline inspector
read a ``tpu`` snapshot's component columns that way and need not know
the encoding.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def _cell_dtype(cells_per_row: int):
    return np.uint16 if cells_per_row <= 1 << 16 else np.int32


class SparseRows:
    __slots__ = ("row_shape", "dtype", "fill", "counts", "cells", "vals",
                 "_offsets")

    def __init__(self, row_shape: Tuple[int, ...], dtype, fill,
                 counts: np.ndarray, cells: np.ndarray, vals: np.ndarray):
        self.row_shape = tuple(int(d) for d in row_shape)
        self.dtype = np.dtype(dtype)
        self.fill = fill
        self.counts = np.asarray(counts, np.int64)
        self.cells = np.asarray(cells, _cell_dtype(self.row_cells))
        self.vals = np.asarray(vals, self.dtype)
        self._offsets: Optional[np.ndarray] = None

    # ---- pickling (no cache) ------------------------------------------
    def __getstate__(self):
        return (self.row_shape, self.dtype.str, self.fill,
                self.counts.astype(np.int32), self.cells, self.vals)

    def __setstate__(self, state):
        row_shape, dtype, fill, counts, cells, vals = state
        self.__init__(row_shape, dtype, fill, counts, cells, vals)

    # ---- the array it stands for --------------------------------------
    @property
    def row_cells(self) -> int:
        return int(np.prod(self.row_shape, dtype=np.int64))

    def __len__(self) -> int:
        return len(self.counts)

    @property
    def shape(self) -> Tuple[int, ...]:
        return (len(self.counts), *self.row_shape)

    @property
    def nbytes(self) -> int:
        """Bytes of the dense array."""
        return len(self.counts) * self.row_cells * self.dtype.itemsize

    @property
    def stored_nbytes(self) -> int:
        """Bytes this encoding holds."""
        return (4 * len(self.counts) + self.cells.nbytes + self.vals.nbytes)

    @property
    def offsets(self) -> np.ndarray:
        if self._offsets is None:
            self._offsets = np.concatenate(
                [[0], np.cumsum(self.counts)]).astype(np.int64)
        return self._offsets

    def dense(self, rows=None) -> np.ndarray:
        """Rows ``rows`` (all of them, or an index vector / slice) as
        the dense array."""
        part = self if rows is None else self.take(rows)
        n = len(part)
        out = np.full((n, part.row_cells), part.fill, part.dtype)
        if len(part.cells):
            out[np.repeat(np.arange(n), part.counts),
                part.cells.astype(np.int64)] = part.vals
        return out.reshape(n, *part.row_shape)

    def __array__(self, dtype=None, copy=None):
        out = self.dense()
        return out if dtype is None else out.astype(dtype)

    def __getitem__(self, item):
        if isinstance(item, (int, np.integer)):
            return self.dense(np.array([item]))[0]
        return self.dense(item)

    def take(self, rows) -> "SparseRows":
        """The rows ``rows`` (an index vector, a boolean mask or a
        slice), in that order."""
        if isinstance(rows, slice):
            rows = np.arange(len(self))[rows]
        rows = np.asarray(rows)
        if rows.dtype == bool:
            rows = np.flatnonzero(rows)
        counts = self.counts[rows]
        starts = self.offsets[rows]
        ends = np.cumsum(counts)
        # element e of the picked rows lies at starts[row] + (e - first
        # element of that row among the picked)
        at = np.repeat(starts - (ends - counts), counts) \
            + np.arange(int(ends[-1]) if len(ends) else 0)
        return SparseRows(self.row_shape, self.dtype, self.fill, counts,
                          self.cells[at], self.vals[at])

    @classmethod
    def concatenate(cls, parts) -> "SparseRows":
        first = parts[0]
        return cls(first.row_shape, first.dtype, first.fill,
                   np.concatenate([p.counts for p in parts]),
                   np.concatenate([p.cells for p in parts]),
                   np.concatenate([p.vals for p in parts]))

    @classmethod
    def from_dense(cls, rows: np.ndarray, fill) -> "SparseRows":
        rows = np.asarray(rows)
        flat = rows.reshape(len(rows), -1)
        at_row, cell = np.nonzero(flat != np.asarray(fill, rows.dtype))
        return cls(rows.shape[1:], rows.dtype, fill,
                   np.bincount(at_row, minlength=len(rows)), cell,
                   flat[at_row, cell])

    @classmethod
    def from_padded(cls, row_shape, dtype, fill, cells: np.ndarray,
                    vals: np.ndarray) -> "SparseRows":
        """From rows of ``L`` (cell, value) slots each, the cells of a
        row first and ascending, the slots behind them holding a cell
        at or past the row's end (what the device's capture writes)."""
        cells_per_row = int(np.prod(row_shape, dtype=np.int64))
        used = cells.astype(np.int64) < cells_per_row
        return cls(row_shape, dtype, fill, used.sum(axis=1),
                   cells[used], vals[used])


def dense_rows(column, rows=None) -> np.ndarray:
    """A component column, dense or :class:`SparseRows`, as the dense
    rows ``rows``."""
    if isinstance(column, SparseRows):
        return column.dense(rows)
    return column if rows is None else column[rows]


def take_rows(column, rows):
    return column.take(rows) if isinstance(column, SparseRows) \
        else column[rows]


def concat_columns(parts):
    """Columns of one component end to end; sparse only if all are."""
    if all(isinstance(p, SparseRows) for p in parts):
        return SparseRows.concatenate(parts)
    return np.concatenate([dense_rows(p) for p in parts])
