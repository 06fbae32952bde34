"""The `tpu` backend's snapshot of one device state, in two parts.

**At the barrier, on the task's thread** (:meth:`SnapshotPlan.copy`,
called by ``DeviceAggregatingState.capture``): the live slots with
their keys and namespaces are read off the slot arrays, and ONE kind
of device program, a gather (``state.snapshot.copy``), copies the live
rows into buffers of their own, ``copy_rows`` rows a buffer.  The table itself is donated
to the next ``state.update``; the buffers are not, and the device runs
its programs in the order they were dispatched, so what they hold is
the table as of the barrier whatever arrives behind it.  Nothing is
transferred and nothing is waited for: a gather runs at the memory's
rate, and the dispatches do not queue up behind one another.

**Later, on whatever thread resolves the capture** (a checkpoint's
writer; :meth:`StateCapture.columns`): the rows are reduced on the
device, out of the buffers, to what has to be written, the buffers are
let go one by one, what is left comes to the host, is cut by key group
and handed on as columns.

A sketch that has seen n values differs from its initial accumulator
in a few n cells of its thousands, so the rows of a component of
``SPARSE_MIN_CELLS`` cells or more are written as the cells that
differ from the fill (:class:`~flink_tpu.state.sparse_rows.SparseRows`:
bit for bit the same rows, a few MB where the dense rows are GBs; what
Flink's ``ExecutionConfig.setUseSnapshotCompression`` is to RocksDB's
blocks).  ``state.snapshot.count`` counts each copied row's cells off
the fill; the counts sort the rows into classes of at most
``SNAPSHOT_CLASSES`` cells, and a row's class picks the program that
writes its (cell, value) pairs, ``L`` slots a row
(``state.snapshot.cells<L>``).  A row past the largest class comes to
the host whole (``state.snapshot.rows``).  Every program runs at tile
shapes the aggregate fixes, never the data, and but for the gather
none sees the table: the capture's shapes follow neither the number of
live rows nor the table's capacity (the gather's follow the capacity,
and the backend warms it whenever the table grows).

While a capture is unresolved its buffers hold the live rows a second
time in the device's memory (3.5 GiB beside a 4 GiB table of 32 KiB
sketches nine tenths full); they go as the rows are reduced, a buffer
at a time.
"""

from __future__ import annotations

import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.runtime.device_stats import TELEMETRY, tree_nbytes
from flink_tpu.runtime.tracing import get_tracer, traced_jit
from flink_tpu.state.heap_backend import split_column_by_key_group
from flink_tpu.state.slot_index import object_column
from flink_tpu.state.sparse_rows import (
    SparseRows,
    concat_columns,
    take_rows,
)
from flink_tpu.state.stats import STATE_STATS

#: a component of fewer cells a row is always copied whole
SPARSE_MIN_CELLS = 256
#: (cell, value) slots a row may take in a sparse tile; a row with
#: more cells off the fill than the largest is copied whole.  A class
#: is used only where its slots are at most half the dense row
SNAPSHOT_CLASSES = (16, 128, 1024)
#: a row's cells are searched in blocks of this many (a TPU's lanes)
SNAPSHOT_BLOCK_CELLS = 128
#: slots one sparse tile's program writes per component
SNAPSHOT_TILE_PAIRS = 1 << 18
#: rows of one buffer of the barrier's copy, in bytes
SNAPSHOT_COPY_BYTES = 1 << 30
#: rows of one dense tile (rows that come to the host whole), in bytes
SNAPSHOT_DENSE_TILE_BYTES = 16 << 20
MAX_TILE_ROWS = 1 << 16
#: rows of a sparse tile at most (its program's scratch is several
#: times the rows it gathers, and its compile time grows with it)
MAX_CLASS_ROWS = 1 << 13


def _pow2_floor(n: int) -> int:
    return 1 << max(0, int(n).bit_length() - 1)


def _pad(slots: np.ndarray, width: int) -> np.ndarray:
    out = np.full(width, slots[0], np.int32)
    out[:len(slots)] = slots
    return out


class SnapshotPlan:
    """The capture programs of one device state: which components go
    sparse, the classes, the tile shapes.  Holds no reference to the
    state (jax keeps a jitted function, and what it closes over, long
    after the job)."""

    def __init__(self, specs: dict, row_bytes: int):
        self.specs = specs
        #: name -> cells a row
        self.sparse: Dict[str, int] = {}
        classes = list(SNAPSHOT_CLASSES)
        for name, spec in specs.items():
            cells = int(np.prod(spec.shape, dtype=np.int64))
            if cells < SPARSE_MIN_CELLS:
                continue
            self.sparse[name] = cells
            item = np.dtype(spec.dtype).itemsize
            classes = [c for c in classes
                       if 2 * c * (4 + item) <= cells * item]
        if not classes:
            self.sparse = {}
        self.classes: Tuple[int, ...] = tuple(classes) if self.sparse else ()
        self.copy_rows = min(
            MAX_TILE_ROWS, _pow2_floor(SNAPSHOT_COPY_BYTES // row_bytes))
        self.class_rows = {c: max(1, min(self.copy_rows, MAX_CLASS_ROWS,
                                         SNAPSHOT_TILE_PAIRS // c))
                           for c in self.classes}
        self.dense_rows = min(
            self.copy_rows,
            _pow2_floor(max(1, SNAPSHOT_DENSE_TILE_BYTES // row_bytes)))
        sparse = dict(self.sparse)
        fills = {name: spec.fill for name, spec in specs.items()}

        def off_fill(rows, name):
            return rows.reshape(rows.shape[0], -1) != jnp.asarray(
                fills[name], rows.dtype)

        def count(copied):
            most = jnp.zeros(self.copy_rows, jnp.int32)
            for name in sparse:
                most = jnp.maximum(most, off_fill(copied[name], name).sum(
                    axis=1, dtype=jnp.int32))
            return most

        def cells_of(width):
            def cells(copied, rows):
                return {name: (first_cells(col[rows], name, width)
                               if name in sparse else col[rows])
                        for name, col in copied.items()}
            return cells

        def first_cells(rows, name, width):
            """Of every row its first `width` cells off the fill, as
            (flat cell, value), ascending; a slot past the row's last
            such cell reads a cell at or past the row's end.  The row
            is cut into blocks of SNAPSHOT_BLOCK_CELLS: the j-th cell
            lies in the block at which the running count of the blocks
            first passes j (as many blocks lie before it as have a
            count of j or less), and inside that block where its own
            running count first passes what is left of j."""
            n = rows.shape[0]
            flat = rows.reshape(n, -1)
            off = off_fill(rows, name)
            block = SNAPSHOT_BLOCK_CELLS
            blocks = -(-sparse[name] // block)
            padded = blocks * block
            if padded != sparse[name]:
                pad = ((0, 0), (0, padded - sparse[name]))
                flat, off = jnp.pad(flat, pad), jnp.pad(off, pad)
            inside = jnp.cumsum(
                off.reshape(n, blocks, block).astype(jnp.int32), axis=2)
            per_block = inside[:, :, -1]
            upto = jnp.cumsum(per_block, axis=1)
            j = jnp.arange(width, dtype=jnp.int32)
            which = (upto[:, None, :] <= j[None, :, None]).sum(
                axis=2, dtype=jnp.int32)                      # [n, width]
            held = jnp.minimum(which, blocks - 1)
            left = j[None, :] - jnp.take_along_axis(upto - per_block, held,
                                                    axis=1)
            counts = jnp.take_along_axis(inside, held[:, :, None], axis=1)
            at = held * block + (counts <= left[:, :, None]).sum(
                axis=2, dtype=jnp.int32)
            at = jnp.where(which < blocks, at, padded)
            vals = jnp.take_along_axis(flat, jnp.minimum(at, padded - 1),
                                       axis=1)
            if padded < 1 << 16:
                at = at.astype(jnp.uint16)
            return at, vals

        def gather(state, slots):
            return {name: col[slots] for name, col in state.items()}

        #: rows out of the table (the barrier's one program: it alone
        #: sees the table) ...
        self.jit_copy = traced_jit(gather, name="state.snapshot.copy")
        #: ... and out of a buffer (rows that stay whole)
        self.jit_rows = traced_jit(gather, name="state.snapshot.rows")
        self.jit_count = traced_jit(count, name="state.snapshot.count")
        self.jit_cells = {c: traced_jit(cells_of(c),
                                        name=f"state.snapshot.cells{c}")
                          for c in self.classes}
        #: program label -> rows one dispatch of it reads (a tile's
        #: width, whatever part of it is padding)
        self.tile_rows: Dict[str, int] = {
            "state.snapshot.copy": self.copy_rows,
            "state.snapshot.rows": self.dense_rows,
            "state.snapshot.count": self.copy_rows,
            **{f"state.snapshot.cells{c}": self.class_rows[c]
               for c in self.classes}}

    def warm(self, state: dict, all_programs: bool) -> None:
        """The gather at the table's shapes, on slot 0; with
        `all_programs` (once a state, at its first capture) the
        reducing programs too, on what the gather copied."""
        copied = self.jit_copy(state, jnp.zeros(self.copy_rows, jnp.int32))
        if not all_programs:
            return
        if self.classes:
            self.jit_count(copied)
        for c in self.classes:
            self.jit_cells[c](copied, jnp.zeros(self.class_rows[c],
                                                jnp.int32))
        self.jit_rows(copied, jnp.zeros(self.dense_rows, jnp.int32))

    def copy(self, state: dict, slots: np.ndarray) -> list:
        """Dispatch the gathers of rows ``slots`` into buffers of
        their own: ``[(positions into slots, buffer)]``."""
        slots = slots.astype(np.int32)
        width = self.copy_rows
        return [(np.arange(i, min(i + width, len(slots))),
                 self.jit_copy(state, jnp.asarray(
                     _pad(slots[i:i + width], width))))
                for i in range(0, len(slots), width)]

    def reduce(self, copies: list) -> list:
        """The buffers reduced to what is written, on the device:
        ``(positions into slots, class or None, device outputs)`` a
        tile; a state without sparse components keeps its buffers as
        they are.  `copies` is emptied as it goes, so a buffer is let
        go once the programs that read it are dispatched."""
        if not self.classes:
            tiles = [(rows, None, buffer) for rows, buffer in copies]
            del copies[:]
            return tiles
        counted = [self.jit_count(buffer) for _, buffer in copies]
        tiles = []
        while copies:
            rows, buffer = copies.pop(0)
            counts = np.asarray(counted.pop(0))[:len(rows)]
            of = np.searchsorted(np.asarray(self.classes), counts)
            for k, cls in enumerate((*self.classes, None)):
                jit, width = ((self.jit_rows, self.dense_rows)
                              if cls is None
                              else (self.jit_cells[cls],
                                    self.class_rows[cls]))
                local = np.flatnonzero(of == k).astype(np.int32)
                for i in range(0, len(local), width):
                    part = local[i:i + width]
                    tiles.append((rows[part], cls, jit(
                        buffer, jnp.asarray(_pad(part, width)))))
        return tiles


class ColumnsCapture:
    """A capture that was resolved when it was taken: per key group
    ``(keys, namespaces, {component: rows})`` read at once (a state
    with a snapshot of its own, as the tests' per-key reference)."""

    def __init__(self, columns: dict):
        self._columns = columns

    def __len__(self) -> int:
        return sum(len(keys) for keys, _, _ in self._columns.values())

    def columns(self) -> dict:
        return self._columns


class StateCapture:
    """One device state as of a barrier: the entries' keys and
    namespaces, the device buffers their rows were copied into, and
    the host tier's rows.  :meth:`columns` (once; any thread) reduces
    the buffers on the device, brings what is left to the host and
    cuts everything by key group."""

    def __init__(self, plan: SnapshotPlan, keys: np.ndarray,
                 namespaces: np.ndarray, copies: list, spilled,
                 max_parallelism: int):
        self.plan = plan
        self.keys = keys
        self.namespaces = namespaces
        #: [(positions, device buffer)]: the barrier's copy, until
        #: the capture is resolved
        self.copies = copies
        self.spilled = spilled
        self.max_parallelism = max_parallelism
        self.device_bytes = sum(tree_nbytes(buffer)
                                for _, buffer in copies)
        STATE_STATS.snapshot_captures += 1
        STATE_STATS.snapshot_tiles += len(copies)
        STATE_STATS.snapshot_bytes_device += self.device_bytes

    def __len__(self) -> int:
        return len(self.keys) + (len(self.spilled) if self.spilled else 0)

    def _to_host(self) -> Tuple[np.ndarray, Dict[str, object]]:
        """The device rows as host columns, and the position in
        ``keys`` of each of their rows."""
        plan = self.plan
        copies, self.copies = self.copies, []
        t0 = time.perf_counter_ns()
        tiles = plan.reduce(copies)
        leaves = jax.tree_util.tree_leaves([out for _, _, out in tiles])
        for leaf in leaves:
            leaf.copy_to_host_async()
        order, parts = [], {name: [] for name in plan.specs}
        for rows, cls, out in tiles:
            order.append(rows)
            m = len(rows)
            for name, spec in plan.specs.items():
                if name in plan.sparse and cls is not None:
                    at, vals = out[name]
                    part = SparseRows.from_padded(
                        spec.shape, spec.dtype, spec.fill,
                        np.asarray(at)[:m], np.asarray(vals)[:m])
                else:
                    part = np.asarray(out[name])[:m]
                    if name in plan.sparse and plan.classes:
                        part = SparseRows.from_dense(part, spec.fill)
                parts[name].append(part)
        if not tiles:
            return np.zeros(0, np.int64), {}
        if TELEMETRY.enabled:
            TELEMETRY.record_transfer(
                "d2h", sum(leaf.nbytes for leaf in leaves), t0,
                time.perf_counter_ns(), "state.snapshot")
        return (np.concatenate(order),
                {name: concat_columns(p) for name, p in parts.items()})

    def columns(self) -> Dict[int, Tuple[list, list, Dict[str, object]]]:
        """Per key group ``(keys, namespaces, {component: rows})``,
        the device's entries in slot order, then the host tier's; a
        component's rows are an ndarray or :class:`SparseRows`."""
        tracer = get_tracer()
        with tracer.phase("state.snapshot.d2h", bytes=self.device_bytes):
            order, comps = self._to_host()
        with tracer.phase("checkpoint.encode", rows=len(self)):
            # a key group's rows in slot order, whatever tiles they
            # were sorted into: `back[i]` is where the i-th entry's
            # row lies in the tiles' order.  The host tier's rows
            # follow the device's
            back = np.argsort(order, kind="stable")
            keys, nss = self.keys, self.namespaces  # object columns
            if self.spilled:
                s_keys, s_nss, s_comps = self.spilled.columns()
                back = np.concatenate(
                    [back, np.arange(len(keys), len(keys) + len(s_keys))])
                n = len(s_keys)
                keys = np.concatenate([keys, object_column(s_keys, n)])
                nss = np.concatenate([nss, object_column(s_nss, n)])
                for name, spec in self.plan.specs.items():
                    col = s_comps[name]
                    if name in self.plan.sparse and self.plan.classes:
                        col = SparseRows.from_dense(col, spec.fill)
                    comps[name] = concat_columns([comps[name], col]) \
                        if name in comps else col
            out = {}
            for kg, sel in split_column_by_key_group(
                    keys.tolist(), self.max_parallelism):
                at = back[sel]
                out[kg] = (keys[sel].tolist(), nss[sel].tolist(),
                           {name: take_rows(col, at)
                            for name, col in comps.items()})
            return out
