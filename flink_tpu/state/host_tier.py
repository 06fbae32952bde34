"""The host-RAM half of the `tpu` state backend's spill tier: rows
evicted out of HBM, kept as the blocks one eviction's device gather
delivered them in, with one index over all of them.

A row has an id that is never reused; a block covers a run of ids.
Nothing here is per row but the index itself, which is keyed by
namespace first (`slot_index.NamespaceIndex`, as the device's): an
eviction's rows enter it with one bulk call per namespace, a fire
slices rows out by id, a clear or a promotion releases ids in one
call, and a block whose rows are all released is dropped whole.  When
released rows outnumber live ones the live rows are copied into one
fresh block.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from flink_tpu.state.slot_index import NamespaceIndex, cut_by_namespace

#: released rows tolerated before a compaction is considered at all
COMPACT_SLACK_ROWS = 1024


class _Block:
    __slots__ = ("base", "comps", "alive", "live")

    def __init__(self, base, comps, n):
        self.base = base
        #: {component: ndarray [rows, ...]}
        self.comps = comps
        self.alive = np.ones(n, bool)
        self.live = n


class HostTier:
    """(key, namespace) → accumulator row, for rows that left HBM.
    `get` / `discard` serve the per-key doors; the bulk calls (`put`,
    `gather`, `release`, and `index` read directly) are what
    the backend's batch paths use."""

    def __init__(self) -> None:
        #: namespace → table of key → row id
        self.index = NamespaceIndex()
        self._blocks: List[_Block] = []
        #: first id of each block, ascending (ids grow with time)
        self._bases = np.zeros(0, np.int64)
        self._next_id = 0
        self._rows = 0  # rows held, released ones included

    # ---- the mapping face -------------------------------------------
    def __len__(self) -> int:
        return len(self.index)

    def __bool__(self) -> bool:
        return bool(self.index)

    def __iter__(self):
        """Entries as ``(key, namespace)``."""
        return iter(self.index)

    def __contains__(self, entry) -> bool:
        return entry in self.index

    def get(self, key, namespace) -> Optional[Dict[str, np.ndarray]]:
        """One row as ``{component: row}``, or None."""
        rid = self.index.get(key, namespace)
        if rid is None:
            return None
        block = self._blocks[self._block_of(np.array([rid]))[0]]
        return {name: arr[rid - block.base]
                for name, arr in block.comps.items()}

    def discard(self, key, namespace) -> None:
        """The entry's row, if it has one here, is gone."""
        rid = self.index.pop(key, namespace)
        if rid is not None:
            self.release([rid])

    def clear(self) -> None:
        self.index.clear()
        self._blocks.clear()
        self._bases = np.zeros(0, np.int64)
        self._rows = 0

    # ---- bulk -------------------------------------------------------
    def file(self, comps: Dict[str, np.ndarray]) -> int:
        """Keep one block of rows, the arrays as they are, not copied;
        returns the id of its first row (the next ones follow).  The
        caller enters the rows in `index`."""
        n = len(next(iter(comps.values())))
        base = self._next_id
        self._blocks.append(_Block(base, comps, n))
        self._bases = np.append(self._bases, base)
        self._next_id = base + n
        self._rows += n
        return base

    def put(self, keys, namespaces, comps: Dict[str, np.ndarray]) -> None:
        """File one block and index it: row i of every component
        belongs to (keys[i], namespaces[i])."""
        if len(keys) == 0:
            return
        base = self.file(comps)
        for namespace, rows, part in cut_by_namespace(list(keys), None,
                                                      namespaces):
            self.index.enter(part, namespace, rows + base)

    def _block_of(self, ids: np.ndarray) -> np.ndarray:
        return np.searchsorted(self._bases, ids, side="right") - 1

    def gather(self, ids: np.ndarray,
               out: Dict[str, np.ndarray]) -> None:
        """Rows `ids` into the first len(ids) rows of `out`'s arrays:
        one copy per run of neighbours that lie in one block, straight
        into its place (ids of one fire arrive sorted, so a tile is a
        few runs)."""
        _gather(self._bases, self._blocks, ids, out)

    def release(self, ids: Iterable[int]) -> None:
        """Rows `ids` (already out of the index) hold nothing any
        more."""
        ids = np.asarray(ids, np.int64)
        if ids.size == 0:
            return
        which = self._block_of(ids)
        dead_blocks = False
        for b in np.unique(which):
            block = self._blocks[b]
            local = ids[which == b] - block.base
            block.alive[local] = False
            block.live -= len(local)
            dead_blocks |= block.live == 0
        if dead_blocks:
            self._keep([b for b in self._blocks if b.live])
        live = len(self.index)
        released = self._rows - live
        if released > COMPACT_SLACK_ROWS and released > live:
            self._compact()

    def _keep(self, blocks: List[_Block]) -> None:
        self._blocks = blocks
        self._bases = np.array([b.base for b in blocks], np.int64)
        self._rows = sum(len(b.alive) for b in blocks)

    def _compact(self) -> None:
        """Copy the live rows of every block into one new block; a
        row's new id follows from its rank among the live ones."""
        blocks, self._blocks = self._blocks, []
        self._bases = np.zeros(0, np.int64)
        self._rows = 0
        old = np.concatenate([b.base + np.flatnonzero(b.alive)
                              for b in blocks])
        base = self.file(
            {name: np.concatenate([b.comps[name][b.alive] for b in blocks])
             for name in blocks[0].comps})
        self.index.remap(lambda ids: base + np.searchsorted(old, ids))

    def columns(self) -> Tuple[list, list, Dict[str, np.ndarray]]:
        """Every live row, for a snapshot: keys, namespaces and their
        stacked components, in index order."""
        return self.capture().columns()

    def capture(self) -> "HostTierCapture":
        """The tier as of now, its rows by reference: a block's arrays
        are never written to once filed (a release marks rows dead, a
        compaction files a new block), so a capture stays true while
        the tier moves on."""
        return HostTierCapture(*self.index.columns(), self._bases,
                               list(self._blocks))


def _gather(bases: np.ndarray, blocks: List[_Block], ids: np.ndarray,
            out: Dict[str, np.ndarray]) -> None:
    which = np.searchsorted(bases, ids, side="right") - 1
    cuts = np.flatnonzero(which[1:] != which[:-1]) + 1
    for a, b in zip([0, *cuts.tolist()], [*cuts.tolist(), len(ids)]):
        block = blocks[which[a]]
        local = ids[a:b] - block.base
        for name, arr in block.comps.items():
            # (mode="raise" would gather into a buffer first)
            np.take(arr, local, axis=0, out=out[name][a:b], mode="clip")


class HostTierCapture:
    """What :meth:`HostTier.capture` took: the index's columns (copies)
    and the blocks (references)."""

    __slots__ = ("keys", "namespaces", "ids", "_bases", "_blocks")

    def __init__(self, keys, namespaces, ids, bases, blocks):
        self.keys = keys
        self.namespaces = namespaces
        self.ids = ids
        self._bases = bases
        self._blocks = blocks

    def __len__(self) -> int:
        return len(self.keys)

    def columns(self) -> Tuple[list, list, Dict[str, np.ndarray]]:
        if not self.keys:
            return self.keys, self.namespaces, {}
        out = {name: np.empty((len(self.ids),) + arr.shape[1:], arr.dtype)
               for name, arr in self._blocks[0].comps.items()}
        _gather(self._bases, self._blocks, self.ids, out)
        return self.keys, self.namespaces, out
