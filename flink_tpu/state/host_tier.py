"""The host-RAM half of the `tpu` state backend's spill tier: rows
evicted out of HBM, kept as the blocks one eviction's device gather
delivered them in, with one index over all of them.

A row has an id that is never reused; a block covers a run of ids.
Nothing here is per row but the index itself: an eviction files its
block with one ``dict.update``, a fire slices rows out by id, a clear
or a promotion releases ids in one call, and a block whose rows are
all released is dropped whole.  When released rows outnumber live
ones the live rows are copied into one fresh block.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

Entry = Tuple[Any, Any]  # (key, namespace)

#: released rows tolerated before a compaction is considered at all
COMPACT_SLACK_ROWS = 1024


class _Block:
    __slots__ = ("base", "entries", "comps", "alive", "live")

    def __init__(self, base, entries, comps):
        self.base = base
        #: entry of each row, in row order (for a compaction's re-index)
        self.entries = entries
        #: {component: ndarray [rows, ...]}
        self.comps = comps
        self.alive = np.ones(len(entries), bool)
        self.live = len(entries)


class HostTier:
    """(key, namespace) → accumulator row, for rows that left HBM.
    Reads like a mapping of entries to ``{component: row}`` dicts; the
    bulk calls (`put`, `gather`, `release`) are what the backend's
    batch paths use."""

    def __init__(self) -> None:
        #: entry → row id
        self.index: Dict[Entry, int] = {}
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
        return iter(self.index)

    def __contains__(self, entry) -> bool:
        return entry in self.index

    def keys(self):
        return self.index.keys()

    def get(self, entry) -> Optional[Dict[str, np.ndarray]]:
        """One row as ``{component: row}``, or None."""
        rid = self.index.get(entry)
        if rid is None:
            return None
        block = self._blocks[self._block_of(np.array([rid]))[0]]
        return {name: arr[rid - block.base]
                for name, arr in block.comps.items()}

    def discard(self, entry) -> None:
        """The entry's row, if it has one here, is gone."""
        rid = self.index.pop(entry, None)
        if rid is not None:
            self.release([rid])

    def clear(self) -> None:
        self.index.clear()
        self._blocks.clear()
        self._bases = np.zeros(0, np.int64)
        self._rows = 0

    # ---- bulk -------------------------------------------------------
    def put(self, entries: List[Entry],
            comps: Dict[str, np.ndarray]) -> None:
        """File one block: row i of every component belongs to
        entries[i].  The arrays are kept as they are, not copied."""
        n = len(entries)
        if n == 0:
            return
        base = self._next_id
        self.index.update(zip(entries, range(base, base + n)))
        self._blocks.append(_Block(base, entries, comps))
        self._bases = np.append(self._bases, base)
        self._next_id = base + n
        self._rows += n

    def _block_of(self, ids: np.ndarray) -> np.ndarray:
        return np.searchsorted(self._bases, ids, side="right") - 1

    def gather(self, ids: np.ndarray,
               out: Dict[str, np.ndarray]) -> None:
        """Rows `ids` into the first len(ids) rows of `out`'s arrays:
        one copy per run of neighbours that lie in one block, straight
        into its place (ids of one fire arrive sorted, so a tile is a
        few runs)."""
        which = self._block_of(ids)
        cuts = np.flatnonzero(which[1:] != which[:-1]) + 1
        for a, b in zip([0, *cuts.tolist()], [*cuts.tolist(), len(ids)]):
            block = self._blocks[which[a]]
            local = ids[a:b] - block.base
            for name, arr in block.comps.items():
                # (mode="raise" would gather into a buffer first)
                np.take(arr, local, axis=0, out=out[name][a:b], mode="clip")

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
        released = self._rows - len(self.index)
        if released > COMPACT_SLACK_ROWS and released > len(self.index):
            self._compact()

    def _keep(self, blocks: List[_Block]) -> None:
        self._blocks = blocks
        self._bases = np.array([b.base for b in blocks], np.int64)
        self._rows = sum(len(b.entries) for b in blocks)

    def _compact(self) -> None:
        """Copy the live rows of every block into one new block."""
        blocks, self._blocks = self._blocks, []
        self._bases = np.zeros(0, np.int64)
        self._rows = 0
        entries: List[Entry] = []
        parts: Dict[str, list] = {}
        for block in blocks:
            live = np.flatnonzero(block.alive)
            entries.extend(block.entries[i] for i in live.tolist())
            for name, arr in block.comps.items():
                parts.setdefault(name, []).append(arr[live])
        self.put(entries, {name: np.concatenate(p)
                           for name, p in parts.items()})

    def columns(self) -> Tuple[List[Entry], Dict[str, np.ndarray]]:
        """Every live row, for a snapshot: entries and their stacked
        components, in index order."""
        entries = list(self.index)
        if not entries:
            return entries, {}
        ids = np.fromiter(self.index.values(), np.int64, len(entries))
        out = {name: np.empty((len(ids),) + arr.shape[1:], arr.dtype)
               for name, arr in self._blocks[0].comps.items()}
        self.gather(ids, out)
        return entries, out
