"""The index both tiers of the `tpu` state backend keep their entries
in: ``namespace → table of key → id``, the id a device slot in
`DeviceAggregatingState.slot_index` and a host row id in
`HostTier.index`.

Keyed by namespace first, because a batch comes with ONE namespace (a
window) or a few: its keys are then resolved against one table with
bulk calls, no tuple and no Python statement per key.  A namespace's
table is a `core/key_columns.py` `KeyTable`, born in the form its
first keys ask for: `cut_by_namespace` hands the keys of a call under
one namespace on as an int64 column where they are one (`key_column`),
a call that brings a namespace per row (session windows) hands on
lists.  The scalar doors (`get`, `put`, `pop`) and the bulk ones
(`resolve`, `lookup`, `enter`, `move`) read and write the same tables.

Invariant: no empty table is kept — a window's table goes with its
last key — so the index is falsy exactly when it holds no entry.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from flink_tpu.core.key_columns import (
    KeyTable,
    as_list,
    key_column,
    object_column,
)


def pick(keys, rows: np.ndarray):
    """The keys at `rows` (indexes or a mask), of a list as a list, of
    a column as a column."""
    if isinstance(keys, np.ndarray):
        return keys[rows]
    return object_column(keys, len(keys))[rows].tolist()


def group_rows(namespaces) -> List[Tuple[Any, np.ndarray]]:
    """``[(namespace, ascending row indexes)]`` of a column of
    namespaces, in order of first appearance; equal means equal as
    dict keys.  One C-level pass, whatever the order they come in."""
    codes: Dict[Any, int] = {}
    code = np.fromiter(map(codes.setdefault, namespaces, itertools.count()),
                       np.int64, len(namespaces))
    if len(codes) == 1:
        return [(next(iter(codes)), np.arange(len(code)))]
    # a namespace's code is the row it first appeared in
    order = np.argsort(code, kind="stable")
    cuts = np.flatnonzero(np.diff(code[order])) + 1
    return list(zip(codes, np.split(order, cuts)))


def cut_by_namespace(keys, namespace, namespaces):
    """A column of rows by namespace, as ``(namespace, positions of its
    rows, their keys)``: all of them under the ONE `namespace`, their
    keys as `key_column` gives them, or (`namespaces` given, one per
    row) grouped by their own, each group's keys a list."""
    n = len(keys)
    if namespaces is None:
        yield namespace, np.arange(n), key_column(keys)
        return
    keys = as_list(keys)
    groups = group_rows(namespaces)
    if len(groups) == 1:
        yield (*groups[0], keys)
        return
    column = object_column(keys, n)
    for namespace, rows in groups:
        yield namespace, rows, column[rows].tolist()


class NamespaceIndex:
    """``(key, namespace) → id`` as ``namespace → KeyTable``."""

    __slots__ = ("tables", "_room")

    def __init__(self) -> None:
        self.tables: Dict[Any, KeyTable] = {}
        #: the room a table born here gets (`KeyTable.dropped`)
        self._room = 0

    # ---- the whole index --------------------------------------------
    def __len__(self) -> int:
        # (a gauge thread may ask while the owner adds a namespace)
        return sum(map(len, list(self.tables.values())))

    def __bool__(self) -> bool:
        return bool(self.tables)

    def __iter__(self) -> Iterator[Tuple[Any, Any]]:
        """Entries as ``(key, namespace)``, namespace by namespace."""
        for namespace, table in self.tables.items():
            yield from zip(table, itertools.repeat(namespace))

    def __contains__(self, entry) -> bool:
        return self.get(*entry) is not None

    def columns(self) -> Tuple[list, list, np.ndarray]:
        """Every entry as three parallel columns: keys, namespaces,
        ids."""
        keys: list = []
        namespaces: list = []
        ids: List[np.ndarray] = []
        for namespace, table in self.tables.items():
            table_keys, table_ids = table.export()
            keys.extend(as_list(table_keys))
            namespaces.extend(itertools.repeat(namespace, len(table_ids)))
            ids.append(table_ids)
        return keys, namespaces, (np.concatenate(ids) if ids
                                  else np.zeros(0, np.int64))

    def clear(self) -> None:
        self.tables.clear()

    def _born(self, namespace, keys) -> KeyTable:
        """The namespace's table, born from `keys` if it has none: for
        a caller about to enter them."""
        table = self.tables.get(namespace)
        if table is None:
            table = self.tables[namespace] = KeyTable.born(keys, self._room)
        return table

    # ---- one entry --------------------------------------------------
    def get(self, key, namespace, default=None):
        table = self.tables.get(namespace)
        return default if table is None else table.get(key, default)

    def put(self, key, namespace, value: int) -> None:
        self._born(namespace, None)[key] = value

    def pop(self, key, namespace, default=None):
        table = self.tables.get(namespace)
        if table is None:
            return default
        value = table.pop(key, default)
        if not table:
            del self.tables[namespace]
            self._room = table.dropped(self._room)
        return value

    # ---- one namespace, in bulk -------------------------------------
    def resolve(self, keys, namespace,
                free: List[int]) -> Tuple[np.ndarray, np.ndarray, Any]:
        """Probe-or-insert, the batch door: `KeyTable.resolve` on the
        namespace's table, born from `keys` if it has none."""
        table = self.tables.get(namespace)
        if table is None:
            table = self.tables[namespace] = KeyTable.born(keys, self._room)
        return table.resolve(keys, free)

    def lookup(self, keys, namespace, take: bool = False) -> np.ndarray:
        """The ids of one namespace's `keys` (a list or a column) as
        int64[n], -1 where the index has none: ONE bulk probe.  `take`
        removes what it finds (`KeyTable.take`)."""
        table = self.tables.get(namespace)
        if table is None:
            return KeyTable.absent(keys)
        if not take:
            return table.lookup(keys)
        ids, room = table.take(keys, self._room)
        if room is not None:
            del self.tables[namespace]
            self._room = room
        return ids

    def missing(self, keys, namespace) -> int:
        """How many distinct keys of `keys` the namespace does not
        hold."""
        absent = pick(keys, self.lookup(keys, namespace) < 0)
        if isinstance(absent, np.ndarray):
            return len(np.unique(absent))
        return len(set(absent))

    def enter(self, keys, namespace, ids: np.ndarray) -> None:
        """``keys[i] → ids[i]`` under one namespace, in bulk: new keys
        enter in row order, a key the table holds keeps its place and
        gets the new id."""
        if len(keys):
            self._born(namespace, keys).set(keys, ids)

    def move(self, keys, namespace, other: "NamespaceIndex",
             ids: np.ndarray) -> None:
        """The entries `keys` of one namespace (distinct, all held
        here) leave for `other`, where they get `ids`: entered there
        before they go here, so a reader finds each in one index or
        the other.  A table born there takes the form of the one they
        leave."""
        keys = self.tables[namespace].own(keys)
        other.enter(keys, namespace, ids)
        self.lookup(keys, namespace, take=True)

    def remap(self, new_ids) -> None:
        """Every id replaced by ``new_ids(int64 column of ids)``'s;
        entries and their order stay."""
        for namespace, table in self.tables.items():
            keys, ids = table.export()
            table.set(keys, new_ids(ids))
