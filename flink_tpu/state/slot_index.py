"""The index both tiers of the `tpu` state backend keep their entries
in: ``namespace → table of key → id``, the id a device slot in
`DeviceAggregatingState.slot_index` and a host row id in
`HostTier.index`.

Keyed by namespace first, because a batch comes with ONE namespace (a
window) or a few: its keys are then resolved against one table with
bulk calls, no tuple and no Python statement per key.  Keys are any
hashable with `dict` equality (``1``, ``1.0`` and ``True`` are one
key).

A namespace's table has one of two forms, picked by the keys it is
born from, never by an option:

- a `dict`, probed with C-level ``map(table.setdefault | get | pop,
  keys, ...)``: any keys.  A table born through a scalar door (`put`)
  or from a LIST of keys (the rows of a call that brings a namespace
  per row, session windows: a table of a key or two each) is one;
- an **integer table** (`native.NativeIntTable`, int64 key → int64 id
  in C++): a whole column probed, entered or taken in ONE call on an
  array.  A table born from an int64 COLUMN of keys is one, and
  `cut_by_namespace` hands the keys of a call under one namespace on
  as such a column where they are one (`key_column`).  The first key
  it cannot hold (a batch with a float, a string, a tuple, an int
  beyond int64) turns it into a dict, once and for good
  (`STATE_STATS.int_table_demotions`).  Without the native host
  runtime every table is a dict.

Both give the same ids for the same calls and keep their entries in
the order they were entered (`columns`, iteration and a snapshot do
not depend on the form).  The scalar doors (`get`, `put`, `pop`) and
the bulk ones (`resolve`, `lookup`, `enter`, `move`) read and write
the same tables.

Invariant: no empty table is kept — a window's table goes with its
last key — so the index is falsy exactly when it holds no entry.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, Iterator, List, Tuple, Union

import numpy as np

from flink_tpu import native  # noqa: F401 (tests turn the runtime off here)
from flink_tpu.core.key_columns import (
    as_list,
    int_column,
    int_key,
    key_column,
    object_column,
)
from flink_tpu.native import NativeIntTable
from flink_tpu.state.stats import STATE_STATS

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


_Table = Union[Dict[Any, int], NativeIntTable]


def _export(table: _Table):
    """A table's ``(keys, ids)`` in the order they were entered."""
    if type(table) is dict:
        return list(table), np.fromiter(table.values(), np.int64, len(table))
    return table.export()


class NamespaceIndex:
    """``(key, namespace) → id`` as ``namespace → table of key → id``."""

    __slots__ = ("tables", "int_rows", "_room")

    def __init__(self) -> None:
        self.tables: Dict[Any, _Table] = {}
        #: entries the integer table that was dropped last held at its
        #: fullest: the next one is born with room for as many (a
        #: window's table as large as the window before it grew, not
        #: doubling its way up from 16 cells again)
        self._room = 0
        #: rows `resolve` and `lookup` took as a column, one call for
        #: all of them: on an integer table, or (an int64 column that
        #: found its namespace without a table) on none, never key by
        #: key on a dict
        self.int_rows = 0

    # ---- the whole index --------------------------------------------
    def __len__(self) -> int:
        # (a gauge thread may ask while the owner adds a namespace)
        return sum(map(len, list(self.tables.values())))

    def __bool__(self) -> bool:
        return bool(self.tables)

    def __iter__(self) -> Iterator[Tuple[Any, Any]]:
        """Entries as ``(key, namespace)``, namespace by namespace."""
        for namespace, table in self.tables.items():
            yield from zip(as_list(_export(table)[0]),
                           itertools.repeat(namespace))

    def __contains__(self, entry) -> bool:
        return self.get(*entry) is not None

    def columns(self) -> Tuple[list, list, np.ndarray]:
        """Every entry as three parallel columns: keys, namespaces,
        ids."""
        keys: list = []
        namespaces: list = []
        ids: List[np.ndarray] = []
        for namespace, table in self.tables.items():
            table_keys, table_ids = _export(table)
            keys.extend(as_list(table_keys))
            namespaces.extend(itertools.repeat(namespace, len(table_ids)))
            ids.append(table_ids)
        return keys, namespaces, (np.concatenate(ids) if ids
                                  else np.zeros(0, np.int64))

    def clear(self) -> None:
        self.tables.clear()

    def _born(self, namespace, keys) -> _Table:
        """The namespace's table, made if it has none in the form the
        first `keys` ask for: for a caller about to enter them."""
        table = self.tables.get(namespace)
        if table is None:
            table = self.tables[namespace] = \
                NativeIntTable(self._room) if isinstance(keys, np.ndarray) \
                else {}
        return table

    def _demote(self, namespace) -> Dict[Any, int]:
        """The namespace's integer table as a dict from now on."""
        keys, ids = self.tables[namespace].export()
        table = self.tables[namespace] = dict(zip(keys.tolist(),
                                                  ids.tolist()))
        STATE_STATS.int_table_demotions += 1
        return table

    def _drop_if_empty(self, namespace, table: _Table) -> None:
        if not len(table):
            del self.tables[namespace]
            if type(table) is not dict:
                self._room = table.peak()

    # ---- one entry --------------------------------------------------
    def get(self, key, namespace, default=None):
        table = self.tables.get(namespace)
        if table is None:
            return default
        if type(table) is dict:
            return table.get(key, default)
        key = int_key(key)
        found = -1 if key is None else table.get(key)
        return default if found < 0 else found

    def put(self, key, namespace, value: int) -> None:
        table = self._born(namespace, None)
        if type(table) is not dict:
            whole = int_key(key)
            if whole is not None:
                table.put(whole, value)
                return
            table = self._demote(namespace)
        table[key] = value

    def pop(self, key, namespace, default=None):
        table = self.tables.get(namespace)
        if table is None:
            return default
        if type(table) is dict:
            value = table.pop(key, default)
        else:
            key = int_key(key)
            found = -1 if key is None else table.pop(key)
            value = default if found < 0 else found
        self._drop_if_empty(namespace, table)
        return value

    # ---- one namespace, in bulk -------------------------------------
    def resolve(self, keys, namespace,
                free: List[int]) -> Tuple[np.ndarray, np.ndarray, Any]:
        """Probe-or-insert, the batch door: the ids of one namespace's
        `keys` (at least one) as int64[n], a key the table did not hold
        taking its id off the end of `free` in the same pass.  `free`
        holds an id for every key that is new (`missing` counts them).
        Returns ``(ids, the ids taken, the keys that took them)``, the
        last two in order of first appearance; which id a new key
        takes does not depend on the table's form."""
        n = len(keys)
        # (a session job comes here once per row or two: the dict's
        # way through is kept short)
        table = self.tables.get(namespace)
        if table is None:
            table = self._born(namespace, keys)
        if type(table) is not dict:
            column = int_column(keys)
            if column is not None:
                ids, first = table.probe(column)
                m = len(first)
                if len(free) >= n:
                    # a new key takes the id its first row was offered,
                    # as on a dict (below): the same ids either way
                    offered = np.fromiter(reversed(free), np.int64, n)
                    del free[-n:]
                    fresh = offered[first]
                    if m < n:
                        offered[first] = -1
                        free.extend(offered[offered >= 0][::-1].tolist())
                else:
                    assert len(free) >= m
                    fresh = np.array(free[:-m - 1:-1], np.int64)
                    del free[len(free) - m:]
                if m:
                    table.assign(fresh, ids)
                self.int_rows += n
                return ids, fresh, column[first]
            table = self._demote(namespace)
        if type(keys) is not list:
            keys = as_list(keys)
        if len(free) >= n:
            # every row offers its key the id `free.pop()` would hand
            # out n-th: a candidate that comes back as its own key's id
            # was taken, the rest return to the free list
            offered = free[:-n - 1:-1]
            del free[-n:]
            ids = np.fromiter(map(table.setdefault, keys, offered),
                              np.int64, n)
            offered = np.array(offered, np.int64)
            took = ids == offered
            fresh = offered[took]
            if len(fresh) < n:
                free.extend(offered[~took][::-1].tolist())
            return ids, fresh, itertools.compress(keys, took.tolist())
        # a table about to fill up has no candidate for every row: find
        # the keys without an id, give each one, probe again
        ids = self.lookup(keys, namespace)
        new_keys = dict.fromkeys(itertools.compress(keys, (ids < 0).tolist()))
        m = len(new_keys)
        fresh = np.array(free[:-m - 1:-1], np.int64)
        if m:
            del free[-m:]
            table.update(zip(new_keys, fresh.tolist()))
            ids = self.lookup(keys, namespace)
        return ids, fresh, new_keys

    def lookup(self, keys, namespace, take: bool = False) -> np.ndarray:
        """The ids of one namespace's `keys` (a list or a column) as
        int64[n], -1 where the index has none: ONE bulk probe.  `take`
        removes what it finds (a key that comes twice is found
        once)."""
        n = len(keys)
        table = self.tables.get(namespace)
        if table is None:
            # (a column that meets no table met no dict: a window all
            # of whose entries are in the other tier)
            self.int_rows += n * isinstance(keys, np.ndarray)
            return np.full(n, -1, np.int64)
        if type(table) is dict:
            if type(keys) is not list:
                keys = as_list(keys)
            ids = np.fromiter(map(table.pop if take else table.get, keys,
                                  itertools.repeat(-1)), np.int64, n)
            if take and not table:
                del self.tables[namespace]
            return ids
        column = int_column(keys)
        if column is not None:
            ids = table.lookup(column, take)
            self.int_rows += n
        else:
            # keys numpy does not read as integers: each as the scalar
            # doors would find it
            door = table.pop if take else table.get
            ids = np.fromiter((-1 if k is None else door(k)
                               for k in map(int_key, keys)), np.int64, n)
        if take:
            self._drop_if_empty(namespace, table)
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
        if not len(keys):
            return
        table = self._born(namespace, keys)
        if type(table) is not dict:
            column = int_column(keys)
            if column is not None:
                table.set(column, np.ascontiguousarray(ids, np.int64))
                return
            table = self._demote(namespace)
        table.update(zip(as_list(keys), ids.tolist()))

    def move(self, keys, namespace, other: "NamespaceIndex",
             ids: np.ndarray) -> None:
        """The entries `keys` of one namespace (distinct, all held
        here) leave for `other`, where they get `ids`: entered there
        before they go here, so a reader finds each in one index or
        the other.  A table born there takes the form of the one they
        leave."""
        if type(self.tables[namespace]) is dict:
            keys = as_list(keys)
        else:
            column = int_column(keys)
            keys = column if column is not None else np.fromiter(
                map(int_key, keys), np.int64, len(keys))
        other.enter(keys, namespace, ids)
        self.lookup(keys, namespace, take=True)

    def remap(self, new_ids) -> None:
        """Every id replaced by ``new_ids(int64 column of ids)``'s;
        entries and their order stay."""
        for namespace, table in self.tables.items():
            keys, ids = _export(table)
            self.enter(keys, namespace, new_ids(ids))
