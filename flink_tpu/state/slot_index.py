"""The index both tiers of the `tpu` state backend keep their entries
in: ``namespace → {key → id}``, the id a device slot in
`DeviceAggregatingState.slot_index` and a host row id in
`HostTier.index`.

Keyed by namespace first, because a batch comes with ONE namespace (a
window) or a few: its keys are then resolved against one plain dict
with C-level bulk calls (``map(table.setdefault, keys, ...)``), no
tuple and no Python statement per key.  Keys are any hashable with
`dict` equality (``1``, ``1.0`` and ``True`` are one key).  The scalar
doors (`get`, `put`, `pop`) and the bulk callers (`tables`, `table`,
`lookup`) read and write the same tables.

Invariant: no empty table is kept — a window's table goes with its
last key — so the index is falsy exactly when it holds no entry.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np


def object_column(values, n: int) -> np.ndarray:
    """`values` as object[n], each element the object it was (a tuple
    key stays one cell)."""
    return np.fromiter(values, object, n)


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


def cut_by_namespace(keys: list, namespace, namespaces):
    """A column of rows by namespace, as ``(namespace, positions of its
    rows, their keys)``: all of them under the ONE `namespace`, or
    (`namespaces` given, one per row) grouped by their own."""
    n = len(keys)
    groups = [(namespace, np.arange(n))] if namespaces is None \
        else group_rows(namespaces)
    if len(groups) == 1:
        yield (*groups[0], keys)
        return
    column = object_column(keys, n)
    for namespace, rows in groups:
        yield namespace, rows, column[rows].tolist()


class NamespaceIndex:
    """``(key, namespace) → id`` as ``namespace → {key → id}``."""

    __slots__ = ("tables",)

    def __init__(self) -> None:
        self.tables: Dict[Any, Dict[Any, int]] = {}

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
        key, namespace = entry
        return key in self.tables.get(namespace, ())

    def columns(self) -> Tuple[list, list, np.ndarray]:
        """Every entry as three parallel columns: keys, namespaces,
        ids."""
        keys: list = []
        namespaces: list = []
        ids: List[np.ndarray] = []
        for namespace, table in self.tables.items():
            keys.extend(table)
            namespaces.extend(itertools.repeat(namespace, len(table)))
            ids.append(np.fromiter(table.values(), np.int64, len(table)))
        return keys, namespaces, (np.concatenate(ids) if ids
                                  else np.zeros(0, np.int64))

    def clear(self) -> None:
        self.tables.clear()

    # ---- one entry --------------------------------------------------
    def get(self, key, namespace, default=None):
        table = self.tables.get(namespace)
        return default if table is None else table.get(key, default)

    def put(self, key, namespace, value: int) -> None:
        self.table(namespace)[key] = value

    def pop(self, key, namespace, default=None):
        table = self.tables.get(namespace)
        if table is None:
            return default
        value = table.pop(key, default)
        if not table:
            del self.tables[namespace]
        return value

    # ---- one namespace, for the bulk callers ------------------------
    def table(self, namespace) -> Dict[Any, int]:
        """The namespace's table, made if it has none: for a caller
        about to write keys into it."""
        table = self.tables.get(namespace)
        if table is None:
            table = self.tables[namespace] = {}
        return table

    def lookup(self, keys, namespace, n: int, take: bool = False) -> np.ndarray:
        """The ids of `n` keys of one namespace as int64[n], -1 where
        the index has none: ONE bulk probe.  `take` removes what it
        finds (a key that comes twice is found once)."""
        table = self.tables.get(namespace)
        if table is None:
            return np.full(n, -1, np.int64)
        ids = np.fromiter(map(table.pop if take else table.get, keys,
                              itertools.repeat(-1)), np.int64, n)
        if take and not table:
            del self.tables[namespace]
        return ids
