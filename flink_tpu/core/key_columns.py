"""Keys as the state layer and the timer store take them, and the one
table both keep them in.

A column of keys is an int64 `ndarray` where the keys are integers a
native table can hold (`key_column`), else a list of Python objects.
Keys have `dict` equality: ``1``, ``1.0``, ``True`` and ``np.int64(1)``
are one key, ``"1"`` another.

`KeyTable` (key → id, an int64 >= 0) is the only code that knows it
has two forms, picked by the keys it is born from, never by an option:

- a `dict`, probed with C-level ``map(table.setdefault | get | pop,
  keys, ...)``: any keys.  A table born through a scalar door or from
  a LIST of keys is one (a session window's key or two), and so is
  every table without the native host runtime;
- an **integer table** (`native.NativeIntTable`, int64 key → int64 id
  in C++): a whole column probed, entered or taken in ONE call.  A
  table born from an int64 COLUMN of keys is one.  The first key it
  cannot hold (a float, a string, a tuple, an int beyond int64) that a
  door would enter turns it into a dict through its export, once and
  for good (`KeyTable.demotions`); a lookup finds such a key absent.

Both forms give the same ids for the same calls and keep their entries
in the order they were entered: `export`, iteration, a copy and a
snapshot do not depend on the form.  `state/slot_index.py` keeps a
table per namespace, `streaming/timers.py` one per timer run."""

from __future__ import annotations

import itertools
from typing import List, Optional, Tuple

import numpy as np

from flink_tpu import native

# (a session job comes through the dict form's bulk doors once per row
# or two: their way through is kept short)
_fromiter, _repeat, _int64 = np.fromiter, itertools.repeat, np.int64


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
    return key if -(1 << 63) <= key < (1 << 63) else None


def as_list(keys):
    """A column of keys as the list of Python objects a dict takes."""
    return keys.tolist() if isinstance(keys, np.ndarray) else keys


class KeyTable(dict):
    """key → id (an int64 >= 0).  The dict form IS the dict: its scalar
    doors are the dict's own (``get``, ``pop``, ``table[key] = id``,
    ``in``, `len`, iteration), free for a session's one-key tables and
    timer runs.  `born` gives the integer form where the keys ask for
    it (`_IntKeyTable`: the same doors answered by a native table).
    The bulk doors take a list of keys or a column: `resolve` and
    `append` probe-or-insert, `set` enters, `lookup` finds, `take`
    removes, `export` and `ends` read.  Only these are the table's."""

    __slots__ = ("_ints",)

    #: rows the bulk doors of every table took as one int64 column, in
    #: one call for all of them (or as a column that met no table,
    #: `absent`), never key by key
    column_rows = 0
    #: integer tables that a key they could not hold turned into dicts
    demotions = 0

    @staticmethod
    def born(keys=None, room: int = 0) -> "KeyTable":
        """A table born from `keys`, the first it is to enter: an int64
        column gives the integer form, with room for `room` entries
        before it grows; a list, or None (a scalar door), the dict."""
        if isinstance(keys, np.ndarray) and keys.dtype.kind == "i" \
                and native.available():
            table = _IntKeyTable()
            table._ints = native.NativeIntTable(room)
            return table
        return KeyTable()

    @staticmethod
    def absent(keys) -> np.ndarray:
        """The ids of `keys` where there is no table: -1 each."""
        n = len(keys)
        KeyTable.column_rows += n * isinstance(keys, np.ndarray)
        return np.full(n, -1, np.int64)

    def export(self) -> Tuple[object, np.ndarray]:
        """``(keys, ids)`` in the order of entry: the keys a list, or
        of the integer form the int64 column it holds."""
        return list(self), _fromiter(self.values(), _int64, len(self))

    def ends(self):
        """`export`'s keys, with the first and the last id."""
        ids = self.values()
        return list(self), next(iter(ids)), next(reversed(ids))

    def dropped(self, room: int) -> int:
        """The room for the table born after this one is dropped: the
        most entries the integer form held at once, else `room`."""
        return room

    def own(self, keys):
        """`keys` (all held here) in this table's form, for another."""
        return as_list(keys)

    def resolve(self, keys, free: List[int]):
        """Probe-or-insert: the ids of `keys` (at least one) as
        int64[n], a new key taking its id off the end of `free` in the
        same pass; ``(ids, the ids taken, the keys that took them)``.
        With an id in `free` for every row, a new key takes the one its
        first row was offered and the rest go back, else the new keys
        take theirs in turn (`append`), in both forms alike."""
        n = len(keys)
        if len(free) < n:
            found = self.append(keys, np.array(free[::-1], _int64))
            del free[len(free) - len(found[1]):]
            return found
        # every row offers its key the id `free.pop()` would hand out
        # n-th: a candidate that comes back as its own key's id was
        # taken, the rest return to the free list
        offered = free[:-n - 1:-1]
        del free[-n:]
        return self._offer(keys, offered, free)

    def _offer(self, keys, offered: List[int], free: List[int]):
        if type(keys) is not list:
            keys = as_list(keys)
        got = list(map(self.setdefault, keys, offered))
        ids = np.array(got, _int64)
        if got == offered:  # every row brought a new key of its own
            return ids, ids, keys
        return _took(ids, np.array(offered, _int64), keys, free)

    def append(self, keys, candidates: np.ndarray):
        """The keys of `keys` the table does not hold enter in the
        order they first come, under ``candidates[0], [1], ...``;
        returns ``(ids, the ids taken, the keys that took them)``."""
        keys = as_list(keys)
        ids = self.lookup(keys)
        new_keys = dict.fromkeys(itertools.compress(keys, (ids < 0).tolist()))
        fresh = candidates[:len(new_keys)]
        if new_keys:
            self.update(zip(new_keys, fresh.tolist()))
            ids = self.lookup(keys)
        return ids, fresh, new_keys

    def lookup(self, keys) -> np.ndarray:
        """The ids of `keys` as int64[n], -1 where the table has none:
        ONE bulk probe."""
        if type(keys) is not list:
            keys = as_list(keys)
        return _fromiter(map(self.get, keys, _repeat(-1)), _int64, len(keys))

    def take(self, keys, room: int):
        """`lookup` that removes what it finds (a key twice is found
        once): ``(ids, None)``, or once the table is empty ``(ids, the
        room for the next one)`` (`dropped`)."""
        if type(keys) is not list:
            keys = as_list(keys)
        ids = _fromiter(map(self.pop, keys, _repeat(-1)), _int64, len(keys))
        return ids, None if self else room

    def set(self, keys, ids: np.ndarray) -> None:
        """``keys[i] → ids[i]``: new keys enter in row order, a key the
        table holds keeps its place and gets the new id."""
        self.update(zip(as_list(keys), ids.tolist()))


class _IntKeyTable(KeyTable):
    """The integer form: `native.NativeIntTable` (`_ints`) holds the
    entries.  The first key a door would enter that it cannot hold
    turns it into the dict form, in place and once (`_demote`)."""

    __slots__ = ()

    def _dict_only(self, *args, **kwargs):
        raise TypeError("a door of the dict form only")
    __getitem__ = __delitem__ = setdefault = update = popitem = clear = \
        keys = values = items = copy = _dict_only

    def __reduce__(self):
        # (a copy or a pickle gets a table of its own, never the handle)
        return _key_table_of, self.export()

    def _demote(self) -> None:
        keys, ids = self._ints.export()
        del self._ints
        self.__class__ = KeyTable
        self.update(zip(keys.tolist(), ids.tolist()))
        KeyTable.demotions += 1

    def __len__(self) -> int:
        return len(self._ints)

    def __iter__(self):
        return iter(self._ints.export()[0].tolist())

    def __contains__(self, key) -> bool:
        return self.get(key) is not None

    def get(self, key, default=None):
        key = int_key(key)
        found = -1 if key is None else self._ints.get(key)
        return default if found < 0 else found

    def pop(self, key, default=None):
        key = int_key(key)
        found = -1 if key is None else self._ints.pop(key)
        return default if found < 0 else found

    def __setitem__(self, key, id_: int) -> None:
        whole = int_key(key)
        if whole is None:
            self._demote()
            self[key] = id_
        else:
            self._ints.put(whole, id_)

    def export(self) -> Tuple[object, np.ndarray]:
        return self._ints.export()

    def ends(self):
        keys, ids = self._ints.export()
        return keys, int(ids[0]), int(ids[-1])

    def dropped(self, room: int) -> int:
        return self._ints.peak()

    def own(self, keys):
        column = int_column(keys)
        return column if column is not None else np.fromiter(
            map(int_key, keys), np.int64, len(keys))

    def _offer(self, keys, offered: List[int], free: List[int]):
        column = int_column(keys)
        if column is None:
            self._demote()
            return self._offer(keys, offered, free)
        offered = np.array(offered, _int64)
        ids, first = self._ints.probe(column)
        if len(first):
            self._ints.assign(offered[first], ids)
        KeyTable.column_rows += len(column)
        return _took(ids, offered, column, free)

    def append(self, keys, candidates: np.ndarray):
        column = int_column(keys)
        if column is None:
            self._demote()
            return self.append(keys, candidates)
        ids, first = self._ints.probe(column)
        fresh = candidates[:len(first)]
        if len(first):
            self._ints.assign(fresh, ids)
        KeyTable.column_rows += len(column)
        return ids, fresh, column[first]

    def lookup(self, keys) -> np.ndarray:
        return self._find(keys, False)

    def take(self, keys, room: int):
        ids = self._find(keys, True)
        return ids, None if len(self._ints) else self._ints.peak()

    def _find(self, keys, take: bool) -> np.ndarray:
        column = int_column(keys)
        if column is not None:
            KeyTable.column_rows += len(column)
            return self._ints.lookup(column, take)
        # keys numpy does not read as integers: each as the scalar
        # doors would find it
        door = self._ints.pop if take else self._ints.get
        return np.fromiter((-1 if k is None else door(k)
                            for k in map(int_key, keys)), np.int64, len(keys))

    def set(self, keys, ids: np.ndarray) -> None:
        column = int_column(keys)
        if column is None:
            self._demote()
            self.set(keys, ids)
        else:
            self._ints.set(column, np.ascontiguousarray(ids, np.int64))


def _took(ids: np.ndarray, offered: np.ndarray, keys, free: List[int]):
    """`resolve`'s answer where each row was offered an id: the rows
    that took theirs are the first of a new key, the rest go back."""
    took = ids == offered
    fresh = offered[took]
    if len(fresh) < len(ids):
        free.extend(offered[~took][::-1].tolist())
    return ids, fresh, (keys[took] if isinstance(keys, np.ndarray)
                        else itertools.compress(keys, took.tolist()))


def _key_table_of(keys, ids: np.ndarray) -> KeyTable:
    table = KeyTable.born(keys, len(ids))
    table.set(keys, ids)
    return table
