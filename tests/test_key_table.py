"""`core/key_columns.py` `KeyTable`, the one table of keys that the
`tpu` backend's slot index and the timer store keep: each door, fed
each kind of key, on a table in either form, against a plain `dict`
fed the same calls (ids, entries, their order), and what only the
form can show: which doors demote the integer form and which never
do, the room it leaves its successor, copies and pickles, the rows
it takes as one column, and no native runtime at all."""

import copy
import pickle

import numpy as np
import pytest

from flink_tpu import native
from flink_tpu.core.key_columns import KeyTable, int_column, int_key

needs_native = pytest.mark.skipif(
    not native.available(), reason="no native host runtime")

FORMS = ["dict", pytest.param("integer", marks=needs_native)]

#: one key of every kind a caller may hand over
KINDS = {
    "int": 7,
    "int_new": 5,
    "float": 7.0,
    "float_new": 5.0,
    "float_half": 2.5,
    "bool": True,
    "np_int64": np.int64(7),
    "str": "7",
    "tuple": (7, 1),
    "beyond_int64": 1 << 63,
    "below_int64": -(1 << 63) - 1,
}

#: the entries every case starts from, and their ids
START = {1: 10, 7: 11, -3: 12}
DOORS = ["get", "put", "pop", "resolve", "append", "lookup", "take", "set"]
#: doors that enter a key: the ones that may demote the integer form
ENTERING = {"put", "resolve", "append", "set"}


def _born(form, keys=START, ids=None):
    """A table in `form` holding `keys` (under `ids`, by default
    `START`'s)."""
    ids = list(START.values()) if ids is None else ids
    keys = np.array(list(keys), np.int64) if form == "integer" \
        else list(keys)
    table = KeyTable.born(keys)
    table.set(keys, np.array(ids, np.int64))
    return table


def _is_int(table):
    return isinstance(getattr(table, "_ints", None), native.NativeIntTable)


def _entries(table):
    keys, ids = table.export()
    keys = keys.tolist() if isinstance(keys, np.ndarray) else keys
    assert list(table) == keys and len(table) == len(keys)
    return list(zip(keys, ids.tolist()))


def _call(door, table, key):
    """`door` with `key` on a `KeyTable`, or (`table` a dict) what a
    dict does for it."""
    if type(table) is dict:
        return {"get": lambda: table.get(key),
                "put": lambda: table.__setitem__(key, 20),
                "pop": lambda: table.pop(key, None),
                "resolve": lambda: [table.setdefault(key, 30)],
                "append": lambda: [table.setdefault(key, 30)],
                "lookup": lambda: [table.get(key, -1)],
                "take": lambda: [table.pop(key, -1)],
                "set": lambda: table.__setitem__(key, 20)}[door]()
    one = [key]
    if door == "get":
        return table.get(key)
    if door == "put":
        table[key] = 20
        return None
    if door == "pop":
        return table.pop(key, None)
    if door == "resolve":
        free = [30]
        ids, fresh, new_keys = table.resolve(one, free)
        assert list(new_keys) == ([key] if len(fresh) else [])
        assert free == ([] if len(fresh) else [30])
        return ids.tolist()
    if door == "append":
        ids, fresh, new_keys = table.append(one, np.array([30, 31]))
        assert fresh.tolist() == ([30] if len(list(new_keys)) else [])
        return ids.tolist()
    if door == "lookup":
        return table.lookup(one).tolist()
    if door == "take":
        return table.take(one, 0)[0].tolist()
    return table.set(one, np.array([20]))


def _would_demote(door, key):
    if door not in ENTERING:
        return False
    if door == "put":
        return int_key(key) is None
    return int_column([key]) is None


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("door", DOORS)
def test_every_door_takes_every_kind_of_key_as_a_dict_does(door, kind, form):
    """The same answer and the same entries, in the same order, as a
    dict fed the same call; the integer form turns into a dict only
    where a door would enter a key it cannot hold, once."""
    key = KINDS[kind]
    table, ref = _born(form), dict(START)
    demoted = KeyTable.demotions
    assert _call(door, table, key) == _call(door, ref, key)
    assert _entries(table) == list(ref.items())
    turned = form == "integer" and _would_demote(door, key)
    assert KeyTable.demotions - demoted == turned
    assert _is_int(table) == (form == "integer" and not turned)


@needs_native
@pytest.mark.parametrize("door", sorted(ENTERING))
@pytest.mark.parametrize("stranger", ["k", 2.5, (1, 2), 1 << 63, None],
                         ids=["str", "float", "tuple", "beyond_int64",
                              "none"])
def test_a_key_it_cannot_hold_turns_the_integer_form_once(door, stranger):
    """Every entry stays, in its place; the table takes every door and
    every column as a dict from then on, and is counted once."""
    table, ref = _born("integer"), dict(START)
    demoted = KeyTable.demotions
    assert _call(door, table, stranger) == _call(door, ref, stranger)
    assert not _is_int(table) and KeyTable.demotions - demoted == 1
    for more in ([7, 8], np.array([9, 1]), [10.5]):
        table.set(more, np.array([40, 41]) if len(more) == 2
                  else np.array([42]))
        ref.update(zip(more.tolist() if isinstance(more, np.ndarray)
                       else more, [40, 41] if len(more) == 2 else [42]))
    assert table.resolve(np.array([9, 50]), [60, 61])[0].tolist() \
        == [ref[9], 60]
    ref[50] = 60
    assert _entries(table) == list(ref.items())
    assert KeyTable.demotions - demoted == 1


@needs_native
@pytest.mark.parametrize("door", ["get", "pop", "lookup", "take"])
def test_a_lookup_never_turns_the_integer_form(door):
    table = _born("integer")
    demoted = KeyTable.demotions
    for stranger in ("k", 2.5, (1, 2), 1 << 63, None, float("nan"), b"1"):
        assert _call(door, table, stranger) in (None, [-1])
    assert _call(door, table, 7.0) in (11, [11])
    assert _is_int(table) and KeyTable.demotions == demoted


@pytest.mark.parametrize("form", FORMS)
def test_entry_order_survives_deletion_growth_and_demotion(form):
    """Entries come back in the order they were entered, whatever was
    taken out, however often the table grew, and across its turning
    into a dict."""
    rng = np.random.default_rng(43)
    first = rng.permutation(3000) - 1500
    table, ref = _born(form, first, np.arange(3000)), {}
    ref.update(zip(first.tolist(), range(3000)))
    gone = first[::3]
    ids, room = table.take(gone if form == "integer" else gone.tolist(), 0)
    assert ids.tolist() == [ref.pop(k) for k in gone.tolist()]
    assert room is None
    later = rng.permutation(20_000) + 5000
    for lo in range(0, len(later), 4000):
        part = later[lo:lo + 4000]
        part = part if form == "integer" else part.tolist()
        free = list(range(100_000 + lo + 3999, 100_000 + lo - 1, -1))
        ids = table.resolve(part, free)[0]
        ref.update(zip(later[lo:lo + 4000].tolist(), ids.tolist()))
    assert _entries(table) == list(ref.items())
    table["stranger"] = 7
    ref["stranger"] = 7
    table.set(gone[::-1].tolist(), np.arange(len(gone)))
    ref.update(zip(gone[::-1].tolist(), range(len(gone))))
    assert _entries(table) == list(ref.items())
    assert not _is_int(table)


@pytest.mark.parametrize("form", FORMS)
def test_the_room_a_dropped_table_leaves(form):
    """An emptied table tells its holder the room for the next one:
    the most entries the integer form held at once; the dict form
    leaves the room as it was."""
    table = _born(form, range(500), np.arange(500))
    keys = np.arange(500) if form == "integer" else list(range(500))
    ids, room = table.take(keys[:200], 3)
    assert room is None and len(table) == 300
    ids, room = table.take(keys, 3)
    assert len(table) == 0 and (ids >= 0).sum() == 300
    assert room == (500 if form == "integer" else 3)
    assert table.dropped(3) == room
    born = KeyTable.born(np.arange(4) if form == "integer" else None, room)
    assert _is_int(born) == (form == "integer")


@pytest.mark.parametrize("form", FORMS)
def test_a_copy_or_a_pickle_is_a_table_of_its_own(form):
    table = _born(form)
    twins = [copy.deepcopy(table), copy.copy(table),
             pickle.loads(pickle.dumps(table))]
    table[1] = 99
    table.pop(7)
    for twin in twins:
        assert _entries(twin) == list(START.items())
        assert _is_int(twin) == (form == "integer")
        twin[-3] = 0
    assert _entries(table) == [(1, 99), (-3, 12)]


@needs_native
def test_only_the_integer_form_counts_column_rows():
    """Rows the bulk doors took as one column, one call for all: an
    integer table's, or a column that met no table."""
    table, listed = _born("integer"), _born("dict")
    before = KeyTable.column_rows
    column = np.array([1, 5, 7, 5])
    table.resolve(column, [40, 41, 42, 43])
    table.lookup(column)
    table.append([1, 2], np.array([50, 51]))
    table.take(np.array([2]), 0)
    listed.resolve(column, [40, 41, 42, 43])
    listed.lookup(column)
    KeyTable.absent([1, 2])
    assert KeyTable.column_rows - before == 4 + 4 + 2 + 1
    KeyTable.absent(column)
    assert KeyTable.column_rows - before == 4 + 4 + 2 + 1 + 4


def test_without_the_native_runtime_every_table_is_a_dict(monkeypatch):
    monkeypatch.setattr(native, "available", lambda: False)
    table, ref = _born("integer"), dict(START)
    assert not _is_int(table)
    before = KeyTable.column_rows, KeyTable.demotions
    for door in DOORS:
        for key in (5, 2.5, "k"):
            assert _call(door, table, key) == _call(door, ref, key)
    assert _entries(table) == list(ref.items())
    assert (KeyTable.column_rows, KeyTable.demotions) == before


@pytest.mark.parametrize("form", FORMS)
def test_a_new_key_takes_the_id_its_first_row_was_offered(form):
    """With an id in `free` for every row, a new key takes its first
    row's, the rest return to `free` in their order; with fewer, the
    new keys take theirs in turn: the same in both forms."""
    keys = [4, 1, 4, 9, 7]
    keys = np.array(keys) if form == "integer" else keys
    table = _born(form)
    free = [90, 80, 70, 60, 50, 40]
    ids, fresh, new_keys = table.resolve(keys, free)
    assert ids.tolist() == [40, 10, 40, 70, 11]
    assert fresh.tolist() == [40, 70] and list(new_keys) == [4, 9]
    assert free == [90, 80, 60, 50]  # the next one out is 50
    short = [3, 2]
    ids, fresh, new_keys = table.resolve(
        np.array([6, 1, 8, 6]) if form == "integer" else [6, 1, 8, 6], short)
    assert ids.tolist() == [2, 10, 3, 2] and fresh.tolist() == [2, 3]
    assert list(new_keys) == [6, 8] and short == []
