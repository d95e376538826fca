"""Who owns a stored buffer: an append into an empty column takes a
read-only numeric payload by reference (``Column.adopts``), every other
append copies, and copy-on-write keeps the two sides apart afterwards."""

import numpy as np
import pytest

from learningorchestra_tpu.core.columns import Column, FrameOwner
from learningorchestra_tpu.core.store import ROW_ID
from learningorchestra_tpu.core.table import ColumnTable, insert_columns_batched
from learningorchestra_tpu.telemetry import tracing

ROWS = 64


def frozen(column: Column) -> Column:
    """``column`` with a read-only payload, as a view of another stored
    column is (``Column.to_float64``)."""
    column.data.flags.writeable = False
    column._shared = True
    return column


READ_ONLY = {
    "f8": lambda: frozen(Column.from_numpy(np.linspace(0.5, 9.5, ROWS))),
    "i8": lambda: frozen(Column.from_numpy(np.arange(ROWS) * 3)),
    "bool": lambda: frozen(Column.from_numpy(np.arange(ROWS) % 3 == 0)),
    "num": lambda: frozen(Column.from_values([i if i % 2 else i + 0.5 for i in range(ROWS)])),
    "vec": lambda: frozen(Column.from_numpy(np.arange(ROWS * 2.0).reshape(ROWS, 2))),
    "f8 with nulls": lambda: frozen(Column.from_numpy(
        np.where(np.arange(ROWS) % 5 == 0, np.nan, np.arange(ROWS) / 4.0))),
}


def stored(store, collection, field="x"):
    return store.read_column_arrays(collection, [field])[field]


def appended(store, collection, column):
    """Append ``column`` as field ``x``; the ``store:append`` span's
    attributes."""
    trace = tracing.Trace()
    with tracing.activate(trace):
        store.insert_columns(collection, {"x": column})
    (span,) = trace.as_dict()["spans"]
    assert span["name"] == "store:append"
    return span["meta"]


@pytest.mark.parametrize("kind", sorted(READ_ONLY))
def test_a_fresh_collection_takes_a_read_only_column_by_reference(store, kind):
    source = READ_ONLY[kind]()
    expected = source.tolist()
    meta = appended(store, "dst", source)
    column = stored(store, "dst")
    assert np.shares_memory(column.data, source.data)
    assert column.kind == source.kind and column.tolist() == expected
    assert meta == {"rows": ROWS, "fields": 1, "adopted_bytes": source.nbytes(),
                    "copied_bytes": 0}


def test_a_view_of_a_stored_column_is_taken_by_reference(store):
    """The build's own case: the test dataset's columns as the frame reads
    them (``ColumnTable.from_store``), written into a new collection."""
    store.insert_columns("src", {"x": np.linspace(0.0, 1.0, ROWS), "y": np.arange(ROWS) * 1.5})
    table = ColumnTable.from_store(store, "src")
    columns = {name: Column.from_numpy(values) for name, values in table.columns.items()}
    insert_columns_batched(store, "dst", columns)
    for name in ("x", "y"):
        source, copy = stored(store, "src", name), stored(store, "dst", name)
        assert np.shares_memory(copy.data, source.data)
        assert copy.tolist() == source.tolist()


def writable():
    return Column.from_numpy(np.linspace(0.5, 9.5, ROWS))


def sliced_writable():
    # what insert_columns_batched hands over: a shared view, but of a
    # buffer its caller may still write
    return writable().slice(0, ROWS)


def wire_view():
    column = READ_ONLY["f8"]()
    column.owner = FrameOwner(np.zeros(8, dtype=np.uint8))
    return column


def strings():
    column = Column.from_values([f"v{i}" for i in range(ROWS)])
    column.data.flags.writeable = False
    column._shared = True
    return column


COPIED = {
    "writable": writable,
    "sliced writable": sliced_writable,
    "wire view": wire_view,
    "strings": strings,
}


@pytest.mark.parametrize("name", sorted(COPIED) + ["memmapped"])
def test_every_other_append_copies(store, tmp_path, name):
    if name == "memmapped":
        source = writable()
        source.spill_to(str(tmp_path), "x")
        assert source.is_spilled() and not source.data.flags.writeable
    else:
        source = COPIED[name]()
    expected = source.tolist()
    meta = appended(store, "dst", source)
    column = stored(store, "dst")
    assert not np.shares_memory(column.data, source.data)
    assert column.tolist() == expected
    assert meta["adopted_bytes"] == 0 and meta["copied_bytes"] == source.nbytes()


def test_an_append_after_the_first_copies(store):
    appended(store, "dst", READ_ONLY["f8"]())
    second = READ_ONLY["f8"]()
    meta = appended(store, "dst", second)
    assert meta["adopted_bytes"] == 0 and meta["copied_bytes"] == second.nbytes()
    assert not np.shares_memory(stored(store, "dst").data, second.data)
    assert len(stored(store, "dst")) == 2 * ROWS


# --- isolation: a write on one side never shows on the other ------------

def adopted_pair(store):
    """``src`` holds a column of its own; ``dst`` took it by reference
    through the frame's read-only view."""
    store.insert_one("src", {ROW_ID: 0, "finished": True})
    store.insert_columns("src", {"x": np.linspace(1.0, 2.0, ROWS)})
    view = Column.from_numpy(ColumnTable.from_store(store, "src").columns["x"])
    store.insert_columns("dst", {"x": view})
    assert np.shares_memory(stored(store, "dst").data, stored(store, "src").data)
    return stored(store, "src").tolist()


WRITES = {
    "set_column": lambda store, c: store.set_column(c, "x", np.full(ROWS, 7.0)),
    "update_one": lambda store, c: store.update_one(c, {ROW_ID: 3}, {"x": 7.0}),
    "set_field_values": lambda store, c: store.set_field_values(c, "x", {1: 7.0, ROWS: 8.0}),
    "insert_columns": lambda store, c: store.insert_columns(c, {"x": np.full(4, 7.0)}),
    "drop": lambda store, c: store.drop(c),
}


@pytest.mark.parametrize("side", ["src", "dst"])
@pytest.mark.parametrize("write", sorted(WRITES))
def test_a_write_to_one_side_leaves_the_other_as_it_was(store, write, side):
    before = adopted_pair(store)
    WRITES[write](store, side)
    other = "dst" if side == "src" else "src"
    assert stored(store, other).tolist() == before
    if write != "drop":
        assert 7.0 in stored(store, side).tolist()


@pytest.mark.parametrize("side, spare", [("store", 0), ("store", 36), ("caller", 0)])
def test_the_taken_buffer_is_exact_size_so_an_append_reallocates(store, side, spare):
    """Also where the source's buffer has room beyond its rows (which
    its own holder, being read-only, cannot append into either)."""
    source = Column.from_numpy(np.linspace(0.5, 9.5, ROWS + spare))
    source.size = ROWS
    source = frozen(source)
    expected = source.tolist()
    appended(store, "dst", source)
    column = stored(store, "dst")
    assert len(column.data) == column.size == ROWS
    more = Column.from_numpy(np.full(3, 7.0))
    if side == "store":
        store.insert_columns("dst", {"x": more})
        grown = stored(store, "dst")
        assert not np.shares_memory(grown.data, source.data)
        assert source.tolist() == expected
    else:
        grown = source.append_column(more)
        assert not np.shares_memory(grown.data, column.data)
        assert stored(store, "dst").tolist() == expected
    assert grown.tolist() == expected + [7.0] * 3
