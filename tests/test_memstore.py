"""MemStore tests — the store_test.cc slice the OSD paths rely on
(reference src/test/objectstore/store_test.cc over MemStore)."""

import pytest

from ceph_tpu.store import MemStore, Transaction, coll_t, ghobject_t

C = coll_t(1, 0, 2)
O1 = ghobject_t("obj1", shard=2)
O2 = ghobject_t("obj2", shard=2)


@pytest.fixture
def store():
    s = MemStore()
    t = Transaction().create_collection(C)
    s.queue_transaction(t)
    return s


class TestBasics:
    def test_write_read(self, store):
        store.queue_transaction(Transaction().write(C, O1, 0, b"hello"))
        assert store.read(C, O1) == b"hello"
        assert store.stat(C, O1) == 5

    @pytest.mark.parametrize("size", [100, 300_000])
    def test_write_borrows_a_view_and_the_store_keeps_its_own(
            self, store, size):
        """A memoryview is not copied when the op is built; once the
        transaction is applied the store holds the bytes itself."""
        buf = bytearray(bytes(range(256)) * (size // 256 + 1))[:size]
        want = bytes(buf)
        t = Transaction().write(C, O1, 0, memoryview(buf))
        assert t.ops[-1][4].obj is buf
        store.queue_transaction(t)
        buf[:] = bytes(size)        # the lender's buffer moves on
        assert store.read(C, O1) == want

    def test_write_copies_a_bytearray_when_the_op_is_built(self, store):
        buf = bytearray(b"0123456789" * 1000)
        want = bytes(buf)
        t = Transaction().write(C, O1, 0, buf)
        buf[:5] = b"xxxxx"          # its owner goes on editing
        store.queue_transaction(t)
        assert store.read(C, O1) == want

    def test_write_extends_with_zero_fill(self, store):
        store.queue_transaction(Transaction().write(C, O1, 8, b"xy"))
        assert store.read(C, O1) == b"\0" * 8 + b"xy"

    def test_partial_read(self, store):
        store.queue_transaction(Transaction().write(C, O1, 0, b"0123456789"))
        assert store.read(C, O1, 2, 3) == b"234"
        assert store.read(C, O1, 8, 100) == b"89"

    def test_zero_truncate(self, store):
        store.queue_transaction(Transaction().write(C, O1, 0, b"0123456789"))
        store.queue_transaction(Transaction().zero(C, O1, 2, 3))
        assert store.read(C, O1) == b"01\0\0\x0056789"
        store.queue_transaction(Transaction().truncate(C, O1, 4))
        assert store.read(C, O1) == b"01\0\0"
        store.queue_transaction(Transaction().truncate(C, O1, 6))
        assert store.read(C, O1) == b"01\0\0\0\0"

    def test_touch_remove_exists(self, store):
        store.queue_transaction(Transaction().touch(C, O1))
        assert store.exists(C, O1)
        assert store.read(C, O1) == b""
        store.queue_transaction(Transaction().remove(C, O1))
        assert not store.exists(C, O1)

    def test_attrs_and_omap(self, store):
        t = (
            Transaction()
            .write(C, O1, 0, b"d")
            .setattrs(C, O1, {"hinfo": b"\x01\x02", "_": b"oi"})
            .omap_setkeys(C, O1, {"k1": b"v1", "k2": b"v2"})
        )
        store.queue_transaction(t)
        assert store.getattr(C, O1, "hinfo") == b"\x01\x02"
        assert store.getattrs(C, O1) == {"hinfo": b"\x01\x02", "_": b"oi"}
        assert store.omap_get(C, O1) == {"k1": b"v1", "k2": b"v2"}
        store.queue_transaction(
            Transaction().rmattr(C, O1, "hinfo").omap_rmkeys(C, O1, ["k1"])
        )
        assert store.getattrs(C, O1) == {"_": b"oi"}
        assert store.omap_get_values(C, O1, ["k1", "k2"]) == {"k2": b"v2"}

    def test_clone(self, store):
        store.queue_transaction(
            Transaction().write(C, O1, 0, b"src").setattrs(C, O1, {"a": b"1"})
        )
        store.queue_transaction(Transaction().clone(C, O1, O2))
        store.queue_transaction(Transaction().write(C, O1, 0, b"XXX"))
        assert store.read(C, O2) == b"src"
        assert store.getattr(C, O2, "a") == b"1"

    def test_collection_list(self, store):
        store.queue_transaction(
            Transaction().touch(C, O1).touch(C, O2)
        )
        assert store.collection_list(C) == sorted([O1, O2])
        assert store.list_collections() == [C]

    def test_collection_move_rename(self, store):
        c2 = coll_t(1, 1, 2)
        store.queue_transaction(Transaction().create_collection(c2))
        store.queue_transaction(Transaction().write(C, O1, 0, b"mv"))
        store.queue_transaction(
            Transaction().collection_move_rename(C, O1, c2, O2)
        )
        assert not store.exists(C, O1)
        assert store.read(c2, O2) == b"mv"


class TestAtomicity:
    def test_failed_txn_mutates_nothing(self, store):
        store.queue_transaction(Transaction().write(C, O1, 0, b"keep"))
        bad = (
            Transaction()
            .write(C, O1, 0, b"clobber")
            .remove(C, ghobject_t("nope", shard=2))
        )
        with pytest.raises(FileNotFoundError):
            store.queue_transaction(bad)
        assert store.read(C, O1) == b"keep"

    def test_missing_collection_rejected(self, store):
        with pytest.raises(FileNotFoundError):
            store.queue_transaction(
                Transaction().write(coll_t(9, 9), O1, 0, b"x")
            )

    def test_rmcoll_nonempty_rejected(self, store):
        store.queue_transaction(Transaction().touch(C, O1))
        with pytest.raises(OSError):
            store.queue_transaction(Transaction().remove_collection(C))

    def test_txn_sequence_create_then_use(self, store):
        """ops inside one txn see earlier ops' effects."""
        c2 = coll_t(2, 0)
        t = (
            Transaction()
            .create_collection(c2)
            .write(c2, O1, 0, b"one-txn")
            .clone(c2, O1, O2)
            .remove(c2, O1)
        )
        store.queue_transaction(t)
        assert store.read(c2, O2) == b"one-txn"
        assert not store.exists(c2, O1)

    def test_callbacks_fire_in_order(self, store):
        events = []
        t = Transaction().touch(C, O1)
        t.register_on_applied(lambda: events.append("applied"))
        t.register_on_commit(lambda: events.append("commit"))
        store.queue_transaction(t)
        assert events == ["applied", "commit"]

    def test_move_rename_onto_existing_rejected(self, store):
        c2 = coll_t(1, 1, 2)
        store.queue_transaction(Transaction().create_collection(c2))
        store.queue_transaction(Transaction().write(C, O1, 0, b"src"))
        store.queue_transaction(Transaction().write(c2, O2, 0, b"live"))
        with pytest.raises(FileExistsError):
            store.queue_transaction(
                Transaction().collection_move_rename(C, O1, c2, O2)
            )
        assert store.read(c2, O2) == b"live"  # untouched
        assert store.read(C, O1) == b"src"


# -- validation: what a transaction may name, op for op -----------------------

C2 = coll_t(1, 1, 2)
MISSING = coll_t(9, 9)
GONE = ghobject_t("nope", shard=2)


def _contents(store):
    return {
        c: {o: (store.read(c, o), store.getattrs(c, o), store.omap_get(c, o))
            for o in store.collection_list(c)}
        for c in store.list_collections()
    }


# every rejected transaction first clobbers O1, which validation lets
# through, so "the store is as it was" has something to show
_REJECTED = {
    "second_mkcoll": (
        lambda t: t.create_collection(C),
        FileExistsError, "collection .* exists"),
    "mkcoll_twice_in_one_txn": (
        lambda t: t.create_collection(C2).create_collection(C2),
        FileExistsError, "collection .* exists"),
    "write_into_missing_collection": (
        lambda t: t.write(MISSING, O1, 0, b"x"),
        FileNotFoundError, "collection "),
    "rmcoll_of_missing_collection": (
        lambda t: t.remove_collection(MISSING),
        FileNotFoundError, "collection "),
    "rmcoll_of_nonempty_collection": (
        lambda t: t.remove_collection(C),
        OSError, "not empty"),
    "rmcoll_of_one_emptied_then_refilled_in_the_txn": (
        lambda t: t.remove(C, O1).touch(C, O2).remove_collection(C),
        OSError, "not empty"),
    "rmcoll_then_write_in_one_txn": (
        lambda t: t.remove(C, O1).remove_collection(C).write(C, O2, 0, b"x"),
        FileNotFoundError, "collection "),
    "rmcoll_twice_in_one_txn": (
        lambda t: t.remove(C, O1).remove_collection(C).remove_collection(C),
        FileNotFoundError, "collection "),
    "move_rename_of_missing_object": (
        lambda t: t.collection_move_rename(C, GONE, C, O2),
        FileNotFoundError, "/"),
    "move_rename_out_of_missing_collection": (
        lambda t: t.collection_move_rename(MISSING, O1, C, O2),
        FileNotFoundError, "/"),
    "move_rename_into_missing_collection": (
        lambda t: t.collection_move_rename(C, O1, MISSING, O2),
        FileNotFoundError, "collection "),
    "move_rename_onto_object_made_in_the_txn": (
        lambda t: t.touch(C, O2).collection_move_rename(C, O1, C, O2),
        FileExistsError, "/"),
    "clone_of_missing_object": (
        lambda t: t.clone(C, GONE, O2),
        FileNotFoundError, "/"),
    "remove_of_missing_object": (
        lambda t: t.remove(C, GONE),
        FileNotFoundError, "/"),
    "remove_twice_in_one_txn": (
        lambda t: t.remove(C, O1).remove(C, O1),
        FileNotFoundError, "/"),
    "rmattr_of_missing_object": (
        lambda t: t.rmattr(C, GONE, "a"),
        FileNotFoundError, "/"),
}

_ACCEPTED = {
    "rmcoll_of_one_emptied_earlier_in_the_txn": (
        lambda t: t.remove(C, O1).remove_collection(C),
        lambda s: not s.collection_exists(C)),
    "mkcoll_then_write_in_one_txn": (
        lambda t: t.create_collection(C2).write(C2, O2, 0, b"new"),
        lambda s: s.read(C2, O2) == b"new"),
    "move_rename_into_collection_made_in_the_txn": (
        lambda t: t.create_collection(C2).collection_move_rename(
            C, O1, C2, O2),
        lambda s: s.read(C2, O2) == b"keep" and not s.exists(C, O1)),
    "rmcoll_then_mkcoll_then_write_in_one_txn": (
        lambda t: t.remove(C, O1).remove_collection(C).create_collection(C)
        .write(C, O2, 0, b"again"),
        lambda s: s.read(C, O2) == b"again" and not s.exists(C, O1)),
    "mkcoll_then_rmcoll_in_one_txn": (
        lambda t: t.create_collection(C2).remove_collection(C2),
        lambda s: not s.collection_exists(C2) and s.read(C, O1) == b"keep"),
    "remove_then_recreate_then_rmattr_in_one_txn": (
        lambda t: t.remove(C, O1).touch(C, O1).rmattr(C, O1, "a"),
        lambda s: s.read(C, O1) == b"" and s.getattrs(C, O1) == {}),
}


class TestValidation:
    @pytest.fixture
    def filled(self, store):
        store.queue_transaction(
            Transaction().write(C, O1, 0, b"keep")
            .setattrs(C, O1, {"a": b"1"}).omap_setkeys(C, O1, {"k": b"v"}))
        return store

    @pytest.mark.parametrize("case", sorted(_REJECTED))
    def test_rejected_with_its_error_and_nothing_written(self, filled, case):
        build, exc, text = _REJECTED[case]
        before = _contents(filled)
        with pytest.raises(exc, match=text) as caught:
            filled.queue_transaction(
                build(Transaction().write(C, O1, 0, b"clobber")))
        assert type(caught.value) is exc
        assert _contents(filled) == before

    @pytest.mark.parametrize("case", sorted(_ACCEPTED))
    def test_accepted_when_an_earlier_op_made_it_valid(self, filled, case):
        build, holds = _ACCEPTED[case]
        filled.queue_transaction(build(Transaction()))
        assert holds(filled)


def iterators_opened_by_a_shard_write(store, monkeypatch, collections=200):
    """Fill a kv-backed store with ``collections`` collections, then
    commit one transaction shaped like an EC shard write and count the
    iterators its db was asked for (each one sorts a column family)."""
    t = Transaction()
    for ps in range(collections):
        t.create_collection(coll_t(7, ps, 2))
    store.queue_transaction(t)
    opened = []
    real = store.db.get_iterator
    monkeypatch.setattr(
        store.db, "get_iterator",
        lambda prefix: opened.append(prefix) or real(prefix))
    c = coll_t(7, collections // 2, 2)
    store.queue_transaction(
        Transaction().touch(c, O1).write(c, O1, 0, b"s" * 70000)
        .truncate(c, O1, 70000)
        .setattrs(c, O1, {"hinfo": b"h", "v": b"1", "reqid": b"r"})
        .omap_setkeys(c, O1, {"log": b"e"}))
    assert store.read(c, O1) == b"s" * 70000
    return opened

