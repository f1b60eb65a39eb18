"""KStore tests: the full MemStore behavioral suite re-run over KStore
(objects-in-kv, reference src/os/kstore/KStore.cc) with both the MemDB
and the durable FileDB backends, plus regressions for key-escaping and
prefix-range deletion (round-2 advisor findings)."""

import pytest

from ceph_tpu.kv import FileDB
from ceph_tpu.store import Transaction, coll_t, ghobject_t
from ceph_tpu.store.kstore import KStore, _okey, _parse_okey

# re-run every MemStore test class over KStore (fixture override below)
from tests.test_memstore import *  # noqa: F401,F403

C = coll_t(1, 0, 2)
O1 = ghobject_t("obj1", shard=2)


@pytest.fixture(params=["mem", "filedb"])
def store(request, tmp_path):
    if request.param == "filedb":
        db = FileDB(str(tmp_path / "kv"))
        s = KStore(db)
        s.mount()
    else:
        s = KStore()
    s.queue_transaction(Transaction().create_collection(C))
    return s


class TestKStoreSpecifics:
    def test_a_write_transaction_opens_no_iterator(self, store, monkeypatch):
        """validation asks for the collections a transaction names; it
        does not list the store's (PR 31)."""
        assert iterators_opened_by_a_shard_write(store, monkeypatch) == []

    def test_blocking_commit_forwards_db(self, tmp_path):
        assert KStore().blocking_commit is False
        assert KStore(FileDB(str(tmp_path / "kv"))).blocking_commit is True

    def test_omap_clear_covers_high_keys(self, store):
        """Keys whose first byte is >= 0x7f must not survive OMAP_CLEAR
        (r2 advisor: rm_range upper bound was base+'\\x7f')."""
        kv = {"\x80high": b"h", "\xffmax": b"m", "low": b"l"}
        store.queue_transaction(
            Transaction().touch(C, O1).omap_setkeys(C, O1, kv))
        assert store.omap_get(C, O1) == kv
        store.queue_transaction(Transaction().omap_clear(C, O1))
        assert store.omap_get(C, O1) == {}

    def test_remove_purges_high_keys_no_resurrection(self, store):
        """omap/xattrs with high key bytes must not leak across object
        lifetimes."""
        store.queue_transaction(
            Transaction().touch(C, O1)
            .omap_setkeys(C, O1, {"\x80k": b"v"})
            .setattrs(C, O1, {"\x7fattr": b"a"}))
        store.queue_transaction(Transaction().remove(C, O1))
        store.queue_transaction(Transaction().touch(C, O1))
        assert store.omap_get(C, O1) == {}
        assert store.getattrs(C, O1) == {}

    def test_object_name_with_separator(self, store):
        """Names containing the \\x01 key separator (or the escape char)
        must round-trip and not inject into other objects' key spaces."""
        evil = ghobject_t("a\x01b\x02c", shard=2)
        store.queue_transaction(Transaction().write(C, evil, 0, b"data"))
        store.queue_transaction(
            Transaction().omap_setkeys(C, evil, {"k": b"v"}))
        assert store.read(C, evil) == b"data"
        assert store.collection_list(C) == [evil]
        # key codec roundtrip is exact
        ck, parsed = _parse_okey(_okey(C, evil))
        assert parsed == evil
        # and a sibling whose name is a prefix-component is unaffected
        sib = ghobject_t("a", shard=2)
        store.queue_transaction(Transaction().write(C, sib, 0, b"s"))
        store.queue_transaction(Transaction().remove(C, evil))
        assert store.read(C, sib) == b"s"
        assert store.collection_list(C) == [sib]

    def test_filedb_durability_across_remount(self, tmp_path):
        db = FileDB(str(tmp_path / "kv"))
        s = KStore(db)
        s.mount()
        s.queue_transaction(Transaction().create_collection(C))
        s.queue_transaction(
            Transaction().write(C, O1, 0, b"persist")
            .setattrs(C, O1, {"a": b"1"})
            .omap_setkeys(C, O1, {"m": b"2"}))
        s.umount()
        s2 = KStore(FileDB(str(tmp_path / "kv")))
        s2.mount()
        assert s2.read(C, O1) == b"persist"
        assert s2.getattr(C, O1, "a") == b"1"
        assert s2.omap_get(C, O1) == {"m": b"2"}

    def test_clone_sees_same_txn_writes(self, store):
        t = (Transaction()
             .write(C, O1, 0, b"fresh")
             .clone(C, O1, ghobject_t("copy", shard=2)))
        store.queue_transaction(t)
        assert store.read(C, ghobject_t("copy", shard=2)) == b"fresh"

    def test_remove_then_recreate_same_txn(self, store):
        """REMOVE followed by re-create in ONE txn: the object must exist
        afterwards, empty — no stale size, no resurrected bytes."""
        store.queue_transaction(
            Transaction().write(C, O1, 0, b"old-bytes")
            .omap_setkeys(C, O1, {"m": b"v"}))
        store.queue_transaction(
            Transaction().remove(C, O1).touch(C, O1))
        assert store.exists(C, O1)
        assert store.read(C, O1) == b""
        assert store.stat(C, O1) == 0
        assert store.omap_get(C, O1) == {}
        # remove-then-write must not resurrect old tail bytes
        store.queue_transaction(
            Transaction().remove(C, O1).write(C, O1, 0, b"x"))
        assert store.read(C, O1) == b"x"
        assert store.stat(C, O1) == 1

    def test_clone_sees_same_txn_attrs_and_omap(self, store):
        """CLONE copies same-txn xattr/omap writes, not just data."""
        dst = ghobject_t("copy2", shard=2)
        t = (Transaction()
             .write(C, O1, 0, b"d")
             .setattrs(C, O1, {"a": b"1"})
             .omap_setkeys(C, O1, {"m": b"2"})
             .clone(C, O1, dst))
        store.queue_transaction(t)
        assert store.read(C, dst) == b"d"
        assert store.getattr(C, dst, "a") == b"1"
        assert store.omap_get(C, dst) == {"m": b"2"}
