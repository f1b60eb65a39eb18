"""BlockStore: the BlueStore-grade engine — the full MemStore behavioral
suite plus checksum-at-rest, COW blob sharing, allocator reuse, and
kill-durability (reference src/os/bluestore/BlueStore.cc)."""

import json
import os

import pytest

from ceph_tpu.store import Transaction, coll_t, ghobject_t
from ceph_tpu.store.blockstore import MIN_ALLOC, BlockStore

# re-run every MemStore test class over BlockStore (fixture override)
from tests.test_memstore import *  # noqa: F401,F403

C = coll_t(1, 0, 2)
O1 = ghobject_t("obj1", shard=2)


@pytest.fixture
def store(tmp_path):
    s = BlockStore(str(tmp_path / "bs"))
    s.mount()
    s.queue_transaction(Transaction().create_collection(C))
    return s


class TestBlockStoreSpecifics:
    def test_a_write_transaction_opens_no_iterator(self, store, monkeypatch):
        """validation asks for the collections a transaction names; it
        does not list the OSD's (PR 31): a shard commit's whole
        ``queue_transaction`` sorts no column family."""
        assert iterators_opened_by_a_shard_write(store, monkeypatch) == []

    def test_large_write_lands_in_block_file_with_checksum(self, store):
        data = os.urandom(3 * MIN_ALLOC + 123)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        assert store.read(C, O1) == data
        assert os.path.getsize(store._block_path) >= len(data)
        assert store.fsck() == []

    def test_checksum_at_rest_detects_bit_rot(self, store):
        from ceph_tpu.store.blockstore import _okey, _parse_blob

        data = os.urandom(2 * MIN_ALLOC)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        # flip bytes in the middle of the blob ON DISK (locate it via
        # the extent map — with BlueFS co-located the device's first
        # units are KV superblocks, not the blob)
        meta = json.loads(store.db.get("O", _okey(C, O1)))
        unit = _parse_blob(meta["extents"][0][1])[0]
        with open(store._block_path, "r+b") as f:
            f.seek(unit * MIN_ALLOC + MIN_ALLOC // 2)
            f.write(b"\xde\xad\xbe\xef")
        with pytest.raises(OSError) as ei:
            store.read(C, O1)
        assert ei.value.errno == 5  # EIO, BlueStore csum failure shape
        bad = store.fsck()
        assert len(bad) == 1 and "blob" in bad[0]

    def test_clone_shares_blobs_cow(self, store):
        data = os.urandom(2 * MIN_ALLOC)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        O2 = ghobject_t("obj2", shard=2)
        size0 = os.path.getsize(store._block_path)
        store.queue_transaction(Transaction().clone(C, O1, O2))
        # no data moved: the block file did not grow
        assert os.path.getsize(store._block_path) == size0
        assert store.read(C, O2) == data
        # overwriting the clone leaves the original intact (COW)
        patch = os.urandom(2 * MIN_ALLOC)
        store.queue_transaction(Transaction().write(C, O2, 0, patch))
        assert store.read(C, O1) == data
        assert store.read(C, O2) == patch
        # removing the original keeps the shared history consistent
        store.queue_transaction(Transaction().remove(C, O1))
        assert store.read(C, O2) == patch
        assert store.fsck() == []

    def test_small_writes_stay_inline(self, store):
        store.queue_transaction(Transaction().write(C, O1, 0, b"tiny"))
        meta = json.loads(store.db.get("O", _okey_of(store, C, O1)))
        assert meta["extents"] == []
        assert meta["inline"]
        assert store.read(C, O1) == b"tiny"

    def test_allocator_reuses_freed_space(self, store):
        blob = os.urandom(4 * MIN_ALLOC)
        store.queue_transaction(Transaction().write(C, O1, 0, blob))
        size0 = os.path.getsize(store._block_path)
        for _ in range(5):  # overwrite loop: freed extents are reused
            store.queue_transaction(
                Transaction().write(C, O1, 0, os.urandom(4 * MIN_ALLOC)))
        # at most one extra generation in flight: no unbounded growth
        assert os.path.getsize(store._block_path) <= size0 + 4 * MIN_ALLOC

    def test_durability_across_remount(self, tmp_path):
        s = BlockStore(str(tmp_path / "bs"))
        s.mount()
        s.queue_transaction(Transaction().create_collection(C))
        big = os.urandom(MIN_ALLOC + 7)
        s.queue_transaction(
            Transaction().write(C, O1, 0, big)
            .setattrs(C, O1, {"a": b"1"}).omap_setkeys(C, O1, {"m": b"2"}))
        s.umount()
        s2 = BlockStore(str(tmp_path / "bs"))
        s2.mount()
        assert s2.read(C, O1) == big
        assert s2.getattr(C, O1, "a") == b"1"
        assert s2.omap_get(C, O1) == {"m": b"2"}
        assert s2.fsck() == []
        # allocator rebuilt: a new write must not clobber live data
        O2 = ghobject_t("obj2", shard=2)
        s2.queue_transaction(
            Transaction().write(C, O2, 0, os.urandom(2 * MIN_ALLOC)))
        assert s2.read(C, O1) == big


def _okey_of(store, c, o):
    from ceph_tpu.store.kstore import _okey

    return _okey(c, o)


class TestDurabilityOrdering:
    def test_truncate_edge_blob_is_fsynced(self, store, monkeypatch):
        """Surviving-edge blobs written during truncate/punch count as
        block writes: the fsync-before-kv-commit invariant holds."""
        data = os.urandom(2 * MIN_ALLOC)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        syncs = []
        real_fsync = os.fsync
        monkeypatch.setattr(
            os, "fsync",
            lambda fd: (syncs.append(fd), real_fsync(fd))[1])
        store.queue_transaction(
            Transaction().truncate(C, O1, MIN_ALLOC + 8192))
        assert store._fd in syncs, "edge blob committed without fsync"
        assert store.read(C, O1) == data[: MIN_ALLOC + 8192]

    def test_zero_punches_without_allocating(self, store):
        data = os.urandom(2 * MIN_ALLOC)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        size0 = os.path.getsize(store._block_path)
        store.queue_transaction(
            Transaction().zero(C, O1, 0, 100 * MIN_ALLOC))
        # zeros consumed no block space
        assert os.path.getsize(store._block_path) == size0
        assert store.stat(C, O1) == 100 * MIN_ALLOC
        got = store.read(C, O1)
        assert got == b"\0" * (100 * MIN_ALLOC)

    def test_many_small_writes_compact(self, store):
        for i in range(100):
            store.queue_transaction(
                Transaction().write(C, O1, i * 1000, bytes([i]) * 1000))
        meta = json.loads(store.db.get("O", _okey_of(store, C, O1)))
        assert len(meta["inline"]) <= 65, "inline set unbounded"
        want = b"".join(bytes([i]) * 1000 for i in range(100))
        assert store.read(C, O1) == want
        assert store.fsck() == []


class TestCompressionAtRest:
    """bluestore_compression: blobs stored compressed when they shrink
    past the required ratio; crc over STORED bytes, verify before
    decompress (reference BlueStore csum/compression order)."""

    @pytest.fixture
    def zstore(self, tmp_path):
        s = BlockStore(str(tmp_path / "bz"), compression="zlib")
        s.mount()
        s.queue_transaction(Transaction().create_collection(C))
        return s

    def test_compressible_data_shrinks_on_disk(self, zstore):
        data = b"A" * (4 * MIN_ALLOC)  # wildly compressible
        zstore.queue_transaction(Transaction().write(C, O1, 0, data))
        assert zstore.read(C, O1) == data
        meta = zstore._require(C, O1)
        blob = meta["extents"][0][1]
        parts = blob.split(":")
        assert len(parts) == 5 and parts[3] == "zlib"
        # far fewer units than the raw payload needs
        assert int(parts[1]) < 4
        # survives remount (compression state is all in the blob id)
        zstore.umount()
        s2 = BlockStore(zstore.path, compression="zlib")
        s2.mount()
        assert s2.read(C, O1) == data
        assert s2.fsck() == []

    def test_incompressible_data_stays_raw(self, zstore):
        rng = __import__("numpy").random.default_rng(3)
        data = rng.integers(0, 256, 2 * MIN_ALLOC, dtype="uint8").tobytes()
        zstore.queue_transaction(Transaction().write(C, O1, 0, data))
        meta = zstore._require(C, O1)
        blob = meta["extents"][0][1]
        assert len(blob.split(":")) == 3  # ratio gate kept it raw
        assert zstore.read(C, O1) == data

    def test_bit_rot_in_compressed_blob_is_detected(self, zstore):
        data = b"B" * (2 * MIN_ALLOC)
        zstore.queue_transaction(Transaction().write(C, O1, 0, data))
        blob = zstore._require(C, O1)["extents"][0][1]
        unit = int(blob.split(":")[0])
        with open(os.path.join(zstore.path, "block"), "r+b") as f:
            f.seek(unit * MIN_ALLOC + 10)
            f.write(b"\xff")
        with pytest.raises(OSError):
            zstore.read(C, O1)
        assert zstore.fsck() != []

    def test_partial_overwrite_of_compressed_blob(self, zstore):
        data = b"C" * (2 * MIN_ALLOC)
        zstore.queue_transaction(Transaction().write(C, O1, 0, data))
        patch = b"patch!" * 100
        zstore.queue_transaction(
            Transaction().write(C, O1, MIN_ALLOC, patch))
        want = bytearray(data)
        want[MIN_ALLOC : MIN_ALLOC + len(patch)] = patch
        assert zstore.read(C, O1) == bytes(want)


class TestBitmapAllocator:
    @pytest.fixture
    def bstore(self, tmp_path):
        s = BlockStore(str(tmp_path / "bm"), allocator="bitmap")
        s.mount()
        s.queue_transaction(Transaction().create_collection(C))
        return s

    def test_write_read_free_reuse(self, bstore):
        a = ghobject_t("a", shard=2)
        b = ghobject_t("b", shard=2)
        da = b"\x11" * (2 * MIN_ALLOC)
        db = b"\x22" * (3 * MIN_ALLOC)
        bstore.queue_transaction(Transaction().write(C, a, 0, da))
        bstore.queue_transaction(Transaction().write(C, b, 0, db))
        assert bstore.read(C, a) == da
        assert bstore.read(C, b) == db
        free_before = bstore._alloc.free_units()
        bstore.queue_transaction(Transaction().remove(C, a))
        assert bstore._alloc.free_units() >= free_before + 2
        # freed space is reused, not appended
        end = bstore._alloc.end_units
        bstore.queue_transaction(
            Transaction().write(C, a, 0, b"\x33" * (2 * MIN_ALLOC)))
        assert bstore._alloc.end_units == end
        assert bstore.read(C, a) == b"\x33" * (2 * MIN_ALLOC)

    def test_remount_rebuild(self, tmp_path):
        s = BlockStore(str(tmp_path / "bm2"), allocator="bitmap")
        s.mount()
        s.queue_transaction(Transaction().create_collection(C))
        data = b"\x44" * (2 * MIN_ALLOC)
        s.queue_transaction(Transaction().write(C, O1, 0, data))
        s.umount()
        s2 = BlockStore(str(tmp_path / "bm2"), allocator="bitmap")
        s2.mount()
        assert s2.read(C, O1) == data
        assert s2.fsck() == []

    def test_unit_alloc_free_semantics(self):
        from ceph_tpu.store.blockstore import _BitmapAllocator

        a = _BitmapAllocator()
        a.init_from_used(set(), 0)
        x = a.alloc(3)
        y = a.alloc(2)
        assert {x, y} == {0, 3}
        a.free(x, 3)
        assert a.alloc(2) <= 1  # reuses the freed low run
        assert a.free_units() >= 1


class TestLegacyLayoutGuard:
    """A store created before the BlueFS-lite default (KV in the kv/
    sidecar directory, blob data from device unit 0) must never be
    mounted as BlueFS: its units 0-1 hold data, not superblocks, and
    activate() would allocate the WAL over live blobs."""

    def _make_legacy(self, path: str) -> bytes:
        from ceph_tpu.kv import FileDB

        legacy = BlockStore(
            str(path), db=FileDB(os.path.join(path, "kv")))
        legacy.mount()
        legacy.queue_transaction(Transaction().create_collection(C))
        data = os.urandom(2 * MIN_ALLOC)
        legacy.queue_transaction(Transaction().write(C, O1, 0, data))
        legacy.umount()
        return data

    def test_remount_keeps_filedb_and_data(self, tmp_path):
        path = str(tmp_path / "old")
        data = self._make_legacy(path)
        from ceph_tpu.kv import FileDB
        from ceph_tpu.store.bluefs import BlueFSLite

        s = BlockStore(path)  # default db selection
        assert isinstance(s.db, FileDB)
        assert not isinstance(s.db, BlueFSLite)
        s.mount()
        assert s.read(C, O1) == data
        assert s.fsck() == []
        # still writable under the legacy layout
        more = os.urandom(MIN_ALLOC)
        O2 = ghobject_t("obj-post", shard=2)
        s.queue_transaction(Transaction().write(C, O2, 0, more))
        assert s.read(C, O2) == more
        s.umount()

    def test_fresh_store_still_defaults_to_bluefs(self, tmp_path):
        from ceph_tpu.store.bluefs import BlueFSLite

        s = BlockStore(str(tmp_path / "new"))
        assert isinstance(s.db, BlueFSLite)
