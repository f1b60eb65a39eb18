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


def _count_preads(monkeypatch):
    calls = []
    real = os.pread

    def pread(fd, n, off):
        calls.append(n)
        return real(fd, n, off)

    monkeypatch.setattr(os, "pread", pread)
    return calls


def _keep_blobs(store, monkeypatch):
    """The objects ``_read_blob`` hands back, in order."""
    blobs = []
    real = store._read_blob

    def read_blob(blob, ln):
        blobs.append(real(blob, ln))
        return blobs[-1]

    monkeypatch.setattr(store, "_read_blob", read_blob)
    return blobs


def _flip_stored_byte(store, c, o, at=10):
    blob = store._require(c, o)["extents"][0][1]
    unit = int(blob.split(":")[0])
    with open(os.path.join(store.path, "block"), "r+b") as f:
        f.seek(unit * MIN_ALLOC + at)
        byte = f.read(1)
        f.seek(unit * MIN_ALLOC + at)
        f.write(bytes([byte[0] ^ 0xFF]))


def _read_via(store, entry, c, o, *a):
    """The same read through either entry point."""
    if entry == "read":
        return store.read(c, o, *a)
    return store.read_object(c, o, *a)[0]


class TestAReadTouchesItsBytesOnce:
    """PR 35: a read is one ``pread`` and one crc, and where one blob
    covers the range the bytes the crc was checked on are the answer;
    only a range over several pieces assembles into a buffer."""

    def test_whole_blob_read_is_one_pread_and_that_object(
            self, store, monkeypatch):
        data = os.urandom(8 * MIN_ALLOC)   # an EC shard: 512 KiB, one blob
        store.queue_transaction(
            Transaction().write(C, O1, 0, data).setattrs(C, O1, {"_v": b"3.7"}))
        preads, blobs = _count_preads(monkeypatch), _keep_blobs(store, monkeypatch)
        marks = {}
        got, attrs = store.read_object(C, O1, marks=marks)
        assert preads == [len(data)] and len(blobs) == 1
        assert got is blobs[0] and type(got) is bytes and got == data
        assert attrs == {"_v": b"3.7"} and marks == {"copies": 0}
        # length given and equal to the blob's, and read(): the same object
        assert store.read_object(C, O1, 0, len(data), attrs=False) == (
            blobs[1], {})
        assert store.read(C, O1) is blobs[2] and len(preads) == 3

    @pytest.mark.parametrize("off,length", [
        (0, 100), (MIN_ALLOC + 7, 3 * MIN_ALLOC), (8 * MIN_ALLOC - 5, None),
        (8 * MIN_ALLOC - 5, 4096)])
    def test_sub_range_of_one_blob_is_one_pread_and_one_slice(
            self, store, monkeypatch, off, length):
        data = os.urandom(8 * MIN_ALLOC)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        preads, marks = _count_preads(monkeypatch), {}
        got, _ = store.read_object(C, O1, off, length, attrs=False, marks=marks)
        end = len(data) if length is None else min(off + length, len(data))
        assert got == data[off:end] and type(got) is bytes
        assert preads == [len(data)] and marks == {"copies": 1}

    @pytest.mark.parametrize("entry", ["read", "read_object"])
    def test_extents_inline_and_hole_still_assemble(self, store, entry):
        want = bytearray(5 * MIN_ALLOC)
        a, b = os.urandom(2 * MIN_ALLOC), os.urandom(MIN_ALLOC + 11)
        small = b"inline piece" * 8
        t = Transaction()
        t.write(C, O1, 0, a)                          # a blob
        t.write(C, O1, 2 * MIN_ALLOC + 1000, small)   # inline, after a hole
        t.write(C, O1, 3 * MIN_ALLOC, b)              # a second blob
        t.truncate(C, O1, 5 * MIN_ALLOC)              # a hole at the end
        store.queue_transaction(t)
        want[: len(a)] = a
        want[2 * MIN_ALLOC + 1000 : 2 * MIN_ALLOC + 1000 + len(small)] = small
        want[3 * MIN_ALLOC : 3 * MIN_ALLOC + len(b)] = b
        meta = store._require(C, O1)
        assert len(meta["extents"]) == 2 and meta["inline"]
        for off, length in [(0, None), (MIN_ALLOC, 3 * MIN_ALLOC),
                            (2 * MIN_ALLOC - 1, 1002), (2 * MIN_ALLOC, 500),
                            (3 * MIN_ALLOC - 1, 2), (4 * MIN_ALLOC, None),
                            (5 * MIN_ALLOC, 10), (0, 10 * MIN_ALLOC)]:
            end = len(want) if length is None else min(off + length, len(want))
            args = (off,) if length is None else (off, length)
            assert _read_via(store, entry, C, O1, *args) == bytes(
                want[off:end]), (off, length)
        marks = {}
        store.read_object(C, O1, attrs=False, marks=marks)
        assert marks == {"copies": 2}
        # a range inside one of several blobs is still one slice of it
        store.read_object(C, O1, 3 * MIN_ALLOC + 5, 100, attrs=False,
                          marks=marks)
        assert marks == {"copies": 1}

    @pytest.mark.parametrize("entry", ["read", "read_object"])
    def test_flipped_byte_on_disk_is_eio(self, store, entry):
        store.queue_transaction(
            Transaction().write(C, O1, 0, os.urandom(8 * MIN_ALLOC)))
        _flip_stored_byte(store, C, O1, at=3 * MIN_ALLOC)
        for args in [(), (0, 10), (5 * MIN_ALLOC, None)]:
            with pytest.raises(OSError) as ei:   # the crc is over the blob
                _read_via(store, entry, C, O1, *args)
            assert ei.value.errno == 5

    @pytest.mark.parametrize("entry", ["read", "read_object"])
    def test_compressed_blob_round_trips(self, tmp_path, entry):
        z = BlockStore(str(tmp_path / "bz"), compression="zlib")
        z.mount()
        z.queue_transaction(Transaction().create_collection(C))
        data = bytes(range(256)) * (2 * MIN_ALLOC // 256)
        z.queue_transaction(Transaction().write(C, O1, 0, data))
        assert len(z._require(C, O1)["extents"][0][1].split(":")) == 5
        assert _read_via(z, entry, C, O1) == data
        assert _read_via(z, entry, C, O1, 300, 70000) == data[300:70300]
        _flip_stored_byte(z, C, O1)
        with pytest.raises(OSError):
            _read_via(z, entry, C, O1)

    @pytest.mark.parametrize("entry", ["read", "read_object"])
    def test_stale_meta_is_retried_and_same_meta_is_rot(
            self, store, monkeypatch, entry):
        """A commit on a worker thread may free and reuse a blob's units
        between the reader's meta load and its pread: a crc failure under
        a CHANGED meta reloads and retries, under the same meta is EIO."""
        from ceph_tpu.store.blockstore import BlobError

        old, new = os.urandom(2 * MIN_ALLOC), os.urandom(2 * MIN_ALLOC)
        store.queue_transaction(Transaction().write(C, O1, 0, old))
        real, calls = store._read_blob, []

        def racing_read_blob(blob, ln):
            calls.append(blob)
            if len(calls) == 1:     # the writer wins the race, once
                store.queue_transaction(Transaction().write(C, O1, 0, new))
                raise BlobError(5, "stale")
            return real(blob, ln)

        monkeypatch.setattr(store, "_read_blob", racing_read_blob)
        assert _read_via(store, entry, C, O1) == new
        assert len(calls) == 2 and calls[0] != calls[1]

        def rotten(blob, ln):
            calls.append(blob)
            raise BlobError(5, "rot")

        del calls[:]
        monkeypatch.setattr(store, "_read_blob", rotten)
        with pytest.raises(OSError) as ei:
            _read_via(store, entry, C, O1)
        assert ei.value.errno == 5 and len(calls) == 1   # same meta: no retry

    def test_absent_object_is_enoent_and_an_inline_one_reads(self, store):
        with pytest.raises(FileNotFoundError):
            store.read_object(C, O1)
        with pytest.raises(FileNotFoundError):
            store.read_object(coll_t(9, 9, 9), O1)
        store.queue_transaction(
            Transaction().write(C, O1, 0, b"tiny").setattrs(C, O1, {"a": b"1"}))
        assert store.read_object(C, O1) == (b"tiny", {"a": b"1"})
        assert store.read_object(C, O1, attrs=False) == (b"tiny", {})


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
