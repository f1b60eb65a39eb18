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
        # a piece: a kv value of its own that the extent names, not a
        # blob in the block file and not bytes in the meta value
        [[off, piece, ln, boff]] = meta["extents"]
        assert (off, ln, boff) == (0, 4, 0) and piece.startswith("k")
        assert store.db.get("D", piece) == b"tiny" and "inline" not in meta
        assert store.stats["block_write_bytes"] == 0
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
        pieces = [e for e in meta["extents"] if e[1].startswith("k")]
        assert len(pieces) <= 64, "pieces unbounded"
        assert store.stats["folds"] == 1
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
        assert len(blob.split(":")) == 4  # ratio gate kept it raw
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
    """The objects ``_read_extent`` hands back, in order."""
    blobs = []
    real = store._read_extent

    def read_extent(*a):
        blobs.append(real(*a))
        return blobs[-1]

    monkeypatch.setattr(store, "_read_extent", read_extent)
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
        assert attrs == {"_v": b"3.7"}
        assert marks == {"copies": 0, "disk_bytes": len(data)}
        # length given and equal to the blob's, and read(): the same object
        assert store.read_object(C, O1, 0, len(data), attrs=False) == (
            blobs[1], {})
        assert store.read(C, O1) is blobs[2] and len(preads) == 3

    @pytest.mark.parametrize("off,length", [
        (0, 100), (MIN_ALLOC + 7, 3 * MIN_ALLOC), (8 * MIN_ALLOC - 5, None),
        (8 * MIN_ALLOC - 5, 4096)])
    def test_sub_range_of_one_blob_is_one_pread_and_one_slice(
            self, store, monkeypatch, off, length):
        """PR 37: the pread and the crcs cover the 4 KiB csum chunks the
        range touches, not the blob."""
        data = os.urandom(8 * MIN_ALLOC)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        preads, marks = _count_preads(monkeypatch), {}
        got, _ = store.read_object(C, O1, off, length, attrs=False, marks=marks)
        end = len(data) if length is None else min(off + length, len(data))
        assert got == data[off:end] and type(got) is bytes
        chunks = -(-end // 4096) * 4096 - off // 4096 * 4096
        assert preads == [chunks] and chunks < len(got) + 8192
        assert marks == {"copies": 1, "disk_bytes": chunks}

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
        assert [e[1].startswith("k") for e in meta["extents"]] == [
            False, True, False]
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
        assert marks == {"copies": 2,
                         "disk_bytes": len(a) + len(b)}
        # a range inside one of several blobs is still one slice of it
        store.read_object(C, O1, 3 * MIN_ALLOC + 5, 100, attrs=False,
                          marks=marks)
        assert marks == {"copies": 1, "disk_bytes": 4096}

    @pytest.mark.parametrize("entry", ["read", "read_object"])
    def test_flipped_byte_on_disk_is_eio(self, store, entry):
        store.queue_transaction(
            Transaction().write(C, O1, 0, os.urandom(8 * MIN_ALLOC)))
        _flip_stored_byte(store, C, O1, at=3 * MIN_ALLOC)
        # a crc a 4 KiB chunk: every read that covers the chunk, none other
        for args in [(), (3 * MIN_ALLOC, 1), (3 * MIN_ALLOC - 5, 10),
                     (MIN_ALLOC, None), (3 * MIN_ALLOC + 4095, 4096)]:
            with pytest.raises(OSError) as ei:
                _read_via(store, entry, C, O1, *args)
            assert ei.value.errno == 5
        for args in [(0, 10), (0, 3 * MIN_ALLOC), (3 * MIN_ALLOC + 4096, None)]:
            assert len(_read_via(store, entry, C, O1, *args)) > 0
        assert len(store.fsck()) == 1

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
        real, calls = store._read_extent, []

        def racing_read_extent(ext, *a):
            calls.append(ext[1])
            if len(calls) == 1:     # the writer wins the race, once
                store.queue_transaction(Transaction().write(C, O1, 0, new))
                raise BlobError(5, "stale")
            return real(ext, *a)

        monkeypatch.setattr(store, "_read_extent", racing_read_extent)
        assert _read_via(store, entry, C, O1) == new
        assert len(calls) == 2 and calls[0] != calls[1]

        def rotten(ext, *a):
            calls.append(ext[1])
            raise BlobError(5, "rot")

        del calls[:]
        monkeypatch.setattr(store, "_read_extent", rotten)
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


# -- PR 37: a small overwrite costs its own bytes ----------------------------

SHARD = 1 << 20     # an EC(4,2) shard of a 4 MiB object
RB = ghobject_t("obj1", snap=7, shard=2)


def _count_pwrites(monkeypatch):
    calls = []
    real = os.pwrite

    def pwrite(fd, data, off):
        calls.append(len(data))
        return real(fd, data, off)

    monkeypatch.setattr(os, "pwrite", pwrite)
    return calls


def _overwrite(store, off, data, rollback=True):
    """One EC sub-write as ``_shard_write_txn`` builds it: the rollback
    clone of the last one goes, the next is made, then the ranged
    write."""
    t = Transaction()
    if rollback:
        if store.exists(C, RB):
            t.remove(C, RB)
        t.clone(C, O1, RB)
    t.write(C, O1, off, data).truncate(C, O1, SHARD)
    t.setattrs(C, O1, {"_version": b"1.%d" % off})
    store.queue_transaction(t)
    return t


def _seeded_overwrites(seed, n):
    rng = __import__("numpy").random.default_rng(seed)
    for _ in range(n):
        yield (int(rng.integers(0, SHARD // 4096)) * 4096,
               rng.integers(0, 256, 4096, dtype="uint8").tobytes())


class TestASmallOverwriteCostsItsOwnBytes:
    """PR 37: extents point into blobs, a crc a 4 KiB chunk, pieces are
    kv values of their own, the fold is bounded."""

    def test_1024_overwrites_replay_remount_and_clone(self, store, tmp_path):
        base = os.urandom(SHARD)
        store.queue_transaction(Transaction().write(C, O1, 0, base))
        want = bytearray(base)
        snap, snap_want = ghobject_t("obj1", snap=3, shard=2), None
        for i, (off, data) in enumerate(_seeded_overwrites(37, 1024), 1):
            _overwrite(store, off, data)
            want[off : off + 4096] = data
            if i == 512:    # a clone taken mid-way keeps what it saw
                store.queue_transaction(Transaction().clone(C, O1, snap))
                snap_want = bytes(want)
            if i % 64 == 0:
                assert store.read(C, O1) == bytes(want), i
                assert store.read(C, O1, off, 4096) == data
        assert store.read(C, snap) == snap_want
        assert 8 <= store.stats["folds"] <= 1024 // 65
        meta = store._require(C, O1)
        assert len(json.dumps(meta)) < 16384 and len(meta["extents"]) <= 130
        assert store.fsck() == []
        store.umount()
        s2 = BlockStore(store.path)
        s2.mount()
        assert s2.read(C, O1) == bytes(want)
        assert s2.read(C, snap) == snap_want
        assert s2.read(C, RB) != s2.read(C, O1)     # the last rollback clone
        assert s2.fsck() == []
        # pieces made after the remount do not reuse a live piece's id
        _overwrite(s2, 8192, b"\x5a" * 4096)
        want[8192:12288] = b"\x5a" * 4096
        assert s2.read(C, O1) == bytes(want) and s2.read(C, snap) == snap_want
        # every blob and piece goes with the last extent that names it
        t = Transaction()
        for o in (O1, RB, snap):
            t.remove(C, o)
        s2.queue_transaction(t)
        for family in ("R", "K", "D", "O"):
            assert not s2.db.get_iterator(family).seek_to_first().valid()
        assert s2._alloc.free_units() + len(s2.db.used_units()) \
            == s2._alloc.end_units

    def test_counters_agree_with_the_medium_and_stay_under_48_kib(
            self, store, monkeypatch):
        store.queue_transaction(
            Transaction().write(C, O1, 0, os.urandom(SHARD)))
        pwrites, preads = _count_pwrites(monkeypatch), _count_preads(monkeypatch)
        before, n = dict(store.stats), 512
        marks = {"block_bytes": 0, "kv_bytes": 0, "folded": 0}
        for off, data in _seeded_overwrites(41, n):
            read_before = len(preads)
            t = _overwrite(store, off, data)
            for k in marks:
                marks[k] += t.marks[k]
            if not t.marks["folded"]:   # only the fold reads
                assert len(preads) == read_before
        grew = {k: store.stats[k] - before[k] for k in before}
        assert grew["block_write_bytes"] + grew["kv_write_bytes"] \
            == sum(pwrites)
        assert (grew["block_write_bytes"], grew["kv_write_bytes"],
                grew["folds"]) == (
            marks["block_bytes"], marks["kv_bytes"], marks["folded"])
        assert 4 <= grew["folds"] <= n // 65 and grew["read_disk_bytes"] == 0
        assert grew["block_write_bytes"] == grew["folds"] * SHARD
        assert sum(pwrites) / n <= 48 * 1024, sum(pwrites) / n
        print("bytes a 4 KiB overwrite:", sum(pwrites) / n, grew)

    def test_a_4k_ranged_read_of_a_1mib_blob_preads_at_most_8k(
            self, store, monkeypatch):
        data = os.urandom(SHARD)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        preads, marks = _count_preads(monkeypatch), {}
        got, _ = store.read_object(C, O1, 40960, 4096, attrs=False, marks=marks)
        assert got == data[40960:45056]
        assert preads == [4096] and marks == {"copies": 0, "disk_bytes": 4096}
        assert store.read(C, O1, 40961, 4096) == data[40961:45057]
        assert preads == [4096, 8192]
        # ... and of an overwritten block: a piece, no pread at all
        _overwrite(store, 40960, b"\x11" * 4096)
        assert store.read(C, O1, 40960, 4096) == b"\x11" * 4096
        assert len(preads) == 2

    def test_a_flipped_bit_is_eio_for_the_reads_that_cover_its_chunk(
            self, store):
        data = os.urandom(SHARD)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        _overwrite(store, 8 * 4096, b"\x22" * 4096, rollback=False)
        for chunk in (0, 7, 9, 100, 255):
            _flip_stored_byte(store, C, O1, at=chunk * 4096 + 17)
            for c2 in (0, 7, 8, 9, 100, 255):
                if c2 != chunk:
                    store.read(C, O1, c2 * 4096, 4096)
            with pytest.raises(OSError) as ei:
                store.read(C, O1, chunk * 4096 + 100, 8)
            assert ei.value.errno == 5
            assert len(store.fsck()) == 1
            _flip_stored_byte(store, C, O1, at=chunk * 4096 + 17)  # back
        assert store.fsck() == []

    def test_a_torn_overwrite_leaves_the_old_object(self, store):
        from ceph_tpu.common.fault_injector import FAULTS, InjectedError

        data = os.urandom(SHARD)
        store.queue_transaction(Transaction().write(C, O1, 0, data))
        _overwrite(store, 4096, b"\x33" * 4096)
        want = store.read(C, O1)
        FAULTS.inject("store.write", torn=True)
        try:
            with pytest.raises(InjectedError):
                _overwrite(store, 4096, b"\x44" * 4096)
            with pytest.raises(InjectedError):    # a big one writes a blob
                FAULTS.inject("store.write", torn=True)
                _overwrite(store, 65536, os.urandom(65536))
        finally:
            FAULTS.clear()
        assert store.read(C, O1) == want
        store.umount()
        s2 = BlockStore(store.path)
        s2.mount()      # the sweep takes the orphaned blob's units back
        assert s2.read(C, O1) == want and s2.fsck() == []

    def test_a_store_in_the_old_form_mounts_reads_and_is_written(
            self, store):
        """Three-field extents, a raw blob under one crc, hex pieces in
        the meta value: as the parent of PR 37 wrote them."""
        import struct

        from ceph_tpu.kv import WriteBatch
        from ceph_tpu.native import crc32c
        from ceph_tpu.store.blockstore import _okey

        a, b = os.urandom(2 * MIN_ALLOC), os.urandom(MIN_ALLOC + 11)
        unit = store._alloc.alloc(2)
        unit_b = store._alloc.alloc(2)
        os.pwrite(store._fd, a, unit * MIN_ALLOC)
        os.pwrite(store._fd, b, unit_b * MIN_ALLOC)
        blob_a, blob_b = (f"{unit}:2:{crc32c(a)}", f"{unit_b}:2:{crc32c(b)}")
        batch = WriteBatch()
        batch.set("O", _okey(C, O1), json.dumps({
            "size": 5 * MIN_ALLOC,
            "extents": [[0, blob_a, len(a)], [3 * MIN_ALLOC, blob_b, len(b)]],
            "inline": {str(2 * MIN_ALLOC + 100): b"old piece".hex()},
        }).encode())
        for blob in (blob_a, blob_b):
            batch.set("R", blob, struct.pack("<I", 1))
        store.db.submit(batch)
        store.umount()
        s2 = BlockStore(store.path)
        s2.mount()
        want = bytearray(5 * MIN_ALLOC)
        want[: len(a)] = a
        want[2 * MIN_ALLOC + 100 : 2 * MIN_ALLOC + 109] = b"old piece"
        want[3 * MIN_ALLOC : 3 * MIN_ALLOC + len(b)] = b
        assert s2.read(C, O1) == bytes(want) and s2.fsck() == []
        assert s2.read(C, O1, 100, 50) == bytes(want[100:150])
        # a new blob does not land on the old ones
        O2 = ghobject_t("obj2", shard=2)
        s2.queue_transaction(Transaction().write(C, O2, 0, os.urandom(MIN_ALLOC * 3)))
        assert s2.read(C, O1) == bytes(want)
        # overwrites cut the old blobs without reading them
        s2.queue_transaction(Transaction().clone(C, O1, RB))
        for off, data in [(4096, b"\x55" * 4096), (2 * MIN_ALLOC + 104, b"XY"),
                          (3 * MIN_ALLOC + 8192, os.urandom(8192))]:
            s2.queue_transaction(Transaction().write(C, O1, off, data))
            want[off : off + len(data)] = data
            assert s2.read(C, O1) == bytes(want)
            assert s2.read(C, O1, off, len(data)) == data
        assert "inline" not in s2._require(C, O1) and s2.fsck() == []
        s2.queue_transaction(Transaction().remove(C, O1))
        free = s2._alloc.free_units()
        s2.queue_transaction(Transaction().remove(C, RB))   # the last names
        assert s2._alloc.free_units() >= free + 4
        assert s2.fsck() == []


def test_crc32c_chunks_is_every_chunks_crc_in_one_call():
    from ceph_tpu import native

    for n in (0, 1, 4095, 4096, 4097, 3 * 4096, 7 * 4096 + 5, SHARD):
        data = os.urandom(n)
        want = b"".join(native.crc32c(data[at : at + 4096]).to_bytes(
            4, "little") for at in range(0, n, 4096))
        for buf in (data, bytearray(data), memoryview(data)):
            assert native.crc32c_chunks(buf, 4096) == want
        assert native.crc32c_chunks(data, 4096, table=True) == want
    small = os.urandom(9000)
    assert native.crc32c_chunks(small, 1000) == b"".join(
        native._py_crc32c(small[at : at + 1000], 0xFFFFFFFF).to_bytes(
            4, "little") for at in range(0, 9000, 1000))
