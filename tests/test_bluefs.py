"""BlueFS-lite (ceph_tpu/store/bluefs.py): the KV living inside the
BlockStore's device under the shared allocator — superblock
generations, WAL replay after kill, checkpoint compaction, shared
space accounting (reference src/os/bluestore/BlueFS.cc)."""

import os
import sys
import threading
import time

import pytest

from ceph_tpu.kv import FileDB, MemDB, WriteBatch
from ceph_tpu.store import Transaction, coll_t, ghobject_t
from ceph_tpu.store.blockstore import MIN_ALLOC, BlockStore
from ceph_tpu.store.bluefs import SUPER_UNITS, BlueFSLite

C = coll_t(1, 0, 0)


def _obj(name: str) -> ghobject_t:
    return ghobject_t(name)


def test_single_device_layout(tmp_path):
    """kv + data share ONE device file: no sidecar kv directory."""
    s = BlockStore(str(tmp_path / "bs"))
    s.mount()
    s.queue_transaction(Transaction().create_collection(C))
    s.queue_transaction(Transaction().write(C, _obj("o"), 0, b"x" * 100))
    s.umount()
    entries = sorted(os.listdir(tmp_path / "bs"))
    assert entries == ["block"], entries


def test_kill_durability_kv_and_data_on_one_device(tmp_path):
    """Die WITHOUT umount (no final checkpoint): remount must replay
    the on-device WAL and serve every committed write."""
    s = BlockStore(str(tmp_path / "bs"))
    s.mount()
    t = Transaction().create_collection(C)
    for i in range(20):
        t.write(C, _obj(f"o{i}"), 0, bytes([i]) * (1000 + i))
    t.setattrs(C, _obj("o3"), {"k": b"v"})
    t.omap_setkeys(C, _obj("o4"), {"a": b"1", "b": b"2"})
    s.queue_transaction(t)
    os.close(s._fd)  # simulated SIGKILL: no umount, no checkpoint
    s2 = BlockStore(str(tmp_path / "bs"))
    s2.mount()
    for i in range(20):
        assert s2.read(C, _obj(f"o{i}")) == bytes([i]) * (1000 + i)
    assert s2.getattr(C, _obj("o3"), "k") == b"v"
    assert s2.omap_get(C, _obj("o4")) == {"a": b"1", "b": b"2"}
    assert s2.fsck() == []
    s2.umount()


def test_checkpoint_compaction_and_replay(tmp_path):
    """Crossing checkpoint_bytes compacts WAL -> checkpoint extents;
    a later kill replays checkpoint + fresh WAL; old extents recycle
    (device usage stays bounded)."""
    db = BlueFSLite(checkpoint_bytes=8 * 1024)
    s = BlockStore(str(tmp_path / "bs"), db=db)
    s.mount()
    s.queue_transaction(Transaction().create_collection(C))
    gen0 = db.gen
    for round_ in range(30):
        t = Transaction()
        t.write(C, _obj("hot"), 0, os.urandom(512))
        t.omap_setkeys(C, _obj("hot"), {f"k{round_}": b"v" * 100})
        s.queue_transaction(t)
    assert db.gen > gen0  # compactions flipped the superblock
    assert db.cp_len > 0
    os.close(s._fd)  # kill after compactions
    s2 = BlockStore(str(tmp_path / "bs"))
    s2.mount()
    assert set(s2.omap_get(C, _obj("hot"))) == {
        f"k{i}" for i in range(30)}
    s2.umount()


def test_shared_allocator_accounting(tmp_path):
    """statfs covers the KV too: metadata growth consumes the same
    device budget as data (the fullness plane sees both)."""
    s = BlockStore(str(tmp_path / "bs"), capacity_bytes=256 * MIN_ALLOC)
    s.mount()
    s.queue_transaction(Transaction().create_collection(C))
    used0 = s.statfs()["used"]
    assert used0 >= len(SUPER_UNITS) * MIN_ALLOC  # superblocks + wal
    s.queue_transaction(
        Transaction().write(C, _obj("big"), 0, b"z" * (4 * MIN_ALLOC)))
    st = s.statfs()
    assert st["used"] >= used0 + 4 * MIN_ALLOC
    assert st["total"] == 256 * MIN_ALLOC
    s.umount()


def test_fsck_reports_corrupt_stale_superblock_slot(tmp_path):
    """Mount tolerates a rotten STALE superblock slot (the live
    generation wins) — fsck must REPORT it instead: silent rot there
    leaves the next torn live-slot write with no good fallback."""
    db = BlueFSLite(checkpoint_bytes=1 << 30)
    s = BlockStore(str(tmp_path / "bs"), db=db)
    s.mount()
    s.queue_transaction(Transaction().create_collection(C))
    s.queue_transaction(Transaction().write(C, _obj("o"), 0, b"keep"))
    # flip the superblock once more so BOTH slots hold a generation
    db._checkpoint()
    assert db.gen >= 2
    assert s.fsck() == []  # both generations intact
    stale_slot = SUPER_UNITS[(db.gen + 1) % 2]
    os.pwrite(db._fd, b"\xff" * 16, stale_slot * MIN_ALLOC + 6)
    bad = s.fsck()
    assert {"kind": "bluefs-superblock", "slot": stale_slot} in bad, bad
    # the damage is metadata-redundancy loss, not data loss: reads and
    # a remount (kill; live slot intact) still serve everything
    assert s.read(C, _obj("o")) == b"keep"
    os.close(s._fd)
    s2 = BlockStore(str(tmp_path / "bs"))
    s2.mount()
    assert s2.read(C, _obj("o")) == b"keep"
    s2.umount()


def test_fsck_reports_corrupt_wal_frame(tmp_path):
    """Rot under an already-applied WAL record: replay-after-crash
    would silently truncate history there — fsck must flag the frame."""
    db = BlueFSLite(checkpoint_bytes=1 << 30)
    s = BlockStore(str(tmp_path / "bs"), db=db)
    s.mount()
    s.queue_transaction(Transaction().create_collection(C))
    for i in range(4):
        s.queue_transaction(
            Transaction().write(C, _obj(f"o{i}"), 0, bytes([i]) * 2000))
    assert s.fsck() == []
    assert db._wal_pos > 0
    # corrupt the SECOND record's body so framing up to it stays valid
    hdr = db._chain_read(db.wal_extents, 0, 18)
    import struct as _struct

    _m, ln, _crc, _seq = _struct.unpack("<HIIQ", hdr)
    second = 18 + ln
    wal_unit = db.wal_extents[0][0]
    os.pwrite(db._fd, b"\xde\xad\xbe\xef",
              wal_unit * MIN_ALLOC + second + 18 + 2)
    bad = s.fsck()
    assert any(b["kind"] == "bluefs-wal-frame" and b["pos"] == second
               for b in bad), bad
    s.umount()


def test_torn_superblock_falls_back_to_previous_generation(tmp_path):
    """A torn superblock write (crash mid-flip) must land on the
    previous generation's complete state, never on garbage."""
    db = BlueFSLite(checkpoint_bytes=1 << 30)
    s = BlockStore(str(tmp_path / "bs"), db=db)
    s.mount()
    s.queue_transaction(Transaction().create_collection(C))
    s.queue_transaction(Transaction().write(C, _obj("o"), 0, b"keep"))
    # force a compaction: gen N (old cp+wal intact, nothing reused
    # yet) -> gen N+1; a crash that tears the N+1 slot must land on N
    db._checkpoint()
    live_slot = SUPER_UNITS[db.gen % 2]
    os.close(s._fd)
    with open(tmp_path / "bs" / "block", "r+b") as f:
        f.seek(live_slot * MIN_ALLOC + 2)
        f.write(b"\xff" * 16)
    s2 = BlockStore(str(tmp_path / "bs"))
    s2.mount()
    # the older generation's WAL still holds every committed batch
    # (freed extents are not reused until a later allocation)
    assert s2.read(C, _obj("o")) == b"keep"
    s2.umount()


# -- the iterator all three KeyValueDBs inherit from MemDB --------------------

@pytest.fixture(params=["memdb", "filedb", "bluefs"])
def db(request, tmp_path):
    if request.param == "memdb":
        return MemDB()
    if request.param == "filedb":
        d = FileDB(str(tmp_path / "kv"))
        d.mount()
        return d
    s = BlockStore(str(tmp_path / "bs"))
    s.mount()
    assert isinstance(s.db, BlueFSLite)
    return s.db


def _drain(it):
    out = []
    while it.valid():
        out.append((it.key(), it.value()))
        it.next()
    return out


def _seed(db):
    b = WriteBatch()
    for k in ("m", "c", "x", "a"):
        b.set("T", k, k.encode())
    db.submit(b)
    return [(k, k.encode()) for k in ("a", "c", "m", "x")]


def test_open_iterator_keeps_keys_and_values_of_its_opening(db):
    was = _seed(db)
    it = db.get_iterator("T").seek_to_first()
    db.submit(WriteBatch().set("T", "b", b"new").set("T", "c", b"over")
              .rmkey("T", "m").rm_range("T", "w", "y"))
    assert _drain(it) == was
    assert _drain(db.get_iterator("T").seek_to_first()) == [
        ("a", b"a"), ("b", b"new"), ("c", b"over")]


_KEY_SET_CHANGES = {
    "set_of_a_new_key": (
        lambda b: b.set("T", "b", b"new"),
        [("a", b"a"), ("b", b"new"), ("c", b"c"), ("m", b"m"), ("x", b"x")]),
    "rmkey": (
        lambda b: b.rmkey("T", "c"),
        [("a", b"a"), ("m", b"m"), ("x", b"x")]),
    "rm_range": (
        lambda b: b.rm_range("T", "b", "n"),
        [("a", b"a"), ("x", b"x")]),
    "rm_prefix": (
        lambda b: b.rm_prefix("T"), []),
    "rm_prefix_then_set": (
        lambda b: b.rm_prefix("T").set("T", "z", b"z").set("T", "d", b"d"),
        [("d", b"d"), ("z", b"z")]),
    "value_overwrite_with_the_key_set_as_it_was": (
        lambda b: b.set("T", "m", b"M2"),
        [("a", b"a"), ("c", b"c"), ("m", b"M2"), ("x", b"x")]),
    "rmkey_of_a_key_that_is_not_there": (
        lambda b: b.rmkey("T", "q"),
        [("a", b"a"), ("c", b"c"), ("m", b"m"), ("x", b"x")]),
}


@pytest.mark.parametrize("case", sorted(_KEY_SET_CHANGES))
def test_fresh_iterator_is_ordered_and_complete_after(db, case):
    change, now = _KEY_SET_CHANGES[case]
    was = _seed(db)
    assert _drain(db.get_iterator("T").seek_to_first()) == was  # list kept
    db.submit(change(WriteBatch()))
    assert _drain(db.get_iterator("T").seek_to_first()) == now
    it = db.get_iterator("T").lower_bound("b")
    assert _drain(it) == [kv for kv in now if kv[0] >= "b"]
    # another family's keys were never touched
    assert _drain(db.get_iterator("U").seek_to_first()) == []


@pytest.mark.parametrize("case", sorted(_KEY_SET_CHANGES))
def test_get_prefix_is_the_iterators_scan_without_the_copy(db, case, monkeypatch):
    """PR 35: one object's attrs are a point read; what an iterator from
    ``lower_bound(base)`` would collect, after any change of the key set,
    and the family is not copied for it."""
    change, _now = _KEY_SET_CHANGES[case]
    b = WriteBatch()
    for k in ("o1\x00_v", "o1\x00hinfo", "o1", "o10\x00_v", "o2\x00_v", "a"):
        b.set("T", k, k.encode())
    db.submit(b)
    db.submit(change(WriteBatch()))

    def by_iterator(base):
        it, out = db.get_iterator("T").lower_bound(base), {}
        while it.valid() and it.key().startswith(base):
            out[it.key()[len(base):]] = it.value()
            it.next()
        return out

    want = {base: by_iterator(base) for base in
            ("o1\x00", "o10\x00", "o2\x00", "o3\x00", "", "z")}
    monkeypatch.setattr(db, "get_iterator", None)     # no iterator is opened
    for base, attrs in want.items():
        assert db.get_prefix("T", base) == attrs
    assert db.get_prefix("U", "o1\x00") == {}
    if case not in ("rm_prefix", "rm_prefix_then_set"):
        assert want["o1\x00"] == {"_v": b"o1\x00_v", "hinfo": b"o1\x00hinfo"}


def test_iterators_share_the_key_list_while_the_key_set_stands(db):
    _seed(db)
    first = db.get_iterator("T")
    db.submit(WriteBatch().set("T", "m", b"M2"))
    second = db.get_iterator("T")
    assert second._keys is first._keys           # no second sort
    db.submit(WriteBatch().set("T", "b", b"new"))
    third = db.get_iterator("T")
    assert third._keys is not first._keys
    assert first._keys == ["a", "c", "m", "x"]   # dropped, never edited


def test_remount_lists_what_was_committed(tmp_path):
    """checkpoint load and WAL replay fill the families behind
    ``_apply``'s back or through it: either way a fresh iterator after
    mount is ordered and complete."""
    d = FileDB(str(tmp_path / "kv"))
    d.mount()
    _seed(d)
    d.umount()                                    # checkpoints
    d = FileDB(str(tmp_path / "kv"))
    d.mount()
    d.submit(WriteBatch().set("T", "b", b"new"))  # into the WAL
    want = [("a", b"a"), ("b", b"new"), ("c", b"c"), ("m", b"m"),
            ("x", b"x")]
    assert _drain(d.get_iterator("T").seek_to_first()) == want
    d2 = FileDB(str(tmp_path / "kv"))             # replay, no umount
    d2.mount()
    assert _drain(d2.get_iterator("T").seek_to_first()) == want


def test_iterators_opened_while_other_threads_submit_are_whole():
    """The kept key list is shared state: writers drop it while readers
    take it.  Every iterator must still be one moment of the family —
    its keys in order and exactly the keys of its own values."""
    d = MemDB()
    stop = time.monotonic() + 0.5
    torn = []

    def write(n):
        i = 0
        while time.monotonic() < stop:
            i += 1
            d.submit(WriteBatch().set("T", f"{n}-{i % 50:03d}", b"v")
                     .rmkey("T", f"{n}-{(i + 25) % 50:03d}"))

    def read():
        while time.monotonic() < stop:
            it = d.get_iterator("T")
            if it._keys != sorted(it._data):
                torn.append((list(it._keys), sorted(it._data)))
                return

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write, args=(n,))
                   for n in range(4)]
        threads += [threading.Thread(target=read) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert torn == []

