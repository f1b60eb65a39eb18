"""BlueFS-lite: the KV database living INSIDE the block device.

The reference BlueStore's defining trait is owning one raw device with
BlueFS hosting RocksDB's WAL + SSTs on allocator-managed extents of
that same device (src/os/bluestore/BlueFS.cc, ~4,800 LoC; the
bluestore_bdev superblock machinery).  This module is that contract at
our FileDB's fidelity:

- **superblock**: the device's first two MIN_ALLOC units hold
  alternating-generation JSON slots (crc-framed).  The live slot names
  the checkpoint extent chain and the WAL extent chain — everything
  needed to find the KV before any KV exists.
- **WAL**: crc+sequence-framed batch records appended into an
  allocator-owned extent chain; the chain grows by allocating another
  extent from the SHARED allocator and committing a new superblock
  generation first, so replay always knows the full chain.  Replay
  stops at the first bad frame OR sequence mismatch — stale frames
  from a reused extent can never replay (sequences are globally
  monotonic, never reused).
- **checkpoint**: the whole keyspace serialized to freshly-allocated
  extents; commit order is write-new -> flip superblock -> free-old,
  so a crash at any point leaves one complete, reachable state.

Space accounting is inherently shared: KV extents come from the same
allocator as data blobs, so BlockStore.statfs covers both (the
fullness plane sees metadata growth).  Durability uses pwrite+fsync
barriers on the shared fd (an O_DIRECT raw device would slot in at
the same seam).

Threading: all mutation entry points (mount/umount single-threaded;
submit via BlockStore.queue_transaction) run under BlockStore's
_txn_lock, which also serializes every allocator access — BlueFS
therefore touches the allocator without further locking.
"""

from __future__ import annotations

import json
import os
import struct

from ceph_tpu.common.fault_injector import store_fault_check
from ceph_tpu.kv import MemDB, WriteBatch
from ceph_tpu.native import crc32c

MIN_ALLOC = 65536
_MAGIC = 0xB1FE
_REC_HDR = struct.Struct("<HIIQ")  # magic, len, crc, seq
SUPER_UNITS = (0, 1)  # device units reserved for the two superblocks


class BlueFSLite(MemDB):
    """KeyValueDB co-located on the BlockStore's device."""

    blocking_commit = True

    def __init__(self, checkpoint_bytes: int = 16 * 2**20):
        super().__init__()
        self.checkpoint_bytes = checkpoint_bytes
        self._fd: int | None = None
        self._alloc = None          # set by activate()
        self.gen = 0
        self.cp_extents: list[list[int]] = []   # [[unit, units], ...]
        self.cp_len = 0
        self.wal_extents: list[list[int]] = []
        self.wal_seq = 1            # seq of the wal chain's FIRST record
        self._next_seq = 1
        self._wal_pos = 0           # append offset within the chain
        #: every byte written to the device since the start: WAL
        #: records, checkpoints, superblocks (BlockStore reads the
        #: growth around a submit)
        self.bytes_written = 0

    # -- wiring (called by BlockStore) ---------------------------------

    def attach(self, fd: int) -> None:
        self._fd = fd

    def activate(self, alloc) -> None:
        """Allocator is rebuilt and our extents are marked used: from
        here on the WAL may grow and checkpoints may run."""
        self._alloc = alloc
        if not self.wal_extents:
            self._grow_wal(1)

    def used_units(self) -> set[int]:
        """Every device unit this KV owns (superblocks + chains) — the
        BlockStore folds these into the allocator's used set."""
        out = set(SUPER_UNITS)
        for unit, units in self.cp_extents + self.wal_extents:
            out.update(range(unit, unit + units))
        return out

    # -- superblock ----------------------------------------------------

    def _write_super(self) -> None:
        self.gen += 1
        blob = json.dumps({
            "gen": self.gen, "cp_extents": self.cp_extents,
            "cp_len": self.cp_len, "wal_extents": self.wal_extents,
            "wal_seq": self.wal_seq,
        }).encode()
        rec = struct.pack("<II", crc32c(blob), len(blob)) + blob
        assert len(rec) <= MIN_ALLOC, "superblock overflow"
        slot = SUPER_UNITS[self.gen % 2]
        # the record alone: its length and crc say where it ends, so the
        # slot's other 60-odd KiB need not be written on every flip
        self.bytes_written += os.pwrite(self._fd, rec, slot * MIN_ALLOC)
        os.fsync(self._fd)

    def _read_super(self) -> dict | None:
        best = None
        for slot in SUPER_UNITS:
            raw = os.pread(self._fd, MIN_ALLOC, slot * MIN_ALLOC)
            if len(raw) < 8:
                continue
            crc, ln = struct.unpack_from("<II", raw)
            body = raw[8:8 + ln]
            if len(body) != ln or crc32c(body) != crc:
                continue
            try:
                sb = json.loads(body)
            except ValueError:
                continue
            if best is None or sb["gen"] > best["gen"]:
                best = sb
        return best

    # -- extent-chain IO -----------------------------------------------

    @staticmethod
    def _chain_len(extents: list[list[int]]) -> int:
        return sum(n for _u, n in extents) * MIN_ALLOC

    def _chain_write(self, extents, pos: int, data: bytes) -> None:
        off = 0
        for unit, units in extents:
            span = units * MIN_ALLOC
            lo = max(pos, off)
            hi = min(pos + len(data), off + span)
            if lo < hi:
                self.bytes_written += os.pwrite(
                    self._fd, data[lo - pos:hi - pos],
                    unit * MIN_ALLOC + (lo - off))
            off += span
        if pos + len(data) > off:
            raise IOError("write past extent chain")

    def _chain_read(self, extents, pos: int, length: int) -> bytes:
        parts = []
        off = 0
        want_end = pos + length
        for unit, units in extents:
            span = units * MIN_ALLOC
            lo = max(pos, off)
            hi = min(want_end, off + span)
            if lo < hi:
                got = os.pread(
                    self._fd, hi - lo, unit * MIN_ALLOC + (lo - off))
                # the backing file grows on demand: space past its
                # physical end is unwritten device, i.e. zeros
                parts.append(got.ljust(hi - lo, b"\0"))
            off += span
        return b"".join(parts)

    # -- lifecycle -----------------------------------------------------

    def mount(self) -> None:
        """Load the live superblock generation, the checkpoint, and
        replay the WAL chain (the BlueFS mount + rocksdb recovery)."""
        store_fault_check("mount", "bluefs")
        assert self._fd is not None, "attach() first"
        sb = self._read_super()
        if sb is None:
            return  # fresh device: empty kv; activate() seeds the WAL
        self.gen = sb["gen"]
        self.cp_extents = [list(e) for e in sb["cp_extents"]]
        self.cp_len = sb["cp_len"]
        self.wal_extents = [list(e) for e in sb["wal_extents"]]
        self.wal_seq = sb["wal_seq"]
        if self.cp_len:
            self._load_checkpoint(
                self._chain_read(self.cp_extents, 0, self.cp_len))
        # WAL replay
        pos = 0
        seq = self.wal_seq
        total = self._chain_len(self.wal_extents)
        while pos + _REC_HDR.size <= total:
            hdr = self._chain_read(self.wal_extents, pos, _REC_HDR.size)
            magic, ln, crc, rseq = _REC_HDR.unpack(hdr)
            if magic != _MAGIC or rseq != seq or \
                    pos + _REC_HDR.size + ln > total:
                break
            body = self._chain_read(
                self.wal_extents, pos + _REC_HDR.size, ln)
            if crc32c(body) != crc:
                break
            self._apply(WriteBatch.decode(body))
            pos += _REC_HDR.size + ln
            seq += 1
        self._wal_pos = pos
        self._next_seq = seq

    def umount(self) -> None:
        if self._fd is None:
            return
        if self._alloc is not None:
            self._checkpoint()
        self._fd = None
        self._alloc = None

    # -- fsck ----------------------------------------------------------

    def fsck(self) -> list[dict]:
        """Verify BlueFS metadata at rest: BOTH superblock generation
        slots and every applied WAL frame's crc.

        Mount TOLERATES a corrupt stale superblock (it falls back to
        the other generation) and a torn WAL tail (replay stops) —
        correct for availability, but silent rot in the fallback slot
        means the NEXT crash has no good generation to land on.  fsck
        therefore REPORTS what mount tolerates (the BlueStore
        fsck-vs-mount split)."""
        out: list[dict] = []
        if self._fd is None:
            return out
        for slot in SUPER_UNITS:
            raw = os.pread(self._fd, MIN_ALLOC, slot * MIN_ALLOC)
            if not raw.rstrip(b"\0"):
                continue  # never-written slot (young device), not rot
            ok = len(raw) >= 8
            if ok:
                crc, ln = struct.unpack_from("<II", raw)
                body = raw[8:8 + ln]
                ok = len(body) == ln and crc32c(body) == crc
                if ok:
                    try:
                        json.loads(body)
                    except ValueError:
                        ok = False
            if not ok:
                out.append({"kind": "bluefs-superblock", "slot": slot})
        # WAL frames: every record up to the applied position must
        # still frame and crc — rot under an already-applied record
        # would silently truncate replay after the next crash
        pos = 0
        seq = self.wal_seq
        total = self._chain_len(self.wal_extents)
        while pos < self._wal_pos and pos + _REC_HDR.size <= total:
            hdr = self._chain_read(self.wal_extents, pos, _REC_HDR.size)
            magic, ln, crc, rseq = _REC_HDR.unpack(hdr)
            body_ok = (
                magic == _MAGIC and rseq == seq
                and pos + _REC_HDR.size + ln <= total
            )
            if body_ok:
                body = self._chain_read(
                    self.wal_extents, pos + _REC_HDR.size, ln)
                body_ok = crc32c(body) == crc
            if not body_ok:
                out.append({
                    "kind": "bluefs-wal-frame", "pos": pos, "seq": seq,
                })
                break  # framing is lost from here on
            pos += _REC_HDR.size + ln
            seq += 1
        return out

    # -- writes --------------------------------------------------------

    def submit(self, batch: WriteBatch, sync: bool = True) -> None:
        store_fault_check("commit", "bluefs")
        body = batch.encode()
        rec = _REC_HDR.pack(_MAGIC, len(body), crc32c(body),
                            self._next_seq) + body
        if self._wal_pos + len(rec) > self._chain_len(self.wal_extents):
            self._grow_wal(-(-len(rec) // MIN_ALLOC))
        self._chain_write(self.wal_extents, self._wal_pos, rec)
        if sync:
            os.fsync(self._fd)
        self._wal_pos += len(rec)
        self._next_seq += 1
        with self._lock:
            self._apply(batch)
        if self._wal_pos >= self.checkpoint_bytes:
            self._checkpoint()

    def _grow_wal(self, units: int) -> None:
        """Extend the WAL chain: allocate, then commit the new chain
        via a superblock flip BEFORE any record lands in it."""
        unit = self._alloc.alloc(max(units, 1))
        self.wal_extents.append([unit, max(units, 1)])
        self._write_super()

    def _checkpoint(self) -> None:
        """Compact: serialize the keyspace to fresh extents, flip the
        superblock, then free the old chains (write-new -> commit ->
        drop-old; a crash anywhere leaves one complete state)."""
        out = [struct.pack("<I", len(self._cf))]
        for p in sorted(self._cf):
            cf = self._cf[p]
            penc = p.encode()
            out.append(struct.pack("<I", len(penc)) + penc)
            out.append(struct.pack("<I", len(cf)))
            for k in sorted(cf):
                kenc = k.encode()
                out.append(struct.pack("<I", len(kenc)) + kenc)
                out.append(struct.pack("<I", len(cf[k])) + cf[k])
        blob = b"".join(out)
        blob = struct.pack("<I", crc32c(blob)) + blob
        old_cp = self.cp_extents
        old_wal = self.wal_extents
        cp_units = max(1, -(-len(blob) // MIN_ALLOC))
        new_cp = [[self._alloc.alloc(cp_units), cp_units]]
        self._chain_write(new_cp, 0, blob)
        new_wal = [[self._alloc.alloc(1), 1]]
        os.fsync(self._fd)
        self.cp_extents = new_cp
        self.cp_len = len(blob)
        self.wal_extents = new_wal
        self.wal_seq = self._next_seq
        self._wal_pos = 0
        self._write_super()
        for unit, units in old_cp + old_wal:
            self._alloc.free(unit, units)

    def _load_checkpoint(self, raw: bytes) -> None:
        (crc,) = struct.unpack_from("<I", raw)
        blob = raw[4:]
        if crc32c(blob) != crc:
            return  # torn checkpoint: WAL replay has everything
        off = 0

        def take():
            nonlocal off
            (ln,) = struct.unpack_from("<I", blob, off)
            off += 4
            v = blob[off:off + ln]
            off += ln
            return v

        (ncf,) = struct.unpack_from("<I", blob, off)
        off += 4
        for _ in range(ncf):
            p = take().decode()
            (nk,) = struct.unpack_from("<I", blob, off)
            off += 4
            cf = self._cf.setdefault(p, {})
            self._sorted.pop(p, None)
            for _ in range(nk):
                k = take().decode()
                cf[k] = bytes(take())
