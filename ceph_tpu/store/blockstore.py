"""BlockStore: the BlueStore-grade engine — raw block space, extent
maps, checksums at rest, copy-on-write blobs.

Behavioral twin of the reference's production store
(src/os/bluestore/BlueStore.cc): object data lives as **blobs** in a
raw block file carved by an allocator; per-object **extent maps** map
logical ranges onto blobs; every blob carries a **crc32c checksum
verified on every read** (checksum-at-rest — a flipped bit on disk
surfaces as EIO, which deep scrub turns into a repairable
inconsistency); metadata (extent maps, xattrs, omap, blob refcounts)
rides a KeyValueDB (ceph_tpu/kv FileDB — the RocksDB role) whose WAL
makes every transaction atomic and durable.

Mapping of BlueStore's moving parts:

- allocator (Avl/Bitmap/...): a free-extent list over ``min_alloc``
  units, rebuilt at mount from the live blob set (the FreelistManager
  role); torn writes can only leak space, never corrupt — leaked blobs
  are reclaimed by the mount-time sweep (fsck-lite);
- deferred small writes: payloads up to ``INLINE_MAX`` are stored in
  the kv (committed by the kv WAL — one durable write instead of block
  write + fsync + kv commit), the same latency trade BlueStore's
  deferred-write policy makes for small I/O: each is a kv value of its
  own (a *piece*, family D), which an extent names like a blob;
- big writes are COW: fresh extents are allocated, written and fsync'd
  BEFORE the kv batch commits the new extent map, so a crash leaves
  either the old object or the new one, never a tear;
- an extent points INTO a blob (``blob_off``): overwriting part of a
  blob edits the extent map and neither reads nor rewrites the edges
  that survive; a blob's refcount counts the extents that name it and
  its units return to the allocator when the last one goes;
- clone: extent maps are copied and blob refcounts bumped (the
  SharedBlob role) — snapshots share unmodified data at rest;
- checksums: one crc32c per ``CSUM_CHUNK`` (4 KiB: BlueStore's
  csum_chunk_order 12) of a blob, kept under the blob's id in family K;
  a read verifies the chunks it touches, fsck every extent.  When an
  object's pieces pass ``PIECES_MAX`` they are folded, with the blobs
  under them, into one new blob: the one write path that reads.

Write ordering invariant: block-file data is durable before the kv
batch that references it commits; the kv batch is the commit point.
"""

from __future__ import annotations

import functools
import json
import os
import struct
import threading
import time
from typing import NamedTuple

from ceph_tpu.common.fault_injector import (
    InjectedError,
    store_data_fault,
    store_fault_check,
)
from ceph_tpu.kv import FileDB, MemDB, WriteBatch
from ceph_tpu.native import crc32c, crc32c_chunks
from ceph_tpu.store.kstore import (
    _TxnView,
    _ckey,
    _okey,
    _parse_okey,
    _prefix_end,
)
from ceph_tpu.store.objectstore import (
    ObjectStore,
    Transaction,
    TxOp,
    coll_t,
    ghobject_t,
)

SEP = "\x01"
MIN_ALLOC = 65536        # min_alloc_size: block allocation unit
INLINE_MAX = 4096        # small writes stay in kv (deferred-write role)
CSUM_CHUNK = 4096        # checksum granularity of a raw blob
PIECES_MAX = 64          # kv pieces an object may hold before the fold


class BlobError(OSError):
    pass


class _Allocator:
    """Free-extent allocator over MIN_ALLOC units (the Bitmap/Avl
    allocator role, unit granularity)."""

    def __init__(self):
        self._free: list[tuple[int, int]] = []  # (unit_off, units), sorted
        self.end_units = 0  # high-water mark (file grows on demand)

    def init_from_used(self, used: set[int], end_units: int) -> None:
        self.end_units = end_units
        self._free = []
        run_start = None
        for u in range(end_units):
            if u in used:
                if run_start is not None:
                    self._free.append((run_start, u - run_start))
                    run_start = None
            elif run_start is None:
                run_start = u
        if run_start is not None:
            self._free.append((run_start, end_units - run_start))

    def alloc(self, units: int) -> int:
        """First-fit; grows the device when no run is large enough."""
        for i, (off, n) in enumerate(self._free):
            if n >= units:
                if n == units:
                    self._free.pop(i)
                else:
                    self._free[i] = (off + units, n - units)
                return off
        off = self.end_units
        self.end_units += units
        return off

    def free(self, off: int, units: int) -> None:
        self._free.append((off, units))
        self._free.sort()
        # coalesce neighbours
        merged: list[tuple[int, int]] = []
        for o, n in self._free:
            if merged and merged[-1][0] + merged[-1][1] == o:
                merged[-1] = (merged[-1][0], merged[-1][1] + n)
            else:
                merged.append((o, n))
        self._free = merged

    def free_units(self) -> int:
        return sum(n for _o, n in self._free)


class _BitmapAllocator:
    """Bit-per-unit allocator (the BitmapAllocator role,
    src/os/bluestore/BitmapAllocator.cc): same interface as the
    first-fit extent list, different structure — O(1) free, scan
    alloc with a rolling cursor so sequential workloads don't rescan
    the device head every time."""

    def __init__(self):
        self._bits = bytearray()  # 1 = used
        self.end_units = 0
        self._cursor = 0

    def _used(self, u: int) -> bool:
        return bool(self._bits[u >> 3] & (1 << (u & 7)))

    def _set(self, u: int, used: bool) -> None:
        if used:
            self._bits[u >> 3] |= 1 << (u & 7)
        else:
            self._bits[u >> 3] &= ~(1 << (u & 7))

    def init_from_used(self, used: set[int], end_units: int) -> None:
        self.end_units = end_units
        self._bits = bytearray((end_units + 7) // 8)
        for u in used:
            self._set(u, True)
        self._cursor = 0

    def _grow(self, end: int) -> None:
        if len(self._bits) * 8 < end:
            self._bits.extend(b"\0" * ((end + 7) // 8 - len(self._bits)))
        self.end_units = max(self.end_units, end)

    def alloc(self, units: int) -> int:
        for base in (self._cursor, 0):
            run = 0
            for u in range(base, self.end_units):
                if self._used(u):
                    run = 0
                    continue
                run += 1
                if run == units:
                    start = u - units + 1
                    self._grow(u + 1)
                    for v in range(start, u + 1):
                        self._set(v, True)
                    self._cursor = u + 1
                    return start
            if base == 0:
                break
        start = self.end_units
        self._grow(start + units)
        for v in range(start, start + units):
            self._set(v, True)
        self._cursor = start + units
        return start

    def free(self, off: int, units: int) -> None:
        for u in range(off, off + units):
            self._set(u, False)
        self._cursor = min(self._cursor, off)

    def free_units(self) -> int:
        return sum(
            1 for u in range(self.end_units) if not self._used(u))


class _Blob(NamedTuple):
    """A blob id, parsed.  ``kind``: ``"chunked"`` (raw, a crc per
    CSUM_CHUNK in family K, ``length`` stored bytes), ``"kv"`` (a piece:
    the bytes are the value under the id in family D, one crc),
    ``"whole"`` (one crc over the stored bytes: compressed at rest, or
    a raw blob an older store wrote)."""
    unit: int
    units: int
    crc: int
    alg: str
    length: int      # stored bytes; 0 = the extent map knows (old raw)
    kind: str


class _Txn:
    """What one transaction has done so far: the kv batch and its view,
    every object meta it touched (written once, when it settles), the
    blobs' refcount deltas (an overwrite removes the rollback clone and
    makes the next: all but a few of those +1/-1 cancel), the blobs it
    wrote, what to free after the commit, and the bytes it cost."""

    def __init__(self, db):
        self.batch = WriteBatch()
        self.view = _TxnView(db, self.batch)
        self.metas: dict[str, dict | None] = {}    # okey -> meta | removed
        self.dirty: set[str] = set()               # the metas to write
        self.refs: dict[str, int] = {}
        self.created: set[str] = set()
        self.freed: list[str] = []
        self.block_bytes = 0
        self.folds = 0

    def ref(self, blob: str, by: int) -> None:
        self.refs[blob] = self.refs.get(blob, 0) + by


class BlockStore(ObjectStore):
    """ObjectStore over raw block space + a KeyValueDB (BlueStore role).

    kv column families: C collections, O object meta (size + extent
    map), X xattrs, M omap, R blob refcounts (one per extent that names
    the blob), K a raw blob's chunk crcs (4 bytes a CSUM_CHUNK, packed),
    D the small pieces.  Object meta value is json: ``{"size": N,
    "extents": [[logical_off, blob_id, length, blob_off], ...]}``: the
    extent is ``length`` bytes of the blob from ``blob_off`` on.  Blob
    ids: "unit:units:fp:length" (raw; fp the crc of its K value, so that
    units freed and written again never pass for the blob a stale reader
    still names), "k<seq>:crc" (a piece in D), compressed at rest
    "unit:units:crc:alg:stored_len" (crc over the STORED bytes — verify
    before decompress, like BlueStore's csum-then-decompress order).
    Read, and edited where a write lands on them, but no longer
    written: three-field extents (``blob_off`` 0), "unit:units:crc" raw
    blobs with one crc (an extent cut out of one carries the blob's
    length as a fifth field), and ``"inline": {"off": hex-bytes}``.

    ``compression``: a compressor plugin name ("zlib", ...) enables
    transparent at-rest compression of non-inline blobs; a blob is
    stored compressed only when it shrinks below
    ``compression_required_ratio`` of the raw size (BlueStore's
    bluestore_compression_required_ratio gate).  ``allocator`` selects
    "first-fit" (extent list, Avl role) or "bitmap".

    ``stats`` are running totals the store keeps itself:
    ``block_write_bytes`` (blob data written to the block file),
    ``kv_write_bytes`` (what the kv engine wrote for the commits: WAL
    records, and its checkpoints and superblocks when they fall due),
    ``read_disk_bytes`` (blob bytes read and checksummed for reads),
    ``folds``.
    """

    def __init__(self, path: str, db=None, compression: str = "none",
                 compression_required_ratio: float = 0.875,
                 allocator: str = "first-fit",
                 capacity_bytes: int = 1 << 40):
        from ceph_tpu.store.bluefs import BlueFSLite

        self.path = path
        # advertised device size for statfs (the block file itself
        # grows on demand up to this)
        self.capacity_bytes = capacity_bytes
        os.makedirs(path, exist_ok=True)
        # default: BlueFS-lite — the KV (WAL + checkpoints) lives on
        # the SAME device under the SAME allocator (the BlueStore raw-
        # device model, src/os/bluestore/BlueFS.cc); pass an external
        # db (e.g. FileDB) to split metadata out instead
        if db is None and os.path.isdir(os.path.join(path, "kv")):
            # legacy layout: a pre-BlueFS store keeps its KV in the
            # kv/ sidecar directory and its device units 0-1 hold BLOB
            # DATA, not superblocks — mounting it as BlueFS would read
            # garbage superblocks, come up with an empty KV, and
            # allocate the WAL over live blobs.  Keep such stores on
            # FileDB (their on-disk contract) instead.
            import logging

            logging.getLogger("ceph_tpu.store").warning(
                "blockstore %s: legacy kv/ sidecar layout detected; "
                "staying on FileDB (create a fresh store to migrate "
                "to the BlueFS-lite co-located KV)", path)
            db = FileDB(os.path.join(path, "kv"))
        self.db = db if db is not None else BlueFSLite()
        self._block_path = os.path.join(path, "block")
        self._fd: int | None = None
        self._alloc = (
            _BitmapAllocator() if allocator == "bitmap" else _Allocator())
        self._txn_lock = threading.Lock()
        self._piece_seq = 0     # the last piece id handed out
        self.stats = dict.fromkeys(
            ("block_write_bytes", "kv_write_bytes", "read_disk_bytes",
             "folds"), 0)
        self._compressor = None
        if compression and compression != "none":
            from ceph_tpu import compressor as _comp

            self._compressor = _comp.create(compression)
            self._comp_alg = compression
        self._comp_ratio = compression_required_ratio

    blocking_commit = True

    # -- lifecycle -----------------------------------------------------

    def statfs(self) -> dict:
        used_units = self._alloc.end_units - self._alloc.free_units()
        used = used_units * MIN_ALLOC
        return {
            "total": self.capacity_bytes,
            "used": used,
            "available": max(0, self.capacity_bytes - used),
        }

    def mount(self) -> None:
        from ceph_tpu.store.bluefs import BlueFSLite

        store_fault_check("mount", self.fault_domain)
        self._fd = os.open(
            self._block_path, os.O_RDWR | os.O_CREAT, 0o644)
        bluefs = isinstance(self.db, BlueFSLite)
        if bluefs:
            # the KV lives on OUR device: superblock + chains first,
            # then the blob sweep below can read its metadata
            self.db.attach(self._fd)
            self.db.mount()
        elif hasattr(self.db, "mount"):
            self.db.mount()
        # rebuild the allocator from the live blob set (FreelistManager
        # role); anything on disk not referenced by a committed extent
        # map is garbage from a torn write -> reclaimed here (fsck-lite)
        used: set[int] = set()
        end = 0
        it = self.db.get_iterator("O").seek_to_first()
        while it.valid():
            meta = json.loads(it.value())
            for ext in meta.get("extents", []):
                b = _parse_blob(ext[1])
                if b.kind != "kv":
                    used.update(range(b.unit, b.unit + b.units))
                    end = max(end, b.unit + b.units)
            it.next()
        if bluefs:
            kv_units = self.db.used_units()
            used |= kv_units
            end = max(end, max(kv_units) + 1)
        self._alloc.init_from_used(used, end)
        it = self.db.get_iterator("D").seek_to_first()
        while it.valid():
            self._piece_seq = max(
                self._piece_seq, int(it.key()[1:].split(":")[0]))
            it.next()
        if bluefs:
            # allocator live: the WAL may now grow and checkpoints run
            self.db.activate(self._alloc)

    def umount(self) -> None:
        # KV first: BlueFS's final checkpoint writes through our fd
        if hasattr(self.db, "umount"):
            self.db.umount()
        if self._fd is not None:
            os.fsync(self._fd)
            os.close(self._fd)
            self._fd = None

    def fsck(self) -> list[dict]:
        """Verify every extent's checksums at rest (BlueStore fsck
        role), plus the co-located KV's own metadata (superblock
        generations + WAL frames) when BlueFS hosts it."""
        bad: list[dict] = []
        db_fsck = getattr(self.db, "fsck", None)
        if callable(db_fsck):
            bad.extend(db_fsck())
        it = self.db.get_iterator("O").seek_to_first()
        while it.valid():
            meta = json.loads(it.value())
            for ext in meta.get("extents", []):
                try:
                    self._read_extent(ext, 0, ext[2], self.db.get, {})
                except BlobError:
                    bad.append({"okey": it.key(), "logical_off": ext[0],
                                "blob": ext[1]})
            it.next()
        return bad

    # -- object meta ---------------------------------------------------

    def _meta(self, c: coll_t, o: ghobject_t, tx: _Txn | None = None
              ) -> dict | None:
        """The object's meta; with ``tx``, as that transaction has left
        it so far (the dict is the transaction's own: edits to it are
        written when it settles)."""
        key = _okey(c, o)
        if tx is not None and key in tx.metas:
            return tx.metas[key]
        raw = self.db.get("O", key)
        meta = None if raw is None else json.loads(raw)
        if tx is not None and meta is not None:
            tx.metas[key] = meta
        return meta

    def _require(self, c: coll_t, o: ghobject_t) -> dict:
        if not self.collection_exists(c):
            raise FileNotFoundError(f"collection {c}")
        meta = self._meta(c, o)
        if meta is None:
            raise FileNotFoundError(f"{c}/{o}")
        return meta

    # -- reads ---------------------------------------------------------

    def read(self, c, o, off=0, length=None):
        return self.read_object(c, o, off, length, attrs=False)[0]

    def read_object(self, c, o, off=0, length=None, *, attrs=True,
                    marks=None):
        """What a served read asks of an object, in one call and from
        one load of its meta: ``(data, attrs)`` (``attrs`` False: no
        xattrs are looked up, ``{}``), ``FileNotFoundError`` where
        ``exists`` would say no.  ``marks``, like a transaction's, is
        the caller's dict to stamp: ``copies``, the passes made over the
        bytes after the ``pread`` (0: the verified bytes themselves),
        and ``disk_bytes``, what was read from the block file and
        checksummed."""
        store_fault_check("read", self.fault_domain)
        if store_data_fault("read", self.fault_domain, peek=True):
            self._maybe_flip_bit(c, o)
        if marks is None:
            marks = {}
        # writers commit on a worker thread and may free+reuse a blob's
        # units between our meta load and the pread; a checksum failure
        # with a CHANGED meta is that benign race — reload and retry.
        # A failure with the SAME committed meta is genuine bit rot.
        last = None
        try:
            for _ in range(3):
                meta = self._require(c, o)
                if meta == last:
                    break
                marks["disk_bytes"] = 0
                try:
                    data = self._read_with_meta(
                        c, o, meta, off, length, marks)
                except BlobError:
                    last = meta
                    continue
                return data, (self.db.get_prefix("X", _okey(c, o) + SEP)
                              if attrs else {})
            raise BlobError(5, f"checksum mismatch in {c}/{o}")
        finally:
            self.stats["read_disk_bytes"] += marks.get("disk_bytes", 0)

    def _maybe_flip_bit(self, c, o) -> None:
        """Armed bitflip data fault: corrupt one stored byte of this
        object's first extent in the block file AT REST, so the normal
        read path's checksum-at-rest verification surfaces it as EIO
        (the BlueStore bit-rot model).  Objects with no blob (pieces
        only, absent) leave the fault armed for the next eligible
        read."""
        meta = self._meta(c, o)
        at = next(((_parse_blob(e[1]), e[3] if len(e) > 3 else 0)
                   for e in (meta or {}).get("extents", [])
                   if e[1][0] != "k"), None)
        if at is None:
            return
        spec = store_data_fault("read", self.fault_domain)
        if spec is None or not spec.get("bitflip"):
            return
        b, boff = at
        pos = b.unit * MIN_ALLOC + (0 if b.alg else boff)
        byte = os.pread(self._fd, 1, pos)
        if byte:
            os.pwrite(self._fd, bytes([byte[0] ^ 0x40]), pos)

    def _read_with_meta(self, c, o, meta, off=0, length=None, marks=None):
        size = meta["size"]
        end = size if length is None else min(off + length, size)
        if off >= end:
            return b""
        extents = meta.get("extents", [])
        if marks is None:
            marks = {}
        get = self.db.get
        try:
            # extents never overlap (_punch_hole clears a range before
            # anything is written into it), so an extent that covers
            # the whole range is all there is to it: every EC shard
            # written by write_full is one blob, and a sub-read of one
            # stripe unit lies in one extent.  The bytes object the
            # crcs were checked on is returned itself, or one slice.
            for ext in extents:
                lo = ext[0]
                if lo <= off and end <= lo + ext[2]:
                    return self._read_extent(
                        ext, off - lo, end - lo, get, marks)
            # several extents, holes (zero-filled) or old inline
            # pieces: each byte is copied into the buffer and once
            # more out of it
            out = bytearray(end - off)
            for ext in extents:
                lo = ext[0]
                s, e = max(off, lo), min(end, lo + ext[2])
                if s < e:
                    out[s - off : e - off] = self._read_extent(
                        ext, s - lo, e - lo, get, marks)
            for hoff, hexdata in meta.get("inline", {}).items():
                lo = int(hoff)
                data = memoryview(bytes.fromhex(hexdata))
                s, e = max(off, lo), min(end, lo + len(data))
                if s < e:
                    out[s - off : e - off] = data[s - lo : e - lo]
            marks["copies"] = 2
            return bytes(out)
        except BlobError:
            # checksum-at-rest violation (or a benign stale-meta race
            # the caller's retry loop disambiguates)
            raise BlobError(5, f"checksum mismatch in {c}/{o}")

    def stat(self, c, o):
        return self._require(c, o)["size"]

    def exists(self, c, o):
        return self.collection_exists(c) and self._meta(c, o) is not None

    def getattr(self, c, o, name):
        self._require(c, o)
        raw = self.db.get("X", _okey(c, o) + SEP + name)
        if raw is None:
            raise KeyError(name)
        return raw

    def getattrs(self, c, o):
        self._require(c, o)
        return self.db.get_prefix("X", _okey(c, o) + SEP)

    def omap_get(self, c, o):
        self._require(c, o)
        return self.db.get_prefix("M", _okey(c, o) + SEP)

    def omap_get_values(self, c, o, keys):
        self._require(c, o)
        base = _okey(c, o) + SEP
        out = {}
        for k in keys:
            v = self.db.get("M", base + k)
            if v is not None:
                out[k] = v
        return out

    def list_collections(self):
        it = self.db.get_iterator("C").seek_to_first()
        out = []
        while it.valid():
            pool, ps, shard = it.key().split(".")
            out.append(coll_t(int(pool), int(ps), int(shard)))
            it.next()
        return sorted(out)

    def collection_exists(self, c):
        return self.db.get("C", _ckey(c)) is not None

    def collection_list(self, c):
        if not self.collection_exists(c):
            raise FileNotFoundError(f"collection {c}")
        base = _ckey(c) + SEP
        it = self.db.get_iterator("O").lower_bound(base)
        out = []
        while it.valid() and it.key().startswith(base):
            out.append(_parse_okey(it.key())[1])
            it.next()
        return sorted(out)

    # -- transactions --------------------------------------------------

    def queue_transaction(self, txn: Transaction) -> None:
        store_fault_check("write", self.fault_domain)
        marks = txn.marks   # phase boundaries, for the submitter to read
        marks["enter"] = time.monotonic()
        with self._txn_lock:
            marks["locked"] = time.monotonic()
            validate_transaction(self, txn)
            marks["validated"] = time.monotonic()
            tx = _Txn(self.db)
            for op in txn.ops:
                self._translate(op, tx)
            self._settle(tx)
            marks["data"] = time.monotonic()    # pwrite + crc done
            if tx.block_bytes:
                # ordering invariant: blob data durable BEFORE the kv
                # commit that references it
                os.fsync(self._fd)
            marks["fsync"] = time.monotonic()
            tear = store_data_fault("write", self.fault_domain)
            if tear is not None and tear.get("torn"):
                # torn write: blob data hit the platter but the kv
                # batch — the commit point — never lands.  This is
                # BlockStore's REAL crash shape: the object keeps its
                # old committed state and the orphaned blobs are
                # reclaimed by the next mount's fsck-lite sweep.
                raise InjectedError(
                    5, "injected torn write (kv commit dropped)")
            store_fault_check("commit", self.fault_domain)
            kv_before = getattr(self.db, "bytes_written", 0)
            self.db.submit(tx.batch)
            marks["kv"] = time.monotonic()
            # what the commit cost the medium, for the submitter's span
            # and counters and for the store's own totals
            marks["block_bytes"] = tx.block_bytes
            marks["kv_bytes"] = getattr(
                self.db, "bytes_written", 0) - kv_before
            marks["folded"] = tx.folds
            self.stats["block_write_bytes"] += marks["block_bytes"]
            self.stats["kv_write_bytes"] += marks["kv_bytes"]
            self.stats["folds"] += tx.folds
            for blob in tx.freed:
                b = _parse_blob(blob)
                self._alloc.free(b.unit, b.units)
        for cb in txn.on_applied:
            cb()
        for cb in txn.on_commit:
            cb()

    def _settle(self, tx: _Txn) -> None:
        """Every meta the transaction touched goes into the batch once,
        and every blob whose count of extents changed gets its new
        refcount; a blob no extent names any more loses its kv entries
        and (after the commit) its units."""
        for key in tx.dirty:
            meta = tx.metas[key]
            if meta is None:
                tx.view.rmkey("O", key)
            else:
                tx.view.set("O", key, json.dumps(
                    meta, separators=(",", ":")).encode())
        for blob, by in tx.refs.items():
            new = blob in tx.created
            if not by and not new:
                continue
            raw = tx.view.get("R", blob)
            refs = (struct.unpack("<I", raw)[0] if raw
                    else 0 if new else 1) + by
            if refs > 0:
                tx.view.set("R", blob, struct.pack("<I", refs))
                continue
            kind = _parse_blob(blob).kind
            if raw:
                tx.view.rmkey("R", blob)
            if kind == "kv":
                tx.view.rmkey("D", blob)
            else:
                if kind == "chunked":
                    tx.view.rmkey("K", blob)
                tx.freed.append(blob)

    # blob helpers ------------------------------------------------------

    def _write_blob(self, tx: _Txn, data) -> str:
        """``data`` as one new blob in the block file; its id.  The
        caller adds the extent, and the reference, that name it."""
        stored = data
        tag = ""
        if self._compressor is not None and len(data) > INLINE_MAX:
            comp = self._compressor.compress(data)
            if len(comp) <= len(data) * self._comp_ratio:
                stored = comp
                tag = f":{self._comp_alg}:{len(comp)}"
        units = max(1, -(-len(stored) // MIN_ALLOC))
        unit = self._alloc.alloc(units)
        os.pwrite(self._fd, stored, unit * MIN_ALLOC)
        tx.block_bytes += len(stored)
        if tag:
            blob = f"{unit}:{units}:{crc32c(stored)}{tag}"
        else:
            crcs = crc32c_chunks(stored, CSUM_CHUNK)
            blob = f"{unit}:{units}:{crc32c(crcs)}:{len(stored)}"
            tx.view.set("K", blob, crcs)
        tx.created.add(blob)
        return blob

    def _write_piece(self, tx: _Txn, data) -> str:
        """``data`` (at most INLINE_MAX bytes) as a kv value of its
        own; its id."""
        self._piece_seq += 1
        blob = f"k{self._piece_seq}:{crc32c(data)}"
        tx.view.set("D", blob, data)
        tx.created.add(blob)
        return blob

    def _read_extent(self, ext, s: int, e: int, get, marks: dict):
        """Bytes [s, e) of the extent, verified: the chunks of a raw
        blob that the range touches and no others (a piece, a compressed
        blob and an old one-crc blob are verified whole).  ``get`` reads
        the kv (the db's, or a transaction's view)."""
        blob = ext[1]
        b = _parse_blob(blob)
        boff = ext[3] if len(ext) > 3 else 0
        a, z = boff + s, boff + e       # within the blob's content
        if b.kind == "chunked":
            ca = a - a % CSUM_CHUNK
            cz = min(b.length, z + -z % CSUM_CHUNK)
            crcs = get("K", blob)
            data = os.pread(self._fd, cz - ca, b.unit * MIN_ALLOC + ca)
            marks["disk_bytes"] = marks.get("disk_bytes", 0) + len(data)
            if crcs is None or len(data) != cz - ca or crc32c_chunks(
                    data, CSUM_CHUNK) != crcs[
                        ca // CSUM_CHUNK * 4 : -(-cz // CSUM_CHUNK) * 4]:
                raise BlobError(5, f"checksum mismatch in blob {blob}")
            a, z = a - ca, z - ca
        elif b.kind == "kv":
            data = get("D", blob)
            if data is None or crc32c(data) != b.crc:
                raise BlobError(5, f"checksum mismatch in piece {blob}")
        else:
            stored = b.length or (ext[4] if len(ext) > 4 else ext[2])
            data = os.pread(self._fd, stored, b.unit * MIN_ALLOC)
            marks["disk_bytes"] = marks.get("disk_bytes", 0) + len(data)
            if crc32c(data) != b.crc:
                raise BlobError(5, f"checksum mismatch in blob {blob}")
            if b.alg:
                if self._compressor is not None and b.alg == self._comp_alg:
                    data = self._compressor.decompress(data)
                else:  # legacy blob from a differently-configured mount
                    from ceph_tpu import compressor as _comp

                    data = _comp.create(b.alg).decompress(data)
        whole = a == 0 and z == len(data)
        marks["copies"] = 0 if whole else 1
        return data if whole else data[a:z]

    # translation -------------------------------------------------------

    def _translate(self, op, tx: _Txn) -> None:
        """Apply one TxOp into the transaction."""
        kind = op[0]
        if kind == TxOp.MKCOLL:
            tx.view.set("C", _ckey(op[1]), b"1")
        elif kind == TxOp.RMCOLL:
            tx.view.rmkey("C", _ckey(op[1]))
        elif kind == TxOp.TOUCH:
            _, c, o = op
            self._touch(tx, c, o)
        elif kind == TxOp.WRITE:
            _, c, o, off, data = op
            self._write_range(tx, self._touch(tx, c, o, True), off, data)
        elif kind == TxOp.ZERO:
            # zeros need no storage: punch the range out of the extent
            # map — read() zero-fills gaps (BlueStore punch-hole zeroing)
            _, c, o, off, length = op
            meta = self._touch(tx, c, o, True)
            self._punch_hole(tx, meta, off, off + length)
            meta["size"] = max(meta.get("size", 0), off + length)
        elif kind == TxOp.TRUNCATE:
            _, c, o, size = op
            meta = self._touch(tx, c, o, True)
            if size < meta.get("size", 0):
                self._punch_hole(tx, meta, size, meta["size"])
            meta["size"] = size
        elif kind == TxOp.REMOVE:
            _, c, o = op
            self._rm_object(tx, c, o)
        elif kind == TxOp.SETATTRS:
            _, c, o, attrs = op
            self._touch(tx, c, o)
            for k, v in attrs.items():
                tx.view.set("X", _okey(c, o) + SEP + k, v)
        elif kind == TxOp.RMATTR:
            _, c, o, name = op
            tx.view.rmkey("X", _okey(c, o) + SEP + name)
        elif kind == TxOp.OMAP_SETKEYS:
            _, c, o, kv = op
            self._touch(tx, c, o)
            for k, v in kv.items():
                tx.view.set("M", _okey(c, o) + SEP + k, v)
        elif kind == TxOp.OMAP_RMKEYS:
            _, c, o, keys = op
            self._touch(tx, c, o)
            for k in keys:
                tx.view.rmkey("M", _okey(c, o) + SEP + k)
        elif kind == TxOp.OMAP_CLEAR:
            _, c, o = op
            base = _okey(c, o) + SEP
            tx.view.rm_range("M", base, _prefix_end(base))
            self._touch(tx, c, o)
        elif kind == TxOp.CLONE:
            _, c, src, dst = op
            self._clone(tx, c, src, c, dst)
        elif kind == TxOp.COLL_MOVE_RENAME:
            _, src_c, src_o, dst_c, dst_o = op
            self._clone(tx, src_c, src_o, dst_c, dst_o)
            self._rm_object(tx, src_c, src_o)
        else:  # pragma: no cover
            raise ValueError(f"unknown op {kind}")

    def _touch(self, tx: _Txn, c, o, dirty: bool = False) -> dict:
        """The object's meta in ``tx``, made if the object is new;
        ``dirty``: the caller is about to edit it."""
        meta = self._meta(c, o, tx)
        if meta is None:
            meta = tx.metas[_okey(c, o)] = _new_meta()
            dirty = True
        if dirty:
            tx.dirty.add(_okey(c, o))
        return meta

    def _write_range(self, tx: _Txn, meta, off, data) -> None:
        """COW write: large payloads get fresh blobs; small ones are
        pieces in the kv (the deferred-write/small-blob policy)."""
        if not data:
            return
        end = off + len(data)
        self._punch_hole(tx, meta, off, end)
        small = len(data) <= INLINE_MAX
        blob = self._write_piece(tx, data) if small \
            else self._write_blob(tx, data)
        tx.ref(blob, +1)
        extents = meta["extents"]
        extents.append([off, blob, len(data), 0])
        extents.sort()
        meta["size"] = max(meta.get("size", 0), end)
        if small and sum(e[1][0] == "k" for e in extents) > PIECES_MAX:
            # deferred-write flush: many small writes consolidate into
            # one blob, so the extent map stays bounded
            self._fold(tx, meta)

    def _punch_hole(self, tx: _Txn, meta, lo, hi) -> None:
        """Remove [lo, hi) from the extent map.  What survives of an
        extent the range cuts keeps pointing into the same blob: the
        map alone is edited, nothing is read and nothing rewritten (so
        overwriting, e.g. pg repair force-pushing a reconstructed
        object, can replace a blob whose checksum no longer
        verifies)."""
        self._adopt_inline(tx, meta)
        kept = []
        for ext in meta.get("extents", []):
            elo, blob, ln = ext[:3]
            ehi = elo + ln
            if ehi <= lo or elo >= hi:
                kept.append(ext)
                continue
            boff = ext[3] if len(ext) > 3 else 0
            # an old raw blob's one crc covers its whole length, which
            # only its first, uncut extent says: hand it on
            b = _parse_blob(blob)
            blen = ext[4:] or (
                [ln] if b.kind == "whole" and not b.length else [])
            if elo < lo:
                kept.append([elo, blob, lo - elo, boff, *blen])
                tx.ref(blob, +1)
            if ehi > hi:
                kept.append([hi, blob, ehi - hi, boff + hi - elo, *blen])
                tx.ref(blob, +1)
            tx.ref(blob, -1)
        kept.sort()
        meta["extents"] = kept

    def _adopt_inline(self, tx: _Txn, meta) -> None:
        """An older store's inline pieces (hex in the meta value)
        become pieces of their own the first time the object changes."""
        for hoff, hexdata in meta.pop("inline", {}).items():
            data = bytes.fromhex(hexdata)
            blob = self._write_piece(tx, data)
            tx.ref(blob, +1)
            meta["extents"].append([int(hoff), blob, len(data), 0])
        meta["extents"].sort()

    def _fold(self, tx: _Txn, meta) -> None:
        """Rewrite the object's content as one blob (the deferred
        small-write flush): the one write that reads what it replaces.
        Caller holds the txn lock."""
        self._adopt_inline(tx, meta)
        size = max([meta.get("size", 0)]
                   + [e[0] + e[2] for e in meta["extents"]])
        if size == 0:
            return
        buf = bytearray(size)
        for ext in meta["extents"]:
            buf[ext[0] : ext[0] + ext[2]] = self._read_extent(
                ext, 0, ext[2], tx.view.get, {})
            tx.ref(ext[1], -1)
        nb = self._write_blob(tx, bytes(buf))
        tx.ref(nb, +1)
        meta["extents"] = [[0, nb, size, 0]]
        tx.folds += 1

    def _rm_object(self, tx: _Txn, c, o) -> None:
        meta = self._meta(c, o, tx)
        if meta:
            for ext in meta.get("extents", []):
                tx.ref(ext[1], -1)
        tx.metas[_okey(c, o)] = None
        tx.dirty.add(_okey(c, o))
        base = _okey(c, o) + SEP
        for prefix in ("X", "M"):
            tx.view.rm_range(prefix, base, _prefix_end(base))

    def _clone(self, tx: _Txn, src_c, src_o, dst_c, dst_o) -> None:
        """Share blobs with the destination (the SharedBlob role):
        refcounts bump, no data moves."""
        meta = self._meta(src_c, src_o, tx)
        if meta is None:
            meta = _new_meta()
        old = self._meta(dst_c, dst_o, tx)
        if old:     # an overwritten destination lets go of its blobs
            for ext in old.get("extents", []):
                tx.ref(ext[1], -1)
        dst = json.loads(json.dumps(meta))  # deep copy
        for ext in dst.get("extents", []):
            tx.ref(ext[1], +1)
        tx.metas[_okey(dst_c, dst_o)] = dst
        tx.dirty.add(_okey(dst_c, dst_o))
        sbase = _okey(src_c, src_o) + SEP
        dbase = _okey(dst_c, dst_o) + SEP
        for prefix in ("X", "M"):
            for key, val in tx.view.items(prefix, sbase):
                tx.view.set(prefix, dbase + key[len(sbase):], val)


def validate_transaction(store: ObjectStore, txn: Transaction) -> None:
    """MemStore-grade structural checks, raising before anything is
    written so a transaction applies whole or not at all.  Called under
    the store's transaction lock; KStore imports it back.

    It costs what the transaction names, not what the store holds: a
    collection is asked for by name (``collection_exists``, one point
    lookup, once a transaction) and an object likewise (``exists``);
    ``colls`` and ``objs`` overlay what earlier ops of this same
    transaction made or removed.  Only RMCOLL lists, and only the
    objects of the collection it removes."""
    colls: dict[coll_t, bool] = {}
    objs: dict[tuple, bool] = {}

    def coll_exists(c):
        have = colls.get(c)
        if have is None:
            have = colls[c] = store.collection_exists(c)
        return have

    def obj_exists(c, o):
        key = (c, o)
        if key not in objs:
            objs[key] = store.exists(c, o)
        return objs[key]

    for op in txn.ops:
        kind = op[0]
        if kind == TxOp.MKCOLL:
            if coll_exists(op[1]):
                raise FileExistsError(f"collection {op[1]} exists")
            colls[op[1]] = True
        elif kind == TxOp.RMCOLL:
            if not coll_exists(op[1]):
                raise FileNotFoundError(f"collection {op[1]}")
            # ENOTEMPTY semantics (MemStore parity): account for
            # objects created/removed earlier in this same txn
            residual = set()
            if store.collection_exists(op[1]):
                residual = {(op[1], o) for o in store.collection_list(op[1])}
            for (oc, oo), alive in objs.items():
                if oc == op[1]:
                    (residual.add if alive else residual.discard)((oc, oo))
            if residual:
                raise OSError(f"collection {op[1]} not empty")
            colls[op[1]] = False
        elif kind == TxOp.COLL_MOVE_RENAME:
            _, src_c, src_o, dst_c, dst_o = op
            if not coll_exists(src_c) or not obj_exists(src_c, src_o):
                raise FileNotFoundError(f"{src_c}/{src_o}")
            if not coll_exists(dst_c):
                raise FileNotFoundError(f"collection {dst_c}")
            if obj_exists(dst_c, dst_o):
                raise FileExistsError(f"{dst_c}/{dst_o}")
            objs[(src_c, src_o)] = False
            objs[(dst_c, dst_o)] = True
        else:
            c = op[1]
            if not coll_exists(c):
                raise FileNotFoundError(f"collection {c}")
            if kind == TxOp.CLONE:
                _, _, src, dst = op
                if not obj_exists(c, src):
                    raise FileNotFoundError(f"{c}/{src}")
                objs[(c, dst)] = True
            elif kind == TxOp.REMOVE:
                _, _, o = op
                if not obj_exists(c, o):
                    raise FileNotFoundError(f"{c}/{o}")
                objs[(c, o)] = False
            elif kind == TxOp.RMATTR:
                _, _, o, _name = op
                if not obj_exists(c, o):
                    raise FileNotFoundError(f"{c}/{o}")
            else:
                objs[(op[1], op[2])] = True


def _new_meta() -> dict:
    return {"size": 0, "extents": []}


@functools.lru_cache(maxsize=4096)
def _parse_blob(blob: str) -> _Blob:
    """A blob id's fields (the ids: :class:`BlockStore`)."""
    parts = blob.split(":")
    if blob[0] == "k":
        return _Blob(0, 0, int(parts[1]), "", 0, "kv")
    unit, units, crc = int(parts[0]), int(parts[1]), int(parts[2])
    if len(parts) == 4:
        return _Blob(unit, units, crc, "", int(parts[3]), "chunked")
    if len(parts) == 5:
        return _Blob(unit, units, crc, parts[3], int(parts[4]), "whole")
    return _Blob(unit, units, crc, "", 0, "whole")
