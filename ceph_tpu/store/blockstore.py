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
- deferred small writes: payloads under ``inline_max`` are stored
  INLINE in the kv (committed by the kv WAL — one durable write instead
  of block write + fsync + kv commit), the same latency trade
  BlueStore's deferred-write policy makes for small I/O;
- big writes are COW: fresh extents are allocated, written and fsync'd
  BEFORE the kv batch commits the new extent map, so a crash leaves
  either the old object or the new one, never a tear;
- clone: extent maps are copied and blob refcounts bumped (the
  SharedBlob role) — snapshots share unmodified data at rest;
- checksums: one crc32c per blob, checked on read and by fsck.

Write ordering invariant: block-file data is durable before the kv
batch that references it commits; the kv batch is the commit point.
"""

from __future__ import annotations

import json
import os
import struct
import threading
import time

from ceph_tpu.common.fault_injector import (
    InjectedError,
    store_data_fault,
    store_fault_check,
)
from ceph_tpu.kv import FileDB, MemDB, WriteBatch
from ceph_tpu.native import crc32c
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


class BlockStore(ObjectStore):
    """ObjectStore over raw block space + a KeyValueDB (BlueStore role).

    kv column families: C collections, O object meta (size + extent
    map), X xattrs, M omap, R blob refcounts.  Object meta value is
    json: ``{"size": N, "extents": [[logical_off, blob_id, length], ...],
    "inline": {"off": hex-bytes, ...}}``; blob id "unit:units:crc" or,
    compressed at rest, "unit:units:crc:alg:stored_len" (crc over the
    STORED bytes — verify before decompress, like BlueStore's
    csum-then-decompress order).

    ``compression``: a compressor plugin name ("zlib", ...) enables
    transparent at-rest compression of non-inline blobs; a blob is
    stored compressed only when it shrinks below
    ``compression_required_ratio`` of the raw size (BlueStore's
    bluestore_compression_required_ratio gate).  ``allocator`` selects
    "first-fit" (extent list, Avl role) or "bitmap".
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
            for _lo, blob, _ln in meta.get("extents", []):
                unit, units = _parse_blob(blob)[:2]
                used.update(range(unit, unit + units))
                end = max(end, unit + units)
            it.next()
        if bluefs:
            kv_units = self.db.used_units()
            used |= kv_units
            end = max(end, max(kv_units) + 1)
        self._alloc.init_from_used(used, end)
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
        """Verify every blob's checksum at rest (BlueStore fsck role),
        plus the co-located KV's own metadata (superblock generations +
        WAL frames) when BlueFS hosts it."""
        bad: list[dict] = []
        db_fsck = getattr(self.db, "fsck", None)
        if callable(db_fsck):
            bad.extend(db_fsck())
        it = self.db.get_iterator("O").seek_to_first()
        while it.valid():
            meta = json.loads(it.value())
            for lo, blob, ln in meta.get("extents", []):
                try:
                    self._read_blob(blob, ln)
                except BlobError:
                    bad.append({"okey": it.key(), "logical_off": lo,
                                "blob": blob})
            it.next()
        return bad

    # -- object meta ---------------------------------------------------

    def _meta(self, c: coll_t, o: ghobject_t, view=None) -> dict | None:
        get = view.get if view is not None else self.db.get
        raw = get("O", _okey(c, o))
        return None if raw is None else json.loads(raw)

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
        bytes after the ``pread`` (0: the verified blob itself)."""
        store_fault_check("read", self.fault_domain)
        if store_data_fault("read", self.fault_domain, peek=True):
            self._maybe_flip_bit(c, o)
        # writers commit on a worker thread and may free+reuse a blob's
        # units between our meta load and the pread; a checksum failure
        # with a CHANGED meta is that benign race — reload and retry.
        # A failure with the SAME committed meta is genuine bit rot.
        last = None
        for _ in range(3):
            meta = self._require(c, o)
            if meta == last:
                break
            try:
                data = self._read_with_meta(c, o, meta, off, length, marks)
            except BlobError:
                last = meta
                continue
            return data, (self.db.get_prefix("X", _okey(c, o) + SEP)
                          if attrs else {})
        raise BlobError(5, f"checksum mismatch in {c}/{o}")

    def _maybe_flip_bit(self, c, o) -> None:
        """Armed bitflip data fault: corrupt one stored byte of this
        object's first blob AT REST, so the normal read path's
        checksum-at-rest verification surfaces it as EIO (the
        BlueStore bit-rot model).  Objects with no blob (inline-only,
        absent) leave the fault armed for the next eligible read."""
        meta = self._meta(c, o)
        if not meta or not meta.get("extents"):
            return
        spec = store_data_fault("read", self.fault_domain)
        if spec is None or not spec.get("bitflip"):
            return
        unit = _parse_blob(meta["extents"][0][1])[0]
        pos = unit * MIN_ALLOC
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
        # extents and inline pieces never overlap (_punch_hole clears a
        # range before anything is written into it), so an extent that
        # covers the whole range is all there is to it: every EC shard
        # written by write_full is one blob.  The bytes object the crc
        # was checked on is returned itself, or one slice of it.
        for lo, blob, ln in extents:
            if lo <= off and end <= lo + ln:
                data = self._verified_blob(c, o, lo, blob, ln)
                whole = end - off == ln
                marks["copies"] = 0 if whole else 1
                return data if whole else data[off - lo : end - lo]
        # several extents, holes (zero-filled) or inline pieces: each
        # byte is copied into the buffer and once more out of it
        marks["copies"] = 2
        out = bytearray(end - off)
        for lo, blob, ln in extents:
            s, e = max(off, lo), min(end, lo + ln)
            if s < e:
                data = memoryview(self._verified_blob(c, o, lo, blob, ln))
                out[s - off : e - off] = data[s - lo : e - lo]
        for hoff, hexdata in meta.get("inline", {}).items():
            lo = int(hoff)
            data = memoryview(bytes.fromhex(hexdata))
            s, e = max(off, lo), min(end, lo + len(data))
            if s < e:
                out[s - off : e - off] = data[s - lo : e - lo]
        return bytes(out)

    def _verified_blob(self, c, o, lo, blob, ln) -> bytes:
        try:
            return self._read_blob(blob, ln)
        except BlobError:
            # checksum-at-rest violation (or a benign stale-meta race
            # the caller's retry loop disambiguates)
            raise BlobError(5, f"checksum mismatch in {c}/{o} @ {lo}")

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
            batch = WriteBatch()
            view = _TxnView(self.db, batch)
            freed: list[str] = []     # blobs to free AFTER commit
            wrote_block = False
            for op in txn.ops:
                wrote_block |= self._translate(op, view, freed)
            marks["data"] = time.monotonic()    # pwrite + crc done
            if wrote_block:
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
            self.db.submit(batch)
            marks["kv"] = time.monotonic()
            for blob in freed:
                self._deref_blob(blob)
        for cb in txn.on_applied:
            cb()
        for cb in txn.on_commit:
            cb()

    # blob helpers ------------------------------------------------------

    def _write_blob(self, data: bytes) -> str:
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
        return f"{unit}:{units}:{crc32c(stored)}{tag}"

    def _read_blob(self, blob: str, ln: int) -> bytes:
        """pread + crc-verify (+ decompress) one blob; ``ln`` is the
        logical (uncompressed) length the extent map records."""
        unit, _units, crc, alg, stored_len = _parse_blob(blob)
        data = os.pread(self._fd, stored_len if alg else ln,
                        unit * MIN_ALLOC)
        if crc32c(data) != crc:
            raise BlobError(5, f"checksum mismatch in blob {blob}")
        if alg:
            if self._compressor is not None and alg == self._comp_alg:
                data = self._compressor.decompress(data)
            else:  # legacy blob from a differently-configured mount
                from ceph_tpu import compressor as _comp

                data = _comp.create(alg).decompress(data)
        return data

    def _bump_blob(self, view: _TxnView, blob: str, by: int = 1) -> None:
        raw = view.get("R", blob)
        refs = (struct.unpack("<I", raw)[0] if raw else 0) + by
        view.set("R", blob, struct.pack("<I", refs))

    def _deref_blob_in_view(self, view: _TxnView, blob: str,
                            freed: list[str]) -> None:
        raw = view.get("R", blob)
        refs = struct.unpack("<I", raw)[0] if raw else 1
        if refs <= 1:
            view.rmkey("R", blob)
            freed.append(blob)
        else:
            view.set("R", blob, struct.pack("<I", refs - 1))

    def _deref_blob(self, blob: str) -> None:
        unit, units = _parse_blob(blob)[:2]
        self._alloc.free(unit, units)

    # translation -------------------------------------------------------

    def _translate(self, op, view: _TxnView, freed: list[str]) -> bool:
        """Apply one TxOp into the view; returns True when block data
        was written (the caller fsyncs once before commit)."""
        kind = op[0]
        wrote = False
        if kind == TxOp.MKCOLL:
            view.set("C", _ckey(op[1]), b"1")
        elif kind == TxOp.RMCOLL:
            view.rmkey("C", _ckey(op[1]))
        elif kind == TxOp.TOUCH:
            _, c, o = op
            if self._meta(c, o, view) is None:
                self._put_meta(view, c, o, _new_meta())
        elif kind == TxOp.WRITE:
            _, c, o, off, data = op
            meta = self._meta(c, o, view) or _new_meta()
            wrote = self._write_range(view, c, o, meta, off, data, freed)
        elif kind == TxOp.ZERO:
            # zeros need no storage: punch the range out of the extent
            # map — read() zero-fills gaps (BlueStore punch-hole zeroing)
            _, c, o, off, length = op
            meta = self._meta(c, o, view) or _new_meta()
            wrote = self._punch_hole(view, meta, off, off + length, freed)
            meta["size"] = max(meta.get("size", 0), off + length)
            self._put_meta(view, c, o, meta)
        elif kind == TxOp.TRUNCATE:
            _, c, o, size = op
            meta = self._meta(c, o, view) or _new_meta()
            wrote = self._truncate(view, c, o, meta, size, freed)
        elif kind == TxOp.REMOVE:
            _, c, o = op
            self._rm_object(view, c, o, freed)
        elif kind == TxOp.SETATTRS:
            _, c, o, attrs = op
            if self._meta(c, o, view) is None:
                self._put_meta(view, c, o, _new_meta())
            for k, v in attrs.items():
                view.set("X", _okey(c, o) + SEP + k, v)
        elif kind == TxOp.RMATTR:
            _, c, o, name = op
            view.rmkey("X", _okey(c, o) + SEP + name)
        elif kind == TxOp.OMAP_SETKEYS:
            _, c, o, kv = op
            if self._meta(c, o, view) is None:
                self._put_meta(view, c, o, _new_meta())
            for k, v in kv.items():
                view.set("M", _okey(c, o) + SEP + k, v)
        elif kind == TxOp.OMAP_RMKEYS:
            _, c, o, keys = op
            if self._meta(c, o, view) is None:
                self._put_meta(view, c, o, _new_meta())
            for k in keys:
                view.rmkey("M", _okey(c, o) + SEP + k)
        elif kind == TxOp.OMAP_CLEAR:
            _, c, o = op
            base = _okey(c, o) + SEP
            view.rm_range("M", base, _prefix_end(base))
            if self._meta(c, o, view) is None:
                self._put_meta(view, c, o, _new_meta())
        elif kind == TxOp.CLONE:
            _, c, src, dst = op
            wrote = self._clone(view, c, src, c, dst)
        elif kind == TxOp.COLL_MOVE_RENAME:
            _, src_c, src_o, dst_c, dst_o = op
            wrote = self._clone(view, src_c, src_o, dst_c, dst_o)
            self._rm_object(view, src_c, src_o, freed)
        else:  # pragma: no cover
            raise ValueError(f"unknown op {kind}")
        return wrote

    def _put_meta(self, view, c, o, meta: dict) -> None:
        view.set("O", _okey(c, o), json.dumps(meta).encode())

    def _write_range(self, view, c, o, meta, off, data, freed) -> bool:
        """COW write: large payloads get fresh blobs; small ones stay
        inline in kv (the deferred-write/small-blob policy)."""
        if not data:
            if self._meta(c, o, view) is None:
                self._put_meta(view, c, o, meta)
            return False
        end = off + len(data)
        # drop the overwritten range from existing state (edge blobs
        # written there count as block writes for the fsync ordering)
        wrote = self._punch_hole(view, meta, off, end, freed)
        if len(data) <= INLINE_MAX:
            meta.setdefault("inline", {})[str(off)] = data.hex()
            if len(meta["inline"]) > 64:
                # deferred-write flush: many small writes consolidate
                # into one blob so the meta value stays bounded
                wrote |= self._compact(view, meta, freed)
        else:
            blob = self._write_blob(data)
            self._bump_blob(view, blob)
            meta.setdefault("extents", []).append([off, blob, len(data)])
            meta["extents"].sort()
            wrote = True
        meta["size"] = max(meta.get("size", 0), end)
        self._put_meta(view, c, o, meta)
        return wrote

    def _punch_hole(self, view, meta, lo, hi, freed) -> bool:
        """Remove [lo, hi) from the extent map and inline set, keeping
        non-overlapped blob sub-ranges; returns True when edge blobs
        were written to the block file (caller must fsync before the
        kv commit — the durability-ordering invariant)."""
        wrote = False
        new_extents = []
        for elo, blob, ln in meta.get("extents", []):
            ehi = elo + ln
            if ehi <= lo or elo >= hi:
                new_extents.append([elo, blob, ln])
                continue
            # overlapped: re-read SURVIVING edges into inline/new blobs;
            # a fully-covered blob is never read, so overwriting (e.g.
            # pg repair force-pushing a reconstructed object) can
            # replace a blob whose checksum no longer verifies
            edges = [
                (s, e) for s, e in ((elo, min(lo, ehi)), (max(hi, elo), ehi))
                if s < e
            ]
            if edges:
                data = self._read_blob(blob, ln)
                for s, e in edges:
                    part = data[s - elo : e - elo]
                    if len(part) <= INLINE_MAX:
                        meta.setdefault("inline", {})[str(s)] = part.hex()
                    else:
                        nb = self._write_blob(part)
                        wrote = True
                        self._bump_blob(view, nb)
                        new_extents.append([s, nb, len(part)])
            self._deref_blob_in_view(view, blob, freed)
        new_extents.sort()
        meta["extents"] = new_extents
        inline = meta.get("inline", {})
        new_inline = {}
        for hoff, hexdata in inline.items():
            s = int(hoff)
            part = bytes.fromhex(hexdata)
            e = s + len(part)
            if e <= lo or s >= hi:
                new_inline[hoff] = hexdata
                continue
            if s < lo:
                new_inline[str(s)] = part[: lo - s].hex()
            if e > hi:
                new_inline[str(hi)] = part[hi - s:].hex()
        meta["inline"] = new_inline
        return wrote

    def _compact(self, view, meta, freed) -> bool:
        """Rewrite the object's content as one blob (the deferred
        small-write flush).  Caller holds the txn lock."""
        # the span covers everything recorded so far — the caller may
        # not have folded the current write into meta["size"] yet
        size = meta.get("size", 0)
        for lo, _blob, ln in meta.get("extents", []):
            size = max(size, lo + ln)
        for hoff, hexdata in meta.get("inline", {}).items():
            size = max(size, int(hoff) + len(hexdata) // 2)
        if size == 0:
            return False
        buf = bytearray(size)
        for lo, blob, ln in meta.get("extents", []):
            data = self._read_blob(blob, ln)
            buf[lo : lo + ln] = data
            self._deref_blob_in_view(view, blob, freed)
        for hoff, hexdata in meta.get("inline", {}).items():
            part = bytes.fromhex(hexdata)
            lo = int(hoff)
            buf[lo : lo + len(part)] = part
        nb = self._write_blob(bytes(buf))
        self._bump_blob(view, nb)
        meta["extents"] = [[0, nb, size]]
        meta["inline"] = {}
        return True

    def _truncate(self, view, c, o, meta, size, freed) -> bool:
        cur = meta.get("size", 0)
        wrote = False
        if size < cur:
            wrote = self._punch_hole(view, meta, size, cur, freed)
        meta["size"] = size
        self._put_meta(view, c, o, meta)
        return wrote

    def _rm_object(self, view, c, o, freed) -> None:
        meta = self._meta(c, o, view)
        if meta:
            for _lo, blob, _ln in meta.get("extents", []):
                self._deref_blob_in_view(view, blob, freed)
        view.rmkey("O", _okey(c, o))
        base = _okey(c, o) + SEP
        for prefix in ("X", "M"):
            view.rm_range(prefix, base, _prefix_end(base))

    def _clone(self, view, src_c, src_o, dst_c, dst_o) -> bool:
        """Share blobs with the destination (the SharedBlob role):
        refcounts bump, no data moves."""
        meta = self._meta(src_c, src_o, view)
        if meta is None:
            meta = _new_meta()
        dst = json.loads(json.dumps(meta))  # deep copy
        for _lo, blob, _ln in dst.get("extents", []):
            self._bump_blob(view, blob)
        self._put_meta(view, dst_c, dst_o, dst)
        sbase = _okey(src_c, src_o) + SEP
        dbase = _okey(dst_c, dst_o) + SEP
        for prefix in ("X", "M"):
            for key, val in view.items(prefix, sbase):
                view.set(prefix, dbase + key[len(sbase):], val)
        return False


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
    return {"size": 0, "extents": [], "inline": {}}


def _parse_blob(blob: str) -> tuple[int, int, int, str, int]:
    """(unit, units, crc, alg, stored_len); alg == "" for raw blobs
    (3-field legacy ids stay readable — stored_len falls back to the
    extent's logical length at the read site)."""
    parts = blob.split(":")
    unit, units, crc = int(parts[0]), int(parts[1]), int(parts[2])
    if len(parts) == 5:
        return unit, units, crc, parts[3], int(parts[4])
    return unit, units, crc, "", 0
