"""Versioned wire encoding — the denc/encoding.h twin.

The reference encodes every wire/disk struct with ENCODE_START(v,
compat, bl) ... ENCODE_FINISH(bl) (src/include/encoding.h): a leading
(version, compat_version, length) header per struct so old decoders can
skip unknown tails and new decoders can reject too-old peers.  This
module is the same contract over little-endian struct packing:

    enc = Encoder()
    with enc.versioned(2, 1):
        enc.u32(x); enc.str_(name)
    wire = enc.bytes()

    dec = Decoder(wire)
    with dec.versioned(compat=1) as v:
        x = dec.u32()
        name = dec.str_()
        # fields added in later versions guarded by `v`
    # decoder skips any unread tail of the struct (DECODE_FINISH)
"""

from __future__ import annotations

import contextlib
import struct


class EncodingError(Exception):
    pass


# in place of a blob's length: the blob is not in this buffer, it is
# the frame's data segment (Encoder.blob / Decoder.blob).  No length
# is ever that large (frames.MAX_FRAME_LEN).
DETACHED = 0xFFFFFFFF
# a blob this large that is copied through bytes_ all the same is
# counted (Encoder.copied / Decoder.copied -> msgr_blob_copied_bytes)
BLOB_COPY_FLOOR = 64 * 1024


class Encoder:
    def __init__(self) -> None:
        self._buf = bytearray()
        self.data = None    # the blob that travels beside this buffer
        self.copied = 0     # bytes of large blobs copied into it

    # scalars (little-endian, like ceph_le types)
    def u8(self, v: int) -> None:
        self._buf += struct.pack("<B", v & 0xFF)

    def u16(self, v: int) -> None:
        self._buf += struct.pack("<H", v & 0xFFFF)

    def u32(self, v: int) -> None:
        self._buf += struct.pack("<I", v & 0xFFFFFFFF)

    def u64(self, v: int) -> None:
        self._buf += struct.pack("<Q", v & 0xFFFFFFFFFFFFFFFF)

    def i32(self, v: int) -> None:
        self._buf += struct.pack("<i", v)

    def i64(self, v: int) -> None:
        self._buf += struct.pack("<q", v)

    def bool_(self, v: bool) -> None:
        self.u8(1 if v else 0)

    def bytes_(self, b: bytes) -> None:
        n = len(b)
        if n >= BLOB_COPY_FLOOR:
            self.copied += n
        self.u32(n)
        self._buf += b

    def blob(self, b) -> None:
        """A message's payload.  The first one that is not empty is
        not copied: it stays in ``self.data`` as the caller gave it,
        for whoever frames this buffer to send beside it, and only a
        marker is encoded (the same object given again encodes the
        marker again, so a reply's ``data`` and its first ``outs``
        entry travel once).  An empty blob, or a further one, goes
        inline like any ``bytes_``."""
        if len(b) and (self.data is None or self.data is b):
            self.data = b
            self.u32(DETACHED)
        else:
            self.bytes_(b)

    def str_(self, s: str) -> None:
        self.bytes_(s.encode("utf-8"))

    def raw(self, b: bytes) -> None:
        self._buf += b

    @contextlib.contextmanager
    def versioned(self, version: int, compat: int):
        """ENCODE_START/ENCODE_FINISH: u8 v, u8 compat, u32 length."""
        self.u8(version)
        self.u8(compat)
        pos = len(self._buf)
        self.u32(0)  # placeholder
        yield
        length = len(self._buf) - pos - 4
        self._buf[pos : pos + 4] = struct.pack("<I", length)

    def bytes(self) -> bytes:
        return bytes(self._buf)

    def __len__(self) -> int:
        return len(self._buf)


class Decoder:
    def __init__(self, data: bytes | bytearray | memoryview, off: int = 0,
                 blob: memoryview | None = None):
        self._d = memoryview(data)
        self._off = off
        self._blob = blob   # the frame's data segment, if it has one
        self.copied = 0     # bytes of large blobs copied out

    def _take(self, n: int) -> memoryview:
        if self._off + n > len(self._d):
            raise EncodingError(
                f"buffer underrun: need {n} at {self._off}/{len(self._d)}"
            )
        v = self._d[self._off : self._off + n]
        self._off += n
        return v

    def u8(self) -> int:
        return self._take(1)[0]

    def u16(self) -> int:
        return struct.unpack("<H", self._take(2))[0]

    def u32(self) -> int:
        return struct.unpack("<I", self._take(4))[0]

    def u64(self) -> int:
        return struct.unpack("<Q", self._take(8))[0]

    def i32(self) -> int:
        return struct.unpack("<i", self._take(4))[0]

    def i64(self) -> int:
        return struct.unpack("<q", self._take(8))[0]

    def bool_(self) -> bool:
        return bool(self.u8())

    def _copy_out(self, n: int) -> bytes:
        if n >= BLOB_COPY_FLOOR:
            self.copied += n
        return bytes(self._take(n))

    def bytes_(self) -> bytes:
        return self._copy_out(self.u32())

    def blob(self) -> bytes | memoryview:
        """What ``Encoder.blob`` wrote: the data segment itself (a view
        of the frame's buffer, not a copy) where the marker stands,
        else the inline bytes."""
        n = self.u32()
        if n != DETACHED:
            return self._copy_out(n)
        if self._blob is None:
            raise EncodingError("detached blob but no data segment")
        return self._blob

    def str_(self) -> str:
        return self.bytes_().decode("utf-8")

    def raw(self, n: int) -> bytes:
        return bytes(self._take(n))

    def remaining(self) -> int:
        return len(self._d) - self._off

    @contextlib.contextmanager
    def versioned(self, compat: int = 1):
        """DECODE_START/DECODE_FINISH: yields the peer's struct version;
        skips the unread tail, errors if the struct's compat is newer
        than what we understand."""
        v = self.u8()
        struct_compat = self.u8()
        length = self.u32()
        end = self._off + length
        if end > len(self._d):
            raise EncodingError("versioned struct overruns buffer")
        if struct_compat > compat:
            # peer says decoders older than struct_compat can't parse it
            raise EncodingError(
                f"struct compat {struct_compat} > supported {compat}"
            )
        yield v
        if self._off > end:
            raise EncodingError("versioned struct over-read")
        self._off = end  # skip what we did not understand
