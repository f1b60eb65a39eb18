"""msgr2-style framed wire protocol.

Behavioral twin of the reference's protocol v2 framing
(src/msg/async/frames_v2.h:40-143): a banner exchange, then segmented
frames — preamble (tag, segment count, segment lengths, preamble crc)
followed by the segments and an epilogue carrying per-segment crc32c.
crc mode matches the reference's rev1 epilogue semantics.

SECURE mode (the reference's crypto_onwire.cc): once a connection's
auth handshake establishes a session key, ``write_frame``/``read_frame``
take a :class:`~ceph_tpu.msg.auth.FrameCrypto` and every frame ships as
``u32 length || AES-GCM(tag || nseg || seg_lens || segments)`` with
per-direction keys and counter nonces — confidentiality + integrity
replace the crc epilogue, and any tamper or replay fails the AEAD tag.

All crcs use the native crc32c runtime (ceph_tpu/native), seeded -1
like the reference frame crcs.
"""

from __future__ import annotations

import asyncio
import struct

from ceph_tpu.native import crc32c

BANNER = b"ceph_tpu msgr2.0\n"
MAX_SEGMENTS = 4
MAX_FRAME_LEN = 256 * 1024 * 1024


class Tag:
    """frames_v2.h:40-54 (the subset the mini-cluster speaks)."""

    HELLO = 1
    AUTH_REQUEST = 2
    AUTH_DONE = 3
    MESSAGE = 17
    KEEPALIVE2 = 14
    KEEPALIVE2_ACK = 15
    ACK = 16
    CLOSE = 18
    # on-wire compression negotiation (frames_v2.h:60-61; the reference
    # marks compressed frames via a preamble flag bit — here a distinct
    # tag carries the same information)
    COMPRESSION_REQUEST = 21
    COMPRESSION_DONE = 22
    MESSAGE_COMPRESSED = 23


class FrameError(ConnectionError):
    pass


# what a Messenger counts about its wire (``Messenger.stats``, ``perf
# dump``'s ``msgr_*``): recv_calls / frames_in and reader_wakeups /
# frames_in say how many socket reads and how many resumptions of the
# reader coroutine one frame costs; data_segs / data_bytes count the
# message blobs that travelled as a data segment, uncopied, and
# blob_copied_bytes the bytes of blobs of denc.BLOB_COPY_FLOOR or more
# that went through denc's bytes_ copies all the same
STATS = ("frames_in", "bytes_in", "recv_calls", "reader_wakeups",
         "frames_out", "bytes_out",
         "data_segs_out", "data_segs_in", "data_bytes_out",
         "data_bytes_in", "blob_copied_bytes")


class FrameStream(asyncio.BufferedProtocol):
    """One connection's socket, both directions, under the method names
    the rest of msg/ uses on an asyncio stream pair (``readexactly``,
    ``write``/``writelines``/``drain``/``close``), so it is passed as
    reader and writer alike.

    What it adds is ``readinto``: the socket fills the caller's buffer
    itself (``recv_into`` on the buffer's unfilled tail) and the caller
    is resumed once, when the buffer is full — a frame's segments are
    received where they stay.  ``readexactly`` serves the short control
    reads (banner, preamble) from a small staging buffer, which also
    takes whatever arrives while no read is posted: a burst of small
    frames lands there in one recv and is split without suspending.
    """

    STAGE = 64 * 1024

    def __init__(self, stats: dict | None = None, on_connect=None):
        self.stats = dict.fromkeys(STATS, 0) if stats is None else stats
        self._on_connect = on_connect   # acceptor side: coroutine(stream)
        self._task: asyncio.Task | None = None
        self._loop = asyncio.get_running_loop()
        self._transport: asyncio.Transport | None = None
        self._stage = memoryview(bytearray(self.STAGE))
        self._r = self._w = 0           # staged, unread: _stage[_r:_w]
        self._dest: memoryview | None = None   # readinto's buffer ...
        self._dest_at = 0                      # ... filled this far
        self._need = 0      # staged bytes a parked readexactly wants
        self._waiter: asyncio.Future | None = None
        self._eof = False
        self._exc: BaseException | None = None
        self._lost = False
        self._read_paused = False
        self._write_paused = False
        self._drainers: list[asyncio.Future] = []

    # -- transport callbacks -------------------------------------------

    def connection_made(self, transport) -> None:
        self._transport = transport
        if self._on_connect is not None:
            self._task = self._loop.create_task(self._on_connect(self))
            self._task.add_done_callback(self._on_connect_done)

    def _on_connect_done(self, task: asyncio.Task) -> None:
        # what asyncio's own stream server does with a handler's crash
        if not task.cancelled() and task.exception() is not None:
            self._loop.call_exception_handler({
                "message": "unhandled exception in a connection handler",
                "exception": task.exception(),
                "transport": self._transport,
            })
            self._transport.close()

    def get_buffer(self, sizehint: int) -> memoryview:
        if self._dest is not None:
            return self._dest[self._dest_at:]
        return self._stage[self._w:]

    def buffer_updated(self, nbytes: int) -> None:
        self.stats["recv_calls"] += 1
        self.stats["bytes_in"] += nbytes
        if self._dest is not None:
            self._dest_at += nbytes
            if self._dest_at == len(self._dest):
                self._dest = None
                self._wake()
            return
        self._w += nbytes
        if self._w == len(self._stage):
            self._compact()
            if self._w == len(self._stage):
                # full and nobody reading: the kernel holds the rest
                self._read_paused = True
                self._transport.pause_reading()
        if self._w - self._r >= self._need:
            self._wake()

    def eof_received(self) -> bool:
        self._eof = True
        self._wake()
        return False        # the transport closes itself

    def connection_lost(self, exc) -> None:
        self._lost = True
        self._eof = True
        self._exc = exc
        self._wake()
        self._wake_drainers()

    def pause_writing(self) -> None:
        self._write_paused = True

    def resume_writing(self) -> None:
        self._write_paused = False
        self._wake_drainers()

    def _wake_drainers(self) -> None:
        for fut in self._drainers:
            if not fut.done():
                fut.set_result(None)

    # -- reading -------------------------------------------------------

    def _wake(self) -> None:
        if self._waiter is not None and not self._waiter.done():
            self._waiter.set_result(None)

    def _compact(self) -> None:
        if self._r:
            n = self._w - self._r
            self._stage[:n] = bytes(self._stage[self._r:self._w])
            self._r, self._w = 0, n

    def _consumed(self) -> None:
        """Staged bytes were taken: rewind an empty stage, and let the
        socket deliver again if the full stage had stopped it."""
        if self._r == self._w:
            self._r = self._w = 0
        if self._read_paused and (self._dest is not None
                                  or self._w < len(self._stage)):
            self._read_paused = False
            self._transport.resume_reading()

    async def _park(self, partial, expected: int) -> None:
        """Suspend the one reader until the transport callbacks wake
        it; the stream's end or loss raises what a StreamReader raises
        (``partial`` is called for the bytes got so far)."""
        if self._exc is not None:
            raise self._exc
        if self._eof:
            raise asyncio.IncompleteReadError(partial(), expected)
        assert self._waiter is None, "one reader per stream"
        self._waiter = self._loop.create_future()
        try:
            await self._waiter
        finally:
            self._waiter = None
        self.stats["reader_wakeups"] += 1

    async def readexactly(self, n: int) -> bytes:
        if n > len(self._stage):
            buf = bytearray(n)
            await self.readinto(memoryview(buf))
            return bytes(buf)
        while self._w - self._r < n:
            if self._r + n > len(self._stage):
                self._compact()
                self._consumed()
            self._need = n
            await self._park(
                lambda: bytes(self._stage[self._r:self._w]), n)
        data = bytes(self._stage[self._r:self._r + n])
        self._r += n
        self._consumed()
        return data

    async def readinto(self, dest: memoryview) -> None:
        """Fill ``dest``: first from what is staged, the rest straight
        from the socket."""
        have = min(self._w - self._r, len(dest))
        if have:
            dest[:have] = self._stage[self._r:self._r + have]
            self._r += have
        if have < len(dest):
            self._dest, self._dest_at = dest, have
        self._consumed()
        try:
            while self._dest is not None:
                await self._park(
                    lambda: bytes(dest[:self._dest_at]), len(dest))
        finally:
            self._dest = None

    # -- writing -------------------------------------------------------

    def write(self, data) -> None:
        self.stats["bytes_out"] += len(data)
        self._transport.write(data)

    def writelines(self, bufs: list) -> None:
        self.stats["bytes_out"] += sum(len(b) for b in bufs)
        self._transport.writelines(bufs)

    async def drain(self) -> None:
        if self._lost:
            raise self._exc or ConnectionResetError("Connection lost")
        if self._transport.is_closing():
            # let connection_lost() run before the next write
            await asyncio.sleep(0)
        while self._write_paused and not self._lost:
            fut = self._loop.create_future()
            self._drainers.append(fut)
            try:
                await fut
            finally:
                self._drainers.remove(fut)
        if self._lost:
            raise self._exc or ConnectionResetError("Connection lost")

    def close(self) -> None:
        self._transport.close()


async def send_banner(writer, features: int = 1) -> None:
    writer.write(BANNER + struct.pack("<Q", features))
    await writer.drain()


async def recv_banner(reader) -> int:
    got = await reader.readexactly(len(BANNER))
    if got != BANNER:
        raise FrameError(f"bad banner {got!r}")
    (features,) = struct.unpack("<Q", await reader.readexactly(8))
    return features


def _head(tag: int, seg_lens: list[int]) -> bytes:
    return struct.pack(
        "<BB4I", tag, len(seg_lens),
        *seg_lens, *([0] * (MAX_SEGMENTS - len(seg_lens))),
    )


def _preamble(tag: int, seg_lens: list[int]) -> bytes:
    head = _head(tag, seg_lens)
    return head + struct.pack("<I", crc32c(head))


def _count(stream, key: str) -> None:
    stats = getattr(stream, "stats", None)
    if stats is not None:
        stats[key] += 1


async def write_frame(writer, tag: int, segments: list, crypto=None) -> None:
    """One frame, handed to the transport in one ``writelines``: the
    segments go as they are (anything else that holds bytes, such as a
    C-contiguous uint8 numpy row, as a view of them), each crc is
    computed once."""
    assert 0 < len(segments) <= MAX_SEGMENTS
    segs = [s if isinstance(s, (bytes, bytearray, memoryview))
            else memoryview(s) for s in segments]
    seg_lens = [len(s) for s in segs]
    if crypto is not None:
        ct = crypto.encrypt(b"".join([_head(tag, seg_lens), *segs]))
        writer.writelines([struct.pack("<I", len(ct)), ct])
    else:
        writer.writelines([
            _preamble(tag, seg_lens),
            *segs,
            # epilogue: one crc32c per present segment
            # (frames_v2.h:124-143)
            struct.pack(f"<{len(segs)}I", *(crc32c(s) for s in segs)),
        ])
    _count(writer, "frames_out")
    await writer.drain()


async def _read_body(reader, n: int) -> memoryview:
    """The ``n`` bytes that follow a frame's lengths, in a buffer of
    their own: a FrameStream's socket fills it in place; a plain
    ``asyncio.StreamReader`` hands over its copy."""
    if isinstance(reader, FrameStream):
        body = memoryview(bytearray(n))
        await reader.readinto(body)
        return body
    return memoryview(await reader.readexactly(n))


async def read_frame(reader, crypto=None) -> tuple[int, list[memoryview]]:
    """-> (tag, segments).  The segments are views of the one buffer
    the frame was received into."""
    if crypto is not None:
        (ln,) = struct.unpack("<I", await reader.readexactly(4))
        if ln > MAX_FRAME_LEN:
            raise FrameError("secure frame too large")
        ct = await _read_body(reader, ln)
        try:
            plain = memoryview(crypto.decrypt(ct))
        except Exception as e:  # InvalidTag and friends
            raise FrameError(f"secure frame authentication failed: {e}")
        tag, nseg = plain[0], plain[1]
        if not 0 < nseg <= MAX_SEGMENTS:
            raise FrameError(f"bad segment count {nseg}")
        seg_lens = struct.unpack_from("<4I", plain, 2)[:nseg]
        off = 2 + 16
        segs = []
        for n in seg_lens:
            segs.append(plain[off : off + n])
            off += n
        if off != len(plain):
            raise FrameError("secure frame length mismatch")
        _count(reader, "frames_in")
        return tag, segs
    pre = await reader.readexactly(22)
    head = pre[:18]
    if crc32c(head) != struct.unpack_from("<I", pre, 18)[0]:
        raise FrameError("preamble crc mismatch")
    tag, nseg = head[0], head[1]
    if not 0 < nseg <= MAX_SEGMENTS:
        raise FrameError(f"bad segment count {nseg}")
    seg_lens = struct.unpack_from("<4I", head, 2)[:nseg]
    total = sum(seg_lens)
    if total > MAX_FRAME_LEN:
        raise FrameError("frame too large")
    # segments and epilogue (one crc per segment) arrive as one read
    body = await _read_body(reader, total + 4 * nseg)
    crcs = struct.unpack_from(f"<{nseg}I", body, total)
    segs = []
    off = 0
    for n, c in zip(seg_lens, crcs):
        seg = body[off : off + n]
        off += n
        if crc32c(seg) != c:
            raise FrameError("segment crc mismatch")
        segs.append(seg)
    _count(reader, "frames_in")
    return tag, segs
