"""The data segment: a message's payload blob rides the frame's third
segment from the caller's buffer to a view of the receiver's frame,
uncopied; everything without a blob keeps its two segments and the
bytes it always had."""

import asyncio

import numpy as np
import pytest

from ceph_tpu.msg import frames
from ceph_tpu.msg.auth import AuthContext, make_secret
from ceph_tpu.msg.denc import (
    BLOB_COPY_FLOOR,
    DETACHED,
    Decoder,
    Encoder,
    EncodingError,
)
from ceph_tpu.msg.messages import (
    OP_DELETE,
    OP_READ,
    OP_SETXATTR,
    OP_WRITE,
    OP_WRITE_FULL,
    MMonCommandAck,
    MOSDECSubOpReadReply,
    MOSDECSubOpWrite,
    MOSDECSubOpWriteReply,
    MOSDOp,
    MOSDOpReply,
    MOSDPGPush,
    MOSDPing,
    MOSDRepOp,
    OSDOp,
)
from ceph_tpu.msg.messenger import Messenger, decode_message, encode_message
from ceph_tpu.osd.pglog import eversion_t
from ceph_tpu.osd.types import pg_t
from tests.test_msg import (
    _Wire,
    _frame_stream_pair,
    _payload,
    _table_frame,
    run,
)

PG = pg_t(3, 7)
SIZE = 256 << 10        # over BLOB_COPY_FLOOR: a copy would be counted

# class -> (build a message around a blob, the blobs of a decoded one)
CARRIERS = {
    "MOSDOp": (
        lambda b: MOSDOp(tid=1, pool=3, oid="o", op=OP_WRITE_FULL, data=b),
        lambda m: [m.ops[0].data, m.data]),
    "MOSDOpReply": (
        lambda b: MOSDOpReply(tid=1, data=b, size=len(b),
                              outs=[(0, b, {"k": b"v"})]),
        lambda m: [m.data, m.outs[0][1]]),
    "MOSDECSubOpWrite": (
        lambda b: MOSDECSubOpWrite(tid=1, pg=PG, shard=2, from_osd=1,
                                   oid="o", data=b, attrs={"_v": b"1"},
                                   version=eversion_t(4, 9)),
        lambda m: [m.data]),
    "MOSDECSubOpReadReply": (
        lambda b: MOSDECSubOpReadReply(tid=1, pg=PG, shard=2, data=b,
                                       attrs={"_v": b"1"}),
        lambda m: [m.data]),
    "MOSDRepOp": (
        lambda b: MOSDRepOp(tid=1, pg=PG, from_osd=1, oid="o",
                            ops=[OSDOp(OP_WRITE_FULL, data=b)]),
        lambda m: [m.ops[0].data]),
    "MOSDRepOp.full_object": (
        lambda b: MOSDRepOp(tid=1, pg=PG, from_osd=1, oid="o", data=b),
        lambda m: [m.data]),
    "MOSDPGPush": (
        lambda b: MOSDPGPush(pg=PG, shard=2, from_osd=1, tid=1,
                             pushes=[("o", b, {"_v": b"1"})]),
        lambda m: [m.pushes[0][1]]),
}

# the blob as a caller may hold it
KINDS = {
    "bytes": lambda raw: raw,
    "bytearray": bytearray,
    "memoryview": lambda raw: memoryview(bytearray(raw))[:],
    "numpy_row": lambda raw: np.frombuffer(
        raw + raw, np.uint8).reshape(2, -1)[1],
}


async def _pair(mode: str):
    """(server, client connection, client, queue of what the server
    got) over loopback in ``mode``: crc, secure or compressed."""
    got = asyncio.Queue()
    kw_s, kw_c = {}, {}
    if mode == "secure":
        secret = make_secret()
        kw_s = {"auth": AuthContext("osd.0", service_secret=secret)}
        kw_c = {"auth": AuthContext("osd.1", service_secret=secret)}
    elif mode == "compressed":
        kw_s = {"compress_mode": "force"}
        kw_c = {"compress_mode": "force", "compress_min_size": 64}
    server = Messenger(("osd", 0), got.put, **kw_s)
    await server.bind()
    client = Messenger(("osd", 1), **kw_c)
    conn = await client.connect(*server.addr)
    assert (conn.crypto is not None) == (mode == "secure")
    assert (conn.compressor is not None) == (mode == "compressed")
    return server, conn, client, got


class TestRoundTrip:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("mode", ["crc", "secure", "compressed"])
    @pytest.mark.parametrize("carrier", CARRIERS)
    def test_blob_arrives_byte_equal_and_uncopied(self, carrier, mode, kind):
        build, blobs_of = CARRIERS[carrier]

        async def go():
            server, conn, client, got = await _pair(mode)
            raw = _payload(SIZE, seed=len(carrier))
            blob = KINDS[kind](raw)
            await conn.send_message(build(blob))
            msg = await asyncio.wait_for(got.get(), 20)
            for b in blobs_of(msg):
                assert b == raw
                assert len(b) == SIZE
                if mode != "compressed":    # decompression returns bytes
                    assert isinstance(b, memoryview)
            assert client.stats["data_segs_out"] == 1
            assert client.stats["data_bytes_out"] == SIZE
            assert server.stats["data_segs_in"] == 1
            assert server.stats["data_bytes_in"] == SIZE
            for m in (client, server):
                assert m.stats["blob_copied_bytes"] == 0
            # the sender's buffer is as it was
            assert bytes(blob) == raw
            await client.shutdown()
            await server.shutdown()

        run(go())

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("carrier", CARRIERS)
    def test_data_segment_is_the_callers_buffer(self, carrier, kind):
        raw = _payload(5000, seed=1)
        blob = KINDS[kind](raw)
        segs = encode_message(CARRIERS[carrier][0](blob), ("osd", 1), 1)
        assert len(segs) == 3
        assert len(segs[2]) == len(raw)
        assert segs[2] is blob
        # the payload segment holds the rest: no run of the blob in it
        assert raw[:64] not in bytes(segs[1])
        assert len(segs[1]) < 200

    def test_reply_data_and_its_out_travel_once(self):
        raw = _payload(SIZE)
        stats = dict.fromkeys(frames.STATS, 0)
        segs = encode_message(
            MOSDOpReply(tid=1, data=raw, outs=[(0, raw, {})]),
            ("osd", 1), 1, stats)
        assert stats["blob_copied_bytes"] == 0
        assert sum(len(s) for s in segs) < SIZE + 200
        msg = decode_message([memoryview(bytes(s)) for s in segs])
        assert msg.data is msg.outs[0][1]
        msg.own_blobs()
        assert type(msg.data) is bytes and msg.data == raw
        assert msg.outs[0][1] is msg.data

    def test_own_blobs_makes_every_view_bytes(self):
        a, b = _payload(100, 1), _payload(100, 2)
        segs = encode_message(
            MOSDOpReply(tid=1, outs=[(0, a, {}), (0, b, {}), (0, b"", {})]),
            ("osd", 1), 1)
        msg = decode_message([memoryview(bytes(s)) for s in segs])
        assert isinstance(msg.outs[0][1], memoryview)   # the first blob
        msg.own_blobs()
        assert [type(d) for _r, d, _kv in msg.outs] == [bytes] * 3
        assert [d for _r, d, _kv in msg.outs] == [a, b, b""]
        assert msg.data == b""

    def test_resend_of_a_kept_message_encodes_the_same(self):
        m = MOSDOp(tid=1, pool=3, oid="o", op=OP_WRITE_FULL,
                   data=_payload(9000))
        one = encode_message(m, ("client", 1), 5)
        two = encode_message(m, ("client", 1), 5)
        assert [bytes(s) for s in one] == [bytes(s) for s in two]
        assert two[2] is m.ops[0].data


# what the parent commit encodes for messages with no blob, recorded
# from its tree: (head segment, payload segment) in hex, sent as
# ("osd", 1) with seq 77
BLOBLESS = {
    "MOSDOp.read": ("2a000000030000006f736401000000000000004d0000000000000000",
        "09000000000000000300000000000000030000006f626a010000000104000000000000006400000000000000000000000000000000000000000000000c00000005000000632e313a39000000000000000000000000feffffffffffffff00000000"),
    "MOSDOp.empty_write_full": ("2a000000030000006f736401000000000000004d0000000000000000",
        "09000000000000000300000000000000030000006f626a010000000200000000000000000000000000000000000000000000000000000000000000000c00000000000000000000000000000000000000feffffffffffffff00000000"),
    "MOSDOp.setxattr": ("2a000000030000006f736401000000000000004d0000000000000000",
        "09000000000000000300000000000000030000006f626a010000000a00000000000000000000000000000000010000006b0500000076616c756500000000000000000c00000000000000000000000000000000000000feffffffffffffff00000000"),
    "MOSDOpReply.ack": ("2b000000030000006f736401000000000000004d0000000000000000",
        "090000000000000000000000000000000c000000000000000000000000000000"),
    "MOSDECSubOpWrite.delete": ("6c000000030000006f736401000000000000004d0000000000000000",
        "05000000000000000300000000000000070000000200000001000000030000006f626a00000000000000000000000001000000020000005f7603000000312e320c000000ffffffffffffffff010c00000028000000000000000000000000000000000000000000000005000000632e313a3900000000000000000000000000000000000000000000000000"),
    "MOSDECSubOpWriteReply": ("6d000000030000006f736401000000000000004d0000000000000000",
        "05000000000000000300000000000000070000000200000004000000000000000c00000000"),
    "MOSDECSubOpReadReply.enoent": ("6f000000030000006f736401000000000000004d0000000000000000",
        "05000000000000000300000000000000070000000200000004000000feffffff00000000000000000c000000"),
    "MOSDRepOp.delete": ("70000000030000006f736401000000000000004d0000000000000000",
        "0500000000000000030000000000000007000000ffffffff01000000030000006f626a0000000001000000020000005f7603000000312e32010c0000000c00000028000000000000000100000003000000000000000000000000000000000000000000000000000000000000000005000000632e313a39"),
    "MOSDPGPush.empty": ("69000000030000006f736401000000000000004d0000000000000000",
        "03000000000000000700000002000000010000000c00000001000000030000006f626a0000000001000000020000005f7603000000312e32000500000000000000"),
    "MOSDPing": ("46000000030000006f736401000000000000004d0000000000000000",
        "0100000000000000000000000000000000"),
}

BLOBLESS_MSGS = {
    "MOSDOp.read": lambda: MOSDOp(
        tid=9, pool=3, oid="obj", op=OP_READ, off=4, length=100, epoch=12,
        reqid="c.1:9"),
    "MOSDOp.empty_write_full": lambda: MOSDOp(
        tid=9, pool=3, oid="obj", op=OP_WRITE_FULL, data=b"", epoch=12),
    "MOSDOp.setxattr": lambda: MOSDOp(
        tid=9, pool=3, oid="obj",
        ops=[OSDOp(OP_SETXATTR, name="k", data=b"value")], epoch=12),
    "MOSDOpReply.ack": lambda: MOSDOpReply(tid=9, result=0, epoch=12),
    "MOSDECSubOpWrite.delete": lambda: MOSDECSubOpWrite(
        tid=5, pg=PG, shard=2, from_osd=1, oid="obj", data=b"",
        attrs={"_v": b"1.2"}, epoch=12, delete=True,
        version=eversion_t(12, 40), reqid="c.1:9"),
    "MOSDECSubOpWriteReply": lambda: MOSDECSubOpWriteReply(
        tid=5, pg=PG, shard=2, from_osd=4, result=0, epoch=12),
    "MOSDECSubOpReadReply.enoent": lambda: MOSDECSubOpReadReply(
        tid=5, pg=PG, shard=2, from_osd=4, result=-2, epoch=12),
    "MOSDRepOp.delete": lambda: MOSDRepOp(
        tid=5, pg=PG, from_osd=1, oid="obj", attrs={"_v": b"1.2"},
        delete=True, epoch=12, version=eversion_t(12, 40),
        ops=[OSDOp(OP_DELETE)], reqid="c.1:9"),
    "MOSDPGPush.empty": lambda: MOSDPGPush(
        pg=PG, shard=2, from_osd=1, pushes=[("obj", b"", {"_v": b"1.2"})],
        epoch=12, tid=5),
    "MOSDPing": lambda: MOSDPing(),
}


class TestTwoSegmentsStay:
    @pytest.mark.parametrize("name", BLOBLESS)
    def test_no_blob_keeps_two_segments_and_the_parents_bytes(self, name):
        stats = dict.fromkeys(frames.STATS, 0)
        segs = encode_message(BLOBLESS_MSGS[name](), ("osd", 1), 77, stats)
        assert [bytes(s).hex() for s in segs] == list(BLOBLESS[name])
        assert stats["data_segs_out"] == 0
        back = decode_message(segs, stats)
        assert type(back) is type(BLOBLESS_MSGS[name]())
        assert stats["data_segs_in"] == 0

    def test_inline_blob_decodes_as_bytes(self):
        m = decode_message(encode_message(
            MOSDOp(tid=1, pool=3, oid="o",
                   ops=[OSDOp(OP_SETXATTR, name="k", data=b"value")]),
            ("client", 1), 1))
        assert type(m.ops[0].data) is bytes


class TestDataSegmentOnTheWire:
    def test_flipped_byte_in_the_data_segment_fails_the_crc(self):
        async def go():
            stream, _, writer, server = await _frame_stream_pair()
            segs = encode_message(
                MOSDECSubOpWrite(tid=1, pg=PG, shard=2, oid="o",
                                 data=_payload(300_000)), ("osd", 1), 1)
            assert len(segs) == 3
            wire = bytearray(_table_frame(frames.Tag.MESSAGE, segs))
            wire[22 + len(segs[0]) + len(segs[1]) + 123_456] ^= 0x01
            writer.write(wire)
            await writer.drain()
            with pytest.raises(frames.FrameError,
                               match="segment crc mismatch"):
                await asyncio.wait_for(frames.read_frame(stream), 10)
            writer.close()
            stream.close()
            server.close()

        run(go())

    def test_crc_mode_covers_the_data_segment(self):
        """What write_frame puts on the wire for three segments is the
        format the table crc describes: a crc of the data segment too."""
        async def go():
            segs = encode_message(
                MOSDECSubOpWrite(tid=1, pg=PG, shard=2, oid="o",
                                 data=np.frombuffer(_payload(70_000),
                                                    np.uint8)),
                ("osd", 1), 1)
            wire = _Wire()
            await frames.write_frame(wire, frames.Tag.MESSAGE, segs)
            assert bytes(wire.sent) == _table_frame(
                frames.Tag.MESSAGE, [bytes(s) for s in segs])

        run(go())

    def test_back_to_back_frames_keep_distinct_blobs(self):
        """Each frame is received into a buffer of its own, so a
        message's view stays what it was when the next frame lands."""
        async def go():
            stream, _, writer, server = await _frame_stream_pair()
            raws = [_payload(90_000, seed=i) for i in range(3)]
            writer.write(b"".join(
                _table_frame(frames.Tag.MESSAGE, encode_message(
                    MOSDECSubOpWrite(tid=i, pg=PG, shard=i, oid="o", data=r),
                    ("osd", 1), i + 1))
                for i, r in enumerate(raws)))
            await writer.drain()
            msgs = []
            for _ in raws:
                tag, segs = await asyncio.wait_for(
                    frames.read_frame(stream), 10)
                assert tag == frames.Tag.MESSAGE and len(segs) == 3
                msgs.append(decode_message(segs))
            for i, (m, r) in enumerate(zip(msgs, raws)):
                assert m.tid == i and isinstance(m.data, memoryview)
                assert m.data == r
            assert len({id(m.data.obj) for m in msgs}) == 3
            writer.close()
            stream.close()
            server.close()

        run(go())

    def test_two_data_ops_decode_both(self):
        a, b = _payload(SIZE, 1), _payload(SIZE, 2)
        stats = dict.fromkeys(frames.STATS, 0)
        segs = encode_message(
            MOSDOp(tid=1, pool=3, oid="o", ops=[
                OSDOp(OP_SETXATTR, name="k", data=b"small"),
                OSDOp(OP_WRITE_FULL, data=a),
                OSDOp(OP_WRITE, off=SIZE, data=b)]),
            ("client", 1), 1, stats)
        assert len(segs) == 3 and segs[2] is a
        # one blob per message: the second data op stays inline, copied
        assert stats["blob_copied_bytes"] == SIZE
        m = decode_message([memoryview(bytes(s)) for s in segs], stats)
        assert [o.data for o in m.ops] == [b"small", a, b]
        assert isinstance(m.ops[1].data, memoryview)
        assert type(m.ops[0].data) is type(m.ops[2].data) is bytes
        assert stats["blob_copied_bytes"] == 2 * SIZE
        assert stats["data_bytes_out"] == stats["data_bytes_in"] == SIZE

    def test_large_blob_of_another_type_is_counted_as_copied(self):
        n = BLOB_COPY_FLOOR
        stats = dict.fromkeys(frames.STATS, 0)
        segs = encode_message(
            MMonCommandAck(tid=1, data=b"x" * n), ("mon", 0), 1, stats)
        assert len(segs) == 2 and stats["blob_copied_bytes"] == n
        decode_message(segs, stats)
        assert stats["blob_copied_bytes"] == 2 * n
        small = dict.fromkeys(frames.STATS, 0)
        decode_message(encode_message(
            MMonCommandAck(tid=1, data=b"x" * (n - 1)), ("mon", 0), 1, small),
            small)
        assert small["blob_copied_bytes"] == 0


class TestDencBlob:
    def test_first_blob_is_detached_the_next_inline(self):
        enc = Encoder()
        a, b = b"first", b"second"
        enc.blob(b"")
        enc.blob(a)
        enc.blob(b)
        enc.blob(a)
        assert enc.data is a
        view = memoryview(a)
        dec = Decoder(enc.bytes(), blob=view)
        assert dec.blob() == b""
        assert dec.blob() is view
        assert dec.blob() == b
        assert dec.blob() is view
        assert dec.remaining() == 0

    def test_marker_is_no_length(self):
        assert DETACHED > frames.MAX_FRAME_LEN

    def test_marker_without_a_data_segment_is_an_error(self):
        enc = Encoder()
        enc.blob(b"payload")
        with pytest.raises(EncodingError, match="no data segment"):
            Decoder(enc.bytes()).blob()
