"""Transport tests: denc round-trips, frame integrity, messenger
dispatch, map encoding (reference test analogues: test_denc.cc,
msgr tests in src/test/msgr/)."""

import asyncio

import numpy as np
import pytest

from ceph_tpu.crush import builder as B
from ceph_tpu.crush.types import ChooseArg, CrushMap
from ceph_tpu.msg import frames
from ceph_tpu.msg.denc import Decoder, Encoder, EncodingError
from ceph_tpu.msg.messages import (
    MOSDECSubOpWrite,
    MOSDMap,
    MOSDOp,
    MOSDOpReply,
    OP_WRITE_FULL,
)
from ceph_tpu.msg.messenger import Messenger, decode_message, encode_message
from ceph_tpu.osd.mapenc import decode_osdmap, encode_osdmap
from ceph_tpu.osd.osdmap import OSDMap
from ceph_tpu.osd.types import PgPool, PoolType, pg_t


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


class TestDenc:
    def test_scalar_roundtrip(self):
        enc = Encoder()
        enc.u8(7); enc.u16(300); enc.u32(70000); enc.u64(1 << 40)
        enc.i32(-5); enc.i64(-(1 << 40)); enc.bool_(True)
        enc.bytes_(b"abc"); enc.str_("héllo")
        dec = Decoder(enc.bytes())
        assert dec.u8() == 7
        assert dec.u16() == 300
        assert dec.u32() == 70000
        assert dec.u64() == 1 << 40
        assert dec.i32() == -5
        assert dec.i64() == -(1 << 40)
        assert dec.bool_() is True
        assert dec.bytes_() == b"abc"
        assert dec.str_() == "héllo"
        assert dec.remaining() == 0

    def test_versioned_skips_unknown_tail(self):
        """A v2 encoder adds a field; a v1 decoder must skip it."""
        enc = Encoder()
        with enc.versioned(2, 1):
            enc.u32(42)
            enc.str_("new-field-from-v2")
        enc.u32(99)  # data after the struct
        dec = Decoder(enc.bytes())
        with dec.versioned() as v:
            assert v == 2
            assert dec.u32() == 42
            # v1 decoder stops reading here
        assert dec.u32() == 99

    def test_underrun_raises(self):
        with pytest.raises(EncodingError):
            Decoder(b"\x01").u32()


class TestFrames:
    def test_frame_roundtrip(self):
        async def go():
            server_got = []

            async def handle(reader, writer):
                tag, segs = await frames.read_frame(reader)
                server_got.append((tag, segs))
                await frames.write_frame(writer, frames.Tag.ACK, [b"ok"])

            server = await asyncio.start_server(handle, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            await frames.write_frame(
                writer, frames.Tag.MESSAGE, [b"head", b"payload" * 100]
            )
            tag, segs = await frames.read_frame(reader)
            assert (tag, segs) == (frames.Tag.ACK, [b"ok"])
            assert server_got == [
                (frames.Tag.MESSAGE, [b"head", b"payload" * 100])
            ]
            writer.close()
            server.close()

        run(go())

    def test_corrupt_segment_detected(self):
        async def go():
            async def handle(reader, writer):
                data = await reader.read(10000)
                data = bytearray(data)
                data[-5] ^= 0xFF  # flip a payload byte
                writer.write(data)
                await writer.drain()

            server = await asyncio.start_server(handle, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            await frames.write_frame(writer, frames.Tag.MESSAGE, [b"payload"])
            with pytest.raises(frames.FrameError):
                await frames.read_frame(reader)
            writer.close()
            server.close()

        run(go())


class TestMessages:
    def test_mosdop_roundtrip(self):
        m = MOSDOp(
            tid=9, pool=3, oid="foo", op=OP_WRITE_FULL,
            data=b"\x00\x01" * 50, epoch=12,
        )
        segs = encode_message(m, ("client", 4), 1)
        m2 = decode_message(segs)
        assert isinstance(m2, MOSDOp)
        assert (m2.tid, m2.pool, m2.oid, m2.op, m2.data, m2.epoch) == (
            9, 3, "foo", OP_WRITE_FULL, b"\x00\x01" * 50, 12,
        )
        assert m2.src == ("client", 4)

    def test_ec_subop_roundtrip(self):
        m = MOSDECSubOpWrite(
            tid=5, pg=pg_t(2, 7), shard=3, from_osd=1, oid="o",
            off=64, data=b"chunk", attrs={"hinfo": b"\x01"}, epoch=4,
        )
        m2 = decode_message(encode_message(m, ("osd", 1), 2))
        assert (m2.pg, m2.shard, m2.off, m2.data, m2.attrs) == (
            pg_t(2, 7), 3, 64, b"chunk", {"hinfo": b"\x01"},
        )


class TestMapEncoding:
    def test_osdmap_roundtrip(self):
        m = CrushMap()
        root = B.build_hierarchy(m, osds_per_host=2, n_hosts=4)
        rid = B.add_simple_rule(m, root.id, 1, mode="indep", rule_type=3)
        m.choose_args[root.id] = ChooseArg(
            root.id, weight_set=[[0x10000] * root.size]
        )
        om = OSDMap(crush=m, epoch=5)
        for o in range(8):
            om.new_osd(o)
        om.mark_down(3)
        om.set_primary_affinity(1, 0x8000)
        om.pools[1] = PgPool(
            id=1, type=PoolType.ERASURE, size=3, min_size=2,
            crush_rule=rid, pg_num=8, pgp_num=8,
            erasure_code_profile="myprofile",
        )
        om.erasure_code_profiles["myprofile"] = {
            "plugin": "jax", "k": "2", "m": "1",
        }
        om.pg_upmap[pg_t(1, 2)] = [0, 2, 4]
        om.pg_upmap_items[pg_t(1, 3)] = [(1, 5)]
        om.pg_temp[pg_t(1, 4)] = [2, 4, 6]
        om.primary_temp[pg_t(1, 5)] = 6
        om.osd_addrs[0] = ("127.0.0.1", 6800)

        # NON-uniform balancer overrides on the OSDMap itself: these
        # drive placement and must survive the wire (straw2 is
        # scale-invariant, so only a non-uniform set catches bugs)
        om.choose_args = {
            root.id: ChooseArg(root.id, weight_set=[[0x8000, 0x10000, 0x18000, 0x20000]])
        }
        om2 = decode_osdmap(encode_osdmap(om))
        assert om2.choose_args == om.choose_args
        assert om2.epoch == 5
        assert om2.osd_state == om.osd_state
        assert om2.osd_weight == om.osd_weight
        assert om2.osd_primary_affinity == om.osd_primary_affinity
        assert om2.pools[1] == om.pools[1]
        assert om2.pg_upmap == om.pg_upmap
        assert om2.pg_upmap_items == om.pg_upmap_items
        assert om2.pg_temp == om.pg_temp
        assert om2.primary_temp == om.primary_temp
        assert om2.erasure_code_profiles == om.erasure_code_profiles
        assert om2.osd_addrs == om.osd_addrs
        # placement must be identical through the round-trip
        for ps in range(8):
            assert om2.pg_to_up_acting_osds(
                pg_t(1, ps)
            ) == om.pg_to_up_acting_osds(pg_t(1, ps))


class TestMessenger:
    def test_hello_and_dispatch(self):
        async def go():
            got = asyncio.Queue()

            async def dispatch(msg):
                await got.put(msg)

            server = Messenger(("osd", 0), dispatch)
            await server.bind()
            client = Messenger(("client", 99))
            conn = await client.connect(*server.addr)
            assert conn.peer == ("osd", 0)
            await conn.send_message(MOSDOpReply(tid=1, result=0, data=b"x"))
            msg = await asyncio.wait_for(got.get(), 5)
            assert isinstance(msg, MOSDOpReply)
            assert msg.src == ("client", 99)
            # server learned the client's identity
            assert server.get_connection(("client", 99)) is not None
            # reply over the server->client direction of the same conn
            await server.get_connection(("client", 99)).send_message(
                MOSDMap(maps={1: b"mapbytes"})
            )
            back = asyncio.Queue()
            client.dispatcher = lambda m: back.put(m)
            msg2 = await asyncio.wait_for(back.get(), 5)
            assert isinstance(msg2, MOSDMap)
            assert msg2.maps == {1: b"mapbytes"}
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_reset_callback_on_peer_close(self):
        async def go():
            resets = []

            async def on_reset(conn):
                resets.append(conn.peer)

            server = Messenger(("mon", 0), on_reset=on_reset)
            await server.bind()
            client = Messenger(("osd", 2))
            conn = await client.connect(*server.addr)
            await asyncio.sleep(0.05)
            await conn.close()
            await asyncio.sleep(0.1)
            assert resets == [("osd", 2)]
            await client.shutdown()
            await server.shutdown()

        run(go())


class TestOnWireCompression:
    """msgr2 on-wire compression negotiation + compressed message
    round-trip (reference src/msg/async/compression_onwire.cc,
    compressor_registry.cc)."""

    def test_negotiated_roundtrip(self):
        import asyncio

        from ceph_tpu.msg.frames import Tag
        from ceph_tpu.msg.messages import MOSDOp
        from ceph_tpu.msg.messenger import Messenger

        async def go():
            got = asyncio.get_running_loop().create_future()

            async def on_msg(msg):
                if not got.done():
                    got.set_result(msg)

            srv = Messenger(("osd", 1), on_msg, compress_mode="force")
            await srv.bind("127.0.0.1", 0)
            cli = Messenger(("client", 2), compress_mode="force",
                            compress_min_size=64)
            conn = await cli.connect(*srv.addr)
            assert conn.compressor is not None, "negotiation failed"
            assert conn.compressor.name == "zlib"
            big = MOSDOp(tid=7, pool=1, oid="o", op=2,
                         data=b"compress me " * 500)
            await conn.send_message(big)
            msg = await asyncio.wait_for(got, 10)
            assert isinstance(msg, MOSDOp)
            assert msg.data == b"compress me " * 500
            # the server side negotiated too: its reply would compress
            assert msg.conn.compressor is not None
            # a tiny message stays below the threshold: still delivered
            got2 = asyncio.get_running_loop().create_future()
            srv.dispatcher = lambda m: _set(got2, m)
            await conn.send_message(MOSDOp(tid=8, pool=1, oid="o", op=2,
                                           data=b"sm"))
            msg2 = await asyncio.wait_for(got2, 10)
            assert msg2.data == b"sm"
            await cli.shutdown()
            await srv.shutdown()

        async def _set(fut, m):
            if not fut.done():
                fut.set_result(m)

        asyncio.run(go())

    def test_none_peer_refuses_negotiation(self):
        """'none = never': a mode-none acceptor answers the request
        with an empty pick and both sides stay uncompressed."""
        import asyncio

        from ceph_tpu.msg.messages import MOSDOp
        from ceph_tpu.msg.messenger import Messenger

        async def go():
            got = asyncio.get_running_loop().create_future()

            async def on_msg(msg):
                if not got.done():
                    got.set_result(msg)

            srv = Messenger(("osd", 1), on_msg)  # compress_mode=none
            await srv.bind("127.0.0.1", 0)
            cli = Messenger(("client", 9), compress_mode="force",
                            compress_min_size=64)
            conn = await cli.connect(*srv.addr)
            assert conn.compressor is None
            await conn.send_message(MOSDOp(tid=1, pool=1, oid="o", op=2,
                                           data=b"plain " * 100))
            msg = await asyncio.wait_for(got, 10)
            assert msg.data == b"plain " * 100
            await cli.shutdown()
            await srv.shutdown()

        asyncio.run(go())

    def test_no_negotiation_stays_plain(self):
        import asyncio

        from ceph_tpu.msg.messages import MOSDOp
        from ceph_tpu.msg.messenger import Messenger

        async def go():
            got = asyncio.get_running_loop().create_future()

            async def on_msg(msg):
                if not got.done():
                    got.set_result(msg)

            srv = Messenger(("osd", 1), on_msg)
            await srv.bind("127.0.0.1", 0)
            cli = Messenger(("client", 3))  # compress_mode=none
            conn = await cli.connect(*srv.addr)
            assert conn.compressor is None
            await conn.send_message(MOSDOp(tid=1, pool=1, oid="x", op=2,
                                           data=b"plain " * 400))
            msg = await asyncio.wait_for(got, 10)
            assert msg.data == b"plain " * 400
            await cli.shutdown()
            await srv.shutdown()

        asyncio.run(go())


# -- the frame path: receive in place, send without re-copying ---------

# what the parent's write_frame (slice-by-8 crc) put on the wire for
# RECORDED_SEGS under Tag.MESSAGE: preamble and epilogue as recorded,
# the segments between them untouched
RECORDED_SEGS = [b"head\x00\x01", bytes(range(256)) * 3 + b"tail", b""]
RECORDED_WIRE = (
    bytes.fromhex("11030600000004030000000000000000000035894771")
    + b"".join(RECORDED_SEGS)
    + bytes.fromhex("e965dc5c7e724c88ffffffff")
)


def _payload(n: int, seed: int = 0) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


class _Wire:
    """A writer that keeps what it is given (write_frame's output)."""

    def __init__(self):
        self.sent = bytearray()

    def write(self, data):
        self.sent += data

    def writelines(self, bufs):
        for b in bufs:
            self.sent += b

    async def drain(self):
        pass


def _table_frame(tag: int, segs: list[bytes]) -> bytes:
    """The wire format, built apart from frames.py with the table crc."""
    import struct

    from ceph_tpu import native

    head = struct.pack("<BB4I", tag, len(segs),
                       *[len(s) for s in segs], *([0] * (4 - len(segs))))
    return (head + struct.pack("<I", native.crc32c(head, table=True))
            + b"".join(segs)
            + b"".join(struct.pack("<I", native.crc32c(s, table=True))
                       for s in segs))


async def _frame_stream_pair():
    """(acceptor-side FrameStream, plain asyncio (reader, writer) of the
    dialer, server)."""
    loop = asyncio.get_running_loop()
    accepted = loop.create_future()

    async def on_connect(stream):
        accepted.set_result(stream)

    server = await loop.create_server(
        lambda: frames.FrameStream(on_connect=on_connect), "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    return await accepted, reader, writer, server


async def _raw_dial(server: Messenger, entity=("client", 7)):
    """Banner + HELLO with a Messenger by hand, over plain asyncio
    streams: returns (reader, writer) ready for MESSAGE frames."""
    reader, writer = await asyncio.open_connection(*server.addr)
    await frames.recv_banner(reader)
    await frames.send_banner(writer)
    enc = Encoder()
    enc.str_(entity[0])
    enc.i64(entity[1])
    await frames.write_frame(writer, frames.Tag.HELLO, [enc.bytes()])
    tag, _ = await frames.read_frame(reader)
    assert tag == frames.Tag.HELLO
    return reader, writer


async def _until(cond, timeout=10.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, "timed out"
        await asyncio.sleep(0.01)


class TestFramePath:
    @pytest.mark.parametrize("size", [4 << 20, 512 << 10])
    def test_large_payload_in_place(self, size):
        """A large payload arrives byte-equal, and its frame resumes the
        reader at most twice (preamble, body) however many recvs the
        body took."""
        async def go():
            got = asyncio.Queue()
            server = Messenger(("osd", 0), got.put)
            await server.bind()
            client = Messenger(("client", 1))
            conn = await client.connect(*server.addr)
            await asyncio.sleep(0.05)       # handshake frames are done
            before = dict(server.stats)
            data = _payload(size)
            await conn.send_message(
                MOSDOp(tid=1, pool=1, oid="o", op=OP_WRITE_FULL, data=data))
            msg = await asyncio.wait_for(got.get(), 20)
            assert msg.data == data
            d = {k: server.stats[k] - before[k] for k in before}
            assert d["frames_in"] == 1
            assert d["bytes_in"] > size
            assert d["reader_wakeups"] <= 2, d
            assert d["recv_calls"] >= 1
            assert client.stats["frames_out"] >= 2     # HELLO + message
            assert client.stats["bytes_out"] > size
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_burst_of_small_messages(self):
        async def go():
            seen = []

            async def dispatch(msg):
                seen.append(msg.tid)

            server = Messenger(("osd", 0), dispatch)
            await server.bind()
            client = Messenger(("client", 1))
            conn = await client.connect(*server.addr)
            await asyncio.sleep(0.05)
            before = dict(server.stats)
            msgs = [MOSDOpReply(tid=i, result=0, data=b"x" * (i % 50))
                    for i in range(1000)]
            await conn.send_messages(msgs[:500])
            for m in msgs[500:]:
                await conn.send_message(m)
            await _until(lambda: len(seen) == 1000)
            assert seen == list(range(1000))
            d = {k: server.stats[k] - before[k] for k in before}
            assert d["frames_in"] == 1000
            assert d["reader_wakeups"] <= d["frames_in"], d
            assert d["recv_calls"] <= 2 * d["frames_in"], d
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_flipped_byte_is_a_crc_mismatch(self):
        async def go():
            stream, _, writer, server = await _frame_stream_pair()
            wire = bytearray(_table_frame(
                frames.Tag.MESSAGE, [b"head", _payload(300_000)]))
            wire[22 + 4 + 123_456] ^= 0x01
            writer.write(wire)
            await writer.drain()
            with pytest.raises(frames.FrameError,
                               match="segment crc mismatch"):
                await asyncio.wait_for(frames.read_frame(stream), 10)
            writer.close()
            stream.close()
            server.close()

        run(go())

    def test_flipped_byte_resets_the_connection(self):
        async def go():
            resets, got = [], []

            async def on_reset(conn):
                resets.append(conn.peer)

            async def dispatch(msg):
                got.append(msg)

            server = Messenger(("osd", 0), dispatch, on_reset=on_reset)
            await server.bind()
            reader, writer = await _raw_dial(server)
            segs = encode_message(
                MOSDOp(tid=1, pool=1, oid="o", op=OP_WRITE_FULL,
                       data=_payload(100_000)), ("client", 7), 1)
            wire = bytearray(_table_frame(frames.Tag.MESSAGE, segs))
            wire[22 + len(segs[0]) + 5000] ^= 0x80
            writer.write(wire)
            await writer.drain()
            await _until(lambda: resets == [("client", 7)])
            assert got == []
            assert await reader.read() == b""       # the server hung up
            assert server.get_connection(("client", 7)) is None
            writer.close()
            await server.shutdown()

        run(go())

    def test_eof_inside_a_segment_closes_with_notify(self):
        async def go():
            resets = []

            async def on_reset(conn):
                resets.append(conn.peer)

            server = Messenger(("osd", 0), on_reset=on_reset)
            await server.bind()
            _, writer = await _raw_dial(server)
            wire = _table_frame(frames.Tag.MESSAGE,
                                [b"head", _payload(1 << 20)])
            writer.write(wire[:600_000])
            await writer.drain()
            await asyncio.sleep(0.05)
            assert resets == []
            writer.close()
            await _until(lambda: resets == [("client", 7)])
            await server.shutdown()

        run(go())

    def test_eof_inside_a_segment_is_an_incomplete_read(self):
        async def go():
            stream, _, writer, server = await _frame_stream_pair()
            wire = _table_frame(frames.Tag.MESSAGE, [_payload(200_000)])
            writer.write(wire[:150_000])
            await writer.drain()
            writer.close()
            with pytest.raises(asyncio.IncompleteReadError) as e:
                await asyncio.wait_for(frames.read_frame(stream), 10)
            assert e.value.expected == 200_000 + 4
            assert len(e.value.partial) == 150_000 - 22
            stream.close()
            server.close()

        run(go())

    def test_oversized_frame_refused_before_allocation(self, monkeypatch):
        import struct

        from ceph_tpu import native

        async def go():
            stream, _, writer, server = await _frame_stream_pair()
            bodies = []
            real = frames._read_body

            async def read_body(reader, n):
                bodies.append(n)
                return await real(reader, n)

            monkeypatch.setattr(frames, "_read_body", read_body)
            head = struct.pack("<BB4I", frames.Tag.MESSAGE, 2,
                               frames.MAX_FRAME_LEN, 1, 0, 0)
            writer.write(head + struct.pack("<I", native.crc32c(head)))
            await writer.drain()
            with pytest.raises(frames.FrameError, match="frame too large"):
                await asyncio.wait_for(frames.read_frame(stream), 10)
            assert bodies == []
            writer.close()
            stream.close()
            server.close()

        run(go())

    def test_secure_mode_4MiB(self):
        from ceph_tpu.msg.auth import AuthContext, make_secret

        async def go():
            secret = make_secret()
            got = asyncio.Queue()
            server = Messenger(("osd", 0), got.put, auth=AuthContext(
                "osd.0", service_secret=secret))
            await server.bind()
            client = Messenger(("osd", 1), auth=AuthContext(
                "osd.1", service_secret=secret))
            conn = await client.connect(*server.addr)
            assert conn.crypto is not None
            data = _payload(4 << 20, seed=3)
            await conn.send_message(
                MOSDOp(tid=1, pool=1, oid="o", op=OP_WRITE_FULL, data=data))
            await conn.send_message(MOSDOpReply(tid=2, result=0, data=b"s"))
            msg = await asyncio.wait_for(got.get(), 30)
            assert msg.data == data
            assert msg.conn.crypto is not None
            assert (await asyncio.wait_for(got.get(), 30)).tid == 2
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_compressed_4MiB(self):
        async def go():
            got = asyncio.Queue()
            server = Messenger(("osd", 0), got.put, compress_mode="force")
            await server.bind()
            client = Messenger(("client", 1), compress_mode="force",
                               compress_min_size=64)
            conn = await client.connect(*server.addr)
            assert conn.compressor is not None
            data = _payload(4096, seed=4) * 1024
            before = client.stats["bytes_out"]
            await conn.send_message(
                MOSDOp(tid=1, pool=1, oid="o", op=OP_WRITE_FULL, data=data))
            msg = await asyncio.wait_for(got.get(), 30)
            assert msg.data == data
            assert client.stats["bytes_out"] - before < len(data)
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_back_to_back_frames_in_one_recv(self):
        """Several frames written at once land in the staging buffer
        together and are split without another socket read."""
        async def go():
            stream, _, writer, server = await _frame_stream_pair()
            sent = [(frames.Tag.MESSAGE, [b"h%d" % i, _payload(10 + 37 * i, i)])
                    for i in range(40)]
            sent.append((frames.Tag.KEEPALIVE2, [b"stamp"]))
            # one larger than the staging buffer, then small ones again
            sent.append((frames.Tag.MESSAGE, [b"big", _payload(200_000)]))
            sent += [(frames.Tag.ACK, [b"a", b"", b"c"])] * 3
            writer.write(b"".join(_table_frame(t, s) for t, s in sent))
            await writer.drain()
            for tag, segs in sent:
                got_tag, got = await asyncio.wait_for(
                    frames.read_frame(stream), 10)
                assert (got_tag, got) == (tag, segs)
            assert stream.stats["frames_in"] == len(sent)
            assert stream.stats["reader_wakeups"] < 10
            assert stream.stats["recv_calls"] < 20
            writer.close()
            stream.close()
            server.close()

        run(go())

    def test_wire_is_byte_identical_to_the_parents(self):
        async def go():
            # the new writer reproduces the recorded bytes ...
            wire = _Wire()
            await frames.write_frame(wire, frames.Tag.MESSAGE, RECORDED_SEGS)
            assert bytes(wire.sent) == RECORDED_WIRE
            assert RECORDED_WIRE == _table_frame(
                frames.Tag.MESSAGE, RECORDED_SEGS)
            # ... from any bytes-like segment, without changing them
            wire = _Wire()
            await frames.write_frame(
                wire, frames.Tag.MESSAGE,
                [bytearray(RECORDED_SEGS[0]), memoryview(RECORDED_SEGS[1]),
                 np.frombuffer(RECORDED_SEGS[2], dtype=np.uint8)])
            assert bytes(wire.sent) == RECORDED_WIRE
            big = [b"h" * 50, _payload(4 << 20, seed=9)]
            wire = _Wire()
            await frames.write_frame(wire, frames.Tag.MESSAGE, big)
            assert bytes(wire.sent) == _table_frame(frames.Tag.MESSAGE, big)
            # ... and the new reader reads what the parent wrote
            stream, _, writer, server = await _frame_stream_pair()
            writer.write(RECORDED_WIRE)
            await writer.drain()
            tag, segs = await asyncio.wait_for(frames.read_frame(stream), 10)
            assert (tag, segs) == (frames.Tag.MESSAGE, RECORDED_SEGS)
            assert all(isinstance(s, memoryview) for s in segs)
            writer.close()
            stream.close()
            server.close()

        run(go())

    @pytest.mark.parametrize("size", [100, 512 << 10])
    def test_read_frame_on_a_plain_stream_reader(self, size):
        async def go():
            reader = asyncio.StreamReader()
            segs = [b"head", _payload(size)]
            reader.feed_data(_table_frame(frames.Tag.MESSAGE, segs))
            reader.feed_data(RECORDED_WIRE)
            reader.feed_eof()
            assert await frames.read_frame(reader) == (
                frames.Tag.MESSAGE, segs)
            assert await frames.read_frame(reader) == (
                frames.Tag.MESSAGE, RECORDED_SEGS)
            with pytest.raises(asyncio.IncompleteReadError):
                await frames.read_frame(reader)

        run(go())

    def test_perf_dump_carries_the_wire_counters(self):
        from ceph_tpu import native

        async def go():
            m = Messenger(("osd", 0))
            dump = m.perf_dump()
            assert {f"msgr_{k}" for k in frames.STATS} <= set(dump)
            assert dump["crc_backend"] == native.crc_backend()

        run(go())
