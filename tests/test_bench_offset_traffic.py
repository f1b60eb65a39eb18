"""The benchmark generator's ``write`` loop, its model alone (tier-1;
issue 36's file, which PR 36 might not add): a block device's writes at
aligned offsets into prefilled objects against a fake ``io`` that keeps
bytes in a dict and completes ops in a shuffled order.  The cases are
``benchmarks/tests/test_offset_writes.py``'s (not tier-1), on the
traffic file the cell ``ec42_rbd_randwrite_4k`` runs; the loop on a
cluster, tiny, and its controls stay there.
"""

from __future__ import annotations

import asyncio
import copy
import json
import os
import random
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
MIX = os.path.join(BENCH, "traffic", "rbd_randwrite_4k.json")
SEED = (1 << 31) + 37


@pytest.fixture(scope="module")
def generator():
    sys.path.insert(0, BENCH)
    try:
        from harness import generator as mod
        yield mod
    finally:
        sys.path.remove(BENCH)


class FakeIo:
    """Objects in a dict; every op ends after a lag of its own, so ops
    complete in another order than they were sent in.  ``fail`` names
    the offset writes (by count) that raise after the lag, unapplied."""

    def __init__(self, fail=()):
        self.objects: dict[str, bytearray] = {}
        self.sent: list[tuple[str, int, bytes]] = []
        self.applied: list[tuple[str, int, bytes]] = []
        self.busy: set[tuple[str, int]] = set()
        self.most_busy = 0
        self.fail = set(fail)
        self._lag = random.Random(7)

    async def _wait(self) -> None:
        await asyncio.sleep(self._lag.random() * 2e-3)

    async def write_full(self, name: str, data: bytes) -> None:
        await self._wait()
        self.objects[name] = bytearray(data)

    async def write(self, name: str, data: bytes, off: int) -> None:
        assert (name, off) not in self.busy, "one block in flight twice"
        self.sent.append((name, off, bytes(data)))
        nth = len(self.sent)
        self.busy.add((name, off))
        self.most_busy = max(self.most_busy, len(self.busy))
        try:
            await self._wait()
            if nth in self.fail:
                raise TimeoutError(f"offset write {nth} timed out")
            assert off % len(data) == 0 and \
                off + len(data) <= len(self.objects[name])
            self.objects[name][off:off + len(data)] = data
            self.applied.append((name, off, bytes(data)))
        finally:
            self.busy.discard((name, off))

    async def read(self, name: str) -> bytes:
        await self._wait()
        return bytes(self.objects[name])


class FakeCluster:
    def __init__(self, **kw):
        self.io = FakeIo(**kw)


def _params(**over) -> dict:
    with open(MIX) as f:
        p = json.load(f)
    p.update(object_bytes=64 << 10, distinct_payloads=4, prefill_objects=8,
             in_flight=8, warmup_ops=8, verify_sample=8)
    p.update(over)
    return p


def _drive(generator, params: dict, seed: int = SEED, ops: int = 600, **kw):
    """Prefill, then the loop until ``ops`` ops have ended, then drain."""
    async def go():
        t = generator.Traffic(FakeCluster(**kw), params, seed)
        await t.prefill()
        await t.start_loop_and_warm_up()
        while t.ended < params["prefill_objects"] + ops:
            await asyncio.sleep(0.001)
        t.stop = True
        await t.drain()
        return t
    return asyncio.run(asyncio.wait_for(go(), 60))


def test_the_cells_traffic_file_is_the_rehearsals(generator):
    with open(MIX) as f:
        cell = json.load(f)
    with open(os.path.join(BENCH, "tests", "traffic",
                           "rbd_randwrite_4k.json")) as f:
        rehearsed = json.load(f)
    cell.pop("source"), rehearsed.pop("source")
    assert cell == rehearsed
    assert cell["loop"] == {"op": "write", "io_bytes": 4096,
                            "offsets": "uniform"}
    assert (cell["in_flight"], cell["prefill_objects"], cell["object_bytes"],
            cell["warmup_ops"], cell["verify_sample"], cell["op_timeout_s"],
            cell["trace"]) == (32, 256, 4 << 20, 64, 32, 30,
                               {"start_s": 12, "seconds": 6})
    t = generator.Traffic(FakeCluster(), cell, SEED)
    assert t.name(5) == "rbd_data.36benchimage.0000000000000005"


def test_expected_is_a_sequential_replay_of_the_acknowledged_ops(generator):
    t = _drive(generator, _params())
    io = t.c.io
    assert len(io.applied) >= 600 and not t.uncertain
    assert [a[:2] for a in io.applied] != [s[:2] for s in io.sent]  # shuffled
    replay = {n: bytearray(t.blobs[i]) for n, i in t.acked.items()}
    for name, off, data in io.applied:
        replay[name][off:off + len(data)] = data
    for name in t.readable:
        assert t.expected(name) == bytes(replay[name]) == \
            bytes(io.objects[name])
    assert all(t.patched[n] for n in t.readable)
    # op number c writes pool block c mod BLOCK_POOL
    assert [d for _n, _o, d in io.sent[:5]] == t.block_bytes[:5]
    assert len(t.block_bytes) == generator.BLOCK_POOL == \
        len(set(t.block_bytes))


def test_no_block_is_in_flight_twice_and_a_busy_block_is_drawn_again(
        generator):
    # 2 objects of 2 blocks under 3 in flight: most draws land on a block
    # in flight (``FakeIo.write`` asserts that none is sent all the same)
    t = _drive(generator, _params(object_bytes=8192, prefill_objects=2,
                                  in_flight=3, warmup_ops=3), ops=200)
    assert t.c.io.most_busy == 3 and t.redraws > 50
    assert not t._busy and not t.uncertain
    for name in t.readable:
        assert t.expected(name) == bytes(t.c.io.objects[name])


def test_the_order_comes_from_the_constant_and_the_bytes_from_the_seed(
        generator):
    one = _params(in_flight=1, warmup_ops=1)
    a = _drive(generator, one, SEED, ops=100)
    b = _drive(generator, one, SEED + 1, ops=100)
    assert [s[:2] for s in a.c.io.sent[:100]] == \
        [s[:2] for s in b.c.io.sent[:100]]
    assert all(x[2] != y[2] for x, y in zip(a.c.io.sent, b.c.io.sent))
    again = _drive(generator, one, SEED, ops=100)
    assert again.c.io.sent[:100] == a.c.io.sent[:100]
    order = np.random.default_rng(generator.WRITE_ORDER_SEED)
    assert [(a.readable[blk // 16], blk % 16 * 4096) for blk in
            (int(order.integers(8 * 16)) for _ in range(100))] == \
        [s[:2] for s in a.c.io.sent[:100]]


def test_verify_reads_back_every_object_with_a_patch_that_is_certain(
        generator):
    """One acknowledged write that never landed, in an object the sample
    may not draw, is a read back that differs; an ``uncertain`` object is
    not read at all."""
    t = _drive(generator, _params(prefill_objects=12), ops=300, fail={40})
    lost = t.c.io.sent[39][0]
    known = sorted(set(t.patched) - t.uncertain)
    assert t.uncertain == {lost} and len(known) == 11
    read = []
    real = t.c.io.read

    async def reading(name):
        read.append(name)
        return await real(name)

    t.c.io.read = reading
    assert asyncio.run(t._read_back(known)) == 11
    assert sorted(read) == known
    name, off, data = next(a for a in t.c.io.applied if a[0] != lost)
    block = t.c.io.objects[name][off:off + len(data)]
    t.c.io.objects[name][off:off + len(data)] = bytes(len(data))
    assert asyncio.run(t._read_back(known)) == 10
    t.c.io.objects[name][off:off + len(data)] = block

    async def no_answer(name):
        raise TimeoutError(name)

    t.c.io.read = no_answer
    assert asyncio.run(t._read_back(known[:3])) == 0


def test_a_failed_op_makes_its_object_uncertain_and_keeps_it_unsampled(
        generator):
    t = _drive(generator, _params(prefill_objects=12, verify_sample=12),
               ops=300, fail={40, 41, 90})
    lost = {t.c.io.sent[n - 1][0] for n in (40, 41, 90)}
    assert t.uncertain == lost and 1 <= len(lost) <= 3
    sample = t.sample()
    assert sample and not set(sample) & lost
    assert all(n in t.patched for n in sample)      # no object is unpatched
    assert len(sample) == min(6, 12 - len(lost))    # half of verify_sample
    for name, want in sample.items():
        assert want == bytes(t.c.io.objects[name])
    # every other object is still known, block for block
    for name in set(t.readable) - lost:
        assert t.expected(name) == bytes(t.c.io.objects[name])


def test_sample_takes_half_with_a_patch_and_half_without(generator):
    t = _drive(generator, _params(), ops=100)
    for i in range(6):                   # as ``touch_every_pg`` leaves them
        t.acked[f"untouched{i}"] = i % 4
    sample = t.sample()
    assert sum(n in t.patched for n in sample) == 4
    assert sum(n.startswith("untouched") for n in sample) == 4
    assert sample == t.sample()
    other = copy.copy(t)
    other.seed = SEED + 1
    assert list(other.sample()) != list(sample)


@pytest.mark.parametrize("bad", [
    {"prefill_objects": 0}, {"object_bytes": 6000}, {"offsets": "zipf:1.2"}],
    ids=["no_image", "part_blocks", "another_law"])
def test_a_write_loop_needs_an_image_of_whole_blocks(generator, bad):
    p = _params()
    if "offsets" in bad:        # ``uniform`` is the one law so far
        p["loop"] = {**p["loop"], **bad}
    else:
        p.update(bad)
    with pytest.raises(ValueError):
        generator.Traffic(FakeCluster(), p, SEED)
