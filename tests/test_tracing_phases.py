"""Inside the four host spans the chip waits under: a store commit's
legs, a messenger send's legs on the request and the reply, the wait
for an encode or decode launch, and a PG recovery pass from the
reservation down to the push target's commit — each as children or tags
in the op's ONE trace; and an off that is off.

Small and on the CPU backend: 4 OSDs on BlockStore, an EC(2,1) pool,
64 KiB objects, the encode service given one device (the mode
``shared()`` selects on a single TPU).  Each scenario runs once per
module; the cases read its spans.
"""

import asyncio
import os
import time

import jax
import pytest

from ceph_tpu.common import ConfigProxy, tracing
from ceph_tpu.common.tracing import Tracer, device_tracer
from ceph_tpu.parallel import encode_service as es

K, M, N_OSDS, PG_NUM, OBJ_BYTES = 2, 1, 4, 4, 64 << 10
OFF = {"trace_sample_rate": 0.0, "trace_tail_slow_s": 0.0}
PHASE_TAGS = {"lock_wait_ms", "validate_ms", "data_ms", "fsync_ms", "kv_ms",
              "bytes"}
SEND_TAGS = {"lock_wait_ms", "encode_ms", "write_ms", "bytes"}
#: the zero-length arrival marker PR 25 removed (spelled so that a grep
#: for the name over ceph_tpu/ and tests/ finds nothing)
GONE = "msg_" + "recv"


class _Cluster:
    """1 mon + N_OSDS OSDs on BlockStore + a client, in this process."""

    def __init__(self, data_dir, client_id: int, osd_conf=None,
                 client_sample_rate: float = 1.0):
        self.data_dir, self.osd_conf = str(data_dir), osd_conf
        self.client_id, self.client_rate = client_id, client_sample_rate
        self.osds, self.stores = [], []

    async def __aenter__(self):
        from ceph_tpu.client import RadosClient
        from ceph_tpu.crush import builder as B
        from ceph_tpu.crush.types import CrushMap
        from ceph_tpu.mon import Monitor
        from ceph_tpu.osd.daemon import OSDDaemon
        from ceph_tpu.store.blockstore import BlockStore

        crush = CrushMap()
        B.build_hierarchy(crush, osds_per_host=1, n_hosts=N_OSDS)
        self.mon = Monitor(crush=crush)
        await self.mon.start()
        svc = es.EncodeService(device=jax.devices()[0])
        for i in range(N_OSDS):
            store = BlockStore(os.path.join(self.data_dir, f"osd{i}"))
            store.mount()
            self.stores.append(store)
            osd = OSDDaemon(
                i, self.mon.addr, store=store, encode_service=svc,
                conf=ConfigProxy(self.osd_conf) if self.osd_conf else None)
            await osd.start()
            self.osds.append(osd)
        self.client = RadosClient(client_id=self.client_id,
                                  trace_sample_rate=self.client_rate)
        await self.client.connect(*self.mon.addr)
        await self.client.ec_profile_set(
            "p", {"plugin": "jax", "k": str(K), "m": str(M)})
        await self.client.pool_create(
            "tp", pg_num=PG_NUM, pool_type="erasure",
            erasure_code_profile="p")
        self.io = self.client.ioctx("tp")
        for _ in range(1200):       # the daemons' EC warm-up compiles
            if all("p" in o._warmed_profiles and not o._warm_tasks
                   for o in self.osds):
                break
            await asyncio.sleep(0.05)
        await self.client.wait_clean(timeout=60)
        return self

    async def __aexit__(self, *exc):
        await self.client.shutdown()
        for osd in self.osds:
            if osd is not None:
                await osd.stop()
        await self.mon.stop()
        for store in self.stores:
            store.umount()

    def tracers(self) -> list[Tracer]:
        return ([o.tracer for o in self.osds if o is not None]
                + [self.client.tracer, device_tracer()])

    def spans(self) -> list[dict]:
        return [s for tr in self.tracers() for s in tr.dump(limit=1 << 20)]

    async def lose_an_osd(self) -> None:
        """Stop an OSD that holds a shard of obj0, mark it down and out,
        wait until every PG is clean again under the new map."""
        from ceph_tpu.osd.daemon import object_to_pg

        om = self.client.osdmap
        pool = om.get_pg_pool(self.io.pool_id)
        _, _, acting, primary = om.pg_to_up_acting_osds(
            object_to_pg(pool, "obj0"))
        victim = next(o for o in acting if o != primary)
        await self.osds[victim].stop()
        self.osds[victim] = None
        for verb in ("down", "out"):
            code, rs, _ = await self.client.command(
                {"prefix": f"osd {verb}", "id": str(victim)})
            assert code == 0, rs
        await self.client._wait_new_map(om.epoch + 1, timeout=10)
        await self.client.wait_clean(
            timeout=60, min_epoch=self.client.osdmap.epoch)


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 170))
    finally:
        loop.close()


def _children(spans: list[dict], parent: dict) -> list[dict]:
    return [s for s in spans if s["parent_id"] == parent["span_id"]]


def _inside(child: dict, parent: dict) -> bool:
    return (parent["start_mono"] <= child["start_mono"]
            and child["end_mono"] <= parent["end_mono"])


# -- the primitive ---------------------------------------------------------

def test_record_files_a_child_with_the_given_interval():
    tr = Tracer("t")
    with tr.span("parent", reqid="r1") as parent:
        pass
    before = tr.counters["spans_recorded"]
    t1 = time.monotonic()
    got = tr.record("wait", parent=parent, start_mono=t1 - 0.25,
                    end_mono=t1, stage="queue", n=3)
    assert tr.counters["spans_recorded"] == before + 1
    assert tr.find(n=3) == [got]
    d = got.dump()
    assert (d["name"], d["parent_id"], d["trace_id"]) == (
        "wait", parent.span_id, parent.trace_id)
    assert (d["start_mono"], d["end_mono"]) == (t1 - 0.25, t1)
    assert d["duration_ms"] == pytest.approx(250.0)
    assert d["tags"] == {"stage": "queue", "n": 3}
    # the wall-clock start lies the same 250 ms before now
    assert time.time() - d["start"] == pytest.approx(0.25, abs=0.05)
    # joined through a wire context it takes the context's trace
    ctx = tr.ctx_for(parent)
    assert tr.record("w2", ctx=ctx, start_mono=t1, end_mono=t1).trace_id \
        == parent.trace_id


def test_span_takes_its_duration_from_the_monotonic_stamps():
    tr = Tracer("t")
    with tr.span("a") as sp:
        time.sleep(0.01)
    assert sp.duration == sp.end_mono - sp.start_mono >= 0.01


@pytest.mark.parametrize("how", ["span", "start_span", "record", "child",
                                 "ctx"])
def test_an_unsampled_trace_with_tail_capture_off_builds_no_span(how):
    tr = Tracer("t", sample_rate=0.0, tail_slow_s=None)
    if how == "span":
        with tr.span("a", x=1) as sp:
            sp.tag(y=2)
    elif how == "start_span":
        sp = tr.start_span("a")
        tr.finish_span(sp)
    elif how == "record":
        sp = tr.record("a", start_mono=1.0, end_mono=2.0)
    elif how == "child":
        with tr.span("a", parent=tracing.INERT) as sp:
            pass
    else:
        unsampled = tracing.TraceContext(7, 8, sampled=False)
        with tr.span("a", ctx=unsampled) as sp:
            pass
    assert sp is tracing.INERT and sp.tags == {}
    assert tr.ctx_for(sp) is None
    assert tr.counters["spans_recorded"] == 0 and tr.dump() == []
    # tail capture alone keeps building them (it has to time them)
    tail = Tracer("t2", sample_rate=0.0, tail_slow_s=1.0)
    with tail.span("a") as kept:
        pass
    assert kept is not tracing.INERT and not kept.sampled


# -- one EC write, one trace -------------------------------------------------

@pytest.fixture(scope="module")
def ec_write(tmp_path_factory):
    async def go():
        async with _Cluster(tmp_path_factory.mktemp("w"), 2501) as c:
            await c.io.write_full("obj0", os.urandom(OBJ_BYTES))
            root = next(s for s in c.client.tracer.find(oid="obj0")
                        if s.name == "client_op")
            spans = c.spans()
            return {"all": spans, "trace": [
                s for s in spans if s["trace_id"] == root.trace_id]}
    return _run(go())


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_write_store_commit_has_its_two_legs_on_every_shard(ec_write):
    trace = ec_write["trace"]
    commits = _named(trace, "store_commit")
    assert len(commits) == K + M
    assert len({c["daemon"] for c in commits}) == K + M   # primary, replicas
    for commit in commits:
        kids = {s["name"]: s for s in _children(trace, commit)}
        assert set(kids) == {"store_exec_wait", "store_txn"}
        wait, txn = kids["store_exec_wait"], kids["store_txn"]
        assert _inside(wait, commit) and _inside(txn, commit)
        assert wait["end_mono"] == txn["start_mono"]
        assert PHASE_TAGS <= set(txn["tags"])
        assert txn["tags"]["bytes"] == OBJ_BYTES // K
        phases = sum(txn["tags"][k] for k in PHASE_TAGS - {"bytes"})
        assert 0 < phases <= txn["duration_ms"] + 1e-3
        # the legs subdivide the commit: no stage of their own for a
        # sum over the store stage to count twice
        assert "stage" not in wait["tags"] and "stage" not in txn["tags"]


def test_write_msg_send_has_its_legs_on_request_and_reply(ec_write):
    trace = ec_write["trace"]
    sends = _named(trace, "msg_send")
    by_msg: dict[str, list] = {}
    for s in sends:
        assert SEND_TAGS <= set(s["tags"]) and s["tags"]["bytes"] > 0
        legs = sum(s["tags"][k] for k in SEND_TAGS - {"bytes"})
        assert legs == pytest.approx(s["duration_ms"], abs=0.01)
        by_msg.setdefault(s["tags"]["msg"], []).append(s)
    assert {m: len(v) for m, v in by_msg.items()} == {
        "MOSDOp": 1, "MOSDECSubOpWrite": K + M - 1,
        "MOSDECSubOpWriteReply": K + M - 1, "MOSDOpReply": 1}
    # a reply's send hangs under the span its request's send hangs under
    root = _named(trace, "client_op")[0]
    assert by_msg["MOSDOp"][0]["parent_id"] == root["span_id"]
    assert by_msg["MOSDOpReply"][0]["parent_id"] == root["span_id"]
    sub_writes = {s["span_id"] for s in _named(trace, "ec_sub_write")}
    assert {s["parent_id"] for s in by_msg["MOSDECSubOpWrite"]} == sub_writes
    assert {s["parent_id"] for s in by_msg["MOSDECSubOpWriteReply"]} \
        == sub_writes
    assert by_msg["MOSDOpReply"][0]["daemon"].startswith("osd.")


def test_write_encode_wait_and_the_launch_that_served_it(ec_write):
    trace = ec_write["trace"]
    (encode,) = _named(trace, "ec_encode")
    (wait,) = _children(trace, encode)
    assert wait["name"] == "encode_batch_wait"
    assert wait["tags"]["stage"] == "queue" and _inside(wait, encode)
    assert wait["daemon"] == encode["daemon"]
    launches = [s for s in _named(ec_write["all"], "xla_launch")
                if encode["span_id"] in s["tags"].get("parents", ())]
    assert len(launches) == 1 and launches[0]["daemon"] == "device"
    assert launches[0]["start_mono"] >= wait["end_mono"]
    assert launches[0]["end_mono"] <= encode["end_mono"]


def test_write_records_no_arrival_marker_and_stays_in_its_span_budget(ec_write):
    assert not _named(ec_write["all"], GONE)
    n = K + M
    # client_op, op_queue, do_op, ec_encode + its wait; a send and a
    # reply send per message; n - 1 sub-writes; n commits of 3 spans
    assert len(ec_write["trace"]) == 5 + 2 * n + (n - 1) + 3 * n
    assert 5 + 2 * 11 + 10 + 3 * 11 <= 75    # the EC(8,3) write's budget


def test_write_critical_path_adds_up_to_the_client_op(ec_write):
    from ceph_tpu.mgr.tracer import TraceCollector

    col = TraceCollector()
    col.ingest("test", ec_write["trace"])
    got = col.assemble(ec_write["trace"][0]["trace_id"])
    assert got["root"] == "client_op"
    assert sum(got["stages_ms"].values()) == pytest.approx(
        got["duration_ms"], rel=0.01)
    on_path = {p["name"] for p in got["critical_path"]}
    assert {"client_op", "do_op", "store_commit"} <= on_path
    # a commit's legs take the commit's stage
    assert all(p["stage"] == "store" for p in got["critical_path"]
               if p["name"] in ("store_txn", "store_exec_wait"))
    assert got["stages_ms"]["store"] > 0 and got["stages_ms"]["net"] > 0


# -- one `osd out`, one trace per PG pass -------------------------------------

@pytest.fixture(scope="module")
def recovery(tmp_path_factory):
    async def go():
        async with _Cluster(tmp_path_factory.mktemp("r"), 2502) as c:
            for i in range(6):
                await c.io.write_full(f"obj{i}", os.urandom(OBJ_BYTES))
            await c.lose_an_osd()
            return c.spans()
    spans = _run(go())
    passes = []
    for root in _named(spans, "recover_pg"):
        trace = [s for s in spans if s["trace_id"] == root["trace_id"]]
        objects = [s for s in _children(trace, root)
                   if s["name"] == "recover_object"
                   and _named(_children(trace, s), "recovery_push")]
        if objects:
            passes.append((root, trace, objects))
    assert passes, "no PG pass pushed an object"
    return {"all": spans, "passes": passes}


def test_recovery_pg_pass_is_one_trace_from_reserve_to_objects(recovery):
    for root, trace, _objects in recovery["passes"]:
        assert root["parent_id"] is None
        assert root["tags"]["result"] in ("ok", "incomplete")
        assert root["tags"]["objects"] >= 1
        kids = _children(trace, root)
        assert {s["name"] for s in kids} == {
            "pg_reserve", "pg_scan", "recovery_admit_wait",
            "recover_object"}
        (reserve,), (scan,) = _named(kids, "pg_reserve"), \
            _named(kids, "pg_scan")
        assert reserve["tags"]["stage"] == "queue"
        assert reserve["tags"]["rounds"] == reserve["tags"]["rejects"] + 1
        assert reserve["end_mono"] <= scan["start_mono"]
        waits = _named(kids, "recovery_admit_wait")
        assert len(waits) == root["tags"]["objects"]
        for w in waits:
            assert w["tags"]["stage"] == "queue"
            assert scan["end_mono"] <= w["start_mono"] and _inside(w, root)
    # every pass, clean PGs too, has the reserve leg and a result
    for root in _named(recovery["all"], "recover_pg"):
        assert root["tags"]["result"] in ("ok", "incomplete", "superseded")


def test_recovery_object_has_read_decode_and_push_children(recovery):
    for _root, trace, objects in recovery["passes"]:
        for obj in objects:
            assert obj["tags"]["result"] == "ok"
            kids = _children(trace, obj)
            legs = [next(s for s in kids if s["name"] == n) for n in (
                "recovery_read", "recovery_decode", "recovery_push")]
            for a, b in zip(legs, legs[1:]):
                assert _inside(a, obj) and a["end_mono"] <= b["start_mono"]
            assert sum(s["duration_ms"] for s in legs) <= obj["duration_ms"]
            (wait,) = _children(trace, legs[1])
            assert wait["name"] == "decode_batch_wait"
            assert wait["tags"]["stage"] == "queue"
            assert any(legs[1]["span_id"] in s["tags"].get("parents", ())
                       for s in _named(recovery["all"], "xla_launch"))


def test_recovery_helpers_reads_and_targets_commit_join_the_tree(recovery):
    for root, trace, objects in recovery["passes"]:
        for obj in objects:
            kids = _children(trace, obj)
            reads = _named(kids, "ec_sub_read")
            assert reads and all(r["daemon"] == root["daemon"] for r in reads)
            # the helper answered under the read's context ...
            assert any(s["tags"].get("msg") == "MOSDECSubOpReadReply"
                       and s["parent_id"] == reads[0]["span_id"]
                       and s["daemon"] != root["daemon"]
                       for s in _named(trace, "msg_send"))
            # ... and the push target committed under the object's
            commits = [s for s in _named(kids, "store_commit")
                       if s["daemon"] != root["daemon"]]
            assert commits
            for commit in commits:
                assert {s["name"] for s in _children(trace, commit)} == {
                    "store_exec_wait", "store_txn"}
            assert {s["tags"]["msg"] for s in _named(kids, "msg_send")} >= {
                "MOSDPGPush", "MOSDPGPushReply"}


def test_recovery_leaves_no_root_but_the_pg_passes_and_launches(recovery):
    roots = {s["name"] for s in recovery["all"] if s["parent_id"] is None}
    assert roots <= {"recover_pg", "xla_launch", "client_op"}
    assert not _named(recovery["all"], GONE)


# -- an off that is off ----------------------------------------------------------

def test_off_records_no_span_for_a_write_or_a_recovered_object(
        tmp_path, monkeypatch):
    dev = device_tracer()
    monkeypatch.setattr(dev, "sample_rate", 0.0)
    monkeypatch.setattr(dev, "tail_slow_s", None)

    async def go():
        async with _Cluster(tmp_path, 2503, osd_conf=OFF,
                            client_sample_rate=0.0) as c:
            c.client.tracer.tail_slow_s = None      # a client has no conf
            tracers = c.tracers()       # the lost OSD's too
            before = [dict(tr.counters) for tr in tracers]
            for i in range(4):
                await c.io.write_full(f"obj{i}", os.urandom(OBJ_BYTES))
            await c.lose_an_osd()
            assert sum(o.perf.dump().get("recovery_ops", 0)
                       for o in c.osds if o is not None) > 0
            for tr, was in zip(tracers, before):
                assert tr.counters["spans_recorded"] == \
                    was["spans_recorded"], tr.name
                assert tr.counters["spans_exported"] == \
                    was["spans_exported"], tr.name
    _run(go())
