"""Cross-daemon trace spans: one client op's trace id flows
client -> primary -> replicas -> store, and the assembled spans form
the full hop tree (src/common/tracer.h role).
"""

import asyncio

import pytest

from ceph_tpu.client.rados import Rados
from ceph_tpu.common.tracing import all_spans, get_tracer
from ceph_tpu.mon import Monitor
from ceph_tpu.osd import OSD


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_trace_spans_cover_every_hop():
    async def main():
        mon = Monitor(rank=0, config={"mon_osd_min_down_reporters": 1})
        addr = await mon.start()
        mon.peer_addrs = [addr]
        osds = []
        for i in range(3):
            o = OSD(host=f"h{i}", whoami=i)
            await o.start(addr)
            osds.append(o)
        r = Rados(addr, name="client.traced")
        await r.connect()
        await r.mon_command("osd pool create",
                            {"name": "p", "pg_num": 4, "size": 3})
        io = await r.open_ioctx("p")
        await io.write_full("traced-obj", b"follow me" * 100)

        # the client's root span carries the trace id
        client_spans = get_tracer("client.traced").dump()
        roots = [s for s in client_spans
                 if s["name"] == "client.osd_op"
                 and s["tags"].get("oid") == "traced-obj"]
        assert roots, "client root span missing"
        trace_id = roots[-1]["trace_id"]

        spans = all_spans(trace_id)
        names = [s["name"] for s in spans]
        assert "client.osd_op" in names
        assert "osd.do_op" in names
        # size=3 pool: two replicas each record a rep_op span
        assert names.count("osd.rep_op") == 2
        # the store commit is traced on the primary AND both replicas
        assert names.count("store.txn") == 3
        # every span belongs to the same trace and timing is recorded
        for s in spans:
            assert s["trace_id"] == trace_id
            assert s["duration_ms"] is not None

        # hop TREE: every non-root span's parent exists in the trace
        by_id = {s["span_id"]: s for s in spans}
        root = [s for s in spans if s["parent_id"] is None]
        assert len(root) == 1 and root[0]["name"] == "client.osd_op"
        for s in spans:
            if s["parent_id"] is not None:
                assert s["parent_id"] in by_id, \
                    f"orphan span {s['name']}"
        # rep_op spans hang off the primary's do_op span
        do_op = next(s for s in spans if s["name"] == "osd.do_op")
        for s in spans:
            if s["name"] == "osd.rep_op":
                assert s["parent_id"] == do_op["span_id"]
        # daemons differ across hops: client + primary + 2 replicas
        assert len({s["daemon"] for s in spans}) == 4

        await r.shutdown()
        for o in osds:
            await o.stop()
        await mon.stop()
    run(main())


def test_ec_write_spans_encode_under_do_op():
    """An EC pool's write: ``ec.encode`` (submit to the batcher until
    parity and checksums are back) and the primary's own ``store.txn``
    hang off its ``osd.do_op``, and the op's stages, cut at the spans'
    edges, add up to the client's root span.  The sub-writes to the
    other shards carry no trace context: nothing reads a span there."""
    from ceph_tpu.loadgen.cluster import SimCluster

    async def main():
        cluster = await SimCluster.create(4)
        r = await Rados(cluster.addr, name="client.ectraced").connect()
        try:
            await r.mon_command(
                "osd erasure-code-profile set",
                {"name": "traced-prof", "profile": {
                    "plugin": "tpu", "k": "2", "m": "1",
                    "technique": "reed_sol_van"}})
            await r.pool_create("ecp", pg_num=4, pool_type="erasure",
                                erasure_code_profile="traced-prof")
            io = await r.open_ioctx("ecp")
            await io.write_full("ec-traced", b"shard me" * 2048)
        finally:
            await r.shutdown()
            await cluster.stop()

        roots = [s for s in get_tracer("client.ectraced").dump()
                 if s["name"] == "client.osd_op"
                 and s["tags"].get("oid") == "ec-traced"]
        assert len(roots) == 1
        root = roots[0]
        spans = all_spans(root["trace_id"])
        by_name: dict = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
            assert s["duration_ms"] is not None and s["duration_ms"] >= 0
        assert {n: len(v) for n, v in by_name.items()} == {
            "client.osd_op": 1, "osd.do_op": 1, "ec.encode": 1,
            "store.txn": 1}
        do_op, = by_name["osd.do_op"]
        enc, = by_name["ec.encode"]
        txn, = by_name["store.txn"]
        assert do_op["parent_id"] == root["span_id"]
        for s in (enc, txn):
            assert s["parent_id"] == do_op["span_id"], s["name"]
            assert s["daemon"] == do_op["daemon"]
        assert len({s["daemon"] for s in spans}) == 2
        # order in time, and the stages sum to the root
        eps = 1e-6
        assert root["start"] <= do_op["start"] + eps
        assert do_op["start"] <= enc["start"] + eps
        assert enc["end"] <= txn["start"] + eps
        assert txn["end"] <= do_op["end"] + eps
        assert do_op["end"] <= root["end"] + eps
        stages = [do_op["start"] - root["start"],       # to the OSD
                  enc["start"] - do_op["start"],        # before the encode
                  enc["end"] - enc["start"],            # encode
                  do_op["end"] - enc["end"],            # commit and reply
                  root["end"] - do_op["end"]]           # reply to client
        assert all(s >= -eps for s in stages)
        assert sum(stages) * 1e3 == pytest.approx(root["duration_ms"],
                                                  abs=1e-2)
    run(main())
