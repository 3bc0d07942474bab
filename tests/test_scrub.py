"""Scrub: cross-shard comparison, repair, scheduling + reservations
(src/osd/scrubber: pg_scrubber.cc, scrub_backend.cc,
osd_scrub_sched.cc)."""

import asyncio

import pytest

from ceph_tpu.client import Rados
from ceph_tpu.os.transaction import Transaction
from ceph_tpu.osd.scrub import scrub_pg

from test_client import make_cluster, teardown, run


async def wait_for(cond, timeout=30.0, msg="condition"):
    for _ in range(int(timeout / 0.2)):
        if cond():
            return
        await asyncio.sleep(0.2)
    raise TimeoutError(f"timed out waiting for {msg}")


def corrupt(osd, coll, oid, data=b"BITROT"):
    txn = Transaction()
    txn.write(coll, oid, 0, data)
    osd.store.queue_transaction(txn)


def find_pg(osds, pool_id, oid, rados):
    pgid, primary = rados.objecter.calc_target(pool_id, oid)
    prim = next(o for o in osds if o.whoami == primary)
    return pgid, prim


def test_replicated_scrub_detects_and_repairs():
    async def main():
        mon, osds = await make_cluster(3)
        rados = await Rados(mon.msgr.addr).connect()
        try:
            await rados.pool_create("p", pg_num=4)
            io = await rados.open_ioctx("p")
            await io.write_full("victim", b"pristine-content")
            await io.write_full("other", b"untouched")
            pgid, prim = find_pg(osds, io.pool_id, "victim", rados)
            # rot a REPLICA (not the primary): majority voting must
            # pick the two good copies
            replica = next(o for o in osds
                           if o.whoami != prim.whoami
                           and o.store.exists(f"pg_{pgid}", "victim"))
            corrupt(replica, f"pg_{pgid}", "victim")
            pg = prim.pgs[pgid]
            res = await scrub_pg(pg, repair=False)
            assert not res.clean
            assert "victim" in res.inconsistent
            assert replica.whoami not in \
                res.inconsistent["victim"]["auth_osds"]
            # repair pushes the authoritative copy back
            res = await scrub_pg(pg, repair=True)
            assert res.repaired == ["victim"]
            assert replica.store.read(f"pg_{pgid}", "victim") \
                == b"pristine-content"
            res = await scrub_pg(pg, repair=False)
            assert res.clean
        finally:
            await teardown(mon, osds, rados)
    run(main())


def test_ec_scrub_reencode_check_and_repair():
    async def main():
        mon, osds = await make_cluster(4)
        rados = await Rados(mon.msgr.addr).connect()
        try:
            await rados.mon_command("osd erasure-code-profile set", {
                "name": "p21", "profile": {"plugin": "isa", "k": "2",
                                           "m": "1"}})
            await rados.pool_create("ec", pg_num=2, pool_type="erasure",
                                    erasure_code_profile="p21")
            io = await rados.open_ioctx("ec")
            payload = bytes(range(256)) * 64
            await io.write_full("obj", payload)
            pgid, prim = find_pg(osds, io.pool_id, "obj", rados)
            pg = prim.pgs[pgid]
            # rot one SHARD: its OSD's own digest disagrees with its
            # tag (no shard travels), the shard rebuilt from the two
            # others says it is the bytes that are wrong, and the
            # rebuilt shard is pushed back
            shard_osd = next(o for o in osds
                             if o.whoami in pg.acting
                             and o.whoami != prim.whoami)
            corrupt(shard_osd, f"pg_{pgid}", "obj", b"\xff" * 16)
            res = await scrub_pg(pg, repair=True)
            assert not res.clean
            bad = pg.acting.index(shard_osd.whoami)
            assert res.inconsistent["obj"]["bad_shards"] == [bad]
            assert res.errors == [("obj", bad, "bytes")]
            assert res.repaired == ["obj"]
            assert res.shards_repaired == [("obj", bad)]
            assert prim.perf_scrub.get("map_bytes") > 0
            assert await io.read("obj") == payload
            res = await scrub_pg(pg, repair=False)
            assert res.clean
        finally:
            await teardown(mon, osds, rados)
    run(main())


def test_scheduled_scrub_with_reservations():
    async def main():
        mon, osds = await make_cluster(
            3, osd_config={"osd_scrub_interval": 1.0,
                           "osd_scrub_auto_repair": True})
        rados = await Rados(mon.msgr.addr).connect()
        try:
            await rados.pool_create("p", pg_num=4)
            io = await rados.open_ioctx("p")
            await io.write_full("obj", b"good-bytes")
            pgid, prim = find_pg(osds, io.pool_id, "obj", rados)
            replica = next(o for o in osds
                           if o.whoami != prim.whoami
                           and o.store.exists(f"pg_{pgid}", "obj"))
            corrupt(replica, f"pg_{pgid}", "obj")
            # the SCHEDULER (tick + reservations) must repair it with
            # no manual trigger
            await wait_for(
                lambda: replica.store.read(f"pg_{pgid}", "obj")
                == b"good-bytes",
                timeout=45, msg="scheduled scrub repair")
            assert prim._scrub_stamps.get(pgid, 0) > 0
            # reservation slots drain back after the rounds
            await wait_for(
                lambda: not prim.scrub_reserver.granted
                and not replica.scrub_reserver.granted,
                msg="scrub slots released")
        finally:
            await teardown(mon, osds, rados)
    run(main())
