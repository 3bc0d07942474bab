"""In-process mini-cluster: mon + N OSDs on loopback.

The tier-3 analog of qa/standalone (vstart-style clusters per test):
replicated and EC pool I/O end-to-end, OSD failure -> mon marks down ->
re-peer -> degraded read, and log-based recovery when the OSD returns.
"""

import asyncio

import pytest

from ceph_tpu.mon import Monitor
from ceph_tpu.msg import Message, Messenger
from ceph_tpu.osd import OSD
from ceph_tpu.osd.backend import pack_mutations


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


class Cluster:
    def __init__(self, mon, osds, client):
        self.mon = mon
        self.osds = osds
        self.client = client

    async def stop(self):
        for o in self.osds:
            await o.stop()
        await self.client.shutdown()
        await self.mon.stop()

    async def command(self, cmd, args=None):
        q = asyncio.Queue()

        async def d(conn, msg):
            if msg.type == "mon_command_reply":
                await q.put(msg.data)

        self.client.add_dispatcher(d)
        try:
            await self.client.send(self.mon.msgr.addr, "mon.0",
                                   Message("mon_command",
                                           {"cmd": cmd, "args": args or {}}))
            data = await asyncio.wait_for(q.get(), 10)
        finally:
            self.client.dispatchers.remove(d)
        if not data["ok"]:
            raise RuntimeError(data["error"])
        return data["result"]

    def target_for(self, pool_name, oid):
        omap = self.mon.osdmap
        pool_id = omap.pool_names[pool_name]
        _, ps = omap.object_to_pg(pool_id, oid)
        up = omap.pg_to_up_acting_osds(pool_id, ps)
        primary = omap.pg_primary(up)
        pgid = omap.pg_name(pool_id, ps)
        return pgid, primary, up

    async def osd_op(self, pool_name, oid, ops, timeout=15, retries=40):
        """Send ops to the current primary, retrying through peering.

        The reqid is stable across retries of the same logical op (the
        Objecter's osd_reqid_t discipline) so a delayed duplicate
        delivery cannot re-apply an old write after newer ones.
        """
        q = asyncio.Queue()
        self._op_serial = getattr(self, "_op_serial", 0) + 1
        tid = self._op_serial
        reqid = [f"{self.client.name}:{self.client.incarnation}", tid]

        async def d(conn, msg):
            # match replies to THIS op by tid: concurrent osd_ops share
            # the client, and an unfiltered dispatcher would hand one
            # writer another writer's ack (a write acked-but-never-
            # committed is exactly the corruption the thrasher hunts)
            if (msg.type == "osd_op_reply"
                    and msg.data.get("tid") == tid):
                await q.put(msg)

        self.client.add_dispatcher(d)
        try:
            for attempt in range(retries):
                pgid, primary, _ = self.target_for(pool_name, oid)
                if primary is None:
                    await asyncio.sleep(0.25)
                    continue
                addr = self.mon.osdmap.osds[primary].addr
                meta, segs = pack_mutations(ops)
                try:
                    await self.client.send(
                        tuple(addr), f"osd.{primary}",
                        Message("osd_op", {"pgid": pgid, "oid": oid,
                                           "ops": meta, "reqid": reqid,
                                           "tid": tid},
                                segments=segs))
                    reply = await asyncio.wait_for(q.get(), timeout)
                except (ConnectionError, OSError, asyncio.TimeoutError):
                    await asyncio.sleep(0.25)
                    continue
                err = reply.data.get("err")
                if err in ("ENOTPRIMARY", "EAGAIN", "ENXIO no such pg"):
                    await asyncio.sleep(0.25)
                    continue
                return reply
            raise TimeoutError(f"osd_op on {oid} never succeeded")
        finally:
            self.client.dispatchers.remove(d)


async def make_cluster(n_osds, mon_config=None, osd_config=None):
    mon = Monitor(rank=0, config={"mon_osd_min_down_reporters": 1,
                                  **(mon_config or {})})
    addr = await mon.start()
    mon.peer_addrs = [addr]
    osds = []
    for i in range(n_osds):
        osd = OSD(host=f"host{i}", config=osd_config)
        await osd.start(addr)
        osds.append(osd)
    client = Messenger("client.test")
    await client.bind()
    return Cluster(mon, osds, client)


def read_result(reply, idx=0):
    r = reply.data["results"][idx]
    if "seg" in r:
        return r, reply.segments[r["seg"]]
    return r, None


def test_replicated_pool_io():
    async def main():
        c = await make_cluster(3)
        try:
            await c.command("osd pool create",
                            {"name": "rbd", "pg_num": 8, "size": 3,
                             "min_size": 2})
            payload = b"hello rados-tpu" * 100
            await c.osd_op("rbd", "obj1", [
                {"op": "write", "off": 0, "data": payload}])
            reply = await c.osd_op("rbd", "obj1", [
                {"op": "read", "off": 0, "len": None}])
            r, data = read_result(reply)
            assert r["ok"] and data == payload
            # append + stat
            await c.osd_op("rbd", "obj1", [
                {"op": "append", "data": b"-tail"}])
            reply = await c.osd_op("rbd", "obj1", [{"op": "stat"}])
            r, _ = read_result(reply)
            assert r["size"] == len(payload) + 5
            # omap + xattr
            await c.osd_op("rbd", "obj1", [
                {"op": "setxattr", "name": "cls", "value": b"rbd"},
                {"op": "omap_set", "kv": {"k1": b"v1", "k2": b"v2"}}])
            reply = await c.osd_op("rbd", "obj1", [
                {"op": "getxattr", "name": "cls"},
                {"op": "omap_get"}])
            r0, xv = read_result(reply, 0)
            r1, _ = read_result(reply, 1)
            assert xv == b"rbd"
            assert r1["omap"] == {"k1": b"v1".hex(), "k2": b"v2".hex()}
            # the write really is replicated: every acting OSD has it
            pgid, primary, up = c.target_for("rbd", "obj1")
            assert len(up) == 3
            for osd in c.osds:
                if osd.whoami in up:
                    assert osd.store.read(
                        f"pg_{pgid}", "obj1", 0, None).startswith(payload)
            # remove
            await c.osd_op("rbd", "obj1", [{"op": "remove"}])
            reply = await c.osd_op("rbd", "obj1", [{"op": "stat"}])
            r, _ = read_result(reply)
            assert r.get("err") == "ENOENT"
        finally:
            await c.stop()
    run(main())


def test_ec_pool_io():
    async def main():
        c = await make_cluster(3)
        try:
            await c.command("osd erasure-code-profile set",
                            {"name": "p21",
                             "profile": {"plugin": "tpu", "k": "2",
                                         "m": "1",
                                         "technique": "reed_sol_van"}})
            await c.command("osd pool create",
                            {"name": "ecpool", "type": "erasure",
                             "pg_num": 4, "erasure_code_profile": "p21"})
            payload = bytes(range(256)) * 64          # 16 KiB
            await c.osd_op("ecpool", "ecobj", [
                {"op": "write", "off": 0, "data": payload}])
            reply = await c.osd_op("ecpool", "ecobj", [
                {"op": "read", "off": 0, "len": None}])
            r, data = read_result(reply)
            assert r["ok"] and data == payload
            # partial read
            reply = await c.osd_op("ecpool", "ecobj", [
                {"op": "read", "off": 100, "len": 50}])
            r, data = read_result(reply)
            assert data == payload[100:150]
            # RMW overwrite inside the object
            await c.osd_op("ecpool", "ecobj", [
                {"op": "write", "off": 10, "data": b"X" * 20}])
            reply = await c.osd_op("ecpool", "ecobj", [
                {"op": "read", "off": 0, "len": 40}])
            r, data = read_result(reply)
            expect = bytearray(payload[:40])
            expect[10:30] = b"X" * 20
            assert data == bytes(expect)
            # all three shards exist on distinct OSDs
            pgid, _, up = c.target_for("ecpool", "ecobj")
            n_shards = sum(
                1 for osd in c.osds
                if osd.whoami in up
                and osd.store.exists(f"pg_{pgid}", "ecobj"))
            assert n_shards == 3
        finally:
            await c.stop()
    run(main())


def test_resent_write_deduped_by_reqid():
    """A resent write (lost reply) must not double-apply — osd_reqid
    dedup via the PG log."""
    async def main():
        c = await make_cluster(3)
        try:
            await c.command("osd pool create",
                            {"name": "rbd", "pg_num": 4, "size": 3,
                             "min_size": 2})
            pgid, primary, _ = c.target_for("rbd", "dup-obj")
            # wait for the pg to activate
            await c.osd_op("rbd", "dup-obj", [
                {"op": "write", "off": 0, "data": b"base"}])
            q = asyncio.Queue()

            async def d(conn, msg):
                if msg.type == "osd_op_reply":
                    await q.put(msg)

            c.client.add_dispatcher(d)
            addr = tuple(c.mon.osdmap.osds[primary].addr)
            meta, segs = pack_mutations([{"op": "append", "data": b"+x"}])
            payload = {"pgid": pgid, "oid": "dup-obj", "ops": meta,
                       "reqid": ["client.test:abc", 42]}
            # send the SAME logical request twice (simulating a resend
            # after a lost reply)
            for _ in range(2):
                await c.client.send(addr, f"osd.{primary}",
                                    Message("osd_op", dict(payload),
                                            segments=list(segs)))
            r1 = await asyncio.wait_for(q.get(), 10)
            r2 = await asyncio.wait_for(q.get(), 10)
            c.client.dispatchers.remove(d)
            assert {bool(r.data.get("dup"))
                    for r in (r1, r2)} == {False, True}
            # both replies carry the same committed version
            assert r1.data["version"] == r2.data["version"]
            reply = await c.osd_op("rbd", "dup-obj", [
                {"op": "read", "off": 0, "len": None}])
            _, data = read_result(reply)
            assert data == b"base+x"          # applied exactly once
        finally:
            await c.stop()
    run(main())


def test_failure_detection_and_degraded_read():
    async def main():
        c = await make_cluster(
            3,
            mon_config={"mon_osd_down_out_interval": 3600.0},
            osd_config={"osd_heartbeat_interval": 0.2,
                        "osd_heartbeat_grace": 3.0})
        try:
            await c.command("osd erasure-code-profile set",
                            {"name": "p21",
                             "profile": {"plugin": "tpu", "k": "2",
                                         "m": "1",
                                         "technique": "reed_sol_van"}})
            await c.command("osd pool create",
                            {"name": "ecpool", "type": "erasure",
                             "pg_num": 4, "erasure_code_profile": "p21"})
            payload = b"degraded-read-me" * 512
            await c.osd_op("ecpool", "victim", [
                {"op": "write", "off": 0, "data": payload}])
            # kill a non-primary shard holder
            _, primary, up = c.target_for("ecpool", "victim")
            victim_id = next(o for o in up if o >= 0 and o != primary)
            victim = next(o for o in c.osds if o.whoami == victim_id)
            await victim.stop()
            # heartbeats miss -> failure reports -> mon marks it down
            for _ in range(100):
                if not c.mon.osdmap.is_up(victim_id):
                    break
                await asyncio.sleep(0.2)
            assert not c.mon.osdmap.is_up(victim_id), "mon never marked down"
            # EC degraded read: k=2 shards remain, decode still works
            reply = await c.osd_op("ecpool", "victim", [
                {"op": "read", "off": 0, "len": None}])
            r, data = read_result(reply)
            assert r["ok"] and data == payload
        finally:
            await c.stop()
    run(main())


def test_replicated_failover_and_recovery():
    async def main():
        c = await make_cluster(
            3,
            mon_config={"mon_osd_down_out_interval": 3600.0},
            osd_config={"osd_heartbeat_interval": 0.2,
                        "osd_heartbeat_grace": 3.0})
        try:
            await c.command("osd pool create",
                            {"name": "rbd", "pg_num": 8, "size": 3,
                             "min_size": 2})
            payload = b"failover" * 64
            await c.osd_op("rbd", "fo1", [
                {"op": "write", "off": 0, "data": payload}])
            pgid, primary, _ = c.target_for("rbd", "fo1")
            victim = next(o for o in c.osds if o.whoami == primary)
            store = victim.store
            uuid, whoami = victim.uuid, victim.whoami
            await victim.stop()
            for _ in range(100):
                if not c.mon.osdmap.is_up(primary):
                    break
                await asyncio.sleep(0.2)
            assert not c.mon.osdmap.is_up(primary)
            # new primary serves reads AND writes after re-peering
            reply = await c.osd_op("rbd", "fo1", [
                {"op": "read", "off": 0, "len": None}])
            r, data = read_result(reply)
            assert data == payload
            await c.osd_op("rbd", "fo1", [
                {"op": "append", "data": b"+while-down"}])
            # bring the dead OSD back with the same store and id:
            # log-based recovery must catch it up
            revived = OSD(uuid=uuid, whoami=whoami, store=store,
                          host=f"host{whoami}",
                          config={"osd_heartbeat_interval": 0.2,
                                  "osd_heartbeat_grace": 3.0})
            await revived.start(c.mon.msgr.addr)
            c.osds = [o for o in c.osds if o.whoami != whoami] + [revived]
            for _ in range(100):
                if c.mon.osdmap.is_up(whoami):
                    break
                await asyncio.sleep(0.2)
            assert c.mon.osdmap.is_up(whoami)
            # wait until recovery pushed the missed append to the
            # revived OSD's local store
            want = payload + b"+while-down"
            for _ in range(200):
                got = revived.store.read(f"pg_{pgid}", "fo1", 0, None)
                if got == want:
                    break
                await asyncio.sleep(0.2)
            assert revived.store.read(f"pg_{pgid}", "fo1", 0, None) == want
        finally:
            await c.stop()
    run(main())


def test_op_vector_in_order_read_after_write():
    """Reads placed after writes in one op vector observe the pending
    write state (PrimaryLogPG runs the vector through one ObjectContext
    in order)."""
    async def main():
        c = await make_cluster(3)
        try:
            await c.command("osd pool create",
                            {"name": "rbd", "pg_num": 4, "size": 3,
                             "min_size": 2})
            await c.osd_op("rbd", "seq", [
                {"op": "write", "off": 0, "data": b"AAAA"}])
            # write then read in ONE vector: the read sees the write
            reply = await c.osd_op("rbd", "seq", [
                {"op": "write", "off": 0, "data": b"BBBB"},
                {"op": "read", "off": 0, "len": None},
                {"op": "append", "data": b"CC"},
                {"op": "stat"},
            ])
            r1, data = read_result(reply, 1)
            assert r1["ok"] and data == b"BBBB"
            r3, _ = read_result(reply, 3)
            assert r3["size"] == 6          # BBBB + CC
            # and the commit is atomic: final state reflects both writes
            reply = await c.osd_op("rbd", "seq", [
                {"op": "read", "off": 0, "len": None}])
            _, data = read_result(reply)
            assert data == b"BBBBCC"
            # read-after-remove in one vector -> ENOENT, then recreate
            reply = await c.osd_op("rbd", "seq", [
                {"op": "remove"},
                {"op": "stat"},
                {"op": "write", "off": 0, "data": b"new"},
                {"op": "read", "off": 0, "len": None},
            ])
            r1, _ = read_result(reply, 1)
            assert r1.get("err") == "ENOENT"
            r3, data = read_result(reply, 3)
            assert data == b"new"
        finally:
            await c.stop()
    run(main())


def test_ec_create_and_attr_only_preserve_data():
    """create / attr-only op vectors on an EC pool must not re-encode
    (and so truncate) existing object content."""
    async def main():
        c = await make_cluster(3)
        try:
            await c.command("osd erasure-code-profile set",
                            {"name": "p21",
                             "profile": {"plugin": "tpu", "k": "2",
                                         "m": "1",
                                         "technique": "reed_sol_van"}})
            await c.command("osd pool create",
                            {"name": "ecpool", "type": "erasure",
                             "pg_num": 4, "erasure_code_profile": "p21"})
            payload = bytes(range(256)) * 32
            await c.osd_op("ecpool", "obj", [
                {"op": "write", "off": 0, "data": payload}])
            # create on an existing object: touch semantics, keeps bytes
            await c.osd_op("ecpool", "obj", [{"op": "create"}])
            reply = await c.osd_op("ecpool", "obj", [
                {"op": "read", "off": 0, "len": None}])
            r, data = read_result(reply)
            assert r["ok"] and data == payload, "create destroyed EC data"
            # attr-only vector: also preserves content
            await c.osd_op("ecpool", "obj", [
                {"op": "setxattr", "name": "a", "value": b"v"},
                {"op": "omap_set", "kv": {"k": b"v"}}])
            reply = await c.osd_op("ecpool", "obj", [
                {"op": "read", "off": 0, "len": None},
                {"op": "getxattr", "name": "a"}])
            r, data = read_result(reply, 0)
            assert data == payload, "attr-only op destroyed EC data"
            _, xv = read_result(reply, 1)
            assert xv == b"v"
        finally:
            await c.stop()
    run(main())


def test_ec_remove_recreate_one_vector_and_reserved_xattrs():
    async def main():
        c = await make_cluster(3)
        try:
            await c.command("osd erasure-code-profile set",
                            {"name": "p21",
                             "profile": {"plugin": "tpu", "k": "2",
                                         "m": "1",
                                         "technique": "reed_sol_van"}})
            await c.command("osd pool create",
                            {"name": "ecpool", "type": "erasure",
                             "pg_num": 4, "erasure_code_profile": "p21"})
            await c.osd_op("ecpool", "rr", [
                {"op": "write", "off": 0, "data": b"old-content"}])
            # remove + recreate in ONE vector: final state is the new data
            await c.osd_op("ecpool", "rr", [
                {"op": "remove"},
                {"op": "write", "off": 0, "data": b"recreated"}])
            reply = await c.osd_op("ecpool", "rr", [
                {"op": "read", "off": 0, "len": None}])
            r, data = read_result(reply)
            assert r["ok"] and data == b"recreated", data
            # clients cannot clobber reserved internal xattrs
            reply = await c.osd_op("ecpool", "rr", [
                {"op": "setxattr", "name": "_size", "value": b"999"}])
            assert "EINVAL" in (reply.data.get("err") or "")
            reply = await c.osd_op("ecpool", "rr", [
                {"op": "read", "off": 0, "len": None}])
            _, data = read_result(reply)
            assert data == b"recreated"
        finally:
            await c.stop()
    run(main())


def test_laggard_replica_healed_after_dropped_subop():
    """A replica that silently drops a sub-write (no reply, stays up)
    is recorded missing that object and recovery re-pushes it -- the
    stale copy must not survive (all-commit laggard healing)."""
    async def main():
        c = await make_cluster(3, osd_config={
            "osd_heartbeat_interval": 0.2, "osd_heartbeat_grace": 5.0})
        try:
            await c.command("osd pool create",
                            {"name": "rbd", "pg_num": 1, "size": 3,
                             "min_size": 2})
            await c.osd_op("rbd", "lag-obj", [
                {"op": "writefull", "data": b"v1" * 50}])
            pgid, primary, up = c.target_for("rbd", "lag-obj")
            replica = next(o for o in c.osds
                           if o.whoami in up and o.whoami != primary)
            # drop exactly one rep_op on the replica: applied nowhere,
            # no reply sent
            orig = replica._h_rep_op
            dropped = {"n": 0}

            async def dropper(conn, msg):
                if (msg.data.get("entry", {}).get("oid") == "lag-obj"
                        and dropped["n"] == 0):
                    dropped["n"] += 1
                    return          # swallow: no apply, no reply
                await orig(conn, msg)

            replica._h_rep_op = dropper
            await c.osd_op("rbd", "lag-obj", [
                {"op": "writefull", "data": b"v2" * 50}],
                timeout=20, retries=3)
            assert dropped["n"] == 1
            # recovery must re-push the object to the laggard
            for _ in range(100):
                try:
                    got = replica.store.read(f"pg_{pgid}", "lag-obj",
                                             0, None)
                    if got == b"v2" * 50:
                        break
                except FileNotFoundError:
                    pass
                await asyncio.sleep(0.3)
            got = replica.store.read(f"pg_{pgid}", "lag-obj", 0, None)
            assert got == b"v2" * 50, "laggard still stale"
        finally:
            await c.stop()
    run(main())


def test_peer_entering_the_heartbeat_set_starts_a_fresh_clock():
    """The capped heartbeat set moves with the map (at 12 OSDs each
    daemon monitors 10 of its 11 peers).  A peer that was outside the
    set keeps no failure-detection stamp: when a death elsewhere pulls
    it in, it must not be judged on how long ago it was last heard --
    while a MONITORED peer silent past the grace is still reported."""
    import time

    osd = OSD(host="h")                  # never started: no messenger
    sent: list[Message] = []

    async def noop(*a, **k):
        pass

    async def to_mon(msg):
        sent.append(msg)

    osd._cephx_refresh = osd._report_to_mgr = osd._ping_one = noop
    osd._maybe_schedule_scrubs = lambda now: None
    osd._mon_send_failover = to_mon
    peers = [1]
    osd._heartbeat_peers = lambda: list(peers)
    now = time.monotonic()
    grace = osd.config["osd_heartbeat_grace"]
    osd._hb_last = {1: now, 2: now - 100 * grace}   # 2: heard at "boot"

    run(osd._heartbeat_once())
    peers.append(2)                      # a map change pulls osd.2 in
    run(osd._heartbeat_once())
    assert [m.data["target"] for m in sent
            if m.type == "osd_failure"] == []
    assert osd._hb_last[2] >= now        # its clock started now

    osd._hb_last[2] -= 2 * grace         # monitored, then silent
    run(osd._heartbeat_once())
    assert [m.data["target"] for m in sent
            if m.type == "osd_failure"] == [2]
