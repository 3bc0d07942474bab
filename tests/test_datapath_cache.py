"""Device-resident shard cache: coherence is the correctness boundary.

The DeviceShardCache (os/device_cache.py) must be PROVABLY
byte-identical to the host path: store-boundary invalidation on every
mutating txn (all mutation paths converge there), kill/revive dropping
residency, byte-budget eviction under pressure, and the write path's
donated-launch output flowing into residency without corrupting the
caller's view.
"""

import asyncio
import os
import tempfile

import numpy as np
import pytest

from ceph_tpu.os.device_cache import DeviceShardCache, PERF
from ceph_tpu.os.store import MemStore, DBStore
from ceph_tpu.os.blockstore import BlockStore
from ceph_tpu.os.transaction import Transaction

from test_osd_cluster import make_cluster, read_result, run


# -- unit: LRU / byte budget -------------------------------------------------

def test_byte_budget_eviction_under_pressure():
    c = DeviceShardCache(max_bytes=3 * 1000)
    for i in range(3):
        c.put("c", f"o{i}", bytes(1000), size=1000, ver=(1, i))
    assert c.used_bytes == 3000 and len(c) == 3
    assert c.get("c", "o0") is not None          # refresh o0
    c.put("c", "o3", bytes(1000), size=1000, ver=(1, 3))
    assert c.used_bytes <= 3000
    assert c.get("c", "o1") is None              # LRU victim
    assert c.get("c", "o0") is not None
    # an entry above the per-entry cap is never cached (and clears any
    # stale resident copy under the same key)
    c2 = DeviceShardCache(max_bytes=1 << 20, entry_max=100)
    c2.put("c", "big", bytes(50), size=50, ver=(1, 1))
    c2.put("c", "big", bytes(500), size=500, ver=(1, 2))
    assert ("c", "big") not in c2
    assert c2.used_bytes == 0


def test_oversize_entries_skip_whole_budget():
    c = DeviceShardCache(max_bytes=10_000, entry_max=10_000)
    c.put("c", "a", bytes(9000), size=9000, ver=(1, 1))
    c.put("c", "b", bytes(9000), size=9000, ver=(1, 2))
    assert c.used_bytes <= 10_000
    assert len(c) == 1                           # a evicted for b
    assert c.get("c", "b") is not None


def test_entry_carries_identity_and_slices():
    c = DeviceShardCache()
    buf = np.arange(256, dtype=np.uint8)
    c.put("c", "o", buf, size=1000, ver=(3, 7), shard=2, crc=123)
    e = c.get("c", "o")
    assert e.size == 1000 and e.ver == (3, 7)
    assert e.shard == 2 and e.crc == 123
    assert bytes(e.buf[10:20]) == bytes(buf[10:20])


def test_device_view_uploads_once():
    c = DeviceShardCache()
    c.put("c", "o", bytes(range(64)), size=64, ver=(1, 1))
    n0 = PERF.get("device_uploads")
    v1 = c.device_view("c", "o")
    v2 = c.device_view("c", "o")
    assert v1 is v2                              # memoized upload
    assert PERF.get("device_uploads") == n0 + 1
    assert bytes(np.asarray(v1)) == bytes(range(64))


# -- unit: store-boundary invalidation ---------------------------------------

def _mutation_cases():
    return [
        ("write", lambda t: t.write("c", "o", 0, b"X")),
        ("zero", lambda t: t.zero("c", "o", 0, 4)),
        ("truncate", lambda t: t.truncate("c", "o", 1)),
        ("remove", lambda t: t.remove("c", "o")),
        ("setattr", lambda t: t.setattr("c", "o", "_crc", b"0")),
        ("rmattr", lambda t: t.rmattr("c", "o", "_crc")),
        ("rmcoll", lambda t: t.remove_collection("c")),
    ]


@pytest.mark.parametrize("store_kind", ["mem", "db", "block"])
def test_every_store_invalidates_on_mutating_txn(store_kind,
                                                 tmp_path):
    for name, mutate in _mutation_cases():
        if store_kind == "mem":
            store = MemStore()
        elif store_kind == "db":
            store = DBStore(str(tmp_path / f"{name}.db"))
        else:
            store = BlockStore(str(tmp_path / f"bs_{name}"))
            store.mount()
        cache = DeviceShardCache()
        store.attach_shard_cache(cache)
        store.queue_transaction(
            Transaction().create_collection("c"))
        t = Transaction()
        t.write("c", "o", 0, b"original")
        store.queue_transaction(t)
        cache.put("c", "o", b"original", size=8, ver=(1, 1))
        assert ("c", "o") in cache
        t = Transaction()
        mutate(t)
        store.queue_transaction(t)
        assert ("c", "o") not in cache, \
            f"{store_kind}: {name} left a stale resident copy"
        if store_kind == "block":
            store.umount()


def test_clone_invalidates_destination_not_source():
    store = MemStore()
    cache = DeviceShardCache()
    store.attach_shard_cache(cache)
    store.queue_transaction(Transaction().create_collection("c"))
    t = Transaction()
    t.write("c", "src", 0, b"src-bytes")
    t.write("c", "dst", 0, b"old-dst")
    store.queue_transaction(t)
    cache.put("c", "src", b"src-bytes", size=9, ver=(1, 1))
    cache.put("c", "dst", b"old-dst", size=7, ver=(1, 1))
    t = Transaction()
    t.clone("c", "src", "dst")
    store.queue_transaction(t)
    assert ("c", "src") in cache
    assert ("c", "dst") not in cache


def test_blockstore_remount_clears_residency(tmp_path):
    store = BlockStore(str(tmp_path / "bs"))
    cache = DeviceShardCache()
    store.attach_shard_cache(cache)
    store.mount()
    store.queue_transaction(Transaction().create_collection("c"))
    t = Transaction()
    t.write("c", "o", 0, b"payload")
    store.queue_transaction(t)
    cache.put("c", "o", b"payload", size=7, ver=(1, 1))
    store.umount()
    store.mount()                                # revive on same dir
    assert len(cache) == 0, "remount must drop all residency"
    assert store.read("c", "o", 0, None) == b"payload"
    store.umount()


# -- cluster: cache-hit reads byte-identical to cold host reads --------------

async def _ec_cluster(n=3, k="2", m="1", osd_config=None):
    c = await make_cluster(n, osd_config=osd_config)
    await c.command("osd erasure-code-profile set",
                    {"name": "prof",
                     "profile": {"plugin": "tpu", "k": k, "m": m,
                                 "technique": "reed_sol_van"}})
    await c.command("osd pool create",
                    {"name": "ecpool", "type": "erasure",
                     "pg_num": 2, "erasure_code_profile": "prof"})
    return c


async def _read(c, oid, off=0, length=None):
    reply = await c.osd_op("ecpool", oid, [
        {"op": "read", "off": off, "len": length}])
    r, data = read_result(reply)
    assert r.get("ok"), r
    return data


def test_cached_reads_byte_identical_across_overwrite_and_truncate():
    async def main():
        c = await _ec_cluster()
        try:
            rng = np.random.default_rng(5)
            base = rng.integers(0, 256, 5 * 8192,
                                dtype=np.uint8).tobytes()
            await c.osd_op("ecpool", "obj", [
                {"op": "writefull", "data": base}])
            h0 = PERF.get("hits")
            warm1 = await _read(c, "obj")        # fills / hits caches
            warm2 = await _read(c, "obj")
            assert warm1 == base and warm2 == base
            assert PERF.get("hits") > h0, "reads never hit the cache"
            # overwrite: resident copies MUST follow the store
            patch = b"P" * 5000
            await c.osd_op("ecpool", "obj", [
                {"op": "write", "off": 3000, "data": patch}])
            shadow = bytearray(base)
            shadow[3000:8000] = patch
            assert await _read(c, "obj") == bytes(shadow)
            # truncate (full-object path): ditto
            await c.osd_op("ecpool", "obj", [
                {"op": "truncate", "size": 9000}])
            assert await _read(c, "obj") == bytes(shadow[:9000])
            # grow again past the truncation point
            await c.osd_op("ecpool", "obj", [
                {"op": "write", "off": 20000, "data": b"Z" * 100}])
            want = bytearray(shadow[:9000])
            want.extend(b"\0" * (20000 - 9000))
            want.extend(b"Z" * 100)
            assert await _read(c, "obj") == bytes(want)
        finally:
            await c.stop()
    run(main())


def test_eviction_pressure_never_breaks_reads():
    async def main():
        # a cache small enough that objects evict each other
        c = await _ec_cluster(osd_config={
            "osd_datapath_cache_bytes": 16 * 1024})
        try:
            rng = np.random.default_rng(6)
            objs = {f"o{i}": rng.integers(0, 256, 3 * 8192,
                                          dtype=np.uint8).tobytes()
                    for i in range(6)}
            for oid, data in objs.items():
                await c.osd_op("ecpool", oid, [
                    {"op": "writefull", "data": data}])
            ev0 = PERF.get("evictions")
            for _ in range(2):
                for oid, data in objs.items():
                    assert await _read(c, oid) == data
            assert PERF.get("evictions") > ev0, \
                "the pressure workload never evicted"
            for osd in c.osds:
                if osd.shard_cache is not None:
                    assert (osd.shard_cache.used_bytes
                            <= osd.shard_cache.max_bytes)
        finally:
            await c.stop()
    run(main())


def test_kill_revive_never_serves_stale_resident_bytes():
    """An OSD killed with hot residency must come back cold: the
    object is overwritten while it is down, and the revived OSD
    (fresh cache, log-driven recovery) must serve the NEW bytes."""
    async def main():
        from ceph_tpu.osd.osd import OSD
        c = await _ec_cluster()
        try:
            rng = np.random.default_rng(7)
            base = rng.integers(0, 256, 4 * 8192,
                                dtype=np.uint8).tobytes()
            await c.osd_op("ecpool", "kv", [
                {"op": "writefull", "data": base}])
            await _read(c, "kv")                 # warm every cache
            pgid, primary, up = c.target_for("ecpool", "kv")
            victim = next(o for o in c.osds
                          if o.whoami in up and o.whoami != primary)
            vid, vuuid, vstore, vhost = (victim.whoami, victim.uuid,
                                         victim.store, victim.host)
            assert victim.shard_cache is not None
            assert len(victim.shard_cache) > 0, "victim never cached"
            await victim.stop()
            c.osds = [o for o in c.osds if o.whoami != vid]
            for _ in range(100):
                if not c.mon.osdmap.is_up(vid):
                    break
                await asyncio.sleep(0.2)
            # overwrite while the victim is down
            new = rng.integers(0, 256, 4 * 8192,
                               dtype=np.uint8).tobytes()
            await c.osd_op("ecpool", "kv", [
                {"op": "writefull", "data": new}])
            # revive on the same store: fresh OSD, fresh (empty) cache
            revived = OSD(uuid=vuuid, whoami=vid, store=vstore,
                          host=vhost)
            await revived.start(c.mon.msgr.addr)
            c.osds.append(revived)
            assert revived.shard_cache is not None
            assert len(revived.shard_cache) == 0, \
                "revived OSD must start cold"
            for _ in range(150):
                if c.mon.osdmap.is_up(vid):
                    break
                await asyncio.sleep(0.2)
            # wait for recovery to repush, then every read (including
            # ones served by the revived shard) returns the NEW bytes
            for _ in range(50):
                if await _read(c, "kv") == new:
                    break
                await asyncio.sleep(0.2)
            assert await _read(c, "kv") == new
        finally:
            await c.stop()
    run(main())


# -- cluster: the steady phases move no shard bytes through the store --------

@pytest.mark.parametrize("phase", ["read", "scrub", "degraded_read"])
def test_cached_steady_phase_reads_no_shard_bytes_from_the_store(phase):
    """After full-stripe writes every acting shard is resident, so a
    read pass, a deep scrub of every PG and a degraded read pass (one
    OSD stopped and marked down) each hit the cache and read ZERO
    shard bytes from the store, by the ``datapath`` counters; every
    byte read equals what was written.  The control makes the zero
    mean something: with residency dropped the same pass returns the
    same bytes and the same counters count the store reads.  Each
    write batch is one device launch (``ec_batch``)."""
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster
    from ceph_tpu.osd.scrub import scrub_pg

    async def main():
        cluster = await SimCluster.create(4)
        rados = await Rados(cluster.addr, name="client.dp").connect()
        try:
            await rados.mon_command(
                "osd erasure-code-profile set",
                {"name": "prof", "profile": {
                    "plugin": "tpu", "k": "2", "m": "1",
                    "technique": "reed_sol_van"}})
            await rados.pool_create("ecpool", pg_num=8,
                                    pool_type="erasure",
                                    erasure_code_profile="prof")
            io = await rados.open_ioctx("ecpool")
            rng = np.random.default_rng(14)
            objs = {f"o{i}": rng.integers(0, 256, 3 * 8192 + 100,
                                          dtype=np.uint8).tobytes()
                    for i in range(12)}
            await asyncio.gather(*(io.write_full(oid, data)
                                   for oid, data in objs.items()))
            batch = cluster.perf_counters("ec_batch")
            assert batch["batches"] == batch["mesh_launches"] > 0

            async def one_pass():
                if phase == "scrub":
                    pgs = [pg for osd in cluster.osds
                           if not osd.is_stopped()
                           for pg in osd.pgs.values()
                           if pg.is_primary()
                           and pg.pool.name == "ecpool"]
                    results = [await scrub_pg(pg) for pg in pgs]
                    assert all(r.clean for r in results)
                    assert sum(r.objects_scrubbed
                               for r in results) == len(objs)
                    return None
                return {oid: await io.read(oid) for oid in objs}

            def counters():
                got = cluster.perf_counters("datapath")
                return [got.get(key, 0) for key in
                        ("hits", "host_reads", "host_bytes_read")]

            if phase == "degraded_read":
                await cluster.kill_osd(3)
                assert await cluster.wait_down(3)
                degraded0 = cluster.perf_counters("ec_degraded").get(
                    "degraded_reads", 0)
            hits0, reads0, bytes0 = counters()
            cached = await one_pass()
            hits1, reads1, bytes1 = counters()
            assert hits1 > hits0, "the pass never hit the cache"
            assert (reads1, bytes1) == (reads0, bytes0), \
                "a cached steady pass read shard bytes from the store"
            if phase == "degraded_read":
                assert cluster.perf_counters("ec_degraded").get(
                    "degraded_reads", 0) > degraded0
            # control: the same pass cold
            for osd in cluster.osds:
                if not osd.is_stopped():
                    osd.shard_cache.clear()
            cold = await one_pass()
            assert counters()[2] > bytes1, \
                "the counter missed the cold pass's store reads"
            if cached is not None:
                assert cached == objs and cold == objs
        finally:
            await rados.shutdown()
            await cluster.stop()
    run(main())


# -- write path: donated launches feed residency -----------------------------

def test_write_path_populates_cache_and_donation_is_safe():
    """A full-stripe write's encoded shards become resident on every
    acting OSD (with the fused-launch CRC as the entry tag), and the
    batcher's RMW launch -- whose mesh path donates/aliases the
    old-parity device buffer -- never corrupts the host arrays the
    caller still holds."""
    async def main():
        c = await _ec_cluster()
        try:
            rng = np.random.default_rng(8)
            data = rng.integers(0, 256, 3 * 8192,
                                dtype=np.uint8).tobytes()
            p0 = PERF.get("puts")
            await c.osd_op("ecpool", "w", [
                {"op": "writefull", "data": data}])
            assert PERF.get("puts") >= p0 + 3    # one per acting shard
            pgid, _, _ = c.target_for("ecpool", "w")
            for osd in c.osds:
                e = osd.shard_cache.get(f"pg_{pgid}", "w") \
                    if pgid in osd.pgs else None
                if e is not None:
                    assert e.size == len(data)
                    assert e.crc is not None
                    # the resident bytes ARE the committed bytes
                    assert bytes(e.buf) == osd.store.read(
                        f"pg_{pgid}", "w", 0, None)
        finally:
            await c.stop()
    run(main())


def test_batcher_rmw_leaves_host_inputs_intact():
    from ceph_tpu.ec import registry
    from ceph_tpu.osd.codec_batcher import CodecBatcher

    codec = registry().factory("tpu", {"k": "4", "m": "2",
                                       "technique": "reed_sol_van"})
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, (8, 4, 512), dtype=np.uint8)
    parity = np.asarray(codec.encode_batch(data, out_np=True))
    delta = np.zeros_like(data)
    delta[:, 1, :100] = rng.integers(0, 256, (8, 100),
                                     dtype=np.uint8)
    old_copy, delta_copy = parity.copy(), delta.copy()
    batcher = CodecBatcher(max_batch=32, flush_timeout=0.05)

    async def drive():
        return await batcher.rmw(codec, parity, delta)

    new_parity = asyncio.new_event_loop().run_until_complete(drive())
    # byte-exact vs a full re-encode of the delta'd data
    want = np.asarray(codec.encode_batch(data ^ delta, out_np=True))
    assert np.array_equal(new_parity, want)
    # donation happens on the DEVICE copies; the caller's host arrays
    # must be untouched
    assert np.array_equal(parity, old_copy)
    assert np.array_equal(delta, delta_copy)
