"""Deep scrub of an erasure pool, shard-local and chunk by chunk
(``ceph_tpu/osd/scrub.py``, ``PG.scrub_chunk_begin``).

Held here: the program's reports against the plain reference
(``benchmark/reference/scrub.py``) for each kind of planted fault on
data and parity positions, and the bytes a repair leaves; no false
report while writers run, on fresh names and on names inside the
scrubbing chunk; a write inside the chunk's range waits and one
outside it does not, with the PG's lock free while the maps are out;
maps on the wire, not shards; both digest routes on the same shard at
512 KiB; the comparison's rules on hand-made maps; what a scheduled
scrub keeps.
"""

from __future__ import annotations

import asyncio
import sys
from pathlib import Path

import numpy as np
import pytest

# the plain reference sits with the benchmark; this file runs clusters,
# so it stays out of tests/benchmark_suite/
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.reference import ec, scrub as ref               # noqa: E402
from ceph_tpu import native                                    # noqa: E402
from ceph_tpu.client.rados import Rados                        # noqa: E402
from ceph_tpu.common import tracing                            # noqa: E402
from ceph_tpu.common.perf import PerfCounters                  # noqa: E402
from ceph_tpu.loadgen.cluster import SimCluster                # noqa: E402
from ceph_tpu.os.device_cache import DeviceShardCache          # noqa: E402
from ceph_tpu.os.store import MemStore                         # noqa: E402
from ceph_tpu.os.transaction import Transaction                # noqa: E402
from ceph_tpu.osd import scrub as scrub_mod                    # noqa: E402
from ceph_tpu.osd.codec_batcher import CodecBatcher            # noqa: E402
from ceph_tpu.osd.scrub import (build_shard_map,              # noqa: E402
                                compare_shard_maps, scrub_pg)

GEOMETRIES = [pytest.param((2, 1, 4), id="k2m1-4osd"),
              pytest.param((8, 3, 12), id="k8m3-12osd")]
UNIT = 4096
POOL, PG_NUM = "ecpool", 4
SEED = 11


def run(coro, timeout: float = 240.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class Pool:
    """A cluster with one erasure pool and a client on it."""

    def __init__(self, geom, osd_config: dict | None = None) -> None:
        self.k, self.m, self.n = geom
        self.size = 3 * self.k * UNIT + 100      # a ragged fourth row
        self.osd_config = osd_config
        self.profile = {"plugin": "tpu", "k": self.k, "m": self.m,
                        "technique": "reed_sol_van", "stripe_unit": UNIT}

    async def __aenter__(self) -> "Pool":
        self.cluster = await SimCluster.create(
            self.n, osd_config=self.osd_config)
        self.rados = await Rados(self.cluster.addr,
                                 name="client.test").connect()
        await self.rados.mon_command("osd erasure-code-profile set", {
            "name": "prof",
            "profile": {k: str(v) for k, v in self.profile.items()}})
        await self.rados.pool_create(POOL, pg_num=PG_NUM,
                                     pool_type="erasure",
                                     erasure_code_profile="prof")
        self.io = await self.rados.open_ioctx(POOL)
        return self

    async def __aexit__(self, *exc) -> None:
        await self.rados.shutdown()
        await self.cluster.stop()

    def pgid(self, oid: str) -> str:
        return self.rados.objecter.calc_target(self.io.pool_id, oid)[0]

    def primaries(self):
        """(osd, pg) of every PG of the pool, at its primary."""
        for osd in self.cluster.osds:
            for pg in osd.pgs.values():
                if pg.is_primary() and pg.pool.name == POOL:
                    yield osd, pg

    def holder(self, oid: str, shard: int):
        pgid = self.pgid(oid)
        for osd in self.cluster.osds:
            pg = osd.pgs.get(pgid)
            if pg is not None and osd.whoami in pg.acting \
                    and pg.acting.index(osd.whoami) == shard:
                return osd, pg
        raise AssertionError(f"no holder of {oid} shard {shard}")

    async def populate(self, n: int) -> None:
        for i in range(n):
            await self.io.write_full(
                f"obj-{i}", ref.object_bytes(SEED, i, self.size))

    def plant(self, fault: dict) -> None:
        """One fault of the reference's list, through the store that
        holds the shard (so the shard cache drops its copy)."""
        osd, pg = self.holder(fault["oid"], fault["shard"])
        txn = Transaction()
        if fault["kind"] == "missing_shard":
            txn.remove(pg.coll, fault["oid"])
        elif fault["kind"] == "tag_rot":
            txn.setattr(pg.coll, fault["oid"], "_crc",
                        str(fault["crc"]).encode())
        else:
            raw = bytes(osd.store.read(pg.coll, fault["oid"], 0, None))
            txn.write(pg.coll, fault["oid"], fault["offset"],
                      ref.rotted(raw, fault["offset"]))
        osd.store.queue_transaction(txn)

    async def scrub_all(self, repair: bool) -> list:
        return [await scrub_pg(pg, repair=repair)
                for _, pg in list(self.primaries())]

    def scrub_counters(self) -> dict:
        return self.cluster.perf_counters("scrub")


# -- (a) against the reference ------------------------------------------------

@pytest.mark.parametrize("kind", ref.KINDS)
@pytest.mark.parametrize("geom", GEOMETRIES)
def test_scrub_reports_exactly_the_planted_faults_and_repairs_them(
        geom, kind):
    """Three faults of one kind on three objects: the union of the
    PGs' reports is the reference's set, no other shard among them;
    after the repairing scrub each faulted shard is the generator's
    for its object, with its label and a matching ``_crc``, and a
    third scrub is clean."""
    async def main():
        async with Pool(geom) as p:
            n_obj = 16
            await p.populate(n_obj)
            faults = [f for f in ref.plant(SEED, p.profile, n_obj, p.size,
                                           3) if f["kind"] == kind]
            assert len(faults) == 3
            if kind == "data_rot":
                assert all(f["shard"] < p.k for f in faults)
            if kind == "parity_rot":
                assert all(f["shard"] >= p.k for f in faults)
            for f in faults:
                p.plant(f)
            found = await p.scrub_all(repair=False)
            reported = [e for r in found for e in r.errors]
            assert ref.check_reports(reported, faults) == {
                "missed": 0, "false_reports": 0}, reported
            assert p.scrub_counters()["shards_repaired"] == 0
            fixed = await p.scrub_all(repair=True)
            assert sorted(e for r in fixed for e in r.errors) \
                == sorted(reported)
            assert sorted(s for r in fixed for s in r.shards_repaired) \
                == sorted((f["oid"], f["shard"]) for f in faults)
            assert not any(r.unrepaired for r in fixed)
            for f in faults:
                osd, pg = p.holder(f["oid"], f["shard"])
                raw, crc, label = ref.repaired_shard(SEED, p.profile, f,
                                                     p.size)
                assert bytes(osd.store.read(pg.coll, f["oid"], 0,
                                            None)) == raw
                assert int(osd.store.getattr(pg.coll, f["oid"],
                                             "_crc")) == crc
                assert int(osd.store.getattr(pg.coll, f["oid"],
                                             "_shard")) == label
                assert await p.io.read(f["oid"]) == ref.object_bytes(
                    SEED, f["index"], p.size)
            assert all(r.clean for r in await p.scrub_all(repair=False))
            c = p.scrub_counters()
            assert c["errors_found"] == 6 and c["shards_repaired"] == 3
    run(main())


# -- (b) no false report under load -------------------------------------------

@pytest.mark.parametrize("geom", GEOMETRIES)
def test_no_false_report_while_writers_run(geom):
    """Writers loop ``write_full`` on fresh names and on names of the
    population (so on names inside whatever chunk is being scrubbed)
    while every PG is scrubbed round and round in chunks of 2: every
    scrub is clean, every write is acknowledged, every object reads
    back, and some write did wait for a chunk."""
    async def main():
        async with Pool(geom, {"osd_scrub_chunk_max": 2}) as p:
            n_obj = 12
            await p.populate(n_obj)
            latest = {f"obj-{i}": ref.object_bytes(SEED, i, p.size)
                      for i in range(n_obj)}
            stop = False
            rng = np.random.default_rng(5)

            async def writer(w: int) -> None:
                n = 0
                while not stop:
                    n += 1
                    data = rng.bytes(p.size)
                    await p.io.write_full(f"new-{w}-{n}", data)
                    latest[f"new-{w}-{n}"] = data

            # one rewriter a name: w rewrites obj-<i> with i = w mod 3
            async def rewriter(w: int) -> None:
                while not stop:
                    oid = f"obj-{3 * int(rng.integers(n_obj // 3)) + w}"
                    data = rng.bytes(p.size)
                    await p.io.write_full(oid, data)
                    latest[oid] = data

            tasks = [asyncio.ensure_future(writer(w)) for w in range(3)] \
                + [asyncio.ensure_future(rewriter(w)) for w in range(3)]
            rounds = []
            try:
                for _ in range(3):
                    rounds += await p.scrub_all(repair=False)
            finally:
                stop = True
                await asyncio.gather(*tasks)
            assert all(r.clean for r in rounds), [
                r.to_dict() for r in rounds if not r.clean]
            assert sum(r.chunks for r in rounds) > len(rounds)
            assert len(latest) > n_obj
            for oid, data in latest.items():
                assert await p.io.read(oid) == data, oid
            assert p.scrub_counters()["writes_blocked"] > 0
            assert all(r.clean for r in await p.scrub_all(repair=False))
    run(main())


# -- (c) what a chunk blocks ----------------------------------------------------

def test_a_write_inside_the_chunk_waits_and_one_outside_does_not():
    """While a chunk's maps are out (a replica holds its answer back)
    the PG's lock is free, a write to a name outside the chunk's range
    is acknowledged and a write to a name inside it is not; it is
    acknowledged once the chunk is compared, and the scrub saw neither
    of them half done."""
    async def main():
        async with Pool((2, 1, 4), {"osd_scrub_chunk_max": 2}) as p:
            # six names of one PG, in listing order
            names, i = [], 0
            pgid = p.pgid("a-0")
            while len(names) < 6:
                if p.pgid(f"a-{i}") == pgid:
                    names.append(f"a-{i}")
                i += 1
            names.sort()
            for oid in names:
                await p.io.write_full(oid, b"v1" * 5000)
            osd, pg = next((o, g) for o, g in p.primaries()
                           if g.pgid == pgid)
            replica = next(o for o in p.cluster.osds
                           if o.whoami in pg.acting_peers())
            asked, release = asyncio.Event(), asyncio.Event()
            answer = replica._h_pg_scrub_map_req

            async def held_back(conn, msg):
                asked.set()
                await release.wait()
                await answer(conn, msg)

            replica._h_pg_scrub_map_req = held_back
            scrub = asyncio.ensure_future(scrub_pg(pg))
            await asyncio.wait_for(asked.wait(), 20)
            # the first chunk is names[0:2]: its range is open to reads,
            # shut to writes; the PG's lock is nobody's
            assert pg.scrub_blocks(names[0]) and pg.scrub_blocks(names[1])
            assert not pg.scrub_blocks(names[2])
            assert not pg.lock.locked()
            inside = asyncio.ensure_future(
                p.io.write_full(names[1], b"v2" * 5000))
            await asyncio.wait_for(
                p.io.write_full(names[4], b"v2" * 5000), 20)
            assert await asyncio.wait_for(p.io.read(names[0]), 20) \
                == b"v1" * 5000
            await asyncio.sleep(0.3)
            assert not inside.done()
            blocked = osd.perf_scrub.get("writes_blocked")
            assert blocked == 1
            release.set()
            res = await asyncio.wait_for(scrub, 60)
            await asyncio.wait_for(inside, 20)
            assert res.clean and res.chunks == 3
            assert res.objects_scrubbed == 6
            assert await p.io.read(names[1]) == b"v2" * 5000
            assert pg._scrub_range is None
            assert (await scrub_pg(pg)).clean
    run(main())


def test_a_chunk_starts_when_the_writes_in_its_range_have_committed():
    """A write whose sub-writes are in flight when its name's chunk
    opens is waited for: the replicas' maps describe it whole."""
    async def main():
        async with Pool((2, 1, 4), {"osd_scrub_chunk_max": 25}) as p:
            await p.io.write_full("x", b"old" * 3000)
            pgid = p.pgid("x")
            osd, pg = next((o, g) for o, g in p.primaries()
                           if g.pgid == pgid)
            replica = next(o for o in p.cluster.osds
                           if o.whoami in pg.acting_peers())
            arrived, release = asyncio.Event(), asyncio.Event()
            apply = replica._h_ec_subop_write

            async def slow_apply(conn, msg):
                arrived.set()
                await release.wait()
                await apply(conn, msg)

            replica._h_ec_subop_write = slow_apply
            write = asyncio.ensure_future(
                p.io.write_full("x", b"new" * 3000))
            await asyncio.wait_for(arrived.wait(), 20)
            scrub = asyncio.ensure_future(scrub_pg(pg))
            await asyncio.sleep(0.3)
            assert not scrub.done() and pg.scrub_blocks("x")
            replica._h_ec_subop_write = apply
            release.set()
            res = await asyncio.wait_for(scrub, 60)
            await asyncio.wait_for(write, 20)
            assert res.clean, res.to_dict()
    run(main())


# -- (d) maps on the wire, not shards -----------------------------------------

@pytest.mark.parametrize("geom", GEOMETRIES)
def test_an_erasure_scrub_ships_maps_not_shards(geom):
    async def main():
        async with Pool(geom) as p:
            await p.populate(8)
            hedge = p.cluster.perf_counters("ec_hedge")
            msgr = p.cluster.perf_counters("msgr")["tx_bytes"]
            assert all(r.clean for r in await p.scrub_all(repair=True))
            c = p.scrub_counters()
            digested = c["bytes_digested_host"] + c["bytes_digested_device"]
            shard_len = ref.shard_bytes(p.profile, p.size)
            assert digested == 8 * (p.k + p.m) * shard_len
            assert 0 < c["map_bytes"] < 0.05 * digested
            # no sub-read, and all the frames of the scrub together
            # are a small part of what was digested
            assert p.cluster.perf_counters("ec_hedge") == hedge
            sent = p.cluster.perf_counters("msgr")["tx_bytes"] - msgr
            assert sent < 0.25 * digested
            assert c["chunks"] == PG_NUM and c["objects"] == 8
    run(main())


# -- (e) the two digest routes ------------------------------------------------

def test_device_and_host_digests_of_a_512k_shard_agree():
    """``build_shard_map`` on one store: a shard digested cold (read
    through the store, ``crc32c_batch`` on the host) and again once it
    is resident (the batcher's digest launch) gives the same CRC32C,
    the native one; resident shards of a chunk share ONE launch."""
    async def main():
        store = MemStore()
        cache = DeviceShardCache()
        store.attach_shard_cache(cache)
        rng = np.random.default_rng(9)
        blobs = {f"s{i}": rng.bytes(512 << 10) for i in range(3)}
        txn = Transaction()
        txn.create_collection("c")
        for oid, blob in blobs.items():
            txn.write("c", oid, 0, blob)
            txn.setattr("c", oid, "_shard", b"4")
            txn.setattr("c", oid, "_ver", b"3,7")
            txn.setattr("c", oid, "_crc",
                        str(native.crc32c(blob)).encode())
            txn.setattr("c", oid, "_crc_alg", b"crc32c")
        store.queue_transaction(txn)
        batch_perf, perf = PerfCounters("ec_batch"), PerfCounters("scrub")
        batcher = CodecBatcher(perf=batch_perf)
        cold = await build_shard_map(store, "c", batcher=batcher,
                                     perf=perf)
        assert perf.get("bytes_digested_host") == 3 * (512 << 10)
        assert perf.get("bytes_digested_device") == 0
        assert len(cache) == 0               # a scrub fills no cache
        for oid in ("s0", "s2"):
            cache.put("c", oid, blobs[oid], size=512 << 10, ver=(3, 7),
                      shard=4, crc=None)
        warm = await build_shard_map(store, "c", batcher=batcher,
                                     perf=perf)
        batcher.close()
        assert warm == cold
        for oid, blob in blobs.items():
            assert cold[oid] == {"size": 512 << 10, "ver": [3, 7],
                                 "shard": 4, "crc": native.crc32c(blob),
                                 "digest": native.crc32c(blob)}
        assert perf.get("bytes_digested_device") == 2 * (512 << 10)
        assert perf.get("bytes_digested_host") == 4 * (512 << 10)
        dump = batch_perf.dump()
        assert dump["digest_launches"] == 1 == dump["batches"]
        assert dump["digest_stripes"] == 2
        # a range: (s0, s1]
        ranged = await build_shard_map(store, "c", "s0", "s1")
        assert list(ranged) == ["s1"]
    run(main())


# -- (f) the comparison's rules -----------------------------------------------

def entry(shard, ver=(1, 5), size=8192, crc=77, digest=77):
    return {"size": size, "ver": list(ver), "shard": shard, "crc": crc,
            "digest": digest}


def sound(n=3):
    return {s: {"o": entry(s)} for s in range(n)}


@pytest.mark.parametrize("change,bad,suspect", [
    (lambda m: None, [], []),
    (lambda m: m[1].pop("o"), [("o", 1, "missing")], []),
    # the primary's own shard is as missing as any other
    (lambda m: m[0].pop("o"), [("o", 0, "missing")], []),
    (lambda m: m[2]["o"].update(shard=1), [("o", 2, "label")], []),
    (lambda m: m[2]["o"].update(ver=[1, 4]), [("o", 2, "version")], []),
    (lambda m: m[1]["o"].update(size=4096), [("o", 1, "size")], []),
    (lambda m: m[1]["o"].update(digest=78), [], [("o", 1, 78)]),
    (lambda m: m[1]["o"].update(crc=78), [], [("o", 1, 77)]),
    # bytes and no tag: only a rebuilt shard can vouch for them
    (lambda m: m[1]["o"].update(crc=None), [], [("o", 1, 77)]),
    # no label is no wrong label
    (lambda m: m[1]["o"].update(shard=None), [], []),
], ids=["sound", "missing", "missing_at_primary", "label", "version",
        "size", "bytes_or_tag", "tag_or_bytes", "untagged", "unlabeled"])
def test_compare_shard_maps_rules(change, bad, suspect):
    maps = sound()
    change(maps)
    got_bad, got_suspect, verified = compare_shard_maps(maps, 0)
    assert (got_bad, got_suspect) == (bad, suspect)
    assert verified == (not bad and not suspect)


def test_compare_shard_maps_sides_with_the_majority_then_the_primary():
    # two of three agree: the third is behind
    maps = sound()
    maps[0]["o"].update(ver=[1, 4])
    assert compare_shard_maps(maps, 0)[0] == [("o", 0, "version")]
    # one against one: the primary's version is the authoritative one
    maps = sound(2)
    maps[1]["o"].update(ver=[1, 6])
    assert compare_shard_maps(maps, 0)[0] == [("o", 1, "version")]
    assert compare_shard_maps(maps, 1)[0] == [("o", 0, "version")]
    # an empty object carries no tag and needs none; a stray object on
    # one shard is missing everywhere else
    maps = sound()
    for s in maps:
        maps[s]["e"] = entry(s, size=0, crc=None, digest=0xFFFFFFFF)
    maps[2]["stray"] = entry(2)
    bad, suspect, verified = compare_shard_maps(maps, 0)
    assert bad == [("stray", 0, "missing"), ("stray", 1, "missing")]
    assert suspect == [] and verified == 2


def test_next_chunk_and_names_in_range_page_through_a_collection():
    store = MemStore()
    txn = Transaction()
    txn.create_collection("c")
    names = sorted(f"o{i:04d}" for i in range(150))
    for oid in names + [scrub_mod.META_OID]:
        txn.touch("c", oid)
    store.queue_transaction(txn)
    got, cursor, chunks = [], "", 0
    while True:
        chunk, end = scrub_mod.next_chunk(store, "c", cursor, 25)
        assert chunk == scrub_mod.names_in_range(store, "c", cursor, end)
        got += chunk
        chunks += 1
        if end is None:
            break
        assert end == chunk[-1] and len(chunk) == 25
        cursor = end
    assert got == names and chunks == 6
    assert scrub_mod.next_chunk(store, "c", names[-1], 25) == ([], None)
    assert scrub_mod.names_in_range(store, "c", "o0009", "o0012") == [
        "o0010", "o0011", "o0012"]


# -- (g) what a scheduled scrub keeps ------------------------------------------

def test_scheduled_erasure_scrub_repairs_and_keeps_its_result():
    """Scheduling is off until ``osd_scrub_interval`` is set, here at
    run time through the monitor as the benchmark does; then every PG
    is scrubbed with a slot on every acting member, a planted fault is
    repaired with no manual trigger, the primary keeps the result, and
    the scrub is a ``pg.scrub`` tree in its tracer."""
    async def main():
        async with Pool((2, 1, 4), {"osd_scrub_chunk_max": 2,
                                    "osd_scrub_auto_repair": True}) as p:
            await p.populate(8)
            await asyncio.sleep(1.5)
            assert not any(o.scrub_results for o in p.cluster.osds)
            fault = {"oid": "obj-3", "index": 3, "shard": 1,
                     "kind": "data_rot", "offset": 100}
            p.plant(fault)
            # every PG is due at once and then not again in this test
            await p.rados.mon_command("config set", {
                "who": "osd", "name": "osd_scrub_interval",
                "value": 20.0})
            osd, pg = p.holder("obj-3", 0)

            def repaired() -> bool:
                kept = osd.scrub_results.get(pg.pgid)
                return bool(kept and kept["shards_repaired"])

            for _ in range(300):
                if repaired() and all(
                        g.pgid in o.scrub_results
                        for o, g in p.primaries()):
                    break
                await asyncio.sleep(0.1)
            kept = osd.scrub_results[pg.pgid]
            assert kept["errors"] == [["obj-3", 1, "bytes"]]
            assert kept["shards_repaired"] == [["obj-3", 1]]
            assert kept["stamp"] >= kept["started"] and kept["deep"]
            assert kept["inconsistent"]["obj-3"]["bad_shards"] == [1]
            holder, hpg = p.holder("obj-3", 1)
            assert bytes(holder.store.read(hpg.coll, "obj-3", 0, None)) \
                == ref.repaired_shard(SEED, p.profile, fault, p.size)[0]
            spans = tracing.get_tracer(f"osd.{osd.whoami}").dump()
            roots = [s for s in spans if s["name"] == "pg.scrub"
                     and s["tags"].get("errors") == 1]
            assert roots and roots[0]["tags"]["pgid"] == pg.pgid
            kids = [s for s in spans
                    if s["trace_id"] == roots[0]["trace_id"]]
            assert {s["name"] for s in kids} >= {
                "pg.scrub", "scrub.reserve", "scrub.chunk", "scrub.maps",
                "scrub.digest", "scrub.compare", "scrub.repair"}
            chunk = next(s for s in kids if s["name"] == "scrub.chunk")
            assert set(chunk["tags"]) == {"objects", "bytes",
                                          "blocked_writes"}
            # slots drain back
            for _ in range(100):
                if not any(o.scrub_reserver.granted
                           for o in p.cluster.osds):
                    break
                await p.rados.mon_command("config set", {
                    "who": "osd", "name": "osd_scrub_interval",
                    "value": 0})
                await asyncio.sleep(0.1)
            assert not any(o.scrub_reserver.granted
                           for o in p.cluster.osds)
    run(main())


# -- (h) whose turn it is ------------------------------------------------------

def test_scheduled_scrubs_take_turns_and_no_pg_starves():
    """Every primary has PGs due all the time (a PG is due again 0.2 s
    after its scrub) and every two PGs share OSDs, so every scrub
    contends with every other for the one slot an OSD has.  The slots
    are taken in ascending OSD id and waited for in each member's
    queue: nobody is refused, the primaries take turns, and every PG
    comes round."""
    async def main():
        async with Pool((2, 1, 4), {"osd_scrub_interval": 0.2,
                                    "osd_scrub_chunk_max": 2}) as p:
            await p.populate(8)
            pgids = {pg.pgid for _, pg in p.primaries()}
            assert len(pgids) == PG_NUM
            seen: dict[str, set] = {pgid: set() for pgid in pgids}
            for _ in range(600):
                for osd in p.cluster.osds:
                    for pgid, kept in list(osd.scrub_results.items()):
                        seen[pgid].add(kept["started"])
                if min(len(s) for s in seen.values()) >= 4:
                    break
                await asyncio.sleep(0.05)
            counts = sorted(len(s) for s in seen.values())
            assert counts[0] >= 4, counts
            # the turns are the primaries': an OSD that leads one PG
            # scrubs it as often as one that leads three scrubs them all
            turns: dict[int, int] = {}
            for osd, pg in p.primaries():
                turns[osd.whoami] = turns.get(osd.whoami, 0) \
                    + len(seen[pg.pgid])
            assert max(turns.values()) <= min(turns.values()) + 4, turns
            assert p.scrub_counters()["reserve_rejects"] == 0
            # never two scrubs at once on an OSD
            assert all(len(o.scrub_reserver.granted) <= 1
                       for o in p.cluster.osds)
            await p.rados.mon_command("config set", {
                "who": "osd", "name": "osd_scrub_interval", "value": 0})
    run(main())


def test_a_scrub_holds_only_a_prefix_of_its_slots_while_it_waits():
    """All members are asked at once; what was granted beyond the
    first busy member is given back before that member's slot is
    waited for, so a scrub never holds a higher OSD's slot while it
    waits for a lower one's (two scrubs cannot wait for each other);
    when the busy slot comes free the rest are asked again."""
    async def main():
        async with Pool((2, 1, 4)) as p:
            await p.io.write_full("o", b"x" * 9000)
            osd, pg = next((o, g) for o, g in p.primaries()
                           if g.pgid == p.pgid("o"))
            members = sorted(pg.acting)
            by_id = {o.whoami: o for o in p.cluster.osds}
            busy = by_id[members[1]]
            assert busy.scrub_reserver.get_or_fail("another-pg")
            taking = asyncio.ensure_future(
                osd._scrub_slots(pg.pgid, members))
            for _ in range(100):
                await asyncio.sleep(0.02)
                if [e[2] for e in busy.scrub_reserver._queue] == [pg.pgid]:
                    break
            assert not taking.done()
            held = [m for m in members
                    if pg.pgid in by_id[m].scrub_reserver.granted]
            assert held == members[:1]
            busy.scrub_reserver.release("another-pg")
            assert await asyncio.wait_for(taking, 10) is True
            assert all(pg.pgid in by_id[m].scrub_reserver.granted
                       for m in members)
            await osd._scrub_give_back(pg.pgid, members)
            assert not any(by_id[m].scrub_reserver.granted
                           or by_id[m].scrub_reserver._queue
                           for m in members)
            # a member that stays busy past the wait: nothing is kept
            assert busy.scrub_reserver.get_or_fail("another-pg")
            from ceph_tpu.osd import osd as osd_mod
            osd_mod.SCRUB_RESERVE_WAIT, kept = 0.3, \
                osd_mod.SCRUB_RESERVE_WAIT
            try:
                assert await osd._scrub_slots(pg.pgid, members) is False
            finally:
                osd_mod.SCRUB_RESERVE_WAIT = kept
            await osd._scrub_give_back(pg.pgid, members)
            assert not any(pg.pgid in by_id[m].scrub_reserver.granted
                           for m in members)
    run(main())


def test_a_queued_scrub_slot_request_is_served_in_turn_or_taken_back():
    """The reserver a scrub slot is asked of: requests wait first come,
    first served; a remote grant carries a lease; ``cancel`` (what
    ``scrub_release`` does) takes a request that is still queued out of
    the queue, and the task that waited sees its request cancelled,
    not itself."""
    from ceph_tpu.common.reserver import AsyncReserver

    async def main():
        r = AsyncReserver(1)
        await r.request("a", lease=120.0)
        assert r.granted == {"a"} and "a" in r._leases
        order = []

        async def want(item):
            try:
                await r.request(item, timeout=5, lease=120.0)
                order.append(item)
            except asyncio.CancelledError:
                assert not asyncio.current_task().cancelling()
                order.append(f"{item} taken back")

        waiting = [asyncio.ensure_future(want(i)) for i in "bcd"]
        await asyncio.sleep(0)
        assert not order
        r.cancel("c")                   # its primary gave up
        await asyncio.sleep(0)
        assert order == ["c taken back"]
        r.release("a")
        await asyncio.sleep(0)
        assert order == ["c taken back", "b"] and r.granted == {"b"}
        r.release("b")
        await asyncio.gather(*waiting)
        assert order == ["c taken back", "b", "d"]
        assert r.granted == {"d"} and set(r._leases) == {"d"}
        with pytest.raises(asyncio.TimeoutError):
            await r.request("e", timeout=0.05)
        assert r.granted == {"d"} and not r._queue
    run(main())
