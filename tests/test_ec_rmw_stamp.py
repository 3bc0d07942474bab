"""The shard side of a partial-stripe overwrite pays for the bytes it
changes (``ECBackend.apply_sub_write`` -> ``_plan_stamp``).

A version-only sub-write leaves the shard's bytes, resident copy and
``_crc`` alone; one that changes bytes in place updates ``_crc`` by
CRC32C's linearity (``ops/crc32c_batch.crc32c_patch``) from the old
bytes of its ranges, in the sub-write's one transaction; a shard is
re-hashed whole only where its length changes.  Held here: the update
rule against the re-hash, every stored shard against the plain
reference (bytes, ``_crc``, label) after random overwrites with the
resident copies dropped at random, the resident copies against the
stores, a deep scrub and a degraded read, the three counters, and what
a version-only sub-write asks of its store.
"""

from __future__ import annotations

import asyncio
import sys
from pathlib import Path

import numpy as np
import pytest

# the plain references and the helpers that take shards out of the
# stores sit with the benchmark; this file runs clusters, so it stays
# out of tests/benchmark_suite/
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.drivers import store_read_loop as drv           # noqa: E402
from benchmark.drivers.store_closed_loop import (              # noqa: E402
    check_shards, stored_shards)
from ceph_tpu.client.rados import Rados                        # noqa: E402
from ceph_tpu.loadgen.cluster import SimCluster                # noqa: E402
from ceph_tpu.ops.crc32c_batch import crc32c_patch             # noqa: E402
from ceph_tpu.os.device_cache import PERF as DATAPATH          # noqa: E402
from ceph_tpu.os.transaction import Transaction                    # noqa: E402
from ceph_tpu.osd.backend import (CRC_ALG, CRC_ALG_XATTR,     # noqa: E402
                                  CRC_XATTR, SHARD_XATTR, VER_XATTR,
                                  shard_crc, shard_crc_matches,
                                  ver_decode)
from ceph_tpu.osd.scrub import scrub_pg                        # noqa: E402

GEOMETRIES = [pytest.param((2, 1, 4), id="k2m1-4osd"),
              pytest.param((8, 3, 12), id="k8m3-12osd")]
UNIT = 4096
POOL, PG_NUM = "ecpool", 8
STORED_AS = {"shard_xattr": "_shard", "crc_xattr": "_crc"}
STAMPS = ("rmw_stamps_kept", "rmw_stamps_patched", "rmw_stamps_rehashed")


def run(coro, timeout: float = 180.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


# -- (a) the update rule alone ----------------------------------------------

def _disjoint_ranges(rng, length: int, n: int, ends: bool):
    """``n`` disjoint non-empty ranges of [0, length); with ``ends``
    the first starts at byte 0 and the last ends at the last byte."""
    n = min(n, length // 2) or 1
    cuts = sorted(int(c) for c in rng.choice(length + 1, 2 * n,
                                             replace=False)) \
        if length >= 2 * n else [0, length]
    if ends:
        cuts[0], cuts[-1] = 0, length
    return list(zip(cuts[::2], cuts[1::2]))


@pytest.mark.parametrize("n_ranges", [1, 2, 3, 4])
@pytest.mark.parametrize("length", [1, 7, 4095, 4096, 4097, 12345, 40000,
                                    65536])
def test_patched_crc_equals_the_rehash_of_the_patched_bytes(length,
                                                            n_ranges):
    rng = np.random.default_rng([length, n_ranges])
    for trial in range(6):
        buf = rng.integers(0, 256, length, dtype=np.uint8)
        crc = shard_crc(buf)
        new = buf.copy()
        patches = []
        ranges = _disjoint_ranges(rng, length, n_ranges, trial == 0)
        if trial == 0:                           # the first and last byte
            assert ranges[0][0] == 0 and ranges[-1][1] == length
        for lo, hi in ranges:
            data = rng.integers(0, 256, hi - lo, dtype=np.uint8).tobytes()
            if trial == 1:
                data = bytes(buf[lo:hi])         # rewritten as it was
            patches.append((buf[lo:hi], data, length - hi))
            new[lo:hi] = np.frombuffer(data, np.uint8)
        order = rng.permutation(len(patches))     # any order of ranges
        got = crc32c_patch(crc, [patches[i] for i in order])
        assert got == shard_crc(new), (length, trial, patches)
        if trial == 1:
            assert got == crc


def test_a_patch_keeps_a_stale_crc_stale():
    """Where the stored ``_crc`` did not match the stored bytes (rot
    between writes), the update keeps the mismatch visible: a re-hash
    would have stamped over it."""
    rng = np.random.default_rng(5)
    buf = rng.integers(0, 256, 3 * UNIT, dtype=np.uint8)
    new = buf.copy()
    new[UNIT:2 * UNIT] ^= 0xFF
    stale = shard_crc(buf) ^ 0x00010000
    got = crc32c_patch(stale, [(buf[UNIT:2 * UNIT],
                                bytes(new[UNIT:2 * UNIT]), UNIT)])
    assert got == shard_crc(new) ^ 0x00010000
    assert crc32c_patch(stale, []) == stale


# -- clusters -----------------------------------------------------------------

class Pool:
    """A cluster with one erasure pool and a client on it."""

    def __init__(self, geom) -> None:
        self.k, self.m, self.n = geom
        self.sw = self.k * UNIT
        self.profile = {"plugin": "tpu", "k": self.k, "m": self.m,
                        "technique": "reed_sol_van", "stripe_unit": UNIT}

    async def __aenter__(self) -> "Pool":
        self.cluster = await SimCluster.create(self.n)
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

    def holders(self, oid: str):
        """(shard, osd, pg) of every live OSD that serves the object."""
        pgid = self.pgid(oid)
        for osd in self.cluster.osds:
            pg = osd.pgs.get(pgid)
            if pg is not None and not osd.is_stopped() \
                    and osd.whoami in pg.acting:
                yield pg.acting.index(osd.whoami), osd, pg

    def stamps(self) -> dict:
        have = self.cluster.perf_counters("ec_pipeline")
        return {key: have.get(key, 0) for key in STAMPS}

    def stamps_since(self, before: dict) -> dict:
        return {key: val - before[key] for key, val in self.stamps().items()}

    def shard_faults(self, oid: str, payload: bytes) -> dict:
        found = stored_shards(self.cluster, self.pgid(oid), oid, STORED_AS)
        assert len(found) == self.k + self.m
        return check_shards(found, self.profile, payload)


@pytest.mark.parametrize("geom", GEOMETRIES)
def test_stamps_stay_true_through_random_overwrites_in_place(geom):
    """50 random overwrites inside four objects, the resident copies
    dropped at random so that both the resident and the ranged-read
    side of the update run: every stored shard equals the reference
    with a ``_crc`` that is the re-hash of its bytes and its own
    label, no shard was re-hashed, a resident entry is the stored
    shard under the newest version, a deep scrub verifies by the tags
    alone, and a degraded read returns the object."""
    async def main():
        async with Pool(geom) as p:
            rng = np.random.default_rng([31, p.k])
            size = 6 * p.sw + 100                # a ragged seventh stripe
            refs = {f"obj-{i}": bytearray(rng.bytes(size)) for i in range(4)}
            for oid, ref in refs.items():
                await p.io.write_full(oid, bytes(ref))
            before = p.stamps()
            for n in range(50):
                oid = f"obj-{int(rng.integers(4))}"
                for _, osd, pg in p.holders(oid):
                    if rng.random() < 0.5:
                        osd.shard_cache.invalidate(pg.coll, oid)
                ln = int(rng.integers(1, 2 * p.sw)) if n % 5 else UNIT
                off = int(rng.integers(0, size - ln + 1))
                if n % 5 == 0:                   # one chunk, aligned
                    off -= off % UNIT
                data = rng.bytes(ln)
                await p.io.write(oid, data, off)
                refs[oid][off:off + ln] = data
            got = p.stamps_since(before)
            assert got["rmw_stamps_rehashed"] == 0
            assert got["rmw_stamps_patched"] >= 50 * (1 + p.m)
            assert got["rmw_stamps_kept"] + got["rmw_stamps_patched"] \
                == 50 * (p.k + p.m)
            assert got["rmw_stamps_kept"] >= 10 * (p.k - 1)

            resident = absent = 0
            for oid, ref in refs.items():
                assert await p.io.read(oid) == bytes(ref)
                assert not any(p.shard_faults(oid, bytes(ref)).values())
                vers = set()
                for shard, osd, pg in p.holders(oid):
                    raw = osd.store.read(pg.coll, oid, 0, None)
                    attrs = osd.store.getattrs(pg.coll, oid)
                    assert int(attrs[CRC_XATTR]) == shard_crc(raw)
                    assert int(attrs[SHARD_XATTR]) == shard
                    vers.add(ver_decode(attrs[VER_XATTR]))
                    entry = osd.shard_cache._lru.get((pg.coll, oid))
                    if entry is None:
                        absent += 1
                        continue
                    resident += 1
                    assert entry.buf.tobytes() == raw
                    assert entry.crc == int(attrs[CRC_XATTR])
                    assert entry.shard == shard
                    assert entry.ver == ver_decode(attrs[VER_XATTR])
                    assert entry.size == size
                assert len(vers) == 1            # the newest, everywhere
            assert resident and absent

            fast = DATAPATH.get("scrub_fast_verifies")
            pgids = {p.pgid(oid) for oid in refs}
            for osd in p.cluster.osds:
                for pg in osd.pgs.values():
                    if pg.is_primary() and pg.pgid in pgids:
                        res = await scrub_pg(pg)
                        assert res.clean, res.to_dict()
            assert DATAPATH.get("scrub_fast_verifies") - fast == len(refs)

            victim = next(osd.whoami for shard, osd, _ in p.holders("obj-0")
                          if shard == 1)
            await drv._fail_victim(p.cluster, victim, PG_NUM, timeout=30.0)
            for oid, ref in refs.items():
                assert await asyncio.wait_for(p.io.read(oid), 30.0) \
                    == bytes(ref)
    run(main())


# -- (c) the counters -----------------------------------------------------------

@pytest.mark.parametrize("geom", GEOMETRIES)
def test_an_overwrite_inside_a_chunk_keeps_and_patches_and_never_rehashes(
        geom):
    async def main():
        async with Pool(geom) as p:
            payload = bytearray(np.random.default_rng(3).bytes(4 * p.sw))
            await p.io.write_full("obj", bytes(payload))
            before = p.stamps()
            off = 2 * p.sw + UNIT + 7
            await p.io.write("obj", b"\x5a" * 100, off)
            payload[off:off + 100] = b"\x5a" * 100
            assert p.stamps_since(before) == {
                "rmw_stamps_kept": p.k - 1,
                "rmw_stamps_patched": 1 + p.m,
                "rmw_stamps_rehashed": 0}
            assert not any(p.shard_faults("obj", bytes(payload)).values())
    run(main())


@pytest.mark.parametrize("geom", GEOMETRIES)
def test_a_write_that_changes_a_shards_length_rehashes_it(geom):
    """An append past the last stripe and a truncate reach the ranged
    branch with another ``shard_len``: the counted whole-shard path."""
    async def main():
        async with Pool(geom) as p:
            rng = np.random.default_rng(4)
            payload = bytearray(rng.bytes(2 * p.sw))
            await p.io.write_full("obj", bytes(payload))
            before = p.stamps()
            tail = rng.bytes(p.sw + 10)
            await p.io.append("obj", tail)
            payload += tail
            got = p.stamps_since(before)
            assert got["rmw_stamps_rehashed"] > 0
            assert sum(got.values()) == p.k + p.m
            assert await p.io.read("obj") == bytes(payload)
            assert not any(p.shard_faults("obj", bytes(payload)).values())
            # and in place again, on the grown shards
            before = p.stamps()
            await p.io.write("obj", b"\xa5" * UNIT, 2 * p.sw)
            payload[2 * p.sw:2 * p.sw + UNIT] = b"\xa5" * UNIT
            assert p.stamps_since(before) == {
                "rmw_stamps_kept": p.k - 1,
                "rmw_stamps_patched": 1 + p.m,
                "rmw_stamps_rehashed": 0}
            assert not any(p.shard_faults("obj", bytes(payload)).values())
    run(main())


@pytest.mark.parametrize("resident", [True, False],
                         ids=["resident", "not-resident"])
def test_a_tag_of_unknown_polynomial_is_rehashed_not_patched(resident):
    """A store written before the integrity pipeline unified holds
    zlib.crc32 tags, which still verify (``shard_crc_matches``) and
    carry no ``_crc_alg``.  A CRC32C delta XORed into one would match
    neither polynomial and fail k+m-7 shards of a sound object: the
    overwrite re-hashes such a shard whole (counted), which stamps
    CRC32C and the marker, and the next overwrite is in place."""
    import zlib

    async def main():
        async with Pool((8, 3, 12)) as p:
            rng = np.random.default_rng(8)
            payload = bytearray(rng.bytes(4 * p.sw))
            await p.io.write_full("obj", bytes(payload))
            for _, osd, pg in p.holders("obj"):
                assert osd.store.getattr(pg.coll, "obj",
                                         CRC_ALG_XATTR) == CRC_ALG
                raw = osd.store.read(pg.coll, "obj", 0, None)
                txn = Transaction()
                txn.setattr(pg.coll, "obj", CRC_XATTR,
                            str(zlib.crc32(raw) & 0xFFFFFFFF).encode())
                txn.rmattr(pg.coll, "obj", CRC_ALG_XATTR)
                osd.store.queue_transaction(txn)
                osd.shard_cache.invalidate(pg.coll, "obj")
            if resident:
                # read-through fills carry the stored tag as it is
                assert await p.io.read("obj") == bytes(payload)
                assert any((pg.coll, "obj") in osd.shard_cache
                           for _, osd, pg in p.holders("obj"))
            before = p.stamps()
            off = p.sw + 2 * UNIT
            await p.io.write("obj", b"\x3c" * UNIT, off)
            payload[off:off + UNIT] = b"\x3c" * UNIT
            assert p.stamps_since(before) == {
                "rmw_stamps_kept": 0, "rmw_stamps_patched": 0,
                "rmw_stamps_rehashed": p.k + p.m}
            for _, osd, pg in p.holders("obj"):
                osd.shard_cache.invalidate(pg.coll, "obj")
                raw = osd.store.read(pg.coll, "obj", 0, None)
                attrs = osd.store.getattrs(pg.coll, "obj")
                assert shard_crc_matches(raw, int(attrs[CRC_XATTR]))
                assert int(attrs[CRC_XATTR]) == shard_crc(raw)
                assert attrs[CRC_ALG_XATTR] == CRC_ALG
            assert await p.io.read("obj") == bytes(payload)
            assert not any(p.shard_faults("obj", bytes(payload)).values())
            for _, osd, pg in p.holders("obj"):
                if pg.is_primary():
                    res = await scrub_pg(pg)
                    assert res.clean, res.to_dict()
            before = p.stamps()
            await p.io.write("obj", b"\xc3" * UNIT, off)
            payload[off:off + UNIT] = b"\xc3" * UNIT
            assert p.stamps_since(before) == {
                "rmw_stamps_kept": p.k - 1,
                "rmw_stamps_patched": 1 + p.m,
                "rmw_stamps_rehashed": 0}
            assert not any(p.shard_faults("obj", bytes(payload)).values())
    run(main())


# -- (d) what a version-only sub-write asks of its store ------------------------

@pytest.mark.parametrize("resident", [True, False],
                         ids=["resident", "not-resident"])
def test_a_version_only_sub_write_is_one_transaction_and_reads_nothing(
        resident):
    async def main():
        async with Pool((2, 1, 4)) as p:
            payload = bytearray(np.random.default_rng(6).bytes(4 * p.sw))
            await p.io.write_full("obj", bytes(payload))
            # chunk 0 of a stripe changes: shard 1 takes the version alone
            (osd, pg), = [(o, g) for s, o, g in p.holders("obj") if s == 1]
            assert not pg.is_primary()
            store, backend = osd.store, pg.backend
            if not resident:
                osd.shard_cache.invalidate(pg.coll, "obj")
            assert ((pg.coll, "obj") in osd.shard_cache) == resident
            old = {name: store.getattr(pg.coll, "obj", name)
                   for name in (CRC_XATTR, SHARD_XATTR, VER_XATTR)}
            seen = {"txns": [], "read_bytes": 0, "applied": []}
            inner = {"read": store.read,
                     "queue_transaction": store.queue_transaction,
                     "apply": backend.apply_sub_write}
            inside = []

            def read(*a, **kw):
                raw = inner["read"](*a, **kw)
                if inside:
                    seen["read_bytes"] += len(raw)
                return raw

            def queue_transaction(txn):
                if inside:
                    seen["txns"].append([op.op for op in txn.ops])
                return inner["queue_transaction"](txn)

            def apply(entry, w, segs, attr_muts, shard=None):
                inside.append(1)
                try:
                    seen["applied"].append((w, shard))
                    return inner["apply"](entry, w, segs, attr_muts,
                                          shard=shard)
                finally:
                    inside.pop()

            store.read, store.queue_transaction = read, queue_transaction
            backend.apply_sub_write = apply
            before = p.stamps()
            await p.io.write("obj", b"\x77" * UNIT, p.sw)
            payload[p.sw:p.sw + UNIT] = b"\x77" * UNIT
            (w, shard), = seen["applied"]
            assert shard == 1 and w["writes"] == []
            assert seen["read_bytes"] == 0
            assert len(seen["txns"]) == 1
            assert not {"write", "truncate", "zero"} & set(seen["txns"][0])
            assert p.stamps_since(before)["rmw_stamps_kept"] == 1
            # bytes, ``_crc`` and label stand as stored under the new version
            now = {name: store.getattr(pg.coll, "obj", name)
                   for name in old}
            assert now[CRC_XATTR] == old[CRC_XATTR]
            assert now[SHARD_XATTR] == old[SHARD_XATTR] == b"1"
            assert ver_decode(now[VER_XATTR]) > ver_decode(old[VER_XATTR])
            entry = osd.shard_cache._lru.get((pg.coll, "obj"))
            assert (entry is not None) == resident
            if resident:
                assert entry.ver == ver_decode(now[VER_XATTR])
                assert entry.crc == int(old[CRC_XATTR])
                assert entry.buf.tobytes() == inner["read"](
                    pg.coll, "obj", 0, None)
            assert await p.io.read("obj") == bytes(payload)
            assert not any(p.shard_faults("obj", bytes(payload)).values())
    run(main())
