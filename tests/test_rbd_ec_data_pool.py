"""RBD on an erasure-coded data pool (rbd create --data-pool): the
image's header, directory entry and locks on a replicated pool, its
data objects on an erasure pool that takes partial-stripe overwrites.

Small random overwrites through ``Image.write`` reach the OSD as
``write`` ops with an offset and are served by the partial-stripe
pipeline (``_plan_rmw`` -> ``_submit_partial`` -> ``CodecBatcher.rmw``
-> ``MeshCodec.rmw``).  The image is held to the benchmark's plain
references: its bytes to ``reference/image.py``, every data object's
stored shards (data, parity, ``_crc``, label) to ``reference/ec.py``.
What the overwrites cost is counted: delta runs, gathers of old
content, ExtentCache hits, version-only sub-writes.
"""

from __future__ import annotations

import argparse
import asyncio
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.drivers.store_closed_loop import (        # noqa: E402
    check_shards, stored_shards)
from benchmark.reference import image as ref_image       # noqa: E402
from ceph_tpu.client.rados import Rados                  # noqa: E402
from ceph_tpu.common import tracing                      # noqa: E402
from ceph_tpu.loadgen.cluster import SimCluster          # noqa: E402
from ceph_tpu.rbd import RBD, Image, RbdError            # noqa: E402
from ceph_tpu.tools import rbd_cli                       # noqa: E402

GEOMETRIES = [pytest.param((8, 3, 12), id="k8m3-12osd"),
              pytest.param((2, 1, 4), id="k2m1-4osd")]
UNIT = 4096
ORDER = 18                       # 256 KiB objects
OBJ = 1 << ORDER
OBJECTS = 4
IO = 4096
SEED = 31
STORED_AS = {"shard_xattr": "_shard", "crc_xattr": "_crc"}
HEADER_POOL, DATA_POOL = "rbd", "rbd_data"


def run(coro, timeout: float = 120.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class Deployment:
    """A cluster with the two pools, one prefilled image and its
    reference."""

    def __init__(self, geom) -> None:
        self.k, self.m, self.n = geom
        self.profile = {"plugin": "tpu", "k": self.k, "m": self.m,
                        "technique": "reed_sol_van", "stripe_unit": UNIT}

    async def __aenter__(self) -> "Deployment":
        self.cluster = await SimCluster.create(self.n)
        self.rados = await Rados(self.cluster.addr,
                                 name="client.test").connect()
        await self.rados.mon_command("osd erasure-code-profile set", {
            "name": "prof",
            "profile": {k: str(v) for k, v in self.profile.items()}})
        await self.rados.pool_create(HEADER_POOL, pg_num=4, size=3)
        await self.rados.pool_create(DATA_POOL, pg_num=8,
                                     pool_type="erasure",
                                     erasure_code_profile="prof")
        self.hio = await self.rados.open_ioctx(HEADER_POOL)
        self.dio = await self.rados.open_ioctx(DATA_POOL)
        self.rbd = RBD()
        await self.rbd.create(self.hio, "vol", OBJECTS * OBJ, order=ORDER,
                              data_pool=self.dio)
        # the header alone names the data pool
        self.img = await Image.open(self.hio, "vol")
        self.ref = ref_image.Image(SEED, 0, OBJECTS * OBJ, OBJ)
        for o in range(OBJECTS):
            await self.img.write(o * OBJ, self.ref.object(o))
        return self

    async def __aexit__(self, *exc) -> None:
        await self.img.close()
        await self.rados.shutdown()
        await self.cluster.stop()

    async def write(self, off: int, data: bytes) -> None:
        await self.img.write(off, data)
        self.ref.write(off, data)

    def counts(self) -> dict:
        out = {}
        for name, keys in (("ec_batch", ("rmw_delta_runs", "rmw_full_runs",
                                         "mesh_rmw_launches")),
                           ("ec_pipeline", ("write_old_gathers",
                                            "writes_blind",
                                            "rmw_stripes_read",
                                            "rmw_stripes_cached",
                                            "rmw_subwrites_empty")),
                           ("ec_hedge", ("subreads",))):
            have = self.cluster.perf_counters(name)
            out.update({key: have.get(key, 0) for key in keys})
        return out

    def delta(self, before: dict) -> dict:
        now = self.counts()
        return {key: now[key] - before[key] for key in now}

    def drop_extent_caches(self) -> None:
        for osd in self.cluster.osds:
            for pg in osd.pgs.values():
                if hasattr(pg.backend, "invalidate_extents"):
                    pg.backend.invalidate_extents()

    def shard_faults(self) -> dict:
        """Every data object's k+m stored shards against the
        reference: data chunks, parity, ``_crc``, label, none missing."""
        total: dict[str, int] = {}
        for o in range(OBJECTS):
            oid = self.img._data_obj(o)
            pgid, _ = self.rados.objecter.calc_target(self.dio.pool_id, oid)
            found = stored_shards(self.cluster, pgid, oid, STORED_AS)
            assert len(found) == self.k + self.m
            for key, val in check_shards(found, self.profile,
                                         self.ref.object(o)).items():
                total[key] = total.get(key, 0) + val
        return total


@pytest.mark.parametrize("geom", GEOMETRIES)
def test_random_4k_overwrites_equal_the_references(geom):
    """Seeded random 4 KiB overwrites with repeats, each served by the
    delta path at the cost of at most one gather of old content; the
    image and every stored shard equal the plain references."""
    async def main():
        async with Deployment(geom) as d:
            sw = d.k * UNIT
            assert await d.img.read(0, OBJECTS * OBJ) == bytes(d.ref.data)
            assert not any(d.shard_faults().values())
            rng = np.random.default_rng(SEED)
            before = d.counts()
            n_writes = 48
            times: dict[int, int] = {}
            for block in rng.integers(0, 24, n_writes):      # repeats
                # 24 blocks spread over every object of the image
                block = int(block) * (OBJECTS * OBJ // IO // 24)
                times[block] = times.get(block, 0) + 1
                await d.write(block * IO, ref_image.write_payload(
                    SEED, 0, block, times[block], IO))
            assert max(times.values()) > 1
            got = d.delta(before)
            # the normal path: delta runs through the mesh launch, no
            # re-encode, nothing blind, one stripe asked for a write
            assert got["rmw_delta_runs"] == n_writes
            assert got["rmw_full_runs"] == 0 and got["writes_blind"] == 0
            assert 0 < got["mesh_rmw_launches"] <= n_writes
            assert got["rmw_stripes_read"] == n_writes
            # the primary holds a shard, so the old size costs no
            # gather; the old content costs one only where the
            # ExtentCache did not serve the stripe
            assert got["write_old_gathers"] \
                == n_writes - got["rmw_stripes_cached"]
            # one chunk of one stripe changed: the other k-1 data
            # shards take the version stamp alone
            assert got["rmw_subwrites_empty"] == n_writes * (d.k - 1)
            assert await d.img.read(0, OBJECTS * OBJ) == bytes(d.ref.data)
            assert not any(d.shard_faults().values())

            # a cold ExtentCache: every overwrite gathers once, never
            # twice; a warm one: none at all, and no sub-read but the
            # parity fetch's
            d.drop_extent_caches()
            before = d.counts()
            cold = [o * OBJ + 5 * sw + UNIT for o in range(OBJECTS)]
            for off in cold:
                await d.write(off, bytes([off % 251]) * IO)
            got = d.delta(before)
            assert got["write_old_gathers"] == len(cold)
            assert got["rmw_stripes_cached"] == 0
            before = d.counts()
            for off in cold:
                await d.write(off, bytes([off % 241 + 1]) * IO)
            got = d.delta(before)
            assert got["write_old_gathers"] == 0
            assert got["rmw_stripes_cached"] == len(cold)
            assert got["subreads"] <= len(cold) * d.m
            assert await d.img.read(0, OBJECTS * OBJ) == bytes(d.ref.data)
            assert not any(d.shard_faults().values())
    run(main())


@pytest.mark.parametrize("geom", GEOMETRIES)
def test_overwrites_that_straddle_a_chunk_a_stripe_and_an_object(geom):
    async def main():
        async with Deployment(geom) as d:
            sw = d.k * UNIT
            before = d.counts()
            rng = np.random.default_rng(SEED + 1)
            for off, n in ((UNIT - 100, 200),               # two chunks
                           (3 * sw - 100, 200),             # two stripes
                           (OBJ + sw - 2048, 4096),         # two stripes
                           (2 * OBJ - 2048, 4096),          # two objects
                           (3 * OBJ + 7, 3 * sw),           # four stripes
                           (UNIT - 100, 200)):              # a repeat
                await d.write(off, rng.bytes(n))
            got = d.delta(before)
            assert got["rmw_full_runs"] == 0 and got["writes_blind"] == 0
            # one run a touched object: the write over two objects is two
            assert got["rmw_delta_runs"] == 7
            assert got["rmw_stripes_read"] == 1 + 2 + 2 + 2 + 4 + 1
            assert await d.img.read(0, OBJECTS * OBJ) == bytes(d.ref.data)
            assert not any(d.shard_faults().values())
            # bytes outside a range are unchanged: a neighbour of every
            # write still reads as the prefill left it
            first = ref_image.first_content(SEED, 0, OBJECTS * OBJ)
            for off in (UNIT + 100, 4 * sw, OBJ + 3 * sw, 3 * OBJ - 2 * sw):
                assert await d.img.read(off, UNIT) == bytes(
                    first[off:off + UNIT])
    run(main())


def test_an_overwrite_has_a_span_for_each_of_its_stages():
    """``ec.rmw_read`` (tags: stripes asked, stripes the ExtentCache
    served), ``ec.rmw_parity`` and ``ec.encode`` under ``osd.do_op``,
    one after the other: on this path ``ec.encode`` is the launch wait
    alone."""
    async def main():
        async with Deployment((2, 1, 4)) as d:
            d.drop_extent_caches()
            t0 = time.time()
            await d.write(5 * IO, b"\x5a" * IO)
            await d.write(5 * IO, b"\xa5" * IO)
            # the rings outlive the clusters of earlier tests
            trees: dict[str, dict] = {}
            for t in tracing._TRACERS.values():
                for s in t.dump():
                    if s["start"] >= t0:
                        trees.setdefault(s["trace_id"], {}).setdefault(
                            s["name"], []).append(s)
            rmw = sorted((t for t in trees.values() if "ec.rmw_read" in t),
                         key=lambda t: t["osd.do_op"][0]["start"])
            assert len(rmw) == 2
            tags = []
            for t in rmw:
                (do_op,), (read,), (parity,), (launch,) = (
                    t[name] for name in ("osd.do_op", "ec.rmw_read",
                                         "ec.rmw_parity", "ec.encode"))
                assert do_op["start"] <= read["start"] <= read["end"] \
                    <= parity["start"] <= parity["end"] \
                    <= launch["start"] <= launch["end"] <= do_op["end"]
                assert parity["tags"]["runs"] == 1
                tags.append((read["tags"]["asked"], read["tags"]["cached"]))
            assert tags == [(1, 0), (1, 1)]
    run(main())


def test_header_and_data_live_on_their_own_pools_and_remove_clears_both(
        capsys):
    async def main():
        async with Deployment((2, 1, 4)) as d:
            stat = d.img.stat()
            assert stat["data_pool"] == DATA_POOL
            assert stat["num_objs"] == OBJECTS
            assert d.img.data_ioctx.pool_id == d.dio.pool_id
            assert d.img.ioctx.pool_id == d.hio.pool_id
            data = set(await d.dio.list_objects())
            header = set(await d.hio.list_objects())
            assert data == {d.img._data_obj(o) for o in range(OBJECTS)}
            assert {"rbd_directory", f"rbd_header.{d.img.id}"} <= header
            assert not any(oid.startswith("rbd_data.") for oid in header)
            # an image without a data pool keeps both in one pool, and
            # says so
            await d.rbd.create(d.hio, "plain", OBJ, order=ORDER)
            plain = await Image.open(d.hio, "plain")
            assert plain.stat()["data_pool"] is None
            assert plain.data_ioctx is plain.ioctx
            await plain.write(0, b"x" * IO)
            await plain.close()
            assert set(await d.dio.list_objects()) == data
            # a data pool that is gone is an error, not the header's pool
            with pytest.raises(RbdError):
                await d.rbd.create(
                    d.hio, "lost", OBJ, order=ORDER,
                    data_pool=type(d.dio)(d.rados, "nosuchpool", 999))
                await Image.open(d.hio, "lost")

            # the CLI: create --data-pool, info names it
            host, port = d.cluster.addr
            mon = f"{host}:{port}"
            assert await rbd_cli.amain(argparse.Namespace(
                mon=mon, pool=HEADER_POOL, cmd="create", image="cli",
                size="512K", order=ORDER, data_pool=DATA_POOL)) == 0
            assert await rbd_cli.amain(argparse.Namespace(
                mon=mon, pool=HEADER_POOL, cmd="info", image="cli")) == 0
            said = capsys.readouterr().out
            assert f"data objects on {DATA_POOL}" in said
            assert f"data_pool: {DATA_POOL}" in said

            await d.img.close()
            await d.rbd.remove(d.hio, "vol")
            assert "vol" not in await d.rbd.list(d.hio)
            assert set(await d.dio.list_objects()) == set()
            assert f"rbd_header.{stat['id']}" not in set(
                await d.hio.list_objects())
            # __aexit__ closes the handle again: closing twice is a no-op
    run(main())


def test_rbd_cli_help_names_the_data_pool(capsys):
    with pytest.raises(SystemExit):
        rbd_cli.main(["create", "--help"])
    said = capsys.readouterr().out
    assert "--data-pool POOL" in said and "erasure" in said
