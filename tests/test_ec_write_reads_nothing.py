"""A write on an erasure pool reads old state only when its result
depends on it.

A vector whose first content mutation is ``truncate 0`` or ``remove``
(what ``writefull`` and ``remove`` resolve to) is a function of the
vector alone: it issues no gather of the old object, whatever the
shards hold.  Any other vector asks for the old size at most once, and
for the old content only where that size is not 0.  Counted through
the hedger's ``subreads`` (every remote ``ec_subop_read`` of a gather)
and the ``ec_pipeline`` counters ``write_old_gathers`` / ``writes_blind``;
the bytes are held to a plain ``bytearray`` model of the same ops.
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.os.transaction import Transaction
from ceph_tpu.osd.backend import (
    CRC_XATTR, SHARD_XATTR, SIZE_XATTR, VER_XATTR, shard_crc)

from test_osd_cluster import make_cluster, read_result, run


GEOMETRIES = [pytest.param((2, 1, 4), id="k2m1-4osd"),
              pytest.param((4, 2, 7), id="k4m2-7osd")]
POOL = "ecpool"


async def _cluster(geom, **osd_config):
    k, m, n = geom
    c = await make_cluster(
        n, mon_config={"mon_osd_down_out_interval": 3600.0},
        osd_config=osd_config or None)
    await c.command("osd erasure-code-profile set",
                    {"name": "prof",
                     "profile": {"plugin": "tpu", "k": str(k),
                                 "m": str(m),
                                 "technique": "reed_sol_van"}})
    await c.command("osd pool create",
                    {"name": POOL, "type": "erasure", "pg_num": 4,
                     "erasure_code_profile": "prof"})
    return c


def _payload(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _counts(c) -> dict:
    """What the live OSDs counted so far: remote sub-reads of every
    gather, and what writes read of the old object."""
    live = [o for o in c.osds if not o._stopped]
    out = {"subreads": sum(o.perf.get("ec_hedge").get("subreads")
                           for o in live)}
    for key in ("write_old_gathers", "writes_blind"):
        out[key] = sum(o.perf.get("ec_pipeline").get(key) for o in live)
    return out


def _delta(c, before: dict) -> dict:
    now = _counts(c)
    return {key: now[key] - before[key] for key in now}


def _primary_pg(c, oid):
    pgid, primary, _ = c.target_for(POOL, oid)
    osd = next(o for o in c.osds if o.whoami == primary)
    return osd, osd.pgs[pgid]


def _remote_data_shards(c, oid) -> int:
    """Sub-reads one gather of the old object costs: the data
    positions the primary does not hold itself."""
    osd, pg = _primary_pg(c, oid)
    dpos = pg.backend.sinfo.data_positions(pg.backend.codec)
    return sum(1 for p in dpos if pg.acting[p] != osd.whoami)


async def _op(c, oid, ops):
    reply = await c.osd_op(POOL, oid, ops)
    assert not reply.data.get("err"), reply.data
    return reply


async def _read(c, oid) -> bytes:
    reply = await c.osd_op(POOL, oid, [{"op": "read"}])
    r, data = read_result(reply)
    assert r.get("ok"), r
    return data


def _model(content: bytearray | None, ops: list[dict]) -> bytearray | None:
    """The op vector on a plain bytearray; None is "no such object"."""
    for op in ops:
        name = op["op"]
        if name == "remove":
            content = None
            continue
        if content is None:
            content = bytearray()
        if name == "writefull":
            content = bytearray(op["data"])
        elif name == "append":
            content += op["data"]
        elif name == "write":
            end = op["off"] + len(op["data"])
            content.extend(b"\0" * (end - len(content)))
            content[op["off"]:end] = op["data"]
        elif name == "truncate":
            content.extend(b"\0" * (op["size"] - len(content)))
            del content[op["size"]:]
        elif name == "zero":          # never extends the object
            end = min(op["off"] + op["len"], len(content))
            if end > op["off"]:
                content[op["off"]:end] = b"\0" * (end - op["off"])
    return content


def _assert_shards(c, oid, data: bytes, version) -> None:
    """Every up acting OSD stores the shard of ``data`` and nothing of
    an older, longer object: its length, size, label, CRC over the
    stored bytes, version stamp, and the bytes themselves against a
    fresh encode."""
    _, pg = _primary_pg(c, oid)
    sinfo, codec = pg.backend.sinfo, pg.backend.codec
    padded = data + b"\0" * (
        sinfo.logical_to_next_stripe_offset(len(data)) - len(data))
    want = sinfo.encode(codec, padded) if padded else {}
    shard_len = sinfo.object_size_to_shard_size(len(data))
    seen = 0
    for shard, osd_id in enumerate(pg.acting):
        osd = next((o for o in c.osds if o.whoami == osd_id), None)
        if osd is None or osd._stopped:
            continue
        seen += 1
        coll = osd.pgs[pg.pgid].coll
        raw = osd.store.read(coll, oid, 0, None)
        assert len(raw) == shard_len, (shard, len(raw), shard_len)
        if shard_len:
            assert raw == want[shard].tobytes(), f"shard {shard} bytes"
        assert int(osd.store.getattr(coll, oid, SIZE_XATTR)) == len(data)
        assert int(osd.store.getattr(coll, oid, SHARD_XATTR)) == shard
        assert int(osd.store.getattr(coll, oid, CRC_XATTR)) == \
            shard_crc(raw)
        assert osd.store.getattr(coll, oid, VER_XATTR) == \
            f"{version[0]},{version[1]}".encode()
    assert seen >= sinfo.k


# -- vectors that read nothing ------------------------------------------------

@pytest.mark.parametrize("geom", GEOMETRIES)
def test_fresh_write_full_reads_nothing(geom):
    async def main():
        c = await _cluster(geom)
        try:
            # a first write brings the PGs and launch shapes up
            await _op(c, "warm", [{"op": "writefull", "data": b"w" * 100}])
            for i, n in enumerate((1, 5000, 3 * geom[0] * 4096 + 77)):
                data = _payload(i, n)
                before = _counts(c)
                reply = await _op(c, f"fresh{i}",
                                  [{"op": "writefull", "data": data}])
                assert _delta(c, before) == {
                    "subreads": 0, "write_old_gathers": 0,
                    "writes_blind": 1}
                assert await _read(c, f"fresh{i}") == data
                _assert_shards(c, f"fresh{i}", data,
                               reply.data["version"])
        finally:
            await c.stop()
    run(main())


@pytest.mark.parametrize("geom", GEOMETRIES)
def test_write_full_over_a_longer_object_reads_nothing(geom):
    async def main():
        c = await _cluster(geom)
        try:
            sw = geom[0] * 4096
            old = _payload(1, 5 * sw + 1234)
            await _op(c, "obj", [{"op": "writefull", "data": old}])
            for seed, n in ((2, 2 * sw + 99), (3, 17), (4, 0)):
                new = _payload(seed, n)
                before = _counts(c)
                reply = await _op(c, "obj",
                                  [{"op": "writefull", "data": new}])
                assert _delta(c, before) == {
                    "subreads": 0, "write_old_gathers": 0,
                    "writes_blind": 1}
                before = _counts(c)
                assert await _read(c, "obj") == new      # no stale tail
                if n:
                    assert _delta(c, before)["subreads"] > 0, \
                        "the read gathers as it did"
                _assert_shards(c, "obj", new, reply.data["version"])
        finally:
            await c.stop()
    run(main())


@pytest.mark.parametrize("geom", GEOMETRIES)
def test_remove_reads_nothing_and_the_name_is_gone(geom):
    async def main():
        c = await _cluster(geom)
        try:
            await _op(c, "obj", [{"op": "writefull",
                                  "data": _payload(5, 20000)}])
            for oid in ("obj", "never-written"):
                before = _counts(c)
                await _op(c, oid, [{"op": "remove"}])
                assert _delta(c, before) == {
                    "subreads": 0, "write_old_gathers": 0,
                    "writes_blind": 1}
                _, pg = _primary_pg(c, oid)
                for osd in c.osds:
                    if pg.pgid in osd.pgs:
                        assert not osd.store.exists(
                            osd.pgs[pg.pgid].coll, oid), osd.whoami
                reply = await c.osd_op(POOL, oid, [{"op": "read"}])
                assert reply.data["results"][0].get("err") == "ENOENT"
        finally:
            await c.stop()
    run(main())


# -- vectors that ask once ----------------------------------------------------

@pytest.mark.parametrize("geom", GEOMETRIES)
@pytest.mark.parametrize("kind", ["write_at_0", "append"])
def test_fresh_write_and_append_gather_exactly_once(geom, kind):
    async def main():
        c = await _cluster(geom)
        try:
            await _op(c, "warm", [{"op": "writefull", "data": b"w" * 100}])
            data = _payload(6, geom[0] * 4096 + 321)
            op = ({"op": "write", "off": 0, "data": data}
                  if kind == "write_at_0"
                  else {"op": "append", "data": data})
            one = _remote_data_shards(c, "fresh")
            before = _counts(c)
            reply = await _op(c, "fresh", [op])
            d = _delta(c, before)
            assert d["write_old_gathers"] == 1 and d["writes_blind"] == 0
            # one gather asks the remote data shards once; a hedge
            # timer on a slow host may add parity holders, never a
            # second round
            assert one <= d["subreads"] <= one + geom[1], (d, one)
            assert await _read(c, "fresh") == data
            _assert_shards(c, "fresh", data, reply.data["version"])
        finally:
            await c.stop()
    run(main())


# -- vectors that need the old bytes: held to the model -----------------------

@pytest.mark.parametrize("geom", GEOMETRIES)
def test_partial_overwrite_and_zero_equal_the_model(geom):
    async def main():
        c = await _cluster(geom)
        try:
            sw = geom[0] * 4096
            base = _payload(7, 6 * sw + 500)
            vectors = [
                [{"op": "writefull", "data": base}],
                [{"op": "write", "off": sw + 100,
                  "data": _payload(8, 3000)}],
                [{"op": "zero", "off": 2 * sw - 50, "len": 4000}],
                [{"op": "write", "off": 3 * sw, "data": _payload(9, sw)},
                 {"op": "zero", "off": 10, "len": 20}],
                [{"op": "append", "data": _payload(10, 777)}],
                [{"op": "zero", "off": 6 * sw, "len": 10 * sw}],
                [{"op": "truncate", "size": 2 * sw + 5},
                 {"op": "append", "data": b"tail"}],
                [{"op": "write", "off": 4 * sw, "data": b"far"}],
            ]
            model = None
            for ops in vectors:
                before = _counts(c)
                reply = await _op(c, "obj", ops)
                model = _model(model, ops)
                # an existing object's size is the primary's own xattr:
                # these vectors read old bytes, but never ask twice
                assert _delta(c, before)["write_old_gathers"] <= len(ops)
                assert await _read(c, "obj") == bytes(model), ops
            _assert_shards(c, "obj", bytes(model), reply.data["version"])
        finally:
            await c.stop()
    run(main())


@pytest.mark.parametrize("geom", GEOMETRIES)
@pytest.mark.parametrize("vector", ["write_full_append", "remove_write"])
def test_compound_vectors_equal_the_model(geom, vector):
    async def main():
        c = await _cluster(geom)
        try:
            sw = geom[0] * 4096
            old = _payload(11, 4 * sw + 9)
            ops = ([{"op": "writefull", "data": _payload(12, sw + 50)},
                    {"op": "append", "data": _payload(13, 600)}]
                   if vector == "write_full_append" else
                   [{"op": "remove"},
                    {"op": "write", "off": 300,
                     "data": _payload(14, 2 * sw)}])
            for oid, start in (("fresh", None), ("old", bytearray(old))):
                if start is not None:
                    await _op(c, oid, [{"op": "writefull", "data": old}])
                before = _counts(c)
                reply = await _op(c, oid, ops)
                # both start from nothing whatever the shards hold, and
                # the append's offset is the write_full's own length
                assert _delta(c, before) == {
                    "subreads": 0, "write_old_gathers": 0,
                    "writes_blind": 1}
                want = bytes(_model(start, ops))
                assert await _read(c, oid) == want
                _assert_shards(c, oid, want, reply.data["version"])
        finally:
            await c.stop()
    run(main())


# -- PGs that are not whole ---------------------------------------------------

@pytest.mark.parametrize("geom", GEOMETRIES)
def test_write_full_into_a_degraded_pg(geom):
    """One acting OSD down and not out: the blind write reaches every
    shard that is up, and the read reconstructs what the hole held."""
    async def main():
        c = await _cluster(geom, osd_heartbeat_interval=0.2,
                           osd_heartbeat_grace=2.0)
        try:
            old = _payload(15, 3 * geom[0] * 4096 + 11)
            await _op(c, "obj", [{"op": "writefull", "data": old}])
            posd, pg = _primary_pg(c, "obj")
            victim = next(o for o in c.osds
                          if o.whoami in pg.acting and o is not posd)
            await victim.stop()
            for _ in range(150):
                if not c.mon.osdmap.is_up(victim.whoami):
                    break
                await asyncio.sleep(0.2)
            assert not c.mon.osdmap.is_up(victim.whoami)
            new = _payload(16, geom[0] * 4096 + 2222)
            for oid in ("obj", "fresh"):
                if c.target_for(POOL, oid)[1] is None:
                    continue
                before = _counts(c)
                reply = await _op(c, oid,
                                  [{"op": "writefull", "data": new}])
                d = _delta(c, before)
                assert d["write_old_gathers"] == 0 \
                    and d["writes_blind"] >= 1, d
                assert await _read(c, oid) == new
                _assert_shards(c, oid, new, reply.data["version"])
        finally:
            await c.stop()
    run(main())


@pytest.mark.parametrize("geom", GEOMETRIES)
def test_write_full_where_the_primary_holds_no_copy(geom):
    """A primary without a local copy of an existing object (remapped
    in, not yet backfilled: taken here by removing its shard from its
    own store) has no size xattr to consult; before, that made every
    write gather.  A write_full still reads nothing and replaces every
    shard; a write at offset 0 asks once and finds the old length."""
    async def main():
        c = await _cluster(geom)
        try:
            old = _payload(17, 4 * geom[0] * 4096 + 100)
            for oid in ("full", "ranged"):
                await _op(c, oid, [{"op": "writefull", "data": old}])
                posd, pg = _primary_pg(c, oid)
                txn = Transaction()
                txn.remove(pg.coll, oid)
                posd.store.queue_transaction(txn)
                assert posd.store.getattr(pg.coll, oid,
                                          SIZE_XATTR) is None
            new = _payload(18, 9000)
            before = _counts(c)
            reply = await _op(c, "full", [{"op": "writefull",
                                           "data": new}])
            assert _delta(c, before) == {
                "subreads": 0, "write_old_gathers": 0, "writes_blind": 1}
            assert await _read(c, "full") == new
            _assert_shards(c, "full", new, reply.data["version"])
            # the same primary, a vector that depends on the old bytes
            before = _counts(c)
            patch = _payload(19, len(old) + 50)
            await _op(c, "ranged", [{"op": "write", "off": 0,
                                     "data": patch}])
            d = _delta(c, before)
            assert d["writes_blind"] == 0
            assert 1 <= d["write_old_gathers"] <= 2, d
            assert await _read(c, "ranged") == patch
        finally:
            await c.stop()
    run(main())
