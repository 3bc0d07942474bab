"""The repair after ``osd out`` beside client writes, at small size on
the CPU: what the monitor's map alone starts (no recovery call made
here), the spans, sections and counters the repair carries, and every
shard position of the k=8,m=3 pool rebuilt through
``ECBackend.read_recovery_payload`` and held to the plain reference.

One k=2,m=1 cluster of 4 OSDs runs the whole story once (populate, stop
osd.3, wait for the map, ``osd out``, writers until the cluster is
clean); the tests read what it left.  The PG log is cut below a PG's
share of the population (``osd_max_pg_log_entries``), as a production
PG's log is far shorter than its history, so the new members are
backfilled by scan; with the default 512 entries the same story is
log-based recovery, which the last test pins.
"""

from __future__ import annotations

import ast
import asyncio
import functools
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import ec                   # noqa: E402
from ceph_tpu.common import tracing                   # noqa: E402
from test_tracing_sections import _is_section         # noqa: E402

UNIT = 4096
K2M1 = {"plugin": "tpu", "k": 2, "m": 1, "technique": "reed_sol_van",
        "stripe_unit": UNIT}
K8M3 = {"plugin": "tpu", "k": 8, "m": 3, "technique": "reed_sol_van",
        "stripe_unit": UNIT}
VICTIM, SEED, PGS, N_OBJ = 3, 5, 8, 48
TREE = ("pg.backfill_push", "ec.recover_gather", "ec.recover_decode",
        "pg.push")


def payload(i: int, size: int, new: bool = False) -> bytes:
    return np.random.default_rng([SEED, int(new), i]).bytes(size)


async def _pool(n_osds: int, profile: dict, pg_num: int, **osd_config):
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster

    cluster = await SimCluster.create(n_osds, osd_config=osd_config)
    rados = await Rados(cluster.addr, name="client.t").connect()
    await rados.mon_command("osd erasure-code-profile set", {
        "name": "p", "profile": {a: str(b) for a, b in profile.items()}})
    await rados.pool_create("bench", pg_num=pg_num, pool_type="erasure",
                            erasure_code_profile="p")
    return cluster, rados, await rados.open_ioctx("bench")


async def _stop_and_wait_down(cluster, victim: int, pg_num: int) -> None:
    index = next(n for n, o in enumerate(cluster.osds)
                 if o.whoami == victim)
    await cluster.kill_osd(index)
    assert await cluster.wait_down(victim, timeout=30.0)
    for _ in range(300):
        if cluster.pg_states() == {"active": pg_num}:
            return
        await asyncio.sleep(0.1)
    raise AssertionError(f"PGs not active: {cluster.pg_states()}")


def _pending(cluster) -> bool:
    return any(o.has_pending_recovery() for o in cluster.osds
               if not o.is_stopped())


def _shards(cluster, pool_id: int, oid: str) -> dict:
    """{position: (bytes, crc xattr, label xattr)} from the stores of
    the OSDs the monitor's map gives the object's PG."""
    osdmap = cluster.mon.osdmap
    _, ps = osdmap.object_to_pg(pool_id, oid)
    up, _ = osdmap.pg_to_up_acting(pool_id, ps)
    pgid = osdmap.pg_name(pool_id, ps)
    by_id = {o.whoami: o for o in cluster.osds if not o.is_stopped()}
    out = {}
    for pos, osd_id in enumerate(up):
        osd = by_id[osd_id]
        coll = osd.pgs[pgid].coll
        out[pos] = (bytes(osd.store.read(coll, oid, 0, None)),
                    int(osd.store.getattr(coll, oid, "_crc")),
                    int(osd.store.getattr(coll, oid, "_shard")))
    return out


async def _story(log_entries: int) -> dict:
    size = 3 * 2 * UNIT + 100
    cluster, rados, ioctx = await _pool(
        4, K2M1, PGS, osd_max_backfills=1, osd_ec_batch_max=4,
        osd_max_pg_log_entries=log_entries)
    try:
        await asyncio.gather(*(ioctx.write_full(f"obj-{i}",
                                                payload(i, size))
                               for i in range(N_OBJ)))
        await _stop_and_wait_down(cluster, VICTIM, PGS)
        for t in tracing._TRACERS.values():
            t.finished.clear()
        before = {name: cluster.perf_counters(name)
                  for name in ("ec_batch", "ec_recovery")}
        await rados.mon_command("osd out", {"osd_id": VICTIM})
        state = {"next": 0, "stop": False}

        async def writer() -> None:
            while not state["stop"]:
                i = state["next"]
                state["next"] += 1
                await ioctx.write_full(f"new-{i}", payload(i, size, True))

        writers = [asyncio.ensure_future(writer()) for _ in range(4)]
        await asyncio.sleep(0.5)
        for _ in range(600):
            if cluster.pg_states() == {"active": PGS} \
                    and not _pending(cluster):
                break
            await asyncio.sleep(0.1)
        clean = not _pending(cluster)
        state["stop"] = True
        await asyncio.gather(*writers)
        spans = [s for t in tracing._TRACERS.values() for s in t.dump()
                 if s["name"] in TREE]
        counters = {name: {key: val - before[name].get(key, 0)
                           for key, val in
                           cluster.perf_counters(name).items()}
                    for name in before}
        names = [f"obj-{i}" for i in range(N_OBJ)] \
            + [f"new-{i}" for i in range(state["next"])]
        wrong = []
        for oid in names:
            i, new = int(oid.split("-")[1]), oid.startswith("new")
            want = payload(i, size, new)
            if await ioctx.read(oid) != want:
                wrong.append((oid, "read"))
            found = _shards(cluster, ioctx.pool_id, oid)
            for pos, ref in enumerate(ec.shards_of(K2M1, want)):
                raw, crc, label = found[pos]
                if (raw, crc, label) != (ref, ec.ceph_crc32c(ref), pos):
                    wrong.append((oid, pos))
        return {"clean": clean, "spans": spans, "counters": counters,
                "wrong": wrong, "objects": len(names),
                "perf_dump": cluster.osds[0].perf.dump()}
    finally:
        await rados.shutdown()
        await cluster.stop()


@functools.cache
def backfilled() -> dict:
    return asyncio.run(_story(log_entries=3))


def test_the_map_alone_heals_the_pool_beside_the_writers():
    res = backfilled()
    assert res["clean"]
    assert res["objects"] > N_OBJ          # the writers wrote meanwhile
    assert res["wrong"] == []              # every object, every position


def test_a_push_is_a_tree_of_gather_decode_and_push():
    spans = backfilled()["spans"]
    roots = [s for s in spans if s["name"] == "pg.backfill_push"]
    assert roots and all(s["parent_id"] is None for s in roots)
    assert all(set(s["tags"]) == {"pgid", "oid", "shard", "dirty"}
               for s in roots)
    by_parent: dict = {}
    for s in spans:
        if s["name"] != "pg.backfill_push":
            by_parent.setdefault(s["parent_id"], []).append(s)
    decoded = whole = 0
    for root in roots:
        kids = sorted(by_parent.get(root["span_id"], []),
                      key=lambda s: s["start"])
        names = [s["name"] for s in kids]
        if "ec.recover_gather" not in names:
            continue        # a ring of 2048 dropped the push's first span
        whole += 1
        assert names in (["ec.recover_gather", "ec.recover_decode",
                          "pg.push"],
                         ["ec.recover_gather", "pg.push"]), names
        assert all(s["trace_id"] == root["trace_id"] for s in kids)
        assert root["start"] <= kids[0]["start"] \
            and kids[-1]["end"] <= root["end"]
        gather = kids[0]
        assert gather["tags"]["excluded"] == [root["tags"]["shard"]]
        assert gather["tags"]["asked"] >= 1
        decoded += "ec.recover_decode" in names
    assert decoded > 0 and whole > len(roots) // 2
    # a gather of the repair is never a client read's
    assert not [s for s in spans if s["name"] == "ec.gather"]


def test_backfill_counters_count_pushes_dirty_pushes_and_moved_positions():
    rec = backfilled()["counters"]["ec_recovery"]
    roots = [s for s in backfilled()["spans"]
             if s["name"] == "pg.backfill_push"]
    # a span a push (a ring of 2048 a daemon may have dropped some)
    assert rec["backfill_pushes"] >= len(roots) > 0
    # a write that landed past a target's cursor was skipped there and
    # the object pushed again before the cursor moved over it
    dirty = sum(bool(s["tags"]["dirty"]) for s in roots)
    assert rec["backfill_dirty_pushes"] >= dirty > 0
    assert rec["backfill_dirty_pushes"] < rec["backfill_pushes"]
    # one host out of four moves survivors' positions in some PGs
    assert rec["backfill_positions_moved"] > 0
    assert rec["repair_bytes_shipped"] > 0


def test_a_shard_found_whole_on_a_moved_survivor_is_not_a_local_repair():
    """A plain RS code has no repair from fewer than k chunks: a gather
    of fewer than k buffers is the wanted shard itself, salvaged under
    its write-time label from a survivor that now serves another
    position."""
    rec = backfilled()["counters"]["ec_recovery"]
    assert rec.get("repair_local_repairs", 0) == 0
    assert rec["repair_relabeled_copies"] > 0
    assert rec["repair_relabeled_copies"] + rec["repair_global_decodes"] \
        <= rec["repair_reads"]
    decodes = sum(s["name"] == "ec.recover_decode"
                  for s in backfilled()["spans"])
    assert decodes <= rec["repair_global_decodes"]


def test_batch_counters_by_kind_add_up_to_the_totals():
    batch = backfilled()["counters"]["ec_batch"]
    assert batch["encode_launches"] > 0 and batch["decode_launches"] > 0
    kinds = ("encode", "decode")
    assert sum(batch[f"{k}_launches"] for k in kinds) == batch["batches"]
    assert sum(batch[f"{k}_stripes"] for k in kinds) == batch["stripes"]
    assert sum(batch[f"{k}_queue_wait_us"] for k in kinds) \
        == batch["queue_wait_us"]
    assert batch["decode_stripes"] == 4 * batch["decode_launches"]
    dump = backfilled()["perf_dump"]
    assert {"decode_stripes", "decode_queue_wait_us", "encode_stripes",
            "encode_queue_wait_us"} <= set(dump["ec_batch"])
    assert "ec_recovery" in dump


def _section_names(path: str) -> set:
    return {item.context_expr.args[0].value
            for node in ast.walk(ast.parse((ROOT / path).read_text()))
            if isinstance(node, ast.With)
            for item in node.items if _is_section(item)}


def test_the_repair_has_three_sections_of_its_own():
    assert "recovery" in tracing.SECTION_LAYERS
    found = set()
    for path in ("ceph_tpu/osd/pg.py", "ceph_tpu/osd/backend.py",
                 "ceph_tpu/osd/ec_util.py"):
        found |= {n for n in _section_names(path)
                  if n.startswith("recovery.")}
    assert found == {"recovery.scan", "recovery.payload", "recovery.apply"}


def test_with_the_whole_history_in_the_log_the_repair_is_not_a_backfill():
    """The default log (512 entries) holds every write these PGs took:
    the new member's empty log overlaps it, the repair is log-based
    recovery under the PG's lock, and no backfill push is made.  The
    objects come out the same."""
    res = asyncio.run(_story(log_entries=512))
    assert res["clean"] and res["wrong"] == []
    assert res["counters"]["ec_recovery"].get("backfill_pushes", 0) == 0
    assert not [s for s in res["spans"] if s["name"] == "pg.backfill_push"]
    gathers = [s for s in res["spans"] if s["name"] == "ec.recover_gather"]
    assert gathers and all(s["parent_id"] is None for s in gathers)


async def _write_during_a_log_based_round() -> dict:
    """One PG, a dozen objects, the default log: the member that takes
    the dead OSD's place is repaired from the log, one push an object.
    A client write sent while the round is under way must not wait
    for its end."""
    from ceph_tpu.osd.pg import PG

    size = 2 * UNIT
    cluster, rados, ioctx = await _pool(4, K2M1, 1)
    sound = PG._push_object
    pushes = {"done": 0}

    async def slow_push(self, peer, oid):
        await sound(self, peer, oid)
        pushes["done"] += 1
        await asyncio.sleep(0.05)       # still under the PG's lock

    try:
        await asyncio.gather(*(ioctx.write_full(f"obj-{i}",
                                                payload(i, size))
                               for i in range(12)))
        pg = next(pg for o in cluster.osds for pg in o.pgs.values()
                  if pg.is_primary())
        victim = next(o for o in pg.acting if o != pg.whoami)
        await _stop_and_wait_down(cluster, victim, 1)
        PG._push_object = slow_push
        await rados.mon_command("osd out", {"osd_id": victim})
        for _ in range(300):             # the round is under way
            if pushes["done"]:
                break
            await asyncio.sleep(0.01)
        await ioctx.write_full("new-0", payload(0, size, True))
        at_ack = pushes["done"]
        for _ in range(300):
            if not _pending(cluster):
                break
            await asyncio.sleep(0.1)
        return {"at_ack": at_ack, "pushes": pushes["done"],
                "clean": not _pending(cluster),
                "read": await ioctx.read("new-0") == payload(0, size, True)}
    finally:
        PG._push_object = sound
        await rados.shutdown()
        await cluster.stop()


def test_a_client_write_waits_for_one_objects_push_not_for_the_round():
    res = asyncio.run(_write_during_a_log_based_round())
    assert res["clean"] and res["read"]
    assert res["pushes"] >= 12           # the round pushed every object
    assert 1 <= res["at_ack"] <= res["pushes"] // 2


async def _push_beside_a_commit_in_flight() -> dict:
    """A write has left the PG's lock and its commit is still on its
    way to the peers: a push of that object must not read it yet (it
    would find fewer than k shards, fail EIO and restart the PG's
    backfill), and must not hold the lock while it waits."""
    size = 2 * UNIT
    cluster, rados, ioctx = await _pool(3, K2M1, 1)
    try:
        await ioctx.write_full("obj-0", payload(0, size))
        pg = next(pg for o in cluster.osds for pg in o.pgs.values()
                  if pg.is_primary())
        peer = next(o for o in pg.acting if o != pg.whoami)
        commit = asyncio.get_running_loop().create_future()
        pg._obj_commits["obj-0"] = commit
        bi = {"inflight": {}, "pushed": set()}
        read, reads = pg.backend.read_recovery_payload, []

        async def reading(oid, shard):
            reads.append(commit.done())
            return await read(oid, shard)

        pg.backend.read_recovery_payload = reading
        push = asyncio.ensure_future(pg._backfill_push_traced(
            bi, peer, "obj-0", pg._shard_of(peer)))
        await asyncio.sleep(0.05)
        waiting = {"reads": list(reads), "marked": "obj-0" in bi["inflight"],
                   "locked": pg.lock.locked()}
        commit.set_result(None)
        acked = await asyncio.wait_for(push, 10)
        return {"waiting": waiting, "reads": reads, "acked": acked,
                "pushed": bi["pushed"], "inflight": bi["inflight"]}
    finally:
        await rados.shutdown()
        await cluster.stop()


def test_a_push_reads_its_object_after_the_commit_in_flight():
    res = asyncio.run(_push_beside_a_commit_in_flight())
    assert res["waiting"] == {"reads": [], "marked": False, "locked": False}
    assert res["reads"] == [True] and res["acked"]
    assert res["pushed"] == {"obj-0"} and res["inflight"] == {}


# -- every position of the k=8,m=3 pool --------------------------------------

async def _rebuild_every_position() -> dict:
    size = 2 * 8 * UNIT + 100
    cluster, rados, ioctx = await _pool(12, K8M3, 1)
    try:
        data = payload(0, size)
        await ioctx.write_full("obj-0", data)
        primary = next(o for o in cluster.osds for pg in o.pgs.values()
                       if pg.is_primary() and pg.state == "active")
        pg = next(pg for pg in primary.pgs.values() if pg.is_primary())
        one = {s: await pg.backend.read_recovery_payload("obj-0", s)
               for s in range(11)}
        # a second hole: stop the OSD of the last position that is not
        # the primary's, and let the cluster's own map show it
        hole = max(s for s, o in enumerate(pg.acting)
                   if o != primary.whoami)
        await _stop_and_wait_down(cluster, pg.acting[hole], 1)
        pg = next(pg for pg in primary.pgs.values() if pg.is_primary())
        assert pg.acting[hole] < 0
        decodes = cluster.perf_counters("ec_batch")["decode_launches"]
        two = {s: await pg.backend.read_recovery_payload("obj-0", s)
               for s in range(11) if s != hole}
        return {"data": data, "one": one, "two": two, "hole": hole,
                "decodes": cluster.perf_counters("ec_batch")[
                    "decode_launches"] - decodes}
    finally:
        await rados.shutdown()
        await cluster.stop()


@functools.cache
def rebuilt() -> dict:
    return asyncio.run(_rebuild_every_position())


def _check(got: dict, shard: int, data: bytes) -> None:
    ref = ec.shards_of(K8M3, data)[shard]
    assert got["data"] == ref
    assert got["shard"] == shard and not got.get("absent")
    assert int(got["xattrs"]["_shard"]) == shard
    assert int(got["xattrs"]["_crc"]) == ec.ceph_crc32c(ref)
    assert int(got["xattrs"]["_size"]) == len(data)


@pytest.mark.parametrize("shard", range(11))
def test_every_position_rebuilds_to_the_reference(shard):
    res = rebuilt()
    _check(res["one"][shard], shard, res["data"])


@pytest.mark.parametrize("nth", range(10))
def test_every_position_rebuilds_beside_a_second_hole(nth):
    res = rebuilt()
    shard = sorted(res["two"])[nth]
    assert shard != res["hole"]
    _check(res["two"][shard], shard, res["data"])
    assert res["decodes"] == 10          # each a launch of its own
