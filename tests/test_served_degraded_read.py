"""Support for the degraded deployment, at small size on the CPU: the
served degraded read (cluster, victim stopped and marked down by the
cluster's own heartbeats, librados ``read``) against the plain
reference's reconstruction and the payload for every position the hole
can take; ``MeshCodec.decode`` against the reference for every single
loss at the deployment's widths; and the reference against itself."""

from __future__ import annotations

import asyncio
import functools
import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

# the benchmark's own code (driver helpers, references) sits beside the
# program; this file is kept out of tests/benchmark_suite/ so that its
# two clusters do not run beside that directory's rehearsals
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.drivers import store_read_loop as drv    # noqa: E402
from benchmark.reference import ec, ec_decode       # noqa: E402

UNIT = 4096
POOLS = {"k2m1": ({"plugin": "tpu", "k": 2, "m": 1,
                   "technique": "reed_sol_van", "stripe_unit": UNIT}, 4, 64),
         "k4m2": ({"plugin": "tpu", "k": 4, "m": 2,
                   "technique": "reed_sol_van", "stripe_unit": UNIT}, 7, 96)}
VICTIM, SEED = 3, 11
STORED_AS = {"shard_xattr": "_shard", "crc_xattr": "_crc"}


async def _served_reads(profile: dict, n_osds: int, n_obj: int) -> dict:
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster

    k = profile["k"]
    size = 3 * k * UNIT + 100            # three stripe rows and a ragged tail
    cluster = await SimCluster.create(n_osds)   # the in-process short grace
    rados = None
    try:
        rados = await Rados(cluster.addr, name="client.t").connect()
        await rados.mon_command("osd erasure-code-profile set", {
            "name": "p", "profile": {a: str(b) for a, b in profile.items()}})
        await rados.pool_create("bench", pg_num=64, pool_type="erasure",
                                erasure_code_profile="p")
        ioctx = await rados.open_ioctx("bench")
        payloads = [drv.object_bytes(SEED, i, size) for i in range(n_obj)]
        await asyncio.gather(*(ioctx.write_full(f"obj-{i}", p)
                               for i, p in enumerate(payloads)))
        osdmap = cluster.mon.osdmap
        holes = []
        for i in range(n_obj):
            _, ps = osdmap.object_to_pg(ioctx.pool_id, f"obj-{i}")
            acting = osdmap.pg_to_up_acting_osds(ioctx.pool_id, ps)
            holes.append(acting.index(VICTIM) if VICTIM in acting else None)
        await drv._fail_victim(cluster, VICTIM, 64, timeout=30.0)
        reads = []
        for i, payload in enumerate(payloads):
            got = await asyncio.wait_for(ioctx.read(f"obj-{i}"), 30.0)
            pgid, _ = rados.objecter.calc_target(ioctx.pool_id, f"obj-{i}")
            reads.append((holes[i], got, payload, drv.stored_shards(
                cluster, pgid, f"obj-{i}", STORED_AS)))
        return {"reads": reads,
                "degraded": cluster.perf_counters("ec_degraded"),
                "batch": cluster.perf_counters("ec_batch"),
                "msgr": cluster.perf_counters("msgr"),
                "perf_dump_sets": sorted(cluster.osds[0].perf.dump()),
                "downs": [e["message"] for e in
                          cluster.mon.services.cluster_log
                          if drv.MARKED_DOWN in e["message"]]}
    finally:
        if rados is not None:
            await rados.shutdown()
        await cluster.stop()


@functools.cache
def served(pool: str) -> dict:
    profile, n_osds, n_obj = POOLS[pool]
    return asyncio.run(_served_reads(profile, n_osds, n_obj))


@pytest.mark.parametrize("pool,hole", [
    (pool, hole) for pool, (profile, _, _) in POOLS.items()
    for hole in range(profile["k"] + profile["m"])])
def test_served_degraded_read_equals_the_reference(pool, hole):
    profile = POOLS[pool][0]
    reads = [r for r in served(pool)["reads"] if r[0] == hole]
    assert reads, f"no object has its hole at shard {hole}"
    for _, got, payload, found in reads:
        assert hole not in found                 # the victim's is gone
        assert len(found) == profile["k"] + profile["m"] - 1
        assert got == payload
        assert got == ec_decode.object_from_shards(
            profile, {s: raw for s, (raw, _, _) in found.items()},
            len(payload))
        faults = drv.check_read(
            got, payload, found,
            set(range(profile["k"] + profile["m"])) - {hole}, profile)
        assert not any(faults.values()), faults


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_served_reads_reconstruct_where_the_hole_is_a_data_shard(pool):
    out, k = served(pool), POOLS[pool][0]["k"]
    need = sum(r[0] is not None and r[0] < k for r in out["reads"])
    assert 0 < need < len(out["reads"])
    assert out["degraded"]["reconstructions"] == need
    assert out["batch"]["decode_launches"] >= 1
    assert out["batch"].get("fallback_ops", 0) == 0
    assert out["downs"] == [f"osd.{VICTIM} marked down after 1 reports"]


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_the_osds_msgr_counters_add_up_over_the_cluster(pool):
    """Plain connections: no frame is joined to be sent, and the
    receive path copies the segments out once and nothing else (the
    stream reader it replaced copied every frame about five times)."""
    out = served(pool)
    msgr = out["msgr"]
    assert "msgr" in out["perf_dump_sets"]
    # a daemon counts what it did, not what a load generator offered
    assert "workload" not in out["perf_dump_sets"]
    assert msgr["tx_frames"] > 0 and msgr["rx_frames"] > 0
    assert msgr.get("tx_frames_joined", 0) == 0
    assert 0 < msgr["rx_copied_bytes"] < msgr["rx_bytes"]
    payload = sum(len(r[2]) for r in out["reads"])
    # every object went to the primary once and (k+m-1)/k of it on to
    # the other shards' OSDs, less what stayed on the primary
    assert msgr["rx_copied_bytes"] > payload


RS83 = {"plugin": "tpu", "k": 8, "m": 3, "technique": "reed_sol_van",
        "stripe_unit": UNIT}


@pytest.mark.parametrize("lost", range(11))
def test_mesh_decode_equals_the_reference_for_every_single_loss(lost):
    from ceph_tpu.ec import registry
    from ceph_tpu.gf.matrices import decode_index_for
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    k, rows = RS83["k"], 4
    payload = np.random.default_rng([SEED, lost]).bytes(rows * k * UNIT)
    shards = ec.shards_of(RS83, payload)
    chunks = np.stack([np.frombuffer(s, np.uint8).reshape(rows, UNIT)
                       for s in shards], axis=1)          # (4, 11, 4096)
    codec = registry().factory("tpu", {"k": "8", "m": "3",
                                       "technique": "reed_sol_van"})
    mesh = MeshCodec(n_devices=1)    # (4, 8, 4096) as it stands, unpadded
    signatures = [(lost,)]
    if lost < k:       # what the primary asks for: all it did not gather
        signatures.append(drv.served_erasures(codec, lost))
    for erasures in signatures:
        survivors = chunks[:, decode_index_for(k, set(erasures))]
        assert survivors.shape == (rows, k, UNIT)
        out = mesh.decode(codec, erasures, survivors.copy())
        assert out.shape == (rows, len(erasures), UNIT)
        for row, shard in enumerate(erasures):
            assert out[:, row].tobytes() == shards[shard], (erasures, shard)
    if lost < k:
        have = {s: shards[s] for s in range(11) if s != lost}
        assert ec_decode.data_shards(RS83, have)[lost].tobytes() \
            == shards[lost]


@pytest.mark.parametrize("profile", [
    {"k": 2, "m": 1, "technique": "reed_sol_van", "stripe_unit": 64},
    {"k": 4, "m": 2, "technique": "reed_sol_van", "stripe_unit": 64},
    {"k": 8, "m": 3, "technique": "reed_sol_van", "stripe_unit": 64},
    {"k": 10, "m": 4, "technique": "cauchy", "stripe_unit": 32},
], ids=lambda p: f"{p['technique']}_k{p['k']}m{p['m']}")
def test_reference_reconstructs_from_any_k_shards(profile):
    k, m = profile["k"], profile["m"]
    payload = np.random.default_rng([SEED, k]).bytes(
        3 * k * profile["stripe_unit"] + 17)
    shards = ec.shards_of(profile, payload)
    for drop in itertools.combinations(range(k + m), m):
        have = {i: s for i, s in enumerate(shards) if i not in drop}
        assert ec_decode.object_from_shards(profile, have,
                                            len(payload)) == payload


def test_reference_inverse_and_its_refusals():
    gen = ec_decode.generator({"k": 4, "m": 2, "technique": "reed_sol_van"})
    sub = gen[[0, 2, 4, 5]]
    assert (ec.gf_matmul(ec_decode.gf_invert(sub), sub)
            == np.eye(4, dtype=np.uint8)).all()
    with pytest.raises(ValueError):
        ec_decode.gf_invert(np.array([[1, 2], [1, 2]], np.uint8))
    profile = {"k": 2, "m": 1, "technique": "reed_sol_van", "stripe_unit": 4}
    with pytest.raises(ValueError):
        ec_decode.object_from_shards(profile, {0: b"abcd"}, 4)
    with pytest.raises(ValueError):
        ec_decode.object_from_shards(profile, {0: b"abcd", 2: b"ab"}, 4)
