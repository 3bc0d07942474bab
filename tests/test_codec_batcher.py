"""Cross-PG EC codec batching (ceph_tpu/osd/codec_batcher.py).

The aggregation stage must (a) coalesce concurrent encode/decode
submissions into few launches of its engine, (b) stay BYTE-IDENTICAL
to the per-op path across ragged tails and padding, (c) leave codecs
the engine cannot launch to the per-op path, counted, and (d) surface
occupancy via perf counters.  The cluster
tests drive the real OSD write path: N concurrent client EC writes
across >=2 PGs must share launches and leave the same shard bytes on
disk as an unbatched cluster.
"""

import asyncio
import math

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.common.perf import PerfCounters
from ceph_tpu.ec import registry
from ceph_tpu.ops.jax_backend import JaxBackend
from ceph_tpu.osd.codec_batcher import CodecBatcher
from ceph_tpu.osd.ec_util import StripeInfo

from test_osd_cluster import make_cluster, read_result, run


def _codec(k="2", m="1"):
    return registry().factory("tpu", {"k": k, "m": m,
                                      "technique": "reed_sol_van"})


# -- unit: coalescing + byte parity -----------------------------------------

def test_concurrent_encodes_coalesce_and_match_per_op():
    codec = _codec()
    si = StripeInfo.for_codec(codec, stripe_unit=64)
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=8, flush_timeout=0.2, perf=perf)
    rng = np.random.default_rng(0)
    datas = [rng.integers(0, 256, si.stripe_width * n,
                          dtype=np.uint8).tobytes()
             for n in (1, 3, 2, 2)]

    async def main():
        return await asyncio.gather(
            *(si.encode_async(codec, d, batcher=b) for d in datas))

    outs = run(main())
    for d, got in zip(datas, outs):
        want = si.encode(codec, d)
        assert set(got) == set(want)
        for i in want:
            assert np.array_equal(got[i], want[i]), i
    dump = perf.dump()
    # 8 stripes from 4 ops in ONE launch (threshold flush at 8)
    assert dump["batches"] == 1
    assert dump["stripes"] == 8
    assert dump["ops_coalesced"] == 4
    assert dump["flush_full"] == 1
    assert dump["stripes_per_batch"]["counts"][4] == 1  # bucket (4, 8]


def test_ragged_tails_pad_and_slice_back_exactly():
    """Submissions with different chunk lengths share a launch: the
    lane axis pads to the max L and the batch axis pads to a power of
    two; results slice back byte-exact and the waste is counted."""
    codec = _codec()
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=4, flush_timeout=0.2, perf=perf)
    rng = np.random.default_rng(1)
    # ragged L: 64 vs 128-byte chunks, 1 and 2 stripes
    a1 = rng.integers(0, 256, (1, 2, 64), dtype=np.uint8)
    a2 = rng.integers(0, 256, (2, 2, 128), dtype=np.uint8)

    async def main():
        return await asyncio.gather(b.encode(codec, a1),
                                    b.encode(codec, a2))

    p1, p2 = run(main())
    assert p1.shape == (1, 1, 64) and p2.shape == (2, 1, 128)
    for arr, par in ((a1, p1), (a2, p2)):
        for s in range(arr.shape[0]):
            want = codec.encode(set(range(3)), arr[s].tobytes())
            assert np.array_equal(par[s, 0], want[2]), s
    dump = perf.dump()
    assert dump["batches"] == 1
    # the launch pads the batch axis to the mesh-bucketed size (power
    # of two AND a multiple of the device count -- 8 under the
    # conftest's forced 8-device mesh) and the waste is all counted
    from ceph_tpu.parallel.mesh_codec import MeshCodec
    b_pad = MeshCodec().pad_batch(3)
    assert dump["pad_waste_bytes"] == b_pad * 2 * 128 - (a1.size
                                                         + a2.size)


def test_decode_groups_by_erasure_signature():
    """Decodes coalesce only when the erasure pattern (the
    DecodeTableCache signature) matches; the recovered chunks are
    byte-identical to the per-stripe decode."""
    codec = _codec(k="3", m="2")
    si = StripeInfo.for_codec(codec, stripe_unit=32)
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=64, flush_timeout=0.2, perf=perf)
    rng = np.random.default_rng(2)
    datas = [rng.integers(0, 256, si.stripe_width * n,
                          dtype=np.uint8).tobytes() for n in (2, 3, 1)]
    shard_sets = [si.encode(codec, d) for d in datas]

    async def main():
        jobs = []
        for shards in shard_sets[:2]:     # same erasures {0, 4}
            avail = {i: v for i, v in shards.items() if i not in (0, 4)}
            jobs.append(si.decode_async(codec, avail, want={0, 4},
                                        batcher=b))
        avail = {i: v for i, v in shard_sets[2].items() if i != 1}
        jobs.append(si.decode_async(codec, avail, want={1}, batcher=b))
        return await asyncio.gather(*jobs)

    outs = run(main())
    for got, shards, want_ids in zip(
            outs, shard_sets, ({0, 4}, {0, 4}, {1})):
        for i in want_ids:
            assert np.array_equal(np.asarray(got[i]), shards[i]), i
    dump = perf.dump()
    # two erasure signatures -> two decode launches, not three
    assert dump["decode_launches"] == 2
    assert dump["stripes"] == 6


def test_fallback_for_non_batch_codec():
    """isa/jerasure (no encode_batch/decode_batch) take the per-op
    path transparently and the fallback is counted."""
    isa = registry().factory("isa", {"k": "2", "m": "1"})
    si = StripeInfo.for_codec(isa, stripe_unit=64)
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(perf=perf)
    assert not b.supports(isa)
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, si.stripe_width * 3,
                        dtype=np.uint8).tobytes()

    async def main():
        got = await si.encode_async(isa, data, batcher=b)
        shards = si.encode(isa, data)
        for i in shards:
            assert np.array_equal(got[i], shards[i])
        avail = {i: v for i, v in shards.items() if i != 1}
        dec = await si.decode_async(isa, avail, want={1}, batcher=b)
        assert np.array_equal(np.asarray(dec[1]), shards[1])

    run(main())
    dump = perf.dump()
    assert dump["fallback_ops"] == 2
    assert "batches" not in dump or dump["batches"] == 0


# every plugin under ceph_tpu/ec/plugins/: (plugin, profile, launches
# through the engine)
PLUGIN_CASES = [
    ("clay", {"k": "4", "m": "2"}, False),
    ("example", {}, False),
    ("isa", {"k": "4", "m": "2"}, False),
    ("jerasure", {"k": "4", "m": "2", "technique": "reed_sol_van"},
     False),
    ("lrc", {"k": "4", "m": "2", "l": "3"}, True),
    ("pmsr", {"k": "3", "m": "2"}, True),
    ("shec", {"k": "4", "m": "3", "c": "2"}, False),
    ("tpu", {"k": "4", "m": "2", "technique": "reed_sol_van"}, True),
    # a chunk remapping decouples shard ids from the matrix rows
    ("tpu", {"k": "2", "m": "1", "mapping": "_DD"}, False),
]


@pytest.mark.parametrize(
    "plugin,profile,launches", PLUGIN_CASES,
    ids=[p + ("-mapped" if "mapping" in prof else "")
         for p, prof, _ in PLUGIN_CASES])
def test_every_plugin_launches_through_the_engine_or_per_op(
        plugin, profile, launches):
    """A codec either launches through the engine (one mesh launch a
    batch, no fallback) or ``supports`` is false and the per-op path
    serves, counted: none takes a third way, and the shard bytes and
    CRCs are the codec's own either way."""
    import pathlib
    import ceph_tpu.ec.plugins as plugins
    on_disk = {f.stem for f in pathlib.Path(plugins.__file__).parent.glob(
        "*.py")} - {"__init__"}
    assert on_disk == {p for p, _, _ in PLUGIN_CASES}

    codec = registry().factory(plugin, dict(profile))
    si = StripeInfo.for_codec(codec, codec.get_alignment())
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=8, flush_timeout=0.2, perf=perf)
    assert b.supports(codec) == launches
    rng = np.random.default_rng(12)
    data = rng.integers(0, 256, si.stripe_width * 3,
                        dtype=np.uint8).tobytes()
    shards, crcs = run(si.encode_async(codec, data, batcher=b,
                                       with_crc=True))
    want = si.encode(codec, data)
    assert set(shards) == set(want)
    for i in want:
        assert np.array_equal(shards[i], want[i]), i
        assert crcs[i] == native.crc32c(want[i].tobytes()), i
    dump = perf.dump()
    if launches:
        assert dump["batches"] == dump["mesh_launches"] == 1
        assert dump.get("fallback_ops", 0) == 0
    else:
        assert dump["fallback_ops"] == 1
        assert dump.get("batches", 0) == dump.get("mesh_launches", 0) == 0


def test_submission_after_close_launches_through_the_engine():
    """A straggler submitted after close() is not coalesced, but it
    launches through the engine like any batch and is counted."""
    codec = _codec()
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=64, flush_timeout=5.0, perf=perf)
    b.close()
    rng = np.random.default_rng(13)
    arr = rng.integers(0, 256, (3, 2, 64), dtype=np.uint8)
    oldp = rng.integers(0, 256, (3, 1, 64), dtype=np.uint8)

    async def main():
        parity, crcs = await asyncio.wait_for(
            b.encode(codec, arr, with_crc=True), timeout=2.0)
        return parity, crcs, await asyncio.wait_for(
            b.rmw(codec, oldp, arr), timeout=2.0)

    parity, crcs, new_parity = run(main())
    for s in range(3):
        want = codec.encode(set(range(3)), arr[s].tobytes())
        assert np.array_equal(parity[s, 0], want[2]), s
        assert int(crcs[s, 2]) == native.crc32c(want[2].tobytes()), s
    assert np.array_equal(new_parity, oldp ^ parity)
    dump = perf.dump()
    assert dump["batches"] == dump["mesh_launches"] == 2
    assert dump["flush_close"] == 2
    assert dump["crc_fused_launches"] == 1
    assert dump.get("crc_host_batches", 0) == 0
    assert not b._groups and not b._staged


def test_timer_flush_when_not_eager():
    """With the drain fast path off, a lone submission launches on the
    timer backstop (and is counted as such)."""
    codec = _codec()
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=64, flush_timeout=0.02,
                     eager_flush=False, perf=perf)
    arr = np.random.default_rng(4).integers(
        0, 256, (2, 2, 64), dtype=np.uint8)

    async def main():
        return await b.encode(codec, arr)

    par = run(main())
    assert par.shape == (2, 1, 64)
    assert perf.dump()["flush_timer"] == 1


def test_drain_flush_is_prompt():
    """Eager mode: a lone submission must NOT sit out the full linger
    timer -- the queue-drained fast path launches it as soon as the
    loop goes idle."""
    codec = _codec()
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=64, flush_timeout=5.0, perf=perf)
    arr = np.zeros((1, 2, 64), np.uint8)

    async def main():
        return await asyncio.wait_for(b.encode(codec, arr), timeout=2.0)

    run(main())                      # wait_for would fail on the timer
    assert perf.dump()["flush_drain"] == 1


def test_launch_error_propagates_to_all_waiters():
    codec = _codec()

    class BoomEngine:
        def pad_batch(self, total):
            return total

        def encode(self, *a, **k):
            raise RuntimeError("driver on fire")

    b = CodecBatcher(max_batch=2, flush_timeout=0.05,
                     engine=BoomEngine())

    async def main():
        jobs = [b.encode(codec, np.zeros((1, 2, 64), np.uint8))
                for _ in range(2)]
        res = await asyncio.gather(*jobs, return_exceptions=True)
        assert all(isinstance(r, RuntimeError) for r in res)

    run(main())


# -- cluster: the OSD hot path ----------------------------------------------

async def _ec_cluster(n=3, k="2", m="1", pg_num=4, osd_config=None):
    c = await make_cluster(n, osd_config=osd_config)
    await c.command("osd erasure-code-profile set",
                    {"name": "prof",
                     "profile": {"plugin": "tpu", "k": k, "m": m,
                                 "technique": "reed_sol_van"}})
    await c.command("osd pool create",
                    {"name": "ecpool", "type": "erasure",
                     "pg_num": pg_num, "erasure_code_profile": "prof"})
    return c


class _LaunchCounter:
    """Instrumented codec driver: counts matmul_batch launches at the
    JaxBackend choke point every tpu-plugin instance shares."""

    def __init__(self):
        self.calls = 0
        self._orig = JaxBackend.matmul_batch

    def __enter__(self):
        counter = self

        def counted(backend_self, matrix, data, out_np=False):
            counter.calls += 1
            return counter._orig(backend_self, matrix, data,
                                 out_np=out_np)

        JaxBackend.matmul_batch = counted
        return self

    def __exit__(self, *exc):
        JaxBackend.matmul_batch = self._orig
        return False


def _shard_bytes(c, pool="ecpool"):
    """{(pgid, oid, osd): shard bytes} across every OSD store."""
    out = {}
    for o in c.osds:
        for pgid, pg in o.pgs.items():
            if not pgid.startswith(f"{c.mon.osdmap.pool_names[pool]}."):
                continue
            for oid in o.store.list_objects(pg.coll):
                if oid.startswith("_"):
                    continue
                out[(pgid, oid, o.whoami)] = o.store.read(
                    pg.coll, oid, 0, None)
    return out


def _pick_oids_one_primary(c, n, pool="ecpool"):
    """n object names in n DISTINCT PGs that all share ONE primary OSD.

    The batcher is a PER-OSD stage, so the ceil(N/B) launch bound is a
    per-primary statement; and writes inside one PG serialize on the
    PG lock, so true N-way concurrency needs N distinct PGs.  Picking
    one primary with one object per PG makes the bound exact while
    exercising exactly the cross-PG coalescing the stage exists for."""
    by_primary: dict[int, dict[str, dict]] = {}
    for i in range(2000):
        oid = f"obj-{i}"
        pgid, primary, _ = c.target_for(pool, oid)
        ent = by_primary.setdefault(primary, {"by_pg": {}})
        ent["by_pg"].setdefault(pgid, oid)
        if len(ent["by_pg"]) >= n:
            return list(ent["by_pg"].values())[:n], set(
                list(ent["by_pg"])[:n])
    raise AssertionError("could not spread oids over one primary")


def test_concurrent_writes_share_launches_and_match_unbatched():
    """N concurrent EC writes across >=2 PGs on one primary:
    <= ceil(N/B) batched encode launches, byte-identical shard bytes
    vs a batching-disabled cluster, and occupancy visible in perf
    counters."""
    N, B = 8, 4
    rng = np.random.default_rng(7)
    # one stripe per object (stripe_width = 8192 for k=2/su=4096)
    payloads = [rng.integers(0, 256, 8192, dtype=np.uint8).tobytes()
                for _ in range(N)]

    async def drive(osd_config):
        c = await _ec_cluster(pg_num=32, osd_config=osd_config)
        try:
            oids, pgids = _pick_oids_one_primary(c, N)
            wants = dict(zip(oids, payloads))
            # warm round: peering, codec compile and object creation
            # happen OUTSIDE the counted window, so the counted round
            # has no retry-staggered arrivals
            for oid in oids:
                await c.osd_op("ecpool", oid, [
                    {"op": "writefull", "data": b"w" * 8192}])
            with _LaunchCounter() as lc:
                await asyncio.gather(*(
                    c.osd_op("ecpool", oid, [
                        {"op": "writefull", "data": data}])
                    for oid, data in wants.items()))
                launches = lc.calls
            shard_map = _shard_bytes(c)
            perf = {}
            for o in c.osds:
                d = o.perf.dump().get("ec_batch", {})
                for key, v in d.items():
                    if isinstance(v, (int, float)):
                        perf[key] = perf.get(key, 0) + v
            return launches, pgids, set(oids), shard_map, perf
        finally:
            await c.stop()

    async def main():
        batched_cfg = {"osd_ec_batch_max": B,
                       "osd_ec_batch_timeout": 0.25,
                       "osd_ec_batch_eager_flush": False}
        launches, pgids, oids, batched, perf = await drive(batched_cfg)
        _, _, _, unbatched, _ = await drive(
            {"osd_ec_batch_enabled": False})
        return launches, pgids, oids, batched, unbatched, perf

    launches, pgids, oids, batched, unbatched, perf = run(main())
    assert len(pgids) >= 2, "objects landed in one PG; widen the test"
    assert launches <= math.ceil(N / B), (launches, N, B)
    # batching must not change a single shard byte
    keys = {key for key in batched if key[1] in oids}
    assert keys == {key for key in unbatched if key[1] in oids}
    for key in keys:
        assert batched[key] == unbatched[key], key
    # perf counters surface the occupancy
    assert perf.get("batches", 0) >= 1
    assert perf.get("stripes", 0) >= N
    assert perf["stripes"] / perf["batches"] > 1.0, perf


def test_batched_cluster_reads_back_byte_exact():
    """End-to-end: concurrent ragged-size writes (tail stripes pad in
    the batcher) read back exactly, including degraded."""
    async def main():
        c = await _ec_cluster()
        try:
            rng = np.random.default_rng(9)
            sizes = [100, 8192, 12345, 3 * 8192, 40000]
            wants = {}
            for i, sz in enumerate(sizes):
                wants[f"r-{i}"] = rng.integers(
                    0, 256, sz, dtype=np.uint8).tobytes()
            await asyncio.gather(*(
                c.osd_op("ecpool", oid, [{"op": "writefull", "data": d}])
                for oid, d in wants.items()))
            for oid, want in wants.items():
                reply = await c.osd_op("ecpool", oid, [
                    {"op": "read", "off": 0, "len": None}])
                _, data = read_result(reply)
                assert data == want, oid
        finally:
            await c.stop()
    run(main())


# -- stripe_unit validation (prepare_pool_stripe_width analog) ---------------

def test_mon_rejects_bad_stripe_unit():
    async def main():
        c = await make_cluster(3)
        try:
            for bad in (0, -4096, "garbage", 100):   # 100: unaligned
                with pytest.raises(RuntimeError):
                    await c.command(
                        "osd erasure-code-profile set",
                        {"name": "bad",
                         "profile": {"plugin": "tpu", "k": "2",
                                     "m": "1", "stripe_unit": bad}})
            # a sane value passes and the pool builds
            await c.command("osd erasure-code-profile set",
                            {"name": "ok",
                             "profile": {"plugin": "tpu", "k": "2",
                                         "m": "1",
                                         "stripe_unit": 8192}})
            await c.command("osd pool create",
                            {"name": "okpool", "type": "erasure",
                             "pg_num": 2,
                             "erasure_code_profile": "ok"})
            await c.osd_op("okpool", "x", [
                {"op": "writefull", "data": b"z" * 100}])
        finally:
            await c.stop()
    run(main())


def test_digest_is_a_kind_of_its_own_with_its_own_counters():
    """A scrub's digest launch beside an encode: each kind counts its
    launches, rows and queue wait; submissions of one lane share a
    launch, another lane takes its own."""
    from ceph_tpu import native
    from ceph_tpu.common.perf import PerfCounters
    from ceph_tpu.ec import registry
    from ceph_tpu.ops.crc32c_batch import digest_rows

    codec = registry().factory("tpu", {"k": "2", "m": "1",
                                       "technique": "reed_sol_van"})
    perf = PerfCounters("ec_batch")
    batcher = CodecBatcher(perf=perf)
    rng = np.random.default_rng(21)
    short = [rng.bytes(n) for n in (100, 4096, 3000)]
    long = [rng.bytes(70000)]

    async def main():
        out = await asyncio.gather(
            batcher.encode(codec, rng.integers(0, 256, (2, 2, 256),
                                               np.uint8), with_crc=True),
            batcher.digest(digest_rows(short[:2]),
                           [len(b) for b in short[:2]]),
            batcher.digest(digest_rows(short[2:]), [len(short[2])]),
            batcher.digest(digest_rows(long), [len(long[0])]))
        batcher.close()
        return out

    _, a, b, c = asyncio.run(main())
    assert [int(x) for x in list(a) + list(b) + list(c)] == [
        native.crc32c(buf) for buf in short + long]
    dump = perf.dump()
    assert dump["digest_launches"] == 2 and dump["encode_launches"] == 1
    assert dump["digest_stripes"] == 4 and dump["encode_stripes"] == 2
    assert dump["batches"] == 3 and dump["stripes"] == 6
    assert dump["digest_queue_wait_us"] + dump["encode_queue_wait_us"] \
        == dump["queue_wait_us"]
