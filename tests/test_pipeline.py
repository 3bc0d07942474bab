"""The pipelined OSD write hot path.

Three contracts:

* BYTE PARITY: a cluster drive reads back exactly the payloads it
  wrote -- the double-buffered batcher, the deferred commits and the
  coalesced sub-op flushes may reorder WORK, never BYTES;
* ORDERING: per (PG, object), commits complete and replies ack in
  version order even when the fan-outs overlap, and the final content
  is the last write's;
* FAULT DRAIN: killing an OSD mid-pipeline (under the deterministic
  MessageFaultInjector) leaves zero wedged ops, no orphaned staged
  batches in any batcher, and no parked sub-op flushes in any pipe.
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.client.rados import Rados
from ceph_tpu.common.faults import MessageFaultInjector
from ceph_tpu.loadgen.cluster import SimCluster
from ceph_tpu.osd.codec_batcher import CodecBatcher


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _payload(i: int, size: int) -> bytes:
    rng = np.random.default_rng(1000 + i)
    return rng.integers(0, 256, size, dtype=np.uint8).tobytes()


async def _boot_ec_cluster(n_osds=4, *, osd_config=None, faults=None,
                           k=2, m=1, pg_num=8):
    cluster = await SimCluster.create(n_osds, osd_config=osd_config,
                                      faults=faults)
    rados = await Rados(cluster.addr, name="client.pipe").connect()
    await rados.mon_command(
        "osd erasure-code-profile set",
        {"name": "pipe-prof", "profile": {
            "plugin": "tpu", "k": str(k), "m": str(m),
            "technique": "reed_sol_van"}})
    await rados.pool_create("pipepool", pg_num=pg_num,
                            pool_type="erasure",
                            erasure_code_profile="pipe-prof")
    io = await rados.open_ioctx("pipepool")
    return cluster, rados, io


async def _drive(n_objects=24, size=12 << 10):
    """Write a deterministic working set (full writes + overwrites +
    partial RMWs), read every object back, return what was written,
    what was read and the summed ec_pipeline counters."""
    cluster, rados, io = await _boot_ec_cluster()
    try:
        names = [f"obj-{i:03d}" for i in range(n_objects)]
        written = {n: _payload(i, size) for i, n in enumerate(names)}
        # concurrent full writes: this is what coalesces and overlaps
        await asyncio.gather(*(io.write_full(n, written[n])
                               for n in names))
        # overwrite a slice of them concurrently (per-object chains)
        for i, n in enumerate(names[:8]):
            written[n] = _payload(i + 500, size)
        await asyncio.gather(*(io.write_full(n, written[n])
                               for n in names[:8]))
        # ranged RMWs ride the delta path
        patches = {n: _payload(i + 900, 2048)
                   for i, n in enumerate(names[8:16])}
        for n, patch in patches.items():
            written[n] = (written[n][:1024] + patch
                          + written[n][1024 + len(patch):])
        await asyncio.gather(*(io.write(n, patch, offset=1024)
                               for n, patch in patches.items()))
        content = {}
        for n in names:
            content[n] = await io.read(n)
        pipe = {}
        for osd in cluster.osds:
            pc = osd.perf.get("ec_pipeline")
            if pc is None:
                continue
            for key, val in pc.dump().items():
                if isinstance(val, (int, float)):
                    pipe[key] = pipe.get(key, 0) + val
        return written, content, pipe
    finally:
        await rados.shutdown()
        await cluster.stop()


@pytest.mark.slow
def test_pipelined_bytes_match_payloads_written():
    """Every object reads back as the payloads written to it, in
    order, and the drive's overlap counters are live."""
    written, read, pipe_on = run(_drive())
    assert set(written) == set(read)
    for name in written:
        assert written[name] == read[name], name
    # the spine must actually pipeline
    assert pipe_on.get("staged_batches", 0) > 0
    assert pipe_on.get("overlapped_commits", 0) > 0
    assert pipe_on.get("commit_overlap_ms", 0) > 0
    assert pipe_on.get("flush_windows", 0) > 0


@pytest.mark.slow
def test_commit_ack_ordering_per_object():
    """Overlapping writes to ONE object ack in version order and the
    final bytes are the last write's -- the per-(PG, object) chain is
    what keeps client-visible semantics serial while the fan-outs
    overlap."""
    async def main():
        cluster, rados, io = await _boot_ec_cluster()
        try:
            payloads = [_payload(i, 8 << 10) for i in range(6)]
            versions = []

            async def one(i):
                data, _ = await io._op("hot-object", [
                    {"op": "writefull", "data": payloads[i]}])
                versions.append((i, tuple(data["version"])))

            # issue strictly in order from one client task context so
            # submission order is deterministic; completions overlap
            await asyncio.gather(*(one(i) for i in range(6)))
            # acks arrived version-monotone in issue order
            issued = [v for _, v in sorted(versions)]
            assert issued == sorted(issued)
            got = await io.read("hot-object")
            assert got == payloads[5]
            # a fresh read observes the settled chain
            for osd in cluster.osds:
                for pg in osd.pgs.values():
                    assert not pg._obj_commits, pg.pgid
            return True
        finally:
            await rados.shutdown()
            await cluster.stop()

    assert run(main())


@pytest.mark.slow
def test_kill_mid_pipeline_drains_clean():
    """An OSD killed mid-pipeline under deterministic chaos leaves
    zero wedged ops (every client call returns), no orphaned staged
    batches, and no parked sub-op flushes."""
    async def main():
        faults = MessageFaultInjector(seed=11)
        # chaos on the commit path itself: some sub-op writes vanish
        faults.drop(mtype="ec_subop_write", probability=0.08)
        cluster, rados, io = await _boot_ec_cluster(
            n_osds=5, faults=faults)
        try:
            names = [f"chaos-{i:03d}" for i in range(20)]

            async def write_all(salt):
                return await asyncio.gather(*(
                    io.write_full(n, _payload(i + salt, 8 << 10))
                    for i, n in enumerate(names)),
                    return_exceptions=True)

            got0 = await write_all(0)
            assert not any(isinstance(g, Exception) for g in got0)
            # kill an OSD while a second wave is in flight.  EVERY op
            # must RETURN (an EAGAIN while its PG re-peers around the
            # dead shard is legal; a hang is the wedge this test
            # exists to catch) -- the 30s client deadline inside the
            # bounded wait IS the no-wedge assertion.
            wave = asyncio.ensure_future(write_all(50))
            await asyncio.sleep(0.05)
            token = await cluster.kill_osd(len(cluster.osds) - 1)
            outcomes = await asyncio.wait_for(wave, 60)
            await cluster.wait_down(token["whoami"], timeout=30)
            # after re-peer settles, the spine converges: a retried
            # write and a degraded read both serve
            await io.write_full(names[0], _payload(50, 8 << 10))
            got = await io.read(names[0])
            assert got == _payload(50, 8 << 10)
            assert len(outcomes) == len(names)
            for osd in cluster.osds:
                if osd._stopped:
                    continue
                if osd.codec_batcher is not None:
                    assert not osd.codec_batcher._staged
                if osd.subop_pipe is not None:
                    assert osd.subop_pipe._n_staged == 0
                for pg in osd.pgs.values():
                    for t in pg._obj_commits.values():
                        assert t.done()
            return True
        finally:
            await rados.shutdown()
            await cluster.stop()

    assert run(main())


# -- batcher double-buffering units (tier-1 fast) ---------------------------

class _XorCodec:
    """What the batcher reads of a codec: the grouping signature."""

    def __init__(self, k=3, m=1):
        self.k, self.m = k, m
        self.encode_matrix = np.vstack([np.eye(k, dtype=np.uint8),
                                        np.ones((m, k), np.uint8)])


class _XorEngine:
    """Tiny deterministic stand-in launch engine: every output row is
    the XOR of the input rows, host arrays throughout."""

    def supports(self, codec):
        return True

    def pad_batch(self, total):
        return 1 << max(0, total - 1).bit_length()

    @staticmethod
    def _xor_rows(batch, rows):
        out = np.bitwise_xor.reduce(batch, axis=1, keepdims=True)
        return np.repeat(out, rows, axis=1)

    def encode(self, codec, batch, with_crc=False, out_np=True):
        out = self._xor_rows(batch, codec.m)
        return (out, None) if with_crc else out

    def decode(self, codec, erasures, batch, out_np=True):
        return self._xor_rows(batch, len(erasures))

    def rmw(self, codec, old_parity, delta, out_np=True):
        return old_parity ^ self._xor_rows(delta, codec.m)


def _stripes(seed, n=4, k=3, lane=512):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, k, lane), dtype=np.uint8)


def test_batcher_pipeline_parity_and_counters():
    """Concurrent submissions, coalesced, padded and staged, come back
    byte-identical to the engine called once per op."""
    class Perf(dict):
        def inc(self, k, by=1):
            self[k] = self.get(k, 0) + by

        def hist_register(self, *a):
            pass

        def hist_sample(self, *a):
            pass

    engine, codec = _XorEngine(), _XorCodec()

    async def drive():
        perf = Perf()
        b = CodecBatcher(max_batch=64, engine=engine, pipe_perf=perf)
        outs = await asyncio.gather(*(
            b.encode(codec, _stripes(s)) for s in range(6)))
        b.close()
        return outs, perf

    outs, perf = run(drive())
    for s, out in enumerate(outs):
        assert np.array_equal(out, engine.encode(codec, _stripes(s)))
    assert perf.get("staged_batches", 0) > 0


def test_batcher_close_drains_staged():
    """close() launches every parked batch synchronously -- no staged
    batch may outlive the batcher (an orphan wedges its op)."""
    async def main():
        b = CodecBatcher(max_batch=1024, engine=_XorEngine(),
                         flush_timeout=60.0, eager_flush=False)
        codec = _XorCodec()
        fut = asyncio.ensure_future(b.encode(codec, _stripes(1)))
        await asyncio.sleep(0.01)    # let it flush into the stage
        b.close()
        assert not b._staged
        out = await asyncio.wait_for(fut, 5)
        want = np.bitwise_xor.reduce(_stripes(1), axis=1,
                                     keepdims=True)
        assert np.array_equal(np.asarray(out), want)
        return True

    assert run(main())


def test_staging_depth_bounds_and_counts_stalls():
    """A flush finding the staging queue full launches inline and
    counts the stall -- parked host memory stays bounded."""
    class Perf(dict):
        def inc(self, k, by=1):
            self[k] = self.get(k, 0) + by

        def hist_register(self, *a):
            pass

        def hist_sample(self, *a):
            pass

    async def main():
        perf = Perf()
        b = CodecBatcher(max_batch=1, engine=_XorEngine(),
                         staging_depth=1, pipe_perf=perf)
        codec = _XorCodec()
        # max_batch=1: every submission flushes instantly; depth=1
        # forces later flushes of the same tick inline
        outs = await asyncio.gather(*(
            b.encode(codec, _stripes(s, n=1)) for s in range(8)))
        b.close()
        assert len(outs) == 8
        assert perf.get("stage_stalls", 0) > 0
        assert perf.get("staged_batches", 0) > 0
        return True

    assert run(main())


# -- sub-op pipe units ------------------------------------------------------

def test_subop_pipe_coalesces_and_orders():
    """Messages staged for one peer in one window arrive as ONE frame
    and dispatch in staging order."""
    from ceph_tpu.msg import Message, Messenger
    from ceph_tpu.msg.messenger import SubOpPipe

    class Perf(dict):
        def inc(self, k, by=1):
            self[k] = self.get(k, 0) + by

    async def main():
        got = []
        a = Messenger("a")
        b = Messenger("b")
        await b.bind()

        async def d(conn, msg):
            got.append((msg.type, msg.data.get("i"),
                        [bytes(s) for s in msg.segments]))

        b.add_dispatcher(d)
        perf = Perf()
        pipe = SubOpPipe(a, perf=perf)
        for i in range(3):
            pipe.stage(b.addr, "b",
                       Message("ec_subop_write",
                               {"i": i}, segments=[b"s%d" % i]))
        await asyncio.sleep(0.2)
        assert [g[1] for g in got] == [0, 1, 2]
        assert [g[2] for g in got] == [[b"s0"], [b"s1"], [b"s2"]]
        assert perf.get("coalesced_subops") == 3
        assert perf.get("flush_windows", 0) >= 1
        # ONE wire frame carried all three (outer seq space moved once)
        assert a.conns["b"].out_seq == 1
        await pipe.close()
        await a.shutdown()
        await b.shutdown()
        return True

    assert run(main())


def test_subop_pipe_send_failure_fails_staged():
    """A dead peer fails every staged message's on_error hook -- the
    op layer sees the same per-send errors as the unbatched path."""
    from ceph_tpu.msg import Message, Messenger
    from ceph_tpu.msg.messenger import SubOpPipe

    async def main():
        a = Messenger("a")
        errors = []
        pipe = SubOpPipe(a)
        for i in range(2):
            pipe.stage(("127.0.0.1", 1), "ghost",
                       Message("ec_subop_write", {"i": i}),
                       on_error=errors.append)
        await asyncio.sleep(0.2)
        assert len(errors) == 2
        await pipe.close()
        await a.shutdown()
        return True

    assert run(main())
