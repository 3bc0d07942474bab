"""Sharded mesh data plane (ceph_tpu/parallel/mesh_codec.py).

Byte-parity pins: under the conftest's forced 8-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), every
MeshCodec launch -- encode, decode, RMW delta, recovery, ragged tail
lanes, fused CRC -- must be byte-identical to the single-device codec
oracle, the CodecBatcher must run EXACTLY ONE mesh launch per
coalesced batch, and no config lookup may happen inside the launch
loop (the construction-time-snapshot contract).
"""

import asyncio

import numpy as np
import pytest

import jax

from ceph_tpu import native
from ceph_tpu.common.perf import PerfCounters
from ceph_tpu.ec import registry
from ceph_tpu.osd.codec_batcher import CodecBatcher
from ceph_tpu.osd.ec_util import StripeInfo
from ceph_tpu.parallel.mesh_codec import MeshCodec


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def _codec(k="4", m="2"):
    return registry().factory("tpu", {"k": k, "m": m,
                                      "technique": "reed_sol_van"})


def test_mesh_spans_the_forced_host_devices():
    """The conftest forces 8 virtual CPU devices; the data-plane mesh
    must claim all of them -- the tier-1 suite then runs the REAL
    8-way SPMD program, not a 1-device degenerate."""
    assert len(jax.devices()) == 8
    mesh = MeshCodec()
    assert mesh.n_devices == 8
    # and an explicit 1-device mesh is the same code path
    assert MeshCodec(n_devices=1).n_devices == 1


def test_pad_batch_is_pow2_and_device_divisible():
    mesh = MeshCodec()
    n = mesh.n_devices
    for total in (1, 2, 3, 7, 8, 9, 17, 63, 64, 65):
        b = mesh.pad_batch(total)
        assert b >= total
        assert b % n == 0, (total, b)
    # bounded: the bucket ladder stays log2-sized above n
    assert mesh.pad_batch(65) == 128


@pytest.mark.parametrize("k,m", [("2", "1"), ("4", "2"), ("8", "3")])
def test_mesh_encode_byte_identical_to_scalar_codec(k, m):
    codec = _codec(k, m)
    ki, mi = int(k), int(m)
    mesh = MeshCodec()
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (16, ki, 256), dtype=np.uint8)
    parity = mesh.encode(codec, data)
    assert parity.shape == (16, mi, 256)
    want_ids = set(range(ki + mi))
    for s in range(16):
        want = codec.encode(want_ids, data[s].tobytes())
        for r in range(mi):
            assert np.array_equal(parity[s, r], want[ki + r]), (s, r)


def test_mesh_encode_with_crc_matches_host_hash():
    codec = _codec()
    mesh = MeshCodec()
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, (8, 4, 512), dtype=np.uint8)
    parity, crcs = mesh.encode(codec, data, with_crc=True)
    assert crcs.shape == (8, 6)
    full = np.concatenate([data, parity], axis=1)
    for s in range(8):
        for c in range(6):
            assert int(crcs[s, c]) == native.crc32c(
                full[s, c].tobytes()), (s, c)


def test_mesh_decode_byte_identical_incl_parity_erasures():
    """Decode parity: data-only, parity-only and mixed erasure
    patterns all reconstruct byte-exact (recovery's shapes)."""
    codec = _codec()
    mesh = MeshCodec()
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (8, 4, 256), dtype=np.uint8)
    parity = mesh.encode(codec, data)
    full = np.concatenate([data, parity], axis=1)
    for erasures in ([0, 1], [4, 5], [2, 4]):
        didx = [i for i in range(6) if i not in erasures][:4]
        rec = mesh.decode(codec, erasures, full[:, didx])
        for s in range(8):
            for p, e in enumerate(erasures):
                assert np.array_equal(rec[s, p], full[s, e]), \
                    (erasures, s, e)
        # identical to the single-device decode_batch engine
        want = np.asarray(codec.decode_batch(
            erasures, full[:, didx], out_np=True))
        assert np.array_equal(rec, want), erasures


def test_mesh_rmw_delta_matches_full_reencode():
    """Partial-stripe RMW: old_parity XOR encode(delta) equals a full
    re-encode of the mutated stripes (GF linearity, the dry-run's
    sharded_rmw promoted), with the old-parity buffer donated."""
    codec = _codec()
    mesh = MeshCodec()
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, (8, 4, 128), dtype=np.uint8)
    parity = mesh.encode(codec, data)
    piece = rng.integers(0, 256, (8, 32), dtype=np.uint8)
    delta = np.zeros_like(data)
    delta[:, 1, 16:48] = data[:, 1, 16:48] ^ piece
    newdata = data.copy()
    newdata[:, 1, 16:48] = piece
    got = mesh.rmw(codec, parity, delta)
    want = mesh.encode(codec, newdata)
    assert np.array_equal(got, want)


def test_mesh_recovery_via_stripe_info_decode_async():
    """The degraded-read/recovery driver (StripeInfo.decode_async ->
    batcher -> mesh) reconstructs wanted shards byte-exact, including
    a parity shard (the recovery-push shape)."""
    codec = _codec()
    si = StripeInfo.for_codec(codec, stripe_unit=64)
    perf = PerfCounters("ec_batch")
    batcher = CodecBatcher(max_batch=64, flush_timeout=0.2, perf=perf)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, si.stripe_width * 5,
                        dtype=np.uint8).tobytes()
    shards = si.encode(codec, data)
    avail = {i: v for i, v in shards.items() if i not in (0, 5)}

    got = run(si.decode_async(codec, avail, want={0, 5},
                              batcher=batcher))
    assert np.array_equal(got[0], shards[0])
    assert np.array_equal(got[5], shards[5])
    assert perf.get("mesh_launches") == 1


def test_mesh_batcher_ragged_tails_with_crc_byte_exact():
    """Ragged co-submissions share ONE mesh launch: lane padding
    strips back byte-exact and the padded-lane CRCs are un-padded by
    the GF(2) inverse, identical to a host re-hash."""
    codec = _codec(k="2", m="1")
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=32, flush_timeout=0.2, perf=perf)
    rng = np.random.default_rng(6)
    a1 = rng.integers(0, 256, (2, 2, 64), dtype=np.uint8)
    a2 = rng.integers(0, 256, (3, 2, 192), dtype=np.uint8)

    async def main():
        return await asyncio.gather(b.encode(codec, a1, with_crc=True),
                                    b.encode(codec, a2, with_crc=True))

    (p1, c1), (p2, c2) = run(main())
    for arr, par, crcs in ((a1, p1, c1), (a2, p2, c2)):
        full = np.concatenate([arr, par], axis=1)
        for s in range(arr.shape[0]):
            want = codec.encode(set(range(3)), arr[s].tobytes())
            assert np.array_equal(par[s, 0], want[2]), s
            for c in range(3):
                assert int(crcs[s, c]) == native.crc32c(
                    full[s, c].tobytes()), (s, c)
    assert perf.get("batches") == 1
    assert perf.get("mesh_launches") == 1      # ONE launch, fused CRC
    assert perf.get("crc_fused_launches") == 1


def test_exactly_one_mesh_launch_per_coalesced_batch():
    """The acceptance gate, as a unit: N concurrent submissions that
    coalesce into B batches run exactly B mesh launches -- the CRC
    side-path rides inside them, never as a second dispatch."""
    codec = _codec()
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=8, flush_timeout=0.2, perf=perf)
    rng = np.random.default_rng(7)
    arrs = [rng.integers(0, 256, (2, 4, 128), dtype=np.uint8)
            for _ in range(8)]                 # 16 stripes -> 2 batches

    async def main():
        return await asyncio.gather(
            *(b.encode(codec, a, with_crc=True) for a in arrs))

    outs = run(main())
    assert len(outs) == 8
    assert perf.get("batches") == perf.get("mesh_launches") == 2


def test_mesh_launch_failure_reaches_the_waiters():
    """A broken mesh fails the batch's waiters with ITS error: the
    batch is never quietly re-run another way."""
    codec = _codec(k="2", m="1")
    perf = PerfCounters("ec_batch")

    class BoomMesh(MeshCodec):
        def encode(self, *a, **k):
            raise RuntimeError("mesh on fire")

        def decode(self, *a, **k):
            raise RuntimeError("mesh on fire")

    b = CodecBatcher(max_batch=8, flush_timeout=0.2, perf=perf,
                     engine=BoomMesh())
    arr = np.random.default_rng(8).integers(0, 256, (2, 2, 64),
                                            dtype=np.uint8)
    with pytest.raises(RuntimeError, match="mesh on fire"):
        run(b.encode(codec, arr))
    assert perf.get("batches") == 0


def test_donated_rmw_old_parity_aliases_in_place():
    """donate_argnums is live where it can bite: the RMW launch's
    old-parity buffer has the output's exact shape, so donating it
    lets XLA alias the update IN PLACE on device -- the buffer is
    consumed (is_deleted).  (Encode/decode donations are advisory: no
    output matches the (B, k, L) input, so XLA only gets an early-free
    hint there.)"""
    from ceph_tpu.parallel.mesh_codec import _compiled_rmw, _w_device

    codec = _codec(k="2", m="1")
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, (8, 2, 128), dtype=np.uint8)
    mesh = MeshCodec()
    parity = mesh.encode(codec, data)
    mat = np.ascontiguousarray(codec.encode_matrix[codec.k:], np.uint8)
    w = _w_device(mesh.mesh, mat.tobytes(), *mat.shape)
    fn = _compiled_rmw(mesh.mesh, 8, 1, 2, 128)
    oldp = mesh._put(parity)
    out = fn(w, oldp, mesh._put(np.zeros_like(data)))
    out.block_until_ready()
    assert oldp.is_deleted()
    # the aliased update is still byte-correct (zero delta = same
    # parity)
    assert np.array_equal(np.asarray(out), parity)


def test_config_snapshot_no_lookup_in_launch_loop():
    """from_config SNAPSHOTS every knob: after construction, driving
    batches performs ZERO config lookups and the batcher/mesh retain
    no reference to the config object (the micro-assertion the
    ROADMAP's config-reads-on-hot-paths item asked for)."""
    class CountingConf(dict):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.gets = 0

        def get(self, *a, **kw):
            self.gets += 1
            return super().get(*a, **kw)

    conf = CountingConf({"osd_ec_batch_max": 8})
    perf = PerfCounters("ec_batch")
    b = CodecBatcher.from_config(conf, perf=perf)
    assert b is not None
    constructed_gets = conf.gets
    assert constructed_gets > 0

    codec = _codec(k="2", m="1")
    arr = np.random.default_rng(10).integers(0, 256, (2, 2, 64),
                                             dtype=np.uint8)
    for _ in range(3):
        run(b.encode(codec, arr))
    assert conf.gets == constructed_gets, \
        "config lookup inside the launch loop"
    assert perf.get("mesh_launches") == 3
    # no retained handle through which a lookup COULD happen
    held = list(vars(b).values()) + list(vars(b.engine).values())
    assert not any(v is conf for v in held)

    # disabled batching snapshots to None
    assert CodecBatcher.from_config(
        {"osd_ec_batch_enabled": False}) is None


def test_mesh_vs_scalar_oracle_on_stripe_info_write_path():
    """encode_async (the ECBackend full-stripe write driver) through a
    mesh-backed batcher returns shard buffers and whole-shard CRCs
    identical to the unbatched scalar path."""
    codec = _codec()
    si = StripeInfo.for_codec(codec, stripe_unit=64)
    batcher = CodecBatcher(max_batch=16, flush_timeout=0.2)
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, si.stripe_width * 4,
                        dtype=np.uint8).tobytes()
    shards, crcs = run(si.encode_async(codec, data, batcher=batcher,
                                       with_crc=True))
    want = si.encode(codec, data)
    for i in want:
        assert np.array_equal(shards[i], want[i]), i
        assert crcs[i] == native.crc32c(want[i].tobytes()), i
