"""Product-matrix MSR plugin (ec/plugins/pmsr.py).

Pins the whole regenerating-code contract: the systematic flat
generator, MDS decode from any k chunks, beta-sized fragment repair
that is byte-identical to the full decode of the same chunk, the
d/alpha repair-bandwidth arithmetic, profile validation EINVALs at
profile-set AND pool-create, and batched/scheduled launch parity
against the host oracle.
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry


@pytest.fixture()
def registry():
    return ErasureCodePluginRegistry()


def rand_bytes(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=n, dtype=np.uint8).tobytes()


def make(registry, k, m, **extra):
    profile = {"k": str(k), "m": str(m),
               **{key: str(v) for key, v in extra.items()}}
    return registry.factory("pmsr", profile)


# -- construction ------------------------------------------------------------

def test_geometry_and_systematic_generator(registry):
    codec = make(registry, 5, 4)
    assert codec.get_chunk_count() == 9
    assert codec.get_data_chunk_count() == 5
    assert codec.get_sub_chunk_count() == 4          # alpha = k-1
    assert codec.d == 8                              # 2(k-1) = k+m-1
    ka = 5 * 4
    assert codec.generator.shape == (9 * 4, ka)
    assert np.array_equal(codec.generator[:ka],
                          np.eye(ka, dtype=np.uint8))


def test_alignment_splits_chunks_into_alpha(registry):
    # alpha=4 divides 32: SIMD alignment suffices
    assert make(registry, 5, 4).get_alignment() == 32
    # alpha=6 does not: chunks must also split into 6 sub-chunks
    codec = make(registry, 7, 6)
    assert codec.get_alignment() == 32 * 6
    assert codec.get_chunk_size(7 * 100) % 6 == 0


def test_profile_validation_einvals(registry):
    with pytest.raises(ValueError, match="k=2 must be >= 3"):
        make(registry, 2, 2)
    with pytest.raises(ValueError, match="m=2 must be >= k-1"):
        make(registry, 4, 2)
    with pytest.raises(ValueError, match="d=5 is not admissible"):
        make(registry, 4, 3, d=5)
    # the default d equals 2(k-1) and is accepted explicitly too
    assert make(registry, 4, 3, d=6).d == 6


def test_pool_create_validates_profile_like_profile_set():
    """The monitor instantiates the plugin at BOTH gates (profile-set
    and pool-create), so a bad pmsr profile raises the same EINVAL at
    each -- mirroring the PR 1 stripe_unit ladder."""
    from ceph_tpu.ec import registry as live_registry
    with pytest.raises(ValueError, match="m=1 must be >= k-1"):
        live_registry().factory("pmsr", {"k": "3", "m": "1"})


# -- round-trips -------------------------------------------------------------

def test_roundtrip_all_single_and_double_erasures(registry):
    codec = make(registry, 3, 2)
    n = codec.get_chunk_count()
    data = rand_bytes(3 * 128 + 17, seed=42)
    chunks = codec.encode(set(range(n)), data)
    got = b"".join(bytes(chunks[i]) for i in range(3))
    assert got[:len(data)] == data                   # systematic
    patterns = [[e] for e in range(n)]
    patterns += [[a, b] for a in range(n) for b in range(a + 1, n)]
    for erased in patterns:
        avail = {i: chunks[i] for i in range(n) if i not in erased}
        decoded = codec.decode(set(range(n)), avail)
        for e in erased:
            assert np.array_equal(decoded[e], chunks[e]), erased


def test_beyond_capability_raises(registry):
    codec = make(registry, 3, 2)
    n = codec.get_chunk_count()
    data = rand_bytes(3 * 64, seed=1)
    chunks = codec.encode(set(range(n)), data)
    avail = {i: chunks[i] for i in range(n) if i not in (0, 1, 2)}
    with pytest.raises(IOError):
        codec.decode({0, 1, 2}, avail)


# -- fragment repair ---------------------------------------------------------

def test_fragment_repair_matches_global_decode_bytewise(registry):
    """The acceptance pin: for every single failure, the fragment
    aggregate is byte-identical to the full k-chunk decode of the same
    chunk, and the helper traffic is d * (chunk/alpha) bytes -- d/alpha
    chunks' worth, strictly under k."""
    codec = make(registry, 5, 4)
    n, d, a = codec.get_chunk_count(), codec.d, codec.alpha
    data = rand_bytes(5 * 256, seed=3)
    chunks = codec.encode(set(range(n)), data)
    csize = len(chunks[0])
    for lost in range(n):
        helpers = sorted(set(range(n)) - {lost})[:d]
        frags = {h: codec.fragment_for(lost, chunks[h])
                 for h in helpers}
        rec = codec.aggregate_fragments(lost, frags)
        have = {i: chunks[i] for i in range(n) if i != lost}
        dec = codec.decode({lost}, have)[lost]
        assert np.array_equal(rec, dec), lost
        assert np.array_equal(rec, chunks[lost]), lost
        traffic = sum(len(f) for f in frags.values())
        assert traffic == d * csize // a
        assert traffic < codec.k * csize             # beats RS repair


def test_fragment_repair_any_helper_subset(registry):
    """Repair works from ANY d survivors, not just the first d (the
    aggregate matrix inverts the helper-specific Psi rows)."""
    codec = make(registry, 3, 2)
    n, d = codec.get_chunk_count(), codec.d
    data = rand_bytes(3 * 96, seed=5)
    chunks = codec.encode(set(range(n)), data)
    lost = 1
    helpers = sorted(set(range(n)) - {lost})[-d:]    # the LAST d
    frags = {h: codec.fragment_for(lost, chunks[h]) for h in helpers}
    rec = codec.aggregate_fragments(lost, frags)
    assert np.array_equal(rec, chunks[lost])


def test_fragment_multi_stripe_chunk_size(registry):
    """Multi-stripe shard buffers reshape per the snapshot stripe
    chunk size (the backend sets it at pool attach): fragments over a
    3-stripe shard equal the per-stripe fragments concatenated."""
    codec = make(registry, 3, 2)
    n = codec.get_chunk_count()
    cs = codec.get_chunk_size(3 * 64)
    stripes = [codec.encode(set(range(n)), rand_bytes(3 * 64, seed=s))
               for s in (10, 11, 12)]
    codec.set_fragment_chunk_size(cs)
    shard0 = np.concatenate([st[0] for st in stripes])
    frag = codec.fragment_for(2, shard0)
    want = np.concatenate([codec.fragment_for(2, st[0])
                           for st in stripes])
    assert np.array_equal(frag, want)


def test_minimum_to_repair_returns_beta_fragment_spec(registry):
    codec = make(registry, 3, 2)
    n, d = codec.get_chunk_count(), codec.d
    plan = codec.minimum_to_repair(0, set(range(1, n)))
    assert plan is not None and len(plan) == d
    assert all(spec == [(0, 1)] for spec in plan.values())
    # fewer than d survivors: no fragment plan, MDS decode serves
    assert codec.minimum_to_repair(0, {1, 2, 3}) is None


# -- batched launch parity ---------------------------------------------------

def test_batched_encode_decode_matches_host(registry):
    from ceph_tpu.osd.codec_batcher import CodecBatcher
    from ceph_tpu.osd.ec_util import StripeInfo
    codec = make(registry, 3, 2)
    sinfo = StripeInfo.for_codec(codec, codec.get_alignment())
    data = rand_bytes(sinfo.stripe_width * 3, seed=9)
    host = sinfo.encode(codec, data)

    async def drive():
        batcher = CodecBatcher(max_batch=8)
        assert batcher.supports(codec)
        shards = await sinfo.encode_async(codec, data,
                                          batcher=batcher)
        for i in host:
            assert np.array_equal(host[i], shards[i]), i
        n = codec.get_chunk_count()
        for lost in range(n):
            have = {i: shards[i] for i in range(n) if i != lost}
            got = await sinfo.decode_async(codec, have, want={lost},
                                           batcher=batcher)
            assert np.array_equal(got[lost], shards[lost]), lost
        batcher.close()

    asyncio.new_event_loop().run_until_complete(drive())


def test_scheduled_engine_parity(registry, monkeypatch):
    """CEPH_TPU_XOR_SCHED=1 forces the CSE-minimized scheduled engine:
    the codec's ``encode_batch`` must stay byte-identical to the host
    driver (a parity-gate miss would raise)."""
    monkeypatch.setenv("CEPH_TPU_XOR_SCHED", "1")
    from ceph_tpu.ops.xor_schedule import STATS
    from ceph_tpu.osd.ec_util import StripeInfo
    codec = make(registry, 3, 2)
    sinfo = StripeInfo.for_codec(codec, codec.get_alignment())
    data = rand_bytes(sinfo.stripe_width * 2, seed=13)
    host = sinfo.encode(codec, data)
    before = STATS.snapshot()
    arr = np.frombuffer(data, np.uint8).reshape(
        2, codec.k, sinfo.chunk_size)
    parity = np.asarray(codec.encode_batch(arr, out_np=True))
    for r, pos in enumerate(p for p in range(codec.get_chunk_count())
                            if p not in sinfo.data_positions(codec)):
        assert np.array_equal(host[pos],
                              parity[:, r].reshape(-1)), pos
    after = STATS.snapshot()
    assert after[0] > before[0]          # scheduled launches served
