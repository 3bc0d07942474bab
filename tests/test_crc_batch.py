"""Batched device-fused integrity pipeline (ceph_tpu/ops/crc32c_batch).

Contracts pinned here:

* ``crc32c_batch`` / ``crc32c_rows`` are byte-identical to the scalar
  ``native.crc32c`` across randomized ragged batches (empty buffers,
  1-byte, non-multiple-of-slice lengths), on every backend of the
  ladder (native batch entry, numpy engine, device kernel);
* the GF(2) register algebra holds: ``crc(a+b) == combine(crc(a),
  crc(b), len(b))``, zeros-advance matches feeding literal zero bytes,
  and strip-zeros inverts it;
* the fused encode+CRC launch returns CRCs identical to a host
  recompute of the emitted shards, through every layer (codec entry
  point, CodecBatcher, StripeInfo.encode_async);
* ``shard_crc`` is unified on CRC32C with a one-shot compat accept for
  pre-unification zlib.crc32 ``_crc`` xattrs;
* the batched consumers (scrub map, blockstore) digest through the
  batched API -- scalar-call count stays 0 on those paths.
"""

import asyncio
import zlib

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.ops import crc32c_batch as cb


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


RAGGED_LENS = [0, 1, 2, 7, 8, 9, 15, 16, 17, 63, 64, 65, 255, 256,
               257, 511, 512, 513, 1000, 4095, 4096, 4097, 20000]


def _ragged(rng, lens=RAGGED_LENS):
    return [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
            for n in lens]


# -- batched == scalar parity ------------------------------------------------

@pytest.mark.parametrize("backend", [None, "numpy"])
def test_ragged_batch_matches_scalar(backend):
    rng = np.random.default_rng(0)
    lens = RAGGED_LENS + [int(x) for x in rng.integers(0, 9000, 16)]
    bufs = _ragged(rng, lens)
    got = cb.crc32c_batch(bufs, backend=backend)
    for ln, g, b in zip(lens, got, bufs):
        assert int(g) == native.crc32c(b), (backend, ln)


@pytest.mark.parametrize("backend", [None, "numpy"])
def test_rows_with_ragged_lengths_match_scalar(backend):
    rng = np.random.default_rng(1)
    arr = rng.integers(0, 256, size=(40, 1333), dtype=np.uint8)
    lens = rng.integers(0, 1334, size=40)
    got = cb.crc32c_rows(arr, lengths=lens, backend=backend)
    for i in range(40):
        assert int(got[i]) == native.crc32c(arr[i, :lens[i]].tobytes())


def test_custom_seed_matches_scalar():
    rng = np.random.default_rng(2)
    bufs = _ragged(rng, [0, 5, 100, 999])
    for seed in (0, 0x12345678, 0xFFFFFFFF):
        for backend in (None, "numpy"):
            got = cb.crc32c_batch(bufs, seed=seed, backend=backend)
            for g, b in zip(got, bufs):
                assert int(g) == native.crc32c(b, seed)


def test_numpy_one_is_the_py_fallback():
    rng = np.random.default_rng(3)
    for n in (0, 1, 13, 512, 70000):
        b = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        assert cb.crc32c_numpy_one(b) == native.crc32c(b)
        assert native._crc32c_py(b, 0xFFFFFFFF) == native.crc32c(b)


@pytest.mark.parametrize("n", [0, 1, 13, 70000])
def test_scalar_crc_takes_every_kind_of_buffer_and_chains(n):
    """``bytes`` and writable views go to the library by address, the
    rest through numpy: one answer, and ``crc32c(b, crc32c(a))`` is the
    register of ``a + b`` (the messenger chains a frame's parts so)."""
    b = np.random.default_rng(n).integers(0, 256, n, np.uint8).tobytes()
    want = native._crc32c_py(b, 0xFFFFFFFF)
    ba = bytearray(b)
    for buf in (b, ba, memoryview(ba), memoryview(b),
                np.frombuffer(b, np.uint8), memoryview(ba)[n // 3:]):
        part = b[n // 3:] if len(buf) != n else b
        assert native.crc32c(buf) == (
            want if part is b else native._crc32c_py(part, 0xFFFFFFFF))
    cut = n // 2
    assert native.crc32c(memoryview(ba)[cut:],
                         native.crc32c(b[:cut])) == want
    assert native.crc32c(b, 7) == native._crc32c_py(b, 7)


def test_empty_batch_and_empty_buffers():
    assert cb.crc32c_batch([]).shape == (0,)
    got = cb.crc32c_batch([b"", b"", b""])
    assert all(int(g) == 0xFFFFFFFF for g in got)


# -- GF(2) register algebra --------------------------------------------------

def test_combine_identity_randomized():
    rng = np.random.default_rng(4)
    for _ in range(24):
        na, nb = int(rng.integers(0, 6000)), int(rng.integers(0, 6000))
        a = rng.integers(0, 256, na, dtype=np.uint8).tobytes()
        b = rng.integers(0, 256, nb, dtype=np.uint8).tobytes()
        assert cb.crc32c_combine(
            native.crc32c(a), native.crc32c(b), nb) \
            == native.crc32c(a + b)


def test_zeros_advance_matches_literal_zero_bytes():
    c = native.crc32c(b"payload")
    for n in (0, 1, 7, 255, 4096, 100000):
        assert cb.crc32c_zeros(c, n) == native.crc32c(b"\0" * n, c)


def test_strip_zeros_inverts_zero_extension():
    rng = np.random.default_rng(5)
    crcs, pads = [], []
    for _ in range(16):
        n, z = int(rng.integers(0, 3000)), int(rng.integers(0, 3000))
        buf = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        crcs.append((native.crc32c(buf + b"\0" * z),
                     native.crc32c(buf)))
        pads.append(z)
    got = cb.crc32c_strip_zeros(
        np.array([c for c, _ in crcs], np.uint32), np.array(pads))
    for g, (_, want) in zip(got, crcs):
        assert int(g) == want


def test_fold_chunk_crcs_equals_whole_buffer():
    rng = np.random.default_rng(6)
    for n_chunks, clen in ((0, 64), (1, 64), (5, 256), (9, 1000)):
        chunks = [rng.integers(0, 256, clen, dtype=np.uint8).tobytes()
                  for _ in range(n_chunks)]
        crcs = np.array([[native.crc32c(c)] for c in chunks],
                        np.uint32).reshape(n_chunks, 1)
        got = cb.fold_chunk_crcs(crcs, clen)
        assert int(got[0]) == native.crc32c(b"".join(chunks))


# -- device kernel / fused encode+CRC ---------------------------------------

@pytest.mark.parametrize("l", (0, 1, 7, 8, 100, 511, 512, 513, 776,
                               4096, 5000, 131072))
def test_device_chunk_crcs_match_scalar(l):
    rng = np.random.default_rng(7 + l)
    x = rng.integers(0, 256, size=(6, l), dtype=np.uint8)
    got = np.asarray(cb.crc32c_device_chunks(x))
    assert got.shape == (6,) and got.dtype == np.uint32
    for i in range(6):
        assert int(got[i]) == native.crc32c(x[i].tobytes()), (l, i)


def test_device_chunk_crcs_keep_leading_axes_and_extremes():
    """(..., L) in, (...,) out; all-zero and all-ones rows are where a
    wrong seed term or a dropped leading segment would show."""
    x = np.zeros((2, 3, 1000), np.uint8)
    x[1] = 0xFF
    x[0, 2, -1] = 1
    got = np.asarray(cb.crc32c_device_chunks(x))
    assert got.shape == (2, 3)
    for i in range(2):
        for j in range(3):
            assert int(got[i, j]) == native.crc32c(x[i, j].tobytes())


def test_device_chunk_crcs_blocked_rows_match_unblocked(monkeypatch):
    """More segment rows than a block holds: the program maps over
    blocks (a ragged last block included) and gives the same CRCs."""
    rng = np.random.default_rng(70)
    x = rng.integers(0, 256, size=(5, 3000), dtype=np.uint8)
    want = cb.crc32c_rows(x)
    monkeypatch.setattr(cb, "_ROW_BLOCK", 16)    # 5 rows x 8 segments
    cb._crc_chunks_compiled.cache_clear()
    try:
        got = np.asarray(cb.crc32c_device_chunks(x))
    finally:
        cb._crc_chunks_compiled.cache_clear()
    assert np.array_equal(got, want)


def test_device_kernel_has_no_loop_over_bytes():
    """The store's launch shape (128 stripes of k=8,m=3 at 4096 bytes)
    compiles to straight-line matmuls: no ``while`` in the jaxpr or in
    the compiled program, so the serial table walk cannot come back
    unnoticed."""
    import jax
    fn = cb._crc_chunks_compiled(4096)
    x = jax.ShapeDtypeStruct((1408, 4096), np.uint8)

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    names = set(primitives(jax.make_jaxpr(fn)(x).jaxpr))
    assert "dot_general" in names
    assert not names & {"while", "scan", "gather", "dynamic_slice"}, names
    assert "while" not in fn.lower(x).compile().as_text()


def test_segment_matrix_rows_are_single_bit_registers():
    """Row (b, p) of the segment matrix is the zero-seed register of a
    segment whose only set bit is bit b of byte p."""
    w = cb._segment_matrix()
    assert w.shape == (8, cb._SEG, 32) and w.dtype == np.int8
    for b, p in ((0, 0), (7, 0), (3, 200), (0, cb._SEG - 1),
                 (7, cb._SEG - 1)):
        seg = bytearray(cb._SEG)
        seg[p] = 1 << b
        reg = native.crc32c(bytes(seg), 0)
        assert int((w[b, p].astype(np.uint32)
                    << np.arange(32, dtype=np.uint32)).sum()) == reg


STORE_SHAPES = [
    # (k, m, technique, stripe rows written, rows launched)
    ("8", "3", "reed_sol_van", 128, 128),     # rs_k8m3, 4 MiB object
    ("10", "4", "cauchy", 103, 128),          # cauchy_k10m4: 103 -> 128
]


@pytest.mark.parametrize("k,m,technique,rows,launched", STORE_SHAPES)
def test_mesh_encode_with_crc_store_shapes_match_host_rehash(
        k, m, technique, rows, launched):
    """The benchmark cells' launch shapes at real width through
    MeshCodec.encode(with_crc=True): every chunk CRC equals a host
    re-hash of the emitted shard bytes, padding rows included."""
    from ceph_tpu.ec import registry
    from ceph_tpu.parallel.mesh_codec import MeshCodec
    codec = registry().factory("tpu", {"k": k, "m": m,
                                       "technique": technique})
    ki, mi = int(k), int(m)
    rng = np.random.default_rng(14)
    data = np.zeros((launched, ki, 4096), np.uint8)
    data[:rows] = rng.integers(0, 256, (rows, ki, 4096), dtype=np.uint8)
    parity, crcs = MeshCodec().encode(codec, data.copy(), with_crc=True)
    assert crcs.shape == (launched, ki + mi)
    assert np.array_equal(
        parity, np.asarray(codec.encode_batch(data, out_np=True)))
    full = np.concatenate([data, parity], axis=1)
    want = cb.crc32c_rows(full.reshape(-1, 4096))
    assert np.array_equal(np.asarray(crcs, np.uint32).reshape(-1), want)
    assert int(crcs[0, 0]) == native.crc32c(data[0, 0].tobytes())


@pytest.mark.parametrize("n", (0, 1, 63, 64, 1000, 64 * 256 + 17,
                               1_000_003))
def test_digest_launch_matches_scalar_on_ragged_buffers(n):
    """Whole-buffer CRC of resident shards through the digest program
    (a deep scrub's device route): buffers of any length sit at the
    end of a power-of-two row, ragged ones share a launch, and the
    host folds each one's own length in."""
    from ceph_tpu.parallel.mesh_codec import MeshCodec
    rng = np.random.default_rng(15)
    bufs = [rng.integers(0, 256, n, dtype=np.uint8),
            rng.integers(0, 256, n // 3, dtype=np.uint8).tobytes()]
    rows = cb.digest_rows(bufs)
    assert rows.shape == (2, cb.digest_lane(n))
    mesh = MeshCodec()
    padded = np.zeros((mesh.pad_batch(2), rows.shape[1]), np.uint8)
    padded[:2] = rows
    got = cb.digest_finish(mesh.digest(padded)[:2], [n, n // 3])
    assert [int(c) for c in got] == [
        native.crc32c(bytes(b)) for b in bufs]


def test_fused_encode_crc_byte_identity_vs_host_recompute():
    """MeshCodec.encode(with_crc=True) on a one-device mesh: parity
    identical to the codec's own encode_batch, CRCs identical to a
    host re-hash of the emitted chunks."""
    from ceph_tpu.ec import registry
    from ceph_tpu.parallel.mesh_codec import MeshCodec
    codec = registry().factory("tpu", {"k": "3", "m": "2",
                                       "technique": "reed_sol_van"})
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, size=(4, 3, 512), dtype=np.uint8)
    parity, crcs = MeshCodec(n_devices=1).encode(codec, data.copy(),
                                                 with_crc=True)
    want_parity = np.asarray(codec.encode_batch(data, out_np=True))
    assert np.array_equal(parity, want_parity)
    full = np.concatenate([data, parity], axis=1)
    for s in range(4):
        for c in range(5):
            assert int(crcs[s, c]) == native.crc32c(
                full[s, c].tobytes()), (s, c)


def test_batcher_with_crc_matches_host_and_strips_ragged_lanes():
    """CodecBatcher.encode(with_crc): chunk CRCs ride the launch; a
    ragged-lane co-submission gets its padded-lane CRCs stripped back
    to its true length."""
    from ceph_tpu.ec import registry
    from ceph_tpu.osd.codec_batcher import CodecBatcher
    codec = registry().factory("tpu", {"k": "2", "m": "1",
                                       "technique": "reed_sol_van"})
    b = CodecBatcher(max_batch=16, flush_timeout=0.2)
    rng = np.random.default_rng(9)
    a1 = rng.integers(0, 256, (2, 2, 64), dtype=np.uint8)
    a2 = rng.integers(0, 256, (1, 2, 128), dtype=np.uint8)

    async def main():
        return await asyncio.gather(b.encode(codec, a1, with_crc=True),
                                    b.encode(codec, a2, with_crc=True))

    (p1, c1), (p2, c2) = run(main())
    for arr, par, crcs in ((a1, p1, c1), (a2, p2, c2)):
        full = np.concatenate([arr, par], axis=1)
        for s in range(arr.shape[0]):
            for c in range(3):
                assert int(crcs[s, c]) == native.crc32c(
                    full[s, c].tobytes()), (s, c)


def test_encode_async_with_crc_returns_whole_shard_crcs():
    from ceph_tpu.ec import registry
    from ceph_tpu.osd.codec_batcher import CodecBatcher
    from ceph_tpu.osd.ec_util import StripeInfo
    codec = registry().factory("tpu", {"k": "2", "m": "1",
                                       "technique": "reed_sol_van"})
    si = StripeInfo.for_codec(codec, stripe_unit=64)
    batcher = CodecBatcher(max_batch=8, flush_timeout=0.2)
    rng = np.random.default_rng(10)
    data = rng.integers(0, 256, si.stripe_width * 3,
                        dtype=np.uint8).tobytes()

    async def main():
        return await si.encode_async(codec, data, batcher=batcher,
                                     with_crc=True)

    shards, crcs = run(main())
    for i, buf in shards.items():
        assert crcs[i] == native.crc32c(buf.tobytes()), i
    # fallback (no batcher) agrees
    shards2, crcs2 = run(si.encode_async(codec, data, with_crc=True))
    assert crcs2 == crcs


def test_encode_async_with_crc_non_batch_codec_fallback():
    from ceph_tpu.ec import registry
    from ceph_tpu.osd.ec_util import StripeInfo
    from ceph_tpu.osd.codec_batcher import CodecBatcher
    isa = registry().factory("isa", {"k": "2", "m": "1"})
    si = StripeInfo.for_codec(isa, stripe_unit=64)
    batcher = CodecBatcher()
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, si.stripe_width * 2,
                        dtype=np.uint8).tobytes()
    shards, crcs = run(si.encode_async(isa, data, batcher=batcher,
                                       with_crc=True))
    for i, buf in shards.items():
        assert crcs[i] == native.crc32c(buf.tobytes()), i


# -- shard_crc polynomial unification ---------------------------------------

def test_shard_crc_is_crc32c():
    from ceph_tpu.osd.backend import shard_crc
    for b in (b"", b"x", b"shard-bytes" * 100):
        assert shard_crc(b) == native.crc32c(b)
        assert shard_crc(bytearray(b)) == native.crc32c(b)


def test_shard_crc_matches_accepts_legacy_zlib_tags():
    """Pre-unification ``_crc`` xattrs were zlib.crc32: the compat
    check accepts them (one-shot, on the mismatch path only) while
    corrupt tags still fail."""
    from ceph_tpu.osd.backend import shard_crc_matches
    buf = b"pre-unification shard" * 7
    new_tag = native.crc32c(buf)
    old_tag = zlib.crc32(buf) & 0xFFFFFFFF
    assert shard_crc_matches(buf, new_tag)
    assert shard_crc_matches(buf, old_tag)          # legacy accept
    assert shard_crc_matches(buf, None)             # untagged
    assert not shard_crc_matches(buf, (new_tag ^ 1))
    # precomputed CRC from a batched pass short-circuits the re-hash
    assert shard_crc_matches(buf, new_tag, precomputed=new_tag)
    assert shard_crc_matches(buf, old_tag, precomputed=new_tag ^ 0)


# -- batched consumers: scrub + blockstore ----------------------------------

def test_scrub_map_digests_ride_batched_api():
    from ceph_tpu.os.store import MemStore
    from ceph_tpu.os.transaction import Transaction
    from ceph_tpu.osd.scrub import build_scrub_map
    rng = np.random.default_rng(12)
    store = MemStore()
    store.queue_transaction(Transaction().create_collection("c"))
    payloads = {}
    for i in range(20):
        data = rng.integers(0, 256, int(rng.integers(0, 9000)),
                            dtype=np.uint8).tobytes()
        t = Transaction()
        t.touch("c", f"o{i}")
        if data:
            t.write("c", f"o{i}", 0, data)
        store.queue_transaction(t)
        payloads[f"o{i}"] = data
    s0 = cb.PERF.get("scalar_calls")
    smap = run(build_scrub_map(store, "c", deep=True))
    assert cb.PERF.get("scalar_calls") == s0, \
        "deep scrub digests must not make per-object scalar CRC calls"
    for oid, data in payloads.items():
        assert smap[oid]["data_digest"] == native.crc32c(data), oid


def test_batcher_write_then_scrub_make_no_scalar_crc_calls():
    """The write path's encode (CRCs fused into the launch) followed by
    a deep-scrub digest pass over a store: at least one fused launch,
    batched calls, and not one per-buffer ``native.crc32c`` call."""
    from ceph_tpu.ec import registry
    from ceph_tpu.os.store import MemStore
    from ceph_tpu.os.transaction import Transaction
    from ceph_tpu.osd.codec_batcher import CodecBatcher
    from ceph_tpu.osd.ec_util import StripeInfo
    from ceph_tpu.osd.scrub import build_scrub_map
    codec = registry().factory("tpu", {"k": "4", "m": "2",
                                       "technique": "reed_sol_van"})
    si = StripeInfo.for_codec(codec, stripe_unit=1024)
    batcher = CodecBatcher(max_batch=32, flush_timeout=0.05)
    rng = np.random.default_rng(15)
    datas = [rng.integers(0, 256, si.stripe_width * n,
                          dtype=np.uint8).tobytes() for n in (3, 2, 4)]
    store = MemStore()
    store.queue_transaction(Transaction().create_collection("c"))
    payloads = {}
    for i in range(24):
        payloads[f"o{i}"] = rng.integers(0, 256, 4096,
                                         dtype=np.uint8).tobytes()
        t = Transaction()
        t.write("c", f"o{i}", 0, payloads[f"o{i}"])
        store.queue_transaction(t)

    async def drive():
        enc = await asyncio.gather(*(
            si.encode_async(codec, d, batcher=batcher, with_crc=True)
            for d in datas))
        return enc, await build_scrub_map(store, "c", deep=True)

    keys = ("scalar_calls", "batched_calls", "fused_launches")
    before = [cb.PERF.get(key) for key in keys]
    enc, smap = run(drive())
    scalar, batched, fused = (cb.PERF.get(key) - b
                              for key, b in zip(keys, before))
    assert scalar == 0, "a batched path made per-buffer CRC calls"
    assert batched >= 1 and fused >= 1
    for data, (shards, crcs) in zip(datas, enc):
        want = si.encode(codec, data)
        for i in want:
            assert np.array_equal(shards[i], want[i]), i
            assert crcs[i] == native.crc32c(want[i].tobytes()), i
    for oid, data in payloads.items():
        assert smap[oid]["data_digest"] == native.crc32c(data), oid


def test_blockstore_write_read_csums_batched(tmp_path):
    from ceph_tpu.os.blockstore import BlockStore
    from ceph_tpu.os.transaction import Transaction
    rng = np.random.default_rng(13)
    bs = BlockStore(str(tmp_path / "s"))
    bs.mount()
    bs.queue_transaction(Transaction().create_collection("c"))
    data = rng.integers(0, 256, 5 * 4096 + 123,
                        dtype=np.uint8).tobytes()
    t = Transaction()
    t.write("c", "obj", 0, data)
    s0 = cb.PERF.get("scalar_calls")
    bs.queue_transaction(t)
    got = bs.read("c", "obj")
    assert got == data
    # the per-block extent csums (write) and checksum-on-read both
    # went through the batched API; only the WAL record framing may
    # have used the scalar entry (one call per txn)
    assert cb.PERF.get("scalar_calls") - s0 <= 2
    bs.umount()
