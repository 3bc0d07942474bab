"""XOR-schedule compiler suite (ceph_tpu/ops/xor_schedule.py).

Contracts:

* byte parity: the scheduled executor (host, jitted XLA family)
  equals the naive row-by-row XOR AND a from-scratch scalar
  oracle on random Cauchy/liberation/arbitrary matrices, ragged tails
  and every erasure pattern of the bitmatrix codecs;
* schedule determinism: the same matrix bytes always compile to the
  identical op stream (the digest is a complete process-wide cache
  key);
* the register bound is respected (peak live temporaries <= the bound,
  including under a deliberately tiny bound);
* CSE actually fires: the scheduled term count is strictly below the
  naive XOR count on the headline Cauchy matrix, reduction >= 30%;
* routing: ``CEPH_TPU_XOR_SCHED`` steers the registry stack's batched
  entry point, and never the CodecBatcher's launches (MeshCodec runs
  the dense program, one launch per batch);
* the repair path of BitMatrixCodec recovers every missing chunk from
  ONE launch and rides a schedule warmed at decode-matrix build time;
* the autotune sweep harness runs under tier-1 (--cpu-smoke) and the
  winner it records steers the cost model.
"""

import asyncio
import json
import os

import numpy as np
import pytest

from ceph_tpu.common.perf import PerfCounters
from ceph_tpu.ec import registry
from ceph_tpu.gf.gf2w import (
    cauchy_improve_coding_matrix, cauchy_original_coding_matrix,
    liberation_coding_bitmatrix, matrix_to_bitmatrix, xor_matmul,
)
from ceph_tpu.ops import xor_schedule as XS


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def scalar_oracle(bm: np.ndarray, planes: np.ndarray) -> np.ndarray:
    """From-scratch row-by-row XOR: the independent third opinion."""
    out = np.zeros((bm.shape[0], planes.shape[1]), np.uint8)
    for r in range(bm.shape[0]):
        acc = np.zeros(planes.shape[1], np.uint8)
        for c in np.flatnonzero(bm[r]):
            acc = acc ^ planes[c]
        out[r] = acc
    return out


def cauchy_bm(k: int, m: int, w: int, improve: bool) -> np.ndarray:
    mat = cauchy_original_coding_matrix(k, m, w)
    if improve:
        mat = cauchy_improve_coding_matrix(mat, k, m, w)
    return matrix_to_bitmatrix(mat, k, m, w)


# -- property-based byte parity ---------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_random_matrix_three_way_parity(seed):
    """Random 0/1 matrices (random shape/density, zero rows, and
    duplicate rows injected) x ragged plane widths: scheduled == naive
    == scalar oracle, and the register bound holds."""
    rng = np.random.default_rng(seed)
    r = int(rng.integers(1, 24))
    c = int(rng.integers(1, 56))
    bm = (rng.random((r, c)) < rng.uniform(0.08, 0.9)).astype(np.uint8)
    if r >= 3:
        bm[r - 1] = 0                      # zero row -> zero output
        bm[r - 2] = bm[0]                  # duplicate row
    sched = XS.compile_schedule(bm)
    n = int(rng.integers(1, 700))          # ragged tail widths
    planes = rng.integers(0, 256, size=(c, n), dtype=np.uint8)
    got = XS.apply_host(sched, planes)
    assert np.array_equal(got, xor_matmul(bm, planes))
    assert np.array_equal(got, scalar_oracle(bm, planes))
    assert sched.peak_registers <= sched.max_registers


@pytest.mark.parametrize("k,m,w,improve", [
    (8, 3, 8, True), (8, 3, 8, False), (4, 2, 8, True),
    (10, 4, 4, True), (3, 3, 4, False),
])
def test_cauchy_parity(k, m, w, improve):
    bm = cauchy_bm(k, m, w, improve)
    sched = XS.compile_schedule(bm)
    rng = np.random.default_rng(k * m * w)
    planes = rng.integers(0, 256, size=(k * w, 333), dtype=np.uint8)
    got = XS.apply_host(sched, planes)
    assert np.array_equal(got, xor_matmul(bm, planes))
    assert np.array_equal(got, scalar_oracle(bm, planes))


@pytest.mark.parametrize("k,w", [(5, 5), (7, 7), (3, 11)])
def test_liberation_parity(k, w):
    bm = liberation_coding_bitmatrix(k, w)
    sched = XS.compile_schedule(bm)
    rng = np.random.default_rng(k * w)
    planes = rng.integers(0, 256, size=(k * w, 257), dtype=np.uint8)
    got = XS.apply_host(sched, planes)
    assert np.array_equal(got, xor_matmul(bm, planes))


# -- structural contracts ---------------------------------------------------

def test_cse_fires_and_headline_reduction():
    """Term count strictly below the naive row-by-row XOR count, and
    the Cauchy k=8,m=3 headline matrix clears the 30% floor, as a
    count of terms."""
    bm = cauchy_bm(8, 3, 8, True)
    sched = XS.compile_schedule(bm)
    assert sched.n_terms < sched.naive_terms
    assert sched.reduction >= 0.30, (
        sched.n_terms, sched.naive_terms)
    assert sched.terms_saved == sched.naive_terms - sched.n_terms


def test_schedule_determinism_same_digest_same_schedule():
    bm = cauchy_bm(8, 3, 8, True)
    a = XS.compile_schedule(bm)
    b = XS.compile_schedule(bm.copy())
    assert a.digest == b.digest
    assert a.ops == b.ops
    assert a.outputs == b.outputs
    assert a.peak_registers == b.peak_registers
    # the process-wide cache serves the SAME object per digest
    XS.clear_schedule_cache()
    s1 = XS.schedule_for(bm)
    s2 = XS.schedule_for(bm.copy())
    assert s1 is s2
    assert XS.cached_schedule(bm) is s1


def test_register_bound_respected_even_when_tiny():
    bm = cauchy_bm(8, 3, 8, False)     # the densest of the family
    wide = XS.compile_schedule(bm)
    assert wide.peak_registers <= XS.DEFAULT_MAX_REGISTERS
    tight = XS.compile_schedule(bm, max_registers=8)
    assert tight.peak_registers <= 8
    rng = np.random.default_rng(0)
    planes = rng.integers(0, 256, size=(64, 129), dtype=np.uint8)
    assert np.array_equal(XS.apply_host(tight, planes),
                          xor_matmul(bm, planes))


def test_zero_copy_and_single_one_rows():
    bm = np.zeros((4, 16), np.uint8)
    bm[1, 3] = 1                           # copy row
    bm[2, [3, 7, 9]] = 1
    bm[3] = bm[2]                          # duplicate
    sched = XS.compile_schedule(bm)
    rng = np.random.default_rng(1)
    planes = rng.integers(0, 256, size=(16, 64), dtype=np.uint8)
    got = XS.apply_host(sched, planes)
    assert not got[0].any()
    assert np.array_equal(got[1], planes[3])
    assert np.array_equal(got, xor_matmul(bm, planes))


# -- the batched (B, k, L) device family ------------------------------------

def test_batched_device_family_parity_ragged():
    """The jitted scheduled family matches the per-stripe host oracle
    across ragged L and non-pow2 batch sizes."""
    from ceph_tpu.gf import gen_rs_matrix, gf_matmul
    from ceph_tpu.ops.gf2kernels import bitmatrix_i8
    import jax.numpy as jnp
    k, m = 8, 3
    mat = np.ascontiguousarray(gen_rs_matrix(k + m, k)[k:], np.uint8)
    sched = XS.schedule_for(bitmatrix_i8(mat))
    rng = np.random.default_rng(2)
    for b, lane in ((1, 128), (3, 1000), (8, 4096)):
        data = rng.integers(0, 256, size=(b, k, lane), dtype=np.uint8)
        out = XS.sched_matmul_batch_device(sched, mat,
                                           jnp.asarray(data), b, k,
                                           lane)
        assert out is not None
        got = np.asarray(out)
        for i in range(b):
            assert np.array_equal(got[i], gf_matmul(mat, data[i])), i


def test_gf_matmul_batch_device_routes_scheduled(monkeypatch):
    """CEPH_TPU_XOR_SCHED=1 forces the dense entry point through the
    scheduled family -- byte-identical, and the launch counted."""
    from ceph_tpu.gf import gen_rs_matrix, gf_matmul
    from ceph_tpu.ops.gf2kernels import gf_matmul_batch_device
    k, m = 4, 2
    mat = np.ascontiguousarray(gen_rs_matrix(k + m, k)[k:], np.uint8)
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, size=(4, k, 512), dtype=np.uint8)
    monkeypatch.setenv("CEPH_TPU_XOR_SCHED", "1")
    l0 = XS.STATS.snapshot()
    got = gf_matmul_batch_device(mat, data, out_np=True)
    l1 = XS.STATS.snapshot()
    assert l1[0] == l0[0] + 1
    monkeypatch.setenv("CEPH_TPU_XOR_SCHED", "0")
    want = gf_matmul_batch_device(mat, data, out_np=True)
    assert np.array_equal(got, want)
    for i in range(4):
        assert np.array_equal(got[i], gf_matmul(mat, data[i]))


# -- the CodecBatcher's launches are not scheduled ---------------------------

def _codec(k="2", m="1"):
    return registry().factory("tpu", {"k": k, "m": m,
                                      "technique": "reed_sol_van"})


def test_batcher_one_launch_per_batch_whatever_the_schedule_env(
        monkeypatch):
    """encode/decode/rmw batches launch EXACTLY ONCE through the mesh
    and stay byte-identical to the per-op path; the registry stack's
    ``CEPH_TPU_XOR_SCHED`` does not reach them (no scheduled launch
    is noted)."""
    from ceph_tpu.osd.codec_batcher import CodecBatcher
    monkeypatch.setenv("CEPH_TPU_XOR_SCHED", "1")
    sched0 = XS.STATS.snapshot()
    codec = _codec("4", "2")
    perf = PerfCounters("ec_batch")
    b = CodecBatcher(max_batch=64, flush_timeout=0.2, perf=perf)
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, size=(3, 4, 256), dtype=np.uint8)
    old_parity = rng.integers(0, 256, size=(3, 2, 256), dtype=np.uint8)

    async def main():
        parity = await b.encode(codec, data)
        erase = (1, 4)
        survivors = np.stack(
            [np.concatenate([data[s], parity[s]])[
                [0, 2, 3, 5]] for s in range(3)])
        recovered = await b.decode(codec, erase, survivors)
        new_parity = await b.rmw(codec, old_parity, data)
        return parity, recovered, new_parity

    parity, recovered, new_parity = run(main())
    assert XS.STATS.snapshot() == sched0
    for s in range(3):
        want = codec.encode(set(range(6)), data[s].tobytes())
        assert np.array_equal(parity[s, 0], want[4])
        assert np.array_equal(parity[s, 1], want[5])
        assert np.array_equal(recovered[s, 0], data[s, 1])
        assert np.array_equal(recovered[s, 1], want[4])
        assert np.array_equal(new_parity[s],
                              old_parity[s] ^ parity[s])
    dump = perf.dump()
    assert dump["batches"] == 3
    assert dump["mesh_launches"] == 3           # one launch per batch


# -- BitMatrixCodec repair path ---------------------------------------------

def _jerasure(technique, **profile):
    prof = {"technique": technique, **{k: str(v)
                                       for k, v in profile.items()}}
    return registry().factory("jerasure", prof)


def test_bitmatrix_decode_is_one_launch(monkeypatch):
    """All missing chunks -- data AND coding -- come back from ONE
    xor launch (the per-lost-chunk loop is gone)."""
    import ceph_tpu.ec.bitmatrix_codec as BMC
    codec = _jerasure("cauchy_good", k=4, m=2, w=8, packetsize=8)
    csize = codec.get_alignment() // codec.k
    rng = np.random.default_rng(6)
    chunks = {i: rng.integers(0, 256, csize, dtype=np.uint8)
              if i < 4 else np.zeros(csize, np.uint8)
              for i in range(6)}
    codec.encode_chunks(chunks)
    full = {i: chunks[i].copy() for i in range(6)}
    calls = []
    real = BMC.scheduled_xor_matmul

    def counting(matrix, planes, **kw):
        calls.append(matrix.shape)
        return real(matrix, planes, **kw)

    monkeypatch.setattr(BMC, "scheduled_xor_matmul", counting)
    have = {i: full[i] for i in (0, 2, 3, 5)}      # lose data 1 + parity 4
    decoded = {i: (full[i].copy() if i in have
                   else np.zeros(csize, np.uint8)) for i in range(6)}
    codec.decode_chunks(set(range(4)), have, decoded)
    assert len(calls) == 1                         # ONE launch
    assert calls[0] == (2 * codec.w, 4 * codec.w)  # both chunks stacked
    for e in (1, 4):
        assert np.array_equal(decoded[e], full[e])


def test_repair_rides_schedule_warmed_at_build(monkeypatch):
    """The repair matrix's schedule is compiled when the decode matrix
    is built, so the read path (allow_compile=False) finds it cached
    and launches scheduled."""
    monkeypatch.setenv("CEPH_TPU_XOR_SCHED", "1")
    codec = _jerasure("cauchy_good", k=4, m=2, w=8, packetsize=8)
    csize = codec.get_alignment() // codec.k
    rng = np.random.default_rng(7)
    chunks = {i: rng.integers(0, 256, csize, dtype=np.uint8)
              if i < 4 else np.zeros(csize, np.uint8)
              for i in range(6)}
    codec.encode_chunks(chunks)
    full = {i: chunks[i].copy() for i in range(6)}

    def repair():
        have = {i: full[i] for i in range(6) if i not in (0, 1)}
        decoded = {i: (full[i].copy() if i in have
                       else np.zeros(csize, np.uint8))
                   for i in range(6)}
        codec.decode_chunks(set(range(4)), have, decoded)
        assert np.array_equal(decoded[0], full[0])
        assert np.array_equal(decoded[1], full[1])

    repair()                     # builds + warms the repair matrix
    before = XS.STATS.snapshot()
    repair()                     # cached schedule serves, no compile
    after = XS.STATS.snapshot()
    assert after[0] > before[0]


# -- autotune sweep harness (tier-1 --cpu-smoke) ----------------------------

@pytest.fixture()
def own_cache_dir(tmp_path, monkeypatch):
    """main() places the persistent compile cache unless the operator
    did: claim the placement, so a main() called in-process does not
    turn the cache on for the rest of the pytest session."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))


def test_autotune_cpu_smoke_writes_winner(tmp_path, capsys,
                                          own_cache_dir):
    from ceph_tpu.tools import ec_autotune
    out = tmp_path / "tuned.json"
    rc = ec_autotune.main(["--k", "4", "--m", "2", "--cpu-smoke",
                           "--write", "--out", str(out)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["xor_sched"]["engine"] in ("dense", "scheduled")
    assert report["xor_sched"]["sched_terms"] \
        < report["xor_sched"]["naive_terms"]
    tuned = json.loads(out.read_text())
    assert "4,2" in tuned["xor_sched"]
    assert "4,2,4096" in tuned["xor_sched"]


def test_autotune_code_matrices_sweep(tmp_path, capsys, own_cache_dir):
    """--codes sweeps the recovery-code matrix families (LRC
    local-parity/local-repair, PMSR parity/fragment-aggregate) into
    xor_sched entries keyed by their matrix dims -- the key the
    runtime cost model looks up."""
    from ceph_tpu.tools import ec_autotune
    out = tmp_path / "tuned.json"
    rc = ec_autotune.main(["--k", "4", "--m", "2", "--cpu-smoke",
                           "--codes", "lrc,pmsr",
                           "--write", "--out", str(out)])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    recs = report["xor_sched_codes"]
    tags = {r["tag"] for r in recs.values()}
    assert "lrc_k8m4l3_parity" in tags
    assert "lrc_k8m4l3_local_repair" in tags
    assert any(t.startswith("pmsr_") and t.endswith("_aggregate")
               for t in tags)
    tuned = json.loads(out.read_text())
    # the LRC parity family key (8 data cols, 8 coding rows)
    assert "8,8" in tuned["xor_sched"]
    # the local-repair row: 3 sources -> 1 lost chunk
    assert "3,1" in tuned["xor_sched"]
    for rec in recs.values():
        assert rec["engine"] in ("dense", "scheduled")


def test_speculative_compile_bound_protects_codec_init():
    """Dense matrices above SPECULATIVE_MAX_CELLS are neither warmed
    at codec build time nor compiled by the CPU backend heuristic --
    a multi-second greedy-CSE pass must never ride profile validation
    or a first launch.  Explicit opt-ins (env, tuned entry) still
    compile."""
    rng = np.random.default_rng(0)
    big = rng.integers(0, 2, size=(160, 160), dtype=np.uint8)
    assert big.size > XS.SPECULATIVE_MAX_CELLS
    assert XS.want_scheduled(big, 4096, "cpu") is None
    assert XS.cached_schedule(big) is None       # nothing compiled


def test_tuned_winner_steers_cost_model(tmp_path, monkeypatch):
    """A gf2_tuned.json xor_sched entry overrides the backend
    heuristic in both directions."""
    from ceph_tpu.ops import gf2kernels as G
    bm = cauchy_bm(8, 3, 8, True)      # (24, 64) -> family key "8,3"
    monkeypatch.delenv("CEPH_TPU_XOR_SCHED", raising=False)
    path = tmp_path / "tuned.json"
    for engine, expect in (("scheduled", True), ("dense", False)):
        path.write_text(json.dumps(
            {"xor_sched": {"8,3": {"engine": engine}}}))
        monkeypatch.setattr(G, "_TUNED_PATH", str(path))
        G._tuned_cfgs.cache_clear()
        # "tpu" backend would default dense; the tuned entry decides
        got = XS.want_scheduled(bm, 4096, "tpu")
        assert (got is not None) == expect, engine
    G._tuned_cfgs.cache_clear()
