"""Byte-parity of the TPU (JAX/Pallas) execution path vs the host oracle."""

import os

import numpy as np
import pytest

from ceph_tpu.gf import gen_rs_matrix, gen_cauchy1_matrix, gf_matmul
from ceph_tpu.ops.gf2kernels import (
    gf_matmul_device, gf_matmul_batch_device, _make_pallas_fn, bitmatrix_i8,
)
from ceph_tpu.ec import ErasureCodePluginRegistry


@pytest.fixture()
def registry():
    return ErasureCodePluginRegistry()


@pytest.mark.parametrize("k,m,n", [(8, 3, 512), (10, 4, 96), (4, 2, 8192),
                                   (8, 3, 1000)])
def test_xla_matmul_parity(k, m, n):
    rng = np.random.default_rng(7)
    gen = gen_rs_matrix(k + m, k)
    data = rng.integers(0, 256, size=(k, n)).astype(np.uint8)
    want = gf_matmul(gen[k:], data)
    got = gf_matmul_device(gen[k:], data)
    assert np.array_equal(want, got)


def test_batch_matmul_parity():
    rng = np.random.default_rng(8)
    k, m = 8, 3
    gen = gen_cauchy1_matrix(k + m, k)
    data = rng.integers(0, 256, size=(16, k, 256)).astype(np.uint8)
    got = gf_matmul_batch_device(gen[k:], data, out_np=True)
    for b in range(16):
        want = gf_matmul(gen[k:], data[b])
        assert np.array_equal(want, got[b])


def test_pallas_kernel_interpret_parity():
    """Run the actual pallas kernel on CPU (the kernels derive
    interpret mode from the cpu backend)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(9)
    k, m, n, tile = 8, 3, 1024, 512
    gen = gen_rs_matrix(k + m, k)
    w = bitmatrix_i8(gen[k:])
    data = rng.integers(0, 256, size=(k, n)).astype(np.uint8)
    fn = _make_pallas_fn(8 * m, k, n, tile)
    got = np.asarray(fn(jnp.asarray(w), jnp.asarray(data)))
    want = gf_matmul(gen[k:], data)
    assert np.array_equal(want, got)


def test_tpu_plugin_parity_with_isa(registry):
    rng = np.random.default_rng(10)
    for technique, k, m in [("reed_sol_van", 8, 3), ("cauchy", 10, 4)]:
        profile = {"k": str(k), "m": str(m), "technique": technique}
        tpu = registry.factory("tpu", dict(profile))
        isa = registry.factory("isa", dict(profile))
        data = rng.integers(0, 256, size=k * 512 + 31, dtype=np.uint8).tobytes()
        enc_tpu = tpu.encode(set(range(k + m)), data)
        enc_isa = isa.encode(set(range(k + m)), data)
        assert set(enc_tpu) == set(enc_isa)
        for i in enc_isa:
            assert np.array_equal(enc_tpu[i], enc_isa[i]), (technique, i)
        # decode parity with two erasures
        avail = {i: enc_tpu[i] for i in range(k + m) if i not in (1, k)}
        dec = tpu.decode(set(range(k + m)), avail)
        assert np.array_equal(dec[1], enc_isa[1])
        assert np.array_equal(dec[k], enc_isa[k])


def test_tpu_plugin_batch_roundtrip(registry):
    rng = np.random.default_rng(11)
    tpu = registry.factory("tpu", {"k": "8", "m": "3"})
    data = rng.integers(0, 256, size=(32, 8, 128)).astype(np.uint8)
    parity = np.asarray(tpu.encode_batch(data, out_np=True))
    assert parity.shape == (32, 3, 128)
    # erase shards 0 and 9 -> decode_index = [1..8,10]
    erasures = [0, 9]
    full = np.concatenate([data, parity], axis=1)  # (B, 11, L)
    decode_index = [i for i in range(11) if i not in erasures][:8]
    survivors = full[:, decode_index, :]
    rec = np.asarray(tpu.decode_batch(erasures, survivors, out_np=True))
    assert np.array_equal(rec[:, 0, :], full[:, 0, :])
    assert np.array_equal(rec[:, 1, :], full[:, 9, :])


def test_pallas_gN_kernel_interpret_parity():
    """The MXU-packed kernel family (g stripes per step, plane-major
    unpack, contraction 8kg) in interpret mode, byte-exact vs the host
    oracle across every (unpack, mm, pack) variant, encode and decode
    shapes."""
    import itertools
    import jax.numpy as jnp
    from ceph_tpu.ops.gf2kernels import _make_pallas_batch_fn_gN, \
        _w_gN_planemajor, pick_group
    from ceph_tpu.gf import build_decode_matrix

    rng = np.random.default_rng(11)
    k, m, b, l = 8, 3, 4, 512
    gen = gen_rs_matrix(k + m, k)
    data = rng.integers(0, 256, size=(b, k, l)).astype(np.uint8)
    g = pick_group(k, b)
    assert g == 2

    for mat in (gen[k:],
                build_decode_matrix(gen, k, [1, 9])[0]):
        mat = np.ascontiguousarray(mat, np.uint8)
        wn = _w_gN_planemajor(mat.tobytes(), mat.shape[0], k, g)
        for unpack, mm, pack in itertools.product(
                ("concat", "bcast"), ("int8", "bf16"), ("vpu", "mxu")):
            w = jnp.asarray(wn.astype(jnp.bfloat16) if mm == "bf16"
                            else wn)
            fn = _make_pallas_batch_fn_gN(
                8 * mat.shape[0], k, b, l, g, 256, unpack, mm, pack)
            got = np.asarray(fn(w, jnp.asarray(data)))
            for i in range(b):
                assert np.array_equal(got[i], gf_matmul(mat, data[i])), \
                    (unpack, mm, pack, i)


def test_pallas_gN_group4_k4():
    """k=4 packs FOUR stripes per grid step (contraction 128)."""
    import jax.numpy as jnp
    from ceph_tpu.ops.gf2kernels import _make_pallas_batch_fn_gN, \
        _w_gN_planemajor, pick_group

    rng = np.random.default_rng(13)
    k, m, b, l = 4, 2, 8, 256
    gen = gen_rs_matrix(k + m, k)
    data = rng.integers(0, 256, size=(b, k, l)).astype(np.uint8)
    g = pick_group(k, b)
    assert g == 4
    mat = np.ascontiguousarray(gen[k:], np.uint8)
    wn = _w_gN_planemajor(mat.tobytes(), m, k, g)
    fn = _make_pallas_batch_fn_gN(8 * m, k, b, l, g, 256, "concat",
                                  "int8", "vpu")
    got = np.asarray(fn(jnp.asarray(wn), jnp.asarray(data)))
    for i in range(b):
        assert np.array_equal(got[i], gf_matmul(mat, data[i])), i


def test_gN_selection_and_failure_raises(monkeypatch):
    """gf_matmul_batch_device serves the packed kernel when the shape
    selects it -- and a kernel that errors or returns wrong bytes makes
    the codec call RAISE instead of returning another engine's
    result."""
    import ceph_tpu.ops.gf2kernels as g
    from ceph_tpu.ec import registry

    monkeypatch.setattr(g, "_want_pallas", lambda: True)
    g.clear_kernel_cache()
    rng = np.random.default_rng(12)
    k, m, b, l = 8, 3, 4, 512
    gen = gen_rs_matrix(k + m, k)
    data = rng.integers(0, 256, size=(b, k, l)).astype(np.uint8)
    assert g.batch_engine(gen[k:], b, k, l) == "gN"
    out = g.gf_matmul_batch_device(gen[k:], data, out_np=True)
    for i in range(b):
        assert np.array_equal(out[i], gf_matmul(gen[k:], data[i]))
    assert g._gN_verified

    codec = registry().factory("tpu", {"k": str(k), "m": str(m)})
    real = g._compiled_batch_gN

    # a compile that raises (a Mosaic refusal on the chip)
    g.clear_kernel_cache()
    monkeypatch.setattr(g, "_compiled_batch_gN",
                        lambda *a: (_ for _ in ()).throw(
                            RuntimeError("mosaic says no")))
    with pytest.raises(RuntimeError, match="mosaic says no"):
        codec.encode_batch(data, out_np=True)

    # a kernel that compiles but returns wrong bytes
    g.clear_kernel_cache()
    monkeypatch.setattr(
        g, "_compiled_batch_gN",
        lambda *a: (lambda w, xd, fn=real(*a): fn(w, xd) ^ 1))
    with pytest.raises(g.KernelParityError):
        codec.encode_batch(data, out_np=True)
    g.clear_kernel_cache()


def test_malformed_tuned_table_is_an_error(monkeypatch, tmp_path):
    import ceph_tpu.ops.gf2kernels as g

    bad = tmp_path / "gf2_tuned.json"
    bad.write_text("{not json")
    monkeypatch.setattr(g, "_TUNED_PATH", str(bad))
    g._tuned_cfgs.cache_clear()
    try:
        with pytest.raises(ValueError):
            g._g2_cfg(8)
        monkeypatch.setattr(g, "_TUNED_PATH", str(tmp_path / "absent"))
        g._tuned_cfgs.cache_clear()
        assert g._g2_cfg(8) == g.G2_DEFAULT
    finally:
        g._tuned_cfgs.cache_clear()
