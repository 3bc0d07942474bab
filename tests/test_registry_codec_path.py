"""The registry's batch path from host memory (``ec/plugins/tpu.py`` ->
``ops/jax_backend.py`` -> ``ops/gf2kernels.py``): every erasure pattern
of k=8, m=3 decoded from numpy, one engine for every count of output
rows, and what a call leaves behind to be measured by: the
``registry.*`` sections, the ``ec_registry`` counters, the programs'
names and scope.
"""

from __future__ import annotations

import contextlib
import itertools

import numpy as np
import pytest

from ceph_tpu.common import tracing
from ceph_tpu.ec import registry
from ceph_tpu.gf import gf_matmul

K, M, B, L = 8, 3, 4, 256
N = K + M
PATTERNS = [list(p) for e in range(1, M + 1)
            for p in itertools.combinations(range(N), e)]
ENGINES = ("sched", "gN", "v1", "xla")


@pytest.fixture(scope="module", autouse=True)
def packed_engine():
    """Every test of this file sees the engine choice of a TPU backend:
    ``gN``, here through the Pallas interpreter, whose matrix is an
    operand (the CPU's choice, ``sched``, compiles a program a matrix:
    231 of them)."""
    import ceph_tpu.ops.gf2kernels as g

    mp = pytest.MonkeyPatch()
    mp.setattr(g, "_want_pallas", lambda: True)
    g.clear_kernel_cache()
    yield
    mp.undo()
    g.clear_kernel_cache()


@pytest.fixture
def packed():
    """The kernel module with no launch verified yet."""
    import ceph_tpu.ops.gf2kernels as g

    g._gN_verified.clear()
    return g


@pytest.fixture(scope="module")
def stripes():
    """(B, k+m, L) whole stripes: seeded data, parity by the plain
    GF(2^8) product of the generator's parity rows."""
    codec = registry().factory("isa", {"k": str(K), "m": str(M)})
    data = np.random.default_rng(45).integers(
        0, 256, (B, K, L), dtype=np.uint8)
    parity = np.stack([gf_matmul(codec.encode_matrix[K:], d) for d in data])
    return np.concatenate([data, parity], axis=1)


@pytest.fixture(scope="module")
def tpu_codec():
    """One plugin for the 231 patterns: its DecodeTableCache (256)
    holds them all, as a long-lived caller's would."""
    return registry().factory("tpu", {"k": str(K), "m": str(M)})


def test_there_are_231_patterns():
    assert len(PATTERNS) == 11 + 55 + 165 == 231


def test_encode_batch_from_numpy_is_the_plain_product(tpu_codec, stripes):
    parity = tpu_codec.encode_batch(stripes[:, :K], out_np=True)
    assert isinstance(parity, np.ndarray) and parity.dtype == np.uint8
    assert np.array_equal(parity, stripes[:, K:])


@pytest.mark.parametrize("erased", PATTERNS,
                         ids=["-".join(map(str, p)) for p in PATTERNS])
def test_every_erasure_pattern_decodes_from_numpy(tpu_codec, stripes,
                                                  erased):
    """Both batch entry points, numpy in and numpy out: the chunk map
    with the erased chunks blanked (never read), and the k survivors in
    decode_index order."""
    from ceph_tpu.gf.matrices import decode_index_for

    blanked = stripes.copy()
    blanked[:, erased] = 0xA5
    got = tpu_codec.decode_stripes(erased, blanked, out_np=True)
    assert got.shape == (B, len(erased), L)
    assert np.array_equal(got, stripes[:, erased])
    survivors = stripes[:, decode_index_for(K, set(erased))]
    again = tpu_codec.decode_batch(erased, survivors, out_np=True)
    assert np.array_equal(again, got)


def test_the_survivors_are_gathered_once_into_a_c_ordered_array(stripes):
    """``stripes[:, index]`` comes back chunk axis outermost, and an
    upload of it copies the whole batch again: the gather makes the
    array the upload takes as it is."""
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    seen = []
    codec.decode_batch = lambda erasures, chunks, out_np=False: \
        seen.append(chunks) or chunks
    codec.decode_stripes([0, 9], stripes, out_np=True)
    (chunks,) = seen
    assert chunks.flags["C_CONTIGUOUS"] and chunks.shape == (B, K, L)
    assert np.array_equal(chunks, stripes[:, [1, 2, 3, 4, 5, 6, 7, 8]])
    assert not stripes[:, [1, 2, 3, 4, 5, 6, 7, 8]].flags["C_CONTIGUOUS"]


@pytest.mark.parametrize("erased", [[4], [4, 9], [0, 4, 9]],
                         ids=["r1", "r2", "r3"])
def test_every_count_of_output_rows_reaches_the_packed_engine(
        packed, stripes, erased):
    """r = 1, 2, 3 rows: the engine a TPU backend picks for the toy
    shape and for the cell's (1024, 8, 131072) is ``gN`` each time (no
    count of rows drops to ``v1`` or ``xla``), it is the one counted,
    and a kernel that returns wrong bytes raises."""
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    matrix = codec.decode_matrix_for(erased)
    assert matrix.shape == (len(erased), K)
    assert packed.batch_engine(matrix, B, K, L) == "gN"
    assert packed.batch_engine(matrix, 1024, K, 131072) == "gN"
    got = codec.decode_stripes(erased, stripes, out_np=True)
    assert np.array_equal(got, stripes[:, erased])
    dump = codec.perf.dump()
    assert dump["engine_gN"] == dump["launches"] == 1
    assert not any(dump.get(f"engine_{e}") for e in ENGINES if e != "gN")

    real = packed._compiled_batch_gN
    packed._gN_verified.clear()
    packed._compiled_batch_gN = \
        lambda *a: (lambda w, xd, fn=real(*a): fn(w, xd) ^ 1)
    try:
        with pytest.raises(packed.KernelParityError):
            codec.decode_stripes(erased, stripes, out_np=True)
    finally:
        packed._compiled_batch_gN = real


@pytest.fixture
def sections(monkeypatch):
    """Every section the registry path opens, in order of opening."""
    import ceph_tpu.ec.plugins.tpu as plugin
    import ceph_tpu.ops.gf2kernels as g

    opened: list[str] = []

    @contextlib.contextmanager
    def record(name):
        opened.append(name)
        yield

    monkeypatch.setattr(g, "section", record)
    monkeypatch.setattr(plugin, "section", record)
    return opened


def test_one_encode_and_one_decode_move_sections_and_counters(
        packed, sections, stripes):
    assert "registry" in tracing.SECTION_LAYERS
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    assert codec.perf.name == "ec_registry" and codec.perf.dump() == {}

    codec.encode_batch(stripes[:, :K], out_np=True)
    assert [s for s in sections if s != "registry.matrix"] == [
        "registry.upload", "registry.launch", "registry.device_wait",
        "registry.copy_out"]
    assert sections.count("registry.matrix") == 2    # the matrix, the gate
    assert codec.perf.dump() == {
        "launches": 1, "engine_gN": 1, "stripes": B, "parity_gates": 1,
        "bytes_in": B * K * L, "bytes_out": B * M * L}

    sections.clear()
    erased = [1, 9]
    codec.decode_stripes(erased, stripes, out_np=True)
    assert sections[0] == "registry.marshal"
    assert sections[1] == "registry.matrix"          # the table miss
    assert [s for s in sections if s != "registry.matrix"] == [
        "registry.marshal", "registry.upload", "registry.launch",
        "registry.device_wait", "registry.copy_out"]
    two = codec.perf.dump()
    assert two["launches"] == 2 and two["stripes"] == 2 * B
    assert two["bytes_in"] == 2 * B * K * L
    assert two["bytes_out"] == B * M * L + B * len(erased) * L
    assert two["engine_gN"] == two["parity_gates"] == 2
    assert two["table_misses"] == 1 and "table_hits" not in two


def test_gates_and_table_misses_count_once_per_new_signature(packed,
                                                             stripes):
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    for _ in range(2):
        for erased in ([2], [2, 10], [0, 5, 8]):
            codec.decode_stripes(erased, stripes, out_np=True)
    dump = codec.perf.dump()
    assert dump["launches"] == dump["engine_gN"] == 6
    assert dump["table_misses"] == 3 and dump["table_hits"] == 3
    assert dump["parity_gates"] == 3
    # the same erased ids in another order are another signature
    codec.decode_stripes([10, 2], stripes, out_np=True)
    dump = codec.perf.dump()
    assert dump["table_misses"] == 4 and dump["parity_gates"] == 4


def test_a_device_array_in_and_out_skips_the_copies(sections, stripes):
    import jax
    import jax.numpy as jnp

    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    out = codec.encode_batch(jnp.asarray(stripes[:, :K]))
    assert isinstance(out, jax.Array)
    assert set(sections) <= {"registry.launch", "registry.matrix"}
    assert np.array_equal(np.asarray(out), stripes[:, K:])


@pytest.mark.parametrize("engine", ENGINES)
def test_every_engines_program_carries_the_registry_name_and_scope(engine):
    import jax.numpy as jnp
    import ceph_tpu.ops.gf2kernels as g
    from ceph_tpu.ops import xor_schedule

    matrix = registry().factory(
        "isa", {"k": str(K), "m": str(M)}).encode_matrix[K:]
    xd = jnp.zeros((B, K, L), jnp.uint8)
    if engine == "sched":
        sched = xor_schedule.schedule_for(g.bitmatrix_i8(matrix))
        lowered = xor_schedule._compiled_sched_batch(
            sched.digest, B, K, L).lower(xd)
    elif engine == "gN":
        cfg = g._g2_cfg(K)
        group, tile = g._gN_plan(K, B, L, cfg)
        lowered = g._compiled_batch_gN(
            8 * M, K, B, L, group, cfg["unpack"], cfg["mm"], cfg["pack"],
            tile).lower(jnp.zeros((group * 8 * M, 8 * group * K), jnp.int8),
                        xd)
    else:
        lowered = g._compiled_batch(8 * M, K, B, L, engine == "v1").lower(
            jnp.zeros((8 * M, 8 * K), jnp.int8), xd)
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_registry_gf_{engine} " in text
    assert g.REGISTRY_SCOPE == "registry_gf"
    assert f'"{g.REGISTRY_SCOPE}/' in text or f"/{g.REGISTRY_SCOPE}/" in text
