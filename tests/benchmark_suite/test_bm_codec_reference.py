"""``reference/codec.py`` against hand-worked cases, ``work_codec.py``'s
bytes and the ``codec_roofline`` reader on facts written out by hand."""

from __future__ import annotations

import numpy as np
import pytest

import bm_toy  # noqa: F401  (puts the repo root on sys.path)
from benchmark import work_codec
from benchmark.readers import codec_roofline
from benchmark.reference import codec, ec

PROFILE = {"k": 8, "m": 3, "technique": "reed_sol_van"}


@pytest.mark.parametrize("k,size,want", [
    (8, 1 << 20, 131072),       # the cell's: 1 MiB over k=8, aligned as is
    (8, 4096 * 8, 4096),        # BASELINE config 1's 4 KiB chunks
    (8, 1, 32),                 # one byte: a whole alignment unit a chunk
    (8, 257, 64),               # ceil(257 / 8) = 33 -> 64
    (10, 1 << 20, 104864),      # ceil = 104858, up to a multiple of 32
    (3, 96, 32),
])
def test_chunk_bytes_is_ceil_over_k_rounded_up_to_32(k, size, want):
    assert codec.chunk_bytes(k, size) == want


def test_chunks_of_cuts_an_object_end_to_end_and_pads_the_tail():
    payload = bytes(range(100))
    chunks = codec.chunks_of(3, payload)            # ceil(100/3)=34 -> 64
    assert chunks.shape == (3, 64)
    assert bytes(chunks[0]) == payload[:64]
    assert bytes(chunks[1]) == payload[64:] + b"\0" * 28
    assert not chunks[2].any()
    whole = codec.chunks_of(8, bytes(256))
    assert whole.shape == (8, 32)


@pytest.mark.parametrize("erased,want", [
    ([], [0, 1, 2, 3, 4, 5, 6, 7]),
    ([10], [0, 1, 2, 3, 4, 5, 6, 7]),
    ([0], [1, 2, 3, 4, 5, 6, 7, 8]),
    ([3, 8], [0, 1, 2, 4, 5, 6, 7, 9]),
    ([0, 1, 2], [3, 4, 5, 6, 7, 8, 9, 10]),
    ([7, 8, 9], [0, 1, 2, 3, 4, 5, 6, 10]),
])
def test_survivors_are_the_first_k_ids_not_erased(erased, want):
    assert codec.survivors(8, 11, erased) == want


def test_survivors_refuses_more_erasures_than_m():
    with pytest.raises(ValueError):
        codec.survivors(8, 11, [0, 1, 2, 3])


def test_parity_of_a_batch_is_the_generators_product_stripe_by_stripe():
    data = np.random.default_rng(3).integers(0, 256, (5, 8, 96),
                                             dtype=np.uint8)
    got = codec.parity_of(PROFILE, data)
    matrix = ec.coding_matrix("reed_sol_van", 8, 3)
    assert got.shape == (5, 3, 96)
    for stripe, parity in zip(data, got):
        assert np.array_equal(parity, ec.gf_matmul(matrix, stripe))
    # the first parity row of reed_sol_van is the XOR of the data chunks
    assert np.array_equal(got[:, 0], np.bitwise_xor.reduce(data, axis=1))
    # a view that is not contiguous (the driver's sampled stripes) too
    assert np.array_equal(codec.parity_of(PROFILE, data[::2]), got[::2])


@pytest.mark.parametrize("erased", [[0], [9], [2, 5], [7, 10], [0, 4, 8],
                                    [8, 9, 10], [0, 1, 2]])
def test_recovered_gives_back_the_erased_chunks_from_the_survivors(erased):
    data = np.random.default_rng(4).integers(0, 256, (1, 8, 64),
                                             dtype=np.uint8)
    stripe = np.concatenate([data, codec.parity_of(PROFILE, data)],
                            axis=1)[0]
    blanked = stripe.copy()
    blanked[erased] = 0x5A                  # never read
    got = codec.recovered(PROFILE, blanked, erased)
    assert got.shape == (len(erased), 64)
    assert np.array_equal(got, stripe[erased])


def test_launch_bytes_by_kind():
    unit = 131072
    assert work_codec.launch_bytes(8, 3, unit, 1024) == 1024 * 11 * unit
    assert work_codec.launch_bytes(8, 1, unit, 1024) == 1024 * 9 * unit
    # the cell's cycle: three encodes and a decode of 1, 2 and 3 erasures
    cycle = {3: 4 * 1024, 1: 1024, 2: 1024}
    assert work_codec.slice_bytes(8, unit, cycle) == \
        1024 * unit * (4 * 11 + 9 + 10)


def test_the_reader_sums_by_rows_over_the_registrys_programs_alone():
    spec = {"stripes_prefix": "slice.codec.stripes_r",
            "programs": "^jit_registry_gf"}
    facts = {"trace.programs": {"jit_registry_gf_gN": 0.100,
                                "jit_registry_gf_xla": 0.025,
                                "jit_dynamic_slice": 9.0},
             "device.kind": "TPU v5 lite",
             "config.profile.k": 8, "config.profile.stripe_unit": 131072,
             "slice.codec.stripes_r3": 4096, "slice.codec.stripes_r1": 1024,
             "slice.codec.stripes_r2": 1024}
    need = 1024 * 131072 * 63
    want = 100.0 * (need / 819e9) / 0.125
    assert codec_roofline.read(spec, facts) == pytest.approx(want)
    # nothing to read: no trace, no stripes handed in, no program of the
    # registry's names (a program from before them)
    assert codec_roofline.read(spec, {}) is None
    assert codec_roofline.read(spec, {
        k: v for k, v in facts.items()
        if not k.startswith("slice.")}) is None
    assert codec_roofline.read(spec, dict(
        facts, **{"trace.programs": {"jit_fn": 1.0}})) is None
    from benchmark.harness import HarnessError
    with pytest.raises(HarnessError):
        codec_roofline.read(spec, dict(facts, **{"device.kind": "abacus"}))
