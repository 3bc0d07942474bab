"""The registry's batch path over objects of unequal size
(``ErasureCodeTpu.encode_objects`` / ``decode_objects`` ->
``JaxBackend.matmul_batch`` -> ``gf2kernels.gf_matmul_batch_device`` with
a ``LanePieces``): the objects' chunks lie end to end on the lane axis
and stream through the one slab loop in slabs of one width, whatever
sizes a call mixes.  Held here, at small size on the CPU, to the plain
reference (``benchmark/reference/``) and to the host ``isa`` plugin
object by object: ``encode_prepare``'s chunking and zero tail, every
double erasure of k=10, m=4, objects that meet a slab's edge, one
program a count of output rows, and the staging and lease rules of the
uniform call (``test_registry_codec_path.py``) under this one.
"""

from __future__ import annotations

import gc
import itertools
import threading

import numpy as np
import pytest

from benchmark.reference import codec as ref
from benchmark.reference import codec_objects as ref_objects
from ceph_tpu.ec import registry
from test_registry_codec_path import (GATHERED, GATHERER, KERNEL, SlabFailed,
                                      Watched, arena, caller_sections,
                                      came_back, depth, every_depth, flat_of,
                                      later_launches, leases, most_in_flight,
                                      nesting_holds, sections, staging_of)

# fixtures of the uniform call's file
__all__ = ["arena", "depth", "sections"]

K, M = 10, 4
N = K + M
PROFILE = {"k": str(K), "m": str(M), "technique": "cauchy"}
REF = {"k": K, "m": M, "technique": "cauchy"}
SIZES = [1, 31, 32, 4095, 4096, 4097, 40 << 10, (1 << 20) - 1]
DOUBLES = [list(p) for p in itertools.combinations(range(N), 2)]
OTHERS = [[0], [9], [13], [0, 1, 2], [3, 9, 12], [11, 12, 13],
          [0, 1, 2, 3], [2, 6, 10, 13], [10, 11, 12, 13]]
LANES = 1024            # a slab of most tests


@pytest.fixture(scope="module", autouse=True)
def pallas_engine():
    """The engine choice of a TPU backend (``v1`` at k=10, ``gN`` at
    k=8, through the Pallas interpreter): the matrix is an operand, so
    91 patterns are one program (the CPU's own choice, ``sched``,
    compiles one a matrix)."""
    import ceph_tpu.ops.gf2kernels as g

    mp = pytest.MonkeyPatch()
    mp.setattr(g, "_want_pallas", lambda: True)
    g.clear_kernel_cache()
    yield
    mp.undo()
    g.clear_kernel_cache()


@pytest.fixture
def kernels():
    """The kernel module with no launch verified yet."""
    import ceph_tpu.ops.gf2kernels as g

    g._gN_verified.clear()
    return g


@pytest.fixture
def slab_lanes(monkeypatch, kernels):
    """Sets the slab to so many lanes of a k=10 call."""
    def set_lanes(lanes: int, k: int = K) -> None:
        monkeypatch.setattr(kernels, "SLAB_BYTES", k * lanes)
        assert kernels._slab_lanes(k) == lanes
    return set_lanes


def objects_of(sizes, seed: int = 50) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size, dtype=np.uint8) for size in sizes]


def maps_of(objects, parity, k: int = K) -> list[np.ndarray]:
    return [np.concatenate([ref.chunks_of(k, obj.tobytes()), par])
            for obj, par in zip(objects, parity)]


@pytest.fixture(scope="module")
def tpu_codec():
    return registry().factory("tpu", PROFILE)


@pytest.fixture(scope="module")
def isa_codec():
    return registry().factory("isa", PROFILE)


@pytest.fixture(scope="module")
def small():
    """Twelve objects of seven sizes and their chunk maps, parity by the
    reference."""
    objects = objects_of([4096, 100, 8192, 1, 5000, 4096, 333, 16384, 32,
                          4097, 100, 8192], seed=7)
    return objects, maps_of(objects,
                            ref_objects.parity_of_objects(REF, objects))


@pytest.fixture
def deep(slab_lanes, small, depth):
    """Sets a slab width that cuts ``small``'s lanes into ``depth + 3``
    slabs, more than the depth's staging: (lanes, slabs)."""
    lanes = sum(stripe.shape[1] for stripe in small[1])
    slabs = depth + 3
    width = next(w for w in range(128, lanes, 128) if -(-lanes // w) == slabs)
    slab_lanes(width)
    return lanes, slabs


def test_there_are_91_double_erasures():
    assert len(DOUBLES) == 91


def test_the_slab_width_comes_from_the_shape_alone(kernels):
    lanes = kernels._slab_lanes(K)
    assert lanes == 3350528 == 409 * kernels.LANE_TILE
    assert K * lanes <= kernels.SLAB_BYTES < K * (lanes + kernels.LANE_TILE)
    assert kernels._slab_lanes(8) == kernels.SLAB_BYTES // 8
    # the process's arena holds what either registry cell's loop leaves
    # at rest while its driver keeps one encode: a second encode's
    # result, a decode's, and the staging of the loop's depth
    staging = (kernels.SLABS_IN_FLIGHT + 1) * kernels.SLAB_BYTES
    assert kernels.ARENA_BYTES >= (M + 2) * 107233280 + staging
    assert kernels.ARENA_BYTES >= (3 + 1) * 1024 * 131072 + staging


def test_the_listed_sizes_in_one_call_equal_the_reference_and_isa(
        tpu_codec, isa_codec, slab_lanes):
    slab_lanes(8192)
    objects = objects_of(SIZES)
    parity = tpu_codec.encode_objects(objects)
    want = ref_objects.parity_of_objects(REF, objects)
    assert len(parity) == len(objects)
    for obj, got, par in zip(objects, parity, want):
        length = ref.chunk_bytes(K, obj.size)
        assert got.shape == (M, length) == par.shape and got.dtype == np.uint8
        assert tpu_codec.get_chunk_size(obj.size) == length
        assert np.array_equal(got, par), obj.size
        host = isa_codec.encode(set(range(N)), obj.tobytes())
        for r in range(M):
            assert got[r].flags["C_CONTIGUOUS"]         # a chunk is whole
            assert np.array_equal(got[r], host[K + r]), (obj.size, r)
    # and back: a decode of the last data chunk and a parity chunk
    chunk_maps = maps_of(objects, parity)
    lost = tpu_codec.decode_objects([K - 1, K + 1], chunk_maps)
    for obj, stripe, got in zip(objects, chunk_maps, lost):
        assert np.array_equal(got, stripe[[K - 1, K + 1]])
        assert np.array_equal(got, ref.recovered(REF, stripe,
                                                 [K - 1, K + 1]))
        assert ref_objects.tail_nonzero(K, obj.size, K - 1, got[0]) == 0
        host = isa_codec.decode({K - 1, K + 1}, {
            i: stripe[i] for i in range(N) if i not in (K - 1, K + 1)})
        assert np.array_equal(got[0], host[K - 1])
        assert np.array_equal(got[1], host[K + 1])


def test_bytes_objects_and_k8_m3_once(slab_lanes):
    slab_lanes(LANES, 8)
    profile = {"k": 8, "m": 3, "technique": "reed_sol_van"}
    codec = registry().factory("tpu", {"k": "8", "m": "3"})
    host = registry().factory("isa", {"k": "8", "m": "3"})
    objects = objects_of([4096, 1, 9000, 4095, 65536], seed=8)
    parity = codec.encode_objects([obj.tobytes() for obj in objects])
    want = ref_objects.parity_of_objects(profile, objects)
    for obj, got, par in zip(objects, parity, want):
        assert np.array_equal(got, par)
        chunks = host.encode(set(range(11)), obj.tobytes())
        assert all(np.array_equal(got[r], chunks[8 + r]) for r in range(3))
    chunk_maps = maps_of(objects, parity, 8)
    for erased in ([0], [7, 8], [1, 4, 10]):
        for stripe, got in zip(chunk_maps,
                               codec.decode_objects(erased, chunk_maps)):
            assert np.array_equal(got, stripe[erased])
            assert np.array_equal(got, ref.recovered(profile, stripe, erased))
    assert codec.perf.dump()["engine_gN"] == codec.perf.dump()["launches"]


@pytest.mark.parametrize("erased", DOUBLES + OTHERS,
                         ids=["-".join(map(str, p))
                              for p in DOUBLES + OTHERS])
def test_every_double_erasure_and_some_others_decode(tpu_codec, slab_lanes,
                                                     small, erased):
    """Poisoned at the erased positions, which are never read; the
    erased chunks' own bytes back, and the reference's from the
    survivors."""
    slab_lanes(LANES)
    objects, chunk_maps = small
    poisoned = [stripe.copy() for stripe in chunk_maps]
    for stripe in poisoned:
        stripe[erased] = 0xA5
    got = tpu_codec.decode_objects(erased, poisoned)
    assert len(got) == len(objects)
    for stripe, lost in zip(chunk_maps, got):
        assert lost.shape == (len(erased), stripe.shape[1])
        assert np.array_equal(lost, stripe[erased])
    for i in (0, 3, 7):
        assert np.array_equal(got[i], ref.recovered(REF, chunk_maps[i],
                                                    erased))


def test_an_erased_position_is_never_read(slab_lanes, small):
    slab_lanes(LANES)
    _, chunk_maps = small
    erased = [2, 11]
    watched = []
    for stripe in chunk_maps:
        view = stripe.view(Watched)
        view.reads = []
        watched.append(view)
    codec = registry().factory("tpu", PROFILE)
    got = codec.decode_objects(erased, watched)
    for stripe, lost in zip(chunk_maps, got):
        assert np.array_equal(lost, stripe[erased])
    for view in watched:
        assert view.reads                       # read, and by rows only:
        for key in view.reads:                  # runs of survivor ids
            rows = key if isinstance(key, slice) else key[0]
            assert isinstance(rows, slice) and rows.step is None
            assert not set(range(rows.start, rows.stop)) & set(erased)
        assert {i for key in view.reads for i in range(
            (key if isinstance(key, slice) else key[0]).start,
            (key if isinstance(key, slice) else key[0]).stop)} == {
            0, 1, 3, 4, 5, 6, 7, 8, 9, 10}


def uploads_of(kernels, monkeypatch) -> list[np.ndarray]:
    """Every slab as it was uploaded, copied before its launch."""
    uploaded: list[np.ndarray] = []
    real = kernels._launch_batch
    monkeypatch.setattr(
        kernels, "_launch_batch", lambda matrix, xd, perf=None:
        uploaded.append(np.array(xd)) or real(matrix, xd, perf))
    return uploaded


# objects against a slab of 1024 lanes: 416-lane objects (4 KiB) make
# the second end at 832, the third lie across the edge in its middle;
# 4 KiB + 1920 B + 4 KiB (416 + 192 + 416 = 1024) end exactly at it; a
# 2240-byte object (224 lanes) more ends one 32-byte lane group past it
EDGES = {"across_in_the_middle": [4096, 4096, 4096, 4096],
         "exactly_at_the_end": [4096, 1920, 4096, 4096],
         "one_lane_group_past": [4096, 1920, 4096 + 320, 4096],
         "a_piece_of_three_slabs": [100, 25000, 4096]}


@pytest.mark.parametrize("case", list(EDGES))
def test_objects_that_meet_a_slabs_edge(kernels, monkeypatch, slab_lanes,
                                        isa_codec, case):
    """The builder's choice is to let an object lie across two slabs:
    the lanes of a call are cut every ``_slab_lanes`` columns wherever
    an object begins or ends, and only the call's last slab is padded.
    What is uploaded is exactly the tool's chunks, laid end to end."""
    slab_lanes(LANES)
    uploaded = uploads_of(kernels, monkeypatch)
    objects = objects_of(EDGES[case], seed=len(case))
    lengths = [ref.chunk_bytes(K, obj.size) for obj in objects]
    ends = np.cumsum(lengths)
    if case == "across_in_the_middle":
        assert ends[1] < LANES < ends[2]
    elif case == "exactly_at_the_end":
        assert ends[2] == LANES
    elif case == "one_lane_group_past":
        assert ends[2] == LANES + 32
    else:
        assert ends[1] - lengths[1] < LANES and ends[1] > 2 * LANES
    codec = registry().factory("tpu", PROFILE)
    parity = codec.encode_objects(objects)
    row = np.concatenate([ref.chunks_of(K, obj.tobytes())
                          for obj in objects], axis=1)
    slabs = -(-row.shape[1] // LANES)
    assert len(uploaded) == slabs
    assert all(xd.shape == (1, K, LANES) for xd in uploaded)
    sent = np.concatenate([xd[0] for xd in uploaded], axis=1)
    assert np.array_equal(sent[:, :row.shape[1]], row)
    for obj, got in zip(objects, parity):
        host = isa_codec.encode(set(range(N)), obj.tobytes())
        assert all(np.array_equal(got[r], host[K + r]) for r in range(M))
    dump = codec.perf.dump()
    assert dump["slabs"] == slabs and dump["objects"] == len(objects)
    assert dump["lanes"] == row.shape[1]
    assert dump["lanes_launched"] == slabs * LANES
    assert dump["lanes_padded"] == slabs * LANES - row.shape[1]
    assert dump["bytes_in"] == K * row.shape[1]
    assert dump["bytes_out"] == M * row.shape[1]
    assert dump.get("pipelined", 0) == (slabs > 1) and "stripes" not in dump
    # and the decode across the same edges
    chunk_maps = maps_of(objects, parity)
    for stripe, lost in zip(chunk_maps,
                            codec.decode_objects([0, K - 1], chunk_maps)):
        assert np.array_equal(lost, stripe[[0, K - 1]])


def test_the_tail_of_every_last_data_chunk_is_zero_in_what_is_encoded(
        kernels, monkeypatch, slab_lanes):
    """Staging is reused and never cleared: the zeros past an object's
    end are written with every fill, over whatever an earlier slab left
    there, and no byte of a neighbour is inside an object's lanes."""
    slab_lanes(LANES)
    uploaded = uploads_of(kernels, monkeypatch)
    codec = registry().factory("tpu", PROFILE)
    enough = -(-(kernels.SLABS_IN_FLIGHT + 1) * LANES
               // ref.chunk_bytes(K, 4096))
    loud = [np.full(size, 0xFF, np.uint8) for size in [4096] * enough]
    codec.encode_objects(loud)                  # every buffer all ones
    del uploaded[:]
    sizes = [1, 4095, 33, 4096, 700, 2, 4097, 31, 5000, 64]
    objects = [np.full(size, 0xFF, np.uint8) for size in sizes]
    codec.encode_objects(objects)
    sent = np.concatenate([xd[0] for xd in uploaded], axis=1)
    at = 0
    for size in sizes:
        length = ref.chunk_bytes(K, size)
        mine = sent[:, at:at + length].reshape(-1)
        assert (mine[:size] == 0xFF).all() and not mine[size:].any(), size
        at += length


@pytest.mark.parametrize("width,slabs", [(5376, 1), (2688, 2), (1792, 3),
                                         (768, 7)],
                         ids=["1slab", "2slabs", "3slabs", "7slabs"])
@every_depth
def test_the_references_bytes_at_1_2_3_and_7_slabs(slab_lanes, small, depth,
                                                   width, slabs):
    """5280 lanes through one slab (filled on the caller's thread), two
    and three (a buffer each, none refilled) and seven (more than the
    depth's staging: buffers refilled behind their readers): parity and
    recovered chunks are ``benchmark/reference/``'s, and what lies at an
    erased position is not read."""
    slab_lanes(width)
    objects, chunk_maps = small
    lanes = sum(stripe.shape[1] for stripe in chunk_maps)
    assert -(-lanes // width) == slabs
    codec = registry().factory("tpu", PROFILE)
    before = set(threading.enumerate())
    for got, stripe in zip(codec.encode_objects(objects), chunk_maps):
        assert np.array_equal(got, stripe[K:])
    for erased in ([4], [0, 12], [2, 9, 13]):
        blanked = [stripe.copy() for stripe in chunk_maps]
        for stripe in blanked:
            stripe[erased] = 0xA5
        lost = codec.decode_objects(erased, blanked)
        for got, stripe in zip(lost, chunk_maps):
            assert np.array_equal(got, stripe[erased])
        assert np.array_equal(lost[7], ref.recovered(REF, blanked[7], erased))
    assert set(threading.enumerate()) == before     # no worker outlives it
    dump = codec.perf.dump()
    assert dump["slabs"] == 4 * slabs
    assert dump.get("gathers", 0) == (4 * slabs if slabs > 1 else 0)
    assert dump.get("gathers_ahead", 0) <= dump.get("gathers", 0)
    assert dump.get("uploads_beside", 0) <= 4 * depth * (slabs - 1)


@pytest.mark.parametrize("width,slabs", [(5376, 1), (2688, 2), (1792, 3),
                                         (768, 7)],
                         ids=["1slab", "2slabs", "3slabs", "7slabs"])
def test_each_threads_sections_in_order_at_1_2_3_and_7_slabs(
        kernels, sections, slab_lanes, small, width, slabs):
    """A call over pieces opens what the uniform call opens, between its
    two ``registry.prepare``: every landing's waits nested in its
    ``registry.drain``, the last slab under ``registry.copy_out`` as it
    was, and on the worker a fill a slab."""
    slab_lanes(width)
    objects, chunk_maps = small
    codec = registry().factory("tpu", PROFILE)
    codec.decode_objects([3, 7], chunk_maps)
    assert sections[0] == "registry.matrix"          # the table miss
    assert [s for s in sections
            if s not in ("registry.matrix", KERNEL)] == (
        ["registry.prepare"] + caller_sections(slabs, GATHERED)
        + ["registry.prepare"])
    assert flat_of(sections) == caller_sections(slabs, GATHERED)
    nesting_holds(sections)
    dump = codec.perf.dump()
    if slabs == 1:                  # filled here, under registry.marshal
        assert sections.elsewhere == {}
        assert "gathers" not in dump
        return
    mine = sections.elsewhere[GATHERER]
    assert [s for s in mine if s != "registry.gather.wait"] == \
        ["registry.gather"] * slabs
    assert mine.count("registry.gather.wait") == dump.get("staging_waits", 0)
    assert dump["gathers"] == slabs


def test_an_empty_call_and_a_call_of_one_object(kernels, arena, slab_lanes,
                                                isa_codec):
    slab_lanes(LANES)
    codec = registry().factory("tpu", PROFILE)
    assert codec.encode_objects([]) == []
    assert codec.decode_objects([1, 2], []) == []
    (nothing,) = codec.encode_objects([np.empty(0, np.uint8)])
    assert nothing.shape == (M, 0)
    assert codec.perf.dump().get("launches", 0) == 0
    (obj,) = objects_of([5000])
    (parity,) = codec.encode_objects([obj])
    host = isa_codec.encode(set(range(N)), obj.tobytes())
    assert all(np.array_equal(parity[r], host[K + r]) for r in range(M))
    (stripe,) = maps_of([obj], [parity])
    (lost,) = codec.decode_objects([4, 12], [stripe])
    assert np.array_equal(lost, stripe[[4, 12]])
    dump = codec.perf.dump()
    assert dump["launches"] == dump["slabs"] == dump["objects"] == 2
    assert "pipelined" not in dump
    # a call of one slab borrows too: its result and one staging buffer
    # (the decode's smaller result fits the buffer the encode gave back);
    # it has no slab to fill ahead, and no worker
    assert leases(codec) == (1, 3)
    assert "gathers" not in dump and "gathers_ahead" not in dump


def test_one_program_a_count_of_output_rows_whatever_the_mix(
        kernels, slab_lanes):
    slab_lanes(LANES)
    kernels.clear_kernel_cache()
    codec = registry().factory("tpu", PROFILE)
    mixes = [[4096, 100, 8192, 1, 5000], [333, 16384, 32, 4097, 100, 8192,
                                          65536, 7]]
    for sizes in mixes:
        objects = objects_of(sizes, seed=len(sizes))
        parity = codec.encode_objects(objects)
        chunk_maps = maps_of(objects, parity)
        for erased in ([0, 1], [5, 13], [9, 10]):
            for stripe, lost in zip(
                    chunk_maps, codec.decode_objects(erased, chunk_maps)):
                assert np.array_equal(lost, stripe[erased])
    # r = 4 and r = 2 at the slab's shape, and nothing else
    assert kernels._compiled_batch.cache_info().currsize == 2
    assert kernels._compiled_batch_gN.cache_info().currsize == 0
    assert kernels.batch_engine(codec.encode_matrix[K:], 1, K, LANES) == "v1"
    dump = codec.perf.dump()
    assert dump["engine_v1"] == dump["launches"] == 8
    assert dump["table_misses"] == 3 and dump["table_hits"] == 3
    assert dump["parity_gates"] == 4                # one a matrix: one shape


def test_sections_and_counters_of_a_call_over_objects(kernels, sections,
                                                      arena, slab_lanes,
                                                      small):
    slab_lanes(LANES)
    objects, chunk_maps = small
    lanes = sum(stripe.shape[1] for stripe in chunk_maps)
    slabs = -(-lanes // LANES)
    assert slabs == 6
    codec = registry().factory("tpu", PROFILE)
    parity = codec.encode_objects(objects)
    flat = [s for s in sections if s not in ("registry.matrix", KERNEL)]
    assert flat == (["registry.prepare"] + caller_sections(slabs, GATHERED)
                    + ["registry.prepare"])
    assert flat.count("registry.drain") == slabs - 1
    # the fills are the worker's, a section each on its own thread, the
    # wait for a launch inside the refills that had to
    dump = codec.perf.dump()
    mine = sections.elsewhere[GATHERER]
    assert sections.elsewhere == {GATHERER: mine}
    assert [s for s in mine if s != "registry.gather.wait"] == \
        ["registry.gather"] * slabs
    assert mine.count("registry.gather.wait") == \
        dump.pop("staging_waits", 0)    # as the launches happen to finish
    nesting_holds(sections)
    assert 0 <= dump.pop("gathers_ahead") <= slabs  # as the fills do
    assert 0 <= dump.pop("uploads_beside", 0) \
        <= kernels.SLABS_IN_FLIGHT * slabs          # as the launches finish
    held = staging_of(slabs)
    assert dump == {
        "launches": 1, "engine_v1": 1, "objects": len(objects),
        "lanes": lanes, "lanes_launched": slabs * LANES,
        "lanes_padded": slabs * LANES - lanes, "bytes_in": K * lanes,
        "bytes_out": M * lanes, "slabs": slabs, "pipelined": 1,
        "gathers": slabs, "arena_misses": 1 + held, "parity_gates": 1}
    del parity
    sections.clear()
    codec.decode_objects([3, 7], chunk_maps)
    assert sections[0] == "registry.matrix"          # the table miss
    assert sections[1] == "registry.prepare" == sections[-1]
    assert [s for s in sections if s != "registry.matrix"] == flat
    two = codec.perf.dump()
    assert two["table_misses"] == 1 and two["objects"] == 2 * len(objects)
    assert two["bytes_out"] == (M + 2) * lanes
    assert two["gathers"] == 2 * slabs >= two["gathers_ahead"]
    assert leases(codec) == (1 + held, 1 + held)     # all the kept ones


# -- leases and staging, as the uniform call keeps them ------------------------

def test_the_results_are_views_of_one_lease_and_keep_it(kernels, arena,
                                                        slab_lanes, small):
    slab_lanes(LANES)
    objects, chunk_maps = small
    lanes = sum(stripe.shape[1] for stripe in chunk_maps)
    codec = registry().factory("tpu", PROFILE)
    parity = codec.encode_objects(objects)
    base = parity[0].ctypes.data
    for got, stripe in zip(parity, chunk_maps):
        assert got.strides == (lanes, 1) and got.flags["WRITEABLE"]
        assert base <= got.ctypes.data < base + lanes
    held = staging_of(6)
    staging = held * K * LANES
    assert arena.at_rest() == staging               # the result is out
    kept = parity[5][1:, ::2]                       # a view of a view
    want = chunk_maps[5][K + 1:, ::2].copy()
    del parity, got
    gc.collect()
    assert arena.at_rest() == staging
    for _ in range(2):                              # later calls: other memory
        later = codec.encode_objects(objects[::-1])
        assert not any(np.shares_memory(out, kept) for out in later)
        del later
    assert np.array_equal(kept, want)
    del kept
    assert arena.events[-1] == ("given", base)
    assert arena.at_rest() == staging + 2 * M * lanes
    # the staging new once and kept twice; a result new while ``kept``
    # held the first, and that one kept for the last call
    assert leases(codec) == (2 * held + 1, held + 2)


@every_depth
def test_staging_is_refilled_only_behind_the_launch_that_read_it(
        kernels, monkeypatch, arena, sections, deep, small, depth):
    objects, chunk_maps = small
    codec = registry().factory("tpu", PROFILE)
    log = arena.events
    _, slabs = deep
    held = staging_of(slabs)
    assert held == depth + 1
    before = set(threading.enumerate())
    buffers, outs = later_launches(kernels, monkeypatch,
                                codec.encode_matrix[K:], log, slabs)
    parity = codec.encode_objects(objects)
    for got, stripe in zip(parity, chunk_maps):
        assert np.array_equal(got, stripe[K:])
    assert set(threading.enumerate()) == before     # the worker is gone
    fills = [ev for ev in log if ev[0] == "fill"]
    fill_at = [i for i, ev in enumerate(log) if ev[0] == "fill"]
    assert len(fills) == len(outs) == slabs and len(buffers) == held
    assert [ev[1] for ev in fills] == [fills[i % held][1]
                                       for i in range(slabs)]
    assert all(ev[2] == [] for ev in fills), fills  # nothing unfinished
    assert {ev[3] for ev in fills} == {GATHERER}    # none on this thread
    # slab n's buffer is refilled for slab n + held, behind slab n's launch
    # and while slab n+1 is in flight; a fill is handed over a slab ahead
    for n in range(slabs - held):
        assert log.index(("done", n)) < fill_at[n + held] \
            < log.index(("done", n + 1))
    for n in range(slabs - 1):
        assert fill_at[n] < fill_at[n + 1] \
            < log.index(("launch", n, fills[n][1]))
    assert log[-held:] == [("given", into.ctypes.data)
                           for into in buffers.values()]
    assert log[-held - 2:-held] == [("done", slabs - 1),
                                    ("landed", slabs - 1)]
    # never more than depth + 1 slabs between device_put and landing
    assert most_in_flight(log) == depth + 1
    dump = codec.perf.dump()
    assert dump["staging_waits"] == slabs - held
    assert 0 < dump["uploads_beside"] <= sum(
        min(i, depth) for i in range(slabs))
    assert dump["gathers"] == slabs >= dump["gathers_ahead"] >= 0
    # no launch here is done until waited for: every refill's fill holds
    # the wait, and the caller waits only for those nobody refilled behind
    # (the last slab's wait is ``registry.device_wait``)
    assert sections.elsewhere == {GATHERER: (
        ["registry.gather"] * held
        + ["registry.gather", "registry.gather.wait"] * (slabs - held))}
    assert flat_of(sections) == caller_sections(slabs, GATHERED)
    assert sections.count(KERNEL) == held - 1
    nesting_holds(sections)


@every_depth
@pytest.mark.parametrize("where", [
    "first_slab", "third_slab", "padded_last_slab"])
def test_buffers_come_back_when_a_slab_raises(kernels, monkeypatch, arena,
                                              deep, small, depth, where):
    objects, chunk_maps = small
    lanes, slabs = deep
    fail_at = {"first_slab": 0, "third_slab": 2,
               "padded_last_slab": slabs - 1}[where]
    codec = registry().factory("tpu", PROFILE)
    before = set(threading.enumerate())
    buffers, outs = later_launches(kernels, monkeypatch,
                                codec.encode_matrix[K:], arena.events, slabs,
                                fail_at=fail_at)
    with pytest.raises(SlabFailed):
        codec.encode_objects(objects)
    assert set(threading.enumerate()) == before
    assert len(outs) == fail_at and all(out.done for out in outs)
    came_back(arena, codec, buffers, slabs, result=M * lanes)


@every_depth
@pytest.mark.parametrize("where", [
    "first_slab", "second_slab", "a_refill", "padded_last_slab"])
def test_a_fill_that_raises_on_the_worker_comes_out_of_the_call(
        kernels, monkeypatch, arena, sections, deep, small, depth, where):
    """The worker's exception is the call's, raised where the caller's
    thread asks for that slab: the launches made before it are waited
    for, every staging buffer goes back, and no thread is left."""
    objects, chunk_maps = small
    lanes, slabs = deep
    fill_fails_at = {"first_slab": 0, "second_slab": 1,
                     "a_refill": staging_of(slabs),
                     "padded_last_slab": slabs - 1}[where]
    codec = registry().factory("tpu", PROFILE)
    before = set(threading.enumerate())
    buffers, outs = later_launches(kernels, monkeypatch,
                                codec.encode_matrix[K:], arena.events, slabs,
                                fill_fails_at=fill_fails_at)
    with pytest.raises(SlabFailed) as caught:
        codec.encode_objects(objects)
    assert caught.value.args == (fill_fails_at,)
    del caught                              # and the frames it holds
    assert set(threading.enumerate()) == before
    assert len(outs) == fill_fails_at and all(out.done for out in outs)
    came_back(arena, codec, buffers, slabs, result=M * lanes)
    assert sections.open_now == []          # the fill's and the marshal's
    nesting_holds(sections)


@every_depth
@pytest.mark.parametrize("where", [
    "first_landing", "last_in_the_loop", "at_the_close", "under_copy_out"])
def test_a_landing_that_raises_leaves_no_section_open_and_no_staging_out(
        kernels, monkeypatch, arena, sections, deep, small, depth, where):
    """``depth + 3`` slabs and a result whose copy to the host raises,
    inside the loop (slab n lands behind launch n + depth: slabs 0-2),
    at the close (the last but one) and under ``registry.copy_out``
    (the last): the error is the call's, every section is left, every
    launch waited for, the staging given back."""
    objects, chunk_maps = small
    lanes, slabs = deep
    last_inside = slabs - depth - 1
    land_fails_at = {"first_landing": 0, "last_in_the_loop": last_inside,
                     "at_the_close": slabs - 2,
                     "under_copy_out": slabs - 1}[where]
    codec = registry().factory("tpu", PROFILE)
    before = set(threading.enumerate())
    buffers, outs = later_launches(kernels, monkeypatch,
                                   codec.encode_matrix[K:], arena.events,
                                   slabs, land_fails_at=land_fails_at)
    with pytest.raises(SlabFailed) as caught:
        codec.encode_objects(objects)
    assert caught.value.args == (land_fails_at,)
    del caught                              # and the frames it holds
    assert set(threading.enumerate()) == before
    assert len(outs) == min(land_fails_at + depth + 1, slabs)
    assert all(out.done for out in outs)
    whole = caller_sections(slabs, GATHERED)
    upto = len(whole) - 1 if land_fails_at == slabs - 1 else [
        i for i, name in enumerate(whole)
        if name == "registry.drain.link"][land_fails_at]
    assert flat_of(sections) == whole[:upto + 1]
    nesting_holds(sections)
    came_back(arena, codec, buffers, slabs, result=M * lanes,
              counted=land_fails_at > last_inside)


def test_a_parity_miss_raises_out_of_a_call_over_objects(kernels, slab_lanes,
                                                         small):
    """Either serves or raises: a matrix's first launch at the slab's
    shape is held to the host oracle, whichever engine serves."""
    slab_lanes(LANES)
    codec = registry().factory("tpu", PROFILE)
    real = kernels._compiled_batch
    kernels._gN_verified.clear()
    launched = []
    kernels._compiled_batch = lambda *a: (
        lambda w, xd, fn=real(*a): launched.append(xd.shape) or fn(w, xd) ^ 1)
    try:
        with pytest.raises(kernels.KernelParityError):
            codec.encode_objects(small[0])
        with pytest.raises(kernels.KernelParityError):
            codec.decode_objects([1, 2], small[1])
    finally:
        kernels._compiled_batch = real
    assert launched == [(1, K, LANES)] * 2          # nothing served after it
    assert "launches" not in codec.perf.dump()
    assert codec.perf.dump()["parity_gates"] == 2


@pytest.mark.parametrize("workload", ["encode", "decode"])
def test_the_tool_times_the_mixed_call_and_names_the_engine(
        slab_lanes, capsys, workload):
    """``ec_bench --batch B --size a,b,c``: B objects of each size a
    call through the same two entry points the cell's driver calls."""
    from ceph_tpu.tools import ec_bench

    slab_lanes(LANES)
    assert ec_bench.main([
        "--plugin", "tpu", "-k", str(K), "-m", str(M), "-P",
        "technique=cauchy", "-s", "4096,100,9000", "--batch", "3", "-i", "2",
        "-w", workload, "-e", "2", "--verify"]) == 0
    out, err = capsys.readouterr()
    seconds, kib = out.strip().split("\t")
    assert float(seconds) > 0
    assert int(kib) == 3 * (4096 + 100 + 9000) * 2 // 1024
    # the warm-up call and the timed ones (a decode: the encode that
    # made its inputs too), one engine on every launch
    assert err.strip() == f"engine: v1 x{3 if workload == 'encode' else 4} " \
        "launches"
