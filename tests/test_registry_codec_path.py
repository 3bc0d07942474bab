"""The registry's batch path from host memory (``ec/plugins/tpu.py`` ->
``ops/jax_backend.py`` -> ``ops/gf2kernels.py``): every erasure pattern
of k=8, m=3 decoded from numpy, one engine for every count of output
rows, and what a call leaves behind to be measured by: the
``registry.*`` sections, the ``ec_registry`` counters, the programs'
names and scope.  A call from numpy to numpy streams through the device
in slabs (``gf2kernels.SLAB_BYTES``; the toy batches here are one slab
unless a test lowers it): the same bytes at every number of slabs, one
result array a call, and the gather of a call of several slabs on a
worker thread of the call's own, one slab ahead of the caller's uploads
over ``SLABS_IN_FLIGHT + 1`` staging buffers, each refilled only behind
the launch that read it, and never more than ``SLABS_IN_FLIGHT + 1``
slabs between ``device_put`` and landing (the tests of the loop's order
of events run at depth 2, PR 46's, and at the module's own).  The result
and the staging of a call of several slabs are
borrowed from the process's host arena (``ops/host_arena.py``): a
result is the caller's own until the last array over its memory is
gone, and only then the next call's.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import gc
import itertools
import sys
import threading
import time

import numpy as np
import pytest

import ceph_tpu.ops.gf2kernels as g
from benchmark.reference import codec as ref
from ceph_tpu.common import tracing
from ceph_tpu.ec import registry
from ceph_tpu.gf import gf_matmul
from ceph_tpu.gf.matrices import decode_index_for

K, M, B, L = 8, 3, 4, 256
N = K + M
WIDE = 10               # stripes of the batches that run as several slabs
# stripes a slab -> the slabs a WIDE batch is cut into
SLABS = {10: [10], 6: [6, 4], 4: [4, 4, 2], 2: [2, 2, 2, 2, 2]}
every_cut = pytest.mark.parametrize("per_slab", list(SLABS), ids=[
    f"{len(cut)}slabs" for cut in SLABS.values()])
PATTERNS = [list(p) for e in range(1, M + 1)
            for p in itertools.combinations(range(N), e)]
ENGINES = ("sched", "gN", "v1", "xla")
GATHERER = "registry-gather_0"      # the one thread of a call's worker
# the depths the loop's order of events is held at: PR 46's and the module's
DEPTHS = sorted({2, g.SLABS_IN_FLIGHT})
every_depth = pytest.mark.parametrize(
    "depth", DEPTHS, ids=[f"depth{d}" for d in DEPTHS], indirect=True)
# (depth, slabs): below, at and above the depth + 1 slabs a call keeps
DEPTHS_AND_SLABS = [(d, n) for d in DEPTHS for n in (d, d + 1, d + 3)]
every_depth_and_count = pytest.mark.parametrize(
    "depth,slabs", DEPTHS_AND_SLABS, indirect=["depth"],
    ids=[f"depth{d}-{n}slabs" for d, n in DEPTHS_AND_SLABS])


@pytest.fixture
def depth(request, monkeypatch):
    """``SLABS_IN_FLIGHT`` set to the test's depth (the module's own
    where the test names none)."""
    value = getattr(request, "param", g.SLABS_IN_FLIGHT)
    monkeypatch.setattr(g, "SLABS_IN_FLIGHT", value)
    return value


def staging_of(slabs: int) -> int:
    """Staging buffers a gathering call of ``slabs`` slabs borrows."""
    return min(g.SLABS_IN_FLIGHT + 1, slabs)


@pytest.fixture(scope="module", autouse=True)
def packed_engine():
    """Every test of this file sees the engine choice of a TPU backend:
    ``gN``, here through the Pallas interpreter, whose matrix is an
    operand (the CPU's choice, ``sched``, compiles a program a matrix:
    231 of them)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(g, "_want_pallas", lambda: True)
    g.clear_kernel_cache()
    yield
    mp.undo()
    g.clear_kernel_cache()


@pytest.fixture
def packed():
    """The kernel module with no launch verified yet."""
    g._gN_verified.clear()
    return g


@pytest.fixture
def arena(monkeypatch):
    """An empty host arena of the process's cap in the kernel module's
    place, with every buffer that comes back to it written down."""
    from ceph_tpu.ops.host_arena import HostArena

    class Logged(HostArena):
        def __init__(self, cap):
            super().__init__(cap)
            self.events: list[tuple] = []

        def give(self, buf):
            self.events.append(("given", buf.ctypes.data))
            super().give(buf)

    fresh = Logged(g.ARENA_BYTES)
    monkeypatch.setattr(g, "_arena", fresh)
    return fresh


def leases(codec) -> tuple[int, int]:
    """(arena_hits, arena_misses) of a plugin's ``ec_registry`` set."""
    dump = codec.perf.dump()
    return dump.get("arena_hits", 0), dump.get("arena_misses", 0)


@pytest.fixture(scope="module")
def stripes():
    """(B, k+m, L) whole stripes: seeded data, parity by the plain
    GF(2^8) product of the generator's parity rows."""
    codec = registry().factory("isa", {"k": str(K), "m": str(M)})
    data = np.random.default_rng(45).integers(
        0, 256, (B, K, L), dtype=np.uint8)
    parity = np.stack([gf_matmul(codec.encode_matrix[K:], d) for d in data])
    return np.concatenate([data, parity], axis=1)


@functools.lru_cache(maxsize=None)
def whole_stripes(count: int) -> np.ndarray:
    """(count, k+m, L) whole stripes, as ``stripes``."""
    codec = registry().factory("isa", {"k": str(K), "m": str(M)})
    data = np.random.default_rng(46).integers(
        0, 256, (count, K, L), dtype=np.uint8)
    parity = np.stack([gf_matmul(codec.encode_matrix[K:], d) for d in data])
    return np.concatenate([data, parity], axis=1)


@pytest.fixture(scope="module")
def wide():
    """(WIDE, k+m, L) whole stripes."""
    return whole_stripes(WIDE)


@pytest.fixture
def pairs(monkeypatch):
    """Sets the slab to two stripes (one group of the packed engine at
    k=8) and hands out whole stripes for so many slabs."""
    def of(slabs: int) -> np.ndarray:
        monkeypatch.setattr(g, "SLAB_BYTES", 2 * K * L)
        assert g._slab_stripes(2 * slabs, K, L) == 2
        return whole_stripes(2 * slabs)
    return of


@pytest.fixture
def slab_of(monkeypatch):
    """Sets the slab to so many of this file's stripes."""
    def set_slab(stripes: int) -> None:
        monkeypatch.setattr(g, "SLAB_BYTES", stripes * K * L)
        assert g._slab_stripes(WIDE, K, L) == stripes
    return set_slab


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


class Watched(np.ndarray):
    """A chunk map that writes down every index it is read by."""
    reads = None

    def __getitem__(self, key):
        if self.reads is not None:
            self.reads.append(key)
        return super().__getitem__(key)


@every_cut
def test_each_slabs_survivors_are_gathered_once_into_a_c_ordered_array(
        packed, monkeypatch, slab_of, wide, per_slab):
    """``stripes[lo:hi, index]`` comes back chunk axis outermost, and an
    upload of it copies the slab again: the gather makes the array the
    upload takes as it is, each stripe's survivors once, and reads
    nothing at an erased position."""
    slab_of(per_slab)
    erased, index = [0, 9], [1, 2, 3, 4, 5, 6, 7, 8]
    gathered, uploaded = [], []
    real = packed._gather_rows

    def gather(data, rows, lo, hi, into):
        slab = real(data, rows, lo, hi, into)
        gathered.append((lo, hi, slab.flags["C_CONTIGUOUS"], slab.copy()))
        return slab

    monkeypatch.setattr(packed, "_gather_rows", gather)
    real_launch = packed._launch_batch
    monkeypatch.setattr(
        packed, "_launch_batch", lambda matrix, xd, perf=None:
        uploaded.append(np.array(xd)) or real_launch(matrix, xd, perf))
    watched = wide.view(Watched)
    watched.reads = []
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    got = codec.decode_stripes(erased, watched, out_np=True)
    assert np.array_equal(got, wide[:, erased])
    spans = [(lo, hi) for lo, hi, _, _ in gathered]
    assert [hi - lo for lo, hi in spans] == SLABS[per_slab]
    assert spans[0][0] == 0 and spans[-1][1] == WIDE
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
    for (lo, hi, c_ordered, slab), xd in zip(gathered, uploaded):
        assert c_ordered and slab.shape == (hi - lo, K, L)
        assert np.array_equal(slab, wide[lo:hi, index])
        assert np.array_equal(xd, slab)     # uploaded as it was gathered
    assert not wide[:, index].flags["C_CONTIGUOUS"]
    # every read is (stripes of one slab, one surviving chunk), each once
    assert sorted((key[0].start, key[0].stop, key[1])
                  for key in watched.reads) == sorted(
        (lo, hi, chunk) for lo, hi in spans for chunk in index)


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


class Opened(list):
    """The sections the test's own thread opened, in order of opening;
    ``elsewhere``: those of every other thread, by the thread's name;
    ``under``: for every name, the sections it was opened directly
    inside (``""``: none); ``open_now``: those entered and not left."""

    def __init__(self) -> None:
        super().__init__()
        self.mine = threading.get_ident()
        self.elsewhere: dict[str, list[str]] = {}
        self.under: dict[str, set[str]] = {}
        self.open_now: list[str] = []


@pytest.fixture
def sections(monkeypatch):
    """Every section the registry path opens, thread by thread."""
    import ceph_tpu.ec.plugins.tpu as plugin
    opened = Opened()
    stacks: dict[int, list[str]] = {}

    @contextlib.contextmanager
    def record(name):
        me = threading.get_ident()
        stack = stacks.setdefault(me, [])
        opened.under.setdefault(name, set()).add(stack[-1] if stack else "")
        if me == opened.mine:
            opened.append(name)
        else:
            opened.elsewhere.setdefault(
                threading.current_thread().name, []).append(name)
        stack.append(name)
        opened.open_now.append(name)
        try:
            yield
        finally:
            stack.pop()
            opened.open_now.remove(name)

    monkeypatch.setattr(g, "section", record)
    monkeypatch.setattr(plugin, "section", record)
    return opened


UPLOAD = ["registry.upload", "registry.launch"]
GATHERED = ["registry.marshal"] + UPLOAD
# a landing inside the loop or at the close; the wait for a launch that
# is not done yet, which comes first in it, is opened only where it waits
KERNEL = "registry.drain.kernel"
DRAIN = ["registry.drain", "registry.drain.link", "registry.drain.land"]
CLOSE = ["registry.device_wait", "registry.copy_out"]
# every section that is opened inside another, and the one it is opened in
NESTED = {KERNEL: "registry.drain",
          "registry.drain.link": "registry.drain",
          "registry.drain.land": "registry.drain",
          "registry.gather.wait": "registry.gather"}


def caller_sections(slabs: int, per: list[str]) -> list[str]:
    """What the caller's thread opens, in order, in a call of ``slabs``
    slabs, ``registry.matrix``, ``registry.prepare`` and the kernel
    waits apart: ``per`` a slab, the slab ``SLABS_IN_FLIGHT`` back
    landed behind every launch once so many are in flight, the rest but
    one landed at the close, and the last under ``registry.copy_out``."""
    depth = g.SLABS_IN_FLIGHT
    out: list[str] = []
    for i in range(slabs):
        out += per + (DRAIN if i >= depth else [])
    return out + DRAIN * (min(slabs, depth) - 1) + CLOSE


def flat_of(opened: list[str]) -> list[str]:
    """The caller's sections without those a call may or may not open;
    a kernel wait is the first thing in its landing wherever it is."""
    for i, name in enumerate(opened):
        if name == KERNEL:
            assert opened[i - 1] == "registry.drain", opened[i - 1]
            assert opened[i + 1] == "registry.drain.link", opened[i + 1]
    return [s for s in opened
            if s not in ("registry.matrix", "registry.prepare", KERNEL)]


def nesting_holds(opened: Opened) -> None:
    """Each section was opened where it belongs: the nested ones inside
    theirs and nowhere else, and all are closed."""
    assert opened.open_now == []
    for name, inside in opened.under.items():
        if name in NESTED:
            assert inside == {NESTED[name]}, (name, inside)
        elif name != "registry.matrix":
            assert inside == {""}, (name, inside)


@every_depth
def test_one_encode_and_one_decode_move_sections_and_counters(
        packed, sections, stripes, arena, slab_of, wide, depth):
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
        "bytes_in": B * K * L, "bytes_out": B * M * L, "slabs": 1}

    sections.clear()
    erased = [1, 9]
    codec.decode_stripes(erased, stripes, out_np=True)
    assert sections[0] == "registry.matrix"          # the table miss
    assert [s for s in sections if s != "registry.matrix"] == [
        "registry.marshal", "registry.upload", "registry.launch",
        "registry.device_wait", "registry.copy_out"]
    two = codec.perf.dump()
    assert two["launches"] == 2 and two["stripes"] == 2 * B
    assert two["bytes_in"] == 2 * B * K * L
    assert two["bytes_out"] == B * M * L + B * len(erased) * L
    assert two["engine_gN"] == two["parity_gates"] == two["slabs"] == 2
    assert two["table_misses"] == 1 and "table_hits" not in two
    assert "pipelined" not in two and "staging_waits" not in two
    # a call of one slab borrows nothing and gathers on its own thread
    assert "arena_hits" not in two and "arena_misses" not in two
    assert "gathers" not in two and "gathers_ahead" not in two
    # nor does its upload go out beside another slab's
    assert "uploads_beside" not in two
    assert sections.elsewhere == {}

    # several slabs: an encode borrows its result, a decode its result
    # and a staging buffer a slab (three slabs here, under the depth's
    # staging at either depth), each counted once as a hit or a miss
    slab_of(4)
    held = staging_of(3)
    assert held == 3
    parity = codec.encode_batch(wide[:, :K], out_np=True)
    assert leases(codec) == (0, 1)                   # nothing kept yet
    assert "gathers" not in codec.perf.dump()        # no gather, no worker
    del parity
    lost = codec.decode_stripes(erased, wide, out_np=True)
    assert leases(codec) == (1, 1 + held)    # the encode's buffer; new staging
    del lost
    codec.decode_stripes(erased, wide, out_np=True)
    assert leases(codec) == (2 + held, 1 + held)
    three = codec.perf.dump()
    # three calls of three slabs: a slab's upload beside two others at most
    assert 0 <= three.get("uploads_beside", 0) <= 3 * (0 + 1 + 2)
    assert three["launches"] == 5 and three["pipelined"] == 3
    assert three["slabs"] == 2 + 3 * 3
    # the decodes' slabs were filled by their workers, and by nobody else
    assert three["gathers"] == 2 * 3
    assert 0 <= three["gathers_ahead"] <= three["gathers"]
    assert sections.elsewhere == {GATHERER: ["registry.gather"] * 6}
    nesting_holds(sections)


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


# -- the slab pipeline --------------------------------------------------------

@every_cut
def test_an_encode_is_the_same_bytes_at_every_number_of_slabs(
        packed, slab_of, wide, per_slab):
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    whole = codec.encode_batch(wide[:, :K], out_np=True)        # one launch
    slab_of(per_slab)
    got = codec.encode_batch(wide[:, :K], out_np=True)
    assert np.array_equal(got, whole) and np.array_equal(got, wide[:, K:])
    assert got.flags["C_CONTIGUOUS"] and got.shape == (WIDE, M, L)
    dump = codec.perf.dump()
    cut = SLABS[per_slab]
    assert dump["launches"] == dump["engine_gN"] == 2
    assert dump["stripes"] == 2 * WIDE
    assert dump["bytes_in"] == 2 * WIDE * K * L
    assert dump["bytes_out"] == 2 * WIDE * M * L
    assert dump["slabs"] == 1 + len(cut)
    assert dump.get("pipelined", 0) == (len(cut) > 1)
    # one gate a new shape: the whole batch, the slab, a ragged last slab
    assert dump["parity_gates"] == len({WIDE, *cut})


@pytest.mark.parametrize("erased", PATTERNS,
                         ids=["-".join(map(str, p)) for p in PATTERNS])
def test_every_erasure_pattern_decodes_in_three_ragged_slabs(
        tpu_codec, slab_of, wide, erased):
    """4 + 4 + 2 stripes through a staging buffer each, filled by the
    call's worker: the bytes of the one-launch call and of the plain
    product."""
    blanked = wide.copy()
    blanked[:, erased] = 0x5A
    whole = tpu_codec.decode_stripes(erased, blanked, out_np=True)
    before = tpu_codec.perf.dump()
    slab_of(4)
    got = tpu_codec.decode_stripes(erased, blanked, out_np=True)
    assert got.shape == (WIDE, len(erased), L) and got.flags["C_CONTIGUOUS"]
    assert np.array_equal(got, whole)
    assert np.array_equal(got, wide[:, erased])
    after = tpu_codec.perf.dump()
    assert after["launches"] - before["launches"] == 1
    assert after["slabs"] - before["slabs"] == 3
    assert after["pipelined"] - before.get("pipelined", 0) == 1
    assert after["gathers"] - before.get("gathers", 0) == 3


@every_depth
@pytest.mark.parametrize("per_slab,slabs", [(14, 1), (8, 2), (6, 3), (2, 7)],
                         ids=["1slab", "2slabs", "3slabs", "7slabs"])
def test_a_decode_from_rows_is_the_references_bytes_at_any_number_of_slabs(
        packed, monkeypatch, depth, per_slab, slabs):
    """14 stripes whose parity is ``benchmark/reference/``'s, recovered
    through one slab (gathered on the caller's thread), two and three (a
    buffer each, none refilled) and seven (more than the depth's
    staging: buffers refilled behind their readers)."""
    profile = {"k": K, "m": M, "technique": "reed_sol_van"}
    data = np.random.default_rng(51).integers(
        0, 256, (14, K, L), dtype=np.uint8)
    stripes = np.concatenate([data, ref.parity_of(profile, data)], axis=1)
    monkeypatch.setattr(packed, "SLAB_BYTES", per_slab * K * L)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    before = set(threading.enumerate())
    for erased in ([6], [0, 9], [3, 8, 10]):
        blanked = stripes.copy()
        blanked[:, erased] = 0xA5
        got = codec.decode_stripes(erased, blanked, out_np=True)
        assert np.array_equal(got, stripes[:, erased])
        assert np.array_equal(got[5], ref.recovered(profile, blanked[5],
                                                    erased))
    assert set(threading.enumerate()) == before
    dump = codec.perf.dump()
    assert dump["slabs"] == 3 * slabs
    assert dump.get("gathers", 0) == (3 * slabs if slabs > 1 else 0)
    assert dump.get("gathers_ahead", 0) <= dump.get("gathers", 0)
    assert dump.get("pipelined", 0) == (3 if slabs > 1 else 0)
    assert dump.get("uploads_beside", 0) <= 3 * depth * slabs
    assert ("uploads_beside" in dump) <= (slabs > 1)


REFERENCE = {"k": K, "m": M, "technique": "reed_sol_van"}


@functools.lru_cache(maxsize=None)
def reference_stripes(count: int) -> np.ndarray:
    """(count, k+m, L) stripes whose parity is ``benchmark/reference/``'s."""
    data = np.random.default_rng(53).integers(
        0, 256, (count, K, L), dtype=np.uint8)
    return np.concatenate([data, ref.parity_of(REFERENCE, data)], axis=1)


@every_depth_and_count
def test_an_encode_is_the_references_bytes_at_every_depth(
        packed, pairs, depth, slabs):
    pairs(slabs)
    stripes = reference_stripes(2 * slabs)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    got = codec.encode_batch(stripes[:, :K], out_np=True)
    assert np.array_equal(got, stripes[:, K:])
    dump = codec.perf.dump()
    assert dump["slabs"] == slabs
    assert dump.get("uploads_beside", 0) <= depth * slabs


@every_depth
@pytest.mark.parametrize("erased", PATTERNS,
                         ids=["-".join(map(str, p)) for p in PATTERNS])
def test_every_erasure_pattern_decodes_to_the_references_bytes_at_every_depth(
        tpu_codec, pairs, depth, erased):
    """Slabs of two stripes, three more of them than the deeper depth
    keeps in flight, so staging buffers are refilled behind their
    readers and slabs land inside the loop: the erased chunks are the
    original bytes, and stripe 5's the reference's own reconstruction."""
    slabs = max(DEPTHS) + 3
    pairs(slabs)
    stripes = reference_stripes(2 * slabs)
    blanked = stripes.copy()
    blanked[:, erased] = 0x5A
    before = tpu_codec.perf.dump()
    got = tpu_codec.decode_stripes(erased, blanked, out_np=True)
    assert got.shape == (2 * slabs, len(erased), L)
    assert got.flags["C_CONTIGUOUS"]
    assert np.array_equal(got, stripes[:, erased])
    assert np.array_equal(got[5], ref.recovered(REFERENCE, blanked[5], erased))
    after = tpu_codec.perf.dump()
    assert after["slabs"] - before["slabs"] == slabs
    assert after["gathers"] - before.get("gathers", 0) == slabs
    assert 0 <= after.get("uploads_beside", 0) \
        - before.get("uploads_beside", 0) <= depth * slabs


@pytest.mark.parametrize("per_slab", [10, 4], ids=["1slab", "3slabs"])
def test_the_result_is_the_callers_own_every_call(packed, slab_of, wide,
                                                  per_slab):
    """The driver keeps one encode's whole output across later calls
    and compares all of it: a recycled or aliased result is a wrong
    result."""
    slab_of(per_slab)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    first = codec.encode_batch(wide[:, :K], out_np=True)
    kept = first.copy()
    other = np.ascontiguousarray(wide[::-1, :K]) ^ 0xFF
    second = codec.encode_batch(other, out_np=True)
    third = codec.decode_stripes([0, 1, 2], wide, out_np=True)
    assert not np.shares_memory(first, second)
    assert not np.shares_memory(first, third)
    assert np.array_equal(first, kept) and np.array_equal(first, wide[:, K:])
    assert not np.array_equal(second, first)
    assert np.array_equal(third, wide[:, :3])
    for out in (first, second, third):
        assert out.flags["C_CONTIGUOUS"] and out.dtype == np.uint8


# -- the host arena -----------------------------------------------------------

def test_a_kept_slice_keeps_the_buffer_out_of_the_arena(
        packed, arena, slab_of, wide):
    """numpy hangs every view of a result on the result's owner: the
    buffer is nobody else's while one stripe of it is alive, whatever
    was dropped and whatever ran since."""
    slab_of(4)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    out = codec.encode_batch(wide[:, :K], out_np=True)
    lo, hi = out.ctypes.data, out.ctypes.data + out.nbytes
    piece = out[3][1:, ::2]                 # a view of a view
    del out
    gc.collect()
    assert arena.at_rest() == 0 and arena.events == []
    other = np.ascontiguousarray(wide[::-1, :K]) ^ 0xFF
    for _ in range(2):                      # the second reuses the first's
        later = codec.encode_batch(other, out_np=True)
        assert not lo <= later.ctypes.data < hi
        assert not np.shares_memory(later, piece)
        assert np.array_equal(later[0], gf_matmul(
            codec.encode_matrix[K:], other[0]))
        del later
    assert np.array_equal(piece, wide[3, K + 1:, ::2])
    assert leases(codec) == (1, 2)
    assert arena.at_rest() == WIDE * M * L
    del piece
    assert arena.events[-1] == ("given", lo)
    assert arena.at_rest() == 2 * WIDE * M * L


@pytest.mark.parametrize("erased", [[], [5], [0, 7], [2, 6, 10]],
                         ids=["encode", "r1", "r2", "r3"])
def test_a_dropped_results_buffer_is_the_next_calls(
        packed, arena, slab_of, wide, erased):
    """The same memory again, counted as a hit, holding exactly the
    next call's bytes in exactly its shape: a smaller result borrows
    the larger buffer and is (b, r, l), C-ordered, writeable."""
    slab_of(4)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    first = codec.encode_batch(wide[:, :K], out_np=True)
    address = first.ctypes.data
    del first
    assert arena.events == [("given", address)]
    hits, misses = leases(codec)
    other = wide ^ 0x3C
    if erased:
        got = codec.decode_stripes(erased, wide, out_np=True)
        want = wide[:, erased]
    else:
        got = codec.encode_batch(other[:, :K], out_np=True)
        want = np.stack([gf_matmul(codec.encode_matrix[K:], d)
                         for d in other[:, :K]])
    assert got.ctypes.data == address
    assert leases(codec) == (hits + 1, misses + (3 if erased else 0))
    assert got.shape == (WIDE, len(erased) or M, L) and got.dtype == np.uint8
    assert got.flags["C_CONTIGUOUS"] and got.flags["WRITEABLE"]
    assert got.strides == (got.shape[1] * L, L, 1)
    assert np.array_equal(got, want)
    got[:] = 0                              # the caller's own, to write too
    assert arena.at_rest() == (3 * 4 * K * L if erased else 0)


def test_the_cap_drops_what_would_pass_it(packed, monkeypatch, slab_of, wide):
    from ceph_tpu.ops.host_arena import HostArena

    # the process's own: room for the cell's result and two slabs, twice
    assert packed._arena.cap == packed.ARENA_BYTES >= 2 * (
        1024 * M * 131072 + 2 * packed.SLAB_BYTES)
    small = HostArena(100)
    a, b, c = small.take(60), small.take(40), small.take(41)
    for buf in (a, c, b):
        small.give(buf)
    assert small.at_rest() == 100           # 60 + 40: 41 did not fit
    assert small.take(41) is a and small.take(41).nbytes == 41
    # the codec path under a cap one byte short of its result
    slab_of(4)
    tight = HostArena(WIDE * M * L - 1)
    monkeypatch.setattr(packed, "_arena", tight)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    for n in range(1, 3):
        out = codec.encode_batch(wide[:, :K], out_np=True)
        assert np.array_equal(out, wide[:, K:])
        del out
        assert tight.at_rest() == 0 and leases(codec) == (0, n)
    lost = codec.decode_stripes([1, 9], wide[:, :, :128], out_np=True)
    address = lost.ctypes.data
    del lost                                # a smaller result fits
    assert tight.at_rest() == WIDE * 2 * 128
    assert codec.decode_stripes([4], wide[:, :, :128],
                                out_np=True).ctypes.data == address


def test_clear_kernel_cache_empties_the_arena(packed, slab_of, wide):
    slab_of(4)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    codec.decode_stripes([0], wide, out_np=True)
    assert packed._arena.at_rest() >= 2 * 4 * K * L + WIDE * L
    packed.clear_kernel_cache()
    assert packed._arena.at_rest() == 0
    out = codec.decode_stripes([0], wide, out_np=True)
    assert np.array_equal(out, wide[:, [0]])


@pytest.mark.parametrize("how", ["one_slab", "one_slab_rows", "device_in",
                                 "device_rows", "host_in_device_out"])
def test_a_call_that_is_one_launch_borrows_nothing(packed, arena, slab_of,
                                                   wide, how):
    import jax
    import jax.numpy as jnp

    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    if how.startswith("one_slab"):
        out = (codec.encode_batch(wide[:, :K], out_np=True)
               if how == "one_slab"
               else codec.decode_stripes([4], wide, out_np=True))
        assert isinstance(out, np.ndarray) and out.base is not None
    else:
        slab_of(4)                          # a host result would be 3 slabs
        if how == "device_in":
            out = codec.encode_batch(jnp.asarray(wide[:, :K]), out_np=True)
        elif how == "device_rows":
            out = codec.decode_stripes([4], jnp.asarray(wide))
        else:
            out = codec.decode_stripes([4], wide, out_np=False)
        assert isinstance(out, np.ndarray if how == "device_in"
                          else jax.Array)
    want = wide[:, K:] if how in ("one_slab", "device_in") else wide[:, [4]]
    assert np.array_equal(np.asarray(out), want)
    assert codec.perf.dump()["slabs"] == 1
    assert leases(codec) == (0, 0)
    del out
    gc.collect()
    assert arena.at_rest() == 0 and arena.events == []


def test_two_threads_never_hold_one_buffer(packed, arena, slab_of, wide):
    """More borrowers than cores on a short switch interval: whoever
    holds a buffer, by ``take`` or by ``lease``, finds in it what it
    wrote; and two threads of codec calls, each call's gather on a
    worker of that call's own, get their own exact bytes."""
    from ceph_tpu.ops.host_arena import HostArena

    shared = HostArena(1 << 16)
    wrong: list = []
    stop = threading.Event()

    def borrow(me: int) -> None:
        rng = np.random.default_rng(me)
        while not stop.is_set():
            size = int(rng.integers(1, 4096))
            if me % 2:
                buf = shared.take(size)
                mine = buf[:size]
            else:
                mine = shared.lease((size,))
            mine[:] = me
            time.sleep(0)                   # let the others run
            if not (mine == me).all():
                wrong.append(me)
            if me % 2:
                shared.give(buf)
            del mine
            if shared.at_rest() > shared.cap:
                wrong.append("cap")

    erased = [2, 9]
    index = decode_index_for(K, set(erased))

    def call(me: int) -> None:
        stripes = wide ^ me
        data = np.ascontiguousarray(stripes[:, :K])
        want = np.stack([gf_matmul(codec.encode_matrix[K:], d)
                         for d in data])
        lost = np.stack([gf_matmul(codec.decode_matrix_for(erased), d[index])
                         for d in stripes])
        for n in range(4):
            got = (codec.decode_stripes(erased, stripes, out_np=True) if n % 2
                   else codec.encode_batch(data, out_np=True))
            if not np.array_equal(got, lost if n % 2 else want):
                wrong.append(("call", me, n))

    slab_of(4)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    codec.encode_batch(wide[:, :K], out_np=True)    # compiled and gated
    codec.decode_stripes(erased, wide, out_np=True)
    before = set(threading.enumerate())
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=borrow, args=(n,))
                   for n in range(1, 17)]
        calls = [threading.Thread(target=call, args=(n,)) for n in (1, 2)]
        for t in threads + calls:
            t.start()
        for t in calls:
            t.join(timeout=120)
        stop.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads + calls)
    assert set(threading.enumerate()) == before     # no worker is left
    assert wrong == []
    # a result a call, and a staging buffer a slab of a decode's three
    hits, misses = leases(codec)
    assert hits + misses == 5 + 2 * (2 + 2 * 4) and misses >= 1
    dump = codec.perf.dump()
    assert dump["gathers"] == 5 * 3 >= dump["gathers_ahead"]


class LaterOut:
    """A launch's result that is not done until somebody waits for it,
    on whichever thread."""
    lock = threading.Lock()

    def __init__(self, log, slab, value, unreadable=False):
        self.log, self.slab, self.value, self.done = log, slab, value, False
        self.unreadable = unreadable        # its copy to the host raises

    def is_ready(self):
        return self.done

    def block_until_ready(self):
        with self.lock:
            if not self.done:
                self.done = True
                self.log.append(("done", self.slab))
        return self

    def copy_to_host_async(self):
        self.log.append(("copy_started", self.slab))

    def __array__(self, dtype=None, copy=None):
        self.block_until_ready()            # host bytes follow the kernel
        if self.unreadable:
            raise SlabFailed(self.slab)
        self.log.append(("landed", self.slab))
        return self.value


class SlabFailed(RuntimeError):
    pass


def later_launches(packed, monkeypatch, matrix, log, slabs, fail_at=None,
                   fill_fails_at=None, land_fails_at=None):
    """Gathers (``_gather_rows`` and ``LanePieces.fill`` alike) and
    launches of a call of ``slabs`` slabs written down in ``log`` (a
    fill with the thread it ran on), the launches as ``LaterOut``s
    (launch ``fail_at``, the gather of slab ``fill_fails_at``, or the
    copy to the host of slab ``land_fails_at``'s result, raises
    instead): (staging buffers by id, the outs).

    A launch is held until the worker has come to the next slab's fill,
    so that what the two threads write down comes in one order every
    time: launch n is handed fill n + 1 first, and the worker has waited
    for that buffer's reader before the caller lands it."""
    buffers: dict[int, np.ndarray] = {}
    outs: list[LaterOut] = []
    read_by: dict[int, list[LaterOut]] = {}
    come = collections.defaultdict(threading.Event)     # slab -> its fill
    real_rows, real_fill = packed._gather_rows, packed.LanePieces.fill

    def begin(into):
        slab = sum(ev[0] == "fill" for ev in log)
        buffers[id(into)] = into
        pending = [o.slab for o in read_by.get(id(into), ()) if not o.done]
        log.append(("fill", id(into), pending,
                    threading.current_thread().name))
        come[slab].set()
        if slab == fill_fails_at:
            raise SlabFailed(slab)

    def gather(data, rows, lo, hi, into):
        begin(into)
        return real_rows(data, rows, lo, hi, into)

    def fill(self, lo, hi, into):
        begin(into)
        return real_fill(self, lo, hi, into)

    def launch(matrix_, xd, perf=None):
        slab = len(outs)
        if slab == fail_at:
            raise SlabFailed(slab)
        if slab + 1 < slabs:
            assert come[slab + 1].wait(timeout=30), slab
        host = np.asarray(xd)
        (buf,) = [key for key, arr in buffers.items()
                  if np.array_equal(arr[:len(host)], host)]
        out = LaterOut(log, slab, np.stack(
            [gf_matmul(matrix, stripe) for stripe in host]),
            unreadable=slab == land_fails_at)
        outs.append(out)
        read_by.setdefault(buf, []).append(out)
        log.append(("launch", slab, buf))
        return packed.batch_engine(matrix, *host.shape), out

    monkeypatch.setattr(packed, "_gather_rows", gather)
    monkeypatch.setattr(packed.LanePieces, "fill", fill)
    monkeypatch.setattr(packed, "_launch_batch", launch)
    return buffers, outs


@every_depth_and_count
def test_a_staging_buffer_is_refilled_only_behind_the_launch_that_read_it(
        packed, monkeypatch, arena, sections, pairs, depth, slabs):
    """``device_put`` may alias the numpy memory (CPU) or read it until
    the transfer completes (TPU): a buffer is written again, or given
    back to the arena, only after the launch that read its upload is
    done.  Launches here never finish by themselves, so every refill
    has to wait, on the worker that fills, and is counted."""
    chunks = pairs(slabs)
    erased = [3, 8, 10]
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    matrix = codec.decode_matrix_for(erased)
    log = arena.events
    held = staging_of(slabs)            # a staging buffer a slab, depth + 1 at most
    assert held == min(depth + 1, slabs)
    before = set(threading.enumerate())
    buffers, outs = later_launches(packed, monkeypatch, matrix, log, slabs)
    got = codec.decode_stripes(erased, chunks, out_np=True)
    assert np.array_equal(got, chunks[:, erased])
    assert set(threading.enumerate()) == before     # the worker is gone
    fills = [ev for ev in log if ev[0] == "fill"]
    fill_at = [i for i, ev in enumerate(log) if ev[0] == "fill"]
    assert len(fills) == len(outs) == slabs
    assert len(buffers) == held                     # reused in turn
    assert [ev[1] for ev in fills] == [fills[i % held][1]
                                       for i in range(slabs)]
    assert all(ev[2] == [] for ev in fills), fills  # nothing unfinished
    assert {ev[3] for ev in fills} == {GATHERER}    # none on this thread
    # each slab: filled, launched, its copy to the host started, and
    # only later waited for and landed
    for n in range(slabs):
        at = {kind: i for i, (kind, *rest) in enumerate(log)
              if kind not in ("fill", "given") and rest[0] == n}
        assert fill_at[n] < at["launch"] < at["copy_started"] < at["done"] \
            < at["landed"]
    # never more than depth + 1 slabs between device_put and landing,
    # and so many whenever the call has them
    assert most_in_flight(log) == min(depth + 1, slabs)
    # slab n's buffer is refilled for slab n + held, after slab n is done
    # and while slab n+1 is still in flight; slab n+1's gather is handed
    # over before slab n is launched
    for n in range(slabs - held):
        assert log.index(("done", n)) < fill_at[n + held] \
            < log.index(("done", n + 1))
    for n in range(slabs - 1):
        assert fill_at[n + 1] < log.index(("launch", n, fills[n][1]))
    # all go back to the arena at the call's end, behind the last launch
    # and its landing (the result is the caller's: it has not come back)
    assert log[-held:] == [("given", into.ctypes.data)
                           for into in buffers.values()]
    assert log[-held - 2:-held] == [("done", slabs - 1),
                                    ("landed", slabs - 1)]
    assert arena.at_rest() == held * 2 * K * L
    dump = codec.perf.dump()
    assert dump["slabs"] == slabs and dump["pipelined"] == 1
    assert dump.get("staging_waits", 0) == slabs - held
    assert dump["gathers"] == slabs >= dump["gathers_ahead"] >= 0
    # an upload goes out beside the slabs in flight whose launch nobody
    # has waited for yet: depth of them at most
    assert 0 < dump["uploads_beside"] <= sum(
        min(i, depth) for i in range(slabs))
    assert leases(codec) == (0, 1 + held)
    # the worker's sections: a fill a slab, and inside every refill's
    # the wait for the launch that read the buffer (none is done here)
    assert sections.elsewhere == {GATHERER: (
        ["registry.gather"] * held
        + ["registry.gather", "registry.gather.wait"] * (slabs - held))}
    assert flat_of(sections) == caller_sections(slabs, GATHERED)
    # a landing waits for its launch under the wait's own name, unless
    # the worker had to have it done before it refilled the buffer
    by_caller = [n for n in range(slabs - 1) if n + held >= slabs]
    assert sections.count(KERNEL) == len(by_caller)
    nesting_holds(sections)


def most_in_flight(log) -> int:
    """The most slabs that were between their launch (their
    ``device_put`` is the step before it, on the same thread) and their
    landing at any one time; every one of them landed."""
    up = most = 0
    for kind, *_ in log:
        up += (kind == "launch") - (kind == "landed")
        most = max(most, up)
    assert up == 0
    return most


WHERE = pytest.mark.parametrize("where", ["first", "second", "last"])


def slab_at(where: str, slabs: int) -> int:
    return {"first": 0, "second": 1, "last": slabs - 1}[where]


@WHERE
@every_depth_and_count
def test_staging_goes_back_behind_the_launches_in_flight_when_a_slab_raises(
        packed, monkeypatch, arena, pairs, depth, slabs, where):
    """A launch that raises: the worker may be filling the next slab and
    the launches before still read their uploads, so the call waits for
    both, and only then gives its staging buffers back; the error
    reaches the caller."""
    chunks, fail_at = pairs(slabs), slab_at(where, slabs)
    erased = [3, 8, 10]
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    matrix = codec.decode_matrix_for(erased)
    log = arena.events
    before = set(threading.enumerate())
    buffers, outs = later_launches(packed, monkeypatch, matrix, log, slabs,
                                   fail_at=fail_at)
    with pytest.raises(SlabFailed):
        codec.decode_stripes(erased, chunks, out_np=True)
    assert set(threading.enumerate()) == before
    assert len(outs) == fail_at and all(out.done for out in outs)
    assert [ev for ev in log if ev[0] == "done"] == [
        ("done", n) for n in range(fail_at)]
    came_back(arena, codec, buffers, slabs,
              result=len(chunks) * len(erased) * L)


def came_back(arena, codec, buffers, slabs: int, result: int,
              counted: bool = False) -> None:
    """After a gathering call of ``slabs`` slabs that raised: its
    staging buffers first, behind every fill's and every launch's end,
    then the result of ``result`` bytes that nobody got, once the error
    lets go of the call's frame; and nothing was served (``counted``: it
    raised at the close, behind its last launch, where a call is
    counted)."""
    log, held = arena.events, staging_of(slabs)
    first = next(i for i, ev in enumerate(log) if ev[0] == "given")
    assert all(ev[0] == "given" for ev in log[first:])
    assert {into.ctypes.data for into in buffers.values()} <= {
        ev[1] for ev in log[first:first + held]}
    gc.collect()
    assert len(log) - first == held + 1
    (staging,) = {into.nbytes for into in buffers.values()}
    assert arena.at_rest() == held * staging + result
    dump = codec.perf.dump()
    assert ("launches" in dump) == ("gathers" in dump) == counted
    assert leases(codec) == (0, held + 1)


@WHERE
@every_depth_and_count
def test_a_gather_that_raises_on_the_worker_comes_out_of_the_call(
        packed, monkeypatch, arena, sections, pairs, depth, slabs, where):
    """The worker's exception is the call's: it comes out where the
    caller's thread asks for that slab, behind the launches made before
    it, and every staging buffer goes back."""
    chunks, fill_fails_at = pairs(slabs), slab_at(where, slabs)
    erased = [3, 8, 10]
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    matrix = codec.decode_matrix_for(erased)
    before = set(threading.enumerate())
    buffers, outs = later_launches(packed, monkeypatch, matrix, arena.events,
                                   slabs, fill_fails_at=fill_fails_at)
    with pytest.raises(SlabFailed) as caught:
        codec.decode_stripes(erased, chunks, out_np=True)
    assert caught.value.args == (fill_fails_at,)
    del caught                              # and the frames it holds
    assert set(threading.enumerate()) == before
    assert len(outs) == fill_fails_at and all(out.done for out in outs)
    came_back(arena, codec, buffers, slabs,
              result=len(chunks) * len(erased) * L)
    # the fill that raised left its section, and the caller's its marshal
    assert sections.open_now == []
    assert [s for s in sections.elsewhere[GATHERER]
            if s != "registry.gather.wait"] == \
        ["registry.gather"] * (fill_fails_at + 1)


@WHERE
@every_depth_and_count
def test_a_landing_that_raises_leaves_no_section_open_and_no_staging_out(
        packed, monkeypatch, arena, sections, pairs, depth, slabs, where):
    """A result whose copy to the host raises: inside the loop (the
    slabs that land behind a later launch), at the close, or, the last
    slab's, under ``registry.copy_out``.  The wait for a launch that was
    not done came first, under its own name; the sections around the
    copy are left on the way out, the launches still in flight are
    waited for, and every staging buffer goes back."""
    chunks, land_fails_at = pairs(slabs), slab_at(where, slabs)
    erased = [3, 8, 10]
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    matrix = codec.decode_matrix_for(erased)
    before = set(threading.enumerate())
    buffers, outs = later_launches(packed, monkeypatch, matrix, arena.events,
                                   slabs, land_fails_at=land_fails_at)
    with pytest.raises(SlabFailed) as caught:
        codec.decode_stripes(erased, chunks, out_np=True)
    assert caught.value.args == (land_fails_at,)
    del caught                              # and the frames it holds
    assert set(threading.enumerate()) == before
    # slab n lands behind launch n + depth, or at the close behind all
    in_loop = land_fails_at + depth < slabs
    made = land_fails_at + depth + 1 if in_loop else slabs
    assert len(outs) == made and all(out.done for out in outs)
    whole = caller_sections(slabs, GATHERED)
    upto = len(whole) - 1 if land_fails_at == slabs - 1 else [
        i for i, name in enumerate(whole)
        if name == "registry.drain.link"][land_fails_at]
    assert flat_of(sections) == whole[:upto + 1]
    # no launch here is done before somebody waits: every landing under
    # ``registry.drain`` did, but for those whose buffer the worker had
    # refilled by then (a launch is held until the next slab's fill has
    # begun: fills up to slab ``made``'s, where there is one)
    filled = min(made, slabs - 1)
    assert sections.count(KERNEL) == sum(
        n + staging_of(slabs) > filled
        for n in range(min(land_fails_at + 1, slabs - 1)))
    nesting_holds(sections)
    came_back(arena, codec, buffers, slabs,
              result=len(chunks) * len(erased) * L, counted=not in_loop)


@every_depth
@pytest.mark.parametrize("per_slab,slabs",
                         [(14, 1), (8, 2), (6, 3), (4, 4), (2, 7)],
                         ids=["1slab", "2slabs", "3slabs", "4slabs", "7slabs"])
def test_each_threads_sections_in_order_at_1_2_3_and_7_slabs(
        packed, monkeypatch, sections, depth, per_slab, slabs):
    """14 stripes through one, two, three, four and seven slabs, a
    uniform encode and a decode from ``rows``: the caller's sections in
    order with the waits of every landing nested in its
    ``registry.drain`` (the last slab's is ``registry.copy_out`` as it
    was), the worker's a fill a slab with the wait for a launch inside
    the refills that had to."""
    chunks = np.random.default_rng(52).integers(
        0, 256, (14, N, L), dtype=np.uint8)
    monkeypatch.setattr(packed, "SLAB_BYTES", per_slab * K * L)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    codec.encode_batch(chunks[:, :K], out_np=True)
    assert flat_of(sections) == caller_sections(slabs, UPLOAD)
    assert sections.elsewhere == {}                 # no gather, no worker
    assert "gathers" not in codec.perf.dump()
    nesting_holds(sections)
    sections.clear()
    codec.decode_stripes([2, 9], chunks, out_np=True)
    assert sections[0] == "registry.matrix"         # the table miss
    assert flat_of(sections) == caller_sections(slabs, GATHERED)
    nesting_holds(sections)
    dump = codec.perf.dump()
    if slabs == 1:                  # gathered here, under registry.marshal
        assert sections.elsewhere == {}
        assert "gathers" not in dump
        return
    mine = sections.elsewhere.pop(GATHERER)
    assert sections.elsewhere == {}
    assert [s for s in mine if s != "registry.gather.wait"] == \
        ["registry.gather"] * slabs
    # a wait is opened inside the fill of a refill, where it is counted
    assert mine.count("registry.gather.wait") == dump.get("staging_waits", 0)
    assert not any(s == "registry.gather.wait"
                   for s in mine[:staging_of(slabs)])
    assert dump["gathers"] == slabs >= dump["gathers_ahead"]
    # two calls of several slabs so far, the encode and this decode
    assert 0 <= dump.get("uploads_beside", 0) <= 2 * sum(
        min(i, depth) for i in range(slabs))
    codec.decode_stripes([2, 9], chunks, out_np=True)
    again = codec.perf.dump()
    assert again["gathers"] == 2 * slabs and again["launches"] == 3


class Fixed:
    """A launch's result that says it is done, or not, whatever it is."""

    def __init__(self, out, ready: bool, waited: list):
        self.out, self.ready, self.waited = out, ready, waited

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.waited.append(threading.current_thread().name)
        self.out.block_until_ready()
        return self

    def copy_to_host_async(self):
        self.out.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.out)


@every_depth
@pytest.mark.parametrize("ready", [True, False], ids=["done", "not_done"])
def test_a_landing_waits_for_its_launch_only_where_it_is_not_done(
        packed, monkeypatch, sections, slab_of, wide, ready, depth):
    """Five slabs, four landed under ``registry.drain``: a launch that
    is done is not waited for, and the landing blocks once, in the copy
    to the host, as it did before its waits had names; one that is not
    is waited for first, under ``registry.drain.kernel``."""
    slab_of(2)
    waited: list[str] = []
    real = packed._launch_batch

    def launch(matrix, xd, perf=None):
        engine, out = real(matrix, xd, perf)
        return engine, Fixed(out, ready, waited)

    monkeypatch.setattr(packed, "_launch_batch", launch)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    parity = codec.encode_batch(wide[:, :K], out_np=True)
    assert np.array_equal(parity, wide[:, K:])
    assert flat_of(sections) == caller_sections(5, UPLOAD)
    assert sections.count(KERNEL) == (0 if ready else 4)
    # the last slab's wait is the call's registry.device_wait either way
    assert len(waited) == 1 + sections.count(KERNEL)
    nesting_holds(sections)
    # an upload is counted beside every slab in flight whose launch is
    # not done when it goes out: the depth's worth once the queue is full
    assert codec.perf.dump().get("uploads_beside", 0) == (
        0 if ready else sum(min(i, depth) for i in range(5)))


def test_a_call_of_many_slabs_drains_and_closes_with_one_copy_out(
        packed, sections, slab_of, wide):
    slab_of(2)                                      # five slabs
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    codec.encode_batch(wide[:, :K], out_np=True)
    flat = flat_of(sections)
    assert flat == caller_sections(5, UPLOAD)
    assert flat.count("registry.drain") == 4 == flat.count(
        "registry.drain.link") == flat.count("registry.drain.land")
    assert flat.count("registry.copy_out") == 1
    assert sections.elsewhere == {}                 # no gather, no worker
    sections.clear()
    codec.decode_stripes([2, 9], wide, out_np=True)
    # this thread's sections are what they were when it gathered itself
    assert flat_of(sections) == caller_sections(5, GATHERED)
    assert sections[0] == "registry.matrix"         # the table miss
    # and the gathers are the worker's, a section each
    assert [s for s in sections.elsewhere[GATHERER]
            if s != "registry.gather.wait"] == ["registry.gather"] * 5
    nesting_holds(sections)
    dump = codec.perf.dump()
    assert dump["launches"] == dump["pipelined"] == dump["engine_gN"] == 2
    assert dump["slabs"] == 10 and dump["stripes"] == 2 * WIDE
    assert dump["parity_gates"] == 2                # one a matrix: one shape


def test_a_parity_miss_on_the_first_slab_raises_out_of_the_call(
        packed, slab_of, wide):
    slab_of(4)
    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    real = packed._compiled_batch_gN
    launched = []
    packed._compiled_batch_gN = lambda *a: (
        lambda w, xd, fn=real(*a): launched.append(xd.shape) or fn(w, xd) ^ 1)
    try:
        with pytest.raises(packed.KernelParityError):
            codec.encode_batch(wide[:, :K], out_np=True)
        with pytest.raises(packed.KernelParityError):
            codec.decode_stripes([1], wide, out_np=True)
    finally:
        packed._compiled_batch_gN = real
    assert launched == [(4, K, L)] * 2              # nothing served after it
    assert "launches" not in codec.perf.dump()


def test_a_device_array_in_and_out_skips_the_copies(sections, stripes):
    import jax
    import jax.numpy as jnp

    codec = registry().factory("tpu", {"k": str(K), "m": str(M)})
    out = codec.encode_batch(jnp.asarray(stripes[:, :K]))
    assert isinstance(out, jax.Array)
    assert set(sections) <= {"registry.launch", "registry.matrix"}
    assert np.array_equal(np.asarray(out), stripes[:, K:])
    # a chunk map on the device: the survivors are selected there
    lost = codec.decode_stripes([0, 9], jnp.asarray(stripes))
    assert isinstance(lost, jax.Array)
    assert set(sections) <= {"registry.launch", "registry.matrix"}
    assert np.array_equal(np.asarray(lost), stripes[:, [0, 9]])
    assert codec.perf.dump()["slabs"] == 2 == codec.perf.dump()["launches"]


@pytest.mark.parametrize("engine", ENGINES)
def test_every_engines_program_carries_the_registry_name_and_scope(engine):
    import jax.numpy as jnp
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
