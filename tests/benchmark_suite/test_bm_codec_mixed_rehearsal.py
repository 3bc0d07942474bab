"""CPU rehearsal of the registry codec cell over objects of unequal
size (drivers/codec_objects_loop.py) at a tiny mix through ``run_cell``:
whole cycles, facts and counts, no timing under a metric's name;
``correct`` true, and false under each of the four faults of
``control_codec_mixed.py``; ``reference/codec_objects.py`` against the
host ``isa`` plugin object by object; the ``HarnessError`` at once on a
program without the entry points; the cell's entries and its 13 metric
files against the manifest.
"""

from __future__ import annotations

import copy
import json

import numpy as np
import pytest

import bm_toy
from benchmark import control_codec_mixed, harness
from benchmark import run as bench_run
from benchmark.drivers import codec_objects_loop
from benchmark.reference import codec as ref
from benchmark.reference import codec_objects as ref_objects

CELL = "cauchy_k10m4_codec_mixed_4k_1m"
CONFIG = "cauchy_k10m4_registry_codec"
SECTION_NAMES = ("prepare", "marshal", "upload", "launch", "matrix", "drain",
                 "device_wait", "copy_out", "unsectioned")
METRICS = [f"registry_ms_per_op.{name}" for name in SECTION_NAMES] + [
    "registry_pad_waste_share", "registry_table_miss_share",
    "device_idle_share.registry", "registry_lanes_hbm_share"]
M = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
SIZES, PER_SIZE, K, N = [100, 4096, 5000, 16384], 4, 10, 14
LANES = sum(ref.chunk_bytes(K, size) for size in SIZES) * PER_SIZE
SLAB = 2048             # lanes a slab of the rehearsal


@pytest.fixture(scope="module", autouse=True)
def small_slabs():
    """A slab of 2048 lanes (a call of the toy mix is six) and the
    engine choice of a TPU backend, through the Pallas interpreter: one
    program whatever matrix (the CPU's ``sched`` compiles one each)."""
    import ceph_tpu.ops.gf2kernels as g

    mp = pytest.MonkeyPatch()
    mp.setattr(g, "_want_pallas", lambda: True)
    mp.setattr(g, "SLAB_BYTES", K * SLAB)
    g.clear_kernel_cache()
    yield
    mp.undo()
    g.clear_kernel_cache()


def toy_cell() -> harness.Cell:
    """The cell cut to 16 objects of four sizes a call (k=10, m=4 cauchy
    as they are): every mechanism, toy numbers."""
    cell = harness.Cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config.update(object_bytes=SIZES, objects_per_size=PER_SIZE)
    cell.traffic.update(host_objects_per_size=2,
                        host_native_objects_per_size=2)
    return cell


def rehearse(seed: int = 7, seconds: float = 0.5, traced: bool = False,
             fault: str = "none") -> dict:
    harness.build_native()
    with control_codec_mixed.FAULTS[fault]():
        return bench_run.run_cell(toy_cell(), seed, seconds, traced,
                                  bm_toy.CPU)


@pytest.fixture(scope="module")
def sound():
    return rehearse()


def test_sound_rehearsal_is_correct_and_prints_no_metric(sound):
    assert sound["rehearsal"] and "metrics" not in sound
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0


def test_the_window_is_whole_cycles_of_an_encode_and_a_decode_of_two(sound):
    facts = sound["facts"]
    assert toy_cell().traffic["cycle"] == [["encode", 0], ["decode", 2]]
    ops = facts["run.ops"]
    assert ops == sound["attempted"] and ops % 2 == 0
    assert facts["window.encodes"] == facts["window.decodes"] == ops // 2
    assert facts["run.objects_a_call"] == len(SIZES) * PER_SIZE
    assert facts["run.lanes_a_call"] == LANES
    w = {k.removeprefix("window.ec_registry."): v for k, v in facts.items()
         if k.startswith("window.ec_registry.")}
    slabs = -(-LANES // SLAB)
    assert w["launches"] == ops and w["slabs"] == slabs * ops
    assert w["objects"] == len(SIZES) * PER_SIZE * ops
    assert w["lanes"] == LANES * ops
    assert w["lanes_launched"] == slabs * SLAB * ops
    assert w["lanes_padded"] == (slabs * SLAB - LANES) * ops
    assert w["bytes_in"] == K * LANES * ops
    assert w["bytes_out"] == (4 + 2) * LANES * ops // 2
    assert w["engine_v1"] == w["launches"] and "stripes" not in w
    assert w["table_hits"] + w["table_misses"] == facts["window.decodes"]


def test_the_checks_and_the_host_facts_are_there(sound):
    facts = sound["facts"]
    for key in ("parity_differs", "isa_differs", "recovered_differs",
                "lanes_differing", "tail_nonzero"):
        assert facts[f"check.{key}"] == 0
    # 2 encodes and 2 decodes, 2 objects of each of the four sizes; one
    # whole output; the tails of the sampled objects
    assert facts["check.objects"] == 4 * 2 * len(SIZES)
    assert facts["check.lanes"] == len(SIZES) * PER_SIZE
    assert facts["check.tails"] == 2 * len(SIZES)
    for key in ("host_isa_mibps", "host_isa_encode_mibps",
                "host_isa_decode_mibps", "host_native_mibps"):
        assert facts[f"run.{key}"] > 0
    assert facts["config.profile.k"] == K and facts["config.profile.m"] == 4
    assert facts["config.objects_per_size"] == PER_SIZE


def test_the_traced_slice_counts_the_lanes_it_handed_in(capsys):
    res = rehearse(traced=True)
    assert res["correct"] is True and "metrics" not in res
    facts = res["facts"]
    cycles = toy_cell().traffic["trace_cycles"]
    assert facts["slice.ops"] == 2 * cycles
    assert facts["slice.codec.lanes_r4"] == cycles * LANES
    assert facts["slice.codec.lanes_r2"] == cycles * LANES
    out = capsys.readouterr().out
    for name in SECTION_NAMES[:-1]:
        if name != "device_wait":           # nothing to wait for on a CPU
            assert f"registry.{name}" in out, name
    assert f"{2 * cycles * -(-LANES // SLAB)} x registry.launch" in out
    assert out.count("  encode: ") == cycles
    assert out.count("  decode of [") == cycles
    # the readers find nothing of a device in a CPU trace's facts, and the
    # counters' ratios read what the window counted
    from benchmark.readers import codec_lanes_roofline, ratio
    spec = harness.layer_metric("registry_lanes_hbm_share")["spec"]
    assert codec_lanes_roofline.read(spec, facts) is None
    waste = ratio.read(
        harness.layer_metric("registry_pad_waste_share")["spec"], facts)
    slabs = -(-LANES // SLAB)
    assert waste == pytest.approx(100 * (slabs * SLAB - LANES)
                                  / (slabs * SLAB))
    miss = ratio.read(
        harness.layer_metric("registry_table_miss_share")["spec"], facts)
    assert 0 < miss <= 100


def test_the_lanes_reader_on_made_up_device_facts():
    """(k + r) bytes a lane over the peak, over the programs' time."""
    from benchmark.readers import codec_lanes_roofline
    spec = harness.layer_metric("registry_lanes_hbm_share")["spec"]
    facts = {"trace.programs": {"jit_registry_gf_v1": 0.5,
                                "jit_something_else": 9.0},
             "slice.codec.lanes_r4": 10 ** 9, "slice.codec.lanes_r2": 10 ** 9,
             "device.kind": "TPU v5 lite", "config.profile.k": K}
    need = (K + 4 + K + 2) * 10 ** 9
    assert codec_lanes_roofline.read(spec, facts) == pytest.approx(
        100 * need / 819e9 / 0.5)
    assert codec_lanes_roofline.read(
        spec, dict(facts, **{"trace.programs": {"jit_other": 1.0}})) is None
    with pytest.raises(harness.HarnessError):
        codec_lanes_roofline.read(spec, dict(facts, **{"device.kind": "x"}))


@pytest.mark.parametrize("seed", [1, 2147641999])
def test_the_seed_draws_payloads_order_erasures_and_sample(seed, capsys):
    res = rehearse(seed=seed)
    assert res["correct"] is True and res["failed"] == 0
    assert "sample holds 2 encodes and 2 decodes" in capsys.readouterr().out
    cfg = toy_cell().config
    a = codec_objects_loop.payload(seed, 0, cfg)
    assert [obj.size for obj in a] == sorted(SIZES * PER_SIZE)
    again = codec_objects_loop.payload(seed, 0, cfg)
    assert all((x == y).all() for x, y in zip(a, again))
    other = codec_objects_loop.payload(seed, 1, cfg)
    assert any((x != y).any() for x, y in zip(a, other))
    order = codec_objects_loop.call_order(seed, len(a))
    assert sorted(order) == list(range(len(a)))
    assert (order == codec_objects_loop.call_order(seed, len(a))).all()
    assert (order != np.arange(len(a))).any()       # shuffled


@pytest.mark.parametrize("fault,wrong,sound_checks", [
    ("tail_pad", ("parity_differs", "isa_differs", "lanes_differing",
                  "tail_nonzero"), ()),
    ("object_offset", ("lanes_differing",), ()),
    ("coefficient", ("parity_differs", "isa_differs", "lanes_differing"),
     ("tail_nonzero",)),
    ("survivor_order", ("recovered_differs",),
     ("parity_differs", "isa_differs", "lanes_differing")),
])
def test_the_control_comes_out_not_correct(fault, wrong, sound_checks):
    res = rehearse(fault=fault)
    assert res["correct"] is False and res["failed"] == 0
    for key in wrong:
        assert res["facts"][f"check.{key}"] > 0, key
    for key in sound_checks:
        assert res["facts"][f"check.{key}"] == 0, key


def test_a_program_without_the_entry_points_is_refused_at_once(monkeypatch):
    from ceph_tpu.ec.plugins.tpu import ErasureCodeTpu
    monkeypatch.delattr(ErasureCodeTpu, "decode_objects")
    with pytest.raises(harness.HarnessError, match="decode_objects"):
        bench_run.run_cell(toy_cell(), 7, 0.5, False, bm_toy.CPU)


# -- the reference file against the host plugin --------------------------------

@pytest.mark.parametrize("profile", [
    {"k": 10, "m": 4, "technique": "cauchy"},
    {"k": 8, "m": 3, "technique": "reed_sol_van"}],
    ids=["cauchy_k10m4", "rs_k8m3"])
def test_the_reference_is_the_host_plugins_bytes_object_by_object(
        profile, monkeypatch):
    from ceph_tpu.ec import registry
    k, m = profile["k"], profile["m"]
    isa = registry().factory("isa", {
        "k": str(k), "m": str(m), "technique": profile["technique"]})
    rng = np.random.default_rng(50)
    sizes = [1, 31, 32, 4095, 4096, 4097, 40 << 10, 100000, 64, 5]
    objects = [rng.integers(0, 256, size, dtype=np.uint8) for size in sizes]
    # blocks of a few objects each: the cut between blocks moves nothing
    monkeypatch.setattr(ref_objects, "BLOCK_LANES", 6000)
    got = ref_objects.parity_of_objects(profile, objects)
    assert len(got) == len(objects)
    for obj, parity in zip(objects, got):
        want = isa.encode(set(range(k + m)), obj.tobytes())
        assert parity.shape == (m, ref.chunk_bytes(k, obj.size))
        for r in range(m):
            assert np.array_equal(parity[r], want[k + r]), (obj.size, r)
        for j in range(k):
            assert ref_objects.tail_nonzero(k, obj.size, j, want[j]) == 0
        loud = want[k - 1].copy()
        if obj.size < k * len(loud):
            loud[-1] = 7
            assert ref_objects.tail_nonzero(k, obj.size, k - 1, loud) == 1
    assert ref_objects.parity_of_objects(profile, []) == []


# -- the manifest, counted by lookup --------------------------------------------

def test_the_cell_is_on_exactly_its_three_end_to_end_metrics():
    cell = harness.Cell(CELL)
    assert set(cell.end_to_end) == {"client_mibps", "op_p95_ms", "setup_s"}
    assert cell.chips == 1 and cell.entry["config"] == CONFIG
    assert cell.entry["traffic"] == "codec_mixed_encode_decode"
    assert cell.traffic["driver"] == "codec_objects_loop"
    assert len(cell.entry["why"]) <= 200
    for e in M["end_to_end"]:
        if e["name"] in ("client_mibps", "op_p95_ms"):
            assert e["workloads"][-1] == CELL
            assert e["workloads"].count(CELL) == 1
    assert len(M["workloads"]) == 11 and len(M["configs"]) == 10


def test_the_13_metrics_stand_at_the_end_of_what_the_benchmark_had():
    names = [p["name"] for p in M["per_layer"]]
    assert len(names) == 121 <= 128 and len(set(names)) == len(names)
    assert names[-13:] == METRICS
    assert harness.Cell(CELL).per_layer == METRICS
    for name in METRICS:
        entry = M["per_layer"][names.index(name)]
        spec = harness.layer_metric(name)
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["workloads"] == [CELL] == spec["workloads"]
        assert entry["moves"] == "client_mibps" == spec["moves"]
        for key in ("unit", "better", "source", "layer"):
            assert entry[key] == spec[key], (name, key)
        assert len(name) <= 64 and len(entry["layer"]) <= 200
        assert "\n" not in entry["layer"] and "\t" not in entry["layer"]
        assert (harness.BENCH / "readers" / f"{spec['reader']}.py").exists()
    accepted = {p["layer"] for p in M["per_layer"][:-13]}
    assert {p["layer"] for p in M["per_layer"][-13:]} - accepted == {
        "registry codec path (ec/registry.py, ec/plugins/tpu.py, "
        "ops/jax_backend.py)"}
    by_reader = {name: harness.layer_metric(name)["reader"]
                 for name in METRICS}
    assert [by_reader[name] for name in METRICS[:9]] == ["layer_time"] * 9
    assert by_reader["registry_lanes_hbm_share"] == "codec_lanes_roofline"
    assert len(json.dumps(M)) < 64 * 1024


def test_the_nine_section_metrics_add_up_to_the_slice():
    """One list of layers, the prefixes apart, the ninth inverted: what
    each reads is disjoint and together they are the slice."""
    specs = [harness.layer_metric(name)["spec"] for name in METRICS[:9]]
    assert all(spec["layers"] == ["registry."] for spec in specs)
    assert all(spec["per_fact"] == "slice.ops" for spec in specs)
    assert [spec["prefix"] for spec in specs[:8]] == [
        f"registry.{name}" for name in SECTION_NAMES[:8]]
    assert specs[8].get("invert") is True
    assert not any(spec.get("invert") for spec in specs[:8])
    prefixes = [spec["prefix"] for spec in specs[:8]]
    assert not any(a != b and a.startswith(b)
                   for a in prefixes for b in prefixes)


def test_the_configuration_states_the_deployment_as_it_is_run():
    entry = {c["name"]: c for c in M["configs"]}[CONFIG]
    assert M["configs"][-1] is entry
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["source"]) <= 200 and "\n" not in entry["source"]
    assert len(entry["why"]) <= 200 and entry["reduced"] == []
    body = json.loads((harness.ROOT / entry["file"]).read_text())
    assert body["kind"] == "registry_codec" and body["architecture"] is None
    assert body["profile"] == {"plugin": "tpu", "k": 10, "m": 4,
                               "technique": "cauchy"}
    assert body["object_bytes"] == [4096 << i for i in range(9)]
    assert body["objects_per_size"] == 512
    assert body["reduced"] == {} and len(body["guarantees"]) == 4
    sizes = codec_objects_loop.sizes_of(body)
    assert len(sizes) == 4608 and sum(sizes) == 1071644672
    assert [ref.chunk_bytes(10, size) for size in body["object_bytes"]] == [
        416, 832, 1664, 3296, 6560, 13120, 26240, 52448, 104864]
    assert sum(ref.chunk_bytes(10, size) for size in sizes) == 107233280
    assert [w["name"] for w in M["workloads"] if w["config"] == CONFIG] \
        == [CELL]
