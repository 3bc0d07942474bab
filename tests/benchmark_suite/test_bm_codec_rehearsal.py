"""CPU rehearsal of the registry codec cell (drivers/codec_loop.py) at
toy size through ``run_cell``: whole cycles, facts and counts, no timing
under a metric's name; ``correct`` true, and false under either fault of
``control_codec.py``; the ``HarnessError`` at once on a program without
the registry's sections; the cell's entries against the manifest.
"""

from __future__ import annotations

import copy
import json

import pytest

import bm_toy
from benchmark import control_codec, harness
from benchmark import run as bench_run
from benchmark.drivers import codec_loop

CELL = "rs_k8m3_codec_1m_b1024"
CONFIG = "rs_k8m3_registry_codec"
METRIC = "registry_gf_hbm_share"
M = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
BATCH, UNIT = 16, 256


def toy_cell() -> harness.Cell:
    """The cell cut to 16 objects of 2 KiB a call (k=8, m=3 as they
    are): every mechanism, toy numbers."""
    cell = harness.Cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["profile"]["stripe_unit"] = UNIT
    cell.config.update(object_bytes=8 * UNIT, batch=BATCH)
    cell.traffic.update(check_stripes=4, host_objects_1m=8,
                        host_objects_4k=1, host_native_stripes=8)
    return cell


def rehearse(seed: int = 7, seconds: float = 0.5, traced: bool = False,
             fault: str = "none") -> dict:
    harness.build_native()
    with control_codec.FAULTS[fault]():
        return bench_run.run_cell(toy_cell(), seed, seconds, traced,
                                  bm_toy.CPU)


@pytest.fixture(scope="module")
def sound():
    return rehearse()


def test_sound_rehearsal_is_correct_and_prints_no_metric(sound):
    assert sound["rehearsal"] and "metrics" not in sound
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0


def test_the_window_is_whole_cycles_of_the_six_ops(sound):
    facts = sound["facts"]
    cycle = toy_cell().traffic["cycle"]
    assert [kind for kind, _ in cycle] == ["encode", "decode"] * 3
    assert [e for kind, e in cycle if kind == "decode"] == [1, 2, 3]
    assert facts["run.ops"] == sound["attempted"]
    assert facts["run.ops"] % len(cycle) == 0
    assert facts["window.encodes"] == facts["window.decodes"] \
        == facts["run.ops"] // 2
    w = {k.removeprefix("window.ec_registry."): v for k, v in facts.items()
         if k.startswith("window.ec_registry.")}
    assert w["launches"] == facts["run.ops"]
    assert w["stripes"] == BATCH * facts["run.ops"]
    assert w["bytes_in"] == BATCH * 8 * UNIT * facts["run.ops"]
    # three encodes of m rows and decodes of 1, 2 and 3 a cycle
    assert w["bytes_out"] == BATCH * UNIT * (3 * 3 + 1 + 2 + 3) \
        * facts["run.ops"] // len(cycle)
    assert sum(v for k, v in w.items() if k.startswith("engine_")) \
        == w["launches"]
    assert w.get("table_hits", 0) + w["table_misses"] \
        == facts["window.decodes"]


def test_the_checks_and_the_host_facts_are_there(sound):
    facts = sound["facts"]
    for key in ("parity_differs", "isa_differs", "recovered_differs",
                "lanes_differing"):
        assert facts[f"check.{key}"] == 0
    # 2 encodes and a decode of each count, 4 stripes each; one whole output
    assert facts["check.stripes"] == 5 * 4 and facts["check.lanes"] == BATCH
    for key in ("host_isa_mibps", "host_isa_encode_mibps",
                "host_isa_decode_mibps", "host_isa_4k_chunks_encode_mibps",
                "host_native_mibps"):
        assert facts[f"run.{key}"] > 0
    assert facts["config.profile.k"] == 8 and facts["config.profile.m"] == 3
    assert facts["config.profile.stripe_unit"] == UNIT
    assert facts["config.batch"] == BATCH


def test_the_traced_slice_counts_the_stripes_it_handed_in(capsys):
    res = rehearse(traced=True)
    assert res["correct"] is True and "metrics" not in res
    facts = res["facts"]
    assert facts["slice.ops"] == 6
    assert facts["slice.codec.stripes_r3"] == 4 * BATCH     # 3 encodes, |E|=3
    assert facts["slice.codec.stripes_r1"] == BATCH
    assert facts["slice.codec.stripes_r2"] == BATCH
    out = capsys.readouterr().out
    for name in ("registry.upload", "registry.launch", "registry.matrix",
                 "registry.device_wait", "registry.copy_out",
                 "registry.marshal"):
        assert name in out, name
    assert "6 x registry.launch" in out
    # and op by op: three encodes, a decode of 1, 2 and 3 erased chunks
    assert out.count("  encode: ") == 3 and out.count("  decode of [") == 3
    # the accepted reader finds nothing of a device in a CPU trace's facts
    from benchmark.readers import codec_roofline
    assert codec_roofline.read(harness.layer_metric(METRIC)["spec"],
                               facts) is None


@pytest.mark.parametrize("seed", [1, 2147641999])
def test_the_seed_draws_the_payloads_the_erasures_and_the_sample(seed,
                                                                 capsys):
    res = rehearse(seed=seed)
    assert res["correct"] is True and res["failed"] == 0
    assert "sample holds 2 encodes" in capsys.readouterr().out
    a = codec_loop.payload(seed, 0, toy_cell().config)
    assert a.shape == (BATCH, 8, UNIT)
    assert (a == codec_loop.payload(seed, 0, toy_cell().config)).all()
    assert (a != codec_loop.payload(seed, 1, toy_cell().config)).any()
    draw, again = (codec_loop.draw_erasures(seed, 11) for _ in range(2))
    drawn = [draw(count) for count in (1, 2, 3, 3)]
    assert drawn == [again(count) for count in (1, 2, 3, 3)]
    assert all(e == sorted(set(e)) and max(e) < 11 for e in drawn)


@pytest.mark.parametrize("fault,wrong", [
    ("coefficient", ("parity_differs", "isa_differs", "lanes_differing")),
    ("survivor_order", ("recovered_differs",)),
])
def test_the_control_comes_out_not_correct(fault, wrong):
    res = rehearse(fault=fault)
    assert res["correct"] is False and res["failed"] == 0
    for key in wrong:
        assert res["facts"][f"check.{key}"] > 0, key
    if fault == "survivor_order":       # every encode is sound
        assert res["facts"]["check.parity_differs"] == 0
        assert res["facts"]["check.lanes_differing"] == 0


def test_a_program_without_the_registry_layer_is_refused_at_once(
        monkeypatch):
    from ceph_tpu.common import tracing
    monkeypatch.setattr(tracing, "SECTION_LAYERS", tuple(
        layer for layer in tracing.SECTION_LAYERS if layer != "registry"))
    with pytest.raises(harness.HarnessError, match="registry"):
        bench_run.run_cell(toy_cell(), 7, 0.5, False, bm_toy.CPU)


def test_an_object_size_that_is_not_the_profiles_chunk_is_refused():
    cell = toy_cell()
    cell.config["object_bytes"] = 8 * UNIT + 1
    with pytest.raises(harness.HarnessError, match="stripe_unit"):
        bench_run.run_cell(cell, 7, 0.5, False, bm_toy.CPU)


# -- the manifest, counted by lookup --------------------------------------------

def test_the_cell_is_on_exactly_its_three_end_to_end_metrics():
    cell = harness.Cell(CELL)
    assert set(cell.end_to_end) == {"client_mibps", "op_p95_ms", "setup_s"}
    assert cell.chips == 1 and cell.entry["config"] == CONFIG
    assert cell.entry["traffic"] == "codec_encode_decode"
    assert cell.traffic["driver"] == "codec_loop"
    for e in M["end_to_end"]:
        if e["name"] in ("client_mibps", "op_p95_ms"):
            assert e["workloads"].count(CELL) == 1


def test_the_cells_metric_stands_behind_what_the_benchmark_had():
    names = [p["name"] for p in M["per_layer"]]
    assert len(names) <= 128            # the most a manifest may hold
    # appended after what there was (PR 45 filled the list; PR 49 made room
    # in front of it): looked up, so what a later PR appends trips nothing
    assert names.index(METRIC) > names.index("scrub_crc_hbm_share")
    assert METRIC in harness.Cell(CELL).per_layer
    entry = M["per_layer"][names.index(METRIC)]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    spec = harness.layer_metric(METRIC)
    assert CELL in entry["workloads"] == spec["workloads"]
    assert entry["moves"] == "client_mibps" and entry["unit"] == "%"
    assert entry["source"] == "device_trace" and spec["reader"] \
        == "codec_roofline"
    assert entry["layer"] == "registry codec kernel (ops/gf2kernels.py)"
    assert len(json.dumps(M)) < 64 * 1024


def test_the_configuration_states_the_deployment_as_it_is_run():
    entry = {c["name"]: c for c in M["configs"]}[CONFIG]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["source"]) <= 200 and "\n" not in entry["source"]
    assert entry["reduced"] == []
    body = json.loads((harness.ROOT / entry["file"]).read_text())
    assert body["kind"] == "registry_codec" and body["architecture"] is None
    assert body["profile"] == {"plugin": "tpu", "k": 8, "m": 3,
                               "technique": "reed_sol_van",
                               "stripe_unit": 131072}
    assert body["object_bytes"] == 1 << 20 and body["batch"] == 1024
    assert body["reduced"] == {} and len(body["guarantees"]) == 3
    from benchmark.reference import codec
    assert codec.chunk_bytes(8, body["object_bytes"]) == 131072
    assert [w["name"] for w in M["workloads"] if w["config"] == CONFIG] \
        == [CELL]
