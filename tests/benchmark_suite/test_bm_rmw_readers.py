"""What the overwrite cell brought beside its driver: ``work_rmw`` (what
a parity update moves), ``rmw_roofline``, ``rmw_stage`` (an overwrite's
latency by stage, from the span rings), the counter ratios' specs, the
plain image reference and the control's fault, on hand-made facts,
rings and arrays."""

from __future__ import annotations

import numpy as np
import pytest

import bm_toy  # noqa: F401
from benchmark import control_rmw, harness, work, work_rmw
from benchmark.readers import rmw_roofline, rmw_stage
from benchmark.reference import ec
from benchmark.reference import image as ref_image
from test_bm_readers import span

CELL = "rbd_ec_randwrite_4k"
ROOFLINE = harness.layer_metric("rmw_hbm_share")["spec"]


@pytest.mark.parametrize("k,m,unit,stripes,want", [
    (8, 3, 4096, 1, 57_344),         # one overwrite alone: 14 chunks, 56 KiB
    (8, 3, 4096, 64, 3_670_016),     # a full batch
    (10, 4, 4096, 2, 147_456),
    (2, 1, 4096, 0, 0),
])
def test_rmw_bytes(k, m, unit, stripes, want):
    # by hand: m old parity + k delta chunks read, m parity written
    assert work_rmw.rmw_bytes(k, m, unit, stripes) == want
    assert want == stripes * (m * unit + k * unit + m * unit)


def test_rmw_roofline_counts_the_launched_batch():
    facts = {"trace.programs": {"jit_ec_rmw": 0.0004,
                                "jit_ec_decode_rows": 0.5},
             "slice.ec_batch.mesh_rmw_padded_stripes": 40,
             "slice.ec_batch.stripes": 33,
             "config.profile.k": 8, "config.profile.m": 3,
             "config.profile.stripe_unit": 4096,
             "device.kind": "TPU v5 lite"}
    want = work.roofline_share(40 * 57_344, 819e9, 0.0004)
    assert rmw_roofline.read(ROOFLINE, facts) == pytest.approx(want)
    assert 0 < want < 100
    # no update ran in the slice, no slice, a program without the
    # counter (the parent of the PR that brought it): nothing to read
    assert rmw_roofline.read(ROOFLINE, dict(
        facts, **{"trace.programs": {"jit_ec_decode_rows": 0.5}})) is None
    assert rmw_roofline.read(ROOFLINE, {
        k: v for k, v in facts.items()
        if k != "slice.ec_batch.mesh_rmw_padded_stripes"}) is None
    assert rmw_roofline.read(ROOFLINE, {}) is None
    with pytest.raises(harness.HarnessError):
        rmw_roofline.read(ROOFLINE, dict(facts, **{"device.kind": "x"}))


def rmw_op(trace, t0, to_osd, before, read, parity, launch, after, reply,
           asked=1, cached=0):
    a = t0 + to_osd
    b = a + before
    c = b + read
    d = c + parity
    e = d + launch
    f = e + after
    spans = [span(trace, "client.osd_op", t0, f + reply),
             span(trace, "osd.do_op", a, f),
             span(trace, "ec.rmw_read", b, c),
             span(trace, "ec.rmw_parity", c, d),
             span(trace, "ec.encode", d, e)]
    spans[2]["tags"] = {"asked": asked, "cached": cached}
    return spans


def test_rmw_stage_keeps_whole_single_attempt_overwrites_of_the_window():
    dumps = (rmw_op("t1", 100.0, 0.1, 0.05, 0.5, 0.2, 0.1, 0.05, 0.1)
             + rmw_op("t2", 101.0, 0.3, 0.0, 0.0, 0.4, 0.2, 0.1, 0.2)
             # before the window
             + rmw_op("t0", 10.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0)
             # sent again: two osd.do_op
             + rmw_op("t3", 102.0, 0.1, 0.0, 0.5, 0.1, 0.1, 0.0, 0.1)
             + [span("t3", "osd.do_op", 102.9, 103.0)]
             # a ring dropped its root; another its parity fetch
             + rmw_op("t4", 102.0, 0.1, 0.0, 0.5, 0.1, 0.1, 0.0, 0.1)[1:]
             + [s for s in rmw_op("t7", 102.0, 0.1, 0.0, 0.5, 0.1, 0.1, 0.0,
                                  0.1) if s["name"] != "ec.rmw_parity"]
             # unfinished, and a whole-object write (no ec.rmw_read)
             + [span("t5", "client.osd_op", 103.0, None)]
             + [span("t6", "client.osd_op", 103.0, 103.2),
                span("t6", "osd.do_op", 103.1, 103.15),
                span("t6", "ec.encode", 103.1, 103.12)])
    ops, left = rmw_stage.whole_writes(dumps, 30.0)
    assert sorted(o["client.osd_op"]["trace_id"] for o in ops) == ["t1", "t2"]
    assert left == {"resent": 1, "partial": 2}
    assert len(rmw_stage.whole_writes(dumps, 100.0)[0]) == 3
    assert rmw_stage.whole_writes([], 30.0) == ([], {"resent": 0,
                                                     "partial": 0})


def test_rmw_stages_read_the_program_rings_and_add_up(monkeypatch, capsys):
    from ceph_tpu.common import tracing

    class Ring:
        def __init__(self, dumps):
            self.dumps = dumps

        def dump(self):
            return self.dumps

    one = rmw_op("t1", 100.0, 0.1, 0.05, 0.5, 0.2, 0.1, 0.05, 0.1)
    two = rmw_op("t2", 101.0, 0.3, 0.0, 0.0, 0.4, 0.2, 0.1, 0.2,
                 cached=1)
    monkeypatch.setattr(tracing, "_TRACERS",
                        {"client.x": Ring(one[:1] + two[:1]),
                         "osd.0": Ring(one[1:] + two[1:])})
    monkeypatch.setattr(rmw_stage, "_reported", False)
    facts = {"run.window_s": 30.0}
    got = {stage: rmw_stage.read({"stage": stage}, facts)
           for stage in rmw_stage.STAGES}
    assert got == pytest.approx({"to_osd": 200.0, "read_old": 250.0,
                                 "read_parity": 300.0, "launch": 150.0,
                                 "commit": 100.0, "reply": 150.0})
    assert sum(got.values()) == pytest.approx((1100 + 1200) / 2)
    said = capsys.readouterr().out
    assert "2 overwrites with a whole span tree" in said
    assert "25.0 ms before ec.rmw_read" in said
    assert "75.0 ms after ec.encode" in said
    assert "stripes asked 2, of which the ExtentCache served 1" in said
    assert rmw_stage.read({"stage": "launch"}, {}) is None
    monkeypatch.setattr(tracing, "_TRACERS", {})
    assert rmw_stage.read({"stage": "launch"}, facts) is None


RATIOS = {"window.ec_batch.stripes": 130, "window.ec_batch.batches": 100,
          "window.ec_batch.queue_wait_us": 250_000,
          "window.ec_batch.rmw_delta_runs": 120, "run.rmw_runs": 125,
          "window.ec_pipeline.rmw_stripes_cached": 5,
          "window.ec_pipeline.rmw_stripes_read": 125,
          "window.ec_hedge.subread_bytes": 125 * 10 * 4096,
          "run.written_bytes": 125 * 4096,
          "trace.busy_s": 0.002, "trace.idle_s": 1.998,
          "trace.window_s": 2.0, "slice.ec_batch.mesh_launches": 80}


@pytest.mark.parametrize("metric,want", [
    ("stripes_per_launch", 1.3),
    ("launch_queue_ms.rmw", 2.5),
    ("rmw_delta_share", 96.0),
    ("extent_cache_hit_share", 4.0),
    ("subread_bytes_per_written_byte", 10.0),
    ("device_ms_per_launch.store", 0.025),
    ("device_idle_share.store", 99.9),
])
def test_counter_ratios_of_the_cell(metric, want):
    got = harness.read_layer_metrics([metric], dict(RATIOS))
    assert got[metric]["value"] == pytest.approx(want)
    # the parent of the PR that brought the counters has none of them:
    # nothing to read, nothing raised, the metric left out
    assert harness.read_layer_metrics([metric], {}) == {}


def test_every_metric_of_the_cell_names_it_in_manifest_and_file():
    """Eight of the cell's metrics are the write cells' own since PR 49
    and list it last; a later PR may append more, so nothing is counted."""
    cell = harness.Cell(CELL)
    manifest = harness.load_json(harness.ROOT / "BENCHMARK.json")
    listed = {p["name"]: p["workloads"] for p in manifest["per_layer"]}
    assert cell.per_layer
    for name in cell.per_layer:
        assert CELL in listed[name]
        assert harness.layer_metric(name)["workloads"] == listed[name]
    assert {"host_ms_per_op.wire", "host_ms_per_rmw.unsectioned",
            "rmw_wait_ms.launch", "rmw_hbm_share",
            "device_idle_share.store"} <= set(cell.per_layer)
    # the tail is the per-layer rmw_op_p95_ms since the check of PR 49
    assert cell.end_to_end == {"client_mibps": "MiB/s", "setup_s": "s"}
    assert "rmw_op_p95_ms" in cell.per_layer


def test_image_reference_applies_writes_in_order_and_cuts_objects():
    img = ref_image.Image(5, 1, 4 * 8192, 8192)
    first = bytes(img.data)
    assert first == bytes(ref_image.first_content(5, 1, 4 * 8192))
    assert first != bytes(ref_image.first_content(5, 0, 4 * 8192))
    assert img.written == {}
    a = ref_image.write_payload(5, 1, 3, 1, 4096)
    b = ref_image.write_payload(5, 1, 3, 2, 4096)
    assert a != b and a == ref_image.write_payload(5, 1, 3, 1, 4096)
    img.write(3 * 4096, a)
    img.write(3 * 4096, b)                       # the later write wins
    img.write(8192 - 100, b"\x01" * 200)         # straddles objects 0 and 1
    assert img.read(3 * 4096, 4096) == b
    assert img.written == {1: 3, 0: 1}
    assert img.object(1) == b"\x01" * 100 + first[8292:3 * 4096] + b
    assert img.object(2) == first[2 * 8192:3 * 8192]     # untouched
    img.mark()
    assert img.written == {}
    with pytest.raises(ValueError):
        img.write(4 * 8192 - 1, b"xx")
    with pytest.raises(ValueError):
        ref_image.Image(5, 0, 8192 + 1, 8192)


def test_stale_parity_withholds_one_stripes_update_and_nothing_else():
    """The control's fault at the launch: the first stripe's parity
    comes back as it went in, so it no longer is the generator's
    product with the data; every other stripe's does."""
    from ceph_tpu.ec import registry
    from ceph_tpu.parallel.mesh_codec import MeshCodec

    profile = {"k": 2, "m": 1, "technique": "reed_sol_van",
               "stripe_unit": 64}
    codec = registry().factory("tpu", {"k": "2", "m": "1",
                                       "technique": "reed_sol_van"})
    mesh = MeshCodec()
    n = mesh.pad_batch(2)             # a multiple of the device count
    rng = np.random.default_rng(3)
    old = rng.integers(0, 256, (n, 2, 64), dtype=np.uint8)
    new = old.copy()
    new[:, 1] = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    matrix = ec.coding_matrix("reed_sol_van", 2, 1)
    parity = np.stack([ec.gf_matmul(matrix, rows) for rows in old])
    want = np.stack([ec.gf_matmul(matrix, rows) for rows in new])
    sound = mesh.rmw(codec, parity, old ^ new)
    assert sound.tobytes() == want.tobytes()
    with control_rmw.FAULTS["stale_parity"]():
        broken = mesh.rmw(codec, parity, old ^ new)
    assert broken[0].tobytes() == parity[0].tobytes() != want[0].tobytes()
    assert broken[1:].tobytes() == want[1:].tobytes()
    assert broken.shape == (n, profile["m"], profile["stripe_unit"])
