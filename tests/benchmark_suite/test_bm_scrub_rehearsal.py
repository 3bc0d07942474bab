"""CPU rehearsals of the scrub driver (drivers/store_scrub_loop.py) at
toy size: sound, traced, with a scrub that takes the tags' word for
the bytes underneath (control_scrub.py), and against a program without
the scrub layer.  A rehearsal skips only the harness's look for a chip:
driver, reference and comparison are the real ones.  No timing of a
rehearsal is a device metric, and none is printed as one."""

from __future__ import annotations

import pytest

import bm_toy
from benchmark import control_scrub, harness
from benchmark import run as bench_run
from benchmark.drivers import store_scrub_loop as driver
from benchmark.readers import layer_time, scrub_stage, span_time
from benchmark.reference import scrub as ref

CELL = "rs_k8m3_scrub_write_4m"
LAYERS = ("wire", "osd_op", "store", "batcher", "device_wait",
          "unsectioned", "scrub")
HOST = [f"host_ms_per_op.{layer}.under_scrub" for layer in LAYERS]
STAGES = [f"scrub_chunk_ms.{stage}" for stage in scrub_stage.STAGES]
ROUTES = ["scrub_thread_ms_per_mib.host", "scrub_thread_ms_per_mib.device"]
COUNTED = ["scrubbed_mibps", "scrub_active_share",
           "scrub_device_digest_share",
           "scrub_wire_bytes_per_digested_byte", "device_idle_share.scrub"]
DEVICE = ["device_ms_per_launch.scrub", "scrub_crc_hbm_share"]


def toy_cell() -> harness.Cell:
    """k=2, m=1 on 4 OSDs, 8 PGs, 48 objects of four stripe rows (six
    a PG, three chunks of 2), two faults of each kind.  The toy
    population fits the shard cache whole, so every shard but the
    faulted ones (their plant dropped the cache's copy) takes the
    device route; the host route is theirs."""
    cell = bm_toy.toy_cell(CELL)
    cell.traffic.update(populate_objects=48, populate_in_flight=4,
                        faults_per_kind=2, check_population_objects=4,
                        scrub_timeout_s=90)
    cell.config["cluster"]["osd_config"].update(
        osd_ec_batch_max=4, osd_scrub_chunk_max=2, osd_scrub_interval=0.5)
    return cell


def rehearse(seconds: float = 2.0, traced: bool = False) -> dict:
    harness.build_native()
    return bench_run.run_cell(toy_cell(), 7, seconds, traced, bm_toy.CPU)


def window(facts: dict, name: str) -> dict:
    return {k.removeprefix(f"window.{name}."): v for k, v in facts.items()
            if k.startswith(f"window.{name}.")}


def test_the_cell_lists_every_metric_this_file_reads():
    assert sorted(harness.Cell(CELL).per_layer) == sorted(
        HOST + STAGES + ROUTES + COUNTED + DEVICE)
    cell = harness.Cell(CELL)
    assert sorted(cell.end_to_end) == ["client_mibps", "op_p95_ms",
                                       "setup_s"]
    assert cell.chips == 1 and cell.traffic["driver"] == "store_scrub_loop"


def test_sound_scrub_rehearsal_finds_and_repairs_beside_the_writers():
    res = rehearse()
    assert res["rehearsal"] and "metrics" not in res
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    facts = res["facts"]
    assert [facts[f"check.{name}"] for name in driver.FAULTS] \
        == [0] * len(driver.FAULTS)
    assert facts["run.ops"] == res["attempted"]
    # the reports the run was judged by are the reference's, to the kind
    cell = toy_cell()
    faults = ref.plant(7, cell.config["profile"], 48,
                       cell.traffic["object_bytes"], 2)
    assert {tuple(r) for r in facts["run.reports"]} \
        == ref.expected_reports(faults)
    assert len(faults) == 8 and not any(
        oid.startswith("new-") for oid, _, _ in facts["run.reports"])
    # scrubs ran inside the window as the OSDs scheduled them, and every
    # PG came round after the plant
    scr, batch = window(facts, "scrub"), window(facts, "ec_batch")
    assert scr["chunks"] > 0 and scr["objects"] > 0
    assert facts["run.scrub_active_s"] > 0
    assert facts["run.scrub_results"] >= 8
    assert facts["run.scrub_bytes_digested"] == \
        scr["bytes_digested_host"] + scr["bytes_digested_device"] > 0
    # maps travelled, shards did not
    assert 0 < scr["map_bytes"] < 0.05 * facts["run.scrub_bytes_digested"]
    # the digest is a kind of launch beside the clients' encodes
    assert batch["encode_launches"] > 0 and batch["digest_launches"] > 0
    # every row is a whole shard; the batcher counts a launch when it
    # leaves and the scrub its bytes when they are back, so a launch in
    # flight at either edge of the window is on one side only
    shard = ref.shard_bytes(cell.config["profile"],
                            cell.traffic["object_bytes"])
    assert scr["bytes_digested_device"] % shard == 0
    assert abs(batch["digest_stripes"]
               - scr["bytes_digested_device"] // shard) <= 16
    assert batch["encode_launches"] + batch["digest_launches"] \
        + batch.get("decode_launches", 0) == batch["batches"] \
        == batch["mesh_launches"]
    assert batch.get("fallback_ops", 0) == 0
    # the spans the stage metrics read are the program's
    chunks, _ = scrub_stage.whole_chunks(
        facts["spans.scrub"], facts["run.wall_open"],
        facts["run.wall_close"])
    assert chunks and all(
        set(c["scrub.chunk"]["tags"]) == {"objects", "bytes",
                                          "blocked_writes"}
        for c in chunks)


def test_traced_scrub_rehearsal_reads_every_host_metric_and_they_add_up(
        monkeypatch, tmp_path):
    """The slice is started and stopped off the loop's thread while the
    writers and the scrubs run: no write fails, the seven listed
    ``.under_scrub`` layers add up to the slice per finished write less
    the ``client.*`` sections' 0.02 ms (ledger, PR 47; PR 49), the
    four stages to the mean chunk, and each digest route's thread time
    is read against its own bytes.  The trace goes to a directory of
    this test's own: the other files' traced rehearsals, on other
    workers, look for the newest trace under the shared one."""
    monkeypatch.setattr(harness, "SCRATCH", tmp_path)
    res = rehearse(seconds=3.0, traced=True)
    assert res["correct"] is True and res["failed"] == 0
    facts = res["facts"]
    path = span_time.newest_trace()
    assert path is not None and tmp_path in path.parents
    monkeypatch.setattr(scrub_stage, "_reported", False)
    assert facts["slice.ec_batch.encode_launches"] > 0
    assert facts["slice.scrub.chunks"] > 0
    facts.update({"trace.window_s": 0.5, "trace.busy_s": 0.0,
                  "trace.idle_s": 0.5})
    names = HOST + STAGES + COUNTED
    got = harness.read_layer_metrics(names + ROUTES + DEVICE, facts)
    assert set(names) <= set(got) and not set(DEVICE) & set(got)
    spec = harness.layer_metric(HOST[0])["spec"]
    sl = layer_time.load(path, tuple(spec["layers"]))
    writes = sl["started"]["client.complete"]
    # less the client.* sections, which no metric lists since PR 49
    assert sum(got[name]["value"] for name in HOST) == pytest.approx(
        1e3 * (sl["hi"] - sl["lo"]) / writes - bm_toy.client_ms_per_op(sl),
        rel=1e-6)
    assert got["host_ms_per_op.scrub.under_scrub"]["value"] > 0
    assert any(name.startswith("scrub.") for name in sl["started"])
    chunks, _ = scrub_stage.whole_chunks(
        facts["spans.scrub"], facts["run.wall_open"],
        facts["run.wall_close"])
    mean = 1e3 * sum(c["scrub.chunk"]["end"] - c["scrub.chunk"]["start"]
                     for c in chunks) / len(chunks)
    assert sum(got[name]["value"] for name in STAGES) == pytest.approx(mean)
    assert got["scrub_chunk_ms.digest"]["value"] > 0
    # a route is read where it digested something in the slice
    for route in ("host", "device"):
        name = f"scrub_thread_ms_per_mib.{route}"
        assert (name in got) == bool(
            facts[f"slice.scrub.bytes_digested_{route}"])
    assert 0 < got["scrub_active_share"]["value"] <= 100.0
    assert got["scrub_wire_bytes_per_digested_byte"]["value"] < 0.05


def test_a_trusting_scrub_comes_out_not_correct_by_what_it_cannot_see():
    """Sizes, versions and labels compared, the tags believed: the
    removed shards are found and repaired; the rots and the replaced
    tags are missed and stay where they are.  Nothing else is wrong."""
    with control_scrub.FAULTS["trusting"]():
        res = rehearse()
    assert res["correct"] is False
    assert res["attempted"] > 0 and res["failed"] == 0
    facts = res["facts"]
    assert facts["check.missed"] == 6                # 2 each of 3 kinds
    assert facts["check.repaired_bytes_wrong"] == 4  # data_rot, parity_rot
    assert facts["check.repaired_crc_wrong"] == 2    # tag_rot
    assert sorted(kind for _, _, kind in facts["run.reports"]) \
        == ["missing", "missing"]
    for name in driver.FAULTS:
        if name not in ("missed", "repaired_bytes_wrong",
                        "repaired_crc_wrong"):
            assert facts[f"check.{name}"] == 0, name


def test_a_program_without_the_scrub_layer_is_refused_with_exit_2(
        monkeypatch, capsys):
    from ceph_tpu.common import tracing

    monkeypatch.setattr(tracing, "SECTION_LAYERS", tuple(
        layer for layer in tracing.SECTION_LAYERS if layer != "scrub"))
    monkeypatch.setattr(harness, "require_chips", lambda chips: bm_toy.CPU)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    rc = bench_run.main(["--workload", CELL, "--seed", "1", "--seconds",
                         "1"])
    assert rc == 2
    out = capsys.readouterr()
    assert "no scrub layer" in out.err
    assert not out.out.strip().endswith("}")     # no result line
