"""CPU rehearsals of the backfill driver (drivers/store_backfill_loop.py)
at toy size: sound, traced, with the rebuild's decode broken underneath
(control_backfill.py), and against a program without the repair's
spans.  A rehearsal skips only the harness's look for a chip: driver,
reference and comparison are the real ones.  No timing of a rehearsal
is a device metric, and none is printed as one.  The toy cluster keeps
the in-process cluster's short heartbeat grace; the benchmark's 20 s
belong to the chip."""

from __future__ import annotations

import pytest

import bm_toy
from benchmark import control_backfill, harness
from benchmark import run as bench_run
from benchmark.drivers import store_backfill_loop as driver
from benchmark.readers import backfill_span_time, backfill_stage, span_time

CELL = "rs_k8m3_backfill_write_4m"
HOST = [f"host_ms_per_op.{layer}.backfill" for layer in (
    "wire", "osd_op", "store", "batcher", "device_wait",
    "unsectioned", "recovery")]
STAGES = [f"backfill_wait_ms.{stage}" for stage in backfill_stage.STAGES]
COUNTED = ["recovered_mibps", "repair_read_bytes_per_shipped_byte",
           "backfill_dirty_push_share", "backfill_active_share",
           "stripes_per_launch.recover", "launch_queue_ms.recover",
           "device_idle_share.store"]       # the write cells' own, since PR 49
DEVICE = ["device_ms_per_launch.recover", "recover_hbm_share"]
FAULTS = ("readback_differs", "shards_missing", "shard_bytes_wrong",
          "crc_xattr_wrong", "shard_label_wrong", "not_clean")


def toy_cell() -> harness.Cell:
    """k=2, m=1 on 4 OSDs, 8 PGs, 48 objects of four stripe rows: one
    host out leaves three for three positions, as 12 and 11 do.  The
    PG log is cut with the population (3 entries for 6 objects a PG),
    so the new members are backfilled by scan, not from the log."""
    cell = bm_toy.toy_cell(CELL)
    cell.traffic.update(populate_objects=48, populate_in_flight=4,
                        check_new_objects=4, check_rebuilt_objects=4,
                        clean_timeout_s=60)
    cell.config["cluster"]["osd_config"].update(
        osd_heartbeat_grace=3.0, osd_ec_batch_max=4,
        osd_max_pg_log_entries=3)
    return cell


def rehearse(seconds: float = 2.0, traced: bool = False) -> dict:
    harness.build_native()
    return bench_run.run_cell(toy_cell(), 7, seconds, traced, bm_toy.CPU)


def window(facts: dict, name: str) -> dict:
    return {k.removeprefix(f"window.{name}."): v for k, v in facts.items()
            if k.startswith(f"window.{name}.")}


def test_the_cell_lists_every_metric_this_file_reads():
    assert sorted(harness.Cell(CELL).per_layer) == sorted(
        HOST + STAGES + COUNTED + DEVICE)


def test_sound_backfill_rehearsal_heals_beside_the_writers_and_is_correct():
    res = rehearse()
    assert res["rehearsal"] and "metrics" not in res
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    facts = res["facts"]
    assert [facts[f"check.{name}"] for name in FAULTS] == [0] * len(FAULTS)
    assert facts["run.ops"] == res["attempted"]
    # the repair ran inside the window, started by the map alone, and
    # was still running when it closed; the cluster went clean after
    assert facts["run.backfill_active_s"] == facts["run.window_s"]
    assert facts["run.out_to_clean_s"] > facts["run.out_to_close_s"]
    assert facts["run.rebuilt_in_window"] >= 4
    assert facts["run.pgs_positions_moved"] > 0
    assert facts["run.shards_requeued"] > 0
    rec, batch = window(facts, "ec_recovery"), window(facts, "ec_batch")
    assert rec["backfill_pushes"] > 0
    assert 0 <= rec["backfill_dirty_pushes"] < rec["backfill_pushes"]
    assert rec["repair_bytes_shipped"] > 0
    assert rec["repair_global_decodes"] > 0
    assert rec.get("repair_local_repairs", 0) == 0      # plain RS
    # both kinds of launch in one window, each kind with its own counts
    assert batch["encode_launches"] > 0 and batch["decode_launches"] > 0
    assert batch["encode_launches"] + batch["decode_launches"] \
        == batch["batches"] == batch["mesh_launches"]
    assert batch["encode_stripes"] + batch["decode_stripes"] \
        == batch["stripes"]
    assert batch["encode_queue_wait_us"] + batch["decode_queue_wait_us"] \
        == batch["queue_wait_us"]
    assert batch["decode_stripes"] == 4 * batch["decode_launches"]
    assert batch.get("fallback_ops", 0) == 0
    # the pushes the sample was drawn from are the program's spans
    pushes = driver.pushes_between(facts["spans.backfill"],
                                   facts["run.wall_open"],
                                   facts["run.wall_close"])
    assert len(pushes) >= facts["run.rebuilt_in_window"]
    assert all(set(p["tags"]) >= {"pgid", "oid", "shard", "dirty"}
               for p in pushes)


def test_traced_backfill_rehearsal_keeps_the_cluster_up_and_its_parts_add_up(
        monkeypatch):
    """The slice is started and stopped off the loop's thread while the
    writers and the repair run: no write fails, and the trace and the
    spans give every host layer, the repair's among them, and every
    stage of a push."""
    res = rehearse(seconds=2.5, traced=True)
    assert res["correct"] is True and res["failed"] == 0
    facts = res["facts"]
    assert facts["slice.ec_batch.decode_launches"] > 0
    assert facts["slice.ec_batch.encode_launches"] > 0
    monkeypatch.setattr(backfill_stage, "_reported", False)
    facts.update({"trace.window_s": 0.5, "trace.busy_s": 0.0,
                  "trace.idle_s": 0.5})
    names = HOST + STAGES + COUNTED
    got = harness.read_layer_metrics(names + DEVICE, facts)
    assert sorted(got) == sorted(names)       # no device, no device metric
    sl = backfill_span_time.load(span_time.newest_trace())
    writes = sl["started"]["client.complete"]
    # less the client.* sections, which no metric lists since PR 49
    assert sum(got[name]["value"] for name in HOST) == pytest.approx(
        1e3 * (sl["hi"] - sl["lo"]) / writes - bm_toy.client_ms_per_op(sl),
        rel=1e-6)
    assert sl["started"]["recovery.payload"] > 0
    assert sl["started"]["recovery.apply"] > 0
    assert got["host_ms_per_op.recovery.backfill"]["value"] > 0
    pushes, _ = backfill_stage.whole_pushes(
        facts["spans.backfill"], facts["run.wall_open"],
        facts["run.wall_close"])
    mean = 1e3 * sum(p["pg.backfill_push"]["end"]
                     - p["pg.backfill_push"]["start"]
                     for p in pushes) / len(pushes)
    assert sum(got[name]["value"] for name in STAGES) == pytest.approx(mean)
    for stage in ("gather", "decode", "push"):
        assert got[f"backfill_wait_ms.{stage}"]["value"] > 0
    assert got["backfill_active_share"]["value"] == 100.0
    assert got["stripes_per_launch.recover"]["value"] == 4.0


def test_flipped_rebuild_comes_out_not_correct_by_the_rebuilt_shards_alone():
    """Every checksum and label matches; a rebuilt shard is wrong, and
    where it is a data shard a read of the object returns it."""
    with control_backfill.FAULTS["rebuilt"]():
        res = rehearse()
    assert res["correct"] is False
    assert res["attempted"] > 0          # it measured: a count, not a crash
    assert res["failed"] == 0            # the writes were acknowledged
    facts = res["facts"]
    assert facts["check.shard_bytes_wrong.rebuilt"] >= 4     # one a pick
    assert facts["check.readback_differs"] <= 8
    # a fresh object whose write was skipped past the target's cursor
    # got that shard by a dirty push: rebuilt too, and as wrong
    assert facts["check.shard_bytes_wrong.new"] <= 4
    for name in FAULTS:
        if name not in ("shard_bytes_wrong", "readback_differs"):
            assert facts[f"check.{name}"] == 0, name


def test_a_program_without_the_repairs_spans_is_refused_with_exit_2(
        monkeypatch, capsys):
    from ceph_tpu.common import tracing

    monkeypatch.setattr(tracing, "SECTION_LAYERS", tuple(
        layer for layer in tracing.SECTION_LAYERS if layer != "recovery"))
    monkeypatch.setattr(harness, "require_chips", lambda chips: bm_toy.CPU)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    rc = bench_run.main(["--workload", CELL, "--seed", "1", "--seconds",
                         "1"])
    assert rc == 2
    out = capsys.readouterr()
    assert "no recovery layer" in out.err
    assert not out.out.strip().endswith("}")     # no result line
