"""CPU rehearsals of the overwrite driver (drivers/rbd_rmw_loop.py) at
toy size: sound, traced, and with the parity update broken underneath
(control_rmw.py).  A rehearsal skips only the harness's look for a
chip: driver, references and comparison are the real ones.  No timing
of a rehearsal is a device metric, and none is printed as one."""

from __future__ import annotations

import copy

import pytest

import bm_toy
from benchmark import control_rmw, harness
from benchmark import run as bench_run
from benchmark.readers import rmw_stage, span_time

CELL = "rbd_ec_randwrite_4k"
# the five layers are the write cells' own metrics since PR 49 (one file,
# one reader, one spec); what no section covers keeps the cell's own name
HOST = [f"host_ms_per_op.{layer}" for layer in (
    "wire", "osd_op", "store", "batcher", "device_wait")] \
    + ["host_ms_per_rmw.unsectioned"]
STAGES = [f"rmw_wait_ms.{stage}" for stage in rmw_stage.STAGES]
IO = 4096


def toy_cell() -> harness.Cell:
    """k=2, m=1 on 4 OSDs; two images of eight 32 KiB objects (four
    stripes each); 2 x 4 writes in flight; launches of 1, 2 and 4."""
    cell = harness.Cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["profile"].update(k=2, m=1)
    cell.config["cluster"]["osds"] = 4
    cell.config["cluster"]["osd_config"]["osd_ec_batch_max"] = 4
    cell.config["pools"]["header"]["pg_num"] = 4
    cell.config["pools"]["data"]["pg_num"] = 8
    cell.config["images"].update(bytes=8 * 32768, order=15)
    cell.traffic.update(iodepth=4, prefill_bytes=32768, prefill_in_flight=4,
                        warmup_ops=8, check_objects=6, trace_slice_s=0.5)
    return cell


def rehearse(seconds: float = 1.0, traced: bool = False) -> dict:
    harness.build_native()
    return bench_run.run_cell(toy_cell(), 7, seconds, traced, bm_toy.CPU)


def window(facts: dict, name: str) -> dict:
    return {k.removeprefix(f"window.{name}."): v for k, v in facts.items()
            if k.startswith(f"window.{name}.")}


def test_sound_rmw_rehearsal_is_correct_and_served_by_the_delta_path():
    res = rehearse()
    assert res["rehearsal"] and "metrics" not in res
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    facts = res["facts"]
    assert facts["run.ops"] == res["attempted"]
    assert facts["run.written_bytes"] == facts["run.ops"] * IO
    assert facts["run.objects_overwritten"] > 0
    assert facts["run.header_pool_writes"] == 0
    batch, pipe, osd = (window(facts, name) for name in
                        ("ec_batch", "ec_pipeline", "osd"))
    # every write of the window took the delta path, none a re-encode,
    # none the blind full-object path; the counters are read at the
    # window's edges, where 8 writes are in flight
    runs = batch["rmw_delta_runs"]
    assert batch.get("rmw_full_runs", 0) == 0 and pipe["writes_blind"] == 0
    assert abs(runs - facts["run.ops"]) <= 8
    assert facts["run.rmw_runs"] == runs
    assert batch["rmw_launches"] == batch["mesh_rmw_launches"] > 0
    assert batch.get("encode_launches", 0) == 0
    assert batch.get("fallback_ops", 0) == 0
    assert abs(batch["stripes"] - runs) <= 8              # one a write
    assert batch["mesh_rmw_padded_stripes"] >= batch["stripes"]
    # a stripe is asked for once a write; k-1 data shards and no other
    # take the version stamp alone; a gather only where the cache missed
    assert abs(pipe["rmw_stripes_read"] - runs) <= 8
    assert 0 <= pipe["rmw_stripes_cached"] <= pipe["rmw_stripes_read"]
    assert abs(pipe["rmw_subwrites_empty"] - runs) <= 8
    assert pipe["write_old_gathers"] \
        == pipe["rmw_stripes_read"] - pipe["rmw_stripes_cached"]
    assert window(facts, "ec_hedge")["subread_bytes"] > 0
    assert osd["op_r"] == 0 and abs(osd["op_w"] - facts["run.ops"]) <= 8
    # the cell's tail is a fact of every run; the manifest lists it per
    # layer (rmw_op_p95_ms) since the check of PR 49 refused it end to end
    assert facts["run.op_p95_ms"] > 0
    assert harness.read_layer_metrics(["rmw_op_p95_ms"], facts) == {
        "rmw_op_p95_ms": {"value": facts["run.op_p95_ms"], "unit": "ms"}}
    assert "op_p95_ms" not in harness.Cell(CELL).end_to_end


def test_traced_rmw_rehearsal_keeps_the_cluster_up_and_its_parts_add_up(
        monkeypatch):
    """The slice is started and stopped off the loop's thread while the
    writers run: no write fails, and the trace and the rings it leaves
    give every host layer and stage."""
    from ceph_tpu.common import tracing

    res = rehearse(seconds=1.5, traced=True)
    assert res["correct"] is True and res["failed"] == 0
    facts = res["facts"]
    assert facts["slice.ec_batch.mesh_rmw_launches"] > 0
    assert facts["slice.ec_batch.mesh_rmw_padded_stripes"] > 0
    monkeypatch.setattr(rmw_stage, "_reported", False)
    facts.update({"trace.window_s": 0.5, "trace.busy_s": 0.0,
                  "trace.idle_s": 0.5})
    got = harness.read_layer_metrics(HOST + STAGES, facts)
    assert sorted(got) == sorted(HOST + STAGES)
    sl = span_time.load(span_time.newest_trace())
    writes = sl["started"]["client.complete"]
    # less the client.* sections, which no metric lists since PR 49
    assert sum(got[name]["value"] for name in HOST) == pytest.approx(
        1e3 * (sl["hi"] - sl["lo"]) / writes - bm_toy.client_ms_per_op(sl),
        rel=1e-6)
    for name in ("osd_op.rmw_merge", "osd_op.stamp"):
        assert sl["started"][name] > 0
    assert got["host_ms_per_op.osd_op"]["value"] > 0
    ops, _ = rmw_stage.whole_writes(
        [s for t in tracing._TRACERS.values() for s in t.dump()],
        facts["run.window_s"])
    mean = 1e3 * sum(o["client.osd_op"]["end"] - o["client.osd_op"]["start"]
                     for o in ops) / len(ops)
    assert sum(got[name]["value"] for name in STAGES) == pytest.approx(mean)
    for stage in ("read_parity", "launch", "commit"):
        assert got[f"rmw_wait_ms.{stage}"]["value"] > 0


def test_stale_parity_comes_out_not_correct():
    with control_rmw.FAULTS["stale_parity"]():
        res = rehearse()
    assert res["correct"] is False
    assert res["attempted"] > 0          # it measured: a count, not a crash
    assert res["failed"] == 0            # the writes were acknowledged
