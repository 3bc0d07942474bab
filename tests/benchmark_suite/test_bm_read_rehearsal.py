"""CPU rehearsals of the read driver (drivers/store_read_loop.py) at toy
size: sound, traced, and with the decode broken underneath
(control_read.py).  A rehearsal skips only the harness's look for a
chip: driver, reference and comparison are the real ones.  No timing of
a rehearsal is a device metric, and none is printed as one.  The toy
cluster keeps the in-process cluster's short heartbeat grace; the
benchmark's 20 s belong to the chip."""

from __future__ import annotations

import pytest

import bm_toy
from benchmark import control_read, harness
from benchmark import run as bench_run
from benchmark.readers import read_span_time, read_stage, span_time

CELL = "rs_k8m3_degraded_read_4m"
HOST = [f"host_ms_per_read.{layer}" for layer in (
    "wire", "osd_read", "store", "batcher", "device_wait",
    "unsectioned")]
STAGES = [f"read_wait_ms.{stage}" for stage in read_stage.STAGES]


def toy_cell() -> harness.Cell:
    cell = bm_toy.toy_cell(CELL)
    cell.traffic.update(populate_objects=24, check_reads=8, warmup_ops=8)
    # one launch shape (an object's 4 stripe rows flush alone), the
    # in-process grace, and two reporters as in the deployment
    cell.config["cluster"]["osd_config"].update(
        osd_heartbeat_grace=3.0, osd_ec_batch_max=4)
    return cell


def rehearse(seconds: float = 1.0, traced: bool = False) -> dict:
    harness.build_native()
    return bench_run.run_cell(toy_cell(), 7, seconds, traced, bm_toy.CPU)


def window(facts: dict, name: str) -> dict:
    return {k.removeprefix(f"window.{name}."): v for k, v in facts.items()
            if k.startswith(f"window.{name}.")}


def test_sound_read_rehearsal_is_correct_and_counts_what_the_metrics_read():
    res = rehearse()
    assert res["rehearsal"] and "metrics" not in res
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    facts = res["facts"]
    assert facts["run.downs_setup"] == 1 and facts["run.downs_window"] == 0
    assert facts["run.ops"] == res["attempted"]
    assert facts["run.read_bytes"] == facts["run.ops"] * (3 * 8192 + 100)
    batch, degraded = window(facts, "ec_batch"), window(facts, "ec_degraded")
    assert 0 < degraded["reconstructions"] < facts["run.ops"] + 4
    assert batch["decode_launches"] == batch["mesh_launches"] \
        == batch["batches"] > 0
    assert batch.get("encode_launches", 0) == 0
    assert batch.get("fallback_ops", 0) == 0
    assert batch["stripes"] == 4 * batch["batches"]   # 4 stripe rows an object
    assert window(facts, "ec_hedge")["subread_bytes"] > 0
    assert facts["window.datapath.lookups"] > 0


def test_traced_read_rehearsal_keeps_the_cluster_up_and_its_parts_add_up(
        monkeypatch):
    """The slice is started and stopped off the loop's thread while the
    readers run: no read fails, nothing more is marked down, and the
    trace and the rings it leaves give every host layer and stage."""
    from ceph_tpu.common import tracing

    res = rehearse(seconds=1.5, traced=True)
    assert res["correct"] is True and res["failed"] == 0
    facts = res["facts"]
    assert facts["run.downs_setup"] == 1 and facts["run.downs_window"] == 0
    assert facts["slice.ec_batch.decode_launches"] > 0
    monkeypatch.setattr(read_stage, "_reported", False)
    facts.update({"trace.window_s": 0.5, "trace.busy_s": 0.0,
                  "trace.idle_s": 0.5})
    got = harness.read_layer_metrics(HOST + STAGES, facts)
    assert sorted(got) == sorted(HOST + STAGES)
    sl = read_span_time.load(span_time.newest_trace())
    reads = sl["started"]["client.complete"]
    # less the client.* sections, which no metric lists since PR 49
    assert sum(got[name]["value"] for name in HOST) == pytest.approx(
        1e3 * (sl["hi"] - sl["lo"]) / reads - bm_toy.client_ms_per_op(sl),
        rel=1e-6)
    assert got["host_ms_per_read.osd_read"]["value"] > 0
    ops, _ = read_stage.whole_reads(
        [s for t in tracing._TRACERS.values() for s in t.dump()],
        facts["run.window_s"])
    mean = 1e3 * sum(o["client.osd_op"]["end"] - o["client.osd_op"]["start"]
                     for o in ops) / len(ops)
    assert sum(got[name]["value"] for name in STAGES) == pytest.approx(mean)
    assert got["read_wait_ms.decode"]["value"] > 0


def test_broken_decode_comes_out_not_correct():
    with control_read.FAULTS["decode"]():
        res = rehearse()
    assert res["correct"] is False
    assert res["attempted"] > 0          # it measured: a count, not a crash
    assert res["failed"] == 0            # the reads returned, wrong
