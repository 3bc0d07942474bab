"""CPU rehearsals of each driver at toy size (bm_toy.py), sound and with
the timed path broken underneath.  A rehearsal skips only the harness's
look for a chip: driver, reference and comparison are the real ones.
No timing of a rehearsal is a device metric, and none is printed as one.
"""

from __future__ import annotations

import pytest

import bm_toy
from benchmark import control


@pytest.mark.parametrize("cell", ["rs_k8m3_write_4m", "crush_1000osd_bulk"])
def test_sound_rehearsal_is_correct_and_prints_no_metric(cell):
    res = bm_toy.rehearse(cell)
    assert res["rehearsal"] and "metrics" not in res
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["facts"]["run.ops"] > 0


def test_store_rehearsal_counts_what_the_layer_metrics_read():
    facts = bm_toy.rehearse("rs_k8m3_write_4m")["facts"]
    w = {k.split(".")[-1]: v for k, v in facts.items()
         if k.startswith("window.ec_batch.")}
    assert w["batches"] == w["mesh_launches"] == w["crc_fused_launches"] > 0
    assert w.get("crc_host_batches", 0) == 0
    assert w.get("fallback_ops", 0) == 0
    assert w["stripes"] >= 4 * w["batches"]      # 4 stripe rows an object


@pytest.mark.parametrize("cell,fault", [
    ("rs_k8m3_write_4m", "parity"),
    ("rs_k8m3_write_4m", "crc"),
    ("crush_1000osd_bulk", "lane"),
])
def test_broken_timed_path_comes_out_not_correct(cell, fault):
    with control.FAULTS[fault]():
        res = bm_toy.rehearse(cell)
    assert res["correct"] is False
    assert res["attempted"] > 0          # it measured: a count, not a crash


def test_traced_store_rehearsal_keeps_the_cluster_up():
    """The slice is started and stopped off the loop's thread while the
    writers run: no operation fails and nothing is marked down."""
    res = bm_toy.rehearse("rs_k8m3_write_4m", seconds=1.5, traced=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["facts"]["slice.ec_batch.mesh_launches"] > 0
