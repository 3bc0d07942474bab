"""CPU rehearsal of the expansion cell (drivers/crush_expand.py) at cut
``pg_num`` through ``run_cell``: facts and counts, no timing under a
metric's name; ``correct`` true, and false when one table entry is
flipped; the ``HarnessError`` at once on a program whose bucket tables
are constants; the cell's six metric files against the manifest.
"""

from __future__ import annotations

import copy
import json

import pytest

import bm_toy
from benchmark import harness
from benchmark import run as bench_run
from benchmark.drivers import crush_expand

CELL = "crush_1000osd_expand_epochs"
M = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
METRICS = ["expand_epoch_ms.launch", "expand_epoch_ms.ingest",
           "expand_epoch_ms.delta", "expand_epoch_ms.rest",
           "crush_indep_device_share", "crush_program_reuse_share"]
TABLE = "placement table (mon/pg_mapping.py)"
MAPPER = "placement (crush/vectorized.py)"


def toy_cell() -> harness.Cell:
    """The cell's deployment cut to 48 + 24 OSDs, 256 + 128 PGs, k=4
    m=2, four weight steps: every mechanism, toy numbers."""
    cell = harness.Cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["tree"]["fanouts"] = [2, 2, 3, 4]
    cell.config["osd_state"]["out"] = 2
    cell.config["expansion"].update(hosts_per_rack=3, osds_per_host=4,
                                    steps=4)
    rep, ec = cell.config["pools"]
    rep["pg_num"], ec["pg_num"] = 256, 128
    ec.update(k=4, m=2, size=6)
    cell.traffic.update(trace_epochs=2)
    return cell


def rehearse(seed: int = 7, traced: bool = False, monkeypatch=None) -> dict:
    from ceph_tpu.mon import pg_mapping

    harness.build_native()
    if monkeypatch is not None:
        # toy pools are under the program's fused threshold
        monkeypatch.setattr(pg_mapping, "FUSED_MIN_LANES", 64)
    return bench_run.run_cell(toy_cell(), seed, 1.0, traced, bm_toy.CPU)


@pytest.fixture(scope="module")
def sound():
    mp = pytest.MonkeyPatch()
    try:
        yield rehearse(monkeypatch=mp)
    finally:
        mp.undo()


def test_sound_rehearsal_is_correct_and_prints_no_metric(sound):
    assert sound["rehearsal"] and "metrics" not in sound
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0


def test_the_window_is_whole_cycles_of_the_same_epochs(sound):
    facts = sound["facts"]
    cycle = 2 * 4                               # four steps up, four down
    assert facts["window.epochs"] == facts["run.ops"] == sound["attempted"]
    assert facts["window.epochs"] % cycle == 0
    w = {k.removeprefix("window.placement_cache."): v
         for k, v in facts.items()
         if k.startswith("window.placement_cache.")}
    assert w["bulk_recomputes"] == facts["window.epochs"]
    assert w["fused_pools"] == w["fused_launches"] == 2 * w["bulk_recomputes"]
    assert w["scalar_pools"] == 0 and w.get("fused_declined", 0) == 0
    assert w["delta_pgs"] > 0 and w["indep_passes"] >= w["bulk_recomputes"]


def test_a_weight_step_compiles_nothing_in_the_window(sound):
    facts = sound["facts"]
    assert facts["run.compiles_in_window"] == 0
    assert facts["window.placement_cache.programs_built"] == 0
    assert facts["window.launches_reused"] == facts["window.fused_launches"]


def test_the_stage_facts_are_there_and_add_up_to_the_epochs(sound):
    facts = sound["facts"]
    stages = [facts[f"window.{k}_s"] for k in ("launch", "ingest", "delta",
                                               "rest")]
    assert all(s > 0 for s in stages)
    assert sum(stages) <= facts["run.window_s"]
    assert facts["check.pgs_differing"] == 0
    assert facts["check.delta_differing"] == 0
    assert facts["check.stale_tables"] == 0
    assert facts["config.osd_state.out"] == 2


@pytest.mark.parametrize("metric", [m for m in METRICS
                                    if m != "crush_indep_device_share"])
def test_the_counter_metrics_read_the_rehearsals_facts(sound, metric):
    spec = harness.layer_metric(metric)
    from benchmark.readers import ratio
    value = ratio.read(spec["spec"], sound["facts"])
    assert value is not None and value >= 0
    if metric == "crush_program_reuse_share":
        assert value == 100.0


@pytest.mark.parametrize("seed", [1, 2147641999])
def test_the_seed_draws_the_start_and_the_checked_epochs(seed, monkeypatch,
                                                         capsys):
    res = rehearse(seed=seed, monkeypatch=monkeypatch)
    assert res["correct"] is True and res["failed"] == 0
    out = capsys.readouterr().out
    assert "pgs_differing 0 (limit 0) of 768" in out    # 2 x (256 + 128)
    assert "delta_differing 0 (limit 0)" in out
    assert "compiles_in_window 0 (must be 0)" in out


def test_traced_rehearsal_marks_whole_epochs_and_lists_the_sections(
        monkeypatch, capsys):
    res = rehearse(traced=True, monkeypatch=monkeypatch)
    assert res["correct"] is True and res["failed"] == 0
    assert res["facts"]["slice.epochs"] == 2
    out = capsys.readouterr().out
    assert "2 x placement.apply" in out
    for name in ("placement.launch", "placement.ingest", "placement.delta",
                 "placement.pps", "device_wait.crush"):
        assert f"  {name} " in out, name


@pytest.mark.parametrize("pool", [1, 2], ids=["replicated", "erasure"])
def test_a_flipped_table_entry_comes_out_not_correct(pool, monkeypatch,
                                                     capsys):
    """One entry of every table the timed path builds, changed after
    the build: the comparison against the reference finds it."""
    from ceph_tpu.mon.pg_mapping import PGMapping

    build = PGMapping.build.__func__

    def flipped(cls, osdmap, **kw):
        pm = build(cls, osdmap, **kw)
        row = list(pm._up[pool][5])
        row[1] = row[1] ^ 1
        pm._up[pool][5] = row
        return pm

    monkeypatch.setattr(PGMapping, "build", classmethod(flipped))
    res = rehearse(monkeypatch=monkeypatch)
    assert res["correct"] is False
    assert res["attempted"] > 0 and res["failed"] == 0
    out = capsys.readouterr().out
    assert "pgs_differing 2 (limit 0)" in out           # one PG, two epochs
    assert res["facts"]["check.delta_differing"] == 0   # flipped on both sides


def test_a_wrong_delta_comes_out_not_correct(monkeypatch):
    from ceph_tpu.mon.pg_mapping import PGMapping

    diff = PGMapping._diff
    monkeypatch.setattr(PGMapping, "_diff",
                        lambda self, prev: diff(self, prev)[1:])
    res = rehearse(monkeypatch=monkeypatch)
    assert res["correct"] is False
    assert res["facts"]["check.pgs_differing"] == 0
    assert res["facts"]["check.delta_differing"] == 2


def test_constant_tables_stop_the_cell_before_anything_compiles(
        monkeypatch):
    """A program whose mapper is no pytree of device tables (the parent
    commit's): ``HarnessError`` from the first line of ``run``."""
    import ceph_tpu.crush.vectorized as V

    class Constant:
        def __init__(self, crush_map, ruleno):
            self.map = crush_map

    meter = harness.CompileMeter()
    monkeypatch.setattr(V, "VectorCrush", Constant)
    with pytest.raises(harness.HarnessError, match="constants"):
        crush_expand.run(toy_cell(), 1, 1.0, False, meter)
    assert meter.programs == 0


def test_a_program_without_the_commands_stops_the_cell(monkeypatch):
    import ceph_tpu.crush.builder as B

    monkeypatch.delattr(B, "crush_command")
    with pytest.raises(harness.HarnessError, match="osd crush command"):
        crush_expand.require_program()


# -- the manifest --------------------------------------------------------------

def test_the_cell_and_its_configuration_are_in_the_manifest():
    cell = {w["name"]: w for w in M["workloads"]}[CELL]
    assert cell == {**cell, "config": "crush_1000osd_ec_expand",
                    "traffic": "expand_epochs", "chips": 1}
    cfg = {c["name"]: c for c in M["configs"]}["crush_1000osd_ec_expand"]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    body = json.loads((harness.ROOT / cfg["file"]).read_text())
    assert body["architecture"] is None and body["reduced"] == {}
    assert len(body["guarantees"]) >= 4
    assert sum(p["pg_num"] for p in body["pools"]) == 24576
    e2e = {e["name"]: e for e in M["end_to_end"]}
    assert CELL in e2e["op_p95_ms"]["workloads"]
    c = harness.Cell(CELL)
    assert {"setup_s", "op_p95_ms"} <= set(c.end_to_end)
    assert c.driver() is crush_expand


@pytest.mark.parametrize("metric", METRICS)
def test_metric_file_agrees_with_its_manifest_entry(metric):
    entry = {p["name"]: p for p in M["per_layer"]}[metric]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == [CELL] and entry["moves"] == "op_p95_ms"
    spec = harness.layer_metric(metric)
    for key, val in entry.items():
        assert spec[key] == val, key
    assert set(spec) - set(entry) == {"reader", "spec", "what"}
    if metric == "crush_indep_device_share":
        assert (entry["source"], spec["reader"]) == ("device_trace",
                                                     "scope_time")
        assert spec["spec"] == {"scope": "crush_indep"}
    else:
        assert (entry["source"], spec["reader"]) == ("program_counter",
                                                     "ratio")
    assert entry["layer"] == (
        TABLE if metric.split(".")[-1] in ("ingest", "delta", "rest")
        else MAPPER)


def test_the_cells_metrics_are_these_six_and_the_manifest_has_room():
    assert sorted(harness.Cell(CELL).per_layer) == sorted(METRICS)
    assert len(M["per_layer"]) <= 128
    assert len(json.dumps(M)) < 64 * 1024
    layers = {p["layer"] for p in M["per_layer"]}
    assert {TABLE, MAPPER} <= layers
