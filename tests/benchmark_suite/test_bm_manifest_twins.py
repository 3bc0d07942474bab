"""What PR 49 did to ``per_layer``: 16 entries whose reader, spec, unit,
direction and moved metric were byte for byte a write cell's own were
folded into that metric's ``workloads``, the five ``client`` entries
were retired, and 20 places came free.  (A 17th fold,
``launch_queue_ms.rmw`` into ``launch_queue_ms``, was undone in the
same PR: the check refused ``op_p95_ms`` in the overwrite cell, the
cell's tail became the per-layer ``rmw_op_p95_ms``, and a metric that
moves ``op_p95_ms`` cannot list a cell that does not report it.)

(a) every entry against its file; (b) every folded twin against the
metric that took its cell: the twin's file is gone, so its reader and
spec stand here as ``git show 2b912af:benchmark/layer_metrics/<twin>.json``
had them, and the survivor reads for that cell what they read; (c) the
manifest's limits.  Nothing here forbids a later twin: a PR that adds a
cell cannot edit a file that exists, so it can only list its own.
"""

from __future__ import annotations

import importlib
import json

import pytest

import bm_toy  # noqa: F401  (puts the repo root on sys.path)
from benchmark import harness
from benchmark.readers import span_time
from test_bm_readers import synthetic

M = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
ENTRIES = {p["name"]: p for p in M["per_layer"]}
CELLS = [w["name"] for w in M["workloads"]]

READ, RMW, BACKFILL = ("rs_k8m3_degraded_read_4m", "rbd_ec_randwrite_4k",
                       "rs_k8m3_backfill_write_4m")
WRITES = ["rs_k8m3_write_4m", "rs_k8m3_write_64k", "cauchy_k10m4_write_4m"]
WRITES_4M = ["rs_k8m3_write_4m", "cauchy_k10m4_write_4m"]


def ratio(num, den, scale=None):
    spec = {"num": num, "den": den}
    if scale is not None:
        spec["scale"] = scale
    return "ratio", spec


def span(layer):
    return "span_time", {"prefix": f"{layer}.", "per": "client.complete"}


IDLE = ratio(["trace.idle_s"], ["trace.window_s"], 100.0)
PER_LAUNCH = ratio(["trace.busy_s"], ["slice.ec_batch.mesh_launches"], 1000.0)
STRIPES = ratio(["window.ec_batch.stripes"], ["window.ec_batch.batches"])


def per_batch(counter):
    return ratio([f"window.ec_batch.{counter}"], ["window.ec_batch.batches"],
                 0.001)


# survivor -> (the cells it listed at 2b912af, unit, moves,
#              [(twin, the twin's one cell, the twin's reader and spec)])
# in the order the twins' cells were appended
FOLDS = {
    "device_idle_share.store": (WRITES, "%", "client_mibps", [
        ("device_idle_share.read", READ, IDLE),
        ("device_idle_share.rmw", RMW, IDLE),
        ("device_idle_share.backfill", BACKFILL, IDLE)]),
    "device_ms_per_launch.store": (WRITES, "ms", "client_mibps", [
        ("device_ms_per_launch.read", READ, PER_LAUNCH),
        ("device_ms_per_launch.rmw", RMW, PER_LAUNCH)]),
    "stripes_per_launch": (WRITES, "stripes", "client_mibps", [
        ("stripes_per_launch.read", READ, STRIPES),
        ("stripes_per_launch.rmw", RMW, STRIPES)]),
    "launch_queue_ms": (WRITES, "ms", "op_p95_ms", [
        ("launch_queue_ms.read", READ, per_batch("queue_wait_us"))]),
    "launch_materialize_ms": (WRITES, "ms", "client_mibps", [
        ("launch_materialize_ms.read", READ, per_batch("materialize_us"))]),
    "launch_overlap_ms": (WRITES, "ms", "client_mibps", [
        ("launch_overlap_ms.read", READ, per_batch("overlap_us"))]),
    "pad_waste_share": (WRITES, "%", "client_mibps", [
        ("pad_waste_share.read", READ, ratio(
            ["window.ec_batch.pad_waste_bytes"],
            ["window.ec_batch.mesh_padded_stripes", "config.profile.k",
             "config.profile.stripe_unit"], 100.0))]),
    **{f"host_ms_per_op.{layer}": (WRITES_4M, "ms/op", "client_mibps", [
        (f"host_ms_per_rmw.{layer}", RMW, span(layer))])
       for layer in ("wire", "osd_op", "store", "batcher", "device_wait")},
}
PAIRS = [(survivor, twin, cell, reader, spec)
         for survivor, (_, _, _, twins) in FOLDS.items()
         for twin, cell, (reader, spec) in twins]
RETIRED = ["host_ms_per_op.client", "host_ms_per_read.client",
           "host_ms_per_rmw.client", "host_ms_per_op.client.backfill",
           "host_ms_per_op.client.under_scrub"]

# the 106 names of 2b912af that PR 49 left, in the order they stood
# there (``launch_queue_ms.rmw`` is the 107th, ``rmw_op_p95_ms`` new)
AT_PR_49 = """
host_cpu_ms_per_op stripes_per_launch pad_waste_share
device_ms_per_launch.store encode_hbm_share device_idle_share.store
crush_device_ms_per_launch device_idle_share.crush host_ms_per_op.wire
host_ms_per_op.osd_op host_ms_per_op.store host_ms_per_op.batcher
host_ms_per_op.device_wait host_ms_per_op.unsectioned op_wait_ms.to_osd
op_wait_ms.encode op_wait_ms.commit op_wait_ms.reply launch_queue_ms
launch_overlap_ms launch_materialize_ms crc_device_share
crush_straw2_device_share op_wait_ms.prepare gf_encode_device_share
crush_device_wait_ms_per_launch decode_hbm_share gf_decode_device_share
host_ms_per_read.wire host_ms_per_read.osd_read host_ms_per_read.store
host_ms_per_read.batcher host_ms_per_read.device_wait
host_ms_per_read.unsectioned read_wait_ms.to_osd read_wait_ms.gather
read_wait_ms.decode read_wait_ms.reply read_wait_ms.rest
reconstructing_read_share subread_bytes_per_read_byte shard_cache_hit_share
rmw_wait_ms.to_osd rmw_wait_ms.read_old rmw_wait_ms.read_parity
rmw_wait_ms.launch rmw_wait_ms.commit rmw_wait_ms.reply
host_ms_per_rmw.unsectioned rmw_delta_share extent_cache_hit_share
subread_bytes_per_written_byte rmw_hbm_share backfill_wait_ms.gather
backfill_wait_ms.decode backfill_wait_ms.push backfill_wait_ms.rest
host_ms_per_op.wire.backfill host_ms_per_op.osd_op.backfill
host_ms_per_op.store.backfill host_ms_per_op.batcher.backfill
host_ms_per_op.device_wait.backfill host_ms_per_op.unsectioned.backfill
host_ms_per_op.recovery.backfill recovered_mibps
repair_read_bytes_per_shipped_byte backfill_dirty_push_share
backfill_active_share stripes_per_launch.recover launch_queue_ms.recover
device_ms_per_launch.recover recover_hbm_share loop_ms_per_op.select
loop_ms_per_op.recv loop_ms_per_op.send loop_ms_per_op.steps loop_busy_share
loop_offcpu_share loop_max_phase_ms scrubbed_mibps scrub_active_share
scrub_chunk_ms.maps scrub_chunk_ms.digest scrub_chunk_ms.compare
scrub_chunk_ms.rest host_ms_per_op.wire.under_scrub
host_ms_per_op.osd_op.under_scrub host_ms_per_op.store.under_scrub
host_ms_per_op.batcher.under_scrub host_ms_per_op.device_wait.under_scrub
host_ms_per_op.unsectioned.under_scrub host_ms_per_op.scrub.under_scrub
scrub_device_digest_share scrub_thread_ms_per_mib.host
scrub_thread_ms_per_mib.device scrub_wire_bytes_per_digested_byte
device_ms_per_launch.scrub device_idle_share.scrub scrub_crc_hbm_share
expand_epoch_ms.launch expand_epoch_ms.ingest expand_epoch_ms.delta
expand_epoch_ms.rest crush_indep_device_share crush_program_reuse_share
registry_gf_hbm_share
""".split()

# facts no two of which give the same quotient, and none 0
FACTS = {"trace.window_s": 2.0, "trace.idle_s": 1.75, "trace.busy_s": 0.25,
         "slice.ec_batch.mesh_launches": 80,
         "window.ec_batch.stripes": 130, "window.ec_batch.batches": 100,
         "window.ec_batch.queue_wait_us": 250_000,
         "window.ec_batch.materialize_us": 70_000,
         "window.ec_batch.overlap_us": 410_000,
         "window.ec_batch.pad_waste_bytes": 3 * 32768,
         "window.ec_batch.mesh_padded_stripes": 144,
         "config.profile.k": 8, "config.profile.stripe_unit": 4096}

# a marked millisecond of the loop's thread with every layer in it
HOST = [("benchmark_slice", 1000, 1000),
        ("client.build", 1010, 20),
        ("wire.encode", 1050, 70),
        ("osd_op.rmw_merge", 1150, 110),
        ("store.read", 1160, 30),                 # nested
        ("batcher.dispatch", 1300, 130),
        ("device_wait.materialize", 1450, 170),
        ("osd_op.stamp", 1650, 50),
        ("wire.deliver", 1720, 90),
        ("client.complete", 1850, 10),
        ("client.complete", 1900, 10)]


@pytest.fixture
def trace(tmp_path, monkeypatch):
    path = synthetic(tmp_path, {
        "/host:CPU": {"loop": HOST},
        "/device:TPU:0": {
            "XLA Modules": [("jit_ec_rmw(1)", 1300, 100)],
            "XLA Ops": [("%fusion.2 = fusion()", 1300, 100)]}})
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    return path


# -- (a) an entry and its file ------------------------------------------------

@pytest.mark.parametrize("metric", list(ENTRIES))
def test_entry_and_file_say_the_same(metric):
    entry = ENTRIES[metric]
    spec = harness.layer_metric(metric)
    assert spec["name"] == metric
    for key in ("workloads", "unit", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    # the files of before PR 31 state no direction; where one does, the same
    assert spec.get("better", entry["better"]) == entry["better"]
    assert isinstance(entry["workloads"], list) and entry["workloads"]
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    for cell in entry["workloads"]:
        assert cell in CELLS
        assert metric in harness.Cell(cell, M).per_layer


def test_every_file_has_an_entry_and_every_entry_a_file():
    files = {p.stem for p in (harness.BENCH / "layer_metrics").glob("*.json")}
    assert files == set(ENTRIES)
    assert len(files) == len(M["per_layer"])       # no name twice


# -- (b) a folded twin and the metric that took its cell ------------------------

@pytest.mark.parametrize("survivor,twin,cell,reader,spec", PAIRS,
                         ids=[f"{p[1]}->{p[0]}" for p in PAIRS])
def test_survivor_reads_for_the_cell_what_the_twin_read(
        survivor, twin, cell, reader, spec, trace):
    assert twin not in ENTRIES
    assert not (harness.BENCH / "layer_metrics" / f"{twin}.json").exists()
    had, unit, moves, twins = FOLDS[survivor]
    kept = harness.layer_metric(survivor)
    # the twin's file differed from this one in name, workloads and what
    assert (kept["reader"], kept["spec"]) == (reader, spec)
    assert (kept["unit"], kept["moves"]) == (unit, moves)
    assert ENTRIES[survivor]["unit"] == unit
    assert ENTRIES[survivor]["moves"] == moves
    # the cells it had, then the twins' in the order they were folded
    want = had + [c for _, c, _ in twins]
    assert ENTRIES[survivor]["workloads"][:len(want)] == want
    assert kept["workloads"][:len(want)] == want
    assert survivor in harness.Cell(cell, M).per_layer
    old = importlib.import_module(f"benchmark.readers.{reader}").read(
        spec, dict(FACTS))
    new = harness.read_layer_metrics([survivor], dict(FACTS))
    assert old is not None and old > 0
    assert new == {survivor: {"value": old, "unit": unit}}   # to the bit
    # nothing to read still leaves the metric out of the line
    assert harness.read_layer_metrics([survivor], {}) == {}


def test_the_layers_of_the_overwrite_cell_add_up_less_the_client(trace):
    """The five folded layers and the cell's own ``.unsectioned`` are
    the slice per finished overwrite less the ``client.*`` sections,
    which stay in the trace and are listed by no metric."""
    names = [n for n in harness.Cell(RMW, M).per_layer
             if n.startswith(("host_ms_per_op.", "host_ms_per_rmw."))]
    assert sorted(names) == sorted(
        [f"host_ms_per_op.{layer}" for layer in (
            "wire", "osd_op", "store", "batcher", "device_wait")]
        + ["host_ms_per_rmw.unsectioned"])
    got = harness.read_layer_metrics(names, dict(FACTS))
    sl = span_time.load(trace)
    client = bm_toy.client_ms_per_op(sl)
    assert client == pytest.approx((20 + 10 + 10) / 2e3)
    assert sum(m["value"] for m in got.values()) == pytest.approx(
        1e3 * (sl["hi"] - sl["lo"]) / 2 - client)
    assert got["host_ms_per_op.osd_op"]["value"] == pytest.approx(
        (110 - 30 + 50) / 2e3)
    assert got["host_ms_per_op.store"]["value"] == pytest.approx(30 / 2e3)
    # what no section covers does not hold the client's 40 us
    assert got["host_ms_per_rmw.unsectioned"]["value"] == pytest.approx(
        (1000 - 20 - 70 - 110 - 130 - 170 - 50 - 90 - 20) / 2e3)


@pytest.mark.parametrize("metric", RETIRED)
def test_retired_client_entry_is_gone_and_its_layer_is_still_a_layer(metric):
    """No reader was edited: ``client.`` is a layer of the reader of the
    cell's ``.unsectioned`` still, so that took none of the client's
    time in."""
    assert metric not in ENTRIES
    assert not (harness.BENCH / "layer_metrics" / f"{metric}.json").exists()
    kept = harness.layer_metric(metric.replace(".client", ".unsectioned"))
    reader = importlib.import_module(f"benchmark.readers.{kept['reader']}")
    assert "client." in (kept["spec"].get("layers") or reader.LAYERS)


# -- (c) the limits -------------------------------------------------------------

def test_the_manifest_is_inside_its_limits_and_has_room():
    names = [p["name"] for p in M["per_layer"]]
    assert len(names) <= 128
    assert len(AT_PR_49) == 106 == len(set(AT_PR_49))
    # looked up, and in the order they stood; what a later PR appends
    # stands among them or behind them and trips nothing
    assert [n for n in names if n in set(AT_PR_49)] == AT_PR_49
    assert not set(RETIRED) & set(names)
    assert not {twin for _, twin, *_ in PAIRS} & set(names)
    assert len(PAIRS) == 16 and len(names) >= 108
    assert len(json.dumps(M)) < 64 * 1024
    assert 1 <= len(M["workloads"]) <= 24 and 1 <= len(M["configs"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16
    # an entry without ``workloads`` has to be reported by every cell that
    # reports the metric it moves, a later PR's too: none is open like that
    assert all(p.get("workloads") for p in M["per_layer"])



# -- the overwrite cell's tail, after the check refused it end to end ------------

def test_the_overwrite_cells_tail_is_a_per_layer_metric():
    """``op_p95_ms`` spread by 18.0 % and 12.2 % of its median in the
    check's two sets of one tree (PR 49): over half of the largest
    bound.  The cell keeps ``client_mibps`` end to end; its tail is
    ``rmw_op_p95_ms``, read from the driver's fact, and whatever of the
    cell moved ``op_p95_ms`` moves ``client_mibps``."""
    e2e = {e["name"]: e for e in M["end_to_end"]}
    assert RMW not in e2e["op_p95_ms"]["workloads"]
    assert RMW in e2e["client_mibps"]["workloads"]
    assert harness.Cell(RMW, M).end_to_end == {"client_mibps": "MiB/s",
                                               "setup_s": "s"}
    assert ENTRIES["rmw_op_p95_ms"]["workloads"] == [RMW]
    assert ENTRIES["rmw_op_p95_ms"]["moves"] == "client_mibps"
    got = harness.read_layer_metrics(["rmw_op_p95_ms"],
                                     {"run.op_p95_ms": 2236.77})
    assert got == {"rmw_op_p95_ms": {"value": 2236.77, "unit": "ms"}}
    assert harness.read_layer_metrics(["rmw_op_p95_ms"], {}) == {}
    for name in harness.Cell(RMW, M).per_layer:
        assert ENTRIES[name]["moves"] == "client_mibps", name


def test_the_unfolded_twin_reads_what_it_read():
    """``launch_queue_ms.rmw`` stands as 2b912af had it but for the
    metric it moves, and ``launch_queue_ms`` no longer lists the cell."""
    twin = harness.layer_metric("launch_queue_ms.rmw")
    kept = harness.layer_metric("launch_queue_ms")
    assert (twin["reader"], twin["spec"]) == per_batch("queue_wait_us")
    assert (twin["reader"], twin["spec"]) == (kept["reader"], kept["spec"])
    assert twin["workloads"] == [RMW] and RMW not in kept["workloads"]
    assert (twin["moves"], kept["moves"]) == ("client_mibps", "op_p95_ms")
    assert harness.read_layer_metrics(["launch_queue_ms.rmw"], dict(FACTS)) \
        == {"launch_queue_ms.rmw": {"value": 2.5, "unit": "ms"}}


def test_the_placement_rate_is_held_to_three_percent():
    """1 % was under twice what the check's runs of one tree spread by
    (1.06 % and 0.57 % of the median: PR 49)."""
    e2e = {e["name"]: e for e in M["end_to_end"]}
    assert e2e["mappings_per_s"]["bound"] == 0.03
    assert e2e["mappings_per_s"]["workloads"] == ["crush_1000osd_bulk"]
