"""The readers the scrub cell brought: ``layer_time`` (``span_time``
with the layers named by the metric's spec, per section started or per
a fact), ``scrub_stage`` (a chunk's length by stage, from the spans the
driver took at the window's close) and ``scrub_roofline`` with
``work_scrub.py``, on hand-made facts, traces and spans; the cell's
metric files against the manifest."""

from __future__ import annotations

import json

import pytest

import bm_toy  # noqa: F401
from benchmark import harness, work, work_scrub
from benchmark.readers import (layer_time, program_ms_per_launch, ratio,
                               scrub_roofline, scrub_stage, span_time)
from benchmark.reference import scrub as ref
from test_bm_readers import span, synthetic

CELL = "rs_k8m3_scrub_write_4m"
TRACED = {"trace.window_s": 1.0, "trace.busy_s": 0.001}
PER_OP = "client.complete"
LAYERS = ["client.", "wire.", "osd_op.", "store.", "batcher.",
          "device_wait.", "scrub."]
MIB = 1 << 20


# -- scrub_stage ---------------------------------------------------------------

def chunk(trace, t0, lock, digest, maps_after, compare, repair, after,
          **tags):
    """One chunk's spans: the root, the maps with the primary's digest
    nested in it, the compare, a repair unless ``repair`` is None."""
    a = t0 + lock
    b = a + digest
    c = b + maps_after
    d = c + compare
    e = d + (repair or 0.0)
    root = span(trace, "scrub.chunk", t0, e + after)
    root["tags"] = dict({"objects": 5, "bytes": 55 * 524288,
                         "blocked_writes": 0}, **tags)
    maps = span(trace, "scrub.maps", a, c, root["span_id"])
    out = [root, maps,
           span(trace, "scrub.digest", a, b, maps["span_id"]),
           span(trace, "scrub.compare", c, d, root["span_id"])]
    if repair is not None:
        out.append(span(trace, "scrub.repair", d, e, root["span_id"]))
    return out


def test_whole_chunks_keeps_the_windows_chunks_with_a_whole_tree():
    spans = (chunk("a", 100.0, 0.01, 0.04, 0.15, 0.001, 0.3, 0.0)
             + chunk("b", 101.0, 0.0, 0.02, 0.1, 0.001, None, 0.001)
             # ended before the window opened, and after it closed
             + chunk("c", 10.0, 0.0, 0.02, 0.1, 0.001, None, 0.0)
             + chunk("d", 149.95, 0.0, 0.02, 0.1, 0.001, None, 0.0)
             # a ring dropped its digest; another its compare
             + [s for s in chunk("e", 102.0, 0.0, 0.02, 0.1, 0.001, None,
                                 0.0) if s["name"] != "scrub.digest"]
             + [s for s in chunk("f", 103.0, 0.0, 0.02, 0.1, 0.001, None,
                                 0.0) if s["name"] != "scrub.compare"]
             # unfinished; and the scrub's root and reservation, no chunk
             + [span("g", "scrub.chunk", 104.0, None),
                span("h", "pg.scrub", 99.0, 105.0),
                span("h", "scrub.reserve", 99.0, 99.5)])
    chunks, partial = scrub_stage.whole_chunks(spans, 50.0, 150.0)
    assert sorted(c["scrub.chunk"]["trace_id"] for c in chunks) \
        == ["a", "b"]
    assert partial == 2
    by = {c["scrub.chunk"]["trace_id"]: c for c in chunks}
    assert "scrub.repair" in by["a"] and "scrub.repair" not in by["b"]
    assert len(scrub_stage.whole_chunks(spans, 0.0, 200.0)[0]) == 4
    assert scrub_stage.whole_chunks([], 0.0, 1.0) == ([], 0)


def test_scrub_stages_read_the_fact_and_add_up(monkeypatch, capsys):
    one = chunk("a", 100.0, 0.01, 0.04, 0.15, 0.002, 0.3, 0.0,
                blocked_writes=2)
    two = chunk("b", 101.0, 0.0, 0.02, 0.1, 0.001, None, 0.001)
    facts = {"spans.scrub": one + two, "run.wall_open": 50.0,
             "run.wall_close": 150.0}
    monkeypatch.setattr(scrub_stage, "_reported", False)
    got = {stage: scrub_stage.read({"stage": stage}, facts)
           for stage in scrub_stage.STAGES}
    # rest: the lock and the listing, the repair, what follows it
    assert got == pytest.approx({"digest": 30.0, "maps": 125.0,
                                 "compare": 1.5, "rest": 155.5})
    assert sum(got.values()) == pytest.approx((502 + 122) / 2)
    said = capsys.readouterr().out
    assert "2 with a whole span tree ended in the window" in said
    assert "10 objects" in said and "2 writes waited" in said
    assert "1 chunks repaired" in said
    # no fact, no bounds, no chunk in the window: nothing to read
    assert scrub_stage.read({"stage": "maps"}, {}) is None
    assert scrub_stage.read({"stage": "maps"},
                            {"spans.scrub": one}) is None
    assert scrub_stage.read({"stage": "maps"}, dict(
        facts, **{"run.wall_open": 200.0, "run.wall_close": 300.0})) is None
    # a parent's rings hold no such span
    assert scrub_stage.read({"stage": "maps"}, dict(
        facts, **{"spans.scrub": [span("x", "osd.do_op", 100.0,
                                       101.0)]})) is None


# -- layer_time ----------------------------------------------------------------

HOST = [("benchmark_slice", 1000, 1000),
        ("wire.deliver", 1000, 100),
        ("scrub.list", 1150, 40),
        ("scrub.digest_host", 1200, 200),
        ("store.read", 1250, 50),             # nested in the host digest
        ("scrub.digest_device", 1450, 30),
        ("batcher.dispatch", 1500, 20),
        ("device_wait.materialize", 1550, 30),
        ("scrub.digest_device", 1600, 10),    # the fold after the launch
        ("scrub.compare", 1650, 5),
        ("recovery.payload", 1700, 60),       # a repair: nobody's layer here
        ("scrub.repair", 1720, 10),           # ... but this inside it is
        ("client.complete", 1900, 10),
        ("client.complete", 1950, 10)]


def scrub_trace(tmp_path):
    return synthetic(tmp_path, {
        "/host:CPU": {"loop": HOST},
        "/device:TPU:0": {"XLA Modules": [("jit_crc32c_shards(1)", 1550,
                                           20)],
                          "XLA Ops": [("%fusion = fusion()", 1550, 20)]}})


def spec_of(**kw):
    return dict({"layers": LAYERS}, **kw)


@pytest.mark.parametrize("spec,want_us", [
    (spec_of(prefix="scrub.", per=PER_OP), 122.5),   # 40+150+40+5+10, /2
    (spec_of(prefix="wire.", per=PER_OP), 50.0),
    (spec_of(prefix="store.", per=PER_OP), 25.0),
    (spec_of(prefix="batcher.", per=PER_OP), 10.0),
    (spec_of(prefix="device_wait.", per=PER_OP), 15.0),
    (spec_of(prefix="client.", per=PER_OP), 10.0),
    (spec_of(prefix="osd_op.", per=PER_OP), 0.0),
    # 1000 - (100+40+200+30+20+30+10+5+10+20) = 535: the repair's
    # recovery.payload outside scrub.repair is uncovered here
    (spec_of(prefix="", invert=True, per=PER_OP), 267.5),
    # one section of the layer, per MiB a route digested in the slice
    (spec_of(prefix="scrub.digest_host",
             per_fact="slice.scrub.bytes_digested_host",
             per_scale=1.0 / MIB), 75.0),            # 150 us / 2 MiB
    (spec_of(prefix="scrub.digest_device",
             per_fact="slice.scrub.bytes_digested_device",
             per_scale=1.0 / MIB), 80.0),            # 40 us / 0.5 MiB
])
def test_layer_time_takes_its_layers_from_the_spec(tmp_path, monkeypatch,
                                                   spec, want_us):
    path = scrub_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    facts = dict(TRACED, **{"slice.scrub.bytes_digested_host": 2 * MIB,
                            "slice.scrub.bytes_digested_device": MIB // 2})
    assert layer_time.read(spec, facts) * 1e3 == pytest.approx(want_us)


def test_the_layers_add_up_and_the_accepted_reader_keeps_its_list(
        tmp_path, monkeypatch):
    path = scrub_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    total = sum(layer_time.read(spec_of(prefix=p, per=PER_OP), TRACED)
                for p in LAYERS)
    total += layer_time.read(spec_of(prefix="", invert=True, per=PER_OP),
                             TRACED)
    assert total == pytest.approx(1.0 / 2)        # 1 ms slice, 2 writes
    # another list of layers over the same trace is another split
    with_recovery = LAYERS + ["recovery."]
    assert layer_time.read({"layers": with_recovery, "prefix": "recovery.",
                            "per": PER_OP}, TRACED) * 1e3 \
        == pytest.approx(25.0)
    # span_time does not know the layer: there its time is nobody's
    assert span_time.read({"prefix": "scrub.", "per": PER_OP},
                          TRACED) == 0.0
    assert "scrub." not in span_time.LAYERS


def test_layer_time_with_nothing_to_read_is_none(tmp_path, monkeypatch):
    spec = spec_of(prefix="scrub.", per=PER_OP)
    path = scrub_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    assert layer_time.read(spec, {}) is None               # not traced
    assert layer_time.read(spec_of(prefix="scrub.", per="device_wait.crush"),
                           TRACED) is None                 # no divisor
    by_fact = spec_of(prefix="scrub.digest_host",
                      per_fact="slice.scrub.bytes_digested_host",
                      per_scale=1.0 / MIB)
    assert layer_time.read(by_fact, TRACED) is None        # no such fact
    assert layer_time.read(by_fact, dict(
        TRACED, **{"slice.scrub.bytes_digested_host": 0})) is None
    monkeypatch.setattr(span_time, "newest_trace", lambda: None)
    assert layer_time.read(spec, TRACED) is None           # no trace


# -- the roofline and the launch's time -----------------------------------------

def test_work_scrub_counts_a_shard_in_and_four_bytes_out():
    assert work_scrub.shard_bytes(8, 4096, 4 << 20) == 512 << 10
    assert work_scrub.shard_bytes(8, 4096, (4 << 20) + 1) == (512 << 10) + 4096
    assert work_scrub.shard_bytes(2, 4096, 3 * 8192 + 100) == 4 * 4096
    assert work_scrub.digest_bytes(5, 512 << 10) == 5 * ((512 << 10) + 4)
    assert work_scrub.digest_bytes(0, 512 << 10) == 0
    # the reference's shard length is the same arithmetic, kept apart
    profile = {"k": 8, "m": 3, "stripe_unit": 4096}
    assert ref.shard_bytes(profile, 4 << 20) == 512 << 10


def test_scrub_crc_hbm_share_is_the_digested_rows_over_the_digest_programs():
    spec = harness.layer_metric("scrub_crc_hbm_share")["spec"]
    facts = {"trace.programs": {"jit_crc32c_shards": 0.002,
                                "jit_ec_encode_crc": 0.5},
             "slice.ec_batch.digest_stripes": 40,
             "slice.ec_batch.stripes": 40 + 50 * 128,
             "config.profile.k": 8, "config.profile.stripe_unit": 4096,
             "config.population.object_bytes": 4 << 20,
             "device.kind": "TPU v5 lite"}
    need = 40 * ((512 << 10) + 4)
    want = work.roofline_share(need, 819e9, 0.002)
    assert scrub_roofline.read(spec, facts) == pytest.approx(want)
    assert 0 < want < 100
    assert scrub_roofline.read(spec, {}) is None
    assert scrub_roofline.read(spec, dict(
        facts, **{"slice.ec_batch.digest_stripes": 0})) is None
    # a parent has no such program in its trace, and no such counter
    assert scrub_roofline.read(spec, dict(
        facts, **{"trace.programs": {"jit_ec_encode_crc": 0.5}})) is None
    with pytest.raises(harness.HarnessError):
        scrub_roofline.read(spec, dict(facts, **{"device.kind": "TPU v9"}))


def test_device_ms_per_launch_scrub_takes_the_digests_time_over_their_count():
    spec = harness.layer_metric("device_ms_per_launch.scrub")["spec"]
    facts = {"trace.programs": {"jit_crc32c_shards": 0.0024,
                                "jit_ec_encode_crc": 0.5},
             "slice.ec_batch.digest_launches": 12,
             "slice.ec_batch.mesh_launches": 60}
    assert program_ms_per_launch.read(spec, facts) == pytest.approx(0.2)
    assert program_ms_per_launch.read(spec, {}) is None


# -- the metric files -----------------------------------------------------------

# what PR 38 brought for the cell (less host_ms_per_op.client.under_scrub,
# retired by PR 49), in the manifest's order
SCRUB_METRICS = [
    "scrubbed_mibps", "scrub_active_share", "scrub_chunk_ms.maps",
    "scrub_chunk_ms.digest", "scrub_chunk_ms.compare", "scrub_chunk_ms.rest",
    "host_ms_per_op.wire.under_scrub",
    "host_ms_per_op.osd_op.under_scrub", "host_ms_per_op.store.under_scrub",
    "host_ms_per_op.batcher.under_scrub",
    "host_ms_per_op.device_wait.under_scrub",
    "host_ms_per_op.unsectioned.under_scrub",
    "host_ms_per_op.scrub.under_scrub", "scrub_device_digest_share",
    "scrub_thread_ms_per_mib.host", "scrub_thread_ms_per_mib.device",
    "scrub_wire_bytes_per_digested_byte", "device_ms_per_launch.scrub",
    "device_idle_share.scrub", "scrub_crc_hbm_share"]


def scrub_metrics() -> list[str]:
    return harness.Cell(CELL).per_layer


def test_the_cells_metrics_name_the_cell_and_follow_the_accepted_ones():
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    names = [p["name"] for p in manifest["per_layer"]]
    mine = scrub_metrics()
    assert set(SCRUB_METRICS) <= set(mine)
    at = [names.index(n) for n in SCRUB_METRICS]
    # in the order PR 38 appended them, after everything the benchmark had
    # then (the driver reads an entry put in the middle as an edit); how
    # many the cell lists and what a later PR appends is not pinned here
    assert at == sorted(at)
    assert at[0] > names.index("loop_max_phase_ms")
    layers = {p["layer"] for p in manifest["per_layer"] if p["name"] in mine}
    accepted = {p["layer"] for p in manifest["per_layer"]
                if p["name"] not in mine}
    assert layers - accepted == {
        "scrub (osd/scrub.py, osd/pg.py, osd/osd.py)"}
    for entry in manifest["per_layer"]:
        if entry["name"] in mine:
            spec = harness.layer_metric(entry["name"])
            assert CELL in entry["workloads"] == spec["workloads"]
            for key in ("unit", "better", "source", "layer", "moves"):
                assert spec[key] == entry[key], (entry["name"], key)
            assert entry["moves"] == (
                "op_p95_ms" if entry["name"].startswith("scrub_chunk_ms")
                else "client_mibps")
    assert len(json.dumps(manifest)) < 64 * 1024
    # by lookup: a later PR's cell or configuration trips nothing here
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    assert cells[CELL]["config"] in configs and cells[CELL]["chips"] == 1
    assert len(cells) <= 24 and len(configs) <= 24


@pytest.mark.parametrize("metric", SCRUB_METRICS)
def test_each_scrub_metric_reads_nothing_from_nothing(metric):
    """What the parent hands a reader laid over it: no fact of the
    scrub, so ``None`` and no exception, and the line leaves the metric
    out."""
    assert metric in scrub_metrics()
    spec = harness.layer_metric(metric)
    assert harness.read_layer_metrics([metric], {}) == {}
    # a traced parent run's facts: a trace, no scrub counter, no span
    facts = dict(TRACED, **{"trace.idle_s": 0.999,
                            "trace.programs": {"jit_ec_encode_crc": 0.001},
                            "device.kind": "TPU v5 lite",
                            "run.window_s": 51.0, "spans.scrub": []})
    got = harness.read_layer_metrics([metric], facts)
    if spec["reader"] == "ratio" and metric == "device_idle_share.scrub":
        assert got[metric]["value"] == pytest.approx(99.9)
    elif spec["reader"] != "layer_time":
        assert got == {}


def test_the_counter_ratios_read_the_drivers_facts():
    facts = {"run.scrub_bytes_digested": 3000 * MIB, "run.window_s": 50.0,
             "window.scrub.bytes_digested_device": 300 * MIB,
             "window.scrub.map_bytes": 600_000,
             "run.scrub_active_s": 49, "run.window_whole_s": 50}
    read = {name: ratio.read(harness.layer_metric(name)["spec"], facts)
            for name in ("scrubbed_mibps", "scrub_device_digest_share",
                         "scrub_wire_bytes_per_digested_byte",
                         "scrub_active_share")}
    assert read == pytest.approx({
        "scrubbed_mibps": 60.0, "scrub_device_digest_share": 10.0,
        "scrub_wire_bytes_per_digested_byte": 600_000 / (3000 * MIB),
        "scrub_active_share": 98.0})
