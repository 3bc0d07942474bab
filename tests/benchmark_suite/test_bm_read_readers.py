"""The readers and the byte count the read cell brought: ``work_read``
(what a reconstruction moves), ``decode_roofline``, ``read_span_time``
(``span_time`` with the ``osd_read.`` layer) and ``read_stage`` (a
read's latency by stage, from the span rings), on hand-made facts,
traces and rings."""

from __future__ import annotations

import pytest

import bm_toy  # noqa: F401
from benchmark import harness, work, work_read
from benchmark.readers import (decode_roofline, read_span_time, read_stage,
                               span_time)
from test_bm_readers import span, synthetic

TRACED = {"trace.window_s": 1.0, "trace.busy_s": 0.001}
PER_OP = "client.complete"
ROOFLINE = {"stripes": "slice.ec_batch.stripes",
            "rows": "config.failure.osds_down", "programs": "^jit_ec_decode"}


@pytest.mark.parametrize("k,rows,unit,stripes,want", [
    (8, 1, 4096, 128, 4_718_592),        # one 4 MiB object, one lost chunk
    (8, 3, 4096, 128, 5_767_168),        # what the program computes beside it
    (10, 1, 4096, 103, 4_640_768),
    (2, 1, 4096, 0, 0),
])
def test_decode_bytes(k, rows, unit, stripes, want):
    assert work_read.decode_bytes(k, rows, unit, stripes) == want


def test_decode_roofline_counts_what_the_reads_needed():
    facts = {"trace.programs": {"jit_ec_decode": 0.002,
                                "jit_ec_encode_crc": 0.5},
             "slice.ec_batch.stripes": 1280, "config.failure.osds_down": 1,
             "config.profile.k": 8, "config.profile.stripe_unit": 4096,
             "device.kind": "TPU v5 lite"}
    want = work.roofline_share(10 * 4_718_592, 819e9, 0.002)
    assert decode_roofline.read(ROOFLINE, facts) == pytest.approx(want)
    assert 0 < want < 100
    # no decode ran in the slice, no slice, nothing lost: nothing to read
    assert decode_roofline.read(ROOFLINE, dict(
        facts, **{"trace.programs": {"jit_ec_encode_crc": 0.5}})) is None
    assert decode_roofline.read(ROOFLINE, dict(
        facts, **{"slice.ec_batch.stripes": 0})) is None
    assert decode_roofline.read(ROOFLINE, {}) is None
    with pytest.raises(harness.HarnessError):
        decode_roofline.read(ROOFLINE, dict(facts, **{"device.kind": "x"}))


HOST = [("benchmark_slice", 1000, 1000),
        ("wire.deliver", 1000, 100),
        ("osd_read.verify", 1200, 200),
        ("wire.crc", 1250, 50),               # nested in the verify
        ("store.read", 1450, 50),
        ("osd_read.assemble", 1600, 100),
        ("batcher.dispatch", 1750, 20),
        ("device_wait.materialize", 1800, 30),
        ("client.complete", 1900, 10),
        ("client.complete", 1950, 10)]


def read_trace(tmp_path):
    return synthetic(tmp_path, {
        "/host:CPU": {"loop": HOST},
        "/device:TPU:0": {"XLA Modules": [("jit_ec_decode(1)", 1800, 20)],
                          "XLA Ops": [("%fusion = fusion()", 1800, 20)]}})


@pytest.mark.parametrize("spec,want_us", [
    ({"prefix": "osd_read.", "per": PER_OP}, 125.0),   # 150 + 100, 2 reads
    ({"prefix": "wire.", "per": PER_OP}, 75.0),
    ({"prefix": "store.", "per": PER_OP}, 25.0),
    ({"prefix": "batcher.", "per": PER_OP}, 10.0),
    ({"prefix": "device_wait.", "per": PER_OP}, 15.0),
    ({"prefix": "client.", "per": PER_OP}, 10.0),
    ({"prefix": "", "invert": True, "per": PER_OP}, 240.0),
])
def test_read_span_time_knows_the_read_layer(tmp_path, monkeypatch, spec,
                                             want_us):
    path = read_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    assert read_span_time.read(spec, TRACED) * 1e3 == pytest.approx(want_us)


def test_read_layers_add_up_and_span_time_leaves_the_read_layer_out(
        tmp_path, monkeypatch):
    path = read_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    total = sum(read_span_time.read({"prefix": p, "per": PER_OP}, TRACED)
                for p in read_span_time.LAYERS)
    total += read_span_time.read(
        {"prefix": "", "invert": True, "per": PER_OP}, TRACED)
    assert total == pytest.approx(1.0 / 2)        # 1 ms slice, 2 reads
    # the reader the write cells use does not know the layer: there
    # its time is nobody's
    assert span_time.read({"prefix": "osd_read.", "per": PER_OP},
                          TRACED) == 0.0
    assert span_time.read({"prefix": "", "invert": True, "per": PER_OP},
                          TRACED) * 1e3 == pytest.approx(240.0 + 125.0)


def test_read_span_time_with_nothing_to_read_is_none(tmp_path, monkeypatch):
    spec = {"prefix": "osd_read.", "per": PER_OP}
    path = read_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    assert read_span_time.read(spec, {}) is None
    assert read_span_time.read(dict(spec, per="device_wait.crush"),
                               TRACED) is None
    (tmp_path / "bare").mkdir()
    bare = synthetic(tmp_path / "bare", {"/host:CPU": {"loop": [
        ("benchmark_slice", 0, 100), ("PjitFunction(x)", 10, 5)]}})
    monkeypatch.setattr(span_time, "newest_trace", lambda: bare)
    assert read_span_time.read(spec, TRACED) is None
    monkeypatch.setattr(span_time, "newest_trace", lambda: None)
    assert read_span_time.read(spec, TRACED) is None


def read_op(trace, t0, to_osd, before, gather, decode, after, reply):
    a = t0 + to_osd
    b = a + before
    c = b + gather
    d = c + (decode or 0.0)
    e = d + after
    spans = [span(trace, "client.osd_op", t0, e + reply),
             span(trace, "osd.do_op", a, e),
             span(trace, "ec.gather", b, c)]
    if decode is not None:
        spans.append(span(trace, "ec.decode", c, d))
    return spans


def test_read_stage_keeps_whole_single_attempt_reads_of_the_window():
    dumps = (read_op("t1", 100.0, 0.1, 0.05, 0.5, 0.2, 0.05, 0.1)
             + read_op("t2", 101.0, 0.3, 0.0, 0.4, None, 0.1, 0.2)
             # before the window
             + read_op("t0", 10.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0)
             # sent again: two osd.do_op
             + read_op("t3", 102.0, 0.1, 0.0, 0.5, None, 0.0, 0.1)
             + [span("t3", "osd.do_op", 102.5, 102.6)]
             # a ring dropped its root
             + read_op("t4", 102.0, 0.1, 0.0, 0.5, 0.1, 0.0, 0.1)[1:]
             # unfinished, and a write (no gather span)
             + [span("t5", "client.osd_op", 103.0, None)]
             + [span("t6", "client.osd_op", 103.0, 103.2),
                span("t6", "osd.do_op", 103.1, 103.15),
                span("t6", "ec.encode", 103.1, 103.12)])
    ops, left = read_stage.whole_reads(dumps, 30.0)
    assert sorted(o["client.osd_op"]["trace_id"] for o in ops) == ["t1", "t2"]
    assert left == {"resent": 1, "partial": 1}
    assert len(read_stage.whole_reads(dumps, 100.0)[0]) == 3
    assert read_stage.whole_reads([], 30.0) == ([], {"resent": 0,
                                                     "partial": 0})


def test_read_stages_read_the_program_rings_and_add_up(monkeypatch, capsys):
    from ceph_tpu.common import tracing

    class Ring:
        def __init__(self, dumps):
            self.dumps = dumps

        def dump(self):
            return self.dumps

    one = read_op("t1", 100.0, 0.1, 0.05, 0.5, 0.2, 0.05, 0.1)
    two = read_op("t2", 101.0, 0.3, 0.0, 0.4, None, 0.1, 0.2)
    monkeypatch.setattr(tracing, "_TRACERS",
                        {"client.x": Ring(one[:1] + two[:1]),
                         "osd.0": Ring(one[1:] + two[1:])})
    monkeypatch.setattr(read_stage, "_reported", False)
    facts = {"run.window_s": 30.0}
    got = {stage: read_stage.read({"stage": stage}, facts)
           for stage in read_stage.STAGES}
    assert got == pytest.approx({"to_osd": 200.0, "gather": 450.0,
                                 "decode": 100.0, "rest": 100.0,
                                 "reply": 150.0})
    assert sum(got.values()) == pytest.approx((1000 + 1000) / 2)
    said = capsys.readouterr().out
    assert "2 reads with a whole span tree" in said
    assert "1 reconstructed, their ec.decode 200.0 ms" in said
    assert read_stage.read({"stage": "gather"}, {}) is None
    monkeypatch.setattr(tracing, "_TRACERS", {})
    assert read_stage.read({"stage": "gather"}, facts) is None
