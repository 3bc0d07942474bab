"""The readers the backfill cell brought: ``backfill_stage`` (a push's
length by stage, from the spans the driver took at the window's
close), ``backfill_span_time`` (``span_time`` with the ``recovery.``
layer) and ``program_ms_per_launch`` (one kind of program's device
time over that kind's launches), on hand-made facts, traces and
spans; and the accepted ``decode_roofline`` with the facts this cell
hands it."""

from __future__ import annotations

import pytest

import bm_toy  # noqa: F401
from benchmark import work, work_read
from benchmark.readers import (backfill_span_time, backfill_stage,
                               decode_roofline, program_ms_per_launch,
                               read_span_time, span_time)
from test_bm_readers import span, synthetic

TRACED = {"trace.window_s": 1.0, "trace.busy_s": 0.001}
PER_OP = "client.complete"


def push(trace, t0, lock, gather, decode, payload, send, after, **tags):
    """One push's spans: the root, the gather, a decode unless
    ``decode`` is None, the send."""
    a = t0 + lock
    b = a + gather
    c = b + (decode or 0.0)
    d = c + payload
    e = d + send
    root = span(trace, "pg.backfill_push", t0, e + after)
    root["tags"] = dict({"pgid": "1.0", "oid": f"obj-{trace}", "shard": 3,
                         "dirty": False}, **tags)
    kids = [span(trace, "ec.recover_gather", a, b, root["span_id"])]
    kids[0]["tags"] = {"asked": 7, "excluded": [3]}
    if decode is not None:
        kids.append(span(trace, "ec.recover_decode", b, c, root["span_id"]))
    kids.append(span(trace, "pg.push", d, e, root["span_id"]))
    return [root] + kids


def test_whole_pushes_keeps_the_windows_pushes_with_a_whole_tree():
    spans = (push("a", 100.0, 0.01, 0.5, 0.2, 0.02, 0.3, 0.01)
             + push("b", 101.0, 0.0, 0.4, None, 0.01, 0.2, 0.0)
             # ended before the window opened, and after it closed
             + push("c", 10.0, 0.0, 0.4, 0.1, 0.0, 0.2, 0.0)
             + push("d", 149.9, 0.0, 0.4, 0.1, 0.0, 0.2, 0.0)
             # a ring dropped its gather
             + [s for s in push("e", 102.0, 0.0, 0.4, 0.1, 0.0, 0.2, 0.0)
                if s["name"] != "ec.recover_gather"]
             # unfinished
             + [span("f", "pg.backfill_push", 103.0, None)]
             # a log-based push's gather: a root of its own, no push span
             + [span("g", "ec.recover_gather", 104.0, 104.5)])
    pushes, partial = backfill_stage.whole_pushes(spans, 50.0, 150.0)
    assert sorted(p["pg.backfill_push"]["trace_id"] for p in pushes) \
        == ["a", "b"]
    assert partial == 1
    assert "ec.recover_decode" not in [p for p in pushes if p[
        "pg.backfill_push"]["trace_id"] == "b"][0]
    assert len(backfill_stage.whole_pushes(spans, 0.0, 200.0)[0]) == 4
    assert backfill_stage.whole_pushes([], 0.0, 1.0) == ([], 0)


def test_backfill_stages_read_the_fact_and_add_up(monkeypatch, capsys):
    one = push("a", 100.0, 0.01, 0.5, 0.2, 0.02, 0.3, 0.01, dirty=True)
    two = push("b", 101.0, 0.0, 0.4, None, 0.01, 0.2, 0.0)
    facts = {"spans.backfill": one + two, "run.wall_open": 50.0,
             "run.wall_close": 150.0}
    monkeypatch.setattr(backfill_stage, "_reported", False)
    got = {stage: backfill_stage.read({"stage": stage}, facts)
           for stage in backfill_stage.STAGES}
    assert got == pytest.approx({"gather": 450.0, "decode": 100.0,
                                 "push": 250.0, "rest": 25.0})
    assert sum(got.values()) == pytest.approx((1040 + 610) / 2)
    said = capsys.readouterr().out
    assert "2 pushes with a whole span tree" in said
    assert "1 decoded, 1 copied a shard found whole, 1 dirty" in said
    assert "sub-reads asked 14" in said
    # no fact, no bounds, no push in the window: nothing to read
    assert backfill_stage.read({"stage": "gather"}, {}) is None
    assert backfill_stage.read({"stage": "gather"}, {
        "spans.backfill": one}) is None
    assert backfill_stage.read({"stage": "gather"}, dict(
        facts, **{"run.wall_open": 200.0, "run.wall_close": 300.0})) is None


HOST = [("benchmark_slice", 1000, 1000),
        ("wire.deliver", 1000, 100),
        ("recovery.scan", 1150, 40),
        ("recovery.payload", 1200, 200),
        ("wire.crc", 1250, 50),               # nested in the payload
        ("store.read", 1450, 50),
        ("recovery.apply", 1600, 100),
        ("batcher.dispatch", 1750, 20),
        ("device_wait.materialize", 1800, 30),
        ("client.complete", 1900, 10),
        ("client.complete", 1950, 10)]


def backfill_trace(tmp_path):
    return synthetic(tmp_path, {
        "/host:CPU": {"loop": HOST},
        "/device:TPU:0": {"XLA Modules": [("jit_ec_decode_rows(1)", 1800,
                                           20)],
                          "XLA Ops": [("%fusion = fusion()", 1800, 20)]}})


@pytest.mark.parametrize("spec,want_us", [
    ({"prefix": "recovery.", "per": PER_OP}, 145.0),  # 40+150+100, 2 writes
    ({"prefix": "wire.", "per": PER_OP}, 75.0),
    ({"prefix": "store.", "per": PER_OP}, 25.0),
    ({"prefix": "batcher.", "per": PER_OP}, 10.0),
    ({"prefix": "device_wait.", "per": PER_OP}, 15.0),
    ({"prefix": "client.", "per": PER_OP}, 10.0),
    ({"prefix": "osd_op.", "per": PER_OP}, 0.0),
    ({"prefix": "", "invert": True, "per": PER_OP}, 220.0),
])
def test_backfill_span_time_knows_the_recovery_layer(tmp_path, monkeypatch,
                                                     spec, want_us):
    path = backfill_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    assert backfill_span_time.read(spec, TRACED) * 1e3 \
        == pytest.approx(want_us)


def test_backfill_layers_add_up_and_the_other_readers_keep_their_lists(
        tmp_path, monkeypatch):
    path = backfill_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    total = sum(backfill_span_time.read({"prefix": p, "per": PER_OP}, TRACED)
                for p in backfill_span_time.LAYERS)
    total += backfill_span_time.read(
        {"prefix": "", "invert": True, "per": PER_OP}, TRACED)
    assert total == pytest.approx(1.0 / 2)        # 1 ms slice, 2 writes
    # the readers of the other cells do not know the layer: there its
    # time is nobody's, and their own lists are as they were
    assert read_span_time.LAYERS == span_time.LAYERS + ("osd_read.",)
    for other in (span_time, read_span_time):
        assert other.read({"prefix": "recovery.", "per": PER_OP},
                          TRACED) == 0.0
        assert other.read({"prefix": "", "invert": True, "per": PER_OP},
                          TRACED) * 1e3 == pytest.approx(220.0 + 145.0)


def test_backfill_span_time_with_nothing_to_read_is_none(tmp_path,
                                                         monkeypatch):
    spec = {"prefix": "recovery.", "per": PER_OP}
    path = backfill_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    assert backfill_span_time.read(spec, {}) is None
    assert backfill_span_time.read(dict(spec, per="device_wait.crush"),
                                   TRACED) is None
    monkeypatch.setattr(span_time, "newest_trace", lambda: None)
    assert backfill_span_time.read(spec, TRACED) is None


def test_program_ms_per_launch_takes_one_kinds_time_over_its_count():
    spec = {"programs": "^jit_ec_decode",
            "launches": "slice.ec_batch.decode_launches"}
    facts = {"trace.programs": {"jit_ec_decode_rows": 0.0038,
                                "jit_ec_encode_crc": 0.5},
             "slice.ec_batch.decode_launches": 20,
             "slice.ec_batch.mesh_launches": 60}
    assert program_ms_per_launch.read(spec, facts) == pytest.approx(0.19)
    assert program_ms_per_launch.read(spec, {}) is None
    assert program_ms_per_launch.read(spec, dict(
        facts, **{"slice.ec_batch.decode_launches": 0})) is None
    assert program_ms_per_launch.read(spec, dict(
        facts, **{"trace.programs": {"jit_ec_encode_crc": 0.5}})) is None


def test_recover_hbm_share_counts_k_chunks_in_and_the_wanted_one_out():
    spec = {"stripes": "slice.ec_batch.decode_stripes",
            "rows": "config.failure.osds_out", "programs": "^jit_ec_decode"}
    facts = {"trace.programs": {"jit_ec_decode_rows": 0.004,
                                "jit_ec_encode_crc": 0.5},
             "slice.ec_batch.decode_stripes": 20 * 128,
             "slice.ec_batch.stripes": 60 * 128,
             "config.failure.osds_out": 1,
             "config.profile.k": 8, "config.profile.stripe_unit": 4096,
             "device.kind": "TPU v5 lite"}
    need = work_read.decode_bytes(8, 1, 4096, 20 * 128)
    assert need == 20 * 4_718_592
    want = work.roofline_share(need, 819e9, 0.004)
    assert decode_roofline.read(spec, facts) == pytest.approx(want)
    assert 0 < want < 100
