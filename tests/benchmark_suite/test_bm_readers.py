"""The readers this benchmark gained with the program's tracing:
``span_time`` (loop-thread time by host layer, from section events of
the trace), ``scope_time`` (device time under a named scope) and
``op_stage`` (op latency by stage, from the span rings), on synthetic
traces and rings, on the recorded TPU trace, and on the trace a traced
toy rehearsal leaves on the CPU backend."""

from __future__ import annotations

import jax
import pytest

import bm_toy  # noqa: F401
from benchmark import harness
from benchmark.readers import op_stage, scope_time, span_time

RECORDED = harness.BENCH / "testdata" / "store_slice.xplane.pb"
TRACED = {"trace.window_s": 1.0, "trace.busy_s": 0.001}
PER_OP = "client.complete"


def synthetic(tmp_path, planes: dict) -> str:
    """{plane: {line: [(name, start_us, dur_us[, {stat: text}]), ...]}}
    -> an .xplane.pb; the stats go where a TPU plane keeps an
    operation's: on the event's metadata."""
    text = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names: dict[str, int] = {}
        stats: dict[str, int] = {}
        of_name: dict[str, str] = {}
        body = []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = []
            for name, start, dur, *rest in events:
                of_name[name] = " ".join(
                    f"stats {{ metadata_id: "
                    f"{stats.setdefault(k, len(stats) + 1)} "
                    f'str_value: "{v}" }}'
                    for k, v in (rest[0] if rest else {}).items())
                evs.append(
                    f"events {{ metadata_id: "
                    f"{names.setdefault(name, len(names) + 1)} offset_ps: "
                    f"{int(start * 1e6)} duration_ps: {int(dur * 1e6)} }}")
            body.append(f'lines {{ id: {lid} name: "{line}" '
                        f'{" ".join(evs)} }}')
        meta = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
            f'{of_name[n]} }} }}' for n, i in names.items())
        smeta = " ".join(
            f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in stats.items())
        text.append(f'planes {{ id: {pid} name: "{plane}" '
                    f'{" ".join(body)} {meta} {smeta} }}')
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        "\n".join(text)))
    return str(path)


HOST = [("benchmark_slice", 1000, 1000),
        ("wire.deliver", 900, 200),           # starts before the mark
        ("wire.decode", 1050, 30),            # nested in it
        ("PjitFunction(x)", 1060, 10),        # not a section
        ("osd_op.sub_write", 1200, 300),
        ("store.queue_transaction", 1250, 100),
        ("wire.crc", 1260, 20),               # two levels down
        ("client.complete", 1600, 10),
        ("client.complete", 1700, 10),
        ("device_wait.materialize", 1900, 200),   # runs past the mark
        ("client.complete", 2100, 10)]            # after the mark


def store_trace(tmp_path):
    return synthetic(tmp_path, {
        "/host:CPU": {"other thread": [("wire.encode", 1000, 900)],
                      "loop": HOST},
        "/device:TPU:0": {
            "XLA Modules": [("jit_ec_encode_crc(1)", 1100, 100),
                            ("jit_ec_encode_crc(1)", 1900, 50)],
            "XLA Ops": [
                ("%while.1 = while()", 1100, 100,
                 {"tf_op": "jit(ec_encode_crc)/jit(main)/crc32c/while"}),
                ("%fusion.9 = fusion()", 1110, 40,
                 {"tf_op": "jit(ec_encode_crc)/crc32c/while/body/xor"}),
                ("%fusion.2 = fusion()", 1150, 20,
                 {"tf_op": "jit(ec_encode_crc)/gf_encode/dot_general"}),
                ("%copy.3 = copy()", 1900, 50,
                 {"tf_op": "jit(ec_encode_crc_more)/crc32c_chunks_x",
                  "source": "ops/crc32c.py:440"})]}})


def test_innermost_counts_nested_time_once():
    ev = [(0.0, 10.0, "a.x"), (2.0, 6.0, "b.y"), (3.0, 4.0, "a.z"),
          (12.0, 13.0, "c.w")]
    pieces = span_time.innermost(ev, 1.0, 12.5)
    assert pieces == [(1.0, 2.0, "a.x"), (2.0, 3.0, "b.y"),
                      (3.0, 4.0, "a.z"), (4.0, 6.0, "b.y"),
                      (6.0, 10.0, "a.x"), (10.0, 12.0, None),
                      (12.0, 12.5, "c.w")]
    times = span_time.self_times(pieces)
    assert sum(times.values()) == pytest.approx(11.5)
    assert times == {"a.x": 5.0, "b.y": 3.0, "a.z": 1.0, None: 2.0,
                     "c.w": 0.5}
    assert span_time.covering(pieces, 9.0, 12.0) == [
        (span_time.UNCOVERED, 2.0), ("a.x", 1.0)]


def test_span_time_clips_to_the_mark_on_the_marked_line(tmp_path):
    sl = span_time.load(store_trace(tmp_path))
    assert sl["hi"] - sl["lo"] == pytest.approx(1e-3)
    # only what STARTED inside the slice is counted; the other thread's
    # sections are not on the marked line
    assert sl["started"] == {"wire.decode": 1, "osd_op.sub_write": 1,
                             "store.queue_transaction": 1, "wire.crc": 1,
                             "client.complete": 2,
                             "device_wait.materialize": 1}
    us = {k: 1e6 * v for k, v in span_time.self_times(sl["pieces"]).items()}
    assert us == pytest.approx({
        "wire.deliver": 70, "wire.decode": 30, "osd_op.sub_write": 200,
        "store.queue_transaction": 80, "wire.crc": 20,
        "client.complete": 20, "device_wait.materialize": 100,
        None: 480})
    # the device's gaps, longest first, with what covers them
    gaps = [(round(1e6 * (e - s)), after) for s, e, after in sl["gaps"]]
    assert gaps == [(700, "jit_ec_encode_crc"), (100, "window start"),
                    (50, "jit_ec_encode_crc")]
    start, end, _ = sl["gaps"][0]
    over = dict(span_time.covering(sl["pieces"], start, end))
    assert over[span_time.UNCOVERED] == pytest.approx(380e-6)
    assert over["osd_op.sub_write"] == pytest.approx(200e-6)


@pytest.mark.parametrize("spec,want_us", [
    # per finished op: 2 in the slice
    ({"prefix": "wire.", "per": PER_OP}, 60.0),
    ({"prefix": "osd_op.", "per": PER_OP}, 100.0),
    ({"prefix": "store.", "per": PER_OP}, 40.0),
    ({"prefix": "device_wait.", "per": PER_OP}, 50.0),
    ({"prefix": "client.", "per": PER_OP}, 10.0),
    ({"prefix": "", "invert": True, "per": PER_OP}, 240.0),
    ({"prefix": "batcher.", "per": PER_OP}, 0.0),
    # per launch, per sub-write
    ({"prefix": "device_wait.", "per": "device_wait.materialize"}, 100.0),
    ({"prefix": "wire.", "per": "osd_op.sub_write"}, 120.0),
])
def test_span_time_reads_self_time_per_counted_section(
        tmp_path, monkeypatch, capsys, spec, want_us):
    path = store_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    assert span_time.read(spec, TRACED) * 1e3 == pytest.approx(want_us)
    said = capsys.readouterr().out
    if said:        # the report is printed once per trace
        assert "device idle" in said and "osd_op.sub_write" in said
        assert "unattributed" not in said


def test_span_time_layers_and_uncovered_add_up_to_the_slice(
        tmp_path, monkeypatch):
    path = store_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    total = sum(span_time.read({"prefix": p, "per": PER_OP}, TRACED)
                for p in span_time.LAYERS)
    total += span_time.read({"prefix": "", "invert": True, "per": PER_OP},
                            TRACED)
    assert total == pytest.approx(1.0 / 2)        # 1 ms slice, 2 ops


@pytest.mark.parametrize("reader,spec", [
    (span_time, {"prefix": "wire.", "per": PER_OP}),
    (span_time, {"prefix": "device_wait.", "per": "device_wait.crush"}),
    (scope_time, {"scope": "crc32c"}),
    (op_stage, {"from": "ec.encode.start", "to": "ec.encode.end"}),
])
def test_readers_find_nothing_outside_a_traced_run(
        tmp_path, monkeypatch, reader, spec):
    path = store_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    assert reader.read(spec, {}) is None
    assert reader.read(spec, {"run.ops": 3}) is None


def test_span_time_without_sections_or_mark_is_none(tmp_path, monkeypatch):
    """A program without sections (the parent commit), a trace without
    the mark, no trace at all."""
    spec = {"prefix": "wire.", "per": PER_OP}
    bare = synthetic(tmp_path, {"/host:CPU": {"loop": [
        ("benchmark_slice", 0, 100), ("PjitFunction(x)", 10, 5)]}})
    monkeypatch.setattr(span_time, "newest_trace", lambda: bare)
    assert span_time.read(spec, TRACED) is None
    assert span_time.read(dict(spec, invert=True), TRACED) is None
    monkeypatch.setattr(span_time, "newest_trace", lambda: str(RECORDED))
    assert span_time.read(spec, TRACED) is None
    (tmp_path / "m").mkdir()
    unmarked = synthetic(tmp_path / "m", {"/host:CPU": {"loop": [
        ("wire.decode", 10, 5)]}})
    assert span_time.load(unmarked) is None
    monkeypatch.undo()
    assert span_time.newest_trace(tmp_path / "m" / "none") is None
    monkeypatch.setattr(span_time, "newest_trace", lambda: None)
    assert span_time.read(spec, TRACED) is None


def test_span_time_with_nothing_to_divide_by_is_none(tmp_path, monkeypatch):
    """Sections, but none of the kind the time is divided by started
    in the slice."""
    path = store_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    assert span_time.read({"prefix": "wire.", "per": "device_wait.crush"},
                          TRACED) is None


def test_scope_time_counts_nested_operations_once(tmp_path, monkeypatch):
    path = store_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    facts = {"trace.window_s": 1e-3, "trace.busy_s": 150e-6}
    # while (100 us) holds a crc fusion (40) and the gf fusion (20):
    # crc32c = 100 - 20; the copy under "crc32c_chunks_x" of another
    # program is not the word crc32c, and its source file is no scope
    assert scope_time.read({"scope": "crc32c"}, facts) == pytest.approx(
        100 * 80 / 150)
    assert scope_time.read({"scope": "gf_encode"}, facts) == pytest.approx(
        100 * 20 / 150)
    assert scope_time.read({"scope": "straw2_draw"}, facts) is None
    both = (scope_time.read({"scope": "crc32c"}, facts)
            + scope_time.read({"scope": "gf_encode"}, facts))
    assert both <= 100.0


def test_scope_time_on_the_recorded_trace(monkeypatch):
    """The recorded TPU trace was trimmed of its event metadata's
    stats: no operation has a scope path, so the reader finds nothing;
    every busy instant still belongs to exactly one operation, though
    asynchronous copies overlap the compute."""
    lo, hi = 0.107567271, 0.407567271
    events = scope_time.op_events(str(RECORDED), lo, hi)
    assert len(events) == 1 and len(events[0]) > 30000
    assert not any(paths for _, _, paths in events[0])
    busy = 0.097405
    everything = scope_time.matched_time(events[0], lambda t: True, lo, hi)
    assert everything == pytest.approx(busy, rel=1e-4)
    monkeypatch.setattr(span_time, "newest_trace", lambda: str(RECORDED))
    assert scope_time.read({"scope": "crc32c"},
                           {"trace.window_s": 0.3,
                            "trace.busy_s": busy}) is None


def test_scope_paths_come_from_the_event_metadata(tmp_path, monkeypatch):
    path = store_trace(tmp_path)
    scopes = scope_time.op_scopes(path)
    assert list(scopes) == ["/device:TPU:0"]
    assert scopes["/device:TPU:0"] == {
        "%while.1 = while()": "jit(ec_encode_crc)/jit(main)/crc32c/while",
        "%fusion.9 = fusion()": "jit(ec_encode_crc)/crc32c/while/body/xor",
        "%fusion.2 = fusion()": "jit(ec_encode_crc)/gf_encode/dot_general",
        "%copy.3 = copy()": "jit(ec_encode_crc_more)/crc32c_chunks_x"}
    # varints of more than one byte, fixed-width fields, nested bytes
    msg = bytes([0x08, 0xAC, 0x02,                  # 1: varint 300
                 0x12, 0x03, 0x61, 0x62, 0x63,      # 2: "abc"
                 0x1D, 1, 0, 0, 0,                  # 3: fixed32
                 0x21, 2, 0, 0, 0, 0, 0, 0, 0])     # 4: fixed64
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in scope_time.wire_fields(memoryview(msg))]
    assert got == [(1, 300), (2, b"abc"), (3, b"\x01\0\0\0"),
                   (4, b"\x02" + b"\0" * 7)]
    # a file that is no XSpace reads as nothing, not as a crash
    junk = tmp_path / "junk.xplane.pb"
    junk.write_bytes(b"\x0b\x0b\x0b")
    monkeypatch.setattr(span_time, "newest_trace", lambda: str(junk))
    monkeypatch.setattr(span_time, "load",
                        lambda p: {"lo": 0.0, "hi": 1.0})
    assert scope_time.read({"scope": "crc32c"}, {
        "trace.window_s": 1.0, "trace.busy_s": 1.0}) is None


def span(trace, name, start, end, parent=None):
    return {"trace_id": trace, "span_id": f"{trace}-{name}-{start}",
            "parent_id": parent, "name": name, "daemon": "d",
            "start": start, "end": end,
            "duration_ms": None if end is None else 1e3 * (end - start),
            "tags": {}}


def write_op(trace, t0, to_osd, before, encode, commit, reply, **kw):
    a = t0 + to_osd
    b = a + before
    c = b + encode
    d = c + commit
    return [span(trace, "client.osd_op", t0, d + reply),
            span(trace, "osd.do_op", a, d),
            span(trace, "ec.encode", b, c),
            span(trace, "store.txn", c, c + 0.001)]


STAGES = {"to_osd": ("client.osd_op.start", "osd.do_op.start"),
          "prepare": ("osd.do_op.start", "ec.encode.start"),
          "encode": ("ec.encode.start", "ec.encode.end"),
          "commit": ("ec.encode.end", "osd.do_op.end"),
          "reply": ("osd.do_op.end", "client.osd_op.end")}


def test_op_stage_keeps_whole_single_attempt_ops_of_the_window():
    dumps = (write_op("t1", 100.0, 0.1, 0.0, 1.0, 0.5, 0.2)
             + write_op("t2", 101.0, 0.3, 0.2, 2.0, 1.5, 0.4)
             # before the window: its root ended 50 s earlier
             + write_op("t0", 10.0, 9.0, 0.0, 9.0, 9.0, 9.0)
             # sent again: two osd.do_op
             + write_op("t3", 102.0, 0.1, 0.0, 1.0, 0.5, 0.2)
             + [span("t3", "osd.do_op", 102.5, 103.0)]
             # a ring dropped its root
             + write_op("t4", 102.0, 0.1, 0.0, 1.0, 0.5, 0.2)[1:]
             # unfinished, and a read (no encode)
             + [span("t5", "client.osd_op", 103.0, None)]
             + [span("t6", "client.osd_op", 103.0, 103.2),
                span("t6", "osd.do_op", 103.1, 103.15)])
    ops, left = op_stage.whole_ops(dumps, 30.0)
    assert sorted(o["client.osd_op"]["trace_id"] for o in ops) == ["t1", "t2"]
    assert left == {"resent": 1, "partial": 1}
    ops, _ = op_stage.whole_ops(dumps, 100.0)
    assert len(ops) == 3
    assert op_stage.whole_ops([], 30.0) == ([], {"resent": 0, "partial": 0})


def test_op_stage_reads_the_program_rings_and_stages_sum(monkeypatch, capsys):
    from ceph_tpu.common import tracing

    class Ring:
        def __init__(self, dumps):
            self.dumps = dumps

        def dump(self):
            return self.dumps

    ops = (write_op("t1", 100.0, 0.1, 0.0, 1.0, 0.5, 0.2)
           + write_op("t2", 101.0, 0.3, 0.2, 2.0, 1.5, 0.4))
    monkeypatch.setattr(tracing, "_TRACERS",
                        {"client.x": Ring(ops[:1] + ops[4:5]),
                         "osd.0": Ring(ops[1:4] + ops[5:])})
    facts = {"run.window_s": 30.0}
    got = {k: op_stage.read({"from": a, "to": b}, facts)
           for k, (a, b) in STAGES.items()}
    assert got == pytest.approx({"to_osd": 200.0, "prepare": 100.0,
                                 "encode": 1500.0, "commit": 1000.0,
                                 "reply": 300.0})
    assert sum(got.values()) == pytest.approx((1800 + 4400) / 2)
    assert "2 writes with a whole span tree" in capsys.readouterr().out
    monkeypatch.setattr(tracing, "_TRACERS", {})
    assert op_stage.read({"from": "ec.encode.start",
                          "to": "ec.encode.end"}, facts) is None


def test_traced_toy_rehearsal_puts_every_layer_inside_the_mark(monkeypatch):
    """The real program on the CPU backend: a traced rehearsal leaves a
    trace whose marked host line holds sections of every host layer
    and one ``client.complete`` per op finished in the slice; the six
    ``host_ms_per_op.*`` as their files specify them add up to the slice
    per finished op less the ``client.*`` sections' own time; the three
    wait counters and the op stages are there to be read."""
    res = bm_toy.rehearse("rs_k8m3_write_64k", seconds=1.5, traced=True)
    assert res["correct"] is True and res["failed"] == 0
    path = span_time.newest_trace(
        harness.SCRATCH / "trace" / "rs_k8m3_write_64k")
    sl = span_time.load(path)
    assert sl is not None and sl["hi"] - sl["lo"] >= 0.45
    started = sl["started"]
    assert started["client.complete"] > 0
    for layer in ("client.", "wire.", "osd_op.", "store.", "batcher.",
                  "device_wait."):
        assert any(n.startswith(layer) for n in started), layer
    for name in ("wire.encode", "wire.decode", "osd_op.sub_write",
                 "store.queue_transaction", "batcher.dispatch",
                 "device_wait.materialize"):
        assert started.get(name, 0) > 0, name
    # a launch that straddles an edge of the mark is in one count only
    assert abs(started["device_wait.materialize"]
               - res["facts"]["slice.ec_batch.batches"]) <= 2
    times = span_time.self_times(sl["pieces"])
    assert sum(times.values()) == pytest.approx(sl["hi"] - sl["lo"])
    assert times["device_wait.materialize"] > 0
    w = {k.split(".")[-1]: v for k, v in res["facts"].items()
         if k.startswith("window.ec_batch.")}
    assert w["materialize_us"] > 0 and w["queue_wait_us"] > 0
    assert w["overlap_us"] >= 0
    facts = res["facts"]
    # another test file's rehearsal may leave a newer trace meanwhile
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    layers = harness.read_layer_metrics(
        [n for n in harness.Cell("rs_k8m3_write_4m").per_layer
         if n.startswith("host_ms_per_op.")],
        dict(facts, **{"trace.window_s": sl["hi"] - sl["lo"]}))
    assert set(layers) >= {"host_ms_per_op." + layer for layer in (
        "wire", "osd_op", "store", "batcher", "device_wait", "unsectioned")}
    # the client's own sections are in the trace and in no metric since
    # PR 49 (0.017-0.019 ms of a 41 ms op: ledger, PR 47): the listed
    # layers add up to the slice less them
    client = bm_toy.client_ms_per_op(sl)
    assert client > 0
    assert sum(m["value"] for m in layers.values()) == pytest.approx(
        1e3 * (sl["hi"] - sl["lo"]) / started["client.complete"] - client)
    stages = {k: op_stage.read({"from": a, "to": b}, facts)
              for k, (a, b) in STAGES.items()}
    assert all(v is not None and v >= 0 for v in stages.values()), stages
    assert stages["encode"] > 0


def test_traced_crush_rehearsal_counts_a_device_wait_per_launch(monkeypatch):
    """The bulk mapper on the CPU backend: every launch of the marked
    slice is one ``device_wait.crush`` section on the marked line, which
    is what ``crush_device_wait_ms_per_launch`` divides by."""
    res = bm_toy.rehearse("crush_1000osd_bulk", seconds=1.0, traced=True)
    assert res["correct"] is True
    path = span_time.newest_trace(
        harness.SCRATCH / "trace" / "crush_1000osd_bulk")
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    sl = span_time.load(path)
    assert sl["started"] == {
        "device_wait.crush": res["facts"]["slice.launches"]}
    slice_s = sl["hi"] - sl["lo"]
    got = harness.read_layer_metrics(
        ["crush_device_wait_ms_per_launch"],
        dict(res["facts"], **{"trace.window_s": slice_s}))
    per_launch = got["crush_device_wait_ms_per_launch"]["value"]
    assert per_launch == pytest.approx(
        1e3 * span_time.self_times(sl["pieces"])["device_wait.crush"]
        / res["facts"]["slice.launches"])
    assert 0 < per_launch <= 1e3 * slice_s
