"""The two readers of the event loop's layer: ``loop_time`` (what the
uncovered time of a traced slice is made of, from the ``loop.*``
sections of the trace) on a toy trace and on the trace a traced toy
rehearsal leaves, and ``loop_phase`` (how full the loop's thread was,
from the phase record the program keeps) on made-up buckets and on the
program's own rings."""

from __future__ import annotations

import json

import pytest

import bm_toy  # noqa: F401
from benchmark import harness
from benchmark.readers import loop_phase, loop_time, span_time
from test_bm_readers import synthetic

TRACED = {"trace.window_s": 1.0, "trace.busy_s": 0.001}
PER_OP = "client.complete"
LOOP_METRICS = ["loop_ms_per_op.select", "loop_ms_per_op.recv",
                "loop_ms_per_op.send", "loop_ms_per_op.steps",
                "loop_busy_share", "loop_offcpu_share", "loop_max_phase_ms"]
CELLS = ["rs_k8m3_write_4m", "cauchy_k10m4_write_4m",
         "rs_k8m3_degraded_read_4m"]

# one marked millisecond of a loop's thread: two passes
HOST = [("benchmark_slice", 1000, 1000),
        ("loop.select", 950, 100),            # starts before the mark
        ("loop.read_ready", 1100, 300),       # 40 us of recv_into, then
        ("wire.recv", 1140, 250),             # the layer's own work
        ("wire.decode", 1150, 50),
        ("osd_read.verify", 1420, 30),        # a layer span_time lacks
        ("recovery.apply", 1460, 20),         # and another
        ("client.complete", 1500, 10),
        ("loop.write_ready", 1600, 60),
        ("loop.select", 1700, 100),
        ("loop.read_ready", 1850, 30),
        ("client.complete", 1900, 10),
        ("loop.select", 1990, 50)]            # runs past the mark


def loop_trace(tmp_path, host=HOST):
    return synthetic(tmp_path, {
        "/host:CPU": {"loop": host},
        "/device:TPU:0": {
            "XLA Modules": [("jit_ec_encode_crc(1)", 1100, 100)],
            "XLA Ops": [("%fusion.2 = fusion()", 1100, 100)]}})


def test_the_four_parts_are_self_times_per_finished_op(tmp_path, monkeypatch):
    path = loop_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    got = {part: loop_time.read({"part": part, "per": PER_OP}, TRACED)
           for part in loop_time.PARTS}
    # microseconds of the slice / 2 completions, in ms per op
    assert got == pytest.approx({
        "select": (50 + 100 + 10) / 2e3,
        "recv": (40 + 10 + 30) / 2e3,
        "send": 60 / 2e3,
        "steps": (1000 - 160 - 80 - 60 - 250 - 30 - 20 - 20) / 2e3})


@pytest.mark.parametrize("accepted,lacks", [
    ("span_time", ("osd_read.", "recovery.")),
    ("read_span_time", ("recovery.",)),
    ("backfill_span_time", ("osd_read.",)),
])
def test_the_four_parts_sum_to_the_accepted_uncovered_time(
        tmp_path, monkeypatch, accepted, lacks):
    """``select`` + ``recv`` + ``send`` + ``steps`` is what a cell's
    accepted reader, blind to ``loop.*``, calls uncovered on the same
    trace, as long as the slice holds no section of a layer that
    reader lacks (none of its cells runs one)."""
    import importlib
    path = loop_trace(tmp_path,
                      [ev for ev in HOST if not ev[0].startswith(lacks)])
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    parts = [loop_time.read({"part": part, "per": PER_OP}, TRACED)
             for part in loop_time.PARTS]
    uncovered = importlib.import_module(
        f"benchmark.readers.{accepted}").read(
            {"prefix": "", "invert": True, "per": PER_OP}, TRACED)
    assert sum(parts) == pytest.approx(uncovered)


def test_loop_time_prints_counts_and_covers_the_idle_gaps(
        tmp_path, monkeypatch, capsys):
    path = loop_trace(tmp_path)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    loop_time._reported.discard(path)
    assert loop_time.read({"part": "select", "per": PER_OP}, TRACED) > 0
    out = capsys.readouterr().out
    assert "loop.select x2" in out and "loop.read_ready x2" in out
    assert "loop.write_ready x1" in out
    # the 900 us of idle device after the one launch, by section
    gap = next(ln for ln in out.splitlines() if "device idle" in ln)
    assert "after jit_ec_encode_crc" in gap and "loop.select" in gap
    sl = loop_time.load(path)
    over = dict(span_time.covering(sl["pieces"], sl["lo"], sl["hi"]))
    assert sum(over.values()) == pytest.approx(1e-3)
    assert over["loop.read_ready"] == pytest.approx(80e-6)


@pytest.mark.parametrize("host", [
    [ev for ev in HOST if ev[0] != "loop.select"],    # a program with no probe
    [ev for ev in HOST if ev[0] != "client.complete"],    # nothing to divide by
    [ev for ev in HOST if ev[0] != "benchmark_slice"],    # no marked line
], ids=["no_probe", "no_ops", "no_mark"])
def test_loop_time_with_nothing_to_read_is_none(tmp_path, monkeypatch, host):
    path = loop_trace(tmp_path, host)
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    assert loop_time.read({"part": "steps", "per": PER_OP}, TRACED) is None


def bucket(sec, **kw):
    base = dict(sec=sec, select_ns=0, run_ns=0, run_cpu_ns=0, iterations=0,
                max_run_ns=0, recv_ns=0, recv_calls=0, send_ns=0,
                send_calls=0, gc_ns=0)
    return dict(base, **kw)


def _client_op(tracer, start_s: float, end_s: float, under: str | None):
    """A finished ``client.osd_op`` and, in its trace, one span
    ``under`` it (an OSD's, were it not a toy)."""
    span = tracer.start(loop_phase.ROOT)
    span.start = int(start_s * 1e9)
    if under:
        tracer.start(under, parent=span.ctx()).finish()
    span.finish()
    span.end = int(end_s * 1e9)


def test_loop_phase_on_made_up_buckets(monkeypatch, capsys):
    """Ten seconds of a loop and a window of 4.2 s that closed at 107.4,
    where the clients issued their last write: the three whole seconds
    inside it are kept; the second the window opened in, the one it
    closed in (the drain), and the comparison's reads after it, with
    their long phase, are left out."""
    from ceph_tpu.common import tracing
    ms = 1_000_000
    buckets = [bucket(100 + i, select_ns=100 * ms, run_ns=900 * ms,
                      run_cpu_ns=810 * ms, iterations=50,
                      max_run_ns=40 * ms) for i in range(10)]
    buckets[2].update(max_run_ns=999 * ms)        # before the window
    buckets[5].update(select_ns=0, run_ns=1000 * ms, run_cpu_ns=100 * ms,
                      iterations=1, max_run_ns=700 * ms)    # a stall
    buckets[7].update(max_run_ns=800 * ms)        # the drain
    buckets[8].update(max_run_ns=900 * ms)        # the comparison
    records = [
        {"kind": "run", "start": 102.2, "wall_ms": 999.0, "cpu_ms": 1.0,
         "proc_cpu_ms": 1.0, "gc_ms": 0.0, "recv_ms": 0.0, "send_ms": 0.0,
         "reads": 0, "writes": 0},
        {"kind": "run", "start": 104.9, "wall_ms": 700.0, "cpu_ms": 70.0,
         "proc_cpu_ms": 690.0, "gc_ms": 0.0, "recv_ms": 3.0, "send_ms": 0.0,
         "reads": 4, "writes": 0}]
    tracer = tracing.Tracer("client.toy")
    _client_op(tracer, 105.1, 105.9, "ec.encode")
    _client_op(tracer, 107.4, 108.3, "ec.encode")  # issued last, drained
    _client_op(tracer, 108.6, 108.7, "ec.gather")  # read back: no codec
    _client_op(tracer, 109.2, 109.3, None)
    monkeypatch.setattr(tracing, "_TRACERS", {"client.toy": tracer})
    monkeypatch.setattr(tracing, "loop_buckets", lambda: buckets)
    monkeypatch.setattr(tracing, "loop_records", lambda: records)
    monkeypatch.setattr(loop_phase, "_reported", False)
    facts = {"run.window_s": 4.2}
    assert loop_phase.window_close(tracer.finished) == pytest.approx(107.4)
    kept = loop_phase.window(buckets, 4.2, 107.4)
    assert [b["sec"] for b in kept] == [104, 105, 106]
    assert loop_phase.read({"value": "busy_share"}, facts) == pytest.approx(
        100 * (2 * 900 + 1000) / 3000)
    assert loop_phase.read({"value": "offcpu_share"}, facts) == pytest.approx(
        100 * (2 * 90 + 900) / 2800)
    assert loop_phase.read({"value": "max_phase_ms"}, facts) == 700.0
    out = capsys.readouterr().out
    assert out.count("loop phases:") == 1         # printed once
    assert "3 whole seconds of the window kept (104-106)" in out
    worst = [ln for ln in out.splitlines() if "  second " in ln]
    assert len(worst) == 3 and "second 105: 1 passes" in worst[0]
    assert "1 phases of 100 ms or more" in out and "at 104.900" in out
    assert "at 102.200" not in out
    # a decode closes a read cell's window as an encode a write cell's
    reads = tracing.Tracer("client.toy")
    _client_op(reads, 106.5, 106.9, "ec.decode")
    _client_op(reads, 106.8, 107.0, "ec.gather")   # a whole read: no codec
    monkeypatch.setattr(tracing, "_TRACERS", {"client.toy": reads})
    assert loop_phase.read({"value": "max_phase_ms"}, facts) == 700.0
    # no client op that reached a codec: nothing says where the window
    # closed, and no value is made up
    bare = tracing.Tracer("client.toy")
    _client_op(bare, 107.4, 108.3, "ec.gather")
    monkeypatch.setattr(tracing, "_TRACERS", {"client.toy": bare})
    assert loop_phase.read({"value": "max_phase_ms"}, facts) is None
    monkeypatch.setattr(tracing, "_TRACERS", {})
    assert loop_phase.read({"value": "busy_share"}, facts) is None


def test_loop_phase_without_a_record_is_none(monkeypatch):
    from ceph_tpu.common import tracing
    facts = {"run.window_s": 3.0}
    monkeypatch.setattr(tracing, "loop_buckets", lambda: [])
    assert loop_phase.read({"value": "busy_share"}, facts) is None
    # a program from before the probe: no such name in the module
    monkeypatch.delattr(tracing, "loop_buckets")
    monkeypatch.delattr(tracing, "loop_records")
    assert loop_phase.read({"value": "busy_share"}, facts) is None
    # a second with a bucket and no run phase in it
    monkeypatch.setattr(tracing, "loop_buckets",
                        lambda: [bucket(5, select_ns=10**9)], raising=False)
    assert loop_phase.read({"value": "offcpu_share"}, facts) is None


@pytest.mark.parametrize("metric", LOOP_METRICS)
def test_loop_metric_lists_the_three_4m_cells_and_reads_nothing_from_nothing(
        metric):
    spec = harness.layer_metric(metric)
    assert spec["workloads"] == CELLS
    assert spec["layer"].startswith("event loop")
    assert spec["moves"] == "client_mibps"
    reader = {"loop_ms_per_op": loop_time}.get(
        metric.split(".")[0], loop_phase)
    assert reader.__name__.endswith(spec["reader"])
    assert reader.read(spec["spec"], {}) is None
    for cell in CELLS:
        assert metric in harness.Cell(cell).per_layer
    # wherever they stand in the list: a later PR appends behind them
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    listed = [p["name"] for p in manifest["per_layer"]]
    assert [n for n in listed if n in LOOP_METRICS] == LOOP_METRICS


def test_traced_toy_rehearsal_splits_its_uncovered_time(monkeypatch):
    """The real program on the CPU backend: the traced slice holds
    ``loop.select`` and ``loop.read_ready`` with ``wire.recv`` nested
    in the latter, the four ``loop_ms_per_op.*`` as their files specify
    them add up to the accepted ``.unsectioned`` metric of the same
    trace, and the three phase metrics read the program's own rings (a
    window of 2.5 s always holds a whole second)."""
    res = bm_toy.rehearse("rs_k8m3_write_64k", seconds=2.5, traced=True)
    assert res["correct"] is True and res["failed"] == 0
    path = span_time.newest_trace(
        harness.SCRATCH / "trace" / "rs_k8m3_write_64k")
    monkeypatch.setattr(span_time, "newest_trace", lambda: path)
    sl = loop_time.load(path)
    assert sl is not None
    started = sl["started"]
    assert started["loop.select"] > 0 and started["loop.read_ready"] > 0
    assert started["wire.recv"] <= started["loop.read_ready"]
    # every wire.recv lies inside a loop.read_ready
    import jax
    from benchmark.xplane import DEVICE_PLANE, SLICE_MARK
    (line,) = [ln for plane in jax.profiler.ProfileData.from_file(
        str(path)).planes if not plane.name.startswith(DEVICE_PLANE)
        for ln in plane.lines
        if any(e.name == SLICE_MARK for e in ln.events)]
    outer = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events
             if e.name == "loop.read_ready"]
    inner = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events
             if e.name == "wire.recv"]
    assert inner and all(any(lo <= a and b <= hi for lo, hi in outer)
                         for a, b in inner)
    facts = dict(res["facts"], **{"trace.window_s": sl["hi"] - sl["lo"]})
    got = harness.read_layer_metrics(
        LOOP_METRICS + ["host_ms_per_op.unsectioned"], facts)
    assert set(got) == set(LOOP_METRICS) | {"host_ms_per_op.unsectioned"}
    assert sum(got[n]["value"] for n in LOOP_METRICS[:4]) == pytest.approx(
        got["host_ms_per_op.unsectioned"]["value"])
    assert got["loop_ms_per_op.steps"]["value"] > 0
    assert 0 < got["loop_busy_share"]["value"] <= 100.5
    assert 0 <= got["loop_offcpu_share"]["value"] < 100
    assert got["loop_max_phase_ms"]["value"] > 0
