"""``readers/registry_idle.py`` on synthetic traces (built as
``test_bm_readers.py`` builds its own): the device's idle time of a
registry cell's slice split at the end of the ``registry.launch`` that
dispatched the module the device ran next, the part before it given to
the section the caller's thread was in; and what
``benchmark/registry_report.py`` prints of the same pairing: the link
(upload start to module start, module end to the end of
``registry.drain.link``, the two directions at once) and the worker's
lines."""

from __future__ import annotations

from pathlib import Path

import pytest

import bm_toy  # noqa: F401  (puts the repo root on sys.path)
from benchmark import harness, registry_report, xplane
from benchmark.readers import registry_idle, span_time
from test_bm_readers import synthetic

US = 1e-6
GF = "jit_registry_gf_gN(7)"
TRACED = {"trace.window_s": 2e-3, "slice.ops": 2}

# four slabs of one call (times in us; the mark is 1000-3000):
#   slab 0  dispatched at 1100, its module starts at 1300: the gap
#           1000-1300 lies across the dispatch;
#   slab 1  dispatched at 1200, behind slab 0's module (1300-1400), its
#           own starts at 1450: the gap 1400-1450 is wholly behind it;
#   slab 2  its module starts at 1990, inside its registry.launch
#           (1960-2000): the gap 1550-1990 is wholly before it;
#   slab 3  a jit_dynamic_slice (2200-2250) under registry.matrix ahead
#           of it, its module 2400-2500 in two operations 20 us apart;
#   the tail 2500-3000 behind the last module.
CALLER = [
    ("benchmark_slice", 1000, 2000),
    ("registry.upload", 1000, 50), ("registry.launch", 1060, 40),
    ("registry.upload", 1110, 40), ("registry.launch", 1160, 40),
    ("registry.drain", 1560, 340),
    ("registry.drain.kernel", 1565, 35), ("registry.drain.link", 1600, 100),
    ("registry.drain.land", 1700, 190),
    ("registry.upload", 1910, 40), ("registry.launch", 1960, 40),
    ("registry.upload", 2091, 3), ("registry.launch", 2095, 205),
    ("registry.matrix", 2100, 160),
    ("registry.device_wait", 2310, 195),
    ("registry.copy_out", 2510, 390),
    ("registry.gather", 2910, 40),        # never the worker's: not counted
    ("registry.upload", 3100, 50), ("registry.launch", 3160, 40)]  # after
WORKER = [("registry.gather", 900, 50),               # before the mark
          ("registry.gather", 1000, 100),
          ("registry.gather", 1500, 300), ("registry.gather.wait", 1500, 100),
          ("PjitFunction(x)", 1900, 10)]
MODULES = [(GF, 1300, 100), (GF, 1450, 100), (GF, 1990, 100),
           ("jit_dynamic_slice(3)", 2200, 50), (GF, 2400, 100)]
OPS = [("%fusion.1 = fusion()", 1300, 100), ("%fusion.1 = fusion()", 1450, 100),
       ("%fusion.1 = fusion()", 1990, 100), ("%slice.2 = slice()", 2200, 50),
       ("%copy.3 = copy()", 2400, 40), ("%fusion.1 = fusion()", 2460, 40)]


def four_slabs(tmp_path, caller=CALLER, modules=MODULES, ops=OPS):
    return synthetic(tmp_path, {
        "/host:CPU": {"worker": WORKER, "caller": caller},
        "/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops}})


def us(secs: float) -> float:
    return round(secs / US, 3)


def test_the_two_parts_add_up_to_the_slices_idle_time(tmp_path):
    path = four_slabs(tmp_path)
    sl = registry_idle.load(path)
    split = sl["split"]
    reduced = xplane.reduce_trace(path)
    assert reduced["marked"] and us(reduced["idle_s"]) == 1570
    before = sum(split[registry_idle.UNDISPATCHED].values())
    assert us(before) == 1200
    assert us(split[registry_idle.OPERAND]) == 350
    assert us(split[registry_idle.INSIDE]) == 20
    assert before + split[registry_idle.OPERAND] \
        + split[registry_idle.INSIDE] == pytest.approx(reduced["idle_s"])
    assert sl["launches"] == 4 == len(sl["slabs"])


def test_a_gap_is_cut_at_the_end_of_the_launch_that_dispatched_its_module(
        tmp_path):
    sl = registry_idle.load(four_slabs(tmp_path))
    by = {name: us(secs) for name, secs in
          sl["split"][registry_idle.UNDISPATCHED].items()}
    # slab 0's gap 1000-1100 (upload 50, nothing 10, launch 40); slab 2's
    # 1550-1990 whole (the module started inside its launch section);
    # 2090-2200 ends at another module's start: whole, under the launch
    # and the matrix nested in it; slab 3's 2250-2300; the tail 2500-3000
    assert by == {
        "registry.upload": 50 + 40 + 3,
        "registry.launch": 40 + 30 + 5 + 40,
        "registry.matrix": 100 + 10,
        "registry.drain": 5 + 10,
        "registry.drain.kernel": 35,
        "registry.drain.link": 100,
        "registry.drain.land": 190,
        "registry.device_wait": 5,
        "registry.copy_out": 390,
        "registry.gather": 40,          # a section like any on this line
        span_time.UNCOVERED: 10 + 10 + 10 + 10 + 1 + 1 + 5 + 60}
    # behind the dispatch: 1100-1300, 1400-1450 whole, 2300-2400
    assert us(sl["split"][registry_idle.OPERAND]) == 200 + 50 + 100
    waited = [s["off_idle"] and s["start"] > s["dispatched"]
              for s in sl["slabs"]]
    assert waited == [True, True, False, True]
    assert [us(s["start"] - s["dispatched"]) for s in sl["slabs"]] == [
        200, 250, -10, 100]


def test_the_tail_behind_the_last_module_is_not_dispatched(tmp_path):
    """The same slice with its last 500 us cut off the mark: only the
    tail's sections leave the account, the operand part stays."""
    whole = registry_idle.load(four_slabs(tmp_path))
    (tmp_path / "cut").mkdir()
    cut = registry_idle.load(four_slabs(
        tmp_path / "cut", [("benchmark_slice", 1000, 1500)] + CALLER[1:]))
    assert cut["split"][registry_idle.OPERAND] == pytest.approx(
        whole["split"][registry_idle.OPERAND])
    gone = sum(whole["split"][registry_idle.UNDISPATCHED].values()) \
        - sum(cut["split"][registry_idle.UNDISPATCHED].values())
    assert us(gone) == 500
    assert "registry.copy_out" not in \
        cut["split"][registry_idle.UNDISPATCHED]


@pytest.mark.parametrize("what", ["a_launch_more", "a_module_more"])
def test_launches_and_modules_that_differ_in_number_give_none(tmp_path, what):
    if what == "a_launch_more":
        path = four_slabs(tmp_path, CALLER + [("registry.launch", 2950, 10)])
    else:
        path = four_slabs(tmp_path, modules=MODULES + [(GF, 2600, 50)],
                          ops=OPS + [("%fusion.1 = fusion()", 2600, 50)])
    assert registry_idle.load(path) is None
    for part in ("undispatched", "operand"):
        assert _read(path, part) is None


def test_a_trace_without_the_mark_or_with_two_devices_gives_none(tmp_path):
    path = four_slabs(tmp_path, CALLER[1:])
    assert registry_idle.load(path) is None
    assert _read(path, "operand") is None
    (tmp_path / "two").mkdir()
    device = {"XLA Modules": MODULES, "XLA Ops": OPS}
    two = synthetic(tmp_path / "two", {
        "/host:CPU": {"caller": CALLER},
        "/device:TPU:0": device, "/device:TPU:1": device})
    assert registry_idle.load(two) is None


def test_worker_sections_are_read_off_the_unmarked_lines_alone(tmp_path):
    sl = registry_idle.load(four_slabs(tmp_path))
    # 100 + 300 of gather started inside the slice, 100 of it the wait;
    # the gather before the mark and the one on the marked line are not
    assert us(sl["gather_wait"]) == 100
    assert us(sl["gather_self"]) == 300
    (tmp_path / "alone").mkdir()
    alone = synthetic(tmp_path / "alone", {
        "/host:CPU": {"caller": CALLER},
        "/device:TPU:0": {"XLA Modules": MODULES, "XLA Ops": OPS}})
    sl = registry_idle.load(alone)
    assert sl["gather_self"] == 0 == sl["gather_wait"]


def _read(path, part: str, facts: dict = TRACED):
    real = span_time.newest_trace
    span_time.newest_trace = lambda root=None: Path(path)
    try:
        return registry_idle.read({"part": part, "per_fact": "slice.ops"},
                                  facts)
    finally:
        span_time.newest_trace = real


def test_read_gives_each_part_per_op_and_reports_once(tmp_path, capsys):
    path = four_slabs(tmp_path)
    assert _read(path, "undispatched") == pytest.approx(0.6)
    assert _read(path, "operand") == pytest.approx(0.185)     # with inside
    out = capsys.readouterr().out
    assert out.count("registry idle: slice 2000.0 ms") == 0
    assert out.count("registry idle: slice 2.0 ms, 4 launches paired with "
                     "4 jit_registry_gf* modules") == 1
    assert "(the operand's upload and the dispatch latency) 0.175" in out
    assert "between a module's own operations 0.010" in out
    assert "not dispatched 0.600 (" in out and "drain.land 0.095" in out
    assert "over the 3 of 4 slabs" in out
    assert "0.100 / 0.200 / 0.250 ms" in out        # dispatch -> start
    assert "0.300 / 0.309 / 0.340 ms" in out        # upload -> start
    # one .link section for three drained slabs: no slab-by-slab line
    assert "registry.drain.link sections: 1, 0.050 ms an op" in out
    assert "module end -> end of the slab's" not in out
    assert "registry.gather self 0.150 ms an op, registry.gather.wait " \
           "0.050 ms an op" in out
    # outside a traced run, without the divisor, and of the manifest's
    # own files with nothing to read
    assert _read(path, "operand", {}) is None
    assert _read(path, "operand", {"trace.window_s": 1.0}) is None
    for name in ("registry_idle_ms_per_op.undispatched",
                 "registry_idle_ms_per_op.operand"):
        assert registry_idle.read(harness.layer_metric(name)["spec"],
                                  {}) is None


def two_slabs(tmp_path):
    """A call of two slabs: both launched, slab 0 landed at the close
    (its .link waited 90 us, 50 of them under slab 1's upload), slab 1
    under registry.copy_out."""
    return synthetic(tmp_path, {
        "/host:CPU": {"caller": [
            ("benchmark_slice", 1000, 500),
            ("registry.upload", 1000, 10), ("registry.launch", 1010, 10),
            ("registry.upload", 1030, 10), ("registry.launch", 1040, 10),
            ("registry.drain", 1055, 300),
            ("registry.drain.kernel", 1060, 140),
            ("registry.drain.link", 1250, 90),
            ("registry.drain.land", 1340, 10),
            ("registry.device_wait", 1360, 40),
            ("registry.copy_out", 1402, 50)]},
        "/device:TPU:0": {
            "XLA Modules": [(GF, 1100, 100), (GF, 1300, 100)],
            "XLA Ops": [("%fusion.1 = fusion()", 1100, 100),
                        ("%fusion.1 = fusion()", 1300, 100)]}})


def test_what_it_says_of_the_link(tmp_path, capsys):
    path = two_slabs(tmp_path)
    sl = registry_idle.load(path)
    assert us(sl["split"][registry_idle.OPERAND]) == 80 + 100
    assert [(us(s), us(e)) for s, e in sl["links"]] == [(1250, 1340)]
    assert [us(s) for s, _ in sl["closes"]] == [1402]
    # the uploads' windows 1000-1100 and 1030-1300 are one, 1000-1300:
    # the .link's first 50 us lie in it
    windows = [(u[0], s["start"]) for u, s in zip(sl["uploads"], sl["slabs"])]
    assert registry_report.overlap_share(sl["links"], windows) == \
        pytest.approx(50 / 90)
    assert registry_report.overlap_share([], windows) is None
    # slab 1 is the call's last: it lands under registry.copy_out
    assert registry_report.drained(sl["slabs"], sl["closes"]) == \
        sl["slabs"][:1]
    facts = dict(TRACED, **{"window.ec_registry.slabs": 4,
                            "window.ec_registry.bytes_in": 4 * (32 << 20),
                            "window.ec_registry.bytes_out": 4 * (12 << 20)})
    assert registry_report.slab_bytes(facts) == (32 << 20, 12 << 20)
    assert registry_report.slab_bytes(TRACED) is None
    # a call over pieces moves its last slab's spare lanes too
    ragged = dict(facts, **{"window.ec_registry.lanes": 3000,
                            "window.ec_registry.lanes_launched": 4000})
    assert registry_report.slab_bytes(ragged) == pytest.approx(
        ((32 << 20) * 4 / 3, (12 << 20) * 4 / 3))
    assert _read(path, "operand", facts) == pytest.approx(0.09)
    out = capsys.readouterr().out
    assert "0.080 / 0.165 / 0.250 ms" in out        # dispatch -> start
    assert "0.100 / 0.185 / 0.270 ms (min / median / max), " \
           f"{(32 << 20) / 185e-6 / (1 << 30):.2f} GiB/s of a slab's " \
           "32.0 MiB up" in out
    assert "registry.drain.link sections: 1, 0.045 ms an op, 55.6 % of " \
           "it" in out
    assert "over the 1 of 1 drained slabs whose .link waited 50 us or " \
           "more: 0.140 / 0.140 / 0.140 ms (min / median / max), " \
           f"{(12 << 20) / 140e-6 / (1 << 30):.2f} GiB/s of a slab's " \
           "12.0 MiB down" in out


def test_each_calls_last_slab_is_not_a_drained_one():
    """Two calls in one slice, of three slabs and of two: the slab
    dispatched last before a ``registry.copy_out`` opened lands there."""
    slabs = [{"dispatched": t} for t in (1.0, 2.0, 3.0, 10.0, 11.0)]
    closes = [(5.0, 6.0), (15.0, 16.0)]
    assert registry_report.drained(slabs, closes) == [
        slabs[0], slabs[1], slabs[3]]
    # a slice that ends before its last call closed: all are drained so far
    assert registry_report.drained(slabs[3:], []) == slabs[3:]


def test_a_program_without_the_nested_sections_still_splits(tmp_path):
    """The parent of the PR that named the waits: launches, uploads and
    modules are there, ``.link`` and ``.gather.wait`` are not; the split
    stands and the report leaves the link's lines out."""
    caller = [ev for ev in CALLER if ev[0].count(".") < 2]
    sl = registry_idle.load(four_slabs(tmp_path, caller))
    assert sl["links"] == []
    assert us(sl["split"][registry_idle.OPERAND]) == 350
    by = {name: us(secs) for name, secs in
          sl["split"][registry_idle.UNDISPATCHED].items()}
    assert by["registry.drain"] == 5 + 35 + 100 + 190 + 10
    assert by["registry.copy_out"] == 390
