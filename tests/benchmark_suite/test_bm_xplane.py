"""The trace reduction on a recorded trace (trimmed from this
benchmark's first traced run of ``rs_k8m3_write_4m`` on a TPU v5e: two
encode launches, operation and program lines, the slice mark) and on
small synthetic traces for the corners the recording does not have."""

from __future__ import annotations

import jax
import pytest

import bm_toy  # noqa: F401
from benchmark import harness, xplane

RECORDED = harness.BENCH / "testdata" / "store_slice.xplane.pb"


def synthetic(tmp_path, planes: dict) -> str:
    """{plane: {line: [(name, start_us, dur_us), ...]}} -> an .xplane.pb"""
    text = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names: dict[str, int] = {}
        body = []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = " ".join(
                f"events {{ metadata_id: {names.setdefault(n, len(names) + 1)}"
                f" offset_ps: {int(s * 1e6)} duration_ps: {int(d * 1e6)} }}"
                for n, s, d in events)
            body.append(f'lines {{ id: {lid} name: "{line}" {evs} }}')
        meta = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in names.items())
        text.append(f'planes {{ id: {pid} name: "{plane}" '
                    f'{" ".join(body)} {meta} }}')
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        "\n".join(text)))
    return str(path)


def test_recorded_slice_reduces_to_busy_idle_programs_ops_and_gaps():
    r = xplane.reduce_trace(RECORDED)
    assert r["marked"] and r["device_planes"] == 1
    assert r["window_s"] == pytest.approx(0.3)
    assert r["launches"] == {"jit_fn": 2}
    # two ~48.7 ms encode+CRC launches; nothing else ran on the device
    assert r["programs"]["jit_fn"] == pytest.approx(0.097405, abs=1e-5)
    assert r["busy_s"] == pytest.approx(r["programs"]["jit_fn"], rel=1e-3)
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])
    ops = r["device_ops"]
    assert 1 <= len(ops) <= 10
    assert ops == sorted(ops, key=lambda o: -o[1])
    # the eight per-iteration table-gather fusions of the CRC loop lead
    assert ops[0][0].startswith("fusion.") and "u32[1024]" in ops[0][0]
    assert sum(s for _, s in ops[:8]) == pytest.approx(0.0702, abs=1e-3)
    gaps = r["idle_gaps"]
    assert gaps == sorted(gaps, key=lambda g: -g[1]) and len(gaps) <= 10
    assert gaps[0] == ["unattributed, after jit_fn",
                       pytest.approx(0.121667, abs=1e-5)]
    assert sum(s for _, s in gaps) == pytest.approx(r["idle_s"], rel=1e-3)


def test_nested_operations_count_once_and_keep_their_self_time(tmp_path):
    path = synthetic(tmp_path, {"/device:TPU:0": {
        "XLA Modules": [("jit_f(1)", 100, 100), ("jit_g(2)", 300, 50)],
        "XLA Ops": [("%while.1 = (s32[]) while(s32[] %a)", 100, 100),
                    ("%add.2 = s32[8]{0} add(s32[8] %x, s32[8] %y)", 110, 30),
                    ("%mul.3 = s32[8]{0} multiply(s32[8] %x)", 150, 40),
                    ("%add.2 = s32[8]{0} add(s32[8] %x, s32[8] %y)", 300, 50)],
    }})
    r = xplane.reduce_trace(path)
    assert not r["marked"]
    assert r["window_s"] == pytest.approx(250e-6)      # first to last op
    assert r["busy_s"] == pytest.approx(150e-6)
    assert r["programs"] == {"jit_f": pytest.approx(100e-6),
                             "jit_g": pytest.approx(50e-6)}
    assert dict(map(tuple, r["device_ops"])) == {
        "add.2 s32[8] add": pytest.approx(80e-6),
        "mul.3 s32[8] multiply": pytest.approx(40e-6),
        "while.1 tuple while": pytest.approx(30e-6)}
    assert r["idle_gaps"] == [["unattributed, after jit_f",
                               pytest.approx(100e-6)]]


def test_mark_clips_and_chips_are_averaged(tmp_path):
    path = synthetic(tmp_path, {
        "/host:CPU": {"python3": [(xplane.SLICE_MARK, 1000, 1000)]},
        "/device:TPU:0": {"XLA Ops": [("%a = f32[] add()", 900, 200),
                                      ("%a = f32[] add()", 1500, 100)]},
        "/device:TPU:1": {"XLA Ops": [("%a = f32[] add()", 1900, 300)]},
    })
    r = xplane.reduce_trace(path)
    assert r["marked"] and r["device_planes"] == 2
    assert r["window_s"] == pytest.approx(1000e-6)
    # chip 0: 100 (clipped) + 100; chip 1: 100 (clipped)
    assert r["busy_s"] == pytest.approx(150e-6)
    assert r["idle_s"] == pytest.approx(850e-6)


def test_a_trace_with_no_device_plane_is_unreadable(tmp_path):
    path = synthetic(tmp_path, {"/host:CPU": {"python3": [("x", 0, 10)]}})
    with pytest.raises(xplane.TraceUnreadable):
        xplane.reduce_trace(path)
    with pytest.raises(xplane.TraceUnreadable):
        xplane.reduce_trace(tmp_path / "missing.xplane.pb")


@pytest.mark.parametrize("name,short", [
    ("%fusion.5 = u32[1024]{0:T(1024)S(1)} fusion(u32[256]{0} %p), "
     "kind=kCustom, calls=%fused_computation.1", "fusion.5 u32[1024] fusion"),
    ("%while.178 = (s32[131072]{0}, pred[4]{0:T(4)(128)}) while((s32[1]) %t),"
     " condition=%c, body=%b", "while.178 tuple while"),
    ("%copy-start = (s8[24,64]{1,0}, s8[24,64]{1,0}, u32[]{:S(2)}) "
     "copy-start(s8[24,64]{1,0} %w)", "copy-start tuple copy-start"),
    ("jit_fn(123)", "jit_fn(123)"),
])
def test_operation_names_are_cut_to_result_type_and_opcode(name, short):
    assert xplane.short_op(name) == short
