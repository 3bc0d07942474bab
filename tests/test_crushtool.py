"""crushtool analog: text grammar compile/decompile roundtrip and
--test simulation (CrushCompiler.cc grammar, crushtool.cc:546)."""

import io
import json
import os
import subprocess
import sys

import pytest

from ceph_tpu.crush import crush_do_rule
from ceph_tpu.crush.builder import build_two_level_map
from ceph_tpu.tools.crushtool import (
    CompileError, compile_text, decompile, run_test)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MAP_TEXT = """
# minimal cluster map
tunable choose_total_tries 50
tunable chooseleaf_vary_r 1
tunable chooseleaf_stable 1

device 0 osd.0
device 1 osd.1
device 2 osd.2
device 3 osd.3

type 0 osd
type 1 host
type 10 root

host host0 {
    id -2
    alg straw2
    hash 0
    item osd.0 weight 1.000
    item osd.1 weight 1.000
}
host host1 {
    id -3
    alg straw2
    hash 0
    item osd.2 weight 1.000
    item osd.3 weight 2.000
}
root default {
    id -1
    alg straw2
    hash 0
    item host0 weight 2.000
    item host1 weight 3.000
}

rule replicated_rule {
    id 0
    type replicated
    step take default
    step chooseleaf firstn 0 type host
    step emit
}
rule ec_rule {
    id 1
    type erasure
    step take default
    step chooseleaf indep 0 type host
    step emit
}
"""


def test_compile_and_map():
    cm, type_names, devices = compile_text(MAP_TEXT)
    assert devices == [0, 1, 2, 3]
    assert cm.buckets[-1].item_weights == [2 * 0x10000, 3 * 0x10000]
    assert cm.tunables.choose_total_tries == 50
    w = [0x10000] * 4
    res = crush_do_rule(cm, 0, 1234, 2, w)
    assert len(res) == 2 and len(set(res)) == 2
    # chooseleaf over hosts: replicas on distinct hosts
    host_of = {0: 0, 1: 0, 2: 1, 3: 1}
    assert host_of[res[0]] != host_of[res[1]]


def test_decompile_compile_roundtrip():
    cm, type_names, devices = compile_text(MAP_TEXT)
    text = decompile(cm, type_names, devices)
    cm2, _, _ = compile_text(text)
    w = [0x10000] * 4
    for x in range(200):
        for rule in (0, 1):
            assert crush_do_rule(cm, rule, x, 3, w) == \
                crush_do_rule(cm2, rule, x, 3, w), (rule, x)


def test_builder_map_decompiles():
    cm = build_two_level_map(3, 4)
    text = decompile(cm)
    cm2, _, _ = compile_text(text)
    w = [0x10000] * 12
    for x in range(100):
        assert crush_do_rule(cm, 0, x, 3, w) == \
            crush_do_rule(cm2, 0, x, 3, w), x


def test_run_test_utilization():
    cm, _, _ = compile_text(MAP_TEXT)
    buf = io.StringIO()
    stats = run_test(cm, 0, 2, 0, 255, {}, True, out=buf)
    assert stats["sizes"] == {2: 256}
    assert sum(stats["counts"].values()) == 512
    # osd.3 (weight 2) carries more than osd.2 (weight 1)
    assert stats["counts"][3] > stats["counts"][2]
    text = buf.getvalue()
    assert "CRUSH rule 0 x 0" in text
    assert "result size == 2:\t256/256" in text


def test_down_weight_reroutes():
    cm, _, _ = compile_text(MAP_TEXT)
    stats = run_test(cm, 0, 2, 0, 255, {0: 0.0}, False,
                     out=io.StringIO())
    assert 0 not in stats["counts"]
    assert stats["sizes"] == {2: 256}


def test_compile_errors():
    with pytest.raises(CompileError):
        compile_text("bogus line here")
    with pytest.raises(CompileError):
        compile_text("type 1 host\nhost h {\n  alg straw2\n}\n")


def test_cli_roundtrip(tmp_path):
    src = tmp_path / "map.txt"
    src.write_text(MAP_TEXT)
    out = tmp_path / "map.json"
    r = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.tools.crushtool",
         "-c", str(src), "-o", str(out)],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert json.loads(out.read_text())["buckets"]
    r = subprocess.run(
        [sys.executable, "-m", "ceph_tpu.tools.crushtool",
         "--test", "-i", str(out), "--rule", "0", "--num-rep", "2",
         "--min-x", "0", "--max-x", "15", "--show-utilization"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "CRUSH rule 0 x 15" in r.stdout


def test_roundtrip_preserves_fixed_point_weights():
    """%.5f keeps 1/0x10000 weight granularity (review regression)."""
    cm, tn, dev = compile_text(MAP_TEXT)
    cm.buckets[-2].item_weights[0] = 65569      # 1.0005035...
    cm2, _, _ = compile_text(decompile(cm, tn, dev))
    assert cm2.buckets[-2].item_weights[0] == 65569
