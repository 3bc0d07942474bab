"""BENCHMARK.json against the files its names point to: a later PR adds
a cell, a configuration or a metric by adding files and entries, and
this is what tells it that it added all of them."""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

import pytest

import bm_toy  # noqa: F401  (puts the repo root on sys.path)
from benchmark import harness

ROOT = harness.ROOT
M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in M["workloads"]]
LAYER_METRICS = [p["name"] for p in M["per_layer"]]
E2E = {e["name"]: e for e in M["end_to_end"]}


def reported_in(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert len(json.dumps(M)) < 64 * 1024
    for p in M["paths"]:
        assert FILE.match(p) and (ROOT / p).is_dir()
    assert not any(w.startswith("/") or ".." in w for w in M["command"])
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(
        1, len(CELLS) // 2)


def test_names_units_and_lengths():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in M[group]]
        assert len(seen) == len(set(seen)), group
        names += seen
    for w in M["workloads"]:
        names += [w["config"], w["traffic"]]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in M["configs"]:
        names += c["reduced"]
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for n in names:
        assert NAME.match(n), n
    for e in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in M["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for p in M["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert "\n" not in p["layer"] and len(p["layer"]) <= 200
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]


def test_files_under_paths_are_plainly_named():
    for p in M["paths"]:
        for f in (ROOT / p).rglob("*"):
            rel = str(f.relative_to(ROOT))
            if "__pycache__" in rel:
                continue
            assert FILE.match(rel), rel


@pytest.mark.parametrize("config", [c["name"] for c in M["configs"]])
def test_configuration_file(config):
    entry = {c["name"]: c for c in M["configs"]}[config]
    assert any(entry["file"].startswith(p + "/") for p in M["paths"])
    body = json.loads((ROOT / entry["file"]).read_text())
    assert body["name"] == config
    assert body["source"] and body["guarantees"]
    assert sorted(entry["reduced"]) == sorted(body["reduced"])
    assert any(w["config"] == config for w in M["workloads"])
    files = [c["file"] for c in M["configs"]]
    assert files.count(entry["file"]) == 1


@pytest.mark.parametrize("cell", CELLS)
def test_cell_has_its_files_and_metrics(cell):
    c = harness.Cell(cell)
    assert callable(c.driver().run)
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert c.per_layer
    for key in ("driver", "why"):
        assert c.traffic[key]


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_layer_metric_file_reader_and_moves(metric):
    entry = {p["name"]: p for p in M["per_layer"]}[metric]
    spec = harness.layer_metric(metric)
    for key in ("name", "layer", "unit", "source", "moves", "workloads"):
        assert spec[key] == entry[key], key
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)
    assert reader.read(spec["spec"], {}) is None      # nothing to read
    moved = E2E[entry["moves"]]
    for cell in entry.get("workloads", CELLS):
        assert cell in CELLS
        assert reported_in(moved, cell), (metric, cell)


def test_one_layer_name_per_layer():
    """Metrics of one layer give the same ``layer``, letter for letter:
    two names that differ only in case or spacing are one layer misspelt."""
    layers = {p["layer"] for p in M["per_layer"]}
    squashed = {re.sub(r"\W+", "", name).lower() for name in layers}
    assert len(squashed) == len(layers)


def test_benchmark_keeps_its_own_yardstick():
    """Nothing under benchmark/ imports bench.py, chip_smoke.py or the
    program's load generator; of ceph_tpu.loadgen only the cluster
    bring-up (the system under test) is taken."""
    banned = re.compile(
        r"^\s*(from|import)\s+(bench\b|chip_smoke|"
        r"ceph_tpu\.loadgen(?!\.cluster)|ceph_tpu\.tools)", re.M)
    for f in (ROOT / "benchmark").rglob("*.py"):
        assert not banned.search(f.read_text()), f
    for f in (ROOT / "benchmark" / "reference").glob("*.py"):
        assert "ceph_tpu" not in f.read_text().replace(
            "tpu-rados", ""), f"{f} must not touch the program"
