"""Toy-size cells for CPU rehearsals of the benchmark's drivers.

A rehearsal runs the real driver, reference and comparison on the CPU
backend at sizes a test run can hold.  It is marked as a rehearsal, and
its timings are never printed under a metric's name: only ``correct``,
the counts and the facts are looked at.
"""

from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness          # noqa: E402
from benchmark import run as bench_run    # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "rehearsal": True}


def toy_cell(name: str) -> harness.Cell:
    cell = harness.Cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    if cell.config["kind"] == "ec_store":
        cell.config["profile"].update(k=2, m=1)
        cell.config["cluster"]["osds"] = 4
        cell.config["pool"]["pg_num"] = 8
        cell.traffic.update(object_bytes=3 * 8192 + 100, in_flight=4,
                            warmup_ops=4, readback_objects=6,
                            shard_check_objects=3, trace_slice_s=0.5)
    else:
        cell.config["tree"]["fanouts"] = [2, 3, 4]
        cell.traffic.update(pools=3, pg_num=512, check_calls=2, trace_calls=1)
    return cell


def rehearse(name: str, seed: int = 7, seconds: float = 1.0,
             traced: bool = False) -> dict:
    harness.build_native()
    return bench_run.run_cell(toy_cell(name), seed, seconds, traced, CPU)
