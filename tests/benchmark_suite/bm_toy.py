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


def client_ms_per_op(sl: dict, per: str = "client.complete") -> float:
    """Self time of the ``client.*`` sections per finished op in a slice
    a span reader loaded.  The sections are in every store cell's trace
    and, since PR 49, in no metric (0.013-0.029 ms of ops of 17-53 ms on
    the chip: ledger, PR 47), so a cell's listed host layers add up to
    the slice per finished op less this."""
    from benchmark.readers import span_time
    times = span_time.self_times(sl["pieces"])
    return 1e3 * sum(secs for name, secs in times.items()
                     if name and name.startswith("client.")) \
        / sl["started"][per]


def rehearse(name: str, seed: int = 7, seconds: float = 1.0,
             traced: bool = False) -> dict:
    harness.build_native()
    return bench_run.run_cell(toy_cell(name), seed, seconds, traced, CPU)
