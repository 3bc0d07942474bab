#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs the TPU chips the cell asks for and the native build; without
either it exits non-zero and prints no result.  A run that measured
exits 0 whatever failed inside it: failed operations are counted in
``failed``, wrong answers turn ``correct`` false.  The last line of
stdout is the result object: with ``--trace 0`` the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics and a breakdown.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import traceback     # noqa: E402
from pathlib import Path    # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import harness               # noqa: E402
from benchmark.harness import HarnessError, say    # noqa: E402


def run_cell(cell: harness.Cell, seed: int, seconds: float, traced: bool,
             device: dict) -> dict:
    """Drive one cell and reduce it to the result object.  ``device`` is
    what ``require_chips`` found; a test passes a CPU marked
    ``rehearsal`` and gets no metric back."""
    from benchmark import xplane

    meter = harness.CompileMeter()
    out = cell.driver().run(cell, seed, seconds, traced, meter)
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if device.get("rehearsal"):
        # a CPU rehearsal: counts and facts, no timing under a metric's name
        return dict(result, rehearsal=True, facts=out["facts"], device=device)
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes())
    if not traced:
        say("end to end: " + ", ".join(
            f"{k} {v:.4f}" for k, v in out["end_to_end"].items()))
        result["metrics"] = {
            name: {"value": float(out["end_to_end"][name]), "unit": unit}
            for name, unit in cell.end_to_end.items()
            if name in out["end_to_end"]}
    else:
        try:
            trace = xplane.reduce_trace(out["trace_file"])
        except xplane.TraceUnreadable as e:
            raise HarnessError(str(e)) from e
        if trace["busy_s"] <= 0:
            raise HarnessError("no operation ran on the device in the "
                               "traced slice")
        facts = out["facts"]
        facts.update({"trace.busy_s": trace["busy_s"],
                      "trace.window_s": trace["window_s"],
                      "trace.idle_s": trace["idle_s"],
                      "trace.programs": trace["programs"],
                      "device.kind": device["kind"]})
        say(f"trace: slice {trace['window_s']:.3f}s "
            f"({'marked' if trace['marked'] else 'first to last device op'})"
            f", busy {trace['busy_s']:.3f}s on {trace['device_planes']} "
            f"device plane(s); programs " + ", ".join(
                f"{k} x{trace['launches'][k]} {v:.3f}s"
                for k, v in sorted(trace["programs"].items(),
                                   key=lambda kv: -kv[1])[:6]))
        result["metrics"] = harness.read_layer_metrics(cell.per_layer, facts)
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["device"] = device
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.T0 = T0
    try:
        harness.require_program()
        cell = harness.Cell(args.workload)
        device = harness.require_chips(cell.chips)
        harness.build_native()
        say(f"{args.workload} seed {args.seed} on {device}; compile cache "
            f"at {harness.enable_compile_cache()}")
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          device)
    except HarnessError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    except Exception:
        # a fault of the harness or a crash of the program outside any
        # operation: nothing was measured, so nothing is printed
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
