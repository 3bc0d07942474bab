"""How full the event loop's thread was over the window, from the phase
record the program keeps all the time.

``ceph_tpu.common.tracing`` splits every pass of a probed event loop
into ``select`` (asleep or polling) and a run phase (the ready
callbacks, from select's exit to its next entry) and adds both, on the
spans' clock, to one bucket a second: ``select_ns``, ``run_ns``,
``run_cpu_ns`` (the loop's thread on the CPU in its run phases),
``iterations``, ``max_run_ns``, ``recv_ns`` / ``send_ns`` (wall time
inside read-ready and write-ready callbacks) with their call counts,
``gc_ns``.  A phase of 100 ms or more also leaves a record (``kind``
``run`` or ``select``, ``start``, ``wall_ms``, ``cpu_ms`` of the
thread, ``proc_cpu_ms`` of the process, ``gc_ms``, ``recv_ms``,
``send_ms``, reads, writes).  Both rings live as long as the process,
so they outlive the cluster a driver ran, like the span rings
``op_stage`` reads.

``read`` keeps the whole seconds that lie inside the window: the
``run.window_s`` seconds that end where the drivers' clients issued
their last op.  No fact says when the window closed, but the clients
issue nothing after it, so the close is the latest START of a
``client.osd_op`` span whose trace holds an ``ec.encode`` or an
``ec.decode`` (``op_stage``'s filter: the write driver's read-back of a
healthy pool, which decides ``correct`` after the window, encodes and
decodes nothing); it lies within one op's inter-arrival of the
driver's ``t_close``.  The last span's END would be a drain later, and
in a traced run the profiler's stop as well.  A second cut by either
edge of the window is left out (a 51.0 s window keeps 50), so no kept
second holds set-up, drain, the comparison or the shutdown.  Without
such a span there is no value.  ``spec["value"]``:

- ``"busy_share"``: ``run_ns`` over the kept seconds, in percent: is the
  thread full, measured on the thread;
- ``"offcpu_share"``: (``run_ns`` - ``run_cpu_ns``) / ``run_ns`` in percent:
  run-phase time the thread was not on the CPU (waits for the GIL,
  blocking calls, a descheduled thread);
- ``"max_phase_ms"``: the longest single run phase that ended in the
  kept seconds: the lag every op in flight saw.

``None`` without ``run.window_s`` and for a program that keeps no such
record.  Once per process it prints the five worst seconds (fewest
passes of the loop) and every record of the kept seconds.
"""

from __future__ import annotations

from benchmark import harness

ROOT = "client.osd_op"
CODEC = ("ec.encode", "ec.decode")
WORST = 5

_reported = False


def window_close(spans) -> float | None:
    """Seconds (the spans' clock) at which the window's clients issued
    their last op: the latest start of a ``ROOT`` span whose trace
    holds a ``CODEC`` span."""
    coded = {s.trace_id for s in spans if s.name in CODEC}
    starts = [s.start for s in spans
              if s.name == ROOT and s.trace_id in coded]
    return max(starts) * 1e-9 if starts else None


def window(buckets: list[dict], window_s: float,
           close_s: float) -> list[dict]:
    """The buckets of the whole seconds inside the ``window_s`` seconds
    that end at ``close_s``."""
    return [b for b in buckets
            if close_s - window_s <= b["sec"] and b["sec"] + 1 <= close_s]


def shares(kept: list[dict]) -> dict[str, float] | None:
    run = sum(b["run_ns"] for b in kept)
    if not kept or not run:
        return None
    cpu = sum(b["run_cpu_ns"] for b in kept)
    return {"busy_share": 100.0 * run / (1e9 * len(kept)),
            "offcpu_share": 100.0 * (run - cpu) / run,
            "max_phase_ms": max(b["max_run_ns"] for b in kept) / 1e6}


def report(kept: list[dict], records: list[dict]) -> None:
    got = shares(kept)
    harness.say(
        f"loop phases: {len(kept)} whole seconds of the window kept "
        f"({kept[0]['sec']}-{kept[-1]['sec']}), busy {got['busy_share']:.2f}%, "
        f"off the cpu {got['offcpu_share']:.2f}% of it, longest run phase "
        f"{got['max_phase_ms']:.1f} ms, "
        f"{sum(b['iterations'] for b in kept)} passes, gc "
        f"{sum(b['gc_ns'] for b in kept) / 1e6:.1f} ms; worst seconds:")
    for b in sorted(kept, key=lambda b: (b["iterations"],
                                         -b["max_run_ns"]))[:WORST]:
        harness.say(
            f"  second {b['sec']}: {b['iterations']} passes, select "
            f"{b['select_ns'] / 1e6:.1f} ms, run {b['run_ns'] / 1e6:.1f} "
            f"(cpu {b['run_cpu_ns'] / 1e6:.1f}, longest "
            f"{b['max_run_ns'] / 1e6:.1f}), recv {b['recv_ns'] / 1e6:.1f} "
            f"x{b['recv_calls']}, send {b['send_ns'] / 1e6:.1f} "
            f"x{b['send_calls']}, gc {b['gc_ns'] / 1e6:.1f}")
    lo, hi = kept[0]["sec"], kept[-1]["sec"] + 1
    inside = [r for r in records if lo <= r["start"] < hi]
    harness.say(f"  {len(inside)} phases of 100 ms or more in them:")
    for r in inside:
        harness.say(
            f"  {r['kind']:6s} at {r['start']:.3f}: wall "
            f"{r['wall_ms']:.1f} ms, thread cpu {r['cpu_ms']:.1f}, "
            f"process cpu {r['proc_cpu_ms']:.1f}, gc {r['gc_ms']:.1f}, "
            f"recv {r['recv_ms']:.1f} x{r['reads']}, send "
            f"{r['send_ms']:.1f} x{r['writes']}")


def read(spec: dict, facts: dict) -> float | None:
    window_s = facts.get("run.window_s")
    if window_s is None:
        return None
    from ceph_tpu.common import tracing
    buckets = getattr(tracing, "loop_buckets", list)()
    close_s = window_close([s for t in getattr(tracing, "_TRACERS",
                                                {}).values()
                            for s in t.finished])
    if close_s is None:
        return None
    kept = window(buckets, window_s, close_s)
    got = shares(kept)
    if got is None:
        return None
    global _reported
    if not _reported:
        _reported = True
        report(kept, getattr(tracing, "loop_records", list)())
    return got[spec["value"]]
