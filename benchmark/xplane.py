"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics and the breakdown read: device busy and idle time, device time
per program, the operations that took most device time, the longest
idle gaps.

A device plane is one whose name starts with ``/device:TPU:``.  On it
the line ``XLA Ops`` holds every operation that ran (nested where an
operation such as a ``while`` contains others) and ``XLA Modules`` one
event per launched program.  Busy time is the union of the operation
intervals; where the harness bracketed its steady slice with a
``benchmark_slice`` host annotation (host and device planes share one
clock), everything is clipped to that slice.  Read with
``jax.profiler.ProfileData`` alone.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SLICE_MARK = "benchmark_slice"
TOP = 10


class TraceUnreadable(RuntimeError):
    pass


def _events(line, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """(start, end, name) in seconds, clipped to [lo, hi]."""
    out = []
    for e in line.events:
        start = e.start_ns * 1e-9
        end = start + e.duration_ns * 1e-9
        start, end = max(start, lo), min(end, hi)
        if end > start:
            out.append((start, end, e.name))
    out.sort(key=lambda t: (t[0], -t[1]))
    return out


def _union(events) -> list[list]:
    """Merged busy intervals [start, end, name of the last event in]."""
    merged: list[list] = []
    for start, end, name in events:
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
                merged[-1][2] = name
        else:
            merged.append([start, end, name])
    return merged


def _self_times(events) -> dict[str, float]:
    """Time per operation name without the time of operations nested in
    it (events sorted by start, longer first)."""
    total: dict[str, float] = {}
    stack: list[tuple[float, str]] = []          # (end, name)
    for start, end, name in events:
        while stack and stack[-1][0] <= start:
            stack.pop()
        total[name] = total.get(name, 0.0) + (end - start)
        if stack:
            parent = stack[-1][1]
            total[parent] -= min(end, stack[-1][0]) - start
        stack.append((end, name))
    return total


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def short_op(name: str) -> str:
    """``%fusion.5 = u32[1024]{0:T(1024)} fusion(...), kind=kCustom`` ->
    ``fusion.5 u32[1024] fusion``: the trace prints whole HLO
    instructions; the breakdown keeps result name, type and opcode."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    if rest.startswith("("):                 # a tuple type: skip it
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                kind, rest = "tuple", rest[i + 1:].lstrip()
                break
    else:
        kind, _, rest = rest.partition(" ")
        kind = kind.split("{")[0]
    opcode = rest.split("(")[0].strip()
    return f"{head.lstrip('%')} {kind} {opcode}"[:80]


def reduce_trace(path: str | Path) -> dict:
    import jax

    try:
        data = jax.profiler.ProfileData.from_file(str(path))
        planes = list(data.planes)
    except Exception as e:      # the reader raises its own C++ error types
        raise TraceUnreadable(f"{path}: {e}") from e

    lo, hi, marked = float("-inf"), float("inf"), False
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SLICE_MARK:
                    lo = e.start_ns * 1e-9
                    hi = lo + e.duration_ns * 1e-9
                    marked = True

    devices = []
    for plane in planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if ops is None:
            continue
        op_events = _events(ops, lo, hi)
        mod_events = (_events(lines[MODULES_LINE], lo, hi)
                      if MODULES_LINE in lines else [])
        devices.append((plane.name, op_events, mod_events))
    if not devices:
        raise TraceUnreadable(
            f"{path}: no plane named {DEVICE_PLANE}* with a line "
            f"{OPS_LINE!r}; planes: {[p.name for p in planes]}")

    if not marked:
        spans = [(ev[0][0], max(e[1] for e in ev))
                 for _, ev, _ in devices if ev]
        if not spans:
            raise TraceUnreadable(f"{path}: no operation ran on a device")
        lo, hi = min(s[0] for s in spans), max(s[1] for s in spans)
    window = hi - lo

    busy, op_time, programs, launches = 0.0, {}, {}, {}
    gaps: list[tuple[float, str]] = []
    for _, op_events, mod_events in devices:
        merged = _union(op_events)
        busy += sum(end - start for start, end, _ in merged)
        for name, secs in _self_times(op_events).items():
            name = short_op(name)
            op_time[name] = op_time.get(name, 0.0) + secs
        for start, end, name in mod_events:
            prog = _program(name)
            programs[prog] = programs.get(prog, 0.0) + (end - start)
            launches[prog] = launches.get(prog, 0) + 1
        # idle gaps, labelled by the program the device had just run
        mods = _union(mod_events) if mod_events else merged
        edge = lo
        last = "window start"
        for start, end, name in mods:
            if start > edge:
                gaps.append((start - edge, f"unattributed, after {last}"))
            edge, last = max(edge, end), _program(name)
        if hi > edge:
            gaps.append((hi - edge, f"unattributed, after {last}"))
    n = len(devices)
    busy /= n
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[0])
    return {
        "marked": marked,
        "device_planes": n,
        "window_s": window,
        "busy_s": busy,
        "idle_s": window - busy,
        "programs": {k: v / n for k, v in programs.items()},
        "launches": launches,
        "device_ops": [[name, secs / n] for name, secs in top_ops],
        "idle_gaps": [[name, secs] for secs, name in gaps[:TOP]],
    }
