"""The device's idle time in a registry cell's traced slice, split at
the instant the program dispatched the launch the device ran next.

The registry path launches one ``jit_registry_gf_<engine>`` program a
slab, each under a ``registry.launch`` section on the caller's thread,
and the device runs them in dispatch order.  Host and device planes of
one profiler session share a clock, so inside the marked slice (whole
ops) the j-th ``registry.launch`` on the marked line is the dispatch of
the j-th ``jit_registry_gf*`` event of ``XLA Modules``.  A device idle
interval (the complement of the union of ``XLA Ops``, as
``xplane.reduce_trace`` takes busy time) that ends where module j
starts is cut at the END of launch section j:

  before it   the program had not dispatched: the host is the cause, and
              the time goes to the innermost ``registry.*`` section the
              caller's thread was in (``span_time.covering``);
  after it    the program had dispatched and the device had not started:
              the slab's upload still on the link AND the runtime's
              dispatch latency, which this trace cannot tell apart (the
              least such part over the slabs bounds the latency from
              above; ``benchmark/registry_report.py`` prints it).

The tail behind the last module, and an interval that ends at any other
module's start (the matrix's device copy, a first launch's gate: busy
time, not paired), are "not dispatched" whole.  What lies between two
operations of one registry module is the device's own: it is counted
with the second part (the program had dispatched) and printed apart.
The two parts add up to the slice's idle time (``trace.idle_s``).

``read`` returns ``spec["part"]`` (``undispatched``, or ``operand``:
dispatched and not started) in milliseconds per ``spec["per_fact"]``
(``slice.ops``).  ``None`` outside a traced run, for a trace without
the mark or with another number of device planes than one (the registry
path launches on one device), and where the slice's launch sections and
registry modules differ in number.  Once per trace it hands what it
loaded to ``benchmark/registry_report.py``, which prints the split by
cause and what the same pairing says of the link and of the worker's
thread; no metric reads those lines.
"""

from __future__ import annotations

import bisect
from pathlib import Path

from benchmark import registry_report
from benchmark.readers import span_time
from benchmark.xplane import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                              SLICE_MARK, _events, _program, _union)

LAYER = "registry."
PROGRAM = "jit_registry_gf"
LAUNCH, UPLOAD = "registry.launch", "registry.upload"
LINK, CLOSE = "registry.drain.link", "registry.copy_out"
GATHER, GATHER_WAIT = "registry.gather", "registry.gather.wait"
UNDISPATCHED, OPERAND, INSIDE = "undispatched", "operand", "inside"
EDGE_S = 1e-6               # a module's first op within this of its start

_cache: dict[str, dict | None] = {}
_reported: set[str] = set()


def load(path: str | Path) -> dict | None:
    key = str(path)
    if key not in _cache:
        _cache[key] = _load(key)
    return _cache[key]


def _started(line, name: str, lo: float, hi: float) -> list[tuple]:
    """(start, end) of the line's events of that name that started
    inside the slice, in order."""
    return sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                  for e in line.events
                  if e.name == name and lo <= e.start_ns * 1e-9 < hi)


def _load(path: str) -> dict | None:
    import jax

    try:
        planes = list(jax.profiler.ProfileData.from_file(path).planes)
    except Exception:        # the reader raises its own C++ error types
        return None
    hosts = [line for plane in planes
             if not plane.name.startswith(DEVICE_PLANE)
             for line in plane.lines]
    marked = None
    for line in hosts:
        for e in line.events:
            if e.name == SLICE_MARK:
                marked = (line, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
    if marked is None:
        return None
    line, lo, hi = marked
    pieces = span_time.innermost(
        [ev for ev in _events(line, lo, hi) if ev[2].startswith(LAYER)],
        lo, hi)
    launches = _started(line, LAUNCH, lo, hi)
    uploads = _started(line, UPLOAD, lo, hi)
    links = _started(line, LINK, lo, hi)
    closes = _started(line, CLOSE, lo, hi)
    gather = wait = 0.0
    for other in hosts:
        if other is not line:
            gather += sum(e - s for s, e in _started(other, GATHER, lo, hi))
            wait += sum(e - s for s, e in
                        _started(other, GATHER_WAIT, lo, hi))

    # the registry path launches on one device: one plane, or nothing
    devices = [lines for lines in (
        {ln.name: ln for ln in plane.lines} for plane in planes
        if plane.name.startswith(DEVICE_PLANE))
        if OPS_LINE in lines and MODULES_LINE in lines]
    if len(devices) != 1:
        return None
    busy = _union(_events(devices[0][OPS_LINE], lo, hi))
    mods = sorted(_events(devices[0][MODULES_LINE], lo, hi))
    paired = [i for i, m in enumerate(mods)
              if _program(m[2]).startswith(PROGRAM)]
    if len(paired) != len(launches):
        return None
    slabs = [{"dispatched": launches[j][1], "start": mods[i][0],
              "end": mods[i][1], "off_idle": False}
             for j, i in enumerate(paired)]
    return {"lo": lo, "hi": hi, "slabs": slabs, "uploads": uploads,
            "links": links, "closes": closes, "launches": len(launches),
            "split": _split(busy, mods, paired, slabs, pieces, lo, hi),
            "gather_self": gather - wait, "gather_wait": wait}


def _split(busy, mods, paired, slabs, pieces, lo, hi) -> dict:
    """The device's idle intervals by part; marks the slabs whose
    module started off an idle device."""
    split = {OPERAND: 0.0, INSIDE: 0.0, UNDISPATCHED: {}}
    slab_of = {i: slabs[j] for j, i in enumerate(paired)}
    starts = [m[0] for m in mods]

    def not_dispatched(a: float, b: float) -> None:
        for name, secs in span_time.covering(pieces, a, b):
            split[UNDISPATCHED][name] = \
                split[UNDISPATCHED].get(name, 0.0) + secs

    edge = lo
    for start, end, _ in busy:
        if start > edge:
            # the module whose operation ends this interval
            i = bisect.bisect_right(starts, start + EDGE_S) - 1
            slab = slab_of.get(i) if i >= 0 and mods[i][1] > start else None
            if slab is None:
                not_dispatched(edge, start)
            elif edge > mods[i][0]:
                # between two operations of the module itself
                split[INSIDE] += start - edge
            else:
                slab["off_idle"] = True
                cut = min(max(slab["dispatched"], edge), start)
                if cut > edge:
                    not_dispatched(edge, cut)
                split[OPERAND] += start - cut
        edge = max(edge, end)
    if hi > edge:
        not_dispatched(edge, hi)
    return split


def read(spec: dict, facts: dict) -> float | None:
    if "trace.window_s" not in facts:
        return None
    path = span_time.newest_trace()
    sl = load(path) if path is not None else None
    ops = facts.get(spec["per_fact"], 0)
    if sl is None or not ops:
        return None
    if str(path) not in _reported:
        _reported.add(str(path))
        registry_report.report(sl, ops, facts)
    split = sl["split"]
    secs = split[OPERAND] + split[INSIDE] if spec["part"] == OPERAND \
        else sum(split[UNDISPATCHED].values())
    return 1e3 * secs / ops
