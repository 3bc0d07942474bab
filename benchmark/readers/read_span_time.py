"""``span_time`` for a cell that reads: the same slice, the same rule
(an instant belongs to the innermost section open at it), with the read
path's host layer ``osd_read.`` beside the layers ``span_time`` knows.
``span_time`` keeps to its own list, so there a read's verify and
assemble sections would count as time no section covers.

``read`` returns the self time of the sections whose names start with
``spec["prefix"]`` in milliseconds per section named ``spec["per"]``
that started inside the slice (``client.complete``: once per finished
read); ``"invert": true`` gives the time no section covers.  The
layers' times and the uncovered time add up to the slice.  ``None``
outside a traced run and for a program without sections.
"""

from __future__ import annotations

from benchmark.readers import span_time
from benchmark.xplane import DEVICE_PLANE, SLICE_MARK, _events

LAYERS = span_time.LAYERS + ("osd_read.",)

_cache: dict[str, dict | None] = {}
_reported: set[str] = set()


def load(path) -> dict | None:
    """The marked slice with the read path's layers: ``span_time``'s
    bounds and idle gaps, the pieces and counts taken again over
    ``LAYERS``."""
    key = str(path)
    if key not in _cache:
        _cache[key] = _load(key)
    return _cache[key]


def _load(path: str) -> dict | None:
    import jax

    base = span_time.load(path)
    if base is None:
        return None
    lo, hi = base["lo"], base["hi"]
    line = next(
        line for plane in jax.profiler.ProfileData.from_file(path).planes
        if not plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines
        if any(e.name == SLICE_MARK for e in line.events))
    started: dict[str, int] = {}
    for e in line.events:
        if e.name.startswith(LAYERS) and lo <= e.start_ns * 1e-9 < hi:
            started[e.name] = started.get(e.name, 0) + 1
    sections = [ev for ev in _events(line, lo, hi)
                if ev[2].startswith(LAYERS)]
    return dict(base, pieces=span_time.innermost(sections, lo, hi),
                started=started)


def read(spec: dict, facts: dict) -> float | None:
    if "trace.window_s" not in facts:
        return None
    path = span_time.newest_trace()
    sl = load(path) if path is not None else None
    if sl is None:
        return None
    ops = sl["started"].get(spec["per"], 0)
    if not ops:
        return None
    if str(path) not in _reported:
        _reported.add(str(path))
        span_time.report(sl, spec["per"])
    times = span_time.self_times(sl["pieces"])
    if spec.get("invert"):
        secs = times.get(None, 0.0)
    else:
        secs = sum(s for name, s in times.items()
                   if name is not None and name.startswith(spec["prefix"]))
    return 1e3 * secs / ops
