"""``span_time`` for a cell in which the cluster repairs itself beside
the clients: the same slice, the same rule (an instant belongs to the
innermost section open at it), with the repair's host layer
``recovery.`` beside the layers ``span_time`` knows.  ``span_time``
keeps to its own list, so there a rebuild's scan, payload and apply
sections would count as time no section covers.

``read`` returns the self time of the sections whose names start with
``spec["prefix"]`` in milliseconds per section named ``spec["per"]``
that started inside the slice (``client.complete``: once per finished
client write, so the ``recovery.`` layer reads as what the repair
costs each client op); ``"invert": true`` gives the time no section
covers.  The layers' times and the uncovered time add up to the slice.
``None`` outside a traced run and for a program without sections.
"""

from __future__ import annotations

from benchmark.readers import read_span_time, span_time

LAYERS = span_time.LAYERS + ("recovery.",)

_cache: dict[str, dict | None] = {}
_reported: set[str] = set()


def load(path) -> dict | None:
    """The marked slice with the repair's layer: ``read_span_time``'s
    reduction (``span_time``'s bounds and idle gaps, the pieces and
    counts taken again) over ``LAYERS`` in place of its own list."""
    key = str(path)
    if key not in _cache:
        saved = read_span_time.LAYERS
        read_span_time.LAYERS = LAYERS
        try:
            _cache[key] = read_span_time._load(key)
        finally:
            read_span_time.LAYERS = saved
    return _cache[key]


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
